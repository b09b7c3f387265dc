"""Distributed VSW: the paper's engine scaled over a TPU mesh.

GraphMP is a single-machine system; its SEM contract ("all vertices resident
in fast memory, edges streamed") maps onto a pod as follows (DESIGN.md §5):

- ``SrcVertexArray`` / ``DstVertexArray`` are **sharded by vertex interval**
  over every device of the mesh (axes flattened) — each device owns
  ``|V| / n_dev`` destination vertices and all edge shards whose destination
  interval falls in its slice.  The paper's lock-free property survives
  verbatim: each destination vertex is updated by exactly one device.
- Per superstep, the per-source messages (``pre(src_vals)``) are computed
  shardwise (elementwise, no comm) and **all-gathered** so every device holds
  the full message array — the distributed analogue of "all vertices in
  memory".  For |V| = 1.1B (EU-2015) that is 4.4 GB fp32 per device: fits
  v5e HBM, and is THE collective-roofline term of the graph workload.
- Each device then runs the same windowed-ELL gather/combine as the
  single-device engine over its local edge tiles (Pallas kernel on TPU).
- The iteration-level activity count is a scalar ``psum``.

Device edge layout: every device gets equal-shaped (padded) ELL arrays so
the whole superstep jits as one SPMD program — required for the multi-pod
dry-run (``launch/dryrun.py --arch graphmp``).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional, Sequence, Tuple

import numpy as np

from ..obs import trace
from .apps import COMBINE_IDENTITY, VertexProgram
from .csr import EllShard, csr_to_ell
from .graph import Graph
from .sharding import preprocess

# jax is imported lazily inside the functions that trace/execute SPMD code:
# the host-side pieces (MeshPartition, the device-layout builders) are used
# by the numpy mesh-emulation path, which must stay importable without
# initialising XLA (run_memcapped runs it under RLIMIT_AS).

__all__ = [
    "DeviceGraph",
    "MeshPartition",
    "equal_device_bounds",
    "build_device_graph",
    "build_device_graph_from_store",
    "device_graph_specs",
    "make_superstep",
    "run_distributed",
]


@dataclasses.dataclass
class DeviceGraph:
    """Per-device-stacked ELL arrays + vertex metadata (all padded/equal)."""

    num_vertices: int  # padded to n_dev * rows_per_dev
    num_vertices_real: int
    rows_per_dev: int
    n_dev: int
    window: int
    k: int
    tr: int
    n_ell_per_dev: int
    ell_idx: np.ndarray  # [n_dev * n_ell_per_dev, K] int32 (global src ids)
    ell_valid: np.ndarray  # [n_dev * n_ell_per_dev, K] bool
    seg: np.ndarray  # [n_dev * n_ell_per_dev] int32 local dst row
    out_deg: np.ndarray  # [num_vertices] int32 (padded with 1)


def equal_device_bounds(num_vertices: int, n_dev: int):
    """THE device vertex layout: ``(rows_per_dev, nv_pad, bounds)``.

    Every mesh consumer — the legacy in-memory builder, the store-backed
    builder, and the engine's :class:`MeshPartition` — derives its
    destination-interval ownership from this one function, so the
    "each destination vertex is updated by exactly one device" contract
    cannot drift between the dry-run and the out-of-core paths.

    Bounds are clipped to the real vertex count; trailing devices own the
    (edge-free) padding rows implicitly via ``rows_per_dev``-sized segments.
    """
    if n_dev < 1:
        raise ValueError("n_dev must be >= 1")
    rows_per_dev = -(-num_vertices // n_dev)
    nv_pad = rows_per_dev * n_dev
    bounds = np.minimum(
        np.arange(n_dev + 1, dtype=np.int64) * rows_per_dev, num_vertices
    )
    return rows_per_dev, nv_pad, bounds


@dataclasses.dataclass(frozen=True)
class MeshPartition:
    """Shard -> device ownership for mesh sweeps over an existing store.

    The store's destination intervals are NOT re-cut: every store shard is
    owned by exactly ONE device (the one whose equal vertex slice contains
    the shard's interval start — intervals are far finer than device slices
    at any realistic shard count, and single-ownership is what lifts the
    paper's lock-free property to SPMD: device ``d`` alone writes the
    destination rows of the shards it owns).  The host therefore reads each
    shard once per sweep and routes it to one device slot — the
    "1 host read, D device slices" invariant (DESIGN.md §10).
    """

    n_dev: int
    num_shards: int
    owner: np.ndarray  # [num_shards] int32 owning device per shard

    @classmethod
    def from_meta(cls, meta, n_dev: int) -> "MeshPartition":
        """Own each shard by the equal device slice holding its interval
        start (:func:`equal_device_bounds` on ``meta.num_vertices``)."""
        rows_per_dev, _, _ = equal_device_bounds(meta.num_vertices, n_dev)
        starts = np.asarray(meta.intervals[:-1], dtype=np.int64)
        owner = np.minimum(starts // rows_per_dev, n_dev - 1).astype(np.int32)
        return cls(n_dev=n_dev, num_shards=int(meta.num_shards), owner=owner)

    def device_of(self, shard_id: int) -> int:
        return int(self.owner[shard_id])

    def group(self, shard_ids: Sequence[int]) -> List[List[int]]:
        """Split an ordered shard list into per-device ordered sublists.
        Devices whose shards were all pruned (or that own none) get an
        empty list — they idle through the SPMD dispatch."""
        out: List[List[int]] = [[] for _ in range(self.n_dev)]
        for p in shard_ids:
            out[int(self.owner[p])].append(p)
        return out

    @staticmethod
    def interleave(device_lists: Sequence[Sequence[int]]) -> List[int]:
        """Round-robin merge (d0[0], d1[0], ..., d0[1], ...) so a streaming
        consumer that buffers one shard per device fills every device's
        slot before dispatching an SPMD round."""
        out: List[int] = []
        longest = max((len(g) for g in device_lists), default=0)
        for i in range(longest):
            for g in device_lists:
                if i < len(g):
                    out.append(g[i])
        return out


def build_device_graph(
    graph: Graph,
    n_dev: int,
    *,
    window: int = 1 << 14,
    k: int = 128,
    tr: int = 8,
) -> DeviceGraph:
    """Partition a real graph into equal per-device ELL blocks."""
    rows_per_dev, nv_pad, bounds = equal_device_bounds(graph.num_vertices, n_dev)

    # Build one destination shard per device, then convert to ELL.
    meta, shards = preprocess_with_bounds(graph, bounds)
    return _device_graph_from_shards(
        shards, graph.num_vertices, rows_per_dev, nv_pad, n_dev,
        graph.out_degrees(), window=window, k=k, tr=tr,
    )


def _device_graph_from_shards(
    shards, num_vertices: int, rows_per_dev: int, nv_pad: int, n_dev: int,
    out_degrees: np.ndarray, *, window: int, k: int, tr: int,
) -> DeviceGraph:
    """Shared tail of both builders: per-device CSR shards -> stacked ELL."""
    ells = [csr_to_ell(s, nv_pad, window=window, k=k, tr=tr) for s in shards]
    n_ell_max = max(e.n_ell for e in ells)
    n_ell_pad = -(-n_ell_max // tr) * tr

    idx = np.zeros((n_dev, n_ell_pad, k), dtype=np.int32)
    valid = np.zeros((n_dev, n_ell_pad, k), dtype=bool)
    seg = np.zeros((n_dev, n_ell_pad), dtype=np.int32)
    for d, e in enumerate(ells):
        gi = e.global_idx().astype(np.int32)
        idx[d, : e.n_ell] = np.where(e.ell_mask, gi, 0)
        valid[d, : e.n_ell] = e.ell_mask
        seg[d, : e.n_ell] = e.seg

    out_deg = np.ones(nv_pad, dtype=np.int32)
    out_deg[:num_vertices] = out_degrees.astype(np.int32)

    return DeviceGraph(
        num_vertices=nv_pad,
        num_vertices_real=num_vertices,
        rows_per_dev=rows_per_dev,
        n_dev=n_dev,
        window=window,
        k=k,
        tr=tr,
        n_ell_per_dev=n_ell_pad,
        ell_idx=idx.reshape(n_dev * n_ell_pad, k),
        ell_valid=valid.reshape(n_dev * n_ell_pad, k),
        seg=seg.reshape(n_dev * n_ell_pad),
        out_deg=out_deg,
    )


def build_device_graph_from_store(
    store,
    n_dev: int,
    *,
    window: Optional[int] = None,
    k: Optional[int] = None,
    tr: Optional[int] = None,
) -> DeviceGraph:
    """Per-device ELL blocks straight from a :class:`ShardStore` — no
    ``Graph`` object, no full edge list in memory, ever (PR 3's contract).

    Store shards are decoded ONE at a time and their destination rows are
    re-cut along :func:`equal_device_bounds`; each store shard's row/col
    slices land in at most two adjacent device shards (intervals are
    ordered), and because every store shard keeps destinations grouped with
    sources sorted, the concatenated per-device CSR is bitwise the one
    :func:`build_device_graph` builds from the same edges.

    ELL parameters default to the store's own (``store.ell_params()``) so
    both representations of the graph share one window coordinate system.
    """
    from .sharding import ShardCSR

    with trace.span("mesh.build_device_graph", devices=n_dev):
        return _build_device_graph_from_store(
            store, n_dev, window=window, k=k, tr=tr, ShardCSR=ShardCSR
        )


def _build_device_graph_from_store(
    store,
    n_dev: int,
    *,
    window: Optional[int],
    k: Optional[int],
    tr: Optional[int],
    ShardCSR,
) -> DeviceGraph:
    meta = store.read_meta()
    if window is None or k is None or tr is None:
        ep = store.ell_params()
        window = ep["window"] if window is None else window
        k = ep["k"] if k is None else k
        tr = ep["tr"] if tr is None else tr
    rows_per_dev, nv_pad, bounds = equal_device_bounds(meta.num_vertices, n_dev)

    # Per-device CSR accumulators (row counts first, then columns).
    dev_counts = [
        np.zeros(int(bounds[d + 1] - bounds[d]), dtype=np.int64)
        for d in range(n_dev)
    ]
    dev_cols: List[List[np.ndarray]] = [[] for _ in range(n_dev)]
    for p in range(meta.num_shards):
        csr = store.load_shard(p, "csr")
        counts = np.diff(csr.row)
        # Destination rows of this store shard, split by device boundary.
        d_lo = int(np.searchsorted(bounds, csr.v0, side="right") - 1)
        d_hi = int(np.searchsorted(bounds, max(csr.v1 - 1, csr.v0), side="right") - 1)
        for d in range(d_lo, min(d_hi, n_dev - 1) + 1):
            lo = max(csr.v0, int(bounds[d]))
            hi = min(csr.v1, int(bounds[d + 1]))
            if hi <= lo:
                continue
            r0, r1 = lo - csr.v0, hi - csr.v0
            dev_counts[d][lo - int(bounds[d]): hi - int(bounds[d])] = counts[r0:r1]
            e0, e1 = int(csr.row[r0]), int(csr.row[r1])
            if e1 > e0:
                dev_cols[d].append(csr.col[e0:e1])

    shards = []
    for d in range(n_dev):
        row = np.zeros(len(dev_counts[d]) + 1, dtype=np.int64)
        np.cumsum(dev_counts[d], out=row[1:])
        col = (
            np.concatenate(dev_cols[d]).astype(np.int32)
            if dev_cols[d] else np.zeros(0, dtype=np.int32)
        )
        shards.append(
            ShardCSR(shard_id=d, v0=int(bounds[d]), v1=int(bounds[d + 1]),
                     row=row, col=col)
        )
    return _device_graph_from_shards(
        shards, meta.num_vertices, rows_per_dev, nv_pad, n_dev,
        meta.out_deg, window=window, k=k, tr=tr,
    )


def preprocess_with_bounds(graph: Graph, bounds: np.ndarray):
    """Preprocess with externally fixed interval bounds (equal vertex slices)."""
    from .sharding import GraphMeta, build_shards

    shards = build_shards(graph, bounds)
    meta = GraphMeta(
        num_vertices=graph.num_vertices,
        num_edges=graph.num_edges,
        num_shards=len(shards),
        intervals=bounds,
        in_deg=graph.in_degrees(),
        out_deg=graph.out_degrees(),
    )
    return meta, shards


def device_graph_specs(
    num_vertices: int,
    num_edges: int,
    n_dev: int,
    *,
    k: int = 128,
    tr: int = 8,
    pad_factor: float = 1.30,
    index_dtype=None,
    sentinel: bool = False,
) -> dict:
    """ShapeDtypeStruct stand-ins for a graph of the given size (dry-run).

    ``pad_factor`` models ELL padding waste (measured ~1.1-1.3 on RMAT).
    ``sentinel`` drops the validity plane (see make_superstep).
    """
    import jax
    import jax.numpy as jnp

    if index_dtype is None:
        index_dtype = jnp.int32
    rows_per_dev = -(-num_vertices // n_dev)
    nv_pad = rows_per_dev * n_dev
    edges_per_dev = -(-num_edges // n_dev)
    n_ell = int(-(-edges_per_dev * pad_factor // k))
    n_ell = max(-(-n_ell // tr) * tr, tr)
    S = jax.ShapeDtypeStruct
    out = dict(
        src_vals=S((nv_pad,), jnp.float32),
        ell_idx=S((n_dev * n_ell, k), index_dtype),
        ell_valid=S((n_dev * n_ell, k), jnp.bool_),
        seg=S((n_dev * n_ell,), jnp.int32),
        out_deg=S((nv_pad,), jnp.int32),
    )
    if sentinel:
        out.pop("ell_valid")
    return out


def _pre_apply_fns(program_name: str, num_vertices: int, damping: float = 0.85):
    """jnp versions of the paper's three applications (Alg. 2)."""
    import jax.numpy as jnp

    if program_name == "pagerank":
        pre = lambda v, od: v / jnp.maximum(od, 1).astype(v.dtype)
        apply = lambda acc, old: (1.0 - damping) / num_vertices + damping * acc
        combine = "sum"
    elif program_name in ("sssp", "bfs"):
        pre = lambda v, od: v + 1.0
        apply = lambda acc, old: jnp.minimum(acc, old)
        combine = "min"
    elif program_name == "wcc":
        pre = lambda v, od: v
        apply = lambda acc, old: jnp.minimum(acc, old)
        combine = "min"
    else:  # pragma: no cover
        raise ValueError(program_name)
    return pre, apply, combine


def make_superstep(
    mesh,
    program_name: str,
    num_vertices: int,
    rows_per_dev: int,
    *,
    damping: float = 0.85,
    use_pallas: bool = False,
    msg_dtype=None,
    sentinel: bool = False,
):
    """Build the jit'd SPMD superstep and its shardings.

    Returns ``(step_fn, in_shardings, out_shardings)`` where ``step_fn`` maps
    ``(src_vals, ell_idx, [ell_valid,] seg, out_deg) -> (new_vals, n_active)``.

    Perf variants (EXPERIMENTS.md §Perf, graphmp cell):
      msg_dtype=bf16  — halves the all-gathered SEM working set on the wire
                        (values re-cast to f32 before accumulation).
      sentinel=True   — no validity plane: padding slots carry an
                        out-of-range index and ``jnp.take(mode='fill')``
                        supplies the combine identity; cuts streamed edge
                        bytes by the whole bool plane.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    if msg_dtype is None:
        msg_dtype = jnp.float32
    axes = tuple(mesh.axis_names)
    vspec = P(axes)  # vertex dim sharded over every mesh axis
    pre, apply_fn, combine = _pre_apply_fns(program_name, num_vertices, damping)
    ident = COMBINE_IDENTITY[combine]

    def _acc(msgs, idx, valid, seg):
        if sentinel:
            g = jnp.take(msgs, idx, axis=0, mode="fill",
                         fill_value=float(ident))  # static: combine identity
        else:
            g = jnp.take(msgs, idx, axis=0, mode="clip")
            g = jnp.where(valid, g, jnp.asarray(ident, g.dtype))
        g = g.astype(jnp.float32)
        if combine == "sum":
            part = g.sum(axis=1)
            return jax.ops.segment_sum(part, seg, num_segments=rows_per_dev)
        part = g.min(axis=1)
        return jax.ops.segment_min(part, seg, num_segments=rows_per_dev)

    def local_update(src_local, idx, valid, seg, out_deg_local):
        # pre(): elementwise on the local vertex slice (no comm).
        msgs_local = pre(src_local, out_deg_local).astype(msg_dtype)
        # SEM working set: every device needs the full message array.
        msgs = jax.lax.all_gather(msgs_local, axes, tiled=True)
        acc = _acc(msgs, idx, valid, seg)
        new_local = apply_fn(acc, src_local).astype(src_local.dtype)
        changed = (new_local != src_local).sum()
        n_active = jax.lax.psum(changed, axes)
        return new_local, n_active

    if sentinel:
        fn = lambda s, i, g, o: local_update(s, i, None, g, o)
        n_in = 4
    else:
        fn = local_update
        n_in = 5
    step = jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(vspec,) * n_in,
        out_specs=(vspec, P()),
        check_vma=False,
    )

    in_shardings = tuple(NamedSharding(mesh, s) for s in (vspec,) * n_in)
    out_shardings = (NamedSharding(mesh, vspec), NamedSharding(mesh, P()))
    step_jit = jax.jit(step, in_shardings=in_shardings, out_shardings=out_shardings)
    return step_jit, in_shardings, out_shardings


def run_distributed(
    graph: Graph,
    program: VertexProgram,
    mesh,
    *,
    max_iters: int = 100,
    window: int = 1 << 12,
    k: int = 32,
    tr: int = 8,
    damping: float = 0.85,
) -> Tuple[np.ndarray, int]:
    """Execute the distributed engine for real (CPU multi-device tests)."""
    import jax
    import jax.numpy as jnp

    n_dev = int(np.prod(mesh.devices.shape))
    dg = build_device_graph(graph, n_dev, window=window, k=k, tr=tr)
    step, in_sh, _ = make_superstep(
        mesh, program.name, dg.num_vertices_real, dg.rows_per_dev, damping=damping
    )

    vals0, _ = program.init_padded(dg) if hasattr(program, "init_padded") else (None, None)
    if vals0 is None:
        from .sharding import GraphMeta

        meta = GraphMeta(
            num_vertices=graph.num_vertices,
            num_edges=graph.num_edges,
            num_shards=n_dev,
            intervals=np.arange(n_dev + 1) * dg.rows_per_dev,
            in_deg=np.zeros(graph.num_vertices, np.int64),
            out_deg=graph.out_degrees(),
        )
        vals0, _ = program.init(meta)
    pad = dg.num_vertices - graph.num_vertices
    # Padding vertices: no in/out edges; init them inert with the identity of
    # is_active (their value never changes).
    vals = np.concatenate([vals0.astype(np.float32),
                           np.zeros(pad, np.float32)])

    args = [
        jax.device_put(jnp.asarray(x), s)
        for x, s in zip(
            (vals, dg.ell_idx, dg.ell_valid, dg.seg, dg.out_deg), in_sh
        )
    ]
    iters = 0
    for it in range(max_iters):
        new_vals, n_active = step(*args)
        iters = it + 1
        args[0] = new_vals
        if int(n_active) == 0:
            break
    out = np.asarray(args[0])[: graph.num_vertices]
    return out, iters
