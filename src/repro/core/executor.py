"""Shard executors: *how planned shards execute* (DESIGN.md §3-4).

Third layer of the engine stack.  An executor consumes the pipeline's
stream of loaded shards and yields per-shard accumulators; it owns the
backend dispatch the engine used to do inline.

Two strategies:

- :class:`PerShardExecutor` — one backend call per shard (the paper's
  worker model; also the only choice for the numpy oracle, whose
  scatter-reduce has no dispatch overhead to amortize).
- :class:`BatchedEllExecutor` — groups up to ``batch_shards`` consecutive
  planned ELL shards into ONE concatenated kernel dispatch (shared
  ``tile_window`` prefetch map, one ``pallas_call`` / one jit call for N
  shards).  Bitwise-equal to per-shard execution by construction: the
  batch is a pure concatenation, so every tile computes identical partials
  and the globalized segment combine preserves per-segment contribution
  order.

Shard-update backends (moved here from ``vsw.py``); signature
``(csr, ell, msgs, combine) -> acc [rows] float32``:

=========  ==================================================================
numpy      ``np.add.at`` / ``np.minimum.at`` scatter-reduce over CSR — the
           bitwise oracle.
jnp        windowed ELL gather + masked reduce + segment combine under
           ``jax.jit`` (shape-bucketed to bound recompiles) — what XLA
           would run.
pallas     the ``repro.kernels.spmv_ell`` Pallas kernel — compiled on a
           TPU, the Pallas interpreter elsewhere
           (:func:`repro.kernels.pallas_compiled`).
=========  ==================================================================
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import (
    Callable, Dict, Iterable, Iterator, List, Optional, Sequence, Tuple,
)

import numpy as np

from ..obs import trace
from .apps import COMBINE_IDENTITY
from .csr import (
    EllShard, bucket_rows, concat_ells, next_pow2, pad_ell_arrays,
    ragged_lane_concat,
)
from .pipeline import LoadedShard
from .sharding import ShardCSR

__all__ = [
    "BACKENDS",
    "LANE_BACKENDS",
    "ExecResult",
    "ExecStats",
    "PerShardExecutor",
    "BatchedEllExecutor",
    "make_executor",
    "make_lane_executor",
    "update_shard_numpy",
    "update_shard_jnp",
    "update_shards_jnp_batched",
    "update_shard_numpy_lanes",
    "update_shard_jnp_lanes",
    "update_shards_jnp_lanes_batched",
    "update_shards_jnp_lanes_multi",
    "GroupDispatch",
]


# --------------------------------------------------------------------------
# Shard-update backends
# --------------------------------------------------------------------------


def update_shard_numpy(
    csr: ShardCSR, ell: Optional[EllShard], msgs: np.ndarray, combine: str
) -> np.ndarray:
    """Scatter-reduce oracle over the CSR shard."""
    rows = csr.rows
    acc = np.full(rows, COMBINE_IDENTITY[combine], dtype=msgs.dtype)
    if csr.nnz == 0:
        return acc
    local_dst = np.repeat(np.arange(rows, dtype=np.int64), np.diff(csr.row))
    vals = msgs[csr.col]
    if combine == "sum":
        np.add.at(acc, local_dst, vals)
    elif combine == "min":
        np.minimum.at(acc, local_dst, vals)
    elif combine == "max":
        np.maximum.at(acc, local_dst, vals)
    else:  # pragma: no cover
        raise ValueError(combine)
    return acc


def _ell_fn_impl(tr: int, rows: int, window: int, combine: str):
    """The pure (un-jitted) windowed-ELL update for one padded shape bucket.

    Shared by the single-query path (jitted directly) and the serving
    layer's lane path (jitted under ``vmap`` over the message axis) so both
    trace the exact same per-lane computation.
    """
    import jax
    import jax.numpy as jnp

    ident = COMBINE_IDENTITY[combine]

    def fn(ell_idx, ell_mask, seg, tile_window, msgs):
        win = jnp.repeat(tile_window, tr)  # [n_ell]
        gidx = ell_idx.astype(jnp.int32) + win[:, None] * window
        g = jnp.take(msgs, gidx, axis=0, mode="clip")
        g = jnp.where(ell_mask, g, jnp.asarray(ident, g.dtype))
        if combine == "sum":
            part = g.sum(axis=1)
            acc = jax.ops.segment_sum(part, seg, num_segments=rows)
        elif combine == "min":
            part = g.min(axis=1)
            acc = jax.ops.segment_min(part, seg, num_segments=rows)
            acc = jnp.where(jnp.isfinite(acc), acc, jnp.asarray(ident, g.dtype))
        else:
            part = g.max(axis=1)
            acc = jax.ops.segment_max(part, seg, num_segments=rows)
            acc = jnp.where(jnp.isfinite(acc), acc, jnp.asarray(ident, g.dtype))
        return acc

    return fn


@functools.lru_cache(maxsize=64)
def _jnp_ell_fn(n_ell: int, k: int, tr: int, rows: int, window: int, combine: str):
    """Build a jit'd ELL update for one padded shape bucket."""
    import jax

    return jax.jit(_ell_fn_impl(tr, rows, window, combine))


@functools.lru_cache(maxsize=64)
def _jnp_ell_lanes_fn(
    n_ell: int, k: int, tr: int, rows: int, window: int, combine: str
):
    """Lane-batched variant: one jit dispatch updates ``[lanes, ...]``
    message rows against shared edge structure (lane count is a traced
    shape; the serving batcher pads it to pow2 to bound retraces)."""
    import jax

    return jax.jit(
        jax.vmap(_ell_fn_impl(tr, rows, window, combine),
                 in_axes=(None, None, None, None, 0))
    )


def _padded_shard_inputs(ell: EllShard, msgs: np.ndarray):
    """Shape-bucket one shard's ELL arrays and pad messages to full windows
    (so the gather never reads OOB).  ``msgs`` may be 1-D (single query) or
    2-D ``[lanes, |V|]`` — only the trailing (vertex) axis is padded.
    Shared by the single-query and lane paths so the padding discipline
    can't drift between them."""
    n_ell_pad = bucket_rows(ell.n_ell, ell.tr)
    idx, mask, seg, tw = pad_ell_arrays(
        ell.ell_idx, ell.ell_mask, ell.seg, ell.tile_window,
        ell.n_ell, ell.tr, n_ell_pad,
    )
    n_pad_v = ell.num_windows * ell.window
    pad = [(0, 0)] * (msgs.ndim - 1) + [(0, n_pad_v - msgs.shape[-1])]
    return n_ell_pad, idx, mask, seg, tw, np.pad(msgs, pad)


def _staged_batch(ells: List[EllShard]):
    """Concatenate + shape-bucket a shard batch (the shard-side staging
    every batched lane path shares — single-group and multi-group dispatch
    MUST pad identically or fusion stops being bitwise-invisible)."""
    batch = concat_ells(ells)
    n_ell_pad = bucket_rows(batch.n_ell, batch.tr)
    idx, mask, seg, tw = pad_ell_arrays(
        batch.ell_idx, batch.ell_mask, batch.seg, batch.tile_window,
        batch.n_ell, batch.tr, n_ell_pad,
    )
    return batch, n_ell_pad, idx, mask, seg, tw


def _padded_batch_inputs(ells: List[EllShard], msgs: np.ndarray):
    """Batch-level counterpart of :func:`_padded_shard_inputs`."""
    batch, n_ell_pad, idx, mask, seg, tw = _staged_batch(ells)
    n_pad_v = batch.num_windows * batch.window
    pad = [(0, 0)] * (msgs.ndim - 1) + [(0, n_pad_v - msgs.shape[-1])]
    return batch, n_ell_pad, idx, mask, seg, tw, np.pad(msgs, pad)


def update_shard_jnp(
    csr: ShardCSR, ell: EllShard, msgs: np.ndarray, combine: str
) -> np.ndarray:
    """Windowed-ELL gather/combine under jit (shape-bucketed)."""
    import jax.numpy as jnp

    n_ell_pad, idx, mask, seg, tw, msgs_p = _padded_shard_inputs(ell, msgs)
    fn = _jnp_ell_fn(n_ell_pad, ell.k, ell.tr, ell.rows, ell.window, combine)
    acc = fn(jnp.asarray(idx), jnp.asarray(mask), jnp.asarray(seg),
             jnp.asarray(tw), jnp.asarray(msgs_p))
    return np.asarray(acc)


def update_shards_jnp_batched(
    ells: List[EllShard], msgs: np.ndarray, combine: str
) -> List[np.ndarray]:
    """One jit dispatch for N concatenated shards (jnp backend).

    Both the ELL row count AND the segment count are shape-bucketed
    (pow2): batch composition changes every iteration under selective
    scheduling, and without bucketing each distinct (n_ell, rows_total)
    pair would force a fresh XLA compile.  Padding rows land in the
    batch's first destination row carrying the combine identity, and
    surplus segments are simply never referenced by ``split`` — both
    no-ops, so bucketing never changes results.
    """
    import jax.numpy as jnp

    if not ells:
        return []
    batch, n_ell_pad, idx, mask, seg, tw, msgs_p = _padded_batch_inputs(
        ells, msgs
    )
    rows_pad = next_pow2(batch.rows_total)
    fn = _jnp_ell_fn(n_ell_pad, batch.k, batch.tr, rows_pad, batch.window,
                     combine)
    acc = fn(jnp.asarray(idx), jnp.asarray(mask), jnp.asarray(seg),
             jnp.asarray(tw), jnp.asarray(msgs_p))
    return batch.split(np.asarray(acc))


def _update_shard_pallas(
    csr: ShardCSR, ell: EllShard, msgs: np.ndarray, combine: str
) -> np.ndarray:
    from repro.kernels.spmv_ell import ops as spmv_ops

    return np.asarray(spmv_ops.ell_update(ell, msgs, combine))


def _update_shards_pallas_batched(
    ells: List[EllShard], msgs: np.ndarray, combine: str
) -> List[np.ndarray]:
    from repro.kernels.spmv_ell import ops as spmv_ops

    return [np.asarray(a) for a in spmv_ops.ell_update_batched(ells, msgs, combine)]


# --------------------------------------------------------------------------
# Lane-batched backends (serving layer): msgs is [lanes, |V|], acc is
# [lanes, rows].  One shard load feeds every in-flight query lane.
# --------------------------------------------------------------------------


def update_shard_numpy_lanes(
    csr: ShardCSR, ell: Optional[EllShard], msgs: np.ndarray, combine: str
) -> np.ndarray:
    """Lane-stacked scatter-reduce oracle: runs :func:`update_shard_numpy`
    per lane, so each lane's row is bitwise THE single-query oracle."""
    return np.stack(
        [update_shard_numpy(csr, ell, msgs[l], combine)
         for l in range(msgs.shape[0])]
    )


def update_shard_jnp_lanes(
    csr: ShardCSR, ell: EllShard, msgs: np.ndarray, combine: str
) -> np.ndarray:
    """Windowed-ELL gather/combine for all lanes under ONE jit dispatch."""
    import jax.numpy as jnp

    n_ell_pad, idx, mask, seg, tw, msgs_p = _padded_shard_inputs(ell, msgs)
    fn = _jnp_ell_lanes_fn(n_ell_pad, ell.k, ell.tr, ell.rows, ell.window,
                           combine)
    acc = fn(jnp.asarray(idx), jnp.asarray(mask), jnp.asarray(seg),
             jnp.asarray(tw), jnp.asarray(msgs_p))
    return np.asarray(acc)


def update_shards_jnp_lanes_batched(
    ells: List[EllShard], msgs: np.ndarray, combine: str
) -> List[np.ndarray]:
    """One jit dispatch for N concatenated shards x K lanes (jnp backend) —
    same shape-bucketing discipline as :func:`update_shards_jnp_batched`."""
    import jax.numpy as jnp

    if not ells:
        return []
    batch, n_ell_pad, idx, mask, seg, tw, msgs_p = _padded_batch_inputs(
        ells, msgs
    )
    rows_pad = next_pow2(batch.rows_total)
    fn = _jnp_ell_lanes_fn(n_ell_pad, batch.k, batch.tr, rows_pad,
                           batch.window, combine)
    acc = fn(jnp.asarray(idx), jnp.asarray(mask), jnp.asarray(seg),
             jnp.asarray(tw), jnp.asarray(msgs_p))
    return batch.split(np.asarray(acc))


def update_shards_jnp_lanes_multi(
    ells: List[EllShard],
    msgs_by_group: Sequence[np.ndarray],
    combines: Sequence[str],
) -> List[List[np.ndarray]]:
    """Multi-GROUP lane dispatch (fused sweeps, DESIGN.md §9): N shards are
    concatenated / shape-bucketed / staged ONCE, then dispatched once per
    program group against that group's own ``[K_g, |V|]`` lane matrix and
    combine monoid — G dispatches share one decode+concat.  Each group's
    dispatch is the exact computation
    :func:`update_shards_jnp_lanes_batched` would run for it alone (same
    padded arrays, same jit'd function), so fusion stays bitwise-invisible
    per lane.  Returns one per-shard accumulator list per group.
    """
    import jax.numpy as jnp

    if not ells:
        return [[] for _ in msgs_by_group]
    batch, n_ell_pad, idx, mask, seg, tw = _staged_batch(ells)
    idx_j, mask_j, seg_j, tw_j = (
        jnp.asarray(idx), jnp.asarray(mask), jnp.asarray(seg), jnp.asarray(tw)
    )
    rows_pad = next_pow2(batch.rows_total)
    n_pad_v = batch.num_windows * batch.window
    out: List[List[np.ndarray]] = []
    for msgs, combine in zip(msgs_by_group, combines):
        msgs_p = np.zeros((msgs.shape[0], n_pad_v), msgs.dtype)
        msgs_p[:, : msgs.shape[1]] = msgs
        fn = _jnp_ell_lanes_fn(n_ell_pad, batch.k, batch.tr, rows_pad,
                               batch.window, combine)
        acc = fn(idx_j, mask_j, seg_j, tw_j, jnp.asarray(msgs_p))
        out.append(batch.split(np.asarray(acc)))
    return out


@functools.lru_cache(maxsize=64)
def _jnp_ell_lanes_ragged_fn(
    n_ell: int, k: int, tr: int, rows: int, window: int, combines: tuple
):
    """RaggedFuse jnp variant: ONE jit dispatch updates the concatenated
    lane state of ALL fusion groups, selecting each lane's combine arm via
    its ``combine_ids`` entry.  The per-arm bodies are the exact
    :func:`_ell_fn_impl` closures the per-group multi path vmaps, and
    ``jnp.where`` keeps the selected arm's value bit-for-bit, so each
    lane's row is bitwise :func:`update_shards_jnp_lanes_multi`'s."""
    import jax
    import jax.numpy as jnp

    bodies = [_ell_fn_impl(tr, rows, window, c) for c in combines]

    def fn(ell_idx, ell_mask, seg, tile_window, combine_ids, msgs2d):
        acc = jnp.zeros((msgs2d.shape[0], rows), msgs2d.dtype)
        for ci, body in enumerate(bodies):
            acc_c = jax.vmap(body, in_axes=(None, None, None, None, 0))(
                ell_idx, ell_mask, seg, tile_window, msgs2d
            )
            acc = jnp.where((combine_ids == ci)[:, None], acc_c, acc)
        return acc

    return jax.jit(fn)


def _ragged_launch_jnp(batch, n_ell_pad: int, staged, lane_ctx):
    """Launch ONE jnp ragged update on a staged batch; the accumulator is
    left unforced so the caller can overlap the next batch's decode
    (double buffering)."""
    fn = _jnp_ell_lanes_ragged_fn(
        n_ell_pad, batch.k, batch.tr, next_pow2(batch.rows_total),
        batch.window, lane_ctx["combines"],
    )
    return fn(*staged, lane_ctx["cids"], lane_ctx["msgs"])


def _ragged_launch_pallas(batch, n_ell_pad: int, staged, lane_ctx):
    from repro.kernels.spmv_ell import ops as spmv_ops

    return spmv_ops.ragged_launch(batch, staged, lane_ctx)


def _ragged_put(idx, mask, seg, tw, lanes_host, lane_ctx):
    """Copy a staged batch to the device, and the lane state when
    ``lanes_host`` (a :func:`ragged_lane_concat` result) is given; returns
    the device arrays and the lane context (``lane_ctx`` when unchanged)."""
    import jax.numpy as jnp

    staged = tuple(jnp.asarray(x) for x in (idx, mask, seg, tw))
    if lanes_host is None:
        return staged, lane_ctx
    msgs_all, cids, combines_set, slices = lanes_host
    return staged, {"msgs": jnp.asarray(msgs_all), "cids": jnp.asarray(cids),
                    "combines": combines_set, "slices": slices}


def _ragged_collect(batch, acc, group_slices) -> List[List[np.ndarray]]:
    """Force a ragged accumulator and slice per group per shard.

    ``exec.wait`` covers only the block on the device; the rest of
    ``exec.collect`` is the copy back and the split."""
    import jax

    with trace.span("exec.collect"):
        with trace.span("exec.wait"):
            jax.block_until_ready(acc)
        acc = np.asarray(acc)
        return [batch.split(acc[sl]) for sl in group_slices]


def _update_shard_pallas_lanes(
    csr: ShardCSR, ell: EllShard, msgs: np.ndarray, combine: str
) -> np.ndarray:
    from repro.kernels.spmv_ell import ops as spmv_ops

    return np.asarray(spmv_ops.ell_update_lanes(ell, msgs, combine))


def _update_shards_pallas_lanes_batched(
    ells: List[EllShard], msgs: np.ndarray, combine: str
) -> List[np.ndarray]:
    from repro.kernels.spmv_ell import ops as spmv_ops

    return [np.asarray(a)
            for a in spmv_ops.ell_update_lanes_batched(ells, msgs, combine)]


def _update_shards_pallas_lanes_multi(
    ells: List[EllShard],
    msgs_by_group: Sequence[np.ndarray],
    combines: Sequence[str],
) -> List[List[np.ndarray]]:
    from repro.kernels.spmv_ell import ops as spmv_ops

    return [
        [np.asarray(a) for a in accs]
        for accs in spmv_ops.ell_update_lanes_multi(ells, msgs_by_group,
                                                    combines)
    ]


BACKENDS: Dict[str, Callable] = {
    "numpy": update_shard_numpy,
    "jnp": update_shard_jnp,
    "pallas": _update_shard_pallas,
}

_BATCHED_BACKENDS: Dict[str, Callable] = {
    "jnp": update_shards_jnp_batched,
    "pallas": _update_shards_pallas_batched,
}

LANE_BACKENDS: Dict[str, Callable] = {
    "numpy": update_shard_numpy_lanes,
    "jnp": update_shard_jnp_lanes,
    "pallas": _update_shard_pallas_lanes,
}

_BATCHED_LANE_BACKENDS: Dict[str, Callable] = {
    "jnp": update_shards_jnp_lanes_batched,
    "pallas": _update_shards_pallas_lanes_batched,
}

_MULTI_LANE_BACKENDS: Dict[str, Callable] = {
    "jnp": update_shards_jnp_lanes_multi,
    "pallas": _update_shards_pallas_lanes_multi,
}

_RAGGED_LANE_BACKENDS: Dict[str, Callable] = {
    "jnp": _ragged_launch_jnp,
    "pallas": _ragged_launch_pallas,
}

#: One program group's dispatch request for ``run_groups``: the group's
#: ``[K_g, |V|]`` message matrix and its combine monoid, or None when the
#: group has nothing to dispatch for these shards (every lane masked off /
#: already retired) — the shard stream is still consumed once.
#: ``run_groups``' keywords describe the call for tracing only: ``masked``
#: marks a dispatch of a lane-masked flush, and ``lanes_live`` counts the
#: rows that carry a query (default: every row of every live group).
GroupDispatch = Optional[Tuple[np.ndarray, str]]


def _dispatch_counters(ells, slots: int, masked: bool, lanes_live: int,
                       lanes_pad: int, copied) -> Dict:
    """The attributes of one ragged ``exec.dispatch`` span: the work it
    launched (real edges from shard metadata, ELL slots after row
    bucketing, lanes carrying a query against lanes launched) and the
    bytes it copied to the device.  Computed only while a tracer is
    installed."""
    return {
        "masked": masked,
        "edges": sum(int(e.nnz) for e in ells),
        "slots": int(slots),
        "lanes_live": int(lanes_live),
        "lanes_pad": int(lanes_pad),
        "h2d_bytes": sum(int(x.nbytes) for x in copied),
    }


def _ragged_kernel_counters(backend: str, batch, n_ell_pad: int,
                            msgs) -> Dict:
    """The Pallas ragged kernel's work in one launch: its grid steps (lane
    blocks x tiles), the gather rounds each lane runs (``rounds``) and
    those of every table row of every tile (``rounds_full``, tiles x W /
    L).  Padding tiles run no rounds, so the batch's own slots give
    ``rounds``.  Empty for a backend that runs no Pallas grid."""
    if backend != "pallas":
        return {}
    from repro.kernels.spmv_ell import kernel as Kn

    n_tiles, w = n_ell_pad // batch.tr, batch.window
    return {
        "grid_steps": Kn.ragged_grid_steps(int(msgs.shape[0]), n_tiles, w,
                                           msgs.dtype.itemsize),
        "rounds": Kn.ragged_rounds(Kn.ragged_rows_read(
            batch.ell_idx, batch.ell_mask, window=w, tr=batch.tr)),
        "rounds_full": n_tiles * (w // Kn.table_lanes(w)),
    }


# --------------------------------------------------------------------------
# Executors
# --------------------------------------------------------------------------


@dataclasses.dataclass
class ExecResult:
    """One shard's accumulator plus which dispatch produced it."""

    shard_id: int
    v0: int
    v1: int
    acc: np.ndarray
    batch_size: int = 1  # shards sharing the kernel dispatch


@dataclasses.dataclass
class ExecStats:
    """Per-iteration dispatch accounting (reset each iteration)."""

    dispatches: int = 0
    shards_executed: int = 0
    exec_s: float = 0.0
    #: shard batches flushed this iteration (a ragged flush is ONE dispatch
    #: per batch; the multi path pays G — conservation:
    #: ragged_dispatches <= batches <= dispatches, DESIGN.md §14).
    batches: int = 0
    ragged_dispatches: int = 0
    #: live (un-padded) lanes covered by ragged launches, summed per flush;
    #: conservation: sum(group_lanes.values()) == ragged_lanes.
    ragged_lanes: int = 0
    group_lanes: Dict[int, int] = dataclasses.field(default_factory=dict)
    #: wall time a dispatched batch stayed in flight while the host staged
    #: the next one (the double-buffer overlap window).
    overlap_s: float = 0.0
    #: mesh executors only: device id -> shard applications / SPMD launches
    #: routed to that device (empty on single-device executors).
    #: Conservation: sum(device_shards.values()) == shards_executed.
    device_shards: Dict[int, int] = dataclasses.field(default_factory=dict)
    device_dispatches: Dict[int, int] = dataclasses.field(default_factory=dict)

    def reset(self) -> None:
        self.dispatches = self.shards_executed = 0
        self.exec_s = 0.0
        self.batches = self.ragged_dispatches = self.ragged_lanes = 0
        self.group_lanes = {}
        self.overlap_s = 0.0
        self.device_shards = {}
        self.device_dispatches = {}


class PerShardExecutor:
    """One backend call per loaded shard (paper worker model).

    With ``lanes=True`` the call consumes ``[lanes, |V|]`` messages and
    yields ``[lanes, rows]`` accumulators — the serving layer's per-shard
    amortization (one load, K lanes).
    """

    def __init__(self, backend: str, *, lanes: bool = False):
        table = LANE_BACKENDS if lanes else BACKENDS
        if backend not in table:
            raise ValueError(f"unknown backend {backend}; have {sorted(table)}")
        self.backend_name = backend
        self.lanes = lanes
        self._fn = table[backend]

    def run(
        self,
        loaded: Iterable[LoadedShard],
        msgs: np.ndarray,
        combine: str,
        stats: Optional[ExecStats] = None,
    ) -> Iterator[ExecResult]:
        for ls in loaded:
            t0 = time.perf_counter()
            with trace.span(
                "exec.dispatch", shard=ls.shard_id, backend=self.backend_name
            ):
                acc = self._fn(ls.csr, ls.ell, msgs, combine)
            if stats is not None:
                stats.dispatches += 1
                stats.shards_executed += 1
                stats.exec_s += time.perf_counter() - t0
            ref = ls.ref
            yield ExecResult(ls.shard_id, ref.v0, ref.v1, np.asarray(acc))

    def run_groups(
        self,
        loaded: Iterable[LoadedShard],
        groups: Sequence[GroupDispatch],
        stats: Optional[ExecStats] = None,
        *,
        masked: bool = False,
        lanes_live: Optional[int] = None,
    ) -> Iterator[Tuple[int, ExecResult]]:
        """Multi-group dispatch (fused sweeps): consume each loaded shard
        ONCE and dispatch it per live program group — one load+decode, G
        backend calls.  Yields ``(group_index, result)``; ``None`` entries
        in ``groups`` are skipped without a dispatch.  ``masked`` and
        ``lanes_live`` are recorded by the ragged paths only
        (:data:`GroupDispatch`).
        """
        for ls in loaded:
            ref = ls.ref
            for gi, ga in enumerate(groups):
                if ga is None:
                    continue
                msgs, combine = ga
                t0 = time.perf_counter()
                with trace.span(
                    "exec.dispatch",
                    shard=ls.shard_id,
                    group=gi,
                    backend=self.backend_name,
                ):
                    acc = self._fn(ls.csr, ls.ell, msgs, combine)
                if stats is not None:
                    stats.dispatches += 1
                    stats.shards_executed += 1
                    stats.exec_s += time.perf_counter() - t0
                yield gi, ExecResult(ls.shard_id, ref.v0, ref.v1,
                                     np.asarray(acc))


class BatchedEllExecutor:
    """Batch consecutive planned ELL shards into one kernel dispatch.

    With ``lanes=True`` each dispatch covers N shards x K query lanes —
    the serving hot loop's maximal amortization point.
    """

    def __init__(self, backend: str, batch_shards: int = 4, *,
                 lanes: bool = False, ragged: bool = True):
        table = _BATCHED_LANE_BACKENDS if lanes else _BATCHED_BACKENDS
        if backend not in table:
            raise ValueError(
                f"batched execution needs an ELL backend, got {backend!r}"
            )
        if batch_shards < 1:
            raise ValueError("batch_shards must be >= 1")
        self.backend_name = backend
        self.batch_shards = batch_shards
        self.lanes = lanes
        #: RaggedFuse (DESIGN.md §14): run_groups concatenates every live
        #: group along the lane axis and launches ONE ragged kernel per
        #: shard batch instead of G, double-buffering collection against
        #: the next batch's host decode.
        self.ragged = bool(ragged) and lanes and backend in _RAGGED_LANE_BACKENDS
        self._fn = table[backend]
        self._multi_fn = _MULTI_LANE_BACKENDS[backend] if lanes else None
        self._ragged_fn = _RAGGED_LANE_BACKENDS.get(backend) if lanes else None

    def run(
        self,
        loaded: Iterable[LoadedShard],
        msgs: np.ndarray,
        combine: str,
        stats: Optional[ExecStats] = None,
    ) -> Iterator[ExecResult]:
        buf: List[LoadedShard] = []
        for ls in loaded:
            buf.append(ls)
            if len(buf) >= self.batch_shards:
                yield from self._flush(buf, msgs, combine, stats)
                buf = []
        if buf:
            yield from self._flush(buf, msgs, combine, stats)

    def _flush(self, buf, msgs, combine, stats) -> Iterator[ExecResult]:
        t0 = time.perf_counter()
        with trace.span(
            "exec.dispatch", shards=len(buf), backend=self.backend_name
        ):
            accs = self._fn([ls.ell for ls in buf], msgs, combine)
        if stats is not None:
            stats.dispatches += 1
            stats.shards_executed += len(buf)
            stats.exec_s += time.perf_counter() - t0
        for ls, acc in zip(buf, accs):
            yield ExecResult(
                ls.shard_id, ls.ell.v0, ls.ell.v1, np.asarray(acc),
                batch_size=len(buf),
            )

    def run_groups(
        self,
        loaded: Iterable[LoadedShard],
        groups: Sequence[GroupDispatch],
        stats: Optional[ExecStats] = None,
        *,
        masked: bool = False,
        lanes_live: Optional[int] = None,
    ) -> Iterator[Tuple[int, ExecResult]]:
        """Multi-group batched dispatch: up to ``batch_shards`` consecutive
        shards are concatenated ONCE (shared decode + concat + pad staging)
        and dispatched once per live program group — the fused serving hot
        loop's cost shape: 1 load, 1 concat, G kernel launches per batch.
        """
        if not self.lanes:
            raise RuntimeError("run_groups needs a lane executor")
        if self.ragged:
            yield from self._run_groups_ragged(loaded, groups, stats,
                                               masked, lanes_live)
            return
        buf: List[LoadedShard] = []
        for ls in loaded:
            buf.append(ls)
            if len(buf) >= self.batch_shards:
                yield from self._flush_groups(buf, groups, stats)
                buf = []
        if buf:
            yield from self._flush_groups(buf, groups, stats)

    def _flush_groups(self, buf, groups, stats):
        live = [(gi, ga) for gi, ga in enumerate(groups) if ga is not None]
        if not live:
            return
        t0 = time.perf_counter()
        with trace.span(
            "exec.dispatch",
            shards=len(buf),
            groups=len(live),
            backend=self.backend_name,
        ):
            accs_by_group = self._multi_fn(
                [ls.ell for ls in buf],
                [ga[0] for _, ga in live],
                [ga[1] for _, ga in live],
            )
        if stats is not None:
            stats.dispatches += len(live)
            stats.batches += 1
            stats.shards_executed += len(buf) * len(live)
            stats.exec_s += time.perf_counter() - t0
        for (gi, _), accs in zip(live, accs_by_group):
            for ls, acc in zip(buf, accs):
                yield gi, ExecResult(
                    ls.shard_id, ls.ell.v0, ls.ell.v1, np.asarray(acc),
                    batch_size=len(buf),
                )

    def _run_groups_ragged(self, loaded, groups, stats, masked, lanes_live):
        """RaggedFuse hot loop: 1 load, 1 concat, ONE kernel launch per
        batch covering every live group, with the collect of batch ``i``
        deferred until batch ``i+1`` has been dispatched — the launch stays
        in flight while the host stages the next batch (double buffering;
        the pipeline's prefetch threads fill the ``loaded`` iterator
        concurrently, so the pull below overlaps device compute too).
        """
        live = [(gi, ga) for gi, ga in enumerate(groups) if ga is not None]
        if not live:
            for _ in loaded:  # consume the stream exactly like the G-path
                pass
            return
        lane_ctx = None  # staged on first flush, reused across batches
        k_total = sum(int(ga[0].shape[0]) for _, ga in live)

        def dispatch(buf):
            nonlocal lane_ctx
            t0 = time.perf_counter()
            ells = [ls.ell for ls in buf]
            with trace.span(
                "exec.dispatch",
                shards=len(buf),
                groups=len(live),
                backend=self.backend_name,
                ragged=True,
            ) as sp:
                with trace.span("exec.stage"):
                    batch, n_ell_pad, idx, mask, seg, tw = _staged_batch(ells)
                    lanes_host = None
                    if lane_ctx is None:
                        lanes_host = ragged_lane_concat(
                            [ga[0] for _, ga in live],
                            [ga[1] for _, ga in live],
                            n_cols=batch.num_windows * batch.window,
                        )
                with trace.span("exec.put"):
                    staged, lane_ctx = _ragged_put(idx, mask, seg, tw,
                                                   lanes_host, lane_ctx)
                with trace.span("exec.launch"):
                    acc = self._ragged_fn(batch, n_ell_pad, staged, lane_ctx)
                if trace.active() is not None:
                    sp.set(**_dispatch_counters(
                        ells, n_ell_pad * batch.k, masked,
                        k_total if lanes_live is None else lanes_live,
                        int(lane_ctx["msgs"].shape[0]),
                        (idx, mask, seg, tw) + (lanes_host[:2] if lanes_host
                                                else ()),
                    ), **_ragged_kernel_counters(self.backend_name, batch,
                                                 n_ell_pad, lane_ctx["msgs"]))
            if stats is not None:
                stats.dispatches += 1
                stats.ragged_dispatches += 1
                stats.batches += 1
                stats.shards_executed += len(buf) * len(live)
                stats.ragged_lanes += k_total
                for gi, ga in live:
                    stats.group_lanes[gi] = (
                        stats.group_lanes.get(gi, 0) + int(ga[0].shape[0])
                    )
                stats.exec_s += time.perf_counter() - t0
            return buf, batch, acc, time.perf_counter()

        def collect(p):
            buf, batch, acc, t_launch = p
            if stats is not None:
                stats.overlap_s += time.perf_counter() - t_launch
            t0 = time.perf_counter()
            accs_by_group = _ragged_collect(batch, acc, lane_ctx["slices"])
            if stats is not None:
                stats.exec_s += time.perf_counter() - t0
            for (gi, _), accs in zip(live, accs_by_group):
                for ls, acc_s in zip(buf, accs):
                    yield gi, ExecResult(
                        ls.shard_id, ls.ell.v0, ls.ell.v1, np.asarray(acc_s),
                        batch_size=len(buf),
                    )

        pending = None
        buf: List[LoadedShard] = []
        for ls in loaded:
            buf.append(ls)
            if len(buf) >= self.batch_shards:
                nxt = dispatch(buf)
                buf = []
                if pending is not None:
                    yield from collect(pending)
                pending = nxt
        if buf:
            nxt = dispatch(buf)
            if pending is not None:
                yield from collect(pending)
            pending = nxt
        if pending is not None:
            yield from collect(pending)


class MeshLaneExecutor:
    """SPMD executor: route each loaded shard to its owning device's batch
    and dispatch every device's batch in ONE ``shard_map`` launch per live
    program group — "1 host read, G x D slices" (DESIGN.md §10).

    Shards buffer per device (by :class:`MeshPartition` ownership) up to
    ``batch_shards`` each; a flush dispatches ALL devices together, so the
    dispatch count is per SPMD program, not per device — each group's
    launch covers every device's slice.  Devices whose buffer is empty this
    round (inactive destination intervals pruned by the scheduler) ride
    along as identity-padded zero blocks inside the same program.

    ``backend="numpy"`` is the mesh EMULATION path: identical routing,
    flush cadence and accounting, but per-shard numpy-oracle calls and no
    jax import — the bitwise reference for the jnp/pallas mesh paths, safe
    under the memory-capped (jax-free) test tier.
    """

    def __init__(self, backend: str, partition, mesh=None, *,
                 batch_shards: int = 1, lanes: bool = False,
                 ragged: bool = True):
        if backend not in LANE_BACKENDS:
            raise ValueError(
                f"unknown backend {backend}; have {sorted(LANE_BACKENDS)}"
            )
        if backend != "numpy" and mesh is None:
            raise ValueError("jnp/pallas mesh execution needs a jax Mesh")
        if batch_shards < 1:
            raise ValueError("batch_shards must be >= 1")
        self.backend_name = backend
        self.partition = partition
        self.mesh = mesh
        self.batch_shards = batch_shards
        self.lanes = lanes
        #: RaggedFuse under the mesh: one shard_map step per flush covers
        #: every live group ("1 host read, 1 SPMD step, D slices"); the
        #: numpy emulation books the identical accounting.  Collection is
        #: double-buffered against the next round's host decode (ROADMAP
        #: mesh item (c)).
        self.ragged = bool(ragged)

    def run(
        self,
        loaded: Iterable[LoadedShard],
        msgs: np.ndarray,
        combine: str,
        stats: Optional[ExecStats] = None,
    ) -> Iterator[ExecResult]:
        """Single-program path (``VSWEngine.run``): the message array rides
        as a 1-lane group; the lane backends reduce to the plain ones for a
        single lane, so this is bitwise the single-device engine sweep."""
        groups: Sequence[GroupDispatch] = [(np.asarray(msgs)[None], combine)]
        for _, res in self.run_groups(loaded, groups, stats):
            yield ExecResult(res.shard_id, res.v0, res.v1, res.acc[0],
                             batch_size=res.batch_size)

    def run_groups(
        self,
        loaded: Iterable[LoadedShard],
        groups: Sequence[GroupDispatch],
        stats: Optional[ExecStats] = None,
        *,
        masked: bool = False,
        lanes_live: Optional[int] = None,
    ) -> Iterator[Tuple[int, ExecResult]]:
        if self.ragged:
            yield from self._run_groups_ragged(loaded, groups, stats,
                                               masked, lanes_live)
            return
        n_dev = self.partition.n_dev
        bufs: List[List[LoadedShard]] = [[] for _ in range(n_dev)]
        for ls in loaded:
            d = self.partition.device_of(ls.shard_id)
            bufs[d].append(ls)
            if len(bufs[d]) >= self.batch_shards:
                yield from self._flush(bufs, groups, stats)
                bufs = [[] for _ in range(n_dev)]
        if any(bufs):
            yield from self._flush(bufs, groups, stats)

    def _run_groups_ragged(self, loaded, groups, stats, masked, lanes_live):
        """One SPMD step (or emulated round) per flush for ALL groups, with
        batch ``i``'s collect deferred until batch ``i+1``'s dispatch is in
        flight — the mesh double-buffer (DESIGN.md §14)."""
        live = [(gi, ga) for gi, ga in enumerate(groups) if ga is not None]
        if not live:
            for _ in loaded:
                pass
            return
        n_dev = self.partition.n_dev
        lane_ctx = None  # staged on first jax flush, reused across rounds
        k_total = sum(int(ga[0].shape[0]) for _, ga in live)
        if self.backend_name != "numpy":
            import jax

            from repro.kernels.spmv_ell import ops as spmv_ops

        def dispatch(bufs):
            nonlocal lane_ctx
            t0 = time.perf_counter()
            total = sum(len(b) for b in bufs)
            with trace.span(
                "exec.dispatch",
                groups=len(live),
                shards=total,
                devices=sum(1 for b in bufs if b),
                backend=self.backend_name,
                ragged=True,
            ) as sp:
                if self.backend_name == "numpy":
                    fn = LANE_BACKENDS["numpy"]
                    results = []
                    for gi, (msgs, combine) in live:
                        for buf in bufs:
                            for ls in buf:
                                acc = np.asarray(
                                    fn(ls.csr, ls.ell, msgs, combine)
                                )
                                results.append((gi, ls, acc, len(buf)))
                    handle = ("numpy", results, None)
                else:
                    with trace.span("exec.stage"):
                        batches, arrays, first, rows_pad = (
                            spmv_ops.pack_device_batches(
                                [[ls.ell for ls in buf] for buf in bufs],
                                n_dev,
                            )
                        )
                        lanes_host = None
                        if lane_ctx is None:
                            lanes_host = ragged_lane_concat(
                                [ga[0] for _, ga in live],
                                [ga[1] for _, ga in live],
                                n_cols=spmv_ops.mesh_lane_cols(
                                    first.num_windows * first.window,
                                    self.mesh,
                                ),
                            )
                    with trace.span("exec.put"):
                        staged = spmv_ops.put_device_batches(arrays,
                                                             self.mesh)
                        if lanes_host is not None:
                            lane_ctx = spmv_ops.ragged_lanes_put(
                                lanes_host, mesh=self.mesh
                            )
                    with trace.span("exec.launch"):
                        h = spmv_ops.mesh_ragged_launch(
                            batches, staged, first, rows_pad, lane_ctx,
                            mesh=self.mesh, backend=self.backend_name,
                        )
                    handle = ("mesh", h, list(bufs))
                    if trace.active() is not None:
                        sp.set(**_dispatch_counters(
                            [ls.ell for buf in bufs for ls in buf],
                            arrays[0].size,
                            masked,
                            k_total if lanes_live is None else lanes_live,
                            int(lane_ctx["msgs"].shape[0]),
                            arrays + (lanes_host[:2] if lanes_host else ()),
                        ))
            if stats is not None:
                stats.dispatches += 1
                stats.ragged_dispatches += 1
                stats.batches += 1
                stats.shards_executed += total * len(live)
                stats.ragged_lanes += k_total
                for gi, ga in live:
                    stats.group_lanes[gi] = (
                        stats.group_lanes.get(gi, 0) + int(ga[0].shape[0])
                    )
                for d, buf in enumerate(bufs):
                    if buf:
                        stats.device_shards[d] = (
                            stats.device_shards.get(d, 0)
                            + len(buf) * len(live)
                        )
                        stats.device_dispatches[d] = (
                            stats.device_dispatches.get(d, 0) + 1
                        )
                stats.exec_s += time.perf_counter() - t0
            return handle, time.perf_counter()

        def collect(p):
            handle, t_launch = p
            if stats is not None:
                stats.overlap_s += time.perf_counter() - t_launch
            t0 = time.perf_counter()
            kind, payload, bufs = handle
            if kind == "numpy":
                results = payload
            else:
                results = []
                if payload is not None:
                    with trace.span("exec.collect"):
                        with trace.span("exec.wait"):
                            jax.block_until_ready(payload["acc"])
                        accs_by_group, _ = spmv_ops.mesh_ragged_collect(
                            payload
                        )
                    for (gi, _), accs_dev in zip(live, accs_by_group):
                        for buf, accs in zip(bufs, accs_dev):
                            for ls, acc in zip(buf, accs):
                                results.append(
                                    (gi, ls, np.asarray(acc), len(buf))
                                )
            if stats is not None:
                stats.exec_s += time.perf_counter() - t0
            for gi, ls, acc, bs in results:
                ref = ls.ref
                yield gi, ExecResult(ls.shard_id, ref.v0, ref.v1, acc,
                                     batch_size=bs)

        pending = None
        bufs: List[List[LoadedShard]] = [[] for _ in range(n_dev)]
        for ls in loaded:
            d = self.partition.device_of(ls.shard_id)
            bufs[d].append(ls)
            if len(bufs[d]) >= self.batch_shards:
                nxt = dispatch(bufs)
                bufs = [[] for _ in range(n_dev)]
                if pending is not None:
                    yield from collect(pending)
                pending = nxt
        if any(bufs):
            nxt = dispatch(bufs)
            if pending is not None:
                yield from collect(pending)
            pending = nxt
        if pending is not None:
            yield from collect(pending)

    def _flush(self, bufs, groups, stats):
        live = [(gi, ga) for gi, ga in enumerate(groups) if ga is not None]
        if not live:
            return
        t0 = time.perf_counter()
        results = []
        with trace.span(
            "exec.dispatch",
            groups=len(live),
            shards=sum(len(b) for b in bufs),
            devices=sum(1 for b in bufs if b),
            backend=self.backend_name,
        ):
            if self.backend_name == "numpy":
                fn = LANE_BACKENDS["numpy"]
                for gi, (msgs, combine) in live:
                    for buf in bufs:
                        for ls in buf:
                            acc = np.asarray(fn(ls.csr, ls.ell, msgs, combine))
                            results.append((gi, ls, acc, len(buf)))
            else:
                from repro.kernels.spmv_ell import ops as spmv_ops

                accs_by_group, _ = spmv_ops.ell_update_lanes_mesh_multi(
                    [[ls.ell for ls in buf] for buf in bufs],
                    [ga[0] for _, ga in live],
                    [ga[1] for _, ga in live],
                    mesh=self.mesh, backend=self.backend_name,
                )
                for (gi, _), accs_dev in zip(live, accs_by_group):
                    for buf, accs in zip(bufs, accs_dev):
                        for ls, acc in zip(buf, accs):
                            results.append((gi, ls, np.asarray(acc), len(buf)))
        if stats is not None:
            total = sum(len(b) for b in bufs)
            # One SPMD launch per group covers every device's slice; the
            # numpy emulation books the same way so accounting is
            # backend-invariant (fig_mesh asserts conservation on it).
            stats.dispatches += len(live)
            stats.batches += 1
            stats.shards_executed += total * len(live)
            for d, buf in enumerate(bufs):
                if buf:
                    stats.device_shards[d] = (
                        stats.device_shards.get(d, 0) + len(buf) * len(live)
                    )
                    stats.device_dispatches[d] = (
                        stats.device_dispatches.get(d, 0) + len(live)
                    )
            stats.exec_s += time.perf_counter() - t0
        for gi, ls, acc, bs in results:
            ref = ls.ref
            yield gi, ExecResult(ls.shard_id, ref.v0, ref.v1, acc,
                                 batch_size=bs)


def make_executor(backend: str, *, batch_shards: int = 1):
    """Pick the executor for a backend: batching only exists for the ELL
    (jnp/pallas) backends; the numpy oracle always runs per-shard."""
    if batch_shards < 1:
        raise ValueError("batch_shards must be >= 1")
    if batch_shards > 1 and backend in _BATCHED_BACKENDS:
        return BatchedEllExecutor(backend, batch_shards)
    return PerShardExecutor(backend)


def make_lane_executor(backend: str, *, batch_shards: int = 1,
                       ragged: bool = True):
    """Executor whose dispatches carry a lane (concurrent-query) axis:
    same selection rule as :func:`make_executor`, except that ``ragged``
    (the RaggedFuse one-launch path, on by default) also wants the batched
    executor at ``batch_shards=1`` — a ragged flush is still 1 launch where
    the per-shard path would pay G."""
    if batch_shards < 1:
        raise ValueError("batch_shards must be >= 1")
    if backend in _BATCHED_LANE_BACKENDS and (batch_shards > 1 or ragged):
        return BatchedEllExecutor(backend, batch_shards, lanes=True,
                                  ragged=ragged)
    return PerShardExecutor(backend, lanes=True)
