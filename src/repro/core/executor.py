"""Shard executors: *how planned shards execute* (DESIGN.md §3-4).

Third layer of the engine stack.  An executor consumes the pipeline's
stream of loaded shards and yields per-shard accumulators; it owns the
backend dispatch the engine used to do inline.

Single-query strategies (:func:`make_executor`):

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

Lane (concurrent-query) executors (:func:`make_lane_executor`): the jnp and
pallas backends dispatch lanes one way only, the ragged launch of
:class:`RaggedLaneExecutor` (DESIGN.md §14); the numpy oracle runs
:class:`PerShardExecutor` with ``lanes=True``; a mesh engine runs
:class:`MeshLaneExecutor`.

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
    "RaggedLaneExecutor",
    "make_executor",
    "make_lane_executor",
    "update_shard_numpy",
    "update_shard_jnp",
    "update_shards_jnp_batched",
    "update_shard_numpy_lanes",
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

    Shared by the single-query path (jitted directly) and the ragged lane
    launch (one arm per combine, vmapped over the lane axis) so both trace
    the exact same per-lane computation.
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


def _padded_shard_inputs(ell: EllShard, msgs: np.ndarray):
    """Shape-bucket one shard's ELL arrays and pad messages to full windows
    (so the gather never reads OOB)."""
    n_ell_pad = bucket_rows(ell.n_ell, ell.tr)
    idx, mask, seg, tw = pad_ell_arrays(
        ell.ell_idx, ell.ell_mask, ell.seg, ell.tile_window,
        ell.n_ell, ell.tr, n_ell_pad,
    )
    n_pad_v = ell.num_windows * ell.window
    return n_ell_pad, idx, mask, seg, tw, np.pad(msgs, (0, n_pad_v - len(msgs)))


def _staged_batch(ells: List[EllShard]):
    """Concatenate + shape-bucket a shard batch (the shard-side staging of
    the batched single-query path and the ragged lane launch — both MUST
    pad identically or fusion stops being bitwise-invisible)."""
    batch = concat_ells(ells)
    n_ell_pad = bucket_rows(batch.n_ell, batch.tr)
    idx, mask, seg, tw = pad_ell_arrays(
        batch.ell_idx, batch.ell_mask, batch.seg, batch.tile_window,
        batch.n_ell, batch.tr, n_ell_pad,
    )
    return batch, n_ell_pad, idx, mask, seg, tw


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
    batch, n_ell_pad, idx, mask, seg, tw = _staged_batch(ells)
    msgs_p = np.pad(msgs, (0, batch.num_windows * batch.window - len(msgs)))
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


@functools.lru_cache(maxsize=64)
def _jnp_ell_lanes_ragged_fn(
    n_ell: int, k: int, tr: int, rows: int, window: int, combines: tuple
):
    """RaggedFuse jnp variant: ONE jit dispatch updates the concatenated
    lane state of ALL fusion groups, selecting each lane's combine arm via
    its ``combine_ids`` entry.  The per-arm bodies are the exact
    :func:`_ell_fn_impl` closures the single-query path jits, and
    ``jnp.where`` keeps the selected arm's value bit-for-bit, so each
    lane's row is bitwise a solo :func:`update_shard_jnp` run."""
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


BACKENDS: Dict[str, Callable] = {
    "numpy": update_shard_numpy,
    "jnp": update_shard_jnp,
    "pallas": _update_shard_pallas,
}

_BATCHED_BACKENDS: Dict[str, Callable] = {
    "jnp": update_shards_jnp_batched,
    "pallas": _update_shards_pallas_batched,
}

#: Per-shard lane backends: the numpy oracle only (the ELL backends
#: dispatch lanes through :class:`RaggedLaneExecutor`).
LANE_BACKENDS: Dict[str, Callable] = {
    "numpy": update_shard_numpy_lanes,
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
    #: per batch — conservation: ragged_dispatches <= batches <=
    #: dispatches, DESIGN.md §14).
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
    yields ``[lanes, rows]`` accumulators (one load, K lanes): the numpy
    lane oracle, the only per-shard lane backend (:data:`LANE_BACKENDS`).
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
    """Batch consecutive planned ELL shards into one kernel dispatch."""

    def __init__(self, backend: str, batch_shards: int = 4):
        if backend not in _BATCHED_BACKENDS:
            raise ValueError(
                f"batched execution needs an ELL backend, got {backend!r}"
            )
        if batch_shards < 1:
            raise ValueError("batch_shards must be >= 1")
        self.backend_name = backend
        self.batch_shards = batch_shards
        self._fn = _BATCHED_BACKENDS[backend]

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


class RaggedLaneExecutor:
    """Lane dispatch of the ELL backends (jnp, pallas): RaggedFuse
    (DESIGN.md §14).

    Up to ``batch_shards`` consecutive shards are concatenated ONCE, every
    live program group rides the lane axis of ONE ragged launch per batch
    (each lane selecting its combine arm), and the collect of batch ``i``
    waits until batch ``i+1`` is in flight (double buffering).  Each
    lane's row is bitwise a solo run of that lane's program.
    """

    def __init__(self, backend: str, batch_shards: int = 1):
        if backend not in _RAGGED_LANE_BACKENDS:
            raise ValueError(
                f"lane dispatch needs an ELL backend, got {backend!r}"
            )
        if batch_shards < 1:
            raise ValueError("batch_shards must be >= 1")
        self.backend_name = backend
        self.batch_shards = batch_shards
        self._ragged_fn = _RAGGED_LANE_BACKENDS[backend]

    def run(
        self,
        loaded: Iterable[LoadedShard],
        msgs: np.ndarray,
        combine: str,
        stats: Optional[ExecStats] = None,
    ) -> Iterator[ExecResult]:
        """One program group: ``msgs`` is ``[lanes, |V|]`` and each result
        carries ``[lanes, rows]``."""
        for _, res in self.run_groups(loaded, [(msgs, combine)], stats):
            yield res

    def run_groups(
        self,
        loaded: Iterable[LoadedShard],
        groups: Sequence[GroupDispatch],
        stats: Optional[ExecStats] = None,
        *,
        masked: bool = False,
        lanes_live: Optional[int] = None,
    ) -> Iterator[Tuple[int, ExecResult]]:
        """RaggedFuse hot loop: 1 load, 1 concat, ONE kernel launch per
        batch covering every live group, with the collect of batch ``i``
        deferred until batch ``i+1`` has been dispatched — the launch stays
        in flight while the host stages the next batch (double buffering;
        the pipeline's prefetch threads fill the ``loaded`` iterator
        concurrently, so the pull below overlaps device compute too).
        """
        live = [(gi, ga) for gi, ga in enumerate(groups) if ga is not None]
        if not live:
            for _ in loaded:  # the shard stream is still consumed once
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
    and dispatch every device's batch, for every live program group, in
    ONE ``shard_map`` step — "1 host read, 1 SPMD step, D slices"
    (DESIGN.md §10, §14).

    Shards buffer per device (by :class:`MeshPartition` ownership) up to
    ``batch_shards`` each; a flush dispatches ALL devices together, so the
    dispatch count is per SPMD program, not per device.  Devices whose
    buffer is empty this round (inactive destination intervals pruned by
    the scheduler) ride along as identity-padded zero blocks inside the
    same program.  Collection is double-buffered against the next round's
    host decode.

    ``backend="numpy"`` is the mesh EMULATION path: identical routing,
    flush cadence and accounting, but per-shard numpy-oracle calls and no
    jax import — the bitwise reference for the jnp/pallas mesh paths, safe
    under the memory-capped (jax-free) test tier.
    """

    def __init__(self, backend: str, partition, mesh=None, *,
                 batch_shards: int = 1):
        if backend not in BACKENDS:
            raise ValueError(
                f"unknown backend {backend}; have {sorted(BACKENDS)}"
            )
        if backend != "numpy" and mesh is None:
            raise ValueError("jnp/pallas mesh execution needs a jax Mesh")
        if batch_shards < 1:
            raise ValueError("batch_shards must be >= 1")
        self.backend_name = backend
        self.partition = partition
        self.mesh = mesh
        self.batch_shards = batch_shards

    def run(
        self,
        loaded: Iterable[LoadedShard],
        msgs: np.ndarray,
        combine: str,
        stats: Optional[ExecStats] = None,
    ) -> Iterator[ExecResult]:
        """Single-program path (``VSWEngine.run``): the message array rides
        as a 1-lane group; a lane's row is bitwise a solo run, so this is
        bitwise the single-device engine sweep."""
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


def make_executor(backend: str, *, batch_shards: int = 1):
    """Pick the executor for a backend: batching only exists for the ELL
    (jnp/pallas) backends; the numpy oracle always runs per-shard."""
    if batch_shards < 1:
        raise ValueError("batch_shards must be >= 1")
    if batch_shards > 1 and backend in _BATCHED_BACKENDS:
        return BatchedEllExecutor(backend, batch_shards)
    return PerShardExecutor(backend)


def make_lane_executor(backend: str, *, batch_shards: int = 1):
    """Executor whose dispatches carry a lane (concurrent-query) axis: the
    ragged launch for the ELL backends at any ``batch_shards``, the
    per-shard oracle for numpy."""
    if batch_shards < 1:
        raise ValueError("batch_shards must be >= 1")
    if backend in _RAGGED_LANE_BACKENDS:
        return RaggedLaneExecutor(backend, batch_shards)
    return PerShardExecutor(backend, lanes=True)
