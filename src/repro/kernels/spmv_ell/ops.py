"""jit'd public wrappers around the ELL pull-update kernel.

``ell_update`` consumes an :class:`~repro.core.csr.EllShard` (host numpy)
and the full message array, runs the Pallas partial kernel + the XLA
segment combine, and returns per-destination accumulations.  It is the
``pallas`` backend of :class:`~repro.core.vsw.VSWEngine`.

``ell_update_batched`` is the multi-shard entry point (DESIGN.md §4): N
consecutive planned shards are concatenated into one grid — one
``pallas_call`` whose scalar-prefetched ``tile_window`` map spans every
tile of every shard against the same resident message table — followed by
one globalized segment combine.  Per-shard dispatch overhead (trace cache
lookup, argument staging, kernel launch) is paid once per batch instead of
once per shard.
"""

from __future__ import annotations

import functools
from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.csr import (
    EllShard,
    bucket_rows,
    concat_ells,
    next_pow2,
    pad_ell_arrays,
    ragged_lane_concat,
)

from . import kernel as K

IDENTITY = K.IDENTITY


def _segment_combine(part, seg, rows, combine):
    if combine == "sum":
        return jax.ops.segment_sum(part, seg, num_segments=rows)
    if combine == "min":
        return jax.ops.segment_min(part, seg, num_segments=rows)
    return jax.ops.segment_max(part, seg, num_segments=rows)


@functools.partial(
    jax.jit,
    static_argnames=("window", "tr", "rows", "combine", "variant"),
)
def _update_jit(
    ell_idx, ell_valid, seg, tile_window, msgs,
    *, window, tr, rows, combine, variant,
):
    if variant == "masked":
        part = K.ell_partials_masked(
            ell_idx, ell_valid, tile_window, msgs,
            window=window, tr=tr, combine=combine,
        )
    else:
        part = K.ell_partials_sentinel(
            ell_idx, tile_window, msgs,
            window=window, tr=tr, combine=combine,
        )
    return _segment_combine(part, seg, rows, combine)


@functools.partial(
    jax.jit, static_argnames=("window", "tr", "rows", "combine")
)
def _update_lanes_jit(
    ell_idx, ell_valid, seg, tile_window, msgs2d,
    *, window, tr, rows, combine,
):
    """Lane-batched update: ONE traced computation covering every lane.

    ``msgs2d`` is ``[lanes, num_windows * window]`` — one message row per
    in-flight query.  The edge structure (idx/mask/seg/tile_window) is
    shared by all lanes, so the whole partials+combine pipeline is vmapped
    over the message axis (``pallas_call`` supports vmap; the lane count is
    a static shape the serving batcher pads to a power of two to bound
    retraces).  Each lane's slice runs the exact computation
    :func:`_update_jit` would run for it alone — the bitwise-equality
    contract of the serving layer (DESIGN.md §6).
    """

    def one_lane(msgs):
        part = K.ell_partials_masked(
            ell_idx, ell_valid, tile_window, msgs,
            window=window, tr=tr, combine=combine,
        )
        return _segment_combine(part, seg, rows, combine)

    return jax.vmap(one_lane)(msgs2d)


def ell_update(
    ell: EllShard,
    msgs: np.ndarray,
    combine: str,
    *,
    variant: str = "masked",
) -> jax.Array:
    """acc[rows] for one shard.  msgs is the full |V| message array."""
    nw = ell.num_windows
    if variant == "masked":
        msgs_p = np.zeros(nw * ell.window, msgs.dtype)
        msgs_p[: msgs.shape[0]] = msgs
        return _update_jit(
            jnp.asarray(ell.ell_idx), jnp.asarray(ell.ell_mask),
            jnp.asarray(ell.seg), jnp.asarray(ell.tile_window),
            jnp.asarray(msgs_p),
            window=ell.window, tr=ell.tr, rows=ell.rows, combine=combine,
            variant=variant,
        )
    # Sentinel layout: extend each window by one aligned tile holding the
    # combine identity; remap invalid slots to the sentinel position.
    ext = ell.window + K.sentinel_pad(ell.window)
    msgs_e = np.full(nw * ext, IDENTITY[combine], msgs.dtype)
    for w in range(nw):
        lo, hi = w * ell.window, min((w + 1) * ell.window, msgs.shape[0])
        msgs_e[w * ext : w * ext + (hi - lo)] = msgs[lo:hi]
    idx = np.where(ell.ell_mask, ell.ell_idx.astype(np.int32), ell.window)
    return _update_jit(
        jnp.asarray(idx), None, jnp.asarray(ell.seg),
        jnp.asarray(ell.tile_window), jnp.asarray(msgs_e),
        window=ext, tr=ell.tr, rows=ell.rows, combine=combine,
        variant=variant,
    )


def _prep_batch(ells: Sequence[EllShard]):
    """Concatenate + shape-bucket a shard batch (shared by the single-query
    and lane-batched entry points so the padding discipline can't drift)."""
    batch = concat_ells(ells)
    n_ell_pad = bucket_rows(batch.n_ell, batch.tr)
    idx, mask, seg, tw = pad_ell_arrays(
        batch.ell_idx, batch.ell_mask, batch.seg, batch.tile_window,
        batch.n_ell, batch.tr, n_ell_pad,
    )
    return batch, idx, mask, seg, tw


def ell_update_batched(
    ells: Sequence[EllShard],
    msgs: np.ndarray,
    combine: str,
) -> List[np.ndarray]:
    """Per-shard accumulators for N shards from ONE kernel dispatch.

    Bitwise-equal to calling :func:`ell_update` per shard: the batch is a
    pure concatenation — every tile computes the same partials it would
    have computed alone, and the segment combine sees the same per-segment
    contribution order (shards are concatenated in plan order, padding rows
    contribute the combine identity).

    Grid and segment shapes are pow2-bucketed: under selective scheduling
    the batch composition changes every iteration, and unbucketed shapes
    would trigger a retrace per distinct (n_ell, rows) pair.
    """
    if not ells:
        return []
    batch, idx, mask, seg, tw = _prep_batch(ells)
    msgs_p = np.zeros(batch.num_windows * batch.window, msgs.dtype)
    msgs_p[: msgs.shape[0]] = msgs
    acc = _update_jit(
        jnp.asarray(idx), jnp.asarray(mask),
        jnp.asarray(seg), jnp.asarray(tw),
        jnp.asarray(msgs_p),
        window=batch.window, tr=batch.tr, rows=next_pow2(batch.rows_total),
        combine=combine, variant="masked",
    )
    return batch.split(np.asarray(acc))


def ell_update_lanes(
    ell: EllShard,
    msgs: np.ndarray,  # [lanes, |V|]
    combine: str,
) -> jax.Array:
    """acc[lanes, rows] for one shard against ``lanes`` message rows.

    The serving layer's per-shard entry point: one dispatch applies the
    shard to every in-flight query lane, so a shard's load+decode cost is
    amortized K ways (ISSUE: lane-batched VSW sweeps).
    """
    if msgs.ndim != 2:
        raise ValueError(f"lane update needs [lanes, |V|] messages, got {msgs.shape}")
    nw = ell.num_windows
    msgs_p = np.zeros((msgs.shape[0], nw * ell.window), msgs.dtype)
    msgs_p[:, : msgs.shape[1]] = msgs
    return _update_lanes_jit(
        jnp.asarray(ell.ell_idx), jnp.asarray(ell.ell_mask),
        jnp.asarray(ell.seg), jnp.asarray(ell.tile_window),
        jnp.asarray(msgs_p),
        window=ell.window, tr=ell.tr, rows=ell.rows, combine=combine,
    )


def ell_update_lanes_batched(
    ells: Sequence[EllShard],
    msgs: np.ndarray,  # [lanes, |V|]
    combine: str,
) -> List[np.ndarray]:
    """Per-shard ``[lanes, rows]`` accumulators for N shards x K lanes from
    ONE dispatch — the serving hot loop's maximal amortization point: the
    batch's edge bytes are decoded once and reused by every lane."""
    if msgs.ndim != 2:
        raise ValueError(f"lane update needs [lanes, |V|] messages, got {msgs.shape}")
    if not ells:
        return []
    batch, idx, mask, seg, tw = _prep_batch(ells)
    msgs_p = np.zeros((msgs.shape[0], batch.num_windows * batch.window), msgs.dtype)
    msgs_p[:, : msgs.shape[1]] = msgs
    acc = _update_lanes_jit(
        jnp.asarray(idx), jnp.asarray(mask),
        jnp.asarray(seg), jnp.asarray(tw),
        jnp.asarray(msgs_p),
        window=batch.window, tr=batch.tr, rows=next_pow2(batch.rows_total),
        combine=combine,
    )
    return batch.split(np.asarray(acc))


def ell_update_lanes_multi(
    ells: Sequence[EllShard],
    msgs_by_group: Sequence[np.ndarray],  # each [K_g, |V|]
    combines: Sequence[str],
) -> List[List[np.ndarray]]:
    """Per-shard ``[K_g, rows]`` accumulators for N shards x G program
    groups: the batch is concatenated, shape-bucketed and staged to device
    ONCE, then dispatched once per group against that group's own lane
    matrix and combine monoid (DESIGN.md §9 — fused sweeps interleave
    heterogeneous query programs on one decoded shard stream).

    Each group's dispatch calls the exact jit'd computation
    :func:`ell_update_lanes_batched` would run for it alone — same padded
    arrays, same shape buckets — so interleaving is bitwise-invisible per
    lane.  Returns one per-shard accumulator list per group.
    """
    if len(msgs_by_group) != len(combines):
        raise ValueError("one combine per message group")
    for msgs in msgs_by_group:
        if msgs.ndim != 2:
            raise ValueError(
                f"lane update needs [lanes, |V|] messages, got {msgs.shape}"
            )
    if not ells:
        return [[] for _ in msgs_by_group]
    batch, idx, mask, seg, tw = _prep_batch(ells)
    idx_j, mask_j, seg_j, tw_j = (
        jnp.asarray(idx), jnp.asarray(mask), jnp.asarray(seg), jnp.asarray(tw)
    )
    rows_pad = next_pow2(batch.rows_total)
    n_pad_v = batch.num_windows * batch.window
    out: List[List[np.ndarray]] = []
    for msgs, combine in zip(msgs_by_group, combines):
        msgs_p = np.zeros((msgs.shape[0], n_pad_v), msgs.dtype)
        msgs_p[:, : msgs.shape[1]] = msgs
        acc = _update_lanes_jit(
            idx_j, mask_j, seg_j, tw_j, jnp.asarray(msgs_p),
            window=batch.window, tr=batch.tr, rows=rows_pad,
            combine=combine,
        )
        out.append(batch.split(np.asarray(acc)))
    return out


@functools.partial(
    jax.jit, static_argnames=("window", "tr", "rows", "combines")
)
def _update_lanes_ragged_jit(
    ell_idx, ell_valid, seg, tile_window, combine_ids, msgs2d,
    *, window, tr, rows, combines,
):
    """RaggedFuse update: ONE pallas launch covers every fusion group.

    ``msgs2d`` is the concatenated ``[k_pad, n_pad_v]`` lane state of ALL
    groups; ``combine_ids`` names each lane's combine arm.  The ragged
    partials kernel gathers once per tile and selects the arm in-kernel;
    the segment combine runs once per arm with the selected rows kept via
    ``jnp.where`` — each lane's value is op-for-op what
    :func:`_update_lanes_jit` computes for its group alone, so the bitwise
    contract of the multi path is preserved (DESIGN.md §14).  Its prologue
    builds the kernel's per-tile row lists from the launched slots, so each
    tile runs only the gather rounds its valid slots read.
    """
    n_iter, tile_rows = K.ragged_row_table(ell_idx, ell_valid,
                                           window=window, tr=tr)
    part = K.ell_partials_ragged(
        ell_idx, ell_valid, tile_window, n_iter, tile_rows, combine_ids,
        msgs2d, window=window, tr=tr, combines=combines,
    )
    acc = jnp.zeros((msgs2d.shape[0], rows), msgs2d.dtype)
    for ci, combine in enumerate(combines):
        acc_c = jax.vmap(
            lambda p, c=combine: _segment_combine(p, seg, rows, c)
        )(part)
        acc = jnp.where((combine_ids == ci)[:, None], acc_c, acc)
    return acc


def _mesh_put(mesh, x, *logical):
    """Stage a host array straight onto its shards of ``mesh`` (each device
    receives only its slice; nothing lands on one chip first)."""
    from jax.sharding import NamedSharding

    from repro.distributed.sharding import graph_ctx

    return jax.device_put(x, NamedSharding(mesh, graph_ctx(mesh).spec(*logical)))


def ragged_lanes_put(lanes_host, *, mesh=None):
    """Copy a :func:`~repro.core.csr.ragged_lane_concat` result to device:
    the lane side of a ragged launch.  With ``mesh`` the lane matrix goes
    straight to its vertex shards and the combine ids are replicated."""
    msgs_all, cids, combines_set, slices = lanes_host
    if mesh is None:
        msgs_d, cids_d = jnp.asarray(msgs_all), jnp.asarray(cids)
    else:
        msgs_d = _mesh_put(mesh, msgs_all, "lane", "vertex")
        cids_d = _mesh_put(mesh, cids, "lane")
    return {"msgs": msgs_d, "cids": cids_d, "combines": combines_set,
            "slices": slices}


def ragged_stage_lanes(msgs_by_group, combines: Sequence[str], n_pad_v: int,
                       *, mesh=None):
    """Stage the lane side of a ragged launch to device ONCE.

    Lane values are fixed within a sweep iteration, so the executor caches
    this across shard batches — the per-group pad+copy the multi path pays
    on every flush is paid once per iteration instead.
    """
    return ragged_lanes_put(
        ragged_lane_concat(msgs_by_group, combines, n_cols=n_pad_v),
        mesh=mesh,
    )


def ragged_launch(batch, staged, lane_ctx):
    """Launch ONE ragged update for a shard batch whose ``(idx, mask, seg,
    tile_window)`` are already on device (``staged``).

    Returns the accumulator *unforced*, so the caller can stage the next
    batch's host decode while this launch is in flight (the double-buffer
    protocol, DESIGN.md §14)."""
    return _update_lanes_ragged_jit(
        *staged, lane_ctx["cids"], lane_ctx["msgs"],
        window=batch.window, tr=batch.tr, rows=next_pow2(batch.rows_total),
        combines=lane_ctx["combines"],
    )


def ell_update_lanes_ragged(
    ells: Sequence[EllShard],
    msgs_by_group: Sequence[np.ndarray],  # each [K_g, |V|]
    combines: Sequence[str],
) -> List[List[np.ndarray]]:
    """Per-shard ``[K_g, rows]`` accumulators for N shards x G groups from
    ONE ragged launch — the one-launch replacement for
    :func:`ell_update_lanes_multi`'s G-dispatch loop (DESIGN.md §14).

    Groups are concatenated along the lane axis with a per-lane combine-id
    vector; the kernel selects the combine arm per lane, so dispatch count
    per batch drops from G to 1 and lane padding is per-launch instead of
    per-group-pow2 (never worse: see :func:`repro.core.csr.ragged_lane_pad`).
    Bitwise-equal per group to the multi path.
    """
    if len(msgs_by_group) != len(combines):
        raise ValueError("one combine per message group")
    for msgs in msgs_by_group:
        if msgs.ndim != 2:
            raise ValueError(
                f"lane update needs [lanes, |V|] messages, got {msgs.shape}"
            )
    if not ells:
        return [[] for _ in msgs_by_group]
    n_pad_v = ells[0].num_windows * ells[0].window
    lane_ctx = ragged_stage_lanes(msgs_by_group, combines, n_pad_v)
    batch, idx, mask, seg, tw = _prep_batch(ells)
    acc = np.asarray(ragged_launch(
        batch, tuple(jnp.asarray(x) for x in (idx, mask, seg, tw)), lane_ctx
    ))
    return [batch.split(acc[sl]) for sl in lane_ctx["slices"]]


#: logical axes of the stacked per-device ELL arrays a mesh step consumes:
#: ell_idx / ell_mask ``[D, n_ell, K]``, seg ``[D, n_ell]``, tile_window
#: ``[D, n_tiles]`` — device ``d``'s block lands on device ``d``.
_DEVICE_AXES = (
    ("device", None, None), ("device", None, None),
    ("device", None), ("device", None),
)


def _mesh_body(backend, window, tr, rows, combine):
    """The single-device lane body a mesh step vmaps:

    - ``backend="jnp"``: :func:`repro.core.executor._ell_fn_impl` — the
      exact function the single-device jnp lane path vmaps,
    - ``backend="pallas"``: ``K.ell_partials_masked`` + the segment combine
      — the exact body of :func:`_update_lanes_jit`'s ``one_lane``.
    """
    if backend == "jnp":
        from repro.core.executor import _ell_fn_impl

        return _ell_fn_impl(tr, rows, window, combine)

    def body(ell_idx, ell_mask, seg, tile_window, msgs):
        part = K.ell_partials_masked(
            ell_idx, ell_mask, tile_window, msgs,
            window=window, tr=tr, combine=combine,
        )
        return _segment_combine(part, seg, rows, combine)

    return body


def _mesh_jit(mesh, step, in_axes):
    """``jax.shard_map`` + ``jax.jit`` of a mesh step whose inputs carry the
    logical ``in_axes`` and whose outputs are the ``[D, lanes, rows]``
    accumulator and one replicated scalar."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.distributed.sharding import graph_ctx

    ctx = graph_ctx(mesh)
    in_specs = tuple(ctx.spec(*ax) for ax in in_axes)
    out_specs = (ctx.spec("device", "lane", None), P())
    fn = jax.shard_map(
        step, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )
    return jax.jit(
        fn,
        in_shardings=tuple(NamedSharding(mesh, s) for s in in_specs),
        out_shardings=tuple(NamedSharding(mesh, s) for s in out_specs),
    )


@functools.lru_cache(maxsize=32)
def _mesh_lanes_jit(mesh, backend, window, tr, rows, combine):
    """One mesh sweep dispatch: shard_map'd lane update over a device axis.

    Device ``d`` receives its own stacked ELL block (leading axis sharded
    over every mesh axis) plus its slice of the lane-message matrix,
    all-gathers the full message array (the SEM working set, DESIGN.md §10)
    and runs THE single-device lane computation (:func:`_mesh_body`) on its
    block.

    Each destination row still belongs to exactly one device (the paper's
    lock-free property lifted to SPMD), so per-shard accumulators are
    bitwise those of the single-device path.  The scalar second output is a
    ``psum``'d count of non-identity accumulator slots — the SPMD activity
    proxy the iteration stats record without a host round-trip per device.
    """
    axes = tuple(mesh.axis_names)
    ident = IDENTITY[combine]
    body = _mesh_body(backend, window, tr, rows, combine)

    def step(idx, mask, seg, tw, msgs_local):
        # Leading axis is this device's single ELL block.
        idx, mask, seg, tw = idx[0], mask[0], seg[0], tw[0]
        # SEM working set: every device needs the full message array.
        msgs = jax.lax.all_gather(msgs_local, axes, axis=1, tiled=True)
        acc = jax.vmap(body, in_axes=(None, None, None, None, 0))(
            idx, mask, seg, tw, msgs
        )
        touched = jax.lax.psum(
            (acc != jnp.asarray(ident, acc.dtype)).sum(), axes
        )
        return acc[None], touched

    return _mesh_jit(mesh, step, _DEVICE_AXES + (("lane", "vertex"),))


def pack_device_batches(device_ells, n_dev: int):
    """Concatenate every device's shard batch with the single-device
    :func:`_prep_batch` discipline and pad them to COMMON (pow2-bucketed)
    shapes, so the round is one SPMD program — host work only.  The common
    padding is the usual identity padding, so each shard's accumulator is
    bitwise what :func:`ell_update_lanes_batched` computes for its device's
    batch alone.

    Returns ``(batches, arrays, first, rows_pad)`` with ``arrays`` the
    stacked ``[D, ...]`` idx, mask, seg and tile_window, or None when every
    device's list is empty.
    """
    if len(device_ells) != n_dev:
        raise ValueError(
            f"device_ells has {len(device_ells)} slots for a {n_dev}-device mesh"
        )
    batches = {
        d: _prep_batch(ells)
        for d, ells in enumerate(device_ells)
        if len(ells)
    }
    if not batches:
        return None
    first = next(iter(batches.values()))[0]
    tr, k = first.tr, first.k
    n_ell_pad = bucket_rows(max(t[1].shape[0] for t in batches.values()), tr)
    rows_pad = next_pow2(max(t[0].rows_total for t in batches.values()))

    idx_all = np.zeros((n_dev, n_ell_pad, k), dtype=first.ell_idx.dtype)
    mask_all = np.zeros((n_dev, n_ell_pad, k), dtype=bool)
    seg_all = np.zeros((n_dev, n_ell_pad), dtype=np.int32)
    tw_all = np.zeros((n_dev, n_ell_pad // tr), dtype=np.int32)
    for d, (batch, idx, mask, seg, tw) in batches.items():
        idx, mask, seg, tw = pad_ell_arrays(
            idx, mask, seg, tw, idx.shape[0], tr, n_ell_pad
        )
        idx_all[d], mask_all[d], seg_all[d], tw_all[d] = idx, mask, seg, tw
    return batches, (idx_all, mask_all, seg_all, tw_all), first, rows_pad


def put_device_batches(arrays, mesh):
    """Stage :func:`pack_device_batches`' stacked arrays straight onto
    their devices (device ``d``'s block lands on device ``d``)."""
    return tuple(_mesh_put(mesh, x, *ax) for x, ax in zip(arrays, _DEVICE_AXES))


def _stage_device_batches(device_ells, mesh):
    """:func:`pack_device_batches` then :func:`put_device_batches`; returns
    ``(batches, staged, first, rows_pad)`` or None."""
    packed = pack_device_batches(device_ells, int(mesh.devices.size))
    if packed is None:
        return None
    batches, arrays, first, rows_pad = packed
    return batches, put_device_batches(arrays, mesh), first, rows_pad


def ell_update_lanes_mesh_multi(
    device_ells: Sequence[Sequence[EllShard]],  # [D] lists, device order
    msgs_by_group: Sequence[np.ndarray],  # each [K_g, |V|]
    combines: Sequence[str],
    *,
    mesh,
    backend: str = "pallas",
):
    """Mesh sweeps' dispatch point: 1 host read, G x D device slices.

    ``device_ells[d]`` holds the shards device ``d`` owns this round (the
    host read each of them ONCE; empty lists idle their device through the
    SPMD program); see :func:`pack_device_batches` for the padding.

    Returns ``(accs_by_group, touched_by_group)`` where
    ``accs_by_group[g][d]`` lists per-shard ``[K_g, rows]`` accumulators
    for device ``d`` (empty for idle devices) and ``touched_by_group[g]``
    is the psum'd non-identity slot count (SPMD activity proxy).
    """
    if len(msgs_by_group) != len(combines):
        raise ValueError("one combine per message group")
    staged_round = _stage_device_batches(device_ells, mesh)
    if staged_round is None:
        return [[[] for _ in device_ells] for _ in msgs_by_group], [0] * len(
            msgs_by_group
        )
    batches, staged, first, rows_pad = staged_round
    n_dev = len(device_ells)

    # Messages: pad to full windows (gathers never pass n_pad_v), then to a
    # multiple of n_dev so the vertex axis shards evenly; the tail past
    # n_pad_v is never addressed by a valid slot.
    n_pad_v = first.num_windows * first.window
    n_pad_dev = -(-n_pad_v // n_dev) * n_dev

    accs_by_group = []
    touched_by_group = []
    for msgs, combine in zip(msgs_by_group, combines):
        if msgs.ndim != 2:
            raise ValueError(
                f"lane update needs [lanes, |V|] messages, got {msgs.shape}"
            )
        msgs_p = np.zeros((msgs.shape[0], n_pad_dev), msgs.dtype)
        msgs_p[:, : msgs.shape[1]] = msgs
        fn = _mesh_lanes_jit(
            mesh, backend, first.window, first.tr, rows_pad, combine
        )
        acc_all, touched = fn(*staged, _mesh_put(mesh, msgs_p, "lane", "vertex"))
        acc_all = np.asarray(acc_all)
        accs_by_group.append(
            [
                batches[d][0].split(acc_all[d]) if d in batches else []
                for d in range(n_dev)
            ]
        )
        touched_by_group.append(int(touched))
    return accs_by_group, touched_by_group


@functools.lru_cache(maxsize=32)
def _mesh_lanes_ragged_jit(mesh, backend, window, tr, rows, combines):
    """RaggedFuse under the mesh: ONE shard_map step for ALL groups.

    Same SPMD schedule as :func:`_mesh_lanes_jit` — per-device ELL block,
    lane-state all-gather, single-device lane bodies — but the lane axis
    carries every group at once with a replicated combine-id vector, and
    the step computes each combine arm's accumulator then keeps the arm
    each lane selects.  The per-backend bodies are EXACTLY the ones the
    per-group mesh path vmaps, so each lane's accumulator is bitwise the
    multi path's.  Padding lanes match no arm: their accumulator rows and
    identity entries both stay zero, so the psum'd touched count (the SPMD
    activity proxy) is unpolluted.
    """
    axes = tuple(mesh.axis_names)
    bodies = [_mesh_body(backend, window, tr, rows, c) for c in combines]

    def step(idx, mask, seg, tw, cids, msgs_local):
        idx, mask, seg, tw = idx[0], mask[0], seg[0], tw[0]
        msgs = jax.lax.all_gather(msgs_local, axes, axis=1, tiled=True)
        acc = jnp.zeros((msgs.shape[0], rows), msgs.dtype)
        ident_vec = jnp.zeros((msgs.shape[0],), msgs.dtype)
        for ci, combine in enumerate(combines):
            acc_c = jax.vmap(bodies[ci], in_axes=(None, None, None, None, 0))(
                idx, mask, seg, tw, msgs
            )
            sel = cids == ci
            acc = jnp.where(sel[:, None], acc_c, acc)
            ident_vec = jnp.where(
                sel, jnp.asarray(IDENTITY[combine], msgs.dtype), ident_vec
            )
        touched = jax.lax.psum((acc != ident_vec[:, None]).sum(), axes)
        return acc[None], touched

    return _mesh_jit(
        mesh, step, _DEVICE_AXES + (("lane",), ("lane", "vertex"))
    )


def mesh_lane_cols(n_pad_v: int, mesh) -> int:
    """Lane-state columns under ``mesh``: the window-padded vertex count,
    padded again to a multiple of the device count so the vertex axis
    shards evenly (the tail past ``n_pad_v`` is never addressed by a valid
    slot)."""
    n_dev = int(mesh.devices.size)
    return -(-n_pad_v // n_dev) * n_dev


def mesh_ragged_stage_lanes(msgs_by_group, combines: Sequence[str],
                            n_pad_v: int, mesh):
    """Mesh variant of :func:`ragged_stage_lanes`: the lane matrix, at
    :func:`mesh_lane_cols` columns, is staged straight onto its vertex
    shards."""
    return ragged_stage_lanes(msgs_by_group, combines,
                              mesh_lane_cols(n_pad_v, mesh), mesh=mesh)


def mesh_ragged_launch(batches, staged, first, rows_pad, lane_ctx, *, mesh,
                       backend: str = "pallas"):
    """Launch ONE SPMD step covering every group for a staged device round
    (:func:`pack_device_batches` + :func:`put_device_batches`).

    Returns an opaque handle for :func:`mesh_ragged_collect`; the
    accumulator is left unforced so the caller can stage the next round's
    host decode while the step is in flight.
    """
    fn = _mesh_lanes_ragged_jit(
        mesh, backend, first.window, first.tr, rows_pad, lane_ctx["combines"]
    )
    acc_all, touched = fn(*staged, lane_ctx["cids"], lane_ctx["msgs"])
    return {
        "batches": batches,
        "n_dev": int(mesh.devices.size),
        "acc": acc_all,
        "touched": touched,
        "slices": lane_ctx["slices"],
    }


def mesh_ragged_collect(handle):
    """Force a mesh ragged handle into ``(accs_by_group, touched_total)``
    where ``accs_by_group[g][d]`` lists per-shard ``[K_g, rows]``
    accumulators (empty for idle devices)."""
    acc_all = np.asarray(handle["acc"])
    batches, n_dev = handle["batches"], handle["n_dev"]
    accs_by_group = [
        [
            batches[d][0].split(acc_all[d][sl]) if d in batches else []
            for d in range(n_dev)
        ]
        for sl in handle["slices"]
    ]
    return accs_by_group, int(handle["touched"])


def ell_update_lanes_mesh_ragged(
    device_ells: Sequence[Sequence[EllShard]],
    msgs_by_group: Sequence[np.ndarray],  # each [K_g, |V|]
    combines: Sequence[str],
    *,
    mesh,
    backend: str = "pallas",
):
    """Mesh RaggedFuse entry point: 1 host read, ONE SPMD step, D device
    slices — where :func:`ell_update_lanes_mesh_multi` pays G steps.

    Returns ``(accs_by_group, touched_total)``; accumulators are bitwise
    the multi path's per group.  ``touched_total`` is one psum over all
    groups (the per-launch activity proxy replaces the per-group one).
    """
    if len(msgs_by_group) != len(combines):
        raise ValueError("one combine per message group")
    for msgs in msgs_by_group:
        if msgs.ndim != 2:
            raise ValueError(
                f"lane update needs [lanes, |V|] messages, got {msgs.shape}"
            )
    first = next((ells[0] for ells in device_ells if len(ells)), None)
    if first is None:
        return [[[] for _ in device_ells] for _ in msgs_by_group], 0
    lane_ctx = mesh_ragged_stage_lanes(
        msgs_by_group, combines, first.num_windows * first.window, mesh
    )
    handle = mesh_ragged_launch(
        *_stage_device_batches(device_ells, mesh), lane_ctx, mesh=mesh,
        backend=backend,
    )
    return mesh_ragged_collect(handle)
