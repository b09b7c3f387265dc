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

``ell_update_lanes_ragged`` is the lane entry point: every lane of every
fusion group in ONE launch, each lane bitwise a solo :func:`ell_update`
with its own combine (DESIGN.md §14).  The mesh step
(:func:`mesh_ragged_launch`) runs the same lanes as one SPMD program.
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
    jax.jit, static_argnames=("window", "tr", "rows", "combine")
)
def _update_jit(
    ell_idx, ell_valid, seg, tile_window, msgs,
    *, window, tr, rows, combine,
):
    part = K.ell_partials_masked(
        ell_idx, ell_valid, tile_window, msgs,
        window=window, tr=tr, combine=combine,
    )
    return _segment_combine(part, seg, rows, combine)


def ell_update(
    ell: EllShard,
    msgs: np.ndarray,
    combine: str,
) -> jax.Array:
    """acc[rows] for one shard.  msgs is the full |V| message array."""
    msgs_p = np.zeros(ell.num_windows * ell.window, msgs.dtype)
    msgs_p[: msgs.shape[0]] = msgs
    return _update_jit(
        jnp.asarray(ell.ell_idx), jnp.asarray(ell.ell_mask),
        jnp.asarray(ell.seg), jnp.asarray(ell.tile_window),
        jnp.asarray(msgs_p),
        window=ell.window, tr=ell.tr, rows=ell.rows, combine=combine,
    )


def _prep_batch(ells: Sequence[EllShard]):
    """Concatenate + shape-bucket a shard batch (shared by the single-query,
    ragged and mesh entry points so the padding discipline can't drift)."""
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
        combine=combine,
    )
    return batch.split(np.asarray(acc))


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
    ``jnp.where``.  The contract (DESIGN.md §14): each lane's row is
    bitwise a solo run of that lane's program, :func:`_update_jit` on its
    message row with its own combine — the same masked partials and the
    same segment combine.  Its prologue builds the kernel's per-tile row
    lists from the launched slots, so each tile runs only the gather rounds
    its valid slots read.
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
    ONE ragged launch (DESIGN.md §14) — the one-call form of what the lane
    executor stages, launches and collects.

    Groups are concatenated along the lane axis with a per-lane combine-id
    vector; the kernel selects the combine arm per lane, and lane padding
    is per launch (see :func:`repro.core.csr.ragged_lane_pad`).  Each
    lane's row is bitwise a solo :func:`ell_update` of that lane.
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
    lane_ctx = ragged_lanes_put(
        ragged_lane_concat(msgs_by_group, combines, n_cols=n_pad_v))
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
      exact function the single-device jnp path jits,
    - ``backend="pallas"``: ``K.ell_partials_masked`` + the segment combine
      — the exact computation of :func:`_update_jit`.
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


def pack_device_batches(device_ells, n_dev: int):
    """Concatenate every device's shard batch with the single-device
    :func:`_prep_batch` discipline and pad them to COMMON (pow2-bucketed)
    shapes, so the round is one SPMD program — host work only.  The common
    padding is the usual identity padding, so each shard's accumulator is
    bitwise what a single-device launch computes for its device's batch
    alone.

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


@functools.lru_cache(maxsize=32)
def _mesh_lanes_ragged_jit(mesh, backend, window, tr, rows, combines):
    """RaggedFuse under the mesh: ONE shard_map step for ALL groups.

    Device ``d`` receives its own stacked ELL block (leading axis sharded
    over every mesh axis) plus its slice of the lane-message matrix, and
    all-gathers the full message array (the SEM working set, DESIGN.md
    §10).  The lane axis carries every group at once with a replicated
    combine-id vector; the step vmaps each combine arm's single-device body
    (:func:`_mesh_body`) over the lanes and keeps the arm each lane
    selects.  Each destination row belongs to exactly one device, so each
    lane's accumulator is bitwise a solo run of that lane's program: the
    :func:`_mesh_body` its combine selects, on the masked path.  Padding
    lanes match no arm: their accumulator rows and identity entries both
    stay zero, so the psum'd touched count (the SPMD activity proxy) is
    unpolluted.
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

