"""Pallas TPU kernel: windowed row-split ELL pull-update (the VSW hot loop).

Schedule (the kernel-level vertex-centric sliding window, DESIGN.md §2):

- grid = (n_tiles,): one step per (TR, K) tile of ELL rows.
- scalar prefetch carries ``tile_window[n_tiles]``; the BlockSpec index map
  of the message table reads it, so each grid step DMAs exactly ONE window
  of the source-message array HBM->VMEM — the sliding window over source
  vertices.  Pallas skips the copy when consecutive steps map to the same
  block, so tiles sharing a window reuse the resident slice and the DMA of
  the next window overlaps the current tile's compute.
- the window is laid out as a 2-D ``(W / L, L)`` table of ``L``-lane rows
  (``L = 128`` at the default widths).  The in-VMEM gather ``table[idx]``
  is written in a form Mosaic lowers: a lane-wise ``take_along_axis`` on
  ``idx & (L - 1)`` over each table row, kept where ``idx >> log2(L)``
  selects that row — ``W / L`` compare/select rounds per tile.
- masked lane reduction -> one partial per ELL row, written as a ``(1, TR)``
  block of an ``(n_tiles, 1, TR)`` output (the block's last two dims equal
  the array's, which Mosaic accepts for any TR).
- the tiny ``seg`` combine (partials -> rows) stays in XLA (ops.py): it is
  O(|E|/K) work, not worth a hand-written scatter.

These kernels compile for a v5e at TR=8, K=128, W=16384
(``tests/test_chip_compile.py``); the gather's ``take_along_axis`` needs
``K == L`` there.  Off the TPU they run in the Pallas interpreter
(:func:`repro.kernels.pallas_compiled`), where any K and W work.

Two variants:
- ``masked``  (paper-faithful layout): validity carried as a bool tile.
- ``ragged``: the masked kernel over many lanes (DESIGN.md §14).  The grid
  is ``(n_lanes / LB, n_tiles)``: one step holds one tile's index and mask
  blocks and an ``(LB, W / L, L)`` block of its lanes' windows, and splits
  the indices once.  A ``fori_loop`` over groups of up to
  :data:`LANE_GROUP` lanes runs each group's gathers interleaved, round by
  round: a round's lane permute is its slowest op, and a lone lane leaves
  the permute unit waiting on its loads and selects.  A tile runs rounds
  only for the table rows its valid slots read: :func:`ragged_row_table`
  lists them per tile (a jitted prologue, before the launch), the
  iteration count arrives by scalar prefetch and the tile's row list as
  an SMEM block, and the rounds run :data:`ROWS_PER_ITER` to a loop
  iteration, which keeps the body, and its compile time, near a lone
  lane's.  A tile with no valid slot runs none.  ``LB`` is every lane
  when their double-buffered windows fit :data:`RAGGED_WINDOW_VMEM`, else
  the largest divisor of the lane count that fits.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import kernels

IDENTITY = {"sum": 0.0, "min": float("inf"), "max": float("-inf")}

#: Tiles per ``pallas_call``.  Scalar prefetch keeps ``tile_window`` in
#: SMEM (1 MiB on a v5e, which a 256K-tile map overflows), so longer grids
#: are launched in chunks of this many tiles; tiles are independent, so the
#: concatenated partials are exactly those of one launch.
MAX_TILES_PER_CALL = 1 << 17

#: Lanes whose gather rounds the ragged kernel interleaves (at most; a
#: divisor of the lane block).
LANE_GROUP = 8

#: Gather rounds per loop iteration of the ragged kernel's row loop.  With
#: a group of lanes interleaved a loop over the rows costs little, and it
#: bounds the body to compile to ``LANE_GROUP x ROWS_PER_ITER`` gathers.
ROWS_PER_ITER = 16

#: VMEM for the double-buffered message windows of one ragged grid step:
#: 64 lanes at W=16384, well inside a v5e's default scoped VMEM.
RAGGED_WINDOW_VMEM = 8 << 20


def table_lanes(window: int) -> int:
    """Lanes per table row: 128 for any window that is a multiple of 128."""
    return math.gcd(window, 128)


def _table(msgs: jax.Array, window: int) -> jax.Array:
    """``[..., nw * window]`` messages -> ``[..., nw * window / L, L]``."""
    lanes = table_lanes(window)
    return msgs.reshape(*msgs.shape[:-1], -1, lanes)


def _split(idx_ref, lanes: int):
    """A ``(TR, K)`` tile of window-local indices -> ``(hi, lo)``: the
    table row each index reads and its lane within that row."""
    idx = idx_ref[...].astype(jnp.int32)
    return idx >> (lanes.bit_length() - 1), idx & (lanes - 1)


def _gather(tabs, hi: jax.Array, lo: jax.Array, listed=None) -> list:
    """``table[idx]`` from each resident ``(rows, L)`` window table in
    ``tabs``, for a tile split by :func:`_split`.

    Round ``j`` keeps row ``j``'s lane-wise gather where ``hi == j``.  The
    tables' gathers within a round are independent: interleaved, the lane
    permute each one needs (the slowest op of a round) overlaps the
    others' loads and selects, and the tables share the ``hi == j`` mask.

    With ``listed`` None every row gets a round, all unrolled (the rows'
    gathers are independent, so the compiler can overlap them; one round
    per loop iteration waits out the gather's latency).  With ``listed =
    (rows_ref, n_iter)`` only the rows the tile's row list names get one:
    a loop of ``n_iter`` iterations of ``step`` (:func:`_rows_per_iter`)
    unrolled rounds each, round ``r`` of iteration ``c`` at row
    ``rows_ref[0, c * step + r]`` (:func:`ragged_row_table`).  A slot whose
    row is not listed keeps the zero init.
    """
    n = hi.shape[0]

    def rounds(row_ids, gs):
        gs = list(gs)
        for j in row_ids:
            hit = hi == j
            for t, tab in enumerate(tabs):
                row = jnp.broadcast_to(tab[pl.ds(j, 1), :], (n, tab.shape[1]))
                gj = jnp.take_along_axis(row, lo, axis=1,
                                         mode="promise_in_bounds")
                gs[t] = jnp.where(hit, gj, gs[t])
        return tuple(gs)

    gs = tuple(jnp.zeros(hi.shape, t.dtype) for t in tabs)
    if listed is None:
        return list(rounds(range(tabs[0].shape[0]), gs))
    rows_ref, n_iter = listed
    step = _rows_per_iter(tabs[0].shape[0])
    return list(jax.lax.fori_loop(
        0, n_iter,
        lambda c, gs: rounds([rows_ref[0, c * step + r] for r in range(step)],
                             gs),
        gs))


def _divisor_at_most(n: int, cap: int) -> int:
    """The largest divisor of ``n`` that is at most ``cap`` (at least 1)."""
    return max(d for d in range(1, min(n, max(cap, 1)) + 1) if n % d == 0)


def _over_tile_chunks(call, tile_arrays, tr, *row_arrays):
    """``call(*tile_arrays, *row_arrays)`` over chunks of at most
    :data:`MAX_TILES_PER_CALL` tiles, partials concatenated on the last
    axis.  ``tile_arrays`` are indexed by tile (``tile_window`` first),
    ``row_arrays`` by ELL row (``tr`` rows per tile)."""
    n_tiles = tile_arrays[0].shape[0]
    if n_tiles <= MAX_TILES_PER_CALL:
        return call(*tile_arrays, *row_arrays)
    return jnp.concatenate([
        call(*(x[a:a + MAX_TILES_PER_CALL] for x in tile_arrays),
             *(x[a * tr:(a + MAX_TILES_PER_CALL) * tr] for x in row_arrays))
        for a in range(0, n_tiles, MAX_TILES_PER_CALL)
    ], axis=-1)


def _reduce(g: jax.Array, combine: str) -> jax.Array:
    if combine == "sum":
        if kernels.pallas_compiled():
            return g.sum(axis=1)
        # The interpreter runs on XLA:CPU, whose order of summation follows
        # its fusion choices, so one row could sum differently in two
        # kernels.  Spelled out in slot order, every kernel sums alike;
        # Mosaic's lane reduction has a single order.
        acc = g[:, 0]
        for j in range(1, g.shape[1]):
            acc = acc + g[:, j]
        return acc
    if combine == "min":
        return g.min(axis=1)
    return g.max(axis=1)


# ---------------------------------------------------------------- masked
def _masked_kernel(combine: str, tile_window_ref, idx_ref, valid_ref, tab_ref,
                   out_ref):
    """One (TR, K) tile: gather from the resident window table, mask, reduce."""
    (g,) = _gather([tab_ref], *_split(idx_ref, tab_ref.shape[-1]))
    ident = jnp.asarray(IDENTITY[combine], g.dtype)
    g = jnp.where(valid_ref[...], g, ident)
    out_ref[...] = _reduce(g, combine)[None]


@functools.partial(jax.jit, static_argnames=("window", "tr", "combine"))
def ell_partials_masked(
    ell_idx: jax.Array,  # [n_ell, K] int16/int32 window-local
    ell_valid: jax.Array,  # [n_ell, K] bool
    tile_window: jax.Array,  # [n_tiles] int32
    msgs: jax.Array,  # [num_windows * window]
    *,
    window: int,
    tr: int,
    combine: str,
) -> jax.Array:
    """Per-ELL-row partial reductions, [n_ell]."""
    k = ell_idx.shape[1]
    tab = _table(msgs, window)
    rows, lanes = window // tab.shape[-1], tab.shape[-1]

    def call(tw, idx, valid):
        n_tiles = tw.shape[0]
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_tiles,),
            in_specs=[
                pl.BlockSpec((tr, k), lambda i, tw: (i, 0)),
                pl.BlockSpec((tr, k), lambda i, tw: (i, 0)),
                # THE sliding window: block index comes from the prefetched
                # tile->window map, one window of msgs resident per step.
                pl.BlockSpec((rows, lanes), lambda i, tw: (tw[i], 0)),
            ],
            out_specs=pl.BlockSpec((None, 1, tr), lambda i, tw: (i, 0, 0)),
        )
        out = pl.pallas_call(
            functools.partial(_masked_kernel, combine),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((n_tiles, 1, tr), msgs.dtype),
            name="ell_partials_masked",
            interpret=not kernels.pallas_compiled(),
        )(tw, idx, valid, tab)
        return out.reshape(n_tiles * tr)

    return _over_tile_chunks(call, (tile_window,), tr, ell_idx, ell_valid)


# --------------------------------------------------------------- ragged
def ragged_lane_block(n_lanes: int, window: int, itemsize: int = 4) -> int:
    """Lanes per ragged grid step: every lane where their double-buffered
    window blocks (``2 * LB * window * itemsize`` bytes) fit
    :data:`RAGGED_WINDOW_VMEM`, else the largest divisor of ``n_lanes``
    that fits (at least 1)."""
    return _divisor_at_most(n_lanes,
                            RAGGED_WINDOW_VMEM // (2 * window * itemsize))


def ragged_grid_steps(n_lanes: int, n_tiles: int, window: int,
                      itemsize: int = 4) -> int:
    """Grid steps of one :func:`ell_partials_ragged` launch: lane blocks
    times tiles."""
    return n_lanes // ragged_lane_block(n_lanes, window, itemsize) * n_tiles


def _rows_per_iter(rows: int) -> int:
    """Rounds per iteration of the ragged kernel's row loop over a window
    table of ``rows`` rows: :data:`ROWS_PER_ITER`, or the largest divisor of
    ``rows`` below it."""
    return _divisor_at_most(rows, ROWS_PER_ITER)


def ragged_rows_read(ell_idx, ell_valid, *, window: int, tr: int):
    """Host (numpy): ``[n_tiles, W / L]`` bool, whether a valid slot of
    tile ``t`` reads window-table row ``j``; one ``bincount`` over ``t *
    (W / L) + (idx >> log2(L))`` of the valid slots.  The oracle of
    :func:`ragged_row_table` and the source of :func:`ragged_rounds`."""
    lanes = table_lanes(window)
    rows = window // lanes
    n_tiles = ell_idx.shape[0] // tr
    slots = np.flatnonzero(ell_valid)
    hi = ell_idx.reshape(-1)[slots].astype(np.int64) >> (lanes.bit_length()
                                                         - 1)
    return np.bincount(slots // (tr * ell_idx.shape[1]) * rows + hi,
                       minlength=n_tiles * rows).reshape(n_tiles, rows) > 0


def ragged_rounds(rows_read) -> int:
    """Gather rounds each lane runs in one :func:`ell_partials_ragged`
    launch, from :func:`ragged_rows_read`: the sum over tiles of ``n_iter``
    times the rounds per iteration."""
    step = _rows_per_iter(rows_read.shape[1])
    return int((-(-rows_read.sum(axis=1) // step)).sum()) * step


def ragged_row_table(ell_idx: jax.Array, ell_valid: jax.Array, *,
                     window: int, tr: int):
    """The ragged kernel's per-tile row lists, on the device.

    ``tile_rows[t]`` lists, ascending, the window-table rows
    (``idx >> log2(L)``) that tile ``t``'s valid slots read, padded to
    whole loop iterations by repeating the last listed row (a repeated
    round selects the same values again: bitwise harmless);
    ``n_iter[t] = ceil(count / step)`` (:func:`_rows_per_iter`), 0 for a
    tile with no valid slot.  Compares and reductions only, no scatter or
    sort: presence as one bit per row in 32-bit words, OR-reduced over the
    tile's slots; then entry ``p`` is the number of rows whose running
    count of listed rows is at most ``p``.  :func:`ragged_rows_read` is
    the host form of its row sets.

    Returns ``(n_iter [n_tiles] int32, tile_rows [n_tiles, W / L] int32)``.
    """
    lanes = table_lanes(window)
    rows = window // lanes
    n_tiles = ell_idx.shape[0] // tr
    hi = (ell_idx.astype(jnp.int32) >> (lanes.bit_length() - 1)).reshape(
        n_tiles, -1)
    valid = ell_valid.reshape(n_tiles, -1)
    # bit (hi & 31) of word (hi >> 5) marks row hi as read
    word = valid[:, :, None] & (hi[:, :, None] >> 5 == jnp.arange(
        -(-rows // 32), dtype=jnp.int32))
    words = jax.lax.reduce(
        jnp.where(word, jnp.left_shift(jnp.int32(1), hi & 31)[:, :, None], 0),
        jnp.int32(0), jax.lax.bitwise_or, (1,))
    present = (words[:, :, None] >> jnp.arange(32, dtype=jnp.int32)
               & 1).reshape(n_tiles, -1)[:, :rows]
    # running count of listed rows, in log2(rows) shifted adds (XLA's
    # cumsum compiles for seconds on the TPU)
    seen, k = present, 1
    while k < rows:
        seen = seen + jnp.pad(seen[:, :-k], ((0, 0), (k, 0)))
        k *= 2
    ids = jnp.arange(rows, dtype=jnp.int32)
    table = (seen[:, None, :] <= ids[:, None]).sum(axis=2, dtype=jnp.int32)
    last = jnp.max(jnp.where(present > 0, ids, 0), axis=1)
    step = _rows_per_iter(rows)
    return (seen[:, -1] + step - 1) // step, jnp.minimum(table, last[:, None])


def _ragged_kernel(combines, tile_window_ref, n_iter_ref, combine_ids_ref,
                   idx_ref, valid_ref, rows_ref, tab_ref, out_ref):
    """One (TR, K) tile for a block of ``LB`` lanes: split the indices
    once, then per group of lanes gather (their rounds interleaved, over
    the tile's listed rows only), and per lane reduce per combine arm and
    keep the arm that lane's ``combine_id`` selects.

    ``jnp.where`` returns the selected arm's value bit-for-bit, so each lane
    is op-for-op identical to a solo ``_masked_kernel`` launch with its own
    combine — the bitwise contract survives the fusion.  Padding lanes carry
    an out-of-range id that matches no arm and stay at the zero init.
    """
    lb = tab_ref.shape[0]
    group = _divisor_at_most(lb, LANE_GROUP)
    hi, lo = _split(idx_ref, tab_ref.shape[-1])
    valid = valid_ref[...]
    lane0 = pl.program_id(0) * lb
    listed = (rows_ref, n_iter_ref[pl.program_id(1)])

    # A loop over groups, not unrolled: every lane unrolled into one body
    # would multiply the code to compile.
    def lanes(i, carry):
        l0 = i * group
        gs = _gather([tab_ref.at[l0 + a] for a in range(group)], hi, lo,
                     listed)
        for a, g in enumerate(gs):
            cid = combine_ids_ref[lane0 + l0 + a]
            out = jnp.zeros((g.shape[0],), g.dtype)
            for ci, combine in enumerate(combines):
                ident = jnp.asarray(IDENTITY[combine], g.dtype)
                gc = jnp.where(valid, g, ident)
                out = jnp.where(cid == ci, _reduce(gc, combine), out)
            out_ref[l0 + a] = out[None]
        return carry

    jax.lax.fori_loop(0, lb // group, lanes, 0)


@functools.partial(jax.jit, static_argnames=("window", "tr", "combines"))
def ell_partials_ragged(
    ell_idx: jax.Array,  # [n_ell, K] int16/int32 window-local
    ell_valid: jax.Array,  # [n_ell, K] bool
    tile_window: jax.Array,  # [n_tiles] int32
    n_iter: jax.Array,  # [n_tiles] int32 row-loop iterations per tile
    tile_rows: jax.Array,  # [n_tiles, W / L] int32 listed table rows
    combine_ids: jax.Array,  # [n_lanes] int32 arm index per lane
    msgs: jax.Array,  # [n_lanes, num_windows * window] ragged lane state
    *,
    window: int,
    tr: int,
    combines: tuple,  # deduplicated combine arms, static
) -> jax.Array:
    """Per-ELL-row partials for ALL lanes of ALL fusion groups, [n_lanes,
    n_ell], from ONE launch (DESIGN.md §14).

    The grid is ``(n_lanes / LB, n_tiles)`` with ``LB`` from
    :func:`ragged_lane_block`: one step holds one tile's index and mask
    blocks and ``LB`` lanes' windows, and loops over those lanes in-kernel.
    ``n_iter`` and ``tile_rows`` come from :func:`ragged_row_table`: the
    iteration counts arrive by scalar prefetch beside ``tile_window`` and
    each lane's combine-arm id, and a step's row list as an SMEM block (the
    whole table would not fit SMEM).
    """
    k = ell_idx.shape[1]
    n_lanes = msgs.shape[0]
    lb = ragged_lane_block(n_lanes, window, msgs.dtype.itemsize)
    tab = _table(msgs, window)
    rows, lanes = window // tab.shape[-1], tab.shape[-1]

    def call(tw, nit, trows, idx, valid):
        n_tiles = tw.shape[0]
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_lanes // lb, n_tiles),
            in_specs=[
                pl.BlockSpec((tr, k), lambda b, i, *_: (i, 0)),
                pl.BlockSpec((tr, k), lambda b, i, *_: (i, 0)),
                pl.BlockSpec((None, 1, rows), lambda b, i, *_: (i, 0, 0),
                             memory_space=pltpu.SMEM),
                # Sliding window per lane block: one window of each of the
                # block's lanes' message tables resident per grid step.
                pl.BlockSpec((lb, rows, lanes),
                             lambda b, i, tw, *_: (b, tw[i], 0)),
            ],
            out_specs=pl.BlockSpec((lb, None, 1, tr),
                                   lambda b, i, *_: (b, i, 0, 0)),
        )
        out = pl.pallas_call(
            functools.partial(_ragged_kernel, combines),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((n_lanes, n_tiles, 1, tr),
                                           msgs.dtype),
            name="ell_partials_ragged",
            interpret=not kernels.pallas_compiled(),
        )(tw, nit, combine_ids, idx, valid, trows[:, None], tab)
        return out.reshape(n_lanes, n_tiles * tr)

    return _over_tile_chunks(call, (tile_window, n_iter, tile_rows), tr,
                             ell_idx, ell_valid)

