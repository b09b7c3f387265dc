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

Three variants:
- ``masked``  (paper-faithful layout): validity carried as a bool tile.
- ``sentinel``: invalid slots point at an identity-filled pad appended to
  each window of the table — no mask tile at all, cutting streamed edge
  bytes by the full mask plane.
- ``ragged``: the masked kernel over many lanes (DESIGN.md §14).  The grid
  is ``(n_lanes / LB, n_tiles)``: one step holds one tile's index and mask
  blocks and an ``(LB, W / L, L)`` block of its lanes' windows, and splits
  the indices once.  A ``fori_loop`` over groups of up to
  :data:`LANE_GROUP` lanes runs each group's gathers interleaved, round by
  round: a round's lane permute is its slowest op, and a lone lane leaves
  the permute unit waiting on its loads and selects.  The rounds run
  :data:`ROWS_PER_ITER` to a loop iteration, which keeps the body, and
  its compile time, near a lone lane's.  ``LB`` is every lane when their
  double-buffered windows fit :data:`RAGGED_WINDOW_VMEM`, else the largest
  divisor of the lane count that fits.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
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

#: Gather rounds per loop iteration of the ragged kernel.  With a group of
#: lanes interleaved a loop over the rows costs little, and it bounds the
#: body to compile to ``LANE_GROUP x ROWS_PER_ITER`` gathers.
ROWS_PER_ITER = 32

#: VMEM for the double-buffered message windows of one ragged grid step:
#: 64 lanes at W=16384, well inside a v5e's default scoped VMEM.
RAGGED_WINDOW_VMEM = 8 << 20


def table_lanes(window: int) -> int:
    """Lanes per table row: 128 for any window that is a multiple of 128."""
    return math.gcd(window, 128)


def sentinel_pad(window: int) -> int:
    """Identity slots appended per window by the sentinel layout: one
    ``(8, L)`` tile, so the extended table keeps 8-row alignment."""
    return 8 * table_lanes(window)


def _table(msgs: jax.Array, window: int) -> jax.Array:
    """``[..., nw * window]`` messages -> ``[..., nw * window / L, L]``."""
    lanes = table_lanes(window)
    return msgs.reshape(*msgs.shape[:-1], -1, lanes)


def _split(idx_ref, lanes: int):
    """A ``(TR, K)`` tile of window-local indices -> ``(hi, lo)``: the
    table row each index reads and its lane within that row."""
    idx = idx_ref[...].astype(jnp.int32)
    return idx >> (lanes.bit_length() - 1), idx & (lanes - 1)


def _gather(tabs, hi: jax.Array, lo: jax.Array, rows_per_iter=None) -> list:
    """``table[idx]`` from each resident ``(rows, L)`` window table in
    ``tabs``, for a tile split by :func:`_split`.

    Round ``j`` keeps row ``j``'s lane-wise gather where ``hi == j``.  The
    tables' gathers within a round are independent: interleaved, the lane
    permute each one needs (the slowest op of a round) overlaps the
    others' loads and selects, and the tables share the ``hi == j`` mask.
    The rounds are unrolled (the rows' gathers are independent, so the
    compiler can overlap them; one round per loop iteration waits out the
    gather's latency), all of them, or ``rows_per_iter`` per iteration of
    a loop over the rows, which bounds the body to compile.
    """
    rows = tabs[0].shape[0]
    n = hi.shape[0]
    step = rows if rows_per_iter is None else _divisor_at_most(
        rows, rows_per_iter)

    def rounds(c, gs):
        gs = list(gs)
        for r in range(step):
            j = c * step + r
            hit = hi == j
            for t, tab in enumerate(tabs):
                row = jnp.broadcast_to(tab[pl.ds(j, 1), :], (n, tab.shape[1]))
                gj = jnp.take_along_axis(row, lo, axis=1,
                                         mode="promise_in_bounds")
                gs[t] = jnp.where(hit, gj, gs[t])
        return tuple(gs)

    gs = tuple(jnp.zeros(hi.shape, t.dtype) for t in tabs)
    if step == rows:
        return list(rounds(0, gs))
    return list(jax.lax.fori_loop(0, rows // step, rounds, gs))


def _divisor_at_most(n: int, cap: int) -> int:
    """The largest divisor of ``n`` that is at most ``cap`` (at least 1)."""
    return max(d for d in range(1, min(n, max(cap, 1)) + 1) if n % d == 0)


def _over_tile_chunks(call, tile_window, tr, *row_arrays):
    """``call(tile_window, *row_arrays)`` over chunks of at most
    :data:`MAX_TILES_PER_CALL` tiles, partials concatenated on the last
    axis.  ``row_arrays`` are indexed by ELL row (``tr`` rows per tile)."""
    n_tiles = tile_window.shape[0]
    if n_tiles <= MAX_TILES_PER_CALL:
        return call(tile_window, *row_arrays)
    return jnp.concatenate([
        call(tile_window[a:a + MAX_TILES_PER_CALL],
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

    return _over_tile_chunks(call, tile_window, tr, ell_idx, ell_valid)


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


def _ragged_kernel(combines, tile_window_ref, combine_ids_ref, idx_ref,
                   valid_ref, tab_ref, out_ref):
    """One (TR, K) tile for a block of ``LB`` lanes: split the indices
    once, then per group of lanes gather (their rounds interleaved), and
    per lane reduce per combine arm and keep the arm that lane's
    ``combine_id`` selects.

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

    # A loop over groups, not unrolled: every lane unrolled into one body
    # would multiply the code to compile.
    def lanes(i, carry):
        l0 = i * group
        gs = _gather([tab_ref.at[l0 + a] for a in range(group)], hi, lo,
                     ROWS_PER_ITER)
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
    combine_ids: jax.Array,  # [n_lanes] int32 arm index per lane
    msgs: jax.Array,  # [n_lanes, num_windows * window] ragged lane state
    *,
    window: int,
    tr: int,
    combines: tuple,  # deduplicated combine arms, static
) -> jax.Array:
    """Per-ELL-row partials for ALL lanes of ALL fusion groups, [n_lanes,
    n_ell] — ONE launch where the multi path pays G (DESIGN.md §14).

    The grid is ``(n_lanes / LB, n_tiles)`` with ``LB`` from
    :func:`ragged_lane_block`: one step holds one tile's index and mask
    blocks and ``LB`` lanes' windows, and loops over those lanes in-kernel.
    A second prefetched scalar vector carries each lane's combine-arm id so
    the selection happens in-kernel instead of at launch granularity.
    """
    k = ell_idx.shape[1]
    n_lanes = msgs.shape[0]
    lb = ragged_lane_block(n_lanes, window, msgs.dtype.itemsize)
    tab = _table(msgs, window)
    rows, lanes = window // tab.shape[-1], tab.shape[-1]

    def call(tw, idx, valid):
        n_tiles = tw.shape[0]
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_lanes // lb, n_tiles),
            in_specs=[
                pl.BlockSpec((tr, k), lambda b, i, tw, cid: (i, 0)),
                pl.BlockSpec((tr, k), lambda b, i, tw, cid: (i, 0)),
                # Sliding window per lane block: one window of each of the
                # block's lanes' message tables resident per grid step.
                pl.BlockSpec((lb, rows, lanes),
                             lambda b, i, tw, cid: (b, tw[i], 0)),
            ],
            out_specs=pl.BlockSpec((lb, None, 1, tr),
                                   lambda b, i, tw, cid: (b, i, 0, 0)),
        )
        out = pl.pallas_call(
            functools.partial(_ragged_kernel, combines),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((n_lanes, n_tiles, 1, tr),
                                           msgs.dtype),
            name="ell_partials_ragged",
            interpret=not kernels.pallas_compiled(),
        )(tw, combine_ids, idx, valid, tab)
        return out.reshape(n_lanes, n_tiles * tr)

    return _over_tile_chunks(call, tile_window, tr, ell_idx, ell_valid)


# -------------------------------------------------------------- sentinel
def _sentinel_kernel(combine: str, tile_window_ref, idx_ref, tab_ref, out_ref):
    """No mask plane: padding slots index the identity pad of the table."""
    (g,) = _gather([tab_ref], *_split(idx_ref, tab_ref.shape[-1]))
    out_ref[...] = _reduce(g, combine)[None]


@functools.partial(jax.jit, static_argnames=("window", "tr", "combine"))
def ell_partials_sentinel(
    ell_idx: jax.Array,  # [n_ell, K] indices into the EXTENDED window
    tile_window: jax.Array,
    msgs_ext: jax.Array,  # [num_windows * window] identity-padded windows
    *,
    window: int,  # EXTENDED window size (W + sentinel_pad(W))
    tr: int,
    combine: str,
) -> jax.Array:
    k = ell_idx.shape[1]
    tab = _table(msgs_ext, window)
    rows, lanes = window // tab.shape[-1], tab.shape[-1]

    def call(tw, idx):
        n_tiles = tw.shape[0]
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_tiles,),
            in_specs=[
                pl.BlockSpec((tr, k), lambda i, tw: (i, 0)),
                pl.BlockSpec((rows, lanes), lambda i, tw: (tw[i], 0)),
            ],
            out_specs=pl.BlockSpec((None, 1, tr), lambda i, tw: (i, 0, 0)),
        )
        out = pl.pallas_call(
            functools.partial(_sentinel_kernel, combine),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((n_tiles, 1, tr), msgs_ext.dtype),
            name="ell_partials_sentinel",
            interpret=not kernels.pallas_compiled(),
        )(tw, idx, tab)
        return out.reshape(n_tiles * tr)

    return _over_tile_chunks(call, tile_window, tr, ell_idx)
