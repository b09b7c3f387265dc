"""CSR -> blocked-ELL conversion: the TPU-native shard format.

TPUs have no efficient scalar row-pointer walk, so the paper's CSR layout is
re-blocked at preprocessing time into a *windowed, row-split ELL* format that
a Pallas kernel can stream tile-by-tile (HBM->VMEM) — the kernel-level
analogue of the paper's vertex-centric sliding window:

- **Source windows**: edges are grouped by ``window = src // W``.  While a
  tile is processed, only the ``W``-wide slice of the source-value array is
  resident in VMEM, so the in-kernel gather hits a small local table.  With
  ``W <= 2**15`` the column indices fit ``int16`` — this *is* the on-device
  variant of the paper's compressed edge cache (half the index bytes).
- **Row splitting**: a destination with in-degree ``d`` inside one window
  becomes ``ceil(d / K)`` ELL rows of width ``K``; a ``seg`` array maps each
  ELL row back to its local destination row.  Partial reductions per ELL row
  are segment-combined afterwards (associative combine: sum/min/max), which
  keeps tiles dense regardless of degree skew — crucial for power-law graphs
  whose max in-degree (e.g. 20M in EU-2015) would otherwise explode padding.
- **Tiling**: ELL rows are padded per-window to a multiple of ``TR`` so a
  ``(TR, K)`` tile never straddles two source windows; ``tile_window[t]``
  drives the scalar-prefetch BlockSpec index map in the Pallas kernel.

Padding rows carry ``valid=False`` masks and ``seg=0``; they contribute the
combine identity and are therefore harmless.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import numpy as np

from .sharding import ShardCSR

__all__ = [
    "EllShard",
    "EllBatch",
    "csr_to_ell",
    "concat_ells",
    "next_pow2",
    "bucket_rows",
    "pad_ell_arrays",
    "ragged_lane_pad",
    "ragged_lane_concat",
    "DEFAULT_K",
    "DEFAULT_TR",
    "DEFAULT_WINDOW",
]


def next_pow2(n: int) -> int:
    return 1 << max(int(np.ceil(np.log2(max(n, 1)))), 0)


def bucket_rows(n_ell: int, tr: int) -> int:
    """Shape bucket for jit caching: next power of two, rounded up to a
    tile multiple so a ``(TR, K)`` grid still covers it exactly."""
    n = max(next_pow2(n_ell), tr)
    return -(-n // tr) * tr


def pad_ell_arrays(idx, mask, seg, tw, n_ell: int, tr: int, n_ell_pad: int):
    """Pad ELL arrays to ``n_ell_pad`` rows (``n_ell_pad % tr == 0``).

    Padding rows carry ``mask=False`` / ``seg=0`` / window id 0 — they
    gather the combine identity into the first destination row, a no-op.
    ``tile_window`` is padded with ceil division: floor (``pad // tr``)
    silently truncates whenever the row padding isn't a tile multiple,
    leaving the padded tail without a window id.
    """
    pad = n_ell_pad - n_ell
    if pad == 0:
        return idx, mask, seg, tw
    assert n_ell_pad % tr == 0, (n_ell_pad, tr)
    idx = np.concatenate([idx, np.zeros((pad, idx.shape[1]), idx.dtype)])
    mask = np.concatenate([mask, np.zeros((pad, mask.shape[1]), bool)])
    seg = np.concatenate([seg, np.zeros(pad, np.int32)])
    tw = np.concatenate([tw, np.zeros(n_ell_pad // tr - tw.shape[0], np.int32)])
    assert idx.shape[0] == n_ell_pad and tw.shape[0] * tr == n_ell_pad
    return idx, mask, seg, tw

DEFAULT_K = 128  # ELL width == TPU lane count
DEFAULT_TR = 8  # tile rows == TPU sublane count
DEFAULT_WINDOW = 1 << 14  # 16384 source vertices per window (64KB fp32 table)


@dataclasses.dataclass
class EllShard:
    """Windowed row-split ELL representation of one destination shard."""

    shard_id: int
    v0: int
    v1: int
    num_vertices: int  # of the whole graph (defines window count)
    window: int  # W
    k: int  # ELL width
    tr: int  # tile rows
    ell_idx: np.ndarray  # int16/int32 [n_ell, K] window-local source indices
    ell_mask: np.ndarray  # bool  [n_ell, K]
    seg: np.ndarray  # int32 [n_ell] local destination row (0 for padding)
    tile_window: np.ndarray  # int32 [n_ell // TR] source-window id per tile
    nnz: int

    @property
    def rows(self) -> int:
        return self.v1 - self.v0

    @property
    def n_ell(self) -> int:
        return int(self.ell_idx.shape[0])

    @property
    def n_tiles(self) -> int:
        return int(self.tile_window.shape[0])

    @property
    def num_windows(self) -> int:
        return max(1, -(-self.num_vertices // self.window))

    @property
    def nbytes(self) -> int:
        return int(
            self.ell_idx.nbytes
            + self.ell_mask.nbytes
            + self.seg.nbytes
            + self.tile_window.nbytes
        )

    def global_idx(self) -> np.ndarray:
        """Recover global source ids, [n_ell, K] (undefined where mask=False)."""
        win = np.repeat(self.tile_window, self.tr).astype(np.int64)
        return self.ell_idx.astype(np.int64) + win[:, None] * self.window

    def padding_ratio(self) -> float:
        """Fraction of ELL slots that are padding (wasted bandwidth)."""
        total = self.ell_idx.size
        return 1.0 - (self.nnz / total) if total else 0.0


@dataclasses.dataclass
class EllBatch:
    """N consecutive ELL shards concatenated into one kernel dispatch.

    All constituent shards share ``window``/``k``/``tr``/``num_vertices``
    (one preprocessing run), so their tile->window prefetch maps live in the
    same coordinate system and simply concatenate: a single Pallas grid
    walks every tile of every shard against ONE resident message table,
    amortizing per-shard dispatch overhead (DESIGN.md §4).

    ``seg`` is globalized (shard-local destination row + the shard's row
    offset) so one segment combine with ``rows_total`` segments covers the
    whole batch; ``row_offsets`` splits the combined accumulator back into
    per-shard intervals.
    """

    shard_ids: list
    ell_idx: np.ndarray  # [sum n_ell, K]
    ell_mask: np.ndarray  # bool [sum n_ell, K]
    seg: np.ndarray  # int32 [sum n_ell] globalized destination rows
    tile_window: np.ndarray  # int32 [sum n_tiles]
    row_offsets: np.ndarray  # int64 [N+1] shard row boundaries in the acc
    num_vertices: int
    window: int
    k: int
    tr: int

    @property
    def rows_total(self) -> int:
        return int(self.row_offsets[-1])

    @property
    def n_ell(self) -> int:
        return int(self.ell_idx.shape[0])

    @property
    def num_windows(self) -> int:
        return max(1, -(-self.num_vertices // self.window))

    def split(self, acc: np.ndarray) -> list:
        """Slice a combined accumulator back per shard.

        Rows are the trailing axis so both the single-query ``[rows_total]``
        accumulator and the serving layer's lane-batched
        ``[lanes, rows_total]`` accumulator split the same way.
        """
        return [
            acc[..., self.row_offsets[i]: self.row_offsets[i + 1]]
            for i in range(len(self.shard_ids))
        ]


def concat_ells(ells: Sequence[EllShard]) -> EllBatch:
    """Concatenate ELL shards for one batched dispatch.

    Requires a homogeneous batch (same window/k/tr/num_vertices — true for
    any shards from one store) and tile-aligned shards (``n_ell % tr == 0``,
    guaranteed by :func:`csr_to_ell`'s per-window padding).
    """
    if not ells:
        raise ValueError("empty ELL batch")
    first = ells[0]
    for e in ells[1:]:
        if (e.window, e.k, e.tr, e.num_vertices) != (
            first.window, first.k, first.tr, first.num_vertices
        ):
            raise ValueError("ELL shards in a batch must share window/k/tr/|V|")
    for e in ells:
        if e.n_ell % e.tr:
            raise ValueError(f"shard {e.shard_id}: n_ell not tile-aligned")
    row_offsets = np.zeros(len(ells) + 1, dtype=np.int64)
    np.cumsum([e.rows for e in ells], out=row_offsets[1:])
    seg = np.concatenate(
        [e.seg.astype(np.int32) + np.int32(off)
         for e, off in zip(ells, row_offsets[:-1])]
    )
    return EllBatch(
        shard_ids=[e.shard_id for e in ells],
        ell_idx=np.concatenate([e.ell_idx for e in ells]),
        ell_mask=np.concatenate([e.ell_mask for e in ells]),
        seg=seg,
        tile_window=np.concatenate([e.tile_window for e in ells]),
        row_offsets=row_offsets,
        num_vertices=first.num_vertices,
        window=first.window,
        k=first.k,
        tr=first.tr,
    )


def ragged_lane_pad(lane_counts: Sequence[int]) -> int:
    """Padded lane count for ONE ragged launch covering all fusion groups.

    Padding every group to its own power of two would waste
    ``sum(next_pow2(k_g)) - sum(k_g)`` lanes.  A single ragged launch only
    needs ONE padded lane axis; padding the concatenated count to
    ``next_pow2(K_total)`` keeps the jit shape-bucket behaviour but can
    exceed that per-group waste (e.g. counts ``1,1,1`` -> 4 vs 3), so the
    target is capped at the per-group pow2 total — the ragged waste is then
    provably never worse.
    """
    k_total = int(sum(int(k) for k in lane_counts))
    per_group = int(sum(next_pow2(max(int(k), 1)) for k in lane_counts))
    return max(1, min(next_pow2(max(k_total, 1)), per_group))


def ragged_lane_concat(msgs_by_group, combines: Sequence[str], *,
                       n_cols: Optional[int] = None):
    """Concatenate per-group lane matrices along the lane axis for one
    ragged launch.

    Returns ``(msgs_all, combine_ids, combines_set, group_slices)``:

    - ``msgs_all``   [k_pad, n_cols] — groups stacked then zero-padded to
      ``ragged_lane_pad`` lanes (and to ``n_cols`` columns when the caller
      passes the window-padded vertex count, saving a second copy).
    - ``combine_ids`` int32 [k_pad] — per lane, the index of its combine op
      in ``combines_set``.  Padding lanes get ``len(combines_set)`` — an id
      that matches NO arm, so every selection pass leaves them at the zero
      init and the results are discarded by ``group_slices`` anyway.
    - ``combines_set`` — deduplicated combine ops in first-seen order (two
      groups sharing a monoid share one kernel arm).
    - ``group_slices`` — per input group, its lane interval in ``msgs_all``.
    """
    if len(msgs_by_group) != len(combines):
        raise ValueError("one combine op per group required")
    if not msgs_by_group:
        raise ValueError("empty ragged lane concat")
    combines_set = tuple(dict.fromkeys(combines))
    counts = [int(m.shape[0]) for m in msgs_by_group]
    k_pad = ragged_lane_pad(counts)
    n = int(msgs_by_group[0].shape[1] if n_cols is None else n_cols)
    msgs_all = np.zeros((k_pad, n), dtype=msgs_by_group[0].dtype)
    combine_ids = np.full(k_pad, len(combines_set), dtype=np.int32)
    group_slices = []
    off = 0
    for m, c in zip(msgs_by_group, combines):
        if m.shape[1] > n:
            raise ValueError("group lane matrix wider than n_cols")
        sl = slice(off, off + int(m.shape[0]))
        msgs_all[sl, : m.shape[1]] = m
        combine_ids[sl] = combines_set.index(c)
        group_slices.append(sl)
        off = sl.stop
    return msgs_all, combine_ids, combines_set, group_slices


def csr_to_ell(
    shard: ShardCSR,
    num_vertices: int,
    *,
    window: int = DEFAULT_WINDOW,
    k: int = DEFAULT_K,
    tr: int = DEFAULT_TR,
    index_dtype: Optional[np.dtype] = None,
) -> EllShard:
    """Convert a CSR destination shard into the windowed row-split ELL format."""
    if window <= 0 or k <= 0 or tr <= 0:
        raise ValueError("window, k, tr must be positive")
    if index_dtype is None:
        index_dtype = np.int16 if window <= (1 << 15) else np.int32

    rows = shard.rows
    nnz = shard.nnz

    if nnz == 0:
        ell_idx = np.zeros((tr, k), dtype=index_dtype)
        ell_mask = np.zeros((tr, k), dtype=bool)
        seg = np.zeros((tr,), dtype=np.int32)
        tile_window = np.zeros((1,), dtype=np.int32)
        return EllShard(
            shard.shard_id, shard.v0, shard.v1, num_vertices, window, k, tr,
            ell_idx, ell_mask, seg, tile_window, nnz=0,
        )

    # Expand CSR to (local_dst, src) pairs, then sort by (window, local_dst, src).
    counts = np.diff(shard.row)
    local_dst = np.repeat(np.arange(rows, dtype=np.int64), counts)
    src = shard.col.astype(np.int64)
    win = src // window
    order = np.lexsort((src, local_dst, win))
    src, local_dst, win = src[order], local_dst[order], win[order]
    local_src = (src - win * window).astype(np.int64)

    # Row splitting: within each (window, local_dst) group, edge j goes to ELL
    # row group_start_ell + j // K, slot j % K.
    grp_change = np.empty(nnz, dtype=bool)
    grp_change[0] = True
    grp_change[1:] = (win[1:] != win[:-1]) | (local_dst[1:] != local_dst[:-1])
    grp_id = np.cumsum(grp_change) - 1  # [nnz]
    grp_start = np.flatnonzero(grp_change)  # first edge index of each group
    pos_in_grp = np.arange(nnz, dtype=np.int64) - grp_start[grp_id]
    rows_per_grp = np.ceil(
        np.diff(np.concatenate([grp_start, [nnz]])) / k
    ).astype(np.int64)

    # ELL row index before per-window tile padding.
    grp_row_start = np.concatenate([[0], np.cumsum(rows_per_grp)])[:-1]
    raw_ell_row = grp_row_start[grp_id] + pos_in_grp // k
    slot = pos_in_grp % k
    n_raw = int(rows_per_grp.sum())

    raw_seg = np.zeros(n_raw, dtype=np.int32)
    raw_win = np.zeros(n_raw, dtype=np.int64)
    raw_seg[grp_row_start] = 0  # filled below via scatter of group attrs
    # Each raw ELL row inherits (window, local_dst) of its group.
    grp_first_edge = grp_start  # [n_groups]
    grp_window = win[grp_first_edge]
    grp_dst = local_dst[grp_first_edge]
    row_grp = np.repeat(np.arange(len(grp_start)), rows_per_grp)
    raw_seg = grp_dst[row_grp].astype(np.int32)
    raw_win = grp_window[row_grp]

    # Pad ELL rows per window to a multiple of TR so tiles are window-pure.
    uniq_wins, win_row_counts = np.unique(raw_win, return_counts=True)
    padded_counts = -(-win_row_counts // tr) * tr
    win_row_offset = np.concatenate([[0], np.cumsum(padded_counts)])[:-1]
    n_ell = int(padded_counts.sum())

    # Map raw rows -> padded positions.
    win_rank = np.searchsorted(uniq_wins, raw_win)
    # position of raw row within its window block:
    row_in_win = np.zeros(n_raw, dtype=np.int64)
    # raw rows are already sorted by window (construction preserves sort order)
    start_of_win = np.concatenate([[0], np.cumsum(win_row_counts)])[:-1]
    row_in_win = np.arange(n_raw) - start_of_win[win_rank]
    padded_row = win_row_offset[win_rank] + row_in_win

    ell_idx = np.zeros((n_ell, k), dtype=index_dtype)
    ell_mask = np.zeros((n_ell, k), dtype=bool)
    seg = np.zeros((n_ell,), dtype=np.int32)
    seg[padded_row] = raw_seg

    # Scatter edges into the padded ELL arrays.
    edge_rows = padded_row[raw_ell_row]
    ell_idx[edge_rows, slot] = local_src.astype(index_dtype)
    ell_mask[edge_rows, slot] = True

    n_tiles = n_ell // tr
    tile_window = np.repeat(uniq_wins, padded_counts // tr).astype(np.int32)
    assert tile_window.shape[0] == n_tiles

    out = EllShard(
        shard.shard_id, shard.v0, shard.v1, num_vertices, window, k, tr,
        ell_idx, ell_mask, seg, tile_window, nnz=nnz,
    )
    assert int(out.ell_mask.sum()) == nnz
    return out
