"""Peaks by device kind, and the work an ELL pull-update has to do.

The least time a shard dispatch can take on the chip is set by the bytes
it must move through HBM, computed from the data the dispatch covers and
never from the kernel's layout (ELL slots, padding, masks), so that a
change of layout leaves the count as it was:

- each edge's source index, 4 bytes;
- each lane's source values, 4 bytes for each of the ``n`` vertices the
  edges may read (R-MAT sources span every vertex);
- each lane's result for each destination row of the shard, 4 bytes.

Operations are one combine per edge per lane.  A kernel's roofline share
is that least time over the device time of its events in the trace.
"""

from __future__ import annotations

import json
import os
from typing import Dict, Iterable, Optional, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> Dict:
    """The peak table's entry for ``device_kind``; an unknown device is an
    error, never a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"bench/peaks.json has {sorted(table)}")
    return table[device_kind]


def spmv_bytes(edges: int, rows: int, n: int, lanes: int) -> float:
    return 4.0 * edges + 4.0 * lanes * (n + rows)


def spmv_flops(edges: int, lanes: int) -> float:
    return float(edges) * lanes


#: The ELL pull-update kernels' events in a device trace.
KERNEL = r"ell_partials|_masked_kernel|_ragged_kernel|_sentinel_kernel"


def kernel_share(ctx: Dict) -> Optional[float]:
    """The kernel's share of its roofline, in %: the least time of the
    dispatched shards' work over the device time of the kernel's events in
    the window; ``None`` where the trace holds no such event."""
    prof = ctx.get("profile")
    if prof is None or not ctx.get("dispatches"):
        return None
    kernel_s = prof.op_seconds(KERNEL)
    if kernel_s <= 0:
        return None
    return 100.0 * least_seconds(ctx["dispatches"], ctx["peak"]) / kernel_s


def least_seconds(dispatches: Iterable[Tuple[int, int, int, int]],
                  peak: Dict) -> float:
    """Roofline time of ``(edges, rows, n, lanes)`` dispatches: the larger
    of bytes over HBM bandwidth and operations over peak FLOP/s."""
    b = f = 0.0
    for edges, rows, n, lanes in dispatches:
        b += spmv_bytes(edges, rows, n, lanes)
        f += spmv_flops(edges, lanes)
    return max(b / peak["hbm_bytes_per_s"], f / peak["bf16_flops_per_s"])
