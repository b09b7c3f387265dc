"""The benchmark's own graph generator: Graph500's Kronecker generator.

The edge draw is a copy of the program's ``rmat_graph`` arithmetic (directed
R-MAT), kept here so that the inputs of every cell stay fixed however the
program changes; without ``permute`` it yields the same edge list as the
program's generator (``bench/tests/test_bench_reference.py`` checks that at
a small size).  With ``permute``, as the Graph500 specification requires
after generation, the vertex labels are randomly permuted and the edge list
shuffled, so hubs do not sit at the lowest ids and a source's id says
nothing about its destinations' ids.
"""

from __future__ import annotations

import numpy as np


def rmat_edges(scale: int, edge_factor: int, seed: int, *, a: float, b: float,
               c: float, permute: bool):
    """``(src, dst)`` int32 arrays of ``edge_factor << scale`` directed edges
    over ``1 << scale`` vertices; duplicates and self-loops are kept."""
    n = 1 << scale
    m = edge_factor * n
    rng = np.random.default_rng(seed)
    src = np.zeros(m, dtype=np.int64)
    dst = np.zeros(m, dtype=np.int64)
    ab, abc = a + b, a + b + c
    for level in range(scale):
        r = rng.random(m)
        right = r >= ab  # quadrant c or d: source bit set
        lower = ((r >= a) & (r < ab)) | (r >= abc)  # quadrant b or d
        src |= right.astype(np.int64) << level
        dst |= lower.astype(np.int64) << level
    src %= n
    dst %= n
    if permute:
        label = rng.permutation(n)
        order = rng.permutation(m)
        src, dst = label[src][order], label[dst][order]
    return src.astype(np.int32), dst.astype(np.int32)


def search_keys(src: np.ndarray, n: int, count: int, rng) -> np.ndarray:
    """Graph500 search keys: ``count`` distinct vertices drawn uniformly
    from those with at least one out-edge."""
    cand = np.flatnonzero(np.bincount(src, minlength=n) > 0)
    return rng.choice(cand, size=min(count, len(cand)), replace=False)
