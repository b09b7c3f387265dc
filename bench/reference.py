"""Plain references over the generated edge list, and their controls.

Written from the vertex programs' published semantics (GraphMP, Alg. 2),
not from the program's code: nothing here imports ``repro``.  A graph is
the pair of int arrays ``(src, dst)`` over ``n`` vertices; duplicate edges
and self-loops count, as they do in the generated R-MAT edge list.

- PageRank: ``pr' = (1 - d) / n + d * sum over in-edges (u, v) of
  pr[u] / outdeg[u]`` from ``pr = 1 / n``, for a fixed number of
  iterations (no redistribution of dangling mass).
- Personalized PageRank from ``s``: ``x' = d * sum ... x[u] / outdeg[u]``
  plus ``1 - d`` at ``s``, from the unit vector at ``s``; it stops early
  once an iteration changes no value.
- BFS levels (and unit-weight SSSP, the same numbers): the hop count from
  the source along edge directions, ``inf`` where a vertex is not reached
  within ``max_iters`` hops.

The references run in float64.  The controls of ``correct`` are the same
functions computed a step lower: ``*_bf16`` keep every value and message in
bfloat16 (the configurations state float32), and ``bfs_levels`` with one
hop fewer than the search needs breaks the guarantee that a search runs to
convergence.
"""

from __future__ import annotations

import numpy as np


def out_degrees(src: np.ndarray, n: int) -> np.ndarray:
    return np.bincount(src, minlength=n).astype(np.float64)


def pagerank(src, dst, n: int, *, damping: float, iterations: int):
    inv = 1.0 / np.maximum(out_degrees(src, n), 1.0)
    pr = np.full(n, 1.0 / n)
    for _ in range(iterations):
        acc = np.bincount(dst, weights=(pr * inv)[src], minlength=n)
        pr = (1.0 - damping) / n + damping * acc
    return pr


def ppr(src, dst, n: int, source: int, *, damping: float, max_iters: int):
    inv = 1.0 / np.maximum(out_degrees(src, n), 1.0)
    x = np.zeros(n)
    x[source] = 1.0
    for _ in range(max_iters):
        new = damping * np.bincount(dst, weights=(x * inv)[src], minlength=n)
        new[source] += 1.0 - damping
        done = np.array_equal(new, x)
        x = new
        if done:
            break
    return x


def bfs_levels(src, dst, n: int, source: int, *, max_iters: int):
    """Hop levels from ``source`` (``inf`` = not reached in ``max_iters``)."""
    level = np.full(n, np.inf)
    level[source] = 0.0
    frontier = np.zeros(n, dtype=bool)
    frontier[source] = True
    for hop in range(1, max_iters + 1):
        nxt = np.zeros(n, dtype=bool)
        nxt[dst[frontier[src]]] = True
        nxt &= np.isinf(level)
        if not nxt.any():
            break
        level[nxt] = hop
        frontier = nxt
    return level


def depth(level: np.ndarray) -> int:
    """Largest finite level: the hops a search needs to converge."""
    return int(level[np.isfinite(level)].max())


def reached_edges(src: np.ndarray, level: np.ndarray) -> int:
    """Graph500's traversed edges of a search: input edges whose source
    vertex the search reached."""
    return int(np.isfinite(level)[src].sum())


def rel_l1(got, want) -> float:
    """``sum |got - want| / sum |want|``."""
    want = np.asarray(want, dtype=np.float64)
    diff = np.abs(np.asarray(got, dtype=np.float64) - want).sum()
    return float(diff / max(np.abs(want).sum(), 1e-300))


def level_mismatch(got, want) -> int:
    """Vertices whose level differs (``inf`` equals ``inf``)."""
    got = np.asarray(got, dtype=np.float64)
    want = np.asarray(want, dtype=np.float64)
    return int((~((got == want) | (np.isinf(got) & np.isinf(want)))).sum())


# ---------------------------------------------------------------- controls
def _bf16_spmv(src, dst, n):
    import jax
    import jax.numpy as jnp

    s = jnp.asarray(src)
    d = jnp.asarray(dst)
    inv = (1.0 / jnp.maximum(jnp.bincount(s, length=n), 1)).astype(jnp.bfloat16)

    @jax.jit
    def spmv(x):
        msgs = (x * inv).astype(jnp.bfloat16)
        return jax.ops.segment_sum(msgs[s], d, num_segments=n)

    return spmv


def pagerank_bf16(src, dst, n: int, *, damping: float, iterations: int):
    import jax.numpy as jnp

    spmv = _bf16_spmv(src, dst, n)
    d = jnp.bfloat16(damping)
    base = jnp.bfloat16((1.0 - damping) / n)
    pr = jnp.full(n, 1.0 / n, jnp.bfloat16)
    for _ in range(iterations):
        pr = (base + d * spmv(pr)).astype(jnp.bfloat16)
    return np.asarray(pr, dtype=np.float64)


def ppr_bf16(src, dst, n: int, source: int, *, damping: float,
             max_iters: int):
    import jax.numpy as jnp

    spmv = _bf16_spmv(src, dst, n)
    d = jnp.bfloat16(damping)
    x = jnp.zeros(n, jnp.bfloat16).at[source].set(1.0)
    for _ in range(max_iters):
        new = (d * spmv(x)).astype(jnp.bfloat16)
        new = new.at[source].add(jnp.bfloat16(1.0 - damping))
        done = bool((new == x).all())
        x = new
        if done:
            break
    return np.asarray(x, dtype=np.float64)
