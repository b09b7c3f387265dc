"""The one traffic generator: turns a traffic file and a seed into work.

Kinds of traffic file (``bench/traffic/<name>.json``):

``analytics``
    Whole-graph runs on ``VSWEngine``, back to back.  ``program`` names a
    vertex program and ``params`` its parameters.  With ``sources`` set to
    ``graph500_keys`` every run starts from its own search key (``keys`` of
    them are drawn), and runs to convergence within
    ``max_iters``; otherwise every run is ``iterations`` iterations long.
``closed_loop``
    ``clients`` callers of ``GraphService``, each with one query in flight,
    sending the next when the last returns.  Queries cycle through
    ``programs`` in equal shares, in a shuffled order within each round of
    one query per program, from uniform ``graph500_keys``, with
    ``max_iters`` and the per-program ``params``.  ``ops`` bounds the
    schedule.

Every choice is drawn from the seed before the run starts, so the same seed
gives the same work whatever the timing.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from bench.graphgen import search_keys

Op = Tuple[str, int, Dict]  # (program, source, params)


def rng(seed: int, stream: int):
    return np.random.default_rng([int(seed), stream])


def analytics_plan(t: Dict, src: np.ndarray, n: int, seed: int):
    """The window's runs, more than any window can use up."""
    params = dict(t.get("params", {}))
    if t.get("sources") == "graph500_keys":
        keys = search_keys(src, n, int(t["keys"]), rng(seed, 1))
        ops = [(t["program"], int(k), params) for k in keys]
        return ops
    if "sources" in t:
        raise ValueError(f"unknown sources {t['sources']!r}")
    return [(t["program"], -1, params)] * 1000


def closed_loop_plan(t: Dict, src: np.ndarray, n: int, seed: int) -> List[Op]:
    if t.get("sources") != "graph500_keys":
        raise ValueError(f"unknown sources {t.get('sources')!r}")
    r = rng(seed, 2)
    programs = list(t["programs"])
    count = int(t["ops"])
    rounds = -(-count // len(programs))
    order = np.concatenate([r.permutation(programs) for _ in range(rounds)])
    cand = np.flatnonzero(np.bincount(src, minlength=n) > 0)
    sources = r.choice(cand, size=count)
    params = t.get("params", {})
    return [(str(p), int(s), dict(params.get(str(p), {})))
            for p, s in zip(order[:count], sources)]
