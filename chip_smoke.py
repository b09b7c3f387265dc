#!/usr/bin/env python3
"""Smoke run of GraphMP's main path on a TPU, checked against the numpy oracle.

    python chip_smoke.py            # one chip: analytics + serving phases
    python chip_smoke.py --chips 4  # four chips: the mesh serving phase only

The graph is a Graph500 Kronecker graph (R-MAT with A, B, C = 0.57, 0.19,
0.19, edge factor 16) made from ``--seed`` and written to a shard store
through ``VSWEngine.from_graph``.  Every engine and service opens that one
store with the default widths (W=16384, K=128).

- analytics: PageRank for a fixed number of iterations and BFS from a
  seeded source to convergence on ``VSWEngine(backend="pallas")``.
- serving: ``GraphService(max_lanes=16, backend="pallas")`` answers one
  ``submit_batch`` of 16 BFS, SSSP, PPR and WCC queries from seeded sources,
  fused into one sweep.
- mesh (``--chips 4`` only): the same batch through
  ``GraphService(mesh=4, backend="pallas")``.

Each pallas result is compared with a ``backend="numpy"`` engine on the same
store: bitwise for the min programs (BFS, SSSP, WCC), ``np.allclose`` for
the sum programs (PageRank, PPR).  The lines before the last are a smoke
record, not benchmark figures.  The last line is the JSON object
``{"ok": true, "device": {...}}``, printed only when JAX found a TPU and every
phase ran and agreed with its oracle; otherwise the script exits non-zero
without it.  The compile cache is ``JAX_COMPILATION_CACHE_DIR`` where set,
else ``.jax_cache/`` in this checkout.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

from repro.core import apps  # noqa: E402
from repro.core.graph import rmat_graph  # noqa: E402
from repro.core.vsw import VSWEngine  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.serve import GraphService  # noqa: E402

#: Graph500 scale of the smoke graph.  LDBC Graphalytics' graph500-22 is
#: scale 22; SCALE_CUT says why this run is smaller.
SCALE = 19
SCALE_CUT = (
    "scale 22 -> 19: a cold scale-22 run is estimated at about 25 min on "
    "one v5e chip (4x the 1.3 GB scale-20 store, ~3e9 ELL slots per sweep "
    "at 2.2% fill, and the numpy oracle replays every query), over a smoke "
    "run's 20-minute budget; scale 19 keeps a cold run to a few minutes"
)
EDGE_FACTOR = 16
NUM_SHARDS = 16
PAGERANK_ITERS = 10
BFS_MAX_ITERS = 100
SERVE_MAX_ITERS = 10
QUERIES_PER_PROGRAM = 4  # x {bfs, sssp, ppr, wcc} = 16 queries
RESULT_TIMEOUT_S = 900


class OracleMismatch(AssertionError):
    """A pallas result disagrees with the numpy oracle."""


def record(phase: str, **fields) -> None:
    print(json.dumps({"smoke": phase, **fields}, default=float), flush=True)


class CompileClock:
    """Sums JAX's backend-compile durations and persistent-cache hits, so a
    warm cache shows up as fewer compile seconds."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.compiles = self.cache_hits = self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snapshot(self) -> dict:
        return {
            "compile_s": self.seconds, "compiles": self.compiles,
            "cache_hits": self.cache_hits, "cache_misses": self.cache_misses,
        }


def peak_bytes() -> list:
    """``peak_bytes_in_use`` per device (None where the backend has none)."""
    import jax

    return [
        (d.memory_stats() or {}).get("peak_bytes_in_use")
        for d in jax.devices()
    ]


def same(name: str, got: np.ndarray, want: np.ndarray, combine: str) -> None:
    """Raise unless ``got`` matches the oracle: bitwise for min/max
    programs, ``np.allclose`` for sum programs."""
    a = np.nan_to_num(np.asarray(got), posinf=1e30, neginf=-1e30)
    b = np.nan_to_num(np.asarray(want), posinf=1e30, neginf=-1e30)
    ok = np.allclose(a, b) if combine == "sum" else np.array_equal(a, b)
    if not ok:
        diff = np.abs(a.astype(np.float64) - b)
        raise OracleMismatch(
            f"{name}: {int((diff > 0).sum())} of {a.size} values differ "
            f"from the numpy oracle (max abs diff {diff.max()})"
        )


def build_store(scale: int, seed: int, root: str) -> VSWEngine:
    """Generate the Graph500 graph and write its shard store; returns the
    numpy oracle engine opened on that store."""
    n = 1 << scale
    t0 = time.perf_counter()
    g = rmat_graph(n, EDGE_FACTOR * n, seed=seed, a=0.57, b=0.19, c=0.19)
    t1 = time.perf_counter()
    oracle = VSWEngine.from_graph(g, root, num_shards=NUM_SHARDS,
                                  backend="numpy")
    t2 = time.perf_counter()
    store_bytes = sum(
        os.path.getsize(os.path.join(root, f)) for f in os.listdir(root)
        if os.path.isfile(os.path.join(root, f))
    )
    record("setup", scale=scale, vertices=g.num_vertices,
           edges=g.num_edges, shards=oracle.meta.num_shards,
           store_bytes=store_bytes, generate_s=t1 - t0, build_s=t2 - t1)
    return oracle


def _sources(oracle: VSWEngine, seed: int, n: int) -> np.ndarray:
    """``n`` seeded sources among vertices with out-edges."""
    cand = np.flatnonzero(oracle.meta.out_deg > 0)
    return np.random.default_rng(seed).choice(cand, size=n, replace=False)


def _timed_run(eng: VSWEngine, program, max_iters: int):
    t0 = time.perf_counter()
    r = eng.run(program, max_iters=max_iters)
    return r, time.perf_counter() - t0


def analytics_phase(root: str, oracle: VSWEngine, seed: int,
                    clock: CompileClock) -> None:
    """PageRank (fixed iterations) and BFS (to convergence) on a pallas
    engine, each checked against the oracle engine."""
    source = int(_sources(oracle, seed + 1, 1)[0])
    with VSWEngine.from_store(root, backend="pallas") as eng:
        for name, program, iters, must_converge in (
            ("pagerank", apps.pagerank(), PAGERANK_ITERS, False),
            ("bfs", apps.bfs(source), BFS_MAX_ITERS, True),
        ):
            c0 = clock.snapshot()
            got, wall = _timed_run(eng, program, iters)
            c1 = clock.snapshot()
            want, oracle_wall = _timed_run(oracle, program, iters)
            same(name, got.values, want.values, program.combine)
            if got.num_iterations != want.num_iterations:
                raise OracleMismatch(
                    f"{name}: {got.num_iterations} iterations, oracle "
                    f"{want.num_iterations}"
                )
            if must_converge and not got.converged:
                raise OracleMismatch(f"{name}: no convergence in {iters}")
            record(
                f"analytics.{name}", source=source if name == "bfs" else None,
                wall_s=wall, compile_s=c1["compile_s"] - c0["compile_s"],
                iterations=got.num_iterations, converged=got.converged,
                shards_loaded=sum(i.shards_processed for i in got.iterations),
                shards_skipped=sum(i.shards_skipped for i in got.iterations),
                oracle_wall_s=oracle_wall, oracle="match",
                peak_bytes_in_use=peak_bytes(),
            )


def serving_queries(oracle: VSWEngine, seed: int) -> list:
    progs = ("bfs", "sssp", "ppr", "wcc")
    srcs = _sources(oracle, seed + 2, QUERIES_PER_PROGRAM * len(progs))
    return [(progs[i % len(progs)], int(s)) for i, s in enumerate(srcs)]


def serving_phase(root: str, oracle: VSWEngine, seed: int,
                  clock: CompileClock, *, mesh=None) -> None:
    """One fused ``submit_batch`` of 16 mixed queries through GraphService;
    every result is checked against a solo oracle run."""
    queries = serving_queries(oracle, seed)
    kw = {} if mesh is None else {"mesh": mesh}
    phase = "serving" if mesh is None else f"mesh{mesh}.serving"
    c0 = clock.snapshot()
    t0 = time.perf_counter()
    with GraphService.from_store(root, backend="pallas", max_lanes=16,
                                 **kw) as svc:
        with svc.submit_batch():
            futs = [svc.submit(p, s, max_iters=SERVE_MAX_ITERS)
                    for p, s in queries]
        results = [f.result(timeout=RESULT_TIMEOUT_S) for f in futs]
        stats = svc.stats()
    wall = time.perf_counter() - t0
    c1 = clock.snapshot()
    if stats["sweeps"] != 1:
        raise OracleMismatch(
            f"{phase}: the batch took {stats['sweeps']} sweeps, not one"
        )
    t1 = time.perf_counter()
    for (p, s), qr in zip(queries, results):
        program = apps.get_program(p, **({} if p == "wcc" else {"source": s}))
        want = oracle.run(program, max_iters=SERVE_MAX_ITERS)
        same(f"{phase}.{p}({s})", qr.values, want.values, program.combine)
        if qr.iterations != want.num_iterations:
            raise OracleMismatch(
                f"{phase}.{p}({s}): {qr.iterations} iterations, oracle "
                f"{want.num_iterations}"
            )
    record(
        phase, queries=len(results), wall_s=wall,
        compile_s=c1["compile_s"] - c0["compile_s"],
        iterations=max(qr.iterations for qr in results),
        sweeps=stats["sweeps"], multi_group_sweeps=stats["multi_group_sweeps"],
        shard_loads=stats["shard_loads_total"],
        mesh_devices=stats["mesh_devices"],
        oracle_wall_s=time.perf_counter() - t1, oracle="match",
        peak_bytes_in_use=peak_bytes(),
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found platform={platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    clock = CompileClock()
    record("start", note="smoke run, not benchmark figures",
           jax=jax.__version__, platform=platform,
           device_kind=devices[0].device_kind, device_count=len(devices),
           chips=args.chips, seed=args.seed, scale=SCALE, cut=SCALE_CUT,
           compile_cache=cache_dir)

    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory(prefix=".smoke-store-", dir=REPO) as root:
        oracle = build_store(SCALE, args.seed, root)
        with oracle:
            if args.chips == 1:
                analytics_phase(root, oracle, args.seed, clock)
                serving_phase(root, oracle, args.seed, clock)
            else:
                serving_phase(root, oracle, args.seed, clock,
                              mesh=args.chips)
    record("end", total_s=time.perf_counter() - t0, **clock.snapshot())
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
