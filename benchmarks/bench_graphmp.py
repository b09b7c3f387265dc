"""Benchmarks reproducing the paper's tables/figures at testbed scale.

Mapping to the paper:
  fig5_selective   — Fig. 5: GraphMP-SS vs GraphMP-NSS per-iteration times +
                     activation ratios (PageRank / SSSP / WCC on RMAT).
  fig8_10_engines  — Figs. 8-10 + Table III: per-iteration execution time of
                     PSW (GraphChi), ESG (X-Stream), DSW (GridGraph),
                     GraphMP-NC and GraphMP-C; speedup ratios vs GraphMP-C.
  fig11_memory     — Fig. 11: resident data bytes per engine.
  table2_io        — Table II: analytic read/write/memory per model, plus
                     measured-vs-analytic validation from the real engines.
  fig3_pipeline    — Fig. 3 / §II-C: pipelined (prefetching loader threads +
                     batched kernel dispatch) vs fully synchronous shard
                     processing on the cache-miss-heavy config.
  fig_serve        — beyond-paper serving layer (repro/serve): queries/sec
                     and bytes-read-per-query at lane budgets K ∈ {1, 4, 16}
                     on the cache-miss-heavy config, plus the bitwise oracle
                     check on a lane-batched result.
  fig_fusion       — cross-query shard-plan fusion (repro/serve, DESIGN.md
                     §9): bytes/query and wall time for a mixed
                     BFS+SSSP+PPR workload at K=16 under (a) per-group
                     sweeps (PR 2 key-equality batching), (b) fused
                     same-algebra sweeps, (c) interleaved multi-group
                     sweeps sharing one shard stream; bitwise oracle
                     asserted per program.
  fig_ingest       — streamed out-of-core ingestion (repro/core/ingest) vs
                     the in-memory preprocess: peak traced bytes and bytes
                     written as |E| scales past the chunk/spill budget; the
                     streamed peak must stay flat while the in-memory peak
                     grows O(|E|).
  fig_mesh         — mesh-sharded VSW sweeps (repro/serve MeshSweep,
                     DESIGN.md §10): host-read bytes per sweep and per-device
                     dispatch/shard counts at mesh sizes D ∈ {1, 2, 4, 8};
                     host reads must stay FLAT in D (each shard is decoded
                     once and sliced per destination device) while per-device
                     shard counts sum to the D=1 total.
  fig_delta        — live edge mutations (repro/delta): per-sweep wall time
                     and bytes read as the pending-delta fraction grows,
                     before and after background-style recompaction, with
                     the bitwise oracle (fresh preprocess of the mutated
                     edge list) asserted at every point.
  fig_restart      — warm-restart checkpoints (repro/checkpoint/warm_state,
                     DESIGN.md §12): cold GraphService boot (full filter-
                     build read pass) vs warm-state restore (zero boot
                     reads) under the emulate_bw throttle; warm boot
                     asserted faster, repeat query asserted a session-cache
                     hit, fresh queries asserted bitwise-equal.
  fig_obs          — GraphScope overhead guard (repro/obs, DESIGN.md §11):
                     disabled-tracer per-call cost in ns, multiplied by the
                     span-event count of an enabled run of the same config,
                     must estimate to < 5 % of the untraced sweep time; the
                     direct traced/untraced wall ratio is reported alongside.
  fig_qps          — GraphPulse load harness + SLO gates (repro/serve/
                     loadgen + repro/obs, DESIGN.md §13): closed- and
                     open-loop replay of a seeded mixed workload with a
                     live mutation stream; sustained vs offered QPS,
                     exact p50/p99 with the queue-wait split, per-version
                     bitwise oracle replay, a violation-free SLO monitor,
                     and round-tripped Prometheus/JSONL exports.

Standalone usage (CI smoke mode)::

    PYTHONPATH=src python benchmarks/bench_graphmp.py --quick \
        --out BENCH_graphmp.json
    PYTHONPATH=src python benchmarks/bench_graphmp.py fig_serve --quick \
        --out BENCH_serve.json

Graphs are synthetic RMAT (the paper's web graphs are power-law; RMAT
matches the degree skew).  Scale is laptop-sized; the claims validated are
RELATIVE (I/O ordering, speedups, selective-scheduling effect), which is
what Table II predicts at any scale.
"""

from __future__ import annotations

import tempfile
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.core import apps
from repro.core.baselines.engines import (
    DSWEngine, ESGEngine, PSWEngine, prepare_baseline_store,
)
from repro.core.baselines.io_model import IOParams, MODELS, io_table
from repro.core.graph import from_edge_list, rmat_graph, small_world_graph
from repro.core.vsw import VSWEngine
from repro.launch.compile_cache import enable_compile_cache
from repro.obs import Tracer, trace

GRAPH_V, GRAPH_E, SHARDS = 20_000, 400_000, 8
#: the paper's testbed is 4x4TB HDD RAID (~150 MB/s effective); the
#: container FS is RAM-cached, so the disk-bound regime is emulated with a
#: bandwidth throttle on the accounted storage channel (EXPERIMENTS.md).
DISK_BW = 150e6


def _mk_graph(seed=0):
    return rmat_graph(GRAPH_V, GRAPH_E, seed=seed)


def fig5_selective(rows: List[str]) -> None:
    """SS vs NSS.  PageRank on RMAT (slow fp convergence); SSSP/WCC on a
    high-diameter small-world graph (travelling activity frontier) —
    the two activation regimes of the paper's Fig. 5."""
    # WCC regime (paper Fig. 5c): the bulk converges in a few iterations,
    # then a small active frontier lingers — rmat bulk + a pendant chain.
    from repro.core.graph import Graph, chain_graph

    bulk = rmat_graph(16_000, 350_000, seed=4)
    chain_src = np.arange(16_000, 20_000 - 1, dtype=np.int32)
    wcc_graph = Graph(
        20_000,
        np.concatenate([bulk.src, chain_src, [0]]).astype(np.int32),
        np.concatenate([bulk.dst, chain_src + 1, [16_000]]).astype(np.int32),
    )
    # threshold: the paper's default is 0.001 and notes "users can choose a
    # better value for specific applications" (§II-D-1).  WCC's lingering
    # frontier is ~18% of vertices but confined to ONE shard, so a higher
    # threshold exposes the shard-locality win.
    cases = [
        ("pagerank", apps.pagerank(), 200, _mk_graph(), 1e-3),
        ("sssp", apps.sssp(0), 300,
         small_world_graph(20_000, k=3, shortcuts=0.001, seed=1), 1e-3),
        ("wcc", apps.wcc(), 300, wcc_graph, 0.3),
    ]
    for prog_name, prog, iters, g, threshold in cases:
        times = {}
        for mode, selective in (("ss", True), ("nss", False)):
            with tempfile.TemporaryDirectory() as d:
                eng = VSWEngine.from_graph(
                    g, d, num_shards=SHARDS, backend="numpy",
                    selective=selective, threshold=threshold,
                    emulate_bw=DISK_BW,
                    # any-member FPs compound over the active set:
                    # P(spurious activation) = 1-(1-fp)^|active|, so fp must
                    # be << 1/|active| (reproduction finding, EXPERIMENTS.md)
                    bloom_fp=1e-6,
                )
                times[mode] = eng.run(prog, max_iters=iters)
        ss, nss = times["ss"], times["nss"]
        t_ss = ss.total_time_s
        t_nss = nss.total_time_s
        skipped = sum(i.shards_skipped for i in ss.iterations)
        sel_iters = [i for i in ss.iterations if i.selective_on]
        rows.append(
            f"fig5_selective_{prog_name},{t_ss/max(ss.num_iterations,1)*1e6:.0f},"
            f"overall_speedup={t_nss/max(t_ss,1e-9):.2f}x"
            f";selective_iters={len(sel_iters)}/{ss.num_iterations}"
            f";skipped_shards={skipped}"
            f";final_active_ratio={ss.iterations[-1].active_ratio:.2e}"
        )


def fig8_10_engines(rows: List[str]) -> None:
    g = _mk_graph(seed=1)
    iters = 8
    results: Dict[str, float] = {}
    reads: Dict[str, float] = {}

    with tempfile.TemporaryDirectory() as d:
        store = prepare_baseline_store(g, d, num_shards=SHARDS,
                                       emulate_bw=DISK_BW)
        for name, cls in (("psw", PSWEngine), ("esg", ESGEngine),
                          ("dsw", DSWEngine)):
            io0 = store.io.snapshot()
            t0 = time.perf_counter()
            cls(store).run(apps.pagerank(), max_iters=iters)
            results[name] = (time.perf_counter() - t0) / iters
            reads[name] = (store.io - io0).bytes_read / iters

    for name, cache in (("graphmp_nc", 0), ("graphmp_c", 1 << 30)):
        with tempfile.TemporaryDirectory() as d:
            eng = VSWEngine.from_graph(
                g, d, num_shards=SHARDS, backend="numpy", selective=True,
                cache_bytes=cache, cache_mode=3 if cache else 1,
                emulate_bw=DISK_BW,
            )
            t0 = time.perf_counter()
            r = eng.run(apps.pagerank(), max_iters=iters)
            results[name] = (time.perf_counter() - t0) / iters
            reads[name] = r.total_bytes_read / iters

    base = results["graphmp_c"]
    for name, t in results.items():
        rows.append(
            f"fig8_engines_pagerank_{name},{t*1e6:.0f},"
            f"speedup_vs_graphmp_c={t/base:.2f}x;read_bytes_iter={reads[name]:.0f}"
        )


def fig11_memory(rows: List[str]) -> None:
    """Resident bytes per engine: VSW holds vertices + cache; baselines
    hold a partition's worth (Table II memory column, measured)."""
    g = _mk_graph(seed=2)
    V, E = g.num_vertices, g.num_edges
    C, D = 4, 8
    p = IOParams(C=C, D=D, V=V, E=E, P=SHARDS, N=1, theta=0.0)
    for key, model in MODELS.items():
        rows.append(
            f"fig11_memory_model_{key},{model.memory(p):.0f},analytic_bytes"
        )
    with tempfile.TemporaryDirectory() as d:
        eng = VSWEngine.from_graph(
            g, d, num_shards=SHARDS, cache_bytes=1 << 30, cache_mode=3,
        )
        eng.run(apps.pagerank(), max_iters=3)
        resident = 2 * C * V + eng.cache.stored_bytes
        rows.append(
            f"fig11_memory_graphmp_measured,{resident},"
            f"cache_stored={eng.cache.stored_bytes}"
            f";compression={eng.cache.stats.compression_ratio:.2f}x"
        )


def table2_io(rows: List[str]) -> None:
    # the paper's EU-2015 point, analytic
    p = IOParams(C=4, D=8, V=1.07e9, E=91.8e9, P=4096, N=24, theta=0.3)
    t = io_table(p)
    for key, vals in t.items():
        rows.append(
            f"table2_io_eu2015_{key},{vals['read']:.3e},"
            f"write={vals['write']:.3e};memory={vals['memory']:.3e}"
        )
    # measured-vs-analytic on the real engines (edge-stream term dominates)
    g = _mk_graph(seed=3)
    with tempfile.TemporaryDirectory() as d:
        store = prepare_baseline_store(g, d, num_shards=SHARDS)
        pp = IOParams(C=4, D=8, V=g.num_vertices, E=g.num_edges, P=SHARDS)
        for name, cls in (("esg", ESGEngine), ("dsw", DSWEngine)):
            io0 = store.io.snapshot()
            r = cls(store).run(apps.pagerank(), max_iters=3)
            measured = (store.io - io0).bytes_read / r.num_iterations
            predicted = MODELS[name].read(pp)
            rows.append(
                f"table2_io_validation_{name},{measured:.0f},"
                f"analytic={predicted:.0f};ratio={measured/predicted:.2f}"
            )


def fig3_pipeline(rows: List[str], *, quick: bool = False) -> None:
    """Pipelined vs synchronous VSW (paper §II-C / Fig. 3).

    Cache-miss-heavy config: no edge cache, throttled storage channel —
    every planned shard pays a real (emulated-HDD) read.  The synchronous
    engine serializes read -> decode -> compute; the pipelined engine runs
    ``prefetch_depth`` loader threads ahead of the consumer and batches
    consecutive shards into one kernel dispatch, so read latency and
    dispatch overhead leave the critical path.
    """
    if quick:
        g = rmat_graph(5_000, 80_000, seed=5)
        iters, shards = 4, 6
    else:
        g = _mk_graph(seed=5)
        iters, shards = 8, SHARDS
    cases = [
        ("sync", dict(prefetch_depth=0, batch_shards=1)),
        ("pipelined", dict(prefetch_depth=4, batch_shards=4)),
    ]
    results = {}
    for name, kw in cases:
        with tempfile.TemporaryDirectory() as d:
            eng = VSWEngine.from_graph(
                g, d, num_shards=shards, backend="jnp", selective=False,
                cache_bytes=0, emulate_bw=DISK_BW, **kw,
            )
            eng.run(apps.pagerank(), max_iters=1)  # warm jit caches
            t0 = time.perf_counter()
            r = eng.run(apps.pagerank(), max_iters=iters)
            wall = time.perf_counter() - t0
            results[name] = (wall / r.num_iterations, r)
            eng.close()
    t_sync, _ = results["sync"]
    t_pipe, rp = results["pipelined"]
    overlap = rp.total_load_overlap_s / rp.num_iterations
    dispatches = rp.iterations[-1].dispatches
    for name, (t, _) in results.items():
        rows.append(
            f"fig3_pipeline_pagerank_{name},{t*1e6:.0f},"
            f"speedup_vs_sync={t_sync/max(t,1e-12):.2f}x"
            + (f";overlap_s_iter={overlap:.4f}"
               f";dispatches_iter={dispatches}" if name == "pipelined" else "")
        )


def fig_serve(rows: List[str], *, quick: bool = False) -> None:
    """GraphServe lane batching: throughput and per-query read volume at
    lane budgets K ∈ {1, 4, 16} (ISSUE 2 acceptance).

    Cache-miss-heavy config — no edge cache, no session cache, throttled
    storage channel — so every planned shard pays a real (emulated-HDD)
    read and the ONLY amortization is the lane batching itself.  The
    workload is personalized PageRank (dense activity, fixed iteration
    budget): K=1 degenerates to sequential single-query sweeps, so
    bytes-read-per-query should drop ≈ K-fold at K lanes.  One K=16 result
    is checked bitwise against a solo single-query oracle run.
    """
    from repro.serve import GraphService

    if quick:
        g = rmat_graph(5_000, 80_000, seed=6)
        n_queries, iters, shards = 16, 3, 6
    else:
        g = _mk_graph(seed=6)
        n_queries, iters, shards = 32, 5, SHARDS
    rng = np.random.default_rng(7)
    sources = rng.choice(g.num_vertices, size=n_queries,
                         replace=False).astype(int)

    bytes_per_query: Dict[int, float] = {}
    for lanes in (1, 4, 16):
        with tempfile.TemporaryDirectory() as d:
            # max_groups=1: measure lane batching alone — the fusion-group
            # dimension (which would give even K=1 a second concurrent
            # group) is fig_fusion's subject.
            with GraphService.from_graph(
                g, d, num_shards=shards, backend="numpy",
                max_lanes=lanes, session_entries=0, max_groups=1,
                cache_bytes=0, emulate_bw=DISK_BW,
            ) as svc:
                t0 = time.perf_counter()
                futs = [svc.submit("ppr", int(s), max_iters=iters)
                        for s in sources]
                results = [f.result() for f in futs]
                wall = time.perf_counter() - t0
                st = svc.stats()
                bpq = st["bytes_read_total"] / n_queries
                bytes_per_query[lanes] = bpq
                rows.append(
                    f"fig_serve_ppr_K{lanes},{wall / n_queries * 1e6:.0f},"
                    f"qps={n_queries / wall:.2f}"
                    f";bytes_per_query={bpq:.0f}"
                    f";loads_per_query={st['loads_per_query']:.2f}"
                    f";sweeps={st['sweeps']}"
                )
                # GraphScope tail latency (DESIGN.md §11): streaming
                # log-bucket percentiles with the queue-wait/sweep split.
                snap = svc.metrics_snapshot()
                lat, qw, sw = (snap["query_latency_s"],
                               snap["queue_wait_s"], snap["sweep_s"])
                rows.append(
                    f"fig_serve_latency_K{lanes},{lat['p50'] * 1e6:.0f},"
                    f"p95_ms={lat['p95'] * 1e3:.2f}"
                    f";p99_ms={lat['p99'] * 1e3:.2f}"
                    f";queue_p50_ms={qw['p50'] * 1e3:.2f}"
                    f";queue_p99_ms={qw['p99'] * 1e3:.2f}"
                    f";sweep_p99_ms={sw['p99'] * 1e3:.2f}"
                    f";conservation_violations="
                    f"{len(snap['conservation_violations'])}"
                )
                if lanes == 16:
                    batched_vals = results[0].values

    # bitwise oracle: the K=16 lane-batched result vs a solo engine run
    with tempfile.TemporaryDirectory() as d:
        eng = VSWEngine.from_graph(g, d, num_shards=shards, backend="numpy")
        solo = eng.run(apps.personalized_pagerank(source=int(sources[0])),
                       max_iters=iters)
        eng.close()
    bitwise = bool(np.array_equal(batched_vals, solo.values))
    amort = bytes_per_query[1] / max(bytes_per_query[16], 1e-9)
    rows.append(
        f"fig_serve_amortization,{amort:.2f},"
        f"bytes_per_query_K1_over_K16={amort:.2f}x"
        f";bitwise_oracle_K16={bitwise}"
    )
    assert bitwise, "lane-batched result diverged from single-query oracle"
    assert amort >= 4.0, f"K=16 amortization {amort:.2f}x below 4x floor"


def _fig_fusion_ragged(rows: List[str], *, quick: bool = False) -> None:
    """RaggedFuse dispatch-count figure (ISSUE 10 acceptance).

    A mixed min+sum workload on the jnp lane executor, run through the
    SAME FusedSweep twice: ``ragged=False`` (the PR 5 multi path — G
    launches per shard batch) and ``ragged=True`` (ONE ragged launch per
    batch).  Asserts the ragged run's dispatch count collapses from
    G x batches to batches, bitwise-identical results per lane, and
    emits the gated ``fig_fusion_dispatch_ratio`` row.
    """
    from repro.serve import FusedSweep, LaneSeed

    if quick:
        g = rmat_graph(3_000, 40_000, seed=11)
        iters, shards = 6, 6
    else:
        g = _mk_graph(seed=11)
        iters, shards = 8, SHARDS
    rng = np.random.default_rng(12)
    bfs, sssp, ppr = apps.lane_bfs(), apps.lane_sssp(), apps.lane_ppr()
    srcs = rng.choice(g.num_vertices, size=8, replace=False).astype(int)
    mk_seeds = lambda: [
        [LaneSeed(source=int(srcs[0]), max_iters=iters, token="b0",
                  program=bfs),
         LaneSeed(source=int(srcs[1]), max_iters=iters, token="s0",
                  program=sssp),
         LaneSeed(source=int(srcs[2]), max_iters=iters, token="b1",
                  program=bfs)],
        [LaneSeed(source=int(srcs[3]), max_iters=iters, token="p0",
                  program=ppr),
         LaneSeed(source=int(srcs[4]), max_iters=iters, token="p1",
                  program=ppr)],
    ]

    disp: Dict[str, int] = {}
    batches: Dict[str, int] = {}
    vals: Dict[str, Dict[str, np.ndarray]] = {}
    wall: Dict[str, float] = {}
    overlap = 0.0
    with tempfile.TemporaryDirectory() as d:
        eng = VSWEngine.from_graph(g, d, num_shards=shards, backend="jnp",
                                   batch_shards=2)
        for name, ragged in (("multi", False), ("ragged", True)):
            sweep = FusedSweep(eng, batch_shards=2, lane_selective=False,
                               ragged=ragged)
            t0 = time.perf_counter()
            res = sweep.run(mk_seeds())
            wall[name] = time.perf_counter() - t0
            disp[name] = sum(s.dispatches for s in sweep.iter_stats)
            batches[name] = sum(s.batches for s in sweep.iter_stats)
            vals[name] = {r.token: r.values for r in res}
            if ragged:
                overlap = sum(s.overlap_s for s in sweep.iter_stats)
        eng.close()

    bitwise = set(vals["multi"]) == set(vals["ragged"]) and all(
        np.array_equal(np.nan_to_num(vals["multi"][t], posinf=1e30),
                       np.nan_to_num(vals["ragged"][t], posinf=1e30))
        for t in vals["multi"]
    )
    one_launch = disp["ragged"] == batches["ragged"]
    assert bitwise, "ragged sweep diverged from the multi path"
    assert one_launch, (disp, batches)
    assert disp["multi"] > disp["ragged"], (disp, batches)
    ratio = disp["multi"] / max(disp["ragged"], 1)
    for name in ("multi", "ragged"):
        rows.append(
            f"fig_fusion_{name}_launch,{wall[name] * 1e6:.0f},"
            f"dispatches={disp[name]};batches={batches[name]}"
        )
    rows.append(
        f"fig_fusion_dispatch_ratio,{ratio:.2f},"
        f"multi_dispatches={disp['multi']}"
        f";ragged_dispatches={disp['ragged']}"
        f";batches={batches['ragged']}"
        f";overlap_s={overlap:.4f}"
        f";ragged_one_launch={one_launch}"
        f";bitwise_vs_multi={bitwise}"
    )


def fig_fusion(rows: List[str], *, quick: bool = False,
               ragged: bool = False) -> None:
    """Cross-query shard-plan fusion (ISSUE 5 acceptance).

    A mixed BFS+SSSP+PPR workload at lane budget K=16 on the
    cache-miss-heavy config (no edge cache, no session cache, throttled
    storage channel), under three serving policies:

    - ``per_group``: PR 2 key-equality batching — every program runs its
      own sweeps (``fuse_programs=False``), so G program groups pay G
      shard streams;
    - ``fused``: same-algebra programs (BFS+SSSP share the min monoid)
      fuse into ONE lane table (``max_groups=1``) — one stream for the
      min programs, another for PPR;
    - ``interleaved``: different algebra groups additionally share one
      stream (``max_groups=2``) — each loaded shard is dispatched once
      per group: G small dispatches, 1 load.

    Bytes-read-per-query must drop strictly at each step, and one result
    per program is checked bitwise against a solo single-query oracle.
    """
    from repro.serve import GraphService

    if quick:
        g = rmat_graph(5_000, 80_000, seed=9)
        iters, shards = 3, 6
    else:
        g = _mk_graph(seed=9)
        iters, shards = 5, SHARDS
    rng = np.random.default_rng(10)
    # 24 queries (8 per program): the interleaved policy fills its K=16
    # budget with one 16-lane min group + one 8-lane PPR group, while the
    # per_group baseline runs one 8-lane sweep per program
    per_prog = 16 // 2
    progs = (["bfs"] * per_prog + ["sssp"] * per_prog + ["ppr"] * per_prog)
    sources = rng.choice(g.num_vertices, size=len(progs),
                         replace=False).astype(int)
    workload = list(zip(progs, sources))
    rng.shuffle(workload)
    n_queries = len(workload)

    policies = [
        ("per_group", dict(fuse_programs=False, max_groups=1)),
        ("fused", dict(fuse_programs=True, max_groups=1)),
        ("interleaved", dict(fuse_programs=True, max_groups=2)),
    ]
    bytes_per_query: Dict[str, float] = {}
    oracle_vals: Dict[str, Dict[Tuple[str, int], np.ndarray]] = {}
    for name, kw in policies:
        with tempfile.TemporaryDirectory() as d:
            with GraphService.from_graph(
                g, d, num_shards=shards, backend="numpy",
                max_lanes=16, session_entries=0,
                cache_bytes=0, emulate_bw=DISK_BW, **kw,
            ) as svc:
                t0 = time.perf_counter()
                with svc.submit_batch():
                    futs = [svc.submit(p, int(s), max_iters=iters)
                            for p, s in workload]
                results = [f.result() for f in futs]
                wall = time.perf_counter() - t0
                st = svc.stats()
                bpq = st["bytes_read_total"] / n_queries
                bytes_per_query[name] = bpq
                oracle_vals[name] = {
                    (p, int(s)): r.values
                    for (p, s), r in zip(workload, results)
                }
                rows.append(
                    f"fig_fusion_{name},{wall / n_queries * 1e6:.0f},"
                    f"bytes_per_query={bpq:.0f}"
                    f";loads_per_query={st['loads_per_query']:.2f}"
                    f";sweeps={st['sweeps']}"
                    f";multi_group_sweeps={st['multi_group_sweeps']}"
                )

    # bitwise oracle: one result per program from the interleaved run vs
    # a solo single-query engine
    checked = {}
    with tempfile.TemporaryDirectory() as d:
        eng = VSWEngine.from_graph(g, d, num_shards=shards, backend="numpy")
        for (p, s) in workload:
            if p in checked:
                continue
            solo = eng.run(apps.get_program(p, source=int(s)),
                           max_iters=iters)
            checked[p] = bool(
                np.array_equal(oracle_vals["interleaved"][(p, int(s))],
                               solo.values)
            )
        eng.close()
    bitwise = all(checked.values())
    gain_fused = bytes_per_query["per_group"] / max(
        bytes_per_query["fused"], 1e-9)
    gain_inter = bytes_per_query["per_group"] / max(
        bytes_per_query["interleaved"], 1e-9)
    rows.append(
        f"fig_fusion_amortization,{gain_inter:.2f},"
        f"bytes_per_query_per_group_over_interleaved={gain_inter:.2f}x"
        f";over_fused={gain_fused:.2f}x"
        f";bitwise_oracle={bitwise}"
    )
    assert bitwise, "fused/interleaved result diverged from solo oracle"
    assert bytes_per_query["fused"] < bytes_per_query["per_group"], (
        "same-algebra fusion did not reduce bytes/query"
    )
    assert bytes_per_query["interleaved"] < bytes_per_query["per_group"], (
        "multi-group interleaving did not reduce bytes/query"
    )
    assert bytes_per_query["interleaved"] < bytes_per_query["fused"], (
        "interleaving gained nothing over same-algebra fusion alone"
    )
    if ragged:
        _fig_fusion_ragged(rows, quick=quick)


def fig_ingest(rows: List[str], *, quick: bool = False) -> None:
    """Streamed external build vs in-memory preprocess (ISSUE 3 tentpole).

    Both paths end in the same on-disk store (bitwise-identical shards,
    asserted); what differs is peak memory.  The in-memory path
    materializes + lexsorts the whole edge list, so its peak grows
    O(|E|); the streamed path's peak is O(chunk + budget + one shard) —
    with a fixed edges-per-shard target it must stay flat as |E| scales.
    Peaks are tracemalloc-traced allocation high-water marks (numpy
    allocations route through tracemalloc's hooks).
    """
    import gc
    import os
    import tracemalloc

    from repro.core.ingest import write_edge_file
    from repro.core.sharding import preprocess
    from repro.core.storage import ShardStore

    num_v = 20_000
    if quick:
        sizes = [100_000, 200_000, 400_000]
        edges_per_shard, chunk_edges, budget = 25_000, 10_000, 256 << 10
    else:
        sizes = [400_000, 800_000, 1_600_000]
        edges_per_shard, chunk_edges, budget = 50_000, 20_000, 1 << 20
    window, k, tr = 256, 16, 8

    peaks_stream: Dict[int, int] = {}
    for num_e in sizes:
        g = rmat_graph(num_v, num_e, seed=8)
        with tempfile.TemporaryDirectory() as d:
            path = os.path.join(d, "edges.bin")
            file_bytes = write_edge_file(path, g.src, g.dst)

            # in-memory oracle path: preprocess + write the same store
            store_m = ShardStore(os.path.join(d, "mem"))
            gc.collect()
            tracemalloc.start()
            tracemalloc.reset_peak()
            t0 = time.perf_counter()
            meta_m, shards_m = preprocess(g, edges_per_shard=edges_per_shard)
            store_m.write_meta(meta_m)
            for s in shards_m:
                store_m.write_shard(s, num_vertices=num_v, window=window,
                                    k=k, tr=tr)
            wall_mem = time.perf_counter() - t0
            peak_mem = tracemalloc.get_traced_memory()[1]
            tracemalloc.stop()
            ref = {s.shard_id: s for s in shards_m}
            del g, shards_m
            gc.collect()

            # streamed external build from the edge file
            store_s = ShardStore(os.path.join(d, "stream"))
            tracemalloc.start()
            tracemalloc.reset_peak()
            t0 = time.perf_counter()
            meta_s, stats = store_s.ingest(
                path, edges_per_shard=edges_per_shard, num_vertices=num_v,
                chunk_edges=chunk_edges, mem_budget_bytes=budget,
                window=window, k=k, tr=tr,
            )
            wall_stream = time.perf_counter() - t0
            peak_stream = tracemalloc.get_traced_memory()[1]
            tracemalloc.stop()
            peaks_stream[num_e] = peak_stream

            # shard-by-shard bitwise oracle on a sample of shards
            step = max(1, meta_s.num_shards // 4)
            for p in range(0, meta_s.num_shards, step):
                got = store_s.load_shard(p, "csr")
                assert np.array_equal(got.row, ref[p].row)
                assert np.array_equal(got.col, ref[p].col)

            rows.append(
                f"fig_ingest_E{num_e},{wall_stream*1e6:.0f},"
                f"peak_stream_bytes={peak_stream}"
                f";peak_inmem_bytes={peak_mem}"
                f";peak_ratio={peak_mem/max(peak_stream,1):.2f}x"
                f";wall_inmem_us={wall_mem*1e6:.0f}"
                f";file_bytes={file_bytes}"
                f";spill_bytes={stats.spill_bytes_written}"
                f";bytes_written={stats.bytes_written_total}"
                f";runs={stats.runs};shards={meta_s.num_shards}"
                f";bitwise_sampled=True"
            )

    growth = peaks_stream[sizes[-1]] / max(peaks_stream[sizes[0]], 1)
    rows.append(
        f"fig_ingest_peak_growth,{growth:.2f},"
        f"stream_peak_E{sizes[-1]}_over_E{sizes[0]}={growth:.2f}x"
        f"_for_{sizes[-1]//sizes[0]}x_edges"
    )
    assert growth < 1.6, (
        f"streamed ingestion peak grew {growth:.2f}x over a "
        f"{sizes[-1]//sizes[0]}x |E| range — no longer out-of-core"
    )


def fig_mesh(rows: List[str], *, quick: bool = False) -> None:
    """Mesh-sharded VSW sweeps: one host read, D device slices (ISSUE 6
    acceptance; DESIGN.md §10).

    A PPR lane group runs under :class:`MeshSweep` at mesh sizes
    D ∈ {1, 2, 4, 8} on the cache-miss-heavy config (no edge cache,
    throttled storage channel).  The numpy emulation exercises the exact
    partition routing and accounting of the SPMD path without importing
    jax, so this section runs anywhere — the CI mesh job additionally
    runs it under 8 forced host devices.

    Invariants asserted: host-read bytes per sweep are FLAT in D (every
    planned shard is decoded ONCE and sliced per destination device — the
    mesh never multiplies host I/O), per-device shard counts sum to the
    D=1 total each iteration, and the D>1 results are bitwise equal to
    the D=1 run.
    """
    from repro.serve import LaneSeed, MeshSweep

    if quick:
        g = rmat_graph(5_000, 80_000, seed=13)
        iters, shards, lanes = 3, 6, 4
    else:
        g = _mk_graph(seed=13)
        iters, shards, lanes = 5, SHARDS, 8
    rng = np.random.default_rng(14)
    sources = rng.choice(g.num_vertices, size=lanes, replace=False)

    bytes_per_sweep: Dict[int, float] = {}
    shard_totals: Dict[int, int] = {}
    ref_vals: Dict[int, List[np.ndarray]] = {}
    for D in (1, 2, 4, 8):
        with tempfile.TemporaryDirectory() as d:
            eng = VSWEngine.from_graph(
                g, d, num_shards=shards, backend="numpy", mesh=D,
                cache_bytes=0, emulate_bw=DISK_BW,
            )
            seeds = [[LaneSeed(source=int(s), max_iters=iters,
                               program=apps.get_lane_program("ppr"))
                      for s in sources]]
            sweep = MeshSweep(eng)
            t0 = time.perf_counter()
            res = sweep.run(seeds)
            wall = time.perf_counter() - t0
            its = sweep.iter_stats
            for it in its:
                assert sum(it.device_shards) == it.shards_processed, (
                    f"D={D}: device shard counts not conserved"
                )
            total_bytes = sum(it.bytes_read for it in its)
            total_shards = sum(it.shards_processed for it in its)
            total_disp = sum(sum(it.device_dispatches) for it in its)
            bytes_per_sweep[D] = total_bytes / max(len(its), 1)
            shard_totals[D] = total_shards
            ref_vals[D] = [r.values for r in res]
            eng.close()
            rows.append(
                f"fig_mesh_ppr_D{D},{wall / max(len(its), 1) * 1e6:.0f},"
                f"bytes_per_sweep={bytes_per_sweep[D]:.0f}"
                f";shards_total={total_shards}"
                f";device_dispatches_total={total_disp}"
                f";sweeps={len(its)}"
            )

    flat = bytes_per_sweep[8] / max(bytes_per_sweep[1], 1e-9)
    bitwise = all(
        np.array_equal(a, b)
        for D in (2, 4, 8)
        for a, b in zip(ref_vals[1], ref_vals[D])
    )
    rows.append(
        f"fig_mesh_host_read_flatness,{flat:.4f},"
        f"bytes_per_sweep_D8_over_D1={flat:.4f}x"
        f";shards_conserved="
        f"{all(shard_totals[D] == shard_totals[1] for D in (2, 4, 8))}"
        f";bitwise_vs_D1={bitwise}"
    )
    assert bitwise, "mesh results diverged from the D=1 run"
    assert abs(flat - 1.0) < 0.01, (
        f"host-read bytes scaled {flat:.4f}x from D=1 to D=8 — the mesh "
        "must slice ONE host read, never multiply it"
    )
    assert all(shard_totals[D] == shard_totals[1] for D in (2, 4, 8)), (
        "per-device shard counts no longer sum to the D=1 total"
    )


def fig_delta(rows: List[str], *, quick: bool = False) -> None:
    """Sweep cost vs pending-delta fraction (ISSUE 4 tentpole).

    A store absorbing updates pays an overlay merge on every decode of a
    dirty shard (and ELL consumers decode via CSR + a host ``csr_to_ell``);
    recompaction folds the runs into new base shards and restores the
    clean-store cost.  This section publishes insert+delete batches sized
    to a fraction of |E|, measures a fixed-iteration PageRank sweep at each
    state, and asserts the bitwise oracle (a fresh in-memory preprocess of
    the mutated edge list on the same intervals) before AND after
    recompaction.
    """
    import os

    from repro.core.graph import Graph
    from repro.core.ingest import write_edge_file
    from repro.core.sharding import build_shards
    from repro.core.storage import ShardStore
    from repro.delta import EdgeLog, Recompactor

    rng = np.random.default_rng(21)
    if quick:
        num_v, num_e, shards, fracs, iters = 10_000, 100_000, 8, [0.05, 0.2], 3
    else:
        num_v, num_e, shards, fracs, iters = 20_000, 400_000, 8, [0.05, 0.2, 0.5], 3
    window, k, tr = 256, 16, 8
    g = rmat_graph(num_v, num_e, seed=21)

    def sweep_cost(store):
        eng = VSWEngine(store, backend="numpy", selective=False)
        io0 = store.io.snapshot()
        t0 = time.perf_counter()
        res = eng.run(apps.pagerank(), max_iters=iters)
        wall = time.perf_counter() - t0
        dio = store.io - io0
        eng.close()
        return res.values, wall, dio.bytes_read

    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "edges.bin")
        write_edge_file(path, g.src, g.dst)
        store = ShardStore(os.path.join(d, "live"))
        meta, _ = store.ingest(path, num_shards=shards, num_vertices=num_v,
                               window=window, k=k, tr=tr)
        base_vals, base_wall, base_bytes = sweep_cost(store)
        rows.append(
            f"fig_delta_clean,{base_wall*1e6:.0f},"
            f"bytes_read={base_bytes};pending_frac=0.00"
        )

        src, dst = g.src, g.dst
        log = EdgeLog(store)
        applied = 0.0
        for frac in fracs:
            n_mut = int(num_e * (frac - applied))
            applied = frac
            ins = (rng.integers(0, num_v, n_mut // 2),
                   rng.integers(0, num_v, n_mut // 2))
            take = rng.choice(len(src), n_mut // 2, replace=False)
            dels = (src[take], dst[take])
            log.append(inserts=ins, deletes=dels)
            pub = log.publish()
            # oracle edge state
            tomb = np.unique((dels[1].astype(np.int64) << 32)
                             | dels[0].astype(np.int64))
            keys = (dst.astype(np.int64) << 32) | src.astype(np.int64)
            pos = np.minimum(np.searchsorted(tomb, keys), len(tomb) - 1)
            keep = tomb[pos] != keys
            src = np.concatenate([src[keep], ins[0].astype(np.int32)])
            dst = np.concatenate([dst[keep], ins[1].astype(np.int32)])

            vals, wall, bytes_read = sweep_cost(store)
            pend_bytes = sum(store.delta.pending_stats(p)[3]
                             for p in store.delta.dirty_shards())
            rows.append(
                f"fig_delta_overlay_f{frac:.2f},{wall*1e6:.0f},"
                f"bytes_read={bytes_read}"
                f";overhead_vs_clean={wall/max(base_wall,1e-9):.2f}x"
                f";pending_run_bytes={pend_bytes}"
                f";dirty_shards={len(store.delta.dirty_shards())}"
                f";version={pub.version}"
            )

        # bitwise oracle on the overlay, then recompact and re-check
        mg = Graph(num_v, src, dst)
        ref = {s.shard_id: s for s in build_shards(mg, meta.intervals)}
        for p in range(0, meta.num_shards, max(1, meta.num_shards // 4)):
            got = store.load_shard(p, "csr")
            assert np.array_equal(got.col, ref[p].col)
        t0 = time.perf_counter()
        cst = Recompactor(store).compact()
        compact_wall = time.perf_counter() - t0
        vals_c, wall_c, bytes_c = sweep_cost(store)
        assert np.array_equal(vals, vals_c), "recompaction changed results"
        for p in range(0, meta.num_shards, max(1, meta.num_shards // 4)):
            got = store.load_shard(p, "csr")
            assert np.array_equal(got.col, ref[p].col)
        rows.append(
            f"fig_delta_compacted,{wall_c*1e6:.0f},"
            f"bytes_read={bytes_c}"
            f";overhead_vs_clean={wall_c/max(base_wall,1e-9):.2f}x"
            f";compact_wall_us={compact_wall*1e6:.0f}"
            f";runs_absorbed={cst.runs_absorbed}"
            f";shards_compacted={cst.shards_compacted}"
            f";bitwise_sampled=True"
        )


def fig_obs(rows: List[str], *, quick: bool = False) -> None:
    """GraphScope disabled-tracer overhead guard (ISSUE 7 acceptance).

    Wall-clock A/B of a traced vs untraced sweep is CI-noise-dominated at
    smoke scale, so the guard is analytic and stable: measure the
    disabled-path cost of one ``trace.span()`` call site (a module-global
    load + None check + no-op context manager) in ns, count the span
    events an ENABLED run of the same config actually records, and assert
    that ``events x ns_per_call`` — the total the instrumentation points
    can possibly add when tracing is off — is under 5 % of the untraced
    sweep wall time.  The direct on/off wall ratio is reported (not
    asserted) alongside.
    """
    if quick:
        g = rmat_graph(5_000, 80_000, seed=8)
        iters, shards = 3, 6
    else:
        g = _mk_graph(seed=8)
        iters, shards = 5, SHARDS

    # fig_obs must measure the DISABLED path even under ``--trace``.
    prev = trace.active()
    if prev is not None:
        trace.uninstall()
    try:
        n_calls = 200_000
        t0 = time.perf_counter()
        for _ in range(n_calls):
            with trace.span("bench.noop", shard=3):
                pass
        ns_per_call = (time.perf_counter() - t0) / n_calls * 1e9

        def sweep() -> float:
            with tempfile.TemporaryDirectory() as d:
                eng = VSWEngine.from_graph(
                    g, d, num_shards=shards, backend="numpy",
                    selective=False, cache_bytes=0, prefetch_depth=2,
                )
                t0 = time.perf_counter()
                eng.run(apps.pagerank(), max_iters=iters)
                wall = time.perf_counter() - t0
                eng.close()
                return wall

        walls_off = [sweep() for _ in range(3)]
        t_off = min(walls_off[1:])  # first run warms allocator/page caches

        tracer = Tracer(capacity=1 << 18)
        with trace.tracing(tracer):
            t_on = min(sweep() for _ in range(2))
        n_events = tracer.event_count()
        assert n_events > 0, "enabled run recorded no span events"

        est_pct = n_events * ns_per_call / (t_off * 1e9) * 100.0
        rows.append(
            f"fig_obs_nullspan,{ns_per_call / 1e3:.4f},"
            f"ns_per_call={ns_per_call:.1f}"
        )
        rows.append(
            f"fig_obs_overhead,{t_off * 1e6:.0f},"
            f"est_disabled_overhead_pct={est_pct:.4f}"
            f";span_events={n_events}"
            f";traced_over_untraced={t_on / t_off:.3f}"
            f";dropped_events={tracer.export_chrome()['otherData']['dropped_events']}"
        )
        assert est_pct < 5.0, (
            f"disabled-tracer overhead estimate {est_pct:.2f}% "
            f"({n_events} events x {ns_per_call:.0f}ns) exceeds 5% budget"
        )
    finally:
        if prev is not None:
            trace.install(prev)


def fig_restart(rows: List[str], *, quick: bool = False) -> None:
    """Cold boot vs warm-state restart (ISSUE 8, DESIGN.md §12).

    A cold ``GraphService`` boot reads every shard once to build the
    scheduler's Bloom/exact filters; a warm boot restores the source
    arrays (and the session cache) from a :mod:`repro.checkpoint.
    warm_state` snapshot and reads NOTHING.  Both boots run under the
    ``emulate_bw`` throttle so the read cost is deterministic wall time,
    and the warm boot is ASSERTED faster — plus zero boot reads, a
    session-cache hit on the repeat query, and bitwise-equal values on a
    never-cached query.
    """
    import os

    from repro.serve import GraphService

    if quick:
        num_v, num_e, shards, bw = 10_000, 120_000, 8, 40e6
    else:
        num_v, num_e, shards, bw = 20_000, 500_000, 8, 40e6
    g = rmat_graph(num_v, num_e, seed=12)
    cb = 32 << 20

    with tempfile.TemporaryDirectory() as d:
        root = os.path.join(d, "store")
        ckdir = os.path.join(d, "warm")
        svc = GraphService.from_graph(
            g, root, num_shards=shards, window=256, k=16, tr=8,
            backend="numpy", cache_bytes=cb,
        )
        svc.apply_updates(
            inserts=(np.array([1, 2, 3]), np.array([4, 5, 6]))
        ).result()
        repeat = svc.query("bfs", 0)  # the query a restarted service re-sees
        svc.save_warm_state(ckdir)
        svc.close()

        t0 = time.perf_counter()
        cold = GraphService.from_store(
            root, emulate_bw=bw, backend="numpy", cache_bytes=cb
        )
        cold_wall = time.perf_counter() - t0
        cold_io = cold.engine.loading_io
        t0 = time.perf_counter()
        cold_repeat = cold.query("bfs", 0)
        cold_first_s = time.perf_counter() - t0

        t0 = time.perf_counter()
        warm = GraphService.from_store(
            root, warm_state=ckdir, emulate_bw=bw, backend="numpy",
            cache_bytes=cb,
        )
        warm_wall = time.perf_counter() - t0
        warm_io = warm.engine.loading_io
        rep = warm.warm_restore_report
        t0 = time.perf_counter()
        warm_repeat = warm.query("bfs", 0)
        warm_first_s = time.perf_counter() - t0

        # the acceptance assertions: faster, read-free, bitwise, cache-hot
        assert rep["valid"] and rep["shards_warm"] == shards, rep
        assert warm_io.reads == 0 and warm_io.bytes_read == 0
        assert warm_wall < cold_wall, (
            f"warm boot {warm_wall:.3f}s not faster than cold {cold_wall:.3f}s"
        )
        assert warm_repeat.cached and not cold_repeat.cached
        assert np.array_equal(warm_repeat.values, repeat.values)
        assert np.array_equal(cold_repeat.values, repeat.values)
        fresh_w = warm.query("sssp", 9)
        fresh_c = cold.query("sssp", 9)
        assert np.array_equal(fresh_w.values, fresh_c.values)

        rows.append(
            f"fig_restart_cold_boot,{cold_wall*1e6:.0f},"
            f"boot_reads={cold_io.reads}"
            f";boot_bytes={cold_io.bytes_read}"
            f";first_query_us={cold_first_s*1e6:.0f}"
        )
        rows.append(
            f"fig_restart_warm_boot,{warm_wall*1e6:.0f},"
            f"boot_reads={warm_io.reads}"
            f";boot_bytes={warm_io.bytes_read}"
            f";first_query_us={warm_first_s*1e6:.0f}"
            f";boot_speedup={cold_wall/max(warm_wall,1e-9):.2f}x"
            f";shards_warm={rep['shards_warm']}"
            f";sessions_restored={rep['sessions_restored']}"
            f";first_answer_speedup="
            f"{(cold_wall+cold_first_s)/max(warm_wall+warm_first_s,1e-9):.2f}x"
        )
        cold.close()
        warm.close()


def fig_qps(rows: List[str], *, quick: bool = False) -> None:
    """GraphPulse closed-loop load harness + SLO gates (DESIGN.md §13).

    A seeded mixed BFS/SSSP/WCC/PPR workload with a concurrent mutation
    stream replays against a live ``GraphService`` in both load-gen
    modes, with the telemetry ticker and an SLO monitor running:

    - closed loop (fixed concurrency, ``submit_batch`` chunks) reports
      sustained QPS plus exact p50/p99 with the queue-wait vs sweep
      split;
    - open loop (arrival-scheduled at a target QPS) reports offered vs
      achieved rate — queueing delay measured, not hidden;
    - every completed query is replayed on a solo oracle engine built at
      exactly its ``graph_version`` and asserted ``np.array_equal``;
    - the SLO monitor (generous objectives a healthy run cannot breach)
      is asserted violation-free — the no-false-positives gate;
    - the Prometheus and JSONL exports are parsed back, proving the
      telemetry is machine-readable end to end.
    """
    import os

    from repro.obs import (
        error_rate_slo,
        latency_slo,
        parse_prometheus,
        prometheus_text,
        read_jsonl,
        share_slo,
        write_jsonl,
    )
    from repro.serve import (
        GraphService,
        LoadGenerator,
        QueryClass,
        Workload,
        edge_state_at_version,
        oracle_kwargs,
    )

    if quick:
        g = rmat_graph(5_000, 80_000, seed=13)
        shards, total_ops, warmup, iters = 6, 48, 8, 4
        concurrency, target_qps = 4, 120.0
    else:
        g = _mk_graph(seed=13)
        shards, total_ops, warmup, iters = SHARDS, 160, 24, 6
        concurrency, target_qps = 8, 60.0
    wl = Workload(
        classes=(
            QueryClass("bfs", weight=2.0, max_iters=iters),
            QueryClass("sssp", weight=1.0, max_iters=iters),
            QueryClass("wcc", weight=1.0, max_iters=iters),
            QueryClass("ppr", weight=1.0, max_iters=iters,
                       params={"damping": 0.85}),
        ),
        seed=29,
        update_every=total_ops // 3,
        update_batch=16,
    )
    slos = [
        latency_slo("latency_p99", threshold_s=30.0, budget=0.01),
        error_rate_slo("admission_errors", budget=0.05),
        share_slo("queue_wait_share", budget=0.95),
    ]
    with tempfile.TemporaryDirectory() as d:
        with GraphService.from_graph(
            g, os.path.join(d, "store"), num_shards=shards,
            backend="numpy", max_lanes=16, session_entries=0,
        ) as svc:
            svc.start_telemetry(interval_s=0.1, slos=slos)
            rep_c = LoadGenerator(
                svc, wl, mode="closed", concurrency=concurrency,
                batch_size=4, total_ops=total_ops, warmup_ops=warmup,
            ).run()
            rep_o = LoadGenerator(
                svc, wl, mode="open", target_qps=target_qps, poisson=True,
                total_ops=total_ops // 2, warmup_ops=warmup // 2,
            ).run()
            snap = svc.metrics_snapshot()
            win = svc.metrics_snapshot(window=True)
            prom = prometheus_text(svc.metrics)
            prom_samples = parse_prometheus(prom)
            ts = svc.stop_telemetry()
            jsonl_path = os.path.join(d, "pulse.jsonl")
            write_jsonl(jsonl_path, ts)
            windows = read_jsonl(jsonl_path)

        # bitwise oracle: replay EVERY completed query at its version
        all_recs = [r for r in rep_c.records + rep_o.records if r.ok]
        all_upds = rep_c.updates + rep_o.updates
        base_edges = np.stack([g.src, g.dst], axis=1)
        norm = lambda v: np.nan_to_num(v, posinf=1e30)
        checked = 0
        for v in sorted({r.graph_version for r in all_recs}):
            g_v = from_edge_list(
                edge_state_at_version(base_edges, all_upds, v),
                g.num_vertices,
            )
            eng = VSWEngine.from_graph(
                g_v, os.path.join(d, f"oracle{v}"), num_shards=shards,
                backend="numpy",
            )
            for r in all_recs:
                if r.graph_version != v:
                    continue
                solo = eng.run(
                    apps.get_program(r.program, **oracle_kwargs(r)),
                    max_iters=r.max_iters,
                )
                assert np.array_equal(norm(solo.values), norm(r.values)), (
                    v, r.program, r.source,
                )
                checked += 1
            eng.close()

    violations = snap["slo"]["violations"]
    lat, qw, sw = rep_c.latency, rep_c.queue_wait, win["sweep_s"]
    rows.append(
        f"fig_qps_closed,{1e6 / max(rep_c.qps, 1e-9):.0f},"
        f"qps={rep_c.qps:.2f}"
        f";p50_ms={lat['p50'] * 1e3:.2f}"
        f";p99_ms={lat['p99'] * 1e3:.2f}"
        f";queue_p99_ms={qw['p99'] * 1e3:.2f}"
        f";queue_wait_share={rep_c.queue_wait_share:.3f}"
        f";completed={rep_c.completed}"
        f";updates_published={rep_c.updates_published}"
    )
    rows.append(
        f"fig_qps_open,{1e6 / max(rep_o.qps, 1e-9):.0f},"
        f"qps={rep_o.qps:.2f}"
        f";offered_qps={rep_o.offered_qps:.2f}"
        f";p99_ms={rep_o.latency['p99'] * 1e3:.2f}"
        f";rejected={rep_o.rejected}"
        f";completed={rep_o.completed}"
    )
    rows.append(
        f"fig_qps_gates,{checked},"
        f"oracle_checked={checked}"
        f";bitwise_oracle=True"
        f";slo_violations={len(violations)}"
        f";slo_evaluations={snap['slo']['evaluations']}"
        f";prom_samples={len(prom_samples)}"
        f";jsonl_windows={len(windows)}"
        f";conservation_violations={len(snap['conservation_violations'])}"
    )
    # the gates: healthy run -> no violations, parseable exports, oracle
    assert checked == len(all_recs) and checked > 0
    assert not violations, f"false SLO violations on a healthy run: {violations}"
    assert len(snap["conservation_violations"]) == 0
    assert len(prom_samples) > 0 and len(windows) > 0
    assert rep_c.completed == rep_c.submitted and rep_c.errors == 0
    assert rep_o.errors == 0


SECTIONS = {
    "fig5_selective": lambda rows, quick: fig5_selective(rows),
    "fig8_10_engines": lambda rows, quick: fig8_10_engines(rows),
    "fig11_memory": lambda rows, quick: fig11_memory(rows),
    "table2_io": lambda rows, quick: table2_io(rows),
    "fig3_pipeline": lambda rows, quick: fig3_pipeline(rows, quick=quick),
    "fig_serve": lambda rows, quick: fig_serve(rows, quick=quick),
    "fig_fusion": lambda rows, quick: fig_fusion(rows, quick=quick),
    "fig_ingest": lambda rows, quick: fig_ingest(rows, quick=quick),
    "fig_mesh": lambda rows, quick: fig_mesh(rows, quick=quick),
    "fig_delta": lambda rows, quick: fig_delta(rows, quick=quick),
    "fig_obs": lambda rows, quick: fig_obs(rows, quick=quick),
    "fig_restart": lambda rows, quick: fig_restart(rows, quick=quick),
    "fig_qps": lambda rows, quick: fig_qps(rows, quick=quick),
}


def run(rows: List[str], *, quick: bool = False,
        sections: Optional[List[str]] = None, ragged: bool = False) -> None:
    # ``ragged`` only augments fig_fusion (the RaggedFuse dispatch-count
    # sub-figure); every other section ignores it.
    def _dispatch(name: str) -> None:
        if name == "fig_fusion":
            fig_fusion(rows, quick=quick, ragged=ragged)
        else:
            SECTIONS[name](rows, quick)

    if sections:
        for name in sections:
            if name not in SECTIONS:
                raise SystemExit(
                    f"unknown section {name!r}; have {sorted(SECTIONS)}"
                )
            _dispatch(name)
        return
    if quick:
        fig3_pipeline(rows, quick=True)
        fig_serve(rows, quick=True)
        fig_fusion(rows, quick=True, ragged=ragged)
        fig_ingest(rows, quick=True)
        fig_mesh(rows, quick=True)
        fig_delta(rows, quick=True)
        fig_obs(rows, quick=True)
        fig_restart(rows, quick=True)
        fig_qps(rows, quick=True)
        return
    for name in SECTIONS:
        _dispatch(name)


def merge_consolidated(path: str, rows: List[str], *, quick: bool,
                       wall_s: float) -> Dict:
    """Append this run's rows to the persistent perf trajectory at ``path``.

    The consolidated file keeps one time-ordered list of samples per row
    name (``trajectory[name] -> [{ts, us_per_call, derived, quick}, ...]``)
    plus a run log, so CI artifacts accumulate a cross-PR perf history in
    ONE ``BENCH_graphmp.json`` instead of a scatter of per-section files.
    A missing or corrupt file starts a fresh trajectory rather than
    failing the bench run.
    """
    import json

    try:
        with open(path) as f:
            doc = json.load(f)
        if not isinstance(doc, dict) or "trajectory" not in doc:
            raise ValueError("not a consolidated bench file")
    except (OSError, ValueError):
        doc = {"bench": "graphmp", "trajectory": {}, "runs": []}
    ts = time.strftime("%Y-%m-%dT%H:%M:%S")
    doc.setdefault("runs", []).append(
        {"ts": ts, "quick": quick, "wall_s": wall_s, "num_rows": len(rows)}
    )
    traj = doc.setdefault("trajectory", {})
    for r in rows:
        name, us, derived = r.split(",", 2)
        traj.setdefault(name, []).append(
            {"ts": ts, "us_per_call": us, "derived": derived, "quick": quick}
        )
    with open(path, "w") as f:
        json.dump(doc, f, indent=2)
    return doc


def main() -> None:
    """Standalone entry point (CI smoke mode emits a BENCH_*.json)."""
    import argparse
    import json

    ap = argparse.ArgumentParser()
    ap.add_argument("sections", nargs="*", metavar="section",
                    help=f"benchmark sections to run (default: all, or the "
                         f"smoke subset with --quick); one of "
                         f"{sorted(SECTIONS)}")
    ap.add_argument("--quick", action="store_true",
                    help="small graphs, smoke subset (pipeline + serve)")
    ap.add_argument("--ragged", action="store_true",
                    help="add the RaggedFuse dispatch-count sub-figure to "
                         "fig_fusion (jnp lane executor, one ragged launch "
                         "per batch vs G; DESIGN.md §14)")
    ap.add_argument("--out", default=None,
                    help="also write rows as JSON to this path")
    ap.add_argument("--consolidated", default=None, metavar="PATH",
                    help="merge rows into a persistent perf-trajectory JSON "
                         "(appends per-name samples; creates the file if "
                         "missing)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="run with the GraphScope tracer installed and "
                         "export a Chrome-trace JSON (Perfetto-loadable) "
                         "to PATH")
    args = ap.parse_args()
    enable_compile_cache()

    tracer = None
    if args.trace:
        tracer = trace.install(Tracer(capacity=1 << 18))

    rows: List[str] = []
    t0 = time.perf_counter()
    run(rows, quick=args.quick, sections=args.sections or None,
        ragged=args.ragged)
    wall = time.perf_counter() - t0

    if tracer is not None:
        trace.uninstall()
        doc = tracer.export_chrome(args.trace)
        print(f"# wrote trace {args.trace}: {len(doc['traceEvents'])} events "
              f"across {len(tracer.thread_names())} threads "
              f"(dropped={doc['otherData']['dropped_events']})")
    print("name,us_per_call,derived")
    for r in rows:
        print(r)
    if args.out:
        payload = {
            "bench": "graphmp",
            "quick": args.quick,
            "wall_s": wall,
            "rows": [
                dict(zip(("name", "us_per_call", "derived"), r.split(",", 2)))
                for r in rows
            ],
        }
        with open(args.out, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"# wrote {args.out}")
    if args.consolidated:
        merge_consolidated(args.consolidated, rows, quick=args.quick,
                           wall_s=wall)
        print(f"# merged {len(rows)} rows into {args.consolidated}")


if __name__ == "__main__":
    main()
