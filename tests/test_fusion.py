"""Cross-query fusion: heterogeneous programs on one shard stream.

The fusion contract extends PR 2's (test_serve.py): mixing programs in a
sweep — same-algebra programs FUSED into one lane table, different algebra
groups INTERLEAVED on one stream — must be invisible in the results.
Every query must be bitwise-equal to the same query run alone on a
single-query engine, across programs (BFS / SSSP / WCC / PPR), backends,
mid-sweep retirement and cross-group backfill, and graph updates between
sweeps.  Cost attribution must be mask-aware AND conserved: the per-lane
bytes/loads of a sweep sum to exactly what the sweep read.

jax-backend tests carry ``e2e`` in their names so the RLIMIT_AS runner
(run_memcapped.py) can exclude them.
"""

import dataclasses
import math
from collections import deque

import numpy as np
import pytest

from repro.core import apps
from repro.core.cache import CacheStats, mode_iteration_cost
from repro.core.graph import Graph, chain_graph, rmat_graph
from repro.core.sharding import preprocess
from repro.core.vsw import VSWEngine
from repro.serve import FusedSweep, GraphService, LaneBatcher, LaneSeed

# (program, source) workloads mixing all three min-algebra programs + PPR
MIXED = [("bfs", 0), ("sssp", 3), ("wcc", 1), ("ppr", 5), ("bfs", 7),
         ("ppr", 11), ("sssp", 2), ("wcc", 9)]


def _norm(v):
    return np.nan_to_num(v, posinf=1e30)


def _mk_service(tmp_path, tag, g, **kw):
    kw.setdefault("num_shards", 6)
    kw.setdefault("window", 128)
    kw.setdefault("k", 16)
    return GraphService.from_graph(g, str(tmp_path / tag), **kw)


def _mk_engine(tmp_path, tag, g, **kw):
    kw.setdefault("num_shards", 6)
    kw.setdefault("window", 128)
    kw.setdefault("k", 16)
    return VSWEngine.from_graph(g, str(tmp_path / tag), **kw)


def _solo(eng, program, source, max_iters):
    kw = {} if program == "wcc" else {"source": source}
    return eng.run(apps.get_program(program, **kw), max_iters=max_iters)


# ------------------------------------------------------------ program keys
def test_combine_key_splits_from_program_key():
    bfs, sssp, wcc = apps.lane_bfs(), apps.lane_sssp(), apps.lane_wcc()
    ppr1, ppr2 = apps.lane_ppr(0.85), apps.lane_ppr(0.5)
    # same algebra -> same fusion identity, distinct full keys
    assert bfs.combine_key == sssp.combine_key == wcc.combine_key == ("min",)
    assert len({bfs.key, sssp.key, wcc.key}) == 3
    # PPR variants fuse with each other but never with the min programs
    assert ppr1.combine_key == ppr2.combine_key == ("sum",)
    assert ppr1.key != ppr2.key
    assert ppr1.combine_key != bfs.combine_key


def test_lane_wcc_matches_vertex_program_oracle(tmp_path):
    g = rmat_graph(300, 3000, seed=60)
    eng = _mk_engine(tmp_path, "wccref", g, backend="numpy")
    svc = _mk_service(tmp_path, "wccsvc", g, backend="numpy", max_lanes=4)
    qr = svc.query("wcc", 0, max_iters=50)
    ref = eng.run(apps.wcc(), max_iters=50)
    assert np.array_equal(_norm(qr.values), _norm(ref.values))
    assert qr.converged == ref.converged
    svc.close()
    eng.close()


# -------------------------------------------------------- batcher formation
def test_batcher_forms_fusion_sets():
    @dataclasses.dataclass
    class P:
        key: tuple
        combine_key: tuple
        n: int

    def mk(name, ck, n):
        return P((name,), ck, n)

    pending = deque([
        mk("bfs", ("min",), 0), mk("ppr", ("sum",), 1),
        mk("sssp", ("min",), 2), mk("wcc", ("min",), 3),
        mk("ppr", ("sum",), 4), mk("bfs", ("min",), 5),
    ])
    b = LaneBatcher(max_lanes=3, max_groups=2)
    groups = b.form_fused(pending)
    # group 0: oldest request's algebra (min), capped at max_lanes;
    # group 1: the next algebra in FIFO order (sum)
    assert [p.n for p in groups[0]] == [0, 2, 3]
    assert [p.n for p in groups[1]] == [1, 4]
    assert [p.n for p in pending] == [5]  # leftover keeps order

    # key-only mode restores PR 2 batching: identical program keys only
    pending = deque([
        mk("bfs", ("min",), 0), mk("sssp", ("min",), 1),
        mk("bfs", ("min",), 2),
    ])
    b = LaneBatcher(max_lanes=4, max_groups=1, fuse_programs=False)
    groups = b.form_fused(pending)
    assert [p.n for p in groups[0]] == [0, 2]
    assert [p.n for p in pending] == [1]


# ------------------------------------------------- fused same-algebra sweeps
def test_fused_min_programs_single_sweep_bitwise(tmp_path):
    """BFS + SSSP + WCC share one lane table and ONE sweep; every result
    bitwise-equals its solo single-query run."""
    g = rmat_graph(500, 6000, seed=61)
    svc = _mk_service(tmp_path, "svc", g, backend="numpy", max_lanes=8,
                      max_groups=1)
    eng = _mk_engine(tmp_path, "eng", g, backend="numpy")
    cases = [(p, s) for p, s in MIXED if p != "ppr"]
    with svc.submit_batch():
        futs = [svc.submit(p, s, max_iters=25) for p, s in cases]
    for (p, s), f in zip(cases, futs):
        qr = f.result(timeout=120)
        ref = _solo(eng, p, s, 25)
        assert np.array_equal(_norm(qr.values), _norm(ref.values)), (p, s)
        assert qr.iterations == ref.num_iterations
        assert qr.converged == ref.converged
    assert svc.stats()["sweeps"] == 1  # all three programs fused
    svc.close()
    eng.close()


def test_interleaved_groups_single_sweep_bitwise(tmp_path):
    """min-algebra and PPR groups interleave on ONE shard stream."""
    g = rmat_graph(500, 6000, seed=62)
    svc = _mk_service(tmp_path, "svc", g, backend="numpy", max_lanes=8,
                      max_groups=2)
    eng = _mk_engine(tmp_path, "eng", g, backend="numpy")
    with svc.submit_batch():
        futs = [svc.submit(p, s, max_iters=20) for p, s in MIXED]
    for (p, s), f in zip(MIXED, futs):
        qr = f.result(timeout=120)
        ref = _solo(eng, p, s, 20)
        assert np.array_equal(_norm(qr.values), _norm(ref.values)), (p, s)
        assert qr.groups == 2
    st = svc.stats()
    assert st["sweeps"] == 1 and st["multi_group_sweeps"] == 1
    svc.close()
    eng.close()


@pytest.mark.parametrize("backend,batch_shards", [("jnp", 1), ("jnp", 3),
                                                  ("pallas", 2)])
def test_interleaved_groups_bitwise_e2e(tmp_path, backend, batch_shards):
    """Fusion + interleaving + shard batching on the ELL backends: each
    query equals the same backend's single-query run bitwise."""
    g = rmat_graph(300, 3500, seed=63)
    svc = _mk_service(tmp_path, f"s{backend}{batch_shards}", g, num_shards=5,
                      backend=backend, max_lanes=8, max_groups=2,
                      batch_shards=batch_shards)
    eng = _mk_engine(tmp_path, f"e{backend}{batch_shards}", g, num_shards=5,
                     backend=backend, batch_shards=batch_shards)
    cases = [("bfs", 2), ("wcc", 0), ("ppr", 3), ("sssp", 1), ("ppr", 9)]
    with svc.submit_batch():
        futs = [svc.submit(p, s, max_iters=12) for p, s in cases]
    for (p, s), f in zip(cases, futs):
        qr = f.result(timeout=240)
        ref = _solo(eng, p, s, 12)
        assert np.array_equal(_norm(qr.values), _norm(ref.values)), (p, s)
    assert svc.stats()["sweeps"] == 1
    svc.close()
    eng.close()


# -------------------------------------------- retirement / cross-group fill
def test_retirement_and_backfill_across_groups(tmp_path):
    """Early-retiring lanes in each group are backfilled from the queue
    mid-sweep — min-algebra and PPR queues drain through ONE sweep."""
    n = 64
    g = chain_graph(n)
    svc = _mk_service(tmp_path, "bf", g, num_shards=4, backend="numpy",
                      max_lanes=3, max_groups=2)
    # 4 min-algebra queries (chain sources converge at wildly different
    # iterations) interleaved with 3 PPR queries, on 3 lanes per group:
    # bfs source 0 overflows group 0 and must be backfilled mid-sweep.
    cases = [("bfs", 60), ("ppr", 0), ("bfs", 55), ("ppr", 1),
             ("bfs", 40), ("ppr", 2), ("bfs", 0)]
    with svc.submit_batch():
        futs = [svc.submit(p, s, max_iters=200 if p == "bfs" else 6)
                for p, s in cases]
    eng = _mk_engine(tmp_path, "bfref", g, num_shards=4, backend="numpy")
    for (p, s), f in zip(cases, futs):
        qr = f.result(timeout=240)
        ref = _solo(eng, p, s, 200 if p == "bfs" else 6)
        assert np.array_equal(_norm(qr.values), _norm(ref.values)), (p, s)
    st = svc.stats()
    assert st["sweeps"] == 1 and st["queries_completed"] == 7
    svc.close()
    eng.close()


def test_fused_sweep_direct_backfill_and_zero_budget(tmp_path):
    """FusedSweep API: per-group backfill callbacks, zero-budget seeds
    finished at admission (initial AND backfilled) without taking lanes."""
    g = chain_graph(48)
    eng = _mk_engine(tmp_path, "direct", g, num_shards=4, backend="numpy")
    bfs, ppr = apps.lane_bfs(), apps.lane_ppr()
    queues = {
        0: [LaneSeed(source=20, max_iters=0, token="z1", program=bfs),
            LaneSeed(source=1, max_iters=200, token="b1", program=bfs)],
        1: [LaneSeed(source=3, max_iters=0, token="z2", program=ppr)],
    }

    def backfill(group, n_free):
        out = queues[group][:n_free]
        del queues[group][:n_free]
        return out

    sweep = FusedSweep(eng)
    results = sweep.run(
        [[LaneSeed(source=44, max_iters=200, token="b0", program=bfs),
          LaneSeed(source=40, max_iters=0, token="z0", program=bfs)],
         [LaneSeed(source=0, max_iters=4, token="p0", program=ppr)]],
        backfill=backfill,
    )
    by_token = {r.token: r for r in results}
    assert set(by_token) == {"b0", "b1", "p0", "z0", "z1", "z2"}
    # zero-budget parity: init values, zero iterations, not converged
    for tok, src, prog in (("z0", 40, "bfs"), ("z1", 20, "bfs"),
                           ("z2", 3, "ppr")):
        r = by_token[tok]
        assert r.iterations == 0 and not r.converged
        assert r.bytes_read == 0.0 and r.shard_loads == 0.0
        ref = _solo(eng, prog, src, 0)
        assert np.array_equal(_norm(r.values), _norm(ref.values))
    # live lanes still bitwise vs solo
    for tok, src, prog, iters in (("b0", 44, "bfs", 200),
                                  ("b1", 1, "bfs", 200), ("p0", 0, "ppr", 4)):
        ref = _solo(eng, prog, src, iters)
        assert np.array_equal(_norm(by_token[tok].values), _norm(ref.values))
    assert sum(s.backfilled for s in sweep.iter_stats) == 1  # only b1
    eng.close()


def test_service_zero_budget_matches_engine(tmp_path):
    g = rmat_graph(200, 2000, seed=64)
    svc = _mk_service(tmp_path, "zb", g, backend="numpy", max_lanes=2)
    eng = _mk_engine(tmp_path, "zbref", g, backend="numpy")
    qr = svc.query("wcc", 5, max_iters=0)
    ref = eng.run(apps.wcc(), max_iters=0)
    assert qr.iterations == 0 and not qr.converged
    assert np.array_equal(_norm(qr.values), _norm(ref.values))
    svc.close()
    eng.close()


# ----------------------------------------------------- cost attribution
def test_cost_attribution_conserved_and_mask_aware(tmp_path):
    """Per-lane bytes/loads sum to the sweep totals exactly, and a lane
    masked out of most of the stream is charged less than an always-on
    lane (ROADMAP mask-aware cost attribution follow-on)."""
    n = 96
    g = chain_graph(n)
    eng = _mk_engine(tmp_path, "cost", g, num_shards=6, backend="numpy",
                     threshold=1.0, cache_bytes=0)
    bfs, wcc = apps.lane_bfs(), apps.lane_wcc()
    sweep = FusedSweep(eng)
    results = sweep.run(
        [[LaneSeed(source=90, max_iters=300, token="fast", program=bfs),
          LaneSeed(source=0, max_iters=300, token="slow", program=bfs),
          LaneSeed(source=1, max_iters=300, token="dense", program=wcc)]],
    )
    total_loads = sum(s.shards_processed for s in sweep.iter_stats)
    total_bytes = sum(s.bytes_read for s in sweep.iter_stats)
    got_loads = sum(r.shard_loads for r in results)
    got_bytes = sum(r.bytes_read for r in results)
    assert math.isclose(got_loads, total_loads, rel_tol=1e-9)
    assert math.isclose(got_bytes, total_bytes, rel_tol=1e-9)
    # mask-awareness: the BFS frontier near the chain end touches one
    # shard per iteration while WCC's dense frontier needs all of them —
    # even-split attribution would charge both lanes identically.
    by = {r.token: r for r in results}
    assert by["fast"].shard_loads < by["dense"].shard_loads
    assert sum(s.lane_rows_skipped for s in sweep.iter_stats) > 0
    eng.close()


def test_plan_lane_shares_sum_to_planned(tmp_path):
    g = rmat_graph(600, 4000, seed=65)
    eng = _mk_engine(tmp_path, "shares", g, num_shards=8, backend="numpy",
                     threshold=1.0)
    lane_active = [np.array([3], dtype=np.int64),
                   np.array([577], dtype=np.int64),
                   np.arange(0, 600, 7, dtype=np.int64)]
    union = np.unique(np.concatenate(lane_active))
    plan = eng.scheduler.plan(union, lane_active=lane_active)
    shares = plan.lane_shares(3)
    assert shares.shape == (3,)
    assert math.isclose(shares.sum(), plan.num_planned, rel_tol=1e-9)
    # unmasked plans split evenly
    full = eng.scheduler.plan(np.arange(600, dtype=np.int64))
    assert np.allclose(full.lane_shares(4), full.num_planned / 4)
    assert full.lane_shares(0).shape == (0,)
    eng.close()


# ------------------------------------------------- updates between sweeps
def test_apply_updates_between_fused_sweeps_per_version_oracle(tmp_path):
    """Mixed-program serving across a live mutation: every result must
    match a from-scratch engine built at exactly its graph_version."""
    rng = np.random.default_rng(66)
    num_v, num_e = 300, 3000
    g = rmat_graph(num_v, num_e, seed=66)
    svc = _mk_service(tmp_path, "upd", g, backend="numpy", max_lanes=4,
                      max_groups=2, session_entries=0)

    cases = [("bfs", 3), ("wcc", 0), ("ppr", 7), ("sssp", 11)]
    # resolved BEFORE the update is even staged: deterministically version 0
    with svc.submit_batch():
        futs_pre = [svc.submit(p, s, max_iters=15) for p, s in cases]
    res_pre = [f.result(timeout=240) for f in futs_pre]

    # stage a mutation while a fresh batch may or may not have formed: the
    # version TAG on each result decides which oracle it must match
    with svc.submit_batch():
        futs0 = [svc.submit(p, s + 20, max_iters=15) for p, s in cases]
    take = rng.choice(num_e, 200, replace=False)
    dels = (g.src[take], g.dst[take])
    ins = (rng.integers(0, num_v, 150).astype(np.int32),
           rng.integers(0, num_v, 150).astype(np.int32))
    upd = svc.apply_updates(inserts=ins, deletes=dels).result(timeout=240)
    assert upd.graph_version == 1

    # submitted after the publish resolved: deterministically version 1
    with svc.submit_batch():
        futs1 = [svc.submit(p, s, max_iters=15) for p, s in cases]
    res0 = [f.result(timeout=240) for f in futs0]
    res1 = [f.result(timeout=240) for f in futs1]

    # version-1 edge state (delete = all copies, deletes before inserts)
    tomb = np.unique((dels[1].astype(np.int64) << 32)
                     | dels[0].astype(np.int64))
    keys = (g.dst.astype(np.int64) << 32) | g.src.astype(np.int64)
    pos = np.minimum(np.searchsorted(tomb, keys), len(tomb) - 1)
    keep = tomb[pos] != keys
    g1 = Graph(num_v,
               np.concatenate([g.src[keep], ins[0]]).astype(np.int32),
               np.concatenate([g.dst[keep], ins[1]]).astype(np.int32))
    oracles = {0: _mk_engine(tmp_path, "v0", g, backend="numpy"),
               1: _mk_engine(tmp_path, "v1", g1, backend="numpy")}
    checks = (
        [(p, s, qr) for (p, s), qr in zip(cases, res_pre)]
        + [(p, s + 20, qr) for (p, s), qr in zip(cases, res0)]
        + [(p, s, qr) for (p, s), qr in zip(cases, res1)]
    )
    for p, s, qr in checks:
        eng = oracles[qr.graph_version]
        ref = _solo(eng, p, s, 15)
        assert np.array_equal(_norm(qr.values), _norm(ref.values)), (
            p, s, qr.graph_version)
    assert all(q.graph_version == 0 for q in res_pre)
    assert all(q.graph_version == 1 for q in res1)
    for eng in oracles.values():
        eng.close()
    svc.close()


# ------------------------------------------------------- property stress
def test_property_mixed_workload_stress(tmp_path):
    """Seeded random mixed workloads: any combination of programs, sources
    and budgets, with more queries than lanes (forcing retirement +
    backfill across groups), stays bitwise vs solo."""
    g = rmat_graph(400, 5000, seed=67)
    eng = _mk_engine(tmp_path, "stressref", g, backend="numpy")
    refs = {}
    for trial in range(3):
        rng = np.random.default_rng(100 + trial)
        svc = _mk_service(tmp_path, f"stress{trial}", g, backend="numpy",
                          max_lanes=4, max_groups=2, session_entries=0)
        progs = ["bfs", "sssp", "wcc", "ppr"]
        cases = []
        for _ in range(12):
            p = progs[int(rng.integers(len(progs)))]
            s = int(rng.integers(g.num_vertices))
            iters = int(rng.integers(0, 18))
            cases.append((p, s, iters))
        with svc.submit_batch():
            futs = [svc.submit(p, s, max_iters=it) for p, s, it in cases]
        for (p, s, it), f in zip(cases, futs):
            qr = f.result(timeout=240)
            ck = (p, s, it)
            if ck not in refs:
                refs[ck] = _solo(eng, p, s, it)
            ref = refs[ck]
            assert np.array_equal(_norm(qr.values), _norm(ref.values)), ck
            assert qr.iterations == ref.num_iterations
        svc.close()
    eng.close()


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_property_mixed_workload_stress_backends_e2e(tmp_path, backend):
    g = rmat_graph(250, 2500, seed=68)
    eng = _mk_engine(tmp_path, f"pref{backend}", g, num_shards=4,
                     backend=backend, batch_shards=2)
    svc = _mk_service(tmp_path, f"p{backend}", g, num_shards=4,
                      backend=backend, batch_shards=2, max_lanes=4,
                      max_groups=2, session_entries=0)
    rng = np.random.default_rng(69)
    progs = ["bfs", "sssp", "wcc", "ppr"]
    cases = [(progs[int(rng.integers(len(progs)))],
              int(rng.integers(g.num_vertices)), int(rng.integers(1, 10)))
             for _ in range(8)]
    with svc.submit_batch():
        futs = [svc.submit(p, s, max_iters=it) for p, s, it in cases]
    for (p, s, it), f in zip(cases, futs):
        qr = f.result(timeout=300)
        ref = _solo(eng, p, s, it)
        assert np.array_equal(_norm(qr.values), _norm(ref.values)), (p, s, it)
    svc.close()
    eng.close()


# ------------------------------------------------------- executor layer
def test_run_groups_matches_per_group_run():
    """PerShardExecutor.run_groups == one run() per group, bitwise; None
    entries produce no dispatch."""
    from repro.core.executor import ExecStats, make_lane_executor
    from repro.core.pipeline import LoadedShard

    g = rmat_graph(300, 4000, seed=70)
    meta, shards = preprocess(g, num_shards=3)
    rng = np.random.default_rng(2)
    msgs_a = rng.random((4, meta.num_vertices)).astype(np.float32)
    msgs_b = rng.random((2, meta.num_vertices)).astype(np.float32)
    loaded = [LoadedShard(s.shard_id, s, None) for s in shards]
    ex = make_lane_executor("numpy")
    stats = ExecStats()
    got = {}
    for gi, res in ex.run_groups(
        loaded, [(msgs_a, "min"), None, (msgs_b, "sum")], stats
    ):
        got.setdefault(gi, []).append(res)
    assert set(got) == {0, 2}
    assert stats.dispatches == 2 * len(shards)
    for gi, msgs, combine in ((0, msgs_a, "min"), (2, msgs_b, "sum")):
        solo = list(ex.run(loaded, msgs, combine))
        for a, b in zip(got[gi], solo):
            assert a.shard_id == b.shard_id
            assert np.array_equal(a.acc, b.acc)


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_batched_run_groups_matches_per_group_run_e2e(backend):
    """The lane executor's run_groups (one concat, one ragged launch for
    every group) must be bitwise each group's own launch, and book one
    dispatch per batch."""
    from repro.core.csr import csr_to_ell
    from repro.core.executor import ExecStats, make_lane_executor
    from repro.core.pipeline import LoadedShard

    g = rmat_graph(250, 3000, seed=71)
    meta, shards = preprocess(g, num_shards=4)
    ells = [csr_to_ell(s, meta.num_vertices, window=64, k=8, tr=8)
            for s in shards]
    loaded = [LoadedShard(s.shard_id, None, e) for s, e in zip(shards, ells)]
    rng = np.random.default_rng(3)
    msgs_a = rng.random((2, meta.num_vertices)).astype(np.float32)
    msgs_b = rng.random((4, meta.num_vertices)).astype(np.float32)
    ex = make_lane_executor(backend, batch_shards=3)
    stats = ExecStats()
    got = {}
    for gi, res in ex.run_groups(loaded, [(msgs_a, "sum"), (msgs_b, "min")],
                                 stats):
        got.setdefault(gi, []).append(res)
    assert stats.dispatches == stats.batches == 2  # 4 shards, 3 a batch
    for gi, msgs, combine in ((0, msgs_a, "sum"), (1, msgs_b, "min")):
        solo = list(ex.run(loaded, msgs, combine))
        for a, b in zip(got[gi], solo):
            assert a.shard_id == b.shard_id
            assert np.array_equal(a.acc, b.acc)


# ----------------------------------------------------- cache-model fixes
def test_mode_iteration_cost_amortizes_compression():
    """The one-time compression cost must count, amortized over the cache
    lifetime — the pre-fix model dropped it entirely."""
    # everything fits cached either way; raw has zero codec cost
    base = dict(capacity_bytes=1 << 30, total_raw_bytes=1 << 20,
                disk_bw=100e6)
    raw = mode_iteration_cost(1.0, 0.0, 0.0, **base)
    # a codec with heavy compression cost and cheap decompression: with a
    # short lifetime the compression dominates; amortized over a long
    # lifetime it fades
    slow_short = mode_iteration_cost(4.0, 1e-6, 1e-9, lifetime_iters=1,
                                     **base)
    slow_long = mode_iteration_cost(4.0, 1e-6, 1e-9, lifetime_iters=1000,
                                    **base)
    assert raw < slow_short  # compression cost now visible
    assert slow_long < slow_short  # and amortized by lifetime
    # when compression unlocks hit rate, it still wins despite its cost
    tight = dict(capacity_bytes=1 << 18, total_raw_bytes=1 << 20,
                 disk_bw=100e6)
    assert (mode_iteration_cost(4.0, 1e-8, 1e-9, **tight)
            < mode_iteration_cost(1.0, 0.0, 0.0, **tight))


def test_select_cache_mode_still_prefers_raw_when_everything_fits():
    from repro.core.cache import select_cache_mode

    compressible = b"xy" * 100_000
    assert select_cache_mode(compressible, capacity_bytes=1 << 30,
                             total_raw_bytes=200_000) == 1


def test_cache_stats_reset_clears_eviction_and_time_counters():
    st = CacheStats(hits=3, misses=4, evictions=5,
                    inserted_bytes_raw=100, inserted_bytes_stored=50,
                    compress_time_s=1.5, decompress_time_s=2.5)
    st.reset_counters()
    assert st.hits == st.misses == st.evictions == 0
    assert st.compress_time_s == 0.0 and st.decompress_time_s == 0.0
    # capacity-describing fields survive a counter reset
    assert st.inserted_bytes_raw == 100 and st.inserted_bytes_stored == 50


# ------------------------------------------------------------- amortization
def test_fused_sweep_reads_less_than_per_group_sweeps(tmp_path):
    """The acceptance direction of fig_fusion at test scale: a mixed
    workload served fused+interleaved reads fewer bytes per query than
    PR 2 key-equality batching (per-group sweeps)."""
    g = rmat_graph(400, 6000, seed=72)
    workload = [("bfs", 0), ("sssp", 1), ("ppr", 2), ("bfs", 3),
                ("ppr", 4), ("sssp", 5), ("wcc", 6), ("ppr", 7)]
    bytes_per_query = {}
    for mode, kw in (
        ("baseline", dict(fuse_programs=False, max_groups=1)),
        ("fused", dict(fuse_programs=True, max_groups=1)),
        ("interleaved", dict(fuse_programs=True, max_groups=2)),
    ):
        svc = _mk_service(tmp_path, mode, g, backend="numpy", max_lanes=8,
                          session_entries=0, cache_bytes=0, **kw)
        with svc.submit_batch():
            futs = [svc.submit(p, s, max_iters=6) for p, s in workload]
        for f in futs:
            f.result(timeout=240)
        st = svc.stats()
        bytes_per_query[mode] = st["bytes_read_total"] / len(workload)
        svc.close()
    assert bytes_per_query["fused"] < bytes_per_query["baseline"]
    assert bytes_per_query["interleaved"] < bytes_per_query["baseline"]
    assert bytes_per_query["interleaved"] < bytes_per_query["fused"]
