"""GraphServe: lane-batched sweeps must be invisible in the results.

Every lane of a concurrent sweep must be bitwise-equal to the same query
run alone on a single-query engine — across programs (BFS / SSSP / PPR),
backends, shard batching, lane retirement and mid-flight backfill — and
the service must survive concurrent submission.
"""

import tempfile
import threading
import time

import numpy as np
import pytest

from repro.core import apps
from repro.core.executor import (
    make_lane_executor,
    update_shard_numpy,
    update_shard_numpy_lanes,
    update_shard_jnp,
)
from repro.core.graph import chain_graph, rmat_graph
from repro.core.sharding import preprocess
from repro.core.vsw import VSWEngine
from repro.serve import (
    GraphService,
    LaneBatcher,
    LaneSeed,
    LaneSweep,
    ServiceOverloaded,
    SessionCache,
    pad_lanes,
)

PROGRAMS = [("bfs", 0), ("bfs", 7), ("sssp", 3), ("ppr", 5), ("ppr", 11)]


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


# ------------------------------------------------------------ lane backends
def test_lane_backend_rows_are_bitwise_single_lane():
    """Each row of the numpy lane oracle and of a one-group jnp ragged
    launch is bitwise that lane's single-query update."""
    g = rmat_graph(300, 4000, seed=40)
    meta, shards = preprocess(g, num_shards=3)
    rng = np.random.default_rng(1)
    msgs = rng.random((4, meta.num_vertices)).astype(np.float32)
    from repro.core.csr import csr_to_ell
    from repro.core.pipeline import LoadedShard

    ex = make_lane_executor("jnp")
    for combine in ("sum", "min", "max"):
        for s in shards:
            lanes_np = update_shard_numpy_lanes(s, None, msgs, combine)
            ell = csr_to_ell(s, meta.num_vertices, window=64, k=8, tr=8)
            (res,) = ex.run([LoadedShard(s.shard_id, s, ell)], msgs, combine)
            lanes_jnp = res.acc
            assert lanes_jnp.shape == (4, ell.rows)
            for l in range(4):
                assert np.array_equal(
                    lanes_np[l], update_shard_numpy(s, None, msgs[l], combine)
                )
                assert np.array_equal(
                    lanes_jnp[l], update_shard_jnp(s, ell, msgs[l], combine)
                )


def test_make_lane_executor_selection():
    """numpy lanes run the per-shard oracle; the ELL backends run the
    ragged launch at every batch size."""
    from repro.core.executor import PerShardExecutor, RaggedLaneExecutor

    for bs in (1, 4):
        ex = make_lane_executor("numpy", batch_shards=bs)
        assert isinstance(ex, PerShardExecutor) and ex.lanes
    for backend in ("jnp", "pallas"):
        for bs in (1, 2):
            ex = make_lane_executor(backend, batch_shards=bs)
            assert isinstance(ex, RaggedLaneExecutor)
            assert ex.batch_shards == bs
    with pytest.raises(ValueError):
        make_lane_executor("nope")
    with pytest.raises(ValueError):
        PerShardExecutor("jnp", lanes=True)  # no per-shard ELL lane route


# ----------------------------------------------- bitwise oracle equivalence
def test_lane_sweep_bitwise_equals_oracle_every_program(tmp_path):
    """The headline contract: K concurrent lanes == K independent
    single-query numpy-oracle runs, bitwise, for every program."""
    g = rmat_graph(500, 6000, seed=41)
    svc = _mk_service(tmp_path, "svc", g, backend="numpy", max_lanes=8)
    eng = _mk_engine(tmp_path, "eng", g, backend="numpy")
    futs = [svc.submit(p, s, max_iters=25) for p, s in PROGRAMS]
    for (p, s), f in zip(PROGRAMS, futs):
        qr = f.result(timeout=120)
        ref = eng.run(apps.get_program(p, source=s), max_iters=25)
        assert np.array_equal(_norm(qr.values), _norm(ref.values)), (p, s)
        assert qr.iterations == ref.num_iterations
        assert qr.converged == ref.converged
    svc.close()
    eng.close()


@pytest.mark.parametrize("backend,batch_shards", [("jnp", 1), ("pallas", 3)])
def test_lane_sweep_bitwise_matches_single_backend(tmp_path, backend,
                                                   batch_shards):
    """Lane + shard batching must also be invisible on the ELL backends:
    each lane equals the same backend's single-query run bitwise."""
    g = rmat_graph(300, 3500, seed=42)
    svc = _mk_service(tmp_path, f"s{backend}", g, num_shards=5,
                      backend=backend, max_lanes=4, batch_shards=batch_shards)
    eng = _mk_engine(tmp_path, f"e{backend}", g, num_shards=5,
                     backend=backend, batch_shards=batch_shards)
    cases = [("sssp", 2), ("ppr", 3), ("bfs", 0)]
    futs = [svc.submit(p, s, max_iters=12) for p, s in cases]
    for (p, s), f in zip(cases, futs):
        qr = f.result(timeout=240)
        ref = eng.run(apps.get_program(p, source=s), max_iters=12)
        assert np.array_equal(_norm(qr.values), _norm(ref.values)), (p, s)
    svc.close()
    eng.close()


# ------------------------------------------------- retirement and backfill
def test_lane_retirement_and_backfill_mid_flight(tmp_path):
    """Lanes converge at different iterations; freed slots are refilled
    mid-sweep and every result still matches its solo oracle run."""
    n = 64
    g = chain_graph(n)
    eng = _mk_engine(tmp_path, "chain", g, num_shards=4, backend="numpy")
    prog = apps.lane_bfs()
    # sources near the chain end converge fast, source 0 is the long tail
    queue = [LaneSeed(source=s, max_iters=200, token=s) for s in (40, 0)]

    def backfill(n_free):
        out = queue[:n_free]
        del queue[:n_free]
        return out

    sweep = LaneSweep(eng, prog)
    results = sweep.run(
        [LaneSeed(source=60, max_iters=200, token=60),
         LaneSeed(source=55, max_iters=200, token=55)],
        backfill=backfill,
    )
    assert sorted(r.token for r in results) == [0, 40, 55, 60]
    assert sum(s.backfilled for s in sweep.iter_stats) == 2
    assert sum(s.retired for s in sweep.iter_stats) == 4
    # retirement is strictly before the sweep's end for the fast lanes
    assert any(s.retired and s.live_lanes > 1 for s in sweep.iter_stats)
    for r in results:
        ref = eng.run(apps.bfs(source=r.token), max_iters=200)
        assert np.array_equal(_norm(r.values), _norm(ref.values)), r.token
        assert r.iterations == ref.num_iterations and r.converged
    eng.close()


def test_service_backfills_within_one_sweep(tmp_path):
    """More compatible queries than lanes: early retirees make room, so one
    sweep serves them all (no second cold start)."""
    g = chain_graph(48)
    svc = _mk_service(tmp_path, "bf", g, num_shards=4, backend="numpy",
                      max_lanes=2)
    futs = [svc.submit("bfs", s, max_iters=100) for s in (44, 40, 20, 1)]
    for f in futs:
        assert f.result(timeout=120).converged
    assert svc.stats()["sweeps"] == 1
    assert svc.stats()["queries_completed"] == 4
    svc.close()


# --------------------------------------------------------------- threading
def test_multithreaded_submit_stress(tmp_path):
    g = rmat_graph(400, 5000, seed=43)
    svc = _mk_service(tmp_path, "mt", g, backend="numpy", max_lanes=8)
    eng = _mk_engine(tmp_path, "mtref", g, backend="numpy")
    refs = {
        (p, s): eng.run(apps.get_program(p, source=s), max_iters=15).values
        for p, s in PROGRAMS
    }
    errors = []

    def client(seed):
        rng = np.random.default_rng(seed)
        try:
            for _ in range(6):
                p, s = PROGRAMS[int(rng.integers(len(PROGRAMS)))]
                qr = svc.submit(p, s, max_iters=15).result(timeout=240)
                if not np.array_equal(_norm(qr.values), _norm(refs[(p, s)])):
                    errors.append((p, s))
        except Exception as e:  # pragma: no cover
            errors.append(repr(e))

    threads = [threading.Thread(target=client, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    st = svc.stats()
    assert st["queries_completed"] + st["session_hits"] == 8 * 6
    svc.close()
    eng.close()


# ------------------------------------------------- sessions, admission, etc
def test_session_cache_and_version_bump(tmp_path):
    g = rmat_graph(300, 3000, seed=44)
    svc = _mk_service(tmp_path, "sess", g, backend="numpy", max_lanes=4)
    a = svc.query("bfs", 3, max_iters=50)
    b = svc.query("bfs", 3, max_iters=50)
    assert not a.cached and b.cached
    assert np.array_equal(_norm(a.values), _norm(b.values))
    assert b.shard_loads == 0.0  # cache hits cost no I/O
    # different static params are a different session key
    c = svc.query("ppr", 3, max_iters=10, damping=0.85)
    d = svc.query("ppr", 3, max_iters=10, damping=0.5)
    assert not c.cached and not d.cached
    svc.bump_graph_version()
    e = svc.query("bfs", 3, max_iters=50)
    assert not e.cached
    assert np.array_equal(_norm(a.values), _norm(e.values))
    svc.close()


def test_zero_iteration_budget_matches_engine(tmp_path):
    """max_iters=0 parity: zero iterations, init values, not converged —
    exactly what ``VSWEngine.run(..., max_iters=0)`` returns."""
    g = rmat_graph(200, 2000, seed=49)
    svc = _mk_service(tmp_path, "zi", g, backend="numpy", max_lanes=2)
    eng = _mk_engine(tmp_path, "ziref", g, backend="numpy")
    qr = svc.query("sssp", 5, max_iters=0)
    ref = eng.run(apps.sssp(5), max_iters=0)
    assert qr.iterations == 0 and not qr.converged
    assert np.array_equal(_norm(qr.values), _norm(ref.values))
    svc.close()
    eng.close()


def test_cached_values_survive_caller_mutation(tmp_path):
    """A caller mutating its result in place must not poison later hits."""
    g = rmat_graph(200, 2000, seed=50)
    svc = _mk_service(tmp_path, "mut", g, backend="numpy", max_lanes=2)
    a = svc.query("bfs", 2, max_iters=30)
    pristine = a.values.copy()
    a.values[:] = -1.0  # caller-side in-place mutation
    b = svc.query("bfs", 2, max_iters=30)
    assert b.cached
    assert np.array_equal(_norm(b.values), _norm(pristine))
    svc.close()


def test_session_cache_predicate_counts_unsuitable_as_miss():
    cache = SessionCache(capacity=4)
    cache.put("k", 10)
    assert cache.get("k", lambda v: v > 50) is None  # present but unsuitable
    assert cache.hits == 0 and cache.misses == 1
    assert cache.get("k", lambda v: v > 5) == 10
    assert cache.hits == 1 and cache.misses == 1


def test_session_cache_lru_eviction():
    cache = SessionCache(capacity=2)
    cache.put("a", 1)
    cache.put("b", 2)
    assert cache.get("a") == 1  # refreshes recency
    cache.put("c", 3)  # evicts b
    assert cache.get("b") is None
    assert cache.get("a") == 1 and cache.get("c") == 3
    assert len(cache) == 2


def test_admission_cap_raises(tmp_path):
    g = rmat_graph(200, 2000, seed=45)
    svc = _mk_service(tmp_path, "cap", g, backend="numpy", max_lanes=2,
                      max_pending=0)
    with pytest.raises(ServiceOverloaded):
        svc.submit("bfs", 0)
    svc.close()


def test_batcher_grouping_and_padding():
    from collections import deque
    import dataclasses

    @dataclasses.dataclass
    class P:
        key: tuple
        n: int

    pending = deque([P(("bfs",), 0), P(("ppr", 0.85), 1), P(("bfs",), 2),
                     P(("ppr", 0.85), 3), P(("bfs",), 4)])
    b = LaneBatcher(max_lanes=2)
    batch = b.form(pending)
    assert [p.n for p in batch] == [0, 2]  # oldest key, FIFO, capped at 2
    assert [p.n for p in pending] == [1, 3, 4]  # others keep order
    assert b.capacity(3) == 4 and b.capacity(1) == 1
    assert [pad_lanes(n) for n in (0, 1, 2, 3, 5, 16)] == [1, 1, 2, 4, 8, 16]


def test_union_plan_is_superset_of_each_lane(tmp_path):
    """Scheduler contract: a shard is skipped only when NO lane needs it."""
    g = rmat_graph(600, 4000, seed=46)
    eng = _mk_engine(tmp_path, "union", g, num_shards=8, backend="numpy",
                     threshold=1.0)
    ids_a = np.array([3], dtype=np.int64)
    ids_b = np.array([577], dtype=np.int64)
    union = np.union1d(ids_a, ids_b)
    pa, pb, pu = (eng.scheduler.plan(i) for i in (ids_a, ids_b, union))
    assert set(pa.shards) | set(pb.shards) <= set(pu.shards)
    eng.close()


# ---------------------------------------------------------------- lifecycle
def test_close_idempotent_and_context_managers(tmp_path):
    g = rmat_graph(200, 2000, seed=47)
    with _mk_engine(tmp_path, "ctx_eng", g, backend="numpy",
                    prefetch_depth=2) as eng:
        eng.run(apps.pagerank(), max_iters=2)
    eng.close()  # second close after __exit__: must be a no-op
    eng.close()
    with _mk_service(tmp_path, "ctx_svc", g, backend="numpy",
                     max_lanes=2) as svc:
        assert svc.query("bfs", 0, max_iters=20).converged
    svc.close()
    svc.close()
    with pytest.raises(RuntimeError):
        svc.submit("bfs", 1)


def test_shard_load_amortization(tmp_path):
    """K lanes share every load: attributed loads/query drop ~K-fold for a
    dense-activity program with a fixed iteration budget."""
    g = rmat_graph(400, 6000, seed=48)
    sources = list(range(8))
    loads = {}
    for k in (1, 8):
        svc = _mk_service(tmp_path, f"amort{k}", g, backend="numpy",
                          max_lanes=k, session_entries=0)
        futs = [svc.submit("ppr", s, max_iters=4) for s in sources]
        for f in futs:
            f.result(timeout=240)
        loads[k] = svc.stats()["loads_per_query"]
        svc.close()
    assert loads[1] >= 4 * loads[8]  # acceptance floor (exact ratio: 8x)


# ------------------- satellite: close() joins in-flight background compaction
def test_close_joins_inflight_compaction(tmp_path):
    """close() must not release the engine while a background compaction
    still holds shard locks: it blocks until the recompactor's maintenance
    thread — including a compaction it is mid-way through — has fully
    exited.  Concurrent closers all observe the same guarantee."""
    from repro.delta.recovery import set_crash_hook

    g = rmat_graph(300, 4000, seed=33)
    svc = _mk_service(tmp_path, "closecomp", g, backend="numpy",
                      num_shards=4, auto_compact_runs=1)
    entered, release = threading.Event(), threading.Event()

    def hook(name):
        if name == "compact.staged":
            entered.set()
            release.wait(10)  # hold the compaction mid-swap

    set_crash_hook(hook)
    try:
        svc.apply_updates(inserts=(np.arange(20) % 300,
                                   (np.arange(20) * 7) % 300)).result()
        assert entered.wait(10), "background compaction never started"

        done = [threading.Event() for _ in range(2)]

        def closer(ev):
            svc.close()
            ev.set()

        threads = [threading.Thread(target=closer, args=(ev,)) for ev in done]
        for t in threads:
            t.start()
        time.sleep(0.2)
        # the compaction is parked inside the hook -> no closer may return
        assert not any(ev.is_set() for ev in done)
        release.set()
        for t in threads:
            t.join(10)
        assert all(ev.is_set() for ev in done)
    finally:
        set_crash_hook(None)
        release.set()
        svc.close()
    # the held compaction ran to completion before close returned
    assert svc.engine.store.delta.dirty_shards() == []
    assert svc._recompactor is None
