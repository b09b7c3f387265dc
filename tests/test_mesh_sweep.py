"""Mesh-sharded VSW sweeps: one host read, D device slices (DESIGN.md §10).

The mesh contract, tested three ways:

1. **Partition algebra** — :func:`equal_device_bounds` /
   :class:`MeshPartition` put every destination interval on exactly one
   device (the paper's lock-free property lifted to SPMD), and the
   device-layout builders (legacy ``build_device_graph`` vs the PR 3-era
   ``build_device_graph_from_store``) agree bitwise.
2. **Bitwise sweeps** — an engine/service booted with ``mesh=D`` produces
   results bitwise-equal to the single-device run of the same backend for
   BFS / SSSP / PPR / WCC at D ∈ {1, 2, 8}, through mid-sweep lane
   retirement/backfill and ``apply_updates`` between sweeps.  The numpy
   mesh EMULATION (no jax — safe under run_memcapped) is compared against
   the numpy oracle directly; jnp/pallas run in a subprocess under
   ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (``e2e`` names,
   like test_distributed_vsw.py).
3. **Conserved attribution** — per-device shard/dispatch/bytes stats sum
   to the sweep totals: the host read each shard ONCE, sliced per device,
   never once per device.

jax-touching tests carry ``e2e`` in their names so the RLIMIT_AS runner
(run_memcapped.py) can exclude them.
"""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import apps
from repro.core.distributed import (
    MeshPartition,
    build_device_graph,
    build_device_graph_from_store,
    equal_device_bounds,
)
from repro.core.graph import Graph, chain_graph, rmat_graph, uniform_graph
from repro.core.ingest import pack_keys
from repro.core.vsw import VSWEngine
from repro.serve import FusedSweep, GraphService, LaneSeed, MeshSweep

MESH_SIZES = (1, 2, 8)


def _norm(v):
    return np.nan_to_num(v, posinf=1e30)


def _mk_engine(tmp_path, tag, g, **kw):
    kw.setdefault("num_shards", 6)
    kw.setdefault("window", 128)
    kw.setdefault("k", 16)
    return VSWEngine.from_graph(g, str(tmp_path / tag), **kw)


def _mk_service(tmp_path, tag, g, **kw):
    kw.setdefault("num_shards", 6)
    kw.setdefault("window", 128)
    kw.setdefault("k", 16)
    return GraphService.from_graph(g, str(tmp_path / tag), **kw)


def _mutated(src, dst, ins, dels):
    """Reference edge-list semantics of apply_updates: delete ALL copies of
    the named edges, then append inserts (same as test_delta's oracle)."""
    tomb = np.unique(pack_keys(
        np.asarray(dels[0], np.int64), np.asarray(dels[1], np.int64)))
    keys = pack_keys(src.astype(np.int64), dst.astype(np.int64))
    pos = np.minimum(np.searchsorted(tomb, keys), len(tomb) - 1)
    keep = tomb[pos] != keys
    src, dst = src[keep], dst[keep]
    src = np.concatenate([src, np.asarray(ins[0], np.int32)])
    dst = np.concatenate([dst, np.asarray(ins[1], np.int32)])
    return src.astype(np.int32), dst.astype(np.int32)


# ------------------------------------------------------- partition algebra
def test_equal_device_bounds_cover_and_order():
    for nv in (1, 7, 64, 1000):
        for d in (1, 2, 3, 8):
            rows_per_dev, nv_pad, bounds = equal_device_bounds(nv, d)
            assert bounds[0] == 0 and bounds[-1] == nv
            assert np.all(np.diff(bounds) >= 0)
            assert rows_per_dev * d == nv_pad >= nv
    with pytest.raises(ValueError):
        equal_device_bounds(10, 0)


def test_mesh_partition_owns_each_shard_once(tmp_path):
    g = rmat_graph(400, 3000, seed=7)
    eng = _mk_engine(tmp_path, "own", g, num_shards=7, backend="numpy")
    for d in (1, 2, 3, 8):
        part = MeshPartition.from_meta(eng.meta, d)
        assert part.owner.shape == (eng.meta.num_shards,)
        assert part.owner.min() >= 0 and part.owner.max() < d
        # ownership follows interval starts monotonically
        assert np.all(np.diff(part.owner) >= 0)
        # group/interleave round-trip: a permutation preserving per-device
        # interval order
        ids = list(range(eng.meta.num_shards))
        groups = part.group(ids)
        assert sorted(p for gr in groups for p in gr) == ids
        inter = MeshPartition.interleave(groups)
        assert sorted(inter) == ids
        for dd, gr in enumerate(groups):
            assert all(part.device_of(p) == dd for p in gr)
            assert gr == sorted(gr)
    eng.close()


def test_mesh_partition_seeded_stress():
    rng = np.random.default_rng(17)
    for _ in range(50):
        n_shards = int(rng.integers(1, 20))
        n_dev = int(rng.integers(1, 9))
        sub = rng.permutation(n_shards)[: int(rng.integers(0, n_shards + 1))]
        sub = sorted(int(p) for p in sub)
        owner = np.sort(rng.integers(0, n_dev, n_shards)).astype(np.int32)
        part = MeshPartition(n_dev=n_dev, num_shards=n_shards, owner=owner)
        groups = part.group(sub)
        assert len(groups) == n_dev
        assert sorted(p for gr in groups for p in gr) == sub
        inter = MeshPartition.interleave(groups)
        assert sorted(inter) == sub


def test_device_graph_builders_agree(tmp_path):
    """Satellite: the legacy dry-run layout builder and the store-backed
    one (no Graph object, PR 3's contract) produce bitwise-equal device
    graphs at every mesh size."""
    g = uniform_graph(300, 2500, seed=3)
    eng = _mk_engine(tmp_path, "dg", g, num_shards=5, backend="numpy",
                     window=256, k=16)
    store = eng.store
    for d in (1, 3, 4, 8):
        dg1 = build_device_graph(g, d, window=256, k=16, tr=8)
        dg2 = build_device_graph_from_store(store, d)
        for f in ("ell_idx", "ell_valid", "seg", "out_deg"):
            assert np.array_equal(getattr(dg1, f), getattr(dg2, f)), (d, f)
        for f in ("num_vertices", "num_vertices_real", "rows_per_dev",
                  "n_dev", "n_ell_per_dev"):
            assert getattr(dg1, f) == getattr(dg2, f), (d, f)
    eng.close()


# ------------------------------------------- engine sweeps (numpy emulation)
def test_engine_mesh_numpy_bitwise_and_conserved(tmp_path):
    g = uniform_graph(500, 4000, seed=0)
    solo = _mk_engine(tmp_path, "solo", g, num_shards=8, backend="numpy")
    for D in MESH_SIZES:
        meshy = _mk_engine(tmp_path, f"m{D}", g, num_shards=8,
                           backend="numpy", mesh=D)
        for prog, kw in (("pagerank", {}), ("bfs", {"source": 0}),
                         ("sssp", {"source": 0}), ("wcc", {})):
            r1 = solo.run(apps.get_program(prog, **kw), max_iters=20)
            r2 = meshy.run(apps.get_program(prog, **kw), max_iters=20)
            assert np.array_equal(r1.values, r2.values), (D, prog)
            for it in r2.iterations:
                assert len(it.device_shards) == D
                assert sum(it.device_shards) == it.shards_processed
                assert abs(sum(it.device_bytes) - it.bytes_read) < 1e-6
        meshy.close()
    solo.close()


def test_mesh_plans_prune_idle_devices(tmp_path):
    """Selective plans leave devices whose destination intervals are all
    inactive with EMPTY groups — no host read for them."""
    n = 256
    g = chain_graph(n)
    eng = _mk_engine(tmp_path, "prune", g, num_shards=8, backend="numpy",
                     mesh=4, threshold=1.1,  # selective always on
                     exact_selective=True)   # no Bloom false positives
    plan = eng.scheduler.plan(np.asarray([0], dtype=np.int64))
    assert plan.device_shards is not None and len(plan.device_shards) == 4
    # vertex 0's only out-edge targets vertex 1 -> only device 0's shards
    assert all(len(gr) == 0 for gr in plan.device_shards[1:])
    assert sorted(p for gr in plan.device_shards for p in gr) \
        == sorted(plan.shards)
    eng.close()


# ------------------------------------------------- serving sweeps (numpy)
CASES = [("bfs", 2), ("wcc", 0), ("ppr", 3), ("sssp", 1), ("ppr", 9)]


def test_service_mesh_numpy_bitwise(tmp_path):
    g = rmat_graph(300, 3500, seed=63)
    solo = _mk_service(tmp_path, "svsolo", g, backend="numpy", max_lanes=8,
                       max_groups=2)
    refs = {c: solo.query(*c, max_iters=12).values for c in CASES}
    solo.close()
    for D in MESH_SIZES:
        svc = _mk_service(tmp_path, f"svm{D}", g, backend="numpy",
                          max_lanes=8, max_groups=2, mesh=D)
        with svc.submit_batch():
            futs = [svc.submit(p, s, max_iters=12) for p, s in CASES]
        for c, f in zip(CASES, futs):
            qr = f.result(timeout=240)
            assert np.array_equal(_norm(qr.values), _norm(refs[c])), (D, c)
        assert svc.stats()["mesh_devices"] == D
        svc.close()


def test_mesh_sweep_retirement_backfill_bitwise(tmp_path):
    """Mid-sweep retirement + backfill under a mesh: chain BFS sources
    converge at wildly different iterations; every result still equals the
    single-device solo run."""
    n = 64
    g = chain_graph(n)
    cases = [("bfs", 60), ("ppr", 0), ("bfs", 55), ("ppr", 1),
             ("bfs", 40), ("ppr", 2), ("bfs", 0)]
    solo = _mk_service(tmp_path, "bfsolo", g, num_shards=4, backend="numpy",
                       max_lanes=3, max_groups=2)
    refs = {}
    for p, s in cases:
        refs[(p, s)] = solo.query(
            p, s, max_iters=200 if p == "bfs" else 6).values
    solo.close()
    for D in (2, 8):
        svc = _mk_service(tmp_path, f"bf{D}", g, num_shards=4,
                          backend="numpy", max_lanes=3, max_groups=2, mesh=D)
        with svc.submit_batch():
            futs = [svc.submit(p, s, max_iters=200 if p == "bfs" else 6)
                    for p, s in cases]
        for (p, s), f in zip(cases, futs):
            qr = f.result(timeout=240)
            assert np.array_equal(_norm(qr.values), _norm(refs[(p, s)])), \
                (D, p, s)
        svc.close()


def test_mesh_sweep_stats_conserved(tmp_path):
    g = rmat_graph(300, 3500, seed=63)
    eng = _mk_engine(tmp_path, "cons", g, backend="numpy", mesh=4)
    sweep = MeshSweep(eng)
    seeds = [
        [LaneSeed(source=s, max_iters=12,
                  program=apps.get_lane_program("bfs")) for s in (0, 5, 9)],
        [LaneSeed(source=3, max_iters=6,
                  program=apps.get_lane_program("ppr"))],
    ]
    res = sweep.run(seeds)
    assert len(res) == 4
    assert sweep.iter_stats
    for it in sweep.iter_stats:
        assert len(it.device_shards) == 4
        assert sum(it.device_shards) == it.shards_processed
        assert abs(sum(it.device_bytes) - it.bytes_read) < 1e-6
        # dispatch conservation: each device that carried work this
        # iteration launched once per live group, never more
        assert all(d <= it.groups * it.shards_processed
                   for d in it.device_dispatches)
    # lane attribution still sums to the sweep totals under the mesh
    total_bytes = sum(it.bytes_read for it in sweep.iter_stats)
    assert abs(sum(r.bytes_read for r in res) - total_bytes) < 1e-6
    eng.close()


def test_mesh_sweep_rejects_plain_engine(tmp_path):
    g = chain_graph(32)
    eng = _mk_engine(tmp_path, "plain", g, num_shards=2, backend="numpy")
    with pytest.raises(ValueError, match="mesh="):
        MeshSweep(eng)
    assert isinstance(FusedSweep(eng), FusedSweep)  # plain path unaffected
    eng.close()


def test_mesh_apply_updates_between_sweeps(tmp_path):
    """Live edge mutations between mesh sweeps: post-publish queries equal
    a fresh single-device service on the mutated graph (delta overlay +
    version pinning compose with the mesh executor)."""
    rng = np.random.default_rng(29)
    num_v, num_e = 250, 2200
    g = rmat_graph(num_v, num_e, seed=66)
    svc = _mk_service(tmp_path, "upd", g, num_shards=5, backend="numpy",
                      max_lanes=4, max_groups=2, mesh=4, session_entries=0)
    cases = [("bfs", 3), ("wcc", 0), ("ppr", 7), ("sssp", 11)]
    pre = {c: svc.query(*c, max_iters=15) for c in cases}

    take = rng.choice(num_e, 200, replace=False)
    dels = (g.src[take], g.dst[take])
    ins = (rng.integers(0, num_v, 150).astype(np.int32),
           rng.integers(0, num_v, 150).astype(np.int32))
    upd = svc.apply_updates(inserts=ins, deletes=dels).result(timeout=240)
    assert upd.graph_version == 1
    post = {c: svc.query(*c, max_iters=15) for c in cases}
    svc.close()

    msrc, mdst = _mutated(g.src, g.dst, ins, dels)
    mg = Graph(num_v, msrc, mdst)
    ref_pre = _mk_service(tmp_path, "ref0", g, num_shards=5, backend="numpy",
                          max_lanes=4, session_entries=0)
    ref_post = _mk_service(tmp_path, "ref1", mg, num_shards=5,
                           backend="numpy", max_lanes=4, session_entries=0)
    for c in cases:
        assert np.array_equal(
            _norm(pre[c].values),
            _norm(ref_pre.query(*c, max_iters=15).values)), ("pre", c)
        assert np.array_equal(
            _norm(post[c].values),
            _norm(ref_post.query(*c, max_iters=15).values)), ("post", c)
    ref_pre.close()
    ref_post.close()


def test_mesh_seeded_property_stress(tmp_path):
    """Seeded stress: random graphs x random mesh sizes x all four lane
    programs, mesh emulation vs solo, every time bitwise."""
    rng = np.random.default_rng(41)
    for trial in range(4):
        n = int(rng.integers(60, 400))
        m = int(rng.integers(2 * n, 8 * n))
        g = rmat_graph(n, m, seed=int(rng.integers(1 << 30)))
        D = int(rng.choice([2, 3, 5, 8]))
        shards = int(rng.integers(2, 9))
        cases = [(p, int(rng.integers(0, n)))
                 for p in ("bfs", "sssp", "ppr", "wcc")]
        solo = _mk_service(tmp_path, f"st{trial}s", g, num_shards=shards,
                           backend="numpy", max_lanes=4, max_groups=2)
        refs = {c: solo.query(*c, max_iters=10).values for c in cases}
        solo.close()
        svc = _mk_service(tmp_path, f"st{trial}m", g, num_shards=shards,
                          backend="numpy", max_lanes=4, max_groups=2, mesh=D)
        with svc.submit_batch():
            futs = [svc.submit(p, s, max_iters=10) for p, s in cases]
        for c, f in zip(cases, futs):
            assert np.array_equal(
                _norm(f.result(timeout=240).values), _norm(refs[c])), \
                (trial, D, c)
        svc.close()


# ------------------------------------------------ jax paths (subprocess)
_JAX_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import tempfile
    from repro.core.graph import rmat_graph
    from repro.serve import GraphService

    g = rmat_graph(300, 3500, seed=63)
    cases = [("bfs", 2), ("wcc", 0), ("ppr", 3), ("sssp", 1), ("ppr", 9)]
    norm = lambda v: np.nan_to_num(v, posinf=1e30)
    with tempfile.TemporaryDirectory() as d:
        for backend in ("jnp", "pallas"):
            solo = GraphService.from_graph(
                g, d + f"/solo{backend}", num_shards=6, window=128, k=16,
                backend=backend, max_lanes=8, max_groups=2, batch_shards=2)
            refs = {c: solo.query(*c, max_iters=12).values for c in cases}
            solo.close()
            for D in (1, 2, 8):
                svc = GraphService.from_graph(
                    g, d + f"/{backend}{D}", num_shards=6, window=128, k=16,
                    backend=backend, max_lanes=8, max_groups=2,
                    batch_shards=2, mesh=D)
                with svc.submit_batch():
                    futs = [svc.submit(p, s, max_iters=12) for p, s in cases]
                for c, f in zip(cases, futs):
                    qr = f.result(timeout=240)
                    assert np.array_equal(norm(qr.values), norm(refs[c])), \\
                        (backend, D, c)
                assert svc.stats()["mesh_devices"] == D
                svc.close()
                print(backend, "D", D, "bitwise-ok", flush=True)
    print("MESH_JAX_OK")
    """
)

_ERR_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import tempfile
    from repro.launch.mesh import make_host_mesh, make_production_mesh

    # both constructors raise the SAME derived-from-shape error
    for fn, needs in ((lambda: make_host_mesh((4, 4)), 16),
                      (make_production_mesh, 256)):
        try:
            fn()
            raise SystemExit("expected RuntimeError")
        except RuntimeError as e:
            msg = str(e)
            assert f"needs {needs} devices, have 8" in msg, msg
            assert "platform 'cpu'" in msg, msg
            assert "xla_force_host_platform" not in msg, msg

    # a 4-device mesh on the 8-device host works (prefix, no truncation)
    m = make_host_mesh((4,), ("dev",))
    assert m.devices.shape == (4,)

    # the engine's mesh= boot path surfaces the same error
    from repro.core.graph import chain_graph
    from repro.core.vsw import VSWEngine
    with tempfile.TemporaryDirectory() as d:
        try:
            VSWEngine.from_graph(chain_graph(64), d + "/x", num_shards=2,
                                 window=128, k=16, backend="jnp", mesh=16)
            raise SystemExit("expected RuntimeError")
        except RuntimeError as e:
            assert "needs 16 devices, have 8" in str(e), str(e)
    print("MESH_ERR_OK")
    """
)


def _run_sub(script):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    return subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        env=env, timeout=600,
    )


@pytest.mark.slow
def test_mesh_jnp_pallas_bitwise_e2e():
    r = _run_sub(_JAX_SCRIPT)
    assert r.returncode == 0, (r.stdout[-1500:], r.stderr[-3000:])
    assert "MESH_JAX_OK" in r.stdout


@pytest.mark.slow
def test_mesh_device_errors_uniform_e2e():
    r = _run_sub(_ERR_SCRIPT)
    assert r.returncode == 0, (r.stdout[-1500:], r.stderr[-3000:])
    assert "MESH_ERR_OK" in r.stdout
