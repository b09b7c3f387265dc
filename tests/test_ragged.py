"""RaggedFuse: one ragged kernel launch per shard batch covering ALL
fusion groups (DESIGN.md §14).

The ragged contract — each lane's row is bitwise a solo run of that
lane's program on the masked path — tested four ways:

1. **Padding algebra** — :func:`ragged_lane_pad` never wastes more lanes
   than padding each group to a power of two, and
   :func:`ragged_lane_concat` lays groups out contiguously with per-lane
   combine-arm ids (padding lanes carry an id matching NO arm).
2. **Bitwise kernels** — ``ell_update_lanes_ragged`` equals a solo
   ``ell_update`` of each lane bit-for-bit across combine mixes
   (including duplicated monoids sharing one arm and inf-heavy min
   inputs), and the mesh step equals the single-device service at D ∈
   {1, 2, 8} (numpy emulation inline; jnp/pallas in a subprocess under
   ``XLA_FLAGS=--xla_force_host_platform_device_count=8``).  The kernel's
   per-tile row lists change no partial: hand-built tiles (no valid slot,
   one row, every row, several slots on a row, invalid slots on listed
   rows) equal the masked kernel and the full schedule, and the device
   prologue's lists equal ``np.unique`` per tile.
3. **Bitwise sweeps** — a ``FusedSweep`` reproduces each query's solo
   engine run exactly through masked groups (lane-selective scheduling),
   mid-sweep retirement and backfill.
4. **Conserved accounting** — a sweep books exactly ONE dispatch per
   flushed batch (``dispatches == batches``), and the declared identities
   (``ragged_dispatches <= batches <= dispatches``,
   ``sum(group_lanes) == ragged_lanes``) replay clean through
   ``MetricsRegistry.verify_conservation``.

jax-touching tests carry ``e2e`` in their names so the RLIMIT_AS runner
(run_memcapped.py) can exclude them.
"""

import os
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

from repro.core import apps
from repro.core.csr import (
    csr_to_ell,
    next_pow2,
    ragged_lane_concat,
    ragged_lane_pad,
)
from repro.core.graph import chain_graph, rmat_graph
from repro.core.sharding import preprocess
from repro.core.vsw import VSWEngine
from repro.serve import FusedSweep, GraphService, LaneSeed

MIXED = [("bfs", 0), ("ppr", 5), ("sssp", 3), ("ppr", 11), ("wcc", 1)]


def _norm(v):
    return np.nan_to_num(v, posinf=1e30, neginf=-1e30)


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


def _solo(eng, program, source, max_iters):
    kw = {} if program == "wcc" else {"source": source}
    return eng.run(apps.get_program(program, **kw), max_iters=max_iters)


# ------------------------------------------------------- padding algebra
def test_ragged_lane_pad_never_worse_than_per_group_pow2():
    """Property (seeded): for ANY group lane counts, the single ragged
    launch's padding waste <= the waste of padding each group to a power
    of two."""
    rng = np.random.default_rng(140)
    for _ in range(300):
        counts = rng.integers(0, 33, size=rng.integers(1, 7)).tolist()
        k_total = sum(counts)
        pad = ragged_lane_pad(counts)
        per_group = sum(next_pow2(max(k, 1)) for k in counts)
        assert pad >= max(k_total, 1)
        assert pad <= per_group, (counts, pad, per_group)
        # ragged waste <= per-group waste (the acceptance inequality)
        assert pad - k_total <= per_group - k_total
    # the two interesting corners from DESIGN.md §14
    assert ragged_lane_pad([1, 1, 1]) == 3  # beats next_pow2(3) == 4
    assert ragged_lane_pad([3, 2, 5]) == 14  # == 4+2+8, beats pow2(10)=16


def test_ragged_lane_concat_layout_and_arm_dedup():
    rng = np.random.default_rng(141)
    groups = [rng.random((k, 10)).astype(np.float32) for k in (3, 1, 2)]
    msgs_all, cids, combines_set, slices = ragged_lane_concat(
        groups, ["sum", "min", "sum"]
    )
    # duplicate monoids share ONE kernel arm, first-seen order
    assert combines_set == ("sum", "min")
    assert msgs_all.shape[0] == ragged_lane_pad([3, 1, 2])
    # every group's lane block round-trips bitwise through its slice
    for m, sl in zip(groups, slices):
        assert np.array_equal(msgs_all[sl], m)
    assert np.asarray(cids)[slices[0]].tolist() == [0, 0, 0]
    assert np.asarray(cids)[slices[1]].tolist() == [1]
    assert np.asarray(cids)[slices[2]].tolist() == [0, 0]
    # padding lanes: zero rows, arm id out of range (matches no arm)
    n_live = sum(m.shape[0] for m in groups)
    assert np.all(msgs_all[n_live:] == 0.0)
    assert np.all(np.asarray(cids)[n_live:] == len(combines_set))
    with pytest.raises(ValueError):
        ragged_lane_concat(groups, ["sum", "min"])
    with pytest.raises(ValueError):
        ragged_lane_concat([], [])


# ------------------------------------------------------- kernel bitwise
@pytest.mark.parametrize("combines", [
    ("sum", "min", "max"),
    ("min", "sum"),
    ("sum", "min", "sum"),   # duplicated monoid -> shared arm
    ("min",),                # single group
])
def test_ragged_ops_bitwise_vs_solo_e2e(combines):
    """Each lane of a ragged launch over three shards equals a solo masked
    ``ell_update`` of that lane on its shard, bit for bit."""
    from repro.kernels.spmv_ell import ops as spmv_ops

    g = rmat_graph(600, 7000, seed=142)
    meta, shards = preprocess(g, num_shards=3)
    ells = [csr_to_ell(s, g.num_vertices, window=128, k=16, tr=8)
            for s in shards]
    rng = np.random.default_rng(142)
    msgs_by_group = []
    for gi, c in enumerate(combines):
        m = rng.random((gi + 1, g.num_vertices)).astype(np.float32)
        if c in ("min", "max"):
            # inf-heavy lanes: the min/max identity must survive the
            # in-kernel arm selection exactly as it does solo
            m[m > 0.6] = np.inf if c == "min" else -np.inf
        msgs_by_group.append(m)
    out = spmv_ops.ell_update_lanes_ragged(ells, msgs_by_group, list(combines))
    assert len(out) == len(combines)
    for gi, (accs, msgs, c) in enumerate(zip(out, msgs_by_group, combines)):
        assert len(accs) == len(ells)
        for si, (a, e) in enumerate(zip(accs, ells)):
            assert a.shape == (msgs.shape[0], e.rows)
            for lane in range(msgs.shape[0]):
                solo = np.asarray(spmv_ops.ell_update(e, msgs[lane], c))
                assert np.array_equal(_norm(a[lane]), _norm(solo)), \
                    (gi, si, lane)
    # empty shard list: shape-compatible empty result
    assert spmv_ops.ell_update_lanes_ragged([], msgs_by_group,
                                            list(combines)) == \
        [[] for _ in combines]


def test_ragged_lane_block_fits_vmem_budget():
    """Every lane in one block while the double-buffered windows fit the
    budget, else the largest divisor of the lane count that fits."""
    from repro.kernels.spmv_ell import kernel as Kn

    w = 16384
    assert Kn.ragged_lane_block(1, w) == 1
    assert Kn.ragged_lane_block(24, w) == 24
    assert Kn.ragged_lane_block(64, w) == 64
    assert Kn.ragged_lane_block(96, w) == 48
    assert Kn.ragged_lane_block(65, w) == 13
    assert Kn.ragged_lane_block(7, 1 << 22) == 1  # nothing fits: one lane
    assert Kn.ragged_grid_steps(24, 4096, w) == 4096
    assert Kn.ragged_grid_steps(96, 1024, w) == 2 * 1024
    for n in range(1, 200):
        lb = Kn.ragged_lane_block(n, w)
        assert n % lb == 0 and 2 * lb * w * 4 <= Kn.RAGGED_WINDOW_VMEM


@pytest.mark.parametrize("n_lanes,block,rows_per_iter", [
    (1, None, None), (3, None, None), (24, None, None),  # 3 groups of 8
    (24, 8, None),  # budget forced down: three lane blocks
    (6, 4, None),   # largest divisor that fits: two blocks of 3
    (5, None, 1),   # the gather's rows in a loop of one round each
])
def test_ragged_lane_blocks_bitwise_vs_masked_e2e(monkeypatch, n_lanes,
                                                  block, rows_per_iter):
    """Each lane of the lane-blocked ragged kernel equals a solo masked
    launch with that lane's combine; a lane whose arm id matches no arm
    (every third lane) is a zero row."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.spmv_ell import kernel as Kn

    window, combines = 256, ("min", "sum")
    forced = block is not None or rows_per_iter is not None
    if block is not None:
        monkeypatch.setattr(Kn, "RAGGED_WINDOW_VMEM", 2 * block * window * 4)
    if rows_per_iter is not None:
        monkeypatch.setattr(Kn, "ROWS_PER_ITER", rows_per_iter)
    if forced:
        jax.clear_caches()  # no trace made under the default constants
    expect = n_lanes if block is None else max(
        d for d in range(1, block + 1) if n_lanes % d == 0)
    assert Kn.ragged_lane_block(n_lanes, window) == expect
    if block is not None:
        assert expect < n_lanes

    g = rmat_graph(600, 7000, seed=144)
    _, shards = preprocess(g, num_shards=1)
    e = csr_to_ell(shards[0], g.num_vertices, window=window, k=16, tr=8)
    rng = np.random.default_rng(144)
    msgs = rng.random((n_lanes, e.num_windows * window)).astype(np.float32)
    cids = np.arange(n_lanes, dtype=np.int32) % 3  # 2: no arm
    msgs[cids == 0] = np.where(msgs[cids == 0] > 0.6, np.inf,
                               msgs[cids == 0])
    ell = (jnp.asarray(e.ell_idx), jnp.asarray(e.ell_mask),
           jnp.asarray(e.tile_window))
    out = np.asarray(Kn.ell_partials_ragged(
        *ell, *Kn.ragged_row_table(*ell[:2], window=window, tr=8),
        jnp.asarray(cids), jnp.asarray(msgs), window=window, tr=8,
        combines=combines))
    if forced:
        jax.clear_caches()
    assert out.shape == (n_lanes, e.n_tiles * 8)
    for lane, cid in enumerate(cids):
        if cid == len(combines):
            assert np.all(out[lane] == 0.0), lane
            continue
        ref = np.asarray(Kn.ell_partials_masked(
            *ell, jnp.asarray(msgs[lane]), window=window, tr=8,
            combine=combines[cid]))
        assert np.array_equal(_norm(out[lane]), _norm(ref)), lane


# ------------------------------------------------------ row lists
# Hand-built (TR, K) tiles over one window of W = 16384: 128 table rows of
# L = 128, so a tile of 128 slots can read every row.
RL_W, RL_TR, RL_K = 16384, 8, 16


def _tile(rng, valid_rows, invalid_rows=()):
    """One tile: a valid slot on each of ``valid_rows`` (repeats give
    several slots on one row), an invalid slot on each of
    ``invalid_rows``, every other slot invalid with a random index."""
    idx = rng.integers(0, RL_W, RL_TR * RL_K)
    valid = np.zeros(RL_TR * RL_K, bool)
    pos = rng.permutation(RL_TR * RL_K)
    rows = list(valid_rows) + list(invalid_rows)
    assert len(rows) <= pos.size
    for p, row in zip(pos, rows):
        idx[p] = row * 128 + rng.integers(0, 128)
    valid[pos[:len(valid_rows)]] = True
    return idx.reshape(RL_TR, RL_K), valid.reshape(RL_TR, RL_K)


ROW_LIST_TILES = {
    "no-valid-slot": lambda rng: _tile(rng, []),
    "one-row": lambda rng: _tile(rng, [77]),
    "all-128-rows": lambda rng: _tile(rng, rng.permutation(128)),
    "count-not-multiple": lambda rng: _tile(
        rng, rng.choice(128, 19, replace=False)),
    "several-slots-one-row": lambda rng: _tile(rng, [5] * 11 + [6, 90]),
    "invalid-on-listed-row": lambda rng: _tile(
        rng, [3, 40, 41], invalid_rows=[3, 40, 40, 41, 127]),
}


def _row_list_launch(case, seed):
    """The case's tile, between a tile with no valid slot and a tile of
    random rows, as device arrays."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    tiles = [ROW_LIST_TILES["no-valid-slot"](rng), ROW_LIST_TILES[case](rng),
             _tile(rng, rng.choice(128, 40))]
    idx = np.concatenate([t[0] for t in tiles]).astype(np.int16)
    valid = np.concatenate([t[1] for t in tiles])
    return jnp.asarray(idx), jnp.asarray(valid)


def _unique_rows(idx, valid):
    """Per tile of 8 ELL rows, the table rows its valid slots read:
    ``np.unique``."""
    hi = np.asarray(idx).reshape(-1, 8 * idx.shape[1]) >> 7
    v = np.asarray(valid).reshape(hi.shape)
    return [np.unique(h[m]) for h, m in zip(hi, v)]


@pytest.mark.parametrize("case", sorted(ROW_LIST_TILES))
def test_ragged_row_lists_bitwise_e2e(case):
    """Each lane of the row-list kernel equals a solo masked launch with its
    combine and the full schedule (every row listed for every tile), on
    the min and the sum arms; a lane whose id matches no arm is zeros."""
    import jax.numpy as jnp

    from repro.kernels.spmv_ell import kernel as Kn

    idx, valid = _row_list_launch(case, 150)
    n_tiles = idx.shape[0] // RL_TR
    tw = jnp.zeros((n_tiles,), jnp.int32)
    combines = ("min", "sum")
    cids = np.array([0, 1, 2, 0, 1], np.int32)
    rng = np.random.default_rng(151)
    msgs = rng.standard_normal((cids.size, RL_W)).astype(np.float32)
    msgs[0, rng.random(RL_W) > 0.5] = np.inf
    n_iter, tile_rows = Kn.ragged_row_table(idx, valid, window=RL_W,
                                            tr=RL_TR)
    step = Kn._rows_per_iter(128)
    full = (jnp.full((n_tiles,), 128 // step, jnp.int32),
            jnp.tile(jnp.arange(128, dtype=jnp.int32), (n_tiles, 1)))

    def launch(table):
        return np.asarray(Kn.ell_partials_ragged(
            idx, valid, tw, *table, jnp.asarray(cids), jnp.asarray(msgs),
            window=RL_W, tr=RL_TR, combines=combines))

    out, out_full = launch((n_iter, tile_rows)), launch(full)
    assert np.array_equal(_norm(out), _norm(out_full))
    for lane, cid in enumerate(cids):
        if cid == len(combines):
            assert np.all(out[lane] == 0.0), lane
            continue
        ref = np.asarray(Kn.ell_partials_masked(
            idx, valid, tw, jnp.asarray(msgs[lane]), window=RL_W, tr=RL_TR,
            combine=combines[cid]))
        assert np.array_equal(_norm(out[lane]), _norm(ref)), lane


@pytest.mark.parametrize("case", sorted(ROW_LIST_TILES) + [
    "rmat-w128", "rmat-w256", "rmat-w1024"])
def test_ragged_row_table_matches_host_helper_e2e(case):
    """The device prologue's row lists equal ``np.unique`` per tile, padded
    with the last listed row to whole loop iterations, and its iteration
    counts the host helper's, whose round count the dispatch counter
    records."""
    import jax.numpy as jnp

    from repro.kernels.spmv_ell import kernel as Kn

    if case.startswith("rmat-w"):
        window = int(case[6:])
        g = rmat_graph(600, 7000, seed=152)
        _, shards = preprocess(g, num_shards=2)
        e = csr_to_ell(shards[1], g.num_vertices, window=window, k=16, tr=8)
        idx, valid = jnp.asarray(e.ell_idx), jnp.asarray(e.ell_mask)
    else:
        window = RL_W
        idx, valid = _row_list_launch(case, 153)
    rows = window // 128
    step = Kn._rows_per_iter(rows)
    n_iter, tile_rows = (np.asarray(x) for x in Kn.ragged_row_table(
        idx, valid, window=window, tr=8))
    read = Kn.ragged_rows_read(np.asarray(idx), np.asarray(valid),
                               window=window, tr=8)
    uniq = _unique_rows(idx, valid)
    assert tile_rows.shape == (len(uniq), rows)
    for t, u in enumerate(uniq):
        assert np.array_equal(np.flatnonzero(read[t]), u), t
        assert n_iter[t] == -(-u.size // step), t
        pad = u[-1] if u.size else 0
        assert np.array_equal(tile_rows[t, :u.size], u), t
        assert np.all(tile_rows[t, u.size:] == pad), t
    assert Kn.ragged_rounds(read) == int(n_iter.sum()) * step


# -------------------------------------------------------- sweep bitwise
@pytest.mark.parametrize("backend,batch_shards,lane_selective", [
    ("jnp", 1, True), ("jnp", 3, True), ("pallas", 2, True),
    ("jnp", 2, False),
])
def test_ragged_sweep_bitwise_vs_solo_e2e(tmp_path, backend, batch_shards,
                                          lane_selective):
    """A FusedSweep's lanes equal each query's solo engine run bitwise
    through masked groups and mid-sweep retirement/backfill — and the
    sweep books ONE dispatch per batch for all its groups."""
    g = rmat_graph(400, 4500, seed=143)
    eng = _mk_engine(tmp_path, f"e{backend}{batch_shards}", g, num_shards=5,
                     backend=backend, batch_shards=batch_shards)
    bfs, sssp, ppr = apps.lane_bfs(), apps.lane_sssp(), apps.lane_ppr()
    # varied max_iters force mid-sweep retirement; the backfill queue
    # re-admits into freed lanes while the other group is still live
    queue = [LaneSeed(source=9, max_iters=12, token="b2", program=bfs)]

    def mk_seeds():
        return [
            [LaneSeed(source=0, max_iters=3, token="b0", program=bfs),
             LaneSeed(source=3, max_iters=12, token="s0", program=sssp)],
            [LaneSeed(source=5, max_iters=8, token="p0", program=ppr),
             LaneSeed(source=11, max_iters=2, token="p1", program=ppr)],
        ]

    def mk_backfill(q):
        def backfill(group, n_free):
            if group != 0:
                return []
            out = q[:n_free]
            del q[:n_free]
            return out
        return backfill

    sweep = FusedSweep(eng, batch_shards=batch_shards,
                       lane_selective=lane_selective)
    res = sweep.run(mk_seeds(), backfill=mk_backfill(list(queue)))
    by_tok = {r.token: r for r in res}
    solo = {"b0": ("bfs", 0, 3), "s0": ("sssp", 3, 12), "p0": ("ppr", 5, 8),
            "p1": ("ppr", 11, 2), "b2": ("bfs", 9, 12)}
    assert set(by_tok) == set(solo)
    for tok, (prog, src, iters) in solo.items():
        ref = _solo(eng, prog, src, iters)
        assert np.array_equal(_norm(by_tok[tok].values),
                              _norm(ref.values)), tok
        assert by_tok[tok].iterations == ref.num_iterations, tok
        assert by_tok[tok].converged == ref.converged, tok
    # accounting: one launch per flushed batch, every iteration
    assert sum(s.dispatches for s in sweep.iter_stats) > 0
    for s in sweep.iter_stats:
        assert s.dispatches == s.batches, s
        assert s.overlap_s >= 0.0
    eng.close()


def test_ragged_service_mixed_workload_bitwise_e2e(tmp_path):
    """Service-level: a mixed-algebra workload with lane retirement, one
    shard and two to a launch — every query bitwise-equal to its solo
    run."""
    g = rmat_graph(300, 3500, seed=144)
    eng = _mk_engine(tmp_path, "ref", g, num_shards=5, backend="jnp")
    refs = {c: _solo(eng, *c, 12) for c in MIXED}
    eng.close()
    for batch_shards in (1, 2):
        svc = _mk_service(tmp_path, f"svc{batch_shards}", g, num_shards=5,
                          backend="jnp", max_lanes=8, max_groups=2,
                          batch_shards=batch_shards)
        with svc.submit_batch():
            futs = [svc.submit(p, s, max_iters=12) for p, s in MIXED]
        for c, f in zip(MIXED, futs):
            qr = f.result(timeout=240)
            assert np.array_equal(_norm(qr.values),
                                  _norm(refs[c].values)), (batch_shards, c)
        # futures resolve inside the sweep; the counter bumps at sweep end
        deadline = time.monotonic() + 30
        while svc.stats()["sweeps"] < 1 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert svc.stats()["sweeps"] == 1
        svc.close()


# ------------------------------------------------------- mesh emulation
@pytest.mark.parametrize("D", [1, 2, 8])
def test_ragged_mesh_numpy_emulation_bitwise(tmp_path, D):
    """The jax-free mesh emulation books ragged accounting (one dispatch
    per flush) while staying bitwise vs the single-device numpy oracle."""
    g = rmat_graph(300, 3000, seed=145)
    eng = _mk_engine(tmp_path, f"m{D}", g, backend="numpy", mesh=D)
    ref = _mk_engine(tmp_path, "mref", g, backend="numpy")
    bfs, ppr = apps.lane_bfs(), apps.lane_ppr()
    sweep = FusedSweep(eng)
    res = sweep.run([
        [LaneSeed(source=2, max_iters=10, token="b", program=bfs)],
        [LaneSeed(source=7, max_iters=6, token="p", program=ppr)],
    ])
    by_tok = {r.token: r for r in res}
    for tok, src, prog, iters in (("b", 2, "bfs", 10), ("p", 7, "ppr", 6)):
        sr = _solo(ref, prog, src, iters)
        assert np.array_equal(_norm(by_tok[tok].values), _norm(sr.values))
    for s in sweep.iter_stats:
        assert s.dispatches == s.batches
        if s.device_dispatches:
            assert sum(s.device_dispatches) >= s.dispatches
    eng.close()
    ref.close()


# --------------------------------------------------- jax mesh subprocess
_MESH_RAGGED_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import tempfile
    from repro.core.graph import rmat_graph
    from repro.serve import GraphService

    g = rmat_graph(300, 3500, seed=146)
    cases = [("bfs", 2), ("ppr", 3), ("sssp", 1), ("ppr", 9)]
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
                    g, d + f"/{backend}{D}", num_shards=6, window=128,
                    k=16, backend=backend, max_lanes=8, max_groups=2,
                    batch_shards=2, mesh=D)
                with svc.submit_batch():
                    futs = [svc.submit(p, s, max_iters=12)
                            for p, s in cases]
                for c, f in zip(cases, futs):
                    qr = f.result(timeout=240)
                    assert np.array_equal(norm(qr.values),
                                          norm(refs[c])), (backend, D, c)
                svc.close()
                print(backend, "D", D, "ragged-bitwise-ok", flush=True)
    print("MESH_RAGGED_OK")
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
def test_ragged_mesh_jax_bitwise_e2e():
    r = _run_sub(_MESH_RAGGED_SCRIPT)
    assert r.returncode == 0, (r.stdout[-1500:], r.stderr[-3000:])
    assert "MESH_RAGGED_OK" in r.stdout


# ---------------------------------------------------------- conservation
def test_ragged_metrics_conservation_e2e(tmp_path):
    """The declared RaggedFuse identities replay clean on a real ragged
    sweep's iteration stats, and a violated identity is caught."""
    from repro.obs.metrics import ConservationError, MetricsRegistry

    g = rmat_graph(250, 2500, seed=147)
    eng = _mk_engine(tmp_path, "cons", g, backend="jnp", batch_shards=2)
    bfs, ppr = apps.lane_bfs(), apps.lane_ppr()
    sweep = FusedSweep(eng, batch_shards=2)
    sweep.run([
        [LaneSeed(source=0, max_iters=8, token="b", program=bfs)],
        [LaneSeed(source=1, max_iters=8, token="p", program=ppr)],
    ])
    reg = MetricsRegistry()
    for s in sweep.iter_stats:
        reg.ingest(s)
    assert reg.verify_conservation() == []
    assert reg.snapshot()["sweep.batches"] == \
        reg.snapshot()["sweep.dispatches"]
    eng.close()

    # a stats row claiming more batches than dispatches must be flagged
    bad = MetricsRegistry()
    s = sweep.iter_stats[0].__class__(
        iteration=0, live_lanes=2, shards_processed=1, shards_skipped=0,
        bytes_read=0, selective_on=False, retired=0, backfilled=0,
        time_s=0.0, dispatches=1, batches=2,
    )
    bad.ingest(s)
    with pytest.raises(ConservationError):
        bad.verify_conservation()


def test_ragged_exec_stats_identities():
    """ExecStats-level identities: ragged_dispatches <= batches <=
    dispatches and sum(group_lanes) == ragged_lanes."""
    from repro.core.executor import ExecStats
    from repro.obs.metrics import ConservationError, MetricsRegistry

    reg = MetricsRegistry()
    reg.ingest(ExecStats(
        dispatches=4, batches=4, ragged_dispatches=4, ragged_lanes=20,
        group_lanes={0: 12, 1: 8}, shards_executed=8, overlap_s=0.01,
    ))
    assert reg.verify_conservation() == []
    snap = reg.snapshot()
    assert snap["exec.ragged_dispatches"] == 4
    assert snap["exec.ragged_lanes"] == 20

    bad = MetricsRegistry()
    bad.ingest(ExecStats(
        dispatches=2, batches=2, ragged_dispatches=2, ragged_lanes=9,
        group_lanes={0: 4, 1: 4}, shards_executed=4,
    ))
    with pytest.raises(ConservationError):
        bad.verify_conservation()
