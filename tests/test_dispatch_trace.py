"""Spans and counters of the serving worker's shard dispatch (DESIGN.md §11).

A traced fused sweep on the ragged executor, on both dispatch paths (lane
masks off, and lane-selective scheduling that sets them):

- each ``exec.dispatch`` holds ``exec.stage``, ``exec.put`` and
  ``exec.launch`` in that order, and each launch is collected by one
  ``exec.collect`` holding one ``exec.wait``;
- ``sweep.iter``'s children do not overlap;
- the dispatch counters add up: real edges over an iteration that planned
  every shard are the graph's edges, slots cover edges, live lanes fit the
  launched lanes, and the launched lanes cover ``ExecStats.ragged_lanes``;
- a Pallas dispatch counts its kernel's grid steps (lane blocks × tiles)
  and the gather rounds each lane runs, as the host helper counts them
  from the launched slots;
- tracing leaves every result bitwise as it was.

Besides: a backend compile is recorded as a ``jax.compile`` span under the
span that caused it, a service's metrics snapshot holds the iterations of a
fusion set that is still running, and one query's admit and retire spans
share its request id.

jax-touching tests carry ``e2e`` in their names (``run_memcapped.py``).
"""

import threading

import numpy as np
import pytest

from repro.core import apps
from repro.core.graph import rmat_graph
from repro.core.vsw import VSWEngine
from repro.obs import Tracer, trace
from repro.serve import FusedSweep, GraphService, LaneSeed

EXEC_CHILDREN = ["exec.stage", "exec.put", "exec.launch"]
WINDOW, K, TR = 128, 16, 8
ITER_CHILDREN = {"sweep.prepare", "sweep.plan", "shard.wait", "exec.dispatch",
                 "exec.collect", "sweep.commit"}


def _spans(tr, tid):
    """``(start_us, end_us, name, attrs)`` of one thread's spans, by start."""
    evs = tr.export_chrome()["traceEvents"]
    return sorted(
        (e["ts"], e["ts"] + e["dur"], e["name"], e.get("args", {}))
        for e in evs if e["ph"] == "X" and e["tid"] == tid
    )


def _inside(outer, spans):
    """Spans inside ``outer`` (export rounds to ns; allow that)."""
    s0, e0 = outer[0], outer[1]
    return [s for s in spans
            if s is not outer and s[0] >= s0 - 1e-3 and s[1] <= e0 + 1e-3]


def _direct(outer, spans):
    """The children of ``outer``: spans inside it and inside no other."""
    kids = []
    for s in _inside(outer, spans):
        if kids and s[0] < kids[-1][1]:
            continue  # nested in the previous child
        kids.append(s)
    return kids


def _norm(v):
    return np.nan_to_num(v, posinf=1e30, neginf=-1e30)


def _seeds(with_ppr):
    """Two algebra groups, or (``with_ppr`` false) one group of searches:
    PPR keeps every vertex active, so only searches plan selectively."""
    bfs, sssp, ppr = apps.lane_bfs(), apps.lane_sssp(), apps.lane_ppr()
    groups = [
        [LaneSeed(source=0, max_iters=6, token="b0", program=bfs),
         LaneSeed(source=3, max_iters=8, token="b3", program=bfs),
         LaneSeed(source=9, max_iters=8, token="s9", program=sssp)],
        [LaneSeed(source=5, max_iters=8, token="p5", program=ppr)],
    ]
    return groups if with_ppr else groups[:1]


@pytest.fixture(scope="module", params=[
    ("jnp", False), ("jnp", True), ("pallas", True),
], ids=lambda p: f"{p[0]}-{'masked' if p[1] else 'unmasked'}")
def traced_sweep(request, tmp_path_factory):
    backend, lane_selective = request.param
    g = rmat_graph(400, 4500, seed=143)
    d = tmp_path_factory.mktemp(f"disp-{backend}-{lane_selective}")
    # threshold: the first iterations (a few sources active) plan
    # selectively, so lane-selective scheduling sets lane masks there
    eng = VSWEngine.from_graph(g, str(d), num_shards=5, window=WINDOW, k=K,
                               tr=TR, backend=backend, batch_shards=2,
                               threshold=0.05)
    base = FusedSweep(eng, batch_shards=2, lane_selective=lane_selective)
    untraced = {r.token: r for r in base.run(_seeds(not lane_selective))}

    sweep = FusedSweep(eng, batch_shards=2, lane_selective=lane_selective)
    run_groups, ragged_lanes = sweep.executor.run_groups, [0]

    def counted(loaded, groups, stats=None, **kw):
        before = stats.ragged_lanes
        yield from run_groups(loaded, groups, stats, **kw)
        ragged_lanes[0] += stats.ragged_lanes - before

    sweep.executor.run_groups = counted
    # the rounds the host helper counts on each launch's device arrays
    ragged_fn, launched_rounds = sweep.executor._ragged_fn, []

    def launch(batch, n_ell_pad, staged, lane_ctx):
        from repro.kernels.spmv_ell import kernel as Kn

        launched_rounds.append(Kn.ragged_rounds(Kn.ragged_rows_read(
            np.asarray(staged[0]), np.asarray(staged[1]), window=WINDOW,
            tr=TR)))
        return ragged_fn(batch, n_ell_pad, staged, lane_ctx)

    sweep.executor._ragged_fn = launch
    tr = Tracer()
    with trace.tracing(tr):
        traced = {r.token: r for r in sweep.run(_seeds(not lane_selective))}
    eng.close()
    return {"spans": _spans(tr, threading.get_ident()), "untraced": untraced,
            "traced": traced, "ragged_lanes": ragged_lanes[0],
            "masked": lane_selective, "edges": g.num_edges,
            "backend": backend, "launched_rounds": launched_rounds,
            "num_shards": eng.meta.num_shards, "tracer": tr}


def _named(spans, name):
    return [s for s in spans if s[2] == name]


def test_dispatch_stage_put_launch_in_order_e2e(traced_sweep):
    spans = traced_sweep["spans"]
    dispatches = _named(spans, "exec.dispatch")
    assert dispatches
    for d in dispatches:
        assert [s[2] for s in _direct(d, spans)] == EXEC_CHILDREN
        # an on-the-fly compile may sit under the launch, nothing else does
        assert {s[2] for s in _inside(d, spans)} <= \
            set(EXEC_CHILDREN) | {"jax.compile"}
    masked = {d[3]["masked"] for d in dispatches}
    assert masked == ({True, False} if traced_sweep["masked"] else {False})


def test_each_launch_collected_once_with_one_wait_e2e(traced_sweep):
    spans = traced_sweep["spans"]
    collects = _named(spans, "exec.collect")
    assert len(collects) == len(_named(spans, "exec.launch"))
    for c in collects:
        assert [s[2] for s in _inside(c, spans)] == ["exec.wait"]


def test_sweep_iter_children_do_not_overlap_e2e(traced_sweep):
    spans = traced_sweep["spans"]
    iters = _named(spans, "sweep.iter")
    assert iters
    for it in iters:
        kids = _direct(it, spans)
        assert {k[2] for k in kids} <= ITER_CHILDREN | {"jax.compile"}
        for a, b in zip(kids, kids[1:]):
            assert a[1] <= b[0] + 1e-3, (a, b)
        assert {"sweep.prepare", "sweep.plan", "sweep.commit"} <= \
            {k[2] for k in kids}


def test_dispatch_counters_add_up_e2e(traced_sweep):
    spans = traced_sweep["spans"]
    dispatches = [d[3] for d in _named(spans, "exec.dispatch")]
    for a in dispatches:
        assert a["slots"] >= a["edges"] > 0
        assert 0 < a["lanes_live"] <= a["lanes_pad"]
        assert a["h2d_bytes"] > 0
    assert sum(a["lanes_pad"] for a in dispatches) >= \
        traced_sweep["ragged_lanes"] > 0
    full = [it for it in _named(spans, "sweep.iter")
            if it[3]["shards"] == traced_sweep["num_shards"]]
    assert full
    for it in full:
        edges = sum(d[3]["edges"] for d in _inside(it, spans)
                    if d[2] == "exec.dispatch")
        assert edges == traced_sweep["edges"]


def test_pallas_dispatch_grid_steps_e2e(traced_sweep):
    """A Pallas ragged dispatch counts its kernel's grid steps: lane blocks
    times tiles; a jnp dispatch runs no grid and counts none."""
    from repro.kernels.spmv_ell.kernel import ragged_lane_block

    dispatches = [d[3] for d in _named(traced_sweep["spans"], "exec.dispatch")]
    assert dispatches
    for a in dispatches:
        if traced_sweep["backend"] != "pallas":
            assert "grid_steps" not in a
            continue
        lane_blocks = a["lanes_pad"] // ragged_lane_block(a["lanes_pad"],
                                                          WINDOW)
        n_ell_pad = a["slots"] // K
        assert a["grid_steps"] == lane_blocks * n_ell_pad // TR > 0


def test_pallas_dispatch_rounds_e2e(traced_sweep):
    """A Pallas ragged dispatch counts the gather rounds each lane runs,
    equal to the host helper's count on the arrays it launched (padding
    tiles run none), against every table row of every tile; a jnp dispatch
    counts none."""
    dispatches = [d[3] for d in _named(traced_sweep["spans"], "exec.dispatch")]
    assert len(dispatches) == len(traced_sweep["launched_rounds"]) > 0
    if traced_sweep["backend"] != "pallas":
        assert not any("rounds" in a or "rounds_full" in a for a in dispatches)
        return
    assert [a["rounds"] for a in dispatches] == \
        traced_sweep["launched_rounds"]
    for a in dispatches:
        assert a["rounds_full"] == a["slots"] // (K * TR) * (WINDOW // 128)
        assert 0 < a["rounds"] <= a["rounds_full"]


def test_traced_sweep_results_bitwise_untraced_e2e(traced_sweep):
    base, traced = traced_sweep["untraced"], traced_sweep["traced"]
    assert set(base) == set(traced)
    assert {"b0", "b3", "s9"} <= set(base)
    for tok, r in base.items():
        assert np.array_equal(_norm(r.values), _norm(traced[tok].values)), tok
        assert r.iterations == traced[tok].iterations
    assert traced_sweep["tracer"].open_span_count() == 0


def test_compile_recorded_under_its_span_e2e():
    import jax
    import jax.numpy as jnp

    # A constant of its own: never a hit in a persistent compilation cache.
    c = float(np.random.default_rng().integers(1, 1 << 30))
    f = jax.jit(lambda x: x * c + 1.0)
    tr = Tracer()
    with trace.tracing(tr):
        with trace.span("outer"):
            f(jnp.ones(37)).block_until_ready()
    spans = _spans(tr, threading.get_ident())
    (outer,) = _named(spans, "outer")
    compiles = _named(spans, "jax.compile")
    assert compiles
    assert all(s in _inside(outer, spans) for s in compiles)
    # with no tracer installed the listener records nothing
    f2 = jax.jit(lambda x: x * (c + 1.0))
    f2(jnp.ones(37)).block_until_ready()
    assert len(_named(_spans(tr, threading.get_ident()), "jax.compile")) == \
        len(compiles)


def test_snapshot_mid_fusion_set_and_query_ids(tmp_path):
    """The service feeds stage timings per iteration: a snapshot taken when
    the first query retires, while the second still runs in the same
    fusion set, already holds the iteration before it."""
    g = rmat_graph(400, 4500, seed=143)
    snaps = []
    tr = Tracer()
    with trace.tracing(tr):
        with GraphService.from_graph(g, str(tmp_path / "svc"), num_shards=5,
                                     window=128, k=16, backend="numpy",
                                     max_lanes=4) as svc:
            with svc.submit_batch():
                short = svc.submit("bfs", 0, max_iters=2)
                long = svc.submit("bfs", 3, max_iters=8)
                short.add_done_callback(
                    lambda _: snaps.append(svc.metrics_snapshot()))
            results = [short.result(timeout=120), long.result(timeout=120)]
            final = svc.metrics_snapshot()
    assert 2 <= results[0].iterations < results[1].iterations
    (mid,) = snaps
    for stage in ("iter_s", "load_s", "load_wait_s", "exec_s"):
        # the retiring iteration itself is fed once it ends
        assert mid["stages"][stage]["count"] == results[0].iterations - 1
    assert svc.stats()["sweeps"] == 1
    assert final["stages"]["iter_s"]["count"] == results[1].iterations
    evs = [e for e in tr.export_chrome()["traceEvents"] if e["ph"] == "X"]
    for name in ("service.admit", "service.retire"):
        ids = sorted(e["args"]["query"] for e in evs if e["name"] == name)
        assert ids == sorted(r.request_id for r in results), name
