"""The readers of the serve worker's dispatch spans and counters, on a
hand-built span list, and on the spans of a program that records none."""

import pytest

from bench import spec

MS = 1_000_000  # ns
W = "graphserve-worker"
NAMES = ["device_wait_share.serve", "stage_share.serve", "lane_fill.serve",
         "slot_fill.serve"]


def _dispatch(t, lanes_live, lanes_pad, edges, slots):
    return (W, "exec.dispatch", t, t + 2 * MS,
            {"lanes_live": lanes_live, "lanes_pad": lanes_pad,
             "edges": edges, "slots": slots, "masked": False})


def _spans():
    return [
        (W, "sweep.iter", 0, 100 * MS, {"live_lanes": 16}),
        (W, "sweep.iter", 100 * MS, 200 * MS, {"live_lanes": 16}),
        _dispatch(10 * MS, 16, 24, 1000, 4096),
        (W, "exec.stage", 10 * MS, 11 * MS, {}),
        (W, "exec.put", 11 * MS, 11.5 * MS, {}),
        (W, "exec.launch", 11.5 * MS, 12 * MS, {}),
        (W, "exec.collect", 20 * MS, 80 * MS, {}),
        (W, "exec.wait", 20 * MS, 70 * MS, {}),
        _dispatch(110 * MS, 8, 8, 3000, 8192),
        (W, "exec.stage", 110 * MS, 112 * MS, {}),
        (W, "exec.put", 112 * MS, 112.5 * MS, {}),
        (W, "exec.collect", 120 * MS, 190 * MS, {}),
        (W, "exec.wait", 120 * MS, 180 * MS, {}),
        # another thread's spans are not the worker's
        ("shard-prefetch_0", "exec.wait", 0, 200 * MS, {}),
        ("client", "exec.dispatch", 0, MS,
         {"lanes_live": 1, "lanes_pad": 1024, "edges": 1, "slots": 1 << 20}),
    ]


def _read(name, spans):
    return spec.metric_reader(name)({"spans": spans, "worker": W})


def test_dispatch_readers_on_hand_built_spans():
    spans = _spans()
    # waits 50 + 60 ms of 200 ms of sweep iterations
    assert _read("device_wait_share.serve", spans) == pytest.approx(55.0)
    # staging 1 + 0.5 + 2 + 0.5 ms of 200 ms
    assert _read("stage_share.serve", spans) == pytest.approx(2.0)
    # (16 * 4096 + 8 * 8192) / (24 * 4096 + 8 * 8192)
    assert _read("lane_fill.serve", spans) == pytest.approx(
        100.0 * 131072 / 163840)
    # (1000 * 24 + 3000 * 8) / (4096 * 24 + 8192 * 8)
    assert _read("slot_fill.serve", spans) == pytest.approx(
        100.0 * 48000 / 163840)


@pytest.mark.parametrize("name", NAMES)
def test_dispatch_readers_find_nothing_without_the_spans(name):
    assert _read(name, []) is None
    assert spec.metric_reader(name)({}) is None
    # what a program without these spans and counters records
    older = [(W, "sweep.iter", 0, 100 * MS, {"live_lanes": 16}),
             (W, "shard.wait", 1 * MS, 2 * MS, {"shard": 0}),
             (W, "exec.dispatch", 2 * MS, 3 * MS,
              {"shards": 1, "groups": 2, "ragged": True})]
    assert _read(name, older) is None


def test_dispatch_readers_are_in_the_benchmark():
    entries = {m["name"]: m for m in spec.benchmark()["per_layer"]}
    for name in NAMES:
        assert entries[name]["workloads"] == ["serve-c16-s17"]
        assert entries[name]["moves"] == "queries_per_s"
