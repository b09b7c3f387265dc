"""The reader of the ragged kernel's gather rounds, on hand-built dispatch
spans, and on the spans of a program that records no rounds."""

import pytest

from bench import spec

MS = 1_000_000  # ns
W = "graphserve-worker"
NAME = "round_share.serve"


def _dispatch(t, lanes_pad, rounds=None, rounds_full=None):
    a = {"lanes_live": lanes_pad, "lanes_pad": lanes_pad, "edges": 1000,
         "slots": 4096, "masked": False}
    if rounds is not None:
        a.update(rounds=rounds, rounds_full=rounds_full)
    return (W, "exec.dispatch", t, t + 2 * MS, a)


def _read(spans):
    return spec.metric_reader(NAME)({"spans": spans, "worker": W})


def test_round_share_on_hand_built_spans():
    spans = [
        (W, "sweep.iter", 0, 100 * MS, {"live_lanes": 16}),
        _dispatch(10 * MS, 24, rounds=8192, rounds_full=65536),
        _dispatch(20 * MS, 8, rounds=1024, rounds_full=2048),
        # a dispatch without the counters, as a jnp launch records it
        _dispatch(30 * MS, 24),
        # another thread's dispatches are not the worker's
        ("client", "exec.dispatch", 0, MS,
         {"lanes_pad": 1024, "rounds": 1, "rounds_full": 1 << 20}),
    ]
    # (8192 * 24 + 1024 * 8) / (65536 * 24 + 2048 * 8)
    assert _read(spans) == pytest.approx(100.0 * 204800 / 1589248)


def test_round_share_finds_nothing_without_rounds():
    assert _read([]) is None
    assert spec.metric_reader(NAME)({}) is None
    # what a program that counts no rounds records
    older = [(W, "sweep.iter", 0, 100 * MS, {"live_lanes": 16}),
             _dispatch(2 * MS, 24),
             (W, "exec.dispatch", 5 * MS, 6 * MS,
              {"shards": 1, "groups": 2, "ragged": True})]
    assert _read(older) is None


def test_round_share_is_in_the_benchmark():
    entries = {m["name"]: m for m in spec.benchmark()["per_layer"]}
    m = entries[NAME]
    assert m["workloads"] == ["serve-c16-s17"]
    assert (m["layer"], m["moves"], m["better"]) == (
        "kernel", "queries_per_s", "lower")
