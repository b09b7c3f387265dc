"""The reduction from a profile and the program's spans to metrics."""

import jax
import jax.numpy as jnp
import pytest

from bench import devtrace, roofline
from bench.devtrace import Profile

MS = 1_000_000  # ns


def _profile():
    k = {"hlo_module": "jit__update_jit",
         "tf_op": "jit(_update_jit)/jit(ell_partials_masked)/pallas_call"}
    c = {"hlo_module": "jit__update_jit"}
    ops = {0: [
        ("custom-call.1", 0 * MS, 5 * MS, k),   # starts before the window
        ("fusion.2", 12 * MS, 14 * MS, c),
        ("custom-call.1", 13 * MS, 20 * MS, k),  # overlaps fusion.2
        ("copy.3", 40 * MS, 50 * MS, c),
        ("fusion.2", 95 * MS, 120 * MS, c),     # ends after the window
    ]}
    return Profile(ops, window=(2 * MS, 100 * MS))


def test_busy_idle_and_kernel_time():
    p = _profile()
    assert p.window_s == pytest.approx(0.098)
    # union of [2,5] [12,20] [40,50] [95,100]
    assert p.busy_s(0) == pytest.approx(0.026)
    assert p.op_seconds(r"ell_partials") == pytest.approx(0.010)
    assert p.gaps(0) == [(5 * MS, 12 * MS), (20 * MS, 40 * MS),
                         (50 * MS, 95 * MS)]
    top = p.top_ops(2)
    assert top[0][0] == "jit__update_jit/custom-call"
    assert top[0][1] == pytest.approx(0.010)


def test_busy_averages_over_chips_used():
    # A cell given four chips whose program runs on one: the idle chips'
    # empty op lines do not dilute the busy time or the top ops.
    ops = {**_profile().ops, 1: [], 2: [], 3: []}
    p = Profile(ops, window=(2 * MS, 100 * MS))
    assert p.used == [0]
    assert p.mean_busy_s() == pytest.approx(0.026)
    assert p.top_ops(1)[0][1] == pytest.approx(0.010)


def test_op_label_drops_hlo_text_and_number():
    text = ("%ell_partials_masked.1 = f32[27377,1,8]{2,1,0} custom-call("
            "s32[27377]{0} %copy-done.1), custom_call_target=\"tpu_custom_call\"")
    assert devtrace._op_label(text, {}) == "ell_partials_masked"
    assert devtrace._op_label("fusion.12", {"hlo_module": "jit_f"}) == \
        "jit_f/fusion"


def test_gaps_named_by_innermost_span():
    p = _profile()
    spans = [
        ("main", "vsw.iter", 0.0, 60 * MS, {}),
        ("main", "shard.wait", 21 * MS, 39 * MS, {"shard": 3}),
        ("main", "exec.dispatch", 41 * MS, 49 * MS, {"shard": 3}),
        ("loader", "shard.load", 0.0, 100 * MS, {}),
    ]
    named = devtrace.name_gaps(p.gaps(0), spans, "main")
    assert named == [["(no span)", pytest.approx(0.045)],
                     ["shard.wait", pytest.approx(0.020)],
                     ["vsw.iter", pytest.approx(0.007)]]


def test_align_spans_through_window_start():
    p = _profile()
    spans = [("main", "x", 1000.0 + 3 * MS, 1000.0 + 4 * MS, {})]
    (s,) = devtrace.align_spans(spans, 1000.0, p)
    assert s[2:4] == (5 * MS, 6 * MS)


def test_roofline_bytes_and_unknown_device():
    peak = {"hbm_bytes_per_s": 1e9, "bf16_flops_per_s": 1e12}
    # 100 edges, 10 rows, 50 vertices, 2 lanes: 400 + 8 * 60 bytes
    assert roofline.spmv_bytes(100, 10, 50, 2) == 880
    assert roofline.least_seconds([(100, 10, 50, 2)] * 2, peak) == \
        pytest.approx(1760 / 1e9)
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("cpu")


def test_load_finds_window_in_recorded_profile(tmp_path):
    f = jax.jit(lambda x: (x * 2).sum())
    x = jnp.ones(128)
    f(x).block_until_ready()
    with devtrace.capture(str(tmp_path)) as t0:
        f(x).block_until_ready()
    assert t0 > 0
    p = devtrace.load(str(tmp_path))
    assert p.window[1] > p.window[0]
    assert p.ops == {}  # the CPU has no TPU device plane
