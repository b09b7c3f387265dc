"""BENCHMARK.json keeps to the benchmark's naming rules, and every cell's
configuration, traffic and metrics are found by name."""

import os
import re

import pytest

from bench import spec
from bench.tests.helpers import with_held

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
BENCH = spec.benchmark()
#: With the entries held for a later benchmark PR, which keep the same rules.
ALL = with_held(BENCH)


def _names():
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in ALL[key]:
            yield key, e["name"]
    for w in ALL["workloads"]:
        yield "config", w["config"]
        yield "traffic", w["traffic"]
    for c in ALL["configs"]:
        for r in c["reduced"]:
            yield "reduced", r


@pytest.mark.parametrize("kind,name", list(_names()))
def test_name_characters(kind, name):
    assert NAME.match(name), (kind, name)


@pytest.mark.parametrize("metric", ALL["end_to_end"] + ALL["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_entry(metric):
    assert UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    keys = {"name", "unit", "better", "source", "workloads"}
    if "bound" in metric:
        assert 0.01 <= metric["bound"] <= 0.25
        assert metric["source"] in ("host_clock", "device_trace")
        keys |= {"bound"}
    else:
        assert metric["source"] in ("device_trace", "program_span",
                                    "program_counter", "host_clock")
        keys |= {"layer", "moves"}
        assert metric["moves"] in {m["name"] for m in ALL["end_to_end"]}
    assert set(metric) <= keys
    cells = {w["name"] for w in ALL["workloads"]}
    assert set(metric.get("workloads", cells)) <= cells


def test_names_unique_and_one_line_text():
    for key in ("configs", "workloads"):
        names = [e["name"] for e in BENCH[key]]
        assert len(names) == len(set(names))
    metrics = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(metrics) == len(set(metrics))
    texts = [w["why"] for w in BENCH["workloads"] + BENCH["configs"]]
    texts += [c["source"] for c in BENCH["configs"]]
    texts += [m["layer"] for m in BENCH["per_layer"]]
    for t in texts:
        assert 1 <= len(t) <= 200 and "\n" not in t and "\t" not in t


@pytest.mark.parametrize("cell", [w["name"] for w in ALL["workloads"]])
def test_cell_found_by_name(cell):
    c = spec.cell(cell, ALL)
    assert c["config"]["name"] == c["workload"]["config"]
    assert c["traffic"]["kind"] in ("analytics", "closed_loop")
    e2e = {m["name"] for m in c["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c["per_layer"]
    for m in c["per_layer"]:
        assert callable(spec.metric_reader(m["name"]))


def test_every_metric_file_is_named_in_benchmark():
    # ... or held with its cells for a later benchmark PR (helpers.HELD).
    files = {f[:-3] for f in os.listdir(os.path.join(spec.HERE, "metrics"))
             if f.endswith(".py")}
    assert files == {m["name"] for m in ALL["per_layer"]}


def test_configuration_files_under_paths():
    paths = BENCH["paths"]
    for c in BENCH["configs"]:
        assert any(c["file"].startswith(p + "/") for p in paths)
        assert spec.load_json(os.path.join(spec.ROOT, c["file"]))["name"] == \
            c["name"]


def test_reader_without_data_returns_nothing():
    for m in BENCH["per_layer"]:
        assert spec.metric_reader(m["name"])({}) is None
