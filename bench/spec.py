"""Find a cell's files by the names in ``BENCHMARK.json``.

- configuration ``<name>``: ``bench/configs/<name>.json``, the file that
  ``BENCHMARK.json`` gives for it;
- traffic mix ``<name>``: ``bench/traffic/<name>.json``;
- per-layer metric ``<name>``: ``bench/metrics/<name>.py``, a module with
  ``read(ctx) -> float | None``.

A later cell or metric is added by adding such files and entries; nothing
here changes.
"""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Callable, Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def benchmark(root: str = ROOT) -> Dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def cell(name: str, bench: Dict) -> Dict:
    """The workload entry ``name`` with its configuration and traffic
    loaded, and the metric entries that apply to it."""
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r}; have {sorted(by_name)}")
    w = by_name[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    e2e = [m for m in bench["end_to_end"] if name in m.get("workloads", [name])]
    e2e_names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if name in m.get("workloads", [name]) and m["moves"] in e2e_names]
    return {
        "workload": w,
        "config": load_json(os.path.join(ROOT, conf["file"])),
        "traffic": traffic(w["traffic"]),
        "end_to_end": e2e,
        "per_layer": layer,
    }


def load_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def traffic(name: str) -> Dict:
    return load_json(os.path.join(HERE, "traffic", f"{name}.json"))


def metric_reader(name: str) -> Callable:
    path = os.path.join(HERE, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_metrics(entries: List[Dict], ctx: Dict) -> Dict[str, Dict]:
    """Each reader's value with its unit; a reader that finds nothing to
    read returns ``None`` and the metric is left out."""
    out = {}
    for m in entries:
        v = metric_reader(m["name"])(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out
