"""A cell cut to a size the CPU test tier can run."""

import copy

from bench import spec


#: Entries the harness keeps while ``BENCHMARK.json`` leaves them out
#: (``PERF.md``, Open questions), so that their path stays tested: the
#: analytics configuration, its cells, ``teps`` and the readers of the
#: ``VSWEngine`` layers.
HELD = {
    "configs": [{"name": "rmat-s19", "file": "bench/configs/rmat-s19.json",
                 "reduced": ["scale"]}],
    "workloads": [{"name": "pagerank-s19", "config": "rmat-s19",
                   "traffic": "pagerank10", "chips": 1},
                  {"name": "bfs-s19", "config": "rmat-s19",
                   "traffic": "bfs-graph500-keys", "chips": 1}],
    "end_to_end": [{"name": "teps", "unit": "edges/s", "better": "higher",
                    "bound": 0.25, "source": "host_clock",
                    "workloads": ["pagerank-s19", "bfs-s19"]}],
    "per_layer": [
        {"name": f"{m}.vsw", "unit": "%", "better": better, "source": src,
         "layer": layer, "moves": "teps",
         "workloads": ["pagerank-s19", "bfs-s19"]}
        for m, better, src, layer in [
            ("load_wait_share", "lower", "program_span", "pipeline"),
            ("exec_share", "lower", "program_span",
             "executor and host staging"),
            ("spmv_roofline", "higher", "device_trace", "kernel"),
            ("device_idle_share", "lower", "device_trace", "device")]],
}


def with_held(bench: dict) -> dict:
    """``bench`` with the held entries added."""
    out = copy.deepcopy(bench)
    for key, entries in HELD.items():
        have = {e["name"] for e in out[key]}
        out[key] += [copy.deepcopy(e) for e in entries if e["name"] not in have]
    return out


def tiny_cell(name: str, scale: int = 9) -> dict:
    c = copy.deepcopy(spec.cell(name, with_held(spec.benchmark())))
    c["config"]["graph"]["scale"] = scale
    c["config"]["store"].update(num_shards=4, window=256, k=16)
    if c["traffic"]["kind"] == "closed_loop":
        c["traffic"]["warmup_completions"] = 4
    return c
