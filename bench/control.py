#!/usr/bin/env python3
"""Readings of the control of ``correct``: the reference put in the
program's place one step below the precision or guarantee the
configuration states.

    python bench/control.py --workload <cell> --seeds 1 2 3

For each seed it prints the numbers a run of the cell compares, as the
control gives them at the cell's own size:

- ``rel_l1`` / ``ppr_rel_l1``: PageRank and PPR computed in bfloat16
  (values and messages), against the float64 reference;
- ``level_mismatch``: each search stopped one hop short of convergence.

The limits in the traffic files lie below these readings and above the
program's own (``PERF.md`` gives both).  The benchmark's runs never run
this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT]

from bench import reference as ref  # noqa: E402
from bench import spec  # noqa: E402
from bench import traffic as traffic_gen  # noqa: E402
from bench.cells import generate  # noqa: E402

#: Searches or PPR queries read per seed.
QUERIES = 6


def readings(c: dict, seed: int) -> dict:
    cfg, t = c["config"], c["traffic"]
    n, src, dst = generate(cfg, seed)
    if t["kind"] == "analytics" and t["program"] == "pagerank":
        d, it = t["params"]["damping"], t["iterations"]
        return {"rel_l1": ref.rel_l1(
            ref.pagerank_bf16(src, dst, n, damping=d, iterations=it),
            ref.pagerank(src, dst, n, damping=d, iterations=it))}
    if t["kind"] == "analytics":
        ops = traffic_gen.analytics_plan(t, src, n, seed)[:QUERIES]
        mism = []
        for _, s, _ in ops:
            lv = ref.bfs_levels(src, dst, n, s, max_iters=t["max_iters"])
            short = ref.bfs_levels(src, dst, n, s, max_iters=ref.depth(lv) - 1)
            mism.append(ref.level_mismatch(short, lv))
        return {"level_mismatch": min(mism)}
    ops = traffic_gen.closed_loop_plan(t, src, n, seed)
    d, mi = t["params"]["ppr"]["damping"], t["max_iters"]
    sources = [s for p, s, _ in ops if p == "ppr"][:QUERIES]
    rel = [ref.rel_l1(ref.ppr_bf16(src, dst, n, s, damping=d, max_iters=mi),
                      ref.ppr(src, dst, n, s, damping=d, max_iters=mi))
           for s in sources]
    return {"ppr_rel_l1": min(rel)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    c = spec.cell(args.workload, spec.benchmark())
    import jax

    out = {"workload": args.workload, "device": jax.devices()[0].device_kind,
           "readings": {s: readings(c, s) for s in args.seeds}}
    print(json.dumps(out, default=float))
    return 0


if __name__ == "__main__":
    sys.exit(main())
