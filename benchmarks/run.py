"""Benchmark driver: one section per paper table/figure + kernel micros.

Prints ``name,us_per_call,derived`` CSV lines (spec contract).  Run:

    PYTHONPATH=src python -m benchmarks.run [--only graphmp|kernels|train]
"""

from __future__ import annotations

import argparse
import sys
import time
from typing import List

from repro.launch.compile_cache import enable_compile_cache


def bench_train_throughput(rows: List[str]) -> None:
    """End-to-end smoke-scale training throughput (CPU, reduced configs)."""
    from repro import configs
    from repro.config import smoke_config
    from repro.data.tokens import DataConfig
    from repro.optim import adamw
    from repro.train.loop import LoopConfig, train

    for arch in ("qwen2.5-3b", "xlstm-350m"):
        cfg = smoke_config(configs.get_config(arch))
        data_cfg = DataConfig(seq_len=64, global_batch=8,
                              vocab_size=cfg.vocab_size)
        r = train(cfg, data_cfg, LoopConfig(total_steps=8, log_every=0),
                  adamw.AdamWConfig(lr=1e-3, total_steps=8))
        t = sum(r.step_times[2:]) / max(len(r.step_times[2:]), 1)
        toks = data_cfg.seq_len * data_cfg.global_batch
        rows.append(
            f"train_smoke_{arch},{t*1e6:.0f},tokens_per_s={toks/t:.0f}"
            f";final_loss={r.losses[-1]:.3f}"
        )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    choices=[None, "graphmp", "kernels", "train"])
    args = ap.parse_args()
    enable_compile_cache()

    rows: List[str] = []
    t0 = time.time()
    print("name,us_per_call,derived")
    if args.only in (None, "graphmp"):
        from benchmarks import bench_graphmp

        bench_graphmp.run(rows)
    if args.only in (None, "kernels"):
        from benchmarks import bench_kernels

        bench_kernels.run(rows)
    if args.only in (None, "train"):
        bench_train_throughput(rows)

    for r in rows:
        print(r)
    print(f"# total {time.time()-t0:.1f}s, {len(rows)} rows", file=sys.stderr)


if __name__ == "__main__":
    main()
