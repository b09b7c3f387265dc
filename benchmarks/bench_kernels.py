"""Kernel microbenchmarks: structure + CPU-reference timings.

Off a TPU the Pallas kernels run in the interpreter
(``repro.kernels.pallas_compiled``); wall times from such a run are CPU
numbers, not device metrics — they validate structure and give the
jnp-path CPU baseline.
"""

from __future__ import annotations

import time
from typing import List

import numpy as np

import jax
import jax.numpy as jnp

from repro.launch.compile_cache import enable_compile_cache


def _t(fn, *args, reps=5):
    fn(*args)  # compile/warm
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps


def bench_spmv(rows: List[str]) -> None:
    from repro.core.csr import csr_to_ell
    from repro.core.graph import rmat_graph
    from repro.core.sharding import preprocess
    from repro.core.vsw import update_shard_jnp, update_shard_numpy

    g = rmat_graph(50_000, 1_000_000, seed=0)
    meta, shards = preprocess(g, num_shards=1)
    s = shards[0]
    ell = csr_to_ell(s, g.num_vertices, window=1 << 14, k=128, tr=8)
    msgs = np.random.default_rng(0).random(g.num_vertices).astype(np.float32)

    t_np = _t(lambda: update_shard_numpy(s, None, msgs, "sum"), reps=3)
    t_jnp = _t(lambda: update_shard_jnp(s, ell, msgs, "sum"), reps=3)
    eps = g.num_edges / t_jnp
    rows.append(f"spmv_numpy_oracle,{t_np*1e6:.0f},edges_per_s={g.num_edges/t_np:.3e}")
    rows.append(
        f"spmv_jnp_ell,{t_jnp*1e6:.0f},edges_per_s={eps:.3e}"
        f";pad_ratio={ell.padding_ratio():.2f}"
    )


def bench_bloom(rows: List[str]) -> None:
    from repro.core.bloom import BloomFilter, BloomFilter32

    rng = np.random.default_rng(1)
    members = rng.choice(1 << 24, size=200_000, replace=False)
    queries = rng.integers(0, 1 << 24, size=100_000)
    f = BloomFilter.build(members)
    t = _t(lambda: f.contains(queries), reps=5)
    rows.append(
        f"bloom_host_contains,{t*1e6:.0f},queries_per_s={len(queries)/t:.3e}"
        f";fp_est={f.fp_rate_estimate():.4f}"
    )
    f32v = BloomFilter32.build(members)
    t2 = _t(lambda: f32v.contains(queries), reps=5)
    rows.append(f"bloom32_host_contains,{t2*1e6:.0f},queries_per_s={len(queries)/t2:.3e}")


def bench_attention(rows: List[str]) -> None:
    from repro.kernels.flash_attention.ref import mha_ref
    from repro.models.attention import blocked_attention

    rng = np.random.default_rng(2)
    B, H, S, D = 1, 8, 2048, 64
    q = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
    k, v = q, q
    qT = q.transpose(0, 2, 1, 3)
    ref = jax.jit(lambda a, b, c: mha_ref(a, b, c, causal=True))
    blk = jax.jit(lambda a, b, c: blocked_attention(a, b, c, block_k=512))
    t_ref = _t(ref, qT, qT, qT, reps=3)
    t_blk = _t(blk, q, k, v, reps=3)
    fl = 4 * B * H * S * S / 2 * D
    rows.append(f"attn_xla_full,{t_ref*1e6:.0f},flops_per_s={fl/t_ref:.3e}")
    rows.append(f"attn_xla_blocked,{t_blk*1e6:.0f},flops_per_s={fl/t_blk:.3e}")


def bench_cache_modes(rows: List[str]) -> None:
    from repro.core.cache import MODES, ShardCache
    from repro.core.graph import rmat_graph
    from repro.core.sharding import preprocess
    from repro.core.storage import ShardStore
    import tempfile

    g = rmat_graph(20_000, 400_000, seed=3)
    with tempfile.TemporaryDirectory() as d:
        store = ShardStore(d)
        meta, shards = preprocess(g, num_shards=4)
        store.write_meta(meta)
        for s in shards:
            store.write_shard(s, num_vertices=g.num_vertices,
                              window=1 << 14, k=128, tr=8)
        raw = store.shard_bytes(0, "ell")
        for mid, mode in MODES.items():
            t0 = time.perf_counter()
            blob = mode.compress(raw)
            tc = time.perf_counter() - t0
            t0 = time.perf_counter()
            mode.decompress(blob)
            td = time.perf_counter() - t0
            rows.append(
                f"cache_mode{mid}_{mode.name},{td*1e6:.0f},"
                f"ratio={len(raw)/max(len(blob),1):.2f}"
                f";compress_us={tc*1e6:.0f}"
            )


def bench_ragged_launch(rows: List[str]) -> None:
    """Launch-overhead microbench for RaggedFuse (DESIGN.md §14).

    For G fusion groups on one decoded shard batch, the multi path pays G
    kernel launches; the ragged path pays ONE with an in-kernel combine-arm
    select.  Small graph on purpose: at this scale per-launch overhead
    (trace + staging + dispatch) dominates compute, which is exactly the
    cost the ragged path removes.  Asserts per-group bitwise equality at
    every G.
    """
    from repro.core.csr import csr_to_ell
    from repro.core.graph import rmat_graph
    from repro.core.sharding import preprocess
    from repro.kernels.spmv_ell import ops as spmv_ops

    g = rmat_graph(3_000, 40_000, seed=5)
    meta, shards = preprocess(g, num_shards=2)
    ells = [csr_to_ell(s, g.num_vertices, window=1024, k=16, tr=8)
            for s in shards]
    rng = np.random.default_rng(5)
    combines_all = ["sum", "min", "max", "sum", "min", "max", "sum", "min"]
    for G in (1, 2, 4, 8):
        combines = combines_all[:G]
        msgs = [rng.random((2, g.num_vertices)).astype(np.float32)
                for _ in range(G)]
        t_multi = _t(
            lambda: spmv_ops.ell_update_lanes_multi(ells, msgs, combines),
            reps=5,
        )
        t_ragged = _t(
            lambda: spmv_ops.ell_update_lanes_ragged(ells, msgs, combines),
            reps=5,
        )
        ref = spmv_ops.ell_update_lanes_multi(ells, msgs, combines)
        out = spmv_ops.ell_update_lanes_ragged(ells, msgs, combines)
        bitwise = all(
            np.array_equal(np.nan_to_num(a, posinf=1e30, neginf=-1e30),
                           np.nan_to_num(b, posinf=1e30, neginf=-1e30))
            for accs_r, accs_m in zip(out, ref)
            for a, b in zip(accs_r, accs_m)
        )
        assert bitwise, f"ragged != multi at G={G}"
        rows.append(
            f"ragged_launch_G{G},{t_ragged*1e6:.0f},"
            f"multi_us={t_multi*1e6:.0f}"
            f";speedup={t_multi/max(t_ragged, 1e-12):.2f}"
            f";launches_saved={G - 1}"
            f";bitwise={bitwise}"
        )


SECTIONS = {
    "spmv": bench_spmv,
    "bloom": bench_bloom,
    "attention": bench_attention,
    "cache_modes": bench_cache_modes,
    "ragged_launch": bench_ragged_launch,
}


def run(rows: List[str]) -> None:
    bench_spmv(rows)
    bench_bloom(rows)
    bench_attention(rows)
    bench_cache_modes(rows)
    bench_ragged_launch(rows)


def main() -> None:
    """Standalone entry point: pick sections, optionally merge the rows
    into the consolidated perf trajectory (same file/format as
    bench_graphmp --consolidated)."""
    import argparse
    import json

    ap = argparse.ArgumentParser()
    ap.add_argument("sections", nargs="*", metavar="section",
                    help=f"microbench sections (default: all); one of "
                         f"{sorted(SECTIONS)}")
    ap.add_argument("--out", default=None,
                    help="also write rows as JSON to this path")
    ap.add_argument("--consolidated", default=None, metavar="PATH",
                    help="merge rows into a persistent perf-trajectory "
                         "JSON (bench_graphmp format)")
    args = ap.parse_args()
    enable_compile_cache()

    rows: List[str] = []
    t0 = time.perf_counter()
    if args.sections:
        for name in args.sections:
            if name not in SECTIONS:
                raise SystemExit(
                    f"unknown section {name!r}; have {sorted(SECTIONS)}"
                )
            SECTIONS[name](rows)
    else:
        run(rows)
    wall = time.perf_counter() - t0

    print("name,us_per_call,derived")
    for r in rows:
        print(r)
    if args.out:
        payload = {
            "bench": "kernels",
            "wall_s": wall,
            "rows": [
                dict(zip(("name", "us_per_call", "derived"), r.split(",", 2)))
                for r in rows
            ],
        }
        with open(args.out, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"# wrote {args.out}")
    if args.consolidated:
        try:
            from benchmarks.bench_graphmp import merge_consolidated
        except ImportError:
            from bench_graphmp import merge_consolidated
        merge_consolidated(args.consolidated, rows, quick=False, wall_s=wall)
        print(f"# merged {len(rows)} rows into {args.consolidated}")


if __name__ == "__main__":
    main()
