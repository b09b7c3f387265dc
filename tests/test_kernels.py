"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp oracles,
swept over shapes, dtypes and combine monoids (spec requirement)."""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.bloom import BloomFilter32
from repro.core.csr import csr_to_ell
from repro.core.graph import rmat_graph, star_graph
from repro.core.sharding import preprocess
from repro.core.vsw import update_shard_numpy
from repro.kernels.bloom import ops as bloom_ops
from repro.kernels.bloom.ref import bloom_contains_ref
from repro.kernels.flash_attention import ops as attn_ops
from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_attention.ref import mha_ref
from repro.kernels.spmv_ell import ops as spmv_ops


# ----------------------------------------------------------------- spmv_ell
@pytest.mark.parametrize("window,k,tr", [(256, 8, 8), (512, 32, 8), (1024, 128, 8)])
@pytest.mark.parametrize("combine", ["sum", "min", "max"])
@pytest.mark.parametrize("path", ["masked", "ragged"])
def test_spmv_ell_matches_oracle(window, k, tr, combine, path):
    """The single-query masked update and a one-group, one-lane ragged
    launch each match the scatter-reduce oracle."""
    g = rmat_graph(1500, 20000, seed=42)
    meta, shards = preprocess(g, num_shards=3)
    msgs = np.random.default_rng(0).random(g.num_vertices).astype(np.float32)
    for s in shards:
        e = csr_to_ell(s, g.num_vertices, window=window, k=k, tr=tr)
        oracle = update_shard_numpy(s, None, msgs.astype(np.float64), combine)
        if path == "masked":
            acc = np.asarray(spmv_ops.ell_update(e, msgs, combine))
        else:
            ((acc,),) = spmv_ops.ell_update_lanes_ragged(
                [e], [msgs[None]], [combine])
            acc = acc[0]
        a = np.nan_to_num(acc, posinf=1e30, neginf=-1e30)
        b = np.nan_to_num(oracle, posinf=1e30, neginf=-1e30)
        assert np.allclose(a, b, rtol=1e-4, atol=1e-5), (s.shard_id, combine)


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_spmv_ell_dtypes(dtype):
    g = rmat_graph(400, 3000, seed=1)
    meta, shards = preprocess(g, num_shards=2)
    msgs = np.random.default_rng(1).random(g.num_vertices).astype(np.float32)
    e = csr_to_ell(shards[0], g.num_vertices, window=256, k=16, tr=8)
    acc = np.asarray(
        spmv_ops.ell_update(e, np.asarray(msgs, dtype=np.float32), "sum")
    ).astype(np.float32)
    oracle = update_shard_numpy(shards[0], None, msgs.astype(np.float64), "sum")
    tol = 1e-4 if dtype == np.float32 else 5e-2
    assert np.allclose(acc, oracle, rtol=tol, atol=tol)


def test_spmv_ell_hub_vertex_row_split():
    """A 10k-in-degree hub exercises row splitting across many ELL rows."""
    g = star_graph(10_000)
    meta, shards = preprocess(g, num_shards=1)
    e = csr_to_ell(shards[0], g.num_vertices, window=2048, k=64, tr=8)
    msgs = np.ones(g.num_vertices, np.float32)
    acc = np.asarray(spmv_ops.ell_update(e, msgs, "sum"))
    assert np.isclose(acc[0], 9999.0)  # all spokes point at vertex 0
    assert np.allclose(acc[1:], 0.0)


def test_spmv_ell_empty_shard():
    from repro.core.graph import from_edge_list

    g = from_edge_list([(0, 1)], num_vertices=64)
    meta, shards = preprocess(g, num_shards=2)
    msgs = np.ones(64, np.float32)
    for s in shards:
        e = csr_to_ell(s, 64, window=32, k=8, tr=8)
        acc = np.asarray(spmv_ops.ell_update(e, msgs, "sum"))
        assert acc.shape == (s.rows,)


# -------------------------------------------------------------------- bloom
@pytest.mark.parametrize("n_items,num_hashes", [(100, 2), (5000, 4), (200, 8)])
def test_bloom_kernel_bitexact_vs_host(n_items, num_hashes):
    rng = np.random.default_rng(3)
    items = rng.choice(1 << 22, size=n_items, replace=False).astype(np.int32)
    f = BloomFilter32.build(items, num_hashes=num_hashes)
    queries = rng.integers(0, 1 << 22, size=4096).astype(np.int32)
    host = f.contains(queries)
    dev = bloom_ops.contains(f, queries)
    refv = np.asarray(
        bloom_contains_ref(
            jnp.asarray(f.words), jnp.asarray(queries),
            num_bits=f.num_bits, num_hashes=f.num_hashes,
        )
    )
    assert np.array_equal(dev, host)
    assert np.array_equal(refv, host)
    # no false negatives ever
    assert bloom_ops.contains(f, items).all()


def test_bloom_any_active_shards():
    rng = np.random.default_rng(4)
    sets = [rng.choice(10**6, 300, replace=False) for _ in range(5)]
    filters = [BloomFilter32.build(s) for s in sets]
    active = sets[2][:3].astype(np.int32)  # only shard 2 truly active
    out = bloom_ops.any_active_shards(filters, active)
    assert out[2]
    out_empty = bloom_ops.any_active_shards(filters, np.array([], np.int32))
    assert not out_empty.any()


# ---------------------------------------------------------- flash attention
@pytest.mark.parametrize("B,Hq,Hkv,S,D", [
    (1, 4, 4, 256, 64),     # MHA
    (2, 8, 2, 128, 64),     # GQA 4:1
    (1, 2, 1, 384, 128),    # MQA, odd-ish seq
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_matches_ref(B, Hq, Hkv, S, D, causal):
    rng = np.random.default_rng(5)
    q = rng.standard_normal((B, Hq, S, D), dtype=np.float32)
    k = rng.standard_normal((B, Hkv, S, D), dtype=np.float32)
    v = rng.standard_normal((B, Hkv, S, D), dtype=np.float32)
    ref = mha_ref(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=causal)
    out = attn_ops.attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
        causal=causal, impl="pallas", block_q=128, block_k=128,
    )
    assert np.allclose(np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3)


def test_flash_attention_bf16():
    rng = np.random.default_rng(6)
    mk = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.bfloat16)
    q, k, v = mk(1, 2, 256, 64), mk(1, 2, 256, 64), mk(1, 2, 256, 64)
    ref = mha_ref(q, k, v, causal=True)
    out = attn_ops.attention(q, k, v, causal=True, impl="pallas")
    assert out.dtype == jnp.bfloat16
    a = np.asarray(out, np.float32)
    b = np.asarray(ref, np.float32)
    assert np.allclose(a, b, rtol=5e-2, atol=5e-2)


def test_flash_attention_decode_suffix_alignment():
    """Sq < Skv: queries are the suffix (KV-cache decode convention)."""
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.standard_normal((1, 2, 128, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 2, 512, 64)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 2, 512, 64)), jnp.float32)
    ref = mha_ref(q, k, v, causal=True)
    out = attn_ops.attention(q, k, v, causal=True, impl="pallas")
    assert np.allclose(np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("BH,G,S,D,bk", [
    (4, 8, 1024, 64, 256),
    (2, 1, 512, 128, 128),   # MHA-style group of 1
    (3, 4, 384, 64, 512),    # S < block_k (single padded block)
])
def test_flash_decode_matches_oracle(BH, G, S, D, bk):
    from repro.kernels.flash_attention.kernel import (
        decode_partials_ref, flash_decode,
    )

    rng = np.random.default_rng(11)
    q = jnp.asarray(rng.standard_normal((BH, G, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((BH, S, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((BH, S, D)), jnp.float32)
    lens = rng.integers(1, S + 1, BH)
    valid = jnp.asarray(np.arange(S)[None, :] < lens[:, None])
    out = flash_decode(q, k, v, valid, block_k=bk)
    o, m, l = decode_partials_ref(q, k, v, valid)
    ref = np.asarray(o) / np.maximum(np.asarray(l), 1e-30)[..., None]
    assert np.allclose(np.asarray(out), ref, rtol=2e-3, atol=2e-3)


def test_flash_decode_shard_combine_exact():
    """Partial-softmax merge over KV shards == full softmax — the property
    that makes seq-sharded decode a psum of stats instead of a score
    re-gather (EXPERIMENTS.md §Perf, whisper)."""
    from repro.kernels.flash_attention.kernel import (
        decode_partials_ref, flash_decode_combine,
    )

    rng = np.random.default_rng(12)
    BH, G, S, D, N = 4, 8, 1024, 64, 4
    q = jnp.asarray(rng.standard_normal((BH, G, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((BH, S, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((BH, S, D)), jnp.float32)
    valid = jnp.asarray(np.arange(S)[None, :] < np.array([700, S, 1, 512])[:, None])
    o, m, l = decode_partials_ref(q, k, v, valid)
    full = np.asarray(o) / np.maximum(np.asarray(l), 1e-30)[..., None]
    parts = [decode_partials_ref(q, k[:, i*S//N:(i+1)*S//N],
                                 v[:, i*S//N:(i+1)*S//N],
                                 valid[:, i*S//N:(i+1)*S//N])
             for i in range(N)]
    comb = flash_decode_combine(
        jnp.stack([p[0] for p in parts]),
        jnp.stack([p[1] for p in parts]),
        jnp.stack([p[2] for p in parts]),
    )
    assert np.allclose(np.asarray(comb), full, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("block_q,block_k", [(64, 64), (128, 256)])
def test_flash_attention_block_sweep(block_q, block_k):
    rng = np.random.default_rng(8)
    q = jnp.asarray(rng.standard_normal((1, 2, 256, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 2, 256, 64)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 2, 256, 64)), jnp.float32)
    ref = mha_ref(q, k, v, causal=True)
    out = flash_attention(
        q.reshape(2, 256, 64), k.reshape(2, 256, 64), v.reshape(2, 256, 64),
        causal=True, block_q=block_q, block_k=block_k,
    )
    assert np.allclose(
        np.asarray(out), np.asarray(ref.reshape(2, 256, 64)),
        rtol=2e-3, atol=2e-3,
    )


def test_spmv_ell_tile_chunks_match_one_launch(monkeypatch):
    """Grids longer than MAX_TILES_PER_CALL launch in chunks (the tile map
    lives in SMEM); the chunked partials equal one launch's exactly."""
    import jax

    from repro.kernels.spmv_ell import kernel as Kn

    g = rmat_graph(1500, 20000, seed=9)
    meta, shards = preprocess(g, num_shards=1)
    e = csr_to_ell(shards[0], g.num_vertices, window=256, k=16, tr=8)
    msgs = np.random.default_rng(2).random(e.num_windows * 256).astype(np.float32)
    args = (jnp.asarray(e.ell_idx), jnp.asarray(e.ell_mask),
            jnp.asarray(e.tile_window), jnp.asarray(msgs))
    lane_args = (jnp.asarray(e.ell_idx), jnp.asarray(e.ell_mask),
                 jnp.asarray(e.tile_window),
                 *Kn.ragged_row_table(args[0], args[1], window=256, tr=8),
                 jnp.asarray([0, 1], jnp.int32),
                 jnp.asarray(np.stack([msgs, msgs[::-1].copy()])))

    def run():
        jax.clear_caches()
        return (
            np.asarray(Kn.ell_partials_masked(*args, window=256, tr=8,
                                              combine="sum")),
            np.asarray(Kn.ell_partials_ragged(*lane_args, window=256, tr=8,
                                              combines=("min", "sum"))),
        )

    one = run()
    monkeypatch.setattr(Kn, "MAX_TILES_PER_CALL", 3)
    assert e.n_tiles > 3
    chunked = run()
    jax.clear_caches()
    for a, b in zip(one, chunked):
        assert np.array_equal(a, b)
