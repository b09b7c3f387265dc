"""Ahead-of-time v5e compiles of the main-path Pallas kernels at real widths.

The TPU compiler is installed with jaxlib, so a described (not attached)
``v5e:2x2`` topology lets the CPU test tier catch what the Mosaic compiler
would refuse on the chip: misaligned blocks, gathers it cannot lower, too
much VMEM.  Nothing runs; results are checked by the interpret-mode tests.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and every test worker
imports this file.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

N_ELL, K, W, TR = 8192, 128, 16384, 8
N_WINDOWS = 4
COMBINES = ("min", "sum")
LANES = 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def tpu_kernels():
    """Trace the kernels as they trace on a TPU process (compiled, not
    interpreted), and drop every trace on both sides of the module so the
    CPU tests never reuse a TPU-traced kernel."""
    import repro.kernels

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(repro.kernels, "pallas_compiled", lambda: True)
        jax.clear_caches()
        yield
    jax.clear_caches()


@pytest.fixture(scope="module")
def one_chip(topo, tpu_kernels):
    from jax.sharding import SingleDeviceSharding

    sh = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=sh)


def _check(compiled):
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis() is not None


def test_masked_compiles_for_v5e(one_chip):
    from repro.kernels.spmv_ell import kernel as Kn

    c = Kn.ell_partials_masked.lower(
        one_chip((N_ELL, K), jnp.int16), one_chip((N_ELL, K), jnp.bool_),
        one_chip((N_ELL // TR,), jnp.int32),
        one_chip((N_WINDOWS * W,), jnp.float32),
        window=W, tr=TR, combine="min",
    ).compile()
    _check(c)


@pytest.mark.parametrize("lanes,n_ell,n_windows,lane_blocks", [
    (24, 32768, 8, 1),   # the serving cell's launch: one block of 24 lanes
    (96, N_ELL, N_WINDOWS, 2),  # past the VMEM budget: two blocks of 48
], ids=["serve-cell", "two-lane-blocks"])
def test_ragged_compiles_for_v5e(one_chip, lanes, n_ell, n_windows,
                                 lane_blocks):
    from repro.kernels.spmv_ell import kernel as Kn

    assert lanes // Kn.ragged_lane_block(lanes, W) == lane_blocks
    n_tiles, rows = n_ell // TR, W // 128
    ell = (one_chip((n_ell, K), jnp.int16), one_chip((n_ell, K), jnp.bool_))
    c = Kn.ell_partials_ragged.lower(
        *ell, one_chip((n_tiles,), jnp.int32),
        one_chip((n_tiles,), jnp.int32),  # row-loop iterations
        one_chip((n_tiles, rows), jnp.int32),  # row lists, one SMEM block
        one_chip((lanes,), jnp.int32),
        one_chip((lanes, n_windows * W), jnp.float32),
        window=W, tr=TR, combines=COMBINES,
    ).compile()
    _check(c)
    # the prologue that builds the row lists on the device
    table = jax.jit(Kn.ragged_row_table, static_argnames=("window", "tr"))
    c = table.lower(*ell, window=W, tr=TR).compile()
    assert [o.shape for o in c.out_info] == [(n_tiles,), (n_tiles, rows)]


def test_ragged_launch_compiles_for_v5e(one_chip):
    """The whole one-chip ragged launch at the serving cell's shape (24
    lanes, 32,768 ELL rows over eight windows, 16 shards of 8,192 rows):
    the row-list prologue, the kernel and the per-arm segment combine."""
    from repro.kernels.spmv_ell import ops

    lanes, n_ell, n_windows, rows = 24, 32768, 8, 8192
    c = ops._update_lanes_ragged_jit.lower(
        one_chip((n_ell, K), jnp.int16), one_chip((n_ell, K), jnp.bool_),
        one_chip((n_ell,), jnp.int32),  # seg
        one_chip((n_ell // TR,), jnp.int32),  # tile_window
        one_chip((lanes,), jnp.int32),  # combine ids
        one_chip((lanes, n_windows * W), jnp.float32),
        window=W, tr=TR, rows=rows, combines=COMBINES,
    ).compile()
    _check(c)
    assert c.out_info.shape == (lanes, rows)


def test_mesh_ragged_step_compiles_for_v5e_2x2(topo, tpu_kernels):
    """One RaggedFuse mesh step (shard_map over the four described chips,
    pallas body) — the SPMD program a ``mesh=4`` sweep launches."""
    from jax.sharding import Mesh, NamedSharding

    from repro.distributed.sharding import graph_ctx
    from repro.kernels.spmv_ell import ops

    mesh = Mesh(np.asarray(topo.devices).reshape(-1), ("dev",))
    n_dev = mesh.devices.size
    ctx = graph_ctx(mesh)
    rows = 4096
    n_pad_dev = N_WINDOWS * W

    def arg(shape, dtype, *axes):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, ctx.spec(*axes))
        )

    fn = ops._mesh_lanes_ragged_jit(mesh, "pallas", W, TR, rows, COMBINES)
    c = fn.lower(
        arg((n_dev, N_ELL, K), jnp.int16, "device", None, None),
        arg((n_dev, N_ELL, K), jnp.bool_, "device", None, None),
        arg((n_dev, N_ELL), jnp.int32, "device", None),
        arg((n_dev, N_ELL // TR), jnp.int32, "device", None),
        arg((LANES,), jnp.int32, "lane"),
        arg((LANES, n_pad_dev), jnp.float32, "lane", "vertex"),
    ).compile()
    _check(c)
    text = c.as_text()
    assert "all-gather" in text
    per_dev = c.memory_analysis()
    # the stacked ELL block is split over the chips, not replicated
    assert per_dev.argument_size_in_bytes < N_ELL * K * 3 * n_dev
