"""Logical-axis sharding: rules mapping model-space axes to mesh axes.

Models annotate parameters and activations with *logical* axis names
(common.py).  A :class:`ShardingRules` maps them onto mesh axes; the same
model code runs unsharded (rules=None, smoke tests), single-pod, or
multi-pod by swapping rules — the core mechanism behind elastic re-meshing
(a checkpoint stores logical axes, not mesh axes, so it can be restored
onto any mesh shape).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

MeshAxes = Union[str, Tuple[str, ...], None]

#: default rules for the production (pod, data, model) mesh
DEFAULT_RULES: Dict[str, MeshAxes] = {
    "batch": ("pod", "data"),
    "seq": None,
    "embed": ("pod", "data"),  # FSDP: shard params' d_model dim
    "qkv": "model",
    "mlp": "model",
    "vocab": "model",
    "expert": "model",
    "inner": "model",
    "layers": None,
    "kvseq": None,
    "heads": "model",  # per-head state/cache dims (SSM states, KV heads)
    "heads_kv": "model",
    "kvshard": None,  # attention scores' key dim (seq-parallel opt-in)
    "embed_expert": ("pod", "data"),  # expert weights' d_model dim (FSDP)
    "mlp_expert": None,  # expert weights' d_ff dim
}

#: single-pod rules (no "pod" axis in the mesh)
SINGLE_POD_RULES: Dict[str, MeshAxes] = {
    **DEFAULT_RULES,
    "batch": "data",
    "embed": "data",
    "embed_expert": "data",
}

#: sequence-sharded variant for long-context cells (activation seq dim over
#: the model axis; params as in the base rules)
def with_seq_sharding(rules: Dict[str, MeshAxes]) -> Dict[str, MeshAxes]:
    return {**rules, "kvseq": "model"}


@dataclasses.dataclass
class ShardingCtx:
    """Runtime sharding context threaded through model code."""

    mesh: Optional[Mesh] = None
    rules: Optional[Dict[str, MeshAxes]] = None
    attn_impl: str = "xla"  # "xla" (dry-run/CPU) | "pallas" (TPU)
    #: kv-block size for the memory-bounded blocked attention path
    #: (0 = full materialization).  Long-sequence prefill cells set this;
    #: the roofline pipeline adds the analytic correction for FLOPs hidden
    #: inside the kv loop (EXPERIMENTS.md §Roofline methodology).
    attn_block_k: int = 0
    #: Megatron-style sequence parallelism for attention intermediates:
    #: constrain the score/prob tensors' KEY dim onto the TP axis — always
    #: divisible, rescues archs whose head count doesn't divide it
    #: (EXPERIMENTS.md §Perf, whisper iteration 1).
    attn_seq_shard: bool = False
    #: store attention probabilities in bf16 (f32 softmax stats kept)
    attn_bf16_probs: bool = False

    def spec(self, *logical: Optional[str]) -> P:
        if self.rules is None:
            return P()
        return P(*(self.rules.get(ax) if ax else None for ax in logical))

    def ac(self, x: jax.Array, *logical: Optional[str]) -> jax.Array:
        """Activation sharding constraint (no-op without a mesh)."""
        if self.mesh is None or self.rules is None:
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(_auto_axes(self.mesh), self.spec(*logical))
        )

    def param_sharding(self, specs_tree):
        """Map a logical-spec tree to NamedShardings (for in_shardings)."""
        assert self.mesh is not None and self.rules is not None

        def one(spec):
            return NamedSharding(
                self.mesh,
                P(*(self.rules.get(ax) if ax else None for ax in spec)),
            )

        return jax.tree_util.tree_map(
            one, specs_tree, is_leaf=lambda x: isinstance(x, tuple)
        )


def _auto_axes(mesh: Mesh) -> Mesh:
    """The same devices and axis names with every axis Auto.

    ``jax.make_mesh`` builds Explicit axes by default, and
    ``with_sharding_constraint`` only accepts specs over Auto axes."""
    from jax.sharding import AxisType

    if all(t == AxisType.Auto for t in mesh.axis_types):
        return mesh
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


LOCAL_CTX = ShardingCtx()  # unsharded (smoke tests, single CPU)


#: logical axes of the GRAPH workload (mesh sweeps, DESIGN.md §10):
#: - "vertex": destination-vertex dim — sharded over every mesh axis (the
#:   per-device resident slice of the vertex / lane matrices),
#: - "device": the stacked per-device ELL block dim — sharded the same way
#:   (device d's block lands on device d),
#: - "lane":  the serving lane (concurrent-query) dim — replicated; lanes
#:   are vmapped, the vertex axis underneath them is what's sharded.
GRAPH_RULES: Dict[str, MeshAxes] = {
    "vertex": (),  # filled per-mesh by graph_ctx (all axes of that mesh)
    "device": (),
    "lane": None,
}


def graph_ctx(mesh: Mesh) -> ShardingCtx:
    """A :class:`ShardingCtx` for graph mesh sweeps: every mesh axis shards
    the vertex/device dims, lanes replicate.  The mesh kernel builds its
    ``shard_map`` specs through :meth:`ShardingCtx.spec`, so the graph path
    shares the model stack's logical-axis mechanism instead of hand-rolled
    PartitionSpecs."""
    axes = tuple(mesh.axis_names)
    rules = {**GRAPH_RULES, "vertex": axes, "device": axes}
    return ShardingCtx(mesh=mesh, rules=rules)
