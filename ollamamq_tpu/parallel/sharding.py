"""Partition specs for model params, KV cache, and activations.

Standard Megatron-style TP layout expressed as jax.sharding PartitionSpecs —
XLA inserts the allgather/reduce-scatter collectives over ICI when the jitted
step consumes these shardings (no explicit NCCL-style calls, unlike the
reference's HTTP fan-out):

  - wq/wk/wv  [D, heads*hd]  -> shard output (head) dim on "tensor"
                                (and wq_gate, a gated model's gate a head)
  - wo        [heads*hd, D]  -> shard input  (head) dim on "tensor"
                                (row-parallel: psum happens via sharding)
  - w_gate/w_up [D, F]       -> shard F on "tensor"
  - w_down     [F, D]        -> shard F on "tensor"
  - embed     [V, D]         -> shard vocab on "tensor" (logits computed
                                shard-local then allgathered by XLA)
  - norms                    -> replicated
  - KV pool   [L, slots, kv_heads*hd]  -> shard the lanes by kv head on
                                "tensor"
"""

from __future__ import annotations

from typing import Any, Dict

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as PS

from ollamamq_tpu.ops.quant import QuantTensor
from ollamamq_tpu.parallel.mesh import AXIS_EXPERT, AXIS_TENSOR


def param_partition_specs(params: Dict[str, Any]) -> Dict[str, Any]:
    """Map a params pytree (nested dicts keyed by layer/tensor name) to
    PartitionSpecs by leaf path name."""

    def spec_for(path: str, leaf) -> PS:
        if isinstance(leaf, QuantTensor):
            # Quantized leaf: payload takes the bf16 tensor's spec; the
            # per-channel scale vector shards with the channel when the
            # payload's SHARDED axis is the channel axis (column-parallel
            # weights, vocab-sharded embed/lm_head) and replicates when
            # the sharded axis is the contraction (row-parallel wo /
            # w_down — their channel dim is unsharded).
            name = path.split("/")[-1]
            qspec = spec_for(path, leaf.q)
            if name in ("wq", "wq_gate", "wk", "wv", "w_gate", "w_up"):
                sspec = PS(*([None] * (leaf.s.ndim - 1)), AXIS_TENSOR)
            elif name in ("embed", "lm_head"):
                sspec = PS(AXIS_TENSOR)  # per-row scales follow the rows
            else:
                sspec = PS()
            return QuantTensor(qspec, sspec)
        name = path.split("/")[-1]
        nd = leaf.ndim
        # Layer weights are stacked on a leading num_layers axis (scan over
        # layers), so the sharded dim is addressed from the right.
        if name in ("wq", "wq_gate", "wk", "wv", "w_gate", "w_up") and nd >= 2:
            return PS(*([None] * (nd - 1)), AXIS_TENSOR)  # column-parallel
        if name in ("wo", "w_down") and nd >= 2:
            return PS(*([None] * (nd - 2)), AXIS_TENSOR, None)  # row-parallel
        # MoE: experts over "expert", per-expert FFN dim over "tensor"
        # (EP x TP composition); the tiny router stays replicated.
        if name in ("we_gate", "we_up"):  # [L, E, D, F]
            return PS(None, AXIS_EXPERT, None, AXIS_TENSOR)
        if name == "we_down":  # [L, E, F, D]
            return PS(None, AXIS_EXPERT, AXIS_TENSOR, None)
        if name in ("bq", "bk", "bv") and nd >= 1:
            return PS(*([None] * (nd - 1)), AXIS_TENSOR)
        if name in ("embed", "lm_head"):
            return PS(AXIS_TENSOR, None)  # vocab-sharded
        return PS()  # norms: replicated

    return _named_map(spec_for, params)


def kv_cache_spec() -> PS:
    """KV slot pool [L, slots, kv_heads*head_dim] and a quantized pool's
    scale rows [L, slots, kv_heads]: the last axis splits by kv head over
    the tensor axis (each shard owns its own heads' lanes and scales)."""
    return PS(None, None, AXIS_TENSOR)


def shard_params(params, mesh: Mesh):
    """Place a params pytree onto the mesh per the partition rules."""
    specs = param_partition_specs(params)
    return jax.tree_util.tree_map(
        lambda p, s: jax.device_put(p, NamedSharding(mesh, s)), params, specs
    )


def _named_map(fn, tree, path=""):
    if isinstance(tree, dict):
        return {k: _named_map(fn, v, f"{path}/{k}") for k, v in tree.items()}
    return fn(path, tree)
