"""Weight initialization and checkpoint loading.

Checkpoints load from either:
  - a safetensors directory in the HF layout (Llama/Qwen2, Mixtral, OLMoE,
    Qwen3-Next and — assumed, see _HF_LAYER_MAP — LFM2 tensor names), or
  - an orbax checkpoint previously saved by `save_orbax`.

Weights land directly in their mesh sharding (each host/device only
materializes its shard) — the TPU analogue of the reference's
"models live inside Ollama" (it never touches weights at all).
"""

from __future__ import annotations

import functools
import os
import re
import logging
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ollamamq_tpu.config import STATE_KINDS, ModelConfig
from ollamamq_tpu.models import llama
from ollamamq_tpu.ops.quant import QuantTensor, quantize_tensor


# Layer matmul weights quantized per-channel along their LAST axis (the
# einsum output channel); embed/lm_head quantize per vocab ROW (axis 0 —
# the logits einsum's output channel AND the embedding gather's row, so
# one scale vector serves both uses of a tied embedding).
QUANT_LAYER_KEYS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
QUANT_ROW_KEYS = ("embed", "lm_head")
log = logging.getLogger("ollamamq.weights")


def quantize_params_int8(params: dict, cfg: ModelConfig) -> dict:
    """Per-channel symmetric int8 quantization of a loaded params tree
    (scales fp32; norms, biases, and q/k norms stay in the load dtype).
    Shapes are unchanged — each quantized leaf becomes a QuantTensor
    pytree node, and the dequant-fused helpers in ops/quant.py keep
    every forward's signature identical."""
    if cfg.num_experts or any(cfg.count(kind) for kind in STATE_KINDS):
        raise ValueError(
            "int8 weight quantization does not cover MoE expert stacks, conv "
            f"or linear-attention layers; load {cfg.name} with "
            "--weights-dtype=bfloat16")
    out = dict(params)
    layers = dict(params["layers"])
    for k in QUANT_LAYER_KEYS:
        if k in layers:
            layers[k] = quantize_tensor(layers[k], axis=-1)
    out["layers"] = layers
    for k in QUANT_ROW_KEYS:
        if k in out:
            out[k] = quantize_tensor(out[k], axis=0)
    return out


# HF tensor name -> (our tree path, transpose?) for one layer.
_HF_LAYER_MAP = {
    "input_layernorm.weight": ("attn_norm", False),
    "self_attn.q_proj.weight": ("wq", True),
    "self_attn.k_proj.weight": ("wk", True),
    "self_attn.v_proj.weight": ("wv", True),
    "self_attn.o_proj.weight": ("wo", True),
    "self_attn.q_proj.bias": ("bq", False),
    "self_attn.q_norm.weight": ("q_norm", False),
    "self_attn.k_norm.weight": ("k_norm", False),
    "self_attn.k_proj.bias": ("bk", False),
    "self_attn.v_proj.bias": ("bv", False),
    "post_attention_layernorm.weight": ("mlp_norm", False),
    "mlp.gate_proj.weight": ("w_gate", True),
    "mlp.up_proj.weight": ("w_up", True),
    "mlp.down_proj.weight": ("w_down", True),
    # LFM2 (names ASSUMED from the published modelling code; no checkpoint
    # can be read offline): norms before the operator and the FFN, the
    # conv operator's two projections, q/k layernorms, `out_proj` for
    # attention's output, and a dense FFN as feed_forward.w1 / w3 / w2
    # (gate / up / down).
    "operator_norm.weight": ("attn_norm", False),
    "ffn_norm.weight": ("mlp_norm", False),
    "self_attn.out_proj.weight": ("wo", True),
    "self_attn.q_layernorm.weight": ("q_norm", False),
    "self_attn.k_layernorm.weight": ("k_norm", False),
    "conv.in_proj.weight": ("conv_in", True),
    "conv.out_proj.weight": ("conv_out", True),
    "feed_forward.w1.weight": ("w_gate", True),
    "feed_forward.w3.weight": ("w_up", True),
    "feed_forward.w2.weight": ("w_down", True),
}
# Qwen3-Next (the published `qwen3_next` tensor names). The shared expert
# and its gate beside the routed experts of the "mlp" block (OLMoE's
# layout below); a linear-attention layer's plain tensors (`A_log` and
# `dt_bias` stay float32). Its two interleaved projections and `q_proj`'s
# [q | gate] a head are un-woven in `_unweave_qwen3_next`.
_HF_LAYER_MAP.update({
    "mlp.shared_expert.gate_proj.weight": ("ws_gate", True),
    "mlp.shared_expert.up_proj.weight": ("ws_up", True),
    "mlp.shared_expert.down_proj.weight": ("ws_down", True),
    "linear_attn.in_proj_qkvz.weight": ("lin_in", True),
    "linear_attn.in_proj_ba.weight": ("lin_ba", True),
    "linear_attn.A_log": ("lin_A_log", False),
    "linear_attn.dt_bias": ("lin_dt_bias", False),
    "linear_attn.norm.weight": ("lin_norm", False),
    "linear_attn.out_proj.weight": ("lin_out", True),
})
# Xing4.0 (`xing4_0`: the hyper-connection's tensor names are ASSUMED — no
# checkpoint or modelling code can be read offline, and config.json names
# none; a loader of real weights must check names, the maps' order H_pre |
# H_post | H_res and that Phi is stored [maps, streams x hidden] as the
# program holds it): a sublayer's `attn_hc` / `mlp_hc` module with its mapping
# matrix, its three scalars and its biases, float32 (inside a sigmoid or an
# exp); the read-out's `model.hc_head.*` beside the final norm (_HC_HEAD).
_HF_LAYER_MAP.update({
    f"{module}.{hf}": (f"{ours}_{name}", False)
    for module, ours in (("attn_hc", "hc_attn"), ("mlp_hc", "hc_mlp"))
    for hf, name in (("phi.weight", "phi"), ("alpha", "alpha"),
                     ("bias", "b"))})
_HC_HEAD = {"model.hc_head.phi.weight": "hc_head_phi",
            "model.hc_head.alpha": "hc_head_alpha",
            "model.hc_head.bias": "hc_head_b"}
_HF_SHARED_GATE = "mlp.shared_expert_gate.weight"  # [1, D]
_HF_LINEAR_TAPS = "linear_attn.conv1d.weight"  # [q | k | v channels, 1, K]
_FLOAT32_KEYS = ("lin_A_log", "lin_dt_bias") + llama.MHC_PARAMS
# The depthwise Conv1d weight [D, 1, K] (cross-correlation behind K-1 zeros
# of left padding: tap K-1 meets the token itself, as `conv_w`'s).
_HF_CONV_TAPS = "conv.conv.weight"
# MoE layouts, told apart by the router's name: (block, the router's
# weight, gate / up / down of one expert, the selection bias or None).
_HF_MOE_LAYOUTS = (
    ("block_sparse_moe", "gate.weight", ("w1", "w3", "w2"), None),  # Mixtral
    ("feed_forward", "gate.weight", ("w1", "w3", "w2"),
     "expert_bias"),  # LFM2 (assumed, as above)
    ("mlp", "gate.weight", ("gate_proj", "up_proj", "down_proj"),
     None),  # OLMoE
)


# Kimi-Linear (the published `kimi_linear` tensor names, ASSUMED from the
# published modelling code; no checkpoint can be read offline). A layer's
# `self_attn` is Kimi Delta Attention or latent attention BY LAYER — both
# have a `q_proj`, of different shapes — so the family has tables of its own,
# a kind each; its expert block is `block_sparse_moe` with the selection
# bias beside the router and the shared expert inside it (`_kimi_linear`).
_KIMI_KDA = {  # published suffix under self_attn. -> (ours, transpose?)
    "f_a_proj.weight": ("kda_fa", True), "f_b_proj.weight": ("kda_fb", True),
    "b_proj.weight": ("kda_b", True), "g_a_proj.weight": ("kda_ga", True),
    "g_b_proj.weight": ("kda_gb", True), "dt_bias": ("lin_dt_bias", False),
    "o_norm.weight": ("lin_norm", False), "o_proj.weight": ("lin_out", True),
}
_KIMI_MLA = {
    "q_proj.weight": ("wq", True),
    "kv_a_proj_with_mqa.weight": ("mla_wdkv", True),
    "kv_a_layernorm.weight": ("mla_kv_norm", False),
    "kv_b_proj.weight": ("mla_wukv", True), "o_proj.weight": ("wo", True),
}
_KIMI_SHARED = {"gate_proj": "ws_gate", "up_proj": "ws_up",
                "down_proj": "ws_down"}
_KIMI_BIAS = "gate.e_score_correction_bias"
# MiMo-V2-Flash (the published `mimo_v2_flash` tensor names, ASSUMED from the
# family's modelling code; no checkpoint can be read offline): every layer's
# `self_attn` has the four projections, of ANOTHER shape in a window layer
# than in a full one — so they are stacked a kind (`_per_kind_attention`),
# the window layers' behind "swa_" — and a window layer the sink's logit a
# head, `attention_sink_bias`; its expert block is `mlp` with the selection
# bias beside the router.
_MIMO_ATTN = {"q_proj.weight": "wq", "k_proj.weight": "wk",
              "v_proj.weight": "wv", "o_proj.weight": "wo"}
_MIMO_SINK = "self_attn.attention_sink_bias"
# (before Mixtral's and OLMoE's, whose block and router they share: told
# apart by the selection bias beside the router)
_HF_MOE_LAYOUTS = (
    ("block_sparse_moe", "gate.weight", ("w1", "w3", "w2"), _KIMI_BIAS),
    ("mlp", "gate.weight", ("gate_proj", "up_proj", "down_proj"),
     _KIMI_BIAS),
) + _HF_MOE_LAYOUTS


# The largest leaf `init_random` draws eagerly (three float32 copies of it
# exist while it is made): the served dense stacks reach 0.95 G parameters
# (Qwen2.5-7B's 14 gate matrices).
EAGER_LEAF_PARAMS = 1 << 30


def init_random(cfg: ModelConfig, seed: int = 0, dtype=jnp.bfloat16,
                mesh=None) -> dict:
    """Seeded random weights. With a `mesh` the tree is generated inside
    one jit whose outputs carry the serving shardings, so every device
    draws only its own shard (the values do not depend on the sharding:
    jax's threefry is partitionable) — a model larger than one chip's
    HBM never has to exist whole on the default device first.

    Without a mesh a dense model is drawn eagerly, stack by stack (each
    holds three float32 copies while it is made: that, and not a step
    program, is a one-chip server's memory peak). An MoE model's expert
    stacks are too large for that — OLMoE's 64 experts x 10 layers are
    1.34 G parameters a stack, 3 x 5.4 GB of float32 — so its tree is
    drawn under one jit, whose fused draw-scale-cast holds no float32
    stack at all; and so is a dense tree with a leaf of more than
    EAGER_LEAF_PARAMS (Falcon-H1's embedding and head, 261,120 x 5120 =
    1.34 G each: drawn eagerly, last, beside 7.8 GB of layers, the first
    ran the chip out of memory; my chip run, PR 54) — and a decoder-hybrid-
    decoder stack's, served whole: its embedding (0.51 G, three float32
    copies 6.1 GB) drawn eagerly beside 6.7 GB of layers set the process's
    memory peak at 15.09 GB, 2.85 GB over what its step programs ever hold
    (my chip run, PR 56)."""
    init = functools.partial(llama.init_params, cfg, dtype=dtype)
    key = jax.random.PRNGKey(seed)
    shapes = jax.eval_shape(init, key)
    largest = max(x.size for x in jax.tree_util.tree_leaves(shapes))
    if mesh is None and not cfg.num_experts and not cfg.mb_per_layer \
            and largest <= EAGER_LEAF_PARAMS:
        return init(key)
    if mesh is None:
        # The stacks held in another device layout than the default
        # (llama.weight_formats) are BORN in it: the draw's own results,
        # so no second copy of such a stack ever exists.
        formats = jax.tree_util.tree_map(lambda _: None, shapes)
        formats["layers"].update(llama.weight_formats(cfg, shapes))
        return jax.jit(init, out_shardings=formats)(key)
    from jax.sharding import NamedSharding

    from ollamamq_tpu.parallel.sharding import param_partition_specs

    specs = param_partition_specs(shapes)
    shardings = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs)
    return jax.jit(init, out_shardings=shardings)(key)


def _unweave_qwen3_next(cfg: ModelConfig, layers: dict) -> None:
    """The published `qwen3_next` projections into the served layout, in
    place. `in_proj_qkvz`'s columns come a KEY-HEAD GROUP at a time — [q_g |
    k_g | v_g | z_g] with dk, dk, r dv, r dv columns, r value heads a key
    head — and `in_proj_ba`'s [b_g | a_g] with r each; served they are [q |
    k | v | z] and [b | a], a part's groups side by side. `q_proj`'s columns
    come a head at a time, [q_h | gate_h]: served apart, `wq` and `wq_gate`."""
    if cfg.attn_output_gate and "wq" in layers:
        w = layers["wq"]
        w = w.reshape(*w.shape[:2], cfg.num_heads, 2, cfg.head_dim)
        layers["wq"], layers["wq_gate"] = (
            w[:, :, :, j].reshape(*w.shape[:2], cfg.q_dim) for j in (0, 1))
    hk, hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    if "lin_in" not in layers or not hk or cfg.kda:  # (KDA: nothing woven)
        return
    r, dk, dv = hv // hk, cfg.linear_key_head_dim, cfg.linear_value_head_dim

    def parts(w, widths):  # [L, D, groups * sum(widths)] -> a part's columns
        w = w.reshape(*w.shape[:2], hk, sum(widths))
        cuts = np.cumsum((0,) + widths)
        return jnp.concatenate(
            [w[..., a:b].reshape(*w.shape[:2], -1)
             for a, b in zip(cuts[:-1], cuts[1:])], axis=-1)

    layers["lin_in"] = parts(layers["lin_in"], (dk, dk, r * dv, r * dv))
    layers["lin_ba"] = parts(layers["lin_ba"], (r, r))


def _kimi_linear(cfg: ModelConfig, grab, having, dtype) -> dict:
    """A Kimi-Linear checkpoint's `self_attn` tensors and shared expert in
    the served layout (`grab`, `having`: load_safetensors'): a KDA layer's
    three projections side by side as `lin_in` and its three depthwise
    convolutions as `lin_conv_w` ([q | k | v]: the same numbers), `A_log`
    [1, 1, H, 1] as [H]; a latent layer's five; the shared expert from inside
    the expert block."""
    def stack(layers_at, make, float32=False):
        return jnp.asarray(np.stack([make(i) for i in layers_at]),
                           dtype=jnp.float32 if float32 else dtype)

    def of(i, suffix, transpose=False):
        return grab(f"model.layers.{i}.self_attn.{suffix}", transpose)

    kda, mla = having("self_attn.f_a_proj.weight"), \
        having("self_attn.kv_b_proj.weight")
    out = {
        "lin_in": stack(kda, lambda i: np.concatenate(
            [of(i, f"{n}_proj.weight", True) for n in "qkv"], axis=1)),
        "lin_conv_w": stack(kda, lambda i: np.concatenate(
            [of(i, f"{n}_conv1d.weight")[:, 0, :] for n in "qkv"])),
        "lin_A_log": stack(kda, lambda i: of(i, "A_log").reshape(-1), True)}
    for table, at in ((_KIMI_KDA, kda), (_KIMI_MLA, mla)):
        for suffix, (ours, tr) in table.items():
            out[ours] = stack(at, lambda i, s=suffix, t=tr: of(i, s, t),
                              ours in _FLOAT32_KEYS)
    shared = having("block_sparse_moe.shared_experts.gate_proj.weight")
    for name, ours in _KIMI_SHARED.items():
        out[ours] = stack(shared, lambda i, n=name: grab(
            f"model.layers.{i}.block_sparse_moe.shared_experts.{n}.weight",
            True))
    return out


def _per_kind_attention(cfg: ModelConfig, grab, dtype) -> dict:
    """A checkpoint's `self_attn` tensors where the window and the full
    layers differ in head shape (`ModelConfig.per_kind_attention`): a stack
    an attention kind, the sink logits in float32."""
    from ollamamq_tpu.config import ATTENTION, WINDOW

    out = {}
    for kind, pre in ((ATTENTION, ""), (WINDOW, "swa_")):
        at = [i for i, (op, _) in enumerate(cfg.kinds) if op == kind]
        for suffix, ours in _MIMO_ATTN.items():
            out[pre + ours] = jnp.asarray(np.stack([
                grab(f"model.layers.{i}.self_attn.{suffix}", True)
                for i in at]), dtype=dtype)
        if cfg.attn_shape(kind).sink:
            out[pre + "sink"] = jnp.asarray(np.stack([
                grab(f"model.layers.{i}.{_MIMO_SINK}", False)
                for i in at]), dtype=jnp.float32)
    return out


def load_safetensors(cfg: ModelConfig, path: str, dtype=jnp.bfloat16) -> dict:
    """Load an HF-layout safetensors checkpoint into the stacked-layer tree."""
    from safetensors import safe_open

    files = sorted(
        os.path.join(path, f) for f in os.listdir(path) if f.endswith(".safetensors")
    )
    if not files:
        raise FileNotFoundError(f"no .safetensors files under {path}")

    raw = {}
    for f in files:
        with safe_open(f, framework="np") as sf:
            for name in sf.keys():
                raw[name] = sf.get_tensor(name)

    def grab(name: str, transpose: bool) -> np.ndarray:
        t = raw[name]
        if t.dtype == np.uint16:  # bfloat16 stored raw
            t = t.view(np.uint16).astype(np.uint32) << 16
            t = t.view(np.float32)
        t = np.asarray(t, dtype=np.float32)
        return t.T if transpose else t

    skipped = sorted(k for k in raw if k.startswith("mtp."))
    if skipped:  # a prediction module the program does not serve
        log.info("%s: %d `mtp.*` tensors of the checkpoint skipped (the "
                 "prediction module is not served for %s)", path,
                 len(skipped), cfg.name)
    layer_names = [k for k in raw if re.match(r"model\.layers\.\d+\.", k)]
    n_layers = 1 + max(int(k.split(".")[2]) for k in layer_names)
    if n_layers != cfg.num_layers:
        raise ValueError(f"checkpoint has {n_layers} layers, config {cfg.num_layers}")

    def having(suffix: str) -> list:
        """The layers that hold `suffix`, in order: a weight is stacked
        over the layers of its kind (models/llama.py:KIND_PARAMS)."""
        return [i for i in range(cfg.num_layers)
                if f"model.layers.{i}.{suffix}" in raw]

    layers: dict = {}
    if cfg.kda:
        layers.update(_kimi_linear(cfg, grab, having, dtype))
    if cfg.per_kind_attention:
        layers.update(_per_kind_attention(cfg, grab, dtype))
    own_attn = cfg.kda or cfg.per_kind_attention
    for hf_suffix, (ours, tr) in _HF_LAYER_MAP.items():
        at = having(hf_suffix)
        if not at or (own_attn and hf_suffix.startswith("self_attn.")):
            continue
        stack = np.stack(
            [grab(f"model.layers.{i}.{hf_suffix}", tr) for i in at])
        layers[ours] = jnp.asarray(
            stack, dtype=jnp.float32 if ours in _FLOAT32_KEYS else dtype)
    for taps, ours in ((_HF_CONV_TAPS, "conv_w"),
                       (_HF_LINEAR_TAPS, "lin_conv_w")):
        if having(taps):  # a depthwise Conv1d's [channels, 1, K]
            layers[ours] = jnp.asarray(np.stack(
                [grab(f"model.layers.{i}.{taps}", False)[:, 0, :]
                 for i in having(taps)]), dtype=dtype)
    if having(_HF_SHARED_GATE):
        layers["w_shared_gate"] = jnp.asarray(np.stack(
            [grab(f"model.layers.{i}.{_HF_SHARED_GATE}", False)[0]
             for i in having(_HF_SHARED_GATE)]), dtype=dtype)
    _unweave_qwen3_next(cfg, layers)

    if cfg.num_experts:
        # Stack experts on axis 1 -> [Le, E, D, F] etc., over the layers
        # that have a router (a dense prefix has none).
        # Host-RAM discipline: the expert stacks dominate the checkpoint
        # (~90% of an 8x7b), so cast each LAYER's expert stack to the
        # target dtype immediately and pop the consumed raw tensors —
        # peak host memory stays near one f32 layer-stack (~2 GB for
        # 8x7b) above the raw checkpoint, instead of ~2.5x it.
        block, router, gate_up_down, bias = next(
            lay for lay in _HF_MOE_LAYOUTS if having(f"{lay[0]}.{lay[1]}")
            and (lay[3] != _KIMI_BIAS or having(f"{lay[0]}.{lay[3]}")))
        at = having(f"{block}.{router}")

        def estack(w_name: str, transpose: bool):
            per_layer = []
            for i in at:
                # (a share holds the experts from `expert_offset` on)
                names = [f"model.layers.{i}.{block}.experts."
                         f"{cfg.expert_offset + e}.{w_name}.weight"
                         for e in range(cfg.num_experts)]
                stack = np.stack([grab(n, transpose) for n in names])
                for n in names:
                    raw.pop(n, None)
                per_layer.append(jnp.asarray(stack, dtype=dtype))
            return jnp.stack(per_layer)

        layers["w_router"] = jnp.asarray(np.stack([
            grab(f"model.layers.{i}.{block}.{router}", True) for i in at
        ]), dtype=dtype)
        if bias is not None and cfg.use_expert_bias:
            layers["router_bias"] = jnp.asarray(np.stack([
                grab(f"model.layers.{i}.{block}.{bias}", False) for i in at
            ]), dtype=jnp.float32)
        layers["we_gate"] = estack(gate_up_down[0], True)
        layers["we_up"] = estack(gate_up_down[1], True)
        layers["we_down"] = estack(gate_up_down[2], True)

    want = jax.eval_shape(lambda: llama.init_params(
        cfg, jax.random.PRNGKey(0), dtype))["layers"]
    bad = [f"{k}: {tuple(layers[k].shape) if k in layers else 'absent'} "
           f"for {tuple(v.shape)}" for k, v in want.items()
           if k not in layers or layers[k].shape != v.shape]
    if bad:
        raise ValueError(f"checkpoint does not hold {cfg.name}'s layers: "
                         + "; ".join(bad))

    params = {
        "embed": jnp.asarray(grab("model.embed_tokens.weight", False), dtype=dtype),
        "final_norm": jnp.asarray(grab(
            "model.embedding_norm.weight" if "model.embedding_norm.weight"
            in raw else "model.norm.weight", False), dtype=dtype),
        "layers": layers,
    }
    if "lm_head.weight" in raw and not cfg.tie_embeddings:
        params["lm_head"] = jnp.asarray(grab("lm_head.weight", False), dtype=dtype)
    if cfg.streams:  # the streams' read-out before the head (ASSUMED names)
        params.update({ours: jnp.asarray(grab(name, False), jnp.float32)
                       for name, ours in _HC_HEAD.items()})
    return params


def save_orbax(params: dict, path: str) -> None:
    import orbax.checkpoint as ocp

    ckptr = ocp.StandardCheckpointer()
    ckptr.save(os.path.abspath(path), params)
    ckptr.wait_until_finished()


def load_orbax(path: str) -> dict:
    import orbax.checkpoint as ocp

    ckptr = ocp.StandardCheckpointer()
    return ckptr.restore(os.path.abspath(path))


def load_params(
    cfg: ModelConfig,
    checkpoint_path: Optional[str] = None,
    seed: int = 0,
    dtype=jnp.bfloat16,
    weights_dtype: str = "bfloat16",
    mesh=None,
) -> dict:
    """Resolve weights: checkpoint dir (safetensors/orbax) or random init.
    `weights_dtype="int8"` quantizes the loaded tree at load time
    (per-channel symmetric, fp32 scales) — the checkpoint is still read
    in `dtype` and the full-precision copy is dropped immediately.
    `mesh` lets a random init land directly in its serving sharding
    (init_random); checkpoints are placed by the caller's shard_params."""
    if checkpoint_path:
        entries = os.listdir(checkpoint_path)
        if any(e.endswith(".safetensors") for e in entries):
            params = load_safetensors(cfg, checkpoint_path, dtype=dtype)
        else:
            params = load_orbax(checkpoint_path)
    else:
        params = init_random(cfg, seed=seed, dtype=dtype, mesh=mesh)
    if weights_dtype == "int8":
        params = quantize_params_int8(params, cfg)
    return params


def _held_in(leaf, fmt) -> bool:
    """Is the placed `leaf` in the dimension order `fmt` names? (The
    device's own layout also carries its tiling, which `fmt` leaves to the
    compiler: only the order is compared.)"""
    return tuple(leaf.format.layout.major_to_minor) == tuple(
        fmt.layout.major_to_minor)


def place_formats(cfg: ModelConfig, params: dict) -> None:
    """Re-lay, IN PLACE and one stack at a time, the leaves of a tree on one
    device that `llama.weight_formats` wants in another device layout than
    they are in (a checkpoint's; `init_random`'s are born so): the tree's
    entry is replaced as each copy is made, so the stack in its old order is
    freed before the next is re-laid and the placement adds one stack, not
    all of them, to what the device holds."""
    for name, fmt in llama.weight_formats(cfg, params).items():
        if not _held_in(params["layers"][name], fmt):
            params["layers"][name] = jax.device_put(
                params["layers"][name], fmt)


def relaid(cfg: ModelConfig, params: dict) -> tuple:
    """(leaves, bytes) of a placed tree held in the device layout
    `llama.weight_formats` names for them, not the default."""
    held = [params["layers"][name]
            for name, fmt in llama.weight_formats(cfg, params).items()
            if _held_in(params["layers"][name], fmt)]
    return len(held), sum(x.nbytes for x in held)


def replicate_kv_heads(params: dict, cfg, r: int) -> dict:
    """Duplicate each KV head r times (consecutively) so num_kv_heads grows
    to r * cfg.num_kv_heads — the replicated-group sharding for
    tp > num_kv_heads: every tensor-parallel shard then owns exactly one
    (duplicated) KV head. Numerics are exactly preserved: q head i maps to
    kv' head i // (H/Hk') and kv'[j] == kv[j // r], which composes to the
    original i // (H/Hk) assignment. Costs r x KV-cache memory."""
    import jax.numpy as jnp

    Hk, hd = cfg.num_kv_heads, cfg.head_dim

    def rep_w(w):  # [L, d, Hk*hd] -> [L, d, r*Hk*hd]
        if isinstance(w, QuantTensor):
            # Per-channel scales live on the duplicated axis: replicate
            # payload and scales in lockstep, numerics exactly preserved.
            return QuantTensor(rep_w(w.q), rep_b(w.s))
        L, d, _ = w.shape
        return jnp.repeat(
            w.reshape(L, d, Hk, hd), r, axis=2
        ).reshape(L, d, r * Hk * hd)

    def rep_b(b):  # [L, Hk*hd] -> [L, r*Hk*hd]
        L, _ = b.shape
        return jnp.repeat(b.reshape(L, Hk, hd), r, axis=1).reshape(L, -1)

    layers = dict(params["layers"])
    layers["wk"] = rep_w(layers["wk"])
    layers["wv"] = rep_w(layers["wv"])
    if "bk" in layers:
        layers["bk"] = rep_b(layers["bk"])
        layers["bv"] = rep_b(layers["bv"])
    out = dict(params)
    out["layers"] = layers
    return out


def _full_logits(params: dict, cfg: ModelConfig, tokens) -> jnp.ndarray:
    """Last-position logits of a full causal forward (no KV pool): the
    minimal teacher-forced probe the quantization guardrail runs on both
    the bf16 and int8 trees."""
    from ollamamq_tpu.ops.attention import causal_attention

    toks = jnp.asarray(tokens, jnp.int32)[None, :]  # [1, T]
    B, T = toks.shape
    seq_lens = jnp.full((B,), T, jnp.int32)
    x = llama.embed_lookup(params["embed"], toks, llama._adtype(params))
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))

    def body(x, lp, kinds, ix):
        return llama._layer_step(
            cfg, lp, kinds, x, positions,
            lambda q, k, v: causal_attention(q, k, v, seq_lens),
            layer=ix.ffn)

    x, _ = llama.scan_layers(cfg, body, x, params["layers"])
    return llama._logits(params, cfg, x[:, -1:, :])[0, 0]  # [V] f32


def quant_guardrail(
    cfg: ModelConfig,
    base_params: Optional[dict] = None,
    q_params: Optional[dict] = None,
    seed: int = 0,
    dtype=jnp.bfloat16,
    prompt_len: int = 16,
    steps: int = 16,
) -> dict:
    """Greedy token-match-rate + max-logit-error of the int8 tree vs its
    bf16 source, teacher-forced on the bf16 model's own greedy rollout
    (so one early mismatch can't cascade into a meaningless diff).
    Publishes `ollamamq_quant_logit_err`; tier-1 pins the bounds
    (tests/test_quantization.py)."""
    from ollamamq_tpu.telemetry import schema as tm

    if base_params is None:
        base_params = init_random(cfg, seed=seed, dtype=dtype)
    if q_params is None:
        q_params = quantize_params_int8(base_params, cfg)
    rng = np.random.default_rng(seed)
    ctx = rng.integers(3, cfg.vocab_size, size=max(1, prompt_len)).tolist()
    step = jax.jit(_full_logits, static_argnums=(1,))
    matches, max_err = 0, 0.0
    for _ in range(steps):
        lb = np.asarray(step(base_params, cfg, ctx))
        lq = np.asarray(step(q_params, cfg, ctx))
        max_err = max(max_err, float(np.max(np.abs(lb - lq))))
        tb, tq = int(np.argmax(lb)), int(np.argmax(lq))
        matches += int(tb == tq)
        ctx = ctx + [tb]  # teacher-forced: both follow the bf16 stream
    out = {
        "steps": steps,
        "token_match_rate": round(matches / max(1, steps), 4),
        "max_logit_err": round(max_err, 6),
        # Scale-free companion: the same max error over the logit spread,
        # so one bound serves both toy and real-shaped configs.
        "rel_logit_err": round(max_err / max(1e-9, float(np.std(lb))), 6),
    }
    tm.QUANT_LOGIT_ERR.labels(model=cfg.name).set(out["max_logit_err"])
    return out
