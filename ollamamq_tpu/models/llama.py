"""Decoder stacks (Llama / Qwen / Mixtral / OLMoE / LFM2 / Olmo-Hybrid /
Qwen3-Next / K-EXAONE / Falcon-H1 / Kimi-Linear) in pure functional JAX.

A layer is `x + Op(norm(x))`, then `x + FFN(norm(x))` — or, with
`norm_order` "post", `x + norm(Op(x))`, `x + norm(FFN(x))`, or with
`sandwich_norm` both norms: `x + norm'(Op(norm(x)))`. Op is attention
(`_attention_op`: over the whole context, or — a `sliding_attention` layer
— over the last `sliding_window` positions, its K and V in a per-slot ring
beside the pool), a gated short convolution (`_conv_op`) or gated
delta-rule linear attention (`_linear_attention_op`) — or, a `PARALLEL`
layer (Falcon-H1), attention AND a state-space mixer (`_ssm_op`) over the
same normed input, both added to the residual; FFN is a dense
SwiGLU (`_mlp`) or routed experts (models/moe.py). Each is defined ONCE and
used by every forward; `ModelConfig.kinds` says which pair a layer is. A
uniform stack (every family but the hybrids) is the case of one kind.

Design notes (TPU-first):
  - Layer parameters are STACKED by kind — a weight's leading axis counts
    the layers that HAVE it (`wq` the attention layers, `conv_in` the conv
    layers, `w_gate` the dense-FFN layers, `we_*` the expert layers; the
    two norms every layer) — and the forward is a `lax.scan` over each run
    of `ModelConfig.layer_plan()`: a repeated period whose layers are
    unrolled in the scan's body, each reading its weights from the stacks
    by index (`scan_layers`). One traced body a DISTINCT layer of a
    period, whatever the depth: fast XLA compile. The per-sequence state —
    the KV pool ([attention layers, S, Hk*hd], the layout the kernels DMA
    from) and the conv layers' state ([conv layers, K-1, slots, D],
    ops/shortconv.py) — is the scan's CARRY, never its xs/ys: a layer
    scatters the step's rows into `pool[a]` / `conv[c]` in place and
    attention reads the whole pool by layer index, so a step moves the
    rows it writes and the pages it reads — not the pool.
  - Served: `forward_ragged` (one flattened stream of prefill spans and
    decode tokens over the paged pool), `forward_decode` (one token per
    slot: the fused scan's body), `forward_embed` and the encoder; all
    shape-static => one jit per padded shape.
    `forward_prefill` (whole prompts, dense causal attention, the
    convolution over shifted copies: no state) is the plain oracle that
    tests, the benchmark's reference and the dry run compare them with;
    the engine does not call it.
  - All matmuls run in the params dtype (bf16 on TPU => MXU), softmax,
    the router, the convolution's sum and logits in f32.
  - Qwen2.5 support = `attn_bias=True` in ModelConfig; the same code path
    serves both families (capability parity with the reference's two
    stress-test models, /root/reference/test_dispatcher.sh:5-7). Qwen3,
    LFM2 and OLMoE are `qk_norm` ("head" / "full"); Mixtral, OLMoE and
    LFM2 replace the FFN by routed experts (`num_experts`, models/moe.py),
    LFM2 after a dense prefix (`num_dense_layers`).
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ollamamq_tpu.config import (ATTENTION, ATTENTION_KINDS, CONV, CROSS,
                                 DENSE, EXPERTS, GMU, LINEAR, MAMBA, PARALLEL,
                                 S6_D_CONV, S6_D_STATE, SPARSE, WINDOW,
                                 ModelConfig)
from ollamamq_tpu.models.moe import (SHARED, SHARED_GATE, STACKED,
                                     init_moe_layer_params, moe_mlp)
from ollamamq_tpu.ops import (block_select, gated_delta, hyper_connection,
                              mla, selective_scan, shortconv, ssd)
from ollamamq_tpu.ops.attention import (
    WindowRing,
    alloc_ring,
    causal_attention,
    bidirectional_attention,
    flat_slot_indices,
    lay_heads,
    paged_decode_attention,
    paged_decode_attention_any,
    ragged_attention_any,
    ring_table,
    ring_write_slots,
    split_head,
)
from ollamamq_tpu.ops.quant import embed_lookup, kv_write, logits_head, qeinsum
from ollamamq_tpu.ops.rope import (apply_rope, apply_rope_freqs, rope_freqs,
                                   yarn_cos_scale, yarn_freqs)

# Stage names on the device trace (jax.named_scope: op metadata only, the
# lowered programs compute the same thing). README's span table lists
# them; tests/test_trace_spans.py finds each in the lowered ragged and
# decode programs. An MoE model's "mlp" holds models/moe.py:SCOPES.
SCOPES = ("embed", "attn_qkv", "kv_write", "attention", "attn_out", "mlp",
          "lm_head", "sampling")
# ...and, inside "attn_out", where a model with `attn_output_gate` multiplies
# the attended values by its sigmoid gate.
GATE_SCOPES = ("attn_gate",)
# ...and of a model whose attention kinds differ in head shape
# (`per_kind_attention`): where v is multiplied by `attention_value_scale`
# (inside "attn_qkv") and where the jnp attentions add a window layer's sink
# to the softmax (inside "attention"; ops/attention.py — the Pallas kernels
# fold it in their last lines, inside the launch).
PER_KIND_SCOPES = ("attn_vscale", "attn_sink")
PER_KIND_KEY = 0x73776131
# ...and of a stack whose residual path is several streams (`hc_mult`): the
# mappings and the weighted sum a sublayer reads, the mix it writes back
# through (both around every sublayer, the second inside "mlp" too), and the
# streams' read-out inside "lm_head" (ops/hyper_connection.py; on the chip
# the launches of ops/pallas/hyper_connection.py).
MHC_SCOPES = ("mhc_mix_in", "mhc_mix_out", "mhc_read_out")  # = schema's
MHC_KEY = 0x6D686331
# A sublayer's connection, stacked over ALL layers as the norms are (no entry
# of KIND_PARAMS): Phi [maps, n D] maps-major, the three scalars, the biases —
# float32, inside a sigmoid or an exp — for the attention and the FFN sublayer.
MHC_PARAMS = ("hc_attn_phi", "hc_attn_alpha", "hc_attn_b",
              "hc_mlp_phi", "hc_mlp_alpha", "hc_mlp_b")
# Seeded random init, drawn so that the DYNAMIC part matters: Phi N(0, 1 / n D)
# under scalars around 1 gives logits of standard deviation ~1 across tokens;
# the biases N(0, sd) with H_res's diagonal lifted, so that H_res is neither
# uniform nor the identity (a token's own stream keeps ~e^lift times the
# weight of another's before the iterations).
MHC_ALPHA_SD, MHC_BIAS_SD, MHC_RES_LIFT = 0.25, 0.5, 1.5
# Standard deviation of a seeded-random sink logit (float32): of the order of
# the scores' (q . k / sqrt(d) of unit-variance heads is ~1), so that a
# forward which drops the sink computes another model.
SINK_SD = 1.0
# ...and the prediction module's three (`forward_mtp`): the two norms and
# the projection of [embedding | hidden], its block (which holds a layer's
# own scopes), its norm and the trunk's head.
MTP_SCOPES = ("mtp_embed_proj", "mtp_block", "mtp_head")
MTP_KEY = 0x6D747030
# ...and a conv layer's three stages, beside the attention layers' four:
# the in-projection, the gated convolution with its state read and write,
# the out-projection.
CONV_SCOPES = ("conv_in", "conv_mix", "conv_out")
# ...and a linear-attention layer's four: the projections and gates, the
# convolution over q | k | v with its window, the delta rule with its state,
# the gated output norm and out-projection.
LINEAR_SCOPES = ("lin_in", "lin_conv", "lin_rule", "lin_out")
# ...and, where the linear kind is read as Kimi Delta Attention, inside
# "lin_in" the decay's and the output gate's low-rank projections with the
# gate arithmetic, and inside "lin_rule" the window solve with a decay a key
# channel (ops/gated_delta.py:_prepare_vector).
KDA_SCOPES = ("kda_gates", "kda_prepare")
KDA_KEY = 0x6B646131
# Seeded random init of Kimi Delta Attention's gated head norm, drawn around
# 1 so that a forward which leaves it out computes another model.
KDA_NORM_SD = 0.1
# ...and a state-space mixer's four, beside the attention's four inside a
# parallel layer: the in-projection with its multipliers, the convolution
# over x | B | C with its window, the recurrence with its state, the gated
# grouped norm and out-projection.
SSM_SCOPES = ("ssm_in", "ssm_conv", "ssm_scan", "ssm_out")
# ...and a decoder-hybrid-decoder stack's: a mamba layer's three (the
# projections, the convolution and dt; the selective scan with its state; the
# gate and out-projection), a gated memory unit, a cross layer's attention
# over another layer's pool rows (beside "attention": the full and window
# layers'), differential attention's subtraction and pair norm, and the
# gather of the sampled rows where the others leave the stack.
HYBRID_SCOPES = ("mamba_in", "mamba_scan", "mamba_out", "gmu",
                 "cross_attention", "diff_combine", "early_exit_gather")
HYBRID_KEY = 0x70686934
# ...and a block-sparse layer's four inside "kv_write" / beside "attention"
# (the pooled keys a step's tokens complete; the block scores and the top-k;
# the walk over the kept blocks' pages; a span under its block masks), and a
# lightning layer's three: the projections with the q/k norm and RoPE, the
# recurrence with its state, the output norm, gate and out-projection.
BSA_SCOPES = ("bsa_pool_write", "bsa_select", "bsa_attention", "bsa_span")
LIGHTNING_SCOPES = ("ltn_in", "ltn_rule", "ltn_out")
LIGHTNING_KEY = 0x6C74676E
# Seeded random init of a lightning layer's three norm weights, drawn around
# 1 so that a forward which leaves one out computes another model.
LIGHTNING_NORM_SD = 0.1
# Seeded random init of what such a stack holds beside its matrices, drawn
# AWAY from the identity (as the mixers' above): every bias — the norms', the
# convolution's, the projections' — and the four lambda vectors N(0, 0.1), the
# skip D and the pair norm's weight around 1.
HYBRID_BIAS_SD, HYBRID_LAMBDA_SD = 0.1, 0.1
# fold_in constant of the conv layers' init keys (the other weights' keys
# are the ten of one split, as before the family existed).
CONV_KEY = 0x636F6E76
LINEAR_KEY = 0x6C696E72
MLA_KEY = 0x6D6C6174
GATED_KEY = 0x67617465
SSM_KEY = 0x73736D78
# Seeded random init of what a mixer holds beside its matrices, drawn AWAY
# from the identity so that a forward which leaves one out computes another
# model: the skip D around 1, the gated norm's weight around 1, the
# convolution's bias around 0.
SSM_D_SD, SSM_NORM_SD, SSM_CONV_BIAS_SD = 0.25, 0.1, 0.25
# Standard deviation of a seeded-random ZERO-CENTRED norm weight (float32
# draw, as moe.ROUTER_BIAS_SD): a stored zero is the identity whether a
# forward multiplies by w or by 1 + w, so the weights are drawn away from it.
ZERO_CENTRED_NORM_SD = 0.1
# A latent-attention layer's weights (config.py: `kv_lora_rank`), beside
# `wo`: q down, its norm, q up; kv down to [c_kv | k_rope], c_kv's norm,
# kv up to a head's [k_nope | v]; the indexer's q (from the normed q
# latent), k (from the hiddens) with its LayerNorm, and head weights.
# (`q_lora_rank` 0: no q down, norm or up — a full-rank `wq`.)
MLA_PARAMS = ("mla_wdq", "mla_q_norm", "mla_wuq", "mla_wdkv", "mla_kv_norm",
              "mla_wukv", "idx_wq", "idx_wk", "idx_k_norm", "idx_k_bias",
              "idx_ww")
# The indexer's LayerNorm (the published module's own: not the stack's).
INDEX_NORM_EPS = 1e-6
# Seeded random init of the rule's decay (the published code's): A uniform
# in [1, 16], the step dt log-uniform in [1e-3, 1e-1], dt_bias its inverse
# softplus — so that a = exp(-A softplus(x W_a + dt_bias)) neither kills
# the state nor freezes it.
LINEAR_A_RANGE, LINEAR_DT_RANGE = (1.0, 16.0), (1e-3, 1e-1)
# The weights of each operator and FFN kind (stacked over the layers of
# that kind; the attention weights over the layers of EITHER attention kind,
# window or full, in layer order: `scan_layers`); every other entry of
# `layers` is stacked over all layers.
KIND_PARAMS = {
    ATTENTION: ("wq", "wq_gate", "wk", "wv", "wo", "bq", "bk", "bv", "bo",
                "q_norm", "k_norm", "diff_lambda", "diff_norm") + MLA_PARAMS,
    MAMBA: ("s6_in", "s6_conv_w", "s6_conv_b", "s6_x", "s6_dt", "s6_dt_bias",
            "s6_A_log", "s6_D", "s6_out"),
    # a window layer's where the two attention kinds differ in head shape
    # (`per_kind_attention`: the full layers' are ATTENTION's four): the
    # attention layers' names behind "swa_", and the sink's logit a head
    WINDOW: ("swa_wq", "swa_wk", "swa_wv", "swa_wo", "swa_sink"),
    GMU: ("gmu_in", "gmu_out"),
    # a cross layer's: the attention layers' names behind an "x" (a stack of
    # their own: no wk, no wv, and another count of layers)
    CROSS: ("xwq", "xbq", "xwo", "xbo", "xdiff_lambda", "xdiff_norm"),
    CONV: ("conv_in", "conv_w", "conv_out"),
    # (the delta rule's — Kimi Delta Attention's: no `lin_ba`, its gates'
    # five —, then the lightning reading's: a model has one)
    LINEAR: ("lin_in", "lin_ba", "lin_conv_w", "lin_A_log", "lin_dt_bias",
             "lin_norm", "lin_out", "kda_fa", "kda_fb", "kda_b", "kda_ga",
             "kda_gb", "ltn_wq", "ltn_wk", "ltn_wv", "ltn_wz",
             "ltn_wo", "ltn_q_norm", "ltn_k_norm", "ltn_norm"),
    PARALLEL: ("ssm_in", "ssm_dt", "ssm_conv_w", "ssm_conv_b", "ssm_A_log",
               "ssm_D", "ssm_dt_bias", "ssm_norm", "ssm_out"),
    DENSE: ("w_gate", "w_up", "w_down"),
    EXPERTS: ("w_router", "router_bias") + SHARED + SHARED_GATE + STACKED,
}


def _adtype(params: dict):
    """Activation dtype for a forward: norm weights are never quantized,
    so final_norm carries the compute dtype even when embed/matmul
    weights are int8 QuantTensors."""
    return params["final_norm"].dtype


def rmsnorm(x: jnp.ndarray, w: jnp.ndarray, eps: float,
            zero_centred: bool = False) -> jnp.ndarray:
    """x / rms(x) times w — or, `zero_centred` (Qwen3-Next's), times
    (1 + w) with the product in float32."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    if zero_centred:
        return (xf * jax.lax.rsqrt(var + eps)
                * (1.0 + w.astype(jnp.float32))).astype(x.dtype)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w


def layernorm(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
              eps: float) -> jnp.ndarray:
    """(x - mean) rsqrt(var + eps) w + b, the statistics in float32."""
    xf = x.astype(jnp.float32)
    xf = xf - jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w + b


def _norm(cfg: ModelConfig, x: jnp.ndarray, w: jnp.ndarray,
          b=None) -> jnp.ndarray:
    """The stack's norm (the block norms, the final norm, the per-head
    q/k norms): an RMSNorm, plain or zero-centred, by the config — or,
    with `layer_norm_eps`, a LayerNorm with the bias `b`."""
    if cfg.layer_norm_eps is not None:
        return layernorm(x, w, b, cfg.layer_norm_eps)
    return rmsnorm(x, w, cfg.rms_norm_eps, cfg.zero_centred_norm)


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16) -> dict:
    """Random-init a params pytree (layers stacked by kind on axis 0)."""
    d, qd, kvd, f = cfg.hidden_size, cfg.q_dim, cfg.kv_dim, cfg.intermediate_size
    # The prediction module's block is ONE MORE entry at the end of every
    # stack its kind of layer has (the norms, the latent attention's, the
    # experts'): the trunk's loop never reaches it, `forward_mtp` reads it.
    n_mtp = cfg.num_nextn_predict_layers
    L, v = cfg.num_layers + n_mtp, cfg.vocab_size
    La, Lc, Ld = cfg.attn_layers + n_mtp, cfg.count(CONV), cfg.count(DENSE)
    Ll, Ls = cfg.count(LINEAR), cfg.count(PARALLEL)
    keys = jax.random.split(key, 10)
    hk = iter(jax.random.split(jax.random.fold_in(key, HYBRID_KEY), 32)) \
        if cfg.mb_per_layer else None

    def bias(shape, sd=HYBRID_BIAS_SD, around=0.0, as_dtype=dtype):
        """A decoder-hybrid-decoder stack's biases and small vectors."""
        return (around + sd * jax.random.normal(
            next(hk), shape, jnp.float32)).astype(as_dtype)

    def w(k, shape, fan_in, over=1.0):
        """N(0, 1 / fan_in) — divided by `over`, the muP scalar(s) a forward
        multiplies the tensor's result by (Falcon-H1; 1 elsewhere: nothing
        is traced): multiplier x tensor then has the plain tensor's scale,
        which is what the published multipliers are for; the checkpoint's
        own init scales are not in config.json."""
        x = jax.random.normal(k, shape, jnp.float32) / jnp.sqrt(fan_in)
        if not isinstance(over, jax.Array) and over == 1:
            return x.astype(dtype)  # (no fourth float32 copy of a stack)
        return (x / over).astype(dtype)

    gk = iter(jax.random.split(jax.random.fold_in(key, GATED_KEY), 8))

    def norm_w(shape):
        """A norm weight of the stack: one — or, zero-centred, drawn around
        zero, so that a forward which reads w for 1 + w computes another
        model."""
        if not cfg.zero_centred_norm:
            return jnp.ones(shape, dtype)
        return (ZERO_CENTRED_NORM_SD * jax.random.normal(
            next(gk), shape, jnp.float32)).astype(dtype)

    layers = {"attn_norm": norm_w((L, d)), "mlp_norm": norm_w((L, d))}
    if cfg.layer_norm_eps is not None:
        layers.update(attn_norm_b=bias((L, d)), mlp_norm_b=bias((L, d)))
    if cfg.sandwich_norm:
        layers.update(post_attn_norm=norm_w((L, d)),
                      post_mlp_norm=norm_w((L, d)))
    if La and cfg.kv_lora_rank:
        mk = jax.random.split(jax.random.fold_in(key, MLA_KEY), 8)
        H, r, c = cfg.num_heads, cfg.q_lora_rank, cfg.kv_lora_rank
        od = H * cfg.v_head_dim
        Hi, di = cfg.index_n_heads, cfg.index_head_dim
        if r:
            layers.update(
                mla_wdq=w(mk[0], (La, d, r), d),
                mla_q_norm=jnp.ones((La, r), dtype),
                mla_wuq=w(mk[1], (La, r, qd), r))
        else:  # a full-rank q projection
            layers["wq"] = w(mk[0], (La, d, qd), d)
        layers.update(
            mla_wdkv=w(mk[2], (La, d, cfg.latent_dim), d),
            mla_kv_norm=jnp.ones((La, c), dtype),
            mla_wukv=w(mk[3], (La, c, H * (cfg.qk_nope_head_dim
                                           + cfg.v_head_dim)), c),
            wo=w(mk[4], (La, od, d), od))
        if cfg.index_topk:
            layers.update(
                idx_wq=w(mk[5], (La, r, Hi * di), r),
                idx_wk=w(mk[6], (La, d, di), d),
                idx_k_norm=jnp.ones((La, di), dtype),
                idx_k_bias=jnp.zeros((La, di), dtype),
                idx_ww=w(mk[7], (La, d, Hi), d))
    elif La and cfg.per_kind_attention:
        # Each kind's four projections at its own head shape, a stack a
        # kind; the window layers' sink logits in float32 (inside an exp).
        pk = jax.random.split(jax.random.fold_in(key, PER_KIND_KEY), 5)
        full, win = map(cfg.attn_shape, ATTENTION_KINDS)
        Lf, Lw = cfg.count(ATTENTION), cfg.count(WINDOW)
        layers.update(
            wq=w(keys[0], (Lf, d, full.q_lanes), d),
            wk=w(keys[1], (Lf, d, full.k_lanes), d),
            wv=w(keys[2], (Lf, d, full.v_lanes), d),
            wo=w(keys[3], (Lf, full.o_lanes, d), full.o_lanes),
            swa_wq=w(pk[0], (Lw, d, win.q_lanes), d),
            swa_wk=w(pk[1], (Lw, d, win.k_lanes), d),
            swa_wv=w(pk[2], (Lw, d, win.v_lanes), d),
            swa_wo=w(pk[3], (Lw, win.o_lanes, d), win.o_lanes))
        if win.sink:
            layers["swa_sink"] = SINK_SD * jax.random.normal(
                pk[4], (Lw, win.heads), jnp.float32)
    elif La:
        layers.update(
            wq=w(keys[0], (La, d, qd), d, cfg.attention_in_multiplier),
            wk=w(keys[1], (La, d, kvd), d,
                 cfg.attention_in_multiplier * cfg.key_multiplier),
            wv=w(keys[2], (La, d, kvd), d, cfg.attention_in_multiplier),
            wo=w(keys[3], (La, qd, d), qd, cfg.attention_out_multiplier))
        if cfg.attn_output_gate:  # a gate a head, beside q
            layers["wq_gate"] = w(next(gk), (La, d, qd), d)
        if cfg.attn_bias:
            layers["bq"] = jnp.zeros((La, qd), dtype)
            layers["bk"] = jnp.zeros((La, kvd), dtype)
            layers["bv"] = jnp.zeros((La, kvd), dtype)
        if cfg.mb_per_layer:
            layers.update(_init_hybrid(cfg, La, w, bias, next(hk)))
        if cfg.qk_norm_kind == "head":
            # Qwen3, LFM2: per-head RMSNorm on q/k (weight over head_dim).
            layers["q_norm"] = norm_w((La, cfg.head_dim))
            layers["k_norm"] = norm_w((La, cfg.head_dim))
        elif cfg.qk_norm_kind == "full":
            # OLMoE: RMSNorm over the whole projected q / k vector.
            layers["q_norm"] = jnp.ones((La, qd), dtype)
            layers["k_norm"] = jnp.ones((La, kvd), dtype)
    if Lc:
        # Gated short convolution: in-projection to [B | C | u], the
        # depthwise taps [D, K] (w[:, K-1] meets the token itself), and
        # the out-projection.
        ck = jax.random.split(jax.random.fold_in(key, CONV_KEY), 3)
        layers.update(
            conv_in=w(ck[0], (Lc, d, 3 * d), d),
            conv_w=w(ck[1], (Lc, d, cfg.conv_L_cache), cfg.conv_L_cache),
            conv_out=w(ck[2], (Lc, d, d), d))
    if Ll and cfg.lightning_nh:
        # Lightning attention: q, k, v and the gate in, out; the per-head
        # q/k norms and the output norm over all heads' lanes.
        tk = jax.random.split(jax.random.fold_in(key, LIGHTNING_KEY), 8)
        ld = cfg.lightning_nh * cfg.lightning_head_dim

        def around_one(k, shape):
            return (1.0 + LIGHTNING_NORM_SD * jax.random.normal(
                k, shape, jnp.float32)).astype(dtype)

        layers.update(
            ltn_wq=w(tk[0], (Ll, d, ld), d), ltn_wk=w(tk[1], (Ll, d, ld), d),
            ltn_wv=w(tk[2], (Ll, d, ld), d), ltn_wz=w(tk[3], (Ll, d, ld), d),
            ltn_wo=w(tk[4], (Ll, ld, d), ld),
            ltn_q_norm=around_one(tk[5], (Ll, cfg.lightning_head_dim)),
            ltn_k_norm=around_one(tk[6], (Ll, cfg.lightning_head_dim)),
            ltn_norm=around_one(tk[7], (Ll, ld)))
    elif Ll and cfg.kda:
        # Kimi Delta Attention: in-projection to [q | k | v] (the three
        # published projections side by side), the depthwise taps over them,
        # the decay's low-rank pair (hidden -> head size -> a key channel a
        # head) with dt_bias a channel and A_log a head (float32: inside an
        # exp), b's projection a head, the output gate's low-rank pair, the
        # gated norm over a value head, the out-projection.
        kk = jax.random.split(jax.random.fold_in(key, KDA_KEY), 11)
        cd, kd, vd = (cfg.linear_conv_dim, cfg.linear_key_dim,
                      cfg.linear_value_dim)
        H, r, K = (cfg.linear_num_value_heads, cfg.linear_key_head_dim,
                   cfg.linear_conv_kernel_dim)
        dt = jnp.exp(jax.random.uniform(
            kk[4], (Ll, kd), jnp.float32, *map(jnp.log, LINEAR_DT_RANGE)))
        layers.update(
            lin_in=w(kk[0], (Ll, d, cd), d),
            lin_conv_w=w(kk[1], (Ll, cd, K), K),
            kda_fa=w(kk[2], (Ll, d, r), d), kda_fb=w(kk[5], (Ll, r, kd), r),
            lin_A_log=jnp.log(jax.random.uniform(
                kk[3], (Ll, H), jnp.float32, *LINEAR_A_RANGE)),
            lin_dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
            kda_b=w(kk[6], (Ll, d, H), d),
            kda_ga=w(kk[7], (Ll, d, r), d), kda_gb=w(kk[8], (Ll, r, vd), r),
            lin_norm=(1.0 + KDA_NORM_SD * jax.random.normal(
                kk[9], (Ll, cfg.linear_value_head_dim),
                jnp.float32)).astype(dtype),
            lin_out=w(kk[10], (Ll, vd, d), vd))
    elif Ll:
        # Gated delta rule: in-projection to [q | k | v | z] (the first
        # three pass the convolution), the two gates [b | a] a head, the
        # depthwise taps [q | k | v channels, K], the decay's A_log and
        # dt_bias (float32: they sit inside an exp), the output norm over
        # a value head, the out-projection.
        lk = jax.random.split(jax.random.fold_in(key, LINEAR_KEY), 6)
        cd, vd, H = (cfg.linear_conv_dim, cfg.linear_value_dim,
                     cfg.linear_num_value_heads)
        K = cfg.linear_conv_kernel_dim
        dt = jnp.exp(jax.random.uniform(
            lk[4], (Ll, H), jnp.float32, *map(jnp.log, LINEAR_DT_RANGE)))
        layers.update(
            lin_in=w(lk[0], (Ll, d, cd + vd), d),
            lin_ba=w(lk[1], (Ll, d, 2 * H), d),
            lin_conv_w=w(lk[2], (Ll, cd, K), K),
            lin_A_log=jnp.log(jax.random.uniform(
                lk[3], (Ll, H), jnp.float32, *LINEAR_A_RANGE)),
            lin_dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
            lin_norm=jnp.ones((Ll, cfg.linear_value_head_dim), dtype),
            lin_out=w(lk[5], (Ll, vd, d), vd))
    if Ls:
        # State-space mixer (Mamba-2): in-projection to [z | x | B | C] and,
        # a stack of its own, to dt (the published matrix's last
        # `mamba_n_heads` columns: `_ssm_op` says why they are held apart),
        # the depthwise taps [x | B | C channels, K] and their bias, per
        # head the decay's A_log, the skip D and dt_bias (float32: they sit
        # inside an exp or beside the float32 state; the delta rule's init
        # ranges), the gated norm's weight over d_ssm, the out-projection.
        sk = jax.random.split(jax.random.fold_in(key, SSM_KEY), 8)
        di, cd, H = cfg.mamba_d_ssm, cfg.ssm_conv_dim, cfg.mamba_n_heads
        K = cfg.mamba_d_conv
        dt = jnp.exp(jax.random.uniform(
            sk[4], (Ls, H), jnp.float32, *map(jnp.log, LINEAR_DT_RANGE)))
        layers.update(
            ssm_in=w(sk[0], (Ls, d, cfg.ssm_in_dim - H), d,
                     cfg.ssm_in_multiplier * _ssm_segments(cfg, jnp.float32)),
            ssm_dt=w(jax.random.fold_in(sk[0], 1), (Ls, d, H), d,
                     cfg.ssm_in_multiplier * cfg.ssm_multipliers[4]),
            ssm_conv_w=w(sk[1], (Ls, cd, K), K),
            ssm_A_log=jnp.log(jax.random.uniform(
                sk[3], (Ls, H), jnp.float32, *LINEAR_A_RANGE)),
            ssm_D=1.0 + SSM_D_SD * jax.random.normal(sk[5], (Ls, H),
                                                     jnp.float32),
            ssm_dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
            ssm_norm=(1.0 + SSM_NORM_SD * jax.random.normal(
                sk[6], (Ls, di), jnp.float32)).astype(dtype),
            ssm_out=w(sk[7], (Ls, di, d), di, cfg.ssm_out_multiplier))
        if cfg.mamba_conv_bias:
            layers["ssm_conv_b"] = (SSM_CONV_BIAS_SD * jax.random.normal(
                sk[2], (Ls, cd), jnp.float32)).astype(dtype)
    if Ld:
        layers.update(
            w_gate=w(keys[4], (Ld, d, f), d, cfg.mlp_multipliers[0]),
            w_up=w(keys[5], (Ld, d, f), d),
            w_down=w(keys[6], (Ld, f, d), f, cfg.mlp_multipliers[1]))
    if cfg.count(EXPERTS):
        layers.update(init_moe_layer_params(cfg, keys[9], dtype))
    n = cfg.streams
    if n:
        ck = iter(jax.random.split(jax.random.fold_in(key, MHC_KEY), 9))

        def connection(lead, maps, lift):
            """(Phi, alpha, b) of `lead` connections of `maps` maps."""
            f32 = jnp.float32
            return (jax.random.normal(next(ck), lead + (maps, n * d), f32)
                    / jnp.sqrt(n * d),
                    1.0 + MHC_ALPHA_SD * jax.random.normal(
                        next(ck), lead + (3 if lift else 1,), f32),
                    MHC_BIAS_SD * jax.random.normal(
                        next(ck), lead + (maps,), f32) + (jnp.concatenate(
                            [jnp.zeros((2 * n,), f32), MHC_RES_LIFT
                             * jnp.eye(n, dtype=f32).reshape(-1)])
                            if lift else 0.0))

        for name in ("hc_attn", "hc_mlp"):
            layers.update(zip((f"{name}_phi", f"{name}_alpha", f"{name}_b"),
                              connection((L,), cfg.hc_maps, True)))
    params = {
        "embed": w(keys[7], (v, d), d, cfg.embedding_multiplier),
        "final_norm": norm_w((d,)),
        "layers": layers,
    }
    if n:  # the read-out before the head: H_pre's form, its own weights
        params.update(zip(("hc_head_phi", "hc_head_alpha", "hc_head_b"),
                          connection((), n, False)))
    if cfg.layer_norm_eps is not None:
        params["final_norm_b"] = bias((d,))
    if not cfg.tie_embeddings and not cfg.is_encoder:
        params["lm_head"] = w(keys[8], (v, d), d, cfg.lm_head_multiplier)
    if n_mtp:
        # u = [enorm(Emb(next token)) | hnorm(hidden)] W_eh; the module's
        # own norm before the (trunk's) head.
        params.update(
            mtp_enorm=jnp.ones((d,), dtype), mtp_hnorm=jnp.ones((d,), dtype),
            mtp_eh_proj=w(jax.random.fold_in(key, MTP_KEY), (2 * d, d),
                          2 * d),
            mtp_norm=jnp.ones((d,), dtype))
    return params


def _init_hybrid(cfg: ModelConfig, La: int, w, bias, key) -> dict:
    """What a decoder-hybrid-decoder stack holds beside the attention
    layers' four matrices (`w`, `bias`: init_params' draws): the attention
    layers' biases, lambda vectors and pair norm; the mamba layers' mixer
    (in to [x | z], the taps [channels, K] with their bias, x -> [delta | B
    | C], dt's projection and its bias — the inverse softplus of a step
    log-uniform in LINEAR_DT_RANGE —, A_log = log(1 .. N) along the states
    and D, float32: they sit inside an exp or beside the float32 state; the
    out-projection); the GMUs' two matrices; the cross layers' q and out
    projections with their own biases, lambdas and norm."""
    d, qd, kvd, hd = cfg.hidden_size, cfg.q_dim, cfg.kv_dim, cfg.head_dim
    di, R, N, K = cfg.s6_inner, cfg.s6_dt_rank, S6_D_STATE, S6_D_CONV
    Lm, Lg, Lx = cfg.count(MAMBA), cfg.count(GMU), cfg.count(CROSS)
    f32 = jnp.float32
    mk = iter(jax.random.split(key, 16))
    dt = jnp.exp(jax.random.uniform(
        next(mk), (Lm, di), f32, *map(jnp.log, LINEAR_DT_RANGE)))
    return dict(
        bq=bias((La, qd)), bk=bias((La, kvd)), bv=bias((La, kvd)),
        bo=bias((La, d)),
        diff_lambda=bias((La, 4, hd), HYBRID_LAMBDA_SD, as_dtype=f32),
        diff_norm=bias((La, 2 * hd), around=1.0),
        s6_in=w(next(mk), (Lm, d, 2 * di), d),
        s6_conv_w=w(next(mk), (Lm, di, K), K),
        s6_conv_b=bias((Lm, di)),
        s6_x=w(next(mk), (Lm, di, R + 2 * N), di),
        s6_dt=w(next(mk), (Lm, R, di), R),
        s6_dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
        s6_A_log=jnp.broadcast_to(jnp.log(jnp.arange(
            1, N + 1, dtype=f32))[None, :, None], (Lm, N, di)),
        s6_D=bias((Lm, di), SSM_D_SD, around=1.0, as_dtype=f32),
        s6_out=w(next(mk), (Lm, di, d), di),
        gmu_in=w(next(mk), (Lg, d, di), d),
        gmu_out=w(next(mk), (Lg, di, d), di),
        xwq=w(next(mk), (Lx, d, qd), d), xbq=bias((Lx, qd)),
        xwo=w(next(mk), (Lx, qd, d), qd), xbo=bias((Lx, d)),
        xdiff_lambda=bias((Lx, 4, hd), HYBRID_LAMBDA_SD, as_dtype=f32),
        xdiff_norm=bias((Lx, 2 * hd), around=1.0))


def _ssm_segments(cfg: ModelConfig, dtype):
    """`ssm_multipliers` along the lanes of `ssm_in`: one scalar on each of
    its four segments [z | x | B | C] (the published `mup_vector`, whose
    fifth scalar meets dt)."""
    gs = cfg.mamba_n_groups * cfg.mamba_d_state
    widths = (cfg.mamba_d_ssm, cfg.mamba_d_ssm, gs, gs)
    return jnp.concatenate([jnp.full((n,), m, dtype) for n, m in
                            zip(widths, cfg.ssm_multipliers)])


def _embed(params: dict, cfg: ModelConfig, tokens: jnp.ndarray):
    """The tokens' embeddings in the activation dtype, times the family's
    `embedding_multiplier` where it has one."""
    x = embed_lookup(params["embed"], tokens, _adtype(params))
    if cfg.embedding_multiplier != 1.0:
        x = x * cfg.embedding_multiplier
    if cfg.streams:  # laid on each of the residual streams: [..., n, D]
        x = jnp.broadcast_to(x[..., None, :],
                             x.shape[:-1] + (cfg.streams, x.shape[-1]))
    return x


def splits_heads_at_once(cfg: ModelConfig) -> bool:
    """Does `_qkv` reshape the projections' results to heads straight away —
    no q/k norm, or a per-head one — and not first norm the flat `[B, T, e]`
    vector (OLMoE's and Olmo-Hybrid's full-width norm)? What `_qkv` branches
    on, and what `weight_formats` holds `wq` / `wk` by: the chip's compiler
    reads a projection that is split into heads at once with the contracted
    dimension minor, one that is normed flat first row-major."""
    return cfg.qk_norm_kind != "full"


def _qkv(cfg: ModelConfig, lp: dict, h: jnp.ndarray, shape=None):
    """Project hidden -> q,k,v with head reshape. h: [B, T, D]. `shape`: the
    layer's kind's `AttnShape` where the kinds differ (`per_kind_attention`:
    its own kv heads, v narrower than k, v times `attention_value_scale`)."""
    B, T, _ = h.shape
    if shape is not None:
        q = qeinsum("btd,de->bte", h, lp["wq"]).reshape(
            B, T, shape.heads, shape.qk_dim)
        k = qeinsum("btd,de->bte", h, lp["wk"]).reshape(
            B, T, shape.kv_heads, shape.qk_dim)
        v = qeinsum("btd,de->bte", h, lp["wv"]).reshape(
            B, T, shape.kv_heads, shape.v_dim)
        if cfg.attention_value_scale is not None:
            with jax.named_scope("attn_vscale"):
                v = v * cfg.attention_value_scale
        return q, k, v
    q = qeinsum("btd,de->bte", h, lp["wq"])
    k = qeinsum("btd,de->bte", h, lp["wk"])
    v = qeinsum("btd,de->bte", h, lp["wv"])
    if cfg.key_multiplier != 1.0:
        k = k * cfg.key_multiplier
    if cfg.attn_bias:
        q = q + lp["bq"]
        k = k + lp["bk"]
        v = v + lp["bv"]
    if not splits_heads_at_once(cfg):
        # Over all heads' lanes at once, BEFORE the split into heads (under
        # tp the lanes are sharded: GSPMD reduces the mean across shards).
        q = _norm(cfg, q, lp["q_norm"])
        k = _norm(cfg, k, lp["k_norm"])
    q = q.reshape(B, T, cfg.num_heads, cfg.head_dim)
    k = k.reshape(B, T, cfg.num_kv_heads, cfg.head_dim)
    v = v.reshape(B, T, cfg.num_kv_heads, cfg.head_dim)
    if cfg.qk_norm_kind == "head":
        q = _norm(cfg, q, lp["q_norm"])
        k = _norm(cfg, k, lp["k_norm"])
    return q, k, v


def _mlp(lp: dict, h: jnp.ndarray, multipliers=(1.0, 1.0)) -> jnp.ndarray:
    """SwiGLU; `multipliers` (Falcon-H1's `mlp_multipliers`) scale the
    gate's pre-activation and the output."""
    gate = qeinsum("btd,df->btf", h, lp["w_gate"])
    up = qeinsum("btd,df->btf", h, lp["w_up"])
    if multipliers[0] != 1.0:
        gate = gate * multipliers[0]
    out = qeinsum("btf,fd->btd", jax.nn.silu(gate) * up, lp["w_down"])
    return out if multipliers[1] == 1.0 else out * multipliers[1]


def _ffn(cfg: ModelConfig, lp: dict, ffn: str, h: jnp.ndarray, valid=None,
         mesh=None, impl: str = "jnp", layer=None):
    """Dense SwiGLU or routed mixture-of-experts, by the layer's FFN kind;
    returns (delta, expert load [E] int32 — None for a dense layer).

    `valid` ([B, T] bool) marks real tokens, `mesh` is the caller's jit
    mesh and `impl` its `attn_impl`; only MoE routing consumes them
    (padding/inactive rows are routed to no expert; the grouped matmul
    runs under a shard_map, as a Pallas kernel or its XLA twin). `layer`:
    inside `scan_layers`, where `lp` holds the expert stacks whole — this
    layer's index among the layers that have experts.
    """
    if ffn == EXPERTS:
        return moe_mlp(cfg, lp, h, valid=valid, mesh=mesh, impl=impl,
                       layer=layer)
    return _mlp(lp, h, cfg.mlp_multipliers), None


def _stream_in(cfg: ModelConfig, lp: dict, name: str, x: jnp.ndarray,
               impl: str):
    """What a sublayer reads of the residual and what it writes back
    through: (x, None) on the one-stream path — nothing is traced —, else
    the streams x [B, T, n, D] under the sublayer's own mappings
    (`name`_phi / _alpha / _b: ops/hyper_connection.mix_in), as (h [B, T, D],
    maps [B T, .])."""
    if not cfg.streams:
        return x, None
    with jax.named_scope("mhc_mix_in"):
        h, maps = hyper_connection.mix_in(
            x.reshape((-1,) + x.shape[2:]), lp[name + "_phi"],
            lp[name + "_alpha"], lp[name + "_b"],
            hyper_connection.consts(cfg), impl)
    return h.reshape(x.shape[:2] + h.shape[1:]), maps


def _stream_out(cfg: ModelConfig, x: jnp.ndarray, delta: jnp.ndarray, maps,
                impl: str) -> jnp.ndarray:
    """The residual after a sublayer's result `delta`: `x + delta` — or the
    streams mixed through `_stream_in`'s maps plus H_post times delta."""
    if maps is None:
        return x + delta
    with jax.named_scope("mhc_mix_out"):
        out = hyper_connection.mix_out(
            x.reshape((-1,) + x.shape[2:]),
            delta.reshape((-1,) + delta.shape[2:]), maps,
            hyper_connection.consts(cfg), impl)
    return out.reshape(x.shape)


def _read_out(params: dict, cfg: ModelConfig, x: jnp.ndarray,
              impl: str = "jnp") -> jnp.ndarray:
    """The ONE vector a token the final norm reads: x itself — or, of
    streams [..., n, D], their learned mix (hyper_connection.read_out)."""
    if not cfg.streams:
        return x
    with jax.named_scope("mhc_read_out"):
        y = hyper_connection.read_out(
            x.reshape((-1,) + x.shape[-2:]), params["hc_head_phi"],
            params["hc_head_alpha"], params["hc_head_b"],
            hyper_connection.consts(cfg), impl)
    return y.reshape(x.shape[:-2] + y.shape[-1:])


@jax.named_scope("lm_head")
def _logits(params: dict, cfg: ModelConfig, x: jnp.ndarray,
            impl: str = "jnp") -> jnp.ndarray:
    x = _read_out(params, cfg, x, impl)
    x = _norm(cfg, x, params["final_norm"], params.get("final_norm_b"))
    head = params.get("lm_head", params["embed"])
    logits = logits_head(x, head)
    if cfg.lm_head_multiplier != 1.0:
        logits = logits * cfg.lm_head_multiplier
    return logits


_RECURRENT = ("rule", "ssm", "scan")


class SlotState(NamedTuple):
    """The per-slot state of a model whose layers keep one, as the forwards
    take it in `conv_state` and give it back — ONE record, a field a kind of
    state, None (a pytree leaf the less) where the layers keep none of that
    kind: `conv` the window of the layers' convolution (ops/shortconv.py's
    array: conv, linear-attention, mixer and mamba layers'); then the float32
    recurrent state, of which a model has ONE — `rule` the delta rule's
    matrices (ops/gated_delta.py's), `ssm` a state-space mixer's (PARALLEL;
    ops/ssd.py's), `scan` a selective scan's (ops/selective_scan.py's) — and
    `ring` the window layers' K/V rings (ops/attention.py:WindowRing) — and
    `pooled`, which is no slot's but the PAGES': the block-sparse layers'
    pooled keys (ops/block_select.py), rows under the K/V pool's page table;
    carried, donated and updated in place with the rest. (A lightning
    layer's matrices are `rule`'s: the linear kind's state.)"""
    conv: Optional[jnp.ndarray] = None
    rule: Optional[jnp.ndarray] = None
    ssm: Optional[jnp.ndarray] = None
    scan: Optional[jnp.ndarray] = None
    ring: Optional[WindowRing] = None
    pooled: Optional[jnp.ndarray] = None

    def loop_carry(self) -> tuple:
        """(conv window, recurrent state, rings, pooled keys), each may be
        None: the four places the layers' loop carries the state in,
        whichever recurrence a model runs (`scan_layers`)."""
        held = [getattr(self, f) for f in _RECURRENT
                if getattr(self, f) is not None]
        return self.conv, held[0] if held else None, self.ring, self.pooled

    def after(self, conv, recurrent, ring, pooled=None) -> "SlotState":
        """...and the loop's four results as a state of this one's form."""
        return self._replace(conv=conv, ring=ring, pooled=pooled, **{
            f: recurrent for f in _RECURRENT if getattr(self, f) is not None})


def slot_state(conv_state) -> SlotState:
    """A forward's `conv_state` as the record: a conv window's bare array is
    its `conv`, None the record with nothing in it."""
    if isinstance(conv_state, SlotState):
        return conv_state
    return SlotState(conv_state)


def alloc_slot_state(cfg: ModelConfig, max_slots: int, dtype=jnp.bfloat16,
                     ring_rows: int = 0, pooled_rows: int = 0):
    """What `conv_state` of the step forwards is for `cfg`, at zero; None
    for a model whose layers keep nothing a slot.
    `ring_rows`: rows of a slot's ring a window layer
    (`ModelConfig.ring_rows` of the longest span a step writes);
    `pooled_rows`: rows of a sparse layer's pooled-key pool
    (`ModelConfig.pooled_rows` of the page pool's size)."""
    window, width = cfg.state_window
    if cfg.count(SPARSE) and not pooled_rows:
        raise ValueError(
            f"{cfg.name}: a {SPARSE} layer's pooled keys need their pool: "
            "pooled_rows is 0 (ModelConfig.pooled_rows)")
    lightning = bool(cfg.lightning_nh)
    if cfg.count(WINDOW) and ring_rows < cfg.sliding_window:
        raise ValueError(
            f"{cfg.name}: a slot's ring holds the window at the least: "
            f"ring_rows {ring_rows}, sliding_window {cfg.sliding_window}")
    state = SlotState(
        shortconv.alloc_state(
            cfg.count(CONV) + (0 if lightning else cfg.count(LINEAR))
            + cfg.count(PARALLEL) + cfg.count(MAMBA), max_slots, window,
            width, dtype),
        gated_delta.alloc_state(
            cfg.count(LINEAR), max_slots,
            *((cfg.lightning_nh,) + (cfg.lightning_head_dim,) * 2
              if lightning else (cfg.linear_num_value_heads,
                                 cfg.linear_key_head_dim,
                                 cfg.linear_value_head_dim))),
        ssd.alloc_state(
            cfg.count(PARALLEL), max_slots, cfg.mamba_n_heads,
            cfg.mamba_d_state, cfg.mamba_d_head),
        selective_scan.alloc_state(
            cfg.count(MAMBA), max_slots, S6_D_STATE, cfg.s6_inner),
        alloc_ring(cfg.count(WINDOW), max_slots, ring_rows,
                   cfg.ring_row_dims, dtype),
        block_select.alloc_pooled(cfg.count(SPARSE), pooled_rows, cfg.kv_dim,
                                  dtype))
    return state if jax.tree_util.tree_leaves(state) else None


class LayerIx(NamedTuple):
    """Where a layer of the scan stands among the layers of its operator's
    kind (an attention layer's row of the KV pool, a conv, linear or window
    layer's of the per-slot state; a parallel layer's of BOTH — every layer
    of such a model is one, so the two rows have one index) and among those
    of its FFN's kind (an expert layer's block of the expert stacks). int32
    scalars, traced inside the scan."""
    op: jnp.ndarray
    ffn: jnp.ndarray
    layer: jnp.ndarray = None  # ...and among ALL layers: its depth


def scan_layers(cfg: ModelConfig, body, x, layers, *state, at_exit=None):
    """The ONE layer loop of every forward.

    `state` is the loop's CARRY beside `x` — the KV pool and the conv
    state for the forwards that touch them, nothing for the others:
    `body(x, lp, kinds, ix, *state) -> (x, *state, per_layer)` gets the
    layer's (operator, FFN) kinds and its `LayerIx`, writes with one
    scatter on the carried arrays (ops/quant.kv_write; the conv state's
    rows) and attends over `pool[ix.op]` by index, and the loop returns the
    buffers it was given — with the jit sites' donation, XLA updates them
    in place. A pool passed as a scan's xs and returned as its ys cannot
    alias: every pass would build a second pool and copy each layer out
    and back.

    One `lax.scan` a run of `cfg.layer_plan()`, over the run's repeats,
    with the period's layers unrolled in the body. No weight is a scan's
    xs: the stacks (by kind, `KIND_PARAMS`) stay whole and a layer reads
    its slice by index — the same dynamic slice a scan makes of its xs,
    but a run may start anywhere in a stack and stride through it. An MoE
    model's expert stacks (moe.STACKED) are not sliced at all: `lp` holds
    them whole, for `_ffn(..., layer=ix.ffn)` to read by index (a
    kernel's operand cannot be a fused slice).

    `per_layer` (small: an expert layer's load, else None) comes back as
    the last result, [expert layers, E] (None for a dense stack).

    `at_exit(x, *state) -> (x, *state)` (a stack with an `exit_layer`): what
    the caller does to the carry between the run that ends below that layer
    and the run that starts at it — the gather of the rows that go on.
    """
    of_kind = {name: kind for kind, names in KIND_PARAMS.items()
               for name in names}
    seen = dict.fromkeys((ATTENTION, CONV, LINEAR, WINDOW, PARALLEL, MAMBA,
                          GMU, CROSS, SPARSE, DENSE, EXPERTS), 0)
    # (where the two attention kinds' stacks are their own, a window layer
    # reads KIND_PARAMS[WINDOW] at its index among the window layers)
    windowed = cfg.count(WINDOW) > 0 and not cfg.per_kind_attention
    loads = []
    for first, period, repeats in cfg.layer_plan():
        per = {k: sum(k in pair for pair in period) for k in seen}
        base = dict(seen)
        if at_exit is not None and first == cfg.exit_layer:
            x, *state = at_exit(x, *state)

        def step(carry, r, first=first, period=period, per=per, base=base):
            x, *state = carry
            outs, n = [], dict.fromkeys(seen, 0)
            for j, (op, ffn) in enumerate(period):
                at = {None: first + r * len(period) + j,
                      op: base[op] + r * per[op] + n[op],
                      ffn: base[ffn] + r * per[ffn] + n[ffn]}
                # ...and among the weights' stacks: a window layer reads
                # the attention weights, which count both attention kinds.
                held = dict(at)
                if op in (PARALLEL, SPARSE):  # ...and a parallel layer both
                    held[ATTENTION] = at[op]  # kinds'; a sparse layer's
                    # (its stack's only attention kind) the attention's
                if windowed and op in ATTENTION_KINDS:
                    held[ATTENTION] = r * sum(
                        per[k] for k in ATTENTION_KINDS) + sum(
                            base[k] + n[k] for k in ATTENTION_KINDS)
                n[op] += 1
                n[ffn] += 1
                lp = {}
                for name, stack in layers.items():
                    kind = of_kind.get(name)
                    if name in STACKED:
                        lp[name] = stack
                    elif kind in held:
                        lp[name] = jax.tree_util.tree_map(
                            lambda w, i=held[kind]:
                            jax.lax.dynamic_index_in_dim(
                                w, i, 0, keepdims=False), stack)
                x, *state, out = body(
                    x, lp, (op, ffn),
                    LayerIx(at[op], at[ffn], at[None]), *state)
                outs.append(out)
            return (x, *state), outs

        (x, *state), outs = jax.lax.scan(
            step, (x, *state), jnp.arange(repeats, dtype=jnp.int32))
        loads += [o for o in outs if o is not None]
        for k in seen:
            seen[k] += repeats * per[k]
    load = None
    if loads:
        load = loads[0] if len(loads) == 1 else jnp.concatenate(loads)
    return (x, *state, load)


def _stored(x: jnp.ndarray) -> jnp.ndarray:
    """K or V heads [..., Hk, d] as `kv_write` takes them: as they are —
    it flattens them to the cache's row — or, a head the cache stores split
    (ops/attention.py:split_head), laid out as the row is."""
    return lay_heads(x) if split_head(x.shape[-1])[1] else x


def _attention_op(cfg: ModelConfig, lp: dict, h: jnp.ndarray,
                  positions: jnp.ndarray, attn_fn,
                  rotate: bool = True, kind: str = ATTENTION) -> jnp.ndarray:
    """Attention over normed hiddens h [B, T, D]: projections, q/k norm
    and RoPE (over a head's first `rotary_dim` lanes; `rotate`: the layer's
    kind takes it, `ModelConfig.rotates`) here; the schedule (the window or
    the whole context, and any write of k, v into a pool or a ring) is the
    caller's `attn_fn(q, k, v) -> [B, T, H, hd]`. With `attn_output_gate`
    the attended values are multiplied by sigmoid(h W_gate), a lane each,
    before `wo`."""
    B, T, _ = h.shape
    gate = None
    if cfg.per_kind_attention:
        # the layer's kind's own head shape, RoPE base and — a window
        # layer's — weights (`swa_*`) and sink
        shape = cfg.attn_shape(kind)
        if kind == WINDOW:
            lp = {**lp, **{name[4:]: lp[name]
                           for name in KIND_PARAMS[WINDOW] if name in lp}}
        with jax.named_scope("attn_qkv"):
            q, k, v = _qkv(cfg, lp, h, shape)
            q = apply_rope(q, positions, shape.theta, cfg.rotary_dim)
            k = apply_rope(k, positions, shape.theta, cfg.rotary_dim)
        attn = attn_fn(q, k, v, sink=lp["sink"]) if shape.sink \
            else attn_fn(q, k, v)
        with jax.named_scope("attn_out"):
            return qeinsum("bte,ed->btd",
                           attn.reshape(B, T, shape.o_lanes), lp["wo"])
    with jax.named_scope("attn_qkv"):
        q, k, v = _qkv(cfg, lp, h)
        if cfg.attn_output_gate:
            gate = qeinsum("btd,de->bte", h, lp["wq_gate"])
        if rotate:
            q = apply_rope(q, positions, cfg.rope_theta, cfg.rotary_dim)
            k = apply_rope(k, positions, cfg.rope_theta, cfg.rotary_dim)
    attn = attn_fn(q, k, v)
    with jax.named_scope("attn_out"):
        attn = attn.reshape(B, T, cfg.q_dim)
        if gate is not None:
            with jax.named_scope("attn_gate"):
                attn = (attn.astype(jnp.float32) * jax.nn.sigmoid(
                    gate.astype(jnp.float32))).astype(h.dtype)
        return qeinsum("bte,ed->btd", attn, lp["wo"])


def _latent_rope(cfg: ModelConfig, x: jnp.ndarray, positions) -> jnp.ndarray:
    """RoPE (rotate-half, YaRN frequencies where the config scales them)
    over the FIRST `qk_rope_head_dim` lanes of x [B, T, H, >= that] — or,
    `mla_use_nope`, x as it is: those lanes are carried unrotated."""
    if cfg.mla_use_nope:
        return x
    dr = cfg.qk_rope_head_dim
    yarn = cfg.yarn
    freqs = yarn_freqs(dr, cfg.rope_theta, yarn) if yarn \
        else rope_freqs(dr, cfg.rope_theta)
    rot = apply_rope_freqs(x[..., :dr], positions, freqs,
                           yarn_cos_scale(yarn) if yarn else 1.0)
    return rot if x.shape[-1] == dr else jnp.concatenate(
        [rot, x[..., dr:]], axis=-1)


def _latent_attention_op(cfg: ModelConfig, lp: dict, h: jnp.ndarray,
                         positions: jnp.ndarray, attn_fn,
                         few=None) -> jnp.ndarray:
    """Latent attention with the indexer's selection over normed hiddens h
    [B, T, D] (ops/mla.py has the mathematics): the projections (q through
    its low rank, or `q_lora_rank` 0 full-rank), norms and RoPE
    (`mla_use_nope`: none) here, in the ABSORBED form; the schedule (the
    write of the token's
    two cache rows, the indexer, the selection, the softmax) is the caller's
    `attn_fn(q_abs [B, T, H, lanes], row [B, T, lanes], (q_idx [B, T, Hi,
    di], k_idx [B, T, di], w_idx [B, T, Hi] float32)) -> [B, T, H, c]`, the
    attended latent a head; the third argument is None for a model with no
    indexer (`index_topk` 0). The schedule also gets the EXPANDED form's
    operands, `expanded=(q [B, T, H, dn + lanes - c]:
    [q_nope | q_rope | 0] scaled, w [H, dn + dv, c]: [W_uk,h | W_uv,h]^T a
    head)`, and may answer `(o_lat, o_v [B, T, H, dv], wide [B, T] bool)`:
    the tokens of `wide` have their result in `o_v`, through W_uv already
    (a prefill span wide enough to pay for expanding its context's keys and
    values: ops/pallas/mla_attention.py).
    `few` = (R, a scalar bool of the step's own spans), from a schedule
    whose launch answers so (ops/mla.absorbed_lead; else None): where it
    holds, every row of the stream from R on is a wide span's or padding —
    no row's result reads their absorbed q (the tiles skip a wide span's
    sequence) nor their `o_lat` — and the absorbed form's two contractions a
    row run over the first R rows alone: q through W_uk in one branch of a
    conditional, the rest of `q_abs` zeros born in the kernel's layout, and
    `o_lat` through W_uv R rows a trip of a loop that updates `o_v` in
    place, ONE trip; where it does not hold, over the rung (the other
    branch; T / R trips). The same operands and sums a row either way."""
    B, T, _ = h.shape
    H, c = cfg.num_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    Hi, di = cfg.index_n_heads, cfg.index_head_dim
    wukv = lp["mla_wukv"].reshape(c, H, dn + dv)
    index = None  # no indexer: every cached position is attended
    o_v = None
    pad = cfg.latent_lanes - cfg.latent_dim
    lead, few = few or (T, None)

    def absorbed(q, q_rope):
        # q_nope W_uk^T meets c_kv itself; the softmax scale (and YaRN's
        # mscale^2) rides q.
        q_lat = jnp.einsum("bthn,chn->bthc", q[..., :dn], wukv[..., :dn],
                           preferred_element_type=jnp.float32)
        return (jnp.concatenate(
            [q_lat, q_rope.astype(jnp.float32),
             jnp.zeros(q_lat.shape[:3] + (pad,), jnp.float32)], axis=-1)
            * cfg.attn_scale).astype(h.dtype)

    def through_w_uv(o_lat):
        return jnp.einsum("bthc,chv->bthv", o_lat, wukv[..., dn:],
                          preferred_element_type=jnp.float32).astype(h.dtype)

    with jax.named_scope("mla_proj"):
        if cfg.q_lora_rank:
            c_q = rmsnorm(qeinsum("btd,de->bte", h, lp["mla_wdq"]),
                          lp["mla_q_norm"], cfg.rms_norm_eps)
            q = qeinsum("btr,re->bte", c_q, lp["mla_wuq"])
        else:  # a full-rank q (and no indexer to read the q latent)
            q = qeinsum("btd,de->bte", h, lp["wq"])
        q = q.reshape(B, T, H, dn + dr)
        q_rope = _latent_rope(cfg, q[..., dn:], positions)
        kv = qeinsum("btd,de->bte", h, lp["mla_wdkv"])
        c_kv = rmsnorm(kv[..., :c], lp["mla_kv_norm"], cfg.rms_norm_eps)
        k_rope = _latent_rope(cfg, kv[..., None, c:], positions)[..., 0, :]
        row = jnp.concatenate(
            [c_kv, k_rope, jnp.zeros((B, T, pad), c_kv.dtype)], axis=-1)
        if few is None:
            q_abs = absorbed(q, q_rope)
        else:
            # The lead's rows outside the conditional, whose branches then
            # hold no weight (the compiler re-lays one it meets as a
            # branch's operand), and both results as the kernel's tiles read
            # them, [rows of (token, head), lanes]: a branch's result is
            # born in that order, and no copy follows the conditional.
            q_abs = jax.lax.cond(
                few,
                lambda first: jnp.pad(
                    first.reshape(B, lead * H, -1),
                    ((0, 0), (0, (T - lead) * H), (0, 0))),
                lambda _: absorbed(q, q_rope).reshape(B, T * H, -1),
                absorbed(q[:, :lead], q_rope[:, :lead])
            ).reshape(B, T, H, -1)
        if cfg.index_topk:
            index = _index_inputs(cfg, lp, h, c_q, positions)
        expanded = ((jnp.concatenate(
            [q[..., :dn].astype(jnp.float32), q_rope.astype(jnp.float32),
             jnp.zeros((B, T, H, pad), jnp.float32)], axis=-1)
            * cfg.attn_scale).astype(h.dtype),
            jnp.transpose(wukv, (1, 2, 0)))
    o_lat = attn_fn(q_abs, row, index, expanded)
    if isinstance(o_lat, tuple):
        o_lat, o_v, wide = o_lat
    assert few is None or o_v is not None, "the launch expands, or `few` lies"
    with jax.named_scope("attn_out"):
        if o_v is None:
            o = through_w_uv(o_lat)
        elif few is None:
            o = jnp.where(wide[..., None, None], o_v, through_w_uv(o_lat))
        else:
            def o_tile(i, o):
                rows = [jax.lax.dynamic_slice_in_dim(a, i * lead, lead, 1)
                        for a in (wide, o, o_lat)]
                return jax.lax.dynamic_update_slice_in_dim(o, jnp.where(
                    rows[0][..., None, None], rows[1],
                    through_w_uv(rows[2])), i * lead, 1)

            # (Not a second conditional: a branch that assembles `o` from
            # `o_v` copies it three times over, a loop's carry is updated in
            # place; PERF.md section 6, PR 55.)
            o = jax.lax.fori_loop(0, jnp.where(few, 1, T // lead), o_tile,
                                  o_v)
        return qeinsum("bte,ed->btd", o.reshape(B, T, H * dv), lp["wo"])


# Which stacks live on ONE device layer-major with the CONTRACTED dimension
# minor — the order the chip's compiler reads them in — and not in the default
# row-major order, from which every step program re-laid them a pass.
#
# The stacks `_latent_attention_op` contracts a head at a time ("btr,re->bte"
# reshaped to heads, "bthn,chn->bthc", "bthc,chv->bthv": `h` a batch
# dimension, the rank r / c contracted): 654 MB a pass at the published
# widths, 2.0 ms of a 15 ms pass (PERF.md section 6, PR 45).
CONTRACTED_MINOR = {"mla_wuq": (0, 2, 1), "mla_wukv": (0, 2, 1)}
# `_qkv`'s q and k projections, WHERE their results are split into heads at
# once (`splits_heads_at_once`): the compiler computes q heads-major with a
# layer of `wq` as the `[e, d]` operand, and held row-major K-EXAONE's ragged
# step re-laid each layer's 100 MB of it after slicing it out (0.88 ms of a
# 17.1 ms step) and its decode scan the 503 MB stack (PERF.md section 6,
# PR 51). Not where the flat result is normed first: OLMoE's and Olmo-Hybrid's
# compiler reads `wq` ROW-major, and held rank-minor their decode scans re-lay
# it. It is the norm, not the head counts: OLMoE's file with `qk_norm` "head"
# re-lays `wq` and `wk` from row-major, with 4 K/V heads and its own
# full-width norm nothing (AOT, ISSUE 51). `wv`, `wo`, `wq_gate` and the MLP
# stacks are read as they lie.
SPLIT_TO_HEADS_MINOR = {"wq": (0, 2, 1), "wk": (0, 2, 1)}
# ...and the window layers' own q and k stacks where the attention kinds
# differ in head shape (`per_kind_attention`: split into heads at once, no
# norm): held row-major, MiMo-V2-Flash's ragged step re-laid a window layer's
# 100 MB of `swa_wq` and 12.6 MB of `swa_wk` a layer a step (AOT for a v5e,
# PR 65: scripts/step_hlo_copies.py on the configuration's file).
PER_KIND_MINOR = {"swa_wq": (0, 2, 1), "swa_wk": (0, 2, 1)}
# A decoder-hybrid-decoder stack's: the q projections of its attention AND
# its cross layers, which `pair_queries` splits into heads at once — and NOT
# `wk`, whose result is read as pairs of heads, a cache row's lanes as they
# lie: its decode scan reads `wk` row-major and re-laid the 59 MB stack a
# launch from rank-minor, as it re-laid `xwq`'s 92 MB from row-major (AOT for
# a v5e, PR 56: scripts/step_hlo_copies.py on the configuration's file).
HYBRID_MINOR = {"wq": (0, 2, 1), "xwq": (0, 2, 1)}
# A lightning layer's q and k projections, whose results are split into
# heads at once (the per-head norm): held row-major, both step programs of
# MiniCPM-SALA's file re-laid each 403 MB stack a launch (AOT for a v5e,
# PR 60: scripts/step_hlo_copies.py). `ltn_wv` is read as it lies by the
# ragged step (the decode scan re-lays it once a launch of eight passes).
LIGHTNING_MINOR = {"ltn_wq": (0, 2, 1), "ltn_wk": (0, 2, 1)}


def weight_formats(cfg: ModelConfig, params: dict) -> dict:
    """{name: jax.experimental.layout.Format} of the entries of
    `params["layers"]` that are held on the device in another order than the
    default: the device LAYOUT of a leaf, never its name, logical shape or
    values. `params` holds arrays or `jax.ShapeDtypeStruct`s (a struct
    without a sharding lands on the default device). Only a plain array on
    ONE device is re-laid: under a mesh a leaf keeps the layout its sharding
    rule was measured with."""
    from jax.experimental.layout import Format, Layout
    from jax.sharding import SingleDeviceSharding

    named = dict(CONTRACTED_MINOR)
    if cfg.mb_per_layer:
        named.update(HYBRID_MINOR)
    elif splits_heads_at_once(cfg):
        named.update(SPLIT_TO_HEADS_MINOR)
    if cfg.per_kind_attention:
        named.update(PER_KIND_MINOR)
    if cfg.lightning_nh:
        named.update(LIGHTNING_MINOR)
    out = {}
    for name, order in named.items():
        leaf = params["layers"].get(name)
        if not isinstance(leaf, (jax.Array, jax.ShapeDtypeStruct)):
            continue  # absent, or a QuantTensor
        sharding = leaf.sharding
        if sharding is None:
            sharding = SingleDeviceSharding(jax.local_devices()[0])
        if len(sharding.device_set) == 1:
            out[name] = Format(Layout(major_to_minor=order), sharding)
    return out


def _index_inputs(cfg: ModelConfig, lp: dict, h, c_q, positions):
    """The lightning indexer's (q_idx, k_idx, w_idx): q from the normed q
    latent, k from the hiddens through a LayerNorm, RoPE on the first rope
    lanes of both, and a learned weight a head."""
    B, T, _ = h.shape
    Hi, di = cfg.index_n_heads, cfg.index_head_dim
    q_idx = _latent_rope(cfg, qeinsum(
        "btr,re->bte", c_q, lp["idx_wq"]).reshape(B, T, Hi, di), positions)
    k_idx = qeinsum("btd,de->bte", h, lp["idx_wk"]).astype(jnp.float32)
    mean = jnp.mean(k_idx, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(k_idx - mean), axis=-1, keepdims=True)
    k_idx = ((k_idx - mean) * jax.lax.rsqrt(var + INDEX_NORM_EPS)
             ).astype(h.dtype) * lp["idx_k_norm"] + lp["idx_k_bias"]
    k_idx = _latent_rope(cfg, k_idx[..., None, :], positions)[..., 0, :]
    w_idx = jnp.einsum("btd,dh->bth", h, lp["idx_ww"],
                       preferred_element_type=jnp.float32) \
        * (Hi ** -0.5 * di ** -0.5)
    return q_idx, k_idx, w_idx


def _conv_op(cfg: ModelConfig, lp: dict, h: jnp.ndarray,
             taps_fn) -> jnp.ndarray:
    """Gated short convolution over normed hiddens h [B, T, D]:
    [B | C | u] = h W_in, z = B * u, c = the depthwise causal convolution
    of z (ops/shortconv.short_conv), y = (C * c) W_out. Where each token's
    predecessors come from (and any write of the state) is the caller's
    `taps_fn(z) -> the K-1 predecessors of z, oldest first`."""
    with jax.named_scope("conv_in"):
        gate_b, gate_c, u = jnp.split(
            qeinsum("btd,de->bte", h, lp["conv_in"]), 3, axis=-1)
        z = gate_b * u
    with jax.named_scope("conv_mix"):
        c = shortconv.short_conv(lp["conv_w"], taps_fn(z), z)
    with jax.named_scope("conv_out"):
        return qeinsum("btd,de->bte", gate_c * c, lp["conv_out"])


def _linear_attention_op(cfg: ModelConfig, lp: dict, h: jnp.ndarray,
                         taps_fn, rule_fn) -> jnp.ndarray:
    """Gated delta-rule linear attention over hiddens h [B, T, D]:
    [q | k | v | z] = h W_in and the gates b, a = h W_ba (float32); q | k |
    v through the depthwise causal convolution and a SiLU; per head the
    rule (ops/gated_delta.py: q, k L2-normalised there and, where the key
    heads are fewer, repeated a value head; the decay from a, the write
    strength from b); y = (RMSNorm(o) * silu(z)) W_out. Where the
    convolution's predecessors come from is the caller's `taps_fn` (as a
    conv layer's), which state the rule continues its `rule_fn(q [B, T,
    Hk, dk], k, v [B, T, H, dv], g, beta) -> o [B, T, H, dv] float32`."""
    B, T, _ = h.shape
    Hk, H, dk, dv = (cfg.linear_num_key_heads, cfg.linear_num_value_heads,
                     cfg.linear_key_head_dim, cfg.linear_value_head_dim)
    kd, cd = cfg.linear_key_dim, cfg.linear_conv_dim
    with jax.named_scope("lin_in"):
        u = qeinsum("btd,de->bte", h, lp["lin_in"])
        qkv, z = u[..., :cd], u[..., cd:]
        ba = jnp.einsum("btd,de->bte", h, lp["lin_ba"],
                        preferred_element_type=jnp.float32)
        g, beta = gated_delta.gates(
            ba[..., H:], ba[..., :H], lp["lin_A_log"], lp["lin_dt_bias"],
            cfg.linear_allow_neg_eigval)
    with jax.named_scope("lin_conv"):
        c = jax.nn.silu(shortconv.short_conv(lp["lin_conv_w"], taps_fn(qkv),
                                             qkv))
    with jax.named_scope("lin_rule"):
        o = rule_fn(c[..., :kd].reshape(B, T, Hk, dk),
                    c[..., kd:2 * kd].reshape(B, T, Hk, dk),
                    c[..., 2 * kd:].reshape(B, T, H, dv), g, beta)
    with jax.named_scope("lin_out"):
        o = rmsnorm(o, lp["lin_norm"], cfg.rms_norm_eps).reshape(B, T, H * dv)
        return qeinsum("bte,ed->btd", (o * jax.nn.silu(z)).astype(h.dtype),
                       lp["lin_out"])


def _kda_op(cfg: ModelConfig, lp: dict, h: jnp.ndarray, taps_fn,
            rule_fn) -> jnp.ndarray:
    """Kimi Delta Attention over normed hiddens h [B, T, D] — the linear kind
    where `ModelConfig.kda`: [q | k | v] = h W_in, each through its depthwise
    causal convolution and a SiLU; the decay a KEY CHANNEL, g = -exp(A_log[h])
    softplus(h W_fa W_fb + dt_bias) in R^{H x dk}, and b = sigmoid(h W_b),
    float32; per head the rule (ops/gated_delta.py at its vector reading: q,
    k L2-normalised there); y = (RMSNorm_head(o; w) * sigmoid(h W_ga W_gb))
    W_out. `taps_fn`, `rule_fn`: as `_linear_attention_op`'s, g [B, T, H,
    dk]."""
    B, T, _ = h.shape
    H, dk, dv = (cfg.linear_num_value_heads, cfg.linear_key_head_dim,
                 cfg.linear_value_head_dim)
    kd = cfg.linear_key_dim
    f32 = jnp.float32
    with jax.named_scope("lin_in"):
        qkv = qeinsum("btd,de->bte", h, lp["lin_in"])
        with jax.named_scope("kda_gates"):
            a = jnp.einsum("btr,re->bte",
                           qeinsum("btd,dr->btr", h, lp["kda_fa"]),
                           lp["kda_fb"], preferred_element_type=f32)
            g = -jnp.exp(lp["lin_A_log"].astype(f32))[:, None] \
                * jax.nn.softplus(a.reshape(B, T, H, dk) + lp[
                    "lin_dt_bias"].astype(f32).reshape(H, dk))
            beta = jax.nn.sigmoid(jnp.einsum(
                "btd,dh->bth", h, lp["kda_b"], preferred_element_type=f32))
            gate = jnp.einsum("btr,re->bte",
                              qeinsum("btd,dr->btr", h, lp["kda_ga"]),
                              lp["kda_gb"], preferred_element_type=f32)
    with jax.named_scope("lin_conv"):
        c = jax.nn.silu(shortconv.short_conv(lp["lin_conv_w"], taps_fn(qkv),
                                             qkv))
    with jax.named_scope("lin_rule"):
        o = rule_fn(c[..., :kd].reshape(B, T, H, dk),
                    c[..., kd:2 * kd].reshape(B, T, H, dk),
                    c[..., 2 * kd:].reshape(B, T, H, dv), g, beta)
    with jax.named_scope("lin_out"):
        o = rmsnorm(o, lp["lin_norm"], cfg.rms_norm_eps).reshape(B, T, H * dv)
        return qeinsum("bte,ed->btd",
                       (o * jax.nn.sigmoid(gate)).astype(h.dtype),
                       lp["lin_out"])


def _lightning_op(cfg: ModelConfig, lp: dict, h: jnp.ndarray, positions,
                  ssm_fn, depth) -> jnp.ndarray:
    """Lightning linear attention over normed hiddens h [B, T, D]: q, k, v =
    h W; q and k RMS-normed a head and (`lightning_use_rope`) roped over the
    whole head; per head S_t = lambda S_{t-1} + k_t v_t^T, o_t = S_t^T q_t
    d^-1/2 — ops/ssd.py's recurrence (k for its B, q for its C, a write
    strength of 1), its log decay the CONSTANT -slope of (head, published
    layer index: `ModelConfig.lightning_level` of `depth`, this layer's
    index in the stack, traced), broadcast over the tokens: no
    projection computes it; y = (RMSNorm(o) over all heads' lanes *
    sigmoid(h W_z)) W_o. Which state the recurrence continues is the
    caller's `ssm_fn(q [B, T, H, d], k, v, g [B, T, H]) -> o float32`."""
    B, T, _ = h.shape
    H, d = cfg.lightning_nh, cfg.lightning_head_dim
    f32 = jnp.float32
    with jax.named_scope("ltn_in"):
        q, k, v = (qeinsum("btd,de->bte", h, lp[name]).reshape(B, T, H, d)
                   for name in ("ltn_wq", "ltn_wk", "ltn_wv"))
        z = qeinsum("btd,de->bte", h, lp["ltn_wz"])
        q = _norm(cfg, q, lp["ltn_q_norm"])
        k = _norm(cfg, k, lp["ltn_k_norm"])
        if cfg.lightning_use_rope:
            q = apply_rope(q, positions, cfg.rope_theta, d)
            k = apply_rope(k, positions, cfg.rope_theta, d)
        base = 2.0 ** (-8.0 * jnp.arange(1, H + 1, dtype=f32) / H)
        g = jnp.broadcast_to(-base * cfg.lightning_level(depth), (B, T, H))
    with jax.named_scope("ltn_rule"):
        o = ssm_fn(q.astype(f32) * d ** -0.5, k, v, g)
    with jax.named_scope("ltn_out"):
        o = rmsnorm(o.reshape(B, T, H * d), lp["ltn_norm"], cfg.rms_norm_eps)
        o = o.astype(f32) * jax.nn.sigmoid(z.astype(f32))
        return qeinsum("bte,ed->btd", o.astype(h.dtype), lp["ltn_wo"])


def _gated_group_norm(y, z, w, groups: int, eps: float):
    """The mixer's output norm (`mamba_rms_norm` with `mamba_norm_before_gate`
    false): the gate FIRST, g = y * silu(z) in float32, then an RMSNorm over
    each of the `groups` groups of channels, in z's dtype times the weight."""
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    g = g.reshape(*g.shape[:-1], groups, -1)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    return g.reshape(y.shape).astype(z.dtype) * w


def _ssm_op(cfg: ModelConfig, lp: dict, h: jnp.ndarray, taps_fn,
            ssm_fn) -> jnp.ndarray:
    """Mamba-2's mixer over normed hiddens h [B, T, D] (Falcon-H1's, with
    its multipliers where the published code applies them): [z | x | B | C |
    dt] = ((h ssm_in_multiplier) W_in) * mu, mu one of `ssm_multipliers` a
    segment — W_in held as TWO stacks, `ssm_in` [D, z | x | B | C] and
    `ssm_dt` [D, heads]: the whole matrix's 9248 lanes are no whole number
    of 128-lane tiles, for such a shape the chip's default order is
    contracted-minor, and the decode scan, which reads it row-major, re-laid
    all 568 MB of the stack a launch (AOT for a v5e, PR 54); 9216 lanes are
    72 tiles, and dt — the step of a float32 recurrence — gets a float32
    result as the delta rule's gates do; x | B | C through the depthwise
    causal convolution (with its bias) and a SiLU; the recurrence a head
    (ops/ssd.py; B and C a group) plus the skip D x, float32; g = y * silu(z) through an RMSNorm over each
    GROUP's channels (gate before norm), times the norm's weight; W_out.
    Where the convolution's predecessors come from is the caller's
    `taps_fn` (as a conv layer's), which state the recurrence continues its
    `ssm_fn(C [B, T, G, ds], B, v [B, T, H, dh], g [B, T, H]) -> y [B, T, H,
    dh] float32`."""
    B, T, _ = h.shape
    H, dh, G, ds = (cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_n_groups,
                    cfg.mamba_d_state)
    di, cd = cfg.mamba_d_ssm, cfg.ssm_conv_dim
    f32 = jnp.float32
    with jax.named_scope("ssm_in"):
        if cfg.ssm_in_multiplier != 1.0:
            h = h * cfg.ssm_in_multiplier
        p = qeinsum("btd,de->bte", h, lp["ssm_in"])
        dt = jnp.einsum("btd,de->bte", h, lp["ssm_dt"],
                        preferred_element_type=f32)
        if cfg.ssm_multipliers != (1.0,) * 5:
            p = p * _ssm_segments(cfg, p.dtype)
            dt = dt * cfg.ssm_multipliers[4]
        z, xbc = p[..., :di], p[..., di:]
    with jax.named_scope("ssm_conv"):
        xbc = jax.nn.silu(shortconv.short_conv(
            lp["ssm_conv_w"], taps_fn(xbc), xbc, lp.get("ssm_conv_b")))
    with jax.named_scope("ssm_scan"):
        x = xbc[..., :di].reshape(B, T, H, dh)
        v, g = ssd.inputs(x, dt, lp["ssm_A_log"], lp["ssm_dt_bias"])
        y = ssm_fn(xbc[..., di + G * ds:].reshape(B, T, G, ds),
                   xbc[..., di:di + G * ds].reshape(B, T, G, ds), v, g)
        y = y + lp["ssm_D"].astype(f32)[:, None] * x.astype(f32)
    with jax.named_scope("ssm_out"):
        y = _gated_group_norm(y.reshape(B, T, di), z, lp["ssm_norm"], G,
                              cfg.rms_norm_eps)
        return qeinsum("bte,ed->btd", y.astype(h.dtype), lp["ssm_out"])


def _parallel_op(cfg: ModelConfig, lp: dict, h: jnp.ndarray, positions,
                 attn_fn, taps_fn, ssm_fn) -> jnp.ndarray:
    """A parallel layer's mixers over ONE normed h: Attn(h m_in) m_out +
    SSM(h) m_ssm, the scalars the family's published multipliers."""
    u = h if cfg.attention_in_multiplier == 1.0 \
        else h * cfg.attention_in_multiplier
    attn = _attention_op(cfg, lp, u, positions, attn_fn,
                         rotate=cfg.rotates(PARALLEL))
    mix = _ssm_op(cfg, lp, h, taps_fn, ssm_fn)
    return attn * cfg.attention_out_multiplier \
        + mix * cfg.ssm_out_multiplier


def _mamba_op(cfg: ModelConfig, lp: dict, h: jnp.ndarray, taps_fn, s6_fn):
    """Mamba-1's mixer over normed hiddens h [B, T, D] (ops/selective_scan.py
    has the recurrence): [x | z] = h W_in; x through the depthwise causal
    convolution (with its bias) and a SiLU; [delta | B | C] = x W_x and dt =
    softplus(delta W_dt + b_dt), float32 — the step of a float32 recurrence,
    as the other mixers' gates; the scan a channel plus the skip D x; out =
    (y * silu(z)) W_out. Where the convolution's predecessors come from is
    the caller's `taps_fn` (as a conv layer's), which state the scan
    continues its `s6_fn(dt [B, T, channels], x, B [B, T, states], C, A
    [states, channels]) -> y [B, T, channels] float32`. Returns (out, y + D
    x: the scan output BEFORE the gate, which the stack's last mamba layer
    hands on to the gated memory units, in h's dtype)."""
    di, R, N = cfg.s6_inner, cfg.s6_dt_rank, S6_D_STATE
    f32 = jnp.float32
    with jax.named_scope("mamba_in"):
        xz = qeinsum("btd,de->bte", h, lp["s6_in"])
        x, z = xz[..., :di], xz[..., di:]
        x = jax.nn.silu(shortconv.short_conv(
            lp["s6_conv_w"], taps_fn(x), x, lp["s6_conv_b"]))
        dbc = jnp.einsum("bte,er->btr", x, lp["s6_x"],
                         preferred_element_type=f32)
        dt = jax.nn.softplus(
            jnp.einsum("btr,re->bte", dbc[..., :R].astype(h.dtype),
                       lp["s6_dt"], preferred_element_type=f32)
            + lp["s6_dt_bias"].astype(f32))
    with jax.named_scope("mamba_scan"):
        y = s6_fn(dt, x, dbc[..., R:R + N], dbc[..., R + N:],
                  -jnp.exp(lp["s6_A_log"].astype(f32)))
        y = y + lp["s6_D"].astype(f32) * x.astype(f32)
    with jax.named_scope("mamba_out"):
        out = qeinsum("bte,ed->btd", (y * jax.nn.silu(
            z.astype(f32))).astype(h.dtype), lp["s6_out"])
    return out, y.astype(h.dtype)


@jax.named_scope("gmu")
def _gmu_op(lp: dict, h: jnp.ndarray, m: jnp.ndarray) -> jnp.ndarray:
    """A gated memory unit: (m * silu(h W1)) W2, m the scan output the last
    mamba layer handed on for the same token. No state."""
    gate = jax.nn.silu(qeinsum("btd,de->bte", h, lp["gmu_in"]))
    return qeinsum("bte,ed->btd", m * gate, lp["gmu_out"])


def pair_queries(q: jnp.ndarray) -> jnp.ndarray:
    """Differential attention's queries as the GQA kernels take them: q [...,
    H, hd], heads in adjacent pairs (q1, q2), to [..., H, 2 hd] — head 2P
    [q1 sqrt(2) | 0], head 2P + 1 [0 | q2 sqrt(2)]. Against a K row read as
    PAIRS of heads, [k1 | k2] of 2 hd lanes, q~1 . [k1 | k2] = sqrt(2) q1 .
    k1, which the kernels' own scale (2 hd)^-1/2 makes q1 . k1 hd^-1/2; and
    the value they return is softmax(.) [v1 | v2]: a1, a2 of the published
    form, from the plain kernel at H / (Hk / 2) heads of 2 hd. The zero half
    costs a second half of q . k and no cache byte."""
    *lead, H, hd = q.shape
    q = q.reshape(*lead, H // 2, 2, hd) * jnp.asarray(2.0 ** 0.5, q.dtype)
    zero = jnp.zeros_like(q[..., 0, :])
    return jnp.stack(
        [jnp.concatenate([q[..., 0, :], zero], axis=-1),
         jnp.concatenate([zero, q[..., 1, :]], axis=-1)],
        axis=-2).reshape(*lead, H, 2 * hd)


def lambda_init(depth) -> jnp.ndarray:
    """Differential attention's constant of the layer at `depth`."""
    return 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(depth, jnp.float32))


@jax.named_scope("diff_combine")
def _diff_combine(attn: jnp.ndarray, lam: jnp.ndarray, w: jnp.ndarray,
                  depth, eps: float) -> jnp.ndarray:
    """attn [B, T, H, 2 hd] (head 2P: a1, head 2P + 1: a2 of pair P) -> the
    pairs' outputs [B, T, H / 2, 2 hd]: RMSNorm(a1 - lambda a2; w, eps) (1 -
    lambda_init), lambda = exp(l_q1 . l_k1) - exp(l_q2 . l_k2) + lambda_init
    from the layer's four vectors `lam` [4, hd] and its depth. float32."""
    B, T, H, lanes = attn.shape
    a = attn.astype(jnp.float32).reshape(B, T, H // 2, 2, lanes)
    first = lambda_init(depth)
    lam = lam.astype(jnp.float32)
    full = jnp.exp(jnp.sum(lam[0] * lam[1])) \
        - jnp.exp(jnp.sum(lam[2] * lam[3])) + first
    d = a[..., 0, :] - full * a[..., 1, :]
    d = d * jax.lax.rsqrt(jnp.mean(d * d, axis=-1, keepdims=True) + eps)
    return (d * w.astype(jnp.float32) * (1.0 - first)).astype(attn.dtype)


def _diff_attention_op(cfg: ModelConfig, lp: dict, h: jnp.ndarray, attn_fn,
                       depth, cross: bool = False) -> jnp.ndarray:
    """Differential attention over normed hiddens h [B, T, D] — every
    attention layer of a decoder-hybrid-decoder stack: biased projections,
    no position encoding, heads in adjacent pairs (`pair_queries`,
    `_diff_combine`), a biased out-projection. A window or full layer hands
    the schedule q [B, T, H, 2 hd] and its k, v as PAIRS of heads [B, T, Hk
    / 2, 2 hd]: the cache row's lanes as they lie. A `cross` layer has a
    query and an out projection only (its own stacks, "x" before the name)
    and hands k = v = None: the schedule attends over what the stack's full
    layer cached. `attn_fn(q, k, v) -> [B, T, H, 2 hd]`."""
    B, T, _ = h.shape
    H, Hk, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    x = "x" if cross else ""
    with jax.named_scope("attn_qkv"):
        if cross:
            q = (qeinsum("btd,de->bte", h, lp["xwq"]) + lp["xbq"]).reshape(
                B, T, H, hd)
            k = v = None
        else:
            q, k, v = _qkv(cfg, lp, h)
            k, v = (a.reshape(B, T, Hk // 2, 2 * hd) for a in (k, v))
        q = pair_queries(q)
    attn = attn_fn(q, k, v)
    o = _diff_combine(attn, lp[x + "diff_lambda"], lp[x + "diff_norm"],
                      depth, cfg.layer_norm_eps)
    with jax.named_scope("attn_out"):
        return qeinsum("bte,ed->btd", o.reshape(B, T, cfg.q_dim),
                       lp[x + "wo"]) + lp[x + "bo"]


class _Memo:
    """What one layer hands on past the residual to later layers, as a layer
    loop's body carries it: `held` is the carry's entries (none for a stack
    that hands nothing on), the call `_layer_step`'s `memo_fn`."""

    def __init__(self, held: tuple):
        self.held = held

    def __call__(self, new=None):
        if new is not None:
            self.held = (new,)
        return self.held[0]


def _layer_step(cfg: ModelConfig, lp: dict, kinds: Tuple[str, str],
                x: jnp.ndarray, positions: jnp.ndarray, attn_fn,
                taps_fn=None, valid=None, mesh=None, impl: str = "jnp",
                layer=None, rule_fn=None, ssm_fn=None, few=None, s6_fn=None,
                memo_fn=None, depth=None):
    """One layer over [B, T, D] hiddens (the streams [B, T, n, D] of a
    residual path of several: `_stream_in` / `_stream_out` around each
    sublayer, the plain sum otherwise): the SINGLE definition of the
    layer math for every forward — full sequences, the ragged stream
    ([1, T, D]) and the decode batch ([B, 1, D]). Only the operator's
    schedule differs, injected as `attn_fn(q, k, v) -> [B, T, H, hd]`
    (attention layers), `taps_fn(z) -> predecessors` (conv and linear
    layers) and `rule_fn` (linear layers); a forward over a pool or a
    state writes it inside them; `few`: what a latent layer's schedule
    says of the step's rows (`_latent_attention_op`); a decoder-hybrid-
    decoder stack's layers take `s6_fn` (mamba layers), `depth` (the
    layer's index in the stack: differential attention's constant) and
    `memo_fn`: called with the scan output a mamba layer hands on, and with
    nothing by a gated memory unit, which gets the last one back. Returns
    (x', expert load — None for a dense FFN)."""
    op, ffn = kinds
    pre = cfg.norm_order == "pre"  # else the norms weigh the OUTPUTS

    def norm(y, name):
        return _norm(cfg, y, lp[name], lp.get(name + "_b"))

    h, maps = _stream_in(cfg, lp, "hc_attn", x, impl)
    h = norm(h, "attn_norm") if pre else h
    if op == MAMBA:
        delta, m = _mamba_op(cfg, lp, h, taps_fn, s6_fn)
        memo_fn(m)
    elif op == GMU:
        delta = _gmu_op(lp, h, memo_fn())
    elif cfg.mb_per_layer:  # window, full or cross: differential attention
        delta = _diff_attention_op(cfg, lp, h, attn_fn, depth,
                                   cross=op == CROSS)
    elif op == CONV:
        delta = _conv_op(cfg, lp, h, taps_fn)
    elif op == LINEAR and cfg.lightning_nh:
        delta = _lightning_op(cfg, lp, h, positions, ssm_fn, depth)
    elif op == LINEAR and cfg.kda:
        delta = _kda_op(cfg, lp, h, taps_fn, rule_fn)
    elif op == LINEAR:
        delta = _linear_attention_op(cfg, lp, h, taps_fn, rule_fn)
    elif op == PARALLEL:
        delta = _parallel_op(cfg, lp, h, positions, attn_fn, taps_fn, ssm_fn)
    elif cfg.kv_lora_rank:
        delta = _latent_attention_op(cfg, lp, h, positions, attn_fn, few)
    else:  # attention over K and V: the whole context, or a window
        delta = _attention_op(cfg, lp, h, positions, attn_fn,
                              rotate=cfg.rotates(op), kind=op)
    if cfg.sandwich_norm:
        delta = norm(delta, "post_attn_norm")
    scale = cfg.residual_multiplier  # `scale_depth`'s; 1: nothing is traced
    if scale != 1.0:
        delta = delta * scale
    x = _stream_out(cfg, x, delta if pre else norm(delta, "attn_norm"),
                    maps, impl)
    h, maps = _stream_in(cfg, lp, "hc_mlp", x, impl)
    with jax.named_scope("mlp"):
        delta, load = _ffn(cfg, lp, ffn, norm(h, "mlp_norm") if pre else h,
                           valid=valid, mesh=mesh, impl=impl, layer=layer)
        if not pre:
            delta = norm(delta, "mlp_norm")
        elif cfg.sandwich_norm:
            delta = norm(delta, "post_mlp_norm")
        if scale != 1.0:
            delta = delta * scale
    return _stream_out(cfg, x, delta, maps, impl), load


def _no_state(cfg: ModelConfig, valid=None) -> dict:
    """taps_fn, rule_fn and ssm_fn of a forward over whole sequences from
    position 0: shifted copies, and the chunked recurrence from an empty
    state."""
    return {
        "taps_fn": lambda z: shortconv.taps_full(z, cfg.state_window[0]),
        "rule_fn": lambda q, k, v, g, beta: gated_delta.chunked(
            q, k, v, g, beta, valid)[0],
        "ssm_fn": lambda c, b, v, g: ssd.chunked(c, b, v, g, valid)[0]}


def _served_forwards_only(cfg: ModelConfig, forward: str) -> None:
    """A decoder-hybrid-decoder stack has the two step forwards only: its
    oracle is the benchmark's reference, and it embeds nothing."""
    if cfg.mb_per_layer:
        raise ValueError(
            f"{cfg.name}: mb_per_layer {cfg.mb_per_layer}: {forward} has no "
            "form for mamba, gated-memory and cross layers (served: "
            "forward_ragged, forward_decode; the oracle: "
            "benchmarks/reference/phi4_flash_decoder.py)")
    if cfg.count(SPARSE):
        raise ValueError(
            f"{cfg.name}: {forward} has no form for {SPARSE} layers: their "
            "pooled keys live under a page table (served: forward_ragged, "
            "forward_decode; the oracle: "
            "benchmarks/reference/minicpm_sala_decoder.py)")


def _causal_fn(cfg: ModelConfig, seq_lens, op: str = ATTENTION):
    """`attn_fn` of a forward over whole sequences from position 0: dense
    causal attention (a window layer's, `op`: under its window) — with
    latent attention over the latent rows, the selection by the span's own
    index scores."""
    if cfg.kv_lora_rank:
        return lambda q_abs, row, index, expanded=None: mla.dense_attention(
            q_abs, row, *(index or (None,) * 3), seq_lens, cfg.kv_lora_rank,
            cfg.index_topk)
    window = cfg.sliding_window if op == WINDOW else 0
    return lambda q, k, v, sink=None: causal_attention(
        q, k, v, seq_lens, window, sink)


def forward_prefill(
    params: dict,
    cfg: ModelConfig,
    tokens: jnp.ndarray,  # [B, T] int32, right-padded
    seq_lens: jnp.ndarray,  # [B] valid lengths
    k_cache: jnp.ndarray,  # [La, S, Hk*hd] flat slot pool (donated; loop carry)
    v_cache: jnp.ndarray,
    page_table: jnp.ndarray,  # [B, max_pages]; padding rows point at trash page
    page_size: int,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Whole prompts in one dense causal pass (the oracle: see the module
    docstring); returns (last_logits [B, V], k_cache', v_cache'). A conv
    layer runs over shifted copies of its z and leaves no state (nor does
    a window layer: its rows belong to no page): the
    oracle of a prompt's logits, not the first half of a generation.

    Padding positions scatter into the allocator's reserved trash page, so
    the write is fully static-shaped — no dynamic trimming needed.
    """
    _served_forwards_only(cfg, "forward_prefill")
    B, T = tokens.shape
    x = _embed(params, cfg, tokens)
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    slots = flat_slot_indices(page_table, positions, page_size)  # [B, T]

    def body(x, lp, kinds, ix, kc, vc):
        def attn_fn(q, k, v, expanded=None, sink=None):
            nonlocal kc, vc
            if kinds[0] == WINDOW:  # its rows are no pool's: no state left
                return _causal_fn(cfg, seq_lens, WINDOW)(q, k, v, sink)
            kc = kv_write(kc, ix.op, slots, _stored(k))  # K, or the latent row
            # V, or the index key (v: the indexer's q, k and head weights;
            # None with no indexer: no second pool)
            if not cfg.kv_lora_rank:
                vc = kv_write(vc, ix.op, slots, v)
            elif v is not None:
                vc = kv_write(vc, ix.op, slots, v[1])
            if sink is not None:
                return _causal_fn(cfg, seq_lens)(q, k, v, sink)
            return _causal_fn(cfg, seq_lens)(q, k, v)

        valid = positions < seq_lens[:, None]
        x, load = _layer_step(
            cfg, lp, kinds, x, positions, attn_fn, valid=valid,
            layer=ix.ffn, **_no_state(cfg, valid))
        return x, kc, vc, load

    x, k_cache, v_cache, _ = scan_layers(cfg, body, x, params["layers"],
                                         k_cache, v_cache)
    last = jnp.clip(seq_lens - 1, 0, T - 1)
    x_last = jnp.take_along_axis(
        x, last.reshape((B,) + (1,) * (x.ndim - 1)), axis=1)  # [B,1,D]
    logits = _logits(params, cfg, x_last)[:, 0, :]  # [B, V]
    return logits, k_cache, v_cache


def forward_ragged(
    params: dict,
    cfg: ModelConfig,
    tokens: jnp.ndarray,  # [T] int32 flattened mixed-batch token stream
    tok_seq: jnp.ndarray,  # [T] int32 sequence (batch row) per token
    tok_pos: jnp.ndarray,  # [T] int32 kv position per token (-1 = pad)
    write_slots: jnp.ndarray,  # [T] int32 flat cache slot per token
    out_idx: jnp.ndarray,  # [B] or [B, O] int32 stream indices to read logits at
    k_cache: jnp.ndarray,  # [La, S, Hk*hd] (donated; loop carry)
    v_cache: jnp.ndarray,
    page_table: jnp.ndarray,  # [B, max_pages]
    q_start: jnp.ndarray,  # [B] span offset per sequence
    q_len: jnp.ndarray,  # [B] span length (0 = padding row)
    kv_len: jnp.ndarray,  # [B] context length incl. the span
    page_size: int,
    attn_impl: str = "jnp",  # "jnp" reference | "pallas" ragged TPU kernel
    interpret: bool = False,
    mesh=None,  # the mesh this forward is jitted over (pallas under tp)
    moe_load: bool = False,  # also return the [Le, E] expert loads
    conv_state=None,  # a SlotState ([Lc, K-1, slots, D]: its `conv`; carry)
    slot_ids=None,  # [B] each row's slot: its row of conv_state
    is_first=None,  # [B] the span is its request's first: state opens at 0
    hidden: bool = False,  # also return the last hiddens [T, D], last
    emits=None,  # [B] the row's logits are SAMPLED (an `exit_layer` stack)
    early_exit: bool = True,  # tests only: False runs every row everywhere
):
    """ONE forward over a ragged mixed batch: variable-length prefill
    spans and single decode tokens share a flattened [T] token stream —
    no per-sequence bucket padding. Each attention layer writes the
    stream's K/V into its pages, then every token attends causally over
    its own sequence's paged context (forward_decode generalized to
    multi-token spans: a prompt fed span by span reproduces
    forward_prefill); each conv layer reads a span's predecessors from the
    stream and, before the span, from the row's slot of `conv_state`, and
    leaves the span's last positions there (ops/shortconv.taps_ragged; a
    model with conv layers needs `conv_state`, `slot_ids`, `is_first`); a
    linear-attention layer does the same for its convolution and continues
    each row's rule state through the row's span (ops/gated_delta.ragged:
    `SlotState.rule`); a window layer writes the stream's
    K/V into the ring of each row's slot (`SlotState.ring`) and every token
    attends over its last `sliding_window` positions there, the walk
    starting at the ring page that holds the first of them
    (ops/attention.py:ring_table).
    `out_idx` names the stream positions whose logits leave the forward: a
    [B] vector (each sequence's last token — the classic shape) returns
    [B, V]; a [B, O] matrix (speculative verification reads a logit at
    EVERY draft position of a span) returns [B, O, V]. Padding rows
    (q_len == 0) yield garbage logits the caller ignores. Returns
    (logits, caches'), then conv_state' where one was given, and with
    `moe_load` (an MoE model's step program asks) the rows each expert of
    each expert layer got, [Le, E] int32, and with `hidden` every stream
    position's last hidden BEFORE the final norm, [T, D] (what the
    prediction module reads: `forward_mtp`), last.

    A stack with an `exit_layer` (`mb_per_layer`: layers from there on hold
    no state and write no cache) runs the layers below it over the stream,
    gathers the B rows at `out_idx` — the hiddens and the scan output the
    gated memory units read — and runs the layers above on those rows alone:
    a GMU is two matmuls on B rows, a cross layer attends ONE query a row
    over the full layer's pages, the decode kernel's shape. A row that does
    not emit (`emits` 0, or padding) is handed to the cross layers with a
    context of 0: its walks read nothing, its logits are garbage the caller
    ignores. The sampled rows' logits are those of a forward that runs every
    row through every layer: no later layer reads the rows left behind.
    """
    exits = bool(cfg.exit_layer) and early_exit
    if cfg.exit_layer and (hidden or (out_idx.ndim == 2
                                       and out_idx.shape[1] != 1)):
        raise ValueError(
            f"{cfg.name}: mb_per_layer {cfg.mb_per_layer}: one sampled row "
            "a sequence passes the upper layers; a verify span's logits at "
            "every draft position (--spec) and the last hiddens of every "
            "row (`hidden`) are not served (ROADMAP B-M9)")
    with jax.named_scope("embed"):
        x = _embed(params, cfg, tokens)[None]  # [1,T,D]
    positions = jnp.maximum(tok_pos, 0)[None, :]  # [1, T] RoPE positions
    valid = (tok_pos >= 0)[None, :]
    state = slot_state(conv_state)
    if state.conv is not None:  # one plan for every layer with a window
        conv_plan = shortconv.ragged_plan(
            state.conv.shape[2], slot_ids, tok_seq, q_start, q_len, is_first)
    if state.ring is not None:  # one table for every window layer
        rows = state.ring.rows
        ring_slots = ring_write_slots(
            slot_ids[tok_seq], tok_pos, tok_pos >= 0, rows, state.ring.trash)
        ring_pt, ring_base = ring_table(
            slot_ids, kv_len, q_len, cfg.sliding_window, rows, page_size,
            tokens.shape[0])
    few = None
    if cfg.kv_lora_rank:  # once for the latent layers: their launch
        few = mla.absorbed_lead(
            attn_impl, q_start, q_len, tokens.shape[0], cfg.num_heads,
            cfg.latent_lanes, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
            cfg.v_head_dim)

    memo, sampled, live_len = (), None, None
    if cfg.exit_layer:
        memo = (jnp.zeros((1, tokens.shape[0], cfg.s6_inner), x.dtype),)
        sampled = out_idx.reshape(-1)  # [B]
        live_len = kv_len if emits is None else jnp.where(
            (emits > 0) & (q_len > 0), kv_len, 0)

    if state.pooled is not None:  # the tokens that complete a pooled row
        sizes = block_select.Sizes.of(cfg)
        done_at, done = block_select.completing(
            tok_pos, tok_pos >= 0, sizes,
            tokens.shape[0] // sizes.stride + page_table.shape[0])

    def body(x, lp, kinds, ix, kc, vc, conv, rule, ring, pooled, *m):
        def attn_fn(q, k, v, expanded=None, sink=None):  # [1, T, H, hd]
            nonlocal kc, vc, ring, pooled
            if kinds[0] == SPARSE:
                with jax.named_scope("kv_write"):
                    kc = kv_write(kc, ix.op, write_slots, k[0])
                    vc = kv_write(vc, ix.op, write_slots, v[0])
                    with jax.named_scope("bsa_pool_write"):
                        pooled = block_select.write_pooled(
                            pooled, kc, ix.op, page_table[tok_seq[done_at]],
                            tok_pos[done_at], done, sizes, page_size)
                return _sparse_ragged(
                    cfg, attn_impl, q[0], kc, vc, pooled, ix.op, page_table,
                    tok_seq, tok_pos, kv_len, q_start, q_len, page_size,
                    interpret, mesh)[None]
            if kinds[0] == CROSS:  # the full layer's rows, as it left them
                with jax.named_scope("cross_attention"):
                    if not exits:
                        return ragged_attention_any(
                            attn_impl, q[0], kc, vc,
                            cfg.count(ATTENTION) - 1, page_table, tok_seq,
                            tok_pos, kv_len, q_start, q_len, page_size,
                            interpret=interpret, mesh=mesh)[None]
                    return _cross_attend(
                        attn_impl, q[:, 0], kc, vc, cfg.count(ATTENTION) - 1,
                        page_table, live_len, page_size,
                        interpret)[:, None]
            if cfg.kv_lora_rank:
                kc, vc, out = _latent_ragged(
                    cfg, q, k, v, kc, vc, ix.op, write_slots, page_table,
                    tok_seq, tok_pos, q_start, q_len, kv_len, page_size,
                    attn_impl, interpret, expanded=expanded)
                return out
            if kinds[0] == WINDOW:
                with jax.named_scope("kv_write"):
                    ring = ring.write(ix.op, ring_slots, _stored(k[0]), v[0])
                with jax.named_scope("attention"):
                    return ragged_attention_any(
                        attn_impl, q[0], ring.k, ring.v, ix.op, ring_pt,
                        tok_seq, tok_pos, kv_len, q_start, q_len, page_size,
                        interpret=interpret, mesh=mesh,
                        window=cfg.sliding_window, pos_base=ring_base,
                        sink=sink)[None]
            with jax.named_scope("kv_write"):
                kc = kv_write(kc, ix.op, write_slots, _stored(k[0]))
                vc = kv_write(vc, ix.op, write_slots, v[0])
            with jax.named_scope("attention"):
                out = ragged_attention_any(
                    attn_impl, q[0], kc, vc, ix.op, page_table, tok_seq,
                    tok_pos, kv_len, q_start, q_len, page_size,
                    interpret=interpret, mesh=mesh, sink=sink,
                )
            return out[None]

        def taps_fn(z):  # [1, T, D]
            nonlocal conv
            taps, conv = shortconv.taps_ragged(z[0], conv, ix.op, conv_plan)
            return [t[None] for t in taps]

        def rule_fn(q, k, v, g, beta):  # [1, T, H, .]
            nonlocal rule
            o, rule = gated_delta.ragged(
                q[0], k[0], v[0], g[0], beta[0], rule, ix.op, slot_ids,
                tok_seq, tok_pos, q_start, q_len, is_first, impl=attn_impl,
                interpret=interpret)
            return o[None]

        def ssm_fn(c, b, v, g):  # [1, T, G | H, .]; its state rides `rule`
            nonlocal rule
            y, rule = ssd.ragged(
                c[0], b[0], v[0], g[0], rule, ix.op, slot_ids, tok_seq,
                tok_pos, q_start, q_len, is_first, impl=attn_impl,
                interpret=interpret)
            return y[None]

        def s6_fn(dt, u, b, c, a):  # [1, T, .]; its state rides `rule` too
            nonlocal rule
            y, rule = selective_scan.ragged(
                dt[0], u[0], b[0], c[0], a, rule, ix.op, slot_ids, tok_seq,
                tok_pos, q_start, q_len, is_first, impl=attn_impl,
                interpret=interpret)
            return y[None]

        handed = _Memo(m)
        x, load = _layer_step(cfg, lp, kinds, x, positions, attn_fn, taps_fn,
                              valid=valid, mesh=mesh, impl=attn_impl,
                              layer=ix.ffn, rule_fn=rule_fn, ssm_fn=ssm_fn,
                              few=few, s6_fn=s6_fn, memo_fn=handed,
                              depth=ix.layer)
        return (x, kc, vc, conv, rule, ring, pooled, *handed.held, load)

    @jax.named_scope("early_exit_gather")
    def at_exit(x, *carried):
        *carried, m = carried
        return (x[0][sampled][:, None], *carried, m[0][sampled][:, None])

    x, k_cache, v_cache, conv, rule, ring, pooled, *_, load = scan_layers(
        cfg, body, x, params["layers"], k_cache, v_cache, *state.loop_carry(), *memo,
        at_exit=at_exit if exits else None)
    if exits:  # x [B, 1, D]: the sampled rows, through every layer
        logits = _logits(params, cfg, x)[:, 0]  # [B, V]
        if out_idx.ndim == 2:
            logits = logits[:, None]
    elif out_idx.ndim == 1:
        x_last = x[0][out_idx]  # [B, D]
        logits = _logits(params, cfg, x_last[None], attn_impl)[0]  # [B, V]
    else:
        x_last = x[0][out_idx]  # [B, O, D]
        logits = _logits(params, cfg, x_last, attn_impl)  # [B, O, V]
    out = _results(logits, k_cache, v_cache, conv_state,
                   state.after(conv, rule, ring, pooled), load, moe_load)
    return out + (x[0],) if hidden else out


def _sparse_rows(cfg, attn_impl, q, kc, vc, pooled, layer, page_table, n,
                 live, page_size, interpret=False):
    """One query a row over the blocks its kv groups keep: q [B, H, hd] at
    contexts n [B] (live [B] bool: the others read nothing) over pool layer
    `layer` — the block scores over the rows' pooled keys, the top-k, and
    the walk over the kept blocks' pages, a (row, kv head) each
    (ops/block_select.py; on the chip
    ops/pallas/block_sparse_attention.py). A row at or under
    `sparse_dense_len` walks its own pages."""
    z = block_select.Sizes.of(cfg)
    B, H, hd = q.shape
    Hk = cfg.num_kv_heads
    with jax.named_scope("bsa_select"):
        # (J in whole lane tiles: rows past the table's are the last page's
        # again, and lie past every context)
        J = -(-page_table.shape[1] * page_size // z.stride // 128) * 128
        at = block_select.pooled_slots(
            page_table, jnp.broadcast_to(jnp.arange(J, dtype=jnp.int32),
                                         (B, J)), z.stride, page_size)
        pk = pooled[layer, at].reshape(B, J, Hk, hd)
        probs = None
        if attn_impl == "pallas":
            from ollamamq_tpu.ops.pallas.bsa_select import bsa_select_pallas

            probs = bsa_select_pallas(q, pk, n, z.kernel, z.stride,
                                      interpret=interpret)
        ids, count = block_select.select(q, pk, n, z, probs)
        table, lens = block_select.walk_table(page_table, ids, count, n,
                                              live, z, page_size)
    with jax.named_scope("bsa_attention"):
        rows = jnp.repeat(q, Hk, axis=0)  # row (b, g): every head of b
        if attn_impl == "pallas":
            from ollamamq_tpu.ops.pallas.block_sparse_attention import (
                bsa_decode_attention_pallas)

            o = bsa_decode_attention_pallas(rows, kc, vc, layer, table, lens,
                                            page_size, interpret=interpret)
        else:
            o = paged_decode_attention(rows, kc, vc, layer, table, lens,
                                       page_size)
        # group g's heads from row (b, g)
        o = o.reshape(B, Hk, Hk, H // Hk, hd)
        return o[:, jnp.arange(Hk), jnp.arange(Hk)].reshape(B, H, hd)


def _sparse_ragged(cfg, attn_impl, q, kc, vc, pooled, layer, page_table,
                   tok_seq, tok_pos, kv_len, q_start, q_len, page_size,
                   interpret=False, mesh=None):
    """A ragged stream's block-sparse attention of one layer, q [T, H, hd].
    A row whose context is at or under `sparse_dense_len` keeps every block:
    the plain ragged walk serves it. A ONE-TOKEN row past it follows its
    block list (`_sparse_rows`: the kept blocks' pages and no other). A
    longer SPAN that ends past it is served under its tokens' block masks
    over the row's whole context, in XLA (block_select.span_attention; a
    loop over such rows, none in most steps): what the mathematics asks,
    not yet what it allows to skip."""
    z = block_select.Sizes.of(cfg)
    T = q.shape[0]
    past = kv_len > z.dense_len
    one, span = past & (q_len == 1), past & (q_len > 1)
    order = jnp.argsort(~span, stable=True).astype(jnp.int32)

    def row(i, out):
        b = order[i]
        with jax.named_scope("bsa_span"):
            o = block_select.span_attention(
                q, kc, vc, pooled, layer, page_table[b], tok_pos, z,
                page_size)
        mine = (tok_seq == b) & (tok_pos >= 0)
        return jnp.where(mine[:, None, None], o, out)

    with jax.named_scope("attention"):
        # (the rows served below walk their own span only here)
        out = ragged_attention_any(
            attn_impl, q, kc, vc, layer, page_table, tok_seq, tok_pos,
            jnp.where(past, q_len, kv_len), q_start, q_len, page_size,
            interpret=interpret, mesh=mesh)
        o_rows = _sparse_rows(cfg, attn_impl, q[jnp.clip(q_start, 0, T - 1)],
                              kc, vc, pooled, layer, page_table, kv_len, one,
                              page_size, interpret)
        out = out.at[jnp.where(one, q_start, T)].set(o_rows, mode="drop")
        return jax.lax.fori_loop(0, jnp.sum(span).astype(jnp.int32), row,
                                 out)


def _cross_attend(attn_impl, q, kc, vc, layer, page_table, ctx_len,
                  page_size, interpret=False):
    """A cross layer's launch: one query a row (q [B, H, 2 hd]) over the
    first `ctx_len` [B] cached positions of pool layer `layer` — the decode
    kernel under a name of its own (ops/pallas/cross_attention.py), or its
    jnp twin. A context of 0 reads nothing."""
    if attn_impl == "pallas":
        from ollamamq_tpu.ops.pallas.cross_attention import (
            xattn_decode_attention_pallas)

        return xattn_decode_attention_pallas(
            q, kc, vc, layer, page_table, ctx_len, page_size,
            interpret=interpret)
    return paged_decode_attention(q, kc, vc, layer, page_table, ctx_len,
                                  page_size)


def _latent_ragged(cfg, q, row, index, kc, vc, layer, write_slots, page_table,
                   tok_seq, tok_pos, q_start, q_len, kv_len, page_size,
                   attn_impl, interpret, name=None, expanded=None):
    """A ragged stream's latent attention of one layer: the write of the
    tokens' cache rows (the latent row; the index key where there is an
    indexer), then ops/mla.attend. q [1, T, H, lanes], row [1, T, lanes];
    returns (kc', vc', o [1, T, H, c]) — or, where the kernel expanded a
    span (`expanded`: _latent_attention_op), o as its three."""
    q_idx = w_idx = None
    with jax.named_scope("mla_cache_write"):
        kc = kv_write(kc, layer, write_slots, row[0])
        if index is not None:
            q_idx, w_idx = index[0][0], index[2][0]
            vc = kv_write(vc, layer, write_slots, index[1][0])
    out = mla.attend(
        attn_impl, q[0], q_idx, w_idx, kc, vc,
        layer, page_table, tok_seq, tok_pos, q_start, q_len, kv_len,
        page_size, cfg.kv_lora_rank, cfg.index_topk, interpret=interpret,
        name=name,
        expanded=expanded and (expanded[0][0], expanded[1]))
    if isinstance(out, tuple):
        return kc, vc, tuple(o[None] for o in out)
    return kc, vc, out[None]


def forward_mtp(
    params: dict,
    cfg: ModelConfig,
    hidden: jnp.ndarray,  # [T, D] the trunk's last hiddens (forward_ragged)
    next_tokens: jnp.ndarray,  # [T] int32 the token FOLLOWING each position
    tok_seq, tok_pos, write_slots,  # [T], as forward_ragged's
    out_idx: jnp.ndarray,  # [B] stream index a row's draft is read at
    k_cache: jnp.ndarray,  # the latent pool (donated): the module's block
    # writes and reads ITS layer of it
    page_table, q_start, q_len, kv_len,
    page_size: int,
    attn_impl: str = "jnp",
    interpret: bool = False,
    mesh=None,
):
    """The multi-token-prediction module over a ragged step's stream (depth
    1, DeepSeek-V3's formulation): at stream position t of sequence position
    i, `u = [enorm(Emb(t_{i+1})) | hnorm(h_i)] W_eh`, `v = Block(u)` — one
    more block of the stack (sandwich norms, latent attention over the
    module's OWN rows of the latent pool, layer `count(ATTENTION)`, written
    here at the span's positions; the expert layer) — and `Head(norm(v))`,
    embedding and head the trunk's, is the distribution of `t_{i+2}`.
    Every position of every span passes (the module's cache needs each);
    the logits leave at `out_idx` only. Returns (logits [B, V], k_cache',
    expert load [E] int32). (No indexer, so no second pool: config.py.)"""
    from ollamamq_tpu.ops.pallas.mla_attention import MTP_NAME

    eps = cfg.rms_norm_eps
    positions = jnp.maximum(tok_pos, 0)[None, :]
    valid = (tok_pos >= 0)[None, :]
    layer = cfg.count(ATTENTION)  # behind the trunk's layers
    with jax.named_scope("mtp_embed_proj"):
        e = embed_lookup(params["embed"], next_tokens, hidden.dtype)
        u = jnp.concatenate([rmsnorm(e, params["mtp_enorm"], eps),
                             rmsnorm(hidden, params["mtp_hnorm"], eps)],
                            axis=-1)
        x = qeinsum("td,de->te", u, params["mtp_eh_proj"])[None]  # [1,T,D]
    # The block's weights: the last entry of every stack its kind of layer
    # has; the expert stacks whole, read by index (as in scan_layers).
    mine = KIND_PARAMS[ATTENTION] + KIND_PARAMS[EXPERTS] + (
        "attn_norm", "mlp_norm", "post_attn_norm", "post_mlp_norm")
    lp = {name: stack if name in STACKED else stack[-1]
          for name, stack in params["layers"].items() if name in mine}

    def attn_fn(q, row, index, expanded=None):
        nonlocal k_cache
        k_cache, _, out = _latent_ragged(
            cfg, q, row, index, k_cache, None, layer, write_slots,
            page_table, tok_seq, tok_pos, q_start, q_len, kv_len, page_size,
            attn_impl, interpret, name=MTP_NAME)
        return out

    with jax.named_scope("mtp_block"):
        x, load = _layer_step(cfg, lp, (ATTENTION, EXPERTS), x, positions,
                              attn_fn, valid=valid, mesh=mesh,
                              impl=attn_impl, layer=cfg.count(EXPERTS))
    with jax.named_scope("mtp_head"):
        v = rmsnorm(x[0][out_idx], params["mtp_norm"], eps)
        logits = logits_head(v[None],
                             params.get("lm_head", params["embed"]))[0]
    return logits, k_cache, load


def _results(logits, k_cache, v_cache, conv_state, after, load, moe_load):
    """(logits, caches'[, conv_state'][, load]) of a step forward:
    `after`, the SlotState it leaves, where a `conv_state` was given."""
    out = (logits, k_cache, v_cache)
    if conv_state is not None:
        out += (after,)
    return out + (load,) if moe_load else out


def forward_decode(
    params: dict,
    cfg: ModelConfig,
    tokens: jnp.ndarray,  # [B] int32 — last generated token per slot
    positions: jnp.ndarray,  # [B] int32 — position of `tokens` in each seq
    k_cache: jnp.ndarray,  # [La, S, Hk*hd] (donated; loop carry)
    v_cache: jnp.ndarray,
    page_table: jnp.ndarray,  # [B, max_pages]
    page_size: int,
    attn_impl: str = "jnp",  # "jnp" reference | "pallas" ragged TPU kernel
    active=None,  # [B] int32/bool — live decode slots (None = all live)
    mesh=None,  # the mesh this forward is jitted over (pallas under tp)
    moe_load: bool = False,  # also return the [Le, E] expert loads
    conv_state=None,  # a SlotState ([Lc, K-1, >= B, D]: its `conv`; donated,
    # the loop's carry): row b is slot b's (of the rings too)
):
    """One decode step for the whole batch (row b is slot b); returns
    (logits [B,V], caches'), then conv_state' where one was given and,
    with `moe_load`, the [Le, E] expert loads (as forward_ragged).

    `active` feeds MoE routing and the conv state: parked slots carry
    garbage tokens that are routed to no expert (models/moe.py) and leave
    their slot's state as it was (ops/shortconv.taps_decode,
    ops/gated_delta.decode).
    """
    B = tokens.shape[0]
    valid = None if active is None else (active > 0)[:, None]
    with jax.named_scope("embed"):
        x = _embed(params, cfg, tokens)[:, None, :]  # [B,1,D]
    pos2 = positions[:, None]  # [B,1]
    write_slots = flat_slot_indices(page_table, pos2, page_size)[:, 0]  # [B]
    seq_lens = positions + 1
    state = slot_state(conv_state)
    if state.ring is not None:  # row b is slot b: one table, every layer
        rows = state.ring.rows
        every = jnp.arange(B, dtype=jnp.int32)
        ring_slots = ring_write_slots(
            every, positions, True if active is None else active > 0, rows,
            state.ring.trash)
        ring_pt, ring_base = ring_table(
            every, seq_lens, jnp.ones_like(seq_lens), cfg.sliding_window,
            rows, page_size, 1)

    memo = ()
    if cfg.exit_layer:  # every row is sampled: no row leaves the stack
        memo = (jnp.zeros((B, 1, cfg.s6_inner), x.dtype),)
        live_len = seq_lens if active is None else jnp.where(
            active > 0, seq_lens, 0)

    def body(x, lp, kinds, ix, kc, vc, conv, rule, ring, pooled, *m):
        def attn_fn(q, k, v, expanded=None, sink=None):  # [B, 1, H, hd]
            nonlocal kc, vc, ring, pooled
            if kinds[0] == SPARSE:
                live = jnp.ones((B,), bool) if active is None else active > 0
                with jax.named_scope("kv_write"):
                    kc = kv_write(kc, ix.op, write_slots, k[:, 0])
                    vc = kv_write(vc, ix.op, write_slots, v[:, 0])
                    with jax.named_scope("bsa_pool_write"):
                        pooled = block_select.write_pooled(
                            pooled, kc, ix.op, page_table, positions, live,
                            block_select.Sizes.of(cfg), page_size)
                with jax.named_scope("attention"):
                    return _sparse_rows(cfg, attn_impl, q[:, 0], kc, vc,
                                        pooled, ix.op, page_table, seq_lens,
                                        live, page_size)[:, None]
            if kinds[0] == CROSS:
                with jax.named_scope("cross_attention"):
                    return _cross_attend(
                        attn_impl, q[:, 0], kc, vc, cfg.count(ATTENTION) - 1,
                        page_table, live_len, page_size)[:, None]
            if kinds[0] == WINDOW:
                with jax.named_scope("kv_write"):
                    ring = ring.write(ix.op, ring_slots, _stored(k[:, 0]),
                                      v[:, 0])
                with jax.named_scope("attention"):
                    return paged_decode_attention_any(
                        attn_impl, q[:, 0], ring.k, ring.v, ix.op, ring_pt,
                        seq_lens, page_size, mesh=mesh,
                        window=cfg.sliding_window, pos_base=ring_base,
                        sink=sink)[:, None]
            if cfg.kv_lora_rank:  # a stream of B one-token spans
                q_idx = w_idx = None
                with jax.named_scope("mla_cache_write"):
                    kc = kv_write(kc, ix.op, write_slots, k[:, 0])
                    if v is not None:  # the indexer's (q, k, head weights)
                        q_idx, w_idx = v[0][:, 0], v[2][:, 0]
                        vc = kv_write(vc, ix.op, write_slots, v[1][:, 0])
                rows = jnp.arange(B, dtype=jnp.int32)
                return mla.attend(
                    attn_impl, q[:, 0], q_idx, w_idx,
                    kc, vc, ix.op, page_table, rows, positions, rows,
                    jnp.ones_like(rows), seq_lens, page_size,
                    cfg.kv_lora_rank, cfg.index_topk, tile=1)[:, None]
            with jax.named_scope("kv_write"):
                kc = kv_write(kc, ix.op, write_slots, _stored(k[:, 0]))
                vc = kv_write(vc, ix.op, write_slots, v[:, 0])
            with jax.named_scope("attention"):
                attn = paged_decode_attention_any(
                    attn_impl, q[:, 0], kc, vc, ix.op, page_table, seq_lens,
                    page_size, mesh=mesh, sink=sink,
                )  # [B,H,hd]
            return attn[:, None]

        def taps_fn(z):  # [B, 1, D]
            nonlocal conv
            taps, conv = shortconv.taps_decode(z[:, 0], conv, ix.op, active)
            return [t[:, None] for t in taps]

        def rule_fn(q, k, v, g, beta):  # [B, 1, H, .]
            nonlocal rule
            o, rule = gated_delta.decode(
                q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], rule, ix.op,
                active, impl=attn_impl)
            return o[:, None]

        def ssm_fn(c, b, v, g):  # [B, 1, G | H, .]
            nonlocal rule
            y, rule = ssd.decode(c[:, 0], b[:, 0], v[:, 0], g[:, 0], rule,
                                 ix.op, active, impl=attn_impl)
            return y[:, None]

        def s6_fn(dt, u, b, c, a):  # [B, 1, .]
            nonlocal rule
            y, rule = selective_scan.decode(
                dt[:, 0], u[:, 0], b[:, 0], c[:, 0], a, rule, ix.op, active,
                impl=attn_impl)
            return y[:, None]

        handed = _Memo(m)
        x, load = _layer_step(cfg, lp, kinds, x, pos2, attn_fn, taps_fn,
                              valid=valid, mesh=mesh, impl=attn_impl,
                              layer=ix.ffn, rule_fn=rule_fn, ssm_fn=ssm_fn,
                              s6_fn=s6_fn, memo_fn=handed, depth=ix.layer)
        return (x, kc, vc, conv, rule, ring, pooled, *handed.held, load)

    x, k_cache, v_cache, conv, rule, ring, pooled, *_, load = scan_layers(
        cfg, body, x, params["layers"], k_cache, v_cache, *state.loop_carry(), *memo)
    logits = _logits(params, cfg, x, attn_impl)[:, 0, :]
    return _results(logits, k_cache, v_cache, conv_state,
                    state.after(conv, rule, ring, pooled), load, moe_load)


def forward_embed(
    params: dict,
    cfg: ModelConfig,
    tokens: jnp.ndarray,  # [B, T]
    seq_lens: jnp.ndarray,  # [B]
) -> jnp.ndarray:
    """Embeddings from a GENERATIVE model: causal forward (no KV write, no
    conv state: whole sequences),
    masked mean pool of the final-norm hidden states, L2 norm — llama.cpp's
    default pooling for causal models, which is what the reference's Ollama
    backends run for /api/embed on e.g. llama3 (README.md /api/embed row).
    """
    _served_forwards_only(cfg, "forward_embed")
    B, T = tokens.shape
    x = _embed(params, cfg, tokens)
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))

    valid = positions < seq_lens[:, None]

    def body(x, lp, kinds, ix):
        return _layer_step(
            cfg, lp, kinds, x, positions, _causal_fn(cfg, seq_lens, kinds[0]),
            valid=valid, layer=ix.ffn, **_no_state(cfg, valid))

    x, _ = scan_layers(cfg, body, x, params["layers"])
    x = _norm(cfg, _read_out(params, cfg, x),
              params["final_norm"]).astype(jnp.float32)
    mask = (positions < seq_lens[:, None]).astype(jnp.float32)[:, :, None]
    pooled = jnp.sum(x * mask, axis=1) / jnp.maximum(jnp.sum(mask, axis=1), 1.0)
    return pooled / jnp.maximum(jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-9)


def forward_encoder(
    params: dict,
    cfg: ModelConfig,
    tokens: jnp.ndarray,  # [B, T]
    seq_lens: jnp.ndarray,  # [B]
) -> jnp.ndarray:
    """Embedding encoder: bidirectional attention + masked mean pool + L2 norm."""
    B, T = tokens.shape
    x = embed_lookup(params["embed"], tokens, _adtype(params))
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))

    def body(x, lp, kinds, ix):
        return _layer_step(
            cfg, lp, kinds, x, positions,
            lambda q, k, v: bidirectional_attention(q, k, v, seq_lens),
            valid=positions < seq_lens[:, None], layer=ix.ffn)

    x, _ = scan_layers(cfg, body, x, params["layers"])
    x = _norm(cfg, x, params["final_norm"]).astype(jnp.float32)
    mask = (positions < seq_lens[:, None]).astype(jnp.float32)[:, :, None]
    pooled = jnp.sum(x * mask, axis=1) / jnp.maximum(jnp.sum(mask, axis=1), 1.0)
    return pooled / jnp.maximum(jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-9)
