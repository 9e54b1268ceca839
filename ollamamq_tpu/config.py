"""Model and engine configuration.

Model architecture configs for the families the framework serves natively:
Llama 3.x (incl. llama3.2:1b and Llama-3-8B), Qwen2.5 (attention bias),
Qwen3 (per-head q/k norm), the sparse families Mixtral (top-2 of 8,
renormalised) and OLMoE (top-8 of 64, not renormalised, MHA, whole-vector
q/k norm) and the hybrids LFM2 (gated short convolutions beside attention,
a dense prefix, a sigmoid router with a selection bias) and Olmo-Hybrid
(gated delta-rule linear attention beside attention, norms on the
sublayers' outputs, no rotary embedding) and Qwen3-Next (the rule with
fewer key heads than value heads beside gated attention with a partial
rotary embedding, zero-centred norms, experts behind a gated shared
expert) and K-EXAONE (window attention over the last `sliding_window`
positions beside full attention, a rotary embedding on the window layers
only), plus a bidirectional encoder config for embedding models
(nomic-embed-text class). The dense names are the ones the reference's
stress test exercises (/root/reference/test_dispatcher.sh:5-7) and
BASELINE.json's configs list.
"""

from __future__ import annotations

import dataclasses
import math
from typing import NamedTuple, Optional


# A layer's operator (the published `layer_types` spellings) and its FFN.
ATTENTION, CONV, LINEAR = "full_attention", "conv", "linear_attention"
WINDOW = "sliding_attention"
# Attention AND a state-space mixer (Mamba-2 / SSD) in ONE layer, both over
# the same normed input, both added to the residual (Falcon-H1). This repo's
# naming: the published config.json has no `layer_types`; a model whose
# `mamba_d_ssm` is set has this kind in every layer.
PARALLEL = "attention_ssm"
# A decoder-hybrid-decoder stack (Phi-4-mini-flash; `mb_per_layer` 2), this
# repo's naming too (its config.json has no `layer_types`): a MIXER-ONLY layer
# whose operator is Mamba-1's selective scan (ops/selective_scan.py); a gated
# memory unit, `(m * silu(h W1)) W2` with m the scan output the LAST mamba
# layer handed on for the same token — no state, no cache; and a cross
# layer, a query and an out projection that attend over the K and V the
# stack's ONE full-attention layer cached — no K, no V, no write of its own.
MAMBA, GMU, CROSS = "mamba", "gated_memory", "cross_attention"
# BLOCK-SPARSE attention over K/V pages (MiniCPM-SALA's `minicpm4` mixer,
# InfLLM-V2; this repo's naming — the published list is `mixer_types`): a
# query past `sparse_dense_len` attends the `sparse_topk` best BLOCKS of
# `sparse_block_size` cached positions, chosen a KV group by a score over
# mean-pooled keys (ops/block_select.py). Its K and V rows live in the paged
# pool as a full layer's; its pooled keys in a pool of their own under the
# same page table (llama.SlotState.pooled).
SPARSE = "sparse_attention"
LAYER_KINDS = (ATTENTION, CONV, LINEAR, WINDOW, PARALLEL, MAMBA, GMU, CROSS,
               SPARSE)
# The published `mixer_types` spellings (`minicpm_sala`) and the kinds they
# are served as: `lightning-attn` is the LINEAR kind read with the
# `lightning_*` keys (a constant decay a head, no convolution).
MIXER_KINDS = {"minicpm4": SPARSE, "lightning-attn": LINEAR}
# Mamba-1's sizes (the published modelling code's constants for its mixer,
# not keys of config.json): channels = S6_EXPAND x hidden, a float32 state of
# S6_D_STATE a channel, S6_D_CONV taps, dt through a rank of hidden / 16.
S6_EXPAND, S6_D_STATE, S6_D_CONV = 2, 16, 4
# The kinds whose layers keep a fixed-size state a SLOT beside the paged pool
# (a window layer's: a ring of its last K and V rows; a parallel layer's: the
# mixer's convolution window and float32 recurrent state — its K and V live
# in the paged pool as a full-attention layer's do).
STATE_KINDS = (CONV, LINEAR, WINDOW, PARALLEL, MAMBA)
# The kinds whose K and V rows live in the paged pool: a layer of the pools
# each, in layer order.
PAGED_KINDS = (ATTENTION, PARALLEL, SPARSE)
# The kinds that are attention over K and V: they share the attention
# weights' stacks (`wq` ... one entry a layer of EITHER kind, in layer order).
ATTENTION_KINDS = (ATTENTION, WINDOW)
DENSE, EXPERTS = "dense", "experts"
# The longest period `ModelConfig.layer_plan` looks for.
MAX_PERIOD = 8
# The residual streams a token that `hc_mult` may ask for beside one: the n
# the mapping kernels and their tests are written for.
HC_STREAMS = 4
# Keys of a published `rope_scaling` group of type "yarn".
YARN_KEYS = ("type", "factor", "original_max_position_embeddings",
             "beta_fast", "beta_slow", "mscale", "mscale_all_dim")


def hybrid_layer_types(num_layers: int) -> tuple:
    """The kinds of a decoder-hybrid-decoder stack of `num_layers` layers
    (`mb_per_layer` 2: ModelConfig's comment says which layer is which)."""
    half = num_layers // 2
    return tuple(
        (MAMBA if i % 2 == 0 else WINDOW) if i < half
        else MAMBA if i == half else ATTENTION if i == half + 1
        else GMU if i % 2 == 0 else CROSS for i in range(num_layers))


class AttnShape(NamedTuple):
    """One attention kind's head shape (`ModelConfig.attn_shape`): what the
    projections, the cache rows, RoPE and the softmax of a layer of that kind
    are built from. `qk_dim` lanes a head of q and k, `v_dim` a head of v and
    of the attended values; `sink`: a learned logit a head in the softmax."""
    heads: int
    kv_heads: int
    qk_dim: int
    v_dim: int
    theta: Optional[float]
    sink: bool

    @property
    def q_lanes(self) -> int:
        return self.heads * self.qk_dim

    @property
    def k_lanes(self) -> int:
        return self.kv_heads * self.qk_dim

    @property
    def v_lanes(self) -> int:
        return self.kv_heads * self.v_dim

    @property
    def o_lanes(self) -> int:  # `wo`'s input
        return self.heads * self.v_dim


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """Decoder-only architecture description. A stack of `num_layers`
    blocks `x + Op(norm(x))`, `x + FFN(norm(x))`: by default every block is
    the same (Llama / Qwen / Mixtral / OLMoE: attention, then a dense SwiGLU
    or routed experts). `layer_types` makes the stack one whose layers
    differ: each layer's operator is attention, a gated short convolution
    with a per-sequence state (LFM2) or gated delta-rule linear attention
    with a per-sequence matrix state (Olmo-Hybrid), the first
    `num_dense_layers` of a sparse stack keep a dense FFN, and the router's
    score, selection bias, normalisation and scale are fields
    (`layer_plan()` is what the forwards scan). `norm_order` "post" moves
    each norm from a sublayer's input to its output: `x + norm(Op(x))`;
    `sandwich_norm` keeps the input norm and adds one on the output:
    `x + norm'(Op(norm(x)))`."""

    name: str
    vocab_size: int
    hidden_size: int
    intermediate_size: int
    num_layers: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    # None: attention without a rotary embedding (Olmo-Hybrid: positions
    # reach its attention layers through the linear layers' recurrence).
    rope_theta: Optional[float] = 500_000.0
    rms_norm_eps: float = 1e-5
    max_seq_len: int = 8192
    tie_embeddings: bool = False
    # Qwen2-style attention projections carry a bias term; Llama's do not.
    attn_bias: bool = False
    # RMSNorm on q and k after projection (pre-RoPE). False: none. True or
    # "head": Qwen3's, per head (weight over head_dim). "full": OLMoE's,
    # over the WHOLE projected vector before the split into heads (weight
    # over q_dim / kv_dim). A value, not a second field, because a
    # configuration file's `qk_norm` key reaches this field verbatim.
    qk_norm: object = False
    # Bidirectional attention + mean pooling => embedding encoder, not a LM.
    is_encoder: bool = False
    # Mixture-of-experts (Mixtral, OLMoE): 0 = dense FFN. When > 0, each
    # layer's FFN becomes num_experts independent SwiGLU experts with
    # top-(num_experts_per_tok) routing (models/moe.py: dropless — every
    # token's every routed expert contributes); experts shard over the
    # mesh "expert" axis.
    num_experts: int = 0
    num_experts_per_tok: int = 2
    # Whether the kept top-k router probabilities are renormalised to sum
    # to 1. False is the published default of the OLMoE/Qwen-MoE configs
    # (the key is `norm_topk_prob` there); Mixtral always renormalises.
    norm_topk_prob: bool = False
    # ...and what is added to their sum before the division (LFM2: 1e-6).
    norm_topk_eps: float = 0.0
    # The router's score over all experts, in float32: "softmax" (Mixtral,
    # OLMoE) or "sigmoid" (LFM2: each expert scored on its own).
    router_score: str = "softmax"
    # A per-expert bias (a buffer of the checkpoint, `router_bias`) added
    # to the scores for the SELECTION of the top k only: the weights are
    # the unbiased scores at the chosen experts.
    use_expert_bias: bool = False
    # The routed experts' output is multiplied by this.
    routed_scaling_factor: float = 1.0
    # The first `num_dense_layers` layers of a sparse stack keep a dense
    # SwiGLU of width `intermediate_size`; an expert is
    # `moe_intermediate_size` wide (0: `intermediate_size`, as OLMoE's).
    num_dense_layers: int = 0
    moe_intermediate_size: int = 0
    # One operator kind a layer (LAYER_KINDS), or None: attention in every
    # layer. "conv" is LFM2's gated short convolution: [B | C | u] = h W_in,
    # z = B * u, a depthwise causal convolution of z over `conv_L_cache`
    # positions, times C, W_out. Its whole state is z at the sequence's
    # last `conv_L_cache - 1` positions: no pages.
    layer_types: Optional[tuple] = None
    conv_L_cache: int = 3
    conv_bias: bool = False
    # "linear_attention" is the gated delta rule (ops/gated_delta.py): per
    # head a [key dim, value dim] float32 state S a sequence, S_t =
    # a_t S_{t-1} + k_t b_t (v_t - (a_t S_{t-1})^T k_t)^T, read by q_t; q, k
    # and v first pass a causal depthwise convolution of
    # `linear_conv_kernel_dim` taps and a SiLU (its window is state too).
    # `linear_allow_neg_eigval`: b in (0, 2) instead of (0, 1). The
    # published spellings, so a configuration file's keys reach them as
    # they are.
    linear_num_key_heads: int = 0
    linear_num_value_heads: int = 0
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = False
    # -- Qwen3-Next's attention, norms and shared expert ---------------------
    # RoPE over the FIRST `head_dim * partial_rotary_factor` lanes of a
    # head of q and k (an even number of them); the others pass unrotated.
    # The published spelling.
    partial_rotary_factor: float = 1.0
    # `wq_gate` gives a gate a head beside q (the published `q_proj` holds
    # both): attn = attn * sigmoid(gate) before `wo`. This repo's naming.
    attn_output_gate: bool = False
    # The block norms, the final norm and the per-head q/k norms multiply
    # by (1 + w), in float32: a stored weight of zero is the identity. The
    # rule's gated output norm (`lin_norm`) keeps the plain weight. This
    # repo's naming.
    zero_centred_norm: bool = False
    # The shared expert's width where it is not the routed experts' (the
    # published spelling; with it `n_shared_experts` stays 0), and whether
    # its output is multiplied by sigmoid(x w_sg) (this repo's naming).
    shared_expert_intermediate_size: int = 0
    shared_expert_gate: bool = False
    # "pre": x + Op(norm(x)) (Llama's). "post": x + norm(Op(x)) (OLMo 2's
    # reordered norm: `attn_norm` / `mlp_norm` weigh the sublayers' OUTPUTS).
    norm_order: str = "pre"
    # openPangu's published key: a SECOND norm a sublayer, on its output
    # (`post_attn_norm`, `post_mlp_norm`), beside the pre-norm on its input:
    # x + norm'(Op(norm(x))). With `norm_order` "pre" only.
    sandwich_norm: bool = False
    # The published `rope_parameters` group of a configuration file, taken
    # whole: its `rope_theta` (null: no rotary embedding) sets the field of
    # that name; any other key of the group is refused.
    rope_parameters: Optional[object] = None
    # -- latent attention with a learned sparse selection (DeepSeek-V3.2) ----
    # `kv_lora_rank` > 0 makes every attention layer multi-head LATENT
    # attention (ops/mla.py): q through a low-rank projection with an
    # RMSNorm (`q_lora_rank`), per head a `qk_nope_head_dim` part and a
    # rotary part of `qk_rope_head_dim`; K and V of a token are ONE normed
    # row of `kv_lora_rank` lanes (expanded a head by W_uk / W_uv, which the
    # served path absorbs into q and the output) and ONE rotary key of
    # `qk_rope_head_dim` lanes for all heads; values are `v_head_dim` wide.
    # The paged cache then holds that row (`latent_dim` lanes) and no V.
    # The published spellings, so a configuration file's keys reach them.
    q_lora_rank: Optional[int] = 0  # 0 / null: a full-rank q projection, `wq`
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # The lightning indexer: `index_n_heads` heads of `index_head_dim` score
    # every cached position of a token's sequence (ReLU, a learned weight a
    # head, summed), its key one row of `index_head_dim` lanes a token in a
    # second paged pool; attention sees the `index_topk` best positions (all
    # of them while the context is shorter). All three 0: latent attention
    # with no indexer (DeepSeek-V3's, openPangu's) — every cached position
    # is attended and there is no index-key pool.
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    # The published `rope_scaling` group of a configuration file, taken whole
    # (null: plain RoPE). Only `type` "yarn" is implemented: the frequencies
    # of ops/rope.yarn_freqs and a softmax scale times mscale^2.
    rope_scaling: Optional[object] = None
    # Group-limited routing: the router's experts in `n_group` equal groups,
    # a group scored by the sum of its best two (biased) scores, the top k
    # taken inside the `topk_group` best groups. 0: no groups.
    n_group: int = 0
    topk_group: int = 0
    # Experts every token passes (no gate), of the routed experts' width:
    # one SwiGLU of `n_shared_experts * expert_width`.
    n_shared_experts: int = 0
    # The chip's SHARE of an expert layer (model-configs guide, section 4):
    # the router scores `router_experts` experts (0: `num_experts`) and keeps
    # its `num_experts_per_tok`, gates normalised over all of them; this
    # program HOLDS the `num_experts` experts from `expert_offset` on and adds
    # what those give. What the absent experts would have added is left out.
    router_experts: int = 0
    expert_offset: int = 0
    # Published keys of the family that have ONE value here; any other is
    # refused. `first_k_dense_replace` is the published spelling of
    # `num_dense_layers` (either or both, agreeing); every layer after the
    # dense ones has experts (`moe_layer_freq` 1); `ep_size` is the published
    # file's own (1: the share held here is said by the two fields above);
    # Qwen3-Next's: every layer has experts (`decoder_sparse_step` 1,
    # `mlp_only_layers` empty), no sliding window, and
    # `full_attention_interval` n says what `layer_types` must: attention
    # in each n-th layer, counted from 1, linear attention in the others.
    first_k_dense_replace: Optional[int] = None
    moe_layer_freq: object = 1  # ...or the published LIST a layer long
    ep_size: int = 1
    decoder_sparse_step: int = 1
    mlp_only_layers: tuple = ()
    use_sliding_window: bool = False
    full_attention_interval: Optional[int] = None
    # -- window attention beside full attention (K-EXAONE) -------------------
    # A "sliding_attention" layer attends over the last `sliding_window`
    # positions only: query i sees key j iff i - sliding_window < j <= i
    # (itself and the sliding_window - 1 before it). Its K and V rows are
    # dead that many positions later, so they do not live in the paged pool:
    # each slot owns a RING of `ring_rows` rows a window layer
    # (ops/attention.py: position p at row p % ring_rows of the slot's),
    # and `cache_layers` counts the full layers only. The published
    # spelling; 0 where no layer has a window.
    sliding_window: int = 0
    # Published keys that say what `layer_types`, `sliding_window` and
    # `first_k_dense_replace` say already, read and held to them: a layer's
    # window (0: full attention), the pattern the list repeats ("L" a window
    # layer, "G" a full one: `layer_types[i]` is pattern[i % len]), a
    # layer's FFN ("dense" | "sparse").
    sliding_windows: Optional[tuple] = None
    sliding_window_pattern: Optional[str] = None
    mlp_layer_types: Optional[tuple] = None
    # ...and the prediction module's layer kind and window: read, and
    # refused unless `num_nextn_predict_layers` is 0 (the module is served
    # for latent attention only).
    mtp_layer_types: Optional[tuple] = None
    mtp_sliding_windows: Optional[tuple] = None
    # The attention kinds whose q and k take the rotary embedding (None:
    # every kind, where `rope_theta` is set). K-EXAONE rotates on the
    # window layers only: its full layers attend without positions. This
    # repo's naming.
    rope_layer_types: Optional[tuple] = None
    # Published spellings of `n_shared_experts` and `router_score` (either
    # or both, agreeing).
    num_shared_experts: Optional[int] = None
    scoring_func: Optional[str] = None
    # The multi-token-prediction module (DeepSeek-V3's formulation, depth 1):
    # from the trunk's last hidden of position i (before the final norm) and
    # the embedding of token i + 1, through two norms, a projection of their
    # concatenation and ONE more block of the stack's last kind with its own
    # cache rows, the trunk's head predicts token i + 2 (models/llama.py:
    # forward_mtp). No part of the next-token distribution: `--spec` serves
    # it as the draft proposer; without `--spec` it is held and not run.
    # 0 (no module) or 1, and 1 with latent attention (no indexer) and
    # experts only — and with ONE residual stream only: the module reads
    # `hidden [T, D]`, and how it joins `hc_mult` streams no config.json
    # says (`_check_streams`).
    num_nextn_predict_layers: int = 0
    # -- attention and a state-space mixer in every layer (Falcon-H1) --------
    # `mamba_d_ssm` > 0 makes every layer PARALLEL: x + Attn(h m_in) m_out +
    # SSM(h) m_ssm over ONE normed h, then the MLP. The mixer is Mamba-2's
    # (SSD; ops/ssd.py): [z | x | B | C | dt] = (h ssm_in_multiplier) W_in,
    # each segment times its entry of `ssm_multipliers`; x | B | C through a
    # depthwise causal convolution of `mamba_d_conv` taps with a bias and a
    # SiLU; per head (`mamba_n_heads` of `mamba_d_head`; B and C in
    # `mamba_n_groups` groups of `mamba_d_state`) a float32 state S
    # [d_head, d_state] a sequence, S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T,
    # y_t = S_t C_t + D x_t; y * silu(z) through an RMSNorm over each group's
    # channels, W_out. The published spellings (a configuration file's keys
    # reach the field of their name); the keys the program implements at one
    # value only are refused by name in `_check_ssm`.
    mamba_d_ssm: int = 0
    mamba_d_state: int = 0
    mamba_d_head: int = 0
    mamba_n_heads: int = 0
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_expand: int = 2  # read, not used: `mamba_d_ssm` says the width
    mamba_chunk_size: int = 128  # read, not used: the program's chunk is its own
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    mamba_rms_norm: bool = True
    mamba_norm_before_gate: bool = False
    mamba_use_mlp: bool = True
    attn_layer_indices: Optional[tuple] = None
    mlp_expansion_factor: int = 0  # read, not used: `intermediate_size` says it
    projectors_bias: bool = False
    mlp_bias: bool = False
    num_logits_to_keep: int = 1
    # The family's muP multipliers, applied in the forward where the published
    # code applies them (never folded into a stored tensor): on the embedding,
    # the logits, the attention branch's input and output, k, the mixer's
    # input and output, the five segments of its in-projection (z, x, B, C,
    # dt) and the MLP's gate and output. 1 everywhere else.
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    ssm_multipliers: tuple = (1.0,) * 5
    mlp_multipliers: tuple = (1.0, 1.0)
    # -- a decoder-hybrid-decoder stack (Phi-4-mini-flash) -------------------
    # `mb_per_layer` 2 derives the whole stack from `num_layers` (L) and
    # `sliding_window` (`hybrid_layer_types`): layer i < L/2 is MAMBA when i
    # is even and WINDOW when odd; layer L/2 a MAMBA layer that also hands on
    # its scan output m; layer L/2 + 1 the ONE full-attention layer; above it
    # GMU layers (even) that read m and CROSS layers (odd) that attend over
    # that layer's cached K and V. Every attention layer of such a stack is
    # DIFFERENTIAL (heads in adjacent pairs, models/llama.py:_diff_combine),
    # has biased projections and no position encoding; every norm of the
    # stack is a LayerNorm with a bias at `layer_norm_eps`; rows nobody
    # samples stop below the GMU layers (`exit_layer`). 0: no such stack.
    mb_per_layer: int = 0
    layer_norm_eps: Optional[float] = None
    lm_head_bias: bool = False
    embd_pdrop: float = 0.0  # read, refused unless 0: dropout says nothing
    resid_pdrop: float = 0.0  # of a served forward
    # -- block-sparse attention beside lightning attention (MiniCPM-SALA) ----
    # The published `mixer_types` (one of MIXER_KINDS' keys a layer) says what
    # `layer_types` says: either or both, agreeing. A "sparse_attention"
    # layer is GQA attention whose query at position t (context n = t + 1)
    # attends every cached position while n <= `sparse_dense_len`, and past
    # it the `sparse_topk` best blocks of `sparse_block_size` positions:
    # the first `sparse_init_blocks`, the last `sparse_window_size /
    # sparse_block_size` up to its own, and the best of the others by the
    # score of ops/block_select.py over keys mean-pooled `sparse_kernel_size`
    # at a stride of `sparse_kernel_stride`. This repo's naming (the family's
    # `sparse_config` group, which this model's config.json does not carry).
    mixer_types: Optional[tuple] = None
    sparse_kernel_size: int = 0
    sparse_kernel_stride: int = 0
    sparse_block_size: int = 0
    sparse_topk: int = 0
    sparse_init_blocks: int = 0
    sparse_window_size: int = 0
    sparse_dense_len: int = 0
    # `lightning_nh` > 0 reads every "linear_attention" layer as LIGHTNING
    # attention: `lightning_nh` heads of `lightning_head_dim` (as many key
    # and value heads: `lightning_nkv`), q and k RMS-normed a head (`qk_norm`)
    # and, with `lightning_use_rope`, roped; per head a float32 state S
    # [d, d] a sequence, S_t = lambda S_{t-1} + k_t v_t^T, o_t = S_t^T q_t
    # d^-1/2 (`lightning_scale`), lambda = exp(-slope) a CONSTANT of (head,
    # published layer index: `lightning_slopes`); an RMSNorm on o
    # (`use_output_norm`), a sigmoid gate a lane (`use_output_gate`), W_o. No
    # convolution, no decay or write-strength projection. The published
    # spellings.
    lightning_nh: int = 0
    lightning_nkv: int = 0
    lightning_head_dim: int = 0
    lightning_use_rope: bool = True
    lightning_scale: str = "1/sqrt(d)"  # read, held to this value
    use_output_norm: bool = True  # read, held to true
    use_output_gate: bool = True  # read, held to true
    # RoPE on the ATTENTION layers' q and k (false: NoPE; the lightning
    # layers' is `lightning_use_rope`), and the published spelling of
    # `attn_output_gate` (either or both, agreeing).
    attn_use_rope: bool = True
    attn_use_output_gate: Optional[bool] = None
    # The family's muP scalars: the embedding times `scale_emb` (folded into
    # `embedding_multiplier`), every sublayer's output times `scale_depth` /
    # sqrt(`scale_depth_layers`) before it joins the residual (0: no scaling;
    # `scale_depth_layers`, this repo's naming: the PUBLISHED depth, which a
    # stack cut in depth keeps — 0: `num_layers`), the logits divided by
    # `hidden_size` / `dim_model_base` (folded into `lm_head_multiplier`).
    # `layer_offset` (this repo's naming): the published index of the
    # stack's first layer, where the stack is a stage of a deeper model: a
    # lightning layer's decay is a function of it.
    scale_emb: float = 1.0
    scale_depth: float = 0.0
    scale_depth_layers: int = 0
    dim_model_base: int = 0
    layer_offset: int = 0
    mup_denominator: int = 32  # read, not used: a training-time constant
    rand_init: bool = False  # read, held to false
    # -- Kimi Delta Attention beside NoPE latent attention (Kimi-Linear) -----
    # The published `linear_attn_config` group of a configuration file, taken
    # whole (as `rope_parameters`): `kda_layers` / `full_attn_layers` (the
    # layers' 1-based indices: together every layer once) say what
    # `layer_types` says — "linear_attention" and "full_attention" — either
    # or both, agreeing; `num_heads`, `head_dim` and `short_conv_kernel_size`
    # are the linear kind's heads (as many key as value heads), head sizes
    # and taps. With the group the "linear_attention" layers are read as
    # KIMI DELTA ATTENTION, the kind's third reading beside the gated delta
    # rule's and lightning's: the rule of ops/gated_delta.py with a decay a
    # KEY CHANNEL, g = -exp(A_log[h]) softplus(x W_fa W_fb + dt_bias) in
    # R^{H x dk} (a low-rank projection through `head_dim` lanes), b =
    # sigmoid(x W_b); q, k, v each through its own projection and depthwise
    # convolution (held as one: depthwise over [q | k | v]); y = W_o
    # [RMSNorm_head(o; w) * sigmoid(x W_ga W_gb)]. Beside them the
    # "full_attention" layers are LATENT attention (`kv_lora_rank`), a layer
    # KIND of the stack: the latent pool has a layer an attention layer.
    linear_attn_config: Optional[object] = None
    # Latent attention WITHOUT a position embedding (the published key): q's
    # and the shared key's `qk_rope_head_dim` lanes are carried unrotated;
    # `rope_theta` is read and unused.
    mla_use_nope: bool = False
    # The family's published spellings of `num_experts_per_tok`,
    # `router_score`, `norm_topk_prob`, `n_group` and `max_seq_len` (either
    # or both, agreeing), and `use_grouped_topk`: read, and held to `n_group`
    # (true with `num_expert_group` 1 and `topk_group` 1 is the plain top k).
    num_experts_per_token: Optional[int] = None
    moe_router_activation_func: Optional[str] = None
    moe_renormalize: Optional[bool] = None
    num_expert_group: Optional[int] = None
    use_grouped_topk: Optional[bool] = None
    model_max_length: Optional[int] = None
    # -- window and full attention at DIFFERENT head shapes (MiMo-V2-Flash) --
    # The published `hybrid_layer_pattern` (0 a full layer, 1 a window layer)
    # says what `layer_types` says, and a LIST `moe_layer_freq` (0 a dense
    # FFN, 1 experts: the dense ones lead) what `num_dense_layers` says:
    # either or both, agreeing. A window layer has its own kv-head count
    # (`swa_num_key_value_heads`) and RoPE base (`swa_rope_theta`);
    # `swa_num_attention_heads`, `swa_head_dim` and `swa_v_head_dim` are read
    # and held to the full layers' (`num_heads`, `head_dim`, `v_head_dim`).
    # With any `swa_*` key the two kinds' weights are stacks of their own
    # (`per_kind_attention`; `attn_shape(kind)` is what every caller on the
    # attention path reads), and `v_head_dim` WITHOUT `kv_lora_rank` is the
    # width of a value head of plain K/V attention (0: `head_dim`): K and V
    # rows of different widths in pool and rings, `wo` from `num_heads x
    # v_head_dim`. `add_swa_attention_sink_bias`: a learned float32 logit a
    # head (`swa_sink`) joins a window layer's softmax as a column that every
    # query sees and that carries no value; `add_full_attention_sink_bias` is
    # read and refused unless false. `attention_value_scale` multiplies v
    # after its projection (before the cache). `sliding_window_size` and
    # `attention_chunk_size` are read and held to `sliding_window`;
    # `layernorm_epsilon` is the family's spelling of `rms_norm_eps`,
    # `topk_method` "noaux_tc" of `use_expert_bias`; a null `n_shared_experts`
    # is 0 and a null `routed_scaling_factor` 1.
    hybrid_layer_pattern: Optional[tuple] = None
    swa_num_attention_heads: Optional[int] = None
    swa_num_key_value_heads: Optional[int] = None
    swa_head_dim: Optional[int] = None
    swa_v_head_dim: Optional[int] = None
    swa_rope_theta: Optional[float] = None
    add_swa_attention_sink_bias: bool = False
    add_full_attention_sink_bias: bool = False
    attention_value_scale: Optional[float] = None
    sliding_window_size: Optional[int] = None
    attention_chunk_size: Optional[int] = None
    layernorm_epsilon: Optional[float] = None
    topk_method: Optional[str] = None
    # -- a residual path of several streams (Xing4.0: manifold-constrained ---
    # hyper-connections, arXiv:2512.24880) -----------------------------------
    # `hc_mult` n > 1: a token's residual is n STREAMS X [n, D], the embedding
    # laid on each. A sublayer F reads h = sum_j H_pre[j] X[j], and writes
    # X'[i] = sum_j H_res[i, j] X[j] + H_post[i] F(norm(h)); the three
    # mappings are a token's own, from ONE product of the flattened streams
    # (an RMS norm over all n D lanes, no weight) with Phi [n D, 2 n + n^2]:
    # H_pre = sigmoid(a_pre . + b) + `hc_eps`, H_post = 2 sigmoid(a_post . +
    # b), H_res = `hc_sinkhorn_iters` Sinkhorn-Knopp iterations (columns, then
    # rows, each over its sum + `hc_eps`) of exp(clip(a_res . + b,
    # `mhc_h_res_clamp_min`, `mhc_h_res_clamp_max`)): doubly stochastic. The
    # head reads the streams through a learned mix of the same form as H_pre
    # (ops/hyper_connection.py has the mathematics). 0 / 1: one stream, `x +
    # F(norm(x))`, and nothing of this is traced. The published spellings.
    hc_mult: int = 0
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30.0
    mhc_h_res_clamp_max: float = 30.0

    def __post_init__(self):
        self._derive_per_kind()
        self._derive_hybrid()
        self._derive_mixers()
        self._derive_kda()
        if self.qk_norm not in (False, True, "head", "full"):
            raise ValueError(
                f"{self.name}: qk_norm must be false, true, 'head' or "
                f"'full', got {self.qk_norm!r}")
        if self.router_score not in ("softmax", "sigmoid"):
            raise ValueError(
                f"{self.name}: router_score must be 'softmax' or 'sigmoid', "
                f"got {self.router_score!r}")
        if self.norm_order not in ("pre", "post"):
            raise ValueError(
                f"{self.name}: norm_order must be 'pre' or 'post', got "
                f"{self.norm_order!r}")
        if self.rope_parameters is not None:
            group = dict(self.rope_parameters)  # a file's dict: hashable
            plain = group.get("rope_type", "default") == "default"
            unknown = sorted(set(group) - {"rope_theta"}
                             - ({"rope_type"} if plain else set()))
            if unknown:
                raise ValueError(
                    f"{self.name}: rope_parameters holds {unknown}; the "
                    "program reads 'rope_theta' only (and a 'rope_type' of "
                    "'default': plain frequencies)")
            object.__setattr__(self, "rope_parameters",
                               tuple(sorted(group.items())))
            if "rope_theta" in group:
                object.__setattr__(self, "rope_theta", group["rope_theta"])
        if self.layer_types is not None:
            kinds = tuple(self.layer_types)  # a file's list: hashable
            object.__setattr__(self, "layer_types", kinds)
            if len(kinds) != self.num_layers:
                raise ValueError(
                    f"{self.name}: layer_types names {len(kinds)} layers, "
                    f"num_layers is {self.num_layers}")
            unknown = sorted(set(kinds) - set(LAYER_KINDS))
            if unknown:
                raise ValueError(
                    f"{self.name}: layer_types holds {unknown}; the program "
                    f"runs {list(LAYER_KINDS)}")
            if self.is_encoder and set(kinds) & set(STATE_KINDS):
                raise ValueError(
                    f"{self.name}: layer_types: a causal convolution or "
                    "recurrence in an encoder")
            if CONV in kinds and LINEAR in kinds:
                raise ValueError(
                    f"{self.name}: layer_types holds both {CONV!r} and "
                    f"{LINEAR!r}: the per-slot conv window has one width")
            if LINEAR in kinds and not self.lightning_nh:
                self._check_linear()
        self._check_sparse()
        self._check_ssm()
        if self.first_k_dense_replace is not None:
            if self.num_dense_layers not in (0, self.first_k_dense_replace):
                raise ValueError(
                    f"{self.name}: first_k_dense_replace "
                    f"{self.first_k_dense_replace} is not num_dense_layers "
                    f"{self.num_dense_layers}")
            object.__setattr__(self, "num_dense_layers",
                               self.first_k_dense_replace)
        object.__setattr__(self, "mlp_only_layers",
                           tuple(self.mlp_only_layers))  # a file's list
        for key, only in (("moe_layer_freq", 1), ("ep_size", 1),
                          ("decoder_sparse_step", 1),
                          ("mlp_only_layers", ())):
            if getattr(self, key) != only:
                raise ValueError(
                    f"{self.name}: {key} {getattr(self, key)}: the program "
                    f"implements only {only}")
        if self.rope_scaling is not None:
            group = dict(self.rope_scaling)  # a file's dict: hashable
            if group.get("type") != "yarn":
                raise ValueError(
                    f"{self.name}: rope_scaling type {group.get('type')!r}: "
                    "the program implements only 'yarn'")
            unknown = sorted(set(group) - set(YARN_KEYS))
            if unknown or "factor" not in group or \
                    "original_max_position_embeddings" not in group:
                raise ValueError(
                    f"{self.name}: rope_scaling (yarn) takes {YARN_KEYS} "
                    f"with 'factor' and 'original_max_position_embeddings'; "
                    f"got {sorted(group)}")
            object.__setattr__(self, "rope_scaling",
                               tuple(sorted(group.items())))
        self._fold_router_spellings()
        self._check_window()
        self._check_latent()
        self._check_share()
        self._check_gated()
        self._check_sandwich_and_module()
        self._check_streams()
        if not 0 <= self.num_dense_layers <= self.num_layers:
            raise ValueError(
                f"{self.name}: num_dense_layers {self.num_dense_layers} is "
                f"not within the stack's {self.num_layers} layers")
        if self.conv_L_cache < 2:
            raise ValueError(
                f"{self.name}: conv_L_cache must be at least 2, got "
                f"{self.conv_L_cache}")
        if self.conv_bias:
            raise ValueError(
                f"{self.name}: conv_bias true: the program's convolution "
                "layers carry no bias")

    def _derive_per_kind(self) -> None:
        """The `mimo_v2_flash` spellings folded into the program's fields,
        BEFORE the other checks read them, and what per-kind attention
        cannot run with."""
        def put(field, value):
            object.__setattr__(self, field, value)

        if self.n_shared_experts is None:
            put("n_shared_experts", 0)
        if self.routed_scaling_factor is None:
            put("routed_scaling_factor", 1.0)
        if self.topk_method is not None:
            if self.topk_method != "noaux_tc":
                raise ValueError(
                    f"{self.name}: topk_method {self.topk_method!r}: the "
                    "program implements only 'noaux_tc' (the top k by score "
                    "+ selection bias: use_expert_bias)")
            put("use_expert_bias", True)
        if self.hybrid_layer_pattern is not None:
            pattern = tuple(self.hybrid_layer_pattern)  # a file's list
            put("hybrid_layer_pattern", pattern)
            if set(pattern) - {0, 1}:
                raise ValueError(
                    f"{self.name}: hybrid_layer_pattern holds "
                    f"{sorted(set(pattern) - {0, 1})}: 0 a full_attention "
                    "layer, 1 a sliding_attention one")
            kinds = tuple(WINDOW if p else ATTENTION for p in pattern)
            if self.layer_types is not None \
                    and tuple(self.layer_types) != kinds:
                raise ValueError(
                    f"{self.name}: hybrid_layer_pattern does not agree with "
                    "layer_types (0 a full_attention layer, 1 a "
                    "sliding_attention one)")
            put("layer_types", kinds)
        if not isinstance(self.moe_layer_freq, int):
            freq = tuple(self.moe_layer_freq)  # a file's list
            put("moe_layer_freq", 1)  # ...folded: every layer after the
            dense = freq.index(1) if 1 in freq else len(freq)  # dense ones
            if set(freq) - {0, 1} or 0 in freq[dense:] \
                    or len(freq) != self.num_layers:
                raise ValueError(
                    f"{self.name}: moe_layer_freq {list(freq)}: one entry a "
                    "layer, 0 (a dense FFN) in the leading layers and 1 "
                    "(experts) in every layer after them")
            if self.num_dense_layers not in (0, dense):
                raise ValueError(
                    f"{self.name}: moe_layer_freq {list(freq)} is not "
                    f"num_dense_layers {self.num_dense_layers}")
            put("num_dense_layers", dense)
        for key in ("sliding_window_size", "attention_chunk_size"):
            if getattr(self, key) not in (None, self.sliding_window):
                raise ValueError(
                    f"{self.name}: {key} {getattr(self, key)} is not "
                    f"sliding_window {self.sliding_window}: the program "
                    "serves one window width")
        if self.add_full_attention_sink_bias:
            raise ValueError(
                f"{self.name}: add_full_attention_sink_bias true: the sink "
                "is served in the window layers' softmax only (ROADMAP B-M2)")
        per_kind = self.per_kind_attention
        if not per_kind and (self.add_swa_attention_sink_bias
                             or self.attention_value_scale is not None):
            raise ValueError(
                f"{self.name}: add_swa_attention_sink_bias / "
                "attention_value_scale belong to per-kind attention (the "
                "swa_* keys)")
        if not per_kind:
            return
        for key, field in (("swa_num_attention_heads", "num_heads"),
                           ("swa_head_dim", "head_dim"),
                           ("swa_v_head_dim", "v_head_dim")):
            if getattr(self, key) not in (None, getattr(self, field)):
                raise ValueError(
                    f"{self.name}: {key} {getattr(self, key)} is not {field} "
                    f"{getattr(self, field)}: the two attention kinds differ "
                    "in kv heads and RoPE base only")
        if self.kv_lora_rank or self.attn_bias or self.qk_norm \
                or self.attn_output_gate or self.mb_per_layer \
                or self.mamba_d_ssm or self.rope_layer_types is not None \
                or self.rope_theta is None or self.norm_order != "pre" \
                or set(self.layer_types or ()) - set(ATTENTION_KINDS):
            raise ValueError(
                f"{self.name}: per-kind attention (the swa_* keys) is served "
                "as plain K/V attention in a pre-norm stack of "
                "full_attention and sliding_attention layers, RoPE on both: "
                "no kv_lora_rank, attention bias, q/k norm, output gate, "
                "rope_layer_types or other layer kind")
        for kind in ATTENTION_KINDS:
            shape = self.attn_shape(kind)
            if shape.kv_heads < 1 or self.num_heads % shape.kv_heads \
                    or shape.v_dim < 1:
                raise ValueError(
                    f"{self.name}: {kind}: {self.num_heads} heads over "
                    f"{shape.kv_heads} kv heads of {shape.v_dim} value lanes")

    def _fold_router_spellings(self) -> None:
        """The `exaone_moe` spellings of the router's fields, folded into
        the program's."""
        for alias, field in (("num_shared_experts", "n_shared_experts"),
                             ("scoring_func", "router_score"),
                             ("moe_router_activation_func", "router_score"),
                             ("num_experts_per_token", "num_experts_per_tok"),
                             ("moe_renormalize", "norm_topk_prob"),
                             ("num_expert_group", "n_group"),
                             ("model_max_length", "max_seq_len"),
                             ("layernorm_epsilon", "rms_norm_eps")):
            value = getattr(self, alias)
            if value is None:
                continue
            if alias == "num_expert_group" and value == 1 \
                    and self.n_group <= 1 and self.topk_group <= 1:
                # one group that is always chosen: no group limit (and no
                # other reading when the config is rebuilt from itself)
                object.__setattr__(self, "n_group", 0)
                object.__setattr__(self, "topk_group", 0)
                continue
            default = type(self).__dataclass_fields__[field].default
            if getattr(self, field) not in (default, value):
                raise ValueError(
                    f"{self.name}: {alias} {value!r} is not {field} "
                    f"{getattr(self, field)!r}")
            object.__setattr__(self, field, value)
        if self.router_score not in ("softmax", "sigmoid"):
            raise ValueError(
                f"{self.name}: scoring_func must be 'softmax' or 'sigmoid', "
                f"got {self.router_score!r}")
        grouped, groups = (self.use_grouped_topk,
                           self.num_expert_group or self.n_group)
        if grouped is not None and bool(grouped) != (groups >= 1):
            raise ValueError(
                f"{self.name}: use_grouped_topk {grouped} with n_group "
                f"(num_expert_group) {groups}: grouped selection and its "
                "groups come together")
        if self.n_group == 1 and self.topk_group == 1:
            # one group that is always chosen: no group limit
            object.__setattr__(self, "n_group", 0)
            object.__setattr__(self, "topk_group", 0)

    def _check_window(self) -> None:
        """Window layers: the published keys that describe them agree."""
        for key in ("sliding_windows", "mlp_layer_types", "mtp_layer_types",
                    "mtp_sliding_windows", "rope_layer_types"):
            if getattr(self, key) is not None:  # a file's list: hashable
                object.__setattr__(self, key, tuple(getattr(self, key)))
        kinds = self.layer_types or (ATTENTION,) * self.num_layers
        has = WINDOW in kinds
        if has != (self.sliding_window > 0) or self.sliding_window < 0:
            raise ValueError(
                f"{self.name}: sliding_window {self.sliding_window} with "
                f"{'a' if has else 'no'} {WINDOW!r} layer in layer_types: "
                "the window layers and their width come together")
        if self.use_sliding_window and not has:
            raise ValueError(
                f"{self.name}: use_sliding_window {self.use_sliding_window}: "
                f"the program's window layers are {WINDOW!r} entries of "
                "layer_types, and there is none")
        if has and (self.kv_lora_rank or self.attn_output_gate):
            raise ValueError(
                f"{self.name}: {WINDOW!r} layers are served with plain K/V "
                "attention (no kv_lora_rank, no attn_output_gate)")
        want = tuple(self.sliding_window if k == WINDOW else 0 for k in kinds)
        if self.sliding_windows not in (None, want):
            raise ValueError(
                f"{self.name}: sliding_windows does not agree with "
                "layer_types and sliding_window (a window layer's entry is "
                "sliding_window, every other layer's 0)")
        pattern = self.sliding_window_pattern
        if pattern is not None:
            letters = {WINDOW: "L", ATTENTION: "G"}
            if not pattern or any(
                    letters.get(k) != pattern[i % len(pattern)]
                    for i, k in enumerate(kinds)):
                raise ValueError(
                    f"{self.name}: sliding_window_pattern {pattern!r} does "
                    "not agree with layer_types ('L' a sliding_attention "
                    "layer, 'G' a full_attention one, repeated)")
        if self.mlp_layer_types is not None:
            first = self.first_k_dense_replace
            if first is None:
                first = self.num_dense_layers
            want = tuple("dense" if i < first or not self.num_experts
                         else "sparse" for i in range(self.num_layers))
            if self.mlp_layer_types != want:
                raise ValueError(
                    f"{self.name}: mlp_layer_types does not agree with "
                    "first_k_dense_replace / num_dense_layers (the leading "
                    "layers 'dense', every other layer 'sparse')")
        if self.num_nextn_predict_layers and (
                self.mtp_layer_types or self.mtp_sliding_windows):
            raise ValueError(
                f"{self.name}: mtp_layer_types / mtp_sliding_windows with "
                "num_nextn_predict_layers "
                f"{self.num_nextn_predict_layers}: the prediction module is "
                "served for latent attention only (ROADMAP B-M6)")
        if self.rope_layer_types is not None and (
                self.rope_theta is None
                or set(self.rope_layer_types) - set(ATTENTION_KINDS)):
            raise ValueError(
                f"{self.name}: rope_layer_types {self.rope_layer_types}: "
                f"kinds of {list(ATTENTION_KINDS)} that rotate, with a "
                "rope_theta")

    def _check_latent(self) -> None:
        """What latent attention and its indexer cannot run with."""
        if self.q_lora_rank is None:  # the published null: a full-rank q
            object.__setattr__(self, "q_lora_rank", 0)
        widths = (self.qk_nope_head_dim, self.qk_rope_head_dim,
                  self.v_head_dim)
        has = (self.index_n_heads, self.index_head_dim, self.index_topk)
        if not self.kv_lora_rank:
            if self.per_kind_attention:  # v_head_dim: a plain value head's
                widths = widths[:2]
            if self.q_lora_rank or any(widths) or any(has) \
                    or self.mla_use_nope:
                raise ValueError(
                    f"{self.name}: q_lora_rank, qk_*_head_dim, v_head_dim, "
                    "mla_use_nope and index_* belong to latent attention: "
                    "kv_lora_rank is 0")
            return
        if min(widths) < 1 or self.q_lora_rank < 0 \
                or self.qk_rope_head_dim % 2:
            raise ValueError(
                f"{self.name}: latent attention needs qk_nope_head_dim, "
                "v_head_dim of at least 1, an even qk_rope_head_dim and a "
                "q_lora_rank of at least 1 (0 / null: a full-rank q); got "
                f"{widths + (self.q_lora_rank,)}")
        if self.head_dim != self.qk_nope_head_dim + self.qk_rope_head_dim:
            raise ValueError(
                f"{self.name}: head_dim {self.head_dim} is not "
                f"qk_nope_head_dim + qk_rope_head_dim")
        kinds = set(self.layer_types or ())
        if kinds and (kinds - {ATTENTION, LINEAR} or not self.kda):
            raise ValueError(
                f"{self.name}: layer_types {sorted(kinds)} with kv_lora_rank "
                f"{self.kv_lora_rank}: latent attention is served in every "
                f"layer, or as the {ATTENTION!r} layers of a stack whose "
                f"other layers are {LINEAR!r} ones read as Kimi Delta "
                "Attention (linear_attn_config) - beside no window, conv, "
                "mixer, mamba, sparse or lightning layer")
        if kinds and (any(has) or self.num_nextn_predict_layers):
            raise ValueError(
                f"{self.name}: index_* {has} / num_nextn_predict_layers "
                f"{self.num_nextn_predict_layers} with layer_types: neither "
                "an indexer nor a prediction module is served where latent "
                "attention is a layer kind beside linear_attention layers")
        if self.num_kv_heads != self.num_heads or self.attn_bias \
                or self.qk_norm or self.is_encoder \
                or self.norm_order != "pre" \
                or (self.rope_theta is None and not self.mla_use_nope):
            raise ValueError(
                f"{self.name}: latent attention is served with as many kv "
                "heads as heads, no attention bias, no q/k head norm, "
                "pre-norm and a rotary embedding (or mla_use_nope: none)")
        if self.mla_use_nope and (any(has) or self.rope_scaling):
            raise ValueError(
                f"{self.name}: mla_use_nope with index_* {has} / "
                "rope_scaling: the indexer's keys and YaRN's softmax scale "
                "belong to a rotary embedding")
        if any(has) and (min(has) < 1 or self.q_lora_rank < 1
                         or self.index_head_dim < self.qk_rope_head_dim):
            raise ValueError(
                f"{self.name}: an indexer needs index_n_heads, index_topk of "
                "at least 1, index_head_dim of at least qk_rope_head_dim "
                "and a q_lora_rank (its q reads the normed q latent) "
                f"(all three 0: no indexer); got {has}")

    def _check_sandwich_and_module(self) -> None:
        """Sandwich norms and the multi-token-prediction module."""
        if self.sandwich_norm and self.norm_order != "pre":
            raise ValueError(
                f"{self.name}: sandwich_norm adds an output norm to the "
                f"pre-norm block; norm_order is {self.norm_order!r}")
        n = self.num_nextn_predict_layers
        if n not in (0, 1):
            raise ValueError(
                f"{self.name}: num_nextn_predict_layers {n}: the program "
                "serves a prediction module of depth 1 (one draft a step), "
                "or none (0)")
        if n and not (self.kv_lora_rank and self.num_experts
                      and self.num_dense_layers < self.num_layers
                      and not self.index_topk):
            raise ValueError(
                f"{self.name}: num_nextn_predict_layers 1: the prediction "
                "module is one more latent-attention expert layer "
                "(kv_lora_rank, num_experts, a layer after the dense ones) "
                "and is served with no indexer (its block has rows in the "
                "latent pool only) and over one residual stream (hc_mult 0 "
                "/ 1: it reads one hidden a token)")

    def _check_streams(self) -> None:
        """A residual path of `hc_mult` streams: the stream counts the
        kernels are written for, and what it is not served beside."""
        if self.hc_mult not in (0, 1, HC_STREAMS):
            raise ValueError(
                f"{self.name}: hc_mult {self.hc_mult}: the program serves "
                f"one residual stream (0 / 1) or {HC_STREAMS} "
                "(ops/pallas/hyper_connection.py lays a token's "
                f"{HC_STREAMS} x {HC_STREAMS} matrix on {HC_STREAMS ** 2} "
                "lanes)")
        if self.hc_sinkhorn_iters < 1:
            raise ValueError(
                f"{self.name}: hc_sinkhorn_iters {self.hc_sinkhorn_iters}: "
                "at least one Sinkhorn-Knopp iteration makes H_res")
        if not self.hc_eps > 0 \
                or not self.mhc_h_res_clamp_min < self.mhc_h_res_clamp_max:
            raise ValueError(
                f"{self.name}: hc_eps {self.hc_eps} / mhc_h_res_clamp_min "
                f"{self.mhc_h_res_clamp_min} / mhc_h_res_clamp_max "
                f"{self.mhc_h_res_clamp_max}: a positive epsilon and a clamp "
                "whose ends are in order")
        if not self.streams:
            return
        if self.num_nextn_predict_layers:
            raise ValueError(
                f"{self.name}: num_nextn_predict_layers "
                f"{self.num_nextn_predict_layers} with hc_mult "
                f"{self.hc_mult}: the prediction module reads ONE hidden a "
                "token (forward_mtp's `hidden [T, D]`), and how it joins "
                f"{self.hc_mult} streams is not in config.json "
                "(ROADMAP B-M12)")
        if self.norm_order != "pre" or self.sandwich_norm \
                or self.residual_multiplier != 1.0 or self.mb_per_layer \
                or self.is_encoder:
            raise ValueError(
                f"{self.name}: hc_mult {self.hc_mult}: the streams are mixed "
                "around pre-norm sublayers with no second norm, no residual "
                "multiplier, no `exit_layer` gather (mb_per_layer) and no "
                "encoder (ROADMAP B-M12)")

    def _check_share(self) -> None:
        """Group-limited routing, shared experts and the share held here."""
        R, E = self.router_width, self.num_experts
        if (self.n_group or self.n_shared_experts or self.router_experts
                or self.expert_offset) and not E:
            raise ValueError(
                f"{self.name}: n_group, n_shared_experts, router_experts "
                "and expert_offset belong to an expert layer: num_experts "
                "is 0")
        if self.n_group:
            if R % self.n_group or not 1 <= self.topk_group <= self.n_group \
                    or self.topk_group * (R // self.n_group) \
                    < self.num_experts_per_tok or R // self.n_group < 2:
                raise ValueError(
                    f"{self.name}: n_group {self.n_group} / topk_group "
                    f"{self.topk_group} do not divide the router's {R} "
                    f"experts into groups that hold the top "
                    f"{self.num_experts_per_tok}")
        if E and not 0 <= self.expert_offset <= R - E:
            raise ValueError(
                f"{self.name}: expert_offset {self.expert_offset}: the "
                f"{E} experts held here are not within the router's {R}")

    def _check_gated(self) -> None:
        """The partial rotary embedding, the attention output gate, the
        shared expert's own width and gate, `full_attention_interval`."""
        # (floored, as the published modelling code does: 0.334 x 192 = 64)
        rot = int(self.head_dim * self.partial_rotary_factor)
        if not 0 < self.partial_rotary_factor <= 1 or rot < 2 or rot % 2:
            raise ValueError(
                f"{self.name}: partial_rotary_factor "
                f"{self.partial_rotary_factor} of head_dim {self.head_dim} "
                "is not an even number of lanes (floored), 2 at the least")
        if self.kv_lora_rank and (self.partial_rotary_factor != 1
                                  or self.attn_output_gate
                                  or self.zero_centred_norm):
            raise ValueError(
                f"{self.name}: partial_rotary_factor, attn_output_gate and "
                "zero_centred_norm are not served with latent attention "
                "(kv_lora_rank)")
        if self.attn_output_gate and self.attn_bias:
            raise ValueError(
                f"{self.name}: attn_output_gate with attn_bias: the gate's "
                "projection carries no bias")
        if (self.shared_expert_intermediate_size
                or self.shared_expert_gate) and not self.num_experts:
            raise ValueError(
                f"{self.name}: shared_expert_intermediate_size and "
                "shared_expert_gate belong to an expert layer: num_experts "
                "is 0")
        if self.shared_expert_intermediate_size and self.n_shared_experts:
            raise ValueError(
                f"{self.name}: shared_expert_intermediate_size "
                f"{self.shared_expert_intermediate_size} AND "
                f"n_shared_experts {self.n_shared_experts}: the shared "
                "expert has one width")
        if self.shared_expert_gate and not self.shared_width:
            raise ValueError(
                f"{self.name}: shared_expert_gate with no shared expert")
        n = self.full_attention_interval
        if n is not None:
            want = tuple(ATTENTION if (i + 1) % n == 0 else LINEAR
                         for i in range(self.num_layers)) if n >= 1 else None
            if self.layer_types != want:
                raise ValueError(
                    f"{self.name}: full_attention_interval {n} does not "
                    "agree with layer_types (attention in each n-th layer, "
                    "linear_attention in the others)")

    def _derive_mixers(self) -> None:
        """`mixer_types`: the published list folded into `layer_types`, and
        the published spellings of fields the program has under another
        name folded into those, BEFORE the other checks read them."""
        if self.mixer_types is not None:
            mixers = tuple(self.mixer_types)  # a file's list: hashable
            object.__setattr__(self, "mixer_types", mixers)
            unknown = sorted(set(mixers) - set(MIXER_KINDS))
            if unknown:
                raise ValueError(
                    f"{self.name}: mixer_types holds {unknown}; the program "
                    f"runs {sorted(MIXER_KINDS)}")
            want = tuple(MIXER_KINDS[m] for m in mixers)
            if self.layer_types is not None \
                    and tuple(self.layer_types) != want:
                raise ValueError(
                    f"{self.name}: layer_types does not agree with "
                    f"mixer_types ({MIXER_KINDS})")
            object.__setattr__(self, "layer_types", want)
        gate = self.attn_use_output_gate
        if gate is not None:
            if self.attn_output_gate and not gate:
                raise ValueError(
                    f"{self.name}: attn_use_output_gate {gate} is not "
                    f"attn_output_gate {self.attn_output_gate}")
            object.__setattr__(self, "attn_output_gate", bool(gate))
        for alias, field, value in (
                ("scale_emb", "embedding_multiplier", self.scale_emb),
                ("dim_model_base", "lm_head_multiplier",
                 self.dim_model_base / self.hidden_size)):
            if getattr(self, alias) in (0, 1.0):
                continue
            if getattr(self, field) not in (1.0, value):
                raise ValueError(
                    f"{self.name}: {alias} {getattr(self, alias)} is not "
                    f"{field} {getattr(self, field)}")
            object.__setattr__(self, field, value)

    def _derive_kda(self) -> None:
        """`linear_attn_config`: the published group folded into
        `layer_types` and the linear kind's fields BEFORE the other checks
        read them, refused on disagreement with key and value; and the
        family's published `head_dim` (hidden_size / heads, which no
        published module reads) folded into what latent attention's
        `head_dim` is here: qk_nope_head_dim + qk_rope_head_dim."""
        if self.linear_attn_config is None:
            return
        group = dict(self.linear_attn_config)  # a file's dict: hashable
        keys = ("full_attn_layers", "head_dim", "kda_layers", "num_heads",
                "short_conv_kernel_size")
        if sorted(group) != list(keys):
            raise ValueError(
                f"{self.name}: linear_attn_config holds {sorted(group)}; the "
                f"program reads {list(keys)}")
        kda, full = (tuple(group[k]) for k in ("kda_layers",
                                               "full_attn_layers"))
        if sorted(kda + full) != list(range(1, self.num_layers + 1)):
            raise ValueError(
                f"{self.name}: linear_attn_config kda_layers {list(kda)} and "
                f"full_attn_layers {list(full)} do not name each of layers "
                f"1..{self.num_layers} (num_hidden_layers) once")
        want = tuple(LINEAR if i + 1 in kda else ATTENTION
                     for i in range(self.num_layers))
        if self.layer_types is not None and tuple(self.layer_types) != want:
            raise ValueError(
                f"{self.name}: layer_types {list(self.layer_types)} does not "
                "agree with linear_attn_config (kda_layers "
                f"{list(kda)}: {LINEAR!r}, full_attn_layers {list(full)}: "
                f"{ATTENTION!r}, counted from 1)")
        defaults = type(self).__dataclass_fields__
        for field, key in (("linear_num_key_heads", "num_heads"),
                           ("linear_num_value_heads", "num_heads"),
                           ("linear_key_head_dim", "head_dim"),
                           ("linear_value_head_dim", "head_dim"),
                           ("linear_conv_kernel_dim",
                            "short_conv_kernel_size")):
            if getattr(self, field) not in (defaults[field].default,
                                            group[key]):
                raise ValueError(
                    f"{self.name}: {field} {getattr(self, field)} is not "
                    f"linear_attn_config's {key} {group[key]}")
            object.__setattr__(self, field, group[key])
        object.__setattr__(self, "layer_types", want)
        object.__setattr__(self, "linear_attn_config", tuple(sorted(
            (k, tuple(v) if isinstance(v, (list, tuple)) else v)
            for k, v in group.items())))
        if self.lightning_nh or self.linear_allow_neg_eigval:
            raise ValueError(
                f"{self.name}: linear_attn_config with lightning_nh "
                f"{self.lightning_nh} / linear_allow_neg_eigval "
                f"{self.linear_allow_neg_eigval}: the linear kind has one "
                "reading, and Kimi Delta Attention's b is a sigmoid")
        latent = self.qk_nope_head_dim + self.qk_rope_head_dim
        if self.kv_lora_rank and self.head_dim != latent:
            if self.head_dim * self.num_heads != self.hidden_size:
                raise ValueError(
                    f"{self.name}: head_dim {self.head_dim} is neither "
                    f"qk_nope_head_dim + qk_rope_head_dim = {latent} (what "
                    "latent attention's q and k are wide) nor the "
                    f"published hidden_size / num_attention_heads = "
                    f"{self.hidden_size // self.num_heads} (which no "
                    "module reads)")
            object.__setattr__(self, "head_dim", latent)

    @property
    def kda(self) -> bool:
        """Are the linear_attention layers Kimi Delta Attention?"""
        return self.linear_attn_config is not None

    def _check_sparse(self) -> None:
        """Block-sparse and lightning layers: the sizes held to each other, a
        value the program does not implement refused by the key's name."""
        kinds = self.layer_types or ()
        sizes = {key: getattr(self, key) for key in (
            "sparse_kernel_size", "sparse_kernel_stride", "sparse_block_size",
            "sparse_topk", "sparse_init_blocks", "sparse_window_size",
            "sparse_dense_len")}
        if SPARSE not in kinds:
            if any(sizes.values()):
                raise ValueError(
                    f"{self.name}: {sorted(k for k, v in sizes.items() if v)} "
                    f"with no {SPARSE!r} layer in layer_types")
        else:
            kernel, stride, block = (self.sparse_kernel_size,
                                     self.sparse_kernel_stride,
                                     self.sparse_block_size)
            local = self.sparse_window_size // max(block, 1)
            if min(sizes.values()) < 1 or kernel != 2 * stride \
                    or block % stride or block // stride != 4 \
                    or self.sparse_window_size % block \
                    or self.sparse_init_blocks + local > self.sparse_topk \
                    or self.sparse_dense_len < self.sparse_topk * block:
                raise ValueError(
                    f"{self.name}: {sizes}: a sparse_attention layer is "
                    "served with a pooling kernel of two strides, a block of "
                    "four strides (the scores' max-pool: window 5, stride 4, "
                    "padding 1), a window of whole blocks that fits "
                    "sparse_topk beside the init blocks, and a "
                    "sparse_dense_len of at least sparse_topk blocks")
            if set(kinds) & {ATTENTION, WINDOW, PARALLEL} or self.kv_lora_rank \
                    or self.is_encoder or self.num_nextn_predict_layers \
                    or self.qk_norm_kind == "full" or self.attn_bias \
                    or self.norm_order != "pre":
                raise ValueError(
                    f"{self.name}: {SPARSE!r} layers are served as the "
                    "stack's only attention kind, with plain K/V heads, no "
                    "bias, no full-width q/k norm, pre-norm blocks and no "
                    "prediction module")
        if not self.lightning_nh:
            if self.layer_offset:
                raise ValueError(
                    f"{self.name}: layer_offset {self.layer_offset}: only a "
                    "lightning layer's decay reads it (lightning_nh is 0)")
            return
        d = self.lightning_head_dim
        if LINEAR not in kinds or self.lightning_nkv != self.lightning_nh \
                or d < 2 or d % 2:
            raise ValueError(
                f"{self.name}: lightning_nh {self.lightning_nh} / "
                f"lightning_nkv {self.lightning_nkv} / lightning_head_dim "
                f"{d}: lightning attention is the linear_attention layers', "
                "with as many key/value heads as heads and an even head size")
        for key, only in (("lightning_scale", "1/sqrt(d)"),
                          ("use_output_norm", True), ("use_output_gate", True),
                          ("rand_init", False)):
            if getattr(self, key) != only:
                raise ValueError(
                    f"{self.name}: {key} {getattr(self, key)!r}: the program "
                    f"implements only {only!r}")
        if self.qk_norm_kind != "head" or CONV in kinds or self.mamba_d_ssm \
                or self.mb_per_layer or (self.lightning_use_rope
                                         and self.rope_theta is None):
            raise ValueError(
                f"{self.name}: lightning attention is served with a per-head "
                "q/k norm (qk_norm true), a rope_theta where "
                "lightning_use_rope is true, and no conv, mixer or mamba "
                "layer beside it")
        if self.layer_offset < 0 or \
                self.layer_offset + self.num_layers > self.published_depth:
            raise ValueError(
                f"{self.name}: layer_offset {self.layer_offset}: the stack's "
                f"{self.num_layers} layers are not within the published "
                f"{self.published_depth} (scale_depth_layers)")

    def _check_linear(self) -> None:
        """What the linear-attention layers cannot run with, key and value
        in the message."""
        hk, hv = self.linear_num_key_heads, self.linear_num_value_heads
        if hk < 1 or self.linear_key_head_dim < 1 \
                or self.linear_value_head_dim < 1:
            raise ValueError(
                f"{self.name}: linear_attention layers need "
                f"linear_num_key_heads ({hk}), linear_key_head_dim "
                f"({self.linear_key_head_dim}) and linear_value_head_dim "
                f"({self.linear_value_head_dim}) of at least 1")
        if hv < hk or hv % hk:
            raise ValueError(
                f"{self.name}: linear_num_value_heads {hv} is not a "
                f"multiple of linear_num_key_heads {hk}: a key head serves "
                "a whole number of value heads")
        if self.linear_conv_kernel_dim < 2:
            raise ValueError(
                f"{self.name}: linear_conv_kernel_dim must be at least 2, "
                f"got {self.linear_conv_kernel_dim}")

    def _derive_hybrid(self) -> None:
        """`mb_per_layer`: the stack's `layer_types` and what the family's
        modelling code fixes beside them, derived BEFORE the other checks
        read them; a value the program does not implement refused by the
        key's name."""
        for key, only in (("lm_head_bias", False), ("embd_pdrop", 0),
                          ("resid_pdrop", 0)):
            if getattr(self, key) != only:
                raise ValueError(
                    f"{self.name}: {key} {getattr(self, key)}: the program "
                    f"implements only {only}")
        if not self.mb_per_layer:
            if self.layer_norm_eps is not None:
                raise ValueError(
                    f"{self.name}: layer_norm_eps {self.layer_norm_eps} "
                    "without mb_per_layer: the program's LayerNorm stacks "
                    "are the decoder-hybrid-decoder family's")
            held = set(self.layer_types or ()) & {MAMBA, GMU, CROSS}
            if held:
                raise ValueError(
                    f"{self.name}: layer_types holds {sorted(held)} and "
                    "mb_per_layer is 0: those kinds come from it")
            return
        if self.mb_per_layer != 2:
            raise ValueError(
                f"{self.name}: mb_per_layer {self.mb_per_layer}: the program "
                "implements only 0 (no such stack) and 2 (a mamba layer, "
                "then an attention layer)")
        if self.num_layers % 4 or self.num_layers < 8:
            raise ValueError(
                f"{self.name}: num_hidden_layers (num_layers) "
                f"{self.num_layers} with mb_per_layer 2: a multiple of 4, "
                "at least 8 (window layers below the full layer, cross "
                "layers above it)")
        if self.mlp_bias:
            raise ValueError(
                f"{self.name}: mlp_bias true: the program implements only "
                "false")
        want = hybrid_layer_types(self.num_layers)
        if self.layer_types is not None and tuple(self.layer_types) != want:
            raise ValueError(
                f"{self.name}: layer_types does not agree with what "
                f"mb_per_layer 2 derives for {self.num_layers} layers: "
                f"{list(want)}")
        if self.num_heads % 2 or self.num_kv_heads % 2 \
                or (self.num_heads // 2) % (self.num_kv_heads // 2):
            raise ValueError(
                f"{self.name}: num_heads {self.num_heads} / num_kv_heads "
                f"{self.num_kv_heads}: differential attention pairs "
                "adjacent heads, and a pair of K/V heads serves a whole "
                "number of query pairs")
        if self.is_encoder or self.num_experts or self.kv_lora_rank \
                or self.norm_order != "pre" or self.sandwich_norm \
                or self.qk_norm or self.attn_output_gate \
                or self.mamba_d_ssm or not self.tie_embeddings \
                or self.num_nextn_predict_layers:
            raise ValueError(
                f"{self.name}: mb_per_layer 2: the stack is served as "
                "causal pre-norm blocks with plain K/V differential "
                "attention, a dense MLP and a tied head only")
        for key, value in (
                ("layer_types", want), ("attn_bias", True),
                ("rope_theta", None),  # no position encoding anywhere
                ("layer_norm_eps", 1e-5 if self.layer_norm_eps is None
                 else self.layer_norm_eps)):
            object.__setattr__(self, key, value)

    def _check_ssm(self) -> None:
        """The state-space mixer's keys: the lists made hashable, a value the
        program does not implement refused by the key's name, the sizes held
        to each other."""
        for key, n in (("ssm_multipliers", 5), ("mlp_multipliers", 2)):
            value = tuple(getattr(self, key))  # a file's list: hashable
            object.__setattr__(self, key, value)
            if len(value) != n:
                raise ValueError(
                    f"{self.name}: {key} holds {len(value)} scalars, the "
                    f"program applies {n}")
        if self.attn_layer_indices is not None:
            raise ValueError(
                f"{self.name}: attn_layer_indices "
                f"{list(self.attn_layer_indices)}: the program implements "
                "only null (every layer attends)")
        for key, only in (("mamba_proj_bias", False),
                          ("projectors_bias", False), ("mlp_bias", False),
                          ("mamba_rms_norm", True),
                          ("mamba_norm_before_gate", False),
                          ("mamba_use_mlp", True), ("num_logits_to_keep", 1)):
            if getattr(self, key) != only:
                raise ValueError(
                    f"{self.name}: {key} {getattr(self, key)}: the program "
                    f"implements only {only}")
        parallel = self.layer_types is not None \
            and PARALLEL in self.layer_types
        if not self.mamba_d_ssm:
            if parallel:
                raise ValueError(
                    f"{self.name}: layer_types holds {PARALLEL!r} and "
                    "mamba_d_ssm is 0: the mixer has no width")
            return
        if self.layer_types is not None \
                and set(self.layer_types) != {PARALLEL}:
            raise ValueError(
                f"{self.name}: mamba_d_ssm {self.mamba_d_ssm} with "
                f"layer_types {sorted(set(self.layer_types))}: a model with "
                f"a state-space mixer has {PARALLEL!r} in every layer")
        if self.mamba_n_heads * self.mamba_d_head != self.mamba_d_ssm:
            raise ValueError(
                f"{self.name}: mamba_n_heads {self.mamba_n_heads} x "
                f"mamba_d_head {self.mamba_d_head} is not mamba_d_ssm "
                f"{self.mamba_d_ssm}")
        if self.mamba_d_state < 1 or self.mamba_n_groups < 1 \
                or self.mamba_n_heads % self.mamba_n_groups:
            raise ValueError(
                f"{self.name}: mamba_d_state {self.mamba_d_state} and "
                f"mamba_n_groups {self.mamba_n_groups} must be at least 1, "
                f"and mamba_n_heads {self.mamba_n_heads} a multiple of the "
                "groups: a group of B and C serves a whole number of heads")
        if self.mamba_d_conv < 2:
            raise ValueError(
                f"{self.name}: mamba_d_conv must be at least 2, got "
                f"{self.mamba_d_conv}")
        if self.is_encoder or self.num_experts or self.kv_lora_rank \
                or self.norm_order != "pre" or self.sandwich_norm:
            raise ValueError(
                f"{self.name}: mamba_d_ssm {self.mamba_d_ssm}: the parallel "
                "layer is served as a causal pre-norm block with K/V "
                "attention and a dense MLP only")

    # -- the stack, layer by layer ------------------------------------------
    @property
    def kinds(self) -> tuple:
        """Each layer's (operator, FFN): (ATTENTION | CONV | LINEAR | WINDOW |
        PARALLEL, DENSE | EXPERTS)."""
        ops = self.layer_types or (
            PARALLEL if self.mamba_d_ssm else ATTENTION,) * self.num_layers
        first_sparse = self.num_dense_layers if self.num_experts \
            else self.num_layers
        return tuple((op, DENSE if i < first_sparse else EXPERTS)
                     for i, op in enumerate(ops))

    def count(self, kind: str) -> int:
        """Layers whose operator or FFN is `kind`."""
        return sum(kind in pair for pair in self.kinds)

    @property
    def attn_layers(self) -> int:
        """Layers that hold attention over K and V — window, full, or beside
        a state-space mixer: the entries of the attention weights' stacks."""
        return sum(self.count(kind) for kind in ATTENTION_KINDS) \
            + self.count(PARALLEL) + self.count(SPARSE)

    @property
    def paged_layers(self) -> int:
        """Layers whose K and V rows live in the paged pool (PAGED_KINDS)."""
        return sum(self.count(kind) for kind in PAGED_KINDS)

    @property
    def exit_layer(self) -> int:
        """The first layer that holds no state and writes no cache — from
        it on a row that nobody samples has nothing to give — or 0: every
        row passes every layer."""
        return self.num_layers // 2 + 2 if self.mb_per_layer else 0

    @property
    def s6_inner(self) -> int:
        """Channels of a mamba layer's scan (and of m, which the GMUs read)."""
        return S6_EXPAND * self.hidden_size

    @property
    def s6_dt_rank(self) -> int:
        return -(-self.hidden_size // 16)

    def rotates(self, kind: str) -> bool:
        """Do q and k of an attention layer of `kind` take RoPE?"""
        return self.rope_theta is not None and self.attn_use_rope and (
            self.rope_layer_types is None or kind in self.rope_layer_types)

    @property
    def published_depth(self) -> int:
        """Layers of the published stack (`scale_depth_layers`), of which
        this one may be a stage."""
        return self.scale_depth_layers or self.num_layers

    @property
    def residual_multiplier(self) -> float:
        """What a sublayer's output is multiplied by before it joins the
        residual: `scale_depth` / sqrt(the published depth), or 1."""
        if not self.scale_depth:
            return 1.0
        return self.scale_depth / math.sqrt(self.published_depth)

    def lightning_level(self, layer):
        """1 - l / (L - 1) + 1e-5: what scales a lightning layer's slopes, l
        the PUBLISHED index of the layer at `layer` of this stack (an int,
        or a traced int32 scalar inside a layer loop), L the published
        depth."""
        return 1.0 - (self.layer_offset + layer) \
            / max(self.published_depth - 1, 1) + 1e-5

    @property
    def lightning_slopes(self) -> tuple:
        """A lightning layer's decay, -log(lambda) a head, one tuple a
        linear_attention layer in stack order: the Lightning Attention
        slopes 2^(-8 (h + 1) / H) times `lightning_level` of the layer."""
        H = self.lightning_nh
        base = [2.0 ** (-8.0 * (h + 1) / H) for h in range(H)]
        return tuple(tuple(b * self.lightning_level(i) for b in base)
                     for i, (op, _) in enumerate(self.kinds) if op == LINEAR)

    @property
    def sparse_local_blocks(self) -> int:
        return self.sparse_window_size // self.sparse_block_size

    def pooled_rows(self, num_pages: int, page_size: int) -> int:
        """Rows of a sparse layer's pooled-key pool: a page's
        `page_size / sparse_kernel_stride` rows, page p's at p * that — the
        page table names them as it names K and V rows."""
        if not self.count(SPARSE):
            return 0
        if page_size % self.sparse_kernel_stride \
                or self.sparse_block_size % page_size:
            raise ValueError(
                f"{self.name}: --page-size {page_size}: a page holds whole "
                f"pooling strides ({self.sparse_kernel_stride}) and a block "
                f"({self.sparse_block_size}) whole pages")
        return num_pages * (page_size // self.sparse_kernel_stride)

    def ring_rows(self, max_span: int, page_size: int) -> int:
        """Rows of a slot's ring a window layer: whole pages that hold the
        window, the longest span a step writes (`max_span` tokens: its
        first query still sees the window before it) and one page (a walk
        starts at a page boundary)."""
        need = self.sliding_window + max_span + page_size
        return -(-need // page_size) * page_size

    @property
    def streams(self) -> int:
        """Residual streams a token, where there are several (`hc_mult` > 1);
        0 for the one-stream path, on which nothing of them is traced."""
        return self.hc_mult if self.hc_mult > 1 else 0

    @property
    def hc_maps(self) -> int:
        """Lanes of a sublayer's mapping product: H_pre | H_post | H_res."""
        return 2 * self.streams + self.streams ** 2

    @property
    def expert_width(self) -> int:
        return self.moe_intermediate_size or self.intermediate_size

    @property
    def shared_width(self) -> int:
        """Width of the SwiGLU every token passes (0: no shared expert)."""
        return self.shared_expert_intermediate_size \
            or self.n_shared_experts * self.expert_width

    @property
    def rotary_dim(self) -> int:
        """Lanes of a head that RoPE rotates: the first ones."""
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def router_width(self) -> int:
        """Experts the router scores (`num_experts` of them are held here)."""
        return self.router_experts or self.num_experts

    @property
    def latent_dim(self) -> int:
        """Lanes of a token's row of the latent pool: [c_kv | k_rope]."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_lanes(self) -> int:
        """...and the lanes that row occupies: whole tiles of 128. The
        TPU's tiled layout pads the pool's last axis to that whatever its
        shape says, and a kernel cannot copy part of a lane tile out of
        HBM, so the pool states the lanes it holds (zeros past
        `latent_dim`)."""
        return -(-self.latent_dim // 128) * 128

    @property
    def cache_layers(self) -> int:
        """Layers of the paged pools: the FULL attention layers (a window
        layer's rows live in its slots' rings), and behind them the
        prediction module's block (its own cache rows)."""
        return self.paged_layers + self.num_nextn_predict_layers

    @property
    def per_kind_attention(self) -> bool:
        """Do the window and the full layers differ in head shape (any
        `swa_*` key)? Their weights are then stacks of their own."""
        return any(getattr(self, key) is not None for key in (
            "swa_num_attention_heads", "swa_num_key_value_heads",
            "swa_head_dim", "swa_v_head_dim", "swa_rope_theta"))

    def attn_shape(self, kind: str = ATTENTION) -> AttnShape:
        """The head shape of an attention layer of `kind` (plain K/V
        attention): one for every kind unless `per_kind_attention`."""
        v_dim = (self.v_head_dim if self.per_kind_attention else 0) \
            or self.head_dim
        if kind == WINDOW and self.per_kind_attention:
            return AttnShape(
                self.num_heads,
                self.swa_num_key_value_heads or self.num_kv_heads,
                self.head_dim, v_dim,
                self.rope_theta if self.swa_rope_theta is None
                else self.swa_rope_theta, self.add_swa_attention_sink_bias)
        return AttnShape(self.num_heads, self.num_kv_heads, self.head_dim,
                         v_dim, self.rope_theta, False)

    @property
    def kv_row_dims(self) -> tuple:
        """Lanes a token a layer in each of the two paged pools: K and V
        rows (a full layer's: a window layer's live in the rings,
        `ring_row_dims`), or with latent attention the latent row and the
        index key (0 lanes: no indexer, no second pool)."""
        if self.kv_lora_rank:
            return self.latent_lanes, self.index_head_dim
        shape = self.attn_shape(ATTENTION)
        return shape.k_lanes, shape.v_lanes

    @property
    def ring_row_dims(self) -> tuple:
        """Lanes a position a window layer in the K ring and in the V ring."""
        shape = self.attn_shape(WINDOW)
        return shape.k_lanes, shape.v_lanes

    @property
    def yarn(self) -> Optional[dict]:
        return dict(self.rope_scaling) if self.rope_scaling else None

    @property
    def attn_scale(self) -> float:
        """The softmax scale: head_dim^-1/2, times YaRN's mscale^2."""
        scale = self.head_dim ** -0.5
        y = self.yarn
        if y and y["factor"] > 1:
            m = 0.1 * y.get("mscale_all_dim", 0) * math.log(y["factor"]) + 1.0
            scale *= m * m
        return scale

    def layer_plan(self) -> tuple:
        """The stack as runs of a repeated period: ((first layer, period,
        repeats), ...) with `period` a tuple of layer kinds. The forwards
        scan each run (`models/llama.py:scan_layers`) with the period's
        layers unrolled in the scan's body, so a program traces each
        DISTINCT layer of a period once, whatever the depth. Greedy: at
        each layer the (period <= MAX_PERIOD, repeats >= 2) that covers
        the most layers, the shortest period among equals; a layer that
        starts no repetition is a run of its own. A uniform stack is one
        run of period 1."""
        kinds, runs, i = self.kinds, [], 0
        while i < len(kinds):
            best = (1, 1)  # (period, repeats)
            for p in range(1, min(MAX_PERIOD, (len(kinds) - i) // 2) + 1):
                r = 1
                while kinds[i + r * p: i + (r + 1) * p] == kinds[i: i + p]:
                    r += 1
                if r > 1 and p * r > best[0] * best[1]:
                    best = (p, r)
            runs.append((i, kinds[i: i + best[0]], best[1]))
            i += best[0] * best[1]
        return tuple(runs)

    @property
    def qk_norm_kind(self) -> Optional[str]:
        """None, "head" (per head) or "full" (whole projected vector)."""
        if not self.qk_norm:
            return None
        return "full" if self.qk_norm == "full" else "head"

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def linear_key_dim(self) -> int:
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def linear_value_dim(self) -> int:
        return self.linear_num_value_heads * self.linear_value_head_dim

    @property
    def linear_conv_dim(self) -> int:
        """Channels of a linear layer's convolution: q | k | v."""
        return 2 * self.linear_key_dim + self.linear_value_dim

    @property
    def ssm_conv_dim(self) -> int:
        """Channels of the state-space mixer's convolution: x | B | C."""
        return self.mamba_d_ssm + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def ssm_in_dim(self) -> int:
        """Lanes of the mixer's published in-projection: z | x | B | C | dt
        (the last `mamba_n_heads` of them are held as a stack of their
        own: models/llama.py:_ssm_op)."""
        return self.mamba_d_ssm + self.ssm_conv_dim + self.mamba_n_heads

    @property
    def state_window(self) -> tuple:
        """(taps, channels) of the per-slot conv window of this model's
        conv, linear-attention or parallel layers (config refuses a stack
        with two of them)."""
        if self.count(PARALLEL):
            return self.mamba_d_conv, self.ssm_conv_dim
        if self.count(MAMBA):
            return S6_D_CONV, self.s6_inner
        if self.count(LINEAR) and not self.lightning_nh:
            return self.linear_conv_kernel_dim, self.linear_conv_dim
        return self.conv_L_cache, self.hidden_size

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim

    def param_count(self, active: bool = False,
                    first_layers: Optional[int] = None) -> int:
        """Approximate parameter count (for HBM budgeting); with `active`,
        the parameters a token touches (the routed experts of the k, not
        the bank: what the FLOPs model counts); with `first_layers`, of a
        token that passes only that many layers (a stack with an
        `exit_layer`)."""
        d, v = self.hidden_size, self.vocab_size
        attention = ((1 + self.attn_output_gate) * d * self.q_dim
                     + 2 * d * self.kv_dim + self.q_dim * d
                     + self.qk_norm_params())
        if self.kv_lora_rank:
            H, r, c = self.num_heads, self.q_lora_rank, self.kv_lora_rank
            attention = (
                (d * r + r + r * self.q_dim if r else d * self.q_dim)
                + d * self.latent_dim + c
                + c * H * (self.qk_nope_head_dim + self.v_head_dim)
                + H * self.v_head_dim * d)
            if self.index_topk:
                # the indexer: q, k with its LayerNorm, the head weights
                attention += (
                    r * self.index_n_heads * self.index_head_dim
                    + (d + 2) * self.index_head_dim + d * self.index_n_heads)
        di, lanes = self.s6_inner, 2 * self.head_dim
        if self.mb_per_layer:
            # the biases of q | k | v and of the out-projection, the four
            # lambda vectors and the pair norm's weight — and a cross layer
            # the same without k and v
            attention += self.q_dim + 2 * self.kv_dim + d \
                + 4 * self.head_dim + lanes
        cross = attention - 2 * (d + 1) * self.kv_dim
        ld = self.lightning_nh * self.lightning_head_dim
        window = attention
        if self.per_kind_attention:  # q, k, v, o of each kind's own shape
            attention, window = (
                d * a.q_lanes + d * (a.k_lanes + a.v_lanes) + a.o_lanes * d
                + a.heads * a.sink
                for a in map(self.attn_shape, ATTENTION_KINDS))
        per_op = {
            ATTENTION: attention, WINDOW: window, CROSS: cross,
            SPARSE: attention,
            GMU: 2 * d * di,
            # in [x | z], the taps and their bias, x -> [dt | B | C], dt's
            # projection and bias, A_log, D, out
            MAMBA: (d * 2 * di + di * (S6_D_CONV + 1)
                    + di * (self.s6_dt_rank + 2 * S6_D_STATE)
                    + (self.s6_dt_rank + 1) * di + di * S6_D_STATE + di
                    + di * d),
            CONV: 3 * d * d + d * d + d * self.conv_L_cache,
            # q | k | v | z and the two gates in, the taps, A_log and
            # dt_bias, the output norm, out.
            # (lightning: q | k | v | the gate in, out, the three norms)
            # (Kimi Delta Attention: q | k | v in, the taps, the decay's and
            # the output gate's two low-rank matrices through
            # `linear_key_head_dim` lanes, dt_bias a key channel, A_log and
            # b's projection a head, the output norm, out)
            LINEAR: (5 * d * ld + 2 * self.lightning_head_dim + ld
                     if self.lightning_nh else
                     d * self.linear_conv_dim
                     + self.linear_conv_dim * self.linear_conv_kernel_dim
                     + self.linear_key_head_dim * (
                         2 * d + self.linear_key_dim + self.linear_value_dim)
                     + self.linear_key_dim
                     + (d + 1) * self.linear_num_value_heads
                     + self.linear_value_head_dim + self.linear_value_dim * d
                     if self.kda else
                     d * (self.linear_conv_dim + self.linear_value_dim
                          + 2 * self.linear_num_value_heads)
                     + self.linear_conv_dim * self.linear_conv_kernel_dim
                     + 2 * self.linear_num_value_heads
                     + self.linear_value_head_dim
                     + self.linear_value_dim * d),
            # attention, and beside it the mixer: in, the taps and their
            # bias, A_log, D and dt_bias a head, the grouped norm, out.
            PARALLEL: (attention + d * self.ssm_in_dim
                       + self.ssm_conv_dim * (self.mamba_d_conv + 1)
                       + 3 * self.mamba_n_heads + self.mamba_d_ssm
                       + self.mamba_d_ssm * d),
        }
        n_experts = self.num_experts_per_tok if active else self.num_experts
        per_ffn = {
            DENSE: 3 * d * self.intermediate_size,
            EXPERTS: (n_experts * 3 * d * self.expert_width
                      + 3 * d * self.shared_width
                      + d * self.shared_expert_gate + d * self.router_width
                      + self.router_width * self.use_expert_bias),
        }
        norms = (4 if self.sandwich_norm else 2) * d
        if self.layer_norm_eps is not None:  # a LayerNorm: weight and bias
            norms *= 2
        n, read_out = self.streams, 0
        if n:
            # a sublayer's connection (Phi, its biases, the three scalars),
            # two a layer — with the norms: every layer has them — and the
            # read-out before the head (Phi_h, its bias, its scalar)
            norms += 2 * (n * d * self.hc_maps + self.hc_maps + 3)
            read_out = n * d * n + n + 1
        layers = sum(per_op[op] + per_ffn[ffn] + norms
                     for op, ffn in self.kinds[:first_layers])
        if self.num_nextn_predict_layers:
            # the prediction module: one more block of the last kind, the
            # norms of its two inputs and of its output, the projection
            layers += (per_op[ATTENTION] + per_ffn[EXPERTS] + norms
                       + 3 * d + 2 * d * d)
        embed = v * d * (1 if self.tie_embeddings else 2)
        return (layers + embed + read_out
                + d * (1 + (self.layer_norm_eps is not None)))

    def qk_norm_params(self) -> int:
        """q/k norm weights of one layer."""
        return {None: 0, "head": 2 * self.head_dim,
                "full": self.q_dim + self.kv_dim}[self.qk_norm_kind]


# ---------------------------------------------------------------------------
# Architecture registry. Sizes follow the public architecture descriptions of
# each family; "test" configs are tiny and used by the unit-test suite.
# ---------------------------------------------------------------------------

MODEL_CONFIGS = {
    # Tiny config for tests — runs on CPU in milliseconds.
    "test-tiny": ModelConfig(
        name="test-tiny", vocab_size=512, hidden_size=64, intermediate_size=128,
        num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
        rope_theta=10_000.0, max_seq_len=512,
    ),
    # GQA variant with enough KV heads for tp=4 sharding tests (test-tiny's
    # 2 KV heads cap it at tp=2).
    "test-tiny-gqa": ModelConfig(
        name="test-tiny-gqa", vocab_size=512, hidden_size=128,
        intermediate_size=256, num_layers=2, num_heads=8, num_kv_heads=4,
        head_dim=16, rope_theta=10_000.0, max_seq_len=512,
    ),
    "test-tiny-qwen": ModelConfig(
        name="test-tiny-qwen", vocab_size=512, hidden_size=64, intermediate_size=128,
        num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
        rope_theta=10_000.0, max_seq_len=512, attn_bias=True,
    ),
    "llama3.2:1b": ModelConfig(
        name="llama3.2:1b", vocab_size=128_256, hidden_size=2048,
        intermediate_size=8192, num_layers=16, num_heads=32, num_kv_heads=8,
        head_dim=64, rope_theta=500_000.0, max_seq_len=131_072,
        tie_embeddings=True,
    ),
    "llama3.2:3b": ModelConfig(
        name="llama3.2:3b", vocab_size=128_256, hidden_size=3072,
        intermediate_size=8192, num_layers=28, num_heads=24, num_kv_heads=8,
        head_dim=128, rope_theta=500_000.0, max_seq_len=131_072,
        tie_embeddings=True,
    ),
    "llama3:8b": ModelConfig(
        name="llama3:8b", vocab_size=128_256, hidden_size=4096,
        intermediate_size=14_336, num_layers=32, num_heads=32, num_kv_heads=8,
        head_dim=128, rope_theta=500_000.0, max_seq_len=8192,
    ),
    "qwen2.5:7b": ModelConfig(
        name="qwen2.5:7b", vocab_size=152_064, hidden_size=3584,
        intermediate_size=18_944, num_layers=28, num_heads=28, num_kv_heads=4,
        head_dim=128, rope_theta=1_000_000.0, max_seq_len=32_768,
        attn_bias=True,
    ),
    "qwen2.5-7b-instruct": ModelConfig(  # LM-Studio style alias used in the
        name="qwen2.5-7b-instruct",      # reference stress test
        vocab_size=152_064, hidden_size=3584, intermediate_size=18_944,
        num_layers=28, num_heads=28, num_kv_heads=4, head_dim=128,
        rope_theta=1_000_000.0, max_seq_len=32_768, attn_bias=True,
    ),
    # Embedding encoder (nomic-embed-text class: 768-d encoder).
    "nomic-embed-text": ModelConfig(
        name="nomic-embed-text", vocab_size=30_528, hidden_size=768,
        intermediate_size=3072, num_layers=12, num_heads=12, num_kv_heads=12,
        head_dim=64, rope_theta=1000.0, max_seq_len=8192, tie_embeddings=True,
        is_encoder=True,
    ),
    "test-tiny-embed": ModelConfig(
        name="test-tiny-embed", vocab_size=512, hidden_size=64,
        intermediate_size=128, num_layers=2, num_heads=4, num_kv_heads=4,
        head_dim=16, rope_theta=1000.0, max_seq_len=512, tie_embeddings=True,
        is_encoder=True,
    ),
    # Qwen3 family: per-head q/k RMSNorm, no attention bias.
    "qwen3:8b": ModelConfig(
        name="qwen3:8b", vocab_size=151_936, hidden_size=4096,
        intermediate_size=12_288, num_layers=36, num_heads=32,
        num_kv_heads=8, head_dim=128, rope_theta=1_000_000.0,
        max_seq_len=32_768, qk_norm=True,
    ),
    "test-tiny-qwen3": ModelConfig(
        name="test-tiny-qwen3", vocab_size=512, hidden_size=64,
        intermediate_size=128, num_layers=2, num_heads=4, num_kv_heads=2,
        head_dim=16, rope_theta=10_000.0, max_seq_len=512, qk_norm=True,
    ),
    # Mixture-of-experts family (Mixtral 8x7b architecture description).
    "mixtral:8x7b": ModelConfig(
        name="mixtral:8x7b", vocab_size=32_000, hidden_size=4096,
        intermediate_size=14_336, num_layers=32, num_heads=32,
        num_kv_heads=8, head_dim=128, rope_theta=1_000_000.0,
        max_seq_len=32_768, num_experts=8, num_experts_per_tok=2,
        norm_topk_prob=True,
    ),
    "test-tiny-moe": ModelConfig(
        name="test-tiny-moe", vocab_size=512, hidden_size=64,
        intermediate_size=96, num_layers=2, num_heads=4, num_kv_heads=2,
        head_dim=16, rope_theta=10_000.0, max_seq_len=512,
        num_experts=4, num_experts_per_tok=2, norm_topk_prob=True,
    ),
    # OLMoE family (allenai/OLMoE-1B-7B-0125-Instruct config.json): MHA,
    # RMSNorm over the whole q / k vector, 64 experts of width 1024 with
    # top-8 routing whose weights are NOT renormalised.
    "olmoe:1b-7b": ModelConfig(
        name="olmoe:1b-7b", vocab_size=50_304, hidden_size=2048,
        intermediate_size=1024, num_layers=16, num_heads=16,
        num_kv_heads=16, head_dim=128, rope_theta=10_000.0,
        rms_norm_eps=1e-5, max_seq_len=4096, qk_norm="full",
        num_experts=64, num_experts_per_tok=8,
    ),
    "test-tiny-olmoe": ModelConfig(
        name="test-tiny-olmoe", vocab_size=512, hidden_size=64,
        intermediate_size=32, num_layers=2, num_heads=4, num_kv_heads=4,
        head_dim=16, rope_theta=10_000.0, max_seq_len=512, qk_norm="full",
        num_experts=16, num_experts_per_tok=4,
    ),
    # LFM2 family (LiquidAI/LFM2-8B-A1B config.json): a stack whose layers
    # differ. 18 gated short convolutions (window 3, a per-sequence state
    # of 2 x hidden values a layer) and 6 GQA attention layers with
    # per-head q/k norm; a dense SwiGLU in the first 2 layers, then 32
    # experts of width 1792, top 4 by sigmoid score plus a selection bias,
    # weights renormalised over (sum + 1e-6); head tied to the embedding.
    "lfm2:8b-a1b": ModelConfig(
        name="lfm2:8b-a1b", vocab_size=65_536, hidden_size=2048,
        intermediate_size=7168, num_layers=24, num_heads=32, num_kv_heads=8,
        head_dim=64, rope_theta=1_000_000.0, rms_norm_eps=1e-5,
        max_seq_len=128_000, tie_embeddings=True, qk_norm="head",
        num_experts=32, num_experts_per_tok=4, norm_topk_prob=True,
        norm_topk_eps=1e-6, router_score="sigmoid", use_expert_bias=True,
        routed_scaling_factor=1.0, num_dense_layers=2,
        moe_intermediate_size=1792, conv_L_cache=3,
        layer_types=("conv", "conv") + ("full_attention", "conv", "conv",
                                        "conv") * 4
        + ("full_attention", "conv", "conv") * 2,
    ),
    # Tiny LFM2: a dense prefix, a repeated period AND an irregular tail
    # (three runs in layer_plan()), an expert width of its own.
    "test-tiny-lfm2": ModelConfig(
        name="test-tiny-lfm2", vocab_size=512, hidden_size=64,
        intermediate_size=96, num_layers=9, num_heads=4, num_kv_heads=2,
        head_dim=16, rope_theta=10_000.0, max_seq_len=512,
        tie_embeddings=True, qk_norm="head", num_experts=8,
        num_experts_per_tok=2, norm_topk_prob=True, norm_topk_eps=1e-6,
        router_score="sigmoid", use_expert_bias=True, num_dense_layers=2,
        moe_intermediate_size=32, conv_L_cache=3,
        layer_types=("conv", "conv") + ("full_attention", "conv") * 2
        + ("conv", "full_attention", "conv"),
    ),
    # Olmo-Hybrid family (allenai/Olmo-Hybrid-7B config.json): three gated
    # delta-rule linear-attention layers (30 heads, keys of 96, values of
    # 192, a convolution of 4 taps; a [96, 192] float32 state a head a
    # sequence) to one MHA attention layer without rotary embedding; the
    # norms on the sublayers' outputs, whole-vector q/k norm; dense SwiGLU.
    "olmo-hybrid:7b": ModelConfig(
        name="olmo-hybrid:7b", vocab_size=100_352, hidden_size=3840,
        intermediate_size=11_008, num_layers=32, num_heads=30,
        num_kv_heads=30, head_dim=128, rope_theta=None, rms_norm_eps=1e-6,
        max_seq_len=65_536, qk_norm="full", norm_order="post",
        linear_num_key_heads=30, linear_num_value_heads=30,
        linear_key_head_dim=96, linear_value_head_dim=192,
        linear_conv_kernel_dim=4, linear_allow_neg_eigval=True,
        layer_types=(("linear_attention",) * 3 + ("full_attention",)) * 8,
    ),
    # Tiny Olmo-Hybrid: two periods and a half (the stack ends inside a
    # period: layer_plan() has a tail), value heads wider than key heads.
    "test-tiny-olmo-hybrid": ModelConfig(
        name="test-tiny-olmo-hybrid", vocab_size=512, hidden_size=64,
        intermediate_size=128, num_layers=10, num_heads=4, num_kv_heads=4,
        head_dim=16, rope_theta=None, rms_norm_eps=1e-6, max_seq_len=512,
        qk_norm="full", norm_order="post", linear_num_key_heads=4,
        linear_num_value_heads=4, linear_key_head_dim=8,
        linear_value_head_dim=16, linear_conv_kernel_dim=4,
        linear_allow_neg_eigval=True,
        layer_types=(("linear_attention",) * 3 + ("full_attention",)) * 2
        + ("linear_attention",) * 2,
    ),
    # Qwen3-Next family (Qwen/Qwen3-Next-80B-A3B-Instruct config.json):
    # three gated delta-rule layers (16 key heads under 32 value heads of
    # 128: a [128, 128] float32 state a value head a sequence; beta in (0,
    # 1)) to one GATED attention layer (16/2 heads of 256, RoPE on the first
    # 64 lanes, a sigmoid gate a head on the output); zero-centred norms;
    # every FFN 512 experts of width 512, softmax top 10 renormalised, plus
    # a shared expert of width 512 times sigmoid(x w_sg); untied head.
    "qwen3-next:80b-a3b": ModelConfig(
        name="qwen3-next:80b-a3b", vocab_size=151_936, hidden_size=2048,
        intermediate_size=5120, num_layers=48, num_heads=16, num_kv_heads=2,
        head_dim=256, rope_theta=10_000_000.0, rms_norm_eps=1e-6,
        max_seq_len=262_144, qk_norm="head", partial_rotary_factor=0.25,
        attn_output_gate=True, zero_centred_norm=True,
        linear_num_key_heads=16, linear_num_value_heads=32,
        linear_key_head_dim=128, linear_value_head_dim=128,
        linear_conv_kernel_dim=4, full_attention_interval=4,
        layer_types=(("linear_attention",) * 3 + ("full_attention",)) * 12,
        num_experts=512, num_experts_per_tok=10, norm_topk_prob=True,
        moe_intermediate_size=512, shared_expert_intermediate_size=512,
        shared_expert_gate=True,
    ),
    # Tiny Qwen3-Next: two periods, 2 key heads under 4 value heads, RoPE on
    # 8 of 16 lanes, one share (8 held of the router's 16) of the experts,
    # a shared expert of its own width.
    "test-tiny-qwen3-next": ModelConfig(
        name="test-tiny-qwen3-next", vocab_size=512, hidden_size=64,
        intermediate_size=128, num_layers=8, num_heads=4, num_kv_heads=2,
        head_dim=16, rope_theta=10_000.0, rms_norm_eps=1e-6, max_seq_len=512,
        qk_norm="head", partial_rotary_factor=0.5, attn_output_gate=True,
        zero_centred_norm=True, linear_num_key_heads=2,
        linear_num_value_heads=4, linear_key_head_dim=8,
        linear_value_head_dim=16, linear_conv_kernel_dim=4,
        full_attention_interval=4,
        layer_types=(("linear_attention",) * 3 + ("full_attention",)) * 2,
        num_experts=8, router_experts=16, expert_offset=0,
        num_experts_per_tok=4, norm_topk_prob=True, moe_intermediate_size=32,
        shared_expert_intermediate_size=48, shared_expert_gate=True,
    ),
    # Tiny K-EXAONE: the published 3 : 1 of window (8 positions) and full
    # layers behind a leading dense layer, RoPE on the window layers only,
    # per-head q/k norm at group 2, a sigmoid router with a selection bias
    # over 16 experts of which this program holds 4, one shared expert.
    "test-tiny-k-exaone": ModelConfig(
        name="test-tiny-k-exaone", vocab_size=512, hidden_size=64,
        intermediate_size=128, num_layers=5, num_heads=4, num_kv_heads=2,
        head_dim=16, rope_theta=10_000.0, rms_norm_eps=1e-5, max_seq_len=512,
        qk_norm="head", sliding_window=8, sliding_window_pattern="LLLG",
        layer_types=("sliding_attention",) * 3 + ("full_attention",
                                                  "sliding_attention"),
        rope_layer_types=("sliding_attention",),
        num_experts=4, router_experts=16, expert_offset=0,
        num_experts_per_tok=4, n_group=1, topk_group=1, num_shared_experts=1,
        moe_intermediate_size=32, first_k_dense_replace=1,
        scoring_func="sigmoid", use_expert_bias=True, norm_topk_prob=True,
        norm_topk_eps=1e-20, routed_scaling_factor=2.5,
    ),
    # MiMo-V2-Flash's architecture (XiaomiMiMo/MiMo-V2-Flash config.json) at
    # toy sizes: the published 5 : 1 of window to full layers behind a leading
    # full + dense layer (F W W W W F W), the two kinds at DIFFERENT kv-head
    # counts (1 full / 2 window at 4 heads: groups 4 and 2), key heads of 24
    # lanes beside value heads of 16, RoPE over the first 8 lanes at two
    # bases, a sink in the window layers' softmax, v times 0.707, a sigmoid
    # router with a selection bias over 16 experts of which this program
    # holds 4, no shared expert, no scale.
    "test-tiny-mimo-v2-flash": ModelConfig(
        name="test-tiny-mimo-v2-flash", vocab_size=512, hidden_size=64,
        intermediate_size=128, num_layers=7, num_heads=4, num_kv_heads=1,
        head_dim=24, v_head_dim=16, rope_theta=5_000_000.0,
        partial_rotary_factor=0.334, layernorm_epsilon=1e-5, max_seq_len=512,
        sliding_window=8, sliding_window_size=8, attention_chunk_size=8,
        hybrid_layer_pattern=(0, 1, 1, 1, 1, 0, 1),
        swa_num_attention_heads=4, swa_num_key_value_heads=2,
        swa_head_dim=24, swa_v_head_dim=16, swa_rope_theta=10_000.0,
        add_swa_attention_sink_bias=True, add_full_attention_sink_bias=False,
        attention_value_scale=0.707,
        num_experts=4, router_experts=16, expert_offset=0,
        num_experts_per_tok=4, n_group=1, topk_group=1, n_shared_experts=None,
        moe_intermediate_size=32, moe_layer_freq=(0, 1, 1, 1, 1, 1, 1),
        scoring_func="sigmoid", topk_method="noaux_tc", norm_topk_prob=True,
        norm_topk_eps=1e-20, routed_scaling_factor=None,
    ),
    # Falcon-H1-34B (tiiuae/Falcon-H1-34B-Instruct config.json): every layer
    # runs GQA attention (20 heads of 128 over 4 K/V heads: 2560 lanes under
    # a hidden size of 5120, RoPE at theta 1e11) AND a Mamba-2 mixer (32
    # heads of 128 under 2 groups of B/C of 256; a convolution of 4 taps with
    # a bias over 5120 channels; a gated RMSNorm over the groups) on one
    # normed input, then a SwiGLU MLP; the family's muP multipliers; a
    # vocabulary of 261,120; untied head.
    "falcon-h1:34b": ModelConfig(
        name="falcon-h1:34b", vocab_size=261_120, hidden_size=5120,
        intermediate_size=21_504, num_layers=72, num_heads=20, num_kv_heads=4,
        head_dim=128, rope_theta=1e11, rms_norm_eps=1e-5,
        max_seq_len=262_144, mamba_d_ssm=4096, mamba_d_state=256,
        mamba_d_head=128, mamba_n_heads=32, mamba_n_groups=2, mamba_d_conv=4,
        embedding_multiplier=5.656854249492381, lm_head_multiplier=0.0078125,
        attention_in_multiplier=1.0, attention_out_multiplier=0.0375,
        key_multiplier=0.011048543456039804, ssm_in_multiplier=0.25,
        ssm_out_multiplier=0.08838834764831845,
        ssm_multipliers=(0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                         0.3535533905932738),
        mlp_multipliers=(0.1767766952966369, 0.011160714285714284),
    ),
    # Tiny Falcon-H1: 4 mixer heads of 16 under 2 groups of B/C of 8, a q
    # width (4 x 16) that is not the hidden size, every multiplier away
    # from 1 (scalars of the published sizes' order).
    "test-tiny-falcon-h1": ModelConfig(
        name="test-tiny-falcon-h1", vocab_size=512, hidden_size=96,
        intermediate_size=128, num_layers=3, num_heads=4, num_kv_heads=2,
        head_dim=16, rope_theta=1e11, rms_norm_eps=1e-5, max_seq_len=512,
        mamba_d_ssm=64, mamba_d_state=8, mamba_d_head=16, mamba_n_heads=4,
        mamba_n_groups=2, mamba_d_conv=4, embedding_multiplier=5.65,
        lm_head_multiplier=0.0078125, attention_in_multiplier=1.0,
        attention_out_multiplier=0.0375, key_multiplier=0.011,
        ssm_in_multiplier=0.25, ssm_out_multiplier=0.088,
        ssm_multipliers=(0.354, 0.25, 0.177, 0.5, 0.354),
        mlp_multipliers=(0.177, 0.0112),
    ),
    # Phi-4-mini-flash-reasoning (microsoft/Phi-4-mini-flash-reasoning
    # config.json, model_type phi4flash; arXiv:2507.06607): a decoder-hybrid-
    # decoder stack — `mb_per_layer` 2 derives 8 x (mamba, window 512), a
    # mamba layer that hands on its scan output, ONE full-attention layer,
    # 7 x (gated memory unit, cross attention over that layer's cache);
    # differential attention (40 / 20 heads of 64 in adjacent pairs) with
    # biased projections and no position encoding, LayerNorms with bias, a
    # tied head over 200,064 ids.
    "phi4-mini-flash:3.8b": ModelConfig(
        name="phi4-mini-flash:3.8b", vocab_size=200_064, hidden_size=2560,
        intermediate_size=10_240, num_layers=32, num_heads=40,
        num_kv_heads=20, head_dim=64, sliding_window=512, mb_per_layer=2,
        layer_norm_eps=1e-5, tie_embeddings=True, max_seq_len=262_144,
    ),
    # Tiny Phi-4-mini-flash: 8 layers, so that all five kinds occur (mamba,
    # window, mamba, window | mamba | full | gated memory | cross); 4 q pairs
    # over 2 K/V pairs of 2 x 16 lanes; a window of 16, under the tests'
    # contexts.
    "test-tiny-phi4-flash": ModelConfig(
        name="test-tiny-phi4-flash", vocab_size=512, hidden_size=128,
        intermediate_size=256, num_layers=8, num_heads=8, num_kv_heads=4,
        head_dim=16, sliding_window=16, mb_per_layer=2, layer_norm_eps=1e-5,
        tie_embeddings=True, max_seq_len=512,
    ),
    # MiniCPM-SALA (openbmb/MiniCPM-SALA config.json, model_type minicpm_sala;
    # the `minicpm4` mixer is InfLLM-V2, arXiv:2506.07900): 8 block-sparse
    # attention layers (32 / 2 heads of 128, NoPE, an output gate; past 8192
    # positions the top 64 blocks of 64) at no period among 24 lightning
    # linear-attention layers (32 heads of 128, roped, a constant decay a
    # head and layer), muP scalars, an untied head over 73,448 ids. The
    # `sparse_*` sizes are the family's convention (its config.json has no
    # `sparse_config`): benchmarks/configs/minicpm-sala-d16.json, `assumed`.
    "minicpm-sala:9b": ModelConfig(
        name="minicpm-sala:9b", vocab_size=73_448, hidden_size=4096,
        intermediate_size=16_384, num_layers=32, num_heads=32, num_kv_heads=2,
        head_dim=128, rope_theta=10_000.0, rms_norm_eps=1e-6,
        max_seq_len=524_288, qk_norm=True,
        mixer_types=tuple(
            "minicpm4" if i in (0, 9, 16, 17, 22, 29, 30, 31)
            else "lightning-attn" for i in range(32)),
        sparse_kernel_size=32, sparse_kernel_stride=16, sparse_block_size=64,
        sparse_topk=64, sparse_init_blocks=1, sparse_window_size=2048,
        sparse_dense_len=8192, lightning_nh=32, lightning_nkv=32,
        lightning_head_dim=128, attn_use_rope=False,
        attn_use_output_gate=True, scale_emb=12, scale_depth=1.4,
        dim_model_base=256,
    ),
    # Tiny MiniCPM-SALA: six layers of a published eight (1 .. 6: the decay
    # reads the published index), sparse layers at no period and two of them
    # adjacent; blocks of 16 (two 8-token pages), pooled keys 8-by-4, the top
    # 4 blocks past 64 positions — init 1, local 2, one chosen — so that the
    # tests' contexts cross `sparse_dense_len` and the score decides a block.
    "test-tiny-minicpm-sala": ModelConfig(
        name="test-tiny-minicpm-sala", vocab_size=512, hidden_size=128,
        intermediate_size=256, num_layers=6, num_heads=8, num_kv_heads=2,
        head_dim=16, rope_theta=10_000.0, rms_norm_eps=1e-6, max_seq_len=512,
        qk_norm=True,
        mixer_types=("minicpm4", "lightning-attn", "lightning-attn",
                     "minicpm4", "minicpm4", "lightning-attn"),
        sparse_kernel_size=8, sparse_kernel_stride=4, sparse_block_size=16,
        sparse_topk=4, sparse_init_blocks=1, sparse_window_size=32,
        sparse_dense_len=64, lightning_nh=8, lightning_nkv=8,
        lightning_head_dim=16, attn_use_rope=False,
        attn_use_output_gate=True, scale_emb=12, scale_depth=1.4,
        scale_depth_layers=8, layer_offset=1, dim_model_base=32,
    ),
    # Tiny DeepSeek-V3.2: latent attention with the indexer's selection (top
    # 16: well under the tests' contexts), YaRN, a dense layer then expert
    # layers with a shared expert, group-limited sigmoid routing over 16
    # experts of which this program holds 4 (one share of four).
    "test-tiny-deepseek-v32": ModelConfig(
        name="test-tiny-deepseek-v32", vocab_size=512, hidden_size=64,
        intermediate_size=128, num_layers=3, num_heads=4, num_kv_heads=4,
        head_dim=24, rope_theta=10_000.0, rms_norm_eps=1e-6, max_seq_len=512,
        q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, index_n_heads=4,
        index_head_dim=16, index_topk=16,
        rope_scaling={"type": "yarn", "factor": 4,
                      "original_max_position_embeddings": 64, "beta_fast": 32,
                      "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1},
        num_experts=4, router_experts=16, expert_offset=0,
        num_experts_per_tok=4, n_group=4, topk_group=2, n_shared_experts=1,
        moe_intermediate_size=32, first_k_dense_replace=1,
        router_score="sigmoid", use_expert_bias=True, norm_topk_prob=True,
        norm_topk_eps=1e-20, routed_scaling_factor=2.5,
    ),
    # Tiny openPangu-Ultra-MoE: latent attention with NO indexer and plain
    # RoPE, sandwich norms, a dense layer then expert layers with a shared
    # expert, a sigmoid router with no groups and no bias over 16 experts of
    # which this program holds 4, and the multi-token-prediction module
    # (one more block, its own cache rows; `--spec` drafts with it).
    # Kimi-Linear-48B-A3B-Instruct (moonshotai; arXiv:2510.26692): Kimi Delta
    # Attention (a delta rule whose decay is a vector a head) in three of
    # four layers, NoPE latent attention with a full-rank q in the fourth
    # and the last, a dense first layer, then 256 experts of 1024 (top 8 by
    # sigmoid score + a selection bias, one shared expert, scale 2.446).
    # The published keys under their own names; `head_dim` 72 is what
    # config.json has (no module reads it) and becomes 192 here.
    "kimi-linear:48b-a3b": ModelConfig(
        name="kimi-linear:48b-a3b", vocab_size=163_840, hidden_size=2304,
        intermediate_size=9216, num_layers=27, num_heads=32, num_kv_heads=32,
        head_dim=72, rope_theta=10_000.0, rms_norm_eps=1e-5,
        model_max_length=1_048_576, q_lora_rank=None, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        mla_use_nope=True,
        linear_attn_config={
            "kda_layers": [i for i in range(1, 27) if i % 4],
            "full_attn_layers": [4, 8, 12, 16, 20, 24, 27],
            "num_heads": 32, "head_dim": 128, "short_conv_kernel_size": 4},
        num_experts=256, num_experts_per_token=8,
        moe_router_activation_func="sigmoid", moe_renormalize=True,
        num_expert_group=1, topk_group=1, use_grouped_topk=True,
        use_expert_bias=True, num_shared_experts=1,
        routed_scaling_factor=2.446, moe_intermediate_size=1024,
        first_k_dense_replace=1,
    ),
    # Two periods at toy widths, 4 of 16 experts held (the tests' size).
    "test-tiny-kimi-linear": ModelConfig(
        name="test-tiny-kimi-linear", vocab_size=512, hidden_size=64,
        intermediate_size=128, num_layers=8, num_heads=4, num_kv_heads=4,
        head_dim=16, rope_theta=10_000.0, rms_norm_eps=1e-5, max_seq_len=512,
        q_lora_rank=None, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, mla_use_nope=True,
        linear_attn_config={
            "kda_layers": [1, 2, 3, 5, 6, 7], "full_attn_layers": [4, 8],
            "num_heads": 4, "head_dim": 8, "short_conv_kernel_size": 4},
        num_experts=4, router_experts=16, expert_offset=0,
        num_experts_per_token=4, moe_router_activation_func="sigmoid",
        moe_renormalize=True, num_expert_group=1, topk_group=1,
        use_grouped_topk=True, use_expert_bias=True, num_shared_experts=1,
        routed_scaling_factor=2.446, moe_intermediate_size=32,
        first_k_dense_replace=1,
    ),
    "test-tiny-openpangu": ModelConfig(
        name="test-tiny-openpangu", vocab_size=512, hidden_size=64,
        intermediate_size=128, num_layers=3, num_heads=4, num_kv_heads=4,
        head_dim=24, rope_theta=10_000.0, rms_norm_eps=1e-5, max_seq_len=512,
        q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, sandwich_norm=True,
        num_experts=4, router_experts=16, expert_offset=0,
        num_experts_per_tok=4, n_shared_experts=1, moe_intermediate_size=32,
        first_k_dense_replace=1, router_score="sigmoid", norm_topk_prob=True,
        norm_topk_eps=1e-20, routed_scaling_factor=2.5,
        num_nextn_predict_layers=1,
    ),
    # Xing4.0-29B-A4B (XingChen-AGI; `model_type` xing4_0): DeepSeek-V3's
    # latent attention (q through rank 768, YaRN x 64 from 4096) and expert
    # layers (64 experts of 1024, 4 a token by sigmoid score + selection
    # bias, gates normalised and x 2, one shared expert; ALL experts on the
    # chip: `ep_size` 1) after two dense layers — around a residual path of
    # FOUR streams mixed by manifold-constrained hyper-connections
    # (`hc_mult`; ops/hyper_connection.py). The published prediction module
    # (`num_nextn_predict_layers` 1) is not served over four streams and is
    # left out (ROADMAP B-M12). 29.5 B parameters: no one chip holds it; the
    # benchmark serves a first pipeline stage
    # (benchmarks/configs/xing4.0-29b-a4b-d6.json).
    "xing4.0:29b-a4b": ModelConfig(
        name="xing4.0:29b-a4b", vocab_size=131072, hidden_size=3584,
        intermediate_size=9216, num_layers=40, num_heads=32, num_kv_heads=32,
        head_dim=192, rope_theta=10_000.0, rms_norm_eps=1e-6,
        max_seq_len=262144, q_lora_rank=768, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        rope_scaling={"type": "yarn", "factor": 64,
                      "original_max_position_embeddings": 4096,
                      "beta_fast": 32, "beta_slow": 1, "mscale": 1,
                      "mscale_all_dim": 1},
        num_experts=64, num_experts_per_tok=4, n_shared_experts=1,
        moe_intermediate_size=1024, first_k_dense_replace=2,
        router_score="sigmoid", use_expert_bias=True, norm_topk_prob=True,
        norm_topk_eps=1e-20, routed_scaling_factor=2.0,
        hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6,
        mhc_h_res_clamp_min=-30.0, mhc_h_res_clamp_max=30.0,
    ),
    # ...and its tiny twin: four streams of 64 around latent attention, one
    # dense layer and two expert layers (8 experts, 2 a token, all held).
    "test-tiny-xing4": ModelConfig(
        name="test-tiny-xing4", vocab_size=512, hidden_size=64,
        intermediate_size=128, num_layers=3, num_heads=4, num_kv_heads=4,
        head_dim=24, rope_theta=10_000.0, rms_norm_eps=1e-6, max_seq_len=512,
        q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16,
        rope_scaling={"type": "yarn", "factor": 4,
                      "original_max_position_embeddings": 64, "beta_fast": 32,
                      "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1},
        num_experts=8, num_experts_per_tok=2, n_shared_experts=1,
        moe_intermediate_size=32, first_k_dense_replace=1,
        router_score="sigmoid", use_expert_bias=True, norm_topk_prob=True,
        norm_topk_eps=1e-20, routed_scaling_factor=2.0,
        hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6,
        mhc_h_res_clamp_min=-30.0, mhc_h_res_clamp_max=30.0,
    ),
}


def smart_match(name: str, candidates) -> Optional[str]:
    """Smart model matching: exact → lowercase → tag-stripped.

    Single Python implementation of the reference's `smart_model_match`
    (/root/reference/src/dispatcher.rs:231-252): `llama3` matches
    `llama3:8b`/`llama3:latest`, matching is case-insensitive. The native
    scheduler gate (cpp/mqcore.cpp) implements the same algorithm for its
    in-core eligibility check; tests/test_mqcore.py pins the two together.
    """
    candidates = list(candidates)
    if name in candidates:
        return name
    low = name.lower()
    by_lower = {c.lower(): c for c in candidates}
    if low in by_lower:
        return by_lower[low]
    base = low.split(":", 1)[0]
    for c in candidates:
        if c.lower().split(":", 1)[0] == base:
            return c
    return None


def get_model_config(name: str) -> Optional[ModelConfig]:
    """Resolve a requested model name to an architecture via smart_match."""
    key = smart_match(name, MODEL_CONFIGS.keys())
    return MODEL_CONFIGS[key] if key is not None else None


@dataclasses.dataclass
class EngineConfig:
    """Continuous-batching engine configuration."""

    model: str = "test-tiny"
    # Decode slots = max sequences generating concurrently in one batch.
    max_slots: int = 64
    # Paged KV cache: total pages in the pool and tokens per page.
    # Larger pages mean fewer, longer DMA bursts in the attention
    # kernels; 32 against 16 or 64 has no recorded measurement yet
    # (PERF.md, open questions).
    num_pages: int = 256
    page_size: int = 32
    # Max pages a single sequence may hold (=> max context length).
    max_pages_per_seq: int = 16
    # -- ragged mixed-batch attention ----------------------------------------
    # ONE token-budget dispatch packs any mix of variable-length prefill
    # spans and decode tokens into a flattened stream (Pallas ragged
    # kernel on TPU, jnp twin elsewhere) — no power-of-two bucket
    # padding.
    # Token budget of one ragged dispatch: decode rows (1 token per
    # active slot) plus as many prefill-tail tokens as fit. Clamped up
    # to max_slots + token_granule so a full decode batch always fits.
    max_batch_tokens: int = 512
    # The ONLY padding the ragged path pays: the stream's total token
    # count rounds up to this granule for shape stability (one compile
    # per padded total). Small => waste bounded by granule/batch_tokens.
    token_granule: int = 16
    # -- speculative multi-token decoding (ragged path) ----------------------
    # Propose up to spec_k draft tokens per greedy decode slot from an
    # n-gram prompt/history lookup (no second model), then verify them
    # all in ONE ragged dispatch as a (k+1)-token span: accepted drafts
    # emit together (the longest prefix where draft == argmax, plus the
    # model's own next token — byte-identical to non-speculative greedy),
    # rejected drafts' KV pages roll back. Greedy no-penalty requests
    # only; sampled/penalized rows stay 1-token decode rows.
    spec: bool = False
    spec_k: int = 4
    # Auto-throttle: once a user's observed accept rate over a warmup
    # sample falls below this, speculation is disabled for that user —
    # wasted verify FLOPs must pay for themselves. 0 = never throttle.
    spec_min_accept: float = 0.1
    # Max new tokens default when request doesn't specify.
    max_new_tokens: int = 256
    # Decode steps executed per host-loop iteration when no prefill pending
    # (amortizes dispatch overhead via lax.scan).
    decode_steps_per_iter: int = 8
    # Repeat-penalty window: how many recent context tokens are penalized
    # (llama.cpp repeat_last_n; engine-wide static).
    repeat_last_n: int = 64
    # Automatic prefix caching: finished prompts' full KV pages merge into
    # a per-model radix tree (engine/prefix_cache.py); admissions sharing
    # a prefix pin those pages and prefill only the uncached tail.
    prefix_cache: bool = False
    # Minimum matched FULL pages before the cached-tail path is taken —
    # tiny hits aren't worth routing through the chunked prefill.
    prefix_cache_min_pages: int = 1
    # Mesh axis sizes; tp=-1 means "all remaining devices". The engine
    # builds its (data, expert, tensor) mesh from these unless
    # an explicit mesh object is passed to TPUEngine.
    dp: int = 1
    tp: int = 1
    ep: int = 1
    dtype: str = "bfloat16"
    # -- int8 quantization (serving density) ---------------------------------
    # weights_dtype="int8": per-channel symmetric int8 weights quantized
    # at load time (scales fp32, dequant fused into the matmuls, bf16
    # accumulation) — roughly halves weight HBM and the bytes every
    # weight-streaming-bound dispatch pays. kv_dtype="int8": int8 KV
    # pages with per-page-row fp32 scales stored alongside the pool —
    # every page shrinks ~2x, so ~2x concurrent requests fit the same
    # HBM. Invalid combinations (MoE weights) fail fast at
    # startup via validate_quant_config.
    weights_dtype: str = "bfloat16"
    kv_dtype: str = "bfloat16"
    seed: int = 0
    # Telemetry: finished request traces kept for GET /debug/trace
    # (Chrome trace-event export); in-flight traces are always exported.
    trace_ring: int = 512
    # Latency SLOs (telemetry/slo.py): 0/None = objective not configured.
    # slo_ttft_ms bounds enqueue -> first token; slo_tpot_ms bounds the
    # per-token decode step. slo_target is the good-fraction objective
    # (0.99 = 1% error budget); burn-rate alerts fire against it.
    slo_ttft_ms: Optional[float] = None
    slo_tpot_ms: Optional[float] = None
    slo_target: float = 0.99
    # -- graceful degradation under load ------------------------------------
    # Preemption with recompute: decode-time KV-pool exhaustion preempts a
    # victim (never the VIP) back to the FRONT of its user's queue instead
    # of truncating; re-admission prefills prompt+generated through the
    # normal path (mostly cache hits with --prefix-cache). Off => explicit
    # kv_exhausted error, NEVER a silent LENGTH.
    preempt: bool = True
    # Anti-livelock budget: after this many preemptions a request holds
    # its reservation (slot + pages) and is never picked as a victim.
    preempt_max: int = 3
    # Bounded admission: total / per-user queued-request caps (0 = off).
    # Over-cap enqueues are shed with 503 / 429 + Retry-After instead of
    # growing the queue unboundedly.
    max_queued: int = 0
    max_queued_per_user: int = 0
    # Failure containment: requests implicated in a failed runtime step
    # are retried this many times (fresh dispatch, exponential backoff
    # from retry_backoff_s) before being poisoned with an explicit error.
    step_retries: int = 1
    retry_backoff_s: float = 0.2
    # Deterministic fault injection (testing/faults.py): path to a plan
    # file, or a FaultPlan instance (tests). None = no injection.
    fault_plan: Optional[object] = None
    # -- fleet router (fleet/router.py) --------------------------------------
    # Engine replicas behind the front-end router (1 = single engine, no
    # router). The router owns the fair-share queues and the bounded-
    # admission caps; members serve uncapped what the router placed.
    replicas: int = 1
    # Placement policy: "affinity" routes to the replica whose prefix-
    # cache radix tree already holds the prompt's prefix (falling back
    # to least-loaded); "least_loaded" skips the affinity probe.
    placement: str = "affinity"
    # POST /admin/drain/{replica}: in-flight streams get this long to
    # complete before the stragglers fail over to healthy replicas.
    drain_timeout_s: float = 30.0
    # KV page migration: failover/drain first tries to SHIP a victim
    # stream's KV pages + request state to a healthy member (resume from
    # shipped state, zero recomputed tokens), falling back to the
    # recompute replay when the source can't export or the transfer
    # fails; affinity misses may ship the cached prefix to the chosen
    # member. Off => every failover/drain uses recompute replay.
    migrate: bool = True
    # Per-transfer budget: a migration (export + ship + import ack) past
    # this aborts and falls back to recompute — a stalled transfer must
    # never hold a stream hostage longer than re-deriving it would.
    migrate_timeout_s: float = 10.0
    # Router-overhead bound: the always-on self-profiler times every
    # placement decision (ollamamq_router_overhead_ms{site="place"});
    # a windowed p99 above this budget fires the health monitor's
    # router_overhead alert — "router overhead measured and
    # bounded". 0 disables the alert
    # (the timers stay on: measurement is not optional).
    router_overhead_budget_ms: float = 50.0
    # Metrics federation: re-export every HTTP member's series from the
    # router's /metrics with a `replica` label (scraped on the member
    # health heartbeat), so one Prometheus target sees the fleet.
    # LocalMembers share the router process's registry and are always
    # in the local exposition regardless.
    federate_metrics: bool = True
    # -- tiered fleet (fleet/tiering.py) -------------------------------------
    # Replica-tier spec: latency-sensitive traffic (VIP/boost users,
    # deadlined requests) places on the `interactive` tier, everything
    # else on `bulk`, with affinity/least-loaded preserved WITHIN a
    # tier and cross-tier placement only under journaled overflow
    # (per-tier SLO burn) or an empty tier. Syntax:
    #   "interactive=r0;bulk=r1,r2"          by member name
    #   "interactive@tp4=tp4;bulk@tp1=tp1"   by TP width (tpN matches
    #                                        every member at width N);
    # the optional @tpN suffix declares the tier's TARGET width — the
    # TierBalancer hot-restarts a retiered LocalMember at it. Members no
    # selector matches default to bulk. None = untiered fleet (every
    # member interchangeable, the pre-tiering behavior).
    tiers: Optional[str] = None
    # -- elastic fleet (fleet/autoscaler.py) ---------------------------------
    # SLO-burn-driven autoscaler: a per-tier control loop that watches
    # sustained burn + queue backlog and resizes the fleet one member at
    # a time through a MemberProvisioner. Scale-down is always drain ->
    # migrate-off -> retire (never a kill); the bulk tier may scale to
    # zero (queued bulk work parks at the router and wakes it), while
    # `interactive` keeps the min_replicas floor. Off = fixed fleet.
    autoscale: bool = False
    # Fleet-size bounds for the scaler: min_replicas is the floor for
    # the interactive tier (and untiered fleets); max_replicas caps the
    # whole fleet.
    min_replicas: int = 1
    max_replicas: int = 4
    # Hysteresis: after any scale event the scaler holds its fire this
    # long (TierBalancer discipline — the burn/idle signal must also be
    # SUSTAINED, with sustain windows derived from this knob). Waking a
    # scaled-to-zero tier with parked work bypasses the cooldown: parked
    # streams must never wait out a timer that exists to stop flapping.
    scale_cooldown_s: float = 30.0
    # Comma-separated member names flagged preemptible (spot-style
    # capacity): POST /admin/preempt/{replica} — or the fault plan's
    # "preempt_notice" site — serves them a termination notice that
    # triggers migrate-off-then-retire within the notice window.
    preemptible: Optional[str] = None
    # -- scheduling policy (engine/scheduler.py) -----------------------------
    # Admission / prefill-packing / preemption-victim ordering: "fcfs"
    # (default; bit-identical to the pre-policy-extraction engine),
    # "srpt" (shortest-predicted-remaining-first off the online
    # output-length predictor, with anti-starvation aging), "edf"
    # (earliest-deadline-first over Request.deadline; srpt order for
    # deadline-less requests). Policies reorder only within what the
    # fair-share core already released; promote a candidate via
    # `tools/journal simulate` counterfactual replay.
    scheduler: str = "fcfs"
    # -- flight recorder (telemetry/journal.py) ------------------------------
    # Decision-journal ring capacity (records retained for /debug/journal
    # and the health monitor's invariant sweep).
    journal_ring: int = 2048
    # Optional JSONL spill of every journal record (--journal-file);
    # rotated at journal_rotate_mb, keeping journal_keep rotated files —
    # bounded disk on soak runs.
    journal_file: Optional[str] = None
    journal_rotate_mb: float = 64.0
    journal_keep: int = 3
    # Probabilistic sampling of high-rate journal kinds (batch/chunk/
    # page_*/broadcast): 1.0 records everything (the default, and what
    # the deterministic record/replay harness requires); lower rates let
    # the ring and spill survive 100x event storms. Decision-critical
    # kinds (enqueue/admit/shed/preempt/finish/migrate_*/recover_*/...)
    # are ALWAYS retained regardless of the rate.
    journal_sample: float = 1.0
    # -- crash durability (durability/) --------------------------------------
    # Write-ahead request log directory: every accepted generation
    # request is durably recorded (batched fsync, --wal-fsync-ms window)
    # BEFORE the enqueue ACKs, emitted tokens are appended behind it,
    # and a restart replays unfinished requests token-exact — clients
    # reattach via GET /api/stream/{req_id}?from=N. None = no WAL (the
    # default; zero overhead). In fleet mode the ROUTER owns the WAL,
    # like the journal spill — member configs clear it.
    wal_dir: Optional[str] = None
    # Group-commit fsync window in ms: every admission waits at most
    # this long for the covering fsync; a crash loses at most this much
    # emitted-token progress (regenerated identically under greedy
    # decoding on recovery). 0 = fsync inline on every admission.
    wal_fsync_ms: float = 20.0
    # -- router HA (fleet/ha.py) ---------------------------------------------
    # Primary role: replicate WAL records + journal decision events to a
    # connected warm standby over GET /admin/ha/sync (batched, sequence-
    # numbered; the standby's poll position is the ack). Requires a WAL
    # (--wal-dir): the replicated WAL is what a takeover recovers from.
    ha: bool = False
    # Standby role: the primary router's base URL to tail. The process
    # builds the full fleet (same member URLs) but serves nothing until
    # the primary's heartbeat is lost past the takeover grace — then it
    # PROMOTES: epoch bump, member re-registration (stale-epoch callers
    # fenced), WAL-replica recovery re-admission. Mutually exclusive
    # with --ha.
    standby_of: Optional[str] = None
    # Heartbeat-loss window before the standby declares the primary dead
    # and promotes; also the sync poll cadence's upper bound (the
    # standby polls at grace/4, floor 50ms).
    takeover_grace_s: float = 3.0

    @property
    def max_context(self) -> int:
        return self.max_pages_per_seq * self.page_size


QUANT_DTYPES = ("bfloat16", "int8")

# Closed scheduling-policy vocabulary (engine/scheduler.py maps each
# name to its implementation and asserts the two stay in sync).
SCHEDULERS = ("fcfs", "srpt", "edf")


def validate_scheduler(name: str) -> Optional[str]:
    """Fail-fast --scheduler validation BEFORE any device work: returns
    an error string (None = valid). Shared by the CLI and the deploy
    plumbing so a typo'd SCHEDULER env kills the process at startup,
    not at the first admission pass."""
    if name not in SCHEDULERS:
        return f"--scheduler must be one of {SCHEDULERS}, got {name!r}"
    return None


def validate_autoscale(min_replicas: int, max_replicas: int,
                       scale_cooldown_s: float,
                       replicas: int) -> Optional[str]:
    """Fail-fast --autoscale validation BEFORE any device work: returns
    an error string (None = valid). Shared by the CLI and the deploy
    plumbing so a bad MIN_REPLICAS/MAX_REPLICAS env kills the process at
    startup, not at the scaler's first decision."""
    if min_replicas < 1:
        return (f"--min-replicas must be >= 1 (the interactive floor), "
                f"got {min_replicas}")
    if max_replicas < min_replicas:
        return (f"--max-replicas ({max_replicas}) must be >= "
                f"--min-replicas ({min_replicas})")
    if scale_cooldown_s <= 0:
        return (f"--scale-cooldown-s must be > 0, got {scale_cooldown_s}")
    if replicas > max_replicas:
        return (f"starting fleet size --replicas {replicas} exceeds "
                f"--max-replicas {max_replicas}")
    return None


def validate_ha(ha: bool, standby_of: Optional[str],
                takeover_grace_s: float, wal_dir: Optional[str],
                fleet: Optional[str]) -> Optional[str]:
    """Fail-fast --ha/--standby-of validation BEFORE any device work:
    returns an error string (None = valid). Shared by the CLI and the
    deploy plumbing so a bad HA/STANDBY_OF env kills the process at
    startup, not at the first (or worst: the promoting) heartbeat."""
    if not ha and not standby_of:
        return None
    if ha and standby_of:
        return ("--ha and --standby-of are mutually exclusive: a process "
                "is the primary or the standby, never both")
    if takeover_grace_s <= 0:
        return (f"--takeover-grace-s must be > 0, got {takeover_grace_s}")
    if not wal_dir:
        flag = "--ha" if ha else "--standby-of"
        return (f"{flag} requires --wal-dir: the replicated WAL is what "
                "a takeover recovers unfinished streams from")
    if standby_of:
        if not (standby_of.startswith("http://")
                or standby_of.startswith("https://")):
            return (f"--standby-of must be the primary router's http(s) "
                    f"base URL, got {standby_of!r}")
        if not fleet:
            return ("--standby-of requires --replica-urls with the SAME "
                    "member URLs the primary serves: promotion "
                    "re-registers those members under the new epoch")
    return None


# Closed tier vocabulary (fleet/tiering.py): `interactive` serves the
# latency-sensitive classes (VIP/boost users, deadlined requests), `bulk`
# everything else. The journal schema, metrics labels, and the TUI tiers
# line all read this tuple.
TIER_NAMES = ("interactive", "bulk")


class TiersError(ValueError):
    """Malformed --tiers spec / unresolvable tier assignment."""


def parse_tiers(spec: str) -> dict:
    """Parse a --tiers spec: `tier[@tpW]=sel[,sel...];tier=...` where a
    selector is a member name (`r0`, `h1`) or `tpN` (every member whose
    TP width is N). Returns {tier: {"tp": Optional[int],
    "selectors": [str, ...]}}; raises TiersError on syntax/vocabulary
    problems (assignment problems surface in assign_tiers, which knows
    the members)."""
    out: dict = {}
    for part in (spec or "").split(";"):
        part = part.strip()
        if not part:
            continue
        if "=" not in part:
            raise TiersError(
                f"tier entry {part!r} is not of the form "
                "tier[@tpN]=member[,member...]")
        head, sels = part.split("=", 1)
        head = head.strip()
        tp = None
        if "@" in head:
            head, width = head.split("@", 1)
            head = head.strip()
            width = width.strip()
            if not width.startswith("tp") or not width[2:].isdigit() \
                    or int(width[2:]) < 1:
                raise TiersError(
                    f"tier width {width!r} must be tpN with N >= 1")
            tp = int(width[2:])
        if head not in TIER_NAMES:
            raise TiersError(
                f"unknown tier name {head!r} (tiers: {TIER_NAMES})")
        if head in out:
            raise TiersError(f"tier {head!r} specified twice")
        selectors = [s.strip() for s in sels.split(",") if s.strip()]
        if not selectors:
            raise TiersError(f"tier {head!r} names no members")
        out[head] = {"tp": tp, "selectors": selectors}
    if not out:
        raise TiersError("--tiers spec is empty")
    return out


def assign_tiers(spec: str, members) -> tuple:
    """Resolve a --tiers spec against the fleet roster. `members` is a
    list of (name, tp_width_or_None) pairs. Returns (assignment, widths):
    assignment maps member name -> tier, widths maps tier -> declared
    target TP width (None = re-label only on regroup). Members no
    selector matches default to `bulk`. Raises TiersError when a
    selector names no member, a member lands in two tiers, or a tier
    ends up with no members — the fail-fast contract the CLI and the
    router share."""
    parsed = parse_tiers(spec)
    by_name = {name: tp for name, tp in members}
    assignment: dict = {}
    for tier, entry in parsed.items():
        for sel in entry["selectors"]:
            if sel.startswith("tp") and sel[2:].isdigit():
                width = int(sel[2:])
                matched = [n for n, tp in members if tp == width]
                if not matched:
                    raise TiersError(
                        f"tier {tier!r} selector {sel!r} matches no "
                        f"member (members: {sorted(by_name)})")
            elif sel in by_name:
                matched = [sel]
            else:
                raise TiersError(
                    f"tier {tier!r} selector {sel!r} names no member "
                    f"(members: {sorted(by_name)})")
            for name in matched:
                prev = assignment.get(name)
                if prev is not None and prev != tier:
                    raise TiersError(
                        f"member {name!r} assigned to both {prev!r} "
                        f"and {tier!r}")
                assignment[name] = tier
    for name in by_name:
        assignment.setdefault(name, "bulk")
    widths = {tier: entry["tp"] for tier, entry in parsed.items()}
    for tier in TIER_NAMES:
        widths.setdefault(tier, None)
        if not any(t == tier for t in assignment.values()):
            raise TiersError(
                f"tier {tier!r} has no members — a tiered fleet needs "
                f"at least one member per tier (assignment: {assignment})")
    return assignment, widths


def validate_tiers(spec: Optional[str], members) -> Optional[str]:
    """Fail-fast --tiers validation BEFORE any device work: returns an
    error string (None = valid). Shared by the CLI and the fleet router
    so a typo'd tier name or an empty tier kills the process at startup,
    not at the first placement."""
    if not spec:
        return None
    try:
        assign_tiers(spec, members)
    except TiersError as e:
        return str(e)
    return None


def validate_quant_config(weights_dtype: str, kv_dtype: str,
                          model_names=()) -> Optional[str]:
    """Fail-fast validation of the quantization flags BEFORE any device
    work: returns an error string (None = valid). One definition shared
    by the CLI, the SPMD worker entry, and ModelRuntime so a typo'd or
    unsupported combination can never reach the first dispatch."""
    if weights_dtype not in QUANT_DTYPES:
        return (f"--weights-dtype must be one of {QUANT_DTYPES}, "
                f"got {weights_dtype!r}")
    if kv_dtype not in QUANT_DTYPES:
        return f"--kv-dtype must be one of {QUANT_DTYPES}, got {kv_dtype!r}"
    if weights_dtype == "int8":
        for name in model_names:
            cfg = get_model_config(name)
            if cfg is not None and cfg.num_experts:
                return (f"--weights-dtype=int8 does not cover MoE expert "
                        f"stacks (model {name}); load it in bfloat16")
            held = [k for k in STATE_KINDS if cfg is not None and cfg.count(k)]
            if held:
                return (f"--weights-dtype=int8 does not cover "
                        f"{' or '.join(held)} layers (model {name}); load "
                        "it in bfloat16")
    return None
