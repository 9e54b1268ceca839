"""Analytic MFU accounting: FLOPs per generated token over chip peak.

The FLOPs model is the standard decoder estimate (PaLM appendix B /
Chinchilla): matmul work is 2 x (active) parameters per token, plus
attention score+value work 4 x attention layers x context x q_dim per
token. For
MoE models only routed-active experts count (a Mixtral 8x7b token pays
~13B, not 47B).

Peak FLOPs are the published bf16 dense peaks per chip; unknown
accelerators (CPU meshes in CI) yield None and the engine publishes
mfu=0 rather than a made-up number. OLLAMAMQ_PEAK_FLOPS overrides —
that is also how CPU tests get a deterministic nonzero MFU.

Stdlib only: the ModelConfig duck-types (param_count, count, attn_shape),
so the doc checker and tests can import this without jax.
"""

from __future__ import annotations

import os
from typing import Optional

# Published bf16 dense peak FLOP/s per chip, keyed by the EXACT
# `device_kind` jax reports. The one table of peaks in the package
# (benchmarks/lib/peaks.py keeps the benchmark's own). Source: Google
# Cloud TPU documentation, the "TPU v5e" page (197 TFLOP/s bf16; its 394 figure is int8) and the
# sibling pages of the other generations. A device that is not listed
# has no peak: an unknown kind is never given a neighbour's rate.
PEAK_FLOPS_BY_KIND = {
    "TPU v6 lite": 918e12,  # v6e (Trillium)
    "TPU v5 lite": 197e12,  # v5e
    "TPU v5p": 459e12,
    "TPU v4": 275e12,
    "TPU v3": 123e12,
    "TPU v2": 45e12,
}


def peak_flops_per_chip(device_kind: str) -> Optional[float]:
    """Peak bf16 FLOP/s for one chip, or None if unknown (CPU, new HW)."""
    env = os.environ.get("OLLAMAMQ_PEAK_FLOPS", "")
    if env:
        try:
            return float(env)
        except ValueError:
            pass
    return PEAK_FLOPS_BY_KIND.get(device_kind or "")


def active_param_count(cfg) -> int:
    """Params touched per token: for MoE, the top-k routed experts plus
    router, not the full expert bank; dense models = param_count."""
    return cfg.param_count(active=True)


def _pair_lanes(cfg, kind: str) -> float:
    """What `q_dim` stands for in a (token, cached position) pair's FLOPs,
    of an attention layer of `kind`: heads x (q . k lanes + p . v lanes) / 2
    — `q_dim` itself where a value head is as wide as a key head (every
    model but one with `v_head_dim` beside plain K/V attention: MiMo-V2-Flash,
    64 x (192 + 128) x 2 FLOPs a pair)."""
    a = cfg.attn_shape(kind)
    return a.heads * (a.qk_dim + a.v_dim) / 2


def flops_per_token(cfg, context_len: float = 0.0,
                    exits: bool = False) -> float:
    """Forward FLOPs to generate one token at the given KV context — or,
    `exits`, of a token that leaves a stack with an `exit_layer` there (a
    prompt token nobody samples): the layers below it alone."""
    below = cfg.exit_layer if exits and cfg.exit_layer else None
    dense = 2.0 * cfg.param_count(active=True, first_layers=below)
    # QK^T and attn x V: each 2 x ctx x q_dim MACs = 2 FLOPs, per layer —
    # differential attention's softmax meets BOTH value heads of a pair:
    # half as much again.
    ctx = max(0.0, context_len)
    pair = 6.0 if cfg.mb_per_layer else 4.0
    # ...a cross layer walks the full layer's rows again (a token that
    # exits passes none of them),
    sparse = cfg.count("sparse_attention")
    walks = cfg.paged_layers - sparse \
        + (0 if below else cfg.count("cross_attention"))
    attn = pair * walks * ctx * _pair_lanes(cfg, "full_attention")
    if sparse:
        # ...a block-sparse layer's stops growing past `sparse_dense_len`,
        # at the kept blocks' keys, and pays the block scores instead: a
        # pooled key a `sparse_kernel_stride` positions, 2 FLOPs a lane.
        kept = ctx if ctx <= cfg.sparse_dense_len else min(
            ctx, cfg.sparse_topk * cfg.sparse_block_size)
        attn += sparse * cfg.q_dim * (
            4.0 * kept + (2.0 * ctx / cfg.sparse_kernel_stride
                          if ctx > cfg.sparse_dense_len else 0.0))
    # ...and a window layer attends its last `sliding_window` positions.
    attn += pair * cfg.count("sliding_attention") \
        * min(ctx, cfg.sliding_window) * _pair_lanes(cfg, "sliding_attention")
    if getattr(cfg, "kda", False):
        # ...and Kimi Delta Attention's recurrence, which no parameter
        # counts: a token decays, reads, corrects and reads again a [dk, dv]
        # float32 state a head — 7 FLOPs an element (the decay a key
        # channel, S^T k, the rank-one update, S^T q), whatever the context.
        dense += 7.0 * cfg.count("linear_attention") \
            * cfg.linear_num_value_heads * cfg.linear_key_head_dim \
            * cfg.linear_value_head_dim
    n = getattr(cfg, "streams", 0)
    if n:
        # ...and a residual path of n streams: the mappings' product is in
        # the parameters (Phi); the weighted sums are not — 2 n + n^2
        # multiply-adds a lane of the hidden size an application, two
        # applications a layer, and n a lane in the read-out.
        dense += 2.0 * cfg.hidden_size * (
            2 * cfg.num_layers * (2 * n + n * n) + n)
    return dense + attn


def mfu(cfg, tokens: float, seconds: float, peak_per_chip: Optional[float],
        n_chips: int = 1, context_len: float = 0.0,
        exited: float = 0.0) -> float:
    """Achieved FLOPs over peak, 0..1; 0.0 when unmeasurable. `exited` of
    the `tokens` left the stack at its `exit_layer`: a step's FLOPs are not
    its tokens times all layers."""
    if not peak_per_chip or seconds <= 0 or tokens <= 0 or n_chips < 1:
        return 0.0
    whole = flops_per_token(cfg, context_len)
    flops = tokens * whole
    if exited:
        flops -= exited * (whole - flops_per_token(cfg, context_len,
                                                   exits=True))
    achieved = flops / seconds
    return achieved / (peak_per_chip * n_chips)
