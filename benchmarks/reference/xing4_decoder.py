"""Plain float32 reference of the Xing4.0 decoder family (a residual path of
`hc_mult` STREAMS mixed by manifold-constrained hyper-connections,
arXiv:2512.24880, around DeepSeek-V3's blocks: multi-head latent attention
with YaRN-scaled RoPE and no indexer; a dense prefix, then a shared expert
beside a sigmoid router with a selection bias and no groups over experts that
are ALL held here), and the comparison that decides whether what the server
returned agrees with it.

Independent of the code under test: no paging, no chunking, no cache, no
absorption of W_uk / W_uv, no kernel, no scheduler, no sampling epilogue, no
dispatch and no layer loop of the program's — one sequence, a Python loop over
the layers, the connection written line by line with a Python `for` over the
Sinkhorn iterations, keys and values EXPANDED a head, attention as a dense
causal softmax, EVERY expert computed for EVERY token and weighted by its gate
(zero where not chosen: no sort, no groups of rows), the shared expert once.
Every matmul is float32 at the highest precision. It is computed in blocks —
heads and queries of the [T, T] terms, one expert at a time — so that it fits
beside the served weights; the blocks change no number.

With n = hc_mult, C = hidden_size, X [n, C] a token's streams (X_0: the
token's embedding laid on each), eps = hc_eps, and for one sublayer F of a
layer (F = attention with `hc_attn_*`, then F = FFN with `hc_mlp_*`; Phi
[2 n + n^2, n C] held maps-major, alpha [3], b [2 n + n^2], float32):

    u      = vec(X) / sqrt(mean(vec(X)^2) + rms_norm_eps)       no weight, all n C lanes
    z      = alpha[group] * (Phi u) + b          groups: n | n | n^2
    H_pre  = sigmoid(z[:n]) + eps
    H_post = 2 sigmoid(z[n:2n])
    M      = exp(clip(z[2n:], mhc_h_res_clamp_min, mhc_h_res_clamp_max))   [n, n] row-major
    hc_sinkhorn_iters times:  M <- M / (column sums + eps);  M <- M / (row sums + eps)
    h      = sum_j H_pre[j] X[j]
    d      = F(RMSNorm(h; attn_norm | mlp_norm))
    X'[i]  = sum_j M[i, j] X[j] + H_post[i] d

F = attention (eps' = rms_norm_eps, H heads):

    c_q = RMSNorm(h W_dq);  q_i = (c_q W_uq)_i = [q_c,i | q_r,i]       heads x (nope | rope)
    [c_kv | k_r] = h W_dkv;  c_kv = RMSNorm(c_kv)
    q_r,i, k_r through RoPE (rotate-half; YaRN's frequencies, cos and sin times
        mscale(mscale) / mscale(mscale_all_dim))
    [k_c,i | v_i] = (c_kv W_ukv)_i
    a_i(t) = sum_{s <= t} softmax_s((q_c,i.k_c,i(s) + q_r,i.k_r(s)) * head_dim^-1/2 * mscale(mscale_all_dim)^2) v_i(s)
    d = concat_i(a_i) W_o

F = FFN: layer i < num_dense_layers: SwiGLU(h; w_gate, w_up, w_down); else
    s = sigmoid(h W_r) in float32 over the n_routed_experts; the
    num_experts_per_tok largest of s + router_bias are chosen (no groups);
    g_e = routed_scaling_factor * s_e / (sum of the chosen s + norm_topk_eps)
    d = SwiGLU_shared(h) + sum_{e chosen} g_e SwiGLU_e(h)

After the last layer (`hc_head_*`: Phi_h [n, n C], alpha_h [1], b_h [n]):

    rho = sigmoid(alpha_h * (Phi_h u) + b_h) + eps;   y = sum_j rho[j] X[j]
    logits = RMSNorm(y; final_norm) W_head^T

Departures from the published description, each also in the configuration
file's `assumed` (recalled of arXiv:2512.24880 with no network here — a loader
of real weights must check them): the flattened norm carries no weight; eps
sits inside H_pre and in every Sinkhorn denominator; columns before rows
inside an iteration; M[i, j] mixes FROM stream j INTO stream i; the clamp is
applied to the logits before exp; the read-out before the head is a learned
mix of H_pre's form (config.json does not say how the streams are read out).
The published prediction module (num_nextn_predict_layers 1) is NOT here: the
file reduces it to 0. The weights are seeded random. The prompt is byte
tokens behind a BOS, not the model's tokenizer.

It reads only the configuration FILE's keys and the weights the server serves
by the program's names: `embed`, `lm_head`, `final_norm`, `hc_head_phi`,
`hc_head_alpha`, `hc_head_b`, and under `layers`, each stacked on a leading
axis over the layers that HAVE it: `attn_norm mlp_norm hc_attn_phi
hc_attn_alpha hc_attn_b hc_mlp_phi hc_mlp_alpha hc_mlp_b mla_wdq mla_q_norm
mla_wuq mla_wdkv mla_kv_norm mla_wukv wo` (every layer), `w_gate w_up w_down`
(the dense prefix), `w_router router_bias ws_gate ws_up ws_down` and `we_gate
we_up we_down` [., E, in, out] (expert layers).

The comparison is dense_decoder.py's, restated here so that the files stay
independent: teacher-forced on the ids the server returned, Ollama's
repetition penalty applied as the request's options ask, and `margin` = how
far below the reference's best (penalised) logit the returned id lies, in
standard deviations of that position's logits. A run agrees when the mean
margin over all checked positions is at most MEAN_MARGIN_SD_MAX (weights
served in float32: FLOAT32_MARGIN_SD_MAX).

`check` also reports what a forward one precision BELOW the configuration's
would read (`lower_precision`): the same forward with both operands of every
matmul of the sublayers and the head rounded to float8 (e4m3) — the
connection's own arithmetic, which the configuration states in float32, is
left so — its own greedy choice at each position held to the float32 logits,
over the LAST LOWER_POSITIONS positions of the first LOWER_TOKENS tokens of
the first request's prompt. It has to come out above the limit, or the limit
cannot tell bf16 from worse.

Before any of that, `check` holds the SHAPES of the weights served to the
file's keys (`served_layout`), as the references beside it do: a program
that lacks the architecture ends the run with an error exit and no result
line (the one before PR 69 does not get this far: its ModelConfig has no
field for `hc_mult`, and serve.py ends at start).
"""

from __future__ import annotations

import functools
import math
import os
import signal
import sys

import jax
import jax.numpy as jnp
import numpy as np

# The limit is a statement about the precision the weights are SERVED in, and
# is set from two readings each (PERF.md section 6, PR 69).
#
# bfloat16 — the configuration's, what the cell runs. Serving
# xing4.0-29b-a4b-d6 on a v5e reads a mean margin of 0.111 to 0.143 sd over
# eight runs on eight seeds (my chip runs, PR 69: 8192 positions each — eight
# requests of 1024 outputs — 66.6-72.4 % of them the reference's own argmax,
# 90.8-93.1 % in its top 10, p99 1.54-1.65). That is not the dense cells'
# 0.0004 nor openPangu's 0.001, and it is not an error of a kernel: the SAME
# weights through the jnp path (XLA's own ops for the connection, the
# attention and the experts) read 0.35 where the Pallas path reads 0.39 on one
# sequence of random tokens, and the two differ from EACH OTHER by 0.26
# (scripts/xing4_logits_check.py, on the chip) — two roundings of one
# mathematics; in float32 the program agrees with this reference to 2e-5 in
# every logit (tests/test_xing4.py). It is LFM2's mechanism (lfm2_decoder.py):
# all 64 experts are here, four of them carry the whole routed output after
# normalisation and x 2, and bfloat16 rounding moves a token's choice among
# near-equal sigmoid scores; the float32 forward with only the STREAMS rounded
# to bfloat16 reads 0.006-0.034 (`held_precision`). The same forward with
# float8 operands (`lower_precision`, 128 positions a run) reads 1.138 at the
# least (1.138 to 1.402: 3-9 % argmax). 0.4 lies between, at the two
# readings' geometric mean: 2.8 times the largest bfloat16 reading (fresh seeds
# read higher: the more room is above), under 0.36 of the smallest float8 one.
# The readings are a factor of eight apart, so the limit cannot stand ten times
# under the float8 reading; what a limit this loose still refuses at this size
# is a forward as wrong as float8's, and the fine distinctions are held where
# precision does not blur them (the twelve wrong forwards of
# tests/test_xing4.py, in float32).
MEAN_MARGIN_SD_MAX = 0.4
# float32 — the tiny-size tests (tests/test_xing4.py): there the program's own
# forward, in chunks and then decode through the latent pool and the fused
# scan, agrees with this reference to 2e-5 in every logit (margin 0.0), and a
# forward that changes one line of the connection, the router or the shared
# expert misses by 1e-3 and more in a logit.
FLOAT32_MARGIN_SD_MAX = 0.003
OLLAMA_DEFAULTS = {"repeat_penalty": 1.1, "repeat_last_n": 64}
CONFIG_KEYS = (
    "num_attention_heads", "hidden_size", "rms_norm_eps", "rope_theta",
    "head_dim", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
    "qk_rope_head_dim", "v_head_dim", "num_hidden_layers", "num_dense_layers",
    "n_routed_experts", "num_experts_per_tok", "n_shared_experts",
    "norm_topk_prob", "norm_topk_eps", "routed_scaling_factor",
    "router_score", "moe_intermediate_size", "intermediate_size",
    "vocab_size", "hc_mult", "hc_sinkhorn_iters", "hc_eps",
    "mhc_h_res_clamp_min", "mhc_h_res_clamp_max")
# Blocks (they change no number): queries a block of the [T, T] terms, heads
# a block of the attention, columns a block of a wide FFN.
QUERY_BLOCK, HEAD_BLOCK, FFN_BLOCK = 256, 8, 4608
LOWER_TOKENS, LOWER_POSITIONS = 1024, 128
HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


class NotServed(Exception):
    """The weights served do not have the configuration's architecture."""


def served_layout(cfg: dict, params: dict) -> None:
    """Raises NotServed unless every weight the reference reads has the shape
    the configuration file's keys give it."""
    if cfg.get("head_dim") != cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] \
            or cfg.get("router_score") != "sigmoid" \
            or not cfg.get("use_expert_bias") \
            or cfg.get("hc_mult", 0) < 2 \
            or cfg.get("num_nextn_predict_layers") \
            or (cfg.get("rope_scaling") or {}).get("type") != "yarn" \
            or cfg.get("index_topk") or cfg.get("n_group", 1) > 1 \
            or cfg.get("sandwich_norm"):
        raise NotServed("this reference is the family's: head_dim = nope + "
                        "rope, router_score 'sigmoid' with a selection bias "
                        "and no groups, hc_mult streams, rope_scaling of type "
                        "'yarn', no prediction module, no indexer")
    lp = params["layers"]
    n_all = cfg["num_hidden_layers"]
    n = {"all": n_all, "dense": cfg["num_dense_layers"],
         "experts": n_all - cfg["num_dense_layers"]}
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    r, c = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    e = cfg["n_routed_experts"]
    f, fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    fs = fe * cfg["n_shared_experts"]
    s = cfg["hc_mult"]
    maps = 2 * s + s * s
    want = {
        "attn_norm": ("all", (d,)), "mlp_norm": ("all", (d,)),
        "hc_attn_phi": ("all", (maps, s * d)), "hc_attn_alpha": ("all", (3,)),
        "hc_attn_b": ("all", (maps,)),
        "hc_mlp_phi": ("all", (maps, s * d)), "hc_mlp_alpha": ("all", (3,)),
        "hc_mlp_b": ("all", (maps,)),
        "mla_wdq": ("all", (d, r)), "mla_q_norm": ("all", (r,)),
        "mla_wuq": ("all", (r, H * (dn + dr))),
        "mla_wdkv": ("all", (d, c + dr)), "mla_kv_norm": ("all", (c,)),
        "mla_wukv": ("all", (c, H * (dn + dv))), "wo": ("all", (H * dv, d)),
        "w_gate": ("dense", (d, f)), "w_up": ("dense", (d, f)),
        "w_down": ("dense", (f, d)),
        "w_router": ("experts", (d, e)), "router_bias": ("experts", (e,)),
        "ws_gate": ("experts", (d, fs)), "ws_up": ("experts", (d, fs)),
        "ws_down": ("experts", (fs, d)),
        "we_gate": ("experts", (e, d, fe)), "we_up": ("experts", (e, d, fe)),
        "we_down": ("experts", (e, fe, d))}
    bad = [f"{name} is {tuple(lp[name].shape) if name in lp else 'absent'}, "
           f"the configuration's is {(n[kind], *shape)}"
           for name, (kind, shape) in want.items()
           if n[kind] and (name not in lp
                           or tuple(lp[name].shape) != (n[kind], *shape))]
    bad += [f"{name} is served: the configuration has no indexer, no second "
            "norm a sublayer and no prediction module"
            for name in ("idx_wq", "post_attn_norm") if name in lp]
    bad += ["mtp_eh_proj is served: the configuration has no prediction "
            "module"] * ("mtp_eh_proj" in params)
    v = cfg["vocab_size"]
    top = {"embed": (v, d), "lm_head": (v, d), "final_norm": (d,),
           "hc_head_phi": (s, s * d), "hc_head_alpha": (1,),
           "hc_head_b": (s,)}
    bad += [f"{name} is not {shape}" for name, shape in top.items()
            if name not in params or tuple(params[name].shape) != shape]
    if bad:
        raise NotServed("; ".join(bad))


def param_count(cfg: dict) -> int:
    """Parameters of the configuration as served, from the file's keys (what
    the file's `arithmetic` reckons; tests hold the served tree to it)."""
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    r, c = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    fe, s = cfg["moe_intermediate_size"], cfg["hc_mult"]
    maps = 2 * s + s * s
    every = 2 * d + 2 * (s * d * maps + maps + 3)  # norms, two connections
    mla = (d * r + r + r * H * (dn + dr) + d * (c + dr) + c
           + c * H * (dn + dv) + H * dv * d)
    dense = mla + every + 3 * d * cfg["intermediate_size"]
    sparse = mla + every + (d + 1) * cfg["n_routed_experts"] + 3 * d * fe * (
        cfg["n_shared_experts"] + cfg["n_routed_experts"])
    n_dense = cfg["num_dense_layers"]
    return (n_dense * dense + (cfg["num_hidden_layers"] - n_dense) * sparse
            + 2 * cfg["vocab_size"] * d + d + s * d * s + s + 1)


def cannot_run(reason: str):
    """The program under test lacks the configuration's architecture: end the
    run with an error exit and no result line (lfm2_decoder.py has the
    mechanism's account)."""
    print(f"xing4_decoder: the program cannot run this "
          f"configuration: {reason}", file=sys.stderr, flush=True)
    os.kill(os.getpid(), signal.SIGTERM)
    raise SystemExit(reason)


def _exact(x):
    return x


def _float8(x):
    """x rounded to float8 e4m3 and back: the precision below bfloat16."""
    return x.astype(jnp.float8_e4m3fn).astype(F32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(F32)


def yarn_inv_freq(cfg: dict):
    """YaRN's inverse frequencies [rope/2], what it multiplies cos and sin by,
    and mscale(mscale_all_dim), whose square rides the softmax scale (the
    family's inference code: find_correction_range, a linear ramp between
    the dimensions that rotate beta_fast and beta_slow times over the
    original context)."""
    y, dim, base = dict(cfg["rope_scaling"]), cfg["qk_rope_head_dim"], \
        float(cfg["rope_theta"])
    factor, orig = float(y["factor"]), \
        float(y["original_max_position_embeddings"])

    def correction(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction(float(y.get("beta_fast", 32)))), 0)
    high = min(math.ceil(correction(float(y.get("beta_slow", 1)))), dim - 1)
    plain = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    inv = plain / factor * ramp + plain * (1.0 - ramp)

    def mscale(m):
        return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0

    return (jnp.asarray(inv, F32),
            mscale(y.get("mscale", 1)) / mscale(y.get("mscale_all_dim", 0)),
            mscale(y.get("mscale_all_dim", 0)))


def _rope(cfg: dict, x):
    """Rotate-half RoPE over x [T, H, qk_rope_head_dim] at positions 0..T-1,
    YaRN's frequencies."""
    inv, cos_scale, _ = yarn_inv_freq(cfg)
    dr = cfg["qk_rope_head_dim"]
    ang = jnp.arange(x.shape[0], dtype=F32)[:, None] * inv
    cos = (jnp.cos(ang) * cos_scale)[:, None, :]
    sin = (jnp.sin(ang) * cos_scale)[:, None, :]
    a, b = x[..., : dr // 2], x[..., dr // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(cfg: dict, mm, rnd, h, lp: dict, i: int):
    t = h.shape[0]
    H, c = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    eps = cfg["rms_norm_eps"]
    scale = cfg["head_dim"] ** -0.5 * yarn_inv_freq(cfg)[2] ** 2
    c_q = _rms(mm(h, lp["mla_wdq"][i]), lp["mla_q_norm"][i], eps)
    kv = mm(h, lp["mla_wdkv"][i])
    c_kv = _rms(kv[:, :c], lp["mla_kv_norm"][i], eps)
    k_r = _rope(cfg, kv[:, None, c:])  # [T, 1, dr]: one for all heads
    hb = math.gcd(HEAD_BLOCK, H)
    w_uq = lp["mla_wuq"][i].reshape(-1, H, dn + dr)
    w_ukv = lp["mla_wukv"][i].reshape(c, H, dn + dv)
    w_o = lp["wo"][i].reshape(H, dv, -1)
    pos = jnp.arange(t)

    def heads(delta, h0):  # hb heads at a time: projected, expanded, attended
        def of(w):  # the block's heads of a [in, H, out] weight, as a matrix
            w = jax.lax.dynamic_slice_in_dim(w, h0, hb, 1)
            return w.reshape(w.shape[0], -1)

        qh = mm(c_q, of(w_uq)).reshape(t, hb, dn + dr)
        qh = rnd(jnp.concatenate(
            [qh[..., :dn], _rope(cfg, qh[..., dn:])], axis=-1))
        uh = mm(c_kv, of(w_ukv)).reshape(t, hb, dn + dv)
        kh = rnd(jnp.concatenate(
            [uh[..., :dn], jnp.broadcast_to(k_r, (t, hb, dr))], axis=-1))
        vh = rnd(uh[..., dn:])

        def block(b):
            q0 = b * QUERY_BLOCK
            qb = jax.lax.dynamic_slice_in_dim(qh, q0, QUERY_BLOCK)
            causal = pos[None, :] <= (q0 + jnp.arange(QUERY_BLOCK))[:, None]
            s = jnp.einsum("qhd,shd->hqs", qb, kh, precision=HI) * scale
            p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
            return jnp.einsum("hqs,shd->qhd", rnd(p), vh, precision=HI)

        o = jax.lax.map(block, jnp.arange(t // QUERY_BLOCK)
                        ).reshape(t, hb * dv)
        w_oh = jax.lax.dynamic_slice_in_dim(w_o, h0, hb, 0)
        return delta + mm(o, w_oh.reshape(hb * dv, -1)), None

    delta, _ = jax.lax.scan(heads, jnp.zeros_like(h), jnp.arange(0, H, hb))
    return delta


def _swiglu(mm, h, gate, up, down):
    """(silu(h gate) * (h up)) down, the FFN's columns FFN_BLOCK at a time."""
    f = gate.shape[-1]
    n = f // FFN_BLOCK if f % FFN_BLOCK == 0 else 1
    if n == 1:
        return mm(jax.nn.silu(mm(h, gate)) * mm(h, up), down)
    gate, up = (w.reshape(-1, n, f // n) for w in (gate, up))
    down = down.reshape(n, f // n, -1)

    def columns(acc, j):
        g, u = (jax.lax.dynamic_index_in_dim(w, j, 1, keepdims=False)
                for w in (gate, up))
        d = jax.lax.dynamic_index_in_dim(down, j, 0, keepdims=False)
        return acc + mm(jax.nn.silu(mm(h, g)) * mm(h, u), d), None

    return jax.lax.scan(columns, jnp.zeros_like(h), jnp.arange(n))[0]


def gates(cfg: dict, h, lp: dict, e: int):
    """[T, n_routed_experts] float32: the gate of every expert, zero where
    not chosen. Sigmoid scores; the num_experts_per_tok largest of score +
    selection bias; the chosen SCORES (no bias) divided by their sum, times
    routed_scaling_factor. No groups."""
    s = jax.nn.sigmoid(jnp.matmul(h, lp["w_router"][e].astype(F32),
                                  precision=HI))
    _, idx = jax.lax.top_k(s + lp["router_bias"][e].astype(F32),
                           cfg["num_experts_per_tok"])
    top = jnp.take_along_axis(s, idx, axis=-1)
    if cfg.get("norm_topk_prob"):
        top = top / (top.sum(axis=-1, keepdims=True)
                     + cfg.get("norm_topk_eps", 0.0))
    top = top * cfg.get("routed_scaling_factor", 1)
    return jnp.zeros_like(s).at[jnp.arange(s.shape[0])[:, None], idx].set(top)


def _experts(cfg: dict, mm, h, lp: dict, e: int, route=_exact):
    w = gates(cfg, route(h), lp, e)  # (`route`: _layer's `held` "router")

    def one(name, j):  # expert j's matrix, read out of the whole stack
        stack = lp[name]
        return jax.lax.dynamic_slice(
            stack, (e, j, 0, 0), (1, 1) + stack.shape[2:])[0, 0]

    def expert(acc, j):  # one expert over every token, weighted
        y = _swiglu(mm, h, one("we_gate", j), one("we_up", j),
                    one("we_down", j))
        return acc + jax.lax.dynamic_index_in_dim(
            w, j, 1, keepdims=False)[:, None] * y, None

    routed, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                             jnp.arange(cfg["n_routed_experts"]))
    return routed + _swiglu(mm, h, lp["ws_gate"][e], lp["ws_up"][e],
                            lp["ws_down"][e])


def _flat_norm(cfg: dict, x):
    """u [T, n C]: a token's streams x [T, n, C] flattened, over the root of
    the mean square of ALL n C lanes (+ rms_norm_eps): no weight."""
    flat = x.reshape(x.shape[0], -1)
    return flat * jax.lax.rsqrt(
        jnp.mean(flat * flat, axis=-1, keepdims=True) + cfg["rms_norm_eps"])


def mappings(cfg: dict, x, phi, alpha, b):
    """(H_pre [T, n], H_post [T, n], H_res [T, n, n]) of streams x [T, n, C]
    under one sublayer's Phi [2 n + n^2, n C], alpha [3], b [2 n + n^2]."""
    n, eps = cfg["hc_mult"], cfg["hc_eps"]
    a = jnp.matmul(_flat_norm(cfg, x), phi.astype(F32).T, precision=HI)
    pre = jax.nn.sigmoid(alpha[0] * a[:, :n] + b[:n]) + eps
    post = 2.0 * jax.nn.sigmoid(alpha[1] * a[:, n:2 * n] + b[n:2 * n])
    m = jnp.exp(jnp.clip(alpha[2] * a[:, 2 * n:] + b[2 * n:],
                         cfg["mhc_h_res_clamp_min"],
                         cfg["mhc_h_res_clamp_max"])).reshape(-1, n, n)
    for _ in range(cfg["hc_sinkhorn_iters"]):
        m = m / (m.sum(axis=1, keepdims=True) + eps)  # each column: over i
        m = m / (m.sum(axis=2, keepdims=True) + eps)  # each row: over j
    return pre, post, m


def _sublayer(cfg: dict, x, lp: dict, name: str, norm: str, i, fn):
    """X' of streams x [T, n, C] around the sublayer `fn(normed h)`."""
    pre, post, res = mappings(
        cfg, x, lp[name + "_phi"][i], lp[name + "_alpha"][i].astype(F32),
        lp[name + "_b"][i].astype(F32))
    h = jnp.einsum("tj,tjc->tc", pre, x, precision=HI)
    d = fn(_rms(h, lp[norm][i], cfg["rms_norm_eps"]))
    return jnp.einsum("tij,tjc->tic", res, x, precision=HI) \
        + post[:, :, None] * d[:, None, :]


def read_out(cfg: dict, params: dict, x):
    """y [T, C]: the streams x [T, n, C] under the learned mix the final norm
    reads."""
    a = jnp.matmul(_flat_norm(cfg, x), params["hc_head_phi"].astype(F32).T,
                   precision=HI)
    rho = jax.nn.sigmoid(params["hc_head_alpha"].astype(F32)[0] * a
                         + params["hc_head_b"].astype(F32)) + cfg["hc_eps"]
    return jnp.einsum("tj,tjc->tc", rho, x, precision=HI)


def _bfloat16(x):
    """x rounded to bfloat16 and back: what the configuration HOLDS the
    streams in between sublayers."""
    return x.astype(jnp.bfloat16).astype(F32)


@functools.partial(jax.jit, static_argnames=("cfg_items", "sparse", "lower",
                                             "held"))
def _layer(params, x, i, e, cfg_items, sparse: bool, lower: bool,
           held=False):
    """X' [T, n, C] of the layer at entry i of the stacks every layer has
    (its experts at entry e of the expert layers'); traced: ONE program a
    kind of layer; the blocks inside are loops, so it compiles small and its
    temporaries are freed before the next layer. `held` (true, "streams"):
    the streams rounded to bfloat16 after each sublayer and NOTHING else
    (`held_precision`); "router": the ROUTER's input alone
    (scripts/xing4_logits_check.py)."""
    cfg = dict(cfg_items)
    rnd = _float8 if lower else _exact
    keep = _bfloat16 if held in (True, "streams") else _exact
    route = _bfloat16 if held == "router" else _exact

    def mm(a, w):
        return jnp.matmul(rnd(a), rnd(w.astype(F32)), precision=HI)

    lp = params["layers"]
    x = keep(_sublayer(cfg, x, lp, "hc_attn", "attn_norm", i,
                       lambda h: _attention(cfg, mm, rnd, h, lp, i)))
    if sparse:
        return keep(_sublayer(cfg, x, lp, "hc_mlp", "mlp_norm", i,
                              lambda h: _experts(cfg, mm, h, lp, e, route)))
    return keep(_sublayer(
        cfg, x, lp, "hc_mlp", "mlp_norm", i, lambda h: _swiglu(
            mm, h, lp["w_gate"][i], lp["w_up"][i], lp["w_down"][i])))


def _cfg_items(cfg: dict) -> tuple:
    items = {k: cfg[k] for k in CONFIG_KEYS if k in cfg}
    items["rope_scaling"] = tuple(sorted(dict(cfg["rope_scaling"]).items()))
    return tuple(sorted(items.items()))


def residual(cfg: dict, params: dict, tokens, lower: bool = False,
             held=False):
    """The last layer's streams [T, n, C] of one sequence `tokens` [T] (T a
    multiple of QUERY_BLOCK), BEFORE the read-out and the final norm."""
    items = _cfg_items(cfg)
    nd = cfg["num_dense_layers"]
    e = params["embed"][tokens].astype(F32)
    x = jnp.broadcast_to(e[:, None, :], (e.shape[0], cfg["hc_mult"],
                                         e.shape[1]))
    for i in range(cfg["num_hidden_layers"]):
        x = _layer(params, x, np.int32(i), np.int32(max(0, i - nd)), items,
                   i >= nd, lower, held)
    return x


def hidden(cfg: dict, params: dict, tokens, lower: bool = False,
           held=False):
    """Final-norm hidden states [T, C] of one sequence."""
    return _rms(read_out(cfg, params,
                         residual(cfg, params, tokens, lower, held)),
                params["final_norm"], cfg["rms_norm_eps"])


def head_logits(params: dict, h, lower: bool = False):
    rnd = _float8 if lower else _exact
    return jnp.matmul(rnd(h), rnd(params["lm_head"].astype(F32)).T,
                      precision=HI)


def _padded(tokens):
    t = len(tokens)
    return t, jnp.zeros((-(-t // QUERY_BLOCK) * QUERY_BLOCK,), jnp.int32
                        ).at[:t].set(jnp.asarray(tokens, jnp.int32))


def logits(cfg: dict, params: dict, tokens):
    """[T, V] float32 logits of one sequence (padded here to whole query
    blocks; causal attention keeps padding from every earlier position):
    what the tier-1 tests hold the served path's logits to."""
    t, padded = _padded(tokens)
    return head_logits(params, hidden(cfg, params, padded)[:t])


def _penalised(logit, tokens, at, penalty, last_n):
    """Ollama's repetition penalty over the last_n context tokens before
    each position of `at`."""
    back = at[:, None] - jnp.arange(last_n)[None, :]
    seen = jnp.zeros(logit.shape, bool).at[
        jnp.arange(at.shape[0])[:, None], tokens[jnp.clip(back, 0)]
    ].max(back >= 0)
    return jnp.where(seen, jnp.where(logit > 0, logit / penalty,
                                     logit * penalty), logit)


@functools.partial(jax.jit, static_argnames=("last_n",))
def _choice(logit, tokens, at, penalty, last_n):
    return jnp.argmax(_penalised(logit, tokens, at, penalty, last_n), axis=-1)


@functools.partial(jax.jit, static_argnames=("last_n",))
def _margins(logit, tokens, at, chosen, penalty, last_n):
    """For each position of `at`: (margin in sd, ids the reference ranks
    above `chosen`), under the repetition penalty."""
    sd = jnp.maximum(logit.std(axis=-1, keepdims=True), 1e-30)
    logit = _penalised(logit, tokens, at, penalty, last_n)
    got = jnp.take_along_axis(logit, chosen[:, None], axis=-1)
    margin = (logit.max(axis=-1, keepdims=True) - got) / sd
    return margin[:, 0], (logit > got).sum(axis=-1)


def _lower_precision(cfg, params, tokens, n_prompt, penalty, last_n):
    """The float8 forward's own greedy choices held to the float32 logits,
    at the last LOWER_POSITIONS positions of the prompt's first
    min(LOWER_TOKENS, its whole blocks) tokens."""
    t = min(LOWER_TOKENS, n_prompt // QUERY_BLOCK * QUERY_BLOCK)
    if t < QUERY_BLOCK:
        return None
    n = min(LOWER_POSITIONS, t - 1)
    at = jnp.arange(t - n, t)
    short = jnp.asarray(tokens[:t])
    exact = head_logits(params, hidden(cfg, params, short)[at])

    def reading(logit):
        chosen = _choice(logit, short, at, penalty, last_n)
        m, a = _margins(exact, short, at, chosen, penalty, last_n)
        return {"mean_margin_sd": float(np.asarray(m).mean()),
                "argmax_share": float((np.asarray(a) == 0).mean())}

    low = head_logits(params, hidden(cfg, params, short, True)[at], True)
    # ...and the float32 forward with NOTHING changed but the streams rounded
    # to bfloat16 between sublayers, as the configuration holds them: what
    # of a served reading is the stated precision's own (routing flips among
    # near-equal sigmoid scores of experts that are all here).
    kept = head_logits(params, hidden(cfg, params, short, held=True)[at])
    return {"precision": "float8_e4m3fn", "positions": int(at.size),
            "tokens": int(t), **reading(low),
            "held_precision": {"streams": "bfloat16", **reading(kept)}}


def check(cfg: dict, params: dict, requests: list, pad_to: int,
          max_out: int) -> dict:
    """`requests`: [{"prompt": text, "ids": returned ids, "options": the
    request's Ollama options}]. The prompt is byte tokens behind a BOS (id 1,
    byte b -> b + 3), as the configuration serves it."""
    try:
        served_layout(cfg, params)
    except NotServed as e:
        cannot_run(str(e))
    pad_to = -(-pad_to // QUERY_BLOCK) * QUERY_BLOCK
    margins, ranks, per_request, lower = [], [], [], None
    for r in requests:
        prompt = [1] + [b + 3 for b in r["prompt"].encode()]
        ids = list(r["ids"])
        n = len(prompt) + len(ids)
        if not ids or len(ids) > max_out or n > pad_to:
            raise ValueError(f"request of {len(prompt)} + {len(ids)} tokens "
                             f"does not fit {pad_to} / {max_out}")
        tokens = np.zeros((pad_to,), np.int32)
        tokens[:n] = prompt + ids
        opts = {**OLLAMA_DEFAULTS, **(r.get("options") or {})}
        if opts.get("temperature", 0.8) != 0:
            raise ValueError("only a greedy request has one right answer")
        penalty = np.float32(opts["repeat_penalty"] or 1.0)
        last_n = int(opts["repeat_last_n"])
        toks = jnp.asarray(tokens)
        at = jnp.clip(len(prompt) - 1 + jnp.arange(max_out), 0, pad_to - 1)
        logit = head_logits(params, hidden(cfg, params, toks)[at])
        chosen = toks[jnp.clip(at + 1, 0, pad_to - 1)]
        m, a = _margins(logit, toks, at, chosen, penalty, last_n)
        m, a = np.asarray(m)[:len(ids)], np.asarray(a)[:len(ids)]
        margins.append(m)
        ranks.append(a)
        per_request.append({"prompt_tokens": len(prompt), "outputs": len(ids),
                            "mean_margin_sd": float(m.mean()),
                            "argmax_share": float((a == 0).mean())})
        if lower is None:
            lower = _lower_precision(cfg, params, tokens, len(prompt),
                                     penalty, last_n) or {}
    m, a = np.concatenate(margins), np.concatenate(ranks)
    mean = float(m.mean())
    limit = FLOAT32_MARGIN_SD_MAX if params["embed"].dtype == jnp.float32 \
        else MEAN_MARGIN_SD_MAX
    return {"agrees": bool(np.isfinite(mean) and mean <= limit),
            "requests": len(requests), "positions": int(m.size),
            "mean_margin_sd": mean, "mean_margin_sd_max": limit,
            "p99_margin_sd": float(np.quantile(m, 0.99)),
            "max_margin_sd": float(m.max()),
            "argmax_share": float((a == 0).mean()),
            "top10_share": float((a < 10).mean()),
            "lower_precision": lower or None,
            "per_request": per_request}
