"""Plain float32 reference of the openPangu-Ultra-MoE decoder family
(multi-head latent attention with plain RoPE and NO indexer, sandwich norms,
a dense prefix, then a shared expert beside a sigmoid router with no groups
and no selection bias over the published router — of whose experts the
configuration HOLDS a share — and the multi-token-prediction module), and the
comparison that decides whether what the server returned agrees with it.

Independent of the code under test: no paging, no chunking, no cache, no
absorption of W_uk / W_uv, no kernel, no scheduler, no sampling epilogue, no
drafts, no verify spans, no dispatch and no layer loop of the program's — one
sequence, a Python loop over the layers, keys and values EXPANDED a head,
attention as a dense causal softmax, EVERY held expert computed for EVERY
token and weighted by its gate (zero where not chosen: no sort, no groups of
rows), the shared expert once. Every matmul is float32 at the highest
precision. It is computed in blocks — heads and queries of the [T, T] terms,
one expert at a time — so that it fits beside the served weights; the blocks
change no number. Layer i, with `x` the residual, eps rms_norm_eps, all norms
RMSNorm (`sandwich_norm`: one on a sublayer's input AND one on its output):

    h = RMSNorm(x; attn_norm)
    c_q = RMSNorm(h W_dq)                              [q_lora_rank]
    q_i = (c_q W_uq)_i = [q_c,i | q_r,i]               heads x (nope | rope)
    [c_kv | k_r] = h W_dkv;  c_kv = RMSNorm(c_kv)      [kv_lora_rank | rope]
    q_r,i, k_r through RoPE (rotate-half, theta rope_theta, no scaling)
    [k_c,i | v_i] = (c_kv W_ukv)_i                     heads x (nope | v)
    a_i(t) = sum_{s <= t} softmax_s((q_c,i.k_c,i(s) + q_r,i.k_r(s)) * head_dim^-1/2) v_i(s)
    x = x + RMSNorm(concat_i(a_i) W_o; post_attn_norm)
    h = RMSNorm(x; mlp_norm)
    i <  num_dense_layers: m = SwiGLU(h; w_gate, w_up, w_down)
    i >= num_dense_layers: s = sigmoid(h W_r) in float32, over ALL router_experts;
        the num_experts_per_tok largest s are chosen (no groups, no bias);
        g_e = routed_scaling_factor * s_e / (sum of the chosen s + norm_topk_eps)
        m = SwiGLU_shared(h) + sum_{e chosen AND held} g_e SwiGLU_e(h)
        held: experts expert_offset .. expert_offset + n_routed_experts - 1.
    x = x + RMSNorm(m; post_mlp_norm)
    logits = RMSNorm(x; final_norm) W_head^T   (the head's rows are the served slice)

The prediction module (depth 1, the DeepSeek-V3 formulation the key
`num_nextn_predict_layers` belongs to), with x_i the trunk's last residual of
position i BEFORE final_norm and t_{i+1} the token that follows:

    u_i = [RMSNorm(Emb(t_{i+1}); mtp_enorm) | RMSNorm(x_i; mtp_hnorm)] W_eh
    v = Block(u)     one more expert layer as above (sandwich norms, latent
                     attention causal over the module's OWN u, RoPE at
                     position i), the LAST entry of every stack it has
    logits_mtp,i = RMSNorm(v_i; mtp_norm) W_head^T      a prediction of t_{i+2}

Emb and the head are the trunk's. The gates are normalised over all the
chosen experts, held or not; what the absent experts would have added is left
out — here as in the program — and that partial result goes on to the next
layer (model-configs guide, section 4). What the configuration file's
`assumed` lists is assumed here too: the sigmoid score (the config has no
`scoring_func`), the order of the concatenation, the module's norm names and
its positions, rotate-half RoPE. The weights are seeded random. The prompt is
byte tokens behind a BOS, not the model's tokenizer.

It reads only the configuration FILE's keys and the weights the server serves
by the program's names: `embed`, `lm_head`, `final_norm`, `mtp_enorm`,
`mtp_hnorm`, `mtp_eh_proj`, `mtp_norm`, and under `layers`, each stacked on a
leading axis over the layers that HAVE it, the module's block LAST: `attn_norm
mlp_norm post_attn_norm post_mlp_norm mla_wdq mla_q_norm mla_wuq mla_wdkv
mla_kv_norm mla_wukv wo` (every layer + 1), `w_gate w_up w_down` (the dense
prefix), `w_router ws_gate ws_up ws_down` and `we_gate we_up we_down` [., E
held, in, out] (expert layers + 1).

The comparison is dense_decoder.py's, restated here so that the files stay
independent: teacher-forced on the ids the server returned, Ollama's
repetition penalty applied as the request's options ask, and `margin` = how
far below the reference's best (penalised) logit the returned id lies, in
standard deviations of that position's logits. A run agrees when the mean
margin over all checked positions is at most MEAN_MARGIN_SD_MAX (weights
served in float32: FLOAT32_MARGIN_SD_MAX). The ids are the TRUNK's: a served
path that speculates returns exactly the ids greedy decoding returns, so the
drafts can move no id, only how many leave a step. What the module would
draft is evaluated all the same (`mtp`): at every checked position the
reference module's best id, teacher-forced, against the id that really came
two places on — the acceptance a served module can reach on these weights
(about one in `vocab_size` with seeded random ones).

`check` also reports what a forward one precision BELOW the configuration's
would read (`lower_precision`): the same forward with both operands of every
matmul rounded to float8 (e4m3), its own greedy choice at each position held
to the float32 logits — over the LAST LOWER_POSITIONS positions of the first
LOWER_TOKENS tokens of the first request's prompt. It has to come out above
the limit, or the limit cannot tell bf16 from worse.

Before any of that, `check` holds the SHAPES of the weights served to the
file's keys (`served_layout`), as the references beside it do: a program
that lacks the architecture ends the run with an error exit and no result
line (the one before PR 42 does not get this far: its ModelConfig has no
field for `sandwich_norm`, and serve.py ends at start).
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
# is set from two readings each (PERF.md section 6, PR 42).
#
# bfloat16 — the configuration's, what the cell runs. Serving
# openpangu-ultra-moe-ep16-d5 on a v5e reads a mean margin of 0.00043 to
# 0.00102 sd over my twelve chip runs of PR 42, a seed each (8192 positions
# each: eight requests of 1024 outputs; the largest single margin 0.24 to
# 0.53, the 99th percentile 0.007 to 0.013: the mean is a few flips of two
# near-equal logits). The same forward with float8 operands
# (`lower_precision`, 128 positions a run) reads 0.129 at the least (0.129 to
# 0.312). 0.02 lies between: 20 times the largest bfloat16 reading, a sixth
# of the smallest float8 one. (The family's other cell, whose contexts are
# eight times as long and whose selection is a threshold, reads ten times as
# much in bfloat16 and keeps 0.08: deepseek_v32_decoder.py.)
MEAN_MARGIN_SD_MAX = 0.02
# float32 — the tiny-size tests (tests/test_openpangu.py): there the program's
# own forward, in chunks and verify spans through the latent pool, agrees with
# this reference to 1e-5 in every logit (margin 0.0), and a forward that
# leaves out a sandwich norm, the shared expert or the 2.5 scale misses by
# 0.01 and more in a logit.
FLOAT32_MARGIN_SD_MAX = 0.003
OLLAMA_DEFAULTS = {"repeat_penalty": 1.1, "repeat_last_n": 64}
CONFIG_KEYS = (
    "num_attention_heads", "hidden_size", "rms_norm_eps", "rope_theta",
    "head_dim", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
    "qk_rope_head_dim", "v_head_dim", "num_hidden_layers", "num_dense_layers",
    "n_routed_experts", "router_experts", "expert_offset",
    "num_experts_per_tok", "n_shared_experts", "norm_topk_prob",
    "norm_topk_eps", "routed_scaling_factor", "router_score",
    "moe_intermediate_size", "intermediate_size", "vocab_size",
    "sandwich_norm", "num_nextn_predict_layers")
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
            or not cfg.get("sandwich_norm") \
            or cfg.get("num_nextn_predict_layers") != 1 \
            or cfg.get("rope_scaling") or cfg.get("index_topk") \
            or cfg.get("n_group") or cfg.get("use_expert_bias"):
        raise NotServed("this reference is the family's: head_dim = nope + "
                        "rope, router_score 'sigmoid' with no groups and no "
                        "bias, sandwich_norm true, one prediction module, "
                        "no rope_scaling, no indexer")
    lp = params["layers"]
    n_all = cfg["num_hidden_layers"]
    n = {"all": n_all + 1, "dense": cfg["num_dense_layers"],
         "experts": n_all - cfg["num_dense_layers"] + 1}
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    r, c = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    e, R = cfg["n_routed_experts"], cfg["router_experts"]
    f, fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    fs = fe * cfg["n_shared_experts"]
    want = {
        "attn_norm": ("all", (d,)), "mlp_norm": ("all", (d,)),
        "post_attn_norm": ("all", (d,)), "post_mlp_norm": ("all", (d,)),
        "mla_wdq": ("all", (d, r)), "mla_q_norm": ("all", (r,)),
        "mla_wuq": ("all", (r, H * (dn + dr))),
        "mla_wdkv": ("all", (d, c + dr)), "mla_kv_norm": ("all", (c,)),
        "mla_wukv": ("all", (c, H * (dn + dv))), "wo": ("all", (H * dv, d)),
        "w_gate": ("dense", (d, f)), "w_up": ("dense", (d, f)),
        "w_down": ("dense", (f, d)),
        "w_router": ("experts", (d, R)),
        "ws_gate": ("experts", (d, fs)), "ws_up": ("experts", (d, fs)),
        "ws_down": ("experts", (fs, d)),
        "we_gate": ("experts", (e, d, fe)), "we_up": ("experts", (e, d, fe)),
        "we_down": ("experts", (e, fe, d))}
    bad = [f"{name} is {tuple(lp[name].shape) if name in lp else 'absent'}, "
           f"the configuration's is {(n[kind], *shape)}"
           for name, (kind, shape) in want.items()
           if n[kind] and (name not in lp
                           or tuple(lp[name].shape) != (n[kind], *shape))]
    bad += [f"{name} is served: the configuration has no indexer and no "
            "selection bias" for name in ("idx_wq", "router_bias")
            if name in lp]
    v = cfg["vocab_size"]
    top = {"embed": (v, d), "lm_head": (v, d), "final_norm": (d,),
           "mtp_enorm": (d,), "mtp_hnorm": (d,), "mtp_eh_proj": (2 * d, d),
           "mtp_norm": (d,)}
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
    fe = cfg["moe_intermediate_size"]
    norms = 4 * d
    mla = (d * r + r + r * H * (dn + dr) + d * (c + dr) + c
           + c * H * (dn + dv) + H * dv * d)
    dense = mla + norms + 3 * d * cfg["intermediate_size"]
    sparse = mla + norms + d * cfg["router_experts"] + 3 * d * fe * (
        cfg["n_shared_experts"] + cfg["n_routed_experts"])
    n_dense = cfg["num_dense_layers"]
    module = sparse + 3 * d + 2 * d * d
    return (n_dense * dense + (cfg["num_hidden_layers"] - n_dense) * sparse
            + module + 2 * cfg["vocab_size"] * d + d)


def cannot_run(reason: str):
    """The program under test lacks the configuration's architecture: end the
    run with an error exit and no result line (lfm2_decoder.py has the
    mechanism's account)."""
    print(f"openpangu_ultra_decoder: the program cannot run this "
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


def _rope(cfg: dict, x):
    """Rotate-half RoPE over x [T, H, qk_rope_head_dim] at positions 0..T-1."""
    dr = cfg["qk_rope_head_dim"]
    inv = jnp.asarray(float(cfg["rope_theta"])
                      ** (-np.arange(0, dr, 2, dtype=np.float64) / dr), F32)
    ang = jnp.arange(x.shape[0], dtype=F32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., : dr // 2], x[..., dr // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(cfg: dict, mm, rnd, h, lp: dict, i: int):
    t = h.shape[0]
    H, c = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    eps = cfg["rms_norm_eps"]
    scale = cfg["head_dim"] ** -0.5
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
    """[T, router_experts] float32: the gate of every expert of the router,
    zero where not chosen. Sigmoid scores, the num_experts_per_tok largest,
    divided by their sum, times routed_scaling_factor: no group, no bias."""
    s = jax.nn.sigmoid(jnp.matmul(h, lp["w_router"][e].astype(F32),
                                  precision=HI))
    top, idx = jax.lax.top_k(s, cfg["num_experts_per_tok"])
    if cfg.get("norm_topk_prob"):
        top = top / (top.sum(axis=-1, keepdims=True)
                     + cfg.get("norm_topk_eps", 0.0))
    top = top * cfg.get("routed_scaling_factor", 1)
    return jnp.zeros_like(s).at[jnp.arange(s.shape[0])[:, None], idx].set(top)


def _experts(cfg: dict, mm, h, lp: dict, e: int):
    w = gates(cfg, h, lp, e)
    first = cfg["expert_offset"]

    def one(name, j):  # held expert j's matrix, read out of the whole stack
        stack = lp[name]
        return jax.lax.dynamic_slice(
            stack, (e, j, 0, 0), (1, 1) + stack.shape[2:])[0, 0]

    def expert(acc, j):  # one held expert over every token, weighted
        y = _swiglu(mm, h, one("we_gate", j), one("we_up", j),
                    one("we_down", j))
        return acc + jax.lax.dynamic_index_in_dim(
            w, first + j, 1, keepdims=False)[:, None] * y, None

    routed, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                             jnp.arange(cfg["n_routed_experts"]))
    return routed + _swiglu(mm, h, lp["ws_gate"][e], lp["ws_up"][e],
                            lp["ws_down"][e])


@functools.partial(jax.jit, static_argnames=("cfg_items", "sparse", "lower"))
def _layer(params, x, i, e, cfg_items, sparse: bool, lower: bool):
    """x' [T, D] of the block at entry i of the stacks every layer has (its
    experts at entry e of the expert layers'); traced: ONE program a kind of
    layer; the blocks inside are loops, so it compiles small and its
    temporaries are freed before the next layer."""
    cfg = dict(cfg_items)
    rnd = _float8 if lower else _exact

    def mm(a, w):
        return jnp.matmul(rnd(a), rnd(w.astype(F32)), precision=HI)

    lp, eps = params["layers"], cfg["rms_norm_eps"]
    a = _attention(cfg, mm, rnd, _rms(x, lp["attn_norm"][i], eps), lp, i)
    x = x + _rms(a, lp["post_attn_norm"][i], eps)
    h = _rms(x, lp["mlp_norm"][i], eps)
    if sparse:
        m = _experts(cfg, mm, h, lp, e)
    else:
        m = _swiglu(mm, h, lp["w_gate"][i], lp["w_up"][i], lp["w_down"][i])
    return x + _rms(m, lp["post_mlp_norm"][i], eps)


def _cfg_items(cfg: dict) -> tuple:
    return tuple(sorted((k, cfg[k]) for k in CONFIG_KEYS if k in cfg))


def residual(cfg: dict, params: dict, tokens, lower: bool = False):
    """The trunk's last residual [T, D] of one sequence `tokens` [T] (T a
    multiple of QUERY_BLOCK), BEFORE the final norm."""
    items = _cfg_items(cfg)
    nd = cfg["num_dense_layers"]
    x = params["embed"][tokens].astype(F32)
    for i in range(cfg["num_hidden_layers"]):
        x = _layer(params, x, np.int32(i), np.int32(max(0, i - nd)), items,
                   i >= nd, lower)
    return x


def hidden(cfg: dict, params: dict, tokens, lower: bool = False):
    """Final-norm hidden states [T, D] of one sequence."""
    return _rms(residual(cfg, params, tokens, lower), params["final_norm"],
                cfg["rms_norm_eps"])


def mtp_hidden(cfg: dict, params: dict, tokens, x=None, lower: bool = False):
    """The prediction module's normed output [T, D]: row i, from the trunk's
    residual of position i and token i + 1, is what the head turns into a
    distribution of token i + 2 (the last row pairs with a token that is not
    there and means nothing). `x`: the trunk's residual, if already there."""
    eps = cfg["rms_norm_eps"]
    rnd = _float8 if lower else _exact
    if x is None:
        x = residual(cfg, params, tokens, lower)
    follows = jnp.roll(jnp.asarray(tokens), -1)
    u = jnp.concatenate(
        [_rms(params["embed"][follows].astype(F32), params["mtp_enorm"], eps),
         _rms(x, params["mtp_hnorm"], eps)], axis=-1)
    u = jnp.matmul(rnd(u), rnd(params["mtp_eh_proj"].astype(F32)),
                   precision=HI)
    n_all, nd = cfg["num_hidden_layers"], cfg["num_dense_layers"]
    v = _layer(params, u, np.int32(n_all), np.int32(n_all - nd),
               _cfg_items(cfg), True, lower)
    return _rms(v, params["mtp_norm"], eps)


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


def mtp_logits(cfg: dict, params: dict, tokens):
    """[T - 1, V] float32: row i the module's distribution of token i + 2,
    from the trunk's residual of position i and token i + 1."""
    t, padded = _padded(tokens)
    return head_logits(params, mtp_hidden(cfg, params, padded)[:t - 1])


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
    low = head_logits(params, hidden(cfg, params, short, True)[at], True)
    chosen = _choice(low, short, at, penalty, last_n)
    m, a = _margins(exact, short, at, chosen, penalty, last_n)
    m, a = np.asarray(m), np.asarray(a)
    return {"precision": "float8_e4m3fn", "positions": int(m.size),
            "tokens": int(t), "mean_margin_sd": float(m.mean()),
            "argmax_share": float((a == 0).mean())}


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
    eps = cfg["rms_norm_eps"]
    margins, ranks, per_request, lower = [], [], [], None
    drafted = right = 0
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
        x = residual(cfg, params, toks)
        logit = head_logits(params, _rms(x, params["final_norm"], eps)[at])
        chosen = toks[jnp.clip(at + 1, 0, pad_to - 1)]
        m, a = _margins(logit, toks, at, chosen, penalty, last_n)
        m, a = np.asarray(m)[:len(ids)], np.asarray(a)[:len(ids)]
        margins.append(m)
        ranks.append(a)
        # The module, teacher-forced: its best id at position i against the
        # id that came at i + 2 (the last output has none to be held to).
        draft = np.asarray(jnp.argmax(head_logits(
            params, mtp_hidden(cfg, params, toks, x)[at]), axis=-1))
        came = tokens[np.clip(np.asarray(at) + 2, 0, pad_to - 1)]
        k = len(ids) - 1
        drafted += k
        right += int((draft[:k] == came[:k]).sum())
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
            "mtp": {"drafts": drafted, "right": right,
                    "accept_share": right / max(1, drafted)},
            "lower_precision": lower or None,
            "per_request": per_request}
