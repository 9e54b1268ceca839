"""Plain float32 reference of the DeepSeek-V3.2 decoder family (multi-head
latent attention with YaRN RoPE, the lightning indexer's selection of the
`index_topk` best cached positions a query, a dense prefix, then a shared
expert beside group-limited sigmoid routing over the published router — of
whose experts the configuration HOLDS a share), and the comparison that
decides whether what the server returned agrees with it.

Independent of the code under test: no paging, no chunking, no cache, no
absorption of W_uk / W_uv, no kernel, no threshold search, no scheduler, no
sampling epilogue, no dispatch and no layer loop of the program's — one
sequence, a Python loop over the layers, keys and values EXPANDED a head, the
indexer as a dense [T, T] score with a causal mask, the selection as a mask
of the `index_topk` largest (`jax.lax.top_k`), attention as a dense masked
softmax, EVERY held expert computed for EVERY token and weighted by its gate
(zero where not chosen: no sort, no groups of rows), the shared expert once.
Every matmul is float32 at the highest precision. It is computed in blocks —
heads and queries of the [T, T] terms, one expert at a time — so that it
fits beside the served weights; the blocks change no number. Layer i, with
`x` the residual, h = RMSNorm(x; attn_norm), eps rms_norm_eps:

    c_q = RMSNorm(h W_dq)                              [q_lora_rank]
    q_i = (c_q W_uq)_i = [q_c,i | q_r,i]               heads x (nope | rope)
    [c_kv | k_r] = h W_dkv;  c_kv = RMSNorm(c_kv)      [kv_lora_rank | rope]
    q_r,i, k_r through RoPE (rotate-half, YaRN frequencies)   one k_r for all heads
    [k_c,i | v_i] = (c_kv W_ukv)_i                     heads x (nope | v)
    indexer: q_I,j = (c_q W_iq)_j (index heads x index_head_dim), k_I =
        LayerNorm(h W_ik), RoPE on the FIRST rope lanes of both;
        w = h W_iw * index_n_heads^-1/2 * index_head_dim^-1/2
        I(t, s) = sum_j w_j(t) ReLU(q_I,j(t) . k_I(s)),  s <= t
        S_t = the index_topk positions of largest I(t, .)  (all, while t < index_topk)
    a_i(t) = sum_{s in S_t} softmax_s((q_c,i.k_c,i(s) + q_r,i.k_r(s)) * scale) v_i(s)
        scale = head_dim^-1/2 * mscale^2,  mscale = 0.1 mscale_all_dim ln(factor) + 1
    x = x + concat_i(a_i) W_o;   h = RMSNorm(x; mlp_norm)
    i <  num_dense_layers: x = x + SwiGLU(h; w_gate, w_up, w_down)
    i >= num_dense_layers: s = sigmoid(h W_r) in float32, over ALL router_experts
        choose by s + b: a group of router_experts / n_group scores the sum of
        its best two, the topk_group best groups stay open, the top
        num_experts_per_tok inside them are chosen; g_e = routed_scaling_factor
        * s_e / (sum of the chosen s + norm_topk_eps)
        x = x + SwiGLU_shared(h) + sum_{e chosen AND held} g_e SwiGLU_e(h)
        held: experts expert_offset .. expert_offset + n_routed_experts - 1.
    logits = RMSNorm(x; final_norm) W_head^T   (the head's rows are the served slice)

The gates are normalised over all the chosen experts, held or not; what the
absent experts would have added is left out — here as in the program — and
that partial result goes on to the next layer (model-configs guide, section
4). Departures from the published model, all in the configuration file's
`assumed`: the indexer's FP8 quantisation of q_I / k_I and the Hadamard
rotation before it are left out (an orthogonal rotation of both sides leaves
the products as they are); RoPE is rotate-half over the rope lanes, the same
in the program; the multi-token-prediction module is not served. The weights
are seeded random; the selection bias is drawn with a standard deviation of
0.1. The prompt is byte tokens behind a BOS, not the model's tokenizer.

It reads only the configuration FILE's keys and the weights the server serves
by the program's names: `embed`, `lm_head`, `final_norm`, and under `layers`,
each stacked on a leading axis over the layers that HAVE it: `attn_norm
mlp_norm mla_wdq mla_q_norm mla_wuq mla_wdkv mla_kv_norm mla_wukv wo idx_wq
idx_wk idx_k_norm idx_k_bias idx_ww` (every layer), `w_gate w_up w_down` (the
dense prefix), `w_router router_bias ws_gate ws_up ws_down` and `we_gate
we_up we_down` [., E held, in, out] (expert layers).

The comparison is dense_decoder.py's, restated here so that the files stay
independent: teacher-forced on the ids the server returned, Ollama's
repetition penalty applied as the request's options ask, and `margin` = how
far below the reference's best (penalised) logit the returned id lies, in
standard deviations of that position's logits. A run agrees when the mean
margin over all checked positions is at most MEAN_MARGIN_SD_MAX (weights
served in float32: FLOAT32_MARGIN_SD_MAX).

`check` also reports what a forward one precision BELOW the configuration's
would read (`lower_precision`): the same forward with both operands of every
matmul rounded to float8 (e4m3), its own greedy choice at each position held
to the float32 logits — over the LAST LOWER_POSITIONS positions of the first
LOWER_TOKENS tokens of the first request's prompt (longer than index_topk, so
the selection selects; a sixteenth of a whole request's cost, which the
harness's time limit has no room for twice). It has to come out above the
limit, or the limit cannot tell bf16 from worse.

Before any of that, `check` holds the SHAPES of the weights served to the
file's keys (`served_layout`), as the references beside it do: a program
that lacks the architecture ends the run with an error exit and no result
line (the one before PR 39 does not get this far: its ModelConfig has no
field for `kv_lora_rank`, and serve.py ends at start).
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
# is set from two readings each (PERF.md section 6, PR 39).
#
# bfloat16 — the configuration's, what the cell runs. Serving
# deepseek-v3.2-ep16-d5 on a v5e reads a mean margin of 0.0031 to 0.0077 sd
# over fifteen runs on fifteen seeds (my chip runs, PR 39: 1024 positions
# each — eight requests of 8-16 k tokens, 128 outputs — 89-92 % of them the
# reference's own argmax, every one in its top 10, the worst single position
# 0.35-0.83). The same forward with float8 operands (`lower_precision`, 128
# positions at 4096 tokens of context a run) reads 0.68 at the least and 0.85
# at the most: 19-31 % argmax. 0.08 lies between, ten times the largest
# bfloat16 reading and an eighth of the smallest float8 one. (The selection
# is discrete: a bfloat16 indexer flips some of a query's 2048 positions near
# the threshold against the float32 one, each worth ~1/2048 of the softmax;
# the readings above include that.)
MEAN_MARGIN_SD_MAX = 0.08
# float32 — the tiny-size tests (tests/test_deepseek_v32.py): there the
# program's own forward, in chunks through the two pools and in decode scans,
# agrees with this reference to 5e-6 in every logit (margin 0.0), and a
# forward that leaves out the selection bias, the group limit, mscale^2, YaRN,
# the shared expert or the selection itself misses by 0.005 to 3 in a logit.
FLOAT32_MARGIN_SD_MAX = 0.003
OLLAMA_DEFAULTS = {"repeat_penalty": 1.1, "repeat_last_n": 64}
CONFIG_KEYS = (
    "num_attention_heads", "hidden_size", "rms_norm_eps", "rope_theta",
    "rope_scaling", "head_dim", "q_lora_rank", "kv_lora_rank",
    "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "index_n_heads",
    "index_head_dim", "index_topk", "num_hidden_layers", "num_dense_layers",
    "n_routed_experts", "router_experts", "expert_offset",
    "num_experts_per_tok", "n_group", "topk_group", "n_shared_experts",
    "norm_topk_prob", "norm_topk_eps", "routed_scaling_factor",
    "router_score", "use_expert_bias", "moe_intermediate_size",
    "intermediate_size", "vocab_size")
INDEX_NORM_EPS = 1e-6  # the indexer's LayerNorm
# Blocks (they change no number): queries a block of the [T, T] terms, heads
# a block of the attention, index heads a block of the indexer.
QUERY_BLOCK, HEAD_BLOCK, INDEX_HEAD_BLOCK, FFN_BLOCK = 256, 8, 8, 4608
LOWER_TOKENS, LOWER_POSITIONS = 4096, 128
HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


class NotServed(Exception):
    """The weights served do not have the configuration's architecture."""


def served_layout(cfg: dict, params: dict) -> None:
    """Raises NotServed unless every weight the reference reads has the shape
    the configuration file's keys give it."""
    lp = params["layers"]
    n_all = cfg["num_hidden_layers"]
    n = {"all": n_all, "dense": cfg["num_dense_layers"],
         "experts": n_all - cfg["num_dense_layers"]}
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    r, c = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    hi, di = cfg["index_n_heads"], cfg["index_head_dim"]
    e, R = cfg["n_routed_experts"], cfg["router_experts"]
    f, fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    fs = fe * cfg["n_shared_experts"]
    want = {
        "attn_norm": ("all", (d,)), "mlp_norm": ("all", (d,)),
        "mla_wdq": ("all", (d, r)), "mla_q_norm": ("all", (r,)),
        "mla_wuq": ("all", (r, H * (dn + dr))),
        "mla_wdkv": ("all", (d, c + dr)), "mla_kv_norm": ("all", (c,)),
        "mla_wukv": ("all", (c, H * (dn + dv))), "wo": ("all", (H * dv, d)),
        "idx_wq": ("all", (r, hi * di)), "idx_wk": ("all", (d, di)),
        "idx_k_norm": ("all", (di,)), "idx_k_bias": ("all", (di,)),
        "idx_ww": ("all", (d, hi)),
        "w_gate": ("dense", (d, f)), "w_up": ("dense", (d, f)),
        "w_down": ("dense", (f, d)),
        "w_router": ("experts", (d, R)), "router_bias": ("experts", (R,)),
        "ws_gate": ("experts", (d, fs)), "ws_up": ("experts", (d, fs)),
        "ws_down": ("experts", (fs, d)),
        "we_gate": ("experts", (e, d, fe)), "we_up": ("experts", (e, d, fe)),
        "we_down": ("experts", (e, fe, d))}
    if cfg.get("head_dim") != dn + dr or not cfg.get("use_expert_bias") \
            or cfg.get("router_score") != "sigmoid" \
            or (cfg.get("rope_scaling") or {}).get("type") != "yarn":
        raise NotServed("this reference is the family's: head_dim = nope + "
                        "rope, router_score 'sigmoid', use_expert_bias true, "
                        "rope_scaling of type 'yarn'")
    bad = [f"{name} is {tuple(lp[name].shape) if name in lp else 'absent'}, "
           f"the configuration's is {(n[kind], *shape)}"
           for name, (kind, shape) in want.items()
           if n[kind] and (name not in lp
                           or tuple(lp[name].shape) != (n[kind], *shape))]
    v = cfg["vocab_size"]
    for name in ("embed", "lm_head"):
        if name not in params or tuple(params[name].shape) != (v, d):
            bad.append(f"{name} is not {(v, d)}")
    if bad:
        raise NotServed("; ".join(bad))


def cannot_run(reason: str):
    """The program under test lacks the configuration's architecture: end the
    run with an error exit and no result line (lfm2_decoder.py has the
    mechanism's account)."""
    print(f"deepseek_v32_decoder: the program cannot run this configuration: "
          f"{reason}", file=sys.stderr, flush=True)
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
    """YaRN's inverse frequencies [rope/2] and what it multiplies cos and
    sin by (the family's inference code: find_correction_range, a linear
    ramp between the dimensions that rotate beta_fast and beta_slow times
    over the original context)."""
    y, dim, base = cfg["rope_scaling"], cfg["qk_rope_head_dim"], \
        float(cfg["rope_theta"])
    factor, orig = float(y["factor"]), \
        float(y["original_max_position_embeddings"])

    def correction(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction(float(y.get("beta_fast", 32)))), 0)
    high = min(math.ceil(correction(float(y.get("beta_slow", 1)))), dim - 1)
    extra = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    inv = extra / factor * ramp + extra * (1.0 - ramp)

    def mscale(m):
        return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0

    return (jnp.asarray(inv, F32),
            mscale(y.get("mscale", 1)) / mscale(y.get("mscale_all_dim", 0)),
            mscale(y.get("mscale_all_dim", 0)))


def _rope(cfg: dict, x):
    """Rotate-half RoPE over the FIRST qk_rope_head_dim lanes of x [T, H, d]
    at positions 0..T-1."""
    inv, cos_scale, _ = yarn_inv_freq(cfg)
    dr = cfg["qk_rope_head_dim"]
    ang = jnp.arange(x.shape[0], dtype=F32)[:, None] * inv
    cos = (jnp.cos(ang) * cos_scale)[:, None, :]
    sin = (jnp.sin(ang) * cos_scale)[:, None, :]
    a, b = x[..., : dr // 2], x[..., dr // 2: dr]
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin, x[..., dr:]], axis=-1)


def _selection(cfg: dict, mm, rnd, h, c_q, lp: dict, i: int):
    """keep [T, T] bool: position s is attended by query t."""
    t = h.shape[0]
    hi, di = cfg["index_n_heads"], cfg["index_head_dim"]
    topk = min(int(cfg["index_topk"]), t)
    q = _rope(cfg, mm(c_q, lp["idx_wq"][i]).reshape(t, hi, di))
    k = mm(h, lp["idx_wk"][i])
    mean = k.mean(axis=-1, keepdims=True)
    k = (k - mean) * jax.lax.rsqrt(
        jnp.mean(jnp.square(k - mean), axis=-1, keepdims=True)
        + INDEX_NORM_EPS) * lp["idx_k_norm"][i].astype(F32) \
        + lp["idx_k_bias"][i].astype(F32)
    k = rnd(_rope(cfg, k[:, None, :])[:, 0, :])
    w = mm(h, lp["idx_ww"][i]) * (hi ** -0.5 * di ** -0.5)
    pos = jnp.arange(t)
    jb = math.gcd(INDEX_HEAD_BLOCK, hi)

    def block(q0):  # QUERY_BLOCK queries against every position
        qb = jax.lax.dynamic_slice_in_dim(q, q0, QUERY_BLOCK)
        wb = jax.lax.dynamic_slice_in_dim(w, q0, QUERY_BLOCK)

        def heads(acc, j0):
            qh = jax.lax.dynamic_slice_in_dim(qb, j0, jb, 1)
            wh = jax.lax.dynamic_slice_in_dim(wb, j0, jb, 1)
            s = jnp.einsum("qjd,sd->qjs", rnd(qh), k, precision=HI)
            return acc + jnp.einsum("qjs,qj->qs", jnp.maximum(s, 0.0), wh,
                                    precision=HI), None

        score, _ = jax.lax.scan(
            heads, jnp.zeros((QUERY_BLOCK, t), F32),
            jnp.arange(0, hi, jb))
        causal = pos[None, :] <= (q0 + jnp.arange(QUERY_BLOCK))[:, None]
        score = jnp.where(causal, score, -jnp.inf)
        kth = jax.lax.top_k(score, topk)[0][:, -1:]
        return causal & (score >= kth)

    keep = jax.lax.map(block, jnp.arange(0, t, QUERY_BLOCK))
    return keep.reshape(t, t)


def _attention(cfg: dict, mm, rnd, h, lp: dict, i: int):
    t = h.shape[0]
    H, c = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    eps = cfg["rms_norm_eps"]
    scale = cfg["head_dim"] ** -0.5 * yarn_inv_freq(cfg)[2] ** 2
    c_q = _rms(mm(h, lp["mla_wdq"][i]), lp["mla_q_norm"][i], eps)
    keep = _selection(cfg, mm, rnd, h, c_q, lp, i)
    kv = mm(h, lp["mla_wdkv"][i])
    c_kv = _rms(kv[:, :c], lp["mla_kv_norm"][i], eps)
    k_r = _rope(cfg, kv[:, None, c:])  # [T, 1, dr]: one for all heads
    hb = math.gcd(HEAD_BLOCK, H)
    w_uq = lp["mla_wuq"][i].reshape(-1, H, dn + dr)
    w_ukv = lp["mla_wukv"][i].reshape(c, H, dn + dv)
    w_o = lp["wo"][i].reshape(H, dv, -1)
    n_blocks = t // QUERY_BLOCK

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
            qb = jax.lax.dynamic_slice_in_dim(qh, b * QUERY_BLOCK,
                                              QUERY_BLOCK)
            mask = jax.lax.dynamic_slice_in_dim(keep, b * QUERY_BLOCK,
                                                QUERY_BLOCK)
            s = jnp.einsum("qhd,shd->hqs", qb, kh, precision=HI) * scale
            p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
            return jnp.einsum("hqs,shd->qhd", rnd(p), vh, precision=HI)

        o = jax.lax.map(block, jnp.arange(n_blocks)).reshape(t, hb * dv)
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
    zero where not chosen."""
    s = jax.nn.sigmoid(jnp.matmul(h, lp["w_router"][e].astype(F32),
                                  precision=HI))
    t, R = s.shape
    choice = s + lp["router_bias"][e].astype(F32)
    g = cfg["n_group"]
    grouped = choice.reshape(t, g, R // g)
    group_score = jax.lax.top_k(grouped, 2)[0].sum(axis=-1)
    _, best = jax.lax.top_k(group_score, cfg["topk_group"])
    open_ = jnp.zeros((t, g), bool).at[jnp.arange(t)[:, None], best].set(True)
    choice = jnp.where(open_[:, :, None], grouped, -jnp.inf).reshape(t, R)
    _, idx = jax.lax.top_k(choice, cfg["num_experts_per_tok"])
    top = jnp.take_along_axis(s, idx, axis=-1)  # the score WITHOUT the bias
    if cfg.get("norm_topk_prob"):
        top = top / (top.sum(axis=-1, keepdims=True)
                     + cfg.get("norm_topk_eps", 0.0))
    top = top * cfg.get("routed_scaling_factor", 1)
    return jnp.zeros_like(s).at[jnp.arange(t)[:, None], idx].set(top)


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
def _layer(params, x, i, cfg_items, sparse: bool, lower: bool):
    """x' [T, D] of layer i (traced: ONE program a kind of layer; the blocks
    inside are loops, so it compiles small and its temporaries are freed
    before the next layer)."""
    cfg = _cfg_of(cfg_items)
    rnd = _float8 if lower else _exact

    def mm(a, w):
        return jnp.matmul(rnd(a), rnd(w.astype(F32)), precision=HI)

    lp, eps = params["layers"], cfg["rms_norm_eps"]
    x = x + _attention(cfg, mm, rnd, _rms(x, lp["attn_norm"][i], eps), lp, i)
    h = _rms(x, lp["mlp_norm"][i], eps)
    if not sparse:
        return x + _swiglu(mm, h, lp["w_gate"][i], lp["w_up"][i],
                           lp["w_down"][i])
    return x + _experts(cfg, mm, h, lp, i - cfg["num_dense_layers"])


def _cfg_of(cfg_items) -> dict:
    return {k: dict(v) if k == "rope_scaling" else v for k, v in cfg_items}


def _cfg_items(cfg: dict) -> tuple:
    return tuple(sorted(
        (k, tuple(sorted(cfg[k].items())) if isinstance(cfg[k], dict)
         else cfg[k]) for k in CONFIG_KEYS if k in cfg))


def hidden(cfg: dict, params: dict, tokens, lower: bool = False):
    """Final-norm hidden states [T, D] of one sequence `tokens` [T] (T a
    multiple of QUERY_BLOCK)."""
    items = _cfg_items(cfg)
    x = params["embed"][tokens].astype(F32)
    for i in range(cfg["num_hidden_layers"]):
        x = _layer(params, x, np.int32(i), items,
                   i >= cfg["num_dense_layers"], lower)
    return _rms(x, params["final_norm"], cfg["rms_norm_eps"])


def head_logits(params: dict, h, lower: bool = False):
    rnd = _float8 if lower else _exact
    return jnp.matmul(rnd(h), rnd(params["lm_head"].astype(F32)).T,
                      precision=HI)


def logits(cfg: dict, params: dict, tokens):
    """[T, V] float32 logits of one sequence (padded here to whole query
    blocks; causal attention keeps padding from every earlier position):
    what the tier-1 tests hold the served path's logits to."""
    t = len(tokens)
    padded = jnp.zeros((-(-t // QUERY_BLOCK) * QUERY_BLOCK,), jnp.int32
                       ).at[:t].set(jnp.asarray(tokens, jnp.int32))
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
