"""Plain float32 reference of the MiMo-V2-Flash decoder family
(XiaomiMiMo/MiMo-V2-Flash, `mimo_v2_flash`: window attention over the last
`sliding_window` positions in five layers of six and full attention in the
sixth, the two kinds at DIFFERENT kv-head counts, key heads of `head_dim`
lanes beside value heads of `v_head_dim`, a partial rotary embedding at a base
a kind, a learned sink in the window layers' softmax, v times
`attention_value_scale`; a leading dense layer and then routed experts by a
sigmoid router with a selection bias, no shared expert, no scale — of whose
routed experts the configuration HOLDS a share), and the comparison that
decides whether what the server returned agrees with it.

Independent of the code under test: no paging, no ring, no chunking, no
per-slot state, no kernel, no split cache rows, no sort of rows by expert, no
scheduler, no sampling epilogue, no dispatch and no layer loop of the
program's — one sequence, a Python loop over the file's
`hybrid_layer_pattern`, attention as a dense softmax under a mask built from
POSITIONS (the window's or the causal one) with the sink as ONE MORE COLUMN
concatenated to the scores and dropped from the weights, EVERY held expert
computed for EVERY token and weighted by its gate (zero where not chosen).
Every matmul is float32 at the highest precision. It is computed in blocks —
128 queries of the [T, T] scores, one expert at a time — so that 16,896
positions fit beside the served weights; the blocks change no number (a
window layer's block still scores every position and masks: nothing here
knows where a window starts). Layer i, with `x` the residual, kind =
hybrid_layer_pattern[i] (0 full, 1 window), N(x; w) = x rsqrt(mean x^2 + eps)
w and h = N(x; attn_norm):

    H = num_attention_heads; d_qk = head_dim; d_v = v_head_dim;
    Hk = num_key_value_heads (full) | swa_num_key_value_heads (window);
    theta = rope_theta (full) | swa_rope_theta (window);
    q, k, v = h Wq, h Wk, attention_value_scale * (h Wv)
                                  (H d_qk | Hk d_qk | Hk d_v; no bias, no norm)
    RoPE (rotate-half, theta, over the FIRST int(d_qk * partial_rotary_factor)
        lanes of a head; the other lanes pass) on q and k, BOTH kinds;
    full:    query i sees key j iff j <= i;
    window:  query i sees key j iff i - sliding_window < j <= i, and the
        softmax runs over those scores AND the head's sink logit b_h
        (`swa_sink`, float32), whose weight multiplies no value:
        p_ij = exp(s_ij - m) / (sum_j exp(s_ij - m) + exp(b_h - m));
    a = softmax(q k^T / sqrt(d_qk) under the mask) v, H / Hk q heads a kv
    head;  x = x + a Wo  (H d_v -> D);   h = N(x; mlp_norm)
    moe_layer_freq[i] == 0:  x = x + SwiGLU_dense(h)
    else:  s = sigmoid(h W_r) in float32 over ALL router_experts; the top
        num_experts_per_tok by s + b (b the selection bias: it selects, it
        weights nothing); g_e = s_e / (sum of the chosen s + 1e-20);
        x = x + sum_{e chosen AND held} g_e SwiGLU_e(h)
        held: experts expert_offset .. expert_offset + n_routed_experts - 1.
    logits = N(x; final_norm) W_head^T   (the head's rows are the served slice)

The gates are normalised over all the chosen experts, held or not; what the
absent experts would have added is left out — here as in the program — and
that partial result goes on to the next layer (model-configs guide, section
4). DEPARTURES from the published description, each in the configuration
file's `assumed`: the sink's form and that `attention_value_scale` multiplies
v are the family's modelling code AS RECALLED with no network here (a loader
of real weights must check both); rotate-half over the first 64 lanes (an
interleaved layout is the same rotation under a fixed permutation of seeded
lanes); the three multi-token-prediction modules of the checkpoint have no
key in config.json and are not served. The weights are seeded random. The
prompt is byte tokens behind a BOS, not the model's tokenizer.

It reads only the configuration FILE's keys and the weights the server serves
by the program's names: `embed`, `lm_head`, `final_norm`, and under `layers`,
each stacked on a leading axis over the layers that HAVE it: `attn_norm
mlp_norm` (every layer), `wq wk wv wo` (the FULL layers, in layer order),
`swa_wq swa_wk swa_wv swa_wo swa_sink` (the WINDOW layers), `w_gate w_up
w_down` (the dense layer), `w_router router_bias` and `we_gate we_up we_down`
[., E held, in, out] (the expert layers).

What it costs (reckoned before the chip run, PR 65): at the cell's longest
request (16,896 positions) the 16 held experts over every token are 16 x
16,896 x 6 layers x 6 x 4096 x 2048 = 8.2e13 FLOP, the dense layer 6.8e12,
the projections ~2.1e13, seven layers' scores and values over every position
(masked, not skipped) 64 x 16,896^2 x (192 + 128) x 2 x 7 = 8.2e13: ~1.9e14 a
request, 1.5e15 for the harness's eight — at the ~19 TFLOP/s a float32 matmul
at the highest precision reaches on a v5e, ~80 s. The harness allows 240 s.

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
LOWER_TOKENS tokens of the first request's prompt. It has to come out above
the limit, or the limit cannot tell bf16 from worse.

Before any of that, `check` holds the SHAPES of the weights served to the
file's keys (`served_layout`), as the references beside it do: a program that
lacks the architecture ends the run with an error exit and no result line
(the one before PR 65 does not get this far: its ModelConfig has no field for
`hybrid_layer_pattern`, and serve.py ends at start).
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
# is set from two readings each (PERF.md section 4, PR 65).
#
# bfloat16 — the configuration's, what the cell runs. Serving
# mimo-v2-flash-ep16-d7 on a v5e reads a mean margin of 0.0 to 0.000125 sd
# over seven runs on seven seeds (my chip runs, PR 65: 4096 positions each —
# eight requests of 8-16 k tokens, 512 outputs; the worst position of any run
# 0.126). The same forward with float8 operands (`lower_precision`, 128
# positions at 4096 tokens of context a run) reads 2.35 at the least (to
# 2.64). 0.05, the limit of the harness's other cells, lies between: 400
# times the largest bfloat16 reading (fresh seeds read higher: the more room
# is above), a forty-seventh of the smallest float8 one. The bfloat16 side is
# small because greedy decoding of these seeded weights falls into short
# loops (`distinct_ids` 1 to 88 of 512 a request): `check` reports that, and
# how far the reference's best id stands above its second best
# (`best_gap_sd_p50` 0.17-0.45), beside the margin.
MEAN_MARGIN_SD_MAX = 0.05
# float32 — the tiny-size tests (tests/test_mimo_v2_flash.py): there the
# program's own forward, in chunks through rings and pool and in decode scans,
# agrees with this reference to ~2e-5 in every logit (margin 0.0), and each of
# the eight forwards that drop or misplace one mechanism misses by far more
# (asserted there).
FLOAT32_MARGIN_SD_MAX = 0.003
OLLAMA_DEFAULTS = {"repeat_penalty": 1.1, "repeat_last_n": 64}
CONFIG_KEYS = (
    "num_attention_heads", "num_key_value_heads", "swa_num_key_value_heads",
    "head_dim", "v_head_dim", "hidden_size", "intermediate_size",
    "layernorm_epsilon", "rope_theta", "swa_rope_theta",
    "partial_rotary_factor", "attention_value_scale", "hybrid_layer_pattern",
    "moe_layer_freq", "sliding_window", "add_swa_attention_sink_bias",
    "n_routed_experts", "router_experts", "expert_offset",
    "num_experts_per_tok", "norm_topk_prob", "moe_intermediate_size",
    "vocab_size")
FULL, WINDOW = 0, 1  # `hybrid_layer_pattern`'s entries
NORM_TOPK_EPS = 1e-20
# Blocks (they change no number): queries a block of the [T, T] scores.
QUERY_BLOCK = 128
LOWER_TOKENS, LOWER_POSITIONS = 4096, 128
HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


class NotServed(Exception):
    """The weights served do not have the configuration's architecture."""


def _counts(cfg: dict) -> dict:
    kinds, ffn = cfg["hybrid_layer_pattern"], cfg["moe_layer_freq"]
    return {"all": len(kinds), "full": kinds.count(FULL),
            "window": kinds.count(WINDOW), "dense": ffn.count(0),
            "sparse": ffn.count(1)}


def _kv_heads(cfg: dict, kind: int) -> int:
    return cfg["swa_num_key_value_heads" if kind == WINDOW
               else "num_key_value_heads"]


def served_layout(cfg: dict, params: dict) -> None:
    """Raises NotServed unless every weight the reference reads has the shape
    the configuration file's keys give it."""
    lp = params["layers"]
    n = _counts(cfg)
    d, dq, dv = cfg["hidden_size"], cfg["head_dim"], cfg["v_head_dim"]
    H = cfg["num_attention_heads"]
    e = cfg["n_routed_experts"]
    R = cfg.get("router_experts") or e
    f, fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    want = {
        "attn_norm": ("all", (d,)), "mlp_norm": ("all", (d,)),
        "w_gate": ("dense", (d, f)), "w_up": ("dense", (d, f)),
        "w_down": ("dense", (f, d)),
        "w_router": ("sparse", (d, R)), "router_bias": ("sparse", (R,)),
        "we_gate": ("sparse", (e, d, fe)), "we_up": ("sparse", (e, d, fe)),
        "we_down": ("sparse", (e, fe, d))}
    for kind, stack, pre in ((FULL, "full", ""), (WINDOW, "window", "swa_")):
        hk = _kv_heads(cfg, kind)
        want.update({
            pre + "wq": (stack, (d, H * dq)), pre + "wk": (stack, (d, hk * dq)),
            pre + "wv": (stack, (d, hk * dv)), pre + "wo": (stack, (H * dv, d))})
    want["swa_sink"] = ("window", (H,))
    ffn = list(cfg["moe_layer_freq"])
    if set(cfg["hybrid_layer_pattern"]) - {FULL, WINDOW} \
            or len(ffn) != n["all"] or ffn != sorted(ffn) \
            or not cfg.get("add_swa_attention_sink_bias") \
            or cfg.get("add_full_attention_sink_bias") \
            or cfg.get("scoring_func") != "sigmoid" \
            or cfg.get("topk_method") != "noaux_tc" \
            or cfg.get("n_shared_experts") \
            or cfg.get("routed_scaling_factor") not in (None, 1.0) \
            or cfg.get("swa_head_dim", dq) != dq \
            or cfg.get("swa_v_head_dim", dv) != dv \
            or cfg.get("swa_num_attention_heads", H) != H:
        raise NotServed("this reference is the family's: layers of full (0) "
                        "and window (1) attention at one q head shape, the "
                        "dense FFNs leading, a sink in the window layers "
                        "only, a sigmoid router with a selection bias "
                        "(noaux_tc), no shared expert, no scale")
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
    print(f"mimo_v2_flash_decoder: the program cannot run this configuration: "
          f"{reason}", file=sys.stderr, flush=True)
    os.kill(os.getpid(), signal.SIGTERM)
    raise SystemExit(reason)


def _exact(x):
    return x


def _float8(x):
    """x rounded to float8 e4m3 and back: the precision below bfloat16."""
    return x.astype(jnp.float8_e4m3fn).astype(F32)


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(F32)


def _rope(theta: float, rot: int, x):
    """Rotate-half RoPE over the first `rot` lanes of a head of x [T, H, d] at
    positions 0..T-1; the other lanes pass."""
    inv = float(theta) ** (-np.arange(0, rot, 2, dtype=np.float64) / rot)
    ang = jnp.arange(x.shape[0], dtype=F32)[:, None] * jnp.asarray(inv, F32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., : rot // 2], x[..., rot // 2: rot]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., rot:]], axis=-1)


def _attention(cfg: dict, mm, rnd, h, lp: dict, i: int, kind: int):
    """`i`: the layer among the layers of its kind."""
    t = h.shape[0]
    H, dq, dv = cfg["num_attention_heads"], cfg["head_dim"], cfg["v_head_dim"]
    Hk = _kv_heads(cfg, kind)
    pre = "swa_" if kind == WINDOW else ""
    q = mm(h, lp[pre + "wq"][i]).reshape(t, H, dq)
    k = mm(h, lp[pre + "wk"][i]).reshape(t, Hk, dq)
    v = rnd(cfg["attention_value_scale"]
            * mm(h, lp[pre + "wv"][i]).reshape(t, Hk, dv))
    theta = cfg["swa_rope_theta" if kind == WINDOW else "rope_theta"]
    rot = int(dq * cfg["partial_rotary_factor"])
    q, k = _rope(theta, rot, q), _rope(theta, rot, k)
    # q head j attends kv head j // (H / Hk): the q heads a kv head at a time
    q, k = rnd(q).reshape(t, Hk, H // Hk, dq), rnd(k)
    pos = jnp.arange(t)
    window = cfg["sliding_window"]
    if kind == WINDOW:  # one more column a head, the same for every query
        sink = jnp.broadcast_to(
            lp["swa_sink"][i].astype(F32).reshape(Hk, H // Hk, 1, 1),
            (Hk, H // Hk, QUERY_BLOCK, 1))

    def block(q0):  # QUERY_BLOCK queries against every position
        qb = jax.lax.dynamic_slice_in_dim(q, q0, QUERY_BLOCK)
        s = jnp.einsum("qkgd,skd->kgqs", qb, k, precision=HI) / math.sqrt(dq)
        at = (q0 + jnp.arange(QUERY_BLOCK))[:, None]
        sees = pos[None, :] <= at
        if kind == WINDOW:
            sees = sees & (pos[None, :] > at - window)
        s = jnp.where(sees[None, None], s, -jnp.inf)
        if kind == WINDOW:  # ...in the softmax, and then dropped: no value
            p = jax.nn.softmax(jnp.concatenate([s, sink], axis=-1),
                               axis=-1)[..., :-1]
        else:
            p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgqs,skd->qkgd", rnd(p), v, precision=HI)

    o = jax.lax.map(block, jnp.arange(0, t, QUERY_BLOCK)).reshape(t, H * dv)
    return mm(o, lp[pre + "wo"][i])


def _swiglu(mm, h, gate, up, down):
    return mm(jax.nn.silu(mm(h, gate)) * mm(h, up), down)


def gates(cfg: dict, h, lp: dict, e: int):
    """[T, router_experts] float32: the gate of every expert of the router,
    zero where not chosen. `e`: the layer among the expert layers."""
    s = jax.nn.sigmoid(jnp.matmul(h, lp["w_router"][e].astype(F32),
                                  precision=HI))
    _, idx = jax.lax.top_k(s + lp["router_bias"][e].astype(F32),
                           cfg["num_experts_per_tok"])
    top = jnp.take_along_axis(s, idx, axis=-1)
    if cfg.get("norm_topk_prob"):
        top = top / (top.sum(axis=-1, keepdims=True) + NORM_TOPK_EPS)
    return jnp.zeros_like(s).at[jnp.arange(s.shape[0])[:, None], idx].set(top)


def _experts(cfg: dict, mm, h, lp: dict, e: int):
    w = gates(cfg, h, lp, e)
    first = cfg.get("expert_offset", 0)

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
    return routed


@functools.partial(jax.jit,
                   static_argnames=("cfg_items", "kind", "dense", "lower"))
def _layer(params, x, i, of_kind, of_ffn, cfg_items, kind: int, dense: bool,
           lower: bool):
    """x' [T, D] of layer i, the `of_kind`-th of its attention kind and the
    `of_ffn`-th of its FFN's (traced: ONE program a (kind, FFN) of layer; the
    blocks inside are loops, so it compiles small and its temporaries are
    freed before the next layer)."""
    cfg = dict(cfg_items)
    rnd = _float8 if lower else _exact

    def mm(a, w):
        return jnp.matmul(rnd(a), rnd(w.astype(F32)), precision=HI)

    lp, eps = params["layers"], cfg["layernorm_epsilon"]
    x = x + _attention(cfg, mm, rnd, _norm(x, lp["attn_norm"][i], eps), lp,
                       of_kind, kind)
    h = _norm(x, lp["mlp_norm"][i], eps)
    if dense:
        return x + _swiglu(mm, h, lp["w_gate"][of_ffn], lp["w_up"][of_ffn],
                           lp["w_down"][of_ffn])
    return x + _experts(cfg, mm, h, lp, of_ffn)


def _cfg_items(cfg: dict) -> tuple:
    def frozen(v):
        if isinstance(v, list):
            return tuple(v)
        return tuple(sorted(v.items())) if isinstance(v, dict) else v

    return tuple(sorted((k, frozen(cfg[k])) for k in CONFIG_KEYS if k in cfg))


def hidden(cfg: dict, params: dict, tokens, lower: bool = False):
    """Final-norm hidden states [T, D] of one sequence `tokens` [T] (T a
    multiple of QUERY_BLOCK)."""
    items = _cfg_items(cfg)
    x = params["embed"][tokens].astype(F32)
    seen = {FULL: 0, WINDOW: 0, "dense": 0, "sparse": 0}
    for i, (kind, ffn) in enumerate(zip(cfg["hybrid_layer_pattern"],
                                        cfg["moe_layer_freq"])):
        if kind not in (FULL, WINDOW) or ffn not in (0, 1):
            raise ValueError(f"layer {i}: kind {kind!r}, FFN {ffn!r}")
        dense = "dense" if ffn == 0 else "sparse"
        x = _layer(params, x, np.int32(i), np.int32(seen[kind]),
                   np.int32(seen[dense]), items, kind, ffn == 0, lower)
        seen[kind] += 1
        seen[dense] += 1
    return _norm(x, params["final_norm"], cfg["layernorm_epsilon"])


def head_logits(params: dict, h, lower: bool = False):
    rnd = _float8 if lower else _exact
    return jnp.matmul(rnd(h), rnd(params["lm_head"].astype(F32)).T,
                      precision=HI)


def logits(cfg: dict, params: dict, tokens):
    """[T, V] float32 logits of one sequence (padded here to whole query
    blocks; causal masks keep padding from every earlier position): what the
    tier-1 tests hold the served path's logits to."""
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
    above `chosen`, how far the reference's best id stands above its second
    best in sd: what a rounding has to bridge to flip a choice), under the
    repetition penalty."""
    sd = jnp.maximum(logit.std(axis=-1, keepdims=True), 1e-30)
    logit = _penalised(logit, tokens, at, penalty, last_n)
    got = jnp.take_along_axis(logit, chosen[:, None], axis=-1)
    best = jax.lax.top_k(logit, 2)[0]
    margin = (best[:, :1] - got) / sd
    return margin[:, 0], (logit > got).sum(axis=-1), \
        (best[:, 0] - best[:, 1]) / sd[:, 0]


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
    m, a, _ = _margins(exact, short, at, chosen, penalty, last_n)
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
    margins, ranks, gaps, per_request, lower = [], [], [], [], None
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
        m, a, g = _margins(logit, toks, at, chosen, penalty, last_n)
        m, a, g = (np.asarray(x)[:len(ids)] for x in (m, a, g))
        margins.append(m)
        ranks.append(a)
        gaps.append(g)
        per_request.append({"prompt_tokens": len(prompt), "outputs": len(ids),
                            "mean_margin_sd": float(m.mean()),
                            "argmax_share": float((a == 0).mean()),
                            "distinct_ids": len(set(ids))})
        if lower is None:
            lower = _lower_precision(cfg, params, tokens, len(prompt),
                                     penalty, last_n) or {}
    m, a = np.concatenate(margins), np.concatenate(ranks)
    g = np.concatenate(gaps)
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
            # the reference's best id over its second best: what a rounding
            # has to bridge to flip a choice (median and 1st percentile, sd)
            "best_gap_sd_p50": float(np.median(g)),
            "best_gap_sd_p01": float(np.quantile(g, 0.01)),
            "lower_precision": lower or None,
            "per_request": per_request}
