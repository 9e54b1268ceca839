"""Plain float32 reference of the K-EXAONE decoder family
(K-EXAONE-236B-A23B: window attention over the last `sliding_window`
positions in three layers of four and full attention in the fourth, a rotary
embedding on the window layers ONLY, per-head q/k norm, a leading dense
layer and then routed experts by a sigmoid router with a selection bias
beside one shared expert — of whose routed experts the configuration HOLDS a
share), and the comparison that decides whether what the server returned
agrees with it.

Independent of the code under test: no paging, no ring, no chunking, no
per-slot state, no kernel, no sort of rows by expert, no scheduler, no
sampling epilogue, no dispatch and no layer loop of the program's — one
sequence, a Python loop over the file's `layer_types`, attention as a dense
softmax under a mask built from POSITIONS (the window's or the causal one),
EVERY held expert computed for EVERY token and weighted by its gate (zero
where not chosen), the shared expert once. Every matmul is float32 at the
highest precision. It is computed in blocks — 128 queries of the [T, T]
scores, one expert at a time — so that 16,640 positions fit beside the
served weights; the blocks change no number (a window layer's block still
scores every position and masks: nothing here knows where a window starts).
Layer i, with `x` the residual, kind = layer_types[i], N(x; w) =
x rsqrt(mean x^2 + eps) w and h = N(x; attn_norm):

    q, k, v = h Wq, h Wk, h Wv   (H hd | Hk hd | Hk hd; no bias); per head
    q = N(q; q_norm), k = N(k; k_norm) (weights over hd);
    kind sliding_attention:  RoPE (rotate-half, theta rope_theta, over the
        whole head) on q and k, after the norm; query i sees key j iff
        i - sliding_window < j <= i  (itself and the sliding_window - 1
        before it);
    kind full_attention:  NO rotary embedding; query i sees key j iff j <= i;
    a = softmax(q k^T / sqrt(hd) under the mask) v, H / Hk q heads a kv
    head;  x = x + a Wo;   h = N(x; mlp_norm)
    layer i < first_k_dense_replace:  x = x + SwiGLU_dense(h)
    else:  s = sigmoid(h W_r) in float32 over ALL router_experts; the top
        num_experts_per_tok by s + b (b the selection bias: it selects, it
        weights nothing); g_e = s_e / (sum of the chosen s + 1e-20) x
        routed_scaling_factor;
        x = x + SwiGLU_shared(h) + sum_{e chosen AND held} g_e SwiGLU_e(h)
        held: experts expert_offset .. expert_offset + num_experts - 1.
    logits = N(x; final_norm) W_head^T   (the head's rows are the served slice)

The gates are normalised over all the chosen experts, held or not; what the
absent experts would have added is left out — here as in the program — and
that partial result goes on to the next layer (model-configs guide, section
4). What config.json has no key for is in the configuration file's
`assumed`: pre-norm blocks, the per-head q/k norm, no RoPE on the full
layers, no attention bias, the window's boundary as above. The prediction
module (`num_nextn_predict_layers`) is not served (`reduced` to 0). The
weights are seeded random. The prompt is byte tokens behind a BOS, not the
model's tokenizer.

It reads only the configuration FILE's keys and the weights the server serves
by the program's names: `embed`, `lm_head`, `final_norm`, and under `layers`,
each stacked on a leading axis over the layers that HAVE it: `attn_norm
mlp_norm` and `wq wk wv wo q_norm k_norm` (every layer: both attention kinds
share the stacks, in layer order), `w_gate w_up w_down` (the dense layers),
`w_router router_bias ws_gate ws_up ws_down` and `we_gate we_up we_down`
[., E held, in, out] (the expert layers).

What it costs (reckoned before the chip run, PR 50): at the cell's longest
request (16,640 positions) the 16 held experts over every token are 16 x
16,640 x 4 layers x 6 x 6144 x 2048 = 8.0e13 FLOP, the dense layer and the
four shared experts 1.6e13, the projections 1.9e13, five layers' scores and
values over every position (masked, not skipped) 64 x 16,640^2 x 128 x 4 x 5
= 4.5e13: ~1.6e14 a request, 1.3e15 for the harness's eight — at the ~19
TFLOP/s a float32 matmul at the highest precision reaches on a v5e (the
DeepSeek reference's rate), ~70 s. The harness allows 240 s.

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
(the one before PR 50 does not get this far: its ModelConfig refuses
`sliding_attention` in `layer_types`, and serve.py ends at start).
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
# is set from two readings each (PERF.md section 4, PR 50).
#
# bfloat16 — the configuration's, what the cell runs. Serving
# k-exaone-236b-a23b-ep8-d5 on a v5e reads a mean margin of 0.0004 to 0.0020
# sd over thirteen runs on thirteen seeds (my chip runs, PR 50: 1024
# positions each — eight requests of 8-16 k tokens, 128 outputs). The same
# forward with float8
# operands (`lower_precision`, 128 positions at 4096 tokens of context a run)
# reads 0.65 at the least (to 1.14). 0.05, the limit of the harness's other
# cells, lies between: 25 times the largest bfloat16 reading (fresh seeds
# read higher: the more room is above), a thirteenth of the smallest float8
# one.
MEAN_MARGIN_SD_MAX = 0.05
# float32 — the tiny-size tests (tests/test_k_exaone.py): there the program's
# own forward, in chunks through ring and pool and in decode scans, agrees
# with this reference to 2e-5 in every logit (margin 0.0), and a forward that
# drops the window's bound, widens it, rotates a full layer, drops the
# selection bias, the gates' scale or the shared expert misses by 0.01 to 3
# in a logit, and one that routes in bfloat16 by 9e-4 (asserted there).
FLOAT32_MARGIN_SD_MAX = 0.003
OLLAMA_DEFAULTS = {"repeat_penalty": 1.1, "repeat_last_n": 64}
CONFIG_KEYS = (
    "num_attention_heads", "num_key_value_heads", "head_dim", "hidden_size",
    "intermediate_size", "rms_norm_eps", "rope_parameters", "layer_types",
    "sliding_window", "num_dense_layers", "num_experts", "router_experts",
    "expert_offset", "num_experts_per_tok", "norm_topk_prob",
    "routed_scaling_factor", "moe_intermediate_size", "num_shared_experts",
    "vocab_size")
WINDOW, FULL = "sliding_attention", "full_attention"
NORM_TOPK_EPS = 1e-20
# Blocks (they change no number): queries a block of the [T, T] scores.
QUERY_BLOCK = 128
LOWER_TOKENS, LOWER_POSITIONS = 4096, 128
HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


class NotServed(Exception):
    """The weights served do not have the configuration's architecture."""


def _counts(cfg: dict) -> dict:
    n = len(cfg["layer_types"])
    dense = int(cfg["num_dense_layers"])
    return {"all": n, "dense": dense, "sparse": n - dense}


def served_layout(cfg: dict, params: dict) -> None:
    """Raises NotServed unless every weight the reference reads has the shape
    the configuration file's keys give it."""
    lp = params["layers"]
    n = _counts(cfg)
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    q_dim, kv_dim = (cfg["num_attention_heads"] * hd,
                     cfg["num_key_value_heads"] * hd)
    e, R = cfg["num_experts"], cfg.get("router_experts") or cfg["num_experts"]
    f, fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    fs = cfg["num_shared_experts"] * fe
    want = {
        "attn_norm": ("all", (d,)), "mlp_norm": ("all", (d,)),
        "wq": ("all", (d, q_dim)), "wk": ("all", (d, kv_dim)),
        "wv": ("all", (d, kv_dim)), "wo": ("all", (q_dim, d)),
        "q_norm": ("all", (hd,)), "k_norm": ("all", (hd,)),
        "w_gate": ("dense", (d, f)), "w_up": ("dense", (d, f)),
        "w_down": ("dense", (f, d)),
        "w_router": ("sparse", (d, R)), "router_bias": ("sparse", (R,)),
        "ws_gate": ("sparse", (d, fs)), "ws_up": ("sparse", (d, fs)),
        "ws_down": ("sparse", (fs, d)),
        "we_gate": ("sparse", (e, d, fe)), "we_up": ("sparse", (e, d, fe)),
        "we_down": ("sparse", (e, fe, d))}
    if set(cfg["layer_types"]) - {WINDOW, FULL} \
            or cfg.get("qk_norm") not in (True, "head") \
            or list(cfg.get("rope_layer_types") or ()) != [WINDOW] \
            or cfg.get("scoring_func") != "sigmoid" \
            or not cfg.get("use_expert_bias") \
            or cfg.get("norm_order", "pre") != "pre":
        raise NotServed("this reference is the family's: layers of "
                        "sliding_attention and full_attention, qk_norm "
                        "'head', rope_layer_types ['sliding_attention'], a "
                        "sigmoid router with a selection bias, pre-norm")
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
    print(f"k_exaone_decoder: the program cannot run this configuration: "
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


def _rope(theta: float, x):
    """Rotate-half RoPE over the whole head of x [T, H, hd] at positions
    0..T-1."""
    hd = x.shape[-1]
    inv = float(theta) ** (-np.arange(0, hd, 2, dtype=np.float64) / hd)
    ang = jnp.arange(x.shape[0], dtype=F32)[:, None] * jnp.asarray(inv, F32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(cfg: dict, mm, rnd, h, lp: dict, i: int, kind: str):
    t = h.shape[0]
    H, Hk, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    q = _norm(mm(h, lp["wq"][i]).reshape(t, H, hd), lp["q_norm"][i], eps)
    k = _norm(mm(h, lp["wk"][i]).reshape(t, Hk, hd), lp["k_norm"][i], eps)
    v = rnd(mm(h, lp["wv"][i]).reshape(t, Hk, hd))
    if kind == WINDOW:  # the full layers attend without positions
        theta = dict(cfg["rope_parameters"])["rope_theta"]
        q, k = _rope(theta, q), _rope(theta, k)
    # q head j attends kv head j // (H / Hk): the q heads a kv head at a time
    q, k = rnd(q).reshape(t, Hk, H // Hk, hd), rnd(k)
    pos = jnp.arange(t)
    window = cfg["sliding_window"]

    def block(q0):  # QUERY_BLOCK queries against every position
        qb = jax.lax.dynamic_slice_in_dim(q, q0, QUERY_BLOCK)
        s = jnp.einsum("qkgd,skd->kgqs", qb, k, precision=HI) / math.sqrt(hd)
        at = (q0 + jnp.arange(QUERY_BLOCK))[:, None]
        sees = pos[None, :] <= at
        if kind == WINDOW:
            sees = sees & (pos[None, :] > at - window)
        p = jax.nn.softmax(jnp.where(sees[None, None], s, -jnp.inf), axis=-1)
        return jnp.einsum("kgqs,skd->qkgd", rnd(p), v, precision=HI)

    o = jax.lax.map(block, jnp.arange(0, t, QUERY_BLOCK)).reshape(t, H * hd)
    return mm(o, lp["wo"][i])


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
    top = top * cfg.get("routed_scaling_factor", 1.0)
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
                             jnp.arange(cfg["num_experts"]))
    return routed + _swiglu(mm, h, lp["ws_gate"][e], lp["ws_up"][e],
                            lp["ws_down"][e])


@functools.partial(jax.jit,
                   static_argnames=("cfg_items", "kind", "dense", "lower"))
def _layer(params, x, i, of_ffn, cfg_items, kind: str, dense: bool,
           lower: bool):
    """x' [T, D] of layer i, the `of_ffn`-th of its FFN's kind (traced: ONE
    program a (kind, FFN) of layer; the blocks inside are loops, so it
    compiles small and its temporaries are freed before the next layer)."""
    cfg = dict(cfg_items)
    rnd = _float8 if lower else _exact

    def mm(a, w):
        return jnp.matmul(rnd(a), rnd(w.astype(F32)), precision=HI)

    lp, eps = params["layers"], cfg["rms_norm_eps"]
    x = x + _attention(cfg, mm, rnd, _norm(x, lp["attn_norm"][i], eps), lp,
                       i, kind)
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
    n_dense = int(cfg["num_dense_layers"])
    for i, kind in enumerate(cfg["layer_types"]):
        if kind not in (WINDOW, FULL):
            raise ValueError(f"layer_types[{i}] = {kind!r}")
        dense = i < n_dense
        x = _layer(params, x, np.int32(i),
                   np.int32(i if dense else i - n_dense), items, kind, dense,
                   lower)
    return _norm(x, params["final_norm"], cfg["rms_norm_eps"])


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
