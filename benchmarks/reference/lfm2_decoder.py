"""Plain float32 reference of the LFM2 hybrid decoder family (LFM2-8B-A1B:
gated short convolutions beside GQA attention, a dense prefix, 32 experts
chosen by a sigmoid score plus a selection bias), and the comparison that
decides whether what the server returned agrees with it.

Independent of the code under test: no paging, no chunking, no conv state, no
kernel, no scheduler, no sampling epilogue, no dispatch and no layer loop of
the program's — one sequence, a Python loop over the file's `layer_types`,
full causal attention over a dense [T, T] score matrix, the convolution as the
sum over `conv_L_cache` shifted copies of z (zeros shifted in: no state, no
cache), the router in float32, and EVERY expert computed for EVERY token and
weighted by the router's weight (zero outside the token's top k): no sort, no
groups. Every matmul is float32 at the highest precision. Layer i, with `x`
the residual and kind = layer_types[i]:

    h = RMSNorm(x; attn_norm)                  (the operator norm; eps norm_eps)
    kind full_attention:
        q, k, v = h Wq, h Wk, h Wv  (no bias); RMSNorm over head_dim on q and
        on k (`qk_norm: "head"`), BEFORE RoPE (rotate-half, theta rope_theta);
        a = causal softmax(q k^T / sqrt(hd)) v;   x = x + a Wo
    kind conv:
        [B | C | u] = h W_in  (three chunks of hidden_size, in that order)
        z = B * u;  c_t = sum_j w[:, j] * z_{t-(L-1)+j}  (L = conv_L_cache,
        z = 0 before position 0);   x = x + (C * c) W_out
    h = RMSNorm(x; mlp_norm)                   (the FFN norm)
    i <  num_dense_layers: x = x + (silu(h Wgate) * (h Wup)) Wdown
    i >= num_dense_layers: s = sigmoid(h W_router)  (float32; `router_score`)
        S = top-k experts of s + b  (b the selection bias, `use_expert_bias`)
        w_e = s_e for e in S — WITHOUT b — divided by (their sum +
        norm_topk_eps) where `norm_topk_prob`, times routed_scaling_factor;
        0 elsewhere;   x = x + sum_e w_e (silu(h Wgate_e) * (h Wup_e)) Wdown_e
    logits = RMSNorm(x; final_norm) E^T        (head tied: tie_word_embeddings)

Departures from the published description, all in the configuration file's
`assumed`: what config.json does not say is taken from the published modelling
code (head_dim = hidden / heads; per-head q/k norm; the sigmoid score; the
order B, C, u of W_in's chunks; the 1e-6 in the normalisation; the tied head).
The weights are seeded random, not the checkpoint's, and the selection bias —
a trained buffer there, near zero at initialisation — is drawn with a standard
deviation of 0.1 so that it decides some tokens' k-th expert: a forward that
drops it, or weights by the biased score, does not agree. The prompt is byte
tokens behind a BOS, not the model's tokenizer.

It reads only the configuration FILE's keys and the weights the server serves
by the program's names: `embed`, `final_norm`, and under `layers`, each
stacked on a leading axis over the layers that HAVE it: `attn_norm mlp_norm`
(every layer), `wq wk wv wo q_norm k_norm` (attention layers), `conv_in conv_w
conv_out` (conv layers), `w_gate w_up w_down` (the dense prefix), `w_router
router_bias` and `we_gate we_up we_down` [., E, in, out] (expert layers).

The comparison is dense_decoder.py's, restated here so that the files stay
independent: teacher-forced on the ids the server returned, Ollama's
repetition penalty applied as the request's options ask, and `margin` = how
far below the reference's best (penalised) logit the returned id lies, in
standard deviations of that position's logits. A run agrees when the mean
margin over all checked positions is at most MEAN_MARGIN_SD_MAX (weights
served in float32: FLOAT32_MARGIN_SD_MAX).

`check` also reports what a forward one precision BELOW the configuration's
would read (`lower_precision`, over the first request): the same forward with
both operands of every matmul rounded to float8 (e4m3), its own greedy choice
at each position held to the float32 logits. It has to come out above the
limit, or the limit cannot tell bf16 from worse.

Before any of that, `check` holds the SHAPES of the weights served to the
file's keys (`served_layout`). A program that lacks this architecture has not
computed the model wrongly; it cannot run the configuration at all (the one
before PR 32 does not get this far: its ModelConfig has no field for
`layer_types`, and serve.py ends at start). So that is not reported as
`agrees: false` beside a throughput: the reason goes to the server's log, the
server is asked to stop (SIGTERM, its graceful path) and no reference.json is
written, which ends the run with an error exit and no result line.
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
# is set from two readings each (PERF.md section 6, PR 32).
#
# bfloat16 — the configuration's, what the cell runs. Serving
# lfm2-8b-a1b-d18 on a v5e reads a mean margin of 0.115 to 0.132 sd over seven
# runs on seven seeds (my chip runs, PR 32: 2048 positions each, 55.8-57.5 %
# of them the reference's own argmax, top-10 share 94-96 %, p99 0.94-1.13);
# the jnp path on the CPU at the same widths reads 0.131 (96 positions). That
# is not the OLMoE cell's 0.0004, and it is not an error of the program: the
# SAME program with float32 weights agrees with this reference to 2.5e-5 in
# every logit at these widths (margin 0.0). With seeded random weights the
# top 4 of 32 sigmoid scores lie close together and each carries a quarter of
# the FFN's output after normalisation, so bfloat16 rounding of the hidden
# states flips some token's 4th expert in most passes, and a flip moves the
# residual far (the same stack under a softmax router that is not renormalised
# reads 0.015). The same forward in float8 (`lower_precision`, 256 positions
# of one request a run) reads 1.21 at the least and 1.33 at the most: 4.7-7 %
# argmax, no better than chance. 0.35 lies between: 2.7 times the largest
# bfloat16 reading, under a third of the smallest float8 one. The two readings
# are only a factor of nine apart, so the limit cannot stand ten times under
# the float8 reading as the OLMoE reference's does; what a limit this loose
# still refuses at this size is a forward as wrong as float8's. The fine
# distinctions are held where precision does not blur them:
MEAN_MARGIN_SD_MAX = 0.35
# float32 — the tiny-size tests (benchmarks/tests/test_lfm2_cell.py, tier-1):
# there the program's own forward, whole or in chunks over carried state,
# reads 0.0 and nine wrong forwards read 0.043 (the biased score as weight),
# 0.12 (q/k norm left out), 0.16 (the state dropped at a chunk boundary), 0.20
# (the selection bias dropped), 0.26 (a softmax for the sigmoid), 0.41 (no
# normalisation), 2.3 (the dense prefix routed), 2.8 (B and C swapped), 3.0
# (the taps reversed), and the float8 forward more than ten times the limit
# (asserted there). 0.003 is a fourteenth of the smallest.
FLOAT32_MARGIN_SD_MAX = 0.003
HEAD_CHUNKS = 8
OLLAMA_DEFAULTS = {"repeat_penalty": 1.1, "repeat_last_n": 64}
CONFIG_KEYS = ("num_attention_heads", "num_key_value_heads", "head_dim",
               "hidden_size", "norm_eps", "rope_theta", "qk_norm",
               "layer_types", "num_dense_layers", "conv_L_cache",
               "num_experts", "num_experts_per_tok", "norm_topk_prob",
               "norm_topk_eps", "router_score", "use_expert_bias",
               "routed_scaling_factor", "tie_word_embeddings")
ATTENTION, CONV = "full_attention", "conv"
HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


class NotServed(Exception):
    """The weights served do not have the configuration's architecture."""


def _counts(cfg: dict) -> dict:
    kinds = list(cfg["layer_types"])
    dense = int(cfg["num_dense_layers"])
    return {"all": len(kinds), "attn": kinds.count(ATTENTION),
            "conv": kinds.count(CONV), "dense": dense,
            "experts": len(kinds) - dense}


def served_layout(cfg: dict, params: dict) -> None:
    """Raises NotServed unless every weight the reference reads has the shape
    the configuration file's keys give it."""
    lp = params["layers"]
    n = _counts(cfg)
    d = cfg["hidden_size"]
    q_dim = cfg["num_attention_heads"] * cfg["head_dim"]
    kv_dim = cfg["num_key_value_heads"] * cfg["head_dim"]
    e, hd = cfg["num_experts"], cfg["head_dim"]
    f = lp["w_gate"].shape[-1] if "w_gate" in lp else 0
    fe = lp["we_gate"].shape[-1] if "we_gate" in lp else 0
    want = {
        "attn_norm": ("all", (d,)), "mlp_norm": ("all", (d,)),
        "wq": ("attn", (d, q_dim)), "wk": ("attn", (d, kv_dim)),
        "wv": ("attn", (d, kv_dim)), "wo": ("attn", (q_dim, d)),
        "q_norm": ("attn", (hd,)), "k_norm": ("attn", (hd,)),
        "conv_in": ("conv", (d, 3 * d)),
        "conv_w": ("conv", (d, cfg["conv_L_cache"])),
        "conv_out": ("conv", (d, d)),
        "w_gate": ("dense", (d, f)), "w_up": ("dense", (d, f)),
        "w_down": ("dense", (f, d)),
        "w_router": ("experts", (d, e)), "router_bias": ("experts", (e,)),
        "we_gate": ("experts", (e, d, fe)), "we_up": ("experts", (e, d, fe)),
        "we_down": ("experts", (e, fe, d))}
    if cfg.get("qk_norm") != "head" or not cfg.get("use_expert_bias") \
            or cfg.get("router_score") != "sigmoid":
        raise NotServed("this reference is the family's: qk_norm 'head', "
                        "router_score 'sigmoid', use_expert_bias true")
    bad = [f"{name} is {tuple(lp[name].shape) if name in lp else 'absent'}, "
           f"the configuration's is {(n[kind], *shape)}"
           for name, (kind, shape) in want.items()
           if n[kind] and (name not in lp
                           or tuple(lp[name].shape) != (n[kind], *shape))]
    if bad:
        raise NotServed("; ".join(bad))


def cannot_run(reason: str):
    """The program under test lacks the configuration's architecture: end the
    run with an error exit and no result line. Called on serve.py's watcher
    thread, inside the server process: the reason goes to the server's log,
    SIGTERM takes the server down its own graceful path, and this thread ends
    without an answer, so run.py finds the launcher gone ("wrote no
    reference.json") and exits 1."""
    print(f"lfm2_decoder: the program cannot run this configuration: "
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


def _rope(x, theta):
    """Rotate-half rotary embedding (the published modelling code's) of
    x [T, H, hd] at positions 0..T-1."""
    t, _, hd = x.shape
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(t, dtype=F32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(cfg: dict, mm, rnd, h, lp: dict, a: int):
    h_, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, eps, t = cfg["head_dim"], cfg["norm_eps"], h.shape[0]
    q, k, v = mm(h, lp["wq"][a]), mm(h, lp["wk"][a]), mm(h, lp["wv"][a])
    q, k, v = (q.reshape(t, h_, hd), k.reshape(t, hk, hd),
               v.reshape(t, hk, hd))
    q, k = _rms(q, lp["q_norm"][a], eps), _rms(k, lp["k_norm"][a], eps)
    q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    k, v = (jnp.repeat(k, h_ // hk, axis=1), jnp.repeat(v, h_ // hk, axis=1))
    s = jnp.einsum("thd,shd->hts", rnd(q), rnd(k), precision=HI) \
        / math.sqrt(hd)
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("hts,shd->thd", rnd(p), rnd(v), precision=HI)
    return mm(o.reshape(t, h_ * hd), lp["wo"][a])


def _short_conv(cfg: dict, mm, h, lp: dict, c: int):
    t = h.shape[0]
    gate_b, gate_c, u = jnp.split(mm(h, lp["conv_in"][c]), 3, axis=-1)
    z = gate_b * u
    w = lp["conv_w"][c].astype(F32)  # [D, L]; w[:, L-1] meets z_t itself
    window = cfg["conv_L_cache"]
    mixed = sum(w[:, j] * jnp.pad(z, ((window - 1 - j, 0), (0, 0)))[:t]
                for j in range(window))
    return mm(gate_c * mixed, lp["conv_out"][c])


def _experts(cfg: dict, mm, h, lp: dict, e: int):
    # The router is float32 whatever the rest runs in, as the model states.
    s = jax.nn.sigmoid(jnp.matmul(h, lp["w_router"][e].astype(F32),
                                  precision=HI))  # [T, E], each on its own
    _, idx = jax.lax.top_k(s + lp["router_bias"][e].astype(F32),
                           cfg["num_experts_per_tok"])
    top = jnp.take_along_axis(s, idx, axis=-1)  # the score WITHOUT the bias
    if cfg.get("norm_topk_prob"):
        top = top / (top.sum(axis=-1, keepdims=True)
                     + cfg.get("norm_topk_eps", 0.0))
    top = top * cfg.get("routed_scaling_factor", 1)
    t = h.shape[0]
    w = jnp.zeros_like(s).at[jnp.arange(t)[:, None], idx].set(top)

    def one(name, j):  # expert j's matrix, read out of the whole stack
        stack = lp[name]
        return jax.lax.dynamic_slice(
            stack, (e, j, 0, 0), (1, 1) + stack.shape[2:])[0, 0]

    def expert(acc, j):  # one expert over every token, weighted
        y = mm(jax.nn.silu(mm(h, one("we_gate", j)))
               * mm(h, one("we_up", j)), one("we_down", j))
        return acc + jax.lax.dynamic_index_in_dim(
            w, j, 1, keepdims=False)[:, None] * y, None

    delta, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                            jnp.arange(cfg["num_experts"]))
    return delta


def hidden(cfg: dict, params: dict, tokens, rnd=_exact):
    """Final-norm hidden states [T, D] of one sequence `tokens` [T]."""
    def mm(a, w):
        return jnp.matmul(rnd(a), rnd(w.astype(F32)), precision=HI)

    lp, eps = params["layers"], cfg["norm_eps"]
    x = params["embed"][tokens].astype(F32)
    n_attn = n_conv = 0
    for i, kind in enumerate(cfg["layer_types"]):
        h = _rms(x, lp["attn_norm"][i], eps)
        if kind == ATTENTION:
            x = x + _attention(cfg, mm, rnd, h, lp, n_attn)
            n_attn += 1
        elif kind == CONV:
            x = x + _short_conv(cfg, mm, h, lp, n_conv)
            n_conv += 1
        else:
            raise ValueError(f"layer_types[{i}] = {kind!r}")
        h = _rms(x, lp["mlp_norm"][i], eps)
        if i < cfg["num_dense_layers"]:
            x = x + mm(jax.nn.silu(mm(h, lp["w_gate"][i]))
                       * mm(h, lp["w_up"][i]), lp["w_down"][i])
        else:
            x = x + _experts(cfg, mm, h, lp, i - cfg["num_dense_layers"])
    return _rms(x, params["final_norm"], eps)


def head_logits(cfg: dict, params: dict, h, rnd=_exact):
    """h [N, D] -> logits [N, V], the head a slice at a time."""
    head = params["embed"] if cfg.get("tie_word_embeddings") \
        else params["lm_head"]
    v = head.shape[0]
    step = -(-v // HEAD_CHUNKS)
    return jnp.concatenate([
        jnp.matmul(rnd(h), rnd(head[i:i + step].astype(F32)).T, precision=HI)
        for i in range(0, v, step)], axis=-1)


def logits(cfg: dict, params: dict, tokens):
    """[T, V] float32 logits of one sequence: what the tier-1 tests hold the
    served path's logits to."""
    return head_logits(cfg, params, hidden(cfg, params, tokens))


@functools.partial(jax.jit, static_argnames=("cfg_items", "max_out", "last_n",
                                             "lower"))
def _margins(params, tokens, n_prompt, penalty, cfg_items, max_out, last_n,
             lower=False):
    """tokens [T] = prompt then returned ids (then padding, which causal
    attention keeps from every earlier position). For output j < max_out:
    (margin in sd, ids the reference ranks above the returned one). With
    `lower` the id held to the reference is not the returned one but the
    float8 forward's own choice at that position."""
    cfg = dict(cfg_items)
    at = jnp.clip(n_prompt - 1 + jnp.arange(max_out), 0, tokens.shape[0] - 1)
    logit = head_logits(cfg, params, hidden(cfg, params, tokens)[at])
    sd = jnp.maximum(logit.std(axis=-1, keepdims=True), 1e-30)
    # the last_n context tokens before each output, penalised
    back = at[:, None] - jnp.arange(last_n)[None, :]
    seen = jnp.zeros(logit.shape, bool).at[
        jnp.arange(max_out)[:, None], tokens[jnp.clip(back, 0)]].max(back >= 0)

    def penalised(lg):
        return jnp.where(seen, jnp.where(lg > 0, lg / penalty, lg * penalty),
                         lg)

    logit = penalised(logit)
    if lower:
        low = head_logits(cfg, params,
                          hidden(cfg, params, tokens, _float8)[at], _float8)
        chosen = jnp.argmax(penalised(low), axis=-1)
    else:
        chosen = tokens[jnp.clip(at + 1, 0, tokens.shape[0] - 1)]
    got = jnp.take_along_axis(logit, chosen[:, None], axis=-1)
    margin = (logit.max(axis=-1, keepdims=True) - got) / sd
    return margin[:, 0], (logit > got).sum(axis=-1)


def check(cfg: dict, params: dict, requests: list, pad_to: int,
          max_out: int) -> dict:
    """`requests`: [{"prompt": text, "ids": returned ids, "options": the
    request's Ollama options}]. The prompt is byte tokens behind a BOS (id 1,
    byte b -> b + 3), as the configuration serves it."""
    try:
        served_layout(cfg, params)
    except NotServed as e:
        cannot_run(str(e))
    cfg_items = tuple(sorted(
        (k, tuple(cfg[k]) if isinstance(cfg[k], list) else cfg[k])
        for k in CONFIG_KEYS if k in cfg))
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
        args = (params, jnp.asarray(tokens), np.int32(len(prompt)),
                np.float32(opts["repeat_penalty"] or 1.0))
        kw = dict(cfg_items=cfg_items, max_out=max_out,
                  last_n=int(opts["repeat_last_n"]))
        m, a = _margins(*args, **kw)
        m, a = np.asarray(m)[:len(ids)], np.asarray(a)[:len(ids)]
        margins.append(m)
        ranks.append(a)
        per_request.append({"prompt_tokens": len(prompt), "outputs": len(ids),
                            "mean_margin_sd": float(m.mean()),
                            "argmax_share": float((a == 0).mean())})
        if lower is None:
            lm, la = _margins(*args, **kw, lower=True)
            lm, la = np.asarray(lm)[:len(ids)], np.asarray(la)[:len(ids)]
            lower = {"precision": "float8_e4m3fn", "positions": int(lm.size),
                     "mean_margin_sd": float(lm.mean()),
                     "argmax_share": float((la == 0).mean())}
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
            "lower_precision": lower,
            "per_request": per_request}
