"""Plain float32 reference of the sparse decoder family (OLMoE: MHA, RMSNorm
over the whole q / k vector, 64 experts with top-8 routing that is not
renormalised; Mixtral by the same keys: per-head or no q/k norm, top-2 of 8,
renormalised), and the comparison that decides whether what the server
returned agrees with it.

Independent of the code under test: no paging, no chunking, no kernel, no
scheduler, no sampling epilogue, and above all no dispatch — one sequence,
full causal attention over a dense [T, T] score matrix, the router in float32,
and EVERY expert computed for EVERY token and weighted by the router's weight
(zero outside the token's top k): no sort, no groups, no capacity. Every
matmul is float32 at the highest precision. One layer (`x` the residual):

    h = RMSNorm(x; attn_norm)
    q = RMSNorm(h Wq; q_norm)   k = RMSNorm(h Wk; k_norm)   v = h Wv
        (`qk_norm: "full"`: over the WHOLE projected vector, before the split
         into heads; `true`/`"head"`: per head, after it; `false`: none)
    q, k = RoPE(q), RoPE(k)  (rotate-half, per head);  a = causal softmax(q k^T / sqrt(hd)) v
    x = x + a Wo;   h = RMSNorm(x; mlp_norm)
    p = softmax(h W_router)  (float32, over all experts)
    S = top-k experts of p;  w_e = p_e for e in S (divided by their sum only
        where `norm_topk_prob`), 0 elsewhere
    x = x + sum_e w_e (silu(h Wgate_e) * (h Wup_e)) Wdown_e

It reads only the configuration FILE's keys and the weights the server serves
by the checkpoint layout's names (`embed`, `lm_head`, `final_norm`, and per
layer, stacked on a leading axis, `attn_norm wq wk wv wo q_norm k_norm
mlp_norm w_router`, and `we_gate we_up we_down` stacked [L, E, in, out]).

The comparison is dense_decoder.py's, restated here so that the two files
stay independent: teacher-forced on the ids the server returned, Ollama's
repetition penalty applied as the request's options ask, and `margin` = how
far below the reference's best (penalised) logit the returned id lies, in
standard deviations of that position's logits. A run agrees when the mean
margin over all checked positions is at most MEAN_MARGIN_SD_MAX.

`check` also reports what a forward one precision BELOW the configuration's
would read (`lower_precision`, over the first request): the same forward with
both operands of every matmul rounded to float8 (e4m3), its own greedy choice
at each position held to the float32 logits. It has to come out above the
limit, or the limit cannot tell bf16 from worse.

Before any of that, `check` holds the SHAPES of the weights served to the
file's keys (`served_layout`). A program that lacks this architecture (the one
before PR 27 reads `qk_norm: "full"` as merely true and serves a 128-wide
per-head norm) has not computed the model wrongly; it cannot run the
configuration at all, and no margin says anything about it. So that is not
reported as `agrees: false` beside a throughput: the reason goes to the
server's log, the server is asked to stop (SIGTERM, its graceful path) and no
reference.json is written, which ends the run with an error exit and no result
line (`cannot_run`). A program that has the architecture never gets there.
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

# Two readings set the limit (PERF.md section 4; my chip runs, PR 27). bf16
# serving of olmoe-1b-7b-d10 on a v5e reads a mean margin of 0.00017 to
# 0.00038 sd over eight seeds (2048 positions each, 95.8-97.9 % of them the
# reference's own argmax, p99 0.006-0.013, worst 0.039): the dense cells'
# level (0.0004). The same forward in float8 (`lower_precision`, 256
# positions of one request a run) reads 0.030 at the least and 0.128 at the
# most. (The seventeen runs of the refused first try, ISSUE 27, read
# 0.00022-0.00057 and 0.04-0.14.) At tiny size (benchmarks/tests, float32)
# the four wrong forwards read: lowest-weight expert of the k left out 0.013,
# a capacity that drops (factor 1.0) 0.029, top-k weights renormalised 0.10,
# per-head in place of whole-vector q/k norm 0.21. 0.003 is five times the
# largest bf16 reading of either try, a tenth of the smallest float8 one
# here and a quarter of the smallest wrong one.
MEAN_MARGIN_SD_MAX = 0.003
HEAD_CHUNKS = 8
OLLAMA_DEFAULTS = {"repeat_penalty": 1.1, "repeat_last_n": 64}
CONFIG_KEYS = ("num_attention_heads", "num_key_value_heads", "head_dim",
               "rms_norm_eps", "rope_theta", "attention_bias", "qk_norm",
               "num_experts", "num_experts_per_tok", "norm_topk_prob",
               "tie_word_embeddings")
HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


class NotServed(Exception):
    """The weights served do not have the configuration's architecture."""


def served_layout(cfg: dict, params: dict) -> None:
    """Raises NotServed unless every weight the reference reads has the shape
    the configuration file's keys give it."""
    lp = params["layers"]
    n_layers, d = lp["attn_norm"].shape
    q_dim = cfg["num_attention_heads"] * cfg["head_dim"]
    kv_dim = cfg["num_key_value_heads"] * cfg["head_dim"]
    e = cfg["num_experts"]
    f = lp["we_gate"].shape[-1]
    want = {"wq": (d, q_dim), "wk": (d, kv_dim), "wv": (d, kv_dim),
            "wo": (q_dim, d), "mlp_norm": (d,), "w_router": (d, e),
            "we_gate": (e, d, f), "we_up": (e, d, f), "we_down": (e, f, d)}
    norm = cfg.get("qk_norm")
    if norm == "full":
        want.update(q_norm=(q_dim,), k_norm=(kv_dim,))
    elif norm:
        want.update(q_norm=(cfg["head_dim"],), k_norm=(cfg["head_dim"],))
    bad = [f"{name} is {tuple(lp[name].shape) if name in lp else 'absent'}, "
           f"the configuration's is {(n_layers, *shape)}"
           for name, shape in want.items()
           if name not in lp or tuple(lp[name].shape) != (n_layers, *shape)]
    if bad:
        raise NotServed("; ".join(bad))


def cannot_run(reason: str):
    """The program under test lacks the configuration's architecture: end the
    run with an error exit and no result line. Called on serve.py's watcher
    thread, inside the server process: the reason goes to the server's log,
    SIGTERM takes the server down its own graceful path, and this thread ends
    without an answer, so run.py finds the launcher gone ("wrote no
    reference.json") and exits 1."""
    print(f"olmoe_decoder: the program cannot run this configuration: "
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


def _layer(cfg: dict, rnd, x, lp: dict):
    def mm(a, w):
        return jnp.matmul(rnd(a), rnd(w.astype(F32)), precision=HI)

    h_, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    norm = cfg.get("qk_norm")
    t = x.shape[0]
    h = _rms(x, lp["attn_norm"], eps)
    q, k, v = mm(h, lp["wq"]), mm(h, lp["wk"]), mm(h, lp["wv"])
    if cfg.get("attention_bias"):
        q, k, v = (q + lp["bq"].astype(F32), k + lp["bk"].astype(F32),
                   v + lp["bv"].astype(F32))
    if norm == "full":  # over all heads' lanes at once
        q, k = _rms(q, lp["q_norm"], eps), _rms(k, lp["k_norm"], eps)
    q, k, v = (q.reshape(t, h_, hd), k.reshape(t, hk, hd),
               v.reshape(t, hk, hd))
    if norm and norm != "full":  # per head
        q, k = _rms(q, lp["q_norm"], eps), _rms(k, lp["k_norm"], eps)
    q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    k, v = (jnp.repeat(k, h_ // hk, axis=1), jnp.repeat(v, h_ // hk, axis=1))
    s = jnp.einsum("thd,shd->hts", rnd(q), rnd(k), precision=HI) \
        / math.sqrt(hd)
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("hts,shd->thd", rnd(p), rnd(v), precision=HI)
    x = x + mm(o.reshape(t, h_ * hd), lp["wo"])

    h = _rms(x, lp["mlp_norm"], eps)
    # The router is float32 whatever the rest runs in, as the model states.
    p = jax.nn.softmax(jnp.matmul(h, lp["w_router"].astype(F32),
                                  precision=HI), axis=-1)  # [T, E]
    top, idx = jax.lax.top_k(p, cfg["num_experts_per_tok"])
    if cfg.get("norm_topk_prob"):
        top = top / top.sum(axis=-1, keepdims=True)
    w = jnp.zeros_like(p).at[jnp.arange(t)[:, None], idx].set(top)

    def expert(acc, e):  # one expert over every token, weighted
        wg, wu, wd, we = e
        return acc + we[:, None] * mm(jax.nn.silu(mm(h, wg)) * mm(h, wu),
                                      wd), None

    delta, _ = jax.lax.scan(expert, jnp.zeros_like(x), (
        lp["we_gate"], lp["we_up"], lp["we_down"], w.T))
    return x + delta


def hidden(cfg: dict, params: dict, tokens, rnd=_exact):
    """Final-norm hidden states [T, D] of one sequence `tokens` [T]."""
    x = params["embed"][tokens].astype(F32)
    x, _ = jax.lax.scan(lambda c, lp: (_layer(cfg, rnd, c, lp), None), x,
                        params["layers"])
    return _rms(x, params["final_norm"], cfg["rms_norm_eps"])


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
    cfg_items = tuple(sorted((k, cfg[k]) for k in CONFIG_KEYS if k in cfg))
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
    return {"agrees": bool(np.isfinite(mean) and mean <= MEAN_MARGIN_SD_MAX),
            "requests": len(requests), "positions": int(m.size),
            "mean_margin_sd": mean, "mean_margin_sd_max": MEAN_MARGIN_SD_MAX,
            "p99_margin_sd": float(np.quantile(m, 0.99)),
            "max_margin_sd": float(m.max()),
            "argmax_share": float((a == 0).mean()),
            "top10_share": float((a < 10).mean()),
            "lower_precision": lower,
            "per_request": per_request}
