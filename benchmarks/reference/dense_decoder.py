"""Plain float32 reference of the dense decoder family (Llama, Qwen2.5 with
q/k/v bias, Qwen3 with per-head q/k norm), and the comparison that decides
whether what the server returned agrees with it.

Independent of the code under test: no paging, no chunking, no kernel, no
scheduler, no sampling epilogue — one sequence, full causal attention over a
dense [T, T] score matrix, every matmul in float32 at the highest precision.
It reads only the configuration FILE's published keys and the weights the
server serves (the checkpoint layout: `embed`, `lm_head`, `final_norm`, and
per layer, stacked on a leading axis, `attn_norm wq wk wv wo mlp_norm w_gate
w_up w_down` with optional `bq bk bv q_norm k_norm`; projection matrices are
[in, out]). Weights are data here, as a checkpoint would be.

The comparison is teacher-forced: the prompt and the ids the server returned
go through the reference in one pass, and at every output position the id the
server chose is looked up in the reference's logits — after the repetition
penalty the request's options ask for, since a greedy Ollama request is the
argmax of the PENALISED logits (llama.cpp's rule: over the last
`repeat_last_n` context tokens, a positive logit is divided by
`repeat_penalty` and a negative one multiplied; Ollama's defaults are 64 and
1.1). `margin` is how far below the reference's best logit the returned id
lies, in standard deviations of that position's logits: 0 where the two agree
on the argmax; a few hundredths where bf16 rounding swapped two near-ties;
about 4.5 where the server's forward has nothing to do with the model (152 k
random logits). A run agrees when the mean margin over all checked positions
is at most MEAN_MARGIN_SD_MAX.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

# bf16 serving on a v5e gives a mean margin of 0.0004 sd (PERF.md section 4),
# logit noise of about 0.01 sd; 0.01 is logit noise of about 0.04 sd. One
# layer of 14 skipped gives about 0.5 (arithmetic, and benchmarks/tests).
MEAN_MARGIN_SD_MAX = 0.01
HEAD_CHUNKS = 8
OLLAMA_DEFAULTS = {"repeat_penalty": 1.1, "repeat_last_n": 64}
HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def _mm(x, w):
    return jnp.matmul(x, w.astype(F32), precision=HI)


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


def _layer(cfg: dict, x, lp: dict):
    h_, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    t = x.shape[0]
    h = _rms(x, lp["attn_norm"], eps)
    q, k, v = _mm(h, lp["wq"]), _mm(h, lp["wk"]), _mm(h, lp["wv"])
    if cfg.get("attention_bias"):
        q, k, v = (q + lp["bq"].astype(F32), k + lp["bk"].astype(F32),
                   v + lp["bv"].astype(F32))
    q, k, v = (q.reshape(t, h_, hd), k.reshape(t, hk, hd),
               v.reshape(t, hk, hd))
    if cfg.get("qk_norm"):
        q, k = _rms(q, lp["q_norm"], eps), _rms(k, lp["k_norm"], eps)
    q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    # grouped-query attention: query head i reads key/value head i // group
    k, v = (jnp.repeat(k, h_ // hk, axis=1), jnp.repeat(v, h_ // hk, axis=1))
    s = jnp.einsum("thd,shd->hts", q, k, precision=HI) / math.sqrt(hd)
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("hts,shd->thd", p, v, precision=HI).reshape(t, h_ * hd)
    x = x + _mm(o, lp["wo"])
    h = _rms(x, lp["mlp_norm"], eps)
    return x + _mm(jax.nn.silu(_mm(h, lp["w_gate"])) * _mm(h, lp["w_up"]),
                   lp["w_down"])


@functools.partial(jax.jit,
                   static_argnames=("cfg_items", "max_out", "last_n"))
def _margins(params, tokens, n_prompt, penalty, cfg_items, max_out, last_n):
    """tokens [T] = prompt then returned ids (then padding, which causal
    attention keeps from every earlier position). For output j < max_out:
    (margin in sd, ids the reference ranks above the returned one)."""
    cfg = dict(cfg_items)
    x = params["embed"][tokens].astype(F32)
    x, _ = jax.lax.scan(lambda c, lp: (_layer(cfg, c, lp), None), x,
                        params["layers"])
    at = jnp.clip(n_prompt - 1 + jnp.arange(max_out), 0, tokens.shape[0] - 1)
    chosen = tokens[jnp.clip(at + 1, 0, tokens.shape[0] - 1)]
    h = _rms(x[at], params["final_norm"], cfg["rms_norm_eps"])
    head = params["embed"] if cfg.get("tie_word_embeddings") \
        else params["lm_head"]
    v = head.shape[0]
    step = -(-v // HEAD_CHUNKS)  # the head a slice at a time, not [V, d] f32
    logits = jnp.concatenate([_mm(h, head[i:i + step].T)
                              for i in range(0, v, step)], axis=-1)
    sd = jnp.maximum(logits.std(axis=-1, keepdims=True), 1e-30)
    # the last_n context tokens before each output, penalised
    back = at[:, None] - jnp.arange(last_n)[None, :]
    seen = jnp.zeros(logits.shape, bool).at[
        jnp.arange(max_out)[:, None], tokens[jnp.clip(back, 0)]].max(back >= 0)
    logits = jnp.where(seen, jnp.where(logits > 0, logits / penalty,
                                       logits * penalty), logits)
    got = jnp.take_along_axis(logits, chosen[:, None], axis=-1)
    margin = (logits.max(axis=-1, keepdims=True) - got) / sd
    return margin[:, 0], (logits > got).sum(axis=-1)


def check(cfg: dict, params: dict, requests: list, pad_to: int,
          max_out: int) -> dict:
    """`requests`: [{"prompt": text, "ids": returned ids, "options": the
    request's Ollama options}]. The prompt is byte tokens behind a BOS (id 1,
    byte b -> b + 3), as the configuration serves it."""
    cfg_items = tuple(sorted(
        (k, cfg[k]) for k in (
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "rms_norm_eps", "rope_theta", "attention_bias", "qk_norm",
            "tie_word_embeddings") if k in cfg))
    margins, ranks, per_request = [], [], []
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
        m, a = _margins(params, jnp.asarray(tokens), np.int32(len(prompt)),
                        np.float32(opts["repeat_penalty"] or 1.0),
                        cfg_items=cfg_items, max_out=max_out,
                        last_n=int(opts["repeat_last_n"]))
        m, a = np.asarray(m)[:len(ids)], np.asarray(a)[:len(ids)]
        margins.append(m)
        ranks.append(a)
        per_request.append({"prompt_tokens": len(prompt), "outputs": len(ids),
                            "mean_margin_sd": float(m.mean()),
                            "argmax_share": float((a == 0).mean())})
    m, a = np.concatenate(margins), np.concatenate(ranks)
    mean = float(m.mean())
    return {"agrees": bool(np.isfinite(mean) and mean <= MEAN_MARGIN_SD_MAX),
            "requests": len(requests), "positions": int(m.size),
            "mean_margin_sd": mean, "mean_margin_sd_max": MEAN_MARGIN_SD_MAX,
            "p99_margin_sd": float(np.quantile(m, 0.99)),
            "max_margin_sd": float(m.max()),
            "argmax_share": float((a == 0).mean()),
            "top10_share": float((a < 10).mean()),
            "per_request": per_request}
