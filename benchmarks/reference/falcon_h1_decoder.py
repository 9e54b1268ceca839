"""Plain float32 reference of the Falcon-H1 decoder family (Falcon-H1-34B:
attention AND a Mamba-2 state-space mixer in every layer, over the same normed
input, both added to the residual; the family's muP multipliers), and the
comparison that decides whether what the server returned agrees with it.

Independent of the code under test: no paging, no chunking, no per-slot state,
no kernel, no scheduler, no sampling epilogue, no dispatch and no layer loop of
the program's — one sequence, a Python loop over the layers, causal softmax
attention in blocks of QUERY_BLOCK queries over all keys, the convolution as
the sum over `mamba_d_conv` shifted copies plus the bias (zeros shifted in: no
window), and the recurrence token by token (a `lax.scan` over the T tokens of
the two lines below: no chunks, no state between calls). Every matmul is
float32 at the highest precision; a layer's weights are cast to float32 as the
loop reaches it, and the head is read in HEAD_CHUNKS blocks of the served rows
(a float32 copy of the 261,120-row head is 5.35 GB, of a layer 1.72 GB: more
than a chip that serves the model has free). Layer i, with `x` the residual,
`N(x; w) = x rsqrt(mean x^2 + rms_norm_eps) w` and `m_*` the configuration
file's published scalars:

    x_0 = E[id] embedding_multiplier
    h = N(x; attn_norm)
    attention:  u = h attention_in_multiplier
        q = u Wq, k = (u Wk) key_multiplier, v = u Wv  (no bias, no q/k norm)
        RoPE (rotate-half over the whole head, theta = rope_theta) on q and k
        a = causal softmax(q k^T / sqrt(head_dim)) v, num_attention_heads /
        num_key_value_heads q heads a K/V head;   attn = a Wo
    mixer (H = mamba_n_heads heads of dh = mamba_d_head, G = mamba_n_groups
    groups of ds = mamba_d_state; di = mamba_d_ssm = H dh):
        p = ((h ssm_in_multiplier) W_in) * mu,  W_in -> [z di | x di | B G ds
        | C G ds | dt H], mu = ssm_multipliers[0..4] on those five segments
        (W_in is served as two stacks, `ssm_in` [D, z | x | B | C] and
        `ssm_dt` [D, H]: its columns, in the published order)
        c_t = silu(bias + sum_j w[:, j] * s_{t-(K-1)+j}) over s = [x | B | C]
        (K = mamba_d_conv taps, s = 0 before position 0)
        dt_t = softplus(dt_t + dt_bias), A = -exp(A_log)   (a head, float32)
        S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T    (S_{-1} = 0, [dh, ds]
        a head; head j reads B, C of group j // (H / G))
        y_t = S_t C_t + D x_t
        g = y * silu(z);  g = g rsqrt(mean over each GROUP's di / G channels
        of g^2 + rms_norm_eps) * ssm_norm;   mix = g W_out
    x = x + attn attention_out_multiplier + mix ssm_out_multiplier
    x = x + ((silu((h' Wgate) mlp_multipliers[0]) * (h' Wup)) Wdown)
            mlp_multipliers[1],   h' = N(x; mlp_norm)
    logits = (N(x; final_norm) W_head^T) lm_head_multiplier    (head untied)

Departures from the published modelling code, all in the configuration file's
`assumed`: the program's weights are per projection (`wq wk wv`, not a fused
matrix), the mixer's in-projection is stored [in, out] and its dt columns are
a stack of their own (`ssm_dt`); dt is not clamped
(the published `time_step_limit` is (0, inf)); `mamba_chunk_size` plays no part
(no chunks here at all). The weights are seeded random, not the checkpoint's:
each stored tensor is drawn so that multiplier x tensor has the scale of a
plain N(0, 1 / fan_in) tensor. The prompt is byte tokens behind a BOS, not the
model's tokenizer.

It reads only the configuration FILE's keys and the weights the server serves
by the program's names: `embed`, `lm_head`, `final_norm`, and under `layers`,
each stacked on a leading axis over the layers: `attn_norm mlp_norm w_gate w_up
w_down wq wk wv wo ssm_in ssm_dt ssm_conv_w ssm_conv_b ssm_A_log ssm_D
ssm_dt_bias ssm_norm ssm_out`.

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
before PR 54 does not get this far: its ModelConfig has no field for the
`mamba_*` keys, and serve.py ends at start). So that is not reported as
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
# is set from two readings each (PERF.md section 6, PR 54).
#
# bfloat16 — the configuration's, what the cell runs. Serving falcon-h1-34b-d6
# on a v5e reads a mean margin of 0.00045 to 0.00065 sd over eight runs on
# eight seeds (my chip runs, PR 54: 2048 positions each, ~96 % of them the
# reference's own argmax, top-10 share 100 %). The same forward in float8
# (`lower_precision`, 256 positions of one request a run) reads 0.078 at the
# least and 0.102 at the most: 55 % argmax. 0.01 lies between: 15 times the
# largest bfloat16 reading, 8 times under the smallest float8 one. (The
# sibling cells' 0.05 would leave the float8 forward only 1.6 times of room:
# six layers under a 261 k head move a logit less than sixteen do.)
MEAN_MARGIN_SD_MAX = 0.01
# float32 — the tiny-size tests (tests/test_falcon_h1.py,
# benchmarks/tests/test_falcon_h1_cell.py): there the program's own forward
# reads 0.0 and the wrong forwards of the tests' ablations read far above.
FLOAT32_MARGIN_SD_MAX = 0.003
HEAD_CHUNKS = 8
QUERY_BLOCK = 128
OLLAMA_DEFAULTS = {"repeat_penalty": 1.1, "repeat_last_n": 64}
CONFIG_KEYS = (
    "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
    "head_dim", "hidden_size", "rms_norm_eps", "rope_theta",
    "tie_word_embeddings", "mamba_d_ssm", "mamba_d_state", "mamba_d_head",
    "mamba_n_heads", "mamba_n_groups", "mamba_d_conv", "mamba_conv_bias",
    "embedding_multiplier", "lm_head_multiplier", "attention_in_multiplier",
    "attention_out_multiplier", "key_multiplier", "ssm_in_multiplier",
    "ssm_out_multiplier", "ssm_multipliers", "mlp_multipliers")
HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


class NotServed(Exception):
    """The weights served do not have the configuration's architecture."""


def _sizes(cfg: dict) -> dict:
    di, gs = cfg["mamba_d_ssm"], cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    return {"di": di, "gs": gs, "conv": di + 2 * gs,
            "in": 2 * di + 2 * gs + cfg["mamba_n_heads"]}


def served_layout(cfg: dict, params: dict) -> None:
    """Raises NotServed unless every weight the reference reads has the shape
    the configuration file's keys give it."""
    lp = params["layers"]
    n, d = cfg["num_hidden_layers"], cfg["hidden_size"]
    q_dim = cfg["num_attention_heads"] * cfg["head_dim"]
    kv_dim = cfg["num_key_value_heads"] * cfg["head_dim"]
    z, h = _sizes(cfg), cfg["mamba_n_heads"]
    f = lp["w_gate"].shape[-1] if "w_gate" in lp else 0
    want = {
        "attn_norm": (d,), "mlp_norm": (d,), "w_gate": (d, f),
        "w_up": (d, f), "w_down": (f, d), "wq": (d, q_dim),
        "wk": (d, kv_dim), "wv": (d, kv_dim), "wo": (q_dim, d),
        "ssm_in": (d, z["in"] - h), "ssm_dt": (d, h),
        "ssm_conv_w": (z["conv"], cfg["mamba_d_conv"]),
        "ssm_A_log": (h,), "ssm_D": (h,), "ssm_dt_bias": (h,),
        "ssm_norm": (z["di"],), "ssm_out": (z["di"], d)}
    if cfg.get("mamba_conv_bias", True):
        want["ssm_conv_b"] = (z["conv"],)
    if h * cfg["mamba_d_head"] != z["di"] or h % cfg["mamba_n_groups"]:
        raise NotServed("mamba_n_heads x mamba_d_head is not mamba_d_ssm, or "
                        "the heads are no whole number a group")
    if cfg.get("intermediate_size") not in (None, f) or "lm_head" not in params:
        raise NotServed(f"w_gate is {f} wide, the configuration's MLP "
                        f"{cfg.get('intermediate_size')}; lm_head "
                        f"{'present' if 'lm_head' in params else 'absent'}")
    bad = [f"{name} is {tuple(lp[name].shape) if name in lp else 'absent'}, "
           f"the configuration's is {(n, *shape)}"
           for name, shape in want.items()
           if name not in lp or tuple(lp[name].shape) != (n, *shape)]
    if bad:
        raise NotServed("; ".join(bad))


def cannot_run(reason: str):
    """The program under test lacks the configuration's architecture: end the
    run with an error exit and no result line. Called on serve.py's watcher
    thread, inside the server process: the reason goes to the server's log,
    SIGTERM takes the server down its own graceful path, and this thread ends
    without an answer, so run.py finds the launcher gone ("wrote no
    reference.json") and exits 1."""
    print(f"falcon_h1_decoder: the program cannot run this configuration: "
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


def _rope(x, theta: float):
    """Rotate-half over the whole head of x [T, H, hd], position = row."""
    t, _, hd = x.shape
    inv = 1.0 / (float(theta) ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(t, dtype=F32)[:, None] * inv  # [T, hd / 2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(cfg: dict, mm, rnd, u, lp: dict, i: int):
    h_, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, t = cfg["head_dim"], u.shape[0]
    q = mm(u, lp["wq"][i])
    k = mm(u, lp["wk"][i]) * cfg.get("key_multiplier", 1.0)
    v = mm(u, lp["wv"][i])
    q = _rope(q.reshape(t, h_, hd), cfg["rope_theta"])
    k = _rope(k.reshape(t, hk, hd), cfg["rope_theta"])
    k = jnp.repeat(k, h_ // hk, axis=1)
    v = jnp.repeat(v.reshape(t, hk, hd), h_ // hk, axis=1)
    out = []
    for at in range(0, t, QUERY_BLOCK):  # a block of queries, every key
        qb = q[at:at + QUERY_BLOCK]
        s = jnp.einsum("thd,shd->hts", rnd(qb), rnd(k), precision=HI) \
            / math.sqrt(hd)
        causal = (at + jnp.arange(qb.shape[0]))[:, None] \
            >= jnp.arange(t)[None, :]
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        out.append(jnp.einsum("hts,shd->thd", rnd(p), rnd(v), precision=HI))
    return mm(jnp.concatenate(out).reshape(t, h_ * hd), lp["wo"][i])


def _recurrence(x, b, c, dt, a, rnd):
    """Token by token. x [T, H, dh], b, c [T, H, ds] (a head's group's), dt
    [T, H], a [H] -> y [T, H, dh]. The state stays float32 whatever `rnd`
    rounds: it is an accumulator, not a matmul operand."""
    def token(s, u):
        x_t, b_t, c_t, dt_t = u
        s = s * jnp.exp(dt_t * a)[:, None, None] \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return s, jnp.einsum("hds,hs->hd", rnd(s), rnd(c_t), precision=HI)

    s0 = jnp.zeros((x.shape[1], x.shape[2], b.shape[2]), F32)
    return jax.lax.scan(token, s0, (x, b, c, dt))[1]


def _mixer(cfg: dict, mm, rnd, h, lp: dict, i: int):
    t = h.shape[0]
    nh, dh, g, ds = (cfg["mamba_n_heads"], cfg["mamba_d_head"],
                     cfg["mamba_n_groups"], cfg["mamba_d_state"])
    z_ = _sizes(cfg)
    di, gs, cd = z_["di"], z_["gs"], z_["conv"]
    mu = jnp.concatenate([jnp.full((n,), m, F32) for n, m in zip(
        (di, di, gs, gs, nh), cfg.get("ssm_multipliers", (1.0,) * 5))])
    u = h * cfg.get("ssm_in_multiplier", 1.0)
    p = jnp.concatenate([mm(u, lp["ssm_in"][i]), mm(u, lp["ssm_dt"][i])],
                        axis=-1) * mu
    z, xbc, dt = p[:, :di], p[:, di:di + cd], p[:, di + cd:]
    w = lp["ssm_conv_w"][i].astype(F32)  # [channels, K]; w[:, K-1] meets s_t
    taps = cfg["mamba_d_conv"]
    conv = sum(w[:, j] * jnp.pad(xbc, ((taps - 1 - j, 0), (0, 0)))[:t]
               for j in range(taps))
    if cfg.get("mamba_conv_bias", True):
        conv = conv + lp["ssm_conv_b"][i].astype(F32)
    conv = jax.nn.silu(conv)
    x = conv[:, :di].reshape(t, nh, dh)
    b = jnp.repeat(conv[:, di:di + gs].reshape(t, g, ds), nh // g, axis=1)
    c = jnp.repeat(conv[:, di + gs:].reshape(t, g, ds), nh // g, axis=1)
    dt = jax.nn.softplus(dt + lp["ssm_dt_bias"][i].astype(F32))
    a = -jnp.exp(lp["ssm_A_log"][i].astype(F32))
    y = _recurrence(x, b, c, dt, a, rnd) \
        + lp["ssm_D"][i].astype(F32)[:, None] * x
    gated = (y.reshape(t, di) * jax.nn.silu(z)).reshape(t, g, di // g)
    gated = gated * jax.lax.rsqrt(
        jnp.mean(gated * gated, axis=-1, keepdims=True) + cfg["rms_norm_eps"])
    return mm(gated.reshape(t, di) * lp["ssm_norm"][i].astype(F32),
              lp["ssm_out"][i])


def hidden(cfg: dict, params: dict, tokens, rnd=_exact):
    """Final-norm hidden states [T, D] of one sequence `tokens` [T]."""
    def mm(a, w):
        return jnp.matmul(rnd(a), rnd(w.astype(F32)), precision=HI)

    lp, eps = params["layers"], cfg["rms_norm_eps"]
    mg, md = cfg.get("mlp_multipliers", (1.0, 1.0))
    x = params["embed"][tokens].astype(F32) \
        * cfg.get("embedding_multiplier", 1.0)
    for i in range(cfg["num_hidden_layers"]):
        h = _rms(x, lp["attn_norm"][i], eps)
        attn = _attention(cfg, mm, rnd,
                          h * cfg.get("attention_in_multiplier", 1.0), lp, i)
        mix = _mixer(cfg, mm, rnd, h, lp, i)
        x = x + attn * cfg.get("attention_out_multiplier", 1.0) \
            + mix * cfg.get("ssm_out_multiplier", 1.0)
        h = _rms(x, lp["mlp_norm"][i], eps)
        x = x + mm(jax.nn.silu(mm(h, lp["w_gate"][i]) * mg)
                   * mm(h, lp["w_up"][i]), lp["w_down"][i]) * md
    return _rms(x, params["final_norm"], eps)


def head_logits(cfg: dict, params: dict, h, rnd=_exact):
    """h [N, D] -> logits [N, V], the head a block of vocabulary rows at a
    time, each cast from the served rows as it is read."""
    head = params["embed"] if cfg.get("tie_word_embeddings") \
        else params["lm_head"]
    v = head.shape[0]
    step = -(-v // HEAD_CHUNKS)
    return jnp.concatenate([
        jnp.matmul(rnd(h), rnd(head[i:i + step].astype(F32)).T, precision=HI)
        for i in range(0, v, step)], axis=-1) \
        * cfg.get("lm_head_multiplier", 1.0)


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
