"""Plain float32 reference of the Olmo-Hybrid decoder family (Olmo-Hybrid-7B:
gated delta-rule linear-attention layers beside full attention without a rotary
embedding, the norms on the sublayers' outputs), and the comparison that
decides whether what the server returned agrees with it.

Independent of the code under test: no paging, no chunking, no per-slot state,
no kernel, no scheduler, no sampling epilogue, no dispatch and no layer loop of
the program's — one sequence, a Python loop over the file's `layer_types`,
full causal attention over a dense [T, T] score matrix, the convolution as the
sum over `linear_conv_kernel_dim` shifted copies (zeros shifted in: no window),
and the rule as its token-serial recurrence (a `lax.scan` over the T tokens of
the four lines below: no chunks, no state between calls). Every matmul is
float32 at the highest precision. Layer i, with `x` the residual and kind =
layer_types[i] (`norm_order: "post"`: each norm weighs a sublayer's OUTPUT):

    kind full_attention:
        q, k, v = x Wq, x Wk, x Wv  (no bias); RMSNorm over the WHOLE q and
        the whole k vector (`qk_norm: "full"`); no rotary embedding
        (`rope_parameters.rope_theta` null); H heads of head_dim;
        a = causal softmax(q k^T / sqrt(hd)) v;   mix = a Wo
    kind linear_attention (H = linear_num_value_heads heads, dk =
    linear_key_head_dim, dv = linear_value_head_dim):
        [q | k | v | z] = x W_in  (H dk | H dk | H dv | H dv),
        [b | a] = x W_ba  (H | H);
        c_t = silu(sum_j w[:, j] * u_{t-(K-1)+j}) over u = [q | k | v]
        (K = linear_conv_kernel_dim taps, u = 0 before position 0);
        per head: q_t = c^q_t / sqrt(|c^q_t|^2 + 1e-6) / sqrt(dk),
        k_t = c^k_t / sqrt(|c^k_t|^2 + 1e-6), v_t = c^v_t;
        beta_t = sigmoid(b_t) (x 2 where `linear_allow_neg_eigval`),
        g_t = -exp(A_log) softplus(a_t + dt_bias);
        S' = exp(g_t) S_{t-1};  r_t = beta_t (v_t - S'^T k_t);
        S_t = S' + k_t r_t^T;  o_t = S_t^T q_t      (S_{-1} = 0, [dk, dv])
        mix = (RMSNorm_dv(o_t; lin_norm) * silu(z_t))_{heads} W_out
    h = x + RMSNorm(mix; attn_norm)
    x = h + RMSNorm((silu(h Wgate) * (h Wup)) Wdown; mlp_norm)
    logits = RMSNorm(x; final_norm) W_head^T    (head untied)

Departures from the published description, all in the configuration file's
`assumed`: what the catalog's copy of config.json does not say is the family's
convention (OLMo 2 / 3: the reordered norm, whole-vector q/k norm; head_dim =
hidden / heads; a null rope_theta = no rotary embedding) or the published
gated-delta-rule code's (the L2 norm of q and k inside the rule with 1e-6
under the root, the order of W_in's parts, the SiLU after the convolution, the
init ranges of A_log and dt_bias). The weights are seeded random, not the
checkpoint's. The prompt is byte tokens behind a BOS, not the model's
tokenizer.

It reads only the configuration FILE's keys and the weights the server serves
by the program's names: `embed`, `lm_head`, `final_norm`, and under `layers`,
each stacked on a leading axis over the layers that HAVE it: `attn_norm
mlp_norm w_gate w_up w_down` (every layer), `wq wk wv wo q_norm k_norm`
(attention layers), `lin_in lin_ba lin_conv_w lin_A_log lin_dt_bias lin_norm
lin_out` (linear-attention layers).

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
before PR 35 does not get this far: its ModelConfig refuses `layer_types` with
"linear_attention" and has no field for the `linear_*` keys, and serve.py ends
at start). So that is not reported as `agrees: false` beside a throughput: the
reason goes to the server's log, the server is asked to stop (SIGTERM, its
graceful path) and no reference.json is written, which ends the run with an
error exit and no result line.
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
# is set from two readings each (PERF.md section 4, PR 35).
#
# bfloat16 — the configuration's, what the cell runs. Serving
# olmo-hybrid-7b-d16 on a v5e reads a mean margin of 0.0036 to 0.0045 sd over
# eight runs on eight seeds (my chip runs, PR 35: 2048 positions each,
# 89.2-90.4 % of them the reference's own argmax, top-10 share 100 %, worst
# position 0.13-0.23). The same forward in float8 (`lower_precision`, 256
# positions of one request a run) reads 1.60 at the least and 1.73 at the
# most: 2 % argmax. 0.05 lies between: 11 times the largest bfloat16 reading,
# 32 times under the smallest float8 one.
MEAN_MARGIN_SD_MAX = 0.05
# float32 — the tiny-size tests (benchmarks/tests/test_olmo_hybrid_cell.py):
# there the program's own forward, in one span or two over carried state,
# reads 0.0 and eight wrong forwards read 0.0085 (the rule's state kept in
# bf16, over 64 outputs a request), 0.52 (RoPE applied), 1.52 (the SiLU after
# the convolution left out), 1.56 (beta without the factor 2), 1.77 (the decay
# left out), 2.83 (the taps reversed), 2.90 (q/k not L2-normalised), 2.98
# (pre-norm for post-norm), and the float8 forward more than ten times the
# limit (asserted there).
FLOAT32_MARGIN_SD_MAX = 0.003
HEAD_CHUNKS = 8
OLLAMA_DEFAULTS = {"repeat_penalty": 1.1, "repeat_last_n": 64}
CONFIG_KEYS = ("num_attention_heads", "num_key_value_heads", "head_dim",
               "hidden_size", "rms_norm_eps", "qk_norm", "norm_order",
               "layer_types", "linear_num_key_heads", "linear_num_value_heads",
               "linear_key_head_dim", "linear_value_head_dim",
               "linear_conv_kernel_dim", "linear_allow_neg_eigval",
               "tie_word_embeddings")
ATTENTION, LINEAR = "full_attention", "linear_attention"
L2_EPS = 1e-6
HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


class NotServed(Exception):
    """The weights served do not have the configuration's architecture."""


def _counts(cfg: dict) -> dict:
    kinds = list(cfg["layer_types"])
    return {"all": len(kinds), "attn": kinds.count(ATTENTION),
            "lin": kinds.count(LINEAR)}


def served_layout(cfg: dict, params: dict) -> None:
    """Raises NotServed unless every weight the reference reads has the shape
    the configuration file's keys give it."""
    lp = params["layers"]
    n = _counts(cfg)
    d = cfg["hidden_size"]
    q_dim = cfg["num_attention_heads"] * cfg["head_dim"]
    kv_dim = cfg["num_key_value_heads"] * cfg["head_dim"]
    h, dk, dv = (cfg["linear_num_value_heads"], cfg["linear_key_head_dim"],
                 cfg["linear_value_head_dim"])
    kd, vd = cfg["linear_num_key_heads"] * dk, h * dv
    f = lp["w_gate"].shape[-1] if "w_gate" in lp else 0
    want = {
        "attn_norm": ("all", (d,)), "mlp_norm": ("all", (d,)),
        "w_gate": ("all", (d, f)), "w_up": ("all", (d, f)),
        "w_down": ("all", (f, d)),
        "wq": ("attn", (d, q_dim)), "wk": ("attn", (d, kv_dim)),
        "wv": ("attn", (d, kv_dim)), "wo": ("attn", (q_dim, d)),
        "q_norm": ("attn", (q_dim,)), "k_norm": ("attn", (kv_dim,)),
        "lin_in": ("lin", (d, 2 * kd + 2 * vd)), "lin_ba": ("lin", (d, 2 * h)),
        "lin_conv_w": ("lin", (2 * kd + vd, cfg["linear_conv_kernel_dim"])),
        "lin_A_log": ("lin", (h,)), "lin_dt_bias": ("lin", (h,)),
        "lin_norm": ("lin", (dv,)), "lin_out": ("lin", (vd, d))}
    if cfg.get("qk_norm") != "full" or cfg.get("norm_order") != "post" \
            or cfg["linear_num_key_heads"] != h \
            or (cfg.get("rope_parameters") or {}).get("rope_theta", 0) \
            is not None:
        raise NotServed("this reference is the family's: qk_norm 'full', "
                        "norm_order 'post', rope_parameters.rope_theta null, "
                        "one key head a value head")
    if cfg.get("intermediate_size") not in (None, f) or "lm_head" not in params:
        raise NotServed(f"w_gate is {f} wide, the configuration's MLP "
                        f"{cfg.get('intermediate_size')}; lm_head "
                        f"{'present' if 'lm_head' in params else 'absent'}")
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
    print(f"olmo_hybrid_decoder: the program cannot run this configuration: "
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


def _attention(cfg: dict, mm, rnd, x, lp: dict, a: int):
    h_, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, eps, t = cfg["head_dim"], cfg["rms_norm_eps"], x.shape[0]
    q, k, v = mm(x, lp["wq"][a]), mm(x, lp["wk"][a]), mm(x, lp["wv"][a])
    q, k = _rms(q, lp["q_norm"][a], eps), _rms(k, lp["k_norm"][a], eps)
    q, k, v = (q.reshape(t, h_, hd), k.reshape(t, hk, hd),
               v.reshape(t, hk, hd))
    k, v = (jnp.repeat(k, h_ // hk, axis=1), jnp.repeat(v, h_ // hk, axis=1))
    s = jnp.einsum("thd,shd->hts", rnd(q), rnd(k), precision=HI) \
        / math.sqrt(hd)
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("hts,shd->thd", rnd(p), rnd(v), precision=HI)
    return mm(o.reshape(t, h_ * hd), lp["wo"][a])


def _delta_rule(q, k, v, g, beta, rnd):
    """The token-serial recurrence. q, k [T, H, dk] (normalised), v [T, H,
    dv], g, beta [T, H] -> o [T, H, dv]. The state stays float32 whatever
    `rnd` rounds: it is an accumulator, not a matmul operand."""
    def token(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = s * jnp.exp(g_t)[:, None, None]
        r = b_t[:, None] * (v_t - jnp.einsum(
            "hkv,hk->hv", rnd(s), rnd(k_t), precision=HI))
        s = s + k_t[:, :, None] * r[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", rnd(s), rnd(q_t), precision=HI)

    s0 = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), F32)
    return jax.lax.scan(token, s0, (q, k, v, g, beta))[1]


def _linear_attention(cfg: dict, mm, rnd, x, lp: dict, c: int):
    t = x.shape[0]
    h, dk, dv = (cfg["linear_num_value_heads"], cfg["linear_key_head_dim"],
                 cfg["linear_value_head_dim"])
    kd, vd = h * dk, h * dv
    u = mm(x, lp["lin_in"][c])
    qkv, z = u[:, :2 * kd + vd], u[:, 2 * kd + vd:]
    # the gates are float32 whatever the rest runs in, as the model states
    ba = jnp.matmul(x, lp["lin_ba"][c].astype(F32), precision=HI)
    beta = jax.nn.sigmoid(ba[:, :h]) \
        * (2.0 if cfg["linear_allow_neg_eigval"] else 1.0)
    g = -jnp.exp(lp["lin_A_log"][c].astype(F32)) * jax.nn.softplus(
        ba[:, h:] + lp["lin_dt_bias"][c].astype(F32))
    w = lp["lin_conv_w"][c].astype(F32)  # [channels, K]; w[:, K-1] meets u_t
    taps = cfg["linear_conv_kernel_dim"]
    mixed = jax.nn.silu(sum(
        w[:, j] * jnp.pad(qkv, ((taps - 1 - j, 0), (0, 0)))[:t]
        for j in range(taps)))
    q, k, v = (mixed[:, :kd].reshape(t, h, dk),
               mixed[:, kd:2 * kd].reshape(t, h, dk),
               mixed[:, 2 * kd:].reshape(t, h, dv))
    q = q / jnp.sqrt(jnp.sum(q * q, axis=-1, keepdims=True) + L2_EPS) \
        / math.sqrt(dk)
    k = k / jnp.sqrt(jnp.sum(k * k, axis=-1, keepdims=True) + L2_EPS)
    o = _delta_rule(q, k, v, g, beta, rnd)
    o = _rms(o, lp["lin_norm"][c], cfg["rms_norm_eps"]).reshape(t, vd)
    return mm(o * jax.nn.silu(z), lp["lin_out"][c])


def hidden(cfg: dict, params: dict, tokens, rnd=_exact):
    """Final-norm hidden states [T, D] of one sequence `tokens` [T]."""
    def mm(a, w):
        return jnp.matmul(rnd(a), rnd(w.astype(F32)), precision=HI)

    lp, eps = params["layers"], cfg["rms_norm_eps"]
    x = params["embed"][tokens].astype(F32)
    n_attn = n_lin = 0
    for i, kind in enumerate(cfg["layer_types"]):
        if kind == ATTENTION:
            mix = _attention(cfg, mm, rnd, x, lp, n_attn)
            n_attn += 1
        elif kind == LINEAR:
            mix = _linear_attention(cfg, mm, rnd, x, lp, n_lin)
            n_lin += 1
        else:
            raise ValueError(f"layer_types[{i}] = {kind!r}")
        x = x + _rms(mix, lp["attn_norm"][i], eps)
        mlp = mm(jax.nn.silu(mm(x, lp["w_gate"][i])) * mm(x, lp["w_up"][i]),
                 lp["w_down"][i])
        x = x + _rms(mlp, lp["mlp_norm"][i], eps)
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
