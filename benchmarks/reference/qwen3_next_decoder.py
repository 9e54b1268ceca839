"""Plain float32 reference of the Qwen3-Next decoder family
(Qwen3-Next-80B-A3B-Instruct: gated delta-rule linear-attention layers with
fewer key heads than value heads beside GATED full attention with a partial
rotary embedding, zero-centred norms, routed experts behind a gated shared
expert — of whose routed experts the configuration HOLDS a share), and the
comparison that decides whether what the server returned agrees with it.

Independent of the code under test: no paging, no chunking, no per-slot state,
no kernel, no sort of rows by expert, no scheduler, no sampling epilogue, no
dispatch and no layer loop of the program's — one sequence, a Python loop over
the file's `layer_types`, attention as a dense causal softmax, the convolution
as the sum over `linear_conv_kernel_dim` shifted copies (zeros shifted in: no
window), the rule as its token-serial recurrence (a `lax.scan` over the T
tokens of the four lines below: no chunks, no state between calls), EVERY held
expert computed for EVERY token and weighted by its gate (zero where not
chosen), the shared expert once. Every matmul is float32 at the highest
precision. It is computed in blocks — queries of the [T, T] scores, one expert
at a time — so that 16,640 positions fit beside the served weights; the
blocks change no number. Layer i, with `x` the residual, kind =
layer_types[i], N(x; w) = x rsqrt(mean x^2 + eps) (1 + w) (the ZERO-CENTRED
norm: `zero_centred_norm`) and h = N(x; attn_norm):

    kind full_attention (H = num_attention_heads, Hk = num_key_value_heads,
    hd = head_dim, rot = hd * partial_rotary_factor):
        q, g = h Wq, h Wq_gate  (H hd each: a head's q and its gate);
        k, v = h Wk, h Wv  (Hk hd); no bias; per head q = N(q; q_norm),
        k = N(k; k_norm) (weights over hd); RoPE (rotate-half, theta
        rope_theta, frequencies over rot) on the FIRST rot lanes of each head
        of q and k, lanes rot.. pass; a = causal softmax(q k^T / sqrt(hd)) v,
        H / Hk q heads a kv head;   mix = (a * sigmoid(g)) Wo
    kind linear_attention (Hk = linear_num_key_heads, H =
    linear_num_value_heads, dk, dv the head sizes, r = H / Hk):
        [q | k | v | z] = h W_in  (Hk dk | Hk dk | H dv | H dv),
        [b | a] = h W_ba  (H | H) in float32;
        c_t = silu(sum_j w[:, j] * u_{t-(K-1)+j}) over u = [q | k | v]
        (K = linear_conv_kernel_dim taps, u = 0 before position 0);
        per key head: q_t = c^q_t / sqrt(|c^q_t|^2 + 1e-6) / sqrt(dk),
        k_t = c^k_t / sqrt(|c^k_t|^2 + 1e-6); key head j serves value heads
        j r .. j r + r - 1 (repeat_interleave); v_t = c^v_t per value head;
        beta_t = sigmoid(b_t) (x 2 where `linear_allow_neg_eigval`: not here),
        g_t = -exp(A_log) softplus(a_t + dt_bias);
        S' = exp(g_t) S_{t-1};  r_t = beta_t (v_t - S'^T k_t);
        S_t = S' + k_t r_t^T;  o_t = S_t^T q_t      (S_{-1} = 0, [dk, dv])
        mix = (RMSNorm_dv(o_t) * lin_norm * silu(z_t))_{heads} W_out
        (the PLAIN weight: not 1 + w)
    x = x + mix;   h = N(x; mlp_norm)
    p = softmax(h W_r) in float32 over ALL router_experts; the top
        num_experts_per_tok chosen, g_e = p_e / (sum of the chosen p)
        (`norm_topk_prob`);
    x = x + sigmoid(h . w_sg) SwiGLU_shared(h)
          + sum_{e chosen AND held} g_e SwiGLU_e(h)
        held: experts expert_offset .. expert_offset + num_experts - 1.
    logits = N(x; final_norm) W_head^T   (the head's rows are the served slice)

The gates are normalised over all the chosen experts, held or not; what the
absent experts would have added is left out — here as in the program — and
that partial result goes on to the next layer (model-configs guide, section
4). Departures from the published model, all in the configuration file's
`assumed`: the published `q_proj` holds a head's q and gate side by side and
`in_proj_qkvz` / `in_proj_ba` interleave their parts a key-head group — the
served layout holds q and gate apart and `[q | k | v | z]`, `[b | a]` in that
order (a permutation of columns: the loader's matter); the multi-token
prediction module `described_as` mentions has no key in config.json and is
not served. The weights are seeded random, the norm weights drawn around
zero with a standard deviation of 0.1. The prompt is byte tokens behind a
BOS, not the model's tokenizer.

It reads only the configuration FILE's keys and the weights the server serves
by the program's names: `embed`, `lm_head`, `final_norm`, and under `layers`,
each stacked on a leading axis over the layers that HAVE it: `attn_norm
mlp_norm w_router ws_gate ws_up ws_down w_shared_gate` and `we_gate we_up
we_down` [., E held, in, out] (every layer), `wq wq_gate wk wv wo q_norm
k_norm` (attention layers), `lin_in lin_ba lin_conv_w lin_A_log lin_dt_bias
lin_norm lin_out` (linear-attention layers).

What it costs (reckoned before the chip run, PR 47): at the cell's longest
request (16,640 positions) the 128 held experts over every token are 128 x
16,640 x 12 layers x 6 x 2048 x 512 = 1.6e14 FLOP, the mixers, attention and
head ~0.3e14: ~1.9e14 a request, 1.5e15 for the harness's eight — at the ~19
TFLOP/s a float32 matmul at the highest precision reaches on a v5e (the
DeepSeek reference's rate), ~80 s, plus 8 x 9 x 16,640 token-serial steps of
the rule. The harness allows 240 s.

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
LOWER_TOKENS tokens of the first request's prompt (a quarter of a request's
cost, twice). It has to come out above the limit, or the limit cannot tell
bf16 from worse.

Before any of that, `check` holds the SHAPES of the weights served to the
file's keys (`served_layout`), as the references beside it do: a program that
lacks the architecture ends the run with an error exit and no result line
(the one before PR 47 does not get this far: its ModelConfig refuses 32 value
heads over 16 key heads and has no field for `partial_rotary_factor`, and
serve.py ends at start).
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
# is set from two readings each (PERF.md section 6, PR 47).
#
# bfloat16 — the configuration's, what the cell runs. Serving
# qwen3-next-80b-a3b-ep4-d12 on a v5e reads a mean margin of 0.0168 to 0.0291
# sd over twelve runs on twelve seeds (my chip runs, PR 47: 1024 positions each —
# eight requests of 8-16 k tokens, 128 outputs — 75-82 % of them the
# reference's own argmax, 99.8-100 % in its top 10, the worst single position
# 0.37-0.91). The same forward with float8 operands (`lower_precision`, 128
# positions at 4096 tokens of context a run) reads 0.641 at the least and
# 0.806 at the most: 11-24 % argmax. 0.1 lies between, 3.4 times the largest
# bfloat16 reading (fresh seeds read higher: the more room is above) and a
# sixth of the smallest float8 one. (The readings are three to five times the
# other families': the router picks 10 of 512 near-equal softmax scores, so a
# bfloat16 hidden flips a token's tenth expert against the float32 one far
# more often than a top-8-of-64 router's, and twelve such layers follow one
# another; the readings above include that.)
MEAN_MARGIN_SD_MAX = 0.1
# float32 — the tiny-size tests (tests/test_qwen3_next.py): there the
# program's own forward, in chunks over carried state and in decode scans,
# agrees with this reference to 2e-5 in every logit (margin 0.0), and a
# forward that reads w for 1 + w, drops either gate, rotates the whole head,
# doubles beta or pairs key heads with the wrong value heads misses by 0.05
# to 3 in a logit (asserted there).
FLOAT32_MARGIN_SD_MAX = 0.003
OLLAMA_DEFAULTS = {"repeat_penalty": 1.1, "repeat_last_n": 64}
CONFIG_KEYS = (
    "num_attention_heads", "num_key_value_heads", "head_dim", "hidden_size",
    "rms_norm_eps", "rope_theta", "partial_rotary_factor", "layer_types",
    "linear_num_key_heads", "linear_num_value_heads", "linear_key_head_dim",
    "linear_value_head_dim", "linear_conv_kernel_dim",
    "linear_allow_neg_eigval", "num_experts", "router_experts",
    "expert_offset", "num_experts_per_tok", "norm_topk_prob",
    "moe_intermediate_size", "shared_expert_intermediate_size", "vocab_size")
ATTENTION, LINEAR = "full_attention", "linear_attention"
L2_EPS = 1e-6
# Blocks (they change no number): queries a block of the [T, T] scores.
QUERY_BLOCK = 128
LOWER_TOKENS, LOWER_POSITIONS = 4096, 128
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
    hd = cfg["head_dim"]
    q_dim, kv_dim = (cfg["num_attention_heads"] * hd,
                     cfg["num_key_value_heads"] * hd)
    h, dk, dv = (cfg["linear_num_value_heads"], cfg["linear_key_head_dim"],
                 cfg["linear_value_head_dim"])
    kd, vd = cfg["linear_num_key_heads"] * dk, h * dv
    e, R = cfg["num_experts"], cfg.get("router_experts") or cfg["num_experts"]
    fe, fs = (cfg["moe_intermediate_size"],
              cfg["shared_expert_intermediate_size"])
    want = {
        "attn_norm": ("all", (d,)), "mlp_norm": ("all", (d,)),
        "w_router": ("all", (d, R)), "ws_gate": ("all", (d, fs)),
        "ws_up": ("all", (d, fs)), "ws_down": ("all", (fs, d)),
        "w_shared_gate": ("all", (d,)),
        "we_gate": ("all", (e, d, fe)), "we_up": ("all", (e, d, fe)),
        "we_down": ("all", (e, fe, d)),
        "wq": ("attn", (d, q_dim)), "wq_gate": ("attn", (d, q_dim)),
        "wk": ("attn", (d, kv_dim)), "wv": ("attn", (d, kv_dim)),
        "wo": ("attn", (q_dim, d)), "q_norm": ("attn", (hd,)),
        "k_norm": ("attn", (hd,)),
        "lin_in": ("lin", (d, 2 * kd + 2 * vd)), "lin_ba": ("lin", (d, 2 * h)),
        "lin_conv_w": ("lin", (2 * kd + vd, cfg["linear_conv_kernel_dim"])),
        "lin_A_log": ("lin", (h,)), "lin_dt_bias": ("lin", (h,)),
        "lin_norm": ("lin", (dv,)), "lin_out": ("lin", (vd, d))}
    if not (cfg.get("attn_output_gate") and cfg.get("zero_centred_norm")
            and cfg.get("shared_expert_gate")) \
            or cfg.get("qk_norm") not in (True, "head") \
            or set(cfg["layer_types"]) - {ATTENTION, LINEAR}:
        raise NotServed("this reference is the family's: attn_output_gate, "
                        "zero_centred_norm and shared_expert_gate true, "
                        "qk_norm 'head', layers of full_attention and "
                        "linear_attention")
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
    print(f"qwen3_next_decoder: the program cannot run this configuration: "
          f"{reason}", file=sys.stderr, flush=True)
    os.kill(os.getpid(), signal.SIGTERM)
    raise SystemExit(reason)


def _exact(x):
    return x


def _float8(x):
    """x rounded to float8 e4m3 and back: the precision below bfloat16."""
    return x.astype(jnp.float8_e4m3fn).astype(F32)


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _norm(x, w, eps):
    """The family's zero-centred RMSNorm: times (1 + w)."""
    return _rms(x, eps) * (1.0 + w.astype(F32))


def _rope(cfg: dict, x):
    """Rotate-half RoPE over the FIRST head_dim * partial_rotary_factor lanes
    of x [T, H, hd] at positions 0..T-1; the other lanes pass."""
    rot = int(cfg["head_dim"] * cfg.get("partial_rotary_factor", 1.0))
    inv = float(cfg["rope_theta"]) ** (
        -np.arange(0, rot, 2, dtype=np.float64) / rot)
    ang = jnp.arange(x.shape[0], dtype=F32)[:, None] * jnp.asarray(inv, F32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., : rot // 2], x[..., rot // 2: rot]
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin, x[..., rot:]], axis=-1)


def _attention(cfg: dict, mm, rnd, h, lp: dict, a: int):
    t = h.shape[0]
    H, Hk, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    q = _norm(mm(h, lp["wq"][a]).reshape(t, H, hd), lp["q_norm"][a], eps)
    k = _norm(mm(h, lp["wk"][a]).reshape(t, Hk, hd), lp["k_norm"][a], eps)
    v = rnd(mm(h, lp["wv"][a]).reshape(t, Hk, hd))
    # q head j attends kv head j // (H / Hk): the q heads a kv head at a time
    q = rnd(_rope(cfg, q)).reshape(t, Hk, H // Hk, hd)
    k = rnd(_rope(cfg, k))
    pos = jnp.arange(t)

    def block(q0):  # QUERY_BLOCK queries against every position
        qb = jax.lax.dynamic_slice_in_dim(q, q0, QUERY_BLOCK)
        s = jnp.einsum("qkgd,skd->kgqs", qb, k, precision=HI) / math.sqrt(hd)
        causal = pos[None, :] <= (q0 + jnp.arange(QUERY_BLOCK))[:, None]
        p = jax.nn.softmax(jnp.where(causal[None, None], s, -jnp.inf),
                           axis=-1)
        return jnp.einsum("kgqs,skd->qkgd", rnd(p), v, precision=HI)

    o = jax.lax.map(block, jnp.arange(0, t, QUERY_BLOCK)).reshape(t, H * hd)
    return mm(o * jax.nn.sigmoid(mm(h, lp["wq_gate"][a])), lp["wo"][a])


def _delta_rule(q, k, v, g, beta, rnd):
    """The token-serial recurrence. q, k [T, Hk, dk] (normalised), v [T, H,
    dv], g, beta [T, H] -> o [T, H, dv]; key head j serves value heads j r ..
    j r + r - 1. The state stays float32 whatever `rnd` rounds: it is an
    accumulator, not a matmul operand."""
    r = v.shape[1] // q.shape[1]

    def token(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        q_t, k_t = jnp.repeat(q_t, r, axis=0), jnp.repeat(k_t, r, axis=0)
        s = s * jnp.exp(g_t)[:, None, None]
        c = b_t[:, None] * (v_t - jnp.einsum(
            "hkv,hk->hv", rnd(s), rnd(k_t), precision=HI))
        s = s + k_t[:, :, None] * c[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", rnd(s), rnd(q_t), precision=HI)

    s0 = jnp.zeros((v.shape[1], q.shape[2], v.shape[2]), F32)
    return jax.lax.scan(token, s0, (q, k, v, g, beta))[1]


def _linear_attention(cfg: dict, mm, rnd, h, lp: dict, c: int):
    t = h.shape[0]
    hk, hv, dk, dv = (cfg["linear_num_key_heads"],
                      cfg["linear_num_value_heads"],
                      cfg["linear_key_head_dim"],
                      cfg["linear_value_head_dim"])
    kd, vd = hk * dk, hv * dv
    w_in = lp["lin_in"][c]
    w = lp["lin_conv_w"][c].astype(F32)  # [channels, K]; w[:, K-1] meets u_t
    taps = cfg["linear_conv_kernel_dim"]

    def mixed(lo, hi):
        """Channels lo..hi of silu(conv([q | k | v])): the convolution is
        depthwise, so a part's channels need that part's projection only
        (the parts are computed one at a time: they change no number)."""
        u = mm(h, w_in[:, lo:hi])
        return jax.nn.silu(sum(
            w[lo:hi, j] * jnp.pad(u, ((taps - 1 - j, 0), (0, 0)))[:t]
            for j in range(taps)))

    # the gates are float32 whatever the rest runs in, as the model states
    ba = jnp.matmul(h, lp["lin_ba"][c].astype(F32), precision=HI)
    beta = jax.nn.sigmoid(ba[:, :hv]) \
        * (2.0 if cfg.get("linear_allow_neg_eigval") else 1.0)
    g = -jnp.exp(lp["lin_A_log"][c].astype(F32)) * jax.nn.softplus(
        ba[:, hv:] + lp["lin_dt_bias"][c].astype(F32))
    q, k, v = (mixed(0, kd).reshape(t, hk, dk),
               mixed(kd, 2 * kd).reshape(t, hk, dk),
               mixed(2 * kd, 2 * kd + vd).reshape(t, hv, dv))
    q = q / jnp.sqrt(jnp.sum(q * q, axis=-1, keepdims=True) + L2_EPS) \
        / math.sqrt(dk)
    k = k / jnp.sqrt(jnp.sum(k * k, axis=-1, keepdims=True) + L2_EPS)
    o = _delta_rule(q, k, v, g, beta, rnd)
    o = _rms(o, cfg["rms_norm_eps"]) * lp["lin_norm"][c].astype(F32)  # plain w
    z = mm(h, w_in[:, 2 * kd + vd:])
    return mm(o.reshape(t, vd) * jax.nn.silu(z), lp["lin_out"][c])


def _swiglu(mm, h, gate, up, down):
    return mm(jax.nn.silu(mm(h, gate)) * mm(h, up), down)


def gates(cfg: dict, h, lp: dict, i: int):
    """[T, router_experts] float32: the gate of every expert of the router,
    zero where not chosen."""
    p = jax.nn.softmax(jnp.matmul(h, lp["w_router"][i].astype(F32),
                                  precision=HI), axis=-1)
    top, idx = jax.lax.top_k(p, cfg["num_experts_per_tok"])
    if cfg.get("norm_topk_prob"):
        top = top / top.sum(axis=-1, keepdims=True)
    return jnp.zeros_like(p).at[jnp.arange(p.shape[0])[:, None], idx].set(top)


def _experts(cfg: dict, mm, h, lp: dict, i: int):
    w = gates(cfg, h, lp, i)
    first = cfg.get("expert_offset", 0)

    def one(name, j):  # held expert j's matrix, read out of the whole stack
        stack = lp[name]
        return jax.lax.dynamic_slice(
            stack, (i, j, 0, 0), (1, 1) + stack.shape[2:])[0, 0]

    def expert(acc, j):  # one held expert over every token, weighted
        y = _swiglu(mm, h, one("we_gate", j), one("we_up", j),
                    one("we_down", j))
        return acc + jax.lax.dynamic_index_in_dim(
            w, first + j, 1, keepdims=False)[:, None] * y, None

    routed, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                             jnp.arange(cfg["num_experts"]))
    sg = jax.nn.sigmoid(jnp.matmul(h, lp["w_shared_gate"][i].astype(F32),
                                   precision=HI))
    return routed + sg[:, None] * _swiglu(
        mm, h, lp["ws_gate"][i], lp["ws_up"][i], lp["ws_down"][i])


@functools.partial(jax.jit, static_argnames=("cfg_items", "kind", "lower"))
def _layer(params, x, i, of_kind, cfg_items, kind: str, lower: bool):
    """x' [T, D] of layer i, the `of_kind`-th of its kind (traced: ONE program
    a kind of layer; the blocks inside are loops, so it compiles small and
    its temporaries are freed before the next layer)."""
    cfg = dict(cfg_items)
    rnd = _float8 if lower else _exact

    def mm(a, w):
        return jnp.matmul(rnd(a), rnd(w.astype(F32)), precision=HI)

    lp, eps = params["layers"], cfg["rms_norm_eps"]
    h = _norm(x, lp["attn_norm"][i], eps)
    if kind == ATTENTION:
        x = x + _attention(cfg, mm, rnd, h, lp, of_kind)
    else:
        x = x + _linear_attention(cfg, mm, rnd, h, lp, of_kind)
    return x + _experts(cfg, mm, _norm(x, lp["mlp_norm"][i], eps), lp, i)


def _cfg_items(cfg: dict) -> tuple:
    return tuple(sorted(
        (k, tuple(cfg[k]) if isinstance(cfg[k], list) else cfg[k])
        for k in CONFIG_KEYS if k in cfg))


def hidden(cfg: dict, params: dict, tokens, lower: bool = False):
    """Final-norm hidden states [T, D] of one sequence `tokens` [T] (T a
    multiple of QUERY_BLOCK)."""
    items = _cfg_items(cfg)
    x = params["embed"][tokens].astype(F32)
    seen = {ATTENTION: 0, LINEAR: 0}
    for i, kind in enumerate(cfg["layer_types"]):
        if kind not in seen:
            raise ValueError(f"layer_types[{i}] = {kind!r}")
        x = _layer(params, x, np.int32(i), np.int32(seen[kind]), items, kind,
                   lower)
        seen[kind] += 1
    return _norm(x, params["final_norm"], cfg["rms_norm_eps"])


def head_logits(params: dict, h, lower: bool = False):
    rnd = _float8 if lower else _exact
    return jnp.matmul(rnd(h), rnd(params["lm_head"].astype(F32)).T,
                      precision=HI)


def logits(cfg: dict, params: dict, tokens):
    """[T, V] float32 logits of one sequence (padded here to whole query
    blocks; causal attention, convolution and recurrence keep padding from
    every earlier position): what the tier-1 tests hold the served path's
    logits to."""
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
