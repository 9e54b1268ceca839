"""Plain float32 reference of the Kimi-Linear decoder family
(Kimi-Linear-48B-A3B-Instruct, `model_type` kimi_linear; arXiv:2510.26692:
Kimi Delta Attention — a delta rule whose decay is a VECTOR a head, one
number a key channel — in three of four layers beside multi-head LATENT
attention with NO position embedding, a dense first layer and then routed
experts behind one shared expert — of whose routed experts the configuration
HOLDS a share), and the comparison that decides whether what the server
returned agrees with it.

Independent of the code under test: no paging, no latent pool, no absorption,
no chunks, no per-slot state, no kernel, no sort of rows by expert, no
scheduler, no sampling epilogue and no layer loop of the program's — one
sequence, a Python loop over the file's `layer_types`, latent attention as a
dense causal softmax over keys and values EXPANDED a head (k_h = [c W_uk,h |
k_pe], v_h = c W_uv,h: the published module's form, never the absorbed one),
the convolution as the sum over `short_conv_kernel_size` shifted copies
(zeros shifted in: no window), the rule as its token-serial recurrence (a
`lax.scan` over the T tokens of the four lines below: no chunks, no state
between calls), EVERY held expert computed for EVERY token and weighted by
its gate (zero where not chosen), the shared expert once. Every matmul is
float32 at the highest precision. It is computed in blocks — queries of the
[T, T] scores, one expert at a time — so that 16,896 positions fit beside the
served weights; the blocks change no number. Layer i, with `x` the residual,
kind = layer_types[i], N(x; w) = x rsqrt(mean x^2 + eps) w (plain RMSNorm,
eps rms_norm_eps) and h = N(x; attn_norm):

    kind linear_attention — Kimi Delta Attention (H = linear_attn_config.
    num_heads heads of d = linear_attn_config.head_dim, keys and values; K =
    short_conv_kernel_size taps):
        [q | k | v] = h W_in  (H d each: the published q_proj, k_proj,
        v_proj side by side); c_t = silu(sum_j w[:, j] * u_{t-(K-1)+j}) over
        u = [q | k | v] (depthwise, causal, no bias; u = 0 before position 0);
        per head q_t = c^q_t / sqrt(|c^q_t|^2 + 1e-6) / sqrt(d),
        k_t = c^k_t / sqrt(|c^k_t|^2 + 1e-6), v_t = c^v_t;
        g_t = -exp(A_log[h]) softplus((h W_fa) W_fb + dt_bias)  in R^{H x d},
        beta_t = sigmoid(h W_b) in R^H, both float32;
        S' = Diag(exp(g_t)) S_{t-1}  (ROWS of S: a decay a key channel);
        r_t = beta_t (v_t - S'^T k_t);  S_t = S' + k_t r_t^T;
        o_t = S_t^T q_t      (S_{-1} = 0, [d, d] float32 a head)
        mix = (RMSNorm_d(o_t) * lin_norm * sigmoid((h W_ga) W_gb))_{heads}
              W_out
    kind full_attention — latent attention, NoPE (H = num_attention_heads,
    c = kv_lora_rank, dn = qk_nope_head_dim, dr = qk_rope_head_dim, dv =
    v_head_dim; `q_lora_rank` null: a full-rank q):
        [q_nope,h | q_pe,h] = h W_q  (H (dn + dr));
        [c_kv | k_pe] = h W_dkv  (c + dr);  cn = N(c_kv; kv_norm);
        [k_nope,h | v_h] = cn W_ukv  (H (dn + dv));
        k_h = [k_nope,h | k_pe]  (k_pe the same for every head; neither it
        nor q_pe is rotated: `mla_use_nope`);
        a = causal softmax(q_h . k_h / sqrt(dn + dr)) v_h;   mix = a W_o
    x = x + mix;   h = N(x; mlp_norm)
    layer i < num_dense_layers:  x = x + SwiGLU(h)  (intermediate_size wide)
    else: s = sigmoid(h W_r) in float32 over ALL router_experts; the top
        num_experts_per_token of s + router_bias chosen (the bias selects, it
        weighs nothing; one group: no group limit); g_e = s_e / (sum of the
        chosen s) * routed_scaling_factor;
        x = x + SwiGLU_shared(h) + sum_{e chosen AND held} g_e SwiGLU_e(h)
        held: experts expert_offset .. expert_offset + num_experts - 1.
    logits = N(x; final_norm) W_head^T   (the head's rows are the served slice)

The gates are normalised over all the chosen experts, held or not; what the
absent experts would have added is left out — here as in the program — and
that partial result goes on to the next layer (model-configs guide, section
4). Departures from the published model, all in the configuration file's
`assumed`: the three projections and the three depthwise convolutions of a
KDA layer are held side by side as one (`lin_in`, `lin_conv_w`: the same
numbers); the published `head_dim` key (72 = hidden_size / heads) is read by
no published module — a latent head's q and k are dn + dr = 192 wide;
`rope_theta` is published and unused (NoPE). The weights are seeded random,
the selection bias drawn NON-zero. The prompt is byte tokens behind a BOS,
not the model's tokenizer.

It reads only the configuration FILE's keys and the weights the server serves
by the program's names: `embed`, `lm_head`, `final_norm`, and under `layers`,
each stacked on a leading axis over the layers that HAVE it: `attn_norm
mlp_norm` (every layer), `w_gate w_up w_down` (the dense layers), `w_router
router_bias ws_gate ws_up ws_down` and `we_gate we_up we_down` [., E held, in,
out] (the expert layers), `wq mla_wdkv mla_kv_norm mla_wukv wo` (attention
layers), `lin_in lin_conv_w kda_fa kda_fb lin_A_log lin_dt_bias kda_b kda_ga
kda_gb lin_norm lin_out` (KDA layers).

What it costs (reckoned before the chip run, PR 63): at the cell's longest
request (16,896 positions) the 64 held experts over every token are 64 x
16,896 x 7 layers x 6 x 2304 x 1024 = 1.07e14 FLOP, the dense layer, mixers,
attention (2 layers x 32 heads x 16,896^2 / 2 x 640) and head ~0.35e14: ~1.4e14
a request, 1.1e15 for the harness's eight — at the ~19 TFLOP/s a float32
matmul at the highest precision reaches on a v5e, ~60 s, plus 8 x 6 x 16,896
token-serial steps of the rule. The harness allows 240 s.

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
(the one before PR 63 does not get this far: its ModelConfig has no field for
`linear_attn_config`, and serve.py ends at start).
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
# is set from two readings each (PERF.md section 6, PR 63).
#
# bfloat16 — the configuration's, what the cell runs. Serving
# kimi-linear-48b-a3b-ep4-d8 on a v5e reads a mean margin of 0.0288 to 0.0337
# sd over seven runs on seven seeds (my chip runs, PR 63: 4096 positions each
# — eight requests of 8-16 k tokens, 512 outputs — 77.7-79.3 % of them the
# reference's own argmax, 99.0-99.5 % in its top 10, the worst single position
# 1.02-1.65). The same forward with float8 operands (`lower_precision`, 128
# positions at 4096 tokens of context a run) reads 0.782 at the least and
# 0.949 at the most: 6-16 % argmax. 0.1 lies between, 3.0 times the largest
# bfloat16 reading (fresh seeds read higher: the more room is above) and an
# eighth of the smallest float8 one.
MEAN_MARGIN_SD_MAX = 0.1
# float32 — the tiny-size tests (tests/test_kimi_linear.py): there the
# program's own forward, in chunks over carried state and in decode scans,
# agrees with this reference to 2e-4 in every logit (margin 0.0), and a
# forward with a bfloat16 rule state, a scalar decay (the mean over the
# channels), no selection bias, a rotated k_pe or no output gate misses by
# far more (asserted there).
FLOAT32_MARGIN_SD_MAX = 0.003
OLLAMA_DEFAULTS = {"repeat_penalty": 1.1, "repeat_last_n": 64}
CONFIG_KEYS = (
    "num_attention_heads", "hidden_size", "rms_norm_eps", "layer_types",
    "linear_attn_config", "kv_lora_rank", "qk_nope_head_dim",
    "qk_rope_head_dim", "v_head_dim", "num_dense_layers", "intermediate_size",
    "num_experts", "router_experts", "expert_offset", "num_experts_per_token",
    "moe_renormalize", "routed_scaling_factor", "moe_intermediate_size",
    "num_shared_experts", "vocab_size")
ATTENTION, LINEAR = "full_attention", "linear_attention"
L2_EPS = 1e-6
# Blocks (they change no number): queries a block of the [T, T] scores.
QUERY_BLOCK = 128
LOWER_TOKENS, LOWER_POSITIONS = 4096, 128
HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


class NotServed(Exception):
    """The weights served do not have the configuration's architecture."""


def _kda_sizes(cfg: dict) -> tuple:
    """(heads, head size, taps) of a KDA layer: `linear_attn_config`'s."""
    group = dict(cfg["linear_attn_config"])
    return (group["num_heads"], group["head_dim"],
            group["short_conv_kernel_size"])


def _counts(cfg: dict) -> dict:
    kinds = list(cfg["layer_types"])
    dense = int(cfg.get("num_dense_layers", 0))
    return {"all": len(kinds), "attn": kinds.count(ATTENTION),
            "lin": kinds.count(LINEAR), "dense": dense,
            "experts": len(kinds) - dense}


def served_layout(cfg: dict, params: dict) -> None:
    """Raises NotServed unless every weight the reference reads has the shape
    the configuration file's keys give it."""
    lp = params["layers"]
    n = _counts(cfg)
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    H, c = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    h, hd, taps = _kda_sizes(cfg)
    e, R = cfg["num_experts"], cfg.get("router_experts") or cfg["num_experts"]
    fe = cfg["moe_intermediate_size"]
    fs = fe * cfg["num_shared_experts"]
    want = {
        "attn_norm": ("all", (d,)), "mlp_norm": ("all", (d,)),
        "w_gate": ("dense", (d, f)), "w_up": ("dense", (d, f)),
        "w_down": ("dense", (f, d)),
        "w_router": ("experts", (d, R)), "router_bias": ("experts", (R,)),
        "ws_gate": ("experts", (d, fs)), "ws_up": ("experts", (d, fs)),
        "ws_down": ("experts", (fs, d)),
        "we_gate": ("experts", (e, d, fe)), "we_up": ("experts", (e, d, fe)),
        "we_down": ("experts", (e, fe, d)),
        "wq": ("attn", (d, H * (dn + dr))), "mla_wdkv": ("attn", (d, c + dr)),
        "mla_kv_norm": ("attn", (c,)),
        "mla_wukv": ("attn", (c, H * (dn + dv))), "wo": ("attn", (H * dv, d)),
        "lin_in": ("lin", (d, 3 * h * hd)),
        "lin_conv_w": ("lin", (3 * h * hd, taps)),
        "kda_fa": ("lin", (d, hd)), "kda_fb": ("lin", (hd, h * hd)),
        "lin_A_log": ("lin", (h,)), "lin_dt_bias": ("lin", (h * hd,)),
        "kda_b": ("lin", (d, h)), "kda_ga": ("lin", (d, hd)),
        "kda_gb": ("lin", (hd, h * hd)), "lin_norm": ("lin", (hd,)),
        "lin_out": ("lin", (h * hd, d))}
    if not cfg.get("mla_use_nope") or cfg.get("q_lora_rank") \
            or cfg.get("moe_router_activation_func") != "sigmoid" \
            or set(cfg["layer_types"]) - {ATTENTION, LINEAR}:
        raise NotServed("this reference is the family's: mla_use_nope true, "
                        "q_lora_rank null, a sigmoid router, layers of "
                        "full_attention and linear_attention")
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
    print(f"kimi_linear_decoder: the program cannot run this configuration: "
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
    return _rms(x, eps) * w.astype(F32)


def _latent_attention(cfg: dict, mm, rnd, h, lp: dict, a: int):
    """Multi-head latent attention in its EXPANDED form, no rotation."""
    t = h.shape[0]
    H, c = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    q = rnd(mm(h, lp["wq"][a]).reshape(t, H, dn + dr))
    kv = mm(h, lp["mla_wdkv"][a])
    cn = _norm(kv[:, :c], lp["mla_kv_norm"][a], cfg["rms_norm_eps"])
    kv_h = mm(cn, lp["mla_wukv"][a]).reshape(t, H, dn + dv)
    k = rnd(jnp.concatenate(
        [kv_h[..., :dn], jnp.broadcast_to(kv[:, None, c:], (t, H, dr))],
        axis=-1))
    v = rnd(kv_h[..., dn:])
    pos = jnp.arange(t)

    def block(q0):  # QUERY_BLOCK queries against every position
        qb = jax.lax.dynamic_slice_in_dim(q, q0, QUERY_BLOCK)
        s = jnp.einsum("qhd,shd->hqs", qb, k, precision=HI) \
            / math.sqrt(dn + dr)
        causal = pos[None, :] <= (q0 + jnp.arange(QUERY_BLOCK))[:, None]
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqs,shd->qhd", rnd(p), v, precision=HI)

    o = jax.lax.map(block, jnp.arange(0, t, QUERY_BLOCK)).reshape(t, H * dv)
    return mm(o, lp["wo"][a])


def _delta_rule(q, k, v, g, beta, rnd):
    """The token-serial recurrence with a decay a key channel. q, k [T, H,
    d] (normalised), v [T, H, d], g [T, H, d], beta [T, H] -> o [T, H, d].
    The state stays float32 whatever `rnd` rounds: it is an accumulator, not
    a matmul operand."""
    def token(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = s * jnp.exp(g_t)[:, :, None]  # rows of S
        c = b_t[:, None] * (v_t - jnp.einsum(
            "hkv,hk->hv", rnd(s), rnd(k_t), precision=HI))
        s = s + k_t[:, :, None] * c[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", rnd(s), rnd(q_t), precision=HI)

    s0 = jnp.zeros((v.shape[1], q.shape[2], v.shape[2]), F32)
    return jax.lax.scan(token, s0, (q, k, v, g, beta))[1]


def _kda(cfg: dict, mm, rnd, h, lp: dict, c: int):
    t = h.shape[0]
    H, d, taps = _kda_sizes(cfg)
    hd = H * d
    w_in = lp["lin_in"][c]
    w = lp["lin_conv_w"][c].astype(F32)  # [channels, K]; w[:, K-1] meets u_t

    def mixed(lo, hi):
        """Channels lo..hi of silu(conv([q | k | v])): the convolution is
        depthwise, so a part's channels need that part's projection only."""
        u = mm(h, w_in[:, lo:hi])
        return jax.nn.silu(sum(
            w[lo:hi, j] * jnp.pad(u, ((taps - 1 - j, 0), (0, 0)))[:t]
            for j in range(taps)))

    def low_rank(first, second):
        return mm(mm(h, lp[first][c]), lp[second][c])

    # the gates are float32 whatever the rest runs in, as the model states
    def exact(x, m):
        return jnp.matmul(x, m.astype(F32), precision=HI)

    a = exact(exact(h, lp["kda_fa"][c]), lp["kda_fb"][c]).reshape(t, H, d)
    g = -jnp.exp(lp["lin_A_log"][c].astype(F32))[:, None] * jax.nn.softplus(
        a + lp["lin_dt_bias"][c].astype(F32).reshape(H, d))
    beta = jax.nn.sigmoid(exact(h, lp["kda_b"][c]))
    q, k, v = (mixed(i * hd, (i + 1) * hd).reshape(t, H, d) for i in range(3))
    q = q / jnp.sqrt(jnp.sum(q * q, axis=-1, keepdims=True) + L2_EPS) \
        / math.sqrt(d)
    k = k / jnp.sqrt(jnp.sum(k * k, axis=-1, keepdims=True) + L2_EPS)
    o = _delta_rule(q, k, v, g, beta, rnd)
    o = _rms(o, cfg["rms_norm_eps"]) * lp["lin_norm"][c].astype(F32)
    gate = jax.nn.sigmoid(low_rank("kda_ga", "kda_gb"))
    return mm(o.reshape(t, hd) * gate, lp["lin_out"][c])


def _swiglu(mm, h, gate, up, down):
    return mm(jax.nn.silu(mm(h, gate)) * mm(h, up), down)


def gates(cfg: dict, h, lp: dict, e: int):
    """[T, router_experts] float32: the gate of every expert of the router,
    zero where not chosen. `e`: the layer's index among the expert layers."""
    s = jax.nn.sigmoid(jnp.matmul(h, lp["w_router"][e].astype(F32),
                                  precision=HI))
    _, idx = jax.lax.top_k(s + lp["router_bias"][e].astype(F32),
                           cfg["num_experts_per_token"])
    top = jnp.take_along_axis(s, idx, axis=-1)
    if cfg.get("moe_renormalize"):
        top = top / top.sum(axis=-1, keepdims=True)
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


@functools.partial(jax.jit, static_argnames=("cfg_items", "kind", "dense",
                                             "lower"))
def _layer(params, x, i, of_kind, of_ffn, cfg_items, kind: str, dense: bool,
           lower: bool):
    """x' [T, D] of layer i, the `of_kind`-th of its kind and the `of_ffn`-th
    of its FFN's kind (traced: ONE program a (kind, FFN) of layer; the
    blocks inside are loops, so it compiles small and its temporaries are
    freed before the next layer)."""
    cfg = {k: dict(v) if k == "linear_attn_config" else v
           for k, v in cfg_items}
    rnd = _float8 if lower else _exact

    def mm(a, w):
        return jnp.matmul(rnd(a), rnd(w.astype(F32)), precision=HI)

    lp, eps = params["layers"], cfg["rms_norm_eps"]
    h = _norm(x, lp["attn_norm"][i], eps)
    if kind == ATTENTION:
        x = x + _latent_attention(cfg, mm, rnd, h, lp, of_kind)
    else:
        x = x + _kda(cfg, mm, rnd, h, lp, of_kind)
    h = _norm(x, lp["mlp_norm"][i], eps)
    if dense:
        return x + _swiglu(mm, h, lp["w_gate"][of_ffn], lp["w_up"][of_ffn],
                           lp["w_down"][of_ffn])
    return x + _experts(cfg, mm, h, lp, of_ffn)


def _hashable(value):
    if isinstance(value, dict):
        return tuple(sorted((k, _hashable(v)) for k, v in value.items()))
    if isinstance(value, (list, tuple)):
        return tuple(_hashable(v) for v in value)
    return value


def _cfg_items(cfg: dict) -> tuple:
    return tuple(sorted((k, _hashable(cfg[k])) for k in CONFIG_KEYS
                        if k in cfg))


def hidden(cfg: dict, params: dict, tokens, lower: bool = False):
    """Final-norm hidden states [T, D] of one sequence `tokens` [T] (T a
    multiple of QUERY_BLOCK)."""
    items = _cfg_items(cfg)
    x = params["embed"][tokens].astype(F32)
    seen = {ATTENTION: 0, LINEAR: 0}
    n_dense = int(cfg.get("num_dense_layers", 0))
    for i, kind in enumerate(cfg["layer_types"]):
        if kind not in seen:
            raise ValueError(f"layer_types[{i}] = {kind!r}")
        dense = i < n_dense
        x = _layer(params, x, np.int32(i), np.int32(seen[kind]),
                   np.int32(i if dense else i - n_dense), items, kind, dense,
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
