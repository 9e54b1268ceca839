"""Plain float32 reference of the MiniCPM-SALA decoder (block-sparse attention
layers — the `minicpm4` mixer, InfLLM-V2 — at no period among lightning
linear-attention layers; the family's muP scalars), and the comparison that
decides whether what the server returned agrees with it.

Independent of the code under test: no paging, no pooled-key pool, no block
lists, no chunking, no per-slot state, no kernel, no scheduler, no sampling
epilogue and no layer loop of the program's — one sequence, a Python loop over
the layers, the pooled keys as means over slices of the sequence's own k, the
selection as a dense [T, T / stride] score -> a [T, kv heads, blocks] mask ->
dense masked softmax in blocks of QUERY_BLOCK queries over all keys, and the
recurrence token by token (a `lax.scan` over the T tokens of the line below:
no chunks, no state between calls). Every matmul is float32 at the highest
precision; a layer's weights are cast to float32 as the loop reaches it, and
the head is read in HEAD_CHUNKS blocks of the served rows. With `x` the
residual, `N(x; w) = x rsqrt(mean x^2 + rms_norm_eps) w`, d = head size,
`r = scale_depth / sqrt(scale_depth_layers)`:

    x_0 = E[id] scale_emb
    x = x + r Mixer(N(x; attn_norm));   x = x + r MLP(N(x; mlp_norm))
    MLP(h) = (silu(h Wgate) * (h Wup)) Wdown
    logits = N(x; final_norm) W_head^T / (hidden_size / dim_model_base)

    `lightning-attn` (H = lightning_nh heads of d = lightning_head_dim):
        q, k, v = h Wq, h Wk, h Wv;  q, k = N over each head (learned
        weights), then RoPE (rotate-half over the whole head, theta =
        rope_theta, where lightning_use_rope)
        S_t = lambda S_{t-1} + k_t v_t^T   (S_{-1} = 0, [d, d] a head,
        float32);   o_t = S_t^T q_t d^-1/2
        lambda = exp(-s), s[h] = 2^(-8 (h + 1) / H) (1 - l / (L - 1) + 1e-5),
        l the layer's PUBLISHED index (layer_offset + its index here),
        L = scale_depth_layers
        o = N(o over all H d lanes; ltn_norm);  o = o * sigmoid(h Wz);
        y = o Wo

    `minicpm4` (H heads, Hk kv heads of d = head_dim, NO RoPE):
        q, k, v = h Wq, h Wk, h Wv;  q, k = N over each head
        pooled[j] = mean of k[stride j : stride j + kernel]  a kv head
        a query at position t (context n = t + 1), kv group g:
          n <= sparse_dense_len: every position s <= t
          else p[h, j] = softmax over the j with stride j + kernel <= n of
               q[t, h] . pooled[j] d^-1/2;  P[j] = sum of p[h, j] over h in g;
               B[b] = max of P[4 b - 1 .. 4 b + 3]  (block b = positions
               [block b, block b + block));  B = +inf for b < init_blocks and
               for the window's blocks (c - window / block, c], c = t // block;
               the sparse_topk largest B among b <= c are kept, ties to the
               lower b;  the positions s <= t of kept blocks
        o[t, h] = softmax over those s of q[t, h] . k[s] d^-1/2, times v
        o = o * sigmoid(h Wgate_q);   y = o Wo

Departures from the published modelling code, all in the configuration file's
`assumed`: the seven `sparse_*` sizes are the family's convention (this
model's config.json carries no `sparse_config`); the rule `n <=
sparse_dense_len` is applied a POSITION (the published code switches a whole
call on its length, which a server that feeds a prompt in chunks cannot
reproduce); the decay slopes are Lightning Attention's; the output norm is
over all heads' lanes; both gates are a sigmoid a lane on the mixer's output
before W_o; the program's weights are per projection. The weights are seeded
random, not the checkpoint's. The prompt is byte tokens behind a BOS.

It reads only the configuration FILE's keys and the weights the server serves
by the program's names: `embed`, `lm_head`, `final_norm`, and under `layers`,
stacked on a leading axis over the layers that have them: `attn_norm mlp_norm
w_gate w_up w_down` (all layers), `wq wq_gate wk wv wo q_norm k_norm` (the
`minicpm4` layers), `ltn_wq ltn_wk ltn_wv ltn_wz ltn_wo ltn_q_norm ltn_k_norm
ltn_norm` (the `lightning-attn` layers).

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
file's keys (`served_layout`). A program that lacks this architecture does not
get this far (its ModelConfig has no field for the `sparse_*` / `lightning_*`
keys, and serve.py ends at start); one that serves other shapes under these
names is stopped here: the reason goes to the server's log, the server is
asked to stop and no reference.json is written, which ends the run with an
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
# is set from two readings each (PERF.md section 6, PR 60).
#
# bfloat16 — the configuration's, what the cell runs. Serving minicpm-sala-d16
# on a v5e reads a mean margin of 0.0043 to 0.0053 sd over thirteen runs on
# thirteen seeds (my chip runs, PR 60: 4096 positions each, 88.4 to 89.6 % of
# them the reference's own argmax, top-10 share 100 %) — ten times the dense
# cells' and steady: with seeded weights the block scores are nearly flat, so
# bfloat16 q and pooled keys rank the chosen blocks otherwise than float32
# does. The same forward in float8 (`lower_precision`, 512 positions of one
# request a run) reads 4.19 at the least and 4.29 at the most: 0 % argmax.
# 0.05 lies between: 9 times the largest bfloat16 reading, 84 times under the
# smallest float8 one.
MEAN_MARGIN_SD_MAX = 0.05
# float32 — the tiny-size tests (tests/test_minicpm_sala.py,
# benchmarks/tests/test_minicpm_sala_cell.py): there the program's own
# forward reads 0.0 and the wrong forwards of the tests' ablations read far
# above.
FLOAT32_MARGIN_SD_MAX = 0.003
HEAD_CHUNKS = 8
QUERY_BLOCK = 128
# Rows an MLP block: a [T, 16384] float32 intermediate at 16,896 positions
# is 1.1 GB, three of them more than a chip that serves the model has free.
MLP_BLOCK = 1024
OLLAMA_DEFAULTS = {"repeat_penalty": 1.1, "repeat_last_n": 64}
SPARSE_KEYS = ("sparse_kernel_size", "sparse_kernel_stride",
               "sparse_block_size", "sparse_topk", "sparse_init_blocks",
               "sparse_window_size", "sparse_dense_len")
CONFIG_KEYS = (
    "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
    "head_dim", "hidden_size", "rms_norm_eps", "rope_theta",
    "tie_word_embeddings", "mixer_types", "lightning_nh",
    "lightning_head_dim", "lightning_use_rope", "scale_emb", "scale_depth",
    "scale_depth_layers", "dim_model_base", "layer_offset") + SPARSE_KEYS
SPARSE_MIXER, LIGHTNING_MIXER = "minicpm4", "lightning-attn"
HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


class NotServed(Exception):
    """The weights served do not have the configuration's architecture."""


def served_layout(cfg: dict, params: dict) -> None:
    """Raises NotServed unless every weight the reference reads has the shape
    the configuration file's keys give it."""
    lp = params["layers"]
    n, d = cfg["num_hidden_layers"], cfg["hidden_size"]
    mixers = list(cfg["mixer_types"])
    ns, nl = mixers.count(SPARSE_MIXER), mixers.count(LIGHTNING_MIXER)
    hd = cfg["head_dim"]
    q_dim, kv_dim = cfg["num_attention_heads"] * hd, \
        cfg["num_key_value_heads"] * hd
    ld, lh = cfg["lightning_nh"] * cfg["lightning_head_dim"], \
        cfg["lightning_head_dim"]
    f = lp["w_gate"].shape[-1] if "w_gate" in lp else 0
    want = {
        "attn_norm": (n, d), "mlp_norm": (n, d), "w_gate": (n, d, f),
        "w_up": (n, d, f), "w_down": (n, f, d),
        "wq": (ns, d, q_dim), "wq_gate": (ns, d, q_dim),
        "wk": (ns, d, kv_dim), "wv": (ns, d, kv_dim), "wo": (ns, q_dim, d),
        "q_norm": (ns, hd), "k_norm": (ns, hd),
        "ltn_wq": (nl, d, ld), "ltn_wk": (nl, d, ld), "ltn_wv": (nl, d, ld),
        "ltn_wz": (nl, d, ld), "ltn_wo": (nl, ld, d),
        "ltn_q_norm": (nl, lh), "ltn_k_norm": (nl, lh), "ltn_norm": (nl, ld)}
    if len(mixers) != n or ns + nl != n:
        raise NotServed(f"mixer_types names {len(mixers)} layers of "
                        f"{sorted(set(mixers))}, num_hidden_layers is {n}")
    if cfg.get("intermediate_size") not in (None, f) or "lm_head" not in params:
        raise NotServed(f"w_gate is {f} wide, the configuration's MLP "
                        f"{cfg.get('intermediate_size')}; lm_head "
                        f"{'present' if 'lm_head' in params else 'absent'}")
    bad = [f"{name} is {tuple(lp[name].shape) if name in lp else 'absent'}, "
           f"the configuration's is {shape}"
           for name, shape in want.items()
           if name not in lp or tuple(lp[name].shape) != shape]
    if bad:
        raise NotServed("; ".join(bad))


def cannot_run(reason: str):
    """The program under test lacks the configuration's architecture: end the
    run with an error exit and no result line (see falcon_h1_decoder.py's:
    the reason to the server's log, SIGTERM down its graceful path)."""
    print(f"minicpm_sala_decoder: the program cannot run this configuration: "
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


def level(cfg: dict, layer: int) -> float:
    """1 - l / (L - 1) + 1e-5 of the layer at index `layer` of THIS stack:
    l its published index, L the published depth."""
    depth = cfg.get("scale_depth_layers") or cfg["num_hidden_layers"]
    return 1.0 - (cfg.get("layer_offset", 0) + layer) / max(depth - 1, 1) \
        + 1e-5


def slopes(cfg: dict, layer: int) -> np.ndarray:
    """s[h] of the lightning layer at index `layer` of THIS stack."""
    h = cfg["lightning_nh"]
    base = 2.0 ** (-8.0 * np.arange(1, h + 1, dtype=np.float64) / h)
    return (base * level(cfg, layer)).astype(np.float32)


def _lightning(cfg: dict, mm, rnd, h, w: dict, lvl):
    """`w`: ONE layer's weights; `lvl`: `level` of it (a traced scalar, so
    that the lightning layers share one compiled program)."""
    t = h.shape[0]
    nh, d = cfg["lightning_nh"], cfg["lightning_head_dim"]
    eps = cfg["rms_norm_eps"]
    q, k, v = (mm(h, w[name]).reshape(t, nh, d)
               for name in ("ltn_wq", "ltn_wk", "ltn_wv"))
    q, k = _rms(q, w["ltn_q_norm"], eps), _rms(k, w["ltn_k_norm"], eps)
    if cfg.get("lightning_use_rope", True):
        q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    base = 2.0 ** (-8.0 * jnp.arange(1, nh + 1, dtype=F32) / nh)
    lam = jnp.exp(-base * lvl)[:, None, None]

    def token(s, u):  # the state stays float32 whatever `rnd` rounds
        q_t, k_t, v_t = u
        s = lam * s + k_t[:, :, None] * v_t[:, None, :]
        return s, jnp.einsum("hkd,hk->hd", rnd(s), rnd(q_t), precision=HI)

    o = jax.lax.scan(token, jnp.zeros((nh, d, d), F32), (q, k, v))[1] \
        / math.sqrt(d)
    o = _rms(o.reshape(t, nh * d), w["ltn_norm"], eps)
    return mm(o * jax.nn.sigmoid(mm(h, w["ltn_wz"])), w["ltn_wo"])


def pooled_keys(cfg: dict, k):
    """[J, Hk, hd]: pooled[j] = mean of k[stride j : stride j + kernel], for
    the j whose slice lies inside the sequence k [T, Hk, hd] (None: none)."""
    kernel, stride = cfg["sparse_kernel_size"], cfg["sparse_kernel_stride"]
    n_pooled = (k.shape[0] - kernel) // stride + 1
    if n_pooled < 1:
        return None
    at = stride * jnp.arange(n_pooled)[:, None] + jnp.arange(kernel)[None, :]
    return k[at].mean(axis=1)


def block_mask(cfg: dict, rnd, q, pooled, positions, nb: int):
    """[N, Hk, nb] bool: the blocks the queries q [N, H, hd] at `positions`
    [N] attend, from the sequence's pooled keys [J, Hk, hd]."""
    n, nh, hd = q.shape
    kernel, stride, block, topk, init, window, dense_len = (
        cfg[key] for key in SPARSE_KEYS)
    own = positions // block  # [N]
    b = jnp.arange(nb)
    every = b[None, :] <= own[:, None]  # [N, nb]
    if pooled is None:
        return jnp.broadcast_to(every[:, None, :], (n, cfg[
            "num_key_value_heads"], nb))
    n_pooled, hk = pooled.shape[0], pooled.shape[1]
    s = jnp.einsum("tkgd,jkd->tkgj", rnd(q.reshape(n, hk, nh // hk, hd)),
                   rnd(pooled), precision=HI) / math.sqrt(hd)
    defined = (jnp.arange(n_pooled) * stride + kernel)[None, :] \
        <= (positions + 1)[:, None]  # [N, J]
    some = defined.any(axis=1)[:, None, None, None]
    p = jax.nn.softmax(jnp.where(defined[:, None, None, :] | ~some, s,
                                 -jnp.inf), axis=-1)
    p = jnp.where(defined[:, None, None, :], p, 0.0).sum(axis=2)  # [N,Hk,J]
    per = block // stride
    j_of = per * b[:, None] + jnp.arange(-1, per)[None, :]  # [nb, per + 1]
    inside = (j_of >= 0) & (j_of < n_pooled)
    score = jnp.where(inside[None, None],
                      p[:, :, jnp.clip(j_of, 0, n_pooled - 1)],
                      0.0).max(axis=-1)  # [N, Hk, nb]
    forced = (b[None, :] < init) | (b[None, :] > own[:, None]
                                    - window // block)
    score = jnp.where(forced[:, None, :], jnp.inf, score)
    score = jnp.where(every[:, None, :], score, -jnp.inf)
    order = jnp.argsort(-score, axis=-1, stable=True)  # ties: the lower b
    rank = jnp.argsort(order, axis=-1, stable=True)
    kept = (rank < topk) & every[:, None, :]
    dense = (positions + 1 <= dense_len)[:, None, None]
    return jnp.where(dense, every[:, None, :], kept)


def _sparse_attention(cfg: dict, mm, rnd, h, w: dict):
    nh, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, t, eps = cfg["head_dim"], h.shape[0], cfg["rms_norm_eps"]
    block = cfg["sparse_block_size"]
    nb = -(-t // block)
    q = _rms(mm(h, w["wq"]).reshape(t, nh, hd), w["q_norm"], eps)
    k = _rms(mm(h, w["wk"]).reshape(t, hk, hd), w["k_norm"], eps)
    v = mm(h, w["wv"]).reshape(t, hk, hd)
    pooled = pooled_keys(cfg, k)
    key_block = jnp.arange(t) // block

    def queries(args):  # a block of queries, every key
        qb, pos = args  # [QB, H, hd], [QB]
        mask = block_mask(cfg, rnd, qb, pooled, pos, nb)  # [QB, Hk, nb]
        s = jnp.einsum("tkgd,skd->tkgs",
                       rnd(qb.reshape(-1, hk, nh // hk, hd)), rnd(k),
                       precision=HI) / math.sqrt(hd)
        keep = mask[:, :, key_block] \
            & (pos[:, None] >= jnp.arange(t)[None, :])[:, None, :]
        p = jax.nn.softmax(jnp.where(keep[:, :, None, :], s, -jnp.inf),
                           axis=-1)
        return jnp.einsum("tkgs,skd->tkgd", rnd(p), rnd(v),
                          precision=HI).reshape(-1, nh * hd)

    pad = -t % QUERY_BLOCK  # (padding queries sit at position 0)
    o = jax.lax.map(queries, (
        jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, QUERY_BLOCK, nh,
                                                       hd),
        jnp.pad(jnp.arange(t), (0, pad)).reshape(-1, QUERY_BLOCK)))
    o = o.reshape(-1, nh * hd)[:t] * jax.nn.sigmoid(mm(h, w["wq_gate"]))
    return mm(o, w["wo"])


def _in_blocks(fn, x, rows: int):
    """fn over x [T, ...] a block of `rows` rows at a time (the last padded
    with zeros, its results dropped): the same numbers as fn(x) for a fn
    that treats rows alone, a block's intermediates at a time."""
    t = x.shape[0]
    if t <= rows:
        return fn(x)
    pad = -t % rows
    x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
    y = jax.lax.map(fn, x.reshape(-1, rows, *x.shape[1:]))
    return y.reshape(-1, *y.shape[2:])[:t]


EVERY = ("attn_norm", "mlp_norm", "w_gate", "w_up", "w_down")
OF_MIXER = {
    SPARSE_MIXER: ("wq", "wq_gate", "wk", "wv", "wo", "q_norm", "k_norm"),
    LIGHTNING_MIXER: ("ltn_wq", "ltn_wk", "ltn_wv", "ltn_wz", "ltn_wo",
                      "ltn_q_norm", "ltn_k_norm", "ltn_norm")}


def _items(cfg: dict) -> tuple:
    """The keys the forward reads, hashable: a jit's static argument."""
    return tuple(sorted(
        (k, tuple(cfg[k]) if isinstance(cfg[k], list) else cfg[k])
        for k in CONFIG_KEYS if k in cfg))


@functools.partial(jax.jit, static_argnames=("mixer", "cfg_items", "low"))
def _layer(w: dict, x, lvl, mixer: str, cfg_items: tuple, low: bool = False):
    """One layer over every position x [T, D], from ITS weights `w`: ONE
    compiled program a KIND of layer (and precision), not a layer — sixteen
    layers traced into one program took 70 s to compile and held 3 GB of
    temporaries, 5.4 GB with the float8 forward beside it, more than the chip
    has free after the window (AOT for a v5e, PR 60)."""
    cfg, rnd = dict(cfg_items), _float8 if low else _exact

    def mm(a, b):
        return jnp.matmul(rnd(a), rnd(b.astype(F32)), precision=HI)

    eps = cfg["rms_norm_eps"]
    depth = cfg.get("scale_depth_layers") or cfg["num_hidden_layers"]
    r = cfg["scale_depth"] / math.sqrt(depth) if cfg.get("scale_depth") \
        else 1.0
    h = _rms(x, w["attn_norm"], eps)
    if mixer == SPARSE_MIXER:
        y = _sparse_attention(cfg, mm, rnd, h, w)
    else:
        y = _lightning(cfg, mm, rnd, h, w, lvl)
    x = x + r * y

    def mlp(rows):
        h = _rms(rows, w["mlp_norm"], eps)
        return mm(jax.nn.silu(mm(h, w["w_gate"])) * mm(h, w["w_up"]),
                  w["w_down"])

    return x + r * _in_blocks(mlp, x, MLP_BLOCK)


def hidden(cfg: dict, params: dict, tokens, rnd=_exact):
    """Final-norm hidden states [T, D] of one sequence `tokens` [T]: a Python
    loop over every layer, every position (`rnd`: `_exact` or `_float8`)."""
    lp, items = params["layers"], _items(cfg)
    x = params["embed"][tokens].astype(F32) * cfg.get("scale_emb", 1.0)
    seen = {SPARSE_MIXER: 0, LIGHTNING_MIXER: 0}
    for layer, mixer in enumerate(cfg["mixer_types"]):
        w = {name: lp[name][layer] for name in EVERY}
        w.update({name: lp[name][seen[mixer]] for name in OF_MIXER[mixer]})
        seen[mixer] += 1
        x = _layer(w, x, np.float32(level(cfg, layer)), mixer=mixer,
                   cfg_items=items, low=rnd is _float8)
    return _rms(x, params["final_norm"], cfg["rms_norm_eps"])


def head_logits(cfg: dict, params: dict, h, rnd=_exact):
    """h [N, D] -> logits [N, V], the head a block of vocabulary rows at a
    time, each cast from the served rows as it is read."""
    head = params["embed"] if cfg.get("tie_word_embeddings") \
        else params["lm_head"]
    v = head.shape[0]
    step = -(-v // HEAD_CHUNKS)
    divisor = cfg["hidden_size"] / cfg["dim_model_base"] \
        if cfg.get("dim_model_base") else 1.0
    return jnp.concatenate([
        jnp.matmul(rnd(h), rnd(head[i:i + step].astype(F32)).T, precision=HI)
        for i in range(0, v, step)], axis=-1) / divisor


def logits(cfg: dict, params: dict, tokens):
    """[T, V] float32 logits of one sequence: what the tier-1 tests hold the
    served path's logits to."""
    return head_logits(cfg, params, hidden(cfg, params, tokens))


@functools.partial(jax.jit, static_argnames=("cfg_items", "last_n", "lower"))
def _score(params, hid, low_hid, tokens, at, penalty, cfg_items, last_n,
           lower=False):
    """(margin in sd, ids the reference ranks above the chosen one) at the
    positions `at` from their final hiddens `hid` (`low_hid`: the float8
    forward's, whose own choice is then the id held to the reference)."""
    cfg = dict(cfg_items)
    logit = head_logits(cfg, params, hid)
    sd = jnp.maximum(logit.std(axis=-1, keepdims=True), 1e-30)
    # the last_n context tokens before each output, penalised
    back = at[:, None] - jnp.arange(last_n)[None, :]
    seen = jnp.zeros(logit.shape, bool).at[
        jnp.arange(at.shape[0])[:, None], tokens[jnp.clip(back, 0)]
    ].max(back >= 0)

    def penalised(lg):
        return jnp.where(seen, jnp.where(lg > 0, lg / penalty, lg * penalty),
                         lg)

    logit = penalised(logit)
    if lower:
        low = head_logits(cfg, params, low_hid, _float8)
        chosen = jnp.argmax(penalised(low), axis=-1)
    else:
        chosen = tokens[jnp.clip(at + 1, 0, tokens.shape[0] - 1)]
    got = jnp.take_along_axis(logit, chosen[:, None], axis=-1)
    margin = (logit.max(axis=-1, keepdims=True) - got) / sd
    return margin[:, 0], (logit > got).sum(axis=-1)


def _margins(cfg, params, tokens, n_prompt, penalty, max_out, last_n,
             lower=False):
    """tokens [T] = prompt then returned ids (then padding, which causal
    attention and the recurrence keep from every earlier position). For
    output j < max_out: (margin in sd, ids the reference ranks above the
    returned one). With `lower` the id held to the reference is not the
    returned one but the float8 forward's own choice at that position."""
    at = jnp.clip(n_prompt - 1 + jnp.arange(max_out), 0, tokens.shape[0] - 1)
    hid = hidden(cfg, params, tokens)[at]
    low_hid = hidden(cfg, params, tokens, _float8)[at] if lower else hid
    head = {k: params[k] for k in ("embed", "lm_head") if k in params}
    return _score(head, hid, low_hid, tokens, at, penalty,
                  cfg_items=_items(cfg), last_n=last_n, lower=lower)


def check(cfg: dict, params: dict, requests: list, pad_to: int,
          max_out: int) -> dict:
    """`requests`: [{"prompt": text, "ids": returned ids, "options": the
    request's Ollama options}]. The prompt is byte tokens behind a BOS (id 1,
    byte b -> b + 3), as the configuration serves it."""
    try:
        served_layout(cfg, params)
    except NotServed as e:
        cannot_run(str(e))
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
        args = (cfg, params, jnp.asarray(tokens), np.int32(len(prompt)),
                np.float32(opts["repeat_penalty"] or 1.0))
        kw = dict(max_out=max_out, last_n=int(opts["repeat_last_n"]))
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
