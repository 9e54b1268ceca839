"""Gated delta rule (Gated DeltaNet; Olmo-Hybrid's and Qwen3-Next's
`linear_attention` layers): the recurrence, in the two forms a served step
needs, and the three schedules that say which tokens continue which state.

Per head, with a float32 state S [dk, dv] a sequence (S = 0 before its
first token), decay a_t = exp(g_t) in (0, 1], write strength b_t, and q, k
L2-normalised per head (q also scaled by dk^-1/2). A head is a VALUE head:
where the model has fewer key heads (Qwen3-Next: 16 under 32), key head j
serves value heads j * r .. j * r + r - 1 (`a_value_head` repeats q and k
after `normalise`; with as many key heads as value heads nothing is traced):

    S' = a_t S_{t-1};  r_t = b_t (v_t - S'^T k_t);  S_t = S' + k_t r_t^T;
    o_t = S_t^T q_t

ONE definition of it (`step`: one token a row), and its chunked form for
spans (`_prepare` + `_apply`: the WY / UT transform over CHUNK tokens — the
chunk's tokens are solved against each other once, (I + A)^-1 by forward
substitution, so the state is read once and written once a chunk instead
of once a token). tests/test_olmo_hybrid.py holds both to the token-serial
scan of the four lines above. Everything here is float32 at the highest
matmul precision: the state is an accumulator over the whole sequence.

Schedules (as ops/shortconv.py's):

  - `chunked`: whole sequences [B, T] from position 0 and an empty state
    (forward_prefill, forward_embed): a scan over the chunks.
  - `ragged`: the flattened stream of a ragged step, rows continuing their
    OWN state. Rows with ONE token (decode rows) go through `step`, batched
    over the rows. Rows with longer spans go through the chunked form on
    the stream where it lies: the stream is cut into WINDOWS of CHUNK
    tokens, a window's tokens are solved against each other under a
    same-row mask (`_prepare`, all windows at once: no gather, no padded
    [rows, T] layout), and a loop whose trip count is the number of (row,
    window) pairs the step's spans really touch carries each row's state
    through its windows (`_apply`): a 200-token span costs 4 or 5 trips
    whatever else is in the stream, a decode row costs none. On the chip
    the pairs are one Pallas kernel a layer that keeps a row's state in
    VMEM across its windows (ops/pallas/chunk_rule.py, at every shape its
    `blocks` takes); the loop is its definition and the CPU's path. The
    solve in front of it is one kernel a layer too, over the windows a span
    touches alone (ops/pallas/chunk_solve.py, at every shape ITS `blocks`
    takes: not `plain`, which has no solve); `_prepare` is its definition,
    the CPU's path and `chunked`'s.
  - `decode`: one token a slot (the fused scan's body): active slots
    advance, parked slots keep their state.

`plain` (a trace-time switch on `step`, the chunked form and the three
schedules; False traces exactly what it traced before the switch existed) is
the same recurrence WITHOUT the correction and the norm: S_t = a_t S_{t-1} +
k_t (b_t v_t)^T, o_t = S_t^T q_t, q and k as given. That is Mamba-2's SSD
(ops/ssd.py: k = B, q = C, v = dt x, b = 1), which shares what says which
tokens continue which state — the windows, the (row, window) loop, the
active mask, the reset — and drops the chunk's triangular solve (T = I).

A VECTOR decay (Kimi Delta Attention: `g` of rank one more than `beta`, [...,
H, dk] — a trace-time reading as `plain` is; with a scalar gate every
function traces exactly what it traced before the reading existed) decays
every key
channel on its own: S' = Diag(a_t) S_{t-1} scales ROWS of S. In the chunked
form the decay between two tokens then sits INSIDE the contraction over the
key channels, A_ij = b_i sum_c k_i[c] k_j[c] exp(G_i[c] - G_j[c]), and the
factored form (k_i e^G_i) . (k_j e^-G_j) overflows at strong decay. `_prepare`
forms no exponent above 0: the window is cut into the _SUB-token blocks
`_tri_inv` uses; a block's tokens against an EARLIER block's are one
contraction of two scaled operands, exp(G_i - R) and exp(R - G_j) with R the
row's G before the block's first token (a row is a stretch of the stream, so
the only row with tokens on both sides holds that token: G_i <= R <= G_j);
inside a block G_i - G_j is formed directly, [_SUB, _SUB, dk] a head.
Exponents are masked (-inf), never products.

State layout: [linear layers, slots + 1, dk, H * dv] float32 — the key
dimension on sublanes, every head's values side by side on lanes. With
dv = 192 a [.., H, dk, dv] array would be padded to 256 lanes in HBM (a
third more bytes to hold and to stream); H * dv = 5760 is a multiple of
128. Row `slots` is the trash row padding rows write. On the chip the one-
token rows run in a Pallas kernel that updates the rows in place
(ops/pallas/gated_delta_step.py); `step` is its definition and the CPU's
path.
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

CHUNK = 64
_SUB = 16  # forward substitution inside blocks of 16, then block merges
_HI = jax.lax.Precision.HIGHEST
_F32 = jnp.float32
NORM_EPS = 1e-6  # inside the root of q's and k's L2 norm


def alloc_state(num_layers: int, max_slots: int, heads: int, key_dim: int,
                value_dim: int):
    """The per-slot rule state of a model's linear-attention layers
    (zeros, float32), or None for a model that has none."""
    if not num_layers:
        return None
    return jnp.zeros((num_layers, max_slots + 1, key_dim, heads * value_dim),
                     _F32)


def gates(a, b, a_log, dt_bias, allow_neg: bool):
    """(g, beta) [..., H] float32 from the two gate projections: g =
    -exp(A_log) softplus(a + dt_bias) (the log of the decay), beta =
    sigmoid(b), doubled where the configuration allows negative
    eigenvalues of I - beta k k^T."""
    g = -jnp.exp(a_log.astype(_F32)) * jax.nn.softplus(
        a.astype(_F32) + dt_bias.astype(_F32))
    beta = jax.nn.sigmoid(b.astype(_F32))
    return g, 2.0 * beta if allow_neg else beta


def normalise(q, k):
    """Per head: q / |q| * dk^-1/2 and k / |k|, float32."""
    q, k = q.astype(_F32), k.astype(_F32)

    def unit(x):
        return x * jax.lax.rsqrt(
            jnp.sum(x * x, axis=-1, keepdims=True) + NORM_EPS)

    return unit(q) * q.shape[-1] ** -0.5, unit(k)


def a_value_head(q, k, heads: int):
    """q, k [..., Hk, dk] (normalised) a VALUE head: where the `heads` value
    heads are more than the key heads, each key head's q and k repeated for
    the value heads it serves (j * r .. j * r + r - 1); else as they are."""
    if heads != q.shape[-2]:
        q, k = (jnp.repeat(x, heads // x.shape[-2], axis=-2) for x in (q, k))
    return q, k


def _heads(state, n_heads: int):
    """[..., dk, H * dv] -> [..., dk, H, dv]."""
    return state.reshape(*state.shape[:-1], n_heads, -1)


def _operands(q, k, heads: int, plain: bool):
    """q, k a value head, float32: L2-normalised — or, `plain`, as given."""
    q, k = (q.astype(_F32), k.astype(_F32)) if plain else normalise(q, k)
    return a_value_head(q, k, heads)


def a_channel(g, beta) -> bool:
    """Is `g` a decay a KEY CHANNEL — of rank one more than `beta`, [..., H,
    dk] — and not one a head? A trace-time reading, as `plain` is."""
    return g.ndim > beta.ndim


def step(state, q, k, v, g, beta, reset=None, plain: bool = False):
    """One token a row. state [..., dk, H * dv]; q, k [..., Hk, dk] as the
    convolution left them (Hk key heads, H a multiple of it); v [..., H,
    dv]; g, beta [..., H] (g [..., H, dk]: a decay a key channel); reset
    [...]: the row's state opens at zero.
    Returns (o [..., H, dv] float32, the state after the token)."""
    q, k = _operands(q, k, v.shape[-2], plain)
    s = _heads(state, q.shape[-2])
    if reset is not None:
        s = jnp.where(reset[..., None, None, None], 0.0, s)
    kt, qt = jnp.swapaxes(k, -1, -2), jnp.swapaxes(q, -1, -2)  # [..., dk, H]
    if a_channel(g, beta):  # rows of S
        s = s * jnp.swapaxes(jnp.exp(g), -1, -2)[..., None]
    else:
        s = s * jnp.exp(g)[..., None, :, None]
    if plain:
        r = beta[..., None] * v.astype(_F32)
    else:
        r = beta[..., None] * (v.astype(_F32)
                               - jnp.sum(s * kt[..., None], axis=-3))
    s = s + kt[..., None] * r[..., None, :, :]
    return jnp.sum(s * qt[..., None], axis=-3), s.reshape(state.shape)


# -- the chunked form --------------------------------------------------------
def _mm(spec: str, a, b):
    return jnp.einsum(spec, a, b, precision=_HI,
                      preferred_element_type=_F32)


def _tri_inv(a):
    """(I + a)^-1 for strictly lower-triangular a [..., C, C]: forward
    substitution inside the diagonal blocks of _SUB (every block of every
    chunk at once, _SUB - 1 small steps), then pairs of blocks merge,
    [[P, 0], [R, Q]]^-1 = [[P', 0], [-Q' R P', Q']], until one is left.
    Exact arithmetic, and as stable as substitution is: no power of `a`
    is formed (with b = 2 and repeated keys those grow like 2^n C(n, k))."""
    lead, c = a.shape[:-2], a.shape[-1]

    def blocks(size, row_off):  # block (2i + row_off, 2i) of the grid
        n = c // size
        a4 = a.reshape(*lead, n, size, n, size)
        step_ = 1 if row_off == 0 else 2
        return jnp.stack([a4[..., i + row_off, :, i, :]
                          for i in range(0, n, step_)], axis=-3)

    d = blocks(_SUB, 0)  # [..., C / _SUB, _SUB, _SUB]
    eye = jnp.eye(_SUB, dtype=_F32)
    rows = [jnp.broadcast_to(eye[0], d.shape[:-2] + (_SUB,))]
    for i in range(1, _SUB):  # x_i = e_i - sum_{m<i} d[i, m] x_m
        prev = jnp.stack(rows, axis=-2)
        rows.append(eye[i] - jnp.sum(d[..., i, :i, None] * prev, axis=-2))
    x, size = jnp.stack(rows, axis=-2), _SUB
    while size < c:
        p, q = x[..., 0::2, :, :], x[..., 1::2, :, :]
        low = -_mm("...ij,...jk->...ik",
                   _mm("...ij,...jk->...ik", q, blocks(size, 1)), p)
        x = jnp.concatenate([
            jnp.concatenate([p, jnp.zeros_like(p)], axis=-1),
            jnp.concatenate([low, q], axis=-1)], axis=-2)
        size *= 2
    return x[..., 0, :, :]


def _prepare(q, k, v, g, beta, same, plain: bool = False):
    """A chunk's tokens solved against each other, for any number of chunks
    at once. q, k [N, C, Hk, dk] (as the convolution left them), v [N, C, H,
    dv], g, beta [N, C, H] (0 on tokens that take no part), same [N, C, C]
    bool: token j is token i's own or an earlier one of the SAME row.
    Returns what `_apply` needs of each chunk, heads leading: u [N, H, C,
    dv] and w [N, H, C, dk] (the chunk's corrected values = u - w S for an
    incoming state S), attn [N, H, C, C], qg (q scaled by its decay), k and
    gc [N, H, C] (each token's log decay since its row entered the chunk).
    `plain`: no token corrects another, so u = beta v and there is no w.
    g [N, C, H, dk] (a decay a key channel; not `plain`): gc [N, H, C, dk]."""
    q, k = _operands(q, k, v.shape[2], plain)
    q, k, v = (jnp.moveaxis(x, 2, 1) for x in (q, k, v.astype(_F32)))
    g, beta = jnp.moveaxis(g, 2, 1), jnp.moveaxis(beta, 2, 1)  # [N, H, C]
    if a_channel(g, beta):  # [N, H, C, dk]
        return _prepare_vector(q, k, v, g, beta, same)
    mask = same[:, None]  # [N, 1, C, C]
    gc = _mm("nij,nhj->nhi", same.astype(_F32), g)
    decay = jnp.exp(jnp.where(mask, gc[..., :, None] - gc[..., None, :],
                              -jnp.inf))
    if plain:
        return {"u": beta[..., None] * v,
                "attn": _mm("nhik,nhjk->nhij", q, k) * decay,
                "qg": q * jnp.exp(gc)[..., None], "k": k, "gc": gc}
    strict = mask & ~jnp.eye(same.shape[-1], dtype=bool)
    kk = _mm("nhik,nhjk->nhij", k, k)
    t = _tri_inv(jnp.where(strict, beta[..., None] * kk * decay, 0.0))
    u = _mm("nhij,nhjd->nhid", t, beta[..., None] * v)
    w = _mm("nhij,nhjd->nhid", t, (beta * jnp.exp(gc))[..., None] * k)
    attn = _mm("nhik,nhjk->nhij", q, k) * decay
    return {"u": u, "w": w, "attn": attn, "qg": q * jnp.exp(gc)[..., None],
            "k": k, "gc": gc}


def _prepare_vector(q, k, v, g, beta, same):
    """`_prepare` with a decay a key channel (the module docstring has the
    block solve), heads leading: q, k, g [N, H, C, dk], v [N, H, C, dv], beta
    [N, H, C]. No exponent above 0 is formed."""
    n, h, c, dk = k.shape
    nb = c // _SUB
    gc = _mm("nij,nhjc->nhic", same.astype(_F32), g)  # [N, H, C, dk]

    def blocked(x):  # [N, H, C, ...] -> [N, H, nb, _SUB, ...]
        return x.reshape(n, h, nb, _SUB, *x.shape[3:])

    qb, kb, gb = blocked(q), blocked(k), blocked(gc)
    # Inside a block: G_i - G_j formed directly, masked before the exp.
    same_b = same.reshape(n, nb, _SUB, nb, _SUB)
    diag = jnp.stack([same_b[:, i, :, i, :] for i in range(nb)], axis=1)
    decay = jnp.exp(jnp.where(
        diag[:, None, ..., None],
        gb[..., :, None, :] - gb[..., None, :, :], -jnp.inf))
    kd = kb[..., None, :, :] * decay  # [N, H, nb, i, j, dk]
    kk_in = jnp.sum(kb[..., :, None, :] * kd, axis=-1)
    qk_in = jnp.sum(qb[..., :, None, :] * kd, axis=-1)
    # A block's tokens against the EARLIER blocks': both sides scaled to the
    # row's G before the block's first token (the first token's own g taken
    # off its G), members of that token's row only.
    first = jnp.arange(nb) * _SUB
    ref = gb[..., 0, :] - blocked(g)[..., 0, :]  # [N, H, nb, dk]
    later = diag[..., 0]  # [N, I, i]: i of first(I)'s row (and behind it)
    earlier = same[:, first, :] & (
        jnp.arange(c)[None, :] < first[:, None])  # [N, I, j]
    up = jnp.exp(jnp.where(later[:, None, ..., None],
                           gb - ref[..., None, :], -jnp.inf))
    down = jnp.exp(jnp.where(
        earlier[:, None, ..., None],
        ref[..., None, :] - gc[:, :, None], -jnp.inf))  # [N, H, I, C, dk]
    k_down = k[:, :, None] * down
    kk_off = _mm("nhbik,nhbjk->nhbij", kb * up, k_down).reshape(n, h, c, c)
    qk_off = _mm("nhbik,nhbjk->nhbij", qb * up, k_down).reshape(n, h, c, c)

    def whole(inside, off):  # the diagonal blocks laid over the rest
        eye = jnp.eye(nb, dtype=_F32)[:, None, :, None]  # [nb, 1, nb, 1]
        return off + (inside[..., None, :] * eye).reshape(n, h, c, c)

    mask = same[:, None]
    strict = mask & ~jnp.eye(c, dtype=bool)
    t = _tri_inv(jnp.where(
        strict, beta[..., None] * whole(kk_in, kk_off), 0.0))
    u = _mm("nhij,nhjd->nhid", t, beta[..., None] * v)
    w = _mm("nhij,nhjd->nhid", t, beta[..., None] * jnp.exp(gc) * k)
    attn = jnp.where(mask, whole(qk_in, qk_off), 0.0)
    return {"u": u, "w": w, "attn": attn, "qg": q * jnp.exp(gc), "k": k,
            "gc": gc}


def _apply(s, c, member):
    """One row's tokens of one chunk against the row's incoming state.
    s [..., H, dk, dv]; c: `_prepare`'s results at that chunk [..., H, C,
    .]; member [..., C] bool: the row's tokens (at least one). Returns
    (o [..., H, C, dv] — right at the row's tokens only —, the state after
    the row's last token of the chunk)."""
    m = member[..., None, :]  # [..., 1, C]
    v_new = c["u"]
    if "w" in c:  # the delta rule's correction against the incoming state
        v_new = v_new - _mm("...hck,...hkd->...hcd", c["w"], s)
    o = _mm("...hck,...hkd->...hcd", c["qg"], s) \
        + _mm("...hij,...hjd->...hid", c["attn"], v_new)
    # gc falls along a row (g <= 0): its least value is the last token's.
    if c["gc"].ndim == c["k"].ndim:  # [..., H, C, dk]: a decay a key channel
        mk = m[..., None]
        g_last = jnp.min(jnp.where(mk, c["gc"], jnp.inf), axis=-2)
        kd = c["k"] * jnp.exp(jnp.where(
            mk, g_last[..., None, :] - c["gc"], -jnp.inf))
        s = s * jnp.exp(g_last)[..., None]  # rows of S
    else:
        g_last = jnp.min(jnp.where(m, c["gc"], jnp.inf), axis=-1)  # [..., H]
        kd = c["k"] * jnp.exp(jnp.where(
            m, g_last[..., None] - c["gc"], -jnp.inf))[..., None]
        s = s * jnp.exp(g_last)[..., None, None]
    return o, s + _mm("...hck,...hcd->...hkd", kd, v_new)


def _to_heads(state, n_heads: int):
    """State rows [..., dk, H * dv] -> [..., H, dk, dv] (`_apply`'s)."""
    return jnp.moveaxis(_heads(state, n_heads), -2, -3)


def _from_heads(s):
    s = jnp.moveaxis(s, -3, -2)  # [..., dk, H, dv]
    return s.reshape(*s.shape[:-2], -1)


def chunked(q, k, v, g, beta, valid=None, state=None, plain: bool = False):
    """Whole sequences from an empty (or a given) state. q, k [B, T, Hk,
    dk], v [B, T, H, dv], g, beta [B, T, H] (g [B, T, H, dk]: a decay a key
    channel); valid [B, T] bool (padding takes no part). Returns (o [B, T,
    H, dv] float32, state [B, dk, H * dv])."""
    b, t, _, dk = q.shape
    h, dv = v.shape[-2:]
    n = -(-t // CHUNK)
    pad = n * CHUNK - t
    if valid is None:
        valid = jnp.ones((b, t), bool)
    g = jnp.where(valid[(...,) + (None,) * (g.ndim - 2)], g, 0.0)
    beta = jnp.where(valid[..., None], beta, 0.0)

    def cut(x):  # [B, T, ...] -> [B * n, C, ...]
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        return x.reshape(b * n, CHUNK, *x.shape[2:])

    same = jnp.broadcast_to(jnp.tril(jnp.ones((CHUNK, CHUNK), bool)),
                            (b * n, CHUNK, CHUNK))
    c = _prepare(cut(q), cut(k), cut(v), cut(g), cut(beta), same, plain)
    c = {name: jnp.moveaxis(x.reshape(b, n, *x.shape[1:]), 1, 0)
         for name, x in c.items()}  # chunks leading: the scan's xs
    s0 = jnp.zeros((b, h, dk, dv), _F32) if state is None \
        else _to_heads(state, h)
    member = jnp.ones((b, CHUNK), bool)
    s, o = jax.lax.scan(lambda s, ci: _apply(s, ci, member)[::-1], s0, c)
    o = jnp.moveaxis(o, 0, 1)  # [B, n, H, C, dv]
    o = jnp.moveaxis(o, 2, 3).reshape(b, n * CHUNK, h, dv)[:, :t]
    return o, _from_heads(s)


# -- the served schedules ----------------------------------------------------
def _step_rows(impl, state, layer, slots, live, reset, q, k, v, g, beta,
               interpret=False, plain: bool = False):
    """`step` over rows of `state[layer]` named by `slots`, in place: live
    rows advance, the others keep their state and read zeros."""
    if impl == "pallas" and plain:  # beta is 1 on every row a kernel sees
        from ollamamq_tpu.ops.pallas.ssd_step import ssd_step_pallas

        return ssd_step_pallas(state, layer, slots, live, reset, q, k, v, g,
                               interpret=interpret)
    if impl == "pallas":
        from ollamamq_tpu.ops.pallas.gated_delta_step import (
            gated_delta_step_pallas)

        return gated_delta_step_pallas(state, layer, slots, live, reset, q, k,
                                       v, g, beta, interpret=interpret)
    rows = jax.lax.dynamic_index_in_dim(state, layer, 0,
                                        keepdims=False)[slots]
    o, new = step(rows, q, k, v, g, beta, reset, plain)
    new = jnp.where(live[:, None, None], new, rows)
    o = jnp.where(live[:, None, None], o, 0.0)
    return o, state.at[layer, slots].set(new)


def _row_major(x):
    """x, held row-major on the device: a row's state as the pair loop
    slices it out of the carried array. The step kernel updates that array
    in place and reads it row-major; left to itself the chip's compiler
    wants the sliced row with the key dimension MINOR where that is a whole
    lane tile (dk = 128: Qwen3-Next's), gives the loop's carry that order
    and re-lays the WHOLE state — 321 MB, ~1 ms — in and out of every
    scanned period (8 copies a 48 ms step on a v5e; PERF.md section 6,
    PR 47). Pinned here, it re-lays the row it reads, if it must. A key
    dimension that is no whole lane tile (Olmo-Hybrid's 96) leaves it no
    such choice, and that model's programs nothing to pin."""
    from jax.experimental.layout import Layout, with_layout_constraint

    if x.shape[-2] % 128:
        return x
    return with_layout_constraint(
        x, Layout(major_to_minor=tuple(range(x.ndim))))


def ragged(q, k, v, g, beta, state, layer, slot_ids, tok_seq, tok_pos,
           q_start, q_len, is_first, impl: str = "jnp", interpret=False,
           plain: bool = False):
    """The flattened stream of a ragged step (see the module docstring).
    q, k [T, Hk, dk], v [T, H, dv], g, beta [T, H] (g [T, H, dk]: a decay a
    key channel); state the whole carried array, `layer` this layer's index
    in it; slot_ids, q_start, q_len, is_first [B] per row; tok_seq, tok_pos
    [T] each token's row and position (-1: padding). The rows lie in stream
    order — `q_start` does not decrease with the row index, as the engine lays a step out
    (tests/test_olmo_hybrid.py holds it to that): the pair loop does not
    need it, the pair kernel does (its output block of a window is fetched
    once, so a window it came back to would lose the earlier row's
    tokens). Returns (o [T, H, dv] float32, state')."""
    t = q.shape[0]
    h, dv = v.shape[-2:]
    single, multi = q_len == 1, q_len > 1
    # 1. Rows with one token: `step` at the row's stream position.
    at = jnp.clip(q_start, 0, t - 1)
    o_rows, state = _step_rows(impl, state, layer, slot_ids, single,
                               is_first > 0, q[at], k[at], v[at], g[at],
                               beta[at], interpret=interpret, plain=plain)
    # 2. Longer spans: windows of CHUNK tokens of the stream, all solved at
    # once under the same-row mask; one-token rows and padding take no part.
    n = -(-t // CHUNK)
    pad = n * CHUNK - t
    part = multi[tok_seq] & (tok_pos >= 0)

    def cut(x, fill=0):
        x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1),
                    constant_values=fill)
        return x.reshape(n, CHUNK, *x.shape[1:])

    row_of = cut(jnp.where(part, tok_seq, -1), -1)  # [n, C]
    same = (row_of[:, :, None] == row_of[:, None, :]) \
        & jnp.tril(jnp.ones((CHUNK, CHUNK), bool))
    # (a decay a key channel: the window solve under a scope of its own)
    vector = a_channel(g, beta)
    along_g = (slice(None),) + (None,) * (g.ndim - 1)  # `part` over g's axes
    kernel = solver = None  # the Pallas kernels, at the shapes they take
    if impl == "pallas":
        from ollamamq_tpu.ops.pallas import chunk_rule, chunk_solve

        shape = (h, q.shape[-1], dv, plain, vector)
        kernel = chunk_rule.blocks(*shape) and chunk_rule
        solver = kernel and chunk_solve.blocks(*shape) and chunk_solve
    with jax.named_scope("kda_prepare") if vector \
            else contextlib.nullcontext():
        def gated():  # v, and the gates of the tokens that take part
            return (cut(v), cut(jnp.where(part[along_g], g, 0.0)),
                    cut(jnp.where(part[:, None], beta, 0.0)))

        if solver:  # ONE launch over the windows a span touches
            c = solver.chunk_solve_pallas(
                *_operands(cut(q), cut(k), h, plain), *gated(), row_of,
                interpret=interpret)
        else:
            c = _prepare(cut(q), cut(k), *gated(), same, plain)
    # 3. The (row, window) pairs the spans touch, rows in order and each
    # row's windows in order: pair p is row `b`, its window `w`.
    first_w = q_start // CHUNK
    n_w = jnp.where(multi, (q_start + q_len - 1) // CHUNK - first_w + 1, 0)
    ends = jnp.cumsum(n_w)

    def of_pair(p):  # (its row, its window)
        b = jnp.sum(ends <= p).astype(jnp.int32)
        return b, first_w[b] + p - (ends[b] - n_w[b])

    def pair(p, carry):
        out, state = carry
        b, w = of_pair(p)
        ci = {name: jax.lax.dynamic_index_in_dim(x, w, 0, keepdims=False)
              for name, x in c.items()}
        member = jax.lax.dynamic_index_in_dim(row_of, w, 0,
                                              keepdims=False) == b
        opens = (is_first[b] > 0) & (w == first_w[b])
        s = _row_major(jax.lax.dynamic_slice(
            state, (layer, slot_ids[b], 0, 0), (1, 1) + state.shape[2:]))[0, 0]
        o, s = _apply(jnp.where(opens, 0.0, _to_heads(s, h)), ci, member)
        old = jax.lax.dynamic_index_in_dim(out, w, 0, keepdims=False)
        out = jax.lax.dynamic_update_index_in_dim(
            out, jnp.where(member[None, :, None], o, old), w, 0)
        state = jax.lax.dynamic_update_slice(
            state, _from_heads(s)[None, None], (layer, slot_ids[b], 0, 0))
        return out, state

    if kernel:
        # ONE launch over the pairs, a row's state in VMEM across its
        # windows (`pair` is its definition): each pair's row, window and
        # slot, past the last pair the last one's again — no pair: the
        # trash row —, and what the pair does to the state.
        some = ends[-1] > 0
        b, w = jax.vmap(of_pair)(jnp.minimum(
            jnp.arange(kernel.pair_bound(n, q_len.shape[0], t)),
            jnp.maximum(ends[-1] - 1, 0)))
        flags = (w == first_w[b]) * (
            kernel.FIRST + kernel.OPENS * (is_first[b] > 0))
        out, state = kernel.chunk_rule_pallas(
            state, layer, jnp.where(some, slot_ids[b], state.shape[1] - 1),
            jnp.where(some, w, 0), jnp.where(some, b, -2), flags, ends[-1],
            row_of, c, interpret=interpret)
        # [n, C, H * dv], the stream's own layout; tokens of no span hold
        # what the kernel's buffers held
        o = jnp.where(part[:, None, None],
                      out.reshape(n * CHUNK, h, dv)[:t], 0.0)
    else:
        out, state = jax.lax.fori_loop(
            0, ends[-1], pair, (jnp.zeros((n, h, CHUNK, dv), _F32), state))
        o = jnp.moveaxis(out, 1, 2).reshape(n * CHUNK, h, dv)[:t]
    # a one-token row's output at its stream position (others: dropped)
    o = o.at[jnp.where(single, q_start, t)].set(o_rows, mode="drop")
    return o, state


def decode(q, k, v, g, beta, state, layer, active=None, impl: str = "jnp",
           plain: bool = False):
    """One token a slot: q, k [S, Hk, dk], v [S, H, dv], g, beta [S, H] (g
    [S, H, dk]: a decay a key channel); row s of `state[layer]` is slot s's.
    Returns (o [S, H, dv], state')."""
    n = q.shape[0]
    live = jnp.ones((n,), bool) if active is None else active > 0
    return _step_rows(impl, state, layer, jnp.arange(n, dtype=jnp.int32),
                      live, jnp.zeros((n,), bool), q, k, v, g, beta,
                      plain=plain)
