"""The delta rule with a decay a KEY CHANNEL (Kimi Delta Attention, PR 63):
`ops/gated_delta.py`'s vector reading — `g` of rank one more than `beta` —
in `step`, `chunked`, `ragged` and `decode`, and both Pallas kernels in
interpret mode, against the token-serial recurrence, at the decays that break
a careless window solve: weak (g ~ -1e-3 a token) and strong (down to -20 a
token a channel, channels at both ends in one head: the factored form
(k e^G) (k e^-G)^T overflows at the third token). And that the scalar-gate
programs trace what they traced before the reading existed.

What interpret mode cannot show of the pair kernel is in
tests/test_chunk_rule.py's docstring; `scripts/chunk_rule_bench.py` holds the
kernel to the jnp path on the chip at (32, 128, 128), vector reading too."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ollamamq_tpu.ops import gated_delta as gd
from ollamamq_tpu.ops.pallas import chunk_rule
from ollamamq_tpu.ops.pallas.gated_delta_step import (gated_delta_step_pallas,
                                                      head_blocks)
from test_chunk_rule import SLOTS, T, stream
from test_lfm2 import close

H, DK, DV = 4, 16, 32
DECAYS = ("weak", "strong")
# The module's float32 margins (test_olmo_hybrid.py, test_chunk_rule.py).
ATOL, KERNEL_ATOL = 2e-5, 5e-6
# Spans that change row inside a window AND inside a 16-token block (70 +
# 60: token 70 is the 7th of its block), one-token rows between spans, a
# span that opens, padding behind.
STREAMS = {
    "rows_meet_inside_a_block": ([70, 60], [1]),
    "decode_rows_between_spans": ([70, 1, 1, 50, 1, 30], [3, 4]),
    "short_spans_in_one_block": ([3, 5, 2, 9], [0, 2]),
    "one_long_span": ([150], []),
}


def decay(rng, shape, which):
    """Log decays [..., H, dk]. weak: about -1e-3. strong: uniform down to
    -20, channel 0 of every head AT -20 and channel 1 at -1e-3 — both ends
    in one head — and every eighth token not decayed at all."""
    if which == "weak":
        return -rng.uniform(0, 2e-3, size=shape)
    g = -rng.uniform(0, 20, size=shape)
    g[..., 0], g[..., 1] = -20.0, -1e-3
    g[::8] = 0.0
    return g


def inputs(which, t=T, seed=0, h=H, dk=DK, dv=DV):
    rng = np.random.default_rng(seed)
    f = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    return (f(rng.normal(size=(t, h, dk))), f(rng.normal(size=(t, h, dk)) + 1),
            f(rng.normal(size=(t, h, dv))), f(decay(rng, (t, h, dk), which)),
            f(rng.uniform(0, 2, size=(t, h))),
            f(rng.normal(size=(2, SLOTS + 1, dk, h * dv))))


@jax.jit
def serial(q, k, v, g, beta, rows0, tok_seq, tok_pos):
    """The recurrence a stream token at a time, each token on its row's
    state (rows0 [ROWS, H, dk, dv]): S' = Diag(e^g) S scales ROWS of S.
    q, k normalised. Returns (o [T, H, dv], rows')."""
    def token(rows, x):
        q_t, k_t, v_t, g_t, b_t, row, pos = x
        s = rows[row] * jnp.exp(g_t)[:, :, None]
        r = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", s, k_t,
                                             precision="highest"))
        s = s + k_t[:, :, None] * r[:, None, :]
        o = jnp.einsum("hkv,hk->hv", s, q_t, precision="highest")
        live = pos >= 0
        return (jnp.where(live, rows.at[row].set(s), rows),
                jnp.where(live, o, 0.0))

    return jax.lax.scan(token, rows0, (q, k, v, g, beta, tok_seq,
                                       tok_pos))[::-1]


def one_row(t):
    return jnp.zeros((t,), jnp.int32), jnp.arange(t, dtype=jnp.int32)


_CHUNKED, _STEP = jax.jit(gd.chunked), jax.jit(gd.step)
_RAGGED = jax.jit(gd.ragged, static_argnames=("impl", "interpret"))


@pytest.mark.parametrize("which", DECAYS)
@pytest.mark.parametrize("t", [1, 17, 64, 65, 200])
def test_chunked_and_step_are_the_token_serial_recurrence(t, which):
    q, k, v, g, beta, state0 = inputs(which, t, seed=t)
    s0 = gd._to_heads(state0[0, 0], H)
    qn, kn = gd.normalise(q, k)
    o_ref, s_ref = serial(qn, kn, v, g, beta, s0[None], *one_row(t))
    o, s = _CHUNKED(q[None], k[None], v[None], g[None], beta[None],
                    state=state0[0, :1])
    assert bool(jnp.isfinite(o).all() & jnp.isfinite(s).all())
    close(o[0], np.asarray(o_ref), atol=ATOL)
    close(gd._to_heads(s[0], H), np.asarray(s_ref[0]), atol=ATOL)
    s, outs = state0[0, 0], []
    for i in range(min(t, 66)):
        o_i, s = _STEP(s, q[i], k[i], v[i], g[i], beta[i])
        outs.append(o_i)
    close(jnp.stack(outs), np.asarray(o_ref)[:len(outs)], atol=ATOL)
    if t <= 66:
        close(gd._to_heads(s, H), np.asarray(s_ref[0]), atol=ATOL)


@pytest.mark.parametrize("which", DECAYS)
def test_a_padded_batch_takes_no_part(which):
    """`chunked` with `valid`: a sequence's padding neither decays nor
    writes its state."""
    q, k, v, g, beta, _ = inputs(which, 100, seed=3)
    valid = jnp.arange(100)[None, :] < 70
    o, s = _CHUNKED(q[None], k[None], v[None], g[None], beta[None], valid)
    o70, s70 = _CHUNKED(*(x[None, :70] for x in (q, k, v, g, beta)))
    close(o[0, :70], np.asarray(o70[0]), atol=ATOL)
    close(s, np.asarray(s70), atol=ATOL)


@pytest.mark.parametrize("which", DECAYS)
@pytest.mark.parametrize("name", STREAMS)
def test_a_ragged_stream_through_the_loop_and_through_the_kernels(name,
                                                                  which):
    """`ragged` on the jnp path (the pair loop, `step`) and with both
    kernels in interpret mode: each is the token-serial recurrence on every
    row's own state, no inf, no nan; rows the step does not touch keep their
    state bit for bit."""
    q, k, v, g, beta, state0 = inputs(which, seed=len(name))
    meta = stream(*STREAMS[name])
    slot_ids, tok_seq, tok_pos, _, q_len, is_first = meta
    assert chunk_rule.blocks(H, DK, DV, False, True)
    args = (q, k, v, g, beta, state0, jnp.int32(1), *meta)
    o_jnp, s_jnp = _RAGGED(*args)
    o, s = _RAGGED(*args, impl="pallas", interpret=True)
    qn, kn = gd.normalise(q, k)
    rows0 = jnp.where((is_first > 0)[:, None, None, None], 0.0,
                      gd._to_heads(state0[1, slot_ids], H))
    o_ref, rows = serial(qn, kn, v, g, beta, rows0, tok_seq, tok_pos)
    live = np.asarray(q_len) > 0
    idle = np.setdiff1d(np.arange(SLOTS), np.asarray(slot_ids)[live])
    for got_o, got_s in ((o_jnp, s_jnp), (o, s)):
        assert bool(jnp.isfinite(got_o).all() & jnp.isfinite(got_s).all())
        close(got_o, np.asarray(o_ref), atol=ATOL)
        close(gd._to_heads(got_s[1, slot_ids[live]], H),
              np.asarray(rows)[live], atol=ATOL)
        assert bool(jnp.all(got_s[0] == state0[0]))
        assert bool(jnp.all(got_s[1, idle] == state0[1, idle]))
    close(o, np.asarray(o_jnp), atol=KERNEL_ATOL)
    close(s, np.asarray(s_jnp), atol=KERNEL_ATOL)


@pytest.mark.parametrize("which", DECAYS)
@pytest.mark.parametrize("heads,dk,dv,live", [
    (4, 8, 16, [1, 0, 1, 1, 0, 1]),    # one lane group a row
    (4, 8, 64, [0, 0, 1, 0, 0, 0]),    # pairs of heads fill a lane tile
    (4, 8, 16, [0, 0, 0, 0, 0, 0]),    # no live row
], ids=["tiny", "pairs", "none_live"])
def test_the_step_kernel_in_interpret_mode_is_step(heads, dk, dv, live,
                                                   which):
    rng = np.random.default_rng(7)
    n, layers, rows = len(live), 3, 9
    f = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    state0 = f(rng.normal(size=(layers, rows, dk, heads * dv)))
    slots = jnp.asarray([5, 8, 0, 3, 8, 7], jnp.int32)  # 8: the trash row
    live = jnp.asarray(live, bool)
    reset = jnp.asarray([0, 0, 1, 0, 0, 0], bool)
    q, k = (f(rng.normal(size=(n, heads, dk))) for _ in range(2))
    v = f(rng.normal(size=(n, heads, dv)))
    g = f(decay(rng, (n, heads, dk), which))
    beta = f(rng.uniform(0, 2, size=(n, heads)))
    hg, hb = head_blocks(heads, dk, dv)
    assert heads % hb == 0 and hb % hg == 0
    o, new = gated_delta_step_pallas(state0, jnp.int32(1), slots, live,
                                     reset, q, k, v, g, beta, interpret=True)
    o_ref, s_ref = gd.step(state0[1][slots], q, k, v, g, beta, reset)
    untouched = np.ones(rows, bool)
    for i in np.flatnonzero(np.asarray(live)):
        close(o[i], np.asarray(o_ref[i]), atol=1e-5)
        close(new[1, slots[i]], np.asarray(s_ref[i]), atol=1e-5)
        untouched[int(slots[i])] = False
    untouched[8] = False  # the trash row may hold anything
    assert bool(jnp.all(o[~live] == 0.0))
    assert bool(jnp.all(new[1][untouched] == state0[1][untouched]))
    assert bool(jnp.all(new[0] == state0[0])) \
        and bool(jnp.all(new[2] == state0[2]))


@pytest.mark.parametrize("which", DECAYS)
def test_decode_advances_the_active_slots_only(which):
    q, k, v, g, beta, state0 = inputs(which, SLOTS, seed=11)
    active = jnp.asarray([1, 0, 1, 1, 0, 0, 1, 0], jnp.int32)
    o, new = jax.jit(gd.decode)(q, k, v, g, beta, state0[:, :SLOTS],
                                jnp.int32(0), active)
    o_ref, s_ref = gd.step(state0[0, :SLOTS], q, k, v, g, beta)
    on = np.asarray(active) > 0
    close(o[on], np.asarray(o_ref)[on], atol=ATOL)
    close(new[0][on], np.asarray(s_ref)[on], atol=ATOL)
    assert bool(jnp.all(o[~on] == 0.0))
    assert bool(jnp.all(new[0][~on] == state0[0, :SLOTS][~on]))
    assert bool(jnp.all(new[1] == state0[1, :SLOTS]))


def test_a_scalar_decay_a_head_is_its_broadcast_over_the_channels():
    """The two readings meet where they must: g [T, H] and the same g
    repeated over the key channels give the same outputs and states."""
    q, k, v, g, beta, state0 = inputs("weak", seed=5)
    g1 = g[..., 0] * 50  # [T, H]
    meta = stream(*STREAMS["decode_rows_between_spans"])
    rest = (beta, state0, jnp.int32(1), *meta)
    o1, s1 = _RAGGED(q, k, v, g1, *rest)
    o2, s2 = _RAGGED(q, k, v, jnp.broadcast_to(g1[..., None], g.shape), *rest)
    close(o2, np.asarray(o1), atol=ATOL)
    close(s2, np.asarray(s1), atol=ATOL)


# (H, Hk, dk, dv) of the scalar-gate callers whose programs must not move.
SCALAR_CALLERS = {"qwen3_next": (32, 16, 128, 128),
                  "olmo_hybrid": (30, 30, 96, 192)}


@pytest.mark.parametrize("model", SCALAR_CALLERS)
def test_the_scalar_gate_programs_do_not_meet_the_vector_reading(
        model, monkeypatch):
    """`ragged` and `decode` traced at Qwen3-Next's and Olmo-Hybrid's shapes
    with a decay a head: the vector solve is never entered, its scope is not
    in the program, the pair kernel's blocks are what they were (PR 62's
    table), and the jaxprs are a function of the shapes alone (traced twice:
    the same text). The builder compared the texts with the parent's
    (PERF.md section 6, PR 63: equal)."""
    h, hk, dk, dv = SCALAR_CALLERS[model]

    def boom(*a, **kw):
        raise AssertionError("a scalar gate reached the vector solve")

    monkeypatch.setattr(gd, "_prepare_vector", boom)
    t, rows, slots = 512, 16, 16
    f32, i32 = jnp.float32, jnp.int32
    sd = jax.ShapeDtypeStruct
    stream_args = (sd((t, hk, dk), f32), sd((t, hk, dk), f32),
                   sd((t, h, dv), f32), sd((t, h), f32), sd((t, h), f32),
                   sd((3, slots + 1, dk, h * dv), f32), sd((), i32),
                   sd((rows,), i32), sd((t,), i32), sd((t,), i32),
                   sd((rows,), i32), sd((rows,), i32), sd((rows,), i32))
    texts = [str(jax.make_jaxpr(gd.ragged)(*stream_args)) for _ in range(2)]
    assert texts[0] == texts[1]
    assert "kda_prepare" not in jax.jit(gd.ragged).lower(
        *stream_args).as_text(debug_info=True)
    slot_args = (sd((slots, hk, dk), f32), sd((slots, hk, dk), f32),
                 sd((slots, h, dv), f32), sd((slots, h), f32),
                 sd((slots, h), f32), sd((3, slots + 1, dk, h * dv), f32),
                 sd((), i32), sd((slots,), i32))
    jax.make_jaxpr(gd.decode)(*slot_args)
    want = {"qwen3_next": (1, 16), "olmo_hybrid": (2, 10)}[model]
    assert chunk_rule.blocks(h, dk, dv, False) == want


def test_the_published_shape_takes_the_kernel_at_the_vector_reading():
    """(32, 128, 128) with a [dk, C] block of G a head more: eight heads a
    block where the scalar reading holds sixteen."""
    assert chunk_rule.blocks(32, 128, 128, False) == (1, 16)
    hg, hb = chunk_rule.blocks(32, 128, 128, False, True)
    assert (hg, hb) == (1, 8)
    assert chunk_rule._block_bytes(hb, 128, 128, False, True) \
        <= chunk_rule.VMEM_BYTES < chunk_rule._block_bytes(
            16, 128, 128, False, True)


def test_the_served_forwards_through_the_kernels_match_the_jnp_path(
        monkeypatch):
    """forward_ragged of the tiny stack with `attn_impl` pallas in interpret
    mode — the dense latent kernel over a two-layer latent pool, the step
    kernel and the pair kernel at the vector reading, the grouped matmul —
    against the jnp path, after a step that left row 0 eleven tokens: a
    one-token row on carried state beside a first span of 30."""
    from ollamamq_tpu.models import llama, moe
    from test_kimi_linear import KL, make_params, state
    from test_lfm2 import ATOL, B, PS, page_table, ragged_step, seq_tokens

    gmm = moe.grouped_matmul
    monkeypatch.setattr(moe, "grouped_matmul", lambda impl, xs, w, sizes:
                        gmm(impl, xs, w, sizes, interpret=True))
    params = make_params()
    seqs = {0: seq_tokens(40, 12), 1: seq_tokens(41, 30)}
    _, (kc, vc, slot_state), _ = ragged_step(
        KL, params, state(garbage=0.5), [(0, seqs[0][:11], 0)])
    tok = jnp.asarray(seqs[0][11:] + seqs[1] + [0], jnp.int32)
    seq = jnp.asarray([0] + [1] * 30 + [0], jnp.int32)
    pos = jnp.asarray([11] + list(range(30)) + [-1], jnp.int32)
    pt = jnp.asarray(page_table())
    at = jnp.maximum(pos, 0)
    args = dict(
        tok_seq=seq, tok_pos=pos,
        write_slots=jnp.where(pos >= 0, pt[seq, at // PS] * PS + at % PS, 0),
        out_idx=jnp.asarray([0, 30, 0, 0]), k_cache=kc, v_cache=vc,
        page_table=pt, q_start=jnp.asarray([0, 1, 32, 32]),
        q_len=jnp.asarray([1, 30, 0, 0]), kv_len=jnp.asarray([12, 30, 0, 0]),
        page_size=PS, conv_state=slot_state,
        slot_ids=jnp.asarray([0, 1, B, B]), is_first=jnp.asarray([0, 1, 0, 0]))
    forward = jax.jit(llama.forward_ragged, static_argnums=1, static_argnames=(
        "page_size", "attn_impl", "interpret"))
    want_, _, _, st_jnp = forward(params, KL, tok, **args)
    got, _, _, st = forward(params, KL, tok, **args, attn_impl="pallas",
                            interpret=True)
    close(got[:2], np.asarray(want_[:2]), atol=ATOL)
    close(st.rule[:, :2], np.asarray(st_jnp.rule[:, :2]), atol=5 * ATOL)
