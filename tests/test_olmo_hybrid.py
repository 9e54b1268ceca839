"""Olmo-Hybrid on the served path (PR 35): gated delta-rule linear-attention
layers with a per-slot matrix state beside the paged KV pool, the norms on
the sublayers' outputs, attention without a rotary embedding.

LOGITS of the served forwards against the benchmark's plain float32
reference (benchmarks/reference/olmo_hybrid_decoder.py: the rule as its
token-serial recurrence) at `test-tiny-olmo-hybrid`, seeded random weights,
float32, on the CPU; the rule's two forms against the token-serial scan;
what the engine does with the state (reset, carry, reuse, refusals)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ollamamq_tpu.config import (ATTENTION, LINEAR, MODEL_CONFIGS,
                                 validate_quant_config)
from ollamamq_tpu.engine.kv_cache import refusal
from ollamamq_tpu.models import llama
from ollamamq_tpu.ops import gated_delta as gd
from test_lfm2 import (ATOL, B, NP, PS, _arrivals, bfloat16_misses, close,
                       decode_scan, fused_scan, keeps_the_filler_from_it,
                       mixed_step, page_table, preempted_and_replayed,
                       ragged_step, reused_slot, seq_tokens)
from test_step_overlap import _engine, _prompt, _rt, both, drive
from testutil import (olmo_hybrid_keys, olmo_hybrid_reference,
                      once_a_sequence, prefill, seeded_params, whole_blocks)

OLMO = MODEL_CONFIGS["test-tiny-olmo-hybrid"]
H, DK, DV = (OLMO.linear_num_value_heads, OLMO.linear_key_head_dim,
             OLMO.linear_value_head_dim)


def make_params(mc, dtype=jnp.float32, seed=0):
    return seeded_params(mc, ("q_norm", "k_norm", "attn_norm", "mlp_norm",
                              "lin_norm"), dtype, seed)


def state(mc, dtype, garbage=0.0, pages=NP):
    """(kc, vc, SlotState): empty pools and a per-slot state that an earlier
    request left full of `garbage`."""
    kv = jnp.zeros((mc.count(ATTENTION), pages * PS, mc.kv_dim), dtype)
    st = llama.alloc_slot_state(mc, B, dtype)
    return kv, kv, jax.tree_util.tree_map(
        lambda a: a + jnp.asarray(garbage, a.dtype), st)


def by_slot(slot_state):
    """(window, rule state) as numpy, the slot axis at 1: the window is
    stored a tap a plane, [layers, K-1, slots, D] (no trash row), the rule's
    state [layers, slots + 1, ...]."""
    return (np.asarray(slot_state.conv).swapaxes(1, 2),
            np.asarray(slot_state.rule))


@once_a_sequence
def want(mc, params, tokens):
    """The reference's ONE full forward: [T, V] logits."""
    return np.asarray(olmo_hybrid_reference().logits(
        olmo_hybrid_keys(mc), params, whole_blocks(tokens)))[:len(tokens)]


# ----------------------------------------------------------- the config
def test_the_registered_family_and_its_plan():
    full = MODEL_CONFIGS["olmo-hybrid:7b"]
    assert (full.count(LINEAR), full.count(ATTENTION)) == (24, 8)
    assert [(f, len(p), n) for f, p, n in full.layer_plan()] == [(0, 4, 8)]
    assert 7.3e9 < full.param_count() < 7.5e9  # "7 B": 7.43
    assert full.rope_theta is None and full.norm_order == "post"
    assert full.state_window == (4, 11520)
    # the tiny one ends inside a period: two whole periods and a tail
    assert [(f, len(p), n) for f, p, n in OLMO.layer_plan()] \
        == [(0, 4, 2), (8, 1, 2)]
    assert (OLMO.count(LINEAR), OLMO.count(ATTENTION)) == (8, 2)


@pytest.mark.parametrize("bad,match", [
    (dict(linear_num_value_heads=6),
     "linear_num_value_heads 6 is not a multiple of linear_num_key_heads 4"),
    (dict(linear_conv_kernel_dim=1),
     "linear_conv_kernel_dim must be at least 2, got 1"),
    (dict(linear_key_head_dim=0), r"linear_key_head_dim \(0\)"),
    (dict(norm_order="sandwich"), "norm_order must be 'pre' or 'post'"),
    (dict(rope_parameters={"rope_theta": None, "rope_type": "yarn"}),
     r"rope_parameters holds \['rope_type'\]"),
    (dict(layer_types=("conv",) + OLMO.layer_types[1:]),
     "holds both 'conv' and 'linear_attention'"),
], ids=["value_heads", "kernel", "key_dim", "norm_order", "rope_group",
        "two_windows"])
def test_a_stack_the_program_cannot_run_is_refused_at_construction(bad,
                                                                   match):
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(OLMO, **bad)


def test_a_files_rope_group_sets_rope_theta_and_stays_hashable():
    mc = dataclasses.replace(OLMO, rope_theta=10_000.0,
                             rope_parameters={"rope_theta": None})
    assert mc.rope_theta is None and isinstance(hash(mc), int)
    mc = dataclasses.replace(OLMO, rope_parameters={"rope_theta": 5e5})
    assert mc.rope_theta == 5e5


def test_weights_are_stacked_by_kind_and_the_decay_is_in_range():
    lp = make_params(OLMO)["layers"]
    d, kd, vd = OLMO.hidden_size, OLMO.linear_key_dim, OLMO.linear_value_dim
    assert lp["attn_norm"].shape == lp["mlp_norm"].shape == (10, d)
    assert lp["wq"].shape == (2, d, OLMO.q_dim)
    assert lp["q_norm"].shape == (2, OLMO.q_dim)
    assert lp["lin_in"].shape == (8, d, 2 * kd + 2 * vd)
    assert lp["lin_ba"].shape == (8, d, 2 * H)
    assert lp["lin_conv_w"].shape == (8, 2 * kd + vd, 4)
    assert lp["lin_norm"].shape == (8, DV)
    assert lp["lin_out"].shape == (8, vd, d)
    # A in [1, 16], dt in [1e-3, 1e-1]: float32 whatever the weights' dtype
    a_log, dt_bias = lp["lin_A_log"], lp["lin_dt_bias"]
    assert a_log.dtype == dt_bias.dtype == jnp.float32
    assert 0.0 <= float(a_log.min()) and float(a_log.max()) <= np.log(16.0)
    dt = jax.nn.softplus(dt_bias)
    assert 1e-3 * 0.999 <= float(dt.min()) and float(dt.max()) <= 0.1 * 1.001


# ------------------------------ the rule: step = chunked = token by token
def serial(q, k, v, g, beta, s0):
    """The four lines of the recurrence, a token at a time. q, k [T, H, dk]
    raw, v [T, H, dv], g, beta [T, H]; s0 [H, dk, dv]."""
    qn, kn = gd.normalise(q, k)

    def token(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = s * jnp.exp(g_t)[:, None, None]
        r = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", s, k_t,
                                             precision="highest"))
        s = s + k_t[:, :, None] * r[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q_t, precision="highest")

    return jax.lax.scan(token, s0, (qn, kn, v, g, beta))


# One program a length (a form, bare, is its ops dispatched one by one).
serial, chunked, step = jax.jit(serial), jax.jit(gd.chunked), jax.jit(gd.step)


def rule_inputs(seed, t):
    """Correlated keys (a positive mean, as a SiLU leaves them), strengths
    up to 2 and decays from 0.6 to 1: what makes the chunk's triangular
    system far from the identity."""
    rng = np.random.default_rng(seed)
    f = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    return (f(rng.normal(size=(t, H, DK))), f(rng.normal(size=(t, H, DK)) + 1),
            f(rng.normal(size=(t, H, DV))),
            f(-rng.uniform(1e-3, 0.5, size=(t, H))),
            f(rng.uniform(0, 2, size=(t, H))))


@pytest.mark.parametrize("t", [1, 63, 64, 65, 200])
def test_chunked_and_step_are_the_token_serial_recurrence(t):
    q, k, v, g, beta = rule_inputs(t, t)
    s0 = jnp.asarray(np.random.default_rng(9).normal(size=(H, DK, DV)),
                     jnp.float32)
    s_ref, o_ref = serial(q, k, v, g, beta, s0)
    o, s = chunked(q[None], k[None], v[None], g[None], beta[None],
                   state=gd._from_heads(s0)[None])
    close(o[0], np.asarray(o_ref), atol=2e-5)
    close(gd._to_heads(s[0], H), np.asarray(s_ref), atol=2e-5)
    s, outs = gd._from_heads(s0), []
    for i in range(min(t, 66)):
        o_i, s = step(s, q[i], k[i], v[i], g[i], beta[i])
        outs.append(o_i)
    close(jnp.stack(outs), np.asarray(o_ref)[:len(outs)], atol=2e-5)
    if t <= 66:
        close(gd._to_heads(s, H), np.asarray(s_ref), atol=2e-5)


def test_repeated_keys_at_full_strength_stay_finite_and_exact():
    """b = 2 and the same key at every token: I - b k k^T is a reflection,
    the chunk's system 2 x (ones below the diagonal). Powers of it grow like
    2^n C(64, n); forward substitution does not form them."""
    t = 128
    q, k, v, g, _ = rule_inputs(3, t)
    k = jnp.broadcast_to(k[:1], k.shape)
    beta, g = jnp.full((t, H), 2.0), jnp.zeros((t, H))
    s_ref, o_ref = serial(q, k, v, g, beta, jnp.zeros((H, DK, DV)))
    o, s = chunked(q[None], k[None], v[None], g[None], beta[None])
    # (a reflection a token: float32 rounding adds up along the sequence,
    # in the serial form as in the chunked one)
    close(o[0], np.asarray(o_ref), atol=5e-3)
    close(gd._to_heads(s[0], H), np.asarray(s_ref), atol=5e-3)


def test_a_ragged_stream_continues_each_rows_own_state():
    """Spans that straddle window boundaries, a span inside one window
    beside another row's, one-token rows, an empty row and padding in ONE
    stream: every row continues its own slot's state (or opens at zero),
    and the slots of no row keep theirs."""
    q_len = np.array([70, 1, 5, 0, 130, 1])
    t = 16 * 14
    q, k, v, g, beta = rule_inputs(5, t)
    q_start = np.concatenate([[0], np.cumsum(q_len)[:-1]]).astype(np.int32)
    tok_seq, tok_pos = np.zeros(t, np.int32), -np.ones(t, np.int32)
    for b, (s, n) in enumerate(zip(q_start, q_len)):
        tok_seq[s:s + n], tok_pos[s:s + n] = b, np.arange(n)
    slot_ids = np.array([3, 1, 0, 6, 2, 5], np.int32)  # 6: the trash row
    is_first = np.array([0, 0, 1, 0, 1, 1], np.int32)
    state0 = jnp.asarray(np.random.default_rng(1).normal(
        size=(2, 7, DK, H * DV)), jnp.float32)
    o, new = jax.jit(gd.ragged)(
        q, k, v, g, beta, state0, jnp.int32(1), jnp.asarray(slot_ids),
        jnp.asarray(tok_seq), jnp.asarray(tok_pos), jnp.asarray(q_start),
        jnp.asarray(q_len, jnp.int32), jnp.asarray(is_first))
    for b, (s, n) in enumerate(zip(q_start, q_len)):
        if not n:
            continue
        s0 = jnp.zeros((H, DK, DV)) if is_first[b] \
            else gd._to_heads(state0[1, slot_ids[b]], H)
        s_ref, o_ref = serial(q[s:s + n], k[s:s + n], v[s:s + n],
                              g[s:s + n], beta[s:s + n], s0)
        close(o[s:s + n], np.asarray(o_ref), atol=2e-5)
        close(gd._to_heads(new[1, slot_ids[b]], H), np.asarray(s_ref),
              atol=2e-5)
    assert bool(jnp.all(new[0] == state0[0]))  # another layer's rows
    assert bool(jnp.all(new[1, jnp.array([4, 6])] == state0[1, jnp.array([4, 6])]))


# ------------------------ the window: a tap a plane, by slot, in place
# A case is a list of steps over 4 slots: ("ragged", {slot: span length}) —
# rows shuffled against slots, a padding row (slot 4: past the last) and
# padding tokens behind the stream — or ("decode", active slots or None for
# no mask). A slot's first span opens its request; ("reuse", slot) starts
# another request on it.
WINDOW_CASES = {
    "decode_then_ragged_then_decode": [
        ("ragged", {0: 4, 1: 5, 2: 3, 3: 6}), ("decode", None),
        ("decode", None), ("ragged", {0: 2, 1: 1, 2: 3, 3: 2}),
        ("decode", None), ("decode", None)],
    "active_mask": [
        ("ragged", {0: 4, 2: 3}), ("decode", [0]), ("decode", [0]),
        ("ragged", {2: 3}), ("decode", [0, 2]), ("decode", [2])],
    "spans_shorter_than_the_window": [
        ("ragged", {0: 1, 1: 2, 3: 1}), ("ragged", {0: 1, 1: 1, 3: 2}),
        ("decode", [0, 1, 3]), ("ragged", {0: 1, 3: 1}),
        ("ragged", {0: 2, 1: 1})],
    "first_spans_over_an_earlier_requests_state": [
        ("ragged", {0: 1, 1: 2, 2: 5, 3: 3}), ("decode", None),
        ("reuse", 1), ("reuse", 2), ("ragged", {0: 2, 1: 1, 2: 4}),
        ("decode", [0, 1, 2])],
    "a_row_without_tokens": [
        ("ragged", {0: 3, 1: 4, 2: 2}), ("ragged", {0: 2, 1: 0, 2: 1}),
        ("decode", [0, 2]), ("ragged", {0: 0, 1: 3, 2: 0})],
}


@pytest.mark.parametrize("n_prev", [2, 3])
@pytest.mark.parametrize("case", WINDOW_CASES.values(),
                         ids=WINDOW_CASES.keys())
def test_the_window_by_slot_is_taps_full_of_the_whole_sequence(case, n_prev):
    """ops/shortconv.py's two step schedules over a carried state [layers,
    K-1, slots, D]: every token's taps are `taps_full`'s of its whole
    sequence, the planes afterwards hold each served slot's last K-1
    positions, and a slot no row serves (or an inactive one) and the other
    layer's planes are bit-identical after a step."""
    from ollamamq_tpu.ops import shortconv

    S, D, P, layer = 4, 8, 16, 1
    rng = np.random.default_rng(n_prev)
    seqs = {}  # (slot, request) -> z [P, D] and its taps_full

    def seq(slot, request):
        if (slot, request) not in seqs:
            z = jnp.asarray(rng.normal(size=(1, P, D)), jnp.float32)
            seqs[slot, request] = (np.asarray(z[0]), [
                np.asarray(t[0]) for t in shortconv.taps_full(z, n_prev + 1)])
        return seqs[slot, request]

    conv = shortconv.alloc_state(2, S, n_prev + 1, D, jnp.float32) + 7.0
    assert conv.shape == (2, n_prev, S, D)
    request, pos = [0] * S, [0] * S
    for kind, arg in case:
        if kind == "reuse":
            request[arg], pos[arg] = request[arg] + 1, 0
            continue
        before = np.asarray(conv)
        if kind == "decode":
            live = list(range(S)) if arg is None else arg
            z = np.stack([seq(s, request[s])[0][pos[s]] for s in range(S)])
            active = None if arg is None else jnp.asarray(
                [int(s in arg) for s in range(S)], jnp.int32)
            taps, conv = shortconv.taps_decode(jnp.asarray(z), conv, layer,
                                               active)
            read = {s: [(s, pos[s])] for s in live}  # slot: (token, position)
        else:
            order = list(reversed(sorted(arg)))  # row r serves order[r]
            T = sum(arg.values()) + 3
            z = np.full((T, D), 9.0, np.float32)
            slot_ids = np.asarray(order + [S], np.int32)
            q_start = np.full(len(order) + 1, T, np.int32)
            q_len, first = (np.zeros(len(order) + 1, np.int32)
                            for _ in range(2))
            tok_seq = np.full(T, len(order), np.int32)
            read, at = {}, 0
            for row, s in enumerate(order):
                n = arg[s]
                q_start[row], q_len[row], first[row] = at, n, pos[s] == 0
                z[at:at + n] = seq(s, request[s])[0][pos[s]:pos[s] + n]
                tok_seq[at:at + n] = row
                read[s] = [(at + i, pos[s] + i) for i in range(n)]
                at += n
            live = [s for s in order if arg[s]]
            taps, conv = shortconv.taps_ragged(
                jnp.asarray(z), conv, layer, shortconv.ragged_plan(
                    S, *(jnp.asarray(a) for a in (
                        slot_ids, tok_seq, q_start, q_len, first))))
        after = np.asarray(conv)
        assert len(taps) == n_prev
        for s, tokens in read.items():
            want = seq(s, request[s])[1]
            for j, tap in enumerate(taps):
                for token, p in tokens:
                    assert (np.asarray(tap)[token] == want[j][p]).all(), (
                        kind, s, j, p)
            pos[s] += len(tokens)
        assert (after[0] == before[0]).all()  # the other layer's planes
        for s in range(S):
            if s not in live:
                assert (after[1, :, s] == before[1, :, s]).all(), (kind, s)
                continue
            zs = seq(s, request[s])[0]
            for i in range(n_prev):  # plane i: position pos - (K-1) + i
                p = pos[s] - n_prev + i
                assert (after[1, i, s] == (zs[p] if p >= 0 else 0)).all(), (
                    kind, s, i)


# --------------------------------------- logits, against the reference
CHUNKINGS = {
    "two_halves": (11, 12),
    "spans_of_1_and_2": (9, 1, 2, 1, 1, 2, 7),   # shorter than the window
    "token_by_token_start": (1, 1, 1, 2, 18),    # state opens on a 1-token row
    "one_span": (23,),
}


@pytest.mark.parametrize("chunks", CHUNKINGS.values(), ids=CHUNKINGS.keys())
def test_prefill_in_chunks_then_decode_matches_the_reference(chunks):
    chunks_then_decode(OLMO, make_params(OLMO), want, chunks)


def chunks_then_decode(mc, params, want, chunks, held=None):
    """A 23-token prompt in `chunks` on slot 1, then six decode passes: every
    logit read agrees with the reference. `held`: the shape of a step's
    expert loads, for a stack that has any."""
    # (Seeds: the output norm over a value head's 16 numbers makes a few
    # positions of a few sequences ill-conditioned at this size — the rule's
    # output there is a difference of a few terms a thousand times its size
    # — and there EVERY float32 path, the oracle forward_prefill too, lies
    # up to 1e-3 from the reference. These sequences have none.)
    toks = seq_tokens(5, 23 + 6)
    ref = want(mc, params, toks)
    st, at = state(mc, jnp.float32, garbage=3.0), 0
    for n in chunks:
        got, st, load = ragged_step(mc, params, st,
                                    [(1, toks[at:at + n], at)])
        at += n
        close(got[1], ref[at - 1])
        assert (load is None) if held is None else (load.shape == held)
    # slot 1 holds the state; the other slots kept the earlier request's
    for arr in by_slot(st[2]):
        assert bool(jnp.all(arr[:, jnp.array([0, 2, 3])] == 3.0))
    got, _ = decode_scan(mc, params, st, {1: (toks[23:], 23)}, active=[1])
    close(got[1], ref[23:])


def test_a_long_prompt_in_two_chunks_across_window_boundaries(monkeypatch):
    """(At this length a perturbation of 1e-7 moves the reference's own
    logits by up to 9e-4 at some positions: the positions read here are not
    among them.)"""
    a_span_across_windows(OLMO, make_params(OLMO), want, ATOL, monkeypatch)


def a_span_across_windows(mc, params, want, atol, monkeypatch):
    """150 tokens as 90 + 60 beside another row's 70: the rule's windows of
    64 are crossed inside a span, between spans and between rows."""
    import test_lfm2

    monkeypatch.setattr(test_lfm2, "MP", 24)  # 192 tokens a sequence
    toks, other = seq_tokens(4, 150), seq_tokens(6, 70)
    ref, ref_other = want(mc, params, toks), want(mc, params, other)
    st = state(mc, jnp.float32, garbage=1.5, pages=1 + B * 24)
    got, st, _ = ragged_step(mc, params, st, [(2, toks[:90], 0)],
                             pad_to=96)
    close(got[2], ref[89], atol=atol)
    got, st, _ = ragged_step(mc, params, st, [
        (0, other, 0), (2, toks[90:], 90)], pad_to=144)
    close(got[0], ref_other[69], atol=atol)
    close(got[2], ref[149], atol=atol)


def test_the_same_path_in_bfloat16_misses_the_tolerance():
    st = bfloat16_misses(OLMO, make_params(OLMO), want,
                         state(OLMO, jnp.bfloat16),
                         kept=("lin_A_log", "lin_dt_bias"))
    assert st[2].rule.dtype == jnp.float32  # the accumulator stays float32


def test_a_ragged_step_mixing_prefill_spans_with_decode_rows():
    def dense(load):
        assert load is None  # a dense stack

    mixed_step(OLMO, make_params(OLMO),
               state(OLMO, jnp.float32, garbage=-2.0), want, dense)


def test_the_fused_scan_beside_inactive_and_mid_prefill_slots():
    fused_scan(OLMO, make_params(OLMO), state(OLMO, jnp.float32, garbage=5.0),
               want, by_slot)


def test_the_published_32_layer_list_at_tiny_widths():
    full = MODEL_CONFIGS["olmo-hybrid:7b"]
    mc = dataclasses.replace(OLMO, name="olmo-32", num_layers=32,
                             layer_types=full.layer_types)
    assert [(f, len(p), n) for f, p, n in mc.layer_plan()] == [(0, 4, 8)]
    params = make_params(mc)
    # Every sublayer adds a vector of unit RMS times its norm's weight: with
    # random weights of size one, 32 such layers at these widths amplify a
    # perturbation of 1e-7 to 5e-2 in the REFERENCE's own logits, and no
    # float32 path can be held to 2e-4. Weights of a tenth keep the stack
    # well-conditioned; every layer still moves the logits by far more than
    # the tolerance.
    for name in ("attn_norm", "mlp_norm"):
        params["layers"][name] = 0.1 * params["layers"][name]
    toks = seq_tokens(2, 19)
    ref = want(mc, params, toks)
    st = state(mc, jnp.float32, garbage=1.0)
    _, st, _ = ragged_step(mc, params, st, [(0, toks[:10], 0)])
    got, st, _ = ragged_step(mc, params, st, [(0, toks[10:], 10)])
    close(got[0], ref[18])


def test_the_whole_sequence_forwards_agree_with_the_reference():
    """forward_prefill (the oracle: shifted copies, the chunked rule from an
    empty state) on two prompts of different lengths in one batch."""
    params = make_params(OLMO)
    a, b = seq_tokens(30, 70), seq_tokens(31, 9)
    tokens = np.zeros((2, 80), np.int32)
    tokens[0, :70], tokens[1, :9] = a, b
    kv = jnp.zeros((OLMO.count(ATTENTION), NP * PS, OLMO.kv_dim), jnp.float32)
    logits, _, _ = prefill(
        params, OLMO, jnp.asarray(tokens), jnp.asarray([70, 9], jnp.int32),
        kv, kv, jnp.zeros((2, 10), jnp.int32), PS)
    close(logits[0], want(OLMO, params, a)[69])
    close(logits[1], want(OLMO, params, b)[8])


def test_the_reference_keeps_the_filler_behind_a_sequence_from_it():
    keeps_the_filler_from_it(want, make_params(OLMO), olmo_hybrid_reference(),
                             olmo_hybrid_keys(OLMO), OLMO)


# ------------------------------------------- the Pallas step kernel
@pytest.mark.parametrize("heads,dk,dv,live", [
    (4, 8, 16, [1, 0, 1, 1, 0, 1]),    # the tiny model's: one group a row
    (4, 8, 64, [0, 0, 1, 0, 0, 0]),    # pairs of heads fill a lane tile
    (6, 8, 192, [1, 0, 1, 1, 0, 1]),   # the published value width: 384-lane groups
    (4, 8, 16, [0, 0, 0, 0, 0, 0]),    # no live row: nothing but the trash row moves
], ids=["tiny", "pairs", "dv192", "none_live"])
def test_the_step_kernel_in_interpret_mode_is_step(heads, dk, dv, live):
    step_kernel_is_step(heads, heads, dk, dv, live)


def step_kernel_is_step(key_heads, heads, dk, dv, live, strongest=2.0):
    """`gated_delta_step_pallas` in interpret mode against `gd.step` on the
    live rows, every other row and layer untouched, q and k at `key_heads`
    heads; returns (the rows' states, q, k, v, g, beta, reset, `gd.step`'s
    outputs) for what a caller adds (tests/test_qwen3_next_rule.py)."""
    from ollamamq_tpu.ops.pallas.gated_delta_step import (
        gated_delta_step_pallas, head_blocks)

    rng = np.random.default_rng(7)
    n, layers, rows = len(live), 3, 9
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    state0 = f(layers, rows, dk, heads * dv)
    slots = jnp.asarray([5, 8, 0, 3, 8, 7], jnp.int32)  # 8: the trash row
    live = jnp.asarray(live, bool)
    reset = jnp.asarray([0, 0, 1, 0, 0, 0], bool)
    q, k, v = f(n, key_heads, dk), f(n, key_heads, dk) + 1, f(n, heads, dv)
    g = -jnp.abs(f(n, heads)) * 0.3
    beta = jnp.asarray(rng.uniform(0, strongest, size=(n, heads)),
                       jnp.float32)
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
    return state0[1][slots], q, k, v, g, beta, reset, o_ref


def test_the_served_forwards_through_the_kernel_match_the_jnp_path():
    served_through_the_kernels(OLMO, make_params(OLMO), ATOL)


def _served_stream(mc, params):
    """One ragged stream with a one-token row, a span and a first span,
    after a step that left row 0 eleven tokens: (tok, forward_ragged's
    keyword arguments)."""
    seqs = {0: seq_tokens(40, 12), 1: seq_tokens(41, 30)}
    st = state(mc, jnp.float32, garbage=0.5)
    _, st, _ = ragged_step(mc, params, st, [(0, seqs[0][:11], 0)])
    kc, vc, slot_state = st
    tok = jnp.asarray(seqs[0][11:] + seqs[1] + [0], jnp.int32)
    seq = jnp.asarray([0] + [1] * 30 + [0], jnp.int32)
    pos = jnp.asarray([11] + list(range(30)) + [-1], jnp.int32)
    pt = jnp.asarray(page_table())
    slots = jnp.where(pos >= 0, pt[seq, jnp.maximum(pos, 0) // PS] * PS
                      + jnp.maximum(pos, 0) % PS, 0)
    return tok, dict(
        tok_seq=seq, tok_pos=pos, write_slots=slots,
        out_idx=jnp.asarray([0, 30, 0, 0]), k_cache=kc, v_cache=vc,
        page_table=pt, q_start=jnp.asarray([0, 1, 32, 32]),
        q_len=jnp.asarray([1, 30, 0, 0]), kv_len=jnp.asarray([12, 30, 0, 0]),
        page_size=PS, conv_state=slot_state,
        slot_ids=jnp.asarray([0, 1, B, B]), is_first=jnp.asarray([0, 1, 0, 0]))


def _forward_with(rule):
    """A jit of forward_ragged whose linear layers run `rule` in place of
    `gated_delta.ragged` (a function of its own: no trace is shared with
    the unpatched forward's)."""
    def forward(*args, **kw):
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(gd, "ragged", rule)
            return llama.forward_ragged(*args, **kw)

    return jax.jit(forward, static_argnums=1, static_argnames=(
        "page_size", "attn_impl", "interpret"))


def served_through_the_kernels(mc, params, atol):
    """forward_ragged with `attn_impl` pallas in interpret mode (the Pallas
    attention kernel, the step kernel and the chunked rule's pair kernel)
    against the jnp path: the logits end to end, and every linear layer's
    state and outputs through the kernels ON THE JNP PATH'S OWN INPUTS to
    that layer — a state compared end to end is compared through this toy
    stack's conditioning, which turns a last bit of the first layer's
    output into 1e-2 at the seventh's state (the test after this one)
    where the end-to-end comparison's bound was 5e-4."""
    tok, args = _served_stream(mc, params)
    apart, ragged = [], gd.ragged

    def both(q, k, v, g, beta, rows, layer, *meta, impl, interpret):
        o, new = ragged(q, k, v, g, beta, rows, layer, *meta)
        o_k, new_k = ragged(q, k, v, g, beta, rows, layer, *meta,
                            impl="pallas", interpret=True)
        jax.debug.callback(
            lambda *x: apart.append([float(d) for d in x]),
            jnp.max(jnp.abs(o_k - o)),
            jnp.max(jnp.abs(new_k[layer, :2] - new[layer, :2])))
        return o, new

    want_, *_ = _forward_with(both)(params, mc, tok, **args)
    forward = jax.jit(llama.forward_ragged, static_argnums=1, static_argnames=(
        "page_size", "attn_impl", "interpret"))  # (bare: an op at a time)
    got, *_ = forward(params, mc, tok, **args, attn_impl="pallas",
                      interpret=True)
    close(got[:2], np.asarray(want_[:2]), atol=atol)
    jax.effects_barrier()
    assert len(apart) == mc.count(LINEAR)
    close(np.asarray(apart)[:, 0], 0.0, atol=1e-5)
    close(np.asarray(apart)[:, 1], 0.0, atol=2e-5)  # (end to end: 5e-4)


def test_a_last_bit_of_the_first_layers_output_is_1e_2_at_the_seventh_state():
    """Why the kernels' states are held to the jnp path's a layer at a time
    (served_through_the_kernels): the jnp path against ITSELF with the
    first linear layer's outputs one ulp up. The second layer's state moves
    in its sixth digit, the seventh's (entries of 25) by more than 5e-4 —
    the bound a state compared end to end was held to while the linear
    layers ahead of the first attention layer ran the same XLA loop on
    both sides."""
    params = make_params(OLMO)
    tok, args = _served_stream(OLMO, params)

    def nudged(*a, impl, interpret):
        o, new = gd_ragged(*a)
        return jnp.where(a[6] == 0, jnp.nextafter(o, jnp.inf), o), new

    gd_ragged = gd.ragged
    *_, want = _forward_with(lambda *a, impl, interpret: gd_ragged(*a))(
        params, OLMO, tok, **args)
    *_, moved = _forward_with(nudged)(params, OLMO, tok, **args)
    apart = np.abs(np.asarray(moved.rule[:, :2])
                   - np.asarray(want.rule[:, :2])).max(axis=(1, 2, 3))
    assert apart[0] == 0.0 and 0.0 < apart[1] < 5e-5 and apart[6] > 5e-4, apart


# ------------------------------------------------- the engine, by id stream
NAME = "test-tiny-olmo-hybrid"


def _olmo_engine(**over):
    return _engine(NAME, **over)


@pytest.fixture(scope="module")
def hybrid():
    return _olmo_engine()


def test_overlapped_against_serial_gives_the_same_ids(hybrid, monkeypatch):
    """Six requests over four slots: spans of several lengths beside decode
    rows, the 32-token budget cuts prompts into chunks, slots free and are
    reused, fused k=4 scans between waves — pipelined and settled loops."""
    piped, settled, samples = both(hybrid, _arrivals(), monkeypatch)
    assert piped == settled
    assert {s["mode"] for s in samples} == {"ragged", "decode"}
    rt = _rt(hybrid)
    # the state is a pytree of two arrays: the window and the rule's
    assert rt.cache.slot_state.conv.shape == (8, 3, 4, 2 * 32 + 64)
    assert rt.cache.slot_state.rule.shape == (8, 5, DK, H * DV)
    assert rt.cache.slot_state.rule.dtype == jnp.float32
    assert rt.state_bytes["lin_state_bytes"] == 8 * 5 * DK * H * DV * 4
    assert rt.stats()["lin_state_bytes"] == 8 * 5 * DK * H * DV * 4
    # every launched step says what it did with the state, and uploads ONE
    # packed array
    assert all(s["h2d_transfers"] == 1 for s in samples)
    assert not any("conv_state_resets" in s for s in samples)
    ragged = [s for s in samples if s["mode"] == "ragged"]
    assert sum(s["lin_state_resets"] for s in ragged) == 6  # one a request
    assert sum(s["lin_state_carried"] for s in ragged) > 6  # later chunks
    assert sum(s["lin_span_tokens"] for s in ragged) \
        >= sum(len(p) for _, _, p, _ in _arrivals()) - 6
    assert any(s["lin_step_rows"] for s in ragged)  # decode rows in a wave
    for s in samples:
        if s["mode"] == "decode":  # a scan: its slots x its passes
            assert s["lin_state_resets"] == 0 and s["lin_span_tokens"] == 0
            assert s["lin_step_rows"] == s["lin_state_carried"] * s["k_cap"]


def test_a_reused_slot_gives_the_ids_a_fresh_engine_gives(monkeypatch):
    def holds_state(rt):  # slot 0 holds its state
        for left in jax.tree_util.tree_leaves(rt.cache.slot_state):
            assert np.abs(np.asarray(left)[:, 0]).max() > 0

    reused_slot(_olmo_engine, holds_state, monkeypatch)


def test_preempt_and_replay_gives_the_same_ids(hybrid, monkeypatch):
    preempted_and_replayed(hybrid, _olmo_engine, "lin_state_resets",
                           monkeypatch)


# ------------------------------- what else touches per-sequence state
@pytest.mark.parametrize("kw,match", [
    (dict(spec=True), "--spec: a rejected draft"),
    (dict(mesh_shape={"tensor": 2}),
     "--tp / --ep: the linear_attention layers"),
    (dict(mesh_shape={"expert": 2}),
     "--tp / --ep: the linear_attention layers"),
], ids=["spec", "tp", "ep"])
def test_features_that_know_only_the_kv_pool_are_refused(kw, match):
    err = refusal(OLMO, **kw)
    assert err and match in err and NAME in err
    assert "linear_attention layers (layer_types)" in err
    assert refusal(MODEL_CONFIGS["test-tiny"], **kw) is None
    assert refusal(OLMO, mesh_shape={"data": 2}) is None


def test_the_runtime_refuses_them_at_construction(caplog):
    with pytest.raises(ValueError, match="--spec"):
        _olmo_engine(spec=True, spec_k=3)
    with pytest.raises(ValueError, match="--tp / --ep"):
        _olmo_engine(tp=2)
    err = validate_quant_config("int8", "bfloat16", model_names=(NAME,))
    assert err and "int8" in err and NAME in err and "linear_attention" in err
    from ollamamq_tpu.models import weights
    with pytest.raises(ValueError, match="does not cover"):
        weights.quantize_params_int8(make_params(OLMO), OLMO)
    with caplog.at_level("WARNING"):
        rt = _rt(_olmo_engine(prefix_cache=True))
    assert rt.cache.prefix_cache is None
    assert any("prefix cache off" in r.message and NAME in r.message
               and "linear_attention" in r.message for r in caplog.records)


def test_migration_is_refused_not_served_without_the_state(hybrid):
    from ollamamq_tpu.engine.engine import MigrationError

    rt = _rt(hybrid)
    with pytest.raises(MigrationError, match="linear_attention layers' state"):
        rt.import_request({"kind": "stream"}, None)
    assert hybrid.export_prefix(NAME, _prompt(1, 40)) is None


def test_gauges_and_counters_size_a_deployment(hybrid, monkeypatch):
    from ollamamq_tpu.telemetry import schema as tm

    eng = hybrid
    _engine("test-tiny")

    def value(series, model):
        return next(c.value for labels, c in series.series()
                    if model in labels)

    # K and V of 2 attention layers of 4 x 16 lanes, float32 here
    assert value(tm.KV_BYTES_PER_TOKEN, NAME) == 2 * 2 * 64 * 4
    assert value(tm.HBM_LIN_STATE_BYTES, NAME) == 8 * 5 * DK * H * DV * 4
    assert value(tm.HBM_CONV_STATE_BYTES, NAME) == 8 * 3 * 4 * 128 * 4
    assert value(tm.HBM_LIN_STATE_BYTES, "test-tiny") == 0
    counters = (tm.LIN_STATE_RESETS_TOTAL, tm.LIN_STATE_CARRIED_TOTAL,
                tm.LIN_STEP_ROWS_TOTAL, tm.LIN_SPAN_TOKENS_TOTAL,
                tm.LIN_CHUNK_PAIRS_TOTAL)
    before = [value(c, NAME) for c in counters]
    _, samples = drive(eng, _arrivals(n=2), False, monkeypatch)
    after = [value(c, NAME) for c in counters]
    for i, field in enumerate(("lin_state_resets", "lin_state_carried",
                               "lin_step_rows", "lin_span_tokens",
                               "lin_chunk_pairs")):
        assert after[i] - before[i] == sum(s[field] for s in samples) > 0
    # a span of n tokens is in n / 64 windows at least, n // 64 + 2 at most
    for s in samples:
        spans = s["lin_state_resets"] + s["lin_state_carried"] \
            - s["lin_step_rows"] if s["mode"] == "ragged" else 0
        assert -(-s["lin_span_tokens"] // 64) <= s["lin_chunk_pairs"] \
            <= s["lin_span_tokens"] // 64 + 2 * spans


def test_a_steps_rows_lie_in_stream_order(hybrid, monkeypatch):
    """What the pair kernel needs of a step (`gated_delta.ragged`'s
    precondition; the XLA loop does not): the engine lays a step's rows out
    in row order, so the spans' windows do not decrease along the pairs —
    on every ragged step of a run whose steps hold several spans."""
    rt, seen = _rt(hybrid), []

    def spy(T_pad, k_cap, buf, _orig=rt._dispatch_ragged):
        lay = rt.dims.ragged_layout(T_pad)
        seen.append([lay.view(buf, name).copy()
                     for name in ("q_start", "q_len")])
        return _orig(T_pad, k_cap, buf)

    monkeypatch.setattr(rt, "_dispatch_ragged", spy)
    drive(hybrid, _arrivals(), False, monkeypatch)
    assert max((n > 1).sum() for _, n in seen) > 1
    for start, n in seen:
        assert np.all(np.diff(start) >= 0), start
        assert np.array_equal(start[1:][n[1:] > 0],
                              np.cumsum(n)[:-1][n[1:] > 0])
