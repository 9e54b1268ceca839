"""Phi-4-mini-flash (a decoder-hybrid-decoder stack: Mamba-1 selective-scan
and window layers, ONE full-attention layer whose cached K/V the cross layers
read, gated memory units that reuse one layer's scan output, differential
attention, rows nobody samples stopping below the gated memory units) on the
served path, held to its plain float32 reference.

The reference is the benchmark's (`benchmarks/reference/phi4_flash_decoder.py`):
one sequence, a Python loop over ALL the layers for EVERY position, the window
as a mask, differential attention as four plain softmax-times-V products a
pair, the convolution over shifted copies plus the bias, the scan token by
token. The system's side is the real thing: `forward_ragged` over a prompt in
chunks — the sampled rows alone passing the upper layers — then decode passes,
through the one pool layer, the rings, the conv window and the float32 scan
state. LOGITS are compared, not sampled ids, in float32: two orders of
summation (pages, rings, chunks and carried state against one dense pass)
differ by ~8e-6 of logits whose spread is ~1 (measured: 3.3e-6 to 8.3e-6), so
ATOL 2e-4, twenty-five times that; the same path with the scan state rounded
to bfloat16 between passes misses it (asserted), and so does every ablation
below.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ollamamq_tpu.config import (ATTENTION, CROSS, GMU, MAMBA, MODEL_CONFIGS,
                                 WINDOW, ModelConfig, hybrid_layer_types)
from ollamamq_tpu.engine.kv_cache import refusal
from ollamamq_tpu.models import llama
from ollamamq_tpu.ops import selective_scan as s6
from ollamamq_tpu.ops.attention import causal_attention
from ollamamq_tpu.ops.pallas.s6_step import s6_step_pallas
from testutil import _reference, span_stream

NAME = "test-tiny-phi4-flash"
PHI = MODEL_CONFIGS[NAME]
PS, MP, B, PAD = 8, 12, 4, 32  # page size, pages a row, rows, a step's rung
RING = PHI.ring_rows(PAD, PS)
ATOL = 2e-4
N, DI = 16, PHI.s6_inner
TOKENS = np.random.default_rng(7).integers(3, 512, size=70).tolist()


def ref():
    return _reference("phi4_flash_decoder")


def keys(mc=PHI, **edit) -> dict:
    """What a configuration file says of the ModelConfig `mc`: all that the
    reference reads."""
    return {"num_hidden_layers": mc.num_layers, "hidden_size": mc.hidden_size,
            "intermediate_size": mc.intermediate_size,
            "num_attention_heads": mc.num_heads,
            "num_key_value_heads": mc.num_kv_heads, "head_dim": mc.head_dim,
            "layer_norm_eps": mc.layer_norm_eps,
            "sliding_window": mc.sliding_window,
            "layer_types": list(mc.layer_types), "mb_per_layer": 2,
            "tie_word_embeddings": True, **edit}


@pytest.fixture(scope="module")
def params():
    return jax.jit(lambda k: llama.init_params(PHI, k, dtype=jnp.float32))(
        jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def want(params):
    """The reference's ONE full forward over TOKENS: [T, V] logits."""
    return np.asarray(ref().logits(keys(), params,
                                   jnp.asarray(TOKENS, jnp.int32)))


def page_table():
    return (1 + np.arange(B)[:, None] * MP + np.arange(MP)[None, :]).astype(
        np.int32)


def state(garbage=0.0):
    """(kc, vc, SlotState): ONE empty pool layer, and per-slot state that an
    earlier request left full of `garbage`."""
    kv = jnp.zeros((PHI.cache_layers, (1 + B * MP) * PS, PHI.kv_dim),
                   jnp.float32)
    st = llama.alloc_slot_state(PHI, B, jnp.float32, ring_rows=RING)
    return kv, kv, jax.tree_util.tree_map(
        lambda a: a + jnp.asarray(garbage, a.dtype), st)


def _ragged(early_exit=True, impl="jnp"):
    def run(p, kc, vc, slot, tok, seq, pos, slots, out_idx, q_start, q_len,
            kv_len, slot_ids, first, emits):
        return llama.forward_ragged(
            p, PHI, tok, seq, pos, slots, out_idx, kc, vc,
            jnp.asarray(page_table()), q_start, q_len, kv_len, PS,
            attn_impl=impl, interpret=impl == "pallas", conv_state=slot,
            slot_ids=slot_ids, is_first=first, emits=emits,
            early_exit=early_exit)

    return jax.jit(run)


_ragged_jit = functools.lru_cache(maxsize=None)(_ragged)


def ragged_step(params, st, spans, emits=None, jit=None, **kw):
    """One `forward_ragged` over `spans` = [(row, tokens, start position)],
    padded to PAD; rows without a span are padding rows (the trash slot).
    Row r serves slot r; a span that starts at position 0 is its request's
    first; `emits`: the rows whose logits are sampled (default: all)."""
    stream, (q_start, q_len, kv_len) = span_stream(spans, PAD, page_table(),
                                                   PS)
    slot_ids = np.where(q_len > 0, np.arange(B), B).astype(np.int32)
    first = ((q_len > 0) & (kv_len == q_len)).astype(np.int32)
    emit = np.asarray([q_len[r] > 0 and (emits is None or r in emits)
                       for r in range(B)], np.int32)
    out_idx = np.clip(q_start + q_len - 1, 0, PAD - 1)
    logits, kc, vc, slot = (jit or _ragged_jit(**kw))(
        params, *st, *stream, out_idx, q_start, q_len, kv_len, slot_ids,
        first, emit)
    return {row: np.asarray(logits[row]) for row, _, _ in spans}, \
        (kc, vc, slot)


@functools.lru_cache(maxsize=None)
def _decode_jit():
    def run(p, kc, vc, slot, tok, pos, table, act):
        return llama.forward_decode(p, PHI, tok, pos, kc, vc, table, PS,
                                    active=act, conv_state=slot)

    return jax.jit(run)


def decode(params, st, row, tokens, first_pos, between=None):
    """Decode passes for `row`, teacher-forced; every other row is parked
    with garbage tokens. `between(state)`: what happens to the per-slot state
    from one pass to the next. Returns ([k, V] logits, state')."""
    act = np.zeros(B, np.int32)
    act[row] = 1
    table = np.where(act[:, None] > 0, page_table(), 0).astype(np.int32)
    out = []
    for i, t in enumerate(tokens):
        toks = np.full(B, 7, np.int32)
        toks[row] = t
        logits, *st = _decode_jit()(params, *st, toks,
                                    np.full(B, first_pos + i, np.int32),
                                    table, act)
        if between is not None:
            st[2] = between(st[2])
        out.append(np.asarray(logits[row]))
    return np.stack(out), tuple(st)


# ----------------------------------------- the served path against the reference
def _chunks_then_decode(params, garbage=0.0, between=None):
    """TOKENS through row 1: three chunks (the last emits), one ragged
    one-token step, then decode passes."""
    st, got = state(garbage), {}
    for lo, hi in ((0, 30), (30, 41), (41, 60)):
        out, st = ragged_step(params, st, [(1, TOKENS[lo:hi], lo)],
                              emits=(1,) if hi == 60 else ())
    got[59] = out[1]
    out, st = ragged_step(params, st, [(1, TOKENS[60:61], 60)])
    got[60] = out[1]
    logits, st = decode(params, st, 1, TOKENS[61:67], 61, between)
    got.update({61 + i: row for i, row in enumerate(logits)})
    return got, st


def test_prefill_in_chunks_then_decode_through_pool_rings_and_state(
        params, want):
    """Chunks longer than the window (16) and shorter, spans of several
    windows of the scan's 16-token stretch; the context outgrows the window
    before decode starts."""
    got, st = _chunks_then_decode(params)
    for at, logits in got.items():
        np.testing.assert_allclose(logits, want[at], atol=ATOL, rtol=0)
    kc, _, slot = st
    assert slot.scan is not None and slot.rule is None
    assert kc.shape[0] == 1  # ONE pool layer under 4 attention layers
    assert slot.scan.shape == (PHI.count(MAMBA), B + 1, N, DI)
    assert slot.conv.shape == (PHI.count(MAMBA), 3, B, DI)
    assert slot.ring.k.shape == (PHI.count(WINDOW), (B + 1) * RING,
                                 PHI.kv_dim)


def test_a_reused_slot_opens_at_zero(params, want):
    """State an earlier request left in the slot — scan state, conv window,
    rings — does not reach the next one: `is_first` opens it in the program."""
    got, _ = _chunks_then_decode(params, garbage=3.0)
    for at, logits in got.items():
        np.testing.assert_allclose(logits, want[at], atol=ATOL, rtol=0)


def test_a_bfloat16_scan_state_misses_the_tolerance(params, want):
    """The float32 limit is tight enough to see the state's precision: the
    same passes with the scan state rounded to bfloat16 between them."""
    def rounded(slot):
        return slot._replace(scan=slot.scan.astype(jnp.bfloat16).astype(
            jnp.float32))

    got, _ = _chunks_then_decode(params, between=rounded)
    miss = max(float(np.abs(got[at] - want[at]).max()) for at in (64, 65, 66))
    assert miss > 3 * ATOL, miss


def test_a_ragged_step_of_mixed_spans(params, want):
    """A decode row beside two prefill spans in ONE stream, a padding row;
    rows continue their OWN state."""
    st = state()
    _, st = ragged_step(params, st, [(0, TOKENS[:20], 0)], emits=())
    out, st = ragged_step(params, st, [(0, TOKENS[20:21], 20),
                                       (2, TOKENS[:17], 0),
                                       (3, TOKENS[:9], 0)], emits=(0, 2))
    np.testing.assert_allclose(out[0], want[20], atol=ATOL, rtol=0)
    np.testing.assert_allclose(out[2], want[16], atol=ATOL, rtol=0)


# ------------------------------------------------------------ the early exit
def test_sampled_rows_are_those_of_a_forward_that_runs_every_row(params):
    """The exit is the mathematics: the sampled rows' logits with the upper
    layers run on those rows alone equal those of a forward that runs every
    row of the stream through every layer (the cross layers then on the
    ragged kernel's shape)."""
    spans = [(0, TOKENS[:21], 0), (2, TOKENS[5:14], 0)]
    short, _ = ragged_step(params, state(), spans)
    whole, _ = ragged_step(params, state(), spans, early_exit=False)
    for row in (0, 2):
        np.testing.assert_allclose(short[row], whole[row], atol=2e-5, rtol=0)


def test_a_row_that_does_not_emit_reads_no_cached_row(params, monkeypatch):
    """A request's non-final chunk and a padding row are handed to the cross
    layers with a context of 0 — and the lowered step holds one cross launch
    a cross layer, on the DECODE shape, whatever the stream holds."""
    seen = []

    def spy(impl, q, kc, vc, layer, table, ctx_len, page_size, interp=False):
        jax.debug.callback(lambda c: seen.append(np.asarray(c)), ctx_len)
        assert q.shape == (B, PHI.num_heads, 2 * PHI.head_dim)
        return jnp.zeros_like(q)

    monkeypatch.setattr(llama, "_cross_attend", spy)
    ragged_step(params, state(), [(0, TOKENS[:20], 0), (1, TOKENS[:9], 0)],
                emits=(1,), jit=_ragged())
    jax.effects_barrier()
    assert len(seen) == PHI.count(CROSS)
    assert all(c.tolist() == [0, 9, 0, 0] for c in seen)


def test_the_zero_padded_query_form_is_the_four_product_form():
    """`pair_queries` + the plain GQA attention at double-width heads +
    `_diff_combine` against the published form written out: four softmax-
    times-V products a pair on head_dim-lane heads."""
    rng = np.random.default_rng(3)
    T, H, Hk, hd, depth = 12, 8, 4, 16, 5
    q, k, v = (jnp.asarray(rng.standard_normal((1, T, h, hd)), jnp.float32)
               for h in (H, Hk, Hk))
    lam = jnp.asarray(0.3 * rng.standard_normal((4, hd)), jnp.float32)
    w = jnp.asarray(1 + 0.1 * rng.standard_normal(2 * hd), jnp.float32)
    lens = jnp.asarray([T])
    attn = causal_attention(llama.pair_queries(q),
                            k.reshape(1, T, Hk // 2, 2 * hd),
                            v.reshape(1, T, Hk // 2, 2 * hd), lens)
    got = llama._diff_combine(attn, lam, w, depth, 1e-5)

    def product(qh, kh, vh):  # one head each
        return causal_attention(q[:, :, qh:qh + 1], k[:, :, kh:kh + 1],
                                v[:, :, vh:vh + 1], lens)[:, :, 0]

    first = 0.8 - 0.6 * np.exp(-0.3 * depth)
    full = float(np.exp(np.sum(lam[0] * lam[1]))
                 - np.exp(np.sum(lam[2] * lam[3])) + first)
    for p in range(H // 2):
        kp = p // (H // Hk)
        a1 = jnp.concatenate([product(2 * p, 2 * kp, 2 * kp),
                              product(2 * p, 2 * kp, 2 * kp + 1)], -1)
        a2 = jnp.concatenate([product(2 * p + 1, 2 * kp + 1, 2 * kp),
                              product(2 * p + 1, 2 * kp + 1, 2 * kp + 1)], -1)
        d = a1 - full * a2
        d = d / np.sqrt(np.mean(np.square(d), -1, keepdims=True) + 1e-5)
        np.testing.assert_allclose(got[:, :, p], d * w * (1 - first),
                                   atol=1e-5, rtol=0)


# ----------------------------------------------------------------- the scan
def _operands(seed, rows):
    rng = np.random.default_rng(seed)
    dt = jnp.asarray(np.exp(rng.uniform(-5, -1, (rows, DI))), jnp.float32)
    x = jnp.asarray(rng.standard_normal((rows, DI)), jnp.float32)
    b, c = (jnp.asarray(rng.standard_normal((rows, N)), jnp.float32)
            for _ in range(2))
    a = -jnp.exp(jnp.asarray(rng.uniform(0, 2.5, (N, DI)), jnp.float32))
    return dt, x, b, c, a


def _serial(dt, x, b, c, a, s):
    ys = []
    for t in range(x.shape[0]):
        s = np.exp(dt[t][None, :] * a) * s \
            + b[t][:, None] * (dt[t] * x[t])[None, :]
        ys.append((s * c[t][:, None]).sum(0))
    return np.stack(ys), s


def test_ragged_and_decode_continue_each_rows_own_state():
    """`ragged` — one-token rows through `step`, a 37-token span through the
    stretches of 16, padding between — and `decode` against the recurrence as
    written, token by token, from each row's own state."""
    dt, x, b, c, a = _operands(0, 48)
    st = jnp.asarray(np.random.default_rng(1).standard_normal(
        (2, B + 1, N, DI)), jnp.float32)
    # the stream: row 0 one token at 0; row 2 a span at 3..39; row 1 one
    # token at 44; padding elsewhere
    q_start, q_len = np.array([0, 44, 3, 48]), np.array([1, 1, 37, 0])
    tok_seq, tok_pos = np.zeros(48, np.int32), np.full(48, -1, np.int32)
    for r in range(3):
        tok_seq[q_start[r]:q_start[r] + q_len[r]] = r
        tok_pos[q_start[r]:q_start[r] + q_len[r]] = 5 + np.arange(q_len[r])
    first = np.array([0, 1, 1, 0])
    y, new = jax.jit(functools.partial(s6.ragged, layer=1))(
        dt, x, b, c, a, st, slot_ids=jnp.asarray([0, 1, 2, B]),
        tok_seq=jnp.asarray(tok_seq), tok_pos=jnp.asarray(tok_pos),
        q_start=jnp.asarray(q_start), q_len=jnp.asarray(q_len),
        is_first=jnp.asarray(first))
    args = [np.asarray(v, np.float64) for v in (dt, x, b, c, a)]
    for r in range(3):
        at = slice(q_start[r], q_start[r] + q_len[r])
        s0 = np.zeros((N, DI)) if first[r] else np.asarray(st[1, r])
        wy, ws = _serial(*(v[at] for v in args[:4]), args[4], s0)
        np.testing.assert_allclose(y[at], wy, atol=2e-5, rtol=1e-5)
        np.testing.assert_allclose(new[1, r], ws, atol=2e-5, rtol=1e-5)
    np.testing.assert_array_equal(new[0], st[0])  # the other layer
    np.testing.assert_array_equal(new[1, 3], st[1, 3])  # a slot no row serves
    # decode: active slots advance, a parked slot keeps its state
    yd, newd = s6.decode(dt[:B], x[:B], b[:B], c[:B], a, st, 0,
                         jnp.asarray([1, 0, 1, 1]))
    for r in (0, 2, 3):
        wy, ws = _serial(*(v[r:r + 1] for v in args[:4]), args[4],
                         np.asarray(st[0, r]))
        np.testing.assert_allclose(yd[r], wy[0], atol=2e-5, rtol=1e-5)
        np.testing.assert_allclose(newd[0, r], ws, atol=2e-5, rtol=1e-5)
    np.testing.assert_array_equal(newd[0, 1], st[0, 1])
    assert not np.asarray(yd[1]).any()


def test_the_kernel_in_interpret_mode_is_step():
    """`s6_step_pallas` (live rows first, a parked row untouched, a reset
    row opened at zero) against `step` on the gathered rows."""
    dt, x, b, c, a = _operands(2, B)
    st = jnp.asarray(np.random.default_rng(4).standard_normal(
        (2, B + 1, N, DI)), jnp.float32)
    slots = jnp.asarray([2, 0, 3, 1])
    live, reset = jnp.asarray([1, 0, 1, 1], bool), jnp.asarray([0, 0, 1, 0],
                                                              bool)
    y, new = s6_step_pallas(st, 1, slots, live, reset, dt, x, b, c, a,
                            interpret=True)
    wy, ws = s6.step(st[1][slots], dt, x, b, c, a, reset)
    for r in (0, 2, 3):
        np.testing.assert_allclose(y[r], wy[r], atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(new[1, slots[r]], ws[r], atol=1e-5,
                                   rtol=1e-5)
    assert not np.asarray(y[1]).any()
    np.testing.assert_array_equal(new[1, 0], st[1, 0])  # the parked row's
    np.testing.assert_array_equal(new[0], st[0])


# ----------------------------------- wrong forwards MUST miss the tolerance
def _depths(kinds, *which):
    return [i for i, k in enumerate(kinds) if k in which]


def _scaled(params, name, depths):
    """`name`'s entries divided by (1 - lambda_init) of their layers: the
    forward that leaves that factor out, as weights."""
    first = 0.8 - 0.6 * np.exp(-0.3 * np.asarray(depths, np.float32))
    return params["layers"][name] / (1.0 - first)[:, None]


def _wrong_params(params, name):
    lp = dict(params["layers"])
    kinds = list(PHI.layer_types)
    if name == "one_minus_lambda_init":
        lp["diff_norm"] = _scaled(params, "diff_norm",
                                  _depths(kinds, WINDOW, ATTENTION))
        lp["xdiff_norm"] = _scaled(params, "xdiff_norm",
                                   _depths(kinds, CROSS))
    else:
        for stack in name.split("+"):
            lp[stack] = jnp.zeros_like(lp[stack])
    return dict(params, layers=lp)


WRONG_WEIGHTS = ("one_minus_lambda_init", "s6_D", "s6_conv_b", "bq+xbq",
                 "bo+xbo", "attn_norm_b", "mlp_norm_b")


@pytest.mark.parametrize("name", WRONG_WEIGHTS)
def test_a_forward_without_one_of_its_terms_misses_the_tolerance(
        name, params, want):
    """Each case leaves ONE thing of the family out of the reference's
    forward, as weights (a zeroed bias, the skip D, the (1 - lambda_init)
    factor folded out of the pair norm): its logits leave the served path's
    (which agree with the whole reference) by far more than the tolerance."""
    wrong = np.asarray(ref().logits(keys(), _wrong_params(params, name),
                                    jnp.asarray(TOKENS[:40], jnp.int32)))
    miss = float(np.abs(wrong[39] - want[39]).max())
    assert miss > 10 * ATOL, (name, miss)


def _cross_with_its_own_kv(params, monkeypatch):
    """A cross layer that computes K and V from its OWN input (through the
    full layer's projections) and not from what the full layer cached."""
    r = ref()
    plain, lp = r._layer, params["layers"]
    full = sum(k in (WINDOW, ATTENTION) for k in PHI.layer_types) - 1

    def layer(w, x, m, cached, depth, kind, **kw):
        if kind == r.CROSS:
            w = dict(w, **{n: lp[n][full] for n in ("wk", "wv", "bk", "bv")})
            kind = r.FULL
        return plain(w, x, m, cached, depth, kind=kind, **kw)

    monkeypatch.setattr(r, "_layer", layer)
    return r, {}


WRONG_FORWARDS = {
    "no_lambda_subtraction": lambda p, mp: (ref(), dict(wrong="no_lambda")),
    "window_off_by_one": lambda p, mp: (ref(), dict(
        cfg=keys(sliding_window=PHI.sliding_window + 1))),
    "cross_layer_with_its_own_kv": _cross_with_its_own_kv,
}


@pytest.mark.parametrize("name", sorted(WRONG_FORWARDS))
def test_a_wrong_reference_forward_misses_the_tolerance(name, params, want,
                                                        monkeypatch):
    r, kw = WRONG_FORWARDS[name](params, monkeypatch)
    toks = jnp.asarray(TOKENS[:40], jnp.int32)
    h = r.hidden(kw.pop("cfg", keys()), params, toks, **kw)
    wrong = np.asarray(r.head_logits(keys(), params, h))
    miss = float(np.abs(wrong[39] - want[39]).max())
    assert miss > 10 * ATOL, (name, miss)


def _norm_a_head(attn, lam, w, depth, eps):
    """The pair norm over each head_dim-lane half and not the pair's lanes."""
    B_, T, H, lanes = attn.shape
    a = attn.astype(jnp.float32).reshape(B_, T, H // 2, 2, lanes)
    first = llama.lambda_init(depth)
    full = jnp.exp(jnp.sum(lam[0] * lam[1])) \
        - jnp.exp(jnp.sum(lam[2] * lam[3])) + first
    d = (a[..., 0, :] - full * a[..., 1, :]).reshape(B_, T, H // 2, 2, -1)
    d = d * jax.lax.rsqrt(jnp.mean(d * d, axis=-1, keepdims=True) + eps)
    return (d.reshape(B_, T, H // 2, lanes) * w * (1.0 - first)).astype(
        attn.dtype)


def _m_after_the_gate(plain):
    def op(cfg, lp, h, taps_fn, s6_fn):
        out, y = plain(cfg, lp, h, taps_fn, s6_fn)
        z = llama.qeinsum("btd,de->bte", h, lp["s6_in"])[..., cfg.s6_inner:]
        return out, y * jax.nn.silu(z)
    return op


@pytest.mark.parametrize("name", ["norm_a_head", "m_after_the_gate"])
def test_a_wrong_program_forward_misses_the_tolerance(name, params, want,
                                                      monkeypatch):
    """...and the two the reference has no switch for, out of the PROGRAM's
    forward: the pair norm over 64-lane halves, and m taken after the gate."""
    if name == "norm_a_head":
        monkeypatch.setattr(llama, "_diff_combine", _norm_a_head)
    else:
        monkeypatch.setattr(llama, "_mamba_op",
                            _m_after_the_gate(llama._mamba_op))
    out, _ = ragged_step(params, state(), [(0, TOKENS[:30], 0)],
                         jit=_ragged())
    miss = float(np.abs(out[0] - want[29]).max())
    assert miss > 10 * ATOL, (name, miss)


# ------------------------------------------------------------ the config
def test_the_stack_is_derived_and_what_is_not_implemented_is_refused():
    assert PHI.layer_types == hybrid_layer_types(8) == (
        MAMBA, WINDOW, MAMBA, WINDOW, MAMBA, ATTENTION, GMU, CROSS)
    assert [first for first, _, _ in PHI.layer_plan()] == [0, 4, 5, 6, 7]
    assert (PHI.cache_layers, PHI.attn_layers, PHI.exit_layer) == (1, 3, 6)
    assert PHI.rope_theta is None and PHI.attn_bias
    big = MODEL_CONFIGS["phi4-mini-flash:3.8b"]
    assert big.param_count() == 3_852_562_944
    assert [(first, len(period), n) for first, period, n
            in big.layer_plan()] == [(0, 2, 8), (16, 1, 1), (17, 1, 1),
                                     (18, 2, 7)]
    assert big.ring_rows(512, 32) == 1056 and big.state_window == (4, 5120)
    leaves = jax.eval_shape(lambda: llama.init_params(
        big, jax.random.PRNGKey(0)))
    assert sum(x.size for x in jax.tree_util.tree_leaves(leaves)) \
        == big.param_count()
    for edit, match in ((dict(mb_per_layer=1), "mb_per_layer"),
                        (dict(num_layers=10), "num_hidden_layers"),
                        (dict(mlp_bias=True), "mlp_bias"),
                        (dict(lm_head_bias=True), "lm_head_bias"),
                        (dict(embd_pdrop=0.1), "embd_pdrop"),
                        (dict(tie_embeddings=False), "tied head"),
                        (dict(layer_types=(MAMBA,) * 8), "layer_types"),
                        (dict(mb_per_layer=0, sliding_window=0),
                         "layer_norm_eps")):
        with pytest.raises(ValueError, match=match):
            dataclasses.replace(PHI, **edit)
    with pytest.raises(ValueError, match="layer_types holds"):
        ModelConfig(name="x", vocab_size=64, hidden_size=32,
                    intermediate_size=64, num_layers=2, num_heads=2,
                    num_kv_heads=2, head_dim=16, layer_types=(MAMBA, GMU))


@pytest.mark.parametrize("kw,match", [
    (dict(spec=True), "--spec"), (dict(mesh_shape={"tensor": 2}), "--tp"),
    (dict(kv_dtype="int8"), "--kv-dtype int8"),
    (dict(prefix_cache=True), "--prefix-cache")])
def test_features_that_know_only_the_kv_pool_are_refused(kw, match):
    err = refusal(PHI, **kw)
    assert err and match in err and NAME in err and "ROADMAP B-M9" in err
    assert refusal(PHI, mesh_shape={"data": 2}) is None


def test_a_verify_span_and_the_last_hiddens_are_refused_by_name(params):
    args = [jnp.zeros(8, jnp.int32)] * 4
    kv = jnp.zeros((1, 16, PHI.kv_dim), jnp.float32)
    rows = [jnp.zeros(B, jnp.int32)] * 3
    for kw in (dict(out_idx=jnp.zeros((B, 2), jnp.int32)),
               dict(out_idx=jnp.zeros(B, jnp.int32), hidden=True)):
        out_idx = kw.pop("out_idx")
        with pytest.raises(ValueError, match="mb_per_layer"):
            llama.forward_ragged(params, PHI, *args, out_idx, kv, kv,
                                 jnp.zeros((B, 2), jnp.int32), *rows, PS,
                                 **kw)
    with pytest.raises(ValueError, match="forward_prefill has no form"):
        llama.forward_prefill(params, PHI, jnp.zeros((1, 8), jnp.int32),
                              jnp.asarray([8]), kv, kv,
                              jnp.zeros((1, 2), jnp.int32), PS)


# ------------------------------------------------- the engine, by id stream
def test_the_engine_serves_it_and_counts_what_the_exit_saves(monkeypatch):
    """Six requests over four slots through `mq_ragged_step` and
    `mq_decode_scan`: pipelined and settled loops give the same ids, and the
    step samples say what the scan, the windows and the early exit did."""
    from ollamamq_tpu.ops.sampling import SamplingParams
    from test_step_overlap import _engine, _prompt, _rt, both

    eng = _engine(NAME)
    lens = (5, 40, 9, 23, 14, 31)
    arrivals = [(2 * i, f"u{i}", _prompt(i, lens[i]),
                 SamplingParams(max_tokens=9 + 2 * i)) for i in range(6)]
    piped, settled, samples = both(eng, arrivals, monkeypatch)
    assert piped == settled
    assert {s["mode"] for s in samples} == {"ragged", "decode"}
    rt = _rt(eng)
    assert rt.cache.slot_state.scan is not None
    assert rt.cache.kc.shape[0] == 1
    held = rt.state_bytes
    assert held["s6_state_bytes"] == PHI.count(MAMBA) * 5 * N * DI * 4
    assert rt.stats()["s6_state_bytes"] == held["s6_state_bytes"]
    assert held["ssm_state_bytes"] == held["lin_state_bytes"] == 0
    assert held["swa_ring_bytes"] > 0
    ragged = [s for s in samples if s["mode"] == "ragged"]
    assert sum(s["s6_state_resets"] for s in ragged) == 6  # one a request
    assert sum(s["s6_span_tokens"] for s in ragged) >= sum(lens) - 6
    assert all("swa_pairs" in s and "attn_pairs" in s for s in samples)
    for s in samples:
        if s["mode"] == "ragged":  # (a scan's `tokens`: what it emitted)
            assert s["xattn_rows"] + s["exit_skipped_tokens"] == s["tokens"]
        else:  # a scan: its slots x its passes
            assert s["exit_skipped_tokens"] == 0 and s["s6_span_tokens"] == 0
            assert s["s6_step_rows"] == s["xattn_rows"] \
                == s["s6_state_carried"] * s["k_cap"]
    # a 40-token prompt under a 32-token budget: its first chunk emits
    # nothing, so every token of it stops below the exit
    assert any(s["xattn_rows"] == 0 or s["exit_skipped_tokens"] > 20
               for s in ragged)
    with pytest.raises(ValueError, match="--spec"):
        _engine(NAME, spec=True)
    assert rt.export_request(0) is None  # migration: refused, not served
