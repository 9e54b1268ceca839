"""LFM2 (gated short convolutions with a per-slot state beside the paged KV
pool, a stack whose layers differ, a sigmoid router with a selection bias) on
the served path, held to its plain float32 reference.

The reference is the benchmark's (`benchmarks/reference/lfm2_decoder.py`): one
sequence, a Python loop over `layer_types`, dense causal attention, the
convolution over shifted copies of z (no state), every expert computed for
every token. The system's side is the real thing: `forward_ragged` over a
prompt in chunks, then decode passes, through the paged pool and the conv
state. LOGITS are compared, not sampled ids (with random weights the largest
logit changes on rounding), in float32: two orders of summation (pages and
chunks and carried state against one dense pass; grouped against per-expert
matmuls) differ by ~1e-5 of logits whose spread is ~1, so 2e-4; the same path
in bfloat16 misses by ~1e-2 (asserted). The engine-level cases compare id
streams of the SAME programs under different schedules: bit-identical.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ollamamq_tpu.config import (ATTENTION, CONV, EXPERTS, MODEL_CONFIGS,
                                 validate_quant_config)
from ollamamq_tpu.engine.kv_cache import refusal
from ollamamq_tpu.models import llama, moe
from ollamamq_tpu.ops import shortconv
from ollamamq_tpu.ops.sampling import SamplingParams
from ollamamq_tpu.testing.faults import FaultPlan
from test_step_overlap import _engine, _prompt, _rt, both, drive
from testutil import (embed, lfm2_keys, lfm2_reference, once_a_sequence,
                      prefill, seeded_params, span_stream, whole_blocks)

LFM2 = MODEL_CONFIGS["test-tiny-lfm2"]
PS, MP, NP, B = 8, 8, 40, 4  # page size, pages a sequence / in the pool, rows
ATOL = 2e-4


def make_params(mc, dtype=jnp.float32, seed=0):
    return seeded_params(mc, ("q_norm", "k_norm", "attn_norm", "mlp_norm"),
                         dtype, seed)


def state(mc, dtype, garbage=0.0):
    """(kc, vc, SlotState): empty pools and a conv state that an earlier
    request left full of `garbage`."""
    kv = jnp.zeros((mc.count(ATTENTION), NP * PS, mc.kv_dim), dtype)
    conv = shortconv.alloc_state(mc.count(CONV), B, mc.conv_L_cache,
                                 mc.hidden_size, dtype)
    return kv, kv, llama.SlotState(conv + jnp.asarray(garbage, dtype))


def page_table(mp=None):
    mp = mp or MP  # (the module's, as it stands at the call)
    pt = np.zeros((B, mp), np.int32)  # page 0: the trash page
    for row in range(B):
        pt[row] = 1 + row * mp + np.arange(mp)
    return pt


@functools.cache
def _ragged_jit(mc, impl):
    """ONE jitted `forward_ragged` a (config, kernel path): what a step is
    made of comes in as arguments, so a second step of the same shapes
    compiles nothing."""
    def run(p, kc, vc, conv, tok, seq, pos, slots, out_idx, pt, q_start,
            q_len, kv_len, slot_ids, first):
        return llama.forward_ragged(
            p, mc, tok, seq, pos, slots, out_idx, kc, vc, pt, q_start, q_len,
            kv_len, PS, attn_impl=impl, interpret=impl == "pallas",
            moe_load=True, conv_state=conv, slot_ids=slot_ids, is_first=first)

    return jax.jit(run)


def ragged_step(mc, params, st, spans, pad_to=32, mp=None, impl="jnp"):
    """One `forward_ragged` over `spans` = [(row, tokens, start position)],
    padded to `pad_to`; rows without a span are padding rows (slot B, the
    trash row). Row r serves slot r (tests/test_kv_pool_inplace.py has rows
    that are not their slots). A span that starts at position 0 is its
    request's first."""
    pt = page_table(mp)
    stream, (q_start, q_len, kv_len) = span_stream(spans, pad_to, pt, PS)
    slot_ids = np.where(q_len > 0, np.arange(B), B).astype(np.int32)
    first = ((q_len > 0) & (kv_len == q_len)).astype(np.int32)
    out_idx = np.clip(q_start + q_len - 1, 0, pad_to - 1)
    logits, kc, vc, conv, load = _ragged_jit(mc, impl)(
        params, *st, *stream, out_idx, pt, q_start, q_len, kv_len, slot_ids,
        first)
    return {row: logits[row] for row, _, _ in spans}, (kc, vc, conv), load


@functools.cache
def _scan_jit(mc):
    """...and ONE jitted scan of `forward_decode` passes a config."""
    def run(p, kc, vc, conv, toks, pos0, table, act):
        def step(carry, tok):
            pos, kc, vc, conv = carry
            logits, kc, vc, *conv = llama.forward_decode(
                p, mc, tok, pos, kc, vc, table, PS, active=act,
                conv_state=conv)  # (no state: `conv` None, nothing returned)
            return (pos + 1, kc, vc, *(conv or [None])), logits

        (_, kc, vc, conv), logits = jax.lax.scan(
            step, (pos0, kc, vc, conv), toks)
        return logits, kc, vc, conv

    return jax.jit(run)


def decode_scan(mc, params, st, feed, active, mp=None, pt=None):
    """A fused scan of `forward_decode` passes, teacher-forced: `feed` =
    {row: (tokens, first position)} for the `active` rows; every other row
    carries garbage tokens and the trash page. Row r is slot r. Returns
    ({row: [k, V] logits}, state')."""
    k = len(next(iter(feed.values()))[0])
    toks = np.full((k, B), 7, np.int32)
    pos0 = np.zeros(B, np.int32)
    act = np.zeros(B, np.int32)
    for row, (t, p) in feed.items():
        toks[:, row], pos0[row] = t, p
    act[list(active)] = 1
    pt = page_table(mp) if pt is None else pt
    table = np.where(act[:, None] > 0, pt, 0).astype(np.int32)
    logits, kc, vc, conv = _scan_jit(mc)(params, *st, toks, pos0, table, act)
    return {row: logits[:, row] for row in feed}, (kc, vc, conv)


@once_a_sequence
def want(mc, params, tokens):
    """The reference's ONE full forward: [T, V] logits."""
    return np.asarray(lfm2_reference().logits(
        lfm2_keys(mc), params, whole_blocks(tokens)))[:len(tokens)]


def seq_tokens(seed, n, vocab=512):
    return np.random.default_rng(seed).integers(3, vocab, size=n).tolist()


def close(got, ref, atol=ATOL):
    np.testing.assert_allclose(np.asarray(got, np.float32), ref, atol=atol,
                               rtol=0)


# ----------------------------------------------------------- the config
def test_the_registered_family_and_its_plan():
    full = MODEL_CONFIGS["lfm2:8b-a1b"]
    assert (full.count(CONV), full.count(ATTENTION)) == (18, 6)
    assert [i for i, k in enumerate(full.layer_types) if k == ATTENTION] \
        == [2, 6, 10, 14, 18, 21]
    assert (full.count(EXPERTS), full.expert_width) == (22, 1792)
    # a dense prefix, four whole periods, the irregular tail as a period
    assert [(first, len(period), n) for first, period, n
            in full.layer_plan()] == [(0, 1, 2), (2, 4, 4), (18, 3, 2)]
    assert 8.2e9 < full.param_count() < 8.5e9          # "8.3 B"
    assert 1.4e9 < full.param_count(active=True) < 1.6e9   # "1.5 B active"
    # the tiny one has all three: prefix, repeated period, irregular tail
    plan = LFM2.layer_plan()
    assert [(first, len(period), n) for first, period, n in plan] \
        == [(0, 1, 2), (2, 2, 2), (6, 1, 1), (7, 1, 1), (8, 1, 1)]
    assert sum(len(p) * n for _, p, n in plan) == LFM2.num_layers
    # a uniform stack is one run of one layer, as before the family
    for name in ("test-tiny", "olmoe:1b-7b", "qwen3:8b"):
        mc = MODEL_CONFIGS[name]
        assert [(f, len(p), n) for f, p, n in mc.layer_plan()] \
            == [(0, 1, mc.num_layers)]
        assert mc.count(CONV) == 0 and mc.count(ATTENTION) == mc.num_layers


@pytest.mark.parametrize("bad,match", [
    (dict(layer_types=("conv",) * 3), "names 3 layers, num_layers is 9"),
    (dict(layer_types=("conv",) * 8 + ("mamba",)), r"holds \['mamba'\]"),
    (dict(num_dense_layers=10), "num_dense_layers 10 is not within"),
    (dict(router_score="tanh"), "router_score must be"),
    (dict(conv_bias=True), "conv_bias true"),
    (dict(conv_L_cache=1), "conv_L_cache must be at least 2"),
], ids=["length", "kind", "prefix", "score", "conv_bias", "window"])
def test_a_stack_the_program_cannot_run_is_refused_at_construction(bad,
                                                                   match):
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(LFM2, **bad)


def test_a_files_list_becomes_a_hashable_field():
    mc = dataclasses.replace(LFM2, layer_types=list(LFM2.layer_types))
    assert mc.layer_types == LFM2.layer_types and hash(mc) == hash(LFM2)


def test_weights_are_stacked_by_kind():
    shapes = {k: v.shape for k, v in make_params(LFM2)["layers"].items()}
    d, e, fe = LFM2.hidden_size, LFM2.num_experts, LFM2.expert_width
    assert shapes["attn_norm"] == shapes["mlp_norm"] == (9, d)
    assert shapes["wq"] == (3, d, LFM2.q_dim)
    assert shapes["q_norm"] == (3, LFM2.head_dim)
    assert shapes["conv_in"] == (6, d, 3 * d) and shapes["conv_w"] == (6, d, 3)
    assert shapes["conv_out"] == (6, d, d)
    assert shapes["w_gate"] == (2, d, LFM2.intermediate_size)
    assert shapes["w_router"] == (7, d, e) and shapes["router_bias"] == (7, e)
    assert shapes["we_gate"] == (7, e, d, fe) and fe == 32


# --------------------------------------- logits, against the reference
CHUNKINGS = {
    "two_halves": (11, 12),
    "spans_of_1_and_2": (9, 1, 2, 1, 1, 2, 7),   # shorter than the window
    "token_by_token_start": (1, 1, 1, 2, 18),    # state opens inside it
    "one_span": (23,),
}


@pytest.mark.parametrize("chunks", CHUNKINGS.values(), ids=CHUNKINGS.keys())
def test_prefill_in_chunks_then_decode_matches_the_reference(chunks):
    params = make_params(LFM2)
    toks = seq_tokens(1, 23 + 6)
    ref = want(LFM2, params, toks)
    st, at = state(LFM2, jnp.float32, garbage=3.0), 0
    for n in chunks:
        got, st, _ = ragged_step(LFM2, params, st, [(1, toks[at:at + n], at)])
        at += n
        close(got[1], ref[at - 1])
    # slot 1 holds the state; the other slots kept the earlier request's
    assert bool(jnp.all(st[2].conv[:, :, [0, 2, 3]] == 3.0))
    got, _ = decode_scan(LFM2, params, st, {1: (toks[23:], 23)}, active=[1])
    close(got[1], ref[23:])


def bfloat16_misses(mc, params, want, st, kept=()):
    """The same path with bfloat16 weights (but `kept`) and state: far from
    the reference — the tolerance has teeth. Returns the state after."""
    toks = seq_tokens(1, 23)
    ref = want(mc, params, toks)
    low = jax.tree_util.tree_map(
        lambda w: w.astype(jnp.bfloat16) if w.dtype == jnp.float32
        and w.ndim > 1 else w, params)
    low["final_norm"] = low["final_norm"].astype(jnp.bfloat16)
    for name in kept:
        low["layers"][name] = params["layers"][name]
    _, st, _ = ragged_step(mc, low, st, [(0, toks[:11], 0)])
    got, _, _ = ragged_step(mc, low, st, [(0, toks[11:], 11)])
    err = float(np.max(np.abs(np.asarray(got[0], np.float32) - ref[22])))
    assert err > 10 * ATOL, err
    return st


def test_the_same_path_in_bfloat16_misses_the_tolerance():
    bfloat16_misses(LFM2, make_params(LFM2), want,
                    state(LFM2, jnp.bfloat16), kept=("router_bias",))


def mixed_step(mc, params, st, want, check_load):
    """Row 0 decodes (a span of one token on carried state), row 1 sends the
    second chunk of its prompt, row 2 its first span, row 3 a whole short
    prompt — in ONE stream, after a step that left rows 0 and 1 mid-way."""
    seqs = {r: seq_tokens(10 + r, n) for r, n in enumerate((14, 20, 9, 3))}
    ref = {r: want(mc, params, t) for r, t in seqs.items()}
    got, st, _ = ragged_step(mc, params, st, [(0, seqs[0][:13], 0),
                                              (1, seqs[1][:7], 0)])
    close(got[0], ref[0][12])
    got, st, load = ragged_step(mc, params, st, [
        (0, seqs[0][13:], 13), (1, seqs[1][7:], 7), (2, seqs[2][:5], 0),
        (3, seqs[3], 0)])
    for row, last in ((0, 13), (1, 19), (2, 4), (3, 2)):
        close(got[row], ref[row][last])
    check_load(load)
    # padding rows and padding tokens wrote no slot's state (the trash row
    # takes them), and row 2's state continues: its second span agrees
    got, st, _ = ragged_step(mc, params, st, [(2, seqs[2][5:], 5)])
    close(got[2], ref[2][8])


def test_a_ragged_step_mixing_prefill_spans_with_decode_rows():
    def routed(load):  # every real token of the stream, in each expert layer
        assert load.shape == (LFM2.count(EXPERTS), LFM2.num_experts)
        assert int(load.sum()) == (1 + 13 + 5 + 3) \
            * LFM2.num_experts_per_tok * LFM2.count(EXPERTS)

    mixed_step(LFM2, make_params(LFM2),
               state(LFM2, jnp.float32, garbage=-2.0), want, routed)


def fused_scan(mc, params, st, want, by_slot):
    """k = 8 decode passes in one scan: slots 0 and 3 live, slot 1 reserved
    mid-chunked-prefill (its state must survive the scan and carry into its
    next span), slot 2 idle with an earlier request's state, 5.0 (kept as
    is). `by_slot(state)`: its arrays, the slot axis at 1."""
    seqs = {0: seq_tokens(20, 10 + 8), 1: seq_tokens(21, 21),
            3: seq_tokens(23, 2 + 8)}
    ref = {r: want(mc, params, t) for r, t in seqs.items()}
    _, st, _ = ragged_step(mc, params, st, [
        (0, seqs[0][:10], 0), (1, seqs[1][:9], 0), (3, seqs[3][:2], 0)])
    before = by_slot(st[2])
    got, st = decode_scan(mc, params, st, {0: (seqs[0][10:], 10),
                                           3: (seqs[3][2:], 2)},
                          active=[0, 3])
    close(got[0], ref[0][10:])
    close(got[3], ref[3][2:])
    for was, arr in zip(before, by_slot(st[2])):
        assert (arr[:, 1] == was[:, 1]).all()      # mid-prefill: kept
        assert (arr[:, 2] == 5.0).all()            # idle: kept
        assert (arr[:, 0] != was[:, 0]).any()      # live: advanced
    got, _, _ = ragged_step(mc, params, st, [(1, seqs[1][9:], 9)])
    close(got[1], ref[1][20])


def test_the_fused_scan_beside_inactive_and_mid_prefill_slots():
    fused_scan(LFM2, make_params(LFM2), state(LFM2, jnp.float32, garbage=5.0),
               want, lambda st: [np.asarray(st.conv).swapaxes(1, 2)])


def keeps_the_filler_from_it(want, params, reference, keys, mc):
    """`want` hands a reference that does not pad itself whole blocks with a
    filler behind (`testutil.whole_blocks`). It is causal: another filler
    moves no bit of the rows `want` keeps; and those rows are the rows of the
    sequence forwarded bare (as one program: op by op it is a minute under
    load), to the tolerance every case grants (two float32 forwards of
    different lengths: a row where seeded Olmo-Hybrid is ill-conditioned
    differs by 1e-4 between paddings to 32 and to 64 as well)."""
    tokens = seq_tokens(41, 13)
    got = want(mc, params, tokens)
    other = whole_blocks(tokens).at[13:].set(99)
    assert np.array_equal(got, reference.logits(keys, params, other)[:13])
    bare = jax.jit(lambda p, t: reference.logits(keys, p, t))(
        params, jnp.asarray(tokens, jnp.int32))
    assert bare.shape == got.shape == (13, mc.vocab_size)
    close(got, bare)


def test_the_reference_keeps_the_filler_behind_a_sequence_from_it():
    keeps_the_filler_from_it(want, make_params(LFM2), lfm2_reference(),
                             lfm2_keys(LFM2), LFM2)


def test_the_published_24_layer_list_at_tiny_widths():
    full = MODEL_CONFIGS["lfm2:8b-a1b"]
    mc = dataclasses.replace(
        LFM2, name="lfm2-24", num_layers=24, layer_types=full.layer_types)
    assert [(f, len(p), n) for f, p, n in mc.layer_plan()] \
        == [(0, 1, 2), (2, 4, 4), (18, 3, 2)]
    params = make_params(mc)
    toks = seq_tokens(3, 17)
    ref = want(mc, params, toks)
    st = state(mc, jnp.float32, garbage=1.0)
    got, st, _ = ragged_step(mc, params, st, [(0, toks[:6], 0)])
    got, st, _ = ragged_step(mc, params, st, [(0, toks[6:14], 6)])
    close(got[0], ref[13])
    got, _ = decode_scan(mc, params, st, {0: (toks[14:], 14)}, active=[0])
    close(got[0], ref[14:])


def test_the_oracle_and_the_embedding_forward_follow():
    params = make_params(LFM2)
    toks = seq_tokens(5, 19)
    ref = want(LFM2, params, toks)
    kc, vc, _ = state(LFM2, jnp.float32)
    batch = np.zeros((2, 24), np.int32)
    batch[0, :19], batch[1, :12] = toks, toks[:12]
    logits, _, _ = prefill(
        params, LFM2, jnp.asarray(batch), jnp.asarray([19, 12]), kc, vc,
        jnp.asarray(page_table()[:2]), PS)
    close(logits[0], ref[18])
    close(logits[1], ref[11])
    # the embedding is the masked mean of the final hidden states: padding
    # behind a sequence moves nothing (causal convolution, causal attention)
    e1 = embed(params, LFM2, jnp.asarray(batch[:1]),
                             jnp.asarray([19]))
    e2 = embed(params, LFM2, jnp.asarray(batch[:1, :19]),
                             jnp.asarray([19]))
    np.testing.assert_allclose(np.asarray(e1), np.asarray(e2), atol=1e-5)
    hidden = lfm2_reference().hidden(lfm2_keys(LFM2), params,
                                     jnp.asarray(toks, jnp.int32))
    pooled = np.asarray(hidden).mean(axis=0)
    np.testing.assert_allclose(np.asarray(e1[0]),
                               pooled / np.linalg.norm(pooled), atol=1e-5)


# ------------------------------------------------------------ the router
def _route(mc, bias, logits):
    """(weights, experts) of ONE token whose router logits are `logits`."""
    e = len(logits)
    lp = {"w_router": jnp.zeros((e, e)).at[0].set(jnp.asarray(logits))}
    if bias is not None:
        lp["router_bias"] = jnp.asarray(bias, jnp.float32)
    gates, experts = moe.route(mc, lp, jnp.eye(e)[:1])
    return np.asarray(gates[0]), np.asarray(experts[0])


def test_the_bias_moves_the_selection_and_not_the_weights():
    logits = [2.0, 1.0, 0.5, 0.0, -0.5, -1.0, -2.0, -3.0]
    s = 1 / (1 + np.exp(-np.asarray(logits)))
    mc = dataclasses.replace(LFM2, norm_topk_prob=False)
    gates, experts = _route(mc, np.zeros(8), logits)
    assert experts.tolist() == [0, 1] and np.allclose(gates, s[:2])
    bias = np.zeros(8)
    bias[5] = 1.0  # lifts expert 5 over every other for the SELECTION
    gates, experts = _route(mc, bias, logits)
    assert experts.tolist() == [5, 0]
    assert np.allclose(gates, [s[5], s[0]])      # its weight: without bias
    # normalised: over the kept UNBIASED scores plus the epsilon
    gates, _ = _route(dataclasses.replace(mc, norm_topk_prob=True), bias,
                      logits)
    assert np.allclose(gates, np.array([s[5], s[0]]) / (s[5] + s[0] + 1e-6))
    big = dataclasses.replace(mc, norm_topk_prob=True, norm_topk_eps=0.5)
    gates, _ = _route(big, bias, logits)
    assert np.allclose(gates, np.array([s[5], s[0]]) / (s[5] + s[0] + 0.5))
    # k, and the scaling factor
    three = dataclasses.replace(mc, num_experts_per_tok=3,
                                routed_scaling_factor=2.5)
    gates, experts = _route(three, bias, logits)
    assert experts.tolist() == [5, 0, 1]
    assert np.allclose(gates, 2.5 * s[[5, 0, 1]])


def test_softmax_routers_read_the_same_function():
    logits = [2.0, 1.0, 0.5, 0.0, -0.5, -1.0, -2.0, -3.0]
    p = np.exp(logits) / np.exp(logits).sum()
    olmoe = dataclasses.replace(MODEL_CONFIGS["test-tiny-olmoe"],
                                num_experts=8, num_experts_per_tok=2)
    gates, experts = _route(olmoe, None, logits)
    assert experts.tolist() == [0, 1] and np.allclose(gates, p[:2], atol=1e-6)
    mixtral = dataclasses.replace(olmoe, norm_topk_prob=True)
    gates, _ = _route(mixtral, None, logits)
    assert np.allclose(gates, p[:2] / p[:2].sum(), atol=1e-6)


def test_the_seeded_selection_bias_is_not_zero():
    bias = np.asarray(llama.init_params(
        LFM2, jax.random.PRNGKey(0), jnp.bfloat16)["layers"]["router_bias"])
    assert bias.dtype == np.float32 and bias.shape == (7, 8)
    assert 0.05 < bias.std() < 0.2


# ------------------------------------------------- the engine, by id stream
def _lfm2_engine(**over):
    return _engine("test-tiny-lfm2", **over)


@pytest.fixture(scope="module")
def hybrid():
    return _lfm2_engine()


def _arrivals(n=6, lens=(5, 40, 9, 23, 14, 31), every=2, out=9):
    return [(every * i, f"u{i}", _prompt(i, lens[i % len(lens)]),
             SamplingParams(max_tokens=out + 2 * i)) for i in range(n)]


def test_overlapped_against_serial_gives_the_same_ids(hybrid, monkeypatch):
    """Six requests over four slots: spans of several lengths beside decode
    rows, the 32-token budget cuts prompts into chunks, slots free and are
    reused, fused k=4 scans between waves — pipelined and settled loops."""
    piped, settled, samples = both(hybrid, _arrivals(), monkeypatch)
    assert piped == settled
    assert {s["mode"] for s in samples} == {"ragged", "decode"}
    rt = _rt(hybrid)
    assert rt.cache.slot_state.conv.shape == (6, 2, 4, 64)
    assert rt.state_bytes["conv_state_bytes"] > 0
    # every launched step says what it did with the conv state, and
    # uploads ONE packed array
    assert all(s["h2d_transfers"] == 1 for s in samples)
    ragged = [s for s in samples if s["mode"] == "ragged"]
    assert sum(s["conv_state_resets"] for s in ragged) == 6  # one a request
    assert sum(s["conv_state_carried"] for s in ragged) > 6  # later chunks
    assert all(s["conv_state_resets"] == 0 and s["conv_state_carried"] > 0
               for s in samples if s["mode"] == "decode")
    assert all(s["moe_assignments"] % (2 * 7) == 0 for s in samples)


def reused_slot(make_engine, holds_state, monkeypatch):
    """A second request in a slot the first left: the program opens the
    slot's state at zero (`is_first`), no host call clears it. (ONE engine:
    the probe on it fresh, then `first`, then the probe again.)"""
    probe = (0, "probe", _prompt(4, 19), SamplingParams(max_tokens=12))
    eng = make_engine()
    fresh, _ = drive(eng, [probe], False, monkeypatch)
    first = (0, "first", _prompt(2, 37), SamplingParams(max_tokens=11))
    drive(eng, [first], False, monkeypatch)
    holds_state(_rt(eng))
    reused, _ = drive(eng, [probe], False, monkeypatch)
    assert reused["probe"] == fresh["probe"]
    assert len(reused["probe"][0]) == 12


def test_a_reused_slot_gives_the_ids_a_fresh_engine_gives(monkeypatch):
    def holds_state(rt):  # slot 0 holds its state
        assert np.abs(np.asarray(rt.cache.slot_state.conv)[:, 0]).max() > 0

    reused_slot(_lfm2_engine, holds_state, monkeypatch)


def preempted_and_replayed(unfaulted, make_engine, resets, monkeypatch):
    """With the prefix cache asked for: a model with per-slot state gets none
    (a cached page carries no state), so the preempted request replays from
    token 0 and its stream does not move — it is the stream of `unfaulted`,
    the module's engine, which no plan ever touched — and the replay opened
    the slot's state anew (`resets`: the samples' field that counts it)."""
    arr = [(0, "victim", _prompt(1, 21), SamplingParams(max_tokens=14))]
    base, _ = drive(unfaulted, arr, False, monkeypatch)
    plan = FaultPlan([{"site": "extend", "kind": "alloc_fail", "at": [2]}])
    eng = make_engine(plan=plan, prefix_cache=True)
    rt = _rt(eng)
    assert rt.cache.prefix_cache is None
    got, samples = drive(eng, arr, False, monkeypatch)
    assert rt.preempt_count >= 1
    assert got == base and len(got["victim"][0]) == 14
    assert sum(s.get(resets, 0) for s in samples) >= 2


def test_preempt_and_replay_gives_the_same_ids(hybrid, monkeypatch):
    preempted_and_replayed(hybrid, _lfm2_engine, "conv_state_resets",
                           monkeypatch)


def test_a_voided_step_leaves_nothing_a_later_request_can_see(hybrid,
                                                              monkeypatch):
    """A fault between launch and settle voids the step in flight; the rows
    replay as new admissions, each of which resets its slot."""
    base, _ = drive(hybrid, _arrivals(n=4), False, monkeypatch)
    plan = FaultPlan([{"site": "ragged", "kind": "exception", "at": [4]}])
    got, _ = drive(_lfm2_engine(plan=plan), _arrivals(n=4), False,
                   monkeypatch)
    assert got == base


# ------------------------------- what else touches per-sequence state
@pytest.mark.parametrize("kw,match", [
    (dict(spec=True), "--spec: a rejected draft"),
    (dict(mesh_shape={"tensor": 2}), "--tp / --ep: the conv layers"),
    (dict(mesh_shape={"expert": 2}), "--tp / --ep: the conv layers"),
], ids=["spec", "tp", "ep"])
def test_features_that_know_only_the_kv_pool_are_refused(kw, match):
    err = refusal(LFM2, **kw)
    assert err and match in err and "test-tiny-lfm2" in err
    # ...and a model without conv layers is not asked
    assert refusal(MODEL_CONFIGS["test-tiny-moe"], **kw) is None
    assert refusal(LFM2, mesh_shape={"data": 2}) is None


def test_the_runtime_refuses_them_at_construction():
    with pytest.raises(ValueError, match="--spec"):
        _lfm2_engine(spec=True, spec_k=3)
    with pytest.raises(ValueError, match="--tp / --ep"):
        _lfm2_engine(tp=2)
    err = validate_quant_config("int8", "bfloat16",
                                model_names=("test-tiny-lfm2",))
    assert err and "int8" in err and "test-tiny-lfm2" in err
    from ollamamq_tpu.models import weights
    with pytest.raises(ValueError, match="does not cover"):
        weights.quantize_params_int8(make_params(LFM2), LFM2)


def test_migration_is_refused_not_served_without_the_state(hybrid,
                                                           monkeypatch):
    from ollamamq_tpu.engine.engine import MigrationError

    rt = _rt(hybrid)
    seen = {}

    def during():
        def hook(tick, reqs):
            if tick == 6 and "u0" in reqs and "out" not in seen:
                seen["out"] = rt.export_request(reqs["u0"].req_id)
        return hook

    drive(hybrid, _arrivals(n=2), False, monkeypatch, during())
    assert seen["out"] is None  # not exportable: the caller replays
    with pytest.raises(MigrationError, match="conv layers' state"):
        rt.import_request({"kind": "stream"}, None)
    assert hybrid.export_prefix("test-tiny-lfm2", _prompt(1, 40)) is None


def test_gauges_size_a_deployment():
    from ollamamq_tpu.telemetry import schema as tm

    _lfm2_engine()
    _engine("test-tiny")

    def gauge(series, model):
        return next(c.value for labels, c in series.series()
                    if model in labels)

    # K and V of 3 attention layers of 2 x 16 lanes, float32 here
    assert gauge(tm.KV_BYTES_PER_TOKEN, "test-tiny-lfm2") == 2 * 3 * 32 * 4
    assert gauge(tm.HBM_CONV_STATE_BYTES, "test-tiny-lfm2") \
        == 6 * 2 * 4 * 64 * 4  # layers x taps x SLOTS (no trash row) x D
    assert gauge(tm.KV_BYTES_PER_TOKEN, "test-tiny") == 2 * 2 * 32 * 4
    assert gauge(tm.HBM_CONV_STATE_BYTES, "test-tiny") == 0
