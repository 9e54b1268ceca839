"""Falcon-H1 (attention AND a Mamba-2 state-space mixer in every layer, over
one normed input; the family's muP multipliers; a convolution with a bias; a
gated RMSNorm over groups) on the served path, held to its plain float32
reference.

The reference is the benchmark's (`benchmarks/reference/falcon_h1_decoder.py`):
one sequence, a Python loop over the layers, causal softmax attention in query
blocks, the convolution over shifted copies plus the bias (no window), the
recurrence token by token (no chunks, no state between calls). The system's
side is the real thing: `forward_ragged` over a prompt in chunks, then decode
passes, through the paged pool, the conv window and the mixer's float32 state.
LOGITS are compared, not sampled ids, in float32: two orders of summation
(pages, chunks and carried state against one dense pass) differ by ~5e-6 of
logits whose spread is ~1 (measured: 1.9e-6 to 4.9e-6), so ATOL 2e-4, forty
times that; the same path with the mixer's state rounded to bfloat16 between
passes misses it (asserted), and so does every ablation below. The
engine-level cases compare id streams of the SAME programs under different
schedules: bit-identical.
"""

import dataclasses
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ollamamq_tpu.config import (MODEL_CONFIGS, PARALLEL, ModelConfig,
                                 validate_quant_config)
from ollamamq_tpu.engine.kv_cache import refusal
from ollamamq_tpu.models import llama
from ollamamq_tpu.ops import gated_delta as gd
from ollamamq_tpu.ops import ssd
from ollamamq_tpu.ops.pallas.ssd_step import ssd_step_pallas
from ollamamq_tpu.ops.sampling import SamplingParams
from test_lfm2 import (ATOL, B, NP, PS, _arrivals, close, page_table,
                       ragged_step, seq_tokens)
from test_step_overlap import _engine, _prompt, _rt, both, drive
from testutil import falcon_h1_keys, falcon_h1_reference

NAME = "test-tiny-falcon-h1"
FALCON = MODEL_CONFIGS[NAME]
H, DH, G, DS = (FALCON.mamba_n_heads, FALCON.mamba_d_head,
                FALCON.mamba_n_groups, FALCON.mamba_d_state)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def params():
    return llama.init_params(FALCON, jax.random.PRNGKey(0), dtype=jnp.float32)


def want(mc, params, tokens):
    """The reference's ONE full forward: [T, V] logits."""
    return np.asarray(falcon_h1_reference().logits(
        falcon_h1_keys(mc), params, jnp.asarray(tokens, jnp.int32)))


def state(mc, dtype=jnp.float32, garbage=0.0):
    """(kc, vc, slot state): empty pools and a per-slot state that an
    earlier request left full of `garbage`."""
    kv = jnp.zeros((mc.cache_layers, NP * PS, mc.kv_dim), dtype)
    slot = llama.alloc_slot_state(mc, B, dtype)
    return kv, kv, jax.tree_util.tree_map(lambda a: a + garbage, slot)


@functools.lru_cache(maxsize=None)
def _decode_jit(mc):
    def run(p, kc, vc, slot, tok, pos, table, act):
        return llama.forward_decode(p, mc, tok, pos, kc, vc, table, PS,
                                    active=act, conv_state=slot)

    return jax.jit(run)


def decode_scan(mc, params, st, feed, active):
    """Decode passes, teacher-forced: `feed` = {row: (tokens, first
    position)} for the `active` rows; every other row carries garbage tokens
    and the trash page. Row r is slot r. Returns ({row: [k, V] logits},
    state')."""
    k = len(next(iter(feed.values()))[0])
    toks = np.full((k, B), 7, np.int32)
    pos = np.zeros(B, np.int32)
    act = np.zeros(B, np.int32)
    for row, (t, p) in feed.items():
        toks[:, row], pos[row] = t, p
    act[list(active)] = 1
    table = np.where(act[:, None] > 0, page_table(), 0).astype(np.int32)
    out = []
    for i in range(k):
        logits, *st = _decode_jit(mc)(params, *st, toks[i], pos + i, table,
                                      act)
        out.append(logits)
    return {row: jnp.stack(out)[:, row] for row in feed}, tuple(st)


# ----------------------------------------- the served path against the reference
def test_prefill_in_chunks_then_decode_through_the_cache(params):
    """A prompt in two spans over a slot an earlier request left dirty, then
    a fused scan of decode passes: every logit the reference's."""
    toks = seq_tokens(1, 60)
    ref = want(FALCON, params, toks)
    st = state(FALCON, garbage=3.0)
    out, st, _ = ragged_step(FALCON, params, st, [(1, toks[:23], 0)])
    close(out[1], ref[22])
    out, st, _ = ragged_step(FALCON, params, st, [(1, toks[23:50], 23)])
    close(out[1], ref[49])
    got, st = decode_scan(FALCON, params, st, {1: (toks[50:58], 50)}, [1])
    close(got[1], ref[50:58])
    assert st[2].ssm is not None and st[2].ring is None
    assert st[2].ssm.shape == (FALCON.num_layers, B + 1, DS, H * DH)
    assert st[2].ssm.dtype == jnp.float32
    assert st[2].conv.shape == (FALCON.num_layers, 3, B, FALCON.ssm_conv_dim)


def test_a_ragged_step_of_mixed_spans(params):
    """One-token rows (the one-token form) beside longer spans (the chunked
    form) in ONE stream, rows that open and rows that continue."""
    a, b, c = seq_tokens(2, 40), seq_tokens(3, 40), seq_tokens(4, 40)
    ra, rb, rc = (want(FALCON, params, t) for t in (a, b, c))
    st = state(FALCON, garbage=-2.0)
    out, st, _ = ragged_step(FALCON, params, st,
                             [(0, a[:9], 0), (2, b[:1], 0)])
    close(out[0], ra[8])
    close(out[2], rb[0])
    out, st, _ = ragged_step(FALCON, params, st, [
        (0, a[9:10], 9), (2, b[1:14], 1), (3, c[:5], 0)])
    close(out[0], ra[9])
    close(out[2], rb[13])
    close(out[3], rc[4])
    out, st, _ = ragged_step(FALCON, params, st, [
        (0, a[10:11], 10), (2, b[14:15], 14), (3, c[5:30], 5)], pad_to=32)
    close(out[0], ra[10])
    close(out[2], rb[14])
    close(out[3], rc[29])


def test_the_prefill_oracle_is_the_reference(served):
    """`forward_prefill` (whole prompts, the chunked recurrence from an
    empty state): the fixture held it to the reference."""
    assert served.shape == (FALCON.vocab_size,)


def test_a_bfloat16_state_misses_the_tolerance(params):
    """The limit tells a float32 accumulator from a bfloat16 one: the same
    passes with the mixer's state rounded between them."""
    toks = seq_tokens(6, 48)
    ref = want(FALCON, params, toks)
    st = state(FALCON)
    _, st, _ = ragged_step(FALCON, params, st, [(0, toks[:32], 0)])
    worst = 0.0
    for i in range(32, 44):
        kc, vc, slot = st
        st = (kc, vc, slot._replace(ssm=slot.ssm.astype(
            jnp.bfloat16).astype(jnp.float32)))
        got, st = decode_scan(FALCON, params, st, {0: (toks[i:i + 1], i)},
                              [0])
        worst = max(worst, float(np.abs(np.asarray(got[0]) - ref[i]).max()))
    assert worst > 5 * ATOL, worst


# ------------------------------------ the recurrence's forms against `step`
def _operands(seed, b, t):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    c = jax.random.normal(ks[0], (b, t, G, DS))
    bb = jax.random.normal(ks[1], (b, t, G, DS))
    x = jax.random.normal(ks[2], (b, t, H, DH))
    dt = jax.random.normal(ks[3], (b, t, H))
    a_log = jnp.log(jax.random.uniform(ks[4], (H,), minval=1.0, maxval=16.0))
    v, g = ssd.inputs(x, dt, a_log, jnp.full((H,), -3.0))
    return c, bb, v, g


def _serial(c, b, v, g, s0=None):
    """Token by token through `ssd.step`: [B, T, ...] -> (y, final state)."""
    s0 = jnp.zeros((c.shape[0], DS, H * DH)) if s0 is None else s0

    def token(s, i):
        y, s = ssd.step(s, c[:, i], b[:, i], v[:, i], g[:, i])
        return s, y

    s, ys = jax.lax.scan(token, s0, jnp.arange(c.shape[1]))
    return jnp.moveaxis(ys, 0, 1), s


def test_step_is_the_recurrence_as_written():
    """S = exp(g) S + B (dt x)^T per head, y = S C; group j // (H / G)."""
    c, b, v, g = (a[0, 0] for a in _operands(0, 1, 1))
    s0 = jax.random.normal(jax.random.PRNGKey(9), (DS, H * DH))
    y, s1 = ssd.step(s0, c, b, v, g)
    s0h, s1h = (np.asarray(s).reshape(DS, H, DH) for s in (s0, s1))
    for j in range(H):
        grp = j // (H // G)
        s_want = np.exp(float(g[j])) * s0h[:, j] \
            + np.outer(np.asarray(b[grp]), np.asarray(v[j]))
        np.testing.assert_allclose(s1h[:, j], s_want, atol=1e-5)
        np.testing.assert_allclose(np.asarray(y[j]),
                                   np.asarray(c[grp]) @ s_want, atol=1e-4)


def test_the_chunked_form_is_the_serial_scan():
    c, b, v, g = _operands(1, 2, 150)  # three windows, the last partial
    y_want, s_want = _serial(c, b, v, g)
    y, s = ssd.chunked(c, b, v, g)
    close(y, np.asarray(y_want), atol=5e-5)
    close(s, np.asarray(s_want), atol=5e-5)
    valid = jnp.arange(150)[None, :] < jnp.asarray([150, 97])[:, None]
    y, s = ssd.chunked(c, b, v, g, valid)
    _, s97 = _serial(c[1:, :97], b[1:, :97], v[1:, :97], g[1:, :97])
    close(s[1], np.asarray(s97[0]), atol=5e-5)


def test_ragged_and_decode_continue_each_rows_own_state():
    """A stream of a one-token row, a span across two windows that opens its
    request and a span that continues: against the serial scan from each
    row's own state; then a decode pass with a parked slot."""
    c, b, v, g = _operands(2, 3, 80)
    state0 = jax.random.normal(jax.random.PRNGKey(5), (2, B + 1, DS, H * DH))
    spans = [(0, 1, False), (1, 70, True), (2, 25, False)]  # row, len, first
    parts = [np.asarray(x) for x in (c, b, v, g)]
    stream = [np.concatenate([p[r, :n] for r, n, _ in spans]) for p in parts]
    pad = 128 - stream[0].shape[0]
    stream = [jnp.asarray(np.pad(s, ((0, pad),) + ((0, 0),) * (s.ndim - 1)))
              for s in stream]
    starts = np.cumsum([0] + [n for _, n, _ in spans])[:-1]
    tok_seq = np.concatenate([np.full(n, r) for r, n, _ in spans]
                             + [np.zeros(pad, int)])
    tok_pos = np.concatenate([np.arange(n) for _, n, _ in spans]
                             + [np.full(pad, -1)])
    slot_ids = jnp.asarray([2, 0, 3, B])
    y, state1 = ssd.ragged(
        *stream, state0, 1, slot_ids, jnp.asarray(tok_seq),
        jnp.asarray(tok_pos), jnp.asarray(list(starts) + [128]),
        jnp.asarray([n for _, n, _ in spans] + [0]),
        jnp.asarray([int(f) for *_, f in spans] + [0]))
    for (r, n, first), at in zip(spans, starts):
        s0 = None if first else state0[1, slot_ids[r]][None]
        y_want, s_want = _serial(c[r:r + 1, :n], b[r:r + 1, :n],
                                 v[r:r + 1, :n], g[r:r + 1, :n], s0)
        close(y[at:at + n], np.asarray(y_want[0]), atol=5e-5)
        close(state1[1, slot_ids[r]], np.asarray(s_want[0]), atol=5e-5)
    close(state1[0], np.asarray(state0[0]))  # the other layer: untouched
    close(state1[1, 1], np.asarray(state0[1, 1]))  # a slot no row serves
    # decode: slots 0..B-1, slot 1 parked
    active = jnp.asarray([1, 0, 1, 1])
    y, state2 = ssd.decode(c[0, :B], b[0, :B], v[0, :B], g[0, :B], state1, 1,
                           active)
    y_want, s_want = ssd.step(state1[1, :B], c[0, :B], b[0, :B], v[0, :B],
                              g[0, :B])
    for s in range(B):
        if active[s]:
            close(y[s], np.asarray(y_want[s]))
            close(state2[1, s], np.asarray(s_want[s]))
        else:
            assert not np.asarray(y[s]).any()
            close(state2[1, s], np.asarray(state1[1, s]))


def test_the_kernel_in_interpret_mode_is_step():
    """Live rows advance in place (one opens at zero), a row that is not
    live and every other row of the array keep their bytes."""
    c, b, v, g = (a[:, 0] for a in _operands(3, 3, 1))
    state0 = jax.random.normal(jax.random.PRNGKey(7), (2, B + 1, DS, H * DH))
    slots = jnp.asarray([3, 1, 0])
    live = jnp.asarray([True, False, True])
    reset = jnp.asarray([False, False, True])
    y, s1 = ssd_step_pallas(state0, 1, slots, live, reset, c, b, v, g,
                            interpret=True)
    y_want, s_want = gd._step_rows("jnp", state0, 1, slots, live, reset, c, b,
                                   v, g, jnp.ones_like(g), plain=True)
    close(y, np.asarray(y_want), atol=1e-5)
    close(s1, np.asarray(s_want), atol=1e-5)
    assert not np.asarray(y[1]).any()
    np.testing.assert_array_equal(np.asarray(s1[1, 1]),
                                  np.asarray(state0[1, 1]))


def test_the_pallas_path_of_a_ragged_step_is_the_jnp_path(params):
    """forward_ragged with `attn_impl="pallas"` in interpret mode: the
    attention kernel's and the step kernel's rows against the jnp path."""
    toks = seq_tokens(8, 31)
    st = state(FALCON)
    _, st, _ = ragged_step(FALCON, params, st, [(0, toks[:11], 0)])
    spans = [(0, toks[11:12], 11), (1, toks[:30], 0)]
    want_, (_, _, want_state), _ = ragged_step(FALCON, params, st, spans)
    got, (_, _, got_state), _ = ragged_step(FALCON, params, st, spans,
                                            impl="pallas")
    for row in (0, 1):
        close(got[row], np.asarray(want_[row]))
    close(got_state.ssm[:, :2], np.asarray(want_state.ssm[:, :2]), atol=5e-4)


# ----------------------------------- wrong forwards MUST miss the tolerance
def _one(i):
    return tuple(1.0 if j == i else m
                 for j, m in enumerate(FALCON.ssm_multipliers))


def _norm_before_gate(y, z, w, groups, eps):
    g = y.astype(jnp.float32).reshape(*y.shape[:-1], groups, -1)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    return (g.reshape(y.shape) * jax.nn.silu(z.astype(jnp.float32))
            ).astype(z.dtype) * w


_GROUPED = llama._gated_group_norm
NORMS = {
    "ungrouped_norm": lambda y, z, w, groups, eps: _GROUPED(y, z, w, 1, eps),
    "norm_before_gate": _norm_before_gate,
}
ABLATIONS = {
    # name: (configuration keys replaced, weights zeroed)
    "no_ssm_branch": (dict(ssm_out_multiplier=0.0), ()),
    "no_attention_branch": (dict(attention_out_multiplier=0.0), ()),
    "no_D": ({}, ("ssm_D",)),
    "no_conv_bias": ({}, ("ssm_conv_b",)),
    **{f"{key}_as_1": ({key: 1.0}, ()) for key in (
        "embedding_multiplier", "lm_head_multiplier",
        "attention_out_multiplier", "key_multiplier", "ssm_in_multiplier",
        "ssm_out_multiplier")},
    **{f"ssm_multipliers_{i}_as_1": (dict(ssm_multipliers=_one(i)), ())
       for i in range(5)},
    **{f"mlp_multipliers_{i}_as_1": (dict(mlp_multipliers=tuple(
        1.0 if j == i else m for j, m in enumerate(FALCON.mlp_multipliers))),
        ()) for i in range(2)},
}
ABLATION_TOKENS = seq_tokens(11, 24)


def _served_logits(params):
    """The program's own forward over the ablations' tokens: last logits."""
    kv = jnp.zeros((FALCON.cache_layers, NP * PS, FALCON.kv_dim), jnp.float32)
    logits, _, _ = llama.forward_prefill(
        params, FALCON, jnp.asarray([ABLATION_TOKENS], jnp.int32),
        jnp.asarray([24]), kv, kv, jnp.asarray(page_table()[:1]), PS)
    return np.asarray(logits[0])


@pytest.fixture(scope="module")
def served(params):
    got = _served_logits(params)
    close(got, want(FALCON, params, ABLATION_TOKENS)[23])
    return got


@pytest.mark.parametrize("name", sorted(ABLATIONS))
def test_a_wrong_forward_misses_the_tolerance(name, params, served):
    """Each case leaves ONE thing of the family out of a forward (a branch,
    the skip, the bias, one of the thirteen multiplier scalars that is not
    1) on the SAME weights — here out of the reference's, whose keys are a
    dict: its logits leave the served path's (which agree with the whole
    reference: `served`) by far more than the tolerance."""
    keys, zeroed = ABLATIONS[name]
    wrong = dict(params, layers={
        k: jnp.zeros_like(w) if k in zeroed else w
        for k, w in params["layers"].items()})
    ref = np.asarray(falcon_h1_reference().logits(
        {**falcon_h1_keys(FALCON), **keys}, wrong,
        jnp.asarray(ABLATION_TOKENS, jnp.int32)))[23]
    miss = float(np.abs(served - ref).max())
    assert miss > 10 * ATOL, (name, miss)


@pytest.mark.parametrize("name", sorted(NORMS))
def test_a_wrong_output_norm_misses_the_tolerance(name, params, served,
                                                  monkeypatch):
    """...and the two the reference has no key for, out of the PROGRAM's
    forward: the norm over all channels at once, and the gate after it."""
    monkeypatch.setattr(llama, "_gated_group_norm", NORMS[name])
    miss = float(np.abs(_served_logits(params) - served).max())
    assert miss > 10 * ATOL, (name, miss)


def test_the_thirteen_scalars_are_the_ones_that_are_not_one():
    scalars = [FALCON.embedding_multiplier, FALCON.lm_head_multiplier,
               FALCON.attention_out_multiplier, FALCON.key_multiplier,
               FALCON.ssm_in_multiplier, FALCON.ssm_out_multiplier,
               *FALCON.ssm_multipliers, *FALCON.mlp_multipliers]
    assert len(scalars) == 13 and all(m != 1.0 for m in scalars)
    assert FALCON.attention_in_multiplier == 1.0
    assert sum(name.endswith("_as_1") for name in ABLATIONS) == 13
    assert len(ABLATIONS) + len(NORMS) == 19


# --------------------------------------------- ModelConfig from the catalog
def _catalog_row() -> dict:
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    return next(r for r in rows if r["name"] == "Falcon-H1-34B-Instruct")


def _from_row(**edit) -> ModelConfig:
    """The catalog row's `config` through the harness's own mapping of keys
    to fields (benchmarks/serve.py: a key goes to the field of its name)."""
    from benchmarks import serve

    cfg = {"name": "falcon-h1-34b", **_catalog_row()["config"], **edit}
    return serve.model_config(cfg, rehearse=False)


def test_the_catalog_row_builds_the_published_model():
    mc = _from_row()
    assert mc.param_count() == 33_642_516_224
    assert dataclasses.replace(mc, num_layers=6).param_count() \
        == 5_254_594_112
    per_layer = (mc.param_count()
                 - dataclasses.replace(mc, num_layers=71).param_count())
    assert per_layer == 430_120_032
    assert mc.kinds == ((PARALLEL, "dense"),) * 72
    assert mc.cache_layers == mc.attn_layers == mc.count(PARALLEL) == 72
    assert mc.state_window == (4, 5120) and mc.ssm_in_dim == 9248
    assert mc.layer_plan() == ((0, ((PARALLEL, "dense"),), 72),)
    registered = MODEL_CONFIGS["falcon-h1:34b"]
    assert dataclasses.replace(
        mc, name=registered.name, mamba_expand=registered.mamba_expand,
        mlp_expansion_factor=registered.mlp_expansion_factor) == registered
    # the mixer's state a slot a layer: 4 MiB of float32
    assert mc.mamba_n_heads * mc.mamba_d_head * mc.mamba_d_state * 4 \
        == 4_194_304


@pytest.mark.parametrize("key,value", [
    ("mamba_proj_bias", True), ("projectors_bias", True), ("mlp_bias", True),
    ("mamba_rms_norm", False), ("mamba_norm_before_gate", True),
    ("mamba_use_mlp", False), ("attn_layer_indices", [0, 2]),
    ("num_logits_to_keep", 0), ("ssm_multipliers", [1.0, 1.0]),
    ("mamba_n_groups", 3), ("mamba_d_head", 64),
])
def test_a_value_the_program_does_not_implement_is_refused_by_name(key, value):
    from benchmarks import serve

    with pytest.raises(serve.Refused, match=key):
        _from_row(**{key: value})


def test_attention_bias_reaches_the_program(params):
    """`attention_bias` true is not this family's, but the field it reaches
    (`attn_bias`) is served: the key is mapped, not dropped."""
    assert _from_row(attention_bias=True).attn_bias is True


@pytest.mark.parametrize("kw,match", [
    (dict(spec=True), "--spec: a rejected draft has already advanced the "
     "mixer's"),
    (dict(mesh_shape={"tensor": 2}), "--tp / --ep: the mixer's weights"),
    (dict(mesh_shape={"expert": 2}), "--tp / --ep: the mixer's weights"),
    (dict(kv_dtype="int8"), "--kv-dtype int8"),
    (dict(prefix_cache=True), "--prefix-cache: a cached page"),
], ids=["spec", "tp", "ep", "int8", "prefix_cache"])
def test_features_that_know_only_the_kv_pool_are_refused(kw, match):
    err = refusal(FALCON, **kw)
    assert err and match in err and NAME in err and "ROADMAP B-M5" in err
    assert f"{PARALLEL} layers (layer_types)" in err
    assert refusal(MODEL_CONFIGS["test-tiny"], **kw) is None
    assert refusal(FALCON, mesh_shape={"data": 2}) is None


# ------------------------------------------------- the engine, by id stream
def _falcon_engine(**over):
    return _engine(NAME, **over)


@pytest.fixture(scope="module")
def falcon():
    return _falcon_engine()


def test_overlapped_against_serial_gives_the_same_ids(falcon, monkeypatch):
    """Six requests over four slots: spans of several lengths beside decode
    rows, the 32-token budget cuts prompts into chunks, slots free and are
    reused, fused k=4 scans between waves — pipelined and settled loops."""
    piped, settled, samples = both(falcon, _arrivals(), monkeypatch)
    assert piped == settled
    assert {s["mode"] for s in samples} == {"ragged", "decode"}
    rt = _rt(falcon)
    n = FALCON.num_layers
    assert rt.cache.slot_state.ssm is not None
    assert rt.cache.slot_state.conv.shape == (n, 3, 4, FALCON.ssm_conv_dim)
    assert rt.cache.slot_state.ssm.shape == (n, 5, DS, H * DH)
    assert rt.cache.kc.shape[0] == n  # the SAME layers' K and V, in the pool
    held = rt.state_bytes
    assert held["ssm_state_bytes"] == n * 5 * DS * H * DH * 4
    assert rt.stats()["ssm_state_bytes"] == held["ssm_state_bytes"]
    assert held["lin_state_bytes"] == 0
    assert not any("lin_step_rows" in s or "conv_state_resets" in s
                   for s in samples)
    ragged = [s for s in samples if s["mode"] == "ragged"]
    assert sum(s["ssm_state_resets"] for s in ragged) == 6  # one a request
    assert sum(s["ssm_state_carried"] for s in ragged) > 6  # later chunks
    assert sum(s["ssm_span_tokens"] for s in ragged) \
        >= sum(len(p) for _, _, p, _ in _arrivals()) - 6
    assert any(s["ssm_step_rows"] for s in ragged)  # decode rows in a wave
    assert all("attn_pairs" in s for s in samples)  # ...and its attention's
    for s in samples:
        if s["mode"] == "decode":  # a scan: its slots x its passes
            assert s["ssm_state_resets"] == 0 and s["ssm_span_tokens"] == 0
            assert s["ssm_step_rows"] == s["ssm_state_carried"] * s["k_cap"]


def test_a_reused_slot_gives_the_ids_a_fresh_engine_gives(falcon, monkeypatch):
    """A second request in a slot the first left: the program opens the
    slot's state at zero (`is_first`), no host call clears it."""
    probe = (0, "probe", _prompt(4, 19), SamplingParams(max_tokens=12))
    fresh, _ = drive(_falcon_engine(), [probe], False, monkeypatch)
    eng = falcon  # whatever the tests before left in its slots
    first = (0, "first", _prompt(2, 37), SamplingParams(max_tokens=11))
    drive(eng, [first], False, monkeypatch)
    rt = _rt(eng)
    assert np.abs(np.asarray(rt.cache.slot_state.ssm[:, 0])).max() > 0
    reused, _ = drive(eng, [probe], False, monkeypatch)
    assert reused["probe"] == fresh["probe"]
    assert len(reused["probe"][0]) == 12


def test_the_runtime_refuses_them_at_construction():
    with pytest.raises(ValueError, match="--spec"):
        _falcon_engine(spec=True, spec_k=3)
    with pytest.raises(ValueError, match="--tp / --ep"):
        _falcon_engine(tp=2)
    with pytest.raises(ValueError, match="--prefix-cache"):
        _falcon_engine(prefix_cache=True)
    err = validate_quant_config("int8", "bfloat16", model_names=(NAME,))
    assert err and "int8" in err and NAME in err and PARALLEL in err


def test_migration_is_refused_not_served_without_the_state(falcon):
    from ollamamq_tpu.engine.engine import MigrationError

    rt = _rt(falcon)
    with pytest.raises(MigrationError, match=f"{PARALLEL} layers' state"):
        rt.import_request({"kind": "stream"}, None)
    assert falcon.export_prefix(NAME, _prompt(1, 40)) is None


def test_gauges_and_counters_size_a_deployment(falcon, monkeypatch):
    from ollamamq_tpu.telemetry import schema as tm

    eng = falcon

    def value(series, model):
        return next(c.value for labels, c in series.series()
                    if model in labels)

    n = FALCON.num_layers
    # K and V of every layer: 2 K/V heads of 16 lanes, float32 here
    assert value(tm.KV_BYTES_PER_TOKEN, NAME) == n * 2 * FALCON.kv_dim * 4
    assert value(tm.HBM_SSM_STATE_BYTES, NAME) == n * 5 * DS * H * DH * 4
    assert value(tm.HBM_CONV_STATE_BYTES, NAME) \
        == n * 3 * 4 * FALCON.ssm_conv_dim * 4
    assert value(tm.HBM_LIN_STATE_BYTES, NAME) == 0
    series = (tm.SSM_STATE_RESETS_TOTAL, tm.SSM_STATE_CARRIED_TOTAL,
              tm.SSM_STEP_ROWS_TOTAL, tm.SSM_SPAN_TOKENS_TOTAL)
    before = [value(c, NAME) for c in series]
    _, samples = drive(eng, _arrivals(n=2), False, monkeypatch)
    after = [value(c, NAME) for c in series]
    for i, field in enumerate(("ssm_state_resets", "ssm_state_carried",
                               "ssm_step_rows", "ssm_span_tokens")):
        assert after[i] - before[i] == sum(s[field] for s in samples) > 0


def test_the_stages_are_named_on_the_lowered_programs(params):
    """The mixer's four scopes beside the attention's four, inside one
    layer, in the ragged and the decode program."""
    st = state(FALCON)
    toks = jnp.zeros((B,), jnp.int32)
    text = jax.jit(lambda p, kc, vc, slot: llama.forward_decode(
        p, FALCON, toks, toks, kc, vc, jnp.asarray(page_table()), PS,
        conv_state=slot)).lower(params, *st).as_text(debug_info=True)
    for scope in llama.SSM_SCOPES + ("attn_qkv", "attention", "attn_out"):
        assert f"/{scope}/" in text or f"{scope}/" in text, scope
