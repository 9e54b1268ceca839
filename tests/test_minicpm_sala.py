"""MiniCPM-SALA (block-sparse attention over K/V pages — InfLLM-V2: pooled
keys, a block score a kv group, the top blocks, NoPE, an output gate — beside
lightning linear attention with a constant decay a head and layer; the
family's muP scalars) on the served path, held to its plain float32 reference.

The reference is the benchmark's (`benchmarks/reference/minicpm_sala_decoder.py`):
one sequence, a Python loop over the layers, the pooled keys as means over
slices of the sequence's own k, a dense score -> block mask -> masked softmax,
the recurrence token by token. The system's side is the real thing:
`forward_ragged` over a prompt in chunks, then decode passes, through the
paged pool, the pooled-key pool under the same page table, the block lists
and the lightning layers' float32 state. LOGITS are compared, not sampled
ids, in float32: two orders of summation (pages, chunks, a compacted walk and
carried state against one dense pass) differ by ~5e-6 to 1e-5 of logits whose
spread is ~1 (measured), so ATOL 2e-4 (test_lfm2's), twenty times that; the
same path with the lightning state rounded to bfloat16 between passes misses
it (asserted), and so does every ablation below. A block chosen differently
moves a logit by ~1e-2 (the ablations): the tolerance would not hide one.
"""

import dataclasses
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ollamamq_tpu.config import (LINEAR, MODEL_CONFIGS, SPARSE, ModelConfig)
from ollamamq_tpu.engine.kv_cache import refusal
from ollamamq_tpu.engine import step_work
from ollamamq_tpu.models import llama
from ollamamq_tpu.ops import block_select as bs
from ollamamq_tpu.ops import gated_delta as gd
from ollamamq_tpu.ops.sampling import SamplingParams
from ollamamq_tpu.telemetry import mfu
from test_lfm2 import (ATOL, B, PS, close, decode_scan, page_table,
                       ragged_step, seq_tokens)
from test_step_overlap import _engine, _prompt, _rt, both
from testutil import (minicpm_sala_keys, minicpm_sala_reference,
                      once_a_sequence)

NAME = "test-tiny-minicpm-sala"
SALA = MODEL_CONFIGS[NAME]
Z = bs.Sizes.of(SALA)
MP = 20  # pages a sequence: 160 positions, past sparse_dense_len (64)
NP = 1 + B * MP
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def params():
    return llama.init_params(SALA, jax.random.PRNGKey(0), dtype=jnp.float32)


@once_a_sequence
def want(mc, params, tokens):
    """The reference's ONE full forward: [T, V] logits."""
    return np.asarray(minicpm_sala_reference().logits(
        minicpm_sala_keys(mc), params, jnp.asarray(tokens, jnp.int32)))


def state(mc=SALA, garbage=0.0):
    """(kc, vc, slot state): empty pools and a lightning state that an
    earlier request left full of `garbage`."""
    kv = jnp.zeros((mc.cache_layers, NP * PS, mc.kv_dim), jnp.float32)
    slot = llama.alloc_slot_state(mc, B, jnp.float32,
                                  pooled_rows=mc.pooled_rows(NP, PS))
    return kv, kv, slot._replace(rule=slot.rule + garbage)


def step(params, st, spans, pad_to=64, impl="jnp", mc=SALA):
    return ragged_step(mc, params, st, spans, pad_to=pad_to, mp=MP, impl=impl)


# ------------------------------------------------------- the registered family
def test_the_registered_family_and_its_plan(params):
    assert SALA.layer_types == (SPARSE, LINEAR, LINEAR, SPARSE, SPARSE, LINEAR)
    assert [(first, len(period), n) for first, period, n
            in SALA.layer_plan()] == [(0, 1, 1), (1, 1, 2), (3, 1, 2),
                                      (5, 1, 1)]  # no period: four runs
    assert SALA.cache_layers == SALA.paged_layers == SALA.attn_layers == 3
    assert SALA.attn_output_gate and not SALA.rotates(SPARSE)
    assert SALA.embedding_multiplier == 12 and SALA.lm_head_multiplier == 0.25
    assert SALA.residual_multiplier == pytest.approx(1.4 / 8 ** 0.5)
    leaves = sum(a.size for a in jax.tree_util.tree_leaves(params))
    assert leaves == SALA.param_count()
    st = state()[2]
    assert st.conv is None and st.ring is None and st.ssm is None
    assert st.rule.shape == (3, B + 1, 16, 8 * 16) \
        and st.rule.dtype == jnp.float32
    assert st.pooled.shape == (3, NP * (PS // Z.stride), SALA.kv_dim)
    big = MODEL_CONFIGS["minicpm-sala:9b"]
    assert big.param_count() == 9_477_206_016
    assert [i for i, k in enumerate(big.layer_types) if k == SPARSE] \
        == [0, 9, 16, 17, 22, 29, 30, 31]
    # attention FLOPs a token stop growing at the kept blocks' keys
    assert mfu.flops_per_token(big, 100_000) - mfu.flops_per_token(big, 50_000) \
        == pytest.approx(8 * big.q_dim * 2.0 * 50_000 / 16)


def test_the_catalog_row_builds_the_published_model():
    """The catalog row's `config` through the harness's own mapping of keys
    to fields, with the family's seven sparse sizes beside it."""
    from benchmarks import serve

    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, filter(str.strip, f))
                   if r["name"] == "MiniCPM-SALA")
    sparse = {f"sparse_{k}": getattr(MODEL_CONFIGS["minicpm-sala:9b"],
                                     f"sparse_{k}")
              for k in ("kernel_size", "kernel_stride", "block_size", "topk",
                        "init_blocks", "window_size", "dense_len")}
    mc = serve.model_config({"name": "minicpm-sala:9b", **row["config"],
                             **sparse}, rehearse=False)
    assert mc == MODEL_CONFIGS["minicpm-sala:9b"]
    with pytest.raises(serve.Refused, match="sparse_topk|sparse_"):
        serve.model_config({"name": "x", **row["config"]}, rehearse=False)


@pytest.mark.parametrize("edit,match", [
    (dict(layer_types=(LINEAR,) * 6), "layer_types does not agree"),
    (dict(mixer_types=("minicpm4", "mamba") * 3), "mixer_types holds"),
    (dict(sparse_kernel_size=12), "a pooling kernel of two strides"),
    (dict(sparse_dense_len=48), "at least sparse_topk blocks"),
    (dict(sparse_window_size=64), "fits sparse_topk"),
    (dict(lightning_nkv=4), "as many key/value heads"),
    (dict(lightning_scale="1"), "lightning_scale"),
    (dict(use_output_norm=False), "use_output_norm"),
    (dict(use_output_gate=False), "use_output_gate"),
    (dict(rand_init=True), "rand_init"),
    (dict(layer_offset=3), "not within the published"),
    (dict(qk_norm=False), "per-head q/k norm"),
    (dict(attn_bias=True), "only attention kind"),
    (dict(mixer_types=("lightning-attn",) * 6), "with no 'sparse_attention'"),
], ids=lambda v: next(iter(v)) if isinstance(v, dict) else None)
def test_a_stack_the_program_cannot_run_is_refused_at_construction(edit, match):
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(SALA, **{"layer_types": None, **edit})


@pytest.mark.parametrize("kw,match", [
    (dict(spec=True), "--spec: a rejected draft"),
    (dict(mesh_shape={"tensor": 2}), "--tp / --ep: the pooled-key pool"),
    (dict(mesh_shape={"expert": 2}), "--tp / --ep: the pooled-key pool"),
    (dict(kv_dtype="int8"), "--kv-dtype int8: a pooled key"),
    (dict(prefix_cache=True), "--prefix-cache: a cached page"),
], ids=["spec", "tp", "ep", "int8", "prefix_cache"])
def test_features_that_know_only_the_kv_pool_are_refused(kw, match):
    err = refusal(SALA, **kw)
    assert err and match in err and NAME in err and "ROADMAP B-M10" in err
    assert refusal(SALA, mesh_shape={"data": 2}) is None
    with pytest.raises(ValueError, match="has no form for sparse_attention"):
        llama.forward_prefill(None, SALA, jnp.zeros((1, 4), jnp.int32), None,
                              None, None, None, PS)


# ----------------------------------------- the served path against the reference
def test_prefill_in_chunks_then_decode_through_the_cache(params):
    """A prompt in spans over a slot an earlier request left dirty — the
    second crosses `sparse_dense_len` mid-chunk —, a one-token row past it,
    another span, then a fused scan of decode passes: every logit the
    reference's. The jnp path and the Pallas path (interpret mode: the
    select kernel, the walk over the kept blocks' pages, the lightning step
    kernel, the ragged kernel)."""
    toks = seq_tokens(1, 150)
    ref = want(SALA, params, toks)
    for impl in ("jnp", "pallas"):
        st = state(garbage=3.0)
        out, st, _ = step(params, st, [(0, toks[:50], 0)], impl=impl)
        close(out[0], ref[49])
        out, st, _ = step(params, st, [(0, toks[50:90], 50)], impl=impl)
        close(out[0], ref[89])
        out, st, _ = step(params, st, [(0, toks[90:91], 90)], impl=impl)
        close(out[0], ref[90])
        out, st, _ = step(params, st, [(0, toks[91:120], 91)], impl=impl)
        close(out[0], ref[119])
    got, st = decode_scan(SALA, params, st, {0: (toks[120:140], 120)}, [0],
                          mp=MP)
    close(got[0], ref[120:140])


def test_a_context_that_crosses_dense_len_mid_decode(params):
    """Decode passes from context 58 to 74: the rows walk their own pages up
    to `sparse_dense_len` (64) and their block lists past it."""
    toks = seq_tokens(2, 80)
    ref = want(SALA, params, toks)
    st = state()
    out, st, _ = step(params, st, [(0, toks[:57], 0)])
    close(out[0], ref[56])
    got, st = decode_scan(SALA, params, st, {0: (toks[57:74], 57)}, [0],
                          mp=MP)
    close(got[0], ref[57:74])


def test_a_ragged_step_of_mixed_spans(params):
    """One-token rows past `sparse_dense_len` (their block lists) beside a
    dense one-token row and longer spans (dense, and under block masks) in
    ONE stream, rows that open and rows that continue."""
    a, b, c = seq_tokens(3, 120), seq_tokens(4, 120), seq_tokens(5, 120)
    ra, rb, rc = (want(SALA, params, t) for t in (a, b, c))
    st = state(garbage=-2.0)
    out, st, _ = step(params, st, [(0, a[:100], 0), (1, b[:20], 0)],
                      pad_to=128)
    close(out[0], ra[99])
    close(out[1], rb[19])
    out, st, _ = step(params, st, [
        (0, a[100:101], 100), (1, b[20:21], 20), (2, c[:40], 0)])
    close(out[0], ra[100])
    close(out[1], rb[20])
    close(out[2], rc[39])
    spans = [(0, a[101:102], 101), (1, b[21:70], 21), (2, c[40:41], 40)]
    out, after, _ = step(params, st, spans)
    close(out[0], ra[101])
    close(out[1], rb[69])
    close(out[2], rc[40])
    got, kernels, _ = step(params, st, spans, impl="pallas")
    for row in range(3):
        close(got[row], np.asarray(out[row]))
    close(kernels[2].rule[:, :3], np.asarray(after[2].rule[:, :3]), atol=5e-4)
    close(kernels[2].pooled, np.asarray(after[2].pooled), atol=1e-6)


def test_a_bfloat16_state_misses_the_tolerance(params):
    """The limit tells a float32 accumulator from a bfloat16 one: the same
    passes with the lightning state rounded between them."""
    toks = seq_tokens(6, 48)
    ref = want(SALA, params, toks)
    st = state()
    _, st, _ = step(params, st, [(0, toks[:32], 0)])
    worst = 0.0
    for i in range(32, 44):
        kc, vc, slot = st
        st = (kc, vc, slot._replace(rule=slot.rule.astype(
            jnp.bfloat16).astype(jnp.float32)))
        got, st = decode_scan(SALA, params, st, {0: (toks[i:i + 1], i)}, [0],
                              mp=MP)
        worst = max(worst, float(np.abs(np.asarray(got[0]) - ref[i]).max()))
    assert worst > 5 * ATOL, worst


# --------------------------------------------- the selection and the pooled keys
def _qk(seed, n):
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    return (jax.random.normal(ks[0], (n, SALA.num_heads, SALA.head_dim)),
            jax.random.normal(ks[1], (n, SALA.num_kv_heads, SALA.head_dim)))


def _paged(k, row=1):
    """k [T, Hk, hd] as row `row`'s pages of a K pool, and the pooled pool
    its tokens complete, written in two steps."""
    t = k.shape[0]
    pt = jnp.asarray(page_table(MP))
    pos = jnp.arange(t, dtype=jnp.int32)
    slots = pt[row][pos // PS] * PS + pos % PS
    pool = jnp.zeros((2, NP * PS, SALA.kv_dim)).at[1, slots].set(
        k.reshape(t, -1))
    pooled = jnp.full((2, NP * (PS // Z.stride), SALA.kv_dim), jnp.nan)
    rows = jnp.broadcast_to(pt[row], (t, MP))
    for part in (pos < 14, pos >= 14):  # two steps: 0..13, then the rest
        pooled = bs.write_pooled(pooled, pool, 1, rows, pos, part, Z, PS)
    return pool, pooled, pt


def test_a_pooled_row_straddles_two_pages_and_two_steps():
    """Row j is the mean of k[4 j : 4 j + 8]: row 1 (positions 4..11) lies in
    pages 0 and 1; row 2 (8..15) is completed by position 15 in the SECOND
    step from keys the first wrote (8..13) and its own; no row is written
    before its last key, none after the sequence's end."""
    _, k = _qk(0, 37)
    _, pooled, pt = _paged(k)
    n_rows = (37 - Z.kernel) // Z.stride + 1
    at = bs.pooled_slots(pt[1], jnp.arange(n_rows + 2), Z.stride, PS)
    got = np.asarray(pooled[1, at])
    mean = np.stack([np.asarray(k[4 * j: 4 * j + 8]).mean(0).reshape(-1)
                     for j in range(n_rows)])
    np.testing.assert_allclose(got[:n_rows], mean, atol=1e-6)
    assert np.isnan(got[n_rows:]).all()  # not yet defined: never written
    assert np.isnan(np.asarray(pooled[0])[2:]).all()  # the other layer's


def test_the_block_list_is_the_references_mask():
    """`select`'s kept ids, a (query, kv head), against the reference's
    block mask over the same q and k: equal as sets — past
    `sparse_dense_len` the init block, the two local blocks and ONE block
    the score chooses; and the walk's table lists exactly those blocks'
    pages, its length the kept blocks' keys up to the query."""
    ref = minicpm_sala_reference()
    keys = minicpm_sala_keys(SALA)
    t = 150
    q, k = _qk(1, t)
    pool, pooled, pt = _paged(k)
    nb = -(-t // Z.block)
    pos = jnp.arange(t)
    mask = np.asarray(ref.block_mask(keys, lambda x: x, q,
                                     ref.pooled_keys(keys, k), pos, nb))
    J = MP * PS // Z.stride
    pk = pooled[1, bs.pooled_slots(pt[1], jnp.arange(J), Z.stride, PS)
                ].reshape(J, SALA.num_kv_heads, SALA.head_dim)
    past = np.arange(t) + 1 > Z.dense_len
    ids, count = bs.select(q, jnp.nan_to_num(pk), pos + 1, Z)
    ids, count = np.asarray(ids), np.asarray(count)
    chosen = set()
    for i in np.flatnonzero(past):
        assert count[i] == Z.topk
        for g in range(SALA.num_kv_heads):
            kept = set(ids[i, g][ids[i, g] >= 0].tolist())
            assert kept == set(np.flatnonzero(mask[i, g]).tolist()), (i, g)
            own = i // Z.block
            assert {0, own - 1, own} <= kept and len(kept) == Z.topk
            chosen |= kept - {0, own - 1, own}
    assert len(chosen) > 3  # the score decides, and not always alike
    # ...and the span form's mask (no sort: a block's rank by comparisons),
    # with every block at or under dense_len, is the reference's whole
    kept = bs.select_mask(q, jnp.nan_to_num(pk), pos + 1, Z)[..., :nb]
    assert (np.asarray(bs.block_mask(kept, pos + 1, Z)) == mask).all()
    rows = np.flatnonzero(past)[::9][:B]  # B queries as one-token rows
    table, lens = bs.walk_table(
        jnp.broadcast_to(pt[1], (len(rows), MP)), jnp.asarray(ids[rows]),
        jnp.asarray(count[rows]), jnp.asarray(rows + 1),
        jnp.ones(len(rows), bool), Z, PS)
    table, lens = np.asarray(table), np.asarray(lens)
    per = Z.block // PS
    assert table.shape[1] == bs.walk_width(Z, PS, MP) and per == 2
    for r, i in enumerate(rows):
        for g in range(SALA.num_kv_heads):
            want_pages = [int(pt[1][b * per + p]) for b in ids[i, g]
                          for p in range(per)]
            row = table[r * SALA.num_kv_heads + g]
            assert row[:Z.topk * per].tolist() == want_pages
            assert not row[Z.topk * per:].any()  # the trash page
            assert lens[r * SALA.num_kv_heads + g] \
                == (Z.topk - 1) * Z.block + i % Z.block + 1 \
                <= Z.topk * Z.block


# ------------------------------------ the recurrence's forms and its decay
def _serial(q, k, v, lam):
    """S = lam S + k v^T; o = S^T q, token by token: q, k, v [T, H, d]."""
    s = np.zeros((q.shape[1], q.shape[2], v.shape[2]))
    out = []
    for t in range(q.shape[0]):
        s = lam[:, None, None] * s + k[t][:, :, None] * v[t][:, None, :]
        out.append(np.einsum("hkd,hk->hd", s, q[t]))
    return np.stack(out), s


def test_the_lightning_forms_are_the_token_serial_scan():
    """`chunked`, `ragged` (a span through the windows, then one-token rows
    through `step`) and `decode` of the plain recurrence under a CONSTANT
    decay a head, against the scan of the two lines above."""
    H, d, t = SALA.lightning_nh, SALA.lightning_head_dim, 90
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q, k, v = (jax.random.normal(key, (t, H, d)) for key in ks)
    slopes = np.asarray(SALA.lightning_slopes[1])
    g = jnp.broadcast_to(-jnp.asarray(slopes, jnp.float32), (t, H))
    ones = jnp.ones_like(g)
    o_want, s_want = _serial(*(np.asarray(a, np.float64) for a in (q, k, v)),
                             np.exp(-slopes.astype(np.float64)))
    o, s = gd.chunked(q[None], k[None], v[None], g[None], ones[None],
                      plain=True)
    close(o[0], o_want, atol=1e-4)
    state0 = jnp.full((2, B + 1, d, H * d), 7.0)  # garbage: opened at zero
    rows = jnp.zeros(B, jnp.int32)
    one = lambda *x: jnp.asarray(x + (0,) * (B - len(x)), jnp.int32)
    o1, st = gd.ragged(q[:70], k[:70], v[:70], g[:70], ones[:70], state0, 1,
                       one(2, B, B, B), jnp.zeros(70, jnp.int32),
                       jnp.arange(70), one(0, 70, 70, 70), one(70),
                       one(1), plain=True)
    close(o1, o_want[:70], atol=1e-4)
    o2, st = gd.ragged(q[70:71], k[70:71], v[70:71], g[70:71], ones[70:71],
                       st, 1, one(2, B, B, B), rows[:1], jnp.asarray([70]),
                       one(0, 1, 1, 1), one(1), one(0), plain=True)
    close(o2, o_want[70:71], atol=1e-4)
    for i in range(71, t):
        pad = lambda x: jnp.zeros((B,) + x.shape[1:]).at[2].set(x[i])
        o3, st = gd.decode(pad(q), pad(k), pad(v), pad(g), pad(ones), st, 1,
                           active=one(0, 0, 1), plain=True)
        close(o3[2], o_want[i], atol=1e-4)
    got = np.asarray(st[1, 2]).reshape(d, H, d).transpose(1, 0, 2)
    close(got, s_want, atol=1e-4)
    assert (np.asarray(st[0]) == 7.0).all()  # the other layer's rows


def test_the_decay_is_a_constant_of_head_and_published_layer():
    """s[h] = 2^(-8 (h + 1) / H) (1 - l / (L - 1) + 1e-5), l the layer's
    PUBLISHED index (layer_offset 1: layers 1 .. 6 of 8): the program's
    table, the reference's, and — through the logits above — the formula
    `_lightning_op` traces on the layer's index in the stack."""
    ref = minicpm_sala_reference()
    keys = minicpm_sala_keys(SALA)
    linear = [i for i, k in enumerate(SALA.layer_types) if k == LINEAR]
    assert linear == [1, 2, 5] and len(SALA.lightning_slopes) == 3
    for row, layer in zip(SALA.lightning_slopes, linear):
        want_ = [2.0 ** (-8.0 * (h + 1) / 8) * (1 - (1 + layer) / 7 + 1e-5)
                 for h in range(8)]
        np.testing.assert_allclose(row, want_, rtol=1e-12)
        np.testing.assert_allclose(ref.slopes(keys, layer), row, rtol=1e-6)
    shifted = dataclasses.replace(SALA, layer_offset=2, layer_types=None)
    assert shifted.lightning_slopes[0][0] < SALA.lightning_slopes[0][0]


# ----------------------------------- wrong forwards MUST miss the tolerance
ABLATION_TOKENS = seq_tokens(8, 100)


def _last_logits(mc, params, patch=None):
    """The program's forward of the ablations' prompt in ONE ragged step (its
    tokens past 64 under their block masks), traced anew: a cached program
    would not see a patch."""
    from testutil import span_stream

    pt = page_table(MP)
    stream, (q_start, q_len, kv_len) = span_stream(
        [(0, ABLATION_TOKENS, 0)], 128, pt, PS)
    slot_ids = jnp.asarray(np.where(q_len > 0, np.arange(B), B), jnp.int32)
    out_idx = jnp.asarray(np.clip(q_start + q_len - 1, 0, 127))
    first = jnp.asarray(q_len > 0, jnp.int32)
    stream = tuple(map(jnp.asarray, stream))
    pt, q_start, q_len, kv_len = map(jnp.asarray, (pt, q_start, q_len, kv_len))
    kc, vc, slot = state(mc)

    def run(p, kc, vc, slot):
        return llama.forward_ragged(
            p, mc, *stream, out_idx, kc, vc, pt, q_start, q_len, kv_len, PS,
            conv_state=slot, slot_ids=slot_ids, is_first=first)[0][0]

    return np.asarray(jax.jit(run)(params, kc, vc, slot))


@pytest.fixture(scope="module")
def served(params):
    got = _last_logits(SALA, params)
    close(got, want(SALA, params, ABLATION_TOKENS)[99])
    return got


def _sizes(**edit):
    return classmethod(lambda cls, cfg: Z._replace(**edit))


ABLATIONS = {
    "no_attention_gate": dict(cfg=dict(attn_use_output_gate=False,
                                       attn_output_gate=False)),
    "no_residual_multiplier": dict(cfg=dict(scale_depth=0.0)),
    "no_logits_divisor": dict(cfg=dict(lm_head_multiplier=1.0,
                                       dim_model_base=0)),
    "no_embedding_scale": dict(cfg=dict(embedding_multiplier=1.0,
                                        scale_emb=1.0)),
    "the_cut_stacks_own_layer_index": dict(cfg=dict(layer_offset=0)),
    "rope_on_the_attention_layers": dict(cfg=dict(attn_use_rope=True)),
    "no_rope_on_the_lightning_layers": dict(cfg=dict(lightning_use_rope=False)),
    "no_init_block": dict(sizes=dict(init_blocks=0)),
    "no_local_blocks": dict(sizes=dict(local_blocks=0)),
    "dense_everywhere": dict(sizes=dict(dense_len=10 ** 6)),
}


@pytest.mark.parametrize("name", sorted(ABLATIONS))
def test_a_wrong_forward_misses_the_tolerance(name, params, served,
                                              monkeypatch):
    """Each case leaves ONE thing of the family out of the PROGRAM's forward
    on the same weights — a gate, a muP scalar, the published layer index,
    the init or the local blocks, the selection itself: its logits leave the
    served path's (which agree with the reference: `served`) by far more
    than the tolerance."""
    case = ABLATIONS[name]
    mc = dataclasses.replace(SALA, layer_types=None, **case.get("cfg", {}))
    if "sizes" in case:
        monkeypatch.setattr(bs.Sizes, "of", _sizes(**case["sizes"]))
    miss = float(np.abs(_last_logits(mc, params) - served).max())
    assert miss > 10 * ATOL, (name, miss)


# --------------------------------------------- a step's work, from its shape
def test_the_counters_follow_from_a_steps_composition():
    big = MODEL_CONFIGS["minicpm-sala:9b"]
    kinds = [k for k, v in step_work.KINDS.items() if v.present(big)]
    assert kinds == ["lightning", "bsa"]
    assert [k for k, v in step_work.KINDS.items()
            if v.present(MODEL_CONFIGS["test-tiny-olmo-hybrid"])][:2] \
        == ["lin", "attn"]
    ragged = step_work.Step([1, 496, 1], [9000, 8300, 8192], None, False, 512,
                            0, None)
    ctx_s, ctx_p, kept_s, kept_p, walk_s, walk_p, dense, rows = \
        step_work.bsa_counts(big, 32, ragged)
    assert (ctx_s, kept_s, walk_s) == (141, 64, 64)  # ceil(9000 / 64); 64
    assert kept_p == 108 * 64  # the span's tokens at contexts 8193..8300
    assert walk_p == ctx_p == 64 * 129 + 44 * 130  # ...of 129, then 130 blocks
    assert dense == 388 + 1 and rows == 31 + 1  # 7805..8192; (p+1) % 16 == 0
    scan = step_work.Step([8, 8], [9007, 100], None, True, 0, 0, None)
    got = step_work.bsa_counts(big, 32, scan)
    assert got[:6] == (8 * 141, 0, 8 * 64, 0, 8 * 64, 0) and got[6] == 8
    assert step_work.slot_state_counts(big, 32, scan) == (0, 2, 16, 0, 0)
    # the span lies on stream tokens 1 .. 496: windows 0 .. 7 of the stream
    assert step_work.slot_state_counts(big, 32, ragged) == (0, 3, 2, 496, 8)


# ------------------------------------------------- the engine, by id stream
@pytest.fixture(scope="module")
def sala():
    return _engine(NAME)


def _arrivals():
    lens, out = (70, 12, 90, 33, 81), (14, 9, 20, 11, 16)
    return [(2 * i, f"u{i}", _prompt(i, lens[i]),
             SamplingParams(max_tokens=out[i])) for i in range(5)]


def test_overlapped_against_serial_gives_the_same_ids(sala, monkeypatch):
    """Five requests over four slots, three of them past `sparse_dense_len`:
    the 32-token budget cuts prompts into chunks beside decode rows, slots
    free and are reused, fused k=4 scans between waves — pipelined and
    settled loops give the same ids; the samples carry the work account."""
    piped, settled, samples = both(sala, _arrivals(), monkeypatch)
    assert piped == settled
    assert {s["mode"] for s in samples} == {"ragged", "decode"}
    rt = _rt(sala)
    assert rt.cache.slot_state.rule.shape == (3, 5, 16, 128)
    assert rt.cache.slot_state.pooled.shape == (3, 96 * 2, SALA.kv_dim)
    assert rt.cache.kc.shape[0] == 3
    held = rt.state_bytes
    assert held["lin_state_bytes"] == 3 * 5 * 16 * 128 * 4
    assert held["bsa_pooled_bytes"] == 3 * 192 * SALA.kv_dim * 4
    assert not any("lin_step_rows" in s or "attn_pairs" in s
                   for s in samples)
    ragged = [s for s in samples if s["mode"] == "ragged"]
    assert sum(s["lightning_state_resets"] for s in ragged) == 5
    assert any(s["lightning_step_rows"] for s in ragged)
    assert sum(s["bsa_blocks_kept_step"] for s in samples) > 0
    assert sum(s["bsa_blocks_kept_span"] for s in samples) > 0
    for s in samples:  # a one-token row's walk follows its list
        assert s["bsa_blocks_walked_step"] == s["bsa_blocks_kept_step"]
        assert s["bsa_blocks_walked_span"] == s["bsa_blocks_in_context_span"]
        if s["mode"] == "decode":
            assert s["lightning_span_tokens"] == 0 \
                and s["bsa_blocks_kept_span"] == 0


def test_the_runtime_refuses_them_at_construction():
    with pytest.raises(ValueError, match="--spec"):
        _engine(NAME, spec=True, spec_k=3)
    with pytest.raises(ValueError, match="--tp / --ep"):
        _engine(NAME, tp=2)
