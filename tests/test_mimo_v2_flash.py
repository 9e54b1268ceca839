"""MiMo-V2-Flash on the served path (PR 65): window and full attention in one
stack AT DIFFERENT HEAD SHAPES — a full layer's K/V in the paged pool at its
kv-head count, a window layer's in a per-slot ring at another; key heads wider
than value heads, so pool and rings hold K rows and V rows of different
widths; a partial rotary embedding at a base a kind; a learned sink in the
window layers' softmax; v times `attention_value_scale` — a leading dense
layer, a chip's share of sigmoid-routed experts with a selection bias, no
shared expert, no scale.

LOGITS of the served forwards against the benchmark's plain float32
reference (benchmarks/reference/mimo_v2_flash_decoder.py) at
`test-tiny-mimo-v2-flash`, seeded random weights, float32, on the CPU: two
orders of summation (pages, ring rows and chunks against one dense pass; the
sink as a last term against a concatenated column; grouped against per-expert
matmuls) differ by ~1e-5 of logits whose spread is ~1, so ATOL 2e-4 (the
K-EXAONE file's, and testutil's floor for a reference fed other lengths);
every departure the seeded weights are drawn to catch misses by 50 times
that (asserted). Contexts run to 150 tokens over a ring of 32 rows: every
ring row is overwritten four times. (The kernels at the published head shapes
against their jnp twins: test_mimo_v2_flash_kernels.py.)"""

import dataclasses
import functools
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ollamamq_tpu.config import (ATTENTION, EXPERTS, MODEL_CONFIGS, WINDOW,
                                 ModelConfig)
from ollamamq_tpu.engine.kv_cache import refusal
from ollamamq_tpu.engine.step_work import KINDS, StepWork
from ollamamq_tpu.models import llama, moe
from ollamamq_tpu.ops import attention
from ollamamq_tpu.telemetry import mfu
import test_lfm2
from test_lfm2 import close, seq_tokens
from testutil import (_reference, moe_mlp, once_a_sequence, prefill,
                      seeded_params)

NAME = "test-tiny-mimo-v2-flash"
MM = MODEL_CONFIGS[NAME]
PS, MP, B, PAD = 8, 24, 4, 16   # page size, pages a row, rows, a step's rung
RING = MM.ring_rows(PAD, PS)    # 8 + 16 + 8 = 32 rows: four pages
ATOL = 2e-4
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FILE = os.path.join(_REPO, "benchmarks", "configs",
                    "mimo-v2-flash-ep16-d7.json")


def keys(mc) -> dict:
    """What a configuration file says of the ModelConfig `mc`: all that the
    reference reads, under the published spellings."""
    dense = mc.num_dense_layers
    return {
        "num_attention_heads": mc.num_heads,
        "num_key_value_heads": mc.num_kv_heads,
        "swa_num_key_value_heads": mc.swa_num_key_value_heads,
        "head_dim": mc.head_dim, "v_head_dim": mc.v_head_dim,
        "hidden_size": mc.hidden_size,
        "intermediate_size": mc.intermediate_size,
        "layernorm_epsilon": mc.rms_norm_eps,
        "rope_theta": mc.rope_theta, "swa_rope_theta": mc.swa_rope_theta,
        "partial_rotary_factor": mc.partial_rotary_factor,
        "attention_value_scale": mc.attention_value_scale,
        "hybrid_layer_pattern": list(mc.hybrid_layer_pattern),
        "moe_layer_freq": [0] * dense + [1] * (mc.num_layers - dense),
        "sliding_window": mc.sliding_window,
        "add_swa_attention_sink_bias": mc.add_swa_attention_sink_bias,
        "scoring_func": mc.router_score, "topk_method": "noaux_tc",
        "n_routed_experts": mc.num_experts,
        "router_experts": mc.router_width,
        "expert_offset": mc.expert_offset,
        "num_experts_per_tok": mc.num_experts_per_tok,
        "norm_topk_prob": mc.norm_topk_prob,
        "moe_intermediate_size": mc.moe_intermediate_size,
        "vocab_size": mc.vocab_size}


def make_params(mc=MM, seed=0):
    """(sink logits and selection bias are drawn non-zero by `init_params`)"""
    return seeded_params(mc, ("attn_norm", "mlp_norm"), seed=seed)


@once_a_sequence
def want(mc, params, tokens):
    """The reference's ONE full forward: [T, V] logits."""
    return np.asarray(_reference("mimo_v2_flash_decoder").logits(
        keys(mc), params, jnp.asarray(tokens, jnp.int32)))


def state(mc=MM, garbage=0.0):
    """(kc, vc, SlotState): an empty pool — of the FULL layers only, K rows
    and V rows at their own widths — and rings that an earlier request left
    full of `garbage`."""
    kc, vc = (jnp.zeros((mc.count(ATTENTION), (1 + B * MP) * PS, lanes),
                        jnp.float32) for lanes in mc.kv_row_dims)
    st = llama.alloc_slot_state(mc, B, jnp.float32, ring_rows=RING)
    return kc, vc, jax.tree_util.tree_map(
        lambda a: a + jnp.asarray(garbage, a.dtype), st)


def oracle(mc, params, tokens):
    """The program's `forward_prefill` at the last position."""
    kc, vc, _ = state(mc)
    toks = jnp.asarray(tokens, jnp.int32)
    return np.asarray(prefill(
        params, mc, toks[None], jnp.asarray([len(tokens)]), kc, vc,
        jnp.asarray(page_table()[:1]), PS)[0][0])


# tests/test_lfm2.py's step and scan, at this file's rung and pages a row.
ragged_step = functools.partial(test_lfm2.ragged_step, pad_to=PAD, mp=MP)
decode_scan = functools.partial(test_lfm2.decode_scan, mp=MP)
page_table = functools.partial(test_lfm2.page_table, MP)


# ----------------------------------------------------------- the config
def test_the_tiny_family_and_its_plan():
    assert (MM.count(WINDOW), MM.count(ATTENTION), MM.count(EXPERTS)) \
        == (5, 2, 6)
    assert MM.attn_layers == 7 and MM.cache_layers == 2
    assert [(f, len(p), n) for f, p, n in MM.layer_plan()] \
        == [(0, 1, 1), (1, 1, 4), (5, 1, 1), (6, 1, 1)]
    full, win = MM.attn_shape(ATTENTION), MM.attn_shape(WINDOW)
    assert full == (4, 1, 24, 16, 5e6, False)
    assert win == (4, 2, 24, 16, 1e4, True)
    assert (full.q_lanes, full.k_lanes, full.v_lanes, full.o_lanes) \
        == (96, 24, 16, 64)
    assert MM.kv_row_dims == (24, 16) and MM.ring_row_dims == (48, 32)
    assert MM.per_kind_attention and MM.rotary_dim == 8
    # the published spellings fold into the program's fields
    assert (MM.layer_types, MM.num_dense_layers, MM.moe_layer_freq) == (
        (ATTENTION,) + (WINDOW,) * 4 + (ATTENTION, WINDOW), 1, 1)
    assert (MM.n_shared_experts, MM.routed_scaling_factor, MM.router_score,
            MM.use_expert_bias, MM.rms_norm_eps) \
        == (0, 1.0, "sigmoid", True, 1e-5)
    assert (MM.n_group, MM.topk_group) == (0, 0)  # one group: no limit
    hash(dataclasses.replace(MM))  # rebuilt from itself: a jit's static key
    # every other model has ONE head shape, whatever the kind
    kx = MODEL_CONFIGS["test-tiny-k-exaone"]
    assert not kx.per_kind_attention
    assert kx.attn_shape(WINDOW) == kx.attn_shape(ATTENTION) \
        == (4, 2, 16, 16, 10_000.0, False)
    assert kx.kv_row_dims == kx.ring_row_dims == (kx.kv_dim,) * 2


@pytest.mark.parametrize("factor,head,want_dim", [
    (0.334, 192, 64), (0.334, 24, 8), (0.25, 256, 64), (0.5, 16, 8),
    (1.0, 128, 128)])
def test_rotary_dim_floors_as_the_published_code_does(factor, head, want_dim):
    """int(head_dim x partial_rotary_factor): 0.334 x 192 is 64.128."""
    mc = dataclasses.replace(MODEL_CONFIGS["test-tiny"], head_dim=head,
                             partial_rotary_factor=factor)
    assert mc.rotary_dim == want_dim


@pytest.mark.parametrize("factor,head", [(0.334, 16), (0.1, 16), (0.2, 16),
                                         (1.5, 16), (0.0, 16)],
                         ids=["odd_5", "one_lane", "odd_3", "over", "zero"])
def test_an_odd_or_empty_rotary_width_is_still_refused(factor, head):
    with pytest.raises(ValueError, match="even number of lanes"):
        dataclasses.replace(MODEL_CONFIGS["test-tiny"], head_dim=head,
                            partial_rotary_factor=factor)


def test_the_presets_that_use_the_factor_read_as_before():
    assert MODEL_CONFIGS["qwen3-next:80b-a3b"].rotary_dim == 64
    assert MODEL_CONFIGS["test-tiny-qwen3-next"].rotary_dim == 8


def test_the_configuration_file_reaches_the_program_key_by_key():
    """Every key of the catalog's `config` is in the file under its
    published spelling and builds the ModelConfig the cell serves."""
    from benchmarks import serve

    with open(FILE) as f:
        cfg = json.load(f)
    mc = serve.model_config(cfg, rehearse=False)
    assert mc.layer_types == (ATTENTION,) + (WINDOW,) * 4 + (ATTENTION,
                                                             WINDOW)
    assert mc.attn_shape(ATTENTION) == (64, 4, 192, 128, 5_000_000, False)
    assert mc.attn_shape(WINDOW) == (64, 8, 192, 128, 10_000, True)
    assert (mc.hidden_size, mc.sliding_window, mc.rotary_dim,
            mc.attention_value_scale, mc.num_dense_layers,
            mc.intermediate_size) == (4096, 128, 64, 0.707, 1, 16384)
    assert (mc.num_experts, mc.router_width, mc.num_experts_per_tok,
            mc.expert_width, mc.shared_width) == (16, 256, 8, 2048, 0)
    assert (mc.router_score, mc.use_expert_bias, mc.norm_topk_prob,
            mc.routed_scaling_factor, mc.n_group, mc.norm_topk_eps) \
        == ("sigmoid", True, True, 1.0, 0, 1e-20)
    # pool and rings hold K and V at their own widths, whole lane tiles
    assert mc.kv_row_dims == (768, 512) and mc.ring_row_dims == (1536, 1024)
    assert mc.cache_layers == 2 and mc.ring_rows(512, 32) == 672
    # the file's arithmetic: 3,429,955,392 parameters served
    assert mc.param_count() == 3_429_955_392
    assert "3,429,955,392" in cfg["arithmetic"]
    tiny = serve.model_config(cfg, rehearse=True)
    assert tiny.sliding_window == 8 and tiny.count(WINDOW) == 5
    assert tiny.attn_shape(WINDOW)[:4] == (8, 4, 24, 16)
    # the refusals a ring model has hold for this one, one line each
    for kw, match in ((dict(spec=True), "--spec"),
                      (dict(mesh_shape={"tensor": 2}), "--tp / --ep"),
                      (dict(mesh_shape={"expert": 2}), "--tp / --ep"),
                      (dict(kv_dtype="int8"), "--kv-dtype int8")):
        err = refusal(mc, **kw)
        assert err and match in err and "B-M2" in err, err


@pytest.mark.parametrize("bad,match", [
    (dict(hybrid_layer_pattern=(0, 1, 2, 1, 1, 0, 1)), "hybrid_layer_pattern"),
    (dict(layer_types=(WINDOW,) * 7), "does not agree with layer_types"),
    (dict(moe_layer_freq=(0, 1, 0, 1, 1, 1, 1)), "moe_layer_freq"),
    (dict(moe_layer_freq=(0, 1, 1)), "moe_layer_freq"),
    (dict(moe_layer_freq=(0, 0, 1, 1, 1, 1, 1), num_dense_layers=1),
     "moe_layer_freq"),
    (dict(sliding_window_size=16), "sliding_window_size"),
    (dict(attention_chunk_size=4), "attention_chunk_size"),
    (dict(add_full_attention_sink_bias=True), "add_full_attention_sink"),
    (dict(swa_head_dim=16), "swa_head_dim"),
    (dict(swa_v_head_dim=8), "swa_v_head_dim"),
    (dict(swa_num_attention_heads=8), "swa_num_attention_heads"),
    (dict(swa_num_key_value_heads=3), "kv heads"),
    (dict(topk_method="greedy"), "topk_method"),
    (dict(layernorm_epsilon=1e-6, rms_norm_eps=1e-3), "layernorm_epsilon"),
    (dict(qk_norm="head"), "per-kind attention"),
    (dict(attn_bias=True), "per-kind attention"),
    (dict(kv_lora_rank=32), "per-kind attention"),
], ids=["pattern_entry", "pattern_vs_types", "dense_not_leading",
        "freq_short", "freq_vs_dense", "window_size", "chunk_size",
        "full_sink", "swa_head", "swa_v_head", "swa_heads", "kv_heads",
        "topk_method", "eps_twice", "qk_norm", "bias", "latent"])
def test_a_stack_the_program_cannot_run_is_refused_at_construction(bad,
                                                                   match):
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(MM, **bad)


def test_the_value_width_and_the_sink_belong_to_per_kind_attention():
    plain = MODEL_CONFIGS["test-tiny"]
    with pytest.raises(ValueError, match="belong to latent attention"):
        dataclasses.replace(plain, v_head_dim=8)
    with pytest.raises(ValueError, match="belong to per-kind attention"):
        dataclasses.replace(plain, add_swa_attention_sink_bias=True)
    with pytest.raises(ValueError, match="belong to per-kind attention"):
        dataclasses.replace(plain, attention_value_scale=0.5)


# ------------------------------------------------------------ the logits
def test_the_oracle_agrees_with_the_reference():
    params = make_params()
    for n in (5, 9, 40, 150):  # inside the window, just past it, far past
        toks = seq_tokens(n, n)
        close(oracle(MM, params, toks), want(MM, params, toks)[-1])


def test_chunks_then_decode_through_rings_and_pool_agree_with_the_reference():
    """A prompt in chunks of every length a rung holds, then decode scans of
    k = 8 passes, through the rings (32 rows: overwritten four times by 150
    tokens) and the pool — into slots an earlier request left full of
    garbage — against the reference's ONE full forward."""
    params, n = make_params(), 150
    toks = seq_tokens(0, n)
    ref = want(MM, params, toks)
    st = state(garbage=3.0)
    p = 0
    for c in (16, 7, 16, 16, 1, 16, 16, 12, 16, 4):  # 120 tokens
        out, st, _ = ragged_step(MM, params, st, [(1, toks[p:p + c], p)])
        p += c
        close(out[1], ref[p - 1])
    while p + 8 <= n:
        out, st = decode_scan(MM, params, st, {1: (toks[p:p + 8], p)}, [1])
        close(out[1], ref[p:p + 8])
        p += 8
    assert p == 144
    # the pool's K rows and V rows, and the rings', at their own widths
    assert (st[0].shape[-1], st[1].shape[-1]) == (24, 16)
    assert (st[2].ring.k.shape[-1], st[2].ring.v.shape[-1]) == (48, 32)


def test_rows_of_one_step_keep_to_their_own_rings():
    """Three sequences at different depths in one stream — a decode row deep
    past its ring's first turn, a chunk that crosses the window's edge, a
    first span — each against its own full forward; the idle slot's ring is
    what it was."""
    params = make_params()
    a, b, c = seq_tokens(1, 90), seq_tokens(2, 30), seq_tokens(3, 6)
    st = state(garbage=-2.0)
    for p in range(0, 80, 16):
        _, st, _ = ragged_step(MM, params, st, [(0, a[p:p + 16], p)])
    _, st, _ = ragged_step(MM, params, st, [(2, b[:16], 0)])
    idle = np.asarray(st[2].ring.k[:, 3 * RING:4 * RING])
    out, st, _ = ragged_step(
        MM, params, st, [(0, a[80:81], 80), (2, b[16:25], 16), (1, c, 0)])
    close(out[0], want(MM, params, a[:81])[-1])
    close(out[2], want(MM, params, b[:25])[-1])
    close(out[1], want(MM, params, c)[-1])
    np.testing.assert_array_equal(
        np.asarray(st[2].ring.k[:, 3 * RING:4 * RING]), idle)
    out, st = decode_scan(MM, params, st, {0: (a[81:89], 81)}, [0])
    close(out[0], want(MM, params, a[:89])[81:89])


# ------------------------------------------- what the seeded weights catch
def _no_sink(mc, p):
    """The sink dropped from the window layers' softmax."""
    return dataclasses.replace(mc, add_swa_attention_sink_bias=False), {
        **p, "layers": {k: v for k, v in p["layers"].items()
                        if k != "swa_sink"}}


def _sink_on_full_too(mc, p, monkeypatch):
    """...and one (the first window layer's) on the full layers as well."""
    plain = llama._attention_op

    def op(cfg, lp, h, positions, attn_fn, rotate=True, kind=ATTENTION):
        if kind == ATTENTION:
            sink = p["layers"]["swa_sink"][0]
            return plain(cfg, lp, h, positions,
                         lambda q, k, v: attn_fn(q, k, v, sink=sink),
                         rotate, kind)
        return plain(cfg, lp, h, positions, attn_fn, rotate, kind)

    monkeypatch.setattr(llama, "_attention_op", op)
    return mc, p


def _as_four_kv_heads(mc, p):
    """The window layers' 2 kv heads read as the full layers' 1: the first
    head's K and V serve every query head."""
    layers = dict(p["layers"])
    layers["swa_wk"] = layers["swa_wk"][..., :mc.head_dim]
    layers["swa_wv"] = layers["swa_wv"][..., :mc.v_head_dim]
    return dataclasses.replace(mc, swa_num_key_value_heads=1), {
        **p, "layers": layers}


WRONG = {
    "no_sink": _no_sink,
    "value_scale_1": lambda mc, p: (
        dataclasses.replace(mc, attention_value_scale=1.0), p),
    "one_theta": lambda mc, p: (
        dataclasses.replace(mc, swa_rope_theta=mc.rope_theta), p),
    "rotary_whole_head": lambda mc, p: (
        dataclasses.replace(mc, partial_rotary_factor=1.0), p),
    "kv_heads_as_full": _as_four_kv_heads,
    "no_bias": lambda mc, p: (
        dataclasses.replace(mc, topk_method=None, use_expert_bias=False), p),
    "no_renorm": lambda mc, p: (
        dataclasses.replace(mc, norm_topk_prob=False), p),
}


@pytest.mark.parametrize("wrong", sorted(WRONG) + ["sink_on_full_too"])
def test_a_forward_that_departs_from_the_equations_misses(wrong,
                                                          monkeypatch):
    """The program's own forward under ONE changed mechanism, against the
    reference of the right one: off by far more than the tolerance (so the
    agreement above says each is there)."""
    params = make_params()
    toks = seq_tokens(7, 60)
    ref = want(MM, params, toks)[-1]
    if wrong == "sink_on_full_too":
        mc, p = _sink_on_full_too(MM, params, monkeypatch)
        kc, vc, _ = state(mc)  # (bare: a cached jit would not see the patch)
        got = np.asarray(llama.forward_prefill(
            p, mc, jnp.asarray(toks, jnp.int32)[None],
            jnp.asarray([len(toks)]), kc, vc,
            jnp.asarray(page_table()[:1]), PS)[0][0])
    else:
        mc, p = WRONG[wrong](MM, params)
        got = oracle(mc, p, toks)
    assert np.abs(got - ref).max() > 50 * ATOL, np.abs(got - ref).max()


# ------------------------------------------------------------- the share
def test_the_shares_add_up_to_the_uncut_layer():
    """The guide's share test: the parts of an expert layer's result that
    all four shares of 4 experts give add up to what the uncut layer of 16
    gives — nothing here is counted once: there is no shared expert."""
    whole = dataclasses.replace(MM, num_experts=16, router_experts=0)
    lp = {k: v[1] for k, v in llama.init_params(
        whole, jax.random.PRNGKey(5), jnp.float32)["layers"].items()
        if k in llama.KIND_PARAMS[EXPERTS]}
    assert not any(k.startswith("ws_") for k in lp)
    h = jax.random.normal(jax.random.PRNGKey(6), (1, 24, MM.hidden_size))
    full, load = moe_mlp(whole, lp, h)
    assert int(load.sum()) == 24 * MM.num_experts_per_tok
    parts, loads = [], 0
    for first in range(0, 16, 4):
        share = dataclasses.replace(MM, expert_offset=first)
        mine = dict(lp, **{k: lp[k][first:first + 4]
                           for k in moe.STACKED})
        out, load = moe_mlp(share, mine, h)
        parts.append(out)
        loads += int(load.sum())
    assert loads == 24 * MM.num_experts_per_tok  # every pair lands once
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(full),
                               atol=2e-5, rtol=0)


# ------------------------------------------------- the cache rows' layout
@pytest.mark.parametrize("hk,dim", [(4, 192), (8, 192), (2, 24), (8, 128),
                                    (2, 320)])
def test_a_stored_row_holds_every_lane_once(hk, dim):
    """`lay_heads` / `heads_of`: a head of whole lane tiles, or one that fits
    in a tile, is stored in one piece; 192 lanes as [Hk x 128 | Hk x 64]."""
    x = jnp.arange(3 * hk * dim, dtype=jnp.float32).reshape(3, hk, dim)
    row = attention.lay_heads(x)
    assert row.shape == (3, hk * dim)
    np.testing.assert_array_equal(np.asarray(attention.heads_of(row, dim)),
                                  np.asarray(x))
    whole, rest = attention.split_head(dim)
    assert whole + rest == dim and (rest == 0) == (dim in (24, 128))
    if rest:  # head h's first part is ONE aligned run, its rest behind all
        np.testing.assert_array_equal(
            np.asarray(row[:, whole:2 * whole]), np.asarray(x[:, 1, :whole]))
        np.testing.assert_array_equal(
            np.asarray(row[:, hk * whole:hk * whole + rest]),
            np.asarray(x[:, 0, whole:]))
    else:
        np.testing.assert_array_equal(np.asarray(row),
                                      np.asarray(x.reshape(3, -1)))


def test_the_published_head_shapes_through_the_split_rows():
    """Two layers (full, window) at the PUBLISHED head shapes — 192 / 128
    lanes, 4 and 8 kv heads, window 128 — under a small hidden size: chunks
    then decode through pool and rings whose K rows are stored split, against
    the reference."""
    mc = ModelConfig(
        name="mimo-heads-at-width", vocab_size=128, hidden_size=64,
        intermediate_size=64, num_layers=2, num_heads=16, num_kv_heads=4,
        head_dim=192, v_head_dim=128, rope_theta=5e6,
        partial_rotary_factor=0.334, sliding_window=128,
        hybrid_layer_pattern=(0, 1), swa_num_key_value_heads=8,
        swa_rope_theta=1e4, add_swa_attention_sink_bias=True,
        attention_value_scale=0.707, max_seq_len=512)
    assert mc.kv_row_dims == (768, 512) and mc.ring_row_dims == (1536, 1024)
    params = make_params(mc)
    toks = seq_tokens(4, 150, vocab=128)
    ref = np.asarray(_reference("mimo_v2_flash_decoder").logits(
        dict(keys(mc), n_routed_experts=0, moe_layer_freq=[0, 0]), params,
        jnp.asarray(toks, jnp.int32)))
    ring = mc.ring_rows(PAD, PS)
    kc, vc = (jnp.zeros((1, (1 + B * MP) * PS, lanes), jnp.float32)
              for lanes in mc.kv_row_dims)
    st = (kc, vc, llama.alloc_slot_state(mc, B, jnp.float32, ring_rows=ring))
    p = 0
    while p + 16 <= 144:
        out, st, _ = ragged_step(mc, params, st, [(1, toks[p:p + 16], p)])
        p += 16
        close(out[1], ref[p - 1])
    out, st = decode_scan(mc, params, st, {1: (toks[p:p + 6], p)}, [1])
    close(out[1], ref[p:p + 6])


# --------------------------------------------------- the step's work account
def test_the_step_samples_carry_the_rows_bytes_and_both_kinds_counters():
    """`attn_row_bytes` / `swa_row_bytes`: a cached position of one full /
    window layer AS STORED (float32 here; bf16 on the chip: 2,560 and 5,120 B
    at the published widths) beside the six pair / row counters."""
    noted = {}
    sp = types.SimpleNamespace(note=noted.update)
    work = StepWork(MM, PS, "step-work-mimo", kv_itemsize=4)
    work.note(sp, [1, 5, 16], [9, 5, 40], [True, True, False],
              stream_len=32, opened=1)
    assert (noted["attn_row_bytes"], noted["swa_row_bytes"]) \
        == ((24 + 16) * 4, (48 + 32) * 4)
    for f in KINDS["attn"].fields + KINDS["swa"].fields:
        assert f in noted, f
    assert noted["attn_pairs"] == 9 + 15 + sum(range(25, 41))
    assert noted["swa_pairs"] == 8 + 15 + 16 * 8  # window 8
    with open(FILE) as f:
        from benchmarks import serve

        served = serve.model_config(json.load(f), rehearse=False)
    sp = types.SimpleNamespace(note=noted.update)
    StepWork(served, 32, "step-work-mimo-served").note(
        sp, [4, 4], [10, 30], scan=True)
    assert (noted["attn_row_bytes"], noted["swa_row_bytes"]) == (2560, 5120)


def test_a_pairs_flops_count_the_key_and_the_value_lanes():
    """64 x (192 + 128) x 2 FLOPs a (token, cached position) pair a layer."""
    with open(FILE) as f:
        from benchmarks import serve

        mc = serve.model_config(json.load(f), rehearse=False)
    base = mfu.flops_per_token(mc, 0.0)
    at = mfu.flops_per_token(mc, 4096.0) - base
    pair = 64 * (192 + 128) * 2
    assert at == pytest.approx(pair * (2 * 4096 + 5 * 128))
    kx = MODEL_CONFIGS["test-tiny-k-exaone"]  # K as wide as V: q_dim, as ever
    assert mfu.flops_per_token(kx, 100.0) - mfu.flops_per_token(kx, 0.0) \
        == pytest.approx(4.0 * kx.q_dim * (100 + 4 * 8))
