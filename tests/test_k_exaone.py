"""K-EXAONE on the served path (PR 50): window and full attention in one
stack — a window layer's K/V in a per-slot ring beside the paged pool, a
rotary embedding on the window layers only — per-head q/k norm, a leading
dense layer, a chip's share of sigmoid-routed experts with a selection bias
beside one shared expert.

LOGITS of the served forwards against the benchmark's plain float32
reference (benchmarks/reference/k_exaone_decoder.py) at
`test-tiny-k-exaone`, seeded random weights, float32, on the CPU: two orders
of summation (pages, ring rows and chunks against one dense pass; grouped
against per-expert matmuls) differ by ~1e-5 of logits whose spread is ~1, so
ATOL 2e-4; every departure the seeded weights are drawn to catch misses by
50 times that, a router in bfloat16 by four times (asserted). Contexts run to 150 tokens over a ring of 32 rows:
every ring row is overwritten four times. (The kernels against their jnp
twins, the ring's arithmetic and the engine's counters:
test_window_cache.py.)"""

import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ollamamq_tpu.config import (ATTENTION, EXPERTS, MODEL_CONFIGS, WINDOW,
                                 EngineConfig)
from ollamamq_tpu.engine.kv_cache import refusal
from ollamamq_tpu.models import llama, moe
import test_lfm2
from test_lfm2 import close, seq_tokens
from testutil import (_reference, moe_mlp, once_a_sequence, prefill,
                      seeded_params)

NAME = "test-tiny-k-exaone"
KX = MODEL_CONFIGS[NAME]
PS, MP, B, PAD = 8, 24, 4, 16   # page size, pages a row, rows, a step's rung
RING = KX.ring_rows(PAD, PS)    # 8 + 16 + 8 = 32 rows: four pages
ATOL = 2e-4
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FILE = os.path.join(_REPO, "benchmarks", "configs",
                    "k-exaone-236b-a23b-ep8-d5.json")


def keys(mc) -> dict:
    """What a configuration file says of the ModelConfig `mc`: all that the
    reference reads."""
    return {
        "num_attention_heads": mc.num_heads,
        "num_key_value_heads": mc.num_kv_heads, "head_dim": mc.head_dim,
        "hidden_size": mc.hidden_size,
        "intermediate_size": mc.intermediate_size,
        "rms_norm_eps": mc.rms_norm_eps,
        "rope_parameters": {"rope_theta": mc.rope_theta},
        "layer_types": list(mc.layer_types),
        "sliding_window": mc.sliding_window,
        "num_dense_layers": mc.num_dense_layers,
        "num_experts": mc.num_experts, "router_experts": mc.router_width,
        "expert_offset": mc.expert_offset,
        "num_experts_per_tok": mc.num_experts_per_tok,
        "norm_topk_prob": mc.norm_topk_prob,
        "routed_scaling_factor": mc.routed_scaling_factor,
        "moe_intermediate_size": mc.moe_intermediate_size,
        "num_shared_experts": mc.n_shared_experts,
        "vocab_size": mc.vocab_size}


def make_params(mc=KX, seed=0):
    """(the selection bias is drawn non-zero by `init_params`)"""
    return seeded_params(mc, ("q_norm", "k_norm", "attn_norm", "mlp_norm"),
                         seed=seed)


@once_a_sequence
def want(mc, params, tokens):
    """The reference's ONE full forward: [T, V] logits."""
    return np.asarray(_reference("k_exaone_decoder").logits(
        keys(mc), params, jnp.asarray(tokens, jnp.int32)))


def state(mc=KX, garbage=0.0):
    """(kc, vc, SlotState): an empty pool — of the FULL layers only — and
    rings that an earlier request left full of `garbage`."""
    kv = jnp.zeros((mc.count(ATTENTION), (1 + B * MP) * PS, mc.kv_dim),
                   jnp.float32)
    st = llama.alloc_slot_state(mc, B, jnp.float32, ring_rows=RING)
    return kv, kv, jax.tree_util.tree_map(
        lambda a: a + jnp.asarray(garbage, a.dtype), st)


def oracle(mc, params, tokens):
    """The program's `forward_prefill` at the last position."""
    kv = state(mc)[0]
    toks = jnp.asarray(tokens, jnp.int32)
    return np.asarray(prefill(
        params, mc, toks[None], jnp.asarray([len(tokens)]), kv, kv,
        jnp.asarray(page_table()[:1]), PS)[0][0])


# tests/test_lfm2.py's step and scan, at this file's rung and pages a row.
ragged_step = functools.partial(test_lfm2.ragged_step, pad_to=PAD, mp=MP)
decode_scan = functools.partial(test_lfm2.decode_scan, mp=MP)
page_table = functools.partial(test_lfm2.page_table, MP)


# ----------------------------------------------------------- the config
def test_the_tiny_family_and_its_plan():
    assert (KX.count(WINDOW), KX.count(ATTENTION), KX.count(EXPERTS)) \
        == (4, 1, 4)
    assert KX.attn_layers == 5 and KX.cache_layers == 1
    assert [(f, len(p), n) for f, p, n in KX.layer_plan()] \
        == [(0, 1, 1), (1, 1, 2), (3, 1, 1), (4, 1, 1)]
    assert KX.rotates(WINDOW) and not KX.rotates(ATTENTION)
    # the published spellings fold into the program's fields
    assert (KX.n_shared_experts, KX.router_score) == (1, "sigmoid")
    assert (KX.n_group, KX.topk_group) == (0, 0)  # one group: no limit
    assert KX.ring_rows(512, 32) == 8 + 512 + 32 + 24 and RING == 32
    # every other model rotates every attention layer, or none
    assert MODEL_CONFIGS["test-tiny"].rotates(ATTENTION)
    assert not MODEL_CONFIGS["test-tiny-olmo-hybrid"].rotates(ATTENTION)


def test_the_configuration_file_reaches_the_program_key_by_key():
    """Every key of the catalog's `config` is in the file under its
    published spelling and builds the ModelConfig the cell serves."""
    from benchmarks import serve

    with open(FILE) as f:
        cfg = json.load(f)
    mc = serve.model_config(cfg, rehearse=False)
    assert mc.layer_types == ("sliding_attention",) * 3 + (
        "full_attention", "sliding_attention")
    assert (mc.num_heads, mc.num_kv_heads, mc.head_dim, mc.hidden_size) \
        == (64, 8, 128, 6144)
    assert (mc.sliding_window, mc.num_dense_layers, mc.intermediate_size) \
        == (128, 1, 18432)
    assert (mc.num_experts, mc.router_width, mc.num_experts_per_tok,
            mc.expert_width, mc.shared_width) == (16, 128, 8, 2048, 2048)
    assert (mc.router_score, mc.use_expert_bias, mc.norm_topk_prob,
            mc.routed_scaling_factor, mc.n_group) \
        == ("sigmoid", True, True, 2.5, 0)
    assert mc.rope_theta == 1_000_000 and mc.rope_layer_types == (WINDOW,)
    assert mc.num_nextn_predict_layers == 0 and mc.cache_layers == 1
    # the file's arithmetic: 3,712,028,416 parameters served
    assert mc.param_count() == 3_712_028_416
    assert "3,712,028,416" in cfg["arithmetic"]
    assert mc.ring_rows(512, 32) == 672
    tiny = serve.model_config(cfg, rehearse=True)
    assert tiny.sliding_window == 8 and tiny.count(WINDOW) == 4


@pytest.mark.parametrize("bad,match", [
    (dict(layer_types=("sliding_attention",) * 4 + ("full_attention",)),
     "sliding_window_pattern"),
    (dict(sliding_windows=(8, 8, 8, 8, 8)), "sliding_windows does not agree"),
    (dict(sliding_windows=(8, 8, 8, 0)), "sliding_windows does not agree"),
    (dict(mlp_layer_types=("sparse",) * 5), "mlp_layer_types does not agree"),
    (dict(mlp_layer_types=("dense", "dense") + ("sparse",) * 3),
     "mlp_layer_types does not agree"),
    (dict(sliding_window_pattern="LLG"), "sliding_window_pattern"),
    (dict(sliding_window=0), "come together"),
    (dict(layer_types=None, sliding_window_pattern=None), "come together"),
    (dict(layer_types=("full_attention",) * 5, sliding_window=0,
          sliding_window_pattern=None, rope_layer_types=None,
          use_sliding_window=True), "use_sliding_window True"),
    (dict(num_nextn_predict_layers=1, mtp_layer_types=("full_attention",)),
     "mtp_layer_types"),
    (dict(rope_layer_types=("conv",)), "rope_layer_types"),
    (dict(rope_parameters={"rope_theta": 1e4, "rope_type": "yarn"}),
     "rope_parameters"),
    (dict(num_shared_experts=2, n_shared_experts=1), "num_shared_experts"),
    (dict(scoring_func="tanh"), "scoring_func"),
    (dict(attn_output_gate=True), "plain K/V attention"),
], ids=["types_vs_pattern", "windows_vs_types", "windows_short",
        "mlp_all_sparse", "mlp_two_dense", "pattern", "no_width",
        "width_no_layer", "flag_no_layer", "module_lists", "rope_kinds",
        "rope_type", "shared_twice", "score", "gate"])
def test_a_stack_the_program_cannot_run_is_refused_at_construction(bad,
                                                                   match):
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(KX, **bad)


def test_the_published_lists_that_agree_are_taken():
    mc = dataclasses.replace(
        KX, sliding_windows=[8, 8, 8, 0, 8],
        mlp_layer_types=["dense"] + ["sparse"] * 4,
        mtp_layer_types=["full_attention"], mtp_sliding_windows=[0],
        rope_parameters={"rope_theta": 10_000.0, "rope_type": "default"})
    assert mc.kinds == KX.kinds and mc.rope_theta == 10_000.0
    hash(mc)  # a jit's static argument: the file's lists became tuples


@pytest.mark.parametrize("kw,match", [
    (dict(spec=True), r"--spec.*B-M2"),
    (dict(mesh_shape={"tensor": 2}), r"--tp / --ep.*B-M2"),
    (dict(mesh_shape={"expert": 2}), r"--tp / --ep.*B-M2"),
    (dict(kv_dtype="int8"), r"--kv-dtype int8.*B-M2"),
], ids=["spec", "tp", "ep", "kv_int8"])
def test_what_knows_only_pages_is_refused_with_a_window_layer(kw, match):
    import re

    err = refusal(KX, **kw)
    assert err and re.search(match, err) and "sliding_attention" in err
    assert refusal(KX, mesh_shape={"data": 2}) is None
    assert refusal(MODEL_CONFIGS["test-tiny"], **kw) is None
    from ollamamq_tpu.engine.engine import ModelRuntime

    if "spec" in kw or "kv_dtype" in kw:
        ecfg = EngineConfig(model=NAME, max_slots=2, num_pages=16,
                            page_size=8, max_pages_per_seq=4, **kw)
        with pytest.raises(ValueError, match="sliding_attention"):
            ModelRuntime(NAME, KX, ecfg, dtype=jnp.float32)


# ------------------------------------------------------------ the logits
def test_the_oracle_agrees_with_the_reference():
    params = make_params()
    for n in (5, 9, 40, 150):  # inside the window, just past it, far past
        toks = seq_tokens(n, n)
        close(oracle(KX, params, toks), want(KX, params, toks)[-1])


def test_chunks_then_decode_through_ring_and_pool_agree_with_the_reference():
    """A prompt in chunks of every length a rung holds, then decode scans of
    k = 8 passes, through the ring (32 rows: overwritten four times by 150
    tokens) and the pool — into slots an earlier request left full of
    garbage — against the reference's ONE full forward."""
    params, n = make_params(), 150
    toks = seq_tokens(0, n)
    ref = want(KX, params, toks)
    st = state(garbage=3.0)
    p = 0
    for c in (16, 7, 16, 16, 1, 16, 16, 12, 16, 4):  # 120 tokens
        out, st, _ = ragged_step(KX, params, st, [(1, toks[p:p + c], p)])
        p += c
        close(out[1], ref[p - 1])
    while p + 8 <= n:
        out, st = decode_scan(KX, params, st, {1: (toks[p:p + 8], p)}, [1])
        close(out[1], ref[p:p + 8])
        p += 8
    assert p == 144


def test_rows_of_one_step_keep_to_their_own_rings():
    """Three sequences at different depths in one stream — a decode row deep
    past its ring's first turn, a chunk that crosses the window's edge, a
    first span — each against its own full forward; then the idle slot's
    ring is what it was."""
    params = make_params()
    a, b, c = seq_tokens(1, 90), seq_tokens(2, 30), seq_tokens(3, 6)
    st = state(garbage=-2.0)
    for p in range(0, 80, 16):
        _, st, _ = ragged_step(KX, params, st, [(0, a[p:p + 16], p)])
    for p in range(0, 16, 16):
        _, st, _ = ragged_step(KX, params, st, [(2, b[p:p + 16], p)])
    idle = np.asarray(st[2].ring.k[:, 3 * RING:4 * RING])
    out, st, _ = ragged_step(
        KX, params, st, [(0, a[80:81], 80), (2, b[16:25], 16), (1, c, 0)])
    close(out[0], want(KX, params, a[:81])[-1])
    close(out[2], want(KX, params, b[:25])[-1])
    close(out[1], want(KX, params, c)[-1])
    np.testing.assert_array_equal(
        np.asarray(st[2].ring.k[:, 3 * RING:4 * RING]), idle)
    # a scan with slot 0 active and the others parked (mid-prefill): their
    # rings keep what they hold, and slot 2 goes on from it afterwards
    held = np.asarray(st[2].ring.k[:, 2 * RING:3 * RING])
    out, st = decode_scan(KX, params, st, {0: (a[81:89], 81)}, [0])
    close(out[0], want(KX, params, a[:89])[81:89])
    np.testing.assert_array_equal(
        np.asarray(st[2].ring.k[:, 2 * RING:3 * RING]), held)
    out, st, _ = ragged_step(KX, params, st, [(2, b[25:30], 25)])
    close(out[2], want(KX, params, b)[-1])


# ------------------------------------------- what the seeded weights catch
@pytest.mark.parametrize("wrong", [
    dict(rope_layer_types=None),                      # RoPE on a full layer
    dict(rope_layer_types=("full_attention",)),       # ...and on it alone
    dict(sliding_window=16, ),                        # a wider window
    dict(layer_types=("full_attention",) * 5, sliding_window=0,
         sliding_window_pattern=None, rope_layer_types=None),  # no window
    dict(use_expert_bias=False),                      # the bias dropped
    dict(routed_scaling_factor=1.0),
    dict(norm_topk_prob=False),
    dict(num_shared_experts=None, n_shared_experts=0),
], ids=["rope_everywhere", "rope_on_full", "window_16", "no_window",
        "no_bias", "no_scale", "no_renorm", "no_shared"])
def test_a_forward_that_departs_from_the_equations_misses(wrong):
    """The program's own forward under a configuration that differs in ONE
    of the family's mechanisms, against the reference of the right one: off
    by far more than the tolerance (so the agreement above says each is
    there). `no_window` is a window served by no bound at all; `rope_*` a
    rotary embedding that reaches a full layer."""
    params = make_params()
    toks = seq_tokens(7, 60)
    ref = want(KX, params, toks)[-1]
    mc = dataclasses.replace(KX, **wrong)
    p = dict(params)
    if "n_shared_experts" in wrong:  # (the tree has the shared expert)
        p["layers"] = {k: v for k, v in params["layers"].items()
                       if not k.startswith("ws_")}
    got = oracle(mc, p, toks)
    assert np.abs(got - ref).max() > 50 * ATOL, np.abs(got - ref).max()


def test_a_bfloat16_router_misses():
    """The router's scores in bfloat16 flip some token's eighth expert: the
    float32 reference is missed by more than the tolerance."""
    params = make_params()
    toks = seq_tokens(11, 120)
    ref = want(KX, params, toks)
    low = dict(params)
    low["layers"] = dict(params["layers"])
    plain = moe.route

    def bf16_route(cfg, lp, x):
        lp = dict(lp, w_router=lp["w_router"].astype(jnp.bfloat16).astype(
            jnp.float32))
        return plain(cfg, lp, x.astype(jnp.bfloat16).astype(jnp.float32))

    moe.route = bf16_route
    try:
        worst = max(np.abs(oracle(KX, low, toks[:n]) - ref[n - 1]).max()
                    for n in (40, 80, 120))
    finally:
        moe.route = plain
    assert worst > 4 * ATOL, worst  # (8.9e-4 here: four of 16 experts held)


# ------------------------------------------------------------- the share
def test_the_shares_add_up_to_the_uncut_layer():
    """The guide's share test: the parts of an expert layer's result that
    all four shares of 4 experts give — the shared expert, which every chip
    computes alike, counted once — add up to what the uncut layer of 16
    gives."""
    whole = dataclasses.replace(KX, num_experts=16, router_experts=0)
    lp = {k: v[1] for k, v in llama.init_params(
        whole, jax.random.PRNGKey(5), jnp.float32)["layers"].items()
        if k in llama.KIND_PARAMS[EXPERTS]}
    h = jax.random.normal(jax.random.PRNGKey(6), (1, 24, KX.hidden_size))
    full, load = moe_mlp(whole, lp, h)
    assert int(load.sum()) == 24 * KX.num_experts_per_tok
    no_shared = {k: v for k, v in lp.items() if not k.startswith("ws_")}
    shared = full - moe_mlp(
        dataclasses.replace(whole, num_shared_experts=None,
                            n_shared_experts=0), no_shared, h)[0]
    parts, loads = [], 0
    for first in range(0, 16, 4):
        share = dataclasses.replace(KX, expert_offset=first)
        mine = dict(lp, **{k: lp[k][first:first + 4]
                           for k in moe.STACKED})
        out, load = moe_mlp(share, mine, h)
        parts.append(out - shared)
        loads += int(load.sum())
    assert loads == 24 * KX.num_experts_per_tok  # every pair lands once
    np.testing.assert_allclose(np.asarray(sum(parts) + shared),
                               np.asarray(full), atol=2e-5, rtol=0)
