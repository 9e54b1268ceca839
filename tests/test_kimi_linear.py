"""Kimi-Linear on the served path (PR 63): Kimi Delta Attention — the delta
rule with a decay a key channel — beside NoPE latent attention as a layer
KIND, a dense first layer, a chip's share of the routed experts behind a
shared expert.

LOGITS of the served forwards (prefill in chunks over the latent pool and the
per-slot rule state and conv windows, then decode; ragged steps that mix spans
and one-token rows; the fused scan; a slot an earlier request left full)
against the benchmark's plain float32 reference
(benchmarks/reference/kimi_linear_decoder.py) at `test-tiny-kimi-linear`,
seeded random weights, float32, on the CPU; each departure the comparison is
there to catch; the chip's share of the expert layer; what the program refuses,
one line each; the served tree's size against the configuration file's
arithmetic; what the engine counts. (The rule itself at weak and strong decays,
and both kernels: test_kimi_linear_rule.py.)

The tolerance is test_lfm2.py's ATOL, 2e-4 on logits of 0.5-3: what two
float32 forwards of one sequence at different lengths differ by on this toy
stack's worst-conditioned rows (the gated head norm over 8 numbers), and a
tenth or less of what the smallest departure below misses by."""

import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ollamamq_tpu.config import (ATTENTION, EXPERTS, LINEAR, MODEL_CONFIGS,
                                 ModelConfig)
from ollamamq_tpu.engine.kv_cache import refusal, state_held
from ollamamq_tpu.models import llama, moe
from ollamamq_tpu.ops import gated_delta as gd
from ollamamq_tpu.ops.sampling import SamplingParams
from test_lfm2 import (ATOL, B, NP, PS, close, decode_scan, fused_scan,
                       mixed_step, ragged_step, seq_tokens)
from test_step_overlap import _engine, _prompt, _rt, drive
from testutil import (kimi_linear_keys, kimi_linear_reference, moe_mlp,
                      once_a_sequence, seeded_params)

NAME = "test-tiny-kimi-linear"
KL = MODEL_CONFIGS[NAME]
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FILE = os.path.join(_REPO, "benchmarks", "configs",
                    "kimi-linear-48b-a3b-ep4-d8.json")
GROUP = dict(KL.linear_attn_config)


def make_params(mc=KL, seed=0):
    """The seeded weights, every norm drawn about one (a norm on the wrong
    axis, or left out, cannot pass); the selection bias, A_log, dt_bias and
    the gated head norm are drawn away from the identity by `init_params`."""
    return seeded_params(mc, ("attn_norm", "mlp_norm", "lin_norm",
                              "mla_kv_norm"), seed=seed,
                         top_norms=("final_norm",))


def state(mc=KL, garbage=0.0, pages=NP):
    """(latent pool, the index-key pool's no lanes, SlotState): an empty pool
    and a per-slot state that an earlier request left full of `garbage`."""
    kc = jnp.zeros((mc.cache_layers, pages * PS, mc.latent_lanes),
                   jnp.float32)
    st = llama.alloc_slot_state(mc, B, jnp.float32)
    return kc, jnp.zeros(kc.shape[:2] + (0,), jnp.float32), \
        jax.tree_util.tree_map(
            lambda a: a + jnp.asarray(garbage, a.dtype), st)


def by_slot(slot_state):
    return (np.asarray(slot_state.conv).swapaxes(1, 2),
            np.asarray(slot_state.rule))


@once_a_sequence
def want(mc, params, tokens):
    """The reference's ONE full forward: [T, V] logits (it pads itself to
    whole query blocks)."""
    return np.asarray(kimi_linear_reference().logits(
        kimi_linear_keys(mc), params, jnp.asarray(tokens, jnp.int32)))


@functools.partial(jax.jit, static_argnums=(0,))
def _prefill(mc, params, tokens):
    kc, vc, _ = state(mc)
    return llama.forward_prefill(
        params, mc, tokens[None], jnp.asarray([tokens.shape[0]]), kc, vc,
        jnp.arange(1, 9, dtype=jnp.int32)[None], PS)[0][0]


def oracle(mc, params, tokens):
    """The program's `forward_prefill` at the last position."""
    return np.asarray(_prefill(mc, params, jnp.asarray(tokens, jnp.int32)))


# ----------------------------------------------------------- the config
def test_the_registered_family_and_its_plan():
    full = MODEL_CONFIGS["kimi-linear:48b-a3b"]
    assert (full.count(LINEAR), full.count(ATTENTION)) == (20, 7)
    assert full.layer_types[3] == full.layer_types[26] == ATTENTION
    assert 48.5e9 < full.param_count() < 49.5e9  # "48 B": 49.12
    assert 3.0e9 < full.param_count(active=True) < 3.6e9  # "A3B": 3.48
    # the published head_dim 72 (= 2304 / 32) is read by no module: a latent
    # head's q and k are 128 + 64 lanes wide
    assert full.head_dim == 192 and full.q_lora_rank == 0 and full.kda
    assert (full.num_experts_per_tok, full.router_score, full.norm_topk_prob,
            full.n_group, full.n_shared_experts, full.max_seq_len) \
        == (8, "sigmoid", True, 0, 1, 1_048_576)
    assert (full.linear_num_key_heads, full.linear_num_value_heads,
            full.linear_key_head_dim, full.linear_value_head_dim,
            full.linear_conv_kernel_dim) == (32, 32, 128, 128, 4)
    assert full.state_window == (4, 3 * 4096)
    assert full.cache_layers == 7 and full.kv_row_dims == (640, 0)
    assert KL.cache_layers == 2 and KL.num_dense_layers == 1
    assert KL.layer_types == ((LINEAR,) * 3 + (ATTENTION,)) * 2
    assert sum(len(p) * n for _, p, n in KL.layer_plan()) == 8
    hash(full)  # the group is held hashable: a jit's static argument


def _with(**change):
    """`KL` rebuilt from its published spellings with `change` laid over."""
    kw = dict(
        name=NAME, vocab_size=512, hidden_size=64, intermediate_size=128,
        num_layers=8, num_heads=4, num_kv_heads=4, head_dim=16,
        rope_theta=10_000.0, rms_norm_eps=1e-5, max_seq_len=512,
        q_lora_rank=None, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, mla_use_nope=True,
        linear_attn_config=dict(GROUP), num_experts=4, router_experts=16,
        num_experts_per_token=4, moe_router_activation_func="sigmoid",
        moe_renormalize=True, num_expert_group=1, topk_group=1,
        use_grouped_topk=True, use_expert_bias=True, num_shared_experts=1,
        routed_scaling_factor=2.446, moe_intermediate_size=32,
        first_k_dense_replace=1)
    kw.update(change)
    return ModelConfig(**kw)


def test_the_published_spellings_rebuild_the_registered_config():
    assert _with() == KL
    assert _with(head_dim=24) == KL  # nope + rope, stated outright
    assert _with(layer_types=list(KL.layer_types)) == KL


REFUSED = {
    "layer_types_disagree": (
        dict(layer_types=[LINEAR] * 2 + [ATTENTION] + [LINEAR] * 4
             + [ATTENTION]),
        r"layer_types \['linear_attention', 'linear_attention', "
        r"'full_attention'.*does not agree with linear_attn_config "
        r"\(kda_layers \[1, 2, 3, 5, 6, 7\]"),
    "head_dim": (dict(head_dim=20),
                 r"head_dim 20 is neither qk_nope_head_dim \+ "
                 r"qk_rope_head_dim = 24 .* nor the published hidden_size / "
                 r"num_attention_heads = 16"),
    "a_layer_named_twice": (
        dict(linear_attn_config=dict(GROUP, full_attn_layers=[3, 4, 8])),
        r"kda_layers \[1, 2, 3, 5, 6, 7\] and full_attn_layers \[3, 4, 8\] "
        r"do not name each of layers 1\.\.8"),
    "a_key_the_group_has_not": (
        dict(linear_attn_config=dict(GROUP, gate_low_rank=8)),
        r"linear_attn_config holds \[.*'gate_low_rank'"),
    "heads_disagree": (dict(linear_num_value_heads=8),
                       r"linear_num_value_heads 8 is not "
                       r"linear_attn_config's num_heads 4"),
    "window_beside_latent": (
        dict(linear_attn_config=None, head_dim=24, sliding_window=8,
             layer_types=["sliding_attention", ATTENTION] * 4),
        r"'sliding_attention' layers are served with plain K/V"),
    "conv_beside_latent": (
        dict(linear_attn_config=None, head_dim=24,
             layer_types=["conv", ATTENTION] * 4),
        r"latent attention is served in every layer, or as the "
        r"'full_attention' layers of a stack whose other layers are "
        r"'linear_attention' ones read as Kimi Delta Attention"),
    "scalar_rule_beside_latent": (
        dict(linear_attn_config=None, head_dim=24,
             layer_types=list(KL.layer_types), linear_num_key_heads=4,
             linear_num_value_heads=4,
             linear_key_head_dim=8, linear_value_head_dim=8),
        r"read as Kimi Delta Attention \(linear_attn_config\)"),
    "indexer_in_such_a_stack": (
        dict(q_lora_rank=16, index_n_heads=2, index_head_dim=8,
             index_topk=4, mla_use_nope=False),
        r"neither an indexer nor a prediction module is served where latent "
        r"attention is a layer kind"),
    "module_in_such_a_stack": (
        dict(num_nextn_predict_layers=1),
        r"num_nextn_predict_layers 1 with layer_types"),
    "nope_without_latent": (
        dict(kv_lora_rank=0, qk_nope_head_dim=0, qk_rope_head_dim=0,
             v_head_dim=0),
        r"mla_use_nope and index_\* belong to latent attention"),
    "no_rotation_unsaid": (dict(mla_use_nope=False, rope_theta=None),
                           r"a rotary embedding \(or mla_use_nope: none\)"),
    "experts_per_token": (dict(num_experts_per_tok=3),
                          r"num_experts_per_token 4 is not "
                          r"num_experts_per_tok 3"),
    "router_func": (dict(moe_router_activation_func="tanh"),
                    r"must be 'softmax' or 'sigmoid', got 'tanh'"),
    "groups_unsaid": (dict(use_grouped_topk=False),
                      r"use_grouped_topk False with n_group"),
    "layer_freq": (dict(moe_layer_freq=2), r"moe_layer_freq 2"),
    "two_sigmoid": (dict(linear_allow_neg_eigval=True),
                    r"Kimi Delta Attention's b is a sigmoid"),
}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_a_stack_the_program_cannot_run_is_refused_with_key_and_value(case):
    change, match = REFUSED[case]
    with pytest.raises(ValueError, match=match):
        _with(**change)


SERVED_WITHOUT = {
    "spec": (dict(spec=True),
             "--spec: a rejected draft has already advanced"),
    # (the state's line is the first a start meets; the pools' follows it)
    "tp": (dict(mesh_shape={"tensor": 4}),
           "--tp / --ep: the linear_attention layers' weights and state"),
    "ep": (dict(mesh_shape={"expert": 4}),
           "--tp / --ep: the linear_attention layers' weights and state"),
    "kv_int8": (dict(kv_dtype="int8"),
                "--kv-dtype int8: the page writer's scales"),
    "weights_int8": (dict(weights_dtype="int8"),
                     "--weights-dtype int8: the low-rank projections"),
    "prefix_cache": (dict(prefix_cache=True),
                     "--prefix-cache: the radix tree shares K and V pages"),
}


@pytest.mark.parametrize("flag", sorted(SERVED_WITHOUT))
def test_a_flag_that_knows_neither_pool_nor_state_is_one_line(flag):
    """Both kinds of state apply to this stack, told before any device work:
    a string, one line, naming the model and the flag."""
    kw, match = SERVED_WITHOUT[flag]
    why = refusal(KL, **kw)
    assert why and "\n" not in why and NAME in why and match in why
    assert refusal(KL) is None
    assert state_held(KL) == ["conv / linear", "latent"]


@pytest.mark.parametrize("flags,match", [
    (["--spec"], "--spec: a rejected draft"),
    (["--tp", "2"], "--tp / --ep"),
    (["--kv-dtype", "int8"], "--kv-dtype int8"),
    (["--weights-dtype", "int8"], "int8"),
    (["--prefix-cache"], "--prefix-cache"),
], ids=["spec", "tp", "kv_int8", "weights_int8", "prefix_cache"])
def test_the_cli_ends_the_start_with_that_line_and_exit_2(flags, match,
                                                          caplog):
    from ollamamq_tpu import cli

    with caplog.at_level("ERROR"):
        assert cli.main(["--models", NAME, "--no-tui", "--cpu", "1",
                         "--port", "1"] + flags) == 2
    said = [r.getMessage() for r in caplog.records]
    assert len(said) == 1 and match in said[0], said


def test_the_seeded_weights_are_drawn_away_from_the_identity():
    lp = llama.init_params(KL, jax.random.PRNGKey(0), jnp.float32)["layers"]
    d = KL.hidden_size
    assert lp["wq"].shape == (2, d, 4 * 24) and "mla_wdq" not in lp
    assert lp["mla_wdkv"].shape == (2, d, 32 + 8) and "wk" not in lp
    assert lp["lin_in"].shape == (6, d, 3 * 32) and "lin_ba" not in lp
    assert lp["lin_conv_w"].shape == (6, 3 * 32, 4)
    assert lp["kda_fa"].shape == lp["kda_ga"].shape == (6, d, 8)
    assert lp["kda_fb"].shape == lp["kda_gb"].shape == (6, 8, 32)
    assert lp["lin_dt_bias"].shape == (6, 32)
    assert lp["lin_A_log"].shape == (6, 4)
    assert lp["w_gate"].shape == (1, d, 128)        # the dense first layer
    assert lp["w_router"].shape == (7, d, 16)       # the router's width
    assert lp["we_gate"].shape == (7, 4, d, 32)     # the experts held
    assert lp["router_bias"].dtype == jnp.float32
    assert float(jnp.abs(lp["router_bias"]).min()) > 0  # NON-zero
    assert 0.03 < float(lp["lin_norm"].std()) < 0.2
    assert abs(float(lp["lin_norm"].mean()) - 1) < 0.1
    a_log, dt = lp["lin_A_log"], jax.nn.softplus(lp["lin_dt_bias"])
    assert 0.0 <= float(a_log.min()) and float(a_log.max()) <= np.log(16.0)
    assert 1e-3 * 0.999 <= float(dt.min()) and float(dt.max()) <= 0.1 * 1.001


# --------------------------------------- logits, against the reference
CHUNKINGS = {
    "two_halves": (11, 12),
    "spans_of_1_and_2": (9, 1, 2, 1, 1, 2, 7),   # shorter than the window
    "token_by_token_start": (1, 1, 1, 2, 18),    # state opens on a 1-token row
}


@pytest.mark.parametrize("chunks", CHUNKINGS.values(), ids=CHUNKINGS.keys())
def test_prefill_in_chunks_then_decode_matches_the_reference(chunks):
    """A 23-token prompt in `chunks` on slot 1 — which an earlier request
    left full of 3.0, state and windows — then six decode passes: every
    logit read agrees with the reference's full forward."""
    params = make_params()
    toks = seq_tokens(5, 23 + 6)
    ref = want(KL, params, toks)
    st, at = state(garbage=3.0), 0
    for n in chunks:
        got, st, load = ragged_step(KL, params, st,
                                    [(1, toks[at:at + n], at)])
        at += n
        close(got[1], ref[at - 1])
        assert load.shape == (KL.count(EXPERTS), KL.num_experts)
    for arr in by_slot(st[2]):  # the other slots kept the earlier request's
        assert bool(jnp.all(arr[:, jnp.array([0, 2, 3])] == 3.0))
    got, _ = decode_scan(KL, params, st, {1: (toks[23:], 23)}, active=[1])
    close(got[1], ref[23:])


def test_a_span_across_window_boundaries_beside_another_row(monkeypatch):
    """150 tokens as 90 + 60 beside another row's 70: the rule's windows of
    64 and their 16-token blocks are crossed inside a span, between spans
    and between rows, over a latent pool of two layers."""
    import test_lfm2

    monkeypatch.setattr(test_lfm2, "MP", 24)  # 192 tokens a sequence
    params = make_params()
    toks, other = seq_tokens(4, 150), seq_tokens(6, 70)
    ref, ref_other = want(KL, params, toks), want(KL, params, other)
    st = state(garbage=1.5, pages=1 + B * 24)
    got, st, _ = ragged_step(KL, params, st, [(2, toks[:90], 0)], pad_to=96)
    close(got[2], ref[89], atol=3 * ATOL)
    got, st, _ = ragged_step(KL, params, st, [
        (0, other, 0), (2, toks[90:], 90)], pad_to=144)
    close(got[0], ref_other[69], atol=3 * ATOL)
    close(got[2], ref[149], atol=3 * ATOL)


def test_a_ragged_step_mixing_prefill_spans_with_decode_rows():
    def routed(load):  # every real token, its share of the chosen experts
        assert load.shape == (KL.count(EXPERTS), KL.num_experts)
        assert 0 < int(load.sum()) <= (1 + 13 + 5 + 3) \
            * KL.num_experts_per_tok * KL.count(EXPERTS)

    mixed_step(KL, make_params(), state(garbage=-2.0), want, routed)


def test_the_fused_scan_beside_inactive_and_mid_prefill_slots():
    fused_scan(KL, make_params(), state(garbage=5.0), want, by_slot)


def test_the_whole_sequence_forwards_follow():
    params = make_params()
    toks = seq_tokens(8, 70)
    close(oracle(KL, params, toks), want(KL, params, toks)[-1])


# --------------------------- departures: each computes ANOTHER model
@functools.lru_cache(maxsize=None)
def _departure_case():
    params = make_params()
    tokens = seq_tokens(4, 40)
    ref = want(KL, params, tokens)[-1]
    assert np.abs(oracle(KL, params, tokens) - ref).max() < ATOL
    return params, tokens, ref


def _scalar_decay(chunked):
    """The rule with ONE decay a head: the mean over the key channels."""
    def rule(q, k, v, g, beta, *args, **kw):
        return chunked(q, k, v, jnp.broadcast_to(
            g.mean(axis=-1, keepdims=True), g.shape), beta, *args, **kw)
    return rule


DEPARTURES = ("scalar_decay", "no_selection_bias", "rotated_k_pe",
              "no_output_gate", "no_gated_head_norm_weight")


@pytest.mark.parametrize("departure", DEPARTURES)
def test_a_forward_that_departs_does_not_agree(departure, monkeypatch):
    """The comparison has teeth only if it says so: every one of these
    misses the reference by 25 x the tolerance or more."""
    params, tokens, ref = _departure_case()
    mc, patched = KL, False
    if departure == "scalar_decay":
        monkeypatch.setattr(gd, "chunked", _scalar_decay(gd.chunked))
        patched = True
    elif departure == "no_selection_bias":
        mc = dataclasses.replace(KL, use_expert_bias=False)
    elif departure == "rotated_k_pe":
        mc = dataclasses.replace(KL, mla_use_nope=False)
    elif departure == "no_output_gate":
        # sigmoid(0) = 1/2, times two: the gate is 1 on every lane
        layers = dict(params["layers"])
        layers["kda_gb"] = jnp.zeros_like(layers["kda_gb"])
        layers["lin_out"] = 2.0 * layers["lin_out"]
        params = dict(params, layers=layers)
    else:
        layers = dict(params["layers"])
        layers["lin_norm"] = jnp.ones_like(layers["lin_norm"])
        params = dict(params, layers=layers)
    if patched:
        _prefill.clear_cache()  # same config, another trace
    miss = np.abs(oracle(mc, params, tokens) - ref).max()
    if patched:
        _prefill.clear_cache()
    assert miss > 25 * ATOL, (departure, miss)


def test_a_bfloat16_rule_state_misses_the_tolerance():
    """The served path with the rule's state rounded to bfloat16 between a
    prompt's two chunks (everything else float32): the accumulator's
    precision is part of the model."""
    params = make_params()
    toks = seq_tokens(1, 23)
    ref = want(KL, params, toks)
    _, st, _ = ragged_step(KL, params, state(), [(0, toks[:11], 0)])
    got, _, _ = ragged_step(KL, params, st, [(0, toks[11:], 11)])
    close(got[0], ref[22])
    low = st[2]._replace(rule=st[2].rule.astype(jnp.bfloat16).astype(
        jnp.float32))
    got, _, _ = ragged_step(KL, params, (st[0], st[1], low),
                            [(0, toks[11:], 11)])
    err = float(np.max(np.abs(np.asarray(got[0]) - ref[22])))
    assert err > 10 * ATOL, err
    assert llama.alloc_slot_state(KL, B, jnp.bfloat16).rule.dtype \
        == jnp.float32  # whatever the weights are served in


# ------------------------------------------------------ the chip's share
def test_the_four_shares_and_the_shared_expert_once_add_up():
    """Four shares of four experts each (offsets 0, 4, 8, 12 of 16 — the
    cell's 0, 64, 128, 192 of 256): what their routed parts give, with the
    shared expert — which every chip computes alike — counted ONCE, is what
    the uncut layer gives, in the program and in the uncut reference. Gates
    are normalised over all the chosen experts in every share, and the
    selection bias is the whole router's, so no share knows the others."""
    uncut = dataclasses.replace(KL, num_experts=16, router_experts=16)
    params = make_params(uncut)
    names = ("w_router", "router_bias") + moe.SHARED + moe.STACKED
    lp = {k: v[0] if k not in moe.STACKED else v
          for k, v in params["layers"].items() if k in names}
    h = jax.random.normal(jax.random.PRNGKey(3), (1, 24, KL.hidden_size))
    whole, load = moe_mlp(uncut, lp, h, layer=0)
    assert int(load.sum()) == 24 * 4
    shared = jnp.einsum(
        "btf,fd->btd", jax.nn.silu(h @ lp["ws_gate"]) * (h @ lp["ws_up"]),
        lp["ws_down"])
    total, loads = shared, []
    for first in (0, 4, 8, 12):
        share = dataclasses.replace(KL, expert_offset=first)
        held = dict(lp, **{k: lp[k][:, first:first + 4]
                           for k in moe.STACKED})
        part, load = moe_mlp(share, held, h, layer=0)
        total = total + (part - shared)
        loads.append(int(load.sum()))
    assert sum(loads) == 24 * 4 and min(loads) >= 0
    assert float(jnp.abs(total - whole).max()) < 1e-5
    # ... and the reference's uncut layer says the same
    ref = kimi_linear_reference()

    def mm(a, w):
        return jnp.matmul(a, w.astype(jnp.float32), precision=ref.HI)

    plain = ref._experts(kimi_linear_keys(uncut), mm, h[0],
                         params["layers"], 0)
    assert float(jnp.abs(plain - whole[0]).max()) < 1e-5
    # a share's reference is the share's program
    at8 = dataclasses.replace(KL, expert_offset=8)
    lp8 = dict(params["layers"], **{k: params["layers"][k][:, 8:12]
                                    for k in moe.STACKED})
    part, _ = moe_mlp(at8, dict(lp, **{k: lp[k][:, 8:12]
                                       for k in moe.STACKED}), h, layer=0)
    assert float(jnp.abs(ref._experts(kimi_linear_keys(at8), mm, h[0], lp8, 0)
                         - part[0]).max()) < 1e-5


# ------------------------------- the file's arithmetic, the served tree
def test_the_served_tree_is_the_files_arithmetic():
    import sys

    sys.path.insert(0, _REPO)
    from benchmarks import serve

    with open(FILE) as f:
        cfg = json.load(f)
    mc = serve.model_config(cfg, rehearse=False)
    assert cfg["head_dim"] == 72 and mc.head_dim == 192
    assert list(mc.layer_types) == cfg["layer_types"]
    shapes = jax.eval_shape(
        lambda: llama.init_params(mc, jax.random.PRNGKey(0)))
    leaves = jax.tree_util.tree_leaves(shapes)
    n = sum(int(np.prod(a.shape)) for a in leaves)
    nbytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in leaves)
    assert n == mc.param_count() == 3_772_368_832
    assert nbytes == 7_544_790_784
    for said in ("39,514,272", "29,114,880", "460,652,800",
                 "3,772,368,832 parameters", "7,544,790,784 B"):
        assert said in cfg["arithmetic"], said
    st = jax.eval_shape(lambda: llama.alloc_slot_state(mc, 16))
    assert st.rule.shape == (6, 17, 128, 4096) and st.rule.dtype == jnp.float32
    assert st.conv.shape == (6, 3, 16, 12288)
    assert mc.kv_row_dims == (640, 0) and mc.cache_layers == 2


# ------------------------------------------------- the engine, by id stream
def _arrivals(n=5, lens=(5, 40, 9, 23, 31), every=2, out=9):
    return [(every * i, f"u{i}", _prompt(i, lens[i % len(lens)]),
             SamplingParams(max_tokens=out + 2 * i)) for i in range(n)]


@pytest.fixture(scope="module")
def engine():
    return _engine(NAME)


def test_the_engine_serves_it_and_counts_both_kinds(engine, monkeypatch):
    """Five requests over four slots through the engine's own loop: spans
    beside decode rows, chunks, fused scans, a slot reused; every launched
    step says what its latent layers attended (`mla_*`) beside the rule's
    (`lin_*`, the windows it solved among them) and the experts'."""
    got, samples = drive(engine, _arrivals(), False, monkeypatch)
    assert all(len(ids[0]) == 9 + 2 * i
               for i, ids in enumerate(got[f"u{i}"] for i in range(5)))
    rt = _rt(engine)
    assert rt.cache.kc.shape[0] == 2
    assert rt.cache.kc.shape[-1] == KL.latent_lanes
    assert rt.cache.vc.shape[-1] == 0
    assert rt.cache.slot_state.rule.shape == (6, 5, 8, 4 * 8)
    assert rt.cache.slot_state.conv.shape == (6, 3, 4, 3 * 32)
    assert rt.cache.prefix_cache is None
    assert {s["mode"] for s in samples} == {"ragged", "decode"}
    for s in samples:
        assert s["mla_pairs"] >= s["mla_ctx_rows"] >= 1
        # (the jnp path serves here: nothing is expanded, and says so)
        assert s["mla_wide_tokens"] == s["mla_absorbed_rows"] == 0
        assert "attn_pairs" not in s and "dsa_ctx_tokens" not in s
        assert s["lin_step_rows"] + s["lin_span_tokens"] >= 1
        assert s["moe_assignments"] >= 0
        if s["mode"] == "decode":
            assert s["lin_prepare_windows"] == s["lin_chunk_pairs"] == 0
        else:
            assert s["lin_prepare_windows"] >= max(s["lin_chunk_pairs"], 1) \
                or s["lin_chunk_pairs"] > s["lin_prepare_windows"]
            assert s["lin_prepare_windows"] >= 1
    first = next(s for s in samples if s["mode"] == "ragged")
    assert first["mla_pairs"] == 5 * 6 // 2  # u0's 5-token prompt
    assert (first["lin_state_resets"], first["lin_prepare_windows"]) == (1, 1)


def test_migration_is_refused_not_served_without_the_state(engine):
    from ollamamq_tpu.engine.engine import MigrationError

    rt = _rt(engine)
    assert rt.export_request(1) is None
    with pytest.raises(MigrationError, match="linear_attention"):
        rt.import_request({"kind": "stream"}, None)


def test_the_model_is_registered_and_its_file_names_it():
    with open(FILE) as f:
        assert json.load(f)["name"].startswith("kimi-linear")
