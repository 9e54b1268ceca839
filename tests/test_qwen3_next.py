"""Qwen3-Next on the served path (PR 47): the gated delta rule with fewer
key heads than value heads, gated attention with a partial rotary embedding,
zero-centred norms, a chip's share of the routed experts behind a gated
shared expert.

LOGITS of the served forwards against the benchmark's plain float32
reference (benchmarks/reference/qwen3_next_decoder.py) at
`test-tiny-qwen3-next`, seeded random weights, float32, on the CPU; the
served tree's size against the configuration file's arithmetic; what the
engine counts. (The departures the seeded weights are drawn to catch, the
share test and the rule at grouped heads: test_qwen3_next_rule.py.)"""

import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ollamamq_tpu.config import (ATTENTION, LINEAR, MODEL_CONFIGS)
from ollamamq_tpu.engine.kv_cache import refusal
from ollamamq_tpu.models import llama
from ollamamq_tpu.ops.sampling import SamplingParams
from test_lfm2 import ATOL
from test_olmo_hybrid import a_span_across_windows, chunks_then_decode
from test_step_overlap import _engine, _prompt, _rt, drive
from testutil import (once_a_sequence, qwen3_next_keys, qwen3_next_reference,
                      seeded_params)

NAME = "test-tiny-qwen3-next"
QN = MODEL_CONFIGS[NAME]
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FILE = os.path.join(_REPO, "benchmarks", "configs",
                    "qwen3-next-80b-a3b-ep4-d12.json")


def make_params(mc=QN, seed=0):
    """The seeded weights as `init_params` draws them: the family's norm
    weights, gates and w_sg are drawn away from the identity there."""
    return seeded_params(mc, (), seed=seed)


@once_a_sequence
def want(mc, params, tokens):
    """The reference's ONE full forward: [T, V] logits."""
    return np.asarray(qwen3_next_reference().logits(
        qwen3_next_keys(mc), params, jnp.asarray(tokens, jnp.int32)))


@functools.partial(jax.jit, static_argnums=(0,))
def _prefill(mc, params, tokens):
    kv = jnp.zeros((mc.count(ATTENTION), 40 * 8, mc.kv_dim), jnp.float32)
    return llama.forward_prefill(
        params, mc, tokens[None], jnp.asarray([tokens.shape[0]]), kv, kv,
        jnp.arange(1, 9, dtype=jnp.int32)[None], 8)[0][0]


def oracle(mc, params, tokens):
    """The program's `forward_prefill` at the last position."""
    return np.asarray(_prefill(mc, params, jnp.asarray(tokens, jnp.int32)))


# ----------------------------------------------------------- the config
def test_the_registered_family_and_its_plan():
    full = MODEL_CONFIGS["qwen3-next:80b-a3b"]
    assert (full.count(LINEAR), full.count(ATTENTION)) == (36, 12)
    assert [(f, len(p), n) for f, p, n in full.layer_plan()] == [(0, 4, 12)]
    assert 79.5e9 < full.param_count() < 79.8e9  # "80 B": 79.67
    assert 3.8e9 < full.param_count(active=True) < 3.95e9  # "A3B"
    assert full.rotary_dim == 64 and full.shared_width == 512
    assert full.state_window == (4, 8192)
    assert (QN.linear_num_key_heads, QN.linear_num_value_heads) == (2, 4)
    assert QN.rotary_dim == 8 and QN.router_width == 16
    assert [(f, len(p), n) for f, p, n in QN.layer_plan()] == [(0, 4, 2)]
    # per-slot state: --tp / --ep and --spec are refused, as the other hybrid
    assert "layer_types" in refusal(QN, spec=True)


@pytest.mark.parametrize("bad,match", [
    (dict(linear_num_value_heads=3), "is not a multiple of"),
    (dict(linear_num_key_heads=8), "is not a multiple of"),
    (dict(partial_rotary_factor=0.35), "not an even number of lanes"),
    (dict(partial_rotary_factor=0.0), "not an even number of lanes"),
    (dict(decoder_sparse_step=2), "decoder_sparse_step 2"),
    (dict(mlp_only_layers=[1]), "mlp_only_layers"),
    (dict(use_sliding_window=True), "use_sliding_window True"),
    (dict(full_attention_interval=2), "does not agree with layer_types"),
    (dict(n_shared_experts=1), "the shared expert has one width"),
    (dict(shared_expert_intermediate_size=0),
     "shared_expert_gate with no shared expert"),
    (dict(attn_bias=True), "the gate's projection carries no bias"),
    (dict(num_experts=0, router_experts=0),
     "belong to an expert layer"),
], ids=["value_heads", "key_heads", "odd_lanes", "no_lanes", "sparse_step",
        "mlp_only", "window", "interval", "two_widths", "gate_of_nothing",
        "gate_bias", "no_experts"])
def test_a_stack_the_program_cannot_run_is_refused_at_construction(bad,
                                                                   match):
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(QN, **bad)


def test_the_seeded_weights_are_drawn_away_from_the_identity():
    params = make_params()
    lp = params["layers"]
    d, hd = QN.hidden_size, QN.head_dim
    assert lp["wq"].shape == lp["wq_gate"].shape == (2, d, QN.q_dim)
    assert lp["q_norm"].shape == lp["k_norm"].shape == (2, hd)
    assert lp["lin_in"].shape == (6, d, 2 * 16 + 2 * 64)
    assert lp["w_router"].shape == (8, d, 16)       # the router's width
    assert lp["we_gate"].shape == (8, 8, d, 32)     # the experts held
    assert lp["ws_gate"].shape == (8, d, 48)        # a width of its own
    assert lp["w_shared_gate"].shape == (8, d)
    for w in (lp["attn_norm"], lp["mlp_norm"], lp["q_norm"], lp["k_norm"],
              params["final_norm"]):  # zero-centred: around 0, sd 0.1
        assert abs(float(w.mean())) < 0.05 and 0.05 < float(w.std()) < 0.2
    assert bool(jnp.all(lp["lin_norm"] == 1.0))     # the plain weight


# --------------------------------------- logits, against the reference
CHUNKINGS = {
    "two_halves": (11, 12),
    "spans_of_1_and_2": (20, 1, 2),   # state carried into one-token rows
}


@pytest.mark.parametrize("chunks", CHUNKINGS.values(), ids=CHUNKINGS.keys())
def test_prefill_in_chunks_then_decode_matches_the_reference(chunks):
    chunks_then_decode(QN, make_params(), want, chunks,
                       held=(8, 8))  # expert layers x experts HELD


def test_a_span_across_window_boundaries_beside_another_row(monkeypatch):
    """...by rows whose key heads each serve two value heads."""
    a_span_across_windows(QN, make_params(), want, 5 * ATOL, monkeypatch)


# ------------------------------- the file's arithmetic, the served tree
def test_the_served_tree_is_the_files_arithmetic():
    import sys

    sys.path.insert(0, _REPO)
    from benchmarks import serve

    with open(FILE) as f:
        cfg = json.load(f)
    mc = serve.model_config(cfg, rehearse=False)
    shapes = jax.eval_shape(
        lambda: llama.init_params(mc, jax.random.PRNGKey(0)))
    leaves = jax.tree_util.tree_leaves(shapes)
    n = sum(int(np.prod(a.shape)) for a in leaves)
    nbytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in leaves)
    assert n == mc.param_count() == 5_423_084_736
    assert nbytes == 10_846_170_624
    assert "5,423,084,736 parameters = 10,846,169,472 B" in cfg["arithmetic"]
    assert "10,846,170,624 B" in cfg["arithmetic"]
    st = jax.eval_shape(lambda: llama.alloc_slot_state(mc, 16))
    assert st.rule.shape == (9, 17, 128, 4096) and st.rule.dtype == jnp.float32
    assert st.conv.shape == (9, 3, 16, 8192)
    assert mc.kv_row_dims == (512, 512) and mc.cache_layers == 3


# ------------------------------------------------- the engine, by id stream
def _arrivals(n=5, lens=(5, 40, 9, 23, 31), every=2, out=9):
    return [(every * i, f"u{i}", _prompt(i, lens[i % len(lens)]),
             SamplingParams(max_tokens=out + 2 * i)) for i in range(n)]


def test_the_engine_serves_it_and_counts_its_attention(monkeypatch):
    """Five requests over four slots through the engine's own loop: spans
    beside decode rows, chunks, fused scans; every launched step says what
    its attention layers
    attended (`attn_pairs`, `attn_ctx_rows`) beside the rule's and the
    experts' counters."""
    eng = _engine(NAME)
    got, samples = drive(eng, _arrivals(), False, monkeypatch)
    assert all(len(ids[0]) == 9 + 2 * i
               for i, ids in enumerate(got[f"u{i}"] for i in range(5)))
    rt = _rt(eng)
    assert rt.cache.kc.shape[0] == 2 and rt.cache.kc.shape[-1] == QN.kv_dim
    assert rt.cache.slot_state.rule.shape == (6, 5, 8, 4 * 16)
    assert rt.cache.prefix_cache is None
    assert {s["mode"] for s in samples} == {"ragged", "decode"}
    for s in samples:
        assert s["attn_pairs"] >= s["attn_ctx_rows"] >= 1
        assert s["attn_tall_tokens"] == 0  # the jnp path serves here
        assert "mla_rows" not in s and "lin_step_rows" in s
        assert s["moe_assignments"] >= 0
        if s["mode"] == "decode":  # a scan's pass reads each slot's context
            assert s["attn_pairs"] == s["attn_ctx_rows"]
    first = next(s for s in samples if s["mode"] == "ragged")
    assert first["attn_pairs"] == 5 * 6 // 2  # u0's 5-token prompt
    assert first["attn_ctx_rows"] == 5


def test_the_model_is_registered_under_a_chatml_name():
    from ollamamq_tpu.server.templates import chat_family

    assert chat_family(MODEL_CONFIGS["qwen3-next:80b-a3b"]) == "chatml"
    with open(FILE) as f:
        assert json.load(f)["name"].startswith("qwen")
