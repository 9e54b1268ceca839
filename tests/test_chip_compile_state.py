"""Ask the chip's compiler, without the chip (test_chip_compile.py): the
engine's own step programs — everything they carry is updated in place — at
the widths of the models that keep a convolution window beside the pool.
"""

import re

import jax
import jax.numpy as jnp
import pytest

from chip_compile import (B, HD, HK, LAYERS, LOOP_CFG, MP, NP, PS,
                          _lower_step_program, step_program)
from ollamamq_tpu.config import ModelConfig


# ---------------------------------------------------------------------------
# The engine's own step programs: everything they carry is updated in place.
# ---------------------------------------------------------------------------

# LFM2-8B-A1B's layers (config.py) over a short stack that keeps its plan —
# a dense prefix and a period of (attention, conv, conv, conv) with experts,
# twice — and a small vocabulary.
LFM2_CFG = ModelConfig(
    name="chip-compile-lfm2-widths", vocab_size=2048, hidden_size=2048,
    intermediate_size=7168, num_layers=9, num_heads=32, num_kv_heads=8,
    head_dim=64, max_seq_len=MP * PS, rope_theta=1e6, rms_norm_eps=1e-5,
    tie_embeddings=True, qk_norm="head", num_experts=32,
    num_experts_per_tok=4, norm_topk_prob=True, norm_topk_eps=1e-6,
    router_score="sigmoid", use_expert_bias=True, num_dense_layers=1,
    moe_intermediate_size=1792,
    layer_types=("conv",) + ("full_attention", "conv", "conv", "conv") * 2)


# Olmo-Hybrid-7B's layers (config.py) over two of its periods and a small
# vocabulary: 30 heads of 128 in the attention kernels (3840 lanes, group 1),
# the rule's state rows [96, 30 x 192] float32 for 6 linear layers.
OLMO_HYBRID_CFG = ModelConfig(
    name="chip-compile-olmo-hybrid-widths", vocab_size=2048, hidden_size=3840,
    intermediate_size=11008, num_layers=8, num_heads=30, num_kv_heads=30,
    head_dim=128, max_seq_len=MP * PS, rope_theta=None, rms_norm_eps=1e-6,
    qk_norm="full", norm_order="post", linear_num_key_heads=30,
    linear_num_value_heads=30, linear_key_head_dim=96,
    linear_value_head_dim=192, linear_conv_kernel_dim=4,
    linear_allow_neg_eigval=True,
    layer_types=(("linear_attention",) * 3 + ("full_attention",)) * 2)


@pytest.mark.parametrize("which", ["mq_ragged_step", "mq_decode_scan"])
def test_step_programs_alias_pools_ring_and_id_carry(v5e, which):
    """The two programs of the pipelined loop as the engine jits them
    (PR 28 added the `last_ids` carry: a step launched behind an unsettled
    one reads a row's input token from it): both pools, the penalty ring
    and the carry are donated and come back aliased — the compiled program
    holds no second copy of any."""
    _, compiled, _, carried = step_program(v5e, which)
    S, W = B, 64
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert carried == (2 * LAYERS * NP * PS * HK * HD * 2   # both pools
                       + (S + 1) * W * 4 + S * 4)   # the ring, the carry
    assert mem.alias_size_in_bytes >= carried, (mem, carried)
    assert mem.temp_size_in_bytes < NP * PS * HK * HD * 2, mem


@pytest.mark.parametrize("cfg", [LOOP_CFG, LFM2_CFG],
                         ids=["uniform", "lfm2_widths"])
@pytest.mark.parametrize("which", ["mq_ragged_step", "mq_decode_scan"])
def test_step_programs_lower_to_the_same_text_twice(v5e, which, cfg):
    """A step program built twice (`engine/step_program.py`'s builders, each
    time a jit object of its own: `fresh`) lowers to the same StableHLO
    text — a uniform stack's and one whose layers differ (a scan over a
    period of kinds, expert matmuls): nothing in the trace depends on what
    was built before it (a counter, an id, a cache's order), and nothing on
    a runtime — there is none here. A change that is
    to leave the step programs alone is shown to by comparing this text,
    hashed, between its parent and itself (ROADMAP C11) — which says
    something only if the text is a function of the code."""
    first, second = (
        _lower_step_program(v5e, which, cfg, fresh=True)[0].as_text()
        for _ in range(2))
    assert which in first and "stablehlo." in first
    assert first == second


@pytest.mark.parametrize("which", ["mq_ragged_step", "mq_decode_scan"])
def test_lfm2_width_step_programs_carry_pool_and_conv_state_in_place(
        v5e, which):
    """A stack whose layers differ (PR 32), at LFM2-8B-A1B's widths: the
    attention kernels at 8 kv heads of 64 (512 lanes, group 4) and the
    grouped expert matmul at [2048, 1792] compile for the chip; the KV pool
    — for the 2 attention layers only — the conv layers' per-slot state,
    the ring and the id carry all come back aliased; no weight stack is
    copied out for a layer (the temporaries stay under a quarter of ONE
    expert layer's gate matrix, 235 MB), and the scan traces each distinct
    layer of the period once: 3 grouped matmuls for each of its 4 layers,
    one attention kernel."""
    _, compiled, _, carried = step_program(v5e, which, LFM2_CFG)
    text = compiled.as_text()
    assert len(re.findall(r"%gmm[.\d]* = ", text)) == 3 * 4
    assert "ragged-dot" not in text
    assert text.count("tpu_custom_call") >= 3 * 4 + 1
    mem = compiled.memory_analysis()
    conv_state = 7 * 2 * B * 2048 * 2  # a tap a plane, no trash row
    assert carried >= 2 * 2 * NP * PS * 512 * 2 + conv_state
    assert mem.alias_size_in_bytes >= carried, (mem, carried)
    assert mem.temp_size_in_bytes < 32 * 2048 * 1792 * 2 // 4, mem


@pytest.mark.parametrize("which", ["mq_ragged_step", "mq_decode_scan"])
def test_olmo_hybrid_width_step_programs_carry_the_rule_state_in_place(
        v5e, which):
    """Linear-attention layers (PR 35), at Olmo-Hybrid-7B's widths: the
    attention kernels at 30 kv heads of 128 (3840 lanes, group 1) and the
    rule's step kernel on [96, 5760] float32 rows compile for the chip; the
    KV pool — for the 2 attention layers only — the window of the linear
    layers' convolution, the rule's state (6 x 65 rows of 2.2 MB: 863 MB,
    held exactly — no lane padding — and never copied), the ring and the id
    carry all come back aliased; the temporaries stay under a TENTH of the
    rule's state (a gather of a layer's 64 rows would be a sixth)."""
    _, compiled, _, carried = step_program(v5e, which, OLMO_HYBRID_CFG)
    text = compiled.as_text()
    assert "gated_delta_step_pallas" in text
    assert text.count("tpu_custom_call") >= 1 + 3  # attention, 3 linear layers
    mem = compiled.memory_analysis()
    rule = 6 * (B + 1) * 96 * 30 * 192 * 4
    window = 6 * 3 * B * 11520 * 2  # a tap a plane, no trash row
    assert carried >= 2 * 2 * NP * PS * 3840 * 2 + rule + window
    assert mem.alias_size_in_bytes >= carried, (mem, carried)
    assert mem.temp_size_in_bytes < rule // 10, mem


# (H, Hk, dk, dv, plain, slots, layers) of the four callers of
# `gated_delta.ragged` at their published widths and their cells' slots.
CHUNK_RULE_SHAPES = {
    "qwen3_next": (32, 16, 128, 128, False, 16, 9),
    "olmo_hybrid": (30, 30, 96, 192, False, 64, 12),
    "falcon_h1": (32, 2, 256, 128, True, 64, 6),
    "minicpm_sala": (32, 32, 128, 128, True, 16, 12),
}


@pytest.mark.parametrize("model", CHUNK_RULE_SHAPES)
def test_the_chunked_rules_pair_kernel_compiles_at_the_published_shapes(
        v5e, model):
    """`gated_delta.ragged` of a 512-token stream on the Pallas path (PR 62),
    at each caller's heads and widths: the (row, window) pairs are ONE
    `chunk_rule_pallas` custom call — fp32 contract precision, a head's
    lanes no whole tile (Olmo-Hybrid), a 256-row state (Falcon-H1) and all —
    beside the one-token rows' kernel and, where the rule has a solve, ONE
    `chunk_solve_pallas` (PR 66), no `while` is left in the program,
    and the carried state comes back aliased: the kernel updates it in
    place."""
    from jax.sharding import SingleDeviceSharding

    from ollamamq_tpu.ops import gated_delta

    h, hk, dk, dv, plain, slots, layers = CHUNK_RULE_SHAPES[model]
    t, one = 512, SingleDeviceSharding(v5e.devices[0])

    def s(*shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one)

    i32 = dict(dt=jnp.int32)
    state = s(layers, slots + 1, dk, h * dv)
    compiled = jax.jit(
        lambda *a: gated_delta.ragged(*a, impl="pallas", plain=plain),
        donate_argnums=5).lower(
            s(t, hk, dk), s(t, hk, dk), s(t, h, dv), s(t, h), s(t, h), state,
            s(**i32), s(slots, **i32), s(t, **i32), s(t, **i32),
            s(slots, **i32), s(slots, **i32), s(slots, **i32)).compile()
    text = compiled.as_text()
    assert "chunk_rule_pallas" in text and " while(" not in text
    # the rows', the pairs', and — not `plain` — the window solve's (PR 66)
    assert ("chunk_solve_pallas" in text) == (not plain)
    assert text.count("tpu_custom_call") == (2 if plain else 3)
    mem = compiled.memory_analysis()
    held = layers * (slots + 1) * dk * h * dv * 4
    assert mem.alias_size_in_bytes >= held, (mem, held)
    assert mem.temp_size_in_bytes < held // 10, mem


def test_qwen3_nexts_ragged_rung_runs_the_pairs_in_the_kernel(v5e):
    """The 512-token ragged step of `qwen3-next-80b-a3b-ep4-d12`'s file as
    the engine builds it, lowered (no compile): its linear layers' rule
    stage (`lin_rule`) holds `chunk_rule_pallas` and no `while` — the pair
    loop that was 72 trips of ~27 fusions a step is not in the program."""
    from chip_compile import _file_model, _script

    script = _script()
    cfg, mc = _file_model("qwen3-next-80b-a3b-ep4-d12")
    from benchmarks import serve
    from ollamamq_tpu import cli

    flags = cli.build_parser().parse_args(
        ["--models", cfg["name"]] + serve.server_flags(cfg, False))
    lowered, _ = script.step_programs(mc, flags, v5e, 512)
    text = lowered["mq_ragged_step"].as_text(debug_info=True)
    assert "chunk_rule_pallas" in text
    assert re.search(r'"lin_rule/[^"]*"', text)  # the stage is named there
    assert not re.search(r'"lin_rule/[^"]*while', text)


@pytest.mark.parametrize("cfg", [LOOP_CFG, LFM2_CFG, OLMO_HYBRID_CFG],
                         ids=["dense", "lfm2", "olmo_hybrid"])
def test_ragged_step_is_fed_one_host_array(v5e, cfg):
    """One upload a step: besides `params`, the compiled ragged step has
    exactly ONE parameter that is not donated device state — the packed
    int32 buffer of its host inputs. The RNG key is made inside (no key
    parameter), so nothing else is dispatched or transferred for a step.
    The conv layers' state is one more donated argument (no leaf at all
    for a model without such layers), a linear-attention model's two."""
    lowered, _, words, _ = step_program(v5e, "mq_ragged_step", cfg)
    _params, *rest = lowered.args_info[0]
    rest = jax.tree_util.tree_leaves(rest)
    fed = [a for a in rest if not a.donated]
    n_state = {LOOP_CFG: 0, LFM2_CFG: 1, OLMO_HYBRID_CFG: 2}[cfg]
    assert len(rest) == 5 + n_state and len(fed) == 1, rest
    assert (fed[0].shape, fed[0].dtype) == ((words,), jnp.int32), fed
