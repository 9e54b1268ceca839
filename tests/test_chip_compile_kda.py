"""Ask the chip's compiler, without the chip (test_chip_compile.py), the
configuration file whose linear layers decay a key channel on its own: Kimi
Delta Attention beside NoPE latent attention as a layer kind, at its
published widths — the vector-decay kernels alone, then both step programs.
"""

import math
import os
import sys

import jax
import jax.numpy as jnp

from chip_compile import _file_model
from ollamamq_tpu.models import llama


def test_the_vector_decay_kernels_compile_at_the_published_shape(v5e):
    """`gated_delta.ragged` of a 512-token stream on the Pallas path at (32,
    128, 128) with g [T, H, dk]: the one-token rows' kernel with the decay as
    columns, the (row, window) pairs ONE `chunk_rule_pallas` custom call —
    a [dk, C] block of G a head, eight heads a block —, the window solve ONE
    `chunk_solve_pallas` custom call in front of it (PR 66: no `while`, and
    none of `_prepare_vector`'s fusions over [windows, H, 4, 16, 16]), and
    the carried state aliased: updated in place."""
    from jax.sharding import SingleDeviceSharding

    from ollamamq_tpu.ops import gated_delta
    from ollamamq_tpu.ops.pallas import chunk_rule

    h, dk, dv, slots, layers, t = 32, 128, 128, 16, 6, 512
    assert chunk_rule.blocks(h, dk, dv, False, True) == (1, 8)
    one = SingleDeviceSharding(v5e.devices[0])

    def s(*shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one)

    i32 = dict(dt=jnp.int32)
    compiled = jax.jit(
        lambda *a: gated_delta.ragged(*a, impl="pallas"),
        donate_argnums=5).lower(
            s(t, h, dk), s(t, h, dk), s(t, h, dv), s(t, h, dk), s(t, h),
            s(layers, slots + 1, dk, h * dv), s(**i32), s(slots, **i32),
            s(t, **i32), s(t, **i32), s(slots, **i32), s(slots, **i32),
            s(slots, **i32)).compile()
    text = compiled.as_text()
    assert "chunk_rule_pallas" in text and "gated_delta_step_pallas" in text
    assert "chunk_solve_pallas" in text and " while(" not in text
    assert text.count("tpu_custom_call") == 3  # the rows', solve, pairs
    assert "f32[8,32,4,16,16]" not in text
    mem = compiled.memory_analysis()
    held = layers * (slots + 1) * dk * h * dv * 4
    assert mem.alias_size_in_bytes >= held, (mem, held)
    # the kernels' operands and results only (the XLA solve's [windows, H,
    # 4, 16, 16, dk] decay was 134 MB unless fused into its sums)
    assert mem.temp_size_in_bytes < 100e6, mem


def test_kimi_linear_file_compiles_whole_and_carries_its_state_in_place(v5e):
    """The Kimi-Linear configuration file (PR 63) at PUBLISHED widths, 8
    layers, a 64-token ragged step: both step programs compile for the chip —
    the dense latent kernel over the TWO-layer latent pool, both rule kernels
    at the vector reading, the grouped matmul over 64 held experts — with the
    memory a deployment has: the arguments (weights 7.54 GB, the latent pool
    1.04, six rule states 0.21, the conv windows) under 8.85 GB, ALL of the
    carried state aliased to the results, temporaries under 0.3 GB; no weight
    stack is re-laid (`wq`, the full-rank q, is held rank-minor: it is split
    into heads at once) and neither program slices a layer out of the pool
    or a row's layer out of the state."""
    name = "kimi-linear-48b-a3b-ep4-d8"
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "scripts"))
    import step_hlo_copies as shc
    from benchmarks import serve
    from ollamamq_tpu import cli

    cfg, mc = _file_model(name)
    args = cli.build_parser().parse_args(
        ["--models", cfg["name"]] + serve.server_flags(cfg, False))
    slots, ps, pages = args.max_slots, args.page_size, args.num_pages
    held = jax.eval_shape(lambda: llama.alloc_slot_state(mc, slots))
    assert held.rule.shape == (6, slots + 1, 128, 4096)
    assert held.conv.shape == (6, 3, slots, 12288)
    assert held.ring is None and held.pooled is None
    pool = (2, pages * ps, 640)
    state_bytes = math.prod(pool) * 2 + sum(
        math.prod(a.shape) * a.dtype.itemsize
        for a in jax.tree_util.tree_leaves(held))
    assert 1.25e9 < state_bytes < 1.27e9
    lowered, params = shc.step_programs(mc, args, v5e, 64)
    assert list(lowered) == ["mq_ragged_step", "mq_decode_scan"]
    assert set(llama.weight_formats(mc, params)) == {"wq", "mla_wukv"}
    for prog, low in lowered.items():
        compiled = low.compile()
        mem = compiled.memory_analysis()
        assert mem.argument_size_in_bytes <= 8.85e9, prog
        assert mem.alias_size_in_bytes >= state_bytes, prog
        assert mem.temp_size_in_bytes < 0.3e9, prog
        text = compiled.as_text()
        want = {"mla_dense_paged_attention_pallas",
                "gated_delta_step_pallas"} | (
                    {"chunk_rule_pallas", "chunk_solve_pallas"}
                    if prog == "mq_ragged_step"
                    else set())
        assert all(k in text for k in want), prog
        found = shc.moves(text, 8 << 20)
        layer = [m for m in found
                 if tuple(d for d in m["dims"] if d != 1) in (
                     pool[1:], held.rule.shape[1:], held.rule.shape[2:])]
        assert not layer, (prog, layer)
        assert not shc.weight_copies(found, params), prog
