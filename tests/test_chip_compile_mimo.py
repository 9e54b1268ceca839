"""Ask the chip's compiler, without the chip (test_chip_compile.py), the
configuration file whose attention kinds differ in head shape: MiMo-V2-Flash
at its published widths — both attention kernels at key heads of 192 lanes
beside value heads of 128 (`kv_contract.MxuSplit`), at the full layers' group
of 16 and at the window layers' group of 8 with the sink, then both step
programs of the file as the cell runs them."""

import math
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from chip_compile import _file_model
from ollamamq_tpu.models import llama
from ollamamq_tpu.ops.attention import (paged_decode_attention_any,
                                        ragged_attention_any)
from ollamamq_tpu.ops.pallas import paged_attention, ragged_attention

NAME = "mimo-v2-flash-ep16-d7"
PS = 32


@pytest.mark.parametrize("hk,window", [(4, 0), (8, 128)],
                         ids=["full_g16", "window_g8_sink"])
def test_both_kernels_compile_at_the_published_head_shapes(v5e, hk, window):
    """64 heads of 192 / 128 lanes over the cell's own pool ([2, 405504, 768]
    and [.., 512]) and rings ([5, 17 x 672, 1536] and [.., 1024]): the ragged
    kernel on the 512-token rung — the tall trip at 64 tokens x 16 = 1024
    row-heads a lane tile in the full layers fits the scoped VMEM — and on
    the 64-token rung, and the decode kernel; a window layer's under its own
    names, the sink a float32 operand."""
    one = SingleDeviceSharding(v5e.devices[0])

    def s(*shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one)

    slots, cols = 16, 24 if window else 536
    rows = 17 * 672 if window else 12672 * PS
    layers = 5 if window else 2
    kc = s(layers, rows, hk * 192, dt=jnp.bfloat16)
    vc = s(layers, rows, hk * 128, dt=jnp.bfloat16)
    more = dict(window=window) if window else {}

    def ragged(q, kc, vc, pt, ts, tp, kl, qs, ql, base, sink):
        return ragged_attention_any(
            "pallas", q, kc, vc, 1, pt, ts, tp, kl, qs, ql, PS,
            **(dict(more, pos_base=base, sink=sink) if window else {}))

    def decode(q, kc, vc, pt, sl, base, sink):
        return paged_decode_attention_any(
            "pallas", q, kc, vc, 1, pt, sl, PS,
            **(dict(more, pos_base=base, sink=sink) if window else {}))

    sink = s(64, dt=jnp.float32)
    for tokens in (512, 64):
        text = jax.jit(ragged).lower(
            s(tokens, 64, 192, dt=jnp.bfloat16), kc, vc, s(slots, cols),
            s(tokens), s(tokens), s(slots), s(slots), s(slots), s(slots),
            sink).compile().as_text()
        assert "tpu_custom_call" in text
        assert (ragged_attention.WINDOW_NAME in text) == bool(window)
    text = jax.jit(decode).lower(
        s(slots, 64, 192, dt=jnp.bfloat16), kc, vc, s(slots, cols), s(slots),
        s(slots), sink).compile().as_text()
    assert (paged_attention.WINDOW_NAME in text) == bool(window)


def test_the_file_compiles_whole_and_carries_pool_and_rings_in_place(v5e):
    """The configuration file at PUBLISHED widths, 7 layers, the cell's
    512-token ragged step and its decode scan of 8 passes: both compile for
    the chip with the memory a deployment has — the arguments (weights 6.86
    GB, the pool 2.08, the rings 0.29) under 9.3 GB, ALL of the carried state
    aliased to the results; two launches of the full layers' names and two
    call sites for the five window layers (four of them one scan's); no
    weight stack is re-laid (`wq` / `wk` and the window layers' `swa_wq` /
    `swa_wk` are held rank-minor: split into heads at once)."""
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "scripts"))
    import step_hlo_copies as shc
    from benchmarks import serve
    from ollamamq_tpu import cli

    cfg, mc = _file_model(NAME)
    args = cli.build_parser().parse_args(
        ["--models", cfg["name"]] + serve.server_flags(cfg, False))
    slots, ps, pages = args.max_slots, args.page_size, args.num_pages
    held = jax.eval_shape(lambda: llama.alloc_slot_state(
        mc, slots, ring_rows=mc.ring_rows(512, ps)))
    assert held.ring.k.shape == (5, 17 * 672, 1536)
    assert held.ring.v.shape == (5, 17 * 672, 1024)
    pool_bytes = 2 * pages * ps * (768 + 512) * 2
    ring_bytes = sum(math.prod(a.shape) * a.dtype.itemsize
                     for a in (held.ring.k, held.ring.v))
    state_bytes = pool_bytes + ring_bytes
    assert (pool_bytes, ring_bytes) == (2_076_180_480, 292_454_400)
    assert mc.param_count() * 2 == 6_859_910_784
    lowered, params = shc.step_programs(mc, args, v5e, 512)
    assert list(lowered) == ["mq_ragged_step", "mq_decode_scan"]
    assert set(llama.weight_formats(mc, params)) \
        == {"wq", "wk", "swa_wq", "swa_wk"}
    names = {"mq_ragged_step": ("ragged_paged_attention_pallas",
                                ragged_attention.WINDOW_NAME),
             "mq_decode_scan": ("paged_decode_attention_pallas",
                                paged_attention.WINDOW_NAME)}
    for prog, low in lowered.items():
        compiled = low.compile()
        mem = compiled.memory_analysis()
        assert mem.argument_size_in_bytes <= 9.3e9, (prog, mem)
        assert mem.alias_size_in_bytes >= state_bytes, (prog, mem)
        assert mem.temp_size_in_bytes < 1.0e9, (prog, mem)
        text = compiled.as_text()
        full, swa = names[prog]
        assert len(re.findall(rf"%{full}[.\d]* = ", text)) == 2, prog
        assert len(re.findall(rf"%{swa}[.\d]* = ", text)) == 2, prog
        assert not shc.weight_copies(shc.moves(text, 8 << 20), params), prog
        print(prog, mem.argument_size_in_bytes, mem.alias_size_in_bytes,
              mem.temp_size_in_bytes, mem.output_size_in_bytes)
