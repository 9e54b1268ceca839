"""Ask the chip's compiler, without the chip (test_chip_compile.py), the
configuration file whose residual path is four streams: both launches of the
hyper-connection at the published width, then every program its cell runs —
both step programs of the Xing4.0 file at PUBLISHED widths.
"""

import math
import os
import sys

import jax
import jax.numpy as jnp

from chip_compile import _file_model
from ollamamq_tpu.models import llama
from ollamamq_tpu.ops import hyper_connection as hc


def test_both_launches_compile_at_the_published_width(v5e):
    """`mhc_mix_in_pallas` (with the read-out through it) and
    `mhc_mix_out_pallas` over 64 rows (a decode pass: less than a tile) and
    512 (a chunk: four tiles) of four bfloat16 streams of 3584: ONE Mosaic
    custom call each, no XLA loop for the twenty iterations, the streams
    aliased through the write-back and no temporary beside the operands."""
    from jax.sharding import SingleDeviceSharding

    one = SingleDeviceSharding(v5e.devices[0])
    k = hc.Consts(4, 20, 1e-6, 1e-6, -30.0, 30.0)
    c = 3584

    def s(*shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one)

    for rows in (64, 512):
        x = s(rows, 4, c, dt=jnp.bfloat16)
        mix_in = jax.jit(lambda x, p, a, b: hc.mix_in(
            x, p, a, b, k, "pallas")).lower(
                x, s(24, 4 * c), s(3), s(24)).compile()
        text = mix_in.as_text()
        assert "mhc_mix_in_pallas" in text and " while(" not in text
        assert text.count("tpu_custom_call") == 1
        assert mix_in.memory_analysis().temp_size_in_bytes < 1 << 20
        mix_out = jax.jit(lambda x, d, m: hc.mix_out(x, d, m, k, "pallas"),
                          donate_argnums=0).lower(
            x, s(rows, c, dt=jnp.bfloat16), s(rows, 128)).compile()
        text = mix_out.as_text()
        assert "mhc_mix_out_pallas" in text
        assert text.count("tpu_custom_call") == 1
        assert mix_out.memory_analysis().alias_size_in_bytes \
            >= rows * 4 * c * 2  # the streams: written where they were read
        read = jax.jit(lambda x, p, a, b: hc.read_out(
            x, p, a, b, k, "pallas")).lower(
                x, s(4, 4 * c), s(1), s(4)).compile()
        assert read.as_text().count("tpu_custom_call") == 1


def test_xing4_file_compiles_whole_and_fits_the_chip(v5e):
    """The Xing4.0 configuration file (PR 69) at PUBLISHED widths, 6 layers,
    a 64-token ragged step and the fused scan: both step programs compile
    for the chip — the dense latent kernel over the six-layer latent pool,
    the grouped matmul over 64 experts, 12 + 1 launches of the mix-in and 12
    of the mix-out — with the memory a deployment has: the arguments
    (weights 9.59 GB, the latent pool 2.27 GB) under 11.95 GB, the pool
    aliased to the results, temporaries under 0.5 GB; the latent stacks held
    rank-minor and no weight stack re-laid, no pool layer sliced out."""
    name = "xing4.0-29b-a4b-d6"
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "scripts"))
    import step_hlo_copies as shc
    from benchmarks import serve
    from ollamamq_tpu import cli

    cfg, mc = _file_model(name)
    args = cli.build_parser().parse_args(
        ["--models", cfg["name"]] + serve.server_flags(cfg, False))
    assert mc.param_count() == 4_792_727_177 and mc.streams == 4
    assert llama.alloc_slot_state(mc, args.max_slots) is None
    pool = (6, args.num_pages * args.page_size, 640)
    pool_bytes = math.prod(pool) * 2
    assert 2.26e9 < pool_bytes < 2.27e9
    lowered, params = shc.step_programs(mc, args, v5e, 64)
    assert list(lowered) == ["mq_ragged_step", "mq_decode_scan"]
    assert set(llama.weight_formats(mc, params)) == {"mla_wuq", "mla_wukv"}
    for prog, low in lowered.items():
        compiled = low.compile()
        mem = compiled.memory_analysis()
        assert 11.8e9 < mem.argument_size_in_bytes <= 11.95e9, (prog, mem)
        assert mem.alias_size_in_bytes >= pool_bytes, prog
        assert mem.temp_size_in_bytes < 0.5e9, (prog, mem)
        text = compiled.as_text()
        assert all(k in text for k in (
            "mla_dense_paged_attention_pallas", "mhc_mix_in_pallas",
            "mhc_mix_out_pallas")), prog
        found = shc.moves(text, 8 << 20)
        layer = [m for m in found
                 if tuple(d for d in m["dims"] if d != 1) == pool[1:]]
        assert not layer, (prog, layer)
        # (the scan re-lays `mla_wdkv`'s 24.8 MB ONCE a launch of eight
        # passes, outside its loop: ~0.06 ms of ~80; nothing a pass)
        copied = {n for c in shc.weight_copies(found, params)
                  for n in c["stacks"]}
        assert copied <= ({"mla_wdkv"} if prog == "mq_decode_scan"
                          else set()), (prog, copied)
        print(prog, mem.argument_size_in_bytes, mem.alias_size_in_bytes,
              mem.temp_size_in_bytes, mem.output_size_in_bytes)
