"""Ask the chip's compiler, without the chip (test_chip_compile.py), the
models whose layers hold a state-space mixer: Falcon-H1's Mamba-2 (SSD)
recurrence at its widths and in its configuration file, Phi-4-mini-flash's
selective scan in its own.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from chip_compile import (B, MP, NP, PS, _file_model, _step_hlo_copies,
                          step_program)
from ollamamq_tpu.config import ModelConfig
from ollamamq_tpu.models import llama


# Falcon-H1-34B's layers (config.py) at every published width over two layers
# and a small vocabulary: GQA 20/4 of 128 (group 5: a new shape to both
# attention kernels) beside the mixer's 32 heads of 128 under 2 groups of 256.
FALCON_H1_CFG = ModelConfig(
    name="chip-compile-falcon-h1-widths", vocab_size=2048, hidden_size=5120,
    intermediate_size=21504, num_layers=2, num_heads=20, num_kv_heads=4,
    head_dim=128, max_seq_len=MP * PS, rope_theta=1e11, rms_norm_eps=1e-5,
    mamba_d_ssm=4096, mamba_d_state=256, mamba_d_head=128, mamba_n_heads=32,
    mamba_n_groups=2, mamba_d_conv=4, embedding_multiplier=5.656854249492381,
    lm_head_multiplier=0.0078125, attention_out_multiplier=0.0375,
    key_multiplier=0.011048543456039804, ssm_in_multiplier=0.25,
    ssm_out_multiplier=0.08838834764831845,
    ssm_multipliers=(0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                     0.3535533905932738),
    mlp_multipliers=(0.1767766952966369, 0.011160714285714284))


@pytest.mark.parametrize("which", ["mq_ragged_step", "mq_decode_scan"])
def test_falcon_h1_width_step_programs_carry_the_mixer_state_in_place(
        v5e, which):
    """Attention AND a state-space mixer in every layer (PR 54), at
    Falcon-H1-34B's widths: the attention kernels at 4 kv heads of 128 under
    20 q heads (group 5) and the recurrence's step kernel on [256, 4096]
    float32 rows (32 heads, 256, 128: four 1 MB blocks a row) compile for the
    chip under a name of their own; the KV pool of the SAME two layers, the
    window of their convolution, the mixers' state (2 x 65 rows of 4 MiB:
    545 MB, held exactly — no lane padding — and never copied), the ring and
    the id carry all come back aliased; the temporaries stay under a QUARTER
    of the state (73 MB in the scan, a layer's slice of `wq` among them: a
    gather of a layer's 64 rows would be half of the state, 268 MB)."""
    _, compiled, _, carried = step_program(v5e, which, FALCON_H1_CFG)
    text = compiled.as_text()
    assert "ssd_step_pallas" in text
    assert text.count("tpu_custom_call") >= 2  # attention, the step kernel
    mem = compiled.memory_analysis()
    state = 2 * (B + 1) * 256 * 32 * 128 * 4
    window = 2 * 3 * B * 5120 * 2  # a tap a plane, no trash row
    assert carried >= 2 * 2 * NP * PS * 512 * 2 + state + window
    assert mem.alias_size_in_bytes >= carried, (mem, carried)
    assert mem.temp_size_in_bytes < state // 4, mem


def test_the_step_kernel_compiles_at_the_published_head_shape(v5e):
    """`ssd_step_pallas` alone at (32 heads, 256, 128) over six layers' state
    of 64 slots, as the cell holds it: `head_blocks` gives 8 heads a 1 MB
    block, and the state comes back aliased."""
    from ollamamq_tpu.ops.pallas.gated_delta_step import head_blocks
    from ollamamq_tpu.ops.pallas.ssd_step import ssd_step_pallas

    assert head_blocks(32, 256, 128) == (1, 8)
    one = SingleDeviceSharding(v5e.devices[0])

    def s(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one)

    compiled = jax.jit(ssd_step_pallas, donate_argnums=0).lower(
        s((6, B + 1, 256, 4096)), s((), jnp.int32), s((B,), jnp.int32),
        s((B,), bool), s((B,), bool), s((B, 2, 256)), s((B, 2, 256)),
        s((B, 32, 128)), s((B, 32))).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 6 * (B + 1) * 256 * 4096 * 4, mem


def test_falcon_h1_file_compiles_and_copies_no_carried_state(v5e, capsys):
    """`scripts/step_hlo_copies.py` on the Falcon-H1 configuration file (PR
    54), at PUBLISHED widths and a 64-token ragged step: both step programs
    compile for the chip, and neither holds a `copy` of the mixers' state
    (`f32[6,65,256,4096]`, whole or a layer's — ONE row of it, 4 MiB, is
    re-laid a trip of the ragged step's (row, window) loop, where
    `gated_delta._row_major` pins it: by design), of the convolution
    window (`bf16[6,3,64,5120]`) or of a pool, nor re-lays a weight stack —
    the mixer's in-projection is held as `ssm_in` (9216 lanes: 72 tiles) and
    `ssm_dt` (32): whole, its 9248 lanes are no whole number of tiles, the
    chip's default order for such a shape is contracted-minor, and the
    decode scan copied all 568 MB of it a launch (it still re-lays `ssm_dt`,
    2 MB, once a launch of eight passes)."""
    name = "falcon-h1-34b-d6"
    programs, re_laid = _step_hlo_copies(capsys, name, "--tokens", "64",
                                         "--min-mb", "0.25")
    assert [p["program"] for p in programs] \
        == ["mq_ragged_step", "mq_decode_scan"]
    assert re_laid <= {"ssm_dt"}, [p["weight_copies"] for p in programs]
    cfg, mc = _file_model(name)
    slots = int(cfg["server_flags"][cfg["server_flags"].index("--max-slots")
                                    + 1])
    pages = int(cfg["server_flags"][cfg["server_flags"].index("--num-pages")
                                    + 1])
    held = jax.eval_shape(lambda: llama.alloc_slot_state(mc, slots))
    assert held.ssm.shape == (6, slots + 1, 256, 4096)
    assert held.conv.shape == (6, 3, slots, 5120)
    pool = (6, pages * 32, 512)
    carried = {tuple(shape[i:]) for shape in (held.ssm.shape,
                                              held.conv.shape, pool)
               for i in range(2)}
    for p in programs:
        copies = [m for m in p["moves"] if m["moves"] == "copy"
                  and tuple(d for d in m["dims"] if d != 1) in carried]
        assert not copies, (p["program"], copies)
    shapes = jax.eval_shape(
        lambda: llama.init_params(mc, jax.random.PRNGKey(0)))
    assert set(llama.weight_formats(mc, shapes)) == {"wq", "wk"}
    assert shapes["layers"]["ssm_in"].shape == (6, 5120, 9216)
    assert shapes["layers"]["ssm_dt"].shape == (6, 5120, 32)


def test_falcon_h1_file_compiles_at_its_rehearse_sizes_too(v5e, capsys):
    """...and at the file's `rehearse` sizes both programs, the step kernel
    among them, compile too (a case of its own since PR 57: 70 s as one)."""
    programs, _ = _step_hlo_copies(capsys, "falcon-h1-34b-d6", "--rehearse",
                                   "--min-mb", "0")
    assert [p["program"] for p in programs] \
        == ["mq_ragged_step", "mq_decode_scan"]


def test_phi4_flash_file_compiles_whole_and_carries_its_state_in_place(
        v5e, capsys):
    """The Phi-4-mini-flash configuration file (PR 56) at PUBLISHED widths,
    all 32 layers, a 64-token ragged step: both step programs compile for
    the chip — the selective scan's step kernel (a [16, 5120] float32 row a
    program), the cross layers' launch of the decode kernel inside the
    RAGGED step, the window walks at 512 over a 1056-row ring, attention at
    40 / 10 heads of 128 lanes — with the memory a deployment has: the
    arguments (weights, ONE pool layer, eight rings, nine scan states and
    conv windows) under 12.5 GB, ALL of the carried state aliased to the
    results, temporaries under half a GB. Neither program holds a `copy` of
    a carried array, whole or a layer's, and of the weight stacks only
    `s6_x` is re-laid (192 lanes are no whole number of tiles: 18 MB once a
    launch of eight passes; `wq` and `xwq` are held rank-minor, `wk`
    row-major: llama.HYBRID_MINOR). (The file's `rehearse` sizes run on the
    CPU in benchmarks/tests/test_phi4_flash_cell.py.)"""
    import math
    import sys

    name = "phi-4-mini-flash-reasoning"
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "scripts"))
    import step_hlo_copies as shc
    from benchmarks import serve
    from ollamamq_tpu import cli

    cfg, mc = _file_model(name)
    args = cli.build_parser().parse_args(
        ["--models", cfg["name"]] + serve.server_flags(cfg, False))
    slots, ps = args.max_slots, args.page_size
    held = jax.eval_shape(lambda: llama.alloc_slot_state(
        mc, slots, ring_rows=mc.ring_rows(args.max_batch_tokens, ps)))
    assert held.scan.shape == (9, slots + 1, 16, 5120)
    assert held.conv.shape == (9, 3, slots, 5120)
    assert held.ring.k.shape == (8, (slots + 1) * 1056, 1280)
    pool = (1, args.num_pages * ps, 1280)
    carried = {tuple(shape[i:]) for shape in (
        held.scan.shape, held.conv.shape, held.ring.k.shape, pool)
        for i in range(2)}
    state_bytes = 2 * math.prod(pool) * 2 + sum(
        math.prod(a.shape) * a.dtype.itemsize
        for a in jax.tree_util.tree_leaves(held))
    assert 4.5e9 < state_bytes < 4.56e9
    lowered, params = shc.step_programs(mc, args, v5e, 64)
    assert list(lowered) == ["mq_ragged_step", "mq_decode_scan"]
    assert set(llama.weight_formats(mc, params)) == {"wq", "xwq"}
    for prog, low in lowered.items():
        compiled = low.compile()
        mem = compiled.memory_analysis()
        assert mem.argument_size_in_bytes <= 12.5e9, prog
        assert mem.alias_size_in_bytes >= state_bytes, prog
        assert mem.temp_size_in_bytes < 0.5e9, prog
        found = shc.moves(compiled.as_text(), 8 << 20)
        copies = [m for m in found if m["moves"] == "copy"
                  and tuple(d for d in m["dims"] if d != 1) in carried]
        assert not copies, (prog, copies)
        re_laid = {n for c in shc.weight_copies(found, params)
                   for n in c["stacks"]}
        assert re_laid <= {"s6_x"}, (prog, re_laid)
