"""Ask the chip's compiler, without the chip (test_chip_compile.py), the
configuration file whose model selects BLOCKS of its K/V pool: MiniCPM-SALA's
block-sparse attention beside lightning linear attention, at its published
widths.
"""

import math
import os
import sys

import jax

from chip_compile import _file_model
from ollamamq_tpu.models import llama


def test_minicpm_sala_file_compiles_whole_and_carries_its_state_in_place(v5e):
    """The MiniCPM-SALA configuration file (PR 60) at PUBLISHED widths, 16
    layers, a 64-token ragged step: both step programs compile for the chip
    — the select kernel ([16, 128] against 1152 pooled keys a program), the
    decode kernel over a 256-page walk table a (row, kv head) inside the
    RAGGED step too, the lightning layers' step kernel at [128, 4096] float32
    a row, the span path's masked softmax — with the memory a deployment has:
    the arguments (weights, four pool layers of K and of V, the pooled-key
    pool, twelve lightning states) under 12.3 GB, ALL of the carried state
    aliased to the results, temporaries under 0.6 GB. Neither program slices
    a LAYER out of a pool (the gathers name (layer, row) at once: a sliced
    layer was a 208 MB copy a sparse layer a step), and of the weight stacks
    only `ltn_wv` is re-laid, by the decode scan, once a launch of eight
    passes (`wq`, `wk`, `ltn_wq`, `ltn_wk` are held rank-minor:
    llama.LIGHTNING_MINOR). (The file's `rehearse` sizes run on the CPU in
    benchmarks/tests/test_minicpm_sala_cell.py.)"""
    name = "minicpm-sala-d16"
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "scripts"))
    import step_hlo_copies as shc
    from benchmarks import serve
    from ollamamq_tpu import cli

    cfg, mc = _file_model(name)
    args = cli.build_parser().parse_args(
        ["--models", cfg["name"]] + serve.server_flags(cfg, False))
    slots, ps, pages = args.max_slots, args.page_size, args.num_pages
    held = jax.eval_shape(lambda: llama.alloc_slot_state(
        mc, slots, pooled_rows=mc.pooled_rows(pages, ps)))
    assert held.rule.shape == (12, slots + 1, 128, 4096)
    assert held.pooled.shape == (4, 2 * pages, 256)
    assert held.conv is None and held.ring is None
    pool = (4, pages * ps, 256)
    state_bytes = 2 * math.prod(pool) * 2 + sum(
        math.prod(a.shape) * a.dtype.itemsize
        for a in jax.tree_util.tree_leaves(held))
    assert 2.13e9 < state_bytes < 2.15e9
    lowered, params = shc.step_programs(mc, args, v5e, 64)
    assert list(lowered) == ["mq_ragged_step", "mq_decode_scan"]
    assert set(llama.weight_formats(mc, params)) \
        == {"wq", "wk", "ltn_wq", "ltn_wk"}
    kernels = {"bsa_select_pallas", "bsa_decode_attention_pallas",
               "ssd_step_pallas"}
    for prog, low in lowered.items():
        compiled = low.compile()
        mem = compiled.memory_analysis()
        assert mem.argument_size_in_bytes <= 12.3e9, prog
        assert mem.alias_size_in_bytes >= state_bytes, prog
        assert mem.temp_size_in_bytes < 0.6e9, prog
        text = compiled.as_text()
        want = kernels | ({"ragged_paged_attention_pallas"}
                          if prog == "mq_ragged_step" else set())
        assert all(k in text for k in want), prog
        found = shc.moves(text, 8 << 20)
        layer = [m for m in found
                 if tuple(d for d in m["dims"] if d != 1) in (
                     pool[1:], held.pooled.shape[1:], held.rule.shape[1:])]
        assert not layer, (prog, layer)
        re_laid = {n for c in shc.weight_copies(found, params)
                   for n in c["stacks"]}
        # (the five lightning stacks share a shape: the copy is `ltn_wv`'s)
        assert not re_laid if prog == "mq_ragged_step" \
            else re_laid <= {"ltn_wq", "ltn_wk", "ltn_wv", "ltn_wz",
                             "ltn_wo"}, (prog, re_laid)
