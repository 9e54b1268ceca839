"""Ask the chip's compiler, without the chip: AOT-compile the attention
kernels of the serving path — and the WHOLE layer loop of the two step
forwards around them — for a DESCRIBED TPU v5e at llama3.2:1b widths and
the CLI's default pool shape (this file: the GQA kernels alone at every
published head shape, the layer loop, the expert and window stacks, `wq` /
`wk`; the models with per-slot state, their files, the state-space mixers
and latent attention: test_chip_compile_{state,files,ssm,latent}.py, a
family a file, each a few minutes of one worker). Interpret-mode tests
cannot see what Mosaic refuses (slices off the tiling, kernels GSPMD cannot
partition), nor what XLA does with the KV pool (a second copy, a layer
re-laid out for the kernel); this file can, at no chip time. Nothing runs —
a compile that passes is not a chip run. Skipped where the v5e topology
cannot be described."""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding

from chip_compile import (B, HD, HK, LAYERS, LOOP_CFG, MP, NP, PS, T,
                          _compile_at, _compile_decode, _compile_ragged,
                          _file_model, _shapes, _step_hlo_copies, step_program)
from ollamamq_tpu.config import ModelConfig
from ollamamq_tpu.engine.engine import select_attn_impl
from ollamamq_tpu.models import llama
from ollamamq_tpu.ops.pallas import paged_attention, ragged_attention
from ollamamq_tpu.parallel.mesh import make_mesh
from ollamamq_tpu.parallel.sharding import param_partition_specs


@pytest.mark.parametrize("compile_fn", [_compile_ragged, _compile_decode],
                         ids=["ragged", "decode"])
def test_bf16_kernel_compiles_for_one_v5e_chip(v5e, compile_fn):
    one = SingleDeviceSharding(v5e.devices[0])
    compiled = compile_fn(_shapes(lambda spec: one))
    assert "tpu_custom_call" in compiled.as_text()  # the kernel is in it


def test_ragged_kernel_compiles_under_a_4way_tensor_shard_map(v5e):
    """tp > 1: GSPMD cannot partition a Mosaic kernel ("wrap the call in
    a shard_map"), so ops/attention.py runs it per tensor shard."""
    mesh = make_mesh(tp=4, devices=v5e.devices)
    compiled = _compile_ragged(
        _shapes(lambda spec: NamedSharding(mesh, spec)), mesh=mesh)
    assert "tpu_custom_call" in compiled.as_text()


# The benchmark's published head shapes (H, Hk, hd). At head_dim 128 a kv
# head is one lane tile, sliced out of the K/V block at a DYNAMIC,
# 128-aligned lane offset by the lane-tile loop of
# ops/pallas/kv_contract.py; at 64 (LFM2) two heads share a tile. Both that
# loop and the ragged kernel's successor walk are loops in the program, so
# this is where Mosaic's verdict on them is asked. One chip sees the first,
# the last three whole (Olmo-Hybrid's 30 heads of group 1 among them: 30
# lane tiles, the widest block a kernel buffers) and Qwen3-8B as its tp=4
# cell shards it; the 4-way shard_map cuts each by four (Qwen2.5 to ONE kv
# head of group 7, Qwen3-8B to (8, 2, 128), LFM2 to one tile of two heads,
# OLMoE to four heads of group 1: mxu in the ragged kernel, vpu in the
# decode kernel; 30 heads do not divide by four, and its cell has one chip).
@pytest.mark.parametrize("compile_fn", [_compile_ragged, _compile_decode],
                         ids=["ragged", "decode"])
@pytest.mark.parametrize("tp,heads", [
    (1, (28, 4, 128)), (1, (8, 2, 128)), (1, (16, 16, 128)),
    (1, (32, 8, 64)), (1, (30, 30, 128)), (1, (16, 2, 256)),
    (4, (28, 4, 128)), (4, (32, 8, 128)), (4, (16, 16, 128)),
    (4, (32, 8, 64))],
    ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple) else f"tp{v}")
def test_published_head_shapes_compile_for_v5e(v5e, compile_fn, tp, heads):
    _compile_at(v5e, compile_fn, tp, heads, T)


# The ragged kernel on a rung that holds whole stretches (PR 48: 128
# tokens and more; the ladder's largest here): a program of 64 tokens, its
# tiles merged along M for the tall trip (`q_ref[:, t]` and the state's
# `[subs, Mp, .]` reshaped to `[subs * Mp, .]`, free where Mp is a multiple
# of 16), a tile's state at a DYNAMIC index of the scratch in the other
# body, scores of `[512, 128]` float32 in VMEM — at the
# widest block (30 lane tiles) and the widest tile (256 lanes) a cell has,
# one chip and under the 4-way shard_map; and (PR 61) the tall trip's lane
# tiles as straight-line code at the most there are of them, K-EXAONE's
# eight, and at Falcon-H1's four.
@pytest.mark.parametrize("tp,heads", [
    (1, (28, 4, 128)), (1, (8, 2, 128)), (1, (16, 16, 128)),
    (1, (32, 8, 64)), (1, (30, 30, 128)), (1, (16, 2, 256)),
    (4, (28, 4, 128)), (4, (32, 8, 128)),
    (1, (64, 8, 128)), (1, (20, 4, 128))],
    ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple) else f"tp{v}")
def test_tall_rung_compiles_for_v5e(v5e, tp, heads):
    _compile_at(v5e, _compile_ragged, tp, heads, 512)


@pytest.mark.parametrize("compile_fn", [_compile_ragged, _compile_decode],
                         ids=["ragged", "decode"])
def test_int8_kv_kernel_compiles_or_is_selected_away(v5e, compile_fn):
    """Int8 pages: either the kernel compiles for the chip, or
    construction must never select it (nothing is discovered at the
    first dispatch). Today Mosaic refuses the [page_size, Hk] f32
    scale-row DMA — slice not aligned to the 128-lane tiling — and
    select_attn_impl answers jnp (kernel repair: ROADMAP A1)."""
    one = SingleDeviceSharding(v5e.devices[0])
    try:
        compile_fn(_shapes(lambda spec: one, kv_dtype=jnp.int8))
    except Exception as e:  # noqa: BLE001 — whatever the compiler raises
        assert "aligned to tiling" in str(e), e
        assert select_attn_impl("tpu", "int8")[0] == "jnp"


# ---------------------------------------------------------------------------
# The whole layer loop: the KV pool is updated in place.
# ---------------------------------------------------------------------------


def _lower_ragged(params, shapes, mesh):
    _, _, pool, pt, per_tok, per_seq = shapes

    def step(params, tok, ts, tp, ws, out_idx, kc, vc, pt, qs, ql, kl):
        return llama.forward_ragged(
            params, LOOP_CFG, tok, ts, tp, ws, out_idx, kc, vc, pt, qs, ql,
            kl, PS, attn_impl="pallas", mesh=mesh)

    return jax.jit(step, donate_argnums=(6, 7)).lower(
        params, per_tok, per_tok, per_tok, per_tok, per_seq, pool, pool, pt,
        per_seq, per_seq, per_seq)


def _lower_decode(params, shapes, mesh):
    _, _, pool, pt, _, per_seq = shapes

    def step(params, tok, pos, kc, vc, pt):
        return llama.forward_decode(params, LOOP_CFG, tok, pos, kc, vc, pt,
                                    PS, attn_impl="pallas", mesh=mesh)

    return jax.jit(step, donate_argnums=(3, 4)).lower(
        params, per_seq, per_seq, pool, pool, pt)


@pytest.mark.parametrize("tp", [1, 4], ids=["one_chip", "tensor4"])
@pytest.mark.parametrize("lower", [_lower_ragged, _lower_decode],
                         ids=["forward_ragged", "forward_decode"])
def test_layer_loop_updates_the_kv_pool_in_place(v5e, lower, tp):
    """forward_ragged / forward_decode with the Pallas kernels, pools
    donated as the jit sites donate them: the compiled program aliases
    both pools to its outputs and ALL its temporaries together are
    smaller than one layer's K pool on a device — so no second pool, no
    layer sliced out, re-laid out for the kernel or written back. (With
    the pool as a scan's xs/ys the one-chip ragged step held 2 pools +
    several layer slices of temporaries.)"""
    if tp == 1:
        mesh, one = None, SingleDeviceSharding(v5e.devices[0])
        sharding_of = lambda spec: one  # noqa: E731
    else:
        mesh = make_mesh(tp=tp, devices=v5e.devices)
        sharding_of = lambda spec: NamedSharding(mesh, spec)  # noqa: E731
    shapes = jax.eval_shape(
        lambda: llama.init_params(LOOP_CFG, jax.random.PRNGKey(0)))
    params = jax.tree_util.tree_map(
        lambda a, spec: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                             sharding=sharding_of(spec)),
        shapes, param_partition_specs(shapes))
    compiled = lower(params, _shapes(sharding_of), mesh).compile()
    assert "tpu_custom_call" in compiled.as_text()  # the kernel is in it
    mem = compiled.memory_analysis()
    layer_pool = NP * PS * HK * HD * 2 // tp  # one layer's K pool, a device
    assert mem.alias_size_in_bytes >= 2 * LAYERS * layer_pool, mem
    assert mem.temp_size_in_bytes < layer_pool, mem


# ---------------------------------------------------------------------------
# OLMoE widths: MHA (group 1, 16 kv heads, 2048 lanes) and 64 experts.
# ---------------------------------------------------------------------------

# olmoe:1b-7b's layer (config.py) over two layers and a small vocabulary.
OLMOE_CFG = ModelConfig(
    name="chip-compile-olmoe-widths", vocab_size=2048, hidden_size=2048,
    intermediate_size=1024, num_layers=2, num_heads=16, num_kv_heads=16,
    head_dim=128, max_seq_len=MP * PS, rope_theta=1e4, rms_norm_eps=1e-5,
    qk_norm="full", num_experts=64, num_experts_per_tok=8)


@pytest.mark.parametrize("which", ["forward_ragged", "forward_decode"])
def test_olmoe_width_step_forward_compiles_for_one_v5e_chip(v5e, which):
    """What Mosaic had never been asked before PR 27: both attention kernels
    at group 1 with 2048-lane page rows, and the grouped expert matmul
    (megablox `gmm`) reading the WHOLE [L, E, ...] stacks by layer index —
    so no layer's expert weights (805 MB here) are copied out of the stack:
    the program's temporaries stay under one expert matrix's size."""
    one = SingleDeviceSharding(v5e.devices[0])

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one)

    cfg = OLMOE_CFG
    shapes = jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))
    params = jax.tree_util.tree_map(lambda a: s(a.shape, a.dtype), shapes)
    pool = s((cfg.num_layers, NP * PS, cfg.kv_dim), jnp.bfloat16)
    pt, per_tok, per_seq = (s((B, MP), jnp.int32), s((T,), jnp.int32),
                            s((B,), jnp.int32))
    if which == "forward_ragged":
        def step(params, tok, ts, tp, ws, out_idx, kc, vc, pt, qs, ql, kl):
            return llama.forward_ragged(
                params, cfg, tok, ts, tp, ws, out_idx, kc, vc, pt, qs, ql,
                kl, PS, attn_impl="pallas", moe_load=True)

        lowered = jax.jit(step, donate_argnums=(6, 7)).lower(
            params, per_tok, per_tok, per_tok, per_tok, per_seq, pool, pool,
            pt, per_seq, per_seq, per_seq)
    else:
        def step(params, tok, pos, kc, vc, pt, active):
            return llama.forward_decode(
                params, cfg, tok, pos, kc, vc, pt, PS, attn_impl="pallas",
                active=active, moe_load=True)

        lowered = jax.jit(step, donate_argnums=(3, 4)).lower(
            params, per_seq, per_seq, pool, pool, pt, per_seq)
    compiled = lowered.compile()
    text = compiled.as_text()
    assert len(re.findall(r"%gmm[.\d]* = ", text)) == 3, \
        "three grouped matmuls a layer, in the one traced layer body"
    assert "ragged-dot" not in text
    assert text.count("tpu_custom_call") >= 4  # + the attention kernel
    mem = compiled.memory_analysis()
    one_matrix = 64 * 2048 * 1024 * 2
    assert mem.temp_size_in_bytes < one_matrix // 4, mem


# (hidden, expert width, experts held, the tiles moe.gmm_tiling gives gate /
# up and down) where the fixed (2048, 1024) left a masked contraction tile:
# Kimi-Linear's 2304 whole on either side — a [2304, 1024] gate block and a
# [1024, 2304] down block, 11.1 and 11.75 MiB of blocks, the most any cell
# asks of Mosaic's scoped 16 — and openPangu's 7680 as four tiles of 1920.
@pytest.mark.parametrize("d,f,experts,tiles", [
    (2304, 1024, 64, ((2304, 1024), (1024, 2304))),
    (7680, 2048, 16, ((1920, 1024), (2048, 1024)))],
    ids=["kimi_linear", "openpangu"])
def test_expert_ffn_compiles_with_tiles_that_divide(v5e, d, f, experts,
                                                    tiles):
    """`_expert_ffn` on the whole [L, E, ...] stacks, a 512-token step's
    4096 rows, with the tiles chosen from (k, n) (PR 68): Mosaic takes the
    blocks the VMEM arithmetic admitted, there are still exactly three `gmm`
    a layer, and no stack is copied."""
    from ollamamq_tpu.models import moe

    one = SingleDeviceSharding(v5e.devices[0])
    L, m = 2, 4096
    assert (moe.gmm_tiling(m, d, f)[1:], moe.gmm_tiling(m, f, d)[1:]) == tiles

    def s(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one)

    compiled = jax.jit(
        lambda xs, sizes, wg, wu, wd, layer: moe._expert_ffn(
            "pallas", xs, sizes, wg, wu, wd, layer)
    ).lower(s((m, d)), s((experts,), jnp.int32), s((L, experts, d, f)),
            s((L, experts, d, f)), s((L, experts, f, d)),
            s((), jnp.int32)).compile()
    text = compiled.as_text()
    assert len(re.findall(r"%gmm[.\d]* = ", text)) == 3
    assert "ragged-dot" not in text
    stack = L * experts * d * f * 2
    assert compiled.memory_analysis().temp_size_in_bytes < stack // 4


# ---------------------------------------------------------------------------
# Window and full attention in one stack: the step programs at K-EXAONE's
# widths (their interpret-mode twins: tests/test_window_cache.py).
# ---------------------------------------------------------------------------

# K-EXAONE-236B-A23B's layers over the cell's five (dense + L L G L), a
# small vocabulary and 4 of 128 experts held: 64 / 8 heads of 128 in both
# kernels, rings of 672 rows a slot.
KX_WIDTHS = ModelConfig(
    name="chip-compile-k-exaone-widths", vocab_size=2048, hidden_size=6144,
    intermediate_size=18432, num_layers=5, num_heads=64, num_kv_heads=8,
    head_dim=128, max_seq_len=256 * 32, rope_theta=1e6, rms_norm_eps=1e-5,
    qk_norm="head", sliding_window=128, sliding_window_pattern="LLLG",
    layer_types=("sliding_attention",) * 3 + ("full_attention",
                                              "sliding_attention"),
    rope_layer_types=("sliding_attention",), num_experts=4,
    router_experts=128, num_experts_per_tok=8, num_shared_experts=1,
    moe_intermediate_size=2048, first_k_dense_replace=1,
    scoring_func="sigmoid", use_expert_bias=True, norm_topk_prob=True,
    norm_topk_eps=1e-20, routed_scaling_factor=2.5)


@pytest.mark.parametrize("which", ["mq_ragged_step", "mq_decode_scan"])
def test_k_exaone_width_step_programs_carry_pool_and_rings_in_place(
        v5e, which):
    """Window and full attention in one stack (PR 50), at K-EXAONE's widths:
    both kernels at 8 kv heads of 128 under group 8 compile for the chip
    with a window — under names of their own, three call sites for the four
    window layers (two of them one scan's) beside ONE of the full layer's
    name; the pool — for the ONE full layer — the rings (4 layers x 65 slots
    x 672 rows), the penalty ring and the id carry all come back aliased."""
    _, compiled, _, carried = step_program(v5e, which, KX_WIDTHS)
    text = compiled.as_text()
    swa = {"mq_ragged_step": ragged_attention.WINDOW_NAME,
           "mq_decode_scan": paged_attention.WINDOW_NAME}[which]
    full = {"mq_ragged_step": "ragged_paged_attention_pallas",
            "mq_decode_scan": "paged_decode_attention_pallas"}[which]
    assert len(re.findall(rf"%{swa}[.\d]* = ", text)) == 3
    assert len(re.findall(rf"%{full}[.\d]* = ", text)) == 1
    rows = KX_WIDTHS.ring_rows(64, PS)
    rings_b = 2 * 4 * (B + 1) * rows * 1024 * 2
    pool_b = 2 * 1 * NP * PS * 1024 * 2
    assert carried >= rings_b + pool_b
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= carried, (mem, carried)


@pytest.mark.parametrize("name,held", [
    ("qwen2.5-7b-d14", False), ("qwen2.5-7b-d14", True),
    ("olmoe-1b-7b-d10", True)],
    ids=["split_row_major", "split_as_served", "full_norm"])
def test_no_step_program_re_lays_wq_or_wk(v5e, capsys, name, held):
    """The same check for `_qkv`'s projections (PR 51), at PUBLISHED widths
    (both of a file's programs compile in ~25 s; K-EXAONE's and LFM2's
    `rehearse` sizes list no program here: PERF.md section 7). Qwen2.5 splits q
    and k into heads at once: with every weight row-major its ragged step
    re-lays a layer of `wq` and of `wk` a layer and its decode scan both
    stacks whole; as a runtime holds them — contracted dimension minor — no
    program copies a weight. OLMoE norms the flat projection first: the rule
    names nothing for it (as served IS row-major) and its programs re-lay
    neither stack."""
    programs, re_laid = _step_hlo_copies(
        capsys, name, "--min-mb", "1",
        *(() if held else ("--default-layouts",)))
    assert [p["program"] for p in programs] \
        == ["mq_ragged_step", "mq_decode_scan"]
    qk = set(llama.SPLIT_TO_HEADS_MINOR)
    if held:
        assert re_laid & qk == set(), [p["weight_copies"] for p in programs]
    else:
        for p in programs:  # either program, each stack
            assert qk <= {n for c in p["weight_copies"]
                          for n in c["stacks"]}, p["weight_copies"]
    # and what the rule names for the file's model
    _, mc = _file_model(name)
    shapes = jax.eval_shape(
        lambda: llama.init_params(mc, jax.random.PRNGKey(0)))
    assert set(llama.weight_formats(mc, shapes)) \
        == (qk if name.startswith("qwen") else set())
