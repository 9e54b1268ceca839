"""Ask the chip's compiler, without the chip: AOT-compile the attention
kernels of the serving path — and the WHOLE layer loop of the two step
forwards around them — for a DESCRIBED TPU v5e at llama3.2:1b widths and
the CLI's default pool shape. Interpret-mode tests cannot see what Mosaic
refuses (slices off the tiling, kernels GSPMD cannot partition), nor what
XLA does with the KV pool (a second copy, a layer re-laid out for the
kernel); this file can, at no chip time. Nothing runs — a compile that
passes is not a chip run. Skipped where the v5e topology cannot be
described."""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from ollamamq_tpu.config import LINEAR, ModelConfig
from ollamamq_tpu.engine.engine import select_attn_impl
from ollamamq_tpu.models import llama
from ollamamq_tpu.ops.attention import (paged_decode_attention_any,
                                        ragged_attention_any)
from ollamamq_tpu.ops.quant import QuantKV
from ollamamq_tpu.parallel.mesh import make_mesh
from ollamamq_tpu.parallel.sharding import (kv_cache_spec,
                                            param_partition_specs)

# llama3.2:1b heads (config.py) under the CLI defaults: 64 slots, 256
# pages a sequence, a 1024-page pool of 32-token pages.
H, HK, HD = 32, 8, 64
B, MP, PS, NP = 64, 256, 32, 1024
T = 64
LAYERS = 4  # a small stack: the kernels read layer 2 of it by index


@pytest.fixture(scope="module")
def v5e():
    """The described 2x2 v5e host, with the persistent compile cache off:
    a compile for a described device is written to the cache but cannot
    be read back without a chip (the next run would warn and recompile)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu, no description
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shapes(sharding_of, kv_dtype=jnp.bfloat16, heads=(H, HK, HD), T=T):
    """(q_ragged, q_decode, pool, page_table, [T] meta, [B] meta) as
    ShapeDtypeStructs; `sharding_of(spec)` places each."""
    def s(shape, dt, spec=P()):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding_of(spec))

    h, hk, hd = heads
    heads = P(None, "tensor", None)
    pool = s((LAYERS, NP * PS, hk * hd), kv_dtype, kv_cache_spec())
    if kv_dtype == jnp.int8:
        pool = QuantKV(pool, s((LAYERS, NP * PS, hk), jnp.float32,
                               kv_cache_spec()))
    return (s((T, h, hd), jnp.bfloat16, heads),
            s((B, h, hd), jnp.bfloat16, heads), pool,
            s((B, MP), jnp.int32), s((T,), jnp.int32), s((B,), jnp.int32))


def _compile_ragged(shapes, mesh=None):
    q, _, pool, pt, per_tok, per_seq = shapes
    return jax.jit(
        lambda q, kc, vc, pt, ts, tp, kl, qs, ql: ragged_attention_any(
            "pallas", q, kc, vc, 2, pt, ts, tp, kl, qs, ql, PS, mesh=mesh)
    ).lower(q, pool, pool, pt, per_tok, per_tok, per_seq, per_seq,
            per_seq).compile()


def _compile_decode(shapes, mesh=None):
    _, q, pool, pt, _, per_seq = shapes
    return jax.jit(
        lambda q, kc, vc, pt, sl: paged_decode_attention_any(
            "pallas", q, kc, vc, 2, pt, sl, PS, mesh=mesh)
    ).lower(q, pool, pool, pt, per_seq).compile()


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


def _compile_at(v5e, compile_fn, tp, heads, tokens):
    if tp == 1:
        mesh, one = None, SingleDeviceSharding(v5e.devices[0])
        sharding_of = lambda spec: one  # noqa: E731
    else:
        mesh = make_mesh(tp=tp, devices=v5e.devices)
        sharding_of = lambda spec: NamedSharding(mesh, spec)  # noqa: E731
    compiled = compile_fn(_shapes(sharding_of, heads=heads, T=tokens),
                          mesh=mesh)
    assert "tpu_custom_call" in compiled.as_text()


# The ragged kernel on a rung that holds whole stretches (PR 48: 128
# tokens and more; the ladder's largest here): a program of 64 tokens, its
# tiles merged along M for the tall trip (`q_ref[:, t]` and the state's
# `[subs, Mp, .]` reshaped to `[subs * Mp, .]`, free where Mp is a multiple
# of 16), a tile's state at a DYNAMIC index of the scratch in the other
# body, scores of `[512, 128]` float32 and P's three terms in VMEM — at the
# widest block (30 lane tiles) and the widest tile (256 lanes) a cell has,
# one chip and under the 4-way shard_map.
@pytest.mark.parametrize("tp,heads", [
    (1, (28, 4, 128)), (1, (8, 2, 128)), (1, (16, 16, 128)),
    (1, (32, 8, 64)), (1, (30, 30, 128)), (1, (16, 2, 256)),
    (4, (28, 4, 128)), (4, (32, 8, 128))],
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
    select_attn_impl answers jnp (kernel repair: ROADMAP A5)."""
    one = SingleDeviceSharding(v5e.devices[0])
    try:
        compile_fn(_shapes(lambda spec: one, kv_dtype=jnp.int8))
    except Exception as e:  # noqa: BLE001 — whatever the compiler raises
        assert "aligned to tiling" in str(e), e
        assert select_attn_impl("tpu", "int8")[0] == "jnp"


# ---------------------------------------------------------------------------
# The whole layer loop: the KV pool is updated in place.
# ---------------------------------------------------------------------------

# llama3.2:1b widths over a small stack and a small vocabulary (the
# logits are the one temporary that would outgrow a layer's pool here,
# and they are not what this test is about).
LOOP_CFG = ModelConfig(
    name="chip-compile-1b-widths", vocab_size=2048, hidden_size=2048,
    intermediate_size=8192, num_layers=LAYERS, num_heads=H, num_kv_heads=HK,
    head_dim=HD, max_seq_len=MP * PS, rope_theta=5e5, rms_norm_eps=1e-5,
    tie_embeddings=True)


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


def _lower_step_program(v5e, which, monkeypatch, cfg=LOOP_CFG):
    """One of the pipelined loop's two programs as the engine jits it,
    lowered for one described chip: (lowered, the packed input's words,
    the bytes of the state it carries)."""
    from types import SimpleNamespace

    from ollamamq_tpu.config import ATTENTION
    from ollamamq_tpu.engine import engine as eng_mod
    from ollamamq_tpu.engine.engine import ModelRuntime

    one = SingleDeviceSharding(v5e.devices[0])

    def s(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one)

    # The jit itself, not the first-call wrapper that times the compile.
    monkeypatch.setattr(eng_mod, "_sp_note_compile",
                        lambda rt, site, key, cache, fn: cache.setdefault(
                            key, fn))
    S, W = B, 64
    rt = object.__new__(ModelRuntime)
    rt.cfg, rt.attn_impl, rt.mesh = cfg, "pallas", None
    rt.ecfg = SimpleNamespace(page_size=PS, max_slots=S,
                              max_pages_per_seq=MP, repeat_last_n=W)
    rt._prefill_jits, rt._decode_jits = {}, {}
    shapes = jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))
    params = jax.tree_util.tree_map(lambda a: s(a.shape, a.dtype), shapes)
    # K and V rows — or a latent-attention model's latent rows and index
    # keys: two pools of different widths (ModelConfig.kv_row_dims).
    pool, pool2 = (s((cfg.cache_layers, NP * PS, lanes), jnp.bfloat16)
                   for lanes in cfg.kv_row_dims)
    recent, last_ids = s((S + 1, W)), s((S,))
    # The per-slot state: None (no leaf) for a model without such layers,
    # the conv window's array, or a SlotState with the rule's state too.
    # ...or a WindowState with the window layers' K/V rings.
    conv = jax.tree_util.tree_map(
        lambda a: s(a.shape, a.dtype),
        jax.eval_shape(lambda: llama.alloc_slot_state(
            cfg, S, ring_rows=cfg.ring_rows(T, PS))))
    drafts = ()
    if which == "mq_spec_step":  # the ragged step of a --spec runtime whose
        rt.mtp = True  # proposer is the model's prediction module
        drafts = (s((S + 1,)),) * 2  # its drafts and its rows' lengths
        fn = rt._get_ragged_jit(T, 1, (True, True, True))
        words = rt._ragged_layout(T).size
    elif which == "mq_ragged_step":
        fn = rt._get_ragged_jit(T, 0, (True, True, True))
        words = rt._ragged_layout(T).size
    else:
        fn = rt._get_decode_jit(8, (True, True, True))
        words = rt._decode_layout().size
    carried = sum(a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(
        (pool, pool2, recent, last_ids, conv, drafts)))
    # The step's host inputs are ONE packed int32 array (step_pack).
    return fn.lower(params, s((words,)), pool, pool2, recent, last_ids,
                    conv, *drafts), words, carried


@pytest.mark.parametrize("which", ["mq_ragged_step", "mq_decode_scan"])
def test_step_programs_alias_pools_ring_and_id_carry(v5e, which, monkeypatch):
    """The two programs of the pipelined loop as the engine jits them
    (PR 28 added the `last_ids` carry: a step launched behind an unsettled
    one reads a row's input token from it): both pools, the penalty ring
    and the carry are donated and come back aliased — the compiled program
    holds no second copy of any."""
    lowered, _, carried = _lower_step_program(v5e, which, monkeypatch)
    S, W = B, 64
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert carried == (2 * LAYERS * NP * PS * HK * HD * 2   # both pools
                       + (S + 1) * W * 4 + S * 4)   # the ring, the carry
    assert mem.alias_size_in_bytes >= carried, (mem, carried)
    assert mem.temp_size_in_bytes < NP * PS * HK * HD * 2, mem


@pytest.mark.parametrize("cfg", [LOOP_CFG, LFM2_CFG],
                         ids=["uniform", "lfm2_widths"])
@pytest.mark.parametrize("which", ["mq_ragged_step", "mq_decode_scan"])
def test_step_programs_lower_to_the_same_text_twice(v5e, which, monkeypatch,
                                                    cfg):
    """Two runtimes built one after the other lower a step program to
    the same StableHLO text — a uniform stack's and one whose layers
    differ (a scan over a period of kinds, expert matmuls): nothing in the
    trace depends on what was built before it (a counter, an id, a cache's
    order). A change that is
    to leave the step programs alone is shown to by comparing this text,
    hashed, between its parent and itself (ROADMAP C11) — which says
    something only if the text is a function of the code."""
    first, second = (
        _lower_step_program(v5e, which, monkeypatch, cfg)[0].as_text()
        for _ in range(2))
    assert which in first and "stablehlo." in first
    assert first == second


@pytest.mark.parametrize("which", ["mq_ragged_step", "mq_decode_scan"])
def test_lfm2_width_step_programs_carry_pool_and_conv_state_in_place(
        v5e, which, monkeypatch):
    """A stack whose layers differ (PR 32), at LFM2-8B-A1B's widths: the
    attention kernels at 8 kv heads of 64 (512 lanes, group 4) and the
    grouped expert matmul at [2048, 1792] compile for the chip; the KV pool
    — for the 2 attention layers only — the conv layers' per-slot state,
    the ring and the id carry all come back aliased; no weight stack is
    copied out for a layer (the temporaries stay under a quarter of ONE
    expert layer's gate matrix, 235 MB), and the scan traces each distinct
    layer of the period once: 3 grouped matmuls for each of its 4 layers,
    one attention kernel."""
    lowered, _, carried = _lower_step_program(v5e, which, monkeypatch,
                                              LFM2_CFG)
    compiled = lowered.compile()
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
        v5e, which, monkeypatch):
    """Linear-attention layers (PR 35), at Olmo-Hybrid-7B's widths: the
    attention kernels at 30 kv heads of 128 (3840 lanes, group 1) and the
    rule's step kernel on [96, 5760] float32 rows compile for the chip; the
    KV pool — for the 2 attention layers only — the window of the linear
    layers' convolution, the rule's state (6 x 65 rows of 2.2 MB: 863 MB,
    held exactly — no lane padding — and never copied), the ring and the id
    carry all come back aliased; the temporaries stay under a TENTH of the
    rule's state (a gather of a layer's 64 rows would be a sixth)."""
    lowered, _, carried = _lower_step_program(v5e, which, monkeypatch,
                                              OLMO_HYBRID_CFG)
    compiled = lowered.compile()
    text = compiled.as_text()
    assert "gated_delta_step_pallas" in text
    assert text.count("tpu_custom_call") >= 1 + 3  # attention, 3 linear layers
    mem = compiled.memory_analysis()
    rule = 6 * (B + 1) * 96 * 30 * 192 * 4
    window = 6 * 3 * B * 11520 * 2  # a tap a plane, no trash row
    assert carried >= 2 * 2 * NP * PS * 3840 * 2 + rule + window
    assert mem.alias_size_in_bytes >= carried, (mem, carried)
    assert mem.temp_size_in_bytes < rule // 10, mem


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
        v5e, which, monkeypatch):
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
    lowered, _, carried = _lower_step_program(v5e, which, monkeypatch,
                                              FALCON_H1_CFG)
    compiled = lowered.compile()
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


# DeepSeek-V3.2's layers (config.py) over its dense layer and two expert
# layers, 16 of the router's 256 experts held, a small vocabulary: the three
# kernels of ops/pallas/mla_attention.py at 128 heads over a 640-lane latent
# pool and a 128-lane index-key pool.
DEEPSEEK_CFG = ModelConfig(
    name="chip-compile-deepseek-v32-widths", vocab_size=2048,
    hidden_size=7168, intermediate_size=18432, num_layers=3, num_heads=128,
    num_kv_heads=128, head_dim=192, max_seq_len=MP * PS, rope_theta=10000.0,
    rms_norm_eps=1e-6, q_lora_rank=1536, kv_lora_rank=512,
    qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
    index_n_heads=64, index_head_dim=128, index_topk=2048,
    rope_scaling={"type": "yarn", "factor": 40, "beta_fast": 32,
                  "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                  "original_max_position_embeddings": 4096},
    num_experts=16, router_experts=256, num_experts_per_tok=8, n_group=8,
    topk_group=4, n_shared_experts=1, moe_intermediate_size=2048,
    first_k_dense_replace=1, router_score="sigmoid", use_expert_bias=True,
    norm_topk_prob=True, norm_topk_eps=1e-20, routed_scaling_factor=2.5)


@pytest.mark.parametrize("which", ["mq_ragged_step", "mq_decode_scan"])
def test_deepseek_width_step_programs_carry_both_pools_in_place(
        v5e, which, monkeypatch):
    """Latent attention with the indexer's selection (PR 39), at
    DeepSeek-V3.2's widths: the indexer's, the selection's and the sparse
    attention's kernels compile for the chip, one launch each a traced layer
    body (the dense layer's and the expert layers'), exactly one of them
    named `...paged_attention...` a body; the latent pool [3, S, 640] and
    the index-key pool [3, S, 128] — two arrays of different widths under
    one page table — the ring and the id carry all come back aliased (no
    second copy of either pool); and the temporaries hold no [tokens,
    context] float32 score a HEAD: the one [T, C] score a token is 2 MB here
    (64 x 8192 x 4 B), all 128 heads' would be 268 MB, the bound is a quarter
    of that above what the program holds without the indexer."""
    lowered, _, carried = _lower_step_program(v5e, which, monkeypatch,
                                              DEEPSEEK_CFG)
    compiled = lowered.compile()
    text = compiled.as_text()
    for name, n in (("mla_sparse_paged_attention_pallas", 2),
                    ("dsa_index_pallas", 2), ("dsa_select_pallas", 2)):
        assert len(re.findall(r'kernel_name = "%s"' % name, text)) == n \
            or text.count(name) >= n, name
    assert len(re.findall(r"%gmm[.\d]* = ", text)) == 3
    pools = 3 * NP * PS * (640 + 128) * 2
    assert carried >= pools
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= carried, (mem, carried)
    tokens = T if which == "mq_ragged_step" else B
    per_head = tokens * MP * PS * 4
    assert mem.temp_size_in_bytes < 128 * per_head // 4 + 512 * 2 ** 20, mem


@pytest.mark.parametrize("tokens", [256, 512])
def test_the_sparse_latent_kernel_compiles_with_its_expanded_body(v5e,
                                                                  tokens):
    """The masked latent attention kernel at DeepSeek-V3.2's widths on the
    512-token rung (PR 49): its absorbed tiles AND its expanded programs —
    16 heads' keys and values of a 256-token block expanded in VMEM, the
    512 stream tokens a program's rows — are one Mosaic kernel the chip's
    compiler takes, under the file's VMEM limit, over the cell's own pool
    and table (12,384 pages of 32, 520 a sequence); a rung under WIDE holds
    the tiles alone and gives the one result."""
    from ollamamq_tpu.ops.pallas import mla_attention as ka

    one = SingleDeviceSharding(v5e.devices[0])

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one)

    bf, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    heads, lanes, rank, rows, pages = 128, 640, 512, 17, 520
    C = ka.context_lanes(pages, PS)
    lowered = jax.jit(
        lambda q, sc, thr, pool, pt, qs, ql, kl, qe, w:
        ka.mla_sparse_paged_attention_pallas(
            q, sc, thr, pool, 2, pt, qs, ql, kl, PS, rank,
            expanded=(qe, w))).lower(
        s((tokens, heads, lanes), bf), s((tokens, C), f32),
        s((tokens,), f32), s((5, 12384 * PS, lanes), bf),
        s((rows, pages), i32), s((rows,), i32), s((rows,), i32),
        s((rows,), i32), s((tokens, heads, 256), bf),
        s((heads, 256, rank), bf))
    compiled = lowered.compile()
    out = jax.tree.leaves(compiled.out_info)
    expands = ka.expands(tokens, heads, lanes, rank, 128, 128)
    assert expands == (tokens >= ka.WIDE) and len(out) == (3 if expands
                                                           else 1)
    assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"",
                          compiled.as_text())) == 1


# openPangu-Ultra-MoE's layers (config.py) over its dense layer and two
# expert layers, 16 of the router's 256 experts held, a small vocabulary, and
# the prediction module: the dense latent attention kernel at 128 heads over a
# 640-lane latent pool of 3 + 1 layers, no second pool.
OPENPANGU_CFG = ModelConfig(
    name="chip-compile-openpangu-widths", vocab_size=2048,
    hidden_size=7680, intermediate_size=18432, num_layers=3, num_heads=128,
    num_kv_heads=128, head_dim=192, max_seq_len=MP * PS,
    rope_theta=25_600_000.0, rms_norm_eps=1e-5, q_lora_rank=1536,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128, sandwich_norm=True, num_experts=16, router_experts=256,
    num_experts_per_tok=8, n_shared_experts=1, moe_intermediate_size=2048,
    first_k_dense_replace=1, router_score="sigmoid", norm_topk_prob=True,
    norm_topk_eps=1e-20, routed_scaling_factor=2.5,
    num_nextn_predict_layers=1)


def test_openpangu_width_spec_step_carries_the_pool_and_the_drafts_in_place(
        v5e, monkeypatch):
    """The `--spec` runtime's ragged step with the prediction module (PR 42),
    at openPangu-Ultra-MoE's widths: the dense latent attention kernel — the
    attention kernel with the selection's operands compiled out — compiles
    for the chip, one launch a traced layer body under the name
    `_ops.ATTENTION` counts and ONE more for the module's block under its
    own; the module's expert layer launches the grouped matmul a third time;
    the latent pool [4, S, 640] (the module's rows its last layer), the
    second pool of NO lanes, the ring, the id carry, the draft carry and the
    length carry (PR 44) all come back aliased."""
    from ollamamq_tpu.ops.pallas.mla_attention import MTP_NAME

    lowered, _, carried = _lower_step_program(
        v5e, "mq_spec_step", monkeypatch, OPENPANGU_CFG)
    compiled = lowered.compile()
    text = compiled.as_text()
    names = re.findall(r"%([\w.\-]+) = [^\n]*custom_call_target="
                       r"\"tpu_custom_call\"", text)
    trunk = [n for n in names if n.startswith(
        "mla_dense_paged_attention_pallas")]
    module = [n for n in names if n.startswith(MTP_NAME)]
    assert (len(trunk), len(module)) == (2, 1), names
    assert "paged_attention" not in MTP_NAME
    assert not any("mla_sparse" in n or "dsa_" in n for n in names)
    assert sum(n.startswith("gmm") for n in names) == 3 * 2  # layers, module
    pool = 4 * NP * PS * 640 * 2
    assert carried >= pool + (B + 1) * 4
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= carried, (mem, carried)
    # no [tokens, context] score a head leaves the kernel
    assert mem.temp_size_in_bytes < 128 * T * MP * PS * 4 // 4 \
        + 512 * 2 ** 20, mem


@pytest.mark.parametrize("cfg", [LOOP_CFG, LFM2_CFG, OLMO_HYBRID_CFG],
                         ids=["dense", "lfm2", "olmo_hybrid"])
def test_ragged_step_is_fed_one_host_array(v5e, monkeypatch, cfg):
    """One upload a step: besides `params`, the compiled ragged step has
    exactly ONE parameter that is not donated device state — the packed
    int32 buffer of its host inputs. The RNG key is made inside (no key
    parameter), so nothing else is dispatched or transferred for a step.
    The conv layers' state is one more donated argument (no leaf at all
    for a model without such layers), a linear-attention model's two."""
    lowered, words, _ = _lower_step_program(v5e, "mq_ragged_step",
                                            monkeypatch, cfg)
    lowered.compile()
    _params, *rest = lowered.args_info[0]
    rest = jax.tree_util.tree_leaves(rest)
    fed = [a for a in rest if not a.donated]
    n_state = {LOOP_CFG: 0, LFM2_CFG: 1, OLMO_HYBRID_CFG: 2}[cfg]
    assert len(rest) == 5 + n_state and len(fed) == 1, rest
    assert (fed[0].shape, fed[0].dtype) == ((words,), jnp.int32), fed


def _step_hlo_copies(capsys, name, *flags):
    """`scripts/step_hlo_copies.py` on `benchmarks/configs/<name>.json`, in
    this process and within its own time limit: the programs' lines and the
    stacks whose shape a listed weight copy has."""
    import contextlib
    import json
    import signal
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "scripts"))
    import step_hlo_copies

    @contextlib.contextmanager
    def time_limit(seconds):
        def stop(signum, frame):
            raise TimeoutError(f"no result within {seconds} s")
        was = signal.signal(signal.SIGALRM, stop)
        signal.alarm(seconds)
        try:
            yield
        finally:
            signal.alarm(0)
            signal.signal(signal.SIGALRM, was)

    config = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                          "configs", name + ".json")
    with time_limit(240):
        assert step_hlo_copies.main([config, *flags]) == 0
    lines = [json.loads(line) for line in
             capsys.readouterr().out.splitlines() if line.startswith("{")]
    programs, last = lines[:-1], lines[-1]
    assert last["programs"] == len(programs)
    re_laid = {name for p in programs for c in p["weight_copies"]
               for name in c["stacks"]}
    return programs, re_laid


def _file_model(name):
    """(`benchmarks/configs/<name>.json` as a dict, its ModelConfig at the
    published widths); `benchmarks` is on the path once `_step_hlo_copies`
    has imported the script."""
    import json

    from benchmarks import serve

    with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                           "configs", name + ".json")) as f:
        cfg = json.load(f)
    return cfg, serve.model_config(cfg, False)


@pytest.mark.parametrize("held", [False, True],
                         ids=["row_major", "as_served"])
def test_no_step_program_re_lays_a_latent_stack(v5e, capsys, held):
    """`scripts/step_hlo_copies.py` on openPangu's configuration file at its
    `rehearse` sizes (PR 45): with every weight row-major the chip's compiler
    puts a `copy` of a layer of `mla_wuq` and of `mla_wukv` into the `--spec`
    step (the re-layout that was 2.0 ms of a 15 ms pass at the published
    widths); with the two stacks in the formats `llama.weight_formats` names
    — as a runtime holds them — it puts none. (At these sizes the toy expert
    stacks' 64 lanes get a copy of their own into the grouped matmul: the
    check is of the stacks the rule names.) Within its own time limit: two
    compiles of some ten seconds."""
    programs, re_laid = _step_hlo_copies(
        capsys, "openpangu-ultra-moe-ep16-d5", "--rehearse", "--min-mb", "0",
        *(() if held else ("--default-layouts",)))
    assert [p["program"] for p in programs] == ["mq_ragged_step"]
    latent = set(llama.CONTRACTED_MINOR)
    assert (re_laid & latent == set()) if held else (latent <= re_laid), \
        programs[0]["weight_copies"]


def _file_ragged_step(v5e, name, tokens=None, rehearse=False):
    """(`scripts/step_hlo_copies.py` as a module, the compiled text of the
    ragged step of `benchmarks/configs/<name>.json` at a stream of `tokens`
    — its `--max-batch-tokens` by default — with the weights in the formats
    a runtime holds them in)."""
    import json
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "scripts"))
    import step_hlo_copies

    from benchmarks import serve
    from ollamamq_tpu import cli

    with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                           "configs", name + ".json")) as f:
        cfg = json.load(f)
    flags = cli.build_parser().parse_args(
        ["--models", cfg["name"]] + serve.server_flags(cfg, rehearse))
    lowered, _ = step_hlo_copies.step_programs(
        serve.model_config(cfg, rehearse), flags, v5e,
        tokens or flags.max_batch_tokens)
    return step_hlo_copies, lowered["mq_ragged_step"].compile().as_text()


def _device_ops(script, hlo):
    """[(computation, opcode, line)] of a compiled module's text, the
    insides of fusions left out (`step_hlo_copies.moves` has the rule)."""
    fused = {c for line in hlo.splitlines() if " fusion(" in line
             for c in script._CALLS.findall(line)}
    out, at = [], None
    for line in hlo.splitlines():
        head = script._COMPUTATION.match(line)
        if head:
            at = head["name"]
        elif at not in fused and (m := script._ANY_INSTR.match(line)):
            out.append((at, m.group(1), line))
    return out


def test_the_wide_rung_computes_the_absorbed_form_of_the_rung_in_a_branch(
        v5e):
    """DeepSeek-V3.2's configuration file, the 512-token ragged step as
    served (PR 55): the absorbed q of the RUNG — the contraction
    `bthn,chn->bthc` over 512 rows, its `bf16[512,128,640]` result and that
    result's 84 MB re-layout for the kernel's tiles — is computed inside
    the branch a conditional takes on a step with a narrow span behind the
    lead; on the other branch (`few`: a prompt's chunk behind a few decode
    rows) nothing of 32 MB is copied, and the contraction outside any branch
    runs over the 32 rows of the lead. W_uv's contraction `bthc,chv->bthv`
    runs over 32 rows a trip, inside a loop's body, and nowhere over the
    rung."""
    script, hlo = _file_ragged_step(v5e, "deepseek-v3.2-ep16-d5", 512)
    conds = script.branches(hlo)
    assert conds, conds  # one a traced layer body
    full = {b[0] for b in conds.values()}  # lax.cond's false branch
    few = {b[1] for b in conds.values()}
    moved = script.moves(hlo, 32 * 2 ** 20)
    q_abs = [m for m in moved if m["dims"] == [512, 128, 640]]
    assert q_abs and all(m["of"] in full for m in q_abs), q_abs
    assert not [m for m in moved if m["of"] in few], moved
    seen = {}  # rows of the contraction -> the computations it is an op of
    for at, op, line in _device_ops(script, hlo):
        if "bthn,chn->bthc/dot_general" in line and op == "fusion":
            dims = script._INSTR.match(line)["dims"].split(",")
            n = 512 if "512" in dims[:2] else 32 if "32" in dims[:2] \
                else None  # [512, 128, .] or [1, 32, 128, .]
            seen.setdefault(n, set()).add(at)
        if "bthc,chv->bthv/dot_general" in line and op == "fusion":
            assert "/attn_out/while/body/" in line, line  # a tile a trip
    assert set(seen) == {512, 32}, seen
    assert seen[512] <= full and not seen[32] & full, seen


# Instructions of openPangu's ragged `--spec` step at the file's `rehearse`
# sizes, the insides of fusions left out, as the tree BEFORE PR 55 compiled
# it: its layers run `_latent_attention_op` too, with no indexer and so no
# expanded body, and PR 55 means to leave them what they were. Take the
# number again (`len(_device_ops(...))`) only with a change that means to
# move that program.
OPENPANGU_REHEARSE_OPS = 1350


def test_a_latent_layer_with_no_expanded_body_is_the_program_it_was(v5e):
    """...and holds no conditional: where nothing is expanded a layer has
    nothing to choose (openPangu: no indexer; a rung of DeepSeek's under
    WIDE is `tests/test_deepseek_v32.py`'s, by its trace)."""
    script, hlo = _file_ragged_step(v5e, "openpangu-ultra-moe-ep16-d5",
                                    rehearse=True)
    assert script.branches(hlo) == {}
    assert len(_device_ops(script, hlo)) == OPENPANGU_REHEARSE_OPS


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
    2 MB, once a launch of eight passes). And at the
    file's `rehearse` sizes the two programs, the step kernel among them,
    compile too."""
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
    programs, _ = _step_hlo_copies(capsys, name, "--rehearse", "--min-mb",
                                   "0")
    assert [p["program"] for p in programs] \
        == ["mq_ragged_step", "mq_decode_scan"]


@pytest.mark.parametrize("name", [
    "olmo-hybrid-7b-d16", "lfm2-8b-a1b-d18", "qwen3-next-80b-a3b-ep4-d12"])
def test_no_step_program_copies_the_conv_window(v5e, capsys, name):
    """The three configuration files whose models keep a convolution window
    (PR 53), at PUBLISHED widths and a 64-token ragged step (their `rehearse`
    sizes list no program here: PERF.md section 7; ~35 s a file): the window
    is stored a tap a plane, [layers, K-1, slots, D], and neither step
    program holds a `copy` of its shape, whole or a layer's — stored a slot
    a sliver, Olmo-Hybrid's ragged step opened and closed with a copy of all
    54 MB and its decode scan re-laid a layer's 4.4 MB twice a layer. (An
    in-place `dynamic-update-slice` fusion keeps the window's shape for its
    result and is no copy; the one READ of a layer's planes is a
    `dynamic-slice`.) And by the compiler's own estimate (`--by-scope`) a
    linear layer's `lin_conv` stage — 4.4 MB of window — costs under two
    thirds of its `lin_in`, which streams 132 MB of weights: it was costed
    ABOVE it."""
    programs, _ = _step_hlo_copies(capsys, name, "--tokens", "64",
                                   "--min-mb", "0.25", "--by-scope")
    assert [p["program"] for p in programs] \
        == ["mq_ragged_step", "mq_decode_scan"]
    cfg, mc = _file_model(name)
    slots = int(cfg["server_flags"][cfg["server_flags"].index("--max-slots")
                                    + 1])
    window = llama.split_state(jax.eval_shape(
        lambda: llama.alloc_slot_state(mc, slots))).conv.shape
    assert window[1:3] == (mc.state_window[0] - 1, slots), window
    for p in programs:
        copies = [m for m in p["moves"] if m["moves"] == "copy"
                  and tuple(d for d in m["dims"] if d != 1)
                  in (tuple(window), tuple(window[1:]))]
        assert not copies, (p["program"], copies)
        if mc.count(LINEAR):  # the computation of a period of the layers
            period = max(p["scope_cycles"].values(),
                         key=lambda by: by.get("lin_in", [0])[0])
            assert 0 < period["lin_conv"][0] * 1.5 < period["lin_in"][0], \
                (p["program"], period)
