"""Ask the chip's compiler, without the chip: AOT-compile the attention
kernels of the serving path for a DESCRIBED TPU v5e at llama3.2:1b widths
and the CLI's default pool shape. Interpret-mode tests cannot see what
Mosaic refuses (slices off the tiling, kernels GSPMD cannot partition);
this file can, at no chip time. Nothing runs — a compile that passes is
not a chip run. Skipped where the v5e topology cannot be described."""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from ollamamq_tpu.engine.engine import select_attn_impl
from ollamamq_tpu.ops.attention import (paged_decode_attention_any,
                                        ragged_attention_any)
from ollamamq_tpu.ops.quant import QuantKV
from ollamamq_tpu.parallel.mesh import make_mesh

# llama3.2:1b heads (config.py) under the CLI defaults: 64 slots, 256
# pages a sequence, a 1024-page pool of 32-token pages.
H, HK, HD = 32, 8, 64
B, MP, PS, NP = 64, 256, 32, 1024
T = 64


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


def _shapes(sharding_of, kv_dtype=jnp.bfloat16):
    """(q_ragged, q_decode, pool, page_table, [T] meta, [B] meta) as
    ShapeDtypeStructs; `sharding_of(spec)` places each."""
    def s(shape, dt, spec=P()):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding_of(spec))

    heads = P(None, "tensor", None)
    pool = s((NP * PS, HK, HD), kv_dtype, heads)
    if kv_dtype == jnp.int8:
        pool = QuantKV(pool, s((NP * PS, HK), jnp.float32,
                               P(None, "tensor")))
    return (s((T, H, HD), jnp.bfloat16, heads),
            s((B, H, HD), jnp.bfloat16, heads), pool,
            s((B, MP), jnp.int32), s((T,), jnp.int32), s((B,), jnp.int32))


def _compile_ragged(shapes, mesh=None):
    q, _, pool, pt, per_tok, per_seq = shapes
    return jax.jit(
        lambda q, kc, vc, pt, ts, tp, kl, qs, ql: ragged_attention_any(
            "pallas", q, kc, vc, pt, ts, tp, kl, qs, ql, PS, mesh=mesh)
    ).lower(q, pool, pool, pt, per_tok, per_tok, per_seq, per_seq,
            per_seq).compile()


def _compile_decode(shapes, mesh=None):
    _, q, pool, pt, _, per_seq = shapes
    return jax.jit(
        lambda q, kc, vc, pt, sl: paged_decode_attention_any(
            "pallas", q, kc, vc, pt, sl, PS, mesh=mesh)
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
