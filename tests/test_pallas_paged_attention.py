"""Pallas ragged paged-attention kernel vs the jnp reference (interpret
mode on CPU; the compiled path runs on real TPU via the engine)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ollamamq_tpu.ops.attention import paged_decode_attention
from ollamamq_tpu.ops.pallas.paged_attention import paged_decode_attention_pallas
from test_ragged_attention import F32_TOL, _f32, assert_kernel_close


LAYERS = 3  # pool depth of the kernel cases: first, middle, last layer


def _case(B, H, Hk, hd, PS_, MP, seq_lens, seed=0, dtype=jnp.float32):
    """The pool is whole — [LAYERS, S, Hk*hd], every layer different —
    and the attentions under test read one layer of it by index."""
    rng = np.random.default_rng(seed)
    S = (sum(-(-n // PS_) for n in seq_lens) + 2) * PS_
    q = jnp.asarray(rng.normal(size=(B, H, hd)), dtype)
    k = jnp.asarray(rng.normal(size=(LAYERS, S, Hk * hd)), dtype)
    v = jnp.asarray(rng.normal(size=(LAYERS, S, Hk * hd)), dtype)
    pt = np.zeros((B, MP), np.int32)
    next_page = 1
    for b, L in enumerate(seq_lens):
        need = -(-L // PS_)
        pt[b, :need] = range(next_page, next_page + need)
        next_page += need
    return q, k, v, jnp.asarray(pt), jnp.asarray(seq_lens, jnp.int32)


SMALL_CASES = [dict(B=3, H=8, Hk=4, hd=32, PS_=8, MP=6, seq_lens=sl)
               for sl in ([20, 9, 37], [1, 48, 16])]
# The published head shapes (H, Hk, hd) at the engine's page size, cut only
# in count of pages: Qwen2.5-7B, Qwen3-8B as one tp=4 shard sees it,
# LFM2-8B-A1B / llama3.2 (two heads a lane tile), OLMoE and Olmo-Hybrid
# (group 1: one row-head a kv head, the Vpu inner product). Eight rows from
# eight sequences: a context of ONE token; ends inside a page, on a page
# edge, on and just past the 128-token block edge; 6 and 7 pages (no
# multiple of a block's four).
HEAD_SHAPES = [(28, 4, 128), (8, 2, 128), (32, 8, 64), (16, 16, 128),
               (30, 30, 128)]
PUBLISHED_CASES = [
    dict(B=8, H=H, Hk=Hk, hd=hd, PS_=32, MP=8, seed=H,
         seq_lens=[1, 33, 128, 129, 163, 200, 64, 100])
    for H, Hk, hd in HEAD_SHAPES]
# The page stream's unit is the block of four pages (kv_contract.py): one
# predicate a block, every page of it copied, the trash page where the
# table's padding begins. Whole pages, so that the last block is all that
# differs: a row of length 0 between live rows (no block: nothing starts,
# nothing is waited for), contexts that end 1, 2 and 3 pages into their
# first and their second block and exactly on both block edges — the last
# a row whose table is full (no padding left to read). SMALL_CASES' table
# of 6 pages is no multiple of a block: the wrapper pads it.
BLOCK_CASES = [
    dict(B=10, H=H, Hk=Hk, hd=hd, PS_=32, MP=8, seed=H + 1,
         seq_lens=[32, 0, 64, 96, 128, 160, 192, 224, 256, 0])
    for H, Hk, hd in HEAD_SHAPES]
# q and the pool in bf16 against the float32 twin fed the same bf16
# values: the f32 tolerance plus the output's rounding and P's into P·V,
# which `test_ragged_attention.two_roundings` derives from the case.
PUBLISHED_CASES.append(dict(PUBLISHED_CASES[0], dtype=jnp.bfloat16))
BLOCK_CASES.append(dict(BLOCK_CASES[0], dtype=jnp.bfloat16))


def _id(case):
    return "H{H}-Hk{Hk}-hd{hd}-".format(**case) + (
        "bf16" if "dtype" in case else "x".join(map(str, case["seq_lens"])))


@pytest.mark.parametrize("layer", range(LAYERS))
@pytest.mark.parametrize("case", SMALL_CASES + PUBLISHED_CASES + BLOCK_CASES,
                         ids=_id)
def test_pallas_matches_reference(case, layer, poison_trash_page):
    q, k, v, pt, sl = _case(**case)
    ps = case["PS_"]
    clean = paged_decode_attention_pallas(q, k, v, layer, pt, sl, ps,
                                          interpret=True)
    # The kernel reads the trash page where a block runs past a row's last
    # page; the reference reads the clean pool (conftest.py).
    out = paged_decode_attention_pallas(
        q, poison_trash_page(k, ps, layer), poison_trash_page(v, ps, layer),
        layer, pt, sl, ps, interpret=True)
    assert out.dtype == q.dtype
    live = np.asarray(sl) > 0  # a row of no context has no defined output
    np.testing.assert_array_equal(np.asarray(_f32(out))[live],
                                  np.asarray(_f32(clean))[live])
    assert_kernel_close(
        out[live], q.dtype, v, lambda v: paged_decode_attention(
            _f32(q), _f32(k), v, layer, pt, sl, ps)[live])


@pytest.mark.parametrize("inner", ["mxu", "vpu"])
@pytest.mark.parametrize("H,Hk", [(4, 1), (4, 4), (8, 4)])
def test_pallas_mqa_single_kv_head(H, Hk, inner):
    """Either inner product of ops/pallas/kv_contract.py at any group: the
    serving path picks by the row-heads that share a kv head (one → vpu),
    a test may force the other."""
    q, k, v, pt, sl = _case(2, H, Hk, 16, 8, 4, [8, 25])
    ref = paged_decode_attention(q, k, v, 2, pt, sl, 8)
    out = paged_decode_attention_pallas(q, k, v, 2, pt, sl, 8,
                                        interpret=True, inner=inner)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **F32_TOL)


def test_model_decode_with_pallas_impl(tiny_cfg, tiny_params):
    """forward_decode(attn_impl='pallas') == forward_decode('jnp') —
    but pallas_call's compiled path needs a TPU, so force interpret by
    monkeypatching the kernel wrapper."""
    import ollamamq_tpu.models.llama as llama_mod
    from ollamamq_tpu.engine import kv_cache as kvc
    import functools

    cfg, params = tiny_cfg, tiny_params
    PS_, MP = 8, 8
    shape = (cfg.num_layers, 32 * PS_, cfg.num_kv_heads * cfg.head_dim)
    import ollamamq_tpu.ops.pallas.paged_attention as pa

    orig = pa.paged_decode_attention_pallas
    # Force interpret even though the caller passes interpret=False
    # explicitly (a partial's keyword would be overridden).
    pa.paged_decode_attention_pallas = (
        lambda *a, **k: orig(*a, **{**k, "interpret": True})
    )
    try:
        a = kvc.PageAllocator(32, PS_, MP)
        pages = a.alloc(6)
        pt = jnp.asarray(np.stack([kvc.make_page_table_row(pages, MP)]))
        kc = jnp.zeros(shape, jnp.float32)
        vc = jnp.zeros(shape, jnp.float32)
        logits, kc, vc = llama_mod.forward_prefill(
            params, cfg, jnp.arange(1, 6, dtype=jnp.int32)[None], jnp.array([5]),
            kc, vc, pt, PS_,
        )
        out_jnp, kcj, vcj = llama_mod.forward_decode(
            params, cfg, jnp.array([7], jnp.int32), jnp.array([5], jnp.int32),
            kc, vc, pt, PS_, attn_impl="jnp",
        )
        out_pal, _, _ = llama_mod.forward_decode(
            params, cfg, jnp.array([7], jnp.int32), jnp.array([5], jnp.int32),
            kc, vc, pt, PS_, attn_impl="pallas",
        )
    finally:
        pa.paged_decode_attention_pallas = orig
    np.testing.assert_allclose(
        np.asarray(out_pal), np.asarray(out_jnp), rtol=5e-5, atol=5e-5
    )
