"""Pallas ragged paged-attention kernel vs the jnp reference (interpret
mode on CPU; the compiled path runs on real TPU via the engine/bench)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ollamamq_tpu.ops.attention import paged_decode_attention
from ollamamq_tpu.ops.pallas.paged_attention import paged_decode_attention_pallas


LAYERS = 3  # pool depth of the kernel cases: first, middle, last layer


def _case(B, H, Hk, hd, PS_, MP, seq_lens, seed=0):
    """The pool is whole — [LAYERS, S, Hk*hd], every layer different —
    and the attentions under test read one layer of it by index."""
    rng = np.random.default_rng(seed)
    S = (MP * B + 2) * PS_
    q = jnp.asarray(rng.normal(size=(B, H, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(LAYERS, S, Hk * hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(LAYERS, S, Hk * hd)), jnp.float32)
    pt = np.zeros((B, MP), np.int32)
    next_page = 1
    for b, L in enumerate(seq_lens):
        need = -(-L // PS_)
        pt[b, :need] = range(next_page, next_page + need)
        next_page += need
    return q, k, v, jnp.asarray(pt), jnp.asarray(seq_lens, jnp.int32)


@pytest.mark.parametrize("layer", range(LAYERS))
@pytest.mark.parametrize("seq_lens", [[20, 9, 37], [1, 48, 16]])
def test_pallas_matches_reference(seq_lens, layer):
    q, k, v, pt, sl = _case(3, 8, 4, 32, 8, 6, seq_lens)
    ref = paged_decode_attention(q, k, v, layer, pt, sl, 8)
    out = paged_decode_attention_pallas(q, k, v, layer, pt, sl, 8,
                                        interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_pallas_mqa_single_kv_head():
    q, k, v, pt, sl = _case(2, 4, 1, 16, 8, 4, [8, 25])
    ref = paged_decode_attention(q, k, v, 2, pt, sl, 8)
    out = paged_decode_attention_pallas(q, k, v, 2, pt, sl, 8,
                                        interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


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


def test_forward_prefill_sp_matches(tiny_cfg, tiny_params):
    """Sequence-parallel prefill (ring attention) == single-device prefill."""
    from jax.sharding import NamedSharding
    from ollamamq_tpu.engine import kv_cache as kvc
    from ollamamq_tpu.models import llama
    from ollamamq_tpu.parallel.mesh import make_mesh

    if len(jax.devices()) < 4:
        pytest.skip("needs virtual devices")
    cfg, params = tiny_cfg, tiny_params
    mesh = make_mesh(dp=1, sp=4, tp=1)
    PS_, MP = 8, 8
    T = 32
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(1, cfg.vocab_size, size=(1, T)),
        jnp.int32,
    )
    seq_lens = jnp.array([T])

    shape = (cfg.num_layers, 32 * PS_, cfg.num_kv_heads * cfg.head_dim)
    kc = jnp.zeros(shape, jnp.float32)
    vc = jnp.zeros(shape, jnp.float32)
    a = kvc.PageAllocator(32, PS_, MP)
    pages = a.alloc(T)
    pt = jnp.asarray(np.stack([kvc.make_page_table_row(pages, MP)]))
    ref_logits, ref_kc, _ = llama.forward_prefill(
        params, cfg, tokens, seq_lens, kc, vc, pt, PS_
    )

    with jax.set_mesh(mesh):
        sp_logits, k_stack, v_stack = llama.forward_prefill_sp(
            params, cfg, tokens, seq_lens, mesh
        )
    np.testing.assert_allclose(
        np.asarray(sp_logits), np.asarray(ref_logits), rtol=2e-4, atol=2e-4
    )
    # K stack matches what single-device prefill wrote into the pages.
    slots = np.asarray(
        [pages[t // PS_] * PS_ + t % PS_ for t in range(T)]
    )
    np.testing.assert_allclose(
        np.asarray(k_stack[:, 0]).reshape(cfg.num_layers, T, -1),
        np.asarray(ref_kc)[:, slots],  # the pool's rows: [L,T,Hk*hd]
        rtol=2e-4, atol=2e-4,
    )
