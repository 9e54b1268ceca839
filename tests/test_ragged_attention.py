"""Ragged mixed-batch attention: jnp reference vs blockwise vs the
Pallas kernel (interpret mode on CPU), and forward_ragged vs the
bucketed forward composition it replaces."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ollamamq_tpu.config import MODEL_CONFIGS
from ollamamq_tpu.engine import kv_cache as kvc
from ollamamq_tpu.models import llama
from ollamamq_tpu.ops.attention import (ragged_paged_attention,
                                        ragged_paged_attention_blockwise)
from ollamamq_tpu.ops.pallas.ragged_attention import (
    ragged_paged_attention_pallas)


LAYERS = 3  # pool depth of the kernel cases: first, middle, last layer


def _case(spans, B, PS=8, MP=8, Hk=2, H=4, hd=16, seed=0):
    """Build one ragged batch: spans = [(q_len, kv_len), ...] laid out
    contiguously in stream order; trailing rows of B are padding. The
    pool is whole — [LAYERS, S, Hk*hd], every layer different — and the
    attentions under test read one layer of it by index."""
    rng = np.random.default_rng(seed)
    T = sum(s for s, _ in spans)
    S = (MP * B + 2) * PS
    q = jnp.asarray(rng.normal(size=(T, H, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(LAYERS, S, Hk * hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(LAYERS, S, Hk * hd)), jnp.float32)
    pt = np.zeros((B, MP), np.int32)
    nxt = 1
    q_start = np.full(B, T, np.int32)
    q_len = np.zeros(B, np.int32)
    kv_len = np.zeros(B, np.int32)
    tok_seq = np.zeros(T, np.int32)
    tok_pos = np.full(T, -1, np.int32)
    off = 0
    for i, (ql, kv) in enumerate(spans):
        need = -(-kv // PS)
        pt[i, :need] = range(nxt, nxt + need)
        nxt += need
        q_start[i] = off
        q_len[i] = ql
        kv_len[i] = kv
        tok_seq[off:off + ql] = i
        tok_pos[off:off + ql] = np.arange(kv - ql, kv)
        off += ql
    return (q, k, v, jnp.asarray(pt), jnp.asarray(tok_seq),
            jnp.asarray(tok_pos), jnp.asarray(kv_len),
            jnp.asarray(q_start), jnp.asarray(q_len), PS)


MIXED_CASES = [
    # prefill span + decode rows + prefill tail, non-multiple-of-8 total
    dict(spans=[(11, 11), (1, 20), (5, 29), (1, 1)], B=6),
    # a whole tile of pure decode rows crossing a tile boundary
    dict(spans=[(1, 5 + 3 * i) for i in range(9)], B=10),
    # one long prefill spanning several tiles + mixed tail
    dict(spans=[(21, 21), (1, 9), (1, 17), (3, 30)], B=6),
]


@pytest.mark.parametrize("case", MIXED_CASES)
def test_blockwise_matches_reference(case):
    q, k, v, pt, tok_seq, tok_pos, kv_len, _qs, _ql, PS = _case(**case)
    ref = ragged_paged_attention(q, k, v, 1, pt, tok_seq, tok_pos, kv_len,
                                 PS)
    blk = ragged_paged_attention_blockwise(
        q, k, v, 1, pt, tok_seq, tok_pos, kv_len, PS, block_pages=2)
    np.testing.assert_allclose(np.asarray(blk), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("layer", range(LAYERS))
@pytest.mark.parametrize("case", MIXED_CASES)
def test_pallas_matches_reference(case, layer):
    q, k, v, pt, tok_seq, tok_pos, kv_len, qs, ql, PS = _case(**case)
    ref = ragged_paged_attention(q, k, v, layer, pt, tok_seq, tok_pos,
                                 kv_len, PS)
    out = ragged_paged_attention_pallas(q, k, v, layer, pt, qs, ql, kv_len,
                                        PS, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_pallas_mqa_and_group1():
    for Hk, H in ((1, 4), (4, 4)):
        q, k, v, pt, tok_seq, tok_pos, kv_len, qs, ql, PS = _case(
            spans=[(6, 6), (1, 12)], B=3, Hk=Hk, H=H, seed=2)
        ref = ragged_paged_attention(q, k, v, 2, pt, tok_seq, tok_pos,
                                     kv_len, PS)
        out = ragged_paged_attention_pallas(q, k, v, 2, pt, qs, ql, kv_len,
                                            PS, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


def test_forward_ragged_matches_bucketed_composition(tiny_cfg, tiny_params):
    """ONE mixed forward_ragged dispatch (decode row for seq A + full
    prefill span for seq B) must reproduce the bucketed composition
    forward_decode(A) then forward_prefill(B): same logits, same cache
    writes, same greedy argmax."""
    cfg, params = tiny_cfg, tiny_params
    PS, MP = 8, 8
    shape = (cfg.num_layers, 64 * PS, cfg.num_kv_heads * cfg.head_dim)
    rng = np.random.default_rng(3)
    a = kvc.PageAllocator(64, PS, MP)
    pagesA, pagesB = a.alloc(12), a.alloc(6)
    ptA = kvc.make_page_table_row(pagesA, MP)
    ptB = kvc.make_page_table_row(pagesB, MP)
    promptA = rng.integers(1, cfg.vocab_size, size=11).astype(np.int32)
    promptB = rng.integers(1, cfg.vocab_size, size=5).astype(np.int32)

    def prefill_a():
        kc = jnp.zeros(shape, jnp.float32)
        vc = jnp.zeros(shape, jnp.float32)
        _, kc, vc = llama.forward_prefill(
            params, cfg, jnp.asarray(promptA)[None], jnp.array([11]),
            kc, vc, jnp.asarray(ptA)[None], PS)
        return kc, vc

    kc, vc = prefill_a()
    logA_ref, kc_ref, vc_ref = llama.forward_decode(
        params, cfg, jnp.array([7], jnp.int32), jnp.array([11], jnp.int32),
        kc, vc, jnp.asarray(ptA)[None], PS, attn_impl="jnp")
    logB_ref, kc_ref, _ = llama.forward_prefill(
        params, cfg, jnp.asarray(promptB)[None], jnp.array([5]),
        kc_ref, vc_ref, jnp.asarray(ptB)[None], PS)

    kc2, vc2 = prefill_a()
    tokens = np.concatenate([[7], promptB]).astype(np.int32)
    tok_seq = np.array([0] + [1] * 5, np.int32)
    tok_pos = np.array([11, 0, 1, 2, 3, 4], np.int32)
    pt = np.stack([ptA, ptB])
    ws = np.array([pt[s][p // PS] * PS + p % PS
                   for s, p in zip(tok_seq, tok_pos)], np.int32)
    logits, kc2, _ = llama.forward_ragged(
        params, cfg, jnp.asarray(tokens), jnp.asarray(tok_seq),
        jnp.asarray(tok_pos), jnp.asarray(ws),
        jnp.asarray(np.array([0, 5], np.int32)), kc2, vc2,
        jnp.asarray(pt), jnp.asarray(np.array([0, 1], np.int32)),
        jnp.asarray(np.array([1, 5], np.int32)),
        jnp.asarray(np.array([12, 5], np.int32)), PS, attn_impl="jnp")

    np.testing.assert_allclose(np.asarray(logits[0]),
                               np.asarray(logA_ref[0]),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(logits[1]),
                               np.asarray(logB_ref[0]),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(kc2), np.asarray(kc_ref),
                               rtol=2e-4, atol=2e-4)
    assert int(jnp.argmax(logits[0])) == int(jnp.argmax(logA_ref[0]))
    assert int(jnp.argmax(logits[1])) == int(jnp.argmax(logB_ref[0]))


def test_forward_ragged_pallas_interpret_matches_jnp(tiny_cfg, tiny_params):
    """forward_ragged(attn_impl='pallas') == forward_ragged('jnp') via the
    interpret-mode kernel (compiled path needs a TPU)."""
    import ollamamq_tpu.ops.pallas.ragged_attention as ra

    cfg, params = tiny_cfg, tiny_params
    PS, MP = 8, 8
    shape = (cfg.num_layers, 64 * PS, cfg.num_kv_heads * cfg.head_dim)
    rng = np.random.default_rng(5)
    a = kvc.PageAllocator(64, PS, MP)
    pages = [a.alloc(10), a.alloc(4)]
    pt = np.stack([kvc.make_page_table_row(p, MP) for p in pages])
    tokens = rng.integers(1, cfg.vocab_size, size=13).astype(np.int32)
    tok_seq = np.array([0] * 9 + [1] * 4, np.int32)
    tok_pos = np.concatenate([np.arange(9), np.arange(4)]).astype(np.int32)
    ws = np.array([pt[s][p // PS] * PS + p % PS
                   for s, p in zip(tok_seq, tok_pos)], np.int32)
    meta = dict(
        last_idx=jnp.asarray(np.array([8, 12], np.int32)),
        page_table=jnp.asarray(pt),
        q_start=jnp.asarray(np.array([0, 9], np.int32)),
        q_len=jnp.asarray(np.array([9, 4], np.int32)),
        kv_len=jnp.asarray(np.array([9, 4], np.int32)),
    )

    orig = ra.ragged_paged_attention_pallas
    ra.ragged_paged_attention_pallas = (
        lambda *args, **kw: orig(*args, **{**kw, "interpret": True}))
    try:
        outs = {}
        for impl in ("jnp", "pallas"):
            kc = jnp.zeros(shape, jnp.float32)
            vc = jnp.zeros(shape, jnp.float32)
            logits, _, _ = llama.forward_ragged(
                params, cfg, jnp.asarray(tokens), jnp.asarray(tok_seq),
                jnp.asarray(tok_pos), jnp.asarray(ws), meta["last_idx"],
                kc, vc, meta["page_table"], meta["q_start"],
                meta["q_len"], meta["kv_len"], PS, attn_impl=impl)
            outs[impl] = np.asarray(logits)
    finally:
        ra.ragged_paged_attention_pallas = orig
    np.testing.assert_allclose(outs["pallas"], outs["jnp"],
                               rtol=5e-5, atol=5e-5)
