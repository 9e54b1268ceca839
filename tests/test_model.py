"""Model correctness: prefill/decode equivalence, paged KV, encoder pooling."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ollamamq_tpu.config import MODEL_CONFIGS, EngineConfig, get_model_config
from ollamamq_tpu.engine import kv_cache as kvc
from ollamamq_tpu.models import llama

PAGE_SIZE = 8
MAX_PAGES = 8


def _fresh_cache(cfg, num_pages=32):
    shape = (cfg.num_layers, num_pages * PAGE_SIZE,
             cfg.num_kv_heads * cfg.head_dim)  # the stored pool layout
    return jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.float32)


def _page_table(alloc, rows):
    return jnp.asarray(
        np.stack([kvc.make_page_table_row(r, MAX_PAGES) for r in rows])
    )


def test_smart_model_match():
    assert get_model_config("llama3:8b").name == "llama3:8b"
    assert get_model_config("LLAMA3:8B").name == "llama3:8b"
    assert get_model_config("llama3.2").name in ("llama3.2:1b", "llama3.2:3b")
    assert get_model_config("qwen2.5:latest") is not None
    assert get_model_config("nope-model") is None


def test_page_allocator():
    a = kvc.PageAllocator(num_pages=8, page_size=4, max_pages_per_seq=4)
    assert a.free_pages == 7  # page 0 reserved
    p = a.alloc(9)  # 3 pages
    assert len(p) == 3 and kvc.TRASH_PAGE not in p
    assert a.extend(p, 16)  # 4 pages
    assert len(p) == 4
    assert not a.extend(p, 17)  # cap hit
    a.free(p)
    assert a.free_pages == 7 and p == []


def test_prefill_decode_equivalence(tiny_cfg, tiny_params):
    """Greedy decode via paged cache must match teacher-forced prefill logits."""
    cfg, params = tiny_cfg, tiny_params
    key = jax.random.PRNGKey(42)
    prompt = jax.random.randint(key, (1, 5), 0, cfg.vocab_size, dtype=jnp.int32)
    alloc = kvc.PageAllocator(32, PAGE_SIZE, MAX_PAGES)
    pages = alloc.alloc(5)
    pt = _page_table(alloc, [pages])

    kc, vc = _fresh_cache(cfg)
    logits, kc, vc = llama.forward_prefill(
        params, cfg, prompt, jnp.array([5]), kc, vc, pt, PAGE_SIZE
    )
    toks = [int(jnp.argmax(logits[0]))]

    # Decode 6 more tokens through the paged cache.
    for i in range(6):
        pos = 5 + i
        alloc.extend(pages, pos + 1)
        pt = _page_table(alloc, [pages])
        logits_d, kc, vc = llama.forward_decode(
            params, cfg, jnp.array([toks[-1]], jnp.int32), jnp.array([pos], jnp.int32),
            kc, vc, pt, PAGE_SIZE,
        )
        # Reference: full prefill over the entire prefix with a fresh cache.
        full = jnp.concatenate([prompt[0], jnp.array(toks, jnp.int32)])[None, :]
        kc2, vc2 = _fresh_cache(cfg)
        a2 = kvc.PageAllocator(32, PAGE_SIZE, MAX_PAGES)
        pt2 = _page_table(a2, [a2.alloc(full.shape[1])])
        logits_ref, _, _ = llama.forward_prefill(
            params, cfg, full, jnp.array([full.shape[1]]), kc2, vc2, pt2, PAGE_SIZE
        )
        np.testing.assert_allclose(
            np.asarray(logits_d[0]), np.asarray(logits_ref[0]), rtol=2e-4, atol=2e-4
        )
        toks.append(int(jnp.argmax(logits_d[0])))


def test_prefill_padding_invariance(tiny_cfg, tiny_params):
    """Padded prompt gives same last-token logits as exact-length prompt."""
    cfg, params = tiny_cfg, tiny_params
    prompt = jnp.arange(1, 6, dtype=jnp.int32)[None, :]  # len 5
    padded = jnp.pad(prompt, ((0, 0), (0, 11)))  # len 16

    out = []
    for toks in (prompt, padded):
        kc, vc = _fresh_cache(cfg)
        a = kvc.PageAllocator(32, PAGE_SIZE, MAX_PAGES)
        pt = _page_table(a, [a.alloc(5)])
        logits, _, _ = llama.forward_prefill(
            params, cfg, toks, jnp.array([5]), kc, vc, pt, PAGE_SIZE
        )
        out.append(np.asarray(logits))
    np.testing.assert_allclose(out[0], out[1], rtol=1e-4, atol=1e-4)


def test_batched_decode_independence(tiny_cfg, tiny_params):
    """Sequences in one decode batch don't contaminate each other."""
    cfg, params = tiny_cfg, tiny_params
    p1 = jnp.array([[1, 2, 3, 4, 5]], jnp.int32)
    p2 = jnp.array([[9, 8, 7]], jnp.int32)

    # Solo run of p1.
    kc, vc = _fresh_cache(cfg)
    a = kvc.PageAllocator(32, PAGE_SIZE, MAX_PAGES)
    pg1 = a.alloc(5)
    pt = _page_table(a, [pg1])
    lg_solo, kc, vc = llama.forward_prefill(params, cfg, p1, jnp.array([5]), kc, vc, pt, PAGE_SIZE)
    t1 = int(jnp.argmax(lg_solo[0]))
    a.extend(pg1, 6)
    lg_solo_d, _, _ = llama.forward_decode(
        params, cfg, jnp.array([t1], jnp.int32), jnp.array([5], jnp.int32),
        kc, vc, _page_table(a, [pg1]), PAGE_SIZE,
    )

    # Batched: p1 and p2 share the pool.
    kc, vc = _fresh_cache(cfg)
    a = kvc.PageAllocator(32, PAGE_SIZE, MAX_PAGES)
    pg1, pg2 = a.alloc(5), a.alloc(3)
    pad2 = jnp.pad(p2, ((0, 0), (0, 2)))
    lg1, kc, vc = llama.forward_prefill(params, cfg, p1, jnp.array([5]), kc, vc, _page_table(a, [pg1]), PAGE_SIZE)
    lg2, kc, vc = llama.forward_prefill(params, cfg, pad2, jnp.array([3]), kc, vc, _page_table(a, [pg2]), PAGE_SIZE)
    bt1 = int(jnp.argmax(lg1[0]))
    a.extend(pg1, 6)
    a.extend(pg2, 4)
    pt = _page_table(a, [pg1, pg2])
    lg_b, _, _ = llama.forward_decode(
        params, cfg,
        jnp.array([bt1, int(jnp.argmax(lg2[0]))], jnp.int32),
        jnp.array([5, 3], jnp.int32),
        kc, vc, pt, PAGE_SIZE,
    )
    assert bt1 == t1
    np.testing.assert_allclose(
        np.asarray(lg_b[0]), np.asarray(lg_solo_d[0]), rtol=2e-4, atol=2e-4
    )


def test_qwen_bias_config():
    cfg = MODEL_CONFIGS["test-tiny-qwen"]
    params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    assert "bq" in params["layers"]
    kc, vc = _fresh_cache(cfg)
    a = kvc.PageAllocator(32, PAGE_SIZE, MAX_PAGES)
    pt = _page_table(a, [a.alloc(4)])
    logits, _, _ = llama.forward_prefill(
        params, cfg, jnp.array([[1, 2, 3, 4]], jnp.int32), jnp.array([4]), kc, vc, pt, PAGE_SIZE
    )
    assert logits.shape == (1, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits)))


def test_qwen3_qk_norm():
    cfg = MODEL_CONFIGS["test-tiny-qwen3"]
    params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    assert "q_norm" in params["layers"] and "bq" not in params["layers"]
    kc, vc = _fresh_cache(cfg)
    a = kvc.PageAllocator(32, PAGE_SIZE, MAX_PAGES)
    pt = _page_table(a, [a.alloc(4)])
    toks = jnp.array([[1, 2, 3, 4]], jnp.int32)
    logits, _, _ = llama.forward_prefill(
        params, cfg, toks, jnp.array([4]), kc, vc, pt, PAGE_SIZE
    )
    assert bool(jnp.all(jnp.isfinite(logits)))
    # The norm is actually in the path: scaling its weight changes logits.
    bent = dict(params, layers=dict(params["layers"]))
    bent["layers"]["q_norm"] = params["layers"]["q_norm"] * 3.0
    kc2, vc2 = _fresh_cache(cfg)
    logits2, _, _ = llama.forward_prefill(
        bent, cfg, toks, jnp.array([4]), kc2, vc2, pt, PAGE_SIZE
    )
    assert not np.allclose(np.asarray(logits), np.asarray(logits2))


def test_encoder_embeddings():
    cfg = MODEL_CONFIGS["test-tiny-embed"]
    params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    toks = jnp.array([[1, 2, 3, 0, 0], [4, 5, 6, 7, 8]], jnp.int32)
    emb = llama.forward_encoder(params, cfg, toks, jnp.array([3, 5]))
    assert emb.shape == (2, cfg.hidden_size)
    norms = jnp.linalg.norm(emb, axis=-1)
    np.testing.assert_allclose(np.asarray(norms), 1.0, rtol=1e-5)
    # Padding invariance: same tokens, different pad width => same embedding.
    emb2 = llama.forward_encoder(
        params, cfg, jnp.array([[1, 2, 3]], jnp.int32), jnp.array([3])
    )
    np.testing.assert_allclose(np.asarray(emb[0]), np.asarray(emb2[0]), rtol=1e-4, atol=1e-5)


def test_forward_embed_generative():
    """Embeddings from a CAUSAL model: normalized, padding-invariant."""
    cfg = MODEL_CONFIGS["test-tiny"]
    params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    toks = jnp.array([[1, 2, 3, 0, 0], [4, 5, 6, 7, 8]], jnp.int32)
    emb = llama.forward_embed(params, cfg, toks, jnp.array([3, 5]))
    assert emb.shape == (2, cfg.hidden_size)
    np.testing.assert_allclose(
        np.asarray(jnp.linalg.norm(emb, axis=-1)), 1.0, rtol=1e-5)
    emb2 = llama.forward_embed(
        params, cfg, jnp.array([[1, 2, 3]], jnp.int32), jnp.array([3])
    )
    np.testing.assert_allclose(
        np.asarray(emb[0]), np.asarray(emb2[0]), rtol=1e-4, atol=1e-5)


def test_chunked_prefill_equivalence(tiny_cfg, tiny_params):
    """Chaining forward_prefill_chunk chunks == one-shot forward_prefill."""
    cfg, params = tiny_cfg, tiny_params
    T, C = 24, 8  # 3 chunks
    toks = jax.random.randint(jax.random.PRNGKey(7), (1, T), 0, cfg.vocab_size,
                              dtype=jnp.int32)
    a = kvc.PageAllocator(32, PAGE_SIZE, MAX_PAGES)
    pages = a.alloc(T)
    pt = _page_table(a, [pages])

    kc, vc = _fresh_cache(cfg)
    ref_logits, ref_kc, ref_vc = llama.forward_prefill(
        params, cfg, toks, jnp.array([T]), kc, vc, pt, PAGE_SIZE
    )

    kc2, vc2 = _fresh_cache(cfg)
    for start in range(0, T, C):
        chunk = toks[:, start:start + C]
        logits, kc2, vc2 = llama.forward_prefill_chunk(
            params, cfg, chunk, jnp.array([start]), jnp.array([C]),
            kc2, vc2, pt, PAGE_SIZE,
        )
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(ref_logits), rtol=2e-4, atol=2e-4
    )
    np.testing.assert_allclose(
        np.asarray(kc2), np.asarray(ref_kc), rtol=1e-5, atol=1e-5
    )


def test_chunked_prefill_ragged_last_chunk(tiny_cfg, tiny_params):
    """Last chunk shorter than the chunk bucket (padding masked)."""
    cfg, params = tiny_cfg, tiny_params
    T, C = 19, 8  # chunks of 8, 8, 3
    toks = jax.random.randint(jax.random.PRNGKey(8), (1, T), 0, cfg.vocab_size,
                              dtype=jnp.int32)
    a = kvc.PageAllocator(32, PAGE_SIZE, MAX_PAGES)
    pt = _page_table(a, [a.alloc(T)])

    kc, vc = _fresh_cache(cfg)
    ref_logits, _, _ = llama.forward_prefill(
        params, cfg, toks, jnp.array([T]), kc, vc, pt, PAGE_SIZE
    )
    kc2, vc2 = _fresh_cache(cfg)
    for start in range(0, T, C):
        piece = np.zeros((1, C), np.int32)
        cl = min(C, T - start)
        piece[0, :cl] = np.asarray(toks[0, start:start + cl])
        logits, kc2, vc2 = llama.forward_prefill_chunk(
            params, cfg, jnp.asarray(piece), jnp.array([start]), jnp.array([cl]),
            kc2, vc2, pt, PAGE_SIZE,
        )
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(ref_logits), rtol=2e-4, atol=2e-4
    )


def test_blockwise_chunk_attention_matches_full_gather():
    """paged_chunk_attention_blockwise (dynamic block walk, online softmax)
    == paged_chunk_attention (full padded gather) on ragged paged batches."""
    from ollamamq_tpu.ops.attention import (
        paged_chunk_attention,
        paged_chunk_attention_blockwise,
    )

    rng = np.random.default_rng(3)
    B, C, H, Hk, hd, ps, MP = 3, 8, 4, 2, 16, 4, 12
    S = 64 * ps
    q = jnp.asarray(rng.normal(size=(B, C, H, hd)), jnp.float32)
    # A 3-layer pool; the attentions read layer 1 of it by index.
    kc = jnp.asarray(rng.normal(size=(3, S, Hk * hd)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(3, S, Hk * hd)), jnp.float32)
    # Distinct pages per sequence; tables longer than any sequence needs.
    pt = jnp.asarray(
        rng.permutation(64 - 1)[: B * MP].reshape(B, MP) + 1, jnp.int32
    )
    # Third sequence's context reaches the LAST page (end=48 == MP*ps), so
    # the final partial block is exercised when block_pages doesn't divide MP.
    start = jnp.asarray([0, 9, 44], jnp.int32)
    chunk_lens = jnp.asarray([8, 5, 4], jnp.int32)  # ragged
    ref = paged_chunk_attention(q, kc, vc, 1, pt, start, chunk_lens, ps)
    # block_pages=5 does NOT divide MP=12: the final partial block must not
    # relabel or double-count pages (clamped-slice regression).
    for bp in (2, 5):
        blk = paged_chunk_attention_blockwise(
            q, kc, vc, 1, pt, start, chunk_lens, ps, block_pages=bp
        )
        for b in range(B):
            n = int(chunk_lens[b])
            np.testing.assert_allclose(
                np.asarray(blk[b, :n]), np.asarray(ref[b, :n]),
                rtol=2e-5, atol=2e-5, err_msg=f"block_pages={bp} seq {b}",
            )


def test_apply_penalties_math():
    from ollamamq_tpu.ops.sampling import apply_penalties

    logits = jnp.array([[2.0, -2.0, 1.0, -1.0]])
    recent = jnp.array([[1, 1, 0, -1]], jnp.int32)  # id1 twice, id0 once
    one = jnp.array([1.0])
    zero = jnp.array([0.0])
    # repeat only: matches apply_repeat_penalty semantics
    out = np.asarray(apply_penalties(logits, recent, jnp.array([2.0]), zero, zero))
    np.testing.assert_allclose(out, [[1.0, -4.0, 1.0, -1.0]])
    # presence: flat -0.5 on seen ids regardless of count
    out = np.asarray(apply_penalties(logits, recent, one, jnp.array([0.5]), zero))
    np.testing.assert_allclose(out, [[1.5, -2.5, 1.0, -1.0]])
    # frequency: -0.5 per occurrence (id1 seen twice)
    out = np.asarray(apply_penalties(logits, recent, one, zero, jnp.array([0.5])))
    np.testing.assert_allclose(out, [[1.5, -3.0, 1.0, -1.0]])
    # all off => identity
    out = np.asarray(apply_penalties(logits, recent, one, zero, zero))
    np.testing.assert_allclose(out, np.asarray(logits))


def test_per_row_keys_seed_isolation():
    """Seeded rows depend only on (seed, position); unseeded rows follow the
    engine stream key."""
    from ollamamq_tpu.ops.sampling import per_row_keys

    seeds = jnp.array([7, 0], jnp.int32)
    pos = jnp.array([5, 5], jnp.int32)
    k1 = per_row_keys(jax.random.PRNGKey(1), seeds, pos)
    k2 = per_row_keys(jax.random.PRNGKey(2), seeds, pos)
    assert np.array_equal(k1[0], k2[0])  # seeded: engine key irrelevant
    assert not np.array_equal(k1[1], k2[1])  # unseeded: engine key matters
    k3 = per_row_keys(jax.random.PRNGKey(1), seeds, jnp.array([6, 5], jnp.int32))
    assert not np.array_equal(k1[0], k3[0])  # position advances the stream


def test_apply_repeat_penalty_math():
    from ollamamq_tpu.ops.sampling import apply_repeat_penalty

    logits = jnp.array([[2.0, -2.0, 1.0, -1.0]])
    seen = jnp.array([[1, 1, 0, 0]], jnp.int8)
    pen = jnp.array([2.0])
    out = np.asarray(apply_repeat_penalty(logits, seen, pen))
    np.testing.assert_allclose(out, [[1.0, -4.0, 1.0, -1.0]])
    # penalty 1.0 => identity
    out2 = np.asarray(apply_repeat_penalty(logits, seen, jnp.array([1.0])))
    np.testing.assert_allclose(out2, np.asarray(logits))
