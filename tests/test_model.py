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


# Span lengths a prompt is fed in. The engine cuts a prompt wherever the
# tick's token budget runs out, so an edge may fall anywhere in a page.
CHUNKINGS = {
    "one-span": (21,),
    "two-equal": (12, 12),
    "ragged-last": (8, 8, 3),
    "edge-inside-page": (5, 6, 10),
    "one-token-chunk": (9, 1, 7),
}


@pytest.mark.parametrize("preset",
                         ["test-tiny", "test-tiny-gqa", "test-tiny-qwen3"])
@pytest.mark.parametrize("spans", CHUNKINGS.values(), ids=CHUNKINGS.keys())
def test_ragged_span_chaining_matches_one_shot_prefill(preset, spans):
    """A prompt fed to forward_ragged span by span through the paged pool
    == the one-shot dense forward_prefill: same logits at the last
    position, same K and V rows in the pool. Each call is laid out as the
    engine lays a step out: the stream padded to the token granule
    (padding writes into the trash page), a padding row beside the span's."""
    cfg = MODEL_CONFIGS[preset]
    params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    T, G = sum(spans), 8
    toks = np.asarray(jax.random.randint(
        jax.random.PRNGKey(7), (T,), 1, cfg.vocab_size, dtype=jnp.int32))
    a = kvc.PageAllocator(32, PAGE_SIZE, MAX_PAGES)
    row = kvc.make_page_table_row(a.alloc(T), MAX_PAGES)
    pt = np.stack([row, np.full_like(row, kvc.TRASH_PAGE)])

    kc, vc = _fresh_cache(cfg)
    ref_logits, ref_kc, ref_vc = llama.forward_prefill(
        params, cfg, jnp.asarray(toks)[None], jnp.array([T]), kc, vc,
        jnp.asarray(pt[:1]), PAGE_SIZE)

    kc, vc = _fresh_cache(cfg)
    start = 0
    for n in spans:
        T_pad = -(-n // G) * G
        stream = np.zeros(T_pad, np.int32)
        stream[:n] = toks[start:start + n]
        tok_pos = np.full(T_pad, -1, np.int32)
        tok_pos[:n] = np.arange(start, start + n)
        ws = np.zeros(T_pad, np.int32)  # padding: slot 0 of the trash page
        ws[:n] = row[tok_pos[:n] // PAGE_SIZE] * PAGE_SIZE \
            + tok_pos[:n] % PAGE_SIZE
        logits, kc, vc = llama.forward_ragged(
            params, cfg, jnp.asarray(stream), jnp.zeros(T_pad, jnp.int32),
            jnp.asarray(tok_pos), jnp.asarray(ws),
            jnp.array([n - 1, 0], jnp.int32), kc, vc, jnp.asarray(pt),
            jnp.array([0, 0], jnp.int32), jnp.array([n, 0], jnp.int32),
            jnp.array([start + n, 0], jnp.int32), PAGE_SIZE, attn_impl="jnp")
        start += n

    np.testing.assert_allclose(np.asarray(logits[0]),
                               np.asarray(ref_logits[0]),
                               rtol=2e-4, atol=2e-4)
    assert int(jnp.argmax(logits[0])) == int(jnp.argmax(ref_logits[0]))
    live = slice(PAGE_SIZE, None)  # every page but the trash page
    for got, ref in ((kc, ref_kc), (vc, ref_vc)):
        np.testing.assert_allclose(np.asarray(got[:, live]),
                                   np.asarray(ref[:, live]),
                                   rtol=1e-5, atol=1e-5)
    assert np.abs(np.asarray(ref_kc[:, live])).sum() > 0


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
