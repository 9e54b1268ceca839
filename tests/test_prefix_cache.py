"""Automatic prefix caching (engine/prefix_cache.py): radix-tree KV
reuse with refcounted pages.

The load-bearing guarantees pinned here:
  - cache-on vs cache-off token streams are BYTE-IDENTICAL under greedy
    sampling (tiny llama on CPU and the fake backend), including
    repeat-penalty requests (the chunked tail seeds the penalty ring
    with the cached prefix) and a request cancelled mid-prefill whose
    pages were partially cached;
  - allocator exhaustion under a full cache triggers LRU eviction, not
    admission failure;
  - the tree + allocator invariants survive randomized
    insert/match/evict/cancel sequences (refcounts ≥ 0, no page both
    free and referenced, free + used + cached == num_pages - 1).
"""

import itertools
import random

import jax.numpy as jnp
import numpy as np
import pytest

from ollamamq_tpu.config import MODEL_CONFIGS, EngineConfig
from ollamamq_tpu.core import MQCore
from ollamamq_tpu.engine.engine import ModelRuntime, TPUEngine
from ollamamq_tpu.engine.fake import FakeEngine
from ollamamq_tpu.engine.kv_cache import PageAllocator
from ollamamq_tpu.engine.prefix_cache import PrefixCache
from ollamamq_tpu.engine.request import FinishReason, Request
from ollamamq_tpu.ops.sampling import SamplingParams
from testutil import collect

_IDS = itertools.count(1)

PS = 8  # page size for every runtime-level test here


def make_rt(prefix_cache: bool, **kw) -> ModelRuntime:
    defaults = dict(
        model="test-tiny", max_slots=4, num_pages=96, page_size=PS,
        max_pages_per_seq=16, max_new_tokens=8,
        decode_steps_per_iter=2, prefix_cache=prefix_cache,
    )
    defaults.update(kw)
    ecfg = EngineConfig(**defaults)
    rt = ModelRuntime("test-tiny", MODEL_CONFIGS["test-tiny"], ecfg,
                      dtype=jnp.float32)
    rt.tokenizer.eos_id = -1  # deterministic full-length streams
    return rt


def run_request(rt: ModelRuntime, core: MQCore, prompt, max_tokens=6,
                repeat_penalty=1.0):
    """Drive one request synchronously to completion; returns its ids."""
    req = Request(next(_IDS), "u", "test-tiny", list(prompt),
                  SamplingParams(max_tokens=max_tokens,
                                 repeat_penalty=repeat_penalty))
    req._inc_decode = rt.tokenizer.make_incremental_decoder()
    rt.pending_prefill.append(req)
    for _ in range(200):
        if any(r is req for r in rt.slot_req):
            break
        assert rt.step_ragged(core), "request stuck in admission"
    else:
        pytest.fail("request never installed")
    while any(r is req for r in rt.slot_req):
        rt.step_decode(core, k_steps=1)
    return list(req.generated_ids)


def pool_invariant(rt: ModelRuntime) -> None:
    a = rt.cache.alloc
    assert a.free_pages + a.used_pages + a.cached_pages == a.num_pages - 1
    assert a.used_pages >= 0
    if rt.cache.prefix_cache is not None:
        rt.cache.prefix_cache.check()


# -- radix tree unit behavior ----------------------------------------------

def test_tree_match_insert_pin_evict():
    alloc = PageAllocator(num_pages=32, page_size=4, max_pages_per_seq=8)
    pc = PrefixCache(4, alloc, model="unit")
    tokens = list(range(12))  # 3 full blocks
    pages = alloc.alloc_n(3)
    assert pc.insert(tokens, pages) == 3
    assert alloc.cached_pages == 3 and pc.cached_pages == 3
    pc.check()

    # Full-prompt query caps the match so ≥ 1 token stays uncached.
    nodes, got = pc.match(tokens)
    assert len(nodes) == 2
    # One extra token exposes all 3 blocks.
    nodes, got = pc.match(tokens + [99])
    assert got == pages

    # Pinned paths survive eviction; unpinned leaves do not.
    pc.pin(nodes[:2])  # pin blocks 0-1; block 2 is an unpinned leaf
    assert pc.evictable_pages == 1
    assert pc.evict(5) == 1  # only the leaf
    assert alloc.cached_pages == 2
    assert pc.evict(5) == 0  # everything left is pinned
    pc.release(nodes[:2])
    pc.check()

    # Duplicate insert: the tree keeps its copy, ours is freed.
    free_before = alloc.free_pages
    dup = alloc.alloc_n(2)
    assert pc.insert(tokens[:8], dup) == 0
    assert alloc.free_pages == free_before  # both duplicates returned
    pc.check()

    # LRU: the least-recently-touched leaf goes first.
    b1 = [100] * 4 + [101] * 4
    b2 = [200] * 4 + [201] * 4
    pc.insert(b1, alloc.alloc_n(2))
    pc.insert(b2, alloc.alloc_n(2))
    pc.pin(pc.match(b2 + [0])[0])  # touch b2's path
    pc.release(pc.match(b2 + [0])[0])
    assert pc.evict(1) == 1
    assert len(pc.match(b2 + [0])[0]) == 2  # b2 untouched by the sweep
    assert len(pc.match(b1 + [0])[0]) == 2  # b1 untouched too
    # The stalest leaf was the original tokens-tree's deepest block
    # (touched last by the duplicate insert, before b1/b2 existed).
    assert len(pc.match(tokens + [99])[0]) == 1
    # Flush reclaims every unreferenced page.
    remaining = pc.cached_pages
    assert pc.flush() == remaining
    assert pc.cached_pages == 0
    pc.check()
    assert alloc.free_pages + alloc.cached_pages == alloc.num_pages - 1


# -- correctness gate: cache on/off byte-identical (tiny llama) -------------

def test_identical_streams_cache_on_vs_off():
    core = MQCore(None)
    rt_off = make_rt(False)
    rt_on = make_rt(True)  # identical weights: same seed, same config

    rng = np.random.RandomState(7)
    prefix = rng.randint(3, 500, size=4 * PS).tolist()  # 4 full pages
    tail_a = rng.randint(3, 500, size=7).tolist()
    tail_b = rng.randint(3, 500, size=9).tolist()
    long_tail = rng.randint(3, 500, size=80).tolist()  # > largest bucket

    prompts = [
        prefix + tail_a,          # miss (populates the tree on rt_on)
        prefix + tail_b,          # hit: shared 4-page prefix
        prefix + tail_a,          # hit: longest match incl. private page
        rng.randint(3, 500, size=5).tolist(),  # short, below any match
        prefix + long_tail,       # hit + chunked tail (> largest bucket)
    ]
    for i, prompt in enumerate(prompts):
        ids_off = run_request(rt_off, core, prompt)
        ids_on = run_request(rt_on, core, prompt)
        assert ids_off == ids_on, f"prompt {i}: {ids_off} != {ids_on}"
        pool_invariant(rt_on)
    assert rt_on.cache.prefix_cache.hits >= 3
    assert rt_on.cache.prefix_cache.tokens_saved >= 3 * 4 * PS
    assert rt_off.cache.alloc.used_pages == 0  # everything reclaimed

    # Repeat-penalty streams must match too: the chunked tail seeds the
    # penalty ring with the cached prefix's last repeat_last_n tokens.
    pen_prompt = prefix + rng.randint(3, 500, size=6).tolist()
    ids_off = run_request(rt_off, core, pen_prompt, repeat_penalty=1.3)
    ids_on = run_request(rt_on, core, pen_prompt, repeat_penalty=1.3)
    assert ids_off == ids_on
    pool_invariant(rt_on)


def test_cancel_mid_prefill_with_partially_cached_pages():
    core = MQCore(None)
    # A 16-token dispatch budget so the 24-token tail needs TWO spans —
    # the cancel really lands mid-prefill.
    small = dict(max_batch_tokens=16, token_granule=8)
    rt_on = make_rt(True, **small)
    rt_off = make_rt(False, **small)
    rng = np.random.RandomState(13)
    base = rng.randint(3, 500, size=96).tolist()  # 12 full pages
    run_request(rt_on, core, base)  # populate the tree
    pool_invariant(rt_on)
    cached = rt_on.cache.prefix_cache.cached_pages
    assert cached == 12

    # A longer prompt sharing the cached prefix: admission pins 12 pages
    # and the 24-token tail rides the span path from the cached boundary.
    # Cancel it after the first span — pages partially written, prefix
    # pinned.
    victim = base + rng.randint(3, 500, size=24).tolist()
    req = Request(next(_IDS), "u", "test-tiny", victim,
                  SamplingParams(max_tokens=4))
    req._inc_decode = rt_on.tokenizer.make_incremental_decoder()
    rt_on.pending_prefill.append(req)
    assert rt_on.step_ragged(core)  # hit: pinned, first tail span runs
    assert rt_on.cache.prefix_cache.hits >= 1
    assert req in rt_on.chunking
    assert req._chunk_base == 96 and 96 < req._chunk_pos < len(victim)
    assert rt_on.cache.prefix_cache.stats()["pinned_pages"] == 12
    req.cancelled.set()
    assert not rt_on.step_ragged(core)  # reaped, nothing left to dispatch:
    #                                     pins released, tail freed
    assert req not in rt_on.chunking
    assert not rt_on.reserved_slots
    pool_invariant(rt_on)
    assert rt_on.cache.prefix_cache.cached_pages == cached  # nothing leaked in
    assert rt_on.cache.prefix_cache.stats()["pinned_pages"] == 0

    # The same prompt run fresh still matches the cache-off stream.
    ids_on = run_request(rt_on, core, victim)
    ids_off = run_request(rt_off, core, base)  # warm rt_off compile path
    ids_off = run_request(rt_off, core, victim)
    assert ids_on == ids_off
    pool_invariant(rt_on)


# -- eviction under allocator pressure -------------------------------------

def test_full_cache_evicts_instead_of_failing_admission():
    core = MQCore(None)
    rt = make_rt(True, num_pages=20, max_pages_per_seq=8, max_new_tokens=4)
    rng = np.random.RandomState(3)
    # Two finished prompts leave 12 pages in the tree (6 full pages each);
    # the 19-page pool now has ≤ 7 free.
    for _ in range(2):
        run_request(rt, core, rng.randint(3, 500, size=48).tolist(),
                    max_tokens=2)
    pool_invariant(rt)
    assert rt.cache.alloc.cached_pages == 12
    assert rt.cache.alloc.free_pages < 8
    assert rt.has_capacity("generate")  # evictable pages count as capacity
    # A fresh 56-token prompt needs 8 pages: admission must evict, not
    # fail or wait forever.
    ids = run_request(rt, core, rng.randint(3, 500, size=56).tolist(),
                      max_tokens=2)
    assert len(ids) == 2
    assert rt.cache.prefix_cache.evictions > 0
    pool_invariant(rt)


# -- property/fuzz: tree + allocator invariants ----------------------------

def test_fuzz_radix_tree_allocator_invariants():
    rng = random.Random(0)
    ps = 4
    num_pages = 48
    alloc = PageAllocator(num_pages=num_pages, page_size=ps,
                          max_pages_per_seq=10)
    pc = PrefixCache(ps, alloc, model="fuzz")
    live = []  # {tokens, nodes, pages, shared}

    def invariants():
        pc.check()
        used = sum(len(e["pages"]) - e["shared"] for e in live)
        assert alloc.free_pages + used + alloc.cached_pages == num_pages - 1
        tree_pages = pc.pages()
        free = set(alloc._free)
        assert not (free & tree_pages)
        private = []
        for e in live:
            private.extend(e["pages"][e["shared"]:])
        assert len(private) == len(set(private))  # no double ownership
        assert not (set(private) & tree_pages)
        assert not (set(private) & free)

    def admit():
        # Small alphabet of blocks => heavy prefix sharing.
        n_tokens = rng.randrange(ps, 9 * ps)
        tokens = []
        for _ in range(-(-n_tokens // ps)):
            tokens.extend([rng.randrange(3)] * ps)
        tokens = tokens[:n_tokens]
        nodes, shared_pages = pc.match(tokens)
        pc.pin(nodes)
        need = alloc.pages_needed(n_tokens + 1) - len(nodes)
        tail = alloc.alloc_n(need, held=len(nodes))
        if tail is None:
            short = need - alloc.free_pages
            if short > 0 and pc.evict(short) > 0:
                tail = alloc.alloc_n(need, held=len(nodes))
        if tail is None:
            pc.release(nodes)
            return
        live.append({"tokens": tokens, "nodes": nodes,
                     "pages": list(shared_pages) + tail,
                     "shared": len(nodes)})

    def retire(insert: bool):
        if not live:
            return
        e = live.pop(rng.randrange(len(live)))
        keep = e["shared"]
        if insert:  # finished request: engine's _release_slot_pages path
            full = min(len(e["tokens"]) // ps, len(e["pages"]))
            if full > keep:
                pc.insert(e["tokens"], e["pages"][:full])
                keep = full
        alloc.free(e["pages"][keep:])
        pc.release(e["nodes"])

    def extend():
        if not live:
            return
        e = rng.choice(live)
        alloc.extend(e["pages"], len(e["pages"]) * ps + rng.randrange(8))

    ops = [admit, lambda: retire(True), lambda: retire(False),
           lambda: pc.evict(rng.randrange(1, 4)), extend,
           lambda: pc.flush() if rng.random() < 0.2 else None]
    for i in range(600):
        rng.choice(ops)()
        invariants()
    while live:
        retire(True)
        invariants()
    pc.flush()
    invariants()
    assert alloc.free_pages + alloc.cached_pages == num_pages - 1


# -- engine-thread integration + fake backend ------------------------------

def engine_streams(prefix_cache: bool, prompts, fake=False):
    ecfg = EngineConfig(model="test-tiny", max_slots=4, num_pages=96,
                        page_size=PS, max_pages_per_seq=16,
                        max_new_tokens=6,
                        decode_steps_per_iter=2, prefix_cache=prefix_cache)
    if fake:
        eng = FakeEngine(ecfg, models={"test-tiny": None},
                         blocklist_path=None)
    else:
        eng = TPUEngine(ecfg, models={"test-tiny": None},
                        blocklist_path=None, dtype=jnp.float32)
    eng.start()
    out = []
    try:
        for prompt in prompts:
            rid = eng.core.enqueue("u", "127.0.0.1", "test-tiny")
            req = Request(rid, "u", "test-tiny", list(prompt),
                          SamplingParams(max_tokens=6))
            eng.submit(req)
            items = collect(req, timeout=120)
            assert items[-1].kind == "done", getattr(items[-1], "error", None)
            out.append(list(req.generated_ids))
    finally:
        eng.stop()
    return out, eng


def test_engine_loop_cache_on_off_identical_and_debug_api():
    rng = np.random.RandomState(23)
    prefix = rng.randint(3, 500, size=3 * PS).tolist()
    prompts = [prefix + [7, 8, 9], prefix + [11, 12], prefix + [7, 8, 9]]
    off, _ = engine_streams(False, prompts)
    on, eng = engine_streams(True, prompts)
    assert off == on
    stats = eng.prefix_cache_stats()
    assert stats["enabled"]
    ms = stats["models"]["test-tiny"]
    assert ms["hits"] >= 1 and ms["misses"] >= 1
    assert ms["cached_pages"] > 0
    # Flush on a stopped engine runs inline (call_on_loop fallback).
    freed = eng.prefix_cache_flush()
    assert freed == ms["cached_pages"]
    assert eng.prefix_cache_stats()["models"]["test-tiny"]["cached_pages"] == 0


def test_fake_backend_cache_flag_is_inert():
    prompts = [b"hello world", b"hello there"]
    prompts = [list(p) for p in prompts]
    off, _ = engine_streams(False, prompts, fake=True)
    on, eng = engine_streams(True, prompts, fake=True)
    assert off == on
    # Fake runtimes hold no KV: the cache surface reports disabled.
    assert eng.prefix_cache_stats() == {"enabled": False, "models": {}}
    assert eng.prefix_cache_flush() == 0


def test_debug_prefix_cache_http_endpoint():
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from ollamamq_tpu.server.app import Server

    async def main():
        eng = FakeEngine(EngineConfig(model="test-tiny", max_slots=4),
                         models={"test-tiny": None}, blocklist_path=None)
        eng.start()
        cl = TestClient(TestServer(Server(eng, timeout_s=10).build_app()))
        await cl.start_server()
        try:
            r = await cl.get("/debug/prefix_cache")
            assert r.status == 200
            body = await r.json()
            assert body == {"enabled": False, "models": {}}
            r = await cl.post("/debug/prefix_cache")
            assert r.status == 200
            assert (await r.json()) == {"status": "success",
                                        "freed_pages": 0}
        finally:
            await cl.close()
            eng.stop()

    asyncio.run(main())


def test_prefix_cache_metrics_exported():
    from ollamamq_tpu.telemetry import schema as tm

    core = MQCore(None)
    rt = make_rt(True)
    rng = np.random.RandomState(5)
    prompt = rng.randint(3, 500, size=3 * PS + 4).tolist()
    run_request(rt, core, prompt, max_tokens=2)
    run_request(rt, core, prompt, max_tokens=2)
    ratio = tm.PREFIX_CACHE_HIT_RATIO.labels(model="test-tiny").value
    assert 0.0 < ratio <= 1.0
    assert tm.PREFIX_CACHE_PAGES.labels(model="test-tiny").value >= 3
    assert tm.PREFIX_CACHE_TOKENS_SAVED.labels(model="test-tiny").value \
        >= 3 * PS
