"""Graceful degradation under load: preemption with recompute, bounded
admission & shedding, deadlines, retry containment, fault injection.

Every path here is driven by the deterministic fault plan
(ollamamq_tpu/testing/faults.py) rather than real resource races, so the
chaos is replayable: the same plan fires the same faults in the same
order on every run.
"""

import asyncio
import json
import time

import pytest

from ollamamq_tpu.config import EngineConfig
from ollamamq_tpu.engine.request import FinishReason
from ollamamq_tpu.ops.sampling import SamplingParams
from ollamamq_tpu.testing.faults import (DeviceLostError, FaultInjected,
                                         FaultPlan, FaultPlanError)
from testutil import _text, collect

TINY = dict(model="test-tiny", max_slots=2, num_pages=64, page_size=8,
            max_pages_per_seq=16,
            decode_steps_per_iter=2)


def _tpu_engine(plan=None, **over):
    import jax.numpy as jnp

    from ollamamq_tpu.engine.engine import TPUEngine

    cfg = dict(TINY)
    cfg.update(over)
    eng = TPUEngine(EngineConfig(fault_plan=plan, **cfg),
                    models={"test-tiny": None}, blocklist_path=None,
                    dtype=jnp.float32)
    eng.start()
    return eng


def _run(eng, user, prompt="the quick brown fox jumps", max_tokens=10,
         deadline_ms=0.0):
    tok = eng.resolve_runtime("test-tiny").tokenizer
    req = eng.enqueue_request(
        user, "", "test-tiny", prompt_tokens=tok.encode(prompt),
        sampling=SamplingParams(max_tokens=max_tokens,
                                deadline_ms=deadline_ms))
    return req


# ---------------------------------------------------------------- fault plan
def test_fault_plan_schema_rejects_malformed(tmp_path):
    bad = [
        {"faults": "nope"},
        {"faults": []},
        {"faults": [{"site": "warp", "kind": "exception", "at": [1]}]},
        {"faults": [{"site": "decode", "kind": "explode", "at": [1]}]},
        {"faults": [{"site": "decode", "kind": "exception"}]},
        {"faults": [{"site": "decode", "kind": "exception", "at": [0]}]},
        {"faults": [{"site": "decode", "kind": "exception", "at": [1],
                     "p": 0.5}]},
        {"faults": [{"site": "decode", "kind": "exception", "at": [1],
                     "bogus_key": 1}]},
        {"faults": [{"site": "decode", "kind": "slow", "at": [1]}]},
        {"seed": "x", "faults": [{"site": "decode", "kind": "exception",
                                  "at": [1]}]},
    ]
    for d in bad:
        with pytest.raises(FaultPlanError):
            FaultPlan.from_dict(d)
    # File-level failures: unreadable and non-JSON both fail fast.
    with pytest.raises(FaultPlanError):
        FaultPlan.load(str(tmp_path / "missing.json"))
    p = tmp_path / "junk.json"
    p.write_text("{not json")
    with pytest.raises(FaultPlanError):
        FaultPlan.load(str(p))
    # And a valid file loads.
    good = tmp_path / "plan.json"
    good.write_text(json.dumps({"seed": 3, "faults": [
        {"site": "ragged", "kind": "exception", "at": [1]}]}))
    assert FaultPlan.load(str(good)).stats()["injected"] == 0


def test_fault_plan_cli_flag_fails_fast(tmp_path):
    from ollamamq_tpu.cli import main

    p = tmp_path / "bad.json"
    p.write_text(json.dumps({"faults": [{"site": "nope"}]}))
    assert main(["--fault-plan", str(p), "--no-tui"]) == 2


def test_fault_plan_device_loss_heals():
    plan = FaultPlan([{"site": "decode", "kind": "device_loss", "at": [1],
                       "heal_after_s": 0.05}])
    with pytest.raises(DeviceLostError):
        plan.check("decode")
    with pytest.raises(DeviceLostError):
        plan.check("ragged")  # a lost device fails EVERY site
    assert plan.blocked("extend")  # ...and can't grow allocations
    time.sleep(0.06)
    plan.check("decode")  # healed


# ------------------------------------------------- preemption with recompute
@pytest.mark.parametrize("prefix_cache", [False, True],
                         ids=["cache-off", "cache-on"])
def test_preemption_round_trip_byte_identical(prefix_cache):
    """A preempted+recomputed greedy request produces EXACTLY the token
    stream an unloaded run produces — preemption must be invisible to
    the client beyond latency."""
    eng = _tpu_engine(prefix_cache=prefix_cache)
    try:
        base_items = collect(_run(eng, "base"))
        base_rt = eng.runtimes["test-tiny"]
    finally:
        eng.stop()
    base_text = _text(base_items)
    assert base_items[-1].kind == "done" and base_text

    # Same engine shape, but the 3rd decode-time page growth "fails":
    # the lone request preempts ITSELF, requeues to the front, replays
    # prompt+generated through prefill, and continues.
    plan = FaultPlan([{"site": "extend", "kind": "alloc_fail", "at": [3]}])
    eng = _tpu_engine(plan=plan, prefix_cache=prefix_cache)
    try:
        req = _run(eng, "victim")
        items = collect(req)
        rt = eng.runtimes["test-tiny"]
        assert req.preemptions >= 1
        assert rt.preempt_count >= 1
        if prefix_cache:
            # The replay re-admission walks the tree seeded by the
            # preemption's page insert: recompute is mostly cached.
            assert rt.cache.prefix_cache.stats()["hits"] >= 1
        # Invariant: no page leaked across preempt/replay.
        assert rt.cache.alloc.used_pages == 0
    finally:
        eng.stop()
    assert items[-1].kind == "done", items[-1].error
    assert _text(items) == base_text
    # (an item carries the ids ONE step gave the stream: compare the ids)
    assert [t for i in items for t in i.token_ids] == \
        [t for i in base_items for t in i.token_ids]
    del base_rt


def test_kv_exhausted_explicit_when_preemption_disabled():
    """Satellite: decode-time page exhaustion must NEVER report a silent
    LENGTH — with preemption off it errors with the distinct
    kv_exhausted done_reason and counts into ollamamq_shed_total."""
    from ollamamq_tpu.telemetry import schema as tm

    shed0 = sum(c.value for (labels, c) in tm.SHED_TOTAL.series()
                if "kv_exhausted" in labels)
    plan = FaultPlan([{"site": "extend", "kind": "alloc_fail", "at": [3]}])
    eng = _tpu_engine(plan=plan, preempt=False)
    try:
        req = _run(eng, "u")
        items = collect(req)
    finally:
        eng.stop()
    assert items[-1].kind == "error"
    assert items[-1].finish_reason == FinishReason.KV_EXHAUSTED
    assert "exhausted" in items[-1].error
    shed1 = sum(c.value for (labels, c) in tm.SHED_TOTAL.series()
                if "kv_exhausted" in labels)
    assert shed1 == shed0 + 1


# ------------------------------------------------ bounded admission/shedding
def test_queue_full_returns_429_and_503_with_retry_after():
    from aiohttp.test_utils import TestClient, TestServer

    from ollamamq_tpu.engine.fake import FakeEngine
    from ollamamq_tpu.server.app import Server

    async def main():
        eng = FakeEngine(
            EngineConfig(model="test-tiny", max_slots=1, max_queued=2,
                         max_queued_per_user=1),
            models={"test-tiny": None}, token_latency_s=0.05)
        eng.start()
        cl = TestClient(TestServer(Server(eng, timeout_s=60).build_app()))
        await cl.start_server()
        try:
            async def fire(user):
                return asyncio.create_task(cl.post(
                    "/api/generate",
                    json={"model": "test-tiny", "prompt": "x",
                          "stream": False},
                    headers={"X-User-ID": user}))

            # One running (slot), one queued for alice: alice is at her
            # per-user cap of 1.
            t1 = await fire("alice")
            await asyncio.sleep(0.2)
            t2 = await fire("alice")
            await asyncio.sleep(0.2)
            r = await (await fire("alice"))
            assert r.status == 429, await r.text()
            assert int(r.headers["Retry-After"]) >= 1
            body = await r.json()
            assert "cap" in body["error"]
            # Global cap (2): bob fills the second queue seat, carol is
            # shed with 503.
            t3 = await fire("bob")
            await asyncio.sleep(0.2)
            r = await (await fire("carol"))
            assert r.status == 503, await r.text()
            assert int(r.headers["Retry-After"]) >= 1
            for t in (t1, t2, t3):
                resp = await t
                assert resp.status == 200
                await resp.read()
            from ollamamq_tpu.telemetry import schema as tm

            reasons = {labels[0] for labels, c in tm.SHED_TOTAL.series()
                       if c.value > 0}
            assert {"queue_full", "user_queue_full"} <= reasons
            assert eng.shed_counts["queue_full"] >= 1
            assert eng.shed_counts["user_queue_full"] >= 1
        finally:
            await cl.close()
            eng.stop()

    asyncio.run(main())


# ------------------------------------------------------------------ deadline
def test_expired_queued_request_drops_before_prefill():
    """A request whose deadline expires while it waits in queue is
    dropped at admission — no prefill is ever dispatched for it — and
    the client gets the explicit deadline reason."""
    from ollamamq_tpu.engine.fake import FakeEngine
    from ollamamq_tpu.telemetry import schema as tm

    drops0 = sum(c.value for _, c in tm.DEADLINE_DROPS_TOTAL.series())
    eng = FakeEngine(EngineConfig(model="test-tiny", max_slots=1),
                     models={"test-tiny": None}, token_latency_s=0.05)
    eng.start()
    try:
        blocker = _run(eng, "hog", max_tokens=16)  # holds the only slot
        time.sleep(0.15)  # let it admit
        doomed = _run(eng, "late", max_tokens=4, deadline_ms=50.0)
        items = collect(doomed)
        assert items[-1].kind == "error"
        assert items[-1].finish_reason == FinishReason.DEADLINE
        # Dropped BEFORE any compute: its trace never saw a prefill.
        names = [e[0] for e in doomed.trace.events]
        assert "prefill" not in names and "first_token" not in names
        assert not _text(items)
        collect(blocker)
        drops1 = sum(c.value for _, c in tm.DEADLINE_DROPS_TOTAL.series())
        assert drops1 == drops0 + 1
    finally:
        eng.stop()


def test_deadline_header_rides_the_http_surface():
    from aiohttp.test_utils import TestClient, TestServer

    from ollamamq_tpu.engine.fake import FakeEngine
    from ollamamq_tpu.server.app import Server

    async def main():
        eng = FakeEngine(EngineConfig(model="test-tiny", max_slots=1),
                         models={"test-tiny": None}, token_latency_s=0.05)
        eng.start()
        cl = TestClient(TestServer(Server(eng, timeout_s=60).build_app()))
        await cl.start_server()
        try:
            r = await cl.post("/api/generate", json={
                "model": "test-tiny", "prompt": "x", "stream": False},
                headers={"X-Deadline-Ms": "junk"})
            assert r.status == 400
            # Occupy the slot, then an impossible deadline => 504 with
            # the explicit deadline reason, not a generic 500.
            hog = asyncio.create_task(cl.post(
                "/api/generate", json={"model": "test-tiny", "prompt": "x",
                                       "stream": False},
                headers={"X-User-ID": "hog"}))
            await asyncio.sleep(0.2)
            r = await cl.post("/api/generate", json={
                "model": "test-tiny", "prompt": "x", "stream": False},
                headers={"X-User-ID": "late", "X-Deadline-Ms": "40"})
            assert r.status == 504, await r.text()
            assert "deadline" in (await r.json())["error"]
            resp = await hog
            assert resp.status == 200
        finally:
            await cl.close()
            eng.stop()

    asyncio.run(main())


# ------------------------------------------------------- retry / containment
def test_injected_prefill_fault_retries_and_succeeds():
    from ollamamq_tpu.telemetry import schema as tm

    # "ragged" is the default mode's prefill-path dispatch site (the
    # mixed token-budget dispatch replaced batched prefill).
    plan = FaultPlan([{"site": "ragged", "kind": "exception", "at": [1]}])
    eng = _tpu_engine(plan=plan)
    try:
        req = _run(eng, "u")
        items = collect(req)
        rt = eng.runtimes["test-tiny"]
        assert req.retries == 1
        assert rt.retry_count == 1
        assert sum(c.value for _, c in tm.RETRIES_TOTAL.series()) >= 1
    finally:
        eng.stop()
    assert items[-1].kind == "done", items[-1].error
    assert _text(items)
    names = [e[0] for e in req.trace.events]
    assert "retry" in names


def test_repeated_fault_poisons_engine_keeps_serving():
    """Two consecutive injected prefill faults exhaust the retry budget:
    the request is poisoned with an explicit error, and the NEXT request
    (fault plan spent) serves normally — no crash loop."""
    plan = FaultPlan([{"site": "ragged", "kind": "exception", "at": [1, 2]}])
    eng = _tpu_engine(plan=plan)
    try:
        poisoned = collect(_run(eng, "bad"), timeout=60)
        assert poisoned[-1].kind == "error"
        assert "poisoned" in poisoned[-1].error
        survivor = collect(_run(eng, "good"))
        assert survivor[-1].kind == "done", survivor[-1].error
        assert _text(survivor)
        snap = eng.core.snapshot()
        assert snap["users"]["bad"]["dropped"] == 1
        assert snap["users"]["good"]["processed"] == 1
        assert sum(u["processing"] for u in snap["users"].values()) == 0
    finally:
        eng.stop()


# --------------------------------------------- server timeout leak (fixed)
def test_server_timeout_cancels_engine_side():
    """Satellite: the per-request timeout must cancel the engine-side
    request (freeing its slot) — not just yield an error item while the
    generation keeps burning resources."""
    from aiohttp.test_utils import TestClient, TestServer

    from ollamamq_tpu.engine.fake import FakeEngine
    from ollamamq_tpu.server.app import Server

    async def main():
        # 16 fake tokens at 80 ms each = ~1.3 s of generation vs a
        # 0.3 s server timeout.
        eng = FakeEngine(EngineConfig(model="test-tiny", max_slots=2),
                         models={"test-tiny": None}, token_latency_s=0.08)
        eng.start()
        cl = TestClient(TestServer(Server(eng, timeout_s=0.3).build_app()))
        await cl.start_server()
        try:
            t0 = time.monotonic()
            r = await cl.post("/api/generate", json={
                "model": "test-tiny", "prompt": "x", "stream": False})
            assert r.status == 500
            assert "timeout" in (await r.json())["error"]
            # The engine-side request must be reaped well before the
            # generation would have finished on its own.
            rt = eng.runtimes["test-tiny"]
            while rt.active and time.monotonic() - t0 < 1.0:
                await asyncio.sleep(0.02)
            assert not rt.active, "slot still held after client timeout"
            snap = eng.core.snapshot()
            assert sum(u["processing"] for u in snap["users"].values()) == 0
        finally:
            await cl.close()
            eng.stop()

    asyncio.run(main())


# -------------------------------------------------------- preemption storm
def test_preempt_storm_alert_fires_and_resolves(monkeypatch):
    from ollamamq_tpu.engine import health as health_mod
    from ollamamq_tpu.engine.health import HealthMonitor
    from ollamamq_tpu.telemetry.slo import AlertManager

    class Stub:
        def __init__(self):
            self.alerts = AlertManager()
            self._n = 0

        def preemption_count(self):
            return self._n

    eng = Stub()
    mon = HealthMonitor(eng)
    monkeypatch.setattr(health_mod, "PREEMPT_STORM_PER_MIN", 10.0)
    # Two samples 1s apart with +2 preemptions => 120/min => storm.
    now = time.monotonic()
    mon._preempt_samples = [(now - 1.0, 0)]
    eng._n = 2
    mon._check_preempt_storm()
    assert any(a.name == "preempt_storm" for a in eng.alerts.active())
    # Rate decays (no new preemptions over a long window) => resolves.
    mon._preempt_samples = [(now - 30.0, 2)]
    mon._check_preempt_storm()
    assert not any(a.name == "preempt_storm" for a in eng.alerts.active())


# ------------------------------------------------------------- embed cancel
def test_cancel_finds_pending_embed_requests():
    """engine.cancel's holder scan must cover pending_embed — a timed-out
    embed on a generative runtime previously leaked until served."""
    import jax.numpy as jnp

    from ollamamq_tpu.engine.engine import TPUEngine

    eng = TPUEngine(EngineConfig(**TINY), models={"test-tiny": None},
                    blocklist_path=None, dtype=jnp.float32)
    # NOT started: the request stays parked in pending_embed.
    rt = eng.runtimes["test-tiny"]
    req = eng.enqueue_request("u", "", "test-tiny", prompt_tokens=[1, 2, 3],
                              kind="embed")
    rt.submit(req)
    eng.pending.pop(req.req_id, None)  # simulate post-admission state
    eng.cancel(req.req_id)
    assert req.cancelled.is_set()
