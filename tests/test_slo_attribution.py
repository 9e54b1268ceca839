"""Latency attribution + SLO burn-rate alerting + stall watchdog +
diagnostics bundle (the flight-recorder stack on telemetry/).

Pins the acceptance contract: /debug/requests/{id} returns a phase
timeline whose phases sum to wall-clock e2e within 5% (streamed AND
cancelled requests); an injected engine-step stall on the fake backend
flips /health to degraded, fires a watchdog alert visible in /metrics,
and surfaces through the TUI's alert feed; SLO violations burn the
budget and fire/resolve multi-window alerts.
"""

import asyncio
import tempfile
import threading
import time

import pytest
from aiohttp.test_utils import TestClient, TestServer

from ollamamq_tpu.config import EngineConfig
from ollamamq_tpu.engine.fake import FakeEngine
from ollamamq_tpu.engine.health import HealthMonitor
from ollamamq_tpu.server.app import Server, _redact
from ollamamq_tpu.telemetry import attribution
from ollamamq_tpu.telemetry.slo import AlertManager, Objective, SLOEngine
from ollamamq_tpu.telemetry.tracing import Tracer


# ------------------------------------------------------------ attribution
def test_phase_totals_sum_to_e2e_exactly():
    tracer = Tracer(capacity=4)
    tr = tracer.begin(1, "u", "m")
    time.sleep(0.005)
    tr.event("admit")
    tr.event("place")
    time.sleep(0.005)
    tr.event("prefill")
    time.sleep(0.01)
    tr.event("first_token")
    time.sleep(0.005)
    tr.finish("stop")
    tl = attribution.timeline(tr)
    assert tl["state"] == "stop"
    total = sum(tl["phases_ms"].values())
    # Contiguous spans: the tolerance only absorbs rounding.
    assert abs(total - tl["e2e_ms"]) < 0.05, tl
    assert set(tl["phases_ms"]) <= set(attribution.PHASES)
    assert tl["phases_ms"]["prefill"] >= 9.0
    # Events are relative to enqueue and monotonic.
    ts = [e["t_ms"] for e in tl["events"]]
    assert ts == sorted(ts) and ts[0] == 0.0


def test_unknown_event_lands_in_other_and_inflight_has_current_phase():
    tracer = Tracer(capacity=4)
    tr = tracer.begin(2, "u", "m")
    tr.event("admit")
    tr.event("totally_new_event")
    time.sleep(0.005)
    tl = attribution.timeline(tr)
    assert tl["state"] == "inflight"
    assert tl["current_phase"] == "other"
    assert tl["phase_age_ms"] >= 4.0
    assert "other" in tl["phases_ms"]
    # In-flight too: phases (up to now) sum to e2e-so-far.
    assert abs(sum(tl["phases_ms"].values()) - tl["e2e_ms"]) < 0.05
    tr.finish("cancelled")


def test_every_engine_event_is_mapped():
    """The attribution table knows every event name the engine emits —
    grep the engine sources for trace_event calls and check coverage."""
    import os
    import re

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    names = set()
    for fname in ("engine/engine.py", "engine/fake.py", "engine/spmd.py"):
        with open(os.path.join(repo, "ollamamq_tpu", fname)) as f:
            names |= set(re.findall(r'trace_event\(\s*"([a-z_]+)"', f.read()))
    # Tracer-internal events:
    names |= {"enqueue"}
    unmapped = {n for n in names if attribution.phase_of(n) == "other"}
    assert not unmapped, f"events not in attribution.EVENT_PHASE: {unmapped}"


def test_request_phase_histogram_observed_on_finish():
    from ollamamq_tpu.telemetry import schema as tm

    child = tm.REQUEST_PHASE_MS.labels(model="attr-test", phase="decode")
    before = child.count
    tracer = Tracer(capacity=4)
    tr = tracer.begin(3, "u", "attr-test")
    tr.event("first_token")
    time.sleep(0.002)
    tr.finish("stop")
    assert child.count == before + 1


# ------------------------------------------------------------------- slo
def test_burn_rate_math():
    obj = Objective("ttft", threshold_ms=100.0, target=0.99)
    now = 1000.0
    for _ in range(90):
        obj.record(50.0, now=now)   # good
    for _ in range(10):
        obj.record(500.0, now=now)  # bad
    # 10% bad over a 1% budget = burn 10x.
    assert abs(obj.burn_rate(60.0, now=now + 1) - 10.0) < 1e-6
    # Outside the window: no data, burn 0.
    assert obj.burn_rate(60.0, now=now + 3000) == 0.0


def test_slo_multiwindow_fire_and_resolve():
    am = AlertManager()
    slo = SLOEngine(am, ttft_ms=10.0, target=0.9,
                    windows=(("fast", 10.0, 3.0, 2.0, "page"),))
    now = 5000.0
    for _ in range(10):
        slo.record("ttft", 100.0)  # all bad -> burn 10x budget
    slo.evaluate(now=time.monotonic())
    names = [a.name for a in am.active()]
    assert "slo_ttft_burn_fast" in names
    assert am.degraded()
    # Recovery: the short window goes clean -> resolve even though the
    # long window still remembers the burn.
    obj = slo.objectives["ttft"]
    obj.counts.record(good=1000, now=time.monotonic())
    time.sleep(0)
    slo.evaluate(now=time.monotonic())
    assert not am.degraded(), [a.to_dict() for a in am.active()]
    # The resolved alert moved to history.
    assert any(h["name"] == "slo_ttft_burn_fast" for h in am.history())


def test_alert_manager_transitions():
    am = AlertManager()
    assert am.fire("x", "page", "first") is True
    assert am.fire("x", "page", "updated") is False  # refresh, no re-fire
    assert am.active()[0].message == "updated"
    assert am.resolve("x") is True
    assert am.resolve("x") is False
    assert not am.degraded()


# ----------------------------------------------------------------- redact
def test_bundle_redaction():
    out = _redact({
        "hf_token": "secret123",
        "nested": {"api_key": "k", "ok_value": 5},
        "list": [{"password": "p"}],
        "checkpoint": "/data/model.safetensors",
    })
    assert out["hf_token"] == "[REDACTED]"
    assert out["nested"]["api_key"] == "[REDACTED]"
    assert out["nested"]["ok_value"] == 5
    assert out["list"][0]["password"] == "[REDACTED]"
    assert out["checkpoint"] == "/data/model.safetensors"


# ------------------------------------------------------------------- e2e
def _serve(fn, *, token_latency_s=0.0, ecfg=None):
    async def main():
        with tempfile.TemporaryDirectory() as tmp:
            eng = FakeEngine(
                ecfg or EngineConfig(model="test-tiny", max_slots=8),
                models={"test-tiny": None},
                blocklist_path=f"{tmp}/blocked_items.json",
                token_latency_s=token_latency_s,
            )
            eng.start()
            server = Server(eng, timeout_s=30)
            cl = TestClient(TestServer(server.build_app()))
            cl.engine = eng
            await cl.start_server()
            try:
                await fn(cl)
            finally:
                await cl.close()
                eng.stop()

    asyncio.run(main())


async def _drain_http(resp):
    async for _ in resp.content:
        pass


def test_debug_requests_timeline_sums_streamed():
    """Acceptance: a streamed request's phases sum to wall-clock e2e
    within 5% on /debug/requests/{id}."""
    async def run(cl):
        r = await cl.post("/api/generate", json={
            "model": "test-tiny", "prompt": "hello world", "stream": True,
            "options": {"num_predict": 8},
        }, headers={"X-User-ID": "alice"})
        assert r.status == 200
        await _drain_http(r)
        r = await cl.get("/debug/requests")
        assert r.status == 200
        body = await r.json()
        assert body["inflight"] == []
        row = next(rw for rw in body["recent"] if rw["user"] == "alice")
        r = await cl.get(f"/debug/requests/{row['req_id']}")
        assert r.status == 200
        tl = await r.json()
        assert tl["state"] in ("length", "stop")
        total = sum(tl["phases_ms"].values())
        assert abs(total - tl["e2e_ms"]) <= max(0.05 * tl["e2e_ms"], 0.5), tl
        # The lifecycle chain is present and decode got the bulk.
        names = [e["name"] for e in tl["events"]]
        for must in ("enqueue", "admit", "place", "prefill", "first_token"):
            assert must in names, names
        assert "decode" in tl["phases_ms"]

    _serve(run)


@pytest.mark.parametrize("path,body", [
    ("/api/generate", {"prompt": "hello world",
                       "options": {"num_predict": 4}}),
    ("/api/chat", {"messages": [{"role": "user", "content": "hello"}],
                   "options": {"num_predict": 4}}),
    ("/v1/chat/completions", {"messages": [{"role": "user",
                                            "content": "hello"}],
                              "max_tokens": 4}),
    ("/v1/completions", {"prompt": "hello world", "max_tokens": 4}),
])
def test_ingress_phase_opens_the_timeline_at_the_handler(path, body):
    """PR 24: the generate/chat/OpenAI handlers time their own entry, so
    a request's trace opens with `ingress` (JSON parse, templating,
    tokenisation) followed by `enqueue`; the phases, ingress included,
    still sum to an end-to-end that now starts at the handler, and the
    phase reaches ollamamq_request_phase_ms."""
    from ollamamq_tpu.telemetry import schema as tm

    def observed():
        return tm.REQUEST_PHASE_MS.labels(model="test-tiny",
                                          phase="ingress").count

    async def run(cl):
        before = observed()
        t0 = time.monotonic()
        r = await cl.post(path, json={"model": "test-tiny", "stream": False,
                                      **body},
                          headers={"X-User-ID": "ingrid"})
        wall_ms = (time.monotonic() - t0) * 1e3
        assert r.status == 200, await r.text()
        body_ = await (await cl.get("/debug/requests")).json()
        row = next(rw for rw in body_["recent"] if rw["user"] == "ingrid")
        tl = await (await cl.get(f"/debug/requests/{row['req_id']}")).json()
        names = [e["name"] for e in tl["events"]]
        assert names[:2] == ["ingress", "enqueue"], names
        assert tl["events"][0]["t_ms"] == 0.0
        assert tl["phases_ms"]["ingress"] > 0.0
        assert tl["phases_ms"]["ingress"] == tl["events"][1]["t_ms"]
        total = sum(tl["phases_ms"].values())
        assert abs(total - tl["e2e_ms"]) <= max(0.05 * tl["e2e_ms"], 0.5), tl
        assert tl["e2e_ms"] <= wall_ms   # the handler's, not the client's
        assert observed() == before + 1

    _serve(run)


def test_debug_requests_timeline_sums_cancelled():
    """Acceptance: a cancelled (client-gone mid-stream) request's
    timeline also closes cleanly and sums within tolerance."""
    async def run(cl):
        resp = await cl.post("/api/generate", json={
            "model": "test-tiny", "prompt": "hello", "stream": True,
            "options": {"num_predict": 10_000},
        }, headers={"X-User-ID": "bob"})
        assert resp.status == 200
        await resp.content.read(16)  # a few chunks, then walk away
        resp.close()
        # The engine notices the disconnect and cancels.
        deadline = time.monotonic() + 20
        tl = None
        while time.monotonic() < deadline:
            r = await cl.get("/debug/requests?recent=10")
            body = await r.json()
            done = [rw for rw in body["recent"] if rw["user"] == "bob"]
            if done and done[0]["state"] == "cancelled":
                r = await cl.get(f"/debug/requests/{done[0]['req_id']}")
                tl = await r.json()
                break
            await asyncio.sleep(0.05)
        assert tl is not None, "cancelled request never reached the ring"
        total = sum(tl["phases_ms"].values())
        assert abs(total - tl["e2e_ms"]) <= max(0.05 * tl["e2e_ms"], 0.5), tl

    _serve(run, token_latency_s=0.02)


def test_debug_requests_unknown_id_404s():
    async def run(cl):
        r = await cl.get("/debug/requests/424242")
        assert r.status == 404
        r = await cl.get("/debug/requests/notanint")
        assert r.status == 400

    _serve(run)


def test_slo_burn_alert_fires_end_to_end():
    """A sub-microsecond TTFT objective makes every request a violation:
    the burn-rate alert fires, /health degrades, and the ollamamq_slo_*
    series land on /metrics."""
    async def run(cl):
        for _ in range(4):
            r = await cl.post("/api/generate", json={
                "model": "test-tiny", "prompt": "x", "stream": False,
                "options": {"num_predict": 4},
            }, headers={"X-User-ID": "alice"})
            assert r.status == 200
        # Health-thread cadence is slow by default; evaluate directly.
        cl.engine.slo.evaluate()
        r = await cl.get("/health")
        body = await r.json()
        assert body["status"] == "degraded", body
        names = [a["name"] for a in body["alerts"]]
        assert any(n.startswith("slo_ttft_burn") for n in names), names
        r = await cl.get("/metrics")
        text = await r.text()
        assert 'ollamamq_slo_violations_total{objective="ttft"}' in text
        assert 'ollamamq_slo_burn_rate{objective="ttft"' in text
        assert 'ollamamq_slo_alerts_firing{alert="slo_ttft_burn' in text
        # The bundle carries the same picture.
        r = await cl.get("/debug/bundle")
        bundle = await r.json()
        assert bundle["slo"]["enabled"] is True
        assert bundle["alerts"]["active"], bundle["alerts"]
        assert bundle["config"]["slo_ttft_ms"] == 1e-6

    _serve(run, ecfg=EngineConfig(model="test-tiny", max_slots=8,
                                  slo_ttft_ms=1e-6, slo_tpot_ms=None))


def test_engine_step_stall_watchdog_fires_and_recovers():
    """Acceptance chaos: wedge the fake backend's step mid-serving. The
    watchdog must flip /health to degraded with an engine_stall alert,
    count it in ollamamq_watchdog_stalls_total, expose it in the TUI
    alert feed — and resolve everything once the engine moves again."""
    async def run(cl):
        eng = cl.engine
        # Fast watchdog for the test (the default is 10 s cadence).
        eng.health.stop()
        eng.health = HealthMonitor(eng, period_s=0.05, stall_s=0.3,
                                   request_stall_s=0.4)
        eng.health.start()
        rt = eng.runtimes["test-tiny"]
        release = threading.Event()
        orig_step = rt.step

        def wedged_step(core):
            release.wait()  # the engine loop thread blocks right here
            return orig_step(core)

        rt.step = wedged_step
        # Traffic that will never progress while wedged.
        req = eng.enqueue_request("alice", "", "test-tiny",
                                  prompt_tokens=[1, 2, 3])
        deadline = time.monotonic() + 20
        body = None
        while time.monotonic() < deadline:
            r = await cl.get("/health")
            body = await r.json()
            if body["status"] == "degraded" and any(
                    a["name"] == "engine_stall" for a in body["alerts"]):
                break
            await asyncio.sleep(0.05)
        assert body and body["status"] == "degraded", body
        names = [a["name"] for a in body["alerts"]]
        assert "engine_stall" in names, names
        # The stuck request shows up too, with the phase it's stuck in.
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            r = await cl.get("/health")
            body = await r.json()
            if any(a["name"] == "request_stall" for a in body["alerts"]):
                break
            await asyncio.sleep(0.05)
        assert any(a["name"] == "request_stall" for a in body["alerts"]), body
        r = await cl.get("/metrics")
        text = await r.text()
        assert 'ollamamq_watchdog_stalls_total{kind="engine_step"}' in text
        assert 'ollamamq_slo_alerts_firing{alert="engine_stall"' in text
        # The TUI alert feed (what the C++ panel renders) sees the same.
        from ollamamq_tpu.admin.tui import _engine_stats_brief

        brief = _engine_stats_brief(eng)
        assert any(a["name"] == "engine_stall" for a in brief["alerts"])
        # Recovery: release the wedge; the request completes and every
        # alert resolves.
        release.set()
        items = []
        while not items or items[-1].kind not in ("done", "error"):
            item = req.stream.get(timeout=10)
            assert item is not None, "request never finished after release"
            items.append(item)
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            r = await cl.get("/health")
            body = await r.json()
            if body["status"] == "ok":
                break
            await asyncio.sleep(0.05)
        assert body["status"] == "ok", body

    _serve(run)


def test_worker_stale_hook_raises_alert():
    """The SPMD-host staleness seam: an engine whose stale_worker_hosts
    reports a dead peer gets a worker_stale alert on the next watchdog
    pass (the SPMD engine wires this to KV-store heartbeats)."""
    async def run(cl):
        eng = cl.engine
        eng.health.stop()
        eng.stale_worker_hosts = lambda: [3]
        hm = HealthMonitor(eng, period_s=3600)
        hm.check_once()
        names = [a.name for a in eng.alerts.active()]
        assert "worker_stale" in names
        eng.stale_worker_hosts = lambda: []
        hm.check_once()
        assert "worker_stale" not in [a.name for a in eng.alerts.active()]

    _serve(run)
