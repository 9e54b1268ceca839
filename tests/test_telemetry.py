"""Telemetry subsystem: histogram/bucket math, Prometheus exposition
format (HELP/TYPE lines, label escaping), trace ring-buffer eviction,
and end-to-end — a FakeEngine request produces a valid exposition with
the headline metrics AND a complete, monotonic, gapless span chain on
/debug/trace. Also pins the two observability satellites: a failed
/debug/profile capture must not wedge the endpoint at 409, and
per_chip_stats must tag backends without memory_stats instead of
reporting fake zeros."""

import asyncio
import json
import re
import tempfile
import time
import unittest.mock

import pytest

from aiohttp.test_utils import TestClient, TestServer

from ollamamq_tpu.config import EngineConfig
from ollamamq_tpu.telemetry.metrics import (Counter, Gauge, Histogram,
                                            MetricsRegistry,
                                            escape_label_value)
from ollamamq_tpu.telemetry.tracing import Tracer
from ollamamq_tpu.telemetry import mfu as mfu_model


# ---------------------------------------------------------------- registry
def test_counter_and_gauge_basics():
    reg = MetricsRegistry()
    c = reg.counter("t_requests_total", "help", labels=("model",))
    c.labels(model="a").inc()
    c.labels(model="a").inc(2)
    c.labels(model="b").inc()
    assert c.labels(model="a").value == 3
    assert c.labels(model="b").value == 1
    g = reg.gauge("t_gauge", "help")
    g.set(5)
    g.inc()
    g.dec(3)
    assert g.value == 3
    # Counters refuse to go down; labels must match the declaration.
    try:
        c.labels(model="a").inc(-1)
        assert False, "negative counter inc must raise"
    except ValueError:
        pass
    try:
        c.labels(nope="a")
        assert False, "wrong label name must raise"
    except ValueError:
        pass


def test_registry_idempotent_and_type_conflict():
    reg = MetricsRegistry()
    a = reg.counter("t_x", "h")
    assert reg.counter("t_x", "h") is a  # same name => same object
    try:
        reg.gauge("t_x", "h")
        assert False, "type flip must raise"
    except ValueError:
        pass


def test_histogram_bucket_boundaries():
    """Prometheus le is INCLUSIVE: observe(boundary) lands in that bucket;
    anything past the last bound lands in +Inf."""
    reg = MetricsRegistry()
    h = reg.histogram("t_h", "h", buckets=(1.0, 5.0, 10.0))
    for v in (0.5, 1.0, 1.0001, 5.0, 9.99, 10.0, 11.0, 1e9):
        h.observe(v)
    child = h.labels()
    # buckets: <=1: {0.5, 1.0}; <=5: {1.0001, 5.0}; <=10: {9.99, 10.0};
    # +Inf: {11.0, 1e9}
    assert child.counts == [2, 2, 2, 2]
    assert child.count == 8
    assert abs(child.sum - (0.5 + 1.0 + 1.0001 + 5.0 + 9.99 + 10.0 + 11.0 + 1e9)) < 1e-3


def test_histogram_quantiles():
    reg = MetricsRegistry()
    h = reg.histogram("t_q", "h", buckets=(10.0, 20.0, 40.0))
    assert h.quantile(0.5) == 0.0  # empty
    for _ in range(10):
        h.observe(5.0)   # bucket (0, 10]
    for _ in range(10):
        h.observe(15.0)  # bucket (10, 20]
    # p50 = rank 10 => exactly fills the first bucket => its upper bound.
    assert abs(h.quantile(0.5) - 10.0) < 1e-9
    # p75 = rank 15 => midway through the second bucket (10..20).
    assert abs(h.quantile(0.75) - 15.0) < 1e-9
    # p100 clamps to the last bound touched.
    assert h.quantile(1.0) <= 40.0
    # all mass in +Inf clamps to the last finite bound.
    h2 = reg.histogram("t_q2", "h", buckets=(10.0,))
    h2.observe(100.0)
    assert h2.quantile(0.5) == 10.0


def test_set_buckets_resets():
    reg = MetricsRegistry()
    h = reg.histogram("t_rebucket", "h", buckets=(1.0, 2.0))
    h.observe(1.5)
    h.set_buckets((5.0, 50.0, 500.0))
    child = h.labels()
    assert child.count == 0 and child.counts == [0, 0, 0, 0]  # 3 + +Inf
    h.observe(7.0)
    assert child.counts == [0, 1, 0, 0]


def test_label_escaping():
    assert escape_label_value('a"b') == 'a\\"b'
    assert escape_label_value("a\\b") == "a\\\\b"
    assert escape_label_value("a\nb") == "a\\nb"


def parse_prom(text):
    """Minimal exposition parser: returns (help, type, samples) maps and
    asserts every line is well-formed."""
    helps, types, samples = {}, {}, {}
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# HELP "):
            name, h = line[7:].split(" ", 1)
            helps[name] = h
        elif line.startswith("# TYPE "):
            name, t = line[7:].split(" ", 1)
            types[name] = t
        else:
            m = re.match(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{.*\})? (.+)$", line)
            assert m, f"malformed exposition line: {line!r}"
            val = m.group(3)
            assert val == "+Inf" or val == "NaN" or float(val) is not None
            samples[m.group(1) + (m.group(2) or "")] = val
    return helps, types, samples


def test_prometheus_exposition_format():
    reg = MetricsRegistry()
    c = reg.counter("t_total", "requests served", labels=("model",))
    c.labels(model='we"ird\\mo\ndel').inc(3)
    h = reg.histogram("t_lat_ms", "latency", buckets=(1.0, 10.0))
    h.observe(0.5)
    h.observe(100.0)
    g = reg.gauge("t_up", "uptime")
    g.set(1.5)
    text = reg.render()
    helps, types, samples = parse_prom(text)
    assert types == {"t_total": "counter", "t_lat_ms": "histogram",
                     "t_up": "gauge"}
    assert helps["t_total"] == "requests served"
    # Label escaping on the wire.
    assert samples['t_total{model="we\\"ird\\\\mo\\ndel"}'] == "3"
    # Histogram: cumulative buckets + +Inf + sum/count.
    assert samples['t_lat_ms_bucket{le="1"}'] == "1"
    assert samples['t_lat_ms_bucket{le="10"}'] == "1"
    assert samples['t_lat_ms_bucket{le="+Inf"}'] == "2"
    assert samples["t_lat_ms_count"] == "2"
    assert float(samples["t_lat_ms_sum"]) == 100.5
    assert samples["t_up"] == "1.5"


def test_snapshot_merge_sums_counters_and_histograms():
    """The SPMD host-merge path: peer snapshots sum into counters and
    histograms; gauges union with local winning."""
    a, b = MetricsRegistry(), MetricsRegistry()
    for reg, n in ((a, 2), (b, 5)):
        c = reg.counter("t_tok_total", "h", labels=("model",))
        c.labels(model="m").inc(n)
        h = reg.histogram("t_ms", "h", buckets=(1.0, 10.0))
        h.observe(n)
        g = reg.gauge("t_g", "h", labels=("chip",))
        g.labels(chip=str(n)).set(n)
    text = a.render(extra_snapshots=[b.snapshot()])
    _, _, samples = parse_prom(text)
    assert samples['t_tok_total{model="m"}'] == "7"
    assert samples['t_ms_bucket{le="+Inf"}'] == "2"
    assert float(samples["t_ms_sum"]) == 7.0
    # disjoint gauge series union:
    assert samples['t_g{chip="2"}'] == "2" and samples['t_g{chip="5"}'] == "5"


# ----------------------------------------------------------------- tracing
def test_trace_ring_eviction():
    tr = Tracer(capacity=4)
    for i in range(10):
        t = tr.begin(i, "u", "m")
        t.finish("stop")
    kept = tr.traces()
    assert len(kept) == 4
    assert [t.req_id for t in kept] == [6, 7, 8, 9]  # oldest evicted
    # Finish is idempotent: a cancel/finish race can't double-insert.
    kept[0].finish("stop")
    assert len(tr.traces()) == 4


def test_trace_event_cap_keeps_terminal():
    tr = Tracer(capacity=4)
    t = tr.begin(1, "u", "m")
    for i in range(1000):
        t.event("decode", tokens=i)
    t.finish("stop")
    assert len(t.events) <= 257  # cap + forced terminal
    assert t.events[-1][0] == "stop"
    assert t.dropped > 0


def test_chrome_export_spans_contiguous():
    tr = Tracer(capacity=8)
    t = tr.begin(7, "alice", "test-tiny")
    for name in ("admit", "place", "prefill", "first_token"):
        t.event(name)
    t.finish("stop")
    out = tr.export_chrome()
    evs = [e for e in out["traceEvents"]
           if e.get("tid") == 7 and e.get("ph") in ("X", "i")]
    names = [e["name"] for e in evs]
    assert names == ["enqueue", "admit", "place", "prefill", "first_token",
                     "stop"]
    # Gapless: each X span ends exactly where the next event begins.
    for cur, nxt in zip(evs, evs[1:]):
        assert cur["ph"] == "X"
        assert abs((cur["ts"] + cur["dur"]) - nxt["ts"]) < 1e-6
        assert nxt["ts"] >= cur["ts"]  # monotonic
    assert evs[-1]["ph"] == "i"


# --------------------------------------------------------------------- mfu
def test_mfu_model():
    from ollamamq_tpu.config import MODEL_CONFIGS

    cfg = MODEL_CONFIGS["test-tiny"]
    base = mfu_model.flops_per_token(cfg)
    assert base == 2.0 * mfu_model.active_param_count(cfg)
    with_ctx = mfu_model.flops_per_token(cfg, context_len=128)
    assert with_ctx == base + 4.0 * cfg.num_layers * 128 * cfg.q_dim
    # MoE counts routed-active experts only.
    moe = MODEL_CONFIGS["test-tiny-moe"]
    assert mfu_model.active_param_count(moe) < moe.param_count()
    # Unknown accelerator => 0, never invented.
    assert mfu_model.mfu(cfg, 100, 1.0, None) == 0.0
    # Known peak: achieved/peak.
    got = mfu_model.mfu(cfg, tokens=10, seconds=1.0, peak_per_chip=base * 100,
                        n_chips=1)
    assert abs(got - 0.1) < 1e-9
    assert mfu_model.peak_flops_per_chip("TPU v5 lite") == 197e12
    assert mfu_model.peak_flops_per_chip("weird-npu") is None
    with unittest.mock.patch.dict("os.environ",
                                  {"OLLAMAMQ_PEAK_FLOPS": "1e12"}):
        assert mfu_model.peak_flops_per_chip("weird-npu") == 1e12


# ------------------------------------------------------------- chip stats
def test_per_chip_stats_tags_missing_memory_stats():
    """CPU backends report memory_stats=False so consumers render n/a
    instead of a fake 0-byte HBM reading."""
    from ollamamq_tpu.engine.engine import per_chip_stats

    rows = per_chip_stats()
    assert rows, "expected the 8 virtual CPU devices"
    for row in rows:
        assert "memory_stats" in row
        if not row["memory_stats"]:
            assert row["hbm_used"] == 0 and row["hbm_total"] == 0


# --------------------------------------------------------------- e2e HTTP
def _serve(fn):
    """Async harness: fresh FakeEngine + server (test_api.py idiom)."""
    async def main():
        with tempfile.TemporaryDirectory() as tmp:
            from ollamamq_tpu.engine.fake import FakeEngine
            from ollamamq_tpu.server.app import Server

            eng = FakeEngine(
                EngineConfig(model="test-tiny", max_slots=8),
                models={"test-tiny": None, "test-tiny-embed": None},
                blocklist_path=f"{tmp}/blocked_items.json",
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


def test_e2e_prometheus_exposition():
    """GET /metrics is valid Prometheus text carrying the acceptance
    metrics with real values after one request."""
    async def run(cl):
        r = await cl.post("/api/generate", json={
            "model": "test-tiny", "prompt": "hello", "stream": False,
            "options": {"num_predict": 4},
        }, headers={"X-User-ID": "alice"})
        assert r.status == 200
        r = await cl.get("/metrics")
        assert r.status == 200
        assert "text/plain" in r.headers["Content-Type"]
        assert "version=0.0.4" in r.headers["Content-Type"]
        helps, types, samples = parse_prom(await r.text())
        for name, typ in (
            ("ollamamq_ttft_ms", "histogram"),
            ("ollamamq_tpot_ms", "histogram"),
            ("ollamamq_queue_depth", "gauge"),
            ("ollamamq_batch_occupancy", "gauge"),
            ("ollamamq_mfu", "gauge"),
            ("ollamamq_requests_total", "counter"),
            ("ollamamq_tokens_generated_total", "counter"),
            ("ollamamq_uptime_seconds", "gauge"),
        ):
            assert types.get(name) == typ, f"{name} missing or wrong type"
            assert name in helps
        # Value lines, not just declarations:
        assert 'ollamamq_queue_depth{user="alice"}' in samples
        assert 'ollamamq_batch_occupancy{model="test-tiny"}' in samples
        assert 'ollamamq_mfu{model="test-tiny"}' in samples
        # The request actually landed in the histograms/counters.
        assert int(samples[
            'ollamamq_ttft_ms_bucket{model="test-tiny",le="+Inf"}']) >= 1
        assert float(samples[
            'ollamamq_tokens_generated_total{model="test-tiny"}']) >= 4

    _serve(run)


def test_e2e_metrics_json_still_serves_legacy_payload():
    async def run(cl):
        r = await cl.get("/metrics.json")
        assert r.status == 200
        body = await r.json()
        assert "runtimes" in body and "queue" in body
        assert all("mfu" in rt for rt in body["runtimes"])

    _serve(run)


def test_e2e_fake_engine_trace_chain():
    """A FakeEngine request's /debug/trace spans cover handler entry
    (ingress) -> enqueue -> complete with monotonic timestamps and no
    gaps."""
    async def run(cl):
        r = await cl.post("/api/generate", json={
            "model": "test-tiny", "prompt": "hello", "stream": False,
            "options": {"num_predict": 3},
        }, headers={"X-User-ID": "bob"})
        assert r.status == 200
        r = await cl.get("/debug/trace")
        assert r.status == 200
        out = await r.json()
        assert "traceEvents" in out
        # Find bob's generate request row.
        metas = [e for e in out["traceEvents"] if e.get("ph") == "M"
                 and "bob" in e.get("args", {}).get("name", "")]
        assert metas, "traced request missing from export"
        tid = metas[0]["tid"]
        evs = [e for e in out["traceEvents"]
               if e.get("tid") == tid and e.get("ph") in ("X", "i")]
        names = [e["name"] for e in evs]
        assert names[:2] == ["ingress", "enqueue"]
        assert names[-1] in ("stop", "length")
        for must in ("admit", "place", "prefill", "first_token"):
            assert must in names, f"span chain missing {must}: {names}"
        prev_end = None
        for e in evs:
            assert e["ts"] >= (prev_end if prev_end is not None else e["ts"])
            if e["ph"] == "X":
                assert e["dur"] >= 0
                if prev_end is not None:
                    assert abs(e["ts"] - prev_end) < 1e-6, "gap in span chain"
                prev_end = e["ts"] + e["dur"]
        # JSON round-trips (chrome://tracing loads it).
        json.dumps(out)

    _serve(run)


@pytest.mark.parametrize("path,body,frame", [
    ("/api/generate", {"prompt": "hello", "options": {"num_predict": 6}},
     b'"done"'),
    ("/v1/chat/completions",
     {"messages": [{"role": "user", "content": "hello"}], "max_tokens": 6},
     b'"choices"'),
])
def test_stream_lag_observed_once_a_frame(path, body, frame):
    """PR 24: a StreamItem carries the monotonic instant of its push, and
    the NDJSON and SSE writers observe now - pushed_at into
    ollamamq_stream_lag_ms when the frame has been written: the count
    grows by exactly the frames streamed (SSE's `[DONE]` sentinel comes
    from no item and is not counted), the sum by plausible lags."""
    from ollamamq_tpu.telemetry import schema as tm

    async def run(cl):
        lag = tm.STREAM_LAG_MS.labels()
        n0, s0 = lag.count, lag.sum
        t0 = time.monotonic()
        r = await cl.post(path, json={"model": "test-tiny", "stream": True,
                                      **body})
        assert r.status == 200
        frames = [ln for ln in (await r.read()).split(b"\n")
                  if frame in ln]
        wall_ms = (time.monotonic() - t0) * 1e3
        assert len(frames) >= 3
        assert lag.count - n0 == len(frames)
        assert 0.0 < lag.sum - s0 < wall_ms * len(frames)
        text = await (await cl.get("/metrics")).text()
        assert re.search(r"^ollamamq_stream_lag_ms_count \d+$", text, re.M)

    _serve(run)


def test_debug_profile_failure_does_not_wedge():
    """Satellite: a capture that throws must clear the running flag — the
    next POST gets a fresh 500/success, never a permanent 409."""
    async def run(cl):
        import jax

        with unittest.mock.patch.object(
                jax.profiler, "start_trace",
                side_effect=RuntimeError("disk full")):
            r1 = await cl.post("/debug/profile", json={"seconds": 0.1})
            assert r1.status == 500
            assert "profile capture failed" in (await r1.json())["error"]
            r2 = await cl.post("/debug/profile", json={"seconds": 0.1})
            assert r2.status == 500, "second capture wedged at 409"

    _serve(run)


def test_trace_ring_flag_bounds_engine_ring():
    from ollamamq_tpu.engine.fake import FakeEngine

    eng = FakeEngine(EngineConfig(model="test-tiny", trace_ring=3),
                     models={"test-tiny": None})
    eng.start()
    try:
        reqs = [eng.enqueue_request("u", "", "test-tiny",
                                    prompt_tokens=[1, 2]) for _ in range(8)]
        for req in reqs:
            items = []
            while not items or items[-1].kind not in ("done", "error"):
                item = req.stream.get(timeout=5)
                assert item is not None, "request never finished"
                items.append(item)
        finished = [t for t in eng.tracer.traces() if t.finished]
        assert len(finished) == 3
    finally:
        eng.stop()
