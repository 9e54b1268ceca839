"""Crash-safe serving: durable admission WAL, cold-restart recovery,
client-resumable streams, graceful shutdown, sampled journaling, and
the fleet-wide journal audit roll-up.

The contract under test: a `kill -9` of the serving process loses at
most one fsync window of emitted-token progress and NO admitted
request — recovery re-admits every unfinished stream token-exact, a
reattaching client receives the remainder byte- and token-identical to
an uninterrupted run, and the journal audit attributes every recovered
stream to exactly one terminal across the pre- and post-crash spills.
"""

import json
import os
import random
import shutil
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import pytest

from ollamamq_tpu.config import EngineConfig
from ollamamq_tpu.durability.wal import RequestWAL, load_wal_records
from ollamamq_tpu.engine.fake import FakeEngine
from ollamamq_tpu.engine.request import FinishReason
from ollamamq_tpu.ops.sampling import SamplingParams
from ollamamq_tpu.telemetry import schema as tm
from ollamamq_tpu.telemetry.journal import (SAMPLED_KINDS, Journal,
                                            check_invariants)
from ollamamq_tpu.tools.journal import main as journal_main
from testutil import collect, free_port

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _fake(tmp_path, latency=0.0, **over):
    wal = str(tmp_path / "wal")
    cfg = dict(model="test-tiny", wal_dir=wal, wal_fsync_ms=2.0)
    cfg.update(over)
    eng = FakeEngine(EngineConfig(**cfg), blocklist_path=None,
                     token_latency_s=latency)
    eng.start()
    return eng


def _crash(eng):
    """Abrupt loop death — deliberately NOT stop(), which would flush
    and tidy the very state a real crash leaves behind. The WAL flusher
    is also stopped so the crash copy below is a stable snapshot."""
    eng._running = False
    eng.notify()
    time.sleep(0.1)
    eng.durability.wal._stop.set()
    t = eng.durability.wal._flusher
    if t is not None:
        t.join(timeout=5)


def _crash_copy(eng, tmp_path, name="wal-crash"):
    """Snapshot the crashed process's WAL dir for an independent
    recovery, then FULLY tear the corpse down — a real crash takes the
    health monitor and drainer threads with it; in-process they would
    keep logging stalls (and leak threads) for the rest of the run."""
    dst = str(tmp_path / name)
    shutil.copytree(eng.ecfg.wal_dir, dst)
    eng.stop()
    return dst


# ---------------------------------------------------------------- WAL basics
def test_wal_admit_is_durable_before_ack(tmp_path):
    """The admit record is on disk (fsynced) by the time enqueue_request
    returns, every emitted token follows within a flush window, and the
    journal carries the wal_admit decision with its fsync cost."""
    eng = _fake(tmp_path)
    try:
        req = eng.enqueue_request("alice", "", "test-tiny",
                                  prompt_tokens=[1, 2, 3],
                                  sampling=SamplingParams(max_tokens=4))
        # Durable BEFORE the ACK: the admit line is already readable.
        entries, torn = load_wal_records(
            os.path.join(eng.ecfg.wal_dir, "wal.jsonl"))
        assert torn == 0
        assert req.req_id in entries
        assert entries[req.req_id]["admit"]["prompt"] == [1, 2, 3]
        items = collect(req)
        assert items[-1].kind == "done"
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            entries, _ = load_wal_records(
                os.path.join(eng.ecfg.wal_dir, "wal.jsonl"))
            ent = entries[req.req_id]
            if ent["finished"] is not None:
                break
            time.sleep(0.02)
        assert ent["finished"] == "length"
        assert [i for i, _ in ent["toks"]] == [1, 2, 3, 4]
        assert "".join(t for _, t in ent["toks"]) \
            == "word0 word1 word2 word3 "
        wal_admits = eng.journal.tail(kind="wal_admit")
        assert len(wal_admits) == 1
        assert wal_admits[0]["fsync_ms"] >= 0
    finally:
        eng.stop()


def test_wal_embeds_not_logged(tmp_path):
    """Embeds recompute cheaply and carry no resumable stream: they are
    served normally but never WAL'd."""
    eng = _fake(tmp_path)
    try:
        req = eng.enqueue_request("e", "", "test-tiny",
                                  prompt_tokens=[1, 2], kind="embed",
                                  sampling=SamplingParams())
        collect(req)
        entries, _ = load_wal_records(
            os.path.join(eng.ecfg.wal_dir, "wal.jsonl"))
        assert req.req_id not in entries
    finally:
        eng.stop()


def test_wal_truncated_tail_is_loadable(tmp_path):
    """Randomized crash points: any byte-truncation of a WAL file loads
    without error into a consistent prefix of the full state."""
    eng = _fake(tmp_path)
    try:
        for i in range(3):
            collect(eng.enqueue_request(
                f"u{i}", "", "test-tiny", prompt_tokens=[1] * (i + 2),
                sampling=SamplingParams(max_tokens=3 + i)))
        time.sleep(0.2)  # let the flusher land everything
    finally:
        eng.stop()
    path = os.path.join(str(tmp_path / "wal"), "wal.jsonl")
    full, torn = load_wal_records(path)
    assert torn == 0 and len(full) == 3
    data = open(path, "rb").read()
    rng = random.Random(7)
    for _ in range(25):
        cut = rng.randrange(0, len(data))
        trunc = str(tmp_path / "trunc.jsonl")
        with open(trunc, "wb") as f:
            f.write(data[:cut])
        part, _torn = load_wal_records(trunc)  # must not raise
        for rid, ent in part.items():
            ref = full[rid]
            assert ent["admit"]["prompt"] == ref["admit"]["prompt"]
            # Token progress is a prefix of the full run's.
            assert ent["toks"] == ref["toks"][:len(ent["toks"])]


def test_wal_fault_degrades_loudly(tmp_path):
    """Injected disk trouble (fault site 'wal') degrades the WAL — the
    alert fires, serving continues un-journaled, nothing hangs."""
    from ollamamq_tpu.testing.faults import FaultPlan

    plan = FaultPlan([{"site": "wal", "kind": "exception", "at": [1]}])
    eng = _fake(tmp_path, fault_plan=plan)
    try:
        req = eng.enqueue_request("f", "", "test-tiny",
                                  prompt_tokens=[1, 2],
                                  sampling=SamplingParams(max_tokens=3))
        items = collect(req)
        assert items[-1].kind == "done"  # serving survived the disk
        deadline = time.monotonic() + 5
        while not eng.durability.wal.dead \
                and time.monotonic() < deadline:
            time.sleep(0.02)
        assert eng.durability.wal.dead
        assert any(a.name == "wal_degraded" for a in eng.alerts.active())
        # Later requests still serve (and no longer block on the WAL).
        items = collect(eng.enqueue_request(
            "f", "", "test-tiny", prompt_tokens=[3],
            sampling=SamplingParams(max_tokens=2)))
        assert items[-1].kind == "done"
    finally:
        eng.stop()


# ------------------------------------------------------------------ recovery
def test_recovery_resumes_token_exact(tmp_path):
    """Crash mid-stream, recover on a fresh engine: the stream completes
    byte- AND token-identical to an uninterrupted run, the journal
    carries recover_replay, and the recovered metric counts it."""
    eng = _fake(tmp_path, latency=0.02)
    req = eng.enqueue_request("alice", "", "test-tiny",
                              prompt_tokens=[1, 2, 3],
                              sampling=SamplingParams(max_tokens=12))
    rid = req.req_id
    while len(req.generated_ids) < 5:
        time.sleep(0.005)
    _crash(eng)
    crash_dir = _crash_copy(eng, tmp_path)

    eng2 = _fake(tmp_path.joinpath("ignored"), wal_dir=crash_dir)
    try:
        dur = eng2.durability
        assert dur.recovered_streams == 1
        entry = dur.registry.find(rid)
        assert entry is not None and entry.recovered
        deadline = time.monotonic() + 20
        while entry.terminal is None and time.monotonic() < deadline:
            time.sleep(0.01)
        frames, term = entry.snapshot(0)
        assert term == {"reason": "length", "error": ""}
        assert "".join(t for _, t in frames) \
            == "".join(f"word{i} " for i in range(12))
        assert [i for i, _ in frames if i >= 0] == list(range(1, 13))
        recs = eng2.journal.tail(kind="recover_replay")
        assert len(recs) == 1
        assert recs[0]["outcome"] == "replayed"
        assert recs[0]["wal_rid"] == rid
        assert recs[0]["tokens"] == len(
            load_wal_records(os.path.join(crash_dir, "wal.jsonl.1")
                             )[0][rid]["toks"])
        # The new WAL generation compacted the survivor under its
        # ORIGINAL rid, so a second crash recovers cumulatively.
        entries, _ = load_wal_records(os.path.join(crash_dir, "wal.jsonl"))
        assert rid in entries
    finally:
        eng2.stop()


def test_recovery_finished_budget_surfaces_terminal(tmp_path):
    """A stream whose budget was already spent at crash time is NOT
    re-admitted (regenerating token 13 of 12 would fork the stream);
    its terminal is surfaced for any resuming client."""
    eng = _fake(tmp_path)
    req = eng.enqueue_request("b", "", "test-tiny", prompt_tokens=[1],
                              sampling=SamplingParams(max_tokens=4))
    rid = req.req_id
    items = collect(req)
    assert items[-1].kind == "done"
    # Forge the crash window: drop the fin record so the WAL says
    # "4/4 tokens emitted, no terminal".
    time.sleep(0.2)
    _crash(eng)
    crash_dir = _crash_copy(eng, tmp_path)
    path = os.path.join(crash_dir, "wal.jsonl")
    lines = [l for l in open(path) if '"fin"' not in l]
    open(path, "w").writelines(lines)

    eng2 = _fake(tmp_path.joinpath("ignored"), wal_dir=crash_dir)
    try:
        assert eng2.durability.recovered_streams == 0
        entry = eng2.durability.registry.find(rid)
        assert entry.terminal == {"reason": "length", "error": ""}
        assert entry.token_count() == 4
        recs = eng2.journal.tail(kind="recover_replay")
        assert recs and recs[0]["outcome"] == "finished"
    finally:
        eng2.stop()


def test_recovery_real_engine_page_conservation(tmp_path, tiny_cfg):
    """The acceptance shape on a REAL runtime: a greedy stream
    interrupted mid-decode recovers byte- and token-identical, with the
    page allocator conserving free+used+cached==pool after recovery and
    the journal invariant checker clean."""
    import jax.numpy as jnp

    from ollamamq_tpu.engine.engine import TPUEngine

    tiny = dict(model="test-tiny", max_slots=2, num_pages=64, page_size=8,
                max_pages_per_seq=8,
                decode_steps_per_iter=1)
    prompt = list(range(7, 19))
    # Golden: an uninterrupted greedy run.
    ref = TPUEngine(EngineConfig(**tiny), blocklist_path=None,
                    dtype=jnp.float32)
    ref.start()
    try:
        gr = ref.enqueue_request("g", "", "test-tiny",
                                 prompt_tokens=list(prompt),
                                 sampling=SamplingParams(max_tokens=10))
        golden_items = collect(gr, timeout=240)
        golden_text = "".join(i.text for i in golden_items
                              if i.kind == "token")
        golden_ids = list(gr.generated_ids)
    finally:
        ref.stop()
    assert len(golden_ids) == 10

    eng = TPUEngine(EngineConfig(wal_dir=str(tmp_path / "wal"),
                                 wal_fsync_ms=2.0, **tiny),
                    blocklist_path=None, dtype=jnp.float32)
    eng.start()
    req = eng.enqueue_request("g", "", "test-tiny",
                              prompt_tokens=list(prompt),
                              sampling=SamplingParams(max_tokens=10))
    rid = req.req_id
    deadline = time.monotonic() + 240
    while len(req.generated_ids) < 4 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert len(req.generated_ids) >= 4, "stream never got going"
    _crash(eng)
    crash_dir = _crash_copy(eng, tmp_path)

    eng2 = TPUEngine(EngineConfig(wal_dir=crash_dir, wal_fsync_ms=2.0,
                                  **tiny),
                     blocklist_path=None, dtype=jnp.float32)
    eng2.start()
    try:
        entry = eng2.durability.registry.find(rid)
        assert entry is not None
        deadline = time.monotonic() + 240
        while entry.terminal is None and time.monotonic() < deadline:
            time.sleep(0.02)
        frames, term = entry.snapshot(0)
        assert term is not None and term["reason"] in ("length", "stop")
        assert "".join(t for _, t in frames) == golden_text
        assert [i for i, _ in frames if i >= 0] == golden_ids
        # Page conservation after recovery, on the live allocators
        # (page 0 is reserved: free + used + cached == pool - 1).
        for rt in eng2._step_targets():
            alloc = getattr(rt, "alloc", None)
            if alloc is None:
                continue
            assert (alloc.free_pages + alloc.used_pages
                    + alloc.cached_pages == alloc.num_pages - 1)
        assert check_invariants(eng2.journal.tail(None)) == []
    finally:
        eng2.stop()


# ------------------------------------------------- resume endpoint (sockets)
class _Http:
    """Real-socket server over an engine (the test_fleet pattern)."""

    def __init__(self, engine, timeout_s=30):
        import asyncio

        from aiohttp import web

        from ollamamq_tpu.server.app import Server

        self.engine = engine
        self.port = free_port()
        started = threading.Event()

        def serve():
            loop = asyncio.new_event_loop()
            asyncio.set_event_loop(loop)
            self._loop = loop
            app = Server(engine, timeout_s=timeout_s).build_app()
            runner = web.AppRunner(app, shutdown_timeout=1.0)
            loop.run_until_complete(runner.setup())
            site = web.TCPSite(runner, "127.0.0.1", self.port)
            loop.run_until_complete(site.start())
            started.set()
            loop.run_forever()

        self._thread = threading.Thread(target=serve, daemon=True)
        self._thread.start()
        assert started.wait(15)

    @property
    def url(self):
        return f"http://127.0.0.1:{self.port}"

    def stop(self):
        self._loop.call_soon_threadsafe(self._loop.stop)
        self._thread.join(timeout=10)
        self.engine.stop()


def _read_ndjson(resp):
    text, ids, done = "", [], None
    for raw in resp:
        obj = json.loads(raw)
        ids.extend(int(t) for t in obj.get("token_ids") or ())
        text += obj.get("response", "")
        if obj.get("done"):
            done = obj.get("done_reason")
            break
    return text, ids, done


def test_resume_endpoint_e2e(tmp_path):
    """GET /api/stream/{rid}?from=N over real sockets: mid-stream
    reattach follows live to the terminal; post-finish replay serves the
    archive; unknown rid is 404; /health carries the wal block."""
    eng = _fake(tmp_path, latency=0.03)
    srv = _Http(eng)
    try:
        h = json.loads(urllib.request.urlopen(
            srv.url + "/health", timeout=5).read())
        assert h["wal"]["enabled"] and h["status"] == "ok"

        body = json.dumps({"model": "test-tiny", "prompt": "x",
                           "stream": True,
                           "options": {"num_predict": 9}}).encode()
        main = urllib.request.urlopen(urllib.request.Request(
            srv.url + "/api/generate", data=body,
            headers={"Content-Type": "application/json"}), timeout=30)
        first = json.loads(next(iter(main)))
        rid = first["req_id"]
        # Reattach from token 1 while the stream is still live.
        text, ids, done = _read_ndjson(urllib.request.urlopen(
            srv.url + f"/api/stream/{rid}?from=1", timeout=30))
        assert done == "length"
        assert text == "".join(f"word{i} " for i in range(1, 9))
        assert ids == list(range(2, 10))
        main.close()
        # Full archive replay after the fact.
        text, ids, done = _read_ndjson(urllib.request.urlopen(
            srv.url + f"/api/stream/{rid}?from=0", timeout=30))
        assert text == "".join(f"word{i} " for i in range(9))
        assert ids == list(range(1, 10))
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(srv.url + "/api/stream/99999",
                                   timeout=5)
        assert e.value.code == 404
    finally:
        srv.stop()


# ------------------------------------------------- subprocess e2e (cli path)
def _spawn_cli(tmp_path, port, wal_dir, extra=(), latency="0.05"):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["FAKE_TOKEN_LATENCY_S"] = latency
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    logf = open(str(tmp_path / f"server-{port}.log"), "ab")
    proc = subprocess.Popen(
        [sys.executable, "-m", "ollamamq_tpu.cli", "--fake-engine",
         "--no-tui", "--models", "test-tiny", "--port", str(port),
         "--wal-dir", wal_dir, "--wal-fsync-ms", "2",
         "--blocklist", str(tmp_path / "bl.json"), *extra],
        stdout=logf, stderr=subprocess.STDOUT, env=env)
    proc._logf = logf
    return proc


def _wait_health(port, budget=90.0):
    deadline = time.monotonic() + budget
    while time.monotonic() < deadline:
        try:
            body = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{port}/health", timeout=2).read())
            if body.get("status") != "recovering":
                return body
        except Exception:  # noqa: BLE001
            pass
        time.sleep(0.1)
    raise TimeoutError(f"server :{port} never became healthy")


def test_sigterm_drains_then_exits_zero(tmp_path):
    """SIGTERM mid-stream: admission stops (503), the live stream runs
    to completion for its client, the WAL records the finish, and the
    process exits 0 — `docker stop` is a zero-drop event."""
    port = free_port()
    wal_dir = str(tmp_path / "wal")
    proc = _spawn_cli(tmp_path, port, wal_dir,
                      extra=("--stop-grace-s", "30"))
    try:
        _wait_health(port)
        body = json.dumps({"model": "test-tiny", "prompt": "x",
                           "stream": True,
                           "options": {"num_predict": 12}}).encode()
        resp = urllib.request.urlopen(urllib.request.Request(
            f"http://127.0.0.1:{port}/api/generate", data=body,
            headers={"Content-Type": "application/json"}), timeout=60)
        first = json.loads(next(iter(resp)))
        assert first["req_id"] >= 1
        proc.send_signal(signal.SIGTERM)
        # Admission is closed almost immediately...
        deadline = time.monotonic() + 10
        shed = None
        while time.monotonic() < deadline:
            try:
                urllib.request.urlopen(urllib.request.Request(
                    f"http://127.0.0.1:{port}/api/generate", data=body,
                    headers={"Content-Type": "application/json"}),
                    timeout=5).read()
            except urllib.error.HTTPError as e:
                shed = e.code
                break
            except Exception:  # noqa: BLE001 — already gone = also fine
                break
            time.sleep(0.1)
        # ...while the live stream completes rather than being cut.
        text, _ids, done = _read_ndjson(resp)
        full = first.get("response", "") + text
        assert done == "length"
        assert full == "".join(f"word{i} " for i in range(12))
        assert proc.wait(timeout=60) == 0
        if shed is not None:
            assert shed == 503
        entries, _ = load_wal_records(os.path.join(wal_dir, "wal.jsonl"))
        assert all(e["finished"] is not None for e in entries.values())
    finally:
        proc.kill()
        proc._logf.close()


def test_kill9_restart_resume_byte_identical(tmp_path):
    """THE headline e2e: a greedy stream interrupted by kill -9 of the
    serving process mid-decode, restart on the same WAL, client
    reconnects via GET /api/stream/{rid}?from=N — the total delivery is
    byte- AND token-identical to an uninterrupted run."""
    port = free_port()
    wal_dir = str(tmp_path / "wal")
    proc = _spawn_cli(tmp_path, port, wal_dir)
    proc2 = None
    try:
        _wait_health(port)
        body = json.dumps({"model": "test-tiny", "prompt": "x",
                           "stream": True,
                           "options": {"num_predict": 12}}).encode()
        resp = urllib.request.urlopen(urllib.request.Request(
            f"http://127.0.0.1:{port}/api/generate", data=body,
            headers={"Content-Type": "application/json"}), timeout=60)
        rid, text, ids = None, "", []
        for raw in resp:
            obj = json.loads(raw)
            rid = obj.get("req_id", rid)
            ids.extend(int(t) for t in obj.get("token_ids") or ())
            text += obj.get("response", "")
            if len(ids) >= 5:
                break
        proc.kill()  # SIGKILL: no flush, no goodbye
        proc.wait(timeout=30)
        try:
            resp.close()
        except Exception:  # noqa: BLE001
            pass

        proc2 = _spawn_cli(tmp_path, port, wal_dir, latency="0.0")
        health = _wait_health(port)
        assert health["wal"]["recovered_streams"] == 1
        rtext, rids, done = _read_ndjson(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/api/stream/{rid}?from={len(ids)}",
            timeout=60))
        assert done == "length"
        assert text + rtext == "".join(f"word{i} " for i in range(12))
        assert ids + rids == list(range(1, 13))
    finally:
        proc.kill()
        if proc2 is not None:
            proc2.kill()
        proc._logf.close()


# ------------------------------------------------------- graceful quiesce
def test_quiesce_sheds_honestly(tmp_path):
    eng = _fake(tmp_path)
    try:
        eng.quiesce()
        from ollamamq_tpu.engine.engine import QueueFullError

        with pytest.raises(QueueFullError) as e:
            eng.enqueue_request("q", "", "test-tiny", prompt_tokens=[1],
                                sampling=SamplingParams(max_tokens=2))
        assert e.value.scope == "queue_full"
        sheds = eng.journal.tail(kind="shed")
        assert sheds and sheds[-1]["limit"] == 0
        assert check_invariants(eng.journal.tail(None)) == []
        assert eng.inflight_count() == 0
    finally:
        eng.stop()


# ------------------------------------------------------- sampled journaling
def test_sampled_journal_keeps_decisions(tmp_path):
    """--journal-sample: high-rate kinds thin out, decision-critical
    kinds all survive, per-record invariants stay checkable, and the
    offline checker understands the sampled spill."""
    path = str(tmp_path / "sampled.jsonl")
    j = Journal(capacity=8192, path=path, sample=0.1)
    for i in range(400):
        j.record("batch", slots=[0], batch_size=1, tokens=4,
                 occupancy=0.5, mode="fake", padded_tokens=4)
        j.record("page_alloc", n=1, free=10, used=5, cached=1, pool=16)
    for i in range(20):
        j.record("enqueue", req_id=i, user="u", n_prompt=3, queued=1)
        j.record("shed", user="u", reason="queue_full", queued=9, limit=8)
        j.record("finish", req_id=i, user="u", reason="stop", tokens=2)
    j.close()
    recs = j.tail(None)
    kinds = {}
    for r in recs:
        kinds[r["kind"]] = kinds.get(r["kind"], 0) + 1
    # Sampled kinds thinned hard (800 -> ~80 expected), decisions whole.
    assert kinds.get("batch", 0) + kinds.get("page_alloc", 0) < 300
    assert kinds["enqueue"] == kinds["shed"] == kinds["finish"] == 20
    assert j.sampled_out > 0
    assert j.snapshot()["sample"] == 0.1
    # Metrics still count every event, sampled-out included.
    batch_total = next(
        child.value for labels, child in
        tm.JOURNAL_EVENTS_TOTAL.series() if labels == ("batch",))
    assert batch_total >= 400
    # Surviving page records are self-contained: conservation holds.
    assert check_invariants(recs, starve_after=None) == []
    # The CLI checker reads the sampled meta and exits clean.
    assert journal_main(["check", path]) == 0


def test_sampled_journal_default_records_everything():
    j = Journal(capacity=64)
    for _ in range(30):
        j.record("batch", slots=[0], batch_size=1, tokens=1,
                 occupancy=0.1)
    assert len(j.tail(None)) == 30
    assert j.sampled_out == 0
    assert "sample" not in j.snapshot()


# ----------------------------------------------- fleet-wide audit roll-up
def _spill(path, records, meta=None):
    with open(path, "w", encoding="utf-8") as f:
        f.write(json.dumps({"journal_meta": {"version": 1,
                                             **(meta or {})}}) + "\n")
        for i, r in enumerate(records):
            f.write(json.dumps({"seq": i, "t": 0.0, "tick": i, **r}) + "\n")


def test_multi_file_check_rolls_up_across_crash(tmp_path):
    """The fleet roll-up: a stream cut off by the router's crash (its
    pre-crash spill ends with a failover and no terminal) is resolved by
    the restarted router's spill naming it in recover_replay.wal_rid —
    and stays a violation when the recovery spill is absent."""
    pre = str(tmp_path / "router1.jsonl")
    post = str(tmp_path / "router2.jsonl")
    _spill(pre, [
        {"kind": "enqueue", "req_id": 5, "user": "u", "n_prompt": 3,
         "queued": 1},
        {"kind": "replica_eject", "replica": "r0", "why": "crash"},
        {"kind": "replica_failover", "req_id": 5, "user": "u",
         "replica": "r0", "to_replica": "r1", "replayed_tokens": 2},
    ])
    _spill(post, [
        {"kind": "recover_replay", "req_id": 1, "user": "u", "tokens": 4,
         "outcome": "replayed", "wal_rid": 5},
        {"kind": "finish", "req_id": 1, "user": "u", "reason": "stop",
         "tokens": 6},
    ])
    # Alone, the cut spill shows a dropped stream...
    assert journal_main(["check", pre]) == 1
    # ...the roll-up resolves it across the crash.
    assert journal_main(["check", pre, post]) == 0
    # An unresolved recovery is still a drop.
    unres = str(tmp_path / "router3.jsonl")
    _spill(unres, [
        {"kind": "recover_replay", "req_id": 1, "user": "u", "tokens": 4,
         "outcome": "replayed", "wal_rid": 5},
    ])
    assert journal_main(["check", pre, unres]) == 1


def test_attribution_flags_double_terminal(tmp_path):
    path = str(tmp_path / "double.jsonl")
    _spill(path, [
        {"kind": "replica_failover", "req_id": 7, "user": "u",
         "replica": "a", "to_replica": "b", "replayed_tokens": 1},
        {"kind": "finish", "req_id": 7, "user": "u", "reason": "stop",
         "tokens": 3},
        {"kind": "finish", "req_id": 7, "user": "u", "reason": "stop",
         "tokens": 3},
    ])
    assert journal_main(["check", path]) == 1


def test_fleet_router_wal_recovery(tmp_path):
    """Fleet-wide recovery: the ROUTER owns the WAL; after a crash its
    streams re-place across the surviving members and the roll-up audit
    over both router generations is clean."""
    import dataclasses

    from ollamamq_tpu.fleet import FleetRouter, LocalMember

    def build(wal_dir, spill, members_n=2):
        ecfg = EngineConfig(model="test-tiny", max_slots=4,
                            wal_dir=wal_dir, wal_fsync_ms=2.0,
                            journal_file=spill)
        member_cfg = dataclasses.replace(ecfg, wal_dir=None,
                                         journal_file=None)
        members = [LocalMember(f"r{i}", FakeEngine(
            member_cfg, blocklist_path=None, token_latency_s=0.02))
            for i in range(members_n)]
        router = FleetRouter(members, ecfg, blocklist_path=None,
                             probe_period_s=0.05, eject_heartbeat_s=5.0,
                             reprobe_backoff_s=0.1, evac_grace_s=0.5)
        router.start()
        return router

    wal_dir = str(tmp_path / "wal")
    r1 = build(wal_dir, str(tmp_path / "r1.jsonl"))
    req = r1.enqueue_request("fl", "", "test-tiny", prompt_tokens=[1, 2],
                             sampling=SamplingParams(max_tokens=10))
    rid = req.req_id
    # The router-side Request never fills generated_ids (members own
    # generation); progress reads off the durability tap's frame log.
    live = r1.durability.registry.find(rid)
    deadline = time.monotonic() + 30
    while live.token_count() < 3 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert live.token_count() >= 3
    # Crash the whole router process-equivalent: loop + members die.
    r1._running = False
    r1.notify()
    for m in r1.members:
        m.engine._running = False
        m.engine.notify()
    time.sleep(0.15)
    wal = r1.durability.wal
    wal._stop.set()
    if wal._flusher is not None:
        wal._flusher.join(timeout=5)
    r1.journal.close()

    crash_dir = str(tmp_path / "wal-crash")
    shutil.copytree(wal_dir, crash_dir)
    r1.stop()  # tear the corpse down (threads), post-snapshot
    r2 = build(crash_dir, str(tmp_path / "r2.jsonl"))
    try:
        assert r2.durability.recovered_streams == 1
        entry = r2.durability.registry.find(rid)
        deadline = time.monotonic() + 30
        while entry.terminal is None and time.monotonic() < deadline:
            time.sleep(0.02)
        frames, term = entry.snapshot(0)
        assert term is not None
        assert "".join(t for _, t in frames) \
            == "".join(f"word{i} " for i in range(10))
    finally:
        r2.stop()
    assert journal_main(["check", str(tmp_path / "r1.jsonl"),
                         str(tmp_path / "r2.jsonl")]) == 0


# ------------------------------------------------------------------- soak
@pytest.mark.slow
def test_recovery_crash_point_soak(tmp_path):
    """Randomized crash points x many streams: every recovery completes
    every stream byte-identical, never duplicates a token, and the WAL
    survives arbitrary interruption points."""
    rng = random.Random(11)
    for trial in range(6):
        base = tmp_path / f"t{trial}"
        base.mkdir()
        eng = _fake(base, latency=0.01)
        reqs = [eng.enqueue_request(
            f"u{i % 3}", "", "test-tiny", prompt_tokens=[1] * (2 + i),
            sampling=SamplingParams(max_tokens=rng.randrange(4, 14)))
            for i in range(5)]
        target = rng.randrange(1, 30)
        deadline = time.monotonic() + 30
        while sum(len(r.generated_ids) for r in reqs) < target \
                and time.monotonic() < deadline:
            time.sleep(0.002)
        _crash(eng)
        crash_dir = _crash_copy(eng, base)
        eng2 = _fake(base.joinpath("x"), wal_dir=crash_dir)
        try:
            for r in reqs:
                entry = eng2.durability.registry.find(r.req_id)
                assert entry is not None
                deadline = time.monotonic() + 60
                while entry.terminal is None \
                        and time.monotonic() < deadline:
                    time.sleep(0.01)
                frames, term = entry.snapshot(0)
                assert term is not None, f"trial {trial} req {r.req_id}"
                want = min(r.sampling.max_tokens, 16)
                assert [i for i, _ in frames if i >= 0] \
                    == list(range(1, want + 1))
                assert "".join(t for _, t in frames) \
                    == "".join(f"word{i} " for i in range(want))
            assert check_invariants(eng2.journal.tail(None),
                                    starve_after=None) == []
        finally:
            eng2.stop()
