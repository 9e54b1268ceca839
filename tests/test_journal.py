"""Engine flight recorder: scheduler decision journal, explainability,
deterministic replay, and invariant checking.

Load-bearing guarantees pinned here:
  - the journal ring stays O(capacity) no matter how many records land;
  - the event schema is loud: unknown kinds / missing-or-unknown fields
    raise at the instrumentation site, never at incident-review time;
  - a seeded chaos run recorded via the harness replays with an
    IDENTICAL decision sequence, and a tampered recording is detected;
  - the invariant checker catches each violation class (pages conserved,
    slot double-assignment, VIP victim, under-bound shed, starvation)
    and stays CLEAN over randomized overload traffic on a real
    ModelRuntime with injected allocation pressure;
  - /debug/journal filter semantics, the per-request journal slice in
    /debug/requests/{id}, and the bundle's journal section;
  - --journal-file spill rotates at the size bound, keeping N files;
  - engine.retry_after_s is clamped on cold start (no completions yet).
"""

import asyncio
import glob
import itertools
import random
import tempfile

import jax.numpy as jnp
import pytest

from ollamamq_tpu.config import MODEL_CONFIGS, EngineConfig
from ollamamq_tpu.core import MQCore
from ollamamq_tpu.engine.engine import ModelRuntime
from ollamamq_tpu.engine.fake import FakeEngine
from ollamamq_tpu.engine.request import FinishReason, Request
from ollamamq_tpu.ops.sampling import SamplingParams
from ollamamq_tpu.telemetry.journal import (DECISION_KINDS, EVENTS,
                                            Journal, JournalError,
                                            batch_stats, check_invariants,
                                            decision_signature, explain,
                                            fair_share_audit, load_jsonl)
from ollamamq_tpu.tools.journal import record_chaos, replay_journal

_IDS = itertools.count(1)


# ------------------------------------------------------------------ schema
def test_ring_stays_bounded():
    j = Journal(capacity=64)
    for i in range(1000):
        j.record("admit", req_id=i, user="u", queued=i)
    snap = j.snapshot()
    assert snap["size"] == 64
    assert snap["seq"] == 1000
    assert snap["evicted"] == 936
    assert len(j.tail(None)) == 64
    # Newest-last, oldest evicted.
    assert j.tail(None)[-1]["req_id"] == 999
    assert j.tail(None)[0]["req_id"] == 936


def test_schema_validation_is_loud():
    j = Journal(capacity=8)
    with pytest.raises(JournalError):
        j.record("warp_speed", req_id=1)
    with pytest.raises(JournalError):
        j.record("shed", user="u")  # missing required 'reason'
    with pytest.raises(JournalError):
        j.record("admit", queued=1, bogus_field=2)  # unknown field
    # Every vocabulary kind has a field spec and a working explanation.
    assert j.seq == 0  # rejected records never land


_MINIMAL = {
    "enqueue": dict(n_prompt=4, queued=1),
    "admit": dict(queued=0),
    "sched": dict(policy="srpt", point="admit", candidates=3, score=5.25,
                  predicted=6),
    "place": dict(runtime="m"),
    "shed": dict(reason="queue_full", queued=9, limit=8, retry_after_s=2.0),
    "batch": dict(slots=[0, 1], bucket=32, batch_size=4, tokens=40,
                  occupancy=0.5),
    "chunk": dict(slot=0, pos=64, tokens=32),
    "install": dict(slot=1, n_prompt=7),
    "speculate": dict(slot=1, k=4, source="ngram"),
    "spec_verify": dict(slot=1, proposed=4, accepted=2, rolled_back=2),
    "spec_rollback": dict(slot=1, kv_before=20, kv_after=18, freed=1,
                          free=11, used=19, cached=1, pool=31),
    "preempt": dict(slot=2, why="kv_pressure", n=1, free_pages=0,
                    victim_served=9, vip="alice"),
    "kv_stall": dict(slot=0, free_pages=0),
    "requeue": dict(why="preempt"),
    "retry": dict(n=1, error="boom"),
    "poison": dict(retries=1),
    "deadline_drop": dict(slack_ms=12.5),
    "finish": dict(reason="stop", slot=0, tokens=8),
    "page_alloc": dict(n=2, free=10, used=20, cached=1, pool=31),
    "page_free": dict(n=2, free=12, used=18, cached=1, pool=31),
    "page_evict": dict(n=1, free=13, used=18, cached=0, pool=31),
    "broadcast": dict(op="decode", wire_seq=5),
    "rebuild": dict(),
    "replica_eject": dict(replica="r1", why="stale_heartbeat", victims=2,
                          heartbeat_age_s=4.0, backoff_s=0.5),
    "replica_failover": dict(replica="r1", to_replica="r0",
                             replayed_tokens=3),
    "replica_drain": dict(replica="r0", inflight=2, timeout_s=30.0),
    "replica_join": dict(replica="r1", why="heal"),
    "tier_place": dict(tier="interactive", cls="vip", replica="r0",
                       overflow=None),
    "tier_overflow": dict(from_tier="interactive", to_tier="bulk",
                          why="burn", burn=14.4, queued=3, replica="r1"),
    "tier_regroup": dict(replica="r1", phase="done", from_tier="bulk",
                         to_tier="interactive", why="mix_shift", mix=0.8,
                         tp_from=1, tp_to=4),
    "migrate_export": dict(replica="r1", tokens=5, kv_len=21, pages=3,
                           bytes=4096),
    "migrate_import": dict(replica="r1", to_replica="r0", tokens=5,
                           pages=3, bytes=4096),
    "migrate_abort": dict(replica="r1", to_replica="r0",
                          why="transfer_failed"),
    "scale_up": dict(replica="a0", phase="done", tier="bulk", why="wake",
                     burn=0.0, queued=3, fleet=2, spawn_ms=412.0),
    "scale_down": dict(replica="r1", phase="start", tier="bulk",
                       why="idle", burn=0.0, queued=0, fleet=2,
                       inflight=1),
    "preempt_notice": dict(replica="r1", tier="bulk", notice_s=30.0,
                           why="fault_plan", inflight=1),
    "wal_admit": dict(fsync_ms=1.25, n_prompt=16),
    "recover_replay": dict(tokens=5, outcome="replayed", n_prompt=16,
                           wal_rid=3),
    "standby_sync": dict(seq=42, lag=0, records=14, epoch=2,
                         why="snapshot"),
    "router_takeover": dict(phase="done", why="primary_dead", epoch=3,
                            from_epoch=2, streams=2, migrated=0,
                            replayed=2, takeover_ms=812.5, lag=0),
    "epoch_fence": dict(epoch=3, stale_epoch=2, path="placement",
                        caller="router"),
    "compile": dict(site="ragged", key="('ragged', 256, 0)",
                    wall_ms=812.5, t0=1.7e9, trace_ms=300.0, lower_ms=200.0,
                    backend_ms=250.0, first_run_ms=62.5, programs=1,
                    cache="hit"),
}


def test_every_kind_records_and_explains():
    assert set(_MINIMAL) == set(EVENTS)
    j = Journal(capacity=64)
    for kind, fields in _MINIMAL.items():
        rec = j.record(kind, req_id=3, user="bob", model="m", **fields)
        text = explain(rec)
        assert isinstance(text, str) and text
    assert j.seq == len(EVENTS)
    # The TUI line tracks the newest DECISION kind (the epoch fence is
    # the last one in the vocabulary walk above); page/broadcast/
    # rebuild bookkeeping must not displace it.
    assert "stale-epoch router call fenced" in j.last_summary()
    j.record("page_alloc", model="m", n=1, free=9, used=21, cached=1,
             pool=31)
    assert "stale-epoch router call fenced" in j.last_summary()


def test_tail_filters():
    j = Journal(capacity=128)
    for i in range(10):
        j.record("admit", req_id=i, user=f"u{i % 2}", queued=i)
    j.record("shed", user="u0", reason="queue_full", queued=9, limit=9)
    assert len(j.tail(n=3)) == 3
    assert all(r["user"] == "u1" for r in j.tail(None, user="u1"))
    assert len(j.tail(None, user="u1")) == 5
    assert [r["kind"] for r in j.tail(None, kind="shed")] == ["shed"]
    assert len(j.tail(None, req_id=7)) == 1


# ------------------------------------------------------------ file spill
def test_journal_file_rotation(tmp_path):
    path = str(tmp_path / "j.jsonl")
    j = Journal(capacity=32, path=path, rotate_bytes=4000, keep=2)
    for i in range(400):
        j.record("admit", req_id=i, user="u", queued=i)
    j.close()
    files = sorted(glob.glob(path + "*"))
    # Current file + at most `keep` rotated generations, each bounded.
    assert path in files
    assert len(files) <= 3
    assert any(f.endswith(".1") for f in files)
    import os

    for f in files:
        assert os.path.getsize(f) < 4000 + 500  # one record of slack
    # Every surviving file parses; the header meta line is skipped.
    meta, records = load_jsonl(path)
    assert records and all(r["kind"] == "admit" for r in records)
    # Rotated files carry a fresh meta header too.
    meta1, recs1 = load_jsonl(files[-1] if files[-1] != path else files[0])
    assert recs1


# ---------------------------------------------------- record/replay loop
def test_chaos_record_replays_deterministically(tmp_path):
    path = str(tmp_path / "chaos.jsonl")
    journal = record_chaos(path, seed=7, requests=32)
    kinds = {r["kind"] for r in journal.tail(None)}
    # The run must actually exercise degradation: sheds (bounded queue),
    # retries + poisons (injected step faults), and normal service.
    assert {"enqueue", "admit", "place", "install", "finish",
            "shed", "retry", "poison"} <= kinds
    # Every shed decision carries the inputs that justify it.
    for r in journal.tail(None, kind="shed"):
        assert r["queued"] >= r["limit"]
        assert "retry_after_s" in r
    # The recorded artifact is invariant-clean...
    assert check_invariants(journal.tail(None)) == []
    # ...and replays with an IDENTICAL decision sequence.
    ok, rec_sig, rep_sig, div = replay_journal(path)
    assert ok, f"diverged at {div}: {rec_sig[div:div+2]} vs {rep_sig[div:div+2]}"
    assert len(rec_sig) > 50


def test_replay_detects_tampered_recording(tmp_path):
    path = str(tmp_path / "chaos.jsonl")
    record_chaos(path, seed=3, requests=24)
    lines = open(path, encoding="utf-8").read().splitlines()
    # Flip one decision: the first finish becomes a different reason.
    import json as _json

    for i, line in enumerate(lines):
        obj = _json.loads(line)
        if obj.get("kind") == "finish":
            obj["reason"] = "cancelled" if obj["reason"] != "cancelled" \
                else "length"
            lines[i] = _json.dumps(obj)
            break
    open(path, "w", encoding="utf-8").write("\n".join(lines) + "\n")
    ok, _rec, _rep, div = replay_journal(path)
    assert not ok and div is not None


# ------------------------------------------------------------ invariants
def test_invariant_checker_catches_each_class():
    # 1. pages not conserved.
    bad = check_invariants([
        {"seq": 0, "kind": "page_alloc", "n": 2, "free": 5, "used": 5,
         "cached": 0, "pool": 31}])
    assert len(bad) == 1 and "not conserved" in bad[0]
    # 2. slot double-assignment.
    bad = check_invariants([
        {"seq": 0, "kind": "install", "model": "m", "slot": 1, "req_id": 1},
        {"seq": 1, "kind": "install", "model": "m", "slot": 1, "req_id": 2}])
    assert len(bad) == 1 and "double-assignment" in bad[0]
    # ...but a finish (or preempt) in between releases the slot.
    assert check_invariants([
        {"seq": 0, "kind": "install", "model": "m", "slot": 1, "req_id": 1},
        {"seq": 1, "kind": "finish", "model": "m", "slot": 1, "req_id": 1,
         "reason": "stop"},
        {"seq": 2, "kind": "install", "model": "m", "slot": 1,
         "req_id": 2}]) == []
    # 3. the VIP must never be the victim.
    bad = check_invariants([
        {"seq": 0, "kind": "preempt", "req_id": 4, "user": "alice",
         "slot": 0, "why": "kv_pressure", "vip": "alice"}])
    assert len(bad) == 1 and "VIP" in bad[0]
    assert check_invariants([
        {"seq": 0, "kind": "preempt", "req_id": 4, "user": "bob",
         "slot": 0, "why": "kv_pressure", "vip": "alice"}]) == []
    # 4. shed only when bounds exceeded.
    bad = check_invariants([
        {"seq": 0, "kind": "shed", "user": "u", "reason": "queue_full",
         "queued": 3, "limit": 8}])
    assert len(bad) == 1 and "below bound" in bad[0]
    # 5. starvation: admitted, then >= N batches with no progress.
    recs = [{"seq": 0, "kind": "admit", "req_id": 9, "queued": 1}]
    recs += [{"seq": 1 + i, "kind": "batch", "slots": [0], "bucket": 32,
              "batch_size": 1, "tokens": 8, "occupancy": 0.5}
             for i in range(60)]
    bad = check_invariants(recs)
    assert len(bad) == 1 and "starved" in bad[0]
    # Progress (install) clears it.
    recs.insert(30, {"seq": 99, "kind": "install", "req_id": 9, "slot": 0})
    assert check_invariants(recs) == []


PS = 8


def _overload_rt(**kw) -> ModelRuntime:
    defaults = dict(model="test-tiny", max_slots=3, num_pages=24,
                    page_size=PS, max_pages_per_seq=8,
                    max_new_tokens=8,
                    decode_steps_per_iter=2, preempt=True)
    defaults.update(kw)
    rt = ModelRuntime("test-tiny", MODEL_CONFIGS["test-tiny"],
                      EngineConfig(**defaults), dtype=jnp.float32)
    rt.tokenizer.eos_id = -1  # deterministic full-length streams
    return rt


@pytest.mark.parametrize("seed", [0, 1])
def test_invariant_fuzz_randomized_overload(seed):
    """Randomized overload traffic on a REAL runtime — arrival storms
    over an undersized page pool with injected allocation pressure, so
    preemptions, kv_stalls, page evictions, and stall-breaks all fire —
    and the journal must come out invariant-clean."""
    from ollamamq_tpu.engine.engine import drop_expired
    from ollamamq_tpu.testing.faults import FaultPlan

    rng = random.Random(seed)
    rt = _overload_rt()
    rt.fault_plan = FaultPlan([
        {"site": "extend", "kind": "alloc_fail", "every": 4},
    ], seed=seed)
    journal = Journal(capacity=8192)
    rt.journal = journal
    core = MQCore(None)

    def requeue(req):
        if req.expired():
            drop_expired(req, core, rt.name, journal=journal)
            return False
        rt.pending_prefill.appendleft(req)
        return True

    rt.on_preempt = requeue
    issued = 0
    reqs = []
    guard = 0
    while True:
        while issued < 14 and len(rt.pending_prefill) < 6 \
                and rng.random() < 0.7:
            n = rng.randrange(4, 40)
            req = Request(next(_IDS), f"u{issued % 4}", rt.name,
                          [rng.randrange(3, 400) for _ in range(n)],
                          SamplingParams(max_tokens=rng.randrange(2, 10)))
            req._inc_decode = rt.tokenizer.make_incremental_decoder()
            reqs.append(req)
            rt.pending_prefill.append(req)
            issued += 1
        if not rt.step_ragged(core) \
                and any(r is not None for r in rt.slot_req):
            rt.step_decode(core, k_steps=2)
        if issued >= 14 and all(r.stats.finished_at for r in reqs):
            break
        guard += 1
        assert guard < 20000, "overload fuzz wedged"
    recs = journal.tail(None)
    assert {"batch", "install", "finish", "page_alloc",
            "page_free"} <= {r["kind"] for r in recs}
    assert check_invariants(recs) == []
    # Batch stats are well-formed: padding waste is a real fraction.
    bs = batch_stats(recs)
    assert bs["batches"] > 0
    assert 0.0 <= bs["padding_waste"] < 1.0
    assert bs["real_tokens"] <= bs["padded_tokens"]


# ---------------------------------------------------------- HTTP surface
def _api(fn):
    def wrapper():
        from aiohttp.test_utils import TestClient, TestServer

        from ollamamq_tpu.server.app import Server

        async def main():
            with tempfile.TemporaryDirectory() as tmp:
                eng = FakeEngine(
                    EngineConfig(model="test-tiny", max_slots=8),
                    models={"test-tiny": None},
                    blocklist_path=f"{tmp}/blocked.json")
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

    wrapper.__name__ = fn.__name__
    wrapper.__doc__ = fn.__doc__
    return wrapper


async def _gen(client, user="alice", prompt="hi"):
    r = await client.post("/api/generate", json={
        "model": "test-tiny", "prompt": prompt, "stream": False},
        headers={"X-User-ID": user})
    assert r.status == 200
    return r


@_api
async def test_debug_journal_filters(client):
    await _gen(client, user="alice")
    await _gen(client, user="bob")
    r = await client.get("/debug/journal")
    assert r.status == 200
    body = await r.json()
    assert body["capacity"] == 2048
    assert body["size"] == len(body["events"]) or body["size"] > 200
    kinds = {e["kind"] for e in body["events"]}
    assert {"enqueue", "admit", "place", "install", "finish"} <= kinds
    # kind filter.
    r = await client.get("/debug/journal?kind=enqueue")
    evs = (await r.json())["events"]
    assert evs and all(e["kind"] == "enqueue" for e in evs)
    # user filter.
    r = await client.get("/debug/journal?user=bob")
    evs = (await r.json())["events"]
    assert evs and all(e["user"] == "bob" for e in evs)
    # req_id filter follows one request through its lifecycle.
    rid = evs[0]["req_id"]
    r = await client.get(f"/debug/journal?req_id={rid}")
    evs = (await r.json())["events"]
    assert {"enqueue", "admit", "place"} <= {e["kind"] for e in evs}
    assert all(e["req_id"] == rid for e in evs)
    # n bounds the tail.
    r = await client.get("/debug/journal?n=2")
    assert len((await r.json())["events"]) == 2
    # Unknown kind is a client error naming the vocabulary, not [].
    r = await client.get("/debug/journal?kind=warp")
    assert r.status == 400
    assert "vocabulary" in (await r.json())["error"]
    # Junk n / req_id are client errors too.
    assert (await client.get("/debug/journal?n=x")).status == 400
    assert (await client.get("/debug/journal?req_id=x")).status == 400


@_api
async def test_request_timeline_includes_journal_slice(client):
    await _gen(client)
    r = await client.get("/debug/journal?kind=finish")
    rid = (await r.json())["events"][-1]["req_id"]
    r = await client.get(f"/debug/requests/{rid}")
    assert r.status == 200
    body = await r.json()
    assert "journal" in body
    assert all(e["req_id"] == rid for e in body["journal"])
    assert {"enqueue", "admit", "place", "install", "finish"} <= {
        e["kind"] for e in body["journal"]}


@_api
async def test_bundle_has_journal_section(client):
    await _gen(client)
    r = await client.get("/debug/bundle")
    assert r.status == 200
    body = await r.json()
    assert "journal" in body
    assert body["journal"]["capacity"] == 2048
    assert body["journal"]["events"]


# ------------------------------------------------------------- satellites
def test_retry_after_cold_start_is_clamped():
    eng = FakeEngine(EngineConfig(model="test-tiny"), blocklist_path=None)
    # No completions observed: whatever the queue depth claims, the
    # estimate stays in a small fixed window instead of extrapolating.
    eng.core.total_queued = lambda: 500
    assert 2.0 <= eng.retry_after_s() <= 10.0
    eng.core.total_queued = lambda: 0
    assert 2.0 <= eng.retry_after_s() <= 10.0


def test_health_monitor_raises_invariant_alert():
    from ollamamq_tpu.engine.health import HealthMonitor
    from ollamamq_tpu.telemetry.slo import AlertManager

    class Eng:
        alerts = AlertManager()
        journal = Journal(capacity=32)

    eng = Eng()
    mon = HealthMonitor.__new__(HealthMonitor)
    mon.engine = eng
    mon._check_journal_invariants()
    assert not any(a.name == "journal_invariant"
                   for a in eng.alerts.active())
    # A pages-conservation bug lands in the journal -> alert fires.
    eng.journal.record("page_alloc", model="m", n=1, free=1, used=1,
                       cached=1, pool=99)
    mon._check_journal_invariants()
    firing = [a for a in eng.alerts.active()
              if a.name == "journal_invariant"]
    assert firing and "not conserved" in firing[0].message
    # Violation ages out of the ring -> resolves.
    eng.journal = Journal(capacity=32)
    mon.engine = eng
    mon._check_journal_invariants()
    assert not any(a.name == "journal_invariant"
                   for a in eng.alerts.active())


def test_fair_share_audit_and_signature_shapes():
    path_free = {"free": 1, "used": 1, "cached": 0, "pool": 2}
    j = Journal(capacity=64)
    j.record("enqueue", req_id=1, user="a", n_prompt=3, queued=1)
    j.record("shed", user="b", reason="queue_full", queued=9, limit=9)
    j.record("page_alloc", model="m", n=1, **path_free)
    audit = fair_share_audit(j.tail(None))
    assert audit["a"]["enqueued"] == 1
    assert audit["b"]["shed"] == 1
    sig = decision_signature(j.tail(None))
    # Page events are not part of the replay-decision stream.
    assert [s[0] for s in sig] == ["enqueue", "shed"]
    assert all(s[0] in DECISION_KINDS for s in sig)
