"""Fleet router: dispatcher-over-engines with health-driven failover,
journal-backed stream replay, and zero-drop draining.

The robustness contract under test: a replica dying (or being ejected,
or drained) mid-stream is INVISIBLE to the client beyond latency — the
stream continues byte-identically on another replica, nothing is
dropped, and the decision journal explains every eject/failover/drain
with the inputs that justified it.
"""

import asyncio
import dataclasses
import json
import threading
import time

import pytest

from ollamamq_tpu.config import EngineConfig
from ollamamq_tpu.engine.fake import FakeEngine
from ollamamq_tpu.engine.request import FinishReason
from ollamamq_tpu.fleet import FleetRouter, HttpMember, LocalMember
from ollamamq_tpu.ops.sampling import SamplingParams
from ollamamq_tpu.telemetry import schema as tm
from ollamamq_tpu.testing.faults import FaultPlan
from ollamamq_tpu.tools.journal import check_no_dropped_streams
from testutil import _text, collect, free_port

TINY = dict(model="test-tiny", max_slots=4, num_pages=64, page_size=8,
            max_pages_per_seq=8,
            decode_steps_per_iter=2)

FAST = dict(probe_period_s=0.05, eject_heartbeat_s=5.0,
            reprobe_backoff_s=0.1, evac_grace_s=1.0)


def _fake_fleet(n=2, token_latency_s=0.0, plan=None, router_kw=None,
                **ecfg_over):
    cfg = dict(TINY)
    cfg.update(ecfg_over)
    ecfg = EngineConfig(fault_plan=plan, **cfg)
    member_cfg = dataclasses.replace(ecfg, fault_plan=None, max_queued=0,
                                     max_queued_per_user=0)
    members = [
        LocalMember(f"r{i}", FakeEngine(member_cfg, blocklist_path=None,
                                        token_latency_s=token_latency_s))
        for i in range(n)
    ]
    kw = dict(FAST)
    kw.update(router_kw or {})
    router = FleetRouter(members, ecfg, blocklist_path=None, **kw)
    router.start()
    return router


def _tpu_fleet(n=2, plan=None, router_kw=None, **ecfg_over):
    import jax.numpy as jnp

    from ollamamq_tpu.engine.engine import TPUEngine

    cfg = dict(TINY)
    cfg.update(ecfg_over)
    ecfg = EngineConfig(fault_plan=plan, **cfg)
    member_cfg = dataclasses.replace(ecfg, fault_plan=None, max_queued=0,
                                     max_queued_per_user=0)
    members = [
        LocalMember(f"r{i}", TPUEngine(member_cfg,
                                       models={"test-tiny": None},
                                       blocklist_path=None,
                                       dtype=jnp.float32))
        for i in range(n)
    ]
    kw = dict(FAST)
    kw.update(router_kw or {})
    router = FleetRouter(members, ecfg, blocklist_path=None, **kw)
    router.start()
    return router


def _run(router, user, prompt="the quick brown fox jumps over", max_tokens=8,
         **sp_kw):
    rt = router.resolve_runtime("test-tiny")
    if rt is not None:
        tokens = rt.tokenizer.encode(prompt)
    else:
        from ollamamq_tpu.engine.tokenizer import ByteTokenizer

        tokens = ByteTokenizer().encode(prompt)
    return router.enqueue_request(
        user, "", "test-tiny", prompt_tokens=tokens,
        sampling=SamplingParams(max_tokens=max_tokens, **sp_kw),
        raw_prompt=prompt)


def _serving_member(router, req):
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        for f in list(router.flights):
            if f.req is req and f.member is not None:
                return f.member
        time.sleep(0.01)
    raise TimeoutError("request never placed")


# ------------------------------------------------------------ basic routing
def test_least_loaded_placement_spreads_across_members():
    router = _fake_fleet(n=2)
    try:
        reqs = [_run(router, f"u{i}") for i in range(8)]
        for r in reqs:
            items = collect(r)
            assert items[-1].kind == "done"
            assert _text(items).startswith("word0 ")
        placed = {rec["runtime"] for rec in router.journal.tail(
            None, kind="place")}
        assert placed == {"r0", "r1"}, placed
        assert check_no_dropped_streams(router.journal.tail(None)) == []
    finally:
        router.stop()


def test_bounded_admission_sheds_fleet_wide_with_aggregate_retry_after():
    from ollamamq_tpu.engine.engine import QueueFullError

    # 2 members x 1 slot, slow tokens: the 3rd+ request queues at the
    # ROUTER; the per-user cap sheds the 4th with a fleet-derived
    # Retry-After.
    router = _fake_fleet(n=2, token_latency_s=0.2, max_slots=1,
                         max_queued_per_user=1)
    try:
        reqs = []
        with pytest.raises(QueueFullError) as ei:
            for _ in range(11):  # the cap must hit while members serve
                reqs.append(_run(router, "greedy", max_tokens=4))
                time.sleep(0.02)  # let earlier ones place (cap is on the
                #                   ROUTER queue, not on in-flight work)
        assert ei.value.scope == "user_queue_full"
        assert 1 <= ei.value.retry_after_s <= 300
        sheds = router.journal.tail(None, kind="shed")
        assert sheds and sheds[-1]["reason"] == "user_queue_full"
        for r in reqs:
            collect(r)
        # Fleet-wide aggregation: the ROUTER tracer observed every
        # member's completions (this is what keeps Retry-After honest
        # when one replica is ejected — the rate is the fleet's, not one
        # member's share).
        assert len(router.tracer.finish_times) == len(reqs)
    finally:
        router.stop()


# ------------------------------------------------------- failover (local)
@pytest.mark.parametrize(
    "prefix_cache,spec",
    [(False, False), (True, False), (True, True)],
    ids=["plain", "cache", "cache+spec"])
def test_failover_byte_identity_fuzz(prefix_cache, spec):
    """Kill a replica mid-stream: every stream — failed-over ones
    included — matches the single-replica golden run byte for byte,
    across prefix cache on/off and speculative decoding on/off."""
    over = dict(prefix_cache=prefix_cache, spec=spec, spec_k=2)
    # Repetitive prompts give the n-gram proposer drafts to verify and
    # the prefix cache shared pages to pin.
    prompts = [
        "the cat sat on the mat the cat sat on the",
        "the cat sat on the mat the cat sat on a",
        "pack my box with five dozen jugs",
        "the cat sat on the mat the cat sat on my",
        "pack my box with five dozen mugs",
        "the cat sat on the mat the cat",
    ]
    # 40 tokens a stream: a crashed loop ends after its CURRENT iteration,
    # which may be a fused scan of eight steps — streams of 12 tokens, the
    # victim picked at 2, could all finish inside it, and then nothing is
    # left to eject or to migrate (red in one run of two until PR 61).
    golden = _tpu_fleet(n=1, **over)
    try:
        gtexts = [_text(collect(_run(golden, f"u{i % 3}", p,
                                     max_tokens=40)))
                  for i, p in enumerate(prompts)]
    finally:
        golden.stop()

    router = _tpu_fleet(n=2, **over)
    try:
        reqs = [_run(router, f"u{i % 3}", p, max_tokens=40)
                for i, p in enumerate(prompts)]
        # Wait for real mid-stream state (some tokens emitted), then
        # kill whichever member is serving the most streams.
        deadline = time.monotonic() + 120
        victim = None
        while time.monotonic() < deadline and victim is None:
            for f in list(router.flights):
                if f.attempt is not None \
                        and len(f.attempt.req.generated_ids) >= 2:
                    victim = f.member
                    break
            time.sleep(0.01)
        assert victim is not None, "no stream reached mid-generation"
        victim.crash()
        texts = [_text(collect(r)) for r in reqs]
        assert texts == gtexts
        recs = router.journal.tail(None)
        assert any(r["kind"] == "replica_eject" for r in recs)
        # Recovery is migration-first (zero recomputed tokens), with
        # recompute failover as the fallback — either way the victim
        # streams above continued byte-identically.
        assert router.migration_count + router.failover_count >= 1
        assert check_no_dropped_streams(recs) == []
        from ollamamq_tpu.telemetry.journal import check_invariants

        assert check_invariants(recs) == []
    finally:
        router.stop()


def test_affinity_placement_routes_to_cached_replica():
    router = _tpu_fleet(n=2, prefix_cache=True)
    try:
        prompt = "shared system preamble for affinity routing tests ok"
        collect(_run(router, "aff", prompt, max_tokens=4))
        first = router.journal.tail(None, kind="place")[-1]["runtime"]
        hits0 = tm.FLEET_AFFINITY_HITS_TOTAL.value
        collect(_run(router, "aff", prompt, max_tokens=4))
        second = router.journal.tail(None, kind="place")[-1]["runtime"]
        assert second == first  # the radix tree holds the prefix there
        assert tm.FLEET_AFFINITY_HITS_TOTAL.value > hits0
    finally:
        router.stop()


# ------------------------------------------------- eject / heal / rejoin
def test_ejected_replica_rejoins_after_heal():
    """faults.py site "replica" device_loss with heal_after_s: the member
    crashes, its stream fails over, the router's backoff re-probe keeps
    it ejected until the plan heals, then it rejoins — and the watchdog
    replica_stale alert fires while it is out and resolves after."""
    plan = FaultPlan([{"site": "replica", "kind": "device_loss",
                       "at": [1], "heal_after_s": 0.6}])
    router = _fake_fleet(n=2, token_latency_s=0.05, plan=plan)
    try:
        req = _run(router, "heal", max_tokens=16)
        deadline = time.monotonic() + 30
        while router.fleet_counts()["ejected"] == 0 \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        assert router.fleet_counts()["ejected"] == 1
        assert router.stale_replicas() == ["r0"]
        before = tm.WATCHDOG_STALLS_TOTAL.labels(kind="replica").value
        router.health.check_once()
        assert any(a.name == "replica_stale"
                   for a in router.alerts.active())
        assert tm.WATCHDOG_STALLS_TOTAL.labels(
            kind="replica").value == before + 1
        items = collect(req)
        assert items[-1].kind == "done"
        assert _text(items).startswith("word0 word1 ")
        deadline = time.monotonic() + 30
        while router.fleet_counts()["healthy"] < 2 \
                and time.monotonic() < deadline:
            time.sleep(0.05)
        assert router.fleet_counts() == {"healthy": 2, "ejected": 0,
                                         "draining": 0}
        joins = [r for r in router.journal.tail(None, kind="replica_join")
                 if r.get("why") == "heal"]
        assert joins and joins[-1]["replica"] == "r0"
        router.health.check_once()
        assert not any(a.name == "replica_stale"
                       for a in router.alerts.active())
    finally:
        router.stop()


def test_process_stall_does_not_eject_in_process_members():
    """A thread that keeps the GIL for longer than the eject heartbeat
    (on the chip: a sibling member lowering a large Pallas kernel) holds
    up the router AND every in-process member's loop. Their stale ticks
    are then evidence of nothing: nobody is ejected, and the members
    show fresh heartbeats once the interpreter runs again."""
    router = _fake_fleet(n=2, router_kw=dict(eject_heartbeat_s=0.3))
    try:
        time.sleep(0.3)  # a few clean probes first
        t0 = time.monotonic()
        while time.monotonic() - t0 < 1.0:
            sum(range(20_000_000))  # one C call: the GIL is never offered
        time.sleep(0.5)
        assert [m.eject_count for m in router.members] == [0, 0]
        assert router.fleet_counts()["healthy"] == 2
    finally:
        router.stop()


def test_member_in_a_compiling_step_is_not_stale():
    """A step that pays an XLA compile blocks its loop thread for as long
    as the compile takes (tens of seconds for a step program on a TPU):
    the member's heartbeat reads fresh meanwhile. A compile that outlives
    the grace is a wedge again."""
    eng = FakeEngine(EngineConfig(**TINY), blocklist_path=None)
    mem = LocalMember("r0", eng)
    rt = next(iter(eng.runtimes.values()))
    eng.last_tick_at = time.monotonic() - 10.0  # the loop is not ticking
    assert not eng.compiling() and mem.heartbeat_age() > 9.0
    rt.compiling_since = time.monotonic()  # as _sp_note_compile sets it
    assert eng.compiling() and mem.heartbeat_age() == 0.0
    rt.compiling_since = time.monotonic() - eng.COMPILE_GRACE_S - 1.0
    assert not eng.compiling() and mem.heartbeat_age() > 9.0


def test_slow_fault_forces_stale_heartbeat_eject_and_rejoin():
    plan = FaultPlan([{"site": "replica", "kind": "slow", "delay_s": 0.5,
                       "at": [2]}])  # call 2 = member r1, first sweep
    router = _fake_fleet(n=2, token_latency_s=0.02, plan=plan,
                         router_kw=dict(eject_heartbeat_s=0.2))
    try:
        reqs = [_run(router, f"s{i}", max_tokens=10) for i in range(4)]
        for r in reqs:
            assert collect(r)[-1].kind == "done"
        recs = router.journal.tail(None)
        ejected = [r for r in recs if r["kind"] == "replica_eject"]
        assert any(r["why"] == "stale_heartbeat" for r in ejected)
        deadline = time.monotonic() + 30
        while router.fleet_counts()["healthy"] < 2 \
                and time.monotonic() < deadline:
            time.sleep(0.05)
        assert router.fleet_counts()["healthy"] == 2
        assert check_no_dropped_streams(router.journal.tail(None)) == []
    finally:
        router.stop()


# --------------------------------------------------------------- drain e2e
def test_drain_completes_all_streams_over_http():
    from aiohttp.test_utils import TestClient, TestServer

    from ollamamq_tpu.server.app import Server

    router = _fake_fleet(n=2, token_latency_s=0.05)

    async def main():
        cl = TestClient(TestServer(Server(router, timeout_s=60).build_app()))
        await cl.start_server()
        try:

            async def stream_one(i):
                texts = []
                async with cl.post("/api/generate", json={
                        "model": "test-tiny", "prompt": f"hello {i}",
                        "options": {"num_predict": 10}},
                        headers={"X-User-ID": f"d{i}"}) as resp:
                    assert resp.status == 200
                    async for line in resp.content:
                        if not line.strip():
                            continue
                        obj = json.loads(line)
                        texts.append(obj.get("response", ""))
                        if obj.get("done"):
                            assert obj["done_reason"] in ("length", "stop")
                return "".join(texts)

            tasks = [asyncio.ensure_future(stream_one(i)) for i in range(6)]
            await asyncio.sleep(0.15)  # streams are mid-flight
            resp = await cl.post("/admin/drain/r0")
            assert resp.status == 200
            body = await resp.json()
            assert body["state"] == "draining"
            texts = await asyncio.gather(*tasks)
            for t in texts:
                assert t.startswith("word0 word1 ")  # nothing dropped
            # The drained member hot-restarts and rejoins.
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                fl = await (await cl.get("/admin/fleet")).json()
                if fl["counts"] == {"healthy": 2, "ejected": 0,
                                    "draining": 0}:
                    break
                await asyncio.sleep(0.05)
            assert fl["counts"]["healthy"] == 2
            assert fl["placement"] == "affinity"
            # Unknown replica 404s; a drain of an ejected member 409s.
            assert (await cl.post("/admin/drain/nope")).status == 404
            recs = router.journal.tail(None)
            kinds = [r["kind"] for r in recs]
            assert "replica_drain" in kinds
            assert any(r["kind"] == "replica_join"
                       and r.get("why") == "drain_complete" for r in recs)
            assert check_no_dropped_streams(recs) == []
        finally:
            await cl.close()

    asyncio.run(main())
    router.stop()


# ------------------------------------------------------------ HTTP members
class _HttpBackend:
    """A real-socket engine server for HttpMember tests."""

    def __init__(self, engine):
        self.engine = engine
        self.port = free_port()
        self._loop = None
        self._runner = None
        self._started = threading.Event()
        self._thread = threading.Thread(target=self._serve, daemon=True)
        self._thread.start()
        assert self._started.wait(15), "backend server did not start"

    def _serve(self):
        from aiohttp import web

        from ollamamq_tpu.server.app import Server

        loop = asyncio.new_event_loop()
        self._loop = loop
        asyncio.set_event_loop(loop)
        app = Server(self.engine, timeout_s=30).build_app()
        runner = web.AppRunner(app, shutdown_timeout=1.0)
        loop.run_until_complete(runner.setup())
        site = web.TCPSite(runner, "127.0.0.1", self.port)
        loop.run_until_complete(site.start())
        self._runner = runner
        self._started.set()
        loop.run_forever()
        loop.run_until_complete(runner.cleanup())
        loop.close()

    @property
    def url(self):
        return f"http://127.0.0.1:{self.port}"

    def stop(self):
        """HARD kill: abort every live connection (RST, not a graceful
        shutdown that would let in-flight handlers finish streaming),
        then stop the loop — the failure mode a crashed service
        actually presents."""
        loop = self._loop
        if loop is not None and loop.is_running():

            async def _abort():
                server = getattr(self._runner, "server", None)
                for conn in list(getattr(server, "connections", None)
                                 or []):
                    t = getattr(conn, "transport", None)
                    if t is not None:
                        t.abort()

            try:
                asyncio.run_coroutine_threadsafe(_abort(),
                                                 loop).result(timeout=5)
            except Exception:
                pass
            loop.call_soon_threadsafe(loop.stop)
        self._thread.join(timeout=15)
        self.engine.stop()


def test_http_members_serve_and_fail_over():
    """The docker-compose shape: a pure router over two engine services
    speaking the existing HTTP API. Killing a backend mid-stream fails
    the victim over (text-level replay) and drops nothing."""
    member_cfg = EngineConfig(**TINY)
    backends = [
        _HttpBackend(FakeEngine(member_cfg, blocklist_path=None,
                                token_latency_s=0.05))
        for _ in range(2)
    ]
    for b in backends:
        b.engine.start()
    ecfg = EngineConfig(**TINY)
    members = [HttpMember(f"h{i}", b.url, timeout_s=30, poll_period_s=0.1)
               for i, b in enumerate(backends)]
    router = FleetRouter(members, ecfg, blocklist_path=None,
                         probe_period_s=0.05, eject_heartbeat_s=1.0,
                         reprobe_backoff_s=0.2, evac_grace_s=0.5)
    router.start()
    try:
        warm = _run(router, "h-warm", "warmup prompt", max_tokens=4)
        items = collect(warm)
        assert items[-1].kind == "done"
        assert _text(items) == "word0 word1 word2 word3 "

        req = _run(router, "h-kill", "victim prompt", max_tokens=16)
        mem = _serving_member(router, req)
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            f = next((f for f in list(router.flights) if f.req is req),
                     None)
            if f is not None and f.attempt is not None \
                    and f.attempt.n_items >= 2:
                break
            time.sleep(0.01)
        backends[int(mem.name[1])].stop()  # the service dies mid-stream
        items = collect(req, timeout=60)
        assert items[-1].kind == "done"
        # The NDJSON frames carry token_ids, so the resumed stream
        # replays in TOKEN space (Ollama `context`): the surviving
        # backend continues the word cursor where the dead one stopped —
        # byte-identical, verified token-identical, no gap.
        # (one item a frame, and a frame carries the ids one step gave
        # the stream: the claim is on ids and text, not on the item count)
        assert _text(items) == "".join(f"word{i} " for i in range(16))
        assert [t for i in items for t in i.token_ids] == list(range(1, 17))
        assert router.failover_count >= 1
        assert check_no_dropped_streams(router.journal.tail(None)) == []
    finally:
        router.stop()
        for b in backends:
            b.stop()


# ------------------------------------------------------- journal & surfaces
def test_fleet_journal_kinds_schema_and_explanations():
    from ollamamq_tpu.telemetry.journal import (Journal, JournalError,
                                                explain)

    j = Journal(capacity=64)
    j.record("replica_eject", replica="r1", why="stale_heartbeat",
             victims=3, heartbeat_age_s=4.2, backoff_s=0.5)
    j.record("replica_failover", req_id=7, user="u", replica="r1",
             to_replica="r0", replayed_tokens=5)
    j.record("replica_drain", replica="r0", inflight=2, timeout_s=30.0)
    j.record("replica_join", replica="r1", why="heal")
    j.record("migrate_export", req_id=7, user="u", replica="r1",
             tokens=5, kv_len=21, pages=3, bytes=4096)
    j.record("migrate_import", req_id=7, user="u", replica="r1",
             to_replica="r0", tokens=5, pages=3, bytes=4096)
    j.record("migrate_abort", req_id=8, user="u", replica="r1",
             why="timeout")
    texts = [explain(r) for r in j.tail(None)]
    assert "r1 ejected (stale_heartbeat)" in texts[0]
    assert "3 in-flight stream(s)" in texts[0]
    assert "failed over from replica r1 to r0" in texts[1]
    assert "replaying 5" in texts[1]
    assert "draining" in texts[2]
    assert "joined rotation (heal)" in texts[3]
    assert "exported for migration" in texts[4]
    assert "0 recomputed" in texts[5] and "r1 -> r0" in texts[5]
    assert "aborted (timeout)" in texts[6]
    assert "recompute" in texts[6]
    with pytest.raises(JournalError):
        j.record("replica_eject", why="missing-replica-field")
    with pytest.raises(JournalError):
        j.record("replica_failover", replica="r1", bogus=1)
    with pytest.raises(JournalError):
        j.record("migrate_export", replica="r1")  # missing tokens
    with pytest.raises(JournalError):
        j.record("migrate_abort", replica="r1")  # missing why


def test_no_dropped_streams_checker_flags_missing_terminal():
    clean = [
        {"kind": "replica_failover", "req_id": 4, "seq": 1},
        {"kind": "finish", "req_id": 4, "seq": 2, "reason": "length"},
    ]
    assert check_no_dropped_streams(clean) == []
    dropped = [
        {"kind": "replica_failover", "req_id": 4, "seq": 1},
        {"kind": "replica_failover", "req_id": 9, "seq": 3},
        {"kind": "deadline_drop", "req_id": 9, "seq": 4},
    ]
    bad = check_no_dropped_streams(dropped)
    assert len(bad) == 1 and "req 4" in bad[0] and "DROPPED" in bad[0]


def test_tui_brief_carries_replica_counts():
    from ollamamq_tpu.admin.tui import _engine_stats_brief

    router = _fake_fleet(n=2)
    try:
        brief = _engine_stats_brief(router)
        assert brief["replicas"] == {"healthy": 2, "ejected": 0,
                                     "draining": 0}
        assert len(brief["models"]) == 2  # one test-tiny row per member
    finally:
        router.stop()
    single = FakeEngine(EngineConfig(**TINY), blocklist_path=None)
    brief = _engine_stats_brief(single)
    assert "replicas" not in brief


def test_fleet_metrics_and_stats_surface():
    router = _fake_fleet(n=2)
    try:
        for i in range(3):
            collect(_run(router, f"m{i}"))
        snap = {}
        for label_values, child in tm.FLEET_REPLICAS.series():
            snap[label_values[0]] = child.value
        assert snap == {"healthy": 2, "ejected": 0, "draining": 0}
        stats = router.stats()
        assert stats["fleet"]["counts"]["healthy"] == 2
        assert len(stats["fleet"]["replicas"]) == 2
        assert stats["queue"] is not None
        assert len(stats["runtimes"]) == 2
        assert {r["replica"] for r in stats["runtimes"]} == {"r0", "r1"}
    finally:
        router.stop()


def test_cli_fleet_flag_validation():
    from ollamamq_tpu.cli import main

    assert main(["--replicas", "0", "--no-tui"]) == 2
    assert main(["--replicas", "-1", "--no-tui"]) == 2
    assert main(["--drain-timeout-s", "0", "--no-tui"]) == 2
    assert main(["--migrate-timeout-s", "0", "--no-tui"]) == 2
    assert main(["--migrate-timeout-s", "-1", "--no-tui"]) == 2
    assert main(["--replicas", "2", "--spmd", "--no-tui"]) == 2


# ------------------------------------------------------------- migration
def _alloc_conserved(router):
    """free + used + cached == pool on every member runtime."""
    for mem in router.local_members:
        for rt in mem.engine.runtimes.values():
            alloc = getattr(rt, "alloc", None)
            if alloc is None:
                continue
            assert (alloc.free_pages + alloc.used_pages
                    + alloc.cached_pages == alloc.num_pages - 1), (
                f"{mem.name}: free {alloc.free_pages} + used "
                f"{alloc.used_pages} + cached {alloc.cached_pages} "
                f"!= pool {alloc.num_pages - 1}")


def _member_journals_clean(router):
    from ollamamq_tpu.telemetry.journal import check_invariants

    for mem in router.local_members:
        assert check_invariants(mem.engine.journal.tail(None)) == [], \
            mem.name


@pytest.mark.parametrize(
    "prefix_cache,kv_dtype,spec,seed",
    [(False, "bfloat16", False, 0), (True, "bfloat16", False, 1),
     (False, "int8", False, 2), (True, "int8", True, 3)],
    ids=["plain", "cache", "int8", "cache+int8+spec"])
def test_migration_fuzz_byte_identity_and_page_conservation(
        prefix_cache, kv_dtype, spec, seed):
    """Kill a member at a randomized decode depth across the
    prefix-cache x int8-KV x spec matrix: victim streams MIGRATE (KV
    pages shipped, zero recomputed tokens), every stream matches the
    single-replica golden byte for byte, and page conservation
    (free+used+cached==pool) holds on BOTH members through the
    export/import/abort traffic."""
    import random

    over = dict(prefix_cache=prefix_cache, kv_dtype=kv_dtype, spec=spec,
                spec_k=2)
    prompts = [
        "the cat sat on the mat the cat sat on the",
        "the cat sat on the mat the cat sat on a",
        "pack my box with five dozen jugs",
        "the cat sat on the mat the cat sat on my",
        "pack my box with five dozen mugs",
        "the cat sat on the mat the cat",
    ]
    # Randomized decode depth for the kill, kept shallow enough that
    # the victim member still holds live streams when the eject's
    # migration pass runs (the dying loop finishes its current
    # iteration first). The budget is generous (48) for the same
    # reason: a 16-token stream could run out between the depth probe
    # below and the health sweep noticing the dead loop, leaving the
    # eject nothing to migrate.
    depth = random.Random(seed).randrange(1, 6)
    golden = _tpu_fleet(n=1, **over)
    try:
        gtexts = [_text(collect(_run(golden, f"mg{i % 2}", p,
                                     max_tokens=48)))
                  for i, p in enumerate(prompts)]
    finally:
        golden.stop()

    router = _tpu_fleet(n=2, **over)
    try:
        reqs = [_run(router, f"mg{i % 2}", p, max_tokens=48)
                for i, p in enumerate(prompts)]
        deadline = time.monotonic() + 120
        victim = None
        while time.monotonic() < deadline and victim is None:
            for f in list(router.flights):
                if f.attempt is not None \
                        and len(f.attempt.req.generated_ids) >= depth:
                    victim = f.member
                    break
            time.sleep(0.01)
        assert victim is not None, "no stream reached the kill depth"
        victim.crash()
        texts = [_text(collect(r)) for r in reqs]
        assert texts == gtexts
        recs = router.journal.tail(None)
        migrated = [r for r in recs if r["kind"] == "migrate_import"
                    and r.get("what") != "prefix"]
        assert migrated, "the crash should have migrated at least one " \
                         "stream (state was frozen, not lost)"
        assert router.migration_count >= 1
        assert tm.FLEET_MIGRATIONS_TOTAL.labels(
            outcome="migrated").value >= 1
        # Two-phase completeness + zero drops on the router journal,
        # page conservation + invariants on each member's own journal.
        assert check_no_dropped_streams(recs) == []
        from ollamamq_tpu.telemetry.journal import check_invariants

        assert check_invariants(recs) == []
        _member_journals_clean(router)
        # Let the healed member's restart settle before the allocator
        # sweep (pages of evacuated slots reclaim via cancellation).
        deadline = time.monotonic() + 30
        while router.fleet_counts()["healthy"] < 2 \
                and time.monotonic() < deadline:
            time.sleep(0.05)
        _alloc_conserved(router)
    finally:
        router.stop()


def test_drain_migrates_streams_instead_of_running_them_out():
    """/admin/drain ships live streams to healthy members: the drain
    completes without waiting out long generations, the migrated word
    streams continue their numbering seamlessly, and nothing drops."""
    router = _fake_fleet(n=2, token_latency_s=0.05)
    try:
        reqs = [_run(router, f"dm{i}", max_tokens=16) for i in range(4)]
        # Wait until every stream is placed and mid-generation.
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            placed = [f for f in list(router.flights)
                      if f.attempt is not None]
            if len(placed) == 4 and all(
                    f.attempt.req.generated_ids for f in placed):
                break
            time.sleep(0.01)
        router.drain_replica("r0")
        for r in reqs:
            items = collect(r)
            assert items[-1].kind == "done"
            text = _text(items)
            assert text.startswith("word0 word1 ")
            # Seamless continuation: the word cursor migrated with the
            # stream, so numbering never restarts.
            words = text.split()
            assert words == [f"word{i}" for i in range(len(words))]
        recs = router.journal.tail(None)
        assert any(r["kind"] == "migrate_export" for r in recs)
        assert any(r["kind"] == "migrate_import" for r in recs)
        assert router.migration_count >= 1
        assert check_no_dropped_streams(recs) == []
        deadline = time.monotonic() + 30
        while router.fleet_counts()["healthy"] < 2 \
                and time.monotonic() < deadline:
            time.sleep(0.05)
        assert router.fleet_counts()["healthy"] == 2
    finally:
        router.stop()


def test_migration_recovers_a_killed_member_without_recomputing():
    """The same kill, migration on and off: a member dies with streams
    mid-generation. With migration the victims resume from their shipped
    state (`migrate_import`) and recompute at most a fifth of the tokens
    the recompute-only fleet replays (`replica_failover.replayed_tokens`)
    — and either way every stream is whole and nothing drops."""
    def leg(migrate):
        router = _fake_fleet(n=2, token_latency_s=0.05,
                             router_kw=dict(migrate=migrate))
        try:
            reqs = [_run(router, f"mk{i}", max_tokens=16) for i in range(4)]
            deadline = time.monotonic() + 30
            victim = None
            while time.monotonic() < deadline and victim is None:
                for f in list(router.flights):
                    if f.attempt is not None \
                            and len(f.attempt.req.generated_ids) >= 4:
                        victim = f.member
                        break
                time.sleep(0.01)
            assert victim is not None, "no stream reached mid-generation"
            victim.crash()
            for r in reqs:
                items = collect(r)
                assert items[-1].kind == "done"
                words = _text(items).split()
                assert words == [f"word{i}" for i in range(16)]
            recs = router.journal.tail(None)
            assert check_no_dropped_streams(recs) == []
            return (sum(int(r.get("replayed_tokens") or 0) for r in recs
                        if r["kind"] == "replica_failover"),
                    router.migration_count)
        finally:
            router.stop()

    replayed_on, migrations = leg(True)
    replayed_off, none = leg(False)
    assert migrations >= 1 and none == 0
    assert replayed_off > 0
    assert replayed_on * 5 <= replayed_off, (replayed_on, replayed_off)


def test_migration_mid_transfer_crash_falls_back_to_recompute():
    """faults.py site "migrate": the first transfer dies mid-flight
    (exception) and the second loses its SOURCE right after export
    (device_loss) — both abort into the recompute-replay fallback with
    zero dropped streams and a clean two-phase journal pairing."""
    plan = FaultPlan([
        {"site": "migrate", "kind": "exception", "at": [1]},
    ])
    router = _fake_fleet(n=2, token_latency_s=0.05, plan=plan)
    try:
        reqs = [_run(router, f"ab{i}", max_tokens=16) for i in range(3)]
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            placed = [f for f in list(router.flights)
                      if f.member is not None
                      and f.member.name == "r0"
                      and f.attempt is not None
                      and f.attempt.req.generated_ids]
            if placed:
                break
            time.sleep(0.01)
        assert placed, "no stream mid-generation on r0"
        router.drain_replica("r0")
        for r in reqs:
            items = collect(r)
            assert items[-1].kind == "done"
            words = _text(items).split()
            assert words == [f"word{i}" for i in range(len(words))]
        recs = router.journal.tail(None)
        aborts = [r for r in recs if r["kind"] == "migrate_abort"]
        assert aborts and aborts[0]["why"] == "fault_injected"
        assert tm.FLEET_MIGRATIONS_TOTAL.labels(
            outcome="aborted").value >= 1
        # The aborted handoff is paired (export -> abort) and the stream
        # still reached its terminal: nothing dropped, nothing orphaned.
        assert check_no_dropped_streams(recs) == []
    finally:
        router.stop()


def test_migration_source_death_after_export_still_lands():
    """site "migrate" device_loss: the source member dies right after
    the export snapshot. The import still lands (the blob is already
    off the member), the commit resolves inline against the dead loop,
    and the ejected source heals back in later."""
    plan = FaultPlan([
        {"site": "migrate", "kind": "device_loss", "at": [1],
         "heal_after_s": 0.5},
    ])
    router = _fake_fleet(n=2, token_latency_s=0.05, plan=plan)
    try:
        reqs = [_run(router, f"dl{i}", max_tokens=16) for i in range(3)]
        deadline = time.monotonic() + 30
        placed = []
        while time.monotonic() < deadline:
            placed = [f for f in list(router.flights)
                      if f.member is not None
                      and f.member.name == "r0"
                      and f.attempt is not None
                      and f.attempt.req.generated_ids]
            if placed:
                break
            time.sleep(0.01)
        assert placed, "no stream mid-generation on r0"
        router.drain_replica("r0")
        for r in reqs:
            items = collect(r)
            assert items[-1].kind == "done"
            words = _text(items).split()
            assert words == [f"word{i}" for i in range(len(words))]
        recs = router.journal.tail(None)
        assert any(r["kind"] == "migrate_import" for r in recs)
        assert check_no_dropped_streams(recs) == []
    finally:
        router.stop()


def test_affinity_miss_ships_prefix_to_chosen_member():
    """When the cached member can't take the request, the prefix ships
    TO the chosen member instead of the router routing around it: the
    target's radix tree gains the pages and journals the shipment."""
    router = _tpu_fleet(n=2, prefix_cache=True)
    try:
        prompt = "shared system preamble for prefix shipping tests ok"
        collect(_run(router, "ps", prompt, max_tokens=4))
        holder = router.journal.tail(None, kind="place")[-1]["runtime"]
        src = next(m for m in router.members if m.name == holder)
        dst = next(m for m in router.members if m.name != holder)
        tokens = router.resolve_runtime("test-tiny").tokenizer.encode(
            prompt)
        assert src.affinity_pages("test-tiny", tokens) >= 1
        assert dst.affinity_pages("test-tiny", tokens) == 0
        flight = type("F", (), {"rid0": 999, "user": "ps", "model":
                      "test-tiny", "kind": "generate",
                      "prompt_tokens": tokens})()
        router._maybe_ship_prefix(flight, dst)
        assert dst.affinity_pages("test-tiny", tokens) >= 1
        ships = [r for r in router.journal.tail(None, kind="migrate_import")
                 if r.get("what") == "prefix"]
        assert ships and ships[-1]["replica"] == holder \
            and ships[-1]["to_replica"] == dst.name
        _alloc_conserved(router)
        _member_journals_clean(router)
    finally:
        router.stop()


def test_http_member_drain_migrates_over_admin_migrate_wire():
    """HTTP-member drain rides the /admin/migrate endpoints end to end:
    export (blob over the wire, keyed by the frames' req_id), import
    (2xx ack + NDJSON continuation), commit — the stream's word cursor
    migrates between two real socket services with zero recompute."""
    member_cfg = EngineConfig(**TINY)
    backends = [
        _HttpBackend(FakeEngine(member_cfg, blocklist_path=None,
                                token_latency_s=0.05))
        for _ in range(2)
    ]
    for b in backends:
        b.engine.start()
    ecfg = EngineConfig(**TINY)
    members = [HttpMember(f"h{i}", b.url, timeout_s=30, poll_period_s=0.1)
               for i, b in enumerate(backends)]
    router = FleetRouter(members, ecfg, blocklist_path=None,
                         probe_period_s=0.05, eject_heartbeat_s=30.0,
                         reprobe_backoff_s=0.2, evac_grace_s=0.5,
                         migrate_timeout_s=10.0)  # (no eject here: at 2 s a
    router.start()  # loaded machine starved a poll, and the stream came back)
    try:
        req = _run(router, "hm", "migrate me over http", max_tokens=16)
        mem = _serving_member(router, req)
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            f = next((f for f in list(router.flights) if f.req is req),
                     None)
            if f is not None and f.attempt is not None \
                    and f.attempt.member_rid is not None \
                    and f.attempt.n_items >= 2:
                break
            time.sleep(0.01)
        router.drain_replica(mem.name)
        items = collect(req, timeout=60)
        assert items[-1].kind == "done"
        words = _text(items).split()
        assert words == [f"word{i}" for i in range(16)]
        recs = router.journal.tail(None)
        migrated = [r for r in recs if r["kind"] == "migrate_import"
                    and r.get("what") != "prefix"]
        assert migrated and migrated[-1]["replica"] == mem.name
        assert migrated[-1]["tokens"] >= 2  # resumed mid-stream, not fresh
        assert router.migration_count >= 1
        assert check_no_dropped_streams(recs) == []
    finally:
        router.stop()
        for b in backends:
            b.stop()


def test_migration_blob_wire_roundtrip():
    import numpy as np

    from ollamamq_tpu.engine import kv_cache as kvc

    blob = {"version": 1, "kind": "stream", "kv_len": 9,
            "request": {"user": "u", "generated_ids": [1, 2, 3]},
            "k_pages": np.arange(24, dtype=np.float32).reshape(2, 3, 4),
            "recent": np.full((8,), -1, np.int32),
            "_inc_decode": object()}  # in-process only: dropped on pack
    raw = kvc.pack_migration_blob(blob)
    out = kvc.unpack_migration_blob(raw)
    assert out["kv_len"] == 9 and out["request"]["generated_ids"] == [1, 2, 3]
    assert np.array_equal(out["k_pages"], blob["k_pages"])
    assert np.array_equal(out["recent"], blob["recent"])
    assert "_inc_decode" not in out
    with pytest.raises(ValueError):
        kvc.unpack_migration_blob(b"not a blob")
    # bfloat16 pools (ml_dtypes, not npz-serializable natively) survive
    # the wire as byte views with the dtype recorded in the header.
    import ml_dtypes

    bf = np.arange(8, dtype=np.float32).astype(ml_dtypes.bfloat16)
    out = kvc.unpack_migration_blob(kvc.pack_migration_blob(
        {"kind": "stream", "k_pages": bf.reshape(2, 4)}))
    assert out["k_pages"].dtype == bf.dtype
    assert np.array_equal(out["k_pages"], bf.reshape(2, 4))


def test_no_dropped_streams_checker_pairs_migrations():
    # Committed handoff: export -> import -> terminal = clean.
    clean = [
        {"kind": "migrate_export", "req_id": 4, "seq": 1, "tokens": 2},
        {"kind": "migrate_import", "req_id": 4, "seq": 2},
        {"kind": "finish", "req_id": 4, "seq": 3, "reason": "length"},
    ]
    assert check_no_dropped_streams(clean) == []
    # Aborted handoff that fell back and finished = clean.
    aborted = [
        {"kind": "migrate_export", "req_id": 5, "seq": 1, "tokens": 2},
        {"kind": "migrate_abort", "req_id": 5, "seq": 2, "why": "t"},
        {"kind": "replica_failover", "req_id": 5, "seq": 3},
        {"kind": "finish", "req_id": 5, "seq": 4, "reason": "stop"},
    ]
    assert check_no_dropped_streams(aborted) == []
    # Export with no resolution AND no terminal: dropped + orphaned.
    orphan = [
        {"kind": "migrate_export", "req_id": 6, "seq": 1, "tokens": 2},
    ]
    bad = check_no_dropped_streams(orphan)
    assert len(bad) == 2
    assert any("DROPPED" in b for b in bad)
    assert any("ORPHANED" in b for b in bad)
    # Imported but never finished: dropped.
    undone = [
        {"kind": "migrate_export", "req_id": 7, "seq": 1, "tokens": 2},
        {"kind": "migrate_import", "req_id": 7, "seq": 2},
    ]
    bad = check_no_dropped_streams(undone)
    assert len(bad) == 1 and "DROPPED" in bad[0]


def test_cancel_mid_stream_releases_fleet_state():
    router = _fake_fleet(n=2, token_latency_s=0.05)
    try:
        req = _run(router, "cx", max_tokens=64)
        _serving_member(router, req)
        router.cancel(req.req_id)
        items = collect(req)
        assert items[-1].finish_reason == FinishReason.CANCELLED
        deadline = time.monotonic() + 10
        while router.flights and time.monotonic() < deadline:
            time.sleep(0.02)
        assert not router.flights
    finally:
        router.stop()
