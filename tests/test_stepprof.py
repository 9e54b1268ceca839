"""Engine performance plane (PR 20): the always-on step profiler,
compile-ladder observability and HBM timeline.

Contracts pinned here:

  - every ring (samples / shape table / compile events / HBM timeline)
    is bounded — always-on means O(1) memory forever;
  - a sample's phase milliseconds sum to its recorded step wall clock,
    and the instrumented wall covers >= 95% of the externally measured
    dispatch wall on a REAL tiny runtime;
  - compile events are exactly-once per (site, key) in steady state;
    an injected `compile`-site fault (jit cache eviction loop) turns
    the ladder into a storm and trips the health monitor's
    compile_storm alert past warmup;
  - the profiler survives injected dispatch faults: an abandoned step
    leaves NO partial sample and the decision journal stays clean;
  - profiler self-overhead stays under the 1% always-on budget;
  - the fleet router federates member `ollamamq_step_phase_ms` series
    with a replica label;
  - a seam inside a phase (PR 52) is a span and nothing else: with no
    capture it makes no object and touches no sample, with one its child
    span opens inside the phase's span and closes before it, carrying its
    parent's `seq`.
"""

import itertools
import json
import re
import threading
import time

import pytest

from ollamamq_tpu.config import EngineConfig
from ollamamq_tpu.ops.sampling import SamplingParams
from ollamamq_tpu.telemetry import stepprof
from ollamamq_tpu.telemetry.journal import check_invariants
from ollamamq_tpu.telemetry.stepprof import (_COMPILE_RING, _HBM_RING,
                                             _RING, _SHAPE_KEYS, PHASES,
                                             PROFILER, StepProfiler)
from ollamamq_tpu.testing.faults import FaultPlan
from test_degradation import _tpu_engine
from testutil import collect


@pytest.fixture(autouse=True)
def _fresh_profiler():
    PROFILER.reset()
    yield
    PROFILER.reset()


def _run(eng, user, prompt="the quick brown fox jumps", max_tokens=8):
    tok = eng.resolve_runtime("test-tiny").tokenizer
    return eng.enqueue_request(
        user, "", "test-tiny", prompt_tokens=tok.encode(prompt),
        sampling=SamplingParams(max_tokens=max_tokens))


def _phase_sum(sample):
    return sum(sample[ph + "_ms"] for ph in PHASES)


# ------------------------------------------------------------- boundedness
def test_every_ring_is_bounded():
    prof = StepProfiler()
    for i in range(_RING + 500):
        t = prof.start("ragged")
        t.mark("host_prep")
        t.finish(T_pad=(i % 100) * 8, k_cap=0, n_prefill=1, n_decode=0,
                 tokens=4, padded_tokens=8, compiled=False)
    for i in range(_COMPILE_RING + 50):
        prof.record_compile("ragged", ("ragged", i), 1.0)
    for i in range(_HBM_RING + 50):
        prof.hbm_record({"models": {}})
    assert len(prof.samples) == _RING
    assert prof.seq == _RING + 500          # seq keeps counting past evict
    assert len(prof._shapes) <= _SHAPE_KEYS
    assert len(prof.compiles) == _COMPILE_RING
    assert prof.compile_count() == _COMPILE_RING + 50
    assert len(prof.hbm) == _HBM_RING
    # Snapshot stays serializable and bounded too.
    snap = prof.snapshot(n=64)
    json.dumps(snap)
    assert len(snap["recent"]) == 64
    assert len(snap["shapes"]) <= _SHAPE_KEYS


# -------------------------------------------------- phase sum == wall clock
def test_phase_sum_matches_dispatch_wall_on_real_runtime():
    """ACCEPTANCE: per-sample phase ms sum EXACTLY to the sample's step
    time (marks of one timer), and the instrumented time covers >= 95% of
    the externally measured wall of the step's two halves — its launch
    and, behind the next step's launch, its settle."""
    eng = _tpu_engine()
    rt = eng.runtimes["test-tiny"]
    pairs = []  # (externally measured wall ms, the sample it produced)
    walls = {}  # id(handle) -> wall ms of its launch
    launch, settle = rt.step_ragged_launch, rt.step_settle

    def timed_launch(core):
        t0 = time.perf_counter()
        h = launch(core)
        if h is not None:
            walls[id(h)] = (time.perf_counter() - t0) * 1e3
        return h

    def timed_settle(h, core):
        seq0 = PROFILER.seq
        t0 = time.perf_counter()
        n = settle(h, core)
        wall = (time.perf_counter() - t0) * 1e3
        if id(h) in walls and PROFILER.seq > seq0:  # one sample: this step's
            pairs.append((walls.pop(id(h)) + wall, PROFILER.tail(1)[0]))
        return n

    rt.step_ragged_launch, rt.step_settle = timed_launch, timed_settle
    try:
        for i, u in enumerate(("alpha", "beta")):
            items = collect(_run(eng, u, prompt="count to ten " * (i + 1)))
            assert items[-1].kind == "done", items[-1].error
    finally:
        rt.step_ragged_launch, rt.step_settle = launch, settle
        eng.stop()

    assert pairs, "no ragged step samples were recorded"
    for wall, s in pairs:
        assert abs(_phase_sum(s) - s["total_ms"]) < 0.01, s
        assert s["mode"] in ("ragged", "spec_verify")
        assert s["tokens"] >= 0 and s["padded_tokens"] >= s["tokens"] >= 0
    measured = sum(w for w, _ in pairs)
    instrumented = sum(s["total_ms"] for _, s in pairs)
    assert instrumented >= 0.95 * measured, \
        f"instrumented {instrumented:.2f}ms < 95% of {measured:.2f}ms"
    # Decode-scan samples carry the same arithmetic identity.
    for s in PROFILER.tail():
        assert abs(_phase_sum(s) - s["total_ms"]) < 0.01, s


# ---------------------------------------------------------- compile ladder
def test_compile_events_exactly_once_per_rung_then_steady_state():
    eng = _tpu_engine()
    try:
        items = collect(_run(eng, "warm", prompt="short"))
        assert items[-1].kind == "done", items[-1].error
        items = collect(_run(eng, "warm2",
                             prompt="a much longer prompt " * 4))
        assert items[-1].kind == "done", items[-1].error
        n_warm = PROFILER.compile_count()
        assert n_warm > 0
        events = list(PROFILER.compiles)
        keys = [(e["site"], e["key"]) for e in events]
        assert len(keys) == len(set(keys)), f"duplicate compiles: {keys}"
        assert all(e["wall_ms"] > 0 for e in events)
        # Every compile journals once, with the same key vocabulary.
        jr = [r for r in eng.journal.tail(n=None) if r["kind"] == "compile"]
        assert len(jr) == n_warm
        assert {(r["site"], r["key"]) for r in jr} == set(keys)
        # At least one step paid a compile and said so.
        assert any(s.get("compiled") for s in PROFILER.tail())
        # Steady state: an identical re-run compiles NOTHING.
        items = collect(_run(eng, "steady", prompt="short"))
        assert items[-1].kind == "done", items[-1].error
        assert PROFILER.compile_count() == n_warm
    finally:
        eng.stop()


def test_injected_recompile_loop_trips_compile_storm(monkeypatch):
    """The faults.py `compile` site evicts cached jit entries, forcing a
    re-trace on every revisit — the recompile loop the compile_storm
    alert exists for. Warmup suppression, firing, and resolution all
    exercised through the real HealthMonitor rule."""
    from ollamamq_tpu.engine import health as health_mod
    from ollamamq_tpu.engine.health import HealthMonitor
    from ollamamq_tpu.telemetry import schema as tm

    plan = FaultPlan([{"site": "compile", "kind": "exception", "every": 1}])
    eng = _tpu_engine(plan=plan)
    hm = HealthMonitor(eng, period_s=999.0)  # never started: driven by hand
    try:
        collect(_run(eng, "w1", prompt="storm me"))
        n1 = PROFILER.compile_count()
        collect(_run(eng, "w2", prompt="storm me"))
        n2 = PROFILER.compile_count()
        assert n2 > n1, "eviction fault did not force recompiles"
        keys = [(e["site"], e["key"]) for e in PROFILER.compiles]
        assert len(keys) > len(set(keys)), "no duplicate (site, key) pairs"
        assert PROFILER.compile_rate_per_min() > 0

        # Inside the warmup window the rule stays quiet by design.
        monkeypatch.setattr(health_mod, "COMPILE_STORM_PER_MIN", 0.5)
        hm._check_compile_storm()
        assert "compile_storm" not in {a.name for a in eng.alerts.active()}

        # Past warmup the same rate fires, counted under kind=compile.
        monkeypatch.setattr(health_mod, "COMPILE_WARMUP_S", 0.0)
        before = tm.WATCHDOG_STALLS_TOTAL.labels(kind="compile").value
        hm._check_compile_storm()
        assert "compile_storm" in {a.name for a in eng.alerts.active()}
        assert tm.WATCHDOG_STALLS_TOTAL.labels(kind="compile").value \
            == before + 1

        # Storm over (events age out / ring reset) -> alert resolves.
        PROFILER.reset()
        hm._check_compile_storm()
        assert "compile_storm" not in {a.name for a in eng.alerts.active()}
    finally:
        eng.stop()


# ------------------------------------------------------- fault containment
def test_profiler_survives_dispatch_faults_with_clean_journal():
    """An injected ragged dispatch fault abandons that step's timer: no
    partial sample lands in the ring (every recorded sample still sums
    clean), the retried stream finishes, and the decision journal's
    invariants hold."""
    plan = FaultPlan([{"site": "ragged", "kind": "exception", "at": [1]}])
    eng = _tpu_engine(plan=plan)
    try:
        items = collect(_run(eng, "faulty"))
        assert items[-1].kind == "done", items[-1].error
        samples = PROFILER.tail()
        assert samples, "no samples after the retried dispatch"
        for s in samples:
            assert s["total_ms"] > 0
            assert abs(_phase_sum(s) - s["total_ms"]) < 0.01, s
        recs = eng.journal.tail(n=None)
        assert not check_invariants(recs)
    finally:
        eng.stop()


# ----------------------------------------------------------- self-overhead
def test_self_overhead_stays_under_one_percent():
    """ACCEPTANCE: always-on means the profiler's own clock reads and
    ring appends — since PR 24 also the loop clock's ticks and phase
    switches of TPUEngine._loop_once — must cost < 1% of the
    engine-thread time the samples account for — an engine of sizes of
    its own, so that its step programs are compiled here as a served
    process's are (the builders hand a second engine of one shape the
    first one's programs: `engine/step_program.py`)."""
    eng = _tpu_engine(max_slots=3)
    try:
        for u in ("o1", "o2"):
            items = collect(_run(eng, u, max_tokens=10))
            assert items[-1].kind == "done", items[-1].error
    finally:
        eng.stop()
    frac = PROFILER.overhead_fraction()
    assert PROFILER.seq > 0
    assert 0.0 <= frac < 0.01, f"profiler overhead {frac:.4f} >= 1%"


def test_the_overhead_meter_counts_no_instant_twice():
    """A loop that does nothing BUT profiler calls — a launch, the settle
    of the step before it, the loop's phases — cannot be metered at more
    than its own wall time, and the meter sees most of it. Up to PR 51
    `_record` metered itself inside `finish`'s metering and the meter read
    1.3 x the wall (PR 52)."""
    prof = StepProfiler()
    clock = stepprof.LoopClock(prof, "t")

    def one(prev):
        clock.tick()
        clock.enter("admit")
        clock.enter("other")
        sp = prof.start("ragged", clock)
        sp.note(T_pad=64, k_cap=0, tokens=50)
        sp.mark("host_prep")
        sp.seam("launch")
        sp.launched(lambda: True, model="m", h2d_transfers=1, h2d_bytes=8)
        sp.seam("note")
        sp.mark("dispatch")
        sp.park()
        if prev is not None:
            prev.resume("collect")
            prev.collected()
            prev.park()
            prev.resume("detok")
            prev.probe()
            prev.mark("detok")
            prev.finish(n_prefill=1, n_decode=2, padded_tokens=64)
        return sp

    prev = None
    for _ in range(200):   # warm: first calls, label children, the ring
        prev = one(prev)
    prof._overhead_ns = 0
    t0 = time.perf_counter_ns()
    for _ in range(2000):
        prev = one(prev)
    wall_ns = time.perf_counter_ns() - t0
    assert 0.5 * wall_ns <= prof._overhead_ns <= wall_ns, \
        (prof._overhead_ns, wall_ns)


# ------------------------------------------------- gapless engine thread
def _fake_engine(models=("test-tiny",), latency=0.002):
    from ollamamq_tpu.engine.fake import FakeEngine

    eng = FakeEngine(EngineConfig(model=models[0], max_slots=4,
                                  num_pages=64, page_size=8,
                                  max_pages_per_seq=8),
                     models={m: None for m in models}, blocklist_path=None,
                     token_latency_s=latency)
    eng.start()
    return eng


def _accounted_ms(sample):
    return sample["total_ms"] + sum(
        sample["loop_" + ph + "_ms"] for ph in stepprof.LOOP_PHASES)


class _OneClock:
    """`stepprof`'s `time` for a test of its bookkeeping: every mark and
    every sample's `ts` read ONE counter a thread, a millisecond a reading,
    so a reading nobody accounts for is a millisecond missing whatever the
    load (on the real clocks a 1 % bound failed under it: ROADMAP C7)."""

    def __init__(self):
        self._of_thread = threading.local()

    def perf_counter(self):
        mine = self._of_thread.__dict__.setdefault("n", itertools.count(1))
        return next(mine) * 1e-3

    time = perf_counter

    def __getattr__(self, name):
        return getattr(time, name)


def _gapless(run, share=0.0):
    """A `ts` is read two or three readings behind its sample's last mark:
    that offset, at the run's two ends, is all that may separate what the
    samples account for from the time between them (`share` of it more on
    the real clocks, where the thread may be descheduled in between)."""
    wall_ms = (run[-1]["ts"] - run[0]["ts"]) * 1e3
    accounted = sum(_accounted_ms(smp) for smp in run[1:])
    assert wall_ms >= 50 and abs(accounted - wall_ms) <= 4 + share * wall_ms, \
        f"{run[0]['thread']}: accounted {accounted:.3f} of {wall_ms:.3f} ms"


def test_engine_thread_time_is_gapless_over_consecutive_samples(monkeypatch):
    """ACCEPTANCE (PR 24): over any run of consecutive samples of one
    engine thread, sum(total_ms + loop_*_ms) is the time between them —
    on `_OneClock`, to the few readings between a last mark and its `ts` —
    with idle ticks (condvar waits between bursts), abandoned timers (a
    step that starts its timer and returns early), two runtimes on one
    engine thread, and a second engine thread recording into the same
    process-wide ring."""
    monkeypatch.setattr(stepprof, "time", _OneClock())
    a = _fake_engine(models=("test-tiny", "test-tiny-qwen"))
    b = _fake_engine()
    # Every other step of one runtime first opens a timer it abandons.
    rt = a.runtimes["test-tiny"]
    orig, n = rt.step, [0]

    def step(core):
        n[0] += 1
        if n[0] % 2:
            stepprof.PROFILER.start("fake", rt.loop_clock).mark("host_prep")
            time.sleep(0.001)  # work done under the abandoned timer
        return orig(core)

    rt.step = step
    try:
        for burst in range(3):
            reqs = [eng.enqueue_request(
                f"u{burst}{i}", "", model,
                prompt_tokens=[1, 2, 3],
                sampling=SamplingParams(max_tokens=6))
                for eng, model in ((a, "test-tiny"), (a, "test-tiny-qwen"),
                                   (b, "test-tiny")) for i in range(2)]
            for r in reqs:
                assert collect(r)[-1].kind == "done"
            time.sleep(0.12)  # idle ticks: several condvar waits
    finally:
        a.stop()
        b.stop()
    samples = PROFILER.tail()
    assert n[0] >= 4, "the abandoning wrapper never ran"
    by_thread = {}
    for smp in samples:
        assert all("loop_" + ph + "_ms" in smp
                   for ph in stepprof.LOOP_PHASES), smp
        assert abs(_phase_sum(smp) - smp["total_ms"]) < 0.01, smp
        by_thread.setdefault(smp["thread"], []).append(smp)
    assert set(by_thread) == {a.loop_clock.name, b.loop_clock.name}
    assert a.loop_clock.name != b.loop_clock.name
    for name, run in by_thread.items():
        assert len(run) >= 10, (name, len(run))
        _gapless(run)
        # ... and over any sub-run, not just the whole one.
        _gapless(run[len(run) // 2:])
        # Idle ticks were waits (three bursts: a reading at each end of a
        # wait), admission was seen, abandoned timers and the rest of the
        # tick are `other`.
        assert sum(smp["loop_wait_ms"] for smp in run) >= 3.0
        assert sum(smp["loop_admit_ms"] for smp in run) > 0.0
        assert sum(smp["loop_other_ms"] for smp in run) > 0.0
    # The abandoned timers' millisecond each went to `other`, not lost.
    assert sum(smp["loop_other_ms"] for smp in by_thread[a.loop_clock.name]) \
        >= 0.9 * (n[0] // 2)


def test_loop_fields_on_every_sample_and_in_the_histogram():
    """`loop_*_ms` are always present (0.0 when none): on an engine's
    samples and on a step timed alone; the loop time reaches
    ollamamq_step_phase_ms as phase="loop_*"."""
    from ollamamq_tpu.telemetry import schema as tm

    def count(phase):
        return tm.STEP_PHASE_MS.labels(phase=phase, mode="fake").count

    before = {ph: count("loop_" + ph) for ph in stepprof.LOOP_PHASES}
    t = PROFILER.start("fake")  # no engine, no clock: a step alone
    t.mark("dispatch")
    alone = t.finish(tokens=1, padded_tokens=1, compiled=False)
    assert [alone["loop_" + ph + "_ms"] for ph in stepprof.LOOP_PHASES] \
        == [0.0, 0.0, 0.0]
    assert alone["thread"] and alone["seq"] == 1
    eng = _fake_engine()
    try:
        time.sleep(0.1)
        assert collect(_run(eng, "u", max_tokens=4))[-1].kind == "done"
    finally:
        eng.stop()
    mine = [smp for smp in PROFILER.tail()
            if smp["thread"] == eng.loop_clock.name]
    assert mine and mine[0]["loop_wait_ms"] > 50.0
    assert mine[0]["T_pad"] == 0 and mine[0]["tokens"] > 0
    for ph in stepprof.LOOP_PHASES:
        assert count("loop_" + ph) > before[ph], ph


def test_real_engine_loop_is_gapless_too(monkeypatch):
    """TPUEngine._loop_once carries the same marks: its samples account
    for the engine thread's time (idle ticks abandon step_ragged's timer
    every 50 ms — those fold into `other`) — on `_OneClock` to the reading,
    and on the real clocks to a fifth of the wall: what a loaded machine
    may take between a mark and a `ts`, where a lost phase loses far more."""
    for clock, share, waited in ((_OneClock(), 0.0, 1.0), (time, 0.2, 50.0)):
        monkeypatch.setattr(stepprof, "time", clock)
        PROFILER.reset()
        eng = _tpu_engine()
        try:
            for u in ("g1", "g2"):
                assert collect(_run(eng, u, max_tokens=6))[-1].kind == "done"
                time.sleep(0.12)
        finally:
            eng.stop()
        run = [smp for smp in PROFILER.tail()
               if smp["thread"] == eng.loop_clock.name]
        assert len(run) >= 4 and len(run) == len(PROFILER.tail())
        _gapless(run, share)
        # the idle wait between the two requests: a reading at each of its
        # ends, or (a sleep only oversleeps) the better part of its 120 ms
        assert sum(smp["loop_wait_ms"] for smp in run) >= waited


def test_two_interleaved_steps_stay_gapless_and_keep_their_own_phases():
    """PR 28: step N is settled behind step N+1's launch. With the two
    timers interleaved on one clock — ticks in between, which must not
    fold a PARKED timer — every instant is in exactly one phase of one
    owner: sum(total_ms + loop_*_ms) over the samples is the wall time to
    1e-6, each sample keeps its own host_prep/dispatch/collect/detok, and
    `collect` is only the time its own step blocked."""
    prof = stepprof.StepProfiler()
    clock = stepprof.LoopClock(prof, "t")

    def nap(ms):
        t = time.perf_counter() + ms / 1e3
        while time.perf_counter() < t:
            pass

    t_a = time.perf_counter()
    clock.reset()
    clock._last = t_a
    clock.tick()
    nap(1)                                   # loop: other
    n = prof.start("ragged", clock)
    nap(2); n.mark("host_prep")
    nap(1); n.mark("dispatch"); n.park()
    clock.tick()                             # N stays in flight over a tick
    clock.enter("admit"); nap(1); clock.enter("other")
    m = prof.start("ragged", clock)          # N+1 launched behind N
    m.note(overlapped=1)
    nap(3); m.mark("host_prep")
    nap(2); m.mark("dispatch"); m.park()
    n.resume("collect"); nap(4); n.mark("collect")
    nap(1); n.park()                         # (ids read: the cheap pass)
    n.resume("detok"); nap(5); n.mark("detok")
    s_n = n.finish(wasted_rows=0, overlapped=0)
    clock.tick()
    nap(1)                                   # loop: other
    m.resume("collect"); nap(1); m.mark("collect"); m.park()
    m.resume("detok"); nap(2); m.mark("detok")
    s_m = m.finish(wasted_rows=2)
    t_b = clock._last                        # the chain's last boundary
    wall = (t_b - t_a) * 1e3
    acc = _accounted_ms(s_n) + _accounted_ms(s_m)
    assert abs(acc - wall) <= 1e-6 * wall + 2e-3, (acc, wall)  # 4-dp fields
    for s, want in ((s_n, (2, 1, 4, 6)), (s_m, (3, 2, 1, 2))):
        got = [s[ph + "_ms"] for ph in stepprof.PHASES]
        # Every phase got at least its own busy time; with the exact sum
        # above none can also hold another's (N's collect is its own 4 ms,
        # not the 5 ms N+1's launch took before it).
        assert all(g >= w for g, w in zip(got, want)), (got, want)
        assert abs(_phase_sum(s) - s["total_ms"]) < 0.01
    # Loop time rides in the next sample RECORDED: 1 ms other + 1 ms admit
    # before N's, 1 ms other before N+1's.
    assert s_n["loop_admit_ms"] >= 1 and s_n["loop_other_ms"] >= 1
    assert s_m["loop_other_ms"] >= 1 and s_m["loop_admit_ms"] == 0
    assert (s_n["overlapped"], s_m["overlapped"]) == (0, 1)
    assert (s_n["wasted_rows"], s_m["wasted_rows"]) == (0, 2)
    assert not clock._timers


def test_a_voided_step_and_an_abandoned_one_fold_into_other():
    """A parked timer survives ticks; abandon() (a step voided after a
    fault) and a tick (a timer neither finished nor parked) both give
    what the timer was charged to the loop's `other`, so the chain has no
    hole and neither records a sample."""
    prof = stepprof.StepProfiler()
    clock = stepprof.LoopClock(prof, "t")
    t_a = time.perf_counter()
    clock.reset()
    clock._last = t_a
    v = prof.start("ragged", clock)
    time.sleep(0.002); v.mark("host_prep"); v.mark("dispatch"); v.park()
    clock.tick(); clock.tick()
    assert v in clock._timers and not v._done
    e = prof.start("decode", clock)          # returns early: never parked
    time.sleep(0.001)
    clock.tick()
    assert e._done and e.finish() is None
    v.abandon()
    assert v.finish() is None and not clock._timers
    k = prof.start("decode", clock)
    k.mark("host_prep"); k.mark("dispatch"); k.park()
    k.resume("collect"); k.mark("collect"); k.mark("detok")
    s = k.finish()
    wall = (clock._last - t_a) * 1e3
    assert abs(_accounted_ms(s) - wall) <= 1e-6 * wall + 1e-3
    assert s["loop_other_ms"] >= 3.0 and prof.seq == 1


def test_real_engine_samples_say_what_the_pipeline_did():
    """Every generative sample carries `overlapped` and `wasted_rows`;
    the counters on /metrics follow them."""
    from ollamamq_tpu.telemetry import schema as tm

    def total(metric):
        return sum(c.value for _, c in metric.series())

    o0, w0 = total(tm.STEPS_OVERLAPPED_TOTAL), total(tm.STEP_WASTED_ROWS_TOTAL)
    eng = _tpu_engine()
    try:
        rt = eng.runtimes["test-tiny"]
        reqs = [_run(eng, u, prompt="count to ten " * 6, max_tokens=12)
                for u in ("p1", "p2")]
        time.sleep(0.05)
        reqs[1].cancelled.set()              # between some launch & settle
        for r in reqs:
            assert collect(r)[-1].kind == "done"
    finally:
        eng.stop()
    gen = [s for s in PROFILER.tail() if s["mode"] in ("ragged", "decode")]
    assert gen and all("overlapped" in s and "wasted_rows" in s for s in gen)
    assert sum(s["overlapped"] for s in gen) >= 1
    assert total(tm.STEPS_OVERLAPPED_TOTAL) - o0 == \
        sum(s["overlapped"] for s in gen)
    assert total(tm.STEP_WASTED_ROWS_TOTAL) - w0 == \
        sum(s["wasted_rows"] for s in gen)
    assert rt.cache.alloc.used_pages == 0


# -------------------------------------------------------------- federation
def test_federation_exposes_per_replica_step_series():
    """A fleet of real HTTP members federates their step-phase series
    into the router's /metrics exposition with a replica label."""
    from ollamamq_tpu.engine.fake import FakeEngine
    from ollamamq_tpu.fleet import FleetRouter, HttpMember
    from ollamamq_tpu.telemetry import REGISTRY
    from test_fleet import TINY as FLEET_TINY
    from test_fleet import _HttpBackend
    from test_fleet import _run as _fleet_run
    from test_fleet_obs import _wait

    member_cfg = EngineConfig(**FLEET_TINY)
    backends = [_HttpBackend(FakeEngine(member_cfg, blocklist_path=None))
                for _ in range(2)]
    for b in backends:
        b.engine.start()
    members = [HttpMember(f"h{i}", b.url, timeout_s=30, poll_period_s=0.1)
               for i, b in enumerate(backends)]
    router = FleetRouter(members, EngineConfig(**FLEET_TINY),
                         blocklist_path=None, probe_period_s=0.05,
                         eject_heartbeat_s=1.0, reprobe_backoff_s=0.1,
                         evac_grace_s=0.5)
    router.start()
    try:
        items = collect(_fleet_run(router, "fed-user"))
        assert items[-1].kind == "done", items[-1].error
        assert PROFILER.seq > 0, "fake member steps recorded no samples"

        def federated_step_series():
            fed = router.member_metric_federation()
            if {name for name, _ in fed} != {"h0", "h1"}:
                return False
            text = REGISTRY.render(federated=fed)
            return re.search(
                r'^ollamamq_step_phase_ms[^\n]*replica="h[01]"',
                text, re.M) is not None

        _wait(federated_step_series, msg="federated step-phase series")
    finally:
        router.stop()
        for b in backends:
            b.stop()


# ----------------------------------------------- capture-window cross-link
def test_window_slices_ring_by_capture_timestamps():
    """/debug/profile links its capture window to the stepprof ring by
    timestamp: samples inside [t0, t1] are returned, others are not."""
    t_before = time.time()
    t = PROFILER.start("fake")
    t.mark("dispatch")
    t.finish(T_pad=0, k_cap=0, n_prefill=0, n_decode=1, tokens=1,
             padded_tokens=1, compiled=False)
    t_after = time.time()
    inside = PROFILER.window(t_before, t_after)
    assert len(inside) == 1 and inside[0]["mode"] == "fake"
    assert PROFILER.window(t_after + 10, t_after + 20) == []
    assert PROFILER.window(t_before - 20, t_before - 10) == []


# --------------------------------------------------- seams inside a phase
class _Span:
    """A stand-in for jax.profiler.TraceAnnotation that logs its life."""

    def __init__(self, log, name, **stats):
        self.log, self.name, self.stats = log, name, stats

    def __enter__(self):
        self.log.append(("open", self.name, self.stats.get("seq")))

    def __exit__(self, *exc):
        self.log.append(("close", self.name, self.stats.get("seq")))


@pytest.mark.parametrize("capturing", [False, True])
def test_a_seam_is_a_child_span_while_capturing_and_nothing_otherwise(
        capturing, monkeypatch):
    """The same marks and seams with and without a capture: the sample is
    the same but for its clock readings; without a capture no span object
    is made; with one the children chain inside their phase's span, are
    closed before it (the profiler's spans nest last-in first-out), carry
    its `seq`, and a phase without seams has none."""
    import functools

    prof, log = StepProfiler(), []
    monkeypatch.setattr(prof, "span_factory", functools.partial(_Span, log))
    prof.capturing = capturing
    clock = stepprof.LoopClock(prof, "t")
    clock.seam("early")              # no phase span open yet: nothing
    clock.enter("other")
    sp = prof.start("ragged", clock)
    sp.mark("host_prep")
    sp.seam("launch")
    sp.seam("note")
    sp.mark("dispatch")
    sp.park()
    clock.seam("hbm")                # a loop phase's seam
    sp.resume("collect")
    sp.mark("collect")
    sp.mark("detok")
    smp = sp.finish(tokens=3)
    clock.enter("wait")              # closes the `other` finish() opened
    clock.reset()
    assert set(smp) >= {"total_ms", "dispatch_ms", "collect_ms",
                        "loop_other_ms", "seq"}
    assert not [k for k in smp if any(c in k for c in (
        "launch", "note", "hbm"))]
    assert abs(_phase_sum(smp) - smp["total_ms"]) < 0.01
    if not capturing:
        assert log == []
        return
    seq = smp["seq"]
    assert [(what, name) for what, name, _ in log] == [
        ("open", "mq.loop.other"), ("close", "mq.loop.other"),
        ("open", "mq.host_prep"), ("close", "mq.host_prep"),
        ("open", "mq.dispatch"),
        ("open", "mq.dispatch.launch"), ("close", "mq.dispatch.launch"),
        ("open", "mq.dispatch.note"), ("close", "mq.dispatch.note"),
        ("close", "mq.dispatch"),
        ("open", "mq.collect"), ("close", "mq.collect"),  # park()
        ("open", "mq.loop.other"),
        ("open", "mq.loop.other.hbm"), ("close", "mq.loop.other.hbm"),
        ("close", "mq.loop.other"),
        ("open", "mq.collect"), ("close", "mq.collect"),
        ("open", "mq.detok"), ("close", "mq.detok"),
        ("open", "mq.loop.other"), ("close", "mq.loop.other"),
        ("open", "mq.loop.wait"), ("close", "mq.loop.wait")]
    # Every open has its close, last in first out, and a child its
    # parent's seq.
    stack = []
    for what, name, q in log:
        if what == "open":
            if stack:
                assert name.startswith(stack[-1][0] + "."), (name, stack)
                assert q == stack[-1][1], (name, q, stack[-1])
            stack.append((name, q))
        else:
            assert stack.pop() == (name, q)
    assert not stack
    assert {q for _, name, q in log if name.startswith(
        ("mq.host_prep", "mq.dispatch", "mq.collect", "mq.detok"))} == {seq}
    # Clearing the flag inside a phase: the spans open then still close.
    log.clear()
    prof.capturing = True
    sp = prof.start("ragged", clock)
    sp.mark("host_prep")
    sp.seam("launch")
    prof.capturing = False
    sp.seam("note")                  # no new child once the flag is clear
    sp.mark("dispatch")
    assert [(w, n) for w, n, _ in log] == [
        ("open", "mq.host_prep"), ("close", "mq.host_prep"),
        ("open", "mq.dispatch"), ("open", "mq.dispatch.launch"),
        ("close", "mq.dispatch.launch"), ("close", "mq.dispatch")]


# ------------------------------------- accounts: what jax did, by who asked
_TRACE, _LOWER, _BACKEND = stepprof.JAX_SPANS  # jax's names, in that order
_HIT, _MISS = stepprof.JAX_CACHE_EVENTS
_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"


def _feed(prof, script):
    """jax's listeners' calls, scripted: ("b", event) a span begins,
    (event, seconds) it ends, (event,) an event."""
    for step in script:
        if step[0] == "b":
            prof.jax_begin(step[1])
        else:
            prof.jax_event(*step)


@pytest.mark.parametrize("script,want", [
    # the six events of one program fetched from the persistent cache
    ([("b", _TRACE), (_TRACE, 0.30), ("b", _LOWER), (_LOWER, 0.20),
      ("b", _BACKEND), (_HIT,), (_RETRIEVAL, 0.04), (_BACKEND, 0.05)],
     dict(trace_ms=300.0, lower_ms=200.0, backend_ms=50.0, programs=1,
          cache_hits=1, cache_misses=0)),
    # ...and of one that was compiled and written
    ([("b", _TRACE), (_TRACE, 0.30), ("b", _LOWER), (_LOWER, 0.20),
      ("b", _BACKEND), (_MISS,), (_BACKEND, 4.0)],
     dict(trace_ms=300.0, lower_ms=200.0, backend_ms=4000.0, programs=1,
          cache_hits=0, cache_misses=1)),
    # a jit traced inside a trace is taken out of what encloses it
    ([("b", _TRACE), ("b", _TRACE), (_TRACE, 0.2), ("b", _TRACE),
      (_TRACE, 0.1), (_TRACE, 1.0)],
     dict(trace_ms=1000.0, lower_ms=0.0, backend_ms=0.0, programs=0,
          cache_hits=0, cache_misses=0)),
    # an eager op compiled while lowering: every kind its SELF time
    ([("b", _LOWER), ("b", _TRACE), (_TRACE, 0.01), ("b", _LOWER),
      (_LOWER, 0.02), ("b", _BACKEND), (_BACKEND, 0.07), (_LOWER, 0.5)],
     dict(trace_ms=10.0, lower_ms=420.0, backend_ms=70.0, programs=1,
          cache_hits=0, cache_misses=0)),
    # a duration whose beginning nobody saw counts whole; a span begun
    # and never ended is dropped by the end of what encloses it
    ([(_TRACE, 0.25), ("b", _LOWER), ("b", _TRACE), (_LOWER, 0.5)],
     dict(trace_ms=250.0, lower_ms=500.0, backend_ms=0.0, programs=0,
          cache_hits=0, cache_misses=0)),
    # what jax reports beside: nobody's
    ([("/jax/compilation_cache/compile_requests_use_cache",),
      ("/jax/compilation_cache/compile_time_saved_sec", 3.0),
      ("b", "/jax/some/scalar")],
     dict(trace_ms=0.0, lower_ms=0.0, backend_ms=0.0, programs=0,
          cache_hits=0, cache_misses=0)),
], ids=["hit", "miss", "nested-trace", "eager-in-lower", "unpaired", "else"])
def test_an_account_sums_a_scripted_feed_of_jax_events(script, want):
    prof = StepProfiler()
    with prof.account() as acct:
        _feed(prof, script)
    got = acct.as_dict()
    assert got == pytest.approx(want), got
    assert prof.other.as_dict() == stepprof.Account().as_dict()
    # ...and outside any account the same feed is `other`'s.
    _feed(prof, script)
    assert prof.other.as_dict() == pytest.approx(want)
    assert not prof._thread().spans and not prof._thread().accounts


def test_events_on_another_thread_go_to_other():
    prof = StepProfiler()
    script = [("b", _BACKEND), (_MISS,), (_BACKEND, 2.0)]
    with prof.account() as mine:
        t = threading.Thread(target=_feed, args=(prof, script))
        t.start()
        t.join(10)
        assert not t.is_alive()
        _feed(prof, [("b", _TRACE), (_TRACE, 0.5)])
    assert mine.as_dict()["trace_ms"] == 500.0 and mine.programs == 0
    assert prof.other.as_dict() == pytest.approx(dict(
        trace_ms=0.0, lower_ms=0.0, backend_ms=2000.0, programs=1,
        cache_hits=0, cache_misses=1))
    assert prof.startup_snapshot()["programs"] == {
        "hit": 0, "miss": 1, "off": 0}


def test_nested_accounts_charge_the_innermost():
    prof = StepProfiler()
    with prof.account() as outer:
        _feed(prof, [("b", _TRACE), (_TRACE, 0.1)])
        with prof.account() as inner:
            _feed(prof, [("b", _BACKEND), (_HIT,), (_BACKEND, 0.2)])
        _feed(prof, [("b", _LOWER), (_LOWER, 0.3)])
    assert (outer.trace_ms, outer.lower_ms, outer.backend_ms,
            outer.programs) == pytest.approx((100.0, 300.0, 0.0, 0))
    assert (inner.backend_ms, inner.programs, inner.cache_hits) \
        == pytest.approx((200.0, 1, 1))
    assert prof.other.programs == 0


@pytest.mark.parametrize("programs,hits,misses,word", [
    (0, 0, 0, "off"), (3, 0, 0, "off"), (2, 2, 0, "hit"), (2, 0, 2, "miss"),
    (3, 2, 1, "miss"),
    (3, 2, 0, "miss"),   # one under the thresholds: compiled all the same
], ids=["nothing", "no-directory", "all-hit", "all-miss", "mixed", "partial"])
def test_a_compile_events_cache_word(programs, hits, misses, word):
    a = stepprof.Account()
    a.programs, a.cache_hits, a.cache_misses = programs, hits, misses
    prof = StepProfiler()
    ev = prof.record_compile("ragged", ("ragged", 16, 0), 10.0, a)
    assert ev["cache"] == word and ev["programs"] == programs
    assert prof.brief() == {"compiles": 1, "hit": int(word == "hit"),
                            "miss": int(word == "miss")}


@pytest.mark.parametrize("wall_ms,spent", [
    (100.0, (30.0, 20.0, 40.0)), (100.0, (60.0, 30.0, 20.0)), (5.0, ())],
    ids=["inside", "clocks-disagree", "no-account"])
def test_first_run_ms_is_what_the_split_leaves_and_never_negative(
        wall_ms, spent):
    prof, a = StepProfiler(), None
    if spent:
        a = stepprof.Account()
        a.trace_ms, a.lower_ms, a.backend_ms = spent
    began = time.time() - wall_ms / 1e3
    ev = prof.record_compile("decode", (8, 0), wall_ms, a,
                             t0=began if spent else None)
    assert set(ev) == {"ts", "seq", "site", "key", "wall_ms",
                       *stepprof.COMPILE_SPLIT}
    assert "cache_size" not in ev
    assert ev["first_run_ms"] == pytest.approx(
        max(0.0, wall_ms - sum(spent)))
    assert ev["t0"] == pytest.approx(ev["ts"] - wall_ms / 1e3, abs=0.05)
    assert ev["t0"] <= ev["ts"]


# ------------------------------------------------------- start-up ledger
def _start_up(prof, models=("m",)):
    """cli.main's calls, and a runtime's, at a few milliseconds each."""
    prof.startup_enter("backend")
    time.sleep(0.002)
    for m in models:
        with prof.phase("alloc", m):
            with prof.phase("weights", m):
                _feed(prof, [("b", _BACKEND), (_MISS,), (_BACKEND, 0.5)])
                time.sleep(0.002)
            with prof.phase("place", m):
                time.sleep(0.002)
            time.sleep(0.002)
        time.sleep(0.001)   # back in `backend`
    prof.startup_enter("serve")
    time.sleep(0.002)


@pytest.mark.parametrize("models", [("m",), ("a", "b")],
                         ids=["one-model", "two-models"])
def test_start_phases_are_contiguous_and_sum_to_ready_s(models):
    from ollamamq_tpu.telemetry import schema as tm

    prof = StepProfiler()
    assert prof.startup_snapshot()["ready_s"] is None
    before = time.time()
    assert prof.process_start <= before
    _start_up(prof, models)
    prof.startup_ready()
    prof.startup_ready()             # once: the second is nobody's
    su = prof.startup_snapshot()
    rows = su["phases"]
    assert all(r["in_ready"] for r in rows)
    assert [(r["phase"], r["model"]) for r in rows] == (
        [("import", ""), ("backend", "")]
        + [(ph, m) for m in models for ph in ("alloc", "weights", "place")]
        + [("serve", "")])
    assert {r["phase"] for r in rows} == set(stepprof.START_PHASES)
    # Gapless by construction: the walls ARE ready_s, and each phase
    # first opened where the one before it was still open or ended.
    assert sum(r["wall_s"] for r in rows) == pytest.approx(
        su["ready_s"], abs=1e-4)
    assert su["ready_at"] - su["process_start"] == pytest.approx(
        su["ready_s"], abs=1e-5)
    assert rows[0]["t0"] == su["process_start"]
    assert rows[1]["t0"] == pytest.approx(
        rows[0]["t0"] + rows[0]["wall_s"], abs=1e-5)
    t0s = [r["t0"] for r in rows]
    assert t0s == sorted(t0s) and t0s[-1] <= su["ready_at"]
    assert all(r["wall_s"] >= 0.002 for r in rows[1:])
    # jax's events went to the phase that was open, of its model.
    for r in rows:
        assert r["programs"] == (1 if r["phase"] == "weights" else 0)
        assert r["cache_misses"] == r["programs"]
    assert su["other"]["programs"] == 0
    # The gauges, set once: by phase over models, and their sum.
    by_phase = {ph: sum(r["wall_s"] for r in rows if r["phase"] == ph)
                for ph in stepprof.START_PHASES}
    for ph, wall_s in by_phase.items():
        assert tm.STARTUP_SECONDS.labels(phase=ph).value == pytest.approx(
            wall_s, abs=1e-5)
    assert tm.READY_SECONDS.value == pytest.approx(su["ready_s"], abs=1e-5)


def test_a_model_loaded_later_appends_its_rows_and_leaves_ready_s_alone():
    prof = StepProfiler()
    _start_up(prof)
    prof.startup_ready()
    ready = prof.startup_snapshot()
    # After ready (any thread), and a phase on ANOTHER thread at any time:
    # rows of their own.
    with prof.phase("weights", "late"):
        _feed(prof, [("b", _BACKEND), (_HIT,), (_BACKEND, 0.1)])
        time.sleep(0.002)
    prof.startup_enter("backend")    # the chain has ended: nothing
    su = prof.startup_snapshot()
    assert (su["ready_s"], su["ready_at"]) == (ready["ready_s"],
                                               ready["ready_at"])
    assert su["phases"][:-1] == ready["phases"]
    late = su["phases"][-1]
    assert (late["phase"], late["model"], late["in_ready"]) == (
        "weights", "late", False)
    assert late["wall_s"] >= 0.002 and late["t0"] >= su["ready_at"]
    assert (late["programs"], late["cache_hits"]) == (1, 1)
    assert all(a.closed for a in prof._thread().accounts)

    prof2 = StepProfiler()
    prof2.startup_enter("backend")

    def elsewhere():
        with prof2.phase("alloc", "x"):
            time.sleep(0.002)
    t = threading.Thread(target=elsewhere)
    t.start()
    t.join(10)
    assert not t.is_alive()
    prof2.startup_ready()
    rows = prof2.startup_snapshot()["phases"]
    assert [(r["phase"], r["in_ready"]) for r in rows] == [
        ("import", True), ("backend", True), ("alloc", False)]
    assert sum(r["wall_s"] for r in rows if r["in_ready"]) == pytest.approx(
        prof2.startup_snapshot()["ready_s"], abs=1e-4)
    with pytest.raises(ValueError):
        prof2.startup_enter("warm")
    with pytest.raises(ValueError):
        with prof2.phase("compile"):
            pass


def test_the_startup_ledgers_rings_stay_bounded():
    from ollamamq_tpu.telemetry.stepprof import _START_RING

    prof = StepProfiler()
    for i in range(_START_RING + 20):
        with prof.phase("alloc", f"m{i}"):
            pass
    for _ in range(500):             # begun and never ended
        prof.jax_begin(_TRACE)
    assert len(prof.startup_snapshot()["phases"]) == _START_RING
    assert len(prof._thread().spans) <= 64
    prof.reset()
    su = prof.startup_snapshot()
    assert su["phases"] == [] and su["ready_s"] is None
    json.dumps(prof.snapshot(n=8))


# --------------------------- the real thing: jax, and its persistent cache
def _fresh_jit():
    """A new function object a call: jax traces and lowers it again, and
    asks the backend (or the persistent cache) for the same program."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def inner(x):
        return jnp.sin(x) * 2.0

    def ledger_probe(x):
        return inner(x) + jnp.cos(x)
    return jax.jit(ledger_probe)


def test_a_real_first_call_reads_miss_then_hit_and_off_with_no_directory(
        tmp_path):
    """engine.py's listeners, jax's own events and the persistent cache
    with its thresholds at 0: the same program compiled (`miss`: written),
    fetched (`hit`), and with no cache directory neither (`off`). The
    first call's wall holds its split."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache

    from ollamamq_tpu.engine import engine  # noqa: F401 — the listeners

    names = ("jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    before = {n: getattr(jax.config, n) for n in names}
    x = jnp.ones((4,), jnp.float32)
    x.block_until_ready()
    events = []
    try:
        for n in names:
            jax.config.update(n, 0)
        for where in (str(tmp_path), str(tmp_path), None):
            jax.config.update("jax_compilation_cache_dir", where)
            compilation_cache.reset_cache()
            fn = _fresh_jit()
            t0 = time.monotonic()
            with PROFILER.account() as acct:
                fn(x).block_until_ready()
            events.append(PROFILER.record_compile(
                "ragged", ("probe", len(events)),
                (time.monotonic() - t0) * 1e3, acct))
    finally:
        for n, v in before.items():
            jax.config.update(n, v)
        # (conftest puts the directory back and resets the cache)
    assert [e["cache"] for e in events] == ["miss", "hit", "off"], events
    for e in events:
        assert e["programs"] >= 1
        assert e["trace_ms"] > 0 and e["lower_ms"] > 0 and e["backend_ms"] > 0
        assert e["trace_ms"] + e["lower_ms"] + e["backend_ms"] \
            <= e["wall_ms"] + 0.01, e
        assert e["first_run_ms"] == pytest.approx(
            e["wall_ms"] - e["trace_ms"] - e["lower_ms"] - e["backend_ms"],
            abs=0.01)
    assert PROFILER.brief()["hit"] == 1 and PROFILER.brief()["miss"] == 1
    programs = PROFILER.startup_snapshot()["programs"]
    assert programs["hit"] >= 1 and programs["miss"] >= 1 \
        and programs["off"] >= 1


def test_the_engines_compile_events_and_journal_carry_the_split():
    """A real tiny engine's first calls: every ledger event and its
    journal record say what their wall was, and no `cache_size`."""
    eng = _tpu_engine()
    try:
        items = collect(_run(eng, "split", prompt="short"))
        assert items[-1].kind == "done", items[-1].error
        events = list(PROFILER.compiles)
        assert events
        for e in events:
            assert set(stepprof.COMPILE_SPLIT) <= set(e) \
                and "cache_size" not in e
            # (A step program is memoised by its builder: where an earlier
            # test of this process built it, this runtime's first call
            # traced and compiled nothing, and says so.)
            assert e["cache"] in stepprof.CACHE_OUTCOMES
            if e["programs"]:
                assert e["trace_ms"] > 0 and e["lower_ms"] > 0, e
            assert e["trace_ms"] + e["lower_ms"] + e["backend_ms"] \
                <= e["wall_ms"] + 0.01, e
            assert e["t0"] == pytest.approx(e["ts"] - e["wall_ms"] / 1e3,
                                            abs=0.05)
        jr = [r for r in eng.journal.tail(n=None) if r["kind"] == "compile"]
        assert len(jr) == len(events)
        for r, e in zip(jr, events):
            assert {k: r[k] for k in stepprof.COMPILE_SPLIT} \
                == {k: e[k] for k in stepprof.COMPILE_SPLIT}
            assert "cache_size" not in r
        # A runtime built outside cli.main's chain: rows of its own.
        rows = PROFILER.startup_snapshot()["phases"]
        assert rows and not any(r["in_ready"] for r in rows)
    finally:
        eng.stop()


# ------------------------------------- where it is read: the server's face
@pytest.mark.parametrize("path", ["/debug/stepprof", "/metrics.json"])
def test_the_server_serves_the_startup_block(path, tmp_path):
    """The fake engine behind the real server, started as cli.main starts
    it: the app's start-up hook calls the process ready, and both
    endpoints carry the same `startup` block; /metrics the three series."""
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from ollamamq_tpu.engine.fake import FakeEngine
    from ollamamq_tpu.server.app import Server
    from test_telemetry import parse_prom

    async def main():
        PROFILER.startup_enter("backend")
        with PROFILER.phase("alloc", "test-tiny"):
            eng = FakeEngine(EngineConfig(model="test-tiny", max_slots=8),
                             models={"test-tiny": None},
                             blocklist_path=f"{tmp_path}/blocked.json")
        PROFILER.startup_enter("serve")
        eng.start()
        cl = TestClient(TestServer(Server(eng, timeout_s=30).build_app()))
        await cl.start_server()
        try:
            PROFILER.jax_event(_BACKEND, 0.25)   # after ready: `other`'s
            PROFILER.record_compile("decode", (4, 0), 12.0)
            r = await cl.get(path)
            assert r.status == 200
            body = await r.json()
            r = await cl.get("/metrics")
            return body, parse_prom(await r.text())[2]
        finally:
            await cl.close()
            eng.stop()

    body, samples = asyncio.run(main())
    su = body["startup"]
    assert set(su) == {"process_start", "ready_at", "ready_s", "phases",
                       "other", "programs"}
    assert [r["phase"] for r in su["phases"]] == [
        "import", "backend", "alloc", "serve"]
    assert su["ready_s"] > 0 and sum(
        r["wall_s"] for r in su["phases"]) == pytest.approx(
            su["ready_s"], abs=1e-4)
    assert su["other"]["programs"] == 1 and su["programs"]["off"] == 1
    assert float(samples["ollamamq_ready_seconds"]) == pytest.approx(
        su["ready_s"], abs=1e-5)
    for r in su["phases"]:
        assert float(samples[
            f'ollamamq_startup_seconds{{phase="{r["phase"]}"}}']) \
            == pytest.approx(r["wall_s"], abs=1e-5)
    assert float(samples[
        'ollamamq_compile_programs_total{cache="off"}']) >= 1
    if path == "/debug/stepprof":
        (ev,) = body["compile_events"]
        assert ev["cache"] == "off" and ev["first_run_ms"] == 12.0
    else:
        assert body["stepprof"] == {"compiles": 1, "hit": 0, "miss": 0}
