"""The program says when its chip ran dry (PR 37): a done-bracket on every
step in flight, dry time a launch, and the engine's and the server's CPU
clocks — none of it needs a profiler, a device sync or a chip.

Contracts pinned here:

  - the bracket's arithmetic, on a scripted clock and a scripted
    readiness: device-bound (host work H < device time D) reads 0, 0;
    host-bound reads dry_lo <= H - D <= dry_hi; the loop's idle wait
    inside the bracket is taken out; a runtime that reads every step
    before it launches the next (--spec, the fake) is dry every step;
    a step is not probed while the one it queued behind is busy, and a
    voided step holds nothing back;
  - every generative sample of a FakeEngine and of a real tiny engine
    carries the three fields with 0 <= dry_lo_ms <= dry_hi_ms, and the
    thread's wall is still the sum of its samples (the pinned contract);
  - the three counters and /debug/stepprof's `dry` block agree with the
    samples;
  - a step's time on the device ends when it was SEEN ready, not when
    the host got round to reading it;
  - ollamamq_thread_cpu_seconds_total has `engine` and `server`, rises
    between two scrapes, and is read at a scrape only.
"""

import asyncio
import time

import pytest

from ollamamq_tpu.config import EngineConfig
from ollamamq_tpu.ops.sampling import SamplingParams
from ollamamq_tpu.telemetry import schema as tm
from ollamamq_tpu.telemetry import stepprof
from ollamamq_tpu.telemetry.stepprof import PROFILER
from test_degradation import TINY, _tpu_engine
from test_stepprof import _accounted_ms, _fresh_profiler  # noqa: F401
from testutil import collect

GENERATIVE = ("ragged", "spec_verify", "decode", "fake")


# ------------------------------------------------ the bracket's arithmetic
class Script:
    """One thread's clock driven by hand (milliseconds from 0) over a
    device that runs launched steps in order, each for its own time."""

    def __init__(self):
        self.prof = stepprof.StepProfiler()
        self.clock = stepprof.LoopClock(self.prof, "t")
        self.t0 = self.clock._last
        self.clock._wait_end = self.t0
        self.device_free_at = 0.0   # ms: when the last queued step ends
        self.now = 0.0
        self.ends = {}              # bracket -> ms its step ends at

    def at(self, ms):
        return self.t0 + ms / 1e3

    def phase(self, ms, owner_phase):
        """The thread spends `ms` in a loop phase (a step's phases are
        charged to the loop here: only the stamps matter)."""
        self.clock._switch(self.at(self.now), None, owner_phase)
        self.now += ms

    def launch(self, device_ms):
        """A launch returns now; its step runs `device_ms` on the device
        once the one ahead of it is done."""
        start = max(self.now, self.device_free_at)
        end = self.device_free_at = start + device_ms
        b, lo, hi, ph = self.clock.launched(
            self.at(self.now), lambda: self.now >= end)
        self.ends[b] = end
        return b, round(lo, 6), round(hi, 6), ph

    def block_on(self, b):
        """A blocking read of `b`: the thread sleeps until it is done."""
        self.clock._switch(self.at(self.now), None, "other")
        self.now = max(self.now, self.ends[b])
        self.clock._switch(self.at(self.now), None, "other")
        assert b.seen_ready_at is not None


def pipelined(host_ms, device_ms, steps=6):
    """The engine's loop in small: launch N+1 (host work split over three
    stamped phases), then read N."""
    s = Script()
    out, prev = [], None
    for _ in range(steps):
        for share, name in ((0.5, "other"), (0.2, "admit"), (0.3, "other")):
            s.phase(host_ms * share, name)
        b, lo, hi, ph = s.launch(device_ms)
        out.append((lo, hi, ph))
        if prev is not None:
            s.block_on(prev)
        prev = b
    return out


def test_a_device_bound_loop_is_never_dry():
    """H = 4 ms of host work a step against D = 10 ms on the device: every
    launch finds the step ahead still busy."""
    rows = pipelined(host_ms=4.0, device_ms=10.0)
    assert rows[0] == (0.0, 0.0, None)      # nothing is known to be ahead
    assert all(r == (0.0, 0.0, None) for r in rows[1:]), rows


@pytest.mark.parametrize("host_ms,device_ms", [(10.0, 4.0), (7.0, 6.5),
                                               (30.0, 1.0)])
def test_a_host_bound_loop_brackets_the_exposed_host(host_ms, device_ms):
    """H > D: the chip is dry H - D a step in the steady state, and the
    two fields bracket it — as far apart as the thread's stamps."""
    rows = pipelined(host_ms, device_ms, steps=8)
    exposed = host_ms - device_ms
    for lo, hi, ph in rows[3:]:
        assert 0.0 <= lo <= exposed + 1e-6 <= hi + 2e-6, (lo, exposed, hi)
        assert hi - lo <= 0.5 * host_ms + 1e-6     # the widest phase
        assert (ph in stepprof.DRY_PHASES) == (lo > 0.0)
    assert sum(hi for _, hi, _ in rows) > 0.0


def test_an_idle_wait_inside_the_bracket_is_taken_out():
    """The step ahead ends, the loop naps 50 ms for want of requests, then
    a request comes: the chip was dry only from the wait's end."""
    s = Script()
    b, *_ = s.launch(2.0)
    s.phase(3.0, "other")              # the step ends during this
    s.phase(50.0, "wait")              # seen ready at the wait's start
    s.phase(1.0, "admit")
    s.phase(2.0, "other")
    _, lo, hi, ph = s.launch(2.0)
    assert b.seen_ready_at == pytest.approx(s.at(3.0))
    assert lo == pytest.approx(3.0) and hi == pytest.approx(3.0)
    assert ph == "loop_other"
    # Without the wait the same stamps read the whole gap.
    s = Script()
    s.launch(2.0)
    s.phase(3.0, "other")
    s.phase(50.0, "other")
    s.phase(3.0, "admit")
    _, lo, hi, ph = s.launch(2.0)
    assert lo == pytest.approx(53.0) and hi == pytest.approx(56.0)
    assert ph == "loop_other"


def test_a_runtime_that_reads_before_it_launches_is_dry_every_step():
    """--spec (and the fake): the step is read in the tick that launched
    it, so the next launch always finds the chip empty — from the read's
    return (exact) for the whole compose-and-launch."""
    s = Script()
    rows = []
    for _ in range(5):
        s.phase(1.5, "admit")
        s.phase(2.5, "other")
        b, lo, hi, ph = s.launch(8.0)
        rows.append((lo, hi, ph))
        s.block_on(b)
        s.phase(1.0, "other")          # the emit loop
    for lo, hi, ph in rows[1:]:
        assert lo == pytest.approx(5.0)          # emit + admit + other
        assert hi == pytest.approx(5.0 + 8.0)    # the read held the rest
        assert ph == "loop_other"


def test_a_step_is_not_probed_behind_a_busy_one_and_a_voided_one_is_let_go():
    prof = stepprof.StepProfiler()
    clock = stepprof.LoopClock(prof, "t")
    t = clock._last
    asked = []

    def probe_of(name, ready):
        def probe():
            asked.append(name)
            return ready[0]
        return probe

    a_ready, b_ready = [False], [True]
    a, *_ = clock.launched(t + 0.001, probe_of("a", a_ready))
    b, *_ = clock.launched(t + 0.002, probe_of("b", b_ready))
    assert clock._watch == [a, b]              # the clock keeps the order
    clock._switch(t + 0.003, None, "other")
    assert "b" not in asked[1:] and b.seen_busy_at == t + 0.003
    a_ready[0] = True
    clock._switch(t + 0.004, None, "other")
    assert (a.seen_ready_at, b.seen_ready_at) == (t + 0.004, t + 0.004)
    assert not clock._watch and a.ready is None
    n = len(asked)
    clock._switch(t + 0.005, None, "other")    # seen ready: asked no more
    assert len(asked) == n
    # A voided step: nothing waits behind it, and a launch after it knows
    # nothing of the device.
    c, *_ = clock.launched(t + 0.006, lambda: False)
    d, *_ = clock.launched(t + 0.007, lambda: True)
    assert clock._watch == [c, d]
    clock._unwatch(c)
    clock._switch(t + 0.008, None, "other")
    assert d.seen_ready_at == t + 0.008 and c.seen_ready_at is None
    e, lo, hi, ph = clock.launched(t + 0.009, lambda: False)
    assert (lo, hi) == pytest.approx((1.0, 2.0)) and ph == "loop_other"
    clock._unwatch(e)                          # voided, and the last launched
    _, lo, hi, ph = clock.launched(t + 0.010, lambda: True)
    assert (lo, hi, ph) == (0.0, 0.0, None)
    # A probe that raises (a failed step's result) counts as off the device.
    f, *_ = clock.launched(t + 0.011, lambda: 1 / 0)
    clock._switch(t + 0.012, None, "other")
    assert f.seen_ready_at == t + 0.012


def test_the_timer_carries_the_bracket_and_notes_the_three_fields():
    """StepTimer.launched/collected over a real clock: the sample carries
    the fields, a probe inside a phase sees the end where no mark does,
    and an abandoned timer's bracket leaves the watch."""
    prof = stepprof.StepProfiler()
    clock = stepprof.LoopClock(prof, "t")
    done = [False]
    n = prof.start("ragged", clock)
    n.mark("host_prep")
    n.launched(lambda: done[0], model="m", h2d_transfers=1)
    first = n.done
    n.mark("dispatch")
    n.park()
    assert first.seen_ready_at is None and clock._watch == [first]
    m = prof.start("ragged", clock)
    m.mark("host_prep")
    done[0] = True
    time.sleep(0.002)
    m.probe()                                  # inside `dispatch`
    seen = first.seen_ready_at
    assert seen is not None and not clock._watch
    time.sleep(0.003)
    m.launched(lambda: False, model="m")
    assert m.fields["dry_lo_ms"] >= 3.0
    assert m.fields["dry_hi_ms"] >= m.fields["dry_lo_ms"] + 2.0
    assert m.fields["dry_phase"] == "dispatch"
    m.mark("dispatch")
    m.park()
    n.resume("collect")
    assert n.collected() == seen               # seen before the read
    n.mark("detok")
    s = n.finish()
    assert (s["dry_lo_ms"], s["dry_hi_ms"], s["dry_phase"]) == (0.0, 0.0,
                                                                None)
    assert s["model"] == "m" and s["h2d_transfers"] == 1
    m.abandon()
    assert not clock._watch and m.done.seen_ready_at is None


# -------------------------------------------------- engines on the CPU
def _fake_engine(latency=0.003, spec=False):
    from ollamamq_tpu.engine.fake import FakeEngine

    eng = FakeEngine(EngineConfig(model="test-tiny", max_slots=4,
                                  num_pages=64, page_size=8,
                                  max_pages_per_seq=8, spec=spec),
                     models={"test-tiny": None}, blocklist_path=None,
                     token_latency_s=latency)
    eng.start()
    return eng


def _total(metric):
    return sum(c.value for _, c in metric.series())


def _check_dry_fields(samples):
    gen = [s for s in samples if s["mode"] in GENERATIVE]
    assert gen
    for s in gen:
        assert 0.0 <= s["dry_lo_ms"] <= s["dry_hi_ms"], s
        assert (s["dry_phase"] in stepprof.DRY_PHASES) \
            == (s["dry_lo_ms"] > 0.0), s
        assert s["model"] == "test-tiny"
    return gen


def test_a_fake_engine_run_is_dry_every_step_and_still_gapless():
    """The fake reads every step before it launches the next: dry a step
    by the emit loop and the loop's work, never by the idle waits between
    bursts; sum(total_ms + loop_*_ms) is still the thread's wall; the
    counters and the `dry` block say what the samples say."""
    before = [_total(m) for m in (tm.DEVICE_DRY_SECONDS_TOTAL,
                                  tm.DEVICE_DRY_UPPER_SECONDS_TOTAL,
                                  tm.STEPS_LAUNCHED_DRY_TOTAL)]
    eng = _fake_engine()
    try:
        for burst in range(3):
            reqs = [eng.enqueue_request(
                f"u{burst}{i}", "", "test-tiny", prompt_tokens=[1, 2, 3],
                sampling=SamplingParams(max_tokens=6)) for i in range(2)]
            for r in reqs:
                assert collect(r)[-1].kind == "done"
            time.sleep(0.12)           # idle ticks between bursts
    finally:
        eng.stop()
    run = PROFILER.tail()
    gen = _check_dry_fields(run)
    assert len(gen) == len(run) >= 15
    # Every launch but a burst's first knows the step ahead and finds the
    # chip empty; none counts an idle wait (20 ms a nap) as dry time.
    dry = [s for s in gen if s["dry_lo_ms"] > 0.0]
    assert len(dry) >= len(gen) - 1
    assert all(s["dry_lo_ms"] < 15.0 for s in gen), \
        max(s["dry_lo_ms"] for s in gen)
    assert sum(s["loop_wait_ms"] for s in run) > 150.0
    wall_ms = (run[-1]["ts"] - run[0]["ts"]) * 1e3
    accounted = sum(_accounted_ms(s) for s in run[1:])
    assert abs(accounted - wall_ms) <= 0.01 * wall_ms, (accounted, wall_ms)
    after = [_total(m) for m in (tm.DEVICE_DRY_SECONDS_TOTAL,
                                 tm.DEVICE_DRY_UPPER_SECONDS_TOTAL,
                                 tm.STEPS_LAUNCHED_DRY_TOTAL)]
    lo = sum(s["dry_lo_ms"] for s in gen)
    hi = sum(s["dry_hi_ms"] for s in gen)
    assert after[0] - before[0] == pytest.approx(lo / 1e3, abs=1e-9)
    assert after[1] - before[1] == pytest.approx(hi / 1e3, abs=1e-9)
    assert after[2] - before[2] == len(dry)
    block = PROFILER.snapshot()["summary"]["dry"]
    assert block["launches"] == len(gen) and block["steps"] == len(dry)
    assert block["lo_ms"] == pytest.approx(lo, abs=1e-3)
    assert block["hi_ms"] == pytest.approx(hi, abs=1e-3)
    assert sum(block["by_phase_ms"].values()) == pytest.approx(lo, abs=1e-3)
    assert set(block["by_phase_ms"]) <= set(stepprof.DRY_PHASES)


def _run(eng, user, max_tokens=10):
    tok = eng.resolve_runtime("test-tiny").tokenizer
    return eng.enqueue_request(
        user, "", "test-tiny",
        prompt_tokens=tok.encode("the quick brown fox jumps"),
        sampling=SamplingParams(max_tokens=max_tokens))


@pytest.mark.parametrize("spec", [False, True])
def test_every_generative_sample_of_a_real_engine_carries_the_fields(spec):
    """The pipelined loop and a speculating runtime (which launches
    nothing before it has read the step ahead: dry every step by design)."""
    eng = _tpu_engine(spec=spec, spec_k=2) if spec else _tpu_engine()
    try:
        for u in ("a", "b"):
            assert collect(_run(eng, u))[-1].kind == "done"
        rt = eng.runtimes["test-tiny"]
        assert rt.inflight is None
    finally:
        eng.stop()
    gen = _check_dry_fields(PROFILER.tail())
    assert not eng.loop_clock._watch           # every bracket was closed
    if spec:
        known = [s for s in gen[1:] if not s["compiled"]]
        assert known and all(s["dry_lo_ms"] > 0.0 for s in known)
    assert PROFILER.summary()["dry"]["launches"] == len(gen)


def test_a_steps_device_time_ends_when_it_was_seen_ready_not_when_read():
    """`h.dt` (the step-latency histograms, the mfu gauge, the TUI's line):
    the host comes back 60 ms late to a step that had long left the
    device, and the lateness is not device time."""
    import jax.numpy as jnp

    from ollamamq_tpu.config import MODEL_CONFIGS
    from ollamamq_tpu.core import MQCore
    from ollamamq_tpu.engine.engine import ModelRuntime
    from ollamamq_tpu.engine.request import Request

    rt = ModelRuntime("test-tiny", MODEL_CONFIGS["test-tiny"],
                      EngineConfig(**TINY), dtype=jnp.float32)
    rt.tokenizer.eos_id = -1
    core = MQCore(None)
    req = Request(1, "u", "test-tiny", [5, 6, 7, 8],
                  SamplingParams(max_tokens=6))
    req._inc_decode = rt.tokenizer.make_incremental_decoder()
    rt.pending_prefill.append(req)
    assert rt.step_ragged(core)                # the prompt; compiles
    rt.step_decode(core, 1)                    # compiles; warm from here on
    h = rt.step_decode_dispatch(core, 1)
    assert h is not None and h.sp.done is not None
    deadline = time.monotonic() + 30
    while not h.toks_dev.is_ready():
        assert time.monotonic() < deadline
        time.sleep(0.001)
    h.sp.probe()                               # a stamp of the thread
    seen = h.sp.done.seen_ready_at
    assert seen is not None
    time.sleep(0.06)                           # the host is late
    rt.step_settle(h, core)
    late = time.perf_counter() - h.t_launch
    assert late >= 0.06
    assert h.dt == pytest.approx(seen - h.t_launch, abs=1e-6)
    assert h.dt <= late - 0.055
    assert rt._last_done == seen


# ------------------------------------------------- the threads' CPU clocks
def _cpu_samples(text):
    out = {}
    for line in text.splitlines():
        if line.startswith("ollamamq_thread_cpu_seconds_total{"):
            out[line.split('thread="')[1].split('"')[0]] = \
                float(line.rsplit(" ", 1)[1])
        elif line.startswith("ollamamq_process_cpu_seconds_total "):
            out["process"] = float(line.rsplit(" ", 1)[1])
    return out


def test_thread_cpu_clocks_are_read_at_a_scrape_and_only_then(monkeypatch):
    """`engine` and `server` (the thread that runs the app's loop, which
    registers itself from the start-up hook), rising between two scrapes
    of /metrics — and between the scrapes nobody reads a clock."""
    from aiohttp.test_utils import TestClient, TestServer

    from ollamamq_tpu.server.app import Server

    if not hasattr(time, "pthread_getcpuclockid"):
        pytest.skip("no per-thread CPU clocks on this platform")
    others = sum(map(len, PROFILER._cpu_clocks.values()))  # leaked by
    reads = []                            # earlier tests of this process
    real = stepprof._clock_gettime
    monkeypatch.setattr(stepprof, "_clock_gettime",
                        lambda clk: reads.append(clk) or real(clk))
    eng = _fake_engine(latency=0.0)
    texts = []

    async def drive():
        client = TestClient(TestServer(Server(eng).build_app()))
        await client.start_server()
        try:
            texts.append(await (await client.get("/metrics")).text())
            n = len(reads)
            for i in range(6):
                r = await client.post("/api/generate", json={
                    "model": "test-tiny", "prompt": "hi " * 40,
                    "stream": False, "options": {"num_predict": 16}},
                    headers={"X-User-ID": f"u{i}"})
                assert r.status == 200, await r.text()
            spin = time.process_time() + 0.05
            while time.process_time() < spin:   # the server thread works
                pass
            assert len(reads) == n, "a CPU clock was read between scrapes"
            texts.append(await (await client.get("/metrics")).text())
            # A scrape reads each registered thread once: two threads.
            assert (n, len(reads)) == (others + 2, 2 * others + 4)
        finally:
            await client.close()

    try:
        asyncio.run(drive())
    finally:
        eng.stop()
    first, second = map(_cpu_samples, texts)
    assert set(first) == set(second) == {"engine", "server", "process"}
    assert second["server"] >= first["server"] + 0.04
    assert second["engine"] > first["engine"]
    assert second["process"] >= first["process"] + 0.04
    # Both threads have said goodbye (one last read each): what they used
    # stays in the totals.
    assert len(reads) == 2 * others + 6
    assert PROFILER.cpu_seconds()["engine"] >= second["engine"]
    assert sum(map(len, PROFILER._cpu_clocks.values())) == others


def test_cpu_seconds_sums_a_roles_threads_and_keeps_ended_ones():
    import threading

    if not hasattr(time, "pthread_getcpuclockid"):
        pytest.skip("no per-thread CPU clocks on this platform")
    base = (PROFILER.cpu_seconds() or {}).get("engine", 0.0)
    go = threading.Event()

    def worker():
        PROFILER.cpu_register("engine")
        try:
            spin = time.thread_time() + 0.03
            while time.thread_time() < spin:
                pass
            go.wait(5)
        finally:
            PROFILER.cpu_unregister("engine")

    ths = [threading.Thread(target=worker) for _ in range(2)]
    for th in ths:
        th.start()
    time.sleep(0.2)
    live = PROFILER.cpu_seconds()["engine"] - base
    assert live >= 0.055                       # two threads, summed
    go.set()
    for th in ths:
        th.join()
    assert PROFILER.cpu_seconds()["engine"] - base >= live
    tm.refresh_cpu_seconds(None)               # a platform without: no-op
