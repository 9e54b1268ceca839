"""Engine step profiler: the engine-hot-loop twin of the router's
always-on overhead plane (PR 14).

Every dispatch the engine makes — ragged mixed batch, pure-decode fused
scan, speculative verify, embed batch, FakeRuntime step; python, fake,
and SPMD-primary alike — records ONE schema'd sample into a bounded
ring: where that step's milliseconds went (`host_prep` → `dispatch` →
`collect` → `detok`, the CLOSED phase vocabulary below), under which
compiled shape (`(mode, T_pad, k_cap)`), over how many real vs padded
token positions, whether the step paid a fresh XLA compile, and — for a
generative step — how many host→device transfers its inputs took and
their bytes (`h2d_transfers`, `h2d_bytes`; the
`ollamamq_step_h2d_*_total` counters: one transfer a step, the packed
buffer of engine/step_pack.py). The
same samples feed `ollamamq_step_phase_ms{phase,mode}` histograms, a
rolling per-shape p50/p99 table, `/debug/stepprof`, the TUI `compiles`
chip, and the `step_profile` section of the diagnostics bundle.

Dependency-free (stdlib only — no jax, no numpy) like the rest of
`telemetry/`, so scripts/check_metrics_docs.py can import the phase
vocabulary in CI.

Contracts the tests pin:

  * Phases are deltas between marks of one monotonic timer, so a
    sample's phase milliseconds sum EXACTLY to its recorded step time —
    and instrumentation covers ≥95% of the measured dispatch wall (the
    5% acceptance gate is coverage, not arithmetic). A step is launched
    in one tick and settled in a later one, behind the NEXT step's
    launch: between its halves the timer is parked and the thread's
    time goes to whoever holds the cursor, so two interleaved steps
    never count an instant twice and `collect` is the time actually
    blocked on the device.
  * The ring, the per-shape table, the compile-event ring, and the HBM
    timeline are all bounded — always-on means O(1) memory forever.
  * Self-overhead is metered: every profiler entry point times itself
    (perf_counter_ns) and `overhead_fraction()` must stay under 1% of
    profiled step time.
  * The engine thread's time is accounted for without a gap: between
    steps the thread is in one of the LOOP_PHASES, kept by a LoopClock
    the ENGINE owns (never this process-wide profiler: an in-process
    fleet runs several engine threads into one ring) and written into
    the next sample as `loop_*_ms`. Over consecutive samples of one
    thread, sum(total_ms + loop_*_ms) is the thread's wall time.
  * While a device capture runs (`PROFILER.capturing`, set by
    POST /debug/profile) every phase is also entered as a span on the
    profiler's clock (SPAN_NAMES), carrying the `seq` its sample will
    have — one mechanism feeds samples, histograms and spans. A phase
    that holds more than one job has child spans at its seams
    (CHILD_SPANS, `seam()`): spans only, never a sample field, and with
    no capture running a seam costs one attribute test.
  * The program knows when its chip ran dry without a profiler: every
    launched step carries a DoneBracket — the last instant its result
    was seen NOT ready and the first it was seen ready, probed
    (non-blocking, through a hook the runtime hands over: this file
    never imports jax) at the instants the thread stamps anyway. A
    launch whose step ahead had been seen ready by then records
    `dry_lo_ms <= true gap <= dry_hi_ms` and `dry_phase`; the loop's
    idle wait is never dry time.
  * The engine's and the server's threads register for their CPU
    clocks (`cpu_register`); the clocks are read only when /metrics is
    rendered (`cpu_seconds`), never on the hot path.
  * Compile events are recorded by the jit-getter seams exactly once
    per cache key (jax.jit traces+compiles synchronously on the first
    call of a fresh cache entry — timing that first call IS the compile
    wall); a recompile loop (ladder bug, pallas-probe thrash, injected
    `compile` fault) shows up as a climbing `rate_per_min` and trips
    the health monitor's `compile_storm` alert after warmup.

Module-global `PROFILER` (same pattern as metrics.REGISTRY): the
engine and FakeRuntime feed it; the server and TUI read it;
tests call `PROFILER.reset()` for isolation.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Dict, List, Optional, Tuple

from ollamamq_tpu.telemetry import schema as tm

# CLOSED phase vocabulary for ollamamq_step_phase_ms{phase} — pinned to
# the README "Engine performance plane" table by
# scripts/check_metrics_docs.py (gate 6). A new timed region of the
# dispatch path means a new entry HERE first.
PHASES = (
    "host_prep",   # python-side batch composition: admission bookkeeping,
    #                token/slot array builds, device_put staging — ends at
    #                the jit call
    "dispatch",    # issuing the jit'd computation: trace + XLA compile on
    #                a fresh cache key (the `compiled` flag), else just
    #                enqueue — returns with device arrays still in flight
    "collect",     # device wait + D2H materialization (np.asarray /
    #                block_until_ready): the time the thread was BLOCKED
    #                on this step — near zero when the step ran behind
    #                the next one's composition
    "detok",       # host-side emit loop: sampling bookkeeping, detokenize,
    #                stream writes, per-request finish handling
)

# CLOSED vocabulary of what the engine thread does BETWEEN steps, pinned
# to the README beside PHASES. Observed into ollamamq_step_phase_ms as
# phase="loop_<name>" and written into the NEXT sample as loop_<name>_ms.
LOOP_PHASES = (
    "admit",   # TPUEngine._admit(): MQCore pops + placement onto runtimes
    "other",   # everything else outside a step timer: HBM sample, engine
    #            calls, swap/recover, check_cancellations, the choice of
    #            k, and step timers that were abandoned (early returns)
    "wait",    # the condvar wait of a tick that did no work
)

# The same phases as spans on the device trace's clock, emitted only
# while a capture runs. Pinned to the README span table (gate 7).
PHASE_SPANS = (tuple("mq." + p for p in PHASES)
               + tuple("mq.loop." + p for p in LOOP_PHASES))
# The seams INSIDE a phase that holds more than one job (LoopClock.seam):
# phase span -> its child spans `<phase span>.<child>`, emitted while a
# capture runs and never otherwise — spans only, no sample field. A phase
# gets children where a capture charged it more than a quarter of a cell's
# idle seconds (PERF.md section 3 says which captures called for which).
CHILD_SPANS = {
    "mq.dispatch": (
        "launch",   # the step's key, the upload of its packed buffer and
        #             the jitted call, up to its return
        "note",     # the done-bracket and what the launch notes on its
        #             sample (slot state, attention and latent counters)
    ),
}
# One span as a capture begins, on the thread that started it (never the
# engine's): its `epoch_ns` is the realtime clock at its own start.
CLOCK_SPAN = "mq.clock"
SPAN_NAMES = PHASE_SPANS + tuple(
    p + "." + c for p, cs in CHILD_SPANS.items() for c in cs) + (CLOCK_SPAN,)

# The phase a mark OPENS: marks name the phase that ended, a span needs
# its name when it begins, and the order is fixed.
_NEXT_PHASE = dict(zip(PHASES, PHASES[1:]))
_LOOP_KEY = {p: "loop_" + p for p in LOOP_PHASES}
# Every `<phase>_ms` field of a sample = every `phase` label value of
# ollamamq_step_phase_ms.
_SAMPLE_PHASES = PHASES + tuple("loop_" + p for p in LOOP_PHASES)
# Sample fields a span carries once the step has noted them.
_SPAN_FIELDS = ("T_pad", "k_cap", "tokens")
# What `dry_phase` may say: the phase that held most of the time the
# chip had nothing queued before a launch. The idle wait is not among
# them — a chip idle for want of requests is not dry.
DRY_PHASES = tuple(p for p in _SAMPLE_PHASES if p != "loop_wait")

# Step modes (the `mode` label + the first element of the shape key).
# Not a validation gate — a sample carries whatever the engine said —
# but the set the engine emits today, for readers.
MODES = ("ragged", "spec_verify", "decode", "embed", "fake")

_RING = 2048          # sample ring (like --journal-ring's default)
_SHAPE_KEYS = 64      # distinct (mode, T_pad, k_cap) keys kept
_SHAPE_WINDOW = 256   # rolling per-shape totals window
_COMPILE_RING = 256   # compile-event ring
_HBM_RING = 512       # HBM/allocator timeline ring
_RATE_WINDOW_S = 60.0  # compile-rate lookback
# thread= of ollamamq_thread_cpu_seconds_total (StepProfiler.cpu_register)
CPU_THREADS = ("engine", "server")
_clock_gettime = getattr(time, "clock_gettime", None)


def _pctl(window, q: float) -> Optional[float]:
    if not window:
        return None
    s = sorted(window)
    return s[min(len(s) - 1, int(q * len(s)))]


class DoneBracket:
    """When a launched step left the device, as two instants of its
    thread's clock: `seen_busy_at`, the last probe that found its result
    not ready (to begin with, the instant its launch returned), and
    `seen_ready_at`, the first that found it ready (None until then; at
    the latest the return of the blocking read). The step ended between
    the two. `ready` is the runtime's probe — non-blocking, no transfer:
    `jax.Array.is_ready` of the step's ids, the fake's own notion of a
    step's end — dropped once it said yes."""

    __slots__ = ("ready", "seen_busy_at", "seen_ready_at", "_cum")

    def __init__(self, ready, t: float):
        self.ready = ready
        self.seen_busy_at = t
        self.seen_ready_at: Optional[float] = None
        self._cum: Optional[dict] = None  # its clock's totals when seen ready


class LoopClock:
    """One engine thread's cursor over its own time: a single open phase
    at any instant, so nothing the thread does is outside a named phase.
    The ENGINE owns it (one per engine thread) and hands it to its
    runtimes; step timers started with it advance the same cursor, so
    step phases and loop phases form one contiguous chain. Used from
    its own thread only.

    `enter(phase)` closes whatever is open and opens a LOOP_PHASES
    entry; a StepTimer's start/mark/resume do the same for PHASES. Time
    charged to loop phases since the last recorded sample rides in the
    NEXT sample recorded as loop_*_ms. A step in flight across ticks has
    PARKED its timer (the cursor is the loop's or the next step's
    meanwhile). `tick()` — top of an engine tick — folds what timers
    that are neither finished nor parked were charged into `other`: they
    were abandoned, so early returns and faulted dispatches leave no
    hole.

    The same stamps are where the thread LOOKS at its steps in flight
    (`_probe`): each switch of phase asks the launched steps' brackets,
    oldest first and without blocking, whether their result is ready —
    so that a launch (`launched`) can say for how long the chip had had
    nothing queued."""

    __slots__ = ("name", "_prof", "_last", "_owner", "_open", "_span",
                 "_child", "_loop", "_timers", "_seq", "_adopted", "_watch",
                 "_ahead", "_cum", "_wait_end", "_cum_wait_end")

    def __init__(self, prof: "StepProfiler", name: str):
        self._prof = prof
        self.name = name
        # While a capture runs: the open phase's (span, name, seq), and
        # the child span open at one of its seams.
        self._span = None
        self._child = None
        # Milliseconds charged to each phase, ever (never reset: a
        # bracket's snapshot of it must stay comparable).
        self._cum = dict.fromkeys(_SAMPLE_PHASES, 0.0)
        self.reset()

    def reset(self) -> None:
        """(Re)start the chain now — the engine thread calls it as it
        starts, so a stopped engine's downtime is in no phase."""
        self._close_span()
        self._last = time.perf_counter()
        self._owner: Optional["StepTimer"] = None  # None = a loop phase
        self._open = "other"
        self._loop = dict.fromkeys(LOOP_PHASES, 0.0)
        self._timers: List["StepTimer"] = []  # started, not yet finished
        self._seq: Optional[int] = None  # reserved for the next sample
        self._adopted: Optional["StepTimer"] = None
        self._watch: List[DoneBracket] = []  # launched, not seen ready
        # The step launched last: an engine's runtimes share its devices,
        # so whatever this thread launches next queues behind it.
        self._ahead: Optional[DoneBracket] = None
        # A (re)start counts as the end of an idle wait: the time the
        # engine was down is not time its chip ran dry.
        self._wait_end = self._last
        self._cum_wait_end = dict(self._cum)

    def _close_span(self) -> None:
        child, self._child = self._child, None
        if child is not None:
            child.__exit__(None, None, None)
        span, self._span = self._span, None
        if span is not None:
            span[0].__exit__(None, None, None)

    def _switch(self, t: float, owner: Optional["StepTimer"], phase: str,
                charge: Optional[tuple] = None, probe: bool = True) -> None:
        """Close the open phase at `t` and open `phase` for `owner`. The
        closed slice goes to `charge` = (timer, phase) when a step mark
        names it, else to the phase it was opened under. `probe`: `t` is
        now, so the steps in flight can be looked at under this stamp."""
        ms = (t - self._last) * 1e3
        self._last = t
        tgt, name = charge if charge is not None \
            else (self._owner, self._open)
        if tgt is None:
            self._loop[name] += ms
            name = _LOOP_KEY[name]
            self._cum[name] += ms
            if name == "loop_wait":
                self._wait_end, self._cum_wait_end = t, dict(self._cum)
        else:
            tgt.phases[name] = tgt.phases.get(name, 0.0) + ms
            self._cum[name] = self._cum.get(name, 0.0) + ms
        if self._span is not None:
            self._close_span()
        self._owner, self._open = owner, phase
        if probe and self._watch:
            self._probe(t)
        prof = self._prof
        if prof.capturing and prof.span_factory is not None:
            self._open_span(prof, owner, phase)

    def _open_span(self, prof: "StepProfiler",
                   owner: Optional["StepTimer"], phase: str) -> None:
        if owner is None:
            # Loop time rides in the next sample RECORDED: the oldest
            # parked step's, else the next step's to start.
            nxt = next((t for t in self._timers if t.parked), None)
            if nxt is not None:
                if nxt.seq is None:
                    nxt.seq = prof._reserve_seq()
                seq = nxt.seq
            else:
                if self._seq is None:
                    self._seq = prof._reserve_seq()
                seq = self._seq
            name = "mq.loop." + phase
            span = prof.span_factory(name, seq=seq)
        else:
            if owner.seq is None:
                owner.seq = prof._reserve_seq()
            name, seq = "mq." + phase, owner.seq
            span = prof.span_factory(
                name, seq=seq, mode=owner.mode,
                **{k: v for k, v in owner.fields.items()
                   if k in _SPAN_FIELDS})
        span.__enter__()
        self._span = (span, name, seq)

    def seam(self, child: str) -> None:
        """A seam INSIDE the open phase, where it holds more than one
        job: while a capture runs, the child span open under the phase's
        span (if any) closes and `<phase span>.<child>` opens, carrying
        its parent's `seq` (CHILD_SPANS; it closes with its parent at the
        latest). A span only: no sample field, no histogram, no counter —
        with no capture running a seam costs this one test."""
        prof = self._prof
        if prof.capturing and self._span is not None:
            t0 = time.perf_counter_ns()
            if self._child is not None:
                self._child.__exit__(None, None, None)
            _, name, seq = self._span
            self._child = prof.span_factory(name + "." + child, seq=seq)
            self._child.__enter__()
            prof._overhead_ns += time.perf_counter_ns() - t0

    # -- the done-bracket of every step in flight ---------------------------
    def _open_key(self) -> str:
        return self._open if self._owner is not None \
            else _LOOP_KEY[self._open]

    def _probe(self, t: float) -> None:
        """Look at the launched steps under the stamp `t`. They run in
        the order they were launched, so only the oldest is asked: seen
        ready (once — it leaves the watch, and the next is asked), or it
        and all behind it are busy still."""
        watch = self._watch
        while watch:
            b = watch[0]
            try:
                ready = b.ready()
            except Exception:  # a failed step has left the device too
                ready = True
            if not ready:
                break
            del watch[0]
            self._seen_ready(b, t)
        for b in watch:
            b.seen_busy_at = t

    def _seen_ready(self, b: DoneBracket, t: float) -> None:
        b.seen_ready_at = t
        b.ready = None
        # The totals as of `t`: the open phase's slice is not in them
        # yet (a probe inside a phase; zero at a switch).
        b._cum = dict(self._cum)
        b._cum[self._open_key()] += (t - self._last) * 1e3

    def probe(self) -> None:
        """A look from INSIDE a long phase (the settle's row loop), where
        the marks lie too far apart to bracket a step's end."""
        if self._watch:
            t = time.perf_counter()
            self._probe(t)
            self._prof._overhead_ns += time.perf_counter_ns() - int(t * 1e9)

    def _unwatch(self, b: Optional[DoneBracket]) -> None:
        """A step that will never be read (voided, abandoned): its end is
        nobody's to know."""
        if b is not None and b.seen_ready_at is None:
            b.ready = None
            if b in self._watch:
                self._watch.remove(b)

    def launched(self, t: float, ready) -> tuple:
        """A step's launch returned at `t`: its program is queued behind
        the step this thread launched before it (none yet, or one that
        was voided: nothing is known). Returns the new step's bracket and
        (dry_lo_ms, dry_hi_ms, dry_phase): if the step ahead had been
        seen ready by now the chip had nothing queued since that step's
        end, which lies between the two probes — so the true gap lies
        between the two numbers — and `dry_phase` is the phase that held
        most of the lower one; else (0, 0, None). An idle wait in between
        means the chip was idle for want of requests, not dry: the gap
        then counts from the wait's end."""
        if self._watch:
            self._probe(t)
        ahead = self._ahead
        lo = hi = 0.0
        phase = None
        if ahead is not None and ahead.seen_ready_at is not None:
            since = self._wait_end
            hi = (t - max(ahead.seen_busy_at, since)) * 1e3
            lo = (t - max(ahead.seen_ready_at, since)) * 1e3
            if lo > 0.0:
                phase = self._dry_phase(t, ahead)
            else:
                lo = 0.0
        b = self._ahead = DoneBracket(ready, t)
        self._watch.append(b)
        return b, lo, max(hi, lo), phase

    def _dry_phase(self, t: float, ahead: DoneBracket) -> str:
        """The phase that holds most of [max(ahead seen ready, the last
        wait's end), t], by this clock's own totals."""
        base = self._cum_wait_end if ahead.seen_ready_at < self._wait_end \
            else ahead._cum
        key = self._open_key()
        by = {k: self._cum[k] - base[k] for k in DRY_PHASES}
        by[key] = by.get(key, 0.0) + (t - self._last) * 1e3
        return max(by, key=by.get)

    def enter(self, phase: str) -> None:
        """Open a LOOP_PHASES entry (closing whatever was open)."""
        t = time.perf_counter()
        self._switch(t, None, phase)
        self._prof._overhead_ns += time.perf_counter_ns() - int(t * 1e9)

    def _fold(self, timer: "StepTimer") -> None:
        """A timer that will never record: what it was charged is the
        loop's `other`."""
        timer._done = True
        self._loop["other"] += sum(timer.phases.values())
        self._timers.remove(timer)
        self._unwatch(timer.done)

    def tick(self) -> None:
        """Top of an engine tick: a timer that is neither finished nor
        parked (parked = its step is in flight, to be settled behind the
        next launch) was abandoned — what it was charged is `other` (and
        it can no longer record), and the seq its spans carried goes
        back to the clock when nobody reserved a later one."""
        if self._owner is not None \
                or any(not t.parked for t in self._timers):
            t = time.perf_counter()
            if self._owner is not None:
                self._switch(t, None, "other")
            folded = [x for x in self._timers if not x.parked]
            for x in folded:
                self._fold(x)
            a = self._adopted
            if a in folded and a.seq == self._prof.seq:
                self._seq = a.seq
            self._prof._overhead_ns += time.perf_counter_ns() - int(t * 1e9)
        self._adopted = None


class StepTimer:
    """One step's phase clock over its thread's LoopClock. `mark(phase)`
    charges everything since the previous boundary to `phase` and opens
    the next phase of the fixed order; `finish(**fields)` records the
    sample (or never call it — an abandoned timer leaves no sample, which
    is exactly what a faulted/preempted dispatch should leave; its time
    is folded into the loop's `other` at the next tick). Phases may be
    marked more than once (chunked host prep); deltas accumulate. Where
    several timers are live on one thread (step N settled behind step
    N+1's launch; one engine, two runtimes), each phase holds the thread
    time charged to it, so the timers never count an instant twice:
    `park()` hands the cursor back to the loop between a step's halves
    and `resume(phase)` takes it again."""

    __slots__ = ("_prof", "_clock", "mode", "phases", "fields", "seq",
                 "_done", "parked", "done")

    def __init__(self, prof: "StepProfiler", mode: str,
                 clock: Optional[LoopClock] = None):
        t = time.perf_counter()
        if clock is None:  # a step outside any engine loop (tests)
            clock = LoopClock(prof, threading.current_thread().name)
            clock._last = t
        self._prof = prof
        self._clock = clock
        self.mode = mode
        self.phases: Dict[str, float] = {}
        self.fields: Dict[str, object] = {}
        self._done = False
        self.parked = False
        self.done: Optional[DoneBracket] = None  # set by launched()
        # The seq the loop spans before this step carried, if any: those
        # spans' time is written into this step's sample.
        self.seq = clock._seq
        if self.seq is not None:
            clock._seq, clock._adopted = None, self
        clock._timers.append(self)
        clock._switch(t, self, PHASES[0])
        prof._overhead_ns += time.perf_counter_ns() - int(t * 1e9)

    def note(self, **fields) -> None:
        """Sample fields known before the step ends (T_pad, k_cap,
        tokens): spans opened from here on carry them, and finish()
        records them unless it names them again."""
        self.fields.update(fields)

    def mark(self, phase: str) -> None:
        t = time.perf_counter()
        nxt = _NEXT_PHASE.get(phase)
        self._clock._switch(t, self if nxt else None, nxt or "other",
                            charge=(self, phase))
        # Self-overhead: the mark itself (two clock reads + a dict op).
        self._prof._overhead_ns += time.perf_counter_ns() - int(t * 1e9)

    def launched(self, ready, **fields) -> None:
        """The step's launch has just returned (the jitted call; a
        fake's start of its sleep): open its done-bracket on `ready`, a
        non-blocking probe of its result, and note how long the chip
        had had nothing queued (`dry_lo_ms`, `dry_hi_ms`, `dry_phase`,
        LoopClock.launched) with whatever else the launch knows
        (`fields`)."""
        t = time.perf_counter()
        self.done, lo, hi, phase = self._clock.launched(t, ready)
        self.fields.update(fields, dry_lo_ms=round(lo, 4),
                           dry_hi_ms=round(hi, 4), dry_phase=phase)
        self._prof._overhead_ns += time.perf_counter_ns() - int(t * 1e9)

    def probe(self) -> None:
        """Look at the steps in flight from inside this step's phase."""
        self._clock.probe()

    def seam(self, child: str) -> None:
        """A seam inside this step's open phase (LoopClock.seam)."""
        self._clock.seam(child)

    def collected(self) -> float:
        """The blocking read of the step's result has just returned:
        mark `collect`, and if no probe saw the step ready before, it is
        seen ready now. Returns the instant it was seen ready."""
        self.mark("collect")
        b, clock = self.done, self._clock
        if b is None:
            return clock._last
        if b.seen_ready_at is None:
            if b in clock._watch:
                clock._watch.remove(b)
            clock._seen_ready(b, clock._last)
        return b.seen_ready_at

    def park(self) -> None:
        """The step is in flight and the thread goes on to other work:
        close the open phase (charged to this timer) and give the cursor
        to the loop until `resume`. A parked timer survives ticks."""
        t = time.perf_counter()
        clock = self._clock
        if clock._owner is self:
            clock._switch(t, None, "other")
        self.parked = True
        self._prof._overhead_ns += time.perf_counter_ns() - int(t * 1e9)

    def resume(self, phase: str) -> None:
        """Take the cursor back: whatever was open closes (charged to its
        own owner) and `phase` opens for this step."""
        t = time.perf_counter()
        self.parked = False
        self._clock._switch(t, self, phase)
        self._prof._overhead_ns += time.perf_counter_ns() - int(t * 1e9)

    def abandon(self) -> None:
        """A parked step that will never be settled (voided after a
        fault): what it was charged is the loop's `other`, at once."""
        if self._done:
            return
        clock = self._clock
        if clock._owner is self:
            clock._switch(time.perf_counter(), None, "other")
        clock._fold(self)

    def finish(self, **fields) -> Optional[dict]:
        clock = self._clock
        # Double-finish is a bug upstream, and a timer the loop already
        # folded into `other` must not count its time twice: stay silent.
        if self._done:
            return None
        self._done = True
        t = time.perf_counter()
        # The step ends at its LAST mark: total is then the exact sum of
        # the phase deltas, and the microseconds between that mark and
        # this call — argument evaluation at the finish() call site —
        # are the loop's, not step time.
        if clock._owner is self:
            clock._switch(clock._last, None, "other", probe=False)
        total_ms = sum(self.phases.values())
        clock._timers.remove(self)
        sample = {
            "ts": time.time(),
            "mode": self.mode,
            "total_ms": round(total_ms, 4),
        }
        for ph in PHASES:
            sample[ph + "_ms"] = round(self.phases.get(ph, 0.0), 4)
        loop = clock._loop
        for ph in LOOP_PHASES:
            sample["loop_" + ph + "_ms"] = round(loop[ph], 4)
            loop[ph] = 0.0
        sample["thread"] = clock.name
        sample.update(self.fields)
        sample.update(fields)
        self._prof._record(sample, total_ms, self.seq)
        self._prof._overhead_ns += time.perf_counter_ns() - int(t * 1e9)
        return sample


class StepProfiler:
    """Always-on bounded-ring step profiler + compile ledger + HBM
    timeline. Thread-safe: runtimes append from the engine loop while
    HTTP readers snapshot."""

    def __init__(self, ring: int = _RING):
        self._lock = threading.Lock()
        self._ring_n = ring
        self._overhead_ns = 0  # time spent inside profiler calls
        # Spans on the device trace's clock. `span_factory(name, **stats)`
        # is handed over by the engine module (which imports jax:
        # jax.profiler.TraceAnnotation) so this file stays stdlib-only;
        # `capturing` is set by POST /debug/profile after start_trace
        # returns and cleared before stop_trace. With no capture running
        # a mark pays one attribute test.
        self.span_factory = None
        self.capturing = False
        # The serving threads' CPU clocks (cpu_register): role ->
        # {thread id: clock id}, and the seconds of threads that ended.
        # Not a sample's business: reset() leaves them alone.
        self._cpu_clocks: Dict[str, Dict[int, int]] = {}
        self._cpu_ended: Dict[str, float] = {}
        self._reset_locked()

    def _reset_locked(self) -> None:
        self.samples: deque = deque(maxlen=self._ring_n)
        self.seq = 0
        self._step_ns = 0      # accounted thread time: steps + loop phases
        self._overhead_ns = 0
        # (mode, T_pad, k_cap) -> deque of total_ms; insertion-ordered so
        # the oldest shape key is evicted when the table fills.
        self._shapes: Dict[Tuple, deque] = {}
        self._phase_sum: Dict[Tuple[str, str], float] = {}
        self._tokens = 0
        self._padded = 0
        # Launches that knew the step ahead of them, those that found
        # the chip dry, and for how long (sum of the samples' fields).
        self._dry = {"launches": 0, "steps": 0, "lo_ms": 0.0, "hi_ms": 0.0,
                     "by_phase_ms": {}}
        self.compiles: deque = deque(maxlen=_COMPILE_RING)
        self.compile_seq = 0
        self._compile_ts: deque = deque(maxlen=_COMPILE_RING)
        self.hbm: deque = deque(maxlen=_HBM_RING)

    def reset(self) -> None:
        with self._lock:
            self._reset_locked()

    # -- step samples ------------------------------------------------------
    def start(self, mode: str, clock: Optional[LoopClock] = None) -> StepTimer:
        """`clock`: the engine thread's LoopClock (runtimes pass the one
        their engine attached); None times the step alone."""
        return StepTimer(self, mode, clock)

    def stamp_clock(self) -> None:
        """One `mq.clock` span on the calling thread, carrying the
        realtime clock at its own start (`epoch_ns`): entered by POST
        /debug/profile as its capture begins, so that a trace's events
        can be placed on the epoch clock of the samples' `ts`."""
        if self.span_factory is not None:
            with self.span_factory(CLOCK_SPAN, epoch_ns=time.time_ns()):
                pass

    def _reserve_seq(self) -> int:
        with self._lock:
            self.seq += 1
            return self.seq

    def _record(self, sample: dict, total_ms: float,
                seq: Optional[int] = None) -> None:
        """(from StepTimer.finish alone, which meters this call with its
        own: metering it here too counted a third of the profiler's time
        twice, up to PR 51.)"""
        key = (sample["mode"], sample.get("T_pad", 0), sample.get("k_cap", 0))
        loop_ms = sum(sample["loop_" + ph + "_ms"] for ph in LOOP_PHASES)
        with self._lock:
            if seq is None:  # no span carried it: in ring order, as ever
                self.seq += 1
                seq = self.seq
            sample["seq"] = seq
            self.samples.append(sample)
            self._step_ns += int((total_ms + loop_ms) * 1e6)
            win = self._shapes.get(key)
            if win is None:
                while len(self._shapes) >= _SHAPE_KEYS:  # bounded key table
                    self._shapes.pop(next(iter(self._shapes)))
                win = self._shapes[key] = deque(maxlen=_SHAPE_WINDOW)
            win.append(total_ms)
            mode = sample["mode"]
            for ph in PHASES:
                v = sample.get(ph + "_ms", 0.0)
                if v:
                    self._phase_sum[(mode, ph)] = \
                        self._phase_sum.get((mode, ph), 0.0) + v
            self._tokens += int(sample.get("tokens", 0) or 0)
            self._padded += int(sample.get("padded_tokens", 0) or 0)
            dry_lo = sample.get("dry_lo_ms")
            if dry_lo is not None:
                d = self._dry
                d["launches"] += 1
                d["hi_ms"] += sample["dry_hi_ms"]
                if dry_lo > 0.0:
                    d["steps"] += 1
                    d["lo_ms"] += dry_lo
                    by, ph = d["by_phase_ms"], sample["dry_phase"]
                    by[ph] = by.get(ph, 0.0) + dry_lo
        if dry_lo is not None:
            model = sample.get("model", "")
            if sample["dry_hi_ms"] > 0.0:
                tm.DEVICE_DRY_UPPER_SECONDS_TOTAL.labels(model=model) \
                    .inc(sample["dry_hi_ms"] / 1e3)
            if dry_lo > 0.0:
                tm.DEVICE_DRY_SECONDS_TOTAL.labels(model=model) \
                    .inc(dry_lo / 1e3)
                tm.STEPS_LAUNCHED_DRY_TOTAL.labels(model=model).inc()
        for ph in _SAMPLE_PHASES:
            v = sample.get(ph + "_ms", 0.0)
            if v:
                tm.STEP_PHASE_MS.labels(phase=ph, mode=sample["mode"]) \
                    .observe(v)
        if "h2d_transfers" in sample:
            tm.STEP_H2D_TRANSFERS_TOTAL.labels(mode=sample["mode"]) \
                .inc(sample["h2d_transfers"])
            tm.STEP_H2D_BYTES_TOTAL.labels(mode=sample["mode"]) \
                .inc(sample.get("h2d_bytes", 0))

    # -- compile ledger ----------------------------------------------------
    def record_compile(self, site: str, key, wall_ms: float,
                       cache_size: int) -> dict:
        t0 = time.perf_counter_ns()
        ev = {
            "ts": time.time(),
            "site": site,
            "key": str(key),
            "wall_ms": round(wall_ms, 3),
            "cache_size": cache_size,
        }
        with self._lock:
            self.compile_seq += 1
            ev["seq"] = self.compile_seq
            self.compiles.append(ev)
            self._compile_ts.append(time.monotonic())
        tm.COMPILE_TOTAL.labels(site=site).inc()
        tm.COMPILE_MS.observe(wall_ms)
        self._overhead_ns += time.perf_counter_ns() - t0
        return ev

    def compile_count(self) -> int:
        with self._lock:
            return self.compile_seq

    def compile_rate_per_min(self, window_s: float = _RATE_WINDOW_S) -> float:
        """Recompiles per minute over the trailing window — the health
        monitor's compile_storm input. A full ladder warmup is a burst
        that ages out of the window; a storm doesn't."""
        now = time.monotonic()
        with self._lock:
            n = sum(1 for t in self._compile_ts if now - t <= window_s)
        return n * 60.0 / window_s if window_s > 0 else 0.0

    # -- HBM / allocator timeline ------------------------------------------
    def hbm_record(self, sample: dict) -> None:
        t0 = time.perf_counter_ns()
        sample.setdefault("ts", time.time())
        with self._lock:
            self.hbm.append(sample)
        self._overhead_ns += time.perf_counter_ns() - t0

    def hbm_tail(self, n: Optional[int] = None) -> List[dict]:
        with self._lock:
            out = list(self.hbm)
        return out[-n:] if n else out

    # -- readers -----------------------------------------------------------
    def overhead_fraction(self) -> float:
        """Profiler-internal time (step marks, loop accounting, spans
        while a capture runs) / the engine-thread time the recorded
        samples account for (step phases + loop phases). The <1%
        always-on budget; 0.0 before any sample."""
        with self._lock:
            if self._step_ns <= 0:
                return 0.0
            return self._overhead_ns / self._step_ns

    def shape_table(self) -> List[dict]:
        with self._lock:
            items = [(k, list(w)) for k, w in self._shapes.items()]
        out = []
        for (mode, t_pad, k_cap), win in items:
            out.append({
                "mode": mode, "T_pad": t_pad, "k_cap": k_cap,
                "n": len(win),
                "p50_ms": round(_pctl(win, 0.50) or 0.0, 4),
                "p99_ms": round(_pctl(win, 0.99) or 0.0, 4),
            })
        out.sort(key=lambda r: -r["n"])
        return out

    def phase_summary(self) -> Dict[str, Dict[str, dict]]:
        """Per-mode, per-phase p50/p99 milliseconds over the ring."""
        with self._lock:
            ring = list(self.samples)
        by_mode: Dict[str, Dict[str, list]] = {}
        for s in ring:
            m = by_mode.setdefault(s["mode"], {ph: [] for ph in PHASES})
            for ph in PHASES:
                m[ph].append(s.get(ph + "_ms", 0.0))
        out: Dict[str, Dict[str, dict]] = {}
        for mode, per in by_mode.items():
            out[mode] = {}
            for ph, vals in per.items():
                out[mode][ph] = {
                    "p50_ms": round(_pctl(vals, 0.50) or 0.0, 4),
                    "p99_ms": round(_pctl(vals, 0.99) or 0.0, 4),
                }
            totals = [s["total_ms"] for s in ring if s["mode"] == mode]
            out[mode]["step"] = {
                "n": len(totals),
                "p50_ms": round(_pctl(totals, 0.50) or 0.0, 4),
                "p99_ms": round(_pctl(totals, 0.99) or 0.0, 4),
            }
        return out

    def padding_waste(self) -> float:
        with self._lock:
            if self._padded <= 0:
                return 0.0
            return max(0.0, 1.0 - self._tokens / self._padded)

    def dry_summary(self) -> dict:
        """How often a launch found the chip with nothing queued, for
        how long (`lo_ms` <= the true total <= `hi_ms`), and the lower
        total by what the thread was doing (`dry_phase`) — the idle-gap
        table of a device trace, made without a capture."""
        with self._lock:
            d = self._dry
            return {"launches": d["launches"], "steps": d["steps"],
                    "lo_ms": round(d["lo_ms"], 4),
                    "hi_ms": round(d["hi_ms"], 4),
                    "by_phase_ms": {k: round(v, 4) for k, v
                                    in sorted(d["by_phase_ms"].items())}}

    def step_p99_ms(self) -> Optional[float]:
        with self._lock:
            totals = [s["total_ms"] for s in self.samples]
        return _pctl(totals, 0.99)

    def window(self, t0: float, t1: float) -> List[dict]:
        """Ring slice by wall-clock timestamp — links a /debug/profile
        capture window to the step samples taken during it."""
        with self._lock:
            return [s for s in self.samples if t0 <= s["ts"] <= t1]

    def tail(self, n: Optional[int] = None) -> List[dict]:
        with self._lock:
            out = list(self.samples)
        return out[-n:] if n else out

    def brief(self) -> Optional[dict]:
        """TUI chip payload: `compiles N · step p99 X ms`."""
        p99 = self.step_p99_ms()
        n = self.compile_count()
        if p99 is None and n == 0:
            return None
        out = {"compiles": n}
        if p99 is not None:
            out["p99_ms"] = round(p99, 3)
        return out

    def summary(self) -> dict:
        """The diagnostics bundle's `step_profile` section: per-mode
        phase p50/p99, compile count + rate, padding waste, overhead."""
        return {
            "samples": self.seq,
            "modes": self.phase_summary(),
            "compiles": self.compile_count(),
            "compile_rate_per_min": round(self.compile_rate_per_min(), 3),
            "padding_waste": round(self.padding_waste(), 4),
            "overhead_fraction": round(self.overhead_fraction(), 6),
            "dry": self.dry_summary(),
        }

    # -- the threads' CPU clocks, read only at a scrape ---------------------
    # The engine's loop thread(s) and the thread that runs the server's
    # event loop register themselves as they start and take themselves
    # off as they end; their CPU seconds are read when /metrics is
    # rendered, from another thread, through the per-thread clock id
    # taken at registration. With them: the engine thread's wall outside
    # `collect` and `loop_wait` minus its CPU seconds is the time it
    # wanted to run and did not (the GIL, the scheduler); the server
    # thread's CPU is the other term of both threads' Python a step.
    # Nothing here runs on the hot path.
    def cpu_register(self, role: str) -> None:
        """The calling thread is one of `role`'s (CPU_THREADS). A
        platform without per-thread CPU clocks registers nothing."""
        ident = threading.get_ident()
        try:
            clk = time.pthread_getcpuclockid(ident)
        except (AttributeError, OSError):
            return
        with self._lock:
            self._cpu_clocks.setdefault(role, {})[ident] = clk

    def cpu_unregister(self, role: str) -> None:
        """The calling thread ends: what it used stays in `role`'s
        total."""
        with self._lock:
            clk = self._cpu_clocks.get(role, {}).pop(
                threading.get_ident(), None)
            if clk is not None:
                self._cpu_ended[role] = (self._cpu_ended.get(role, 0.0)
                                         + _clock_gettime(clk))

    def cpu_seconds(self) -> Optional[Dict[str, float]]:
        """CPU seconds by role — every registered thread of it, live or
        ended, summed — and the whole process's under "process". None
        where the platform has no per-thread CPU clocks. Called when
        /metrics is rendered, and by nothing else."""
        if _clock_gettime is None \
                or not hasattr(time, "pthread_getcpuclockid"):
            return None
        with self._lock:
            out = dict(self._cpu_ended)
            for role, clocks in self._cpu_clocks.items():
                for clk in clocks.values():
                    try:
                        out[role] = out.get(role, 0.0) + _clock_gettime(clk)
                    except OSError:  # the thread ended without saying so
                        pass
        out["process"] = time.process_time()
        return out

    def snapshot(self, n: int = 128) -> dict:
        """/debug/stepprof payload."""
        with self._lock:
            compiles = list(self.compiles)
        return {
            "summary": self.summary(),
            "shapes": self.shape_table(),
            "recent": self.tail(n),
            "compile_events": compiles[-n:],
            "hbm_samples": len(self.hbm),
        }


# THE process-wide profiler (metrics.REGISTRY pattern): engine + fake
# write, server/TUI read, tests reset().
PROFILER = StepProfiler()
