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
chip, and the `step_profile` block bench.py embeds in every BENCH
record (what `scripts/bench_compare.py` diffs across rounds).

Dependency-free (stdlib only — no jax, no numpy) like the rest of
`telemetry/`, so scripts/check_metrics_docs.py can import the phase
vocabulary in CI and bench's error path can always attach a summary.

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
    have — one mechanism feeds samples, histograms and spans.
  * Compile events are recorded by the jit-getter seams exactly once
    per cache key (jax.jit traces+compiles synchronously on the first
    call of a fresh cache entry — timing that first call IS the compile
    wall); a recompile loop (ladder bug, pallas-probe thrash, injected
    `compile` fault) shows up as a climbing `rate_per_min` and trips
    the health monitor's `compile_storm` alert after warmup.

Module-global `PROFILER` (same pattern as metrics.REGISTRY): the
engine, FakeRuntime, and bench feed it; the server and TUI read it;
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
SPAN_NAMES = (tuple("mq." + p for p in PHASES)
              + tuple("mq.loop." + p for p in LOOP_PHASES))

# The phase a mark OPENS: marks name the phase that ended, a span needs
# its name when it begins, and the order is fixed.
_NEXT_PHASE = dict(zip(PHASES, PHASES[1:]))
# Every `<phase>_ms` field of a sample = every `phase` label value of
# ollamamq_step_phase_ms.
_SAMPLE_PHASES = PHASES + tuple("loop_" + p for p in LOOP_PHASES)
# Sample fields a span carries once the step has noted them.
_SPAN_FIELDS = ("T_pad", "k_cap", "tokens")

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


def _pctl(window, q: float) -> Optional[float]:
    if not window:
        return None
    s = sorted(window)
    return s[min(len(s) - 1, int(q * len(s)))]


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
    hole."""

    __slots__ = ("name", "_prof", "_last", "_owner", "_open", "_span",
                 "_loop", "_timers", "_seq", "_adopted")

    def __init__(self, prof: "StepProfiler", name: str):
        self._prof = prof
        self.name = name
        self._span = None
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

    def _close_span(self) -> None:
        span, self._span = self._span, None
        if span is not None:
            span.__exit__(None, None, None)

    def _switch(self, t: float, owner: Optional["StepTimer"], phase: str,
                charge: Optional[tuple] = None) -> None:
        """Close the open phase at `t` and open `phase` for `owner`. The
        closed slice goes to `charge` = (timer, phase) when a step mark
        names it, else to the phase it was opened under."""
        ms = (t - self._last) * 1e3
        self._last = t
        tgt, name = charge if charge is not None \
            else (self._owner, self._open)
        if tgt is None:
            self._loop[name] += ms
        else:
            tgt.phases[name] = tgt.phases.get(name, 0.0) + ms
        if self._span is not None:
            self._close_span()
        self._owner, self._open = owner, phase
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
            span = prof.span_factory("mq.loop." + phase, seq=seq)
        else:
            if owner.seq is None:
                owner.seq = prof._reserve_seq()
            span = prof.span_factory(
                "mq." + phase, seq=owner.seq, mode=owner.mode,
                **{k: v for k, v in owner.fields.items()
                   if k in _SPAN_FIELDS})
        span.__enter__()
        self._span = span

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
                 "_done", "parked")

    def __init__(self, prof: "StepProfiler", mode: str,
                 clock: Optional[LoopClock] = None):
        t = time.perf_counter()
        if clock is None:  # a step outside any engine loop (bench, tests)
            clock = LoopClock(prof, threading.current_thread().name)
            clock._last = t
        self._prof = prof
        self._clock = clock
        self.mode = mode
        self.phases: Dict[str, float] = {}
        self.fields: Dict[str, object] = {}
        self._done = False
        self.parked = False
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
            clock._switch(clock._last, None, "other")
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

    def _reserve_seq(self) -> int:
        with self._lock:
            self.seq += 1
            return self.seq

    def _record(self, sample: dict, total_ms: float,
                seq: Optional[int] = None) -> None:
        t0 = time.perf_counter_ns()
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
        self._overhead_ns += time.perf_counter_ns() - t0

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
        """The bench `step_profile` block / bundle section: per-mode
        phase p50/p99, compile count + rate, padding waste, overhead."""
        return {
            "samples": self.seq,
            "modes": self.phase_summary(),
            "compiles": self.compile_count(),
            "compile_rate_per_min": round(self.compile_rate_per_min(), 3),
            "padding_waste": round(self.padding_waste(), 4),
            "overhead_fraction": round(self.overhead_fraction(), 6),
        }

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


# THE process-wide profiler (metrics.REGISTRY pattern): engine + fake +
# bench write, server/TUI read, tests reset().
PROFILER = StepProfiler()
