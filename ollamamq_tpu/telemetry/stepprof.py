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
  * What jax did in that first call is on the event too: the engine
    module registers `jax.monitoring` listeners that hand jax's own
    compile events to `jax_begin` / `jax_event`, and they land on the
    innermost ACCOUNT open on the thread that fired them (`account()`),
    else on the process-wide `other`. A span's time is its SELF time —
    a jit traced inside a trace, an eager op compiled while lowering,
    is taken out of what encloses it — so an account's `trace_ms +
    lower_ms + backend_ms` never passes the wall it was open for.
  * Between process start and ready the main thread is in exactly one
    of the START_PHASES (`startup_begin` / `phase()` /
    `startup_enter` / `startup_ready`): their walls sum to `ready_s`
    as a request's phases sum to its latency. A phase opened on
    another thread, or after ready, is a row of its own and leaves
    `ready_s` alone.

Module-global `PROFILER` (same pattern as metrics.REGISTRY): the
engine and FakeRuntime feed it; the server and TUI read it;
tests call `PROFILER.reset()` for isolation.
"""

from __future__ import annotations

import contextlib
import os
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

# CLOSED vocabulary of what the process does between its start and
# ready, in order; pinned to the README start-up table (gate 8). The
# `phase` label values of ollamamq_startup_seconds.
START_PHASES = (
    "import",   # the kernel's process start -> cli.main entered: the
    #             interpreter and every import the entry point makes
    "backend",  # everything from there that no later phase names: the
    #             arguments, the compile cache's place, jax's first
    #             device touch, the engine's own construction
    "weights",  # weights.load_params: the seeded draw, or the read
    "place",    # shard_params, replicate_kv_heads, weights.place_formats
    "alloc",    # the pool, the rings, the slot state, `recent` /
    #             `last_ids`, the allocator, the prefix cache — and the
    #             rest of the runtime's constructor
    "serve",    # runtimes built -> the HTTP server starts to listen
)

# jax.monitoring's names for what a first call is made of (jax 0.9): the
# three spans (begun with a scalar of the same name, ended with a
# duration) and the persistent cache's two outcomes. The retrieval time
# jax reports on a hit lies INSIDE that program's backend span.
JAX_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_ms",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_ms",
    "/jax/core/compile/backend_compile_duration": "backend_ms",
}
JAX_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}
# cache= of ollamamq_compile_programs_total, and `cache` on a compile
# event: every program fetched / one compiled / jax reported neither (no
# cache directory, or a program under the cache's thresholds).
CACHE_OUTCOMES = ("hit", "miss", "off")
# What a compile event (and the journal's `compile` record) says beside
# site, key and wall_ms: the call's start, the split of its wall, the
# programs the backend was asked for and the cache's word on them.
COMPILE_SPLIT = ("t0", "trace_ms", "lower_ms", "backend_ms", "first_run_ms",
                 "programs", "cache")

_RING = 2048          # sample ring (like --journal-ring's default)
_SHAPE_KEYS = 64      # distinct (mode, T_pad, k_cap) keys kept
_SHAPE_WINDOW = 256   # rolling per-shape totals window
_COMPILE_RING = 256   # compile-event ring
_HBM_RING = 512       # HBM/allocator timeline ring
_START_RING = 64      # start-up rows of phases opened after ready
_RATE_WINDOW_S = 60.0  # compile-rate lookback
# thread= of ollamamq_thread_cpu_seconds_total (StepProfiler.cpu_register)
CPU_THREADS = ("engine", "server")
_clock_gettime = getattr(time, "clock_gettime", None)


def _pctl(window, q: float) -> Optional[float]:
    if not window:
        return None
    s = sorted(window)
    return s[min(len(s) - 1, int(q * len(s)))]


def _process_start() -> float:
    """The epoch second the kernel started this process (its start time
    in /proc, which counts clock ticks since boot, against the boot
    clock now: 10 ms fine); where the platform has neither, now — the
    import of this module."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        up_s = time.clock_gettime(time.CLOCK_BOOTTIME)
        return time.time() - (up_s - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError, AttributeError):
        return time.time()


class Account:
    """What jax did while this account was the innermost open on its
    thread: self milliseconds of tracing, of lowering and of the backend
    (the compile on a miss, the retrieval on a hit), the programs the
    backend built or fetched, and what the persistent cache said."""

    __slots__ = ("trace_ms", "lower_ms", "backend_ms", "programs",
                 "cache_hits", "cache_misses", "closed")

    def __init__(self):
        self.trace_ms = self.lower_ms = self.backend_ms = 0.0
        self.programs = self.cache_hits = self.cache_misses = 0
        self.closed = False  # a start-up phase's, once its chain ended

    def cache(self) -> str:
        """CACHE_OUTCOMES: "hit" where every program came from the
        persistent cache, "miss" where one was compiled, "off" where
        jax reported neither."""
        if not (self.cache_hits or self.cache_misses):
            return "off"
        return "hit" if (not self.cache_misses
                         and self.cache_hits >= self.programs) else "miss"

    def as_dict(self) -> dict:
        return {"trace_ms": round(self.trace_ms, 3),
                "lower_ms": round(self.lower_ms, 3),
                "backend_ms": round(self.backend_ms, 3),
                "programs": self.programs, "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}


class _PhaseRow:
    """One (phase, model) of the start-up ledger: when it first opened,
    the seconds it was open for, and its account."""

    __slots__ = ("phase", "model", "t0", "wall_s", "in_ready", "acct")

    def __init__(self, phase: str, model: str, t0: float, in_ready: bool):
        self.phase, self.model, self.t0 = phase, model, t0
        self.wall_s = 0.0
        self.in_ready = in_ready  # a link of the chain that sums to ready_s
        self.acct = Account()

    def as_dict(self) -> dict:
        return {"phase": self.phase, "model": self.model,
                "t0": round(self.t0, 6), "wall_s": round(self.wall_s, 6),
                "in_ready": self.in_ready, **self.acct.as_dict()}


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
        seen ready now. Returns the instant it was seen ready. Notes
        `collect_ready`: 1 where a probe had seen it ready before the
        read began — `resume("collect")`'s own at the latest: the host
        came late and the read waited for no step — else 0."""
        b, clock = self.done, self._clock
        self.fields["collect_ready"] = int(
            b is not None and b.seen_ready_at is not None)
        self.mark("collect")
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
        # The start-up ledger's clock: the epoch clock at construction,
        # advanced by the monotonic one (a stepped wall clock moves no
        # phase). `process_start` is the kernel's, on the same clock.
        self.process_start = _process_start()
        self._epoch0 = time.time() - time.monotonic()
        # Per thread: the open accounts (innermost last), jax's spans
        # begun and not ended, the cache's word on the program in hand.
        self._tls = threading.local()
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
        # Steps that were read back, and those whose ids a probe had seen
        # ready before the read began (the samples' `collect_ready`).
        self._collect = {"steps": 0, "ready": 0}
        self.compiles: deque = deque(maxlen=_COMPILE_RING)
        self.compile_seq = 0
        self._compile_ts: deque = deque(maxlen=_COMPILE_RING)
        self.hbm: deque = deque(maxlen=_HBM_RING)
        # Ledger events by `cache`, every program by it (the counter's
        # face), and what jax did under no account.
        self._compile_cache = dict.fromkeys(CACHE_OUTCOMES, 0)
        self._programs = dict.fromkeys(CACHE_OUTCOMES, 0)
        for row in getattr(self, "_su_rows", ()):
            row.acct.closed = True
        self.other = Account()
        # The start-up chain: its thread (None: not begun, or ended), the
        # instant its open phase began, the open rows (innermost last),
        # every row of it; then the rows of phases opened outside it.
        self._su_thread: Optional[int] = None
        self._su_last = 0.0
        self._su_stack: List[_PhaseRow] = []
        self._su_rows: List[_PhaseRow] = []
        self._su_later: deque = deque(maxlen=_START_RING)
        self.ready_at: Optional[float] = None

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
            ready = sample.get("collect_ready")
            if ready is not None:
                self._collect["steps"] += 1
                self._collect["ready"] += ready
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
                       account: Optional[Account] = None,
                       t0: Optional[float] = None) -> dict:
        """A step program's first call: `wall_ms` from its start (`t0`,
        epoch) to its return, and the `account` that was open around it —
        what of the wall was tracing, lowering and the backend; the rest
        is the program's first run."""
        n0 = time.perf_counter_ns()
        a = account if account is not None else Account()
        ts = time.time()
        ev = {
            "ts": ts,
            "t0": t0 if t0 is not None else ts - wall_ms / 1e3,
            "site": site,
            "key": str(key),
            "wall_ms": round(wall_ms, 3),
            "first_run_ms": round(max(
                0.0, wall_ms - a.trace_ms - a.lower_ms - a.backend_ms), 3),
            "cache": a.cache(),
        }
        sums = a.as_dict()
        ev.update((k, sums[k]) for k in COMPILE_SPLIT if k in sums)
        with self._lock:
            self.compile_seq += 1
            ev["seq"] = self.compile_seq
            self.compiles.append(ev)
            self._compile_ts.append(time.monotonic())
            self._compile_cache[ev["cache"]] += 1
        tm.COMPILE_TOTAL.labels(site=site).inc()
        tm.COMPILE_MS.observe(wall_ms)
        self._overhead_ns += time.perf_counter_ns() - n0
        return ev

    # -- accounts: what jax did, by who asked ------------------------------
    def _thread(self):
        """The calling thread's state: `accounts` (open, innermost last),
        `spans` (jax's, begun and not ended: [event, seconds nested in
        it]) and `outcome` (the cache's word on the program in hand)."""
        tls = self._tls
        if not hasattr(tls, "accounts"):
            tls.accounts, tls.spans, tls.outcome = [], [], None
        return tls

    @contextlib.contextmanager
    def account(self):
        """Open an account on the calling thread until the block ends:
        jax's events on this thread land on it, unless a block inside
        opens another."""
        acct, stack = Account(), self._thread().accounts
        stack.append(acct)
        try:
            yield acct
        finally:
            stack.remove(acct)

    def jax_begin(self, event: str) -> None:
        """jax began one of JAX_SPANS on the calling thread (its scalar
        listener's call): whatever ends before it does is nested in it."""
        if event in JAX_SPANS:
            spans = self._thread().spans
            if len(spans) >= 64:  # begun and never ended: start over
                del spans[:]
            spans.append([event, 0.0])

    def jax_event(self, event: str, seconds: Optional[float] = None) -> None:
        """One of jax's compile events on the calling thread (its duration
        and event listeners' call; others are ignored). A span is charged
        its SELF time — its seconds minus those of the spans that began
        and ended inside it — to the innermost open account of the
        thread, else to `other`; a backend span is one program, labelled
        by the cache's event that came inside it."""
        field = JAX_SPANS.get(event)
        outcome = JAX_CACHE_EVENTS.get(event)
        if field is None and outcome is None:
            return
        n0 = time.perf_counter_ns()
        tls = self._thread()
        stack = tls.accounts
        while stack and stack[-1].closed:
            stack.pop()
        with self._lock:  # (`other` is every thread's; events are rare)
            a = stack[-1] if stack else self.other
            if outcome == "hit":
                tls.outcome, a.cache_hits = outcome, a.cache_hits + 1
            elif outcome == "miss":
                tls.outcome, a.cache_misses = outcome, a.cache_misses + 1
            else:
                spans, inner = tls.spans, 0.0
                if any(sp[0] == event for sp in spans):
                    while spans[-1][0] != event:  # begun, never ended
                        spans.pop()
                    inner = spans.pop()[1]
                if spans:
                    spans[-1][1] += seconds
                ms = max(0.0, seconds - inner) * 1e3
                setattr(a, field, getattr(a, field) + ms)
                if field == "backend_ms":
                    a.programs += 1
                    label, tls.outcome = tls.outcome or "off", None
                    self._programs[label] += 1
                    tm.COMPILE_PROGRAMS_TOTAL.labels(cache=label).inc()
        self._overhead_ns += time.perf_counter_ns() - n0

    # -- start-up ledger ---------------------------------------------------
    def _now(self) -> float:
        return self._epoch0 + time.monotonic()

    def _su_switch(self, now: float, phase: Optional[str], model: str = "",
                   base: bool = False) -> None:
        """(the chain's thread, lock held) Close the open phase's slice
        at `now`; then open `phase` of `model` above it (`base`: in its
        place, and of everything open), or with `phase` None go back to
        the one below. The thread's innermost account is the open row's."""
        stack, accounts = self._su_stack, self._thread().accounts
        if stack:
            stack[-1].wall_s += now - self._su_last
            accounts.remove(stack[-1].acct)
        self._su_last = now
        if phase is None:
            if len(stack) > 1:  # (the base stays: a block that outlived it)
                stack.pop()
        else:
            if base:
                del stack[:]
            row = next((r for r in self._su_rows
                        if (r.phase, r.model) == (phase, model)), None)
            if row is None:
                row = _PhaseRow(phase, model, now, True)
                self._su_rows.append(row)
            stack.append(row)
        accounts.append(stack[-1].acct)

    def startup_enter(self, phase: str) -> None:
        """The main thread is in `phase` from now on, whatever was open.
        The FIRST call — cli.main's first statement — begins the chain on
        the calling thread: `import` ran from the kernel's process start
        to now."""
        if phase not in START_PHASES:
            raise ValueError(f"unknown start-up phase {phase!r}")
        now = self._now()
        with self._lock:
            if self._su_thread is None:
                if self._su_rows or self.ready_at is not None:
                    return  # the chain has ended
                self._su_thread = threading.get_ident()
                first = _PhaseRow("import", "", self.process_start, True)
                first.wall_s = now - self.process_start
                self._su_rows.append(first)
            elif self._su_thread != threading.get_ident():
                return
            self._su_switch(now, phase, base=True)

    @contextlib.contextmanager
    def phase(self, phase: str, model: str = ""):
        """`phase` (of START_PHASES) of `model` for the block. On the
        chain's thread while the chain is open: a link of it — the phase
        that was open resumes when the block ends. Anywhere else (a model
        loaded later, a rebuild, a runtime a test builds): a row of its
        own, `in_ready` false."""
        if phase not in START_PHASES:
            raise ValueError(f"unknown start-up phase {phase!r}")
        me = threading.get_ident()
        if self._su_thread != me:
            row = _PhaseRow(phase, model, self._now(), False)
            stack = self._thread().accounts
            stack.append(row.acct)
            try:
                yield row.acct
            finally:
                stack.remove(row.acct)
                row.wall_s = self._now() - row.t0
                with self._lock:
                    self._su_later.append(row)
            return
        with self._lock:
            self._su_switch(self._now(), phase, model)
            acct = self._su_stack[-1].acct
        try:
            yield acct
        finally:
            with self._lock:
                if self._su_thread == me:  # not ended from another thread
                    self._su_switch(self._now(), None)

    def startup_ready(self) -> None:
        """The HTTP server starts to listen (its start-up hook, whichever
        thread runs it): the chain ends here, `ready_s` is fixed and the
        gauges are set — once. Without a chain (no cli.main: an embedder,
        a test's server) nothing is ready that was ever begun."""
        now = self._now()
        with self._lock:
            if self._su_thread is None:
                return
            if self._su_stack:
                self._su_stack[-1].wall_s += now - self._su_last
            for row in self._su_rows:
                row.acct.closed = True
            del self._su_stack[:]
            self._su_thread = None
            self.ready_at = now
            walls = dict.fromkeys(START_PHASES, 0.0)
            for row in self._su_rows:
                walls[row.phase] += row.wall_s
        for ph, wall_s in walls.items():
            tm.STARTUP_SECONDS.labels(phase=ph).set(wall_s)
        tm.READY_SECONDS.set(now - self.process_start)

    def startup_snapshot(self) -> dict:
        """The `startup` block of /debug/stepprof and /metrics.json. The
        `in_ready` rows' `wall_s` sum to `ready_s` once ready (before, an
        open phase's running slice is not in its row yet)."""
        with self._lock:
            ready_at = self.ready_at
            return {
                "process_start": round(self.process_start, 6),
                "ready_at": None if ready_at is None else round(ready_at, 6),
                "ready_s": None if ready_at is None
                else round(ready_at - self.process_start, 6),
                "phases": [r.as_dict() for r in self._su_rows]
                + [r.as_dict() for r in self._su_later],
                "other": self.other.as_dict(),
                "programs": dict(self._programs),
            }

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

    def collect_summary(self) -> dict:
        """How many steps were read back, and how many of them had been
        seen ready before the read began (`collect_ready`): the host was
        late for those, and their `collect_ms` is the transfer's alone."""
        with self._lock:
            return dict(self._collect)

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
        """TUI chip payload: `compiles N (h hit / m miss) · step p99 X
        ms` — of the ledger's N first calls, those whose every program
        came out of the persistent cache and those that compiled one."""
        p99 = self.step_p99_ms()
        n = self.compile_count()
        if p99 is None and n == 0:
            return None
        with self._lock:
            out = {"compiles": n, "hit": self._compile_cache["hit"],
                   "miss": self._compile_cache["miss"]}
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
            "collect": self.collect_summary(),
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
            "startup": self.startup_snapshot(),
            "hbm_samples": len(self.hbm),
        }


# THE process-wide profiler (metrics.REGISTRY pattern): engine + fake
# write, server/TUI read, tests reset().
PROFILER = StepProfiler()
