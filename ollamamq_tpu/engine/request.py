"""Request objects and token streams.

A Request is the engine-side unit of work — the analogue of the reference's
`Task` (/root/reference/src/dispatcher.rs:33-40), but carrying tokenized
prompts and sampling params instead of opaque HTTP bodies. The TokenStream
replaces the 32-deep mpsc responder channel (dispatcher.rs:617): the engine
thread pushes items into a thread-safe queue; an optional callback lets the
asyncio server mirror items into its event loop without the engine knowing
about asyncio.
"""

from __future__ import annotations

import dataclasses
import enum
import queue
import threading
import time
from typing import Callable, List, Optional, Sequence

from ollamamq_tpu.ops.sampling import SamplingParams


class FinishReason(str, enum.Enum):
    STOP = "stop"          # EOS token or stop string
    LENGTH = "length"      # max_tokens or context budget hit
    CANCELLED = "cancelled"  # client disconnected / admin drop
    ERROR = "error"
    # Degradation-specific terminals: the client must be able to tell an
    # honest resource/deadline failure from a generic engine error, so
    # these surface as their own API done_reason (never folded into
    # "length" or a bare "error").
    KV_EXHAUSTED = "kv_exhausted"  # decode-time page-pool exhaustion
    DEADLINE = "deadline"          # per-request deadline expired


# Terminal reasons delivered to the client as an "error" stream item
# (with finish_reason carrying the specific done_reason).
ERROR_REASONS = (FinishReason.ERROR, FinishReason.KV_EXHAUSTED,
                 FinishReason.DEADLINE)


@dataclasses.dataclass
class StreamItem:
    kind: str  # "token" | "done" | "error"
    text: str = ""
    token_id: int = -1
    finish_reason: Optional[FinishReason] = None
    error: str = ""
    # Monotonic instant of TokenStream.push (0.0 = never pushed): the
    # stream writers observe now - pushed_at into ollamamq_stream_lag_ms
    # once the frame is written.
    pushed_at: float = 0.0


class TokenStream:
    """Thread-safe token channel, engine thread -> consumer.

    Backpressure: bounded queue (default 1024 items — generous vs the
    reference's 32 because items are single tokens, not HTTP chunks).
    `on_item` (if set) fires after each push, from the engine thread; the
    server uses it to wake the asyncio loop.
    """

    def __init__(self, maxsize: int = 1024):
        self._q: "queue.Queue[StreamItem]" = queue.Queue(maxsize=maxsize)
        self.on_item: Optional[Callable[[], None]] = None
        # Durability tap (durability/manager.py): observes every pushed
        # item — the WAL's emitted-token log and the resumable-stream
        # frame registry read here, WITHOUT consuming the queue (the
        # client stream stays the sole consumer). Fires even when the
        # queue overflows: the durable record must be complete.
        self.tap: Optional[Callable[[StreamItem], None]] = None
        # Consumer-not-draining threshold: the engine marks the request's
        # trace with a stream_stall span when the backlog crosses this
        # (latency attribution's "stream" phase) — well before the hard
        # overflow below turns it into a disconnect.
        self.high_water = max(1, maxsize // 2)
        self._closed = False
        # Set when the consumer stops reading and the queue fills: the engine
        # treats it as a client disconnect (the reference likewise interprets
        # a failed channel send as client-gone, dispatcher.rs:537-551). The
        # engine thread must NEVER block on a slow consumer.
        self.overflowed = False

    def push(self, item: StreamItem) -> None:
        if self._closed:
            return
        item.pushed_at = time.monotonic()
        tap = self.tap
        if tap is not None:
            try:
                tap(item)
            except Exception:  # noqa: BLE001 — a broken tap must never
                self.tap = None  # take the engine thread down with it
        try:
            self._q.put_nowait(item)
        except queue.Full:
            if item.kind in ("done", "error"):
                # Terminal items must reach the consumer: shed one token.
                try:
                    self._q.get_nowait()
                except queue.Empty:
                    pass
                try:
                    self._q.put_nowait(item)
                except queue.Full:
                    pass
                self._closed = True
            else:
                self.overflowed = True
            return
        if item.kind in ("done", "error"):
            self._closed = True
        cb = self.on_item
        if cb is not None:
            cb()

    def depth(self) -> int:
        return self._q.qsize()

    def get(self, timeout: Optional[float] = None) -> Optional[StreamItem]:
        try:
            return self._q.get(timeout=timeout)
        except queue.Empty:
            return None

    def get_nowait(self) -> Optional[StreamItem]:
        try:
            return self._q.get_nowait()
        except queue.Empty:
            return None

    def drain(self) -> List[StreamItem]:
        out = []
        while (item := self.get_nowait()) is not None:
            out.append(item)
        return out


@dataclasses.dataclass
class RequestStats:
    enqueued_at: float = dataclasses.field(default_factory=time.monotonic)
    prefill_started_at: float = 0.0
    first_token_at: float = 0.0
    finished_at: float = 0.0
    prompt_tokens: int = 0
    completion_tokens: int = 0

    @property
    def ttft_ms(self) -> float:
        if self.first_token_at:
            return (self.first_token_at - self.enqueued_at) * 1e3
        return 0.0

    @property
    def total_duration_s(self) -> float:
        end = self.finished_at or time.monotonic()
        return end - self.enqueued_at


class Request:
    """One generation (or embedding) request flowing through the engine."""

    def __init__(
        self,
        req_id: int,
        user: str,
        model: str,
        prompt_tokens: Sequence[int],
        sampling: Optional[SamplingParams] = None,
        kind: str = "generate",  # "generate" | "embed"
        raw_prompt: str = "",
    ):
        self.req_id = req_id
        self.user = user
        self.model = model
        self.prompt_tokens = list(prompt_tokens)
        self.sampling = sampling or SamplingParams()
        self.kind = kind
        self.raw_prompt = raw_prompt
        self.stream = TokenStream()
        self.stats = RequestStats(prompt_tokens=len(self.prompt_tokens))
        self.cancelled = threading.Event()
        # Per-request deadline (monotonic instant), from the sampling
        # params' deadline_ms budget (header or options). None = none.
        dm = float(getattr(self.sampling, "deadline_ms", 0.0) or 0.0)
        self.deadline = (self.stats.enqueued_at + dm / 1e3) if dm > 0 else None
        # Scheduler-accounting flag: True once mark_started ran for this
        # request — a preempted/retried requeue must not double-count it.
        self.started = False
        # Graceful-degradation state (engine-owned): preemption count
        # (anti-livelock budget), fault-retry count (poisoning budget),
        # earliest next retry attempt, and how many generated ids are
        # already folded into prompt_tokens for recompute replay.
        self.preemptions = 0
        self.retries = 0
        self._retry_at = 0.0
        self._replay_gen = 0
        # Incremental detokenizer: attached at first runtime submit and
        # PRESERVED across preemption/retry requeues — the replay prompt
        # carries already-generated ids, so the decoder must not re-see
        # them (stream continuity).
        self._inc_decode = None
        # Lifecycle trace (telemetry.tracing.Trace), attached by the
        # engine's enqueue path; None for directly-constructed Requests
        # (bench, unit tests) — every trace hook below no-ops then.
        self.trace = None
        # Stream-stall attribution state (engine-owned): True while the
        # consumer's backlog sits above the TokenStream high-water mark.
        self._stream_stalled = False
        # Generation state (engine-owned):
        self.generated_ids: List[int] = []
        self.emitted_len = 0  # chars of detok text already pushed
        self._detok_text = ""
        self.embedding: Optional[list] = None

    # -- stop-string handling ---------------------------------------------
    def emit_text(self, new_text: str) -> Optional[str]:
        """Accumulate detokenized text, honoring stop strings with hold-back.

        Returns the safe-to-emit chunk (may be ""), or None if a stop string
        fired (caller should finish the request with reason=STOP).
        """
        self._detok_text += new_text
        stops = self.sampling.stop
        if stops:
            for s in stops:
                idx = self._detok_text.find(s)
                if idx != -1:
                    chunk = self._detok_text[self.emitted_len:idx]
                    self.emitted_len = idx
                    if chunk:
                        self.stream.push(StreamItem("token", text=chunk))
                    return None
            holdback = max(len(s) for s in stops) - 1
        else:
            holdback = 0
        safe_end = len(self._detok_text) - holdback
        if safe_end > self.emitted_len:
            chunk = self._detok_text[self.emitted_len:safe_end]
            self.emitted_len = safe_end
            return chunk
        return ""

    def flush_text(self) -> str:
        """Emit any held-back text (at finish, when no stop matched)."""
        chunk = self._detok_text[self.emitted_len:]
        self.emitted_len = len(self._detok_text)
        return chunk

    @property
    def full_text(self) -> str:
        return self._detok_text[: self.emitted_len]

    def expired(self, now: Optional[float] = None) -> bool:
        """True when the request's deadline has passed."""
        if self.deadline is None:
            return False
        return (now if now is not None else time.monotonic()) >= self.deadline

    def trace_event(self, name: str, **args) -> None:
        """Record a lifecycle span event; no-op for untraced requests."""
        tr = self.trace
        if tr is not None:
            tr.event(name, **args)

    def finish(self, reason: FinishReason, error: str = "") -> None:
        self.stats.finished_at = time.monotonic()
        # The trace closes BEFORE the terminal item goes out: a client
        # that has read its last frame finds the request finished at
        # /debug/requests, not in flight for the engine thread's next
        # few microseconds.
        tr = self.trace
        if tr is not None:
            tr.finish(reason.value)
        kind = "error" if reason in ERROR_REASONS else "done"
        self.stream.push(StreamItem(kind, finish_reason=reason, error=error))
