"""Request objects and token streams.

A Request is the engine-side unit of work — the analogue of the reference's
`Task` (/root/reference/src/dispatcher.rs:33-40), but carrying tokenized
prompts and sampling params instead of opaque HTTP bodies. The TokenStream
replaces the 32-deep mpsc responder channel (dispatcher.rs:617): the engine
thread pushes items into a thread-safe queue; a consumer registers how it
is woken (`TokenStream.set_waker`), so the asyncio server hears of new items
without the engine knowing about asyncio.

The unit handed over is (step, stream): a "token" item carries ALL the
tokens one step gave the stream — one on a ragged step, k of a fused scan,
the accepted run of a speculated row — and a settled step wakes each
consumer thread ONCE for every stream it touched (`wake_batch`).
"""

from __future__ import annotations

import dataclasses
import enum
import queue
import threading
import time
from typing import Callable, List, Optional, Sequence

from ollamamq_tpu.ops.sampling import SamplingParams
from ollamamq_tpu.telemetry import schema as tm


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
    # A "token" item: the text to send now — the tokens' emitted chunks
    # joined (a chunk is "" while a stop string or a UTF-8 sequence holds
    # its bytes back; held-back text rides a later item, or a flush item
    # with no ids).
    text: str = ""
    # The sampled ids ONE step gave the stream, in order, and beside each
    # the chunk it emitted: `"".join(texts) == text`.
    token_ids: Sequence[int] = ()
    texts: Sequence[str] = ()
    finish_reason: Optional[FinishReason] = None
    error: str = ""
    # Monotonic instant of TokenStream.push (0.0 = never pushed): the
    # stream writers observe now - pushed_at into ollamamq_stream_lag_ms
    # once the frame is written.
    pushed_at: float = 0.0

    @classmethod
    def tokens(cls, ids: Sequence[int], texts: Sequence[str]) -> "StreamItem":
        return cls("token", text="".join(texts), token_ids=ids, texts=texts)

    def pairs(self) -> list:
        """One (id, text) a token, as the WAL and the resume registry
        keep them; text without an id rides id -1."""
        if self.token_ids:
            return [[int(t), x] for t, x in zip(self.token_ids, self.texts)]
        return [[-1, self.text]]


# Wake-ups owed by the pushes of this thread's open `wake_batch`.
_batch = threading.local()


class wake_batch:
    """While open, this thread's pushes do not wake their consumers one
    by one: at exit every waker met is called ONCE with the keys of all
    its streams that were pushed to. The engine opens one around a step's
    settle, so the server's loop gets one `call_soon_threadsafe` a step,
    not one a token. `wakeups` reads the calls made at exit."""

    def __enter__(self) -> "wake_batch":
        self.wakeups = 0
        self._outer = getattr(_batch, "owed", None)
        if self._outer is None:
            _batch.owed = {}
        return self

    def __exit__(self, *exc) -> None:
        if self._outer is not None:
            return  # nested: the outermost batch makes the calls
        owed, _batch.owed = _batch.owed, None
        for waker, keys in owed.items():
            self.wakeups += 1
            _wake(waker, list(keys))


def _wake(waker: Callable[[list], None], keys: list) -> None:
    tm.STREAM_WAKEUPS_TOTAL.inc()
    waker(keys)


class TokenStream:
    """Thread-safe token channel, engine thread -> consumer.

    Backpressure: bounded queue (default 1024 items — generous vs the
    reference's 32: an item is what one step gave the stream, not an HTTP
    chunk). A consumer that wants to be woken calls `set_waker(waker,
    key)`: after a push `waker([key, ...])` runs on the pushing thread —
    at once, or once for all the streams pushed to inside a `wake_batch`
    that share the waker (the server's: one per event loop).
    """

    def __init__(self, maxsize: int = 1024):
        self._q: "queue.Queue[StreamItem]" = queue.Queue(maxsize=maxsize)
        self._waker: Optional[Callable[[list], None]] = None
        self._wake_key = None
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
        waker = self._waker
        if waker is not None:
            owed = getattr(_batch, "owed", None)
            if owed is None:
                _wake(waker, [self._wake_key])
            else:  # a dict as an ordered set: a stream is woken once
                owed.setdefault(waker, {})[self._wake_key] = None

    def set_waker(self, waker: Optional[Callable[[list], None]],
                  key=None) -> None:
        """`waker(keys)` is called from the pushing thread after a push;
        `key` is whatever lets it find this stream's consumer. None
        unregisters."""
        self._wake_key = key
        self._waker = waker

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
        # (unit tests) — every trace hook below no-ops then.
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
    def emit_text(self, new_text: str) -> tuple:
        """Accumulate detokenized text, honoring stop strings with hold-back.

        Returns (chunk, stopped): the safe-to-emit chunk (may be "") and
        whether a stop string fired — the chunk is then the text before
        it, and the caller finishes the request with reason=STOP without
        flushing (the held-back rest holds the stop string).
        """
        self._detok_text += new_text
        stops = self.sampling.stop
        if stops:
            for s in stops:
                idx = self._detok_text.find(s)
                if idx != -1:
                    chunk = self._detok_text[self.emitted_len:idx]
                    self.emitted_len = idx
                    return chunk, True
            holdback = max(len(s) for s in stops) - 1
        else:
            holdback = 0
        safe_end = len(self._detok_text) - holdback
        if safe_end > self.emitted_len:
            chunk = self._detok_text[self.emitted_len:safe_end]
            self.emitted_len = safe_end
            return chunk, False
        return "", False

    def flush_text(self) -> str:
        """Emit any held-back text (at finish, when no stop matched)."""
        chunk = self._detok_text[self.emitted_len:]
        self.emitted_len = len(self._detok_text)
        return chunk

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
