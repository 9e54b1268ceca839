"""Deterministic fake engine for tests and API development.

The moral equivalent of the reference's test strategy of pointing the proxy
at real Ollama servers (SURVEY.md §4): an in-process engine with the same
interface as TPUEngine but no JAX — tokens are deterministic, latency is
configurable, cancellation works mid-stream. Lets the full HTTP surface be
conformance-tested without a TPU in the loop.
"""

from __future__ import annotations

import collections
import logging
import time
from typing import Dict, List, Optional

from ollamamq_tpu.config import EngineConfig, get_model_config
from ollamamq_tpu.engine.engine import TPUEngine
from ollamamq_tpu.engine.request import (FinishReason, Request, StreamItem,
                                         wake_batch)
from ollamamq_tpu.engine.tokenizer import ByteTokenizer
from ollamamq_tpu.telemetry import schema as tm
from ollamamq_tpu.telemetry import stepprof

log = logging.getLogger("ollamamq.fake")


class FakeRuntime:
    """Generates `word0 word1 ...` tokens, one per step, per active request."""

    slo = None  # attached by FakeEngine.load_model, like ModelRuntime
    fault_plan = None  # deterministic fault injection (testing/faults.py)
    on_preempt = None  # attached like ModelRuntime's (unused by fakes)
    journal = None  # decision journal, attached like ModelRuntime's
    # Scheduling policy (engine/scheduler.py), attached like the
    # journal — the deterministic seam the replay/simulate harness and
    # the policy tests drive without jax. None behaves exactly as fcfs.
    policy = None
    loop_clock = None  # the engine thread's stepprof.LoopClock

    def __init__(self, name: str, engine_cfg: EngineConfig,
                 token_latency_s: float = 0.0, is_encoder: bool = False):
        # Kind gate (engine._place): encoder fakes are embedding-only, like
        # EncoderRuntime; generative fakes also implement embed in step(),
        # so they truthfully serve both kinds.
        self.SERVES = ("embed",) if is_encoder else ("generate", "embed")
        self.name = name
        self.ecfg = engine_cfg
        self.token_latency_s = token_latency_s
        self.is_encoder = is_encoder
        self.tokenizer = ByteTokenizer()
        self.pending_prefill: collections.deque = collections.deque()
        self.active: List[Request] = []
        self.tokens_generated = 0
        self.step_latency_ms = 0.0
        self.prefill_latency_ms = 0.0
        self.param_bytes = 0
        self.kv_bytes = 0
        # Same metric surface as ModelRuntime, so the exposition (and the
        # e2e telemetry tests) look identical under the fake engine.
        self._tm_ttft = tm.TTFT_MS.labels(model=name)
        self._tm_tpot = tm.TPOT_MS.labels(model=name)
        self._tm_tokens = tm.TOKENS_GENERATED_TOTAL.labels(model=name)
        self._tm_occupancy = tm.BATCH_OCCUPANCY.labels(model=name)
        self._tm_mfu = tm.MFU.labels(model=name)
        self._tm_occupancy.set(0.0)
        self._tm_mfu.set(0.0)

    def has_capacity(self, kind=None) -> bool:
        return len(self.active) + len(self.pending_prefill) < self.ecfg.max_slots

    def has_work(self) -> bool:
        return bool(self.pending_prefill) or bool(self.active)

    def active_count(self) -> int:
        return len(self.active)

    def submit(self, req: Request) -> bool:
        self.pending_prefill.append(req)
        return True

    def _jrec(self, kind, req=None, **fields) -> None:
        # Same journaling seam as ModelRuntime: the fake engine's decision
        # stream is what the deterministic replay harness re-drives.
        if self.journal is not None:
            self.journal.record(kind, req=req, model=self.name, **fields)

    def _finish_served(self, req: Request, core, reason: FinishReason) -> None:
        """Served-to-completion finish: journal the outcome next to the
        scheduler's prediction and feed the output-length predictor —
        same contract as ModelRuntime._finish_slot."""
        core.mark_done(req.user, tokens=len(req.generated_ids))
        req.stats.completion_tokens = len(req.generated_ids)
        pol = self.policy
        extra = ({"predicted_tokens": pol.predict(req)}
                 if pol is not None else {})
        self._jrec("finish", req, reason=reason.value,
                   tokens=len(req.generated_ids), **extra)
        if pol is not None:
            pol.observe_finish(req, model=self.name)
        req.finish(reason)

    def _drop_cancelled(self, req: Request, core) -> None:
        self.active.remove(req)
        core.mark_dropped(req.user)
        self._jrec("finish", req, reason="cancelled",
                   tokens=len(req.generated_ids))
        req.finish(FinishReason.CANCELLED)

    def check_cancellations(self, core) -> None:
        for req in list(self.active):
            if req.cancelled.is_set():
                self._drop_cancelled(req, core)

    def step(self, core) -> None:
        # Fault seam: the fake analogue of ModelRuntime's dispatch hooks,
        # so shedding/retry/watchdog paths are testable without jax.
        if self.fault_plan is not None:
            self.fault_plan.check("step")
        # Step profiler, fake shape: admission is host_prep, the token-
        # latency sleep is the "device dispatch", the emit loop is detok
        # — so stepprof surfaces/tests run without jax. Idle ticks
        # abandon the timer (no zero-sample flood).
        _sp = stepprof.PROFILER.start("fake", self.loop_clock)
        _gen0 = self.tokens_generated
        # Admission: slot-bounded so scheduling-policy order actually
        # decides WHO enters a contended batch (pre-policy the pop gate
        # alone bounded concurrency, so this gate never binds for fcfs
        # traces — the decision stream is unchanged). Cancelled/expired
        # heads always drain regardless, and embeds hold no slot.
        # NOTE: core.mark_started already ran in TPUEngine._admit.
        if self.policy is not None:
            # Decision point (a): slot-admission order (fcfs: no-op).
            self.policy.reorder_pending(self.pending_prefill)
        admitted: List[Request] = []
        while self.pending_prefill:
            head = self.pending_prefill[0]
            if head._retry_at > time.monotonic():
                break  # head is backing off after a contained fault
            if (len(self.active) >= self.ecfg.max_slots
                    and not (self.is_encoder or head.kind == "embed")
                    and not head.cancelled.is_set()
                    and not head.expired()):
                break  # batch full: the policy order decides who's next
            req = self.pending_prefill.popleft()
            if req.cancelled.is_set():
                core.mark_dropped(req.user)
                self._jrec("finish", req, reason="cancelled", tokens=0)
                req.finish(FinishReason.CANCELLED)
                continue
            if req.expired():
                # Same deadline semantics as the real engine: expired
                # queued work drops before any "compute" is spent.
                from ollamamq_tpu.engine.engine import drop_expired

                drop_expired(req, core, self.name, journal=self.journal)
                continue
            if self.is_encoder or req.kind == "embed":
                req.trace_event("embed_batch", tokens=len(req.prompt_tokens))
                req.embedding = self._fake_embedding(req)
                req.stats.first_token_at = time.monotonic()
                core.mark_done(req.user, tokens=len(req.prompt_tokens))
                self._jrec("finish", req, reason="stop",
                           tokens=len(req.prompt_tokens))
                req.finish(FinishReason.STOP)
            else:
                req.trace_event("prefill", tokens=len(req.prompt_tokens))
                # Resume-aware: a retried request (engine containment
                # path) continues its word stream where it stopped rather
                # than restarting at word0 — mirrors the real engine's
                # replay-recompute continuity.
                done = len(req.generated_ids)
                req._fake_remaining = max(
                    1, min(req.sampling.max_tokens, 16) - done)
                req._fake_idx = done
                self._jrec("install", req, slot=-1,
                           n_prompt=len(req.prompt_tokens))
                self.active.append(req)
                admitted.append(req)
        real = sum(len(r.prompt_tokens) for r in admitted)
        if admitted:
            # Batch-compose record, fake shape: no padding (tokens are
            # words, not tensors), so real == padded — keeps the replay
            # harness's batch_stats/occupancy output meaningful.
            self._jrec("batch", slots=[-1] * len(admitted),
                       reqs=[r.req_id for r in admitted],
                       batch_size=len(admitted), tokens=real,
                       occupancy=round(len(self.active)
                                       / max(1, self.ecfg.max_slots), 4),
                       pending=len(self.pending_prefill),
                       mode="fake", padded_tokens=real)
        self._tm_occupancy.set(len(self.active) / max(1, self.ecfg.max_slots))
        _had_work = bool(admitted or self.active)
        _n_decode = len(self.active)
        _sp.note(T_pad=0, k_cap=0, tokens=real)
        _sp.mark("host_prep")
        # The fake's device is its sleep: the step is launched here and
        # has left the "chip" when the sleep is over — the done-bracket a
        # real step gets from its ids' is_ready(), from the fake's own
        # notion of a step's end. Like an n-gram --spec runtime it launches
        # nothing before it has read the step ahead: dry every step.
        t_end = time.perf_counter() + self.token_latency_s
        _sp.launched(lambda: time.perf_counter() >= t_end, model=self.name)
        if self.token_latency_s:
            time.sleep(self.token_latency_s)
        _sp.mark("dispatch")
        _sp.collected()  # nothing to wait for; keeps the fixed order
        n_items = 0
        with wake_batch() as woken:  # one wake-up a step, as the real one
            for req in list(self.active):
                n_items += self._emit_step(req, core)
        if _had_work:
            _sp.mark("detok")
            _sp.finish(n_prefill=len(admitted), n_decode=_n_decode,
                       stream_items=n_items, stream_wakeups=woken.wakeups,
                       tokens=real + (self.tokens_generated - _gen0),
                       padded_tokens=real + (self.tokens_generated - _gen0),
                       compiled=False)

    def _emit_step(self, req: Request, core) -> int:
        """One step's words for one request, handed to its stream as ONE
        item (1 word, or 1 + k with --spec). Returns the items pushed."""
        if req.cancelled.is_set():
            self._drop_cancelled(req, core)
            return 0
        # Speculative fake: with --spec the step emits 1 + k words at
        # once and journals the speculate/spec_verify decision pair —
        # the fake word stream is deterministic regardless of
        # stepping, so spec-on/off streams stay identical while the
        # journal vocabulary (and its invariants, /debug surfaces,
        # replay harness) exercise without jax. Fake drafts always
        # verify: the "model" IS the proposer here.
        emit_n = 1
        if (self.ecfg.spec and self.ecfg.spec_k > 0
                and req._fake_remaining > 1):
            k = min(self.ecfg.spec_k, req._fake_remaining - 1)
            self._jrec("speculate", req, slot=-1, k=k, source="fake")
            self._jrec("spec_verify", req, slot=-1, proposed=k,
                       accepted=k, rolled_back=0)
            tm.SPEC_TOKENS_TOTAL.labels(
                model=self.name, outcome="proposed",
                proposer="fake").inc(k)
            tm.SPEC_TOKENS_TOTAL.labels(
                model=self.name, outcome="accepted",
                proposer="fake").inc(k)
            tm.SPEC_ACCEPT_RATE.labels(model=self.name).set(1.0)
            emit_n = 1 + k
        ids, texts, end, tail = [], [], None, ""
        for _ in range(emit_n):
            word = f"word{req._fake_idx} "
            req._fake_idx += 1
            req._fake_remaining -= 1
            req.generated_ids.append(req._fake_idx)
            self.tokens_generated += 1
            self._tm_tokens.inc()
            if not req.stats.first_token_at:
                req.stats.first_token_at = time.monotonic()
                self._tm_ttft.observe(req.stats.ttft_ms)
                self._tm_tpot.observe(self.token_latency_s * 1e3)
                if self.slo is not None:
                    self.slo.record("ttft", req.stats.ttft_ms)
                req.trace_event("first_token",
                                ttft_ms=round(req.stats.ttft_ms, 3))
            elif self.slo is not None:
                self.slo.record("tpot", self.token_latency_s * 1e3)
            chunk, stopped = req.emit_text(word)
            if stopped:
                tail, end = chunk, FinishReason.STOP
                break
            if chunk:
                ids.append(req._fake_idx)
                texts.append(chunk)
            if req._fake_remaining <= 0:
                tail, end = req.flush_text(), FinishReason.LENGTH
                break
        if ids:
            req.stream.push(StreamItem.tokens(ids, texts))
        if tail:
            req.stream.push(StreamItem("token", text=tail))
        if end is not None:
            self.active.remove(req)
            self._finish_served(req, core, end)
        return bool(ids) + bool(tail)

    # -- KV page migration (fake shape: no pages, just the word cursor) ----
    def export_request(self, rid: int):
        """Same export contract as ModelRuntime, fake state: the word
        cursor IS the KV. Lets fleet drain/failover exercise the full
        two-phase migration path without jax."""
        from ollamamq_tpu.engine.engine import request_migration_state

        for req in self.active:
            if req.req_id == rid:
                break
        else:
            return None
        blob = {
            "version": 1, "kind": "fake", "model": self.name,
            "fake_idx": int(req._fake_idx),
            "fake_remaining": int(req._fake_remaining),
            "request": request_migration_state(req),
            "_inc_decode": req._inc_decode,
        }
        self.active.remove(req)
        return {"req": req}, blob

    def release_export(self, handle: dict) -> None:
        pass  # fakes hold no pages to free

    def import_request(self, blob: dict, req: Request) -> bool:
        if blob.get("kind") != "fake" \
                or len(self.active) >= self.ecfg.max_slots:
            return False
        req._fake_idx = int(blob["fake_idx"])
        req._fake_remaining = int(blob["fake_remaining"])
        self._jrec("install", req, slot=-1,
                   n_prompt=len(req.prompt_tokens))
        self.active.append(req)
        return True

    def _fake_embedding(self, req: Request) -> list:
        # Deterministic unit vector derived from the prompt bytes.
        dim = 64
        v = [0.0] * dim
        for i, t in enumerate(req.prompt_tokens):
            v[i % dim] += float((t % 13) + 1)
        norm = sum(x * x for x in v) ** 0.5 or 1.0
        return [x / norm for x in v]

    def stats(self) -> dict:
        return {
            "model": self.name,
            "active_slots": len(self.active),
            "max_slots": self.ecfg.max_slots,
            "pending_prefill": len(self.pending_prefill),
            "pages_used": 0,
            "pages_total": 0,
            "step_latency_ms": round(self.token_latency_s * 1e3, 3),
            "prefill_latency_ms": 0.0,
            "tokens_generated": self.tokens_generated,
            "preemptions": 0,  # fakes hold no KV pages to run out of
            "retries": 0,
            "stalled_slots": 0,
            "mfu": 0.0,
            "param_bytes": self.param_bytes,
            "kv_bytes": self.kv_bytes,
            "prefix_cache": None,  # fake tokens carry no KV to share
            "weights_dtype": "bfloat16",  # fake engine holds no weights
            "kv_dtype": "bfloat16",  # ...and no KV pool
            "spec": None,  # fake drafts never roll back
        }


class FakeEngine(TPUEngine):
    """TPUEngine with FakeRuntimes — identical scheduler/admission path."""

    def __init__(self, engine_cfg: Optional[EngineConfig] = None,
                 models: Optional[Dict[str, Optional[str]]] = None,
                 blocklist_path: Optional[str] = None,
                 token_latency_s: float = 0.0, **kw):
        self.token_latency_s = token_latency_s
        engine_cfg = engine_cfg or EngineConfig(model="test-tiny")
        super().__init__(engine_cfg, models=models,
                         blocklist_path=blocklist_path, mesh=None, **kw)

    def load_model(self, name: str, checkpoint_path: Optional[str] = None) -> None:
        if name in self.runtimes:
            return
        cfg = get_model_config(name)
        is_enc = bool(cfg and cfg.is_encoder)
        rt = FakeRuntime(
            name, self.ecfg, token_latency_s=self.token_latency_s, is_encoder=is_enc
        )
        rt.slo = self.slo
        rt.fault_plan = self.fault_plan
        rt.journal = self.journal
        rt.policy = self.policy
        rt.loop_clock = self.loop_clock
        self.runtimes[name] = rt
        self.notify()

    def _loop_once(self) -> None:
        # Same loop-phase marks as TPUEngine._loop_once (stepprof
        # LOOP_PHASES), so the gapless chain is testable without jax.
        clock = self.loop_clock
        clock.tick()
        self.last_tick_at = time.monotonic()
        self.journal.tick += 1
        # Deferred engine-thread calls (the fleet's migration
        # export/import run through call_on_loop here too).
        self._drain_engine_calls()
        clock.enter("admit")
        self._admit()
        clock.enter("other")
        did_work = False
        for rt in list(self.runtimes.values()):
            rt.check_cancellations(self.core)
            if rt.has_work():
                try:
                    rt.step(self.core)
                except Exception:
                    # Same containment contract as the real engine:
                    # retry-or-poison the implicated requests, keep
                    # the loop (and the fake runtime) alive.
                    log.exception("fake runtime %s step failed", rt.name)
                    self._fail_runtime(rt, "engine step failed")
                did_work = True
        if not did_work:
            clock.enter("wait")
            with self._cond:
                self._cond.wait(timeout=0.02)
            clock.enter("other")
