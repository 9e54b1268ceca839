"""TPU continuous-batching engine.

This module replaces the reference's entire backend layer: where the Rust
dispatcher forwarded one request per Ollama backend over HTTP
(/root/reference/src/dispatcher.rs:496-575) and gated parallelism at
`active_requests < 1` per backend (dispatcher.rs:438), here many requests
share one forward step on the TPU:

  - admission: the engine loop pops requests from the native fair-share
    core (cpp/mqcore.cpp) whenever a model runtime has slot+page capacity —
    the queue-side policy is identical to the reference, but what's being
    scheduled is a seat in the decode batch, not a backend slot.
  - prefill: a prompt rides the ragged step as a span of tokens — as many
    as the token budget leaves room for, tick by tick — beside every live
    decode row; the step that holds a prompt's last token samples its
    first output token (TTFT path).
  - decode: when no prompt is waiting mid-span the engine runs K decode
    steps inside a lax.scan to amortize host dispatch (critical:
    per-dispatch latency to the chip dominates otherwise).
  - cancellation: client disconnects free the slot and its KV pages
    immediately (reference analogue: dispatcher.rs:537-551 drops the stream
    and frees the backend; here the reclaimed resource is HBM pages).

All step functions are shape-static (fixed slot count, a ladder of padded
token totals, donated caches) => each (total, K) compiles exactly once.
"""

from __future__ import annotations

import collections
import copy
import itertools
import logging
import os
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ollamamq_tpu.config import (ATTENTION, EngineConfig, ModelConfig,
                                 get_model_config, smart_match,
                                 validate_quant_config)
from ollamamq_tpu.core import MQCore, Fairness, Family
from ollamamq_tpu.core.mqcore import BlockedError, StuckQueue
from ollamamq_tpu.engine import kv_cache as kvc
from ollamamq_tpu.engine import step_program, step_work
from ollamamq_tpu.engine.request import (FinishReason, Request, StreamItem,
                                         wake_batch)
from ollamamq_tpu.engine.scheduler import make_policy
from ollamamq_tpu.engine.tokenizer import load_tokenizer
from ollamamq_tpu.models import llama, weights
from ollamamq_tpu.ops.sampling import sampling_flags
from ollamamq_tpu.parallel.mesh import (make_mesh, replica_submesh,
                                        validate_tp_for_model)
from ollamamq_tpu.parallel.sharding import kv_cache_spec, shard_params
from ollamamq_tpu.telemetry import mfu as mfu_model
from ollamamq_tpu.telemetry import schema as tm
from ollamamq_tpu.telemetry import stepprof
from ollamamq_tpu.telemetry.journal import Journal
from ollamamq_tpu.telemetry.slo import AlertManager, SLOEngine
from ollamamq_tpu.telemetry.tracing import DECODE_EVENT_EVERY, Tracer

log = logging.getLogger("ollamamq.engine")

# The step profiler stays stdlib-only; this module imports jax, so it
# hands over the span type the profiler opens while a capture runs.
stepprof.PROFILER.span_factory = jax.profiler.TraceAnnotation
# ...and what jax says of its own compiles, on the thread that compiles
# (stepprof.JAX_SPANS begin with a scalar and end with a duration; the
# persistent cache's hit or miss is an event): once a process.
jax.monitoring.register_scalar_listener(
    lambda event, value, **kw: stepprof.PROFILER.jax_begin(event))
jax.monitoring.register_event_duration_secs_listener(
    lambda event, secs, **kw: stepprof.PROFILER.jax_event(event, secs))
jax.monitoring.register_event_listener(
    lambda event, **kw: stepprof.PROFILER.jax_event(event))
_ENGINE_IDS = itertools.count(1)


def sweep_blocked(core: MQCore, held_fn, last_version: int) -> int:
    """Cancel held requests of blocked users; returns the blocklist version
    the sweep ran against. No-op (zero FFI calls beyond the version read)
    unless the blocklist changed since `last_version` — blocks are rare,
    ticks are not. Starting runtimes at version -1 makes the first tick
    sweep once, covering blocklist entries loaded from disk at startup."""
    ver = core.block_version()
    if ver == last_version:
        return ver
    held = held_fn()
    users = {r.user for r in held if not r.cancelled.is_set()}
    blocked = {u for u in users if core.is_user_or_ip_blocked(u)}
    for req in held:
        if req.user in blocked:
            req.cancelled.set()
    return ver


def drop_expired(req: Request, core: MQCore, model: str,
                 journal=None) -> None:
    """Finish an expired request with the explicit deadline reason and
    count the shed — expired queued work is dropped without burning a
    single TPU cycle on it, and the client learns WHY. The journal
    record carries the slack (how long past the deadline the drop
    happened), the input that justifies the decision."""
    core.mark_dropped(req.user, started=getattr(req, "started", True))
    tm.DEADLINE_DROPS_TOTAL.labels(model=model or "?").inc()
    tm.SHED_TOTAL.labels(reason="deadline").inc()
    if journal is not None:
        slack = ((time.monotonic() - req.deadline) * 1e3
                 if req.deadline is not None else 0.0)
        journal.record("deadline_drop", req=req, model=model or None,
                       slack_ms=round(slack, 3))
    req.finish(FinishReason.DEADLINE,
               error="deadline expired before completion")


def ragged_budget(engine_cfg: EngineConfig) -> int:
    """Tokens of the longest ragged stream a runtime launches: the token
    budget — or a full decode batch (one token a slot) plus one granule of
    prefill, which must always fit one dispatch — in whole granules."""
    g = max(1, engine_cfg.token_granule)
    return -(-max(engine_cfg.max_batch_tokens,
                  engine_cfg.max_slots + g) // g) * g


def select_attn_impl(backend: str, kv_dtype: str) -> Tuple[str, str]:
    """(attention implementation, reason) for a runtime on `backend`
    holding `kv_dtype` pages: the Pallas kernels on a TPU, the jnp
    reference everywhere else. OLLAMAMQ_NO_PALLAS=1 is the reference
    switch (A/B runs, chip_smoke's comparison leg). Int8 pools take the
    jnp path: Mosaic refuses the kernels' [page_size, Hk] f32 scale-row
    DMA (slice not aligned to the 128-lane tiling; ROADMAP A1)."""
    if os.environ.get("OLLAMAMQ_NO_PALLAS", "").lower() not in (
            "", "0", "false", "no"):
        return "jnp", "OLLAMAMQ_NO_PALLAS is set"
    if backend != "tpu":
        return "jnp", f"backend is {backend}, not tpu"
    if kv_dtype == "int8":
        return "jnp", "int8 KV pages: the kernels' scale-row DMA does " \
                      "not compile for TPU"
    return "pallas", "tpu backend"


def per_chip_stats() -> List[dict]:
    """One row per LOCAL device: id, kind, HBM in use / limit. The TUI
    chips panel and /metrics render these per chip (a v5e-16 must not
    show chip 0's counters for the whole pod). Remote hosts' chips are
    merged in by the SPMD stats path (engine/spmd.py publishes them on
    the KV store alongside the heartbeat)."""
    out = []
    try:
        for d in jax.local_devices():
            # memory_stats=False marks a backend that doesn't report HBM
            # (CPU): /metrics omits the series and the TUI renders "n/a"
            # instead of a fake 0-byte reading.
            row = {"device": str(d), "id": int(d.id),
                   "process": int(getattr(d, "process_index", 0)),
                   "hbm_used": 0, "hbm_total": 0, "memory_stats": False}
            try:
                ms = d.memory_stats()
                if ms:
                    row["hbm_used"] = int(ms.get("bytes_in_use", 0))
                    row["hbm_total"] = int(ms.get("bytes_limit", 0) or 0)
                    row["memory_stats"] = True
            except Exception:
                pass
            out.append(row)
    except Exception:
        pass
    return out


def device_summary() -> dict:
    """What this process's jax runs on, as jax reports it — the fields
    every status payload and benchmark line names, so a number can never
    be read without the device it came from."""
    devs = jax.devices()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "device_count": len(devs),
            "devices": [str(d) for d in devs]}


class QueueFullError(Exception):
    """Bounded admission refused an enqueue: the queue (global or this
    user's) is at its --max-queued / --max-queued-per-user cap. Carries
    the Retry-After estimate (seconds) derived from the observed
    completion rate, so the HTTP layer can answer 503/429 honestly
    instead of growing the queue unboundedly."""

    def __init__(self, scope: str, retry_after_s: float, limit: int):
        self.scope = scope  # "queue_full" | "user_queue_full"
        self.retry_after_s = retry_after_s
        self.limit = limit
        super().__init__(
            f"{scope.replace('_', ' ')}: admission cap {limit} reached; "
            f"retry after ~{retry_after_s:.0f}s")


def _prefix_cache(rt):
    """A runtime's radix tree; None where it holds or shares no pages."""
    return getattr(getattr(rt, "cache", None), "prefix_cache", None)


class MigrationError(RuntimeError):
    """A KV migration import could not land (no slot / no pages / shape
    mismatch / malformed blob). The caller falls back to recompute
    replay — a failed transfer degrades, it never drops."""


def request_migration_state(req: Request) -> dict:
    """Everything a Request carries that the TARGET member of a KV
    migration needs to continue the stream seamlessly: token history,
    detokenizer text + emitted watermark (stop-string holdback included),
    degradation budgets, and the sampling params verbatim."""
    s = req.sampling
    return {
        "user": req.user, "model": req.model, "kind": req.kind,
        "raw_prompt": req.raw_prompt,
        "prompt_tokens": [int(t) for t in req.prompt_tokens],
        "generated_ids": [int(t) for t in req.generated_ids],
        "replay_gen": int(req._replay_gen),
        "emitted_len": int(req.emitted_len),
        "detok_text": req._detok_text,
        "preemptions": int(req.preemptions),
        "retries": int(req.retries),
        "sampling": {
            "temperature": s.temperature, "top_k": s.top_k,
            "top_p": s.top_p, "repeat_penalty": s.repeat_penalty,
            "presence_penalty": s.presence_penalty,
            "frequency_penalty": s.frequency_penalty,
            "seed": s.seed, "max_tokens": s.max_tokens,
            "stop": list(s.stop), "deadline_ms": s.deadline_ms,
        },
    }


def request_from_migration_state(rid: int, state: dict) -> Request:
    """Rebuild a migrated Request. Sampling fields are set RAW (seed was
    already folded into its seeded form on the source — running
    __post_init__ again would re-fold it and fork the sampled stream)."""
    from ollamamq_tpu.ops.sampling import SamplingParams

    sp = SamplingParams()
    for key, val in (state.get("sampling") or {}).items():
        setattr(sp, key, val)
    sp.stop = tuple(sp.stop or ())
    req = Request(rid, state["user"], state.get("model", ""),
                  [int(t) for t in state.get("prompt_tokens", ())], sp,
                  kind=state.get("kind", "generate"),
                  raw_prompt=state.get("raw_prompt", ""))
    req.generated_ids = [int(t) for t in state.get("generated_ids", ())]
    req._replay_gen = int(state.get("replay_gen", 0))
    req.emitted_len = int(state.get("emitted_len", 0))
    req._detok_text = state.get("detok_text", "")
    req.preemptions = int(state.get("preemptions", 0))
    req.retries = int(state.get("retries", 0))
    return req


class WorkerDesyncError(RuntimeError):
    """An SPMD status sync reported a worker-host replay failure: device
    state diverged across hosts. Unlike a local batch failure this must
    NEVER be absorbed by a fail-only-this-batch handler — the runtime has
    to be killed and reloaded on every host (engine/spmd.py raises it)."""


class PeerDeadError(WorkerDesyncError):
    """A peer host's heartbeat went stale mid-sync: the host is presumed
    dead (process kill, host loss), so the barrier would only time out —
    fail the in-flight work loudly NOW instead of waiting it out
    (reference detects a dead backend in ~10s, dispatcher.rs:385)."""


def _sp_compile_evict(rt, cache, key_) -> bool:
    """faults.py "compile" site: a fired rule evicts the jit cache entry
    before the lookup, so the next fill re-traces — the injected
    recompile loop the compile_storm health alert is tested against.
    Observer-style (draw): the eviction IS the enacted fault. True where
    it fired: a step program's builder then hands out a fresh jit too."""
    fp = getattr(rt, "fault_plan", None)
    if fp is not None and key_ in cache and fp.draw("compile"):
        cache.pop(key_, None)
        return True
    return False


def _sp_note_compile(rt, site: str, key_, cache, fn):
    """Wrap a freshly cached jit so its FIRST call — the one jax traces,
    lowers, compiles (or fetches from the persistent cache) and runs
    synchronously — is timed and recorded exactly once per cache key,
    under an account that says what of its wall was which (stepprof.
    Account): journal `compile` record, ollamamq_compile_total/
    _compile_ms, the stepprof compile ledger, and the in-flight step's
    `compiled` flag. The wrapper then replaces itself with the raw jit,
    so steady state pays nothing."""
    def first_call(*a, **kw):
        t0, began = time.monotonic(), time.time()
        # Read by TPUEngine.compiling(); cleared with the `compiled` flag
        # when the step that paid this compile takes its sample — the
        # first execution of a fresh program is slow too.
        rt.compiling_since = t0
        try:
            with stepprof.PROFILER.account() as acct:
                out = fn(*a, **kw)
        except BaseException:
            rt.compiling_since = None
            raise
        wall_ms = (time.monotonic() - t0) * 1e3
        cache[key_] = fn
        rt._stepprof_compiled = True
        ev = stepprof.PROFILER.record_compile(site, key_, wall_ms, acct,
                                              t0=began)
        j = getattr(rt, "journal", None)
        if j is not None:
            j.record("compile", model=rt.name, **{
                k: ev[k] for k in ("site", "key", "wall_ms")
                + stepprof.COMPILE_SPLIT})
        return out

    cache[key_] = first_call
    return first_call


def _sp_take_compiled(rt) -> bool:
    """Read-and-clear the per-step compiled flag for the sample."""
    c = getattr(rt, "_stepprof_compiled", False)
    rt._stepprof_compiled = False
    rt.compiling_since = None
    return c


def serve_embed_batch(rt, core: "MQCore", pending, max_len: int,
                      dispatch, max_batch: int = 8) -> bool:
    """Pop up to `max_batch` ready embed requests, pad to a power-of-2
    bucket, run ONE stateless forward, finish each request. The single
    batching scheme for both embedding paths (EncoderRuntime.step and
    ModelRuntime.step_embed) so they cannot drift. Returns True if ran.

    On a dispatch failure the batch's requests are errored BEFORE the
    exception propagates — a popped request must never be left hanging
    (it is in no queue _fail_runtime can see)."""
    _sp = stepprof.PROFILER.start("embed", getattr(rt, "loop_clock", None))
    journal = getattr(rt, "journal", None)

    def jfinish(req: Request, reason: str) -> None:
        if journal is not None:
            journal.record("finish", req=req, model=rt.name, reason=reason,
                           tokens=len(req.prompt_tokens))

    batch: List[Request] = []
    while pending and len(batch) < max_batch:
        if pending[0]._retry_at > time.monotonic():
            break  # head is backing off after a contained fault
        req = pending.popleft()
        if req.cancelled.is_set():
            core.mark_dropped(req.user)
            jfinish(req, "cancelled")
            req.finish(FinishReason.CANCELLED)
            continue
        if req.expired():
            # Expired queued embeds are dropped before the batch forward.
            drop_expired(req, core, rt.name, journal=journal)
            continue
        n = len(req.prompt_tokens)
        if n > max_len:
            # Reject per-request: a failed batch forward errors every
            # pending request of this runtime (cross-user blast radius,
            # ADVICE r1).
            core.mark_dropped(req.user)
            jfinish(req, "error")
            req.finish(FinishReason.ERROR,
                       error=f"input length {n} exceeds maximum {max_len}")
            continue
        batch.append(req)
    if not batch:
        return False
    for r in batch:
        r.trace_event("embed_batch", tokens=len(r.prompt_tokens))
    longest = max(len(r.prompt_tokens) for r in batch)
    bucket = 32
    while bucket < longest:
        bucket *= 2
    # Two batch buckets per length bucket (like prefill): B=1 so a lone
    # request doesn't pay max_batch x compute, B=max_batch for bursts.
    B = 1 if len(batch) == 1 else max_batch
    tokens = np.zeros((B, bucket), np.int32)
    lens = np.zeros((B,), np.int32)
    for i, r in enumerate(batch):
        tokens[i, : len(r.prompt_tokens)] = r.prompt_tokens
        lens[i] = len(r.prompt_tokens)
    _sp.note(T_pad=int(bucket), k_cap=0, tokens=int(lens.sum()))
    _sp.mark("host_prep")
    t0 = time.monotonic()
    try:
        out_dev = dispatch(B, bucket, tokens, lens)
        _sp.mark("dispatch")
        out = np.asarray(out_dev)
        _sp.mark("collect")
    except Exception as e:
        # Retry-or-poison each implicated request where the runtime
        # offers the seam (generative ModelRuntime keeps serving after an
        # embed failure); encoders error the batch as before — the
        # exception still propagates so the caller decides runtime fate.
        retry = getattr(rt, "_retry_embed", None)
        desync = isinstance(e, WorkerDesyncError)
        for r in batch:
            if not desync and retry is not None \
                    and retry(r, f"embed failed: {e}"):
                continue
            core.mark_dropped(r.user)
            poison = getattr(rt, "_poison_msg", None)
            msg = f"embed failed: {e}"
            jfinish(r, "error")
            r.finish(FinishReason.ERROR,
                     error=poison(r, msg) if poison else msg)
        raise
    rt.step_latency_ms = (time.monotonic() - t0) * 1e3
    for i, r in enumerate(batch):
        r.embedding = out[i].tolist()
        r.stats.first_token_at = time.monotonic()
        # Count processed tokens so embeddings traffic shows up in the
        # TUI tok/s telemetry.
        rt.tokens_generated += int(lens[i])
        core.mark_done(r.user, tokens=int(lens[i]))
        jfinish(r, "stop")
        r.finish(FinishReason.STOP)
    _sp.mark("detok")
    _sp.finish(n_prefill=len(batch), n_decode=0,
               padded_tokens=int(B) * int(bucket),
               compiled=_sp_take_compiled(rt))
    return True


def _host_ids_ready() -> bool:
    """The done-probe of a step whose ids are a host array."""
    return True


class StepInFlight:
    """One launched step: the device futures it left behind and the
    host's plan of it — all `step_collect` and `step_settle` need to
    finish it while the NEXT step already runs. When it left the device
    is bracketed by its timer (`sp.done`, stepprof.DoneBracket), probed
    on `toks_dev` wherever the engine thread stamps its time.

    `rows`: (kind, slot, req, chunk_pos|drafts, span) as composed; a
    fused scan's are its active slots with span = `k_steps` (0 = a
    ragged step). `emits[i]`: whether the host emits an id for row i (a
    span inside a prompt samples nothing); `exited`: the stream tokens
    that left a stack with an `exit_layer` there (`StepWork.note`: what the
    step's FLOPs are reckoned by); where a row starts is not
    kept here: steps settle in launch order, so at its settle it is what
    the slot's settled ids say (`step_settle`), whatever the verify spans
    before it accepted. `ending`: slots whose request is
    known to end with this step — left out of the next composition,
    finished when this step is settled. `state`: launched → collected
    (ids on the host, next input tokens set) → settled."""

    __slots__ = ("rows", "k_steps", "sp", "fields", "emits", "exited",
                 "mean_ctx", "toks_dev", "n_emit_dev", "toks", "n_emit",
                 "ending", "state", "t_launch", "dt", "prev", "no")

    def __init__(self, rows, k_steps, sp, fields, emits, mean_ctx):
        self.rows, self.k_steps, self.sp, self.fields = \
            rows, k_steps, sp, fields
        self.emits, self.mean_ctx, self.exited = emits, mean_ctx, 0
        self.toks_dev = self.n_emit_dev = self.toks = self.n_emit = None
        self.ending: set = set()
        self.state = "launched"
        self.t_launch = time.perf_counter()  # the step profiler's clock
        self.dt = 0.0
        self.prev: Optional["StepInFlight"] = None  # unsettled step before
        self.no = 0  # which launch composed it (ModelRuntime._launch_no)

    def futures(self, toks_dev, n_emit_dev=None) -> None:
        """The jitted call has returned the step's futures: keep the ones
        `step_collect` will read and ask for their transfer to the host
        NOW, behind the program that makes them — the read then waits for
        the step alone, never for a transfer it has yet to ask for (a
        replicated array moves one shard: jax's own rule). A host array
        (a dispatch seam that answers with numpy) has nothing to ask for.
        The request only schedules: whatever is wrong with the step, or
        with the request itself, is `step_collect`'s to meet at its read."""
        self.toks_dev, self.n_emit_dev = toks_dev, n_emit_dev
        for a in (toks_dev, n_emit_dev):
            start = getattr(a, "copy_to_host_async", None)
            if start is not None:
                try:
                    start()
                except Exception:
                    pass


class ModelRuntime:
    """Per-model decode state: KV pool, slot table, compiled step fns."""

    # Generative runtimes also serve /api/embed: the reference's Ollama
    # backends compute embeddings from causal models (llama.cpp mean
    # pooling), so embed-on-llama3 must work here too (README /api/embed).
    SERVES = ("generate", "embed")

    # SLO recording hook (telemetry/slo.py SLOEngine), attached by the
    # owning engine's load_model/_swap_rebuilt. None on SPMD worker
    # hosts' replay runtimes — SLO accounting is primary-only.
    slo = None

    # Preemption hook, attached by the owning engine (load_model /
    # _swap_rebuilt) when cfg.preempt is on: callable(req) -> bool that
    # returns the victim to the FRONT of its user's native queue and
    # re-registers it (False = the hook finished the request instead —
    # blocked/cancelled/expired). None => preemption disabled: decode
    # page exhaustion errors EXPLICITLY (kv_exhausted), never truncates.
    on_preempt = None

    # Deterministic fault injection (testing/faults.py), attached by the
    # engine when --fault-plan is set. Shared across a process's runtimes
    # so the plan's call counters form one deterministic stream.
    fault_plan = None

    # Decision journal (telemetry/journal.py), attached by the owning
    # engine's _attach_hooks. None on SPMD worker hosts' replay runtimes —
    # journaling, like SLO accounting, is primary-only.
    journal = None

    # Scheduling policy (engine/scheduler.py), attached by the owning
    # engine's _attach_hooks (tests attach directly). None behaves
    # exactly like fcfs: identity orderings, legacy victim key, no
    # output-length prediction.
    policy = None

    # Engine performance plane (telemetry/stepprof.py): the per-step
    # "paid a compile" flag (_sp_note_compile sets, the step's finish
    # read-and-clears). A step's timer rides its StepInFlight handle.
    _stepprof_compiled = False
    # The owning engine thread's loop clock (stepprof.LoopClock), attached
    # by _attach_hooks: step timers advance it, so step and loop phases
    # form one gapless chain. None (unit tests) times steps alone.
    loop_clock = None

    def __init__(
        self,
        name: str,
        model_cfg: ModelConfig,
        engine_cfg: EngineConfig,
        mesh=None,
        checkpoint_path: Optional[str] = None,
        dtype=jnp.bfloat16,
        preloaded_params=None,
    ):
        self.name = name
        self.cfg = model_cfg
        # Pristine config as passed in: __init__ may rewrite num_kv_heads
        # below (replicated-group KV for tp > kv_heads), and a recovery
        # rebuild must start from the UN-mutated config or it would skip
        # replication and load weights against the wrong shapes.
        self._orig_cfg = model_cfg
        self.ecfg = engine_cfg
        self.mesh = mesh
        self.dtype = dtype
        self.tokenizer = load_tokenizer(checkpoint_path)
        # Int8 quantization (weights and/or KV pages): validated here
        # too — tests and embedders construct runtimes directly, and an
        # unsupported combination must fail at build, not first dispatch.
        err = validate_quant_config(
            engine_cfg.weights_dtype, engine_cfg.kv_dtype,
            model_names=(name,))
        if err is not None:
            raise ValueError(err)
        err = kvc.refusal(
            model_cfg, spec=engine_cfg.spec,
            mesh_shape=dict(mesh.shape) if mesh is not None else {},
            kv_dtype=engine_cfg.kv_dtype,
            weights_dtype=engine_cfg.weights_dtype,
            prefix_cache=engine_cfg.prefix_cache)
        if err is not None:
            raise ValueError(err)
        self.weights_dtype = engine_cfg.weights_dtype
        self.kv_dtype = engine_cfg.kv_dtype
        if mesh is not None and mesh.shape.get("tensor", 1) > 1:
            validate_tp_for_model(
                mesh.shape["tensor"], model_cfg.num_kv_heads, model_cfg.num_heads
            )
        # Dense models on an --ep mesh are fine (their weights carry no
        # expert-axis spec, so they replicate over it); only an MoE model
        # whose expert count doesn't divide is a real layout error.
        ep = dict(mesh.shape).get("expert", 1) if mesh is not None else 1
        if ep > 1 and model_cfg.num_experts and model_cfg.num_experts % ep:
            raise ValueError(
                f"ep={ep} must divide num_experts={model_cfg.num_experts} "
                f"({name})")
        # `preloaded_params`: host-side tree shared across dp replicas so a
        # checkpoint is read/parsed once, not once per replica; each replica
        # still device_puts its own copy via shard_params below.
        # (The start-up ledger's `weights` and `place`, stepprof.
        # START_PHASES; the rest of this constructor is its caller's
        # `alloc`, build_model_runtimes.)
        tp_axis = mesh.shape.get("tensor", 1) if mesh is not None else 1
        with stepprof.PROFILER.phase("weights", name):
            params = preloaded_params if preloaded_params is not None else (
                weights.load_params(
                    model_cfg, checkpoint_path, seed=engine_cfg.seed,
                    dtype=dtype, weights_dtype=engine_cfg.weights_dtype,
                    # Random weights are drawn shard by shard on the mesh
                    # (the replicated-group rewrite below needs them whole).
                    mesh=mesh if tp_axis <= model_cfg.num_kv_heads else None,
                )
            )
        kv_sharding = None
        with stepprof.PROFILER.phase("place", name):
            if tp_axis > model_cfg.num_kv_heads:
                # Replicated-group KV sharding (e.g. qwen2.5's 4 KV heads
                # on tp=8): duplicate each KV head so every shard owns one
                # copy. validate_tp_for_model already guaranteed
                # divisibility.
                r = tp_axis // model_cfg.num_kv_heads
                params = weights.replicate_kv_heads(params, model_cfg, r)
                import dataclasses as _dc

                model_cfg = _dc.replace(model_cfg, num_kv_heads=tp_axis)
                self.cfg = model_cfg
                log.info("replicated KV heads x%d for tp=%d (%s)", r,
                         tp_axis, name)
            if mesh is not None:
                from jax.sharding import NamedSharding

                params = shard_params(params, mesh)
                kv_sharding = NamedSharding(mesh, kv_cache_spec())
            # The stacks whose contraction reads another order than
            # row-major live on the device in that order (models/llama.py:
            # weight_formats). Every jit site is handed `self.params`, and
            # a jit with no `in_shardings` compiles for the layout of the
            # committed array it is given: no step program re-lays a stack.
            weights.place_formats(model_cfg, params)
        self.params = params
        # A program's results are committed to a device once any of its
        # arguments is, and a re-laid stack is (`device_put` to a Format
        # commits; a tree born in its formats is committed whole): a carry
        # that started uncommitted would come back committed — another jit
        # key — and the first program launched would compile twice. So on
        # one device the carried state starts where the weights are held.
        held = {d for x in jax.tree_util.tree_leaves(params)
                if x.committed for d in x.devices()}
        carried_on = held.pop() if mesh is None and len(held) == 1 else None
        # Pool, per-slot state and page books: `cache.kc`, `.vc`, `.slot_state`
        # are, with recent and last_ids, donated to every step program.
        self.cache = kvc.SeqCache(
            name, model_cfg, engine_cfg, max_span=ragged_budget(engine_cfg),
            dtype=dtype, sharding=kv_sharding, device=carried_on,
            record=self._jrec, blocked=self._alloc_blocked)
        # Repeat-penalty state: ring of each slot's last-W context token ids
        # (-1 = empty), llama.cpp repeat_last_n semantics. Row S is a trash
        # row so padded/inactive scatter targets never touch a live slot.
        self.recent = jnp.full(
            (engine_cfg.max_slots + 1, engine_cfg.repeat_last_n), -1, jnp.int32
        )
        # The ids the last launched step sampled, one a row, kept ON THE
        # DEVICE: a step launched while that one is still unsettled takes a
        # row's input token from here — the host marks such a token as
        # -1 - (its row in that step) in the `tokens` it uploads — so
        # composing step N+1 never waits for step N's ids. Only the step
        # right before can be unsettled at a launch (the loop's depth is
        # one), so one step's rows are all the carry ever has to hold.
        self.last_ids = jnp.zeros((engine_cfg.max_slots,), jnp.int32)
        S = engine_cfg.max_slots
        # Slots mid-chunked-prefill: reserved (not schedulable) but not yet
        # decoding — slot_req stays None so decode skips them.
        self.reserved_slots: set = set()
        # Slots holding a page reservation: their request exhausted its
        # preemption budget (or no victim was eligible) when the pool ran
        # dry, so it KEEPS slot + pages but sits out decode dispatches
        # until growth succeeds — never truncated, never a victim spiral.
        self._stalled_slots: set = set()
        self._stall_since: Optional[float] = None
        self.slot_req: List[Optional[Request]] = [None] * S
        self.seq_lens = np.zeros((S,), np.int32)
        # A slot's next input token; -1 - row while the step that samples
        # it (as its row `row`) is unsettled: the device reads it from
        # `last_ids` then.
        self.last_tokens = np.zeros((S,), np.int32)
        # Tokens launched for a slot's request and not yet appended to its
        # generated_ids: what count-based finishes are predicted from.
        self._ahead = np.zeros((S,), np.int32)
        # Positions a slot's unsettled verify spans may add BEYOND what
        # `seq_lens` and `_ahead` already count (a span of 1 + d ids is
        # counted as one until it is collected): while a step is unsettled
        # the slot's true length lies in [seq_lens, seq_lens + _slack].
        self._slack = np.zeros((S,), np.int32)
        # What a speculating runtime's compositions claimed pages for: the
        # tokens a slot's pages were last grown to hold, and the number of
        # the launch that composed it — a rollback at the settle of an
        # EARLIER step must not trim below it (`step_settle`).
        self._launch_no = 0
        self._claim = np.zeros((S,), np.int32)
        self._claim_no = np.zeros((S,), np.int64)
        # Stream items (hand-overs) pushed since the settle in progress
        # began: `stream_items` on its step sample.
        self._stream_items = 0
        # The step that samples each slot's next input token (None: the
        # host holds the id), and the newest launched, unsettled step.
        self._tok_step: List[Optional["StepInFlight"]] = [None] * S
        self.inflight: Optional["StepInFlight"] = None
        self._last_done = 0.0  # when the last collected step left the device
        self.temp = np.zeros((S,), np.float32)
        self.top_k = np.zeros((S,), np.int32)
        self.top_p = np.ones((S,), np.float32)
        self.rep_pen = np.ones((S,), np.float32)
        self.pres_pen = np.zeros((S,), np.float32)
        self.freq_pen = np.zeros((S,), np.float32)
        self.seeds = np.zeros((S,), np.int32)  # >0 = per-request seed

        self.pending_prefill: collections.deque = collections.deque()
        # Embed-kind requests: stateless batch forwards, no slot/KV claim.
        self.pending_embed: collections.deque = collections.deque()
        self._block_ver = -1  # force one startup sweep (disk-loaded blocklist)
        # Admitted prompts mid-prefill (a span of each rides every tick's
        # ragged step, as the token budget allows).
        self.chunking: collections.deque = collections.deque()
        # Requests inside a prefill forward right now (cancel() must still
        # find them; installation re-checks the cancelled flag).
        self.inflight_prefill: List[Request] = []
        # Keys carry the trace-time sampling flags: ("ragged", T_pad, k_cap,
        # flags); decode: (k_steps, flags).
        self._prefill_jits: Dict[tuple, callable] = {}
        self._decode_jits: Dict[tuple, callable] = {}
        self._embed_jits: Dict[tuple, callable] = {}
        self._rng_counter = engine_cfg.seed
        # [transfers, bytes] `_upload` made for the step being launched.
        self._h2d = [0, 0]
        # Set after an unrecoverable step failure; the engine stops stepping
        # this runtime and rebuilds it (weights reloaded) when the device
        # answers again.
        self._failed = False
        # Decided ONCE, from what is known at construction, and never
        # changed afterwards: a kernel that then fails to compile fails
        # its dispatches loudly instead of being swapped out.
        self.attn_impl, why = select_attn_impl(
            jax.default_backend(), engine_cfg.kv_dtype)
        # Which inner product each Pallas kernel is built with at this
        # model's query group (ops/pallas/kv_contract.py): every launch of
        # that kernel, for the runtime's life. None without the kernels.
        self.attn_inner = None
        if self.attn_impl == "pallas":
            from ollamamq_tpu.ops.pallas.kv_contract import inner_report
            self.attn_inner = inner_report(
                model_cfg.num_heads // model_cfg.num_kv_heads)
        # What a launched step's layers did (engine/step_work.py), and what
        # of `engine_cfg` a step program's shapes depend on.
        self.work = step_work.StepWork(
            model_cfg, engine_cfg.page_size, name,
            step_work.kernel_counts(model_cfg, self.attn_impl),
            kv_itemsize=jnp.dtype(dtype).itemsize)
        self.dims = step_program.StepDims.of(engine_cfg)
        log.info("%s: attention=%s (%s)%s", name, self.attn_impl, why,
                 "".join(f" {k}={v}" for k, v in
                         (self.attn_inner or {}).items()))
        # Ragged mixed-batch scheduling: prefill spans + decode tokens
        # pack into ONE token-budget dispatch (no bucket padding).
        g = max(1, engine_cfg.token_granule)
        self._ragged_budget = ragged_budget(engine_cfg)
        # Allowed stream totals: a power-of-two ladder over the granule,
        # capped by the budget — one compile per rung; the composer TRIMS
        # the last span down to a rung instead of padding up to one, so
        # steady-state dispatches pay (near) zero padding.
        ladder = []
        v = g
        while v < self._ragged_budget:
            ladder.append(v)
            v *= 2
        ladder.append(self._ragged_budget)
        self._ragged_ladder = ladder
        self._max_ctx = min(engine_cfg.max_context, model_cfg.max_seq_len)

        # Speculative decoding state (--spec): n-gram drafts verified on
        # the ragged span path. Host-side accounting feeds the accept-
        # rate gauge and the per-user auto-throttle; the actual accept/
        # rollback machinery lives in step_program.ragged_step / step_ragged.
        self.spec = bool(engine_cfg.spec) and engine_cfg.spec_k > 0
        # The proposer: the model's own multi-token-prediction module where
        # it has one — ONE draft a row, computed and kept on the device by
        # the step that verifies the last: `draft_ids[slot]` (row S: the
        # padding rows') and, beside it, `len_ids[slot]`, the slot's LENGTH,
        # two more donated carries of every ragged step
        # (step_program.ragged_step says what the program does with them);
        # the host never sees a draft, only how many were accepted, so the
        # next step is composed and launched while this one runs
        # (`may_overlap`). `_draft_ok[slot]`: a step with the module has
        # left the slot's draft there. N-gram prompt lookup on the host
        # otherwise.
        self.mtp = self.spec and model_cfg.num_nextn_predict_layers > 0
        self.spec_k = 1 if self.mtp else engine_cfg.spec_k
        self.draft_ids = self.len_ids = None
        if self.mtp:
            self.draft_ids, self.len_ids = (
                jnp.zeros((engine_cfg.max_slots + 1,), jnp.int32)
                for _ in range(2))
        if carried_on is not None:  # (beside the cache's arrays)
            (self.recent, self.last_ids, self.draft_ids,
             self.len_ids) = jax.device_put(
                (self.recent, self.last_ids, self.draft_ids, self.len_ids),
                carried_on)
        self._draft_ok = np.zeros((engine_cfg.max_slots,), bool)
        self.spec_proposed = 0
        self.spec_accepted = 0
        self.spec_rollbacks = 0
        # user -> [proposed, accepted]; users whose observed accept rate
        # under-runs --spec-min-accept after a warmup sample stop
        # speculating (the verify FLOPs stopped paying for themselves).
        self._spec_user: Dict[str, list] = {}
        self._spec_throttled: set = set()
        proposer = self.proposer = "mtp" if self.mtp else "ngram"
        self._tm_spec_prop = tm.SPEC_TOKENS_TOTAL.labels(
            model=name, outcome="proposed", proposer=proposer)
        self._tm_spec_acc = tm.SPEC_TOKENS_TOTAL.labels(
            model=name, outcome="accepted", proposer=proposer)
        self._tm_spec_rej = tm.SPEC_TOKENS_TOTAL.labels(
            model=name, outcome="rejected", proposer=proposer)
        self._tm_spec_rate = tm.SPEC_ACCEPT_RATE.labels(model=name)

        # Telemetry.
        self.step_latency_ms = 0.0
        self.prefill_latency_ms = 0.0
        self.tokens_generated = 0
        self.preempt_count = 0
        self.retry_count = 0
        self.ttft_window: collections.deque = collections.deque(maxlen=512)
        self.step_window: collections.deque = collections.deque(maxlen=512)
        # Registry handles resolved once (child lookup is a dict hit, but
        # the hot path shouldn't even pay that).
        self._tm_ttft = tm.TTFT_MS.labels(model=name)
        self._tm_tpot = tm.TPOT_MS.labels(model=name)
        self._tm_step = tm.STEP_LATENCY_MS.labels(model=name)
        self._tm_prefill = tm.PREFILL_LATENCY_MS.labels(model=name)
        self._tm_occupancy = tm.BATCH_OCCUPANCY.labels(model=name)
        self._tm_padding = tm.BATCH_PADDING_WASTE.labels(model=name)
        self._tm_pages = tm.KV_PAGES_USED.labels(model=name)
        self._tm_page_util = tm.KV_PAGE_UTILIZATION.labels(model=name)
        self._tm_mfu = tm.MFU.labels(model=name)
        self._tm_tokens = tm.TOKENS_GENERATED_TOTAL.labels(model=name)
        self._tm_prompt_tokens = tm.PROMPT_TOKENS_TOTAL.labels(model=name)
        self._tm_preempt = tm.PREEMPTIONS_TOTAL.labels(model=name)
        self._tm_retries = tm.RETRIES_TOTAL.labels(model=name)
        # MFU accounting: analytic FLOPs/token (models/llama config) over
        # this runtime's share of chip peak. Unknown accelerators (CPU
        # meshes) publish 0, never a made-up peak.
        try:
            kind = jax.local_devices()[0].device_kind
        except Exception:
            kind = ""
        self.peak_flops = mfu_model.peak_flops_per_chip(kind)
        self.n_chips = int(mesh.size) if mesh is not None else 1
        self.mfu = 0.0
        # FLOPs model on the PRISTINE config: the replicated-group KV
        # rewrite above duplicates KV heads as a sharding layout trick —
        # it adds no real math.
        tm.FLOPS_PER_TOKEN.labels(model=name).set(
            mfu_model.flops_per_token(self._orig_cfg))
        self._tm_occupancy.set(0.0)
        self._tm_mfu.set(0.0)
        self.param_bytes = sum(
            x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(params)
        )
        self.kv_bytes = self.cache.kv_bytes
        self.state_bytes = self.cache.state_bytes
        # Where this runtime's weights live (stats: which member of a
        # fleet, which slice of a dp mesh, sits on which device).
        self.devices = sorted(
            {str(d) for x in jax.tree_util.tree_leaves(params)
             for d in x.devices()})
        # HBM density scoreboard: what weights and KV actually cost on
        # this runtime — the quantization PR's before/after lever.
        tm.HBM_WEIGHT_BYTES.labels(model=name).set(self.param_bytes)
        self.weight_stacks_relaid, relaid_bytes = weights.relaid(
            model_cfg, params)
        tm.WEIGHT_STACKS_RELAID.labels(model=name).set(
            self.weight_stacks_relaid)
        tm.WEIGHT_STACKS_RELAID_BYTES.labels(model=name).set(relaid_bytes)
        if self.weight_stacks_relaid:
            log.info("%s: %d weight stacks (%.1f MB) held in the device "
                     "layout their contractions read", name,
                     self.weight_stacks_relaid, relaid_bytes / 1e6)
        if model_cfg.kv_lora_rank and model_cfg.num_nextn_predict_layers:
            log.info("%s: prediction module held (its block's rows are "
                     "layer %d of the latent pool); %s", name,
                     model_cfg.count(ATTENTION),
                     "--spec drafts with it, on the device" if self.mtp
                     else "not run without --spec")

    # -- capacity ----------------------------------------------------------
    def free_slots(self) -> int:
        return sum(
            r is None and i not in self.reserved_slots
            for i, r in enumerate(self.slot_req)
        )

    def has_capacity(self, kind: Optional[str] = None) -> bool:
        """Can we take one more request from the scheduler right now?

        Kind-aware: embeds are stateless batch forwards bounded only by
        their queue (same 4x ceiling as EncoderRuntime), while generates
        need a decode slot + KV pages — independent pools, so a full
        decode batch must not park embeds and a deep embed backlog must
        not park generates. kind=None answers "either"."""
        if self._failed:
            return False
        embed_ok = len(self.pending_embed) < 4 * self.ecfg.max_slots
        if kind == "embed":
            return embed_ok
        pc = self.cache.prefix_cache
        evictable = pc.evictable_pages if pc is not None else 0
        gen_ok = (
            len(self.pending_prefill) < 2 * self.ecfg.max_slots
            and self.free_slots() > 0
            # Unreferenced cached pages count as capacity: allocator
            # exhaustion under a full cache evicts, never rejects.
            and self.cache.alloc.free_pages + evictable >= 2
        )
        return gen_ok if kind == "generate" else (gen_ok or embed_ok)

    def has_work(self) -> bool:
        return (
            bool(self.pending_prefill)
            or bool(self.pending_embed)
            or bool(self.chunking)
            or any(r is not None for r in self.slot_req)
        )

    def active_count(self) -> int:
        return sum(r is not None for r in self.slot_req)

    # -- submission --------------------------------------------------------
    def submit(self, req: Request) -> bool:
        if req.kind == "embed":
            self.pending_embed.append(req)
            return True
        if getattr(req, "_inc_decode", None) is None:
            # Preserved across preemption/retry requeues: the replay
            # prompt carries already-generated ids the decoder has seen.
            req._inc_decode = self.tokenizer.make_incremental_decoder()
        self.pending_prefill.append(req)
        return True

    # -- compiled steps ----------------------------------------------------
    def _next_rng(self) -> int:
        """The next step's RNG counter (a field of its packed input): the
        step program makes its key from it, `jax.random.PRNGKey(counter)`
        traced — nothing but the step program is dispatched for a step."""
        self._rng_counter = (self._rng_counter + 1) & 0x7FFFFFFF
        return self._rng_counter

    def _upload(self, arr: np.ndarray) -> np.ndarray:
        """The one place a step's host inputs leave for the device,
        counted onto the step's sample (`h2d_transfers`, `h2d_bytes`).
        The transfer itself is the jitted call's: handed the numpy array,
        its C++ argument path puts it on the chip (replicated over a
        mesh) with no Python in between — measured against an explicit
        `jnp.asarray` / `device_put` first, PERF.md §6, PR 30. `arr` is
        never written again (the transfer may alias host memory)."""
        self._h2d[0] += 1
        self._h2d[1] += arr.nbytes
        return arr

    def _fault(self, site: str) -> None:
        """Fault-injection seam, called at the top of every dispatch: a
        firing rule raises (exception/device_loss) or sleeps (slow)
        BEFORE the jit call, so donated buffers are never consumed by an
        injected failure — exactly the recoverable-fault shape the
        retry/containment paths exist for."""
        if self.fault_plan is not None:
            self.fault_plan.check(site)

    def _alloc_blocked(self, site: str) -> bool:
        """The fault plan's allocation seam ("alloc", "extend")."""
        return self.fault_plan is not None and self.fault_plan.blocked(site)

    # -- decision journal seams --------------------------------------------
    def _jrec(self, kind: str, req=None, **fields) -> None:
        """Journal one decision with this runtime's model name; no-op
        when no journal is attached (SPMD workers, bare unit tests)."""
        j = self.journal
        if j is not None:
            j.record(kind, req=req, model=self.name, **fields)

    # -- dispatch seams (SPMD subclass broadcasts before dispatching) ------
    # Each returns (sampled_tokens, kc', vc', recent'); the caller assigns
    # the three state arrays back. The two step programs of the pipelined
    # loop (ragged, decode) also take and return the `last_ids` carry and
    # the per-slot state (None for a model without such layers).
    def _dispatch_ragged(self, T_pad, k_cap, buf):
        """`buf`: the step's packed host inputs (step_pack.ragged_layout)."""
        # Speculative dispatches get their own fault site: a chaos plan
        # can target the verify span without perturbing plain mixed
        # dispatches (and vice versa).
        self._fault("spec_verify" if k_cap else "ragged")
        lay = self.dims.ragged_layout(T_pad)
        fn = self._get_ragged_jit(
            T_pad, k_cap, sampling_flags(*lay.sampling(buf)))
        c = self.cache
        if not self.mtp:
            return fn(self.params, self._upload(buf), c.kc, c.vc,
                      self.recent, self.last_ids, c.slot_state)
        # The module's drafts and the rows' lengths stay on the device:
        # two more carries.
        *out, self.draft_ids, self.len_ids = fn(
            self.params, self._upload(buf), c.kc, c.vc, self.recent,
            self.last_ids, c.slot_state, self.draft_ids, self.len_ids)
        return tuple(out)

    def _took_back(self, out: tuple) -> tuple:
        """A step program's results, its donated carries (the last five)
        taken back into their places."""
        c = self.cache
        *_, c.kc, c.vc, self.recent, self.last_ids, c.slot_state = out
        return out

    def _program(self, cache, site, key_, build, *shape, **kw):
        """The per-runtime compile ledger and nothing else: the program
        `build(cfg, dims, *shape, …)` (engine/step_program.py) is in `cache`
        under `key_` once it was asked for, behind the wrapper that times
        its first call (`_sp_note_compile`); a fired `compile` fault drops
        it, and the builder's copy with it."""
        evicted = _sp_compile_evict(self, cache, key_)
        if key_ not in cache:
            _sp_note_compile(self, site, key_, cache, build(
                self.cfg, self.dims, *shape, attn_impl=self.attn_impl,
                mesh=self.mesh, fresh=evicted, **kw))
        return cache[key_]

    def _get_ragged_jit(self, T_pad: int, k_cap: int = 0,
                        flags=(True, True, True)):
        return self._program(
            self._prefill_jits, "ragged", ("ragged", T_pad, k_cap, flags),
            step_program.ragged_step, T_pad, k_cap, flags, mtp=self.mtp)

    def _dispatch_decode(self, k_steps, buf):
        """`buf`: the scan's packed host inputs (step_pack.decode_layout)."""
        self._fault("decode")
        fn = self._get_decode_jit(
            k_steps, sampling_flags(*self.dims.decode_layout().sampling(buf)))
        c = self.cache
        return fn(self.params, self._upload(buf), c.kc, c.vc,
                  self.recent, self.last_ids, c.slot_state)

    def _get_decode_jit(self, k_steps: int, flags=(True, True, True)):
        return self._program(self._decode_jits, "decode", (k_steps, flags),
                             step_program.decode_scan, k_steps, flags)

    # -- slot lifecycle ----------------------------------------------------
    def _clear_slot(self, slot: int) -> None:
        """Reset a slot's sampling rows and bookkeeping (pages must be
        released by the caller — finish and preempt release differently)."""
        self.seq_lens[slot] = 0
        self.temp[slot] = 0.0
        self.top_k[slot] = 0
        self.top_p[slot] = 1.0
        self.rep_pen[slot] = 1.0
        self.pres_pen[slot] = 0.0
        self.freq_pen[slot] = 0.0
        self.seeds[slot] = 0
        self.slot_req[slot] = None
        self._ahead[slot] = 0
        self._slack[slot] = 0
        self._tok_step[slot] = None
        self._draft_ok[slot] = False
        self._stalled_slots.discard(slot)

    def _finish_slot(
        self, slot: int, reason: FinishReason, core: MQCore,
        flush: bool = True, error: str = "",
    ) -> None:
        """`flush=False` on the stop-string path: held-back text contains the
        stop sequence the client asked to suppress."""
        req = self.slot_req[slot]
        if req is None:
            return
        pol = self.policy
        extra = ({"predicted_tokens": pol.predict(req)}
                 if pol is not None else {})
        self._jrec("finish", req, reason=reason.value, slot=slot,
                   tokens=len(req.generated_ids), **extra)
        if pol is not None and reason in (FinishReason.STOP,
                                          FinishReason.LENGTH):
            # Served-to-completion outcomes feed the output-length
            # predictor; cancels/errors would teach it client behavior.
            pol.observe_finish(req, model=self.name)
        # Pass req: an installed slot's prompt KV is fully written, so
        # its full prompt pages are insertable into the prefix cache.
        self.cache.release(slot, req)
        self._clear_slot(slot)
        req.stats.completion_tokens = len(req.generated_ids)
        chunk = (req.flush_text()
                 if flush and reason != FinishReason.CANCELLED else "")
        if chunk:
            req.stream.push(StreamItem("token", text=chunk))
        if reason in (FinishReason.CANCELLED, FinishReason.KV_EXHAUSTED,
                      FinishReason.ERROR, FinishReason.DEADLINE):
            # (An honest failure: the client keeps the text generated so far,
            # flushed, but the request counts dropped, not processed.)
            core.mark_dropped(req.user)
        else:
            core.mark_done(req.user, tokens=len(req.generated_ids))
        req.finish(reason, error=error)

    def _emit_row(self, slot: int, toks: Sequence[int], core: MQCore,
                  ctx_len: int) -> int:
        """Process the tokens ONE step sampled for a slot, in order, and
        hand them to its stream as one item. Returns how many of them
        were taken: all while the sequence continues, up to and including
        the one that ended it otherwise (EOS, a stop string, a count —
        the rest of the row is dropped). `ctx_len`: the slot's context
        length once the first of them is counted, as the step that
        sampled them planned it — the live seq_lens may already be a step
        ahead (the next step is launched before this one's tokens are
        emitted).

        What a token costs stays a token's: the EOS comparison, the id
        appended, the incremental detokeniser, the stop strings' hold-back,
        the limits. The hand-over — the item, its push, the consumer's
        wake-up, the durable tap's call — is once a row."""
        req = self.slot_req[slot]
        if req is None:
            return 0
        if req.cancelled.is_set() or req.stream.overflowed:
            # Overflowed stream == consumer stopped reading == client gone.
            self._finish_slot(slot, FinishReason.CANCELLED, core)
            return 0
        eos = self.tokenizer.eos_id
        gen = req.generated_ids
        decode = req._inc_decode
        max_tokens = req.sampling.max_tokens
        ids: List[int] = []
        texts: List[str] = []
        end = None  # the finish to make once the item is out
        tail = ""   # text before a stop string: it has no id of its own
        taken = 0
        for tok in toks:
            taken += 1
            if tok == eos:
                end = (FinishReason.STOP, True)
                break
            gen.append(tok)
            if not req.stats.first_token_at:
                req.stats.first_token_at = time.monotonic()
                self.ttft_window.append(req.stats.ttft_ms)
                self._tm_ttft.observe(req.stats.ttft_ms)
                if self.slo is not None:
                    self.slo.record("ttft", req.stats.ttft_ms)
                req.trace_event("first_token",
                                ttft_ms=round(req.stats.ttft_ms, 3))
            elif len(gen) % DECODE_EVENT_EVERY == 0:
                req.trace_event("decode", tokens=len(gen))
            text = decode(tok)
            chunk, stopped = req.emit_text(text) if text else ("", False)
            if stopped:  # suppress the held-back text; the id is counted,
                tail = chunk  # never pushed
                end = (FinishReason.STOP, False)
                break
            # EVERY sampled token goes out, text or not (held-back bytes
            # mid UTF-8 sequence, stop-string holdback): the id stream
            # must be complete for the fleet's token-space failover
            # replay — text consumers already skip empty chunks.
            ids.append(tok)
            texts.append(chunk)
            if len(gen) >= max_tokens or ctx_len + 1 >= self._max_ctx:
                end = (FinishReason.LENGTH, True)
                break
            ctx_len += 1
        if ids:
            req.stream.push(StreamItem.tokens(ids, texts))
            self._stream_items += 1
            # Stream-write stall attribution: a consumer backlog above the
            # high-water mark opens a "stream" span on the trace; dropping
            # back under closes it. Transition-edged so the event cap isn't
            # chewed up by a persistently slow reader.
            depth = req.stream.depth()
            if not req._stream_stalled and depth >= req.stream.high_water:
                req._stream_stalled = True
                req.trace_event("stream_stall", depth=depth)
            elif req._stream_stalled and depth < req.stream.high_water // 2:
                req._stream_stalled = False
                req.trace_event("stream_resume", depth=depth)
        if tail:
            req.stream.push(StreamItem("token", text=tail))
        if end is not None:
            self._finish_slot(slot, end[0], core, flush=end[1])
        return taken

    def _ends_by_count(self, slot: int, req: Request) -> bool:
        """Will the tokens launched so far for `slot` (all counted in
        `_ahead` and `seq_lens` already) end its request by LENGTH? The
        finishes _emit_row decides from a count, known before the ids
        are: such a row is left out of the next step's composition."""
        return (len(req.generated_ids) + int(self._ahead[slot])
                >= req.sampling.max_tokens
                or int(self.seq_lens[slot]) + 1 >= self._max_ctx)

    # -- steps -------------------------------------------------------------
    def _claim_slot(self) -> Optional[int]:
        for i, r in enumerate(self.slot_req):
            if r is None and i not in self.reserved_slots:
                return i
        return None

    def _seat_slot(self, slot: int, req: Request, n: int,
                   kv_len: Optional[int] = None) -> None:
        """Seat a request in its decode slot, its prompt's last span
        dispatched — or (`kv_len`) a migrated stream's pages landed: from
        here on it is a decode row."""
        self._jrec("install", req, slot=slot, n_prompt=n)
        self.slot_req[slot] = req
        if kv_len is None:
            self._tm_prompt_tokens.inc(n)
        self.seq_lens[slot] = n if kv_len is None else kv_len
        self.temp[slot] = req.sampling.temperature
        self.top_k[slot] = req.sampling.top_k
        self.top_p[slot] = req.sampling.top_p
        self.rep_pen[slot] = req.sampling.repeat_penalty
        self.pres_pen[slot] = req.sampling.presence_penalty
        self.freq_pen[slot] = req.sampling.frequency_penalty
        self.seeds[slot] = req.sampling.seed

    # -- KV page migration (fleet export/import; engine-thread only) -------
    def export_request(self, rid: int):
        """Snapshot + DETACH one installed decode slot for migration.
        Returns (handle, blob) or None when `rid` holds no installed slot
        (queued / mid-prefill / chunking work replays cheaply via
        recompute — only written decode state is worth shipping). The
        detached slot keeps its pages (reserved, undispatchable) until
        release_export resolves the two-phase handoff.

        The blob is the portable wire state of the slot: its page run
        (int8 payload + scale rows for quantized pools — ~2x cheaper to
        move), the decode cursor (written kv_len + the pending last
        token, mirroring the install convention), the penalty ring row,
        request state, and the scheduler predictor's view of the user."""
        if kvc.unserved(self.cfg, "migrate"):
            # The blob has no place for the per-slot state, and pages
            # without it resume another sequence; nor for latent and
            # index-key pages: not exportable (the caller's fallback
            # replays the request from its tokens).
            return None
        for slot, req in enumerate(self.slot_req):
            if req is not None and req.req_id == rid:
                break
        else:
            return None
        c = self.cache
        pages = list(c.slot_pages[slot])
        blob = {
            **c.header("stream"),
            "kv_len": int(self.seq_lens[slot]),
            "last_token": int(self.last_tokens[slot]),
            "n_pages": len(pages),
            "recent": np.asarray(self.recent[slot]),
            "request": request_migration_state(req),
            # In-process handoff carries the live incremental detokenizer
            # (exact stream continuity); the wire packer drops it and the
            # importer builds a fresh one off the carried detok text.
            "_inc_decode": req._inc_decode,
            **c.gather(pages),
        }
        pol = self.policy
        if pol is not None:
            blob["predictor"] = pol.predictor.export_user(req.user)
        self.slot_req[slot] = None
        self.reserved_slots.add(slot)
        self._stalled_slots.discard(slot)
        return {"slot": slot, "req": req}, blob

    def release_export(self, handle: dict) -> None:
        """Resolve a detached export (commit OR abort): the pages go the
        same way a finished slot's do — full prompt pages merge into the
        prefix cache (a recompute fallback then replays mostly from
        cache), the rest return to the free list."""
        slot, req = handle["slot"], handle["req"]
        self.reserved_slots.discard(slot)
        self.cache.release(slot, req)
        self._clear_slot(slot)

    def import_request(self, blob: dict, req: Request) -> bool:
        """Install a migrated stream into a fresh slot from shipped
        state: allocate a same-length page run, scatter the wire pages
        into this pool, and resume the decode cursor exactly where the
        source froze it — no token is ever recomputed. False when the
        blob's shape doesn't match this runtime or capacity is gone
        (the caller falls back to recompute replay). A model that holds
        more than K and V pages refuses every blob: pages come without
        its state."""
        why = kvc.unserved(self.cfg, "migrate")
        if why:
            raise MigrationError(f"{self.name}: {why}")
        if not self.cache.accepts(blob, "stream"):
            return False
        slot = self._claim_slot()
        if slot is None or not self.cache.install(slot, blob):
            return False
        self.recent = self.recent.at[slot].set(
            jnp.asarray(np.asarray(blob["recent"], np.int32)))
        if req._inc_decode is None:
            req._inc_decode = self.tokenizer.make_incremental_decoder()
        pol = self.policy
        if pol is not None and blob.get("predictor"):
            pol.predictor.import_user(req.user, blob["predictor"])
        self._seat_slot(slot, req, len(req.prompt_tokens),
                        kv_len=int(blob["kv_len"]))
        self.last_tokens[slot] = int(blob["last_token"])
        return True

    # (The SPMD runtime overrides both; TPUEngine reaches them by name.)
    def export_prefix(self, tokens: List[int]):
        return self.cache.export_prefix(tokens)

    def import_prefix(self, blob: dict) -> int:
        return self.cache.import_prefix(blob)

    # -- speculative decoding (n-gram draft + ragged verify) ---------------
    # Accept-rate warmup sample per user before the auto-throttle may
    # fire, and how far back the n-gram proposer searches (longer
    # contexts still match — recency wins — but the scan stays O(window)
    # per tick, never O(context)).
    SPEC_THROTTLE_SAMPLE = 64
    SPEC_LOOKUP_WINDOW = 1024
    SPEC_NGRAMS = (3, 2)

    def _spec_eligible(self, req: Request) -> bool:
        """Speculation is host-gated to rows whose sampling the greedy
        verifier reproduces exactly: temperature 0 (argmax) with neutral
        penalties — a penalized row's argmax depends on the ring state
        at EACH draft position, which the single-dispatch verify does
        not replay. Sampled/penalized requests stay 1-token decode rows
        (byte-identical either way); throttled users sit out."""
        s = req.sampling
        return (s.temperature == 0.0 and s.repeat_penalty == 1.0
                and s.presence_penalty == 0.0 and s.frequency_penalty == 0.0
                and req.user not in self._spec_throttled)

    def _propose_drafts(self, req: Request, slot: int) -> List[int]:
        """The slot's drafts for the step being composed, by this
        runtime's proposer. The model's own prediction module (`mtp`): ONE
        draft, which the step before left on the device (`draft_ids`) —
        the host composes its place ([0]: the program writes the id in)
        and never reads it; none while no step with the module has served
        the slot, or no budget remains in the LONGER case of a step still
        unsettled (every id it may emit counted, `_slack`). Otherwise
        n-gram prompt lookup on the host (`_propose_ngram`)."""
        if not self.mtp:
            return self._propose_ngram(req, slot)
        slack = int(self._slack[slot])
        remaining = (req.sampling.max_tokens - len(req.generated_ids)
                     - int(self._ahead[slot]) - slack - 1)
        room = self._max_ctx - int(self.seq_lens[slot]) - slack - 2
        return [0] if self._draft_ok[slot] and min(remaining, room) > 0 \
            else []

    def _propose_ngram(self, req: Request, slot: int) -> List[int]:
        """Prompt-lookup draft proposal: match the context's trailing
        n-gram (n in SPEC_NGRAMS, longest first) against its most recent
        earlier occurrence and propose the tokens that followed — free
        (no second model, no device work) and strong exactly when the
        model is reproducing earlier text (repetitive generation, quote-
        the-prompt workloads). Returns [] when nothing matches or no
        budget remains; caps at spec_k, the request's remaining token
        budget, and the context ceiling."""
        k = self.spec_k
        remaining = req.sampling.max_tokens - len(req.generated_ids) - 1
        pos = int(self.seq_lens[slot])
        k = min(k, remaining, self._max_ctx - pos - 2)
        if k <= 0:
            return []
        # Full token history as the decoder saw it: a preempted request
        # folded already-streamed ids into prompt_tokens, so only the
        # post-replay generated tail appends.
        ctx = req.prompt_tokens + req.generated_ids[req._replay_gen:]
        lo = max(0, len(ctx) - self.SPEC_LOOKUP_WINDOW)
        for n in self.SPEC_NGRAMS:
            if len(ctx) - lo < n + 1:
                continue
            key = ctx[-n:]
            for s in range(len(ctx) - n - 1, lo - 1, -1):
                if ctx[s:s + n] == key:
                    drafts = ctx[s + n:s + n + k]
                    if drafts:
                        return list(drafts)
                    break
        return []

    def _spec_outcome(self, req: Request, proposed: int,
                           accepted: int) -> None:
        """Per-dispatch speculative accounting: totals, the accept-rate
        gauge, and the per-user auto-throttle — a user whose drafts keep
        getting rejected stops paying the (proposed - accepted) wasted
        verify tokens on every dispatch."""
        self.spec_proposed += proposed
        self.spec_accepted += accepted
        self._tm_spec_prop.inc(proposed)
        self._tm_spec_acc.inc(accepted)
        self._tm_spec_rej.inc(proposed - accepted)
        if self.spec_proposed:
            self._tm_spec_rate.set(
                round(self.spec_accepted / self.spec_proposed, 4))
        row = self._spec_user.setdefault(req.user, [0, 0])
        row[0] += proposed
        row[1] += accepted
        min_rate = self.ecfg.spec_min_accept
        if (min_rate > 0 and row[0] >= self.SPEC_THROTTLE_SAMPLE
                and row[1] / row[0] < min_rate
                and req.user not in self._spec_throttled):
            self._spec_throttled.add(req.user)
            log.info("%s: speculation throttled for user %s (accept rate "
                     "%.2f < %.2f over %d proposed)", self.name, req.user,
                     row[1] / row[0], min_rate, row[0])

    def _drop_expired_slot(self, slot: int, core: MQCore) -> None:
        """Deadline enforcement at the speculative composer: an expired
        request must not burn a k-token verify span (the same
        before-the-dispatch check prefill and chunking already make).
        The slot finishes with the explicit deadline reason — text
        streamed so far flushes, the drop counts as dropped work."""
        req = self.slot_req[slot]
        tm.DEADLINE_DROPS_TOTAL.labels(model=self.name).inc()
        tm.SHED_TOTAL.labels(reason="deadline").inc()
        slack = ((time.monotonic() - req.deadline) * 1e3
                 if req.deadline is not None else 0.0)
        self._jrec("deadline_drop", req, slack_ms=round(slack, 3))
        self._finish_slot(slot, FinishReason.DEADLINE, core,
                          error="deadline expired before completion")

    # -- preemption with recompute -----------------------------------------
    KV_EXHAUSTED_MSG = ("KV page pool exhausted mid-decode and preemption "
                       "is disabled; retry, shorten the prompt, or raise "
                       "--num-pages")

    def _pick_victim(self) -> Optional[int]:
        """Victim slot for a preemption. Decision point (c) of the
        scheduler policy: eligibility stays here — NEVER the VIP, never
        a request that spent its preemption budget (anti-livelock: it
        holds a reservation) — while the preference among eligible slots
        is the policy's victim_key (max wins). fcfs/None keeps the
        legacy heuristic: lowest fair-share priority first (the user
        with the most lifetime served requests), youngest arrival as
        tie-break. Stalled reservation-holders under budget still
        qualify — they hold pages too. None = nobody is preemptible."""
        vip = None
        users: dict = {}
        try:
            snap = self.core_snapshot_for_preempt()
            vip = snap.get("vip")
            users = snap.get("users", {})
        except Exception:
            pass  # degraded victim pick (age only) beats no preemption
        pol = self.policy
        best, best_key = None, None
        for i, r in enumerate(self.slot_req):
            if r is None or r.preemptions >= self.ecfg.preempt_max:
                continue
            if vip is not None and r.user == vip:
                continue
            served = users.get(r.user, {}).get("processed", 0)
            key = (pol.victim_key(r, served) if pol is not None
                   else (served, r.stats.enqueued_at))
            if best_key is None or key > best_key:
                best, best_key = i, key
        if best is not None and pol is not None and pol.name != "fcfs":
            victim = self.slot_req[best]
            self._jrec("sched", victim, policy=pol.name, point="victim",
                       predicted=pol.predict(victim),
                       score=round(pol.remaining(victim), 3))
        return best

    # Seam for _pick_victim's policy inputs: the engine loop owns `core`
    # only inside step calls, so the snapshot source is stashed per call.
    def core_snapshot_for_preempt(self) -> dict:
        core = getattr(self, "_preempt_core", None)
        return core.snapshot() if core is not None else {}

    def _preempt_slot(self, slot: int, core: MQCore) -> None:
        """Evict `slot` for recompute: snapshot prompt + generated tokens,
        merge the WRITTEN KV pages into the prefix cache (re-admission
        then replays mostly from cache), free the rest, and hand the
        request to the engine's requeue-front hook. The stream, the
        incremental detokenizer, and generated_ids survive untouched, so
        the client sees one seamless token stream across the preemption."""
        req = self.slot_req[slot]
        self.preempt_count += 1
        self._tm_preempt.inc()
        req.preemptions += 1
        # KV is written for prompt + all generated tokens but the LAST
        # sampled one (its write belongs to the decode step that never
        # ran). The replay prompt carries that token too — its KV is
        # recomputed by the re-prefill, and the forward samples the NEXT
        # token, continuing the stream exactly where it stopped.
        replay = req.prompt_tokens + req.generated_ids[req._replay_gen:]
        written = len(replay) - 1 if req.generated_ids else len(replay)
        req.trace_event("preempt", slot=slot, tokens=written,
                        n=req.preemptions)
        if self.journal is not None:
            # Decision inputs: pool pressure plus the victim's fair-share
            # standing (most-served user loses) and the VIP it must never
            # be — the explainability contract for every preemption.
            vip, served = None, None
            try:
                snap = self.core_snapshot_for_preempt()
                vip = snap.get("vip")
                served = snap.get("users", {}).get(req.user, {}).get(
                    "processed")
            except Exception:
                pass
            self._jrec("preempt", req, slot=slot, why="kv_pressure",
                       n=req.preemptions,
                       free_pages=self.cache.alloc.free_pages,
                       victim_served=served, vip=vip)
        self._release_for_replay(slot, req, replay, written)
        hook = self.on_preempt
        if hook is not None:
            hook(req)  # False => hook finished it (blocked/expired)

    def _release_for_replay(self, slot: int, req: Request, replay: List[int],
                            written: int) -> None:
        """Release `slot` with its `written` tokens' pages merged into the
        prefix cache, and leave `req` to replay prompt + generated ids."""
        req.prompt_tokens = replay[:written]
        self.cache.release(slot, req if written else None)
        req.prompt_tokens = replay
        req._replay_gen = len(req.generated_ids)
        self._clear_slot(slot)

    def _page_exhausted(self, slot: int, need_tokens: int,
                        core: MQCore) -> None:
        """Decode-time page growth failed for `slot`. Never a silent
        LENGTH: preempt a victim and retry, stall on a reservation, or —
        with preemption off — error explicitly as kv_exhausted. A genuine
        per-sequence context-cap hit is still an honest LENGTH (that IS
        the context budget, not pool pressure)."""
        pages, alloc = self.cache.slot_pages[slot], self.cache.alloc
        if (alloc.pages_needed(need_tokens) > alloc.max_pages_per_seq
                or len(pages) >= alloc.max_pages_per_seq):
            self._finish_slot(slot, FinishReason.LENGTH, core)
            return
        if self.on_preempt is None or not self.ecfg.preempt:
            tm.SHED_TOTAL.labels(reason="kv_exhausted").inc()
            self._finish_slot(slot, FinishReason.KV_EXHAUSTED, core,
                              error=self.KV_EXHAUSTED_MSG)
            return
        self._preempt_core = core
        try:
            # Bounded: each pass preempts one victim or gives up — at
            # most one pass per occupied slot, so an injected/persistent
            # extend failure can't spin this loop forever.
            for _ in range(len(self.slot_req)):
                victim = self._pick_victim()
                if victim is None:
                    break
                self._preempt_slot(victim, core)
                if self.slot_req[slot] is None:
                    return  # this slot WAS the victim
                if self.cache.extend(slot, need_tokens):
                    self._stalled_slots.discard(slot)
                    return
            # Nobody (left) preemptible: hold the reservation (slot +
            # pages), sit out dispatches until pages free up.
            self.slot_req[slot].trace_event("kv_stall", pages=len(pages))
            self._jrec("kv_stall", self.slot_req[slot], slot=slot,
                       free_pages=alloc.free_pages, need=need_tokens)
            self._stalled_slots.add(slot)
        finally:
            self._preempt_core = None

    # Reservation-holders may only stall this long with the whole batch
    # blocked before the youngest is failed loudly (full-deadlock escape;
    # any other slot finishing or a client cancel clears it sooner).
    STALL_BREAK_S = 5.0

    def _break_stall_deadlock(self, core: MQCore) -> None:
        """Every active slot is a stalled reservation-holder and has been
        for STALL_BREAK_S: nothing can finish, so nothing will ever free
        pages. Fail the youngest reservation with the explicit exhaustion
        error rather than wedging the runtime."""
        youngest = max(self._stalled_slots,
                       key=lambda i: self.slot_req[i].stats.enqueued_at)
        tm.SHED_TOTAL.labels(reason="kv_exhausted").inc()
        log.warning("breaking KV-reservation deadlock: failing slot %d "
                    "(req %d)", youngest, self.slot_req[youngest].req_id)
        self._finish_slot(youngest, FinishReason.KV_EXHAUSTED, core,
                          error=self.KV_EXHAUSTED_MSG)

    # -- fault-retry containment -------------------------------------------
    def _retry_requeue(self, req: Request, queue: collections.deque,
                       msg: str) -> bool:
        """Queue a fault-implicated request for ONE more attempt on this
        runtime (front of the pending queue, exponential backoff) —
        False once its budget is spent or it's already gone (caller
        errors it: poisoned inputs must not crash-loop the engine)."""
        if req.retries >= self.ecfg.step_retries or req.cancelled.is_set():
            return False
        req.retries += 1
        self.retry_count += 1
        self._tm_retries.inc()
        req._retry_at = time.monotonic() + (
            self.ecfg.retry_backoff_s * (2 ** (req.retries - 1)))
        req.trace_event("retry", error=msg[:200], n=req.retries)
        self._jrec("retry", req, n=req.retries, error=msg[:120])
        queue.appendleft(req)
        return True

    def _retry_embed(self, req: Request, msg: str) -> bool:
        return self._retry_requeue(req, self.pending_embed, msg)

    def _poison_msg(self, req: Request, msg: str) -> str:
        """Error text for a request whose retry budget is spent: the
        client (and the log) must see that retries happened and stopped
        on purpose."""
        self._jrec("poison", req, retries=req.retries, error=msg[:120])
        if req.retries:
            return (f"{msg} (request poisoned after {req.retries} "
                    f"retr{'y' if req.retries == 1 else 'ies'})")
        return msg

    # -- ragged mixed-batch scheduling -------------------------------------
    def _admit_ragged(self, core: MQCore) -> bool:
        """Admission for the ragged path: claim a reserved slot + the
        full page allocation for each pending prompt and queue it on
        `chunking` — EVERY prefill rides the span path, sized each tick
        by the token budget instead of a bucket. Prefix-cache hits pin
        their shared pages and start the span at the cached boundary.
        Returns True if anything was admitted."""
        if self.policy is not None:
            # Decision point (a): slot-admission order out of the
            # released queue (fcfs/None: untouched FIFO).
            self.policy.reorder_pending(self.pending_prefill)
        did = False
        while self.pending_prefill:
            req = self.pending_prefill[0]
            if req.cancelled.is_set():
                self.pending_prefill.popleft()
                core.mark_dropped(req.user)
                self._jrec("finish", req, reason="cancelled")
                req.finish(FinishReason.CANCELLED)
                continue
            if req._retry_at > time.monotonic():
                break  # head is backing off after a contained fault
            if req.expired():
                self.pending_prefill.popleft()
                drop_expired(req, core, self.name, journal=self.journal)
                continue
            n = len(req.prompt_tokens)
            max_prompt = min(self.ecfg.max_context - 1,
                             self.cfg.max_seq_len - 1)
            if n > max_prompt:
                self.pending_prefill.popleft()
                core.mark_dropped(req.user)
                self._jrec("finish", req, reason="error")
                req.finish(
                    FinishReason.ERROR,
                    error=f"prompt length {n} exceeds maximum {max_prompt}",
                )
                continue
            c = self.cache
            slot = self._claim_slot()
            if slot is None:
                break
            prefix_len = c.admit(slot, req.prompt_tokens)
            if prefix_len is None:
                break  # pool exhausted; retry after frees
            if prefix_len:
                req.trace_event("prefix_hit", cached_tokens=prefix_len,
                                tokens=n)
            req._chunk_pos = req._chunk_base = prefix_len
            self.pending_prefill.popleft()
            req.stats.prefill_started_at = time.monotonic()
            # The row stays OFF the shared page table until install —
            # decode steps write through cache.page_table and a reserved
            # slot must keep pointing at the trash page meanwhile.
            req._pt_row = kvc.make_page_table_row(
                c.slot_pages[slot], self.ecfg.max_pages_per_seq
            )[None, :]
            req._prefill_slot = slot
            self.reserved_slots.add(slot)
            self.chunking.append(req)
            did = True
        return did

    def _drop_chunking(self, req: Request, slot: int) -> None:
        """Remove a span-path request (cancel/overflow): release its
        pages + reservation without finishing it (caller decides)."""
        try:
            self.chunking.remove(req)
        except ValueError:
            pass
        self.cache.release(slot)
        self.reserved_slots.discard(slot)

    def step_ragged(self, core: MQCore) -> bool:
        """One ragged mixed-batch step, launched and settled at once:
        `step_ragged_launch` then `step_settle` — the pipelined loop's
        own two halves with nothing between them (tests, and
        every runtime whose next composition needs the ids on the host).
        Returns True when a mixed dispatch ran (decode slots advanced
        inside it); False leaves decode to the fused-scan path."""
        h = self.step_ragged_launch(core)
        if h is None:
            return False
        self.step_settle(h, core)
        return True

    def may_overlap(self) -> bool:
        """May a step be launched while the one before it is unsettled?
        Not where composing needs the host to have seen the ids: the
        n-gram proposer reads generated_ids, so its runtime settles every
        step in the tick that launched it. The prediction module's drafts
        and the lengths its verify spans leave stay on the device
        (`draft_ids`, `len_ids`), and the host plans on the range a
        length lies in (`_slack`)."""
        return not self.spec or self.mtp

    def settle_inflight(self, core: MQCore) -> None:
        """Bring the runtime to rest: settle the step in flight, if any.
        Called wherever the host must have seen every id, or slot and
        page state must be final — preemption and page exhaustion,
        engine calls that read slots (migration, /debug), the next
        composition of a runtime whose proposer reads the ids, shutdown."""
        h = self.inflight
        if h is not None:
            self.step_settle(h, core)

    def _settle_before_compile(self, cache: dict, key_, core: MQCore):
        """A step whose program is not compiled yet holds this thread for
        seconds: the ids of the step in flight must not wait behind that
        (their clients would), and nothing is gained by queueing behind a
        compile. Returns the step still in flight (None once settled).
        Rows composed for slots that the settle finished ride as wasted
        rows, like any late finish."""
        if self.inflight is not None and key_ not in cache:
            self.settle_inflight(core)
        return self.inflight

    def void_inflight(self) -> None:
        """Drop the step in flight without reading it (a failure path is
        about to replay its requests from what was already emitted): its
        ids are never seen, its sample never recorded. Slot state the
        launch advanced is reset by the replay (every row's slot is
        released and cleared)."""
        h, self.inflight = self.inflight, None
        for x in (h, h.prev if h is not None else None):
            if x is not None and x.state != "settled":
                x.state = "settled"
                x.sp.abandon()
        self._ahead[:] = 0
        self._slack[:] = 0
        self._tok_step = [None] * len(self._tok_step)

    def _live_rows(self) -> List[int]:
        """Slots the next step serves a decode row for: seated, not
        holding a stalled reservation, and not already known to end with
        the step in flight (by a count; for a collected scan also by
        EOS/cancel) — those are finished when that step is emitted."""
        h = self.inflight
        ending = h.ending if h is not None else ()
        return [i for i, r in enumerate(self.slot_req)
                if r is not None and i not in self._stalled_slots
                and i not in ending]

    def _retry_stalled(self, n: int) -> None:
        """Reservation-holders first: pages may have freed since they
        stalled — growth by the step's `n` positions puts them back into
        the batch."""
        for i in sorted(self._stalled_slots):
            if self.slot_req[i] is None \
                    or self.cache.extend(i, int(self.seq_lens[i]) + n):
                self._stalled_slots.discard(i)

    def _reach(self, slot: int) -> int:
        """The slot's length in the longer case of what is unsettled."""
        return int(self.seq_lens[slot]) + int(self._slack[slot])

    def _grow_or_settle(self, slot: int, n: int, core: MQCore) -> None:
        """Page headroom for a decode row's next `n` positions. When the
        pool cannot give it, the way out (preempt a victim, stall, finish
        by LENGTH) needs every slot at rest: settle the step in flight
        first, which may itself free pages, make the slot's length exact
        — or finish this very slot."""
        need = self._reach(slot) + n
        c = self.cache
        if c.extend(slot, need):
            return
        if self.inflight is not None:
            req, free = self.slot_req[slot], c.alloc.free_pages
            self.settle_inflight(core)
            if self.slot_req[slot] is not req:
                return
            exact = self._reach(slot) + n
            if (c.alloc.free_pages > free or exact < need) \
                    and c.extend(slot, exact):
                return  # the settled step's finishes freed the pages
            need = exact
        self._page_exhausted(slot, need, core)

    def step_ragged_launch(self, core: MQCore) -> Optional["StepInFlight"]:
        """LAUNCH one ragged mixed-batch step: admit pending prompts,
        then pack every live decode slot (one token each — or, with
        --spec, a (1+k)-token speculative verify span) plus as many
        prefill-span tokens as the --max-batch-tokens budget allows into
        a single dispatch — prompts of any length mix freely, and the
        only padding is the stream total rounding up to the token
        granule. Returns the step's handle (device futures + the host's
        plan of it) WITHOUT waiting for the ids; None when no mixed
        dispatch is due (decode runs as a fused scan).

        The step before may still be unsettled (`self.inflight`): all
        this composition needs from it is predictable on the host — a
        decode row advanced one position, a span by its length, a final
        span became a decode row, finishes by max_tokens/max_ctx are
        counts — except the sampled id, which the program reads from its
        `last_ids` carry, and, behind a verify span of the prediction
        module's runtime, whether the row advanced one position or two:
        the program reads its length from the `len_ids` carry, and the
        host plans for the range — `seq_lens` is the least it can be,
        `_slack` what the unsettled span may add; pages are claimed and
        the draft's budget is taken for the longer case (`_reach`). A row
        whose request turns out to have finished (EOS, a stop string, a
        cancel; a count reached by a draft the unsettled span accepted)
        rides this step anyway and its output is dropped at settle
        (`wasted_rows`). Whatever needs the ids or slot state at rest
        settles the step in flight first: page exhaustion and preemption
        here; a runtime whose proposer reads generated_ids (n-gram) has
        none in flight to begin with (`may_overlap`).

        Host state advances only after the dispatch returned: a launch
        that raises leaves the step in flight intact, is settled behind
        it, and its rows retry (`_ragged_failed`)."""
        # Step profiler: an early return or a faulted dispatch just
        # abandons the timer — no partial samples in the ring.
        _sp = stepprof.PROFILER.start("ragged", self.loop_clock)
        self._admit_ragged(core)
        if not self.chunking and not self.spec:
            return None
        if not self.chunking and not any(r is not None
                                         for r in self.slot_req):
            return None

        # Decode-row page headroom, as step_decode_dispatch does per
        # chunk (reservation-holders get their retry first). Speculating
        # slots claim headroom for their whole draft span OPTIMISTICALLY
        # — rejected drafts' pages roll back after the verify — but a
        # draft is dropped, never stalled on, when the pool can't cover
        # it: speculation is an optimization, not a page priority.
        self._retry_stalled(1)
        spec_plan: Dict[int, List[int]] = {}  # slot -> draft tokens
        self._launch_no += 1
        live = self._live_rows()
        # Draft budget: the stream must always fit every decode row at
        # one token plus whatever drafts we compose.
        spec_budget = self._ragged_budget - len(live)
        for i in live:
            r = self.slot_req[i]
            if r is None:
                continue  # finished when a step was settled, above
            drafts: List[int] = []
            if self.spec and self._spec_eligible(r):
                if r.expired():
                    # Deadline check BEFORE composing the verify span —
                    # an expired request must not burn a k-token
                    # verification (admission and the span composer make
                    # the same check).
                    self._drop_expired_slot(i, core)
                    continue
                drafts = self._propose_drafts(r, i)[:max(0, spec_budget)]
            # (Behind an unsettled verify span: pages for the LONGER case.)
            if drafts and not self.cache.extend(
                    i, self._reach(i) + 1 + len(drafts)):
                drafts = []  # no headroom to speculate: plain decode row
            if not drafts:
                self._grow_or_settle(i, 1, core)
            if self.slot_req[i] is not None and i not in self._stalled_slots:
                self.cache.publish(i)
                if drafts:
                    spec_plan[i] = drafts
                    spec_budget -= len(drafts)
                    self._claim[i] = self._reach(i) + 1 + len(drafts)
                    self._claim_no[i] = self._launch_no
                    self._jrec("speculate", r, slot=i, k=len(drafts),
                               source=self.proposer)
        if not self.chunking and not spec_plan and not self.mtp:
            return None  # nothing multi-token this tick: decode fused
        # (With the module every step is a ragged one: its cache has a row
        # for every position only if it sees every position.)

        # Compose: decode/spec rows first (every live stream advances,
        # and the ladder trim below must only ever shorten prefill
        # tails), then prefill spans in FIFO order until the budget runs
        # out. Spec rows ride as (kind="spec", slot, req, drafts, 1+d).
        # Read again: settling above may have finished or stalled slots.
        prev = self.inflight
        rows: List[tuple] = []  # (kind, slot, req, chunk_pos|drafts, span)
        for i in self._live_rows():
            d = spec_plan.get(i)
            if d:
                rows.append(("spec", i, self.slot_req[i], d, 1 + len(d)))
            else:
                rows.append(("decode", i, self.slot_req[i], 0, 1))
        n_decode = len(rows)
        fixed_tokens = sum(span for *_, span in rows)
        budget = self._ragged_budget - fixed_tokens
        now = time.monotonic()
        # Decision point (b): prefill-span packing order — which
        # in-flight prefills the remaining token budget goes to first
        # (fcfs/None: FIFO, exactly the legacy composition).
        chunk_order = (self.policy.pack_order(self.chunking)
                       if self.policy is not None else list(self.chunking))
        for req in chunk_order:
            if budget <= 0:
                break
            slot = req._prefill_slot
            if req.cancelled.is_set() or req.stream.overflowed:
                self._drop_chunking(req, slot)
                core.mark_dropped(req.user)
                self._jrec("finish", req, reason="cancelled")
                req.finish(FinishReason.CANCELLED)
                continue
            if req.expired():
                self._drop_chunking(req, slot)
                drop_expired(req, core, self.name, journal=self.journal)
                continue
            if req._retry_at > now:
                continue  # backing off after a contained fault
            span = min(len(req.prompt_tokens) - req._chunk_pos, budget)
            if span <= 0:
                continue
            rows.append(("prefill", slot, req, req._chunk_pos, span))
            budget -= span
        if len(rows) == n_decode and not spec_plan and not self.mtp:
            return None  # no span ready this tick: decode runs fused
        if not rows:
            return None

        # Pick the dispatch total from the compile ladder. Prefer the
        # largest rung we can TRIM down to (tail prefill tokens just go
        # next tick — no compute wasted); pad up to the next rung only
        # when the decode/spec rows alone nearly fill the stream and
        # leave no prefill slack to trim. Spec spans are never trimmed:
        # the lower bound covers every fixed token (decode rows + draft
        # spans), so the cut below only ever shortens prefill tails.
        T_raw = sum(span for *_, span in rows)
        lower = fixed_tokens + (1 if len(rows) > n_decode else 0)
        L = None
        for v in reversed(self._ragged_ladder):
            if v <= T_raw and v >= lower:
                L = v
                break
        if L is None:
            L = next(v for v in self._ragged_ladder if v >= T_raw)
        if L < T_raw:
            cut, acc = [], 0
            for row in rows:
                take = min(row[4], L - acc)
                if take <= 0:
                    break  # trailing spans wait for the next tick
                cut.append(row[:4] + (take,))
                acc += take
            rows = cut

        S = self.ecfg.max_slots
        W = self.ecfg.repeat_last_n
        ps = self.ecfg.page_size
        T_real = sum(span for *_, span in rows)
        T_pad = L

        # The step's host inputs are fields of ONE fresh buffer, written
        # here and never after its launch (step_pack). Padding tokens
        # belong to padding row len(rows) (trash pages, position -1 =>
        # masked everywhere) and write into the trash page; padding rows
        # hold the layout's fill values.
        lay = self.dims.ragged_layout(T_pad)
        buf = lay.new()
        (tokens, tok_seq, tok_pos, write_slots, q_start, q_len, kv_len,
         ring_len, is_first, append, is_spec, next_tok, seed_rows, slot_ids,
         pt_rows, temp, top_k, top_p, pen, pres, freq, seeds,
         rng) = lay.views(buf)
        tok_seq[:] = min(len(rows), S - 1)

        off = 0
        # Per row: whether it samples an id the host emits at all, and its
        # context once the span is in (a row from the carry: at the least).
        emits: List[bool] = []
        row_kv: List[int] = []
        # Decode and verify rows of the module's runtime: where they are,
        # the device says (`tok_pos` -2 - j marks a span's j-th token).
        carry_pos = (-2 - np.arange(self.spec_k + 1, dtype=np.int32)
                     if self.mtp else None)
        for idx, (kind, slot, req, cpos, span) in enumerate(rows):
            s = req.sampling
            slot_ids[idx] = slot
            q_start[idx] = off
            q_len[idx] = span
            temp[idx] = s.temperature
            top_k[idx] = s.top_k
            top_p[idx] = s.top_p
            pen[idx] = s.repeat_penalty
            pres[idx] = s.presence_penalty
            freq[idx] = s.frequency_penalty
            seeds[idx] = s.seed
            if kind == "decode":
                pos = int(self.seq_lens[slot])
                # The id itself, or -1 - r: "what row r of the unsettled
                # step before this one sampled" (the carry).
                tokens[off] = self.last_tokens[slot]
                tok_seq[off] = idx
                row = self.cache.page_table[slot]
                if carry_pos is not None:
                    tok_pos[off] = -2
                    kv_len[idx] = -1
                else:
                    tok_pos[off] = pos
                    write_slots[off] = row[pos // ps] * ps + pos % ps
                    kv_len[idx] = pos + 1
                append[idx] = 1  # ring_len 0: input token already rolled
                pt_rows[idx] = row
                row_kv.append(pos + 1)
                emits.append(True)
            elif kind == "spec":
                # Speculative verify span: the slot's input token plus
                # its drafts, written optimistically at positions
                # pos..pos+d (rejected positions are masked by the
                # rolled-back kv_len and overwritten later). The jit
                # computes the accepted count and advances the ring by
                # it; append always rolls in the bonus token.
                drafts = cpos  # rows tuple carries the draft list here
                pos = int(self.seq_lens[slot])
                d = len(drafts)
                tokens[off:off + d + 1] = [self.last_tokens[slot]] + drafts
                tok_seq[off:off + d + 1] = idx
                row = self.cache.page_table[slot]
                if carry_pos is not None:
                    tok_pos[off:off + d + 1] = carry_pos[:d + 1]
                    kv_len[idx] = -1
                else:
                    positions = np.arange(pos, pos + d + 1, dtype=np.int32)
                    tok_pos[off:off + d + 1] = positions
                    write_slots[off:off + d + 1] = (
                        row[positions // ps] * ps + positions % ps)
                    kv_len[idx] = pos + 1 + d
                is_spec[idx] = 1
                append[idx] = 1
                pt_rows[idx] = row
                row_kv.append(pos + 1 + d)
                emits.append(True)
            else:
                piece = req.prompt_tokens[cpos:cpos + span]
                tokens[off:off + span] = piece
                tok_seq[off:off + span] = idx
                positions = np.arange(cpos, cpos + span, dtype=np.int32)
                tok_pos[off:off + span] = positions
                row = req._pt_row[0]
                write_slots[off:off + span] = (
                    row[positions // ps] * ps + positions % ps)
                kv_len[idx] = cpos + span
                ring_len[idx] = span
                first = 1 if cpos == req._chunk_base else 0
                is_first[idx] = first
                if first and cpos > 0:
                    # Prefix-cache hit: the ring opens with the cached
                    # prefix's last W tokens, as a full prefill would.
                    prev_toks = req.prompt_tokens[max(0, cpos - W):cpos]
                    seed_rows[idx, W - len(prev_toks):] = prev_toks
                final = cpos + span >= len(req.prompt_tokens)
                append[idx] = 1 if final else 0
                if not final:  # (what the prediction module reads there)
                    next_tok[idx] = req.prompt_tokens[cpos + span]
                pt_rows[idx] = row
                row_kv.append(cpos + span)
                emits.append(final)
                req.trace_event("prefill_chunk", pos=cpos, tokens=span)
                self._jrec("chunk", req, slot=slot, pos=cpos, tokens=span,
                           cached=req._chunk_base)
            off += span

        n_prefill = sum(1 for r in rows if r[0] == "prefill")
        spec_rows = [r for r in rows if r[0] == "spec"]
        spec_tokens = sum(len(r[3]) for r in spec_rows)
        # k_cap in {0, spec_k}: one extra compile variant total when
        # speculation is live, not one per observed draft length (and with
        # the module, whose every step drafts, the one variant).
        k_cap = self.spec_k if spec_rows or self.mtp else 0
        # Batch-compose decision inputs, recorded when the step is
        # collected so the record can also carry the per-dispatch
        # accepted-token count (the speculative scoreboard reads straight
        # off batch records); a failed dispatch records them without it.
        batch_fields = dict(
            slots=[slot for _, slot, *_ in rows],
            reqs=[req.req_id for _, _, req, _, _ in rows],
            batch_size=len(rows), tokens=int(T_real),
            occupancy=round(len(rows) / max(1, S), 4),
            pending=(len(self.pending_prefill) + len(self.chunking)),
            free_pages=self.cache.alloc.free_pages,
            mode="ragged", padded_tokens=int(T_pad),
            n_decode=n_decode - len(spec_rows),
            n_prefill=n_prefill)
        if spec_rows:
            batch_fields["n_spec"] = len(spec_rows)
            batch_fields["spec_tokens"] = int(spec_tokens)
            _sp.mode = "spec_verify"
        if self.mtp:
            # drafts verified this pass, the positions the module ran over
            # to leave the next ones (every token of the stream), and the
            # rows whose positions the program took from its length carry
            _sp.note(mtp_drafts=int(spec_tokens), mtp_rows=int(T_real),
                     len_carry_rows=n_decode)
        prev = self._settle_before_compile(
            self._prefill_jits,
            ("ragged", T_pad, k_cap,
             sampling_flags(temp, top_k, top_p, pen, pres, freq)), core)
        _sp.note(T_pad=int(T_pad), k_cap=int(k_cap), tokens=int(T_real),
                 overlapped=int(prev is not None))
        _sp.mark("host_prep")
        h = StepInFlight(rows, 0, _sp, batch_fields, emits,
                         float(np.mean(row_kv)))
        h.no = self._launch_no
        rng[0] = self._next_rng()
        self._h2d = [0, 0]
        # `dispatch` holds two jobs; while a capture runs each is a child
        # span at its seam (stepprof.CHILD_SPANS), so that an idle gap of
        # the chip that lies inside one names it.
        _sp.seam("launch")
        try:
            toks, n_emit, *_ = self._took_back(
                self._dispatch_ragged(T_pad, k_cap, buf))
        except Exception as e:
            # The step before is untouched by this failure: settle it
            # (its ids are good, and the replay below folds them in),
            # then retry this one's rows.
            self.settle_inflight(core)
            self._jrec("batch", **batch_fields)
            self._ragged_failed(rows, e, core)
            return None
        h.futures(toks, n_emit)
        _sp.seam("note")
        self._queued(h)
        h.exited = self.work.note(
            _sp, [span for *_, span in rows], row_kv, emits,
            stream_len=T_pad, opened=int(is_first.sum()))
        _sp.mark("dispatch")
        _sp.park()

        # The plan becomes the host's state: what the NEXT composition
        # reads is all here, whatever ids this step samples.
        for idx, (kind, slot, req, cpos, span) in enumerate(rows):
            if self.mtp and emits[idx]:
                self._draft_ok[slot] = True  # this step leaves it there
            if kind != "prefill":
                # A verify span counts as ONE id until it is collected;
                # the drafts it may accept besides are the slot's slack.
                if self.slot_req[slot] is req:  # (not finished by a settle
                    self._launched(  # this launch itself had to make)
                        h, idx, slot, req, int(self.seq_lens[slot]) + 1)
                    self._slack[slot] += span - 1
            else:
                req._chunk_pos = cpos + span
                if emits[idx]:
                    # Final span: publish the page-table row (decode
                    # writes through it from now on) and seat the
                    # request; its first id is emitted at settle.
                    try:
                        self.chunking.remove(req)
                    except ValueError:
                        pass
                    self.reserved_slots.discard(slot)
                    self.cache.publish(slot)
                    n = len(req.prompt_tokens)
                    self._seat_slot(slot, req, n)
                    self._launched(h, idx, slot, req, n)
        self._launch_made(h, prev)
        return h

    def _launched(self, h: "StepInFlight", row: int, slot: int,
                  req: Request, seq_len: int, n: int = 1) -> None:
        """Step `h` samples, as its row `row`, `n` more ids for `slot`,
        the last of them its next input token: advance the slot to where
        they leave it."""
        self.seq_lens[slot] = seq_len
        self.last_tokens[slot] = -1 - row
        self._tok_step[slot] = h
        self._ahead[slot] += n
        if self._ends_by_count(slot, req):
            h.ending.add(slot)

    def _queued(self, h: "StepInFlight") -> None:
        """The jitted call of step `h` has just returned: its program is
        queued behind the step launched before it. Opens h's done-bracket
        (probed with `is_ready()` on its ids: non-blocking, no transfer;
        ids that never were on a device are ready)
        and notes on its sample how long the chip had had nothing queued
        — `dry_lo_ms`, `dry_hi_ms`, `dry_phase` — with what the launch
        uploaded."""
        h.sp.launched(getattr(h.toks_dev, "is_ready", None) or _host_ids_ready,
                      model=self.name,
                      h2d_transfers=self._h2d[0], h2d_bytes=self._h2d[1])

    def _launch_made(self, h: "StepInFlight",
                     prev: Optional["StepInFlight"]) -> None:
        self.inflight = h
        if prev is not None:
            # Still to be settled, behind this launch; reachable from here
            # so that a failure in between can void it too.
            h.prev, prev.prev = prev, None
            tm.STEPS_OVERLAPPED_TOTAL.labels(model=self.name).inc()

    def _ragged_failed(self, rows, e: Exception, core: MQCore) -> None:
        """Contain a failed mixed dispatch: prefill spans release their
        reservation and retry from scratch; decode rows fold their
        generated tokens into a replay prompt (preemption semantics —
        the stream resumes byte-identically) and retry too. Called with
        the runtime at rest (no step in flight): a failed launch settles
        the step before it first, a failed collect voids the one behind
        it and passes both steps' rows. A request seated by the failed
        step's final span retries from scratch like any span (its
        prompt's KV is not known to be written). A worker desync still
        propagates: diverged SPMD state must kill+reload."""
        desync = isinstance(e, WorkerDesyncError)
        log.exception("ragged mixed dispatch failed (%d rows)", len(rows))
        msg = f"ragged dispatch failed: {e}"
        for kind, slot, req, _cpos, _span in rows:
            if kind == "prefill":
                if self.slot_req[slot] is req:  # seated by its final span
                    self.cache.release(slot)
                    self._clear_slot(slot)
                elif req in self.chunking:
                    self._drop_chunking(req, slot)
                else:
                    continue  # already handled (a row of both steps)
                if desync or not self._retry_requeue(
                        req, self.pending_prefill, msg):
                    core.mark_dropped(req.user)
                    req.finish(FinishReason.ERROR,
                               error=self._poison_msg(req, msg))
            else:
                r = self.slot_req[slot]
                if r is not req:
                    continue
                # Journaled as a preempt: the slot's holder is released
                # for replay-recompute — the invariant checker (and any
                # postmortem) must see the seat change hands.
                self._jrec("preempt", r, slot=slot, why="dispatch_fault",
                           n=r.retries + 1,
                           free_pages=self.cache.alloc.free_pages)
                replay = r.prompt_tokens + r.generated_ids[r._replay_gen:]
                written = len(replay) - 1 if r.generated_ids else len(replay)
                self._release_for_replay(slot, r, replay, written)
                if desync or not self._retry_requeue(
                        r, self.pending_prefill, msg):
                    core.mark_dropped(r.user)
                    r.finish(FinishReason.ERROR,
                             error=self._poison_msg(r, msg))
        if desync:
            raise e

    def step_decode(self, core: MQCore, k_steps: int = 1) -> int:
        """Advance all active slots by up to k_steps tokens, launched and
        settled at once. Returns #tokens."""
        h = self.step_decode_dispatch(core, k_steps)
        if h is None:
            return 0
        return self.step_settle(h, core)

    def step_decode_dispatch(self, core: MQCore, k_steps: int = 1):
        """LAUNCH one fused decode scan of k_steps passes WITHOUT
        blocking on the result: the returned handle holds device arrays
        that are still computing. The engine loop launches every
        runtime's step before it settles any (`step_settle`), so dp
        replicas' scans — which live on disjoint device sets — execute
        concurrently instead of serializing on the host thread. A ragged
        step may still be unsettled when this is called (its rows' input
        tokens then come from the `last_ids` carry, as in
        `step_ragged_launch`); the loop never launches anything behind
        an uncollected SCAN — a second scan queued behind a running one
        would make an arrival wait for both. Returns None when nothing
        is active."""
        if not any(r is not None for r in self.slot_req):
            return None
        # Step profiler: the timer is parked between the step's halves
        # and rides the handle. Early returns and faulted dispatches
        # abandon it.
        _sp = stepprof.PROFILER.start("decode", self.loop_clock)
        self._retry_stalled(k_steps)
        # Ensure page headroom for k_steps new tokens per active slot.
        for i in self._live_rows():
            if self.slot_req[i] is None:
                continue  # finished when a step was settled, below
            # Never a silent LENGTH: preempt-with-recompute, stall on a
            # reservation, or error explicitly (kv_exhausted).
            self._grow_or_settle(i, k_steps, core)
            if self.slot_req[i] is not None and i not in self._stalled_slots:
                self.cache.publish(i)
        prev = self._settle_before_compile(
            self._decode_jits,
            (k_steps, sampling_flags(self.temp, self.top_k, self.top_p,
                                     self.rep_pen, self.pres_pen,
                                     self.freq_pen)), core)
        active = self._live_rows()
        if not active:
            # Whole batch is stalled reservations: nothing can finish, so
            # nothing will free pages — after a grace window, break the
            # deadlock loudly instead of wedging (any other in-flight
            # work, e.g. a chunked prefill, can still unblock it first).
            if self._stalled_slots and not self.chunking and prev is None:
                now = time.monotonic()
                if self._stall_since is None:
                    self._stall_since = now
                elif now - self._stall_since > self.STALL_BREAK_S:
                    self._break_stall_deadlock(core)
                    self._stall_since = None
            return None
        self._stall_since = None

        # The scan's host inputs, copied into ONE fresh buffer that is
        # never written after its launch (step_pack): the live per-slot
        # arrays advance below, while the program may still be reading
        # what it was handed.
        lay = self.dims.decode_layout()
        buf = lay.new()
        (tokens, positions, active_mask, pt, temp, top_k, top_p, pen, pres,
         freq, seeds, rng) = lay.views(buf)
        tokens[:] = self.last_tokens
        positions[:] = self.seq_lens  # position of the incoming token
        active_mask[active] = 1
        pt[:] = self.cache.page_table
        temp[:], top_k[:], top_p[:] = self.temp, self.top_k, self.top_p
        pen[:], pres[:], freq[:] = self.rep_pen, self.pres_pen, self.freq_pen
        seeds[:], rng[0] = self.seeds, self._next_rng()
        rows = [("decode", i, self.slot_req[i], 0, k_steps) for i in active]
        # `tokens` as planned; the sample takes what was really emitted.
        _sp.note(T_pad=0, k_cap=int(k_steps),
                 tokens=len(active) * int(k_steps),
                 overlapped=int(prev is not None))
        _sp.mark("host_prep")
        # Mean context BEFORE the step advances seq_lens: feeds the
        # attention term of the per-step FLOPs model.
        h = StepInFlight(rows, k_steps, _sp, None, [True] * len(active),
                         float(np.mean(self.seq_lens[active])))
        self._h2d = [0, 0]
        _sp.seam("launch")
        toks, *_ = self._took_back(self._dispatch_decode(k_steps, buf))
        h.futures(toks)
        _sp.seam("note")
        self._queued(h)
        self.work.note(_sp, [int(k_steps)] * len(active),
                       (self.seq_lens[active] + int(k_steps)).tolist(),
                       scan=True)
        _sp.mark("dispatch")
        _sp.park()
        for i in active:
            self._launched(h, i, i, self.slot_req[i],
                           int(self.seq_lens[i]) + k_steps, k_steps)
        self._launch_made(h, prev)
        return h

    def step_collect(self, h: "StepInFlight", core: MQCore) -> None:
        """First half of a settle: block until the step's ids are on the
        host and do only what the NEXT composition needs — each slot's
        next input token, and for a fused scan the rows that ended
        inside it (EOS, a cancel; finishes by count were known at
        launch). Finishing those slots, and everything else a token
        costs, is `step_settle`'s — run behind the next launch. A device
        error in the step surfaces HERE (np.asarray materializes the
        async result): a ragged step's rows — and those of a step
        already launched behind it, which is voided — retry through
        `_ragged_failed`; a scan's error propagates to the loop, which
        fails the runtime."""
        if h.state != "launched":
            return
        _sp = h.sp
        _sp.resume("collect")
        try:
            self._fault("collect")
            toks = np.asarray(h.toks_dev)  # blocks until the step is done
            if h.n_emit_dev is not None:
                h.n_emit = np.asarray(h.n_emit_dev)
        except Exception as e:
            if h.k_steps:
                raise
            rows = list(h.rows)
            behind = self.inflight
            if behind is not None and behind is not h:
                rows += behind.rows  # launched on ids that never came
            self._jrec("batch", **h.fields)
            h.state = "settled"
            _sp.abandon()
            self.void_inflight()
            self._ragged_failed(rows, e, core)
            return
        # When the step left the device: the first probe that saw its
        # ids ready — this read's return at the latest, and earlier where
        # the host came back late (the time in between is the host's
        # lateness, not the step's time on the device).
        t_done = _sp.collected()
        if h.fields is not None:  # (a scan has its sample alone)
            h.fields["collect_ready"] = _sp.fields["collect_ready"]
        h.toks, h.toks_dev, h.n_emit_dev = toks, None, None
        h.state = "collected"
        # The step's time on the device: from its launch, or from when
        # the step before it was done if it queued behind that one.
        h.dt = max(0.0, t_done - max(h.t_launch, self._last_done))
        self._last_done = t_done
        S = len(self.slot_req)
        # Per row (a scan: per slot): its last id, and whether EOS is
        # among its ids.
        if h.k_steps:
            last = toks[-1].tolist()
            eos = (toks[:, :S] == self.tokenizer.eos_id).any(axis=0).tolist()
        else:
            last = toks[:, 0].tolist()
            eos = [t == self.tokenizer.eos_id for t in last]
        for idx, (kind, slot, req, _cpos, span) in enumerate(h.rows):
            if self.slot_req[slot] is not req or not h.emits[idx]:
                continue
            if kind == "spec":
                # How many ids it emitted is known only now: the launch
                # counted one, the accepted drafts come off the slack.
                n = int(h.n_emit[idx])  # accepted + 1
                self.seq_lens[slot] += n - 1
                self._ahead[slot] += n - 1
                self._slack[slot] -= span - 1
                if self._tok_step[slot] is h:
                    self.last_tokens[slot] = int(toks[idx, n - 1])
                    self._tok_step[slot] = None
                continue
            i = slot if h.k_steps else idx
            # Ended by its ids or a cancel (by a count: known at launch).
            # Of use when nothing was launched behind this step yet — a
            # fused scan always, a ragged step when the loop holds back.
            if eos[i] or req.cancelled.is_set() or req.stream.overflowed:
                h.ending.add(slot)
            if self._tok_step[slot] is h:  # no later step samples past it
                self.last_tokens[slot] = last[i]
                self._tok_step[slot] = None
        if self.cfg.num_experts:
            self.work.note_moe_load(
                _sp, toks[:, S:] if h.k_steps else toks[S:, :1].T)
        _sp.park()

    def step_settle(self, h: "StepInFlight", core: MQCore) -> int:
        """SETTLE a launched step: collect it if that is still to do,
        then everything its tokens cost — detokenise, stop strings,
        stream pushes, trace events, finishing slots and freeing their
        pages, histograms, the step's sample. The pipelined loop runs
        this behind the NEXT step's launch, so the chip works meanwhile;
        called right after the launch it is the synchronous step. A row
        whose request finished before its output was emitted (a stop
        string or EOS seen one step late, a cancel between launch and
        settle) is dropped and counted (`wasted_rows`): extra work,
        never a token more or less. Returns #tokens emitted."""
        self.step_collect(h, core)
        if h.state != "collected":
            return 0
        h.state = "settled"
        if self.inflight is h:
            self.inflight = None
        _sp = h.sp
        _sp.resume("detok")
        rows, toks, K = h.rows, h.toks, h.k_steps
        n_decode = sum(1 for r in rows if r[0] != "prefill")
        n_prefill = len(rows) - n_decode
        if h.fields is not None:
            if "n_spec" in h.fields:
                h.fields["spec_accepted"] = int(sum(
                    int(h.n_emit[idx]) - 1
                    for idx, r in enumerate(rows) if r[0] == "spec"))
            self._jrec("batch", **h.fields)
            waste = 1.0 - h.fields["tokens"] / max(
                1, h.fields["padded_tokens"])
            self._tm_padding.set(round(waste, 4))
        dt_ms = h.dt * 1e3 / max(1, K)
        if n_prefill:
            self.prefill_latency_ms = dt_ms
            self._tm_prefill.observe(dt_ms)
        if n_decode:
            # TPOT: every decode row gains one token per pass, so the
            # pass time IS time-per-output-token for each stream in it.
            self.step_latency_ms = dt_ms
            self.step_window.append(dt_ms)
            self._tm_step.observe(dt_ms)
            self._tm_tpot.observe(dt_ms)
            if self.slo is not None:
                # One SLO observation per emitted token, not per step:
                # the objective is per-token latency and the budget math
                # needs event counts that match what users experienced.
                self.slo.record("tpot", dt_ms, n=n_decode * max(1, K))

        emitted = wasted = 0
        spec_accepted = spec_pages = 0
        self._stream_items = 0
        # Row-major: a row's tokens of this step — 1, `n_emit` of a
        # speculated row, K of a scan — leave as one stream item, and the
        # consumers hear of the whole step once (`wake_batch`).
        ids = (toks[:, :len(self.slot_req)].T if K else toks).tolist()
        with wake_batch() as woken:
            for idx, (kind, slot, req, cpos, span) in enumerate(rows):
                if not idx & 15:
                    _sp.probe()  # the emit loop is long between marks
                if not h.emits[idx]:
                    continue  # a span inside a prompt samples nothing
                if self.slot_req[slot] is not req:
                    wasted += 1  # finished/cancelled between launch & emit
                    continue
                n = int(h.n_emit[idx]) if kind == "spec" else max(1, K)
                # The context its first id leaves: every step before this
                # one is settled, so the slot's length less what is still
                # unsettled (this row and any launched behind it) is exact.
                ctx0 = int(self.seq_lens[slot]) - int(self._ahead[slot]) + 1
                self._ahead[slot] -= n
                taken = self._emit_row(
                    slot, ids[slot] if K else ids[idx][:n], core, ctx0)
                self.tokens_generated += taken
                if kind != "prefill":  # decode-row tokens, as ever
                    emitted += taken
                if kind == "spec":
                    proposed, accepted = span - 1, n - 1
                    spec_accepted += accepted
                    self._spec_outcome(req, proposed, accepted)
                    self._jrec("spec_verify", req, slot=slot,
                               proposed=proposed, accepted=accepted,
                               rolled_back=proposed - accepted,
                               source=self.proposer)
                    if proposed > accepted and self.slot_req[slot] is req:
                        # Rejected drafts wrote KV past the accepted
                        # context: release their page claim (the finish
                        # paths already freed everything when the stream
                        # ended mid-emission) — down to the next id's own
                        # position, or to what a LATER composition claimed
                        # for the slot's next verify span (launched behind
                        # this step, or being composed as a launch settles
                        # it): the longer case, which the composition after
                        # it claims again whatever this step accepted.
                        keep = (int(self._claim[slot])
                                if self._claim_no[slot] > h.no
                                else self._reach(slot) + 1)
                        self.spec_rollbacks += 1
                        spec_pages += self.cache.rollback(
                            slot, req, max(ctx0 - 1 + span, keep), keep,
                            source=self.proposer)
        _sp.note(stream_items=self._stream_items,
                 stream_wakeups=woken.wakeups)
        if self.mtp:
            _sp.note(mtp_accepted=spec_accepted,
                     spec_rollback_pages=spec_pages)

        # Per-step engine telemetry: occupancy, KV-page pressure, MFU.
        self._tm_tokens.inc(emitted)
        if wasted:
            tm.STEP_WASTED_ROWS_TOTAL.labels(model=self.name).inc(wasted)
        S = self.ecfg.max_slots
        self._tm_occupancy.set(
            sum(r is not None for r in self.slot_req) / max(1, S))
        alloc = self.cache.alloc
        self._tm_pages.set(alloc.used_pages)
        self._tm_page_util.set(
            alloc.used_pages / max(1, alloc.num_pages - 1))
        # MFU over EVERY real token the step processed (prefill spans do
        # the same per-token matmuls as decode rows). _orig_cfg, not
        # self.cfg: replicated-group KV inflates kv_dim as a layout
        # trick, not real FLOPs.
        real = h.fields["tokens"] if h.fields is not None else emitted
        self.mfu = mfu_model.mfu(self._orig_cfg, int(real), h.dt,
                                 self.peak_flops, n_chips=self.n_chips,
                                 context_len=h.mean_ctx, exited=h.exited)
        self._tm_mfu.set(self.mfu)
        _sp.mark("detok")
        extra = ({"tokens": emitted,
                  "padded_tokens": int(K) * S} if K else
                 {"padded_tokens": h.fields["padded_tokens"]})
        _sp.finish(n_prefill=n_prefill,
                   n_decode=n_decode - sum(r[0] == "spec" for r in rows),
                   wasted_rows=int(wasted),
                   compiled=_sp_take_compiled(self), **extra)
        return emitted

    def check_cancellations(self, core: MQCore) -> None:
        """Reap cancelled requests and requests whose user was blocked after
        admission. The reference re-checks the blocklist at dispatch time
        (dispatcher.rs:503-512); with continuous batching a request is
        'dispatched' for its whole lifetime, so the late re-check covers the
        slots and prefill queues — version-gated so the hot loop pays no FFI
        cost unless the blocklist actually changed. Blocked ⇒ cancel: the
        existing cancel paths (slot finish, chunked-prefill abort,
        pending-prefill pop) do the page reclaim and dropped accounting."""
        self._block_ver = sweep_blocked(core, self._held_requests, self._block_ver)
        for i, req in enumerate(self.slot_req):
            if req is not None and req.cancelled.is_set():
                self._finish_slot(i, FinishReason.CANCELLED, core)

    def _held_requests(self):
        return (
            [r for r in self.slot_req if r is not None]
            + list(self.pending_prefill)
            + list(self.pending_embed)
            + list(self.chunking)
        )

    # -- embeddings on a generative model ----------------------------------
    def _get_embed_jit(self, batch: int, bucket: int):
        key = (batch, bucket)
        _sp_compile_evict(self, self._embed_jits, key)
        if key not in self._embed_jits:
            cfg = self.cfg

            def mq_embed(params, tokens, seq_lens):
                return llama.forward_embed(params, cfg, tokens, seq_lens)

            _sp_note_compile(self, "embed", key, self._embed_jits,
                             jax.jit(mq_embed))
        return self._embed_jits[key]

    # Dispatch seam: the SPMD subclass broadcasts (OP_EMBED, payload) to
    # worker hosts before issuing the same jit call.
    def _dispatch_embed(self, B, bucket, tokens, lens):
        self._fault("embed")
        return self._get_embed_jit(B, bucket)(
            self.params, jnp.asarray(tokens), jnp.asarray(lens)
        )

    def step_embed(self, core: MQCore) -> bool:
        """Serve pending embed requests — stateless forwards (no KV
        write), so no generated-token position is reserved from the
        length budget and a failure never needs to touch decode state.
        Returns True if a batch ran."""
        max_len = min(self.ecfg.max_context, self.cfg.max_seq_len)
        try:
            return serve_embed_batch(self, core, self.pending_embed,
                                     max_len, self._dispatch_embed)
        except WorkerDesyncError:
            raise  # diverged device state: engine loop must kill + reload
        except Exception:
            # Local embed failure (the batch is already errored by the
            # helper): keep the runtime — its decode slots are healthy,
            # and a genuinely dead device will fail the next decode
            # dispatch, which DOES kill + rebuild.
            log.exception("embed forward failed on %s", self.name)
            return True

    def stats(self) -> dict:
        def pctl(window, q):
            if not window:
                return 0.0
            xs = sorted(window)
            return round(xs[min(len(xs) - 1, int(q * len(xs)))], 3)

        return {
            "model": self.name,
            "active_slots": self.active_count(),
            "max_slots": self.ecfg.max_slots,
            "pending_prefill": len(self.pending_prefill),
            "pages_used": self.cache.alloc.used_pages,
            "pages_total": self.cache.alloc.num_pages - 1,
            "step_latency_ms": round(self.step_latency_ms, 3),
            "step_p50_ms": pctl(self.step_window, 0.50),
            "step_p99_ms": pctl(self.step_window, 0.99),
            "prefill_latency_ms": round(self.prefill_latency_ms, 3),
            "ttft_p50_ms": pctl(self.ttft_window, 0.50),
            "ttft_p99_ms": pctl(self.ttft_window, 0.99),
            "tokens_generated": self.tokens_generated,
            "preemptions": self.preempt_count,
            "retries": self.retry_count,
            "stalled_slots": len(self._stalled_slots),
            "mfu": round(self.mfu, 4),
            "param_bytes": self.param_bytes,
            "kv_bytes": self.kv_bytes,
            # the per-slot state beside the pool (0 for a model without)
            **self.state_bytes,
            "weights_dtype": self.weights_dtype,
            "kv_dtype": self.kv_dtype,
            "attn_impl": self.attn_impl,
            "attn_inner": self.attn_inner,
            "weight_stacks_relaid": self.weight_stacks_relaid,
            "devices": self.devices,
            # None = caching disabled (the TUI renders "cache n/a").
            "prefix_cache": (self.cache.prefix_cache.stats()
                             if self.cache.prefix_cache is not None
                             else None),
            # None = speculation disabled on this runtime.
            "spec": ({
                "proposed": self.spec_proposed,
                "accepted": self.spec_accepted,
                "accept_rate": round(
                    self.spec_accepted / self.spec_proposed, 4)
                if self.spec_proposed else 0.0,
                "rollbacks": self.spec_rollbacks,
                "proposer": self.proposer,
                "throttled_users": len(self._spec_throttled),
            } if self.spec else None),
        }


class EncoderRuntime:

    SERVES = ("embed",)
    """Embedding model runtime: batch encode, no KV cache."""

    slo = None  # encoders emit no tokens; attached but never recorded into
    fault_plan = None  # attached by the engine like ModelRuntime's
    on_preempt = None  # encoders hold no KV pages; attached but unused
    journal = None  # decision journal (the SPMD broadcast seam reads it)
    loop_clock = None  # the engine thread's stepprof.LoopClock

    def __init__(self, name, model_cfg, engine_cfg, mesh=None,
                 checkpoint_path=None, dtype=jnp.bfloat16):
        self.name = name
        self.cfg = model_cfg
        self.ecfg = engine_cfg
        self.mesh = mesh
        self._failed = False
        self.tokenizer = load_tokenizer(checkpoint_path)
        with stepprof.PROFILER.phase("weights", name):
            params = weights.load_params(
                model_cfg, checkpoint_path, seed=engine_cfg.seed, dtype=dtype,
                weights_dtype=engine_cfg.weights_dtype)
        if mesh is not None:
            with stepprof.PROFILER.phase("place", name):
                params = shard_params(params, mesh)
        self.params = params
        self.pending: collections.deque = collections.deque()
        self._block_ver = -1  # force one startup sweep (disk-loaded blocklist)
        self._jits: Dict[Tuple[int, int], callable] = {}
        self.param_bytes = sum(
            x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(params)
        )
        tm.HBM_WEIGHT_BYTES.labels(model=name).set(self.param_bytes)
        tm.HBM_KV_BYTES.labels(model=name).set(0)
        self.kv_bytes = 0
        self.tokens_generated = 0
        self.step_latency_ms = 0.0

    def has_capacity(self, kind: Optional[str] = None) -> bool:
        return not self._failed and len(self.pending) < 4 * self.ecfg.max_slots

    def has_work(self) -> bool:
        return bool(self.pending)

    def active_count(self) -> int:
        return 0

    def submit(self, req: Request) -> bool:
        self.pending.append(req)
        return True

    def check_cancellations(self, core: MQCore) -> None:
        # Late blocked re-check (see ModelRuntime.check_cancellations).
        self._block_ver = sweep_blocked(core, lambda: self.pending,
                                        self._block_ver)

    def _get_jit(self, batch: int, bucket: int):
        key = (batch, bucket)
        _sp_compile_evict(self, self._jits, key)
        if key not in self._jits:
            cfg = self.cfg

            def mq_encode(params, tokens, seq_lens):
                return llama.forward_encoder(params, cfg, tokens, seq_lens)

            _sp_note_compile(self, "embed", key, self._jits,
                             jax.jit(mq_encode))
        return self._jits[key]

    # Dispatch seam: the SPMD subclass broadcasts (OP_ENCODE, payload) to
    # worker hosts before issuing the same jit call.
    def _dispatch_encode(self, B, bucket, tokens, lens):
        if self.fault_plan is not None and not getattr(self, "_spmd", False):
            # (multi-host: the check runs pre-broadcast in the SPMD seam)
            self.fault_plan.check("encode")
        return self._get_jit(B, bucket)(
            self.params, jnp.asarray(tokens), jnp.asarray(lens)
        )

    def step(self, core: MQCore) -> None:
        """Encode pending requests in padded batches (shared scheme:
        serve_embed_batch). A dispatch failure errors the batch, then
        propagates so the engine loop kills + rebuilds this runtime —
        an encoder has no decode path that could prove the device dead."""
        serve_embed_batch(self, core, self.pending, self.cfg.max_seq_len,
                          self._dispatch_encode)

    def stats(self) -> dict:
        return {
            "model": self.name,
            "active_slots": 0,
            "max_slots": 0,
            "pending_prefill": len(self.pending),
            "pages_used": 0,
            "pages_total": 0,
            "step_latency_ms": round(self.step_latency_ms, 3),
            "prefill_latency_ms": 0.0,
            "tokens_generated": self.tokens_generated,
            "preemptions": 0,  # encoders hold no decode slots to preempt
            "retries": 0,
            "stalled_slots": 0,
            "mfu": 0.0,  # encoders don't publish decode-step MFU
            "param_bytes": self.param_bytes,
            "kv_bytes": self.kv_bytes,
            "weights_dtype": self.ecfg.weights_dtype,
            "kv_dtype": "bfloat16",  # encoders hold no KV pool
            "attn_impl": "jnp",  # bidirectional attention has no kernel
            "attn_inner": None,
            "prefix_cache": None,  # encoders hold no KV to share
            "spec": None,  # encoders decode nothing to speculate on
        }


def build_model_runtimes(name, cfg, engine_cfg, mesh, dtype, checkpoint_path,
                         model_cls, encoder_cls):
    """Replica list for one model — THE construction path, shared by
    TPUEngine.load_model and the SPMD worker (engine/spmd.py). Under SPMD
    every host must build byte-identical computations, so there is exactly
    one copy of the dp-submesh / preloaded-params / encoder branching.

    dp generative replicas each land on their own slice of the mesh's
    data axis (a [1, ep, tp] submesh): N param copies + KV pools serving
    concurrently — the reference's "one request per backend, N backends"
    scale-out story with backends = mesh slices. The checkpoint is
    read/parsed once and shared host-side across replicas."""
    with stepprof.PROFILER.phase("alloc", name):  # the start-up ledger's:
        # all of it but what the constructors name `weights` and `place`
        if cfg.is_encoder:
            return [encoder_cls(name, cfg, engine_cfg, mesh=mesh,
                                checkpoint_path=checkpoint_path, dtype=dtype)]
        if engine_cfg.dp > 1 and mesh is not None:
            with stepprof.PROFILER.phase("weights", name):
                host_params = weights.load_params(
                    cfg, checkpoint_path, seed=engine_cfg.seed, dtype=dtype,
                    weights_dtype=engine_cfg.weights_dtype,
                )
            reps = [
                model_cls(name, cfg, engine_cfg,
                          mesh=replica_submesh(mesh, r),
                          checkpoint_path=checkpoint_path, dtype=dtype,
                          preloaded_params=host_params)
                for r in range(engine_cfg.dp)
            ]
            del host_params  # replicas hold their own device copies
            return reps
        return [model_cls(name, cfg, engine_cfg, mesh=mesh,
                          checkpoint_path=checkpoint_path, dtype=dtype)]


def merge_prefix_cache_stats(stats_list) -> Optional[dict]:
    """Sum per-replica prefix-cache stat dicts (None entries = replicas
    without a cache). Returns None when no replica caches."""
    live = [s for s in stats_list if s]
    if not live:
        return None
    keys = ("hits", "misses", "evictions", "tokens_saved", "cached_pages",
            "evictable_pages", "pinned_pages")
    merged = {k: sum(s.get(k, 0) for s in live) for k in keys}
    total = merged["hits"] + merged["misses"]
    merged["hit_ratio"] = round(merged["hits"] / total, 4) if total else 0.0
    return merged


class ReplicaSet:
    """Data parallelism as replica serving: dp independent ModelRuntimes for
    one model, each TP-sharded over its own slice of the mesh's data axis,
    with least-loaded placement and round-robin rotation among ties — the
    TPU analogue of the reference's least-connections backend pick
    (dispatcher.rs:475-487). Each replica holds its own params copy, KV
    pool, and jits, so replicas step independently (and their dispatches
    overlap on disjoint device sets)."""

    def __init__(self, replicas: List[ModelRuntime]):
        assert replicas
        self.replicas = list(replicas)
        self.name = self.replicas[0].name
        self.cfg = self.replicas[0].cfg
        self.ecfg = self.replicas[0].ecfg
        self._last_idx = 0  # rotation cursor (dispatcher.rs last_backend_idx)

    # -- placement ---------------------------------------------------------
    @staticmethod
    def _load(rt: ModelRuntime) -> int:
        return (rt.active_count() + len(rt.pending_prefill)
                + len(getattr(rt, "pending_embed", ()))
                + len(rt.chunking))

    def has_capacity(self, kind: Optional[str] = None) -> bool:
        return any(r.has_capacity(kind) for r in self.replicas)

    def submit(self, req: Request) -> bool:
        """Least-loaded replica wins; ties rotate after the previous pick.
        Returns False when NO replica has capacity (the admission gate
        raced): the caller returns the request to the native queue — the
        reference's wait-in-queue semantics (dispatcher.rs:467-473) —
        instead of parking it on a full replica where it would jump the
        fair-share order."""
        eligible = [i for i, r in enumerate(self.replicas)
                    if r.has_capacity(req.kind)]
        if not eligible:
            return False
        best = min(self._load(self.replicas[i]) for i in eligible)
        ties = {i for i in eligible if self._load(self.replicas[i]) == best}
        n = len(self.replicas)
        for off in range(1, n + 1):
            i = (self._last_idx + off) % n
            if i in ties:
                self._last_idx = i
                return self.replicas[i].submit(req)
        return False

    def force_submit(self, req: Request) -> None:
        """Place even with zero capacity (least-loaded live replica): for
        requests the native queue can't hold back (empty model name)."""
        live = ([i for i, r in enumerate(self.replicas) if not r._failed]
                or list(range(len(self.replicas))))
        best = min(live, key=lambda i: self._load(self.replicas[i]))
        self.replicas[best].submit(req)

    # -- aggregate runtime surface (registry / health / TUI / app) ---------
    @property
    def tokenizer(self):
        return self.replicas[0].tokenizer

    @property
    def param_bytes(self) -> int:
        return sum(r.param_bytes for r in self.replicas)

    @property
    def kv_bytes(self) -> int:
        return sum(r.kv_bytes for r in self.replicas)

    @property
    def tokens_generated(self) -> int:
        return sum(r.tokens_generated for r in self.replicas)

    def has_work(self) -> bool:
        return any(r.has_work() for r in self.replicas)

    def active_count(self) -> int:
        return sum(r.active_count() for r in self.replicas)

    def check_cancellations(self, core: MQCore) -> None:
        for r in self.replicas:
            r.check_cancellations(core)

    def stats(self) -> dict:
        per = [r.stats() for r in self.replicas]
        agg = dict(per[0])
        for key in ("active_slots", "max_slots", "pending_prefill",
                    "pages_used", "pages_total", "tokens_generated",
                    "preemptions", "retries", "stalled_slots",
                    "param_bytes", "kv_bytes"):
            agg[key] = sum(p[key] for p in per)
        for key in ("step_latency_ms", "step_p50_ms", "step_p99_ms",
                    "prefill_latency_ms", "ttft_p50_ms", "ttft_p99_ms",
                    "mfu"):
            agg[key] = max(p.get(key, 0.0) for p in per)
        agg["prefix_cache"] = merge_prefix_cache_stats(
            [p.get("prefix_cache") for p in per])
        agg["devices"] = sorted({d for p in per for d in p["devices"]})
        agg["replicas"] = len(per)
        return agg


class TPUEngine:
    """Engine front: owns the scheduler core, model runtimes, and the loop."""

    # Runtime classes; SPMD deployments swap in SPMD variants so every
    # device dispatch is broadcast to worker hosts first.
    runtime_class = ModelRuntime
    encoder_runtime_class = EncoderRuntime

    def __init__(
        self,
        engine_cfg: EngineConfig,
        models: Optional[Dict[str, Optional[str]]] = None,  # name -> ckpt path
        blocklist_path: Optional[str] = "blocked_items.json",
        mesh=None,
        fairness: Fairness = Fairness.REQUESTS,
        dtype=None,
    ):
        self.ecfg = engine_cfg
        # Scheduling policy (engine/scheduler.py): built BEFORE any
        # device/model work so an unknown --scheduler fails loudly at
        # startup. fcfs (the default) is bit-identical to the
        # pre-extraction engine; srpt/edf reorder admission, prefill
        # packing, and victim picks within what fairness releases.
        self.policy = make_policy(engine_cfg)
        self.core = MQCore(blocklist_path)
        self.core.set_fairness(fairness)
        if mesh is None and (engine_cfg.dp, engine_cfg.tp,
                             engine_cfg.ep) != (1, 1, 1):
            mesh = make_mesh(dp=engine_cfg.dp, tp=engine_cfg.tp,
                             ep=engine_cfg.ep)
        self.mesh = mesh
        self.dtype = dtype if dtype is not None else jnp.dtype(engine_cfg.dtype)
        self.runtimes: Dict[str, object] = {}
        self.pending: Dict[int, Request] = {}
        # Load-shed accounting by reason (mirrors ollamamq_shed_total;
        # kept engine-side too so the TUI chip needs no registry walk).
        self.shed_counts: Dict[str, int] = {}
        self._engine_retries = 0  # retries issued by _retry_or_error
        self._orphans: List[tuple] = []
        self._expired_orphans: Dict[int, float] = {}
        # In-flight KV migration exports: rid -> (runtime, handle). A
        # detached slot parks here between migrate_export and the
        # commit/abort that resolves the two-phase handoff.
        self._migrations: Dict[int, tuple] = {}
        self._last_stuck_log = 0.0
        self._pending_lock = threading.Lock()
        self._cond = threading.Condition()
        self._running = False
        self._thread: Optional[threading.Thread] = None
        # Deferred engine-thread calls (call_on_loop): work that must run in
        # order with device dispatches — e.g. SPMD control broadcasts, which
        # would race the dispatch broadcast stream from any other thread.
        self._engine_calls: collections.deque = collections.deque()
        self.health = None
        self.started_at = time.time()
        # Request-lifecycle tracing: bounded ring of finished traces plus
        # the in-flight table, exported at GET /debug/trace.
        self.tracer = Tracer(capacity=engine_cfg.trace_ring)
        # This engine thread's own clock over the time between steps
        # (telemetry/stepprof.py LOOP_PHASES). Per engine, never the
        # process-wide profiler's: an in-process fleet runs several
        # engine threads into one sample ring, and `thread` on a sample
        # says which.
        self.loop_clock = stepprof.LoopClock(
            stepprof.PROFILER, f"engine-{next(_ENGINE_IDS)}")
        # Alerting + SLO burn-rate engine: the one alert table /health,
        # /metrics, /debug/bundle, and the TUI alerts panel all read.
        # Objectives are opt-in (--slo-ttft-ms / --slo-tpot-ms); the
        # alert table exists regardless — the stall watchdog uses it too.
        self.alerts = AlertManager()
        self.slo = SLOEngine(self.alerts,
                             ttft_ms=engine_cfg.slo_ttft_ms or None,
                             tpot_ms=engine_cfg.slo_tpot_ms or None,
                             target=engine_cfg.slo_target)
        # Flight recorder: every scheduler decision (admit/shed/batch/
        # preempt/...) as a typed record in a bounded ring, tailed at
        # GET /debug/journal and optionally spilled to --journal-file.
        self.journal = Journal(
            capacity=engine_cfg.journal_ring,
            path=engine_cfg.journal_file,
            rotate_bytes=int(engine_cfg.journal_rotate_mb * 1e6),
            keep=engine_cfg.journal_keep,
            sample=getattr(engine_cfg, "journal_sample", 1.0),
            meta={"model": engine_cfg.model,
                  "max_slots": engine_cfg.max_slots,
                  "num_pages": engine_cfg.num_pages})
        # Engine-loop liveness tick for the stall watchdog: bumped at the
        # top of every _loop_once, so a dispatch wedged inside a step
        # leaves it stale while work is pending.
        self.last_tick_at = time.monotonic()
        # Graceful-shutdown gate: quiesce() flips it and every later
        # enqueue sheds honestly (503) while in-flight streams drain.
        self.accepting = True
        # Deterministic fault injection: a plan path (--fault-plan) loads
        # here — fail-fast on a malformed file — or tests hand an already
        # built FaultPlan instance via EngineConfig.fault_plan.
        self.fault_plan = None
        if engine_cfg.fault_plan:
            from ollamamq_tpu.testing.faults import FaultPlan

            self.fault_plan = (
                FaultPlan.load(engine_cfg.fault_plan)
                if isinstance(engine_cfg.fault_plan, str)
                else engine_cfg.fault_plan)
        # Crash durability (--wal-dir): admission WAL + cold-restart
        # recovery + the resumable-stream registry. None = no overhead.
        self.durability = None
        if getattr(engine_cfg, "wal_dir", None):
            from ollamamq_tpu.durability import DurabilityManager

            self.durability = DurabilityManager(
                engine_cfg, journal=self.journal, alerts=self.alerts,
                fault_plan=self.fault_plan)
        # CPU-gloo can't run two cross-host computations concurrently: XLA's
        # CPU thread pool executes them in nondeterministic order and their
        # collective ops interleave differently per process on the shared
        # TCP pairs (observed as gloo size-mismatch aborts). On TPU each
        # replica's collectives ride its own disjoint ICI clique, so the
        # dispatch/collect overlap is safe — serialize only multi-host CPU.
        self._serialize_multihost = (
            jax.process_count() > 1 and jax.default_backend() == "cpu"
        )
        # Failure recovery: runtimes marked failed are rebuilt (weights
        # reloaded) on this cadence instead of requiring a process restart.
        self._model_sources: Dict[str, Optional[str]] = {}
        self._failed_runtimes: List[object] = []
        self._recovering: set = set()  # id(rt) with a rebuild in flight
        self._rebuilt: List[tuple] = []  # (dead_rt, fresh_rt) awaiting swap
        self._rebuilt_lock = threading.Lock()
        self._last_recover_attempt = 0.0
        self.recover_interval = 5.0
        self.runtime_failures = 0  # runtimes killed by a failed step
        self.rebuilds = 0  # replacements swapped in for them
        models = models if models is not None else {engine_cfg.model: None}
        for name, ckpt in models.items():
            self.load_model(name, ckpt)

    # -- model management (registry-facing; /api/pull and /api/delete) -----
    def load_model(self, name: str, checkpoint_path: Optional[str] = None) -> None:
        cfg = get_model_config(name)
        if cfg is None:
            raise KeyError(f"unknown model architecture: {name}")
        if name in self.runtimes:
            return
        self._model_sources[name] = checkpoint_path
        reps = build_model_runtimes(
            name, cfg, self.ecfg, self.mesh, self.dtype, checkpoint_path,
            self.runtime_class, self.encoder_runtime_class,
        )
        for rep in reps:
            self._attach_hooks(rep)
        self.runtimes[name] = reps[0] if len(reps) == 1 else ReplicaSet(reps)
        log.info("loaded model %s (%.1f MB params)", name,
                 self.runtimes[name].param_bytes / 1e6)
        self.notify()

    def _attach_hooks(self, rep) -> None:
        """Primary-side engine hooks on a (re)built runtime: SLO
        accounting, fault injection, decision journaling, and the
        preemption requeue path."""
        rep.slo = self.slo
        rep.fault_plan = self.fault_plan
        rep.journal = self.journal
        rep.policy = self.policy
        rep.loop_clock = self.loop_clock
        if self.ecfg.preempt:
            rep.on_preempt = self._requeue_preempted

    def evict_model(self, name: str) -> bool:
        rt = self.runtimes.get(name)
        if rt is None:
            return False
        if rt.has_work():
            raise RuntimeError(f"model {name} has in-flight work")
        del self.runtimes[name]
        # ...and the builders' copies of its step programs, so that the
        # executables go with the runtime (an encoder's or the fake
        # engine's runtime: none were built).
        step_program.forget_config(getattr(rt, "cfg", None))
        return True

    def loaded_models(self) -> List[str]:
        return list(self.runtimes.keys())

    # -- request flow ------------------------------------------------------
    def enqueue_request(
        self,
        user: str,
        ip: str,
        model: str,
        family=None,
        prompt_tokens=None,
        sampling=None,
        kind: str = "generate",
        raw_prompt: str = "",
        context_ids=None,
        trace_ctx=None,
        ingress_at: Optional[float] = None,
    ) -> Request:
        """Atomically enqueue into the native core AND register the Request,
        so the engine loop can never pop a req_id it doesn't know yet.
        Raises BlockedError for blocked users/IPs, QueueFullError when a
        bounded-admission cap (--max-queued / --max-queued-per-user) is
        hit — honest backpressure instead of an unbounded queue.

        `trace_ctx` (the `traceparent` header / fleet router context):
        a propagated fleet-stable trace id this request's spans adopt,
        so a member process's timeline stitches under the router's rid
        at GET /debug/trace/{rid}. None mints a fresh root context.
        `ingress_at`: monotonic instant the HTTP handler was entered
        (the trace's `ingress` event; None = the trace starts here).

        `context_ids` (Ollama's /api/generate `context` field, also the
        fleet's token-space HTTP failover replay): token ids already
        generated in a prior turn/attempt. They fold into the replay
        prompt with generated_ids pre-filled — the engine's own
        preemption-replay convention — so the decode continues exactly
        after them and max_tokens still budgets NEW tokens only."""
        cfg = self.ecfg
        if not self.accepting:
            # Graceful shutdown in progress: shed honestly while the
            # in-flight streams drain (limit 0 = "the door is closed").
            self._count_shed("queue_full")
            self.journal.record(
                "shed", user=user, model=model or None, reason="queue_full",
                queued=self.core.total_queued(), limit=0,
                retry_after_s=5.0, n_prompt=len(prompt_tokens or []))
            raise QueueFullError("queue_full", 5.0, 0)
        if cfg.max_queued and self.core.total_queued() >= cfg.max_queued:
            self._count_shed("queue_full")
            retry_s = self.retry_after_s()
            self.journal.record(
                "shed", user=user, model=model or None, reason="queue_full",
                queued=self.core.total_queued(), limit=cfg.max_queued,
                retry_after_s=round(retry_s, 3),
                n_prompt=len(prompt_tokens or []),
                max_tokens=getattr(sampling, "max_tokens", None))
            raise QueueFullError("queue_full", retry_s, cfg.max_queued)
        if (cfg.max_queued_per_user
                and self.core.queue_len(user) >= cfg.max_queued_per_user):
            self._count_shed("user_queue_full")
            retry_s = self.retry_after_s()
            self.journal.record(
                "shed", user=user, model=model or None,
                reason="user_queue_full", queued=self.core.queue_len(user),
                limit=cfg.max_queued_per_user,
                retry_after_s=round(retry_s, 3),
                n_prompt=len(prompt_tokens or []),
                max_tokens=getattr(sampling, "max_tokens", None))
            raise QueueFullError("user_queue_full", retry_s,
                                 cfg.max_queued_per_user)
        with self._pending_lock:
            rid = self.core.enqueue(
                user, ip, model,
                family if family is not None else Family.UNKNOWN, kind=kind,
            )
            req = Request(rid, user, model, prompt_tokens or [], sampling,
                          kind=kind, raw_prompt=raw_prompt)
            if context_ids:
                ctx = [int(t) for t in context_ids]
                sp = copy.copy(req.sampling)  # skip __post_init__ refold
                sp.max_tokens = sp.max_tokens + len(ctx)
                req.sampling = sp
                req.prompt_tokens = list(req.prompt_tokens) + ctx
                req.generated_ids = list(ctx)
                req._replay_gen = len(ctx)
                req.stats.prompt_tokens = len(req.prompt_tokens)
            req.trace = self.tracer.begin(rid, user, model, kind=kind,
                                          ctx=trace_ctx,
                                          ingress_at=ingress_at)
            self.pending[rid] = req
        self.journal.record(
            "enqueue", req=req, n_prompt=len(req.prompt_tokens),
            queued=self.core.total_queued(), kind_req=kind,
            max_tokens=req.sampling.max_tokens,
            deadline_ms=getattr(req.sampling, "deadline_ms", 0.0) or None)
        if self.durability is not None:
            # Durable admission: the WAL fsync must land BEFORE this
            # enqueue is ACKed to the caller — a kill -9 after return
            # can never lose an admitted request. The pristine prompt
            # (pre context-fold) is what recovery re-folds from.
            self.durability.admit(req, prompt_tokens=prompt_tokens or [])
        self.notify()
        return req

    def submit(self, req: Request) -> None:
        """Register a pre-built Request (req.req_id from core.enqueue).
        NOTE: prefer enqueue_request — with this two-step flow the engine
        loop may observe the queued id before registration; _admit tolerates
        that by parking the id as an orphan, but only enqueue_request is
        race-free."""
        with self._pending_lock:
            if req.req_id in self._expired_orphans:
                # Its queue slot was already dropped after the orphan grace
                # period; registering it now would leak it in `pending`.
                del self._expired_orphans[req.req_id]
                expired = True
            else:
                self.pending[req.req_id] = req
                expired = False
        if expired:
            req.finish(FinishReason.ERROR,
                       error="request expired before registration")
            return
        self.notify()

    def inject_request(self, req: Request, ip: str = "",
                       family=None, trace_ctx=None,
                       trace_meter: bool = True) -> Request:
        """Fleet handoff seam: atomically enqueue AND register a
        PRE-BUILT Request (the fleet router's attempt objects, which may
        carry replayed generation state — generated_ids, detokenizer,
        penalty context folded into the prompt — that enqueue_request
        could not construct). Bypasses bounded admission on purpose: the
        router owns the fleet-wide caps; a member must never second-guess
        a placement the router already admitted.

        `trace_ctx` gives the member-side attempt its own Trace under
        the router's fleet context, so its prefill/decode spans stitch
        into the client's /debug/trace/{rid} timeline. `trace_meter`
        False = an in-process LocalMember attempt: the router's root
        trace already meters this stream into requests_inflight/total —
        the member copy must not double-count the shared registry."""
        with self._pending_lock:
            rid = self.core.enqueue(
                req.user, ip, req.model,
                family if family is not None else Family.UNKNOWN,
                kind=req.kind)
            req.req_id = rid
            if trace_ctx is not None and req.trace is None:
                req.trace = self.tracer.begin(
                    rid, req.user, req.model, kind=req.kind,
                    ctx=trace_ctx, metered=trace_meter)
            self.pending[rid] = req
        self.journal.record(
            "enqueue", req=req, n_prompt=len(req.prompt_tokens),
            queued=self.core.total_queued(), kind_req=req.kind,
            max_tokens=req.sampling.max_tokens)
        self.notify()
        return req

    def prefix_match_pages(self, model: str, tokens) -> int:
        """Longest cached-prefix match (in full pages) any runtime of
        `model` holds for this prompt — the fleet router's placement-
        affinity probe. Advisory read from another thread: the radix walk
        only follows dict gets under the GIL, so a racing engine-loop
        mutation can at worst return a stale count (a placement-quality
        issue, never a correctness one). 0 when nothing caches."""
        rt = self.resolve_runtime(model)
        if rt is None:
            return 0
        reps = rt.replicas if isinstance(rt, ReplicaSet) else [rt]
        best = 0
        for rep in reps:
            pc = _prefix_cache(rep)
            if pc is None:
                continue
            try:
                _nodes, pages = pc.match(list(tokens))
            except Exception:  # noqa: BLE001 — advisory probe only
                continue
            best = max(best, len(pages))
        return best

    # -- KV page migration (fleet export/import seam) ----------------------
    def export_stream(self, rid: int, deadline: Optional[float] = None):
        """Phase 1 of the two-phase handoff: snapshot + detach `rid`'s
        decode slot into a portable blob, parking the source state until
        resolve_export commits or aborts. Runs on the engine thread
        (slot tables and the KV pool are loop state); `deadline` bounds
        how long a caller will wait on a wedged loop — a late-running
        export past it is a no-op, so the caller's recompute fallback
        can never race a zombie detach. None = not exportable."""
        def _do():
            if deadline is not None and time.monotonic() > deadline:
                return None
            for rt in self._step_targets():
                export = getattr(rt, "export_request", None)
                if export is None:
                    continue
                out = export(rid)
                if out is None:
                    continue
                handle, blob = out
                self._migrations[rid] = (rt, handle)
                req = handle["req"]
                self.journal.record(
                    "migrate_export", req=req,
                    tokens=len(req.generated_ids),
                    kv_len=blob.get("kv_len"), pages=blob.get("n_pages"))
                return blob
            return None

        timeout = (max(0.05, deadline - time.monotonic())
                   if deadline is not None else 30.0)
        if not self._running:
            # Crashed member (fleet kill): call_on_loop would run the
            # export inline — but the loop thread may still be INSIDE
            # its final iteration, mutating the very slot state the
            # snapshot reads. Wait for it to die first; a loop that
            # won't die within the budget is a recompute fallback, not
            # a torn snapshot.
            t = self._thread
            if t is not None and t.is_alive():
                t.join(timeout=timeout)
                if t.is_alive():
                    return None
        try:
            return self.call_on_loop(_do, timeout=timeout)
        except TimeoutError:
            return None  # wedged loop: the guarded fn no-ops if it runs

    def resolve_export(self, rid: int, commit: bool = True,
                       why: str = "") -> bool:
        """Phase 2: release the parked source state. Commit and abort
        free identically (full prompt pages merge into the prefix
        cache); they differ in the journal story — an abort records WHY
        the transfer failed, and the caller falls back to recompute.
        The parked member-side request finishes CANCELLED either way so
        its server handler / stream consumers unblock."""
        def _do():
            ent = self._migrations.pop(rid, None)
            if ent is None:
                return False
            rt, handle = ent
            req = handle["req"]
            try:
                rt.release_export(handle)
            except Exception:  # noqa: BLE001 — state release must not wedge
                log.exception("release of migrated slot failed (%s)",
                              getattr(rt, "name", "?"))
            if not commit:
                self.journal.record("migrate_abort", req=req,
                                    why=why or "transfer_failed")
            self.core.mark_dropped(req.user)
            # The finish carries the freed slot so the journal's
            # slot-occupancy story stays consistent: the next install
            # into this slot is a reuse, not a double-assignment.
            extra = ({"slot": handle["slot"]} if "slot" in handle else {})
            self.journal.record("finish", req=req, reason="cancelled",
                                tokens=len(req.generated_ids),
                                model=getattr(rt, "name", None), **extra)
            req.finish(FinishReason.CANCELLED)
            self.notify()
            return True

        return self.call_on_loop(_do)

    def import_stream(self, blob: dict, ip: str = "", family=None,
                      deadline: Optional[float] = None, trace_ctx=None,
                      trace_meter: bool = True) -> Request:
        """Target side of a migration: rebuild the Request and install
        it DIRECTLY into a decode slot from the shipped pages — no
        queue wait, no re-prefill. Raises MigrationError when it cannot
        land (caller falls back to recompute). Bypasses bounded
        admission like inject_request: the router already admitted.
        `trace_ctx`/`trace_meter` as in inject_request: the continuation
        traces under the router's fleet context."""
        state = blob.get("request") or {}
        if not state.get("user"):
            raise MigrationError("malformed migration blob (no request)")

        def _do():
            rid = self.core.enqueue(
                state["user"], ip, state.get("model"),
                family if family is not None else Family.UNKNOWN)
            # The id is all we need — the stream never waits in this
            # member's queue (it resumes mid-decode), so take the queue
            # entry straight back out and count it started instead.
            self.core.cancel(rid)
            req = request_from_migration_state(rid, state)
            req._inc_decode = blob.get("_inc_decode")
            req.deadline = deadline
            if trace_ctx is not None:
                req.trace = self.tracer.begin(
                    rid, req.user, req.model, kind=req.kind,
                    ctx=trace_ctx, metered=trace_meter)
            rt = self.resolve_runtime(state.get("model"), kind="generate")
            if rt is None:
                raise MigrationError(
                    f"model not loaded: {state.get('model')}")
            reps = rt.replicas if isinstance(rt, ReplicaSet) else [rt]
            for rep in reps:
                import_fn = getattr(rep, "import_request", None)
                if import_fn is not None and import_fn(blob, req):
                    break
            else:
                raise MigrationError("no slot/pages for migrated stream")
            self.core.mark_started(req.user)
            req.started = True
            self.journal.record(
                "migrate_import", req=req, tokens=len(req.generated_ids),
                pages=blob.get("n_pages"))
            self.notify()
            return req

        return self.call_on_loop(_do)

    def export_prefix(self, model: str, tokens) -> Optional[dict]:
        """Affinity-miss prefix shipping, source side (router seam)."""
        return self._ship_prefix(model, "export_prefix", list(tokens), None)

    def import_prefix(self, model: str, blob: dict) -> int:
        """Affinity-miss prefix shipping, target side: pages adopted."""
        return self._ship_prefix(model, "import_prefix", blob, 0)

    def _ship_prefix(self, model: str, side: str, arg, nothing):
        """`side` of the first replica of `model` that answers with anything."""
        def _do():
            rt = self.resolve_runtime(model)
            if rt is None:
                return nothing
            reps = rt.replicas if isinstance(rt, ReplicaSet) else [rt]
            for rep in reps:
                fn = getattr(rep, side, None)
                got = fn(arg) if fn is not None else nothing
                if got:
                    return got
            return nothing

        try:
            return self.call_on_loop(_do, timeout=10.0)
        except TimeoutError:
            return nothing

    def _count_shed(self, reason: str) -> None:
        tm.SHED_TOTAL.labels(reason=reason).inc()
        self.shed_counts[reason] = self.shed_counts.get(reason, 0) + 1

    def retry_after_s(self) -> float:
        """Retry-After estimate for shed responses: queue depth over the
        OBSERVED completion rate (recent finish timestamps from the
        tracer), clamped to [1, 300]. No completions observed yet =>
        a conservative small default — better an honest guess than a
        magic constant pretending precision."""
        queued = max(1, self.core.total_queued())
        window = getattr(self.tracer, "finish_times", None)
        if window and len(window) >= 2:
            span = window[-1] - window[0]
            if span > 0:
                rate = (len(window) - 1) / span  # completions per second
                return float(min(300.0, max(1.0, queued / rate)))
        # Cold start: no completions observed yet, so queue depth says
        # nothing about drain rate — clamp to a small fixed window
        # instead of extrapolating (a 500-deep startup queue must not
        # answer "Retry-After: 500 seconds" off zero samples).
        return float(min(10.0, max(2.0, float(queued))))

    def _requeue_preempted(self, req: Request) -> bool:
        """on_preempt hook: return a preempted request to the FRONT of
        its user's native queue for recompute re-admission. False => the
        request could not be requeued (cancelled/expired/blocked) and was
        finished here — its pages are already released by the caller."""
        if req.cancelled.is_set():
            self.core.mark_dropped(req.user)
            self.journal.record("finish", req=req, reason="cancelled")
            req.finish(FinishReason.CANCELLED)
            return False
        if req.expired():
            # Deadline check at preemption re-admission: recompute for a
            # response nobody will wait for is pure waste.
            drop_expired(req, self.core, req.model, journal=self.journal)
            return False
        try:
            with self._pending_lock:
                new_rid = self.core.requeue_front(req.user, "", req.model,
                                                  kind=req.kind)
                req.req_id = new_rid
                self.pending[new_rid] = req
            req.trace_event("requeue")
            self.journal.record("requeue", req=req, why="preempt")
            self.notify()
            return True
        except BlockedError:
            self.core.mark_dropped(req.user)
            self.journal.record("finish", req=req, reason="cancelled")
            req.finish(FinishReason.CANCELLED)
            return False

    def _retry_or_error(self, req: Request, msg: str,
                        replay: bool = False) -> None:
        """Route a request implicated in a runtime failure: one retried
        dispatch via the front of its user's native queue (backoff
        honored by the runtime's pending gate), or a poisoned explicit
        error once the budget is spent. `replay=True` folds generated
        ids into the prompt so a mid-decode victim resumes its stream."""
        started = getattr(req, "started", True)
        if req.cancelled.is_set():
            self.core.mark_dropped(req.user, started=started)
            self.journal.record("finish", req=req, reason="cancelled")
            req.finish(FinishReason.CANCELLED)
            return
        if req.expired():
            drop_expired(req, self.core, req.model, journal=self.journal)
            return
        if req.retries >= self.ecfg.step_retries:
            self.core.mark_dropped(req.user, started=started)
            self.journal.record("poison", req=req, retries=req.retries,
                                error=msg[:120])
            req.finish(FinishReason.ERROR, error=(
                f"{msg} (request poisoned after {req.retries} retr"
                f"{'y' if req.retries == 1 else 'ies'})"))
            return
        req.retries += 1
        self._engine_retries += 1
        tm.RETRIES_TOTAL.labels(model=req.model or "?").inc()
        req._retry_at = time.monotonic() + (
            self.ecfg.retry_backoff_s * (2 ** (req.retries - 1)))
        if replay and req.generated_ids:
            # Resume-from-failure recompute: the fresh runtime re-prefills
            # prompt + everything already streamed, then continues.
            req.prompt_tokens = (req.prompt_tokens
                                 + req.generated_ids[req._replay_gen:])
            req._replay_gen = len(req.generated_ids)
        req.trace_event("retry", error=msg[:200], n=req.retries)
        self.journal.record("retry", req=req, n=req.retries,
                            error=msg[:120])
        try:
            with self._pending_lock:
                new_rid = self.core.requeue_front(req.user, "", req.model,
                                                  kind=req.kind)
                req.req_id = new_rid
                self.pending[new_rid] = req
            self.notify()
        except BlockedError:
            self.core.mark_dropped(req.user, started=started)
            self.journal.record("finish", req=req, reason="cancelled")
            req.finish(FinishReason.CANCELLED)

    def cancel(self, req_id: int) -> None:
        with self._pending_lock:
            req = self.pending.get(req_id)
        if req is not None:
            req.cancelled.set()
            # Still in the native queue (never admitted): remove it there and
            # finish the stream now — nothing else will ever pop it.
            if self.core.cancel(req_id):
                with self._pending_lock:
                    self.pending.pop(req_id, None)
                req.finish(FinishReason.CANCELLED)
            self.notify()
            return
        if req is None:
            # Mid-migration: the request is detached from every slot but
            # still parked in the two-phase handoff table.
            for _rt, handle in self._migrations.values():
                if handle["req"].req_id == req_id:
                    req = handle["req"]
                    break
        if req is None:
            # Already admitted: find it in a runtime (active slot or
            # waiting for prefill). _step_targets flattens replica sets —
            # requests live on the individual replicas, never the set.
            for rt in self._step_targets():
                holders = (
                    list(getattr(rt, "slot_req", []))
                    + list(getattr(rt, "active", []))
                    + list(getattr(rt, "pending_prefill", []))
                    + list(getattr(rt, "pending_embed", []))
                    + list(getattr(rt, "chunking", []))
                    + list(getattr(rt, "inflight_prefill", []))
                    + list(getattr(rt, "pending", []))
                )
                for cand in holders:
                    if cand is not None and cand.req_id == req_id:
                        req = cand
                        break
                if req is not None:
                    break
        if req is not None:
            req.cancelled.set()
        else:
            self.core.cancel(req_id)  # still queued in the native core
        self.notify()

    def notify(self) -> None:
        with self._cond:
            self._cond.notify()

    def call_on_loop(self, fn, timeout: float = 900.0):
        """Run `fn` on the engine thread, serialized with device dispatches,
        and return its result (raising what it raised). When the loop isn't
        running — or we ARE the engine thread — runs inline. The generous
        default timeout covers weight reloads behind queued work."""
        if not self._running or threading.current_thread() is self._thread:
            return fn()
        ev = threading.Event()
        box: dict = {}
        entry = (fn, ev, box)
        self._engine_calls.append(entry)
        self.notify()
        if not self._running:
            # stop() may have drained the queue just before our append; if
            # our entry is still there, nothing will ever run it — reclaim
            # and run inline.
            try:
                self._engine_calls.remove(entry)
            except ValueError:
                pass  # loop or stop() took it; the event will fire
            else:
                return fn()
        if not ev.wait(timeout):
            raise TimeoutError("engine-loop call timed out")
        if "err" in box:
            raise box["err"]
        return box.get("ret")

    def _drain_engine_calls(self) -> None:
        while self._engine_calls:
            fn, ev, box = self._engine_calls.popleft()
            try:
                box["ret"] = fn()
            except BaseException as e:  # delivered to the waiting thread
                box["err"] = e
            ev.set()

    def resolve_runtime(self, model: str, kind: str = "generate"):
        if not model:
            # No model requested: any LIVE runtime of the right KIND
            # (reference lets Unknown-family tasks hit any online backend,
            # dispatcher.rs:453-461 — offline ones are skipped). The kind
            # filter keeps a generative request off an EncoderRuntime when
            # only encoders are loaded: it would "finish" with an embedding
            # and no tokens.
            def kind_ok(rt):
                return kind in getattr(rt, "SERVES", ("generate",))

            for rt in self.runtimes.values():
                if isinstance(rt, ReplicaSet) and kind_ok(rt.replicas[0]) \
                        and any(not r._failed for r in rt.replicas):
                    return rt
                if isinstance(rt, (ModelRuntime, EncoderRuntime)) \
                        and kind_ok(rt) and not rt._failed:
                    return rt
            # Everything of the right kind is failed (mid-recovery): pick
            # one anyway — the request parks on it and drains post-reload.
            for rt in self.runtimes.values():
                probe = rt.replicas[0] if isinstance(rt, ReplicaSet) else rt
                if kind_ok(probe):
                    return rt
            return None
        key = smart_match(model, self.runtimes.keys())
        return self.runtimes[key] if key is not None else None

    # -- main loop ---------------------------------------------------------
    def start(self) -> None:
        if self._running:
            return
        self._running = True
        self._thread = threading.Thread(target=self._loop, name="engine", daemon=True)
        self._thread.start()
        if self.health is None:
            from ollamamq_tpu.engine.health import HealthMonitor

            self.health = HealthMonitor(self)
            self.health.start()
        if self.durability is not None:
            # WAL recovery runs with the loop live (re-admissions flow
            # through the normal enqueue path) and before the HTTP
            # front-end starts serving — readiness is gated on it.
            self.durability.start(self)

    def stop(self) -> None:
        self._running = False
        self.notify()
        if self._thread:
            self._thread.join(timeout=10)
            self._thread = None
        # Fail any deferred engine-thread calls that raced the shutdown —
        # their waiters would otherwise block until the call_on_loop
        # timeout.
        while self._engine_calls:
            _fn, ev, box = self._engine_calls.popleft()
            box["err"] = RuntimeError("engine stopped")
            ev.set()
        if self.health is not None:
            self.health.stop()
            self.health = None
        if self.durability is not None:
            self.durability.close()  # final WAL flush + fsync
        self.journal.close()  # flush any --journal-file spill

    def quiesce(self) -> None:
        """Graceful-shutdown gate: stop accepting new requests (later
        enqueues shed with 503) while everything in flight drains."""
        self.accepting = False

    def inflight_count(self) -> int:
        """Queued + admitted-but-unfinished work — what a graceful
        shutdown waits on before flushing and exiting."""
        n = self.core.total_queued()
        for rt in self._step_targets():
            n += rt.active_count()
            for attr in ("pending_prefill", "pending_embed", "chunking",
                         "pending"):
                n += len(getattr(rt, attr, ()) or ())
        return n + len(self._migrations)

    @staticmethod
    def _gate_eligible(rt, kind: str) -> bool:
        """Gate-eligibility of a runtime for one request kind: it can
        accept one NOW, or it permanently cannot serve the kind — then
        the pop must still reach _place so the mismatch errors loudly
        (never parks as unservable)."""
        probe = rt.replicas[0] if isinstance(rt, ReplicaSet) else rt
        if kind not in getattr(probe, "SERVES", ("generate",)):
            return True
        return rt.has_capacity(kind)

    def _admit(self, tick: bool = True) -> int:
        """`tick` False: a second pass inside one loop iteration (behind
        a scan's blocking read) — the scheduler's clock does not move."""
        admitted = 0
        pol = self.policy
        # One batch tick on the scheduler clock — the anti-starvation
        # aging runs on admission passes, which fire once per engine
        # loop iteration in the live engine AND once per virtual tick in
        # the synchronous replay/simulate drivers.
        if tick:
            pol.on_admit_tick()
        # Retry orphans: ids popped before their Request was registered
        # (two-step submit flow); give them a 5 s grace. Expiry always runs;
        # the capacity gate only defers placement of registered requests.
        now = time.monotonic()
        for rid, user, model, ts in list(self._orphans):
            with self._pending_lock:
                req = self.pending.pop(rid, None)
                if req is None and now - ts > 5.0:
                    # Expire under the lock so submit() can't slip the
                    # Request into `pending` between our check and write.
                    self._orphans.remove((rid, user, model, ts))
                    self._expired_orphans[rid] = now
                    req_expired = True
                else:
                    req_expired = False
            if req_expired:
                self.core.mark_dropped(user, started=False)
                continue
            if req is None:
                continue  # still within grace, not yet registered
            rt = self.resolve_runtime(model, kind=req.kind)
            if rt is not None and not self._gate_eligible(rt, req.kind):
                # Runtime full for this kind: put the Request back and
                # retry later.
                with self._pending_lock:
                    self.pending[rid] = req
                continue
            self._orphans.remove((rid, user, model, ts))
            req.trace_event("admit")
            self.journal.record("admit", req=req,
                                queued=self.core.total_queued())
            if self._place(req, user, model):
                admitted += 1
        # Age out expiry tombstones nothing ever claimed (slow leak guard).
        for rid, ts in list(self._expired_orphans.items()):
            if now - ts > 60.0:
                del self._expired_orphans[rid]
        # Candidate batch: the window of pops the fair-share core
        # released this pass, placed in POLICY order (decision point
        # (a)). fcfs has admission_window == 1, so each pop flushes
        # immediately — the exact legacy pop-and-place flow.
        batch: List[tuple] = []  # (rid, user, model, req)

        def flush() -> None:
            nonlocal admitted
            if not batch:
                return
            ordered = pol.order_admission(list(batch))
            batch.clear()
            if len(ordered) > 1 and pol.name != "fcfs":
                first = ordered[0][3]
                self.journal.record(
                    "sched", req=first, policy=pol.name, point="admit",
                    candidates=len(ordered),
                    predicted=pol.predict(first),
                    score=round(pol.score(first), 3))
            for rid, user, model, req in ordered:
                req.trace_event("admit")
                self.journal.record("admit", req=req,
                                    queued=self.core.total_queued())
                if self._place(req, user, model):
                    admitted += 1

        while True:
            # Two capacity pools, one gate each: the native pop gates an
            # embed task on the embed list and a generate task on the
            # generate list, so neither kind's backlog parks the other.
            gen_ok = [name for name, rt in self.runtimes.items()
                      if self._gate_eligible(rt, "generate")]
            emb_ok = [name for name, rt in self.runtimes.items()
                      if self._gate_eligible(rt, "embed")]
            if not gen_ok and not emb_ok:
                break
            items, stuck = self.core.next_window(
                pol.admission_window, eligible_models=gen_ok,
                eligible_embed=emb_ok)
            for rid, user, model in items:
                with self._pending_lock:
                    req = self.pending.pop(rid, None)
                if req is None:
                    # Popped before registration (legacy two-step
                    # submit): park it and retry for a grace period.
                    self._orphans.append((rid, user, model,
                                          time.monotonic()))
                    continue
                batch.append((rid, user, model, req))
            flush()
            if stuck:
                # Policy pick unservable; cursor advanced, retry on wake.
                # Rate-limited warn for operator visibility (the reference
                # logs "Request stuck in queue", dispatcher.rs:467-473).
                now = time.monotonic()
                if now - self._last_stuck_log > 10.0:
                    self._last_stuck_log = now
                    log.warning(
                        "request stuck in queue: scheduler pick needs a model "
                        "not currently servable (generate-ready: %s, "
                        "embed-ready: %s; %d queued)",
                        gen_ok, emb_ok, self.core.total_queued(),
                    )
                break
            if not items:
                break
        return admitted

    def _admit_behind_scan(self) -> None:
        """A scan's blocking read has just returned, and the thread sat in
        it for most of the scan: whoever arrived meanwhile is placed NOW,
        so that the step launched next takes the prompt in — not a
        one-pass scan (k = 1: "an admission could land") with a second
        blocking read and a second idle gap of the chip behind it. A
        control-plane fault here is the next tick's to raise, not the
        runtime's to die of."""
        if not (self.core.total_queued() or self._orphans):
            return
        self.loop_clock.enter("admit")
        try:
            self._admit(tick=False)
        except Exception:
            log.exception("admission behind a scan failed")
        self.loop_clock.enter("other")

    def _place(self, req: Request, user: str, model: str) -> bool:
        # Late re-check (dispatcher.rs:503-512): client gone OR user/IP
        # blocked after enqueueing ⇒ drop, never serve.
        if req.cancelled.is_set() or self.core.is_user_or_ip_blocked(user):
            self.core.mark_dropped(user, started=req.started)
            self.journal.record("finish", req=req, reason="cancelled")
            req.finish(FinishReason.CANCELLED)
            return False
        if req.expired():
            # Deadline check at admission: an expired pop is dropped here,
            # before it can claim a slot or a prefill forward.
            drop_expired(req, self.core, model, journal=self.journal)
            return False
        rt = self.resolve_runtime(model, kind=req.kind)
        if rt is None and model:
            # The native eligibility gate raced an evict: the model vanished
            # between mq_next's model check and placement. Stuck-queue
            # semantics (the reference parks requests whose backend is gone,
            # dispatcher.rs:467-473) — put it back rather than erroring.
            # Named models only: an empty model always passes the native
            # gate, so requeueing it would spin.
            return self._requeue(req, user, model)
        if rt is None:
            self.core.mark_dropped(user, started=req.started)
            self.journal.record("finish", req=req, reason="error")
            req.finish(FinishReason.ERROR, error=f"model not loaded: {model}")
            return False
        # Named-model kind check: generate on an encoder would "finish"
        # with an embedding and zero tokens — a permanent mismatch, so
        # error, don't park. (Generative runtimes serve BOTH kinds via
        # step_embed; the embed-side message is kept for runtime kinds
        # that opt out of embedding.)
        probe = rt.replicas[0] if isinstance(rt, ReplicaSet) else rt
        if req.kind not in getattr(probe, "SERVES", ("generate",)):
            self.core.mark_dropped(user, started=req.started)
            self.journal.record("finish", req=req, reason="error")
            req.finish(FinishReason.ERROR, error=(
                f"model {model or probe.name} is an embedding-only model"
                if req.kind == "generate"
                else f"model {model or probe.name} does not support "
                     "embeddings"))
            return False
        if not rt.submit(req):
            if model:
                # Replica capacity raced away between the admission gate
                # and placement: wait-in-queue, same as the evict race
                # above — the native gate holds it until capacity returns.
                return self._requeue(req, user, model)
            # Empty-model requests always pass the native gate, so a
            # requeue would spin; park on the least-loaded live replica.
            rt.force_submit(req)
        req.trace_event("place", runtime=getattr(rt, "name", model))
        self.journal.record("place", req=req,
                            runtime=getattr(rt, "name", model))
        if not req.started:
            # Preempted/retried requeues were already counted as started;
            # a second mark would leak a processing count forever.
            self.core.mark_started(user)
            req.started = True
        return True

    def _requeue(self, req: Request, user: str, model: str) -> bool:
        """Return a popped-but-unplaceable request to the FRONT of its
        user's native queue (wait-don't-fail, FIFO preserved: the evict/
        capacity race must never let the user's later request overtake
        this one). Always returns False (nothing was placed)."""
        try:
            with self._pending_lock:
                new_rid = self.core.requeue_front(user, "", model,
                                                  kind=req.kind)
                req.req_id = new_rid
                self.pending[new_rid] = req
            req.trace_event("requeue")
            self.journal.record("requeue", req=req, why="unplaceable")
        except BlockedError:
            self.core.mark_dropped(user, started=False)
            self.journal.record("finish", req=req, reason="cancelled")
            req.finish(FinishReason.CANCELLED)
        return False

    def _step_targets(self) -> List[object]:
        """Individually-steppable runtimes: replica sets flatten so each
        replica advances every tick. The loop launches every runtime's
        next step before settling any (launch/settle split in
        ModelRuntime), so replicas on disjoint device sets genuinely
        execute concurrently rather than serializing on this thread."""
        out: List[object] = []
        for rt in self.runtimes.values():
            if isinstance(rt, ReplicaSet):
                out.extend(rt.replicas)
            else:
                out.append(rt)
        return out

    # A compile blocks the loop thread for as long as XLA takes — tens of
    # seconds for a step program on a TPU. That is progress, not a wedge,
    # up to this bound (a compile that outlives it is one).
    COMPILE_GRACE_S = 600.0

    def compiling(self) -> bool:
        """True while a runtime is in a step that pays a compile (the
        first call of a fresh jit, through that step's collect). The
        stall watchdog and a fleet router's heartbeat check read it, so
        a cold member is not ejected mid-compile."""
        now = time.monotonic()
        return any(
            t is not None and now - t < self.COMPILE_GRACE_S
            for t in (getattr(rt, "compiling_since", None)
                      for rt in self._step_targets()))

    def _kill_runtime(self, rt) -> None:
        """A runtime failure must not kill the engine loop: fail every
        request this runtime holds and keep serving the rest (reference
        analogue: an errored dispatch returns 500 and counts dropped,
        dispatcher.rs:555-559)."""
        if isinstance(rt, ModelRuntime):
            # Unread ids are dropped, never half-emitted: the replay
            # below regenerates them from what clients already have.
            rt.void_inflight()
        self._fail_runtime(rt, "engine step failed")
        rt._failed = True
        self.runtime_failures += 1
        # Drop the dead runtime's device buffers NOW: the HBM must be free
        # before the replacement loads, or a large model could never
        # recover (params + KV would be resident twice).
        rt.params = None
        if getattr(rt, "cache", None) is not None:
            rt.cache.drop()
        self._failed_runtimes.append(rt)

    def _loop(self) -> None:
        self.loop_clock.reset()
        stepprof.PROFILER.cpu_register("engine")  # read at a scrape only
        try:
            while self._running:
                try:
                    self._loop_once()
                except Exception:
                    # The engine thread must never die: a control-plane
                    # bug (admission, recovery bookkeeping) would
                    # otherwise stop ALL serving with requests parked
                    # forever. Runtime step errors are already handled
                    # per-runtime inside _loop_once.
                    log.exception("engine loop iteration failed; continuing")
                    time.sleep(0.1)
            self._settle_all()  # no launched step's tokens are lost at stop
        finally:
            stepprof.PROFILER.cpu_unregister("engine")

    # HBM/allocator timeline (telemetry/stepprof.py): one bounded-ring
    # sample per period — the engine ticks far faster — of every
    # runtime's page-pool state + weight/KV footprint, the trend
    # /debug/hbm serves and an OOM postmortem reads back over time.
    HBM_SAMPLE_PERIOD_S = 1.0
    _hbm_last_sample = 0.0

    def _sample_hbm_timeline(self) -> None:
        now = time.monotonic()
        if now - self._hbm_last_sample < self.HBM_SAMPLE_PERIOD_S:
            return
        self._hbm_last_sample = now
        models = {}
        for name, rt in self.runtimes.items():
            entry = {"weight_bytes": int(getattr(rt, "param_bytes", 0)),
                     "kv_bytes": int(getattr(rt, "kv_bytes", 0)),
                     "slot_state_bytes": sum(
                         getattr(rt, "state_bytes", {}).values())}
            cache = getattr(rt, "cache", None)
            if cache is not None:
                entry.update(cache.page_state())
            models[name] = entry
        stepprof.PROFILER.hbm_record({"models": models})

    def _settle_all(self) -> None:
        """Bring every runtime to rest (no step in flight): before
        anything that reads or moves slot state from outside the step
        functions — engine calls (migration export/import, prefix
        export, eviction, /debug reads), a rebuilt runtime's swap-in —
        and when the loop ends."""
        for rt in self._step_targets():
            if getattr(rt, "inflight", None) is None:
                continue
            try:
                rt.settle_inflight(self.core)
            except Exception:
                log.exception("runtime %s settle failed", rt.name)
                self._kill_runtime(rt)

    def _loop_once(self) -> None:
        """One engine tick: a depth-1 software pipeline of this thread.

        Each runtime has at most one step launched and unsettled when a
        tick starts. The tick LAUNCHES the next step — compose and
        dispatch, while the chip still runs the one in flight — and only
        then SETTLES the earlier one (detokenise, push, finish slots):
        the host's work of step N hides behind step N+1 on the device.
        A fused scan in flight is collected first (ids only): nothing is
        queued behind an unfinished scan, so an arrival never waits for
        two — and requests that arrived while the thread was blocked in
        that read are admitted right behind it (a second admission pass,
        the scheduler's clock not moved), so the next launch is theirs.
        Where the next composition needs the host to have seen the
        ids, or state must be at rest, the depth falls to zero and the
        step is settled in the tick that launched it: a runtime whose
        `--spec` proposer is the n-gram lookup, CPU multi-host (one
        cross-host computation at a time), pending engine calls and
        rebuild swaps (settled above, before they run); page exhaustion
        and failures settle or void inside the step functions. Every runtime's launch comes before any
        settle, so dp replicas and models on disjoint submeshes run
        concurrently."""
        # The engine thread's time is accounted for without a gap
        # (stepprof.LOOP_PHASES): `other` is open wherever nothing below
        # says otherwise, step timers take the cursor while they run.
        clock = self.loop_clock
        clock.tick()
        self.last_tick_at = time.monotonic()
        self.journal.tick += 1
        self._sample_hbm_timeline()
        if self._engine_calls or self._rebuilt:
            self._settle_all()
        self._drain_engine_calls()
        self._swap_rebuilt()
        if (self._failed_runtimes
                and time.monotonic() - self._last_recover_attempt
                > self.recover_interval):
            self._try_recover()
        clock.enter("admit")
        self._admit()
        clock.enter("other")
        did_work = False
        # Phase 1: every runtime launches its next step. JAX dispatch is
        # async, so once runtime A's step is queued the loop immediately
        # launches runtime B's.
        to_settle: List[tuple] = []  # (rt, handle), settled in phase 2
        for rt in self._step_targets():
            if getattr(rt, "_failed", False):
                continue
            try:
                rt.check_cancellations(self.core)
                if isinstance(rt, ModelRuntime):
                    prev = rt.inflight
                    if prev is not None:
                        did_work = True
                        if prev.k_steps:
                            rt.step_collect(prev, self.core)
                            self._admit_behind_scan()
                    # Ragged mixed batch: admission + ONE token-budget
                    # dispatch packing prefill spans AND every live
                    # decode slot (each advances one token inside it).
                    h = rt.step_ragged_launch(self.core)
                    # Embeds on a generative model: one stateless batch
                    # forward, no slot/page contention with decode.
                    if rt.pending_embed and rt.step_embed(self.core):
                        did_work = True
                    if h is None and any(r is not None
                                         for r in rt.slot_req):
                        # Short decode chunks (k=1) keep TTFT low ONLY
                        # when an admission could actually land between
                        # steps: pending work AND a free seat, or a
                        # chunked prefill to interleave. A saturated
                        # batch with a deep backlog must run the full
                        # fused chunk — per-step dispatch latency
                        # (host composition + launch + D2H collect)
                        # would otherwise gate every token under exactly
                        # the 64-user load the engine is built for.
                        # Scoped to work THIS runtime could serve:
                        # backlog parked for another (or evicted) model
                        # must not hold a healthy runtime at k=1.
                        waiting = bool(rt.pending_prefill) or bool(
                            self.core.queued_matching(rt.name)
                        )
                        can_admit = waiting and rt.has_capacity("generate")
                        k = (1 if (can_admit or rt.chunking)
                             else self.ecfg.decode_steps_per_iter)
                        h = rt.step_decode_dispatch(self.core, k_steps=k)
                        # h None with slots occupied = every occupant is a
                        # stalled page reservation (or ends with the step
                        # in flight): nap on the condvar (did_work stays
                        # False) instead of spinning.
                    if h is not None:
                        did_work = True
                    # Launching may have had to settle `prev` itself
                    # (page exhaustion, a failed dispatch): settling
                    # again is a no-op.
                    if prev is not None:
                        to_settle.append((rt, prev))
                    if h is not None:
                        if self._serialize_multihost:
                            rt.step_settle(h, self.core)
                        elif not rt.may_overlap():
                            to_settle.append((rt, h))
                else:
                    if rt.has_work():
                        rt.step(self.core)
                        did_work = True
            except Exception:
                log.exception("runtime %s step failed", rt.name)
                self._kill_runtime(rt)
                did_work = True
        # Phase 2: settle behind the launches. Device errors in the async
        # computation surface here, not at dispatch.
        for rt, h in to_settle:
            if getattr(rt, "_failed", False):
                continue
            try:
                rt.step_settle(h, self.core)
            except Exception:
                log.exception("runtime %s settle failed", rt.name)
                self._kill_runtime(rt)
        if not did_work:
            clock.enter("wait")
            with self._cond:
                self._cond.wait(timeout=0.05)
            clock.enter("other")

    def _try_recover(self) -> None:
        """Kick off background rebuilds of failed runtimes. The reference's
        recovery story is backends re-entering rotation when the health
        probe succeeds (dispatcher.rs:373-377); here re-entering rotation
        means a fresh runtime (weights reloaded), since the old one's
        device state is gone. The reload runs OFF the engine thread so
        healthy runtimes keep serving; _swap_rebuilt installs the result."""
        self._last_recover_attempt = time.monotonic()
        for rt in list(self._failed_runtimes):
            if id(rt) in self._recovering:
                continue
            self._recovering.add(id(rt))
            self._start_rebuild(rt)

    def _start_rebuild(self, rt) -> None:
        """Rebuild seam: background thread here; the SPMD engine overrides
        to broadcast a reload opcode and rebuild inline on the engine thread
        (ordered with the dispatch broadcast stream)."""
        threading.Thread(
            target=self._rebuild_runtime, args=(rt,),
            name=f"recover-{rt.name}", daemon=True,
        ).start()

    def _rebuild_runtime(self, rt) -> bool:
        """(background thread) Build a replacement runtime; post it for the
        engine thread to swap in. Returns success — the SPMD rebuild path
        must report its OWN failure truthfully at the status sync (ADVICE
        r3: claiming ok while failed re-broadcasts OP_RELOAD every retry,
        making healthy workers re-download weights each cycle)."""
        try:
            fresh = type(rt)(
                rt.name, getattr(rt, "_orig_cfg", rt.cfg), self.ecfg,
                mesh=rt.mesh,
                checkpoint_path=self._model_sources.get(rt.name),
                dtype=self.dtype,
            )
        except Exception:
            log.exception(
                "recovery reload of %s failed; retrying in %.0fs",
                rt.name, self.recover_interval,
            )
            self._recovering.discard(id(rt))  # next interval retries
            return False
        with self._rebuilt_lock:
            self._rebuilt.append((rt, fresh))
        self.notify()
        return True

    def _swap_rebuilt(self) -> None:
        """(engine thread) Install finished rebuilds and hand over any
        requests that raced into the dead runtime between failure and
        swap."""
        with self._rebuilt_lock:
            if not self._rebuilt:
                return
            items, self._rebuilt = self._rebuilt, []
        for rt, fresh in items:
            self._attach_hooks(fresh)
            if hasattr(rt, "spmd_index"):
                fresh.spmd_index = rt.spmd_index
                fresh.spmd_replica = getattr(rt, "spmd_replica", 0)
            cur = self.runtimes.get(rt.name)
            if isinstance(cur, ReplicaSet) and rt in cur.replicas:
                cur.replicas[cur.replicas.index(rt)] = fresh
            elif cur is rt:
                self.runtimes[rt.name] = fresh
            # else: evicted while failed — drop the rebuild silently.
            for attr in ("pending_prefill", "pending_embed", "chunking",
                         "pending"):
                q = getattr(rt, attr, None)
                while q:
                    fresh.submit(q.popleft())  # restart from scratch
            self._failed_runtimes.remove(rt)
            self._recovering.discard(id(rt))
            self.rebuilds += 1
            self.journal.record("rebuild", model=rt.name)
            log.warning("runtime %s recovered: weights reloaded, serving "
                        "resumes", rt.name)
            self.notify()

    def _fail_runtime(self, rt, msg: str) -> None:
        """Contain a runtime-step failure to the implicated requests: each
        one is retried ONCE on a fresh dispatch (front of its user's
        queue, exponential backoff; mid-decode victims replay
        prompt+generated so their stream resumes seamlessly after the
        rebuild), and requests that keep failing are poisoned with an
        explicit error — one bad input can't crash-loop the engine."""
        try:
            if isinstance(rt, ModelRuntime):
                for i, req in enumerate(rt.slot_req):
                    if req is not None:
                        rt.cache.release(i)
                        rt.seq_lens[i] = 0
                        rt.slot_req[i] = None
                        self._retry_or_error(req, msg, replay=True)
                rt._stalled_slots.clear()
            act = getattr(rt, "active", None)
            if isinstance(act, list):  # FakeRuntime's slot table
                while act:
                    self._retry_or_error(act.pop(), msg, replay=True)
            for attr in ("pending_prefill", "pending_embed", "chunking",
                         "pending"):
                pending = getattr(rt, attr, None)
                while pending:
                    self._retry_or_error(pending.popleft(), msg)
            if hasattr(rt, "reserved_slots"):
                for slot in list(rt.reserved_slots):
                    rt.cache.release(slot)
                rt.reserved_slots.clear()
        except Exception:
            log.exception("error while failing runtime %s", rt.name)

    # -- prefix cache (GET/POST /debug/prefix_cache) -----------------------
    def scheduler_stats(self) -> dict:
        """Live scheduling-policy readout (TUI sched chip, engine stats,
        /metrics.json): active policy, output-length predictor accuracy
        over its recent window (None until warmed up — rendered as
        "acc n/a"), observation count, and reorder decisions applied."""
        p = self.policy
        acc = p.predictor.accuracy()
        return {"policy": p.name,
                "pred_accuracy": round(acc, 4) if acc is not None else None,
                "pred_observed": p.predictor.observed,
                "decisions": p.decisions}

    def prefix_cache_stats(self) -> dict:
        """Per-model prefix-cache stats (replicas summed); works on any
        engine subclass — runtimes without a cache are skipped."""
        models: Dict[str, list] = {}
        for rt in self._step_targets():
            pc = _prefix_cache(rt)
            if pc is not None:
                models.setdefault(rt.name, []).append(pc.stats())
        merged = {name: merge_prefix_cache_stats(reps)
                  for name, reps in models.items()}
        return {"enabled": bool(merged), "models": merged}

    def prefix_cache_flush(self) -> int:
        """Evict every unreferenced cached page on every runtime. Runs on
        the engine thread: the tree and allocator are engine-loop state."""
        def _do() -> int:
            return sum(pc.flush()
                       for pc in map(_prefix_cache, self._step_targets())
                       if pc is not None)

        if not any(_prefix_cache(rt) is not None
                   for rt in self._step_targets()):
            return 0  # nothing to flush (also: FakeEngine's loop has no
            #           call_on_loop drain — don't park on it)
        return self.call_on_loop(_do)

    # -- telemetry ---------------------------------------------------------
    def preemption_count(self) -> int:
        """Total KV-pressure preemptions across runtimes (TUI chip; the
        health monitor's preemption-storm rule rates this)."""
        return sum(getattr(rt, "preempt_count", 0)
                   for rt in self._step_targets())

    def retry_count(self) -> int:
        return self._engine_retries + sum(
            getattr(rt, "retry_count", 0) for rt in self._step_targets())

    def chip_stats(self) -> List[dict]:
        """Per-chip rows; the SPMD engine overrides to merge worker
        hosts' chips from the KV store."""
        return per_chip_stats()

    def worker_metric_snapshots(self) -> List[dict]:
        """Peer-host registry snapshots to merge into /metrics; the SPMD
        engine overrides to read them off the KV store."""
        return []

    def stale_worker_hosts(self) -> List[int]:
        """Process ids of SPMD worker hosts whose KV-store snapshots have
        stopped advancing; the stall watchdog alerts on them. The SPMD
        engine overrides — single-host engines have no peers."""
        return []

    def stats(self) -> dict:
        runtime_stats = [rt.stats() for rt in self.runtimes.values()]
        # Per-chip HBM (north star: "per-chip HBM occupancy", not one
        # device's counters standing in for the pod — VERDICT r3 weak #6).
        chips = self.chip_stats()
        hbm_used = sum(c["hbm_used"] for c in chips) or sum(
            r["param_bytes"] + r["kv_bytes"]
            + sum(r.get(k, 0) for _, k, _ in step_work.STATE_BYTES)
            for r in runtime_stats)
        hbm_total = sum(c["hbm_total"] for c in chips) or None
        return {
            "runtimes": runtime_stats,
            "chips": chips,
            # Mesh layout so operators can see WHICH parallelism the pod
            # is running (axis name -> size), not just how many chips.
            "mesh": dict(self.mesh.shape) if self.mesh is not None else None,
            "hbm_used_bytes": hbm_used,
            "hbm_total_bytes": hbm_total,
            **device_summary(),
            "uptime_s": round(time.time() - self.started_at, 1),
            "health": health.status() if (health := self.health) else None,
            "queue": self.core.snapshot(),
            # Degradation counters: sheds by reason (admission caps,
            # deadlines, kv exhaustion) + total preemptions/retries.
            "shed": dict(self.shed_counts),
            "preemptions": self.preemption_count(),
            "retries": self.retry_count(),
            "runtime_failures": self.runtime_failures,
            "rebuilds": self.rebuilds,
            # Scheduling policy + output-length predictor accuracy.
            "scheduler": self.scheduler_stats(),
            # Engine performance plane: compile count (and how many came
            # out of the persistent cache) + rolling step p99 (the TUI
            # `compiles N (h hit / m miss) · step p99` chip's source), and
            # the start-up ledger: ready by phase.
            "stepprof": stepprof.PROFILER.brief(),
            "startup": stepprof.PROFILER.startup_snapshot(),
        }
