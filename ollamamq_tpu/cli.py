"""CLI entrypoint: `python -m ollamamq_tpu.cli`.

Flag parity with the reference CLI (/root/reference/src/main.rs:19-41),
re-targeted at TPU: `--backend-urls` becomes `--models` (the pool being
scheduled is model runtimes on TPU chips, not HTTP backends). Logging
mirrors main.rs:62-87: file appender when the TUI owns the terminal,
stdout otherwise, level from OLLAMAMQ_LOG (the RUST_LOG analogue).
"""

from __future__ import annotations

import argparse
import logging
import os
import sys


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="ollamamq-tpu",
        description="TPU-native LLM serving with per-user fair-share queuing",
    )
    p.add_argument("--port", type=int, default=int(os.environ.get("PORT", 11434)),
                   help="HTTP port (default 11434)")
    p.add_argument("--host", default="0.0.0.0")
    p.add_argument("--models", default=os.environ.get("MODELS", "llama3:8b"),
                   help="comma-separated model names to load at startup "
                        "(replaces the reference's --backend-urls)")
    p.add_argument("--checkpoints", default=os.environ.get("CHECKPOINTS", ""),
                   help="comma-separated name=path checkpoint mappings; "
                        "models without one use random weights")
    p.add_argument("--timeout", type=float,
                   default=float(os.environ.get("TIMEOUT", 300)),
                   help="per-request timeout seconds (default 300)")
    p.add_argument("--no-tui", action="store_true",
                   help="disable the admin TUI")
    p.add_argument("--allow-all-routes", action="store_true",
                   help="expose the fallback route for unhandled paths")
    p.add_argument("--fake-engine", action="store_true",
                   help="serve deterministic fake tokens (no TPU; for tests)")
    p.add_argument("--blocklist", default="blocked_items.json",
                   help="blocklist persistence path")
    # Engine shape.
    p.add_argument("--max-slots", type=int, default=64,
                   help="decode batch slots (max concurrent generations)")
    # 1024 pages of 32 tokens: a 32768-slot KV pool (1 GiB for
    # llama3.2:1b in bf16).
    p.add_argument("--num-pages", type=int, default=1024)
    p.add_argument("--page-size", type=int, default=32)
    p.add_argument("--max-pages-per-seq", type=int, default=256)
    p.add_argument("--max-new-tokens", type=int, default=256)
    p.add_argument("--decode-steps", type=int, default=8,
                   help="decode steps fused per dispatch when idle")
    p.add_argument("--weights-dtype", choices=("bfloat16", "int8"),
                   default="bfloat16",
                   help="weight storage dtype: 'int8' quantizes at load "
                        "time (per-channel symmetric, fp32 scales, "
                        "dequant fused into the matmuls) — roughly "
                        "halves weight HBM and the bytes every weight-"
                        "streaming-bound dispatch reads")
    p.add_argument("--kv-dtype", choices=("bfloat16", "int8"),
                   default="bfloat16",
                   help="KV page dtype: 'int8' shrinks every page ~2x "
                        "(per-page-row fp32 scales stored alongside the "
                        "pool), so ~2x concurrent requests fit the same "
                        "HBM; invalid combinations (MoE weights) fail "
                        "at startup")
    p.add_argument("--max-batch-tokens", type=int, default=512,
                   help="token budget of one ragged dispatch (decode rows "
                        "+ prefill-span tokens); clamped up so a full "
                        "decode batch always fits")
    p.add_argument("--token-granule", type=int, default=16,
                   help="ragged streams pad their TOTAL token count to "
                        "this granule (the only padding the ragged path "
                        "pays; one compile per padded total)")
    p.add_argument("--spec", action="store_true", default=False,
                   help="speculative multi-token decoding on the ragged "
                        "path: drafts (up to --spec-k per greedy decode "
                        "slot) verified in one ragged dispatch; accepted "
                        "drafts emit together, rejected drafts' KV pages "
                        "roll back. The proposer is the model's own "
                        "multi-token-prediction module where it has one "
                        "(num_nextn_predict_layers: one draft a step, "
                        "computed on the device), n-gram prompt lookup "
                        "otherwise. Greedy streams stay byte-identical "
                        "to --no-spec")
    p.add_argument("--no-spec", dest="spec", action="store_false",
                   help="disable speculative decoding (the default)")
    p.add_argument("--spec-k", type=int, default=4,
                   help="max draft tokens proposed per decode slot per "
                        "dispatch")
    p.add_argument("--spec-min-accept", type=float, default=0.1,
                   help="per-user auto-throttle: once a user's observed "
                        "draft accept rate falls below this (after a "
                        "warmup sample), speculation is disabled for "
                        "that user — wasted verify FLOPs must pay for "
                        "themselves; 0 never throttles")
    p.add_argument("--scheduler",
                   default=os.environ.get("SCHEDULER", "fcfs"),
                   help="scheduling policy: 'fcfs' (default; FIFO within "
                        "fair share, bit-identical to the pre-policy "
                        "engine), 'srpt' (shortest-predicted-remaining-"
                        "first off an online output-length predictor, "
                        "with anti-starvation aging), or 'edf' "
                        "(earliest-deadline-first; srpt order for "
                        "deadline-less requests). Policies reorder only "
                        "within what fair-share already allows; promote "
                        "a candidate with `python -m "
                        "ollamamq_tpu.tools.journal simulate TRACE "
                        "--scheduler srpt` counterfactual replay")
    p.add_argument("--prefix-cache", action="store_true",
                   help="automatic prefix caching: share finished prompts' "
                        "KV pages (page-granular radix tree) across "
                        "requests; prefills only the uncached tail")
    p.add_argument("--prefix-cache-min-pages", type=int, default=1,
                   help="minimum matched full pages before a cached "
                        "prefix is reused (smaller hits prefill normally)")
    # Mesh.
    p.add_argument("--dp", type=int, default=1, help="data-parallel axis size")
    p.add_argument("--tp", type=int, default=1,
                   help="tensor-parallel axis size (-1 = all devices)")
    p.add_argument("--ep", type=int, default=1,
                   help="expert-parallel axis size (MoE models)")
    # Fleet router: dispatcher-over-engines.
    p.add_argument("--replicas", type=int,
                   default=int(os.environ.get("REPLICAS", 1)),
                   help="in-process engine replicas behind the fleet "
                        "router (1 = single engine, no router): health-"
                        "driven ejection with backoff re-probe, mid-"
                        "stream failover replaying prompt + emitted "
                        "tokens, POST /admin/drain/{replica} zero-drop "
                        "rolling restarts")
    p.add_argument("--replica-urls",
                   default=os.environ.get("REPLICA_URLS", ""),
                   help="comma-separated base URLs of subprocess/remote "
                        "engines speaking the existing HTTP API, joined "
                        "to the fleet as members (the docker-compose "
                        "'router + engine services' shape); combines "
                        "with --replicas local members")
    p.add_argument("--placement", choices=("affinity", "least_loaded"),
                   default=os.environ.get("PLACEMENT", "affinity"),
                   help="fleet placement policy: 'affinity' routes to "
                        "the replica whose prefix-cache radix tree "
                        "already holds the prompt's prefix, falling "
                        "back to least-loaded (with round-robin tie "
                        "rotation); 'least_loaded' skips the probe")
    p.add_argument("--drain-timeout-s", type=float,
                   default=float(os.environ.get("DRAIN_TIMEOUT_S", 30.0)),
                   help="drain budget: in-flight streams get this long "
                        "to finish on a draining replica before the "
                        "stragglers fail over (still zero dropped "
                        "streams)")
    p.add_argument("--no-migrate", action="store_true",
                   default=os.environ.get("MIGRATE", "").lower()
                   in ("0", "false", "no"),
                   help="disable KV page migration: failover and drain "
                        "fall back to recompute replay (prompt + every "
                        "emitted token) instead of shipping KV pages + "
                        "request state to a healthy member, and affinity "
                        "misses stop shipping cached prefixes")
    p.add_argument("--migrate-timeout-s", type=float,
                   default=float(os.environ.get("MIGRATE_TIMEOUT_S", 10.0)),
                   help="per-transfer migration budget: a transfer "
                        "(export + ship + import ack) past this aborts "
                        "and the stream falls back to recompute replay")
    p.add_argument("--tiers", default=os.environ.get("TIERS", ""),
                   help="SLO-aware replica tiers for the fleet router "
                        "(needs --replicas/--replica-urls): "
                        "'interactive=r0;bulk=r1,r2' maps members to "
                        "tiers by name (or tpN for every member at that "
                        "TP width; an @tpN suffix on the tier declares "
                        "the width a retiered member restarts at). "
                        "VIP/boost users and deadlined requests place "
                        "on the interactive tier, everything else on "
                        "bulk; cross-tier placement only under "
                        "journaled SLO burn-rate overflow or an empty "
                        "tier, and a TierBalancer retiers members "
                        "(drain -> migrate -> restart -> rejoin) as the "
                        "class mix shifts. Unknown tier names or a tier "
                        "with no members fail startup")
    # Elastic fleet (fleet/autoscaler.py): SLO-burn-driven sizing.
    p.add_argument("--autoscale", action="store_true",
                   default=os.environ.get("AUTOSCALE", "").lower()
                   in ("1", "true", "yes"),
                   help="elastic fleet sizing: a per-tier control loop "
                        "watches sustained SLO burn + queue backlog and "
                        "scales the fleet one member at a time "
                        "(provisioned members join via the normal probe "
                        "path; scale-down is always drain -> migrate -> "
                        "retire, never a kill). The bulk tier may scale "
                        "to zero overnight — its queued work parks at "
                        "the router and wakes the tier. Implies a fleet "
                        "even with --replicas 1")
    p.add_argument("--min-replicas", type=int,
                   default=int(os.environ.get("MIN_REPLICAS", 1)),
                   help="scale-down floor for the interactive tier (and "
                        "for an untiered elastic fleet); the bulk tier's "
                        "floor is 0 (scale-to-zero)")
    p.add_argument("--max-replicas", type=int,
                   default=int(os.environ.get("MAX_REPLICAS", 4)),
                   help="fleet-wide scale-up ceiling")
    p.add_argument("--scale-cooldown-s", type=float,
                   default=float(os.environ.get("SCALE_COOLDOWN_S", 30.0)),
                   help="anti-flap cooldown between scale events; the "
                        "burn/idle sustain windows derive from it "
                        "(pressure must hold cooldown/3 before a scale-"
                        "up, idleness a full cooldown before a scale-"
                        "down). Waking a scaled-to-zero tier bypasses it")
    p.add_argument("--preemptible",
                   default=os.environ.get("PREEMPTIBLE", ""),
                   help="comma-separated member names (r0, h1, ...) that "
                        "accept a spot-style termination notice (POST "
                        "/admin/preempt/{replica} or the fault plan's "
                        "'preempt' site): live streams migrate off "
                        "within the notice window, then the member "
                        "retires — zero dropped streams")
    p.add_argument("--router-overhead-budget-ms", type=float,
                   default=float(os.environ.get(
                       "ROUTER_OVERHEAD_BUDGET_MS", 50.0)),
                   help="bound on the router's own placement-decision "
                        "cost: the always-on self-profiler "
                        "(ollamamq_router_overhead_ms{site}) feeds a "
                        "windowed p99; above this budget the health "
                        "monitor fires the router_overhead alert. 0 "
                        "disables the alert (the timers stay on)")
    # Router HA (fleet/ha.py): warm-standby router with epoch fencing.
    p.add_argument("--ha", action="store_true",
                   default=os.environ.get("HA", "").lower()
                   in ("1", "true", "yes"),
                   help="run this fleet router as the HA PRIMARY: expose "
                        "the replication stream (GET /admin/ha/sync — "
                        "WAL records + decision-journal events + shadow "
                        "placement state) a --standby-of router tails, "
                        "stamp every member-facing call with the router "
                        "epoch, and on SIGTERM hand the fleet to the "
                        "caught-up standby instead of draining. "
                        "Requires --wal-dir and a fleet")
    p.add_argument("--standby-of", default=os.environ.get("STANDBY_OF", ""),
                   help="run as the warm STANDBY of the primary router at "
                        "this base URL: tail its replication stream into "
                        "local WAL/journal replicas, shed clients with "
                        "503 + Retry-After meanwhile, and after "
                        "--takeover-grace-s of heartbeat loss PROMOTE — "
                        "bump the epoch (fencing the old primary if it "
                        "revives), re-register the members, replay every "
                        "unfinished stream through recovery, then serve. "
                        "Requires --wal-dir and --replica-urls naming "
                        "the same members the primary serves")
    p.add_argument("--takeover-grace-s", type=float,
                   default=float(os.environ.get("TAKEOVER_GRACE_S", 3.0)),
                   help="standby heartbeat-loss grace before promotion; "
                        "sync polls run at grace/4 (floored at 50ms)")
    p.add_argument("--no-federate-metrics", action="store_true",
                   default=os.environ.get("FEDERATE_METRICS", "").lower()
                   in ("0", "false", "no"),
                   help="disable metrics federation: the router's "
                        "/metrics stops re-exporting HTTP members' "
                        "series under a replica label (members stay "
                        "scrapable individually)")
    # Graceful degradation under load.
    p.add_argument("--max-queued", type=int, default=0,
                   help="global queued-request cap: past it, enqueues are "
                        "shed with 503 + Retry-After (derived from the "
                        "observed completion rate); 0 = unbounded")
    p.add_argument("--max-queued-per-user", type=int, default=0,
                   help="per-user queued-request cap: past it, that "
                        "user's enqueues are shed with 429 + Retry-After; "
                        "0 = unbounded")
    p.add_argument("--no-preempt", action="store_true",
                   help="disable preemption-with-recompute: decode-time "
                        "KV-pool exhaustion then errors the request "
                        "explicitly (done_reason kv_exhausted) instead "
                        "of preempting a victim for later recompute")
    p.add_argument("--preempt-max", type=int, default=3,
                   help="anti-livelock budget: preemptions allowed per "
                        "request before it holds its reservation and is "
                        "never picked as a victim again")
    p.add_argument("--fault-plan", default="",
                   help="deterministic fault-injection plan (JSON; see "
                        "ollamamq_tpu/testing/faults.py) wired into the "
                        "dispatch/allocation seams — chaos benching; "
                        "malformed plans fail startup loudly")
    # SLOs + alerting.
    p.add_argument("--slo-ttft-ms", type=float, default=0.0,
                   help="TTFT latency objective in ms (enqueue to first "
                        "token); 0 = no TTFT SLO. Violations burn the "
                        "error budget; multi-window burn-rate alerts "
                        "surface in /health, /metrics, and the TUI")
    p.add_argument("--slo-tpot-ms", type=float, default=0.0,
                   help="per-token decode latency objective in ms; "
                        "0 = no TPOT SLO")
    p.add_argument("--slo-target", type=float, default=0.99,
                   help="good-fraction target for both SLOs (0.99 = 1%% "
                        "error budget)")
    # Telemetry.
    p.add_argument("--log-file", default=os.environ.get("OLLAMAMQ_LOG_FILE",
                                                        ""),
                   help="write logs to this file as structured JSON lines "
                        "(one object per line, request-scoped lines carry "
                        "req_id). Default: ollamamq.log in CWD when the "
                        "TUI owns the terminal, stdout otherwise")
    p.add_argument("--log-rotate-mb", type=float, default=64.0,
                   help="rotate --log-file when it reaches this size "
                        "(MB); 0 disables rotation")
    p.add_argument("--log-keep", type=int, default=3,
                   help="rotated --log-file generations kept "
                        "(file.1 .. file.N)")
    p.add_argument("--journal-ring", type=int, default=2048,
                   help="scheduler decision-journal records kept for "
                        "GET /debug/journal (the engine flight recorder)")
    p.add_argument("--journal-file", default=os.environ.get(
                       "OLLAMAMQ_JOURNAL_FILE", ""),
                   help="spill every decision-journal record to this "
                        "JSONL file (analyze/replay offline with "
                        "`python -m ollamamq_tpu.tools.journal`)")
    p.add_argument("--journal-rotate-mb", type=float, default=64.0,
                   help="rotate --journal-file at this size (MB); "
                        "0 disables rotation")
    p.add_argument("--journal-keep", type=int, default=3,
                   help="rotated --journal-file generations kept")
    p.add_argument("--journal-sample", type=float,
                   default=float(os.environ.get("JOURNAL_SAMPLE", 1.0)),
                   help="probabilistic sampling rate (0, 1] for high-"
                        "rate journal kinds (batch/chunk/page_*/"
                        "broadcast) so the ring and spill survive 100x "
                        "event rates; decision-critical kinds (shed/"
                        "preempt/finish/migrate_*/recover_*) are always "
                        "retained. 1.0 (default) records everything; "
                        "tools/journal check understands sampled traces")
    # Crash durability: admission WAL + cold-restart recovery +
    # client-resumable streams (durability/).
    p.add_argument("--wal-dir", default=os.environ.get("WAL_DIR", ""),
                   help="write-ahead request log directory: every "
                        "accepted generation request is durably recorded "
                        "(batched fsync) BEFORE the enqueue is ACKed, "
                        "emitted tokens are logged behind it, and a "
                        "restart replays unfinished requests token-exact "
                        "— disconnected clients reattach via GET "
                        "/api/stream/{req_id}?from=N. Empty = no WAL")
    p.add_argument("--wal-fsync-ms", type=float,
                   default=float(os.environ.get("WAL_FSYNC_MS", 20.0)),
                   help="WAL group-commit window in ms: admissions wait "
                        "at most this long for their covering fsync; a "
                        "crash loses at most this much emitted-token "
                        "progress (regenerated identically on recovery "
                        "under greedy decoding). 0 = fsync every append")
    p.add_argument("--no-wal", action="store_true",
                   help="disable the admission WAL even when WAL_DIR is "
                        "set in the environment")
    p.add_argument("--stop-grace-s", type=float,
                   default=float(os.environ.get("STOP_GRACE_S", 30.0)),
                   help="graceful-shutdown budget: on SIGTERM/SIGINT the "
                        "server stops admission, lets in-flight streams "
                        "drain up to this long, flushes + fsyncs the "
                        "journal and WAL, then exits 0 (stragglers stay "
                        "in the WAL and recover on the next start)")
    p.add_argument("--metrics-buckets", default="",
                   help="comma-separated upper bounds (ms) for the latency "
                        "histograms on /metrics (ttft/tpot/step/prefill); "
                        "default is a 1ms..30s ladder")
    p.add_argument("--trace-ring", type=int, default=512,
                   help="finished request traces kept for /debug/trace "
                        "(Chrome trace-event export)")
    p.add_argument("--token-fairness", action="store_true",
                   help="fair-share by served tokens instead of request count")
    p.add_argument("--spmd", action="store_true",
                   help="multi-host SPMD serving: process 0 runs the "
                        "scheduler+HTTP and broadcasts step plans; other "
                        "processes replay them (requires jax.distributed "
                        "env vars)")
    p.add_argument("--cpu", type=int, nargs="?", const=1, default=0,
                   metavar="N",
                   help="run on the CPU platform with N virtual devices "
                        "(development / CI). Without it — and without "
                        "JAX_PLATFORMS=cpu in the environment — a real "
                        "engine refuses to start unless jax finds a TPU")
    return p


class JsonLineFormatter(logging.Formatter):
    """Structured log lines: one JSON object per line. Request-scoped
    records (logged with extra={"req_id": N}) carry the id, so a log line
    correlates directly with GET /debug/requests/{id}."""

    def format(self, record: logging.LogRecord) -> str:
        import json

        out = {
            "ts": self.formatTime(record, "%Y-%m-%dT%H:%M:%S")
            + f".{int(record.msecs):03d}Z",
            "level": record.levelname,
            "logger": record.name,
            "msg": record.getMessage(),
        }
        rid = getattr(record, "req_id", None)
        if rid is not None:
            out["req_id"] = rid
        if record.exc_info:
            out["exc"] = self.formatException(record.exc_info)
        return json.dumps(out, ensure_ascii=False)


def setup_logging(use_tui: bool, log_file: str = "",
                  rotate_mb: float = 64.0, keep: int = 3) -> None:
    """File logging (JSON lines) when --log-file names a path, or — TUI
    owning the terminal with no explicit path — the reference's
    ollamamq.log default; human-readable stdout otherwise. File logs
    rotate at --log-rotate-mb keeping --log-keep generations, so a
    long soak run cannot fill the disk."""
    level = os.environ.get("OLLAMAMQ_LOG", "INFO").upper()
    if not log_file and use_tui:
        log_file = "ollamamq.log"  # reference default (main.rs:66-87)
    if log_file:
        if rotate_mb and rotate_mb > 0:
            from logging.handlers import RotatingFileHandler

            handler: logging.Handler = RotatingFileHandler(
                log_file, maxBytes=int(rotate_mb * 1e6),
                backupCount=max(1, keep))
        else:
            handler = logging.FileHandler(log_file)
        handler.setFormatter(JsonLineFormatter())
    else:
        handler = logging.StreamHandler(sys.stdout)
        handler.setFormatter(logging.Formatter(
            "%(asctime)s %(levelname)s %(name)s: %(message)s"
        ))
    logging.basicConfig(level=getattr(logging, level, logging.INFO),
                        handlers=[handler])


def _fake_latency() -> float:
    """Per-token delay for --fake-engine servers (env
    FAKE_TOKEN_LATENCY_S): crash/restart and drain tests need streams
    that stay in flight long enough for the chaos to land mid-decode."""
    try:
        return max(0.0, float(os.environ.get("FAKE_TOKEN_LATENCY_S", 0.0)))
    except ValueError:
        return 0.0


def _member_mesh(cfg, index: int):
    """Fleet member `index`'s own slice of the local devices: without it
    every in-process replica's weights and KV pool would land on device
    0. A member needs dp*ep*tp devices; slices follow one another
    and wrap around when the fleet outgrows the devices (said in the
    log — members then share a device)."""
    import jax

    from ollamamq_tpu.parallel.mesh import make_mesh

    if cfg.tp == -1:
        return None  # "all devices": the engine builds that mesh itself
    devs = jax.devices()
    k = cfg.dp * cfg.ep * cfg.tp
    if k > len(devs):
        raise ValueError(f"a fleet member needs {k} devices, "
                         f"{len(devs)} available")
    picked = [devs[(index * k + j) % len(devs)] for j in range(k)]
    if (index + 1) * k > len(devs):
        logging.getLogger("ollamamq").warning(
            "fleet member %d shares device(s) %s with an earlier member "
            "(%d devices for the fleet)", index,
            [str(d) for d in picked], len(devs))
    return make_mesh(dp=cfg.dp, tp=cfg.tp, ep=cfg.ep, devices=picked)


def install_graceful_shutdown(engine, grace_s: float) -> None:
    """SIGTERM/SIGINT => zero-drop shutdown: stop admission (new
    enqueues shed with 503), let in-flight streams drain up to
    `grace_s`, flush + fsync the journal and WAL, exit 0. Stragglers
    past the grace stay recorded in the WAL (when --wal-dir is on) and
    recover token-exact on the next start — so `docker stop` with an
    adequate stop_grace_period drops nothing either way."""
    import signal
    import threading
    import time

    log = logging.getLogger("ollamamq")
    fired = threading.Event()

    def run(signum: int) -> None:
        # HA primary: hand the fleet to the caught-up standby (it
        # promotes with why="handover") instead of draining the world.
        # ha_handover quiesces first either way; False (no standby, or
        # it never confirmed) falls through to the normal drain below.
        handover = getattr(engine, "ha_handover", None)
        if handover is not None:
            try:
                if handover(timeout_s=min(10.0, max(1.0, grace_s))):
                    log.warning("signal %d: fleet handed over to the "
                                "standby; exiting 0", signum)
                    engine.stop()
                    os._exit(0)
            except Exception:  # noqa: BLE001
                log.exception("HA handover failed; draining instead")
        log.warning("signal %d: graceful shutdown — admission stopped, "
                    "draining in-flight work (grace %.0fs)",
                    signum, grace_s)
        try:
            engine.quiesce()
        except Exception:  # noqa: BLE001
            log.exception("quiesce failed; stopping anyway")
        deadline = time.monotonic() + grace_s
        while time.monotonic() < deadline:
            try:
                if engine.inflight_count() == 0:
                    break
            except Exception:  # noqa: BLE001
                break
            time.sleep(0.1)
        # The engine finishing a stream and the HTTP layer flushing its
        # final frames to the socket are asynchronous: give the event
        # loop a moment to drain before the hard exit cuts connections.
        time.sleep(min(1.0, max(0.0, deadline - time.monotonic())))
        try:
            left = engine.inflight_count()
        except Exception:  # noqa: BLE001
            left = -1
        if left:
            log.warning("grace expired with %s stream(s) still in "
                        "flight; they remain in the WAL and recover on "
                        "the next start", left)
        engine.stop()  # joins the loop, fsyncs journal + WAL
        log.warning("graceful shutdown complete; exiting 0")
        os._exit(0)

    def handler(signum, frame):  # noqa: ARG001
        if fired.is_set():
            os._exit(0)  # second signal: operator means NOW
        fired.set()
        threading.Thread(target=run, args=(signum,), daemon=True,
                         name="graceful-shutdown").start()

    signal.signal(signal.SIGTERM, handler)
    signal.signal(signal.SIGINT, handler)


def main(argv=None) -> int:
    # The start-up ledger (telemetry/stepprof.py START_PHASES): `import`
    # ends here; `backend` is all of this function that no later phase
    # names, up to `serve` below.
    from ollamamq_tpu.telemetry import stepprof

    stepprof.PROFILER.startup_enter("backend")
    args = build_parser().parse_args(argv)
    use_tui = not args.no_tui and sys.stdout.isatty()
    setup_logging(use_tui, log_file=args.log_file,
                  rotate_mb=args.log_rotate_mb, keep=args.log_keep)
    log = logging.getLogger("ollamamq")
    if not (0.0 < args.slo_target < 1.0):
        log.error("--slo-target must be in (0, 1), got %s", args.slo_target)
        return 2
    if args.max_queued < 0 or args.max_queued_per_user < 0 \
            or args.preempt_max < 0:
        log.error("--max-queued / --max-queued-per-user / --preempt-max "
                  "must be >= 0")
        return 2
    if args.journal_ring < 1 or args.journal_keep < 1 or args.log_keep < 1:
        log.error("--journal-ring / --journal-keep / --log-keep "
                  "must be >= 1")
        return 2
    if args.token_granule < 1 or args.max_batch_tokens < 1:
        log.error("--token-granule / --max-batch-tokens must be >= 1")
        return 2
    if args.spec_k < 1 or not (0.0 <= args.spec_min_accept <= 1.0):
        log.error("--spec-k must be >= 1 and --spec-min-accept in [0, 1]")
        return 2
    if args.journal_rotate_mb < 0 or args.log_rotate_mb < 0:
        log.error("--journal-rotate-mb / --log-rotate-mb must be >= 0 "
                  "(0 disables rotation)")
        return 2
    if not (0.0 < args.journal_sample <= 1.0):
        log.error("--journal-sample must be in (0, 1], got %s",
                  args.journal_sample)
        return 2
    if args.wal_fsync_ms < 0 or args.stop_grace_s < 0:
        log.error("--wal-fsync-ms / --stop-grace-s must be >= 0")
        return 2
    # Scheduler policy fails fast BEFORE any device work — argparse
    # doesn't validate env-supplied defaults, so a typo'd SCHEDULER env
    # must die here, not at the first admission pass.
    from ollamamq_tpu.config import validate_scheduler

    sched_err = validate_scheduler(args.scheduler)
    if sched_err is not None:
        log.error("%s", sched_err)
        return 2
    fleet_urls = [u.strip() for u in args.replica_urls.split(",")
                  if u.strip()]
    if args.replicas < 0 or (args.replicas == 0 and not fleet_urls):
        log.error("--replicas must be >= 1 (0 only with --replica-urls)")
        return 2
    if args.drain_timeout_s <= 0:
        log.error("--drain-timeout-s must be > 0")
        return 2
    if args.migrate_timeout_s <= 0:
        log.error("--migrate-timeout-s must be > 0")
        return 2
    if args.router_overhead_budget_ms < 0:
        log.error("--router-overhead-budget-ms must be >= 0 "
                  "(0 disables the alert)")
        return 2
    roster_names = ([f"r{i}" for i in range(max(0, args.replicas))]
                    + [f"h{j}" for j in range(len(fleet_urls))])
    if args.autoscale:
        # Autoscale knobs fail fast BEFORE any device work — argparse
        # doesn't validate env-supplied defaults (MIN_REPLICAS etc.), so
        # a bad compose file must die here, not at the first scale
        # decision.
        from ollamamq_tpu.config import validate_autoscale

        scale_err = validate_autoscale(
            args.min_replicas, args.max_replicas, args.scale_cooldown_s,
            replicas=args.replicas + len(fleet_urls))
        if scale_err is not None:
            log.error("%s", scale_err)
            return 2
    # HA knobs fail fast BEFORE any device work — argparse doesn't
    # validate env-supplied defaults (HA/STANDBY_OF/TAKEOVER_GRACE_S),
    # so a bad compose file must die here, not at the first heartbeat.
    from ollamamq_tpu.config import validate_ha

    ha_err = validate_ha(args.ha, args.standby_of or None,
                         args.takeover_grace_s,
                         (None if args.no_wal else (args.wal_dir or None)),
                         args.replica_urls or None)
    if ha_err is not None:
        log.error("%s", ha_err)
        return 2
    if args.ha and args.replicas <= 1 and not fleet_urls \
            and not args.autoscale:
        log.error("--ha needs a fleet (--replicas > 1, --replica-urls, "
                  "or --autoscale): the standby re-registers those "
                  "members at takeover")
        return 2
    if args.preemptible:
        want = [s.strip() for s in args.preemptible.split(",")
                if s.strip()]
        if args.replicas <= 1 and not fleet_urls and not args.autoscale:
            log.error("--preemptible needs a fleet (--replicas > 1, "
                      "--replica-urls, or --autoscale)")
            return 2
        unknown = sorted(set(want) - set(roster_names))
        if unknown:
            log.error("--preemptible names unknown members: %s "
                      "(fleet: %s)", ", ".join(unknown),
                      ", ".join(roster_names))
            return 2
    if args.tiers:
        # Tier spec fails fast BEFORE any device work: unknown tier
        # names, selectors naming no member, and a tier with no members
        # all kill the process at startup, not at the first placement.
        if args.replicas <= 1 and not fleet_urls:
            log.error("--tiers needs a fleet "
                      "(--replicas > 1 and/or --replica-urls)")
            return 2
        from ollamamq_tpu.config import validate_tiers

        roster = ([(f"r{i}", args.tp) for i in range(args.replicas)]
                  + [(f"h{j}", None) for j in range(len(fleet_urls))])
        tiers_err = validate_tiers(args.tiers, roster)
        if tiers_err is not None:
            log.error("invalid --tiers: %s", tiers_err)
            return 2
    # Quantization flags fail fast BEFORE any device/runtime work: an
    # unsupported combination must kill the process at startup, not at
    # the first dispatch (same validator the SPMD worker and the
    # runtimes run).
    from ollamamq_tpu.config import validate_quant_config

    quant_err = validate_quant_config(
        args.weights_dtype, args.kv_dtype,
        model_names=[m.strip() for m in args.models.split(",") if m.strip()])
    if quant_err is not None:
        log.error("%s", quant_err)
        return 2
    # ...and what a model's per-sequence state cannot be served with.
    from ollamamq_tpu.config import get_model_config
    from ollamamq_tpu.engine.kv_cache import refusal

    for name in (m.strip() for m in args.models.split(",") if m.strip()):
        served = get_model_config(name)
        refused = served and refusal(
            served, spec=args.spec,
            mesh_shape={"tensor": args.tp, "expert": args.ep},
            kv_dtype=args.kv_dtype, weights_dtype=args.weights_dtype,
            prefix_cache=args.prefix_cache)
        if refused:
            log.error("%s", refused)
            return 2
    if args.fault_plan:
        # Schema-check the plan BEFORE any engine/device work: a typo'd
        # chaos plan must fail the process at startup, not mid-traffic.
        from ollamamq_tpu.testing.faults import FaultPlan, FaultPlanError

        try:
            FaultPlan.load(args.fault_plan)
        except FaultPlanError as e:
            log.error("invalid --fault-plan: %s", e)
            return 2

    from ollamamq_tpu.platform_force import force_cpu, place_compile_cache

    if args.cpu:
        from ollamamq_tpu.parallel.distributed import multiprocess_configured

        # Multi-process only: defer the backend-touch verification, since
        # jax.distributed.initialize below must run before the first
        # backend touch. Single-process keeps the loud platform check.
        force_cpu(args.cpu, check=not multiprocess_configured())
    log.info("compile cache: %s", place_compile_cache())

    from ollamamq_tpu.config import EngineConfig
    from ollamamq_tpu.core import Fairness

    if args.metrics_buckets:
        from ollamamq_tpu.telemetry import schema as tm_schema

        try:
            bounds = tuple(float(b) for b in args.metrics_buckets.split(",")
                           if b.strip())
        except ValueError:
            log.error("invalid --metrics-buckets %r (want comma-separated "
                      "numbers)", args.metrics_buckets)
            return 2
        if not bounds:
            log.error("--metrics-buckets must name at least one bound")
            return 2
        tm_schema.configure_latency_buckets(bounds)

    # Multi-host control plane: no-op unless JAX_COORDINATOR_ADDRESS /
    # JAX_NUM_PROCESSES are set (or a TPU pod auto-detects). After this,
    # jax.devices() spans all hosts and tp=-1 shards over the whole pod.
    from ollamamq_tpu.parallel import distributed

    distributed.initialize()

    if not args.fake_engine and args.replicas > 0 and not (
            args.cpu or os.environ.get("JAX_PLATFORMS", "").lower() == "cpu"):
        # A server started for the chip that finds none must not carry
        # on from the host: the CPU is served only when asked for.
        import jax

        if jax.default_backend() != "tpu":
            log.error("no TPU: jax's default backend is %r. Pass --cpu N "
                      "(or set JAX_PLATFORMS=cpu) to serve from the CPU on "
                      "purpose.", jax.default_backend())
            return 3

    model_names = [m.strip() for m in args.models.split(",") if m.strip()]
    checkpoints = {}
    for pair in args.checkpoints.split(","):
        if "=" in pair:
            name, path = pair.split("=", 1)
            checkpoints[name.strip()] = path.strip()
    models = {name: checkpoints.get(name) for name in model_names}

    ecfg = EngineConfig(
        model=model_names[0] if model_names else "llama3:8b",
        max_slots=args.max_slots,
        num_pages=args.num_pages,
        page_size=args.page_size,
        max_pages_per_seq=args.max_pages_per_seq,
        max_new_tokens=args.max_new_tokens,
        decode_steps_per_iter=args.decode_steps,
        max_batch_tokens=args.max_batch_tokens,
        token_granule=args.token_granule,
        spec=args.spec,
        spec_k=args.spec_k,
        spec_min_accept=args.spec_min_accept,
        scheduler=args.scheduler,
        prefix_cache=args.prefix_cache,
        prefix_cache_min_pages=args.prefix_cache_min_pages,
        dp=args.dp,
        tp=args.tp,
        ep=args.ep,
        trace_ring=args.trace_ring,
        slo_ttft_ms=args.slo_ttft_ms or None,
        slo_tpot_ms=args.slo_tpot_ms or None,
        slo_target=args.slo_target,
        preempt=not args.no_preempt,
        preempt_max=args.preempt_max,
        max_queued=args.max_queued,
        max_queued_per_user=args.max_queued_per_user,
        fault_plan=args.fault_plan or None,
        journal_ring=args.journal_ring,
        journal_file=args.journal_file or None,
        journal_rotate_mb=args.journal_rotate_mb,
        journal_keep=args.journal_keep,
        journal_sample=args.journal_sample,
        wal_dir=(None if args.no_wal else (args.wal_dir or None)),
        wal_fsync_ms=args.wal_fsync_ms,
        ha=args.ha,
        standby_of=args.standby_of or None,
        takeover_grace_s=args.takeover_grace_s,
        weights_dtype=args.weights_dtype,
        kv_dtype=args.kv_dtype,
        replicas=args.replicas,
        placement=args.placement,
        drain_timeout_s=args.drain_timeout_s,
        migrate=not args.no_migrate,
        migrate_timeout_s=args.migrate_timeout_s,
        tiers=args.tiers or None,
        autoscale=args.autoscale,
        min_replicas=args.min_replicas,
        max_replicas=args.max_replicas,
        scale_cooldown_s=args.scale_cooldown_s,
        preemptible=args.preemptible or None,
        router_overhead_budget_ms=args.router_overhead_budget_ms,
        federate_metrics=not args.no_federate_metrics,
    )
    fairness = Fairness.TOKENS if args.token_fairness else Fairness.REQUESTS

    standby = None
    if args.spmd and args.fake_engine:
        log.error("--spmd and --fake-engine are mutually exclusive")
        return 2
    if (args.replicas > 1 or fleet_urls or args.autoscale) and args.spmd:
        log.error("--replicas/--replica-urls/--autoscale and --spmd are "
                  "mutually exclusive (the SPMD engine already owns a "
                  "worker pool; run the fleet router over separate SPMD "
                  "services via --replica-urls from a non-SPMD front-end "
                  "instead)")
        return 2
    if args.replicas > 1 or fleet_urls or args.autoscale:
        import dataclasses
        import itertools

        from ollamamq_tpu.fleet import FleetRouter, HttpMember, LocalMember

        # Members serve uncapped what the router placed (the router owns
        # the fleet-wide bounded-admission caps), keep no blocklist (the
        # router blocks at ingress), and leave the journal spill AND the
        # admission WAL to the router (a member WAL would double-record
        # and double-recover every stream).
        member_cfg = dataclasses.replace(
            ecfg, max_queued=0, max_queued_per_user=0, journal_file=None,
            wal_dir=None, tiers=None, ha=False, standby_of=None)
        # Tiered fleets: members assigned to a tier that declares an
        # @tpN width START at that width; the same factory rebuilds a
        # member at a new width when the TierBalancer regroups it.
        tier_assign, tier_widths = {}, {}
        if args.tiers:
            from ollamamq_tpu.config import assign_tiers

            roster = ([(f"r{i}", args.tp) for i in range(args.replicas)]
                      + [(f"h{j}", None)
                         for j in range(len(fleet_urls))])
            tier_assign, tier_widths = assign_tiers(args.tiers, roster)

        # Members provisioned later take the device slices after the
        # seed members', wrapping around when the devices run out.
        next_index = itertools.count(args.replicas)

        def _member_factory(base_cfg, index=None):
            def build(tp=None):
                cfg = (base_cfg if tp in (None, base_cfg.tp)
                       else dataclasses.replace(base_cfg, tp=tp))
                if args.fake_engine:
                    from ollamamq_tpu.engine.fake import FakeEngine

                    return FakeEngine(cfg, models=models,
                                      blocklist_path=None,
                                      fairness=fairness,
                                      token_latency_s=_fake_latency())
                from ollamamq_tpu.engine.engine import TPUEngine

                i = index if index is not None else next(next_index)
                return TPUEngine(cfg, models=models, blocklist_path=None,
                                 fairness=fairness,
                                 mesh=_member_mesh(cfg, i))
            return build

        members = []
        for i in range(args.replicas):
            name = f"r{i}"
            width = tier_widths.get(tier_assign.get(name))
            cfg_i = (member_cfg if width in (None, member_cfg.tp)
                     else dataclasses.replace(member_cfg, tp=width))
            factory = _member_factory(cfg_i, i)
            members.append(LocalMember(name, factory(),
                                       engine_factory=factory))
        for j, url in enumerate(fleet_urls):
            members.append(HttpMember(f"h{j}", url,
                                      timeout_s=args.timeout))
        provisioner = None
        if args.autoscale:
            if args.fake_engine:
                # The subprocess harness: scale-ups spawn real
                # `python -m ollamamq_tpu.cli --fake-engine` servers on
                # free ports and join them as HTTP members — the same
                # member shape the docker-compose fleet runs. The
                # member config rides as argv (router-owned caps, WAL,
                # journal spill all stay OFF member-side).
                from ollamamq_tpu.fleet.autoscaler import (
                    SubprocessProvisioner)

                member_argv = [
                    "--fake-engine", "--models", args.models,
                    "--scheduler", args.scheduler,
                    "--max-slots", str(args.max_slots),
                    "--max-new-tokens", str(args.max_new_tokens),
                ]
                provisioner = SubprocessProvisioner(
                    member_argv, env={"JAX_PLATFORMS": "cpu"})
            else:
                # Real engines divide the local chips: provision in-
                # process replicas from the same factory the seed
                # members use. A cloud provisioner (TPU VM create/
                # delete) drops in via FleetRouter(provisioner=...).
                from ollamamq_tpu.fleet.autoscaler import LocalProvisioner

                provisioner = LocalProvisioner(
                    _member_factory(member_cfg))
        # A standby's router must not attach a primary-side coordinator
        # at construction — it becomes one only at promotion.
        router_cfg = (dataclasses.replace(ecfg, ha=False)
                      if args.standby_of else ecfg)
        engine = FleetRouter(
            members, router_cfg, blocklist_path=args.blocklist,
            fairness=fairness, placement=args.placement,
            drain_timeout_s=args.drain_timeout_s,
            provisioner=provisioner)
        if args.standby_of:
            from ollamamq_tpu.fleet.ha import HAStandby

            standby = HAStandby(engine, args.standby_of)
            engine.ha = standby
            engine.accepting = False  # shed until promotion opens the gate
    elif args.spmd:
        import jax

        from ollamamq_tpu.parallel.mesh import make_mesh

        # SPMD with an unspecified mesh means "the whole pod": default the
        # tensor axis to all global devices so worker hosts own shards.
        tp = args.tp
        if (args.dp, args.ep, tp) == (1, 1, 1):
            tp = -1
        mesh = make_mesh(dp=args.dp, tp=tp, ep=args.ep)
        if not distributed.is_primary():
            # Worker host: replay the primary's step plans until shutdown.
            from ollamamq_tpu.engine import spmd

            log.info("SPMD worker %d starting for %s",
                     jax.process_index(), model_names)
            spmd.run_worker(models, ecfg, mesh)
            return 0

        from ollamamq_tpu.engine.spmd import SPMDEngine

        engine = SPMDEngine(ecfg, models=models, blocklist_path=args.blocklist,
                            fairness=fairness, mesh=mesh)
    elif args.fake_engine:
        from ollamamq_tpu.engine.fake import FakeEngine

        engine = FakeEngine(ecfg, models=models, blocklist_path=args.blocklist,
                            fairness=fairness,
                            token_latency_s=_fake_latency())
    else:
        from ollamamq_tpu.engine.engine import TPUEngine

        engine = TPUEngine(ecfg, models=models, blocklist_path=args.blocklist,
                           fairness=fairness)
    # Every runtime is built: `serve` lasts until the HTTP server's
    # start-up hook (server/app.py) calls the process ready.
    stepprof.PROFILER.startup_enter("serve")
    if standby is not None:
        # The standby's router stays UNSTARTED until promotion — no
        # member probes, no placements, just the replication tail.
        # Clients shed with 503 + Retry-After (takeover-cost EMA).
        standby.start()
        log.warning("warm standby: tailing primary %s "
                    "(takeover grace %.1fs)",
                    args.standby_of, args.takeover_grace_s)
    else:
        engine.start()

    from ollamamq_tpu.server.app import Server

    server = Server(engine, timeout_s=args.timeout,
                    allow_all_routes=args.allow_all_routes)
    app = server.build_app()
    log.info("serving %s on %s:%d (tui=%s)", model_names, args.host, args.port, use_tui)

    if use_tui:
        import threading

        from aiohttp import web as aioweb

        from ollamamq_tpu.admin.tui import run_tui

        # Server on a background thread; TUI owns the terminal (main thread),
        # like the reference (main.rs:134-150). TUI exit ends the process.
        def serve():
            aioweb.run_app(app, host=args.host, port=args.port,
                           print=None, handle_signals=False)

        t = threading.Thread(target=serve, daemon=True, name="http")
        t.start()
        run_tui(engine, server.registry)
        engine.stop()
        return 0

    from aiohttp import web as aioweb

    # Signals are ours, not aiohttp's: SIGTERM/SIGINT run the zero-drop
    # drain (stop admission -> drain -> fsync journal+WAL -> exit 0)
    # instead of aiohttp's immediate GracefulExit, which would cut live
    # streams mid-generation.
    install_graceful_shutdown(engine, args.stop_grace_s)
    aioweb.run_app(app, host=args.host, port=args.port, print=None,
                   handle_signals=False)
    engine.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
