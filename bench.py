"""Benchmark: decode throughput (tok/s/chip) + prefill TTFT through the
real engine runtime on the TPU jax finds. Without one it prints a
structured error and exits non-zero; `--cpu` smoke-tests the harness on
the CPU on purpose, and every record names the platform, device kind and
device count it ran on.

Workload = BASELINE.json config 4's shape: a full decode batch of
concurrent sequences sharing every step (the reference's ceiling is one
request per backend; the TPU engine's is `--slots` per chip). Prints ONE
JSON line: {"metric", "value", "unit", "vs_baseline", ...extras}.
vs_baseline is against the 2000 tok/s/chip north-star target
(BASELINE.md — the reference itself publishes no numbers).

Usage: python bench.py [--model llama3.2:1b] [--slots 64] [--steps 256]
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import threading
import time


# What jax runs on, as jax reports it; filled in by main() once the
# backend is up. Every record — result and error alike — carries it, so
# no number is ever read without the device it came from.
_DEVICE = {"platform": None, "device_kind": None, "device_count": 0}


def _emit_error(msg: str, **extras) -> None:
    """Structured failure line: same shape as the success line so the
    driver's JSON parse always gets a record."""
    rec = {
        "metric": "decode_tok_per_s_per_chip",
        "value": 0.0,
        "unit": "tok/s/chip",
        "vs_baseline": 0.0,
        "error": msg,
        **_DEVICE,
        **extras,
    }
    # Error lines carry whatever the step profiler saw before the
    # failure — a round that died mid-ladder still shows its compile
    # walls and partial phase timings to the regression sentinel.
    try:
        from ollamamq_tpu.telemetry import stepprof
        rec["step_profile"] = stepprof.PROFILER.summary()
    except Exception:
        pass
    print(json.dumps(rec), flush=True)


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="llama3.2:1b")
    p.add_argument("--slots", type=int, default=64)
    p.add_argument("--prompt-len", type=int, default=128)
    p.add_argument("--steps", type=int, default=256, help="decode steps to time")
    p.add_argument("--chunk", type=int, default=16, help="decode steps per dispatch")
    p.add_argument("--warmup-steps", type=int, default=32)
    p.add_argument("--ttft-samples", type=int, default=8)
    p.add_argument("--page-size", type=int, default=32,
                   help="KV page size (tokens per page)")
    p.add_argument("--weights-dtype", choices=("bfloat16", "int8"),
                   default="bfloat16",
                   help="weight storage dtype under test (int8 = "
                        "per-channel symmetric, dequant-fused matmuls); "
                        "every BENCH record carries this field next to "
                        "'attention'/'spec' so A/B rounds are "
                        "attributable")
    p.add_argument("--kv-dtype", choices=("bfloat16", "int8"),
                   default="bfloat16",
                   help="KV page dtype under test (int8 = ~2x pages per "
                        "HBM byte); carried in every BENCH record")
    p.add_argument("--density", type=int, default=16,
                   help="requests per leg of the density scenario: the "
                        "SAME arrival trace against a bf16-KV pool and "
                        "an int8-KV pool sized to the SAME HBM byte "
                        "budget — reports concurrent-requests-at-equal-"
                        "HBM, preemptions/sheds per leg, and the int8-"
                        "vs-bf16 quality guardrail; 0 disables")
    p.add_argument("--max-batch-tokens", type=int, default=512,
                   help="ragged dispatch token budget")
    p.add_argument("--token-granule", type=int, default=16,
                   help="ragged stream-total padding granule")
    p.add_argument("--spec", action="store_true",
                   help="enable speculative decoding in the engine config "
                        "under test (n-gram drafts + ragged verify); every "
                        "BENCH record carries this field next to "
                        "'attention' so A/B rounds are attributable")
    p.add_argument("--spec-k", type=int, default=4,
                   help="max draft tokens per decode slot per dispatch")
    p.add_argument("--speculative", type=int, default=4,
                   help="requests in the speculative scenario (spec-off vs "
                        "spec-on decode throughput on a repetitive "
                        "generation regime + accept-rate/throttle readout "
                        "on a non-repetitive one; reports byte-identity "
                        "and rollback counts); 0 disables")
    p.add_argument("--scheduler", choices=("fcfs", "srpt", "edf"),
                   default="fcfs",
                   help="scheduling policy of the engine config under "
                        "test (fcfs = legacy FIFO-within-fair-share; "
                        "srpt = shortest-predicted-remaining-first; edf "
                        "= earliest-deadline-first); every BENCH record "
                        "carries this field next to 'attention'/'spec'/"
                        "'*_dtype' so A/B rounds are attributable")
    p.add_argument("--scheduling", type=int, default=32,
                   help="requests in the scheduling scenario: a bimodal "
                        "trace (a few long batch requests parked ahead "
                        "of many short interactive ones) run at the "
                        "same seed under fcfs and srpt, reporting "
                        "p50/p99 TTFT per leg with a pass gate (srpt "
                        "p99 TTFT <= fcfs) and the journal invariant + "
                        "zero-silent-truncation checks in-band; "
                        "0 disables")
    p.add_argument("--sampled", action="store_true",
                   help="use Ollama-default sampling (temp 0.8, repeat 1.1) "
                        "instead of greedy — exercises the full sampler")
    p.add_argument("--long-prompt", type=int, default=0,
                   help="if >0, also time chunked prefill of a prompt this "
                        "long (should exceed the largest bucket)")
    p.add_argument("--sweep-chunks", default="32,64",
                   help="comma-separated extra decode-chunk sizes to sweep "
                        "(same runtime; batch reset between legs); the "
                        "headline number is the best leg. Defaults on so "
                        "the driver's plain run self-tunes the dispatch "
                        "amortization (per-dispatch latency dominates "
                        "small chunks); pass '' for a single-chunk run")
    p.add_argument("--embed-model", default="",
                   help="if set, also measure embedding batch throughput "
                        "on this encoder model (BASELINE config 3)")
    p.add_argument("--shared-prefix", type=int, default=4,
                   help="users in the shared_prefix scenario (N requests "
                        "behind one common system prompt, TTFT measured "
                        "with the prefix cache off vs on); 0 disables")
    p.add_argument("--shared-prefix-len", type=int, default=512,
                   help="common system-prompt length in tokens (should be "
                        "a multiple of --page-size)")
    p.add_argument("--shared-prefix-tail", type=int, default=32,
                   help="per-user unique prompt tail in tokens")
    p.add_argument("--slo-burst", type=int, default=4,
                   help="bursts in the slo_burst scenario (bursty arrivals "
                        "measured against a TTFT SLO, with latency "
                        "attribution and burn rate reported); 0 disables")
    p.add_argument("--slo-burst-size", type=int, default=8,
                   help="requests arriving at once per burst")
    p.add_argument("--slo-ttft-ms", type=float, default=250.0,
                   help="TTFT objective for the slo_burst scenario (ms)")
    p.add_argument("--overload", type=int, default=24,
                   help="requests in the overload scenario (arrival rate "
                        "> capacity over a bounded queue, with fault "
                        "injection driving KV-pressure preemption and a "
                        "prefill fault; reports shed rate, preemptions, "
                        "recompute overhead, p99 TTFT); 0 disables")
    p.add_argument("--overload-queue-cap", type=int, default=0,
                   help="queued-request cap for the overload scenario "
                        "(0 = 2x slots)")
    p.add_argument("--fleet", type=int, default=240,
                   help="requests in the fleet scenario (kill-and-drain "
                        "chaos through the dispatcher-over-engines "
                        "router at ~10x the overload scenario's count; "
                        "0 disables). Runs on tiny members so the chaos "
                        "is cheap — the readout is robustness counters "
                        "(dropped_streams, failovers, affinity hits, "
                        "byte-identical resumed streams), not tok/s")
    p.add_argument("--fleet-replicas", type=int, default=2,
                   help="engine replicas behind the router in the fleet "
                        "scenario's chaos leg (the golden leg always "
                        "runs one)")
    p.add_argument("--tiering", type=int, default=32,
                   help="interactive requests in the tiering scenario "
                        "(0 disables): a seeded bimodal VIP/bulk trace "
                        "through a 2-tier fleet vs homogeneous fleets "
                        "at equal member count — per-tier p50/p99 TTFT, "
                        "aggregate tok/s, overflow/regroup counts, 0 "
                        "dropped streams, and a clean multi-spill "
                        "journal audit; pass gate: tiered <= the "
                        "latency-viable homogeneous fleet on p99 "
                        "interactive TTFT AND >= on aggregate tok/s")
    p.add_argument("--diurnal", type=int, default=24,
                   help="interactive requests across the diurnal "
                        "scenario's compressed day (0 disables): a "
                        "night-day-night sinusoidal + bursty trace "
                        "through an ELASTIC tiered fleet (--autoscale: "
                        "burn/backlog scale-up, drain-based scale-down, "
                        "a mid-day preemption notice, and a bulk "
                        "scale-to-zero + wake cycle) and through a "
                        "FIXED fleet at the elastic leg's peak size — "
                        "p99 interactive TTFT, member-hours, scale "
                        "events, 0 drops / 0 silent truncations, and "
                        "the multi-spill journal audit incl. scale "
                        "pairing; pass gate: elastic within tolerance "
                        "of fixed on p99 TTFT at strictly fewer "
                        "member-hours")
    p.add_argument("--crash-restart", type=int, default=8,
                   help="streams in the crash_restart scenario: real "
                        "server subprocesses (router + two HTTP member "
                        "services, admission WAL on) with a mid-run "
                        "kill -9 of a MEMBER (failover) and then of the "
                        "ROUTER itself; the router restarts, recovers "
                        "from the WAL, and clients reconnect via GET "
                        "/api/stream/{req_id}?from=N — gated on 0 "
                        "dropped streams, 0 silent truncations, "
                        "recovered_streams > 0, every resumed stream "
                        "byte-identical to the golden run, and the "
                        "fleet-wide journal audit clean across router + "
                        "member spills; 0 disables")
    p.add_argument("--router-ha", type=int, default=6,
                   help="streams in the router_ha scenario: real server "
                        "subprocesses — an HA primary router (--ha, WAL "
                        "on) + a warm standby (--standby-of) over two "
                        "HTTP member services; mid-decode kill -9 of the "
                        "PRIMARY, the standby replays the shipped "
                        "WAL/journal into a promotion (epoch bump, "
                        "member re-registration, WAL re-admission) and "
                        "clients reconnect to the STANDBY via GET "
                        "/api/stream/{req_id}?from=N; the dead primary "
                        "is then revived and must be FENCED (members "
                        "409 its stale epoch) — gated on 0 dropped "
                        "streams, 0 silent truncations, byte-identical "
                        "resumed streams vs the golden run, >=1 fenced "
                        "call, and the multi-spill journal audit "
                        "(takeover pairing + epoch monotonicity) clean; "
                        "0 disables")
    p.add_argument("--cpu", action="store_true",
                   help="run on the CPU platform on purpose (smoke-testing "
                        "the harness); without it, no TPU is an error")
    p.add_argument("--init-timeout", type=float, default=300.0,
                   help="seconds to wait for device init before emitting "
                        "a structured error and exiting")
    args = p.parse_args()

    # Everything that can fail on operator error must fail BEFORE the first
    # device touch: device init can hang, and an argument typo must not
    # spend the one chip claim.
    if (min(args.slots, args.prompt_len, args.steps, args.chunk,
            args.ttft_samples) < 1 or args.warmup_steps < 0
            or args.long_prompt < 0):
        _emit_error("invalid arguments: counts must be positive")
        return 2
    try:
        sweep_extra = [int(c) for c in args.sweep_chunks.split(",")
                       if c.strip()]
    except ValueError:
        _emit_error(f"invalid --sweep-chunks '{args.sweep_chunks}'")
        return 2
    if any(c < 1 for c in sweep_extra):
        _emit_error("sweep chunks must be positive")
        return 2

    from ollamamq_tpu.config import MODEL_CONFIGS, EngineConfig, get_model_config

    model_cfg = get_model_config(args.model)
    if model_cfg is None:
        _emit_error(f"unknown model '{args.model}'", known=sorted(MODEL_CONFIGS))
        return 2
    emodel_cfg = None
    if args.embed_model:
        emodel_cfg = get_model_config(args.embed_model)
        if emodel_cfg is None or not emodel_cfg.is_encoder:
            _emit_error(f"--embed-model '{args.embed_model}' is not an "
                        "encoder architecture")
            return 2

    from ollamamq_tpu.platform_force import force_cpu, place_compile_cache

    if args.cpu:
        force_cpu(1)
    place_compile_cache()

    import jax

    import numpy as np

    from ollamamq_tpu.engine.engine import ModelRuntime, device_summary
    from ollamamq_tpu.engine.request import Request
    from ollamamq_tpu.core import MQCore
    from ollamamq_tpu.ops.sampling import SamplingParams

    # Device init can hang (jax.devices() blocks inside the runtime
    # client), and so can the weight upload inside ModelRuntime init. A
    # daemon watchdog spanning both phases turns a hang into a structured
    # error line instead of a silent driver timeout.
    # --init-timeout <= 0 disables the watchdog.
    def arm_watchdog(done: threading.Event, budget: float, phase: str,
                     exit_code: int, msg: str) -> None:
        """One definition for every hang-to-structured-error conversion
        (init, embed): if `done` isn't set within `budget`, emit and
        exit. Disabled when --init-timeout <= 0."""
        if args.init_timeout <= 0:
            return

        def w():
            if not done.wait(budget):
                _emit_error(msg, phase=phase, **cell)
                os._exit(exit_code)

        threading.Thread(target=w, daemon=True).start()

    # The A/B matrix cell this run measures; rides every record.
    cell = dict(attention="ragged", weights_dtype=args.weights_dtype,
                kv_dtype=args.kv_dtype, spec=args.spec,
                scheduler=args.scheduler)
    init_done = threading.Event()
    arm_watchdog(init_done, args.init_timeout, "init", 3,
                 f"device/runtime init exceeded {args.init_timeout:.0f}s")
    try:
        _DEVICE.update(device_summary())
    except Exception as e:
        init_done.set()
        _emit_error(f"device init failed: {type(e).__name__}: {e}",
                    phase="init", **cell)
        return 3
    dev = jax.devices()[0]
    if not args.cpu and dev.platform != "tpu":
        # The measurement path fails without a chip: a CPU number is
        # never printed under a device metric's name by accident.
        init_done.set()
        _emit_error(f"no TPU: jax's default platform is '{dev.platform}' "
                    "(pass --cpu to smoke-test the harness on the CPU)",
                    phase="init", **cell)
        return 3
    # Pages: prompt + generated headroom for every slot. A leg consumes,
    # beyond prompt + steps: one compile dispatch (chunk), timed_decode's
    # unconditional first dispatch (chunk), warmup rounded UP to a chunk
    # multiple (chunk - 1 over), and the final timed dispatch overshooting
    # `steps` by up to chunk - 1 — so 4 chunks of slack on top of
    # warmup + steps covers the worst case for the largest sweep leg.
    max_chunk = max([args.chunk] + sweep_extra)
    tokens_per_seq = max(
        args.prompt_len + args.warmup_steps + args.steps + 4 * max_chunk,
        args.long_prompt + max_chunk,
    )
    page_size = args.page_size
    pages_per_seq = -(-tokens_per_seq // page_size) + 1
    ecfg = EngineConfig(
        model=args.model,
        max_slots=args.slots,
        num_pages=args.slots * pages_per_seq + 2,
        page_size=page_size,
        max_pages_per_seq=pages_per_seq,
        prefill_buckets=(args.prompt_len,),
        max_new_tokens=10**9,
        decode_steps_per_iter=args.chunk,
        max_batch_tokens=args.max_batch_tokens,
        token_granule=args.token_granule,
        spec=args.spec,
        spec_k=args.spec_k,
        scheduler=args.scheduler,
        weights_dtype=args.weights_dtype,
        kv_dtype=args.kv_dtype,
    )
    core = MQCore(None)
    t0 = time.monotonic()
    try:
        rt = ModelRuntime(args.model, model_cfg, ecfg)
        from ollamamq_tpu.engine.scheduler import make_policy

        # Scheduling-policy seam, attached like the engine does in
        # _attach_hooks (bench drives the runtime directly).
        rt.policy = make_policy(ecfg)
    except Exception as e:
        _emit_error(f"runtime init failed: {type(e).__name__}: {e}",
                    phase="runtime_init", device=str(dev), **cell)
        return 4
    finally:
        init_done.set()  # watchdog covers device + runtime init, not the run
    init_s = time.monotonic() - t0

    # Run-phase watchdog: a device that answers init and then wedges
    # mid-run would otherwise hang the whole bench with nothing emitted —
    # and the official run may get exactly one shot at a live chip.
    # INACTIVITY-based so long honest runs (many sweep legs, long prompts)
    # never trip it: the run touches the deadman after every dispatch, and
    # only `run_budget` seconds with NO completed dispatch counts as a
    # wedge. A single decode chunk or prefill taking that long is one.
    run_done = threading.Event()
    run_budget = max(600.0, args.init_timeout)
    deadman = {"t": time.monotonic(), "phase": "ttft"}

    def touch(phase: str) -> None:
        deadman["t"] = time.monotonic()
        deadman["phase"] = phase

    if args.init_timeout > 0:
        def _run_watchdog():
            while not run_done.wait(15.0):
                idle = time.monotonic() - deadman["t"]
                if idle > run_budget:
                    _emit_error(
                        f"no progress for {idle:.0f}s in phase "
                        f"'{deadman['phase']}' after successful init "
                        "(device wedged mid-run?)", phase=deadman["phase"],
                        init_s=round(init_s, 1))
                    os._exit(6)

        threading.Thread(target=_run_watchdog, daemon=True).start()

    rng = np.random.default_rng(0)

    def make_req(i):
        prompt = rng.integers(3, min(model_cfg.vocab_size, 30000),
                              size=args.prompt_len).tolist()
        sp = (SamplingParams(max_tokens=10**9, temperature=0.8,
                             repeat_penalty=1.1, seed=i + 1)
              if args.sampled else SamplingParams(max_tokens=10**9))
        req = Request(i + 1, f"user{i}", args.model, prompt, sp)
        req._inc_decode = rt.tokenizer.make_incremental_decoder()
        return req

    # TTFT: sequential prefills on the otherwise-empty engine (compile first).
    ttfts = []
    for i in range(args.ttft_samples):
        req = make_req(1000 + i)
        rt.pending_prefill.append(req)
        t0 = time.monotonic()
        for _ in range(10_000):
            _pump(rt, core, touch, "ttft")
            if req.stats.first_token_at:
                break
        else:
            raise RuntimeError("ttft request never produced a token")
        ttfts.append((time.monotonic() - t0) * 1e3)
        # Clear the slot again so the throughput phase starts clean.
        for s, r in enumerate(rt.slot_req):
            if r is not None:
                from ollamamq_tpu.engine.request import FinishReason
                rt._finish_slot(s, FinishReason.CANCELLED, core)
    ttft_compile_ms = ttfts[0]
    ttft_p50_ms = statistics.median(ttfts[1:]) if len(ttfts) > 1 else ttfts[0]

    rt.tokenizer.eos_id = -1  # keep sequences alive (incl. long-prompt runs)

    # Long-prompt prefill: a prompt 4x the largest bucket streams through
    # the chunked path (block-wise paged attention) — tracks the HBM-gap
    # work on long-context prefill. Timed after a compile pass.
    long_ms = None
    if args.long_prompt:
        from ollamamq_tpu.engine.request import FinishReason

        def run_long(i):
            prompt = rng.integers(3, min(model_cfg.vocab_size, 30000),
                                  size=args.long_prompt).tolist()
            req = Request(5000 + i, f"lpuser{i}", args.model, prompt,
                          SamplingParams(max_tokens=10**9))
            req._inc_decode = rt.tokenizer.make_incremental_decoder()
            rt.pending_prefill.append(req)
            t0 = time.monotonic()
            while rt.pending_prefill or rt.chunking:
                progressed = _pump(rt, core, touch, "long_prefill")
                if not progressed and not rt.chunking:
                    # step_prefill returned False with the request still
                    # pending (page allocation failed): no iteration will
                    # ever succeed — surface the structured error instead
                    # of spinning forever.
                    break
            ms = (time.monotonic() - t0) * 1e3
            installed = any(r is req for r in rt.slot_req)
            for s, r in enumerate(rt.slot_req):
                if r is not None:
                    rt._finish_slot(s, FinishReason.CANCELLED, core)
            if not installed:
                raise RuntimeError("long prompt rejected (pages too small?)")
            return ms

        run_long(0)  # compile
        long_ms = statistics.median(run_long(i) for i in range(1, 4))

    from ollamamq_tpu.engine.request import FinishReason

    def reset_batch():
        """Finish every slot and re-prefill a fresh full batch, so each
        sweep leg starts from the same context length / page budget."""
        for s, r in enumerate(rt.slot_req):
            if r is not None:
                rt._finish_slot(s, FinishReason.CANCELLED, core)
        for i in range(args.slots):
            rt.pending_prefill.append(make_req(i))
            _pump(rt, core, touch, "batch_prefill")
        # Ragged spans may still be mid-flight: drain the admission queue
        # so every leg starts with the full batch installed.
        for _ in range(10_000):
            if not (rt.pending_prefill or rt.chunking):
                break
            if not _pump(rt, core, touch, "batch_prefill"):
                break
        return rt.active_count()

    def timed_decode(chunk):
        """Warmup (compiles this chunk size) + timed run; returns
        (steps_done, elapsed_s)."""
        rt.step_decode(core, k_steps=chunk)
        touch("decode_warmup")
        warm_remaining = max(0, args.warmup_steps - chunk)
        while warm_remaining > 0:
            rt.step_decode(core, k_steps=chunk)
            touch("decode_warmup")
            warm_remaining -= chunk
        done = 0
        t0 = time.monotonic()
        while done < args.steps:
            if rt.step_decode(core, k_steps=chunk) == 0:
                break
            touch("decode")
            done += chunk
        return done, time.monotonic() - t0

    active = reset_batch()

    # First dispatch compiles the decode chunk (a kernel that does not
    # compile fails the run: attn_impl is the runtime's, never flipped).
    rt.step_decode(core, k_steps=args.chunk)

    sweep = []
    chunks = [args.chunk] + [c for c in sweep_extra if c != args.chunk]
    for leg_chunk in chunks:
        # Each leg is error-contained: with the sweep on by default, a
        # compile/device failure on a later chunk size must not discard
        # the legs already measured (this may be a one-shot live-chip run).
        try:
            if leg_chunk != chunks[0]:
                active = reset_batch()
            done, el = timed_decode(leg_chunk)
        except Exception as e:
            print(f"# sweep leg chunk={leg_chunk} failed: "
                  f"{type(e).__name__}: {e}", file=sys.stderr)
            sweep.append({"chunk": leg_chunk, "tok_per_s": 0.0, "steps": 0,
                          "elapsed_s": 0.0, "step_ms": None,
                          "error": f"{type(e).__name__}: {e}"})
            continue
        leg_tok_s = active * done / el if el > 0 else 0.0
        sweep.append({"chunk": leg_chunk, "tok_per_s": round(leg_tok_s, 1),
                      "steps": done, "elapsed_s": el,
                      "step_ms": round(el / done * 1e3, 3) if done else None})
    best = max(sweep, key=lambda s: s["tok_per_s"])
    if best["steps"] == 0:
        _emit_error("decode made no progress on any sweep leg (page "
                    "budget too small, or every leg failed?)",
                    device=str(dev), sweep=sweep)
        return 5
    done_steps, elapsed = best["steps"], best.pop("elapsed_s")
    for leg in sweep:
        leg.pop("elapsed_s", None)
    tok_per_s = best["tok_per_s"]
    best_chunk = best["chunk"]

    # Embedding throughput (BASELINE config 3: /api/embed batches). A
    # failure here (second model's weights may not fit next to the decode
    # model) must not discard the decode numbers already measured — report
    # it in-band instead. A watchdog covers the second weight upload, which
    # can hang the same way initial init can.
    embed_tok_per_s = None
    embed_error = None
    if emodel_cfg is not None:
        embed_done = threading.Event()
        arm_watchdog(embed_done, args.init_timeout, "embed_init", 3,
                     f"embed-model init exceeded {args.init_timeout:.0f}s")
        try:
            from ollamamq_tpu.engine.engine import EncoderRuntime

            ert = EncoderRuntime(args.embed_model, emodel_cfg, ecfg)

            def embed_batch(i0):
                for i in range(8):
                    prompt = rng.integers(
                        3, min(emodel_cfg.vocab_size, 30000), size=64).tolist()
                    ereq = Request(9000 + i0 + i, "embuser", args.embed_model,
                                   prompt, SamplingParams(), kind="embed")
                    ert.pending.append(ereq)
                ert.step(core)
                touch("embed")

            embed_batch(0)  # compile
            n_batches = 8
            t0 = time.monotonic()
            for b in range(1, n_batches + 1):
                embed_batch(b * 10)
            embed_elapsed = time.monotonic() - t0
            embed_tok_per_s = n_batches * 8 * 64 / embed_elapsed
        except Exception as e:
            embed_error = f"{type(e).__name__}: {e}"
            print(f"# embed phase failed: {embed_error}", file=sys.stderr)
        finally:
            embed_done.set()

    # Hardware-relative efficiency: decode is HBM-bandwidth-bound, so
    # report achieved weight+KV streaming rate and MFU against the
    # chip's bf16 peak (telemetry/mfu.py's table; null for a device it
    # does not list). KV read per step ~ active x mean context x Hk x
    # hd x 2 (K+V) x bytes x layers.
    step_s = elapsed / max(1, done_steps)
    mean_ctx = args.prompt_len + (args.warmup_steps + done_steps / 2)
    kv_read = (active * mean_ctx * rt.cfg.num_kv_heads * rt.cfg.head_dim
               * 2 * 2 * rt.cfg.num_layers)
    hbm_gbps = (rt.param_bytes + kv_read) / step_s / 1e9
    flops_per_step = 2 * (rt.param_bytes / 2) * active  # 2*params*tokens
    from ollamamq_tpu.telemetry.mfu import peak_flops_per_chip

    peak = peak_flops_per_chip(dev.device_kind)
    mfu_pct = flops_per_step / step_s / peak * 100 if peak else None

    # Serving-path telemetry readback: the same registry /metrics exposes,
    # populated by the runtime steps this bench just drove — the bench's
    # external timers and the engine's own accounting must agree.
    from ollamamq_tpu.telemetry import schema as tm

    telemetry = {
        "ttft_p50_ms": round(tm.TTFT_MS.labels(model=args.model)
                             .quantile(0.5), 1),
        "tpot_p50_ms": round(tm.TPOT_MS.labels(model=args.model)
                             .quantile(0.5), 3),
        "step_p99_ms": round(tm.STEP_LATENCY_MS.labels(model=args.model)
                             .quantile(0.99), 3),
        "mfu": round(tm.MFU.labels(model=args.model).value, 4),
    }

    # Shared-prefix scenario: N users behind one common system prompt,
    # TTFT measured with the prefix cache OFF then ON against the same
    # runtime (the cache is attached between legs). Reports the hit
    # ratio and the TTFT delta the radix-tree KV reuse buys.
    shared_prefix = None
    if args.shared_prefix > 0:
        try:
            shared_prefix = _shared_prefix_scenario(rt, core, args, rng, touch)
        except Exception as e:  # never discard the decode numbers
            shared_prefix = {"error": f"{type(e).__name__}: {e}"}
            print(f"# shared_prefix scenario failed: {shared_prefix['error']}",
                  file=sys.stderr)
        finally:
            rt.prefix_cache = None  # detach: rt state stays cache-free

    # overload scenario: arrival rate > capacity over a bounded queue,
    # with deterministic fault injection supplying KV-pressure (preempt +
    # recompute) and one contained prefill fault — the chaos acceptance
    # run: zero crashes, zero silent truncations, every request either
    # completes or terminates with an explicit shed/deadline/error.
    overload = None
    if args.overload > 0:
        try:
            overload = _overload_scenario(rt, core, args, rng, touch)
        except Exception as e:  # never discard the decode numbers
            overload = {"error": f"{type(e).__name__}: {e}"}
            print(f"# overload scenario failed: {overload['error']}",
                  file=sys.stderr)
        finally:
            rt.fault_plan = None
            rt.on_preempt = None

    # density scenario: the SAME arrival trace against a bf16-KV pool
    # and an int8-KV pool sized to the SAME HBM byte budget — the
    # quantization PR's acceptance line: ~2x concurrent requests at
    # equal HBM, fewer preemptions/sheds at the same arrival rate, with
    # the int8-vs-bf16 quality guardrail and journal invariants in-band.
    density = None
    if args.density > 0:
        try:
            density = _density_scenario(rt, model_cfg, args, rng, touch)
        except Exception as e:  # never discard the decode numbers
            density = {"error": f"{type(e).__name__}: {e}"}
            print(f"# density scenario failed: {density['error']}",
                  file=sys.stderr)

    # speculative scenario: spec-off vs spec-on decode throughput on a
    # repetitive generation regime (where n-gram drafts verify), plus an
    # accept-rate/auto-throttle readout on the chaotic regime — with the
    # byte-identity of the two legs' streams checked in-band.
    speculative = None
    if args.speculative > 0:
        try:
            speculative = _speculative_scenario(rt, core, args, rng, touch)
        except Exception as e:  # never discard the decode numbers
            speculative = {"error": f"{type(e).__name__}: {e}"}
            print(f"# speculative scenario failed: {speculative['error']}",
                  file=sys.stderr)

    # slo_burst scenario: bursty arrivals against a TTFT objective —
    # where does the burst's latency actually go (queue vs prefill), and
    # how fast does it burn the error budget? Anchors the SLO/attribution
    # observability stack with real numbers.
    slo_burst = None
    if args.slo_burst > 0:
        try:
            slo_burst = _slo_burst_scenario(rt, core, args, rng, touch)
        except Exception as e:  # never discard the decode numbers
            slo_burst = {"error": f"{type(e).__name__}: {e}"}
            print(f"# slo_burst scenario failed: {slo_burst['error']}",
                  file=sys.stderr)

    # scheduling scenario: the SAME bimodal arrival trace (long batch
    # requests parked ahead of a burst of short interactive ones) under
    # --scheduler=fcfs and --scheduler=srpt on identically shaped tiny
    # runtimes — p50/p99 TTFT per leg, the srpt-must-not-lose pass gate,
    # and journal invariants (incl. the anti-starvation bound) +
    # zero-silent-truncation checks in-band.
    scheduling = None
    if args.scheduling > 0:
        try:
            scheduling = _scheduling_scenario(args, touch)
        except Exception as e:  # never discard the decode numbers
            scheduling = {"error": f"{type(e).__name__}: {e}"}
            print(f"# scheduling scenario failed: {scheduling['error']}",
                  file=sys.stderr)

    # fleet scenario: kill-and-drain chaos through the fleet router at
    # ~10x the overload request count — a seeded replica-kill fault plan
    # plus a mid-run POST /admin/drain, with the zero-drop contract
    # checked in-band: dropped_streams == 0, silent_truncations == 0,
    # journal invariants clean, and every failed-over stream
    # byte-identical to the unkilled golden run.
    fleet = None
    if args.fleet > 0:
        try:
            fleet = _fleet_scenario(args, rng, touch)
        except Exception as e:  # never discard the decode numbers
            fleet = {"error": f"{type(e).__name__}: {e}"}
            print(f"# fleet scenario failed: {fleet['error']}",
                  file=sys.stderr)

    # tiering scenario: the same seeded bimodal VIP/bulk trace through a
    # 2-tier fleet (latency-grade interactive member + throughput-grade
    # bulk member) and through homogeneous fleets at equal member count;
    # gate: tiered <= the latency-viable homogeneous fleet on p99
    # interactive TTFT AND >= on aggregate tok/s, zero dropped streams,
    # clean multi-spill journal audit — plus a balancer regroup
    # exercise (class-mix shift -> drain -> migrate -> rejoin).
    tiering = None
    if args.tiering > 0:
        try:
            tiering = _tiering_scenario(args, rng, touch)
        except Exception as e:  # never discard the decode numbers
            tiering = {"error": f"{type(e).__name__}: {e}"}
            print(f"# tiering scenario failed: {tiering['error']}",
                  file=sys.stderr)

    # diurnal scenario: a compressed day of sinusoidal + bursty load
    # through an elastic fleet (--autoscale, with a mid-day preemption
    # notice and a bulk scale-to-zero + wake cycle) vs a fixed fleet at
    # the elastic leg's peak size; gate: elastic within tolerance of
    # fixed on p99 interactive TTFT at STRICTLY fewer member-hours,
    # zero drops, clean multi-spill journal audit incl. scale pairing.
    diurnal = None
    if args.diurnal > 0:
        try:
            diurnal = _diurnal_scenario(args, rng, touch)
        except Exception as e:  # never discard the decode numbers
            diurnal = {"error": f"{type(e).__name__}: {e}"}
            print(f"# diurnal scenario failed: {diurnal['error']}",
                  file=sys.stderr)

    # crash_restart scenario: real subprocess servers (router + two HTTP
    # members, WAL on), kill -9 of a member mid-run (failover) and then
    # of the router itself; restart, WAL recovery, clients reconnect via
    # the resume endpoint — the durability acceptance run, gated on zero
    # drops, zero silent truncations, recovered_streams > 0, and
    # byte-identical resumed streams vs the unkilled golden leg.
    crash_restart = None
    if args.crash_restart > 0:
        try:
            crash_restart = _crash_restart_scenario(args, touch)
        except Exception as e:  # never discard the decode numbers
            crash_restart = {"error": f"{type(e).__name__}: {e}"}
            print(f"# crash_restart scenario failed: "
                  f"{crash_restart['error']}", file=sys.stderr)

    # router_ha scenario: real subprocess servers again — an HA primary
    # (replication stream on) with a warm standby tailing it; kill -9
    # the primary mid-decode, the standby promotes (epoch bump + member
    # re-registration + WAL re-admission), clients resume against the
    # standby byte-identically, and the revived zombie primary is fenced
    # by every member. The ROADMAP item-3 closer.
    router_ha = None
    if args.router_ha > 0:
        try:
            router_ha = _router_ha_scenario(args, touch)
        except Exception as e:  # never discard the decode numbers
            router_ha = {"error": f"{type(e).__name__}: {e}"}
            print(f"# router_ha scenario failed: {router_ha['error']}",
                  file=sys.stderr)

    result = {
        "metric": "decode_tok_per_s_per_chip",
        "value": round(tok_per_s, 1),
        "unit": "tok/s/chip",
        "vs_baseline": round(tok_per_s / 2000.0, 3),
        "model": args.model,
        "device": str(dev),
        **_DEVICE,
        # The A/B matrix cell this record measured: device above + batch
        # composition, storage dtypes, speculation and scheduling policy
        # of the config under test ride EVERY record (error lines too),
        # so rounds are attributable. attention is constant since the
        # bucketed oracle was removed (PR 8) — kept so round-over-round
        # tooling keys on a stable field set.
        **cell,
        "telemetry": telemetry,
        "hbm_gbps_est": round(hbm_gbps, 1),
        "mfu_pct_est": None if mfu_pct is None else round(mfu_pct, 2),
        "page_size": page_size,
        "sampled": args.sampled,
        "slots": active,
        "prompt_len": args.prompt_len,
        "decode_steps": done_steps,
        "chunk": best_chunk,
        "step_ms": round(step_s * 1e3, 3),
        "ttft_p50_ms": round(ttft_p50_ms, 1),
        "ttft_compile_ms": round(ttft_compile_ms, 1),
        "init_s": round(init_s, 1),
        "attn_impl": rt.attn_impl,
    }
    if len(sweep) > 1:
        result["sweep"] = sweep
    if long_ms is not None:
        result["long_prompt_len"] = args.long_prompt
        result["long_prefill_ms"] = round(long_ms, 1)
    if args.embed_model:
        result["embed_model"] = args.embed_model
        if embed_tok_per_s is not None:
            result["embed_tok_per_s"] = round(embed_tok_per_s, 1)
        if embed_error is not None:
            result["embed_error"] = embed_error
    if shared_prefix is not None:
        result["shared_prefix"] = shared_prefix
    if speculative is not None:
        result["speculative"] = speculative
    if slo_burst is not None:
        result["slo_burst"] = slo_burst
    if overload is not None:
        result["overload"] = overload
    if density is not None:
        result["density"] = density
    if scheduling is not None:
        result["scheduling"] = scheduling
    if fleet is not None:
        result["fleet"] = fleet
    if tiering is not None:
        result["tiering"] = tiering
    if diurnal is not None:
        result["diurnal"] = diurnal
    if crash_restart is not None:
        result["crash_restart"] = crash_restart
    if router_ha is not None:
        result["router_ha"] = router_ha
    # Step-profiler summary (per-mode phase p50/p99, compile count,
    # padding waste) rides EVERY official record so the regression
    # sentinel (scripts/bench_compare.py) can diff phase-level timings
    # round-over-round, not just the headline tok/s.
    try:
        from ollamamq_tpu.telemetry import stepprof
        result["step_profile"] = stepprof.PROFILER.summary()
    except Exception:
        pass
    run_done.set()
    print(json.dumps(result), flush=True)
    return 0


def _pump(rt, core, touch, phase):
    """One admission/prefill tick: the ragged mixed token-budget dispatch
    (decode rows advance inside it) — the ONE seam every scenario
    drives. The bucketed-oracle branch this used to carry was removed
    with --attention=bucketed (single-mesh runtimes are always ragged)."""
    progressed = rt.step_ragged(core)
    touch(phase)
    return progressed


def _scheduling_scenario(args, touch):
    """Size-aware scheduling A/B: the SAME bimodal trace — a few long
    batch requests enqueued ahead of many short interactive ones, over a
    2-slot runtime — runs under fcfs and srpt on identically shaped
    test-tiny runtimes (same prompt seed, eos disabled so every stream
    runs exactly max_tokens). The readout is p50/p99 TTFT per leg; the
    pass gate is srpt p99 TTFT <= fcfs with 0 journal invariant
    violations (the anti-starvation bound included) and 0 silent
    truncations — ordering must only ever change timing, never tokens."""
    import time

    import numpy as np

    import jax.numpy as jnp

    from ollamamq_tpu.config import MODEL_CONFIGS, EngineConfig
    from ollamamq_tpu.core.mqcore import MQCore
    from ollamamq_tpu.engine.engine import ModelRuntime, drop_expired
    from ollamamq_tpu.engine.request import Request
    from ollamamq_tpu.engine.scheduler import make_policy
    from ollamamq_tpu.ops.sampling import SamplingParams
    from ollamamq_tpu.telemetry.journal import Journal, check_invariants

    n_total = max(8, args.scheduling)
    n_long = max(1, n_total // 8)
    long_new, short_new = 48, 4
    long_prompt, short_prompt = 48, 8
    # Longs FIRST: the regime ROADMAP item 4 names — one long output
    # parked ahead of a burst of short interactive requests.
    arrivals = [(f"batch{i}", long_prompt, long_new) for i in range(n_long)]
    arrivals += [(f"chat{i % 8}", short_prompt, short_new)
                 for i in range(n_total - n_long)]

    def leg(policy_name):
        ecfg = EngineConfig(
            model="test-tiny", max_slots=2, num_pages=256, page_size=8,
            max_pages_per_seq=16, decode_steps_per_iter=2,
            max_batch_tokens=128, token_granule=8,
            scheduler=policy_name)
        rt = ModelRuntime("test-tiny", MODEL_CONFIGS["test-tiny"], ecfg,
                          dtype=jnp.float32)
        rt.tokenizer.eos_id = -1  # deterministic full-length streams
        policy = make_policy(ecfg)
        rt.policy = policy
        journal = Journal(capacity=65536)
        rt.journal = journal
        core = MQCore(None)

        def requeue(req):
            if req.expired():
                drop_expired(req, core, rt.name)
                return False
            rt.pending_prefill.appendleft(req)
            return True

        rt.on_preempt = requeue
        prompt_rng = np.random.default_rng(1234)  # SAME prompts per leg
        reqs = []
        for i, (user, plen, mnew) in enumerate(arrivals):
            prompt = prompt_rng.integers(
                3, rt.cfg.vocab_size - 1, size=plen).tolist()
            req = Request(60000 + i, user, rt.name, prompt,
                          SamplingParams(max_tokens=mnew))
            req._inc_decode = rt.tokenizer.make_incremental_decoder()
            reqs.append(req)
            rt.pending_prefill.append(req)
        guard = 0
        while any(not r.stats.finished_at for r in reqs):
            policy.on_admit_tick()  # the aging clock, as the engine loop
            progressed = _pump(rt, core, touch, "scheduling")
            if any(r is not None for r in rt.slot_req):
                progressed = (rt.step_decode(core, k_steps=2) > 0) \
                    or progressed
            guard += 1
            if guard > 2000 * n_total:
                raise RuntimeError("scheduling leg wedged")
            if not progressed:
                time.sleep(0.001)
        ttfts = sorted(r.stats.ttft_ms for r in reqs)
        # Ordering must never change tokens: every stream runs exactly
        # its max_tokens (eos disabled), or something truncated silently.
        silent = sum(1 for r in reqs
                     if len(r.generated_ids) != r.sampling.max_tokens)
        recs = journal.tail(None)
        rt.journal = None
        return {
            "scheduler": policy_name,
            "served": len(ttfts),
            "ttft_p50_ms": round(ttfts[len(ttfts) // 2], 1),
            "ttft_p99_ms": round(
                ttfts[min(len(ttfts) - 1, int(0.99 * len(ttfts)))], 1),
            "ttft_max_ms": round(ttfts[-1], 1),
            "invariant_violations": len(check_invariants(recs)),
            "silent_truncations": silent,
            "sched_decisions": policy.decisions,
            "pred_observed": policy.predictor.observed,
        }

    legs = {name: leg(name) for name in ("fcfs", "srpt")}
    delta = legs["fcfs"]["ttft_p99_ms"] - legs["srpt"]["ttft_p99_ms"]
    return {
        "requests": n_total,
        "long_requests": n_long,
        "long_tokens": long_new,
        "short_tokens": short_new,
        "legs": legs,
        "p99_ttft_delta_ms": round(delta, 1),
        "pass": bool(
            legs["srpt"]["ttft_p99_ms"] <= legs["fcfs"]["ttft_p99_ms"]
            and all(leg_["invariant_violations"] == 0
                    and leg_["silent_truncations"] == 0
                    for leg_ in legs.values())),
    }


def _fleet_scenario(args, rng, touch):
    """Fleet robustness acceptance: the SAME seeded arrival trace runs
    (a) through a single-replica fleet untouched (the golden leg),
    (b) through an N-replica fleet under kill-and-drain chaos — a seeded
    `replica` fault plan crashes a member mid-serving and a mid-run
    drain_replica exercises the zero-drop rolling-restart path — with
    KV migration ON (recovery resumes from shipped state), and
    (c) the same chaos trace with migration OFF (every recovery is a
    recompute replay). The contract checked in-band: dropped_streams ==
    0, silent_truncations == 0, journal invariants (incl.
    no-dropped-streams and migration handoff pairing) clean, every
    stream — failed-over ones included — byte-identical to the golden
    leg, and the migration gate: leg (b) recomputes >= 5x fewer tokens
    than leg (c). Members are tiny real engines (test-tiny, prefix
    cache on so affinity placement has a radix signal); the readout is
    robustness counters, not throughput."""
    import dataclasses
    import time

    import jax.numpy as jnp

    from ollamamq_tpu.config import EngineConfig
    from ollamamq_tpu.engine.engine import TPUEngine
    from ollamamq_tpu.fleet import FleetRouter, LocalMember
    from ollamamq_tpu.ops.sampling import SamplingParams
    from ollamamq_tpu.telemetry import schema as tm
    from ollamamq_tpu.telemetry.journal import check_invariants
    from ollamamq_tpu.testing.faults import FaultPlan
    from ollamamq_tpu.tools.journal import check_no_dropped_streams

    n_total = args.fleet
    n_members = max(2, args.fleet_replicas)
    max_new = 8
    member_kw = dict(model="test-tiny", max_slots=8, num_pages=128,
                     page_size=8, max_pages_per_seq=8,
                     decode_steps_per_iter=2, prefill_buckets=(32, 64),
                     prefix_cache=True)
    # Per-user shared prompt prefixes: repeat traffic from the same user
    # hits that user's cached prefix, giving --placement=affinity a
    # radix-tree signal to route on.
    n_users = 8
    prefixes = [rng.integers(3, 500, size=17).tolist()
                for _ in range(n_users)]
    arrivals = [(f"fl{i % n_users}",
                 prefixes[i % n_users]
                 + rng.integers(3, 500, size=6).tolist())
                for i in range(n_total)]

    def run_leg(replicas, plan, drain, migrate=True, late_kill=False):
        ecfg = EngineConfig(fault_plan=plan, **member_kw)
        member_cfg = dataclasses.replace(ecfg, fault_plan=None)
        members = [
            LocalMember(f"r{i}", TPUEngine(
                member_cfg, models={"test-tiny": None},
                blocklist_path=None, dtype=jnp.float32))
            for i in range(replicas)
        ]
        # Heartbeat threshold generous enough that a multi-second jit
        # compile inside one engine iteration doesn't read as a hung
        # loop; the injected kill is detected via thread death, not
        # staleness, so it still ejects immediately.
        # migrate_timeout bounds how long an export may wait on a
        # wedged (e.g. mid-compile) member before recompute takes over.
        router = FleetRouter(
            members, ecfg, blocklist_path=None, probe_period_s=0.1,
            eject_heartbeat_s=5.0, reprobe_backoff_s=0.2,
            evac_grace_s=1.0, drain_timeout_s=8.0, migrate=migrate,
            migrate_timeout_s=2.0)
        router.start()
        reqs, rids, items = [], [], []
        issued, drained = 0, not drain
        killed_late = not late_kill
        t0 = time.monotonic()
        deadline = t0 + 600.0
        try:
            while True:
                if time.monotonic() > deadline:
                    raise RuntimeError(
                        "fleet leg wedged: "
                        f"{sum(1 for r in reqs if not r.stats.finished_at)}"
                        " unresolved")
                done = sum(1 for r in reqs if r.stats.finished_at)
                # Progress-triggered mid-serving kill (identical in both
                # chaos legs): lands deterministically once the engines
                # are warm and streams are mid-decode — the regime where
                # migrating shipped state vs recomputing it actually
                # differs. The plan's sweep-counted kill stays for the
                # mid-compile (0-token) edge.
                if not killed_late and done >= n_total // 2:
                    router._member(f"r{replicas - 1}").crash()
                    killed_late = True
                # Bounded in-flight issuance with slot HEADROOM (3/2 x
                # one member's slots across N members): the trace
                # stretches across the whole serving window so the chaos
                # lands mid-stream, while the surviving member keeps
                # free slots for migrated/replayed victims to land in —
                # this is a robustness readout, not a saturation one.
                while issued < n_total and issued - done < 3 * member_kw[
                        "max_slots"] // 2:
                    user, prompt = arrivals[issued]
                    req = router.enqueue_request(
                        user, "", "test-tiny", prompt_tokens=prompt,
                        sampling=SamplingParams(max_tokens=max_new))
                    reqs.append(req)
                    rids.append(req.req_id)  # rid0: stable journal id
                    items.append([])
                    issued += 1
                for i, r in enumerate(reqs):
                    items[i].extend(r.stream.drain())
                if not drained and done >= n_total // 3 \
                        and issued > n_total // 2:
                    router.drain_replica("r0")
                    drained = True
                touch("fleet")
                if issued >= n_total and done >= n_total:
                    for i, r in enumerate(reqs):
                        items[i].extend(r.stream.drain())
                    break
                time.sleep(0.01)
            jrecs = router.journal.tail(None)
            return {
                "texts": ["".join(it.text for it in seq
                                  if it.kind == "token") for seq in items],
                "terminals": [next((it for it in reversed(seq)
                                    if it.kind in ("done", "error")), None)
                              for seq in items],
                "rids": rids,
                "journal": jrecs,
                "failovers": router.failover_count,
                "migrations": router.migration_count,
                "migrate_aborts": router.migrate_abort_count,
                # Router-overhead self-profiling: this leg's windowed
                # placement p99 (the per-instance window, NOT the
                # process-cumulative histogram, so legs don't bleed
                # into each other's gate).
                "router_overhead_p99_ms": router.router_overhead_p99_ms(),
                "router_overhead_budget_ms":
                    ecfg.router_overhead_budget_ms,
                "elapsed_s": round(time.monotonic() - t0, 3),
            }
        finally:
            router.stop()

    golden = run_leg(1, None, drain=False)
    affinity0 = tm.FLEET_AFFINITY_HITS_TOTAL.value
    # Seeded replica-kill plan: members are probed in order each health
    # sweep (n_members "replica"-site calls per sweep), so call
    # s * n_members crashes the LAST member on sweep s. One kill lands
    # early (sweep 10, ~1s — often mid-compile, exercising 0-token
    # failovers) and one mid-serving (sweep 45, ~4.5s) if the run lasts
    # that long. A FRESH plan per leg: the per-site call counters are
    # stateful, and the migration A/B below must see the same kills.
    def kill_plan():
        return FaultPlan([{"site": "replica", "kind": "exception",
                           "at": [10 * n_members, 45 * n_members],
                           "times": 2}], seed=7)

    chaos = run_leg(n_members, kill_plan(), drain=True, late_kill=True)
    # Affinity delta bounds to the chaos leg only (the recompute leg
    # below increments the same process-global counter).
    chaos_affinity = int(tm.FLEET_AFFINITY_HITS_TOTAL.value - affinity0)
    # Migration A/B: the SAME kill-and-drain chaos trace with migration
    # disabled — every recovery recomputes. The gate: migration
    # recomputes >= 5x fewer tokens (journal replayed_tokens), still
    # with zero drops and clean invariants on both legs.
    recompute = run_leg(n_members, kill_plan(), drain=True, migrate=False,
                        late_kill=True)

    mismatches = [i for i, (a, b) in enumerate(zip(golden["texts"],
                                                   chaos["texts"]))
                  if a != b]
    # A chaos stream that is a strict PREFIX of its golden twin AND ended
    # with a normal done was silently truncated — the exact bug the
    # zero-drop contract kills. (An explicit error terminal is loud, not
    # silent — it still counts as a mismatch above.)
    silent = sum(
        1 for i in mismatches
        if golden["texts"][i].startswith(chaos["texts"][i])
        and chaos["terminals"][i] is not None
        and chaos["terminals"][i].kind == "done")
    dropped = sum(1 for t in chaos["terminals"] if t is None)
    jrecs = chaos["journal"]
    violations = check_invariants(jrecs) + check_no_dropped_streams(jrecs)
    # Victim streams = everything a recovery touched, whether it rode a
    # migration (migrate_import, prefix shipments excluded) or the
    # recompute replay (replica_failover).
    failover_rids = {r.get("req_id") for r in jrecs
                     if r["kind"] == "replica_failover"
                     or (r["kind"] == "migrate_import"
                         and r.get("what") != "prefix")}
    failover_idx = [i for i, rid in enumerate(chaos["rids"])
                    if rid in failover_rids]
    outcomes: dict = {}
    for t in chaos["terminals"]:
        reason = (t.finish_reason.value
                  if t is not None and t.finish_reason else "none")
        outcomes[reason] = outcomes.get(reason, 0) + 1
    placements = sum(1 for r in jrecs if r["kind"] == "place")
    affinity_hits = chaos_affinity

    # Migration leg readout: recomputed tokens = what each leg's
    # recoveries replayed (replica_failover.replayed_tokens); the
    # migration leg's shipped tokens rode migrate_import instead.
    def recomputed_tokens(recs):
        return sum(int(r.get("replayed_tokens") or 0) for r in recs
                   if r["kind"] == "replica_failover")

    recomputed_off = recomputed_tokens(recompute["journal"])
    recomputed_on = recomputed_tokens(jrecs)
    shipped = sum(int(r.get("tokens") or 0) for r in jrecs
                  if r["kind"] == "migrate_import"
                  and r.get("what") != "prefix")
    rec_mismatch = [i for i, (a, b) in enumerate(zip(golden["texts"],
                                                     recompute["texts"]))
                    if a != b]
    rec_violations = (check_invariants(recompute["journal"])
                      + check_no_dropped_streams(recompute["journal"]))
    rec_dropped = sum(1 for t in recompute["terminals"] if t is None)
    migration = {
        "migrations": chaos["migrations"],
        "migrate_aborts": chaos["migrate_aborts"],
        "shipped_tokens": shipped,
        "recomputed_tokens_migrate_on": recomputed_on,
        "recomputed_tokens_migrate_off": recomputed_off,
        "recompute_leg_mismatches": len(rec_mismatch),
        "recompute_leg_dropped": rec_dropped,
        "recompute_leg_invariant_violations": len(rec_violations),
        "elapsed_s_migrate_off": recompute["elapsed_s"],
        # Gate: resuming from shipped state must recompute >= 5x fewer
        # tokens than recompute-only recovery on the same chaos trace,
        # with zero drops and clean invariants on both legs.
        "pass": bool(
            recomputed_on * 5 <= recomputed_off
            and (recomputed_off > 0 or chaos["migrations"] > 0)
            and dropped == 0 and rec_dropped == 0
            and not violations and not rec_violations),
    }
    # Router-overhead gate (ROADMAP: "router overhead (placement +
    # journal) measured and bounded"): the CHAOS leg's windowed
    # placement p99 must come in under the configured budget — chaos is
    # exactly when an unbounded router hot path would hide behind the
    # failover noise.
    overhead_p99 = chaos["router_overhead_p99_ms"]
    overhead_budget = chaos["router_overhead_budget_ms"]
    overhead_pass = bool(overhead_p99 is not None
                         and (not overhead_budget
                              or overhead_p99 <= overhead_budget))
    return {
        "requests": n_total,
        "replicas": n_members,
        "max_new_tokens": max_new,
        "router_overhead_p99_ms": (round(overhead_p99, 4)
                                   if overhead_p99 is not None else None),
        "router_overhead_budget_ms": overhead_budget,
        "router_overhead_pass": overhead_pass,
        "ejects": sum(1 for r in jrecs if r["kind"] == "replica_eject"),
        "failovers": chaos["failovers"],
        "drains": sum(1 for r in jrecs if r["kind"] == "replica_drain"),
        "rejoins": sum(1 for r in jrecs if r["kind"] == "replica_join"
                       and r.get("why") != "start"),
        "dropped_streams": dropped,
        "silent_truncations": silent,
        "stream_mismatches": len(mismatches),
        "failover_streams": len(failover_idx),
        "failover_streams_byte_identical": bool(failover_idx) and not any(
            i in mismatches for i in failover_idx),
        "placements": placements,
        "affinity_hits": affinity_hits,
        "affinity_hit_ratio": round(affinity_hits / max(1, placements), 4),
        "invariant_violations": len(violations),
        "outcomes": outcomes,
        "migration": migration,
        "elapsed_s_golden": golden["elapsed_s"],
        "elapsed_s_chaos": chaos["elapsed_s"],
    }


def _tiering_scenario(args, rng, touch):
    """Tiered-fleet acceptance (Nitsum): the SAME seeded bimodal trace —
    deadlined interactive shorts paced through a window, a bulk backlog
    of long generations — runs through

      (a) the TIERED fleet: one latency-grade member (few slots, fast
          steps) serving `interactive`, one throughput-grade member
          (many slots, slower steps — the big-batch config) serving
          `bulk`, with per-tier burn-rate overflow ON so bulk backlog
          may spill into interactive headroom;
      (b) the latency-viable HOMOGENEOUS fleet at equal member count:
          both members latency-grade — what an operator bound by the
          interactive SLO must deploy without tiers (Nitsum's
          comparator); and
      (c) the throughput-grade homogeneous fleet, reported for the full
          tradeoff picture (it wins raw tok/s but blows the interactive
          p99 — the tradeoff tiering escapes).

    Readout: per-tier p50/p99 TTFT, aggregate tok/s, overflow/regroup
    counts, dropped streams, invariant violations, and the multi-spill
    journal audit (router + both members' spills through tools/journal
    check_files). Gate: tiered <= leg (b) on p99 interactive TTFT AND
    >= on aggregate tok/s, zero drops, clean audit. A separate
    3-member regroup exercise shifts the class mix and lets the
    TierBalancer retier a member (drain -> migrate -> rejoin),
    journaled tier_regroup start -> done."""
    import dataclasses
    import os
    import tempfile
    import time

    from ollamamq_tpu.config import EngineConfig
    from ollamamq_tpu.engine.fake import FakeEngine
    from ollamamq_tpu.fleet import FleetRouter, LocalMember
    from ollamamq_tpu.ops.sampling import SamplingParams
    from ollamamq_tpu.telemetry.journal import check_invariants
    from ollamamq_tpu.tools.journal import check_files

    n_short = args.tiering
    # Bulk sized to outlast the interactive window: the backlog's tail
    # drains after the shorts stop, which is exactly when burn-driven
    # overflow finds idle interactive headroom to spill into.
    n_bulk = max(6, (n_short * 5) // 4)
    short_toks, bulk_toks = 2, 16
    window_s = 1.0  # interactive pacing window
    # Member grades: the real big-batch tradeoff modeled on the fake —
    # throughput-grade runs many slots at a slower step (higher
    # aggregate tok/s, worse latency), latency-grade few slots fast.
    lat_grade = dict(max_slots=2, latency=0.01)
    thr_grade = dict(max_slots=12, latency=0.03)
    base_kw = dict(model="test-tiny", num_pages=64, page_size=8,
                   max_pages_per_seq=8, decode_steps_per_iter=2)
    tmp = tempfile.mkdtemp(prefix="ollamamq-tiering-")

    def run_leg(tag, grades, tiers_spec):
        ecfg = EngineConfig(
            max_slots=max(g["max_slots"] for g in grades),
            journal_file=os.path.join(tmp, f"{tag}-router.jsonl"),
            tiers=tiers_spec, **base_kw)
        members = []
        spills = [ecfg.journal_file]
        for i, grade in enumerate(grades):
            mcfg = dataclasses.replace(
                ecfg, max_slots=grade["max_slots"], tiers=None,
                journal_file=os.path.join(tmp, f"{tag}-r{i}.jsonl"))
            spills.append(mcfg.journal_file)
            members.append(LocalMember(
                f"r{i}", FakeEngine(mcfg, blocklist_path=None,
                                    token_latency_s=grade["latency"])))
        router = FleetRouter(
            members, ecfg, blocklist_path=None, probe_period_s=0.05,
            eject_heartbeat_s=5.0, reprobe_backoff_s=0.2,
            evac_grace_s=1.0,
            # Overflow windows shrunk to the smoke's timescale so bulk
            # backlog (bulk-tier TTFT burn) can spill into interactive
            # headroom within the run; untiered legs ignore this.
            tiering_kw=dict(windows=(("fast", 5.0, 1.0, 1.0, "warn"),),
                            bulk_ttft_ms=150.0, balance=False))
        router.start()
        reqs, kinds = [], []
        t0 = time.monotonic()
        deadline = t0 + 300.0
        issued_shorts = issued_bulk = 0
        try:
            while True:
                if time.monotonic() > deadline:
                    raise RuntimeError(f"tiering leg {tag} wedged")
                now = time.monotonic() - t0
                # Bulk backlog lands up front; interactive shorts pace
                # through the window (deadline_ms classifies them —
                # generous enough that none can expire: the zero-drop
                # gate stays meaningful).
                while issued_bulk < n_bulk:
                    sp = SamplingParams(max_tokens=bulk_toks)
                    reqs.append(router.enqueue_request(
                        f"bulk{issued_bulk % 4}", "", "test-tiny",
                        prompt_tokens=[1] * 8, sampling=sp))
                    kinds.append("bulk")
                    issued_bulk += 1
                want = min(n_short, int(now / window_s * n_short) + 1)
                while issued_shorts < want:
                    sp = SamplingParams(max_tokens=short_toks)
                    sp.deadline_ms = 60_000.0
                    reqs.append(router.enqueue_request(
                        f"int{issued_shorts % 8}", "", "test-tiny",
                        prompt_tokens=[1] * 4, sampling=sp))
                    kinds.append("interactive")
                    issued_shorts += 1
                for r in reqs:
                    r.stream.drain()
                done = sum(1 for r in reqs if r.stats.finished_at)
                touch("tiering")
                if issued_shorts >= n_short and done >= len(reqs):
                    break
                time.sleep(0.005)
            elapsed = time.monotonic() - t0
            tokens = sum(r.stats.completion_tokens for r in reqs)
            dropped = sum(1 for r in reqs if not r.stats.finished_at)

            def pctl(xs, q):
                xs = sorted(xs)
                return (round(xs[min(len(xs) - 1, int(q * len(xs)))], 1)
                        if xs else None)

            out = {"tok_per_s": round(tokens / max(1e-9, elapsed), 1),
                   "elapsed_s": round(elapsed, 3),
                   "tokens": tokens, "dropped_streams": dropped}
            for cls in ("interactive", "bulk"):
                ttfts = [r.stats.ttft_ms for r, k in zip(reqs, kinds)
                         if k == cls and r.stats.first_token_at]
                out[f"{cls}_ttft_p50_ms"] = pctl(ttfts, 0.5)
                out[f"{cls}_ttft_p99_ms"] = pctl(ttfts, 0.99)
            # Counter, not a ring scan: the admission churn of a parked
            # bulk backlog can rotate early records out of the ring (the
            # spill files below keep everything for the audit).
            out["overflows"] = (router.tiers.overflow_count
                                if router.tiers is not None else 0)
            p99 = router.router_overhead_p99_ms()
            out["router_overhead_p99_ms"] = (round(p99, 4)
                                             if p99 is not None else None)
            out["invariant_violations"] = len(
                check_invariants(router.journal.tail(None)))
            return out, spills
        finally:
            router.stop()

    tiered, tiered_spills = run_leg(
        "tiered", [lat_grade, thr_grade], "interactive=r0;bulk=r1")
    homo_lat, lat_spills = run_leg("homo-lat", [lat_grade, lat_grade],
                                   None)
    homo_thr, _ = run_leg("homo-thr", [thr_grade, thr_grade], None)

    # Multi-spill audit: the tiered leg's router + member journals
    # checked as ONE run (invariants, zero-drop, regroup pairing).
    audit_bad, audit_records = check_files(
        [p for p in tiered_spills if os.path.exists(p)])

    # Regroup exercise: a 3-member tiered mini-fleet under a class-mix
    # shift — the balancer must retier a bulk member into interactive
    # (drain -> migrate live streams -> rejoin), journaled start->done.
    regroup = {"regroups_done": 0, "regroups_aborted": 0}
    ecfg = EngineConfig(max_slots=4, **base_kw)
    members = [LocalMember(f"r{i}",
                           FakeEngine(dataclasses.replace(ecfg),
                                      blocklist_path=None,
                                      token_latency_s=0.02))
               for i in range(3)]
    router = FleetRouter(
        members, ecfg, blocklist_path=None, probe_period_s=0.05,
        eject_heartbeat_s=5.0, reprobe_backoff_s=0.2, evac_grace_s=1.0,
        tiers="interactive=r0;bulk=r1,r2",
        tiering_kw=dict(ema_alpha=0.3, deadband=0.1, cooldown_s=0.2,
                        min_samples=8))
    router.start()
    try:
        deadline = time.monotonic() + 60.0
        i = 0
        while time.monotonic() < deadline:
            sp = SamplingParams(max_tokens=4)
            sp.deadline_ms = 60_000.0  # all-interactive mix shift
            req = router.enqueue_request(f"mix{i % 4}", "", "test-tiny",
                                         prompt_tokens=[1] * 4,
                                         sampling=sp)
            i += 1
            t1 = time.monotonic() + 5.0
            while not req.stats.finished_at and time.monotonic() < t1:
                req.stream.drain()
                time.sleep(0.005)
            touch("tiering")
            recs = router.journal.tail(None, kind="tier_regroup")
            regroup["regroups_done"] = sum(
                1 for r in recs if r.get("phase") == "done")
            regroup["regroups_aborted"] = sum(
                1 for r in recs if r.get("phase") == "aborted")
            if regroup["regroups_done"] >= 1:
                break
        regroup["interactive_members"] = len(
            router.tiers._tier_members("interactive"))
        regroup["mix_ema"] = (round(router.tiers.mix_ema, 3)
                              if router.tiers.mix_ema is not None
                              else None)
    finally:
        router.stop()

    gate = bool(
        tiered["interactive_ttft_p99_ms"] is not None
        and homo_lat["interactive_ttft_p99_ms"] is not None
        and tiered["interactive_ttft_p99_ms"]
        <= homo_lat["interactive_ttft_p99_ms"]
        and tiered["tok_per_s"] >= homo_lat["tok_per_s"]
        and tiered["dropped_streams"] == 0
        and tiered["invariant_violations"] == 0
        and regroup["regroups_done"] >= 1
        and not audit_bad)
    return {
        "interactive_requests": n_short,
        "bulk_requests": n_bulk,
        "router_overhead_p99_ms": tiered.get("router_overhead_p99_ms"),
        "tiered": tiered,
        "homogeneous_latency_grade": homo_lat,
        "homogeneous_throughput_grade": homo_thr,
        "regroup_exercise": regroup,
        "journal_audit_records": audit_records,
        "journal_audit_violations": len(audit_bad),
        "pass": gate,
    }


def _diurnal_scenario(args, rng, touch):
    """Elastic-fleet acceptance: a compressed day of load — a quiet
    night, a bursty sinusoidal day with a bulk backlog, a quiet night —
    runs through

      (a) the ELASTIC tiered fleet (--autoscale): starts at interactive
          r0 + bulk r1, sleeps the idle bulk tier to ZERO overnight,
          wakes it when the day's backlog arrives (parked work is the
          wake signal), grows interactive under the burst pressure, and
          survives a mid-day PREEMPTION NOTICE on a spot member — every
          size change the drain -> migrate-off -> retire ladder or a
          journaled spawn; and
      (b) the FIXED fleet at the elastic leg's observed PEAK size —
          what an operator without elasticity must keep running all
          day to hold the same burst.

    Readout per leg: p99/p50 interactive TTFT, member-hours (the
    resource-cost denominator), scale events by direction/why,
    preemptions, drops, silent truncations. Gate: elastic holds the
    fixed leg's p99 interactive TTFT within tolerance at STRICTLY
    fewer member-hours, zero drops and zero silent truncations through
    every scale event (incl. the preemption notice and the zero/wake
    cycle), at least one wake and one idle scale-down, and the
    multi-spill journal audit (router + seed + provisioned member
    spills through tools/journal check_files, scale pairing included)
    comes back clean."""
    import dataclasses
    import itertools
    import os
    import tempfile
    import time

    from ollamamq_tpu.config import EngineConfig
    from ollamamq_tpu.engine.fake import FakeEngine
    from ollamamq_tpu.fleet import FleetRouter, LocalMember
    from ollamamq_tpu.ops.sampling import SamplingParams
    from ollamamq_tpu.telemetry.journal import check_invariants
    from ollamamq_tpu.tools.journal import check_files

    n_day = args.diurnal
    n_bulk = max(6, n_day // 2)
    short_toks, bulk_toks = 2, 10
    # Elastic p99 tolerance vs fixed: the elastic leg pays a bounded
    # queueing premium while a scale-up spawns; it must not pay an
    # unbounded one.
    tol_mult, tol_abs_ms = 2.0, 150.0
    base_kw = dict(model="test-tiny", max_slots=4, num_pages=64,
                   page_size=8, max_pages_per_seq=8,
                   decode_steps_per_iter=2)
    tmp = tempfile.mkdtemp(prefix="ollamamq-diurnal-")
    # Day-phase burst sizes, a one-humped "sinusoid" scaled by n_day —
    # the midday hump must overflow one interactive member's slots so
    # the backlog-pressure scale-up path fires, not just the wake.
    shape = [1, 2, 6, 6, 2, 1]
    bursts = [max(1, round(n_day * s / sum(shape))) for s in shape]

    def run_leg(tag, elastic, tiers_spec, n_members):
        ecfg = EngineConfig(
            journal_file=os.path.join(tmp, f"{tag}-router.jsonl"),
            tiers=tiers_spec, autoscale=elastic, min_replicas=1,
            max_replicas=4, scale_cooldown_s=0.3,
            preemptible="r1" if elastic else None, **base_kw)
        member_cfg = dataclasses.replace(
            ecfg, fault_plan=None, max_queued=0, max_queued_per_user=0,
            tiers=None, autoscale=False, preemptible=None,
            journal_file=None)
        spills = [ecfg.journal_file]
        prov_seq = itertools.count()

        def mkfactory(seed_name=None):
            def build(tp=None):
                jf = os.path.join(
                    tmp, f"{tag}-{seed_name or f'prov{next(prov_seq)}'}"
                         ".jsonl")
                spills.append(jf)
                mcfg = dataclasses.replace(member_cfg, journal_file=jf)
                return FakeEngine(mcfg, blocklist_path=None,
                                  token_latency_s=0.02)
            return build

        members = []
        for i in range(n_members):
            f = mkfactory(seed_name=f"r{i}")
            members.append(LocalMember(f"r{i}", f(), engine_factory=f))
        router = FleetRouter(
            members, ecfg, blocklist_path=None, probe_period_s=0.05,
            eject_heartbeat_s=5.0, reprobe_backoff_s=0.2,
            evac_grace_s=1.0,
            tiering_kw=dict(balance=False,
                            windows=(("fast", 5.0, 1.0, 1.0, "warn"),),
                            bulk_ttft_ms=150.0),
            # Hysteresis shrunk to the smoke's timescale; backlog_high
            # lowered so the midday hump's queue depth reads as
            # pressure on these tiny members; provisioned members join
            # as preemptible SPOT capacity — what the mid-day
            # termination notice reclaims.
            autoscale_kw=dict(tick_period_s=0.02, cooldown_s=0.3,
                              sustain_s=0.1, idle_sustain_s=0.25,
                              backlog_high=2,
                              provision_preemptible=True))
        router.start()
        reqs, kinds, want = [], [], []
        peak = {"interactive": 0, "bulk": 0, "total": 0}
        seen = {"zero": False, "preempted": False}

        def issue(user, cls, toks, deadline_ms=None):
            sp = SamplingParams(max_tokens=toks)
            if deadline_ms is not None:
                sp.deadline_ms = deadline_ms
            reqs.append(router.enqueue_request(
                user, "", "test-tiny", prompt_tokens=[1] * 4,
                sampling=sp))
            kinds.append(cls)
            want.append(toks)

        def pulse():
            for r in reqs:
                r.stream.drain()
            counts = {"interactive": 0, "bulk": 0}
            for m in router.members:
                t = getattr(m, "tier", None)
                if t in counts and m.state != "ejected":
                    counts[t] += 1
            for t in counts:
                peak[t] = max(peak[t], counts[t])
            peak["total"] = max(peak["total"], len(router.members))
            if (router.tiers is not None
                    and "bulk" in router.tiers.scaled_to_zero):
                seen["zero"] = True
            touch("diurnal")

        t0 = time.monotonic()
        try:
            # --- night 0: an interactive trickle, nothing for bulk.
            # The elastic leg's idle bulk member drains off; the tier
            # sleeps at zero. Phase timings are IDENTICAL across legs —
            # the member-hours comparison depends on it.
            i_seq = itertools.count()
            end = time.monotonic() + 1.2
            while time.monotonic() < end:
                issue(f"n{next(i_seq) % 4}", "interactive", short_toks,
                      deadline_ms=60_000.0)
                for _ in range(5):
                    pulse()
                    time.sleep(0.05)
            # --- day: the bulk backlog lands (the elastic leg's WAKE
            # signal) and interactive arrives in sinusoidal bursts.
            b_seq = itertools.count()
            bulk_per_step = -(-n_bulk // len(bursts))  # ceil
            for step, size in enumerate(bursts):
                for _ in range(bulk_per_step):
                    if next(b_seq) < n_bulk:
                        issue(f"b{step % 4}", "bulk", bulk_toks)
                for _ in range(size):
                    issue(f"d{next(i_seq) % 8}", "interactive",
                          short_toks, deadline_ms=60_000.0)
                # Mid-day spot reclamation: serve a termination notice
                # on a preemptible member (elastic leg only).
                if elastic and step == len(bursts) // 2 \
                        and not seen["preempted"]:
                    victim = next(
                        (m for m in router.members
                         if getattr(m, "preemptible", False)
                         and m.state == "healthy"
                         and not getattr(m, "retiring", False)), None)
                    serving = sum(
                        1 for m in router.members
                        if m.state != "ejected"
                        and not getattr(m, "retiring", False))
                    if victim is not None and serving > 1:
                        router.preempt_replica(victim.name,
                                               notice_s=5.0)
                        seen["preempted"] = True
                for _ in range(6):
                    pulse()
                    time.sleep(0.05)
            # --- night 1: arrivals stop; everything drains, then an
            # evening beat (same length both legs) in which the
            # elastic fleet shrinks back toward the floor and the
            # fixed one just keeps burning member-hours.
            deadline = time.monotonic() + 300.0
            while any(not r.stats.finished_at for r in reqs):
                if time.monotonic() > deadline:
                    raise RuntimeError(f"diurnal leg {tag} wedged")
                pulse()
                time.sleep(0.01)
            end = time.monotonic() + 2.5
            while time.monotonic() < end:
                pulse()
                time.sleep(0.05)
            elapsed = time.monotonic() - t0
            pulse()

            def pctl(xs, q):
                xs = sorted(xs)
                return (round(xs[min(len(xs) - 1, int(q * len(xs)))], 1)
                        if xs else None)

            ttfts = [r.stats.ttft_ms for r, k in zip(reqs, kinds)
                     if k == "interactive" and r.stats.first_token_at]
            dropped = sum(1 for r in reqs if not r.stats.finished_at)
            # A stream that finished "normally" with fewer tokens than
            # it asked for was silently truncated somewhere in a scale
            # event — the exact bug the drain ladder exists to prevent.
            silent = sum(
                1 for r, w in zip(reqs, want)
                if r.stats.finished_at and r.stats.completion_tokens < w)
            jrecs = router.journal.tail(None)
            hours = (router.autoscaler.member_hours() if elastic
                     else n_members * elapsed / 3600.0)
            scale = {"up_done": 0, "up_aborted": 0, "down_done": 0,
                     "down_aborted": 0, "wakes": 0, "idle_downs": 0}
            for r in jrecs:
                if r["kind"] == "scale_up" and r.get("phase") == "start" \
                        and r.get("why") == "wake":
                    scale["wakes"] += 1
                if r["kind"] == "scale_down" \
                        and r.get("phase") == "start" \
                        and r.get("why") == "idle":
                    scale["idle_downs"] += 1
                for kind, key in (("scale_up", "up"), ("scale_down",
                                                       "down")):
                    if r["kind"] == kind:
                        if r.get("phase") == "done":
                            scale[f"{key}_done"] += 1
                        elif r.get("phase") == "aborted":
                            scale[f"{key}_aborted"] += 1
            out = {
                "elapsed_s": round(elapsed, 3),
                "requests": len(reqs),
                "interactive_ttft_p50_ms": pctl(ttfts, 0.5),
                "interactive_ttft_p99_ms": pctl(ttfts, 0.99),
                "member_hours": round(hours, 5),
                "dropped_streams": dropped,
                "silent_truncations": silent,
                "scale_events": scale,
                "preempt_notices": sum(1 for r in jrecs
                                       if r["kind"] == "preempt_notice"),
                "slept_to_zero": seen["zero"],
                "preempted": seen["preempted"],
                "peak_members": dict(peak),
                "final_members": len(router.members),
                "invariant_violations": len(check_invariants(jrecs)),
            }
            return out, spills
        finally:
            router.stop()

    elastic, elastic_spills = run_leg(
        "elastic", True, "interactive=r0;bulk=r1", 2)
    # The fixed comparator runs all day at the elastic leg's peak —
    # tier spec rebuilt at the observed per-tier peak counts.
    n_int = max(1, elastic["peak_members"]["interactive"])
    n_blk = max(1, elastic["peak_members"]["bulk"])
    spec = ("interactive=" + ",".join(f"r{i}" for i in range(n_int))
            + ";bulk=" + ",".join(f"r{i}"
                                  for i in range(n_int, n_int + n_blk)))
    fixed, _ = run_leg("fixed", False, spec, n_int + n_blk)

    # Multi-spill audit of the elastic leg: router + seed + provisioned
    # member journals as ONE run — invariants, zero-drop, regroup AND
    # scale pairing (a hanging scale_up/scale_down or a lapsed
    # preemption notice fails here).
    audit_bad, audit_records = check_files(
        [p for p in elastic_spills if os.path.exists(p)])

    p99_e = elastic["interactive_ttft_p99_ms"]
    p99_f = fixed["interactive_ttft_p99_ms"]
    gate = bool(
        p99_e is not None and p99_f is not None
        and p99_e <= p99_f * tol_mult + tol_abs_ms
        and elastic["member_hours"] < fixed["member_hours"]
        and elastic["dropped_streams"] == 0
        and fixed["dropped_streams"] == 0
        and elastic["silent_truncations"] == 0
        and fixed["silent_truncations"] == 0
        and elastic["invariant_violations"] == 0
        and elastic["slept_to_zero"]
        and elastic["preempted"]
        and elastic["preempt_notices"] >= 1
        and elastic["scale_events"]["wakes"] >= 1
        and elastic["scale_events"]["up_done"] >= 1
        and elastic["scale_events"]["down_done"] >= 1
        and not audit_bad)
    return {
        "interactive_requests_day": n_day,
        "bulk_requests": n_bulk,
        "ttft_tolerance": {"mult": tol_mult, "abs_ms": tol_abs_ms},
        "elastic": elastic,
        "fixed": fixed,
        "member_hours_saved_pct": round(
            100.0 * (1.0 - elastic["member_hours"]
                     / max(1e-12, fixed["member_hours"])), 1),
        "journal_audit_records": audit_records,
        "journal_audit_violations": len(audit_bad),
        "pass": gate,
    }


def _crash_restart_scenario(args, touch):
    """Durability acceptance at the PROCESS level: everything runs as
    real server subprocesses (fake engines — the machinery under test
    is the WAL/recovery/resume plumbing, not kernels). Topology: a
    fleet router (admission WAL on, journal spilled) over two HTTP
    member services. One seeded trace, two legs:

      golden leg  N streams served untouched; texts recorded.
      chaos leg   the same N streams; mid-run, `kill -9` a MEMBER
                  process (PR-9/11 failover covers it, clients see one
                  seamless stream), then `kill -9` the ROUTER itself —
                  every client connection dies. The router restarts on
                  the same --wal-dir, the recovery pass re-admits the
                  unfinished streams token-exact across the surviving
                  members, and each client reconnects with
                  GET /api/stream/{rid}?from=N to collect the remainder.

    Gates, all in-band: dropped_streams == 0, silent_truncations == 0,
    recovered_streams > 0, every resumed stream byte-identical to its
    golden twin, and the fleet-wide journal audit clean across the
    union of router (pre- and post-crash) + member spills."""
    import json as _json
    import shutil
    import socket
    import subprocess
    import tempfile
    import urllib.request

    from ollamamq_tpu.tools.journal import check_files

    n = args.crash_restart
    max_new = 14  # under the fake runtime's 16-token ceiling
    golden_text = "".join(f"word{i} " for i in range(max_new))
    tmp = tempfile.mkdtemp(prefix="ollamamq-crash-")
    wal_dir = os.path.join(tmp, "wal")
    procs = []

    def free_port():
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        return port

    def spawn(argv, log_name):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["FAKE_TOKEN_LATENCY_S"] = "0.05"
        logf = open(os.path.join(tmp, log_name), "wb")
        p = subprocess.Popen(
            [sys.executable, "-m", "ollamamq_tpu.cli"] + argv,
            stdout=logf, stderr=subprocess.STDOUT, env=env)
        p._logf = logf
        procs.append(p)
        return p

    def wait_health(port, budget=90.0, want_ready=True):
        deadline = time.monotonic() + budget
        while time.monotonic() < deadline:
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/health",
                        timeout=2.0) as r:
                    body = _json.loads(r.read())
                if not want_ready or body.get("status") != "recovering":
                    return body
            except Exception:  # noqa: BLE001
                pass
            touch("crash_restart")
            time.sleep(0.2)
        raise RuntimeError(f"server on :{port} never became healthy")

    class Client:
        """One NDJSON stream through the router: records every frame's
        text + token ids, notes its req_id, and survives the router
        dying mid-read (the resume endpoint picks up from there)."""

        def __init__(self, port, user, prompt):
            self.port = port
            self.user = user
            self.prompt = prompt
            self.rid = None
            self.text = ""
            self.ids = []
            self.done_reason = None
            self.thread = threading.Thread(target=self._run, daemon=True)
            self.thread.start()

        def _consume(self, resp):
            for raw in resp:
                obj = _json.loads(raw)
                if obj.get("req_id") is not None:
                    self.rid = int(obj["req_id"])
                self.ids.extend(int(t) for t in obj.get("token_ids") or ())
                self.text += obj.get("response", "")
                if obj.get("done"):
                    self.done_reason = obj.get("done_reason", "stop")
                    return

        def _run(self):
            body = _json.dumps({
                "model": "test-tiny", "prompt": self.prompt,
                "stream": True, "options": {"num_predict": max_new}})
            req = urllib.request.Request(
                f"http://127.0.0.1:{self.port}/api/generate",
                data=body.encode(),
                headers={"Content-Type": "application/json",
                         "X-User-ID": self.user}, method="POST")
            try:
                with urllib.request.urlopen(req, timeout=120) as resp:
                    self._consume(resp)
            except Exception:  # noqa: BLE001 — the router died under us
                pass

        def resume(self):
            """Reattach after the router restart: frames from the token
            index this client already holds, byte-identical remainder."""
            req = urllib.request.Request(
                f"http://127.0.0.1:{self.port}/api/stream/{self.rid}"
                f"?from={len(self.ids)}",
                headers={"X-User-ID": self.user}, method="GET")
            with urllib.request.urlopen(req, timeout=120) as resp:
                self._consume(resp)

    def run_leg(port, chaos):
        clients = [Client(port, f"cr{i % 4}", f"crash restart {i}")
                   for i in range(n)]
        member_killed = not chaos
        router_killed = not chaos
        deadline = time.monotonic() + 300.0
        while time.monotonic() < deadline:
            touch("crash_restart")
            tokens = sum(len(c.ids) for c in clients)
            if not member_killed and tokens >= 2 * n:
                procs[0].kill()  # member A: SIGKILL, failover territory
                member_killed = True
            if member_killed and not router_killed and tokens >= 6 * n \
                    and all(c.rid is not None for c in clients):
                # Every client holds its resume handle (the req_id its
                # frames carried) before the router goes down.
                router.kill()  # the router itself: the WAL's moment
                router_killed = True
                break
            if all(c.done_reason is not None for c in clients):
                break
            time.sleep(0.05)
        if not chaos:
            for c in clients:
                c.thread.join(timeout=120)
            return clients, 0
        for c in clients:
            c.thread.join(timeout=30)  # reader dies with the router
        # Restart the router on the same WAL; readiness gates on the
        # recovery pass (status "recovering" until re-admission done).
        restarted = spawn(router_argv(journal_tag="2"), "router2.log")
        health = wait_health(port)
        recovered = (health.get("wal") or {}).get("recovered_streams", 0)
        for c in clients:
            if c.done_reason is None and c.rid is not None:
                c.resume()
        return clients, recovered, restarted

    # -- topology ----------------------------------------------------------
    ports = {"a": free_port(), "b": free_port(), "router": free_port()}
    member_argv = ["--fake-engine", "--no-tui", "--models", "test-tiny",
                   "--blocklist", os.path.join(tmp, "bl.json")]
    spawn(member_argv + ["--port", str(ports["a"]),
                         "--journal-file", os.path.join(tmp, "ma.jsonl")],
          "member_a.log")
    spawn(member_argv + ["--port", str(ports["b"]),
                         "--journal-file", os.path.join(tmp, "mb.jsonl")],
          "member_b.log")

    def router_argv(journal_tag=""):
        return ["--fake-engine", "--no-tui", "--models", "test-tiny",
                "--port", str(ports["router"]),
                "--replicas", "0",
                "--replica-urls",
                f"http://127.0.0.1:{ports['a']},"
                f"http://127.0.0.1:{ports['b']}",
                "--wal-dir", wal_dir, "--wal-fsync-ms", "5",
                "--journal-file",
                os.path.join(tmp, f"router{journal_tag}.jsonl"),
                "--blocklist", os.path.join(tmp, "bl.json")]

    try:
        wait_health(ports["a"])
        wait_health(ports["b"])
        router = spawn(router_argv(), "router.log")
        wait_health(ports["router"])

        golden_clients, _ = run_leg(ports["router"], chaos=False)
        chaos_clients, recovered, router2 = run_leg(ports["router"],
                                                    chaos=True)

        dropped = sum(1 for c in chaos_clients if c.done_reason is None)
        mismatches = [i for i, c in enumerate(chaos_clients)
                      if c.text != golden_text]
        silent = sum(1 for i in mismatches
                     if golden_text.startswith(chaos_clients[i].text)
                     and chaos_clients[i].done_reason
                     in ("stop", "length"))
        golden_ok = all(c.text == golden_text for c in golden_clients)
        id_exact = all(c.ids == list(range(1, max_new + 1))
                       for c in chaos_clients if c.done_reason)
        # Router-overhead readout off the RESTARTED router's own stats
        # surface (/metrics.json → fleet.router_overhead): the crash
        # leg's recovery placements are the router hot path under the
        # worst realistic conditions.
        overhead_p99 = None
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{ports['router']}/metrics.json",
                    timeout=10) as r:
                stats = _json.loads(r.read())
            overhead_p99 = ((stats.get("fleet") or {})
                            .get("router_overhead") or {}).get(
                                "place_p99_ms")
        except Exception:  # noqa: BLE001 — readout only, never the gate
            pass
        # Graceful close of the restarted router flushes its spill, so
        # the audit reads a complete journal.
        router2.send_signal(15)
        try:
            router2.wait(timeout=60)
        except subprocess.TimeoutExpired:
            router2.kill()
        spills = [os.path.join(tmp, f) for f in
                  ("router.jsonl", "router2.jsonl", "ma.jsonl",
                   "mb.jsonl")
                  if os.path.exists(os.path.join(tmp, f))]
        violations, audited = check_files(spills)
        return {
            "requests": n,
            "max_new_tokens": max_new,
            "router_overhead_p99_ms": overhead_p99,
            "recovered_streams": recovered,
            "dropped_streams": dropped,
            "silent_truncations": silent,
            "stream_mismatches": len(mismatches),
            "resumed_streams": sum(1 for c in chaos_clients
                                   if c.rid is not None
                                   and c.done_reason is not None),
            "token_exact": id_exact,
            "golden_leg_ok": golden_ok,
            "journal_spills_audited": len(spills),
            "journal_records_audited": audited,
            "invariant_violations": len(violations),
            "violations_sample": violations[:5],
            "pass": bool(golden_ok and dropped == 0 and silent == 0
                         and not mismatches and recovered > 0
                         and id_exact and not violations),
        }
    finally:
        for p in procs:
            try:
                p.kill()
                p.wait(timeout=10)
            except Exception:  # noqa: BLE001
                pass
            try:
                p._logf.close()
            except Exception:  # noqa: BLE001
                pass
        shutil.rmtree(tmp, ignore_errors=True)


def _router_ha_scenario(args, touch):
    """Router-HA acceptance at the PROCESS level: an HA primary router
    (admission WAL + journal tap replicated over /admin/ha/sync) and a
    warm standby tailing it, over two HTTP member services. One seeded
    trace, two legs:

      golden leg  N streams through the primary, untouched.
      chaos leg   the same N streams; mid-decode, `kill -9` the
                  PRIMARY. The standby detects heartbeat loss past the
                  takeover grace, promotes — epoch bump, member
                  re-registration, WAL-replica re-admission — and each
                  client reconnects TO THE STANDBY with
                  GET /api/stream/{rid}?from=N for the remainder.

    Then the dead primary is REVIVED on its old WAL dir: its recovery
    replays the same streams at the stale epoch and every member must
    fence it (409 + epoch_fence journaled) — zero stale-epoch
    placements accepted, while a fresh stream through the promoted
    standby still completes. Gates: dropped_streams == 0,
    silent_truncations == 0, every resumed stream byte-identical to
    its golden twin, the standby 503s (with Retry-After) before
    promotion, >= 1 fenced call after revival, and the multi-spill
    journal audit — primary spill, standby spill (takeover pairing +
    epoch monotonicity bind here), the standby's primary-journal
    replica, and both member spills — clean."""
    import json as _json
    import shutil
    import socket
    import subprocess
    import tempfile
    import urllib.error
    import urllib.request

    from ollamamq_tpu.tools.journal import check_files
    from ollamamq_tpu.telemetry.journal import load_jsonl

    n = args.router_ha
    max_new = 14  # under the fake runtime's 16-token ceiling
    golden_text = "".join(f"word{i} " for i in range(max_new))
    tmp = tempfile.mkdtemp(prefix="ollamamq-ha-")
    wal_p = os.path.join(tmp, "wal-primary")
    wal_s = os.path.join(tmp, "wal-standby")
    procs = []

    def free_port():
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        return port

    def spawn(argv, log_name):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["FAKE_TOKEN_LATENCY_S"] = "0.05"
        logf = open(os.path.join(tmp, log_name), "wb")
        p = subprocess.Popen(
            [sys.executable, "-m", "ollamamq_tpu.cli"] + argv,
            stdout=logf, stderr=subprocess.STDOUT, env=env)
        p._logf = logf
        procs.append(p)
        return p

    def get_health(port, timeout=2.0):
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/health", timeout=timeout) as r:
            return _json.loads(r.read())

    def wait_health(port, budget=90.0, ok=None):
        """Poll /health until `ok(body)` (default: not recovering)."""
        if ok is None:
            ok = lambda b: b.get("status") != "recovering"  # noqa: E731
        deadline = time.monotonic() + budget
        while time.monotonic() < deadline:
            try:
                body = get_health(port)
                if ok(body):
                    return body
            except Exception:  # noqa: BLE001
                pass
            touch("router_ha")
            time.sleep(0.2)
        raise RuntimeError(f"server on :{port} never became healthy")

    def prom_counter(port, name):
        """Sum a counter across its label rows off /metrics; None if
        the metric never fired (no rows exported)."""
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10) as r:
            text = r.read().decode()
        total, found = 0.0, False
        for line in text.splitlines():
            if line.startswith(name) and " " in line:
                try:
                    total += float(line.rsplit(" ", 1)[1])
                    found = True
                except ValueError:
                    pass
        return total if found else None

    class Client:
        """One NDJSON stream: records frames + token ids, notes its
        req_id, survives the router dying mid-read (resume() collects
        the remainder — possibly from a DIFFERENT router port)."""

        def __init__(self, port, user, prompt):
            self.port = port
            self.user = user
            self.prompt = prompt
            self.rid = None
            self.text = ""
            self.ids = []
            self.done_reason = None
            self.thread = threading.Thread(target=self._run, daemon=True)
            self.thread.start()

        def _consume(self, resp):
            for raw in resp:
                obj = _json.loads(raw)
                if obj.get("req_id") is not None:
                    self.rid = int(obj["req_id"])
                self.ids.extend(int(t) for t in obj.get("token_ids") or ())
                self.text += obj.get("response", "")
                if obj.get("done"):
                    self.done_reason = obj.get("done_reason", "stop")
                    return

        def _run(self):
            body = _json.dumps({
                "model": "test-tiny", "prompt": self.prompt,
                "stream": True, "options": {"num_predict": max_new}})
            req = urllib.request.Request(
                f"http://127.0.0.1:{self.port}/api/generate",
                data=body.encode(),
                headers={"Content-Type": "application/json",
                         "X-User-ID": self.user}, method="POST")
            try:
                with urllib.request.urlopen(req, timeout=120) as resp:
                    self._consume(resp)
            except Exception:  # noqa: BLE001 — the primary died under us
                pass

        def resume(self):
            req = urllib.request.Request(
                f"http://127.0.0.1:{self.port}/api/stream/{self.rid}"
                f"?from={len(self.ids)}",
                headers={"X-User-ID": self.user}, method="GET")
            with urllib.request.urlopen(req, timeout=120) as resp:
                self._consume(resp)

    # -- topology ----------------------------------------------------------
    ports = {"a": free_port(), "b": free_port(),
             "primary": free_port(), "standby": free_port()}
    member_argv = ["--fake-engine", "--no-tui", "--models", "test-tiny",
                   "--blocklist", os.path.join(tmp, "bl.json")]
    spawn(member_argv + ["--port", str(ports["a"]),
                         "--journal-file", os.path.join(tmp, "ma.jsonl")],
          "member_a.log")
    spawn(member_argv + ["--port", str(ports["b"]),
                         "--journal-file", os.path.join(tmp, "mb.jsonl")],
          "member_b.log")
    replica_urls = (f"http://127.0.0.1:{ports['a']},"
                    f"http://127.0.0.1:{ports['b']}")

    def primary_argv(journal_tag=""):
        return ["--fake-engine", "--no-tui", "--models", "test-tiny",
                "--port", str(ports["primary"]),
                "--replicas", "0", "--replica-urls", replica_urls,
                "--ha", "--takeover-grace-s", "1.0",
                "--wal-dir", wal_p, "--wal-fsync-ms", "5",
                "--journal-file",
                os.path.join(tmp, f"router-primary{journal_tag}.jsonl"),
                "--blocklist", os.path.join(tmp, "bl.json")]

    standby_argv = [
        "--fake-engine", "--no-tui", "--models", "test-tiny",
        "--port", str(ports["standby"]),
        "--replicas", "0", "--replica-urls", replica_urls,
        "--standby-of", f"http://127.0.0.1:{ports['primary']}",
        "--takeover-grace-s", "1.0",
        "--wal-dir", wal_s, "--wal-fsync-ms", "5",
        "--journal-file", os.path.join(tmp, "standby.jsonl"),
        "--blocklist", os.path.join(tmp, "bl.json")]

    try:
        wait_health(ports["a"])
        wait_health(ports["b"])
        primary = spawn(primary_argv(), "primary.log")
        wait_health(ports["primary"])
        standby = spawn(standby_argv, "standby.log")
        # Standby is healthy once it reports its role AND has applied
        # the cold snapshot (lag 0 against an idle primary).
        wait_health(ports["standby"],
                    ok=lambda b: b.get("role") == "standby"
                    and b.get("sync_lag_records") == 0)

        # -- golden leg (through the primary, untouched) -------------------
        golden = [Client(ports["primary"], f"ha{i % 4}", f"router ha {i}")
                  for i in range(n)]
        for c in golden:
            c.thread.join(timeout=120)
        golden_ok = all(c.text == golden_text for c in golden)

        # -- standby never serves pre-promotion ----------------------------
        standby_503 = False
        retry_after = None
        try:
            urllib.request.urlopen(urllib.request.Request(
                f"http://127.0.0.1:{ports['standby']}/api/generate",
                data=_json.dumps({"model": "test-tiny", "prompt": "x",
                                  "stream": False}).encode(),
                headers={"Content-Type": "application/json"},
                method="POST"), timeout=10)
        except urllib.error.HTTPError as e:
            standby_503 = e.code in (429, 503)
            retry_after = e.headers.get("Retry-After")

        # -- chaos leg: kill -9 the primary mid-decode ---------------------
        clients = [Client(ports["primary"], f"ha{i % 4}", f"router ha {i}")
                   for i in range(n)]
        deadline = time.monotonic() + 120.0
        killed_at = None
        pre_kill_lag = None
        while time.monotonic() < deadline:
            touch("router_ha")
            tokens = sum(len(c.ids) for c in clients)
            if tokens >= 4 * n and all(c.rid is not None for c in clients):
                try:  # standby's replication position just before the cut
                    pre_kill_lag = get_health(
                        ports["standby"]).get("sync_lag_records")
                except Exception:  # noqa: BLE001
                    pass
                primary.kill()  # SIGKILL: no drain, no handover
                killed_at = time.monotonic()
                break
            if all(c.done_reason is not None for c in clients):
                break
            time.sleep(0.05)
        if killed_at is None:
            raise RuntimeError("streams finished before the kill point")
        for c in clients:
            c.thread.join(timeout=30)  # readers die with the primary

        # Promotion: role flips standby -> (promoting) -> primary, and
        # the WAL replay must be done before clients resume.
        wait_health(ports["standby"], budget=60.0,
                    ok=lambda b: b.get("role") == "primary"
                    and b.get("status") != "recovering")
        takeover_observed_ms = round((time.monotonic() - killed_at) * 1e3)
        for c in clients:
            if c.done_reason is None and c.rid is not None:
                c.port = ports["standby"]
                c.resume()

        # -- revive the zombie primary: every member must fence it --------
        zombie = spawn(primary_argv(journal_tag="-zombie"), "zombie.log")
        time.sleep(3.0)  # register + WAL recovery placements, all fenced
        touch("router_ha")
        fenced = sum(
            prom_counter(ports[m], "ollamamq_ha_fenced_calls_total") or 0
            for m in ("a", "b"))
        # The promoted router must still place fresh work while the
        # zombie is being turned away.
        probe = Client(ports["standby"], "ha-probe", "post takeover")
        probe.thread.join(timeout=60)
        post_ok = probe.text == golden_text

        # -- scoring -------------------------------------------------------
        dropped = sum(1 for c in clients if c.done_reason is None)
        mismatches = [i for i, c in enumerate(clients)
                      if c.text != golden_text]
        silent = sum(1 for i in mismatches
                     if golden_text.startswith(clients[i].text)
                     and clients[i].done_reason in ("stop", "length"))
        id_exact = all(c.ids == list(range(1, max_new + 1))
                       for c in clients if c.done_reason)

        # Graceful close of the promoted standby flushes its spill (its
        # handover attempt no-ops: nobody tails it). The zombie is
        # killed hard — its spill stays out of the audit below.
        zombie.kill()
        standby.send_signal(15)
        try:
            standby.wait(timeout=60)
        except subprocess.TimeoutExpired:
            standby.kill()
        # Multi-spill audit as ONE run: the dead primary's spill, the
        # standby's spill (router_takeover pairing + epoch monotonicity
        # bind here), the standby's primary-journal replica (byte copy,
        # journal_meta replica_of excludes it from the cross-spill
        # duplicate-epoch check), and both member spills (epoch_fence
        # sanity binds there). The ZOMBIE's spill is excluded by
        # design: its recovery replays streams other spills already
        # resolved, at an epoch the fleet fenced — it is not part of
        # the surviving run.
        spills = [p for p in
                  (os.path.join(tmp, "router-primary.jsonl"),
                   os.path.join(tmp, "standby.jsonl"),
                   os.path.join(wal_s, "primary-journal.jsonl"),
                   os.path.join(tmp, "ma.jsonl"),
                   os.path.join(tmp, "mb.jsonl"))
                  if os.path.exists(p)]
        violations, audited = check_files(spills)
        takeover_ms = None
        new_epoch = None
        try:
            _, srecs = load_jsonl(os.path.join(tmp, "standby.jsonl"))
            for r in srecs:
                if r.get("kind") == "router_takeover" \
                        and r.get("phase") == "done":
                    takeover_ms = r.get("takeover_ms")
                    new_epoch = r.get("epoch")
        except Exception:  # noqa: BLE001 — readout only, never the gate
            pass
        return {
            "requests": n,
            "max_new_tokens": max_new,
            "takeover_ms": takeover_ms,
            "takeover_observed_ms": takeover_observed_ms,
            "epoch_after_takeover": new_epoch,
            "pre_kill_sync_lag_records": pre_kill_lag,
            "standby_shed_pre_promotion": standby_503,
            "standby_retry_after_s": retry_after,
            "fenced_calls": fenced,
            "post_takeover_stream_ok": post_ok,
            "dropped_streams": dropped,
            "silent_truncations": silent,
            "stream_mismatches": len(mismatches),
            "resumed_streams": sum(1 for c in clients
                                   if c.rid is not None
                                   and c.done_reason is not None),
            "token_exact": id_exact,
            "golden_leg_ok": golden_ok,
            "journal_spills_audited": len(spills),
            "journal_records_audited": audited,
            "invariant_violations": len(violations),
            "violations_sample": violations[:5],
            "pass": bool(golden_ok and dropped == 0 and silent == 0
                         and not mismatches and id_exact
                         and standby_503 and retry_after is not None
                         and fenced >= 1 and post_ok
                         and takeover_observed_ms < 60_000
                         and not violations),
        }
    finally:
        for p in procs:
            try:
                p.kill()
                p.wait(timeout=10)
            except Exception:  # noqa: BLE001
                pass
            try:
                p._logf.close()
            except Exception:  # noqa: BLE001
                pass
        shutil.rmtree(tmp, ignore_errors=True)


def _overload_scenario(rt, core, args, rng, touch):
    """Graceful-degradation acceptance: N requests arrive faster than the
    engine drains them, over a bounded queue, with a seeded fault plan
    supplying KV-allocation pressure (every few decode-time page growths
    fail => preemption with recompute) and one injected prefill fault
    (=> contained retry). Reports shed rate, preemption count, recompute
    token overhead, deadline drops, p99 TTFT — and `silent_truncations`,
    which the chaos acceptance criterion requires to be ZERO: every
    request either completes or carries an explicit shed/deadline/error
    reason."""
    import statistics
    import time

    from ollamamq_tpu.engine.engine import drop_expired
    from ollamamq_tpu.engine.request import FinishReason, Request
    from ollamamq_tpu.ops.sampling import SamplingParams
    from ollamamq_tpu.telemetry import schema as tm
    from ollamamq_tpu.testing.faults import FaultPlan

    n_total = args.overload
    qcap = args.overload_queue_cap or max(2, 2 * args.slots)
    max_ctx = rt.ecfg.max_pages_per_seq * rt.ecfg.page_size
    prompt_len = min(args.prompt_len, 64)
    max_new = 16
    hi = min(rt.cfg.vocab_size, 30000)

    def drain():
        for s, r in enumerate(rt.slot_req):
            if r is not None:
                rt._finish_slot(s, FinishReason.CANCELLED, core)

    drain()
    # The prefill path IS the ragged mixed dispatch.
    prefill_site = "ragged"
    plan = FaultPlan([
        # KV pressure: every 5th decode-time page growth "fails",
        # driving the preempt-with-recompute path repeatedly.
        {"site": "extend", "kind": "alloc_fail", "every": 5},
        # One transient prefill fault: its batch must retry and survive.
        {"site": prefill_site, "kind": "exception", "at": [4]},
    ], seed=7)
    rt.fault_plan = plan
    # Flight recorder on: the chaos run becomes a checked artifact —
    # batch occupancy / padding waste read off the journal, and the
    # invariant checker must stay clean under injected pressure.
    from ollamamq_tpu.telemetry.journal import (Journal, batch_stats,
                                                check_invariants)
    journal = Journal(capacity=65536)
    rt.journal = journal

    recompute = {"tokens": 0}
    preempt0, retries0 = rt.preempt_count, rt.retry_count

    def requeue(req):
        # The engine's on_preempt hook, bench-local: front of the queue,
        # deadline honored, recompute overhead tallied.
        if req.expired():
            drop_expired(req, core, rt.name)
            return False
        recompute["tokens"] += len(req.prompt_tokens)
        rt.pending_prefill.appendleft(req)
        return True

    rt.on_preempt = requeue

    def shed_count():
        return sum(c.value for _, c in tm.SHED_TOTAL.series())

    def deadline_count():
        return sum(c.value for _, c in tm.DEADLINE_DROPS_TOTAL.series())

    shed0, dl0 = shed_count(), deadline_count()
    reqs, shed_at_admission, issued = [], 0, 0
    peak_active = 0
    t_start = time.monotonic()
    guard = 0
    while True:
        # Arrivals: a burst of 4 per engine tick — strictly faster than
        # the batch drains, so the bounded queue must shed.
        burst = 0
        while issued < n_total and burst < 4:
            burst += 1
            if len(rt.pending_prefill) + len(rt.chunking) >= qcap:
                # Bounded admission (the server's 503/429 path): count
                # the shed, never construct engine-side state for it.
                tm.SHED_TOTAL.labels(reason="queue_full").inc()
                shed_at_admission += 1
                issued += 1
                continue
            prompt = rng.integers(3, hi, size=prompt_len).tolist()
            sp = SamplingParams(max_tokens=max_new)
            if issued % 5 == 4:
                # Every 5th request carries a tight deadline; under the
                # backlog some expire in queue and must drop BEFORE
                # prefill, with the explicit deadline reason.
                sp = SamplingParams(max_tokens=max_new, deadline_ms=30.0)
            req = Request(40000 + issued, f"ovl{issued % 8}", rt.name,
                          prompt, sp)
            req._inc_decode = rt.tokenizer.make_incremental_decoder()
            reqs.append(req)
            rt.pending_prefill.append(req)
            issued += 1
        # One engine tick: admission + chunk/mixed dispatch + decode.
        progressed = False
        try:
            progressed = _pump(rt, core, touch, "overload")
            if any(r is not None for r in rt.slot_req):
                progressed = (rt.step_decode(core, k_steps=2) > 0) \
                    or progressed
        except Exception as e:
            # The acceptance criterion is ZERO engine crashes: any
            # escape from the contained paths fails the scenario.
            raise RuntimeError(f"engine step escaped containment: "
                               f"{type(e).__name__}: {e}")
        touch("overload")
        peak_active = max(peak_active,
                          sum(1 for r in rt.slot_req if r is not None)
                          + len(rt.chunking))
        unresolved = [r for r in reqs if not r.stats.finished_at]
        if issued >= n_total and not unresolved:
            break
        guard += 1
        if guard > 2000 * n_total:
            raise RuntimeError(
                f"overload scenario wedged: {len(unresolved)} unresolved")
        if not progressed:
            if not unresolved:
                break
            time.sleep(0.001)  # head-of-queue backoff: don't spin hot
    elapsed_s = time.monotonic() - t_start

    outcomes: dict = {}
    silent_truncations = 0
    ttfts = []
    for r in reqs:
        item = None
        for it in r.stream.drain():
            if it.kind in ("done", "error"):
                item = it
        reason = (item.finish_reason.value
                  if item is not None and item.finish_reason else "none")
        outcomes[reason] = outcomes.get(reason, 0) + 1
        if r.stats.first_token_at:
            ttfts.append(r.stats.ttft_ms)
        if (item is not None and item.finish_reason == FinishReason.LENGTH
                and len(r.generated_ids) < r.sampling.max_tokens
                and len(r.prompt_tokens) + len(r.generated_ids) + 1 < max_ctx):
            silent_truncations += 1  # MUST stay 0: the bug this PR kills

    ttfts.sort()
    served = len(ttfts)
    rt.journal = None  # detach before later scenarios reuse this runtime
    jrecs = journal.tail(None)
    # Density readout: how many of THIS workload's requests the pool
    # could hold concurrently at the configured HBM (pages per request =
    # prompt + generation headroom), next to the observed peak — the
    # quantized-vs-bf16 A/B line reads straight off these when two
    # rounds differ only in --kv-dtype.
    pages_per_req = rt.alloc.pages_needed(prompt_len + max_new)
    return {
        "requests": n_total,
        "queue_cap": qcap,
        "kv_dtype": rt.kv_dtype,
        "weights_dtype": rt.weights_dtype,
        "peak_active": peak_active,
        "concurrent_capacity_at_hbm": (rt.alloc.num_pages - 1)
        // max(1, pages_per_req),
        "journal": batch_stats(jrecs),
        "invariant_violations": len(check_invariants(jrecs)),
        "elapsed_s": round(elapsed_s, 3),
        "shed": int(shed_count() - shed0),
        "shed_at_admission": shed_at_admission,
        "shed_rate": round((shed_count() - shed0) / max(1, n_total), 4),
        "deadline_drops": int(deadline_count() - dl0),
        "preemptions": rt.preempt_count - preempt0,
        "retries": rt.retry_count - retries0,
        "recompute_tokens": recompute["tokens"],
        "injected_faults": plan.injected,
        "outcomes": outcomes,
        "served": served,
        "ttft_p50_ms": round(ttfts[served // 2], 1) if served else None,
        "ttft_p99_ms": (round(ttfts[min(served - 1,
                                        int(0.99 * served))], 1)
                        if served else None),
        "silent_truncations": silent_truncations,
    }


def _density_scenario(rt, model_cfg, args, rng, touch):
    """Serving-density A/B at EQUAL HBM: size a bf16-KV pool to hold
    only ~half the offered concurrency, compute its byte budget, size an
    int8-KV pool to the SAME budget (more pages per byte), and drive the
    identical arrival trace through both. The int8 leg must hold ~2x the
    concurrent requests (2*hd/(hd+4) exactly — fp32 scale rows are the
    overhead) and therefore preempt/shed less at the same arrival rate.
    The int8-vs-bf16 weight-quality guardrail (teacher-forced greedy
    token match + max logit error) and the journal invariant checker run
    in-band; `gate` summarizes pass/fail for the density regression."""
    import time

    from ollamamq_tpu.config import EngineConfig
    from ollamamq_tpu.core import MQCore
    from ollamamq_tpu.engine import kv_cache as kvc
    from ollamamq_tpu.engine.engine import ModelRuntime, drop_expired
    from ollamamq_tpu.engine.request import Request
    from ollamamq_tpu.models import weights as weights_mod
    from ollamamq_tpu.ops.sampling import SamplingParams
    from ollamamq_tpu.telemetry.journal import Journal, check_invariants

    n_total = args.density
    slots = min(args.slots, 4)
    prompt_len = min(args.prompt_len, 32)
    max_new = 8
    ps = rt.ecfg.page_size
    pages_per_req = -(-(prompt_len + max_new) // ps) + 1
    # bf16 pool: room for ~half the decode batch -> the trace MUST hit
    # the ceiling, so preemptions register on the scoreboard.
    pages_bf16 = max(2, (slots * pages_per_req) // 2) + 1
    budget = pages_bf16 * kvc.kv_page_bytes(model_cfg, ps,
                                            kv_dtype="bfloat16")
    pages_int8 = budget // kvc.kv_page_bytes(model_cfg, ps,
                                             kv_dtype="int8")
    hd = model_cfg.head_dim
    expected_ratio = 2 * hd / (hd + 4)

    def run_leg(kv_dtype, num_pages):
        ecfg = EngineConfig(
            model=args.model, max_slots=slots, num_pages=num_pages + 1,
            page_size=ps, max_pages_per_seq=pages_per_req + 2,
            prefill_buckets=(max(32, prompt_len),), max_new_tokens=max_new,
            decode_steps_per_iter=2,
            max_batch_tokens=max(64, slots * 16), token_granule=16,
            weights_dtype=args.weights_dtype, kv_dtype=kv_dtype,
            preempt=True, preempt_max=2, seed=rt.ecfg.seed,
        )
        leg = ModelRuntime(args.model, model_cfg, ecfg,
                           preloaded_params=rt.params)
        leg.tokenizer.eos_id = -1  # full-length streams: equal pressure
        journal = Journal(capacity=65536)
        leg.journal = journal
        core = MQCore(None)
        recompute = {"tokens": 0}

        def requeue(req):
            if req.expired():
                drop_expired(req, core, leg.name)
                return False
            recompute["tokens"] += len(req.prompt_tokens)
            leg.pending_prefill.appendleft(req)
            return True

        leg.on_preempt = requeue
        trace = __import__("numpy").random.default_rng(1234)
        hi = min(model_cfg.vocab_size, 30000)
        reqs, issued, peak_active, guard = [], 0, 0, 0
        t0 = time.monotonic()
        while True:
            while issued < n_total and len(leg.pending_prefill) < 4:
                prompt = trace.integers(3, hi, size=prompt_len).tolist()
                req = Request(60000 + issued, f"dn{issued % 4}", leg.name,
                              prompt, SamplingParams(max_tokens=max_new))
                req._inc_decode = leg.tokenizer.make_incremental_decoder()
                reqs.append(req)
                leg.pending_prefill.append(req)
                issued += 1
            progressed = leg.step_ragged(core)
            if any(r is not None for r in leg.slot_req):
                progressed = (leg.step_decode(core, k_steps=2) > 0) \
                    or progressed
            touch("density")
            peak_active = max(peak_active,
                              sum(1 for r in leg.slot_req if r is not None)
                              + len(leg.chunking))
            unresolved = [r for r in reqs if not r.stats.finished_at]
            if issued >= n_total and not unresolved:
                break
            guard += 1
            if guard > 3000 * n_total:
                raise RuntimeError(
                    f"density leg {kv_dtype} wedged: "
                    f"{len(unresolved)} unresolved")
            if not progressed and unresolved:
                time.sleep(0.001)
        outcomes = {}
        for r in reqs:
            item = None
            for it in r.stream.drain():
                if it.kind in ("done", "error"):
                    item = it
            reason = (item.finish_reason.value
                      if item is not None and item.finish_reason else "none")
            outcomes[reason] = outcomes.get(reason, 0) + 1
        jrecs = journal.tail(None)
        leg.journal = None
        return {
            "kv_dtype": kv_dtype,
            "pages": num_pages,
            "kv_pool_bytes": leg.kv_bytes,
            "concurrent_capacity_at_hbm": num_pages // pages_per_req,
            "peak_active": peak_active,
            "preemptions": leg.preempt_count,
            "kv_exhausted": outcomes.get("kv_exhausted", 0),
            "recompute_tokens": recompute["tokens"],
            "outcomes": outcomes,
            "elapsed_s": round(time.monotonic() - t0, 3),
            "invariant_violations": len(check_invariants(jrecs)),
        }

    bf16 = run_leg("bfloat16", pages_bf16)
    int8 = run_leg("int8", pages_int8)

    # Weight-quality guardrail: int8 tree vs its bf16 source. Reuses the
    # runtime-under-test's params for whichever side it already is.
    guardrail = None
    try:
        if args.weights_dtype == "int8":
            base = weights_mod.load_params(model_cfg, None,
                                           seed=rt.ecfg.seed)
            qp = rt.params
        else:
            base = rt.params
            qp = weights_mod.quantize_params_int8(rt.params, model_cfg)
        guardrail = weights_mod.quant_guardrail(
            model_cfg, base_params=base, q_params=qp,
            seed=rt.ecfg.seed, prompt_len=8, steps=4)
        touch("density")
    except Exception as e:
        guardrail = {"error": f"{type(e).__name__}: {e}"}

    ratio = int8["concurrent_capacity_at_hbm"] / max(
        1, bf16["concurrent_capacity_at_hbm"])
    reasons = []
    if ratio < 0.85 * expected_ratio:
        reasons.append(f"capacity ratio {ratio:.2f} under "
                       f"{0.85 * expected_ratio:.2f}")
    if int8["preemptions"] > bf16["preemptions"]:
        reasons.append("int8 leg preempted MORE than bf16 at equal HBM")
    if int8["invariant_violations"] or bf16["invariant_violations"]:
        reasons.append("journal invariant violations")
    if (isinstance(guardrail, dict)
            and guardrail.get("token_match_rate", 1.0) < 0.8):
        reasons.append("quality guardrail under 0.8 token match")
    return {
        "requests": n_total,
        "hbm_budget_bytes": budget,
        "page_bytes_bf16": kvc.kv_page_bytes(model_cfg, ps,
                                             kv_dtype="bfloat16"),
        "page_bytes_int8": kvc.kv_page_bytes(model_cfg, ps,
                                             kv_dtype="int8"),
        "capacity_ratio": round(ratio, 3),
        "expected_ratio": round(expected_ratio, 3),
        "bf16": bf16,
        "int8": int8,
        "guardrail": guardrail,
        "gate": "pass" if not reasons else "fail",
        "gate_reasons": reasons,
    }


def _speculative_scenario(rt, core, args, rng, touch):
    """Speculative-decoding acceptance: the same prompt mix driven
    spec-off then spec-on at the same seed, on the serving-path tick
    shape (one mixed/decode dispatch per tick — the regime the ISSUE
    targets, where decode tok/s is bounded by dispatch rate).

    Two generation regimes, because draft accept rate is a property of
    what the model GENERATES, not of the engine: random weights produce
    chaotic streams no lookup can predict, so the "repetitive" leg
    rebuilds the same architecture as a deterministic copy map (residual
    output projections zeroed => next token is a pure function of the
    last => generation enters a cycle, exactly the regime real LMs hit
    on repetitive text) and measures spec-on vs spec-off tok/s there;
    the "non_repetitive" leg keeps the real random weights and reports
    the accept rate and whether the per-user auto-throttle engaged.
    Both legs assert byte-identical streams — `identical` and
    `silent_truncations` land in the record."""
    import time

    import jax.numpy as jnp

    from ollamamq_tpu.engine.request import FinishReason, Request
    from ollamamq_tpu.ops.sampling import SamplingParams

    if not getattr(rt, "ragged", False):
        return {"skipped": "speculation needs the ragged path (pp=1)"}
    n_req = min(args.speculative, args.slots)
    # Floor high enough that the spec-on leg sees several STEADY verify
    # dispatches after its compile ticks are excluded — a 2-tick sample
    # is noise, not a measurement.
    max_new = max(24, min(48, args.steps))
    prompt_len = min(args.prompt_len, 48)
    hi = min(rt.cfg.vocab_size, 30000)

    def drain():
        for s, r in enumerate(rt.slot_req):
            if r is not None:
                rt._finish_slot(s, FinishReason.CANCELLED, core)

    def make_prompts(repetitive):
        out = []
        for i in range(n_req):
            if repetitive and i % 2 == 0:
                pat = rng.integers(3, hi, size=6).tolist()
                out.append((pat * ((prompt_len // 6) + 1))[:prompt_len])
            else:
                out.append(rng.integers(3, hi, size=prompt_len).tolist())
        return out

    def copy_map_cycle(start, budget=128):
        """The copy model's next-token map is context-free (next =
        argmax(logits(embed[last]))), so its cycle is computable
        off-engine: iterate the map until a token repeats. Prompts tiled
        from the cycle make generation predictable from the FIRST decode
        tick — the repetitive regime at full strength even on a short
        smoke run. One probe step is a full-vocab logit row (heavy on a
        big CPU-smoke model), so the walk is budgeted and probed ONCE;
        an unclosed walk degrades to its tail (lower accept, reported
        honestly)."""
        from ollamamq_tpu.models import llama as llm

        seen, seq, t = {}, [], int(start)
        for _ in range(budget):
            if t in seen:
                return seq[seen[t]:]
            seen[t] = len(seq)
            seq.append(t)
            x = rt.params["embed"][t][None, None, :]
            t = int(jnp.argmax(llm._logits(rt.params, rt.cfg, x)[0, 0]))
        return seq[-16:]

    def cycle_prompts():
        # One probe, rotated per request: any rotation of a cycle is
        # still map-consecutive, so every prompt stays predictable.
        cyc = copy_map_cycle(int(rng.integers(3, hi)))
        out = []
        for i in range(n_req):
            rot = cyc[i % len(cyc):] + cyc[:i % len(cyc)]
            out.append((rot * (prompt_len // len(rot) + 2))[:prompt_len])
        return out

    def run_leg(prompts, spec_on, idx0, new_tokens=None):
        """Drive one A/B leg on the serving-path tick shape. Throughput
        is computed over STEADY-STATE ticks only: a tick that grew the
        jit cache paid a compile, and counting it would bill one leg
        for one-time cost the other never sees — this is also what
        makes the scenario affordable on slow backends (no separate
        full-length warmup leg per mode)."""
        drain()
        rt.spec = spec_on
        rt._spec_user.clear()
        rt._spec_throttled.clear()
        p0, a0, r0 = rt.spec_proposed, rt.spec_accepted, rt.spec_rollbacks
        reqs = []
        for i, p in enumerate(prompts):
            req = Request(50000 + idx0 + i, f"spec{i}", rt.name, list(p),
                          SamplingParams(max_tokens=new_tokens or max_new))
            req._inc_decode = rt.tokenizer.make_incremental_decoder()
            rt.pending_prefill.append(req)
            reqs.append(req)
        ticks = 0
        steady_s, steady_tokens, gen_prev = 0.0, 0, 0
        while not all(r.stats.finished_at for r in reqs):
            jits0 = len(rt._prefill_jits) + len(rt._decode_jits)
            t0 = time.monotonic()
            progressed = rt.step_ragged(core)
            if not progressed and any(r is not None for r in rt.slot_req):
                progressed = rt.step_decode(core, k_steps=1) > 0
            dt = time.monotonic() - t0
            touch("speculative")
            ticks += 1
            gen_now = sum(len(r.generated_ids) for r in reqs)
            if len(rt._prefill_jits) + len(rt._decode_jits) == jits0:
                steady_s += dt
                steady_tokens += gen_now - gen_prev
            gen_prev = gen_now
            if ticks > 4000 * max(1, n_req):
                raise RuntimeError("speculative leg wedged")
        return {
            "streams": [list(r.generated_ids) for r in reqs],
            "tok_s": (round(steady_tokens / steady_s, 1)
                      if steady_s > 0 else 0.0),
            "ticks": ticks,
            "proposed": rt.spec_proposed - p0,
            "accepted": rt.spec_accepted - a0,
            "rollbacks": rt.spec_rollbacks - r0,
            "throttled_users": len(rt._spec_throttled),
        }

    spec0, k0, min0 = rt.spec, rt.ecfg.spec_k, rt.ecfg.spec_min_accept
    eos0 = rt.tokenizer.eos_id
    layers = rt.params["layers"]
    orig_wo, orig_wd = layers["wo"], layers["w_down"]
    rt.ecfg.spec_k = args.spec_k
    rt.tokenizer.eos_id = -1  # full-length streams: compare whole outputs
    silent_truncations = 0
    try:
        # Repetitive regime: deterministic copy map (see docstring),
        # prompts tiled from the map's own cycle so drafts verify from
        # the first decode tick. One untimed warmup leg per mode first:
        # each leg's jit variants must be compiled before the A/B is
        # timed, or the first leg pays compile time the second doesn't.
        layers["wo"] = jnp.zeros_like(orig_wo)
        layers["w_down"] = jnp.zeros_like(orig_wd)
        rt.ecfg.spec_min_accept = 0.0  # measuring, not throttling
        rep_prompts = cycle_prompts()
        rep_off = run_leg(rep_prompts, spec_on=False, idx0=0)
        rep_on = run_leg(rep_prompts, spec_on=True, idx0=1000)
        rep_identical = rep_off["streams"] == rep_on["streams"]
        for leg in (rep_off, rep_on):
            silent_truncations += sum(
                1 for s in leg.pop("streams") if len(s) < max_new)
        # Chaotic regime: real weights, default throttle — what accept
        # rate does prompt-lookup actually get, and does the throttle
        # stop paying for hopeless users? (Accept-rate readout only;
        # spec-on/off byte-identity across regimes is pinned by tier-1
        # tests/test_spec_decoding.py, so no off-baseline leg is spent
        # here — the CPU-smoke budget is tight on a 1B model.)
        layers["wo"], layers["w_down"] = orig_wo, orig_wd
        rt.ecfg.spec_min_accept = 0.1
        chaos_new = max(8, max_new // 2)  # readout leg: keep it cheap
        chaos_on = run_leg(make_prompts(repetitive=False), spec_on=True,
                           idx0=3000, new_tokens=chaos_new)
        silent_truncations += sum(
            1 for s in chaos_on.pop("streams") if len(s) < chaos_new)
    finally:
        layers["wo"], layers["w_down"] = orig_wo, orig_wd
        rt.spec = spec0
        rt.ecfg.spec_k = k0
        rt.ecfg.spec_min_accept = min0
        rt.tokenizer.eos_id = eos0
        rt._spec_user.clear()
        rt._spec_throttled.clear()
        drain()
    prop = max(1, rep_on["proposed"])
    cprop = max(1, chaos_on["proposed"])
    return {
        "requests": n_req,
        "max_new": max_new,
        "spec_k": args.spec_k,
        "repetitive": {
            "tok_s_spec_off": rep_off["tok_s"],
            "tok_s_spec_on": rep_on["tok_s"],
            "speedup": round(rep_on["tok_s"] / max(0.001,
                                                   rep_off["tok_s"]), 2),
            "ticks_off": rep_off["ticks"],
            "ticks_on": rep_on["ticks"],
            "proposed": rep_on["proposed"],
            "accepted": rep_on["accepted"],
            "accept_rate": round(rep_on["accepted"] / prop, 4),
            "rollbacks": rep_on["rollbacks"],
            "identical": rep_identical,
        },
        "non_repetitive": {
            "proposed": chaos_on["proposed"],
            "accepted": chaos_on["accepted"],
            "accept_rate": round(chaos_on["accepted"] / cprop, 4),
            "rollbacks": chaos_on["rollbacks"],
            "throttled_users": chaos_on["throttled_users"],
        },
        "silent_truncations": silent_truncations,
    }


def _slo_burst_scenario(rt, core, args, rng, touch):
    """Bursty arrivals against a TTFT SLO on a drained runtime: each of
    B bursts drops `--slo-burst-size` requests into the prefill queue at
    once, then steps the engine until every request has its first token.
    Requests carry real traces, so the report includes the latency
    attribution breakdown (mean ms per phase — under a burst, queueing
    behind batch-mates dominates) plus the burn rate against
    --slo-ttft-ms at a 0.99 target. One warmup burst (compiles the
    batched-prefill jit) is excluded from the recorded stats."""
    import statistics
    import time

    from ollamamq_tpu.engine.request import FinishReason, Request
    from ollamamq_tpu.ops.sampling import SamplingParams
    from ollamamq_tpu.telemetry import attribution
    from ollamamq_tpu.telemetry.slo import AlertManager, SLOEngine
    from ollamamq_tpu.telemetry.tracing import Tracer

    from ollamamq_tpu.telemetry.journal import Journal, batch_stats

    target = 0.99
    tracer = Tracer(capacity=args.slo_burst * args.slo_burst_size + 8)
    slo = SLOEngine(AlertManager(), ttft_ms=args.slo_ttft_ms, target=target)
    hi = min(rt.cfg.vocab_size, 30000)
    # Journal the bursts: batch occupancy and padding waste per burst
    # land in the BENCH record (how much of each padded prefill forward
    # was real work).
    journal = Journal(capacity=16384)

    def drain():
        for s, r in enumerate(rt.slot_req):
            if r is not None:
                rt._finish_slot(s, FinishReason.CANCELLED, core)

    def run_burst(idx0, record):
        reqs = []
        for i in range(args.slo_burst_size):
            prompt = rng.integers(3, hi, size=args.prompt_len).tolist()
            req = Request(30000 + idx0 + i, f"burst{i}", rt.name, prompt,
                          SamplingParams(max_tokens=10**9))
            req._inc_decode = rt.tokenizer.make_incremental_decoder()
            req.trace = tracer.begin(req.req_id, req.user, rt.name)
            reqs.append(req)
        # The burst lands at once; admission order is queue order.
        for req in reqs:
            req.trace_event("admit")
            rt.pending_prefill.append(req)
        while any(not r.stats.first_token_at for r in reqs):
            progressed = _pump(rt, core, touch, "slo_burst")
            if not progressed and not rt.chunking:
                raise RuntimeError("slo_burst request never admitted "
                                   "(slots/pages too small for the burst?)")
        if record:
            for req in reqs:
                slo.record("ttft", req.stats.ttft_ms)
        drain()  # finishes the traces (outcome: cancelled)
        return [r.stats.ttft_ms for r in reqs]

    drain()
    run_burst(0, record=False)  # warmup: compiles the B=MAX batch jit
    rt.journal = journal  # after warmup: stats cover recorded bursts only
    ttfts = []
    t0 = time.monotonic()
    for b in range(args.slo_burst):
        ttfts.extend(run_burst((b + 1) * 1000, record=True))
    elapsed_s = time.monotonic() - t0
    rt.journal = None

    # Attribution breakdown: mean per-phase ms over the recorded bursts'
    # finished traces (warmup requests excluded by req_id).
    phase_sums, n_traces = {}, 0
    for tr in tracer.traces():
        if not tr.finished or tr.req_id < 31000:
            continue
        n_traces += 1
        for phase, ms in attribution.phase_totals(list(tr.events)).items():
            phase_sums[phase] = phase_sums.get(phase, 0.0) + ms
    violations = sum(1 for t in ttfts if t > args.slo_ttft_ms)
    obj = slo.objectives["ttft"]
    return {
        "bursts": args.slo_burst,
        "burst_size": args.slo_burst_size,
        "slo_ttft_ms": args.slo_ttft_ms,
        "target": target,
        "elapsed_s": round(elapsed_s, 3),
        "ttft_p50_ms": round(statistics.median(ttfts), 1),
        "ttft_max_ms": round(max(ttfts), 1),
        "violations": violations,
        "violation_ratio": round(violations / max(1, len(ttfts)), 4),
        # Burn over a window covering the whole run: ratio_bad / budget.
        "burn_rate": round(obj.burn_rate(max(60.0, elapsed_s + 5)), 2),
        "journal": batch_stats(journal.tail(None)),
        "attribution_ms": {
            p: round(phase_sums[p] / max(1, n_traces), 2)
            for p in attribution.PHASES if p in phase_sums
        },
    }


def _shared_prefix_scenario(rt, core, args, rng, touch):
    """TTFT for N same-prefix users, cache off vs on, on a drained
    runtime. One warmup (compile) request per leg is excluded from the
    means; the on-leg warmup also seeds the tree, so every timed on-leg
    request is a hit."""
    import statistics
    import time

    import numpy as np

    from ollamamq_tpu.engine.prefix_cache import PrefixCache
    from ollamamq_tpu.engine.request import FinishReason, Request
    from ollamamq_tpu.ops.sampling import SamplingParams

    ps = rt.ecfg.page_size
    prefix_len = max(ps, (args.shared_prefix_len // ps) * ps)
    tail_len = max(1, args.shared_prefix_tail)
    n = prefix_len + tail_len
    if rt.alloc.pages_needed(n + 1) > rt.ecfg.max_pages_per_seq:
        return {"skipped": f"prompt of {n} tokens exceeds the page budget "
                           f"({rt.ecfg.max_pages_per_seq} pages/seq)"}
    hi = min(rt.cfg.vocab_size, 30000)
    prefix = rng.integers(3, hi, size=prefix_len).tolist()

    def drain():
        for s, r in enumerate(rt.slot_req):
            if r is not None:
                rt._finish_slot(s, FinishReason.CANCELLED, core)

    def run_one(i):
        prompt = prefix + rng.integers(3, hi, size=tail_len).tolist()
        req = Request(20000 + i, f"spuser{i}", rt.name, prompt,
                      SamplingParams(max_tokens=10**9))
        req._inc_decode = rt.tokenizer.make_incremental_decoder()
        rt.pending_prefill.append(req)
        t0 = time.monotonic()
        while not req.stats.first_token_at:
            progressed = _pump(rt, core, touch, "shared_prefix")
            if not progressed and not rt.chunking:
                raise RuntimeError("shared_prefix request never admitted "
                                   "(page budget?)")
        ms = (time.monotonic() - t0) * 1e3
        drain()  # finish-on-install: the on-leg insert populates the tree
        return ms

    drain()
    legs = {}
    for leg, idx0 in (("off", 0), ("on", 1000)):
        if leg == "on":
            rt.prefix_cache = PrefixCache(ps, rt.alloc, model=rt.name)
        run_one(idx0)  # warmup: compiles (off) / seeds the tree (on)
        legs[leg] = statistics.mean(
            run_one(idx0 + 1 + i) for i in range(args.shared_prefix))
    stats = rt.prefix_cache.stats()
    return {
        "users": args.shared_prefix,
        "prefix_tokens": prefix_len,
        "tail_tokens": tail_len,
        "hit_ratio": stats["hit_ratio"],
        "tokens_saved": stats["tokens_saved"],
        "ttft_cache_off_ms": round(legs["off"], 1),
        "ttft_cache_on_ms": round(legs["on"], 1),
        "ttft_delta_ms": round(legs["off"] - legs["on"], 1),
    }


if __name__ == "__main__":
    sys.exit(main())
