#!/usr/bin/env python3
"""One run of one benchmark cell over the served path.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Builds cpp/libmqcore.so, starts one server child (benchmarks/serve.py: the
program's own CLI serving the cell's configuration), warms every shape the
cell's flags define, drives the cell's traffic over HTTP, stops the child,
and prints — last, after every child has exited — one JSON line that has
been held to the contract (lib/result.py). Everything else goes to earlier
lines and to chiprun_out/benchmarks/<workload>/.

This process never imports jax: the child holds the chip. No TPU is an
error, not a CPU run. `--rehearse-cpu` runs the same control flow at a tiny
size on the CPU and ends `correct: false` at the platform check.
`--sweep r1,r2,...` (builder's tool) holds each rate for the window after
one set-up and prints one line a rate instead of a result.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import random  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks import serve  # noqa: E402 — jax-free at import
from benchmarks.lib import loadgen, result, spec, stats, steps  # noqa: E402
from benchmarks.lib import traffic as tg  # noqa: E402
from benchmarks.lib.peaks import peaks_of  # noqa: E402
from benchmarks.lib.server import Child, ServerError  # noqa: E402

TRACE_SECONDS = 5.0
HEALTH_TIMEOUT_S = 900.0
REFERENCE_REQUESTS = 8     # of the window, evenly spaced, held to the reference
REFERENCE_TIMEOUT_S = 240.0


def say(note: str, /, **fields) -> None:
    """An earlier line: never the last one."""
    print(json.dumps({"note": note, **fields}), flush=True)


class Ctx:
    """What a metric's reader may look at."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def flag(flags: list, name: str, default: int) -> int:
    return int(flags[flags.index(name) + 1]) if name in flags else default


def warm_plan(flags: list) -> tuple:
    """The ragged ladder the server's flags define (the engine's own rule:
    a power-of-two ladder over the granule up to the token budget), a
    prompt that walks down all of it when sent alone, and the decode scans
    the loop can ask for."""
    g = flag(flags, "--token-granule", 16)
    slots = flag(flags, "--max-slots", 64)
    budget = -(-max(flag(flags, "--max-batch-tokens", 512), slots + g) // g) * g
    ladder, v = [], g
    while v < budget:
        ladder.append(v)
        v *= 2
    ladder.append(budget)
    k = flag(flags, "--decode-steps", 8)
    return ladder, sorted({1, k})


def warm_up(gen: loadgen.LoadGen, child: Child, flags: list) -> dict:
    """Every shape, by scripted requests; then the compile ledger says
    which keys compiled. One greedy prompt, alone, twice: the same ids."""
    ladder, ks = warm_plan(flags)
    n_predict = 2 * max(ks) + 2
    prompt = tg.make_text("warm", sum(ladder) - 1, random.Random(1))
    p = tg.Planned(index=-1, user="warm", prompt=prompt,
                   prompt_tokens=len(prompt) + 1, num_predict=n_predict)
    first = gen.alone([p])[0]
    second = gen.alone([p])[0]
    events = child.http("/debug/stepprof?n=256")["compile_events"]
    keys = [(e["site"], e["key"]) for e in events]
    missing = [f"ragged T_pad={t}" for t in ladder
               if not any(s == "ragged" and f"'ragged', {t}, 0," in k
                          for s, k in keys)]
    missing += [f"decode k={k}" for k in ks
                if not any(s == "decode" and key.startswith(f"({k},")
                           for s, key in keys)]
    return {"deterministic": bool(first.ok and second.ok
                                  and first.ids == second.ids),
            "warm_ok": bool(first.ok and second.ok),
            "warm_error": first.error or second.error,
            "compiled": [[e["site"], e["key"], e["wall_ms"]] for e in events],
            "unwarmed_keys": missing, "n_compiles": len(events)}


def server_checks(child: Child, chips: int) -> tuple:
    """(fields, problems) from the server's own account of what it ran on."""
    st = child.http("/metrics.json")
    rts = st.get("runtimes") or [{}]
    fields = {"platform": st.get("platform"), "kind": st.get("device_kind"),
              "count": st.get("device_count"),
              "attn_impl": [r.get("attn_impl") for r in rts],
              "retries": st.get("retries"), "preemptions": st.get("preemptions"),
              "runtime_failures": st.get("runtime_failures"),
              "rebuilds": st.get("rebuilds"), "shed": st.get("shed"),
              "pages_used": [r.get("pages_used") for r in rts],
              "pages_total": [r.get("pages_total") for r in rts],
              "param_bytes": [r.get("param_bytes") for r in rts],
              "kv_bytes": [r.get("kv_bytes") for r in rts],
              "hbm_used": [c.get("hbm_used") for c in st.get("chips", [])]}
    problems = []
    if fields["platform"] != "tpu":
        problems.append(f"platform is {fields['platform']!r}, not 'tpu'")
    if any(a != "pallas" for a in fields["attn_impl"]):
        problems.append(f"attn_impl is {fields['attn_impl']}, not pallas")
    if fields["count"] != chips:
        problems.append(f"{fields['count']} devices, the cell asks for {chips}")
    for k in ("retries", "runtime_failures", "rebuilds"):
        if fields[k]:
            problems.append(f"{k} = {fields[k]}")
    return fields, problems


def reference_check(child: Child, window: list, options: dict) -> dict:
    """The configuration's plain reference (serve.py runs it, after the
    window, on the weights served) over some of the window's completed
    requests. Shapes come from the whole window — the longest request, the
    most outputs — so every seed of a cell compiles the same program."""
    done = sorted((r for r in window if r.ok), key=lambda r: r.index)
    if not done:
        return {"agrees": False, "error": "no completed request to check"}
    n = min(REFERENCE_REQUESTS, len(done))
    picked = [done[(2 * i + 1) * len(done) // (2 * n)] for i in range(n)]
    return child.ask("reference", {
        "pad_to": -(-max(r.prompt_tokens + r.num_predict for r in done)
                    // 256) * 256,
        "max_out": -(-max(r.num_predict for r in done) // 64) * 64,
        "requests": [{"index": r.index, "prompt": r.prompt, "ids": r.ids,
                      "options": options} for r in picked]},
        REFERENCE_TIMEOUT_S)


def memory_content(fields: dict, at_end: dict, device: dict) -> dict:
    """What the peak holds: the pool is reserved whole (and the step
    programs hold a second copy of it), the traffic fills only part."""
    rts = at_end.get("runtimes") or [{}]
    used = sum(int(r.get("pages_used") or 0) for r in rts)
    total = sum(int(r.get("pages_total") or 0) for r in rts)
    params = sum(int(b or 0) for b in fields["param_bytes"])
    pool = sum(int(b or 0) for b in fields["kv_bytes"])
    chips = max(1, int(device["count"]))
    return {"peak_bytes_a_chip": device["memory_peak_bytes"],
            "param_bytes": params, "kv_pool_bytes": pool,
            "pages_used_at_window_end": used, "pages_total": total,
            "content_bytes_a_chip": (params + pool * used / max(1, total))
            / chips}


def reduce_trace(out_dir: str, chips: str, keep: bool, fixture: list) -> dict:
    """A process of its own parses the .xplane.pb (it imports jax, pinned
    to the CPU; the server has exited by now)."""
    prof = os.path.join(out_dir, "profile")
    red = os.path.join(out_dir, "trace_reduced.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(spec.BENCH_DIR, "lib", "trace.py"),
         prof, red, chips] + fixture,
        cwd=ROOT, env=env, stdin=subprocess.DEVNULL, capture_output=True,
        text=True)
    if not keep:
        shutil.rmtree(prof, ignore_errors=True)
    if not os.path.exists(red):
        raise RuntimeError(f"trace reduction wrote nothing: {r.stderr[-600:]}")
    with open(red) as f:
        out = json.load(f)
    if "error" in out:
        raise RuntimeError(f"trace reduction: {out['error']}; planes "
                           f"{out.get('planes')}")
    return out


def breakdown_of(trace: dict) -> dict:
    """The device ops that took most time, and the idle gaps of the first
    chip summed by the innermost host function that ran for the whole gap."""
    ops = sorted(trace["op_self_s"].items(), key=lambda kv: -kv[1])[:10]
    by_host: dict = {}
    for _, dur_ns, next_op, frame in trace["gaps"]:
        name = (frame or f"before {next_op}")[:120]
        by_host[name] = by_host.get(name, 0.0) + dur_ns / 1e9
    gaps = sorted(by_host.items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[n[:120], s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in gaps]}


def read_metrics(cell, group: str, ctx: Ctx) -> tuple:
    values, units = {}, {}
    for m in cell.metrics_of(group):
        units[m.name] = m.unit
        v = spec.load_reader(cell, m).read(ctx)
        if v is not None:
            values[m.name] = float(v)
    return values, units


def summarize(records: list, seconds: float, drain_ms: float) -> dict:
    tt = stats.ttft_ms(records, drain_ms)
    tp = stats.tpot_ms(records, drain_ms)
    return {"requests": len(records),
            "failed": sum(1 for r in records if not r.ok),
            "ttft_ms_p50": stats.percentile(tt, 50),
            "ttft_ms_p95": stats.percentile(tt, 95),
            "tpot_ms_p50": stats.percentile(tp, 50),
            "tpot_ms_p95": stats.percentile(tp, 95),
            "errors": sorted({r.error for r in records if r.error})[:3]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--keep-trace", action="store_true")
    ap.add_argument("--fixture-ms", type=float, default=0.0,
                    help="also write the first N ms of the device trace")
    ap.add_argument("--sweep", default="")
    args = ap.parse_args(argv)
    traced = bool(args.trace)

    cell = spec.load_cell(args.workload)
    seconds = float(args.seconds if args.seconds is not None
                    else cell.run_seconds)
    traffic = dict(cell.traffic)
    if args.rehearse_cpu:
        traffic = tg.rehearsal(traffic)
    try:   # the cell's configuration as this run runs it
        cell = dataclasses.replace(cell, config=serve.as_run(
            cell.config, args.rehearse_cpu))
    except serve.Refused as e:
        print(f"benchmark run failed: {cell.config_file}: {e}",
              file=sys.stderr)
        return 1
    flags = serve.server_flags(cell.config, args.rehearse_cpu)
    out_dir = os.path.join(ROOT, "chiprun_out", "benchmarks", cell.name,
                           f"seed{args.seed}_trace{args.trace}")
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)

    r = subprocess.run(["make", "-C", os.path.join(ROOT, "cpp")],
                       capture_output=True, text=True,
                       stdin=subprocess.DEVNULL)
    if r.returncode != 0:
        print(f"make failed: {r.stderr[-600:]}", file=sys.stderr)
        return 1

    child = Child(cell.config_file, out_dir, args.rehearse_cpu, traced)
    try:
        return drive(args, cell, child, traffic, flags, seconds, traced,
                     out_dir)
    except ServerError as e:
        print(f"benchmark run failed: {e}", file=sys.stderr)
        return 1
    finally:
        child.stop()


def drive(args, cell, child, traffic, flags, seconds, traced, out_dir) -> int:
    health_s = child.wait_health(HEALTH_TIMEOUT_S)
    gen = loadgen.LoadGen(child.base_url, cell.config["name"], traffic,
                          int(cell.config["vocab_size"]), args.seed, seconds)
    warm = warm_up(gen, child, flags)
    say("warm_up", health_s=health_s,
        warm_s=time.monotonic() - T_START - health_s,
        **{k: warm[k] for k in ("deterministic", "warm_ok", "warm_error",
                                "unwarmed_keys", "n_compiles", "compiled")})
    if not warm["warm_ok"]:
        raise ServerError(f"warm-up request failed: {warm['warm_error']}: "
                          f"{child.log_tail()}")
    drain_ms = 1e3 * float(traffic.get("drain_s", 30))

    if args.sweep:
        for rate in (float(x) for x in args.sweep.split(",")):
            g = loadgen.LoadGen(child.base_url, cell.config["name"],
                                dict(traffic, rate_per_s=rate),
                                gen.vocab, args.seed, seconds)
            recs = [r for r in g.run() if 0.0 <= r.due_s < seconds]
            def in_flight(t):
                return sum(1 for r in g.records if r.due_s <= t
                           and (r.last_s is None or r.last_s > t or not r.ok))
            say("sweep", rate_per_s=rate, offered=len(recs),
                finished_in_window=sum(
                    1 for r in g.records if r.ok and 0.0 <= r.last_s < seconds),
                in_flight_at_start=in_flight(0.0),
                in_flight_at_end=in_flight(seconds),
                tokens_per_s=stats.tokens_in_window(g.records, seconds) / seconds,
                lateness=loadgen.lateness_ms(recs),
                **summarize(recs, seconds, drain_ms))
        return 0

    prof_reply: dict = {}
    proms: dict = {}
    at_end: dict = {}

    async def pages(session):
        async with session.get(child.base_url + "/metrics.json") as r:
            at_end.update(await r.json())

    async def grab(session, key):
        async with session.get(child.base_url + "/metrics") as r:
            proms[key] = await r.text()

    async def profile(session):
        prof_reply["sent_epoch"] = time.time()
        async with session.post(child.base_url + "/debug/profile",
                                json={"seconds": trace_s}) as r:
            prof_reply.update(await r.json())
        prof_reply["reply_s"] = time.time() - prof_reply["sent_epoch"]

    gen.window_hooks = [(seconds, pages)]
    if traced:
        trace_s = min(TRACE_SECONDS, max(0.5, seconds / 2))
        gen.window_hooks += [
            (0.0, lambda s: grab(s, 0)),
            ((seconds - trace_s) / 2, profile),
            (seconds, lambda s: grab(s, 1)),
        ]
    all_records = gen.run()
    set_up_s = gen.t0 - T_START
    e0 = time.time() - (time.monotonic() - gen.t0)
    window = [r for r in all_records if 0.0 <= r.due_s < seconds]
    say("window", seconds=seconds, set_up_s=set_up_s,
        lateness=loadgen.lateness_ms(window),
        ramp_requests=sum(1 for r in all_records if r.due_s < 0),
        tokens_per_s=stats.tokens_in_window(all_records, seconds) / seconds,
        **summarize(window, seconds, drain_ms))

    events = child.http("/debug/stepprof?n=256")["compile_events"]
    in_window = [e for e in events if e0 <= e["ts"] <= e0 + seconds]
    say("compiles", total=len(events), in_window=len(in_window),
        in_window_keys=[[e["site"], e["key"], e["wall_ms"]] for e in in_window])
    if in_window:
        print(f"WARNING: {len(in_window)} compile(s) inside the measured "
              f"window: {[e['key'] for e in in_window]}", file=sys.stderr)
    fields, problems = server_checks(child, cell.chips)
    device = child.ask("device")   # the peak first: the reference allocates
    say("server", **fields, device=device, problems=problems)
    say("memory", **memory_content(fields, at_end, device))
    reference = reference_check(child, window, gen.options)
    say("reference", **reference)
    rc = child.stop()
    say("stopped", server_exit_code=rc)

    trace = samples = trace_steps = None
    if traced:
        with open(os.path.join(out_dir, "steps.jsonl")) as f:
            samples = [json.loads(line) for line in f if line.strip()]
        fixture = ([os.path.join(out_dir, "fixture.json"),
                    str(args.fixture_ms)] if args.fixture_ms else [])
        trace = reduce_trace(
            out_dir, "cpu" if args.rehearse_cpu else str(cell.chips),
            args.keep_trace, fixture)
        # The trace's clock starts when the profiler did: about when the
        # capture was asked for. The steps "inside the traced window" are
        # the samples that ended in that span of the epoch clock; the
        # profiler's start-up shifts it by a fraction of a second, which
        # steady traffic does not feel.
        c0 = prof_reply["sent_epoch"] + trace["t0_ns"] / 1e9
        trace_steps = steps.in_window(samples, c0, c0 + trace["window_s"])
        say("trace", window_s=trace["window_s"], busy_s=trace["busy_s"],
            per_chip=trace["per_chip"], planes=trace["planes"],
            xplane_bytes=trace["xplane_bytes"], t0_ns=trace["t0_ns"],
            profile_reply_s=prof_reply.get("reply_s"),
            capture_steps=len(trace_steps),
            capture_passes=steps.total_passes(trace_steps))

    peaks = None
    if device["platform"] == "tpu":
        peaks = peaks_of(device["kind"])
    ctx = Ctx(cell=cell, seconds=seconds, traced=traced, records=window,
              all_records=all_records, drain_limit_ms=drain_ms,
              set_up_s=set_up_s, prom0=proms.get(0), prom1=proms.get(1),
              steps=(steps.in_window(samples, e0, e0 + seconds)
                     if samples is not None else None),
              trace_steps=trace_steps,
              compile_events=events, window_epoch=(e0, e0 + seconds),
              trace=trace, device=device, peaks=peaks, say=say)
    values, units = read_metrics(
        cell, "per_layer" if traced else "end_to_end", ctx)

    completed_wrong = [r.index for r in window
                       if r.done_reason is not None and not r.ok]
    correct = (not completed_wrong and warm["deterministic"]
               and not problems and reference.get("agrees") is True)
    if not correct:
        say("not_correct", completed_wrong=completed_wrong[:10],
            deterministic=warm["deterministic"], problems=problems,
            reference_agrees=reference.get("agrees"),
            reference_error=reference.get("error"))
    # every number compared, beside its limit: the end of standard error
    print(f"correct={correct}: reference mean_margin_sd "
          f"{reference.get('mean_margin_sd')} (limit "
          f"{reference.get('mean_margin_sd_max')}, "
          f"{reference.get('positions')} positions); requests completed "
          f"wrong {len(completed_wrong)} (limit 0); the greedy prompt sent "
          f"twice the same ids: {warm['deterministic']}; server problems "
          f"{problems or 'none'}", file=sys.stderr, flush=True)
    dev = {k: device[k] for k in result.DEVICE_KEYS}
    bd = None
    if traced:
        dev["busy_s"], dev["window_s"] = trace["busy_s"], trace["window_s"]
        bd = breakdown_of(trace)
    line = result.build(correct, len(window),
                        sum(1 for r in window if not r.ok), values, units,
                        dev, bd)
    result.validate(line, units, traced)
    with open(os.path.join(out_dir, "result.json"), "w") as f:
        f.write(result.emit(line) + "\n")
    print(result.emit(line), flush=True)
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except BaseException as e:  # noqa: BLE001 — the reason, then no result
        import traceback

        traceback.print_exc()
        code = 1 if not isinstance(e, SystemExit) else (e.code or 0)
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
