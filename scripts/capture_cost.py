#!/usr/bin/env python3
"""What a `POST /debug/profile` capture costs the path it measures (a
builder's tool; needs the chip).

    chiprun --timeout 1500 -- python scripts/capture_cost.py \
        --workload qwen2.5-7b-d14.batch --seed 2147485301

One server (the benchmark's own child, serving the cell's configuration),
the cell's traffic, ONE window of `--seconds`; inside it two captures of
`--capture-s` seconds, the first with the profiler's Python tracer off (the
program's default since PR 52), the second — `--gap-s` quiet seconds after
the first call replied — with it on. For each: the output
tokens a second that reached the clients while it recorded, from its end to
its reply, and over the quiet stretch before it; the seconds the call took;
the xplane's bytes; and, from the capture's own step samples (the reply's
`stepprof`), the host milliseconds a forward pass by phase over the
capture's first three fifths and its last two, beside the quiet stretch's —
with the engine thread's CPU seconds over the capture (two scrapes), so that
wall less CPU says whether the thread ran or waited for the GIL. One JSON
line last; the same under chiprun_out/capture_cost/.
"""

from __future__ import annotations

import argparse
import asyncio
import dataclasses
import glob
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks import run as bench, serve  # noqa: E402 — jax-free at import
from benchmarks.lib import loadgen, spec, stats, steps  # noqa: E402
from benchmarks.lib import traffic as tg  # noqa: E402
from benchmarks.lib.server import Child  # noqa: E402

PHASES = ("host_prep_ms", "dispatch_ms", "collect_ms", "detok_ms",
          "loop_admit_ms", "loop_other_ms", "loop_wait_ms")
CPU = "ollamamq_thread_cpu_seconds_total"


def per_pass(samples: list) -> dict:
    """Milliseconds a forward pass by phase, and the passes."""
    n = steps.total_passes(samples)
    out = {"samples": len(samples), "passes": n}
    for p in PHASES:
        out[p] = round(sum(float(s.get(p, 0.0)) for s in samples)
                       / max(1, n), 4)
    out["dry_lo_ms"] = round(sum(float(s.get("dry_lo_ms") or 0.0)
                                 for s in samples) / max(1, n), 4)
    return out


FIND_CLOCK = """
import json, sys
from jax.profiler import ProfileData
for plane in ProfileData.from_file(sys.argv[1]).planes:
    for line in plane.lines:
        for e in line.events:
            if e.name == "mq.clock":
                print(json.dumps({"start_ns": int(e.start_ns),
                                  "epoch_ns": int(dict(e.stats)["epoch_ns"])}))
"""


def clock_of(xplane: str) -> dict | None:
    """The capture's `mq.clock` span, parsed by a process of its own (it
    imports jax, pinned to the CPU; the server has exited by now). The
    xplane goes afterwards: two of them pass what a chip call brings back."""
    r = subprocess.run([sys.executable, "-c", FIND_CLOCK, xplane],
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       stdin=subprocess.DEVNULL, capture_output=True,
                       text=True)
    os.remove(xplane)
    return json.loads(r.stdout) if r.stdout.strip() else None


def rate(records: list, t0: float, t1: float) -> float:
    """Output tokens a second that arrived in [t0, t1) of the window."""
    n = sum(k for r in records for t, k in r.frames if t0 <= t < t1)
    return n / max(1e-9, t1 - t0)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="qwen2.5-7b-d14.batch")
    ap.add_argument("--seed", type=int, default=2147485301)
    ap.add_argument("--seconds", type=float, default=150.0)
    ap.add_argument("--capture-s", type=float, default=5.0)
    ap.add_argument("--at", type=float, default=12.0,
                    help="window second of the first capture")
    ap.add_argument("--gap-s", type=float, default=12.0,
                    help="quiet seconds from a capture's reply to the next")
    ap.add_argument("--quiet", action="store_true",
                    help="no capture at all: the untraced window's rate, "
                         "its samples by phase and overhead_fraction")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="the same control flow here, tiny; no number")
    args = ap.parse_args()
    cpu = args.rehearse_cpu

    cell = spec.load_cell(args.workload)
    cell = dataclasses.replace(cell, config=serve.as_run(cell.config, cpu))
    flags = serve.server_flags(cell.config, cpu)
    traffic = tg.rehearsal(dict(cell.traffic)) if cpu else dict(cell.traffic)
    out_dir = os.path.join(ROOT, "chiprun_out", "capture_cost", cell.name)
    os.makedirs(out_dir, exist_ok=True)
    subprocess.run(["make", "-C", os.path.join(ROOT, "cpp")], check=True,
                   capture_output=True, stdin=subprocess.DEVNULL)
    child = Child(cell.config_file, out_dir, cpu, True)
    try:
        child.wait_health(bench.HEALTH_TIMEOUT_S)
        gen = loadgen.LoadGen(child.base_url, cell.config["name"],
                              traffic,
                              int(cell.config["vocab_size"]), args.seed,
                              args.seconds)
        warm = bench.warm_up(gen, child, flags)
        replies, scrapes = {}, {}

        async def capture(session, key: str, tracer: bool):
            sent = time.time()
            async with session.post(
                    child.base_url + "/debug/profile",
                    json={"seconds": args.capture_s,
                          "python_tracer": tracer}) as r:
                replies[key] = await r.json()
            replies[key].update(sent_epoch=sent, reply_epoch=time.time())
            newest = max(glob.glob(os.path.join(
                out_dir, "profile", "plugins", "profile", "*",
                "*.xplane.pb")), key=os.path.getmtime)
            replies[key].update(xplane=newest,
                                xplane_bytes=os.path.getsize(newest))

        async def scrape(session, key: str, after_s: float):
            await asyncio.sleep(after_s)
            async with session.get(child.base_url + "/metrics") as r:
                scrapes[key] = (time.time(), await r.text())

        async def both(session):
            """One capture after the other: a second POST while the first
            call has not replied (stop_trace takes tens of seconds) is a
            409."""
            for key, tracer in (("off", False), ("on", True)):
                around = [asyncio.ensure_future(scrape(session, key + k, t))
                          for k, t in (("0", 0.6),
                                       ("1", args.capture_s - 0.3))]
                await capture(session, key, tracer)
                await asyncio.gather(*around)
                await asyncio.sleep(args.gap_s)

        gen.window_hooks = [] if args.quiet else [(args.at, both)]
        records = gen.run()
        e0 = time.time() - (time.monotonic() - gen.t0)
        summary = child.http("/debug/stepprof?n=1")["summary"]
        child.stop()
        with open(os.path.join(out_dir, "steps.jsonl")) as f:
            samples = [json.loads(line) for line in f if line.strip()]
    finally:
        child.stop()

    out = {"workload": cell.name, "seed": args.seed, "seconds": args.seconds,
           "warm_ok": warm["warm_ok"],
           "tokens_per_s_window": stats.tokens_in_window(
               records, args.seconds) / args.seconds,
           "overhead_fraction": summary["overhead_fraction"],
           "failed": sum(1 for r in records
                         if 0.0 <= r.due_s < args.seconds and not r.ok)}
    if args.quiet:
        out["per_pass_window"] = per_pass(
            steps.in_window(samples, e0, e0 + args.seconds))
        out["dry"] = summary["dry"]
    quiet_from = 2.0
    for key in () if args.quiet else ("off", "on"):
        rep = replies[key]
        cap = rep["capture"]
        at = rep["sent_epoch"] - e0
        c0, c1 = cap["start_epoch"] - e0, cap["stop_epoch"] - e0
        reply = rep["reply_epoch"] - e0
        own = rep["stepprof"]
        clock = clock_of(rep["xplane"])
        cut = cap["start_epoch"] + 0.6 * (cap["stop_epoch"]
                                          - cap["start_epoch"])
        quiet = steps.in_window(samples, e0 + quiet_from, e0 + at - 0.5)
        s0, s1 = scrapes[key + "0"], scrapes[key + "1"]
        cpu_s = (stats.prom_value(s1[1], CPU, thread="engine")
                 - stats.prom_value(s0[1], CPU, thread="engine"))
        between = steps.in_window(samples, s0[0], s1[0])
        out[key] = {
            "python_tracer": rep["python_tracer"],
            "call_s": rep["reply_epoch"] - rep["sent_epoch"],
            "start_trace_s": cap["start_epoch"] - rep["sent_epoch"],
            "stop_to_reply_s": rep["reply_epoch"] - cap["stop_epoch"],
            "xplane_bytes": rep["xplane_bytes"],
            "origin_epoch_ns": cap.get("origin_epoch_ns"),
            # mq.clock's place in the trace against what the two readings
            # of the realtime clock say it should be
            "clock_span": clock,
            "clock_off_by_ns": clock and clock["start_ns"] - (
                clock["epoch_ns"] - cap["origin_epoch_ns"]),
            "tok_s_quiet_before": rate(records, quiet_from, at - 0.5),
            "tok_s_recording": rate(records, c0, c1),
            "tok_s_stop_to_reply": rate(records, c1, reply),
            "per_pass_quiet_before": per_pass(quiet),
            "per_pass_head": per_pass([s for s in own if s["ts"] <= cut]),
            "per_pass_tail": per_pass([s for s in own if s["ts"] > cut]),
            "engine_cpu_s_between_scrapes": cpu_s,
            "scrape_wall_s": s1[0] - s0[0],
            "engine_wall_outside_collect_and_wait_s": sum(
                float(s["total_ms"]) + float(s["loop_admit_ms"])
                + float(s["loop_other_ms"]) - float(s["collect_ms"])
                for s in between) / 1e3,
        }
        quiet_from = reply + 2.0
    line = json.dumps(out)
    with open(os.path.join(out_dir, "capture_cost.json"), "w") as f:
        f.write(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
