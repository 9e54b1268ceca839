#!/usr/bin/env python3
"""What happens inside the chip's idle gaps: every event of every plane of a
kept `.xplane.pb` that overlaps a gap of the first chip's op line, with its
offset from the gap's start (a builder's tool; run it ON the chip machine
after a `--keep-trace` run, print, and delete the trace before the command
ends — a kept xplane is 12–43 MB and what comes back is capped).

    python benchmarks/run.py --workload olmoe-1b-7b-d10.batch --seed 7 \\
        --seconds 51 --trace 1 --keep-trace
    JAX_PLATFORMS=cpu python scripts/gap_events.py \\
        chiprun_out/benchmarks/<cell>/seed7_trace1/profile/plugins/profile/*/*.xplane.pb 3

Prints the planes, the gaps of 0.5 ms and more (count, median), then the
longest gap and N from the middle of the list (`lib/trace.py:idle_gaps`, the
harness's own): the module that ended, the TPU runtime's own threads
(`ReadSyncFlag`, `CompleteCallbacks`, `D2H Dispatch`, `TransferFromDevice`…),
the program's `mq.*` spans and the module that started. The host's clock and the chip's are NOT one clock: at PR 70 the
host's read ~0.85 ms late (a launch's `mq.host_prep` appears to end after the
module it launched began) — compare host events with host events.
"""

from __future__ import annotations

import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from benchmarks.lib.trace import OPS_LINES, idle_gaps  # noqa: E402

MIN_GAP_NS = 500_000
MARGIN_NS = 100_000  # events that start or end this close to a gap count
LONG_NS = 400_000_000  # the capture-long spans say nothing about a gap


def main(argv: list) -> int:
    from jax.profiler import ProfileData

    path, n = argv[1], int(argv[2]) if len(argv) > 2 else 3
    planes = list(ProfileData.from_file(path).planes)
    print("PLANES", [(p.name, [(ln.name, sum(1 for _ in ln.events))
                               for ln in p.lines][:12]) for p in planes][:12])
    dev = next(p for p in planes if p.name == "/device:TPU:0")
    lines = {ln.name: ln for ln in dev.lines}
    ops_line = next(name for name in OPS_LINES if name in lines
                    and any(True for _ in lines[name].events))
    ops = [[e.name[:60], int(e.start_ns), int(e.duration_ns)]
           for e in lines[ops_line].events]
    t0 = min(s for _, s, _ in ops)
    gaps = sorted((g for g in idle_gaps(ops, t0, t0) if g[1] > MIN_GAP_NS),
                  key=lambda g: -g[1])
    print("GAPS", len(gaps), "median_ms",
          statistics.median(g[1] for g in gaps) / 1e6 if gaps else None)
    mid = gaps[len(gaps) // 2: len(gaps) // 2 + n] if len(gaps) > 2 * n else []
    for g0, gd, next_op in gaps[:1] + mid:
        print(f"\nGAP start {g0} dur_ms {gd / 1e6:.3f} "
              f"next_op {next_op[:50]!r}")
        rows = []
        for p in planes:
            for ln in p.lines:
                if p is dev and ln.name == ops_line:
                    continue
                for e in ln.events:
                    s, d = int(e.start_ns), int(e.duration_ns)
                    if s < g0 + gd + MARGIN_NS and s + d > g0 - MARGIN_NS \
                            and d < LONG_NS:
                        rows.append((s - g0, d, p.name, ln.name[:28],
                                     e.name[:70]))
        for off, d, plane, line, name in sorted(rows)[:90]:
            print(f"  {off / 1e3:9.1f}us +{d / 1e3:9.1f}us  "
                  f"{plane[:14]:14s} {line:28s} {name}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
