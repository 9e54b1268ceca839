#!/usr/bin/env python3
"""Bench regression sentinel: diff the BENCH_r*.json trajectory.

The driver wraps every official bench round as
``{"n": int, "cmd": str, "rc": int, "tail": str, "parsed": dict|null}``
where ``parsed`` is the last JSON line bench.py printed (the structured
result record — success OR the ``_emit_error`` failure line). This tool
classifies each round and diffs the *comparable* ones:

- ``init-failed``  — the round never got a working device (nonzero rc
  with no parsed record, or a parsed error record from the init phase,
  e.g. "device/runtime init exceeded 300s"). These are environment casualties, NOT
  performance regressions, and are excluded from all comparisons.
- ``failed``       — bench ran but died past init (parsed error record
  with a non-init phase). Excluded from comparisons, reported loudly.
- ``ok``           — a real measurement (rc == 0, value > 0).

Between consecutive ``ok`` rounds it checks:

- headline ``decode_tok_per_s_per_chip`` drop >= --threshold-pct
- per-mode step p99 (from the ``step_profile`` summary block, when both
  rounds carry one) increase >= --threshold-pct

Exit codes: 0 = no regression (including "nothing comparable"),
2 = regression detected, 1 = usage/load error. Stdlib-only on purpose —
it must run in the bare driver container, before any jax import works.

Usage:
    python scripts/bench_compare.py                  # BENCH_r*.json in cwd
    python scripts/bench_compare.py A.json B.json    # explicit trajectory
    python scripts/bench_compare.py --threshold-pct 10
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys


def load_round(path: str) -> dict:
    """One driver wrapper -> {"path", "n", "rc", "parsed", ...}."""
    with open(path, "r", encoding="utf-8") as f:
        rec = json.load(f)
    if not isinstance(rec, dict):
        raise ValueError(f"{path}: not a JSON object")
    rec.setdefault("rc", 0)
    rec.setdefault("parsed", None)
    rec["path"] = path
    # Round ordering key: the driver's round number when present, else
    # the filename (BENCH_r03.json sorts correctly either way).
    rec.setdefault("n", os.path.basename(path))
    return rec


def classify(rec: dict) -> str:
    """'init-failed' | 'failed' | 'ok' for one round wrapper."""
    parsed = rec.get("parsed")
    rc = rec.get("rc", 0)
    if parsed is None:
        # Crashed before bench.py could even print its structured line
        # (round 1 in history: jax backend init raised). Only an error
        # if rc says so; an rc-0 round with no record is also unusable.
        return "init-failed" if rc != 0 else "failed"
    if not isinstance(parsed, dict):
        return "failed"
    if parsed.get("error"):
        phase = parsed.get("phase", "")
        if phase == "init":
            return "init-failed"
        # No phase tag + zero value + nonzero rc: bench never measured
        # anything — treat as an init-class casualty, not a regression.
        if not phase and rc != 0 and not parsed.get("value"):
            return "init-failed"
        return "failed"
    if rc != 0:
        return "failed"
    return "ok"


def _step_p99s(parsed: dict) -> dict:
    """{mode: step p99 ms} from a record's step_profile block, if any."""
    sp = parsed.get("step_profile")
    if not isinstance(sp, dict):
        return {}
    out = {}
    for mode, phases in (sp.get("modes") or {}).items():
        step = (phases or {}).get("step") or {}
        p99 = step.get("p99_ms")
        if isinstance(p99, (int, float)) and p99 > 0:
            out[mode] = float(p99)
    return out


def compare(prev: dict, cur: dict, threshold_pct: float) -> list:
    """Regressions going prev -> cur, as human-readable strings."""
    regs = []
    pv = float(prev["parsed"].get("value") or 0.0)
    cv = float(cur["parsed"].get("value") or 0.0)
    if pv > 0:
        drop_pct = (pv - cv) / pv * 100.0
        if drop_pct >= threshold_pct:
            regs.append(
                f"tok/s regression: {pv:.1f} -> {cv:.1f} "
                f"(-{drop_pct:.1f}% >= {threshold_pct:g}%)")
    prev_p99 = _step_p99s(prev["parsed"])
    cur_p99 = _step_p99s(cur["parsed"])
    for mode in sorted(set(prev_p99) & set(cur_p99)):
        a, b = prev_p99[mode], cur_p99[mode]
        rise_pct = (b - a) / a * 100.0
        if rise_pct >= threshold_pct:
            regs.append(
                f"step p99 regression [{mode}]: {a:.2f}ms -> {b:.2f}ms "
                f"(+{rise_pct:.1f}% >= {threshold_pct:g}%)")
    return regs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="diff BENCH_r*.json rounds; exit 2 on regression")
    ap.add_argument("files", nargs="*",
                    help="round files in order (default: BENCH_r*.json "
                         "in the current directory, sorted)")
    ap.add_argument("--threshold-pct", type=float, default=20.0,
                    help="regression threshold in percent (default 20)")
    ap.add_argument("--json", action="store_true",
                    help="emit a machine-readable report to stdout")
    args = ap.parse_args(argv)

    files = args.files or sorted(glob.glob("BENCH_r*.json"))
    if not files:
        print("bench_compare: no BENCH_r*.json files found", file=sys.stderr)
        return 1
    try:
        rounds = [load_round(p) for p in files]
    except (OSError, ValueError, json.JSONDecodeError) as e:
        print(f"bench_compare: {e}", file=sys.stderr)
        return 1
    rounds.sort(key=lambda r: (str(r["n"]).zfill(8)
                               if not isinstance(r["n"], int)
                               else f"{r['n']:08d}"))

    report = {"rounds": [], "regressions": [], "threshold_pct":
              args.threshold_pct}
    comparable = []
    for rec in rounds:
        status = classify(rec)
        row = {"n": rec["n"], "path": rec["path"], "status": status}
        if status == "ok":
            row["tok_per_s"] = rec["parsed"].get("value")
            comparable.append(rec)
        elif isinstance(rec.get("parsed"), dict):
            row["error"] = rec["parsed"].get("error")
        report["rounds"].append(row)

    for prev, cur in zip(comparable, comparable[1:]):
        for msg in compare(prev, cur, args.threshold_pct):
            report["regressions"].append(
                {"from": prev["n"], "to": cur["n"], "what": msg})

    if args.json:
        print(json.dumps(report, indent=2))
    else:
        for row in report["rounds"]:
            extra = ""
            if row["status"] == "ok":
                extra = f"  {row['tok_per_s']} tok/s/chip"
            elif row.get("error"):
                extra = f"  ({row['error']})"
            print(f"round {row['n']}: {row['status']}{extra}")
        if len(comparable) < 2:
            print(f"bench_compare: {len(comparable)} comparable round(s) — "
                  f"nothing to diff")
        for reg in report["regressions"]:
            print(f"REGRESSION r{reg['from']} -> r{reg['to']}: "
                  f"{reg['what']}")
        if not report["regressions"] and len(comparable) >= 2:
            print(f"bench_compare: {len(comparable)} comparable rounds, "
                  f"no regression >= {args.threshold_pct:g}%")
    return 2 if report["regressions"] else 0


if __name__ == "__main__":
    sys.exit(main())
