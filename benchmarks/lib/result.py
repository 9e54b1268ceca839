"""The last line: built in one place, and held to a literal encoding of the
contract before it is printed. A line that does not validate is a crash
with the reason, never a print."""

from __future__ import annotations

import json
import math

TOP_KEYS = ("correct", "attempted", "failed", "metrics", "device")
DEVICE_KEYS = ("platform", "kind", "count", "memory_peak_bytes")
TRACED_DEVICE_KEYS = ("busy_s", "window_s")
BREAKDOWN_KEYS = ("device_ops", "idle_gaps")


class MalformedResult(Exception):
    pass


def _number(x) -> bool:
    return (isinstance(x, (int, float)) and not isinstance(x, bool)
            and math.isfinite(x))


def build(correct: bool, attempted: int, failed: int, values: dict,
          units: dict, device: dict, breakdown: dict | None = None) -> dict:
    line = {
        "correct": bool(correct), "attempted": int(attempted),
        "failed": int(failed),
        "metrics": {name: {"value": v, "unit": units[name]}
                    for name, v in values.items()},
        "device": device,
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    return line


def validate(line: dict, expected_units: dict, traced: bool) -> None:
    """`expected_units`: name -> unit of every metric this kind of run of
    this workload must report (BENCHMARK.json's)."""
    def bad(why: str):
        raise MalformedResult(why)

    if not isinstance(line, dict):
        bad("the line is not an object")
    allowed = set(TOP_KEYS) | ({"breakdown"} if traced else set())
    if set(line) - allowed:
        bad(f"extra top-level keys {sorted(set(line) - allowed)}")
    for k in TOP_KEYS:
        if k not in line:
            bad(f"missing top-level key {k!r}")
    if not isinstance(line["correct"], bool):
        bad("'correct' is not true or false")
    for k in ("attempted", "failed"):
        if not isinstance(line[k], int) or isinstance(line[k], bool) \
                or line[k] < 0:
            bad(f"{k!r} is not a count")
    if line["failed"] > line["attempted"]:
        bad("more failed than attempted")
    metrics = line["metrics"]
    if not isinstance(metrics, dict):
        bad("'metrics' is not an object")
    if set(metrics) != set(expected_units):
        bad(f"metrics are {sorted(metrics)}, this run must report "
            f"{sorted(expected_units)}")
    for name, m in metrics.items():
        if not isinstance(m, dict) or set(m) != {"value", "unit"}:
            bad(f"metric {name!r} is not {{value, unit}}: {m!r}")
        if not _number(m["value"]):
            bad(f"metric {name!r} has no finite value: {m['value']!r}")
        if m["unit"] != expected_units[name]:
            bad(f"metric {name!r} has unit {m['unit']!r}, "
                f"BENCHMARK.json says {expected_units[name]!r}")
    dev = line["device"]
    if not isinstance(dev, dict):
        bad("'device' is not an object")
    for k in DEVICE_KEYS + (TRACED_DEVICE_KEYS if traced else ()):
        if k not in dev:
            bad(f"device lacks {k!r}")
    if not all(isinstance(dev[k], str) and dev[k] for k in ("platform", "kind")):
        bad("device platform/kind are not names")
    if not isinstance(dev["count"], int) or dev["count"] < 1:
        bad(f"device count {dev['count']!r}")
    if not isinstance(dev["memory_peak_bytes"], int) \
            or isinstance(dev["memory_peak_bytes"], bool) \
            or dev["memory_peak_bytes"] < 0:
        bad(f"memory_peak_bytes {dev['memory_peak_bytes']!r}")
    if traced:
        busy, window = dev["busy_s"], dev["window_s"]
        if not _number(busy) or not _number(window):
            bad(f"busy_s {busy!r} / window_s {window!r} are not numbers")
        if not 0.0 < busy <= window:
            bad(f"need 0 < busy_s <= window_s, got {busy!r} and {window!r}")
    if "breakdown" in line:
        bd = line["breakdown"]
        if not isinstance(bd, dict) or set(bd) - set(BREAKDOWN_KEYS):
            bad(f"breakdown keys {bd!r}")
        for k, rows in bd.items():
            if not isinstance(rows, list) or len(rows) > 10:
                bad(f"breakdown.{k} is not a list of at most 10")
            for row in rows:
                if (not isinstance(row, list) or len(row) != 2
                        or not isinstance(row[0], str) or not _number(row[1])):
                    bad(f"breakdown.{k} row {row!r} is not [name, seconds]")
    json.loads(json.dumps(line, allow_nan=False))


def emit(line: dict) -> str:
    return json.dumps(line, allow_nan=False, separators=(", ", ": "))
