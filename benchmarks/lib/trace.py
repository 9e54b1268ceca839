"""From a profiler trace (.xplane.pb) to device busy time, op times and idle
gaps. Two stages, so that the arithmetic can be tested without a chip:

  extract(path)  -> planes/lines/events as plain lists (needs jax, parsing
                    only: no device is touched; run as a process of its own
                    after the server has exited)
  reduce(planes) -> window_s, busy_s, self time by op, idle gaps

busy_s is the UNION of the op intervals of one chip's ops line, clipped to
the window, so 0 < busy_s <= window_s by construction; over several chips it
is the mean of each chip's own union. Never a sum over lines or chips.
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE, DRIVER_PLANE = "/host:CPU", "/host:driver"
DEVICE_WAIT = "np.asarray(jax.Array)"  # jax's own annotation of a blocking read
MIN_HOST_EVENT_NS = 100_000
OPS_LINES = ("XLA Ops", "XLA Modules")  # the first that a plane has
_HLO = re.compile(r"^%?(\S+) = (\(?[a-z0-9]+\[[0-9,]*\])")


def short_name(name: str) -> str:
    """An op's name and result shape out of the HLO text the TPU trace uses
    as the event name: `%copy.91 = bf16[14,98304,4,128]{...} copy(...)` ->
    `copy.91 bf16[14,98304,4,128]`. Other names pass unchanged."""
    m = _HLO.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name[:120]


class TraceError(Exception):
    pass


def find_xplane(profile_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        profile_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise TraceError(f"no .xplane.pb under {profile_dir}")
    return found[-1]


def extract(path: str, device_only: bool = True,
            cpu_stand_in: bool = False) -> list:
    """[{name, lines: [{name, events: [[name, start_ns, dur_ns], ...]}]}]
    `cpu_stand_in` (rehearsal on the CPU only): the host's XLA threads are
    merged into one line of a plane named like a chip's, so that the same
    reduction runs; its numbers mean nothing."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        if cpu_stand_in and plane.name == "/host:CPU":
            ev = [[e.name, int(e.start_ns), int(e.duration_ns)]
                  for ln in plane.lines if ln.name.startswith("tf_XLA")
                  for e in ln.events]
            planes.append({"name": "/device:TPU:0", "lines": [
                {"name": OPS_LINES[0], "events": ev, "n_events": len(ev)}]})
            continue
        if plane.name == HOST_PLANE and not cpu_stand_in:
            planes.append(_driver_plane(plane))
        if device_only and not DEVICE_PLANE.match(plane.name):
            planes.append({"name": plane.name, "lines": [
                {"name": ln.name, "events": [], "n_events": sum(
                    1 for _ in ln.events)} for ln in plane.lines]})
            continue
        lines = []
        for ln in plane.lines:
            ev = [[short_name(e.name), int(e.start_ns), int(e.duration_ns)]
                  for e in ln.events]
            lines.append({"name": ln.name, "events": ev, "n_events": len(ev)})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def _driver_plane(host_plane) -> dict:
    """The host thread that drives the device — the one that spends most
    time blocked reading device results — as a plane of its own: its
    Python-function events (the profiler's Python tracer, on the device
    events' clock) that last 0.1 ms or more."""
    best, best_wait = None, -1
    for ln in host_plane.lines:
        wait = sum(e.duration_ns for e in ln.events if e.name == DEVICE_WAIT)
        if wait > best_wait:
            best, best_wait = ln, wait
    ev = [] if best is None else [
        [e.name.lstrip("$"), int(e.start_ns), int(e.duration_ns)]
        for e in best.events if e.duration_ns >= MIN_HOST_EVENT_NS]
    return {"name": DRIVER_PLANE, "lines": [
        {"name": "python", "events": ev, "n_events": len(ev)}]}


def host_frame(driver_events: list, start_ns: int, dur_ns: int) -> str:
    """The host function a device gap is charged to: the one that overlaps
    most of it, and of those that do so equally (a function and its callers)
    the innermost."""
    best, best_key = None, None
    for name, s, d in driver_events:
        overlap = min(s + d, start_ns + dur_ns) - max(s, start_ns)
        if overlap > 0 and (best_key is None or (overlap, -d) > best_key):
            best, best_key = name, (overlap, -d)
    return best or "no host function overlaps it"


def union_s(intervals: list, t0: float, t1: float) -> float:
    """Seconds covered by [start_ns, end_ns) intervals, clipped to t0..t1."""
    covered, end = 0.0, t0
    for s, e in sorted(intervals):
        s, e = max(s, end), min(e, t1)
        if e > s:
            covered += e - s
            end = e
    return covered / 1e9


def self_times(events: list) -> dict:
    """Self time by name on one line: an event's duration minus what the
    events nested inside it cover (a `while` holds its body's ops)."""
    out: dict = {}
    stack: list = []  # [name, end_ns, self_ns]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, _, self_ns = stack.pop()
            out[name] = out.get(name, 0.0) + max(0, self_ns) / 1e9

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][2] -= min(dur, stack[-1][1] - start)
        stack.append([name, start + dur, dur])
    close(float("inf"))
    return out


def idle_gaps(events: list, t0: int, t1: int) -> list:
    """[[start_ns, dur_ns, next op]] between the ops of one line."""
    gaps, end = [], t0
    for name, start, dur in sorted(events, key=lambda e: e[1]):
        if start > end:
            gaps.append([end, start - end, name])
        end = max(end, start + dur)
    if t1 > end:
        gaps.append([end, t1 - end, "end of capture"])
    return gaps


def reduce(planes: list, chips: int | None = None) -> dict:
    devs = sorted((p for p in planes if DEVICE_PLANE.match(p["name"])),
                  key=lambda p: int(DEVICE_PLANE.match(p["name"]).group(1)))
    devs = [p for p in devs
            if any(ln["events"] for ln in p["lines"])]
    if not devs:
        raise TraceError("no device plane with events; planes: "
                         + ", ".join(p["name"] for p in planes))
    if chips is not None and len(devs) != chips:
        raise TraceError(f"{len(devs)} device planes with events, "
                         f"the cell runs on {chips} chips")
    per_chip, ops_by_name, op_count, gaps0 = [], {}, {}, []
    t0 = min(e[1] for p in devs for ln in p["lines"] for e in ln["events"])
    t1 = max(e[1] + e[2] for p in devs for ln in p["lines"]
             for e in ln["events"])
    for i, p in enumerate(devs):
        by_name = {ln["name"]: ln for ln in p["lines"]}
        line = next((by_name[n] for n in OPS_LINES
                     if n in by_name and by_name[n]["events"]), None)
        if line is None:
            raise TraceError(f"{p['name']} has none of the lines {OPS_LINES}; "
                             f"it has {sorted(by_name)}")
        ev = line["events"]
        busy = union_s([(s, s + d) for _, s, d in ev], t0, t1)
        per_chip.append({"plane": p["name"], "line": line["name"],
                         "events": len(ev), "busy_s": busy})
        for name, s in self_times(ev).items():
            ops_by_name[name] = ops_by_name.get(name, 0.0) + s / len(devs)
        for name, _, _ in ev:
            op_count[name] = op_count.get(name, 0.0) + 1.0 / len(devs)
        if i == 0:
            gaps0 = idle_gaps(ev, t0, t1)
    driver = [e for p in planes if p["name"] == DRIVER_PLANE
              for ln in p["lines"] for e in ln["events"]]
    gaps0 = sorted(gaps0, key=lambda g: -g[1])[:300]
    for g in gaps0:
        g.append(host_frame(driver, g[0], g[1]) if driver else "")
    window_s = (t1 - t0) / 1e9
    busy_s = sum(c["busy_s"] for c in per_chip) / len(per_chip)
    if not 0.0 < busy_s <= window_s:
        raise TraceError(f"busy_s {busy_s} not in (0, window_s {window_s}]")
    return {"t0_ns": t0, "t1_ns": t1, "window_s": window_s, "busy_s": busy_s,
            "per_chip": per_chip, "op_self_s": ops_by_name,
            "op_count": op_count,
            "gaps": gaps0,  # [start_ns, dur_ns, next op, host function]
            "gap_total_s": sum(g[1] for g in gaps0) / 1e9}


def trim(planes: list, ms: float) -> list:
    """The first `ms` milliseconds of the device planes, for a fixture."""
    starts = [e[1] for p in planes for ln in p["lines"] for e in ln["events"]]
    if not starts:
        return planes
    cut = min(starts) + int(ms * 1e6)
    return [{"name": p["name"], "lines": [
        {"name": ln["name"], "n_events": ln.get("n_events"),
         "events": [e for e in ln["events"] if e[1] + e[2] <= cut]}
        for ln in p["lines"]]} for p in planes]


def main(argv: list) -> int:
    """python benchmarks/lib/trace.py <profile dir | xplane.pb> <out.json>
    [chips | cpu] [fixture.json fixture_ms]"""
    src, out = argv[0], argv[1]
    stand_in = len(argv) > 2 and argv[2] == "cpu"
    chips = int(argv[2]) if len(argv) > 2 and not stand_in else None
    path = src if src.endswith(".pb") else find_xplane(src)
    planes = extract(path, cpu_stand_in=stand_in)
    summary = [{"plane": p["name"], "lines": [
        [ln["name"], ln.get("n_events", len(ln["events"]))]
        for ln in p["lines"]]} for p in planes]
    if len(argv) > 4:
        with open(argv[3], "w") as f:
            json.dump(trim([p for p in planes if DEVICE_PLANE.match(p["name"])],
                           float(argv[4])), f, separators=(",", ":"))
    try:
        red = reduce(planes, chips)
    except TraceError as e:
        red = {"error": str(e)}
    red["planes"] = summary
    red["xplane_bytes"] = os.path.getsize(path)
    with open(out, "w") as f:
        json.dump(red, f)
    return 1 if "error" in red else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
