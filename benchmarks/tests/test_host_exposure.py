"""The per-layer metrics that read what the program says about its own host
exposure (PR 37) — `collect_ms`, the done-bracket's `dry_lo_ms`, the two
threads' CPU clocks — and the two readers of PR 36's stream counters, on the
CPU:
    python -m pytest benchmarks/tests/test_host_exposure.py -q

Four entries are in BENCHMARK.json now: what PR 37's PARENT already records
(`device_wait_ms_per_step.lat/.thr`, `stream_frame_tokens`,
`stream_wakeups_per_step`). The seven that read PR 37's own fields and family
wait in `host_exposure_entries.json` beside this file: a reader with nothing
to read returns None, run.py then refuses the whole traced line
(test_span_metrics.py pins that), and the driver lays a PR's `benchmarks/`
over its parent. The first PR whose parent is PR 37 appends them. Nothing
here gives a device number."""

from __future__ import annotations

import os
import sys
import types

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from benchmarks.lib import spec  # noqa: E402

BENCHMARK = spec.load_json(os.path.join(ROOT, "BENCHMARK.json"))
WAITING = spec.load_json(os.path.join(BENCH, "tests",
                                      "host_exposure_entries.json"))
LISTED_NOW = ("device_wait_ms_per_step.lat", "device_wait_ms_per_step.thr",
              "stream_frame_tokens", "stream_wakeups_per_step")
CHAT = "qwen2.5-7b-d14.chat"


def reader(name: str):
    cell = types.SimpleNamespace(bench_dir=BENCH)
    return spec.load_reader(cell, spec.Metric(name, "", "per_layer", {}))


def ctx(**kw):
    base = dict(steps=None, trace_steps=None, trace=None, prom0=None,
                prom1=None)
    return types.SimpleNamespace(**{**base, **kw})


# Two samples as PR 37's program writes them (8 + 1 passes), and the same as
# its parent does (no dry fields).
SCAN = {"mode": "decode", "k_cap": 8, "tokens": 512, "total_ms": 110.0,
        "host_prep_ms": 1.0, "dispatch_ms": 2.0, "collect_ms": 100.0,
        "detok_ms": 7.0, "loop_admit_ms": 3.0, "loop_other_ms": 5.0,
        "loop_wait_ms": 50.0, "stream_items": 64, "stream_wakeups": 1,
        "dry_lo_ms": 0.0, "dry_hi_ms": 0.4, "dry_phase": None}
RAGGED = {"mode": "ragged", "k_cap": 0, "tokens": 200, "total_ms": 16.0,
          "host_prep_ms": 2.0, "dispatch_ms": 1.0, "collect_ms": 8.0,
          "detok_ms": 5.0, "loop_admit_ms": 1.0, "loop_other_ms": 0.0,
          "loop_wait_ms": 0.0, "stream_items": 60, "stream_wakeups": 1,
          "dry_lo_ms": 3.6, "dry_hi_ms": 112.0, "dry_phase": "host_prep"}
EMBED = {"mode": "embed", "k_cap": 0, "tokens": 9, "total_ms": 4.0,
         "host_prep_ms": 1.0, "dispatch_ms": 1.0, "collect_ms": 2.0,
         "detok_ms": 0.0, "loop_admit_ms": 0.0, "loop_other_ms": 0.0,
         "loop_wait_ms": 0.0}
DRY = ("dry_lo_ms", "dry_hi_ms", "dry_phase")
PARENT = [{k: v for k, v in s.items() if k not in DRY} for s in (SCAN, RAGGED)]


def exposition(engine=None, server=None, frames=None, tokens=None):
    text = "ollamamq_uptime_seconds 12\n"
    for thread, v in (("engine", engine), ("server", server)):
        if v is not None:
            text += ('ollamamq_thread_cpu_seconds_total'
                     f'{{thread="{thread}"}} {v}\n')
    if frames is not None:
        text += f"ollamamq_stream_frames_total {frames}\n"
    if tokens is not None:
        text += f"ollamamq_stream_frame_tokens_total {tokens}\n"
    return text


# ------------------------------------------------------------- the readers
@pytest.mark.parametrize("half", ["lat", "thr"])
def test_device_wait_is_collect_ms_over_passes(half):
    read = reader("device_wait_ms_per_step." + half).read
    assert read(ctx(steps=[SCAN, RAGGED])) == pytest.approx(108.0 / 9)
    # The parent's samples carry collect_ms too: listed now.
    assert read(ctx(steps=PARENT)) == pytest.approx(108.0 / 9)
    assert read(ctx(steps=[])) is None and read(ctx()) is None
    old = {k: v for k, v in RAGGED.items() if k != "collect_ms"}
    assert read(ctx(steps=[SCAN, old])) is None


@pytest.mark.parametrize("half", ["lat", "thr"])
def test_dry_ms_per_step_is_the_lower_bound_over_passes(half):
    read = reader("dry_ms_per_step." + half).read
    # 0 + 3.6 ms over 8 + 1 + 1 passes; an embed step has no bracket and
    # still is a pass of the window.
    assert read(ctx(steps=[SCAN, RAGGED, EMBED])) == pytest.approx(0.36)
    # A program older than PR 37: unknown, never zero.
    assert read(ctx(steps=PARENT)) is None
    assert read(ctx(steps=[])) is None and read(ctx()) is None


@pytest.mark.parametrize("half", ["lat", "thr"])
def test_idle_late_launch_pct_is_dry_seconds_over_idle_seconds(half):
    read = reader("idle_late_launch_pct." + half).read
    trace = {"window_s": 5.0, "busy_s": 4.99}
    # 3.6 ms dry of 10 ms idle, from the CAPTURE's samples.
    assert read(ctx(trace=trace, trace_steps=[SCAN, RAGGED],
                    steps=[RAGGED] * 9)) == pytest.approx(36.0)
    assert read(ctx(trace=trace, trace_steps=PARENT)) is None
    assert read(ctx(trace=trace, trace_steps=[])) is None
    assert read(ctx(trace=None, trace_steps=[RAGGED])) is None
    assert read(ctx(trace={"window_s": 5.0, "busy_s": 5.0},
                    trace_steps=[RAGGED])) is None


def test_the_cpu_metrics_are_counter_deltas_over_passes():
    p0 = exposition(engine=10.0, server=4.0)
    p1 = exposition(engine=10.045, server=4.018)
    steps = [SCAN, RAGGED]                      # 9 passes
    assert reader("engine_cpu_ms_per_step.thr").read(
        ctx(prom0=p0, prom1=p1, steps=steps)) == pytest.approx(5.0)
    assert reader("server_cpu_ms_per_step.thr").read(
        ctx(prom0=p0, prom1=p1, steps=steps)) == pytest.approx(2.0)
    # Wall outside collect and the idle wait: (110 + 3 + 5 - 100) +
    # (16 + 1 + 0 - 8) = 27 ms; minus 45 ms of CPU... the thread cannot
    # use more than its wall, so a fixture that fits: 18 ms of CPU.
    p1 = exposition(engine=10.018, server=4.018)
    assert reader("engine_offcpu_ms_per_step.thr").read(
        ctx(prom0=p0, prom1=p1, steps=steps)) == pytest.approx(1.0)
    for name in ("engine_cpu_ms_per_step.thr", "server_cpu_ms_per_step.thr",
                 "engine_offcpu_ms_per_step.thr"):
        read = reader(name).read
        old = exposition()                      # the parent: no such family
        assert read(ctx(prom0=old, prom1=old, steps=steps)) is None
        assert read(ctx(prom0=p0, prom1=p1, steps=[])) is None
        assert read(ctx(steps=steps)) is None   # an untraced run: no grab
    only_engine = exposition(engine=1.0)
    assert reader("server_cpu_ms_per_step.thr").read(
        ctx(prom0=only_engine, prom1=only_engine, steps=steps)) is None


def test_stream_frame_tokens_is_a_ratio_of_two_counter_deltas():
    read = reader("stream_frame_tokens").read
    p0 = exposition(frames=1000, tokens=1500)
    p1 = exposition(frames=3000, tokens=5000)
    assert read(ctx(prom0=p0, prom1=p1)) == pytest.approx(1.75)
    assert read(ctx(prom0=p0, prom1=p0)) is None          # no frame written
    assert read(ctx(prom0=exposition(), prom1=exposition())) is None
    assert read(ctx(prom0=p0, prom1=exposition(frames=3000))) is None
    assert read(ctx()) is None


def test_stream_wakeups_per_step_counts_steps_that_handed_over():
    read = reader("stream_wakeups_per_step").read
    quiet = dict(RAGGED, stream_items=0, stream_wakeups=0)
    assert read(ctx(steps=[SCAN, RAGGED, quiet, EMBED])) == pytest.approx(1.0)
    noisy = dict(RAGGED, stream_wakeups=60)     # PR 36's parent: one a row
    assert read(ctx(steps=[SCAN, noisy])) == pytest.approx(30.5)
    assert read(ctx(steps=[quiet, EMBED])) is None
    assert read(ctx(steps=[])) is None and read(ctx()) is None


# ------------------------------------------------ the entries, now and later
KEYS = {"name", "unit", "better", "source", "layer", "moves", "workloads"}
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _held_to_the_benchmark(e: dict) -> None:
    cells = {w["name"] for w in BENCHMARK["workloads"]}
    layers = {m["layer"] for m in BENCHMARK["per_layer"]}
    assert set(e) == KEYS, e
    assert e["better"] in ("lower", "higher") and e["source"] in SOURCES
    assert e["layer"] in layers, e["layer"]
    assert e["workloads"] and set(e["workloads"]) <= cells, e["name"]
    moved = next(m for m in BENCHMARK["end_to_end"] if m["name"] == e["moves"])
    assert set(e["workloads"]) <= set(moved["workloads"]), e["name"]
    half = e["name"].rsplit(".", 1)[-1]
    if half == "thr":       # a .thr half covers every throughput cell
        assert sorted(e["workloads"]) == sorted(moved["workloads"])
    if half == "lat":
        assert e["workloads"] == [CHAT] and e["moves"] == "tpot_p95_ms"
    cell = types.SimpleNamespace(bench_dir=BENCH)
    metric = spec.Metric(e["name"], e["unit"], "per_layer", e)
    assert callable(spec.load_reader(cell, metric).read)


@pytest.mark.parametrize("name", LISTED_NOW)
def test_an_entry_listed_now_reads_what_the_parent_records(name):
    mine = [m for m in BENCHMARK["per_layer"] if m["name"] == name]
    assert len(mine) == 1
    _held_to_the_benchmark(mine[0])
    # It finds a number in the parent's samples and scrapes.
    p0 = exposition(frames=1000, tokens=1500)
    p1 = exposition(frames=3000, tokens=5000)
    assert reader(name).read(ctx(steps=PARENT, prom0=p0, prom1=p1)) is not None
    # New entries go at the end of the list.
    tail = [m["name"] for m in BENCHMARK["per_layer"][-len(LISTED_NOW):]]
    assert name in tail


@pytest.mark.parametrize("entry", WAITING, ids=lambda e: e["name"])
def test_a_waiting_entry_fits_the_benchmark_and_is_in_no_list(entry):
    """Held to BENCHMARK.json's schema and to cells it has; in no
    `per_layer` list while the parent of a PR cannot give it a number —
    which the parent's samples and scrapes cannot."""
    _held_to_the_benchmark(entry)
    assert entry["name"] not in {m["name"] for m in BENCHMARK["per_layer"]}
    assert entry["name"] not in LISTED_NOW
    old = exposition(frames=1, tokens=1)
    assert reader(entry["name"]).read(ctx(
        steps=PARENT, trace_steps=PARENT, prom0=old, prom1=old,
        trace={"window_s": 5.0, "busy_s": 4.0})) is None
    # ... and PR 37's own give it one.
    p0, p1 = exposition(engine=1.0, server=1.0), exposition(engine=1.01,
                                                            server=1.01)
    assert reader(entry["name"]).read(ctx(
        steps=[SCAN, RAGGED], trace_steps=[SCAN, RAGGED], prom0=p0, prom1=p1,
        trace={"window_s": 5.0, "busy_s": 4.0})) is not None


def test_the_waiting_file_names_each_metric_once():
    names = [e["name"] for e in WAITING]
    assert sorted(names) == sorted(set(names)) and len(names) == 7
    assert {n.rsplit(".", 1)[0] for n in names} == {
        "dry_ms_per_step", "idle_late_launch_pct", "engine_cpu_ms_per_step",
        "server_cpu_ms_per_step", "engine_offcpu_ms_per_step"}
