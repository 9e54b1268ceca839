"""The per-layer metrics that read PR 24's spans and counters — the engine
thread's loop phases in the step samples, the `ingress` request phase, the
stream-lag histogram — on the CPU:
    python -m pytest benchmarks/tests/test_span_metrics.py -q

Their six `per_layer` entries are in BENCHMARK.json since PR 31 (they
waited in a file beside this one while the PARENT commit of a PR lacked the
loop fields and histograms: a reader that has nothing to read returns None,
and run.py then refuses the whole line — pinned below, and still the rule).
Nothing here gives a device number."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import types

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from benchmarks import run  # noqa: E402
from benchmarks.lib import result, spec  # noqa: E402
from benchmarks.lib import trace as tr  # noqa: E402

BENCHMARK = spec.load_json(os.path.join(ROOT, "BENCHMARK.json"))
SPAN_METRICS = ("loop_ms_per_step.lat", "loop_ms_per_step.thr",
                "idle_explained_pct.lat", "idle_explained_pct.thr",
                "ingress_mean_ms", "stream_lag_mean_ms")
ENTRIES = [m for m in BENCHMARK["per_layer"] if m["name"] in SPAN_METRICS]
CHAT = "qwen2.5-7b-d14.chat"


def reader(name: str):
    cell = types.SimpleNamespace(bench_dir=BENCH)
    return spec.load_reader(cell, spec.Metric(name, "", "per_layer", {}))


def ctx(**kw):
    base = dict(steps=None, trace_steps=None, trace=None, prom0=None,
                prom1=None)
    return types.SimpleNamespace(**{**base, **kw})


SCAN = {"mode": "decode", "k_cap": 8, "tokens": 512, "total_ms": 400.0,
        "host_prep_ms": 1.0, "dispatch_ms": 2.0, "collect_ms": 317.0,
        "detok_ms": 80.0, "loop_admit_ms": 3.0, "loop_other_ms": 5.0,
        "loop_wait_ms": 50.0}
RAGGED = {"mode": "ragged", "k_cap": 0, "tokens": 200, "total_ms": 90.0,
          "host_prep_ms": 6.0, "dispatch_ms": 1.0, "collect_ms": 80.0,
          "detok_ms": 3.0, "loop_admit_ms": 1.0, "loop_other_ms": 0.0,
          "loop_wait_ms": 0.0}
OLD = {k: v for k, v in RAGGED.items() if not k.startswith("loop_")}


# ------------------------------------------------------------- the readers
@pytest.mark.parametrize("half", ["lat", "thr"])
def test_loop_ms_per_step_is_admit_plus_other_over_passes(half):
    read = reader("loop_ms_per_step." + half).read
    # 3 + 5 + 1 + 0 ms over 8 + 1 passes; the idle wait is not work.
    assert read(ctx(steps=[SCAN, RAGGED])) == pytest.approx(9.0 / 9)
    assert read(ctx(steps=[])) is None and read(ctx(steps=None)) is None
    # Samples of a program older than PR 24: unknown, never zero.
    assert read(ctx(steps=[OLD])) is None
    assert read(ctx(steps=[SCAN, OLD])) is None


@pytest.mark.parametrize("half", ["lat", "thr"])
def test_idle_explained_pct_is_host_spans_over_idle_seconds(half):
    read = reader("idle_explained_pct." + half).read
    trace = {"window_s": 5.0, "busy_s": 4.0}
    # host_prep + dispatch + detok + the three loop phases:
    # (83 + 58) + (10 + 1) = 152 ms of 1.0 s idle.
    assert read(ctx(trace=trace, trace_steps=[SCAN, RAGGED])) \
        == pytest.approx(15.2)
    # It is a metric of the measurement and may pass 100.
    assert read(ctx(trace={"window_s": 5.0, "busy_s": 4.9},
                    trace_steps=[SCAN, RAGGED])) == pytest.approx(152.0)
    assert read(ctx(trace=None, trace_steps=[SCAN])) is None
    assert read(ctx(trace=trace, trace_steps=[])) is None
    assert read(ctx(trace=trace, trace_steps=[OLD])) is None


def exposition(ingress, lag):
    text = ('ollamamq_request_phase_ms_sum{model="m",phase="queue"} 900\n'
            'ollamamq_request_phase_ms_count{model="m",phase="queue"} 9\n')
    if ingress:
        text += ('ollamamq_request_phase_ms_sum{model="m",phase="ingress"} '
                 f'{ingress[0]}\nollamamq_request_phase_ms_count'
                 f'{{model="m",phase="ingress"}} {ingress[1]}\n')
    if lag:
        text += (f"ollamamq_stream_lag_ms_sum {lag[0]}\n"
                 f"ollamamq_stream_lag_ms_count {lag[1]}\n")
    return text


def test_ingress_and_stream_lag_are_histogram_deltas():
    p0 = exposition((10.0, 10), (100.0, 1000))
    p1 = exposition((25.0, 20), (400.0, 2000))
    assert reader("ingress_mean_ms").read(ctx(prom0=p0, prom1=p1)) \
        == pytest.approx(1.5)
    assert reader("stream_lag_mean_ms").read(ctx(prom0=p0, prom1=p1)) \
        == pytest.approx(0.3)
    # A program that exports neither (older than PR 24), or no grab:
    old = exposition(None, None)
    for name in ("ingress_mean_ms", "stream_lag_mean_ms"):
        assert reader(name).read(ctx(prom0=old, prom1=old)) is None
        assert reader(name).read(ctx()) is None
        assert reader(name).read(ctx(prom0=p0, prom1=p0)) is None


# ------------------------------------------ the entries, in BENCHMARK.json
def test_the_waiting_entries_fit_the_benchmark_as_it_is():
    """All six are there, once; each names cells, a layer and an end-to-end
    metric that are there, every cell it names reports that metric, and it
    finds its reader (a `.lat`/`.thr` pair the one file of its stem). Each
    entry is held to the cells it names and to nothing about the others."""
    assert sorted(e["name"] for e in ENTRIES) == sorted(SPAN_METRICS)
    cells = {w["name"] for w in BENCHMARK["workloads"]}
    layers = {m["layer"] for m in BENCHMARK["per_layer"]
              if m["name"] not in SPAN_METRICS}
    for e in ENTRIES:
        assert set(e) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert e["layer"] in layers, e["layer"]
        assert e["workloads"] and set(e["workloads"]) <= cells, e["name"]
        for w in e["workloads"]:
            assert any(m["name"] == e["moves"] and w in m["workloads"]
                       for m in BENCHMARK["end_to_end"]), (e["name"], w)
            cell = spec.load_cell(w)
            mine = [m for m in cell.metrics_of("per_layer")
                    if m.name == e["name"]]
            assert len(mine) == 1
            assert callable(spec.load_reader(cell, mine[0]).read)
    # a split pair covers every cell that reports the metric its half moves
    for stem in ("loop_ms_per_step", "idle_explained_pct"):
        thr = next(e for e in ENTRIES if e["name"] == stem + ".thr")
        moved = next(m for m in BENCHMARK["end_to_end"]
                     if m["name"] == thr["moves"])
        assert sorted(thr["workloads"]) == sorted(moved["workloads"])


def test_a_reader_with_nothing_to_read_fails_the_whole_line_today():
    """The rule, and no longer why entries wait: run.py lists a metric's
    unit before it reads it, and the last line must hold exactly the listed
    metrics — so over a program without the loop fields the metrics do not
    drop out, the run fails. Whether a traced line may omit a listed metric
    is the contract's to say."""
    cell = spec.load_cell(CHAT)
    mine = [m for m in cell.metrics_of("per_layer")
            if m.name.startswith("loop_ms_per_step")]
    cell = spec.Cell(cell.name, cell.chips, cell.config, cell.config_file,
                     cell.traffic, tuple(mine), cell.run_seconds)
    values, units = run.read_metrics(cell, "per_layer", ctx(steps=[OLD]))
    assert values == {} and list(units) == ["loop_ms_per_step.lat"]
    dev = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
           "memory_peak_bytes": 1, "busy_s": 1.0, "window_s": 2.0}
    with pytest.raises(result.MalformedResult):
        result.validate(result.build(True, 1, 0, values, units, dev),
                        units, True)


# ----------------------------------- idle gaps against the mq.* spans, today
LOOP_ONCE = ["engine.py:4498 _loop_once", 0, 10_000_000]


def test_an_mq_span_wins_the_gap_it_covers_alone():
    """`host_frame` charges a gap to the event that overlaps it most, the
    innermost of equals: an mq.detok span nested in _loop_once covers the
    whole gap as its caller does, and is shorter."""
    driver = [LOOP_ONCE, ["mq.detok", 2_000_000, 3_000_000]]
    assert tr.host_frame(driver, 2_500_000, 1_000_000) == "mq.detok"


def test_a_gap_across_three_mq_spans_still_goes_to_the_enclosing_frame():
    """The limitation the next `benchmark` issue lifts: a gap between two
    device programs runs from mq.detok through mq.loop.other into the next
    mq.host_prep; no single span overlaps it as much as the enclosing
    function, which keeps it. Charging gaps to spans BY OVERLAP is an edit
    to lib/trace.py."""
    driver = [LOOP_ONCE,
              ["mq.detok", 1_000_000, 2_000_000],
              ["mq.loop.other", 3_000_000, 1_000_000],
              ["mq.host_prep", 4_000_000, 3_000_000]]
    assert tr.host_frame(driver, 1_500_000, 5_000_000) == LOOP_ONCE[0]
    by_overlap = {name: min(s + d, 6_500_000) - max(s, 1_500_000)
                  for name, s, d in driver[1:]}
    assert by_overlap == {"mq.detok": 1_500_000, "mq.loop.other": 1_000_000,
                          "mq.host_prep": 2_500_000}   # what it should say


# ------------------------------------------------------------- end to end
def test_rehearsal_with_the_entries_prints_the_four_new_chat_metrics():
    """`--rehearse-cpu --trace 1` on the chat cell reads all four from the
    served program's samples and counters, and the last line validates."""
    r = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"),
         "--workload", CHAT, "--seed", "2147483999", "--seconds", "4",
         "--trace", "1", "--rehearse-cpu"], cwd=ROOT,
        capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode == 0, r.stderr[-2000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    cell = spec.load_cell(CHAT)
    result.validate(line, {m.name: m.unit
                           for m in cell.metrics_of("per_layer")}, True)
    got = line["metrics"]
    for name in ("loop_ms_per_step.lat", "idle_explained_pct.lat",
                 "ingress_mean_ms", "stream_lag_mean_ms"):
        assert got[name]["value"] > 0.0, (name, got[name])
    assert line["attempted"] > 0 and line["failed"] == 0
