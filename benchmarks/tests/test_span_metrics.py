"""The per-layer metrics that read PR 24's spans and counters — the engine
thread's loop phases in the step samples, the `ingress` request phase, the
stream-lag histogram — on the CPU:
    python -m pytest benchmarks/tests/test_span_metrics.py -q

Their `per_layer` entries wait in `span_metric_entries.json`, beside this
file, and NOT in BENCHMARK.json: a reader that has nothing to read returns
None, and run.py then refuses the whole line (pinned below) — which is what
the driver's traced run of the PARENT commit, laid over with these files,
would meet. The `benchmark` PR that lets run.py leave such a metric out
appends the entries. Nothing here gives a device number."""

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

ENTRIES = spec.load_json(os.path.join(BENCH, "tests",
                                      "span_metric_entries.json"))
CHAT = "qwen2.5-7b-d14.chat"


def with_entries() -> dict:
    """BENCHMARK.json as the next `benchmark` PR leaves it: the entries
    appended at the end of `per_layer`, nothing else touched."""
    bj = spec.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    bj["per_layer"] = bj["per_layer"] + ENTRIES
    return bj


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


# ------------------------------------------------ the entries that wait
def test_the_waiting_entries_fit_the_benchmark_as_it_is():
    """Appended to BENCHMARK.json they name cells, layers and end-to-end
    metrics that are there, every metric of every cell finds its reader
    (a `.lat`/`.thr` pair the one file of its stem), and no other entry
    moves."""
    old = spec.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    new = with_entries()
    assert new["per_layer"][:len(old["per_layer"])] == old["per_layer"]
    assert {k: v for k, v in new.items() if k != "per_layer"} \
        == {k: v for k, v in old.items() if k != "per_layer"}
    names = [m["name"] for m in new["per_layer"]]
    assert len(names) == len(set(names))
    layers = {m["layer"] for m in old["per_layer"]}
    for e in ENTRIES:
        assert set(e) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert e["layer"] in layers, e["layer"]
        for w in e["workloads"]:
            assert any(m["name"] == e["moves"] and w in m["workloads"]
                       for m in old["end_to_end"]), (e["name"], w)
        path = os.path.join(BENCH, "layer_metrics",
                            e["name"].rsplit(".", 1)[0] + ".py")
        assert os.path.exists(path) or os.path.exists(os.path.join(
            BENCH, "layer_metrics", e["name"] + ".py")), e["name"]
    per_cell = {w["name"]: sum(w["name"] in e["workloads"] for e in ENTRIES)
                for w in old["workloads"]}
    assert per_cell == {CHAT: 4, "qwen2.5-7b-d14.batch": 2,
                        "qwen3-8b-tp4.chat48": 2}


def test_a_reader_with_nothing_to_read_fails_the_whole_line_today(tmp_path):
    """Why the entries wait: run.py lists a metric's unit before it reads
    it, and the last line must hold exactly the listed metrics — so over a
    program without the loop fields (the parent commit, in the driver's
    traced run) the new metrics do not drop out, the run fails. The edit
    that lifts this is run.py's (PERF.md §7)."""
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(with_entries()))
    cell = spec.load_cell(CHAT, str(path))
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
def test_rehearsal_with_the_entries_prints_the_four_new_chat_metrics(
        tmp_path):
    """A checkout that differs from this one only by BENCHMARK.json with
    the entries appended (everything else a link): `--rehearse-cpu --trace
    1` on the chat cell reads all four new metrics from the served
    program's samples and counters, and the last line validates."""
    for name in ("benchmarks", "ollamamq_tpu", "cpp"):
        os.symlink(os.path.join(ROOT, name), tmp_path / name)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(with_entries()))
    r = subprocess.run(
        [sys.executable, str(tmp_path / "benchmarks" / "run.py"),
         "--workload", CHAT, "--seed", "2147483999", "--seconds", "4",
         "--trace", "1", "--rehearse-cpu"], cwd=tmp_path,
        capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode == 0, r.stderr[-2000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    cell = spec.load_cell(CHAT, str(tmp_path / "BENCHMARK.json"))
    result.validate(line, {m.name: m.unit
                           for m in cell.metrics_of("per_layer")}, True)
    got = line["metrics"]
    for name in ("loop_ms_per_step.lat", "idle_explained_pct.lat",
                 "ingress_mean_ms", "stream_lag_mean_ms"):
        assert got[name]["value"] > 0.0, (name, got[name])
    assert line["attempted"] > 0 and line["failed"] == 0
