"""The three per-layer stems that read a capture's idle gaps by the phase of
the program each was charged to (PR 52) — `idle_named_pct`,
`idle_launch_ms_per_step`, `idle_settle_ms_per_step` — on the CPU:
    python -m pytest benchmarks/tests/test_idle_gaps.py -q

They read `trace_reduced.json`'s `gaps` rows, which every traced run of every
program holds, so the parent of the PR that lists them gives each a number:
under the profiler's Python tracer (the program's default before PR 52) the
rows name Python frames and the stems read 0. Nothing here gives a device
number."""

from __future__ import annotations

import os
import sys
import types

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from benchmarks.lib import spec, trace as tr  # noqa: E402

BENCHMARK = spec.load_json(os.path.join(ROOT, "BENCHMARK.json"))
STEMS = ("idle_named_pct", "idle_launch_ms_per_step",
         "idle_settle_ms_per_step")
HALVES = ("lat", "thr")
MS = 1_000_000  # ns


def reader(name: str):
    cell = types.SimpleNamespace(bench_dir=BENCH)
    return spec.load_reader(cell, spec.Metric(name, "", "per_layer", {}))


def ctx(**kw):
    base = dict(steps=None, trace_steps=None, trace=None)
    return types.SimpleNamespace(**{**base, **kw})


def trace_of(rows: list) -> dict:
    """A reduced trace with these `[dur_ms, host frame]` gaps."""
    gaps = [[i * 100 * MS, int(ms * MS), "fusion.1", frame]
            for i, (ms, frame) in enumerate(rows)]
    return {"window_s": 5.0, "busy_s": 4.0, "gaps": gaps,
            "gap_total_s": sum(g[1] for g in gaps) / 1e9}


# One fused scan of k = 8 and one ragged step: nine passes.
SCAN = {"mode": "decode", "k_cap": 8, "tokens": 512}
RAGGED = {"mode": "ragged", "k_cap": 0, "tokens": 200}
# What the parent's capture (Python tracer on) charges its gaps to ...
PARENT = trace_of([[600.0, "engine.py:4853__loop_once"],
                   [300.0, "engine.py:2559_step_ragged_launch"]])
# ... and the change's: 4 + 2 ms of launch, 3 of settle, 0.5 of the loop,
# 0.5 that nothing overlaps — 10 ms of idle, 9.5 with a name.
CHANGE = trace_of([[4.0, "mq.host_prep.pack"],
                   [2.0, "PjitFunction(mq_ragged_step)"],
                   [3.0, "mq.detok"],
                   [0.5, "mq.loop.other"],
                   [0.5, "no host function overlaps it"]])


@pytest.mark.parametrize("half", HALVES)
@pytest.mark.parametrize("stem", STEMS)
def test_the_parents_frames_read_zero_not_none(stem, half):
    read = reader(f"{stem}.{half}").read
    assert read(ctx(trace=PARENT, trace_steps=[SCAN, RAGGED])) == 0.0


@pytest.mark.parametrize("half", HALVES)
@pytest.mark.parametrize("stem,want", [
    ("idle_named_pct", 95.0),                  # 9.5 of 10 ms
    ("idle_launch_ms_per_step", 6.0 / 9),      # 4 + 2 ms over 8 + 1 passes
    ("idle_settle_ms_per_step", 3.0 / 9)])
def test_the_changes_frames_read_the_hand_computed_values(stem, want, half):
    read = reader(f"{stem}.{half}").read
    assert read(ctx(trace=CHANGE, trace_steps=[SCAN, RAGGED])) \
        == pytest.approx(want)


def test_a_fused_scan_of_k_8_counts_eight_passes():
    read = reader("idle_launch_ms_per_step.thr").read
    assert read(ctx(trace=CHANGE, trace_steps=[SCAN])) \
        == pytest.approx(6.0 / 8)
    assert read(ctx(trace=CHANGE, trace_steps=[RAGGED])) \
        == pytest.approx(6.0)


@pytest.mark.parametrize("frame,launch,settle", [
    ("mq.host_prep", 1, 0), ("mq.host_prep.admit", 1, 0),
    ("mq.dispatch", 1, 0), ("DevicePut", 1, 0),
    ("PjitFunction(mq_decode_scan)", 1, 0),
    ("mq.collect", 0, 1), ("mq.detok.emit", 0, 1),
    ("np.asarray(jax.Array)", 0, 1),
    ("mq.loop.admit", 0, 0), ("mq.loop.wait", 0, 0), ("mq.clock", 0, 0)])
def test_every_name_of_the_programs_is_named_and_goes_to_one_side(
        frame, launch, settle):
    t = trace_of([[2.0, frame]])
    c = ctx(trace=t, trace_steps=[RAGGED])
    assert reader("idle_named_pct.thr").read(c) == pytest.approx(100.0)
    assert reader("idle_launch_ms_per_step.thr").read(c) \
        == pytest.approx(2.0 * launch)
    assert reader("idle_settle_ms_per_step.thr").read(c) \
        == pytest.approx(2.0 * settle)


@pytest.mark.parametrize("frame", [
    "engine.py:4853__loop_once", "no host function overlaps it", "",
    "threading.py:359_wait", "PjRtCApiLoadedExecutable::Execute"])
def test_a_frame_that_is_not_the_programs_has_no_name(frame):
    c = ctx(trace=trace_of([[2.0, frame]]), trace_steps=[RAGGED])
    for stem in STEMS:
        assert reader(stem + ".thr").read(c) == 0.0


@pytest.mark.parametrize("half", HALVES)
@pytest.mark.parametrize("stem", STEMS)
def test_none_only_without_a_trace_or_its_samples(stem, half):
    read = reader(f"{stem}.{half}").read
    assert read(ctx()) is None
    assert read(ctx(trace=None, trace_steps=[SCAN])) is None
    # A capture in which the chip never idled: no idle second is without a
    # name, and a pass waited 0 ms.
    want = 100.0 if stem == "idle_named_pct" else 0.0
    assert read(ctx(trace=trace_of([]), trace_steps=[SCAN])) == want


def test_the_readers_read_what_reduce_writes():
    """`gaps` rows as lib/trace.py builds them from planes: the frame is the
    driver line's event that overlaps most of the gap, the innermost on a
    tie — so a child span wins its parent."""
    ops = [["fusion.1", 0, 10 * MS], ["fusion.1", 14 * MS, 10 * MS],
           ["fusion.1", 26 * MS, 4 * MS]]
    driver = [["mq.host_prep", 9 * MS, 6 * MS],
              ["mq.host_prep.pack", 10 * MS, 4 * MS],
              ["mq.collect", 20 * MS, 9 * MS],
              ["np.asarray(jax.Array)", 24 * MS, 2 * MS]]
    planes = [{"name": "/device:TPU:0",
               "lines": [{"name": "XLA Ops", "events": ops}]},
              {"name": tr.DRIVER_PLANE,
               "lines": [{"name": "python", "events": driver}]}]
    t = tr.reduce(planes, chips=1)
    assert sorted(g[3] for g in t["gaps"]) == ["mq.host_prep.pack",
                                               "np.asarray(jax.Array)"]
    c = ctx(trace=t, trace_steps=[RAGGED])
    assert reader("idle_named_pct.thr").read(c) == pytest.approx(100.0)
    assert reader("idle_launch_ms_per_step.thr").read(c) == pytest.approx(4.0)
    assert reader("idle_settle_ms_per_step.thr").read(c) == pytest.approx(2.0)


# --------------------------------------------------------------- the entries
GAP_CELLS = {"lat": ["qwen2.5-7b-d14.chat"],
             "thr": ["qwen2.5-7b-d14.batch", "qwen3-8b-tp4.chat48",
                     "olmoe-1b-7b-d10.batch", "lfm2-8b-a1b-d18.batch",
                     "olmo-hybrid-7b-d16.batch",
                     "openpangu-ultra-moe-ep16-d5.reason"]}
MOVES = {"lat": "tpot_p95_ms", "thr": "output_tok_s"}
SHAPE = {"idle_named_pct": ("%", "higher", "device"),
         "idle_launch_ms_per_step": ("ms", "lower", "step dispatch (host)"),
         "idle_settle_ms_per_step": ("ms", "lower", "step dispatch (host)")}


@pytest.mark.parametrize("half", HALVES)
@pytest.mark.parametrize("stem", STEMS)
def test_an_entry_is_listed_once_on_the_cells_with_idle_to_explain(stem, half):
    (e,) = [m for m in BENCHMARK["per_layer"]
            if m["name"] == f"{stem}.{half}"]
    unit, better, layer = SHAPE[stem]
    assert e == {"name": f"{stem}.{half}", "unit": unit, "better": better,
                 "source": "device_trace", "layer": layer,
                 "moves": MOVES[half], "workloads": GAP_CELLS[half]}
    moved = next(m for m in BENCHMARK["end_to_end"] if m["name"] == e["moves"])
    assert set(e["workloads"]) <= set(moved["workloads"])
    # The three long-context cells idle for a millisecond in all: not listed.
    assert not any(c.endswith(".longctx") for c in e["workloads"])
    assert layer in {m["layer"] for m in BENCHMARK["per_layer"][:45]}
