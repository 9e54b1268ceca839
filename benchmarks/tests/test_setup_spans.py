"""The seven `start-up` readers (PR 67: they give `setup_s`, judged in every
cell, its layers) over a `ctx` of this program's shape and over one of its
PARENT's — compile events with `site / key / wall_ms / ts` alone, no
start-up series — on the CPU:
    python -m pytest benchmarks/tests/test_setup_spans.py -q

A number from each on both (lib/result.py fails a traced line that lacks a
listed metric, and a PR's parent runs under that PR's benchmark files), 0.0
where the docstring says. Nothing here gives a device number."""

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
ENTRIES = [m for m in BENCHMARK["per_layer"] if m["layer"] == "start-up"]
NAMES = ("ready_s", "ready_weights_s", "warm_compile_s",
         "warm_trace_lower_s", "warm_backend_s", "compile_cache_hit_pct",
         "setup_named_pct")
WINDOW = (1000.0, 1051.0)


def reader(name: str):
    cell = types.SimpleNamespace(bench_dir=BENCH)
    return spec.load_reader(cell, spec.Metric(name, "", "per_layer", {}))


def event(ts, wall_ms, split=None):
    ev = {"ts": ts, "seq": 1, "site": "ragged", "key": "('ragged', 64, 0, ())",
          "wall_ms": wall_ms}
    if split is not None:
        trace, lower, backend = split
        ev.update(t0=ts - wall_ms / 1e3, trace_ms=trace, lower_ms=lower,
                  backend_ms=backend, programs=1, cache="hit",
                  first_run_ms=wall_ms - trace - lower - backend)
    return ev


PROM = """\
# HELP ollamamq_ready_seconds Seconds from the kernel's process start
# TYPE ollamamq_ready_seconds gauge
ollamamq_ready_seconds 21.5
ollamamq_startup_seconds{phase="import"} 2.5
ollamamq_startup_seconds{phase="backend"} 6.0
ollamamq_startup_seconds{phase="weights"} 7.25
ollamamq_startup_seconds{phase="place"} 1.75
ollamamq_startup_seconds{phase="alloc"} 3.5
ollamamq_startup_seconds{phase="serve"} 0.5
ollamamq_compile_programs_total{cache="hit"} 9
ollamamq_compile_programs_total{cache="miss"} 1
ollamamq_compile_programs_total{cache="off"} 70
ollamamq_compile_total{site="ragged"} 3
"""
PARENT_PROM = 'ollamamq_compile_total{site="ragged"} 3\n'
SPLITS = ((700.0, 300.0, 400.0), (900.0, 100.0, 2000.0), (5.0, 5.0, 5.0))
WALLS = (2000.0, 4000.0, 500.0)
# two first calls of the warm-up, and one that ENDS inside the window
TIMES = (980.0, 990.0, 1001.0)


def ctx(parent: bool, set_up_s: float = 50.0, **kw):
    events = [event(ts, wall, None if parent else split)
              for ts, wall, split in zip(TIMES, WALLS, SPLITS)]
    base = dict(compile_events=events, window_epoch=WINDOW,
                set_up_s=set_up_s, prom0=None,
                prom1=PARENT_PROM if parent else PROM)
    return types.SimpleNamespace(**{**base, **kw})


THIS = {"ready_s": 21.5, "ready_weights_s": 9.0, "warm_compile_s": 6.0,
        "warm_trace_lower_s": 2.0, "warm_backend_s": 2.4,
        "compile_cache_hit_pct": 90.0,
        "setup_named_pct": 100.0 * (21.5 + 6.0) / 50.0}
PARENT = {"ready_s": 0.0, "ready_weights_s": 0.0, "warm_compile_s": 6.0,
          "warm_trace_lower_s": 0.0, "warm_backend_s": 0.0,
          "compile_cache_hit_pct": 0.0,
          "setup_named_pct": 100.0 * 6.0 / 50.0}


def test_the_seven_entries_are_the_last_and_list_every_cell():
    assert tuple(m["name"] for m in BENCHMARK["per_layer"][-7:]) == NAMES
    assert [m["name"] for m in ENTRIES] == list(NAMES)
    cells = [w["name"] for w in BENCHMARK["workloads"]]
    for m in ENTRIES:
        assert m["moves"] == "setup_s" and m["workloads"] == cells
        assert m["better"] == ("higher" if m["name"].endswith("_pct")
                               else "lower")
        assert m["source"] == ("program_counter" if m["name"]
                               == "compile_cache_hit_pct" else "program_span")
        assert m["unit"] == ("%" if m["name"].endswith("_pct") else "s")


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("shape", ["this", "parent"])
def test_a_number_from_each_reader_on_both_programs(name, shape):
    """The event that ends inside the window is in none of the three sums;
    the parent's shape reads 0.0 where the reader's docstring says so."""
    mod = reader(name)
    want = (THIS if shape == "this" else PARENT)[name]
    got = mod.read(ctx(parent=shape == "parent"))
    assert isinstance(got, float) and got == pytest.approx(want)
    if PARENT[name] == 0.0:
        assert "0.0 from a program older than PR 67" in " ".join(
            mod.__doc__.split()) or "0.0 where" in mod.__doc__


@pytest.mark.parametrize("name", NAMES)
def test_a_number_with_nothing_to_read(name):
    """No exposition, no events (a ledger that saw no first call): 0.0,
    never None and never an exception."""
    got = reader(name).read(ctx(parent=True, compile_events=[], prom1=None))
    assert got == 0.0


def test_the_hit_share_is_of_the_programs_the_cache_had_a_word_on():
    read = reader("compile_cache_hit_pct").read
    cold = PROM.replace('cache="hit"} 9', 'cache="hit"} 1') \
               .replace('cache="miss"} 1', 'cache="miss"} 9')
    assert read(ctx(False, prom1=cold)) == pytest.approx(10.0)
    only_off = "\n".join(ln for ln in PROM.splitlines()
                         if 'cache="hit"' not in ln and 'cache="miss"' not in ln)
    assert read(ctx(False, prom1=only_off)) == 0.0
