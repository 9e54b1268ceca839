"""CI wiring for scripts/check_metrics_docs.py: the registry's metric
surface and README.md's Observability table must not drift. Runs in
tier-1 (non-slow, no jax/engine needed by the script)."""

import importlib.util
import os

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SCRIPT = os.path.join(_REPO, "scripts", "check_metrics_docs.py")


def _load():
    spec = importlib.util.spec_from_file_location("check_metrics_docs",
                                                  _SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_readme_documents_every_registered_metric():
    mod = _load()
    assert mod.main(["check_metrics_docs.py"]) == 0


def test_checker_catches_missing_and_ghost_names(tmp_path):
    mod = _load()
    # Missing: a README without any metric names.
    bare = tmp_path / "README_bare.md"
    bare.write_text("# no metrics documented here\n")
    assert mod.main(["check_metrics_docs.py", str(bare)]) == 1
    # Ghost: documents a metric the registry never registered.
    with open(os.path.join(_REPO, "README.md"), encoding="utf-8") as f:
        full = f.read()
    ghost = tmp_path / "README_ghost.md"
    ghost.write_text(full + "\n| `ollamamq_definitely_not_real` | gauge |\n")
    assert mod.main(["check_metrics_docs.py", str(ghost)]) == 1


def test_checker_pins_journal_event_table(tmp_path):
    """Satellite: every decision-journal event kind the engine can record
    (telemetry/journal.py EVENTS) must appear in the README flight-
    recorder table (marker-scoped), and the table must not document
    kinds the journal no longer emits — the checker exits non-zero on
    any drift, and this test gates it in tier-1."""
    mod = _load()
    with open(os.path.join(_REPO, "README.md"), encoding="utf-8") as f:
        full = f.read()
    assert "| `preempt` |" in full, "journal table row shape changed"
    # A documented event row removed => missing-event failure.
    missing = tmp_path / "README_noevent.md"
    missing.write_text(full.replace("| `preempt` |", "| preempt-less |", 1))
    assert mod.main(["check_metrics_docs.py", str(missing)]) == 1
    # A ghost kind inside the markers => ghost-event failure.
    ghost = tmp_path / "README_ghostevent.md"
    ghost.write_text(full.replace(
        mod.JOURNAL_END,
        "| `notarealevent` | bogus |\n" + mod.JOURNAL_END, 1))
    assert mod.main(["check_metrics_docs.py", str(ghost)]) == 1
    # Markers stripped => every kind reads as undocumented.
    bare = tmp_path / "README_nojournalmarkers.md"
    bare.write_text(full.replace(mod.JOURNAL_BEGIN, "").replace(
        mod.JOURNAL_END, ""))
    assert mod.main(["check_metrics_docs.py", str(bare)]) == 1


def test_checker_pins_attribution_phase_table(tmp_path):
    """Satellite: every phase the attribution layer can emit must appear
    in the README phase table (marker-scoped), and the table must not
    document phases that no longer exist."""
    mod = _load()
    with open(os.path.join(_REPO, "README.md"), encoding="utf-8") as f:
        full = f.read()
    # A documented phase row removed => missing-phase failure.
    assert "| `queue` |" in full, "phase table row shape changed"
    missing = tmp_path / "README_nophase.md"
    missing.write_text(full.replace("| `queue` |", "| queue-less |", 1))
    assert mod.main(["check_metrics_docs.py", str(missing)]) == 1
    # A ghost phase inside the markers => ghost-phase failure.
    ghost = tmp_path / "README_ghostphase.md"
    ghost.write_text(full.replace(
        mod.PHASES_END, "| `notarealphase` | bogus |\n" + mod.PHASES_END, 1))
    assert mod.main(["check_metrics_docs.py", str(ghost)]) == 1
    # Markers stripped entirely => every phase reads as undocumented.
    bare = tmp_path / "README_nomarkers.md"
    bare.write_text(full.replace(mod.PHASES_BEGIN, "").replace(
        mod.PHASES_END, ""))
    assert mod.main(["check_metrics_docs.py", str(bare)]) == 1


def test_checker_pins_stepprof_phase_table(tmp_path):
    """Satellite (PR 20): the step profiler's closed dispatch-phase
    vocabulary (telemetry/stepprof.py PHASES — the `phase` label of
    `ollamamq_step_phase_ms`) is pinned to the README engine-
    performance-plane table, same marker pattern as the others."""
    mod = _load()
    from ollamamq_tpu.telemetry.stepprof import PHASES

    with open(os.path.join(_REPO, "README.md"), encoding="utf-8") as f:
        full = f.read()
    assert "| `host_prep` |" in full, "stepprof table row shape changed"
    assert set(PHASES) == {"host_prep", "dispatch", "collect", "detok"}
    # A documented phase row removed => missing-phase failure.
    missing = tmp_path / "README_nostepphase.md"
    missing.write_text(full.replace("| `host_prep` |", "| prep-less |", 1))
    assert mod.main(["check_metrics_docs.py", str(missing)]) == 1
    # A ghost phase inside the markers => ghost-phase failure.
    ghost = tmp_path / "README_ghoststepphase.md"
    ghost.write_text(full.replace(
        mod.STEPPROF_END,
        "| `notastepphase` | bogus |\n" + mod.STEPPROF_END, 1))
    assert mod.main(["check_metrics_docs.py", str(ghost)]) == 1
    # Markers stripped entirely => every phase reads as undocumented.
    bare = tmp_path / "README_nostepmarkers.md"
    bare.write_text(full.replace(mod.STEPPROF_BEGIN, "").replace(
        mod.STEPPROF_END, ""))
    assert mod.main(["check_metrics_docs.py", str(bare)]) == 1


import pytest  # noqa: E402


@pytest.mark.parametrize("begin,end,row,ghost", [
    ("LOOP_PHASES_BEGIN", "LOOP_PHASES_END", "| `wait` | the condvar",
     "| `notaloopphase` | bogus |\n"),
    ("SPANS_BEGIN", "SPANS_END", "`mq.loop.wait`",
     "| `mq.loop.bogus` | host span | bogus |\n"),
    # PR 52: the child spans at a phase's seams and the capture's clock
    # span are of the same closed vocabulary.
    ("SPANS_BEGIN", "SPANS_END", "`mq.dispatch.note` |",
     "| `mq.dispatch.bogus` | host span | bogus |\n"),
    ("SPANS_BEGIN", "SPANS_END", "| `mq.clock` |",
     "| `mq.clock.bogus` | host span | bogus |\n"),
    # PR 67: what the process does between its start and ready
    # (stepprof.START_PHASES), the first and the last of them.
    ("START_PHASES_BEGIN", "START_PHASES_END", "| `import` | the kernel's",
     "| `warm` | bogus |\n"),
    ("START_PHASES_BEGIN", "START_PHASES_END", "| `serve` | every runtime",
     "| `compile` | bogus |\n"),
])
def test_checker_pins_loop_phase_and_span_tables(tmp_path, begin, end, row,
                                                 ghost):
    """PR 24: what the engine thread does between steps
    (stepprof.LOOP_PHASES) and the `mq.*` span names emitted during a
    device capture (stepprof.SPAN_NAMES) are pinned to their README
    tables like PHASES: a missing row, a ghost row and stripped markers
    each fail the gate."""
    mod = _load()
    from ollamamq_tpu.telemetry.stepprof import (CHILD_SPANS, CLOCK_SPAN,
                                                 LOOP_PHASES, PHASE_SPANS,
                                                 PHASES, SPAN_NAMES,
                                                 START_PHASES)

    assert set(LOOP_PHASES) == {"admit", "other", "wait"}
    assert START_PHASES == ("import", "backend", "weights", "place", "alloc",
                            "serve")
    assert set(PHASE_SPANS) == ({"mq." + p for p in PHASES}
                                | {"mq.loop." + p for p in LOOP_PHASES})
    # A child span hangs under a phase span, and the names are unique.
    assert set(CHILD_SPANS) <= set(PHASE_SPANS)
    assert set(SPAN_NAMES) == (
        set(PHASE_SPANS) | {CLOCK_SPAN}
        | {p + "." + c for p, cs in CHILD_SPANS.items() for c in cs})
    assert len(SPAN_NAMES) == len(set(SPAN_NAMES))
    begin, end = getattr(mod, begin), getattr(mod, end)
    with open(os.path.join(_REPO, "README.md"), encoding="utf-8") as f:
        full = f.read()
    assert full.count(row) == 1, "table row shape changed"
    missing = tmp_path / "README_missing.md"
    missing.write_text(full.replace(row, "gone", 1))
    assert mod.main(["check_metrics_docs.py", str(missing)]) == 1
    ghosted = tmp_path / "README_ghost.md"
    ghosted.write_text(full.replace(end, ghost + end, 1))
    assert mod.main(["check_metrics_docs.py", str(ghosted)]) == 1
    bare = tmp_path / "README_bare.md"
    bare.write_text(full.replace(begin, "").replace(end, ""))
    assert mod.main(["check_metrics_docs.py", str(bare)]) == 1


_SOURCE_EXT = (".py", ".sh", ".md", ".yml", ".toml", ".cpp", ".h")
_ROOTS = ("", "ollamamq_tpu", "benchmarks", "tests")


def _cited_source_paths(text):
    """The source files a document cites in backticks: `dir/file.py`,
    `file.py:120`, `tests/test_x.py::test_y`. Not globs, placeholders,
    absolute paths or what a run leaves behind (no source extension)."""
    import re

    for token in re.findall(r"`([^`\n]+)`", text):
        for word in token.split():
            path = re.split(r"::|:\d", word.strip(".,;()[]"))[0]
            if (path.endswith(_SOURCE_EXT) and not path.startswith(("/", "~"))
                    and not re.search(r"[*<>{}$]|\.\.\.", path)):
                yield path


@pytest.mark.parametrize("doc", ["README.md", "PARITY.md",
                                 ".claude/skills/verify/SKILL.md"])
def test_documents_cite_only_files_the_tree_has(doc):
    """A new owner is sent to the files the README, the parity table and
    the verify recipe name: each must exist — from the repo's root, or from the package,
    `benchmarks/` or `tests/` where the document names it from there; a
    bare file name must be some file's name. (`parallel/pipeline.py` and
    `bench.py` outlived their files in these documents.)"""
    with open(os.path.join(_REPO, doc), encoding="utf-8") as f:
        cited = sorted(set(_cited_source_paths(f.read())))
    assert len(cited) >= 10, cited
    names = set(os.listdir(_REPO))  # not what a run leaves: _proof/, ...
    for top in ("ollamamq_tpu", "scripts", "tests", "benchmarks", "cpp"):
        for _, _, files in os.walk(os.path.join(_REPO, top)):
            names.update(files)
    missing = [p for p in cited
               if not (any(os.path.exists(os.path.join(_REPO, root, p))
                           for root in _ROOTS)
                       or ("/" not in p and p in names))]
    assert missing == []
