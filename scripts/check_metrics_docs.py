#!/usr/bin/env python3
"""Doc/telemetry consistency gate, two surfaces:

  1. metrics — every metric the registry exports must be documented in
     README.md's Observability table, and every documented ollamamq_*
     name must still exist in the registry (no ghost docs);
  2. phases — every latency-attribution phase the engine can emit
     (telemetry/attribution.py PHASES) must appear in the README phase
     table (between the `<!-- phases:begin -->` / `<!-- phases:end -->`
     markers), and the table must not document phases that no longer
     exist;
  3. shed reasons — the closed `ollamamq_shed_total{reason}` label
     vocabulary (telemetry/schema.py SHED_REASONS) must match the README
     shed-reason table (between the `<!-- shed-reasons:begin -->` /
     `<!-- shed-reasons:end -->` markers) exactly;
  4. journal events — the decision-journal event vocabulary
     (telemetry/journal.py EVENTS) must match the README "Flight
     recorder" table (between the `<!-- journal-events:begin -->` /
     `<!-- journal-events:end -->` markers) exactly: an event kind the
     engine can record but the table doesn't document is a drift
     failure, and so is a documented kind the journal no longer emits;
  5. router spans — the fleet router's closed trace-span vocabulary
     (telemetry/tracing.py ROUTER_EVENTS: the event names the router
     drops into request traces, stitched fleet-wide at
     GET /debug/trace/{rid}) must match the README router-span table
     (between the `<!-- router-spans:begin -->` /
     `<!-- router-spans:end -->` markers) exactly — same pattern as
     phases;
  6. stepprof phases — the step profiler's closed dispatch-phase
     vocabulary (telemetry/stepprof.py PHASES: the `phase` label values
     of `ollamamq_step_phase_ms`) must match the README "Engine
     performance plane" phase table (between the
     `<!-- stepprof-phases:begin -->` / `<!-- stepprof-phases:end -->`
     markers) exactly;
  7. stepprof loop phases and spans — the closed vocabulary of what the
     engine thread does between steps (telemetry/stepprof.py
     LOOP_PHASES: `loop_<name>_ms` on a sample, `phase="loop_<name>"`
     on the histogram) must match the README loop-phase table (between
     the `<!-- stepprof-loop-phases:begin -->` / `...:end -->` markers),
     and the `mq.*` span names the profiler emits during a device
     capture (SPAN_NAMES) must match the `mq.`-prefixed names of the
     README span table (between `<!-- stepprof-spans:begin -->` /
     `<!-- stepprof-spans:end -->`) exactly;
  8. start-up phases — the closed vocabulary of what the process does
     between its start and ready (telemetry/stepprof.py START_PHASES:
     the `phase` label values of `ollamamq_startup_seconds`) must match
     the README start-up phase table (between the
     `<!-- stepprof-start-phases:begin -->` / `...:end -->` markers)
     exactly.

Imports ONLY ollamamq_tpu.telemetry.schema/.attribution/.journal/
.tracing — the declaration sites — so the check runs without jax, a
device, or an engine. Wired into tier-1 via tests/test_metrics_docs.py.

Usage: python scripts/check_metrics_docs.py [README.md]
Exit 0 = consistent; 1 = drift (names printed); 2 = usage error.
"""

from __future__ import annotations

import os
import re
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PHASES_BEGIN = "<!-- phases:begin -->"
PHASES_END = "<!-- phases:end -->"
SHED_BEGIN = "<!-- shed-reasons:begin -->"
SHED_END = "<!-- shed-reasons:end -->"
JOURNAL_BEGIN = "<!-- journal-events:begin -->"
JOURNAL_END = "<!-- journal-events:end -->"
ROUTER_SPANS_BEGIN = "<!-- router-spans:begin -->"
ROUTER_SPANS_END = "<!-- router-spans:end -->"
STEPPROF_BEGIN = "<!-- stepprof-phases:begin -->"
STEPPROF_END = "<!-- stepprof-phases:end -->"
LOOP_PHASES_BEGIN = "<!-- stepprof-loop-phases:begin -->"
LOOP_PHASES_END = "<!-- stepprof-loop-phases:end -->"
START_PHASES_BEGIN = "<!-- stepprof-start-phases:begin -->"
START_PHASES_END = "<!-- stepprof-start-phases:end -->"
SPANS_BEGIN = "<!-- stepprof-spans:begin -->"
SPANS_END = "<!-- stepprof-spans:end -->"


def _documented(readme_text: str, begin: str, end: str,
                pattern: str = r"`([a-z_]+)`") -> set:
    """Backticked names matching `pattern` inside a marked region."""
    start = readme_text.find(begin)
    stop = readme_text.find(end)
    if start == -1 or stop == -1 or stop < start:
        return set()
    return set(re.findall(pattern, readme_text[start:stop]))


def documented_metric_names(readme_text: str) -> set:
    """ollamamq_* names that appear in backticks anywhere in the README
    (the Observability table is the intended home; being generous about
    WHERE keeps the check about coverage, not markdown layout)."""
    return set(re.findall(r"`(ollamamq_[a-z0-9_]+)`", readme_text))


def registered_metric_names() -> set:
    sys.path.insert(0, _REPO)
    from ollamamq_tpu.telemetry import schema  # noqa: F401  (declares all)
    from ollamamq_tpu.telemetry.metrics import REGISTRY

    return set(REGISTRY.names())


def documented_phase_names(readme_text: str) -> set:
    """Backticked names inside the marked phase-table region. Markers
    (not layout) scope the search, so `queue`-the-word elsewhere in the
    README can't satisfy the check by accident."""
    return _documented(readme_text, PHASES_BEGIN, PHASES_END)


def registered_phase_names() -> set:
    sys.path.insert(0, _REPO)
    from ollamamq_tpu.telemetry.attribution import PHASES

    return set(PHASES)


def documented_shed_reasons(readme_text: str) -> set:
    """Backticked names inside the marked shed-reason region."""
    return _documented(readme_text, SHED_BEGIN, SHED_END)


def registered_shed_reasons() -> set:
    sys.path.insert(0, _REPO)
    from ollamamq_tpu.telemetry.schema import SHED_REASONS

    return set(SHED_REASONS)


def documented_journal_events(readme_text: str) -> set:
    """Backticked names inside the marked journal-event region."""
    return _documented(readme_text, JOURNAL_BEGIN, JOURNAL_END)


def registered_journal_events() -> set:
    sys.path.insert(0, _REPO)
    from ollamamq_tpu.telemetry.journal import EVENTS

    return set(EVENTS)


def documented_router_spans(readme_text: str) -> set:
    """Backticked names inside the marked router-span region."""
    return _documented(readme_text, ROUTER_SPANS_BEGIN, ROUTER_SPANS_END)


def registered_router_spans() -> set:
    sys.path.insert(0, _REPO)
    from ollamamq_tpu.telemetry.tracing import ROUTER_EVENTS

    return set(ROUTER_EVENTS)


def documented_stepprof_phases(readme_text: str) -> set:
    """Backticked names inside the marked stepprof-phase region."""
    return _documented(readme_text, STEPPROF_BEGIN, STEPPROF_END)


def registered_stepprof_phases() -> set:
    sys.path.insert(0, _REPO)
    from ollamamq_tpu.telemetry.stepprof import PHASES

    return set(PHASES)


def documented_loop_phases(readme_text: str) -> set:
    """First-column names only: the meanings quote function names."""
    return _documented(readme_text, LOOP_PHASES_BEGIN, LOOP_PHASES_END,
                       r"(?m)^\| `([a-z_]+)` \|")


def registered_loop_phases() -> set:
    sys.path.insert(0, _REPO)
    from ollamamq_tpu.telemetry.stepprof import LOOP_PHASES

    return set(LOOP_PHASES)


def documented_start_phases(readme_text: str) -> set:
    """First-column names only: the meanings quote function names."""
    return _documented(readme_text, START_PHASES_BEGIN, START_PHASES_END,
                       r"(?m)^\| `([a-z_]+)` \|")


def registered_start_phases() -> set:
    sys.path.insert(0, _REPO)
    from ollamamq_tpu.telemetry.stepprof import START_PHASES

    return set(START_PHASES)


def documented_span_names(readme_text: str) -> set:
    """The `mq.`-prefixed names of the span table (it also lists jit
    function and scope names, which tests/test_trace_spans.py pins
    against the lowered programs — they need jax)."""
    return _documented(readme_text, SPANS_BEGIN, SPANS_END,
                       r"`(mq\.[a-z_.]+)`")


def registered_span_names() -> set:
    sys.path.insert(0, _REPO)
    from ollamamq_tpu.telemetry.stepprof import SPAN_NAMES

    return set(SPAN_NAMES)


def _diff(readme: str, what: str, registered: set, documented: set,
          missing_msg: str, ghost_msg: str) -> int:
    rc = 0
    missing = sorted(registered - documented)
    ghosts = sorted(documented - registered)
    if missing:
        rc = 1
        print(f"{readme}: {len(missing)} {missing_msg}:", file=sys.stderr)
        for name in missing:
            print(f"  - {name}", file=sys.stderr)
    if ghosts:
        rc = 1
        print(f"{readme}: {len(ghosts)} {ghost_msg}:", file=sys.stderr)
        for name in ghosts:
            print(f"  - {name}", file=sys.stderr)
    return rc


def main(argv) -> int:
    readme = argv[1] if len(argv) > 1 else os.path.join(_REPO, "README.md")
    try:
        with open(readme, encoding="utf-8") as f:
            text = f.read()
    except OSError as e:
        print(f"cannot read {readme}: {e}", file=sys.stderr)
        return 2
    rc = _diff(
        readme, "metrics", registered_metric_names(),
        documented_metric_names(text),
        "registered metric(s) missing from the README metric table",
        "documented metric(s) no longer registered")
    rc |= _diff(
        readme, "phases", registered_phase_names(),
        documented_phase_names(text),
        "attribution phase(s) missing from the README phase table "
        f"(between {PHASES_BEGIN} / {PHASES_END})",
        "documented phase(s) the attribution layer no longer emits")
    rc |= _diff(
        readme, "shed reasons", registered_shed_reasons(),
        documented_shed_reasons(text),
        "shed reason(s) missing from the README shed-reason table "
        f"(between {SHED_BEGIN} / {SHED_END})",
        "documented shed reason(s) the engine no longer emits")
    rc |= _diff(
        readme, "journal events", registered_journal_events(),
        documented_journal_events(text),
        "journal event kind(s) missing from the README flight-recorder "
        f"table (between {JOURNAL_BEGIN} / {JOURNAL_END})",
        "documented journal event kind(s) the engine no longer records")
    rc |= _diff(
        readme, "router spans", registered_router_spans(),
        documented_router_spans(text),
        "router trace-span name(s) missing from the README router-span "
        f"table (between {ROUTER_SPANS_BEGIN} / {ROUTER_SPANS_END})",
        "documented router span(s) the router no longer emits")
    rc |= _diff(
        readme, "stepprof phases", registered_stepprof_phases(),
        documented_stepprof_phases(text),
        "step-profiler phase(s) missing from the README engine-"
        f"performance-plane table (between {STEPPROF_BEGIN} / "
        f"{STEPPROF_END})",
        "documented stepprof phase(s) the step profiler no longer emits")
    rc |= _diff(
        readme, "stepprof loop phases", registered_loop_phases(),
        documented_loop_phases(text),
        "step-profiler loop phase(s) missing from the README loop-phase "
        f"table (between {LOOP_PHASES_BEGIN} / {LOOP_PHASES_END})",
        "documented loop phase(s) the step profiler no longer emits")
    rc |= _diff(
        readme, "stepprof spans", registered_span_names(),
        documented_span_names(text),
        "mq.* span name(s) missing from the README span table "
        f"(between {SPANS_BEGIN} / {SPANS_END})",
        "documented mq.* span(s) the step profiler no longer emits")
    rc |= _diff(
        readme, "start-up phases", registered_start_phases(),
        documented_start_phases(text),
        "start-up phase(s) missing from the README start-up phase table "
        f"(between {START_PHASES_BEGIN} / {START_PHASES_END})",
        "documented start-up phase(s) the step profiler no longer has")
    if rc == 0:
        print(f"ok: {len(registered_metric_names())} metrics, "
              f"{len(registered_phase_names())} phases, "
              f"{len(registered_shed_reasons())} shed reasons, "
              f"{len(registered_journal_events())} journal events, "
              f"{len(registered_router_spans())} router spans, "
              f"{len(registered_stepprof_phases())} stepprof phases, "
              f"{len(registered_loop_phases())} loop phases, "
              f"{len(registered_start_phases())} start-up phases, and "
              f"{len(registered_span_names())} mq.* spans, "
              "all documented")
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv))
