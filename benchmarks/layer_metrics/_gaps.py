"""The chip's idle gaps by the phase of the program that was open while each
one lasted, for the metric files beside this one. A traced run's
`trace["gaps"]` rows are `[start_ns, dur_ns, next op, host frame]`
(lib/trace.py: the first chip's 300 longest gaps, each charged to the event
of the engine thread's line that overlaps most of it, the innermost on a
tie, events under 0.1 ms left out) and `gap_total_s` their sum. With the
profiler's Python tracer off (the program's default since PR 52) the only
events on that line are the program's own `mq.*` spans
(telemetry/stepprof.py SPAN_NAMES) and jax's annotations of the calls a
phase makes, so a frame IS a phase of the program. Under the Python tracer
(the default until PR 52) nearly every gap is charged to a Python frame
(`engine.py:4853 _loop_once`): the readers then find next to nothing, and
say so with a number — a trace with no matching frame reads 0.0, not None.
None only without a trace."""
from benchmarks.lib import steps

PROGRAM = "mq."
# jax's own annotations on the engine thread, as a capture without the
# Python tracer names them: the jitted call and the upload inside a launch
# (`mq.host_prep`, `mq.dispatch`), the blocking read inside `mq.collect`.
LAUNCH_CALLS = ("PjitFunction(", "DevicePut")
SETTLE_CALLS = ("np.asarray(jax.Array)",)
LAUNCH = ("mq.host_prep", "mq.dispatch") + LAUNCH_CALLS
SETTLE = ("mq.collect", "mq.detok") + SETTLE_CALLS
NAMED = (PROGRAM,) + LAUNCH_CALLS + SETTLE_CALLS


def gap_s(trace: dict, prefixes: tuple) -> float:
    """Seconds of the trace's gaps whose host frame starts with one of
    `prefixes` (a child span `mq.dispatch.launch` counts for its parent,
    and four chips' `DevicePutWithSharding` for `DevicePut`)."""
    return sum(dur_ns for _, dur_ns, _, frame in trace["gaps"]
               if frame.startswith(prefixes)) / 1e9


def ms_per_pass(ctx, prefixes: tuple) -> float | None:
    """Milliseconds of such gaps per forward pass of the capture's samples
    (a fused scan of k holds k passes). None without a trace or without
    samples of the capture."""
    if not ctx.trace or not ctx.trace_steps:
        return None
    return 1e3 * gap_s(ctx.trace, prefixes) \
        / steps.total_passes(ctx.trace_steps)
