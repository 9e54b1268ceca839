"""A metric of the measurement: the share of the capture's idle seconds (the
first chip's 300 longest gaps, `gap_total_s`) that lie on a name of the
program's — an `mq.*` span, or one of jax's annotations of the call a phase
makes (`_gaps.NAMED`). What is left was charged to a Python source line, or
to nothing at all. About 0 under the profiler's Python tracer, whose frames
win lib/trace.py's winner-takes-all; 90 and more with the tracer off, the
program's default since PR 52. A capture with no gap reads 100: no idle
second is without a name. Less `idle_launch_ms_per_step` and
`idle_settle_ms_per_step` (as seconds), what remains named is gaps in the
engine thread's loop phases. None without a trace."""
from benchmarks.layer_metrics import _gaps


def read(ctx):
    if not ctx.trace:
        return None
    total_s = ctx.trace["gap_total_s"]
    if total_s <= 0:
        return 100.0
    return 100.0 * _gaps.gap_s(ctx.trace, _gaps.NAMED) / total_s
