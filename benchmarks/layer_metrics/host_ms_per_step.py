"""Host milliseconds per forward pass: the step profiler's host_prep +
dispatch + detok, summed over the window's samples, over their passes."""
from benchmarks.lib import steps


def read(ctx):
    if not ctx.steps:
        return None
    return sum(steps.host_ms(s) for s in ctx.steps) \
        / steps.total_passes(ctx.steps)
