"""Real token positions per forward pass, over the step profiler's samples
of the window (a fused decode dispatch of k passes counts k)."""
from benchmarks.lib import steps


def read(ctx):
    if not ctx.steps:
        return None
    return sum(int(s.get("tokens") or 0) for s in ctx.steps) \
        / steps.total_passes(ctx.steps)
