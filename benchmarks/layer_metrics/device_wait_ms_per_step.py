"""Milliseconds per forward pass the engine thread spent BLOCKED on the chip:
the step profiler's `collect_ms` (since PR 28 only the time blocked reading a
step's ids), summed over the window's samples, over their passes. It is the
host's slack: while it stays near the device's milliseconds a pass the chip
sets the pace, and host work added to a pass comes out of it before it shows
end to end; near zero, the host is the pace. None where the samples carry no
`collect_ms`."""
from benchmarks.lib import steps


def read(ctx):
    if not ctx.steps or not all("collect_ms" in s for s in ctx.steps):
        return None
    return sum(float(s["collect_ms"]) for s in ctx.steps) \
        / steps.total_passes(ctx.steps)
