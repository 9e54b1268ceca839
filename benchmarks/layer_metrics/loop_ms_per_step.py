"""Engine-loop milliseconds per forward pass: what the engine thread spent
between steps in admission (`loop_admit_ms`) and in the rest of its tick
(`loop_other_ms`; the idle condvar wait is not work and is left out),
summed over the window's samples, over their passes. None where the
program's samples do not carry the loop fields (a program older than PR 24):
unknown is not zero."""
from benchmarks.lib import steps

FIELDS = ("loop_admit_ms", "loop_other_ms")


def read(ctx):
    if not ctx.steps or not all(f in s for s in ctx.steps for f in FIELDS):
        return None
    return sum(float(s[f]) for s in ctx.steps for f in FIELDS) \
        / steps.total_passes(ctx.steps)
