"""Largest over mean tokens an expert got: each step sample's `moe_load_max`
(the most rows any expert of any layer got in a pass of that dispatch) over
its `moe_load_mean` (assignments / passes / layers / experts), mean over the
window's forward passes. 1 is perfectly even routing."""
from benchmarks.layer_metrics import _moe
from benchmarks.lib import steps


def read(ctx):
    if not _moe.has_counters(ctx.steps):
        return None
    live = [s for s in ctx.steps if s["moe_load_mean"] > 0]
    if not live:
        return None
    return sum(steps.passes(s) * s["moe_load_max"] / s["moe_load_mean"]
               for s in live) / steps.total_passes(live)
