"""Share of device-busy time in collectives (mean over chips): self time of
the all-reduce / all-gather / reduce-scatter / permute / all-to-all ops
(_ops.COLLECTIVE) over busy_s."""
from benchmarks.layer_metrics import _ops


def read(ctx):
    if not ctx.trace:
        return None
    return 100.0 * _ops.time_of(ctx.trace, _ops.COLLECTIVE) / ctx.trace["busy_s"]
