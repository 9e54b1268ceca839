"""Share of device-busy time in the attention kernels: self time of the
Mosaic custom calls (_ops.ATTENTION) over busy_s."""
from benchmarks.layer_metrics import _ops


def read(ctx):
    if not ctx.trace:
        return None
    return 100.0 * _ops.time_of(ctx.trace, _ops.ATTENTION) / ctx.trace["busy_s"]
