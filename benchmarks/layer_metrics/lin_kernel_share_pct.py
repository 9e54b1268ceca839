"""Share of device-busy time in the delta rule's kernels: self time of the
`gated_delta_*_pallas` custom calls (_lin.LIN_KERNEL) over busy_s. 0 where
the trace holds no op of that name (a rehearsal on the CPU); None for a
program whose samples carry no linear-attention counters."""
from benchmarks.layer_metrics import _lin


def read(ctx):
    if not ctx.trace or not _lin.has_counters(ctx.trace_steps):
        return None
    return 100.0 * _lin.time_and_launches(ctx.trace)[0] / ctx.trace["busy_s"]
