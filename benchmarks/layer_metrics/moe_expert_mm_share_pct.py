"""Share of device-busy time in the expert matmuls: self time of the grouped
matmul ops (_moe.EXPERT_MM) over busy_s. 0 where the trace holds no op of
that name (a rehearsal on the CPU); None for a program whose samples carry
no expert counters."""
from benchmarks.layer_metrics import _moe


def read(ctx):
    if not ctx.trace or not _moe.has_counters(ctx.trace_steps):
        return None
    return 100.0 * _moe.time_and_launches(ctx.trace)[0] / ctx.trace["busy_s"]
