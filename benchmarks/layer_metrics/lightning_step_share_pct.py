"""Share of device-busy time in the lightning recurrence's one-token kernel:
self time of the `ssd_step_*pallas` custom calls (_lightning.KERNEL) over
busy_s. 0 where the trace holds no op of that name (a rehearsal on the CPU);
None for a program whose samples carry no `lightning_*` counters."""
from benchmarks.layer_metrics import _lightning


def read(ctx):
    if not ctx.trace or not _lightning.has_counters(ctx.trace_steps):
        return None
    return 100.0 * _lightning.time_and_launches(ctx.trace)[0] \
        / ctx.trace["busy_s"]
