"""Share of device-busy time in the state-space recurrence's one-token
kernel: self time of the `ssd_step_*pallas` custom calls (_ssm.SSM_KERNEL)
over busy_s. 0 where the trace holds no op of that name (a rehearsal on the
CPU); None for a program whose samples carry no `ssm_*` counters."""
from benchmarks.layer_metrics import _ssm


def read(ctx):
    if not ctx.trace or not _ssm.has_counters(ctx.trace_steps):
        return None
    return 100.0 * _ssm.time_and_launches(ctx.trace)[0] / ctx.trace["busy_s"]
