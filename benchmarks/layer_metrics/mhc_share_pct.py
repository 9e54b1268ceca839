"""Share of device-busy time in the hyper-connection's launches: self time of
the `mhc_mix_in_pallas` / `mhc_mix_out_pallas` custom calls (_mhc.KERNEL) over
busy_s — what four residual streams cost beside the sublayers they wrap. 0
where the trace holds no op of that name (a rehearsal on the CPU); None for a
program whose samples carry no `mhc_*` counters."""
from benchmarks.layer_metrics import _mhc


def read(ctx):
    if not ctx.trace or not _mhc.has_counters(ctx.trace_steps):
        return None
    return 100.0 * _mhc.time_and_launches(ctx.trace)[0] / ctx.trace["busy_s"]
