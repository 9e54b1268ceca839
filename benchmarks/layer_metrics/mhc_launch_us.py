"""Microseconds ONE launch of the hyper-connection takes on the device: the
`mhc_mix_*_pallas` custom calls' self time over their count — the number a
decode pass pays 2 x (2 x layers + 1) - 1 times. 0 where the trace holds no
op of that name; None for a program whose samples carry no `mhc_*`
counters."""
from benchmarks.layer_metrics import _mhc


def read(ctx):
    if not ctx.trace or not _mhc.has_counters(ctx.trace_steps):
        return None
    seconds, launches = _mhc.time_and_launches(ctx.trace)
    return 1e6 * seconds / launches if launches else 0.0
