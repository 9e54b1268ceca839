"""The hyper-connection's share of its roofline: the least seconds the chip
needs for the capture's applications (_mhc.least_seconds: one pass over the
streams an application around a sublayer plus every application's Phi once a
forward pass at the HBM peak, or the product's FLOPs at the bf16 peak if that
is more) over the seconds the `mhc_mix_*_pallas` launches took on the device
trace. Both sides cover the same passes: the trace holds 2 x `mhc_apps` - 1
launches a forward pass, the step samples taken during the capture say how
many tokens a pass carried (`mhc_rows` over the samples' passes). 0 where the
trace holds no such op; None for a configuration without `hc_mult`, a program
without the counters, or no peaks."""
from benchmarks.layer_metrics import _mhc
from benchmarks.lib import steps


def read(ctx):
    cfg = ctx.cell.config
    if not ctx.trace or not _mhc.sizes(cfg) \
            or not _mhc.has_counters(ctx.trace_steps):
        return None
    seconds, launches = _mhc.time_and_launches(ctx.trace)
    if not launches:
        return 0.0
    if not ctx.peaks:
        return None
    sampled = steps.total_passes(ctx.trace_steps)
    rows = sum(s["mhc_rows"] for s in ctx.trace_steps) / sampled
    apps = ctx.trace_steps[0]["mhc_apps"]
    passes = launches / _mhc.launches_a_pass(apps)
    least, bound = _mhc.least_seconds(cfg, rows, apps, passes, ctx.peaks)
    ctx.say("mhc_roofline", launches_in_trace=launches,
            passes_in_trace=passes, passes_sampled=sampled,
            rows_a_pass=rows, apps_a_pass=apps, least_s=least,
            bound_by=bound, measured_s=seconds,
            us_a_launch=1e6 * seconds / launches)
    return 100.0 * least / seconds
