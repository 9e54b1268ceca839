"""Kimi Delta Attention's one-token kernel's share of its roofline: the least
seconds the chip needs for its launches (_kda.step_least_seconds: every LIVE
row's state read once and written once, and the row's q, k, decay a key
channel, v, beta and output, at the HBM peak; or its FLOPs at the bf16 peak if
that is more) over the seconds they took on the device trace. Both sides cover
the same passes, as lin_step_roofline_pct.py's: the trace says how many
launches it holds (one a KDA layer a forward pass), the step samples taken
during the capture how many rows a pass had live (`lin_step_rows` over their
passes). 0 where the trace holds no such op; None for a configuration without
`linear_attn_config`, a program without the counters, or no peaks (a
rehearsal on the CPU)."""
from benchmarks.layer_metrics import _kda
from benchmarks.lib import steps


def read(ctx):
    cfg = ctx.cell.config
    if not ctx.trace or not _kda.heads(cfg) \
            or not _kda.has_counters(ctx.trace_steps):
        return None
    seconds, launches = _kda.time_and_launches(ctx.trace, _kda.STEP_KERNEL)
    if not launches:
        return 0.0
    if not ctx.peaks:
        return None
    sampled = steps.total_passes(ctx.trace_steps)
    rows = sum(s["lin_step_rows"] for s in ctx.trace_steps) / sampled
    least, bound = _kda.step_least_seconds(cfg, rows * launches, ctx.peaks)
    ctx.say("kda_step_roofline", launches_in_trace=launches,
            passes_sampled=sampled, live_rows_a_pass=rows, least_s=least,
            bound_by=bound, measured_s=seconds)
    return 100.0 * least / seconds
