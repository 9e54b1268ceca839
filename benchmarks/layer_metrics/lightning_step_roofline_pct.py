"""The lightning recurrence's one-token kernel's share of its roofline: the
least seconds the chip needs for its launches (_lightning.least_seconds: every
LIVE row's 2 MiB state read once and written once, and the row's q, k, v,
decay and output, at the HBM peak; or its FLOPs at the bf16 peak if that is
more) over the seconds they took on the device trace. Both sides cover the
same passes, as ssm_step_roofline_pct.py's: the trace says how many launches
it holds (one a lightning layer a pass), the step samples taken during the
capture how many rows a pass had live (`lightning_step_rows` over their
passes). 0 where the trace holds no such op; None without the counters or
the peaks."""
from benchmarks.layer_metrics import _lightning
from benchmarks.lib import steps


def read(ctx):
    if not ctx.trace or not _lightning.has_counters(ctx.trace_steps):
        return None
    cfg = ctx.cell.config
    seconds, launches = _lightning.time_and_launches(ctx.trace)
    if not launches:
        return 0.0
    if not ctx.peaks:
        return None
    sampled = steps.total_passes(ctx.trace_steps)
    rows = sum(s["lightning_step_rows"] for s in ctx.trace_steps) / sampled
    least, bound = _lightning.least_seconds(cfg, rows * launches, ctx.peaks)
    ctx.say("lightning_step_roofline", launches_in_trace=launches,
            passes_sampled=sampled, live_rows_a_pass=rows,
            row_bytes=_lightning.row_bytes(cfg), least_s=least,
            bound_by=bound, measured_s=seconds)
    return 100.0 * least / seconds
