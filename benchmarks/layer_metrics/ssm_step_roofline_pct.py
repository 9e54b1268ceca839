"""The state-space recurrence's one-token kernel's share of its roofline: the
least seconds the chip needs for its launches (_ssm.least_seconds: every LIVE
row's state read once and written once, and the row's C, B, dt x, decay and
output, at the HBM peak; or its FLOPs at the bf16 peak if that is more) over
the seconds they took on the device trace.

Both sides cover the same passes. The trace says how many launches it holds
(one a layer a forward pass). The step samples taken during the capture say
how many rows a pass had live: `ssm_step_rows` (row-passes: a ragged step's
1-token rows, a scan's active slots x its passes), summed, over their passes —
as lin_step_roofline_pct.py takes the delta rule's. 0 where the trace holds no
such op; None for a program without the counters, or with no peaks (a
rehearsal on the CPU)."""
from benchmarks.layer_metrics import _ssm
from benchmarks.lib import steps


def read(ctx):
    if not ctx.trace or not _ssm.has_counters(ctx.trace_steps):
        return None
    cfg = ctx.cell.config
    seconds, launches = _ssm.time_and_launches(ctx.trace)
    if not launches:
        return 0.0
    if not ctx.peaks:
        return None
    sampled = steps.total_passes(ctx.trace_steps)
    rows = sum(s["ssm_step_rows"] for s in ctx.trace_steps) / sampled
    least, bound = _ssm.least_seconds(cfg, rows * launches, ctx.peaks)
    ctx.say("ssm_step_roofline", launches_in_trace=launches,
            passes_sampled=sampled, live_rows_a_pass=rows,
            row_bytes=_ssm.row_bytes(cfg), least_s=least, bound_by=bound,
            measured_s=seconds)
    return 100.0 * least / seconds
