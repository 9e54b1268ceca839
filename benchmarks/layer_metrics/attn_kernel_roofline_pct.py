"""The two attention kernels' share of their roofline, the ragged kernel's and
the decode kernel's launches together: the least seconds the chip needs for
the capture's causal (query token, cached position) pairs
(_attn.least_seconds: their FLOPs at the bf16 peak, or each span's cached K
and V rows once at the HBM peak if that is more) over the seconds the kernels
took on the device trace. Both sides cover the same launches: a pass's counts
(the step samples taken during the capture, over their passes; a layer's
worth) times the launches the trace holds (one an attention layer a pass). 0
where the trace holds no such op; None without the counters (a program before
PR 47) or peaks (a rehearsal on the CPU)."""
from benchmarks.layer_metrics import _attn
from benchmarks.lib import steps


def read(ctx):
    if not ctx.trace or not _attn.has_counters(ctx.trace_steps):
        return None
    cfg = ctx.cell.config
    seconds, launches = _attn.time_and_launches(ctx.trace)
    if not launches:
        return 0.0
    if not ctx.peaks:
        return None
    sampled = steps.total_passes(ctx.trace_steps)
    pairs = sum(s["attn_pairs"] for s in ctx.trace_steps) / sampled
    rows = sum(s["attn_ctx_rows"] for s in ctx.trace_steps) / sampled
    least, bound = _attn.least_seconds(cfg, pairs * launches, rows * launches,
                                       ctx.peaks)
    ctx.say("attn_kernel_roofline", launches_in_trace=launches,
            passes_sampled=sampled, pairs_a_launch=pairs,
            ctx_rows_a_launch=rows, pair_flops=_attn.pair_flops(cfg),
            row_bytes=_attn.row_bytes(cfg), least_s=least, bound_by=bound,
            measured_s=seconds)
    return 100.0 * least / seconds
