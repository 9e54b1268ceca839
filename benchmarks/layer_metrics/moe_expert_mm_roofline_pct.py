"""The expert matmuls' share of their roofline: the least seconds the chip
needs for them (_moe.least_seconds: the weights of every (layer, expert) pair
that was hit, once, plus the rows read and written, at the HBM peak; or their
FLOPs at the bf16 peak if that is more) over the seconds they took on the
device trace.

Both sides cover the same passes. The trace says how many forward passes it
holds: grouped-matmul launches / (3 x the layers that have experts,
lib/arch.py). The step samples taken during the capture say what a pass hit:
pairs and assignments, summed, over their passes. (Counting the samples'
passes instead would put two clocks into one ratio; PERF.md section 6, PR 23.)
0 where the trace holds no such op; None for a program without the counters,
or with no peaks (a rehearsal on the CPU)."""
from benchmarks.layer_metrics import _moe
from benchmarks.lib import arch, steps


def read(ctx):
    if not ctx.trace or not _moe.has_counters(ctx.trace_steps):
        return None
    cfg = ctx.cell.config
    seconds, launches = _moe.time_and_launches(ctx.trace)
    if not launches:
        return 0.0
    if not ctx.peaks:
        return None
    passes = launches / (_moe.MATMULS_A_LAYER * arch.expert_layers(cfg))
    sampled = steps.total_passes(ctx.trace_steps)
    pairs = sum(s["moe_pairs_hit"] for s in ctx.trace_steps) / sampled
    rows = sum(s["moe_assignments"] for s in ctx.trace_steps) / sampled
    least, bound = _moe.least_seconds(cfg, pairs * passes, rows * passes,
                                      ctx.peaks)
    ctx.say("moe_expert_mm_roofline", passes_in_trace=passes,
            passes_sampled=sampled, pairs_hit_a_pass=pairs,
            assignments_a_pass=rows, least_s=least, bound_by=bound,
            measured_s=seconds)
    return 100.0 * least / seconds
