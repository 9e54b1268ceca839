"""The dense latent attention kernel's share of its roofline, the prediction
module's launches included: the least seconds the chip needs for the
capture's causal (query, position) pairs (_mla_dense.least_seconds: their
FLOPs in the expanded form at the bf16 peak, or each span's cached latent
rows once at the HBM peak if that is more) over the seconds the kernel took
on the device trace. Both sides cover the same launches: a pass's counts
(the step samples taken during the capture, over their passes) times the
launches the trace holds. 0 where the trace holds no such op; None without
the counters or peaks."""
from benchmarks.layer_metrics import _mla_dense
from benchmarks.lib import steps


def read(ctx):
    if not ctx.trace or not _mla_dense.has_counters(ctx.trace_steps):
        return None
    cfg = ctx.cell.config
    seconds, launches = _mla_dense.time_and_launches(ctx.trace)
    if not launches:
        return 0.0
    if not ctx.peaks:
        return None
    sampled = steps.total_passes(ctx.trace_steps)
    pairs = sum(s["mla_pairs"] for s in ctx.trace_steps) / sampled
    rows = sum(s["mla_ctx_rows"] for s in ctx.trace_steps) / sampled
    least, bound = _mla_dense.least_seconds(cfg, pairs * launches,
                                            rows * launches, ctx.peaks)
    ctx.say("mla_dense_attn_roofline", launches_in_trace=launches,
            passes_sampled=sampled, pairs_a_launch=pairs,
            ctx_rows_a_launch=rows, pair_flops=_mla_dense.pair_flops(cfg),
            row_bytes=_mla_dense.row_bytes(cfg), least_s=least,
            bound_by=bound, measured_s=seconds)
    return 100.0 * least / seconds
