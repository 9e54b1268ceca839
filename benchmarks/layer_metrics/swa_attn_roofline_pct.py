"""The window launches' share of their roofline, the ragged kernel's and the
decode kernel's together: the least seconds the chip needs for the capture's
IN-WINDOW (query token, cached position) pairs (_swa.least_seconds: their
FLOPs at the bf16 peak, or the cached K and V rows inside each span's windows
once at the HBM peak if that is more) over the seconds the launches took on
the device trace. Both sides cover the same launches: a pass's counts (the
step samples taken during the capture, over their passes; a window layer's
worth) times the launches the trace holds (one a window layer a pass). 0
where the trace holds no such op; None without the counters (a program
before PR 50, a configuration without window layers) or peaks (a rehearsal
on the CPU)."""
from benchmarks.layer_metrics import _swa
from benchmarks.lib import arch_window, steps


def read(ctx):
    if not ctx.trace or not _swa.has_counters(ctx.trace_steps):
        return None
    cfg = ctx.cell.config
    seconds, launches = _swa.time_and_launches(ctx.trace)
    if not launches:
        return 0.0
    if not ctx.peaks:
        return None
    sampled = steps.total_passes(ctx.trace_steps)
    pairs = sum(s["swa_pairs"] for s in ctx.trace_steps) / sampled
    rows = sum(s["swa_ctx_rows"] for s in ctx.trace_steps) / sampled
    least, bound = _swa.least_seconds(cfg, pairs * launches, rows * launches,
                                      ctx.peaks)
    ctx.say("swa_attn_roofline", launches_in_trace=launches,
            passes_sampled=sampled,
            window_layers=arch_window.window_layers(cfg),
            pairs_a_launch=pairs, ctx_rows_a_launch=rows,
            pair_flops=_swa.pair_flops(cfg), row_bytes=_swa.row_bytes(cfg),
            least_s=least, bound_by=bound, measured_s=seconds)
    return 100.0 * least / seconds
