"""The dense latent attention kernel's share of its roofline where latent
attention is a layer KIND without a position embedding (Kimi-Linear):
mla_dense_attn_roofline_pct.py's ratio with a latent head's width read from
`qk_nope_head_dim` + `qk_rope_head_dim` (_kda.latent_pair_flops) — this
family's published `head_dim` is hidden_size / heads, which no module reads,
and `_mla_dense.pair_flops` would count 72 lanes of q . k where there are 192.
The least seconds the chip needs for the capture's causal (query, position)
pairs (their FLOPs in the expanded form at the bf16 peak, or each span's
cached latent rows once at the HBM peak if that is more) over the seconds the
kernel took on the device trace; a pass's counts (`mla_pairs`,
`mla_ctx_rows`: a launch's worth) times the launches the trace holds. 0 where
the trace holds no such op; None without the counters or peaks."""
from benchmarks.layer_metrics import _kda, _mla_dense
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
    least, bound = _kda.bounded(
        rows * launches * _kda.latent_row_bytes(cfg)
        / ctx.peaks["hbm_bytes_per_s"],
        pairs * launches * _kda.latent_pair_flops(cfg)
        / ctx.peaks["flops_bf16"])
    ctx.say("mla_nope_attn_roofline", launches_in_trace=launches,
            passes_sampled=sampled, pairs_a_launch=pairs,
            ctx_rows_a_launch=rows,
            pair_flops=_kda.latent_pair_flops(cfg), least_s=least,
            bound_by=bound, measured_s=seconds)
    return 100.0 * least / seconds
