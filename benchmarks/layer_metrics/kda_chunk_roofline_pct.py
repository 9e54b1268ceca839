"""The chunked rule's pair kernel's share of its roofline, for Kimi Delta
Attention's spans: the least seconds the chip needs for what its launches
carried (_kda.chunk_least_seconds: a span row's state out once and in once
unless the step opened it, every span token's operands and output once, at
the HBM peak; or the token-serial recurrence's FLOPs at the bf16 peak if that
is more) over the seconds `chunk_rule_pallas` took on the device trace. Both
sides cover the same passes: the trace holds one launch a KDA layer a RAGGED
step — a fused decode scan launches none —, the step samples taken during the
capture say what a ragged step carried (span rows from `lin_state_resets` +
`lin_state_carried` - `lin_step_rows`, tokens from `lin_span_tokens`), summed
over the ragged samples and scaled to the trace's launches. 0 where the trace
holds no such op; None for a configuration without `linear_attn_config`, a
program without the counters, or no peaks."""
from benchmarks.layer_metrics import _kda


def read(ctx):
    cfg = ctx.cell.config
    if not ctx.trace or not _kda.heads(cfg) \
            or not _kda.has_counters(ctx.trace_steps):
        return None
    seconds, launches = _kda.time_and_launches(ctx.trace, _kda.CHUNK_KERNEL)
    if not launches:
        return 0.0
    ragged = [s for s in ctx.trace_steps if s.get("mode") == "ragged"]
    if not ctx.peaks or not ragged:
        return None
    rows_out = sum(_kda.span_rows(s)[0] for s in ragged) / len(ragged)
    rows_in = sum(_kda.span_rows(s)[1] for s in ragged) / len(ragged)
    tokens = sum(s["lin_span_tokens"] for s in ragged) / len(ragged)
    pairs = sum(s["lin_chunk_pairs"] for s in ragged) / len(ragged)
    least, bound = _kda.chunk_least_seconds(
        cfg, rows_out * launches, rows_in * launches, tokens * launches,
        ctx.peaks)
    ctx.say("kda_chunk_roofline", launches_in_trace=launches,
            ragged_steps_sampled=len(ragged), span_rows_a_step=rows_out,
            span_tokens_a_step=tokens, pairs_a_step=pairs, least_s=least,
            bound_by=bound, measured_s=seconds,
            us_a_pair=1e6 * seconds / (pairs * launches) if pairs else None)
    return 100.0 * least / seconds
