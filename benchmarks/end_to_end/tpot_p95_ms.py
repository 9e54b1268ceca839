"""95th percentile over requests of (last token - first token) / (tokens - 1),
client side; a failed request counts as the drain limit."""
from benchmarks.lib import stats


def read(ctx):
    return stats.percentile(stats.tpot_ms(ctx.records, ctx.drain_limit_ms), 95)
