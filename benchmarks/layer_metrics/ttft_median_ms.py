"""Median, over requests due in the window, of due -> first token (client
side; a failed request counts as the drain limit). Read in the traced run,
so it carries the tracer's cost; the untraced value is on each run's
`window` line. Not an end-to-end metric: over the ~100 requests a window
holds, a median of times spread over a k-pass scan repeats to 5-10 %."""
from benchmarks.lib import stats


def read(ctx):
    return stats.percentile(stats.ttft_ms(ctx.records, ctx.drain_limit_ms), 50)
