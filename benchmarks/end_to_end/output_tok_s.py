"""Output tokens that reached a client inside the window, per second of the
window, all chips together (tokens of ramp requests that arrive inside the
window count: the rate is over all the work of the window)."""
from benchmarks.lib import stats


def read(ctx):
    return stats.tokens_in_window(ctx.all_records, ctx.seconds) / ctx.seconds
