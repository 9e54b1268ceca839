"""Mean time from the engine thread's push of a stream item to its frame
having been written to the socket by the asyncio thread: delta _sum / delta
_count of ollamamq_stream_lag_ms between the window's two ends. None where
the program exports no such histogram."""
from benchmarks.lib import stats


def read(ctx):
    if ctx.prom0 is None or ctx.prom1 is None:
        return None
    return stats.delta_mean(ctx.prom0, ctx.prom1, "ollamamq_stream_lag_ms")
