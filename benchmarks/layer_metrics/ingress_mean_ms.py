"""Mean time from the HTTP handler's entry to the enqueue — JSON parse,
templating, tokenisation, on the asyncio thread: delta _sum / delta _count
of ollamamq_request_phase_ms{phase="ingress"} between the window's two ends.
None where the program exports no such phase."""
from benchmarks.lib import stats


def read(ctx):
    if ctx.prom0 is None or ctx.prom1 is None:
        return None
    return stats.delta_mean(ctx.prom0, ctx.prom1, "ollamamq_request_phase_ms",
                            phase="ingress")
