"""Mean time a request sat in the fair-share queue: delta _sum / delta _count
of ollamamq_request_phase_ms{phase="queue"} between the window's two ends."""
from benchmarks.lib import stats


def read(ctx):
    if ctx.prom0 is None or ctx.prom1 is None:
        return None
    return stats.delta_mean(ctx.prom0, ctx.prom1, "ollamamq_request_phase_ms",
                            phase="queue")
