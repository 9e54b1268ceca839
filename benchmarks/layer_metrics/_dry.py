"""What the program says about its own chip running dry, for the metric files
beside this one: `dry_lo_ms` on a generative step sample is the least time
the chip had nothing queued before that step's launch (PR 37: from the first
probe that saw the step ahead ready to the return of the launch, the engine
thread's idle wait left out), and `ollamamq_thread_cpu_seconds_total{thread}`
the CPU clocks of the engine's and the server's threads, read at a scrape. A
program without them (older than PR 37) gives the readers nothing to read:
unknown is not zero."""
from benchmarks.lib import stats, steps

CPU_FAMILY = "ollamamq_thread_cpu_seconds_total"
# What the engine thread's wall is made of outside the time it is blocked on
# the chip (`collect_ms`, a part of `total_ms`) and its idle wait.
ENGINE_WALL = ("total_ms", "loop_admit_ms", "loop_other_ms")


def dry_lo_ms(samples) -> float | None:
    """Sum of `dry_lo_ms` over the samples that carry it; None if none does."""
    vals = [float(s["dry_lo_ms"]) for s in samples or () if "dry_lo_ms" in s]
    return sum(vals) if vals else None


def cpu_ms(ctx, thread: str) -> float | None:
    """CPU milliseconds `thread` used between the window's two scrapes."""
    if ctx.prom0 is None or ctx.prom1 is None:
        return None
    v0 = stats.prom_value(ctx.prom0, CPU_FAMILY, thread=thread)
    v1 = stats.prom_value(ctx.prom1, CPU_FAMILY, thread=thread)
    if v0 is None or v1 is None:
        return None
    return 1e3 * (v1 - v0)


def per_pass(ms: float | None, ctx) -> float | None:
    if ms is None or not ctx.steps:
        return None
    return ms / steps.total_passes(ctx.steps)
