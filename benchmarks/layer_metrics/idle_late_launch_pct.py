"""The share of the capture's idle seconds that late launches account for:
100 x the sum of `dry_lo_ms` over the step samples taken during the capture,
over window_s - busy_s of the trace. What `idle_explained_pct` was meant to
be: the dry time is counted only while the chip has nothing queued, so it
cannot overlap busy time and cannot pass 100 but for the alignment of the
capture's samples (a fraction of a second). The rest of the idle time lies
between a step's true end and the probe that first saw it (inside the
bracket), and between a launch's return and its program's first op. None
without a trace, without samples of the capture, or where they carry no
`dry_lo_ms` (older than PR 37)."""
from benchmarks.layer_metrics import _dry


def read(ctx):
    dry_ms = _dry.dry_lo_ms(ctx.trace_steps)
    if not ctx.trace or dry_ms is None:
        return None
    idle_s = ctx.trace["window_s"] - ctx.trace["busy_s"]
    if idle_s <= 0:
        return None
    return 100.0 * dry_ms / 1e3 / idle_s
