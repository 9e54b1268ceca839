"""Share of the context that the window layers' launches walk: the rows their
walks cover (`swa_walk_rows`: from the ring page each row's table starts at
to the span's end) over the rows a walk of the same contexts from position 0
would have covered (`swa_full_rows`), over the window's step samples. Single
digits at 8-16 k of context under a window of 128; 100 where a window is
served as a mask only. None without the counters (a program before PR 50, a
configuration without window layers)."""
from benchmarks.layer_metrics import _swa


def read(ctx):
    if not _swa.has_counters(ctx.steps):
        return None
    walked = sum(s["swa_walk_rows"] for s in ctx.steps)
    full = sum(s["swa_full_rows"] for s in ctx.steps)
    if not full:
        return None
    ctx.say("swa_rows_walked", steps=len(ctx.steps), walk_rows=walked,
            least_rows=sum(s["swa_ctx_rows"] for s in ctx.steps),
            full_rows=full)
    return 100.0 * walked / full
