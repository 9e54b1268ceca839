"""Milliseconds per forward pass the chip had NOTHING queued because the next
launch came late: the sum of `dry_lo_ms` over the window's samples, over
their passes — the program's own lower bound, from its done-brackets, with
no profiler (46 of the window's 51 s run without the tracer). It holds
neither the time from a launch's return to its program's first op nor a gap
inside a step program, which no host change can reach. None where the
samples carry no `dry_lo_ms` (a program older than PR 37)."""
from benchmarks.layer_metrics import _dry


def read(ctx):
    return _dry.per_pass(_dry.dry_lo_ms(ctx.steps), ctx)
