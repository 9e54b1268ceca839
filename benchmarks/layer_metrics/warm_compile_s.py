"""Seconds the step programs' first calls held the engine thread before the
window: the sum of `wall_ms` over the ledger's events that ended before it —
tracing, lowering, the backend's compile or the cache's retrieval, and the
first run, of every rung the warm-up walked. A SUM, never rung by rung: one
rung's wall depends on which rung traced the shared functions first. Reads a
program older than PR 67 as it stands (its events have `wall_ms`)."""
from benchmarks.layer_metrics import _setup


def read(ctx):
    return _setup.warm_compile_s(ctx)
