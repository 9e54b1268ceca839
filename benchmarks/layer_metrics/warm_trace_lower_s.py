"""Of those first calls, the seconds jax spent tracing and lowering
(`trace_ms` + `lower_ms`, self times): the host's Python, the same whatever
the persistent cache holds — the part of `setup_s` on which the two sides of
a pair can be compared. 0.0 from a program older than PR 67, whose events
lack the fields."""
from benchmarks.layer_metrics import _setup


def read(ctx):
    return _setup.warm_s(ctx, "trace_ms", "lower_ms")
