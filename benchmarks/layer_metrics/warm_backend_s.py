"""Of those first calls, the seconds inside jax's backend (`backend_ms`):
XLA's and Mosaic's compile where the persistent cache missed, the retrieval
where it hit — the part of `setup_s` that depends on what the cache held.
0.0 from a program older than PR 67, whose events lack the field."""
from benchmarks.layer_metrics import _setup


def read(ctx):
    return _setup.warm_s(ctx, "backend_ms")
