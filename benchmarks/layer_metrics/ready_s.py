"""Seconds from the kernel's start of the server process to its HTTP server
starting to listen, as the program itself reads them
(`ollamamq_ready_seconds`, set once): the harness's `health_s` from inside,
without the spawn and the poll. 0.0 from a program older than PR 67, which
has no such gauge."""
from benchmarks.layer_metrics import _setup


def read(ctx):
    return _setup.ready_s(ctx)
