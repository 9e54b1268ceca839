"""CPU milliseconds per forward pass of the engine loop thread: the rise of
ollamamq_thread_cpu_seconds_total{thread="engine"} between the window's two
scrapes, over the passes of the window's samples. One term of "both threads'
Python a step"; the other is server_cpu_ms_per_step. None where the program
exports no such family (older than PR 37)."""
from benchmarks.layer_metrics import _dry


def read(ctx):
    return _dry.per_pass(_dry.cpu_ms(ctx, "engine"), ctx)
