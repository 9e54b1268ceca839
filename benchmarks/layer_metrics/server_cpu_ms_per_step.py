"""CPU milliseconds per forward pass of the thread that runs the server's
event loop (handlers, the stream writer's frames): the rise of
ollamamq_thread_cpu_seconds_total{thread="server"} between the window's two
scrapes, over the passes of the window's samples. None where the program
exports no such family (older than PR 37)."""
from benchmarks.layer_metrics import _dry


def read(ctx):
    return _dry.per_pass(_dry.cpu_ms(ctx, "server"), ctx)
