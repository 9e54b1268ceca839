"""Device-busy milliseconds per forward pass: the trace's busy_s over the
forward passes it holds (attention-kernel launches / layers, _ops.py; where
the trace has no such kernel — a rehearsal on the CPU — the passes of the
step profiler's samples taken during the capture)."""
from benchmarks.layer_metrics import _ops
from benchmarks.lib import steps


def read(ctx):
    if not ctx.trace:
        return None
    n = _ops.forward_passes(ctx.trace, ctx.cell.config["num_hidden_layers"]) \
        or steps.total_passes(ctx.trace_steps or ())
    return 1e3 * ctx.trace["busy_s"] / n if n > 0 else None
