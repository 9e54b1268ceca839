"""Device-busy milliseconds per forward pass: the trace's busy_s over the
forward passes it holds (attention-kernel launches / the layers that have
attention, _ops.py and lib/arch.py; where the trace has no such kernel — a
rehearsal on the CPU — or the stack no such layer, the passes of the step
profiler's samples taken during the capture)."""
from benchmarks.layer_metrics import _ops
from benchmarks.lib import arch, steps


def read(ctx):
    if not ctx.trace:
        return None
    n = _ops.forward_passes(ctx.trace, arch.attention_layers(ctx.cell.config)) \
        or steps.total_passes(ctx.trace_steps or ())
    return 1e3 * ctx.trace["busy_s"] / n if n > 0 else None
