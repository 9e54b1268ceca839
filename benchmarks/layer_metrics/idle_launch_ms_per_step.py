"""Milliseconds per forward pass the chip had nothing to run while the host
composed and launched the next step: the seconds of the capture's idle gaps
charged to `mq.host_prep*`, `mq.dispatch*` and jax's annotations of the
launch (`_gaps.LAUNCH`), over the passes of the capture's samples. 0.0
where no gap carries such a name (every capture made under the Python
tracer). None without a trace or without samples of the capture."""
from benchmarks.layer_metrics import _gaps


def read(ctx):
    return _gaps.ms_per_pass(ctx, _gaps.LAUNCH)
