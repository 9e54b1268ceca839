"""Milliseconds per forward pass the chip had nothing to run while the host
was reading a step's ids or settling them: the seconds of the capture's idle
gaps charged to `mq.collect`, `mq.detok*` and jax's annotation of the
blocking read (`_gaps.SETTLE`), over the passes of the capture's samples. A
gap while the thread is already blocked on the chip (`mq.collect`,
`np.asarray(jax.Array)`) is launch or read-back latency, not Python. 0.0
where no gap carries such a name. None without a trace or without samples
of the capture."""
from benchmarks.layer_metrics import _gaps


def read(ctx):
    return _gaps.ms_per_pass(ctx, _gaps.SETTLE)
