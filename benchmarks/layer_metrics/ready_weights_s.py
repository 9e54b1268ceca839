"""Seconds of that start the weights took: `ollamamq_startup_seconds` of the
phases `weights` (the seeded draw, or the read) and `place` (sharding, the
kv heads' replication, the device formats), every model's summed. 0.0 from a
program older than PR 67."""
from benchmarks.layer_metrics import _setup


def read(ctx):
    return (_setup.series(ctx, _setup.PHASE, phase="weights")
            + _setup.series(ctx, _setup.PHASE, phase="place"))
