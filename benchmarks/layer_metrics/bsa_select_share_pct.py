"""Share of device-busy time in the block selection's kernel: self time of
the `bsa_select_pallas` custom calls (_bsa.SELECT: the softmax over the pooled
keys a head, summed a kv group) over busy_s. The gather of the pooled rows,
the max-pool and the top-k around it are XLA fusions the trace does not name
and are not in it. 0 where the trace holds no op of that name; None for a
program whose samples carry no `bsa_*` counters."""
from benchmarks.layer_metrics import _bsa


def read(ctx):
    return _bsa.share(ctx, _bsa.SELECT)
