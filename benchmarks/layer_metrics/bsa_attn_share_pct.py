"""Share of device-busy time in the block-sparse walk: self time of the
`bsa_decode_attention_pallas` custom calls (_bsa.WALK: one query a (row, kv
head) over the kept blocks' pages) over busy_s. 0 where the trace holds no op
of that name (a rehearsal on the CPU); None for a program whose samples carry
no `bsa_*` counters."""
from benchmarks.layer_metrics import _bsa


def read(ctx):
    return _bsa.share(ctx, _bsa.WALK)
