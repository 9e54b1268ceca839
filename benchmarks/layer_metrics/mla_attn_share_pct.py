"""Share of device-busy time in the sparse latent attention kernel: self time
of `mla_sparse_paged_attention_pallas` (_mla.ATTEND) over busy_s. 0 where the
trace holds no op of that name (a rehearsal on the CPU); None for a program
whose samples carry no latent-attention counters."""
from benchmarks.layer_metrics import _mla


def read(ctx):
    if not ctx.trace or not _mla.has_counters(ctx.trace_steps):
        return None
    return 100.0 * _mla.time_and_launches(ctx.trace, _mla.ATTEND)[0] \
        / ctx.trace["busy_s"]
