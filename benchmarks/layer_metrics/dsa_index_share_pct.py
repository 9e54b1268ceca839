"""Share of device-busy time in the learned selection: self time of the
indexer's and the selection's kernels (`dsa_index_pallas`, `dsa_select_pallas`)
over busy_s. 0 where the trace holds no op of those names (a rehearsal on the
CPU); None for a program whose samples carry no latent-attention counters."""
from benchmarks.layer_metrics import _mla


def read(ctx):
    if not ctx.trace or not _mla.has_counters(ctx.trace_steps):
        return None
    return 100.0 * sum(_mla.time_and_launches(ctx.trace, p)[0]
                       for p in (_mla.INDEX, _mla.SELECT)) \
        / ctx.trace["busy_s"]
