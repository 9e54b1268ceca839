"""Share of device-busy time in the dense latent attention kernel, the
prediction module's launch included: self time of
`mla_dense_paged_attention_pallas` and `mtp_latent_attention_pallas`
(_mla_dense.ATTEND) over busy_s. (`attn_kernel_share_pct.thr` beside it holds
the trunk's launches alone: the module's is no layer lib/arch.py counts.) 0
where the trace holds no op of that name (a rehearsal on the CPU); None for
a program whose samples carry no such counters."""
from benchmarks.layer_metrics import _mla_dense


def read(ctx):
    if not ctx.trace or not _mla_dense.has_counters(ctx.trace_steps):
        return None
    return 100.0 * _mla_dense.time_and_launches(ctx.trace)[0] \
        / ctx.trace["busy_s"]
