"""Share of device-busy time in the chunked rule's pair kernel: self time of
the `chunk_rule_pallas` custom calls (_kda.CHUNK_KERNEL) over busy_s — the
one-token kernel's share is lin_kernel_share_pct's. 0 where the trace holds no
op of that name (a rehearsal on the CPU); None for a program whose samples
carry no linear-attention counters."""
from benchmarks.layer_metrics import _kda


def read(ctx):
    if not ctx.trace or not _kda.has_counters(ctx.trace_steps):
        return None
    return 100.0 * _kda.time_and_launches(
        ctx.trace, _kda.CHUNK_KERNEL)[0] / ctx.trace["busy_s"]
