"""What block-sparse attention (MiniCPM-SALA's `minicpm4` layers: InfLLM-V2)
costs at the least, and the names its two kernels have on the device trace.
Data and arithmetic for the `bsa_*` metric files beside it; everything is
computed from the configuration file's keys and the counters the program's
step samples carry (`bsa_blocks_kept_step`, ...: telemetry of PR 60; a program
without them gives the readers nothing to read).

The kernels (Mosaic custom calls carry the Pallas function's name), each once
a sparse layer a forward pass: `bsa_select_pallas` (the softmax over the
pooled keys a head, summed a kv group: the block scores' inner part) and
`bsa_decode_attention_pallas` (one query a (row, kv head) over the kept
blocks' pages). They serve the ONE-TOKEN rows — a ragged step's decode rows, a
fused scan's passes — which is what the `_step` halves of the counters count;
a longer span past `sparse_dense_len` is served under a block mask in XLA
fusions the trace does not name (the `_span` halves: `bsa_blocks_walked_pct`).

The rooflines count THE LEAST ANY IMPLEMENTATION OF THE SAME MATHEMATICS
NEEDS, never these kernels' own walks (both kv heads' lanes a kept page, whole
128-token blocks, padded lists), so that a later kernel is read against the
same work and nothing reads over 100:
  attention: a one-token query reads each kept block's K and V rows once a kv
      head — block x 2 x kv heads x head_dim x 2 B a kept block — at the HBM
      peak; every (query, kept key) pair costs heads x head_dim x 4 FLOPs at
      the bf16 peak (all of a kept block's keys: the last block's causal cut
      is not credited). The larger of the two times.
  select: a one-token query reads its context's pooled keys once a kv group —
      block / stride pooled rows a block in context, kv heads x head_dim x 2
      B each; heads x head_dim x 2 FLOPs a (head, pooled key).
The counters are a layer's worth (every sparse layer does the same), a launch
is a layer's, so a pass's counts times the trace's launches is the trace's
work. A one-token row at or under `sparse_dense_len` (`bsa_dense_queries`)
walks its whole context through the same kernel and is credited nothing: a
cell with such rows reads lower, never over 100.
"""
import re

from benchmarks.lib import steps

WALK = re.compile(r"bsa_decode_attention\w*pallas")
SELECT = re.compile(r"bsa_select\w*pallas")
FIELDS = ("bsa_blocks_in_context_step", "bsa_blocks_in_context_span",
          "bsa_blocks_kept_step", "bsa_blocks_kept_span",
          "bsa_blocks_walked_step", "bsa_blocks_walked_span")
CACHE_BYTES = 2  # bf16, as the configuration file states


def has_counters(samples) -> bool:
    return bool(samples) and all(f in s for s in samples for f in FIELDS)


def time_and_launches(trace: dict, pattern) -> tuple:
    t = sum(s for name, s in trace["op_self_s"].items()
            if pattern.search(name))
    n = sum(c for name, c in trace["op_count"].items()
            if pattern.search(name))
    return t, n


def row_bytes(cfg: dict) -> int:
    """One cached position of one layer, or one pooled row: kv heads x
    head_dim lanes."""
    return cfg["num_key_value_heads"] * cfg["head_dim"] * CACHE_BYTES


def walk_block(cfg: dict) -> tuple:
    """(bytes, FLOPs) a kept block costs a one-token query at the least."""
    block = cfg["sparse_block_size"]
    return (block * 2 * row_bytes(cfg),
            block * cfg["num_attention_heads"] * cfg["head_dim"] * 4)


def select_block(cfg: dict) -> tuple:
    """(bytes, FLOPs) a block in context costs a one-token query's scores."""
    rows = cfg["sparse_block_size"] // cfg["sparse_kernel_stride"]
    return (rows * row_bytes(cfg),
            rows * cfg["num_attention_heads"] * cfg["head_dim"] * 2)


def roofline(ctx, pattern, field: str, a_block: tuple, said: str):
    """100 x least seconds / measured seconds of the launches `pattern`
    names, their work `field` (blocks a pass, from the samples taken during
    the capture) x `a_block` (bytes, FLOPs); 0 where the trace holds no such
    op; None without the counters or the peaks."""
    if not ctx.trace or not has_counters(ctx.trace_steps):
        return None
    seconds, launches = time_and_launches(ctx.trace, pattern)
    if not launches:
        return 0.0
    if not ctx.peaks:
        return None
    sampled = steps.total_passes(ctx.trace_steps)
    blocks = sum(s[field] for s in ctx.trace_steps) / sampled * launches
    by_bytes = blocks * a_block[0] / ctx.peaks["hbm_bytes_per_s"]
    by_flops = blocks * a_block[1] / ctx.peaks["flops_bf16"]
    ctx.say(said, launches_in_trace=launches, passes_sampled=sampled,
            blocks_a_pass=blocks / launches, block_bytes=a_block[0],
            block_flops=a_block[1], least_s=max(by_bytes, by_flops),
            bound_by="hbm" if by_bytes >= by_flops else "flops",
            measured_s=seconds)
    return 100.0 * max(by_bytes, by_flops) / seconds


def share(ctx, pattern):
    if not ctx.trace or not has_counters(ctx.trace_steps):
        return None
    return 100.0 * time_and_launches(ctx.trace, pattern)[0] \
        / ctx.trace["busy_s"]


def counter_pct(ctx, over: str, under: str, said: str):
    """100 x Σ `over` / Σ `under` (each the `_step` and `_span` halves
    together) over the window's step samples; None without the counters or
    with no query past `sparse_dense_len`."""
    if not has_counters(ctx.steps):
        return None
    halves = ("_step", "_span")
    top = {h: sum(s[over + h] for s in ctx.steps) for h in halves}
    bottom = {h: sum(s[under + h] for s in ctx.steps) for h in halves}
    if not sum(bottom.values()):
        return None
    ctx.say(said, steps=len(ctx.steps),
            **{over + h: top[h] for h in halves},
            **{under + h: bottom[h] for h in halves})
    return 100.0 * sum(top.values()) / sum(bottom.values())
