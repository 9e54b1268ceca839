"""What latent attention and its indexer's selection cost at the least in a
configuration with them, and the names their kernels have on the device trace.
Data and arithmetic for the `mla_*` / `dsa_*` metric files beside it;
everything is computed from the configuration file's keys and the counters
the program's step samples carry (`mla_rows`, `dsa_ctx_tokens`, ...:
telemetry of PR 39; a program without them gives the readers nothing to read).

The kernels (Mosaic custom calls carry the Pallas function's name), each once
a layer a forward pass: `mla_sparse_paged_attention_pallas` (the one whose
name `_ops.ATTENTION` matches), `dsa_index_pallas` (the indexer's scores) and
`dsa_select_pallas` (the threshold of the selection).

The rooflines count THE LEAST ANY IMPLEMENTATION OF THE SAME MATHEMATICS
NEEDS, never this kernel's own tiling, so that a later kernel is read against
the same work and nothing reads over 100:
  attention: every SELECTED (query, position) pair costs heads x (head_dim +
      v_head_dim) x 2 FLOPs — the expanded form's count, the smaller of the
      two forms — at the bf16 peak; and every one-token row (a decode row, a
      scan's pass: no other query shares its positions) reads each of its
      selected latent rows once, latent_dim x 2 B, at the HBM peak. The
      larger of the two times.
  indexer: every SCORED pair costs index_n_heads x index_head_dim x 2 FLOPs;
      every one-token row reads each scored index key once, index_head_dim
      x 2 B.
The counters are a layer's worth (every layer does the same), a launch is a
layer's, so a pass's counts times the trace's launches is the trace's work.
"""
import re

from benchmarks.lib import steps

ATTEND = re.compile(r"mla_sparse_paged_attention\w*pallas")
INDEX = re.compile(r"dsa_index\w*pallas")
SELECT = re.compile(r"dsa_select\w*pallas")
FIELDS = ("mla_rows", "dsa_ctx_tokens", "dsa_selected_tokens",
          "dsa_step_ctx_tokens", "dsa_step_selected_tokens")
CACHE_BYTES = 2  # bf16, as the configuration files state


def has_counters(samples) -> bool:
    return bool(samples) and all(f in s for s in samples for f in FIELDS)


def time_and_launches(trace: dict, pattern) -> tuple:
    t = sum(s for name, s in trace["op_self_s"].items()
            if pattern.search(name))
    n = sum(c for name, c in trace["op_count"].items()
            if pattern.search(name))
    return t, n


def attend_pair_flops(cfg: dict) -> int:
    return (cfg["num_attention_heads"]
            * (cfg["head_dim"] + cfg["v_head_dim"]) * 2)


def latent_row_bytes(cfg: dict) -> int:
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * CACHE_BYTES


def index_pair_flops(cfg: dict) -> int:
    return cfg["index_n_heads"] * cfg["index_head_dim"] * 2


def index_key_bytes(cfg: dict) -> int:
    return cfg["index_head_dim"] * CACHE_BYTES


def least_seconds(pairs: float, pair_flops: int, step_rows: float,
                  row_bytes: int, peaks: dict) -> tuple:
    """(seconds the chip needs at the least, which peak bounds it) for
    `pairs` (query, position) pairs of `pair_flops` each, of which one-token
    rows read `step_rows` cached rows of `row_bytes`."""
    by_flops = pairs * pair_flops / peaks["flops_bf16"]
    by_bytes = step_rows * row_bytes / peaks["hbm_bytes_per_s"]
    return max(by_flops, by_bytes), ("hbm" if by_bytes >= by_flops
                                     else "flops")


def roofline(ctx, pattern, pairs_field: str, step_field: str, pair_flops: int,
             row_bytes: int, note: str):
    """100 x least / measured for the kernel `pattern` over the capture; 0
    where the trace holds no such op; None without counters or peaks."""
    if not ctx.trace or not has_counters(ctx.trace_steps):
        return None
    seconds, launches = time_and_launches(ctx.trace, pattern)
    if not launches:
        return 0.0
    if not ctx.peaks:
        return None
    sampled = steps.total_passes(ctx.trace_steps)
    pairs = sum(s[pairs_field] for s in ctx.trace_steps) / sampled
    rows = sum(s[step_field] for s in ctx.trace_steps) / sampled
    least, bound = least_seconds(pairs * launches, pair_flops,
                                 rows * launches, row_bytes, ctx.peaks)
    ctx.say(note, launches_in_trace=launches, passes_sampled=sampled,
            pairs_a_pass=pairs, one_token_rows_read_a_pass=rows,
            pair_flops=pair_flops, row_bytes=row_bytes, least_s=least,
            bound_by=bound, measured_s=seconds)
    return 100.0 * least / seconds
