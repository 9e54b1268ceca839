"""What plain (K and V pages, non-latent) attention costs at the least, and
the names its two kernels have on the device trace. Data and arithmetic for
`attn_kernel_roofline_pct.py` beside it; everything is computed from the
configuration file's keys and the counters the program's step samples carry
(`attn_pairs`, `attn_ctx_rows`: telemetry of PR 47; a program without them
gives the reader nothing to read).

The kernels (Mosaic custom calls under their Pallas functions' names): the
ragged kernel once an attention layer a ragged step
(`ragged_paged_attention_pallas`) and the decode kernel once an attention
layer a pass of a fused scan (`paged_decode_attention_pallas`). BOTH are
counted here, together: the counters are a layer's worth of a launch
whichever kernel ran it.

The roofline counts THE LEAST ANY IMPLEMENTATION OF THE SAME MATHEMATICS
NEEDS, never these kernels' own tiling (whole 128-token blocks, masked
positions, padded rows), so that a later kernel is read against the same work
and nothing reads over 100: every causal (query token, cached position) pair
costs heads x head_dim x 4 FLOPs (q . k and p . v, a multiply and an add
each) at the bf16 peak; and every span (a decode row, a prefill chunk) reads
each cached K and V row of its sequence once, 2 x kv heads x head_dim x 2 B,
at the HBM peak. The larger of the two times. A pass's counts times the
trace's launches is the trace's work.
"""
import re

ATTEND = re.compile(r"ragged_paged_attention\w*pallas"
                    r"|paged_decode_attention\w*pallas")
FIELDS = ("attn_pairs", "attn_ctx_rows")
CACHE_BYTES = 2  # bf16, as the configuration files state


def has_counters(samples) -> bool:
    return bool(samples) and all(f in s for s in samples for f in FIELDS)


def time_and_launches(trace: dict) -> tuple:
    t = sum(s for name, s in trace["op_self_s"].items()
            if ATTEND.search(name))
    n = sum(c for name, c in trace["op_count"].items()
            if ATTEND.search(name))
    return t, n


def pair_flops(cfg: dict) -> int:
    return cfg["num_attention_heads"] * cfg["head_dim"] * 4


def row_bytes(cfg: dict) -> int:
    """One cached position of one layer: its K row and its V row."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * CACHE_BYTES


def least_seconds(cfg: dict, pairs: float, ctx_rows: float,
                  peaks: dict) -> tuple:
    """(seconds the chip needs at the least, which peak bounds it) for
    `pairs` causal pairs whose spans read `ctx_rows` cached positions."""
    by_flops = pairs * pair_flops(cfg) / peaks["flops_bf16"]
    by_bytes = ctx_rows * row_bytes(cfg) / peaks["hbm_bytes_per_s"]
    return max(by_flops, by_bytes), ("hbm" if by_bytes >= by_flops
                                     else "flops")
