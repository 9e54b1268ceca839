"""What WINDOW attention (a `sliding_attention` layer: a query at position p
sees the positions p - sliding_window < j <= p) costs at the least, and the
names its two launches have on the device trace. Data and arithmetic for the
`swa_*` metric files beside it; everything is computed from the configuration
file's keys and the counters the program's step samples carry (`swa_pairs`,
`swa_ctx_rows`, `swa_walk_rows`, `swa_full_rows`: telemetry of PR 50; a
program without them, or a configuration without window layers, gives the
readers nothing to read).

The launches (Mosaic custom calls under the names the program gives them):
the ragged kernel once a window layer a ragged step, as
`swa_ragged_attention_pallas`, and the decode kernel once a window layer a
pass of a fused scan, as `swa_decode_attention_pallas` — names of their own,
which neither `_attn.ATTEND` nor `_ops.ATTENTION` matches: those read the FULL
layers' launches (`lib/arch.py` counts `full_attention` layers), whose
counters are a full causal walk's. BOTH window launches are counted here,
together: the counters are a window layer's worth of a launch whichever
kernel ran it.

The roofline counts THE LEAST ANY IMPLEMENTATION OF THE SAME MATHEMATICS
NEEDS, never these kernels' own tiling (whole 128-token blocks from a page
boundary, masked positions, padded rows), so that a later kernel is read
against the same work and nothing reads over 100: every IN-WINDOW (query
token, cached position) pair costs heads x head_dim x 4 FLOPs (q . k and p . v,
a multiply and an add each) at the bf16 peak; and every span (a decode row, a
prefill chunk) reads each cached K and V row inside the windows of its
queries once — min(context, span + sliding_window - 1) rows — 2 x kv heads x
head_dim x 2 B, at the HBM peak. The larger of the two times. A pass's
counts times the trace's launches is the trace's work.
"""
import re

ATTEND = re.compile(r"swa_ragged_attention\w*pallas"
                    r"|swa_decode_attention\w*pallas")
FIELDS = ("swa_pairs", "swa_ctx_rows", "swa_walk_rows", "swa_full_rows")
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
    `pairs` in-window pairs whose spans read `ctx_rows` cached positions."""
    by_flops = pairs * pair_flops(cfg) / peaks["flops_bf16"]
    by_bytes = ctx_rows * row_bytes(cfg) / peaks["hbm_bytes_per_s"]
    return max(by_flops, by_bytes), ("hbm" if by_bytes >= by_flops
                                     else "flops")
