"""What latent attention with NO selection costs at the least, and the names
its kernel has on the device trace. Data and arithmetic for the
`mla_dense_*` metric files beside it; everything is computed from the
configuration file's keys and the counters the program's step samples carry
(`mla_rows`, `mla_pairs`, `mla_ctx_rows`: telemetry of PR 42; a program
without them gives the readers nothing to read).

The kernel (a Mosaic custom call under the name its launch gives it): the
attention kernel with the selection compiled out, once a layer a forward
pass as `mla_dense_paged_attention_pallas` (the name `_ops.ATTENTION`
matches: lib/arch.py's attention layers) and once more a pass as
`mtp_latent_attention_pallas`, the prediction module's block — same rows,
same contexts, its own layer of the pool. BOTH are counted here.

The roofline counts THE LEAST ANY IMPLEMENTATION OF THE SAME MATHEMATICS
NEEDS, never this kernel's own tiling, so that a later kernel is read against
the same work and nothing reads over 100: every causal (query, position)
pair costs heads x (head_dim + v_head_dim) x 2 FLOPs — the expanded form's
count, the smaller of the two forms — at the bf16 peak; and every span (a
decode row, a verify span, a prefill chunk) reads each cached latent row of
its sequence once, latent_dim x 2 B, at the HBM peak. The larger of the two
times. The counters are a launch's worth (every layer and the module do the
same), so a pass's counts times the trace's launches is the trace's work.
"""
import re

ATTEND = re.compile(r"mla_dense_paged_attention\w*pallas"
                    r"|mtp_latent_attention\w*pallas")
FIELDS = ("mla_rows", "mla_pairs", "mla_ctx_rows")
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
    return (cfg["num_attention_heads"]
            * (cfg["head_dim"] + cfg["v_head_dim"]) * 2)


def row_bytes(cfg: dict) -> int:
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * CACHE_BYTES


def least_seconds(cfg: dict, pairs: float, ctx_rows: float,
                  peaks: dict) -> tuple:
    """(seconds the chip needs at the least, which peak bounds it) for
    `pairs` causal pairs whose spans read `ctx_rows` cached rows."""
    by_flops = pairs * pair_flops(cfg) / peaks["flops_bf16"]
    by_bytes = ctx_rows * row_bytes(cfg) / peaks["hbm_bytes_per_s"]
    return max(by_flops, by_bytes), ("hbm" if by_bytes >= by_flops
                                     else "flops")
