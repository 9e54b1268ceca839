"""What the expert layer of a sparse configuration costs at the least, and the
names its matmuls have on the device trace. Data and arithmetic for the
`moe_*` metric files beside it; everything is computed from the configuration
file's keys and the counters the program's step samples carry
(`moe_assignments`, `moe_pairs_hit`: telemetry of PR 27; a program without
them gives the readers nothing to read).

The expert matmuls are three grouped matmuls a layer (gate, up, down). On a
TPU they are jax's megablox kernel (`gmm.N`) or, for a program that uses
`jax.lax.ragged_dot`, XLA's own (`ragged-dot...`); neither name matches
`_ops.ATTENTION`.
"""
import re

from benchmarks.lib import arch

EXPERT_MM = re.compile(r"^(gmm|ragged-dot)")
MATMULS_A_LAYER = 3
FIELDS = ("moe_assignments", "moe_pairs_hit", "moe_load_max", "moe_load_mean")
WEIGHT_BYTES = 2  # bf16, as the configuration files state


def has_counters(samples) -> bool:
    return bool(samples) and all(f in s for s in samples for f in FIELDS)


def pair_bytes(cfg: dict) -> int:
    """One (layer, expert) pair that got a token streams its three matrices
    once: gate and up [hidden, expert width], down [expert width, hidden]."""
    return 3 * cfg["hidden_size"] * arch.expert_width(cfg) * WEIGHT_BYTES


def assignment_bytes(cfg: dict) -> int:
    """One (token, expert) assignment: gate and up each read the token's
    hidden row and write an expert-width row, down reads one and writes a
    hidden row."""
    d, f = cfg["hidden_size"], arch.expert_width(cfg)
    return (2 * (d + f) + (f + d)) * WEIGHT_BYTES


def assignment_flops(cfg: dict) -> int:
    return 3 * 2 * cfg["hidden_size"] * arch.expert_width(cfg)


def least_seconds(cfg: dict, pairs_hit: float, assignments: float,
                  peaks: dict) -> tuple:
    """(seconds the chip needs at the least, which peak bounds it) for
    expert matmuls that hit `pairs_hit` (layer, expert) pairs with
    `assignments` rows. Only experts that were hit count: weights that were
    never read are not credited."""
    by_bytes = (pairs_hit * pair_bytes(cfg)
                + assignments * assignment_bytes(cfg)) \
        / peaks["hbm_bytes_per_s"]
    by_flops = assignments * assignment_flops(cfg) / peaks["flops_bf16"]
    return max(by_bytes, by_flops), ("hbm" if by_bytes >= by_flops
                                     else "flops")


def time_and_launches(trace: dict) -> tuple:
    t = sum(s for name, s in trace["op_self_s"].items()
            if EXPERT_MM.search(name))
    n = sum(c for name, c in trace["op_count"].items()
            if EXPERT_MM.search(name))
    return t, n
