"""What the gated delta rule's one-token kernel of a linear-attention
configuration costs at the least, and the name it has on the device trace.
Data and arithmetic for the `lin_*` metric files beside it; everything is
computed from the configuration file's keys and the counters the program's
step samples carry (`lin_step_rows`, ...: telemetry of PR 35; a program
without them gives the readers nothing to read).

The kernel (`gated_delta_step_pallas`: the Mosaic custom call carries the
Pallas function's name) runs once a linear-attention layer a forward pass and
updates, in place, the state row of every LIVE row of the pass — a decode
row, a fused scan's active slot. Rows that are parked cost it nothing and are
credited nothing here: a kernel that streamed them would read lower, never
over 100. Spans longer than one token do not pass through it (they take the
chunked form, jnp contractions in XLA fusions: no kernel of its own to name).
"""
import re

LIN_KERNEL = re.compile(r"gated_delta_\w*pallas")
FIELDS = ("lin_state_resets", "lin_state_carried", "lin_step_rows",
          "lin_span_tokens")
STATE_BYTES = 4  # float32, as the configuration files state
FLOPS_A_STATE_ELEMENT = 7  # decay; S^T k; the rank-one update; S^T q


def has_counters(samples) -> bool:
    return bool(samples) and all(f in s for s in samples for f in FIELDS)


def state_elements(cfg: dict) -> int:
    """One row's state in one layer: heads x key dim x value dim."""
    return (cfg["linear_num_value_heads"] * cfg["linear_key_head_dim"]
            * cfg["linear_value_head_dim"])


def row_bytes(cfg: dict) -> int:
    """One live row in one layer's launch: its state read once and written
    once, and beside it what the kernel is handed and hands back in float32 —
    q and k (heads x key dim each), v, the decay and the write strength along
    the value lanes, and the output (heads x value dim each)."""
    h = cfg["linear_num_value_heads"]
    return STATE_BYTES * (2 * state_elements(cfg)
                          + 2 * h * cfg["linear_key_head_dim"]
                          + 4 * h * cfg["linear_value_head_dim"])


def row_flops(cfg: dict) -> int:
    return FLOPS_A_STATE_ELEMENT * state_elements(cfg)


def least_seconds(cfg: dict, row_launches: float, peaks: dict) -> tuple:
    """(seconds the chip needs at the least, which peak bounds it) for
    `row_launches` (live row, layer) updates."""
    by_bytes = row_launches * row_bytes(cfg) / peaks["hbm_bytes_per_s"]
    by_flops = row_launches * row_flops(cfg) / peaks["flops_bf16"]
    return max(by_bytes, by_flops), ("hbm" if by_bytes >= by_flops
                                     else "flops")


def time_and_launches(trace: dict) -> tuple:
    t = sum(s for name, s in trace["op_self_s"].items()
            if LIN_KERNEL.search(name))
    n = sum(c for name, c in trace["op_count"].items()
            if LIN_KERNEL.search(name))
    return t, n
