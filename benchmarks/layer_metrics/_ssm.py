"""What the state-space recurrence's one-token kernel of a configuration with
Mamba-2 mixers costs at the least, and the name it has on the device trace.
Data and arithmetic for the `ssm_*` metric files beside it; everything is
computed from the configuration file's keys and the counters the program's
step samples carry (`ssm_step_rows`, ...: telemetry of PR 54; a program
without them gives the readers nothing to read).

The kernel (`ssd_step_pallas`: the Mosaic custom call carries the Pallas
function's name, which is not the delta rule's `gated_delta_*`) runs once a
layer a forward pass and updates, in place, the state row of every LIVE row of
the pass — a decode row, a fused scan's active slot. Rows that are parked cost
it nothing and are credited nothing here: a kernel that streamed them would
read lower, never over 100. Spans longer than one token do not pass through it
(they take the chunked form, jnp contractions in XLA fusions: no kernel of its
own to name).
"""
import re

SSM_KERNEL = re.compile(r"ssd_step_\w*pallas")
FIELDS = ("ssm_state_resets", "ssm_state_carried", "ssm_step_rows",
          "ssm_span_tokens")
STATE_BYTES = 4  # float32, as the configuration file states
FLOPS_A_STATE_ELEMENT = 5  # decay; the rank-one update; S C


def has_counters(samples) -> bool:
    return bool(samples) and all(f in s for s in samples for f in FIELDS)


def state_elements(cfg: dict) -> int:
    """One row's state in one layer: heads x head dim x state dim."""
    return cfg["mamba_n_heads"] * cfg["mamba_d_head"] * cfg["mamba_d_state"]


def row_bytes(cfg: dict) -> int:
    """One live row in one layer's launch: its state read once and written
    once, and beside it what the kernel is handed and hands back in float32 —
    C and B a head (heads x state dim each), dt x, the decay along the lanes
    and the output (heads x head dim each), the log decay a head."""
    h = cfg["mamba_n_heads"]
    return STATE_BYTES * (2 * state_elements(cfg)
                          + 2 * h * cfg["mamba_d_state"]
                          + 3 * h * cfg["mamba_d_head"] + h)


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
            if SSM_KERNEL.search(name))
    n = sum(c for name, c in trace["op_count"].items()
            if SSM_KERNEL.search(name))
    return t, n
