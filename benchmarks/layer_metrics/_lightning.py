"""What the lightning recurrence's one-token kernel of a configuration with
lightning linear-attention layers costs at the least, and the name it has on
the device trace. Data and arithmetic for the `lightning_*` metric files beside
it, as `_ssm.py` reckons a state-space mixer's; everything is computed from
the configuration file's keys and the counters the program's step samples
carry (`lightning_step_rows`, ...: telemetry of PR 60; a program without them
gives the readers nothing to read).

The kernel is the state-space recurrence's (`ssd_step_pallas`: S = lambda S +
k v^T, o = S^T q is its `plain` form with a write strength of 1), once a
lightning layer a forward pass; it updates, in place, the state row of every
LIVE row of the pass. Rows that are parked cost it nothing and are credited
nothing here. Spans longer than one token take the chunked form (jnp
contractions in XLA fusions: no kernel of its own to name).
"""
import re

KERNEL = re.compile(r"ssd_step_\w*pallas")
FIELDS = ("lightning_state_resets", "lightning_state_carried",
          "lightning_step_rows", "lightning_span_tokens")
STATE_BYTES = 4  # float32, as the configuration file states
FLOPS_A_STATE_ELEMENT = 5  # decay; the rank-one update; S^T q


def has_counters(samples) -> bool:
    return bool(samples) and all(f in s for s in samples for f in FIELDS)


def state_elements(cfg: dict) -> int:
    """One row's state in one layer: heads x head dim x head dim."""
    return cfg["lightning_nh"] * cfg["lightning_head_dim"] ** 2


def row_bytes(cfg: dict) -> int:
    """One live row in one layer's launch: its state read once and written
    once, and beside it what the kernel is handed and hands back in float32 —
    q and k a head, v, the decay along the lanes and the output (heads x head
    dim each), the log decay a head."""
    h, d = cfg["lightning_nh"], cfg["lightning_head_dim"]
    return STATE_BYTES * (2 * state_elements(cfg) + 5 * h * d + h)


def least_seconds(cfg: dict, row_launches: float, peaks: dict) -> tuple:
    by_bytes = row_launches * row_bytes(cfg) / peaks["hbm_bytes_per_s"]
    by_flops = row_launches * FLOPS_A_STATE_ELEMENT * state_elements(cfg) \
        / peaks["flops_bf16"]
    return max(by_bytes, by_flops), ("hbm" if by_bytes >= by_flops
                                     else "flops")


def time_and_launches(trace: dict) -> tuple:
    t = sum(s for name, s in trace["op_self_s"].items()
            if KERNEL.search(name))
    n = sum(c for name, c in trace["op_count"].items()
            if KERNEL.search(name))
    return t, n
