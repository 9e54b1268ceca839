"""Names the device trace gives to the program's kernels and collectives
today (there is no named scope in the program yet: the Mosaic custom calls
carry the Pallas function's name). Data for the metric files beside it."""
import re

ATTENTION = re.compile(r"ragged_paged_attention|paged_decode_attention"
                       r"|paged_attention|tpu_custom_call|mosaic", re.I)
COLLECTIVE = re.compile(r"all-reduce|all_reduce|all-gather|all_gather"
                        r"|reduce-scatter|reduce_scatter|collective-permute"
                        r"|all-to-all", re.I)


def time_of(trace: dict, pattern) -> float:
    return sum(s for name, s in trace["op_self_s"].items()
               if pattern.search(name))


def forward_passes(trace: dict, layers: int) -> float:
    """Forward passes the device ran inside the traced window: every layer
    that has attention (`layers` of them, lib/arch.py) launches one attention
    kernel a pass, so launches / layers; 0 for a stack with no such layer."""
    return sum(n for name, n in trace["op_count"].items()
               if ATTENTION.search(name)) / layers if layers else 0.0
