"""(layer, expert) pairs that got at least one token, as a share of all pairs
(layers that have experts x experts, lib/arch.py), mean over the window's
forward passes: what sets the weight-streaming floor of a pass. From the step
samples' `moe_pairs_hit`."""
from benchmarks.layer_metrics import _moe
from benchmarks.lib import arch, steps


def read(ctx):
    if not _moe.has_counters(ctx.steps):
        return None
    cfg = ctx.cell.config
    pairs = arch.expert_layers(cfg) * arch.num_experts(cfg)
    return 100.0 * sum(s["moe_pairs_hit"] for s in ctx.steps) \
        / (pairs * steps.total_passes(ctx.steps))
