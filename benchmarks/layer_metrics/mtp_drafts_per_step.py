"""Drafts verified a forward pass (`mtp_drafts` over the window's passes):
one a greedy decode row while the mechanism is on, so it stands near the
decode rows a pass — and falls when a change proposes fewer drafts, which
would "win" tokens/s at an acceptance of zero by switching the mechanism
off. None without the counters."""
from benchmarks.layer_metrics import _mtp


def read(ctx):
    return _mtp.per_pass(ctx.steps, "mtp_drafts")
