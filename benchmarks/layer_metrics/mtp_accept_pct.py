"""Drafts of the model's prediction module that the trunk's own greedy choice
confirmed, as a share of those verified, over the window's step samples
(`mtp_accepted` / `mtp_drafts`). The WEIGHTS own this number, not the system:
with seeded random weights a draft is right about once in `vocab_size` tries,
so it reads ~0 and the cell's tokens/s is the floor of a self-drafting
deployment (PERF.md section 4); a trained module of this family reads 85-90.
None without the counters or where no draft was verified."""
from benchmarks.layer_metrics import _mtp


def read(ctx):
    taken = _mtp.carrying(ctx.steps)
    drafts = sum(int(s["mtp_drafts"]) for s in taken)
    if not drafts:
        return None
    return 100.0 * sum(int(s["mtp_accepted"]) for s in taken) / drafts
