"""What the model's own prediction module did as the `--spec` proposer, for
the `mtp_*` / `spec_*` metric files beside this one: the counters a step
sample of a program that serves the module carries (telemetry of PR 42) —
`mtp_drafts`, the drafts a pass verified (one a greedy decode row);
`mtp_accepted`, those of them the trunk's own choice confirmed;
`mtp_rows`, the stream positions the module ran over to leave the next
drafts; `spec_rollback_pages`, the pages given back for rejected drafts. A
program without them (the parent, or a runtime whose proposer is n-gram
lookup) gives the readers nothing to read: unknown is not zero."""
from benchmarks.lib import steps

FIELDS = ("mtp_drafts", "mtp_accepted", "mtp_rows", "spec_rollback_pages")


def carrying(samples) -> list:
    """The samples that say what the module did."""
    return [s for s in samples or () if all(f in s for f in FIELDS)]


def per_pass(samples, field: str):
    """Sum of `field` over the samples that carry the counters, over their
    forward passes; None where none does."""
    taken = carrying(samples)
    if not taken:
        return None
    return sum(int(s[field]) for s in taken) / steps.total_passes(taken)
