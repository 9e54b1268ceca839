"""Pages given back a forward pass for rejected drafts
(`spec_rollback_pages` over the window's passes). With ONE draft a row the
rejected position is the next token's own, which the row keeps: it reads 0
until a proposer drafts more than one token a row. None without the
counters."""
from benchmarks.layer_metrics import _mtp


def read(ctx):
    return _mtp.per_pass(ctx.steps, "spec_rollback_pages")
