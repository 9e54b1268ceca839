"""Cached positions attention saw as a share of those the indexer scored, over
the window's steps: sum of `dsa_selected_tokens` over sum of `dsa_ctx_tokens`.
100 on traffic whose contexts never pass index_topk (the selection takes
everything), index_topk / mean context beyond. None for a program whose
samples carry no latent-attention counters."""
from benchmarks.layer_metrics import _mla


def read(ctx):
    if not _mla.has_counters(ctx.steps):
        return None
    scored = sum(s["dsa_ctx_tokens"] for s in ctx.steps)
    return 100.0 * sum(s["dsa_selected_tokens"] for s in ctx.steps) / scored \
        if scored else None
