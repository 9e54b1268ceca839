"""Share of their contexts' blocks that the sparse layers' walks cover, over
all sparse-layer queries past `sparse_dense_len` of the window's step samples:
`bsa_blocks_walked_*` over `bsa_blocks_in_context_*`. A one-token row's walk
follows its block list (walked = kept: 25-50 at 8-16 k of context under 64
blocks of 64); a longer span is served under a block mask over the row's whole
context (walked = in context: 100) — the figure says how much of the traffic
the list serves in fact. None without the counters."""
from benchmarks.layer_metrics import _bsa


def read(ctx):
    return _bsa.counter_pct(ctx, "bsa_blocks_walked", "bsa_blocks_in_context",
                            "bsa_blocks_walked")
