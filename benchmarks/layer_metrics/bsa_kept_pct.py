"""Share of their contexts' blocks that the sparse layers' queries KEEP — what
the mathematics asks a walk to read, the floor of `bsa_blocks_walked_pct` —
over all sparse-layer queries past `sparse_dense_len` of the window's step
samples: `bsa_blocks_kept_*` over `bsa_blocks_in_context_*`. None without the
counters."""
from benchmarks.layer_metrics import _bsa


def read(ctx):
    return _bsa.counter_pct(ctx, "bsa_blocks_kept", "bsa_blocks_in_context",
                            "bsa_kept")
