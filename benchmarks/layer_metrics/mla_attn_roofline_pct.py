"""The sparse latent attention kernel's share of its roofline: the least
seconds the chip needs for the capture's SELECTED (query, position) pairs
(_mla.least_seconds: their FLOPs in the expanded form at the bf16 peak, or the
one-token rows' selected latent rows at the HBM peak if that is more) over the
seconds `mla_sparse_paged_attention_pallas` took on the device trace. Both
sides cover the same passes, as lin_step_roofline_pct.py's do. A kernel that
multiplies positions the selection dropped reads low here, never over 100.
0 where the trace holds no such op; None without the counters or peaks."""
from benchmarks.layer_metrics import _mla


def read(ctx):
    cfg = ctx.cell.config
    return _mla.roofline(ctx, _mla.ATTEND, "dsa_selected_tokens",
                         "dsa_step_selected_tokens",
                         _mla.attend_pair_flops(cfg),
                         _mla.latent_row_bytes(cfg), "mla_attn_roofline")
