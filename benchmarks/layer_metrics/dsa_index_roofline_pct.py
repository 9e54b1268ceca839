"""The lightning indexer's share of its roofline: the least seconds the chip
needs for the capture's SCORED (query, position) pairs (_mla.least_seconds:
index heads x index_head_dim x 2 FLOPs a pair at the bf16 peak, or the
one-token rows' index keys at the HBM peak if that is more) over the seconds
`dsa_index_pallas` took on the device trace (the selection's kernel is not
counted: it is no part of this mathematics' floor, and its time is in
dsa_index_share_pct). 0 where the trace holds no such op; None without the
counters or peaks."""
from benchmarks.layer_metrics import _mla


def read(ctx):
    cfg = ctx.cell.config
    return _mla.roofline(ctx, _mla.INDEX, "dsa_ctx_tokens",
                         "dsa_step_ctx_tokens", _mla.index_pair_flops(cfg),
                         _mla.index_key_bytes(cfg), "dsa_index_roofline")
