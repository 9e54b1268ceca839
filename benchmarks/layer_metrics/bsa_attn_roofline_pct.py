"""The block-sparse walk's share of its roofline: the least seconds the chip
needs for its launches (_bsa.walk_block: every kept block's K and V rows once
a kv head at the HBM peak, or heads x head_dim x 4 FLOPs a kept key at the
bf16 peak if that is more) over the seconds they took on the device trace.
The work is `bsa_blocks_kept_step` of the samples taken during the capture, a
pass, times the trace's launches (one a sparse layer a pass). As served the
walk moves BOTH kv heads' lanes of a kept page for one head's use, so it
cannot read above 50."""
from benchmarks.layer_metrics import _bsa


def read(ctx):
    return _bsa.roofline(ctx, _bsa.WALK, "bsa_blocks_kept_step",
                         _bsa.walk_block(ctx.cell.config),
                         "bsa_attn_roofline")
