"""The block selection kernel's share of its roofline: the least seconds the
chip needs for its launches (_bsa.select_block: a one-token query's context's
pooled keys read once a kv group at the HBM peak, or heads x head_dim x 2 FLOPs
a (head, pooled key) at the bf16 peak if that is more) over the seconds they
took on the device trace. The work is `bsa_blocks_in_context_step` of the
samples taken during the capture, a pass, times the trace's launches."""
from benchmarks.layer_metrics import _bsa


def read(ctx):
    return _bsa.roofline(ctx, _bsa.SELECT, "bsa_blocks_in_context_step",
                         _bsa.select_block(ctx.cell.config),
                         "bsa_select_roofline")
