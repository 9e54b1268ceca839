"""What the stored layout of the K/V cache costs over the least the model's
head shapes need: 100 x (stored - least) / least, over one cached position of
one full layer (in the paged pool: `attn_row_bytes` on the window's step
samples) and of one window layer (in the rings: `swa_row_bytes`) together,
against `_qkv.row_bytes` of each kind (kv heads x (head_dim + v_head_dim) x 2
B). 0 for a layout that holds no lane the model lacks; a key head of 192
lanes padded to 256 would read 20. None without the counters (a program
before PR 65, a model whose K and V rows are alike) or the file's keys."""
from benchmarks.layer_metrics import _qkv


def read(ctx):
    cfg = ctx.cell.config
    rows = [s for s in ctx.steps if all(f in s for f in _qkv.ROW_FIELDS)]
    if not rows or not _qkv.has_keys(cfg):
        return None
    stored = [rows[-1][f] for f in _qkv.ROW_FIELDS]
    least = [_qkv.row_bytes(cfg, kind) for kind in (_qkv.FULL, _qkv.WINDOW)]
    ctx.say("kv_row_padding", attn_row_bytes=stored[0],
            swa_row_bytes=stored[1], attn_row_least=least[0],
            swa_row_least=least[1])
    return 100.0 * (sum(stored) - sum(least)) / sum(least)
