"""The WINDOW layers' attention launches' share of their roofline where key
and value heads differ in width and a window layer has kv heads of its own
(`_qkv.py`; MiMo-V2-Flash): the least seconds the chip needs for the
capture's IN-WINDOW (query token, cached position) pairs — heads x (head_dim +
v_head_dim) x 2 FLOPs each at the bf16 peak, or the cached K and V rows inside
each span's windows once, swa_num_key_value_heads x (head_dim + v_head_dim) x
2 B, at the HBM peak if that is more — over the seconds the window launches
took on the device trace (`_swa.ATTEND`; the sink's term is inside them). A
pass's counts (`swa_pairs`, `swa_ctx_rows`: a window layer's worth) times the
launches the trace holds. 0 where the trace holds no such op; None without
the file's keys, the counters or peaks (a rehearsal on the CPU)."""
from benchmarks.layer_metrics import _qkv, _swa


def read(ctx):
    return _qkv.roofline(ctx, _qkv.WINDOW, _swa,
                         ("swa_pairs", "swa_ctx_rows"), "swa_qkv_roofline")
