"""The FULL layers' attention launches' share of their roofline where key and
value heads differ in width (`_qkv.py`; MiMo-V2-Flash): the least seconds the
chip needs for the capture's causal (query token, cached position) pairs —
heads x (head_dim + v_head_dim) x 2 FLOPs each at the bf16 peak, or each
span's cached K and V rows once, num_key_value_heads x (head_dim + v_head_dim)
x 2 B, at the HBM peak if that is more — over the seconds the ragged and the
decode kernel's launches took on the device trace (`_attn.ATTEND`). A pass's
counts (`attn_pairs`, `attn_ctx_rows`: a layer's worth) times the launches
the trace holds. 0 where the trace holds no such op; None without the file's
keys, the counters or peaks (a rehearsal on the CPU)."""
from benchmarks.layer_metrics import _attn, _qkv


def read(ctx):
    return _qkv.roofline(ctx, _qkv.FULL, _attn,
                         ("attn_pairs", "attn_ctx_rows"), "attn_qkv_roofline")
