"""What attention costs at the least where a KEY head and a VALUE head differ
in width and the two attention kinds differ in kv heads (MiMo-V2-Flash: 64
query heads of 192 q . k lanes and 128 p . v lanes; 4 kv heads in a
`full_attention` layer, `swa_num_key_value_heads` 8 in a `sliding_attention`
one). `_attn.py` and `_swa.py` count heads x head_dim x 4 FLOPs a pair and 2 x
num_key_value_heads x head_dim lanes a cached row — a fifth too many FLOPs and
the wrong kv heads for such a file — so its cells are read here instead, by
readers of their own (`attn_qkv_roofline_pct.py`, `swa_qkv_roofline_pct.py`,
`kv_row_padding_pct.py`). Data and arithmetic only; everything is computed
from the configuration file's keys and the counters the program's step
samples carry (`attn_pairs`, `attn_ctx_rows`; `swa_pairs`, `swa_ctx_rows`;
`attn_row_bytes`, `swa_row_bytes`: telemetry of PR 65 — a program without
them, or a file without the keys, gives the readers nothing to read).

The launches are `_attn.ATTEND`'s (the full layers') and `_swa.ATTEND`'s (the
window layers'), as for every other cell: the kernels keep their names.

The roofline counts THE LEAST ANY IMPLEMENTATION OF THE SAME MATHEMATICS
NEEDS, never these kernels' own tiling (whole 128-token blocks, masked
positions, padded rows, a key head's rest contracted over a whole lane tile):
every (query token, cached position) pair costs heads x (head_dim +
v_head_dim) x 2 FLOPs — q . k over the key head's lanes and p . v over the
value head's, a multiply and an add each — at the bf16 peak; and every span
reads each cached position it attends once, kv heads x (head_dim + v_head_dim)
x 2 B (its K row and its V row, no lane more), at the HBM peak. The larger of
the two times. A pass's counts times the trace's launches is the trace's
work."""
FULL, WINDOW = "full", "window"
CACHE_BYTES = 2  # bf16, as the configuration files state
KEYS = ("num_attention_heads", "num_key_value_heads",
        "swa_num_key_value_heads", "head_dim", "v_head_dim")
ROW_FIELDS = ("attn_row_bytes", "swa_row_bytes")


def has_keys(cfg: dict) -> bool:
    return all(cfg.get(k) for k in KEYS)


def kv_heads(cfg: dict, kind: str) -> int:
    return cfg["swa_num_key_value_heads" if kind == WINDOW
               else "num_key_value_heads"]


def pair_flops(cfg: dict) -> int:
    return cfg["num_attention_heads"] * (cfg["head_dim"]
                                         + cfg["v_head_dim"]) * 2


def row_bytes(cfg: dict, kind: str) -> int:
    """One cached position of one layer of `kind` at the least: its K row
    and its V row, the lanes the model has."""
    return kv_heads(cfg, kind) * (cfg["head_dim"] + cfg["v_head_dim"]) \
        * CACHE_BYTES


def least_seconds(cfg: dict, kind: str, pairs: float, ctx_rows: float,
                  peaks: dict) -> tuple:
    """(seconds the chip needs at the least, which peak bounds it) for
    `pairs` attended pairs whose spans read `ctx_rows` cached positions."""
    by_flops = pairs * pair_flops(cfg) / peaks["flops_bf16"]
    by_bytes = ctx_rows * row_bytes(cfg, kind) / peaks["hbm_bytes_per_s"]
    return max(by_flops, by_bytes), ("hbm" if by_bytes >= by_flops
                                     else "flops")


def roofline(ctx, kind: str, timed, fields: tuple, say: str):
    """A kind's launches' share of their roofline: `timed` = the sibling
    helper whose `time_and_launches` names them, `fields` = the (pairs, rows)
    counters of a launch. 0 where the trace holds no such op; None without
    the file's keys, the counters or peaks."""
    cfg = ctx.cell.config
    if not ctx.trace or not has_keys(cfg) \
            or not timed.has_counters(ctx.trace_steps):
        return None
    seconds, launches = timed.time_and_launches(ctx.trace)
    if not launches:
        return 0.0
    if not ctx.peaks:
        return None
    from benchmarks.lib import steps

    sampled = steps.total_passes(ctx.trace_steps)
    pairs, rows = (sum(s[f] for s in ctx.trace_steps) / sampled
                   for f in fields)
    least, bound = least_seconds(cfg, kind, pairs * launches,
                                 rows * launches, ctx.peaks)
    ctx.say(say, launches_in_trace=launches, passes_sampled=sampled,
            pairs_a_launch=pairs, ctx_rows_a_launch=rows,
            pair_flops=pair_flops(cfg), row_bytes=row_bytes(cfg, kind),
            least_s=least, bound_by=bound, measured_s=seconds)
    return 100.0 * least / seconds
