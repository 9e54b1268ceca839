"""What Kimi Delta Attention's two rule kernels and the NoPE latent layers
beside them cost at the least, and the names they have on the device trace.
Data and arithmetic for the `kda_*` and `mla_nope_*` metric files beside it;
everything is computed from the configuration file's keys — the KDA heads
from `linear_attn_config` (`num_heads` heads of `head_dim`, keys and values),
never from `num_hidden_layers`; a latent head's width from `qk_nope_head_dim`
+ `qk_rope_head_dim`, never from `head_dim`, which this family publishes as
hidden_size / heads and no module reads — and the counters the program's step
samples carry (`lin_step_rows`, `lin_span_tokens`, `lin_chunk_pairs`, ...,
`mla_pairs`, `mla_ctx_rows`; a program without them gives the readers nothing
to read).

The kernels (Mosaic custom calls under their Pallas functions' names), once a
KDA layer a forward pass each:

  `gated_delta_step_pallas` — the one-token form at its vector reading: every
      LIVE row's [d, H d] float32 state read once and written once in place,
      the decay a key channel. Rows that are parked cost it nothing and are
      credited nothing.
  `chunk_rule_pallas` — the (row, 64-token window) pairs of longer spans, a
      row's state in VMEM across its windows.

Each roofline counts THE LEAST ANY IMPLEMENTATION OF THE SAME MATHEMATICS
NEEDS, never a kernel's own tiling, so that a later kernel is read against
the same work and nothing reads over 100. The step: a live row's state in and
out, q, k and g (H x d each), v and o (H x d), beta (H), float32, at the HBM
peak; or 7 FLOPs a state element (the decay, S^T k, the rank-one update, S^T
q) at the bf16 peak. The pairs: a span row's state OUT once (and IN once
unless the span opens it: counted for the rows the step's resets cannot
cover), every span token's q, k, g, v, beta in and o out once, at the HBM
peak; or the token-serial recurrence's 7 FLOPs a state element a span token
at the bf16 peak — the chunked form's own contractions (float32 at the
highest precision, several MXU passes) are the kernel's choice, not the
mathematics'. The larger of the two times.
"""
import re

STEP_KERNEL = re.compile(r"gated_delta_\w*pallas")
CHUNK_KERNEL = re.compile(r"chunk_rule_\w*pallas")
FIELDS = ("lin_state_resets", "lin_state_carried", "lin_step_rows",
          "lin_span_tokens", "lin_chunk_pairs")
STATE_BYTES = 4  # float32, as the configuration file states
FLOPS_A_STATE_ELEMENT = 7
CACHE_BYTES = 2  # bf16 latent rows


def has_counters(samples, fields=FIELDS) -> bool:
    return bool(samples) and all(f in s for s in samples for f in fields)


def heads(cfg: dict) -> tuple:
    """(KDA heads, head size) from `linear_attn_config`; None without it."""
    group = cfg.get("linear_attn_config")
    if not group:
        return None
    return int(group["num_heads"]), int(group["head_dim"])


def state_elements(cfg: dict) -> int:
    h, d = heads(cfg)
    return h * d * d


def token_bytes(cfg: dict) -> int:
    """One token of one row in one layer, beside the state: q, k, g (a key
    channel each), v and the output (a value lane each), beta a head."""
    h, d = heads(cfg)
    return STATE_BYTES * (5 * h * d + h)


def time_and_launches(trace: dict, kernel) -> tuple:
    t = sum(s for name, s in trace["op_self_s"].items() if kernel.search(name))
    n = sum(c for name, c in trace["op_count"].items() if kernel.search(name))
    return t, n


def bounded(by_bytes: float, by_flops: float) -> tuple:
    return max(by_bytes, by_flops), ("hbm" if by_bytes >= by_flops
                                     else "flops")


def step_least_seconds(cfg: dict, row_launches: float, peaks: dict) -> tuple:
    """...for `row_launches` (live row, layer) one-token updates."""
    n = state_elements(cfg)
    return bounded(
        row_launches * (2 * STATE_BYTES * n + token_bytes(cfg))
        / peaks["hbm_bytes_per_s"],
        row_launches * FLOPS_A_STATE_ELEMENT * n / peaks["flops_bf16"])


def span_rows(sample: dict) -> tuple:
    """(rows of a RAGGED step with a span of more than one token, of them
    those whose state must be read: all but as many as the step opened)."""
    if sample.get("mode") != "ragged":
        return 0, 0
    rows = sample["lin_state_resets"] + sample["lin_state_carried"] \
        - sample["lin_step_rows"]
    return rows, max(rows - sample["lin_state_resets"], 0)


def chunk_least_seconds(cfg: dict, rows_out: float, rows_in: float,
                        tokens: float, peaks: dict) -> tuple:
    """...for a layer's launches that carried `rows_out` span rows' states
    out, `rows_in` of them in, over `tokens` span tokens."""
    n = state_elements(cfg)
    return bounded(
        (STATE_BYTES * n * (rows_out + rows_in) + tokens * token_bytes(cfg))
        / peaks["hbm_bytes_per_s"],
        tokens * FLOPS_A_STATE_ELEMENT * n / peaks["flops_bf16"])


def latent_pair_flops(cfg: dict) -> int:
    """A causal (query, position) pair of the expanded form: q . k over
    qk_nope + qk_rope lanes and p . v over v_head_dim, a head."""
    return cfg["num_attention_heads"] * 2 * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"])


def latent_row_bytes(cfg: dict) -> int:
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * CACHE_BYTES
