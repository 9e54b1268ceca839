"""A step's work account: what a launched step's layers did, counted from
its composition alone — each row's span length, its context at the span's
end and whether its logits are sampled — onto the step's sample
(`telemetry/stepprof.py`) and the /metrics series.

ONE table, `KINDS`: a row a kind of layer — whether a configuration has it,
the sample's fields, the series that count the same (a field's definition is
its series' help, `telemetry/schema.py`; the count functions say what that
leaves out) and the pure function that gives the counts. A `StepWork` binds
the rows of the kinds its configuration has, once, and `note` walks them
once a launch. A new kind of layer is one more row here and no line of
`engine/engine.py`.
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional, Tuple

import numpy as np

from ollamamq_tpu.config import (ATTENTION, CONV, EXPERTS, LINEAR, MAMBA,
                                 PARALLEL, SPARSE, WINDOW, ModelConfig)
from ollamamq_tpu.ops.attention import ring_first_page
from ollamamq_tpu.ops.gated_delta import CHUNK
from ollamamq_tpu.telemetry import schema as tm


class KernelCounts(NamedTuple):
    """The Pallas kernels' own tests of how they serve a stream, each
    `(tokens, stream_len) -> count`: the ragged kernel's (tokens served a
    whole stretch at a time) and, of a latent model, the latent attention
    kernel's (tokens attended in the expanded form; rows a layer then takes
    through the absorbed form's contractions); and, of a model whose delta
    rule's shape the window solve's kernel takes, that kernel's (the windows
    it solves)."""
    tall_tokens: Callable
    wide_tokens: Optional[Callable] = None
    absorbed_rows: Optional[Callable] = None
    solved_windows: Optional[Callable] = None


def kernel_counts(cfg: ModelConfig, attn_impl: str) -> Optional[KernelCounts]:
    """`cfg`'s on the Pallas path; None without the kernels."""
    if attn_impl != "pallas":
        return None
    from ollamamq_tpu.ops.pallas import chunk_rule, chunk_solve
    from ollamamq_tpu.ops.pallas.kv_contract import tall_tokens
    counts = KernelCounts(tall_tokens)
    rule = (cfg.linear_num_value_heads, cfg.linear_key_head_dim,
            cfg.linear_value_head_dim, False, cfg.kda)
    if KINDS["lin"].present(cfg) and chunk_rule.blocks(*rule) \
            and chunk_solve.blocks(*rule):  # as `gated_delta.ragged` asks
        counts = counts._replace(solved_windows=chunk_solve.solved_windows)
    if not cfg.kv_lora_rank:
        return counts
    from ollamamq_tpu.ops.pallas.mla_attention import (absorbed_rows,
                                                       wide_tokens)
    return counts._replace(**{
        fn.__name__: functools.partial(
            fn, heads=cfg.num_heads, lanes=cfg.latent_lanes,
            rank=cfg.kv_lora_rank, nope=cfg.qk_nope_head_dim,
            v=cfg.v_head_dim)
        for fn in (wide_tokens, absorbed_rows)})


class Step(NamedTuple):
    """A launched step's composition. `tokens`, `kv`, `emits`: each row's
    span length, its context at the span's end and whether its logits are
    sampled — a ragged step's spans in stream order (`stream_len`: the rung
    the stream is padded to; `opened`: the rows whose span is their
    request's first), or with `scan` a fused scan's active slots with its
    passes as tokens, every one sampled."""
    tokens: list
    kv: list
    emits: Optional[list]
    scan: bool
    stream_len: int
    opened: int
    kernels: Optional[KernelCounts]
    kv_itemsize: int = 2  # bytes an element of pool and rings (bfloat16)


def _pairs(n, kv):
    """Causal (query token, cached position) pairs of spans of `n` tokens
    ending at context `kv` (a token at p attends p + 1): Σ kv-n+1 .. kv."""
    return n * (2 * kv - n + 1) // 2


def slot_state_counts(cfg, page_size, s: Step) -> tuple:
    """A step's use of the per-slot state: rows whose slot it opened at
    zero (a request's first span), rows that read the state an earlier step
    left (a later chunk, a decode row; a scan's active slots), then how the
    recurrence ran: row-passes through the one-token form (1-token rows; a
    scan's active slots x its passes), tokens of longer spans, through the
    chunked form, and the (row, window) pairs that form runs over them, a
    layer's worth — a span of n > 1 tokens from stream token s touches
    windows s // CHUNK .. (s + n - 1) // CHUNK (ops/gated_delta.ragged: on
    the chip, the programs a head block of `chunk_rule_pallas`). A kind
    keeps as many of the five as it has fields: conv layers the first two."""
    carried = len(s.tokens) - s.opened
    if s.scan:
        return s.opened, carried, sum(s.tokens), 0, 0
    n = np.asarray(s.tokens, np.int64)
    at = np.cumsum(n) - n  # each row's first stream token
    pairs = ((at + n - 1) // CHUNK - at // CHUNK + 1)[n > 1]
    return (s.opened, carried, int((n == 1).sum()), int(n[n > 1].sum()),
            int(pairs.sum()))


def rule_counts(cfg, page_size, s: Step) -> tuple:
    """`slot_state_counts`, and behind them the windows the delta rule's
    chunked form SOLVED, a layer's worth: with the solve's kernel those a
    span touches (`chunk_solve.solved_windows`), else every window of the
    padded stream, a span in it or not (`gated_delta._prepare` solves them
    all at once); a scan has none."""
    if s.scan:
        windows = 0
    elif s.kernels is not None and s.kernels.solved_windows:
        windows = s.kernels.solved_windows(s.tokens, s.stream_len)
    else:
        windows = -(-max(s.stream_len, sum(s.tokens)) // CHUNK)
    return slot_state_counts(cfg, page_size, s) + (windows,)


def _expanded_counts(s: Step) -> tuple:
    """The latent kernel's own counts of the expanded form, a layer's worth
    (0 for a scan and on the jnp path): the tokens it attends so, and the
    rows a layer takes through the absorbed form's contractions."""
    if s.kernels is None or s.scan:
        return 0, 0
    return (s.kernels.wide_tokens(s.tokens, s.stream_len),
            s.kernels.absorbed_rows(s.tokens, s.stream_len))


def latent_counts(cfg, page_size, s: Step) -> tuple:
    """Latent attention under the indexer, a layer's worth: the query
    tokens, the cached positions the indexer scored for them (a token at
    position p scores p + 1) and those attention then saw (min(p + 1,
    index_topk)); `dsa_step_*`: the part of the two that ONE-TOKEN rows
    account for (a decode row, a scan's pass: rows that share their cached
    positions with no other query of the launch); then `_expanded_counts`."""
    counts = np.zeros(5, np.int64)
    for n, kv in zip(s.tokens, s.kv):
        ctx = np.arange(kv - n + 1, kv + 1)
        both = (int(ctx.sum()), int(np.minimum(ctx, cfg.index_topk).sum()))
        counts[:3] += (n,) + both
        if s.scan or n == 1:
            counts[3:5] += both
    return tuple(counts.tolist()) + _expanded_counts(s)


def dense_latent_counts(cfg, page_size, s: Step) -> tuple:
    """Latent attention with NO indexer (every cached position is attended:
    the `dsa_*` fields have no honest value there), a launch's worth — the
    trunk's layers and the prediction module's block do the same: the query
    tokens, the causal pairs, and the cached rows a launch has to read at
    the least: each span's context once (a scan's pass: each slot's); then
    `_expanded_counts` (a trunk layer's: the module's launch expands
    nothing)."""
    n, kv = np.asarray(s.tokens, np.int64), np.asarray(s.kv, np.int64)
    pairs = _pairs(n, kv)
    return (int(n.sum()), int(pairs.sum()),
            int((pairs if s.scan else kv).sum())) + _expanded_counts(s)


def attn_counts(cfg, page_size, s: Step) -> tuple:
    """Plain (K and V pages, non-latent) attention, a layer's worth: the
    causal pairs, the cached rows the walks have to read at the least (each
    span's context once; a scan's pass: each slot's) and the kernel's own
    count of tall tokens (0 for a scan and without the kernel)."""
    n, kv = np.asarray(s.tokens, np.int64), np.asarray(s.kv, np.int64)
    pairs = _pairs(n, kv)
    tall = 0
    if s.kernels is not None and not s.scan:
        tall = s.kernels.tall_tokens(s.tokens, s.stream_len)
    return int(pairs.sum()), int((pairs if s.scan else kv).sum()), tall


def swa_counts(cfg, page_size, s: Step) -> tuple:
    """WINDOW attention, a window layer's worth (`attn_counts` stands for
    the FULL layers): the in-window pairs, the cached rows a launch has to
    read at the least (min(kv, n + sliding_window - 1) a span), the rows its
    walks DO cover — from the page its table starts at
    (ops/attention.py:ring_first_page, the table's own rule) to the span's
    end — and what a walk from position 0 would have covered."""
    w = cfg.sliding_window
    n, kv = np.asarray(s.tokens, np.int64), np.asarray(s.kv, np.int64)
    if s.scan:  # each pass is a span of one token at its own context
        k = int(n.max(initial=0))
        kv = (kv[:, None] - n[:, None] + 1 + np.arange(k)[None, :]
              )[np.arange(k)[None, :] < n[:, None]]
        n = np.ones_like(kv)
    # positions kv-n .. kv-1 attend min(p + 1, w): all w but the first
    # w - 1 positions of a sequence, which attend p + 1
    first = kv - n  # the span's first position
    short = np.clip(w - 1 - first, 0, n)  # its tokens at p < w - 1
    pairs = (n - short) * w + short * (2 * first + short + 1) // 2
    walk = kv - ring_first_page(kv, n, w, page_size) * page_size
    return tuple(int(a.sum()) for a in (
        pairs, np.minimum(kv, n + w - 1), walk, kv))


def row_bytes(cfg, page_size, s: Step) -> tuple:
    """Bytes ONE cached position of one full layer occupies in the pool and
    of one window layer in the rings, AS STORED (K row and V row: their
    lanes times the cache's element size) — a constant of the runtime, on
    every sample beside the rows the walks read (`attn_ctx_rows`,
    `swa_ctx_rows`), so that a reader can hold the stored layout to the
    least the model's head shapes need."""
    return tuple(stored_row_bytes(cfg, s.kv_itemsize).values())


def stored_row_bytes(cfg, itemsize: int) -> dict:
    """{attention kind: bytes a cached position of one layer of it as stored}
    — a full layer's in the pool, a window layer's in the rings."""
    return {kind: sum(lanes) * itemsize for kind, lanes in (
        (ATTENTION, cfg.kv_row_dims), (WINDOW, cfg.ring_row_dims))}


def bsa_counts(cfg, page_size, s: Step) -> tuple:
    """Block-sparse attention, a sparse layer's worth, each count split into
    one-token rows (decode rows of a ragged step, a scan's passes: their
    walks follow the block list) and the tokens of longer spans (served
    under a block mask over the row's context). Of the queries PAST
    `sparse_dense_len`: the blocks in their contexts, ceil(n / block); those
    they keep, min(topk, that) — what the mathematics asks; those the walks
    cover — the kept ones a one-token row, the context's a span token. Then
    the queries at or under it (every block kept, the plain walk), and the
    pooled-key rows the step's tokens completed (position p completes one
    where p + 1 - kernel is a whole number of strides)."""
    block, topk = cfg.sparse_block_size, cfg.sparse_topk
    kernel, stride = cfg.sparse_kernel_size, cfg.sparse_kernel_stride
    counts = np.zeros(8, np.int64)  # ctx, kept, walked: (step, span); dense;
    for n, kv in zip(s.tokens, s.kv):  # pooled rows
        ctx = np.arange(kv - n + 1, kv + 1)  # each token's context
        j = ctx - kernel
        counts[7] += int(((j >= 0) & (j % stride == 0)).sum())
        counts[6] += int((ctx <= cfg.sparse_dense_len).sum())
        blocks = -(-ctx[ctx > cfg.sparse_dense_len] // block)
        kept = np.minimum(blocks, topk)
        one = s.scan or n == 1
        counts[0 if one else 1] += int(blocks.sum())
        counts[2 if one else 3] += int(kept.sum())
        counts[4 if one else 5] += int((kept if one else blocks).sum())
    return tuple(counts.tolist())


def exit_counts(cfg, page_size, s: Step) -> tuple:
    """A stack with an `exit_layer`: the sampled rows, which pass the layers
    from there on; the cached rows ONE cross layer's walks read for them
    (each sampled row's context); the stream tokens that stopped below."""
    n, kv = np.asarray(s.tokens, np.int64), np.asarray(s.kv, np.int64)
    if s.scan:  # every pass of every slot is sampled, at its own context
        rows, ctx = int(n.sum()), int(_pairs(n, kv).sum())
    else:
        emits = np.asarray(s.emits, bool)
        rows, ctx = int(emits.sum()), int(kv[emits].sum())
    return rows, ctx, int(n.sum()) - rows


def mhc_counts(cfg, page_size, s: Step) -> tuple:
    """A residual path of several streams: the step's real tokens, each of
    which passes every application of the connection (a scan's: its active
    slots x its passes), and the applications a forward pass — two a layer
    (around the operator, around the FFN) and the read-out before the head,
    which runs over the sampled rows alone in a ragged step."""
    return int(sum(s.tokens)), 2 * cfg.num_layers + 1


class Kind(NamedTuple):
    present: Callable  # (ModelConfig) -> does a model have such layers?
    fields: Tuple[str, ...]  # what `note` writes onto a step's sample
    series: tuple  # ...and the /metrics family of each (None: none)
    counts: Callable  # (cfg, page_size, Step) -> a count a field


def _recurrent(kind: str, prefix: str, *series) -> Kind:
    """`slot_state_counts` under a recurrence's own names, a field a series
    given (None: a field without one): the first four, and `chunk_pairs`
    for a recurrence whose spans go through `gated_delta.ragged` (then
    `prepare_windows`: `rule_counts`)."""
    fields = ("state_resets", "state_carried", "step_rows", "span_tokens",
              "chunk_pairs", "prepare_windows")[:len(series)]
    return Kind(lambda cfg: cfg.count(kind),
                tuple(f"{prefix}_{f}" for f in fields), series,
                slot_state_counts)


# In the order a sample carries them.
KINDS = {
    "conv": Kind(lambda cfg: cfg.count(CONV),
                 ("conv_state_resets", "conv_state_carried"),
                 (tm.CONV_STATE_RESETS_TOTAL, tm.CONV_STATE_CARRIED_TOTAL),
                 slot_state_counts),
    "lin": _recurrent(
        LINEAR, "lin", tm.LIN_STATE_RESETS_TOTAL, tm.LIN_STATE_CARRIED_TOTAL,
        tm.LIN_STEP_ROWS_TOTAL, tm.LIN_SPAN_TOKENS_TOTAL,
        tm.LIN_CHUNK_PAIRS_TOTAL, tm.LIN_PREPARE_WINDOWS_TOTAL)._replace(
            present=lambda cfg: cfg.count(LINEAR) and not cfg.lightning_nh,
            counts=rule_counts),
    # (the linear kind's other reading: a model has one of the two)
    "lightning": _recurrent(
        LINEAR, "lightning", None, None, tm.LIGHTNING_STEP_ROWS_TOTAL,
        tm.LIGHTNING_SPAN_TOKENS_TOTAL, None)._replace(
            present=lambda cfg: cfg.count(LINEAR) and cfg.lightning_nh),
    "ssm": _recurrent(
        PARALLEL, "ssm", tm.SSM_STATE_RESETS_TOTAL,
        tm.SSM_STATE_CARRIED_TOTAL, tm.SSM_STEP_ROWS_TOTAL,
        tm.SSM_SPAN_TOKENS_TOTAL, None),
    "s6": _recurrent(
        MAMBA, "s6", tm.S6_STATE_RESETS_TOTAL, tm.S6_STATE_CARRIED_TOTAL,
        tm.S6_STEP_ROWS_TOTAL, tm.S6_SPAN_TOKENS_TOTAL),
    "latent": Kind(
        lambda cfg: cfg.kv_lora_rank and cfg.index_topk,
        ("mla_rows", "dsa_ctx_tokens", "dsa_selected_tokens",
         "dsa_step_ctx_tokens", "dsa_step_selected_tokens",
         "mla_wide_tokens", "mla_absorbed_rows"),
        (tm.MLA_ROWS_TOTAL, tm.DSA_CTX_TOKENS_TOTAL,
         tm.DSA_SELECTED_TOKENS_TOTAL, None, None,
         tm.MLA_WIDE_TOKENS_TOTAL, tm.MLA_ABSORBED_ROWS_TOTAL),
        latent_counts),
    "dense_latent": Kind(
        lambda cfg: cfg.kv_lora_rank and not cfg.index_topk,
        ("mla_rows", "mla_pairs", "mla_ctx_rows", "mla_wide_tokens",
         "mla_absorbed_rows"),
        (tm.MLA_ROWS_TOTAL, None, None, tm.MLA_WIDE_TOKENS_TOTAL,
         tm.MLA_ABSORBED_ROWS_TOTAL), dense_latent_counts),
    # Nothing for an encoder, a model with latent attention or one with
    # no attention layer.
    "attn": Kind(
        lambda cfg: not cfg.kv_lora_rank
        and cfg.paged_layers - cfg.count(SPARSE),
        ("attn_pairs", "attn_ctx_rows", "attn_tall_tokens"),
        (tm.ATTN_PAIRS_TOTAL, tm.ATTN_CTX_ROWS_TOTAL,
         tm.ATTN_TALL_TOKENS_TOTAL), attn_counts),
    "swa": Kind(
        lambda cfg: cfg.sliding_window,
        ("swa_pairs", "swa_ctx_rows", "swa_walk_rows", "swa_full_rows"),
        (tm.SWA_PAIRS_TOTAL, tm.SWA_CTX_ROWS_TOTAL, tm.SWA_WALK_ROWS_TOTAL,
         tm.SWA_FULL_ROWS_TOTAL), swa_counts),
    # (a model whose attention kinds differ in head shape: K and V rows of
    # their own widths, a full layer's other than a window layer's)
    "kv_rows": Kind(
        lambda cfg: cfg.per_kind_attention,
        ("attn_row_bytes", "swa_row_bytes"), (None, None), row_bytes),
    "bsa": Kind(
        lambda cfg: cfg.count(SPARSE),
        ("bsa_blocks_in_context_step", "bsa_blocks_in_context_span",
         "bsa_blocks_kept_step", "bsa_blocks_kept_span",
         "bsa_blocks_walked_step", "bsa_blocks_walked_span",
         "bsa_dense_queries", "bsa_pooled_rows_written"),
        (tm.BSA_BLOCKS_IN_CONTEXT_TOTAL, tm.BSA_BLOCKS_IN_CONTEXT_TOTAL,
         tm.BSA_BLOCKS_KEPT_TOTAL, tm.BSA_BLOCKS_KEPT_TOTAL,
         tm.BSA_BLOCKS_WALKED_TOTAL, tm.BSA_BLOCKS_WALKED_TOTAL, None, None),
        bsa_counts),
    "exit": Kind(lambda cfg: cfg.exit_layer,
                 ("xattn_rows", "xattn_ctx_rows", "exit_skipped_tokens"),
                 (tm.XATTN_ROWS_TOTAL, tm.XATTN_CTX_ROWS_TOTAL,
                  tm.EXIT_SKIPPED_TOKENS_TOTAL), exit_counts),
    "mhc": Kind(lambda cfg: cfg.streams, tm.MHC_SAMPLE_FIELDS, (None, None),
                mhc_counts),
}


class StepWork:
    """The rows of `KINDS` that `cfg` has, their series bound to `model`.
    `kernels`: `kernel_counts(cfg, attn_impl)`."""

    def __init__(self, cfg: ModelConfig, page_size: int, model: str,
                 kernels: Optional[KernelCounts] = None,
                 kv_itemsize: int = 2):
        self.cfg, self.page_size, self.kernels = cfg, page_size, kernels
        self.kv_itemsize = kv_itemsize
        if KINDS["kv_rows"].present(cfg):
            for kind, n in stored_row_bytes(cfg, kv_itemsize).items():
                tm.KV_ROW_BYTES.labels(model=model, kind=kind).set(n)
        self._rows = [
            (k.fields, k.counts,
             [(i, c.labels(model=model)) for i, c in enumerate(k.series)
              if c is not None], name == "exit")
            for name, k in KINDS.items() if k.present(cfg)]
        if cfg.num_experts:
            self._moe = [c.labels(model=model) for c in (
                tm.MOE_ASSIGNMENTS_TOTAL, tm.MOE_EXPERT_PAIRS_HIT_TOTAL,
                tm.MOE_EXPERT_LOAD_MAX, tm.MOE_EXPERT_LOAD_MEAN)]

    def note(self, sp, tokens, kv, emits=None, *, scan: bool = False,
             stream_len: int = 0, opened: int = 0) -> int:
        """A launched step's work (the arguments are `Step`'s) onto its
        sample `sp` and the series, each once; returns the stream tokens
        that stopped below an `exit_layer` (0 for a model without one)."""
        step = Step(tokens, kv, emits, scan, stream_len, opened,
                    self.kernels, self.kv_itemsize)
        skipped = 0
        for fields, count, series, exits in self._rows:
            counts = count(self.cfg, self.page_size, step)
            sp.note(**dict(zip(fields, counts)))
            for i, child in series:
                child.inc(counts[i])
            if exits:
                skipped = counts[2]
        return skipped

    def note_moe_load(self, sp, stats: np.ndarray) -> None:
        """`stats` [passes, 3]: each forward pass's moe.LOAD_STATS as they
        came back behind the sampled ids. Onto the step's sample (sums
        over its passes; the largest load of any; the mean rows a (layer,
        expert) pair got in a pass) and the /metrics series."""
        cfg = self.cfg
        n, hit, top = (int(stats[:, 0].sum()), int(stats[:, 1].sum()),
                       int(stats[:, 2].max()))
        mean = n / (len(stats) * cfg.count(EXPERTS) * cfg.num_experts)
        sp.note(moe_assignments=n, moe_pairs_hit=hit, moe_load_max=top,
                moe_load_mean=round(mean, 4))
        assign, pairs_hit, load_max, load_mean = self._moe
        assign.inc(n)
        pairs_hit.inc(hit)
        load_max.set(top)
        load_mean.set(mean)


# The per-slot state's fields (llama.SlotState), each with the name its
# bytes go by in a runtime's stats and the gauge that publishes them.
STATE_BYTES = (("conv", "conv_state_bytes", tm.HBM_CONV_STATE_BYTES),
               ("rule", "lin_state_bytes", tm.HBM_LIN_STATE_BYTES),
               ("ssm", "ssm_state_bytes", tm.HBM_SSM_STATE_BYTES),
               ("scan", "s6_state_bytes", tm.HBM_S6_STATE_BYTES),
               ("ring", "swa_ring_bytes", tm.HBM_SWA_RING_BYTES),
               ("pooled", "bsa_pooled_bytes", tm.HBM_BSA_POOLED_BYTES))


def state_bytes(state, model: str) -> dict:
    """{stats name: bytes} of every field of a per-slot state (0: not kept;
    `state` None: none is), each set on its gauge."""
    out = {}
    for field, name, gauge in STATE_BYTES:
        held = getattr(state, field, None)
        out[name] = 0 if held is None else int(held.nbytes)
        gauge.labels(model=model).set(out[name])
    return out
