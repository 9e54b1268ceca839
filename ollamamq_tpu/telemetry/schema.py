"""THE declaration site for every ollamamq_* metric.

Everything the process exports lives here, so (a) the engine/server grab
handles instead of re-declaring names inline, and (b)
scripts/check_metrics_docs.py can enumerate the full metric surface by
importing this one module — no engine, no jax — and diff it against the
README's Observability table.

Naming: `ollamamq_` prefix; latencies in milliseconds carry an `_ms`
suffix; counters carry `_total`. Per-model series are labeled
{model=...}; per-user queue depth {user=...}; per-chip HBM {chip=,host=}.
"""

from __future__ import annotations

from ollamamq_tpu.telemetry.metrics import (DEFAULT_LATENCY_BUCKETS_MS,
                                            REGISTRY)

# -- request latency histograms (re-bucketable via --metrics-buckets) ------
TTFT_MS = REGISTRY.histogram(
    "ollamamq_ttft_ms",
    "Time to first token per request, enqueue to first sampled token (ms)",
    buckets=DEFAULT_LATENCY_BUCKETS_MS, labels=("model",))
TPOT_MS = REGISTRY.histogram(
    "ollamamq_tpot_ms",
    "Time per output token: decode step latency per emitted token (ms)",
    buckets=DEFAULT_LATENCY_BUCKETS_MS, labels=("model",))
STEP_LATENCY_MS = REGISTRY.histogram(
    "ollamamq_step_latency_ms",
    "A step's time on the device per forward pass (ms): from its launch "
    "(or the end of the step it queued behind) to the first probe that "
    "saw its ids ready, at the latest the return of the blocking read",
    buckets=DEFAULT_LATENCY_BUCKETS_MS, labels=("model",))
PREFILL_LATENCY_MS = REGISTRY.histogram(
    "ollamamq_prefill_latency_ms",
    "Prefill forward latency per dispatched batch or chunk (ms)",
    buckets=DEFAULT_LATENCY_BUCKETS_MS, labels=("model",))

# -- engine occupancy / utilization gauges ---------------------------------
BATCH_OCCUPANCY = REGISTRY.gauge(
    "ollamamq_batch_occupancy",
    "Active decode slots / max_slots (0..1), sampled per decode step",
    labels=("model",))
BATCH_PADDING_WASTE = REGISTRY.gauge(
    "ollamamq_batch_padding_waste",
    "Fraction of the last dispatched batch's token positions that were "
    "padding (0..1): the ragged stream's tail up to its ladder rung — the "
    "compute burned for shape stability", labels=("model",))
# -- mixture-of-experts routing (models/moe.py; MoE models only) -----------
# Counted on the device inside the step program and read back with the
# sampled ids. No "dropped" series: the dispatch has no capacity to pass.
MOE_ASSIGNMENTS_TOTAL = REGISTRY.counter(
    "ollamamq_moe_assignments_total",
    "(token, expert) assignments the router made: real tokens x experts "
    "per token x layers, summed over forward passes", labels=("model",))
MOE_EXPERT_PAIRS_HIT_TOTAL = REGISTRY.counter(
    "ollamamq_moe_expert_pairs_hit_total",
    "(layer, expert) pairs that got at least one token in a forward pass, "
    "summed over passes — each streams that expert's weights once",
    labels=("model",))
MOE_EXPERT_LOAD_MAX = REGISTRY.gauge(
    "ollamamq_moe_expert_load_max",
    "Most tokens any one expert of any layer got in a forward pass of the "
    "last dispatch", labels=("model",))
MOE_EXPERT_LOAD_MEAN = REGISTRY.gauge(
    "ollamamq_moe_expert_load_mean",
    "Mean tokens a (layer, expert) pair got in a forward pass of the last "
    "dispatch (assignments / passes / layers / experts)", labels=("model",))
KV_PAGES_USED = REGISTRY.gauge(
    "ollamamq_kv_pages_used",
    "KV cache pages currently allocated", labels=("model",))
KV_PAGE_UTILIZATION = REGISTRY.gauge(
    "ollamamq_kv_page_utilization",
    "KV cache pages allocated / pool size (0..1)", labels=("model",))
MFU = REGISTRY.gauge(
    "ollamamq_mfu",
    "Model FLOPs utilization (0..1): analytic FLOPs/token x tokens per "
    "decode step over per-chip peak FLOPs x chips (0 when the peak for "
    "this accelerator is unknown; override with OLLAMAMQ_PEAK_FLOPS)",
    labels=("model",))
FLOPS_PER_TOKEN = REGISTRY.gauge(
    "ollamamq_model_flops_per_token",
    "Analytic forward FLOPs per generated token at zero context "
    "(2 x active params; attention adds ~4 x layers x ctx x q_dim)",
    labels=("model",))

# -- queue / request flow --------------------------------------------------
QUEUE_DEPTH = REGISTRY.gauge(
    "ollamamq_queue_depth",
    "Requests waiting in the fair-share queue, per user",
    labels=("user",))
REQUESTS_INFLIGHT = REGISTRY.gauge(
    "ollamamq_requests_inflight",
    "Requests accepted and not yet finished (any kind)")
REQUESTS_TOTAL = REGISTRY.counter(
    "ollamamq_requests_total",
    "Finished requests by outcome (stop/length/cancelled/error)",
    labels=("model", "outcome"))
TOKENS_GENERATED_TOTAL = REGISTRY.counter(
    "ollamamq_tokens_generated_total",
    "Tokens sampled across all requests", labels=("model",))
PROMPT_TOKENS_TOTAL = REGISTRY.counter(
    "ollamamq_prompt_tokens_total",
    "Prompt tokens prefilled across all requests", labels=("model",))

# -- prefix cache (engine/prefix_cache.py; series exist only when
# --prefix-cache is on) ----------------------------------------------------
PREFIX_CACHE_HITS_TOTAL = REGISTRY.counter(
    "ollamamq_prefix_cache_hits_total",
    "Admissions that reused a cached prompt prefix (≥ min-pages match)",
    labels=("model",))
PREFIX_CACHE_MISSES_TOTAL = REGISTRY.counter(
    "ollamamq_prefix_cache_misses_total",
    "Admissions with no (or below-threshold) cached prefix",
    labels=("model",))
PREFIX_CACHE_EVICTIONS_TOTAL = REGISTRY.counter(
    "ollamamq_prefix_cache_evictions_total",
    "Cached KV pages evicted back to the free list (LRU, on allocator "
    "pressure or flush)", labels=("model",))
PREFIX_CACHE_HIT_RATIO = REGISTRY.gauge(
    "ollamamq_prefix_cache_hit_ratio",
    "Prefix-cache hits / lookups since start (0..1)", labels=("model",))
PREFIX_CACHE_TOKENS_SAVED = REGISTRY.gauge(
    "ollamamq_prefix_cache_tokens_saved",
    "Cumulative prompt tokens served from cached KV pages instead of "
    "recomputed", labels=("model",))
PREFIX_CACHE_PAGES = REGISTRY.gauge(
    "ollamamq_prefix_cache_pages",
    "KV pages currently owned by the prefix-cache radix tree",
    labels=("model",))

# -- graceful degradation under load (engine preemption / bounded
# admission / deadlines / retry containment) -------------------------------
# Closed vocabulary for ollamamq_shed_total{reason}; the doc gate
# (scripts/check_metrics_docs.py) pins the README table to this tuple.
SHED_REASONS = ("queue_full", "user_queue_full", "deadline", "kv_exhausted")
PREEMPTIONS_TOTAL = REGISTRY.counter(
    "ollamamq_preemptions_total",
    "Decode slots preempted under KV-pool pressure (victim requeued to "
    "the front of its user's queue for recompute)", labels=("model",))
SHED_TOTAL = REGISTRY.counter(
    "ollamamq_shed_total",
    "Requests shed instead of served, by reason (queue_full / "
    "user_queue_full / deadline / kv_exhausted)", labels=("reason",))
RETRIES_TOTAL = REGISTRY.counter(
    "ollamamq_retries_total",
    "Requests re-dispatched after a contained runtime-step failure "
    "(once each with backoff; repeat offenders are poisoned and errored)",
    labels=("model",))
DEADLINE_DROPS_TOTAL = REGISTRY.counter(
    "ollamamq_deadline_drops_total",
    "Requests dropped because their per-request deadline expired "
    "(at admission, before prefill dispatch, before composing a "
    "speculative verify span, or at preemption re-admission)",
    labels=("model",))

# -- speculative decoding (--spec; n-gram draft + ragged verify) -----------
SPEC_TOKENS_TOTAL = REGISTRY.counter(
    "ollamamq_spec_tokens_total",
    "Speculative draft tokens by outcome: proposed (composed into a "
    "verify span), accepted (matched the model's greedy argmax and "
    "emitted), rejected (KV pages rolled back); `proposer` is where the "
    "drafts came from: ngram (prompt lookup on the host), mtp (the "
    "model's own prediction module, on the device), fake",
    labels=("model", "outcome", "proposer"))
SPEC_ACCEPT_RATE = REGISTRY.gauge(
    "ollamamq_spec_accept_rate",
    "Accepted / proposed speculative draft tokens since start (0..1); "
    "the per-user auto-throttle (--spec-min-accept) keys off the same "
    "accounting", labels=("model",))


# -- size-aware scheduling (engine/scheduler.py; --scheduler) --------------
SCHED_PRED_ERR = REGISTRY.histogram(
    "ollamamq_sched_pred_err",
    "Output-length predictor absolute error in tokens (|predicted - "
    "actual|), observed at request finish — the srpt/edf scheduling "
    "policies order by these predictions, so this histogram is the "
    "promotion guardrail's live twin",
    buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256, 512), labels=("model",))
SCHED_DECISIONS_TOTAL = REGISTRY.counter(
    "ollamamq_sched_decisions_total",
    "Scheduling-policy reorder decisions applied (admission windows, "
    "pending-queue reorders), by policy; fcfs never reorders so its "
    "series stays 0", labels=("policy",))


# -- latency attribution / SLO / alerting (telemetry/attribution.py,
# telemetry/slo.py, engine/health.py watchdog) ------------------------------
REQUEST_PHASE_MS = REGISTRY.histogram(
    "ollamamq_request_phase_ms",
    "Per-request latency attribution: milliseconds spent in each lifecycle "
    "phase (ingress/queue/admission/prefix_cache/prefill/decode/stream), "
    "observed at request finish; phases sum to end-to-end latency",
    buckets=DEFAULT_LATENCY_BUCKETS_MS, labels=("model", "phase"))
STREAM_LAG_MS = REGISTRY.histogram(
    "ollamamq_stream_lag_ms",
    "Milliseconds between the engine thread pushing a stream item "
    "(TokenStream.push) and its NDJSON/SSE frame having been written to "
    "the client socket by the asyncio thread — the far end of the "
    "request path, which no request phase times",
    buckets=(0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0,
             250.0, 1000.0))
STREAM_FRAMES_TOTAL = REGISTRY.counter(
    "ollamamq_stream_frames_total",
    "NDJSON/SSE frames written to client sockets for stream items: one a "
    "(step, stream) hand-over that has text, plus each stream's terminal")
STREAM_FRAME_TOKENS_TOTAL = REGISTRY.counter(
    "ollamamq_stream_frame_tokens_total",
    "Sampled token ids those frames carried; over "
    "ollamamq_stream_frames_total it is tokens a frame — 1 where ragged "
    "steps feed the streams, up to --decode-steps where fused scans do")
STREAM_WAKEUPS_TOTAL = REGISTRY.counter(
    "ollamamq_stream_wakeups_total",
    "Wake-ups of a stream consumer's thread by a pushing thread (for the "
    "server: one call_soon_threadsafe): one a settled step for every "
    "stream it touched (`stream_wakeups` on a step sample), one a push "
    "outside a step")
SLO_VIOLATIONS_TOTAL = REGISTRY.counter(
    "ollamamq_slo_violations_total",
    "Observations over the configured SLO threshold (--slo-ttft-ms / "
    "--slo-tpot-ms), by objective; series exist only with SLOs configured",
    labels=("objective",))
SLO_BURN_RATE = REGISTRY.gauge(
    "ollamamq_slo_burn_rate",
    "Error-budget burn rate over each alerting window's long leg "
    "(bad/total over window / (1 - target)); 1.0 = spending exactly the "
    "budget, above the window's factor = alert", labels=("objective",
                                                         "window"))
SLO_ALERTS_FIRING = REGISTRY.gauge(
    "ollamamq_slo_alerts_firing",
    "Active alerts (SLO burn, watchdog stalls, device loss): 1 per "
    "firing alert, rebuilt each scrape so resolved alerts disappear",
    labels=("alert", "severity"))
WATCHDOG_STALLS_TOTAL = REGISTRY.counter(
    "ollamamq_watchdog_stalls_total",
    "Stall watchdog firings by kind (engine_step, request_phase, "
    "worker_host, device, replica, scale, standby, takeover)",
    labels=("kind",))

# -- decision journal (telemetry/journal.py; GET /debug/journal) -----------
JOURNAL_EVENTS_TOTAL = REGISTRY.counter(
    "ollamamq_journal_events_total",
    "Scheduler decision-journal records appended, by event kind (the "
    "flight recorder's write rate; tail the ring at /debug/journal)",
    labels=("kind",))

# -- int8 quantization (serving density; --weights-dtype / --kv-dtype) -----
HBM_WEIGHT_BYTES = REGISTRY.gauge(
    "ollamamq_hbm_weight_bytes",
    "Bytes the loaded weights occupy per model runtime (int8 payloads + "
    "fp32 scales when --weights-dtype=int8 — the density lever's "
    "before/after)", labels=("model",))
HBM_KV_BYTES = REGISTRY.gauge(
    "ollamamq_hbm_kv_bytes",
    "Bytes the KV page pool occupies per model runtime (int8 pages + "
    "fp32 scale rows when --kv-dtype=int8; ~2x more concurrent requests "
    "fit the same budget)", labels=("model",))
HBM_CONV_STATE_BYTES = REGISTRY.gauge(
    "ollamamq_hbm_conv_state_bytes",
    "Bytes the conv layers' per-slot state occupies per model runtime "
    "(conv layers x (window - 1) x slots x hidden; fixed, whatever "
    "the context lengths; 0 for a model without such layers)",
    labels=("model",))
KV_BYTES_PER_TOKEN = REGISTRY.gauge(
    "ollamamq_kv_bytes_per_token",
    "Bytes one token of context adds to the KV pool: K and V of every "
    "ATTENTION layer (a hybrid stack's conv layers add none) — what a "
    "deployment's context capacity is sized by", labels=("model",))
CONV_STATE_RESETS_TOTAL = REGISTRY.counter(
    "ollamamq_conv_state_resets_total",
    "Rows of launched steps whose slot's conv state the program opened "
    "at zero: a request's first span (every admission, every replay)",
    labels=("model",))
CONV_STATE_CARRIED_TOTAL = REGISTRY.counter(
    "ollamamq_conv_state_carried_total",
    "Rows of launched steps that read the conv state an earlier step left "
    "in their slot: later chunks of a prompt, decode rows, a fused scan's "
    "active slots", labels=("model",))
HBM_LIN_STATE_BYTES = REGISTRY.gauge(
    "ollamamq_hbm_lin_state_bytes",
    "Bytes the linear-attention layers' per-slot rule state occupies per "
    "model runtime (linear layers x (slots + 1) x key dim x heads x value "
    "dim, float32; fixed, whatever the context lengths; their "
    "convolution's window is under ollamamq_hbm_conv_state_bytes; 0 for a "
    "model without such layers)", labels=("model",))
LIN_STATE_RESETS_TOTAL = REGISTRY.counter(
    "ollamamq_lin_state_resets_total",
    "Rows of launched steps whose slot's linear-attention state the "
    "program opened at zero: a request's first span (every admission, "
    "every replay)", labels=("model",))
LIN_STATE_CARRIED_TOTAL = REGISTRY.counter(
    "ollamamq_lin_state_carried_total",
    "Rows of launched steps that continued the linear-attention state an "
    "earlier step left in their slot: later chunks of a prompt, decode "
    "rows, a fused scan's active slots", labels=("model",))
LIN_STEP_ROWS_TOTAL = REGISTRY.counter(
    "ollamamq_lin_step_rows_total",
    "Row-passes through the delta rule's one-token form (a state row read "
    "once and written once a linear layer): a ragged step's 1-token rows, "
    "a fused scan's active slots x its passes", labels=("model",))
LIN_SPAN_TOKENS_TOTAL = REGISTRY.counter(
    "ollamamq_lin_span_tokens_total",
    "Tokens of spans longer than one token that went through the delta "
    "rule's chunked form (the state read and written once a 64-token "
    "window a span touches)", labels=("model",))
LIN_CHUNK_PAIRS_TOTAL = REGISTRY.counter(
    "ollamamq_lin_chunk_pairs_total",
    "(Row, 64-token window) pairs the delta rule's chunked form ran over "
    "the longer spans of launched steps, a linear layer's worth: a span of "
    "n > 1 tokens from stream token s touches (s + n - 1) // 64 - s // 64 "
    "+ 1 windows (on a TPU, the programs a head block of one "
    "chunk_rule_pallas launch)", labels=("model",))
LIN_PREPARE_WINDOWS_TOTAL = REGISTRY.counter(
    "ollamamq_lin_prepare_windows_total",
    "64-token windows of the stream the delta rule's chunked form solved in "
    "launched ragged steps, a linear layer's worth: on a TPU, where the "
    "solve is one chunk_solve_pallas launch a layer, the windows that hold "
    "a token of a span longer than one token (beside "
    "ollamamq_lin_chunk_pairs_total: how often the kernel engages); on the "
    "XLA path (the CPU, a shape the kernel does not take) every window of "
    "the padded stream, ceil(stream tokens / 64), whether a span lies in it "
    "or not (ops/gated_delta._prepare solves them all at once); 0 for a "
    "fused scan, which has no window", labels=("model",))
HBM_SSM_STATE_BYTES = REGISTRY.gauge(
    "ollamamq_hbm_ssm_state_bytes",
    "Bytes the state-space mixers' per-slot recurrent state occupies per "
    "model runtime (layers x (slots + 1) x state dim x heads x head dim, "
    "float32; fixed, whatever the context lengths; their convolution's "
    "window is under ollamamq_hbm_conv_state_bytes and the same layers' K "
    "and V under ollamamq_hbm_kv_bytes; 0 for a model without mixers)",
    labels=("model",))
SSM_STATE_RESETS_TOTAL = REGISTRY.counter(
    "ollamamq_ssm_state_resets_total",
    "Rows of launched steps whose slot's mixer state the program opened at "
    "zero: a request's first span (every admission, every replay)",
    labels=("model",))
SSM_STATE_CARRIED_TOTAL = REGISTRY.counter(
    "ollamamq_ssm_state_carried_total",
    "Rows of launched steps that continued the mixer state an earlier step "
    "left in their slot: later chunks of a prompt, decode rows, a fused "
    "scan's active slots", labels=("model",))
SSM_STEP_ROWS_TOTAL = REGISTRY.counter(
    "ollamamq_ssm_step_rows_total",
    "Row-passes through the state-space recurrence's one-token form (a "
    "state row read once and written once a layer): a ragged step's "
    "1-token rows, a fused scan's active slots x its passes",
    labels=("model",))
SSM_SPAN_TOKENS_TOTAL = REGISTRY.counter(
    "ollamamq_ssm_span_tokens_total",
    "Tokens of spans longer than one token that went through the "
    "recurrence's chunked form (the state read and written once a "
    "64-token window a span touches)", labels=("model",))
HBM_BSA_POOLED_BYTES = REGISTRY.gauge(
    "ollamamq_hbm_bsa_pooled_bytes",
    "Bytes the block-sparse layers' pooled-key pool occupies per model "
    "runtime (sparse layers x pages x page size / pooling stride x kv heads "
    "x head dim; rows under the K/V pool's page table, carried beside the "
    "per-slot state; 0 for a model without such layers)", labels=("model",))
BSA_BLOCKS_IN_CONTEXT_TOTAL = REGISTRY.counter(
    "ollamamq_bsa_blocks_in_context_total",
    "Blocks of cached positions in the contexts of the sparse-attention "
    "queries past sparse_dense_len of launched steps, one sparse layer's "
    "worth: ceil(n / sparse_block_size) a query at context n (decode rows "
    "and span tokens together)", labels=("model",))
BSA_BLOCKS_KEPT_TOTAL = REGISTRY.counter(
    "ollamamq_bsa_blocks_kept_total",
    "...and the blocks those queries keep, what the mathematics asks a "
    "walk to read: min(sparse_topk, blocks in context) a query",
    labels=("model",))
BSA_BLOCKS_WALKED_TOTAL = REGISTRY.counter(
    "ollamamq_bsa_blocks_walked_total",
    "...and the blocks the program's walks cover for them: a one-token "
    "row's kept blocks (its walk follows the list), a longer span's whole "
    "context a token (served under a block mask)", labels=("model",))
LIGHTNING_STEP_ROWS_TOTAL = REGISTRY.counter(
    "ollamamq_lightning_step_rows_total",
    "Row-passes through the lightning recurrence's one-token form (a state "
    "row read once and written once a lightning layer): a ragged step's "
    "1-token rows, a fused scan's active slots x its passes",
    labels=("model",))
LIGHTNING_SPAN_TOKENS_TOTAL = REGISTRY.counter(
    "ollamamq_lightning_span_tokens_total",
    "Tokens of spans longer than one token that went through the lightning "
    "recurrence's chunked form (the state read and written once a 64-token "
    "window a span touches)", labels=("model",))
HBM_S6_STATE_BYTES = REGISTRY.gauge(
    "ollamamq_hbm_s6_state_bytes",
    "Bytes the mamba layers' per-slot selective-scan state occupies per "
    "model runtime (layers x (slots + 1) x states x channels, float32; "
    "fixed, whatever the context lengths; their convolution's window is "
    "under ollamamq_hbm_conv_state_bytes, the window layers' rings under "
    "ollamamq_hbm_swa_ring_bytes; 0 for a model without mamba layers)",
    labels=("model",))
S6_STATE_RESETS_TOTAL = REGISTRY.counter(
    "ollamamq_s6_state_resets_total",
    "Rows of launched steps whose slot's scan state the program opened at "
    "zero: a request's first span (every admission, every replay)",
    labels=("model",))
S6_STATE_CARRIED_TOTAL = REGISTRY.counter(
    "ollamamq_s6_state_carried_total",
    "Rows of launched steps that continued the scan state an earlier step "
    "left in their slot: later chunks of a prompt, decode rows, a fused "
    "scan's active slots", labels=("model",))
S6_STEP_ROWS_TOTAL = REGISTRY.counter(
    "ollamamq_s6_step_rows_total",
    "Row-passes through the selective scan's one-token form (a state row "
    "read once and written once a mamba layer): a ragged step's 1-token "
    "rows, a fused scan's active slots x its passes", labels=("model",))
S6_SPAN_TOKENS_TOTAL = REGISTRY.counter(
    "ollamamq_s6_span_tokens_total",
    "Tokens of spans longer than one token that went through the selective "
    "scan token after token (the state read and written once a 16-token "
    "window of the stream a span touches)", labels=("model",))
XATTN_ROWS_TOTAL = REGISTRY.counter(
    "ollamamq_xattn_rows_total",
    "Sampled rows of launched steps that passed the layers above the full "
    "attention layer of a decoder-hybrid-decoder stack (gated memory units "
    "and cross-attention layers): a ragged step's emitting rows, a fused "
    "scan's active slots x its passes", labels=("model",))
XATTN_CTX_ROWS_TOTAL = REGISTRY.counter(
    "ollamamq_xattn_ctx_rows_total",
    "Cached rows of the full layer's pool ONE cross-attention layer's walks "
    "read for those rows: each sampled row's context (a row that does not "
    "emit is handed a context of 0); every cross layer reads the same",
    labels=("model",))
EXIT_SKIPPED_TOKENS_TOTAL = REGISTRY.counter(
    "ollamamq_exit_skipped_tokens_total",
    "Stream tokens of launched steps that stopped below the first layer "
    "that holds no state and writes no cache (prompt tokens that are not "
    "their span's sampled position): the layers above never ran for them",
    labels=("model",))
HBM_LATENT_POOL_BYTES = REGISTRY.gauge(
    "ollamamq_hbm_latent_pool_bytes",
    "Bytes the latent pool of a model with latent attention occupies "
    "(attention layers x slots x latent lanes: c_kv and the shared rotary "
    "key, padded to whole 128-lane tiles; counted in ollamamq_hbm_kv_bytes "
    "too)", labels=("model",))
HBM_INDEX_POOL_BYTES = REGISTRY.gauge(
    "ollamamq_hbm_index_pool_bytes",
    "Bytes the index-key pool of a model with latent attention occupies "
    "(attention layers x slots x index_head_dim; counted in "
    "ollamamq_hbm_kv_bytes too)", labels=("model",))
WEIGHT_STACKS_RELAID = REGISTRY.gauge(
    "ollamamq_weight_stacks_relaid",
    "Weight leaves a model runtime holds in another device layout than the "
    "default row-major order, because the step programs' contractions read "
    "that order, contracted dimension minor, on one device (2 for a model "
    "with latent attention: mla_wuq, mla_wukv; 2 for one whose q and k "
    "projections are split into heads at once: wq, wk; 0 for one that norms "
    "them at full width first, for int8 weights and under a mesh). Names, "
    "logical shapes and values are unchanged", labels=("model",))
WEIGHT_STACKS_RELAID_BYTES = REGISTRY.gauge(
    "ollamamq_weight_stacks_relaid_bytes",
    "Bytes of those leaves: what every step program re-laid a pass while "
    "they were held row-major", labels=("model",))
MLA_ROWS_TOTAL = REGISTRY.counter(
    "ollamamq_mla_rows_total",
    "Query tokens of launched steps that went through latent attention "
    "(a ragged step's tokens, a fused scan's active slots x its passes)",
    labels=("model",))
MLA_WIDE_TOKENS_TOTAL = REGISTRY.counter(
    "ollamamq_mla_wide_tokens_total",
    "Those of them that a latent attention launch attended in the "
    "EXPANDED form: tokens of prefill spans of at least mla_attention.WIDE "
    "tokens, whose programs expand each block's keys and values once a head "
    "group; 0 without the kernel and for a fused scan",
    labels=("model",))
MLA_ABSORBED_ROWS_TOTAL = REGISTRY.counter(
    "ollamamq_mla_absorbed_rows_total",
    "Stream rows of ragged steps whose launch holds that expanded body for "
    "which a layer computed the ABSORBED form (q through W_uk before the "
    "launch, the attended latent through W_uv behind it): "
    "mla_attention.ABSORBED_LEAD a step where every row behind the lead is a "
    "wide span's or padding, else the rung; a layer's worth. 0 where nothing "
    "is expanded (every row is absorbed there, and not counted)",
    labels=("model",))
DSA_CTX_TOKENS_TOTAL = REGISTRY.counter(
    "ollamamq_dsa_ctx_tokens_total",
    "Cached positions the indexer scored for those query tokens, a layer: "
    "a token at position p scores p + 1", labels=("model",))
DSA_SELECTED_TOKENS_TOTAL = REGISTRY.counter(
    "ollamamq_dsa_selected_tokens_total",
    "Cached positions attention then saw for them, a layer: min(p + 1, "
    "index_topk)", labels=("model",))
ATTN_PAIRS_TOTAL = REGISTRY.counter(
    "ollamamq_attn_pairs_total",
    "Causal (query token, cached position) pairs of launched steps' plain "
    "(K and V pages, non-latent) attention, a layer: a token at position p "
    "attends p + 1 (a ragged step's spans, a fused scan's active slots x "
    "its passes)", labels=("model",))
ATTN_CTX_ROWS_TOTAL = REGISTRY.counter(
    "ollamamq_attn_ctx_rows_total",
    "Cached K/V rows those steps' walks have to read at the least, a "
    "layer: each span's context once (a fused scan's pass: each active "
    "slot's)", labels=("model",))
ATTN_TALL_TOKENS_TOTAL = REGISTRY.counter(
    "ollamamq_attn_tall_tokens_total",
    "Stream tokens of launched ragged steps that the ragged attention "
    "kernel served a whole stretch at a time (kv_contract.TALL consecutive "
    "tokens inside one span share each K/V block's trip); 0 without the "
    "kernel", labels=("model",))
HBM_SWA_RING_BYTES = REGISTRY.gauge(
    "ollamamq_hbm_swa_ring_bytes",
    "Bytes the window (sliding_attention) layers' per-slot K/V rings occupy "
    "per model runtime (window layers x (slots + 1) x ring rows x K and V "
    "rows; fixed, whatever the context lengths — ollamamq_hbm_kv_bytes is "
    "then the FULL layers' pool alone; 0 for a model without such layers)",
    labels=("model",))
SWA_PAIRS_TOTAL = REGISTRY.counter(
    "ollamamq_swa_pairs_total",
    "In-window (query token, cached position) pairs of launched steps' "
    "window attention, a window layer: a token at position p attends "
    "min(p + 1, sliding_window)", labels=("model",))
SWA_CTX_ROWS_TOTAL = REGISTRY.counter(
    "ollamamq_swa_ctx_rows_total",
    "Cached K/V rows those steps' window launches have to read at the "
    "least, a window layer: a span of n tokens ending at context kv reads "
    "min(kv, n + sliding_window - 1) (a fused scan's pass: each active "
    "slot's min(kv, sliding_window))", labels=("model",))
SWA_WALK_ROWS_TOTAL = REGISTRY.counter(
    "ollamamq_swa_walk_rows_total",
    "Rows those launches' walks cover, a window layer: from the ring page "
    "each row's table starts at (the page that holds the first position "
    "the span's first query sees) to the span's end", labels=("model",))
SWA_FULL_ROWS_TOTAL = REGISTRY.counter(
    "ollamamq_swa_full_rows_total",
    "Rows a walk of the same contexts from position 0 would have covered "
    "(what a window served as a mask costs): ollamamq_swa_walk_rows_total "
    "over this is the share of the context the window launches walk",
    labels=("model",))
KV_ROW_BYTES = REGISTRY.gauge(
    "ollamamq_kv_row_bytes",
    "Bytes ONE cached position of one attention layer occupies AS STORED, K "
    "row and V row, by the layer's kind (full_attention: in the paged pool; "
    "sliding_attention: in the rings) — of a model whose attention kinds "
    "differ in head shape (MiMo-V2-Flash: kv heads a kind, key heads wider "
    "than value heads); `attn_row_bytes` / `swa_row_bytes` on a step sample. "
    "The step programs' named scopes of such a model: `attn_vscale` (v "
    "times attention_value_scale) and `attn_sink` (the jnp attentions' sink "
    "term; the Pallas kernels fold it inside their launch)",
    labels=("model", "kind"))
# A residual path of several streams (`hc_mult`; ops/hyper_connection.py):
# what a step sample of such a model carries — the step's real tokens that
# pass the connection (a ragged step's stream tokens, a fused scan's active
# slots x its passes) and the applications a forward pass (two a layer and
# the read-out before the head) — and the step programs' named scopes around
# the calls, kernel or jnp twin. Sample fields and op metadata only: /metrics
# exports nothing for them (a traced benchmark run reads them:
# benchmarks/layer_metrics/_mhc.py).
MHC_SAMPLE_FIELDS = ("mhc_rows", "mhc_apps")
MHC_SCOPES = ("mhc_mix_in", "mhc_mix_out", "mhc_read_out")
QUANT_LOGIT_ERR = REGISTRY.gauge(
    "ollamamq_quant_logit_err",
    "Max absolute logit error of the int8-quantized weights vs their "
    "bf16 source on the guardrail probe (teacher-forced greedy rollout; "
    "set when the guardrail runs — tests)",
    labels=("model",))

# -- fleet router (fleet/router.py; dispatcher-over-engines) ---------------
# Closed site vocabulary for ollamamq_router_overhead_ms{site}: every
# always-on nanosecond timer around the router hot path. "place" is the
# bounded one (the router_overhead alert fires when its p99 exceeds
# --router-overhead-budget-ms); the rest attribute where the router's
# own time goes per decision.
ROUTER_OVERHEAD_SITES = ("place", "journal", "wal_fsync",
                         "migrate_export", "migrate_ship",
                         "migrate_import")
ROUTER_OVERHEAD_MS = REGISTRY.histogram(
    "ollamamq_router_overhead_ms",
    "Router hot-path self-profiling: milliseconds the router itself "
    "spent per decision, by site (place = the placement decision, "
    "journal = one flight-recorder append, wal_fsync = the durable-"
    "admission gate, migrate_export/_ship/_import = the three legs of "
    "a KV handoff) — always-on perf_counter_ns timers, the measured "
    "and bounded 'router overhead' of the fleet-scale story",
    buckets=(0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0,
             25.0, 50.0, 100.0, 250.0, 1000.0),
    labels=("site",))
FLEET_REPLICAS = REGISTRY.gauge(
    "ollamamq_fleet_replicas",
    "Engine replicas under the fleet router by state (healthy / ejected "
    "/ draining); absent when serving single-engine", labels=("state",))
FLEET_FAILOVERS_TOTAL = REGISTRY.counter(
    "ollamamq_fleet_failovers_total",
    "In-flight streams re-dispatched to another replica after their "
    "replica died or was ejected (each replays prompt + already-emitted "
    "tokens so the client sees one seamless stream)")
FLEET_AFFINITY_HITS_TOTAL = REGISTRY.counter(
    "ollamamq_fleet_placement_affinity_hits_total",
    "Placements routed to the replica whose prefix-cache radix tree "
    "already held the prompt's prefix (--placement=affinity); misses "
    "fall back to least-loaded")
FLEET_TIER_MEMBERS = REGISTRY.gauge(
    "ollamamq_fleet_tier_members",
    "Fleet members per replica tier by state (healthy / ejected / "
    "draining) under --tiers; a tier whose healthy count hits 0 is "
    "serving its traffic cross-tier (journaled tier_overflow) until a "
    "member heals or regroups in", labels=("tier", "state"))
FLEET_TIER_OVERFLOW_TOTAL = REGISTRY.counter(
    "ollamamq_fleet_tier_overflow_total",
    "Streams placed cross-tier, by (from, to) tier: per-tier SLO "
    "burn-rate overflow, an empty home tier, or a failover with no "
    "in-tier capacity — every one journaled as tier_overflow with its "
    "inputs", labels=("from", "to"))
FLEET_REGROUPS_TOTAL = REGISTRY.counter(
    "ollamamq_fleet_regroups_total",
    "Tier regroups (a member drained, live streams migrated off, "
    "hot-restarted at the target tier's TP width, rejoined the other "
    "tier) by outcome: 'done' or 'aborted' (crash/restart failure "
    "mid-retier; the member keeps its original tier)",
    labels=("outcome",))
FLEET_MIGRATIONS_TOTAL = REGISTRY.counter(
    "ollamamq_fleet_migrations_total",
    "KV page migrations between fleet members by outcome: 'migrated' "
    "(stream resumed from shipped state on the target), 'aborted' "
    "(transfer failed; the stream fell back to recompute replay), "
    "'prefix' (an affinity-miss shipped cached prefix pages to the "
    "chosen member)", labels=("outcome",))
FLEET_MIGRATE_BYTES_TOTAL = REGISTRY.counter(
    "ollamamq_fleet_migrate_bytes_total",
    "KV page payload bytes shipped between fleet members (migrations "
    "and prefix shipping; int8 pools move ~2x fewer bytes than bf16)")

# -- elastic fleet (fleet/autoscaler.py; --autoscale) ----------------------
FLEET_SCALE_EVENTS_TOTAL = REGISTRY.counter(
    "ollamamq_fleet_scale_events_total",
    "Autoscaler fleet-size changes by direction ('up' = member "
    "provisioned and joined, 'down' = member drained, migrated off, and "
    "retired) and outcome ('done' or 'aborted': a failed spawn, or an "
    "eject mid-retire) — every one journaled as scale_up/scale_down "
    "with the burn + backlog inputs that justified it",
    labels=("direction", "outcome"))
FLEET_MEMBER_HOURS_TOTAL = REGISTRY.counter(
    "ollamamq_fleet_member_hours_total",
    "Cumulative member-serving hours (fractional; accrued each scaler "
    "tick over every non-ejected member) — the resource-cost side of "
    "the elastic-fleet ledger")
FLEET_PREEMPTIONS_TOTAL = REGISTRY.counter(
    "ollamamq_fleet_preemptions_total",
    "Termination notices served to preemptible members (POST "
    "/admin/preempt/{replica} or the fault plan's preempt_notice site); "
    "each triggers migrate-off-then-retire within the notice window — "
    "spot reclamation with zero dropped streams")

# -- router HA (fleet/ha.py; --ha / --standby-of) --------------------------
HA_SYNC_LAG_RECORDS = REGISTRY.gauge(
    "ollamamq_ha_sync_lag_records",
    "Replication records the warm standby has not yet applied (primary "
    "head seq minus last acked seq); primary-side it tracks the "
    "connected standby's ack, standby-side its own apply position — "
    "what a takeover would have to recover without")
HA_SYNC_RECORDS_TOTAL = REGISTRY.counter(
    "ollamamq_ha_sync_records_total",
    "Replication records shipped over /admin/ha/sync by kind ('wal' = "
    "admission-WAL records into the standby's WAL replica, 'journal' = "
    "decision events into the standby's journal spill)",
    labels=("kind",))
HA_TAKEOVERS_TOTAL = REGISTRY.counter(
    "ollamamq_ha_takeovers_total",
    "Standby promotions to primary by why ('primary_dead' = heartbeat "
    "loss past the takeover grace, 'handover' = graceful SIGTERM on the "
    "primary handed the fleet over)", labels=("why",))
HA_TAKEOVER_DURATION_MS = REGISTRY.histogram(
    "ollamamq_ha_takeover_duration_ms",
    "Promotion wall time (ms): primary declared dead to the standby "
    "serving with every unfinished WAL stream re-admitted — the EMA of "
    "this feeds promotion-window Retry-After hints",
    buckets=(10, 25, 50, 100, 250, 500, 1000, 2500, 5000, 10000, 30000))
HA_FENCED_CALLS_TOTAL = REGISTRY.counter(
    "ollamamq_ha_fenced_calls_total",
    "Stale-epoch router calls a member rejected after a takeover, by "
    "kind (placement / migrate / register) — each one is a zombie "
    "primary's write the epoch fence turned away, journaled epoch_fence",
    labels=("kind",))

# -- crash durability (durability/; --wal-dir) -----------------------------
WAL_FSYNC_MS = REGISTRY.histogram(
    "ollamamq_wal_fsync_ms",
    "Admission-WAL fsync latency (ms): how long the group-commit window "
    "plus the fsync itself held each durable write — the durability tax "
    "every ACKed enqueue pays under --wal-dir",
    buckets=(0.1, 0.5, 1, 2, 5, 10, 20, 50, 100, 250, 1000))
RECOVERED_STREAMS_TOTAL = REGISTRY.counter(
    "ollamamq_recovered_streams_total",
    "WAL'd requests handled by the cold-restart recovery pass, by "
    "outcome: 'replayed' (re-admitted token-exact with generated_ids "
    "pre-filled), 'finished' (budget already spent — only the terminal "
    "was surfaced), 'failed' (re-admission errored; the stream ends "
    "with an explicit error, never a silent drop)",
    labels=("outcome",))

# -- engine performance plane (telemetry/stepprof.py) ----------------------
# Closed site vocabulary for ollamamq_compile_total{site}: one per jit
# cache the engine fills (the compile ladder's rungs live in these).
COMPILE_SITES = ("ragged", "decode", "embed")
STEP_PHASE_MS = REGISTRY.histogram(
    "ollamamq_step_phase_ms",
    "Engine dispatch self-profiling: milliseconds each step spent per "
    "phase (host_prep = python batch composition, dispatch = issuing "
    "the jit'd computation — XLA compile on a fresh cache key, "
    "collect = device wait + D2H materialization, detok = the host "
    "emit loop; and between steps loop_admit = MQCore pops and "
    "placement, loop_other = the rest of the engine tick, loop_wait = "
    "the idle condvar wait), by step mode (ragged / spec_verify / decode "
    "/ embed / fake) — the always-on stepprof ring's metric face",
    buckets=(0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0,
             25.0, 50.0, 100.0, 250.0, 1000.0),
    labels=("phase", "mode"))
STEPS_OVERLAPPED_TOTAL = REGISTRY.counter(
    "ollamamq_steps_overlapped_total",
    "Steps launched while the step before them was still unsettled: the "
    "engine thread composed and dispatched them while the chip ran, and "
    "emitted the earlier step's tokens behind them", labels=("model",))
STEP_WASTED_ROWS_TOTAL = REGISTRY.counter(
    "ollamamq_step_wasted_rows_total",
    "Rows a step served whose output was dropped at settle because the "
    "request had already finished (EOS or a stop string seen one step "
    "late, a cancel between launch and settle): extra device work, never "
    "a token more or less", labels=("model",))
STEP_H2D_TRANSFERS_TOTAL = REGISTRY.counter(
    "ollamamq_step_h2d_transfers_total",
    "Host-to-device transfers the engine made for its step programs' "
    "inputs (`h2d_transfers` on a step sample), by step mode: one a "
    "step — the step's host inputs travel as one packed array",
    labels=("mode",))
STEP_H2D_BYTES_TOTAL = REGISTRY.counter(
    "ollamamq_step_h2d_bytes_total",
    "Bytes of those transfers (`h2d_bytes` on a step sample), by step "
    "mode", labels=("mode",))
DEVICE_DRY_SECONDS_TOTAL = REGISTRY.counter(
    "ollamamq_device_dry_seconds_total",
    "Seconds the chip had NOTHING queued before a step's launch, at "
    "least (`dry_lo_ms` on a step sample): from the first probe that saw "
    "the step ahead of it ready to the return of the launch. Time the "
    "engine thread spent in its idle wait is left out — a chip idle for "
    "want of requests is not dry. Over wall seconds: the idle share "
    "late launches cost, without a profiler", labels=("model",))
DEVICE_DRY_UPPER_SECONDS_TOTAL = REGISTRY.counter(
    "ollamamq_device_dry_upper_seconds_total",
    "The same gap at most (`dry_hi_ms`): counted from the last probe "
    "that saw the step ahead still busy. The true dry time lies between "
    "the two counters; they lie as far apart as the engine thread's "
    "marks (and as far as a blocking read lasted, where the step ahead "
    "was read before the launch)", labels=("model",))
STEPS_LAUNCHED_DRY_TOTAL = REGISTRY.counter(
    "ollamamq_steps_launched_dry_total",
    "Steps launched onto a chip that was known to have nothing queued "
    "(`dry_lo_ms` > 0): every step after a fused scan and every step of "
    "an n-gram --spec runtime by design, any other step only when the "
    "host was late", labels=("model",))
THREAD_CPU_SECONDS_TOTAL = REGISTRY.counter(
    "ollamamq_thread_cpu_seconds_total",
    "CPU seconds of the threads that serve: thread=\"engine\" the engine "
    "loop thread (an in-process fleet's several, summed), "
    "thread=\"server\" the thread that runs the HTTP event loop. Read "
    "from the threads' own CPU clocks when /metrics is rendered, never "
    "on the hot path; left out where the platform has no such clock",
    labels=("thread",))
PROCESS_CPU_SECONDS_TOTAL = REGISTRY.counter(
    "ollamamq_process_cpu_seconds_total",
    "CPU seconds of the whole server process (every thread), read when "
    "/metrics is rendered")
COMPILE_TOTAL = REGISTRY.counter(
    "ollamamq_compile_total",
    "XLA compiles the engine paid, by jit-cache site (ragged / decode "
    "/ embed) — exactly one per compile-ladder rung in steady state; "
    "a climbing rate past warmup is a ladder bug (compile_storm "
    "alert)", labels=("site",))
COMPILE_MS = REGISTRY.histogram(
    "ollamamq_compile_ms",
    "Wall milliseconds a step program's FIRST CALL held the dispatch "
    "path: tracing, lowering, the backend (XLA's and Mosaic's compile, "
    "or the persistent cache's retrieval) and the program's first run, "
    "all synchronous — the cost the step paid. The compile event splits "
    "it (`trace_ms` / `lower_ms` / `backend_ms` / `first_run_ms`)",
    buckets=(1, 5, 10, 25, 50, 100, 250, 500, 1000, 2500, 5000, 10000,
             30000, 60000, 120000))
COMPILE_PROGRAMS_TOTAL = REGISTRY.counter(
    "ollamamq_compile_programs_total",
    "Programs jax's backend built or fetched since process start — the "
    "step programs' first calls, the eager programs of start-up and "
    "whatever else compiled alike — by what the persistent compilation "
    "cache said: cache=\"hit\" fetched, cache=\"miss\" compiled and "
    "written, cache=\"off\" jax reported neither (no cache directory, or "
    "a program under the cache's thresholds). hit over hit + miss says "
    "whether a start was warm", labels=("cache",))
STARTUP_SECONDS = REGISTRY.gauge(
    "ollamamq_startup_seconds",
    "Seconds of start-up by phase (stepprof.START_PHASES: import / "
    "backend / weights / place / alloc / serve), set once when the HTTP "
    "server starts to listen; the phases are contiguous on the main "
    "thread and sum to ollamamq_ready_seconds", labels=("phase",))
READY_SECONDS = REGISTRY.gauge(
    "ollamamq_ready_seconds",
    "Seconds from the kernel's process start to the HTTP server "
    "starting to listen, set once; absent until then")

# -- host / device ---------------------------------------------------------
HBM_USED_BYTES = REGISTRY.gauge(
    "ollamamq_hbm_used_bytes",
    "Per-chip HBM in use (chips without memory_stats are omitted, "
    "never reported as 0)", labels=("chip", "host"))
HBM_TOTAL_BYTES = REGISTRY.gauge(
    "ollamamq_hbm_total_bytes",
    "Per-chip HBM capacity", labels=("chip", "host"))
UPTIME_SECONDS = REGISTRY.gauge(
    "ollamamq_uptime_seconds", "Engine uptime")

_LATENCY_HISTOGRAMS = (TTFT_MS, TPOT_MS, STEP_LATENCY_MS, PREFILL_LATENCY_MS)


def refresh_cpu_seconds(seconds) -> None:
    """At a scrape: `seconds` is stepprof.cpu_seconds() — totals the
    kernel keeps, so the counters are SET to them (never lowered)."""
    if seconds is None:
        return
    for role, v in seconds.items():
        child = (PROCESS_CPU_SECONDS_TOTAL.labels() if role == "process"
                 else THREAD_CPU_SECONDS_TOTAL.labels(thread=role))
        child.inc(max(0.0, v - child.value))


def configure_latency_buckets(bounds) -> None:
    """Apply the --metrics-buckets ladder to every latency histogram.
    Resets prior observations (boundaries don't translate); call at
    startup, before serving."""
    for h in _LATENCY_HISTOGRAMS:
        h.set_buckets(bounds)
