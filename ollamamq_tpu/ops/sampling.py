"""Token sampling under jit: greedy, temperature, top-k, top-p.

All branches are trace-friendly (no data-dependent Python control flow):
the sampling mode is encoded in per-sequence parameter vectors so one
compiled decode step serves heterogeneous per-request options — requests
with different temperatures share a batch, unlike the reference which
forwards options opaquely to Ollama.
"""

from __future__ import annotations

import dataclasses

import numpy as np

import jax
import jax.numpy as jnp


@dataclasses.dataclass
class SamplingParams:
    """Host-side per-request sampling options (Ollama/OpenAI option names)."""

    temperature: float = 0.0  # 0 => greedy
    top_k: int = 0  # 0 => disabled
    top_p: float = 1.0
    repeat_penalty: float = 1.0  # 1.0 => off (Ollama's default is 1.1)
    presence_penalty: float = 0.0  # additive, OpenAI semantics (0 => off)
    frequency_penalty: float = 0.0  # additive per occurrence (0 => off)
    # None => unseeded. Any provided integer — INCLUDING 0, which OpenAI
    # clients pass expecting reproducibility — maps to a seeded stream.
    seed: "int | None" = None  # stored as int32 > 0 after __post_init__
    max_tokens: int = 256
    stop: tuple = ()
    # Per-request deadline budget in ms from enqueue (0 = none). Not a
    # sampling knob, but it rides the options/body like one (and the
    # X-Deadline-Ms header overrides it): expired requests are dropped
    # at admission / before prefill instead of burning TPU time.
    deadline_ms: float = 0.0

    def __post_init__(self):
        # Non-positive / junk deadlines mean "no deadline".
        try:
            self.deadline_ms = max(0.0, float(self.deadline_ms or 0.0))
        except (TypeError, ValueError):
            self.deadline_ms = 0.0
        # Seeds ride int32 device arrays; an out-of-range value would raise
        # OverflowError in the engine thread (numpy 2 rejects lossy int32
        # assignment) and fail every in-flight request on the runtime. Fold
        # arbitrary client seeds (OpenAI seeds are commonly 64-bit) into
        # [1, 2^31-1] deterministically; seed=0 is a VALID seed (folds to
        # 1), distinct from absent (None -> 0 = engine-stream sampling).
        self.seed = 0 if self.seed is None else (
            int(self.seed) % 0x7FFFFFFE) + 1

    @classmethod
    def from_ollama_options(cls, options: dict, max_tokens_default: int) -> "SamplingParams":
        options = options or {}
        return cls(
            temperature=float(options.get("temperature", 0.8) or 0.0),
            top_k=int(options.get("top_k", 0) or 0),
            top_p=float(options.get("top_p", 1.0) or 1.0),
            repeat_penalty=float(options.get("repeat_penalty", 1.1) or 1.0),
            presence_penalty=float(options.get("presence_penalty", 0.0) or 0.0),
            frequency_penalty=float(options.get("frequency_penalty", 0.0) or 0.0),
            seed=options.get("seed"),  # absent/null => None => unseeded
            max_tokens=int(options.get("num_predict", max_tokens_default) or max_tokens_default),
            stop=tuple(options.get("stop", []) or []),
            deadline_ms=options.get("deadline_ms", 0.0),
        )

    @classmethod
    def from_openai(cls, body: dict, max_tokens_default: int) -> "SamplingParams":
        stop = body.get("stop") or []
        if isinstance(stop, str):
            stop = [stop]
        return cls(
            temperature=float(body.get("temperature", 1.0) or 0.0),
            top_k=0,
            top_p=float(body.get("top_p", 1.0) or 1.0),
            # Not an OpenAI field, but accepted for parity with clients that
            # pass Ollama-style options through the /v1 surface.
            repeat_penalty=float(body.get("repeat_penalty", 1.0) or 1.0),
            presence_penalty=float(body.get("presence_penalty", 0.0) or 0.0),
            frequency_penalty=float(body.get("frequency_penalty", 0.0) or 0.0),
            seed=body.get("seed"),  # absent/null => None => unseeded
            max_tokens=int(
                body.get("max_tokens") or body.get("max_completion_tokens") or max_tokens_default
            ),
            stop=tuple(stop),
            # Not an OpenAI field either; same pass-through rationale.
            deadline_ms=body.get("deadline_ms", 0.0),
        )


def recent_token_mask(recent: jnp.ndarray, vocab: int) -> jnp.ndarray:
    """[B, W] ring of recent token ids (-1 = empty) -> [B, V] int8 mask."""
    B, _ = recent.shape
    valid = (recent >= 0).astype(jnp.int8)
    mask = jnp.zeros((B, vocab), jnp.int8)
    return mask.at[jnp.arange(B)[:, None], jnp.clip(recent, 0)].max(valid)


def recent_token_counts(recent: jnp.ndarray, vocab: int) -> jnp.ndarray:
    """[B, W] ring of recent token ids (-1 = empty) -> [B, V] int32 counts."""
    B, _ = recent.shape
    valid = (recent >= 0).astype(jnp.int32)
    counts = jnp.zeros((B, vocab), jnp.int32)
    return counts.at[jnp.arange(B)[:, None], jnp.clip(recent, 0)].add(valid)


def apply_repeat_penalty(
    logits: jnp.ndarray,  # [B, V] float32
    recent: jnp.ndarray,  # [B, W] int32 — last-W context token ids (-1 pad)
    penalty: jnp.ndarray,  # [B] float (1.0 = off)
) -> jnp.ndarray:
    """llama.cpp-style repetition penalty over the recent-token window
    (repeat_last_n semantics): for tokens in the window, positive logits
    divide by the penalty and negative logits multiply by it."""
    mask = recent_token_mask(recent, logits.shape[1])
    p = penalty[:, None]
    penalized = jnp.where(logits > 0, logits / p, logits * p)
    return jnp.where((mask > 0) & (p != 1.0), penalized, logits)


def apply_penalties(
    logits: jnp.ndarray,  # [B, V] float32
    recent: jnp.ndarray,  # [B, W] int32 — last-W context token ids (-1 pad)
    repeat: jnp.ndarray,  # [B] multiplicative, llama.cpp semantics (1.0 = off)
    presence: jnp.ndarray,  # [B] additive once per seen token (0.0 = off)
    frequency: jnp.ndarray,  # [B] additive per occurrence (0.0 = off)
) -> jnp.ndarray:
    """Repetition control over the recent-token window: llama.cpp-style
    multiplicative repeat_penalty plus OpenAI-style additive presence /
    frequency penalties (counts come from the same window)."""
    counts = recent_token_counts(recent, logits.shape[1])
    seen = counts > 0
    p = repeat[:, None]
    penalized = jnp.where(logits > 0, logits / p, logits * p)
    out = jnp.where(seen & (p != 1.0), penalized, logits)
    out = out - frequency[:, None] * counts.astype(logits.dtype)
    return out - presence[:, None] * seen.astype(logits.dtype)


# Candidate pool for top-k / top-p thresholds. A full [B, V] sort per
# decode step is the single most expensive op in the sampler (V is 128K
# for Llama-3); lax.top_k over a fixed pool is ~an order of magnitude
# cheaper. Requests asking top_k > MAX_TOPK are clamped, and a nucleus
# wider than MAX_TOPK candidates degrades to top-MAX_TOPK — same spirit
# as llama.cpp's default top_k=40 pre-filter that the reference inherits
# via Ollama. Probabilities use the FULL softmax normalizer (logsumexp
# over all logits), so within the pool the nucleus cutoff is exact.
MAX_TOPK = 256


def _masked_scaled_logits(
    logits: jnp.ndarray,  # [B, V] float32
    temperature: jnp.ndarray,  # [B]
    top_k: jnp.ndarray,  # [B] int32 (0 = off)
    top_p: jnp.ndarray,  # [B]
    need_mask: bool = True,
):
    """(masked scaled logits, greedy argmax) shared by both samplers.
    `need_mask` is a trace-time flag: when the host knows no row in the
    batch uses top-k/top-p, the threshold computation is skipped."""
    B, V = logits.shape
    greedy = jnp.argmax(logits, axis=-1)

    safe_t = jnp.where(temperature > 0, temperature, 1.0)
    scaled = logits / safe_t[:, None]
    if not need_mask:
        return scaled, greedy

    K = min(MAX_TOPK, V)
    vals, _ = jax.lax.top_k(scaled, K)  # [B, K] descending

    # top-k mask: keep the k largest (k==0 -> keep all; k > K clamps).
    k_idx = jnp.clip(top_k - 1, 0, K - 1)
    kth = jnp.take_along_axis(vals, k_idx[:, None], axis=-1)  # [B,1]
    topk_mask = jnp.where((top_k > 0)[:, None], scaled >= kth, True)

    # top-p (nucleus) mask: exact probabilities for the pool via the full
    # normalizer; cutoff at the last token whose cumulative mass (before
    # itself) is below top_p.
    log_z = jax.scipy.special.logsumexp(scaled, axis=-1, keepdims=True)
    probs = jnp.exp(vals - log_z)  # [B, K]
    cum = jnp.cumsum(probs, axis=-1)
    cutoff_count = jnp.sum(cum - probs < top_p[:, None], axis=-1)  # >=1
    cut_idx = jnp.clip(cutoff_count - 1, 0, K - 1)
    p_kth = jnp.take_along_axis(vals, cut_idx[:, None], axis=-1)
    topp_mask = jnp.where((top_p < 1.0)[:, None], scaled >= p_kth, True)

    return jnp.where(topk_mask & topp_mask, scaled, -jnp.inf), greedy


def sampling_flags(temp, top_k, top_p, repeat, presence, frequency):
    """(need_penalties, need_mask, need_sample) from HOST-side parameter
    arrays. These are trace-time specialization flags: the engine keys its
    compiled step variants on them, so an all-greedy batch (the common
    /api/generate default) runs argmax only — no [B, V] scatter-counts, no
    top-k scan, no categorical draw. Each flag covers the whole batch;
    mixed batches take the general path for everyone."""
    return (
        bool(np.any(np.asarray(repeat) != 1.0)
             or np.any(np.asarray(presence) != 0.0)
             or np.any(np.asarray(frequency) != 0.0)),
        bool(np.any(np.asarray(top_k) > 0)
             or np.any(np.asarray(top_p) < 1.0)),
        bool(np.any(np.asarray(temp) > 0)),
    )


@jax.named_scope("sampling")
def maybe_apply_penalties(logits, recent, repeat, presence, frequency,
                          need_penalties: bool = True):
    """apply_penalties, skipped entirely at trace time when the host knows
    every row is neutral (repeat==1, presence==frequency==0)."""
    if not need_penalties:
        return logits
    return apply_penalties(logits, recent, repeat, presence, frequency)


def accept_prefix(
    draft: jnp.ndarray,  # [B, K] int32 proposed draft tokens
    greedy: jnp.ndarray,  # [B, K] int32 model argmax at each draft's position
    draft_len: jnp.ndarray,  # [B] int32 valid drafts per row (0 = none)
) -> jnp.ndarray:
    """[B] number of leading draft tokens the model verified.

    Greedy speculative verification: draft j is accepted iff every draft
    before it was accepted AND the model's argmax at its position equals
    it — the longest matching prefix, computed as the sum of a running
    product over the match mask (the first mismatch zeroes everything
    after it). Positions at or past draft_len never count, so k=0 rows
    answer 0. Exact: accepting this prefix and then taking the model's
    own next token reproduces the non-speculative greedy stream
    byte-for-byte."""
    K = draft.shape[1]
    if K == 0:
        return jnp.zeros(draft.shape[0], jnp.int32)
    valid = jnp.arange(K)[None, :] < draft_len[:, None]
    match = ((draft == greedy) & valid).astype(jnp.int32)
    return jnp.sum(jnp.cumprod(match, axis=1), axis=1).astype(jnp.int32)


@jax.named_scope("sampling")
def per_row_keys(
    key: jax.Array,  # engine-stream key for this dispatch
    seeds: jnp.ndarray,  # [B] int32; >0 = request-provided seed
    positions: jnp.ndarray,  # [B] int32 absolute position being sampled
) -> jnp.ndarray:
    """[B, 2] uint32 sampling keys. Seeded rows derive their key purely from
    (seed, position) — replaying the request reproduces the exact stream no
    matter what else shares the batch; unseeded rows draw from the engine
    stream, decorrelated per row."""
    n = seeds.shape[0]
    unseeded = jax.random.split(key, n)
    seeded = jax.vmap(jax.random.fold_in)(
        jax.vmap(jax.random.PRNGKey)(seeds), positions.astype(jnp.uint32)
    )
    return jnp.where((seeds > 0)[:, None], seeded, unseeded)


@jax.named_scope("sampling")
def sample_tokens_rowwise(
    logits: jnp.ndarray,  # [B, V] float32
    row_keys: jnp.ndarray,  # [B, 2] uint32 (per_row_keys)
    temperature: jnp.ndarray,  # [B]
    top_k: jnp.ndarray,  # [B] int32 (0 = off)
    top_p: jnp.ndarray,  # [B]
    need_mask: bool = True,
    need_sample: bool = True,
) -> jnp.ndarray:
    """sample_tokens with an independent key per row (per-request seeds)."""
    masked, greedy = _masked_scaled_logits(logits, temperature, top_k, top_p,
                                           need_mask)
    if not need_sample:
        return greedy.astype(jnp.int32)
    sampled = jax.vmap(lambda k, l: jax.random.categorical(k, l))(row_keys, masked)
    return jnp.where(temperature > 0, sampled, greedy).astype(jnp.int32)
