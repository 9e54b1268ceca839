"""Int8 quantization primitives: weights and paged KV cache.

Two quantized containers, both plain NamedTuples (JAX treats them as
pytrees, so they flow through jit/scan/donation/sharding unchanged):

  QuantTensor — a weight matrix as (q: int8, s: f32 per-channel scales).
    Symmetric per-channel quantization: q = round(w / s), s chosen per
    OUTPUT channel so each channel's max magnitude maps to 127. Layer
    matmul weights quantize along their LAST axis (the output features of
    "btd,de->bte"-shaped einsums); embed/lm_head quantize along axis 0
    (per vocab row — the output channel of the logits einsum AND the
    gathered row of the embedding lookup, so one scale vector serves
    both uses).

  QuantKV — one KV slot pool as (q: int8 [L, S, Hk*hd],
    s: f32 [L, S, Hk]). Scales are per token-slot per kv-head, stored
    page-aligned alongside the pool (slot index == page * page_size +
    offset), so the allocator/prefix-tree/preemption/rollback machinery
    is untouched: pages just shrink ~2x and their scale rows travel with
    the same page ids. Per-slot (not per-page-amax) scales keep writes
    exact and incremental — a decode step writes one token's row without
    requantizing the rest of the page.

The dequant-fused entry points keep quantized data in its narrow dtype
until inside the consuming op: `qeinsum` casts int8 weights to the
activation dtype inside the contraction (HBM streams int8 bytes; the
MXU accumulates in bf16/f32 as usual), and `kv_gather` dequantizes
gathered page rows straight to f32 for the softmax path.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax.numpy as jnp

# Epsilon floor for scales: an all-zero channel/row must not divide by 0.
_EPS = 1e-8


class QuantTensor(NamedTuple):
    """Per-channel symmetric int8 weight: w ≈ q * s (s broadcast along
    the quantized axis)."""

    q: Any  # int8 payload, original weight shape
    s: Any  # f32 scales, shaped to broadcast against q

    @property
    def shape(self):
        return self.q.shape

    @property
    def ndim(self):
        return self.q.ndim

    @property
    def dtype(self):
        return self.q.dtype

    @property
    def nbytes(self) -> int:
        return self.q.nbytes + self.s.nbytes


class QuantKV(NamedTuple):
    """One quantized KV slot pool: q int8 [L, S, Hk*hd] plus
    page-aligned per-slot per-head scales s f32 [L, S, Hk]."""

    q: Any
    s: Any

    @property
    def shape(self):
        return self.q.shape

    @property
    def ndim(self):
        return self.q.ndim

    @property
    def dtype(self):
        return self.q.dtype

    @property
    def nbytes(self) -> int:
        return self.q.nbytes + self.s.nbytes


# -- weights ---------------------------------------------------------------
def quantize_tensor(w, axis: int = -1) -> QuantTensor:
    """Per-channel symmetric int8 quantization of `w` along `axis` (the
    channel axis KEEPS its extent in s; every other axis of s matches w,
    reduced away). s keeps a broadcastable singleton where the reduced
    axes were NOT — concretely: s = amax(|w|, all axes except `axis`)?
    No: per-channel means ONE scale per slice along `axis`... the
    convention here is one scale per index of `axis`, shared by the
    whole slice — but layer stacks carry a leading L that must stay
    per-layer. So the reduction is over every axis EXCEPT leading
    "batch-like" axes and `axis` itself: for a [L, d, e] stack with
    axis=-1 the scales are [L, e]; for [V, D] with axis=0 they are [V].
    """
    wf = jnp.asarray(w, jnp.float32)
    nd = wf.ndim
    axis = axis % nd
    if axis == nd - 1:
        # [..., d, e] -> reduce d: scales [..., e] (per trailing channel,
        # per leading layer).
        amax = jnp.max(jnp.abs(wf), axis=-2)
        s = jnp.maximum(amax, _EPS) / 127.0
        q = jnp.clip(jnp.round(wf / s[..., None, :]), -127, 127)
        return QuantTensor(q.astype(jnp.int8), s.astype(jnp.float32))
    if axis == 0:
        # [V, ...] -> reduce everything else: scales [V] (per row).
        amax = jnp.max(jnp.abs(wf), axis=tuple(range(1, nd)))
        s = jnp.maximum(amax, _EPS) / 127.0
        sb = s.reshape((-1,) + (1,) * (nd - 1))
        q = jnp.clip(jnp.round(wf / sb), -127, 127)
        return QuantTensor(q.astype(jnp.int8), s.astype(jnp.float32))
    raise ValueError(f"unsupported quantization axis {axis} for ndim {nd}")


def dequantize_tensor(t: QuantTensor, axis: int = -1, dtype=jnp.float32):
    """Inverse of quantize_tensor (tests/roundtrip bounds)."""
    qf = t.q.astype(jnp.float32)
    nd = qf.ndim
    axis = axis % nd
    if axis == nd - 1:
        return (qf * t.s[..., None, :]).astype(dtype)
    sb = t.s.reshape((-1,) + (1,) * (nd - 1))
    return (qf * sb).astype(dtype)


def qeinsum(spec: str, x, w):
    """Dequant-fused einsum over a last-axis-quantized weight: the int8
    payload is cast to the activation dtype INSIDE the contraction (XLA
    fuses the convert, so HBM streams half the bytes of bf16) and the
    f32 per-channel scale lands on the output's trailing channel axis.
    Raw arrays pass straight through — every matmul call site uses this
    one entry point, so quantized params flow through the forwards with
    no shape changes."""
    if isinstance(w, QuantTensor):
        y = jnp.einsum(spec, x, w.q.astype(x.dtype))
        return (y * w.s).astype(x.dtype)
    return jnp.einsum(spec, x, w)


def embed_lookup(embed, tokens, dtype):
    """Embedding-row gather with optional row-quantized table: gathered
    int8 rows dequantize by their row scale. `dtype` names the activation
    dtype (the caller's norm weights carry it — norms stay unquantized)."""
    if isinstance(embed, QuantTensor):
        rows = embed.q[tokens].astype(dtype)
        return (rows * embed.s[tokens][..., None]).astype(dtype)
    return embed[tokens].astype(dtype)


def logits_head(x, head):
    """lm_head/tied-embed logits einsum ("btd,vd->btv") in f32, with the
    row-quantized head dequant-fused: per-vocab-row scales multiply the
    logit columns."""
    if isinstance(head, QuantTensor):
        y = jnp.einsum("btd,vd->btv", x.astype(jnp.float32),
                       head.q.astype(jnp.float32))
        return y * head.s
    return jnp.einsum("btd,vd->btv", x.astype(jnp.float32),
                      head.astype(jnp.float32))


# -- KV cache --------------------------------------------------------------
def kv_quantize(vals):
    """Quantize K/V rows [..., Hk, hd] -> (int8 rows, f32 [..., Hk]
    scales): symmetric amax over head_dim per token per head."""
    vf = jnp.asarray(vals, jnp.float32)
    amax = jnp.max(jnp.abs(vf), axis=-1)
    s = jnp.maximum(amax, _EPS) / 127.0
    q = jnp.clip(jnp.round(vf / s[..., None]), -127, 127).astype(jnp.int8)
    return q, s.astype(jnp.float32)


def kv_write(cache, layer, slots, vals):
    """Scatter-write the step's K/V rows into layer `layer` of the WHOLE
    pool ([L, S, Hk*hd]), quantizing on the fly when the pool is int8:
    ONE scatter on the pool itself, so a pool that rides a loop as its
    carry is updated in place and no layer slice is ever materialised.
    `slots` indexes the slot axis; `vals` is [*slots.shape, Hk, hd] and
    takes the pool's row shape (a few hundred rows: free). Returns the
    updated pool (same container type — QuantKV scatters payload AND
    scales)."""
    if isinstance(cache, QuantKV):
        q, s = kv_quantize(vals)
        return QuantKV(
            cache.q.at[layer, slots].set(
                q.reshape(slots.shape + cache.q.shape[2:])),
            cache.s.at[layer, slots].set(s))
    return cache.at[layer, slots].set(
        vals.reshape(slots.shape + cache.shape[2:]))


def kv_gather(cache, layer, slots, head_dim: int):
    """Gather rows of layer `layer` from the whole pool as
    [*slots.shape, Hk, hd], dequantizing int8 pools to f32 (the softmax
    path consumes f32 regardless of pool dtype). Only the gathered rows
    are viewed per head; the pool is never sliced or reshaped."""
    if isinstance(cache, QuantKV):
        s = cache.s[layer, slots]
        q = cache.q[layer, slots].reshape(s.shape + (head_dim,))
        return q.astype(jnp.float32) * s[..., None]
    return cache[layer, slots].reshape(slots.shape + (-1, head_dim))
