"""Attention ops: causal prefill attention and paged decode attention.

The paged decode path is the TPU replacement for the reference's
one-request-per-backend model (/root/reference/src/dispatcher.rs:438):
many sequences share one forward step, each reading its own scattered KV
pages. The jnp implementations here are the semantic reference; the Pallas
ragged-paged-attention kernel (ollamamq_tpu/ops/pallas) is the fast path
and must match these numerically.

KV cache layout (flat token-slot pool, page-aligned):
    k_cache, v_cache: [num_layers, num_pages * page_size, kv_heads * head_dim]
    (an int8 pool's scale planes: [num_layers, slots, kv_heads])
A "page" is page_size contiguous slots; the host-side allocator
(engine/kv_cache.py) hands out page indices, and `flat_slot_indices`
translates (page_table, position) -> slot index. Every attention below
takes the WHOLE pool and a layer index: the pool rides the forwards'
layer loop as its carry (models/llama.py:scan_layers), the jnp paths
gather `pool[layer, slots]` and view only the gathered rows per head, and
the Pallas kernels DMA pages out of `pool[layer]` by index. Nothing here
slices a layer out of the pool or re-lays it out.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as PS

from ollamamq_tpu.ops.quant import QuantKV, kv_gather
from ollamamq_tpu.parallel.mesh import AXIS_TENSOR

NEG_INF = -1e30


def repeat_kv(x: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    """[.., L, kv_heads, hd] -> [.., L, kv_heads*n_rep, hd] (GQA head groups)."""
    if n_rep == 1:
        return x
    return jnp.repeat(x, n_rep, axis=-2)


def causal_attention(
    q: jnp.ndarray,  # [B, T, H, hd]
    k: jnp.ndarray,  # [B, T, Hk, hd]
    v: jnp.ndarray,  # [B, T, Hk, hd]
    seq_lens: jnp.ndarray,  # [B] valid lengths (padding masked out)
) -> jnp.ndarray:
    """Causal self-attention over a padded prefill batch. f32 softmax."""
    B, T, H, hd = q.shape
    n_rep = H // k.shape[2]
    k = repeat_kv(k, n_rep)
    v = repeat_kv(v, n_rep)
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32))
    logits = logits * scale
    pos = jnp.arange(T)
    causal = pos[None, :] <= pos[:, None]  # [q, k]
    valid = pos[None, None, :] < seq_lens[:, None, None]  # [B, 1, k]
    mask = causal[None, None, :, :] & valid[:, None, :, :]
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


def bidirectional_attention(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, seq_lens: jnp.ndarray
) -> jnp.ndarray:
    """Full (non-causal) attention for encoder/embedding models."""
    B, T, H, hd = q.shape
    n_rep = H // k.shape[2]
    k = repeat_kv(k, n_rep)
    v = repeat_kv(v, n_rep)
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32))
    logits = logits * scale
    pos = jnp.arange(T)
    valid = pos[None, None, None, :] < seq_lens[:, None, None, None]
    logits = jnp.where(valid, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


def flat_slot_indices(
    page_table: jnp.ndarray,  # [B, max_pages] int32 page ids
    positions: jnp.ndarray,  # [B, L] int32 token positions within each seq
    page_size: int,
) -> jnp.ndarray:
    """Translate per-sequence token positions to flat cache slot indices."""
    page = jnp.take_along_axis(page_table, positions // page_size, axis=-1)
    return page * page_size + positions % page_size


def paged_chunk_attention(
    q: jnp.ndarray,  # [B, C, H, hd] — a chunk of new tokens per sequence
    k_cache: jnp.ndarray,  # [L, S, Hk*hd] the whole slot pool
    v_cache: jnp.ndarray,
    layer,  # int32 scalar: the pool layer to attend over
    page_table: jnp.ndarray,  # [B, max_pages]
    start: jnp.ndarray,  # [B] global position of the chunk's first token
    chunk_lens: jnp.ndarray,  # [B] valid tokens in this chunk (<= C)
    page_size: int,
) -> jnp.ndarray:
    """Chunked-prefill attention: the chunk's K/V are already scattered
    into the cache, so each query at global position start+i attends to
    cache positions <= start+i. Generalizes decode attention (C == 1).
    """
    B, C, H, hd = q.shape
    max_pages = page_table.shape[1]
    L = max_pages * page_size
    positions = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (B, L))
    slots = flat_slot_indices(page_table, positions, page_size)  # [B, L]
    k = kv_gather(k_cache, layer, slots, hd)  # [B, L, Hk, hd] (int8 -> f32)
    v = kv_gather(v_cache, layer, slots, hd)
    n_rep = H // k.shape[2]
    k = repeat_kv(k, n_rep)
    v = repeat_kv(v, n_rep)
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    logits = jnp.einsum(
        "bchd,blhd->bhcl", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale  # [B, H, C, L]
    q_pos = start[:, None] + jnp.arange(C)[None, :]  # [B, C] global positions
    causal = positions[:, None, :] <= q_pos[:, :, None]  # [B, C, L]
    in_seq = positions[:, None, :] < (start + chunk_lens)[:, None, None]
    mask = (causal & in_seq)[:, None, :, :]
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhcl,blhd->bchd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


def paged_decode_attention(
    q: jnp.ndarray,  # [B, H, hd] one new token per sequence
    k_cache: jnp.ndarray,  # [L, S, Hk*hd] the whole slot pool
    v_cache: jnp.ndarray,
    layer,  # int32 scalar: the pool layer to attend over
    page_table: jnp.ndarray,  # [B, max_pages]
    seq_lens: jnp.ndarray,  # [B] context length INCLUDING the new token
    page_size: int,
) -> jnp.ndarray:
    """Decode attention: each query attends to its own paged context.

    The C == 1 case of paged_chunk_attention (the new token sits at
    position seq_len-1 and sees everything before it). jnp reference
    path — on TPU the Pallas kernel replaces it with per-page reads and
    no materialization.
    """
    out = paged_chunk_attention(
        q[:, None], k_cache, v_cache, layer, page_table,
        start=seq_lens - 1, chunk_lens=jnp.ones_like(seq_lens),
        page_size=page_size,
    )
    return out[:, 0]


def ragged_paged_attention(
    q: jnp.ndarray,  # [T, H, hd] flattened mixed-batch query stream
    k_cache: jnp.ndarray,  # [L, S, Hk*hd] the whole slot pool
    v_cache: jnp.ndarray,
    layer,  # int32 scalar: the pool layer to attend over
    page_table: jnp.ndarray,  # [B, max_pages] one row per sequence
    tok_seq: jnp.ndarray,  # [T] int32 sequence index of each token
    tok_pos: jnp.ndarray,  # [T] int32 kv position of each token (-1 = pad)
    kv_lens: jnp.ndarray,  # [B] context length incl. each seq's new tokens
    page_size: int,
) -> jnp.ndarray:
    """Ragged mixed-batch attention, materializing reference.

    One flattened token stream holds ANY mix of variable-length prefill
    spans and single decode tokens; each token attends causally over its
    own sequence's paged context (positions <= its kv position). The
    semantic twin of the Pallas ragged kernel
    (ops/pallas/ragged_attention.py) and the ground truth the blockwise
    serving path below is tested against. Padding tokens (tok_pos < 0)
    produce garbage rows the caller must ignore.
    """
    T, H, hd = q.shape
    B, max_pages = page_table.shape
    L = max_pages * page_size
    rows = page_table[jnp.clip(tok_seq, 0, B - 1)]  # [T, max_pages]
    positions = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (T, L))
    slots = flat_slot_indices(rows, positions, page_size)  # [T, L]
    k = kv_gather(k_cache, layer, slots, hd)  # [T, L, Hk, hd] (int8 -> f32)
    v = kv_gather(v_cache, layer, slots, hd)
    n_rep = H // k.shape[2]
    k = repeat_kv(k, n_rep)
    v = repeat_kv(v, n_rep)
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    logits = jnp.einsum(
        "thd,tlhd->thl", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale  # [T, H, L]
    causal = positions <= tok_pos[:, None]  # [T, L]
    in_seq = positions < kv_lens[jnp.clip(tok_seq, 0, B - 1)][:, None]
    mask = (causal & in_seq)[:, None, :]
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("thl,tlhd->thd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


def ragged_paged_attention_blockwise(
    q: jnp.ndarray,  # [T, H, hd] flattened mixed-batch query stream
    k_cache: jnp.ndarray,  # [L, S, Hk*hd] the whole slot pool
    v_cache: jnp.ndarray,
    layer,  # int32 scalar: the pool layer to attend over
    page_table: jnp.ndarray,  # [B, max_pages]
    tok_seq: jnp.ndarray,  # [T] int32 sequence index of each token
    tok_pos: jnp.ndarray,  # [T] int32 kv position of each token (-1 = pad)
    kv_lens: jnp.ndarray,  # [B]
    page_size: int,
    block_pages: int = 8,
) -> jnp.ndarray:
    """Non-materializing ragged attention: the jnp serving path.

    Walks the paged context in blocks of `block_pages` pages with an
    online (flash-style) softmax; the loop trip count is DYNAMIC —
    bounded by the deepest causal frontier in the batch — so HBM reads
    scale with the actual context, not the padded maximum. Numerics
    match ragged_paged_attention (same f32 online softmax; pinned in
    tests/test_ragged_attention.py)."""
    T, H, hd = q.shape
    B, max_pages = page_table.shape
    Hk = k_cache.shape[-1] // hd
    n_rep = H // Hk
    BLK = block_pages * page_size
    n_blocks = -(-max_pages // block_pages)  # static ceiling
    rows = page_table[jnp.clip(tok_seq, 0, B - 1)]  # [T, max_pages]
    end = tok_pos + 1  # per-token causal frontier (0 for padding)
    needed = jnp.max(-(-jnp.maximum(end, 0) // BLK))

    scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    qf = q.astype(jnp.float32) * scale  # [T, H, hd]

    def body(i, carry):
        m, l, acc = carry
        pidx = jnp.clip(
            i * block_pages + jnp.arange(block_pages), 0, max_pages - 1
        )
        pages = rows[:, pidx]  # [T, block_pages]
        pos = i * BLK + jnp.arange(BLK, dtype=jnp.int32)
        slots = (pages[:, :, None] * page_size
                 + jnp.arange(page_size)[None, None, :]).reshape(T, BLK)
        k = repeat_kv(kv_gather(k_cache, layer, slots, hd).astype(
            jnp.float32), n_rep)  # [T,BLK,H,hd]
        v = repeat_kv(kv_gather(v_cache, layer, slots, hd).astype(
            jnp.float32), n_rep)
        logits = jnp.einsum("thd,tlhd->thl", qf, k)  # [T, H, BLK]
        keep = (pos[None, :] <= tok_pos[:, None]) \
            & (pos[None, :] < end[:, None])  # [T, BLK]
        logits = jnp.where(keep[:, None, :], logits, NEG_INF)
        blk_m = jnp.max(logits, axis=-1)  # [T, H]
        new_m = jnp.maximum(m, blk_m)
        p = jnp.exp(logits - new_m[..., None])
        p = jnp.where(logits <= NEG_INF / 2, 0.0, p)
        corr = jnp.where(m <= NEG_INF / 2, 0.0, jnp.exp(m - new_m))
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum("thl,tlhd->thd", p, v)
        return new_m, l, acc

    m0 = jnp.full((T, H), NEG_INF, jnp.float32)
    l0 = jnp.zeros((T, H), jnp.float32)
    a0 = jnp.zeros((T, H, hd), jnp.float32)
    m, l, acc = jax.lax.fori_loop(
        0, jnp.minimum(needed, n_blocks), body, (m0, l0, a0)
    )
    out = acc / jnp.maximum(l, 1e-30)[..., None]  # [T, H, hd]
    return out.astype(q.dtype)


def _per_tensor_shard(mesh, kernel, q, k_cache, v_cache, *meta):
    """Run a Pallas attention `kernel(q, k_cache, v_cache, *meta)`.

    GSPMD cannot partition a Mosaic kernel, so on a mesh of more than one
    device the call is wrapped in a shard_map: attention heads are
    independent, so q splits on H over the "tensor" axis, the whole pools
    keep the sharding they are stored with (lanes split by kv head; an
    int8 pool's scale planes on Hk), and the layer index, the page table
    and the length metadata replicate — every shard runs the kernel on
    its own heads. Without a mesh (one device) the call is the plain
    kernel."""
    if mesh is None or mesh.size == 1:
        return kernel(q, k_cache, v_cache, *meta)
    heads = PS(None, AXIS_TENSOR, None)
    pool = PS(None, None, AXIS_TENSOR)  # [L, S, Hk*hd] and [L, S, Hk]
    if isinstance(k_cache, QuantKV):
        pool = QuantKV(pool, pool)
    return jax.shard_map(
        kernel, mesh=mesh,
        in_specs=(heads, pool, pool) + (PS(),) * len(meta),
        out_specs=heads, check_vma=False,
    )(q, k_cache, v_cache, *meta)


def _split_quant(k_cache, v_cache):
    """(k payload, v payload, scale kwargs) for a bf16 or int8 pool: int8
    payloads DMA as usual, the per-slot scale rows ride along and
    dequantize in-kernel."""
    if isinstance(k_cache, QuantKV):
        return k_cache.q, v_cache.q, {"k_scale": k_cache.s,
                                      "v_scale": v_cache.s}
    return k_cache, v_cache, {}


def ragged_attention_any(
    attn_impl: str,
    q: jnp.ndarray,  # [T, H, hd]
    k_cache: jnp.ndarray,  # [L, S, Hk*hd] the whole slot pool
    v_cache: jnp.ndarray,
    layer,  # int32 scalar: the pool layer to attend over
    page_table: jnp.ndarray,  # [B, max_pages]
    tok_seq: jnp.ndarray,  # [T] (jnp path metadata)
    tok_pos: jnp.ndarray,  # [T]
    kv_lens: jnp.ndarray,  # [B]
    q_start: jnp.ndarray,  # [B] (pallas path metadata)
    q_lens: jnp.ndarray,  # [B]
    page_size: int,
    interpret: bool = False,
    mesh=None,  # the GSPMD mesh the caller's jit runs over (None inside
    #             a shard_map or on one device)
) -> jnp.ndarray:
    """The ONE pallas-vs-jnp ragged-attention dispatch (mirror of
    paged_decode_attention_any), shared by models/llama.forward_ragged so
    the two paths cannot drift. Both metadata encodings travel together:
    per-token (tok_seq/tok_pos) feeds the jnp gather path, per-sequence
    (q_start/q_lens) rides the Pallas kernel's scalar prefetch."""
    if attn_impl == "pallas":
        from ollamamq_tpu.ops.pallas.ragged_attention import (
            ragged_paged_attention_pallas,
        )

        def kernel(q, kc, vc, layer, page_table, q_start, q_lens, kv_lens):
            kq, vq, scales = _split_quant(kc, vc)
            return ragged_paged_attention_pallas(
                q, kq, vq, layer, page_table, q_start, q_lens, kv_lens,
                page_size, interpret=interpret, **scales)

        return _per_tensor_shard(mesh, kernel, q, k_cache, v_cache, layer,
                                 page_table, q_start, q_lens, kv_lens)
    return ragged_paged_attention_blockwise(
        q, k_cache, v_cache, layer, page_table, tok_seq, tok_pos, kv_lens,
        page_size
    )


def paged_decode_attention_any(
    attn_impl: str,
    q: jnp.ndarray,  # [B, H, hd]
    k_cache: jnp.ndarray,  # [L, S, Hk*hd] the whole slot pool
    v_cache: jnp.ndarray,
    layer,  # int32 scalar: the pool layer to attend over
    page_table: jnp.ndarray,  # [B, max_pages]
    seq_lens: jnp.ndarray,  # [B]
    page_size: int,
    interpret: bool = False,
    mesh=None,  # see ragged_attention_any
) -> jnp.ndarray:
    """The ONE pallas-vs-jnp decode-attention dispatch
    (models/llama.forward_decode). The pallas import stays deferred: the
    kernel module only loads when selected."""
    if attn_impl == "pallas":
        from ollamamq_tpu.ops.pallas.paged_attention import (
            paged_decode_attention_pallas,
        )

        def kernel(q, kc, vc, layer, page_table, seq_lens):
            kq, vq, scales = _split_quant(kc, vc)
            return paged_decode_attention_pallas(
                q, kq, vq, layer, page_table, seq_lens, page_size,
                interpret=interpret, **scales)

        return _per_tensor_shard(mesh, kernel, q, k_cache, v_cache, layer,
                                 page_table, seq_lens)
    return paged_decode_attention(
        q, k_cache, v_cache, layer, page_table, seq_lens, page_size
    )
