"""Pallas TPU kernel: ragged paged decode attention.

The jnp reference path (ops/attention.py:paged_decode_attention) gathers a
padded [B, max_pages*page_size, Hk, hd] context per step — materializing
the whole window in HBM traffic even for short sequences. This kernel
instead walks each sequence's ACTUAL pages: per batch element, double-
buffered DMA streams K/V pages HBM→VMEM while the previous page's partial
attention accumulates with an online (flash-style) softmax, so HBM reads
scale with true context length (ragged), not the padded maximum.

Mosaic layout constraints (learned against the real v5e compiler):
  - DMA slices must be tile-aligned: a [.., Hk, hd=64] block sits padded
    inside 128-lane tiles and cannot be sliced, so K/V move as flattened
    [page_size, Hk*hd] rows (Hk*hd is a multiple of 128).
  - In-kernel reshapes/transposes that split or merge the lane dim are
    "unsupported shape cast" relayouts. GQA head bookkeeping therefore
    happens OUTSIDE the kernel: q arrives packed as [B, group, Hk*hd]
    (query-group-major, kv-segment lanes) and per-head score/weight
    segmentation uses constant 0/1 segment matrices on the MXU:
        scores_g = (k_row * q_g) @ SEG          [ps, Hk]
        expand_g = p_g @ SEG.T                  [ps, Hk*hd]
    so every vector op keeps its layout end to end.

Layout contract (matches engine/kv_cache.py):
    k_cache, v_cache: [L, S, Hk*hd] — the WHOLE slot pool in the layout
    it is stored in, left in HBM; `layer` (int32 scalar, scalar-
    prefetched) picks the layer, and a page is `page_size` contiguous
    slots starting at page_id * page_size: the kernel DMAs
    `k_hbm.at[layer, pl.ds(start, page_size)]`. The wrapper never
    slices, reshapes or copies the pool (the forwards carry it through
    their layer loop and update it in place); an int8 pool's scale
    planes are [L, S, Hk] likewise.
    page_table: [B, max_pages] int32 (trash page 0 padding)
    seq_lens:   [B] int32 — context length INCLUDING the current token

Grid: one program per batch element; page_table/seq_lens ride scalar
prefetch so the DMA offsets are known before the body runs
(PrefetchScalarGridSpec pattern from the Pallas TPU guide).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(
    # scalar prefetch
    layer_ref,  # [1] SMEM: the pool layer this launch attends over
    page_table_ref,  # [B, max_pages] SMEM
    seq_lens_ref,  # [B] SMEM
    # inputs + output + scratch (quantized pools append scale planes —
    # see the unpack below; layouts match the unquantized kernel)
    *refs,
    page_size: int,
    max_pages: int,
    num_heads: int,
    num_kv_heads: int,
    head_dim: int,
    ring: int,
    quantized: bool,
):
    if quantized:
        (q_ref, k_hbm, v_hbm, ks_hbm, vs_hbm, o_ref,
         k_buf, v_buf, ks_buf, vs_buf, acc, m_i, l_i, sems) = refs
    else:
        (q_ref, k_hbm, v_hbm, o_ref,
         k_buf, v_buf, acc, m_i, l_i, sems) = refs
        ks_hbm = vs_hbm = ks_buf = vs_buf = None
    b = pl.program_id(0)
    nb = pl.num_programs(0)
    layer = layer_ref[0]
    seq_len = seq_lens_ref[b]

    # Clamp to the table width: a seq_len beyond capacity must not index
    # page_table out of bounds (the jnp reference implicitly truncates the
    # context the same way).
    def pages_of(row):
        return jnp.minimum(
            pl.cdiv(seq_lens_ref[row], page_size), max_pages
        )

    num_pages = pages_of(b)
    group = num_heads // num_kv_heads
    lanes = num_kv_heads * head_dim

    def page_dma(slot, row, page_idx):
        page_id = page_table_ref[row, page_idx]
        start = page_id * page_size
        copies = [
            pltpu.make_async_copy(
                k_hbm.at[layer, pl.ds(start, page_size)], k_buf.at[slot],
                sems.at[slot, 0]),
            pltpu.make_async_copy(
                v_hbm.at[layer, pl.ds(start, page_size)], v_buf.at[slot],
                sems.at[slot, 1]),
        ]
        if quantized:
            copies.append(pltpu.make_async_copy(
                ks_hbm.at[layer, pl.ds(start, page_size)], ks_buf.at[slot],
                sems.at[slot, 2]))
            copies.append(pltpu.make_async_copy(
                vs_hbm.at[layer, pl.ds(start, page_size)], vs_buf.at[slot],
                sems.at[slot, 3]))
        return copies

    def start_page(slot, row, page_idx):
        for dma in page_dma(slot, row, page_idx):
            dma.start()

    # Fill the ring — but ONLY for the first grid program: every later
    # program's first `ring` pages were started by its predecessor's
    # epilogue (cross-program prefetch), so the DMA pipeline never drains
    # at a program boundary. Starts and waits share the same `i <
    # num_pages` condition, so semaphore counts always balance.
    for i in range(ring):
        @pl.when((b == 0) & (i < num_pages))
        def _(i=i):
            start_page(i % ring, b, i)

    acc[...] = jnp.zeros_like(acc)
    m_i[...] = jnp.full_like(m_i, NEG_INF)
    l_i[...] = jnp.zeros_like(l_i)

    scale = 1.0 / (head_dim ** 0.5)
    # Segment matrices: SEG[d, h] = 1 iff lane d belongs to kv head h.
    # Constant f32 [lanes, Hk] / [Hk, lanes]; they ride VMEM and let the
    # MXU do per-head lane reductions/expansions without relayouts.
    seg = (
        jax.lax.broadcasted_iota(jnp.int32, (lanes, num_kv_heads), 0)
        // head_dim
        == jax.lax.broadcasted_iota(jnp.int32, (lanes, num_kv_heads), 1)
    ).astype(jnp.float32)
    seg_t = (
        jax.lax.broadcasted_iota(jnp.int32, (num_kv_heads, lanes), 1)
        // head_dim
        == jax.lax.broadcasted_iota(jnp.int32, (num_kv_heads, lanes), 0)
    ).astype(jnp.float32)

    def body(p, _):
        slot = p % ring

        for dma in page_dma(slot, b, p):
            dma.wait()

        k = k_buf[slot].astype(jnp.float32)  # [ps, lanes]
        v = v_buf[slot].astype(jnp.float32)
        if quantized:
            # In-kernel dequant: per-head scale rows expand to lane
            # segments via the seg_t MXU trick (no relayouts).
            k = k * jax.lax.dot_general(
                ks_buf[slot], seg_t,
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            v = v * jax.lax.dot_general(
                vs_buf[slot], seg_t,
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        # Ring slot consumed (values loaded above): refill it with the
        # page `ring` ahead, keeping ring-1 copies in flight.
        @pl.when(p + ring < num_pages)
        def _():
            start_page(slot, b, p + ring)
        # Valid-position mask for this page (final page may be partial).
        pos = p * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (page_size, num_kv_heads), 0
        )
        valid = pos < seq_len  # [ps, Hk]

        for g in range(group):  # static unroll; group is small (1-8)
            qg = q_ref[0, g : g + 1, :].astype(jnp.float32)  # [1, lanes]
            # scores[t, h] = sum_d q[h-seg d] * k[t, d]  via masked-lane
            # elementwise product + segment-sum on the MXU.
            s = jax.lax.dot_general(
                k * qg, seg,
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # [ps, Hk]
            s = jnp.where(valid, s, NEG_INF)

            # Online softmax update for this query group.
            m_prev = m_i[g : g + 1, :]  # [1, Hk]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)  # [1, Hk]
            p_ij = jnp.exp(s - m_new)  # [ps, Hk]
            l_i[g : g + 1, :] = l_i[g : g + 1, :] * alpha + jnp.sum(
                p_ij, axis=0, keepdims=True
            )
            # Per-head weights expanded back to lane segments, then a
            # sublane reduction contracts over page positions.
            e = jax.lax.dot_general(
                p_ij, seg_t,
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [ps, lanes]
            contrib = jnp.sum(e * v, axis=0, keepdims=True)  # [1, lanes]
            alpha_l = jax.lax.dot_general(
                alpha, seg_t,
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [1, lanes]
            acc[g : g + 1, :] = acc[g : g + 1, :] * alpha_l + contrib
            m_i[g : g + 1, :] = m_new
        return ()

    jax.lax.fori_loop(0, num_pages, body, ())

    # Cross-program prefetch: start the NEXT batch element's first `ring`
    # pages. Every one of this program's copies has been consumed by the
    # loop above (refills are guarded to < num_pages), so all ring slots
    # are free; the next program starts no DMAs of its own and its body
    # waits land on copies already in flight. The row index is clamped
    # BEFORE the predicate so the last program never reads seq_lens_ref
    # out of bounds (the b+1 < nb guard then discards the dummy value).
    succ = jnp.minimum(b + 1, nb - 1)
    for i in range(ring):
        @pl.when((b + 1 < nb) & (i < pages_of(succ)))
        def _(i=i):
            start_page(i % ring, succ, i)

    denom = jax.lax.dot_general(
        jnp.maximum(l_i[...], 1e-20), seg_t,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [group, lanes]
    o_ref[0] = (acc[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("page_size", "interpret"))
def paged_decode_attention_pallas(
    q: jnp.ndarray,  # [B, H, hd]
    k_cache: jnp.ndarray,  # [L, S, Hk*hd] (int8 when k_scale is passed)
    v_cache: jnp.ndarray,  # [L, S, Hk*hd]
    layer,  # int32 scalar: the pool layer to attend over
    page_table: jnp.ndarray,  # [B, max_pages]
    seq_lens: jnp.ndarray,  # [B]
    page_size: int,
    interpret: bool = False,
    k_scale=None,  # [L, S, Hk] f32 per-slot per-head scales (int8 pools)
    v_scale=None,
) -> jnp.ndarray:
    quantized = k_scale is not None
    B, H, hd = q.shape
    max_pages = page_table.shape[1]
    lanes = k_cache.shape[-1]
    Hk = lanes // hd
    group = H // Hk

    # Pages in flight per sequence: measured on v5e, 4-16 are within noise
    # of each other (the DMA path is issue-overhead-bound); 8 is the middle.
    ring = 8
    kernel = functools.partial(
        _decode_kernel,
        page_size=page_size,
        max_pages=max_pages,
        num_heads=H,
        num_kv_heads=Hk,
        head_dim=hd,
        ring=ring,
        quantized=quantized,
    )

    in_specs = [
        pl.BlockSpec((1, group, lanes), lambda b, *_: (b, 0, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec(memory_space=pl.ANY),  # k stays in HBM
        pl.BlockSpec(memory_space=pl.ANY),  # v stays in HBM
    ]
    scratch = [
        pltpu.VMEM((ring, page_size, lanes), k_cache.dtype),
        pltpu.VMEM((ring, page_size, lanes), v_cache.dtype),
    ]
    if quantized:
        in_specs += [
            pl.BlockSpec(memory_space=pl.ANY),  # k scale rows (HBM)
            pl.BlockSpec(memory_space=pl.ANY),  # v scale rows (HBM)
        ]
        scratch += [
            pltpu.VMEM((ring, page_size, Hk), jnp.float32),
            pltpu.VMEM((ring, page_size, Hk), jnp.float32),
        ]
    scratch += [
        pltpu.VMEM((group, lanes), jnp.float32),
        pltpu.VMEM((group, Hk), jnp.float32),
        pltpu.VMEM((group, Hk), jnp.float32),
        pltpu.SemaphoreType.DMA((ring, 4 if quantized else 2)),
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, group, lanes), lambda b, *_: (b, 0, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=scratch,
    )

    # Pack q head-group-major so each kernel row g holds every kv head's
    # group-g query in its lane segment: q_packed[b, g, h*hd + d] =
    # q[b, h*group + g, d]. (Plain XLA transposes are free of Mosaic's
    # relayout limits; doing this outside the kernel keeps the kernel
    # relayout-free.)
    q_packed = (
        q.reshape(B, Hk, group, hd).transpose(0, 2, 1, 3).reshape(B, group, lanes)
    )
    operands = [q_packed, k_cache, v_cache]
    if quantized:
        operands += [k_scale, v_scale]
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, group, lanes), q.dtype),
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      page_table.astype(jnp.int32), seq_lens.astype(jnp.int32), *operands)
    return (
        out.reshape(B, group, Hk, hd).transpose(0, 2, 1, 3).reshape(B, H, hd)
    )
