"""Pallas TPU kernel: ragged mixed-batch paged attention.

One launch processes a single FLATTENED token stream holding any mix of
variable-length prefill spans and single decode tokens — the "Ragged
Paged Attention" design (PAPERS.md): no per-sequence bucket padding, no
separate prefill/decode kernels, HBM reads that scale with each
sequence's true context.

Layout contract (matches engine/kv_cache.py and the decode kernel in
ops/pallas/paged_attention.py):
    q:          [T, H, hd] flattened queries; sequence s owns rows
                [q_start[s], q_start[s] + q_len[s]) and its tokens sit at
                kv positions kv_len[s] - q_len[s] .. kv_len[s] - 1.
    k/v cache:  [L, S, Hk*hd] the WHOLE slot pool, in the layout it is
                stored in (engine/kv_cache.py), left in HBM; `layer` (an
                int32 scalar, scalar-prefetched) picks the layer and
                page = page_size contiguous slots at page_id * page_size,
                so a page is the DMA `k_hbm.at[layer, pl.ds(start,
                page_size)]` — [page_size, Hk*hd] rows. The wrapper never
                slices, reshapes or copies the pool: the forwards carry
                it through their layer loop and update it in place.
                An int8 pool's scale planes are [L, S, Hk] likewise.
    page_table: [B, max_pages] int32 (trash page 0 padding).
    Spans are contiguous and ascending in stream order; padding rows
    carry q_len = 0 with q_start = T.

Grid: one program per G_TILE-token tile of the stream. A tile may span
several sequences (e.g. 8 decode tokens from 8 different sequences), so
per-tile scalar-prefetch metadata names the FIRST overlapping sequence
and the kernel walks forward over the (at most G_TILE) sequences that
intersect the tile, masking rows by span membership. Per sequence it
streams that sequence's pages HBM→VMEM double-buffered and accumulates a
flash-style online softmax per (row, query-group); the page loop is
bounded by the tile's deepest causal frontier, so an early prefill tile
reads only the prefix it can see.

Mosaic layout constraints follow the proven decode kernel: K/V move as
flattened [page_size, Hk*hd] rows, q arrives packed [T, group, Hk*hd]
(query-group-major, kv-segment lanes), and per-head segmentation uses
constant 0/1 segment matrices on the MXU so no in-kernel relayouts are
needed.

Int8 KV pages (`k_scale`/`v_scale` passed): the payload DMAs exactly as
bf16 pages do (half the bytes), each page's fp32 [page_size, Hk] scale
row rides a third/fourth DMA into its own VMEM buffer, and dequant
happens in-kernel right after the wait — scale rows expand to lane
segments with the same seg_t matmul the softmax bookkeeping uses, so the
int8 path adds no relayouts. Softmax/accumulation stay f32 as before. Cross-tile DMA prefetch (the decode kernel's cross-program
epilogue) is intentionally absent for now: sequence boundaries inside a
tile make the hand-off non-trivial, and the page loop already overlaps
DMA with compute within a sequence.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# Tokens per grid program. 8 keeps the q/o blocks one sublane tile tall
# and bounds the worst case (8 distinct decode sequences) to the same
# page-loop total work as 8 decode-kernel programs.
G_TILE = 8


def _ragged_kernel(
    # scalar prefetch
    layer_ref,  # [1] SMEM: the pool layer this launch attends over
    tile_seq_ref,  # [n_tiles] SMEM: first sequence overlapping each tile
    q_start_ref,  # [B] SMEM: stream offset of each sequence's span
    q_len_ref,  # [B] SMEM: span length (0 = padding row)
    kv_len_ref,  # [B] SMEM: context length incl. the span's tokens
    page_table_ref,  # [B, max_pages] SMEM
    # inputs (quantized pools append ks_hbm/vs_hbm scale planes)
    *refs,
    page_size: int,
    max_pages: int,
    num_heads: int,
    num_kv_heads: int,
    head_dim: int,
    ring: int,
    num_seqs: int,
    quantized: bool,
):
    if quantized:
        (q_ref, k_hbm, v_hbm, ks_hbm, vs_hbm, o_ref,
         k_buf, v_buf, ks_buf, vs_buf, acc, m_i, l_i, sems) = refs
    else:
        (q_ref, k_hbm, v_hbm, o_ref,
         k_buf, v_buf, acc, m_i, l_i, sems) = refs
        ks_hbm = vs_hbm = ks_buf = vs_buf = None
    t = pl.program_id(0)
    layer = layer_ref[0]
    tile_start = t * G_TILE
    group = num_heads // num_kv_heads
    lanes = num_kv_heads * head_dim
    scale = 1.0 / (head_dim ** 0.5)

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
            # Scale rows travel with their page: same layer and slot
            # indexing, a [page_size, Hk] f32 plane per page.
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

    acc[...] = jnp.zeros_like(acc)
    m_i[...] = jnp.full_like(m_i, NEG_INF)
    l_i[...] = jnp.zeros_like(l_i)

    # Segment matrices: SEG[d, h] = 1 iff lane d belongs to kv head h
    # (the decode kernel's relayout-free per-head reduction trick).
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

    s0 = tile_seq_ref[t]
    # At most G_TILE sequences can have a token inside a G_TILE-token
    # tile (spans are contiguous, zero-length rows only trail the
    # stream), so a static walk of G_TILE successors covers every case.
    for j in range(G_TILE):
        s = jnp.minimum(s0 + j, num_seqs - 1)
        qs = q_start_ref[s]
        ql = q_len_ref[s]
        kv = kv_len_ref[s]
        overlaps = (
            (s0 + j < num_seqs)
            & (ql > 0)
            & (qs < tile_start + G_TILE)
            & (qs + ql > tile_start)
        )

        @pl.when(overlaps)
        def _(s=s, qs=qs, ql=ql, kv=kv):
            # Deepest causal frontier among this tile's rows of s bounds
            # the page walk: an early tile of a long prefill reads only
            # the prefix its own queries can see.
            last_tok = jnp.minimum(tile_start + G_TILE, qs + ql) - 1
            last_pos = kv - ql + (last_tok - qs)
            npages = jnp.minimum(
                pl.cdiv(last_pos + 1, page_size), max_pages
            )
            for i in range(ring):
                @pl.when(i < npages)
                def _(i=i):
                    start_page(i % ring, s, i)

            def body(p, _):
                slot = p % ring
                for dma in page_dma(slot, s, p):
                    dma.wait()
                k = k_buf[slot].astype(jnp.float32)  # [ps, lanes]
                v = v_buf[slot].astype(jnp.float32)
                if quantized:
                    # Dequantize in-kernel: per-head scale rows expand to
                    # lane segments via the same seg_t MXU trick the
                    # softmax bookkeeping uses (no relayouts).
                    k = k * jax.lax.dot_general(
                        ks_buf[slot], seg_t,
                        dimension_numbers=(((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
                    v = v * jax.lax.dot_general(
                        vs_buf[slot], seg_t,
                        dimension_numbers=(((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)

                @pl.when(p + ring < npages)
                def _():
                    start_page(slot, s, p + ring)

                pos = p * page_size + jax.lax.broadcasted_iota(
                    jnp.int32, (page_size, num_kv_heads), 0
                )
                for r in range(G_TILE):
                    g_tok = tile_start + r
                    in_span = (g_tok >= qs) & (g_tok < qs + ql)
                    row_pos = kv - ql + (g_tok - qs)

                    @pl.when(in_span)
                    def _(r=r, row_pos=row_pos):
                        # Causal within the span + bounded by the
                        # sequence's written context.
                        valid = (pos <= row_pos) & (pos < kv)  # [ps, Hk]
                        for g in range(group):
                            idx = r * group + g
                            qg = q_ref[r, g:g + 1, :].astype(jnp.float32)
                            sc = jax.lax.dot_general(
                                k * qg, seg,
                                dimension_numbers=(((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32,
                            ) * scale  # [ps, Hk]
                            sc = jnp.where(valid, sc, NEG_INF)
                            m_prev = m_i[idx:idx + 1, :]  # [1, Hk]
                            m_new = jnp.maximum(
                                m_prev, jnp.max(sc, axis=0, keepdims=True)
                            )
                            # A page entirely beyond a row's causal
                            # frontier leaves every score at NEG_INF;
                            # guard the exps so the no-op update stays a
                            # no-op instead of adding exp(0) mass.
                            alpha = jnp.where(
                                m_prev <= NEG_INF / 2, 0.0,
                                jnp.exp(m_prev - m_new))
                            p_ij = jnp.where(
                                sc <= NEG_INF / 2, 0.0,
                                jnp.exp(sc - m_new))
                            l_i[idx:idx + 1, :] = (
                                l_i[idx:idx + 1, :] * alpha
                                + jnp.sum(p_ij, axis=0, keepdims=True))
                            e = jax.lax.dot_general(
                                p_ij, seg_t,
                                dimension_numbers=(((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32,
                            )  # [ps, lanes]
                            contrib = jnp.sum(e * v, axis=0, keepdims=True)
                            alpha_l = jax.lax.dot_general(
                                alpha, seg_t,
                                dimension_numbers=(((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32,
                            )  # [1, lanes]
                            acc[idx:idx + 1, :] = (
                                acc[idx:idx + 1, :] * alpha_l + contrib)
                            m_i[idx:idx + 1, :] = m_new
                return ()

            jax.lax.fori_loop(0, npages, body, ())

    denom = jax.lax.dot_general(
        jnp.maximum(l_i[...], 1e-20), seg_t,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [G_TILE*group, lanes]
    out = (acc[...] / denom).reshape(G_TILE, group, lanes)
    o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("page_size", "interpret"))
def ragged_paged_attention_pallas(
    q: jnp.ndarray,  # [T, H, hd] flattened mixed-batch queries
    k_cache: jnp.ndarray,  # [L, S, Hk*hd] (int8 when k_scale is passed)
    v_cache: jnp.ndarray,  # [L, S, Hk*hd]
    layer,  # int32 scalar: the pool layer to attend over
    page_table: jnp.ndarray,  # [B, max_pages]
    q_start: jnp.ndarray,  # [B] span offset per sequence (T for padding)
    q_lens: jnp.ndarray,  # [B] span length per sequence (0 for padding)
    kv_lens: jnp.ndarray,  # [B] context length incl. the span
    page_size: int,
    interpret: bool = False,
    k_scale=None,  # [L, S, Hk] f32 per-slot per-head scales (int8 pools)
    v_scale=None,
) -> jnp.ndarray:
    quantized = k_scale is not None
    T, H, hd = q.shape
    B, max_pages = page_table.shape
    lanes = k_cache.shape[-1]
    Hk = lanes // hd
    group = H // Hk

    Tp = -(-T // G_TILE) * G_TILE
    n_tiles = Tp // G_TILE
    # First sequence overlapping each tile: spans are contiguous and
    # ascending, so it is the first whose END lies past the tile start.
    ends = (q_start + q_lens).astype(jnp.int32)
    tile_first = jnp.searchsorted(
        ends, jnp.arange(n_tiles, dtype=jnp.int32) * G_TILE, side="right"
    ).astype(jnp.int32)

    ring = 4  # pages in flight per sequence (ring restarts per sequence)
    kernel = functools.partial(
        _ragged_kernel,
        page_size=page_size,
        max_pages=max_pages,
        num_heads=H,
        num_kv_heads=Hk,
        head_dim=hd,
        ring=ring,
        num_seqs=B,
        quantized=quantized,
    )

    in_specs = [
        pl.BlockSpec((G_TILE, group, lanes), lambda t, *_: (t, 0, 0),
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
        pltpu.VMEM((G_TILE * group, lanes), jnp.float32),
        pltpu.VMEM((G_TILE * group, Hk), jnp.float32),
        pltpu.VMEM((G_TILE * group, Hk), jnp.float32),
        pltpu.SemaphoreType.DMA((ring, 4 if quantized else 2)),
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(n_tiles,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((G_TILE, group, lanes),
                               lambda t, *_: (t, 0, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=scratch,
    )

    # Pack q head-group-major (see the decode kernel): row r holds every
    # kv head's group-g query in its lane segment.
    q_packed = (
        q.reshape(T, Hk, group, hd).transpose(0, 2, 1, 3).reshape(T, group, lanes)
    )
    if Tp != T:
        q_packed = jnp.pad(q_packed, ((0, Tp - T), (0, 0), (0, 0)))
    operands = [q_packed, k_cache, v_cache]
    if quantized:
        operands += [k_scale, v_scale]
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Tp, group, lanes), q.dtype),
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), tile_first,
      q_start.astype(jnp.int32), q_lens.astype(jnp.int32),
      kv_lens.astype(jnp.int32), page_table.astype(jnp.int32),
      *operands)
    return (
        out[:T].reshape(T, group, Hk, hd).transpose(0, 2, 1, 3).reshape(T, H, hd)
    )
