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
intersect the tile, masking rows by span membership. That walk is a loop
IN THE PROGRAM (`lax.fori_loop` over the successors, cut at the last
sequence; `q_start` / `q_len` / `kv_len` / the page table are SMEM reads
at a dynamic row), not G_TILE predicated copies of the body: the ring
restarts per sequence, so nothing crosses a trip, a launch costs the
same within 2 %, and the traced body — which every start-up pays for
once a rung of the token ladder, `setup_s` — is an eighth of the
unrolled one (`kv_contract.py` has the numbers and says which loops stay
in Python). Per sequence it streams that sequence's pages HBM→VMEM
through a ring of block buffers and accumulates a flash-style online
softmax; the page loop is bounded by the tile's deepest causal frontier,
so an early prefill tile reads only the prefix it can see.

The page walk and the inner product are shared with the decode kernel
(`ops/pallas/kv_contract.py`, which also holds the Mosaic layout
constraints and which shapes take which inner product): per sequence the
kernel streams that sequence's pages in blocks through a ring of VMEM
buffers and folds each block into the tile's online-softmax state on
the MXU: one contraction a (block, kv head) for all G_TILE × group
row-heads of the tile (a decode row's tile-mates are masked out of its
sequence's blocks; a prefill tile's rows all share them). q arrives
packed for that inner product (`Mxu.pack_q`: `[n_tiles, lane tiles, M,
W]`, no transpose left for the kernel) and the output leaves in the
same layout.

Int8 KV pages (`k_scale`/`v_scale` passed): the payload DMAs exactly as
bf16 pages do (half the bytes), each page's fp32 [page_size, Hk] scale
rows ride a third/fourth DMA into their own VMEM buffers, and the block
is dequantised in-kernel right after the wait — scale rows expand to
lane segments with a 0/1 segment-matrix matmul, no relayout — before the
same contraction. Softmax/accumulation stay f32. Cross-tile DMA prefetch
(the decode kernel's cross-program epilogue) is intentionally absent for
now: sequence boundaries inside a tile make the hand-off non-trivial,
and the block loop already overlaps DMA with compute within a sequence.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ollamamq_tpu.ops.pallas.kv_contract import (G_TILE, PageStream,
                                                 make_inner, ring_grid_spec,
                                                 split_refs)

RING = 4  # pages in flight per sequence (the ring restarts per sequence)


def _ragged_kernel(
    # scalar prefetch
    layer_ref,  # [1] SMEM: the pool layer this launch attends over
    tile_seq_ref,  # [n_tiles] SMEM: first sequence overlapping each tile
    q_start_ref,  # [B] SMEM: stream offset of each sequence's span
    q_len_ref,  # [B] SMEM: span length (0 = padding row)
    kv_len_ref,  # [B] SMEM: context length incl. the span's tokens
    page_table_ref,  # [B, max_pages] SMEM
    *refs,  # kv_contract.split_refs
    inner,  # kv_contract.Mxu
    nbuf: int,
    max_pages: int,
    num_seqs: int,
):
    q_ref, hbm, o_ref, bufs, state, sems = split_refs(refs)
    t = pl.program_id(0)
    tile_start = t * G_TILE
    page_size, bp = inner.page_size, inner.block_pages
    stream = PageStream(hbm, bufs, sems, layer_ref[0], page_table_ref,
                        page_size, bp)
    inner.init(bufs, *state)

    s0 = tile_seq_ref[t]

    def one_sequence(j, _):
        s = s0 + j
        qs = q_start_ref[s]
        ql = q_len_ref[s]
        kv = kv_len_ref[s]
        overlaps = (
            (ql > 0)
            & (qs < tile_start + G_TILE)
            & (qs + ql > tile_start)
        )

        @pl.when(overlaps)
        def _():
            # Deepest causal frontier among this tile's rows of s bounds
            # the page walk: an early tile of a long prefill reads only
            # the prefix its own queries can see.
            last_tok = jnp.minimum(tile_start + G_TILE, qs + ql) - 1
            last_pos = kv - ql + (last_tok - qs)
            npages = jnp.minimum(
                pl.cdiv(last_pos + 1, page_size), max_pages
            )
            for i in range(nbuf):
                stream.start(i, s, i, npages)

            def body(b, _):
                slot = b % nbuf
                stream.wait(slot, s, b, npages)
                inner.update(
                    q_ref, bufs, slot, (tile_start, qs, ql, kv),
                    b * (bp * page_size), state,
                    # Ring slot consumed: refill it with the block `nbuf`
                    # ahead, keeping nbuf-1 blocks in flight.
                    lambda: stream.start(slot, s, b + nbuf, npages))
                return ()

            jax.lax.fori_loop(0, pl.cdiv(npages, bp), body, ())

        return ()

    # At most G_TILE sequences can have a token inside a G_TILE-token
    # tile (spans are contiguous, zero-length rows only trail the
    # stream), so a walk of G_TILE successors, cut at the last sequence,
    # covers every case: one loop in the program (module docstring).
    jax.lax.fori_loop(0, jnp.minimum(G_TILE, num_seqs - s0), one_sequence,
                      ())

    inner.finish(o_ref, state)


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
    T, H, hd = q.shape
    B, max_pages = page_table.shape
    lanes = k_cache.shape[-1]
    Hk = lanes // hd
    # Always the Mxu inner product: a tile's G_TILE rows share each block.
    inner = make_inner(None, rows=G_TILE, group=H // Hk, num_kv_heads=Hk,
                       head_dim=hd, page_size=page_size)

    Tp = -(-T // G_TILE) * G_TILE
    n_tiles = Tp // G_TILE
    # First sequence overlapping each tile: spans are contiguous and
    # ascending, so it is the first whose END lies past the tile start.
    ends = (q_start + q_lens).astype(jnp.int32)
    tile_first = jnp.searchsorted(
        ends, jnp.arange(n_tiles, dtype=jnp.int32) * G_TILE, side="right"
    ).astype(jnp.int32)

    pools = [k_cache, v_cache]
    if k_scale is not None:  # an int8 pool's scale planes
        pools += [k_scale, v_scale]
    nbuf, grid_spec = ring_grid_spec(inner, RING, (n_tiles,), 6, pools)
    kernel = functools.partial(
        _ragged_kernel,
        inner=inner,
        nbuf=nbuf,
        max_pages=max_pages,
        num_seqs=B,
    )

    q_packed = inner.pack_q(jnp.pad(q, ((0, Tp - T), (0, 0), (0, 0))))
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q_packed.shape, q.dtype),
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), tile_first,
      q_start.astype(jnp.int32), q_lens.astype(jnp.int32),
      kv_lens.astype(jnp.int32), page_table.astype(jnp.int32),
      q_packed, *pools)
    return inner.unpack_o(out)[:T]
