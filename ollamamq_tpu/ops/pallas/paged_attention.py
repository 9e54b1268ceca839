"""Pallas TPU kernel: ragged paged decode attention.

The jnp reference path (ops/attention.py:paged_decode_attention) gathers a
padded [B, max_pages*page_size, Hk, hd] context per step — materializing
the whole window in HBM traffic even for short sequences. This kernel
instead walks each sequence's ACTUAL pages: per batch element, double-
buffered DMA streams K/V pages HBM→VMEM while the previous page's partial
attention accumulates with an online (flash-style) softmax, so HBM reads
scale with true context length (ragged), not the padded maximum.

The page walk and the inner product are shared with the ragged kernel
(`ops/pallas/kv_contract.py`, which also holds the Mosaic layout
constraints — learned against the real v5e compiler — and which shapes
take which inner product): K/V move as flattened [page_size, Hk*hd] rows
in blocks through a ring of VMEM buffers, and each block folds into the
row's online-softmax state on the MXU — one contraction a (block, kv
head) for all `group` query heads that share the kv head — when group >
1, on the VPU with 0/1 segment matrices when group == 1. All GQA head
bookkeeping that needs a transpose happens OUTSIDE the kernel (`pack_q`
/ `unpack_o`, plain XLA), so every vector op keeps its layout end to end.
`head_dim % 128 == 0`: a kv head is a lane tile of the block, sliced for
free; `head_dim == 64`: the wrapper packs two heads a tile, the kernel
does not know. The row's blocks are a loop in the program; the lane
tiles (MXU body) or query-group heads (VPU body) are unrolled in Python —
at most 16 / 8 copies, a body small enough that a decode scan costs
~1.9 s to trace and lower (`kv_contract.py`: why not the tiles too).

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

from ollamamq_tpu.ops.pallas.kv_contract import (PageStream, make_inner,
                                                 ring_grid_spec, split_refs)

# Pages in flight per sequence: measured on v5e, 4-16 are within noise
# of each other (the DMA path is issue-overhead-bound); 8 is the middle.
RING = 8


def _decode_kernel(
    # scalar prefetch
    layer_ref,  # [1] SMEM: the pool layer this launch attends over
    page_table_ref,  # [B, max_pages] SMEM
    seq_lens_ref,  # [B] SMEM
    *refs,  # kv_contract.split_refs
    inner,  # kv_contract.Mxu | Vpu
    nbuf: int,
    max_pages: int,
):
    q_ref, hbm, o_ref, bufs, state, sems = split_refs(refs)
    b = pl.program_id(0)
    nb = pl.num_programs(0)
    seq_len = seq_lens_ref[b]
    page_size, bp = inner.page_size, inner.block_pages
    stream = PageStream(hbm, bufs, sems, layer_ref[0], page_table_ref,
                        page_size, bp)

    # Clamp to the table width: a seq_len beyond capacity must not index
    # page_table out of bounds (the jnp reference implicitly truncates the
    # context the same way).
    def pages_of(row):
        return jnp.minimum(
            pl.cdiv(seq_lens_ref[row], page_size), max_pages
        )

    num_pages = pages_of(b)
    inner.init(bufs, *state)

    # Fill the ring — but ONLY for the first grid program: every later
    # program's first `nbuf` blocks were started by its predecessor's
    # epilogue (cross-program prefetch), so the DMA pipeline never drains
    # at a program boundary. Starts and waits share the same "the page
    # exists" condition, so semaphore counts always balance.
    for i in range(nbuf):
        stream.start(i, b, i, num_pages, cond=b == 0)

    def body(p, _):
        slot = p % nbuf
        stream.wait(slot, b, p, num_pages)
        inner.update(
            q_ref, bufs, slot, (0, 0, 1, seq_len), p * (bp * page_size),
            state,
            # Ring slot consumed: refill it with the block `nbuf` ahead,
            # keeping nbuf-1 blocks in flight.
            lambda: stream.start(slot, b, p + nbuf, num_pages))
        return ()

    jax.lax.fori_loop(0, pl.cdiv(num_pages, bp), body, ())

    # Cross-program prefetch: start the NEXT batch element's first `nbuf`
    # blocks. Every one of this program's copies has been consumed by the
    # loop above (refills are guarded to existing pages), so all ring
    # slots are free; the next program starts no DMAs of its own and its
    # body waits land on copies already in flight. The row index is
    # clamped BEFORE the predicate so the last program never reads
    # seq_lens_ref out of bounds (the b+1 < nb guard then discards the
    # dummy value).
    succ = jnp.minimum(b + 1, nb - 1)
    for i in range(nbuf):
        stream.start(i, succ, i, pages_of(succ), cond=b + 1 < nb)

    inner.finish(o_ref, state)


@functools.partial(jax.jit,
                   static_argnames=("page_size", "interpret", "inner"))
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
    inner: str | None = None,  # tests and microbenchmarks only: the
    #   serving path leaves the inner product to kv_contract.choose_inner
) -> jnp.ndarray:
    B, H, hd = q.shape
    max_pages = page_table.shape[1]
    lanes = k_cache.shape[-1]
    Hk = lanes // hd
    inner = make_inner(inner, rows=1, group=H // Hk, num_kv_heads=Hk,
                       head_dim=hd, page_size=page_size)

    pools = [k_cache, v_cache]
    if k_scale is not None:  # an int8 pool's scale planes
        pools += [k_scale, v_scale]
    nbuf, grid_spec = ring_grid_spec(inner, RING, (B,), 3, pools)
    kernel = functools.partial(
        _decode_kernel,
        inner=inner,
        nbuf=nbuf,
        max_pages=max_pages,
    )

    q_packed = inner.pack_q(q)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q_packed.shape, q.dtype),
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      page_table.astype(jnp.int32), seq_lens.astype(jnp.int32),
      q_packed, *pools)
    return inner.unpack_o(out)
