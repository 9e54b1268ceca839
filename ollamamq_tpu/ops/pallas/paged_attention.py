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
(PrefetchScalarGridSpec pattern from the Pallas TPU guide). The programs
run in order on one core and share the ring: it runs over the launch's
rows as one stream of blocks, so a row's first blocks are started from
inside its predecessor's loop (`_decode_kernel`, and `kv_contract.py` on
what a cold block costs).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from ollamamq_tpu.ops.pallas.kv_contract import (PageStream, cdiv,
                                                 make_inner, mod,
                                                 ring_grid_spec, split_refs,
                                                 split_sink, split_window,
                                                 whole_blocks)

# A window layer's launch on the device trace: a name of its own, so that
# what reads the full layers' launches by name does not count these.
WINDOW_NAME = "swa_decode_attention_pallas"

# Pages in flight: two blocks under the Mxu body, eight pages under the
# Vpu body. Re-measured with the ring running over the launch's rows (my
# chip run, PR 38, `scripts/attn_kernel_bench.py --set
# paged_attention.RING=…`): 16 for 8 costs 64 rows over 200-380 tokens
# 0.089 → 0.122 ms at (8, 2, 128) and 0.332 → 0.376 at (16, 16, 128) — a
# row of fewer blocks than the ring starts its successor's before its
# loop, where nothing hides the issue — and buys rows of 2048 tokens
# 0.452 → 0.383: no cell holds such rows, so 8 stands.
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
    base_ref, refs = split_window(refs, inner.window)
    sink_ref, refs = split_sink(refs, inner.sink)
    q_ref, hbm, o_ref, bufs, state, sems, at_ref = split_refs(refs)
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
        rows = seq_lens_ref[row]
        if base_ref is not None:  # a window layer: from its first page on
            rows = lax.sub(rows, base_ref[row])
        return lax.min(cdiv(rows, page_size), max_pages)

    # The ring runs over the launch's rows as ONE stream of blocks: row
    # b's block p sits in slot (at + p) % nbuf, `at` the blocks of the
    # rows before it (carried in SMEM from program to program), and the
    # refill of a consumed slot that falls past this row's last block
    # starts the NEXT row's block instead. A row of n >= nbuf blocks thus
    # starts all of its successor's first nbuf blocks from inside its own
    # loop, nbuf trips before they are waited for: no row but the first
    # begins on a cold DMA, and no refill is skipped (`kv_contract.py`:
    # what either costs, and why the scalars are `lax` calls). Starts and
    # waits balance because a block is started exactly once — by its own
    # row's refill, by its predecessor's, or before the predecessor's
    # loop — and waited for once, by its own row. The row index is
    # clamped BEFORE the predicate so the last program never reads
    # seq_lens_ref out of bounds (the b+1 < nb guard then discards the
    # dummy value).
    num_pages = pages_of(b)
    n = cdiv(num_pages, bp)
    is_first = lax.eq(b, 0)
    has_succ = lax.lt(lax.add(b, 1), nb)
    succ = lax.min(lax.add(b, 1), lax.sub(nb, 1))
    succ_pages = pages_of(succ)

    at = lax.select(is_first, 0, at_ref[0])
    inner.init(*state)

    # What a program starts before its loop, under ONE predicate that a
    # row of nbuf blocks or more (after the first) skips as a whole.
    @pl.when(lax.bitwise_or(is_first,
                            lax.bitwise_and(has_succ, lax.lt(n, nbuf))))
    def _():
        for i in range(nbuf):
            # The first program fills its own ring; every other program's
            # first blocks were started by its predecessor.
            stream.start(i, b, i, num_pages, cond=is_first)
            # The successor's blocks that land in slots this row leaves
            # unused (a row of fewer than nbuf blocks) start right away.
            stream.start(mod(lax.add(at, lax.add(n, i)), nbuf), succ, i,
                         succ_pages,
                         cond=lax.bitwise_and(has_succ,
                                              lax.lt(lax.add(n, i), nbuf)))

    def body(p, _):
        slot = mod(lax.add(at, p), nbuf)
        stream.wait(slot)

        def refill():
            # Ring slot consumed: the block `nbuf` ahead in the stream,
            # this row's or the next row's.
            ahead = lax.add(p, nbuf)
            own = lax.lt(ahead, n)
            stream.start(slot, lax.select(own, b, succ),
                         lax.select(own, ahead, lax.sub(ahead, n)),
                         lax.select(own, num_pages, succ_pages),
                         cond=lax.bitwise_or(own, has_succ))

        pos0 = lax.mul(p, bp * page_size)
        if base_ref is not None:
            pos0 = lax.add(base_ref[b], pos0)
        inner.update(q_ref, bufs, slot, (0, 0, 1, seq_len), pos0, state,
                     refill)
        return ()

    jax.lax.fori_loop(0, n, body, ())
    at_ref[0] = mod(lax.add(at, n), nbuf)

    if sink_ref is None:
        inner.finish(o_ref, state)
    else:
        inner.finish(o_ref, state, sink_ref)


@functools.partial(jax.jit,
                   static_argnames=("page_size", "interpret", "inner",
                                    "window"))
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
    window: int = 0,  # a window layer's launch: a row sees its last
    #   `window` positions, and `page_table` lists its pages from position
    sink=None,  # [H] float32: a learned logit a head that joins the
    #   softmax's denominator and carries no value (kv_contract.Mxu.finish)
    pos_base=None,  # [B] on (ops/attention.py:ring_table; WINDOW_NAME)
) -> jnp.ndarray:
    B, H, hd = q.shape
    max_pages = page_table.shape[1]
    lanes = k_cache.shape[-1]
    Hk = lanes // hd
    # A value head's lanes: hd for every model but one whose K and V rows
    # differ in width (kv_contract.MxuSplit); with those equal and no sink
    # the launch is what it was before either existed.
    v_dim = v_cache.shape[-1] // Hk
    inner = make_inner(inner, rows=1, group=H // Hk, num_kv_heads=Hk,
                       head_dim=hd, page_size=page_size, window=window,
                       v_dim=v_dim if v_dim != hd else 0,
                       sink=sink is not None)

    pools = [k_cache, v_cache]
    if k_scale is not None:  # an int8 pool's scale planes
        pools += [k_scale, v_scale]
    base = [pos_base.astype(jnp.int32)] if window else []
    nbuf, grid_spec = ring_grid_spec(inner, RING, (B,), 3 + len(base), pools)
    kernel = functools.partial(
        _decode_kernel,
        inner=inner,
        nbuf=nbuf,
        max_pages=max_pages,
    )

    q_packed = inner.pack_q(q)
    packed_sink = [] if sink is None else [inner.pack_sink(sink)]
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(inner.o_shape(q_packed.shape),
                                       q.dtype),
        interpret=interpret, **({"name": WINDOW_NAME} if window else {}),
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      whole_blocks(page_table, inner), seq_lens.astype(jnp.int32), *base,
      q_packed, *packed_sink, *pools)
    return inner.unpack_o(out)
