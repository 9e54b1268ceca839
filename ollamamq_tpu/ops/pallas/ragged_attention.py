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
    carry q_len = 0 with q_start = T and only trail the live rows (a
    tile's walk ends at the first row that has no token in it).

Grid: one program a stretch of the stream, and THE TILE FOLLOWS THE SPAN
(PR 48). A tile is G_TILE = 8 stream tokens: the rows that share a K/V
block's trip. It may span several sequences (8 decode tokens from 8
different sequences), so per-tile scalar-prefetch metadata names the FIRST
overlapping sequence and the kernel walks forward over the (at most
G_TILE) sequences that intersect the tile, masking rows by span
membership. That walk is a loop IN THE PROGRAM (`lax.while_loop` over the
successors, ended at the last sequence with a row in the tile; `q_start` /
`q_len` / `kv_len` / the page table are SMEM reads at a dynamic row), not
G_TILE predicated copies of the body: a launch costs no more, and the
traced body — which every start-up pays for once a rung of the token
ladder, `setup_s` — is an eighth of the unrolled one (`kv_contract.py` has
the numbers and says which loops stay in Python). Per sequence it streams
that sequence's pages HBM→VMEM through a ring of block buffers and
accumulates a flash-style online softmax; the page loop is bounded by the
tile's deepest causal frontier, so an early prefill tile reads only the
prefix it can see.
    But a prefill span's tiles all walk the SAME blocks: 63 tiles of a
507-token chunk over 8 k of context stream and contract its ~64 blocks 63
times, each trip bound by a fixed chain latency and by K/V tiles latched
into the MXU for 64 row-heads. So on a rung of 2 * TALL tokens or more
(`kv_contract.programs_height`: a static shape) a program holds TALL = 64
tokens, TALL // G_TILE tiles of the packed q (`q_ref[:, t]`, merged over
its leading dimension at no cost), and chooses ONE of two bodies by a
scalar test on `q_start` / `q_len` (`whole`): a program whose stretch
[p * TALL, (p + 1) * TALL) lies inside one span runs ONE walk, up to the
stretch's causal frontier, whose every block is contracted once for all
TALL × group row-heads (`Mxu.update(..., sub=None)`; the mask's token of a
row from the row's index); any other program — decode rows, a span's head
and tail, padding — runs its tiles' walks one after another as above, each
on its slice of the same scratch. The arithmetic a row sees is the same:
same pairs, same blocks, same operands; only how many rows share a trip
changes. A rung of fewer than 2 * TALL tokens cannot hold a whole stretch
beside a decode row: its programs are one tile and the tall body is not
traced (the 64-row decode-only step is such a rung). One `pallas_call` a
layer either way.

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
same contraction. Softmax/accumulation stay f32. The ring of block
buffers runs over the launch's (tile, sequence) walks as one stream — a
walk's last refills start its successor's first blocks, within a tile
and across tiles (the programs run in order on one core) — so only the
launch's first walk begins on a cold DMA (`_ragged_kernel`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from ollamamq_tpu.ops.pallas.kv_contract import (G_TILE, PageStream, cdiv,
                                                 make_inner, mod,
                                                 programs_height,
                                                 ring_grid_spec, split_refs,
                                                 split_sink, split_window,
                                                 whole_blocks)

# Pages in flight: one block is the least a ring holds, and it holds two.
# 12 or 16 pages (3 or 4 blocks) read 3-15 % slower on 64 rows over
# 200-380 tokens at every shape and 12 % faster at 2048 tokens (my chip
# run, PR 38; `paged_attention.RING` has the reason).
RING = 4
# A window layer's launch on the device trace: a name of its own, so that
# what reads the full layers' launches by name does not count these.
WINDOW_NAME = "swa_ragged_attention_pallas"


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
    base_ref, refs = split_window(refs, inner.window)
    sink_ref, refs = split_sink(refs, inner.sink)
    q_ref, hbm, o_ref, bufs, state, sems, at_ref = split_refs(refs)
    subs = inner.subs  # tiles a program holds; 1: no tall body is traced
    height = subs * G_TILE
    prog = pl.program_id(0)
    n_progs = pl.num_programs(0)
    n_tiles = n_progs if subs == 1 else lax.mul(n_progs, subs)
    page_size, bp = inner.page_size, inner.block_pages
    stream = PageStream(hbm, bufs, sems, layer_ref[0], page_table_ref,
                        page_size, bp)
    inner.init(*state)

    def walk_pages(lo, hi, s):
        """Pages of sequence `s` that a walk for stream tokens [lo, hi)
        reads — up to the deepest causal frontier among those of them
        that are s's, so an early tile of a long prefill reads only the
        prefix its own queries can see — and 0 where s is no sequence or
        has no token there. (`lax` calls, not operators: `kv_contract.py`
        says why.)"""
        row = lax.clamp(0, s, num_seqs - 1)
        qs, ql, kv = q_start_ref[row], q_len_ref[row], kv_len_ref[row]
        end = lax.add(qs, ql)
        overlaps = functools.reduce(lax.bitwise_and, (
            lax.ge(s, 0), lax.lt(s, num_seqs), lax.gt(ql, 0),
            lax.lt(qs, hi), lax.gt(end, lo)))
        # kv - ql + (last_tok - qs) + 1, last_tok = min(hi, end) - 1
        frontier = lax.sub(lax.add(lax.sub(kv, ql), lax.min(hi, end)), qs)
        if base_ref is not None:  # a window layer: from its first page on
            frontier = lax.sub(frontier, base_ref[row])
        return lax.select(
            overlaps, lax.min(cdiv(frontier, page_size), max_pages), 0)

    def whole(p):
        """Is program p's stretch of the stream wholly inside ONE span?
        Then one tall walk serves it: its first sequence's, every tile
        merged along M (`kv_contract.tall_tokens` is this test on the
        host)."""
        lo = lax.mul(p, height)
        row = lax.min(tile_seq_ref[lax.mul(p, subs)], num_seqs - 1)
        qs = q_start_ref[row]
        return lax.bitwise_and(
            lax.le(qs, lo),
            lax.ge(lax.add(qs, q_len_ref[row]), lax.add(lo, height)))

    # A launch is ONE stream of walks — in grid order; a tall program is
    # one walk, any other program's are (tile, sequence) pairs, its tiles
    # and a tile's sequences ascending — and the ring runs over their
    # blocks without a break: a walk's block b sits in slot (at + b) %
    # nbuf, `at` the blocks of the walks before it, and the refill of a
    # consumed slot that falls past the walk's last block starts the NEXT
    # walk's block instead, so no walk but the launch's first begins on a
    # cold DMA (`kv_contract.py` has the numbers).
    def one_walk(s, pages, at, lo, succ, succ_pages, sub):
        """Sequence s's walk for the tile at `lo` (`sub`; None: for the
        whole program, tall), then the ring's position after it."""
        n = cdiv(pages, bp)
        row = lax.max(s, 0)
        span = (lo, q_start_ref[row], q_len_ref[row], kv_len_ref[row])

        # The successor's blocks that land in slots this walk leaves
        # unused start right away; a walk of nbuf blocks or more skips
        # them as a whole.
        @pl.when(lax.lt(n, nbuf))
        def _():
            for i in range(nbuf):
                stream.start(mod(lax.add(at, lax.add(n, i)), nbuf), succ, i,
                             succ_pages, cond=lax.lt(lax.add(n, i), nbuf))

        def body(b, _):
            slot = mod(lax.add(at, b), nbuf)
            stream.wait(slot)

            def refill():
                # Ring slot consumed: the block `nbuf` ahead in the
                # stream, this walk's or the next walk's.
                ahead = lax.add(b, nbuf)
                own = lax.lt(ahead, n)
                stream.start(slot, lax.select(own, s, succ),
                             lax.select(own, ahead, lax.sub(ahead, n)),
                             lax.select(own, pages, succ_pages))

            pos0 = lax.mul(b, bp * page_size)
            if base_ref is not None:
                pos0 = lax.add(base_ref[row], pos0)
            inner.update(q_ref, bufs, slot, span, pos0, state, refill, sub)
            return ()

        jax.lax.fori_loop(0, n, body, ())
        return mod(lax.add(at, n), nbuf)

    def entry(tile, tokens):
        """(sequence, pages) of the first walk of the program or tile that
        begins at tile `tile` (clamped to the launch) and holds `tokens`
        tokens; no pages past the launch's end."""
        at_tile = lax.min(tile, lax.sub(n_tiles, 1))
        lo = lax.mul(at_tile, G_TILE)
        s = tile_seq_ref[at_tile]
        return s, lax.select(lax.lt(tile, n_tiles),
                             walk_pages(lo, lax.add(lo, tokens), s), 0)

    tall = next_tall = False  # static: a rung of one tile a program
    if subs > 1:
        tall = whole(prog)
        after = lax.add(prog, 1)
        next_tall = lax.bitwise_and(
            lax.lt(after, n_progs),
            whole(lax.min(after, lax.sub(n_progs, 1))))
    at0 = lax.select(lax.eq(prog, 0), 0, at_ref[0])

    def tile_walks(j, at):
        """The walks of tile j of this program, as every program's were
        before there was a tall one: the sequences with a row in the
        tile, in a loop that ends at the last of them. A walk's successor
        is the next sequence if it has a row in this tile, else the next
        tile's first walk — the next PROGRAM's after the last tile, which
        may be a tall one; every tile runs its first walk even when it is
        empty (a tile past the stream's end), so the chain never breaks,
        and the launch's first tile begins one walk EARLIER, on an empty
        walk whose successor is the launch's first: that is what starts
        the first blocks."""
        tile = prog if subs == 1 else lax.add(lax.mul(prog, subs), j)
        lo = lax.mul(tile, G_TILE)
        hi = lax.add(lo, G_TILE)
        next_height = G_TILE if subs == 1 else lax.select(
            lax.bitwise_and(lax.eq(j, subs - 1), next_tall), height, G_TILE)
        next_first, next_pages = entry(lax.add(tile, 1), next_height)
        s0 = tile_seq_ref[tile]

        def walk(carry):
            s, pages, _, at = carry
            after = lax.add(s, 1)
            stay_pages = walk_pages(lo, hi, after)
            stays = lax.gt(stay_pages, 0)
            at = one_walk(s, pages, at, lo,
                          lax.select(stays, after, next_first),
                          lax.select(stays, stay_pages, next_pages), j)
            return (after, stay_pages,
                    lax.convert_element_type(stays, jnp.int32), at)

        first = lax.eq(tile, 0)
        *_, at = jax.lax.while_loop(
            lambda carry: lax.gt(carry[2], 0), walk,
            (lax.select(first, lax.sub(s0, 1), s0),
             lax.select(first, 0, walk_pages(lo, hi, s0)),
             jnp.int32(1), at))
        return at

    if subs == 1:
        at_ref[0] = tile_walks(0, at0)
    else:
        @pl.when(lax.bitwise_not(tall))
        def _():
            at_ref[0] = jax.lax.fori_loop(0, subs, tile_walks, at0)

        @pl.when(tall)
        def _():
            lo = lax.mul(prog, height)
            s, pages = entry(lax.mul(prog, subs), height)
            for i in range(nbuf):  # the launch's first blocks
                stream.start(i, s, i, pages, cond=lax.eq(prog, 0))
            at_ref[0] = one_walk(
                s, pages, at0, lo,
                *entry(lax.mul(lax.add(prog, 1), subs),
                       lax.select(next_tall, height, G_TILE)), None)

    if sink_ref is None:
        inner.finish(o_ref, state)
    else:
        inner.finish(o_ref, state, sink_ref)


@functools.partial(jax.jit,
                   static_argnames=("page_size", "interpret", "window"))
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
    window: int = 0,  # a window layer's launch: a token sees its last
    #   `window` positions, and `page_table` lists a row's pages from
    sink=None,  # [H] float32: a learned logit a head that joins the
    #   softmax's denominator and carries no value (kv_contract.Mxu.finish)
    pos_base=None,  # [B] position on (ops/attention.py:ring_table;
    #   WINDOW_NAME on the trace)
) -> jnp.ndarray:
    T, H, hd = q.shape
    B, max_pages = page_table.shape
    lanes = k_cache.shape[-1]
    Hk = lanes // hd
    # A value head's lanes: hd for every model but one whose K and V rows
    # differ in width (kv_contract.MxuSplit); with those equal and no sink
    # the launch is what it was before either existed.
    v_dim = v_cache.shape[-1] // Hk
    # Always the Mxu inner product: a tile's G_TILE rows share each block,
    # and on a rung that holds a whole stretch a program's tiles do.
    height = programs_height(T)
    inner = make_inner(None, rows=G_TILE, group=H // Hk, num_kv_heads=Hk,
                       head_dim=hd, page_size=page_size,
                       subs=height // G_TILE, window=window,
                       v_dim=v_dim if v_dim != hd else 0,
                       sink=sink is not None)

    Tp = -(-T // height) * height
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
    base = [pos_base.astype(jnp.int32)] if window else []
    nbuf, grid_spec = ring_grid_spec(inner, RING, (Tp // height,),
                                     6 + len(base), pools)
    kernel = functools.partial(
        _ragged_kernel,
        inner=inner,
        nbuf=nbuf,
        max_pages=max_pages,
        num_seqs=B,
    )

    q_packed = inner.pack_q(jnp.pad(q, ((0, Tp - T), (0, 0), (0, 0))))
    packed_sink = [] if sink is None else [inner.pack_sink(sink)]
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(inner.o_shape(q_packed.shape),
                                       q.dtype),
        interpret=interpret, **({"name": WINDOW_NAME} if window else {}),
    )(jnp.asarray(layer, jnp.int32).reshape(1), tile_first,
      q_start.astype(jnp.int32), q_lens.astype(jnp.int32),
      kv_lens.astype(jnp.int32), whole_blocks(page_table, inner), *base,
      q_packed, *packed_sink, *pools)
    return inner.unpack_o(out)[:T]
