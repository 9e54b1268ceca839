"""Block selection of a block-sparse attention layer (MiniCPM-SALA's
`minicpm4` mixer, InfLLM-V2): which BLOCKS of cached positions a query
attends, and the pooled keys the choice is made from. No parameters: it reads
q and the keys.

Pooled keys. For each kv head, pooled row j is the mean of the layer's K rows
(as cached: normed, not roped) at positions [stride j, stride j + kernel),
kernel = 2 stride: defined once position stride j + kernel - 1 is cached. They
live in a pool of their own under the K/V pool's page table — a page of
`page_size` positions holds `page_size / stride` pooled rows, page p's at row
p (page_size / stride) — so a sequence's pooled row j is at
`page_table[stride j // page_size]`'s rows, offset (stride j % page_size) /
stride (`pooled_slots`). A row is written in the step in which its last key
lands (`write_pooled`), from K rows that may lie in an earlier page and have
been written by an earlier step: it reads them back from the K pool.

The score, for a query at position t (context n = t + 1) in kv group g, over
the rows j with stride j + kernel <= n:

    p[h, j] = softmax_j(q[h] . pooled[j] hd^-1/2)      a head, float32
    P[j]    = sum of p[h, j] over the group's heads
    B[b]    = max of P[4 b - 1 .. 4 b + 3]             (a block is 4 strides:
              a max-pool of window 5, stride 4, padding 1 over the rows)

Block b is positions [block b, block b + block). Kept: the first
`init_blocks`, the last `local_blocks` up to and including the query's own
(c = t // block), and the best of the others, `topk` in all — every block
while there are no more than that. Ties go to the lower block index
(`jax.lax.top_k` keeps the lower index first). `select` returns the kept ids
ASCENDING, so that the query's own block is the list's last: a walk over the
list's pages in order is causal with one length, `walk_len`, as a walk over a
sequence's own pages is (no position enters the attention: the layers are
NoPE).

Two forms of the attention itself:

  - `walk_table`: the list as a page table a (row, kv head) for the paged
    decode kernel (ops/pallas/block_sparse_attention.py) or its jnp twin — one
    query a row reads the kept blocks' pages and nothing else. A row at or
    under `dense_len` walks its own pages (all of them: it keeps every block).
  - `span_attention`: a span's tokens of ONE row under their block masks, in
    XLA: the row's whole context gathered once, a mask a (token, kv head,
    block), dense masked softmax a tile of queries at a time. What it reads is
    the context, not the kept blocks: the sparse prefill walk is not built.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

_F32 = jnp.float32
# Above any score (a sum of `group` probabilities), below float32's top.
_FORCED = 1e9
# Queries a tile of `span_attention`'s masked softmax.
SPAN_TILE = 128


class Sizes(NamedTuple):
    """A configuration's `sparse_*` sizes (ModelConfig's fields)."""
    kernel: int
    stride: int
    block: int
    topk: int
    init_blocks: int
    local_blocks: int
    dense_len: int

    @classmethod
    def of(cls, cfg) -> "Sizes":
        return cls(cfg.sparse_kernel_size, cfg.sparse_kernel_stride,
                   cfg.sparse_block_size, cfg.sparse_topk,
                   cfg.sparse_init_blocks, cfg.sparse_local_blocks,
                   cfg.sparse_dense_len)


def alloc_pooled(layers: int, rows: int, lanes: int, dtype=jnp.bfloat16):
    """The pooled-key pool of a model's sparse layers, [layers, rows, kv
    heads * head_dim] (zeros), or None for a model that has none. `rows`:
    `ModelConfig.pooled_rows` — the trash page's rows are rows 0 .. and take
    what padding writes."""
    if not layers or not rows:
        return None
    return jnp.zeros((layers, rows, lanes), dtype)


def pooled_slots(page_table, j, stride: int, page_size: int):
    """Rows of the pooled pool that hold pooled rows `j` [..., n] of the
    sequences whose page-table rows are `page_table` [..., max_pages]."""
    per_page = page_size // stride
    page = jnp.take_along_axis(
        page_table, jnp.clip(j // per_page, 0, page_table.shape[-1] - 1),
        axis=-1)
    return page * per_page + j % per_page


def write_pooled(pooled, k_cache, layer, page_rows, positions, live,
                 z: Sizes, page_size: int):
    """Write the pooled rows that the tokens at `positions` [M] complete.
    page_rows [M, max_pages]: each token's sequence's page-table row; live
    [M] bool. Token p completes row j = (p + 1 - kernel) / stride when that
    is a whole number >= 0; its `kernel` keys are read back from
    `k_cache[layer]` (this step's rows are already there: a sparse layer's
    index among the pool's layers is its index among the pooled pool's). A
    token that completes nothing writes the trash page's row."""
    j = positions + 1 - z.kernel
    done = live & (j >= 0) & (j % z.stride == 0)
    j = jnp.where(done, j // z.stride, 0)
    pos = jnp.maximum(positions[:, None] - z.kernel + 1, 0) \
        + jnp.arange(z.kernel, dtype=jnp.int32)[None, :]  # [M, kernel]
    page = jnp.take_along_axis(
        page_rows, jnp.clip(pos // page_size, 0, page_rows.shape[1] - 1),
        axis=1)
    # (ONE gather by (layer, row): a layer sliced out first is a copy of it)
    keys = k_cache[layer, page * page_size + pos % page_size]
    # [M, kernel, lanes]
    mean = jnp.mean(keys.astype(_F32), axis=1).astype(pooled.dtype)
    rows = jnp.where(done, pooled_slots(page_rows, j[:, None], z.stride,
                                        page_size)[:, 0], 0)
    return pooled.at[layer, rows].set(mean)


def completing(tok_pos, valid, z: Sizes, size: int):
    """The stream indices of the at most `size` tokens that complete a
    pooled row (`size`: stream tokens / stride + rows bounds them), and
    which of the `size` entries are one."""
    j = tok_pos + 1 - z.kernel
    done = valid & (j >= 0) & (j % z.stride == 0)
    idx = jnp.nonzero(done, size=size, fill_value=0)[0].astype(jnp.int32)
    return idx, jnp.arange(size) < jnp.sum(done)


def pooled_probs(q, pooled, n, z: Sizes):
    """P [N, Hk, J] float32: the softmax over the defined pooled rows a head,
    summed over each kv group's heads — of queries q [N, H, hd] at contexts n
    [N] over pooled keys [N, J, Hk, hd] (each query's sequence's) or [J, Hk,
    hd] (one sequence's, every query of it). On the chip, one query a row:
    ops/pallas/bsa_select.py."""
    N, H, hd = q.shape
    J, Hk = pooled.shape[-3], pooled.shape[-2]
    qg = q.reshape(N, Hk, H // Hk, hd)
    spec = "nkgd,njkd->nkgj" if pooled.ndim == 4 else "nkgd,jkd->nkgj"
    s = jnp.einsum(spec, qg, pooled, preferred_element_type=_F32) \
        * hd ** -0.5
    defined = jnp.arange(J, dtype=jnp.int32)[None, :] * z.stride + z.kernel \
        <= n[:, None]  # [N, J]
    s = jnp.where(defined[:, None, None, :], s, -jnp.inf)
    top = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.where(defined[:, None, None, :],
                  jnp.exp(s - jnp.where(jnp.isfinite(top), top, 0.0)), 0.0)
    p = e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)
    return jnp.sum(p, axis=2)  # over the group's heads


def block_scores(P, z: Sizes):
    """B [N, Hk, blocks] of P [N, Hk, J] (J >= 4 blocks; rows past that are
    padding): the max-pool of window 5, stride 4, padding 1."""
    N, Hk, J = P.shape
    per = z.block // z.stride  # 4
    nb = J // per
    P = jnp.pad(P[..., :nb * per], ((0, 0), (0, 0), (1, per - 1)))
    R = P.reshape(N, Hk, nb + 1, per)  # row -1 first, and a tail
    return jnp.maximum(jnp.max(R[:, :, :-1], axis=-1), R[:, :, 1:, 0])


def _ranked(P, n, z: Sizes):
    """The block scores with the forced blocks above every score and the
    blocks past the query's own below: what both forms of the choice rank.
    Returns (score [N, Hk, nb], own [N, 1])."""
    score = block_scores(P, z)
    b = jnp.arange(score.shape[-1], dtype=jnp.int32)[None, :]
    own = ((n - 1) // z.block)[:, None]  # [N, 1]
    forced = (b < z.init_blocks) | ((b > own - z.local_blocks) & (b <= own))
    score = jnp.where(forced[:, None, :], _FORCED, score)
    return jnp.where((b <= own)[:, None, :], score, -1.0), own


def select(q, pooled, n, z: Sizes, probs=None):
    """(ids [N, Hk, topk] int32 ascending, -1 past the count; count [N]) of
    the blocks the queries keep (see the module docstring). A query with no
    more than `topk` blocks keeps them all. `probs`: `pooled_probs`' result
    where a kernel computed it."""
    score, own = _ranked(
        pooled_probs(q, pooled, n, z) if probs is None else probs, n, z)
    nb = score.shape[-1]
    k = min(z.topk, nb)
    vals, ids = jax.lax.top_k(score, k)
    ids = jnp.sort(jnp.where(vals >= 0, ids, nb), axis=-1).astype(jnp.int32)
    if k < z.topk:
        ids = jnp.pad(ids, ((0, 0), (0, 0), (0, z.topk - k)),
                      constant_values=nb)
    count = jnp.minimum(own[:, 0] + 1, z.topk).astype(jnp.int32)
    return jnp.where(ids < nb, ids, -1), count


def select_mask(q, pooled, n, z: Sizes):
    """`select`'s choice as a mask [N, Hk, nb] bool, WITHOUT a sort: block b
    is kept when fewer than `topk` blocks rank before it — a higher score,
    or the same score at a lower index (`top_k`'s order). For many queries
    at once (a span's tokens): a sort of [512, 2, 268] scores cost 1.9 ms a
    layer a step on a v5e, 7 % of the cell's device time (my chip run,
    PR 60); nb^2 comparisons a (query, kv head) are a fraction of that."""
    score, _ = _ranked(pooled_probs(q, pooled, n, z), n, z)
    b = jnp.arange(score.shape[-1], dtype=jnp.int32)
    mine, other = score[..., :, None], score[..., None, :]
    before = (other > mine) | ((other == mine) & (b[None, :] < b[:, None]))
    return (jnp.sum(before, axis=-1) < z.topk) & (score >= 0)


def walk_len(n, count, z: Sizes):
    """A walk's length over a kept list of `count` blocks, the last the
    query's own: whole blocks, then the own block up to the query."""
    return (count - 1) * z.block + n - ((n - 1) // z.block) * z.block


def walk_width(z: Sizes, page_size: int, max_pages: int) -> int:
    """Columns of `walk_table`: the kept blocks' pages, or a dense row's own
    (`dense_len` positions at the most) — whole multiples of 8 columns."""
    cols = max(z.topk * (z.block // page_size), -(-z.dense_len // page_size))
    return -(-min(cols, max_pages) // 8) * 8


def walk_table(page_table, ids, count, n, live, z: Sizes, page_size: int):
    """The page table and lengths of a one-query-a-row walk, a (row, kv
    head): (table [B * Hk, width], lens [B * Hk]). page_table [B, max_pages],
    ids [B, Hk, topk] and count [B] from `select`, n [B] the rows' contexts,
    live [B] bool (a row that is not live reads nothing: length 0). A row
    with n <= dense_len walks its own first pages, both kv heads alike."""
    B, Hk, K = ids.shape
    per = z.block // page_size
    width = walk_width(z, page_size, page_table.shape[1])
    col = jnp.arange(width, dtype=jnp.int32)
    blk = jnp.take_along_axis(
        jnp.maximum(ids, 0), jnp.broadcast_to(
            jnp.clip(col // per, 0, K - 1), (B, Hk, width)), axis=-1)
    kept = (col // per)[None, None, :] < count[:, None, None]
    sparse_page = jnp.take_along_axis(
        jnp.broadcast_to(page_table[:, None, :], (B, Hk, page_table.shape[1])),
        jnp.clip(blk * per + col % per, 0, page_table.shape[1] - 1), axis=-1)
    sparse_page = jnp.where(kept, sparse_page, 0)
    own = jnp.pad(page_table, ((0, 0), (0, max(0, width - page_table.shape[1])))
                  )[:, :width]
    dense = (n <= z.dense_len)[:, None, None]
    table = jnp.where(dense, own[:, None, :], sparse_page)
    lens = jnp.where(dense[:, 0, 0], n, walk_len(n, count, z))
    lens = jnp.where(live, lens, 0)
    return (table.reshape(B * Hk, width).astype(jnp.int32),
            jnp.repeat(lens, Hk).astype(jnp.int32))


def block_mask(kept, n, z: Sizes):
    """[N, Hk, nb] bool: the blocks each query attends — `kept`
    (`select_mask`'s), or every block up to its own where n <= dense_len."""
    b = jnp.arange(kept.shape[-1], dtype=jnp.int32)[None, None, :]
    every = b <= ((n - 1) // z.block)[:, None, None]
    return jnp.where((n <= z.dense_len)[:, None, None], every, kept)


def span_attention(q, k_cache, v_cache, pooled, layer, page_row, tok_pos,
                   z: Sizes, page_size: int):
    """Attention of stream tokens q [T, H, hd] at positions tok_pos [T], ALL
    read as tokens of the one sequence whose page-table row is `page_row`
    [max_pages] (the caller keeps the rows that are): each under its own
    block mask, causally a position. Returns o [T, H, hd] in q's dtype."""
    T, H, hd = q.shape
    lanes = k_cache.shape[-1]
    Hk = lanes // hd
    C = page_row.shape[0] * page_size
    nb = C // z.block
    per_page = page_size // z.stride
    slots = (page_row[:, None] * page_size
             + jnp.arange(page_size, dtype=jnp.int32)[None, :]).reshape(-1)
    prows = (page_row[:, None] * per_page
             + jnp.arange(per_page, dtype=jnp.int32)[None, :]).reshape(-1)
    slots, prows = slots[:nb * z.block], prows[:nb * (z.block // z.stride)]
    k = k_cache[layer, slots].reshape(nb * z.block, Hk, hd)
    v = v_cache[layer, slots].reshape(nb * z.block, Hk, hd)
    pk = pooled[layer, prows].reshape(-1, Hk, hd)
    n = jnp.maximum(tok_pos, 0) + 1
    mask = block_mask(select_mask(q, pk, n, z), n, z)  # [T, Hk, nb]
    tile = SPAN_TILE if T % SPAN_TILE == 0 else T
    pos = jnp.arange(nb * z.block, dtype=jnp.int32)

    def one(args):
        qt, mt, nt = args  # [tile, H, hd], [tile, Hk, nb], [tile]
        qg = qt.reshape(tile, Hk, H // Hk, hd)
        s = jnp.einsum("tkgd,skd->tkgs", qg, k,
                       preferred_element_type=_F32) * hd ** -0.5
        keep = jnp.repeat(mt, z.block, axis=-1) \
            & (pos[None, None, :] < nt[:, None, None])  # [tile, Hk, S]
        s = jnp.where(keep[:, :, None, :], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("tkgs,skd->tkgd", p.astype(v.dtype), v,
                       preferred_element_type=_F32)
        return o.reshape(tile, H, hd).astype(q.dtype)

    out = jax.lax.map(one, (q.reshape(T // tile, tile, H, hd),
                            mask.reshape(T // tile, tile, Hk, nb),
                            n.reshape(T // tile, tile)))
    return out.reshape(T, H, hd)
