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

A WINDOW layer (`sliding_attention`: a query at position p sees the positions
p - window < j <= p) keeps its K and V in the same layout, but in a per-slot
RING and not in the paged pool (`WindowRing`): its rows are dead `window`
positions later. The same attentions serve it — `window` is a static
argument, 0 for a full layer — over a page table that is arithmetic
(`ring_table`): the ring pages of the row's slot from the page that holds the
earliest position any of the span's queries sees, with the position that page
stands for (`pos_base`), so a walk reads the window and not the context.

K and V rows need not be alike (MiMo-V2-Flash: a key head of 192 lanes beside
a value head of 128): every attention here reads the q·k width off q, the kv
heads off the K pool's lanes and the p·v width off the V pool's. A head wider
than a lane tile that does not fill whole tiles is STORED split (`lay_heads`:
every head's whole tiles first, `[Hk x 128 | Hk x 64]` for 192 — the row holds
no lane the model lacks, each head has an aligned tile, and two heads share
the tile of their rests, which is the kernels' packed-heads case); the jnp
twins put a gathered row's heads together again (`heads_of`). A window
layer's softmax may carry a SINK, a learned float32 logit a head that every
query sees and that carries no value: `sink` [H], one more term of the
denominator (`_softmax`, the blockwise walk's last line).
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as PS

from ollamamq_tpu.ops.quant import QuantKV, kv_gather, kv_write
from ollamamq_tpu.parallel.mesh import AXIS_TENSOR

NEG_INF = -1e30
LANE = 128


def split_head(dim: int) -> tuple:
    """(lanes of a head stored first, lanes of its rest stored behind every
    head's first part): a head of whole lane tiles, or one that fits in a
    tile, is stored in one piece — rest 0."""
    rest = dim % LANE if dim > LANE else 0
    return dim - rest, rest


def lay_heads(x: jnp.ndarray) -> jnp.ndarray:
    """[..., Hk, d] -> [..., Hk * d], a cache row as it is stored: the heads
    side by side, or (`split_head`) every head's whole tiles and then every
    head's rest."""
    whole, rest = split_head(x.shape[-1])
    flat = x.shape[:-2] + (-1,)
    if not rest:
        return x.reshape(flat)
    return jnp.concatenate([x[..., :whole].reshape(flat),
                            x[..., whole:].reshape(flat)], axis=-1)


def heads_of(rows: jnp.ndarray, dim: int) -> jnp.ndarray:
    """`lay_heads`' inverse: stored rows [..., Hk * dim] -> [..., Hk, dim]."""
    whole, rest = split_head(dim)
    if not rest:
        return rows.reshape(rows.shape[:-1] + (-1, dim))
    hk = rows.shape[-1] // dim
    lead = rows.shape[:-1]
    return jnp.concatenate(
        [rows[..., :hk * whole].reshape(lead + (hk, whole)),
         rows[..., hk * whole:].reshape(lead + (hk, rest))], axis=-1)


def _gather_kv(k_cache, v_cache, layer, slots, hd: int):
    """(k [*slots, Hk, hd], v [*slots, Hk, v lanes a head]) of `slots`."""
    if split_head(hd)[1]:
        k = heads_of(k_cache[layer, slots], hd)
    else:
        k = kv_gather(k_cache, layer, slots, hd)
    # (a V row's lanes over the kv heads; a pool may be held [L, S, Hk, d])
    return k, kv_gather(v_cache, layer, slots,
                        math.prod(v_cache.shape[2:]) // k.shape[-2])


def _softmax(logits: jnp.ndarray, sink=None):
    """Softmax over the last axis of logits [., H, ..., keys], float32 — with
    `sink` ([H], along axis 1) over one more column, the sink's logit, whose
    weight is dropped: it carries no value."""
    if sink is None:
        return jax.nn.softmax(logits, axis=-1)
    with jax.named_scope("attn_sink"):
        b = sink.astype(jnp.float32).reshape(
            (1, -1) + (1,) * (logits.ndim - 2))
        m = jnp.maximum(jnp.max(logits, axis=-1, keepdims=True), b)
        p = jnp.exp(logits - m)
        return p / (jnp.sum(p, axis=-1, keepdims=True) + jnp.exp(b - m))


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
    window: int = 0,  # > 0: the last `window` positions only
    sink=None,  # [H] float32: a logit a head in the softmax (`_softmax`)
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
    if window:
        causal = causal & (pos[None, :] > pos[:, None] - window)
    valid = pos[None, None, :] < seq_lens[:, None, None]  # [B, 1, k]
    mask = causal[None, None, :, :] & valid[:, None, :, :]
    logits = jnp.where(mask, logits, NEG_INF)
    probs = _softmax(logits, sink)
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
    window: int = 0,  # a window layer: each query's last `window` positions,
    pos_base=None,  # [B] and the position the table's first page stands for
    sink=None,  # [H] (`_softmax`)
) -> jnp.ndarray:
    """Chunked-prefill attention: the chunk's K/V are already scattered
    into the cache, so each query at global position start+i attends to
    cache positions <= start+i. Generalizes decode attention (C == 1).
    """
    B, C, H, hd = q.shape
    max_pages = page_table.shape[1]
    L = max_pages * page_size
    index = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (B, L))
    slots = flat_slot_indices(page_table, index, page_size)  # [B, L]
    positions = index if pos_base is None else index + pos_base[:, None]
    k, v = _gather_kv(k_cache, v_cache, layer, slots, hd)  # [B, L, Hk, .]
    n_rep = H // k.shape[2]
    k = repeat_kv(k, n_rep)
    v = repeat_kv(v, n_rep)
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    logits = jnp.einsum(
        "bchd,blhd->bhcl", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale  # [B, H, C, L]
    q_pos = start[:, None] + jnp.arange(C)[None, :]  # [B, C] global positions
    causal = positions[:, None, :] <= q_pos[:, :, None]  # [B, C, L]
    if window:
        causal = causal & (positions[:, None, :] > q_pos[:, :, None] - window)
    in_seq = positions[:, None, :] < (start + chunk_lens)[:, None, None]
    mask = (causal & in_seq)[:, None, :, :]
    logits = jnp.where(mask, logits, NEG_INF)
    probs = _softmax(logits, sink)
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
    window: int = 0,
    pos_base=None,
    sink=None,
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
        page_size=page_size, window=window, pos_base=pos_base, sink=sink,
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
    window: int = 0,
    pos_base=None,
    sink=None,
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
    seq = jnp.clip(tok_seq, 0, B - 1)
    rows = page_table[seq]  # [T, max_pages]
    index = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (T, L))
    slots = flat_slot_indices(rows, index, page_size)  # [T, L]
    positions = index if pos_base is None else index + pos_base[seq][:, None]
    k, v = _gather_kv(k_cache, v_cache, layer, slots, hd)  # [T, L, Hk, .]
    n_rep = H // k.shape[2]
    k = repeat_kv(k, n_rep)
    v = repeat_kv(v, n_rep)
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    logits = jnp.einsum(
        "thd,tlhd->thl", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale  # [T, H, L]
    causal = positions <= tok_pos[:, None]  # [T, L]
    if window:
        causal = causal & (positions > tok_pos[:, None] - window)
    in_seq = positions < kv_lens[seq][:, None]
    mask = (causal & in_seq)[:, None, :]
    logits = jnp.where(mask, logits, NEG_INF)
    probs = _softmax(logits, sink)
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
    window: int = 0,
    pos_base=None,
    sink=None,
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
    Hk = math.prod(k_cache.shape[2:]) // hd
    n_rep = H // Hk
    vd = math.prod(v_cache.shape[2:]) // Hk  # a value head's lanes: hd for
    # every model but one whose K and V rows differ in width
    BLK = block_pages * page_size
    n_blocks = -(-max_pages // block_pages)  # static ceiling
    seq = jnp.clip(tok_seq, 0, B - 1)
    rows = page_table[seq]  # [T, max_pages]
    end = tok_pos + 1  # per-token causal frontier (0 for padding)
    # Where a token's walk starts: position 0 — or, over a window layer's
    # table, the position its row's first listed page stands for.
    first = 0 if pos_base is None else pos_base[seq]
    needed = jnp.max(-(-jnp.maximum(end - first, 0) // BLK))

    scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    qf = q.astype(jnp.float32) * scale  # [T, H, hd]

    def body(i, carry):
        m, l, acc = carry
        pidx = jnp.clip(
            i * block_pages + jnp.arange(block_pages), 0, max_pages - 1
        )
        pages = rows[:, pidx]  # [T, block_pages]
        pos = i * BLK + jnp.arange(BLK, dtype=jnp.int32)[None, :]
        if pos_base is not None:
            pos = pos + first[:, None]
        slots = (pages[:, :, None] * page_size
                 + jnp.arange(page_size)[None, None, :]).reshape(T, BLK)
        k, v = _gather_kv(k_cache, v_cache, layer, slots, hd)
        k = repeat_kv(k.astype(jnp.float32), n_rep)  # [T,BLK,H,hd]
        v = repeat_kv(v.astype(jnp.float32), n_rep)
        logits = jnp.einsum("thd,tlhd->thl", qf, k)  # [T, H, BLK]
        keep = (pos <= tok_pos[:, None]) & (pos < end[:, None])  # [T, BLK]
        if window:
            keep = keep & (pos > tok_pos[:, None] - window)
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
    a0 = jnp.zeros((T, H, vd), jnp.float32)
    m, l, acc = jax.lax.fori_loop(
        0, jnp.minimum(needed, n_blocks), body, (m0, l0, a0)
    )
    if sink is not None:  # one more term of the denominator, no value
        with jax.named_scope("attn_sink"):
            b = sink.astype(jnp.float32)[None, :]
            top = jnp.maximum(m, b)
            keep = jnp.where(m <= NEG_INF / 2, 0.0, jnp.exp(m - top))
            l, acc = l * keep + jnp.exp(b - top), acc * keep[..., None]
    out = acc / jnp.maximum(l, 1e-30)[..., None]  # [T, H, vd]
    return out.astype(q.dtype)


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=("k", "v"), meta_fields=("rows",))
@dataclasses.dataclass(frozen=True)
class WindowRing:
    """The window layers' K and V: `[window layers, (slots + 1) * rows,
    Hk*hd]` each (K's lanes and V's may differ), the pool's row layout (the
    kernels DMA pages out of it as out of the pool). Slot s owns rows
    [s * rows, (s + 1) * rows) of every
    layer for life — no allocator, no table on the host — and position p of
    its sequence lives at row s * rows + p % rows: a ring that keeps the
    last `rows` positions, of which a query reads its window. `rows`
    (static: `ModelConfig.ring_rows`) is whole pages. Slot `slots` is the
    trash slot padding tokens and idle rows write (as the conv state's and
    `recent`'s). Never reset: a row that a request has not written yet lies
    past its causal frontier and is masked."""
    k: jnp.ndarray
    v: jnp.ndarray
    rows: int

    @property
    def nbytes(self) -> int:
        return self.k.nbytes + self.v.nbytes

    @property
    def trash(self) -> int:
        """The trash slot: the slots' count."""
        return self.k.shape[1] // self.rows - 1

    def write(self, layer, slots, k, v) -> "WindowRing":
        """The rings with rows `slots` (`ring_write_slots`) of window layer
        `layer` set to a stream's k and v."""
        return WindowRing(kv_write(self.k, layer, slots, k),
                          kv_write(self.v, layer, slots, v), self.rows)


def alloc_ring(layers: int, max_slots: int, rows: int, lanes: tuple,
               dtype=jnp.bfloat16):
    """The rings of a model with `layers` window layers (zeros), or None
    for a model that has none — a pytree without leaves. `lanes`: of a K
    row and of a V row (`ModelConfig.ring_row_dims`)."""
    if not layers:
        return None
    k_lanes, v_lanes = lanes
    shape = (layers, (max_slots + 1) * rows)
    return WindowRing(jnp.zeros(shape + (k_lanes,), dtype),
                      jnp.zeros(shape + (v_lanes,), dtype), rows)


def ring_write_slots(slots, positions, valid, rows: int, trash: int):
    """The ring row each token writes: `slots` its sequence's slot,
    `positions` its position, `valid` false for padding tokens and idle
    rows — they write the trash slot (`trash`: the slots' count)."""
    return jnp.where(valid, slots, trash) * rows + jnp.maximum(
        positions, 0) % rows


def ring_first_page(kv_lens, q_lens, window: int, page_size: int):
    """The page of a row's sequence that a window launch's walk starts at:
    the one that holds the earliest position the span's first query sees,
    kv - q - (window - 1). numpy or jax arrays alike: `ring_table` builds the
    launch's table from it, and the engine counts from it what the launch
    walks (`swa_walk_rows`)."""
    return (kv_lens - q_lens - (window - 1)).clip(0) // page_size


def ring_table(slots, kv_lens, q_lens, window: int, rows: int,
               page_size: int, max_span: int):
    """(page table [B, cols], pos_base [B]) of a window layer's launch: row
    b's span of `q_lens[b]` tokens ends at context `kv_lens[b]`, its first
    query sees positions from kv - q - (window - 1) on, and the table lists
    the ring pages of slot `slots[b]` from the page that holds that position
    (`pos_base`: the position the page starts at). `cols` (static) holds
    the window before a span of `max_span` tokens, in whole blocks of eight
    pages (both kernels' and the jnp twin's block divide it); a column past
    the span's last page names a ring page whose positions lie past the
    causal frontier, so what it holds is masked."""
    ring_pages = rows // page_size
    cols = -(-(window + max_span + page_size - 2) // page_size)
    cols = -(-cols // 8) * 8
    first = ring_first_page(kv_lens, q_lens, window, page_size)
    page = (first[:, None] + jnp.arange(cols, dtype=jnp.int32)[None, :]
            ) % ring_pages
    return (slots[:, None] * ring_pages + page).astype(jnp.int32), \
        (first * page_size).astype(jnp.int32)


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
    window: int = 0,  # a window layer: the caches are its rings,
    pos_base=None,  # `page_table` / `pos_base` its `ring_table`
    sink=None,  # [H] float32: a logit a head in the softmax (no mesh)
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

        def kernel(q, kc, vc, layer, page_table, q_start, q_lens, kv_lens,
                   *base):
            kq, vq, scales = _split_quant(kc, vc)
            if window:  # (no mesh: kv_cache.refusal)
                scales = dict(scales, window=window, pos_base=base[0])
            if sink is not None:  # (closed over: no mesh either)
                scales = dict(scales, sink=sink)
            return ragged_paged_attention_pallas(
                q, kq, vq, layer, page_table, q_start, q_lens, kv_lens,
                page_size, interpret=interpret, **scales)

        return _per_tensor_shard(mesh, kernel, q, k_cache, v_cache, layer,
                                 page_table, q_start, q_lens, kv_lens,
                                 *([pos_base] if window else []))
    return ragged_paged_attention_blockwise(
        q, k_cache, v_cache, layer, page_table, tok_seq, tok_pos, kv_lens,
        page_size, window=window, pos_base=pos_base if window else None,
        sink=sink,
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
    window: int = 0,  # as ragged_attention_any's
    pos_base=None,
    sink=None,
) -> jnp.ndarray:
    """The ONE pallas-vs-jnp decode-attention dispatch
    (models/llama.forward_decode). The pallas import stays deferred: the
    kernel module only loads when selected."""
    if attn_impl == "pallas":
        from ollamamq_tpu.ops.pallas.paged_attention import (
            paged_decode_attention_pallas,
        )

        def kernel(q, kc, vc, layer, page_table, seq_lens, *base):
            kq, vq, scales = _split_quant(kc, vc)
            if window:
                scales = dict(scales, window=window, pos_base=base[0])
            if sink is not None:
                scales = dict(scales, sink=sink)
            return paged_decode_attention_pallas(
                q, kq, vq, layer, page_table, seq_lens, page_size,
                interpret=interpret, **scales)

        return _per_tensor_shard(mesh, kernel, q, k_cache, v_cache, layer,
                                 page_table, seq_lens,
                                 *([pos_base] if window else []))
    return paged_decode_attention(
        q, k_cache, v_cache, layer, page_table, seq_lens, page_size,
        window=window, pos_base=pos_base if window else None, sink=sink,
    )
