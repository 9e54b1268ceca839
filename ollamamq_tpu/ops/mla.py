"""Latent attention with a learned sparse selection over the paged cache
(DeepSeek-V3.2: multi-head latent attention + the lightning indexer).

What a token leaves in the cache is two rows, in two pools with one page
table (engine/kv_cache.py): the LATENT row `[c_kv | k_rope | 0…]` — the
normed `kv_lora_rank` lanes every head's key and value are expanded from, the
one rotary key all heads share, zeros up to the lane tile the TPU's layout
pads a row to anyway (`latent_lanes`) — and the INDEX KEY, `index_head_dim`
lanes. Attention runs in the ABSORBED form: `q_abs[t, i] = [q_nope[t, i]
W_uk,i^T | q_rope[t, i]] * scale` against the latent row, the output's latent
`sum_s p(t, i, s) c_kv(s)` through W_uv,i afterwards — the expanded keys and
values never exist in HBM. (One query token cannot pay for expanding its
context. A prefill span of a few hundred tokens can: the Pallas kernels
attend such a span in the EXPANDED form — `(q W_uk^T) . c = q . (c W_uk)^T`
— a block's keys and values expanded in VMEM once a group of heads for all
the span's tokens; ops/pallas/mla_attention.py, `WIDE`.)

Three steps a layer, each a named scope on the device trace:
  dsa_index   I(t, s) = sum_j w_j(t) ReLU(q_I,j(t) . k_I(s)) for every cached
              position s of t's sequence: `[T, C]` float32, C the page
              table's context. The Pallas kernel streams index-key pages
              (`dsa_index_pallas`); the twin gathers them.
  dsa_select  thr(t): the `index_topk`-th largest I(t, s) over s <= pos(t),
              -inf while the context holds no more than that. EXACT, by a
              bitwise search over the scores' order-preserving integer keys
              (32 counting passes over [T, C]; `lax.top_k` on the TPU is a
              sort). Position s is selected iff I(t, s) >= thr(t). The
              Pallas kernel keeps a tile's scores in VMEM for the passes
              (`dsa_select_pallas`); the twin is the same search in XLA.
  mla_attend  softmax over the selected s <= pos(t) of q_abs . row, times
              the rows' first `kv_lora_rank` lanes. The Pallas kernel applies
              the selection as a mask inside a flash-style walk over the
              latent pages (`mla_sparse_paged_attention_pallas`).
The same three serve a ragged step's stream and the decode scan's batch (a
stream of one-token spans, a tile a token).

A model with NO indexer (`index_topk` 0: DeepSeek-V3's, openPangu's latent
attention) has no second pool and runs `mla_attend` alone: a causal softmax
over every cached position — the same walk with the selection's operands and
comparison compiled out (`scores` and `thr` None), not a mask of all ones.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ollamamq_tpu.ops.attention import NEG_INF, flat_slot_indices

SCOPES = ("mla_proj", "mla_cache_write", "dsa_index", "dsa_select",
          "mla_attend")


def _gather_rows(pool, layer, page_table, tok_seq, page_size):
    """Each token's own sequence's rows of `pool[layer]`: [T, C, lanes]
    (the twins' materialising read; C = max_pages * page_size)."""
    B, max_pages = page_table.shape
    rows = page_table[jnp.clip(tok_seq, 0, B - 1)]
    C = max_pages * page_size
    positions = jnp.broadcast_to(jnp.arange(C, dtype=jnp.int32),
                                 (tok_seq.shape[0], C))
    return pool[layer, flat_slot_indices(rows, positions, page_size)]


def index_scores(q_idx, w, idx_pool, layer, page_table, tok_seq, page_size):
    """The indexer's twin: I [T, C] float32. q_idx [T, Hi, di], w [T, Hi]
    float32, idx_pool [L, S, di]. Products of the pool's dtype, float32
    sums — the kernel's arithmetic."""
    k = _gather_rows(idx_pool, layer, page_table, tok_seq, page_size)
    s = jnp.einsum("thd,tcd->thc", q_idx.astype(jnp.float32),
                   k.astype(jnp.float32))
    return jnp.einsum("thc,th->tc", jnp.maximum(s, 0.0), w)


def _ordered(x):
    """float32 -> uint32, order-preserving (NaN patterns aside)."""
    b = jax.lax.bitcast_convert_type(x, jnp.int32)
    b = jnp.where(b < 0, b ^ jnp.int32(0x7FFFFFFF), b)
    return jax.lax.bitcast_convert_type(b, jnp.uint32) ^ jnp.uint32(1 << 31)


def _unordered(u):
    b = jax.lax.bitcast_convert_type(u ^ jnp.uint32(1 << 31), jnp.int32)
    b = jnp.where(b < 0, b ^ jnp.int32(0x7FFFFFFF), b)
    return jax.lax.bitcast_convert_type(b, jnp.float32)


def select_threshold(scores, tok_pos, topk: int):
    """thr [T] float32: the `topk`-th largest of scores[t, :tok_pos[t] + 1],
    NEG_INF where that context holds `topk` positions or fewer (and for
    padding tokens, tok_pos < 0). Exact: the answer is built bit by bit,
    the largest key v with count(keys >= v) >= topk."""
    T, C = scores.shape
    live = jnp.arange(C, dtype=jnp.int32)[None, :] <= tok_pos[:, None]
    keys = jnp.where(live, _ordered(scores), jnp.uint32(0))

    def bit(i, ans):
        cand = ans | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
        enough = jnp.sum(keys >= cand[:, None], axis=1,
                         dtype=jnp.int32) >= topk
        return jnp.where(enough, cand, ans)

    ans = jax.lax.fori_loop(0, 32, bit, jnp.zeros((T,), jnp.uint32))
    return jnp.where(tok_pos + 1 > topk, _unordered(ans), NEG_INF)


def _attend(logits, keep, rows, rank: int):
    """softmax over the kept positions (the last axis; `keep` broadcast over
    the heads) times the rows' first `rank` lanes."""
    keep = jnp.expand_dims(keep, -2)
    p = jnp.where(keep, jax.nn.softmax(
        jnp.where(keep, logits, NEG_INF), axis=-1), 0.0)
    return jnp.einsum("...hc,...cd->...hd", p, rows[..., :rank])


def sparse_attention(q_abs, scores, thr, lat_pool, layer, page_table,
                     tok_seq, tok_pos, page_size, rank: int):
    """The attention's twin: o [T, H, rank] in q's dtype. q_abs [T, H,
    lanes] (absorbed, scaled), scores [T, >= C] and thr [T] the selection,
    lat_pool [L, S, lanes]. `scores` None: no selection, every position up
    to the token's own."""
    rows = _gather_rows(lat_pool, layer, page_table, tok_seq,
                        page_size).astype(jnp.float32)  # [T, C, lanes]
    C = rows.shape[1]
    logits = jnp.einsum("thd,tcd->thc", q_abs.astype(jnp.float32), rows)
    keep = jnp.arange(C, dtype=jnp.int32)[None, :] <= tok_pos[:, None]
    if scores is not None:
        keep &= scores[:, :C] >= thr[:, None]
    return _attend(logits, keep, rows, rank).astype(q_abs.dtype)


def dense_attention(q_abs, row, q_idx, k_idx, w_idx, seq_lens, rank: int,
                    topk: int):
    """Whole sequences from position 0, no cache: [B, T, H, rank]. The
    oracle of the step forwards (models/llama.forward_prefill). `topk` 0:
    no indexer (q_idx, k_idx, w_idx None)."""
    B, T = row.shape[:2]
    pos = jnp.arange(T, dtype=jnp.int32)
    tok_pos = jnp.where(pos[None, :] < seq_lens[:, None], pos[None, :], -1)
    keep = pos[None, None, :] <= tok_pos[:, :, None]
    if topk:
        s = jnp.einsum("bthd,bcd->bthc", q_idx.astype(jnp.float32),
                       k_idx.astype(jnp.float32))
        scores = jnp.einsum("bthc,bth->btc", jnp.maximum(s, 0.0), w_idx)
        thr = select_threshold(scores.reshape(B * T, T),
                               tok_pos.reshape(B * T), topk).reshape(B, T)
        keep &= scores >= thr[..., None]
    rows = row.astype(jnp.float32)
    logits = jnp.einsum("bthd,bcd->bthc", q_abs.astype(jnp.float32), rows)
    return _attend(logits, keep, rows[:, None], rank).astype(q_abs.dtype)


def attend(impl: str, q_abs, q_idx, w_idx, lat_pool, idx_pool, layer,
           page_table, tok_seq, tok_pos, q_start, q_lens, kv_lens,
           page_size: int, rank: int, topk: int, tile=None,
           interpret: bool = False, name=None, expanded=None):
    """index, select, attend — the ONE pallas-vs-jnp dispatch of both step
    forwards. Both metadata encodings travel together, as in
    ops/attention.ragged_attention_any. `topk` 0 (no indexer: q_idx, w_idx
    None, idx_pool unread): attend alone, over every cached position.
    `name`: the attention launch's name on the device trace where it is not
    the kernel's own (the prediction module's). `expanded`: the expanded
    form's (q [T, H, .], w [H, ., rank]), for the Pallas kernels — which
    then may return (o, o_v, wide) (mla_sparse_paged_attention_pallas)."""
    if impl == "pallas":
        from ollamamq_tpu.ops.pallas import mla_attention as kernels

        if not topk:
            with jax.named_scope("mla_attend"):
                return kernels.mla_dense_paged_attention_pallas(
                    q_abs, lat_pool, layer, page_table, q_start, q_lens,
                    kv_lens, page_size, rank, tile=tile, interpret=interpret,
                    name=name, expanded=expanded)
        with jax.named_scope("dsa_index"):
            scores = kernels.dsa_index_pallas(
                q_idx, w_idx, idx_pool, layer, page_table, q_start, q_lens,
                kv_lens, page_size, tile=tile, interpret=interpret)
        with jax.named_scope("dsa_select"):
            thr = kernels.dsa_select_pallas(scores, tok_pos, topk, tile=tile,
                                            interpret=interpret)
        with jax.named_scope("mla_attend"):
            return kernels.mla_sparse_paged_attention_pallas(
                q_abs, scores, thr, lat_pool, layer, page_table, q_start,
                q_lens, kv_lens, page_size, rank, tile=tile,
                interpret=interpret, expanded=expanded)
    if not topk:
        with jax.named_scope("mla_attend"):
            return sparse_attention(q_abs, None, None, lat_pool, layer,
                                    page_table, tok_seq, tok_pos, page_size,
                                    rank)
    with jax.named_scope("dsa_index"):
        scores = index_scores(q_idx, w_idx, idx_pool, layer, page_table,
                              tok_seq, page_size)
    with jax.named_scope("dsa_select"):
        thr = select_threshold(scores, tok_pos, topk)
    with jax.named_scope("mla_attend"):
        return sparse_attention(q_abs, scores, thr, lat_pool, layer,
                                page_table, tok_seq, tok_pos, page_size, rank)


def absorbed_lead(impl: str, q_start, q_lens, tokens: int, heads: int,
                  lanes: int, rank: int, nope: int, v: int):
    """(rows, few) for a layer whose launch over a stream of `tokens` holds
    the expanded body, else None (the jnp path, a rung under
    WIDE: every row is attended in the absorbed form, nothing to choose).
    `few` — a scalar on the device, of the step's own spans — says that no
    stream row at or behind `rows` reads the absorbed form: each is a wide
    span's, whose result leaves the launch through W_uv already, or padding.
    The layer then runs the absorbed form's contractions — W_uk before the
    launch, W_uv behind it — over the first `rows` alone
    (models/llama.py:_latent_attention_op)."""
    if impl != "pallas":
        return None
    from ollamamq_tpu.ops.pallas import mla_attention as kernels

    lead = kernels.absorbed_lead(tokens, heads, lanes, rank, nope, v)
    if not lead:
        return None
    return lead, jnp.all(kernels.absorbed_few(q_start, q_lens))
