"""Pallas TPU kernels of latent attention with a learned sparse selection
(ops/mla.py has the mathematics, the selection and the jnp twins).

Three kernels, two of them over one walk: a launch of those takes the step's
flattened token stream — any mix of prefill spans and decode tokens,
`ragged_attention.py`'s layout contract — in tiles of tokens (one program a
tile: TILE tokens for the indexer and the selection, ATTEND_TILE for the
attention; the decode scan launches tiles of ONE token, a row of 128 heads
being an MXU pass by itself) and, per sequence with a row in the tile,
streams that sequence's pages of ONE pool HBM→VMEM in blocks of tokens
(BLOCK for the indexer, ATTEND_BLOCK for the attention) through two buffers,
up to the tile's deepest causal frontier:

  `dsa_index_pallas`: the lightning indexer. Per block ONE contraction for
      all the tile's (token, index head) rows against the block's index keys
      `[tile*Hi, di] · [BLOCK, di]ᵀ`, ReLU, times the head's learned weight,
      summed over the heads: `I[t, s]`, written as `[T, C]` float32 — one
      number a (token, cached position), never one a head.
  `dsa_select_pallas` (no walk: a tile's `[tile, C]` scores resident in
      VMEM): `thr[t]`, the index_topk-th largest score of t's context, by
      32 counting passes over the block — exact, where `lax.top_k` is a
      sort.
  `mla_sparse_paged_attention_pallas`: the absorbed 128-head contraction
      over the latent pool. Per block `[tile*H, lanes] · [block, lanes]ᵀ`
      (q carries W_uk and the softmax scale; the lanes are the row's 576
      and the zeros that fill its last 128-lane tile), the SELECTION applied as a mask
      inside the kernel — position s is attended iff `s <= pos(t)` and
      `I[t, s] >= thr[t]`, `thr[t]` the index_topk-th largest score of t
      (ops/mla.select_threshold) — a flash-style online softmax, and
      `acc += p · block[:, :512]`: the values are the first `kv_lora_rank`
      lanes of the same rows. No `[tokens, context]` score leaves VMEM and
      no row is gathered into HBM. A prefill span of WIDE tokens or more is
      attended in the EXPANDED form by programs of the same launch (the
      docstring's last part).

The selection is a MASK over the streamed context, not a gather of the
selected rows: exact, MXU-shaped at any selection, and it reads and
multiplies every cached position of a prefill tile where a gather would
touch index_topk of them (PERF.md section 5 has what that costs at 8-16 k
tokens and what a gathering kernel is read against: `mla_attn_roofline_pct`
counts the selected pairs only).

A model with no indexer launches the attention kernel alone, as
`mla_dense_paged_attention_pallas`: the same walk and trip with the
selection's two operands and its comparison compiled out (`masked` false) —
a causal walk over the latent pages. There a span of at most SHORT (2)
tokens — a decode row, or `--spec`'s verify span `[t, draft]` of one draft —
folds the row-heads of those tokens alone, not the tile's (the masked
kernel's path of its own is for one-token rows). A span of WIDE tokens or
more is the expanded programs' there too (PR 64: the docstring's end).

Names: exactly one launch a layer a forward pass carries `paged_attention`
in its name (benchmarks/layer_metrics/_ops.py divides such launches by the
attention layers); the indexer's and the selection's do not, and neither
does the prediction module's launch of the dense kernel (`MTP_NAME`: one
more launch a pass, of no layer the configuration file counts).

The attention kernel's trip — one (tile, block) of the walk: at 128 heads
16 tokens are 2048 row-heads, and a 256-token block costs the MXU
`[2048, 640] · [256, 640]ᵀ` + `[2048, 256] · [256, 512]` = 1.21 GFLOP, 6.13 µs
at the bf16 peak. Measured (my chip runs, PR 40, one v5e;
`scripts/attn_kernel_bench.py --traffic latent`: 5 one-token rows and a
507-token span, 2048 selected a token; µs a (16 tokens, 256 keys) of the
span with the one-token trips taken off; 8 k of context unless said):

  PR 39's trip 8.08 (ms a launch at 4 k / 8 k / 12 k / 16 k: 4.31 / 8.37 /
  12.46 / 16.53). With a part taken out: its page DMAs 7.63; mask, max, exp
  and row state (scores → bf16 → P·V) 6.87; the second select of `p` 8.05;
  the `m`, `l`, `corr` updates alone 7.47; `acc`'s read-scale-add 7.47; both
  contractions 3.05. So Mosaic ALREADY ran the float32 softmax beside the
  MXU inside one straight-line trip (one chain's latency is not exposed,
  as it is in `kv_contract.py`'s small blocks), and what stood out was the
  row state: `m`, `l`, `corr` as `[2048, 1]` arrays — 256 vregs of ONE live
  lane each, a lane broadcast of each over the scores' 256 and `acc`'s 512
  lanes, a second cross-lane reduction a row for `l` — made the trip's
  vector side 3.05 µs, of which ~1.2 showed.
  This file's trip, same tile and block: **6.93** (1 chain), 6.89 (2), 6.89
  (4). `m` is kept lane-replicated and `l` lane-PARTIAL in `[rows, 128]`
  (whole vregs in and out of VMEM, no broadcast; ONE cross-lane reduction a
  row a trip, the maximum's; `l`'s lanes are added up once a program), and
  the running maximum starts at M_INIT above the mask's NEG_INF, so a masked
  score's exp is 0 without a second select. Its vector side is 1.85 µs and
  hidden: softmax out 6.87, `acc` out 6.93, DMAs out 6.59, contractions out
  1.85. What is left over 6.13 is the MXU's own (re-latching 18 key/value
  weight tiles a trip) and ~0.3 of page DMA that no variant hid.
  Wider blocks LOSE: 512 wide 7.07 / 6.98 / 6.96 (1 / 2 / 4 chains), 1024
  wide 7.14 / 7.15 / 7.08 / 7.01 (1 / 2 / 4 / 8) — with the state in whole
  vregs nothing a trip pays is exposed any more, and a walk's last block
  over-reads half a block a (tile, sequence). A one-token trip (M = 128:
  weight-tile bound) reads 1.43 → 1.13 a 256 keys, 0.99 at 512 wide, 0.96
  at 1024: 2.5 % of a launch, not worth the spans' 2–3 %.
  A larger TILE wins: 32 tokens 6.72 / 6.73 / 6.65 (1 / 2 / 4 chains), 64
  tokens 6.65 / 6.59 / 6.54 (2 / 4 / 8; ~90 MB of VMEM, not taken) — a
  block's DMA and weight tiles are paid over twice the rows. Chains of 8
  tokens (1024 row-heads) are the best at every tile, by ~1 %.
  Taken: ATTEND_TILE 32, CHAIN 8, ATTEND_BLOCK 256 — **6.68** at 8 k (6.99
  / 6.68 / 6.57 / 6.53 at 4 k / 8 k / 12 k / 16 k against 8.43 / 8.08 / 7.98
  / 7.92; ms a launch 3.58 / 6.91 / 10.25 / 13.59: −17 … −18 %), 92 % of the
  MXU's 6.13.

The expanded body (PR 49). What the absorbed trip multiplies is 640 + 512 =
1152 lanes a (head, pair); expanded keys and values need 192 + 128 = 320,
and expanding a cached position's key and value of one head from its latent
row costs 512 x 256 x 2 FLOPs ONCE for every query token that attends it —
nothing a decode row can pay, half the absorbed form's work for a span of
512. So a launch of either attention kernel over a stream of
WIDE..WIDE_STREAM tokens (`expands`: the 512-token rung; a trace-time choice,
smaller rungs hold the tiles alone) takes the expanded form's operands too —
q as `[q_nope | q_rope | 0]` head-major and `[W_uk,h | W_uv,h]^T` a head, the
rank minor as the stack lives on the device (llama.CONTRACTED_MINOR: no
re-laid copy) — and runs `heads / WIDE_GROUP` EXPANDED programs before its
tiles, in the ONE `pallas_call` (the readers count launches by name):
`_expanded`. A program's rows are the stream's tokens themselves; it walks
the context of every span of at least WIDE tokens (run time, from `q_lens`)
once, with the block's `[tokens, 256]` selection scores prefetched beside its
pages (the masked kernel's; a dense launch has neither the scores nor their
buffers, and its mask is `mine & causal`); a block: the mask once for the
group; `[K_h^T ; V_h^T]` of the group's 16 heads in ONE contraction `[16 x
256, 512] . [256, 512]^T` whose stationary operand is the block (a
contraction a head re-latches the eight weight tiles a head: below); then a
head at a time `[512, 256] . [256, 256]` scores, the tile's online softmax
(`m` lane-replicated, `l` lane-partial), `[512, 256] . [128, 256]^T` into
`acc [512, 128]`, WIDE_CHAIN heads in one straight-line body. The result
leaves in `v_head_dim` lanes. The tiles skip a wide span's sequence, and a
tile inside one returns at once; every other row — decode rows, shorter
spans — is the absorbed tiles' to the bit.
  Measured (my chip runs, PR 49, one v5e; the same script and step, `latent`
  against `latent_wide`; µs a (16 tokens, 256 keys) of the span, one-token
  trips taken off):
  The absorbed tiles 7.21 / 6.79 / 6.65 / 6.58 at 4 k / 8 k / 12 k / 16 k
  (ms a launch 3.71 / 7.05 / 10.38 / 13.72); this body **4.87 / 4.28 / 4.08
  / 3.98** (2.54 / 4.51 / 6.47 / 8.44: −31 / −36 / −38 / −38 %). At the MXU's
  peak the expanded trip is 3.41 (1.37 expansion, 1.37 scores — 192 lanes
  are two passes of 128 — 0.68 P·V).
  How it got there, 8 k: a head's own expansion `[256, 512] . [256, 512]^T`
  inside a loop over the heads, chunks of 256 rows under a `pl.when` each:
  7.35 (8 heads), SLOWER than the tiles; chunks of 512: 6.12, of 128: 9.87 —
  every chunk's region costs ~0.3 µs a (block, head), and a `pl.when` inside
  the head's body keeps the scheduler from overlapping anything across it.
  With a part taken out of that one (6.12): the expansion 4.20, the scores
  4.25, P·V 5.07, the softmax 5.75, the scores' DMA and the mask 5.96, all
  three contractions 1.80 — the contractions cost 4.83 where the peak needs
  3.41, and the vector side (1.80) ran BESIDE nothing. Both had one cause
  each. A weight tile's latch (~128 cycles) is not hidden behind the rows
  streamed through it: a contraction costs tiles x (rows + ~128) cycles over
  the four MXUs — 8 x (256 + 128) a head for the expansion, 1.5 x its peak
  (the tiles' 18 x (1024 + 128) is PR 40's 6.9 against 6.13) — so the
  expansion became ONE contraction a group with the block stationary and
  `[W_uk | W_uv]^T`'s 4096 rows streamed: 5.51 (a head a loop trip). And
  heads in one straight-line body overlap one's softmax with the next one's
  contractions: 2 heads 4.91, 4 heads 4.64, 8 heads 4.50 (8 heads a
  program); 16 heads a program 4.41 / 4.25 / 4.17 at 4 / 8 / 16 heads a
  body (8 is taken: compiled here for a described v5e the kernel takes
  5.6 / 6.5 / 8.3 s at 4 / 8 / 16 against 2.7 s for the tiles alone;
  `setup_s`).
  This body (4.28) with a part taken out: the expansion 2.87, the scores
  3.57, P·V 3.47, the softmax 4.16, the scores' DMA and the mask 4.22, the
  three contractions 1.50. By the latch arithmetic the contractions cost
  1.41 + 1.71 + 0.85 = 3.97: the body is MXU-bound at 93 % of that, and
  what is left over the peak's 3.41 is latches — 512 rows a weight tile is
  all a head's scores and P·V can stream (the span's tokens), and the
  padded 64 lanes of the rope pass.
  WIDE: a program's rows are the rung's whatever the span's length, so the
  expanded form costs a span of L tokens what it costs 512 and the tiles
  cost it L / 512 of theirs: it wins from L = 318 (8 k, 16 k) to 352 (4 k).
  The DENSE launch (PR 64; my chip run, PR 64, one v5e; the same script and
  step, `latent_dense` against `latent_dense_wide`; ms a launch at 4 k / 8 k
  / 12 k / 16 k of context). At Kimi-Linear's 32 heads — two expanded
  programs; a tile of 32 tokens is 1024 row-heads — the absorbed tiles 1.039
  / 2.006 / 2.973 / 3.936, this body **0.668 / 1.212 / 1.760 / 2.307** (−36 /
  −40 / −41 / −41 %): 16.3 µs a (program, block) at 8 k, the masked kernel's
  17.1 less its selection, where the tiles pay 3.59 a (tile, block). At
  openPangu's 128 heads 3.687 / 7.061 / 10.420 / 13.785 → 2.548 / 4.569 /
  6.597 / 8.631 (−31 / −35 / −37 / −37 %: the masked kernel's pair, re-read
  in the same call at 3.707 / 7.044 / 10.387 / 13.718 → 2.543 / 4.511 / 6.474
  / 8.444), and at its cell's contexts, where a walk is 4 or 8 blocks and a
  program's fixed cost shows: 1 k 1.162 → 1.026 (−12 %), 2 k 2.002 → 1.534
  (−23 %). One-token rows 0.9–1.6 µs a trip, the tiles' either way.
"""

from __future__ import annotations

import functools
import types

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ollamamq_tpu.ops.pallas.kv_contract import cdiv

# Tokens of context a block of the indexer's walk: 8 pages of 32. The scores'
# lanes.
BLOCK = 256
# Tokens a tile of a ragged step (the decode scan's tiles hold one).
TILE = 16
# The attention kernel's own walk (the sweep in the docstring): tokens of
# context a block, tokens a tile of a ragged step, and tokens a chain — a
# tile's row-heads are folded as tile / CHAIN independent chains.
ATTEND_BLOCK = 256
ATTEND_TILE = 32
CHAIN = 8
NEG_INF = -1e30
# Where a row's running maximum starts: above NEG_INF, what a masked score
# is set to, so that exp(masked - m) is 0 while a row has met no kept key
# (no second select a score), and below any score.
M_INIT = -1e20
LANES = 128
VMEM_LIMIT = 96 * 1024 * 1024
# Tokens of a span that the dense kernel folds by themselves (`short`): a
# decode row's one, a verify span's [t, draft].
SHORT = 2
# The dense kernel's launch by the prediction module, on the device trace.
MTP_NAME = "mtp_latent_attention_pallas"
# An attention launch's EXPANDED body (the docstring's last part): tokens of
# a prefill span from which its programs expand each block's keys and values
# (under it the absorbed tiles cost less), heads a program, and heads a
# straight-line body of a program's loop over them.
WIDE = 320
WIDE_GROUP = 16
WIDE_CHAIN = 8
# ...and the longest stream whose tokens a program holds as its rows.
WIDE_STREAM = 512


def _walk(t, tile, refs, hbm, buf, sem, page_size, num_seqs, block, update,
          want=None, also=None):
    """The tile's walk: for every sequence with a row in tile `t`, its
    blocks of `block` tokens up to the deepest causal frontier among those
    rows, each waited for in `buf[slot]` and handed to `update(slot, b, lo,
    hi, base)` — rows [lo, hi) of the tile are the sequence's, row i at
    position base + i. `want(s)`: which of those sequences are walked at
    all; `also` = (start(b, slot), wait(slot)): what else travels with a
    block (the expanded body's selection scores: module docstring)."""
    layer_ref, first_ref, q_start_ref, q_len_ref, kv_len_ref, pt_ref = refs
    ppb = block // page_size
    layer = layer_ref[0]
    tile_lo = lax.mul(t, tile)
    tile_hi = lax.add(tile_lo, tile)

    def fetch(s, b, slot):
        for j in range(ppb):
            page = pt_ref[s, lax.add(lax.mul(b, ppb), j)]
            pltpu.make_async_copy(
                hbm.at[layer, pl.ds(lax.mul(page, page_size), page_size)],
                buf.at[slot, pl.ds(j * page_size, page_size)],
                sem.at[slot]).start()
        if also:
            also[0](b, slot)

    def wait(slot):
        pltpu.make_async_copy(hbm.at[layer, pl.ds(0, block)], buf.at[slot],
                              sem.at[slot]).wait()
        if also:
            also[1](slot)

    def overlaps(s):
        row = lax.min(s, num_seqs - 1)
        qs, ql = q_start_ref[row], q_len_ref[row]
        return functools.reduce(lax.bitwise_and, (
            lax.lt(s, num_seqs), lax.gt(ql, 0), lax.lt(qs, tile_hi),
            lax.gt(lax.add(qs, ql), tile_lo)))

    def one_sequence(s):
        if want is None:
            walk(s)
        else:
            pl.when(want(s))(lambda: walk(s))
        return lax.add(s, 1)

    def walk(s):
        qs, ql, kv = q_start_ref[s], q_len_ref[s], kv_len_ref[s]
        lo = lax.sub(lax.max(qs, tile_lo), tile_lo)
        hi = lax.sub(lax.min(lax.add(qs, ql), tile_hi), tile_lo)
        base = lax.sub(lax.add(lax.sub(kv, ql), tile_lo), qs)
        n = cdiv(lax.add(base, hi), block)  # frontier = base + hi
        fetch(s, 0, 0)

        def body(b, _):
            slot = lax.bitwise_and(b, 1)

            @pl.when(lax.lt(lax.add(b, 1), n))
            def _():
                fetch(s, lax.add(b, 1), lax.sub(1, slot))

            wait(slot)
            update(slot, b, lo, hi, base)
            return ()

        lax.fori_loop(0, n, body, ())

    lax.while_loop(overlaps, one_sequence, first_ref[t])


def _widen(x, width):
    """A lane-replicated `[rows, 128]` as `[rows, width]`: the same vregs
    again where `width` is whole lane tiles."""
    if width % LANES:
        return jnp.broadcast_to(x[:, :1], (x.shape[0], width))
    return jnp.concatenate([x] * (width // LANES), axis=1)


def _rows_of(shape, lo, hi):
    row = lax.broadcasted_iota(jnp.int32, shape, 0)
    return row, lax.bitwise_and(lax.ge(row, lo), lax.lt(row, hi))


def _index_kernel(*refs, tile, heads, page_size, num_seqs):
    meta, (q_ref, w_ref, hbm, o_ref, buf, sem) = refs[:6], refs[6:]
    o_ref[...] = jnp.zeros_like(o_ref)

    def update(slot, b, lo, hi, base):
        s = lax.dot_general(q_ref[...], buf[slot], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        s = jnp.maximum(s, 0.0) * w_ref[...]  # [tile*Hi, BLOCK]
        score = jnp.sum(s.reshape(tile, heads, BLOCK), axis=1)
        _, mine = _rows_of((tile, BLOCK), lo, hi)
        at = pl.ds(pl.multiple_of(lax.mul(b, BLOCK), BLOCK), BLOCK)
        o_ref[:, at] = jnp.where(mine, score, o_ref[:, at])

    _walk(pl.program_id(0), tile, meta, hbm, buf, sem, page_size, num_seqs,
          BLOCK, update)


def _select_kernel(s_ref, pos_ref, o_ref, *, topk):
    """thr [tile, 1]: the topk-th largest of each row's scores at positions
    <= pos, by a bitwise search over the scores' order-preserving int32 keys
    with the row's block resident in VMEM (ops/mla.select_threshold is the
    same search in XLA, on unsigned keys)."""
    b = lax.bitcast_convert_type(s_ref[...], jnp.int32)
    keys = jnp.where(b < 0, b ^ jnp.int32(0x7FFFFFFF), b)
    pos = pos_ref[...]  # [tile, 1]
    lowest = jnp.int32(-(1 << 31))
    live = lax.broadcasted_iota(jnp.int32, keys.shape, 1) <= pos
    keys = jnp.where(live, keys, lowest)

    def enough(cand):
        n = jnp.sum(jnp.where(keys >= cand, 1.0, 0.0), axis=1, keepdims=True)
        return n >= float(topk)

    ans = jnp.full(pos.shape, lowest, jnp.int32)
    ans = jnp.where(enough(jnp.zeros_like(ans)), 0, ans)  # the sign bit

    def bit(i, ans):
        cand = ans | lax.shift_left(jnp.int32(1), jnp.int32(30) - i)
        return jnp.where(enough(cand), cand, ans)

    ans = lax.fori_loop(0, 31, bit, ans)
    thr = lax.bitcast_convert_type(
        jnp.where(ans < 0, ans ^ jnp.int32(0x7FFFFFFF), ans), jnp.float32)
    o_ref[...] = jnp.where(pos + 1 > topk, thr, NEG_INF)


def _attend_kernel(*refs, tile, heads, rank, page_size, num_seqs, block,
                   chains, masked=True, wide=None):
    meta = refs[:6]
    if masked:
        q_ref, i_ref, thr_ref, *refs = refs[6:]
    else:  # no selection: neither its scores nor its threshold is here
        q_ref, *refs = refs[6:]
    if wide:  # the expanded body's operands, result and scratch (_expanded)
        hbm, *refs = refs
        n = 4 if masked else 2  # q, w and, of a selection, thr and scores
        ins, (o_ref, ow_ref, buf, sem, m_ref, l_ref, acc_ref, *scratch) = (
            refs[:n], refs[n:])
        q_len_ref = meta[3]

        @pl.when(lax.lt(pl.program_id(0), wide.lead))
        def _():
            _expanded(meta, ins, hbm, ow_ref, buf, sem, scratch, wide, rank,
                      page_size, num_seqs, block)
    else:
        hbm, o_ref, buf, sem, m_ref, l_ref, acc_ref = refs

    def scores(at, rows):
        return lax.dot_general(q_ref[at, :], rows, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)

    def fold(at, s, keep, n_tok, rows):
        """One block's scores `s` into the online softmax of the `n_tok *
        heads` row-heads at `at`; keep [n_tok, block] says what each token
        attends. A row's maximum and sum live lane-replicated / lane-partial
        in `[rows, 128]` (no broadcast a trip; the sum's lanes are added up
        once a program)."""
        keep = jnp.broadcast_to(keep[:, None, :], (n_tok, heads, block)
                                ).reshape(n_tok * heads, block)
        s = jnp.where(keep, s, NEG_INF)
        m_old = m_ref[at, :]
        m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - _widen(m_new, block))
        corr = jnp.exp(m_old - m_new)
        l_ref[at, :] = l_ref[at, :] * corr + functools.reduce(
            jnp.add, (p[:, j:j + LANES] for j in range(0, block, LANES)))
        acc_ref[at, :] = acc_ref[at, :] * _widen(corr, rank) + lax.dot_general(
            p.astype(rows.dtype), rows[:, :rank], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[at, :] = m_new

    def keep_of(r0, n, b, lo, hi, base):
        """What rows [r0, r0 + n) of the tile attend of block `b` (`r0` a
        constant where there is a selection to slice)."""
        row, mine = _rows_of((n, block), lax.sub(lo, r0), lax.sub(hi, r0))
        key = lax.add(lax.broadcasted_iota(jnp.int32, (n, block), 1),
                      lax.mul(b, block))
        keep = jnp.logical_and(mine, key <= row + (base + r0))
        if not masked:
            return keep
        at = pl.ds(pl.multiple_of(lax.mul(b, block), block), block)
        return jnp.logical_and(
            keep, i_ref[r0:r0 + n, at] >= thr_ref[r0:r0 + n, :])

    # A decode row in a tile of other sequences' tokens: its walk folds its
    # OWN `heads` row-heads, not the tile's (masked) `tile * heads` — a
    # `tile`-th of the MXU work for each such row of a step. Only
    # where a token's row-heads are whole sublane tiles (a dynamic slice at
    # `row * heads`), which every published width is.
    alone = tile > 1 and heads % 8 == 0
    per_chain = tile // chains  # tokens

    def whole(rows, b, lo, hi, base):
        """The tile's row-heads as `chains` independent chains in one
        straight-line body: chain h + 1's scores are asked of the MXU before
        chain h's softmax, chain h's P.V before chain h + 1's softmax (worth
        ~1 % over one chain: the scheduler overlaps one chain with itself)."""
        ats = [pl.ds(h * per_chain * heads, per_chain * heads)
               for h in range(chains)]
        s = scores(ats[0], rows)
        for h in range(chains):
            nxt = scores(ats[h + 1], rows) if h + 1 < chains else None
            fold(ats[h], s, keep_of(h * per_chain, per_chain, b, lo, hi, base),
                 per_chain, rows)
            s = nxt

    def short(rows, b, lo, hi, base):
        """A span of at most SHORT tokens — a decode row, a `--spec` verify
        span of one draft — folds the SHORT tokens' row-heads that hold it
        (from its first row, or the tile's last SHORT), not the tile's:
        rows of them that are another sequence's are masked, as in
        `whole`."""
        r0 = lax.min(lo, tile - SHORT)
        at = pl.ds(pl.multiple_of(lax.mul(r0, heads), heads), SHORT * heads)
        fold(at, scores(at, rows), keep_of(r0, SHORT, b, lo, hi, base), SHORT,
             rows)

    def update(slot, b, lo, hi, base):
        rows = buf[slot]  # [block, lanes]
        if not alone:
            return whole(rows, b, lo, hi, base)
        if not masked and tile >= SHORT:
            few = lax.le(lax.sub(hi, lo), SHORT)
            pl.when(few)(lambda: short(rows, b, lo, hi, base))
            pl.when(jnp.logical_not(few))(
                lambda: whole(rows, b, lo, hi, base))
            return
        one = lax.eq(lax.sub(hi, lo), 1)

        @pl.when(one)
        def _():  # `mine` is the one row: its line of `keep`, by a reduce
            keep = keep_of(0, tile, b, lo, hi, base)
            line = jnp.max(keep.astype(jnp.float32), axis=0, keepdims=True)
            at = pl.ds(pl.multiple_of(lax.mul(lo, heads), heads), heads)
            fold(at, scores(at, rows), line > 0.0, 1, rows)

        @pl.when(jnp.logical_not(one))
        def _():
            whole(rows, b, lo, hi, base)

    def tile_program(t=None, want=None):
        m_ref[...] = jnp.full_like(m_ref, M_INIT)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        _walk(pl.program_id(0) if t is None else t, tile, meta, hbm, buf, sem,
              page_size, num_seqs, block, update, want)
        o_ref[...] = (acc_ref[...] / jnp.maximum(
            jnp.sum(l_ref[...], axis=1, keepdims=True), 1e-30)
        ).astype(o_ref.dtype)

    if not wide:
        return tile_program()
    # Behind the expanded programs: the tiles, which leave a wide span's
    # rows to those — a tile that lies inside one returns at once (its block
    # of `o` is never read: `_attention`).
    t = lax.sub(pl.program_id(0), wide.lead)
    first = lax.min(meta[1][lax.max(t, 0)], num_seqs - 1)
    qs, ql = meta[2][first], q_len_ref[first]
    inside = functools.reduce(lax.bitwise_and, (
        lax.ge(ql, WIDE), lax.le(qs, lax.mul(t, tile)),
        lax.ge(lax.add(qs, ql), lax.mul(lax.add(t, 1), tile))))
    pl.when(lax.bitwise_and(lax.ge(t, 0), jnp.logical_not(inside)))(
        lambda: tile_program(t, lambda s: lax.lt(q_len_ref[s], WIDE)))


def _expanded(meta, ins, hbm, o_ref, buf, sem, scratch, wide, rank,
              page_size, num_seqs, block):
    """One expanded program: the step's wide spans (at least WIDE tokens)
    for `wide.group` heads. Its rows are the STREAM's tokens, all of
    them (row i is stream token i: the selection's scores and the result
    need no re-alignment to a span; another sequence's rows are masked, as
    in a tile). It walks each wide span's context once and, a block,
    expands `[K_h^T ; V_h^T] = [W_uk,h | W_uv,h]^T . c_kv^T` for all its
    heads in ONE contraction with the block's rows the stationary operand
    (float32 sums, rounded to the pool's dtype as the absorbed q is), then a
    head at a time scores the rows `[q_nope | q_rope] . [K_h^T ; k_rope^T]`
    under the same mask and folds them into the same online softmax as a
    tile does, `acc += p . V_h`: the result leaves in `v_head_dim` lanes.
    The dense kernel's launch hands no selection (`ins` is q and w alone):
    nothing travels with a block — neither its operands nor its scratch is
    here — and a row attends every position of its sequence up to its own."""
    q_ref, w_ref, *selection = ins
    *travels, bias_ref, m_ref, l_ref, acc_ref, kvt_ref = scratch
    masked = bool(selection)
    q_len_ref = meta[3]
    tokens, per, dn = q_ref.shape[1], w_ref.shape[1], wide.nope
    m_ref[...] = jnp.full_like(m_ref, M_INIT)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    also = None
    if masked:
        (thr_ref, i_hbm), (ibuf, isem) = selection, travels

        def columns(b):
            return pl.ds(pl.multiple_of(lax.mul(b, block), block), block)

        def start(b, slot):  # the scores of the block's positions
            pltpu.make_async_copy(i_hbm.at[:, columns(b)], ibuf.at[slot],
                                  isem.at[slot]).start()

        def wait(slot):
            pltpu.make_async_copy(i_hbm.at[:, columns(0)], ibuf.at[slot],
                                  isem.at[slot]).wait()

        also = (start, wait)

    def contract(a, b, b_dim):
        return lax.dot_general(a, b, (((1,), (b_dim,)), ((), ())),
                               preferred_element_type=jnp.float32)

    def update(slot, b, lo, hi, base):
        key0 = lax.mul(b, block)
        # Rows past the span's last position are the page's, not the
        # sequence's (the trash page's, an earlier request's): zero, so that
        # what is expanded from them is finite under the mask.
        rows = buf[slot]
        own = lax.lt(lax.add(lax.broadcasted_iota(
            jnp.int32, rows.shape, 0), key0), lax.add(base, hi))
        rows = jnp.where(own, rows, jnp.zeros_like(rows))
        # The mask, once for the heads: 0 where row i attends the key.
        row, mine = _rows_of((tokens, block), lo, hi)
        key = lax.add(lax.broadcasted_iota(jnp.int32, (tokens, block), 1),
                      key0)
        keep = [mine, key <= row + base]
        if masked:
            keep.append(ibuf[slot] >= thr_ref[...])
        keep = functools.reduce(jnp.logical_and, keep)
        bias_ref[...] = jnp.where(keep, 0.0, NEG_INF)
        kvt_ref[...] = contract(w_ref[...].reshape(-1, rank), rows[:, :rank],
                                1).astype(rows.dtype)
        # k_rope^T through the MXU too: an identity's rows, exact
        rope_t = contract(jnp.eye(rows.shape[1] - rank, dtype=rows.dtype),
                          rows[:, rank:], 1).astype(rows.dtype)

        def head(h):
            at = pl.multiple_of(lax.mul(h, per), per)
            k_t = jnp.concatenate([kvt_ref[pl.ds(at, dn), :], rope_t], axis=0)
            v_t = kvt_ref[pl.ds(lax.add(at, dn), per - dn), :]
            s = contract(q_ref[h], k_t, 0) + bias_ref[...]
            m_old = m_ref[h]
            m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - _widen(m_new, block))
            corr = jnp.exp(m_old - m_new)
            l_ref[h] = l_ref[h] * corr + functools.reduce(jnp.add, (
                p[:, j:j + LANES] for j in range(0, block, LANES)))
            acc_ref[h] = acc_ref[h] * _widen(corr, per - dn) + contract(
                p.astype(rows.dtype), v_t, 1)
            m_ref[h] = m_new

        def heads(i, _):  # `wide.chain` heads in one straight-line body
            for j in range(wide.chain):
                head(lax.add(lax.mul(i, wide.chain), j))
            return ()

        lax.fori_loop(0, wide.group // wide.chain, heads, ())

    _walk(0, tokens, meta, hbm, buf, sem, page_size, num_seqs, block, update,
          lambda s: lax.ge(q_len_ref[s], WIDE), also)
    for h in range(wide.group):
        o_ref[h] = (acc_ref[h] / jnp.maximum(
            jnp.sum(l_ref[h], axis=1, keepdims=True), 1e-30)
        ).astype(o_ref.dtype)


def _launch(kernel, tile, block, inputs, pool, out_lanes, out_dtype, scratch,
            layer, page_table, q_start, q_lens, kv_lens, page_size, interpret,
            name=None, wide=None):
    """One program a tile: the tile's blocks of `inputs` ([n_tiles, rows,
    lanes] each) in VMEM, the pool left in HBM, two buffers of `block`
    tokens. `wide` (an attention kernel's, `_wide_launch`): its
    `lead` expanded programs run before the tiles', their operands behind
    the pool, their result and scratch last."""
    n_tiles = inputs[0].shape[0]
    ppb = block // page_size
    page_table = page_table.astype(jnp.int32)
    page_table = jnp.pad(page_table, ((0, 0), (0, -page_table.shape[1] % ppb)))
    ends = (q_start + q_lens).astype(jnp.int32)
    tile_first = jnp.searchsorted(
        ends, jnp.arange(n_tiles, dtype=jnp.int32) * tile, side="right"
    ).astype(jnp.int32)
    lead = wide.static.lead if wide else 0

    def spec(shape):
        at = (lambda i, *_: (i, 0, 0)) if not lead else (
            lambda i, *_: (jnp.maximum(i - lead, 0), 0, 0))
        return pl.BlockSpec((None,) + shape[1:], at, memory_space=pltpu.VMEM)

    out_shape = (n_tiles, out_lanes[0], out_lanes[1])
    operands = [*inputs, pool]
    in_specs = [spec(x.shape) for x in inputs] \
        + [pl.BlockSpec(memory_space=pl.ANY)]
    out_specs = spec(out_shape)
    out_shapes = jax.ShapeDtypeStruct(out_shape, out_dtype)
    scratch = [pltpu.VMEM((2, block, pool.shape[-1]), pool.dtype),
               pltpu.SemaphoreType.DMA((2,))] + scratch
    if wide:
        operands += wide.operands
        in_specs += wide.in_specs
        out_specs, out_shapes = [out_specs, wide.out_spec], [
            out_shapes, wide.out_shape]
        scratch += wide.scratch
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6, grid=(lead + n_tiles,),
            in_specs=in_specs, out_specs=out_specs, scratch_shapes=scratch),
        out_shape=out_shapes,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret, name=name,
    )(jnp.asarray(layer, jnp.int32).reshape(1), tile_first,
      q_start.astype(jnp.int32), q_lens.astype(jnp.int32),
      kv_lens.astype(jnp.int32), page_table, *operands)


def _tiles(x, tile):
    """[T, ...] -> [n_tiles, tile * prod(mid), lanes], T padded to tiles."""
    T = x.shape[0]
    x = jnp.pad(x, ((0, -T % tile),) + ((0, 0),) * (x.ndim - 1))
    return x.reshape(x.shape[0] // tile, -1, x.shape[-1])


def context_lanes(max_pages: int, page_size: int) -> int:
    """Width of the `[T, C]` index scores: the table's context in whole
    blocks of the wider of the two walks (the indexer writes them a BLOCK,
    the attention kernel reads them an ATTEND_BLOCK)."""
    wide = max(BLOCK, ATTEND_BLOCK)
    return -(-max_pages * page_size // wide) * wide


@functools.partial(jax.jit,
                   static_argnames=("page_size", "tile", "interpret"))
def dsa_index_pallas(q_idx, w, idx_pool, layer, page_table, q_start, q_lens,
                     kv_lens, page_size: int, tile: int | None = None,
                     interpret: bool = False):
    """I [T, C] float32 (C = context_lanes): q_idx [T, Hi, di] in the pool's
    dtype, w [T, Hi] float32, idx_pool [L, S, di]. Positions past a token's
    sequence's frontier hold 0 or what the trash page scores."""
    T, Hi, _ = q_idx.shape
    tile = tile or TILE
    C = context_lanes(page_table.shape[1], page_size)
    kernel = functools.partial(_index_kernel, tile=tile, heads=Hi,
                               page_size=page_size,
                               num_seqs=page_table.shape[0])
    out = _launch(kernel, tile, BLOCK,
                  [_tiles(q_idx, tile),
                   _tiles(w.astype(jnp.float32)[..., None], tile)], idx_pool,
                  (tile, C), jnp.float32, [], layer, page_table, q_start,
                  q_lens, kv_lens, page_size, interpret)
    return out.reshape(-1, C)[:T]


@functools.partial(jax.jit, static_argnames=("topk", "tile", "interpret"))
def dsa_select_pallas(scores, tok_pos, topk: int, tile: int | None = None,
                      interpret: bool = False):
    """thr [T] float32 of scores [T, C] and tok_pos [T] (-1: padding), as
    ops/mla.select_threshold."""
    T = scores.shape[0]
    tile = tile or TILE
    tok_pos = jnp.pad(tok_pos.astype(jnp.int32), (0, -T % tile),
                      constant_values=-1)
    inputs = [_tiles(scores, tile), tok_pos.reshape(-1, tile, 1)]

    def spec(shape):
        return pl.BlockSpec((None,) + shape[1:], lambda i: (i, 0, 0),
                            memory_space=pltpu.VMEM)

    out_shape = (inputs[0].shape[0], tile, 1)
    out = pl.pallas_call(
        functools.partial(_select_kernel, topk=topk),
        grid=(out_shape[0],),
        in_specs=[spec(x.shape) for x in inputs], out_specs=spec(out_shape),
        out_shape=jax.ShapeDtypeStruct(out_shape, jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(*inputs)
    return out.reshape(-1)[:T]


@functools.partial(jax.jit, static_argnames=("page_size", "rank", "tile",
                                             "interpret"))
def mla_sparse_paged_attention_pallas(q_abs, scores, thr, lat_pool, layer,
                                      page_table, q_start, q_lens, kv_lens,
                                      page_size: int, rank: int,
                                      tile: int | None = None,
                                      interpret: bool = False, expanded=None):
    """o [T, H, rank] in q's dtype: q_abs [T, H, latent] (absorbed, scaled),
    scores [T, C] and thr [T] float32 (the selection), lat_pool [L, S,
    latent]. With `expanded` = (q [T, H, nope + latent - rank]: `[q_nope |
    q_rope | 0]`, scaled; w [H, nope + v, rank]: `[W_uk,h | W_uv,h]^T` a
    head, the rank minor) and a launch that holds the expanded body
    (`expands`): (o, o_v [T, H, v], wide [T] bool) — a token of a span of
    at least WIDE tokens has its result in `o_v`, through W_uv already, and
    nothing in `o`."""
    return _attention(q_abs, (scores, thr), lat_pool, layer, page_table,
                      q_start, q_lens, kv_lens, page_size, rank, tile,
                      interpret, expanded=expanded)


@functools.partial(jax.jit, static_argnames=("page_size", "rank", "tile",
                                             "interpret", "name"))
def mla_dense_paged_attention_pallas(q_abs, lat_pool, layer, page_table,
                                     q_start, q_lens, kv_lens, page_size: int,
                                     rank: int, tile: int | None = None,
                                     interpret: bool = False,
                                     name: str | None = None, expanded=None):
    """o [T, H, rank] with NO selection: every cached position up to the
    token's own (a model with no indexer). `name`: the launch's name on the
    device trace, where it is not this function's (the prediction module's:
    such a launch holds the tiles alone, whatever it is handed). `expanded`
    and what then comes back: mla_sparse_paged_attention_pallas."""
    return _attention(q_abs, None, lat_pool, layer, page_table, q_start,
                      q_lens, kv_lens, page_size, rank, tile, interpret,
                      name or "mla_dense_paged_attention_pallas",
                      None if name else expanded)


def expands(tokens: int, heads: int, lanes: int, rank: int, nope: int,
            v: int) -> bool:
    """Does an attention kernel's launch over a stream of `tokens` hold the
    expanded body? Where a span of WIDE tokens fits the rung and the rung a
    program's rows, and the head widths are whole lane tiles (the body
    slices and joins at them)."""
    group = min(WIDE_GROUP, heads)
    return (WIDE <= tokens <= WIDE_STREAM and tokens % 8 == 0
            and heads % group == 0 and group % min(WIDE_CHAIN, group) == 0
            and not any(n % LANES for n in (nope, v, rank, lanes - rank)))


def wide_tokens(spans, stream_len: int, heads: int, lanes: int, rank: int,
                nope: int, v: int) -> int:
    """Stream tokens of a ragged step that a launch attends in the
    expanded form: those of its spans of at least WIDE tokens, on a rung
    (`stream_len` tokens, padding included) that holds the expanded body.
    `spans`: each row's tokens. The kernel's own test (`expands`,
    `_expanded`'s `want`), on the host."""
    if not expands(stream_len, heads, lanes, rank, nope, v):
        return 0
    return sum(n for n in spans if n >= WIDE)


def _attention(q_abs, selection, lat_pool, layer, page_table, q_start, q_lens,
               kv_lens, page_size, rank, tile, interpret, name=None,
               expanded=None):
    """The attention kernel's launch; `selection` (scores, thr) or None;
    `expanded`: mla_sparse_paged_attention_pallas."""
    T, H, lanes = q_abs.shape
    # a step no longer than the indexer's tile stays one tile of that size
    tile = tile or (ATTEND_TILE if T > TILE else TILE)
    wide = None
    if expanded is not None:
        q, w = expanded
        nope = q.shape[-1] - (lanes - rank)
        if expands(T, H, lanes, rank, nope, w.shape[1] - nope):
            wide = _wide_launch(q, w, nope, selection)
    kernel = functools.partial(_attend_kernel, tile=tile, heads=H, rank=rank,
                               page_size=page_size,
                               num_seqs=page_table.shape[0],
                               block=ATTEND_BLOCK,
                               chains=tile // CHAIN if tile % CHAIN == 0
                               else 1, masked=selection is not None,
                               wide=wide and wide.static)
    rows = tile * H
    scratch = [pltpu.VMEM((rows, LANES), jnp.float32),
               pltpu.VMEM((rows, LANES), jnp.float32),
               pltpu.VMEM((rows, rank), jnp.float32)]
    inputs = [_tiles(q_abs, tile)]
    if selection is not None:
        scores, thr = selection
        inputs += [_tiles(scores, tile),
                   _tiles(thr.astype(jnp.float32)[:, None], tile)]
    out = _launch(kernel, tile, ATTEND_BLOCK, inputs, lat_pool,
                  (rows, rank), q_abs.dtype, scratch, layer, page_table,
                  q_start, q_lens, kv_lens, page_size, interpret, name, wide)
    if not wide:
        return out.reshape(-1, H, rank)[:T]
    out, o_v = out
    at = jnp.arange(T, dtype=jnp.int32)[:, None]
    served = jnp.any((q_lens >= WIDE) & (q_start <= at)
                     & (at < q_start + q_lens), axis=1)
    return (out.reshape(-1, H, rank)[:T], jnp.swapaxes(o_v, 0, 1), served)


def _wide_launch(q, w, nope, selection):
    """What `_launch` and the kernel need of the expanded programs: q [T,
    H, lanes] and the result head-major, a group of heads a program; with a
    `selection` (scores, thr) its threshold a row in VMEM, its scores left
    in HBM and the two buffers a block of them travels through."""
    T, H, _ = q.shape
    group = min(WIDE_GROUP, H)
    v = w.shape[1] - nope
    lead = H // group

    def a_group(shape):
        return pl.BlockSpec(
            (group,) + shape[1:],
            lambda i, *_: (jnp.minimum(i, lead - 1), 0, 0),
            memory_space=pltpu.VMEM)

    f32 = jnp.float32
    operands = [jnp.swapaxes(q, 0, 1), w]
    in_specs = [a_group((H, T, q.shape[-1])), a_group(w.shape)]
    travels = []
    if selection is not None:
        scores, thr = selection
        operands += [thr.astype(f32)[:, None], scores]
        in_specs += [pl.BlockSpec((T, 1), lambda i, *_: (0, 0),
                                  memory_space=pltpu.VMEM),
                     pl.BlockSpec(memory_space=pl.ANY)]
        travels = [pltpu.VMEM((2, T, ATTEND_BLOCK), f32),
                   pltpu.SemaphoreType.DMA((2,))]
    return types.SimpleNamespace(
        static=types.SimpleNamespace(lead=lead, group=group, nope=nope,
                                     chain=min(WIDE_CHAIN, group)),
        operands=operands, in_specs=in_specs, out_spec=a_group((H, T, v)),
        out_shape=jax.ShapeDtypeStruct((H, T, v), q.dtype),
        scratch=travels + [
            pltpu.VMEM((T, ATTEND_BLOCK), f32),
            pltpu.VMEM((group, T, LANES), f32),
            pltpu.VMEM((group, T, LANES), f32),
            pltpu.VMEM((group, T, v), f32),
            pltpu.VMEM((group * w.shape[1], ATTEND_BLOCK), q.dtype)])


# What a LAYER computes for a launch that holds the expanded body
# (models/llama.py:_latent_attention_op). Rows from the stream's start that it
# always hands the launch in the ABSORBED form: where every row behind them is
# a wide span's or padding — a prompt's chunk behind a few decode rows, the
# engine's common step — it takes these alone through W_uk and, behind the
# launch, through W_uv, not the rung: one tile of the attention's, so that no
# tile that walks reads another row's q (kept at the file's end: a kernel's
# serialised body carries its source lines, and no other launch's program
# should move).
ABSORBED_LEAD = ATTEND_TILE


def absorbed_lead(tokens: int, heads: int, lanes: int, rank: int, nope: int,
                  v: int) -> int:
    """Rows of that lead on a rung of `tokens`: 0 where there is nothing to
    choose — the launch holds no expanded body, or the rung is not whole
    tiles of the lead (every row is absorbed there, as ever)."""
    whole = tokens % ABSORBED_LEAD == 0
    return ABSORBED_LEAD * (whole and expands(tokens, heads, lanes, rank,
                                              nope, v))


def absorbed_few(q_start, q_len):
    """May a layer leave a span (or, of arrays, each span) out of the
    absorbed form behind the lead? Where it is one the expanded programs
    serve (`_expanded`'s `want`), padding (a row of no tokens), or ends
    inside the lead. Where this holds for EVERY row of a step, no row at or
    behind ABSORBED_LEAD reads the absorbed form: a layer asks on the
    device, of the step's own `q_start` / `q_lens`; the engine's counter on
    the host (`absorbed_rows`)."""
    return ((q_len >= WIDE) | (q_len <= 0)
            | (q_start + q_len <= ABSORBED_LEAD))


def absorbed_rows(spans, stream_len: int, heads: int, lanes: int, rank: int,
                  nope: int, v: int) -> int:
    """Stream rows of a ragged step that a layer takes through the absorbed
    form's two contractions (W_uk before the launch, W_uv behind it):
    ABSORBED_LEAD where the rows behind those are wide spans'
    (`absorbed_few`), else the rung's `stream_len`; 0 where there is no lead
    (`absorbed_lead`). `spans`: each row's tokens, in the stream's order from
    its start."""
    lead = absorbed_lead(stream_len, heads, lanes, rank, nope, v)
    start, few = 0, True
    for n in spans:
        few &= bool(absorbed_few(start, n))
        start += n
    return lead if few or not lead else stream_len
