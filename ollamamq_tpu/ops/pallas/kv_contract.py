"""What the two paged-attention kernels share: the page stream and the
inner product.

`ragged_attention.py` (one program a stretch of a mixed stream: a tile of
G_TILE tokens, or TALL of them on a rung that holds whole stretches) and
`paged_attention.py` (one program a decode row) differ in their grid
and in what a walk's successor is. Everything inside a sequence's walk is
here, once: `PageStream` moves K/V pages HBM→VMEM in blocks through a
ring of buffers, and an inner product — `Mxu` or `Vpu` — folds one
buffered block into the online-softmax state `(acc, m_i, l_i)` of the
rows the program holds.

Which inner product (`choose_inner`, a function of the row-heads M =
rows × group that share each kv head's K/V in one program — known at
trace time from the kernel and the model's `(group, head_dim)`; no flag,
no environment variable, no model name):

  M > 1 → `Mxu`: the ragged kernel always (a tile's 8 rows, or the 64
      of a stretch inside one span), the decode kernel when group > 1. Per block and per lane tile ONE contraction
      serves every query row-head that shares the K/V: scores `[M, blk] =
      q_t [M, W] · k_t [blk, W]ᵀ` on the MXU with the pool's dtype as
      operands (bf16 × bf16 products are exact) and float32 accumulation,
      an online softmax in float32 on `[M, blk]` with the block's tokens
      along the lanes (a block is 128 tokens: 4 pages of 32), `acc_t [M, W]
      += p · v_t` on the MXU again, P cast ONCE to the pool's dtype ("P
      into P·V" below). `m_i`, `l_i`, `acc` are float32 and touched once a
      (block, tile), not once a (row, group, page).
        head_dim % 128 == 0: a lane tile IS a kv head, `k_buf[..., h*hd:
      (h+1)*hd]` is a free lane-tile slice of the buffer, M = rows*group
      (56 for Qwen2.5-7B's ragged tile, padded to 64).
        head_dim < 128 (64: LFM2, llama3.2): 128 // hd heads share a lane
      tile and Mosaic cannot slice inside one. The WRAPPER packs q so
      that each head's rows carry zeros in the other heads' lanes and
      stacks the tile's heads along M; the kernel contracts the whole
      tile — the zeros drop the other heads' k out of the scores — and
      the wrapper keeps each row's own lanes of the result. Twice the
      MXU work, no relayout, and the kernel does not know: it sees
      `tiles` tiles of width W with `Mp` rows each.
        head_dim = 128 + r, r | 128, beside a VALUE head of 128 (192 / 128:
      MiMo-V2-Flash; `MxuSplit`, PR 65): the third layout case. The pools'
      rows are stored SPLIT (`ops/attention.py:lay_heads`) — K `[Hk x 128 |
      Hk x r]`, V `[Hk x 128]`: no lane the model lacks, every head an
      aligned tile, 128 // r heads share the tile of their rests — so a
      lane tile of the state is a kv head as at 128 lanes, and the scores
      are TWO contractions a (block, head): q's first 128 lanes against the
      head's tile, q's rest — zero-padded in the other heads' lanes of the
      shared tile, the packed-heads case's trick — against that tile, summed
      in float32; p · v one. q arrives `[.., M, 256]`, the output leaves
      `[.., M, 128]` (`o_block`: the one shape whose output block is not
      q's). Both widths are read off the operands (q's last axis, the V
      pool's lanes over the kv heads): with them equal and no sink a launch
      is what it was (`tests/test_mimo_v2_flash_kernels.py` holds operands,
      grid, scratch and contractions a tile at four head shapes). M = rows x
      16 in the full layers: a tile of 8 rows is 128 row-heads, a `TALL`
      stretch 1024 a lane tile — kept at 64 tokens: Mosaic fits it in the
      scoped VMEM (q 2 x 2 MB double-buffered at 256 lanes, the state 6 MB;
      AOT, `tests/test_chip_compile_mimo.py`) and the trip does a stretch's
      work for 4 kv heads in 3.3 µs where (64, 8, 128)'s takes 2.3 for 8 at
      half the rows; `TALL` 32 there reads 2.49 ms a launch at 8 k for 64's
      2.36 (939 tall trips of 1.87 µs for 439 of 3.39; PR 65, call 5). Measured, ms a launch (`scripts/attn_kernel_bench.py
      --shapes 8 9`, one v5e; my chip run, PR 65, call 3; (64, 8, 128) in
      call 2), `decode` / `ragged64` / `ragged512`, `raggedlong` at 4 k / 8
      k / 16 k with µs a tall / a short trip, and the window launch (at the
      new shapes WITH a sink):
        (64, 4, 192/128)  0.190 0.213 0.319   1.202 2.343 4.627
                          3.56 3.35 3.25 / 1.12 1.08 1.06   window 0.242
        (64, 8, 192/128)  0.328 0.279 0.443   1.396 2.672 5.222
                          3.82 3.48 3.31 / 1.46 1.42 1.39   window 0.329
        (64, 8, 128)      0.188 0.171 0.233   0.896 1.767 3.502
                          2.49 2.38 2.31 / 0.92 0.90 0.89   window 0.150
      — a full layer's launch at 8 k costs 1.33 x K-EXAONE's for 1.25 x its
      FLOPs a pair (320 lanes for 256); every row agrees with its jnp twin
      to the bf16 output's last bit or two (max |diff| 0.0005-0.016).
  M == 1 → `Vpu`: the decode kernel of an MHA model (OLMoE, 16 × 128).
      The body both kernels had before PR 33: per (group, page) `k * q`
      over the whole `[page_size, Hk*hd]` page in float32 on the VPU,
      per-head sums and expansions through constant 0/1 segment matrices
      `[lanes, Hk]` on the MXU, no relayout at any head_dim. One row-head
      a kv head is a matrix-vector product, where an MXU pass buys
      nothing and costs a K-tile weight load a head.

Measured, ms a launch of 64 decode rows over 200-380 tokens of context
(my chip run, PR 34, `scripts/attn_kernel_bench.py`, one v5e; vpu → mxu):
  (28, 4, 128)  decode 0.824 → 0.170   ragged 0.981 → 0.180
  (8, 2, 128)   decode 0.264 → 0.124   ragged 0.329 → 0.138
  (32, 8, 64)   decode 0.529 → 0.178   ragged 0.620 → 0.191
  (16, 16, 128) decode 0.368 → 0.473   ragged 0.657 → 0.476
and with two 228-token prefill spans in a 512-token ragged step 4.48 →
0.281, 1.01 → 0.206, 2.04 → 0.359, 1.58 → 0.696 (the ragged kernel's vpu
figures: PR 33's run, before that body was deleted). Only the decode
kernel at group 1 is faster on the VPU; that is the one shape that keeps
it.

The page stream: what moves, and what a launch's time is made of (PR 38).
The UNIT IS THE BLOCK: `block_pages` pages (4 of 32 tokens under the Mxu
body — the scores' 128 lanes — one under the Vpu body) into one ring slot.
`PageStream.start` has ONE predicate, the block exists (it holds one of
the walk's pages), and under it copies every page of the block, pool by
pool, in straight-line code; `PageStream.wait` has none and waits once a
pool with a block-sized descriptor (a DMA semaphore counts bytes: four
page-sized starts balance one `[block, lanes]` wait, and the wait needs
no page-table read). Pages of a block past the walk's last read what the
table holds there: THE TRASH PAGE (page 0; `engine/kv_cache.py` pads
every row with it, and `whole_blocks` pads a table whose width is no
multiple of a block). Their tokens are masked (`pos < kv`, the causal
test) and p is 0 there, so what the invariant needs is that page 0 holds
FINITE values — it holds what padding rows wrote — and then 0 · v is 0
and every output bit is what it was (`tests/`: the trash page poisoned
with ±1e30 and the dtype's largest). A slot is therefore always written
whole before it is read, and the ring is never cleared. The over-read is
the last block's: 615 → 692 pages a launch of 64 rows over 200-380
tokens (+12.5 %), 771 → 932 with two prefill spans beside 56 rows.
  One predicate a block for sixteen a block bought NOTHING by itself
(my chip run, PR 38, call 1: 0.1722 → 0.1720 ms a decode launch at (28, 4,
128), 0.1247 → 0.1276 at (8, 2, 128), every row within 2 %): a `pl.when`
whose region RUNS costs the scalar core next to nothing. What a launch's
time is made of, from the same script's sweeps (64 rows, every row at
128 … 2048 tokens, and the stream with its copies or its arithmetic
taken out; calls 2-7), at (8, 2, 128), µs:
  - a block once a walk is under way: 0.43, of which arithmetic alone
    0.36 (two lane tiles; 0.62 / 0.55 at four) and copies alone 0.31 —
    they overlap, and what is left over (0.07) is a block's 8 descriptors
    issued after its arithmetic. A block of 256 or 512 tokens, or one
    descriptor a pool a block, changes none of it; a ring of 4 blocks
    for 2 takes 0.43 to 0.38 (2048 tokens: 0.452 → 0.383 ms a launch) but
    costs the 200-380 mix 0.089 → 0.122, so the rings stay.
  - a program (a decode row) before its first block: 0.77 at the parent,
    0.22 with the copies taken out. THAT was the constant, and three
    things made it: (1) a page copy started and waited for at once takes
    0.73 µs (8 copies: 0.90; `--probe-dma`), and the parent started a
    row's first blocks in its predecessor's epilogue, ~0.2 µs before
    their wait — every row, and in the ragged kernel every (tile,
    sequence) walk, began on a cold DMA; (2) `x // d` and `x % d` on the
    scalar core cost ~0.1-0.2 µs EACH (no divider: `cdiv` and `mod` here
    are a shift and a mask where d is a power of two, as every page
    size, block and ring in use is), one a block and three or four a
    program; (3) a `pl.when` whose region is SKIPPED costs ~0.08 µs — a
    taken jump — where one that runs is free: the parent skipped two
    refills at the end of every walk, four starts at the head of every
    program, and up to seven trailing sequences of every prefill tile.
  So the ring runs over a launch's walks as ONE stream of blocks
(`paged_attention.py`, `ragged_attention.py`): the refill of a consumed
slot that falls past a walk's last block starts the NEXT walk's block,
a slot's position is carried from walk to walk in SMEM, what a short
walk must start before its loop sits under one predicate that a walk of
nbuf blocks skips as a whole, and the ragged kernel's walk over a tile's
sequences ends at the last that has a row in the tile. Measured, ms a
launch, parent → this (my chip run, PR 38, call 7; 64 rows over 200-380
tokens: decode, ragged, ragged with two 228-token prefill spans):
  (28, 4, 128)   0.1705 → 0.1231   0.1785 → 0.1280   0.2793 → 0.1839
  (8, 2, 128)    0.1238 → 0.0892   0.1336 → 0.0960   0.2074 → 0.1336
  (32, 8, 64)    0.1833 → 0.1350   0.1907 → 0.1378   0.3599 → 0.2628
  (16, 16, 128)  0.3696 → 0.3317*  0.4791 → 0.3477   0.6978 → 0.4860
  (30, 30, 128)  0.5530 → 0.4936*  0.8193 → 0.6157   1.1832 → 0.8510
(* the Vpu body, a block of one page.) Per (sequence, block) at (8, 2,
128): 0.72 → 0.52 µs, of which the arithmetic is 0.36 a block and 0.22 a
program: what is left to take is ~0.1 µs a block.

How many rows share a block's trip: the tile follows the span (PR 48). A
trip — one block folded into one tile's state — costs 0.55-0.63 µs at M =
64 row-heads whatever the rows hold: ~0.17 µs of MXU work, ~0.17 of QKᵀ →
max → exp → P·V chain latency that no width moves, the VPU's softmax on
`[64, 128]`, and K/V tiles latched into the MXU for 64 rows each. A
prefill span's tiles all walk the SAME blocks, so a 507-token chunk over
8 k of context was 63 tiles × ~64 blocks of them. `Mxu.update(...,
sub=None)` folds a block into ALL of a program's tiles at once — TALL //
G_TILE of them, merged along M: `[512, W] · [128, W]ᵀ` at group 8 — and
the ragged kernel runs it for a program whose TALL = 64 stream tokens lie
inside one span; every other stretch (decode rows, a span's head and
tail, padding, any rung of fewer than 2 * TALL tokens) keeps tiles of 8.
Measured, `scripts/attn_kernel_bench.py --traffic raggedlong` at (16, 2,
256) — 5 one-token rows then one 507-token span, every sequence at one
context; ms a launch, then µs a short trip / a tall trip (my chip run,
PR 48, call 1, one v5e):
  context   parent          every tile 64   TALL 32        TALL 64        TALL 128
  4096      1.301 (0.64)    0.857 (2.12)    0.687 (1.03)   0.689 (2.03)   0.736 (3.58)
  8192      2.627 (0.62)    1.692 (2.06)    1.361 (1.00)   1.369 (1.98)   1.469 (3.53)
  16384     5.278 (0.61)    3.369 (2.04)    2.707 (0.99)   2.728 (1.95)   2.935 (3.47)
("every tile 64": `--set ragged_attention.G_TILE=64,kv_contract.G_TILE=64`,
what decode rows would pay if they were not kept at 8: their trips cost
2.0 µs for ONE live row.) A tall trip of 64 tokens does the work of eight
short ones (4.9 µs) in 1.95-2.03, against 1.36 µs of MXU work at the bf16
peak with the three bf16 terms P then went into P·V as (until PR 59,
below); 32 tokens a trip cost the same a token, and 128
a little less a token but leave a longer head to the short tiles, and at
(30, 30, 128) the compiler refuses them (19.65 MB of scoped VMEM for a
limit of 16): 64. What is left of the 8 k launch: 808 short trips (the
five decode rows' 320 at one live row of 8, the span's first 59 tokens'
488) 0.50 ms, 439 tall trips 0.87 ms.
  The tall trip's lane tiles are straight-line code up to `TALL_UNROLL` = 8
of them (PR 61) and a loop in the program beyond — the one place where that
loop is rolled. What decides is what a rung's second body costs every start
(`setup_s`) against what the roll costs a launch, and both moved. PR 48
rolled the loop beyond TWO tiles: unrolled it was one more copy of the inner
product a lane tile — 1142 → 2347 equations at (16, 16, 128), 1954 → 3985 at
(30, 30, 128), OLMoE's warm start +1.6-2.9 s of ~42 and the hybrid's +4.1-4.3
of ~68 over the three rungs that carry it (my chip run, PR 48, call 2) where
rolled the same rungs read +0.5 and +0.7 s (call 3) — and the roll then cost
a launch NOTHING at 448-512 row-heads a 128-lane tile ((28, 4, 128) 3.04 /
3.03 µs a tall trip rolled / unrolled, (32, 8, 64) 3.38 / 3.35; 2.5-2.7 × at
the MHA shapes' 128 row-heads a tile: (16, 16, 128) 6.85 / 2.72, (30, 30, 128)
12.71 / 4.67; call 3): three bf16 terms of P·V hid every chain's latency. PR
59 took two of the terms out, and the roll came out as HALF the trip.
Re-measured (my chip run, PR 61, call 1; `raggedlong`, one v5e, parent and
change in one call, each row twice within 1 %), µs a tall trip at 4 k / 8 k /
16 k rolled beyond two → unrolled, then ms a launch and ms a window layer's
launch at 8 k:
  (64, 8, 128)  4.84 4.71 4.65 → 2.51 2.36 2.30   2.797 → 1.765   0.211 → 0.149
  (28, 4, 128)  2.53 2.46 2.43 → 1.52 1.46 1.42   1.625 → 1.185   0.133 → 0.110
  (20, 4, 128)  2.28 2.23 2.20 → 1.22 1.19 1.15   1.621 → 1.162   0.114 → 0.085
  (32, 8, 64)   2.85 2.62 2.51 → 1.83 1.61 1.50   1.703 → 1.264   0.199 → 0.174
and `ragged512` (two 228-token spans over no prefix) 0.2569 → 0.2339, 0.1724
→ 0.1618, 0.1852 → 0.1738, 0.2521 → 0.2426; `decode` and `ragged64` within
1 % (a 64-token rung traces no tall body). The form in between at eight
tiles — a loop of two trips, four unrolled tiles each — reads 3.62 µs a trip
and 2.315 ms a launch at 8 k (a loop of four trips of two: 4.44, 2.679): 47 %
of the full unroll's gain for half its copies, so all eight are unrolled. A
loop's edge is what costs: whatever crosses it waits for the chain before it.
  What the copies cost a start is the other half of PR 61. A copy's price was
never its equations but its nested jits ("what a rung's trace is charged
for", below): `fold` and `finish` bound ~23 a lane tile. Written with `lax`
primitives a copy binds none and is 40 equations; a tall rung is 829 → 1105
equations at (64, 8, 128), 673 → 789 at four tiles and 637 at two of 256
lanes as before, and 304 more than the short rung's at any width where the
loop stays (`tests/test_ragged_attention.py` holds the counts and the binds).
A warm rung, parent → this (the `warm_up` note of K-EXAONE's, `.batch`'s,
LFM2's and Falcon-H1's cells, both trees in one call and in both orders; my
chip runs, PR 61):
K-EXAONE, four warm runs a side, s a rung 512 / 256 / 128: 6.13 3.96 4.00 →
6.23 4.02 4.11 (the decode scan 5.14 → 4.18, `warm_s` 31.8 → 31.2); LFM2 6.47
3.43 3.48 → 6.58 3.61 3.60 (27.1 → 26.8); `.batch` 2.10 1.17 1.04 → 2.01 1.21
1.17 (12.6 → 10.4); Falcon-H1 2.63 2.13 2.12 → 2.63 2.10 2.17 (16.3 → 16.1):
-0.1 … +0.2 s a tall rung, and less warm-up in all four.
  Beyond eight tiles the loop stays rolled: 10 (Phi-4-mini-flash, (40, 20,
64)), 16 (OLMoE) and 30 (Olmo-Hybrid) are MHA shapes whose cells run no
launch that the 2.5-2.7 × would show in, and every one of them would pay its
start; rolled, an MHA model's long chunk is still 1.8 × faster a launch than
on tiles of 8 (4 k: 4.08 → 2.23 ms at (16, 16, 128), 7.31 → 4.09 at (30, 30,
128); PR 48, call 3). A long-prompt cell at such a shape is the judge of more.
  The cells' other steps at PR 48, parent → this, ms a launch (calls 1, 3):
`ragged64` and `decode` within 2 % at every shape (a 64-token rung traces
the parent's body), `ragged512` (56 decode rows, two 228-token spans over
no prefix: six whole stretches)
  (28, 4, 128) 0.1863 → 0.1692   (8, 2, 128) 0.1357 → 0.1098   (32, 8, 64) 0.2646 → 0.2528
  (16, 16, 128) 0.4864 → 0.4199  (30, 30, 128) 0.8542 → 0.7325 (16, 2, 256) 0.1753 → 0.1504

Which loops are in the program, which in Python, and why. A step program
is traced, lowered and keyed once a rung of the token ladder at every
start, compile cache warm or not, and that cost follows the size of the
kernel's traced body: it is most of `setup_s`, which the benchmark judges
in every cell (PR 33 was refused for it: 8 successors × 16 lane tiles =
128 copies of `Mxu.update` at OLMoE's shape, 8354 equations, a warm rung
7.7-11.5 s where the VPU body's 64 light copies took 4.5-6.9 s).
  - IN THE PROGRAM: a walk's blocks (`lax.fori_loop`, both kernels,
    always), and since PR 34 the ragged kernel's walk over the at most
    G_TILE sequences that overlap a tile (since PR 38 a `lax.while_loop`
    that ends at the last of them). What crosses a trip is scalars: the
    walk's sequence, its pages and the ring's position; the DMAs in
    flight are the ring's, whoever waits for them; the per-sequence
    scalars are SMEM reads at a dynamic index. Same launch within 2 % at
    every published shape when written (0.474 → 0.476, 0.178 → 0.180 ms;
    PR 34, same run), an eighth of the body: 1145 equations at (16, 16,
    128), 449 at (28, 4, 128), whatever G_TILE is
    (`tests/test_ragged_attention.py` holds that). Since PR 48 a rung of
    2 * TALL tokens or more holds TWO bodies — a program's tiles are a
    loop in the program too, and the tall walk beside it is one more copy
    of the inner product a lane tile up to `TALL_UNROLL` = 8 of them and
    ONE copy, its lane tiles a loop in the program, beyond (PR 61: 369 and
    837 equations on a rung of one body — 39 a lane tile and 213 of
    everything else —, 789 and 1141 on one of two).
  - IN PYTHON: the lane tiles (`for t in range(self.tiles)` in
    `Mxu.update` / `finish`) and a block's pages (`PageStream`, at most 4:
    straight-line copies under the block's one predicate).
    Mosaic ACCEPTS the lane-tile loop in the program (`bufs[..][slot, :,
    pl.ds(pl.multiple_of(t * W, 128), W)]`, every published shape, one
    chip and under `shard_map`), and it would leave ONE copy of the inner
    product (282 equations at any shape) — but a launch then costs 1.5-2.4
    times as much: ragged 64 rows 0.180 → 0.361 at (28, 4, 128), 0.138 →
    0.214 at (8, 2, 128), 0.476 → 1.118 at (16, 16, 128); decode 0.170 →
    0.331 (PR 34, same run; OLMoE end to end 4170-4259 → 3363-3959
    tokens/s over 20 s windows). A rolled tile loop serialises what the
    unrolled one lets the scheduler overlap (tile t+1's MXU pushes under
    tile t's softmax). So the body grows with the lane tiles alone — 30 at
    most among the published shapes — and with nothing else. (The tall
    trip's tile loop IS rolled beyond eight tiles, PRs 48 and 61: see above
    for what that buys a start and costs a launch.)
`hd % 128 == 0` or `hd == 64` changes none of this: the packing is the
wrapper's, the kernel sees `tiles` tiles of width W (`MxuSplit`: and a
second key tile a head, read in `_qk`).
  A SINK (PR 65; a window layer's learned float32 logit a query head, one
more column of the softmax that carries no value) is an optional operand:
packed as `m_i` is (`Mxu.pack_sink`: `[tiles, Mp, 128]`, lane-replicated,
rows in `pack_q`'s order), in VMEM, whole in every program, behind q; the
walks never see it — `finish` rebases the state on max(m, b), adds exp(b -
that) to l and nothing to acc. Absent (`split_sink`), nothing of it is
traced.
  What a rung's trace is charged for is not only equations (PR 38): on a
tracer every operator (`a + b`, `a < b`) and every `jnp.where` /
`minimum` / `clip` is a NESTED JIT, and inside a serving process — whose
step program's trace has pushed everything else out of jax's tracing
caches — each costs ~1-2 ms. The walks' scalar arithmetic written with
operators read +0.25 s a ragged rung on `.batch` (six rungs a start;
`JAX_LOG_COMPILES` and a profile of the first call, my chip run, PR 38)
though the kernel alone traced no slower anywhere; written with `lax`
primitives (`add`, `mul`, `cdiv`, `mod`, `lax.select` … below) the
ragged kernel bound 117 nested jits a trace where the parent's bound 191
and the decode kernel 97 for 196 (at (28, 4, 128)); all of them left were
the inner products' vector code — `jnp.where`, `maximum`, `exp`, `sum`, `*`,
`+`, `-`, `/` on `[M, 128]` values, ~19 a lane tile in `Mxu.update`'s fold
and 4 in `finish`, so 209 a trace of a tall rung at (64, 8, 128) and 353 at
(16, 16, 128). Since PR 61 `Mxu`'s vector code is `lax` calls too
(`lax.select` over `full_like`, `reduce_max` / `reduce_sum` with an explicit
`broadcast_in_dim`, `lax.max` / `exp` / `sub` / `mul` / `add` / `div`): a
trace of the ragged kernel binds 7-23 nested jits at ANY width, all of them
the wrapper's (`pad`, `searchsorted`, the packing), and every kernel that
`Mxu` builds is the parent's to the equation but for the wrappers
themselves — a `jnp.where` was a `jit` equation around a weak scalar's
`convert_element_type`, its `broadcast_in_dim` and the `select_n`; the last
two are what is traced now, 6 equations fewer a lane tile, every other
primitive where it was, with the avals it had (compared equation by equation
at ten head shapes, both rungs, bf16 / float32 / int8 pools, with and
without a window, and the decode kernel; PR 61). `Vpu` keeps its operators:
one decode row of an MHA model, 64 light copies, no tall body.

P into P·V (PR 59): against a bf16 pool P enters the contraction at the
pool's dtype, rounded once to nearest — `_dot(p.astype(v.dtype), v)`, what
the published modelling code of every served model does (softmax in float32,
cast to the query's dtype, `matmul(attn_weights, value)`), what both latent
kernels (`mla_attention.py`) have done since they were written, and what
Mosaic's default-precision float32 matmul does to the Vpu body's `p @ seg_t`
anyway (it rounds its operands to bf16: my chip run, PR 33, `(1 + 2**-10) @
1` comes back 1.0 — so the Vpu body rounds BOTH its `(k * q) @ seg` products
and its P; the Mxu body's q·k has the stored bf16 values as operands and
rounds nothing). `l` sums the float32 p, so an output moves by at most 2**-8
of sum(p |v|) / sum(p) before its own rounding to bf16
(`tests/test_ragged_attention.py:two_roundings`). Against a float32 (or
dequantised int8) block both contractions are float32 matmuls at Mosaic's
default precision, to the bit what they were.
  Until PR 59 P went in as THREE bf16 terms whose sum is P to float32's
last bit, stacked along M and contracted once: 1536 of the 2048 rows a lane
tile of a tall trip streamed through the MXU were P's second and third
terms, for an output that `finish` stores in bf16. "3 terms cost under 3 %
of a launch over 1" (PR 33) was measured on 64 decode rows over 200-380
tokens; the tall trip did not exist. Measured, parent → this, one v5e, both
trees in one call (`scripts/attn_kernel_bench.py`; my chip run, PR 59, call
2: every row twice a side, the two readings within 1 %). `--traffic
raggedlong`, µs a tall trip / a short trip at 4 k, 8 k, 16 k, then ms a
launch and ms a window layer's launch at 8 k:
  (64, 8, 128)  5.89 5.77 5.71 → 4.84 4.71 4.65 / 1.22 1.19 1.17 → 0.93 0.90 0.89
                3.493 → 2.795   window 0.248 → 0.213
  (16, 2, 256)  2.04 1.98 1.94 → 1.33 1.28 1.25 / 0.64 0.62 0.61 → 0.69 0.67 0.65
                1.370 → 1.099   window 0.103 → 0.086
  (28, 4, 128)  3.05 2.98 2.95 → 2.53 2.46 2.43 / 0.67 0.65 0.63 → 0.70 0.68 0.66
                1.831 → 1.628   window 0.148 → 0.135
— the tall trip −18 % at 512 row-heads a 128-lane tile (the MXU's latch
arithmetic, tiles × (rows + 128) cycles over four MXUs at 1.5 GHz, gives
3.1 → 1.7 µs there: what is left is not the MXU's), −35 % at (16, 2, 256),
−17 % at 448. 64 decode rows over 200-380 tokens, ms a launch (`decode`,
`ragged64`, `ragged512`):
  (28, 4, 128)   0.1253 → 0.1213   0.1290 → 0.1338   0.1727 → 0.1729
  (8, 2, 128)    0.0904 → 0.0874   0.0959 → 0.0918   0.1091 → 0.1016
  (32, 8, 64)    0.1348 → 0.1300   0.1378 → 0.1441   0.2528 → 0.2527
  (16, 16, 128)  vpu               0.3501 → 0.3233   0.4159 → 0.3845
  (30, 30, 128)  vpu               0.6175 → 0.5530   0.7348 → 0.6580
  (16, 2, 256)   0.1267 → 0.1196   0.1233 → 0.1324   0.1501 → 0.1521
  (64, 8, 128)   0.2000 → 0.1892   0.2256 → 0.1730   0.3223 → 0.2557
(the decode kernel at group 1 is the Vpu body, untouched: 0.3337 → 0.3351,
0.4956 → 0.4946; built with the Mxu body there 0.356 → 0.325, 0.635 → 0.570).
The SHORT trip of the ragged kernel at 56-64 row-heads and FOUR lane tiles
costs 4-7 % MORE with less work in it — (28, 4, 128), (32, 8, 64), (16, 2,
256), repeatably, and with two, eight, sixteen or thirty tiles it costs
4-24 % less: a schedule, not an operation; no bundle dump was read (PERF.md
§7).

Mosaic layout constraints (v5e compiler; `tests/test_chip_compile.py`
asks it at every published shape):
  - DMA slices are tile-aligned: K/V move as flattened `[page_size,
    Hk*hd]` rows, `Hk*hd` a multiple of 128; page i of a block lands in
    rows `i*page_size…` of the block's VMEM buffer.
  - A static slice of whole 128-lane tiles (`[:, t*W:(t+1)*W]`, W % 128
    == 0) is free; a slice inside a tile, or a reshape that splits or
    merges lanes, is an "unsupported shape cast". Hence the wrapper-side
    packing for head_dim 64 and the segment matrices of the Vpu body.
  - All head bookkeeping that needs a transpose happens OUTSIDE the
    kernel, in `pack_q` / `unpack_o` (plain XLA).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANE = 128
# Tokens of one tile of the ragged kernel: the rows that share a block's
# trip wherever a stretch of the stream is NOT one span's (decode rows, a
# span's head and tail, padding). 8 keeps the q/o blocks one sublane tile
# tall and bounds the worst case (8 distinct decode sequences) to the same
# page-loop total work as 8 decode-kernel programs.
G_TILE = 8
# Tokens of one PROGRAM of the ragged kernel on a rung that can hold one
# (`ragged_attention.py`): a stretch of TALL consecutive stream tokens that
# lies inside one span shares each block's trip, TALL // G_TILE tiles
# merged along M. Swept 32 / 64 / 128 on `scripts/attn_kernel_bench.py`
# (module docstring, PR 48).
TALL = 64
# Lane tiles up to which the tall trip's loop over them is unrolled in
# Python, as the tile's trip is at any width; beyond, it is a loop in the
# program. 2 until PR 61, when the roll was half of a tall trip at eight
# tiles and 40 % of one at four (module docstring: what either costs a
# launch and a rung); the MHA shapes — 10, 16, 30 tiles — stay rolled.
TALL_UNROLL = 8
_NN = (((1,), (0,)), ((), ()))  # [M, K] · [K, N]
_NT = (((1,), (1,)), ((), ()))  # [M, K] · [N, K]ᵀ

def _dot(a, b, dims=_NN):
    return jax.lax.dot_general(a, b, dimension_numbers=dims,
                               preferred_element_type=jnp.float32)


# The kernels' scalar arithmetic — which walk, which block, which slot —
# is written with `lax` primitives, not with operators or `jnp`: on a
# tracer every operator and every `jnp.where` / `minimum` / `clip` is a
# nested jit, ~1-2 ms of tracing each inside a serving process, and a
# step program is traced once a rung of the token ladder at every start
# (`setup_s`; my chip run, PR 38: the same scalar code with operators
# cost a ragged rung +0.25 s). `add` / `mul` / `both` / `either` keep
# static values static, so a static index stays one.
def _static(*xs):
    return all(isinstance(x, (int, bool)) for x in xs)


def add(a, b):
    return a + b if _static(a, b) else lax.add(a, b)


def mul(a, b):
    return a * b if _static(a, b) else lax.mul(a, b)


def both(a, b):
    return (a and b) if _static(a, b) else lax.bitwise_and(a, b)


def either(a, b):
    return (a or b) if _static(a, b) else lax.bitwise_or(a, b)


def cdiv(x, d: int):
    """ceil(x / d), x a traced int32 >= 0 and d static: a shift where d is
    a power of two (the scalar core has no divider: `kv_contract.py`'s
    docstring has what a `//` costs)."""
    if d & (d - 1) == 0:
        return lax.shift_right_arithmetic(lax.add(x, d - 1),
                                          d.bit_length() - 1)
    return lax.div(lax.add(x, d - 1), d)


def mod(x, d: int):
    """x % d, x a traced int32 >= 0 and d static: a mask where d is a
    power of two."""
    return lax.bitwise_and(x, d - 1) if d & (d - 1) == 0 else lax.rem(x, d)


def choose_inner(rows: int, group: int) -> str:
    """"mxu" or "vpu" for a kernel whose programs hold `rows` query rows
    (G_TILE in the ragged kernel, 1 in the decode kernel) of `group` query
    heads a kv head: the module docstring says why."""
    return "vpu" if rows * group == 1 else "mxu"


def inner_report(group: int) -> dict:
    """Which inner product each kernel of a model with this query group is
    built with — what a runtime reports beside `attn_impl`."""
    return {"ragged": choose_inner(G_TILE, group),
            "decode": choose_inner(1, group)}


def make_inner(name, *, rows, group, num_kv_heads, head_dim, page_size,
               subs=1, window=0, v_dim=0, sink=False):
    """The inner product of a launch. `head_dim`: lanes of a q and k head;
    `v_dim`: of a v head where it is another (0: the same — then, and with
    no `sink`, the inner product and its launch are what they were before
    either existed: no operand, tile or scratch row more)."""
    if v_dim in (0, head_dim) and not sink:
        cls = {"mxu": Mxu, "vpu": Vpu}[name or choose_inner(rows, group)]
        return cls(rows, group, num_kv_heads, head_dim, page_size, subs,
                   window)
    cls = Mxu if v_dim in (0, head_dim) else MxuSplit
    if (name or "mxu") != "mxu":
        raise ValueError("a sink or a value head of another width than the "
                         "key's is served by the Mxu inner product")
    inner = cls(rows, group, num_kv_heads, head_dim, page_size, subs, window,
                v_dim or head_dim, sink)
    inner.check()
    return inner


def programs_height(stream_len: int) -> int:
    """Tokens a program of the ragged kernel holds on a rung of
    `stream_len` tokens: TALL where a whole stretch fits beside a decode
    row (two programs at the least), else one tile — and then the kernel
    traces no tall body (`setup_s`)."""
    return TALL if stream_len >= 2 * TALL else G_TILE


def tall_tokens(spans, stream_len: int) -> int:
    """Stream tokens of a ragged step that the kernel serves TALL at a
    time: those of its stretches [k*TALL, (k+1)*TALL) that lie inside ONE
    span, on a rung (`stream_len` tokens, padding included) that holds the
    tall body at all. `spans`: each row's tokens, in stream order. The
    kernel's own test (`programs_height`, `ragged_attention.py:whole`), on
    the host."""
    if programs_height(stream_len) != TALL:
        return 0
    tall = start = 0
    for n in spans:
        first = -(-start // TALL) * TALL  # the span's first whole stretch
        tall += max(0, start + n - first) // TALL * TALL
        start += n
    return tall


def ring_grid_spec(inner, ring, grid, num_scalar_prefetch, pools):
    """(nbuf, grid spec) of either kernel: one program a grid step, q and
    o blocked in VMEM by program in the inner product's packed layout, the
    pools — K, V and an int8 pool's two scale planes — left in HBM, and as
    scratch a ring of `nbuf` block buffers a pool (`ring` pages in flight,
    at least two blocks), the softmax state, the DMA semaphores and the
    ring's position (`RingWalk`)."""
    nbuf = max(2, ring // inner.block_pages)
    blk = inner.block_pages * inner.page_size

    def by_program(block):
        return pl.BlockSpec(
            block, lambda i, *_: (i,) + (0,) * (len(block) - 1),
            memory_space=pltpu.VMEM)

    q_spec = by_program(inner.q_block)
    sink = []
    if inner.sink:  # the packed sink, whole, in every program
        sink = [pl.BlockSpec(inner.sink_block, lambda i, *_: (0, 0, 0),
                             memory_space=pltpu.VMEM)]
    return nbuf, pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=num_scalar_prefetch,
        grid=grid,
        in_specs=[q_spec] + sink
        + [pl.BlockSpec(memory_space=pl.ANY)] * len(pools),
        out_specs=q_spec if inner.o_block == inner.q_block
        else by_program(inner.o_block),
        scratch_shapes=[pltpu.VMEM((nbuf, blk, p.shape[-1]), p.dtype)
                        for p in pools] + inner.scratch()
        + [pltpu.SemaphoreType.DMA((nbuf, len(pools))),
           pltpu.SMEM((1,), jnp.int32)],
    )


def whole_blocks(page_table, inner):
    """The page table as the kernels read it: its width a multiple of the
    inner product's block, padded — as every row already is — with the
    trash page (page 0), so that a block's every page has an entry. The
    engine's tables (256 pages a row) come back as they are."""
    short = -page_table.shape[1] % inner.block_pages
    page_table = page_table.astype(jnp.int32)
    return jnp.pad(page_table, ((0, 0), (0, short))) if short else page_table


def split_window(refs, window: int):
    """(base_ref, the other refs) of a kernel's refs after ITS OWN scalar
    prefetch: a window layer's launch carries one more, `[B]` int32 in SMEM —
    the position the first listed page of each row's table stands for (a
    multiple of the page size). Its table lists the pages from that position
    on, so a walk streams only blocks that can hold keys inside the window
    (block b of row r starts at position base[r] + b * block), not the
    context before them; None without a window, and then nothing of this is
    traced."""
    return (refs[0], refs[1:]) if window else (None, refs)


def split_sink(refs, sink: bool):
    """(sink_ref, the other refs) of a kernel's refs after the scalar
    prefetch and `split_window`: a launch with a sink carries it packed
    (`Mxu.pack_sink`) behind q, in VMEM; None without one, and then nothing
    of it is traced."""
    return (refs[1], refs[:1] + refs[2:]) if sink else (None, refs)


def split_refs(refs):
    """A kernel's refs after the scalar prefetch, as `ring_grid_spec` lays
    them out: (q, the n pools in HBM, o, their n ring buffers, (acc, m_i,
    l_i), the semaphores, the ring's position)."""
    n = (len(refs) - 7) // 2
    return (refs[0], refs[1:1 + n], refs[1 + n], refs[2 + n:2 + 2 * n],
            refs[2 + 2 * n:-2], refs[-2], refs[-1])


class PageStream:
    """One walk's K/V pages, HBM→VMEM, a block of `block_pages` pages at
    a time into ring slot `slot` of `[nbuf, block_pages*page_size, lanes]`
    buffers (page i of the block at rows i*page_size…). The unit is the
    block (module docstring): `start` has one predicate — the block
    exists — and `wait` none, and a slot's semaphores (one a pool)
    balance because every block that was started is waited for exactly
    once, by the walk it belongs to."""

    def __init__(self, hbm, bufs, sems, layer, page_table_ref, page_size,
                 block_pages):
        self.hbm, self.bufs, self.sems = hbm, bufs, sems
        self.layer, self.page_table_ref = layer, page_table_ref
        self.page_size, self.block_pages = page_size, block_pages

    def start(self, slot, row, block, npages, cond=None):
        """Start block `block` of sequence `row` into `slot` if the block
        holds one of the walk's `npages` pages (and `cond`): every page
        of it, those past the last included — the table holds the trash
        page there."""
        ps, bp = self.page_size, self.block_pages
        first = mul(block, bp)
        exists = lax.lt(first, npages)
        if cond is not None:
            exists = both(cond, exists)

        @pl.when(exists)
        def _():
            for i in range(bp):
                at = lax.mul(self.page_table_ref[row, add(first, i)], ps)
                for n, (src, dst) in enumerate(zip(self.hbm, self.bufs)):
                    pltpu.make_async_copy(
                        src.at[self.layer, pl.ds(at, ps)],
                        dst.at[slot, pl.ds(i * ps, ps)],
                        self.sems.at[slot, n]).start()

    def wait(self, slot):
        """Wait for the block in flight into `slot`: once a pool, for a
        block's bytes (a DMA semaphore counts bytes, so the block's
        page-sized starts balance one block-sized wait; the source of
        the descriptor is only a shape)."""
        blk = self.block_pages * self.page_size
        for n, (src, dst) in enumerate(zip(self.hbm, self.bufs)):
            pltpu.make_async_copy(
                src.at[self.layer, pl.ds(0, blk)], dst.at[slot],
                self.sems.at[slot, n]).wait()


def _segments(num_kv_heads, head_dim):
    """SEG[d, h] = 1 iff lane d belongs to kv head h, and its transpose:
    constant f32 matrices that let the MXU do per-head lane reductions and
    expansions without relayouts."""
    lanes = num_kv_heads * head_dim

    def one(shape, lane_dim):
        return (jax.lax.broadcasted_iota(jnp.int32, shape, lane_dim)
                // head_dim
                == jax.lax.broadcasted_iota(jnp.int32, shape, 1 - lane_dim)
                ).astype(jnp.float32)

    return one((lanes, num_kv_heads), 0), one((num_kv_heads, lanes), 1)


def _load_block(bufs, slot, seg_t):
    """(k, v) of ring slot `slot` as float32 values; an int8 block's scale
    rows `[blk, Hk]` expand to lane segments through `seg_t`."""
    k = bufs[0][slot].astype(jnp.float32)
    v = bufs[1][slot].astype(jnp.float32)
    if len(bufs) == 4:
        k = k * _dot(bufs[2][slot], seg_t)
        v = v * _dot(bufs[3][slot], seg_t)
    return k, v


@dataclasses.dataclass(frozen=True)
class _Inner:
    rows: int  # query rows of one tile: G_TILE, or 1 (decode kernel)
    group: int
    num_kv_heads: int
    head_dim: int
    page_size: int
    subs: int = 1  # tiles a program holds (ragged kernel: TALL // G_TILE)
    # A WINDOW layer's launch (0: none): a query at position p sees the
    # positions p - window < j <= p only. The walk then starts at the page
    # that holds the earliest of them (`split_window`); the mask here.
    window: int = 0
    # Lanes of a VALUE head where they are not `head_dim` (`MxuSplit`), and
    # whether the launch carries a sink: a float32 logit a query head that
    # joins the softmax's denominator in `finish` and carries no value.
    v_dim: int = 0
    sink: bool = False

    @property
    def lanes(self):
        return self.num_kv_heads * self.head_dim

    @property
    def o_block(self):  # the output's block: q's, where V is as wide as K
        return self.q_block

    def o_shape(self, q_packed_shape):
        return tuple(q_packed_shape[:-1]) + (self.o_block[-1],)

    def init(self, acc, m_i, l_i):
        acc[...] = jnp.zeros_like(acc)
        m_i[...] = jnp.full_like(m_i, NEG_INF)
        l_i[...] = jnp.zeros_like(l_i)


class Vpu(_Inner):
    """One VPU pass a (group, page) of ONE decode row; see the module
    docstring."""

    name = "vpu"
    block_pages = 1

    def __post_init__(self):
        assert self.rows == self.subs == 1, \
            "the Vpu inner product serves a decode row"

    def pack_q(self, q):
        """[N, H, hd] → [N, group, lanes], query-group-major: row g holds
        every kv head's group-g query in its lane segment."""
        n = q.shape[0]
        return q.reshape(n, self.num_kv_heads, self.group,
                         self.head_dim).transpose(0, 2, 1, 3).reshape(
                             n, self.group, self.lanes)

    def unpack_o(self, o):
        n = o.shape[0]
        return o.reshape(n, self.group, self.num_kv_heads,
                         self.head_dim).transpose(0, 2, 1, 3).reshape(
                             n, self.group * self.num_kv_heads,
                             self.head_dim)

    @property
    def q_block(self):
        return (1, self.group, self.lanes)

    def scratch(self):
        return [pltpu.VMEM((self.group, self.lanes), jnp.float32),
                pltpu.VMEM((self.group, self.num_kv_heads), jnp.float32),
                pltpu.VMEM((self.group, self.num_kv_heads), jnp.float32)]

    def update(self, q_ref, bufs, slot, span, pos0, state, done_reading):
        kv = span[-1]
        acc, m_i, l_i = state
        seg, seg_t = _segments(self.num_kv_heads, self.head_dim)
        k, v = _load_block(bufs, slot, seg_t)  # [ps, lanes] f32
        done_reading()  # values are loaded: the slot may refill now
        scale = 1.0 / (self.head_dim ** 0.5)
        # Valid-position mask for this page (the last may be partial).
        pos = pos0 + jax.lax.broadcasted_iota(
            jnp.int32, (self.page_size, self.num_kv_heads), 0)
        valid = pos < kv
        if self.window:
            valid = valid & (pos >= kv - self.window)
        for g in range(self.group):  # static unroll; group is small (1-8)
            qg = q_ref[0, g:g + 1, :].astype(jnp.float32)  # [1, lanes]
            # scores[t, h] = sum_d q[h-seg d] * k[t, d]: masked-lane
            # elementwise product + segment-sum on the MXU.
            sc = jnp.where(valid, _dot(k * qg, seg) * scale, NEG_INF)
            m_prev = m_i[g:g + 1, :]  # [1, Hk]
            m_new = jnp.maximum(m_prev, jnp.max(sc, axis=0, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)  # [1, Hk]
            p_ij = jnp.exp(sc - m_new)  # [ps, Hk]
            l_i[g:g + 1, :] = l_i[g:g + 1, :] * alpha + jnp.sum(
                p_ij, axis=0, keepdims=True)
            # Per-head weights expanded back to lane segments, then a
            # sublane reduction contracts over page positions.
            contrib = jnp.sum(_dot(p_ij, seg_t) * v, axis=0, keepdims=True)
            acc[g:g + 1, :] = acc[g:g + 1, :] * _dot(alpha, seg_t) + contrib
            m_i[g:g + 1, :] = m_new

    def finish(self, o_ref, state):
        acc, _, l_i = state
        _, seg_t = _segments(self.num_kv_heads, self.head_dim)
        denom = _dot(jnp.maximum(l_i[...], 1e-20), seg_t)  # [group, lanes]
        o_ref[0] = (acc[...] / denom).astype(o_ref.dtype)


def _lane_fit(x, n):
    """A lane-replicated `[..., M, 128]` statistic at width n."""
    if n == LANE:
        return x
    if n % LANE == 0:
        return jnp.tile(x, (1,) * (x.ndim - 1) + (n // LANE,))
    return jnp.broadcast_to(x[..., :1], x.shape[:-1] + (n,))


def _where(pred, x, y):
    """`jnp.where` on an array and a constant, as the two primitives it
    is (the module docstring: "what a rung's trace is charged for")."""
    if isinstance(x, float):
        return lax.select(pred, lax.full_like(y, x), y)
    return lax.select(pred, x, lax.full_like(x, y))


def _rows(reduce, x):
    """`[M, n]` → `[M, 1]`: a row's maximum or sum, kept as a column."""
    return lax.broadcast_in_dim(reduce(x, (1,)), (x.shape[0], 1), (0,))


class Mxu(_Inner):
    """One MXU contraction a (block, lane tile) for every row-head that
    shares it; see the module docstring."""

    name = "mxu"

    @property
    def heads_per_tile(self):
        if self.head_dim >= LANE:
            return 1
        return max(d for d in range(1, self.num_kv_heads + 1)
                   if self.num_kv_heads % d == 0
                   and d * self.head_dim <= LANE)

    @property
    def width(self):  # lanes of one contraction
        return self.heads_per_tile * self.head_dim

    @property
    def tiles(self):
        return self.lanes // self.width

    @property
    def m(self):  # row-heads of one kv head
        return self.rows * self.group

    @property
    def mp(self):  # rows of one contraction: bf16 packs 16 to a sublane tile
        return -(-self.heads_per_tile * self.m // 16) * 16

    @property
    def block_pages(self):
        # Enough pages to fill the scores' 128 lanes, and no more than 4
        # DMA pairs a block: their issue and wait code is unrolled.
        return max(1, min(4, LANE // self.page_size))

    def pack_q(self, q):
        """[N*rows, H, hd] → [N, tiles, Mp, W]: row i*M + g*rows + r of
        tile t is query head (t*hpt + i)*group + g of row r, in head i's
        lanes of the tile and zero in the others'."""
        hpt, hd = self.heads_per_tile, self.head_dim
        n = q.shape[0] // self.rows
        x = q.reshape(n, self.rows, self.tiles, hpt, self.group, hd)
        x = x.transpose(0, 2, 3, 4, 1, 5).reshape(
            n, self.tiles, hpt, self.m, hd)
        if hpt > 1:
            eye = jnp.eye(hpt, dtype=q.dtype)
            x = x[:, :, :, :, None, :] * eye[:, None, :, None]
        x = x.reshape(n, self.tiles, hpt * self.m, self.width)
        return jnp.pad(x, ((0, 0), (0, 0), (0, self.mp - hpt * self.m),
                           (0, 0)))

    def unpack_o(self, o):
        hpt, hd = self.heads_per_tile, self.head_dim
        n = o.shape[0]
        x = o[:, :, :hpt * self.m].reshape(
            n, self.tiles, hpt, self.m, hpt, hd)
        x = jnp.stack([x[:, :, i, :, i] for i in range(hpt)], axis=2)
        x = x.reshape(n, self.tiles, hpt, self.group, self.rows, hd)
        return x.transpose(0, 4, 1, 2, 3, 5).reshape(
            n * self.rows, self.num_kv_heads * self.group, hd)

    @property
    def q_block(self):
        return (self.subs, self.tiles, self.mp, self.width)

    def scratch(self):
        # A lane tile's tiles are contiguous: merged along M they are the
        # tall trip's `[subs * Mp, ·]` state at no cost.
        held = (self.tiles, self.subs, self.mp)
        return [pltpu.VMEM(held + (self.width,), jnp.float32),
                pltpu.VMEM(held + (LANE,), jnp.float32),
                pltpu.VMEM(held + (LANE,), jnp.float32)]

    def check(self):
        """(`make_inner`: what this inner product cannot serve raises.)"""

    def _qk(self, q, k, bufs, slot, t):
        """q [M, W] · k [blk, W]ᵀ of lane tile t → [M, blk] f32."""
        return _dot(q, k, _NT)

    @property
    def sink_block(self):
        return (self.tiles, self.mp, LANE)

    def pack_sink(self, sink):
        """[H] float32 → `sink_block`: a row-head's own logit, lane-
        replicated as `m_i` is, rows in `pack_q`'s order (padding rows 0)."""
        hpt = self.heads_per_tile
        x = sink.astype(jnp.float32).reshape(self.tiles, hpt, self.group, 1)
        x = jnp.broadcast_to(x, x.shape[:-1] + (self.rows,)).reshape(
            self.tiles, hpt * self.m)
        x = jnp.pad(x, ((0, 0), (0, self.mp - hpt * self.m)))
        return jnp.broadcast_to(x[..., None], self.sink_block)

    def _pv(self, p, v):
        """p [M, blk] f32 · v [blk, W] → [M, W] f32: P enters at the
        block's dtype (module docstring)."""
        if v.dtype == jnp.bfloat16:
            return _dot(p.astype(v.dtype), v)
        return _dot(p, v.astype(jnp.float32))

    def update(self, q_ref, bufs, slot, span, pos0, state, done_reading,
               sub=0):
        """Fold the block in ring slot `slot` (its first token at position
        `pos0`) into the state of tile `sub` of the program — or, `sub`
        None, of ALL its tiles merged along M: the tall trip, for a
        program whose every token lies in `span`."""
        tile_start, qs, ql, kv = span
        acc, m_i, l_i = state
        W, mp = self.width, self.mp
        blk = self.block_pages * self.page_size
        scale = 1.0 / (self.head_dim ** 0.5)
        tall = sub is None
        held = (self.subs, mp) if tall else (mp,)
        m = self.subs * mp if tall else mp

        def get(ref, t):
            if not tall:
                return ref[t, sub]
            return ref[t].reshape(m, ref.shape[-1])

        def put(ref, t, x):
            if tall:
                ref[t] = x.reshape(held + x.shape[-1:])
            else:
                ref[t, sub] = x

        def iota(dim):  # of the rows' (tile, row in it, position) index
            return jax.lax.broadcasted_iota(
                jnp.int32, held + (blk,), dim).reshape(m, blk)

        pos = lax.add(pos0, iota(len(held)))
        if self.rows == 1:
            valid = lax.lt(pos, kv)
            if self.window:
                valid = lax.bitwise_and(
                    valid, lax.ge(pos, lax.sub(kv, self.window)))
        else:
            # Row-head i*M + g*rows + r is token tile_start + r: inside
            # this sequence's span it sees positions up to its own (which
            # lies below kv), outside it nothing.
            tok = lax.add(tile_start, lax.bitwise_and(
                iota(len(held) - 1), self.rows - 1))
            if tall:  # tile j's rows are `rows` tokens further on each
                tok = lax.add(tok, lax.mul(iota(0), self.rows))

            def own():  # the position of a row's token
                return lax.add(lax.sub(kv, ql), lax.sub(tok, qs))

            if tall:
                valid = lax.le(pos, own())
            else:
                valid = lax.bitwise_and(
                    lax.ge(tok, qs), lax.lt(tok, lax.add(qs, ql)))
                valid = lax.bitwise_and(valid, lax.le(pos, own()))
            if self.window:
                valid = lax.bitwise_and(
                    valid, lax.gt(pos, lax.sub(own(), self.window)))
        if len(bufs) == 4:  # int8: dequantise the block, then the same
            _, seg_t = _segments(self.num_kv_heads, self.head_dim)
            k_all, v_all = _load_block(bufs, slot, seg_t)
        def fold(t):  # lane tile t: a static index, or a loop's
            if isinstance(t, int):
                lanes = slice(t * W, (t + 1) * W)
            else:
                lanes = pl.ds(pl.multiple_of(lax.mul(t, W), LANE), W)
            if len(bufs) == 4:
                k, v = (x[:, lanes] if isinstance(t, int)
                        else lax.dynamic_slice_in_dim(x, lax.mul(t, W), W, 1)
                        for x in (k_all, v_all))
            else:
                k, v = bufs[0][slot, :, lanes], bufs[1][slot, :, lanes]
            q = q_ref[:, t].reshape(m, q_ref.shape[-1]) if tall \
                else q_ref[sub, t]
            if q.dtype != k.dtype:
                q, k = q.astype(jnp.float32), k.astype(jnp.float32)
            sc = _where(valid, lax.mul(self._qk(q, k, bufs, slot, t), scale),
                        NEG_INF)
            m_prev = get(m_i, t)  # [M, 128], lane-replicated
            m_new = lax.max(m_prev, _rows(lax.reduce_max, sc))
            # Rows outside the span, and blocks wholly beyond a row's
            # causal frontier, leave every score at NEG_INF: guard the
            # exps so the no-op update stays a no-op.
            alpha = _where(lax.le(m_prev, NEG_INF / 2), 0.0,
                           lax.exp(lax.sub(m_prev, m_new)))
            p = _where(valid,
                       lax.exp(lax.sub(sc, _lane_fit(m_new, blk))), 0.0)
            put(l_i, t, lax.add(lax.mul(get(l_i, t), alpha),
                                _rows(lax.reduce_sum, p)))
            put(acc, t, lax.add(lax.mul(get(acc, t), _lane_fit(alpha, W)),
                                self._pv(p, v)))
            put(m_i, t, m_new)

        if tall and self.tiles > TALL_UNROLL:
            jax.lax.fori_loop(0, self.tiles, lambda t, _: fold(t), None)
        else:
            for t in range(self.tiles):
                fold(t)
        done_reading()

    def finish(self, o_ref, state, sink_ref=None):
        acc, m_i, l_i = state
        for t in range(self.tiles):  # every tile of the program at once
            if sink_ref is None:
                denom = _lane_fit(lax.max(l_i[t], 1e-20), self.width)
                o_ref[:, t] = lax.div(acc[t], denom).astype(o_ref.dtype)
                continue
            # One more column, the sink's logit b: the state rebased on
            # max(m, b), exp(b - that) joins the sum, nothing joins acc.
            b = lax.broadcast_in_dim(  # [Mp, 128] over the tiles held
                sink_ref[t], m_i[t].shape, (1, 2))
            top = lax.max(m_i[t], b)
            keep = _where(lax.le(m_i[t], NEG_INF / 2), 0.0,
                          lax.exp(lax.sub(m_i[t], top)))
            denom = _lane_fit(lax.max(lax.add(
                lax.mul(l_i[t], keep), lax.exp(lax.sub(b, top))), 1e-20),
                self.width)
            o_ref[:, t] = lax.div(
                lax.mul(acc[t], _lane_fit(keep, self.width)),
                denom).astype(o_ref.dtype)


class MxuSplit(Mxu):
    """`Mxu` for a key head of one whole lane tile and a rest (192 = 128 +
    64) beside a value head of one tile (128): MiMo-V2-Flash's. The pools'
    rows are stored split (`ops/attention.py:lay_heads`): K `[Hk x 128 | Hk x
    64]`, V `[Hk x 128]`. A lane tile of the state is a kv head, as where
    `head_dim` is 128 — K's tile t and V's tile t are head t's — and the
    head's rest lies in a tile that LANE // rest heads share, behind every
    head's first part: the packed-heads case's trick once more, q's rest
    zero-padded in the other heads' lanes of that tile (`pack_q`), so the
    scores are TWO contractions a (block, head), `[M, 128] · [blk, 128]ᵀ`
    twice, summed in float32 (2 tiles of MXU work for 1.5 of lanes), and p ·
    v one. q arrives `[.., M, 256]`, the output leaves `[.., M, 128]`."""

    def check(self):
        whole = self.head_dim - self.rest
        if not (whole == self.v_dim == LANE and self.rest
                and LANE % self.rest == 0
                and self.num_kv_heads * self.rest % LANE == 0
                and self.num_kv_heads <= TALL_UNROLL):
            raise ValueError(
                f"kv heads of {self.head_dim} key and {self.v_dim} value "
                f"lanes x {self.num_kv_heads}: the split layout serves a key "
                "head of one lane tile and a rest that divides one, whole "
                f"tiles of rests, a value head of one tile and at most "
                f"{TALL_UNROLL} kv heads")

    @property
    def rest(self):
        return self.head_dim % LANE

    heads_per_tile = 1
    width = LANE

    @property
    def tiles(self):
        return self.num_kv_heads

    def pack_q(self, q):
        """[N*rows, H, 192] → [N, Hk, Mp, 256]: row g*rows + r of tile t is
        query head t*group + g of row r — its first 128 lanes, then a tile
        with its rest where head t's rest lies in the shared K tile."""
        rest, share = self.rest, LANE // self.rest
        n = q.shape[0] // self.rows
        x = q.reshape(n, self.rows, self.tiles, self.group, self.head_dim)
        x = x.transpose(0, 2, 3, 1, 4).reshape(n, self.tiles, self.m,
                                               self.head_dim)
        at = jnp.eye(share, dtype=q.dtype)[
            jnp.arange(self.tiles) % share]  # [Hk, share]
        tail = (x[..., None, LANE:] * at[:, None, :, None]).reshape(
            n, self.tiles, self.m, LANE)
        x = jnp.concatenate([x[..., :LANE], tail], axis=-1)
        return jnp.pad(x, ((0, 0), (0, 0), (0, self.mp - self.m), (0, 0)))

    def unpack_o(self, o):
        n = o.shape[0]
        x = o[:, :, :self.m].reshape(n, self.tiles, self.group, self.rows,
                                     self.v_dim)
        return x.transpose(0, 3, 1, 2, 4).reshape(
            n * self.rows, self.tiles * self.group, self.v_dim)

    @property
    def q_block(self):
        return (self.subs, self.tiles, self.mp, 2 * LANE)

    @property
    def o_block(self):
        return (self.subs, self.tiles, self.mp, LANE)

    def _qk(self, q, k, bufs, slot, t):
        assert isinstance(t, int) and len(bufs) == 2, \
            "the split layout: unrolled tiles over a bf16 / float32 pool"
        at = (self.num_kv_heads + t // (LANE // self.rest)) * LANE
        k_rest = bufs[0][slot, :, at:at + LANE].astype(k.dtype)
        return lax.add(_dot(q[:, :LANE], k, _NT),
                       _dot(q[:, LANE:], k_rest, _NT))
