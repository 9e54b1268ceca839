"""Seconds a launch of the two paged-attention kernels, by inner product
and head shape, on the chip: `chiprun -- python scripts/attn_kernel_bench.py`.

For each published head shape (H, Hk, hd) — the sixth, (16, 2, 256), is
Qwen3-Next's gated attention: two lane tiles a kv head; the seventh and
eighth, (64, 8, 128) and (20, 4, 128), are K-EXAONE's and Falcon-H1's; the
ninth and tenth, (64, 4, 192, 128) and (64, 8, 192, 128), are MiMo-V2-Flash's
full and window layers: a FOURTH number is a value head's lanes where they
are not the key head's (the Mxu inner product alone; the window row of such a
shape carries a sink) — and each inner product a kernel can be built with (`ops/pallas/kv_contract.py`:
the decode kernel takes either, the ragged kernel has one) it builds a bf16
pool at the CLI's defaults (1024 pages of 32 tokens, 64 slots) with 64
sequences of 200-380 tokens of context, checks the kernel against the jnp
reference, and times
LAUNCHES calls chained inside one jit (each call's q is the last one's
output, so they cannot overlap). Traffic: "decode" = the decode kernel,
64 rows; "ragged64" = the ragged kernel on the same 64 decode rows (a
ragged step with nothing to prefill); "ragged512" = 56 decode rows and
two 228-token prefill spans. `--contexts N…` replaces the 200-380 mix by
one row a value with every sequence at exactly N tokens: the slope over
N is what a block costs once a walk is under way, the intercept what a
program (a row, a tile) costs before its first block. Each row also
says what its walks need (`pages_live`), what whole blocks move
(`pages_read`: the over-read of each walk's last block) and µs a
(sequence, block). It also asks what Mosaic's default-precision float32
matmul keeps of its operand (`f32_matmul_keeps`): the Vpu body's
`p @ seg_t` is one. One JSON line a measurement; exits 1 without a TPU.

`--traffic raggedlong` (PR 48) is a long prompt's chunk on the ragged
kernel: 5 one-token rows, then one 507-token span that ENDS at the context
(a later chunk of its prompt), every sequence at one context (`--contexts`,
default 4 k / 8 k / 16 k) in a pool sized for them, checked against the jnp
reference on a sample of the stream (`LONG_CHECKED`) — the step of
`qwen3-next-80b-a3b-ep4-d12.longctx` at (16, 2, 256), `--shapes 5`. Two
rows a (context, variant): `raggedlong_head` is the stream's first 64
tokens by themselves (the rows and the span's first 59: a rung of one tile
a program, every trip a short one — `us_a_short_trip`), `raggedlong` the
step: `trips_short` / `trips_tall` the (tile, block) trips of its walks of
8 tokens and of its walks of a whole stretch
(`kv_contract.programs_height`), `us_a_tall_trip` what is left of its
launch after its short trips at the head row's price, a trip. `--set
kv_contract.TALL=32` sweeps the stretch, `--set
ragged_attention.G_TILE=64,kv_contract.G_TILE=64` makes EVERY tile tall
(what decode rows would pay), `--set kv_contract.TALL=8` is the kernel of
one tile a program at every rung. Since PR 50 a third row a context,
`raggedlong_window`: the same step as a WINDOW layer launches it (a window
of 128 positions over per-slot rings; `rows_walked` of the context's rows) —
with `--shapes 6`, (64, 8, 128), the two launches of
`k-exaone-236b-a23b-ep8-d5.longctx`: the full layer's and a window layer's.

`--traffic latent` times the latent-attention kernel instead
(`ops/pallas/mla_attention.py:mla_sparse_paged_attention_pallas`) on the
`deepseek-v3.2-ep16-d5.longctx` cell's step: 128 heads over a 640-lane
latent pool, pages of 32, 5 one-token rows then one 507-token prefill
span, every sequence at one context (`--contexts`, default 4 k / 8 k /
12 k / 16 k), 2048 selected a token from seeded scores, checked against
`ops/mla.sparse_attention` on a sample of the stream. Three rows a (context,
`--set mla_attention.NAME=VALUE`): the step's one-token rows alone
(`latent_rows`), the step through the absorbed tiles (`latent`) and the
step as the engine launches it since PR 49, its span in the expanded form
(`latent_wide`: the same launch with the expanded form's q and `[W_uk |
W_uv]`, checked through W_uv) — ms a launch and µs a (tile, block) trip
(`latent`'s docstring has the two normalisations; a `--set` of a `WIDE…`
constant alone skips the absorbed row). Since PR 64 the same three rows
follow for the DENSE kernel (`mla_dense_paged_attention_pallas`: no
selection, every cached position attended; `latent_dense_rows`,
`latent_dense`, `latent_dense_wide`) at Kimi-Linear's 32 heads and at
openPangu's 128, the latter also at 1 k and 2 k of context — its cell's
prompts (a row's `shape` has the heads) — and a pair on the same rung with
no wide span (`latent_dense_narrow`, `latent_dense_narrow_wide`: the launch
without and with the expanded programs, which then serve nothing).

A variant of a kernel's body is measured here before a whole cell: a
module constant of the four kernel modules that this script sets
(`--set kv_contract.TALL_UNROLL=2` — the tall trip's lane tiles rolled
beyond two, as they were served until PR 61 —, `--set
paged_attention.RING=16,ragged_attention.RING=16`), `jax.clear_caches()`,
one more row a (shape, traffic); a variant that needs code gets a constant
that lives for that run (PR 34 timed the successor walk and the lane-tile
loop each unrolled in Python and as a loop in the program so, PR 38 the
page stream with a predicate a page and a block, PR 40 the latent kernel
with its DMAs, its softmax, its `acc` update and its contractions each taken
out, PR 61 the tall trip's eight lane tiles as two trips of four; the
results are in `kv_contract.py`'s and `mla_attention.py`'s docstrings).
"""

from __future__ import annotations

import argparse
import ast
import contextlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ollamamq_tpu.ops import mla
from ollamamq_tpu.ops.attention import (paged_decode_attention_any,
                                        ragged_attention_any, ring_table)
from ollamamq_tpu.ops.pallas import (kv_contract, mla_attention,
                                     paged_attention, ragged_attention)

MODULES = {m.__name__.rsplit(".", 1)[1]: m
           for m in (kv_contract, mla_attention, paged_attention,
                     ragged_attention)}
# (H, Hk, hd) — or (H, Hk, hd, vd) where a value head is narrower than a key
# head (MiMo-V2-Flash's two, `--shapes 8 9`: the full layers' group of 16 and
# the window layers' group of 8, whose `raggedlong_window` row carries a sink;
# their K rows are read in the split layout, `kv_contract.MxuSplit`).
SHAPES = ((28, 4, 128), (8, 2, 128), (32, 8, 64), (16, 16, 128),
          (30, 30, 128), (16, 2, 256), (64, 8, 128), (20, 4, 128),
          (64, 4, 192, 128), (64, 8, 192, 128))
B, MP, PS, NP, LAYER = 64, 256, 32, 1024, 1
# The jnp reference gathers a table's whole width, [B, width*PS, lanes]:
# it is fed the columns a context here can reach and no more.
REF_PAGES = 64
LAUNCHES = 64
# The latent traffic: DeepSeek-V3.2's attention widths (128 heads over a
# 640-lane latent pool whose first 512 lanes are the values, 2048 selected a
# token) and the `deepseek-v3.2-ep16-d5.longctx` cell's step — 5 one-token
# rows, then one 507-token prefill span, every sequence at one context.
LAT_H, LAT_LANES, LAT_RANK, LAT_TOPK = 128, 640, 512, 2048
LAT_ROWS, LAT_SPAN, LAT_LAUNCHES = 5, 507, 32
LAT_CONTEXTS = (4096, 8192, 12288, 16384)
# ...and the dense kernel's rows behind them: (heads, contexts beside those).
LAT_DENSE = ((32, ()), (128, (1024, 2048)))
# ...each with one more pair (`latent_dense_narrow`, `…_narrow_wide`): the
# 512-token rung with NO span of WIDE tokens — two spans of a prompt's tail's
# length — without and with the expanded form's operands: what a launch that
# holds the expanded programs costs a step they serve nothing of.
LAT_NARROW = (253, 254)
# Stream tokens the jnp twin is asked for (it gathers [tokens, C, 640]
# float32): the one-token rows, the span's first and last tokens, tokens at
# both sides of a tile's edge and of the tile's halves.
LAT_CHECKED = (0, 1, 2, 3, 4, 5, 12, 13, 15, 16, 23, 24, 255, 256, 511)
# The long-prefill traffic (`raggedlong`): the step of the
# `qwen3-next-80b-a3b-ep4-d12.longctx` cell, which has the latent cell's
# composition. The jnp reference gathers [tokens, block, H, hd] float32 a
# block, so it is asked for a sample of the stream: the one-token rows, the
# span's first tokens, both sides of tile and stretch edges, the last token.
LONG_ROWS, LONG_SPAN = LAT_ROWS, LAT_SPAN
LONG_CONTEXTS = (4096, 8192, 16384)
# ...and, a third row a context (`raggedlong_window`), the same step as a
# WINDOW layer launches it (K-EXAONE's, `--shapes 6`: PR 50): a window of 128
# positions over per-slot rings of 672 rows, the walk from the ring page that
# holds each span's first visible position (ops/attention.py:ring_table).
WINDOW, RING_ROWS = 128, 672
LONG_CHECKED = (0, 1, 2, 3, 4, 5, 6, 7, 8, 15, 16, 58, 59, 63, 64, 65, 71, 72,
                127, 128, 255, 256, 300, 447, 448, 504, 511)


def f32_matmul_keeps() -> dict:
    """1 + 2**-10 has 11 significant bits: a bf16 pass returns 1.0, and
    1 + 2**-18 tells bf16x3 (yes) from one tf32-like pass (no)."""
    def kernel(p_ref, w_ref, o_ref):
        o_ref[...] = jax.lax.dot_general(
            p_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    out = {}
    for bits in (10, 18):
        p = jnp.full((32, 8), 1.0 + 2.0 ** -bits, jnp.float32)
        w = jnp.eye(8, 128, dtype=jnp.float32)
        got = pl.pallas_call(kernel, out_shape=jax.ShapeDtypeStruct(
            (32, 128), jnp.float32))(p, w)
        out[f"one_plus_2^-{bits}"] = float(got[0, 0]) == 1.0 + 2.0 ** -bits
    return out


def dma_probe(lanes) -> list:
    """What a page copy HBM→VMEM costs a kernel that has nothing to hide
    it behind: TRIPS trips of `k` copies of `rows` pool rows started, then
    waited for — µs a trip. One copy of one page reads the latency, the
    slope over `k` what one more descriptor costs to issue and to move."""
    TRIPS = 2048
    pool = jnp.zeros((NP * PS, lanes), jnp.bfloat16)
    out = []
    for rows, k in ((PS, 1), (PS, 2), (PS, 4), (PS, 8), (4 * PS, 1),
                    (4 * PS, 2)):
        def kernel(hbm, o_ref, buf, sems, rows=rows, k=k):
            def trip(i, _):
                copies = [pltpu.make_async_copy(
                    hbm.at[pl.ds(((i * k + j) % (NP // 4)) * rows, rows)],
                    buf.at[j], sems.at[j]) for j in range(k)]
                for c in copies:
                    c.start()
                for c in copies:
                    c.wait()
                return ()

            jax.lax.fori_loop(0, TRIPS, trip, ())
            o_ref[...] = buf[0, :8].astype(jnp.float32)

        fn = jax.jit(lambda pool, kernel=kernel: pl.pallas_call(
            kernel,
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_shape=jax.ShapeDtypeStruct((8, lanes), jnp.float32),
            scratch_shapes=[pltpu.VMEM((k, rows, lanes), jnp.bfloat16),
                            pltpu.SemaphoreType.DMA((k,))])(pool))
        fn(pool).block_until_ready()
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            fn(pool).block_until_ready()
            best = min(best, time.perf_counter() - t0)
        out.append({"dma_probe": {"lanes": lanes, "rows": rows, "copies": k},
                    "us_a_trip": round(best / TRIPS * 1e6, 4)})
    return out


def batch(rng, n_decode, spans, context=None, span_ends=None, B=B, mp=MP,
          pool_pages=NP):
    """(page_table, tok_seq, tok_pos, kv_len, q_start, q_len, T). Decode
    rows hold 200-380 tokens of context, or `context` each; a span is a
    prompt's first tokens, or with `span_ends` a later chunk that ends at
    that context; pages are handed out in order and wrap around the pool
    when a sweep asks for more than it holds (the kernels only read)."""
    rows = [(1, context - 1 if context else int(rng.integers(200, 380)))
            for _ in range(n_decode)]
    rows += [(n, span_ends - n if span_ends else 0) for n in spans]
    T = sum(n for n, _ in rows)
    pt = np.zeros((B, mp), np.int32)
    q_len, kv_len = np.zeros(B, np.int32), np.zeros(B, np.int32)
    q_start = np.full(B, T, np.int32)
    tok_seq, tok_pos = np.zeros(T, np.int32), np.zeros(T, np.int32)
    off, page = 0, 0
    for s, (n, prefix) in enumerate(rows):
        need = -(-(prefix + n) // PS)
        pt[s, :need] = 1 + (page + np.arange(need)) % (pool_pages - 1)
        page += need
        q_len[s], kv_len[s], q_start[s] = n, prefix + n, off
        tok_seq[off:off + n] = s
        tok_pos[off:off + n] = prefix + np.arange(n)
        off += n
    assert context or page < pool_pages, page
    return [jnp.asarray(a) for a in (pt, tok_seq, tok_pos, kv_len, q_start,
                                     q_len)], T


def walks(traffic, kv_len, q_start, q_len, T) -> tuple:
    """Pages of each walk of a launch, (short walks, tall walks): a decode
    program walks its row's context, a ragged tile each overlapping
    sequence's up to the tile's deepest causal frontier — and a program of
    the ragged kernel whose stretch of the stream lies inside one span
    (`kv_contract.programs_height`: a tree that has none walks tiles
    alone) walks that span's once, tall, up to the stretch's."""
    kv_len, q_start, q_len = (np.asarray(a) for a in (kv_len, q_start,
                                                      q_len))
    if traffic == "decode":
        return [-(-int(n) // PS) for n in kv_len], []
    tile = kv_contract.G_TILE
    height = getattr(kv_contract, "programs_height", lambda T: tile)(T)
    short, tall = [], []
    for lo in range(0, T, tile):
        at = lo // height * height
        whole = height > tile and any(
            qs <= at and qs + ql >= at + height
            for qs, ql in zip(q_start, q_len))
        if whole and lo > at:
            continue  # its program's one walk is counted at its first tile
        hi = lo + (height if whole else tile)
        for qs, ql, kv in zip(q_start, q_len, kv_len):
            if ql > 0 and qs < hi and qs + ql > lo:
                last_pos = kv - ql + (min(hi, qs + ql) - 1 - qs)
                (tall if whole else short).append(-(-int(last_pos + 1) // PS))
    return short, tall


def long_trips(traffic, us_short, variant, us, short, tall) -> dict:
    """µs a (tile, block) trip of a `raggedlong` launch, short trips and
    tall ones apart: the variant's `raggedlong_head` row — the step's first
    64 tokens by themselves, every trip of it a short one — prices a short
    trip, and what is left of the step's launch after its short trips is
    its tall trips'. A tree or a `--set` with no tall walk has `tall` 0."""
    out = {"trips_short": short, "trips_tall": tall}
    if traffic == "raggedlong_head":
        us_short[variant] = us / short
    out["us_a_short_trip"] = round(us_short[variant], 4)
    if tall:
        out["us_a_tall_trip"] = round(
            (us - short * us_short[variant]) / tall, 4)
    elif traffic == "raggedlong":
        out["us_a_trip"] = round(us / short, 4)
    return out


def best_of_three(fn, *args) -> float:
    """Seconds a call of a jitted `fn`, the least of three after one."""
    fn(*args).block_until_ready()
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        fn(*args).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best


def timed(fn, q, *args) -> float:
    """Seconds a launch: LAUNCHES calls chained through q in one jit."""
    def again(_, q):  # (a value head narrower than the key head: the
        out = fn(q, *args).astype(q.dtype)  # output fills q's first lanes)
        if out.shape == q.shape:
            return out
        return jnp.concatenate([out, q[..., out.shape[-1]:]], axis=-1)

    @jax.jit
    def chain(q, *args):
        return jax.lax.fori_loop(0, LAUNCHES, again, q)

    return best_of_three(chain, q, *args) / LAUNCHES


@contextlib.contextmanager
def constants(consts):
    """The module constants of a `--set` in place for the block (and the
    jit caches cleared around it); yields their names for a row."""
    was = {k: getattr(*k) for k in consts}
    for (mod, attr), value in consts.items():
        setattr(mod, attr, value)
    if consts:
        jax.clear_caches()
    try:
        yield {f"{m.__name__.rsplit('.', 1)[1]}.{a}": v
               for (m, a), v in consts.items()}
    finally:
        for (mod, attr), value in was.items():
            setattr(mod, attr, value)
        if consts:
            jax.clear_caches()


def latent_trips(q_start, q_len, kv_len, T, tile, block):
    """(span trips, one-token trips) of an attention launch that walks
    tiles of `tile` tokens in blocks of `block`: a tile walks each sequence
    with a row in it up to the tile's deepest causal frontier; a sequence's
    ONE row in a tile is the kernel's one-token trip."""
    span = one = 0
    for lo in range(0, T, tile):
        hi = lo + tile
        for qs, ql, kv in zip(q_start, q_len, kv_len):
            if ql > 0 and qs < hi and qs + ql > lo:
                first, last = max(qs, lo), min(hi, qs + ql)
                n = -(-int(kv - ql + last - qs) // block)
                if last - first == 1 and tile > 1:
                    one += n
                else:
                    span += n
    return span, one


def latent_batch(rows, mp):
    """(page_table, q_start, q_len, kv_len), tok_seq, tok_pos, T of `rows`
    = (tokens, cached prefix) a sequence, each on pages of its own."""
    B, T = 8, sum(n for n, _ in rows)
    pt = np.zeros((B, mp), np.int32)
    q_len, kv_len = np.zeros(B, np.int32), np.zeros(B, np.int32)
    q_start = np.full(B, T, np.int32)
    tok_seq, tok_pos = np.zeros(T, np.int32), np.zeros(T, np.int32)
    off = 0
    for s, (n, prefix) in enumerate(rows):
        need = -(-(prefix + n) // PS)
        pt[s, :need] = 1 + s * mp + np.arange(need)
        q_len[s], kv_len[s], q_start[s] = n, prefix + n, off
        tok_seq[off:off + n] = s
        tok_pos[off:off + n] = prefix + np.arange(n)
        off += n
    return (pt, q_start, q_len, kv_len), tok_seq, tok_pos, T


def latent(args, variants, heads=LAT_H, dense=False, contexts=None) -> None:
    """The latent-attention kernel's rows: for each context the cell's step
    in the absorbed form (`latent`: every row through the tiles), with the
    span in the expanded form (`latent_wide`, PR 49: the kernel as the
    engine launches it) and its one-token rows by themselves (`latent_rows`,
    which prices a one-token trip), each checked on LAT_CHECKED against
    ops/mla.sparse_attention (followed by W_uv where the expanded body
    serves) and timed over LAT_LAUNCHES launches chained in one jit (each
    one's layer index a function of the last one's output). With the
    one-token trips taken off a launch, `us_a_tile_block` is a (tile,
    block) trip of the span at the variant's own tile and block, and
    `us_a_16_256` the same time over the trips tiles of 16 tokens and
    blocks of 256 would make: what tiles, widths and the two forms are
    compared by (it carries a wider block's over-read, and the expanded
    form's walk of the whole span to its last frontier). `dense`: the
    kernel with no selection, at `heads` (the rows' names say `latent_dense`
    where the masked kernel's say `latent`)."""
    ka = mla_attention
    contexts = contexts or args.contexts or LAT_CONTEXTS
    mp = max(contexts) // PS
    prefix = "latent_dense" if dense else "latent"
    key = jax.random.PRNGKey(args.seed)
    pool = (jax.random.normal(
        key, (2, (1 + (LAT_ROWS + 1 + dense) * mp) * PS, LAT_LANES),
        jnp.float32)
        * 0.3).astype(jnp.bfloat16).at[:, :, 576:].set(0)
    nope = LAT_V = 128
    # [W_uk,h | W_uv,h] a head, as the model holds it: [rank, H, nope + v]
    wukv = (jax.random.normal(jax.random.fold_in(key, 1), (
        LAT_RANK, heads, nope + LAT_V), jnp.float32) * nope ** -0.5
    ).astype(jnp.bfloat16)
    w_t = jnp.transpose(wukv, (1, 2, 0))

    def fn(layer, q, *operands):
        """operands: the selection's two (the masked kernel's), the pool,
        the step's four, the expanded form's two or none."""
        n = 0 if dense else 2
        kernel = ka.mla_dense_paged_attention_pallas if dense \
            else ka.mla_sparse_paged_attention_pallas
        expanded = operands[n + 5:]
        return kernel(q, *operands[:n + 1], layer, *operands[n + 1:n + 5],
                      PS, LAT_RANK,
                      **({"expanded": expanded} if expanded else {}))

    def through_w_uv(out):
        """[T, H, v] of a launch's result, float32."""
        o, o_v, served = out if isinstance(out, tuple) else (out, None, None)
        o = jnp.einsum("thc,chv->thv", o, wukv[..., nope:],
                       preferred_element_type=jnp.float32)
        return o if o_v is None else jnp.where(
            served[:, None, None], o_v.astype(jnp.float32), o)

    def chain(*operands):
        def body(_, layer):
            # row 0 is a one-token row's, the last row the span's: both
            # finite, so the next launch reads the same layer
            o = through_w_uv(fn(layer, *operands))
            bad = jnp.isnan(o[0, 0, 0]) | jnp.isnan(o[-1, 0, 0])
            return LAYER * (1 - bad.astype(jnp.int32))
        return jax.lax.fori_loop(0, LAT_LAUNCHES, body, jnp.int32(LAYER))

    for context in contexts:
        us_one = {}  # a variant's one-token trip, from its `latent_rows`
        for traffic, spans in ((prefix + "_rows", ()), (prefix, (LAT_SPAN,)),
                               (prefix + "_wide", (LAT_SPAN,))) + dense * (
                (prefix + "_narrow", LAT_NARROW),
                (prefix + "_narrow_wide", LAT_NARROW)):
            meta, tok_seq, tok_pos, T = latent_batch(
                [(1, context - 1)] * LAT_ROWS
                + [(n, context - n) for n in spans], mp)
            k1, k2, k3 = jax.random.split(jax.random.fold_in(key, context), 3)
            q_nope, q_rope = (
                (jax.random.normal(k, (T, heads, n), jnp.float32) * 0.1
                 ).astype(jnp.bfloat16) for k, n in ((k1, nope), (k3, 64)))
            pad = jnp.zeros((T, heads, LAT_LANES - LAT_RANK - 64), jnp.float32)
            q = jnp.concatenate([jnp.einsum(
                "thn,chn->thc", q_nope, wukv[..., :nope],
                preferred_element_type=jnp.float32),
                q_rope.astype(jnp.float32), pad], -1).astype(jnp.bfloat16)
            expanded = ()
            if traffic.endswith("_wide"):
                expanded = (jnp.concatenate(
                    [q_nope, q_rope, pad.astype(jnp.bfloat16)], -1), w_t)
            checked = np.asarray([t for t in LAT_CHECKED if t < T])
            for consts in variants:
                if traffic == prefix and consts and all(
                        a.startswith(("WIDE", "_")) for _, a in consts):
                    continue  # a constant of the expanded body alone
                with constants(consts) as names:
                    tile = getattr(ka, "ATTEND_TILE", ka.TILE)
                    block = getattr(ka, "ATTEND_BLOCK", ka.BLOCK)
                    span, one = latent_trips(*meta[1:], T, tile, block)
                    row = {"shape": [heads, LAT_LANES, LAT_RANK],
                           "traffic": traffic, "tokens": T,
                           "context": context, "tile": tile, "block": block,
                           "trips_span": span, "trips_one": one,
                           "set": names}
                    try:
                        selection = ()
                        if not dense:
                            scores = jax.random.normal(
                                k2, (T, ka.context_lanes(mp, PS)),
                                jnp.float32)
                            selection = (scores, mla.select_threshold(
                                scores, jnp.asarray(tok_pos), LAT_TOPK))
                        pt, *rest = (jnp.asarray(a) for a in meta)
                        operands = (q, *selection, pool, pt, *rest,
                                    *expanded)
                        out = fn(LAYER, *operands)
                        if expanded:
                            row["wide_tokens"] = int(out[2].sum())
                        out = np.asarray(through_w_uv(out)[checked])
                        ref = np.asarray(through_w_uv(mla.sparse_attention(
                            q[checked], *(
                                [s[checked] for s in selection]
                                or (None, None)), pool,
                            LAYER, pt, jnp.asarray(tok_seq[checked]),
                            jnp.asarray(tok_pos[checked]), PS, LAT_RANK)))
                        us = best_of_three(jax.jit(chain), *operands) \
                            / LAT_LAUNCHES * 1e6
                        diff = float(np.abs(out - ref).max())
                        row.update({
                            "ms_a_launch": round(us / 1e3, 4),
                            "max_abs_diff_vs_jnp": diff,
                            "within_tolerance": bool(diff <= 2 ** -8 * max(
                                1.0, np.abs(ref).max())),
                            "finite": bool(np.isfinite(out).all())})
                        if traffic == prefix + "_rows":
                            us_one[str(names)] = us / one
                            row["us_a_one_token_trip"] = round(us / one, 4)
                        else:
                            span_us = us - one * us_one[str(names)]
                            span_16_256, _ = latent_trips(*meta[1:], T, 16,
                                                          256)
                            row["us_a_tile_block"] = round(span_us / span, 4)
                            row["us_a_16_256"] = round(
                                span_us / span_16_256, 4)
                    except Exception as e:  # noqa: BLE001 — as in main()
                        row["error"] = str(e)[:300]
                    print(json.dumps(row), flush=True)


def parse_set(text) -> dict:
    """"mod.NAME=value,mod.NAME=value" → {(module, NAME): value}."""
    out = {}
    for item in text.split(","):
        name, value = item.split("=")
        mod, attr = name.strip().split(".")
        getattr(MODULES[mod], attr)  # a constant that exists
        try:
            value = ast.literal_eval(value)
        except ValueError:
            pass  # a bare word is a string
        out[MODULES[mod], attr] = value
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--set", action="append", default=[], type=parse_set,
                    metavar="MODULE.NAME=VALUE[,…]", dest="variants",
                    help="one more row a (shape, traffic) with these "
                         "constants of kv_contract / paged_attention / "
                         "ragged_attention / mla_attention set (the served "
                         "inner product)")
    ap.add_argument("--only-set", action="store_true",
                    help="leave out the rows of the tree as it stands")
    ap.add_argument("--contexts", type=int, nargs="*", default=[],
                    help="every sequence at exactly this many tokens, a "
                         "row a value, instead of the 200-380 mix (and "
                         "no ragged512)")
    ap.add_argument("--probe-dma", action="store_true",
                    help="first, what a page copy costs with nothing to "
                         "hide it behind (latency, issue), at 256 and "
                         "512 lanes")
    ap.add_argument("--traffic", nargs="*",
                    default=["decode", "ragged64", "ragged512"],
                    choices=["decode", "ragged64", "ragged512", "raggedlong",
                             "latent"],
                    help="`raggedlong`: the ragged kernel on a long "
                         "prompt's chunk, two rows a context (--contexts, "
                         "default 4096 8192 16384); `latent`: the "
                         "latent-attention kernel on the DeepSeek-V3.2 "
                         "cell's step, a row a context (--contexts, default "
                         "4096 8192 12288 16384) and a --set "
                         "mla_attention.NAME=VALUE")
    ap.add_argument("--shapes", type=int, nargs="*",
                    default=list(range(len(SHAPES))),
                    help="indices into SHAPES")
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(json.dumps({"detail": f"no TPU: platform is {dev.platform}"}))
        return 1
    print(json.dumps({"device_kind": dev.device_kind,
                      "f32_matmul_keeps": f32_matmul_keeps()}), flush=True)
    if args.probe_dma:
        for lanes in (256, 512):
            for row in dma_probe(lanes):
                print(json.dumps(row), flush=True)
    rng = np.random.default_rng(args.seed)
    if "latent" in args.traffic:
        rows = ([] if args.only_set else [{}]) + args.variants
        latent(args, rows)
        for heads, more in LAT_DENSE:
            latent(args, rows, heads, dense=True, contexts=args.contexts or (
                more + LAT_CONTEXTS))
    variants = [] if args.only_set else [("vpu", {}), ("mxu", {})]
    variants += [(None, v) for v in args.variants]
    # (traffic, decode rows, spans, context, where a span ends)
    traffics = [("decode", 64, (), c, None) for c in args.contexts or [None]]
    traffics += [("ragged64", 64, (), c, None)
                 for c in args.contexts or [None]]
    if not args.contexts:
        traffics.append(("ragged512", 56, (228, 228), None, None))
    traffics = [t for t in traffics if t[0] in args.traffic]
    if "raggedlong" in args.traffic:
        # The stream's first 64 tokens by themselves price a short trip
        # (one tile a program, before and after PR 48), then the step.
        head = 64 - LONG_ROWS
        for c in args.contexts or LONG_CONTEXTS:
            traffics += [
                ("raggedlong_head", LONG_ROWS, (head,), c,
                 c - (LONG_SPAN - head)),
                ("raggedlong", LONG_ROWS, (LONG_SPAN,), c, c)]
    long_mp = max(args.contexts or LONG_CONTEXTS) // PS
    for shape in (SHAPES[i] for i in args.shapes if traffics):
        H, Hk, hd, vd = (shape + shape[2:])[:4]  # (vd: hd unless given)
        kc, vc = (jnp.asarray(rng.standard_normal((2, NP * PS, Hk * d)),
                              jnp.bfloat16) for d in (hd, vd))
        if "raggedlong" in args.traffic:  # a pool that holds every context
            long_pages = (LONG_ROWS + 1) * long_mp + 1
            kc_long, vc_long = (jax.random.normal(
                key, (2, long_pages * PS, Hk * d), jnp.bfloat16)
                for key, d in zip(jax.random.split(
                    jax.random.PRNGKey(args.seed)), (hd, vd)))
        us_short = {}  # a variant's short trip, from its `raggedlong_head`
        for traffic, n_dec, spans, context, span_ends in traffics:
            long = traffic.startswith("raggedlong")
            (pt, tok_seq, tok_pos, kv_len, q_start, q_len), T = batch(
                np.random.default_rng(args.seed), n_dec, spans, context,
                span_ends, **(dict(B=8, mp=long_mp, pool_pages=long_pages)
                              if long else {}))
            q = jnp.asarray(rng.standard_normal((T, H, hd)), jnp.bfloat16)
            ref_pt = pt[:, :max(REF_PAGES, -(-(context or 0) // PS))]
            checked = np.arange(T)
            pools = (kc, vc)
            if long:
                pools = (kc_long, vc_long)
                checked = np.asarray([t for t in LONG_CHECKED if t < T])
            if traffic == "decode":
                ref = paged_decode_attention_any(
                    "jnp", q, *pools, LAYER, ref_pt, kv_len, PS)
            else:
                ref = ragged_attention_any(
                    "jnp", q[checked], *pools, LAYER, ref_pt,
                    tok_seq[checked], tok_pos[checked], kv_len, q_start,
                    q_len, PS)
            for inner, consts in variants:
                if inner == "vpu" and (traffic != "decode" or vd != hd):
                    continue  # the ragged kernel has one inner product, and
                    # so has a value head narrower than the key head
                with constants(consts) as names:
                    built = kv_contract.make_inner(
                        inner if traffic == "decode" else None,
                        rows=1 if traffic == "decode" else kv_contract.G_TILE,
                        group=H // Hk, num_kv_heads=Hk, head_dim=hd,
                        page_size=PS, v_dim=vd if vd != hd else 0)
                    bp = built.block_pages
                    short, tall = walks(traffic, kv_len, q_start, q_len, T)
                    pages = short + tall
                    blocks = sum(-(-n // bp) for n in pages)
                    if traffic == "decode":
                        def fn(q, kc, vc, pt, kv_len, inner=inner):
                            launch = \
                                paged_attention.paged_decode_attention_pallas
                            return launch(q, kc, vc, LAYER, pt, kv_len, PS,
                                          inner=inner)
                        operands = (*pools, pt, kv_len)
                    else:
                        def fn(q, kc, vc, pt, qs, ql, kl):
                            launch = \
                                ragged_attention.ragged_paged_attention_pallas
                            return launch(q, kc, vc, LAYER, pt, qs, ql, kl,
                                          PS)
                        operands = (*pools, pt, q_start, q_len, kv_len)
                    row = {
                        "shape": list(shape), "traffic": traffic, "tokens": T,
                        "context": context or "200-380",
                        "inner": built.name,
                        "set": names}
                    try:
                        out = np.asarray(fn(q, *operands)[checked],
                                         np.float32)
                        ms = timed(fn, q, *operands) * 1e3
                        row.update({
                            "ms_a_launch": round(ms, 4),
                            "pages_live": sum(pages),
                            "pages_read": sum(-(-n // bp) * bp for n in pages),
                            "us_a_seq_block": round(ms * 1e3 / blocks, 4),
                            "max_abs_diff_vs_jnp": float(np.abs(
                                out - np.asarray(ref, np.float32)).max()),
                            "finite": bool(np.isfinite(out).all()),
                        })
                        if long:
                            row.update(long_trips(
                                traffic, us_short, str(names), ms * 1e3,
                                *(sum(-(-n // bp) for n in w)
                                  for w in (short, tall))))
                    except Exception as e:  # noqa: BLE001 — a variant the
                        # compiler refuses is a row, not the end of the run
                        row["error"] = str(e)[:300]
                    print(json.dumps(row), flush=True)
            if traffic == "raggedlong":
                print(json.dumps(windowed(
                    rng, (H, Hk, hd, vd), q, checked, tok_seq, tok_pos,
                    kv_len, q_start, q_len, T, context)), flush=True)
    return 0


def windowed(rng, shape, q, checked, tok_seq, tok_pos, kv_len, q_start, q_len,
             T, context) -> dict:
    """The `raggedlong` step as a window layer's launch: the row of the
    kernel over rings, against its jnp twin over the same table — with a
    SINK a head where the value head is narrower than the key head (the one
    model that has either has both)."""
    H, Hk, hd, vd = shape
    rows = q_len.shape[0]
    rk, rv = (jnp.asarray(rng.standard_normal(
        (2, (rows + 1) * RING_ROWS, Hk * d)), jnp.bfloat16)
        for d in (hd, vd))
    sink = {} if vd == hd else {"sink": jnp.asarray(
        rng.standard_normal((H,)), jnp.float32)}
    pt, base = ring_table(jnp.arange(rows, dtype=jnp.int32), kv_len, q_len,
                          WINDOW, RING_ROWS, PS, T)
    ref = ragged_attention_any(
        "jnp", q[checked], rk, rv, LAYER, pt, tok_seq[checked],
        tok_pos[checked], kv_len, q_start, q_len, PS, window=WINDOW,
        pos_base=base, **sink)

    def fn(q, kc, vc, pt, qs, ql, kl, base):
        return ragged_attention.ragged_paged_attention_pallas(
            q, kc, vc, LAYER, pt, qs, ql, kl, PS, window=WINDOW,
            pos_base=base, **sink)

    operands = (rk, rv, pt, q_start, q_len, kv_len, base)
    row = {"shape": list(shape[:3 if vd == hd else 4]),
           "traffic": "raggedlong_window", "tokens": T, "context": context,
           "window": WINDOW, "sink": bool(sink)}
    try:
        out = np.asarray(fn(q, *operands)[checked], np.float32)
        row.update({
            "ms_a_launch": round(timed(fn, q, *operands) * 1e3, 4),
            "rows_walked": int((kv_len - base).sum()),
            "max_abs_diff_vs_jnp": float(np.abs(
                out - np.asarray(ref, np.float32)).max()),
            "finite": bool(np.isfinite(out).all())})
    except Exception as e:  # noqa: BLE001 — a row, not the end of the run
        row["error"] = str(e)[:300]
    return row


if __name__ == "__main__":
    sys.exit(main())
