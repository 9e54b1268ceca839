"""Ragged mixed-batch attention: jnp reference vs blockwise vs the
Pallas kernel (interpret mode on CPU), and forward_ragged vs the
bucketed forward composition it replaces."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ollamamq_tpu.engine import kv_cache as kvc
from ollamamq_tpu.models import llama
from ollamamq_tpu.ops.attention import (ragged_paged_attention,
                                        ragged_paged_attention_blockwise)
from ollamamq_tpu.ops.pallas import kv_contract
from ollamamq_tpu.ops.pallas.kv_contract import TALL
from ollamamq_tpu.ops.pallas.ragged_attention import (
    ragged_paged_attention_pallas)


LAYERS = 3  # pool depth of the kernel cases: first, middle, last layer


def _case(spans, B, PS=8, MP=8, Hk=2, H=4, hd=16, seed=0,
          dtype=jnp.float32):
    """Build one ragged batch: spans = [(q_len, kv_len), ...] laid out
    contiguously in stream order; trailing rows of B are padding. The
    pool is whole — [LAYERS, S, Hk*hd], every layer different — and the
    attentions under test read one layer of it by index."""
    rng = np.random.default_rng(seed)
    T = sum(s for s, _ in spans)
    S = (sum(-(-kv // PS) for _, kv in spans) + 2) * PS
    q = jnp.asarray(rng.normal(size=(T, H, hd)), dtype)
    k = jnp.asarray(rng.normal(size=(LAYERS, S, Hk * hd)), dtype)
    v = jnp.asarray(rng.normal(size=(LAYERS, S, Hk * hd)), dtype)
    pt = np.zeros((B, MP), np.int32)
    nxt = 1
    q_start = np.full(B, T, np.int32)
    q_len = np.zeros(B, np.int32)
    kv_len = np.zeros(B, np.int32)
    tok_seq = np.zeros(T, np.int32)
    tok_pos = np.full(T, -1, np.int32)
    off = 0
    for i, (ql, kv) in enumerate(spans):
        need = -(-kv // PS)
        pt[i, :need] = range(nxt, nxt + need)
        nxt += need
        q_start[i] = off
        q_len[i] = ql
        kv_len[i] = kv
        tok_seq[off:off + ql] = i
        tok_pos[off:off + ql] = np.arange(kv - ql, kv)
        off += ql
    return (q, k, v, jnp.asarray(pt), jnp.asarray(tok_seq),
            jnp.asarray(tok_pos), jnp.asarray(kv_len),
            jnp.asarray(q_start), jnp.asarray(q_len), PS)


MIXED_CASES = [
    # prefill span + decode rows + prefill tail, non-multiple-of-8 total
    dict(spans=[(11, 11), (1, 20), (5, 29), (1, 1)], B=6),
    # a whole tile of pure decode rows crossing a tile boundary
    dict(spans=[(1, 5 + 3 * i) for i in range(9)], B=10),
    # one long prefill spanning several tiles + mixed tail
    dict(spans=[(21, 21), (1, 9), (1, 17), (3, 30)], B=6),
]


# The published head shapes (H, Hk, hd) at the engine's page size, cut only
# in count of pages: Qwen2.5-7B, Qwen3-8B as one tp=4 shard sees it,
# LFM2-8B-A1B / llama3.2 (two heads a lane tile), OLMoE and Olmo-Hybrid
# (group 1). Tile 0 is eight decode rows from eight sequences — a
# context of ONE token; ends inside a page, on a page edge, on and just
# past the 128-token block edge; 6 and 7 pages (no multiple of a block's
# four). Then a prefill span over a cached prefix that crosses two tile
# edges and the block edge, and a decode row sharing its last tile.
HEAD_SHAPES = [(28, 4, 128), (8, 2, 128), (32, 8, 64), (16, 16, 128),
               (30, 30, 128)]
_PUBLISHED = dict(
    spans=[(1, 1), (1, 33), (1, 128), (1, 129), (1, 163), (1, 200),
           (1, 64), (1, 100), (19, 140), (1, 97)], B=12, PS=32, MP=8)
PUBLISHED_CASES = [dict(_PUBLISHED, H=H, Hk=Hk, hd=hd, seed=H)
                   for H, Hk, hd in HEAD_SHAPES]
# The page stream's unit is the block of four pages (kv_contract.py): one
# predicate a block, every page of it copied, the trash page where the
# table's padding begins. Whole pages, so that the last block is all that
# differs: decode rows whose contexts end 1, 2 and 3 pages into their
# first and their second block and exactly on both block edges — the
# last a row whose table is full (no padding left to read) — and a
# prefill span whose tiles' causal frontiers end 1, 2, 3 and 4 pages into
# a block. Two rows of length 0 trail them (B = 11). MIXED_CASES' pages
# of 8 tokens put four pages in a block of 32.
_BLOCKS = dict(
    spans=[(1, 32), (1, 64), (1, 96), (1, 128), (1, 160), (1, 192),
           (1, 224), (1, 256), (128, 128)], B=11, PS=32, MP=8)
BLOCK_CASES = [dict(_BLOCKS, H=H, Hk=Hk, hd=hd, seed=H + 1)
               for H, Hk, hd in HEAD_SHAPES]
# q and the pool in bf16 against the float32 twin fed the same bf16
# values. The kernel's products are exact (bf16 x bf16 into float32) and its
# softmax, `l` and `acc` are float32; what separates it from the twin is
# the f32 tolerance below plus TWO roundings to bf16's 8 significant bits,
# each to nearest: at most half of a spacing of 2**-7 just above a power of
# two, 2**-8 of the value rounded (`two_roundings`).
#   - P into P·V (`kv_contract.Mxu._pv`: P enters at the pool's dtype, as
#     the published modelling code casts it). Every p_j moves by at most
#     2**-8 p_j, the float32 rescaling of the online softmax keeps that
#     ratio, and `l` sums the unrounded p: acc / l = sum(p_j v_j) / sum(p_j)
#     moves by at most 2**-8 * sum(p_j |v_j|) / sum(p_j) — the twin's own
#     output over |v|, an element: computed from the case, not chosen.
#   - The output: 2**-8 of what it was before, the twin's value and the
#     error above.
PUBLISHED_CASES.append(dict(PUBLISHED_CASES[0], dtype=jnp.bfloat16))
BLOCK_CASES.append(dict(BLOCK_CASES[0], dtype=jnp.bfloat16))
F32_TOL = dict(rtol=2e-5, atol=2e-5)


def two_roundings(ref, ref_abs_v):
    """The bound derived above, an output element: `ref` the float32 twin's
    output, `ref_abs_v` the same twin's over |v|."""
    before = (F32_TOL["atol"] + F32_TOL["rtol"] * np.abs(ref)
              + 2 ** -8 * ref_abs_v)
    return before + 2 ** -8 * (np.abs(ref) + before)


def assert_kernel_close(out, dtype, v, twin):
    """A kernel's `out` against `twin(v)`, its float32 twin as a function of
    the float32 values: F32_TOL, or `two_roundings` for a bf16 launch.
    Returns the twin's output and the bound (None at float32)."""
    out, ref = np.asarray(_f32(out)), np.asarray(twin(_f32(v)))
    if dtype != jnp.bfloat16:
        np.testing.assert_allclose(out, ref, **F32_TOL)
        return ref, None
    bound = two_roundings(ref, np.asarray(twin(jnp.abs(_f32(v)))))
    over = np.abs(out - ref) - bound
    at = np.unravel_index(np.argmax(over), over.shape)
    assert over[at] <= 0, (
        f"{out[at]} against {ref[at]} at {at}: the bound is {bound[at]}")
    return ref, bound


def _id(case):
    if "hd" not in case:
        return "mixed" + str(MIXED_CASES.index(case))
    return "H{H}-Hk{Hk}-hd{hd}".format(**case) + (
        "-bf16" if "dtype" in case else "") + (
        "-blocks" if case["spans"] is _BLOCKS["spans"] else "")


@pytest.mark.parametrize("case", MIXED_CASES)
def test_blockwise_matches_reference(case):
    q, k, v, pt, tok_seq, tok_pos, kv_len, _qs, _ql, PS = _case(**case)
    ref = ragged_paged_attention(q, k, v, 1, pt, tok_seq, tok_pos, kv_len,
                                 PS)
    blk = ragged_paged_attention_blockwise(
        q, k, v, 1, pt, tok_seq, tok_pos, kv_len, PS, block_pages=2)
    np.testing.assert_allclose(np.asarray(blk), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def _f32(x):
    return x.astype(jnp.float32)


@pytest.mark.parametrize("layer", range(LAYERS))
@pytest.mark.parametrize("case", MIXED_CASES + PUBLISHED_CASES + BLOCK_CASES,
                         ids=_id)
def test_pallas_matches_reference(case, layer, poison_trash_page):
    q, k, v, pt, tok_seq, tok_pos, kv_len, qs, ql, PS = _case(**case)
    clean = ragged_paged_attention_pallas(q, k, v, layer, pt, qs, ql,
                                          kv_len, PS, interpret=True)
    # The kernel reads the trash page where a block runs past a walk's
    # last page; the reference reads the clean pool (conftest.py).
    out = ragged_paged_attention_pallas(
        q, poison_trash_page(k, PS, layer), poison_trash_page(v, PS, layer),
        layer, pt, qs, ql, kv_len, PS, interpret=True)
    assert out.dtype == q.dtype
    np.testing.assert_array_equal(np.asarray(_f32(out)),
                                  np.asarray(_f32(clean)))
    assert_kernel_close(out, q.dtype, v, lambda v: ragged_paged_attention(
        _f32(q), _f32(k), v, layer, pt, tok_seq, tok_pos, kv_len, PS))


@pytest.mark.parametrize("Hk,H", [(1, 4), (4, 4)])
def test_pallas_mqa_and_group1(Hk, H):
    q, k, v, pt, tok_seq, tok_pos, kv_len, qs, ql, PS = _case(
        spans=[(6, 6), (1, 12)], B=3, Hk=Hk, H=H, seed=2)
    ref = ragged_paged_attention(q, k, v, 2, pt, tok_seq, tok_pos,
                                 kv_len, PS)
    out = ragged_paged_attention_pallas(q, k, v, 2, pt, qs, ql, kv_len,
                                        PS, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **F32_TOL)


def _inside(eqn):
    """Every equation of an equation's sub-jaxprs (a loop's body, a
    `pl.when`'s branches), nested ones included."""
    for v in eqn.params.values():
        for x in v if isinstance(v, (tuple, list)) else (v,):
            x = getattr(x, "jaxpr", x)
            if hasattr(x, "eqns"):
                yield from _walk(x)


def _walk(jaxpr):
    """Every equation of a jaxpr, sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn
        yield from _inside(eqn)


def _kernel_jaxpr(H, Hk, hd, T=64, B=64, PS=32, MP=8,
                  launch=ragged_paged_attention_pallas):
    """The ragged kernel's traced body at one head shape. Shapes only:
    nothing runs."""
    def s(*shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt)

    pool = s(2, (MP * 4 + 2) * PS, Hk * hd, dt=jnp.bfloat16)
    closed = jax.make_jaxpr(
        lambda *a: launch(*a, PS))(
            s(T, H, hd, dt=jnp.bfloat16), pool, pool, s(), s(B, MP), s(B),
            s(B), s(B))
    calls = [e for e in _walk(closed.jaxpr)
             if e.primitive.name == "pallas_call"]
    assert len(calls) == 1  # one Mosaic call a layer a pass
    return calls[0].params["jaxpr"]


def _count(eqns, name):
    return sum(e.primitive.name == name for e in eqns)


BODY_SHAPES = ((28, 4, 128),     # 4 lane tiles
               (16, 16, 128),    # 16
               (32, 8, 64),      # 4, two heads each
               (30, 30, 128))    # 30


def _tiles(H, Hk, hd):
    return kv_contract.make_inner("mxu", rows=8, group=H // Hk,
                                  num_kv_heads=Hk, head_dim=hd,
                                  page_size=32).tiles


def test_traced_body_does_not_grow_with_tile_height_times_kv_heads():
    """The defect that refused PR 33, held shut without a chip. A ragged
    step program is traced, lowered and keyed once a rung of the token
    ladder at every start, and that cost follows the size of the kernel's
    traced body (`setup_s`, judged in every cell). PR 33 unrolled the walk
    of a tile's G_TILE successor sequences AND the lane tiles in Python:
    8 x 16 copies of the inner product at OLMoE's 16 kv heads (8354
    equations, 256 `dot_general`s, where PR 32's VPU body had 4378). The
    walk is a loop in the program now, so the body holds one copy a lane
    tile — a Q.K^T and a P.V contraction each — and nothing a successor.
    A rung of fewer than 2 * TALL tokens holds no whole stretch beside a
    decode row and traces this body alone: its parent's."""
    few, many, packed, widest = (
        list(_walk(_kernel_jaxpr(*shape))) for shape in BODY_SHAPES)
    assert [_count(e, "dot_general") for e in (few, many, packed, widest)
            ] == [8, 32, 8, 60]
    # Four times the kv heads, under three times the body; and an eighth
    # of PR 33's. What the body measures when written (PR 38): 449, 1145,
    # 449 and 1957 equations — 58 a lane tile and 217 of everything else,
    # none of them a nested jit (`kv_contract.py`: scalars are `lax`
    # calls, because every operator on a tracer is one). PR 48: 446, 1142,
    # 446, 1954. PR 59 (P into P·V once, not as three terms: 13 a lane
    # tile): 394, 934, 394, 1564. PR 61 (the inner product's vector code
    # as `lax` calls: a `jnp.where` was a `jit` equation around a weak
    # scalar's `convert_element_type`, its broadcast and the `select_n` —
    # the last two are what is left, 6 equations fewer a lane tile and
    # every other primitive in its place): 369, 837, 369, 1383 — 39 a lane
    # tile and 213 of everything else.
    assert [len(e) for e in (few, many, packed, widest)] == [
        213 + 39 * _tiles(*shape) for shape in BODY_SHAPES]
    for body in (few, many, packed, widest):  # "pjit" until jax 0.7
        assert _count(body, "jit") == _count(body, "pjit") == 0


TALL_BODY_SHAPES = BODY_SHAPES + ((64, 8, 128),   # 8 lane tiles
                                  (16, 2, 256))   # 2, of 256 lanes


def test_a_tall_rung_holds_two_bodies_and_no_more():
    """The tile follows the span (PR 48): a rung of 2 * TALL tokens or
    more traces the tall body beside the tile's, for the program whose
    stretch of the stream is one span's — and still nothing a successor, a
    tile of the program or a block: the program's tiles are a loop in the
    program. The tall trip's lane tiles are straight-line code up to
    `TALL_UNROLL` = 8 of them (PR 61: one more copy of the inner product a
    lane tile, 40 equations each since a copy is `lax` calls — 420 more
    than the short rung's at four tiles, 580 at eight, 342 at two of 256
    lanes) and a loop in the program beyond (ONE more copy at any width,
    304 equations: what a rung costs every start does not grow with the
    kv heads of an MHA model again)."""
    assert kv_contract.TALL_UNROLL == 8
    short = [list(_walk(_kernel_jaxpr(*shape))) for shape in TALL_BODY_SHAPES]
    tall = [list(_walk(_kernel_jaxpr(*shape, T=2 * TALL)))
            for shape in TALL_BODY_SHAPES]
    tiles = [_tiles(*shape) for shape in TALL_BODY_SHAPES]
    assert tiles == [4, 16, 4, 30, 8, 2]
    unrolled = [n if n <= kv_contract.TALL_UNROLL else 1 for n in tiles]
    assert [_count(two, "dot_general") - _count(one, "dot_general")
            for one, two in zip(short, tall)] == [2 * n for n in unrolled]
    for one, two, n in zip(short, tall, unrolled):
        assert len(two) <= len(one) + 270 + 40 * n
        assert _count(two, "jit") == _count(two, "pjit") == 0
    # ...whatever the rung: the programs are a grid, not a trace.
    assert len(list(_walk(_kernel_jaxpr(*BODY_SHAPES[0], T=512)))) == len(
        tall[0])


@pytest.mark.parametrize("shape", [(64, 8, 128), (28, 4, 128),
                                   (16, 16, 128)],
                         ids=lambda s: "x".join(map(str, s)))
def test_tracing_a_tall_rung_binds_no_nested_jit_a_lane_tile(shape,
                                                             monkeypatch):
    """What a rung's trace is charged for is its nested jits
    (`kv_contract.py`: on a tracer every operator and every `jnp.where` /
    `maximum` / `exp` / `sum` is one, ~1-2 ms each inside a serving
    process), and until PR 61 a copy of the inner product bound ~19 of them
    and `finish` 4 a lane tile: 209 a trace at (64, 8, 128), 137 at (28, 4,
    128), 353 at (16, 16, 128), and the unrolled tall trip would have added
    19 a tile. Counted without a chip: the binds of `jit` while the launch
    is traced, wrapper and both bodies — 7 to 23 now at any width (the
    wrapper's own `pad`, `searchsorted` and the packing's operators; fewer
    where jax's caches already hold their traces), under a ceiling that a
    single copy written with operators breaks."""
    from jax.extend.core.primitives import jit_p

    binds, bind = [], jit_p.bind

    def counted(*args, **params):
        binds.append(params.get("name"))
        return bind(*args, **params)

    monkeypatch.setattr(jit_p, "bind", counted)
    # The launch's own function, not its jit: a trace that another case
    # left in the cache would bind nothing.
    _kernel_jaxpr(*shape, T=2 * TALL,
                  launch=ragged_paged_attention_pallas.__wrapped__)
    assert 0 < len(binds) <= 30, sorted(binds)


def _stream_of_one_walk(walk_body):
    """What a walk of either body is made of: before its loop, under one
    predicate that a walk of two blocks or more skips as a whole, what it
    starts for its successor — the ring's two slots, each under its own
    test; then the loop over its blocks, a block of four pages and two
    pools refilled under its one predicate and waited for once a pool
    under none."""
    names = [e.primitive.name for e in walk_body.eqns]
    assert names.count("while") == 1
    blocks = next(e for e in walk_body.eqns if e.primitive.name == "while")
    in_loop = list(_walk(blocks.params["body_jaxpr"].jaxpr))
    assert _count(in_loop, "cond") == 1
    assert _count(in_loop, "dma_start") == 8
    assert _count(in_loop, "dma_wait") == 2
    waits = [e for e in blocks.params["body_jaxpr"].jaxpr.eqns
             if e.primitive.name == "dma_wait"]
    assert len(waits) == 2  # at the loop's own level: no predicate
    before = [e for e in walk_body.eqns if e.primitive.name == "cond"][-1]
    assert _count(_inside(before), "cond") == 2
    assert _count(_inside(before), "dma_start") == 16
    return names.count("cond")


def test_page_stream_has_one_predicate_a_block():
    """The unit of the page stream is the block (kv_contract.PageStream):
    a block's pages start under ONE `pl.when` and are waited for once a
    pool under none — a predicate a page, or a page-table read in the
    wait, cannot come back unseen. On a rung of one tile a program the
    walk over the tile's sequences is the kernel's one loop at top level;
    in it, what a short walk starts for its successor and the loop over
    the walk's blocks."""
    kernel = _kernel_jaxpr(28, 4, 128)
    top = [e.primitive.name for e in kernel.eqns]
    assert top.count("while") == 1 and "scan" not in top
    assert "cond" not in top  # nothing at top level is skipped
    walk = next(e for e in kernel.eqns if e.primitive.name == "while")
    assert _stream_of_one_walk(walk.params["body_jaxpr"].jaxpr) == 1


def test_a_tall_rung_chooses_its_body_once_a_program():
    """On a rung that holds whole stretches the program runs ONE of two
    bodies, chosen by a scalar test on `q_start` / `q_len` in SMEM: its
    tiles' walks in a loop (the walk over a tile's sequences inside it),
    or one tall walk — which, where the program is the launch's first,
    starts the ring's two slots itself. Both stream their pages the same
    way."""
    kernel = _kernel_jaxpr(28, 4, 128, T=2 * TALL)
    top = [e.primitive.name for e in kernel.eqns]
    assert top.count("cond") == 2 and "while" not in top and "scan" not in top

    def taken(cond):  # the branch that holds the body
        return max((b.jaxpr for b in cond.params["branches"]),
                   key=lambda j: len(j.eqns))

    tiles, tall = (taken(e) for e in kernel.eqns
                   if e.primitive.name == "cond")
    assert [e.primitive.name for e in tiles.eqns].count("scan") == 1
    tile = next(e for e in tiles.eqns if e.primitive.name == "scan")
    walks = [e for e in tile.params["jaxpr"].jaxpr.eqns
             if e.primitive.name == "while"]
    assert len(walks) == 1
    assert _stream_of_one_walk(walks[0].params["body_jaxpr"].jaxpr) == 1
    assert _stream_of_one_walk(tall) == 3  # + the launch's first blocks


def test_forward_ragged_matches_bucketed_composition(tiny_cfg, tiny_params):
    """ONE mixed forward_ragged dispatch (decode row for seq A + full
    prefill span for seq B) must reproduce the bucketed composition
    forward_decode(A) then forward_prefill(B): same logits, same cache
    writes, same greedy argmax."""
    cfg, params = tiny_cfg, tiny_params
    PS, MP = 8, 8
    shape = (cfg.num_layers, 64 * PS, cfg.num_kv_heads * cfg.head_dim)
    rng = np.random.default_rng(3)
    a = kvc.PageAllocator(64, PS, MP)
    pagesA, pagesB = a.alloc(12), a.alloc(6)
    ptA = kvc.make_page_table_row(pagesA, MP)
    ptB = kvc.make_page_table_row(pagesB, MP)
    promptA = rng.integers(1, cfg.vocab_size, size=11).astype(np.int32)
    promptB = rng.integers(1, cfg.vocab_size, size=5).astype(np.int32)

    def prefill_a():
        kc = jnp.zeros(shape, jnp.float32)
        vc = jnp.zeros(shape, jnp.float32)
        _, kc, vc = llama.forward_prefill(
            params, cfg, jnp.asarray(promptA)[None], jnp.array([11]),
            kc, vc, jnp.asarray(ptA)[None], PS)
        return kc, vc

    kc, vc = prefill_a()
    logA_ref, kc_ref, vc_ref = llama.forward_decode(
        params, cfg, jnp.array([7], jnp.int32), jnp.array([11], jnp.int32),
        kc, vc, jnp.asarray(ptA)[None], PS, attn_impl="jnp")
    logB_ref, kc_ref, _ = llama.forward_prefill(
        params, cfg, jnp.asarray(promptB)[None], jnp.array([5]),
        kc_ref, vc_ref, jnp.asarray(ptB)[None], PS)

    kc2, vc2 = prefill_a()
    tokens = np.concatenate([[7], promptB]).astype(np.int32)
    tok_seq = np.array([0] + [1] * 5, np.int32)
    tok_pos = np.array([11, 0, 1, 2, 3, 4], np.int32)
    pt = np.stack([ptA, ptB])
    ws = np.array([pt[s][p // PS] * PS + p % PS
                   for s, p in zip(tok_seq, tok_pos)], np.int32)
    logits, kc2, _ = llama.forward_ragged(
        params, cfg, jnp.asarray(tokens), jnp.asarray(tok_seq),
        jnp.asarray(tok_pos), jnp.asarray(ws),
        jnp.asarray(np.array([0, 5], np.int32)), kc2, vc2,
        jnp.asarray(pt), jnp.asarray(np.array([0, 1], np.int32)),
        jnp.asarray(np.array([1, 5], np.int32)),
        jnp.asarray(np.array([12, 5], np.int32)), PS, attn_impl="jnp")

    np.testing.assert_allclose(np.asarray(logits[0]),
                               np.asarray(logA_ref[0]),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(logits[1]),
                               np.asarray(logB_ref[0]),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(kc2), np.asarray(kc_ref),
                               rtol=2e-4, atol=2e-4)
    assert int(jnp.argmax(logits[0])) == int(jnp.argmax(logA_ref[0]))
    assert int(jnp.argmax(logits[1])) == int(jnp.argmax(logB_ref[0]))


def test_forward_ragged_pallas_interpret_matches_jnp(tiny_cfg, tiny_params):
    """forward_ragged(attn_impl='pallas') == forward_ragged('jnp') via the
    interpret-mode kernel (compiled path needs a TPU)."""
    import ollamamq_tpu.ops.pallas.ragged_attention as ra

    cfg, params = tiny_cfg, tiny_params
    PS, MP = 8, 8
    shape = (cfg.num_layers, 64 * PS, cfg.num_kv_heads * cfg.head_dim)
    rng = np.random.default_rng(5)
    a = kvc.PageAllocator(64, PS, MP)
    pages = [a.alloc(10), a.alloc(4)]
    pt = np.stack([kvc.make_page_table_row(p, MP) for p in pages])
    tokens = rng.integers(1, cfg.vocab_size, size=13).astype(np.int32)
    tok_seq = np.array([0] * 9 + [1] * 4, np.int32)
    tok_pos = np.concatenate([np.arange(9), np.arange(4)]).astype(np.int32)
    ws = np.array([pt[s][p // PS] * PS + p % PS
                   for s, p in zip(tok_seq, tok_pos)], np.int32)
    meta = dict(
        last_idx=jnp.asarray(np.array([8, 12], np.int32)),
        page_table=jnp.asarray(pt),
        q_start=jnp.asarray(np.array([0, 9], np.int32)),
        q_len=jnp.asarray(np.array([9, 4], np.int32)),
        kv_len=jnp.asarray(np.array([9, 4], np.int32)),
    )

    orig = ra.ragged_paged_attention_pallas
    ra.ragged_paged_attention_pallas = (
        lambda *args, **kw: orig(*args, **{**kw, "interpret": True}))
    try:
        outs = {}
        for impl in ("jnp", "pallas"):
            kc = jnp.zeros(shape, jnp.float32)
            vc = jnp.zeros(shape, jnp.float32)
            logits, _, _ = llama.forward_ragged(
                params, cfg, jnp.asarray(tokens), jnp.asarray(tok_seq),
                jnp.asarray(tok_pos), jnp.asarray(ws), meta["last_idx"],
                kc, vc, meta["page_table"], meta["q_start"],
                meta["q_len"], meta["kv_len"], PS, attn_impl=impl)
            outs[impl] = np.asarray(logits)
    finally:
        ra.ragged_paged_attention_pallas = orig
    np.testing.assert_allclose(outs["pallas"], outs["jnp"],
                               rtol=5e-5, atol=5e-5)
