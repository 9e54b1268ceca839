"""Both paged-attention kernels at MiMo-V2-Flash's head shapes (PR 65), in
interpret mode on the CPU against their jnp twins: key heads of 192 lanes
beside value heads of 128 over pools whose K rows are stored split (`[Hk x 128
| Hk x 64]`: ops/attention.py:lay_heads; `kv_contract.MxuSplit`), at group 16
(the full layers') and at group 8 under a window of 128 with a sink (the
window layers'), ragged (tiles of 8 and the tall trip) and decode — float32
pools, so the two orders of summation agree to ~1e-6 of outputs of order 1
(ATOL 2e-5) — and bf16 pools within the rounding of P (`kv_contract.py`: "P
into P.V"). And the guard of everything else: with a value head as wide as
the key head and no sink, a launch is what it was before either existed."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ollamamq_tpu.ops import attention as A
from ollamamq_tpu.ops.pallas import kv_contract
from ollamamq_tpu.ops.pallas.paged_attention import \
    paged_decode_attention_pallas
from ollamamq_tpu.ops.pallas.ragged_attention import \
    ragged_paged_attention_pallas
from test_ragged_attention import _count, _walk

PS, MP, HD, VD = 32, 24, 192, 128
ATOL = 2e-5
# (heads, kv heads, window, sink): the full layers' group of 16, the window
# layers' group of 8 — at fewer kv heads than published, the same tiles
SHAPES = {"full_g16": (32, 2, 0, False), "window_g8_sink": (16, 2, 128, True)}
# spans (q_len, kv_len) of a step: decode rows beside a chunk
SPANS = [(1, 70), (150, 400), (1, 300)]


def _case(H, Hk, window, sink, t_pad, spans, dtype, seed=0):
    rng = np.random.default_rng(seed)
    B = len(spans)
    pages = B * MP + 1
    k = jnp.asarray(rng.normal(size=(2, pages * PS, Hk, HD)), dtype)
    v = jnp.asarray(rng.normal(size=(2, pages * PS, Hk, VD)), dtype)
    kc, vc = A.lay_heads(k), A.lay_heads(v)
    assert (kc.shape[-1], vc.shape[-1]) == (Hk * HD, Hk * VD)
    table = rng.permutation(np.arange(1, pages)).reshape(B, MP)
    q_len = np.array([s[0] for s in spans], np.int32)
    kv = np.array([s[1] for s in spans], np.int32)
    q_start = (np.cumsum(q_len) - q_len).astype(np.int32)
    tok_seq = np.zeros(t_pad, np.int32)
    tok_pos = -np.ones(t_pad, np.int32)
    for b in range(B):
        at = slice(q_start[b], q_start[b] + q_len[b])
        tok_seq[at], tok_pos[at] = b, kv[b] - q_len[b] + np.arange(q_len[b])
    extra = {}
    if window:  # (the table from position 0 on: the mask alone)
        extra.update(window=window, pos_base=jnp.zeros((B,), jnp.int32))
    if sink:
        extra["sink"] = jnp.asarray(2 * rng.normal(size=(H,)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(t_pad, H, HD)), dtype)
    qd = jnp.asarray(rng.normal(size=(B, H, HD)), dtype)
    meta = tuple(map(jnp.asarray, (table.astype(np.int32), tok_seq, tok_pos,
                                   kv, q_start, q_len)))
    return q, qd, kc, vc, meta, extra, tok_pos >= 0


@pytest.mark.parametrize("t_pad,spans", [(16, [(1, 70), (5, 133), (1, 300)]),
                                         (192, SPANS)],
                         ids=["tiles_of_8", "tall_trip"])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_the_ragged_kernel_agrees_with_its_jnp_twins(shape, t_pad, spans):
    H, Hk, window, sink = SHAPES[shape]
    q, _, kc, vc, meta, extra, live = _case(H, Hk, window, sink, t_pad,
                                            spans, jnp.float32)
    table, tok_seq, tok_pos, kv, q_start, q_len = meta
    want = A.ragged_paged_attention(q, kc, vc, 1, table, tok_seq, tok_pos,
                                    kv, PS, **extra)
    walk = A.ragged_paged_attention_blockwise(
        q, kc, vc, 1, table, tok_seq, tok_pos, kv, PS, **extra)
    got = ragged_paged_attention_pallas(q, kc, vc, 1, table, q_start, q_len,
                                        kv, PS, interpret=True, **extra)
    assert got.shape == (t_pad, H, VD)  # the value head's width
    np.testing.assert_allclose(np.asarray(walk)[live], np.asarray(want)[live],
                               atol=ATOL, rtol=0)
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               atol=ATOL, rtol=0)
    if sink:  # ...and the sink is there: without it the outputs are others
        bare = {k: v for k, v in extra.items() if k != "sink"}
        other = ragged_paged_attention_pallas(
            q, kc, vc, 1, table, q_start, q_len, kv, PS, interpret=True,
            **bare)
        assert np.abs(np.asarray(other - got))[live].max() > 1e-2


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_the_decode_kernel_agrees_with_its_jnp_twin(shape):
    H, Hk, window, sink = SHAPES[shape]
    _, qd, kc, vc, meta, extra, _ = _case(H, Hk, window, sink, 192, SPANS,
                                          jnp.float32)
    table, kv = meta[0], meta[3]
    want = A.paged_decode_attention(qd, kc, vc, 1, table, kv, PS, **extra)
    got = paged_decode_attention_pallas(qd, kc, vc, 1, table, kv, PS,
                                        interpret=True, **extra)
    assert got.shape == (len(SPANS), H, VD)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=ATOL,
                               rtol=0)


def test_bfloat16_pools_agree_within_the_rounding_of_p():
    """The served dtype: P enters P.V rounded once to bf16 (2**-8 of sum(p
    |v|) / sum(p)) and the output is stored in bf16 (2**-8 of itself):
    outputs of order 1 agree with the float32 twin to ~1e-2."""
    H, Hk, window, sink = SHAPES["window_g8_sink"]
    q, qd, kc, vc, meta, extra, live = _case(H, Hk, window, sink, 192, SPANS,
                                             jnp.bfloat16)
    table, tok_seq, tok_pos, kv, q_start, q_len = meta
    want = A.ragged_paged_attention(q, kc, vc, 1, table, tok_seq, tok_pos,
                                    kv, PS, **extra).astype(jnp.float32)
    got = ragged_paged_attention_pallas(
        q, kc, vc, 1, table, q_start, q_len, kv, PS, interpret=True,
        **extra).astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               atol=3e-2, rtol=0)


def test_what_the_split_layout_cannot_serve_is_refused_by_name():
    for kw in (dict(num_kv_heads=1), dict(head_dim=176),
               dict(v_dim=64), dict(num_kv_heads=16)):
        shape = dict(rows=8, group=8, num_kv_heads=4, head_dim=192,
                     page_size=32, v_dim=128)
        with pytest.raises(ValueError, match="split layout"):
            kv_contract.make_inner(None, **dict(shape, **kw))
    with pytest.raises(ValueError, match="Mxu inner product"):
        kv_contract.make_inner("vpu", rows=1, group=1, num_kv_heads=4,
                               head_dim=128, page_size=32, sink=True)


# ------------------------------------- ...and every other model's launches
def _launch(launch, H, Hk, hd, T, window=0, vd=None, sink=False):
    """The `pallas_call` equation of one launch, traced: shapes only."""
    def s(*shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt)

    B, mp = 16, 8
    k = s(2, 34 * PS, Hk * hd, dt=jnp.bfloat16)
    v = s(2, 34 * PS, Hk * (vd or hd), dt=jnp.bfloat16)
    extra = [s(B)] if window else []
    extra += [s(H, dt=jnp.float32)] if sink else []

    def run(q, kc, vc, layer, table, *meta):
        meta, more = list(meta), {}
        if sink:
            more["sink"] = meta.pop()
        if window:
            more.update(window=window, pos_base=meta.pop())
        return launch(q, kc, vc, layer, table, *meta, PS, **more)

    ragged = launch is ragged_paged_attention_pallas
    closed = jax.make_jaxpr(run)(
        s(T if ragged else B, H, hd, dt=jnp.bfloat16), k, v, s(), s(B, mp),
        *([s(B)] * (3 if ragged else 1)), *extra)
    (call,) = [e for e in _walk(closed.jaxpr)
               if e.primitive.name == "pallas_call"]
    return call


# (heads, kv heads, head_dim) the benchmark's other cells launch, with the
# inner product's lane tiles, rows a tile (Mp) and tile width each has.
RUNS_TODAY = {(28, 4, 128): (4, 64, 128), (64, 8, 128): (8, 64, 128),
              (32, 8, 64): (4, 64, 128), (16, 2, 256): (2, 64, 256)}


@pytest.mark.parametrize("window", [0, 128], ids=["full", "window"])
@pytest.mark.parametrize("T", [64, 512], ids=["tiles", "tall"])
@pytest.mark.parametrize("shape", sorted(RUNS_TODAY),
                         ids=lambda s: "x".join(map(str, s)))
def test_a_launch_with_k_as_wide_as_v_and_no_sink_is_what_it_was(shape, T,
                                                                 window):
    """The four head shapes the benchmark already runs: the ragged launch
    has the six scalar-prefetch operands (seven with a window), q and the two
    pools — NO sink operand; the grid, the q/o block and the scratch shapes
    stated here; and two contractions a lane tile a body (q . k and p . v:
    NO second key tile)."""
    H, Hk, hd = shape
    tiles, mp, width = RUNS_TODAY[shape]
    call = _launch(ragged_paged_attention_pallas, H, Hk, hd, T, window)
    subs = kv_contract.programs_height(T) // kv_contract.G_TILE
    n_scalar = 6 + bool(window)
    assert len(call.invars) == n_scalar + 3
    assert [v.aval.shape for v in call.invars[n_scalar:]] == [
        (T // 8, tiles, mp, width),
        (2, 34 * PS, Hk * hd), (2, 34 * PS, Hk * hd)]
    assert call.outvars[0].aval.shape == call.invars[n_scalar].aval.shape
    gm = call.params["grid_mapping"]
    assert gm.grid == (T // (8 * subs),)
    assert gm.num_index_operands == n_scalar
    body = call.params["jaxpr"]
    refs = [v.aval for v in body.invars[n_scalar:]]
    held = (tiles, subs, mp)
    assert [r.shape for r in refs] == [
        (subs, tiles, mp, width),                      # q block
        (2, 34 * PS, Hk * hd), (2, 34 * PS, Hk * hd),  # the pools, in HBM
        (subs, tiles, mp, width),                      # o block: q's
        (2, 128, Hk * hd), (2, 128, Hk * hd),          # the ring of blocks
        held + (width,), held + (128,), held + (128,),  # acc, m, l
        (2, 2), (1,)]                                  # semaphores, position
    eqns = list(_walk(body))
    bodies = 1 if subs == 1 else 2  # (the tall trip: one more copy a tile)
    assert _count(eqns, "dot_general") == 2 * tiles * bodies
    # ...and the decode kernel's launch: three scalars, q, the pools
    call = _launch(paged_decode_attention_pallas, H, Hk, hd, T, window)
    n_scalar = 3 + bool(window)
    assert len(call.invars) == n_scalar + 3
    assert call.outvars[0].aval.shape == call.invars[n_scalar].aval.shape
    assert _count(list(_walk(call.params["jaxpr"])),
                  "dot_general") == 2 * tiles


@pytest.mark.parametrize("Hk,window,sink", [(4, 0, False), (8, 128, True)],
                         ids=["full", "window_sink"])
def test_the_new_head_shape_launches_what_the_docstring_says(Hk, window,
                                                             sink):
    """64 heads of 192 / 128 lanes: a lane tile a kv head, q 256 lanes wide
    (the head's first 128 and the tile its rest lies in), the output 128;
    THREE contractions a tile a body (q . k twice, p . v once); the sink one
    more VMEM operand, `[tiles, Mp, 128]` float32, behind q."""
    H, T = 64, 512
    call = _launch(ragged_paged_attention_pallas, H, Hk, HD, T, window, VD,
                   sink)
    n_scalar = 6 + bool(window)
    mp = 8 * (H // Hk)
    assert [v.aval.shape for v in call.invars[n_scalar:]] == [
        (T // 8, Hk, mp, 256)] + [(Hk, mp, 128)] * sink + [
        (2, 34 * PS, Hk * HD), (2, 34 * PS, Hk * VD)]
    assert call.outvars[0].aval.shape == (T // 8, Hk, mp, 128)
    eqns = list(_walk(call.params["jaxpr"]))
    assert _count(eqns, "dot_general") == 3 * Hk * 2
    assert call.params["grid_mapping"].grid == (T // 64,)
