"""The chunked rule's pair kernel (ops/pallas/chunk_rule.py), in interpret
mode on the CPU: `gated_delta.ragged(impl="pallas")` against the jnp pair loop
it replaces on the chip and against the token-serial recurrence, at toy
shapes of the four callers' kinds, on the streams a served step composes; the
shapes it takes; and the step sample's count of its pairs.

What interpret mode cannot show: an aliased output block starts as its input
there, so a kernel that never read a row's state would pass — on the chip the
block is whatever the buffer held — nor a window the pairs come back to
(`gated_delta.ragged`: rows in stream order). `scripts/chunk_rule_bench.py`
holds the kernel to the jnp path on the chip and exits 1 where it is
further than 1e-5 of the largest entry (its `shortspan` stream's state is
the one that depends on the state read)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ollamamq_tpu.engine import step_work
from ollamamq_tpu.ops import gated_delta as gd
from ollamamq_tpu.ops.pallas import chunk_rule
from test_lfm2 import close

T, ROWS, SLOTS = 192, 6, 8  # slot SLOTS is the trash row

# (H, Hk, dk, dv, plain): a toy shape of each caller's kind.
SHAPES = {
    "grouped_key_heads": (4, 2, 16, 32, False),  # Qwen3-Next: Hk < H
    "dv_no_lane_tile": (8, 8, 8, 48, False),  # Olmo-Hybrid: 8 heads a group
    "plain": (4, 4, 16, 32, True),  # the lightning layers
    "plain_b_and_c_a_group": (4, 1, 24, 32, True),  # Falcon-H1's mixer
}
# (each row's span, the rows whose span opens their state); rows with no
# token sit on the trash row.
STREAMS = {
    "one_long_span": ([150], []),
    "two_spans_meet_inside_a_window": ([70, 60], [1]),
    "one_opens_and_one_continues": ([40, 100], [0]),
    "decode_rows_between_spans": ([70, 1, 1, 50, 1, 30], [3, 4]),
    "padding": ([30, 0, 20], [2]),
    "zero_pairs": ([1, 1, 0, 1], [1]),
}


def inputs(shape, seed=0):
    """Correlated keys, strengths up to 2 and decays from 0.6 to 1 (as
    test_olmo_hybrid.py's `rule_inputs`); `plain`: q, k as a mixer hands
    them, a write strength of 1."""
    h, hk, dk, dv, plain = shape
    rng = np.random.default_rng(seed)
    f = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    q, k = f(rng.normal(size=(T, hk, dk))), f(rng.normal(size=(T, hk, dk)) + 1)
    if plain:
        q, k = q * dk ** -0.5, k * dk ** -0.5
    beta = np.ones((T, h)) if plain else rng.uniform(0, 2, size=(T, h))
    state = f(rng.normal(size=(2, SLOTS + 1, dk, h * dv)))
    return (q, k, f(rng.normal(size=(T, h, dv))),
            f(-rng.uniform(1e-3, 0.5, size=(T, h))), f(beta), state)


def stream(spans, opens):
    """(slot_ids, tok_seq, tok_pos, q_start, q_len, is_first) over ROWS rows
    and T stream tokens: rows shuffled against slots, padding behind."""
    q_len = np.zeros(ROWS, np.int32)
    q_len[:len(spans)] = spans
    q_start = np.concatenate([[0], np.cumsum(q_len)[:-1]]).astype(np.int32)
    tok_seq, tok_pos = np.zeros(T, np.int32), -np.ones(T, np.int32)
    for b, (s, n) in enumerate(zip(q_start, q_len)):
        tok_seq[s:s + n], tok_pos[s:s + n] = b, 7 + np.arange(n)
    slot_ids = np.where(q_len > 0, np.array([3, 1, 0, 6, 2, 5]), SLOTS)
    is_first = np.zeros(ROWS, np.int32)
    is_first[opens] = 1
    return tuple(jnp.asarray(a, jnp.int32) for a in (
        slot_ids, tok_seq, tok_pos, q_start, q_len, is_first))


_RAGGED = jax.jit(gd.ragged, static_argnames=("impl", "interpret", "plain"))


@jax.jit
def _serial(q, k, v, g, beta, rows0, tok_seq, tok_pos, plain):
    """The recurrence a stream token at a time, each token on its row's
    state: rows0 [ROWS, H, dk, dv]. Returns (o [T, H, dv], rows')."""
    def token(rows, x):
        q_t, k_t, v_t, g_t, b_t, row, pos = x
        s = rows[row] * jnp.exp(g_t)[:, None, None]
        r = b_t[:, None] * jnp.where(plain, v_t, v_t - jnp.einsum(
            "hkv,hk->hv", s, k_t, precision="highest"))
        s = s + k_t[:, :, None] * r[:, None, :]
        o = jnp.einsum("hkv,hk->hv", s, q_t, precision="highest")
        live = pos >= 0
        return (jnp.where(live, rows.at[row].set(s), rows),
                jnp.where(live, o, 0.0))

    return jax.lax.scan(token, rows0, (q, k, v, g, beta, tok_seq,
                                       tok_pos))[::-1]


@pytest.mark.parametrize("which", STREAMS)
@pytest.mark.parametrize("kind", SHAPES)
def test_the_kernel_is_the_pair_loop_and_the_serial_recurrence(kind, which):
    """Through `ragged` with the kernels in interpret mode: the outputs and
    the rows' states are the jnp pair loop's and — at the tolerance the
    chunked form is held to the serial scan at (test_olmo_hybrid.py) — the
    token-serial recurrence's; state rows of slots the step does not touch,
    and the other layer's, are bit-identical before and after."""
    h, hk, dk, dv, plain = shape = SHAPES[kind]
    assert chunk_rule.blocks(h, dk, dv, plain)
    q, k, v, g, beta, state0 = inputs(shape)
    meta = stream(*STREAMS[which])
    slot_ids, tok_seq, tok_pos, _, q_len, is_first = meta
    args = (q, k, v, g, beta, state0, jnp.int32(1), *meta)
    o_jnp, s_jnp = _RAGGED(*args, plain=plain)
    o, s = _RAGGED(*args, impl="pallas", interpret=True, plain=plain)
    close(o, np.asarray(o_jnp), atol=5e-6)
    close(s, np.asarray(s_jnp), atol=5e-6)
    qn, kn = gd._operands(q, k, h, plain)
    rows0 = jnp.where((is_first > 0)[:, None, None, None], 0.0,
                      gd._to_heads(state0[1, slot_ids], h))
    o_ref, rows = _serial(qn, kn, v, g, beta, rows0, tok_seq, tok_pos, plain)
    close(o, np.asarray(o_ref), atol=2e-5)
    live = np.asarray(q_len) > 0
    close(gd._to_heads(s[1, slot_ids[live]], h), np.asarray(rows)[live],
          atol=2e-5)
    idle = np.setdiff1d(np.arange(SLOTS), np.asarray(slot_ids)[live])
    assert bool(jnp.all(s[0] == state0[0]))
    assert bool(jnp.all(s[1, idle] == state0[1, idle]))


def test_head_blocks_each_carry_their_own_lanes(monkeypatch):
    """A row cut into two blocks of heads (a VMEM budget that holds four of
    eight): the pairs run once a block, each block's state in its own
    lanes."""
    shape = h, hk, dk, dv, plain = (8, 4, 8, 32, False)
    monkeypatch.setattr(chunk_rule, "VMEM_BYTES",
                        chunk_rule._block_bytes(4, dk, dv, plain))
    assert chunk_rule.blocks(h, dk, dv, plain) == (4, 4)
    chunk_rule.chunk_rule_pallas.clear_cache()
    try:
        args = (*inputs(shape, 3), jnp.int32(1),
                *stream(*STREAMS["decode_rows_between_spans"]))
        o_jnp, s_jnp = jax.jit(gd.ragged)(*args)
        o, s = jax.jit(gd.ragged, static_argnames=("impl", "interpret"))(
            *args, impl="pallas", interpret=True)
    finally:
        chunk_rule.chunk_rule_pallas.clear_cache()
    close(o, np.asarray(o_jnp), atol=5e-6)
    close(s, np.asarray(s_jnp), atol=5e-6)


# (H, dk, dv, plain) of the four published callers -> (heads a lane group,
# heads a block).
PUBLISHED = {
    "qwen3_next": ((32, 128, 128, False), (1, 16)),
    "olmo_hybrid": ((30, 96, 192, False), (2, 10)),
    "falcon_h1": ((32, 256, 128, True), (1, 8)),
    "minicpm_sala": ((32, 128, 128, True), (1, 16)),
}


@pytest.mark.parametrize("model", PUBLISHED)
def test_the_published_shapes_take_the_kernel(model):
    """Which shapes take the kernel is a function of (H, dk, dv, plain): the
    four callers' all do, a block's lanes are whole tiles and its buffers
    fit the budget the kernel asks the compiler for."""
    (h, dk, dv, plain), want = PUBLISHED[model]
    hg, hb = chunk_rule.blocks(h, dk, dv, plain)
    assert (hg, hb) == want
    assert (hg * dv) % 128 == 0 and h % hb == 0 and hb % hg == 0
    assert chunk_rule._block_bytes(hb, dk, dv, plain) <= chunk_rule.VMEM_BYTES


@pytest.mark.parametrize("shape", [(4, 12, 32, False), (32, 4096, 128, True)],
                         ids=["key_dim_off_the_sublanes", "no_block_fits"])
def test_a_shape_the_kernel_cannot_hold_keeps_the_loop(shape):
    assert chunk_rule.blocks(*shape) is None


@pytest.mark.parametrize("seed", range(4))
def test_the_sample_counts_the_pairs_the_kernel_runs(seed):
    """`step_work.slot_state_counts`' fifth count is `ragged`'s own pair
    count (`ends[-1]`) for the step's composition, and `pair_bound` holds
    it: random spans in stream order, one-token rows between them."""
    rng = np.random.default_rng(seed)
    tokens = []
    while sum(tokens) < 400:
        tokens.append(int(rng.choice([1, 1, 2, 5, 63, 64, 65, 130])))
    t, rows = 512, 64
    q_len = np.zeros(rows, np.int64)
    q_len[:len(tokens)] = tokens
    q_start = np.cumsum(q_len) - q_len
    n_w = np.where(q_len > 1, (q_start + q_len - 1) // gd.CHUNK
                   - q_start // gd.CHUNK + 1, 0)
    step = step_work.Step(tokens, [n + 3 for n in tokens], None, False, t, 0,
                          None)
    counts = step_work.slot_state_counts(None, 32, step)
    assert counts[4] == n_w.sum() > 0
    assert counts[2:4] == (tokens.count(1),
                           sum(n for n in tokens if n > 1))
    assert n_w.sum() <= chunk_rule.pair_bound(t // gd.CHUNK, rows, t)
    scan = step._replace(scan=True)
    assert step_work.slot_state_counts(None, 32, scan)[3:] == (0, 0)


def test_the_pair_bound_is_met():
    """Spans of two tokens that each straddle a window's edge, and one-token
    rows nowhere: a window and a row more is a pair more."""
    assert chunk_rule.pair_bound(8, 16, 512) == 8 + 15
    assert chunk_rule.pair_bound(1, 64, 16) == 1 + 7
    assert chunk_rule.pair_bound(1, 4, 1) == 1
