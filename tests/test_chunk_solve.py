"""The chunked rule's window solve as a kernel (ops/pallas/chunk_solve.py), in
interpret mode on the CPU: `chunk_solve_pallas` against `gated_delta._prepare`
/ `_prepare_vector` on every result, laid out as `chunk_rule_pallas` reads
them, on the windows a span touches — at toy shapes of the three callers'
kinds and at their published widths —; `gated_delta.ragged(impl="pallas")`
(solve kernel + pair kernel) against the jnp path end to end; the shapes the
kernel takes; and the step sample's count of the windows solved.

What interpret mode cannot show — Mosaic's own lowering of the body, and what
a launch costs — `tests/test_chip_compile_kda.py` (the AOT compile at the
published widths) and `scripts/chunk_rule_bench.py --solve` (on the chip,
against `_prepare`, exit 1 where further than 1e-5 of the largest entry)
hold."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ollamamq_tpu.engine import step_work
from ollamamq_tpu.ops import gated_delta as gd
from ollamamq_tpu.ops.pallas import chunk_rule, chunk_solve
from test_chunk_rule import T, _RAGGED, inputs, stream
from test_lfm2 import close

C = gd.CHUNK
# (H, Hk, dk, dv), the decay's reading, its strength a token: a toy shape of
# each caller's kind.
KINDS = {
    "qwen3_next": ((4, 2, 16, 32), "scalar", 0.5),  # Hk < H
    "olmo_hybrid": ((8, 8, 8, 48), "scalar", 0.5),  # 8 heads a lane group
    "kimi_linear": ((4, 4, 16, 32), "vector", 0.5),
    "kimi_linear_strong_decay": ((4, 4, 16, 32), "vector", 20.0),
    # (a key head of one whole lane tile, as the published one is)
    "kimi_linear_whole_lane_tiles": ((2, 2, 128, 64), "vector", 20.0),
}
# (each row's span, the rows whose span opens their state)
STREAMS = {
    "a_window_shared_by_two_rows": ([50, 60], [1]),
    "a_span_that_starts_and_ends_inside_a_window": ([1, 1, 1, 40, 1], [3]),
    "padding_and_one_token_rows_inside_a_window": ([20, 1, 1, 30], [0]),
    "spans_that_fill_whole_windows": ([64, 64], [0]),
    "no_span": ([1, 1, 0, 1], [1]),
}


def gated(kind, seed=0):
    """`test_chunk_rule.inputs` at the kind's shape, g at its reading: a
    decay a key channel from a thousandth of its strength to all of it, a
    token its own."""
    shape, reading, strength = KINDS[kind]
    q, k, v, g, beta, state = inputs(shape + (False,), seed)
    if reading == "vector":
        dk = shape[2]
        rng = np.random.default_rng(seed + 1)
        g = jnp.asarray(-rng.uniform(0.5, 1, size=(T, shape[0], dk))
                        * strength * 10.0 ** np.linspace(-3, 0, dk),
                        jnp.float32)
    return q, k, v, g, beta, state


@jax.jit
def _both(q, k, v, g, beta, tok_seq, tok_pos, q_len):
    """(the kernel's results, `_prepare`'s laid out alike, the windows a
    span touches) on a stream: `ragged`'s step 2."""
    part = (q_len > 1)[tok_seq] & (tok_pos >= 0)

    def cut(x):
        return x.reshape(T // C, C, *x.shape[1:])

    row_of = cut(jnp.where(part, tok_seq, -1))
    same = (row_of[:, :, None] == row_of[:, None, :]) \
        & jnp.tril(jnp.ones((C, C), bool))
    g = cut(jnp.where(part[(slice(None),) + (None,) * (g.ndim - 1)], g, 0.0))
    beta = cut(jnp.where(part[:, None], beta, 0.0))
    got = chunk_solve.chunk_solve_pallas(
        *gd._operands(cut(q), cut(k), v.shape[-2], False), cut(v), g, beta,
        row_of, interpret=True)
    want = chunk_solve.laid_out(
        gd._prepare(cut(q), cut(k), cut(v), g, beta, same))
    return got, want, jnp.any(row_of >= 0, axis=1)


def held(got, want, at):
    """Every result — u; [w; qg]; [attn; k^T]; gc — finite and `_prepare`'s
    at windows `at`, to 1e-5 of the largest entry (of 1, for a smaller)."""
    for name, x in want.items():
        a, b = np.asarray(got[name])[at], np.asarray(x)[at]
        assert np.isfinite(a).all(), name
        assert np.abs(a - b).max() <= 1e-5 * max(np.abs(b).max(), 1), name


@pytest.mark.parametrize("which", STREAMS)
@pytest.mark.parametrize("kind", KINDS)
def test_the_kernel_is_prepare_on_the_windows_a_span_touches(kind, which):
    """…and it solves no other: a window nothing touches keeps what the
    buffer held (interpret mode: not `_prepare`'s k^T), a stream with no
    span runs one program, on window 0."""
    (h, _, dk, dv), reading, _ = KINDS[kind]
    assert chunk_solve.blocks(h, dk, dv, False, reading == "vector")
    q, k, v, g, beta, _ = gated(kind)
    _, tok_seq, tok_pos, _, q_len, _ = stream(*STREAMS[which])
    got, want, touched = _both(q, k, v, g, beta, tok_seq, tok_pos, q_len)
    touched = np.asarray(touched)
    solved = touched if touched.any() else np.arange(T // C) < 1
    held(got, want, solved)
    assert which != "no_span" or not touched.any()
    assert not solved.all()
    assert not np.allclose(np.asarray(got["on_v"])[~solved],
                           np.asarray(want["on_v"])[~solved])


@pytest.mark.parametrize("which", [
    "a_window_shared_by_two_rows", "no_span",
    "padding_and_one_token_rows_inside_a_window"])
@pytest.mark.parametrize("kind", KINDS)
def test_ragged_with_both_kernels_is_the_jnp_path(kind, which):
    """`ragged` end to end, the solve kernel feeding the pair kernel: the
    outputs and every state row are the jnp path's (the XLA solve and the
    pair loop) to the tolerance tests/test_chunk_rule.py holds the pair
    kernel to."""
    q, k, v, g, beta, state0 = gated(kind, 2)
    args = (q, k, v, g, beta, state0, jnp.int32(1), *stream(*STREAMS[which]))
    o_jnp, s_jnp = _RAGGED(*args)
    o, s = _RAGGED(*args, impl="pallas", interpret=True)
    assert bool(jnp.isfinite(o).all() & jnp.isfinite(s).all())
    close(o, np.asarray(o_jnp), atol=5e-6)
    close(s, np.asarray(s_jnp), atol=5e-6)


def test_ragged_without_the_solver_still_feeds_the_pair_kernel(monkeypatch):
    """A shape `chunk_solve.blocks` refuses keeps the XLA solve in front of
    the pair kernel (what PR 62 left), to the same results."""
    q, k, v, g, beta, state0 = gated("qwen3_next", 3)
    args = (q, k, v, g, beta, state0, jnp.int32(1),
            *stream(*STREAMS["a_window_shared_by_two_rows"]))
    ragged = jax.jit(gd.ragged, static_argnames=("impl", "interpret"))
    o, s = ragged(*args, impl="pallas", interpret=True)
    monkeypatch.setattr(chunk_solve, "blocks", lambda *a: None)
    jaxpr = str(jax.make_jaxpr(lambda *a: gd.ragged(
        *a, impl="pallas", interpret=True))(*args))
    assert "chunk_solve_pallas" not in jaxpr and "chunk_rule_pallas" in jaxpr
    o_xla, s_xla = jax.jit(lambda *a: gd.ragged(
        *a, impl="pallas", interpret=True))(*args)
    close(o, np.asarray(o_xla), atol=5e-6)
    close(s, np.asarray(s_xla), atol=5e-6)


# (H, Hk, dk, dv, a decay a key channel) of the three published callers ->
# (heads a lane group, heads a block).
PUBLISHED = {
    "qwen3_next": ((32, 16, 128, 128, False), (1, 16)),
    "olmo_hybrid": ((30, 30, 96, 192, False), (2, 10)),
    "kimi_linear": ((32, 32, 128, 128, True), (1, 16)),
}


@pytest.mark.parametrize("model", PUBLISHED)
def test_the_published_shapes_take_the_kernel(model):
    """Which shapes take the kernel is a function of (H, dk, dv, plain, the
    decay's reading), the pair kernel's takes them too, a block's lanes are
    whole tiles and its buffers fit the budget; and at those widths the
    kernel is `_prepare` on a window two rows share (one window: interpret
    mode pays a head at a time)."""
    (h, hk, dk, dv, vector), want = PUBLISHED[model]
    hg, hb = chunk_solve.blocks(h, dk, dv, False, vector)
    assert (hg, hb) == want and chunk_rule.blocks(h, dk, dv, False, vector)
    assert (hg * dv) % 128 == 0 and h % hb == 0 and hb % hg == 0
    assert chunk_solve._block_bytes(hb, dk, dv, vector) \
        <= chunk_solve.VMEM_BYTES
    rng = np.random.default_rng(5)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    row_of = jnp.asarray(np.repeat([-1, 0, 1, -1], [3, 30, 25, 6])[None],
                         jnp.int32)
    live = (row_of >= 0)[..., None]
    g = -jnp.abs(f(1, C, h, dk)) * 10.0 ** jnp.linspace(-3, 1.3, dk) \
        if vector else -jnp.abs(f(1, C, h)) * 0.3
    g = jnp.where(live[..., None] if vector else live, g, 0.0)
    beta = jnp.where(live, jax.nn.sigmoid(f(1, C, h)) * 2, 0.0)
    q, k, v = f(1, C, hk, dk), f(1, C, hk, dk) + 1, f(1, C, h, dv)
    same = (row_of[:, :, None] == row_of[:, None, :]) \
        & jnp.tril(jnp.ones((C, C), bool))
    got = chunk_solve.chunk_solve_pallas(
        *gd._operands(q, k, h, False), v, g, beta, row_of, interpret=True)
    want = chunk_solve.laid_out(jax.jit(gd._prepare)(q, k, v, g, beta, same))
    held(got, want, np.ones(1, bool))


@pytest.mark.parametrize("shape", [
    (32, 128, 128, True, False), (32, 256, 128, True, False),
    (4, 12, 32, False, False), (32, 8192, 128, False, False)
    ], ids=["minicpm_sala_plain", "falcon_h1_plain",
            "key_dim_off_the_sublanes", "no_block_fits"])
def test_a_shape_the_kernel_does_not_solve_keeps_prepare(shape):
    """`plain` has no solve (u = beta v); the others `_prepare` solves."""
    assert chunk_solve.blocks(*shape) is None


@pytest.mark.parametrize("seed", range(4))
def test_the_sample_counts_the_windows_solved(seed):
    """`step_work.rule_counts`' last count: with the kernels the windows a
    span of the step's composition touches (`chunk_solve_pallas`'s own
    `touched`, from `ragged`'s `row_of`; never under the spans' (row,
    window) pairs less the windows two rows share, never over the stream's
    windows), without them every window of the padded stream; a scan has
    none."""
    rng = np.random.default_rng(seed)
    tokens = []
    while sum(tokens) < 300:
        tokens.append(int(rng.choice([1, 1, 1, 2, 5, 63, 64, 65, 130])))
    t = 512
    row_of = np.full(t, -1)
    at = np.cumsum(tokens) - tokens
    for b, (s, n) in enumerate(zip(at, tokens)):
        if n > 1:
            row_of[s:s + n] = b
    touched = int((row_of.reshape(-1, C) >= 0).any(axis=1).sum())
    step = step_work.Step(tokens, [n + 3 for n in tokens], None, False, t, 0,
                          step_work.KernelCounts(
                              None, solved_windows=chunk_solve.solved_windows))
    counts = step_work.rule_counts(None, 32, step)
    assert counts[-1] == touched <= t // C
    assert 0 < counts[-1] <= counts[4]
    assert step_work.rule_counts(None, 32, step._replace(kernels=None))[
        -1] == t // C
    assert step_work.rule_counts(
        None, 32, step._replace(scan=True))[-2:] == (0, 0)
    ones = step._replace(tokens=[1] * 7, kv=[9] * 7)
    assert step_work.rule_counts(None, 32, ones)[-2:] == (0, 0)

