"""The ragged kernel on a rung that holds whole stretches (PR 48): the tile
follows the span. Second file of tests/test_ragged_attention.py (a file is one
worker's under `--dist loadfile`; the two kernel matrices were 580 s of one)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ollamamq_tpu.config import MODEL_CONFIGS
from ollamamq_tpu.ops.attention import (ragged_paged_attention,
                                        ragged_paged_attention_blockwise,
                                        ring_table)
from ollamamq_tpu.ops.pallas import kv_contract
from ollamamq_tpu.ops.pallas.kv_contract import TALL, tall_tokens
from ollamamq_tpu.ops.pallas.ragged_attention import (
    ragged_paged_attention_pallas)
from test_ragged_attention import (F32_TOL, LAYERS, _case, _f32,
                                   assert_kernel_close)


# The tile follows the span (PR 48): on a rung of 2 * TALL tokens or more a
# program holds TALL tokens, and one whose stretch lies inside ONE span
# walks that span's context once for all of them; every other program
# walks its tiles as before. `tall`: the stream tokens in whole stretches,
# which `kv_contract.tall_tokens` — the engine's counter — must say too.
# Pages of 8 tokens put four in a block of 32; a table of 48 pages holds
# contexts up to 384.
_DECODE5 = [(1, 9), (1, 33), (1, 64), (1, 65), (1, 100)]
TALL_CASES = {
    # 5 decode rows, then a span from mid-stretch to mid-stretch: tokens
    # [5, 205), whole over [64, 192)
    "mid-to-mid": dict(spans=_DECODE5 + [(200, 200)], tall=128),
    # the cell's step in small: the span is NOT its prompt's first chunk
    "later-chunk": dict(spans=_DECODE5 + [(187, 379)], tall=128),
    # a span of exactly TALL tokens on a stretch's edges, a shorter one
    "exactly-tall": dict(spans=[(TALL, TALL), (30, 41), (34, 34)], tall=TALL),
    # TALL - 1 tokens fill no stretch; the next span's TALL do, exactly,
    # over a cached prefix
    "one-short": dict(spans=[(TALL - 1, TALL - 1), (1, 7), (TALL, 80)],
                      tall=TALL),
    # two whole stretches and a tail of 3 that shares its tile with a
    # decode row and the launch's padding
    "twice-and-3": dict(spans=[(2 * TALL + 3, 2 * TALL + 3), (1, 3)],
                        tall=2 * TALL),
    # two spans: the first ends inside stretch 0, the second fills [64, 128)
    "one-of-two": dict(spans=[(40, 40), (100, 120)], tall=TALL),
    # the launch's FIRST program is tall (it starts the ring itself), and
    # so is its last
    "first-and-last": dict(spans=[(2 * TALL, 2 * TALL + 16)], tall=2 * TALL),
    # a rung of fewer than 2 * TALL tokens holds no tall body
    "short-rung": dict(spans=[(TALL + 8, TALL + 8), (1, 5)], tall=0),
    # 2 * TALL tokens and more, and no stretch inside one span
    "no-stretch": dict(spans=[(1, 5 + 3 * i) for i in range(70)]
                       + [(TALL - 4, TALL)], tall=0),
}
for _c in TALL_CASES.values():
    _c.update(B=len(_c["spans"]) + 2, MP=48)
TALL_SHAPES = [(28, 4, 128), (32, 8, 64), (16, 16, 128), (16, 2, 256)]
for H, Hk, hd in TALL_SHAPES:
    # at a block of 128 tokens: the span's tall walks read 2 and 3 blocks
    TALL_CASES["H%d-Hk%d-hd%d" % (H, Hk, hd)] = dict(
        spans=[(1, 1), (1, 33), (1, 128), (1, 129), (1, 200), (200, 290)],
        B=8, PS=32, MP=12, H=H, Hk=Hk, hd=hd, seed=H, tall=128)
TALL_CASES["H28-Hk4-hd128-bf16"] = dict(TALL_CASES["H28-Hk4-hd128"],
                                        dtype=jnp.bfloat16)


def _tall_case(name):
    case = dict(TALL_CASES[name])
    tall = case.pop("tall")
    return case, tall, sum(n for n, _ in case["spans"])


@pytest.mark.parametrize("name", TALL_CASES)
def test_tall_tokens_counts_the_whole_stretches(name):
    case, tall, T = _tall_case(name)
    spans = [n for n, _ in case["spans"]]
    assert tall_tokens(spans, T) == tall
    # On a rung that holds the tall body: the stretches whose every token
    # is one sequence's, counted token by token.
    seq = np.repeat(np.arange(len(spans)), spans)
    seq = np.pad(seq, (0, -len(seq) % TALL), constant_values=-1)
    stretches = seq.reshape(-1, TALL)
    assert tall_tokens(spans, max(T, 2 * TALL)) == TALL * int(
        ((stretches == stretches[:, :1]).all(1) & (stretches[:, 0] >= 0)
         ).sum())


@pytest.mark.parametrize("name", TALL_CASES)
def test_step_sample_carries_attn_tall_tokens(name):
    """`StepWork.note` puts the kernel's own count on the step's sample and
    the /metrics series, beside `attn_pairs`: a ragged step's, where the
    kernel serves; nothing tall in a fused scan or on the jnp path."""
    import types

    from ollamamq_tpu.engine.step_work import KernelCounts, StepWork
    from ollamamq_tpu.telemetry import schema as tm

    case, tall, T = _tall_case(name)
    spans, kv = zip(*case["spans"])
    series = [c.labels(model="tall-" + name) for c in (
        tm.ATTN_PAIRS_TOTAL, tm.ATTN_CTX_ROWS_TOTAL,
        tm.ATTN_TALL_TOKENS_TOTAL)]
    work = StepWork(MODEL_CONFIGS["test-tiny"], 8, "tall-" + name,
                    KernelCounts(tall_tokens))
    noted = {}
    sp = types.SimpleNamespace(note=noted.update)
    work.note(sp, list(spans), list(kv), stream_len=T)
    assert noted["attn_tall_tokens"] == tall
    assert noted["attn_ctx_rows"] == sum(kv)
    assert series[2].value == tall
    work.note(sp, list(spans), list(kv), scan=True)
    assert noted["attn_tall_tokens"] == 0
    # the jnp path: no kernel, nothing tall
    StepWork(MODEL_CONFIGS["test-tiny"], 8, "tall-" + name).note(
        sp, list(spans), list(kv), stream_len=T)
    assert noted["attn_tall_tokens"] == 0 and series[2].value == tall


@pytest.mark.parametrize("layer", range(LAYERS))
@pytest.mark.parametrize("name", TALL_CASES)
def test_tall_stretches_match_reference(name, layer, poison_trash_page):
    case, _, _ = _tall_case(name)
    q, k, v, pt, tok_seq, tok_pos, kv_len, qs, ql, PS = _case(**case)
    clean = ragged_paged_attention_pallas(q, k, v, layer, pt, qs, ql,
                                          kv_len, PS, interpret=True)
    # A tall walk's last block, too, reads the trash page past the span's
    # last page.
    out = ragged_paged_attention_pallas(
        q, poison_trash_page(k, PS, layer), poison_trash_page(v, PS, layer),
        layer, pt, qs, ql, kv_len, PS, interpret=True)
    np.testing.assert_array_equal(np.asarray(_f32(out)),
                                  np.asarray(_f32(clean)))
    assert_kernel_close(out, q.dtype, v, lambda v: ragged_paged_attention(
        _f32(q), _f32(k), v, layer, pt, tok_seq, tok_pos, kv_len, PS))


@pytest.mark.parametrize("name", TALL_CASES)
def test_tokens_outside_a_whole_stretch_keep_every_bit(name, monkeypatch):
    """A stream with no whole stretch gives bit for bit what the kernel
    gave when every program was one tile (its parent's body: TALL beyond
    every rung) — and in a stream that has one, so does every token
    outside it: decode rows, a span's head and tail. Inside, the rows of
    TALL tokens share one contraction where 8 did; what a row sees is the
    same pairs, in a matmul of another height."""
    case, _, T = _tall_case(name)
    q, k, v, pt, _, _, kv_len, qs, ql, PS = _case(**case)
    out = ragged_paged_attention_pallas(q, k, v, 1, pt, qs, ql, kv_len, PS,
                                        interpret=True)
    monkeypatch.setattr(kv_contract, "TALL", 1 << 30)  # no rung holds one
    parent = ragged_paged_attention_pallas.__wrapped__(
        q, k, v, 1, pt, qs, ql, kv_len, PS, interpret=True)
    out, parent = np.asarray(_f32(out)), np.asarray(_f32(parent))
    tall = np.zeros(T, bool)
    if T >= 2 * TALL:
        for start, n in zip(np.asarray(qs), np.asarray(ql)):
            first = -(-start // TALL) * TALL
            tall[first:first + (start + n - first) // TALL * TALL] = True
    np.testing.assert_array_equal(out[~tall], parent[~tall])
    # Two bf16 launches round float32 values F32_TOL apart, P and output:
    # the same number or its neighbour, one spacing of 2**-7 away.
    np.testing.assert_allclose(
        out[tall], parent[tall],
        **(dict(rtol=2 ** -7 + 4e-5, atol=3e-5)
           if q.dtype == jnp.bfloat16 else F32_TOL))


# The shape `k-exaone-236b-a23b-ep8-d5.longctx`'s full walk runs at, composed
# as that cell's step is (PR 59): five decode rows over three and four
# blocks, then a 507-token span that is a later chunk of its prompt — 512
# tokens, seven whole stretches of [512, 128] row-heads a kv head — through
# the full layer's launch and a window layer's (128 positions over per-slot
# rings of window + step + a page). V is drawn around 2, not 0: an output is
# then of order 2 whatever its context, so a rounding that leans one way — a
# truncating cast of P or of the output shrinks every one by ~2**-9 — shows
# in the MEAN of the signed error, which round-to-nearest leaves at 0.
_CLAIMED = dict(
    spans=[(1, 257), (1, 300), (1, 384), (1, 385), (1, 450), (507, 640)],
    B=8, PS=32, MP=20, H=64, Hk=8, hd=128, seed=64, dtype=jnp.bfloat16)
_WINDOW = 128
_RING_ROWS = _WINDOW + 512 + 32


def _claimed(window):
    """`_CLAIMED`'s batch as the full layer's launch reads it, or a window
    layer's — K and V in per-slot rings whose rows hold seeded values:
    (the case, k, v, the page table, each row's first listed position)."""
    case = _case(**_CLAIMED)
    q, k, v, pt, _, _, kv_len, _, ql, PS = case
    T, B, base = q.shape[0], ql.shape[0], None
    if window:
        rng = np.random.default_rng(65)
        k, v = (jnp.asarray(rng.standard_normal(
            (LAYERS, (B + 1) * _RING_ROWS, k.shape[-1])), k.dtype)
            for _ in range(2))
        pt, base = ring_table(jnp.arange(B, dtype=jnp.int32), kv_len, ql,
                              window, _RING_ROWS, PS, T)
    return case, k, v, pt, base


@pytest.mark.parametrize("window", [0, _WINDOW], ids=["full", "window"])
def test_claimed_shape_keeps_two_roundings_and_no_bias(window):
    case, k, v, pt, base = _claimed(window)
    q, _, _, _, tok_seq, tok_pos, kv_len, qs, ql, PS = case
    T = q.shape[0]
    assert tall_tokens([n for n, _ in _CLAIMED["spans"]], T) == 7 * TALL
    v = (_f32(v) + 2).astype(v.dtype)
    out = ragged_paged_attention_pallas(q, k, v, 1, pt, qs, ql, kv_len, PS,
                                        interpret=True, window=window,
                                        pos_base=base)

    @jax.jit
    def stretch(k, v, q, seq, pos):  # TALL tokens of the float32 twin
        return ragged_paged_attention_blockwise(
            _f32(q), k, v, 1, pt, seq, pos, kv_len, PS, block_pages=1,
            window=window, pos_base=base)

    def twin(v, k=_f32(k)):
        return jnp.concatenate([
            stretch(k, v, q[t:t + TALL], tok_seq[t:t + TALL],
                    tok_pos[t:t + TALL]) for t in range(0, T, TALL)])

    ref, bound = assert_kernel_close(out, q.dtype, v, twin)
    assert abs((np.asarray(_f32(out)) - ref).mean()) < bound.mean() / 10


@pytest.mark.parametrize("launch", ["64x8x128-full", "64x8x128-window",
                                    "28x4x128"])
def test_unrolled_lane_tiles_keep_every_bit_of_the_rolled_trip(launch,
                                                               monkeypatch):
    """The tall trip's lane tiles as straight-line code (PR 61: up to
    `TALL_UNROLL` = 8 of them) against the same tiles as a loop in the
    program, which is what they were beyond two: per tile the arithmetic is
    the same in the same order, tile t's state is tile t's alone — the
    launches are equal TO THE BIT, at K-EXAONE's eight tiles through both
    its launches and at Qwen2.5-7B's four. The rolled launch is the
    function under the jit, so no cache keeps a program traced under the
    patched constant."""
    window, base = _WINDOW if "window" in launch else 0, None
    if launch == "28x4x128":
        case = _case(**_tall_case("H28-Hk4-hd128-bf16")[0])
        k, v, pt = case[1:4]
    else:
        case, k, v, pt, base = _claimed(window)
    q, _, _, _, _, _, kv_len, qs, ql, PS = case
    assert 2 < k.shape[-1] // 128 <= kv_contract.TALL_UNROLL
    assert q.dtype == jnp.bfloat16
    args = (q, k, v, 1, pt, qs, ql, kv_len, PS)
    kw = dict(interpret=True, window=window, pos_base=base)
    unrolled = ragged_paged_attention_pallas(*args, **kw)
    monkeypatch.setattr(kv_contract, "TALL_UNROLL", 2)
    rolled = ragged_paged_attention_pallas.__wrapped__(*args, **kw)
    np.testing.assert_array_equal(np.asarray(_f32(unrolled)),
                                  np.asarray(_f32(rolled)))
