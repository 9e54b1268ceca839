"""tests/test_deepseek_v32.py, second file (a file is one worker's under
`--dist loadfile`): the selection's threshold and the three kernels of
ops/pallas/mla_attention.py in interpret mode against their jnp twins (a wide
span in the expanded form and the layer over it: test_deepseek_v32_wide.py)."""

import jax.numpy as jnp
import numpy as np
import pytest

from ollamamq_tpu.ops import mla


# ------------------------------------ the selection and the three kernels
@pytest.mark.parametrize("topk", [1, 16, 40, 300])
def test_the_threshold_is_the_kth_largest_exactly(topk):
    rng = np.random.default_rng(topk)
    scores = rng.standard_normal((12, 256)).astype(np.float32)
    scores[3] = np.abs(scores[3])          # one sign only
    scores[4, :100] = scores[4, 100]       # ties at the threshold
    pos = np.asarray([255, 100, 17, 200, 150, 0, -1, 39, 40, 15, 16, 255],
                     np.int32)
    thr = np.asarray(mla.select_threshold(jnp.asarray(scores),
                                          jnp.asarray(pos), topk))
    from ollamamq_tpu.ops.pallas import mla_attention as kernels

    for tile in (1, 4):
        assert np.array_equal(thr, np.asarray(kernels.dsa_select_pallas(
            jnp.asarray(scores), jnp.asarray(pos), topk, tile=tile,
            interpret=True)))
    for t, p in enumerate(pos):
        if p + 1 <= topk:
            assert thr[t] == mla.NEG_INF
        else:
            assert thr[t] == np.sort(scores[t, :p + 1])[-topk]
            assert (scores[t, :p + 1] >= thr[t]).sum() >= topk


# (spans = (tokens, context at the span's end) a row, tile, heads). With 8
# heads a token's row-heads are a whole sublane tile, and a one-token row in
# a tile of other sequences' tokens takes the kernel's path of its own. The
# attention kernel folds a tile as chains of CHAIN (8) tokens — a tile of 16
# as two halves, its own tile of 32 as four — over its own block width: the
# later mixes are what that trip can get wrong.
MIXES = {
    "prefill_and_decode": ([(40, 300), (1, 150), (1, 77), (3, 20)], 16, 4),
    "one_token_rows_alone": ([(40, 300), (1, 150), (1, 77), (3, 20), (1, 9)],
                             16, 8),
    "tiles_of_8": ([(16, 16), (1, 150), (9, 80)], 8, 8),
    "decode_rows": ([(1, 300), (1, 150), (1, 77), (1, 20), (1, 1)], 1, 4),
    "a_long_span": ([(64, 64)], 16, 4),
    # each half of the one tile is another sequence's, at other depths
    "halves_of_two_sequences": ([(8, 700), (8, 150)], 16, 8),
    # 5 live rows: the second half of the tile has none
    "a_half_with_no_live_row": ([(5, 600)], 16, 4),
    # the second tile holds 5 tokens of the span and nothing else
    "a_span_ending_inside_the_first_half": ([(21, 540)], 16, 8),
    # the deepest frontier lies inside the last page of a block
    "a_frontier_inside_a_blocks_last_page": ([(12, 1020), (4, 508)],
                                              16, 4),
    # positions 10..24 of one block: -inf thresholds up to 15, then real ones
    "crossing_index_topk_inside_one_block": ([(15, 25), (1, 16), (1, 17)],
                                             16, 8),
    # a one-token row and a span share a tile, both past two blocks
    "a_one_token_row_beside_a_span": ([(1, 700), (15, 1100)], 16, 8),
    # the decode scan's launch: a tile a token, contexts at a block's edges
    "the_scans_tiles_of_one": ([(1, 1100), (1, 513), (1, 512), (1, 40)], 1,
                               8),
    "the_trash_page_at_the_largest_finite": ([(40, 300), (1, 150), (9, 530)],
                                             16, 8),
    # the ragged step's own launch: four chains a tile, one-token rows in
    # the first, a span over two tiles, a short one ending inside a chain
    "four_chains_a_tile_of_32": ([(1, 150), (1, 77), (45, 700), (3, 20)],
                                 None, 8),
}
# What the trash page holds (latent rows; the index keys hold its negative):
# a walk's last block reads it past the sequence's last page, and masks it.
POISON = {"the_trash_page_at_the_largest_finite":
          float(jnp.finfo(jnp.bfloat16).max)}


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_the_pallas_kernels_match_their_twins_in_interpret_mode(mix):
    """index, select, attend over paged pools whose pages are scattered,
    contexts past one block of either walk, spans that share a tile with
    rows of other sequences — and the trash page poisoned with large finite
    values (its rows are read past a walk's last page and masked)."""
    spans, tile, H = MIXES[mix]
    rng = np.random.default_rng(0)
    L, ps = 2, 8
    mp = max(40, max(-(-kv // ps) for _, kv in spans))
    n_pages = max(96, 1 + sum(-(-kv // ps) for _, kv in spans))
    lanes, rank, Hi, di, topk = 128, 32, 4, 16, 16
    poison = POISON.get(mix, 3e4)
    lat = jnp.asarray(rng.standard_normal((L, n_pages * ps, lanes)) * 0.3,
                      jnp.bfloat16).at[:, :, 40:].set(0)
    lat = lat.at[:, :ps].set(poison)
    idx = jnp.asarray(rng.standard_normal((L, n_pages * ps, di)),
                      jnp.bfloat16).at[:, :ps].set(-poison)
    rows = max(5, len(spans))
    pt = np.zeros((rows, mp), np.int32)
    perm = rng.permutation(np.arange(1, n_pages))
    used = 0
    qs, ql, kl, ts, tp = [], [], [], [], []
    for r, (n, kv) in enumerate(spans):
        need = -(-kv // ps)
        pt[r, :need] = perm[used:used + need]
        used += need
        qs.append(len(ts)); ql.append(n); kl.append(kv)
        ts += [r] * n
        tp += list(range(kv - n, kv))
    T = len(ts)
    Tp = -(-T // 32) * 32
    ts += [0] * (Tp - T)
    tp += [-1] * (Tp - T)
    while len(qs) < rows:
        qs.append(Tp); ql.append(0); kl.append(0)
    q = jnp.asarray(rng.standard_normal((Tp, H, lanes)) * 0.3, jnp.bfloat16)
    qi = jnp.asarray(rng.standard_normal((Tp, Hi, di)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((Tp, Hi)), jnp.float32)
    args = (q, qi, w, lat, idx, 1, jnp.asarray(pt),
            *(jnp.asarray(a, jnp.int32) for a in (ts, tp, qs, ql, kl)),
            ps, rank, topk)
    twin = np.asarray(mla.attend("jnp", *args), np.float32)[:T]
    got = np.asarray(mla.attend("pallas", *args, tile=tile, interpret=True),
                     np.float32)[:T]
    assert np.isfinite(got).all()
    # bfloat16 outputs of the same float32 sums: a rounding step apart
    assert np.abs(got - twin).max() <= 2 ** -8 * max(1.0, np.abs(twin).max())
