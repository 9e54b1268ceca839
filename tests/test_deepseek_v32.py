"""DeepSeek-V3.2 on the served path (PR 39): multi-head latent attention over
a latent pool and an index-key pool under one page table, the lightning
indexer's selection of the `index_topk` best cached positions inside paged
attention, YaRN RoPE, a shared expert, and group-limited sigmoid routing over
an expert layer that holds a SHARE of the router's experts.

LOGITS of the served forwards against the benchmark's plain float32 reference
(benchmarks/reference/deepseek_v32_decoder.py: expanded heads, a dense [T, T]
indexer, the selection by `lax.top_k`) at `test-tiny-deepseek-v32` — whose
`index_topk` (16) is well under the tests' contexts, so the selection selects
— seeded random weights, float32, on the CPU; the three Pallas kernels in
interpret mode against their jnp twins; the shares' sum; what leaving a piece
out costs; what the engine counts and refuses."""

import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ollamamq_tpu.config import (ATTENTION, MODEL_CONFIGS, EngineConfig,
                                 ModelConfig, validate_latent_pool)
from ollamamq_tpu.engine import kv_cache as kvc
from ollamamq_tpu.models import llama, moe
from ollamamq_tpu.ops import mla
from ollamamq_tpu.ops.sampling import SamplingParams
from test_step_overlap import _engine, _prompt, _rt, drive
from testutil import deepseek_v32_keys, deepseek_v32_reference

NAME = "test-tiny-deepseek-v32"
DS = MODEL_CONFIGS[NAME]
PS, MP, NP, B = 8, 8, 40, 4  # page size, pages a sequence / in the pool, rows
# float32 logits (sd ~1) of two float32 forwards that order their sums
# differently (absorbed against expanded heads, pages against a dense
# square): 2e-4 is ~40 x what they read here (5e-6) and a hundredth of what
# bfloat16 weights read (test below) or any piece left out (0.02 and more).
ATOL = 2e-4


def make_params(mc=DS, dtype=jnp.float32, seed=0):
    """Seeded weights with norm weights (and the indexer's LayerNorm bias)
    that are not all ones / zeros, so a norm left out cannot pass."""
    params = llama.init_params(mc, jax.random.PRNGKey(seed), dtype=dtype)
    for i, name in enumerate(("attn_norm", "mlp_norm", "mla_q_norm",
                              "mla_kv_norm", "idx_k_norm", "idx_k_bias")):
        w = params["layers"][name]
        params["layers"][name] = (
            (0.0 if name == "idx_k_bias" else 1.0) + 0.5 * jax.random.normal(
                jax.random.PRNGKey(100 + i), w.shape, jnp.float32)
        ).astype(dtype)
    return params


def pools(mc=DS, dtype=jnp.float32):
    return kvc.alloc_kv_pool(mc, EngineConfig(num_pages=NP, page_size=PS),
                             dtype=dtype)


def page_table():
    pt = np.zeros((B, MP), np.int32)
    pages = np.random.default_rng(5).permutation(np.arange(1, NP))
    for r in range(B):
        pt[r] = pages[r * MP:(r + 1) * MP]
    return pt


def seq_tokens(seed, n):
    return np.random.default_rng(seed).integers(3, 500, n).astype(np.int32)


def want(params, tokens, mc=DS):
    """The reference's ONE full forward: [T, V] logits."""
    return np.asarray(deepseek_v32_reference().logits(
        deepseek_v32_keys(mc), params, np.asarray(tokens, np.int32)))


def ragged_step(params, st, spans, mc=DS, pad_to=32, impl="jnp"):
    """One `forward_ragged` over `spans` = [(row, tokens, start position)],
    padded to `pad_to`. Returns ({row: last logits}, (kc, vc))."""
    kc, vc = st
    tok, seq, pos = [], [], []
    q_start = np.full(B, pad_to, np.int32)
    q_len, kv_len = np.zeros(B, np.int32), np.zeros(B, np.int32)
    for row, toks, start in spans:
        q_start[row], q_len[row] = len(tok), len(toks)
        kv_len[row] = start + len(toks)
        tok += list(toks)
        seq += [row] * len(toks)
        pos += list(range(start, start + len(toks)))
    n = len(tok)
    tok, seq, pos = (np.asarray(a + [f] * (pad_to - n), np.int32)
                     for a, f in ((tok, 0), (seq, 0), (pos, -1)))
    pt = page_table()
    slots = np.where(pos >= 0, pt[seq, np.maximum(pos, 0) // PS] * PS
                     + np.maximum(pos, 0) % PS, 0)
    out_idx = np.clip(q_start + q_len - 1, 0, pad_to - 1)
    logits, kc, vc = jax.jit(lambda p, kc, vc: llama.forward_ragged(
        p, mc, *map(jnp.asarray, (tok, seq, pos, slots, out_idx)), kc, vc,
        *map(jnp.asarray, (pt, q_start, q_len, kv_len)), PS,
        attn_impl=impl))(params, kc, vc)
    return {row: np.asarray(logits[row]) for row, _, _ in spans}, (kc, vc)


def decode_scan(params, st, feed, mc=DS):
    """A fused scan of `forward_decode` passes, teacher-forced: `feed` =
    {row: (tokens, first position)}; every other row is parked on the trash
    page. Returns ({row: [k, V] logits}, (kc, vc))."""
    k = len(next(iter(feed.values()))[0])
    toks = np.full((k, B), 7, np.int32)
    pos0, act = np.zeros(B, np.int32), np.zeros(B, np.int32)
    for row, (t, p) in feed.items():
        toks[:, row], pos0[row], act[row] = t, p, 1
    table = np.where(act[:, None] > 0, page_table(), 0).astype(np.int32)

    def run(p, kc, vc):
        def step(carry, tok):
            pos, kc, vc = carry
            logits, kc, vc = llama.forward_decode(
                p, mc, tok, pos, kc, vc, jnp.asarray(table), PS,
                active=jnp.asarray(act))
            return (pos + 1, kc, vc), logits

        (_, kc, vc), logits = jax.lax.scan(
            step, (jnp.asarray(pos0), kc, vc), jnp.asarray(toks))
        return logits, kc, vc

    logits, kc, vc = jax.jit(run)(params, *st)
    return {row: np.asarray(logits[:, row]) for row in feed}, (kc, vc)


def serve(params, tokens, n_prompt, chunk, mc=DS):
    """Row 1 serves `tokens`: the prompt in chunks of `chunk` through the
    two pools (a short second request beside it in row 2), then fused scans
    of 4 passes. Returns {position: logits} for every position read."""
    st, got = pools(mc, params["embed"].dtype), {}
    other = seq_tokens(9, 11)
    for at in range(0, n_prompt, chunk):
        end = min(at + chunk, n_prompt)
        spans = [(1, tokens[at:end], at)]
        if at == 0:
            spans.append((2, other, 0))
        out, st = ragged_step(params, st, spans, mc, pad_to=64)
        got[end - 1] = out[1]
    for at in range(n_prompt, len(tokens) - 3, 4):
        out, st = decode_scan(params, st, {1: (tokens[at:at + 4], at)}, mc)
        for j in range(4):
            got[at + j] = out[1][j]
    return got


# ----------------------------------------------------------- the config
def test_the_tiny_family_its_plan_its_pools_and_its_counts():
    assert DS.kinds[0] == (ATTENTION, "dense") and DS.num_dense_layers == 1
    assert [(f, len(p), n) for f, p, n in DS.layer_plan()] \
        == [(0, 1, 1), (1, 1, 2)]
    assert (DS.latent_dim, DS.latent_lanes, DS.kv_row_dims) \
        == (40, 128, (128, 16))
    assert (DS.router_width, DS.num_experts, DS.expert_width) == (16, 4, 32)
    kc, vc = pools()
    assert kc.shape == (3, NP * PS, 128) and vc.shape == (3, NP * PS, 16)
    ecfg = EngineConfig(num_pages=NP, page_size=PS)
    assert kvc.kv_pool_bytes(DS, ecfg, 4) == kc.nbytes + vc.nbytes
    assert kvc.kv_page_bytes(DS, PS) == 3 * PS * (128 + 16) * 2
    params = make_params()
    leaves = jax.tree_util.tree_leaves(params)
    assert sum(a.size for a in leaves) == DS.param_count()
    # the published widths' count: 1 + 4 layers, 16 of 256 experts held
    full = dataclasses.replace(
        DS, vocab_size=16160, hidden_size=7168, intermediate_size=18432,
        num_layers=5, num_heads=128, num_kv_heads=128, head_dim=192,
        q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, index_n_heads=64,
        index_head_dim=128, index_topk=2048, num_experts=16,
        router_experts=256, n_group=8, topk_group=4, num_experts_per_tok=8,
        moe_intermediate_size=2048)
    assert full.param_count() == 4_635_518_208  # 9.27 GB in bf16
    assert full.kv_row_dims == (640, 128)
    assert abs(full.attn_scale - 192 ** -0.5) > 1e-3  # no mscale: not YaRN's


@pytest.mark.parametrize("bad,match", [
    (dict(rope_scaling={"type": "linear", "factor": 2}),
     "rope_scaling type 'linear'"),
    (dict(rope_scaling={"type": "yarn", "factor": 4}),
     "original_max_position_embeddings"),
    (dict(num_dense_layers=2), "first_k_dense_replace 1 is not"),
    (dict(num_nextn_predict_layers=2), "num_nextn_predict_layers 2"),
    (dict(moe_layer_freq=2), "moe_layer_freq 2"),
    (dict(ep_size=16), "ep_size 16"),
    (dict(head_dim=16), "head_dim 16 is not"),
    (dict(index_topk=0), "an indexer needs index_n_heads"),
    (dict(num_kv_heads=2), "as many kv heads as heads"),
    (dict(n_group=3), "n_group 3"),
    (dict(topk_group=1, num_experts_per_tok=5), "hold the top 5"),
    (dict(expert_offset=13), "expert_offset 13"),
    (dict(kv_lora_rank=0), "kv_lora_rank is 0"),
], ids=["rope_type", "yarn_keys", "dense_keys", "mtp", "layer_freq", "ep",
        "head_dim", "no_indexer", "kv_heads", "groups", "open_groups",
        "offset", "no_latent"])
def test_a_stack_the_program_cannot_run_is_refused_at_construction(bad,
                                                                   match):
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(DS, **bad)


def test_yarn_keeps_fast_frequencies_and_divides_slow_ones():
    from ollamamq_tpu.ops.rope import rope_freqs, yarn_freqs

    yarn = {"type": "yarn", "factor": 40,
            "original_max_position_embeddings": 4096, "beta_fast": 32,
            "beta_slow": 1}
    base, got = (np.asarray(rope_freqs(64, 10000.0)),
                 np.asarray(yarn_freqs(64, 10000.0, yarn)))
    assert np.allclose(got[:8], base[:8])           # rotate often: kept
    assert np.allclose(got[-6:], base[-6:] / 40)    # rotate < once: / factor
    assert np.all(np.diff(got / base) <= 1e-6)      # a ramp between
    assert np.allclose(np.asarray(yarn_freqs(64, 10000.0,
                                             dict(yarn, factor=1))), base)
    full = dataclasses.replace(DS, rope_scaling=dict(yarn, mscale=1,
                                                     mscale_all_dim=1))
    m = 0.1 * np.log(40) + 1
    assert np.isclose(full.attn_scale, DS.head_dim ** -0.5 * m * m)


# ------------------------------------------- the forwards, in float32 logits
@pytest.mark.parametrize("chunk", [16, 7, 44], ids=["c16", "c7", "whole"])
def test_prefill_in_chunks_then_decode_matches_the_reference(chunk):
    """Chunked ragged prefill through the two pools, then fused decode
    scans: every logit read agrees with the reference's ONE full forward —
    contexts from 7 to 60 tokens against index_topk 16, so nearly every
    query selects."""
    params = make_params()
    tokens = seq_tokens(1, 60)
    ref = want(params, tokens)
    got = serve(params, tokens, 44, chunk)
    assert len(got) >= 16 + 44 // max(chunk, 1) - 1
    for pos, logits in got.items():
        assert np.abs(logits - ref[pos]).max() < ATOL, pos


def test_a_row_whose_context_crosses_index_topk_mid_request():
    """A 12-token prompt (everything selected: 12 < 16), then decode passes
    that cross index_topk: the pass at position 15 still sees all 16, the
    one at 16 drops its first position."""
    params = make_params()
    tokens = seq_tokens(2, 12 + 12)
    ref = want(params, tokens)
    got = serve(params, tokens, 12, 12)
    assert sorted(got) == [11] + list(range(12, 24))
    for pos, logits in got.items():
        assert np.abs(logits - ref[pos]).max() < ATOL, pos
    # ... and the selection is what moved them: with it off the passes
    # before the crossing agree and those after do not
    off = serve(params, tokens, 12, 12,
                dataclasses.replace(DS, index_topk=10_000))
    assert max(np.abs(off[p] - ref[p]).max() for p in range(11, 16)) < ATOL
    assert min(np.abs(off[p] - ref[p]).max() for p in range(17, 24)) \
        > 10 * ATOL


def test_the_same_path_in_bfloat16_misses_the_tolerance():
    """bfloat16 weights and pools for float32: far outside ATOL, so the
    tolerance tells the configuration's precision from the one below."""
    params = make_params()
    tokens = seq_tokens(1, 60)
    ref = want(params, tokens)
    low = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16) if a.dtype == jnp.float32
        and a.ndim > 1 else a, params)
    low["final_norm"] = low["final_norm"].astype(jnp.bfloat16)
    got = serve(low, tokens, 44, 16)
    worst = max(np.abs(np.asarray(v, np.float32) - ref[p]).max()
                for p, v in got.items())
    assert worst > 50 * ATOL, worst


def test_the_oracle_and_the_embedding_forward_follow():
    params = make_params()
    tokens = seq_tokens(3, 40)
    ref = want(params, tokens)
    kc, vc = pools()
    pt = jnp.asarray(page_table()[:2])
    both = np.stack([tokens, np.pad(tokens[:25], (0, 15))])
    logits, kc, vc = llama.forward_prefill(
        params, DS, jnp.asarray(both), jnp.asarray([40, 25]), kc, vc, pt, PS)
    assert np.abs(np.asarray(logits[0]) - ref[39]).max() < ATOL
    assert np.abs(np.asarray(logits[1]) - ref[24]).max() < ATOL
    # the oracle wrote both pools: decode continues from them
    assert float(jnp.abs(kc).max()) > 0 and float(jnp.abs(vc).max()) > 0
    emb = llama.forward_embed(params, DS, jnp.asarray(both),
                              jnp.asarray([40, 25]))
    assert emb.shape == (2, DS.hidden_size)
    assert np.allclose(np.linalg.norm(np.asarray(emb), axis=-1), 1.0,
                       atol=1e-5)


# --------------------------- leave one piece out and it fails the tolerance
def _oracle(params, tokens, mc):
    kc, vc = pools(mc)
    logits, _, _ = llama.forward_prefill(
        params, mc, jnp.asarray(tokens[None]), jnp.asarray([len(tokens)]),
        kc, vc, jnp.asarray(page_table()[:1]), PS)
    return np.asarray(logits[0])


@pytest.mark.parametrize("piece", ["selection_bias", "group_limit",
                                   "mscale", "selection", "shared_expert",
                                   "yarn"])
def test_a_forward_that_leaves_one_piece_out_fails(piece, monkeypatch):
    params = make_params()
    tokens = seq_tokens(4, 56)
    ref = want(params, tokens)[-1]
    assert np.abs(_oracle(params, tokens, DS) - ref).max() < ATOL
    mc, wrong = DS, params
    if piece == "selection_bias":
        wrong = jax.tree_util.tree_map(lambda a: a, params)
        wrong["layers"] = dict(params["layers"], router_bias=jnp.zeros_like(
            params["layers"]["router_bias"]))
    elif piece == "group_limit":
        mc = dataclasses.replace(DS, n_group=0, topk_group=0)
    elif piece == "mscale":
        monkeypatch.setattr(ModelConfig, "attn_scale", property(
            lambda self: self.head_dim ** -0.5))
    elif piece == "selection":  # attending to everything
        mc = dataclasses.replace(DS, index_topk=10_000)
    elif piece == "shared_expert":
        wrong = dict(params, layers=dict(
            params["layers"], ws_down=jnp.zeros_like(
                params["layers"]["ws_down"])))
    elif piece == "yarn":
        monkeypatch.setattr(llama, "yarn_freqs",
                            lambda d, theta, yarn: llama.rope_freqs(d, theta))
    miss = np.abs(_oracle(wrong, tokens, mc) - ref).max()
    assert miss > 25 * ATOL, (piece, miss)


# ------------------------------------------------------ the chip's share
def test_the_shares_routed_parts_and_the_shared_expert_once_add_up():
    """Four shares of four experts each (offsets 0, 4, 8, 12): what their
    routed parts give, with the shared expert — which every chip computes
    alike — counted ONCE, is what the uncut layer gives, in the program and
    in the reference. Gates are normalised over all four chosen experts in
    every share, so no share knows the others."""
    uncut = dataclasses.replace(DS, num_experts=16, router_experts=16)
    params = make_params(uncut)
    lp = {k: v[0] if k not in moe.STACKED else v
          for k, v in params["layers"].items()
          if k in ("w_router", "router_bias") + moe.SHARED + moe.STACKED}
    h = jax.random.normal(jax.random.PRNGKey(3), (1, 24, DS.hidden_size))
    whole, load = moe.moe_mlp(uncut, lp, h, layer=0)
    assert int(load.sum()) == 24 * 4
    shared = jnp.einsum(
        "btf,fd->btd", jax.nn.silu(h @ lp["ws_gate"]) * (h @ lp["ws_up"]),
        lp["ws_down"])
    total, loads = shared, []
    for first in (0, 4, 8, 12):
        share = dataclasses.replace(DS, expert_offset=first)
        held = dict(lp, **{k: lp[k][:, first:first + 4]
                           for k in moe.STACKED})
        part, load = moe.moe_mlp(share, held, h, layer=0)
        total = total + (part - shared)
        loads.append(int(load.sum()))
    assert sum(loads) == 24 * 4 and min(loads) >= 0
    assert float(jnp.abs(total - whole).max()) < 1e-5
    # ... and the reference's uncut layer says the same
    ref = deepseek_v32_reference()
    keys = deepseek_v32_keys(uncut)

    def mm(a, w):
        return jnp.matmul(a, w.astype(jnp.float32), precision=ref.HI)

    plain = ref._experts(keys, mm, h[0], params["layers"], 0)
    assert float(jnp.abs(plain - whole[0]).max()) < 1e-5
    # a share's reference is the share's program
    keys4 = deepseek_v32_keys(dataclasses.replace(DS, expert_offset=8))
    lp4 = dict(params["layers"], **{k: params["layers"][k][:, 8:12]
                                    for k in moe.STACKED})
    part, _ = moe.moe_mlp(dataclasses.replace(DS, expert_offset=8),
                          dict(lp, **{k: lp[k][:, 8:12]
                                      for k in moe.STACKED}), h, layer=0)
    assert float(jnp.abs(ref._experts(keys4, mm, h[0], lp4, 0)
                         - part[0]).max()) < 1e-5


def test_group_limited_routing_stays_inside_the_open_groups():
    params = make_params()
    lp = {k: v[0] for k, v in params["layers"].items()
          if k in ("w_router", "router_bias")}
    x = jax.random.normal(jax.random.PRNGKey(5), (64, DS.hidden_size))
    gates, experts = moe.route(DS, lp, x)
    groups = np.asarray(experts) // 4
    assert all(len(set(g)) <= DS.topk_group for g in groups)
    assert np.allclose(np.asarray(gates).sum(-1), DS.routed_scaling_factor,
                       atol=1e-5)
    free, _ = moe.route(dataclasses.replace(DS, n_group=0, topk_group=0),
                        lp, x)
    assert any(len(set(g)) > 2 for g in np.asarray(_) // 4)
    # the reference's gates are the same function
    ref = deepseek_v32_reference()
    w = np.asarray(ref.gates(deepseek_v32_keys(DS), x, params["layers"], 0))
    dense = np.zeros_like(w)
    np.put_along_axis(dense, np.asarray(experts), np.asarray(gates), axis=-1)
    assert np.abs(w - dense).max() < 1e-5


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


def _paged_stream(spans, ps, mp, n_pages, rng, pad_to=32, rows=0):
    """A ragged step's metadata for `spans` = (tokens, context at the span's
    end) a row, each sequence on scattered pages of its own: (page table
    with two spare rows, tok_seq, tok_pos, q_start, q_len, kv_len) as int32
    arrays and the stream's real length; the stream is padded to `pad_to`,
    the table to `rows` where that is more."""
    rows = max(rows, len(spans) + 2)
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
    Tp = -(-T // pad_to) * pad_to
    ts += [0] * (Tp - T)
    tp += [-1] * (Tp - T)
    while len(qs) < rows:
        qs.append(Tp); ql.append(0); kl.append(0)
    return (jnp.asarray(pt), *(jnp.asarray(a, jnp.int32)
                               for a in (ts, tp, qs, ql, kl)), T)


# A prefill span of at least WIDE tokens is attended in the EXPANDED form
# (PR 49): programs of the same launch expand each block's keys and values
# once a group of heads and leave the span's rows in v_head_dim lanes; every
# other row keeps the absorbed tiles, bit for bit. (spans = (tokens, context
# at the span's end) a row, under a WIDE of 48; mp the table's pages a
# sequence.) Head widths of whole lane tiles, which the body needs; pages of
# 8 put 32 in a block of the walk, so a table of 90 (or 41) pages needs
# padding to whole blocks; 32 heads are two programs of WIDE_GROUP.
WIDE_CASES = {
    # a prompt's first chunk: thr is -inf, every position is kept
    "alone_at_base_0": dict(spans=[(64, 64)], served=64, mp=41),
    # a later chunk, the selection real; the last block holds 44 positions
    "alone_past_index_topk": dict(spans=[(64, 300)], served=64),
    # two blocks and 188 positions of a third, a table of 90 pages
    "a_partial_last_block": dict(spans=[(48, 700)], served=48),
    # the cell's step in small: decode rows first, one wide span, a short one
    "decode_rows_a_wide_span_a_short_one": dict(
        spans=[(1, 150), (1, 77), (1, 513), (70, 600), (9, 40)], served=70),
    # both take the expanded programs, each over its own sequence's pages
    "two_wide_spans": dict(spans=[(1, 9), (50, 520), (60, 90)], served=110),
    # one token short: the tiles, and today's bits
    "one_short_of_wide": dict(spans=[(1, 30), (47, 400)], served=0),
    # the trash page at the largest finite value: a walk's last block reads
    # it past the span's last page, and what is expanded from it is masked
    "the_trash_page_at_the_largest_finite": dict(
        spans=[(56, 530)], served=56,
        poison=float(jnp.finfo(jnp.bfloat16).max)),
}


@pytest.fixture
def wide_of_48(monkeypatch):
    """The kernel's WIDE at 48 tokens (a constant it reads as it traces: the
    traces kept from before, and these, are dropped)."""
    from ollamamq_tpu.ops.pallas import mla_attention as ka

    monkeypatch.setattr(ka, "WIDE", 48)
    jax.clear_caches()
    yield ka
    jax.clear_caches()


def _wide_case(name, H=32):
    from ollamamq_tpu.ops.pallas import mla_attention as ka

    case = WIDE_CASES[name]
    spans = case["spans"]
    rng = np.random.default_rng(len(name))
    ps, rank, lanes, dn, dv, dr, topk = 8, 128, 256, 128, 128, 64, 16
    mp = case.get("mp", 90)
    n_pages = 2 + sum(-(-kv // ps) for _, kv in spans)
    lat = jnp.asarray(rng.standard_normal((2, n_pages * ps, lanes)) * 0.3,
                      jnp.bfloat16).at[:, :, rank + dr:].set(0)
    lat = lat.at[:, :ps].set(case.get("poison", 3e4))
    pt, tok_seq, tok_pos, qs, ql, kl, T = _paged_stream(spans, ps, mp,
                                                        n_pages, rng)
    Tp = tok_pos.shape[0]
    scores = jnp.asarray(rng.standard_normal(
        (Tp, ka.context_lanes(mp, ps))), jnp.float32)
    thr = mla.select_threshold(scores, tok_pos, topk)
    q_nope, q_rope = (jnp.asarray(rng.standard_normal((Tp, H, n)) * 0.3,
                                  jnp.bfloat16) for n in (dn, dr))
    wukv = jnp.asarray(rng.standard_normal((rank, H, dn + dv))
                       * rank ** -0.5, jnp.bfloat16)
    pad = jnp.zeros((Tp, H, lanes - rank - dr), jnp.float32)
    q_abs = jnp.concatenate([jnp.einsum(
        "thn,chn->thc", q_nope, wukv[..., :dn],
        preferred_element_type=jnp.float32), q_rope.astype(jnp.float32),
        pad], axis=-1).astype(jnp.bfloat16)
    expanded = (jnp.concatenate([q_nope, q_rope, pad.astype(jnp.bfloat16)],
                                axis=-1), jnp.transpose(wukv, (1, 2, 0)))
    args = (q_abs, scores, thr, lat, 1, pt, qs, ql, kl, ps, rank)

    def w_uv(o):
        return np.asarray(jnp.einsum(
            "thc,chv->thv", o, wukv[..., dn:],
            preferred_element_type=jnp.float32), np.float32)

    twin = w_uv(mla.sparse_attention(q_abs, scores, thr, lat, 1, pt, tok_seq,
                                     tok_pos, ps, rank))
    return case, T, args, expanded, twin, w_uv


@pytest.mark.parametrize("name", sorted(WIDE_CASES))
def test_a_wide_span_is_attended_in_the_expanded_form(name, wide_of_48):
    """Interpret mode against ops/mla.sparse_attention followed by W_uv: the
    tokens of spans of at least WIDE tokens, and no others, come back in
    `o_v`, through W_uv already; every other row is what the launch without
    the expanded operands gives, bit for bit."""
    ka = wide_of_48
    case, T, args, expanded, twin, w_uv = _wide_case(name)
    today = ka.mla_sparse_paged_attention_pallas(*args, interpret=True)
    o, o_v, served = ka.mla_sparse_paged_attention_pallas(
        *args, interpret=True, expanded=expanded)
    served = np.asarray(served)
    want = np.zeros(len(served), bool)
    for start, (n, _) in zip(np.cumsum([0] + [n for n, _ in case["spans"]]),
                             case["spans"]):
        want[start:start + n] = n >= ka.WIDE
    assert (served == want).all() and served.sum() == case["served"]
    others = ~served
    others[T:] = False
    assert np.array_equal(np.asarray(o, np.float32)[others],
                          np.asarray(today, np.float32)[others])
    got = np.where(served[:, None, None], np.asarray(o_v, np.float32),
                   w_uv(o))[:T]
    assert np.isfinite(got).all()
    # two roundings apart: K and V are rounded where the absorbed q is
    assert np.abs(got - twin[:T]).max() <= 2 ** -7 * max(
        1.0, np.abs(twin[:T]).max())


# A layer over a launch that holds the expanded body computes the ABSORBED
# form — q through W_uk before the launch, the attended latent through W_uv
# behind it — for the stream's first ABSORBED_LEAD rows alone where every row
# behind them is a wide span's or padding (`few`, chosen on the device from
# the step's own spans), and for the rung otherwise. (spans under a WIDE of
# 48 on a rung of 64, the lead 32; `few`: which branch the step takes.)
LEAD_CASES = {
    # the cell's step in small: rows 2..56 are the span's, 57..63 padding
    "decode_rows_and_a_wide_span": dict(
        spans=[(1, 90), (1, 33), (55, 200)], few=True),
    # a narrow span behind the lead reads the absorbed form: the rung
    "a_wide_span_and_a_narrow_one_behind_the_lead": dict(
        spans=[(1, 90), (1, 33), (50, 300), (5, 20)], few=False),
    # a prompt's last chunk, under WIDE, alone on the wide rung
    "a_narrow_span_alone": dict(spans=[(1, 90), (40, 200)], few=False),
}


def _control_flow_outside_kernels(jaxpr) -> list:
    """The `cond` and `while` equations of a traced function, by name, the
    kernels' own aside."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            continue
        if eqn.primitive.name in ("cond", "while"):
            found.append(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _control_flow_outside_kernels(sub)
    return found


class TestALayerOverTheExpandedBody:
    """`_latent_attention_op` with the kernel's schedule (interpret mode),
    at head widths of whole lane tiles, under a WIDE of 48: one set of
    weights, pools and traced kernels for the cases."""

    @pytest.fixture(scope="class")
    def layer(self):
        from ollamamq_tpu.ops.pallas import mla_attention as ka

        patch = pytest.MonkeyPatch()
        patch.setattr(ka, "WIDE", 48)  # read as the kernels trace
        jax.clear_caches()
        mc = dataclasses.replace(
            DS, name="wide-lanes", num_heads=16, num_kv_heads=16,
            head_dim=192, kv_lora_rank=128, qk_nope_head_dim=128,
            qk_rope_head_dim=64, v_head_dim=128, index_head_dim=64)
        assert mc.latent_lanes == 256
        params = make_params(mc, dtype=jnp.bfloat16)
        lp = {k: v[0] for k, v in params["layers"].items()
              if k in llama.MLA_PARAMS or k == "wo"}
        ps, n_pages = 8, 64
        rng = np.random.default_rng(4)
        kc = jnp.asarray(rng.standard_normal((1, n_pages * ps, 256)) * 0.3,
                         jnp.bfloat16).at[:, :, 192:].set(0)
        vc = jnp.asarray(rng.standard_normal((1, n_pages * ps, 64)),
                         jnp.bfloat16)
        hidden = jnp.asarray(rng.standard_normal((1, 64, mc.hidden_size)),
                             jnp.bfloat16)
        widths = (mc.num_heads, mc.latent_lanes, mc.kv_lora_rank,
                  mc.qk_nope_head_dim, mc.v_head_dim)

        def stream(spans, T=64):
            """(run(impl, few) -> the layer's [T, D] float32 and what the
            schedule answered; few: ops/mla.absorbed_lead's answer; real)"""
            pt, ts, tp, qs, ql, kl, real = _paged_stream(
                spans, ps, 40, n_pages, np.random.default_rng(3), pad_to=T,
                rows=6)  # one shape, one trace of the kernels, for the cases
            slots = jnp.where(tp >= 0, llama.flat_slot_indices(
                pt[ts], jnp.maximum(tp, 0)[:, None], ps)[:, 0], 0)
            few = mla.absorbed_lead("pallas", qs, ql, T, *widths)

            def run(impl, few=None, trace=False):
                seen = []

                def attn_fn(q, row, index, expanded=None):
                    _, _, out = llama._latent_ragged(
                        mc, q, row, index, kc, vc, 0, slots, pt, ts, tp, qs,
                        ql, kl, ps, impl, True, expanded=expanded)
                    seen.append(out)
                    return out

                def op(h):
                    return llama._latent_attention_op(
                        mc, lp, h, jnp.maximum(tp, 0)[None], attn_fn, few)

                if trace:
                    return jax.make_jaxpr(op)(hidden[:, :T])
                op = jax.jit(op) if impl == "jnp" else op
                return np.asarray(op(hidden[:, :T]), np.float32)[0], seen[0]
            return run, few, real

        yield types.SimpleNamespace(stream=stream, widths=widths, ka=ka)
        patch.undo()
        jax.clear_caches()

    def test_takes_a_wide_spans_rows_as_the_kernel_leaves_them(self, layer):
        """Against the jnp twin's schedule: the layer builds the expanded
        form's q and `[W_uk | W_uv]^T`, the kernel serves the wide span from
        them, and `attn_out` takes those rows through W_uv already and the
        others through it — one answer, and `wide` names the span's rows."""
        run, _, real = layer.stream(
            LEAD_CASES["a_wide_span_and_a_narrow_one_behind_the_lead"][
                "spans"])
        (twin, plain), (got, answer) = run("jnp"), run("pallas")
        assert not isinstance(plain, tuple)
        wide = np.asarray(answer[2])[0]
        assert wide[2:52].all() and wide.sum() == 50
        assert np.abs(got - twin)[:real].max() <= 2 ** -6 * max(
            1.0, np.abs(twin[:real]).max())

    @pytest.mark.parametrize("name", sorted(LEAD_CASES))
    def test_computes_the_absorbed_form_for_the_rows_that_read_it(
            self, name, layer):
        """Against the same layer with the absorbed form over every row of
        the rung (`few` None: the formulation before): a wide span's rows to
        the bit — `o_v` is untouched — and every other real row within one
        bfloat16 rounding of it on the `few` branch (a contraction over 32
        rows may be tiled otherwise than one over the rung), to the bit on
        the other; all within the twin's bound; and the engine's count of
        the rows is the branch the device took."""
        case = LEAD_CASES[name]
        run, (lead, few), real = layer.stream(case["spans"])
        assert lead == layer.ka.ABSORBED_LEAD == 32
        assert bool(few) == case["few"]
        assert layer.ka.absorbed_rows(
            [n for n, _ in case["spans"]], 64, *layer.widths) == (
                lead if case["few"] else 64)
        before, answer = run("pallas")
        got, _ = run("pallas", (lead, few))
        wide = np.array(answer[2])[0]
        wide[real:] = False
        others = ~wide
        others[real:] = False
        assert wide.sum() == sum(n for n, _ in case["spans"] if n >= 48)
        assert np.array_equal(got[wide], before[wide])
        if not case["few"]:
            assert np.array_equal(got[others], before[others])
        scale = max(1.0, np.abs(before[:real]).max())
        assert np.abs(got - before)[others].max() <= 2 ** -8 * scale
        twin, _ = run("jnp")
        assert np.abs(got - twin)[:real].max() <= 2 ** -6 * max(
            1.0, np.abs(twin[:real]).max())

    def test_traces_no_conditional_where_nothing_is_expanded(self, layer):
        """A rung under WIDE, and the jnp path on any: `few` is None and the
        layer's trace holds no `cond` and no loop (on the wide rung one
        of each: the conditional before the launch, the loop over W_uv's
        tiles of rows behind it)."""
        spans = [(1, 90), (20, 200)]
        run, few, _ = layer.stream(spans, T=32)
        assert few is None
        assert mla.absorbed_lead("jnp", None, None, 64, *layer.widths) is None
        assert _control_flow_outside_kernels(
            run("pallas", trace=True).jaxpr) == []
        run, few, _ = layer.stream(spans)
        assert _control_flow_outside_kernels(
            run("pallas", few, trace=True).jaxpr) == ["cond", "while"]


def _kernel_jaxpr(fn, *shapes):
    """The pallas_call a traced launch holds, as text: its grid and blocks,
    and the kernel's body."""
    def find(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                return eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                found = find(sub)
                if found is not None:
                    return found

    eqn = find(jax.make_jaxpr(fn)(*shapes).jaxpr)
    return str(eqn.params["grid_mapping"]) + str(eqn.params["jaxpr"])


def test_no_other_launch_holds_the_expanded_body():
    """The dense kernel's traced program is the one PR 48's tree traced (a
    digest of its text at openPangu's widths, a ragged rung and the scan's
    tiles of one: take it again from `_kernel_jaxpr` only with a change that
    means to touch that kernel), and the masked kernel's on a rung under
    WIDE is the same program with the expanded operands as without."""
    import hashlib

    from ollamamq_tpu.ops.pallas import mla_attention as ka

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt)

    bf, i32, f32 = jnp.bfloat16, jnp.int32, jnp.float32
    pool = s((2, 4096, 640), bf)
    meta = (s((8, 40), i32), s((8,), i32), s((8,), i32), s((8,), i32))
    for (T, tile), digest in (((512, None), "896b332f0bdee44d"),
                              ((16, 1), "c9460fcf403aa502")):
        text = str(jax.make_jaxpr(
            lambda q, pool, *m: ka.mla_dense_paged_attention_pallas(
                q, pool, 1, *m, 32, 512, tile=tile))(
                    s((T, 128, 640), bf), pool, *meta))
        assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest
    T = ka.WIDE - ka.WIDE % -64 - 64  # the rung under WIDE
    C = ka.context_lanes(40, 32)
    sel = (s((T, 128, 640), bf), s((T, C), f32), s((T,), f32), pool)
    plain = _kernel_jaxpr(
        lambda q, sc, thr, pool, *m: ka.mla_sparse_paged_attention_pallas(
            q, sc, thr, pool, 1, *m, 32, 512), *sel, *meta)
    given = _kernel_jaxpr(
        lambda q, sc, thr, pool, qe, w, *m:
        ka.mla_sparse_paged_attention_pallas(
            q, sc, thr, pool, 1, *m, 32, 512, expanded=(qe, w)),
        *sel, s((T, 128, 256), bf), s((128, 256, 512), bf), *meta)
    assert ka.expands(512, 128, 640, 512, 128, 128)
    assert not ka.expands(T, 128, 640, 512, 128, 128)
    assert plain == given


# `ModelRuntime._note_latent` puts the kernel's own count of those tokens on
# the step's sample: (spans, rung, wide tokens) at DeepSeek-V3.2's widths.
WIDE_STEPS = {
    "the_cells_step": ([(1, 9000)] * 5 + [(507, 12000)], 512, 507, 32),
    "a_tail_and_a_head": ([(1, 9000)] * 4 + [(188, 16000), (320, 320)], 512,
                          320, 512),
    "a_wide_span_alone": ([(512, 4096)], 512, 512, 32),
    "a_wide_span_and_padding": ([(1, 9000)] * 16 + [(400, 400)], 512, 400,
                                32),
    "two_short_spans": ([(250, 8200), (262, 262)], 512, 0, 512),
    "a_rung_under_wide": ([(1, 9000), (255, 255)], 256, 0, 0),
    "decode_rows_alone": ([(1, 9000)] * 16, 16, 0, 0),
}


@pytest.mark.parametrize("name", sorted(WIDE_STEPS))
def test_step_sample_carries_mla_wide_tokens(name):
    """From a step's composition alone, beside `mla_rows`: a ragged step's,
    where the kernel serves; nothing in a fused scan or on the jnp path. And
    `mla_absorbed_rows` beside it: the lead where every row behind it is a
    wide span's, the rung where one is not, 0 where nothing is expanded."""
    import functools
    import types

    from ollamamq_tpu.engine.engine import ModelRuntime
    from ollamamq_tpu.ops.pallas.mla_attention import (WIDE, absorbed_rows,
                                                       wide_tokens)
    from ollamamq_tpu.telemetry import schema as tm

    spans, rung, wide, absorbed = WIDE_STEPS[name]
    assert wide == sum(n for n, _ in spans if n >= WIDE) * (rung >= WIDE)
    series = [c.labels(model="wide-" + name) for c in (
        tm.MLA_ROWS_TOTAL, tm.DSA_CTX_TOKENS_TOTAL,
        tm.DSA_SELECTED_TOKENS_TOTAL, tm.MLA_WIDE_TOKENS_TOTAL,
        tm.MLA_ABSORBED_ROWS_TOTAL)]
    cfg = types.SimpleNamespace(kv_lora_rank=512, index_topk=2048)
    widths = dict(heads=128, lanes=640, rank=512, nope=128, v=128)
    rt = types.SimpleNamespace(
        cfg=cfg, LATENT_FIELDS=ModelRuntime.LATENT_FIELDS, _tm_dsa=series,
        _wide_tokens=functools.partial(wide_tokens, **widths),
        _absorbed_rows=functools.partial(absorbed_rows, **widths))
    noted = {}
    sp = types.SimpleNamespace(note=noted.update)
    ModelRuntime._note_latent(rt, sp, spans, stream_len=rung)
    assert noted["mla_wide_tokens"] == wide
    assert noted["mla_absorbed_rows"] == absorbed
    assert noted["mla_rows"] == sum(n for n, _ in spans)
    assert series[3].value == wide and series[4].value == absorbed
    ModelRuntime._note_latent(rt, sp, [(8, kv) for _, kv in spans], scan=True)
    assert noted["mla_wide_tokens"] == noted["mla_absorbed_rows"] == 0
    rt._wide_tokens = None  # the jnp path: no kernel, nothing expanded
    ModelRuntime._note_latent(rt, sp, spans, stream_len=rung)
    assert noted["mla_wide_tokens"] == noted["mla_absorbed_rows"] == 0
    assert series[3].value == wide and series[4].value == absorbed


# ------------------------------------------------- the engine, by id stream
def _arrivals(n=5, lens=(5, 40, 9, 23, 31), every=2, out=9):
    return [(every * i, f"u{i}", _prompt(i, lens[i % len(lens)]),
             SamplingParams(max_tokens=out + 2 * i)) for i in range(n)]


def test_the_engine_serves_it_and_counts_what_the_selection_did(monkeypatch):
    """Five requests over four slots through the engine's own loop: spans
    beside decode rows, chunks, fused scans; every launched step says how
    many query tokens went through latent attention, how many cached
    positions the indexer scored for them and how many attention saw."""
    eng = _engine(NAME)
    got, samples = drive(eng, _arrivals(), True, monkeypatch)
    assert all(len(ids[0]) == 9 + 2 * i
               for i, ids in enumerate(got[f"u{i}"] for i in range(5)))
    settled, _ = drive(_engine(NAME), _arrivals(), False, monkeypatch)
    assert got == settled
    rt = _rt(eng)
    assert rt.kc.shape[-1] == 128 and rt.vc.shape[-1] == 16
    assert rt.kv_bytes == rt.kc.nbytes + rt.vc.nbytes
    assert rt.prefix_cache is None and rt.export_request(1) is None
    assert {s["mode"] for s in samples} == {"ragged", "decode"}
    for s in samples:
        assert s["mla_rows"] >= 1
        assert s["dsa_selected_tokens"] <= s["dsa_ctx_tokens"]
        assert s["dsa_step_ctx_tokens"] <= s["dsa_ctx_tokens"]
        if s["mode"] == "decode":  # a scan: every row is a one-token row
            assert s["dsa_step_ctx_tokens"] == s["dsa_ctx_tokens"]
            assert s["dsa_step_selected_tokens"] == s["dsa_selected_tokens"]
            assert s["mla_rows"] % s["k_cap"] == 0
    # a 40-token prompt selected 16 of up to 40 positions: under the whole
    assert sum(s["dsa_selected_tokens"] for s in samples) \
        < 0.8 * sum(s["dsa_ctx_tokens"] for s in samples)
    first = next(s for s in samples if s["mode"] == "ragged")
    assert first["dsa_ctx_tokens"] == 5 * 6 // 2  # u0's 5-token prompt


# ------------------------------- what cannot carry the two pools yet
@pytest.mark.parametrize("kw,match", [
    (dict(kv_dtype="int8"), "--kv-dtype int8: the page writer's scales"),
    (dict(weights_dtype="int8"), "--weights-dtype int8"),
    (dict(prefix_cache=True), "--prefix-cache: the radix tree"),
    (dict(mesh_shape={"tensor": 2}), "--tp / --ep: the latent"),
    (dict(mesh_shape={"expert": 2}), "--tp / --ep: the latent"),
], ids=["kv_int8", "w_int8", "prefix_cache", "tp", "ep"])
def test_what_the_latent_pools_cannot_do_yet_is_refused_by_one_line(kw,
                                                                    match):
    err = validate_latent_pool(DS, **kw)
    assert err is not None and match in err and "\n" not in err
    assert err.startswith(f"model {NAME} has latent attention")
    assert validate_latent_pool(MODEL_CONFIGS["test-tiny"], **kw) is None
    assert validate_latent_pool(DS) is None


@pytest.mark.parametrize("over,match", [
    (dict(kv_dtype="int8"), "--kv-dtype int8"),
    (dict(prefix_cache=True), "--prefix-cache"),
], ids=["kv_int8", "prefix_cache"])
def test_the_runtime_refuses_them_at_construction(over, match):
    with pytest.raises(ValueError, match=match):
        _engine(NAME, **over)


@pytest.mark.parametrize("flags,match", [
    (["--kv-dtype", "int8"], "--kv-dtype int8"),
    (["--tp", "2"], "--tp / --ep"),
    (["--ep", "2"], "--tp / --ep"),
    (["--prefix-cache"], "--prefix-cache"),
], ids=["kv_int8", "tp", "ep", "prefix_cache"])
def test_the_cli_ends_at_start_with_one_line(flags, match, caplog):
    from ollamamq_tpu import cli

    with caplog.at_level("ERROR"):
        rc = cli.main(["--no-tui", "--models", NAME, "--cpu", "1"] + flags)
    assert rc == 2
    lines = [r.getMessage() for r in caplog.records if r.levelname == "ERROR"]
    assert len(lines) == 1 and match in lines[0], lines
