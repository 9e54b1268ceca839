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
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ollamamq_tpu.config import (ATTENTION, MODEL_CONFIGS, EngineConfig,
                                 ModelConfig)
from ollamamq_tpu.engine.kv_cache import refusal
from ollamamq_tpu.engine import kv_cache as kvc
from ollamamq_tpu.models import llama, moe
from ollamamq_tpu.ops.sampling import SamplingParams
import test_lfm2
from test_step_overlap import _engine, _prompt, _rt, drive
from testutil import (deepseek_v32_keys, deepseek_v32_reference, embed,
                      moe_mlp, once_a_sequence, prefill, seeded_params,
                      span_stream)

NAME = "test-tiny-deepseek-v32"
DS = MODEL_CONFIGS[NAME]
PS, MP, NP, B = 8, 8, 40, 4  # page size, pages a sequence / in the pool, rows
# float32 logits (sd ~1) of two float32 forwards that order their sums
# differently (absorbed against expanded heads, pages against a dense
# square): 2e-4 is ~40 x what they read here (5e-6) and a hundredth of what
# bfloat16 weights read (test below) or any piece left out (0.02 and more).
ATOL = 2e-4


def make_params(mc=DS, dtype=jnp.float32, seed=0):
    return seeded_params(mc, ("attn_norm", "mlp_norm", "mla_q_norm",
                              "mla_kv_norm", "idx_k_norm", "idx_k_bias"),
                         dtype, seed)


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


@once_a_sequence
def want(params, tokens, mc=DS):
    """The reference's ONE full forward: [T, V] logits."""
    return np.asarray(deepseek_v32_reference().logits(
        deepseek_v32_keys(mc), params, np.asarray(tokens, np.int32)))


def ragged_step(params, st, spans, mc=DS, pad_to=32, impl="jnp"):
    """One `forward_ragged` over `spans` = [(row, tokens, start position)],
    padded to `pad_to`. Returns ({row: last logits}, (kc, vc))."""
    pt = page_table()
    stream, (q_start, q_len, kv_len) = span_stream(spans, pad_to, pt, PS)
    out_idx = np.clip(q_start + q_len - 1, 0, pad_to - 1)
    logits, kc, vc = _ragged_jit(mc, impl)(
        params, *stream, out_idx, *st, pt, q_start, q_len, kv_len)
    return {row: np.asarray(logits[row]) for row, _, _ in spans}, (kc, vc)


@functools.cache
def _ragged_jit(mc, impl):
    """ONE jitted `forward_ragged` a (config, implementation): what a step is
    made of comes in as arguments, so a second step of the same shapes
    compiles nothing."""
    return jax.jit(lambda p, *step: llama.forward_ragged(
        p, mc, *step, PS, attn_impl=impl))


def decode_scan(params, st, feed, mc=DS):
    """tests/test_lfm2.py's fused scan over this file's pages: `feed` = {row:
    (tokens, first position)}. Returns ({row: [k, V] logits}, (kc, vc))."""
    got, (kc, vc, _) = test_lfm2.decode_scan(
        mc, params, (*st, None), feed, list(feed), pt=page_table())
    return {row: np.asarray(v) for row, v in got.items()}, (kc, vc)


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
    assert ecfg.num_pages * kvc.kv_page_bytes(DS, ecfg.page_size, 4) \
        == kc.nbytes + vc.nbytes
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
    logits, kc, vc = prefill(
        params, DS, jnp.asarray(both), jnp.asarray([40, 25]), kc, vc, pt, PS)
    assert np.abs(np.asarray(logits[0]) - ref[39]).max() < ATOL
    assert np.abs(np.asarray(logits[1]) - ref[24]).max() < ATOL
    # the oracle wrote both pools: decode continues from them
    assert float(jnp.abs(kc).max()) > 0 and float(jnp.abs(vc).max()) > 0
    emb = embed(params, DS, jnp.asarray(both),
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
    whole, load = moe_mlp(uncut, lp, h, layer=0)
    assert int(load.sum()) == 24 * 4
    shared = jnp.einsum(
        "btf,fd->btd", jax.nn.silu(h @ lp["ws_gate"]) * (h @ lp["ws_up"]),
        lp["ws_down"])
    total, loads = shared, []
    for first in (0, 4, 8, 12):
        share = dataclasses.replace(DS, expert_offset=first)
        held = dict(lp, **{k: lp[k][:, first:first + 4]
                           for k in moe.STACKED})
        part, load = moe_mlp(share, held, h, layer=0)
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
    part, _ = moe_mlp(dataclasses.replace(DS, expert_offset=8),
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
    assert rt.cache.kc.shape[-1] == 128 and rt.cache.vc.shape[-1] == 16
    assert rt.kv_bytes == rt.cache.kc.nbytes + rt.cache.vc.nbytes
    assert rt.cache.prefix_cache is None and rt.export_request(1) is None
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
    err = refusal(DS, **kw)
    assert err is not None and match in err and "\n" not in err
    assert err.startswith(f"model {NAME} has latent attention")
    assert refusal(MODEL_CONFIGS["test-tiny"], **kw) is None
    assert refusal(DS) is None


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
