"""openPangu-Ultra-MoE on the served path (PR 42): multi-head latent attention
with NO indexer (a causal walk over the latent pool, no second pool), sandwich
norms, a sigmoid router with no groups and no bias over an expert layer that
holds a SHARE of the router's experts beside a shared expert — and the model's
own multi-token-prediction module, served as the `--spec` proposer: its draft
is computed on the device inside the step that verifies the last one, and the
verify span reads a logit a draft position through the latent pool.

LOGITS of the served forwards (trunk AND module) against the benchmark's plain
float32 reference (benchmarks/reference/openpangu_ultra_decoder.py: expanded
heads, a dense causal softmax, no cache) at `test-tiny-openpangu`, seeded
random weights, float32, on the CPU; the dense kernel in interpret mode
against its jnp twin; the shares' sum; what leaving a piece out costs; and the
engine by id stream: what `--spec` emits is what greedy decoding emits, with
the module's real drafts and with a proposer forced right, wrong and both by
turns (a test double on the device: the draft carry overwritten after every
launch at the program's own length carry, `test_step_overlap.force_proposer`),
under the pipelined loop and under the loop settled in its tick (PR 44)."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ollamamq_tpu.config import (ATTENTION, MODEL_CONFIGS, EngineConfig)
from ollamamq_tpu.engine.kv_cache import refusal
from ollamamq_tpu.engine import kv_cache as kvc
from ollamamq_tpu.engine.engine import ModelRuntime
from ollamamq_tpu.models import llama, moe
from ollamamq_tpu.ops import mla
from ollamamq_tpu.ops.sampling import SamplingParams
from test_deepseek_v32 import page_table, seq_tokens
from test_step_overlap import (PROPOSERS, _engine, _prompt, _rt, drive,
                               force_proposer)
from testutil import (moe_mlp, once_a_sequence, openpangu_keys,
                      openpangu_reference, prefill, seeded_params,
                      span_stream)

NAME = "test-tiny-openpangu"
PG = MODEL_CONFIGS[NAME]
PS, MP, NP, B = 8, 8, 40, 4  # page size, pages a sequence / in the pool, rows
# float32 logits (sd ~1) of two float32 forwards that order their sums
# differently (absorbed against expanded heads, pages against a dense
# square): 2e-4 is ~50 x what they read here (4e-6) and a hundredth of what
# bfloat16 weights read (test below) or any piece left out (0.01 and more).
ATOL = 2e-4
NORMS = ("attn_norm", "mlp_norm", "post_attn_norm", "post_mlp_norm",
         "mla_q_norm", "mla_kv_norm")
MTP_NORMS = ("mtp_enorm", "mtp_hnorm", "mtp_norm", "final_norm")


def make_params(mc=PG, dtype=jnp.float32, seed=0):
    return seeded_params(mc, NORMS, dtype, seed, top_norms=MTP_NORMS)


def pools(mc=PG, dtype=jnp.float32):
    return kvc.alloc_kv_pool(mc, EngineConfig(num_pages=NP, page_size=PS),
                             dtype=dtype)


@once_a_sequence
def want(params, tokens, mc=PG):
    """The reference's ONE full forward: ([T, V] trunk logits, [T - 1, V]
    module logits: row i the distribution of token i + 2)."""
    ref, keys = openpangu_reference(), openpangu_keys(mc)
    tokens = np.asarray(tokens, np.int32)
    return (np.asarray(ref.logits(keys, params, tokens)),
            np.asarray(ref.mtp_logits(keys, params, tokens)))


def ragged_step(params, st, spans, follows, mc=PG, pad_to=32, impl="jnp",
                read=None):
    """One `forward_ragged` and `forward_mtp` over `spans` = [(row, tokens,
    start position)], padded to `pad_to`; `follows[row]` is the token after
    the row's span. Logits leave at each row's last position, or at
    `read[row]` (an offset into its span: the trunk's there AND at the last).
    Returns ({row: (trunk logits, module logits)}, (kc, vc))."""
    pt = page_table()
    stream, (q_start, q_len, kv_len) = span_stream(spans, pad_to, pt, PS)
    nxt = [t for row, toks, _ in spans
           for t in list(toks[1:]) + [follows[row]]]
    nxt = np.asarray(nxt + [0] * (pad_to - len(nxt)), np.int32)
    at = q_start + q_len - 1
    for row, off in (read or {}).items():
        at[row] = q_start[row] + off
    at = np.clip(at, 0, pad_to - 1)
    logits, draft, kc, vc, load = _step_jit(mc, impl)(
        params, *st, (*stream, at), nxt, (pt, q_start, q_len, kv_len))
    assert load.shape == (mc.num_experts,)
    return {row: (np.asarray(logits[row]), np.asarray(draft[row]))
            for row, _, _ in spans}, (kc, vc)


@functools.cache
def _step_jit(mc, impl):
    """ONE jitted trunk-and-module step a (config, implementation): what a
    step is made of comes in as arguments, so a second step of the same
    shapes compiles nothing."""
    def run(p, kc, vc, stream, nxt, meta):
        logits, kc, vc, _, hidden = llama.forward_ragged(
            p, mc, *stream, kc, vc, *meta, PS, attn_impl=impl, moe_load=True,
            hidden=True)
        draft, kc, load = llama.forward_mtp(
            p, mc, hidden, nxt, *stream[1:], kc, *meta, PS, attn_impl=impl)
        return logits, draft, kc, vc, load

    return jax.jit(run)


def serve(params, tokens, n_prompt, chunk, mc=PG):
    """Row 1 serves `tokens`: the prompt in chunks of `chunk` through the
    latent pool (a short second request beside it in row 2), then one-token
    decode rows. Returns {position: (trunk logits, module logits)}."""
    st, got = pools(mc, params["embed"].dtype), {}
    other = seq_tokens(9, 11)
    for at in range(0, n_prompt, chunk):
        end = min(at + chunk, n_prompt)
        spans, follows = [(1, tokens[at:end], at)], {1: tokens[end]}
        if at == 0:
            spans.append((2, other, 0))
            follows[2] = 7
        out, st = ragged_step(params, st, spans, follows, mc, pad_to=64)
        got[end - 1] = out[1]
    for at in range(n_prompt, len(tokens) - 1):
        out, st = ragged_step(params, st, [(1, tokens[at:at + 1], at)],
                              {1: tokens[at + 1]}, mc, pad_to=8)
        got[at] = out[1]
    return got


# ----------------------------------------------------------- the config
def test_the_tiny_family_its_plan_its_pool_and_its_counts():
    assert PG.kinds[0] == (ATTENTION, "dense") and PG.num_dense_layers == 1
    assert (PG.latent_dim, PG.latent_lanes, PG.kv_row_dims) \
        == (40, 128, (128, 0))
    assert (PG.router_width, PG.num_experts, PG.expert_width) == (16, 4, 32)
    assert PG.cache_layers == 4 and PG.count(ATTENTION) == 3
    kc, vc = pools()
    # the module's block has its own rows; no indexer, no second pool
    assert kc.shape == (4, NP * PS, 128) and vc.shape == (4, NP * PS, 0)
    ecfg = EngineConfig(num_pages=NP, page_size=PS)
    assert ecfg.num_pages * kvc.kv_page_bytes(PG, ecfg.page_size, 4) \
        == kc.nbytes and vc.nbytes == 0
    params = make_params()
    n = sum(a.size for a in jax.tree_util.tree_leaves(params))
    ref, keys = openpangu_reference(), openpangu_keys(PG)
    assert n == PG.param_count() == ref.param_count(keys)
    ref.served_layout(keys, params)
    assert "idx_wq" not in params["layers"] \
        and "router_bias" not in params["layers"]
    # every stack of the module's kind of layer holds its block LAST
    assert params["layers"]["mla_wdq"].shape[0] == 4
    assert params["layers"]["we_gate"].shape[:2] == (3, 4)
    assert params["layers"]["w_gate"].shape[0] == 1
    # the published widths' count: 1 + 4 layers and the module, 16 of 256
    # experts held, an eighth of the vocabulary (the file's arithmetic)
    full = dataclasses.replace(
        PG, vocab_size=19200, hidden_size=7680, intermediate_size=18432,
        num_layers=5, num_heads=128, num_kv_heads=128, head_dim=192,
        q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, num_experts=16,
        router_experts=256, num_experts_per_tok=8,
        moe_intermediate_size=2048, rope_theta=25_600_000.0)
    assert full.param_count() == ref.param_count(openpangu_keys(full)) \
        == 6_037_862_400  # 12.08 GB in bf16
    assert full.kv_row_dims == (640, 0) and full.cache_layers == 6
    assert full.attn_scale == 192 ** -0.5  # no YaRN, no mscale
    assert kvc.kv_page_bytes(full, 32) == 6 * 32 * 640 * 2


@pytest.mark.parametrize("base,bad,match", [
    (MODEL_CONFIGS["test-tiny"], dict(sandwich_norm=True, norm_order="post"),
     "sandwich_norm adds an output norm"),
    (PG, dict(num_nextn_predict_layers=2), "num_nextn_predict_layers 2"),
    (PG, dict(num_nextn_predict_layers=-1), "num_nextn_predict_layers -1"),
    (PG, dict(num_dense_layers=3, first_k_dense_replace=3),
     "one more latent-attention expert layer"),
    (MODEL_CONFIGS["test-tiny"], dict(num_nextn_predict_layers=1),
     "one more latent-attention expert layer"),
    (MODEL_CONFIGS["test-tiny-olmoe"], dict(num_nextn_predict_layers=1),
     "one more latent-attention expert layer"),
    (PG, dict(index_topk=16), "an indexer needs index_n_heads"),
    (MODEL_CONFIGS["test-tiny-deepseek-v32"],
     dict(num_nextn_predict_layers=1), "served with no indexer"),
    (PG, dict(index_n_heads=4, index_head_dim=4, index_topk=16),
     "index_head_dim of at least qk_rope_head_dim"),
    (PG, dict(kv_lora_rank=0), "kv_lora_rank is 0"),
    (PG, dict(expert_offset=13), "expert_offset 13"),
], ids=["sandwich_post", "mtp_depth_2", "mtp_negative", "mtp_no_experts",
        "mtp_not_latent", "mtp_moe_not_latent", "half_an_indexer",
        "mtp_beside_an_indexer",
        "narrow_index_key", "no_latent", "offset"])
def test_a_stack_the_program_cannot_run_is_refused_at_construction(base, bad,
                                                                   match):
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(base, **bad)


# ------------------------------------------- the forwards, in float32 logits
@pytest.mark.parametrize("chunk", [16, 7, 44], ids=["c16", "c7", "whole"])
def test_prefill_in_chunks_then_decode_matches_the_reference(chunk):
    """Chunked ragged prefill through the latent pool, then decode rows:
    every trunk logit read agrees with the reference's ONE full forward, and
    so does every logit of the module — whose block reads its OWN rows of
    the pool (layer 3), written chunk by chunk with the token that follows
    each position."""
    params = make_params()
    tokens = seq_tokens(1, 60)
    ref, ref_mtp = want(params, tokens)
    got = serve(params, tokens, 44, chunk)
    assert len(got) >= 15 + 44 // max(chunk, 1) - 1
    for pos, (logits, draft) in got.items():
        assert np.abs(logits - ref[pos]).max() < ATOL, pos
        assert np.abs(draft - ref_mtp[pos]).max() < ATOL, pos


@pytest.mark.parametrize("accepted", [True, False], ids=["accept", "reject"])
def test_a_verify_span_reads_a_logit_a_draft_position_through_the_pool(
        accepted):
    """A decode row as `--spec` composes it, [t_i, d]: the trunk's logits at
    BOTH positions agree with the reference's forward of that very sequence;
    a rejected draft's rows (trunk's and module's) lie past the rolled-back
    length and are overwritten by the next span, after which every logit
    agrees again — the module's draft read at the last ACCEPTED position."""
    params = make_params()
    tokens = seq_tokens(6, 40)
    ref, ref_mtp = want(params, tokens)
    st = pools()
    _, st = ragged_step(params, st, [(1, tokens[:20], 0)], {1: tokens[20]})
    draft = tokens[21] if accepted else (tokens[21] + 1) % 500
    span = np.asarray([tokens[20], draft], np.int32)
    # (what follows position 20 is the TRUE token 21, whatever was drafted)
    with_draft, _ = want(params, np.concatenate([tokens[:20], span]))
    out, st = ragged_step(params, st, [(1, span, 20)], {1: tokens[22]},
                          read={1: 0})
    assert np.abs(out[1][0] - ref[20]).max() < ATOL
    out1, _ = ragged_step(params, st, [(1, span, 20)], {1: tokens[22]})
    assert np.abs(out1[1][0] - with_draft[21]).max() < ATOL
    if accepted:  # both positions stand: the next span starts at 22
        assert np.abs(out1[1][0] - ref[21]).max() < ATOL
        assert np.abs(out1[1][1] - ref_mtp[21]).max() < ATOL
        nxt = 22
    else:  # position 21 is rolled back: the module's draft is position 20's
        fix = np.asarray([tokens[20], tokens[21]], np.int32)
        out, st = ragged_step(params, st, [(1, fix, 20)], {1: tokens[22]},
                              read={1: 0})
        assert np.abs(out[1][1] - ref_mtp[20]).max() < ATOL
        nxt = 22
    out, st = ragged_step(params, st, [(1, tokens[nxt:nxt + 2], nxt)],
                          {1: tokens[nxt + 2]})
    assert np.abs(out[1][0] - ref[nxt + 1]).max() < ATOL
    assert np.abs(out[1][1] - ref_mtp[nxt + 1]).max() < ATOL


def test_the_same_path_in_bfloat16_misses_the_tolerance():
    """bfloat16 weights and pool for float32: far outside ATOL, so the
    tolerance tells the configuration's precision from the one below."""
    params = make_params()
    tokens = seq_tokens(1, 60)
    ref, ref_mtp = want(params, tokens)
    low = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), params)
    got = serve(low, tokens, 44, 16)
    worst = [max(np.abs(np.asarray(v[i], np.float32) - r[p]).max()
                 for p, v in got.items())
             for i, r in enumerate((ref, ref_mtp))]
    assert min(worst) > 50 * ATOL, worst


def test_the_oracle_and_the_decode_scan_follow():
    """`forward_prefill` (the dense oracle) and the fused scan's
    `forward_decode` run the trunk of a model with no indexer (the module is
    the ragged step's: without `--spec` it is held and not run)."""
    params = make_params()
    tokens = seq_tokens(3, 44)
    ref, _ = want(params, tokens)
    kc, vc = pools()
    pt = jnp.asarray(page_table()[:2])
    both = np.stack([tokens[:40], np.pad(tokens[:25], (0, 15))])
    logits, kc, vc = prefill(
        params, PG, jnp.asarray(both), jnp.asarray([40, 25]), kc, vc, pt, PS)
    assert np.abs(np.asarray(logits[0]) - ref[39]).max() < ATOL
    assert np.abs(np.asarray(logits[1]) - ref[24]).max() < ATOL
    assert float(jnp.abs(kc[:3]).max()) > 0 and vc.size == 0
    assert float(jnp.abs(kc[3]).max()) == 0  # the module's rows: untouched
    for at in range(40, 43):  # decode continues from the oracle's rows
        logits, kc, vc = llama.forward_decode(
            params, PG, jnp.asarray([tokens[at], 7]), jnp.asarray([at, 0]),
            kc, vc, pt, PS, active=jnp.asarray([1, 0]))
        assert np.abs(np.asarray(logits[0]) - ref[at]).max() < ATOL


# --------------------------- leave one piece out and it fails the tolerance
def _served(params, tokens, mc=PG):
    """(trunk logits, module logits) at the last position whose successor is
    known, through the pool in one span."""
    n = len(tokens) - 1
    out, _ = ragged_step(params, pools(mc), [(1, tokens[:n], 0)],
                         {1: tokens[n]}, mc, pad_to=64)
    return out[1]


@pytest.mark.parametrize("piece", [
    "sandwich_norms", "post_attn_norm", "post_mlp_norm", "shared_expert",
    "routed_scale", "gate_normalisation", "mtp_enorm", "mtp_hnorm",
    "mtp_concat_order"])
def test_a_forward_that_leaves_one_piece_out_fails(piece):
    params = make_params()
    tokens = seq_tokens(4, 57)
    ref, ref_mtp = want(params, tokens)
    got = _served(params, tokens)
    assert np.abs(got[0] - ref[55]).max() < ATOL
    assert np.abs(got[1] - ref_mtp[55]).max() < ATOL
    mc, wrong, module_only = PG, dict(params), piece.startswith("mtp_")
    if piece == "sandwich_norms":  # the sublayers' outputs not normed at all
        mc = dataclasses.replace(PG, sandwich_norm=False)
    elif piece in ("post_attn_norm", "post_mlp_norm"):  # ...or not weighed
        wrong["layers"] = dict(params["layers"], **{piece: jnp.ones_like(
            params["layers"][piece])})
    elif piece == "shared_expert":
        wrong["layers"] = dict(params["layers"], ws_down=jnp.zeros_like(
            params["layers"]["ws_down"]))
    elif piece == "routed_scale":
        mc = dataclasses.replace(PG, routed_scaling_factor=1.0)
    elif piece == "gate_normalisation":
        mc = dataclasses.replace(PG, norm_topk_prob=False)
    elif piece in ("mtp_enorm", "mtp_hnorm"):
        wrong[piece] = jnp.ones_like(params[piece])
    elif piece == "mtp_concat_order":
        d = PG.hidden_size
        w = params["mtp_eh_proj"]
        wrong["mtp_eh_proj"] = jnp.concatenate([w[d:], w[:d]])
    miss = _served(wrong, tokens, mc)
    assert np.abs(miss[1] - ref_mtp[55]).max() > 25 * ATOL, piece
    if module_only:  # the trunk does not read the module's weights
        assert np.abs(miss[0] - ref[55]).max() < ATOL
    else:
        assert np.abs(miss[0] - ref[55]).max() > 25 * ATOL, piece


# ------------------------------------------------------ the chip's share
def test_the_shares_routed_parts_and_the_shared_expert_once_add_up():
    """Four shares of four experts each (offsets 0, 4, 8, 12): what their
    routed parts give, with the shared expert — which every chip computes
    alike — counted ONCE, is what the uncut layer gives, in the program and
    in the reference. Gates are normalised over all four chosen experts in
    every share, so no share knows the others."""
    uncut = dataclasses.replace(PG, num_experts=16, router_experts=16)
    params = make_params(uncut)
    lp = {k: v[0] if k not in moe.STACKED else v
          for k, v in params["layers"].items()
          if k in ("w_router",) + moe.SHARED + moe.STACKED}
    h = jax.random.normal(jax.random.PRNGKey(3), (1, 24, PG.hidden_size))
    whole, load = moe_mlp(uncut, lp, h, layer=0)
    assert int(load.sum()) == 24 * 4
    shared = jnp.einsum(
        "btf,fd->btd", jax.nn.silu(h @ lp["ws_gate"]) * (h @ lp["ws_up"]),
        lp["ws_down"])
    total, loads = shared, []
    for first in (0, 4, 8, 12):
        share = dataclasses.replace(PG, expert_offset=first)
        held = dict(lp, **{k: lp[k][:, first:first + 4]
                           for k in moe.STACKED})
        part, load = moe_mlp(share, held, h, layer=0)
        total = total + (part - shared)
        loads.append(int(load.sum()))
    assert sum(loads) == 24 * 4 and min(loads) >= 0
    assert float(jnp.abs(total - whole).max()) < 1e-5
    # ... and the reference's uncut layer says the same
    ref = openpangu_reference()

    def mm(a, w):
        return jnp.matmul(a, w.astype(jnp.float32), precision=ref.HI)

    plain = ref._experts(openpangu_keys(uncut), mm, h[0], params["layers"], 0)
    assert float(jnp.abs(plain - whole[0]).max()) < 1e-5
    # a share's reference is the share's program
    at8 = dataclasses.replace(PG, expert_offset=8)
    lp4 = dict(params["layers"], **{k: params["layers"][k][:, 8:12]
                                    for k in moe.STACKED})
    part, _ = moe_mlp(at8, dict(lp, **{k: lp[k][:, 8:12]
                                       for k in moe.STACKED}), h, layer=0)
    assert float(jnp.abs(ref._experts(openpangu_keys(at8), mm, h[0], lp4, 0)
                         - part[0]).max()) < 1e-5


def test_the_router_is_a_plain_top_k_of_sigmoid_scores():
    """No groups, no bias: the k largest sigmoid scores over all 16, divided
    by their sum, times 2.5 — and the reference's gates are that function."""
    params = make_params()
    lp = {"w_router": params["layers"]["w_router"][0]}
    x = jax.random.normal(jax.random.PRNGKey(5), (64, PG.hidden_size))
    gates, experts = moe.route(PG, lp, x)
    s = np.asarray(jax.nn.sigmoid(x @ lp["w_router"]))
    assert np.array_equal(np.sort(np.asarray(experts), -1),
                          np.sort(np.argsort(-s, -1)[:, :4], -1))
    assert np.allclose(np.asarray(gates).sum(-1), 2.5, atol=1e-5)
    assert any(len(set(g)) > 2 for g in np.asarray(experts) // 4)
    w = np.asarray(openpangu_reference().gates(
        openpangu_keys(PG), x, params["layers"], 0))
    dense = np.zeros_like(w)
    np.put_along_axis(dense, np.asarray(experts), np.asarray(gates), axis=-1)
    assert np.abs(w - dense).max() < 1e-5


# ---------------------------------------- the dense kernel against its twin
MIXES = {
    "prefill_and_decode": ([(40, 300), (1, 150), (1, 77), (3, 20)], 16, 4),
    "verify_spans_of_two": ([(2, 300), (2, 150), (2, 77), (2, 513), (2, 2)],
                            None, 8),
    "one_token_rows_alone": ([(40, 300), (1, 150), (1, 77), (1, 9)], 16, 8),
    "a_span_over_two_tiles": ([(1, 150), (2, 77), (45, 700), (3, 20)], None,
                              8),
    "the_scans_tiles_of_one": ([(1, 1100), (1, 513), (1, 512), (1, 40)], 1,
                               8),
    # rows 15 | 16 of tiles of 16: a verify span split over two tiles, then
    # a one-token row in a tile's LAST row (the short path's two row-heads
    # start one row before it) and a span of three (the whole tile's path)
    "a_verify_span_across_two_tiles": ([(1, 90)] * 15 + [(2, 300), (2, 41)]
                                       + [(1, 7)] * 11 + [(1, 260), (3, 50)],
                                       16, 8),
    "verify_spans_in_the_last_rows": ([(2, 70)] * 7 + [(2, 515)] + [(2, 9)]
                                      * 15 + [(2, 1030)], None, 8),
}


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_the_dense_kernel_matches_its_twin_in_interpret_mode(mix):
    """`mla_dense_paged_attention_pallas` — the attention kernel with the
    selection's operands and comparison compiled out — over a paged pool
    whose pages are scattered, the trash page poisoned with large finite
    values, under both of its names on the trace."""
    spans, tile, H = MIXES[mix]
    rng = np.random.default_rng(0)
    L, ps, lanes, rank = 2, 8, 128, 32
    mp = max(40, max(-(-kv // ps) for _, kv in spans))
    n_pages = max(96, 1 + sum(-(-kv // ps) for _, kv in spans))
    lat = jnp.asarray(rng.standard_normal((L, n_pages * ps, lanes)) * 0.3,
                      jnp.bfloat16).at[:, :, 40:].set(0)
    lat = lat.at[:, :ps].set(3e4)
    rows = max(5, len(spans))
    pt = np.zeros((rows, mp), np.int32)
    perm = rng.permutation(np.arange(1, n_pages))
    used = 0
    qs, ql, kl, ts, tp = [], [], [], [], []
    for r, (n, kv) in enumerate(spans):
        need = -(-kv // ps)
        pt[r, :need] = perm[used:used + need]
        used += need
        qs.append(len(ts))
        ql.append(n)
        kl.append(kv)
        ts += [r] * n
        tp += list(range(kv - n, kv))
    T = len(ts)
    Tp = -(-T // 32) * 32
    ts += [0] * (Tp - T)
    tp += [-1] * (Tp - T)
    while len(qs) < rows:
        qs.append(Tp)
        ql.append(0)
        kl.append(0)
    q = jnp.asarray(rng.standard_normal((Tp, H, lanes)) * 0.3, jnp.bfloat16)
    args = (q, None, None, lat, None, 1, jnp.asarray(pt),
            *(jnp.asarray(a, jnp.int32) for a in (ts, tp, qs, ql, kl)),
            ps, rank, 0)
    twin = np.asarray(mla.attend("jnp", *args), np.float32)[:T]
    from ollamamq_tpu.ops.pallas.mla_attention import MTP_NAME

    for name in (None, MTP_NAME):
        got = np.asarray(mla.attend("pallas", *args, tile=tile,
                                    interpret=True, name=name),
                         np.float32)[:T]
        assert np.isfinite(got).all()
        # bfloat16 outputs of the same float32 sums: a rounding step apart
        assert np.abs(got - twin).max() \
            <= 2 ** -8 * max(1.0, np.abs(twin).max())


# ------------------------------------------------- the engine, by id stream
GREEDY = dict(temperature=0.0, repeat_penalty=1.0)


def _arrivals(n=5, lens=(5, 40, 9, 23, 31), every=2, out=9, **sampling):
    sampling = sampling or GREEDY
    return [(every * i, f"u{i}", _prompt(i, lens[i % len(lens)]),
             SamplingParams(max_tokens=out + 2 * i, **sampling))
            for i in range(n)]


def _spec_engine(**over):
    return _engine(NAME, spec=True, spec_k=1, spec_min_accept=0.0, **over)


@pytest.fixture(scope="module")
def greedy():
    """What plain greedy decoding emits, request for request (no --spec: the
    trunk alone, fused scans and all)."""
    mp = pytest.MonkeyPatch()
    try:
        got, samples = drive(_engine(NAME), _arrivals(), False, mp)
    finally:
        mp.undo()
    assert {s["mode"] for s in samples} == {"ragged", "decode"}
    assert not any("mtp_rows" in s for s in samples)
    return got


@pytest.mark.parametrize("proposer", ["module", "right", "wrong", "mixed"])
def test_speculative_ids_are_the_greedy_ids_request_for_request(
        proposer, greedy, monkeypatch):
    """Five requests over four slots with `--spec`: chunks beside verify
    spans, every decode row a [t, draft] span through the latent pool. The
    module's own drafts (seeded weights: nearly all rejected), a proposer
    forced right (every draft accepted: two ids a row a step), forced wrong
    (every one rejected and its page position rolled back) and both by
    turns — each under the pipelined loop (a step composed and launched
    while the one before it is unsettled: positions from the length carry,
    pages for the longer case) and under the same loop settled in its tick:
    the ids, texts and finish reasons are greedy decoding's, every page
    comes back (`drive` holds the allocator to rest)."""
    if proposer != "module":
        ids = {name: out[0] for name, out in greedy.items()}
        force_proposer(monkeypatch, _arrivals(), ids, PROPOSERS[proposer])
    eng = _spec_engine()
    rt = _rt(eng)
    assert rt.mtp and rt.proposer == "mtp" and rt.may_overlap()
    for settle_every_step in (False, True):
        was = rt.spec_proposed, rt.spec_accepted, rt.spec_rollbacks
        got, samples = drive(eng, _arrivals(), settle_every_step,
                             monkeypatch)
        assert got == greedy
        assert {s["mode"] for s in samples} <= {"ragged", "spec_verify"}
        drafts = sum(s["mtp_drafts"] for s in samples)
        accepted = sum(s["mtp_accepted"] for s in samples)
        assert drafts == rt.spec_proposed - was[0] > 20
        assert accepted == rt.spec_accepted - was[1]
        assert rt.spec_rollbacks - was[2] == drafts - accepted
        if proposer == "right":
            assert accepted == drafts
        elif proposer == "wrong":
            assert accepted == 0
        elif proposer == "mixed":
            assert 0 < accepted < drafts
        # With ONE draft a row the rejected position is the next token's
        # own (or, behind an unsettled span, inside the next row's claim):
        # a rollback frees a page only where k > 1 (the n-gram test below).
        assert sum(s["spec_rollback_pages"] for s in samples) == 0
        overlapped = sum(s["overlapped"] for s in samples)
        if settle_every_step:
            assert not overlapped
        else:
            assert overlapped >= len(samples) // 2, samples
        # every decode and verify row took its positions from the carry
        assert sum(s["len_carry_rows"] for s in samples) \
            == sum(s["n_decode"] + s.get("mtp_drafts", 0) for s in samples) \
            > 20
        for s in samples:  # the module ran over every token of every step
            assert s["mtp_rows"] == s["tokens"] and s["k_cap"] == 1
            assert s["mla_rows"] == s["tokens"]
            assert s["mla_pairs"] >= s["mla_ctx_rows"] >= s["mla_rows"]
            assert "dsa_ctx_tokens" not in s


def test_the_journal_and_the_counters_carry_the_proposers_kind(monkeypatch):
    from ollamamq_tpu.telemetry import schema as tm

    def count(outcome):
        return tm.SPEC_TOKENS_TOTAL.labels(model=NAME, outcome=outcome,
                                           proposer="mtp").value

    base = count("proposed"), count("rejected")
    eng = _spec_engine()
    recs = []
    real = eng.journal.record
    monkeypatch.setattr(eng.journal, "record", lambda kind, **kw: (
        recs.append((kind, kw)), real(kind, **kw))[1])
    drive(eng, _arrivals(n=2), False, monkeypatch)
    rt = _rt(eng)
    kinds = {k for k, _ in recs}
    assert {"speculate", "spec_verify", "spec_rollback"} <= kinds
    assert all(kw["source"] == "mtp" for k, kw in recs
               if k in ("speculate", "spec_verify", "spec_rollback"))
    assert count("proposed") - base[0] == rt.spec_proposed
    assert count("rejected") - base[1] \
        == rt.spec_proposed - rt.spec_accepted
    assert rt.stats()["spec"]["proposed"] == rt.spec_proposed


def test_a_sampled_row_takes_no_draft_and_the_module_still_sees_it(
        monkeypatch):
    """Module drafts are for greedy rows (the verifier is an argmax): a
    sampled or penalised request rides one-token decode rows of the SAME
    ragged steps — the module runs over them all the same (its cache has a
    row a position), and nothing is speculated for it."""
    eng = _spec_engine()
    arrivals = _arrivals(n=2, temperature=0.7, seed=11) \
        + [(0, "g", _prompt(7, 12), SamplingParams(max_tokens=8, **GREEDY))]
    got, samples = drive(eng, arrivals, False, monkeypatch)
    rt = _rt(eng)
    assert all(len(got[f"u{i}"][0]) == 9 + 2 * i for i in range(2))
    assert {s["mode"] for s in samples} <= {"ragged", "spec_verify"}
    # one draft a step while the greedy request decodes, none after it
    assert rt.spec_proposed == sum(s["mtp_drafts"] for s in samples) <= 7
    assert all(s["mtp_drafts"] <= 1 for s in samples)
    assert not rt._spec_eligible(SimpleReq(arrivals[0][3]))
    assert rt._spec_eligible(SimpleReq(arrivals[2][3]))


class SimpleReq:
    """What `_spec_eligible` reads of a request."""
    user = "nobody"

    def __init__(self, sampling):
        self.sampling = sampling


@pytest.mark.parametrize("model,match", [
    ("test-tiny-lfm2", "--spec: a rejected draft has already advanced"),
    ("test-tiny-olmo-hybrid", "--spec: a rejected draft has already"),
], ids=["conv", "recurrent"])
def test_spec_with_per_sequence_state_is_still_refused(model, match):
    err = refusal(MODEL_CONFIGS[model], spec=True)
    assert err is not None and match in err and "\n" not in err
    assert refusal(PG, spec=True) is None
    with pytest.raises(ValueError, match="--spec"):
        _engine(model, spec=True)


@pytest.mark.parametrize("kw,match", [
    (dict(kv_dtype="int8"), "--kv-dtype int8"),
    (dict(prefix_cache=True), "--prefix-cache: the radix tree"),
    (dict(mesh_shape={"tensor": 2}), "--tp / --ep: the latent"),
], ids=["kv_int8", "prefix_cache", "tp"])
def test_what_the_latent_pool_cannot_do_yet_is_still_refused(kw, match):
    err = refusal(PG, **kw)
    assert err is not None and match in err and "\n" not in err
    assert refusal(PG) is None


def test_without_spec_the_module_is_held_and_not_run(monkeypatch):
    eng = _engine(NAME)
    rt = _rt(eng)
    assert not rt.mtp and rt.draft_ids is None and rt.may_overlap()
    assert rt.cache.kc.shape[0] == 4  # its rows are there all the same
    assert "mtp_eh_proj" in rt.params


def test_ngram_spec_now_verifies_through_the_two_pools_of_the_indexer_model(
        monkeypatch):
    """The refusal PR 39 kept (`--spec` with a latent pool) is gone for every
    latent model: DeepSeek's tiny twin, n-gram drafts, the verify span
    through the latent AND the index-key pool — the greedy ids."""
    name = "test-tiny-deepseek-v32"
    arrivals = [(2 * i, f"u{i}", _prompt(i, 29 + i),
                 SamplingParams(max_tokens=14, **GREEDY)) for i in range(3)]
    plain, _ = drive(_engine(name), arrivals, False, monkeypatch)

    calls = iter(range(10 ** 6))

    def lookup(self, req, slot):  # (seeded weights repeat nothing: a double)
        ids, m = plain[req.user][0], len(req.generated_ids)
        k = min(3, req.sampling.max_tokens - m - 1)
        right = ids[m:m + k]  # every other call's first draft is wrong
        return right if next(calls) % 2 else [(t + 1) % 500 for t in right]

    monkeypatch.setattr(ModelRuntime, "_propose_ngram", lookup)
    eng = _engine(name, spec=True, spec_k=3, spec_min_accept=0.0)
    got, samples = drive(eng, arrivals, False, monkeypatch)
    rt = _rt(eng)
    assert got == plain
    assert not rt.mtp and rt.proposer == "ngram"
    assert 0 < rt.spec_accepted < rt.spec_proposed and rt.spec_rollbacks
    assert any(s["mode"] == "spec_verify" for s in samples)
    assert not any("mtp_rows" in s for s in samples)


# ----------------------------------------- names on the device-side program
def test_the_modules_scopes_are_in_the_lowered_step_and_in_the_readme(
        monkeypatch):
    """`mtp_embed_proj`, `mtp_block`, `mtp_head` (llama.MTP_SCOPES) are whole
    components of op names in the `--spec` runtime's own ragged step, after
    the trunk's; a runtime without `--spec` lowers none of them; README's
    span table lists them."""
    import os
    import re

    from ollamamq_tpu.engine import engine as eng_mod

    # The jit itself, not the first-call wrapper that times the compile.
    monkeypatch.setattr(eng_mod, "_sp_note_compile",
                        lambda rt, site, key, cache, fn: cache.setdefault(
                            key, fn))

    def lowered(rt):
        fn = rt._get_ragged_jit(16, rt.spec_k if rt.mtp else 0,
                                (False, False, False))
        lay = rt.dims.ragged_layout(16)
        args = (rt.params, jnp.zeros((lay.size,), jnp.int32), rt.cache.kc, rt.cache.vc,
                rt.recent, rt.last_ids, rt.cache.slot_state)
        args += (rt.draft_ids, rt.len_ids) if rt.mtp else ()
        return fn.lower(*jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), args)
        ).as_text(debug_info=True)

    def has(text, scope):
        return re.search(r'loc\("(?:[^"/]+/)*%s(?:/[^"]+)?"' % scope, text)

    text = lowered(_rt(_spec_engine()))
    for scope in llama.MTP_SCOPES + mla.SCOPES[:2] + ("mla_attend",
                                                      "moe_shared"):
        assert has(text, scope), scope
    assert has(text, "mtp_block/mla_attend") or has(text, "mtp_block")
    assert not has(text, "dsa_index") and not has(text, "dsa_select")
    plain = lowered(_rt(_engine(NAME)))
    assert not any(has(plain, scope) for scope in llama.MTP_SCOPES)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "README.md"), encoding="utf-8") as f:
        readme = f.read()
    table = readme[readme.index("<!-- stepprof-spans:begin -->"):
                   readme.index("<!-- stepprof-spans:end -->")]
    assert set(llama.MTP_SCOPES) <= set(re.findall(r"`([a-z_.]+)`", table))
