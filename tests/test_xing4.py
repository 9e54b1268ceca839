"""Xing4.0 on the served path (PR 69): a residual path of FOUR streams mixed
by manifold-constrained hyper-connections (ops/hyper_connection.py) around
DeepSeek-V3's blocks — latent attention with YaRN, a dense layer, then expert
layers whose 64 (here 8) experts are ALL held, a sigmoid router with a
selection bias and no groups, a shared expert.

LOGITS of the served forwards against the benchmark's plain float32 reference
(benchmarks/reference/xing4_decoder.py: the connection line by line, expanded
heads, a dense causal softmax, no cache) at `test-tiny-xing4`, seeded random
weights, float32, on the CPU; twelve wrong forwards that each miss the
tolerance; the mapping's own properties (doubly stochastic, the clamp, the
epsilon); the tie of four streams to one; both kernels in interpret mode
against their jnp twins; and the guard for every other model: with `hc_mult`
0 the step programs hold nothing of this."""

import dataclasses
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ollamamq_tpu.config import (ATTENTION, MODEL_CONFIGS, EngineConfig)
from ollamamq_tpu.engine.kv_cache import (STATE_REFUSES, refusal,
                                          state_held)
from ollamamq_tpu.engine import step_work
from ollamamq_tpu.models import llama
from ollamamq_tpu.ops import hyper_connection as hc
from ollamamq_tpu.telemetry import mfu, schema
from test_deepseek_v32 import page_table, pools, seq_tokens, serve
from testutil import (_reference, deepseek_v32_keys, once_a_sequence,
                      seeded_params)

NAME = "test-tiny-xing4"
XG = MODEL_CONFIGS[NAME]
PS = 8
# float32 logits (sd ~0.65) of two float32 forwards that order their sums
# differently (absorbed against expanded heads, pages against a dense square,
# (x Phi) r against (x r) Phi): 2e-4 is ~12 x what they read here (1.7e-5 over
# chunks and fused scans) and under a fifth of what the mildest wrong forward
# reads (the table below), or bfloat16 inside the mapping alone.
ATOL = 2e-4
NORMS = ("attn_norm", "mlp_norm", "mla_q_norm", "mla_kv_norm")
K = hc.consts(XG)


def xing4_reference():
    return _reference("xing4_decoder")


def xing4_keys(mc) -> dict:
    """What a configuration file says of the ModelConfig `mc`, in the
    published spellings: all that reference reads."""
    return {**deepseek_v32_keys(mc), "n_group": 1, "topk_group": 1,
            "hc_mult": mc.hc_mult, "hc_sinkhorn_iters": mc.hc_sinkhorn_iters,
            "hc_eps": mc.hc_eps,
            "mhc_h_res_clamp_min": mc.mhc_h_res_clamp_min,
            "mhc_h_res_clamp_max": mc.mhc_h_res_clamp_max,
            "num_nextn_predict_layers": mc.num_nextn_predict_layers}


def make_params(mc=XG, dtype=jnp.float32, seed=0):
    return seeded_params(mc, NORMS, dtype, seed, top_norms=("final_norm",))


@once_a_sequence
def want(params, tokens, mc=XG):
    """The reference's ONE full forward: [T, V] logits."""
    return np.asarray(xing4_reference().logits(
        xing4_keys(mc), params, np.asarray(tokens, np.int32)))


def oracle(params, tokens, mc=XG):
    """The program's logits at the last position, one dense causal pass
    under a jit of ITS OWN (a patched forward is traced afresh)."""
    kc, vc = pools(mc)
    run = jax.jit(lambda p, t, n, kc, vc, pt: llama.forward_prefill(
        p, mc, t, n, kc, vc, pt, PS))
    return np.asarray(run(params, jnp.asarray(tokens[None]),
                          jnp.asarray([len(tokens)]), kc, vc,
                          jnp.asarray(page_table()[:1]))[0][0])


# ----------------------------------------------------------- the config
def test_the_tiny_family_its_streams_its_pool_and_its_counts():
    assert XG.streams == 4 and XG.hc_maps == 24
    assert XG.kinds[0] == (ATTENTION, "dense") and XG.num_dense_layers == 1
    assert (XG.num_experts, XG.router_width, XG.n_group) == (8, 8, 0)
    assert XG.use_expert_bias and XG.router_score == "sigmoid"
    params = make_params()
    n = sum(a.size for a in jax.tree_util.tree_leaves(params))
    ref, keys = xing4_reference(), xing4_keys(XG)
    assert n == XG.param_count() == ref.param_count(keys)
    ref.served_layout(keys, params)
    lp = params["layers"]
    assert lp["hc_attn_phi"].shape == (3, 24, 256) \
        and lp["hc_mlp_alpha"].shape == (3, 3) \
        and params["hc_head_phi"].shape == (4, 256)
    assert all(lp[name].dtype == jnp.float32 for name in llama.MHC_PARAMS)
    # the published widths, the first stage's depth (the file's arithmetic)
    d6 = dataclasses.replace(
        MODEL_CONFIGS["xing4.0:29b-a4b"], num_layers=6,
        first_k_dense_replace=1, num_dense_layers=1)
    assert d6.param_count() == ref.param_count(xing4_keys(d6)) \
        == 4_792_727_177  # 9.585 GB in bf16
    assert d6.kv_row_dims == (640, 0) and d6.cache_layers == 6
    m = 0.1 * np.log(64) + 1
    assert abs(d6.attn_scale - 192 ** -0.5 * m * m) < 1e-9
    # one stream: nothing of the connection is drawn or counted
    one = dataclasses.replace(XG, hc_mult=0)
    assert not one.streams and XG.param_count() - one.param_count() \
        == 3 * 2 * (256 * 24 + 24 + 3) + 256 * 4 + 4 + 1
    assert not set(llama.MHC_PARAMS) & set(jax.eval_shape(
        lambda: llama.init_params(one, jax.random.PRNGKey(0)))["layers"])
    assert llama.MHC_SCOPES == schema.MHC_SCOPES
    assert mfu.flops_per_token(XG) - mfu.flops_per_token(one) \
        == 2 * (XG.param_count(active=True) - one.param_count(active=True)) \
        + 2 * 64 * (6 * 24 + 4)
    assert hc.flops_per_token(4, 3584) == 2 * 14336 * 24 + 24 * 3584 * 2
    # the loader's (assumed) names reach every tensor of the connection
    from ollamamq_tpu.models import weights

    named = {ours for hf, (ours, _) in weights._HF_LAYER_MAP.items()
             if hf.startswith(("attn_hc.", "mlp_hc."))}
    assert named == set(llama.MHC_PARAMS) <= set(weights._FLOAT32_KEYS)
    assert set(weights._HC_HEAD.values()) == {
        k for k in params if k.startswith("hc_head_")}


@pytest.mark.parametrize("base,bad,match", [
    (XG, dict(hc_mult=2), "hc_mult 2"),
    (XG, dict(hc_mult=8), "hc_mult 8"),
    (XG, dict(hc_sinkhorn_iters=0), "hc_sinkhorn_iters 0"),
    (XG, dict(hc_eps=0.0), "hc_eps 0.0"),
    (XG, dict(mhc_h_res_clamp_min=30.0), "mhc_h_res_clamp_min 30.0"),
    (XG, dict(num_nextn_predict_layers=1), "ROADMAP B-M12"),
    (XG, dict(sandwich_norm=True), "ROADMAP B-M12"),
    (MODEL_CONFIGS["test-tiny"], dict(hc_mult=4, norm_order="post"),
     "ROADMAP B-M12"),
    (MODEL_CONFIGS["test-tiny-openpangu"], dict(hc_mult=4),
     "num_nextn_predict_layers 1 with hc_mult 4"),
], ids=["two_streams", "eight_streams", "no_iteration", "no_epsilon",
        "clamp_ends", "module", "sandwich", "post_norm", "openpangu_module"])
def test_streams_the_program_cannot_run_are_refused_by_the_keys_name(
        base, bad, match):
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(base, **bad)


@pytest.mark.parametrize("kw,match", [
    (dict(spec=True), "--spec"),
    (dict(mesh_shape={"tensor": 2}), "--tp / --ep"),
    (dict(mesh_shape={"expert": 4}), "--tp / --ep"),
], ids=["spec", "tp", "ep"])
def test_what_four_streams_are_not_served_with_is_told_before_the_device(
        kw, match):
    why = refusal(XG, **kw)
    assert match in why
    if "spec" in kw:  # the one flag of the three that the streams alone refuse
        assert "ROADMAP B-M12" in why and "4 streams" in why
    else:  # the pools' line is asked first; the streams' stands behind it
        assert "latent" in why and state_held(XG) == ["latent", "streams"]
        assert "ROADMAP B-M12" in STATE_REFUSES["streams"][1]["mesh"]
    assert refusal(XG) is None
    assert refusal(MODEL_CONFIGS["test-tiny"], **kw) is None


# ------------------------------------------- the forwards, in float32 logits
@pytest.mark.parametrize("chunk", [16, 7, 44], ids=["c16", "c7", "whole"])
def test_prefill_in_chunks_then_the_fused_scan_matches_the_reference(chunk):
    """Chunked ragged prefill through the latent pool (a second request
    beside it), then fused scans of four passes: every logit read agrees
    with the reference's ONE full forward."""
    params = make_params()
    tokens = seq_tokens(1, 60)
    ref = want(params, tokens)
    got = serve(params, tokens, 44, chunk, XG)
    assert len(got) >= 16 + 44 // chunk - 1
    for pos, logits in got.items():
        assert np.abs(logits - ref[pos]).max() < ATOL, pos


def test_the_oracle_and_another_seed_follow():
    for seed, n in ((0, 56), (3, 41)):
        params = make_params(seed=seed)
        tokens = seq_tokens(seed + 2, n + 1)
        assert np.abs(oracle(params, tokens[:n])
                      - want(params, tokens)[n - 1]).max() < ATOL


# ---------------------------- change one line and it fails the tolerance
def _swapped(m, iters, eps):
    def once(_, m):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)  # rows first
        return m / (jnp.sum(m, axis=-2, keepdims=True) + eps)

    return jax.lax.fori_loop(0, iters, once, m)


def _mix_in_with(pre_of=None, flat_of=None, product=None):
    """`hc.mix_in`'s jnp path with one line changed: H_pre of its logits,
    the normed flattened streams, or the product's operands."""
    def mix_in(x, phi, alpha, bias, k, impl="jnp", interpret=False):
        n, T = k.n, x.shape[0]
        xf = x.astype(jnp.float32)
        flat = (flat_of or _all_lanes)(xf, k)
        a = (product or (lambda u, p: jnp.einsum(
            "tk,mk->tm", u, p, precision=jax.lax.Precision.HIGHEST)))(
                flat, phi)
        z = jnp.repeat(alpha, jnp.array([n, n, n * n]),
                       total_repeat_length=n * (n + 2)) * a + bias
        pre = pre_of(z[:, :n], k) if pre_of \
            else jax.nn.sigmoid(z[:, :n]) + k.eps
        post = 2.0 * jax.nn.sigmoid(z[:, n:2 * n])
        res = hc.res_map(z[:, 2 * n:], k)
        h = jnp.einsum("tj,tjc->tc", pre, xf)
        return h.astype(x.dtype), jnp.concatenate(
            [pre, post, res.reshape(T, -1)], axis=-1)

    return mix_in


def _all_lanes(xf, k):
    flat = xf.reshape(xf.shape[0], -1)
    return flat * jax.lax.rsqrt(
        jnp.mean(flat * flat, axis=-1, keepdims=True) + k.norm_eps)


def _per_stream(xf, k):
    r = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + k.norm_eps)
    return (xf * r).reshape(xf.shape[0], -1)


def _bf16_product(u, phi):
    return jnp.einsum("tk,mk->tm", u.astype(jnp.bfloat16),
                      phi.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


def _sum_before_ffn(plain):
    def stream_in(cfg, lp, name, x, impl):
        h, maps = plain(cfg, lp, name, x, impl)
        return (x.sum(axis=2) if name == "hc_mlp" else h), maps

    return stream_in


WRONG = {
    # name: (config changes, weights to zero, [(module, attribute, wrong)])
    "one_sinkhorn_iteration": (dict(hc_sinkhorn_iters=1), (), ()),
    "h_res_identity": ({}, (), [(hc, "res_map", lambda z, k: jnp.broadcast_to(
        jnp.eye(k.n), z.shape[:-1] + (k.n, k.n)))]),
    "h_res_transposed": ({}, (), [(hc, "res_map", lambda z, k, f=hc.res_map:
                                   jnp.swapaxes(f(z, k), -1, -2))]),
    "h_post_without_the_2": ({}, (), [(
        hc, "mix_out", lambda x, d, maps, k, impl="jnp", f=hc.mix_out: f(
            x, d, maps.at[:, k.n:2 * k.n].multiply(0.5), k))]),
    "h_pre_without_sigmoid": ({}, (), [(hc, "mix_in", _mix_in_with(
        pre_of=lambda z, k: z + k.eps))]),
    "norm_a_stream": ({}, (), [(hc, "mix_in", _mix_in_with(
        flat_of=_per_stream))]),
    "read_out_plain_sum": ({}, (), [(
        hc, "read_out", lambda x, phi, alpha, bias, k, impl="jnp":
        x.sum(axis=1))]),
    "streams_summed_before_the_ffn": ({}, (), [(
        llama, "_stream_in", _sum_before_ffn(llama._stream_in))]),
    "gates_not_times_2": (dict(routed_scaling_factor=1.0), (), ()),
    "selection_bias_dropped": ({}, ("router_bias",), ()),
    "shared_expert_dropped": ({}, ("ws_down",), ()),
    "bfloat16_inside_the_mapping": ({}, (), [(hc, "mix_in", _mix_in_with(
        product=_bf16_product))]),
}


def test_the_changed_mix_in_is_the_twin_where_nothing_is_changed():
    """(the table's instrument: with no line changed it IS `hc.mix_in`)"""
    x = jax.random.normal(jax.random.PRNGKey(1), (9, 4, 64))
    lp = make_params()["layers"]
    args = (x, lp["hc_attn_phi"][1], lp["hc_attn_alpha"][1],
            lp["hc_attn_b"][1], K)
    for a, b in zip(hc.mix_in(*args), _mix_in_with()(*args)):
        assert np.abs(np.asarray(a) - np.asarray(b)).max() < 1e-6


@pytest.mark.parametrize("piece", sorted(WRONG))
def test_a_forward_with_one_line_changed_fails(piece, monkeypatch):
    """Each is a model of its own: its logits at the last position are
    further from the reference's than five times the tolerance the served
    forward is held to."""
    changes, zeroed, patches = WRONG[piece]
    params = make_params()
    tokens = seq_tokens(4, 57)
    ref = want(params, tokens)[55]
    assert np.abs(oracle(params, tokens[:56]) - ref).max() < ATOL
    for name in zeroed:
        params["layers"][name] = jnp.zeros_like(params["layers"][name])
    for module, attribute, wrong in patches:
        monkeypatch.setattr(module, attribute, wrong)
    got = oracle(params, tokens[:56], dataclasses.replace(XG, **changes))
    assert np.abs(got - ref).max() > 5 * ATOL, np.abs(got - ref).max()


# ------------------------------------------------- the mapping on its own
def test_h_res_is_doubly_stochastic_as_far_as_twenty_iterations_reach():
    """Rows are normalised last: they sum to 1 within 1e-5 for ANY logits in
    [-30, 30]. Columns do where the iteration has converged — logits of the
    seeded weights' order (|z| <= 1: within 1e-5 too) — and not for logits
    spread over the whole clamp range, whose matrix spans e^60 and which
    twenty iterations leave up to ~1 off (measured here; the published
    iteration count is what it is: the reference iterates as often)."""
    for scale, cols in ((30.0, None), (1.0, 1e-5)):
        z = jax.random.uniform(jax.random.PRNGKey(0), (512, 16),
                               minval=-scale, maxval=scale)
        m = np.asarray(hc.res_map(z, K))
        assert (m >= 0).all()
        assert np.abs(m.sum(axis=2) - 1).max() < 1e-5
        if cols:
            assert np.abs(m.sum(axis=1) - 1).max() < cols
    # ...and one more iteration moves a converged matrix by nothing
    again = np.asarray(hc.sinkhorn(jnp.asarray(m), 1, K.eps))
    assert np.abs(again - m).max() < 1e-5


def test_columns_come_before_rows_inside_an_iteration():
    """The order shows where twenty iterations have NOT converged (logits
    over the clamp's range): the program's H_res is the reference's to 1e-5
    there, rows before columns is another matrix (columns then sum to 1 and
    rows do not). On the seeded weights' logits the iteration converges and
    both orders reach the same fixed point — no forward can tell them apart
    there (8e-6 in a logit), which is why this case is held on the mapping
    and not in the table below."""
    ref, keys = xing4_reference(), xing4_keys(XG)
    z = jax.random.uniform(jax.random.PRNGKey(7), (64, 16), minval=-30.0,
                           maxval=30.0)
    got = np.asarray(hc.res_map(z, K))
    bias = jnp.concatenate([jnp.zeros(8), z[0]])
    want_res = np.asarray(ref.mappings(
        keys, jnp.ones((1, 4, 64)), jnp.zeros((24, 256)), jnp.zeros((3,)),
        bias)[2])
    assert np.abs(got[0] - want_res[0]).max() < 1e-5
    swapped = np.asarray(_swapped(jnp.exp(z).reshape(-1, 4, 4), K.iters,
                                  K.eps))
    assert np.abs(swapped - got).max() > 0.1
    assert np.abs(swapped.sum(axis=1) - 1).max() < 1e-5 \
        < np.abs(swapped.sum(axis=2) - 1).max()


def test_the_mapping_is_the_references_and_the_clamp_and_epsilon_are_in_it():
    """On the mapping alone, logits crafted past the clamp (alpha 0, the
    biases ARE the logits): a row of [40, 35, 0, 0] is [30, 30, 0, 0] after
    it — two equal weights — where an unclamped exp keeps e^5 between them;
    and where H_pre's sigmoid is ~1e-13 (a bias of -30) epsilon is all of
    it."""
    ref, keys = xing4_reference(), xing4_keys(XG)
    x = jax.random.normal(jax.random.PRNGKey(2), (5, 4, 64))
    phi = jax.random.normal(jax.random.PRNGKey(3), (24, 256)) / 16
    alpha = jnp.zeros((3,))
    bias = jnp.concatenate([jnp.array([-30.0, 0.0, 1.0, 30.0]), jnp.zeros(4),
                            jnp.array([40.0, 35, 0, 0, 0, 40, 35, 0, 0, 0,
                                       40, 35, 35, 0, 0, 40])])
    pre, post, res = hc.split_maps(hc.mix_in(x, phi, alpha, bias, K)[1], 4)
    want_pre, want_post, want_res = map(np.asarray, ref.mappings(
        keys, x, phi, alpha, bias))
    assert np.abs(np.asarray(res) - want_res).max() < 1e-6
    assert np.abs(np.asarray(post) - want_post).max() < 1e-6
    assert np.abs(np.asarray(pre) / want_pre - 1).max() < 1e-6
    assert abs(float(pre[0, 0]) / 1e-6 - 1) < 1e-3  # epsilon, not 9e-14
    assert abs(float(res[0, 0, 0]) - 0.5) < 1e-3 \
        and abs(float(res[0, 0, 1]) - 0.5) < 1e-3
    loose = K._replace(lo=-1e9, hi=1e9)
    unclamped = np.asarray(hc.res_map(bias[8:][None], loose))
    assert np.abs(unclamped - want_res[0]).max() > 0.4
    no_eps = jax.nn.sigmoid(bias[:4])
    assert float(no_eps[0]) < 1e-12 < 1e-6 * 0.999 < float(pre[0, 0])
    # a real sublayer's: the seeded weights' dynamic part matters
    lp = make_params()["layers"]
    maps = hc.mix_in(x, lp["hc_mlp_phi"][2], lp["hc_mlp_alpha"][2],
                     lp["hc_mlp_b"][2], K)[1]
    assert float(jnp.std(maps, axis=0).min()) > 0.01  # across tokens


def test_four_streams_tied_to_one_are_the_one_stream_model():
    """alpha 0 and the biases at the clamp's ends: H_pre ~ e_0 (sigmoid(+-
    30)), H_post ~ e_0 (2 sigmoid(0) = 1 on stream 0), H_res ~ I, the
    read-out on stream 0 — stream 0 is then the residual of the one-stream
    model with the same sublayer weights, and the logits agree within the
    float32 tolerance (epsilon leaks 1e-6 of the other streams into h)."""
    params = make_params()
    one = dataclasses.replace(XG, hc_mult=0)
    plain = {k: v for k, v in params.items() if not k.startswith("hc_head")}
    plain["layers"] = {k: v for k, v in params["layers"].items()
                       if k not in llama.MHC_PARAMS}
    e0 = jnp.array([30.0, -30, -30, -30])
    tie_b = jnp.concatenate([e0, jnp.array([0.0, -30, -30, -30]),
                             (60 * jnp.eye(4) - 30).reshape(-1)])
    tied = dict(params, hc_head_alpha=jnp.zeros((1,)), hc_head_b=e0)
    tied["layers"] = dict(params["layers"])
    for name in ("hc_attn", "hc_mlp"):
        tied["layers"][name + "_alpha"] = jnp.zeros((3, 3))
        tied["layers"][name + "_b"] = jnp.broadcast_to(tie_b, (3, 24))
    tokens = seq_tokens(8, 40)
    got, want_one = oracle(tied, tokens), oracle(plain, tokens, one)
    assert np.abs(got - want_one).max() < ATOL
    assert np.abs(oracle(params, tokens) - want_one).max() > 50 * ATOL


# ------------------------------------- the kernels, in interpret mode
@pytest.mark.parametrize("rows,width,dtype", [
    (8, 64, jnp.float32), (64, 64, jnp.float32), (512, 64, jnp.bfloat16),
    (8, 3584, jnp.bfloat16), (64, 3584, jnp.float32),
    (512, 3584, jnp.bfloat16)],
    ids=["8x64", "64x64", "512x64_bf16", "8x3584_bf16", "64x3584",
         "512x3584_bf16"])
def test_both_launches_are_their_jnp_twins(rows, width, dtype):
    """`mhc_mix_in_pallas` (and the read-out through it) and
    `mhc_mix_out_pallas` at tiles of 8, 64 and 512 rows, n = 4: the maps to
    float32 rounding (2e-6: another order of the same sums), the streams to
    that — or, bfloat16 streams, to an ulp of theirs."""
    ks = jax.random.split(jax.random.PRNGKey(rows + width), 5)
    x = jax.random.normal(ks[0], (rows, 4, width)).astype(dtype)
    d = jax.random.normal(ks[1], (rows, width)).astype(dtype)
    phi = jax.random.normal(ks[2], (24, 4 * width)) / np.sqrt(4 * width)
    alpha = jnp.array([1.0, 0.7, 1.3])
    bias = jax.random.normal(ks[3], (24,)) + jnp.concatenate(
        [jnp.zeros(8), 1.5 * jnp.eye(4).reshape(-1)])
    ulp = 2e-5 if dtype == jnp.float32 else 2.0 ** -6  # of |x| < 8

    def far(a, b):
        return float(jnp.abs(a.astype(jnp.float32)
                             - b.astype(jnp.float32)).max())

    h0, m0 = hc.mix_in(x, phi, alpha, bias, K)
    h1, m1 = hc.mix_in(x, phi, alpha, bias, K, "pallas", interpret=True)
    assert m1.shape == (rows, 128) and h1.dtype == dtype
    assert far(m0, m1[:, :24]) < 2e-6 and float(jnp.abs(m1[:, 24:]).max()) == 0
    assert far(h0, h1) <= ulp
    out0 = hc.mix_out(x, d, m0, K)
    out1 = hc.mix_out(x, d, m1, K, "pallas", interpret=True)
    assert out1.shape == x.shape and far(out0, out1) <= 2 * ulp
    y0 = hc.read_out(x, phi[:4], alpha[:1], bias[:4], K)
    y1 = hc.read_out(x, phi[:4], alpha[:1], bias[:4], K, "pallas",
                     interpret=True)
    assert y1.shape == (rows, width) and far(y0, y1) <= ulp


# ------------------------------------------------ the step's work account
def test_step_samples_carry_the_connections_rows_for_this_model_alone():
    class Sample(dict):
        def note(self, **kw):
            self.update(kw)

    work = step_work.StepWork(XG, PS, NAME)
    sp = Sample()
    work.note(sp, [1, 5, 16], [9, 5, 16], [1, 1, 0], stream_len=32, opened=1)
    assert (sp["mhc_rows"], sp["mhc_apps"]) == (22, 7)
    sp = Sample()
    work.note(sp, [4, 4], [20, 9], scan=True)
    assert (sp["mhc_rows"], sp["mhc_apps"]) == (8, 7)
    assert schema.MHC_SAMPLE_FIELDS == step_work.KINDS["mhc"].fields
    for other in ("test-tiny", "test-tiny-openpangu",
                  "test-tiny-deepseek-v32"):
        sp = Sample()
        step_work.StepWork(MODEL_CONFIGS[other], PS, other).note(
            sp, [1, 5], [9, 5], [1, 1], stream_len=16, opened=1)
        assert not set(schema.MHC_SAMPLE_FIELDS) & set(sp)


# --------------------------- the guard for every model with ONE stream
@functools.cache
def _lowered_step_programs(name, **changes):
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "scripts"))
    import step_hlo_copies as shc
    from ollamamq_tpu.engine import step_program

    mc = dataclasses.replace(MODEL_CONFIGS[name], **changes)
    dims = step_program.StepDims(PS, 4, 8, EngineConfig.repeat_last_n)
    args = shc.step_args(mc, dims, jax.devices()[:1], num_pages=40,
                         ring_tokens=64, default_layouts=True)
    every = (True, True, True)
    lowered = {
        "mq_ragged_step": args.lower(step_program.ragged_step(
            mc, dims, 32, 0, every, attn_impl="jnp", mesh=None),
            dims.ragged_layout(32).size),
        "mq_decode_scan": args.lower(step_program.decode_scan(
            mc, dims, 2, every, attn_impl="jnp", mesh=None),
            dims.decode_layout().size)}
    # (the program, and the program with its ops' scopes and operand names)
    return {prog: (low.as_text(), low.as_text(debug_info=True))
            for prog, low in lowered.items()}


@pytest.mark.parametrize("name", ["test-tiny", "test-tiny-openpangu"],
                         ids=["dense", "latent_sparse"])
def test_one_stream_step_programs_hold_nothing_of_the_connection(name):
    """`mq_ragged_step` and `mq_decode_scan` of a dense preset and of a
    latent sparse one, `hc_mult` 0 (and 1: the same): no `mhc` scope, no
    `hc_*` operand, no carry of rank 4 over [., tokens, streams, hidden] —
    and the SAME text whichever way one stream is spelled."""
    mc = MODEL_CONFIGS[name]
    assert not mc.streams
    texts = _lowered_step_programs(name)
    spelled = _lowered_step_programs(name, hc_mult=1)
    # the residual's shape were it streams: [1, tokens, 4, D] in the ragged
    # step, [slots, 1, 4, D] in a pass of the scan
    carry = {"mq_ragged_step": "tensor<1x32x4x%dx", "mq_decode_scan":
             "tensor<4x1x4x%dx"}
    for prog, (text, named) in texts.items():
        assert text == spelled[prog][0], prog
        assert "mhc_" not in named and "hc_attn" not in named \
            and "hc_head" not in named, prog
        assert carry[prog] % mc.hidden_size not in text, prog
    # ...where THIS model's programs do hold them, so the words would show
    for prog, (text, named) in _lowered_step_programs(NAME).items():
        assert all(s in named for s in llama.MHC_SCOPES), prog
        assert "hc_attn_phi" in named and "hc_head_phi" in named, prog
        assert carry[prog] % XG.hidden_size in text, prog
