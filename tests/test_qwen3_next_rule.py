"""Qwen3-Next on the served path (PR 47), second file (a file a worker under
`--dist loadfile`): each departure the seeded weights are drawn to catch —
every one must FAIL to agree with the reference —, the chip's share of the
expert layer, and the rule at grouped heads: the step kernel in interpret
mode, the chunked form, the served forwards through the kernels, the pair
loop's pinned row."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ollamamq_tpu.models import moe
from ollamamq_tpu.ops import gated_delta as gd
from test_lfm2 import ATOL, close, seq_tokens
from test_olmo_hybrid import served_through_the_kernels, step_kernel_is_step
from test_qwen3_next import QN, _prefill, make_params, oracle, want
from testutil import moe_mlp, qwen3_next_keys, qwen3_next_reference


def _tiled(q, k, heads):
    """`a_value_head` with the key heads TILED over the value heads (0, 1, 0,
    1) where the model repeats each (0, 0, 1, 1): the wrong pairing."""
    if heads != q.shape[-2]:
        reps = (1,) * (q.ndim - 2) + (heads // q.shape[-2], 1)
        q, k = jnp.tile(q, reps), jnp.tile(k, reps)
    return q, k


@functools.lru_cache(maxsize=None)
def _departure_case():
    """(params, tokens, the reference's last logits) — which the program's
    own oracle forward agrees with."""
    params = make_params()
    tokens = seq_tokens(4, 40)
    ref = want(QN, params, tokens)[-1]
    assert np.abs(oracle(QN, params, tokens) - ref).max() < ATOL
    return params, tokens, ref


DEPARTURES = {
    "w_for_1_plus_w": dict(zero_centred_norm=False),
    "no_attention_gate": dict(attn_output_gate=False),
    "no_shared_expert_gate": dict(shared_expert_gate=False),
    "whole_head_rotated": dict(partial_rotary_factor=1.0),
    "two_sigmoid": dict(linear_allow_neg_eigval=True),
    "wrong_head_pairing": None,
}


@pytest.mark.parametrize("departure", sorted(DEPARTURES))
def test_a_forward_that_departs_does_not_agree(departure, monkeypatch):
    """Each of these computes ANOTHER model on the seeded weights: the
    comparison has teeth only if it says so."""
    params, tokens, ref = _departure_case()
    change = DEPARTURES[departure]
    if change is None:
        monkeypatch.setattr(gd, "a_value_head", _tiled)
        _prefill.clear_cache()  # same config, another trace
        mc = QN
    else:
        mc = dataclasses.replace(QN, **change)
    miss = np.abs(oracle(mc, params, tokens) - ref).max()
    if change is None:
        _prefill.clear_cache()
    assert miss > 25 * ATOL, (departure, miss)


# ------------------------------------------------------ the chip's share
def test_the_shares_routed_parts_and_the_gated_shared_expert_once_add_up():
    """Four shares of four experts each (offsets 0, 4, 8, 12): what their
    routed parts give, with the GATED shared expert — which every chip
    computes alike — counted ONCE, is what the uncut layer gives, in the
    program and in the reference. Gates are normalised over all four chosen
    experts in every share, so no share knows the others."""
    uncut = dataclasses.replace(QN, num_experts=16, router_experts=16)
    share4 = dataclasses.replace(QN, num_experts=4)
    params = make_params(uncut)
    names = ("w_router",) + moe.SHARED + moe.SHARED_GATE + moe.STACKED
    lp = {k: v[0] if k not in moe.STACKED else v
          for k, v in params["layers"].items() if k in names}
    h = jax.random.normal(jax.random.PRNGKey(3), (1, 24, QN.hidden_size))
    whole, load = moe_mlp(uncut, lp, h, layer=0)
    assert int(load.sum()) == 24 * 4
    shared = jax.nn.sigmoid(h @ lp["w_shared_gate"])[..., None] * jnp.einsum(
        "btf,fd->btd", jax.nn.silu(h @ lp["ws_gate"]) * (h @ lp["ws_up"]),
        lp["ws_down"])
    total, loads = shared, []
    for first in (0, 4, 8, 12):
        share = dataclasses.replace(share4, expert_offset=first)
        held = dict(lp, **{k: lp[k][:, first:first + 4]
                           for k in moe.STACKED})
        part, load = moe_mlp(share, held, h, layer=0)
        total = total + (part - shared)
        loads.append(int(load.sum()))
    assert sum(loads) == 24 * 4 and min(loads) >= 0
    assert float(jnp.abs(total - whole).max()) < 1e-5
    # ... and the reference's uncut layer says the same
    ref = qwen3_next_reference()

    def mm(a, w):
        return jnp.matmul(a, w.astype(jnp.float32), precision=ref.HI)

    plain = ref._experts(qwen3_next_keys(uncut), mm, h[0],
                         params["layers"], 0)
    assert float(jnp.abs(plain - whole[0]).max()) < 1e-5
    # a share's reference is the share's program
    at8 = dataclasses.replace(share4, expert_offset=8)
    lp8 = dict(params["layers"], **{k: params["layers"][k][:, 8:12]
                                    for k in moe.STACKED})
    part, _ = moe_mlp(at8, dict(lp, **{k: lp[k][:, 8:12]
                                       for k in moe.STACKED}), h, layer=0)
    assert float(jnp.abs(ref._experts(qwen3_next_keys(at8), mm, h[0], lp8, 0)
                         - part[0]).max()) < 1e-5


# ------------------------------------------- the rule at grouped heads
@pytest.mark.parametrize("key_heads,heads,dk,dv,live", [
    (2, 4, 8, 16, [1, 0, 1, 1, 0, 1]),     # the tiny model's
    (2, 4, 8, 128, [1, 0, 1, 1, 0, 1]),    # the published value width:
    (4, 16, 8, 128, [0, 0, 1, 0, 0, 1]),   # one head a lane group
], ids=["tiny", "dv128", "four_a_key_head"])
def test_the_step_kernel_at_grouped_heads_in_interpret_mode_is_step(
        key_heads, heads, dk, dv, live):
    rows, q, k, v, g, beta, reset, o_ref = step_kernel_is_step(
        key_heads, heads, dk, dv, live, strongest=1.0)
    # ... and `step` at grouped heads is `step` on the repeated heads
    rep = heads // key_heads
    o_rep, _ = gd.step(rows, jnp.repeat(q, rep, axis=1),
                       jnp.repeat(k, rep, axis=1), v, g, beta, reset)
    close(o_ref, np.asarray(o_rep), atol=1e-6)


def test_the_published_widths_meet_head_blocks_of_sixteen():
    from ollamamq_tpu.ops.pallas.gated_delta_step import head_blocks

    assert head_blocks(32, 128, 128) == (1, 16)   # Qwen3-Next's
    assert head_blocks(30, 96, 192) == (2, 10)    # Olmo-Hybrid's, unchanged


def test_chunked_at_grouped_heads_is_the_token_serial_recurrence():
    rng = np.random.default_rng(3)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    t, hk, h, dk, dv = 70, 2, 4, 8, 16
    q, k, v = f(t, hk, dk), f(t, hk, dk) + 1, f(t, h, dv)
    g, beta = -jnp.abs(f(t, h)) * 0.3, jax.nn.sigmoid(f(t, h))
    o, s = jax.jit(gd.chunked)(q[None], k[None], v[None], g[None],
                               beta[None])
    st, outs, step = jnp.zeros((dk, h * dv)), [], jax.jit(gd.step)
    for i in range(t):
        o_i, st = step(st, q[i], k[i], v[i], g[i], beta[i])
        outs.append(o_i)
    close(o[0], np.asarray(jnp.stack(outs)), atol=2e-5)
    close(s[0], np.asarray(st), atol=2e-5)


def test_the_served_forwards_through_the_kernels_match_the_jnp_path(
        monkeypatch):
    """forward_ragged with `attn_impl` pallas in interpret mode (the Pallas
    attention kernel at a partial rotary embedding's heads, the step kernel
    at grouped heads, the grouped matmul) against the jnp path."""
    gmm = moe.grouped_matmul
    monkeypatch.setattr(moe, "grouped_matmul", lambda impl, xs, w, sizes:
                        gmm(impl, xs, w, sizes, interpret=True))
    served_through_the_kernels(QN, make_params(), 5 * ATOL)


def test_the_pair_loop_pins_a_row_at_a_whole_lane_tile_key_dim():
    """dk = 128 (the published width): the sliced row carries a layout
    constraint (`gated_delta._row_major`), on any backend, and the spans'
    chunked form is still the whole-sequence one."""
    rng = np.random.default_rng(0)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    t, hk, h, dk, dv = 80, 1, 2, 128, 128
    q, k, v = f(t, hk, dk), f(t, hk, dk), f(t, h, dv)
    g, beta = -jnp.abs(f(t, h)) * 0.3, jax.nn.sigmoid(f(t, h))
    o, new = jax.jit(gd.ragged)(
        q, k, v, g, beta, jnp.zeros((2, 3, dk, h * dv)), jnp.int32(1),
        jnp.asarray([1, 2], jnp.int32), jnp.zeros(t, jnp.int32),
        jnp.arange(t, dtype=jnp.int32), jnp.asarray([0, t], jnp.int32),
        jnp.asarray([t, 0], jnp.int32), jnp.asarray([1, 0], jnp.int32))
    o_ref, s_ref = jax.jit(gd.chunked)(q[None], k[None], v[None], g[None],
                                       beta[None])
    close(o, np.asarray(o_ref[0]), atol=1e-6)
    close(new[1, 1], np.asarray(s_ref[0]), atol=1e-6)
    assert bool(jnp.all(new[0] == 0.0)) and bool(jnp.all(new[1, 2] == 0.0))
