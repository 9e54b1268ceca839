"""The device LAYOUT of the weights (PR 45, PR 51): the stacks whose
contraction the chip's compiler reads with the contracted dimension MINOR live
on one device layer-major in that order, which every step program re-laid them
into from row-major before — the two latent up-projection stacks, `mla_wuq`
and `mla_wukv` (654 MB a pass at the published widths), and `wq` / `wk` of a
model whose `_qkv` splits the projections into heads at once (no q/k norm, or
a per-head one: K-EXAONE's 100 MB of `wq` a layer), NOT of one that norms the
flat result first (OLMoE, Olmo-Hybrid: their compiler reads `wq` row-major).
`models/llama.py:weight_formats` is the rule (a pure function of the config
and the leaves' names and shardings), `weights.init_random` bears an expert
model's stacks so, `weights.place_formats` re-lays a loaded tree and a dense
one drawn eagerly, and a runtime's step programs are compiled for the layout
of the arrays they are handed. Names, logical shapes and values never change:
the benchmark's references read `runtime.params` by both. On the CPU, which
honours the same `Format`: the time is the chip's to say."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from ollamamq_tpu.config import MODEL_CONFIGS
from ollamamq_tpu.engine import engine
from ollamamq_tpu.models import llama, weights
from ollamamq_tpu.ops.quant import quantize_tensor
from test_step_overlap import _engine, _greedy, _rt, _wave, drive
from testutil import (deepseek_v32_keys, deepseek_v32_reference,
                      openpangu_keys, openpangu_reference)

LATENT = ("test-tiny-deepseek-v32", "test-tiny-openpangu")
# every other decoder family the program serves, at its toy size: those whose
# `_qkv` splits q and k into heads at once, and those that norm them flat
SPLIT = ("test-tiny", "test-tiny-gqa", "test-tiny-qwen3", "test-tiny-moe",
         "test-tiny-lfm2", "test-tiny-k-exaone", "test-tiny-qwen3-next")
FULL_NORM = ("test-tiny-olmoe", "test-tiny-olmo-hybrid")
STACKS = ("mla_wuq", "mla_wukv")
QK = ("wq", "wk")
RANK_MINOR = (0, 2, 1)


def stacks_of(name):
    return STACKS if name in LATENT else () if name in FULL_NORM else QK


def order(leaf):
    return tuple(leaf.format.layout.major_to_minor)


def shapes(mc):
    return jax.eval_shape(
        lambda: llama.init_params(mc, jax.random.PRNGKey(0)))


@pytest.mark.parametrize("name", LATENT + SPLIT + FULL_NORM)
def test_what_the_rule_names_for_each_family(name):
    """The two latent stacks of a latent model; `wq` and `wk` where `_qkv`
    splits the projections into heads at once, nothing where it norms the flat
    result first — the one observation `_qkv` itself branches on. Each
    layer-major with the contracted dimension minor, on one device."""
    mc = MODEL_CONFIGS[name]
    assert llama.splits_heads_at_once(mc) == (name not in FULL_NORM)
    formats = llama.weight_formats(mc, shapes(mc))
    assert sorted(formats) == sorted(stacks_of(name))
    for fmt in formats.values():
        assert tuple(fmt.layout.major_to_minor) == RANK_MINOR
        assert len(fmt.sharding.device_set) == 1


@pytest.mark.parametrize("name", [LATENT[1], "test-tiny-gqa"])
def test_the_rule_leaves_a_leaf_on_a_mesh_and_a_quantized_one_alone(name):
    """Under a mesh no cell has measured the layout: a leaf spread over
    devices keeps the one its sharding rule was measured with; a QuantTensor
    (`--weights-dtype int8`; refused for a latent model at start anyway) is
    no plain array."""
    mc = MODEL_CONFIGS[name]
    tree = shapes(mc)
    mesh = jax.make_mesh((2,), ("tensor",), devices=jax.devices()[:2])
    spread = NamedSharding(mesh, P(None, None, "tensor"))
    first, second = (tree["layers"][k] for k in stacks_of(name))
    tree["layers"][stacks_of(name)[0]] = jax.ShapeDtypeStruct(
        first.shape, first.dtype, sharding=spread)
    tree["layers"][stacks_of(name)[1]] = quantize_tensor(
        jnp.ones(second.shape, jnp.float32))
    assert llama.weight_formats(mc, tree) == {}


@pytest.mark.parametrize(
    "name", LATENT + ("test-tiny-moe", "test-tiny-k-exaone",
                      "test-tiny-olmoe"))
def test_seeded_stacks_are_born_in_their_layout_with_the_same_draw(name):
    """`init_random`'s one jit has the formats as its results': the seeded
    values are what the same jit drew without them (an expert model's tree
    was always drawn under one jit), every other leaf in the default
    order — every leaf of a model the rule names nothing for."""
    mc = MODEL_CONFIGS[name]
    assert mc.num_experts
    named = stacks_of(name)
    born = weights.init_random(mc, seed=3)
    plain = jax.jit(lambda key: llama.init_params(mc, key))(
        jax.random.PRNGKey(3))
    assert jax.tree_util.tree_structure(born) \
        == jax.tree_util.tree_structure(plain)
    for k, leaf in born["layers"].items():
        assert leaf.shape == plain["layers"][k].shape, k
        assert order(leaf) == (RANK_MINOR if k in named
                               else tuple(range(leaf.ndim))), k
        np.testing.assert_array_equal(
            np.asarray(leaf, np.float32),
            np.asarray(plain["layers"][k], np.float32), err_msg=k)
    assert weights.relaid(mc, born) == (
        len(named), sum(born["layers"][k].nbytes for k in named))


@pytest.mark.parametrize("name,draw", [
    (LATENT[0], lambda mc: llama.init_params(mc, jax.random.PRNGKey(1))),
    ("test-tiny-gqa", lambda mc: weights.init_random(mc, seed=1)),
    ("test-tiny-qwen3", lambda mc: weights.init_random(mc, seed=1)),
], ids=["latent", "gqa", "qwen3"])
def test_a_row_major_tree_is_re_laid_in_place_once(name, draw):
    """A checkpoint's tree comes row-major, and so does a dense model's
    seeded one (`init_random` draws it eagerly, stack by stack):
    `place_formats` replaces the named entries (same values, same shapes),
    leaves every other leaf the object it was, and finds nothing to do the
    second time."""
    mc = MODEL_CONFIGS[name]
    named = stacks_of(name)
    tree = draw(mc)
    before = dict(tree["layers"])
    assert weights.relaid(mc, tree) == (0, 0)
    weights.place_formats(mc, tree)
    for k, leaf in tree["layers"].items():
        if k not in named:
            assert leaf is before[k], k
            continue
        assert order(leaf) == RANK_MINOR and leaf.shape == before[k].shape
        np.testing.assert_array_equal(np.asarray(leaf, np.float32),
                                      np.asarray(before[k], np.float32))
    placed = dict(tree["layers"])
    weights.place_formats(mc, tree)
    assert all(tree["layers"][k] is placed[k] for k in placed)
    assert weights.relaid(mc, tree) == (
        2, sum(tree["layers"][k].nbytes for k in named))


def taken_formats(rt, monkeypatch, T=16):
    """The formats the ragged step program, lowered as the engine calls it,
    takes `params["layers"]` in."""
    with monkeypatch.context() as m:
        # The jit itself, not the first-call wrapper that times its compile.
        m.setattr(engine, "_sp_note_compile",
                  lambda rt, site, key, cache, fn: cache.setdefault(key, fn))
        fn = rt._get_ragged_jit(T, 1 if rt.mtp else 0, (False, False, False))
    buf = jax.ShapeDtypeStruct((rt.dims.ragged_layout(T).size,), jnp.int32)
    carries = (rt.draft_ids, rt.len_ids) if rt.mtp else ()
    compiled = fn.lower(rt.params, buf, rt.cache.kc, rt.cache.vc, rt.recent, rt.last_ids,
                        rt.cache.slot_state, *carries).compile()
    return compiled.input_formats[0][0]["layers"]


def carried_where_the_weights_are(rt):
    """A re-laid stack is COMMITTED to its device, so a step program's results
    are: the carried state (pools, rings, id carries) starts committed there
    too, or it would come back under another jit key and the first program
    launched would compile twice (`tests/test_stepprof.py`'s gapless engine
    loop saw that as a second 400 ms dispatch)."""
    carried = jax.tree_util.tree_leaves(
        (rt.cache.kc, rt.cache.vc, rt.recent, rt.last_ids, rt.cache.slot_state, rt.draft_ids,
         rt.len_ids))
    return all(x.committed and x.devices() == rt.params["embed"].devices()
               for x in carried)


@pytest.mark.parametrize("name,keys,reference,spec", [
    (LATENT[0], deepseek_v32_keys, deepseek_v32_reference, False),
    (LATENT[1], openpangu_keys, openpangu_reference, True)])
def test_a_runtime_holds_the_stacks_so_and_its_step_program_reads_them_so(
        name, keys, reference, spec, monkeypatch):
    """The served tree: the two stacks in their layout, under the published
    names and LOGICAL shapes (`(n, r, H (dn + dr))`, `(n, c, H (dn + dv))`:
    what `benchmarks/reference/*_decoder.py:served_layout` refuses a run
    without); the gauge's count; and the ragged step program, lowered as the
    engine calls it, takes `params` in exactly those formats — the jit sites
    name no layout, so a program compiled for row-major parameters and fed
    these would copy them a call."""
    mc = MODEL_CONFIGS[name]
    over = dict(spec=True, spec_k=1, spec_min_accept=0) if spec else {}
    rt = _rt(_engine(name, **over))
    lp = rt.params["layers"]
    n = mc.num_layers + mc.num_nextn_predict_layers
    H, dn, dr, dv = (mc.num_heads, mc.qk_nope_head_dim, mc.qk_rope_head_dim,
                     mc.v_head_dim)
    assert lp["mla_wuq"].shape == (n, mc.q_lora_rank, H * (dn + dr))
    assert lp["mla_wukv"].shape == (n, mc.kv_lora_rank, H * (dn + dv))
    reference().served_layout(keys(mc), rt.params)
    assert [order(lp[k]) for k in STACKS] == [RANK_MINOR] * 2
    assert rt.weight_stacks_relaid == 2
    assert rt.stats()["weight_stacks_relaid"] == 2
    assert carried_where_the_weights_are(rt)

    for k, fmt in taken_formats(rt, monkeypatch).items():
        assert tuple(fmt.layout.major_to_minor) == order(lp[k]), k


def test_a_gqa_runtime_holds_wq_and_wk_so_and_serves_the_same_greedy_ids(
        monkeypatch):
    """The engine on a dense GQA toy: its tree is drawn row-major and re-laid
    by `ModelRuntime.__init__` (2 stacks on the gauge and in the stats), its
    ragged step program takes the two in that order — and a wave of greedy
    requests (ragged spans, then fused decode scans) gets the ids that a
    runtime with every weight row-major serves."""
    name = "test-tiny-gqa"
    rt = _rt(eng := _engine(name))
    lp = rt.params["layers"]
    assert [order(lp[k]) for k in QK] == [RANK_MINOR] * 2
    assert rt.weight_stacks_relaid == 2
    assert rt.stats()["weight_stacks_relaid"] == 2
    assert carried_where_the_weights_are(rt)
    for k, fmt in taken_formats(rt, monkeypatch).items():
        assert tuple(fmt.layout.major_to_minor) == order(lp[k]), k
    served, samples = drive(eng, _wave(_greedy), False, monkeypatch)
    assert {s["mode"] for s in samples} == {"ragged", "decode"}

    with monkeypatch.context() as m:
        m.setattr(llama, "weight_formats", lambda cfg, params: {})
        plain = _engine(name)
    assert _rt(plain).weight_stacks_relaid == 0
    assert all(order(x) == tuple(range(x.ndim))
               for x in jax.tree_util.tree_leaves(_rt(plain).params))
    row_major, _ = drive(plain, _wave(_greedy), False, monkeypatch)
    assert served == row_major
    assert [len(served[f"u{i}"][0]) for i in range(6)] \
        == [_greedy(i).max_tokens for i in range(6)]


def test_a_runtime_with_a_full_width_norm_reports_no_re_laid_stack():
    rt = _rt(_engine("test-tiny-olmoe"))
    assert rt.weight_stacks_relaid == 0
    assert all(order(x) == tuple(range(x.ndim))
               for x in jax.tree_util.tree_leaves(rt.params))
