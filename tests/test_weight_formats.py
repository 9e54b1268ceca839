"""The device LAYOUT of the weights (PR 45): the two latent up-projection
stacks, `mla_wuq` and `mla_wukv`, live on one device layer-major with the
contracted rank MINOR — the order the chip's compiler reads them in, which it
re-laid from row-major once a step program before (654 MB a pass at the
published widths). `models/llama.py:weight_formats` is the rule (a pure
function of the config and the leaves' names and shardings),
`weights.init_random` bears the stacks so, `weights.place_formats` re-lays a
loaded tree, and a runtime's step programs are compiled for the layout of the
arrays they are handed. Names, logical shapes and values never change: the
benchmark's references read `runtime.params` by both. On the CPU, which
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
from test_step_overlap import _engine, _rt
from testutil import (deepseek_v32_keys, deepseek_v32_reference,
                      openpangu_keys, openpangu_reference)

LATENT = ("test-tiny-deepseek-v32", "test-tiny-openpangu")
# every other decoder family the program serves, at its toy size
OTHERS = ("test-tiny", "test-tiny-gqa", "test-tiny-qwen3", "test-tiny-moe",
          "test-tiny-olmoe", "test-tiny-lfm2", "test-tiny-olmo-hybrid")
STACKS = ("mla_wuq", "mla_wukv")
RANK_MINOR = (0, 2, 1)


def order(leaf):
    return tuple(leaf.format.layout.major_to_minor)


def shapes(mc):
    return jax.eval_shape(
        lambda: llama.init_params(mc, jax.random.PRNGKey(0)))


@pytest.mark.parametrize("name", LATENT)
def test_the_rule_names_the_two_latent_stacks_rank_minor(name):
    formats = llama.weight_formats(MODEL_CONFIGS[name],
                                   shapes(MODEL_CONFIGS[name]))
    assert sorted(formats) == sorted(STACKS)
    for fmt in formats.values():
        assert tuple(fmt.layout.major_to_minor) == RANK_MINOR
        assert len(fmt.sharding.device_set) == 1


@pytest.mark.parametrize("name", OTHERS)
def test_the_rule_names_nothing_for_a_model_without_latent_attention(name):
    mc = MODEL_CONFIGS[name]
    assert llama.weight_formats(mc, shapes(mc)) == {}


def test_the_rule_leaves_a_leaf_on_a_mesh_and_a_quantized_one_alone():
    """Under a mesh no cell has measured the layout: a leaf spread over
    devices keeps the one its sharding rule was measured with; a QuantTensor
    (refused for a latent model at start anyway) is no plain array."""
    mc = MODEL_CONFIGS[LATENT[1]]
    tree = shapes(mc)
    mesh = jax.make_mesh((2,), ("tensor",), devices=jax.devices()[:2])
    spread = NamedSharding(mesh, P(None, None, "tensor"))
    wuq, wukv = (tree["layers"][k] for k in STACKS)
    tree["layers"]["mla_wuq"] = jax.ShapeDtypeStruct(
        wuq.shape, wuq.dtype, sharding=spread)
    tree["layers"]["mla_wukv"] = quantize_tensor(
        jnp.ones(wukv.shape, jnp.float32))
    assert llama.weight_formats(mc, tree) == {}


@pytest.mark.parametrize("name", LATENT)
def test_seeded_stacks_are_born_in_their_layout_with_the_same_draw(name):
    """`init_random`'s one jit has the formats as its results': the seeded
    values are what the same jit drew without them (an expert model's tree
    was always drawn under one jit), every other leaf in the default
    order."""
    mc = MODEL_CONFIGS[name]
    born = weights.init_random(mc, seed=3)
    plain = jax.jit(lambda key: llama.init_params(mc, key))(
        jax.random.PRNGKey(3))
    assert jax.tree_util.tree_structure(born) \
        == jax.tree_util.tree_structure(plain)
    for k, leaf in born["layers"].items():
        assert leaf.shape == plain["layers"][k].shape, k
        assert order(leaf) == (RANK_MINOR if k in STACKS
                               else tuple(range(leaf.ndim))), k
        np.testing.assert_array_equal(
            np.asarray(leaf, np.float32),
            np.asarray(plain["layers"][k], np.float32), err_msg=k)
    assert weights.relaid(mc, born) == (
        2, sum(born["layers"][k].nbytes for k in STACKS))


def test_a_loaded_tree_is_re_laid_in_place_once():
    """A checkpoint's tree comes row-major: `place_formats` replaces the two
    entries (same values, same shapes), leaves every other leaf the object
    it was, and finds nothing to do the second time."""
    mc = MODEL_CONFIGS[LATENT[0]]
    tree = llama.init_params(mc, jax.random.PRNGKey(1))
    before = dict(tree["layers"])
    assert weights.relaid(mc, tree) == (0, 0)
    weights.place_formats(mc, tree)
    for k, leaf in tree["layers"].items():
        if k not in STACKS:
            assert leaf is before[k], k
            continue
        assert order(leaf) == RANK_MINOR and leaf.shape == before[k].shape
        np.testing.assert_array_equal(np.asarray(leaf, np.float32),
                                      np.asarray(before[k], np.float32))
    placed = dict(tree["layers"])
    weights.place_formats(mc, tree)
    assert all(tree["layers"][k] is placed[k] for k in placed)
    assert weights.relaid(mc, tree)[0] == 2


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

    # The jit itself, not the first-call wrapper that times its compile.
    monkeypatch.setattr(engine, "_sp_note_compile",
                        lambda rt, site, key, cache, fn: cache.setdefault(
                            key, fn))
    T = 16
    fn = rt._get_ragged_jit(T, 1 if rt.mtp else 0, (False, False, False))
    buf = jax.ShapeDtypeStruct((rt._ragged_layout(T).size,), jnp.int32)
    carries = (rt.draft_ids, rt.len_ids) if rt.mtp else ()
    compiled = fn.lower(rt.params, buf, rt.kc, rt.vc, rt.recent, rt.last_ids,
                        rt.slot_state, *carries).compile()
    taken = compiled.input_formats[0][0]["layers"]
    for k, fmt in taken.items():
        assert tuple(fmt.layout.major_to_minor) == order(lp[k]), k


def test_a_runtime_without_latent_attention_reports_no_re_laid_stack():
    rt = _rt(_engine("test-tiny-moe"))
    assert rt.weight_stacks_relaid == 0
    assert all(order(x) == tuple(range(x.ndim))
               for x in jax.tree_util.tree_leaves(rt.params))
