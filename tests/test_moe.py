"""Mixture-of-experts layer + expert parallelism.

Covers: routing math against a plain per-token numpy-style reference for
both families (Mixtral: top-2 of 4, renormalised; OLMoE: top-4 of 16, not),
that the dispatch drops nothing at any token count, that invalid rows are
routed nowhere, EP-sharded == unsharded execution on the 8-virtual-device
mesh, and the engine serving a MoE model end-to-end.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ollamamq_tpu.config import MODEL_CONFIGS, EngineConfig
from ollamamq_tpu.models import llama
from ollamamq_tpu.models.moe import load_stats, moe_mlp
from ollamamq_tpu.parallel.mesh import make_mesh
from ollamamq_tpu.parallel.sharding import shard_params

CFG = MODEL_CONFIGS["test-tiny-moe"]
OLMOE = MODEL_CONFIGS["test-tiny-olmoe"]


def _layer_params(cfg, seed=0):
    params = llama.init_params(cfg, jax.random.PRNGKey(seed), dtype=jnp.float32)
    # Layer 0's slice of the stacked tree.
    return {k: v[0] for k, v in params["layers"].items()}, params


def _reference_moe(cfg, lp, h):
    """Per-token loop: softmax -> top-k -> (renormalize) -> sum of expert
    FFNs. Every routed expert contributes, whatever the others got."""
    B, T, D = h.shape
    x = np.asarray(h, np.float32).reshape(-1, D)
    out = np.zeros_like(x)
    wr = np.asarray(lp["w_router"], np.float32)
    for n in range(x.shape[0]):
        logits = x[n] @ wr
        p = np.exp(logits - logits.max())
        p = p / p.sum()
        top = np.argsort(-p)[: cfg.num_experts_per_tok]
        gates = p[top] / (p[top].sum() if cfg.norm_topk_prob else 1.0)
        for g, e in zip(gates, top):
            gate = x[n] @ np.asarray(lp["we_gate"], np.float32)[e]
            up = x[n] @ np.asarray(lp["we_up"], np.float32)[e]
            silu = gate / (1.0 + np.exp(-gate))
            out[n] += g * ((silu * up) @ np.asarray(lp["we_down"], np.float32)[e])
    return out.reshape(B, T, D)


@pytest.mark.parametrize("cfg", [CFG, OLMOE], ids=lambda c: c.name)
def test_moe_matches_per_token_reference(cfg):
    lp, _ = _layer_params(cfg)
    h = jax.random.normal(jax.random.PRNGKey(1), (2, 5, cfg.hidden_size),
                          jnp.float32)
    got, load = moe_mlp(cfg, lp, h)
    want = _reference_moe(cfg, lp, h)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    assert int(load.sum()) == 10 * cfg.num_experts_per_tok


def _rows(kind, cfg):
    if kind == "509-rows":  # no small factor: the old grouping fell to G = 1
        return jax.random.normal(jax.random.PRNGKey(8),
                                 (1, 509, cfg.hidden_size), jnp.float32)
    if kind == "all-alike":  # every token picks the same experts
        return jnp.broadcast_to(
            jax.random.normal(jax.random.PRNGKey(9), (1, 1, cfg.hidden_size),
                              jnp.float32), (1, 64, cfg.hidden_size))
    return jax.random.normal(jax.random.PRNGKey(8), (1, 16, cfg.hidden_size),
                             jnp.float32)


@pytest.mark.parametrize("kind", ["509-rows", "all-alike", "16-rows"])
def test_no_assignment_is_dropped_at_any_token_count(kind):
    """Dropless: every token's every routed expert contributes, whether the
    token count has a small factor or not and however the load falls —
    64 rows that all pick the same 4 of 16 experts put 64 rows on each
    (a capacity of 2 x the even share would have kept 32)."""
    cfg = OLMOE
    lp, _ = _layer_params(cfg, seed=7)
    h = _rows(kind, cfg)
    got, load = moe_mlp(cfg, lp, h)
    np.testing.assert_allclose(got, _reference_moe(cfg, lp, h), rtol=2e-4,
                               atol=2e-4)
    n = h.shape[1]
    assert int(load.sum()) == n * cfg.num_experts_per_tok
    if kind == "all-alike":
        assert sorted(np.asarray(load))[-cfg.num_experts_per_tok:] == \
            [n] * cfg.num_experts_per_tok
        assert int((np.asarray(load) > 0).sum()) == cfg.num_experts_per_tok


def test_invalid_tokens_are_routed_nowhere():
    """Garbage rows (inactive decode slots / prefill padding) get no
    expert: they add nothing to any expert's load, their own output is
    zero, and the real rows read what they read alone."""
    cfg = OLMOE
    lp, _ = _layer_params(cfg, seed=5)
    real = jax.random.normal(jax.random.PRNGKey(6), (1, 2, cfg.hidden_size),
                             jnp.float32)
    garbage = jnp.full((1, 14, cfg.hidden_size), jnp.nan, jnp.float32)
    h = jnp.concatenate([garbage, real], axis=1)
    valid = jnp.concatenate(
        [jnp.zeros((1, 14), bool), jnp.ones((1, 2), bool)], axis=1
    )
    out, load = moe_mlp(cfg, lp, h, valid=valid)
    np.testing.assert_allclose(out[:, 14:], _reference_moe(cfg, lp, real),
                               rtol=2e-4, atol=2e-4)
    assert not np.asarray(out[:, :14]).any()
    alone, load_alone = moe_mlp(cfg, lp, real)
    np.testing.assert_array_equal(np.asarray(load), np.asarray(load_alone))
    assert int(load.sum()) == 2 * cfg.num_experts_per_tok
    # the counters a step program sends back: assignments, pairs hit, max
    stats = np.asarray(load_stats(jnp.stack([load, load])))
    assert stats.tolist() == [2 * int(load.sum()),
                              2 * int((np.asarray(load) > 0).sum()),
                              int(load.max())]


def test_ep_sharded_matches_unsharded():
    cfg = CFG
    _, params = _layer_params(cfg, seed=3)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (4, 16), 1,
                                cfg.vocab_size, jnp.int32)
    seq_lens = jnp.asarray([16, 12, 16, 9], jnp.int32)

    ref = llama.forward_embed(params, cfg, tokens, seq_lens)

    mesh = make_mesh(dp=1, ep=4, tp=2)  # EP x TP over all 8 devices
    sharded = shard_params(params, mesh)
    got = jax.jit(
        lambda p, t, l: llama.forward_embed(p, cfg, t, l)
    )(sharded, tokens, seq_lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_dense_model_allowed_on_ep_mesh():
    """A dense model on an --ep mesh replicates over the expert axis —
    building its runtime must not raise (multi-model pools mix families)."""
    from ollamamq_tpu.engine.engine import ModelRuntime

    ecfg = EngineConfig(
        model="test-tiny", max_slots=2, num_pages=32, page_size=8,
        max_pages_per_seq=8, dtype="float32",
    )
    mesh = make_mesh(dp=1, ep=2, tp=2)
    import jax.numpy as jnp

    rt = ModelRuntime("test-tiny", MODEL_CONFIGS["test-tiny"], ecfg,
                      mesh=mesh, dtype=jnp.float32)
    assert rt is not None


def test_engine_serves_moe_end_to_end():
    from ollamamq_tpu.engine.engine import TPUEngine
    from ollamamq_tpu.engine.request import Request
    from ollamamq_tpu.ops.sampling import SamplingParams
    from testutil import collect

    ecfg = EngineConfig(
        model="test-tiny-moe", max_slots=4, num_pages=64, page_size=8,
        max_pages_per_seq=16, max_new_tokens=8,
        decode_steps_per_iter=2, ep=4, tp=2, dtype="float32",
    )
    eng = TPUEngine(ecfg, blocklist_path=None)
    eng.start()
    try:
        tok = eng.runtimes["test-tiny-moe"].tokenizer
        texts = []
        for _ in range(2):  # determinism across runs (greedy)
            rid = eng.core.enqueue("u", "127.0.0.1", "test-tiny-moe")
            req = Request(rid, "u", "test-tiny-moe", tok.encode("route me"),
                          SamplingParams(max_tokens=6))
            eng.submit(req)
            items = collect(req, timeout=120)
            assert items[-1].kind == "done", items[-1].error
            texts.append("".join(i.text for i in items if i.kind == "token"))
        assert texts[0] == texts[1] and len(texts[0]) > 0
    finally:
        eng.stop()


# ---------------------------------------------------------------------------
# The grouped matmul's tiles (PR 68): chosen from the matrices' own (k, n).
# ---------------------------------------------------------------------------

# (hidden, expert width) of the eight sparse configuration files.
EXPERT_SHAPES = {
    "olmoe": (2048, 1024), "qwen3-next": (2048, 512),
    "k-exaone": (6144, 2048), "mimo": (4096, 2048),
    "kimi-linear": (2304, 1024), "deepseek": (7168, 2048),
    "openpangu": (7680, 2048), "lfm2": (2048, 1792),
    "xing4": (3584, 1024)}
# Every dimension a whole number of the fixed tiles: today's programs.
UNCHANGED = ("olmoe", "qwen3-next", "k-exaone", "mimo")


@pytest.mark.parametrize("side", ["in", "out"])
@pytest.mark.parametrize("name", EXPERT_SHAPES)
def test_gmm_tiles_divide_the_matrices_they_walk(name, side):
    """The nine configurations' gate / up ("in": k = hidden) and down
    matrices: no contraction tile is masked, no column tile is a sliver, the
    blocks fit the VMEM arithmetic, and where the fixed tiles already walked
    the matrix whole they are the tiles still — the same programs."""
    from ollamamq_tpu.models.moe import (GMM_SLIVER, GMM_TILING,
                                         GMM_VMEM_BYTES, gmm_tiling,
                                         gmm_vmem_bytes)

    d, f = EXPERT_SHAPES[name]
    k, n = (d, f) if side == "in" else (f, d)
    tm, tk, tn = gmm_tiling(4096, k, n)
    assert tm == GMM_TILING[0]
    assert tk % 128 == 0 and k % tk == 0, (tk, k)
    assert tn % 128 == 0 and (n % tn == 0 or n % tn >= tn // GMM_SLIVER)
    assert gmm_vmem_bytes(tm, tk, tn, 2) <= GMM_VMEM_BYTES
    fixed = (min(GMM_TILING[1], k), min(GMM_TILING[2], n))
    if name in UNCHANGED:
        assert (tk, tn) == fixed
    if name == "kimi-linear":  # the whole 2304, rows or columns
        assert (tk, tn) == ((2304, 1024) if side == "in" else (1024, 2304))
    if name == "xing4":  # two k tiles by the rule; two whole column tiles,
        # measured 3.4 % faster than three and a half (GMM_SHAPE_TILES)
        assert (tk, tn) == ((1792, 1024) if side == "in" else (1024, 1792))
    # a function of (k, n) alone: the rows change nothing
    assert gmm_tiling(128, k, n) == (tm, tk, tn)
    # a tile whose blocks would not fit falls to the next that divides
    less = gmm_vmem_bytes(tm, tk, tn, 2) - 1
    small = gmm_tiling(4096, k, n, vmem=less)
    if (tk, tn) != fixed:
        assert small != (tm, tk, tn) and k % small[1] == 0
        assert small[1] <= GMM_TILING[1] and small[2] <= GMM_TILING[2]
    else:
        assert small == (tm, tk, tn)  # the fixed tiles are never refused


def test_gmm_at_tiles_that_divide_agrees_with_ragged_dot():
    """Interpret mode, toy shapes the fixed tiles' clip leaves remainders
    on — k = 384 under a cap of 256, n = 640 with a sliver of 128 under a
    cap of 512, and both with a VMEM budget that takes neither whole — the
    caps scaled down through the function's arguments: whole and dividing
    tiles give what `jax.lax.ragged_dot` gives to the bf16 output's last bit
    or two, empty groups and unowned trailing rows included."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    from ollamamq_tpu.models.moe import gmm_tiling

    rng = np.random.default_rng(0)
    sizes = jnp.asarray([0, 70, 0, 3, 130, 0, 21, 0], jnp.int32)  # 224 of 384
    for k, n, caps, vmem, tiles in (
            (384, 256, (128, 256, 256), 2**20, (128, 384, 256)),
            (256, 640, (128, 256, 512), 2**21, (128, 256, 640)),
            (384, 640, (128, 256, 512), 450_000, (128, 128, 128))):
        assert gmm_tiling(384, k, n, 2, caps, vmem) == tiles
        xs = jnp.asarray(rng.standard_normal((384, k)), jnp.bfloat16)
        w = jnp.asarray(rng.standard_normal((8, k, n)) / np.sqrt(k),
                        jnp.bfloat16)
        got = gmm(xs, w, sizes, xs.dtype, tiles, interpret=True)
        want = jax.lax.ragged_dot(xs, w, sizes)
        assert got.dtype == want.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(got[:224], np.float32),
                                   np.asarray(want[:224], np.float32),
                                   rtol=2**-7, atol=2**-9)
