"""Checkpoint loading: HF-layout safetensors and orbax round-trip."""

import numpy as np
import pytest

from ollamamq_tpu.config import MODEL_CONFIGS
from ollamamq_tpu.models import weights


def _fake_hf_checkpoint(cfg, tmp_path, with_bias=False):
    from safetensors.numpy import save_file

    rng = np.random.default_rng(0)
    d, qd, kvd, f, v = (cfg.hidden_size, cfg.q_dim, cfg.kv_dim,
                        cfg.intermediate_size, cfg.vocab_size)
    tensors = {
        "model.embed_tokens.weight": rng.normal(size=(v, d)).astype(np.float32),
        "model.norm.weight": np.ones((d,), np.float32),
        "lm_head.weight": rng.normal(size=(v, d)).astype(np.float32),
    }
    for i in range(cfg.num_layers):
        p = f"model.layers.{i}."
        tensors[p + "input_layernorm.weight"] = np.ones((d,), np.float32)
        tensors[p + "post_attention_layernorm.weight"] = np.ones((d,), np.float32)
        # HF stores projections as [out, in]; our tree wants [in, out].
        tensors[p + "self_attn.q_proj.weight"] = rng.normal(size=(qd, d)).astype(np.float32)
        tensors[p + "self_attn.k_proj.weight"] = rng.normal(size=(kvd, d)).astype(np.float32)
        tensors[p + "self_attn.v_proj.weight"] = rng.normal(size=(kvd, d)).astype(np.float32)
        tensors[p + "self_attn.o_proj.weight"] = rng.normal(size=(d, qd)).astype(np.float32)
        tensors[p + "mlp.gate_proj.weight"] = rng.normal(size=(f, d)).astype(np.float32)
        tensors[p + "mlp.up_proj.weight"] = rng.normal(size=(f, d)).astype(np.float32)
        tensors[p + "mlp.down_proj.weight"] = rng.normal(size=(d, f)).astype(np.float32)
        if with_bias:
            tensors[p + "self_attn.q_proj.bias"] = rng.normal(size=(qd,)).astype(np.float32)
            tensors[p + "self_attn.k_proj.bias"] = rng.normal(size=(kvd,)).astype(np.float32)
            tensors[p + "self_attn.v_proj.bias"] = rng.normal(size=(kvd,)).astype(np.float32)
    save_file(tensors, str(tmp_path / "model.safetensors"))
    return tensors


def test_safetensors_hf_layout(tmp_path):
    import jax.numpy as jnp

    cfg = MODEL_CONFIGS["test-tiny"]
    raw = _fake_hf_checkpoint(cfg, tmp_path)
    params = weights.load_safetensors(cfg, str(tmp_path), dtype=jnp.float32)
    assert params["layers"]["wq"].shape == (cfg.num_layers, cfg.hidden_size, cfg.q_dim)
    # Transposition check: our [in, out] equals HF [out, in].T for layer 0.
    np.testing.assert_allclose(
        np.asarray(params["layers"]["wq"][0]),
        raw["model.layers.0.self_attn.q_proj.weight"].T,
        rtol=1e-6,
    )
    np.testing.assert_allclose(
        np.asarray(params["embed"]), raw["model.embed_tokens.weight"], rtol=1e-6
    )
    assert "lm_head" in params  # untied config keeps its head

    # And the loaded checkpoint actually runs.
    from ollamamq_tpu.models import llama
    import jax

    kc = jnp.zeros((cfg.num_layers, 64, cfg.num_kv_heads * cfg.head_dim), jnp.float32)
    from ollamamq_tpu.engine import kv_cache as kvc
    a = kvc.PageAllocator(8, 8, 4)
    pt = jnp.asarray(np.stack([kvc.make_page_table_row(a.alloc(4), 4)]))
    logits, _, _ = llama.forward_prefill(
        params, cfg, jnp.array([[1, 2, 3, 4]], jnp.int32), jnp.array([4]),
        kc, jnp.zeros_like(kc), pt, 8,
    )
    assert bool(jnp.all(jnp.isfinite(logits)))


def test_safetensors_qwen_bias(tmp_path):
    import jax.numpy as jnp

    cfg = MODEL_CONFIGS["test-tiny-qwen"]
    _fake_hf_checkpoint(cfg, tmp_path, with_bias=True)
    params = weights.load_safetensors(cfg, str(tmp_path), dtype=jnp.float32)
    assert params["layers"]["bq"].shape == (cfg.num_layers, cfg.q_dim)


def test_layer_count_mismatch_rejected(tmp_path):
    import dataclasses

    cfg = MODEL_CONFIGS["test-tiny"]
    _fake_hf_checkpoint(cfg, tmp_path)
    wrong = dataclasses.replace(cfg, num_layers=cfg.num_layers + 1)
    with pytest.raises(ValueError, match="layers"):
        weights.load_safetensors(wrong, str(tmp_path))


def test_orbax_round_trip(tmp_path, tiny_cfg, tiny_params):
    weights.save_orbax(tiny_params, str(tmp_path / "ckpt"))
    restored = weights.load_orbax(str(tmp_path / "ckpt"))
    np.testing.assert_allclose(
        np.asarray(restored["layers"]["wq"]),
        np.asarray(tiny_params["layers"]["wq"]),
        rtol=1e-6,
    )
    # load_params resolves an orbax dir automatically.
    via_resolver = weights.load_params(tiny_cfg, str(tmp_path / "ckpt"))
    np.testing.assert_allclose(
        np.asarray(via_resolver["embed"]),
        np.asarray(tiny_params["embed"]), rtol=1e-6,
    )


def test_safetensors_mixtral_moe_layout(tmp_path):
    import jax.numpy as jnp

    cfg = MODEL_CONFIGS["test-tiny-moe"]
    rng = np.random.default_rng(1)
    from safetensors.numpy import save_file

    d, f, E = cfg.hidden_size, cfg.intermediate_size, cfg.num_experts
    tensors = {
        "model.embed_tokens.weight": rng.normal(
            size=(cfg.vocab_size, d)).astype(np.float32),
        "model.norm.weight": np.ones((d,), np.float32),
        "lm_head.weight": rng.normal(
            size=(cfg.vocab_size, d)).astype(np.float32),
    }
    for i in range(cfg.num_layers):
        p = f"model.layers.{i}."
        tensors[p + "input_layernorm.weight"] = np.ones((d,), np.float32)
        tensors[p + "post_attention_layernorm.weight"] = np.ones((d,), np.float32)
        tensors[p + "self_attn.q_proj.weight"] = rng.normal(
            size=(cfg.q_dim, d)).astype(np.float32)
        tensors[p + "self_attn.k_proj.weight"] = rng.normal(
            size=(cfg.kv_dim, d)).astype(np.float32)
        tensors[p + "self_attn.v_proj.weight"] = rng.normal(
            size=(cfg.kv_dim, d)).astype(np.float32)
        tensors[p + "self_attn.o_proj.weight"] = rng.normal(
            size=(d, cfg.q_dim)).astype(np.float32)
        tensors[p + "block_sparse_moe.gate.weight"] = rng.normal(
            size=(E, d)).astype(np.float32)
        for e in range(E):
            ep = p + f"block_sparse_moe.experts.{e}."
            tensors[ep + "w1.weight"] = rng.normal(size=(f, d)).astype(np.float32)
            tensors[ep + "w2.weight"] = rng.normal(size=(d, f)).astype(np.float32)
            tensors[ep + "w3.weight"] = rng.normal(size=(f, d)).astype(np.float32)
    save_file(tensors, str(tmp_path / "model.safetensors"))

    params = weights.load_safetensors(cfg, str(tmp_path), dtype=jnp.float32)
    L = cfg.num_layers
    assert params["layers"]["w_router"].shape == (L, d, E)
    assert params["layers"]["we_gate"].shape == (L, E, d, f)
    assert params["layers"]["we_down"].shape == (L, E, f, d)
    assert "w_gate" not in params["layers"]  # no dense FFN in an MoE tree
    np.testing.assert_allclose(
        np.asarray(params["layers"]["we_gate"][0, 1]),
        tensors["model.layers.0.block_sparse_moe.experts.1.w1.weight"].T,
        rtol=1e-6,
    )
    np.testing.assert_allclose(
        np.asarray(params["layers"]["w_router"][0]),
        tensors["model.layers.0.block_sparse_moe.gate.weight"].T,
        rtol=1e-6,
    )

    # The loaded MoE checkpoint actually runs a prefill.
    from ollamamq_tpu.engine import kv_cache as kvc
    from ollamamq_tpu.models import llama

    kc = jnp.zeros((L, 64, cfg.num_kv_heads * cfg.head_dim), jnp.float32)
    a = kvc.PageAllocator(8, 8, 4)
    pt = jnp.asarray(np.stack([kvc.make_page_table_row(a.alloc(4), 4)]))
    logits, _, _ = llama.forward_prefill(
        params, cfg, jnp.array([[1, 2, 3, 4]], jnp.int32), jnp.array([4]),
        kc, jnp.zeros_like(kc), pt, 8,
    )
    assert np.isfinite(np.asarray(logits)).all()


def test_safetensors_olmoe_layout(tmp_path):
    """OLMoE's checkpoint names: `mlp.gate` (router), `mlp.experts.N.
    {gate,up,down}_proj`, and q/k norm weights as wide as the whole
    projected vector. The loaded tree equals what the reference computes."""
    import jax.numpy as jnp
    from safetensors.numpy import save_file
    from testutil import olmoe_reference, reference_keys

    cfg = MODEL_CONFIGS["test-tiny-olmoe"]
    rng = np.random.default_rng(2)
    d, f, E = cfg.hidden_size, cfg.intermediate_size, cfg.num_experts

    def normal(*shape):
        return rng.normal(size=shape).astype(np.float32) / np.sqrt(shape[-1])

    tensors = {"model.embed_tokens.weight": normal(cfg.vocab_size, d),
               "model.norm.weight": np.ones((d,), np.float32),
               "lm_head.weight": normal(cfg.vocab_size, d)}
    for i in range(cfg.num_layers):
        p = f"model.layers.{i}."
        tensors[p + "input_layernorm.weight"] = np.ones((d,), np.float32)
        tensors[p + "post_attention_layernorm.weight"] = np.ones(
            (d,), np.float32)
        tensors[p + "self_attn.q_proj.weight"] = normal(cfg.q_dim, d)
        tensors[p + "self_attn.k_proj.weight"] = normal(cfg.kv_dim, d)
        tensors[p + "self_attn.v_proj.weight"] = normal(cfg.kv_dim, d)
        tensors[p + "self_attn.o_proj.weight"] = normal(d, cfg.q_dim)
        tensors[p + "self_attn.q_norm.weight"] = 1 + 0.3 * normal(cfg.q_dim)
        tensors[p + "self_attn.k_norm.weight"] = 1 + 0.3 * normal(cfg.kv_dim)
        tensors[p + "mlp.gate.weight"] = normal(E, d)
        for e in range(E):
            ep = p + f"mlp.experts.{e}."
            tensors[ep + "gate_proj.weight"] = normal(f, d)
            tensors[ep + "up_proj.weight"] = normal(f, d)
            tensors[ep + "down_proj.weight"] = normal(d, f)
    save_file(tensors, str(tmp_path / "model.safetensors"))

    params = weights.load_safetensors(cfg, str(tmp_path), dtype=jnp.float32)
    L = cfg.num_layers
    layers = params["layers"]
    assert layers["w_router"].shape == (L, d, E)
    assert layers["we_gate"].shape == layers["we_up"].shape == (L, E, d, f)
    assert layers["we_down"].shape == (L, E, f, d)
    assert layers["q_norm"].shape == (L, cfg.q_dim)
    assert layers["k_norm"].shape == (L, cfg.kv_dim)
    assert "w_gate" not in layers
    for ours, theirs in (("we_gate", "gate_proj"), ("we_up", "up_proj"),
                         ("we_down", "down_proj")):
        np.testing.assert_allclose(
            np.asarray(layers[ours][1, 3]),
            tensors[f"model.layers.1.mlp.experts.3.{theirs}.weight"].T,
            rtol=1e-6)
    np.testing.assert_allclose(np.asarray(layers["w_router"][0]),
                               tensors["model.layers.0.mlp.gate.weight"].T,
                               rtol=1e-6)

    # The loaded checkpoint runs a prefill, and reads what the plain
    # reference reads from the same tree.
    from ollamamq_tpu.engine import kv_cache as kvc
    from ollamamq_tpu.models import llama

    kc = jnp.zeros((L, 64, cfg.kv_dim), jnp.float32)
    a = kvc.PageAllocator(8, 8, 4)
    pt = jnp.asarray(np.stack([kvc.make_page_table_row(a.alloc(4), 4)]))
    toks = [1, 2, 3, 4, 5]
    logits, _, _ = llama.forward_prefill(
        params, cfg, jnp.array([toks], jnp.int32), jnp.array([5]),
        kc, jnp.zeros_like(kc), pt, 8,
    )
    want = olmoe_reference().logits(reference_keys(cfg), params,
                                    jnp.asarray(toks, jnp.int32))
    np.testing.assert_allclose(np.asarray(logits[0]), np.asarray(want[-1]),
                               atol=2e-4)


def test_safetensors_lfm2_layout(tmp_path):
    """LFM2's checkpoint names (ASSUMED from the published modelling code):
    `operator_norm` / `ffn_norm`, `conv.in_proj / conv / out_proj`,
    `self_attn.{q,k}_layernorm` and `out_proj`, a dense prefix as
    `feed_forward.w1 / w3 / w2`, the router as `feed_forward.gate` with its
    `expert_bias`, experts `feed_forward.experts.N.w1 / w3 / w2`,
    `embedding_norm`. Each weight is stacked over the layers of its kind,
    and the loaded tree equals what the reference computes."""
    import jax.numpy as jnp
    from safetensors.numpy import save_file
    from testutil import lfm2_keys, lfm2_reference

    cfg = MODEL_CONFIGS["test-tiny-lfm2"]
    rng = np.random.default_rng(3)
    d, f, E = cfg.hidden_size, cfg.intermediate_size, cfg.num_experts
    fe, K = cfg.expert_width, cfg.conv_L_cache

    def normal(*shape):
        return rng.normal(size=shape).astype(np.float32) / np.sqrt(shape[-1])

    tensors = {"model.embed_tokens.weight": normal(cfg.vocab_size, d),
               "model.embedding_norm.weight": 1 + 0.3 * normal(d)}
    for i, (op, ffn) in enumerate(cfg.kinds):
        p = f"model.layers.{i}."
        tensors[p + "operator_norm.weight"] = 1 + 0.3 * normal(d)
        tensors[p + "ffn_norm.weight"] = 1 + 0.3 * normal(d)
        if op == "conv":
            tensors[p + "conv.in_proj.weight"] = normal(3 * d, d)
            tensors[p + "conv.conv.weight"] = normal(d, 1, K)
            tensors[p + "conv.out_proj.weight"] = normal(d, d)
        else:
            tensors[p + "self_attn.q_proj.weight"] = normal(cfg.q_dim, d)
            tensors[p + "self_attn.k_proj.weight"] = normal(cfg.kv_dim, d)
            tensors[p + "self_attn.v_proj.weight"] = normal(cfg.kv_dim, d)
            tensors[p + "self_attn.out_proj.weight"] = normal(d, cfg.q_dim)
            tensors[p + "self_attn.q_layernorm.weight"] = \
                1 + 0.3 * normal(cfg.head_dim)
            tensors[p + "self_attn.k_layernorm.weight"] = \
                1 + 0.3 * normal(cfg.head_dim)
        if ffn == "dense":
            tensors[p + "feed_forward.w1.weight"] = normal(f, d)
            tensors[p + "feed_forward.w3.weight"] = normal(f, d)
            tensors[p + "feed_forward.w2.weight"] = normal(d, f)
        else:
            tensors[p + "feed_forward.gate.weight"] = normal(E, d)
            tensors[p + "feed_forward.expert_bias"] = 0.1 * normal(E) * 3
            for e in range(E):
                ep = p + f"feed_forward.experts.{e}."
                tensors[ep + "w1.weight"] = normal(fe, d)
                tensors[ep + "w3.weight"] = normal(fe, d)
                tensors[ep + "w2.weight"] = normal(d, fe)
    save_file(tensors, str(tmp_path / "model.safetensors"))

    params = weights.load_safetensors(cfg, str(tmp_path), dtype=jnp.float32)
    layers = params["layers"]
    assert layers["attn_norm"].shape == layers["mlp_norm"].shape == (9, d)
    assert layers["wq"].shape == (3, d, cfg.q_dim)
    assert layers["q_norm"].shape == (3, cfg.head_dim)
    assert layers["conv_in"].shape == (6, d, 3 * d)
    assert layers["conv_w"].shape == (6, d, K)
    assert layers["w_gate"].shape == (2, d, f)
    assert layers["w_router"].shape == (7, d, E)
    assert layers["router_bias"].shape == (7, E)
    assert layers["router_bias"].dtype == jnp.float32
    assert layers["we_down"].shape == (7, E, fe, d)
    assert "lm_head" not in params  # the head is the embedding
    # by kind, in layer order: the second attention layer is layer 4, the
    # third conv layer layer 3, the first expert layer layer 2
    np.testing.assert_allclose(
        np.asarray(layers["wo"][1]),
        tensors["model.layers.4.self_attn.out_proj.weight"].T, rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(layers["conv_w"][2]),
        tensors["model.layers.3.conv.conv.weight"][:, 0, :], rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(layers["we_up"][0, 5]),
        tensors["model.layers.2.feed_forward.experts.5.w3.weight"].T,
        rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(params["final_norm"]),
        tensors["model.embedding_norm.weight"], rtol=1e-6)

    # The loaded tree runs, and computes what the reference computes.
    from ollamamq_tpu.models import llama

    toks = np.asarray([1, 9, 200, 31, 77, 5, 410, 8], np.int32)
    kc = jnp.zeros((3, 64, cfg.kv_dim), jnp.float32)
    logits, _, _ = llama.forward_prefill(
        params, cfg, jnp.asarray(toks[None]), jnp.array([8]), kc, kc,
        jnp.arange(8, dtype=jnp.int32)[None], 8)
    want = lfm2_reference().logits(lfm2_keys(cfg), params, jnp.asarray(toks))
    np.testing.assert_allclose(np.asarray(logits[0]), np.asarray(want[-1]),
                               atol=2e-4, rtol=0)

    # a checkpoint of another stack is told, not served
    del tensors["model.layers.3.conv.conv.weight"]
    save_file(tensors, str(tmp_path / "model.safetensors"))
    with pytest.raises(ValueError, match="conv_w"):
        weights.load_safetensors(cfg, str(tmp_path), dtype=jnp.float32)


def test_safetensors_qwen3_next_layout_is_unwoven(tmp_path, caplog):
    """The published `qwen3_next` tensor names: `in_proj_qkvz` / `in_proj_ba`
    interleaved a key-head group, `q_proj` holding [q | gate] a head, the
    gated shared expert, a share's experts from `expert_offset` on, `mtp.*`
    skipped with a log line — loaded into the served tree, which the seeded
    tree they were woven from equals."""
    import dataclasses
    import logging

    import jax
    import jax.numpy as jnp
    from safetensors.numpy import save_file

    from ollamamq_tpu.models import llama

    cfg = dataclasses.replace(MODEL_CONFIGS["test-tiny-qwen3-next"],
                              num_experts=4, expert_offset=8)
    src = llama.init_params(cfg, jax.random.PRNGKey(3), dtype=jnp.float32)
    lp = {k: np.asarray(v) for k, v in src["layers"].items()}
    d, hk, hv = cfg.hidden_size, 2, 4
    dk, dv, r, hd = 8, 16, 2, cfg.head_dim
    kd, vd = hk * dk, hv * dv
    tensors = {"model.embed_tokens.weight": np.asarray(src["embed"]),
               "model.norm.weight": np.asarray(src["final_norm"]),
               "lm_head.weight": np.asarray(src["lm_head"]),
               "mtp.layers.0.input_layernorm.weight": np.ones((d,), np.float32),
               "mtp.fc.weight": np.ones((d, 2 * d), np.float32)}
    a = c = 0
    for i, kind in enumerate(cfg.layer_types):
        p = f"model.layers.{i}."
        tensors[p + "input_layernorm.weight"] = lp["attn_norm"][i]
        tensors[p + "post_attention_layernorm.weight"] = lp["mlp_norm"][i]
        if kind == "full_attention":
            q = lp["wq"][a].reshape(d, cfg.num_heads, 1, hd)
            g = lp["wq_gate"][a].reshape(d, cfg.num_heads, 1, hd)
            tensors[p + "self_attn.q_proj.weight"] = np.concatenate(
                [q, g], axis=2).reshape(d, 2 * cfg.q_dim).T.copy()
            for hf, ours in (("k_proj", "wk"), ("v_proj", "wv"),
                             ("o_proj", "wo")):
                tensors[p + f"self_attn.{hf}.weight"] = lp[ours][a].T.copy()
            tensors[p + "self_attn.q_norm.weight"] = lp["q_norm"][a]
            tensors[p + "self_attn.k_norm.weight"] = lp["k_norm"][a]
            a += 1
        else:
            w = lp["lin_in"][c]
            cuts = (0, kd, 2 * kd, 2 * kd + vd, 2 * kd + 2 * vd)
            woven = np.concatenate(
                [w[:, lo:hi].reshape(d, hk, -1)
                 for lo, hi in zip(cuts[:-1], cuts[1:])], axis=-1)
            tensors[p + "linear_attn.in_proj_qkvz.weight"] = \
                woven.reshape(d, -1).T.copy()
            ba = lp["lin_ba"][c]
            tensors[p + "linear_attn.in_proj_ba.weight"] = np.concatenate(
                [ba[:, :hv].reshape(d, hk, r), ba[:, hv:].reshape(d, hk, r)],
                axis=-1).reshape(d, -1).T.copy()
            tensors[p + "linear_attn.conv1d.weight"] = \
                lp["lin_conv_w"][c][:, None, :].copy()
            tensors[p + "linear_attn.A_log"] = lp["lin_A_log"][c]
            tensors[p + "linear_attn.dt_bias"] = lp["lin_dt_bias"][c]
            tensors[p + "linear_attn.norm.weight"] = lp["lin_norm"][c]
            tensors[p + "linear_attn.out_proj.weight"] = lp["lin_out"][c].T.copy()
            c += 1
        tensors[p + "mlp.gate.weight"] = lp["w_router"][i].T.copy()
        tensors[p + "mlp.shared_expert_gate.weight"] = \
            lp["w_shared_gate"][i][None].copy()
        for hf, ours in (("gate_proj", "ws_gate"), ("up_proj", "ws_up"),
                         ("down_proj", "ws_down")):
            tensors[p + f"mlp.shared_expert.{hf}.weight"] = lp[ours][i].T.copy()
        for e in range(cfg.router_width):  # the checkpoint holds ALL experts
            held = e - cfg.expert_offset
            for hf, ours in (("gate_proj", "we_gate"), ("up_proj", "we_up"),
                             ("down_proj", "we_down")):
                w = lp[ours][i][held] if 0 <= held < 4 \
                    else np.full_like(lp[ours][i][0], 7.0)
                tensors[p + f"mlp.experts.{e}.{hf}.weight"] = w.T.copy()
    save_file(tensors, str(tmp_path / "model.safetensors"))
    with caplog.at_level(logging.INFO, logger="ollamamq.weights"):
        params = weights.load_safetensors(cfg, str(tmp_path),
                                          dtype=jnp.float32)
    assert "2 `mtp.*` tensors" in caplog.text
    assert set(params["layers"]) == set(src["layers"])
    for name, leaf in src["layers"].items():
        np.testing.assert_array_equal(np.asarray(params["layers"][name]),
                                      np.asarray(leaf), err_msg=name)
    assert params["layers"]["lin_A_log"].dtype == jnp.float32
    for name in ("embed", "final_norm", "lm_head"):
        np.testing.assert_array_equal(np.asarray(params[name]),
                                      np.asarray(src[name]))


def test_safetensors_kimi_linear_layout(tmp_path):
    """The served tree of the tiny Kimi-Linear written under the published
    `kimi_linear` names (three projections and three depthwise convolutions a
    KDA layer, `A_log` [1, 1, H, 1], `q_proj` in BOTH kinds of layer, the
    shared expert and the selection bias inside `block_sparse_moe`) loads
    back to the same tree, the float32 buffers float32."""
    import jax
    import jax.numpy as jnp
    from safetensors.numpy import save_file

    from ollamamq_tpu.models import llama

    cfg = MODEL_CONFIGS["test-tiny-kimi-linear"]
    want = llama.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    lp = {k: np.asarray(v) for k, v in want["layers"].items()}
    t = {"model.embed_tokens.weight": np.asarray(want["embed"]),
         "model.norm.weight": np.asarray(want["final_norm"]),
         "lm_head.weight": np.asarray(want["lm_head"])}
    kinds = {"linear_attention": 0, "full_attention": 0}
    hd = cfg.linear_key_dim
    for i, kind in enumerate(cfg.layer_types):
        p, n = f"model.layers.{i}.", kinds[kind]
        kinds[kind] += 1
        t[p + "input_layernorm.weight"] = lp["attn_norm"][i]
        t[p + "post_attention_layernorm.weight"] = lp["mlp_norm"][i]
        a = p + "self_attn."
        if kind == "linear_attention":
            for j, name in enumerate("qkv"):
                at = slice(j * hd, (j + 1) * hd)
                t[a + f"{name}_proj.weight"] = lp["lin_in"][n][:, at].T
                t[a + f"{name}_conv1d.weight"] = lp["lin_conv_w"][n][
                    at, None, :]
            t[a + "A_log"] = lp["lin_A_log"][n].reshape(1, 1, -1, 1)
            t[a + "dt_bias"] = lp["lin_dt_bias"][n]
            t[a + "o_norm.weight"] = lp["lin_norm"][n]
            for ours, theirs in (
                    ("kda_fa", "f_a_proj"), ("kda_fb", "f_b_proj"),
                    ("kda_b", "b_proj"), ("kda_ga", "g_a_proj"),
                    ("kda_gb", "g_b_proj"), ("lin_out", "o_proj")):
                t[a + theirs + ".weight"] = lp[ours][n].T
        else:
            for ours, theirs in (("wq", "q_proj"),
                                 ("mla_wdkv", "kv_a_proj_with_mqa"),
                                 ("mla_wukv", "kv_b_proj"), ("wo", "o_proj")):
                t[a + theirs + ".weight"] = lp[ours][n].T
            t[a + "kv_a_layernorm.weight"] = lp["mla_kv_norm"][n]
        if i < cfg.num_dense_layers:
            for ours, theirs in (("w_gate", "gate_proj"), ("w_up", "up_proj"),
                                 ("w_down", "down_proj")):
                t[p + f"mlp.{theirs}.weight"] = lp[ours][i].T
            continue
        e, m = i - cfg.num_dense_layers, p + "block_sparse_moe."
        t[m + "gate.weight"] = lp["w_router"][e].T
        t[m + "gate.e_score_correction_bias"] = lp["router_bias"][e]
        for ours, theirs in (("ws_gate", "gate_proj"), ("ws_up", "up_proj"),
                             ("ws_down", "down_proj")):
            t[m + f"shared_experts.{theirs}.weight"] = lp[ours][e].T
        for x in range(cfg.num_experts):
            for ours, theirs in (("we_gate", "w1"), ("we_up", "w3"),
                                 ("we_down", "w2")):
                t[m + f"experts.{x}.{theirs}.weight"] = lp[ours][e, x].T
    save_file({k: np.ascontiguousarray(v, np.float32) for k, v in t.items()},
              str(tmp_path / "model.safetensors"))
    got = weights.load_safetensors(cfg, str(tmp_path), dtype=jnp.float32)
    assert set(got["layers"]) == set(want["layers"])
    for name, w in want["layers"].items():
        np.testing.assert_array_equal(np.asarray(got["layers"][name]),
                                      np.asarray(w), err_msg=name)
        assert got["layers"][name].dtype == w.dtype, name
    for name in ("embed", "lm_head", "final_norm"):
        np.testing.assert_array_equal(np.asarray(got[name]),
                                      np.asarray(want[name]))


def test_safetensors_mimo_v2_flash_layout(tmp_path):
    """The served tree of the tiny MiMo-V2-Flash written under the published
    `mimo_v2_flash` names (`self_attn.{q,k,v,o}_proj` in EVERY layer, of
    another shape in a window layer than in a full one; a window layer's
    `attention_sink_bias`; the selection bias beside the router inside `mlp`)
    loads back to the same tree: a stack an attention kind, the sink and the
    bias float32."""
    import jax
    import jax.numpy as jnp
    from safetensors.numpy import save_file

    from ollamamq_tpu.models import llama

    cfg = MODEL_CONFIGS["test-tiny-mimo-v2-flash"]
    want = llama.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    lp = {k: np.asarray(v) for k, v in want["layers"].items()}
    t = {"model.embed_tokens.weight": np.asarray(want["embed"]),
         "model.norm.weight": np.asarray(want["final_norm"]),
         "lm_head.weight": np.asarray(want["lm_head"])}
    seen = {"full_attention": 0, "sliding_attention": 0}
    for i, kind in enumerate(cfg.layer_types):
        p, n = f"model.layers.{i}.", seen[kind]
        seen[kind] += 1
        pre = "swa_" if kind == "sliding_attention" else ""
        t[p + "input_layernorm.weight"] = lp["attn_norm"][i]
        t[p + "post_attention_layernorm.weight"] = lp["mlp_norm"][i]
        for ours in ("wq", "wk", "wv", "wo"):
            t[p + f"self_attn.{ours[1]}_proj.weight"] = lp[pre + ours][n].T
        if pre:
            t[p + "self_attn.attention_sink_bias"] = lp["swa_sink"][n]
        if i < cfg.num_dense_layers:
            for ours, theirs in (("w_gate", "gate_proj"), ("w_up", "up_proj"),
                                 ("w_down", "down_proj")):
                t[p + f"mlp.{theirs}.weight"] = lp[ours][i].T
            continue
        e, m = i - cfg.num_dense_layers, p + "mlp."
        t[m + "gate.weight"] = lp["w_router"][e].T
        t[m + "gate.e_score_correction_bias"] = lp["router_bias"][e]
        for x in range(cfg.num_experts):
            for ours, theirs in (("we_gate", "gate_proj"),
                                 ("we_up", "up_proj"),
                                 ("we_down", "down_proj")):
                t[m + f"experts.{x}.{theirs}.weight"] = lp[ours][e, x].T
    save_file({k: np.ascontiguousarray(v, np.float32) for k, v in t.items()},
              str(tmp_path / "model.safetensors"))
    got = weights.load_safetensors(cfg, str(tmp_path), dtype=jnp.float32)
    assert set(got["layers"]) == set(want["layers"])
    assert got["layers"]["wk"].shape != got["layers"]["swa_wk"].shape[1:]
    for name, w in want["layers"].items():
        np.testing.assert_array_equal(np.asarray(got["layers"][name]),
                                      np.asarray(w), err_msg=name)
        assert got["layers"][name].dtype == w.dtype, name
