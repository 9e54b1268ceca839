"""Shared test helpers (pytest puts this directory on sys.path)."""

import time


def collect(req, timeout=120):
    """Drain a request's stream until its terminal item (done/error)."""
    deadline = time.monotonic() + timeout
    items = []
    while time.monotonic() < deadline:
        item = req.stream.get(timeout=0.2)
        if item is None:
            continue
        items.append(item)
        if item.kind in ("done", "error"):
            return items
    raise TimeoutError(f"request {req.req_id} did not finish; got {items}")


def free_port() -> int:
    """An OS-assigned free TCP port (close-then-rebind race is acceptable
    for the jax.distributed coordinator in these short-lived tests)."""
    import socket

    s = socket.socket()
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def run_two_process(script_text, tmp_path, timeout=540):
    """Launch a 2-process jax.distributed child script (argv: pid, port)
    and return the parsed RESULT json of each process. THE harness for
    every cross-host SPMD test — write-script/Popen/kill-on-timeout/parse
    lives here once."""
    import json
    import os
    import subprocess
    import sys

    import pytest

    script = tmp_path / "spmd_child.py"
    script.write_text(script_text)
    env = dict(os.environ)
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    port = free_port()
    procs = [
        subprocess.Popen([sys.executable, str(script), str(pid), str(port)],
                         stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                         text=True, env=env)
        for pid in (0, 1)
    ]
    outs = []
    for p in procs:
        try:
            out, err = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("SPMD processes hung")
        assert p.returncode == 0, f"child failed:\n{err[-2000:]}"
        outs.append(out)
    return [
        json.loads([l for l in o.splitlines()
                    if l.startswith("RESULT ")][0][7:])
        for o in outs
    ]


def single_device_greedy_tokens(model, prompt, max_tokens=6, **ecfg_kw):
    """Generated ids from a plain single-device engine — the numeric
    reference every cross-host parallelism test compares against."""
    import time

    import jax.numpy as jnp

    from ollamamq_tpu.config import EngineConfig
    from ollamamq_tpu.engine.engine import TPUEngine
    from ollamamq_tpu.engine.request import Request
    from ollamamq_tpu.ops.sampling import SamplingParams

    defaults = dict(model=model, max_slots=2, num_pages=32, page_size=8,
                    max_pages_per_seq=8,
                    decode_steps_per_iter=2)
    defaults.update(ecfg_kw)
    eng = TPUEngine(EngineConfig(**defaults), models={model: None},
                    blocklist_path=None, dtype=jnp.float32)
    eng.start()
    try:
        tok = eng.runtimes[model].tokenizer
        rid = eng.core.enqueue("u", "127.0.0.1", model)
        req = Request(rid, "u", model, tok.encode(prompt),
                      SamplingParams(max_tokens=max_tokens))
        eng.submit(req)
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            item = req.stream.get(timeout=0.5)
            if item and item.kind in ("done", "error"):
                break
    finally:
        eng.stop()
    return req.generated_ids


def olmoe_reference():
    """The benchmark's plain float32 reference of the sparse family
    (benchmarks/reference/olmoe_decoder.py), as a module."""
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "reference",
        "olmoe_decoder.py")
    spec = importlib.util.spec_from_file_location("olmoe_decoder", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference_keys(mc) -> dict:
    """What a configuration file says of the ModelConfig `mc`: all that
    reference reads."""
    return {"num_attention_heads": mc.num_heads,
            "num_key_value_heads": mc.num_kv_heads, "head_dim": mc.head_dim,
            "rms_norm_eps": mc.rms_norm_eps, "rope_theta": mc.rope_theta,
            "qk_norm": mc.qk_norm, "num_experts": mc.num_experts,
            "num_experts_per_tok": mc.num_experts_per_tok,
            "norm_topk_prob": mc.norm_topk_prob,
            "tie_word_embeddings": mc.tie_embeddings}


def _reference(name: str):
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "reference", name + ".py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def lfm2_reference():
    """The benchmark's plain float32 reference of the hybrid family
    (benchmarks/reference/lfm2_decoder.py), as a module."""
    return _reference("lfm2_decoder")


def olmo_hybrid_reference():
    """...and of the linear-attention hybrid family
    (benchmarks/reference/olmo_hybrid_decoder.py)."""
    return _reference("olmo_hybrid_decoder")


def olmo_hybrid_keys(mc) -> dict:
    """What a configuration file says of the linear-attention hybrid
    ModelConfig `mc`, in the published spellings: all that reference
    reads."""
    return {"hidden_size": mc.hidden_size,
            "intermediate_size": mc.intermediate_size,
            "num_attention_heads": mc.num_heads,
            "num_key_value_heads": mc.num_kv_heads, "head_dim": mc.head_dim,
            "rms_norm_eps": mc.rms_norm_eps, "qk_norm": mc.qk_norm,
            "norm_order": mc.norm_order,
            "rope_parameters": {"rope_theta": mc.rope_theta},
            "layer_types": list(mc.layer_types),
            "linear_num_key_heads": mc.linear_num_key_heads,
            "linear_num_value_heads": mc.linear_num_value_heads,
            "linear_key_head_dim": mc.linear_key_head_dim,
            "linear_value_head_dim": mc.linear_value_head_dim,
            "linear_conv_kernel_dim": mc.linear_conv_kernel_dim,
            "linear_allow_neg_eigval": mc.linear_allow_neg_eigval,
            "tie_word_embeddings": mc.tie_embeddings}


def falcon_h1_reference():
    """...and of the family that runs attention beside a state-space mixer in
    every layer (benchmarks/reference/falcon_h1_decoder.py)."""
    return _reference("falcon_h1_decoder")


def falcon_h1_keys(mc) -> dict:
    """What a configuration file says of the parallel-hybrid ModelConfig
    `mc`, in the published spellings: all that reference reads."""
    keys = {"num_hidden_layers": mc.num_layers, "hidden_size": mc.hidden_size,
            "intermediate_size": mc.intermediate_size,
            "num_attention_heads": mc.num_heads,
            "num_key_value_heads": mc.num_kv_heads, "head_dim": mc.head_dim,
            "rms_norm_eps": mc.rms_norm_eps, "rope_theta": mc.rope_theta,
            "tie_word_embeddings": mc.tie_embeddings,
            "ssm_multipliers": list(mc.ssm_multipliers),
            "mlp_multipliers": list(mc.mlp_multipliers)}
    for name in ("mamba_d_ssm", "mamba_d_state", "mamba_d_head",
                 "mamba_n_heads", "mamba_n_groups", "mamba_d_conv",
                 "mamba_conv_bias", "embedding_multiplier",
                 "lm_head_multiplier", "attention_in_multiplier",
                 "attention_out_multiplier", "key_multiplier",
                 "ssm_in_multiplier", "ssm_out_multiplier"):
        keys[name] = getattr(mc, name)
    return keys


def lfm2_keys(mc) -> dict:
    """What a configuration file says of the hybrid ModelConfig `mc`, in
    the published spellings: all that reference reads."""
    return {"hidden_size": mc.hidden_size,
            "num_attention_heads": mc.num_heads,
            "num_key_value_heads": mc.num_kv_heads, "head_dim": mc.head_dim,
            "norm_eps": mc.rms_norm_eps, "rope_theta": mc.rope_theta,
            "qk_norm": mc.qk_norm, "layer_types": list(mc.layer_types),
            "num_dense_layers": mc.num_dense_layers,
            "conv_L_cache": mc.conv_L_cache, "num_experts": mc.num_experts,
            "num_experts_per_tok": mc.num_experts_per_tok,
            "norm_topk_prob": mc.norm_topk_prob,
            "norm_topk_eps": mc.norm_topk_eps,
            "router_score": mc.router_score,
            "use_expert_bias": mc.use_expert_bias,
            "routed_scaling_factor": mc.routed_scaling_factor,
            "tie_word_embeddings": mc.tie_embeddings}


def deepseek_v32_reference():
    """...and of the latent-attention family with a learned sparse selection
    (benchmarks/reference/deepseek_v32_decoder.py)."""
    return _reference("deepseek_v32_decoder")


def deepseek_v32_keys(mc) -> dict:
    """What a configuration file says of the latent-attention ModelConfig
    `mc`, in the published spellings: all that reference reads."""
    return {"num_attention_heads": mc.num_heads,
            "hidden_size": mc.hidden_size, "rms_norm_eps": mc.rms_norm_eps,
            "rope_theta": mc.rope_theta,
            "rope_scaling": dict(mc.rope_scaling or ()),
            "head_dim": mc.head_dim, "q_lora_rank": mc.q_lora_rank,
            "kv_lora_rank": mc.kv_lora_rank,
            "qk_nope_head_dim": mc.qk_nope_head_dim,
            "qk_rope_head_dim": mc.qk_rope_head_dim,
            "v_head_dim": mc.v_head_dim, "index_n_heads": mc.index_n_heads,
            "index_head_dim": mc.index_head_dim, "index_topk": mc.index_topk,
            "num_hidden_layers": mc.num_layers,
            "num_dense_layers": mc.num_dense_layers,
            "n_routed_experts": mc.num_experts,
            "router_experts": mc.router_width,
            "expert_offset": mc.expert_offset,
            "num_experts_per_tok": mc.num_experts_per_tok,
            "n_group": mc.n_group, "topk_group": mc.topk_group,
            "n_shared_experts": mc.n_shared_experts,
            "norm_topk_prob": mc.norm_topk_prob,
            "norm_topk_eps": mc.norm_topk_eps,
            "routed_scaling_factor": mc.routed_scaling_factor,
            "router_score": mc.router_score,
            "use_expert_bias": mc.use_expert_bias,
            "moe_intermediate_size": mc.moe_intermediate_size,
            "intermediate_size": mc.intermediate_size,
            "vocab_size": mc.vocab_size}


def qwen3_next_reference():
    """...and of the Qwen3-Next family: the rule with grouped heads beside
    gated attention, zero-centred norms, a gated shared expert
    (benchmarks/reference/qwen3_next_decoder.py)."""
    return _reference("qwen3_next_decoder")


def qwen3_next_keys(mc) -> dict:
    """What a configuration file says of the Qwen3-Next ModelConfig `mc`, in
    the published spellings (and this repo's, for what the published file
    has no key for): all that reference reads."""
    return {"hidden_size": mc.hidden_size,
            "num_attention_heads": mc.num_heads,
            "num_key_value_heads": mc.num_kv_heads, "head_dim": mc.head_dim,
            "rms_norm_eps": mc.rms_norm_eps, "rope_theta": mc.rope_theta,
            "partial_rotary_factor": mc.partial_rotary_factor,
            "qk_norm": mc.qk_norm, "attn_output_gate": mc.attn_output_gate,
            "zero_centred_norm": mc.zero_centred_norm,
            "shared_expert_gate": mc.shared_expert_gate,
            "layer_types": list(mc.layer_types),
            "linear_num_key_heads": mc.linear_num_key_heads,
            "linear_num_value_heads": mc.linear_num_value_heads,
            "linear_key_head_dim": mc.linear_key_head_dim,
            "linear_value_head_dim": mc.linear_value_head_dim,
            "linear_conv_kernel_dim": mc.linear_conv_kernel_dim,
            "linear_allow_neg_eigval": mc.linear_allow_neg_eigval,
            "num_experts": mc.num_experts,
            "router_experts": mc.router_width,
            "expert_offset": mc.expert_offset,
            "num_experts_per_tok": mc.num_experts_per_tok,
            "norm_topk_prob": mc.norm_topk_prob,
            "moe_intermediate_size": mc.expert_width,
            "shared_expert_intermediate_size": mc.shared_width,
            "vocab_size": mc.vocab_size}


def openpangu_reference():
    """...and of the latent-attention family with no indexer, sandwich norms
    and a prediction module (benchmarks/reference/openpangu_ultra_decoder.py)."""
    return _reference("openpangu_ultra_decoder")


def openpangu_keys(mc) -> dict:
    """What a configuration file says of the openPangu ModelConfig `mc`, in
    the published spellings: all that reference reads (the latent family's
    keys — no indexer, no groups, no bias, no rope_scaling: all empty — and
    its own two)."""
    return {**deepseek_v32_keys(mc), "sandwich_norm": mc.sandwich_norm,
            "num_nextn_predict_layers": mc.num_nextn_predict_layers}
