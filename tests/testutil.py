"""Shared test helpers (pytest puts this directory on sys.path)."""

import functools
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


def _text(items):
    return "".join(i.text for i in items if i.kind == "token")


def _wait(pred, budget=30.0, period=0.01):
    deadline = time.monotonic() + budget
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(period)
    return False


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
    return _reference("olmoe_decoder")


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


@functools.cache
def _drawn(mc, dtype, seed, norms, top_norms):
    import jax
    import jax.numpy as jnp

    from ollamamq_tpu.models import llama

    def about(base, i, w):
        return (base + 0.5 * jax.random.normal(
            jax.random.PRNGKey(100 + i), w.shape, jnp.float32)).astype(dtype)

    params = llama.init_params(mc, jax.random.PRNGKey(seed), dtype=dtype)
    for i, name in enumerate(norms):
        if name in params["layers"]:
            params["layers"][name] = about(
                0.0 if name.endswith("_bias") else 1.0, i,
                params["layers"][name])
    for i, name in enumerate(top_norms):
        if name in params:
            params[name] = about(1.0, 20 + i, params[name])
    return params


def seeded_params(mc, norms, dtype=None, seed=0, top_norms=()):
    """`init_params(mc, PRNGKey(seed))` with the layers' `norms` (and the
    `top_norms` beside `embed`) drawn about one — a `..._bias` about zero —
    so a norm on the wrong axis, or left out, cannot pass. Drawn ONCE a
    process: every caller gets dicts of its own over the same arrays."""
    import jax.numpy as jnp

    params = _drawn(mc, dtype or jnp.float32, seed, tuple(norms),
                    tuple(top_norms))
    return {**params, "layers": dict(params["layers"])}


def once_a_sequence(want):
    """`want(...)`, a reference's full forward of one sequence, computed once
    a process for the same arguments: weights are the same where their ARRAYS
    are (`seeded_params`'s; a case that swapped one gets a forward of its
    own), tokens where their values are. What comes back is read-only."""
    import jax
    import numpy as np

    seen = {}

    def part(a):
        if isinstance(a, dict):
            return tuple(map(id, jax.tree_util.tree_leaves(a)))
        if isinstance(a, (list, np.ndarray)):
            return tuple(np.asarray(a).ravel().tolist())
        return a

    @functools.wraps(want)
    def cached(*args, **kw):
        key = (tuple(map(part, args)),
               tuple((k, part(v)) for k, v in sorted(kw.items())))
        if key not in seen:
            out = want(*args, **kw)
            for a in jax.tree_util.tree_leaves(out):
                a.flags.writeable = False
            seen[key] = (out, args, kw)  # (held: an id is its array's)
        return seen[key][0]

    return cached


def span_stream(spans, pad_to, pt, ps):
    """`spans` = [(row, tokens, start position)] as a ragged step's arrays,
    the stream padded to `pad_to`, row r on the pages of `pt[r]`: ((tokens,
    tok_seq, tok_pos, write_slots), (q_start, q_len, kv_len)), int32."""
    import numpy as np

    tok, seq, pos = [], [], []
    q_start = np.full(len(pt), pad_to, np.int32)
    q_len, kv_len = (np.zeros(len(pt), np.int32) for _ in range(2))
    for row, toks, start in spans:
        q_start[row], q_len[row] = len(tok), len(toks)
        kv_len[row] = start + len(toks)
        tok += list(toks)
        seq += [row] * len(toks)
        pos += list(range(start, start + len(toks)))
    tok, seq, pos = (np.asarray(a + [f] * (pad_to - len(a)), np.int32)
                     for a, f in ((tok, 0), (seq, 0), (pos, -1)))
    at = np.maximum(pos, 0)
    slots = np.where(pos >= 0, pt[seq, at // ps] * ps + at % ps, 0)
    return (tok, seq, pos, slots), (q_start, q_len, kv_len)


@functools.cache
def _one_program(fn, nums=(), names=()):
    """`fn` as ONE program a (static arguments, shapes): bare, a forward of
    the program's dispatches — and compiles — an op at a time, 10–30 s a
    case. (Not for a case that patches what `fn` calls: a cached trace does
    not see the patch.)"""
    import jax

    return jax.jit(fn, static_argnums=nums, static_argnames=names)


def prefill(*args):
    """`llama.forward_prefill(params, cfg, ..., page_size)`."""
    from ollamamq_tpu.models import llama

    return _one_program(llama.forward_prefill, (1, 7))(*args)


def embed(*args):
    """`llama.forward_embed(params, cfg, tokens, seq_lens)`."""
    from ollamamq_tpu.models import llama

    return _one_program(llama.forward_embed, 1)(*args)


def moe_mlp(cfg, lp, h, layer=None):
    """`moe.moe_mlp(cfg, lp, h, layer=layer)`."""
    from ollamamq_tpu.models import moe

    return _one_program(moe.moe_mlp, 0, "layer")(cfg, lp, h, layer=layer)


def whole_blocks(tokens, block=32):
    """`tokens` as int32 with a filler behind, up to whole `block`s: a
    reference that forwards a sequence op by op compiles every op once a
    LENGTH, and causal attention, a causal convolution and a token-serial
    rule keep what stands behind a position from it (the references that pad
    themselves, `QUERY_BLOCK`, say so). The caller keeps `len(tokens)` rows."""
    import jax.numpy as jnp

    return jnp.full((-(-len(tokens) // block) * block,), 7, jnp.int32
                    ).at[:len(tokens)].set(jnp.asarray(tokens, jnp.int32))


@functools.cache
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


def kimi_linear_reference():
    """...and of Kimi Delta Attention beside NoPE latent attention
    (benchmarks/reference/kimi_linear_decoder.py)."""
    return _reference("kimi_linear_decoder")


def kimi_linear_keys(mc) -> dict:
    """What a configuration file says of the Kimi-Linear ModelConfig `mc`, in
    the published spellings (and this repo's, for the share held): all that
    reference reads."""
    return {"hidden_size": mc.hidden_size,
            "num_attention_heads": mc.num_heads,
            "rms_norm_eps": mc.rms_norm_eps,
            "layer_types": list(mc.layer_types),
            "linear_attn_config": {k: list(v) if isinstance(v, tuple) else v
                                   for k, v in mc.linear_attn_config},
            "kv_lora_rank": mc.kv_lora_rank, "q_lora_rank": None,
            "qk_nope_head_dim": mc.qk_nope_head_dim,
            "qk_rope_head_dim": mc.qk_rope_head_dim,
            "v_head_dim": mc.v_head_dim, "mla_use_nope": mc.mla_use_nope,
            "num_dense_layers": mc.num_dense_layers,
            "intermediate_size": mc.intermediate_size,
            "num_experts": mc.num_experts,
            "router_experts": mc.router_width,
            "expert_offset": mc.expert_offset,
            "num_experts_per_token": mc.num_experts_per_tok,
            "moe_router_activation_func": mc.router_score,
            "moe_renormalize": mc.norm_topk_prob,
            "routed_scaling_factor": mc.routed_scaling_factor,
            "moe_intermediate_size": mc.expert_width,
            "num_shared_experts": mc.n_shared_experts,
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


def minicpm_sala_reference():
    """...and of the family that runs block-sparse attention beside lightning
    linear attention (benchmarks/reference/minicpm_sala_decoder.py)."""
    return _reference("minicpm_sala_decoder")


def minicpm_sala_keys(mc) -> dict:
    """What a configuration file says of the block-sparse / lightning
    ModelConfig `mc`, in the published spellings: all that reference reads."""
    keys = {"num_hidden_layers": mc.num_layers, "hidden_size": mc.hidden_size,
            "intermediate_size": mc.intermediate_size,
            "num_attention_heads": mc.num_heads,
            "num_key_value_heads": mc.num_kv_heads, "head_dim": mc.head_dim,
            "rms_norm_eps": mc.rms_norm_eps, "rope_theta": mc.rope_theta,
            "tie_word_embeddings": mc.tie_embeddings,
            "mixer_types": list(mc.mixer_types)}
    for name in ("sparse_kernel_size", "sparse_kernel_stride",
                 "sparse_block_size", "sparse_topk", "sparse_init_blocks",
                 "sparse_window_size", "sparse_dense_len", "lightning_nh",
                 "lightning_head_dim", "lightning_use_rope", "scale_emb",
                 "scale_depth", "scale_depth_layers", "dim_model_base",
                 "layer_offset"):
        keys[name] = getattr(mc, name)
    return keys
