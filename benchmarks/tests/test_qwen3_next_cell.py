"""The Qwen3-Next configuration, its reference and its cell, on the CPU:
    python -m pytest benchmarks/tests/test_qwen3_next_cell.py -q

That they load as files and entries; that the configuration file holds the
catalog's numbers and reaches the program's ModelConfig key by key; that the
reference's tolerance passes the program's own forward and refuses wrong ones
(tiny size, float32); the attention roofline reader's arithmetic on a
synthetic capture; and the whole control flow of the cell at a tiny size.
Nothing here gives a device number."""

from __future__ import annotations

import dataclasses
import functools
import json
import os
import subprocess
import sys
import types

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from benchmarks.lib import arch, result, spec  # noqa: E402

CELL = "qwen3-next-80b-a3b-ep4-d12.longctx"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PERIOD = ["linear_attention"] * 3 + ["full_attention"]
# `config` of Qwen3-Next-80B-A3B-Instruct in the model-configs guide's
# catalog, as of PR 47 (held here too, so that the test runs where the guide
# is not installed)
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512, "num_experts_per_tok": 10,
    "num_hidden_layers": 48, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}
NEW_METRICS = ("attn_kernel_roofline_pct",)
LISTED = ("tokens_per_step.thr", "host_ms_per_step.thr",
          "device_ms_per_step.thr", "attn_kernel_share_pct.thr",
          "device_idle_pct.thr", "loop_ms_per_step.thr",
          "idle_explained_pct.thr", "device_wait_ms_per_step.thr",
          "stream_frame_tokens", "stream_wakeups_per_step",
          "moe_expert_mm_share_pct.thr", "moe_expert_mm_roofline_pct",
          "moe_experts_hit_pct.thr", "moe_load_max_over_mean.thr",
          "lin_kernel_share_pct.thr", "lin_step_roofline_pct")


# ------------------------------------------------------- files and entries
def test_the_cell_its_configuration_and_its_reference_load():
    cell = spec.load_cell(CELL)
    cfg = cell.config
    assert cell.chips == 1 and cfg["chips"] == 1
    assert cell.traffic["kind"] == "closed" and cell.traffic["clients"] == 24
    published = dict(PUBLISHED)
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
        assert row["config"] == published
        assert cfg["source"] == row["source_url"]
    # every key of the catalog's config is in the file, under the same key
    assert set(published) <= set(cfg)
    changed = {k for k, v in published.items() if cfg[k] != v}
    assert changed | {"layer_types"} == set(cfg["reduced"]) == {
        "num_hidden_layers", "layer_types", "num_experts", "vocab_size"}
    assert cfg["num_hidden_layers"] == 12
    assert cfg["layer_types"] == PERIOD * 3  # three whole periods
    assert cfg["reduced_from"] == {
        "num_hidden_layers": 48, "layer_types": PERIOD * 12,
        "num_experts": 512, "vocab_size": 151936}
    assert (cfg["num_experts"], cfg["router_experts"],
            cfg["expert_offset"]) == (128, 512, 0)
    assert cfg["vocab_size"] * 4 == 151936
    assert {"layer_types", "qk_norm", "attn_output_gate", "zero_centred_norm",
            "shared_expert_gate", "router_experts", "linear_attention",
            "rope", "multi_token_prediction", "init"} <= set(cfg["assumed"])
    assert "float32" in cfg["dtype"]  # the rule's state
    assert arch.attention_layers(cfg) == 3 and arch.expert_layers(cfg) == 12
    assert arch.num_experts(cfg) == 128 and arch.expert_width(cfg) == 512
    flags = cfg["server_flags"]
    assert int(flags[flags.index("--num-pages") + 1]) >= 8256  # worst case
    per_layer = {m.name for m in cell.metrics_of("per_layer")}
    assert per_layer >= set(NEW_METRICS) | set(LISTED)
    assert {m.name for m in cell.metrics_of("end_to_end")} == \
        {"output_tok_s", "setup_s"}
    for m in cell.metrics:
        assert callable(spec.load_reader(cell, m).read)
    ref = os.path.join(BENCH, "reference", cfg["reference"] + ".py")
    assert cfg["reference"] == "qwen3_next_decoder" and os.path.exists(ref)
    with open(ref) as f:  # independent of the program's model code
        src = f.read()
    assert "ollamamq_tpu" not in src.split('"""', 2)[2]
    bj = spec.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = next(c for c in bj["configs"] if c["name"] == cfg["name"])
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]
    assert sum(w["chips"] == 4 for w in bj["workloads"]) == 1
    assert all(len(w["why"]) <= 200 for w in bj["workloads"])


def test_the_program_runs_the_configuration_files_model():
    """serve.py hands every architecture key of the file to ModelConfig; the
    stack the program then scans is the file's, and its bytes the file's."""
    from benchmarks import serve
    from ollamamq_tpu.config import ATTENTION, EXPERTS, LINEAR

    cfg = spec.load_cell(CELL).config
    mc = serve.model_config(cfg, rehearse=False)
    assert mc.layer_types == tuple(cfg["layer_types"])
    assert (mc.count(LINEAR), mc.count(ATTENTION), mc.count(EXPERTS)) \
        == (9, 3, 12)
    assert [(f, len(p), n) for f, p, n in mc.layer_plan()] == [(0, 4, 3)]
    assert (mc.num_heads, mc.num_kv_heads, mc.head_dim, mc.qk_norm_kind,
            mc.rotary_dim) == (16, 2, 256, "head", 64)
    assert (mc.linear_num_key_heads, mc.linear_num_value_heads,
            mc.linear_key_head_dim, mc.linear_value_head_dim,
            mc.linear_conv_kernel_dim, mc.linear_allow_neg_eigval) \
        == (16, 32, 128, 128, 4, False)
    assert mc.attn_output_gate and mc.zero_centred_norm \
        and mc.shared_expert_gate
    assert (mc.num_experts, mc.router_width, mc.expert_offset,
            mc.num_experts_per_tok, mc.expert_width, mc.shared_width,
            mc.norm_topk_prob) == (128, 512, 0, 10, 512, 512, True)
    assert (mc.hidden_size, mc.vocab_size, mc.rope_theta) \
        == (2048, 37984, 10000000)
    assert not mc.tie_embeddings and mc.rms_norm_eps == 1e-6
    assert mc.max_seq_len == 262144
    assert mc.param_count() == 5_423_084_736  # as the file's arithmetic
    assert "5,423,084,736" in cfg["arithmetic"]
    # the rehearsal's tiny stack keeps the period, the grouped heads, the
    # share and an even number of rotated lanes
    tiny = serve.model_config(cfg, rehearse=True)
    assert tiny.num_layers == 8 and tiny.count(LINEAR) == 6
    assert (tiny.linear_num_key_heads, tiny.linear_num_value_heads) == (2, 4)
    assert (tiny.num_experts, tiny.router_width, tiny.rotary_dim) == (4, 16, 4)
    # a file the program cannot run still ends serve.py at start
    for key, value in (("layer_types", cfg["layer_types"][:5]),
                       ("linear_num_value_heads", 24),
                       ("partial_rotary_factor", 0.3),
                       ("decoder_sparse_step", 2), ("mlp_only_layers", [0]),
                       ("use_sliding_window", True),
                       ("full_attention_interval", 3)):
        with pytest.raises(serve.Refused, match=key):
            serve.model_config(dict(cfg, **{key: value}), rehearse=False)
    # ... and a rehearsal needs the block to cover both lists
    block = {k: v for k, v in cfg["rehearse"].items()
             if k != "mlp_only_layers"}
    with pytest.raises(serve.Refused, match="mlp_only_layers"):
        serve.as_run(dict(cfg, rehearse=block), True)


# ------------------------------------------------------------ the reference
@functools.lru_cache(maxsize=None)
def _tiny():
    import jax
    import jax.numpy as jnp

    from ollamamq_tpu.config import MODEL_CONFIGS
    from ollamamq_tpu.models import llama

    mc = dataclasses.replace(MODEL_CONFIGS["test-tiny-qwen3-next"],
                             vocab_size=600)
    params = llama.init_params(mc, jax.random.PRNGKey(0), dtype=jnp.float32)
    cfg = {"hidden_size": mc.hidden_size, "num_attention_heads": 4,
           "num_key_value_heads": 2, "head_dim": 16, "rms_norm_eps": 1e-6,
           "rope_theta": 10000.0, "partial_rotary_factor": 0.5,
           "qk_norm": "head", "attn_output_gate": True,
           "zero_centred_norm": True, "shared_expert_gate": True,
           "layer_types": list(mc.layer_types), "linear_num_key_heads": 2,
           "linear_num_value_heads": 4, "linear_key_head_dim": 8,
           "linear_value_head_dim": 16, "linear_conv_kernel_dim": 4,
           "num_experts": 8, "router_experts": 16, "expert_offset": 0,
           "num_experts_per_tok": 4, "norm_topk_prob": True,
           "moe_intermediate_size": 32,
           "shared_expert_intermediate_size": 48, "vocab_size": 600}
    return mc, params, cfg


PROMPTS = ("hello chip, two value heads a key head",
           "a gate a head and a gate on the shared expert")


def _check(cfg, params, served: dict, pad_to=128, max_out=16) -> dict:
    from benchmarks.reference import qwen3_next_decoder as ref

    return ref.check(cfg, params, [
        {"prompt": p, "ids": ids,
         "options": {"temperature": 0, "repeat_penalty": 1.0}}
        for p, ids in served.items()], pad_to, max_out)


WRONG = {"w_for_1_plus_w": dict(zero_centred_norm=False),
         "no_attention_gate": dict(attn_output_gate=False),
         "no_shared_expert_gate": dict(shared_expert_gate=False),
         "whole_head_rotated": dict(partial_rotary_factor=1.0),
         "two_sigmoid": dict(linear_allow_neg_eigval=True)}


def test_the_tolerance_passes_the_program_and_refuses_wrong_forwards():
    """Greedy ids from the PROGRAM's served forward (the prompt in two spans
    over carried state, then one-token rows) agree with the reference; the
    ids a forward that departs from the model gives do not."""
    from test_olmo_hybrid_cell import _greedy

    mc, params, cfg = _tiny()
    served = {p: _greedy(mc, params, p, 12, cut=9) for p in PROMPTS}
    out = _check(cfg, params, served)
    assert out["agrees"] is True and out["positions"] == 24
    assert out["mean_margin_sd"] <= out["mean_margin_sd_max"] == 0.003
    assert out["argmax_share"] == 1.0
    for name, change in WRONG.items():
        wrong = dataclasses.replace(mc, **change)
        ids = {p: _greedy(wrong, params, p, 12) for p in PROMPTS}
        bad = _check(cfg, params, ids)
        assert bad["agrees"] is False, name
        assert bad["mean_margin_sd"] > 10 * bad["mean_margin_sd_max"], \
            (name, bad["mean_margin_sd"])


def test_a_program_without_the_architecture_ends_the_run_not_a_comparison(
        monkeypatch):
    """Weights that lack the family's shapes (a gate, a grouped rule) are no
    wrong answer: the reference stops the server and answers nothing."""
    from benchmarks.reference import qwen3_next_decoder as ref

    mc, params, cfg = _tiny()
    ref.served_layout(cfg, params)  # the program's own tree: as the file's
    layers = {k: v for k, v in params["layers"].items() if k != "wq_gate"}
    with pytest.raises(ref.NotServed, match="wq_gate is absent"):
        ref.served_layout(cfg, dict(params, layers=layers))
    with pytest.raises(ref.NotServed, match="lin_in"):
        ref.served_layout(dict(cfg, linear_num_key_heads=4), params)
    with pytest.raises(ref.NotServed, match="zero_centred_norm"):
        ref.served_layout(dict(cfg, zero_centred_norm=False), params)
    killed = []
    monkeypatch.setattr(ref.os, "kill", lambda pid, sig: killed.append(sig))
    with pytest.raises(SystemExit):
        ref.check(cfg, dict(params, layers=layers), [], 128, 16)
    assert killed == [ref.signal.SIGTERM]


def test_the_attention_roofline_counts_pairs_on_a_synthetic_capture():
    """3 attention layers a pass; 4 passes in the trace (one ragged step,
    one scan of three): 12 launches. The samples of the capture say what a
    pass attended, a layer's worth."""
    from benchmarks.layer_metrics import _attn
    from benchmarks.lib.peaks import peaks_of

    cell = spec.load_cell(CELL)
    cfg = cell.config
    assert _attn.pair_flops(cfg) == 16 * 256 * 4
    assert _attn.row_bytes(cfg) == 2048
    peaks = peaks_of("TPU v5 lite")
    # a 512-token chunk ending at 16 k: compute-bound; 16 decode rows: memory
    chunk_pairs = 512 * (2 * 16384 - 512 + 1) // 2
    least, bound = _attn.least_seconds(cfg, chunk_pairs, 16384, peaks)
    assert bound == "flops"
    assert least == pytest.approx(chunk_pairs * 16384 / 197e12)
    least, bound = _attn.least_seconds(cfg, 16 * 12000, 16 * 12000, peaks)
    assert bound == "hbm" and least == pytest.approx(16 * 12000 * 2048 / 819e9)
    trace = {"busy_s": 0.1, "op_self_s": {
        "ragged_paged_attention_pallas.3_bf16_": 0.006,
        "paged_decode_attention_pallas.8_bf16_": 0.002,
        "gated_delta_step_pallas_f32_": 0.01, "gmm.5": 0.05},
        "op_count": {"ragged_paged_attention_pallas.3_bf16_": 3.0,
                     "paged_decode_attention_pallas.8_bf16_": 9.0,
                     "gated_delta_step_pallas_f32_": 36.0, "gmm.5": 144.0}}
    samples = [dict(mode="ragged", k_cap=0, attn_pairs=chunk_pairs,
                    attn_ctx_rows=16384),
               dict(mode="decode", k_cap=3, attn_pairs=3 * 16 * 12000,
                    attn_ctx_rows=3 * 16 * 12000)]
    said = {}
    ctx = types.SimpleNamespace(
        cell=cell, trace=trace, trace_steps=samples, peaks=peaks,
        say=lambda note, **kw: said.update(kw))
    roof = spec.load_reader(cell, next(
        m for m in cell.metrics if m.name == "attn_kernel_roofline_pct"))
    pairs = (chunk_pairs + 3 * 16 * 12000) / 4
    rows = (16384 + 3 * 16 * 12000) / 4
    want, _ = _attn.least_seconds(cfg, pairs * 12, rows * 12, peaks)
    assert roof.read(ctx) == pytest.approx(100 * want / 0.008)
    assert said["launches_in_trace"] == 12 and said["passes_sampled"] == 4
    assert said["pairs_a_launch"] == pairs
    # a program without the counters (the parent), or a run without a
    # trace, gives the reader nothing to read: None, and nothing raised
    ctx.trace_steps = [{"mode": "decode", "k_cap": 8}]
    assert roof.read(ctx) is None
    ctx.trace, ctx.trace_steps = None, samples
    assert roof.read(ctx) is None
    # no such op on the trace (a rehearsal on the CPU): 0
    ctx.trace = {"busy_s": 0.1, "op_self_s": {"fusion": 0.1},
                 "op_count": {"fusion": 9.0}}
    assert roof.read(ctx) == 0.0


# ------------------------------------------------------------- end to end
def test_rehearsal_of_the_cell_reads_every_metric_it_lists():
    r = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "2147483999", "--seconds", "4", "--trace", "1",
         "--rehearse-cpu"], cwd=ROOT, capture_output=True, text=True,
        timeout=900, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode == 0, r.stderr[-2000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["correct"] is False and line["device"]["platform"] == "cpu"
    cell = spec.load_cell(CELL)
    result.validate(line, {m.name: m.unit
                           for m in cell.metrics_of("per_layer")}, True)
    assert set(LISTED) - {"lin_step_roofline_pct",
                          "moe_expert_mm_roofline_pct"} <= set(line["metrics"])
    assert line["attempted"] > 0 and line["failed"] == 0
    notes = {n["note"]: n for n in map(json.loads, r.stdout.splitlines()[:-1])}
    assert notes["reference"]["positions"] > 0
    assert "error" not in notes["reference"]
