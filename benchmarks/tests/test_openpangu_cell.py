"""The openPangu-Ultra-MoE configuration (latent attention with no indexer,
sandwich norms, the prediction module served as the `--spec` proposer), its
reference, its readers and its cell, on the CPU:
    python -m pytest benchmarks/tests/test_openpangu_cell.py -q

That they load as files and entries; that the configuration file holds the
catalog's numbers and reaches the program's ModelConfig key by key; the
readers' counts on a hand-made trace and step samples (a reading of exactly
100 at the floor, None without counters); and the whole control flow of the
cell at a tiny size, drafting for real. Nothing here gives a device number.
(The reference's tolerance against the program's forwards, the module's
logits, the shares' sum and the wrong forwards are tier-1:
tests/test_openpangu.py.)"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import types

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from benchmarks.lib import arch, result, spec, steps  # noqa: E402

CELL = "openpangu-ultra-moe-ep16-d5.reason"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# the numbers of `config` of openPangu-Ultra-MoE-718B in the model-configs
# guide's catalog, as of PR 42 (held here too, for where the guide is not
# installed)
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 3,
    "hidden_size": 7680, "intermediate_size": 18432, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "moe_intermediate_size": 2048,
    "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 61, "num_key_value_heads": 128,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
    "rope_theta": 25600000, "routed_scaling_factor": 2.5,
    "sandwich_norm": True, "tie_word_embeddings": False, "v_head_dim": 128,
    "vocab_size": 153600}
REDUCED = {"num_hidden_layers": 5, "first_k_dense_replace": 1,
           "n_routed_experts": 16, "vocab_size": 19200}
NEW_METRICS = ("mtp_accept_pct", "mtp_drafts_per_step.thr",
               "spec_rollback_pages_per_step.thr",
               "mla_dense_attn_share_pct.thr", "mla_dense_attn_roofline_pct")
OLD_METRICS = ("tokens_per_step.thr", "host_ms_per_step.thr",
               "device_ms_per_step.thr", "attn_kernel_share_pct.thr",
               "device_idle_pct.thr", "loop_ms_per_step.thr",
               "idle_explained_pct.thr", "device_wait_ms_per_step.thr",
               "stream_frame_tokens", "stream_wakeups_per_step",
               "moe_expert_mm_share_pct.thr", "moe_expert_mm_roofline_pct",
               "moe_experts_hit_pct.thr", "moe_load_max_over_mean.thr")


# ------------------------------------------------------- files and entries
def test_the_cell_its_configuration_and_its_reference_load():
    cell = spec.load_cell(CELL)
    cfg = cell.config
    assert cell.chips == 1 and cfg["chips"] == 1
    t = cell.traffic
    assert (t["kind"], t["clients"], t["users"]["count"]) == ("closed", 48, 48)
    assert t["prompt_tokens"] == {"dist": "uniform", "min": 256, "max": 2048}
    assert t["output_tokens"] == {"dist": "fixed", "value": 1024}
    assert (t["ramp_s"], t["drain_s"]) == (20.0, 60.0)
    # greedy AND unpenalised: the rows the engine speculates for
    assert t["options"] == {"temperature": 0, "repeat_penalty": 1.0}
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "openPangu-Ultra-MoE-718B")
        numbers = {k: v for k, v in row["config"].items()
                   if not isinstance(v, str)}
        assert numbers == PUBLISHED
        assert cfg["source"] == row["source_url"]
        assert cfg["model_type"] == row["config"]["model_type"]
    # every published number is in the file under its own key; what differs
    # is listed, with what it was
    changed = {k for k, v in PUBLISHED.items() if cfg[k] != v}
    assert changed == set(REDUCED)
    assert {k: cfg[k] for k in changed} == REDUCED
    assert set(cfg["reduced"]) == changed | {"num_dense_layers"}
    assert cfg["reduced_from"] == {**{k: PUBLISHED[k] for k in changed},
                                   "num_dense_layers": 3}
    assert not any(k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"
                   for k in cfg["reduced"])  # no width
    # the module is served whole: 1, as published, and not reduced
    assert cfg["num_nextn_predict_layers"] == 1
    assert "num_nextn_predict_layers" not in cfg["reduced"]
    assert {"head_dim", "router_experts", "expert_offset", "router_score",
            "norm_topk_eps", "rope", "latent_pool", "norms", "mtp",
            "serving"} <= set(cfg["assumed"])
    assert "16 chips" in cfg["deployment"] and "experts 0-15" in \
        cfg["deployment"] and "eighth" in cfg["deployment"]
    assert "SIXTH of a pass" in cfg["deployment"] and "1/62" in \
        cfg["deployment"]
    # the guide's floors: four layers after the dense one, >= 8 experts, an
    # eighth of the vocabulary
    assert cfg["num_hidden_layers"] - cfg["num_dense_layers"] >= 4
    assert cfg["n_routed_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= PUBLISHED["vocab_size"]
    # what the existing readers divide by comes from this file's own counts:
    # the TRUNK's layers (the module's launches carry another name)
    assert (arch.attention_layers(cfg), arch.expert_layers(cfg),
            arch.expert_width(cfg), arch.num_experts(cfg)) == (5, 4, 2048, 16)
    flags = cfg["server_flags"]

    def flag(name):
        return int(flags[flags.index(name) + 1])

    assert flag("--max-slots") == 32 and flag("--page-size") == 32
    assert "--spec" in flags and flag("--spec-k") == 1
    assert float(flags[flags.index("--spec-min-accept") + 1]) == 0  # never off
    assert "--decode-steps" not in flags
    worst = 2048 + 1 + 1024 + 1  # BOS, the outputs, a draft's position
    assert flag("--max-pages-per-seq") * 32 - 1 >= worst
    # the pool holds the traffic's worst case with room
    assert flag("--num-pages") >= 32 * -(-worst // 32) * 1.5
    assert "AOT memory analysis" in cfg["num_pages_reason"]
    # AT LEAST the new names (an exact set breaks at the next entry)
    per_layer = {m.name for m in cell.metrics_of("per_layer")}
    assert set(NEW_METRICS) | set(OLD_METRICS) <= per_layer
    assert not {"mla_attn_roofline_pct", "mla_attn_share_pct.thr",
                "dsa_selected_pct.thr"} & per_layer  # no indexer to read
    assert {m.name for m in cell.metrics_of("end_to_end")} == \
        {"output_tok_s", "setup_s"}
    for m in cell.metrics:
        if m.name in NEW_METRICS:
            assert m.entry["moves"] == "output_tok_s"
            assert m.entry["workloads"] == [CELL]
            assert hasattr(spec.load_reader(cell, m), "read")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bj = json.load(f)
    assert sum(w["chips"] == 4 for w in bj["workloads"]) == 1
    assert bj["workloads"][-1]["name"] == CELL  # appended, nothing moved
    assert len(bj["workloads"][-1]["why"]) <= 200


def test_the_program_runs_the_configuration_files_model():
    """serve.py hands every architecture key of the file to ModelConfig; the
    stack the program then scans is the file's, and its bytes the file's."""
    from benchmarks import serve

    cfg = spec.load_cell(CELL).config
    mc = serve.model_config(cfg, rehearse=False)
    assert [(f, [k[1] for k in p], n) for f, p, n in mc.layer_plan()] \
        == [(0, ["dense"], 1), (1, ["experts"], 4)]
    assert (mc.num_heads, mc.num_kv_heads, mc.head_dim) == (128, 128, 192)
    assert (mc.q_lora_rank, mc.kv_lora_rank, mc.qk_nope_head_dim,
            mc.qk_rope_head_dim, mc.v_head_dim) == (1536, 512, 128, 64, 128)
    assert (mc.index_n_heads, mc.index_head_dim, mc.index_topk) == (0, 0, 0)
    assert (mc.num_experts, mc.router_width, mc.expert_offset, mc.n_group,
            mc.num_experts_per_tok, mc.n_shared_experts) \
        == (16, 256, 0, 0, 8, 1)
    assert (mc.router_score, mc.use_expert_bias, mc.norm_topk_prob,
            mc.routed_scaling_factor) == ("sigmoid", False, True, 2.5)
    assert mc.sandwich_norm and mc.num_nextn_predict_layers == 1
    assert mc.yarn is None and mc.attn_scale == 192 ** -0.5
    assert mc.rope_theta == 25600000
    assert mc.kv_row_dims == (640, 0) and mc.cache_layers == 6
    assert mc.param_count() == 6_037_862_400  # as the file's arithmetic
    assert "6,037,862,400" in cfg["arithmetic"]
    # the reference reckons the same count from the file's keys alone
    ref = _reference()
    assert ref.param_count(cfg) == mc.param_count()
    # the rehearsal's tiny stack keeps the plan, the share and the module
    tiny = serve.model_config(cfg, rehearse=True)
    assert tiny.num_layers == 3 and tiny.num_dense_layers == 1
    assert (tiny.num_experts, tiny.router_width) == (4, 16)
    assert tiny.num_nextn_predict_layers == 1 and tiny.sandwich_norm
    # a file the program cannot run still ends serve.py at start
    with pytest.raises(serve.Refused, match="num_nextn_predict_layers"):
        serve.model_config(dict(cfg, num_nextn_predict_layers=2), False)
    with pytest.raises(serve.Refused, match="rope_scaling"):
        serve.model_config(dict(cfg, rope_scaling={"type": "linear",
                                                   "factor": 4}), False)
    with pytest.raises(serve.Refused, match="first_k_dense_replace"):
        serve.model_config(dict(cfg, first_k_dense_replace=3), False)
    with pytest.raises(serve.Refused, match="no field for it"):
        serve.model_config(dict(cfg, scoring_func="sigmoid"), False)


def _reference():
    import importlib.util

    path = os.path.join(BENCH, "reference", "openpangu_ultra_decoder.py")
    s = importlib.util.spec_from_file_location("openpangu_ultra_decoder",
                                               path)
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod


def test_the_reference_holds_the_served_shapes_to_the_files_keys():
    import jax
    import jax.numpy as jnp

    from benchmarks import serve
    from ollamamq_tpu.models import llama

    cfg = serve.as_run(spec.load_cell(CELL).config, True)
    mc = serve.model_config(cfg, rehearse=True)
    params = llama.init_params(mc, jax.random.PRNGKey(0), jnp.float32)
    ref = _reference()
    ref.served_layout(cfg, params)
    n = sum(a.size for a in jax.tree_util.tree_leaves(params))
    assert n == ref.param_count(cfg) == mc.param_count()
    # a tree without the module, or with one norm missing, is not served
    for gone in ("mtp_eh_proj", "mtp_enorm"):
        with pytest.raises(ref.NotServed, match=gone):
            ref.served_layout(cfg, {k: v for k, v in params.items()
                                    if k != gone})
    less = dict(params, layers={k: v for k, v in params["layers"].items()
                                if k != "post_mlp_norm"})
    with pytest.raises(ref.NotServed, match="post_mlp_norm is absent"):
        ref.served_layout(cfg, less)
    short = dict(params, layers=dict(
        params["layers"], mla_wdq=params["layers"]["mla_wdq"][:-1]))
    with pytest.raises(ref.NotServed, match="mla_wdq is"):
        ref.served_layout(cfg, short)
    with pytest.raises(ref.NotServed, match="this reference is the family's"):
        ref.served_layout(dict(cfg, sandwich_norm=False), params)


# ------------------------------------------------------------ the readers
def _ctx(cell, trace, samples, peaks):
    said = {}
    return types.SimpleNamespace(
        cell=cell, trace=trace, trace_steps=samples, steps=samples,
        peaks=peaks, say=lambda note, **kw: said.setdefault(note, kw)), said


def test_the_readers_count_the_least_work_on_a_synthetic_capture():
    """6 launches a pass of the dense kernel (5 layers and the module); 2
    passes in the trace. The samples say what a launch attended; the floor
    is the causal pairs' FLOPs in the expanded form or each span's cached
    rows once, whatever the kernel multiplied or read."""
    from benchmarks.layer_metrics import _mla_dense, _ops
    from benchmarks.lib.peaks import peaks_of

    cell = spec.load_cell(CELL)
    cfg = cell.config
    assert _mla_dense.pair_flops(cfg) == 128 * (192 + 128) * 2
    assert _mla_dense.row_bytes(cfg) == 1152
    peaks = peaks_of("TPU v5 lite")
    # a draft-and-verify pass: 32 spans of 2 tokens at ~1500 of context
    ctx_rows = 32 * 1500
    pass_ = dict(mla_rows=64, mla_pairs=2 * ctx_rows - 32,
                 mla_ctx_rows=ctx_rows, mtp_drafts=32, mtp_accepted=2,
                 mtp_rows=64, spec_rollback_pages=0)
    samples = [dict(pass_, mode="spec_verify", k_cap=1)] * 2
    least = ctx_rows * 6 * 2 * 1152 / 819e9
    assert _mla_dense.least_seconds(cfg, (2 * ctx_rows - 32) * 12,
                                    ctx_rows * 12, peaks) \
        == (pytest.approx(least), "hbm")
    # a prefill chunk is bound by its pairs' FLOPs
    assert _mla_dense.least_seconds(cfg, 512 * 1024, 1280, peaks)[1] \
        == "flops"
    trace = {"busy_s": 1.0, "op_self_s": {
        "mla_dense_paged_attention_pallas.1 bf16[2,4096,512]": least * 1.5,
        "mla_dense_paged_attention_pallas bf16[2,4096,512]": least * 0.1,
        "mtp_latent_attention_pallas bf16[2,4096,512]": least * 0.4,
        "gmm.12": 0.2, "fusion.7": 0.3},
        "op_count": {
        "mla_dense_paged_attention_pallas.1 bf16[2,4096,512]": 8.0,
        "mla_dense_paged_attention_pallas bf16[2,4096,512]": 2.0,
        "mtp_latent_attention_pallas bf16[2,4096,512]": 2.0,
        "gmm.12": 30.0, "fusion.7": 99.0}}
    ctx, said = _ctx(cell, trace, samples, peaks)
    read = {m.name: spec.load_reader(cell, m).read
            for m in cell.metrics if m.name in NEW_METRICS}
    # a kernel that takes twice the least reads exactly 50
    assert read["mla_dense_attn_roofline_pct"](ctx) == pytest.approx(50.0)
    assert said["mla_dense_attn_roofline"]["launches_in_trace"] == 12
    assert said["mla_dense_attn_roofline"]["bound_by"] == "hbm"
    assert read["mla_dense_attn_share_pct.thr"](ctx) == pytest.approx(
        100 * 2 * least)
    assert read["mtp_accept_pct"](ctx) == pytest.approx(100 * 2 / 32)
    assert read["mtp_drafts_per_step.thr"](ctx) == 32.0
    assert read["spec_rollback_pages_per_step.thr"](ctx) == 0.0
    # the launches `_ops.forward_passes` counts are the TRUNK's five a pass:
    # the module's carries another name, so a pass is still a pass
    assert _ops.forward_passes(trace, arch.attention_layers(cfg)) == 2.0
    assert _mla_dense.time_and_launches(trace)[1] \
        / steps.total_passes(samples) == 6.0
    # ... and the existing share holds the trunk's launches alone
    old = {m.name: spec.load_reader(cell, m).read for m in cell.metrics
           if m.name == "attn_kernel_share_pct.thr"}
    assert old["attn_kernel_share_pct.thr"](ctx) == pytest.approx(
        100 * 1.6 * least)
    # a program without the counters (the parent, an n-gram proposer), or a
    # run without a trace, gives the readers nothing to read: None, and
    # nothing raised
    ctx.steps = ctx.trace_steps = [{"mode": "decode", "k_cap": 8}]
    assert all(r(ctx) is None for r in read.values())
    ctx.trace, ctx.trace_steps = None, samples
    assert read["mla_dense_attn_roofline_pct"](ctx) is None
    assert read["mla_dense_attn_share_pct.thr"](ctx) is None
    # no such op on the trace (a rehearsal on the CPU): 0
    ctx.trace = {"busy_s": 0.1, "op_self_s": {"fusion": 0.1},
                 "op_count": {"fusion": 9.0}}
    assert read["mla_dense_attn_roofline_pct"](ctx) == 0.0
    assert read["mla_dense_attn_share_pct.thr"](ctx) == 0.0
    # no draft verified: the acceptance is unknown, not zero
    ctx.steps = [dict(pass_, mtp_drafts=0, mtp_accepted=0, mode="ragged",
                      k_cap=1)]
    assert read["mtp_accept_pct"](ctx) is None
    assert read["mtp_drafts_per_step.thr"](ctx) == 0.0


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
    assert set(OLD_METRICS) | set(NEW_METRICS) <= set(line["metrics"])
    assert line["attempted"] > 0 and line["failed"] == 0
    # the mechanism is on: drafts in the window's passes, none given back
    assert line["metrics"]["mtp_drafts_per_step.thr"]["value"] > 0.5
    assert 0 <= line["metrics"]["mtp_accept_pct"]["value"] <= 100
    notes = {n["note"]: n for n in map(json.loads, r.stdout.splitlines()[:-1])}
    # The reference ran to its end over the window's requests, module and
    # all. Whether a bfloat16 model of 128 hidden lanes lies inside the limit
    # set at 7680 is no statement about either (tests/test_openpangu.py holds
    # the program to the reference in float32).
    assert "error" not in notes["reference"], notes["reference"]
    assert notes["reference"]["positions"] > 0
    assert notes["reference"]["mean_margin_sd"] < 1.0
    assert notes["reference"]["mtp"]["drafts"] > 0


def test_a_parent_without_the_fields_ends_the_run_at_start(tmp_path):
    """The program before PR 42, laid under these files: serve.py finds a
    key ModelConfig has no field for and ends with exit code 2 before the
    CLI starts. Shown with a ModelConfig that lacks the field."""
    import dataclasses

    from benchmarks import serve
    from ollamamq_tpu import config

    cfg = spec.load_cell(CELL).config
    fields = [(f.name, f.type, f) for f in dataclasses.fields(
        config.ModelConfig) if f.name != "sandwich_norm"]
    older = dataclasses.make_dataclass("ModelConfig", fields, frozen=True)
    real = config.ModelConfig
    config.ModelConfig = older
    try:
        with pytest.raises(serve.Refused, match="'sandwich_norm'.*no field"):
            serve.model_config(cfg, rehearse=False)
    finally:
        config.ModelConfig = real
