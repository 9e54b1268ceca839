"""The latent-attention configuration, its reference, its readers and its
cell, on the CPU:
    python -m pytest benchmarks/tests/test_deepseek_v32_cell.py -q

That they load as files and entries; that the configuration file holds the
catalog's numbers and reaches the program's ModelConfig key by key; the
readers' counts on a hand-made trace and step samples (a reading of exactly
100 at the floor, None without counters); and the whole control flow of the
cell at a tiny size, selecting for real. Nothing here gives a device number.
(The reference's tolerance against the program's forwards, the shares' sum
and the wrong forwards are tier-1: tests/test_deepseek_v32.py.)"""

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

from benchmarks.lib import arch, result, spec  # noqa: E402

CELL = "deepseek-v3.2-ep16-d5.longctx"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# the numbers of `config` of DeepSeek-V3.2 in the model-configs guide's
# catalog, as of PR 39 (held here too, for where the guide is not installed)
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 3,
    "hidden_size": 7168, "index_head_dim": 128, "index_n_heads": 64,
    "index_topk": 2048, "intermediate_size": 18432, "kv_lora_rank": 512,
    "max_position_embeddings": 163840, "moe_intermediate_size": 2048,
    "moe_layer_freq": 1, "n_group": 8, "n_routed_experts": 256,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 61, "num_key_value_heads": 128,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 2.5,
    "tie_word_embeddings": False, "topk_group": 4, "v_head_dim": 128,
    "vocab_size": 129280}
REDUCED = {"num_hidden_layers": 5, "first_k_dense_replace": 1,
           "n_routed_experts": 16, "vocab_size": 16160,
           "num_nextn_predict_layers": 0}
NEW_METRICS = ("mla_attn_share_pct.thr", "dsa_index_share_pct.thr",
               "dsa_selected_pct.thr", "mla_attn_roofline_pct",
               "dsa_index_roofline_pct")
THR_METRICS = ("tokens_per_step.thr", "host_ms_per_step.thr",
               "device_ms_per_step.thr", "attn_kernel_share_pct.thr",
               "device_idle_pct.thr", "loop_ms_per_step.thr",
               "idle_explained_pct.thr", "device_wait_ms_per_step.thr",
               "stream_frame_tokens", "stream_wakeups_per_step")


# ------------------------------------------------------- files and entries
def test_the_cell_its_configuration_and_its_reference_load():
    cell = spec.load_cell(CELL)
    cfg = cell.config
    assert cell.chips == 1 and cfg["chips"] == 1
    t = cell.traffic
    assert (t["kind"], t["clients"], t["users"]["count"]) == ("closed", 24, 24)
    assert t["prompt_tokens"] == {"dist": "uniform", "min": 8192,
                                  "max": 16384}
    assert t["output_tokens"] == {"dist": "fixed", "value": 128}
    assert (t["ramp_s"], t["drain_s"]) == (20.0, 60.0)
    assert t["options"] == {"temperature": 0}
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "DeepSeek-V3.2")
        numbers = {k: v for k, v in row["config"].items()
                   if not isinstance(v, str)}
        assert numbers == PUBLISHED
        assert cfg["source"] == row["source_url"]
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
    assert {"head_dim", "router_experts", "expert_offset", "router_score",
            "use_expert_bias", "norm_topk_eps", "rope", "indexer",
            "latent_pool", "mtp"} <= set(cfg["assumed"])
    assert "16 chips" in cfg["deployment"] and "experts 0-15" in \
        cfg["deployment"] and "eighth" in cfg["deployment"]
    # the guide's floors: four layers after the dense one, >= 8 experts, an
    # eighth of the vocabulary
    assert cfg["num_hidden_layers"] - cfg["num_dense_layers"] >= 4
    assert cfg["n_routed_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= PUBLISHED["vocab_size"]
    # what the readers divide by comes from this file's own counts
    assert (arch.attention_layers(cfg), arch.expert_layers(cfg),
            arch.expert_width(cfg), arch.num_experts(cfg)) == (5, 4, 2048, 16)
    flags = cfg["server_flags"]

    def flag(name):
        return int(flags[flags.index(name) + 1])

    assert flag("--max-slots") == 16 and flag("--page-size") == 32
    # the engine's bound on a prompt admits the traffic's longest request
    assert flag("--max-pages-per-seq") * 32 - 1 >= 16384 + 128
    # the pool holds the traffic's worst case with room
    assert flag("--num-pages") >= 16 * (16384 + 128) // 32 * 1.5
    assert "AOT memory analysis" in cfg["num_pages_reason"]
    # AT LEAST the new names (an exact set breaks at the next entry)
    per_layer = {m.name for m in cell.metrics_of("per_layer")}
    assert set(NEW_METRICS) | set(THR_METRICS) <= per_layer
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
    assert (mc.index_n_heads, mc.index_head_dim, mc.index_topk) \
        == (64, 128, 2048)
    assert (mc.num_experts, mc.router_width, mc.expert_offset, mc.n_group,
            mc.topk_group, mc.num_experts_per_tok, mc.n_shared_experts) \
        == (16, 256, 0, 8, 4, 8, 1)
    assert (mc.router_score, mc.use_expert_bias, mc.norm_topk_prob,
            mc.routed_scaling_factor) == ("sigmoid", True, True, 2.5)
    assert mc.yarn["factor"] == 40 and mc.kv_row_dims == (640, 128)
    assert mc.attn_scale == pytest.approx(
        192 ** -0.5 * (0.1 * 3.6888794541139363 + 1) ** 2)
    assert mc.param_count() == 4_635_518_208  # as the file's arithmetic
    assert "4.636 B" in cfg["arithmetic"]
    # the rehearsal's tiny stack keeps the plan, the share and a selection
    # smaller than its contexts
    tiny = serve.model_config(cfg, rehearse=True)
    assert tiny.num_layers == 3 and tiny.num_dense_layers == 1
    assert (tiny.num_experts, tiny.router_width) == (4, 16)
    assert tiny.index_topk == 16
    # a file the program cannot run still ends serve.py at start
    with pytest.raises(serve.Refused, match="rope_scaling"):
        serve.model_config(dict(cfg, rope_scaling={"type": "linear",
                                                   "factor": 4}), False)
    with pytest.raises(serve.Refused, match="num_nextn_predict_layers"):
        serve.model_config(dict(cfg, num_nextn_predict_layers=1), False)
    with pytest.raises(serve.Refused, match="first_k_dense_replace"):
        serve.model_config(dict(cfg, first_k_dense_replace=3), False)
    with pytest.raises(serve.Refused, match="no field for it"):
        serve.model_config(dict(cfg, scoring_func="sigmoid"), False)


# ------------------------------------------------------------ the readers
def _ctx(cell, trace, samples, peaks):
    said = {}
    return types.SimpleNamespace(
        cell=cell, trace=trace, trace_steps=samples, steps=samples,
        peaks=peaks, say=lambda note, **kw: said.setdefault(note, kw)), said


def test_the_readers_count_the_least_work_on_a_synthetic_capture():
    """5 launches a pass of each kernel; 2 passes in the trace. The samples
    say what a pass scored and selected; the floor is the selected pairs'
    FLOPs in the expanded form (or the one-token rows' bytes), whatever the
    kernel multiplied."""
    from benchmarks.layer_metrics import _mla
    from benchmarks.lib.peaks import peaks_of

    cell = spec.load_cell(CELL)
    cfg = cell.config
    assert _mla.attend_pair_flops(cfg) == 128 * (192 + 128) * 2
    assert _mla.latent_row_bytes(cfg) == 1152
    assert _mla.index_pair_flops(cfg) == 64 * 128 * 2
    assert _mla.index_key_bytes(cfg) == 256
    peaks = peaks_of("TPU v5 lite")
    # a prefill pass: 512 tokens at 8 k, one decode row beside them
    pass_ = dict(mla_rows=513, dsa_ctx_tokens=512 * 8192 + 9000,
                 dsa_selected_tokens=513 * 2048, dsa_step_ctx_tokens=9000,
                 dsa_step_selected_tokens=2048)
    samples = [dict(pass_, mode="ragged", k_cap=0)] * 2
    flops = 513 * 2048 * 81920 * 10 / 197e12
    assert _mla.least_seconds(513 * 2048 * 10, 81920, 2048 * 10, 1152,
                              peaks) == (pytest.approx(flops), "flops")
    # a decode scan's passes are bound by the rows they read
    assert _mla.least_seconds(16 * 2048, 81920, 16 * 2048, 1152,
                              peaks)[1] == "hbm"
    assert _mla.least_seconds(16 * 12000, 16384, 16 * 12000, 256,
                              peaks)[1] == "hbm"
    index_flops = (512 * 8192 + 9000) * 16384 * 10 / 197e12
    trace = {"busy_s": 1.0, "op_self_s": {
        "mla_sparse_paged_attention_pallas.1 bf16[32,2048,512]": flops * 0.75,
        "mla_sparse_paged_attention_pallas bf16[32,2048,512]": flops * 0.25,
        "dsa_index_pallas.1 f32[32,16,16640]": index_flops * 4,
        "dsa_select_pallas.15 f32[32,16,1]": 0.01, "gmm.12": 0.2,
        "fusion.7": 0.3},
        "op_count": {
        "mla_sparse_paged_attention_pallas.1 bf16[32,2048,512]": 8.0,
        "mla_sparse_paged_attention_pallas bf16[32,2048,512]": 2.0,
        "dsa_index_pallas.1 f32[32,16,16640]": 10.0,
        "dsa_select_pallas.15 f32[32,16,1]": 10.0, "gmm.12": 24.0,
        "fusion.7": 99.0}}
    ctx, said = _ctx(cell, trace, samples, peaks)
    read = {m.name: spec.load_reader(cell, m).read
            for m in cell.metrics if m.name in NEW_METRICS}
    # a kernel that does exactly the least work reads exactly 100
    assert read["mla_attn_roofline_pct"](ctx) == pytest.approx(100.0)
    assert read["dsa_index_roofline_pct"](ctx) == pytest.approx(25.0)
    assert said["mla_attn_roofline"]["launches_in_trace"] == 10
    assert said["mla_attn_roofline"]["bound_by"] == "flops"
    assert read["mla_attn_share_pct.thr"](ctx) == pytest.approx(100 * flops)
    assert read["dsa_index_share_pct.thr"](ctx) == pytest.approx(
        100 * (index_flops * 4 + 0.01))
    assert read["dsa_selected_pct.thr"](ctx) == pytest.approx(
        100 * 513 * 2048 / (512 * 8192 + 9000))
    # the launch that `_ops.forward_passes` counts is the attention's alone
    from benchmarks.layer_metrics import _ops

    assert _ops.forward_passes(trace, arch.attention_layers(cfg)) == 2.0
    # traffic that never selects reads 100
    ctx.steps = [dict(pass_, dsa_selected_tokens=pass_["dsa_ctx_tokens"],
                      mode="ragged", k_cap=0)]
    assert read["dsa_selected_pct.thr"](ctx) == pytest.approx(100.0)
    # a program without the counters (the parent), or a run without a
    # trace, gives the readers nothing to read: None, and nothing raised
    ctx.steps = ctx.trace_steps = [{"mode": "decode", "k_cap": 8}]
    assert all(r(ctx) is None for r in read.values())
    ctx.trace, ctx.trace_steps = None, samples
    assert all(read[n](ctx) is None for n in NEW_METRICS
               if n != "dsa_selected_pct.thr")
    # no such op on the trace (a rehearsal on the CPU): 0
    ctx.trace = {"busy_s": 0.1, "op_self_s": {"fusion": 0.1},
                 "op_count": {"fusion": 9.0}}
    assert all(read[n](ctx) == 0.0 for n in NEW_METRICS
               if n != "dsa_selected_pct.thr")


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
    assert set(THR_METRICS) | set(NEW_METRICS) <= set(line["metrics"])
    assert line["attempted"] > 0 and line["failed"] == 0
    # the rehearsal's contexts pass its index_topk: the selection selects
    assert 5 < line["metrics"]["dsa_selected_pct.thr"]["value"] < 80
    notes = {n["note"]: n for n in map(json.loads, r.stdout.splitlines()[:-1])}
    # The reference ran to its end over the window's requests. Whether a
    # bfloat16 model of 128 hidden lanes lies inside the limit set at 7168
    # is no statement about either (tests/test_deepseek_v32.py holds the
    # program to the reference in float32).
    assert "error" not in notes["reference"], notes["reference"]
    assert notes["reference"]["positions"] > 0
    assert notes["reference"]["mean_margin_sd"] < 1.0


def test_a_parent_without_the_fields_ends_the_run_at_start(tmp_path):
    """The program before PR 39, laid under these files: serve.py finds a
    key ModelConfig has no field for and ends with exit code 2 before the
    CLI starts. Shown with a ModelConfig that lacks the field."""
    import dataclasses

    from benchmarks import serve
    from ollamamq_tpu import config

    cfg = spec.load_cell(CELL).config
    fields = [(f.name, f.type, f) for f in dataclasses.fields(
        config.ModelConfig) if f.name != "kv_lora_rank"]
    older = dataclasses.make_dataclass("ModelConfig", fields, frozen=True)
    real = config.ModelConfig
    config.ModelConfig = older
    try:
        with pytest.raises(serve.Refused, match="'kv_lora_rank'.*no field"):
            serve.model_config(cfg, rehearse=False)
    finally:
        config.ModelConfig = real
