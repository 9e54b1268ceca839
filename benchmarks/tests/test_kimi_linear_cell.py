"""The Kimi-Linear configuration (Kimi Delta Attention beside NoPE latent
attention as a layer kind, a chip's share of the experts), its reference and
its cell, on the CPU:
    python -m pytest benchmarks/tests/test_kimi_linear_cell.py -q

That they load as files and entries; that the configuration file holds every
number of the catalog's row — `head_dim` 72 as published — and reaches the
program's ModelConfig key by key; the `kda_*` / `mla_nope_*` readers'
arithmetic against the file's, and on a synthetic capture; and the whole
control flow of the cell at a tiny size. Nothing here gives a device number."""

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

CELL = "kimi-linear-48b-a3b-ep4-d8.longctx512"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
OWN_METRICS = ("kda_step_roofline_pct", "kda_chunk_roofline_pct",
               "kda_chunk_share_pct.thr", "mla_nope_attn_roofline_pct")
SHARED_METRICS = ("lin_kernel_share_pct.thr", "mla_dense_attn_share_pct.thr",
                  "moe_expert_mm_share_pct.thr", "moe_expert_mm_roofline_pct",
                  "moe_experts_hit_pct.thr", "moe_load_max_over_mean.thr")
THR_METRICS = ("tokens_per_step.thr", "host_ms_per_step.thr",
               "device_ms_per_step.thr", "attn_kernel_share_pct.thr",
               "device_idle_pct.thr", "loop_ms_per_step.thr",
               "idle_explained_pct.thr", "device_wait_ms_per_step.thr",
               "stream_frame_tokens", "stream_wakeups_per_step",
               "dry_ms_per_step.thr", "idle_late_launch_pct.thr",
               "engine_cpu_ms_per_step.thr", "server_cpu_ms_per_step.thr",
               "engine_offcpu_ms_per_step.thr")
# (`lin_step_roofline_pct` reads `linear_num_value_heads`, which this file
# has not; `mla_dense_attn_roofline_pct` reads `head_dim`, which this family
# publishes as hidden / heads: `kda_step_*` and `mla_nope_*` stand for them.)
NOT_THIS_CELLS = ("lin_step_roofline_pct", "mla_dense_attn_roofline_pct",
                  "mla_attn_", "dsa_", "swa_", "mtp_", "ssm_", "s6_", "bsa_",
                  "lightning_", "collective_share_pct",
                  "attn_kernel_roofline_pct")


def _published() -> dict:
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    return next(r for r in rows if r["name"] == "Kimi-Linear-48B-A3B-Instruct")


# ------------------------------------------------------- files and entries
def test_the_cell_its_configuration_and_its_reference_load():
    cell = spec.load_cell(CELL)
    cfg = cell.config
    assert cell.chips == 1 and cfg["chips"] == 1
    assert cell.traffic["kind"] == "closed" and cell.traffic["clients"] == 24
    assert cell.traffic["output_tokens"] == {"dist": "fixed", "value": 512}
    assert cell.traffic == spec.load_cell(
        "minicpm-sala-d16.longctx512").traffic  # the EXISTING mix, unedited
    assert cfg["reference"] == "kimi_linear_decoder"
    assert os.path.exists(os.path.join(BENCH, "reference",
                                       cfg["reference"] + ".py"))
    bj = spec.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = next(c for c in bj["configs"] if c["name"] == cfg["name"])
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "linear_attn_config", "num_experts", "vocab_size"]
    assert cfg["reduced_from"]["num_hidden_layers"] == 27
    names = {m.name for m in cell.metrics_of("per_layer")}
    assert names == set(OWN_METRICS) | set(SHARED_METRICS) | set(THR_METRICS)
    assert not [n for n in names if n.startswith(NOT_THIS_CELLS)]
    assert {m.name for m in cell.metrics_of("end_to_end")} \
        == {"output_tok_s", "setup_s"}
    for m in cell.metrics:
        spec.load_reader(cell, m)  # every listed metric has its reader
    assert sum(w["chips"] == 4 for w in bj["workloads"]) == 1
    assert len(bj["workloads"]) == 14 and len(bj["configs"]) == 13
    assert bj["workloads"][-1]["name"] == CELL  # appended, nothing moved
    assert [m["name"] for m in bj["per_layer"][-4:]] == list(OWN_METRICS)


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_the_file_holds_every_number_of_the_catalog_row():
    row, cfg = _published(), spec.load_cell(CELL).config
    assert cfg["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items()
                     if cfg.get(k, "absent") != v)
    assert differs == sorted(cfg["reduced"])
    assert cfg["head_dim"] == row["config"]["head_dim"] == 72  # as published
    group, whole = cfg["linear_attn_config"], row["config"][
        "linear_attn_config"]
    assert {k: v for k, v in group.items() if not k.endswith("_layers")} \
        == {k: v for k, v in whole.items() if not k.endswith("_layers")}
    assert group["kda_layers"] == [i for i in whole["kda_layers"] if i <= 8]
    assert group["full_attn_layers"] == [4, 8]  # (27's pattern ends ..., 27)
    assert cfg["reduced_from"]["linear_attn_config"] == whole
    assert (cfg["reduced_from"]["num_experts"],
            cfg["reduced_from"]["vocab_size"]) == (256, 163840)
    assert cfg["router_experts"] == 256 and cfg["num_experts"] * 4 == 256
    assert cfg["vocab_size"] * 4 == 163840
    assert arch.attention_layers(cfg) == 2 and arch.expert_layers(cfg) == 7
    assert arch.expert_width(cfg) == 1024 and arch.num_experts(cfg) == 64


def test_the_program_runs_the_configuration_files_model():
    from benchmarks import serve

    cfg = spec.load_cell(CELL).config
    mc = serve.model_config(cfg, rehearse=False)
    assert mc.param_count() == 3_772_368_832
    assert "3,772,368,832 parameters" in cfg["arithmetic"]
    assert mc.head_dim == 192 and mc.kda and mc.mla_use_nope
    assert mc.count("linear_attention") == 6
    assert mc.count("full_attention") == mc.cache_layers == 2
    assert (mc.router_width, mc.num_experts, mc.num_experts_per_tok,
            mc.router_score, mc.use_expert_bias, mc.n_shared_experts) \
        == (256, 64, 8, "sigmoid", True, 1)
    assert mc.kv_row_dims == (640, 0) and mc.state_window == (4, 12288)
    tiny = serve.model_config(cfg, rehearse=True)
    assert tiny.layer_types == mc.layer_types and tiny.head_dim == 24
    assert (tiny.linear_num_value_heads, tiny.linear_key_head_dim) == (2, 16)
    with pytest.raises(serve.Refused,
                       match=r"layer_types \['full_attention', .* does not "
                       r"agree with linear_attn_config"):
        serve.model_config(dict(cfg, layer_types=cfg["layer_types"][::-1]),
                           rehearse=False)
    with pytest.raises(serve.Refused, match=r"head_dim 100 is neither"):
        serve.model_config(dict(cfg, head_dim=100), rehearse=False)
    with pytest.raises(serve.Refused, match="moe_layer_freq"):
        serve.model_config(dict(cfg, moe_layer_freq=2), rehearse=False)
    with pytest.raises(serve.Refused, match="no field"):
        serve.model_config(dict(cfg, kda_gate_rank=64), rehearse=False)


# ------------------------------------------------------------ the readers
def test_the_readers_bytes_are_the_files_arithmetic():
    """A live row a KDA layer: 2 MiB of state read and written, and 5 x 4096 +
    32 float32 beside it; a causal pair of the latent layers 32 heads x (192
    + 128) x 2 FLOPs; a cached row 576 lanes of bf16."""
    from benchmarks.layer_metrics import _kda, _lin, _mla_dense, _ops
    from benchmarks.lib.peaks import peaks_of

    cfg = spec.load_cell(CELL).config
    assert _kda.heads(cfg) == (32, 128)
    assert _kda.state_elements(cfg) * 4 == 2_097_152
    assert "2,097,152 B a slot a KDA layer" in cfg["arithmetic"]
    assert _kda.token_bytes(cfg) == 4 * (5 * 4096 + 32)
    peaks = peaks_of("TPU v5 lite")
    least, bound = _kda.step_least_seconds(cfg, 15 * 6, peaks)
    assert bound == "hbm" and least == pytest.approx(
        15 * 6 * (2 * 2_097_152 + 82_048) / 819e9, rel=1e-2)
    # a 497-token span a layer: its row's state in and out and 497 tokens'
    # float32 operands, 82 KB each — five times what 7 x 524,288 FLOPs a
    # token take at the bf16 peak
    least, bound = _kda.chunk_least_seconds(cfg, 6, 6, 6 * 497, peaks)
    assert bound == "hbm" and least == pytest.approx(
        (12 * 2_097_152 + 6 * 497 * 82_048) / 819e9, rel=1e-2)
    assert least > 5 * 6 * 497 * 7 * 524_288 / 197e12
    assert _kda.latent_pair_flops(cfg) == 32 * 2 * (192 + 128) == 20_480
    assert _mla_dense.pair_flops(cfg) == 32 * 2 * (72 + 128)  # why not that
    assert _kda.latent_row_bytes(cfg) == _mla_dense.row_bytes(cfg) == 1152
    step, chunk = "gated_delta_step_pallas.3 (f32[16,1,4096]", \
        "chunk_rule_pallas.1 (f32[8,64,4096]"
    assert _kda.STEP_KERNEL.search(step) and not _kda.STEP_KERNEL.search(chunk)
    assert _kda.CHUNK_KERNEL.search(chunk) \
        and not _kda.CHUNK_KERNEL.search(step)
    assert _kda.STEP_KERNEL.pattern == _lin.LIN_KERNEL.pattern
    assert _ops.ATTENTION.search("mla_dense_paged_attention_pallas.2")
    assert _kda.heads({"hidden_size": 4}) is None  # another family's file


def test_the_readers_on_a_synthetic_capture():
    """6 launches of each rule kernel a ragged pass and 2 of the latent
    kernel; the trace holds 3 ragged steps and 2 scans of 8 passes; the
    capture's samples say a ragged step has 15 one-token rows and one span of
    497 tokens over 9 pairs."""
    from benchmarks.layer_metrics import _kda
    from benchmarks.lib.peaks import peaks_of

    cell = spec.load_cell(CELL)
    peaks = peaks_of("TPU v5 lite")
    trace = {"busy_s": 0.5, "op_self_s": {
        "gated_delta_step_pallas.3": 0.02, "chunk_rule_pallas.1": 0.005,
        "mla_dense_paged_attention_pallas.2": 0.05, "gmm.4": 0.1,
        "fusion.7": 0.05},
        "op_count": {"gated_delta_step_pallas.3": 6.0 * (3 + 16),
                     "chunk_rule_pallas.1": 18.0,
                     "mla_dense_paged_attention_pallas.2": 2.0 * (3 + 16),
                     "gmm.4": 21.0 * 19, "fusion.7": 99.0}}
    ragged = dict(mode="ragged", k_cap=0, lin_state_resets=0,
                  lin_state_carried=16, lin_step_rows=15,
                  lin_span_tokens=497, lin_chunk_pairs=9,
                  lin_prepare_windows=8, mla_rows=512,
                  mla_pairs=497 * 12_000, mla_ctx_rows=16 * 12_000)
    scan = dict(mode="decode", k_cap=8, lin_state_resets=0,
                lin_state_carried=16, lin_step_rows=128, lin_span_tokens=0,
                lin_chunk_pairs=0, lin_prepare_windows=0, mla_rows=128,
                mla_pairs=128 * 12_000, mla_ctx_rows=128 * 12_000)
    samples = [ragged, scan]
    said = {}
    ctx = types.SimpleNamespace(
        cell=cell, trace=trace, trace_steps=samples, steps=samples,
        peaks=peaks, say=lambda note, **kw: said.update({note: kw}))
    read = {name: spec.load_reader(cell, next(
        m for m in cell.metrics if m.name == name)).read
        for name in OWN_METRICS + ("lin_kernel_share_pct.thr",)}
    assert read["kda_chunk_share_pct.thr"](ctx) == pytest.approx(1.0)
    assert read["lin_kernel_share_pct.thr"](ctx) == pytest.approx(4.0)
    rows = (15 + 128) / 9  # live rows a pass, over the samples' 9 passes
    least, _ = _kda.step_least_seconds(cell.config, rows * 114, peaks)
    assert read["kda_step_roofline_pct"](ctx) \
        == pytest.approx(100 * least / 0.02)
    assert said["kda_step_roofline"]["bound_by"] == "hbm"
    least, _ = _kda.chunk_least_seconds(cell.config, 18, 18, 18 * 497, peaks)
    assert read["kda_chunk_roofline_pct"](ctx) \
        == pytest.approx(100 * least / 0.005)
    assert said["kda_chunk_roofline"]["us_a_pair"] \
        == pytest.approx(1e6 * 0.005 / (9 * 18))
    # the scans' passes each read every slot's context: the cached rows at
    # the HBM peak are more than the pairs' FLOPs at the bf16 peak
    pairs, rows = (497 + 128) * 12_000 / 9, (16 + 128) * 12_000 / 9
    by_flops = pairs * 38 * 20_480 / peaks["flops_bf16"]
    by_bytes = rows * 38 * 1152 / peaks["hbm_bytes_per_s"]
    assert by_bytes > by_flops
    assert read["mla_nope_attn_roofline_pct"](ctx) == pytest.approx(
        100 * by_bytes / 0.05)
    assert said["mla_nope_attn_roofline"]["bound_by"] == "hbm"
    ctx.trace_steps = [ragged]  # a chunk's span alone: its FLOPs bound it
    assert read["mla_nope_attn_roofline_pct"](ctx) == pytest.approx(
        100 * 497 * 12_000 * 38 * 20_480 / peaks["flops_bf16"] / 0.05)
    assert said["mla_nope_attn_roofline"]["bound_by"] == "flops"
    ctx.trace_steps = samples
    # a program without the counters (the parent), or a run without a
    # trace, gives the readers nothing to read: None, and nothing raised
    ctx.trace_steps = ctx.steps = [{"mode": "decode", "k_cap": 8}]
    assert all(read[n](ctx) is None for n in OWN_METRICS)
    ctx.trace, ctx.trace_steps = None, samples
    assert all(read[n](ctx) is None for n in OWN_METRICS)
    # no such op on the trace (a rehearsal on the CPU): 0
    ctx.trace = {"busy_s": 0.1, "op_self_s": {"fusion": 0.1},
                 "op_count": {"fusion": 9.0}}
    assert all(read[n](ctx) == 0.0 for n in OWN_METRICS)


def test_the_reference_is_independent_of_the_programs_ops():
    with open(os.path.join(BENCH, "reference", "kimi_linear_decoder.py")) as f:
        src = f.read()
    assert "import ollamamq_tpu" not in src and "from ollamamq_tpu" not in src
    assert "pallas" not in src.split('"""', 2)[2]  # (its docstring aside)


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
    assert set(THR_METRICS) | set(OWN_METRICS) | set(SHARED_METRICS) \
        <= set(line["metrics"])
    assert line["attempted"] > 0 and line["failed"] == 0
    notes = {n["note"]: n for n in map(json.loads, r.stdout.splitlines()[:-1])}
    # (tiny bfloat16 weights over a 512-id vocabulary, 48 positions, a top 4
    # of 16 near-equal sigmoid scores: the margin is reported and finite —
    # 0.05 to 0.15 from run to run here — and judged on the chip, at the
    # published widths; a 96-token prompt is under the lower-precision
    # reading's one query block)
    assert notes["reference"]["positions"] > 0
    assert "error" not in notes["reference"]
    assert 0 <= notes["reference"]["mean_margin_sd"] < 0.5
