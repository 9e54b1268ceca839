"""The MiMo-V2-Flash configuration (window and full attention at different
kv-head counts, key heads wider than value heads, a sink in the window
layers' softmax, a chip's share of the experts), its reference and its cell,
on the CPU:
    python -m pytest benchmarks/tests/test_mimo_v2_flash_cell.py -q

That they load as files and entries; that the configuration file holds every
number of the catalog's row and reaches the program's ModelConfig key by key;
the `*_qkv_roofline_pct` / `kv_row_padding_pct` readers' arithmetic against
the file's, and on a synthetic capture; and the whole control flow of the cell
at a tiny size. The cell is held to AT LEAST its names: a later PR's cell,
configuration or metric is appended behind it and breaks nothing here.
Nothing here gives a device number."""

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

from benchmarks.lib import arch, arch_window, result, spec  # noqa: E402

CELL = "mimo-v2-flash-ep16-d7.longctx512"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "hybrid_layer_pattern", "moe_layer_freq",
           "n_routed_experts", "vocab_size"]
OWN_METRICS = ("attn_qkv_roofline_pct", "swa_qkv_roofline_pct",
               "kv_row_padding_pct.thr")
SHARED_METRICS = ("attn_kernel_share_pct.thr", "swa_attn_share_pct.thr",
                  "swa_rows_walked_pct.thr", "moe_expert_mm_share_pct.thr",
                  "moe_expert_mm_roofline_pct", "moe_experts_hit_pct.thr",
                  "moe_load_max_over_mean.thr")
THR_METRICS = ("tokens_per_step.thr", "host_ms_per_step.thr",
               "device_ms_per_step.thr", "device_idle_pct.thr",
               "loop_ms_per_step.thr", "idle_explained_pct.thr",
               "device_wait_ms_per_step.thr", "stream_frame_tokens",
               "stream_wakeups_per_step", "dry_ms_per_step.thr",
               "idle_late_launch_pct.thr", "engine_cpu_ms_per_step.thr",
               "server_cpu_ms_per_step.thr", "engine_offcpu_ms_per_step.thr")
# (`attn_kernel_roofline_pct` / `swa_attn_roofline_pct` count heads x
# head_dim x 4 FLOPs a pair and 2 x num_key_value_heads x head_dim lanes a
# row: a fifth too many FLOPs and the wrong kv heads here — `*_qkv_*` stand
# for them.)
NOT_THIS_CELLS = ("attn_kernel_roofline_pct", "swa_attn_roofline_pct")


def _published() -> dict:
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    return next(r for r in rows if r["name"] == "MiMo-V2-Flash")


# ------------------------------------------------------- files and entries
def test_the_cell_its_configuration_and_its_reference_load():
    cell = spec.load_cell(CELL)
    cfg = cell.config
    assert cell.chips == 1 and cfg["chips"] == 1
    assert cell.traffic["kind"] == "closed" and cell.traffic["clients"] == 24
    assert cell.traffic["output_tokens"] == {"dist": "fixed", "value": 512}
    assert cell.traffic == spec.load_cell(
        "minicpm-sala-d16.longctx512").traffic  # the EXISTING mix, unedited
    assert cfg["reference"] == "mimo_v2_flash_decoder"
    assert os.path.exists(os.path.join(BENCH, "reference",
                                       cfg["reference"] + ".py"))
    bj = spec.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = next(c for c in bj["configs"] if c["name"] == cfg["name"])
    assert entry["reduced"] == cfg["reduced"] == REDUCED
    assert entry["source"] == cfg["source"]
    assert cfg["reduced_from"]["num_hidden_layers"] == 48
    names = {m.name for m in cell.metrics_of("per_layer")}
    assert set(OWN_METRICS) | set(SHARED_METRICS) | set(THR_METRICS) <= names
    assert not names & set(NOT_THIS_CELLS)
    assert {"output_tok_s", "setup_s"} \
        <= {m.name for m in cell.metrics_of("end_to_end")}
    for m in cell.metrics:
        spec.load_reader(cell, m)  # every listed metric has its reader
    by_name = {m["name"]: m for m in bj["per_layer"]}
    for name in OWN_METRICS:
        assert by_name[name]["workloads"][0] == CELL
        assert by_name[name]["moves"] == "output_tok_s"
        assert by_name[name]["layer"] == by_name[
            "attn_kernel_share_pct.thr"]["layer"]
    # AT LEAST: the accepted cells are all there, one four-chip cell among
    # them, and this one is a one-chip cell on the existing traffic file
    cells = [w["name"] for w in bj["workloads"]]
    assert len(cells) >= 15 and len(bj["configs"]) >= 14 and CELL in cells
    assert sum(w["chips"] == 4 for w in bj["workloads"]) == 1
    mine = next(w for w in bj["workloads"] if w["name"] == CELL)
    assert (mine["traffic"], mine["chips"], mine["config"]) \
        == ("longctx512", 1, cfg["name"])
    assert len(mine["why"]) <= 200 and len(entry["why"]) <= 200


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_the_file_holds_every_number_of_the_catalog_row():
    row, cfg = _published(), spec.load_cell(CELL).config
    assert cfg["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items()
                     if cfg.get(k, "absent") != v)
    assert differs == sorted(cfg["reduced"])
    for key in ("hybrid_layer_pattern", "moe_layer_freq"):
        assert cfg[key] == row["config"][key][:7]  # the lists' first 7
        assert cfg["reduced_from"][key] == row["config"][key]
    assert (cfg["reduced_from"]["n_routed_experts"],
            cfg["reduced_from"]["vocab_size"]) == (256, 152576)
    assert cfg["router_experts"] == 256 and cfg["n_routed_experts"] * 16 == 256
    assert cfg["vocab_size"] * 8 == 152576
    # no width changed
    for key in ("hidden_size", "head_dim", "v_head_dim", "swa_head_dim",
                "swa_v_head_dim", "num_attention_heads",
                "num_key_value_heads", "swa_num_key_value_heads",
                "sliding_window", "intermediate_size",
                "moe_intermediate_size", "num_experts_per_tok",
                "partial_rotary_factor", "rope_theta", "swa_rope_theta",
                "attention_value_scale"):
        assert cfg[key] == row["config"][key], key
    # this repo's spellings, which the existing readers count the stack by
    assert arch.attention_layers(cfg) == 2
    assert arch_window.window_layers(cfg) == 5
    assert arch.expert_layers(cfg) == 6 and arch.num_experts(cfg) == 16
    assert arch.expert_width(cfg) == 2048


def test_the_program_runs_the_configuration_files_model():
    from benchmarks import serve

    cfg = spec.load_cell(CELL).config
    mc = serve.model_config(cfg, rehearse=False)
    assert mc.param_count() == 3_429_955_392
    assert "3,429,955,392 parameters" in cfg["arithmetic"]
    assert mc.count("sliding_attention") == 5
    assert mc.count("full_attention") == mc.cache_layers == 2
    assert mc.kv_row_dims == (768, 512) and mc.ring_row_dims == (1536, 1024)
    assert (mc.router_width, mc.num_experts, mc.num_experts_per_tok,
            mc.router_score, mc.use_expert_bias, mc.n_shared_experts,
            mc.routed_scaling_factor) == (256, 16, 8, "sigmoid", True, 0, 1.0)
    assert mc.rotary_dim == 64 and mc.ring_rows(512, 32) == 672
    tiny = serve.model_config(cfg, rehearse=True)
    assert tiny.layer_types == mc.layer_types and tiny.rotary_dim == 8
    with pytest.raises(serve.Refused, match="hybrid_layer_pattern does not "
                       "agree with layer_types"):
        serve.model_config(dict(cfg, layer_types=cfg["layer_types"][::-1]),
                           rehearse=False)
    with pytest.raises(serve.Refused, match="add_full_attention_sink_bias"):
        serve.model_config(dict(cfg, add_full_attention_sink_bias=True),
                           rehearse=False)
    with pytest.raises(serve.Refused, match="sliding_window_size"):
        serve.model_config(dict(cfg, sliding_window_size=256),
                           rehearse=False)
    with pytest.raises(serve.Refused, match="no field"):
        serve.model_config(dict(cfg, swa_sink_rank=64), rehearse=False)


# ------------------------------------------------------------ the readers
def test_the_readers_arithmetic_is_the_files():
    """A pair of either kind 64 heads x (192 + 128) x 2 FLOPs; a cached row
    4 x 320 lanes of bf16 in a full layer, 8 x 320 in a window layer: what
    the file's arithmetic says (5,120 B a token over the two full layers)."""
    from benchmarks.layer_metrics import _attn, _qkv, _swa
    from benchmarks.lib.peaks import peaks_of

    cfg = spec.load_cell(CELL).config
    assert _qkv.has_keys(cfg)
    assert _qkv.pair_flops(cfg) == 64 * (192 + 128) * 2 == 40_960
    assert _attn.pair_flops(cfg) == 64 * 192 * 4  # why not that: 20 % more
    assert _qkv.row_bytes(cfg, _qkv.FULL) == 2560
    assert _qkv.row_bytes(cfg, _qkv.WINDOW) == 5120
    assert _swa.row_bytes(cfg) == 2 * 4 * 192 * 2  # ...the wrong kv heads
    assert "2 full layers x (768 + 512) lanes x 2 B = 5,120 B" \
        in cfg["arithmetic"]
    peaks = peaks_of("TPU v5 lite")
    # a 497-token chunk at 12 k: its FLOPs bound it; 15 decode rows: bytes
    least, bound = _qkv.least_seconds(cfg, _qkv.FULL, 497 * 12_000, 12_000,
                                      peaks)
    assert bound == "flops" and least == pytest.approx(
        497 * 12_000 * 40_960 / 197e12)
    least, bound = _qkv.least_seconds(cfg, _qkv.FULL, 15 * 12_000,
                                      15 * 12_000, peaks)
    assert bound == "hbm" and least == pytest.approx(
        15 * 12_000 * 2560 / 819e9)
    assert not _qkv.has_keys({"num_attention_heads": 64, "head_dim": 128})


def test_the_readers_on_a_synthetic_capture():
    """2 launches of the full layers' kernels a pass and 5 of the window
    layers'; the trace holds 3 ragged steps and 2 scans of 8 passes."""
    from benchmarks.layer_metrics import _qkv
    from benchmarks.lib.peaks import peaks_of

    cell = spec.load_cell(CELL)
    peaks = peaks_of("TPU v5 lite")
    passes = 3 + 16
    trace = {"busy_s": 0.5, "op_self_s": {
        "ragged_paged_attention_pallas.1": 0.03,
        "paged_decode_attention_pallas.2": 0.02,
        "swa_ragged_attention_pallas.3": 0.006,
        "swa_decode_attention_pallas.4": 0.004, "gmm.4": 0.1},
        "op_count": {"ragged_paged_attention_pallas.1": 2.0 * 3,
                     "paged_decode_attention_pallas.2": 2.0 * 16,
                     "swa_ragged_attention_pallas.3": 5.0 * 3,
                     "swa_decode_attention_pallas.4": 5.0 * 16,
                     "gmm.4": 18.0 * passes}}
    rows = dict(attn_row_bytes=2560, swa_row_bytes=5120)
    ragged = dict(mode="ragged", k_cap=0, attn_pairs=512 * 12_000,
                  attn_ctx_rows=16 * 12_000, attn_tall_tokens=448,
                  swa_pairs=512 * 128, swa_ctx_rows=16 * 128 + 497,
                  swa_walk_rows=16 * 160 + 497, swa_full_rows=16 * 12_000,
                  **rows)
    scan = dict(mode="decode", k_cap=8, attn_pairs=128 * 12_000,
                attn_ctx_rows=128 * 12_000, attn_tall_tokens=0,
                swa_pairs=128 * 128, swa_ctx_rows=128 * 128,
                swa_walk_rows=128 * 160, swa_full_rows=128 * 12_000, **rows)
    samples = [ragged, scan]
    said = {}
    ctx = types.SimpleNamespace(
        cell=cell, trace=trace, trace_steps=samples, steps=samples,
        peaks=peaks, say=lambda note, **kw: said.update({note: kw}))
    read = {name: spec.load_reader(cell, next(
        m for m in cell.metrics if m.name == name)).read
        for name in OWN_METRICS + ("attn_kernel_share_pct.thr",
                                   "swa_attn_share_pct.thr")}
    assert read["attn_kernel_share_pct.thr"](ctx) == pytest.approx(10.0)
    assert read["swa_attn_share_pct.thr"](ctx) == pytest.approx(2.0)
    # the full layers: a pass's pairs and rows over the samples' 9 passes,
    # times the trace's 38 launches; the scans' passes each read every slot's
    # context: the cached rows at the HBM peak are more than the pairs' FLOPs
    pairs, ctx_rows = (512 + 128) * 12_000 / 9, (16 + 128) * 12_000 / 9
    by_flops = pairs * 38 * 40_960 / peaks["flops_bf16"]
    by_bytes = ctx_rows * 38 * 2560 / peaks["hbm_bytes_per_s"]
    assert by_bytes > by_flops
    assert read["attn_qkv_roofline_pct"](ctx) == pytest.approx(
        100 * by_bytes / 0.05)
    assert said["attn_qkv_roofline"]["bound_by"] == "hbm"
    ctx.trace_steps = [ragged]  # a chunk's span alone: its FLOPs bound it
    assert read["attn_qkv_roofline_pct"](ctx) == pytest.approx(
        100 * 512 * 12_000 * 38 * 40_960 / peaks["flops_bf16"] / 0.05)
    assert said["attn_qkv_roofline"]["bound_by"] == "flops"
    ctx.trace_steps = samples
    assert said["attn_qkv_roofline"]["row_bytes"] == 2560
    pairs, ctx_rows = (512 + 128) * 128 / 9, (16 * 128 + 497 + 128 * 128) / 9
    by_flops = pairs * 95 * 40_960 / peaks["flops_bf16"]
    by_bytes = ctx_rows * 95 * 5120 / peaks["hbm_bytes_per_s"]
    assert read["swa_qkv_roofline_pct"](ctx) == pytest.approx(
        100 * max(by_flops, by_bytes) / 0.01)
    assert said["swa_qkv_roofline"]["row_bytes"] == 5120
    # the layout holds no lane the model lacks: 0; a 192-lane key head
    # padded to 256 lanes would read 20
    assert read["kv_row_padding_pct.thr"](ctx) == 0.0
    padded = [dict(s, attn_row_bytes=4 * (256 + 128) * 2,
                   swa_row_bytes=8 * (256 + 128) * 2) for s in samples]
    ctx.steps = padded
    assert read["kv_row_padding_pct.thr"](ctx) == pytest.approx(20.0)
    ctx.steps = samples
    # a program without the counters (the parent), or a run without a
    # trace, gives the readers nothing to read: None, and nothing raised
    ctx.trace_steps = ctx.steps = [{"mode": "decode", "k_cap": 8}]
    assert all(read[n](ctx) is None for n in OWN_METRICS)
    ctx.trace, ctx.trace_steps, ctx.steps = None, samples, []
    assert all(read[n](ctx) is None for n in OWN_METRICS)
    # no such op on the trace (a rehearsal on the CPU): 0
    ctx.trace = {"busy_s": 0.1, "op_self_s": {"fusion": 0.1},
                 "op_count": {"fusion": 9.0}}
    assert read["attn_qkv_roofline_pct"](ctx) == 0.0
    assert read["swa_qkv_roofline_pct"](ctx) == 0.0
    # ...and another family's file gives them nothing to read either
    other = spec.load_cell("k-exaone-236b-a23b-ep8-d5.longctx")
    ctx.cell, ctx.steps = other, samples
    assert all(read[n](ctx) is None for n in OWN_METRICS)
    assert _qkv.has_keys(cell.config) and not _qkv.has_keys(other.config)


def test_the_reference_is_independent_of_the_programs_ops():
    with open(os.path.join(BENCH, "reference",
                           "mimo_v2_flash_decoder.py")) as f:
        src = f.read()
    assert "import ollamamq_tpu" not in src and "from ollamamq_tpu" not in src
    assert "pallas" not in src.split('"""', 2)[2]  # (its docstring aside)
    assert "lay_heads" not in src.split('"""', 2)[2]


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
    assert set(THR_METRICS) | set(SHARED_METRICS) | set(OWN_METRICS) \
        <= set(line["metrics"])
    assert line["metrics"]["kv_row_padding_pct.thr"]["value"] == 0.0
    assert line["attempted"] > 0 and line["failed"] == 0
    notes = {n["note"]: n for n in map(json.loads, r.stdout.splitlines()[:-1])}
    # (tiny bfloat16 weights over a 512-id vocabulary, 48 positions: the
    # margin is reported and finite and judged on the chip, at the published
    # widths)
    assert notes["reference"]["positions"] > 0
    assert "error" not in notes["reference"]
    assert 0 <= notes["reference"]["mean_margin_sd"] < 0.5
