"""The Xing4.0 configuration (four residual streams mixed by
manifold-constrained hyper-connections around latent attention and 64 experts
ALL on the chip), its reference and its cell, on the CPU:
    python -m pytest benchmarks/tests/test_xing4_cell.py -q

That they load as files and entries; that the configuration file holds every
number of the catalog's row and reaches the program's ModelConfig key by key;
the `mhc_*` readers' arithmetic against the file's, and on a synthetic
capture; and the whole control flow of the cell at a tiny size. The cell is
held to AT LEAST its names (a later PR may append to the lists it is on).
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

from benchmarks.lib import arch, result, spec  # noqa: E402

CELL = "xing4.0-29b-a4b-d6.reason64"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
OWN_METRICS = ("mhc_share_pct.thr", "mhc_roofline_pct", "mhc_launch_us")
SHARED_METRICS = ("mla_dense_attn_share_pct.thr",
                  "mla_dense_attn_roofline_pct",
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
START_UP = ("ready_s", "ready_weights_s", "warm_compile_s",
            "warm_trace_lower_s", "warm_backend_s", "compile_cache_hit_pct",
            "setup_named_pct")
# another stack's own (the other `reason64` cell's among them)
NOT_THIS_CELLS = ("attn_kernel_roofline_pct", "swa_", "s6_", "xattn_",
                  "exit_", "mla_attn_", "dsa_", "mtp_", "ssm_", "bsa_",
                  "lightning_", "kda_", "lin_", "collective_share_pct")


def _published() -> dict:
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    return next(r for r in rows if r["name"] == "Xing4.0-29B-A4B")


# ------------------------------------------------------- files and entries
def test_the_cell_its_configuration_and_its_reference_load():
    cell = spec.load_cell(CELL)
    cfg = cell.config
    assert cell.chips == 1 and cfg["chips"] == 1
    assert cell.traffic["kind"] == "closed" and cell.traffic["clients"] == 96
    assert cell.traffic["output_tokens"] == {"dist": "fixed", "value": 1024}
    assert cell.traffic == spec.load_cell(
        "phi-4-mini-flash-reasoning.reason64").traffic  # the EXISTING mix
    assert cfg["reference"] == "xing4_decoder"
    assert os.path.exists(os.path.join(BENCH, "reference",
                                       cfg["reference"] + ".py"))
    bj = spec.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = next(c for c in bj["configs"] if c["name"] == cfg["name"])
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace", "num_dense_layers",
        "num_nextn_predict_layers"]
    assert entry["source"] == cfg["source"]
    assert cfg["reduced_from"] == {
        "num_hidden_layers": 40, "first_k_dense_replace": 2,
        "num_dense_layers": 2, "num_nextn_predict_layers": 1}
    names = {m.name for m in cell.metrics_of("per_layer")}
    assert names >= set(OWN_METRICS) | set(SHARED_METRICS) \
        | set(THR_METRICS) | set(START_UP)
    assert not [n for n in names if n.startswith(NOT_THIS_CELLS)]
    assert {m.name for m in cell.metrics_of("end_to_end")} \
        >= {"output_tok_s", "setup_s"}
    for m in cell.metrics:
        spec.load_reader(cell, m)  # every listed metric has its reader
    own = [m for m in bj["per_layer"] if m["name"] in OWN_METRICS]
    assert [m["name"] for m in own] == list(OWN_METRICS)
    assert all(m["layer"] == "residual path" and m["moves"] == "output_tok_s"
               and m["source"] == "device_trace" and CELL in m["workloads"]
               for m in own)
    assert sum(w["chips"] == 4 for w in bj["workloads"]) == 1
    assert len(bj["workloads"]) >= 16 and len(bj["configs"]) >= 15
    for key in ("deployment", "assumed", "arithmetic", "dtype", "routing",
                "rehearse", "server_flags", "num_pages_reason"):
        assert cfg.get(key), key
    assert "ep_size 1" in cfg["deployment"] and "FIRST stage" \
        in cfg["deployment"] and "pipeline stages" in cfg["deployment"]
    warned = [k for k, v in cfg["assumed"].items()
              if "A LOADER OF REAL WEIGHTS MUST CHECK IT" in v]
    assert set(warned) >= {"hc_flat_norm", "hc_eps_placement",
                           "hc_sinkhorn_order", "hc_res_direction",
                           "hc_clamp"}


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_the_file_holds_every_number_of_the_catalog_row():
    row, cfg = _published(), spec.load_cell(CELL).config
    assert cfg["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items()
                     if cfg.get(k, "absent") != v)
    assert differs == sorted(set(cfg["reduced"]) - {"num_dense_layers"})
    assert {k: cfg["reduced_from"][k] for k in differs} \
        == {k: row["config"][k] for k in differs}
    assert cfg["rope_scaling"] == row["config"]["rope_scaling"]  # whole
    for key, value in (("hidden_size", 3584), ("num_attention_heads", 32),
                       ("q_lora_rank", 768), ("kv_lora_rank", 512),
                       ("qk_nope_head_dim", 128), ("qk_rope_head_dim", 64),
                       ("v_head_dim", 128), ("intermediate_size", 9216),
                       ("n_routed_experts", 64),
                       ("moe_intermediate_size", 1024),
                       ("num_experts_per_tok", 4),
                       ("routed_scaling_factor", 2), ("n_shared_experts", 1),
                       ("vocab_size", 131072), ("hc_mult", 4),
                       ("hc_sinkhorn_iters", 20), ("hc_eps", 1e-6),
                       ("mhc_h_res_clamp_min", -30),
                       ("mhc_h_res_clamp_max", 30), ("ep_size", 1)):
        assert cfg[key] == row["config"][key] == value, key
    assert arch.attention_layers(cfg) == 6 and arch.expert_layers(cfg) == 5
    assert arch.expert_width(cfg) == 1024 and arch.num_experts(cfg) == 64


def test_the_program_runs_the_configuration_files_model():
    from benchmarks import serve

    cfg = spec.load_cell(CELL).config
    mc = serve.model_config(cfg, rehearse=False)
    assert mc.param_count() == 4_792_727_177
    assert "4,792,727,177 parameters" in cfg["arithmetic"]
    assert mc.streams == 4 and mc.hc_maps == 24 and mc.head_dim == 192
    assert mc.count("full_attention") == mc.cache_layers == 6
    assert (mc.router_width, mc.num_experts, mc.num_experts_per_tok,
            mc.router_score, mc.use_expert_bias, mc.n_shared_experts,
            mc.n_group, mc.num_dense_layers, mc.routed_scaling_factor) \
        == (64, 64, 4, "sigmoid", True, 1, 0, 1, 2)
    assert mc.kv_row_dims == (640, 0) and mc.num_nextn_predict_layers == 0
    tiny = serve.model_config(cfg, rehearse=True)
    assert tiny.streams == 4 and tiny.hc_sinkhorn_iters == 20
    assert (tiny.head_dim, tiny.num_experts, tiny.num_layers) == (24, 8, 3)
    with pytest.raises(serve.Refused, match="hc_mult 3"):
        serve.model_config(dict(cfg, hc_mult=3), rehearse=False)
    with pytest.raises(serve.Refused, match="hc_sinkhorn_iters 0"):
        serve.model_config(dict(cfg, hc_sinkhorn_iters=0), rehearse=False)
    with pytest.raises(serve.Refused, match="B-M12"):  # the module, held out
        serve.model_config(dict(cfg, num_nextn_predict_layers=1),
                           rehearse=False)
    with pytest.raises(serve.Refused, match="ep_size"):
        serve.model_config(dict(cfg, ep_size=4), rehearse=False)
    with pytest.raises(serve.Refused, match="no field"):
        serve.model_config(dict(cfg, hc_read_out="sum"), rehearse=False)


# ------------------------------------------------------------ the readers
def test_the_readers_bytes_are_the_files_arithmetic():
    """An application around a sublayer over 64 rows: ten streams' worth of
    3584 bfloat16 lanes a row (four in, four out, delta in, h out) and Phi's
    [24, 14336] float32 once — 5.96 MB, 7.3 us at the HBM peak, against 0.45
    us of the product's FLOPs; a latent pair 32 heads x (192 + 128) x 2."""
    from benchmarks.layer_metrics import _mhc, _mla_dense, _ops
    from benchmarks.lib.peaks import peaks_of

    cfg = spec.load_cell(CELL).config
    assert _mhc.sizes(cfg) == (4, 3584)
    assert _mhc.sizes({"hidden_size": 4}) is None  # another family's file
    peaks = peaks_of("TPU v5 lite")
    least, bound = _mhc.least_seconds(cfg, 64, 2, 1, peaks)
    stream_bytes = 64 * 10 * 3584 * 2
    phi_bytes = (24 + 4) * 14336 * 4  # a sublayer's and the read-out's
    assert "14,336 x 24 + 24 + 3" in cfg["arithmetic"]
    assert bound == "hbm" and least == pytest.approx(
        (stream_bytes + phi_bytes) / 819e9)
    assert 64 * 2 * 14336 * 24 / 197e12 < least / 10
    # a pass: 12 applications around sublayers and the read-out
    least13, _ = _mhc.least_seconds(cfg, 64, 13, 1, peaks)
    assert least13 == pytest.approx(
        (12 * stream_bytes + (12 * 24 + 4) * 14336 * 4) / 819e9)
    assert _mhc.launches_a_pass(13) == 25
    # 4096 rows an application: still bytes (10 x 7168 B against 688 kFLOP)
    assert _mhc.least_seconds(cfg, 4096, 2, 1, peaks)[1] == "hbm"
    assert _mla_dense.pair_flops(cfg) == 32 * (192 + 128) * 2
    assert _mla_dense.row_bytes(cfg) == 576 * 2
    for name in ("mhc_mix_in_pallas.3 (bf16[64,3584]", "mhc_mix_out_pallas"):
        assert _mhc.KERNEL.search(name) and not _ops.ATTENTION.search(name)
    assert not _mhc.KERNEL.search("mla_dense_paged_attention_pallas.2")
    assert _ops.ATTENTION.search("mla_dense_paged_attention_pallas.2")


def test_the_readers_on_a_synthetic_capture():
    """13 mix-ins and 12 mix-outs a pass; the trace holds 2 ragged steps and
    3 scans of 8 passes (26 passes); the capture's samples say a ragged step
    carries 575 tokens and a scan 64 rows a pass."""
    from benchmarks.layer_metrics import _mhc
    from benchmarks.lib.peaks import peaks_of

    cell = spec.load_cell(CELL)
    peaks = peaks_of("TPU v5 lite")
    passes = 2 + 3 * 8
    trace = {"busy_s": 0.5, "op_self_s": {
        "mhc_mix_in_pallas.3": 0.006, "mhc_mix_out_pallas.1": 0.004,
        "mla_dense_paged_attention_pallas.2": 0.05, "gmm.4": 0.2,
        "fusion.7": 0.05},
        "op_count": {"mhc_mix_in_pallas.3": 13.0 * passes,
                     "mhc_mix_out_pallas.1": 12.0 * passes,
                     "mla_dense_paged_attention_pallas.2": 6.0 * passes,
                     "gmm.4": 15.0 * passes, "fusion.7": 99.0}}
    ragged = dict(mode="ragged", k_cap=0, mhc_rows=575, mhc_apps=13)
    scan = dict(mode="decode", k_cap=8, mhc_rows=64 * 8, mhc_apps=13)
    samples = [ragged, scan, scan]
    said = {}
    ctx = types.SimpleNamespace(
        cell=cell, trace=trace, trace_steps=samples, steps=samples,
        peaks=peaks, say=lambda note, **kw: said.update({note: kw}))
    read = {name: spec.load_reader(cell, next(
        m for m in cell.metrics if m.name == name)).read
        for name in OWN_METRICS}
    assert read["mhc_share_pct.thr"](ctx) == pytest.approx(2.0)
    assert read["mhc_launch_us"](ctx) == pytest.approx(
        1e6 * 0.010 / (25 * passes))
    rows = (575 + 2 * 512) / 17  # tokens a pass over the samples' 17 passes
    least, _ = _mhc.least_seconds(cell.config, rows, 13, passes, peaks)
    assert read["mhc_roofline_pct"](ctx) == pytest.approx(100 * least / 0.010)
    assert 0 < read["mhc_roofline_pct"](ctx) <= 100
    assert said["mhc_roofline"]["passes_in_trace"] == pytest.approx(passes)
    assert said["mhc_roofline"]["bound_by"] == "hbm"
    # a program without the counters (the parent), or a run without a
    # trace, gives the readers nothing to read: None, and nothing raised
    ctx.trace_steps = ctx.steps = [{"mode": "decode", "k_cap": 8}]
    assert all(read[n](ctx) is None for n in OWN_METRICS)
    ctx.trace_steps = []
    assert all(read[n](ctx) is None for n in OWN_METRICS)
    ctx.trace, ctx.trace_steps = None, samples
    assert all(read[n](ctx) is None for n in OWN_METRICS)
    # no such op on the trace (a rehearsal on the CPU): 0
    ctx.trace = {"busy_s": 0.1, "op_self_s": {"fusion": 0.1},
                 "op_count": {"fusion": 9.0}}
    assert all(read[n](ctx) == 0.0 for n in OWN_METRICS)


def test_the_reference_is_independent_of_the_programs_ops():
    with open(os.path.join(BENCH, "reference", "xing4_decoder.py")) as f:
        src = f.read()
    assert "import ollamamq_tpu" not in src and "from ollamamq_tpu" not in src
    assert "pallas" not in src.split('"""', 2)[2]  # (its docstring aside)
    assert "for _ in range(cfg[\"hc_sinkhorn_iters\"])" in src


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
    assert all(line["metrics"][n]["value"] == 0.0 for n in OWN_METRICS)
    assert line["attempted"] > 0 and line["failed"] == 0
    notes = {n["note"]: n for n in map(json.loads, r.stdout.splitlines()[:-1])}
    # (tiny bfloat16 weights over a 512-id vocabulary; the margin is reported
    # and finite and judged on the chip, at the published widths)
    assert notes["reference"]["positions"] > 0
    assert "error" not in notes["reference"]
    assert 0 <= notes["reference"]["mean_margin_sd"] < 0.5
