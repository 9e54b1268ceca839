"""The Falcon-H1 configuration (attention AND a state-space mixer in every
layer), its reference and its cell, on the CPU:
    python -m pytest benchmarks/tests/test_falcon_h1_cell.py -q

That they load as files and entries; that the configuration file holds every
number of the catalog's row and reaches the program's ModelConfig key by key;
the `ssm_*` readers' arithmetic against the file's, and on a synthetic
capture; and the whole control flow of the cell at a tiny size. Nothing here
gives a device number."""

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

CELL = "falcon-h1-34b-d6.batch"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SSM_METRICS = ("ssm_kernel_share_pct.thr", "ssm_step_roofline_pct")
# what every `.batch` cell reports beside its own (AT LEAST these: a later PR
# may list more on every throughput cell, PERF.md section 7 row 14)
THR_METRICS = ("tokens_per_step.thr", "host_ms_per_step.thr",
               "device_ms_per_step.thr", "attn_kernel_share_pct.thr",
               "device_idle_pct.thr", "loop_ms_per_step.thr",
               "idle_explained_pct.thr", "device_wait_ms_per_step.thr",
               "stream_frame_tokens", "stream_wakeups_per_step",
               "idle_named_pct.thr")
NOT_THIS_CELLS = ("lin_", "moe_", "mla_", "dsa_", "swa_", "mtp_",
                  "collective_share_pct", "attn_kernel_roofline_pct")


def _published() -> dict:
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    return next(r for r in rows if r["name"] == "Falcon-H1-34B-Instruct")


# ------------------------------------------------------- files and entries
def test_the_cell_its_configuration_and_its_reference_load():
    cell = spec.load_cell(CELL)
    cfg = cell.config
    assert cell.chips == 1 and cfg["chips"] == 1
    assert cell.traffic["kind"] == "closed" and cell.traffic["clients"] == 96
    assert cfg["reference"] == "falcon_h1_decoder"
    assert os.path.exists(os.path.join(BENCH, "reference",
                                       cfg["reference"] + ".py"))
    bj = spec.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = next(c for c in bj["configs"] if c["name"] == cfg["name"])
    assert entry["reduced"] == cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["reduced_from"] == {"num_hidden_layers": 72}
    names = {m.name for m in cell.metrics_of("per_layer")}
    assert set(SSM_METRICS) | set(THR_METRICS) <= names  # at least
    assert not [n for n in names if n.startswith(NOT_THIS_CELLS)]
    assert {m.name for m in cell.metrics_of("end_to_end")} \
        == {"output_tok_s", "setup_s"}
    for m in cell.metrics:
        spec.load_reader(cell, m)  # every listed metric has its reader
    assert sum(w["chips"] == 4 for w in bj["workloads"]) == 1


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_the_file_holds_every_number_of_the_catalog_row():
    row, cfg = _published(), spec.load_cell(CELL).config
    assert cfg["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items() if cfg.get(k, "absent") != v]
    assert differs == ["num_hidden_layers"]
    assert cfg["num_hidden_layers"] == 6 and row["config"][differs[0]] == 72
    assert arch.attention_layers(cfg) == 6  # every layer attends


def test_the_program_runs_the_configuration_files_model():
    from benchmarks import serve

    cfg = spec.load_cell(CELL).config
    mc = serve.model_config(cfg, rehearse=False)
    assert mc.param_count() == 5_254_594_112
    assert mc.count("attention_ssm") == mc.cache_layers == 6
    assert mc.state_window == (4, 5120)
    tiny = serve.model_config(cfg, rehearse=True)
    assert (tiny.mamba_d_ssm, tiny.mamba_n_heads, tiny.head_dim) \
        == (128, 4, 32)
    assert tiny.ssm_multipliers == tuple(cfg["ssm_multipliers"])
    with pytest.raises(serve.Refused, match="mamba_norm_before_gate"):
        serve.model_config(dict(cfg, mamba_norm_before_gate=True),
                           rehearse=False)
    with pytest.raises(serve.Refused, match="_multipliers"):
        serve.model_config({k: v for k, v in cfg.items() if k != "rehearse"},
                           rehearse=True)  # a list the block does not cover


# ------------------------------------------------------------ the readers
def test_the_readers_bytes_are_the_files_arithmetic():
    """A row a layer: 4 MiB of state read and written, 8,503,424 B with what
    the kernel is handed; 64 x 6 of them 3.27 GB, 3.99 ms at the HBM peak."""
    from benchmarks.layer_metrics import _ssm
    from benchmarks.lib.peaks import peaks_of

    cfg = spec.load_cell(CELL).config
    assert _ssm.state_elements(cfg) * 4 == 4_194_304
    assert _ssm.row_bytes(cfg) == 8_503_424
    assert "4,194,304 B a slot a layer" in cfg["arithmetic"]
    least, bound = _ssm.least_seconds(cfg, 64 * 6, peaks_of("TPU v5 lite"))
    assert bound == "hbm" and 64 * 6 * _ssm.row_bytes(cfg) == 3_265_314_816
    assert least == pytest.approx(3.987e-3, rel=1e-3)
    assert _ssm.SSM_KERNEL.search("ssd_step_pallas.3 (f32[64,1,4096]")
    assert not _ssm.SSM_KERNEL.search("gated_delta_step_pallas.1")
    from benchmarks.layer_metrics import _lin, _ops
    assert not _lin.LIN_KERNEL.search("ssd_step_pallas.3")
    assert not _ops.ATTENTION.search("ssd_step_pallas.3")


def test_the_roofline_reader_counts_live_rows_only_on_a_synthetic_capture():
    """6 launches a pass; 3 passes in the trace; the samples of the capture
    say 40 live rows a pass."""
    from benchmarks.layer_metrics import _ssm
    from benchmarks.lib.peaks import peaks_of

    cell = spec.load_cell(CELL)
    peaks = peaks_of("TPU v5 lite")
    least, _ = _ssm.least_seconds(cell.config, 40 * 18, peaks)
    trace = {"busy_s": 0.1, "op_self_s": {
        "ssd_step_pallas.3_f32_64_1_4096_": 0.012,
        "ssd_step_pallas_f32_64_1_4096_": 0.008,
        "ragged_paged_attention_pallas.11_bf16_": 0.03, "fusion.7": 0.05},
        "op_count": {"ssd_step_pallas.3_f32_64_1_4096_": 12.0,
                     "ssd_step_pallas_f32_64_1_4096_": 6.0,
                     "ragged_paged_attention_pallas.11_bf16_": 6.0,
                     "fusion.7": 99.0}}
    counters = dict(ssm_state_resets=0, ssm_state_carried=40,
                    ssm_span_tokens=0)
    samples = [dict(counters, mode="decode", k_cap=2, ssm_step_rows=80),
               dict(counters, mode="ragged", k_cap=0, ssm_step_rows=40,
                    ssm_span_tokens=100)]
    said = {}
    ctx = types.SimpleNamespace(
        cell=cell, trace=trace, trace_steps=samples, peaks=peaks,
        say=lambda note, **kw: said.update(kw))
    share, roof = (spec.load_reader(cell, next(
        m for m in cell.metrics if m.name == name)) for name in SSM_METRICS)
    assert share.read(ctx) == pytest.approx(20.0)
    assert roof.read(ctx) == pytest.approx(100 * least / 0.020)
    assert said["live_rows_a_pass"] == 40 and said["launches_in_trace"] == 18
    # a program without the counters (the parent), or a run without a
    # trace, gives the readers nothing to read: None, and nothing raised
    ctx.trace_steps = [{"mode": "decode", "k_cap": 8}]
    assert share.read(ctx) is None and roof.read(ctx) is None
    ctx.trace, ctx.trace_steps = None, samples
    assert share.read(ctx) is None and roof.read(ctx) is None
    # no such op on the trace (a rehearsal on the CPU): 0
    ctx.trace = {"busy_s": 0.1, "op_self_s": {"fusion": 0.1},
                 "op_count": {"fusion": 9.0}}
    assert share.read(ctx) == 0.0 and roof.read(ctx) == 0.0


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
    assert set(THR_METRICS) | set(SSM_METRICS) <= set(line["metrics"])
    assert line["attempted"] > 0 and line["failed"] == 0
    notes = {n["note"]: n for n in map(json.loads, r.stdout.splitlines()[:-1])}
    assert notes["reference"]["agrees"] is True, notes["reference"]
    assert notes["reference"]["positions"] > 0
    # (six positions of one tiny request: the float8 forward's reading is
    # reported, and judged on the chip, where a request has 256)
    assert notes["reference"]["lower_precision"]["positions"] > 0
