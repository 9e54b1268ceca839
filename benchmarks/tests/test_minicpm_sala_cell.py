"""The MiniCPM-SALA configuration (block-sparse attention beside lightning
linear attention), its reference and its cell, on the CPU:
    python -m pytest benchmarks/tests/test_minicpm_sala_cell.py -q

That they load as files and entries; that the configuration file holds every
number of the catalog's row and reaches the program's ModelConfig key by key;
the `bsa_*` / `lightning_*` readers' arithmetic against the file's, and on a
synthetic capture; and the whole control flow of the cell at a tiny size, at
which `sparse_dense_len` and `sparse_topk` still bite: the rehearsal SELECTS.
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

CELL = "minicpm-sala-d16.longctx512"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
OWN_METRICS = ("bsa_select_share_pct.thr", "bsa_attn_share_pct.thr",
               "bsa_attn_roofline_pct", "bsa_select_roofline_pct",
               "bsa_blocks_walked_pct.thr", "bsa_kept_pct.thr",
               "lightning_step_share_pct.thr", "lightning_step_roofline_pct")
THR_METRICS = ("tokens_per_step.thr", "host_ms_per_step.thr",
               "device_ms_per_step.thr", "attn_kernel_share_pct.thr",
               "device_idle_pct.thr", "loop_ms_per_step.thr",
               "idle_explained_pct.thr", "device_wait_ms_per_step.thr",
               "stream_frame_tokens", "stream_wakeups_per_step")
NOT_THIS_CELLS = ("lin_", "moe_", "mla_", "dsa_", "swa_", "mtp_", "ssm_",
                  "s6_", "collective_share_pct", "attn_kernel_roofline_pct")


def _published() -> dict:
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    return next(r for r in rows if r["name"] == "MiniCPM-SALA")


# ------------------------------------------------------- files and entries
def test_the_cell_its_configuration_and_its_reference_load():
    cell = spec.load_cell(CELL)
    cfg = cell.config
    assert cell.chips == 1 and cfg["chips"] == 1
    assert cell.traffic["kind"] == "closed" and cell.traffic["clients"] == 24
    assert cell.traffic["output_tokens"] == {"dist": "fixed", "value": 512}
    longctx = spec.load_json(os.path.join(BENCH, "traffic", "longctx.json"))
    assert {k: v for k, v in cell.traffic.items()
            if k not in ("about", "output_tokens")} \
        == {k: v for k, v in longctx.items()
            if k not in ("about", "output_tokens")}
    assert cfg["reference"] == "minicpm_sala_decoder"
    assert os.path.exists(os.path.join(BENCH, "reference",
                                       cfg["reference"] + ".py"))
    bj = spec.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = next(c for c in bj["configs"] if c["name"] == cfg["name"])
    assert entry["reduced"] == cfg["reduced"] \
        == ["num_hidden_layers", "mixer_types"]
    assert cfg["reduced_from"]["num_hidden_layers"] == 32
    names = {m.name for m in cell.metrics_of("per_layer")}
    assert set(OWN_METRICS) | set(THR_METRICS) <= names  # at least
    assert not [n for n in names if n.startswith(NOT_THIS_CELLS)]
    assert {m.name for m in cell.metrics_of("end_to_end")} \
        == {"output_tok_s", "setup_s"}
    for m in cell.metrics:
        spec.load_reader(cell, m)  # every listed metric has its reader
    assert sum(w["chips"] == 4 for w in bj["workloads"]) == 1
    assert len(bj["workloads"]) == 13 and len(bj["configs"]) == 12


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_the_file_holds_every_number_of_the_catalog_row():
    row, cfg = _published(), spec.load_cell(CELL).config
    assert cfg["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items()
                     if cfg.get(k, "absent") != v)
    assert differs == ["mixer_types", "num_hidden_layers"]
    assert cfg["mixer_types"] == row["config"]["mixer_types"][9:25]
    assert cfg["reduced_from"]["mixer_types"] == row["config"]["mixer_types"]
    assert cfg["layer_offset"] == 9 and cfg["scale_depth_layers"] == 32
    kinds = {"minicpm4": "sparse_attention",
             "lightning-attn": "linear_attention"}
    assert cfg["layer_types"] == [kinds[m] for m in cfg["mixer_types"]]
    assert cfg["layer_types"].count("sparse_attention") == 4
    assert arch.attention_layers(cfg) == 0  # no `full_attention` layer:
    # device_ms_per_step counts the capture's samples' passes instead


def test_the_program_runs_the_configuration_files_model():
    from benchmarks import serve

    cfg = spec.load_cell(CELL).config
    mc = serve.model_config(cfg, rehearse=False)
    assert mc.param_count() == 5_039_448_064
    assert "5,039,448,064 parameters" in cfg["arithmetic"]
    assert mc.count("sparse_attention") == mc.cache_layers == 4
    assert mc.count("linear_attention") == 12 and mc.lightning_nh == 32
    assert mc.residual_multiplier == pytest.approx(1.4 / 32 ** 0.5)
    assert len(mc.layer_plan()) == 6  # no period: six runs
    flags = serve.server_flags(cfg, False)
    pages, ps = (int(flags[flags.index(f) + 1])
                 for f in ("--num-pages", "--page-size"))
    assert mc.pooled_rows(pages, ps) == 2 * pages
    tiny = serve.model_config(cfg, rehearse=True)
    assert tiny.layer_types == ("sparse_attention", "linear_attention",
                                "linear_attention", "sparse_attention")
    # the rehearsal's prompts (96 tokens) pass dense_len and hold more
    # blocks than are kept: it selects for real
    assert tiny.sparse_dense_len == 32 < 96
    assert 96 // tiny.sparse_block_size > tiny.sparse_topk == 4
    with pytest.raises(serve.Refused, match="use_output_norm"):
        serve.model_config(dict(cfg, use_output_norm=False), rehearse=False)
    with pytest.raises(serve.Refused, match="sparse_kernel_size"):
        serve.model_config(dict(cfg, sparse_kernel_size=24), rehearse=False)
    with pytest.raises(serve.Refused, match="mixer_types|layer_types"):
        serve.model_config({k: v for k, v in cfg.items() if k != "rehearse"},
                           rehearse=True)  # a list the block does not cover


# ------------------------------------------------------------ the readers
def test_the_readers_bytes_are_the_files_arithmetic():
    """A kept block a one-token query: 64 positions x 1 KB of K and V = 64
    KiB, 1.05 MFLOP; a block in context costs its scores 4 pooled rows of
    512 B; a lightning row a layer 2 MiB of state read and written."""
    from benchmarks.layer_metrics import _bsa, _lightning, _ops, _ssm
    from benchmarks.lib.peaks import peaks_of

    cfg = spec.load_cell(CELL).config
    assert _bsa.row_bytes(cfg) == 512
    assert _bsa.walk_block(cfg) == (65_536, 64 * 32 * 128 * 4)
    assert _bsa.select_block(cfg) == (2_048, 4 * 32 * 128 * 2)
    # 16 rows x 64 kept blocks x 4 layers: 0.27 GB — the file's figure
    assert 16 * 64 * 4 * _bsa.walk_block(cfg)[0] == 268_435_456
    assert "0.27 GB is what the mathematics asks" in cfg["arithmetic"]
    assert _lightning.state_elements(cfg) * 4 == 2_097_152
    assert "2,097,152 B a slot a layer" in cfg["arithmetic"]
    assert _lightning.row_bytes(cfg) == 2 * 2_097_152 + 4 * (5 * 4096 + 32)
    least, bound = _lightning.least_seconds(cfg, 16 * 12,
                                            peaks_of("TPU v5 lite"))
    assert bound == "hbm" and least == pytest.approx(
        16 * 12 * _lightning.row_bytes(cfg) / 819e9, rel=1e-2)
    walk, select = "bsa_decode_attention_pallas.3 (bf16[32,", \
        "bsa_select_pallas.1 (f32[16,2,1,1152]"
    assert _bsa.WALK.search(walk) and not _bsa.WALK.search(select)
    assert _bsa.SELECT.search(select) and not _bsa.SELECT.search(walk)
    assert not _ops.ATTENTION.search(walk)  # the full layers' readers
    assert not _ops.ATTENTION.search(select)  # do not count these
    assert _lightning.KERNEL.pattern == _ssm.SSM_KERNEL.pattern


def test_the_readers_on_a_synthetic_capture():
    """4 launches of each kernel a pass; 3 passes in the trace; the samples
    of the capture say 960 kept blocks and 2,880 blocks in context a pass
    for the one-token rows, 15 live rows a pass."""
    from benchmarks.layer_metrics import _bsa, _lightning
    from benchmarks.lib.peaks import peaks_of

    cell = spec.load_cell(CELL)
    peaks = peaks_of("TPU v5 lite")
    trace = {"busy_s": 0.1, "op_self_s": {
        "bsa_decode_attention_pallas.3": 0.004, "bsa_select_pallas": 0.001,
        "ssd_step_pallas.2": 0.006,
        "ragged_paged_attention_pallas.11": 0.02, "fusion.7": 0.05},
        "op_count": {"bsa_decode_attention_pallas.3": 12.0,
                     "bsa_select_pallas": 12.0, "ssd_step_pallas.2": 36.0,
                     "ragged_paged_attention_pallas.11": 8.0,
                     "fusion.7": 99.0}}
    zeros = dict.fromkeys(_bsa.FIELDS + _lightning.FIELDS, 0)
    samples = [
        dict(zeros, mode="decode", k_cap=2, bsa_blocks_kept_step=1920,
             bsa_blocks_walked_step=1920, bsa_blocks_in_context_step=5760,
             lightning_step_rows=30),
        dict(zeros, mode="ragged", k_cap=0, bsa_blocks_kept_step=960,
             bsa_blocks_walked_step=960, bsa_blocks_in_context_step=2880,
             bsa_blocks_kept_span=64 * 100, bsa_blocks_walked_span=200 * 100,
             bsa_blocks_in_context_span=200 * 100, lightning_step_rows=15,
             lightning_span_tokens=496)]
    said = {}
    ctx = types.SimpleNamespace(
        cell=cell, trace=trace, trace_steps=samples, steps=samples,
        peaks=peaks, say=lambda note, **kw: said.update({note: kw}))
    read = {name: spec.load_reader(cell, next(
        m for m in cell.metrics if m.name == name)).read
        for name in OWN_METRICS}
    assert read["bsa_attn_share_pct.thr"](ctx) == pytest.approx(4.0)
    assert read["bsa_select_share_pct.thr"](ctx) == pytest.approx(1.0)
    assert read["lightning_step_share_pct.thr"](ctx) == pytest.approx(6.0)
    least = 960 * 12 * 65_536 / peaks["hbm_bytes_per_s"]
    assert read["bsa_attn_roofline_pct"](ctx) \
        == pytest.approx(100 * least / 0.004)
    assert said["bsa_attn_roofline"]["bound_by"] == "hbm"
    assert said["bsa_attn_roofline"]["blocks_a_pass"] == 960
    least = 2880 * 12 * 2_048 / peaks["hbm_bytes_per_s"]
    assert read["bsa_select_roofline_pct"](ctx) \
        == pytest.approx(100 * least / 0.001)
    least, _ = _lightning.least_seconds(cell.config, 15 * 36, peaks)
    assert read["lightning_step_roofline_pct"](ctx) \
        == pytest.approx(100 * least / 0.006)
    kept, ctx_blocks = 1920 + 960 + 6400, 5760 + 2880 + 20000
    assert read["bsa_kept_pct.thr"](ctx) \
        == pytest.approx(100 * kept / ctx_blocks)
    assert read["bsa_blocks_walked_pct.thr"](ctx) \
        == pytest.approx(100 * (1920 + 960 + 20000) / ctx_blocks)
    # a program without the counters (the parent), or a run without a
    # trace, gives the readers nothing to read: None, and nothing raised
    ctx.trace_steps = ctx.steps = [{"mode": "decode", "k_cap": 8}]
    assert all(r(ctx) is None for r in read.values())
    ctx.trace, ctx.trace_steps = None, samples
    assert all(read[n](ctx) is None for n in OWN_METRICS
               if "blocks" not in n and "kept" not in n)
    # no such op on the trace (a rehearsal on the CPU): 0
    ctx.trace = {"busy_s": 0.1, "op_self_s": {"fusion": 0.1},
                 "op_count": {"fusion": 9.0}}
    assert all(read[n](ctx) == 0.0 for n in OWN_METRICS
               if "blocks" not in n and "kept" not in n)


# ------------------------------------------------------------- end to end
def test_rehearsal_of_the_cell_selects_and_reads_every_metric_it_lists():
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
    assert set(THR_METRICS) | set(OWN_METRICS) <= set(line["metrics"])
    assert line["attempted"] > 0 and line["failed"] == 0
    # 96-token prompts over blocks of 8 under a top 4: 4 of 12 to 13 kept
    assert 25 < line["metrics"]["bsa_kept_pct.thr"]["value"] < 60
    assert line["metrics"]["bsa_kept_pct.thr"]["value"] \
        < line["metrics"]["bsa_blocks_walked_pct.thr"]["value"] <= 100
    notes = {n["note"]: n for n in map(json.loads, r.stdout.splitlines()[:-1])}
    walked = notes["bsa_blocks_walked"]
    assert walked["bsa_blocks_walked_step"] < walked[
        "bsa_blocks_in_context_step"]  # the decode rows follow their lists
    # (tiny bfloat16 weights over a 512-id vocabulary: the margin is
    # reported and finite, and judged on the chip, at the published widths)
    assert notes["reference"]["positions"] > 0
    assert notes["reference"]["mean_margin_sd"] < 0.05
    assert notes["reference"]["lower_precision"]["positions"] > 0
