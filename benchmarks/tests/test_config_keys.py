"""A configuration file is the whole description of its model, on the CPU:
    python -m pytest benchmarks/tests/test_config_keys.py -q

Every architecture key of a file reaches the served program's `ModelConfig`
or stops the run at start (serve.py exits non-zero before the CLI starts,
run.py then exits 1 with no result line); a rehearsal takes its tiny sizes
from the harness and from the file's own `rehearse` block; and the readers
take their layer counts from `lib/arch.py`. Nothing here gives a device
number."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
import types

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from benchmarks import serve  # noqa: E402
from benchmarks.lib import arch, spec  # noqa: E402

DENSE_7B, DENSE_8B, SPARSE = ("qwen2.5-7b-d14", "qwen3-8b-tp4",
                              "olmoe-1b-7b-d10")
# What the parent's serve.py built from each file, field for field (PR 30's
# tree, read there): the step programs, the weights and the pool of a cell
# are functions of exactly this.
SERVED = {
    DENSE_7B: dict(
        vocab_size=152064, hidden_size=3584, intermediate_size=18944,
        num_layers=14, num_heads=28, num_kv_heads=4, head_dim=128,
        rope_theta=1000000.0, rms_norm_eps=1e-06, max_seq_len=32768,
        tie_embeddings=False, attn_bias=True, qk_norm=False,
        is_encoder=False, num_experts=0, num_experts_per_tok=2,
        norm_topk_prob=False),
    DENSE_8B: dict(
        vocab_size=151936, hidden_size=4096, intermediate_size=12288,
        num_layers=36, num_heads=32, num_kv_heads=8, head_dim=128,
        rope_theta=1000000.0, rms_norm_eps=1e-06, max_seq_len=32768,
        tie_embeddings=False, attn_bias=False, qk_norm=True,
        is_encoder=False, num_experts=0, num_experts_per_tok=2,
        norm_topk_prob=False),
    SPARSE: dict(
        vocab_size=50304, hidden_size=2048, intermediate_size=1024,
        num_layers=10, num_heads=16, num_kv_heads=16, head_dim=128,
        rope_theta=10000, rms_norm_eps=1e-05, max_seq_len=4096,
        tie_embeddings=False, attn_bias=False, qk_norm="full",
        is_encoder=False, num_experts=64, num_experts_per_tok=8,
        norm_topk_prob=False),
}
REHEARSED = dict(vocab_size=512, hidden_size=128, intermediate_size=256,
                 num_layers=2, num_heads=8, num_kv_heads=4, head_dim=16,
                 max_seq_len=2048)
# The 14-layer hybrid of ISSUE 31's Motivation: two leading dense layers of
# the other kind, then three periods of (attention, other, other, other);
# experts of a width of their own in all but the dense prefix.
HYBRID = {
    "num_hidden_layers": 14, "hidden_size": 2048, "intermediate_size": 7168,
    "moe_intermediate_size": 1792, "num_dense_layers": 2, "num_experts": 32,
    "layer_types": ["conv", "conv"] + ["full_attention", "conv", "conv",
                                       "conv"] * 3,
}


def config(name: str) -> dict:
    return spec.load_json(os.path.join(BENCH, "configs", name + ".json"))


# ------------------------------------------------- keys reach the program
@pytest.mark.parametrize("name", sorted(SERVED))
def test_each_file_builds_the_model_config_the_parent_built(name):
    import dataclasses

    from ollamamq_tpu.config import ModelConfig

    mc = serve.model_config(config(name), rehearse=False)
    assert mc == ModelConfig(name=name, **SERVED[name])
    assert dataclasses.asdict(mc) == {"name": name, **SERVED[name]}
    tiny = serve.model_config(config(name), rehearse=True)
    assert tiny == ModelConfig(name=name, **{**SERVED[name], **REHEARSED})


def test_no_architecture_key_of_a_file_is_dropped():
    """Every key of the three files is the harness's own, reaches a field,
    or is one of the three the program implements at one value."""
    import dataclasses

    from ollamamq_tpu.config import ModelConfig

    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    for name in SERVED:
        for key in serve.architecture(config(name)):
            assert serve.RENAMES.get(key, key) in fields \
                or key in serve.ONLY_VALUE, (name, key)
    assert {"hidden_act", "rope_scaling", "clip_qkv", "norm_topk_prob"} \
        <= set(serve.architecture(config(SPARSE)))
    assert not {"reference", "server_flags", "num_pages_reason", "rehearse",
                "model_type"} & set(serve.architecture(
                    dict(config(SPARSE), rehearse={})))


@pytest.mark.parametrize("edit,field,value", [
    ({"norm_topk_prob": True}, "norm_topk_prob", True),
    ({"norm_eps": 3e-6, "rms_norm_eps": None}, "rms_norm_eps", 3e-6),
    ({"num_local_experts": 8, "num_experts": None}, "num_experts", 8),
    ({"n_routed_experts": 16, "num_experts": None}, "num_experts", 16),
    ({"is_encoder": True}, "is_encoder", True),
])
def test_a_key_reaches_the_field_of_its_name_or_of_its_published_alias(
        edit, field, value):
    cfg = {k: v for k, v in {**config(SPARSE), **edit}.items()
           if not (k in edit and v is None)}
    assert getattr(serve.model_config(cfg, rehearse=False), field) == value


# ------------------------------------- what the program cannot express stops
REFUSALS = {
    "layer_types": (["full_attention"] * 10, "has no field for it"),
    "hidden_act": ("gelu", 'implements only "silu"'),
    "qk_norm": ("banana", "refuses it"),
    "rope_scaling": ({"rope_type": "yarn", "factor": 4.0},
                     "implements only null"),
    "num_local_experts": (64, "'num_experts' has already given the field"),
}


@pytest.mark.parametrize("key", sorted(REFUSALS))
def test_model_config_refuses_with_key_and_value(key):
    value, why = REFUSALS[key]
    with pytest.raises(serve.Refused) as e:
        serve.model_config({**config(SPARSE), key: value}, rehearse=False)
    assert f"key {key!r} = {json.dumps(value)}" in str(e.value)
    assert why in str(e.value)


def test_a_file_that_lacks_a_size_the_program_needs_is_refused_too():
    cfg = {k: v for k, v in config(DENSE_7B).items() if k != "head_dim"}
    with pytest.raises(serve.Refused, match="head_dim"):
        serve.model_config(cfg, rehearse=False)


def checkout_with(tmp_path, edit: dict) -> tuple:
    """A checkout that differs from this one by some keys of the sparse
    configuration file (everything else a link)."""
    for name in ("benchmarks", "ollamamq_tpu", "cpp"):
        os.symlink(os.path.join(ROOT, name), tmp_path / name)
    bad = tmp_path / "edited.json"
    bad.write_text(json.dumps({**config(SPARSE), **edit}))
    bj = spec.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    for c in bj["configs"]:
        if c["name"] == SPARSE:
            c["file"] = "edited.json"
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bj))
    cell = next(w["name"] for w in bj["workloads"] if w["config"] == SPARSE)
    return str(bad), cell


@pytest.mark.parametrize("key", ["layer_types", "hidden_act", "qk_norm"])
def test_a_run_on_such_a_file_ends_at_start_with_no_result_line(
        tmp_path, key):
    """serve.py is gone in under 10 s, before the CLI starts, with one line
    naming file, key and value; run.py sees the dead child (not a /health
    timeout), exits 1 and prints no result line."""
    value = REFUSALS[key][0]
    # (a block that covers the list, so that the rehearsal itself is sound
    # and what stops the run is the key the program lacks)
    bad, cell = checkout_with(tmp_path, {key: value, "rehearse": {
        key: value[:2]} if isinstance(value, list) else {}})
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    t0 = time.monotonic()
    r = subprocess.run(
        [sys.executable, os.path.join(BENCH, "serve.py"), "--config", bad,
         "--out", str(tmp_path), "--port", "1"],
        cwd=ROOT, capture_output=True, text=True, timeout=60, env=env)
    assert time.monotonic() - t0 < 10.0
    assert r.returncode == 2, r.stderr[-2000:]
    said = f"{bad}: key {key!r} = {json.dumps(value)}"
    lines = r.stdout.strip().splitlines()
    assert len(lines) == 1 and lines[0].startswith(said), r.stdout
    assert "ollamamq_tpu.cli" not in r.stdout + r.stderr   # never started

    t0 = time.monotonic()
    r = subprocess.run(
        [sys.executable, str(tmp_path / "benchmarks" / "run.py"),
         "--workload", cell, "--seed", "2147483999", "--seconds", "4",
         "--trace", "0", "--rehearse-cpu"], cwd=tmp_path,
        capture_output=True, text=True, timeout=120, env=env)
    assert time.monotonic() - t0 < 40.0       # HEALTH_TIMEOUT_S is 900
    assert r.returncode == 1, r.stderr[-2000:]
    assert '"correct"' not in r.stdout
    assert "the server exited with code 2 before /health" in r.stderr
    assert f"{bad}: key {key!r} = " in r.stderr


# ------------------------------------------------------------- rehearsals
def test_a_rehearse_block_is_laid_over_the_harnesss_sizes():
    cfg = {**config(SPARSE), "moe_intermediate_size": 1792,
           "rehearse": {"moe_intermediate_size": 64, "hidden_size": 64}}
    tiny = serve.as_run(cfg, rehearse=True)
    assert tiny["moe_intermediate_size"] == 64 and tiny["hidden_size"] == 64
    assert tiny["num_hidden_layers"] == 2 and tiny["vocab_size"] == 512
    assert tiny["num_experts"] == 64            # a scalar passes as it is
    assert serve.as_run(cfg, rehearse=False) is cfg
    # the three files carry no block and rehearse as they did
    for name in SERVED:
        assert "rehearse" not in config(name)
        tiny = serve.as_run(config(name), rehearse=True)
        assert {k: tiny[k] for k in serve.REHEARSE_SIZES} \
            == serve.REHEARSE_SIZES
        assert {k: v for k, v in tiny.items()
                if k not in serve.REHEARSE_SIZES} \
            == {k: v for k, v in config(name).items()
                if k not in serve.REHEARSE_SIZES}


def test_a_list_valued_key_the_block_does_not_cover_refuses_the_rehearsal():
    kinds = HYBRID["layer_types"]
    cfg = {**config(SPARSE), "layer_types": kinds}
    with pytest.raises(serve.Refused, match="key 'layer_types' = .*rehearse"):
        serve.as_run(cfg, rehearse=True)
    assert serve.as_run(cfg, rehearse=False)["layer_types"] == kinds
    covered = {**cfg, "rehearse": {"layer_types": kinds[1:3]}}
    assert serve.as_run(covered, rehearse=True)["layer_types"] == kinds[1:3]


def test_run_py_refuses_such_a_rehearsal_before_it_starts_a_server(tmp_path):
    _, cell = checkout_with(tmp_path, {"layer_types": HYBRID["layer_types"]})
    r = subprocess.run(
        [sys.executable, str(tmp_path / "benchmarks" / "run.py"),
         "--workload", cell, "--seed", "5", "--seconds", "4", "--trace", "0",
         "--rehearse-cpu"], cwd=tmp_path, capture_output=True, text=True,
        timeout=120, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode == 1 and '"correct"' not in r.stdout
    assert "edited.json: key 'layer_types' = " in r.stderr
    assert "`rehearse` block" in r.stderr


# ------------------------------------------------- the readers' layer counts
@pytest.mark.parametrize("cfg,counts", [
    (DENSE_7B, (14, 0, 18944)), (DENSE_8B, (36, 0, 12288)),
    (SPARSE, (10, 10, 1024)), (HYBRID, (3, 12, 1792))])
def test_arch_counts_attention_layers_expert_layers_and_expert_width(
        cfg, counts):
    cfg = config(cfg) if isinstance(cfg, str) else cfg
    assert (arch.attention_layers(cfg), arch.expert_layers(cfg),
            arch.expert_width(cfg)) == counts


def reader(name: str):
    cell = types.SimpleNamespace(bench_dir=BENCH)
    return spec.load_reader(cell, spec.Metric(name, "", "per_layer", {}))


SAMPLE = {"mode": "decode", "k_cap": 8, "tokens": 512,
          "moe_assignments": 40960, "moe_pairs_hit": 8 * 300,
          "moe_load_max": 19, "moe_load_mean": 8.0}


@pytest.mark.parametrize("cfg,layers", [
    (DENSE_7B, 14), (DENSE_8B, 36), (SPARSE, 10), (HYBRID, 3)])
def test_device_ms_per_step_divides_launches_by_the_attention_layers(
        cfg, layers):
    """Over one and the same trace the reader returns what it returned when
    it divided by `num_hidden_layers`: the same integer for the three files."""
    cfg = config(cfg) if isinstance(cfg, str) else cfg
    trace = {"busy_s": 3.0, "op_self_s": {}, "op_count": {
        "ragged_paged_attention_pallas.5 bf16[512,7,512]": 5 * layers,
        "paged_decode_attention_pallas.8 bf16[64,7,512]": 7 * layers,
        "fusion.157 bf16[64,18944]": 1000}}
    ctx = types.SimpleNamespace(trace=trace, trace_steps=[SAMPLE],
                                cell=types.SimpleNamespace(config=cfg))
    for half in ("lat", "thr"):
        assert reader("device_ms_per_step." + half).read(ctx) \
            == pytest.approx(1e3 * 3.0 / 12)
    # a stack with no attention layer falls back to the samples' passes
    none = dict(cfg, layer_types=["conv"] * cfg["num_hidden_layers"])
    ctx.cell.config = none
    assert reader("device_ms_per_step.thr").read(ctx) \
        == pytest.approx(1e3 * 3.0 / 8)


@pytest.mark.parametrize("cfg,layers,width", [
    (SPARSE, 10, 1024), (HYBRID, 12, 1792)])
def test_the_expert_readers_take_layers_and_width_from_arch(
        cfg, layers, width):
    from benchmarks.layer_metrics import _moe
    from benchmarks.lib import peaks

    cfg = config(cfg) if isinstance(cfg, str) else cfg
    d, e = cfg["hidden_size"], cfg["num_experts"]
    assert _moe.pair_bytes(cfg) == 3 * d * width * 2
    assert _moe.assignment_bytes(cfg) == (2 * (d + width) + width + d) * 2
    assert _moe.assignment_flops(cfg) == 6 * d * width
    ctx = types.SimpleNamespace(
        steps=[SAMPLE], trace_steps=[SAMPLE], say=lambda *a, **k: None,
        peaks=peaks.peaks_of("TPU v5 lite"),
        cell=types.SimpleNamespace(config=cfg),
        trace={"busy_s": 0.2, "op_self_s": {"gmm.3 bf16[512,1024]": 0.09},
               "op_count": {"gmm.3 bf16[512,1024]": 3 * layers * 4}})
    assert reader("moe_experts_hit_pct.thr").read(ctx) \
        == pytest.approx(100 * 300 / (layers * e))
    # 4 passes in the trace, each hitting 300 pairs with 5120 assignments
    least, _ = _moe.least_seconds(cfg, 4 * 300, 4 * 5120, ctx.peaks)
    assert reader("moe_expert_mm_roofline_pct").read(ctx) \
        == pytest.approx(100 * least / 0.09)


def test_no_reader_reads_a_layer_count_or_a_width_but_through_arch():
    for folder in ("layer_metrics", "end_to_end"):
        for name in os.listdir(os.path.join(BENCH, folder)):
            if name.endswith(".py"):
                with open(os.path.join(BENCH, folder, name)) as f:
                    text = f.read()
                for key in ("num_hidden_layers", "intermediate_size",
                            "num_experts\"", "layer_types"):
                    assert key not in text, (name, key)
