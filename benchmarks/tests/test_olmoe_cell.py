"""The sparse configuration, its reference and its cell, on the CPU:
    python -m pytest benchmarks/tests/test_olmoe_cell.py -q

That they load as files and entries; that the configuration file holds the
catalog's numbers; that the reference's tolerance passes the program's own
forward and rejects five wrong ones (tiny size, float32); what the `moe_*`
readers compute, on made-up samples and traces; and the whole control flow
of the cell at a tiny size. Nothing here gives a device number."""

from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
import types

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from benchmarks.lib import peaks, result, spec  # noqa: E402

CELL = "olmoe-1b-7b-d10.batch"
NEW_METRICS = ("moe_expert_mm_share_pct.thr", "moe_expert_mm_roofline_pct",
               "moe_experts_hit_pct.thr", "moe_load_max_over_mean.thr")
# `config` of OLMoE-1B-7B-0125-Instruct in the model-configs guide's catalog
CATALOG = {"attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
           "hidden_size": 2048, "intermediate_size": 1024,
           "max_position_embeddings": 4096, "model_type": "olmoe",
           "norm_topk_prob": False, "num_attention_heads": 16,
           "num_experts": 64, "num_experts_per_tok": 8,
           "num_hidden_layers": 16, "num_key_value_heads": 16,
           "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
           "tie_word_embeddings": False, "vocab_size": 50304}


# ------------------------------------------------------- files and entries
def test_the_cell_its_configuration_and_its_reference_load():
    cell = spec.load_cell(CELL)
    cfg = cell.config
    assert cell.chips == 1 and cfg["chips"] == 1
    assert cell.traffic["kind"] == "closed" and cell.traffic["clients"] == 96
    changed = {k for k, v in CATALOG.items() if cfg[k] != v}
    assert changed == set(cfg["reduced"]) == {"num_hidden_layers"}
    assert cfg["reduced_from"] == {"num_hidden_layers": 16}
    assert cfg["qk_norm"] == "full" and cfg["norm_topk_prob"] is False
    assert {"head_dim", "qk_norm"} <= set(cfg["assumed"])
    flags = cfg["server_flags"]
    assert int(flags[flags.index("--num-pages") + 1]) >= 1536
    per_layer = {m.name for m in cell.metrics_of("per_layer")}
    assert set(NEW_METRICS) <= per_layer
    assert {"tokens_per_step.thr", "host_ms_per_step.thr",
            "device_ms_per_step.thr", "attn_kernel_share_pct.thr",
            "device_idle_pct.thr"} <= per_layer
    assert {m.name for m in cell.metrics_of("end_to_end")} == \
        {"output_tok_s", "setup_s"}
    for m in cell.metrics:
        assert callable(spec.load_reader(cell, m).read)
    ref = os.path.join(BENCH, "reference", cfg["reference"] + ".py")
    assert cfg["reference"] == "olmoe_decoder" and os.path.exists(ref)
    # the new metrics are this cell's alone: no other cell has to report them
    bj = spec.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    for m in bj["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL] and m["moves"] == "output_tok_s"


def test_the_program_runs_the_configuration_files_model():
    """serve.py hands every architecture key of the file to ModelConfig: the
    value of `qk_norm` selects the whole-vector norm, and `norm_topk_prob`
    reaches the field of its own name (since PR 31; before, it was the
    program's default that happened to be the file's value)."""
    from benchmarks import serve

    cfg = spec.load_cell(CELL).config
    mc = serve.model_config(cfg, rehearse=False)
    assert mc.qk_norm_kind == "full" and mc.norm_topk_prob is False
    assert (mc.num_experts, mc.num_experts_per_tok) == (64, 8)
    assert (mc.num_heads, mc.num_kv_heads, mc.head_dim) == (16, 16, 128)
    assert mc.norm_topk_prob == cfg["norm_topk_prob"]
    assert 2 * mc.param_count() == 8_803_487_744  # bf16 bytes, as the file says


# ------------------------------------------------------------ the reference
def _tiny():
    import jax
    import jax.numpy as jnp

    from ollamamq_tpu.config import ModelConfig
    from ollamamq_tpu.models import llama

    mc = ModelConfig(name="t", vocab_size=600, hidden_size=128,
                     intermediate_size=64, num_layers=3, num_heads=8,
                     num_kv_heads=8, head_dim=16, max_seq_len=512,
                     qk_norm="full", rope_theta=1e4, rms_norm_eps=1e-5,
                     num_experts=16, num_experts_per_tok=4)
    params = llama.init_params(mc, jax.random.PRNGKey(0), dtype=jnp.float32)
    key = jax.random.PRNGKey(1)
    for name, a in list(params["layers"].items()):
        if name.endswith("norm"):
            key, k = jax.random.split(key)  # init gives ones
            params["layers"][name] = a + 0.3 * jax.random.normal(k, a.shape)
    cfg = {"num_attention_heads": 8, "num_key_value_heads": 8, "head_dim": 16,
           "rms_norm_eps": 1e-5, "rope_theta": 1e4, "qk_norm": "full",
           "num_experts": 16, "num_experts_per_tok": 4,
           "norm_topk_prob": False, "tie_word_embeddings": False}
    return mc, params, cfg


PROMPTS = ("hello chip, route tokens", "sixteen experts, four a token",
           "no row is dropped here")


def _greedy(mc, params, prompt: str, n: int) -> list:
    """n greedy ids (no penalty) from the PROGRAM's own padded prefill."""
    import jax.numpy as jnp
    import numpy as np

    from ollamamq_tpu.models import llama

    seq = [1] + [b + 3 for b in prompt.encode()]
    cache = jnp.zeros((mc.num_layers, 64, mc.kv_dim))
    table = jnp.arange(8, dtype=jnp.int32)[None, :]
    out = []
    for _ in range(n):
        toks = np.zeros((1, 64), np.int32)
        toks[0, :len(seq)] = seq
        logits, _, _ = llama.forward_prefill(
            params, mc, jnp.asarray(toks), jnp.asarray([len(seq)]), cache,
            cache, table, 8)
        out.append(int(jnp.argmax(logits[0])))
        seq.append(out[-1])
    return out


def _capacity_moe(factor):
    """The trade the program made before PR 27: an expert takes the first C
    rows routed to it, C = ceil(even share x factor), and drops the rest."""
    import math

    import jax
    import jax.numpy as jnp

    def moe_mlp(cfg, lp, h, valid=None, mesh=None, impl="jnp", layer=None):
        b, t, d = h.shape
        x = h.reshape(-1, d)
        n, e, k = x.shape[0], cfg.num_experts, cfg.num_experts_per_tok
        top, idx = jax.lax.top_k(jax.nn.softmax(x @ lp["w_router"]), k)
        w = jnp.zeros((n, e)).at[jnp.arange(n)[:, None], idx].set(top)
        if valid is not None:
            w = w * valid.reshape(n, 1)
        queue = jnp.cumsum(w > 0, axis=0) - (w > 0)
        w = jnp.where(queue < math.ceil(n * k / e * factor), w, 0.0)
        wg, wu, wd = (lp[name] if layer is None else lp[name][layer]
                      for name in ("we_gate", "we_up", "we_down"))
        y = jnp.einsum("enf,efd->end", jax.nn.silu(
            jnp.einsum("nd,edf->enf", x, wg)) * jnp.einsum("nd,edf->enf", x,
                                                           wu), wd)
        return jnp.einsum("end,ne->nd", y, w).reshape(b, t, d), \
            jnp.sum(w > 0, axis=0, dtype=jnp.int32)

    return moe_mlp


def _check(cfg, params, served: dict) -> dict:
    from benchmarks.reference import olmoe_decoder as ref

    return ref.check(cfg, params, [
        {"prompt": p, "ids": ids,
         "options": {"temperature": 0, "repeat_penalty": 1.0}}
        for p, ids in served.items()], 64, 16)


def test_the_tolerance_passes_the_program_and_rejects_five_wrong_forwards(
        monkeypatch):
    from ollamamq_tpu.models import llama

    mc, params, cfg = _tiny()

    def served(mc, params):
        return {p: _greedy(mc, params, p, 12) for p in PROMPTS}

    good = _check(cfg, params, served(mc, params))
    assert good["agrees"] and good["argmax_share"] == 1.0
    assert good["positions"] == 36 and good["mean_margin_sd"] < 1e-4
    # the first wrong forward: one precision lower (every matmul in float8)
    assert good["lower_precision"]["mean_margin_sd"] > good["mean_margin_sd_max"]

    hd = mc.head_dim
    per_head = dict(params, layers=dict(
        params["layers"], q_norm=params["layers"]["q_norm"][:, :hd],
        k_norm=params["layers"]["k_norm"][:, :hd]))
    wrong = {
        "the lowest-weight expert of the k left out": served(
            dataclasses.replace(mc, num_experts_per_tok=3), params),
        "the top-k weights renormalised": served(
            dataclasses.replace(mc, norm_topk_prob=True), params),
        "per-head in place of whole-vector q/k norm": served(
            dataclasses.replace(mc, qk_norm="head"), per_head),
    }
    monkeypatch.setattr(llama, "moe_mlp", _capacity_moe(1.0))
    wrong["a capacity that drops"] = served(mc, params)
    monkeypatch.undo()
    readings = {}
    for what, ids in wrong.items():
        bad = _check(cfg, params, ids)
        readings[what] = bad["mean_margin_sd"]
        assert not bad["agrees"], (what, bad["mean_margin_sd"])
        assert bad["mean_margin_sd"] > 2 * bad["mean_margin_sd_max"], readings
    # a capacity nothing overflows is the right forward again
    monkeypatch.setattr(llama, "moe_mlp", _capacity_moe(16.0))
    assert _check(cfg, params, served(mc, params))["agrees"]


def test_a_program_without_the_architecture_ends_the_run_not_a_comparison(
        monkeypatch):
    """Weights of another layout (the program before PR 27 serves a per-head
    norm under `qk_norm: "full"`) are no wrong answer: `check` asks the server
    to stop and answers nothing, so the run ends with an error exit."""
    import signal

    from benchmarks.reference import olmoe_decoder as ref

    mc, params, cfg = _tiny()
    ref.served_layout(cfg, params)
    hd = mc.head_dim
    per_head = dict(params, layers=dict(
        params["layers"], q_norm=params["layers"]["q_norm"][:, :hd],
        k_norm=params["layers"]["k_norm"][:, :hd]))
    with pytest.raises(ref.NotServed, match=r"q_norm is \(3, 16\), the "
                       r"configuration's is \(3, 128\)"):
        ref.served_layout(cfg, per_head)
    no_experts = dict(params, layers={
        k: v for k, v in params["layers"].items() if k != "we_up"})
    with pytest.raises(ref.NotServed, match="we_up is absent"):
        ref.served_layout(cfg, no_experts)
    sent = []
    monkeypatch.setattr(ref.os, "kill", lambda pid, sig: sent.append(
        (pid, sig)))
    with pytest.raises(SystemExit):
        _check_with(ref, cfg, per_head)
    assert sent == [(os.getpid(), signal.SIGTERM)]


def _check_with(ref, cfg, params):
    return ref.check(cfg, params, [{"prompt": "x", "ids": [5], "options": {
        "temperature": 0}}], 64, 16)


# -------------------------------------------------------------- the readers
def _reader(name: str):
    cell = types.SimpleNamespace(bench_dir=BENCH)
    return spec.load_reader(cell, spec.Metric(name, "", "per_layer", {}))


CFG = {"num_hidden_layers": 10, "num_experts": 64, "hidden_size": 2048,
       "intermediate_size": 1024}
SCAN = {"mode": "decode", "k_cap": 8, "tokens": 512,
        "moe_assignments": 8 * 64 * 8 * 10, "moe_pairs_hit": 8 * 638,
        "moe_load_max": 19, "moe_load_mean": 8.0}
RAGGED = {"mode": "ragged", "k_cap": 0, "tokens": 300,
          "moe_assignments": 300 * 8 * 10, "moe_pairs_hit": 640,
          "moe_load_max": 60, "moe_load_mean": 37.5}


def _ctx(**kw):
    base = dict(steps=None, trace_steps=None, trace=None, peaks=None,
                cell=types.SimpleNamespace(config=CFG),
                say=lambda *a, **k: None)
    return types.SimpleNamespace(**{**base, **kw})


def test_counter_metrics_are_means_over_forward_passes():
    hit = _reader("moe_experts_hit_pct.thr").read(_ctx(steps=[SCAN, RAGGED]))
    assert hit == pytest.approx(100 * (8 * 638 + 640) / (640 * 9))
    skew = _reader("moe_load_max_over_mean.thr").read(
        _ctx(steps=[SCAN, RAGGED]))
    assert skew == pytest.approx((8 * 19 / 8.0 + 60 / 37.5) / 9)
    # a program without the counters (the parent) gives nothing to read
    old = {k: v for k, v in SCAN.items() if not k.startswith("moe_")}
    for name in NEW_METRICS:
        assert _reader(name).read(_ctx(
            steps=[old], trace_steps=[old],
            trace={"busy_s": 1.0, "op_self_s": {}, "op_count": {}})) is None


def test_roofline_counts_only_the_experts_that_were_hit():
    from benchmarks.layer_metrics import _moe

    v5e = peaks.peaks_of("TPU v5 lite")
    assert _moe.pair_bytes(CFG) == 12_582_912
    # 90 launches = 3 matmuls x 10 layers x 3 passes; the sampled passes hit
    # 638 pairs each with 5120 assignments
    trace = {"busy_s": 0.2, "op_self_s": {
        "gmm.3 bf16[512,1024]": 0.06, "gmm.5 bf16[512,2048]": 0.03,
        "fusion.9 bf16[64,2048]": 0.05,
        "ragged_paged_attention_pallas.5 bf16[64,1,2048]": 0.04},
        "op_count": {"gmm.3 bf16[512,1024]": 60, "gmm.5 bf16[512,2048]": 30,
                     "fusion.9 bf16[64,2048]": 30}}
    ctx = _ctx(trace=trace, trace_steps=[SCAN], peaks=v5e)
    assert _reader("moe_expert_mm_share_pct.thr").read(ctx) == \
        pytest.approx(45.0)
    least = 3 * (638 * 12_582_912 + 5120 * 18_432) / 819e9
    assert _reader("moe_expert_mm_roofline_pct").read(ctx) == \
        pytest.approx(100 * least / 0.09)
    # half the experts hit: half the weight bytes, not all of them
    half = dict(SCAN, moe_pairs_hit=8 * 320)
    assert _reader("moe_expert_mm_roofline_pct").read(
        _ctx(trace=trace, trace_steps=[half], peaks=v5e)) < \
        0.52 * 100 * least / 0.09
    # XLA's own grouped matmul is found by name too; other ops are not
    assert _moe.EXPERT_MM.search("ragged-dot-none.4 bf16[512,1024]")
    from benchmarks.layer_metrics import _ops
    for name in trace["op_self_s"]:
        assert not (_moe.EXPERT_MM.search(name)
                    and _ops.ATTENTION.search(name))
    # no op of that name in the trace (a rehearsal on the CPU): 0, not None
    none = {"busy_s": 0.2, "op_self_s": {"dot.5": 0.1}, "op_count": {}}
    assert _reader("moe_expert_mm_roofline_pct").read(
        _ctx(trace=none, trace_steps=[SCAN])) == 0.0
    # prefill-heavy passes can be bound by FLOPs: the larger floor is taken
    assert _moe.least_seconds(CFG, 640, 640 * 4000, v5e)[1] == "flops"
    assert _moe.least_seconds(CFG, 640, 5120, v5e)[1] == "hbm"


# ------------------------------------------------------------- end to end
def test_rehearsal_of_the_cell_reads_every_new_metric():
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
    assert set(NEW_METRICS) <= set(line["metrics"])
    assert 0 < line["metrics"]["moe_experts_hit_pct.thr"]["value"] <= 100
    assert line["metrics"]["moe_load_max_over_mean.thr"]["value"] >= 1
    assert line["attempted"] > 0 and line["failed"] == 0
    notes = {n["note"]: n for n in map(json.loads, r.stdout.splitlines()[:-1])}
    assert notes["reference"]["agrees"] is True, notes["reference"]
    assert notes["reference"]["positions"] > 0
