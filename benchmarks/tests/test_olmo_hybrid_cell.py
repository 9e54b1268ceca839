"""The linear-attention hybrid configuration, its reference and its cell, on
the CPU:
    python -m pytest benchmarks/tests/test_olmo_hybrid_cell.py -q

That they load as files and entries; that the configuration file holds the
catalog's numbers and reaches the program's ModelConfig key by key; that the
reference's tolerance passes the program's own forward and refuses eight wrong
ones (tiny size, float32); the roofline reader's arithmetic on a synthetic
capture; and the whole control flow of the cell at a tiny size. Nothing here
gives a device number."""

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

CELL = "olmo-hybrid-7b-d16.batch"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PERIOD = ["linear_attention"] * 3 + ["full_attention"]
# `config` of Olmo-Hybrid-7B in the model-configs guide's catalog, as of
# PR 35 (held here too, so that the test runs where the guide is not
# installed)
PUBLISHED = {
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
    "intermediate_size": 11008, "num_hidden_layers": 32,
    "num_attention_heads": 30, "num_key_value_heads": 30,
    "hidden_act": "silu", "max_position_embeddings": 65536,
    "attention_bias": False, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False, "layer_types": PERIOD * 8,
    "linear_num_key_heads": 30, "linear_num_value_heads": 30,
    "linear_key_head_dim": 96, "linear_value_head_dim": 192,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None}}
LIN_METRICS = ("lin_kernel_share_pct.thr", "lin_step_roofline_pct")
THR_METRICS = ("tokens_per_step.thr", "host_ms_per_step.thr",
               "device_ms_per_step.thr", "attn_kernel_share_pct.thr",
               "device_idle_pct.thr", "loop_ms_per_step.thr",
               "idle_explained_pct.thr")


# ------------------------------------------------------- files and entries
def test_the_cell_its_configuration_and_its_reference_load():
    cell = spec.load_cell(CELL)
    cfg = cell.config
    assert cell.chips == 1 and cfg["chips"] == 1
    assert cell.traffic["kind"] == "closed" and cell.traffic["clients"] == 96
    published = dict(PUBLISHED)
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Olmo-Hybrid-7B")
        assert row["config"] == published
        assert cfg["source"] == row["source_url"]
    changed = {k for k, v in published.items() if cfg[k] != v}
    assert changed == set(cfg["reduced"]) == {"num_hidden_layers",
                                              "layer_types"}
    assert cfg["num_hidden_layers"] == 16
    assert cfg["layer_types"] == PERIOD * 4  # four whole periods
    assert cfg["reduced_from"] == {k: published[k] for k in changed}
    assert {"head_dim", "norm_order", "qk_norm", "rope_parameters",
            "linear_attention", "init"} <= set(cfg["assumed"])
    assert "float32" in cfg["dtype"]  # the rule's state
    assert arch.attention_layers(cfg) == 4 and arch.expert_layers(cfg) == 0
    flags = cfg["server_flags"]
    assert int(flags[flags.index("--num-pages") + 1]) >= 1024
    per_layer = {m.name for m in cell.metrics_of("per_layer")}
    assert per_layer == set(LIN_METRICS) | set(THR_METRICS)
    assert {m.name for m in cell.metrics_of("end_to_end")} == \
        {"output_tok_s", "setup_s"}
    for m in cell.metrics:
        assert callable(spec.load_reader(cell, m).read)
    ref = os.path.join(BENCH, "reference", cfg["reference"] + ".py")
    assert cfg["reference"] == "olmo_hybrid_decoder" and os.path.exists(ref)
    bj = spec.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = next(c for c in bj["configs"] if c["name"] == cfg["name"])
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]
    assert len(bj["workloads"]) == 6
    assert sum(w["chips"] == 4 for w in bj["workloads"]) == 1


def test_the_program_runs_the_configuration_files_model():
    """serve.py hands every architecture key of the file to ModelConfig; the
    stack the program then scans is the file's, and its bytes the file's."""
    from benchmarks import serve
    from ollamamq_tpu.config import ATTENTION, LINEAR

    cfg = spec.load_cell(CELL).config
    mc = serve.model_config(cfg, rehearse=False)
    assert mc.layer_types == tuple(cfg["layer_types"])
    assert (mc.count(LINEAR), mc.count(ATTENTION)) == (12, 4)
    assert [(f, len(p), n) for f, p, n in mc.layer_plan()] == [(0, 4, 4)]
    assert (mc.num_heads, mc.num_kv_heads, mc.head_dim, mc.qk_norm_kind) \
        == (30, 30, 128, "full")
    assert (mc.linear_num_key_heads, mc.linear_num_value_heads,
            mc.linear_key_head_dim, mc.linear_value_head_dim,
            mc.linear_conv_kernel_dim, mc.linear_allow_neg_eigval) \
        == (30, 30, 96, 192, 4, True)
    assert mc.rope_theta is None and mc.norm_order == "post"
    assert (mc.hidden_size, mc.intermediate_size, mc.vocab_size) \
        == (3840, 11008, 100352)
    assert not mc.tie_embeddings and mc.rms_norm_eps == 1e-6
    assert mc.max_seq_len == 65536 and mc.num_experts == 0
    assert mc.param_count() == 4_100_788_944  # as the file's arithmetic
    assert "4,100,788,944" in cfg["arithmetic"]
    # the rehearsal's tiny stack keeps the period and the rule's head shapes
    tiny = serve.model_config(cfg, rehearse=True)
    assert tiny.num_layers == 6 and tiny.count(LINEAR) == 4
    assert tiny.state_window == (4, 2 * 32 + 64)
    # a file the program cannot run still ends serve.py at start
    with pytest.raises(serve.Refused, match="layer_types"):
        serve.model_config(dict(cfg, layer_types=cfg["layer_types"][:5]),
                           rehearse=False)
    with pytest.raises(serve.Refused, match="linear_num_value_heads"):
        serve.model_config(dict(cfg, linear_num_value_heads=60),
                           rehearse=False)
    with pytest.raises(serve.Refused, match="rope_parameters"):
        serve.model_config(dict(cfg, rope_parameters={"rope_type": "yarn"}),
                           rehearse=False)


# ------------------------------------------------------------ the reference
KINDS = ("linear_attention", "linear_attention", "full_attention",
         "linear_attention", "full_attention", "linear_attention")


def _tiny():
    import jax
    import jax.numpy as jnp

    from ollamamq_tpu.config import ModelConfig
    from ollamamq_tpu.models import llama

    mc = ModelConfig(name="t", vocab_size=600, hidden_size=128,
                     intermediate_size=192, num_layers=len(KINDS),
                     num_heads=8, num_kv_heads=8, head_dim=16,
                     max_seq_len=512, qk_norm="full", rope_theta=None,
                     rms_norm_eps=1e-6, norm_order="post",
                     linear_num_key_heads=4, linear_num_value_heads=4,
                     linear_key_head_dim=8, linear_value_head_dim=16,
                     linear_conv_kernel_dim=4, linear_allow_neg_eigval=True,
                     layer_types=KINDS)
    params = llama.init_params(mc, jax.random.PRNGKey(0), dtype=jnp.float32)
    key = jax.random.PRNGKey(1)
    for name, a in list(params["layers"].items()):
        if name.endswith("norm"):
            key, k = jax.random.split(key)  # init gives ones
            params["layers"][name] = a + 0.3 * jax.random.normal(k, a.shape)
    cfg = {"hidden_size": 128, "intermediate_size": 192,
           "num_attention_heads": 8, "num_key_value_heads": 8,
           "head_dim": 16, "rms_norm_eps": 1e-6, "qk_norm": "full",
           "norm_order": "post", "rope_parameters": {"rope_theta": None},
           "layer_types": list(KINDS), "linear_num_key_heads": 4,
           "linear_num_value_heads": 4, "linear_key_head_dim": 8,
           "linear_value_head_dim": 16, "linear_conv_kernel_dim": 4,
           "linear_allow_neg_eigval": True, "tie_word_embeddings": False}
    return mc, params, cfg


PROMPTS = ("hello chip, keep a matrix a head", "a state of one matrix a slot",
           "the decay forgets, the rule corrects")


@functools.lru_cache(maxsize=None)
def _chunk_fn(mc):
    """One span of one sequence through the served ragged forward — row 0,
    slot 0, a 64-token stream — jitted once a model."""
    import jax
    import jax.numpy as jnp

    from ollamamq_tpu.models import llama

    def chunk(params, table, toks, start, n_tok, first, kc, vc, state):
        pos = jnp.where(jnp.arange(64) < n_tok, start + jnp.arange(64), -1)
        return llama.forward_ragged(
            params, mc, toks, jnp.zeros(64, jnp.int32), pos,
            jnp.where(pos >= 0, pos + 8, 0), (n_tok - 1)[None], kc, vc, table,
            jnp.zeros(1, jnp.int32), n_tok[None], (start + n_tok)[None], 8,
            conv_state=state, slot_ids=jnp.zeros(1, jnp.int32),
            is_first=first[None])

    return jax.jit(chunk)


def _greedy(mc, params, prompt: str, n: int, cut=None):
    """n greedy ids (no penalty) from the PROGRAM's served forward: the
    prompt as one span (or two, cut at `cut`), then one-token rows on the
    carried pages and per-slot state, as a decode row of a ragged step."""
    import jax.numpy as jnp
    import numpy as np

    from ollamamq_tpu.config import ATTENTION
    from ollamamq_tpu.models import llama

    seq = [1] + [b + 3 for b in prompt.encode()]
    cache = jnp.zeros((mc.count(ATTENTION), 17 * 8, mc.kv_dim))
    # page 0 is the trash page (padding tokens write its slot 0)
    table = jnp.arange(1, 17, dtype=jnp.int32)[None, :]
    chunk = _chunk_fn(mc)
    st = (cache, cache, llama.alloc_slot_state(mc, 1, jnp.float32))
    spans = [(0, len(seq))] if cut is None else [(0, cut), (cut, len(seq))]
    out = []
    while len(out) < n:
        for start, stop in spans:
            span = np.zeros(64, np.int32)
            span[:stop - start] = seq[start:stop]
            logits, *st = chunk(params, table, jnp.asarray(span),
                                jnp.int32(start), jnp.int32(stop - start),
                                jnp.int32(start == 0), *st)
        out.append(int(jnp.argmax(logits[0])))
        seq.append(out[-1])
        spans = [(len(seq) - 1, len(seq))]
    return out


def _check(cfg, params, served: dict, pad_to=64, max_out=16) -> dict:
    from benchmarks.reference import olmo_hybrid_decoder as ref

    return ref.check(cfg, params, [
        {"prompt": p, "ids": ids,
         "options": {"temperature": 0, "repeat_penalty": 1.0}}
        for p, ids in served.items()], pad_to, max_out)


def test_the_tolerance_passes_the_program_and_refuses_eight_wrong_forwards(
        monkeypatch):
    import jax
    import jax.numpy as jnp

    from ollamamq_tpu.models import llama
    from ollamamq_tpu.ops import gated_delta, shortconv

    mc, params, cfg = _tiny()
    lay = params["layers"]

    def served(mc, params, n=12, **how):
        return {p: _greedy(mc, params, p, n, **how) for p in PROMPTS}

    def patched(target, name, value, n=12):
        """The served ids with `target.name` replaced while tracing."""
        monkeypatch.setattr(target, name, value)
        _chunk_fn.cache_clear()
        try:
            return served(mc, params, n)
        finally:
            monkeypatch.undo()
            _chunk_fn.cache_clear()

    good = _check(cfg, params, served(mc, params))
    assert good["agrees"] and good["argmax_share"] == 1.0
    assert good["positions"] == 36 and good["mean_margin_sd"] < 1e-4
    # the same model with the prompt in two spans over carried state
    chunked = _check(cfg, params, served(mc, params, cut=7))
    assert chunked["agrees"] and chunked["mean_margin_sd"] < 1e-4
    # the first wrong forward: one precision lower (every matmul in float8)
    assert good["lower_precision"]["mean_margin_sd"] \
        > 10 * good["mean_margin_sd_max"]

    plain_gates, plain_step, plain_from = (
        gated_delta.gates, gated_delta.step, gated_delta._from_heads)
    plain_conv, plain_silu = shortconv.short_conv, jax.nn.silu
    skip = []

    def conv_then_no_silu(w, taps, z):
        skip.append(True)  # the next SiLU is the one after this convolution
        return plain_conv(w, taps, z)

    def silu_unless_after_the_conv(x):
        return x if skip and skip.pop() else plain_silu(x)

    def bf16(x):
        return x.astype(jnp.bfloat16).astype(jnp.float32)

    def step_bf16_state(state, *a, **kw):
        o, s = plain_step(bf16(state), *a, **kw)
        return o, bf16(s)

    wrong = {
        "beta without the factor 2": served(dataclasses.replace(
            mc, linear_allow_neg_eigval=False), params),
        "RoPE applied": served(dataclasses.replace(
            mc, rope_theta=10_000.0), params),
        "pre-norm for post-norm": served(dataclasses.replace(
            mc, norm_order="pre"), params),
        "the taps reversed": served(mc, dict(params, layers=dict(
            lay, lin_conv_w=lay["lin_conv_w"][..., ::-1]))),
        "q/k not L2-normalised": patched(
            gated_delta, "normalise", lambda q, k: (
                q.astype(jnp.float32) * q.shape[-1] ** -0.5,
                k.astype(jnp.float32))),
        "the decay left out": patched(
            gated_delta, "gates", lambda *a: (
                0.0 * plain_gates(*a)[0], plain_gates(*a)[1])),
    }
    # (rounding the accumulator moves the logits little at this size: it
    # shows over a longer output, 64 tokens a request)
    monkeypatch.setattr(gated_delta, "step", step_bf16_state)
    bf16_state = patched(gated_delta, "_from_heads",
                         lambda s: bf16(plain_from(s)), n=64)
    monkeypatch.setattr(llama.jax.nn, "silu", silu_unless_after_the_conv)
    wrong["the SiLU after the convolution left out"] = patched(
        shortconv, "short_conv", conv_then_no_silu)
    assert jax.nn.silu is plain_silu and gated_delta.step is plain_step
    readings = {}
    for what, ids in wrong.items():
        bad = _check(cfg, params, ids)
        readings[what] = round(bad["mean_margin_sd"], 4)
        assert not bad["agrees"], (what, readings)
        assert bad["mean_margin_sd"] > 2 * bad["mean_margin_sd_max"], readings
    long_good = _check(cfg, params, served(mc, params, n=64), 128, 64)
    assert long_good["agrees"] and long_good["mean_margin_sd"] < 1e-4
    bad = _check(cfg, params, bf16_state, 128, 64)
    readings["the state in bf16"] = round(bad["mean_margin_sd"], 4)
    assert not bad["agrees"], readings
    print(readings)


def test_a_program_without_the_architecture_ends_the_run_not_a_comparison(
        monkeypatch):
    """Weights of another layout are no wrong answer: `check` asks the server
    to stop and answers nothing, so the run ends with an error exit."""
    import signal

    import jax.numpy as jnp

    from benchmarks.reference import olmo_hybrid_decoder as ref

    mc, params, cfg = _tiny()
    ref.served_layout(cfg, params)
    lay = params["layers"]
    narrow = dict(params, layers=dict(lay, lin_in=lay["lin_in"][..., :-64]))
    with pytest.raises(ref.NotServed, match=r"lin_in is \(4, 128, 128\), the "
                       r"configuration's is \(4, 128, 192\)"):
        ref.served_layout(cfg, narrow)
    no_rule = dict(params, layers={k: v for k, v in lay.items()
                                   if k != "lin_A_log"})
    with pytest.raises(ref.NotServed, match="lin_A_log is absent"):
        ref.served_layout(cfg, no_rule)
    with pytest.raises(ref.NotServed, match="norm_order 'post'"):
        ref.served_layout(dict(cfg, norm_order="pre"), params)
    with pytest.raises(ref.NotServed, match="rope_theta null"):
        ref.served_layout(dict(cfg, rope_parameters={"rope_theta": 1e4}),
                          params)
    del jnp
    sent = []
    monkeypatch.setattr(ref.os, "kill", lambda pid, sig: sent.append(
        (pid, sig)))
    with pytest.raises(SystemExit):
        ref.check(cfg, no_rule, [{"prompt": "x", "ids": [5], "options": {
            "temperature": 0}}], 64, 16)
    assert sent == [(os.getpid(), signal.SIGTERM)]


# ------------------------------------------------------------ the readers
def test_the_roofline_reader_counts_live_rows_only_on_a_synthetic_capture():
    """12 launches a pass; 3 passes in the trace; the samples of the capture
    say 40 live rows a pass. One row-launch moves its [30, 96, 192] float32
    state twice and the row's vectors once."""
    from benchmarks.layer_metrics import _lin
    from benchmarks.lib.peaks import peaks_of

    cell = spec.load_cell(CELL)
    cfg = cell.config
    assert _lin.state_elements(cfg) * 4 == 2_211_840
    assert _lin.row_bytes(cfg) == 2 * 2_211_840 + 4 * (2 * 2880 + 4 * 5760)
    peaks = peaks_of("TPU v5 lite")
    least, bound = _lin.least_seconds(cfg, 40 * 36, peaks)
    assert bound == "hbm"
    assert least == pytest.approx(40 * 36 * _lin.row_bytes(cfg) / 819e9)
    trace = {"busy_s": 0.1, "op_self_s": {
        "gated_delta_step_pallas.3_f32_64_1_5760_": 0.012,
        "gated_delta_step_pallas_f32_64_1_5760_": 0.008,
        "ragged_paged_attention_pallas.11_bf16_": 0.03, "fusion.7": 0.05},
        "op_count": {"gated_delta_step_pallas.3_f32_64_1_5760_": 24.0,
                     "gated_delta_step_pallas_f32_64_1_5760_": 12.0,
                     "ragged_paged_attention_pallas.11_bf16_": 12.0,
                     "fusion.7": 99.0}}
    counters = dict(lin_state_resets=0, lin_state_carried=40,
                    lin_span_tokens=0)
    samples = [dict(counters, mode="decode", k_cap=2, lin_step_rows=80),
               dict(counters, mode="ragged", k_cap=0, lin_step_rows=40,
                    lin_span_tokens=100)]
    said = {}
    ctx = types.SimpleNamespace(
        cell=cell, trace=trace, trace_steps=samples, peaks=peaks,
        say=lambda note, **kw: said.update(kw))
    share = spec.load_reader(cell, next(
        m for m in cell.metrics if m.name == "lin_kernel_share_pct.thr"))
    assert share.read(ctx) == pytest.approx(20.0)
    roof = spec.load_reader(cell, next(
        m for m in cell.metrics if m.name == "lin_step_roofline_pct"))
    assert roof.read(ctx) == pytest.approx(100 * least / 0.020)
    assert said["live_rows_a_pass"] == 40 and said["launches_in_trace"] == 36
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
    assert set(THR_METRICS) | {"lin_kernel_share_pct.thr"} \
        <= set(line["metrics"])
    assert line["attempted"] > 0 and line["failed"] == 0
    notes = {n["note"]: n for n in map(json.loads, r.stdout.splitlines()[:-1])}
    assert notes["reference"]["agrees"] is True, notes["reference"]
    assert notes["reference"]["positions"] > 0
    assert notes["reference"]["lower_precision"]["mean_margin_sd"] \
        > 3 * notes["reference"]["mean_margin_sd"]
