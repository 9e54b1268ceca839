"""The hybrid configuration, its reference and its cell, on the CPU:
    python -m pytest benchmarks/tests/test_lfm2_cell.py -q

That they load as files and entries; that the configuration file holds the
catalog's numbers and reaches the program's ModelConfig key by key; that the
reference's tolerance passes the program's own forward and refuses eight wrong
ones (tiny size, float32); and the whole control flow of the cell at a tiny
size. Nothing here gives a device number."""

from __future__ import annotations

import dataclasses
import functools
import json
import os
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from benchmarks.lib import arch, result, spec  # noqa: E402

CELL = "lfm2-8b-a1b-d18.batch"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# `config` of LFM2-8B-A1B in the model-configs guide's catalog, as of PR 32
# (held here too, so that the test runs where the guide is not installed)
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168, "max_position_embeddings": 128000,
    "model_type": "lfm2_moe", "moe_intermediate_size": 1792,
    "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 24, "num_key_value_heads": 8, "rope_theta": 1000000,
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536,
    "layer_types": ["conv", "conv"] + ["full_attention", "conv", "conv",
                                       "conv"] * 4
    + ["full_attention", "conv", "conv"] * 2}
MOE_METRICS = ("moe_expert_mm_share_pct.thr", "moe_expert_mm_roofline_pct",
               "moe_experts_hit_pct.thr", "moe_load_max_over_mean.thr")
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
                       if r["name"] == "LFM2-8B-A1B")
        assert row["config"] == published
        assert cfg["source"] == row["source_url"]
    changed = {k for k, v in published.items() if cfg[k] != v}
    assert changed == set(cfg["reduced"]) == {"num_hidden_layers",
                                              "layer_types"}
    assert cfg["num_hidden_layers"] == 18
    assert cfg["layer_types"] == published["layer_types"][:18]
    assert cfg["reduced_from"] == {k: published[k] for k in changed}
    assert {"head_dim", "qk_norm", "router_score", "norm_topk_eps",
            "tie_word_embeddings"} <= set(cfg["assumed"])
    # what the readers divide by comes from this file's own counts
    assert (arch.attention_layers(cfg), arch.expert_layers(cfg),
            arch.expert_width(cfg), arch.num_experts(cfg)) == (4, 16, 1792, 32)
    flags = cfg["server_flags"]
    assert int(flags[flags.index("--num-pages") + 1]) >= 1024
    per_layer = {m.name for m in cell.metrics_of("per_layer")}
    assert per_layer == set(MOE_METRICS) | set(THR_METRICS)
    assert {m.name for m in cell.metrics_of("end_to_end")} == \
        {"output_tok_s", "setup_s"}
    for m in cell.metrics:
        assert callable(spec.load_reader(cell, m).read)
    ref = os.path.join(BENCH, "reference", cfg["reference"] + ".py")
    assert cfg["reference"] == "lfm2_decoder" and os.path.exists(ref)
    bj = spec.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = next(c for c in bj["configs"] if c["name"] == cfg["name"])
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]


def test_the_program_runs_the_configuration_files_model():
    """serve.py hands every architecture key of the file to ModelConfig; the
    stack the program then scans is the file's, and its bytes the file's."""
    from benchmarks import serve
    from ollamamq_tpu.config import ATTENTION, CONV, EXPERTS

    cfg = spec.load_cell(CELL).config
    mc = serve.model_config(cfg, rehearse=False)
    assert mc.layer_types == tuple(cfg["layer_types"])
    assert (mc.count(CONV), mc.count(ATTENTION), mc.count(EXPERTS)) \
        == (14, 4, 16)
    assert [(f, len(p), n) for f, p, n in mc.layer_plan()] \
        == [(0, 1, 2), (2, 4, 4)]
    assert (mc.router_score, mc.use_expert_bias, mc.norm_topk_prob,
            mc.norm_topk_eps, mc.routed_scaling_factor) \
        == ("sigmoid", True, True, 1e-6, 1)
    assert (mc.num_heads, mc.num_kv_heads, mc.head_dim, mc.qk_norm_kind) \
        == (32, 8, 64, "head")
    assert (mc.num_experts, mc.num_experts_per_tok, mc.expert_width,
            mc.intermediate_size, mc.num_dense_layers) \
        == (32, 4, 1792, 7168, 2)
    assert mc.tie_embeddings and mc.rms_norm_eps == 1e-5
    assert mc.conv_L_cache == 3 and mc.max_seq_len == 128000
    assert 2 * mc.param_count() == 12_274_956_288  # as the file's arithmetic
    assert "12,274,956,288" in cfg["arithmetic"]
    # the rehearsal's tiny stack keeps a prefix, a period and a tail
    tiny = serve.model_config(cfg, rehearse=True)
    assert tiny.num_layers == 6 and tiny.count(CONV) == 4
    assert tiny.num_dense_layers == 2 and tiny.expert_width == 64
    # a file the program cannot run still ends serve.py at start
    with pytest.raises(serve.Refused, match="layer_types"):
        serve.model_config(dict(cfg, layer_types=cfg["layer_types"][:5]),
                           rehearse=False)
    with pytest.raises(serve.Refused, match="mamba"):
        serve.model_config(dict(cfg, layer_types=["mamba"] * 18),
                           rehearse=False)


# ------------------------------------------------------------ the reference
KINDS = ("conv", "conv", "full_attention", "conv", "full_attention", "conv",
         "conv")


def _tiny():
    import jax
    import jax.numpy as jnp

    from ollamamq_tpu.config import ModelConfig
    from ollamamq_tpu.models import llama

    mc = ModelConfig(name="t", vocab_size=600, hidden_size=128,
                     intermediate_size=192, num_layers=len(KINDS),
                     num_heads=8, num_kv_heads=4, head_dim=16,
                     max_seq_len=512, qk_norm="head", rope_theta=1e4,
                     rms_norm_eps=1e-5, tie_embeddings=True, num_experts=8,
                     num_experts_per_tok=2, norm_topk_prob=True,
                     norm_topk_eps=1e-6, router_score="sigmoid",
                     use_expert_bias=True, num_dense_layers=2,
                     moe_intermediate_size=64, layer_types=KINDS)
    params = llama.init_params(mc, jax.random.PRNGKey(0), dtype=jnp.float32)
    key = jax.random.PRNGKey(1)
    for name, a in list(params["layers"].items()):
        if name.endswith("norm"):
            key, k = jax.random.split(key)  # init gives ones
            params["layers"][name] = a + 0.3 * jax.random.normal(k, a.shape)
    cfg = {"hidden_size": 128, "num_attention_heads": 8,
           "num_key_value_heads": 4, "head_dim": 16, "norm_eps": 1e-5,
           "rope_theta": 1e4, "qk_norm": "head", "layer_types": list(KINDS),
           "num_dense_layers": 2, "conv_L_cache": 3, "num_experts": 8,
           "num_experts_per_tok": 2, "norm_topk_prob": True,
           "norm_topk_eps": 1e-6, "router_score": "sigmoid",
           "use_expert_bias": True, "routed_scaling_factor": 1,
           "tie_word_embeddings": True}
    return mc, params, cfg


PROMPTS = ("hello chip, mix the last three", "a state of two rows a slot",
           "the bias picks, it does not weigh")


@functools.lru_cache(maxsize=None)
def _chunk_fn(mc):
    """(one span of one sequence through the served ragged forward — row 0,
    slot 0, a 64-token stream; the padded prefill oracle), jitted once a
    model."""
    import jax
    import jax.numpy as jnp

    from ollamamq_tpu.models import llama

    def chunk(params, table, toks, start, n_tok, first, kc, vc, conv):
        pos = jnp.where(jnp.arange(64) < n_tok, start + jnp.arange(64), -1)
        return llama.forward_ragged(
            params, mc, toks, jnp.zeros(64, jnp.int32), pos,
            jnp.where(pos >= 0, pos + 8, 0), (n_tok - 1)[None], kc, vc, table,
            jnp.zeros(1, jnp.int32), n_tok[None], (start + n_tok)[None], 8,
            conv_state=conv, slot_ids=jnp.zeros(1, jnp.int32),
            is_first=first[None])

    def whole(params, table, toks, n_tok, kc, vc):
        return llama.forward_prefill(params, mc, toks, n_tok, kc, vc, table,
                                     8)[0]

    return jax.jit(chunk), jax.jit(whole)


def _greedy(mc, params, prompt: str, n: int, cut=None, drop_state=False):
    """n greedy ids (no penalty) from the PROGRAM's own forwards: the padded
    prefill oracle, or (with `cut`) the served ragged forward over the
    sequence in two chunks at `cut` — with `drop_state`, the second chunk
    opened as if it were a request's first."""
    import jax.numpy as jnp
    import numpy as np

    from ollamamq_tpu.config import ATTENTION, CONV
    from ollamamq_tpu.ops import shortconv

    seq = [1] + [b + 3 for b in prompt.encode()]
    cache = jnp.zeros((mc.count(ATTENTION), 72, mc.kv_dim))
    # page 0 is the trash page (padding tokens write its slot 0)
    table = jnp.arange(1, 9, dtype=jnp.int32)[None, :]
    chunk, whole = _chunk_fn(mc)

    out = []
    for _ in range(n):
        toks = np.zeros((1, 64), np.int32)
        toks[0, :len(seq)] = seq
        if cut is None:
            logits = whole(params, table, jnp.asarray(toks),
                           jnp.asarray([len(seq)]), cache, cache)
        else:
            st = (cache, cache, shortconv.alloc_state(
                mc.count(CONV), 1, mc.conv_L_cache, mc.hidden_size,
                jnp.float32))
            for start, stop in ((0, cut), (cut, len(seq))):
                span = np.zeros(64, np.int32)
                span[:stop - start] = seq[start:stop]
                logits, *st = chunk(
                    params, table, jnp.asarray(span), jnp.int32(start),
                    jnp.int32(stop - start),
                    jnp.int32(start == 0 or drop_state), *st)
        out.append(int(jnp.argmax(logits[0])))
        seq.append(out[-1])
    return out


def _check(cfg, params, served: dict) -> dict:
    from benchmarks.reference import lfm2_decoder as ref

    return ref.check(cfg, params, [
        {"prompt": p, "ids": ids,
         "options": {"temperature": 0, "repeat_penalty": 1.0}}
        for p, ids in served.items()], 64, 16)


def _biased_weights(cfg, lp, x):
    """A router that weights by the BIASED score at the chosen experts."""
    import jax
    import jax.numpy as jnp

    s = jax.nn.sigmoid(x.astype(jnp.float32) @ lp["w_router"]) \
        + lp["router_bias"]
    gates, experts = jax.lax.top_k(s, cfg.num_experts_per_tok)
    return gates / (gates.sum(-1, keepdims=True) + cfg.norm_topk_eps), experts


def test_the_tolerance_passes_the_program_and_refuses_eight_wrong_forwards(
        monkeypatch):
    import jax.numpy as jnp

    from ollamamq_tpu.models import moe

    mc, params, cfg = _tiny()
    lay = params["layers"]

    def served(mc, params, **how):
        return {p: _greedy(mc, params, p, 12, **how) for p in PROMPTS}

    def with_layers(**changed):
        return dict(params, layers={
            k: v for k, v in {**lay, **changed}.items() if v is not None})

    good = _check(cfg, params, served(mc, params))
    assert good["agrees"] and good["argmax_share"] == 1.0
    assert good["positions"] == 36 and good["mean_margin_sd"] < 1e-4
    # the served path — chunks and carried state — is the same model
    chunked = _check(cfg, params, served(mc, params, cut=7))
    assert chunked["agrees"] and chunked["mean_margin_sd"] < 1e-4
    # the first wrong forward: one precision lower (every matmul in float8)
    assert good["lower_precision"]["mean_margin_sd"] \
        > 10 * good["mean_margin_sd_max"]

    d = mc.hidden_size
    b, c, u = (lay["conv_in"][..., i * d:(i + 1) * d] for i in range(3))
    routed_prefix = {
        name: jnp.concatenate([lay[name][:2], lay[name]])
        for name in ("w_router", "router_bias", "we_gate", "we_up",
                     "we_down")}
    wrong = {
        "a softmax for the sigmoid": served(
            dataclasses.replace(mc, router_score="softmax"), params),
        "no normalisation of the kept weights": served(
            dataclasses.replace(mc, norm_topk_prob=False), params),
        "the selection bias dropped": served(
            dataclasses.replace(mc, use_expert_bias=False), params),
        "the taps reversed": served(mc, with_layers(
            conv_w=lay["conv_w"][..., ::-1])),
        "B and C swapped": served(mc, with_layers(
            conv_in=jnp.concatenate([c, b, u], axis=-1))),
        "the state dropped at a chunk boundary": served(
            mc, params, cut=7, drop_state=True),
        "q/k norm left out": served(
            dataclasses.replace(mc, qk_norm=False),
            with_layers(q_norm=None, k_norm=None)),
        "the dense prefix routed": served(
            dataclasses.replace(mc, num_dense_layers=0),
            with_layers(**routed_prefix)),
    }
    monkeypatch.setattr(moe, "route", _biased_weights)
    _chunk_fn.cache_clear()  # traced with the right router
    wrong["the biased score as weight"] = served(mc, params)
    monkeypatch.undo()
    _chunk_fn.cache_clear()
    readings = {}
    for what, ids in wrong.items():
        bad = _check(cfg, params, ids)
        readings[what] = round(bad["mean_margin_sd"], 4)
        assert not bad["agrees"], (what, readings)
        assert bad["mean_margin_sd"] > 2 * bad["mean_margin_sd_max"], readings
    print(readings)


def test_a_program_without_the_architecture_ends_the_run_not_a_comparison(
        monkeypatch):
    """Weights of another layout are no wrong answer: `check` asks the server
    to stop and answers nothing, so the run ends with an error exit."""
    import signal

    from benchmarks.reference import lfm2_decoder as ref

    mc, params, cfg = _tiny()
    ref.served_layout(cfg, params)
    lay = params["layers"]
    all_layers = dict(params, layers=dict(lay, wq=jnp_repeat(lay["wq"], 7)))
    with pytest.raises(ref.NotServed, match=r"wq is \(7, 128, 128\), the "
                       r"configuration's is \(2, 128, 128\)"):
        ref.served_layout(cfg, all_layers)
    no_state = dict(params, layers={k: v for k, v in lay.items()
                                    if k != "conv_w"})
    with pytest.raises(ref.NotServed, match="conv_w is absent"):
        ref.served_layout(cfg, no_state)
    with pytest.raises(ref.NotServed, match="router_score 'sigmoid'"):
        ref.served_layout(dict(cfg, router_score="softmax"), params)
    sent = []
    monkeypatch.setattr(ref.os, "kill", lambda pid, sig: sent.append(
        (pid, sig)))
    with pytest.raises(SystemExit):
        ref.check(cfg, no_state, [{"prompt": "x", "ids": [5], "options": {
            "temperature": 0}}], 64, 16)
    assert sent == [(os.getpid(), signal.SIGTERM)]


def jnp_repeat(a, n):
    import jax.numpy as jnp

    return jnp.concatenate([a] * n)[:n]


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
    assert set(MOE_METRICS) | set(THR_METRICS) <= set(line["metrics"])
    assert 0 < line["metrics"]["moe_experts_hit_pct.thr"]["value"] <= 100
    assert line["metrics"]["moe_load_max_over_mean.thr"]["value"] >= 1
    assert line["attempted"] > 0 and line["failed"] == 0
    notes = {n["note"]: n for n in map(json.loads, r.stdout.splitlines()[:-1])}
    # bfloat16 at a hidden size of 128 reads ~0.03 sd (a flipped k-th expert
    # moves a 128-wide residual far): under the bfloat16 limit, and far
    # under what the float8 forward reads
    assert notes["reference"]["agrees"] is True, notes["reference"]
    assert notes["reference"]["positions"] > 0
    assert notes["reference"]["mean_margin_sd_max"] == 0.35
    assert notes["reference"]["lower_precision"]["mean_margin_sd"] \
        > 3 * notes["reference"]["mean_margin_sd"]
