"""Tests of the benchmark's own harness, on the CPU:
    python -m pytest benchmarks/tests -q
Nothing here gives a device number."""

from __future__ import annotations

import copy
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from benchmarks.lib import loadgen, result, spec, stats, steps  # noqa: E402
from benchmarks.lib import trace as tr  # noqa: E402
from benchmarks.lib import traffic as tg  # noqa: E402
from benchmarks.lib.peaks import peaks_of  # noqa: E402

UNITS = {"a_ms": "ms", "b_pct": "%"}


def good_line(traced: bool) -> dict:
    dev = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
           "memory_peak_bytes": 14_000_000_000}
    if traced:
        dev.update(busy_s=3.2, window_s=5.0)
    return result.build(True, 400, 0, {"a_ms": 12.5, "b_pct": 40.0}, UNITS,
                        dev, {"device_ops": [["fusion.1", 1.5]],
                              "idle_gaps": [["decode.detok", 0.2]]}
                        if traced else None)


@pytest.mark.parametrize("traced", [False, True])
def test_validator_accepts_a_good_line(traced):
    line = good_line(traced)
    result.validate(line, UNITS, traced)
    assert json.loads(result.emit(line)) == line


def _busy_over_window(line):
    line["device"]["busy_s"] = 6.0


def _zero_busy(line):
    line["device"]["busy_s"] = 0.0


def _null_metric(line):
    line["metrics"]["a_ms"]["value"] = None


def _nan_metric(line):
    line["metrics"]["a_ms"]["value"] = float("nan")


def _missing_unit(line):
    del line["metrics"]["a_ms"]["unit"]


def _missing_metric(line):
    del line["metrics"]["b_pct"]


def _extra_key(line):
    line["notes"] = "x"


def _no_peak(line):
    del line["device"]["memory_peak_bytes"]


def _no_window(line):
    del line["device"]["window_s"]


def _long_breakdown(line):
    line["breakdown"]["device_ops"] = [["op", 0.1]] * 11


@pytest.mark.parametrize("spoil", [
    _busy_over_window, _zero_busy, _null_metric, _nan_metric, _missing_unit,
    _missing_metric, _extra_key, _no_peak, _no_window, _long_breakdown])
def test_validator_rejects_each_malformed_line(spoil):
    line = copy.deepcopy(good_line(True))
    spoil(line)
    with pytest.raises(result.MalformedResult):
        result.validate(line, UNITS, True)


def test_breakdown_is_refused_in_an_untraced_line():
    line = good_line(False)
    line["breakdown"] = {"device_ops": []}
    with pytest.raises(result.MalformedResult):
        result.validate(line, UNITS, False)


# ------------------------------------------------------------- the trace
def test_reducer_on_hand_counted_events():
    # chip 0: a `while` 0-100 ns holding a 10-30 and b 40-50; c 120-150.
    # chip 1: one op 0-60. A second line (modules) must not be added in.
    planes = [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [["jit_fn", 0, 150]]},
            {"name": "XLA Ops", "events": [
                ["while", 0, 100], ["a", 10, 20], ["b", 40, 10],
                ["c", 120, 30]]}]},
        {"name": "/device:TPU:1", "lines": [
            {"name": "XLA Ops", "events": [["c", 0, 60]]}]},
        {"name": "/host:driver", "lines": [
            {"name": "python", "events": [["loop", 0, 10_000],
                                          ["emit", 95, 30], ["far", 500, 9]]}]},
    ]
    red = tr.reduce(planes, chips=2)
    assert red["window_s"] == pytest.approx(150e-9)
    assert [c["busy_s"] for c in red["per_chip"]] == pytest.approx(
        [130e-9, 60e-9])
    assert red["busy_s"] == pytest.approx(95e-9)  # the mean, not the sum
    assert red["op_self_s"]["while"] == pytest.approx(70e-9 / 2)
    assert red["op_self_s"]["c"] == pytest.approx((30e-9 + 60e-9) / 2)
    # the gap 100-120 ns on chip 0, under the innermost host function that
    # spans all of it
    assert red["gaps"][0] == [100, 20, "c", "emit"]
    with pytest.raises(tr.TraceError):
        tr.reduce(planes, chips=4)
    with pytest.raises(tr.TraceError):
        tr.reduce([planes[2]])  # no device plane at all


def test_reducer_on_the_trimmed_chip_trace():
    """benchmarks/fixtures/v5e_trace_trimmed.json: the first milliseconds of
    a real one-chip capture (PR 23). Hand-checked values are beside it in
    v5e_trace_trimmed.expect.json."""
    path = os.path.join(BENCH, "fixtures", "v5e_trace_trimmed.json")
    with open(path) as f:
        planes = json.load(f)
    with open(path.replace(".json", ".expect.json")) as f:
        want = json.load(f)
    red = tr.reduce(planes, chips=want["chips"])
    assert red["per_chip"][0]["line"] == want["line"]
    assert red["per_chip"][0]["events"] == want["events"]
    assert red["window_s"] == pytest.approx(want["window_s"])
    assert red["busy_s"] == pytest.approx(want["busy_s"])
    assert 0 < red["busy_s"] <= red["window_s"]
    # summing every line's durations — the mistake the reducer must not
    # make — would pass the window
    every = sum(e[2] for p in planes for ln in p["lines"]
                for e in ln["events"]) / 1e9
    assert every > red["busy_s"]


# ---------------------------------------------------------- the generator
def _mix(name):
    return spec.load_json(os.path.join(BENCH, "traffic", name + ".json"))


def test_open_schedule_is_the_seeds_and_keeps_the_work_fixed():
    mix = _mix("chat")
    a = tg.open_schedule(mix, 2147483999, 32)
    b = tg.open_schedule(mix, 2147483999, 32)
    c = tg.open_schedule(mix, 5, 32)
    key = [(r.due_s, r.user, r.prompt, r.num_predict) for r in a]
    assert key == [(r.due_s, r.user, r.prompt, r.num_predict) for r in b]
    assert key != [(r.due_s, r.user, r.prompt, r.num_predict) for r in c]
    wa = [r for r in a if r.due_s >= 0]
    wc = [r for r in c if r.due_s >= 0]
    # another seed: the same arrival times, and the same sizes in another
    # order inside each block of 8 — so the same load over time
    assert [r.due_s for r in wa] == [r.due_s for r in wc]
    assert [r.prompt_tokens for r in wa] != [r.prompt_tokens for r in wc]
    for i in range(0, len(wa), tg.BLOCK):
        for field in ("prompt_tokens", "num_predict", "user"):
            assert sorted(getattr(r, field) for r in wa[i:i + tg.BLOCK]) == \
                sorted(getattr(r, field) for r in wc[i:i + tg.BLOCK])
    assert len(wa) == round(mix["rate_per_s"] * 32)  # four burst periods
    assert all(-mix["ramp_s"] <= r.due_s < 32 for r in a)
    assert all(r.prompt_tokens == len(r.prompt) + 1 for r in a)
    lo, hi = mix["prompt_tokens"]["min"], mix["prompt_tokens"]["max"]
    assert all(lo <= r.prompt_tokens <= hi for r in a)
    # the burst second of each 8 s period carries about twice the base
    busy = [r for r in tg.open_schedule(dict(mix, rate_per_s=16.0), 3, 64)
            if r.due_s >= 0]
    burst = sum(1 for r in busy if r.due_s % 8 >= 7)
    assert burst / len(busy) == pytest.approx(2 / 9, abs=0.04)


def test_closed_plan_is_the_seeds():
    mix = _mix("batch")
    a, b = tg.closed_plan(mix, 9), tg.closed_plan(mix, 9)
    assert len(a) == mix["clients"]
    assert [[r.prompt for r in c] for c in a] == [[r.prompt for r in c]
                                                  for c in b]
    assert {r.num_predict for c in a for r in c} == {256}
    assert a[5][0].user == "user005"


def test_shared_prefix_and_sessions_arrive_as_data():
    mix = dict(_mix("chat"), shared_prefix={"share": 1.0, "tokens": 300,
                                            "groups": 2},
               session={"turns": 2, "think_s": 0.5})
    plan = tg.open_schedule(mix, 1, 10)
    heads = {r.prompt[:300] for r in plan}
    assert len(heads) == 2 and all(r.prompt_tokens >= 309 for r in plan)
    import random
    nxt = tg.follow_up(plan[0], mix, 999, random.Random(0))
    assert nxt.prompt.startswith(plan[0].prompt) and nxt.turn == 1
    assert tg.follow_up(nxt, mix, 1000, random.Random(0)) is None


def _rec(**kw):
    base = dict(index=0, user="u", prompt_tokens=10, num_predict=4, due_s=1.0,
                sent_s=1.002, first_s=1.1, last_s=1.4, tokens=4,
                frames=[(1.1, 1), (1.4, 3)], status=200, done_reason="length")
    base.update(kw)
    return loadgen.Record(**base)


def test_lateness_and_percentiles_count_failures_at_the_drain_limit():
    ok = [_rec(index=i, first_s=1.0 + 0.01 * (i + 1)) for i in range(18)]
    shed = _rec(index=18, status=503, error="shed", first_s=None, tokens=0,
                frames=[], done_reason=None)
    cut = _rec(index=19, tokens=2, done_reason=None,
               error="not finished within the drain limit")
    recs = ok + [shed, cut]
    assert not shed.ok and not cut.ok and ok[0].ok
    late = loadgen.lateness_ms(recs)
    assert late["n"] == 20 and late["median_ms"] == pytest.approx(2.0)
    tt = stats.ttft_ms(recs, 30000.0)
    assert stats.percentile(tt, 50) == pytest.approx(100.0)
    assert stats.percentile(tt, 95) == 30000.0  # 2 of 20 failed
    assert stats.percentile(stats.tpot_ms(recs, 30000.0), 95) == 30000.0
    assert stats.percentile(stats.tpot_ms(ok, 30000.0), 95) == pytest.approx(
        1e3 * (1.4 - 1.01) / 3)  # nearest rank: the largest of 18
    assert stats.tokens_in_window(ok, 1.2) == 18  # first frames only


def test_prometheus_delta_mean():
    p0 = ('x_sum{model="m",phase="queue"} 10\nx_count{model="m",phase="queue"} 2\n'
          'x_sum{model="m",phase="decode"} 99\nx_count{model="m",phase="decode"} 9\n')
    p1 = p0.replace(" 10\n", " 40\n").replace('"queue"} 2', '"queue"} 8')
    assert stats.delta_mean(p0, p1, "x", phase="queue") == pytest.approx(5.0)
    assert stats.delta_mean(p0, p0, "x", phase="queue") is None


# ---------------------------------------------------- steps and the peaks
def test_step_samples_count_forward_passes():
    scan = {"mode": "decode", "k_cap": 8, "tokens": 512, "n_decode": 64,
            "host_prep_ms": 0.1, "dispatch_ms": 1.0, "collect_ms": 90.0,
            "detok_ms": 2.0, "total_ms": 93.1, "ts": 100.0}
    ragged = {"mode": "ragged", "k_cap": 0, "tokens": 200, "n_decode": 8,
              "n_prefill": 2}
    assert steps.passes(scan) == 8 and steps.passes(ragged) == 1
    assert steps.host_ms(scan) == pytest.approx(3.1)


def test_an_unknown_device_kind_has_no_peaks():
    assert peaks_of("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks_of("TPU v9")


# --------------------------------------------------- data-driven, as a rule
def test_no_cell_model_or_metric_name_in_the_general_code():
    bj = spec.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    names = {w["name"] for w in bj["workloads"]}
    names |= {c["name"] for c in bj["configs"]}
    # (a mix's name is a common word — "batch" is in a server flag — so
    # mixes are held to the rule by test_a_new_cell_is_files_and_one_entry)
    names |= {m["name"] for g in ("end_to_end", "per_layer") for m in bj[g]}
    general = [os.path.join(BENCH, "run.py"), os.path.join(BENCH, "serve.py")]
    general += [os.path.join(BENCH, "lib", f)
                for f in os.listdir(os.path.join(BENCH, "lib"))
                if f.endswith(".py")]
    for path in general:
        with open(path) as f:
            text = f.read()
        for name in names:
            assert not re.search(r"(?<![\w.])" + re.escape(name) + r"(?![\w.])",
                                 text), f"{name!r} appears in {path}"


def test_a_new_cell_is_files_and_one_entry(tmp_path):
    """The `qwen2.5-7b-d14.prefix` cell of PERF.md's Open questions: a
    traffic file, a metric file and entries — no edit to any file."""
    bench = tmp_path / "benchmarks"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    mix = dict(_mix("chat"), shared_prefix={"share": 0.9, "tokens": 1500,
                                            "groups": 4})
    (bench / "traffic" / "prefix.json").write_text(json.dumps(mix))
    (bench / "layer_metrics" / "prefix_hit_share.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    bj = spec.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    bj["workloads"].append({"name": "qwen2.5-7b-d14.prefix",
                            "config": "qwen2.5-7b-d14", "traffic": "prefix",
                            "chips": 1, "why": "sessions sharing a prefix"})
    bj["per_layer"].append({
        "name": "prefix_hit_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "engine loop",
        "moves": "tpot_p95_ms", "workloads": ["qwen2.5-7b-d14.prefix"]})
    for m in bj["end_to_end"]:
        if "workloads" in m and m["name"] == "tpot_p95_ms":
            m["workloads"].append("qwen2.5-7b-d14.prefix")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bj))
    cell = spec.load_cell("qwen2.5-7b-d14.prefix",
                          str(tmp_path / "BENCHMARK.json"), str(bench))
    assert cell.traffic["shared_prefix"]["tokens"] == 1500
    assert {m.name for m in cell.metrics_of("end_to_end")} == {
        "tpot_p95_ms", "setup_s"}
    mine = [m for m in cell.metrics_of("per_layer")
            if m.name == "prefix_hit_share"]
    assert spec.load_reader(cell, mine[0]).read(None) == 42.0
    plan = tg.open_schedule(cell.traffic, 3, 10)
    assert sum(r.prompt.startswith("system") for r in plan) > len(plan) / 2


def test_every_metric_of_every_cell_has_a_reader_and_every_mix_a_file():
    bj = spec.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    for w in bj["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.traffic["kind"] in tg.KINDS
        assert any(m.name == "setup_s" for m in cell.metrics_of("end_to_end"))
        assert len(cell.metrics_of("end_to_end")) >= 2
        assert cell.metrics_of("per_layer")
        for m in cell.metrics:
            spec.load_reader(cell, m)
            if m.group == "per_layer":
                moved = m.entry["moves"]
                assert any(e.name == moved
                           for e in cell.metrics_of("end_to_end")), (
                    f"{m.name} moves {moved}, which {cell.name} lacks")


def test_a_split_metric_shares_the_reader_of_its_stem(tmp_path):
    folder = tmp_path / "layer_metrics"
    folder.mkdir()
    (folder / "x_ms.py").write_text("def read(ctx):\n    return 1.0\n")
    (folder / "x_ms.own.py").write_text("def read(ctx):\n    return 2.0\n")
    cell = spec.Cell("c", 1, {}, "", {}, (), 1, str(tmp_path))
    def reader(name):
        return spec.load_reader(cell, spec.Metric(name, "ms", "per_layer", {}))
    assert reader("x_ms").read(None) == 1.0
    assert reader("x_ms.lat").read(None) == reader("x_ms.thr").read(None) == 1.0
    assert reader("x_ms.own").read(None) == 2.0
    with pytest.raises(spec.SpecError):
        reader("y_ms.lat")


def test_nothing_is_written_outside_the_checkout():
    """No fixed path such as /tmp/<name>: the driver gives each side its
    own HOME and TMPDIR, and two checkouts share a machine."""
    for folder, _, files in os.walk(BENCH):
        if "tests" in folder or "fixtures" in folder:
            continue
        for name in files:
            if name.endswith((".py", ".sh")):
                with open(os.path.join(folder, name)) as f:
                    assert "/tmp" not in f.read(), name


# ------------------------------------------------------------ the reference
def _tiny(bias: bool, qk_norm: bool, layers: int = 3):
    import jax
    import jax.numpy as jnp

    from ollamamq_tpu.config import ModelConfig
    from ollamamq_tpu.models import llama

    mc = ModelConfig(name="t", vocab_size=600, hidden_size=128,
                     intermediate_size=256, num_layers=layers, num_heads=8,
                     num_kv_heads=4, head_dim=16, max_seq_len=512,
                     attn_bias=bias, qk_norm=qk_norm, rope_theta=1e6,
                     rms_norm_eps=1e-6, tie_embeddings=False)
    params = llama.init_params(mc, jax.random.PRNGKey(0), dtype=jnp.float32)
    key = jax.random.PRNGKey(1)
    for name, a in list(params["layers"].items()):
        if name.startswith("b") or name.endswith("norm"):
            key, k = jax.random.split(key)  # init gives zeros and ones
            params["layers"][name] = a + 0.3 * jax.random.normal(k, a.shape)
    cfg = {"num_attention_heads": 8, "num_key_value_heads": 4, "head_dim": 16,
           "rms_norm_eps": 1e-6, "rope_theta": 1e6, "attention_bias": bias,
           "qk_norm": qk_norm, "tie_word_embeddings": False}
    return mc, params, cfg


def _greedy(mc, params, prompt: str, n: int, penalty: float = 1.1) -> list:
    """n greedy ids from the PROGRAM's own forward (its padded prefill) and
    its own repetition penalty over the last 64 context tokens."""
    import jax.numpy as jnp
    import numpy as np

    from ollamamq_tpu.models import llama
    from ollamamq_tpu.ops.sampling import apply_repeat_penalty

    seq = [1] + [b + 3 for b in prompt.encode()]
    cache = jnp.zeros((mc.num_layers, 64, mc.num_kv_heads, mc.head_dim))
    table = jnp.arange(8, dtype=jnp.int32)[None, :]
    out = []
    for _ in range(n):
        toks = np.zeros((1, 64), np.int32)
        toks[0, :len(seq)] = seq
        logits, _, _ = llama.forward_prefill(
            params, mc, jnp.asarray(toks), jnp.asarray([len(seq)]), cache,
            cache, table, 8)
        recent = np.full((1, 64), -1, np.int32)
        recent[0, 64 - min(64, len(seq)):] = seq[-64:]
        logits = apply_repeat_penalty(logits, jnp.asarray(recent),
                                      jnp.asarray([penalty], jnp.float32))
        out.append(int(jnp.argmax(logits[0])))
        seq.append(out[-1])
    return out


@pytest.mark.parametrize("bias,qk_norm", [(True, False), (False, True)])
def test_the_reference_agrees_with_the_program_and_not_with_a_broken_one(
        bias, qk_norm):
    import dataclasses

    from benchmarks.reference import dense_decoder as ref

    mc, params, cfg = _tiny(bias, qk_norm)
    prompt = "hello chip, serve tokens"

    def check(ids, pad_to=64, **options):
        return ref.check(cfg, params, [{"prompt": prompt, "ids": ids, "options":
                                        {"temperature": 0, **options}}],
                         pad_to, 16)

    served = _greedy(mc, params, prompt, 10)
    good = check(served)
    assert good["agrees"] and good["argmax_share"] == 1.0
    assert good["positions"] == 10 and good["mean_margin_sd"] < 1e-4
    # with the penalty off the program chooses other ids, and a request is
    # held to the options it carried
    plain = _greedy(mc, params, prompt, 10, penalty=1.0)
    assert plain != served
    assert check(plain, repeat_penalty=1.0)["argmax_share"] == 1.0
    assert check(plain)["argmax_share"] < 1.0
    # a server that skips the last layer
    cut = dict(params, layers={k: a[:2] for k, a in params["layers"].items()})
    bad = check(_greedy(dataclasses.replace(mc, num_layers=2), cut, prompt, 10))
    assert not bad["agrees"] and bad["mean_margin_sd"] > 0.3
    # what has no one right answer, or does not fit the shapes, is an error
    with pytest.raises(ValueError):
        check(served, temperature=0.8)
    with pytest.raises(ValueError):
        check(served, pad_to=16)


# ------------------------------------------------------------- end to end
@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_end_to_end(trace):
    """The whole control flow at a tiny size on the CPU: it must end
    `correct: false` at the platform check, with a line that validates."""
    bj = spec.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    name = bj["workloads"][0]["name"]
    r = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", name,
         "--seed", "2147483999", "--seconds", "4", "--trace", str(trace),
         "--rehearse-cpu"], cwd=ROOT, capture_output=True, text=True,
        timeout=600, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode == 0, r.stderr[-2000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["correct"] is False and line["device"]["platform"] == "cpu"
    cell = spec.load_cell(name)
    group = "per_layer" if trace else "end_to_end"
    result.validate(line, {m.name: m.unit for m in cell.metrics_of(group)},
                    bool(trace))
    assert line["attempted"] > 0 and line["failed"] == 0
    notes = {n["note"]: n for n in map(json.loads, r.stdout.splitlines()[:-1])}
    assert notes["reference"]["positions"] > 0, notes["reference"]
    assert "error" not in notes["reference"]
    assert notes["memory"]["param_bytes"] > 0


# Prompts whose greedy continuation reaches the byte tokenizer's EOS id on
# the rehearsal-sized sparse model (CPU, engine seed 0): found by serving the
# `batch` mix at that size on three seeds, where five requests of 221 ended
# `done_reason: "stop"` after 15 to 28 of their 32 tokens.
PROMPTS_THAT_SAMPLE_EOS = [
    "c24 long fills queues", "c40 fair a long of and while", "c6 queues ",
    "c29 long in of valu", "c2 fair qu"]


def test_no_id_ends_a_request_before_its_count(tmp_path):
    """A request of the harness's traffic ends by count. Random weights reach
    the byte tokenizer's EOS id now and then (about once in 2,000 tokens at
    the rehearsal's vocabulary of 512; once in 10^7 or more on the chip, so
    once in some hundred runs): the server child goes on past it
    (`serve.end_by_count_only`), the id is returned like any other, and the
    request completes as asked — where the program alone ends it `stop`,
    short, and the run would read `correct: false`."""
    from ollamamq_tpu.engine.tokenizer import ByteTokenizer

    from benchmarks import serve
    from benchmarks.lib.server import Child

    eos = ByteTokenizer.eos_id   # the program's, unshimmed in this process
    assert eos == 2
    subprocess.run(["make", "-C", os.path.join(ROOT, "cpp")], check=True,
                   capture_output=True)
    cell = spec.load_cell("olmoe-1b-7b-d10.batch")
    cfg = serve.as_run(cell.config, True)
    child = Child(cell.config_file, str(tmp_path), True, False)
    try:
        child.wait_health(300)
        gen = loadgen.LoadGen(child.base_url, cfg["name"],
                              {"options": {"temperature": 0}},
                              int(cfg["vocab_size"]), 0, 1.0)
        recs = gen.alone([
            tg.Planned(index=i, user=f"u{i}", prompt=p,
                       prompt_tokens=len(p) + 1, num_predict=32)
            for i, p in enumerate(PROMPTS_THAT_SAMPLE_EOS)])
    finally:
        child.stop()
    assert [(r.done_reason, r.tokens, r.ok) for r in recs] == \
        [("length", 32, True)] * len(recs), [r.error for r in recs]
    assert any(eos in r.ids for r in recs), \
        "no prompt reached the EOS id: the test has lost its subject"
