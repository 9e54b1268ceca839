"""The engine thread's phases on the device trace's clock (PR 24).

While a capture runs (`PROFILER.capturing`, set by POST /debug/profile)
the marks that feed the step samples also open `mq.*` spans
(jax.profiler.TraceAnnotation) on the engine thread, each carrying the
`seq` of the sample its time is written into. On the device side the jit
sites are named functions and jax.named_scopes name the stages inside
the ragged and decode programs. Pinned here, on the CPU:

  - a real start_trace around fake-engine steps yields every name of
    stepprof.PHASE_SPANS on ONE host line, never nested in one another,
    with `seq` stats that are recorded samples' seqs, and nothing once
    the flag is cleared;
  - POST /debug/profile replies with `capture` bounds and exactly the
    samples that ended inside them, although the call lasts longer;
    leaves the profiler's Python tracer off unless asked for it (PR 52);
    clears the flag after a failure; stamps the realtime clock before
    `start_trace` (`capture.origin_epoch_ns`) and enters one `mq.clock`
    span, which place the trace on the samples' epoch clock;
  - the real engine's child spans (stepprof.CHILD_SPANS: the seams inside
    a phase that holds more than one job) lie inside a span of their
    parent's name with the same `seq`, and no span at all is created
    while no capture runs;
  - every scope name is in the lowered text of the engine's ragged and
    decode programs, whose module names are the stable jit names, and
    README's span table lists them all.
"""

import asyncio
import glob
import os
import re
import time
import unittest.mock

import pytest
from aiohttp.test_utils import TestClient, TestServer

from ollamamq_tpu.config import EngineConfig
from ollamamq_tpu.ops.sampling import SamplingParams
from ollamamq_tpu.telemetry import stepprof
from ollamamq_tpu.telemetry.stepprof import (CHILD_SPANS, CLOCK_SPAN,
                                             PHASE_SPANS, PROFILER,
                                             SPAN_NAMES)
from testutil import collect

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_profiler():
    PROFILER.reset()
    PROFILER.capturing = False
    yield
    PROFILER.capturing = False
    PROFILER.reset()


def _fake_engine(latency=0.002):
    from ollamamq_tpu.engine.fake import FakeEngine

    eng = FakeEngine(EngineConfig(model="test-tiny", max_slots=4),
                     models={"test-tiny": None}, blocklist_path=None,
                     token_latency_s=latency)
    eng.start()
    return eng


def _burst(eng, tag, n=2, max_tokens=5):
    reqs = [eng.enqueue_request(f"{tag}{i}", "", "test-tiny",
                                prompt_tokens=[1, 2, 3],
                                sampling=SamplingParams(max_tokens=max_tokens))
            for i in range(n)]
    for r in reqs:
        assert collect(r)[-1].kind == "done"


def _mq_lines(profile_dir, clock=False):
    """{line name: [(span name, start_ns, end_ns, stats)]} for every host
    line of the capture that holds an mq.* span of the engine's phases —
    or, with `clock`, the `mq.clock` span of the thread that started the
    capture."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(
        profile_dir, "plugins", "profile", "*", "*.xplane.pb"))
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            spans = [(e.name, int(e.start_ns),
                      int(e.start_ns + e.duration_ns), dict(e.stats))
                     for e in line.events if e.name.startswith("mq.")
                     and (e.name == CLOCK_SPAN) == clock]
            if spans:
                out[line.name] = sorted(spans, key=lambda s: s[1])
    return out


def test_capture_puts_one_unnested_span_chain_on_the_engine_thread(tmp_path):
    import jax

    eng = _fake_engine()
    try:
        _burst(eng, "before")          # no capture: no spans
        jax.profiler.start_trace(str(tmp_path))
        try:
            seq_set = PROFILER.seq
            PROFILER.capturing = True
            _burst(eng, "in")
            time.sleep(0.08)           # idle ticks: mq.loop.wait
            _burst(eng, "in2")
            PROFILER.capturing = False
            time.sleep(0.05)           # the span open at the clear closes
            seq_cleared = PROFILER.seq
            _burst(eng, "after", n=3)
        finally:
            PROFILER.capturing = False
            jax.profiler.stop_trace()
    finally:
        eng.stop()
    samples = {s["seq"]: s for s in PROFILER.tail()}
    lines = _mq_lines(str(tmp_path))
    assert len(lines) == 1, f"mq.* spans on {len(lines)} host lines"
    (spans,) = lines.values()
    # The fake's phases hold one job each: the chain, and no child span.
    assert {n for n, *_ in spans} == set(PHASE_SPANS)
    prev_end = 0
    for name, start, end, stats in spans:
        assert start >= prev_end, f"{name} nested in / overlaps another mq.*"
        prev_end = end
        assert isinstance(stats.get("seq"), int), (name, stats)
        if not name.startswith("mq.loop."):
            assert stats["mode"] == "fake"
    # Identity: a step span's seq is the seq of a recorded sample, and
    # from mq.dispatch on it carries the shape the sample has.
    dispatched = [(n, st) for n, _, _, st in spans if n == "mq.dispatch"]
    assert len(dispatched) >= 6
    for _, st in dispatched:
        smp = samples[st["seq"]]
        assert (st["T_pad"], st["k_cap"]) == (smp["T_pad"], smp["k_cap"])
        assert "tokens" in st
    # A loop span carries the seq of the sample its time is written to.
    waits = [st["seq"] for n, _, _, st in spans if n == "mq.loop.wait"]
    assert waits and all(samples[q]["loop_wait_ms"] > 0 for q in waits
                         if q in samples)
    # Nothing from before the flag was set or after it was cleared (the
    # one seq reserved while capturing may still be recorded after).
    span_seqs = {st["seq"] for *_, st in spans}
    assert seq_set < min(span_seqs) and max(span_seqs) <= seq_cleared + 1
    assert len([q for q in samples if q <= seq_set]) >= 3
    assert len([q for q in samples if q > seq_cleared + 1]) >= 3


def _real_engine(model="test-tiny"):
    import jax.numpy as jnp

    from ollamamq_tpu.engine.engine import TPUEngine

    eng = TPUEngine(EngineConfig(model=model, max_slots=4, num_pages=64,
                                 page_size=8, max_pages_per_seq=16,
                                 decode_steps_per_iter=2),
                    models={model: None}, blocklist_path=None,
                    dtype=jnp.float32)
    eng.start()
    return eng


def _prompts(eng, tag, n=3, max_tokens=6):
    tok = eng.runtimes["test-tiny"].tokenizer
    reqs = [eng.enqueue_request(
        f"{tag}{i}", "", "test-tiny",
        prompt_tokens=tok.encode("count to ten, then back " * (i + 1)),
        sampling=SamplingParams(max_tokens=max_tokens)) for i in range(n)]
    for r in reqs:
        assert collect(r)[-1].kind == "done"


def test_no_span_without_a_capture_and_children_inside_their_parents(
        tmp_path):
    """The real engine's loop, ragged launches and settles: while no
    capture runs not one span object is made (a mark and a seam pay an
    attribute test each); while one runs every name is of the closed
    vocabulary, and a child span (a seam inside a phase that holds more
    than one job) lies inside a span of its parent's name on the same
    line, with its parent's `seq`."""
    import jax

    made = []
    real = PROFILER.span_factory

    def factory(name, **stats):
        made.append(name)
        return real(name, **stats)

    eng = _real_engine()
    try:
        _prompts(eng, "warm")          # compiles are out of the way
        with unittest.mock.patch.object(PROFILER, "span_factory", factory):
            _prompts(eng, "quiet")
            assert made == [], f"spans with no capture running: {made[:5]}"
            jax.profiler.start_trace(str(tmp_path))
            try:
                PROFILER.capturing = True
                _prompts(eng, "in")
            finally:
                PROFILER.capturing = False
                time.sleep(0.05)       # the span open at the clear closes
                n_made = len(made)
                _prompts(eng, "after")
                jax.profiler.stop_trace()
    finally:
        eng.stop()
    assert len(made) == n_made, "spans after the flag was cleared"
    assert set(made) <= set(SPAN_NAMES), set(made) - set(SPAN_NAMES)
    children = {p + "." + c: p for p, cs in CHILD_SPANS.items() for c in cs}
    (spans,) = _mq_lines(str(tmp_path)).values()
    assert {n for n, *_ in spans} >= set(children), \
        set(children) - {n for n, *_ in spans}
    phases = [sp for sp in spans if sp[0] in PHASE_SPANS]
    prev_end = 0
    for name, start, end, _ in phases:  # the chain itself stays unnested
        assert start >= prev_end, f"{name} overlaps another phase span"
        prev_end = end
    for name, start, end, st in spans:
        if name in children:
            inside = [p for p in phases if p[0] == children[name]
                      and p[1] <= start and end <= p[2]]
            assert len(inside) == 1, (name, start, end)
            assert inside[0][3]["seq"] == st["seq"], (name, st, inside[0])
    kids = sorted((sp for sp in spans if sp[0] in children),
                  key=lambda sp: sp[1])
    for a, b in zip(kids, kids[1:]):   # siblings never overlap
        assert a[2] <= b[1], (a, b)


# ------------------------------------------------------------- the endpoint
def _serve(fn):
    async def main():
        from ollamamq_tpu.engine.fake import FakeEngine
        from ollamamq_tpu.server.app import Server

        eng = FakeEngine(EngineConfig(model="test-tiny", max_slots=8),
                         models={"test-tiny": None}, blocklist_path=None,
                         token_latency_s=0.002)
        eng.start()
        cl = TestClient(TestServer(Server(eng, timeout_s=30).build_app()))
        await cl.start_server()
        try:
            await fn(cl)
        finally:
            await cl.close()
            eng.stop()

    asyncio.run(main())


def test_debug_profile_reply_holds_the_captures_own_samples(
        tmp_path, monkeypatch):
    """The call outlasts the capture (stop_trace is slow — here made so)
    while steps keep running: `stepprof` holds only samples that ended
    between start_trace returning and stop_trace being called, `capture`
    says which, and the cheap capture — the default since PR 52 — passes
    ProfileOptions with the Python tracer off."""
    import jax

    monkeypatch.setenv("OLLAMAMQ_PROFILE_DIR", str(tmp_path))
    real_start, real_stop = jax.profiler.start_trace, jax.profiler.stop_trace
    seen = {}

    def start(log_dir, **kw):
        seen["options"] = kw.get("profiler_options")
        real_start(log_dir, **kw)

    def slow_stop():
        seen["capturing_at_stop"] = PROFILER.capturing
        time.sleep(0.4)  # steps go on while the trace is written
        # ... and on the chip for so long (43-51 s for a 5 s capture) that
        # the sample ring turns over before stop_trace returns: the reply
        # holds the capture's samples all the same (PR 52).
        for _ in range(stepprof._RING):
            PROFILER.start("fake").finish()
        seen["ring_turned_over"] = min(s["ts"] for s in PROFILER.tail())
        real_stop()

    async def traffic(cl, stop):
        while not stop.is_set():
            r = await cl.post("/api/generate", json={
                "model": "test-tiny", "prompt": "hi", "stream": False,
                "options": {"num_predict": 4}})
            assert r.status == 200

    async def run(cl):
        stop = asyncio.Event()
        load = asyncio.ensure_future(traffic(cl, stop))
        await asyncio.sleep(0.1)
        with unittest.mock.patch.object(jax.profiler, "start_trace", start), \
                unittest.mock.patch.object(jax.profiler, "stop_trace",
                                           slow_stop):
            t_call = time.time()
            r = await cl.post("/debug/profile", json={"seconds": 0.3})
            t_reply = time.time()
        stop.set()
        await load
        assert r.status == 200, await r.text()
        out = await r.json()
        cap = out["capture"]
        assert out["python_tracer"] is False
        assert seen["options"].python_tracer_level == 0
        assert seen["capturing_at_stop"] is False and not PROFILER.capturing
        assert t_call <= cap["start_epoch"] < cap["stop_epoch"] <= t_reply
        # (no upper bound on the capture: a sleep under load oversleeps)
        assert cap["stop_epoch"] - cap["start_epoch"] >= 0.3
        assert t_reply - cap["stop_epoch"] >= 0.4   # the call lasted longer
        got = out["stepprof"]
        assert len(got) >= 10
        assert all(cap["start_epoch"] <= s["ts"] <= cap["stop_epoch"]
                   for s in got)
        seqs = [s["seq"] for s in got]
        assert (cap["first_seq"], cap["last_seq"]) == (min(seqs), max(seqs))
        # Steps that ended while stop_trace ran are in the ring, not here
        # — and by the reply the ring held nothing of the capture any more.
        assert any(cap["stop_epoch"] < s["ts"] <= t_reply
                   for s in PROFILER.tail())
        assert seen["ring_turned_over"] > cap["stop_epoch"]
        # The spans reached the trace, python tracer or not.
        (spans,) = _mq_lines(str(tmp_path)).values()
        assert {st["seq"] for n, _, _, st in spans
                if n == "mq.dispatch"} <= set(seqs) | {max(seqs) + 1}
        # The realtime clock as the one mq.clock span began, less the span's
        # place on the trace's clock: the instant the profiler's session
        # started — INSIDE start_trace, between the reading before it and
        # its return (how long that call takes is the machine's load; the
        # 10 ms stand for the instructions between the span's reading of
        # the clock and its start; another clock misses by seconds).
        assert t_call * 1e9 <= cap["origin_epoch_ns"] \
            <= cap["start_epoch"] * 1e9
        ((name, start_ns, _, st),) = [
            sp for line in _mq_lines(str(tmp_path), clock=True).values()
            for sp in line]
        assert cap["origin_epoch_ns"] - 10_000_000 \
            <= st["epoch_ns"] - start_ns \
            <= cap["start_epoch"] * 1e9 + 1_000, (start_ns, st, cap)
        # Asked for: no options at all — jax's own default, the capture
        # as it was before PR 52. And the default says the same as `false`.
        for body, want in (({"python_tracer": True}, True),
                           ({"python_tracer": False}, False)):
            seen.pop("options", None)
            with unittest.mock.patch.object(jax.profiler, "start_trace",
                                            start):
                r = await cl.post("/debug/profile",
                                  json={"seconds": 0.1, **body})
            assert r.status == 200
            assert (await r.json())["python_tracer"] is want
            assert (seen["options"] is None) is want
            assert want or seen["options"].python_tracer_level == 0
        r = await cl.post("/debug/profile", json={"python_tracer": "no"})
        assert r.status == 400

    _serve(run)


def test_debug_profile_clears_the_span_flag_after_a_failed_capture(
        tmp_path, monkeypatch):
    import jax

    monkeypatch.setenv("OLLAMAMQ_PROFILE_DIR", str(tmp_path))

    async def run(cl):
        with unittest.mock.patch.object(jax.profiler, "start_trace"), \
                unittest.mock.patch.object(
                    jax.profiler, "stop_trace",
                    side_effect=RuntimeError("disk full")):
            r = await cl.post("/debug/profile", json={"seconds": 0.1})
        assert r.status == 500
        assert PROFILER.capturing is False
        r = await cl.post("/debug/profile", json={"seconds": 0.1})
        assert r.status == 200, "the next capture must get a fresh try"
        assert (await r.json())["capture"]["first_seq"] is None

    _serve(run)


# ------------------------------------------- names on the device-side programs
@pytest.mark.parametrize("model", ["test-tiny", "test-tiny-olmoe",
                                   "test-tiny-lfm2",
                                   "test-tiny-olmo-hybrid",
                                   "test-tiny-deepseek-v32",
                                   "test-tiny-qwen3-next",
                                   "test-tiny-falcon-h1",
                                   "test-tiny-phi4-flash",
                                   "test-tiny-minicpm-sala",
                                   "test-tiny-kimi-linear",
                                   "test-tiny-mimo-v2-flash",
                                   "test-tiny-xing4"])
def test_scope_and_jit_names_in_the_lowered_ragged_and_decode_programs(model):
    """Scopes change op metadata only; every name of llama.SCOPES (and,
    for an MoE model, of moe.SCOPES inside `mlp`; for a model with conv
    layers, of llama.CONV_SCOPES beside the attention layers'; with
    linear-attention layers, of llama.LINEAR_SCOPES; with a state-space
    mixer beside the attention, of llama.SSM_SCOPES; for a decoder-hybrid-
    decoder stack, of llama.HYBRID_SCOPES — the gather of the sampled rows
    in the ragged program only: a decode pass samples every row; with an attention
    output gate or a gated shared expert, of llama.GATE_SCOPES and
    moe.SHARED_SCOPES + moe.SHARED_GATE_SCOPES; with attention kinds of
    different head shapes, of llama.PER_KIND_SCOPES) is in the debug text of
    the engine's OWN ragged and decode programs, the modules are named
    after the stable jit functions, and README lists every one of these
    names in its span table."""
    import jax
    import jax.numpy as jnp

    from ollamamq_tpu.engine.engine import TPUEngine
    from ollamamq_tpu.models import llama, moe

    eng = TPUEngine(EngineConfig(model=model, max_slots=2, num_pages=64,
                                 page_size=8, max_pages_per_seq=16,
                                 decode_steps_per_iter=2),
                    models={model: None}, blocklist_path=None,
                    dtype=jnp.float32)
    rt = eng.runtimes[model]
    scopes = llama.SCOPES + (moe.SCOPES if rt.cfg.num_experts else ()) \
        + (llama.CONV_SCOPES if rt.cfg.count("conv") else ()) \
        + (llama.LINEAR_SCOPES if rt.cfg.count("linear_attention")
           and not rt.cfg.lightning_nh else ()) \
        + (llama.LIGHTNING_SCOPES if rt.cfg.lightning_nh else ()) \
        + (llama.KDA_SCOPES if rt.cfg.kda else ()) \
        + (llama.BSA_SCOPES if rt.cfg.count("sparse_attention") else ()) \
        + (llama.SSM_SCOPES if rt.cfg.count("attention_ssm") else ()) \
        + (llama.HYBRID_SCOPES if rt.cfg.mb_per_layer else ()) \
        + (llama.GATE_SCOPES if rt.cfg.attn_output_gate else ()) \
        + (llama.PER_KIND_SCOPES if rt.cfg.per_kind_attention else ()) \
        + (llama.MHC_SCOPES if rt.cfg.streams else ()) \
        + (moe.SHARED_SCOPES + moe.SHARED_GATE_SCOPES
           if rt.cfg.shared_expert_gate else ())
    if rt.cfg.kv_lora_rank:  # latent attention: its stages for the three
        from ollamamq_tpu.ops import mla

        scopes = tuple(s for s in scopes if s not in (
            "attn_qkv", "kv_write", "attention")) + tuple(
                s for s in mla.SCOPES  # (no indexer: no `dsa_*` stage)
                if rt.cfg.index_topk or not s.startswith("dsa_")) \
            + moe.SHARED_SCOPES
    seen = {}

    def spy(site, getter):
        def get(*key):
            fn = getter(*key)

            def call(*args):
                seen[site] = (key, jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), args))
                return fn(*args)
            return call
        return get

    rt._get_ragged_jit = spy("ragged", rt._get_ragged_jit)
    rt._get_decode_jit = spy("decode", rt._get_decode_jit)
    eng.start()
    try:
        tok = rt.tokenizer
        req = eng.enqueue_request(
            "u", "", model, prompt_tokens=tok.encode("count to ten"),
            sampling=SamplingParams(max_tokens=8))
        assert collect(req)[-1].kind == "done"
    finally:
        eng.stop()
    assert set(seen) == {"ragged", "decode"}, seen.keys()
    names = {"ragged": "mq_ragged_step", "decode": "mq_decode_scan"}
    for site, (key, abstract) in seen.items():
        cache = rt._prefill_jits if site == "ragged" else rt._decode_jits
        (jitted,) = [f for k, f in cache.items()
                     if (k[1:] if site == "ragged" else k) == key]
        text = jitted.lower(*abstract).as_text(debug_info=True)
        assert re.search(r"module @jit_%s\b" % names[site], text), \
            text[:200]
        for scope in scopes:
            if scope in ("early_exit_gather", "bsa_span", "kda_prepare") \
                    and site == "decode":
                continue  # (the ragged program's alone)
            # A whole component of an op's name stack ("embed/gather"),
            # not a parameter name or a file path that contains the word.
            assert re.search(r'loc\("(?:[^"/]+/)*%s(?:/[^"]+)?"' % scope,
                             text), \
                f"scope {scope!r} not in the lowered {site} program"
    with open(os.path.join(_REPO, "README.md"), encoding="utf-8") as f:
        readme = f.read()
    table = readme[readme.index("<!-- stepprof-spans:begin -->"):
                   readme.index("<!-- stepprof-spans:end -->")]
    documented = set(re.findall(r"`([a-z_.]+)`", table))
    jit_names = {"mq_ragged_step", "mq_decode_scan", "mq_embed", "mq_encode"}
    from ollamamq_tpu.ops import mla

    assert set(llama.SCOPES) | set(llama.CONV_SCOPES) | set(moe.SCOPES) \
        | set(llama.LINEAR_SCOPES) | set(llama.SSM_SCOPES) \
        | set(llama.HYBRID_SCOPES) | set(mla.SCOPES) \
        | set(llama.BSA_SCOPES) | set(llama.LIGHTNING_SCOPES) \
        | set(llama.KDA_SCOPES) \
        | set(moe.SHARED_SCOPES) | set(llama.GATE_SCOPES) \
        | set(llama.PER_KIND_SCOPES) | set(llama.MHC_SCOPES) \
        | set(moe.SHARED_GATE_SCOPES) | jit_names | set(SPAN_NAMES) \
        <= documented
    # ... and the jit sites really are those functions: the two step
    # programs' in their builders' module, the embedders' in the engine.
    src = ""
    for name in ("engine.py", "step_program.py"):
        with open(os.path.join(_REPO, "ollamamq_tpu", "engine", name),
                  encoding="utf-8") as f:
            src += f.read()
    assert set(re.findall(r"jax\.jit\((\w+)", src)) == jit_names
