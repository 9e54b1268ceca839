"""Ragged token-budget batch composition, pinned without the oracle.

PR 6 shipped the ragged path with the legacy bucketed composer kept one
release as a live byte-identity oracle; PR 8 removed that oracle as
scheduled. The guarantees the oracle used to witness are pinned here
directly:

  - ragged greedy token streams match RECORDED expectations
    (tests/data/ragged_golden.json — regenerate with
    OLLAMAMQ_REGEN_GOLDEN=1 after an intentional numerics change);
  - streams are COMPOSITION-INVARIANT: prefix cache on/off and a
    mid-prefill cancel (which reshapes every subsequent mixed dispatch)
    leave the surviving requests' streams byte-identical;
  - the journal's batch records on the ragged path report padding waste
    <= 0.10 under a synthetic overload (seed baseline on the old
    bucketed path: 0.56) with occupancy above the 0.43 baseline;
  - an arrival storm never starves decode: every dispatch carries every
    live decode row and at most the token budget of prefill;
  - a prompt longer than one dispatch (and than any prefill bucket) is
    served in spans with the stream of a one-span run, and one beyond
    the context limit is refused with an explicit error;
  - a faulted ragged dispatch retries its implicated requests (prefill
    spans AND decode rows) and the streams still finish byte-identical.
"""

import itertools
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from ollamamq_tpu.config import MODEL_CONFIGS, EngineConfig
from ollamamq_tpu.core import MQCore
from ollamamq_tpu.engine.engine import ModelRuntime
from ollamamq_tpu.engine.request import Request
from ollamamq_tpu.ops.sampling import SamplingParams
from ollamamq_tpu.telemetry.journal import (Journal, batch_stats,
                                            check_invariants)
from ollamamq_tpu.testing.faults import FaultPlan

_IDS = itertools.count(1)

PS = 8
BUCKETS = (16, 64)  # boundaries the fuzz prompts straddle
GOLDEN = os.path.join(os.path.dirname(__file__), "data",
                      "ragged_golden.json")


def make_rt(**kw):
    defaults = dict(
        model="test-tiny", max_slots=4, num_pages=96, page_size=PS,
        max_pages_per_seq=16, max_new_tokens=8,
        decode_steps_per_iter=2, max_batch_tokens=48, token_granule=8,
    )
    defaults.update(kw)
    rt = ModelRuntime("test-tiny", MODEL_CONFIGS["test-tiny"],
                      EngineConfig(**defaults), dtype=jnp.float32)
    rt.tokenizer.eos_id = -1  # deterministic full-length streams
    return rt


def tick(rt, core):
    """One engine-loop-shaped tick (ragged is the only single-mesh mode)."""
    ran = rt.step_ragged(core)
    if not ran and any(r is not None for r in rt.slot_req):
        rt.step_decode(core, k_steps=1)


def _submit(rt, prompt, max_tokens, user="u0", repeat_penalty=1.0):
    req = Request(next(_IDS), user, "test-tiny", list(prompt),
                  SamplingParams(max_tokens=max_tokens,
                                 repeat_penalty=repeat_penalty))
    req._inc_decode = rt.tokenizer.make_incremental_decoder()
    rt.pending_prefill.append(req)
    return req


def run_all(rt, prompts, max_tokens=6, repeat_penalty=1.0,
            cancel_mid_prefill=None, max_ticks=800):
    """Drive a batch of prompts to completion; returns each request's
    generated ids (None for a cancelled one). `cancel_mid_prefill`
    names a request index to cancel as soon as its prefill is
    partially done (0 < _chunk_pos < n)."""
    core = MQCore(None)
    reqs = [_submit(rt, p, max_tokens, user=f"u{i % 3}",
                    repeat_penalty=repeat_penalty)
            for i, p in enumerate(prompts)]
    victim = (reqs[cancel_mid_prefill]
              if cancel_mid_prefill is not None else None)
    for _ in range(max_ticks):
        if victim is not None and not victim.cancelled.is_set():
            pos = getattr(victim, "_chunk_pos", 0)
            if 0 < pos < len(victim.prompt_tokens):
                victim.cancelled.set()
        if all(r.stats.finished_at for r in reqs):
            break
        tick(rt, core)
    assert all(r.stats.finished_at for r in reqs), "requests wedged"
    return [None if r is victim else list(r.generated_ids) for r in reqs]


def _fuzz_prompts(rng, n):
    """Prompt lengths hugging/straddling the bucket boundaries plus a
    few randoms — the shapes the old bucketed composer split into
    separate batches and the ragged composer packs together."""
    straddle = [b + d for b in BUCKETS for d in (-1, 0, 1)]
    lens = [straddle[int(rng.integers(len(straddle)))]
            if rng.random() < 0.6 else int(rng.integers(2, 80))
            for _ in range(n)]
    return [rng.integers(3, 500, size=max(1, L)).tolist() for L in lens]


def _golden_case(repeat_penalty):
    """The fuzz workload the recorded expectations pin: 3 rounds of 6
    boundary-straddling prompts (seed 11) per penalty setting."""
    rng = np.random.default_rng(11)
    rounds = [_fuzz_prompts(rng, 6) for _ in range(3)]
    outs = [run_all(make_rt(), prompts, repeat_penalty=repeat_penalty)
            for prompts in rounds]
    return outs


@pytest.mark.parametrize("repeat_penalty", [1.0, 1.1],
                         ids=["greedy", "repeat-penalty"])
def test_ragged_matches_recorded_expectations(repeat_penalty):
    """The oracle's replacement: the exact token streams the ragged path
    produced when the bucketed path was retired, recorded. A diff here
    means the ragged composer/jit changed NUMERICS, not just schedule —
    regenerate (OLLAMAMQ_REGEN_GOLDEN=1) only for an intentional change."""
    key = "greedy" if repeat_penalty == 1.0 else "repeat-penalty"
    outs = _golden_case(repeat_penalty)
    if os.environ.get("OLLAMAMQ_REGEN_GOLDEN"):
        data = {}
        if os.path.exists(GOLDEN):
            with open(GOLDEN) as f:
                data = json.load(f)
        data[key] = outs
        os.makedirs(os.path.dirname(GOLDEN), exist_ok=True)
        with open(GOLDEN, "w") as f:
            json.dump(data, f, indent=1, sort_keys=True)
        pytest.skip("golden regenerated")
    with open(GOLDEN) as f:
        expected = json.load(f)[key]
    assert outs == expected, "ragged streams drifted from recorded run"


@pytest.mark.parametrize("prefix_cache", [False, True],
                         ids=["cache-off", "cache-on"])
def test_prefix_cache_leaves_streams_identical(prefix_cache):
    """Composition invariance: the SAME prompts produce byte-identical
    streams with the prefix cache off and on (cache hits reshape every
    span the composer packs — the tokens must not care)."""
    rng = np.random.default_rng(7)
    shared = rng.integers(3, 500, size=3 * PS).tolist()
    prompts = [shared + rng.integers(3, 500, size=t).tolist()
               for t in (5, 17, 40)] + _fuzz_prompts(rng, 2)
    base = run_all(make_rt(prefix_cache=False), prompts)
    out = run_all(make_rt(prefix_cache=prefix_cache), prompts)
    assert out == base


def test_mid_prefill_cancel_leaves_survivors_identical():
    """Cancelling a long prompt mid-prefill (its spans already dispatched)
    must not perturb the other requests' streams — the survivors match a
    clean run of the same prompts exactly — and the cancelled slot's
    pages must all return to the pool."""
    rng = np.random.default_rng(3)
    prompts = [rng.integers(3, 500, size=n).tolist()
               for n in (70, 15, 33)]  # 70 spans several mixed dispatches
    clean = run_all(make_rt(), prompts)
    rt = make_rt()
    out = run_all(rt, prompts, cancel_mid_prefill=0)
    assert out[0] is None
    assert out[1:] == clean[1:]
    assert rt.cache.alloc.used_pages == 0
    assert not rt.reserved_slots and not rt.chunking


def test_arrival_storm_never_starves_decode_rows():
    """Every slot but one is decoding and a backlog of long prompts keeps
    arriving: each tick is ONE dispatch that carries every live decode
    row (each stream gains a token a tick) and no more prefill than the
    token budget — the backlog waits, the streams do not."""
    rt = make_rt()  # 4 slots, 48-token dispatches
    core = MQCore(None)
    rng = np.random.default_rng(5)
    streams = [_submit(rt, rng.integers(3, 500, size=6).tolist(), 40,
                       user=f"d{i}") for i in range(3)]
    while not all(r.generated_ids for r in streams):
        tick(rt, core)
    dispatched = []
    orig = rt._dispatch_ragged

    def spy(T_pad, k_cap, buf):
        lay = rt.dims.ragged_layout(T_pad)  # the step's one packed input
        slot_ids, q_len = lay.view(buf, "slot_ids"), lay.view(buf, "q_len")
        dispatched.append({int(sl): int(n) for sl, n in zip(slot_ids, q_len)
                           if n > 0})
        return orig(T_pad, k_cap, buf)

    rt._dispatch_ragged = spy
    # One token each: a backlog prompt leaves the free slot with the step
    # that ends its prefill, and the next one takes it the tick after.
    backlog = [_submit(rt, rng.integers(3, 500, size=100).tolist(), 1,
                       user=f"b{i}") for i in range(4)]
    budget = rt.ecfg.max_batch_tokens
    for _ in range(12):  # 400 backlog tokens: well over 12 ticks of budget
        live = {i: len(r.generated_ids) for i, r in enumerate(rt.slot_req)
                if r in streams}
        assert len(live) == 3
        n = len(dispatched)
        tick(rt, core)
        assert len(dispatched) == n + 1, "one mixed dispatch a tick"
        rows = dispatched[-1]
        assert all(rows.get(i) == 1 for i in live), (rows, live)
        prefill = sum(rows.values()) - len(live)
        assert 0 < prefill <= budget - len(live), rows
        for i, before in live.items():
            assert len(rt.slot_req[i].generated_ids) == before + 1
    assert any(not r.stats.finished_at for r in backlog), \
        "the storm outlasted the window it was watched for"


def test_prompt_longer_than_a_dispatch_is_served_in_spans():
    """A prompt longer than max_batch_tokens AND than the largest prefill
    bucket rides several spans (no bucket to pad it to, no truncation):
    its stream is the one a runtime whose budget holds it whole gives."""
    rng = np.random.default_rng(9)
    prompt = rng.integers(3, 500, size=BUCKETS[-1] + 40).tolist()  # 104
    rt = make_rt()
    assert len(prompt) > rt.ecfg.max_batch_tokens
    rt.journal = Journal()
    out = run_all(rt, [prompt])
    spans = [r for r in rt.journal.tail(0) if r["kind"] == "chunk"]
    assert len(spans) >= 3
    assert sum(r["tokens"] for r in spans) == len(prompt)
    assert [r["pos"] for r in spans] == sorted(r["pos"] for r in spans)
    # The composer trims a span down to a rung of its ladder: only a
    # prompt that IS the top rung goes out whole.
    whole = make_rt(max_batch_tokens=len(prompt))
    whole.journal = Journal()
    assert run_all(whole, [prompt]) == out
    assert len([r for r in whole.journal.tail(0)
                if r["kind"] == "chunk"]) == 1


def test_prompt_beyond_the_context_limit_is_refused():
    """One token over what the paged context holds: an explicit error at
    admission, no slot or page claimed, the queue behind it served."""
    rt = make_rt()
    core = MQCore(None)
    limit = min(rt.ecfg.max_context, rt.cfg.max_seq_len) - 1
    over = _submit(rt, [5] * (limit + 1), 4)
    fits = _submit(rt, [5] * limit, 1)
    tick(rt, core)
    end = over.stream.drain()[-1]
    assert end.kind == "error" and end.finish_reason.value == "error"
    assert f"prompt length {limit + 1} exceeds maximum {limit}" in end.error
    assert over not in rt.chunking and fits in rt.chunking
    for _ in range(20):
        if fits.stats.finished_at:
            break
        tick(rt, core)
    assert fits.stream.drain()[-1].finish_reason.value == "length"
    assert len(fits.generated_ids) == 1
    assert rt.cache.alloc.used_pages == 0 and not rt.reserved_slots


def test_ragged_dispatch_fault_retries_and_streams_survive():
    """An injected exception in the mixed dispatch retries BOTH its
    prefill spans and its decode rows (replay semantics): every stream
    still completes, byte-identical to an unfaulted run."""
    rng = np.random.default_rng(9)
    prompts = [rng.integers(3, 500, size=n).tolist() for n in (20, 7, 35)]
    clean = run_all(make_rt(), prompts)
    # The 2nd mixed dispatch carries a prefill tail AND live decode rows,
    # so the containment path must replay both kinds.
    plan = FaultPlan([{"site": "ragged", "kind": "exception", "at": [2]}])
    rt = make_rt(retry_backoff_s=0.0)
    rt.fault_plan = plan
    faulted = run_all(rt, prompts)
    assert plan.injected == 1
    assert faulted == clean
    assert rt.retry_count >= 1


# ------------------------------------------------ padding-waste regression
def _overload_trace(n_requests=24, seed=5):
    """Synthetic overload: arrivals outpace the drain so composition
    always has a backlog to pack; returns the journal's batch stats."""
    rng = np.random.default_rng(seed)
    rt = make_rt(max_slots=4, num_pages=160,
                 max_batch_tokens=64, token_granule=8)
    journal = Journal(capacity=65536)
    rt.journal = journal
    core = MQCore(None)
    reqs = []
    issued = 0
    guard = 0
    while True:
        while issued < n_requests and len(rt.pending_prefill) < 6:
            n = int(rng.integers(5, 70))
            req = Request(next(_IDS), f"ov{issued % 4}", "test-tiny",
                          rng.integers(3, 500, size=n).tolist(),
                          SamplingParams(max_tokens=4))
            req._inc_decode = rt.tokenizer.make_incremental_decoder()
            rt.pending_prefill.append(req)
            reqs.append(req)
            issued += 1
        tick(rt, core)
        if issued >= n_requests and all(r.stats.finished_at for r in reqs):
            break
        guard += 1
        assert guard < 5000, "overload trace wedged"
    recs = journal.tail(None)
    assert not check_invariants(recs)
    return batch_stats(recs)


def test_padding_waste_gate_ragged():
    """CI gate: the ragged path's padding waste must stay <= 0.10 under
    overload (seed baseline on the retired bucketed path: 0.56), with
    batch occupancy strictly above the 0.43 baseline."""
    stats = _overload_trace()
    assert stats["batches"] > 0
    assert stats["padding_waste"] <= 0.10, stats
    assert stats["mean_occupancy"] > 0.43, stats


def test_ragged_batch_records_carry_the_split():
    """Every ragged batch record carries mode/padded_tokens and the
    prefill/decode row split the schema promises."""
    rng = np.random.default_rng(2)
    rt = make_rt()
    journal = Journal(capacity=4096)
    rt.journal = journal
    core = MQCore(None)
    run_all_rt(rt, core, rng)
    recs = journal.tail(None, kind="batch")
    assert recs, "no batch records journaled"
    for r in recs:
        assert r["mode"] == "ragged"
        assert r["padded_tokens"] >= r["tokens"]
        assert r["n_prefill"] + r["n_decode"] == r["batch_size"]
        assert r["padded_tokens"] % 8 == 0  # the granule


def run_all_rt(rt, core, rng):
    reqs = []
    for n in (20, 5, 33):
        req = Request(next(_IDS), "u", "test-tiny",
                      rng.integers(3, 500, size=n).tolist(),
                      SamplingParams(max_tokens=4))
        req._inc_decode = rt.tokenizer.make_incremental_decoder()
        rt.pending_prefill.append(req)
        reqs.append(req)
    for _ in range(400):
        if all(r.stats.finished_at for r in reqs):
            return
        tick(rt, core)
    raise AssertionError("requests wedged")
