"""Speculative multi-token decoding on the ragged path.

The load-bearing guarantees pinned here:
  - greedy streams with speculation ON are BYTE-IDENTICAL to speculation
    OFF across a randomized fuzz matrix: chaotic and repetitive (copy-
    map) generation regimes, prefix cache on and off, an injected
    mid-stream fault at the new `spec_verify` site, and preemption under
    page pressure mid-speculation — and the penalty-ring device state
    ends identical too (the ring advances by the ACCEPTED count, never
    by k);
  - accept_prefix (ops/sampling.py) answers the longest verified prefix,
    including k=0 and all-rejected;
  - PageAllocator.rollback_to releases exactly the rejected tail's
    pages, never below the shared-prefix floor, conserving
    free + used + cached == pool under randomized alloc/rollback fuzz;
  - the journal vocabulary (speculate / spec_verify / spec_rollback)
    records with explanations, the accepted <= proposed invariant is
    checked, and page conservation holds through rollback;
  - an EXPIRED request never burns a k-token verification (the deadline
    is checked before the verify span is composed — regression test);
  - the per-user auto-throttle disables speculation for users whose
    drafts keep getting rejected.
"""

import itertools
import time

import jax.numpy as jnp
import numpy as np
import pytest

from ollamamq_tpu.config import MODEL_CONFIGS, EngineConfig
from ollamamq_tpu.core import MQCore
from ollamamq_tpu.engine import kv_cache as kvc
from ollamamq_tpu.engine.engine import ModelRuntime
from ollamamq_tpu.engine.request import FinishReason, Request
from ollamamq_tpu.ops.sampling import SamplingParams, accept_prefix
from ollamamq_tpu.telemetry.journal import (Journal, check_invariants,
                                            explain)
from ollamamq_tpu.testing.faults import FaultPlan
from test_ragged_engine import tick

_IDS = itertools.count(1)

PS = 8


def make_rt(spec, copy_weights=False, **kw):
    defaults = dict(
        model="test-tiny", max_slots=4, num_pages=256, page_size=PS,
        max_pages_per_seq=32, max_new_tokens=96,
        decode_steps_per_iter=2,
        max_batch_tokens=64, token_granule=8, spec=spec, spec_k=4,
        spec_min_accept=0.0,
    )
    defaults.update(kw)
    rt = ModelRuntime("test-tiny", MODEL_CONFIGS["test-tiny"],
                      EngineConfig(**defaults), dtype=jnp.float32)
    rt.tokenizer.eos_id = -1  # deterministic full-length streams
    if copy_weights:
        # Copy-map regime: zeroing the residual output projections makes
        # the next token a pure function of the last, so greedy
        # generation enters a cycle — the repetitive regime where
        # n-gram lookup drafts actually verify (random weights generate
        # chaos no lookup can predict).
        rt.params["layers"]["wo"] = jnp.zeros_like(rt.params["layers"]["wo"])
        rt.params["layers"]["w_down"] = jnp.zeros_like(
            rt.params["layers"]["w_down"])
    return rt


def run_all(rt, prompts, max_tokens=48, max_ticks=4000):
    core = MQCore(None)
    reqs = []
    for i, p in enumerate(prompts):
        req = Request(next(_IDS), f"u{i % 3}", "test-tiny", list(p),
                      SamplingParams(max_tokens=max_tokens))
        req._inc_decode = rt.tokenizer.make_incremental_decoder()
        rt.pending_prefill.append(req)
        reqs.append(req)
    for _ in range(max_ticks):
        if all(r.stats.finished_at for r in reqs):
            break
        tick(rt, core)
    assert all(r.stats.finished_at for r in reqs), "requests wedged"
    return [list(r.generated_ids) for r in reqs]


def _mixed_prompts(rng, n):
    """Half repetitive patterns (repetitions the lookup can match), half
    random, lengths straddling the page/budget boundaries."""
    out = []
    for i in range(n):
        if i % 2 == 0:
            pat = rng.integers(3, 500, size=int(rng.integers(3, 8))).tolist()
            L = int(rng.integers(12, 60))
            out.append((pat * (L // len(pat) + 1))[:L])
        else:
            out.append(rng.integers(3, 500,
                                    size=int(rng.integers(4, 60))).tolist())
    return out


# ------------------------------------------------------- accept_prefix unit
def test_accept_prefix_shapes_and_cases():
    draft = jnp.asarray([[5, 6, 7, 8],
                         [5, 6, 7, 8],
                         [5, 6, 7, 8],
                         [5, 6, 7, 8]], jnp.int32)
    greedy = jnp.asarray([[5, 6, 7, 8],   # all match
                          [9, 6, 7, 8],   # first rejected
                          [5, 6, 9, 8],   # partial prefix
                          [5, 6, 7, 8]], jnp.int32)
    dlen = jnp.asarray([4, 4, 4, 2], jnp.int32)
    out = np.asarray(accept_prefix(draft, greedy, dlen))
    # Row 3: matches everywhere but only 2 drafts are valid.
    assert out.tolist() == [4, 0, 2, 2]


def test_accept_prefix_k0_and_all_rejected():
    empty = jnp.zeros((3, 0), jnp.int32)
    assert np.asarray(accept_prefix(empty, empty,
                                    jnp.zeros(3, jnp.int32))).tolist() \
        == [0, 0, 0]
    draft = jnp.asarray([[1, 2, 3]], jnp.int32)
    greedy = jnp.asarray([[4, 5, 6]], jnp.int32)
    assert np.asarray(accept_prefix(draft, greedy,
                                    jnp.asarray([3]))).tolist() == [0]


def test_accept_prefix_match_after_mismatch_does_not_count():
    draft = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
    greedy = jnp.asarray([[1, 9, 3, 4]], jnp.int32)  # 3,4 match but gap at 2
    assert np.asarray(accept_prefix(draft, greedy,
                                    jnp.asarray([4]))).tolist() == [1]


# ------------------------------------------------------ allocator rollback
def test_rollback_to_frees_rejected_tail_only():
    a = kvc.PageAllocator(32, 8, 16)
    pages = a.alloc(8 * 5)  # 5 pages = 40 token positions
    assert len(pages) == 5
    freed = a.rollback_to(pages, kv_len=18)  # needs 3 pages
    assert freed == 2 and len(pages) == 3
    assert a.free_pages + a.used_pages + a.cached_pages == a.num_pages - 1
    # Already-tight allocations are a no-op.
    assert a.rollback_to(pages, kv_len=24) == 0


def test_rollback_to_never_drops_below_shared_floor():
    a = kvc.PageAllocator(32, 8, 16)
    pages = a.alloc(8 * 4)
    # Pretend the first 3 pages are shared prefix-tree pages: even a
    # kv_len of 1 (1 page needed) must keep them.
    freed = a.rollback_to(pages, kv_len=1, keep=3)
    assert freed == 1 and len(pages) == 3


def test_rollback_fuzz_conserves_pages():
    rng = np.random.default_rng(13)
    a = kvc.PageAllocator(64, 8, 32)
    live = []
    for _ in range(300):
        op = rng.random()
        if op < 0.45 or not live:
            n = int(rng.integers(1, 80))
            pages = a.alloc(n)
            if pages is not None:
                live.append((pages, n))
        elif op < 0.8:
            i = int(rng.integers(len(live)))
            pages, n = live[i]
            new_len = int(rng.integers(1, n + 1))
            a.rollback_to(pages, new_len)
            live[i] = (pages, new_len)
        else:
            pages, _ = live.pop(int(rng.integers(len(live))))
            a.free(pages)
        assert a.free_pages + a.used_pages + a.cached_pages \
            == a.num_pages - 1
    for pages, _ in live:
        a.free(pages)
    assert a.used_pages == 0


# ------------------------------------------------------------ the proposer
def test_proposer_matches_repeated_pattern():
    rt = make_rt(True)
    pat = [11, 22, 33, 44, 55]
    req = Request(next(_IDS), "u", "test-tiny", (pat * 6)[:28],
                  SamplingParams(max_tokens=32))
    rt.slot_req[0] = req
    rt.seq_lens[0] = 28
    drafts = rt._propose_drafts(req, 0)
    # Trailing 3-gram of pat*6[:28] recurs one period earlier; the
    # proposal continues the pattern.
    assert drafts == list((pat * 7)[28:28 + 4])
    rt.slot_req[0] = None


def test_proposer_respects_remaining_budget_and_novel_context():
    rt = make_rt(True)
    pat = [7, 8, 9]
    req = Request(next(_IDS), "u", "test-tiny", pat * 5,
                  SamplingParams(max_tokens=3))
    req.generated_ids = [100, 101]  # 2 of 3 emitted: 0 budget for drafts
    rt.slot_req[0] = req
    rt.seq_lens[0] = 17
    assert rt._propose_drafts(req, 0) == []
    novel = Request(next(_IDS), "u", "test-tiny", list(range(3, 40)),
                    SamplingParams(max_tokens=32))
    rt.slot_req[1] = novel
    rt.seq_lens[1] = 37
    assert rt._propose_drafts(novel, 1) == []  # nothing repeats
    rt.slot_req[0] = rt.slot_req[1] = None


# ------------------------------------------- byte-identical stream fuzzing
@pytest.mark.parametrize("regime", ["chaotic", "copy"])
def test_spec_on_off_byte_identical_fuzz(regime):
    rng = np.random.default_rng(17)
    copy = regime == "copy"
    for round_ in range(2):
        # At most max_slots prompts: with more, which slot the overflow
        # request lands on depends on finish ORDER in wall ticks (which
        # speculation legitimately changes), and the final ring rows
        # would compare across different occupants.
        prompts = _mixed_prompts(rng, 4)
        off_rt = make_rt(False, copy_weights=copy)
        on_rt = make_rt(True, copy_weights=copy)
        off = run_all(off_rt, prompts)
        on = run_all(on_rt, prompts)
        assert off == on, f"{regime} round {round_}: streams diverged"
        # Ring state must match too: the spec path's penalty ring
        # advances by the accepted count, so the device state after the
        # run is indistinguishable from single-token stepping. (Rows
        # 0..S-1 only: the trash row collects padding garbage.)
        S = off_rt.ecfg.max_slots
        assert np.array_equal(np.asarray(off_rt.recent)[:S],
                              np.asarray(on_rt.recent)[:S])
        assert on_rt.cache.alloc.used_pages == 0
        if copy:
            assert on_rt.spec_accepted > 0, "copy regime accepted nothing"


@pytest.mark.parametrize("prefix_cache", [False, True],
                         ids=["cache-off", "cache-on"])
def test_spec_on_off_identical_with_prefix_cache(prefix_cache):
    rng = np.random.default_rng(23)
    shared = rng.integers(3, 500, size=3 * PS).tolist()
    prompts = [shared + rng.integers(3, 500, size=t).tolist()
               for t in (5, 17, 30)]
    off = run_all(make_rt(False, copy_weights=True,
                          prefix_cache=prefix_cache), prompts)
    on_rt = make_rt(True, copy_weights=True, prefix_cache=prefix_cache)
    on = run_all(on_rt, prompts)
    assert off == on
    assert on_rt.cache.alloc.used_pages == 0


def test_spec_verify_fault_retries_and_streams_survive():
    """An injected exception at the spec_verify site (a mixed dispatch
    carrying verify spans) retries its implicated rows with replay
    semantics: every stream completes byte-identical to unfaulted."""
    rng = np.random.default_rng(29)
    prompts = _mixed_prompts(rng, 4)
    clean = run_all(make_rt(True, copy_weights=True), prompts)
    plan = FaultPlan([{"site": "spec_verify", "kind": "exception",
                       "at": [2]}])
    rt = make_rt(True, copy_weights=True, retry_backoff_s=0.0)
    rt.fault_plan = plan
    faulted = run_all(rt, prompts)
    assert plan.injected == 1
    assert faulted == clean
    assert rt.retry_count >= 1


def test_preemption_during_speculation_resumes_byte_identical():
    """Page pressure mid-speculation: a tiny pool forces decode-time
    extends to fail while slots are actively speculating, driving the
    preempt-with-recompute path. Streams must still finish identical to
    an unpressured spec-off run, and the pool must balance after."""
    rng = np.random.default_rng(31)
    prompts = _mixed_prompts(rng, 4)
    baseline = run_all(make_rt(False, copy_weights=True), prompts,
                       max_tokens=32)
    rt = make_rt(True, copy_weights=True, num_pages=20, retry_backoff_s=0.0)

    def requeue(req):
        rt.pending_prefill.appendleft(req)
        return True

    rt.on_preempt = requeue
    pressured = run_all(rt, prompts, max_tokens=32, max_ticks=8000)
    assert pressured == baseline
    assert rt.preempt_count > 0, "pool never pressured: test is vacuous"
    assert rt.cache.alloc.used_pages == 0
    assert rt.cache.alloc.free_pages + rt.cache.alloc.cached_pages \
        == rt.cache.alloc.num_pages - 1


# ------------------------------------------------------- deadline bugfix
def test_expired_request_never_burns_a_verify_span():
    """Regression (satellite): the deadline must be checked BEFORE a
    speculative verify span is composed — an expired request drops with
    the explicit deadline reason instead of paying k verify tokens."""
    rt = make_rt(True)
    journal = Journal(capacity=4096)
    rt.journal = journal
    # Force a proposal whenever asked: if the deadline check were
    # missing, the speculate record below would exist.
    rt._propose_drafts = lambda req, slot: [1, 2, 3]
    core = MQCore(None)
    req = Request(next(_IDS), "dl", "test-tiny",
                  list(range(3, 20)), SamplingParams(max_tokens=32))
    req._inc_decode = rt.tokenizer.make_incremental_decoder()
    rt.pending_prefill.append(req)
    while not any(r is req for r in rt.slot_req):
        tick(rt, core)
    req.deadline = time.monotonic() - 1.0  # expired mid-decode
    tick(rt, core)
    assert req.stats.finished_at, "expired request kept decoding"
    items = [i for i in req.stream.drain() if i.kind in ("done", "error")]
    assert items and items[-1].finish_reason == FinishReason.DEADLINE
    recs = journal.tail(None)
    assert not [r for r in recs if r["kind"] == "speculate"
                and r.get("req_id") == req.req_id], \
        "a verify span was composed for an expired request"
    assert [r for r in recs if r["kind"] == "deadline_drop"
            and r.get("req_id") == req.req_id]
    assert rt.cache.alloc.used_pages == 0


# ------------------------------------------------- journal + invariants
def test_spec_journal_records_explain_and_invariants():
    rng = np.random.default_rng(37)
    rt = make_rt(True, copy_weights=True)
    journal = Journal(capacity=65536)
    rt.journal = journal
    core = MQCore(None)
    reqs = []
    for p in _mixed_prompts(rng, 4):
        req = Request(next(_IDS), "ju", "test-tiny", p,
                      SamplingParams(max_tokens=32))
        req._inc_decode = rt.tokenizer.make_incremental_decoder()
        rt.pending_prefill.append(req)
        reqs.append(req)
    for _ in range(4000):
        if all(r.stats.finished_at for r in reqs):
            break
        tick(rt, core)
    assert all(r.stats.finished_at for r in reqs)
    recs = journal.tail(None)
    spec = [r for r in recs if r["kind"] == "speculate"]
    verify = [r for r in recs if r["kind"] == "spec_verify"]
    assert spec and verify, "speculation never journaled"
    assert all(v["accepted"] <= v["proposed"] for v in verify)
    for r in spec + verify:
        assert explain(r)  # every kind has human text
    batches = [r for r in recs if r["kind"] == "batch"
               and r.get("n_spec")]
    assert batches, "no batch record carried the spec split"
    assert all("spec_accepted" in r and "spec_tokens" in r
               for r in batches)
    # Page conservation holds through speculative alloc/rollback, and
    # every other invariant stays clean under speculation.
    assert check_invariants(recs) == []
    # Rollback records, when present, carry the full page post-state.
    for r in recs:
        if r["kind"] == "spec_rollback":
            assert r["kv_after"] <= r["kv_before"]
            assert r["free"] + r["used"] + r["cached"] == r["pool"]
            assert explain(r)


def test_invariant_checker_flags_accepted_over_proposed():
    bad = [{"seq": 0, "kind": "spec_verify", "req_id": 1, "slot": 0,
            "proposed": 2, "accepted": 3}]
    out = check_invariants(bad)
    assert out and "accepted 3 > proposed 2" in out[0]


def test_spec_metrics_and_stats_surface():
    from ollamamq_tpu.telemetry import schema as tm

    rng = np.random.default_rng(41)
    rt = make_rt(True, copy_weights=True)
    base = tm.SPEC_TOKENS_TOTAL.labels(model="test-tiny", outcome="proposed",
                                       proposer="ngram").value
    run_all(rt, _mixed_prompts(rng, 3), max_tokens=32)
    assert rt.spec_proposed > 0
    assert tm.SPEC_TOKENS_TOTAL.labels(model="test-tiny", outcome="proposed",
                                       proposer="ngram").value > base
    s = rt.stats()["spec"]
    assert s is not None
    assert s["proposed"] == rt.spec_proposed
    assert 0.0 <= s["accept_rate"] <= 1.0
    off = make_rt(False)
    assert off.stats()["spec"] is None


# ------------------------------------------------------- auto-throttle
def test_auto_throttle_disables_hopeless_users():
    rng = np.random.default_rng(43)
    rt = make_rt(True, spec_min_accept=0.5)
    rt.SPEC_THROTTLE_SAMPLE = 8  # shrink the warmup for the test
    journal = Journal(capacity=65536)
    rt.journal = journal
    # Garbage drafts: essentially always rejected, so the user's accept
    # rate pins near 0 and the throttle must fire.
    rt._propose_drafts = lambda req, slot: [2, 2, 2, 2]
    prompts = [rng.integers(3, 500, size=12).tolist() for _ in range(2)]
    core = MQCore(None)
    reqs = []
    for p in prompts:
        req = Request(next(_IDS), "hopeless", "test-tiny", p,
                      SamplingParams(max_tokens=48))
        req._inc_decode = rt.tokenizer.make_incremental_decoder()
        rt.pending_prefill.append(req)
        reqs.append(req)
    for _ in range(4000):
        if all(r.stats.finished_at for r in reqs):
            break
        tick(rt, core)
    assert all(r.stats.finished_at for r in reqs)
    assert "hopeless" in rt._spec_throttled
    # After the throttle fired, no further speculate records appear.
    recs = journal.tail(None)
    throttle_seq = max(r["seq"] for r in recs if r["kind"] == "spec_verify")
    late = [r for r in recs if r["kind"] == "speculate"
            and r["seq"] > throttle_seq]
    assert late == []


# --------------------------------------------------- fake engine + wire
def test_fake_runtime_journals_speculation_with_identical_stream():
    from ollamamq_tpu.engine.fake import FakeRuntime

    def drive(spec):
        ecfg = EngineConfig(model="test-tiny", spec=spec, spec_k=3)
        rt = FakeRuntime("test-tiny", ecfg)
        journal = Journal(capacity=4096)
        rt.journal = journal
        core = MQCore(None)
        req = Request(next(_IDS), "fk", "test-tiny", [1, 2, 3],
                      SamplingParams(max_tokens=10))
        rt.submit(req)
        for _ in range(64):
            if req.stats.finished_at:
                break
            rt.step(core)
        assert req.stats.finished_at
        text = "".join(i.text for i in req.stream.drain()
                       if i.kind == "token")
        return text, journal.tail(None)

    text_off, _ = drive(False)
    text_on, recs = drive(True)
    assert text_on == text_off  # stream content identical, pacing apart
    assert [r for r in recs if r["kind"] == "speculate"]
    assert [r for r in recs if r["kind"] == "spec_verify"]
    assert check_invariants(recs) == []


def test_op_spec_payload_roundtrip():
    """OP_SPEC's wire payload (the RAGGED payload + is_spec) packs and
    unpacks byte-exact — the worker decodes what the primary sent."""
    from ollamamq_tpu.engine.spmd import (OP_SPEC, _pack_payload,
                                          _unpack_payload, payload_spec)

    rng = np.random.default_rng(47)
    S, MP, W, T = 4, 8, 16, 24
    spec = payload_spec(OP_SPEC, T, 3, S, MP, W)
    values = []
    for shape, dt in spec:
        if np.dtype(dt) == np.uint32:
            values.append(rng.integers(0, 2**32, size=shape,
                                       dtype=np.uint32))
        elif np.dtype(dt) == np.float32:
            values.append(rng.random(shape).astype(np.float32))
        else:
            values.append(rng.integers(0, 100, size=shape).astype(dt))
    raw = _pack_payload([np.asarray(v, dt) for v, (_, dt)
                        in zip(values, spec)])
    out = _unpack_payload(raw, spec)
    assert len(out) == len(values)
    for a, b in zip(values, out):
        assert np.array_equal(np.asarray(a), b)


def test_wire_op_codes_keep_their_numbers_and_retired_ones_stay_free():
    """The opcode is what a worker of another build reads first: taking
    an op out (5 was the sequence-parallel prefill) must leave the others
    where they were and its number refused, not handed to a new op."""
    from ollamamq_tpu.engine import spmd

    codes = {name: getattr(spmd, name) for name in dir(spmd)
             if name.startswith("OP_")}
    assert codes == {"OP_SHUTDOWN": 0, "OP_DECODE": 3, "OP_ENCODE": 4,
                     "OP_RELOAD": 6, "OP_LOAD": 7, "OP_EVICT": 8,
                     "OP_EMBED": 9, "OP_RAGGED": 10, "OP_SPEC": 11}
    for retired in (1, 2, 5):
        with pytest.raises(ValueError, match="no payload spec"):
            spmd.payload_spec(retired, 64, 0, 4, 8, 16)
