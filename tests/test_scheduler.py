"""Size-aware scheduling: the SchedulerPolicy seam (fcfs/srpt/edf), the
online output-length predictor, and the counterfactual promotion loop.

Load-bearing guarantees pinned here:
  - `fcfs` stays the default and is decision-for-decision identical to
    the pre-policy engine: a journal recorded under fcfs replays AND
    `simulate --scheduler fcfs` reproduces its decision_signature
    exactly;
  - `simulate` is deterministic — the same simulate twice yields an
    identical decision_signature — and srpt's counterfactual p99 TTFT
    on a bimodal trace does not lose to fcfs (and strictly wins on the
    pinned seed);
  - srpt anti-starvation aging: under a hostile stream of short
    requests a long request still finishes, the journal invariants
    (incl. the 50-batch starvation bound) stay clean, and
    `tools/journal.py check` exits 0;
  - single-request greedy streams are byte-identical across all three
    policies on a REAL runtime (ordering changes timing, never tokens);
  - predictor semantics: cold start predicts the max_tokens budget,
    EMAs converge toward observed lengths, accuracy is None before
    warmup ("acc n/a" in the TUI);
  - ordering semantics: srpt shortest-first, edf deadline-first, aging
    promotes a parked request to the queue front;
  - fail-fast validation: config.validate_scheduler, make_policy, and
    the CLI all reject an unknown policy loudly, pre-device;
  - observability: finish records carry predicted_tokens, `sched`
    records appear under srpt, scheduler_stats rides engine stats and
    the TUI brief.
"""

import collections
import itertools
import random

import jax.numpy as jnp
import pytest

from ollamamq_tpu.config import (MODEL_CONFIGS, SCHEDULERS, EngineConfig,
                                 validate_scheduler)
from ollamamq_tpu.core import MQCore
from ollamamq_tpu.engine.engine import ModelRuntime
from ollamamq_tpu.engine.fake import FakeEngine
from ollamamq_tpu.engine.request import Request
from ollamamq_tpu.engine.scheduler import (AGING_TICKS, OutputLenPredictor,
                                           make_policy)
from ollamamq_tpu.ops.sampling import SamplingParams
from ollamamq_tpu.telemetry.journal import (Journal, check_invariants,
                                            decision_signature)
from ollamamq_tpu.tools.journal import (counterfactual_stats, drive_chaos,
                                        record_chaos, replay_journal,
                                        simulate_journal)
from ollamamq_tpu.tools.journal import main as journal_main

_IDS = itertools.count(1)


def _req(user="u", n_prompt=8, max_tokens=8, deadline_ms=0.0):
    return Request(next(_IDS), user, "test-tiny", [1] * n_prompt,
                   SamplingParams(max_tokens=max_tokens,
                                  deadline_ms=deadline_ms))


# ------------------------------------------------------------- validation
def test_scheduler_validation_fails_fast():
    for name in SCHEDULERS:
        assert validate_scheduler(name) is None
    err = validate_scheduler("sjf")
    assert err is not None and "sjf" in err and "fcfs" in err
    with pytest.raises(ValueError, match="sjf"):
        make_policy(EngineConfig(model="test-tiny", scheduler="sjf"))
    # Engines reject it at construction, pre-device.
    with pytest.raises(ValueError):
        FakeEngine(EngineConfig(model="test-tiny", scheduler="sjf"),
                   blocklist_path=None)


def test_cli_rejects_unknown_scheduler_pre_device():
    from ollamamq_tpu.cli import main

    # Dies at the config validator (exit 2), before any jax/device work.
    assert main(["--scheduler", "warp", "--no-tui"]) == 2


# -------------------------------------------------------------- predictor
def test_predictor_cold_start_and_learning():
    p = OutputLenPredictor()
    # Cold start: the request's own budget is the honest guess.
    assert p.predict("a", 10, 64) == 64
    for _ in range(12):
        pred = p.predict("a", 10, 64)
        p.observe("a", 10, 8, predicted=pred)
    # EMAs converge toward the observed short outputs.
    assert p.predict("a", 10, 64) <= 16
    # A new user blends from the global EMA, not the 64 ceiling.
    assert p.predict("newcomer", 10, 64) <= 32
    # Predictions clamp into [1, max_tokens].
    assert p.predict("a", 10, 2) <= 2
    assert p.predict("a", 0, 1) >= 1


def test_predictor_accuracy_warmup_then_reports():
    p = OutputLenPredictor()
    assert p.accuracy() is None  # "acc n/a" before warmup
    for _ in range(OutputLenPredictor.WARMUP):
        p.observe("u", 4, 8, predicted=8)
    acc = p.accuracy()
    assert acc is not None and acc == pytest.approx(1.0)


# --------------------------------------------------------------- ordering
def test_srpt_orders_shortest_predicted_first():
    pol = make_policy(EngineConfig(model="test-tiny", scheduler="srpt"))
    long = _req(user="batch", max_tokens=64)
    short = _req(user="chat", max_tokens=2)
    dq = collections.deque([long, short])
    pol.reorder_pending(dq)
    assert list(dq) == [short, long]
    assert pol.decisions == 1
    # pack_order and order_admission agree.
    assert pol.pack_order([long, short]) == [short, long]
    batch = [(1, "batch", "m", long), (2, "chat", "m", short)]
    assert [t[3] for t in pol.order_admission(batch)] == [short, long]
    # fcfs never reorders.
    fcfs = make_policy(EngineConfig(model="test-tiny"))
    dq2 = collections.deque([long, short])
    fcfs.reorder_pending(dq2)
    assert list(dq2) == [long, short] and fcfs.decisions == 0


def test_srpt_aging_promotes_parked_request():
    pol = make_policy(EngineConfig(model="test-tiny", scheduler="srpt"))
    long = _req(user="batch", max_tokens=64)
    dq = collections.deque([long])
    pol.reorder_pending(dq)  # stamps first-seen tick
    for _ in range(AGING_TICKS):
        pol.on_admit_tick()
    fresh_short = _req(user="chat", max_tokens=2)
    dq = collections.deque([fresh_short, long])
    pol.reorder_pending(dq)
    # Fully aged => score 0 beats any fresh score, however short.
    assert list(dq) == [long, fresh_short]


def test_edf_deadline_first_then_srpt_fallback():
    pol = make_policy(EngineConfig(model="test-tiny", scheduler="edf"))
    tight = _req(user="slo", max_tokens=64, deadline_ms=50.0)
    loose = _req(user="slo", max_tokens=64, deadline_ms=5000.0)
    free_short = _req(user="chat", max_tokens=2)
    free_long = _req(user="batch", max_tokens=64)
    dq = collections.deque([free_long, loose, free_short, tight])
    pol.reorder_pending(dq)
    # Deadlines first (earliest wins), deadline-less in srpt order.
    assert list(dq) == [tight, loose, free_short, free_long]


def test_victim_keys_per_policy():
    fcfs = make_policy(EngineConfig(model="test-tiny"))
    srpt = make_policy(EngineConfig(model="test-tiny", scheduler="srpt"))
    edf = make_policy(EngineConfig(model="test-tiny", scheduler="edf"))
    long = _req(user="batch", max_tokens=64)
    short = _req(user="chat", max_tokens=2)
    dl = _req(user="slo", max_tokens=64, deadline_ms=50.0)
    # fcfs: the legacy key, fair-share standing then age.
    assert fcfs.victim_key(long, 3) == (3, long.stats.enqueued_at)
    # srpt: the longest predicted remaining loses its slot first.
    assert srpt.victim_key(long, 0) > srpt.victim_key(short, 99)
    # edf: deadline-less victims before deadline-carrying ones.
    assert edf.victim_key(long, 0) > edf.victim_key(dl, 99)


# ------------------------------------------- fcfs identity + simulate
def test_fcfs_bimodal_record_replays_and_simulates_identically(tmp_path):
    path = str(tmp_path / "bimodal.jsonl")
    journal = record_chaos(path, seed=5, requests=40, trace="bimodal")
    recs = journal.tail(None)
    assert check_invariants(recs) == []
    # No faults in the bimodal trace: the stream is pure scheduling.
    assert not {"retry", "poison", "shed"} & {r["kind"] for r in recs}
    ok, _rec, _rep, div = replay_journal(path)
    assert ok, f"fcfs bimodal replay diverged at {div}"
    # simulate under fcfs IS a replay: identical decision stream.
    rec, sim = simulate_journal(path, "fcfs")
    assert decision_signature(rec) == decision_signature(sim)
    # finish records journal the prediction next to the outcome.
    fins = [r for r in recs if r["kind"] == "finish"]
    assert fins and all("predicted_tokens" in r for r in fins)


def test_simulate_srpt_deterministic_and_wins_p99_ttft(tmp_path):
    path = str(tmp_path / "bimodal.jsonl")
    record_chaos(path, seed=5, requests=40, trace="bimodal")
    rec, sim1 = simulate_journal(path, "srpt")
    _, sim2 = simulate_journal(path, "srpt")
    # Determinism: same simulate twice => identical decision signature.
    assert decision_signature(sim1) == decision_signature(sim2)
    assert check_invariants(sim1) == []
    base = counterfactual_stats(rec)
    cf = counterfactual_stats(sim1)
    # Same work served, counterfactually better tail latency (strict
    # win on this pinned seed; the acceptance gate is "does not lose").
    assert cf["served"] == base["served"] == 40
    assert cf["ttft_p99"] < base["ttft_p99"]
    assert cf["ttft_mean"] < base["ttft_mean"]
    # The policy's ordering decisions are explainable from the journal.
    scheds = [r for r in sim1 if r["kind"] == "sched"]
    assert scheds and all(r["policy"] == "srpt" for r in scheds)
    # edf on a deadline-less trace degrades to srpt order and stays
    # invariant-clean too.
    _, sime = simulate_journal(path, "edf")
    assert check_invariants(sime) == []
    assert counterfactual_stats(sime)["served"] == 40


def test_simulate_cli_reports_and_exits_clean(tmp_path, capsys):
    path = str(tmp_path / "bimodal.jsonl")
    record_chaos(path, seed=5, requests=32, trace="bimodal")
    assert journal_main(["simulate", path, "--scheduler", "srpt"]) == 0
    out = capsys.readouterr().out
    assert "ttft_p99" in out and "decision_signature" in out
    assert "invariant-clean" in out


def test_simulate_runs_over_live_spilled_journal(tmp_path):
    """A LIVE engine's --journal-file spill (no scenario meta, raw
    loop-iteration ticks with a big idle offset and dead gaps) is
    simulatable: arrivals are tick-normalized relative to the first one
    and the engine shape is read off the journal_meta header."""
    import json

    from ollamamq_tpu.tools.journal import (MAX_ARRIVAL_GAP_TICKS,
                                            normalize_arrival_ticks)

    # Tick normalization: rebase + gap cap, order/coincidence kept.
    arr = [{"tick": 100_000}, {"tick": 100_000}, {"tick": 100_007},
           {"tick": 190_000}]
    norm = normalize_arrival_ticks(arr)
    assert [a["tick"] for a in norm] == [0, 0, 7, 7 + MAX_ARRIVAL_GAP_TICKS]

    # A hand-rolled "live spill": journal_meta header (the live engine's
    # shape), no scenario block, enqueue ticks offset by ~1e5.
    path = str(tmp_path / "live.jsonl")
    with open(path, "w") as f:
        f.write(json.dumps({"journal_meta": {
            "version": 1, "model": "test-tiny", "max_slots": 2,
            "num_pages": 64}}) + "\n")
        for i in range(6):
            f.write(json.dumps({
                "seq": i, "t": 0.0, "tick": 100_000 + i * 5_000,
                "kind": "enqueue", "req_id": i + 1, "user": f"u{i % 2}",
                "model": "test-tiny", "n_prompt": 4 + i,
                "max_tokens": 4, "queued": 1}) + "\n")
    rec, sim = simulate_journal(path, "srpt")
    stats = counterfactual_stats(sim)
    assert stats["served"] == 6  # every live arrival re-drove to finish
    assert check_invariants(sim) == []
    # Deterministic over live spills too.
    _, sim2 = simulate_journal(path, "srpt")
    assert decision_signature(sim) == decision_signature(sim2)
    # The CLI path exercises the same branch.
    assert journal_main(["simulate", path, "--scheduler", "fcfs"]) == 0


# --------------------------------------------------- starvation fairness
@pytest.mark.parametrize("seed", [0, 1])
def test_srpt_hostile_short_stream_never_starves_long(tmp_path, seed):
    """Fuzz: one long request enqueued first, then a relentless stream
    of short requests. Under srpt the long must still finish within the
    aging bound — the journal invariants (incl. no-starvation-past-50-
    batches) stay clean and `tools/journal.py check` exits 0."""
    rng = random.Random(seed)
    arrivals = [{"tick": 0, "user": "longy", "n_prompt": 30,
                 "max_tokens": 16}]
    for t in range(60):
        for _ in range(1 + (rng.random() < 0.5)):
            arrivals.append({"tick": t, "user": f"c{rng.randrange(4)}",
                             "n_prompt": rng.randrange(3, 10),
                             "max_tokens": 2})
    engine = {"max_slots": 2, "max_queued": 0, "max_queued_per_user": 0,
              "step_retries": 1, "scheduler": "srpt"}
    path = str(tmp_path / f"hostile{seed}.jsonl")
    journal = Journal(capacity=65536, path=path,
                      meta={"scenario": {"engine": engine}})
    drive_chaos(arrivals, {"seed": 0, "faults": []}, engine, journal)
    recs = journal.tail(None)
    long_rids = {r["req_id"] for r in recs
                 if r["kind"] == "enqueue" and r.get("max_tokens") == 16}
    assert len(long_rids) == 1
    fins = [r for r in recs if r["kind"] == "finish"
            and r["req_id"] in long_rids]
    assert fins and fins[-1]["tokens"] == 16, "long request starved"
    assert check_invariants(recs) == []
    assert journal_main(["check", path]) == 0


# ------------------------------------------------------- byte identity
def _drive_one(policy_name: str):
    """One greedy request through a REAL runtime under `policy_name`;
    returns its generated ids."""
    from ollamamq_tpu.engine.request import FinishReason  # noqa: F401

    ecfg = EngineConfig(model="test-tiny", max_slots=2, num_pages=64,
                        page_size=8, max_pages_per_seq=8,
                        decode_steps_per_iter=2, scheduler=policy_name)
    rt = ModelRuntime("test-tiny", MODEL_CONFIGS["test-tiny"], ecfg,
                      dtype=jnp.float32)
    rt.tokenizer.eos_id = -1
    rt.policy = make_policy(ecfg)
    core = MQCore(None)
    req = Request(77, "alice", "test-tiny", list(range(3, 20)),
                  SamplingParams(max_tokens=8))
    req._inc_decode = rt.tokenizer.make_incremental_decoder()
    rt.pending_prefill.append(req)
    guard = 0
    while not req.stats.finished_at:
        rt.policy.on_admit_tick()
        rt.step_ragged(core)
        if any(r is not None for r in rt.slot_req):
            rt.step_decode(core, k_steps=2)
        guard += 1
        assert guard < 500, f"single-request drive wedged ({policy_name})"
    return list(req.generated_ids)


def test_greedy_streams_byte_identical_across_policies():
    """Ordering must never change tokens — only timing. One greedy
    request produces the exact same ids under fcfs, srpt, and edf."""
    streams = {name: _drive_one(name) for name in SCHEDULERS}
    assert streams["fcfs"] == streams["srpt"] == streams["edf"]
    assert len(streams["fcfs"]) == 8


@pytest.mark.parametrize("policy_name", SCHEDULERS)
def test_bimodal_trace_on_a_real_runtime_changes_order_never_tokens(
        policy_name):
    """A long batch request queued AHEAD of a burst of short chat ones,
    over a 2-slot REAL runtime: under every policy each stream runs
    exactly its max_tokens (eos off — a shorter one was truncated in
    silence) and the journal is invariant-clean, the anti-starvation
    bound included. Under fcfs the long is seated first; srpt and edf
    seat a short ahead of it — the order that wins p99 TTFT in
    `simulate`, read here off the journal and not off a clock."""
    ecfg = EngineConfig(model="test-tiny", max_slots=2, num_pages=256,
                        page_size=8, max_pages_per_seq=16,
                        decode_steps_per_iter=2, max_batch_tokens=128,
                        token_granule=8, scheduler=policy_name)
    rt = ModelRuntime("test-tiny", MODEL_CONFIGS["test-tiny"], ecfg,
                      dtype=jnp.float32)
    rt.tokenizer.eos_id = -1
    rt.policy = make_policy(ecfg)
    rt.journal = Journal(capacity=65536)
    core = MQCore(None)
    rng = random.Random(1234)
    arrivals = [("batch0", 48, 48)] + [(f"chat{i % 4}", 8, 4)
                                       for i in range(7)]
    reqs = []
    for user, n_prompt, max_tokens in arrivals:
        req = Request(next(_IDS), user, "test-tiny",
                      [rng.randrange(3, 500) for _ in range(n_prompt)],
                      SamplingParams(max_tokens=max_tokens))
        req._inc_decode = rt.tokenizer.make_incremental_decoder()
        reqs.append(req)
        rt.pending_prefill.append(req)
    guard = 0
    while not all(r.stats.finished_at for r in reqs):
        rt.policy.on_admit_tick()
        rt.step_ragged(core)
        if any(r is not None for r in rt.slot_req):
            rt.step_decode(core, k_steps=2)
        guard += 1
        assert guard < 4000, f"bimodal drive wedged ({policy_name})"
    assert [len(r.generated_ids) for r in reqs] == \
        [r.sampling.max_tokens for r in reqs]
    recs = rt.journal.tail(None)
    assert check_invariants(recs) == []
    seated = [r["req_id"] for r in recs if r["kind"] == "install"]
    assert sorted(seated) == sorted(r.req_id for r in reqs)
    long_first = seated[0] == reqs[0].req_id
    assert long_first == (policy_name == "fcfs"), seated


# ---------------------------------------------------------- observability
def test_engine_stats_and_tui_brief_carry_scheduler(tmp_path):
    from ollamamq_tpu.admin.tui import _engine_stats_brief

    eng = FakeEngine(EngineConfig(model="test-tiny", scheduler="srpt"),
                     models={"test-tiny": None}, blocklist_path=None)
    ss = eng.scheduler_stats()
    assert ss["policy"] == "srpt"
    assert ss["pred_accuracy"] is None  # "acc n/a" before warmup
    assert eng.stats()["scheduler"]["policy"] == "srpt"
    brief = _engine_stats_brief(eng)
    assert brief["sched"]["policy"] == "srpt"
    assert brief["sched"]["pred_accuracy"] is None
    # Default remains fcfs.
    eng2 = FakeEngine(EngineConfig(model="test-tiny"),
                      models={"test-tiny": None}, blocklist_path=None)
    assert eng2.stats()["scheduler"]["policy"] == "fcfs"


def test_predictor_warms_through_served_requests():
    """Serving real (fake) traffic feeds the predictor: finishes update
    observation counts and eventually the accuracy gauge."""
    eng = FakeEngine(EngineConfig(model="test-tiny", scheduler="srpt"),
                     models={"test-tiny": None}, blocklist_path=None)
    rt = eng.runtimes["test-tiny"]
    for i in range(10):
        req = eng.enqueue_request("warm", "", "test-tiny",
                                  prompt_tokens=[1] * 5,
                                  sampling=SamplingParams(max_tokens=4))
        guard = 0
        while not req.stats.finished_at:
            eng._admit()
            rt.step(eng.core)
            guard += 1
            assert guard < 100
    ss = eng.scheduler_stats()
    assert ss["pred_observed"] == 10
    assert ss["pred_accuracy"] is not None
