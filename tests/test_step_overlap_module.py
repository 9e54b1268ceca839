"""tests/test_step_overlap.py, second file (a file is one worker's under `--dist
loadfile`): the `--spec` runtime whose proposer is the model's own prediction
module (PR 44) held to greedy decoding with a proposer forced right and forced
to alternate — counts, EOS, cancels, faults, two-token pages."""

import jax
import jax.numpy as jnp
import pytest

from ollamamq_tpu.engine.request import FinishReason
from ollamamq_tpu.ops.sampling import SamplingParams
from ollamamq_tpu.telemetry import journal as journal_mod
from ollamamq_tpu.testing.faults import FaultPlan
from test_step_overlap import (GREEDY, PANGU, PROPOSERS, _engine, _mtp_engine,
                               _mtp_wave, _prompt, _rt, both, drive,
                               force_proposer)


@pytest.fixture(scope="module")
def pangu_greedy():
    """{arrivals' key: what plain greedy decoding emits for them} — the
    trunk alone, no --spec, fused scans and all; one engine for the file."""
    eng, memo = _engine(PANGU), {}

    def of(arrivals):
        key = repr([(t, n, p, s.max_tokens) for t, n, p, s in arrivals])
        if key not in memo:
            mp = pytest.MonkeyPatch()
            try:
                memo[key] = drive(eng, arrivals, False, mp)[0]
            finally:
                mp.undo()
        return memo[key]
    return of


@pytest.fixture(scope="module")
def mtp():
    return _mtp_engine()


def _forced(monkeypatch, arrivals, greedy, proposer):
    force_proposer(monkeypatch, arrivals,
                   {n: out[0] for n, out in greedy.items()},
                   PROPOSERS[proposer])


def _accepted(samples):
    return (sum(s["mtp_accepted"] for s in samples),
            sum(s["mtp_drafts"] for s in samples))


@pytest.mark.parametrize("proposer", ["right", "mixed"])
def test_module_runtime_ends_on_exactly_max_tokens(mtp, pangu_greedy,
                                                   proposer, monkeypatch):
    """Two ids a row a step, `max_tokens` odd and even: a row that MAY
    reach its count inside the unsettled step rides the next one (its claim
    and its count taken for the longer case) and is dropped at settle where
    it did — never a token more or less than greedy decoding emits."""
    arr = _mtp_wave()
    greedy = pangu_greedy(arr)
    _forced(monkeypatch, arr, greedy, proposer)
    piped, settled, samples = both(mtp, arr, monkeypatch)
    assert piped == settled == greedy
    for (_, name, _, sampling) in arr:
        assert len(piped[name][0]) == sampling.max_tokens
        assert piped[name][2] == FinishReason.LENGTH
    accepted, drafts = _accepted(samples)
    assert accepted == drafts > 10 if proposer == "right" \
        else 0 < accepted < drafts
    assert sum(s["overlapped"] for s in samples) >= len(samples) // 2
    assert sum(s["len_carry_rows"] for s in samples) > 20
    if proposer == "right":
        # (an even count is reached by an accepted draft the host had not
        # seen when it composed the next step)
        assert sum(s["wasted_rows"] for s in samples) >= 1


@pytest.mark.parametrize("proposer", ["right", "mixed"])
def test_module_runtime_max_ctx_ends_by_count(pangu_greedy, proposer,
                                              monkeypatch):
    over = dict(max_pages_per_seq=4)  # a 32-token context
    arr = [(0, "a", _prompt(0, 20), SamplingParams(max_tokens=64, **GREEDY)),
           (1, "b", _prompt(1, 6), SamplingParams(max_tokens=64, **GREEDY))]
    greedy = pangu_greedy(arr)  # (in a context four times as long)
    _forced(monkeypatch, arr, greedy, proposer)
    piped, settled, samples = both(_mtp_engine(**over), arr, monkeypatch)
    assert piped == settled
    for name, n_prompt in (("a", 20), ("b", 6)):
        # the id that would leave the context full is the last one
        # (`_emit_row`), however the ids before it were paired into steps
        ids = piped[name][0]
        assert len(ids) == 32 - n_prompt and ids == greedy[name][0][:len(ids)]
    assert all(r[2] == FinishReason.LENGTH for r in piped.values())
    accepted, drafts = _accepted(samples)
    assert 0 < accepted <= drafts


def test_module_runtime_eos_as_an_accepted_draft_is_a_wasted_row(
        mtp, pangu_greedy, monkeypatch):
    """EOS arrives as the SECOND id of a verify span: the row already rides
    the next step, on a length the host took for one id. Same ids, STOP."""
    arr = _mtp_wave(outs=(20, 20, 20, 20, 20))
    greedy = pangu_greedy(arr)
    ids = greedy["u1"][0]
    # forced right, a request's ids leave in pairs after its first: an even
    # index is an accepted draft
    k = next(k for k in range(2, len(ids), 2) if ids[k] not in ids[:k])
    _forced(monkeypatch, arr, greedy, "right")
    monkeypatch.setattr(_rt(mtp).tokenizer, "eos_id", ids[k])
    piped, settled, samples = both(mtp, arr, monkeypatch)
    assert piped == settled
    assert piped["u1"][0] == ids[:k] and piped["u1"][2] == FinishReason.STOP
    for name, (got, _, reason) in piped.items():
        full = greedy[name][0]
        cut = full.index(ids[k]) if ids[k] in full else len(full)
        assert got == full[:cut], name
    assert sum(s["wasted_rows"] for s in samples) >= 1


def test_module_runtime_cancel_between_launch_and_settle(mtp, pangu_greedy,
                                                         monkeypatch):
    arr = _mtp_wave(outs=(16, 16, 16, 16, 16))
    greedy = pangu_greedy(arr)
    _forced(monkeypatch, arr, greedy, "mixed")

    def during():
        def hook(tick, reqs):
            if tick == 7 and "u1" in reqs:
                reqs["u1"].cancelled.set()
        return hook

    piped, settled, samples = both(mtp, arr, monkeypatch, during)
    for res in (piped, settled):
        ids, _, reason = res["u1"]
        assert reason == FinishReason.CANCELLED
        assert 0 < len(ids) < 16 and ids == greedy["u1"][0][:len(ids)]
        assert {n: r for n, r in res.items() if n != "u1"} == \
            {n: r for n, r in greedy.items() if n != "u1"}
    assert sum(s["wasted_rows"] for s in samples) >= 1


@pytest.mark.parametrize("site,at", [("spec_verify", 5), ("collect", 5)],
                         ids=["launch_N+1_while_N_unsettled",
                              "collect_N_while_N+1_launched"])
def test_module_runtime_fault_in_either_half_resumes_byte_identically(
        site, at, pangu_greedy, monkeypatch):
    arr = _mtp_wave(outs=(12, 13, 14, 15, 16))
    greedy = pangu_greedy(arr)
    _forced(monkeypatch, arr, greedy, "mixed")
    plan = FaultPlan([{"site": site, "kind": "exception", "at": [at]}])
    eng = _mtp_engine(plan=plan, retry_backoff_s=0.0)
    eng.recover_interval = 0.0
    out, samples = drive(eng, arr, False, monkeypatch)
    assert plan.stats()["injected"] == 1
    assert out == greedy  # no id lost, none doubled, same finish reasons
    assert sum(s["overlapped"] for s in samples) >= 3
    recs = eng.journal.tail(None)
    assert journal_mod.check_invariants(recs, starve_after=None) == []


@pytest.mark.parametrize("proposer", ["right", "mixed"])
def test_module_runtime_length_crosses_a_page_boundary_unsettled(
        pangu_greedy, proposer, monkeypatch):
    """Two-token pages: nearly every verify span ends in another page than
    it began in, and which one the host cannot say when it composes the
    step behind it. The program finds its write slots through the row's
    page-table row from its own length; the host claims for the longer
    case; the ids are greedy decoding's and every page comes back
    (`drive`), the journal's page accounts balanced at every event."""
    arr = _mtp_wave()
    greedy = pangu_greedy(arr)
    _forced(monkeypatch, arr, greedy, proposer)
    eng = _mtp_engine(page_size=2, max_pages_per_seq=64, num_pages=192)
    piped, settled, samples = both(eng, arr, monkeypatch)
    assert piped == settled == greedy
    accepted, drafts = _accepted(samples)
    assert 0 < accepted <= drafts
    assert sum(s["overlapped"] for s in samples) >= len(samples) // 2
    recs = eng.journal.tail(None)
    assert journal_mod.check_invariants(recs, starve_after=None) == []


def test_module_runtime_page_pressure_preempts_and_resumes(pangu_greedy,
                                                          monkeypatch):
    """A pool too small for four long streams: growth for the longer case
    fails while a verify span is unsettled, the step in flight is settled
    first (the length exact, the slots at rest), a victim is preempted and
    replays — the ids are greedy decoding's under both loops."""
    arr = _mtp_wave(outs=(30, 30, 30, 30, 30))
    greedy = pangu_greedy(arr)
    _forced(monkeypatch, arr, greedy, "mixed")
    eng = _mtp_engine(num_pages=16, retry_backoff_s=0.0)
    piped, settled, samples = both(eng, arr, monkeypatch)
    assert piped == settled == greedy
    assert _rt(eng).preempt_count >= 2  # (each loop met the pressure)
    assert sum(s["overlapped"] for s in samples) >= len(samples) // 2


@pytest.mark.parametrize("kind", ["dense", "ngram_spec", "module_held",
                                  "module"])
def test_only_the_module_runtime_lowers_the_length_carry(kind, monkeypatch):
    """`mq_ragged_step` of every runtime whose proposer is not the module
    takes and returns what it always did — weights, the packed buffer, two
    pools, the ring, `last_ids`, the per-slot state: no draft carry, no
    length carry, so nothing to derive a position from. The module runtime
    takes two more [S + 1] carries and returns both, donated."""
    from ollamamq_tpu.engine import engine as eng_mod

    monkeypatch.setattr(eng_mod, "_sp_note_compile",
                        lambda rt, site, key, cache, fn: cache.setdefault(
                            key, fn))
    eng = {"dense": lambda: _engine(),
           "ngram_spec": lambda: _engine(spec=True, spec_k=3),
           "module_held": lambda: _engine(PANGU),
           "module": _mtp_engine}[kind]()
    rt = _rt(eng)
    assert rt.mtp == (kind == "module")
    fn = rt._get_ragged_jit(16, rt.spec_k if rt.spec else 0,
                            (False, False, False))
    lay = rt.dims.ragged_layout(16)
    args = (rt.params, jnp.zeros((lay.size,), jnp.int32), rt.cache.kc, rt.cache.vc,
            rt.recent, rt.last_ids, rt.cache.slot_state)
    carries = (rt.draft_ids, rt.len_ids) if rt.mtp else ()
    assert rt.mtp or rt.draft_ids is rt.len_ids is None

    def shapes(xs):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), xs)

    lowered = fn.lower(*shapes(args + carries))
    n_in = len(jax.tree.leaves(args + carries))
    assert len(jax.tree.leaves(lowered.args_info)) == n_in
    outs = jax.tree.leaves(lowered.out_info)
    # ids, n_emit, two pools, the ring, last_ids (+ state) (+ two carries)
    assert len(outs) == 6 + len(jax.tree.leaves(rt.cache.slot_state)) \
        + len(carries)
    donated = [a.donated for a in jax.tree.leaves(lowered.args_info)]
    n_params = len(jax.tree.leaves(rt.params))
    assert not any(donated[:n_params + 1]) and all(donated[n_params + 1:])
