"""One step kept in flight (PR 28): the engine thread launches step N+1
while the chip runs step N and settles N behind it. The pipelined loop
and the SAME loop forced to settle every step in the tick that launched
it (`ModelRuntime.may_overlap` answering False: the seam a speculating
runtime uses, not a user option) must give bit-identical id streams,
texts and finish reasons; a row that rode a step after its request had
finished is dropped and counted, its pages freed once; a fault in either
half of the pipeline resumes every stream byte-identically.

Ticks are driven by hand (`eng._loop_once()`, no engine thread), so an
arrival, a cancel or a fault lands between the same two steps in both
loops.

Since PR 44 a `--spec` runtime whose proposer is the model's own
prediction module runs the same pipeline: a verify span emits one id or
two and only the device knows which until the step is collected, so a
row's length rides on the device (`len_ids`) and the host composes the
next step on the range it lies in — pages for the longer case, counts
against the longer case. The second half of this file holds that runtime
to greedy decoding with a proposer forced right and forced to alternate
(a device-side test double: two ids a row a step)."""

import jax.numpy as jnp
import numpy as np
import pytest

from ollamamq_tpu.config import EngineConfig
from ollamamq_tpu.engine.engine import ModelRuntime, TPUEngine
from ollamamq_tpu.engine.request import FinishReason
from ollamamq_tpu.ops.sampling import SamplingParams
from ollamamq_tpu.telemetry import journal as journal_mod
from ollamamq_tpu.telemetry.stepprof import PROFILER
from ollamamq_tpu.testing.faults import FaultPlan

BASE = dict(max_slots=4, num_pages=96, page_size=8, max_pages_per_seq=16,
            max_batch_tokens=32,
            token_granule=8, decode_steps_per_iter=4)


def _engine(model="test-tiny", plan=None, **over):
    cfg = dict(BASE, model=model)
    cfg.update(over)
    return TPUEngine(EngineConfig(fault_plan=plan, **cfg),
                     models={model: None}, blocklist_path=None,
                     dtype=jnp.float32)


@pytest.fixture(scope="module")
def dense():
    return _engine()


@pytest.fixture(scope="module")
def cached():
    return _engine(prefix_cache=True)


def _rt(eng):
    return next(iter(eng.runtimes.values()))


def _prompt(i, n):
    """n ids, distinct per request, inside test-tiny's 512-id vocabulary
    and away from the tokenizer's specials."""
    return [10 + (7 * i + 3 * j) % 200 for j in range(n)]


def drive(eng, arrivals, settle_every_step, monkeypatch, during=None):
    """arrivals: [(tick, name, prompt_ids, SamplingParams)]. Ticks the loop
    by hand until every request finished; `during(tick, reqs)` runs before
    each tick. Returns ({name: (ids, text, finish_reason)}, samples)."""
    PROFILER.reset()
    rt = _rt(eng)
    reqs, tick, todo = {}, 0, sorted(arrivals, key=lambda a: a[0])
    with monkeypatch.context() as m:
        if settle_every_step:
            m.setattr(ModelRuntime, "may_overlap", lambda self: False)
        while todo or not all(r.stats.finished_at for r in reqs.values()):
            while todo and todo[0][0] <= tick:
                _, name, prompt, sampling = todo.pop(0)
                reqs[name] = eng.enqueue_request(
                    name, "", rt.name, prompt_tokens=list(prompt),
                    sampling=sampling)
            if during is not None:
                during(tick, reqs)
            eng._loop_once()
            tick += 1
            assert tick < 3000, {n: len(r.generated_ids)
                                 for n, r in reqs.items()}
    eng._settle_all()
    out = {}
    for name, r in reqs.items():
        items = []
        while True:
            it = r.stream.get(timeout=0)
            if it is None:
                break
            items.append(it)
        assert items[-1].kind in ("done", "error"), name
        ids = [t for i in items for t in i.token_ids]
        # (the id that completed a stop string is counted, never pushed)
        assert ids == list(r.generated_ids)[:len(ids)], name
        assert len(r.generated_ids) - len(ids) <= bool(r.sampling.stop), name
        out[name] = (ids, "".join(i.text for i in items if i.kind == "token"),
                     items[-1].finish_reason)
    # At rest: every page back, every slot empty, nothing reserved.
    assert rt.cache.alloc.used_pages == 0, rt.cache.alloc.used_pages
    assert all(r is None for r in rt.slot_req) and not rt.reserved_slots
    assert not rt._ahead.any()
    return out, PROFILER.tail()


def both(eng, arrivals, monkeypatch, during=None):
    """The scenario under the pipelined loop and under the settled one
    (`during`: a factory, one hook a drive)."""
    out = []
    for settle_every_step in (False, True):
        if eng.ecfg.prefix_cache:
            eng.prefix_cache_flush()
        out.append(drive(eng, arrivals, settle_every_step, monkeypatch,
                         during() if during is not None else None))
    (piped, samples), (settled, serial) = out
    assert not any(s.get("overlapped") for s in serial)
    return piped, settled, samples


def _wave(sampling, n=6, lens=(5, 40, 9, 23, 14, 31), every=2):
    """n requests over 4 slots, arriving every `every` ticks: prefill
    spans of several lengths ride beside decode rows, the token budget
    (32) cuts long prompts into spans, slots free and refill, and between
    waves decode runs as fused scans — ragged <-> scan both ways."""
    return [(every * i, f"u{i}", _prompt(i, lens[i % len(lens)]),
             sampling(i)) for i in range(n)]


def _greedy(i):
    return SamplingParams(max_tokens=9 + 2 * i)


def _seeded(i):
    return SamplingParams(max_tokens=8 + i, temperature=0.9, top_k=40,
                          top_p=0.95, seed=1234 + i)


def _short(i):
    return SamplingParams(max_tokens=1 + i % 3)


@pytest.mark.parametrize("sampling", [_greedy, _seeded, _short],
                         ids=["greedy", "seeded_temperature", "max_tokens"])
def test_streams_are_bit_identical(dense, sampling, monkeypatch):
    piped, settled, samples = both(dense, _wave(sampling), monkeypatch)
    assert piped == settled
    assert all(r[2] == FinishReason.LENGTH for r in piped.values())
    modes = {s["mode"] for s in samples}
    if sampling is not _short:
        assert modes == {"ragged", "decode"}  # both step kinds ran
    ragged = [s for s in samples if s["mode"] == "ragged"]
    # The pipelined loop did overlap: nearly every ragged step was
    # launched behind an unsettled one, and a scan behind a ragged step.
    assert sum(s["overlapped"] for s in ragged) >= len(ragged) // 2, ragged
    assert not any(s["wasted_rows"] for s in samples)  # all ends by count


def test_a_final_prefill_span_becomes_a_decode_row_unseen(dense, monkeypatch):
    """A prompt cut into three spans; the step after its last span serves
    it as a decode row whose input id the host has not seen (the carry)."""
    arr = [(0, "long", _prompt(1, 70), SamplingParams(max_tokens=6)),
           (0, "short", _prompt(2, 4), SamplingParams(max_tokens=12))]
    piped, settled, samples = both(dense, arr, monkeypatch)
    assert piped == settled
    assert len(piped["long"][0]) == 6 and len(piped["short"][0]) == 12
    assert sum(s["overlapped"] for s in samples) >= 3


def test_eos_mid_stream_is_a_wasted_row_not_a_token(dense, monkeypatch):
    """EOS is seen only when the step is settled: the row already rides
    the next step, whose output for it is dropped. Same ids, same STOP."""
    rt = _rt(dense)
    base, _ = drive(dense, _wave(_greedy), False, monkeypatch)
    ids = base["u1"][0]
    k = next(k for k in range(3, len(ids)) if ids[k] not in ids[:k])
    eos = ids[k]  # an id u1 first samples mid-stream is EOS for everyone
    monkeypatch.setattr(rt.tokenizer, "eos_id", eos)
    piped, settled, samples = both(dense, _wave(_greedy), monkeypatch)
    assert piped == settled
    assert piped["u1"][0] == ids[:k]
    assert piped["u1"][2] == FinishReason.STOP
    assert sum(s["wasted_rows"] for s in samples) >= 1


def test_a_stop_string_that_fires_one_step_late(dense, monkeypatch):
    """Only the detokenised text can say so, and that is read when the step
    is settled — behind the next launch, which the row already rides."""
    def arr(stop=()):
        # b's prompt takes four spans, so a's first ids come from ragged
        # steps, each launched before the one before it was settled.
        return [(0, "a", _prompt(0, 9),
                 SamplingParams(max_tokens=16, stop=stop)),
                (1, "b", _prompt(1, 100), SamplingParams(max_tokens=12))]

    # The tiny model's ids are mostly unprintable bytes: give every id a
    # text of its own, so a stop string names one sampled id.
    monkeypatch.setattr(_rt(dense).tokenizer, "make_incremental_decoder",
                        lambda: lambda tok: f"{tok},")
    base, _ = drive(dense, arr(), False, monkeypatch)
    ids, text, _ = base["a"]
    j = next(j for j in (2, 3) if ids[j] not in ids[:j])
    stop = f",{ids[j]},"
    cut = text.index(stop)
    piped, settled, samples = both(dense, arr((stop,)), monkeypatch)
    assert piped == settled
    assert piped["a"][1] == text[:cut] and piped["a"][2] == FinishReason.STOP
    assert piped["b"] == base["b"]
    assert sum(s["wasted_rows"] for s in samples) == 1


def test_max_ctx_ends_a_request_by_count(monkeypatch):
    eng = _engine(max_pages_per_seq=4)  # a 32-token context
    arr = [(0, "a", _prompt(0, 20), SamplingParams(max_tokens=64)),
           (1, "b", _prompt(1, 6), SamplingParams(max_tokens=64))]
    piped, settled, samples = both(eng, arr, monkeypatch)
    assert piped == settled
    # Both ran into the context's end, far short of max_tokens.
    assert 5 < len(piped["a"][0]) <= 11 and 5 < len(piped["b"][0]) <= 25
    assert all(r[2] == FinishReason.LENGTH for r in piped.values())
    assert not any(s["wasted_rows"] for s in samples)


def test_cancel_between_launch_and_settle(dense, monkeypatch):
    """The cancel lands while a step holding the request's row is in
    flight: that row's output is dropped, the ids before it stand, the
    others' streams do not move."""
    base, _ = drive(dense, _wave(_greedy), False, monkeypatch)

    def during():
        def hook(tick, reqs):
            if tick == 9 and "u1" in reqs:
                reqs["u1"].cancelled.set()
        return hook

    piped, settled, samples = both(dense, _wave(_greedy), monkeypatch, during)
    for res in (piped, settled):
        ids, _, reason = res["u1"]
        assert reason == FinishReason.CANCELLED
        assert 0 < len(ids) < len(base["u1"][0])
        assert ids == base["u1"][0][:len(ids)]
        assert {n: r for n, r in res.items() if n != "u1"} == \
            {n: r for n, r in base.items() if n != "u1"}
    assert sum(s["wasted_rows"] for s in samples) >= 1


def test_a_prefix_cache_hit_under_overlap(cached, monkeypatch):
    shared = _prompt(9, 48)
    arr = [(0, "first", shared + [300, 301], SamplingParams(max_tokens=8)),
           (14, "again", shared + [302, 303, 304],
            SamplingParams(max_tokens=8)),
           (15, "other", _prompt(3, 12), SamplingParams(max_tokens=10))]
    rt = _rt(cached)
    hits0 = rt.cache.prefix_cache.stats()["hits"]
    piped, settled, _ = both(cached, arr, monkeypatch)
    assert piped == settled
    assert rt.cache.prefix_cache.stats()["hits"] >= hits0 + 2  # once a loop


@pytest.mark.parametrize("model,over", [
    ("test-tiny-moe", {}),
    ("test-tiny-gqa", {"tp": 2}),
], ids=["moe", "tp2_cpu_mesh"])
def test_other_runtimes_overlap_and_agree(model, over, monkeypatch):
    eng = _engine(model, **over)
    piped, settled, samples = both(eng, _wave(_greedy, n=5), monkeypatch)
    assert piped == settled
    assert sum(s["overlapped"] for s in samples) >= 3
    if model == "test-tiny-moe":
        assert all("moe_assignments" in s for s in samples)


def test_ngram_spec_runtime_never_overlaps(monkeypatch):
    """Its next composition needs the ids on the host (the n-gram
    proposer reads generated_ids): every step is settled in the tick that
    launched it."""
    eng = _engine("test-tiny", spec=True, spec_k=3)
    arr = [(i, f"u{i}", (_prompt(i, 6) * 3)[:14 + i],
            SamplingParams(max_tokens=10)) for i in range(3)]
    assert not _rt(eng).may_overlap() and _rt(eng).len_ids is None
    out, samples = drive(eng, arr, False, monkeypatch)
    assert samples and not any(s.get("overlapped") for s in samples)
    assert not any("len_carry_rows" in s for s in samples)
    assert not any(s.get("wasted_rows") for s in samples)
    assert all(len(r[0]) == 10 for r in out.values())


@pytest.mark.parametrize("module", [False, True], ids=["dense", "module"])
def test_both_loops_compile_the_same_programs(module, monkeypatch):
    """The carry changed the step programs' signatures, not their number:
    same compile keys either way, one per (rung, flags) and (k, flags) —
    and for the module runtime, whose every step is a ragged one with its
    one draft cap, one per rung."""
    keys = []
    for settle in (False, True):
        eng = _mtp_engine() if module else _engine()
        _, samples = drive(eng, _mtp_wave() if module else _wave(_greedy),
                           settle, monkeypatch)
        # A step that pays a compile holds the thread for seconds: the
        # step in flight is settled first, its ids do not wait behind it.
        paid = [s for s in samples if s["compiled"]]
        assert paid and not any(s["overlapped"] for s in paid)
        rt = _rt(eng)
        keys.append((sorted(map(str, rt._prefill_jits)),
                     sorted(map(str, rt._decode_jits))))
    assert keys[0] == keys[1]
    assert all(k.startswith("('ragged',") for k in keys[0][0])
    if module:
        assert not keys[0][1]  # no fused scan under the module
        assert all(", 1, (" in k for k in keys[0][0])  # k_cap 1, always


# --------------------------------------------------------------- hygiene
@pytest.mark.parametrize("cache", [False, True],
                         ids=["pages_reused", "pages_published"])
def test_a_late_finish_frees_its_pages_once_and_they_serve_again(
        cache, monkeypatch):
    """The dropped row's KV write lands one position past its sequence's
    end, in pages that are released only when the step before is settled
    — after the step holding it was dispatched. The next owner of those
    pages (or, with the prefix cache on, the next reader of the prompt
    pages the finish published) gets exactly the output it gets on an
    idle engine; the journal's invariants hold (pages conserved at every
    page event, no slot double-assignment); `wasted_rows` counted it."""
    eng = _engine(max_slots=2, prefix_cache=cache)
    rt = _rt(eng)
    a_prompt = _prompt(0, 20)
    probe = a_prompt + [300, 301, 302] if cache else _prompt(5, 11)
    alone, _ = drive(eng, [(0, "probe", probe,
                            SamplingParams(max_tokens=7))], False,
                     monkeypatch)
    base, _ = drive(eng, [(0, "a", a_prompt, SamplingParams(max_tokens=20))],
                    False, monkeypatch)
    ids = base["a"][0]
    k = next(k for k in range(2, 4) if ids[k] not in ids[:k])
    monkeypatch.setattr(rt.tokenizer, "eos_id", ids[k])
    if cache:
        eng.prefix_cache_flush()
    # b's prompt takes four spans: a's EOS is sampled in a ragged step, and
    # a rides the step launched behind it.
    arr = [(0, "a", a_prompt, SamplingParams(max_tokens=20)),
           (1, "b", _prompt(1, 100), SamplingParams(max_tokens=12)),
           (3, "probe", probe, SamplingParams(max_tokens=7))]
    seq0 = eng.journal.snapshot()["seq"]
    hits0 = rt.cache.prefix_cache.stats()["hits"] if cache else 0
    out, samples = drive(eng, arr, False, monkeypatch)
    assert out["a"][2] == FinishReason.STOP and out["a"][0] == ids[:k]
    assert out["probe"] == alone["probe"]
    assert sum(s["wasted_rows"] for s in samples) == 1
    recs = [r for r in eng.journal.tail(None) if r["seq"] > seq0]
    assert journal_mod.check_invariants(recs) == []
    if cache:
        assert rt.cache.prefix_cache.stats()["hits"] == hits0 + 1  # a's pages
    else:
        frees = [r for r in recs if r["kind"] == "page_free"]
        assert len(frees) == 3  # one per request, none twice


@pytest.mark.parametrize("site,at", [("ragged", 4), ("collect", 4),
                                     ("collect", 7), ("decode", 2)],
                         ids=["launch_N+1_while_N_unsettled",
                              "collect_N_while_N+1_launched",
                              "collect_later", "scan_launch"])
def test_a_fault_in_either_half_resumes_byte_identically(site, at, dense,
                                                         monkeypatch):
    base, _ = drive(dense, _wave(_greedy), False, monkeypatch)
    plan = FaultPlan([{"site": site, "kind": "exception", "at": [at]}])
    eng = _engine(plan=plan, retry_backoff_s=0.0)
    eng.recover_interval = 0.0
    out, _ = drive(eng, _wave(_greedy), False, monkeypatch)
    assert plan.stats()["injected"] == 1
    assert out == base  # no id lost, none doubled, same finish reasons
    if site != "decode":  # (a killed runtime's seats are not journaled)
        recs = eng.journal.tail(None)
        assert journal_mod.check_invariants(recs, starve_after=None) == []


def test_export_request_during_overlap_sees_settled_state(dense,
                                                          monkeypatch):
    """An engine call (here: a migration snapshot) runs with every runtime
    at rest: its cursor and last token are the emitted stream's."""
    rt = _rt(dense)
    seen = {}

    class _Done:
        def set(self):
            pass

    def during(tick, reqs):
        def snap():
            req = reqs["u0"]
            assert rt.inflight is None
            handle, blob = rt.export_request(req.req_id)
            # a snapshot only: seat the request again where it was
            rt.slot_req[handle["slot"]] = req
            rt.reserved_slots.discard(handle["slot"])
            seen.update(kv_len=blob["kv_len"], last=blob["last_token"],
                        ids=list(req.generated_ids),
                        n_prompt=len(req.prompt_tokens))

        if tick == 8:
            assert rt.inflight is not None  # a step IS in flight
            dense._engine_calls.append((snap, _Done(), {}))

    arr = [(0, "u0", _prompt(0, 10), SamplingParams(max_tokens=40)),
           (2, "u1", _prompt(1, 30), SamplingParams(max_tokens=12)),
           (6, "u2", _prompt(2, 40), SamplingParams(max_tokens=12))]
    drive(dense, arr, False, monkeypatch, during)
    assert seen["ids"] and seen["last"] == seen["ids"][-1]
    assert seen["kv_len"] == seen["n_prompt"] + len(seen["ids"]) - 1


# ------------------------------------------------- the module runtime (PR 44)
PANGU = "test-tiny-openpangu"
GREEDY = dict(temperature=0.0, repeat_penalty=1.0)
LMAX = 256  # longer than any prompt + output of these tests
PROPOSERS = {"right": lambda at: at >= 0, "wrong": lambda at: at < 0,
             "mixed": lambda at: at % 3 != 0}


def _mtp_engine(plan=None, **over):
    return _engine(PANGU, plan=plan, spec=True, spec_k=1,
                   spec_min_accept=0.0, **over)


def _mtp_wave(outs=(7, 8, 9, 10, 12), lens=(5, 40, 9, 23, 31), every=2):
    """Greedy, unpenalised requests (the rows the module drafts for) over
    four slots; `outs`: odd and even `max_tokens`."""
    return [(every * i, f"u{i}", _prompt(i, lens[i % len(lens)]),
             SamplingParams(max_tokens=out, **GREEDY))
            for i, out in enumerate(outs)]


def force_proposer(monkeypatch, arrivals, greedy_ids, right):
    """The proposer's test double, ON THE DEVICE like the proposer: after
    every launch each seated slot's draft is overwritten with the id greedy
    decoding has at the position after the slot's next input token — read
    at the program's own length carry, which the host may not know yet —
    where `right(position)` holds, and with another id elsewhere."""
    full = {name: list(prompt) + list(greedy_ids[name])
            for _, name, prompt, _ in arrivals}
    real = ModelRuntime.step_ragged_launch

    def launch(self, core):
        h = real(self, core)
        if h is not None and self.mtp:
            S = len(self.slot_req)
            table = np.zeros((S + 1, LMAX), np.int32)
            for slot, req in enumerate(self.slot_req):
                if req is not None:
                    table[slot, :len(full[req.user])] = full[req.user]
            at = jnp.clip(self.len_ids + 1, 0, LMAX - 1)
            ids = jnp.asarray(table)[jnp.arange(S + 1), at]
            self.draft_ids = jnp.where(right(at), ids, (ids + 1) % 500)
        return h

    monkeypatch.setattr(ModelRuntime, "step_ragged_launch", launch)
