"""tests/test_step_overlap.py, third file (a file is one worker's under `--dist
loadfile`): a step's ids start for the host when the step is LAUNCHED (PR 70).
`StepInFlight.futures` asks for the transfer of every array `step_collect`
will read right after the jitted call returned them — a ragged step's `toks`
and `n_emit`, a fused scan's `toks` — so the read waits for the step alone. A
dispatch seam that answers with host arrays has nothing to ask for and is read
like any other; the request only schedules, so a device error still surfaces
in `step_collect` and is contained as before; and every step says whether the
host came late for its ids (`collect_ready`: its sample, a ragged step's
`batch` record, `/debug/stepprof`'s `collect` block). Its last section holds
the loop to admitting again right behind a scan's blocking read: a request
that arrived during the scan rides the very next launch."""

import jax
import numpy as np
import pytest

from ollamamq_tpu.engine.engine import ModelRuntime
from ollamamq_tpu.ops.sampling import SamplingParams
from ollamamq_tpu.telemetry import journal as journal_mod
from ollamamq_tpu.telemetry.stepprof import PROFILER
from ollamamq_tpu.testing.faults import FaultPlan
from test_step_overlap import (_engine, _greedy, _prompt, _rt, _wave,
                               drive)

SEAMS = {"ragged": ("_dispatch_ragged", 2), "scan": ("_dispatch_decode", 1)}


@pytest.fixture(scope="module")
def dense():
    return _engine()


@pytest.fixture(scope="module")
def base(dense):
    """What the wave emits with nothing patched."""
    mp = pytest.MonkeyPatch()
    try:
        return drive(dense, _wave(_greedy), False, mp)[0]
    finally:
        mp.undo()


def spy_on_transfers(monkeypatch):
    """ids of the jax arrays whose transfer to the host was asked for."""
    asked, cls = [], type(jax.numpy.zeros(1))
    orig = cls.copy_to_host_async

    def copy_to_host_async(self):
        asked.append(id(self))
        return orig(self)

    monkeypatch.setattr(cls, "copy_to_host_async", copy_to_host_async)
    return asked


def spy_on_collects(monkeypatch, asked):
    """[(k_steps, the arrays step_collect is about to read, which of them
    had been asked for by then)] a launched step, in collect order."""
    seen, orig = [], ModelRuntime.step_collect

    def step_collect(self, h, core):
        if h.state == "launched":
            reads = [a for a in (h.toks_dev, h.n_emit_dev) if a is not None]
            seen.append((h.k_steps, reads, [id(a) in asked for a in reads]))
        return orig(self, h, core)

    monkeypatch.setattr(ModelRuntime, "step_collect", step_collect)
    return seen


def answer_with(monkeypatch, rt, kind, wrap):
    """The `kind` step's dispatch seam hands `wrap(array, i)` for each of the
    arrays step_collect reads, i = 0 for `toks`."""
    seam, n = SEAMS[kind]
    orig = getattr(rt, seam)

    def dispatch(*args):
        out = orig(*args)
        return tuple(wrap(a, i) for i, a in enumerate(out[:n])) + out[n:]

    monkeypatch.setattr(rt, seam, dispatch)


@pytest.mark.parametrize("arrays", ["jax", "numpy"])
@pytest.mark.parametrize("kind", ["ragged", "scan"])
def test_a_steps_ids_leave_for_the_host_at_its_launch(dense, base, kind,
                                                      arrays, monkeypatch):
    asked = spy_on_transfers(monkeypatch)
    seen = spy_on_collects(monkeypatch, asked)
    if arrays == "numpy":
        answer_with(monkeypatch, _rt(dense), kind,
                    lambda a, i: np.asarray(a))
    out, samples = drive(dense, _wave(_greedy), False, monkeypatch)
    assert out == base
    steps = [s for s in seen if bool(s[0]) == (kind == "scan")]
    assert len(steps) >= 3
    for k_steps, reads, was_asked in steps:
        assert len(reads) == SEAMS[kind][1]
        if arrays == "numpy":
            assert all(type(a) is np.ndarray for a in reads)
            assert not any(was_asked)
        else:
            # One request an array, made before the read — and one shard's
            # worth: jax moves a replicated array from its first device.
            assert all(was_asked), (k_steps, was_asked)
            assert all(a.is_fully_replicated for a in reads)
    assert len(asked) == len(set(asked))  # never asked twice
    assert all("collect_ready" in s for s in samples)
    if arrays == "numpy":  # ids that never were on a device are ready
        mode = "decode" if kind == "scan" else "ragged"
        assert all(s["collect_ready"] == 1
                   for s in samples if s["mode"] == mode)


def test_a_replicated_step_moves_one_shard(monkeypatch):
    """Under `--tp` the ids are replicated over the mesh: the launch asks
    once an array, and jax's rule for a fully replicated array is one
    device's copy, not the mesh's."""
    eng = _engine("test-tiny-gqa", tp=2)
    asked = spy_on_transfers(monkeypatch)
    seen = spy_on_collects(monkeypatch, asked)
    drive(eng, _wave(_greedy, n=3), False, monkeypatch)
    assert {bool(k) for k, *_ in seen} == {False, True}
    for _k, reads, was_asked in seen:
        assert all(was_asked)
        assert all(len(a.sharding.device_set) == 2 and a.is_fully_replicated
                   for a in reads)
    assert len(asked) == sum(len(reads) for _k, reads, _ in seen)


def test_a_collect_fault_still_surfaces_in_step_collect(base, monkeypatch):
    """The transfer was asked for at the launch of the faulted step AND of
    the step launched behind it; the error still comes out of the read's
    seam, voids the one behind and retries both steps' rows."""
    plan = FaultPlan([{"site": "collect", "kind": "exception", "at": [4]}])
    eng = _engine(plan=plan, retry_backoff_s=0.0)
    eng.recover_interval = 0.0
    rt = _rt(eng)
    asked = spy_on_transfers(monkeypatch)
    seen = spy_on_collects(monkeypatch, asked)
    voided, orig = [], rt.void_inflight

    def void_inflight():
        h = rt.inflight
        voided.append(h is not None and id(h.toks_dev) in asked)
        return orig()

    monkeypatch.setattr(rt, "void_inflight", void_inflight)
    out, _ = drive(eng, _wave(_greedy), False, monkeypatch)
    assert plan.stats()["injected"] == 1
    assert voided == [True]  # a step WAS in flight behind it, and asked for
    assert all(all(was_asked) for *_, was_asked in seen)
    assert out == base  # no id lost, none doubled, same finish reasons
    recs = eng.journal.tail(None)
    assert journal_mod.check_invariants(recs, starve_after=None) == []
    batches = [r for r in recs if r["kind"] == "batch"]
    # (the faulted step's record has no read to speak of)
    assert sum("collect_ready" not in r for r in batches) == 1
    assert sum(r["kind"] == "retry" for r in recs) >= 1


class Ids:
    """A step's `toks` with its readiness scripted: `ready_before_read`
    says what `is_ready()` answers until the read; the read itself blocks
    on the real array, as a read does."""

    def __init__(self, arr, ready_before_read, broken=False):
        self.arr, self.ready, self.broken = arr, ready_before_read, broken
        self.asked = 0
        if ready_before_read:
            arr.block_until_ready()

    def is_ready(self):
        return self.ready

    def copy_to_host_async(self):
        self.asked += 1
        if self.broken:
            raise RuntimeError("a transfer that cannot even be asked for")
        self.arr.copy_to_host_async()

    def __array__(self, dtype=None, copy=None):
        self.ready = True
        if self.broken:
            raise RuntimeError("device lost")
        return np.asarray(self.arr)


@pytest.mark.parametrize("ready", [1, 0], ids=["host_late", "host_waits"])
def test_the_batch_record_says_whether_the_ids_were_waiting(dense, base,
                                                            ready,
                                                            monkeypatch):
    """`collect_ready` on a ragged step's `batch` record, on its sample and
    in the profiler's `collect` block: 1 when the ids were ready on entry
    to `step_collect`, 0 when the read had to wait for the step."""
    made = []

    def wrap(a, i):
        if i:
            return a
        made.append(Ids(a, bool(ready)))
        return made[-1]

    answer_with(monkeypatch, _rt(dense), "ragged", wrap)
    n0 = len(dense.journal.tail(None))
    out, samples = drive(dense, _wave(_greedy), False, monkeypatch)
    assert out == base
    assert len(made) >= 5 and all(x.asked == 1 for x in made)
    batches = [r for r in dense.journal.tail(None)[n0:]
               if r["kind"] == "batch"]
    ragged = [s for s in samples if s["mode"] == "ragged"]
    assert len(batches) == len(ragged) == len(made)
    assert [r["collect_ready"] for r in batches] == [ready] * len(made)
    assert [s["collect_ready"] for s in ragged] == [ready] * len(made)
    block = PROFILER.summary()["collect"]
    scans = [s["collect_ready"] for s in samples if s["mode"] == "decode"]
    assert block == {"steps": len(samples),
                     "ready": ready * len(made) + sum(scans)}


def test_a_request_that_fails_leaves_the_error_to_the_read(dense, base,
                                                           monkeypatch):
    """`copy_to_host_async` raising at a launch is not the launch's error:
    the step is launched all the same, the read meets what is wrong, and
    the rows retry."""
    made = []

    def wrap(a, i):
        if i:
            return a
        made.append(Ids(a, False, broken=len(made) == 3))
        return made[-1]

    eng = dense
    monkeypatch.setattr(eng.ecfg, "retry_backoff_s", 0.0)
    answer_with(monkeypatch, _rt(eng), "ragged", wrap)
    n0 = len(eng.journal.tail(None))
    out, _ = drive(eng, _wave(_greedy), False, monkeypatch)
    assert out == base
    assert made[3].broken and made[3].asked == 1
    recs = eng.journal.tail(None)[n0:]
    assert any(r["kind"] == "retry" and "device lost" in r.get("error", "")
               for r in recs)


# ------------------------------------ admission behind a scan's blocking read
def _arrive_during_scans(eng, monkeypatch, late):
    """u0 and u1 decode as fused scans; `late` requests arrive WHILE the
    engine thread is blocked reading a scan (enqueued from inside that
    read). Returns (requests, samples)."""
    PROFILER.reset()
    rt, reqs, todo = _rt(eng), {}, list(late)
    orig = ModelRuntime.step_collect

    def step_collect(self, h, core):
        if h.state == "launched" and h.k_steps == 4 and todo:
            name, n = todo.pop(0)
            reqs[name] = eng.enqueue_request(
                name, "", rt.name, prompt_tokens=_prompt(7, n),
                sampling=SamplingParams(max_tokens=6))
        return orig(self, h, core)

    monkeypatch.setattr(ModelRuntime, "step_collect", step_collect)
    for i in range(2):
        reqs[f"u{i}"] = eng.enqueue_request(
            f"u{i}", "", rt.name, prompt_tokens=_prompt(i, 6),
            sampling=SamplingParams(max_tokens=30))
    for tick in range(400):
        eng._loop_once()
        if not todo and all(r.stats.finished_at for r in reqs.values()):
            break
    eng._settle_all()
    assert all(r.stats.finished_at for r in reqs.values())
    assert rt.cache.alloc.used_pages == 0
    return reqs, PROFILER.tail()


def test_an_arrival_during_a_scan_rides_the_step_behind_it(dense,
                                                           monkeypatch):
    """The request that arrived while the thread was blocked on a scan is
    admitted right behind that read: the next launch is the ragged step that
    takes its prompt in — no one-pass scan, with a blocking read and an idle
    chip of its own, in between."""
    reqs, samples = _arrive_during_scans(dense, monkeypatch,
                                         [("late0", 9), ("late1", 12)])
    assert [len(r.generated_ids) for r in reqs.values()] == [30, 30, 6, 6]
    modes = [(s["mode"], s["k_cap"]) for s in samples]
    full = [i for i, m in enumerate(modes) if m == ("decode", 4)]
    assert len(full) >= 3
    # Each of the two scans an arrival landed in is followed by a ragged
    # step with a prefill span; never by a scan of one pass.
    took_in = [i for i in full[:-1] if modes[i + 1][0] == "ragged"
               and samples[i + 1]["n_prefill"] == 1]
    assert len(took_in) == 2, modes
    assert ("decode", 1) not in modes, modes


def test_the_second_admission_pass_leaves_the_schedulers_clock(dense,
                                                              monkeypatch):
    """One scheduler tick a loop iteration, however many admission passes."""
    ticks, orig = [], dense.policy.on_admit_tick
    monkeypatch.setattr(dense.policy, "on_admit_tick",
                        lambda: (ticks.append(dense.journal.tick), orig()))
    _arrive_during_scans(dense, monkeypatch, [("late0", 9)])
    assert ticks and len(ticks) == len(set(ticks))
