"""Multi-model HBM pool on the REAL engine (BASELINE config 5): runtime
load (/api/pull), serving both models concurrently, evict (/api/delete),
stuck-in-queue for the evicted model, and re-load draining it."""

import time

import pytest

from ollamamq_tpu.config import EngineConfig
from ollamamq_tpu.engine.engine import TPUEngine
from ollamamq_tpu.engine.request import FinishReason, Request
from ollamamq_tpu.ops.sampling import SamplingParams
from ollamamq_tpu.server.registry import ModelRegistry
from testutil import collect


@pytest.fixture(scope="module")
def setup():
    eng = TPUEngine(
        EngineConfig(model="test-tiny", max_slots=4, num_pages=128,
                     page_size=8, max_pages_per_seq=16,
                     max_new_tokens=8,
                     decode_steps_per_iter=2),
        blocklist_path=None,
    )
    eng.start()
    reg = ModelRegistry(eng)
    yield eng, reg
    eng.stop()


def run(eng, user, model, max_tokens=4):
    # Target model's tokenizer when loaded; any runtime's only for the
    # deliberately-evicted case (both test models use ByteTokenizer).
    rt = eng.runtimes.get(model) or next(iter(eng.runtimes.values()))
    tok = rt.tokenizer
    rid = eng.core.enqueue(user, "", model)
    req = Request(rid, user, model, tok.encode(f"for {model}"),
                  SamplingParams(max_tokens=max_tokens))
    eng.submit(req)
    return req


def test_pull_load_serve_evict_reload(setup):
    eng, reg = setup
    assert eng.loaded_models() == ["test-tiny"]

    # Runtime pull: second model loads into HBM and serves.
    reg.pull("test-tiny-gqa")
    assert set(eng.loaded_models()) == {"test-tiny", "test-tiny-gqa"}
    r1 = run(eng, "mmA", "test-tiny")
    r2 = run(eng, "mmB", "test-tiny-gqa")
    assert collect(r1)[-1].kind == "done"
    assert collect(r2)[-1].kind == "done"
    # HBM accounting covers both runtimes.
    stats = eng.stats()
    assert len(stats["runtimes"]) == 2
    assert all(s["param_bytes"] > 0 for s in stats["runtimes"])

    # Evict: requests for the gone model wait in queue (stuck semantics).
    assert reg.delete("test-tiny-gqa")
    assert eng.loaded_models() == ["test-tiny"]
    r3 = run(eng, "mmC", "test-tiny-gqa")
    time.sleep(0.5)
    assert r3.stream.get_nowait() is None  # not served, not errored
    snap = eng.core.snapshot()
    assert snap["users"]["mmC"]["queued"] == 1
    # Other model keeps serving during the outage.
    r4 = run(eng, "mmD", "test-tiny")
    assert collect(r4)[-1].kind == "done"

    # Re-pull: the parked request drains.
    reg.pull("test-tiny-gqa")
    assert collect(r3)[-1].kind == "done"


def test_evict_with_inflight_work_refuses(setup):
    eng, reg = setup
    if "test-tiny-gqa" not in eng.runtimes:  # independent of test order
        reg.pull("test-tiny-gqa")
    rt = eng.runtimes["test-tiny-gqa"]
    rt.tokenizer.eos_id = -1
    req = run(eng, "mmE", "test-tiny-gqa", max_tokens=10_000)
    deadline = time.monotonic() + 60
    while not req.stats.first_token_at and time.monotonic() < deadline:
        time.sleep(0.01)
    assert req.stats.first_token_at
    with pytest.raises(RuntimeError, match="in-flight"):
        eng.evict_model("test-tiny-gqa")
    eng.cancel(req.req_id)
    items = collect(req)
    assert items[-1].finish_reason == FinishReason.CANCELLED
    rt.tokenizer.eos_id = 2
