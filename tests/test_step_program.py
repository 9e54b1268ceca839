"""The step programs' builders (`engine/step_program.py`) are memoised: two
runtimes of one configuration share one jit object, and so one trace and one
executable a process. The two hooks that drop an entry — a fired `compile`
fault, `TPUEngine.evict_model` — are held here."""

import jax.numpy as jnp

from ollamamq_tpu.config import MODEL_CONFIGS, EngineConfig
from ollamamq_tpu.engine import engine as eng_mod
from ollamamq_tpu.engine import step_program
from ollamamq_tpu.testing.faults import FaultPlan

SIZES = dict(max_slots=2, num_pages=16, page_size=8, max_pages_per_seq=4,
             max_batch_tokens=16, token_granule=16)
GREEDY = (False, False, False)


def _runtime(name="test-tiny", **over):
    return eng_mod.ModelRuntime(
        name, MODEL_CONFIGS[name],
        EngineConfig(model=name, **{**SIZES, **over}), dtype=jnp.float32)


def test_two_runtimes_of_one_configuration_hold_one_program(monkeypatch):
    # the jit itself, not the first-call wrapper that times its compile
    monkeypatch.setattr(eng_mod, "_sp_note_compile",
                        lambda rt, site, key, cache, fn: cache.setdefault(
                            key, fn))
    a, b = _runtime(), _runtime()
    ragged = a._get_ragged_jit(16, 0, GREEDY)
    scan = a._get_decode_jit(4, GREEDY)
    assert b._get_ragged_jit(16, 0, GREEDY) is ragged
    assert b._get_decode_jit(4, GREEDY) is scan
    # ...another shape, other flags, other sizes: programs of their own
    assert a._get_ragged_jit(32, 0, GREEDY) is not ragged
    assert a._get_ragged_jit(16, 0, (True, True, True)) is not ragged
    assert _runtime(max_slots=4)._get_decode_jit(4, GREEDY) is not scan
    other = _runtime("test-tiny-gqa")
    assert other._get_ragged_jit(16, 0, GREEDY) is not ragged

    # A fired `compile` fault: the runtime's entry AND the builder's go, so
    # the next launch traces and compiles anew — and a runtime built later
    # is handed that one.
    a.fault_plan = FaultPlan([{"site": "compile", "kind": "exception",
                               "every": 1}])
    again = a._get_ragged_jit(16, 0, GREEDY)
    assert again is not ragged
    assert b._get_ragged_jit(16, 0, GREEDY) is ragged  # its own ledger's
    assert _runtime()._get_ragged_jit(16, 0, GREEDY) is again


def test_evict_model_drops_the_configurations_programs():
    eng = eng_mod.TPUEngine(
        EngineConfig(model="test-tiny", **SIZES), models={"test-tiny": None},
        blocklist_path=None, dtype=jnp.float32)  # never started: no loop
    rt = eng.resolve_runtime("test-tiny")
    _runtime("test-tiny-gqa")._get_decode_jit(4, GREEDY)
    rt._get_ragged_jit(16, 0, GREEDY)
    rt._get_decode_jit(4, GREEDY)
    held = [k for k in step_program._BUILT if k[1] == rt.cfg]
    assert len(held) >= 2
    assert eng.evict_model("test-tiny")
    assert not [k for k in step_program._BUILT if k[1] == rt.cfg]
    assert [k for k in step_program._BUILT
            if k[1] == MODEL_CONFIGS["test-tiny-gqa"]]
