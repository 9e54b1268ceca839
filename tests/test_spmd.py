"""SPMD multi-host serving: 2 CPU processes, global mesh tp=2 spanning
both, primary serves a request while the worker replays its dispatches.
The generated tokens must equal a single-process run (same seed) — i.e.
cross-host tensor parallelism is numerically transparent."""

from testutil import run_two_process

_SCRIPT = r"""
import json, os, sys
pid = int(sys.argv[1]); port = sys.argv[2]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.pop("XLA_FLAGS", None)  # exactly 1 local device per process
import jax
jax.config.update("jax_platforms", "cpu")
jax.distributed.initialize(coordinator_address=f"localhost:{port}",
                           num_processes=2, process_id=pid)
assert jax.device_count() == 2

from ollamamq_tpu.config import MODEL_CONFIGS, EngineConfig
from ollamamq_tpu.parallel.mesh import make_mesh
import jax.numpy as jnp

mesh = make_mesh(dp=1, tp=2)
ecfg = EngineConfig(model="test-tiny", max_slots=2, num_pages=32, page_size=8,
                    max_pages_per_seq=8,
                    decode_steps_per_iter=2)
mcfg = MODEL_CONFIGS["test-tiny"]

MODELS = {"test-tiny": None, "test-tiny-embed": None}

if pid == 0:
    from ollamamq_tpu.engine.spmd import SPMDEngine
    from ollamamq_tpu.ops.sampling import SamplingParams

    eng = SPMDEngine(ecfg, models=MODELS, blocklist_path=None,
                     mesh=mesh, dtype=jnp.float32)
    eng.start()
    import time

    def wait(req, budget=300):
        deadline = time.monotonic() + budget
        while time.monotonic() < deadline:
            item = req.stream.get(timeout=0.5)
            if item and item.kind in ("done", "error"):
                return item
        return None

    tok = eng.runtimes["test-tiny"].tokenizer
    req = eng.enqueue_request("u", "", "test-tiny",
                              prompt_tokens=tok.encode("spmd check"),
                              sampling=SamplingParams(max_tokens=6))
    wait(req)
    # Embedding request across both hosts (OP_ENCODE replay).
    etok = eng.runtimes["test-tiny-embed"].tokenizer
    ereq = eng.enqueue_request("u", "", "test-tiny-embed",
                               prompt_tokens=etok.encode("embed me"),
                               sampling=SamplingParams(), kind="embed")
    eitem = wait(ereq)
    eng.stop()  # also releases workers (single shutdown broadcast)
    print("RESULT " + json.dumps({
        "tokens": req.generated_ids,
        "embed_ok": bool(eitem and eitem.kind == "done"),
        "embed_dim": len(ereq.embedding or []),
        "embed_head": (ereq.embedding or [0.0, 0.0])[:2],
    }), flush=True)
else:
    from ollamamq_tpu.engine.spmd import run_worker

    steps = run_worker(MODELS, ecfg, mesh, dtype=jnp.float32)
    print("RESULT " + json.dumps({"steps": steps}), flush=True)
"""

def test_spmd_two_process_serving(tmp_path):
    primary, worker = run_two_process(_SCRIPT, tmp_path)
    assert worker["steps"] >= 3  # prefill + decode(s) + encode dispatch
    assert len(primary["tokens"]) >= 1
    assert primary["embed_ok"] and primary["embed_dim"] > 0

    # Single-process reference with the same seed/config must match exactly.
    from ollamamq_tpu.config import EngineConfig
    from ollamamq_tpu.engine.engine import TPUEngine
    from ollamamq_tpu.ops.sampling import SamplingParams
    import jax.numpy as jnp
    import time

    eng = TPUEngine(
        EngineConfig(model="test-tiny", max_slots=2, num_pages=32, page_size=8,
                     max_pages_per_seq=8,
                     decode_steps_per_iter=2),
        models={"test-tiny": None, "test-tiny-embed": None},
        blocklist_path=None, dtype=jnp.float32,
    )
    eng.start()
    try:
        tok = eng.runtimes["test-tiny"].tokenizer

        def wait(req, budget=120):
            deadline = time.monotonic() + budget
            while time.monotonic() < deadline:
                item = req.stream.get(timeout=0.5)
                if item and item.kind in ("done", "error"):
                    return item

        req = eng.enqueue_request("u", "", "test-tiny",
                                  prompt_tokens=tok.encode("spmd check"),
                                  sampling=SamplingParams(max_tokens=6))
        wait(req)
        assert req.generated_ids == primary["tokens"]
        etok = eng.runtimes["test-tiny-embed"].tokenizer
        ereq = eng.enqueue_request("u", "", "test-tiny-embed",
                                   prompt_tokens=etok.encode("embed me"),
                                   sampling=SamplingParams(), kind="embed")
        wait(ereq)
        assert len(ereq.embedding) == primary["embed_dim"]
        import numpy as np

        np.testing.assert_allclose(
            ereq.embedding[:2], primary["embed_head"], rtol=1e-4, atol=1e-5
        )
    finally:
        eng.stop()
