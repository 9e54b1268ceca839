"""Expert parallelism ACROSS hosts: 2 CPU processes, global mesh ep=2 —
each process owns half the experts of the MoE model; GSPMD inserts the
expert all-to-all across the process boundary. Greedy tokens must equal a
plain single-device run (EP is layout-only)."""

from testutil import run_two_process, single_device_greedy_tokens

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

from ollamamq_tpu.config import EngineConfig
from ollamamq_tpu.parallel.mesh import make_mesh
import jax.numpy as jnp

mesh = make_mesh(dp=1, tp=1, ep=2)  # half the experts per host
ecfg = EngineConfig(model="test-tiny-moe", max_slots=2, num_pages=32,
                    page_size=8, max_pages_per_seq=8,
                    decode_steps_per_iter=2, ep=2)
MODELS = {"test-tiny-moe": None}

if pid == 0:
    from ollamamq_tpu.engine.spmd import SPMDEngine
    from ollamamq_tpu.ops.sampling import SamplingParams

    eng = SPMDEngine(ecfg, models=MODELS, blocklist_path=None,
                     mesh=mesh, dtype=jnp.float32)
    eng.start()
    import time

    tok = eng.runtimes["test-tiny-moe"].tokenizer
    req = eng.enqueue_request("u", "", "test-tiny-moe",
                              prompt_tokens=tok.encode("experts apart"),
                              sampling=SamplingParams(max_tokens=6))
    deadline = time.monotonic() + 300
    item = None
    while time.monotonic() < deadline:
        item = req.stream.get(timeout=0.5)
        if item and item.kind in ("done", "error"):
            break
    eng.stop()
    print("RESULT " + json.dumps({
        "kind": item.kind if item else "timeout",
        "error": getattr(item, "error", "") if item else "",
        "tokens": req.generated_ids,
    }), flush=True)
else:
    from ollamamq_tpu.engine.spmd import run_worker

    steps = run_worker(MODELS, ecfg, mesh, dtype=jnp.float32)
    print("RESULT " + json.dumps({"steps": steps}), flush=True)
"""


def test_spmd_expert_parallel_across_processes(tmp_path):
    primary, worker = run_two_process(_SCRIPT, tmp_path)
    assert primary["kind"] == "done", primary
    assert worker["steps"] >= 2  # prefill + decode dispatches replayed
    assert len(primary["tokens"]) >= 1
    # EP across hosts must be numerically transparent.
    assert single_device_greedy_tokens(
        "test-tiny-moe", "experts apart") == primary["tokens"]
