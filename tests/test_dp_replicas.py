"""Data-parallel replica serving: dp=2 x tp=4 on the 8-virtual-device CPU
mesh (VERDICT r1 item 4). Each replica is an independent ModelRuntime
TP-sharded over its own slice of the mesh's data axis; placement is
least-loaded with round-robin rotation (dispatcher.rs:475-487 analogue)."""

import time

import jax
import pytest

from ollamamq_tpu.config import EngineConfig
from ollamamq_tpu.engine.engine import ReplicaSet, TPUEngine
from ollamamq_tpu.engine.request import FinishReason, Request
from ollamamq_tpu.ops.sampling import SamplingParams
from testutil import collect


def dp_cfg(**kw):
    defaults = dict(
        model="test-tiny-gqa", max_slots=2, num_pages=64, page_size=8,
        max_pages_per_seq=16,
        max_new_tokens=8, decode_steps_per_iter=2, dp=2, tp=4,
    )
    defaults.update(kw)
    return EngineConfig(**defaults)


@pytest.fixture(scope="module")
def dp_engine():
    eng = TPUEngine(dp_cfg(), blocklist_path=None)
    eng.start()
    yield eng
    eng.stop()


def test_replicas_shard_over_disjoint_device_slices(dp_engine):
    """dp=2 builds two runtimes whose param shards live on DISJOINT 4-device
    subsets of the 8-device mesh (per-replica shards differ — this is
    replication of the model, not of the work)."""
    rs = dp_engine.runtimes["test-tiny-gqa"]
    assert isinstance(rs, ReplicaSet) and len(rs.replicas) == 2
    device_sets = []
    for rt in rs.replicas:
        leaf = jax.tree_util.tree_leaves(rt.params)[0]
        device_sets.append({d.id for d in leaf.sharding.device_set})
    assert device_sets[0] and device_sets[1]
    assert device_sets[0].isdisjoint(device_sets[1])
    # TP really sharded: each replica's tensor axis spans its 4 devices.
    assert all(len(s) == 4 for s in device_sets)


def test_two_requests_land_on_different_replicas(dp_engine):
    """Least-loaded placement spreads concurrent requests across replicas,
    and both generate correctly (greedy => identical outputs for identical
    prompts, which also pins replica weight equivalence)."""
    rs = dp_engine.runtimes["test-tiny-gqa"]
    tok = rs.tokenizer
    reqs = []
    for i, user in enumerate(("dp-a", "dp-b")):
        rid = dp_engine.core.enqueue(user, "", "test-tiny-gqa")
        req = Request(rid, user, "test-tiny-gqa", tok.encode("same prompt"),
                      SamplingParams(max_tokens=6))
        reqs.append(req)
    for r in reqs:
        dp_engine.submit(r)
    outs = [collect(r) for r in reqs]
    assert all(o[-1].kind == "done" for o in outs)
    # Both replicas were exercised.
    assert all(rt.tokens_generated > 0 for rt in rs.replicas), [
        rt.tokens_generated for rt in rs.replicas
    ]
    # Identical random-init seed + greedy => identical tokens on BOTH
    # replicas: per-replica param shards differ in placement, not values.
    assert reqs[0].generated_ids == reqs[1].generated_ids


@pytest.mark.parametrize("model", ["test-tiny-gqa", "test-tiny-qwen3"])
def test_dp2_x_tp2_streams_equal_the_single_mesh_stream(model):
    """Two replicas, each tensor-parallel over its own two devices, serve
    two different prompts at once (the longer one in several spans): each
    greedy stream is the one a plain single-device engine gives — neither
    the data axis nor the tensor axis shows in the tokens (q/k norm
    included, for the qwen3 preset)."""
    import jax.numpy as jnp

    from testutil import single_device_greedy_tokens

    kw = dict(max_slots=2, num_pages=32, page_size=8, max_pages_per_seq=8,
              max_batch_tokens=16, token_granule=8, decode_steps_per_iter=2)
    prompts = ["dp by tp, the long one: " + "spans and spans " * 2,
               "dp by tp"]
    eng = TPUEngine(EngineConfig(model=model, dp=2, tp=2, **kw),
                    models={model: None}, blocklist_path=None,
                    dtype=jnp.float32)
    eng.start()
    try:
        rs = eng.runtimes[model]
        assert isinstance(rs, ReplicaSet) and len(rs.replicas) == 2
        assert all(rt.mesh.shape["tensor"] == 2 for rt in rs.replicas)
        reqs = []
        for i, text in enumerate(prompts):
            rid = eng.core.enqueue(f"dt{i}", "", model)
            reqs.append(Request(rid, f"dt{i}", model, rs.tokenizer.encode(text),
                                SamplingParams(max_tokens=6)))
        assert len(reqs[0].prompt_tokens) > 2 * kw["max_batch_tokens"]
        for r in reqs:
            eng.submit(r)
        assert all(collect(r)[-1].kind == "done" for r in reqs)
        assert all(rt.tokens_generated > 0 for rt in rs.replicas)
    finally:
        eng.stop()
    for r, text in zip(reqs, prompts):
        assert r.generated_ids == single_device_greedy_tokens(
            model, text, **kw), text


def test_full_mesh_dp_tp_serving():
    """Both axes at once on the whole 8-device mesh: dp=2 replicas, each
    a [1, 1, tp=4] submesh. A prompt of several spans is prefilled as
    ragged spans inside a TP-sharded replica — the one prefill program
    there is — and both replicas' outputs match the plain dp=tp=1 engine
    token-for-token."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    kw = dict(model="test-tiny-gqa", max_slots=2, num_pages=128,
              page_size=8, max_pages_per_seq=32, max_batch_tokens=32,
              token_granule=8, max_new_tokens=8, decode_steps_per_iter=2)
    eng = TPUEngine(EngineConfig(dp=2, tp=4, **kw), blocklist_path=None)
    ref = TPUEngine(EngineConfig(**kw), blocklist_path=None)
    eng.start()
    ref.start()
    try:
        rs = eng.runtimes["test-tiny-gqa"]
        assert len(rs.replicas) == 2
        assert all(dict(rt.mesh.shape) == {"data": 1, "expert": 1,
                                           "tensor": 4}
                   for rt in rs.replicas)
        prompt = rs.tokenizer.encode("full mesh " * 15)
        assert len(prompt) > 4 * kw["max_batch_tokens"]

        def run(e, user):
            rid = e.core.enqueue(user, "", "test-tiny-gqa")
            req = Request(rid, user, "test-tiny-gqa", prompt,
                          SamplingParams(max_tokens=5))
            e.submit(req)
            items = collect(req)
            assert items[-1].kind == "done", items[-1]
            return req.generated_ids

        ids_a = run(eng, "mesh-a")
        ids_b = run(eng, "mesh-b")  # second request: other replica
        ids_ref = run(ref, "mesh-ref")
        assert ids_a == ids_ref and ids_b == ids_ref
        assert all(rt.tokens_generated > 0 for rt in rs.replicas)
        assert all(k[0] == "ragged"
                   for rt in rs.replicas for k in rt._prefill_jits)
    finally:
        eng.stop()
        ref.stop()


def test_least_loaded_placement_and_rotation():
    """Placement picks the least-loaded replica; ties rotate (reference
    least-conn + rotate-after-last, dispatcher.rs:475-487)."""

    class FakeReplica:
        def __init__(self):
            self.pending_prefill = []
            self.chunking = []
            self.submitted = []
            self.capacity = True

        def has_capacity(self, kind=None):
            return self.capacity

        def active_count(self):
            return len(self.submitted)

        def submit(self, req):
            self.submitted.append(req.name)

        name = "fake"
        cfg = ecfg = None

    def _req(name):
        from types import SimpleNamespace

        return SimpleNamespace(name=name, kind="generate")

    a, b, c = FakeReplica(), FakeReplica(), FakeReplica()
    rs = ReplicaSet.__new__(ReplicaSet)
    rs.replicas = [a, b, c]
    rs._last_idx = 0
    # All empty: rotation starts after index 0 => b, then ties rotate c, a.
    rs.submit(_req("r1"))
    assert b.submitted == ["r1"]
    rs.submit(_req("r2"))
    assert c.submitted == ["r2"]
    rs.submit(_req("r3"))
    assert a.submitted == ["r3"]
    # Load-based: make b busiest, c without capacity => a wins.
    b.submitted += ["x", "y"]
    c.capacity = False
    rs.submit(_req("r4"))
    assert a.submitted == ["r3", "r4"]


def test_cancel_reaches_replica_held_request(dp_engine):
    """engine.cancel() finds requests held INSIDE a replica (client
    disconnects must cancel + reclaim under dp>1, not run to max_tokens)."""
    rs = dp_engine.runtimes["test-tiny-gqa"]
    for rt in rs.replicas:
        rt.tokenizer.eos_id = -1  # keep generating until cancelled
    free_before = [rt.cache.alloc.free_pages for rt in rs.replicas]
    tok = rs.tokenizer
    rid = dp_engine.core.enqueue("dp-cancel", "", "test-tiny-gqa")
    req = Request(rid, "dp-cancel", "test-tiny-gqa", tok.encode("cancel me"),
                  SamplingParams(max_tokens=10_000))
    dp_engine.submit(req)
    deadline = time.monotonic() + 60
    while not req.stats.first_token_at and time.monotonic() < deadline:
        time.sleep(0.01)
    assert req.stats.first_token_at, "never started generating"
    dp_engine.cancel(rid)
    items = collect(req)
    assert items[-1].finish_reason == FinishReason.CANCELLED
    deadline = time.monotonic() + 10
    while ([rt.cache.alloc.free_pages for rt in rs.replicas] != free_before
           and time.monotonic() < deadline):
        time.sleep(0.01)
    assert [rt.cache.alloc.free_pages for rt in rs.replicas] == free_before
    for rt in rs.replicas:
        rt.tokenizer.eos_id = 2  # restore for other tests


def test_fairness_counters_shared_across_replicas(dp_engine):
    """Replicas share ONE scheduler core: processed counts accumulate per
    user regardless of which replica served them."""
    snap = dp_engine.core.snapshot()
    assert snap["users"]["dp-a"]["processed"] >= 1
    assert snap["users"]["dp-b"]["processed"] >= 1


def test_dp_decode_dispatches_overlap_before_any_collect():
    """The throughput point of dp (VERDICT r2 weak #1): the engine loop must
    dispatch EVERY replica's fused decode chunk before blocking on any —
    replicas on disjoint device sets then execute concurrently. Asserted
    structurally (dispatch/collect event order) rather than by wall-clock,
    which would be flaky on shared CPU cores. Since PR 28 a launched step
    stays in flight into the next tick, where it is collected just before
    the runtime's next launch: both replicas' first launches still come
    before either is read, and from then on each replica always has a step
    queued while the other's is read."""
    from ollamamq_tpu.engine.engine import ModelRuntime

    eng = TPUEngine(dp_cfg(), blocklist_path=None)
    rs = eng.runtimes["test-tiny-gqa"]
    tok = rs.tokenizer
    events = []

    orig_dispatch = ModelRuntime.step_decode_dispatch
    orig_collect = ModelRuntime.step_collect

    def rec_dispatch(self, core, k_steps=1):
        h = orig_dispatch(self, core, k_steps=k_steps)
        if h is not None:
            events.append(("dispatch", id(self)))
        return h

    def rec_collect(self, handle, core):
        if handle.state == "launched":
            events.append(("collect", id(self)))
        return orig_collect(self, handle, core)

    ModelRuntime.step_decode_dispatch = rec_dispatch
    ModelRuntime.step_collect = rec_collect
    try:
        # One request per replica, installed by a ragged step driven by
        # hand (no loop thread — deterministic ordering).
        for i, rep in enumerate(rs.replicas):
            req = Request(9000 + i, f"ovl{i}", "test-tiny-gqa",
                          tok.encode("overlap probe"),
                          SamplingParams(max_tokens=64))
            assert rep.submit(req)
            assert rep.step_ragged(eng.core)
        events.clear()
        eng._loop_once()
        assert [e[0] for e in events] == ["dispatch", "dispatch"], events
        eng._loop_once()
        a, b = (id(rep) for rep in rs.replicas)
        assert events[2:] == [("collect", a), ("dispatch", a),
                              ("collect", b), ("dispatch", b)], events
    finally:
        ModelRuntime.step_decode_dispatch = orig_dispatch
        ModelRuntime.step_collect = orig_collect
        for rep in rs.replicas:
            rep.void_inflight()
            for s, r in enumerate(rep.slot_req):
                if r is not None:
                    rep._finish_slot(s, FinishReason.CANCELLED, eng.core)
