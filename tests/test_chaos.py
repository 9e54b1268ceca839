"""Concurrency chaos: admin mutations racing live traffic.

The reference's thread-safety story is Rust's compiler (SURVEY.md §5
"race detection: none beyond what the compiler enforces"); here the
equivalent assurance is exercised empirically: concurrent generate /
cancel / block / unblock / VIP-boost flips / model pull+delete / metrics
polls against one engine, then assert the system settled consistently —
no deadlock, queues drained, gauges zeroed, no thread deaths.
"""

import asyncio
import random
import tempfile

from aiohttp.test_utils import TestClient, TestServer

from ollamamq_tpu.config import EngineConfig
from ollamamq_tpu.engine.fake import FakeEngine
from ollamamq_tpu.server.app import Server


def test_admin_mutations_race_traffic():
    rng = random.Random(7)

    async def main():
        with tempfile.TemporaryDirectory() as tmp:
            eng = FakeEngine(
                EngineConfig(model="test-tiny", max_slots=8),
                models={"test-tiny": None},
                blocklist_path=f"{tmp}/blocked_items.json",
                token_latency_s=0.002,
            )
            eng.start()
            server = Server(eng, timeout_s=60)
            cl = TestClient(TestServer(server.build_app()))
            await cl.start_server()
            try:
                stop = asyncio.Event()

                async def traffic(user):
                    while not stop.is_set():
                        try:
                            async with cl.post("/api/generate", json={
                                "model": "test-tiny", "prompt": "x",
                                "stream": rng.random() < 0.5,
                                "options": {"num_predict": rng.randint(1, 6)},
                            }, headers={"X-User-ID": user}) as r:
                                await r.read()  # drive streams to completion
                        except Exception:
                            pass
                        await asyncio.sleep(0)

                async def admin():
                    core = eng.core
                    for _ in range(200):
                        action = rng.randint(0, 6)
                        user = f"chaos{rng.randint(0, 4)}"
                        if action == 0:
                            core.block_user(user)
                        elif action == 1:
                            core.unblock_user(user)
                        elif action == 2:
                            core.set_vip(user if rng.random() < 0.8 else None)
                        elif action == 3:
                            core.set_boost(user if rng.random() < 0.8 else None)
                        elif action == 4:
                            try:
                                await cl.post("/api/pull", json={
                                    "model": "test-tiny-qwen", "stream": False})
                            except Exception:
                                pass
                        elif action == 5:
                            try:
                                await cl.post("/api/delete", json={
                                    "model": "test-tiny-qwen"})
                            except Exception:
                                pass
                        else:
                            try:
                                async with cl.get("/metrics") as r:
                                    await r.read()
                            except Exception:
                                pass
                        await asyncio.sleep(0.002)
                    stop.set()

                users = [f"chaos{i}" for i in range(5)]
                await asyncio.gather(admin(), *(traffic(u) for u in users))

                # Unblock everyone, then the system must settle.
                for u in users:
                    eng.core.unblock_user(u)
                for _ in range(200):
                    if eng.core.total_queued() == 0 and not any(
                        rt.has_work() for rt in eng.runtimes.values()
                    ):
                        break
                    await asyncio.sleep(0.05)
                assert eng.core.total_queued() == 0
                snap = eng.core.snapshot()
                assert sum(u["processing"] for u in snap["users"].values()) == 0
                total = sum(u["processed"] + u["dropped"]
                            for u in snap["users"].values())
                assert total > 0
                # Engine thread is alive and still serves.
                r = await cl.post("/api/generate", json={
                    "model": "test-tiny", "prompt": "after-chaos",
                    "stream": False, "options": {"num_predict": 2}})
                assert r.status == 200
                assert (await r.json())["done"] is True
            finally:
                await cl.close()
                eng.stop()

    asyncio.run(main())


def test_runtime_recovers_after_step_failure():
    """Failure recovery beyond fail-everything (VERDICT r1 item 10), now
    with retry containment: a failing decode dispatch no longer errors
    the in-flight request — it is requeued (front, with its generated
    tokens folded in for replay), the engine rebuilds the runtime
    (weights reloaded), and BOTH the victim and a request enqueued while
    the runtime was down complete without a process restart."""
    import time

    from ollamamq_tpu.engine.engine import TPUEngine
    from ollamamq_tpu.engine.request import Request
    from ollamamq_tpu.ops.sampling import SamplingParams

    eng = TPUEngine(
        EngineConfig(model="test-tiny", max_slots=4, num_pages=64, page_size=8,
                     max_pages_per_seq=16,
                     max_new_tokens=8, decode_steps_per_iter=2),
        blocklist_path=None,
    )
    eng.recover_interval = 0.2
    eng.start()
    try:
        rt = eng.runtimes["test-tiny"]
        tok = rt.tokenizer

        def boom(*a, **kw):
            raise RuntimeError("injected device failure")

        rt._dispatch_decode = boom

        def start_req(user):
            rid = eng.core.enqueue(user, "", "test-tiny")
            req = Request(rid, user, "test-tiny", tok.encode("hello"),
                          SamplingParams(max_tokens=4))
            eng.submit(req)
            return req

        def finish(req):
            deadline = time.monotonic() + 120
            while time.monotonic() < deadline:
                item = req.stream.get(timeout=0.2)
                if item and item.kind in ("done", "error"):
                    return item
            raise TimeoutError(req.user)

        victim = start_req("victim")
        # The failed dispatch kills the runtime; the victim is retried,
        # not errored.
        deadline = time.monotonic() + 60
        while not rt._failed and time.monotonic() < deadline:
            time.sleep(0.05)
        assert rt._failed and not rt.has_capacity()
        assert victim.retries == 1

        # Enqueue while the runtime is STILL failed: the request must wait
        # in queue ("stuck in queue" semantics), not error.
        sreq = start_req("survivor")

        # The engine swaps in a fresh runtime on its recovery cadence,
        # then serves the retried victim AND the parked survivor.
        deadline = time.monotonic() + 60
        while eng.runtimes["test-tiny"] is rt and time.monotonic() < deadline:
            time.sleep(0.05)
        assert eng.runtimes["test-tiny"] is not rt, "runtime never rebuilt"

        item = finish(victim)
        assert item.kind == "done", getattr(item, "error", None)
        assert len(victim.generated_ids) == 4
        item = finish(sreq)
        assert item.kind == "done", getattr(item, "error", None)
        snap = eng.core.snapshot()
        assert snap["users"]["survivor"]["processed"] == 1
        assert snap["users"]["victim"]["processed"] == 1
        assert snap["users"]["victim"].get("dropped", 0) == 0
        assert sum(u["processing"] for u in snap["users"].values()) == 0
    finally:
        eng.stop()


def test_poisoned_request_errors_after_repeated_runtime_failure():
    """The flip side of retry containment: a request that fails its
    retried dispatch too is poisoned with an explicit error — one bad
    input cannot crash-loop the engine through endless rebuilds."""
    import time

    from ollamamq_tpu.engine.engine import ModelRuntime, TPUEngine
    from ollamamq_tpu.engine.request import Request
    from ollamamq_tpu.ops.sampling import SamplingParams

    eng = TPUEngine(
        EngineConfig(model="test-tiny", max_slots=4, num_pages=64, page_size=8,
                     max_pages_per_seq=16,
                     max_new_tokens=8, decode_steps_per_iter=2),
        blocklist_path=None,
    )
    eng.recover_interval = 0.2
    eng.start()

    def boom(self, *a, **kw):
        raise RuntimeError("injected persistent device failure")

    # Patch the CLASS so every rebuilt runtime fails too.
    orig = ModelRuntime._dispatch_decode
    ModelRuntime._dispatch_decode = boom
    try:
        rt = eng.runtimes["test-tiny"]
        rid = eng.core.enqueue("victim", "", "test-tiny")
        req = Request(rid, "victim", "test-tiny", rt.tokenizer.encode("hi"),
                      SamplingParams(max_tokens=4))
        eng.submit(req)
        deadline = time.monotonic() + 120
        item = None
        while time.monotonic() < deadline:
            item = req.stream.get(timeout=0.2)
            if item and item.kind in ("done", "error"):
                break
        assert item is not None and item.kind == "error"
        assert "poisoned" in item.error
        assert req.retries == 1
    finally:
        ModelRuntime._dispatch_decode = orig
        eng.stop()
