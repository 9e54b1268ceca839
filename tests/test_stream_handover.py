"""One hand-over a (step, stream) (PR 36): the engine settles a row's
tokens of one step — 1 on a ragged step, k of a fused scan, the accepted
run of a speculated row — into ONE stream item, wakes each consumer thread
once a settled step, and the server writes one frame an item.

What a client, the WAL and the resume registry get is the parent's
per-token stream, id for id and byte for byte: the literals below are the
PARENT's (commit 9180caa, one item and one frame a token), captured on the
CPU at float32 with the same scenarios; only the number of hand-overs
differs."""

import asyncio
import json
import os
import threading

import jax.numpy as jnp
import pytest
from aiohttp.test_utils import TestClient, TestServer

from ollamamq_tpu.config import EngineConfig
from ollamamq_tpu.durability.wal import WAL_NAME, load_wal_records
from ollamamq_tpu.engine.engine import TPUEngine
from ollamamq_tpu.engine.fake import FakeEngine
from ollamamq_tpu.engine.request import (FinishReason, Request, StreamItem,
                                         TokenStream, wake_batch)
from ollamamq_tpu.ops.sampling import SamplingParams
from ollamamq_tpu.server.app import Server, _LoopWaker
from ollamamq_tpu.telemetry import schema as tm
from ollamamq_tpu.telemetry.stepprof import PROFILER
from test_step_overlap import _prompt, _rt

TINY = dict(model="test-tiny", max_slots=4, num_pages=96, page_size=8,
            max_pages_per_seq=16,
            max_batch_tokens=32, token_granule=8, decode_steps_per_iter=8)

REPL = "�"


def _engine(**over):
    return TPUEngine(EngineConfig(**dict(TINY, **over)),
                     models={"test-tiny": None}, blocklist_path=None,
                     dtype=jnp.float32)


def drive(eng, arrivals, hook=None):
    """Tick the loop by hand (no engine thread) until every request has
    finished. arrivals: [(tick, name, prompt_ids, SamplingParams)];
    `hook(name, req)` runs at enqueue. Returns ({name: (req, items)},
    step samples)."""
    PROFILER.reset()
    rt = _rt(eng)
    reqs, tick, todo = {}, 0, sorted(arrivals, key=lambda a: a[0])
    while todo or not all(r.stats.finished_at for r in reqs.values()):
        while todo and todo[0][0] <= tick:
            _, name, prompt, sampling = todo.pop(0)
            reqs[name] = eng.enqueue_request(
                name, "", rt.name, prompt_tokens=list(prompt),
                sampling=sampling)
            if hook is not None:
                hook(name, reqs[name])
        eng._loop_once()
        tick += 1
        assert tick < 3000
    eng._settle_all()
    assert rt.cache.alloc.used_pages == 0 and not rt._ahead.any()
    return ({n: (r, r.stream.drain()) for n, r in reqs.items()},
            PROFILER.tail())


def _flat(items):
    toks = [i for i in items if i.kind == "token"]
    for i in toks:  # an item's text is its tokens' chunks, joined
        assert not i.token_ids or "".join(i.texts) == i.text
        assert len(i.texts) == len(i.token_ids)
    return ([t for i in toks for t in i.token_ids],
            "".join(i.text for i in toks))


# -- (a) the parent's per-token streams, id for id and byte for byte ------
SCAN = [(0, "a", _prompt(0, 9), SamplingParams(
            max_tokens=21, temperature=0.9, top_k=40, top_p=0.95, seed=1234)),
        (0, "g", _prompt(2, 5), SamplingParams(max_tokens=19)),
        (6, "b", _prompt(1, 40), SamplingParams(max_tokens=10))]
SPEC = [(i, f"u{i}", (_prompt(i, 6) * 3)[:14 + i],
         SamplingParams(max_tokens=12)) for i in range(2)]
PARENT = {
    "scan": {
        "a": ([115, 454, 293, 87, 48, 506, 384, 62, 222, 343, 243, 15, 264,
               335, 91, 270, 462, 500, 37, 487, 67],
              "pT-;" + REPL * 2 + "\fX\"@"),
        "g": ([457, 490, 466, 351, 466, 351, 466, 351, 466, 234, 466, 349,
               234, 50, 340, 226, 349, 466, 234], REPL * 2 + "/" + REPL),
        "b": ([487, 493, 467, 139, 467, 134, 366, 376, 40, 245],
              REPL * 2 + "%"),
    },
    "spec": {
        "u0": ([85, 277, 167, 44, 240, 85, 400, 274, 25, 509, 85, 475],
               "R" + REPL + ")" + REPL + "R\x16R"),
        "u1": ([210, 372, 207, 380, 380, 407, 366, 207, 380, 454, 228, 368],
               REPL * 3),
    },
}


@pytest.mark.parametrize("name,arrivals,over", [
    ("scan", SCAN, {}), ("spec", SPEC, {"spec": True, "spec_k": 3})],
    ids=["ragged_steps_and_k8_scans", "speculated_rows"])
def test_engine_streams_are_the_parents_per_token_streams(name, arrivals,
                                                          over):
    out, samples = drive(_engine(**over), arrivals)
    sizes = set()
    for who, (ids, text) in PARENT[name].items():
        req, items = out[who]
        assert _flat(items) == (ids, text), who
        assert list(req.generated_ids) == ids      # eval_count
        assert items[-1].kind == "done"
        assert items[-1].finish_reason == FinishReason.LENGTH
        n_items = sum(i.kind == "token" for i in items)
        assert n_items < len(ids), who             # fewer hand-overs
        sizes |= {len(i.token_ids) for i in items}
    # A k=8 scan hands a row its eight tokens at once; a speculated row
    # its accepted run (1 + up to spec_k accepted drafts).
    assert 8 in sizes if name == "scan" else sizes & {2, 3, 4}, sizes
    if name == "spec":
        assert any(s["mode"] == "spec_verify" and s["stream_items"]
                   for s in samples)
    gen = [s for s in samples if s["mode"] in ("ragged", "decode",
                                               "spec_verify")]
    assert gen and all("stream_items" in s for s in gen)
    pushed = sum(len(i.token_ids) > 0 for _, items in out.values()
                 for i in items)
    assert sum(s["stream_items"] for s in gen) == pushed
    for s in gen:
        if s["mode"] == "decode" and s["k_cap"] == 8:
            # one item a live row, k tokens each (a row that ends by
            # count inside the scan takes fewer)
            assert 0 < s["stream_items"] <= s["n_decode"]
            assert s["tokens"] <= 8 * s["stream_items"]


# -- (b) a stop string or EOS met at token 3 of a scan's 8 ----------------
def _solo(eng, stop=(), max_tokens=20):
    out, samples = drive(eng, [(0, "a", _prompt(0, 9), SamplingParams(
        max_tokens=max_tokens, stop=stop))])
    req, items = out["a"]
    return req, items, samples


@pytest.fixture(scope="module")
def texty():
    """A tiny engine whose every id has a text of its own (the tiny
    model's ids are mostly unprintable bytes), so a stop string can name
    one sampled id."""
    eng = _engine()
    _rt(eng).tokenizer.make_incremental_decoder = \
        lambda: lambda tok: f"{tok},"
    return eng


def test_a_stop_string_at_token_3_of_a_scans_8(texty):
    req, items, samples = _solo(texty)
    ids, text = _flat(items)
    # ids[0] came from the prompt's ragged step; the first k=8 scan
    # sampled ids[1:9], so its token 3 is ids[3].
    assert [len(i.token_ids) for i in items[:2]] == [1, 8]
    assert ids[3] not in ids[:3]
    stop = f",{ids[3]},"
    req2, items2, samples2 = _solo(texty, stop=(stop,))
    ids2, text2 = _flat(items2)
    assert text2 == text[:text.index(stop)]   # hold-back text suppressed
    assert items2[-1].finish_reason == FinishReason.STOP
    # The id that completed the stop string is counted, never pushed;
    # nothing after it is either.
    assert ids2 == ids[:3] and list(req2.generated_ids) == ids[:4]
    # Only the text says so, and that is read at the settle, behind the
    # next launch which the row already rides: dropped there and counted.
    assert sum(s["wasted_rows"] for s in samples2) == 1


def test_eos_at_token_3_of_a_scans_8(texty, monkeypatch):
    req, items, _ = _solo(texty)
    ids, text = _flat(items)
    assert ids[3] not in ids[:3]
    monkeypatch.setattr(_rt(texty).tokenizer, "eos_id", ids[3])
    req2, items2, samples2 = _solo(texty)
    ids2, text2 = _flat(items2)
    assert ids2 == ids[:3] == list(req2.generated_ids)
    assert text2 == "".join(f"{t}," for t in ids[:3])
    assert items2[-1].finish_reason == FinishReason.STOP
    # The scan's item holds the two tokens before EOS, not its eight.
    assert [len(i.token_ids) for i in items2 if i.kind == "token"] == [1, 2]
    # EOS is among the ids read at collect: the row rides no later step.
    assert sum(s["wasted_rows"] for s in samples2) == 0


# -- (d) one wake-up a settled step --------------------------------------
class _StubLoop:
    """Counts what an event loop would be asked; runs it at once."""

    def __init__(self):
        self.calls = []

    def call_soon_threadsafe(self, cb, *args):
        self.calls.append(args)
        cb(*args)


class _Flag:
    def __init__(self):
        self.n = 0

    def set(self):
        self.n += 1


def test_a_settled_step_of_64_rows_makes_one_call_soon_threadsafe():
    eng = _engine(max_slots=64, num_pages=512, max_batch_tokens=512)
    loop, flags = _StubLoop(), {}
    waker = _LoopWaker(loop)

    def hook(name, req):
        flags[name] = _Flag()
        req.stream.set_waker(waker, flags[name])

    w0 = tm.STREAM_WAKEUPS_TOTAL.value
    arrivals = [(0, f"u{i}", _prompt(i, 4), SamplingParams(max_tokens=18))
                for i in range(64)]
    out, samples = drive(eng, arrivals, hook)
    gen = [s for s in samples if s["mode"] in ("ragged", "decode")]
    # Every settled step that handed anything over woke the loop ONCE.
    assert all(s["stream_wakeups"] == (1 if s["stream_items"] else 0)
               for s in gen), gen
    assert len(loop.calls) == sum(s["stream_wakeups"] for s in gen)
    assert tm.STREAM_WAKEUPS_TOTAL.value - w0 == len(loop.calls)
    # ... for all the streams it touched: a k=8 scan over 64 live rows
    # pushed one item a row and set 64 events in its one call.
    scans = [s for s in gen if s["mode"] == "decode" and s["k_cap"] == 8]
    assert scans and scans[0]["stream_items"] == 64
    assert scans[0]["tokens"] == 8 * 64
    assert max(len(events) for (events,) in loop.calls) == 64
    for name, (req, items) in out.items():
        assert len(req.generated_ids) == 18
        assert [len(i.token_ids) for i in items[:-1]] == [1, 8, 8, 1], name
        # The stream's consumer was woken once a step that touched it:
        # the last step's item and the terminal share one wake-up.
        assert flags[name].n == 4


def test_wake_batch_calls_each_waker_once_with_its_streams_keys():
    calls = []
    a, b = (lambda keys: calls.append(("a", keys)),
            lambda keys: calls.append(("b", keys)))
    s1, s2, s3, quiet = (TokenStream() for _ in range(4))
    s1.set_waker(a, "k1")
    s2.set_waker(a, "k2")
    s3.set_waker(b, "k3")
    s1.push(StreamItem("token", text="x"))          # no batch: at once
    assert calls == [("a", ["k1"])]
    del calls[:]
    with wake_batch() as outer:
        s1.push(StreamItem("token", text="y"))
        with wake_batch():                           # nested: no call of
            s2.push(StreamItem("token", text="z"))   # its own
            quiet.push(StreamItem("token", text="q"))
        s3.push(StreamItem("token", text="w"))
        s1.push(StreamItem("done"))                  # a stream once
        assert calls == []
    assert calls == [("a", ["k1", "k2"]), ("b", ["k3"])]
    assert outer.wakeups == 2
    assert [i.text for i in s1.drain()] == ["x", "y", ""]

    def other_thread():                              # a batch is a thread's
        s2.push(StreamItem("token", text="t"))

    del calls[:]
    with wake_batch():
        t = threading.Thread(target=other_thread)
        t.start()
        t.join()
        assert calls == [("a", ["k2"])]


# -- (e) the wire: NDJSON frames, SSE, the non-streaming collectors -------
HTTP_PARENT = {  # "the quick brown fox", 21 greedy tokens
    "generate_ids": [340, 243, 307, 196, 219, 164, 29, 466, 190, 167, 385,
                     219, 466, 47, 341, 307, 274, 327, 219, 320, 384],
    "generate_text": REPL * 2 + "ء\x1a" + REPL * 3 + ",",
    "chat_ids": [219, 29, 466, 413, 466, 167, 29, 299, 327, 219, 320, 8, 430,
                 499, 351, 219, 219, 429, 385, 188, 190],
    "chat_text": REPL + "\x1a" + REPL + "\x1a" + REPL + "\x05" + REPL
                 + "ع" + REPL,
    "v1_chat_text": REPL + "\x1a" + REPL * 4,
    "v1_completions_text": REPL * 2 + "ء\x1a" + REPL * 3 + ",؎",
}


def _serve(make_engine, fn, timeout_s=120):
    async def main():
        eng = make_engine()
        eng.start()
        cl = TestClient(TestServer(Server(eng, timeout_s).build_app()))
        cl.engine = eng
        await cl.start_server()
        try:
            await fn(cl)
        finally:
            await cl.close()
            eng.stop()

    asyncio.run(main())


def _ndjson(text):
    return [json.loads(line) for line in text.strip().split("\n")]


def _sse(text):
    assert text.endswith("data: [DONE]\n\n")
    return [json.loads(ev[6:]) for ev in text.split("\n\n")
            if ev.startswith("data: {")]


def test_the_wire_carries_the_parents_ids_and_text():
    opts = {"num_predict": 21, "temperature": 0}
    msgs = [{"role": "user", "content": "the quick brown fox"}]
    P = HTTP_PARENT

    async def run(cl):
        f0, t0 = (tm.STREAM_FRAMES_TOTAL.value,
                  tm.STREAM_FRAME_TOKENS_TOTAL.value)
        r = await cl.post("/api/generate", json={
            "model": "test-tiny", "prompt": "the quick brown fox",
            "options": opts})
        lines = _ndjson(await r.text())
        assert [t for ln in lines for t in ln.get("token_ids", ())] \
            == P["generate_ids"]
        assert "".join(ln["response"] for ln in lines) == P["generate_text"]
        assert lines[-1]["eval_count"] == 21
        assert lines[-1]["done_reason"] == "length"
        assert all(ln["req_id"] == lines[0]["req_id"] for ln in lines)
        # One frame an item with text: far fewer than 21, several ids each.
        assert len(lines) < 12
        assert max(len(ln.get("token_ids", ())) for ln in lines) > 1
        assert tm.STREAM_FRAMES_TOTAL.value - f0 == len(lines)
        assert tm.STREAM_FRAME_TOKENS_TOTAL.value - t0 == 21

        r = await cl.post("/api/chat", json={
            "model": "test-tiny", "messages": msgs, "options": opts})
        lines = _ndjson(await r.text())
        assert [t for ln in lines for t in ln.get("token_ids", ())] \
            == P["chat_ids"]
        assert "".join(ln["message"]["content"] for ln in lines) \
            == P["chat_text"]
        assert all(ln["message"]["role"] == "assistant" for ln in lines)
        assert (lines[-1]["eval_count"], lines[-1]["done_reason"]) \
            == (21, "length")

        r = await cl.post("/api/generate", json={
            "model": "test-tiny", "prompt": "the quick brown fox",
            "stream": False, "options": opts})
        body = await r.json()                      # _collect
        assert (body["response"], body["eval_count"], body["done_reason"]) \
            == (P["generate_text"], 21, "length")

        v1 = {"model": "test-tiny", "max_tokens": 21, "temperature": 0}
        r = await cl.post("/v1/chat/completions",
                          json=dict(v1, messages=msgs, stream=True))
        evs = _sse(await r.text())
        deltas = [e["choices"][0]["delta"] for e in evs]
        assert "".join(d.get("content", "") for d in deltas) \
            == P["v1_chat_text"]
        assert deltas[0]["role"] == "assistant"
        assert all("role" not in d for d in deltas[1:])
        assert evs[-1]["choices"][0]["finish_reason"] == "length"
        assert all(e["object"] == "chat.completion.chunk"
                   and e["id"] == evs[0]["id"]
                   and e["created"] == evs[0]["created"] for e in evs)

        r = await cl.post("/v1/completions", json=dict(
            v1, prompt="the quick brown fox", stream=True))
        evs = _sse(await r.text())
        assert "".join(e["choices"][0]["text"] for e in evs) \
            == P["v1_completions_text"]

        r = await cl.post("/v1/chat/completions",
                          json=dict(v1, messages=msgs))
        body = await r.json()                      # _collect
        assert body["choices"][0]["message"]["content"] == P["v1_chat_text"]
        assert body["choices"][0]["finish_reason"] == "length"
        assert body["usage"]["completion_tokens"] == 21

    _serve(_engine, run)


def _fake_spec(**over):
    return FakeEngine(EngineConfig(model="test-tiny", max_slots=8,
                                   spec=True, spec_k=3, **over),
                      models={"test-tiny": None}, blocklist_path=None)


def test_a_frame_is_byte_for_byte_what_json_dumps_gave():
    """The frame is put together from pieces encoded once a request; its
    bytes are those of `json.dumps` of the parent's frame dict, text that
    needs escaping included."""
    async def run(cl):
        r = await cl.post("/api/generate", json={
            "model": "test-tiny", "prompt": "x",
            "options": {"num_predict": 9}})
        raw = (await r.read()).split(b"\n")[:-2]   # the token frames
        assert len(raw) == 3                       # 4 + 4 + 1 words
        for line in raw:
            obj = json.loads(line)
            assert list(obj) == ["model", "created_at", "done", "req_id",
                                 "token_ids", "response"]
            assert json.dumps(obj).encode() == line
        assert [json.loads(ln)["token_ids"] for ln in raw] \
            == [[1, 2, 3, 4], [5, 6, 7, 8], [9]]
        assert json.loads(raw[0])["response"] == "word0 word1 word2 word3 "

        r = await cl.post("/api/chat", json={
            "model": "test-tiny", "options": {"num_predict": 2},
            "messages": [{"role": "user", "content": "x"}]})
        line = (await r.read()).split(b"\n")[0]
        obj = json.loads(line)
        assert list(obj) == ["model", "created_at", "done", "req_id",
                             "token_ids", "message"]
        assert json.dumps(obj).encode() == line

        r = await cl.post("/v1/chat/completions", json={
            "model": "test-tiny", "max_tokens": 9, "stream": True,
            "messages": [{"role": "user", "content": "x"}]})
        evs = [e for e in (await r.read()).split(b"\n\n")
               if e.startswith(b"data: {")]
        assert len(evs) == 4                       # 3 text frames + finish
        for e in evs:
            assert ("data: " + json.dumps(json.loads(e[6:]))).encode() == e

    _serve(_fake_spec, run)


@pytest.mark.parametrize("text", ['say "hi"\n', "café ☃ \\ \x00\x1f",
                                  "\U0001f600 </script>"],
                         ids=["quotes_newline", "latin_snowman_controls",
                              "astral"])
def test_a_frames_text_is_escaped_as_json_dumps_escapes_it(text, monkeypatch):
    """Only the text passes through a JSON string escape: the same one."""
    plain = Request.emit_text
    monkeypatch.setattr(Request, "emit_text",   # every fake word reads `text`
                        lambda self, word: plain(self, text))

    async def run(cl):
        for path, body, key in (
                ("/api/generate", {"prompt": "x"}, lambda o: o["response"]),
                ("/api/chat", {"messages": [{"role": "user", "content": "x"}]},
                 lambda o: o["message"]["content"])):
            r = await cl.post(path, json=dict(
                body, model="test-tiny", options={"num_predict": 9}))
            lines = (await r.read()).split(b"\n")[:-1]
            assert len(lines) == 4
            for line in lines:
                assert json.dumps(json.loads(line)).encode() == line
            assert "".join(key(json.loads(ln)) for ln in lines) == text * 9
        r = await cl.post("/v1/completions", json={
            "model": "test-tiny", "max_tokens": 9, "stream": True,
            "prompt": "x"})
        evs = [e for e in (await r.read()).split(b"\n\n")
               if e.startswith(b"data: {")]
        for e in evs:
            assert ("data: " + json.dumps(json.loads(e[6:]))).encode() == e
        assert "".join(json.loads(e[6:])["choices"][0]["text"]
                       for e in evs) == text * 9

    _serve(_fake_spec, run)


# -- (c) resume inside a coalesced item; the WAL keeps a pair a token -----
def test_resume_from_a_token_inside_a_coalesced_item(tmp_path):
    wal_dir = str(tmp_path / "wal")

    async def run(cl):
        r = await cl.post("/api/generate", json={
            "model": "test-tiny", "prompt": "x",
            "options": {"num_predict": 9}})
        lines = _ndjson(await r.text())
        assert [ln.get("token_ids") for ln in lines[:-1]] \
            == [[1, 2, 3, 4], [5, 6, 7, 8], [9]]
        rid = lines[0]["req_id"]
        words = [f"word{i} " for i in range(9)]
        for n in (0, 2, 5, 8, 9):   # 2 and 5 lie inside an item
            r = await cl.get(f"/api/stream/{rid}?from={n}")
            got = _ndjson(await r.text())
            assert got[-1]["done"] and got[-1]["done_reason"] == "length"
            assert [ln["token_ids"] for ln in got[:-1]] \
                == [[i + 1] for i in range(n, 9)], n
            assert [ln["response"] for ln in got[:-1]] == words[n:], n
        cl.engine.durability.wal.snapshot_lines()   # flushes the buffer
        recs, torn = load_wal_records(os.path.join(wal_dir, WAL_NAME))
        assert torn == 0
        assert recs[rid]["toks"] == [[i + 1, words[i]] for i in range(9)]
        assert recs[rid]["finished"] == "length"

    _serve(lambda: _fake_spec(wal_dir=wal_dir, wal_fsync_ms=2.0), run)


def test_one_deadline_around_the_iteration_times_a_stream_out():
    """No timer an item: the request's timeout is one timer, which wakes
    the consumer as a push would; the stream then ends with the error
    frame and the engine-side request is cancelled."""
    def slow():
        return FakeEngine(EngineConfig(model="test-tiny", max_slots=2),
                          models={"test-tiny": None}, blocklist_path=None,
                          token_latency_s=0.1)

    async def run(cl):
        r = await cl.post("/api/generate", json={
            "model": "test-tiny", "prompt": "x",
            "options": {"num_predict": 16}})
        lines = _ndjson(await r.text())
        assert lines[-1]["done"] and "timeout" in lines[-1]["error"]
        assert 1 <= len(lines) - 1 < 16
        assert [ln["response"] for ln in lines[:-1]] \
            == [f"word{i} " for i in range(len(lines) - 1)]
        rt = cl.engine.runtimes["test-tiny"]
        for _ in range(50):
            if not rt.active:
                break
            await asyncio.sleep(0.02)
        assert not rt.active

    _serve(slow, run, timeout_s=0.45)


def test_no_wake_up_is_lost_between_pushing_threads_and_the_loop():
    """The consumer has no poll to fall back on: every push must reach it
    through its waker, whatever the interleaving of `set_waker`, `push`,
    a batch's flush and the consumer's `get_nowait` / `wait` / `clear`.
    More pushing threads than a step has, a switch interval of 10 µs."""
    import sys

    n_threads, per_thread, rounds = 6, 8, 150
    eng = FakeEngine(EngineConfig(model="test-tiny", max_slots=2),
                     models={"test-tiny": None}, blocklist_path=None)
    server = Server(eng, timeout_s=60)
    reqs = [Request(i, "u", "test-tiny", [1])
            for i in range(n_threads * per_thread)]

    def pusher(mine, start):
        start.wait(10)
        for r in range(rounds):
            with wake_batch():
                for req in mine:
                    req.stream.push(StreamItem.tokens([r], [f"{r},"]))
        for req in mine:            # outside a batch: woken at once
            req.finish(FinishReason.STOP)

    async def consume(req):
        return [i async for i in server._aiter(req)]

    async def main():
        start = threading.Event()
        threads = [threading.Thread(
            target=pusher, args=(reqs[t::n_threads], start), daemon=True)
            for t in range(n_threads)]
        for t in threads:
            t.start()
        tasks = [asyncio.ensure_future(consume(r)) for r in reqs[::2]]
        start.set()                 # half the consumers attach mid-stream
        await asyncio.sleep(0.01)
        tasks += [asyncio.ensure_future(consume(r)) for r in reqs[1::2]]
        got = await asyncio.wait_for(asyncio.gather(*tasks), 60)
        for t in threads:
            t.join(10)
            assert not t.is_alive()
        return got

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        got = asyncio.run(main())
    finally:
        sys.setswitchinterval(old)
        eng.stop()
    for items in got:
        assert items[-1].kind == "done"
        assert [t for i in items for t in i.token_ids] == list(range(rounds))
