"""Drive one run's traffic over HTTP from one thread (asyncio + aiohttp) and
record, per request, what a user would have seen. Times are seconds on the
monotonic clock relative to the start of the window."""

from __future__ import annotations

import asyncio
import dataclasses
import json
import random
import time

import aiohttp

from benchmarks.lib import traffic as tg


@dataclasses.dataclass
class Record:
    index: int
    user: str
    prompt_tokens: int
    num_predict: int
    due_s: float
    sent_s: float | None = None
    first_s: float | None = None     # first frame that carried a token id
    last_s: float | None = None      # last such frame
    tokens: int = 0
    frames: list = dataclasses.field(default_factory=list)  # [(t_s, n ids)]
    status: int | None = None
    done_reason: str | None = None
    error: str | None = None
    ids_in_vocab: bool = True
    ids: list = dataclasses.field(default_factory=list)  # as returned
    prompt: str = ""                 # as sent (the reference reads both)

    @property
    def ok(self) -> bool:
        """Completed as asked: every token, ended by length, ids valid."""
        return (self.error is None and self.status == 200
                and self.done_reason == "length"
                and self.tokens == self.num_predict and self.ids_in_vocab)


class LoadGen:
    def __init__(self, base_url: str, model: str, traffic: dict,
                 vocab_size: int, seed: int, seconds: float):
        self.base_url, self.model, self.traffic = base_url, model, traffic
        self.vocab, self.seed, self.seconds = vocab_size, seed, seconds
        self.options = dict(traffic.get("options") or {})
        self.records: list = []
        self.t0 = None           # monotonic time of the window's start
        self.window_hooks: list = []  # [(at_s, coroutine function)]
        self._session = None

    def now(self) -> float:
        return time.monotonic() - self.t0

    @staticmethod
    def _client() -> aiohttp.ClientSession:
        return aiohttp.ClientSession(
            timeout=aiohttp.ClientTimeout(total=None, sock_connect=30),
            connector=aiohttp.TCPConnector(limit=0))

    async def request(self, p: tg.Planned, due_s: float) -> Record:
        rec = Record(p.index, p.user, p.prompt_tokens, p.num_predict, due_s,
                     prompt=p.prompt)
        self.records.append(rec)
        body = {"model": self.model, "prompt": p.prompt, "stream": True,
                "options": {**self.options, "num_predict": p.num_predict}}
        rec.sent_s = self.now()
        try:
            async with self._session.post(
                    self.base_url + "/api/generate", json=body,
                    headers={"X-User-ID": p.user}) as resp:
                rec.status = resp.status
                if resp.status != 200:
                    rec.error = (await resp.text())[:200]
                    return rec
                async for raw in resp.content:
                    t = self.now()
                    if not raw.strip():
                        continue
                    frame = json.loads(raw)
                    ids = frame.get("token_ids") or ()
                    if ids:
                        if rec.first_s is None:
                            rec.first_s = t
                        rec.last_s = t
                        rec.tokens += len(ids)
                        rec.frames.append((t, len(ids)))
                        if not all(0 <= i < self.vocab for i in ids):
                            rec.ids_in_vocab = False
                        rec.ids.extend(ids)
                    if frame.get("error"):
                        rec.error = str(frame["error"])[:200]
                    if frame.get("done"):
                        rec.done_reason = frame.get("done_reason")
        except asyncio.CancelledError:
            rec.error = rec.error or "not finished within the drain limit"
            raise
        except (aiohttp.ClientError, OSError, ValueError) as e:
            rec.error = f"{type(e).__name__}: {e}"[:200]
        return rec

    # ------------------------------------------------------------- loops
    async def _open(self, tasks: list) -> None:
        plan = tg.open_schedule(self.traffic, self.seed, self.seconds)
        rng = random.Random(self.seed + 11)
        counter = [len(plan)]

        async def one(p: tg.Planned, due_s: float) -> None:
            delay = due_s - self.now()
            if delay > 0:
                await asyncio.sleep(delay)
            await self.request(p, due_s)
            think = float((self.traffic.get("session") or {})
                          .get("think_s", 0.0))
            nxt = tg.follow_up(p, self.traffic, counter[0], rng)
            if nxt is not None and self.now() + think < self.seconds:
                counter[0] += 1
                tasks.append(asyncio.ensure_future(
                    one(nxt, self.now() + think)))

        for p in plan:
            tasks.append(asyncio.ensure_future(one(p, p.due_s)))

    async def _closed(self, tasks: list) -> None:
        plans = tg.closed_plan(self.traffic, self.seed)
        ramp = float(self.traffic.get("ramp_s", 0.0))

        async def client(c: int, mine: list) -> None:
            # clients start spread over the first half of the ramp
            await asyncio.sleep(0.5 * ramp * c / max(1, len(plans)))
            k = 0
            while self.now() < self.seconds:
                await self.request(mine[k % len(mine)], self.now())
                k += 1

        for c, mine in enumerate(plans):
            tasks.append(asyncio.ensure_future(client(c, mine)))

    async def _run(self) -> None:
        kind = self.traffic.get("kind")
        if kind not in tg.KINDS:
            raise tg.TrafficError(f"unknown traffic kind {kind!r}")
        async with self._client() as session:
            self._session = session
            self.t0 = time.monotonic() + float(self.traffic.get("ramp_s", 0))
            tasks: list = []
            hooks = [asyncio.ensure_future(self._hook(at, fn))
                     for at, fn in self.window_hooks]
            await (self._open(tasks) if kind == "open_poisson_burst"
                   else self._closed(tasks))
            await asyncio.sleep(max(0.0, self.seconds - self.now()))
            deadline = self.seconds + float(self.traffic.get("drain_s", 30))
            while any(not t.done() for t in tasks) and self.now() < deadline:
                await asyncio.sleep(0.05)
            pending = [t for t in tasks if not t.done()]
            for t in pending:
                t.cancel()
            await asyncio.gather(*tasks, *hooks, return_exceptions=True)

    async def _hook(self, at_s: float, fn) -> None:
        await asyncio.sleep(max(0.0, at_s - self.now()))
        await fn(self._session)

    def run(self) -> list:
        """Ramp, window and drain. Returns every record, ramp included."""
        asyncio.run(self._run())
        return self.records

    # ---------------------------------------------------------- warm-up
    def alone(self, planned: list, gap_s: float = 0.0) -> list:
        """Send `planned` outside any window (set-up): all at once when
        gap_s is 0, else gap_s apart. Returns their records."""
        async def go():
            async with self._client() as session:
                self._session, self.t0 = session, time.monotonic()

                async def one(i, p):
                    await asyncio.sleep(i * gap_s)
                    return await self.request(p, i * gap_s)
                return await asyncio.gather(
                    *(one(i, p) for i, p in enumerate(planned)))
        out = asyncio.run(go())
        self.records = []
        return out


def lateness_ms(records: list) -> dict:
    """How late the generator sent, against when each request was due."""
    late = sorted(1e3 * (r.sent_s - r.due_s) for r in records
                  if r.sent_s is not None)
    if not late:
        return {"n": 0}
    return {"n": len(late), "median_ms": late[len(late) // 2],
            "max_ms": late[-1]}
