"""One general traffic generator. A mix is a data file of parameters (see
traffic/*.json and README.md); this module turns it and a seed into a
schedule. Two kinds: `open_poisson_burst` (arrival times fixed in advance)
and `closed` (each client sends its next request when the last one ended).

Every seed gets the same SET of lengths and of gaps between arrivals —
stratified quantiles of the mix's distributions — so that seeds change the
interleaving and not the amount of work. Arrival times and the order of
lengths come from a fixed base order; a seed permutes lengths and users
only inside blocks of BLOCK consecutive requests (and writes its own
texts), so that the load over time is the same for every seed: on the chip,
two seeds with a free order differed by 7 % in a 95th percentile where two
runs of one seed differed by 0.5 % (PR 23).
"""

from __future__ import annotations

import dataclasses
import json
import math
import random
import statistics

KINDS = ("open_poisson_burst", "closed")
_WORDS = ("the", "chip", "serves", "tokens", "from", "pages", "of", "keys",
          "and", "values", "while", "users", "wait", "in", "fair", "queues",
          "a", "long", "prompt", "fills", "its", "span", "then", "decodes")
_NORMAL = statistics.NormalDist()
BLOCK = 8
BASE_SEED = 20260927


class TrafficError(Exception):
    """The traffic file asks for something the generator cannot draw."""


@dataclasses.dataclass
class Planned:
    """One request as planned. `due_s` is relative to the start of the
    window (negative in the ramp); None in a closed loop, where a request
    is due when its client's previous one ended."""
    index: int
    user: str
    prompt: str
    prompt_tokens: int   # byte tokens + BOS
    num_predict: int
    due_s: float | None = None
    turn: int = 0


def quantiles(dist: dict, n: int) -> list:
    """n stratified values of `dist`, ascending: its inverse CDF at
    (i + 0.5) / n, rounded to whole tokens and clipped to min..max."""
    kind = dist.get("dist")
    out = []
    for i in range(n):
        p = (i + 0.5) / n
        if kind == "fixed":
            v = dist["value"]
        elif kind == "uniform":
            v = dist["min"] + p * (dist["max"] - dist["min"])
        elif kind == "lognormal":
            v = math.exp(math.log(dist["median"])
                         + dist["sigma"] * _NORMAL.inv_cdf(p))
        else:
            raise TrafficError(f"unknown length distribution {kind!r}")
        lo, hi = dist.get("min", v), dist.get("max", v)
        out.append(int(round(min(max(v, lo), hi))))
    return out


def user_names(users: dict, n: int, rng: random.Random) -> list:
    """n user names out of users.count, with Zipf(zipf_s) shares (0 = even):
    the counts are fixed (largest remainder), the order is the seed's."""
    count, s = int(users["count"]), float(users.get("zipf_s", 0.0))
    w = [1.0 / (k + 1) ** s for k in range(count)]
    tot = sum(w)
    exact = [n * x / tot for x in w]
    counts = [int(x) for x in exact]
    by_rest = sorted(range(count), key=lambda k: exact[k] - counts[k],
                     reverse=True)
    for k in by_rest[: n - sum(counts)]:
        counts[k] += 1
    names = [f"user{k:03d}" for k in range(count) for _ in range(counts[k])]
    rng.shuffle(names)
    return names


def make_text(tag: str, n_chars: int, rng: random.Random,
              prefix: str = "") -> str:
    """ASCII text of exactly n_chars (one byte token each). It opens with
    the shared prefix, if any, then a tag of its own, so that two prompts
    share nothing but a prefix they were given."""
    text = prefix + tag + " "
    while len(text) < n_chars:
        text += rng.choice(_WORDS) + " "
    return text[:n_chars]


def _prefixes(traffic: dict) -> list:
    sp = traffic.get("shared_prefix") or {}
    if not sp.get("share") or not sp.get("tokens"):
        return []
    rng = random.Random(7)  # the same system prompts in every run
    return [make_text(f"system{g}", int(sp["tokens"]), rng)
            for g in range(max(1, int(sp.get("groups", 1))))]


def _seeded_order(values: list, base: random.Random,
                  rng: random.Random) -> list:
    """The fixed base order of `values`, then the seed's own order inside
    each block of BLOCK."""
    values = list(values)
    base.shuffle(values)
    for i in range(0, len(values), BLOCK):
        block = values[i:i + BLOCK]
        rng.shuffle(block)
        values[i:i + BLOCK] = block
    return values


def _requests(traffic: dict, n: int, rng: random.Random, first: int,
              tag: str) -> list:
    """n planned requests (no due times yet), numbered from `first`."""
    base = random.Random(BASE_SEED + first)
    prompts = _seeded_order(quantiles(traffic["prompt_tokens"], n), base, rng)
    outputs = _seeded_order(quantiles(traffic["output_tokens"], n), base, rng)
    users = _seeded_order(sorted(user_names(traffic["users"], n, base)),
                          base, rng)
    prefixes = _prefixes(traffic)
    share = (traffic.get("shared_prefix") or {}).get("share", 0.0)
    plan = []
    for i in range(n):
        prefix = ""
        if prefixes and rng.random() < share:
            prefix = prefixes[rng.randrange(len(prefixes))]
        n_chars = max(prompts[i] - 1, len(prefix) + 8)
        plan.append(Planned(
            index=first + i, user=users[i],
            prompt=make_text(f"{tag}{first + i}", n_chars, rng, prefix),
            prompt_tokens=n_chars + 1, num_predict=outputs[i]))
    return plan


def _rate_at(traffic: dict, t: float) -> float:
    """Arrivals a second at time t (0 = start of the window). The mean over
    a burst period is rate_per_s; bursts sit at fixed times, not the seed's."""
    rate = float(traffic["rate_per_s"])
    b = traffic.get("burst") or {}
    period, length = float(b.get("period_s", 0)), float(b.get("length_s", 0))
    factor = float(b.get("factor", 1.0))
    if period <= 0 or length <= 0 or factor == 1.0:
        return rate
    base = rate / (1.0 + (factor - 1.0) * length / period)
    # the burst is the last `length` seconds of each period
    return base * factor if (t % period) >= period - length else base


def _arrivals(traffic: dict, t0: float, t1: float) -> list:
    """Arrival times in [t0, t1): a fixed number of them (the rate's
    integral), with exponential gaps in operational time in a fixed order —
    the same times for every seed — mapped through the rate's integral."""
    rng = random.Random(BASE_SEED)
    step = 0.01
    grid, acc = [], 0.0
    t = t0
    while t < t1:
        grid.append((t, acc))
        acc += _rate_at(traffic, t + step / 2) * min(step, t1 - t)
        t += step
    n = int(round(acc))
    if n <= 0:
        return []
    gaps = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = acc / (sum(gaps) + gaps[0])  # the last arrival falls before t1
    rng.shuffle(gaps)
    out, s, j = [], 0.0, 0
    for g in gaps:
        s += g * scale
        while j + 1 < len(grid) and grid[j + 1][1] <= s:
            j += 1
        tj, aj = grid[j]
        rate = _rate_at(traffic, tj + step / 2)
        out.append(min(tj + (s - aj) / rate, t1 - 1e-6))
    return out


def open_schedule(traffic: dict, seed: int, seconds: float) -> list:
    """Every request of an open-loop run, ramp included, by due time."""
    ramp = float(traffic.get("ramp_s", 0.0))
    plan = []
    for tag, t0, t1, salt in (("r", -ramp, 0.0, 1), ("w", 0.0, seconds, 2)):
        rng = random.Random(seed * 1000003 + salt)
        times = _arrivals(traffic, t0, t1)
        reqs = _requests(traffic, len(times), rng, len(plan), tag)
        for r, t in zip(reqs, times):
            r.due_s = t
        plan.extend(reqs)
    return plan


def closed_plan(traffic: dict, seed: int, per_client: int = 64) -> list:
    """Per client, the requests it sends one after another (it starts over
    when they run out). Client c is user c."""
    clients = int(traffic["clients"])
    rng = random.Random(seed * 1000003 + 3)
    reqs = _requests(traffic, clients * per_client, rng, 0, "c")
    plans = [reqs[c::clients] for c in range(clients)]
    for c, mine in enumerate(plans):
        for r in mine:
            r.user = f"user{c:03d}"
    return plans


def follow_up(prev: Planned, traffic: dict, index: int,
              rng: random.Random) -> Planned | None:
    """The next turn of a session: the same user sends the previous prompt
    again with a new tail, so it shares that prefix (session.turns > 1)."""
    sess = traffic.get("session") or {}
    if prev.turn + 1 >= int(sess.get("turns", 1)):
        return None
    tail = quantiles(traffic["prompt_tokens"], 8)[rng.randrange(8)] // 4
    text = make_text(f"t{index}", len(prev.prompt) + 1 + max(8, tail), rng,
                     prev.prompt + " ")
    return Planned(index=index, user=prev.user, prompt=text,
                   prompt_tokens=len(text) + 1,
                   num_predict=prev.num_predict, turn=prev.turn + 1)


def rehearsal(traffic: dict) -> dict:
    """The mix at a size the CPU can serve in a few seconds (a rehearsal,
    never a measurement): lengths cut to a sixteenth (prompts <= 96 tokens,
    outputs <= 6), a quarter of the rate, at most 6 clients."""
    t = json.loads(json.dumps(traffic))
    for key, cap in (("prompt_tokens", 96), ("output_tokens", 6)):
        d = t[key]
        for k in ("median", "min", "max", "value"):
            if k in d:
                d[k] = max(2, min(cap, int(d[k]) // 16))
    if "rate_per_s" in t:
        t["rate_per_s"] = min(3.0, float(t["rate_per_s"]) / 4)
    if "clients" in t:
        t["clients"] = min(6, int(t["clients"]))
        t["users"]["count"] = t["clients"]
    t["ramp_s"], t["drain_s"] = 1.0, 60.0
    return t
