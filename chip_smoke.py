#!/usr/bin/env python3
"""Chip smoke test: prove that the serving path starts and answers on a TPU.

    python chip_smoke.py                  # one chip: what the driver runs
    python chip_smoke.py --chips4 [tp|replicas]   # four chips (the builder's)
    python chip_smoke.py --rehearse-cpu   # same control flow, tiny, on the CPU

One chip: build cpp/libmqcore.so from the committed sources, start
`python -m ollamamq_tpu.cli --models llama3.2:1b` (full width and depth,
seeded random weights, engine defaults) as a child, send greedy requests
over /api/generate, /api/chat (NDJSON stream) and /v1/chat/completions plus
one burst of 8 users with mixed prompt lengths, read the server's own status
and fail unless it ran on a TPU with the Pallas kernels and no failure,
retry or shed; restart it once on the compile cache the first start filled;
then run both Pallas kernels against the jnp reference in a second child.

Four chips (--chips4): `--models llama3:8b --tp 4` against the same server
under OLLAMAMQ_NO_PALLAS=1, and `--models llama3.2:1b --replicas 4`; no
one-chip phase runs.

This process never imports jax: a chip belongs to one process at a time, so
the children that need it run one after another. The compile cache goes
where JAX_COMPILATION_CACHE_DIR says, else to .jax_cache in the checkout
(ollamamq_tpu/platform_force.py).

Every phase prints one JSON line. The last line of stdout is
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
with the device as the server reported it; any failed phase makes `ok` false
and the exit code 1. --rehearse-cpu therefore ends `ok: false` at the
platform check, having passed the request phases.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import random
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(ROOT, "chiprun_out", "chip_smoke")
DEADLINE_S = 1150.0  # the driver allows 1200 s, compilation included
N_PREDICT = 16
BURST_PREDICT = 24
BURST_PROMPT_LENS = (16, 48, 96, 150, 220, 300, 360, 400)

_children: list = []
_failed: list = []  # names of the phases that failed


class PhaseFailed(Exception):
    """A phase's check did not hold. `fields` is what the phase had
    gathered by then; it is printed with the failure."""

    def __init__(self, msg: str, fields: dict | None = None):
        super().__init__(msg)
        self.fields = fields or {}


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise PhaseFailed(msg)


def check_all(fields: dict, checks: list) -> dict:
    """Every (holds, message) pair must hold. The gathered `fields` are
    reported either way, with every message that did not."""
    broken = [msg for holds, msg in checks if not holds]
    if broken:
        raise PhaseFailed("; ".join(broken), fields)
    return fields


def phase(name: str, fn, *args, **kw) -> dict | None:
    """Run one phase and print its line. A failure is recorded — never
    skipped — and the next phase still runs. Returns the phase's fields,
    None when it failed."""
    t0 = time.monotonic()
    try:
        fields, ok = fn(*args, **kw) or {}, True
    except Exception as e:  # noqa: BLE001 — recorded and reported, not hidden
        fields = {**getattr(e, "fields", {}),
                  "error": f"{type(e).__name__}: {e}"}
        ok = False
        _failed.append(name)
    print(json.dumps({"phase": name, "ok": ok, **fields,
                      "wall_s": round(time.monotonic() - t0, 1)}), flush=True)
    return fields if ok else None


# --------------------------------------------------------------- children
def spawn(argv: list, log_name: str, rehearse: bool,
          env_extra: dict | None = None) -> subprocess.Popen:
    os.makedirs(OUT_DIR, exist_ok=True)
    env = dict(os.environ)
    # A sandbox pins jax to the CPU through the environment. This script
    # exists to test the chip, so that pin is not a request to serve from
    # the CPU: without a TPU the server must refuse to start.
    if not rehearse and env.get("JAX_PLATFORMS", "").lower() == "cpu":
        del env["JAX_PLATFORMS"]
    env.setdefault("TPU_LOG_DIR", "disabled")
    env.update(env_extra or {})
    path = os.path.join(OUT_DIR, log_name)
    with open(path, "w") as log:
        proc = subprocess.Popen(argv, cwd=ROOT, env=env, stdout=log,
                                stderr=subprocess.STDOUT,
                                start_new_session=True)
    proc.log_path = path
    _children.append(proc)
    return proc


def kill(proc: subprocess.Popen) -> None:
    with contextlib.suppress(ProcessLookupError, PermissionError):
        os.killpg(proc.pid, signal.SIGKILL)
    with contextlib.suppress(subprocess.TimeoutExpired):
        proc.wait(timeout=10)


def kill_all() -> None:
    for proc in _children:
        if proc.poll() is None:
            kill(proc)


def log_tail(proc: subprocess.Popen, n: int) -> list:
    try:
        with open(proc.log_path, errors="replace") as f:
            return f.read().splitlines()[-n:]
    except OSError:
        return []


# ------------------------------------------------------------------- http
def http(port: int, path: str, body: dict | None = None, user: str = "smoke",
         timeout: float = 600.0):
    """(status, parsed JSON | list of NDJSON frames | text)."""
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=data,
        headers={"X-User-ID": user, "Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            status, raw = r.status, r.read().decode()
            ctype = r.headers.get("Content-Type", "")
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode(errors="replace")
    if "ndjson" in ctype:
        return status, [json.loads(line) for line in raw.splitlines() if line]
    if "json" in ctype:
        return status, json.loads(raw)
    return status, raw


class Server:
    """One `python -m ollamamq_tpu.cli` child serving `model`."""

    def __init__(self, tag: str, model: str, argv: list, rehearse: bool,
                 env_extra: dict | None = None):
        self.tag, self.model = tag, model
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            self.port = s.getsockname()[1]
        self.t0 = time.monotonic()
        self.proc = spawn(
            [sys.executable, "-m", "ollamamq_tpu.cli", "--no-tui", "--host",
             "127.0.0.1", "--port", str(self.port), "--models", model] + argv,
            f"{tag}_server.log", rehearse, env_extra)

    def wait_health(self, timeout: float) -> dict:
        while time.monotonic() - self.t0 < timeout:
            rc = self.proc.poll()
            check(rc is None, f"server exited with code {rc} before /health: "
                              + " | ".join(log_tail(self.proc, 6)))
            with contextlib.suppress(urllib.error.URLError, OSError):
                if http(self.port, "/health", timeout=5)[0] == 200:
                    return {"server": self.tag, "health_s": round(
                        time.monotonic() - self.t0, 1)}
            time.sleep(1.0)
        raise PhaseFailed(f"no /health within {timeout:.0f}s: "
                          + " | ".join(log_tail(self.proc, 6)))

    def generate_ids(self, prompt: str, n: int, user: str = "smoke") -> list:
        """Greedy streaming /api/generate; the sampled token ids."""
        status, frames = http(self.port, "/api/generate", {
            "model": self.model, "prompt": prompt, "stream": True,
            "options": {"temperature": 0, "num_predict": n}}, user=user)
        check(status == 200 and isinstance(frames, list),
              f"/api/generate stream -> {status}: {str(frames)[:200]}")
        last = frames[-1]
        check(last.get("done") is True and "error" not in last
              and last.get("done_reason") == "length"
              and last.get("eval_count") == n,
              f"want {n} tokens ending in 'length', got {last}")
        ids = [t for f in frames for t in f.get("token_ids", [])]
        check(len(ids) == n, f"{len(ids)} token ids for num_predict {n}")
        return ids

    def stop(self) -> dict:
        """SIGTERM: the server drains, flushes and exits 0."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                kill(self.proc)
                raise PhaseFailed("server ignored SIGTERM for 60s; killed")
        check(self.proc.returncode == 0,
              f"server exited with code {self.proc.returncode}")
        return {"server": self.tag}


@contextlib.contextmanager
def serving(tag: str, model: str, argv: list, rehearse: bool,
            env_extra: dict | None = None, health_timeout: float = 600.0):
    """Start a server, yield it once /health answers (None if it never
    does), stop it afterwards; both ends are phases of their own."""
    server = Server(tag, model, argv, rehearse, env_extra)
    try:
        up = phase(f"{tag}_start", server.wait_health, health_timeout)
        yield server if up else None
        if up:
            phase(f"{tag}_stop", server.stop)
    finally:
        if server.proc.poll() is None:
            kill(server.proc)


def prompt_of(rng: random.Random, n_chars: int) -> str:
    words = ("the", "chip", "serves", "tokens", "from", "pages", "of", "keys",
             "and", "values", "while", "users", "wait", "in", "fair", "queues")
    text = ""
    while len(text) < n_chars:
        text += rng.choice(words) + " "
    return text[:n_chars]


def cache_dir() -> str:
    from ollamamq_tpu.platform_force import compile_cache_dir  # jax-free

    return compile_cache_dir()


def cache_entries() -> set:
    """Names, not a count: a cache kept at a size cap (the chip machines
    come with one) evicts as it writes, so only new names show a write."""
    try:
        return set(os.listdir(cache_dir()))
    except OSError:
        return set()


# ----------------------------------------------------------------- phases
def build_native() -> dict:
    """cpp/libmqcore.so is git-ignored and rebuilt by mtime; a copied
    tree can carry a stale binary with fresh mtimes. Always rebuild."""
    r = subprocess.run(["make", "-B", "-C", os.path.join(ROOT, "cpp")],
                       capture_output=True, text=True)
    check(r.returncode == 0, f"make failed: {r.stderr[-400:]}")
    return {"built": "cpp/libmqcore.so"}


def versions() -> dict:
    from importlib import metadata

    out = {"python": sys.version.split()[0], "cache_dir": cache_dir()}
    for pkg in ("jax", "jaxlib", "libtpu", "numpy"):
        try:
            out[pkg] = metadata.version(pkg)
        except metadata.PackageNotFoundError:
            out[pkg] = None
    return out


def requests_phase(server: Server, rng: random.Random) -> dict:
    model, port = server.model, server.port
    p1 = prompt_of(rng, 20)
    opts = {"temperature": 0, "num_predict": N_PREDICT}
    # /api/generate, non-streaming.
    t0 = time.monotonic()
    status, body = http(port, "/api/generate", {
        "model": model, "prompt": p1, "stream": False, "options": opts})
    first_request_s = round(time.monotonic() - t0, 1)
    check(status == 200, f"/api/generate -> {status}: {str(body)[:200]}")
    check(body["done_reason"] == "length" and body["eval_count"] == N_PREDICT
          and body["prompt_eval_count"] == len(p1) + 1,  # byte tokens + BOS
          f"/api/generate answered {body}")
    # The same greedy prompt twice gives the same tokens.
    ids_a = server.generate_ids(p1, N_PREDICT)
    ids_b = server.generate_ids(p1, N_PREDICT)
    check(ids_a == ids_b, f"greedy repeat differs: {ids_a} vs {ids_b}")
    # /api/chat, streaming NDJSON.
    status, frames = http(port, "/api/chat", {
        "model": model, "stream": True, "options": opts,
        "messages": [{"role": "user", "content": prompt_of(rng, 40)}]})
    check(status == 200 and isinstance(frames, list),
          f"/api/chat -> {status}: {str(frames)[:200]}")
    last = frames[-1]
    check(last.get("done") is True and last.get("done_reason") == "length"
          and last.get("eval_count") == N_PREDICT, f"/api/chat ended {last}")
    check(sum(len(f.get("token_ids", [])) for f in frames) == N_PREDICT,
          "/api/chat frames do not carry every token id")
    # /v1/chat/completions.
    status, body = http(port, "/v1/chat/completions", {
        "model": model, "temperature": 0, "max_tokens": N_PREDICT,
        "messages": [{"role": "user", "content": prompt_of(rng, 30)}]})
    check(status == 200, f"/v1/chat/completions -> {status}: {str(body)[:200]}")
    check(body["choices"][0]["finish_reason"] == "length"
          and body["usage"]["completion_tokens"] == N_PREDICT,
          f"/v1/chat/completions ended {body['choices'][0]} {body['usage']}")
    return {"first_request_s": first_request_s, "prompt": p1,
            "greedy_ids": ids_a}


def burst_phase(server: Server, rng: random.Random,
                prompt_lens=BURST_PROMPT_LENS) -> dict:
    """8 users at once, mixed prompt lengths: the ragged mixed
    prefill+decode dispatch, then the fused decode scan once all are in."""
    prompts = [prompt_of(rng, n) for n in prompt_lens]
    out: list = [None] * len(prompts)

    def one(i: int) -> None:
        try:
            out[i] = server.generate_ids(prompts[i], BURST_PREDICT,
                                         user=f"user{i}")
        except Exception as e:  # noqa: BLE001 — reported below
            out[i] = e

    threads = [threading.Thread(target=one, args=(i,))
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=900)
    check(not any(t.is_alive() for t in threads), "burst request hung")
    errs = [f"user{i}: {r}" for i, r in enumerate(out)
            if not isinstance(r, list)]
    check(not errs, "; ".join(errs)[:600])
    return {"users": len(prompts), "tokens_each": BURST_PREDICT,
            "prompt_tokens": [n + 1 for n in prompt_lens]}


def server_status(server: Server, cfg, want_impl: str = "pallas"):
    """(raw stats, fields to print, checks) from the server's own account
    of what it ran on: /metrics.json, /metrics, /debug/stepprof."""
    status, stats = http(server.port, "/metrics.json")
    check(status == 200, f"/metrics.json -> {status}")
    status, prom = http(server.port, "/metrics")
    check(status == 200, f"/metrics -> {status}")
    status, prof = http(server.port, "/debug/stepprof")
    check(status == 200, f"/debug/stepprof -> {status}")
    compiles = {}
    for line in prom.splitlines():  # ollamamq_compile_total{site="x"} n
        if line.startswith('ollamamq_compile_total{site="'):
            compiles[line.split('"')[1]] = float(line.rpartition(" ")[2])
    rts = stats["runtimes"]
    fields = {
        "device": {"platform": stats.get("platform"),
                   "kind": stats.get("device_kind"),
                   "count": stats.get("device_count")},
        "mesh": stats.get("mesh"), "model": rts[0]["model"],
        "layers": cfg.num_layers, "hidden": cfg.hidden_size,
        "param_bytes": rts[0]["param_bytes"], "kv_bytes": rts[0]["kv_bytes"],
        "attn_impl": [r["attn_impl"] for r in rts],
        "attn_inner": [r.get("attn_inner") for r in rts],
        "runtime_devices": [r["devices"] for r in rts],
        "hbm_used": [c["hbm_used"] for c in stats["chips"]],
        "compile_total": compiles,
        "compile_walls_ms": [[e["site"], e["key"], e["wall_ms"]]
                             for e in prof.get("compile_events", [])],
        "step_p99_ms": (stats.get("stepprof") or {}).get("p99_ms"),
    }
    clean = {k: stats.get(k) for k in ("runtime_failures", "rebuilds",
                                       "retries", "preemptions")}
    clean["shed"] = sum((stats.get("shed") or {}).values())
    fields.update(clean)
    checks = [(v == 0, f"{k} = {v}") for k, v in clean.items()] + [
        (rts[0]["param_bytes"] == 2 * cfg.param_count(),
         f"param_bytes {rts[0]['param_bytes']} is not {cfg.name} in bf16 "
         f"({2 * cfg.param_count()})"),
        (compiles.get("ragged", 0) >= 1 and compiles.get("decode", 0) >= 1,
         f"ragged/decode never compiled: {compiles}"),
        (stats.get("platform") == "tpu",
         f"platform is {stats.get('platform')!r}, not 'tpu'"),
        (all(r["attn_impl"] == want_impl for r in rts),
         f"attn_impl = {fields['attn_impl']}, want {want_impl}"),
    ]
    return stats, fields, checks


def one_chip_status(server: Server, cfg, out: dict) -> dict:
    stats, fields, checks = server_status(server, cfg)
    out["device"] = fields["device"]
    used, weights = stats["chips"][0]["hbm_used"], fields["param_bytes"]
    return check_all(fields, checks + [
        (used >= weights,
         f"chip 0 holds {used} B, less than the weights ({weights} B)")])


def warm_request(server: Server, rehearse: bool, first: dict,
                 entries_before: set) -> dict:
    """On a second start, over the compile cache the first one filled:
    the same request gives the same tokens; its first-call walls show
    what the cache saves."""
    # (jax caches only compiles of a second or more: test-tiny's on the
    # CPU stay under that, so a rehearsal cannot expect new entries.)
    written = cache_entries() - entries_before
    check(rehearse or bool(written),
          f"the first server wrote nothing to {cache_dir()}")
    t0 = time.monotonic()
    ids = server.generate_ids(first["prompt"], N_PREDICT)
    first_s = round(time.monotonic() - t0, 1)
    check(ids == first["greedy_ids"],
          f"warm tokens differ: {ids} vs {first['greedy_ids']}")
    _, prof = http(server.port, "/debug/stepprof")
    return {"first_request_s": first_s, "cache_dir": cache_dir(),
            "cache_entries": len(cache_entries()),
            "cache_entries_written": len(written),
            "compile_walls_ms": [[e["site"], e["key"], e["wall_ms"]]
                                 for e in prof.get("compile_events", [])]}


def kernels_phase(rehearse: bool) -> dict:
    """A chip process of its own: both Pallas kernels against the jnp
    reference (kernels_child below)."""
    argv = [sys.executable, os.path.abspath(__file__), "--kernels-child"]
    proc = spawn(argv + (["--rehearse-cpu"] if rehearse else []),
                 "kernels.log", rehearse)
    try:
        proc.wait(timeout=600)
    except subprocess.TimeoutExpired:
        kill(proc)
        raise PhaseFailed("kernel child timed out")
    lines = [ln for ln in log_tail(proc, 200) if ln.startswith("{")]
    check(bool(lines), f"kernel child rc={proc.returncode}, no result: "
                       + " | ".join(log_tail(proc, 6)))
    fields = json.loads(lines[-1])
    return check_all(fields, [(proc.returncode == 0,
                               f"kernel child exited {proc.returncode}")])


def run_one_chip(args) -> dict | None:
    from ollamamq_tpu.config import MODEL_CONFIGS  # jax-free

    rng = random.Random(args.seed)
    rehearse = args.rehearse_cpu
    model = "test-tiny" if rehearse else "llama3.2:1b"
    argv = ["--cpu", "1"] if rehearse else []
    entries_before = cache_entries()
    out, first = {}, None
    with serving("serve", model, argv, rehearse) as server:
        if server is not None:
            first = phase("requests", requests_phase, server, rng)
            phase("burst", burst_phase, server, rng)
            phase("status", one_chip_status, server, MODEL_CONFIGS[model],
                  out)
    if first:
        with serving("warm", model, argv, rehearse) as server:
            if server is not None:
                phase("warm_request", warm_request, server, rehearse, first,
                      entries_before)
    phase("kernels", kernels_phase, rehearse)
    return out.get("device")


# ------------------------------------------------------------ four chips
def run_tp4(args) -> dict | None:
    """The sharded model: llama3:8b — the registered model that cannot
    fit one 16 GB chip in bf16, the README's documented --tp 4 deployment
    — with the Pallas kernels per shard, against the same server on the
    jnp reference attention."""
    from ollamamq_tpu.config import MODEL_CONFIGS

    rng = random.Random(args.seed)
    rehearse = args.rehearse_cpu
    model = "test-tiny-gqa" if rehearse else "llama3:8b"
    cfg = MODEL_CONFIGS[model]
    argv = ["--tp", "4"] + (["--cpu", "4"] if rehearse else [])
    prompts = [prompt_of(rng, n) for n in (12, 40, 90, 200)]
    out, ids = {}, {}

    def status(server, want_impl):
        stats, fields, checks = server_status(server, cfg, want_impl)
        out.setdefault("device", fields["device"])
        # Each chip holds about a quarter of weights + KV pool.
        share = (fields["param_bytes"] + fields["kv_bytes"]) / 4
        return check_all(fields, checks + [
            ((stats.get("mesh") or {}).get("tensor") == 4
             and len(fields["runtime_devices"][0]) == 4,
             f"mesh = {stats.get('mesh')} on {fields['runtime_devices']}"),
            (len(fields["hbm_used"]) == 4 and all(
                0.9 * share <= u <= 1.5 * share for u in fields["hbm_used"]),
             f"per-chip HBM {fields['hbm_used']} is not four times about "
             f"{share:.0f} B"),
        ])

    def requests(server, tag):
        ids[tag] = [server.generate_ids(p, N_PREDICT) for p in prompts]
        return {"prompts": len(prompts), "tokens_each": N_PREDICT}

    for tag, env, impl in (("tp4_pallas", {}, "pallas"),
                           ("tp4_reference", {"OLLAMAMQ_NO_PALLAS": "1"},
                            "jnp")):
        with serving(tag, model, argv, rehearse, env, 900) as server:
            if server is not None:
                phase(f"{tag}_requests", requests, server, tag)
                phase(f"{tag}_status", status, server, impl)

    def compare():
        """Same first token for every prompt; a later divergence (bf16
        rounding differs between kernel and reference) is reported."""
        check(len(ids) == 2, f"only {list(ids)} produced tokens")
        report = []
        for a, b in zip(ids["tp4_pallas"], ids["tp4_reference"]):
            report.append({"pallas": a, "reference": b, "first_divergence": next(
                (i for i, (x, y) in enumerate(zip(a, b)) if x != y), None)})
        return check_all({"streams": report}, [
            (all(r["first_divergence"] != 0 for r in report),
             "a first token differs from the reference run")])

    phase("tp4_compare", compare)
    return out.get("device")


def run_replicas4(args) -> dict | None:
    """Four one-chip replicas behind the fleet router, in one process."""
    from ollamamq_tpu.config import MODEL_CONFIGS

    rng = random.Random(args.seed)
    rehearse = args.rehearse_cpu
    model = "test-tiny" if rehearse else "llama3.2:1b"
    argv = ["--replicas", "4"] + (["--cpu", "4"] if rehearse else [])
    out = {}

    def status(server):
        stats, fields, checks = server_status(server, MODEL_CONFIGS[model])
        out["device"] = fields["device"]
        status_, fleet = http(server.port, "/admin/fleet")
        check(status_ == 200, f"/admin/fleet -> {status_}")
        members = [[m["name"], m["state"], m["ejects"]]
                   for m in fleet["replicas"]]
        served = {r["replica"]: r["tokens_generated"]
                  for r in stats["runtimes"]}
        one = fields["param_bytes"] + fields["kv_bytes"]
        fields.update(members=members, tokens_by_member=served,
                      failovers=fleet["failovers"], one_replica_bytes=one)
        return check_all(fields, checks + [
            (len(members) == 4 and all(
                s == "healthy" and e == 0 for _, s, e in members),
             f"members = {members}"),
            (fleet["failovers"] == 0, f"failovers = {fleet['failovers']}"),
            (len(served) == 4 and all(v > 0 for v in served.values()),
             f"not every member took a stream: {served}"),
            (len({tuple(d) for d in fields["runtime_devices"]}) == 4
             and all(len(d) == 1 for d in fields["runtime_devices"]),
             f"members are not on four devices of their own: "
             f"{fields['runtime_devices']}"),
            # Four devices, each holding one copy (weights + KV pool).
            (len(fields["hbm_used"]) == 4 and all(
                one <= u <= 1.5 * one for u in fields["hbm_used"]),
             f"per-chip HBM {fields['hbm_used']} is not one replica "
             f"({one} B) each"),
        ])

    with serving("replicas4", model, argv, rehearse, None, 900) as server:
        if server is not None:
            # Two bursts of 8 users: the first finds every member cold.
            phase("replicas4_burst1", burst_phase, server, rng,
                  (16, 30, 44, 60, 16, 30, 44, 60))
            phase("replicas4_burst2", burst_phase, server, rng,
                  (20, 34, 48, 56, 20, 34, 48, 56))
            phase("replicas4_status", status, server)
    return out.get("device")


# ---------------------------------------------------------- kernel child
def kernels_child(rehearse: bool) -> int:
    """Runs in its own process (it needs the chip): the ragged and the
    decode Pallas kernel at llama3.2:1b widths and the engine-default
    pool shape against the jnp serving reference, on the same seeded bf16
    inputs. Prints one JSON line; exit code 1 beyond bf16 tolerance."""
    sys.path.insert(0, ROOT)
    from ollamamq_tpu.platform_force import force_cpu, place_compile_cache

    if rehearse:
        force_cpu(1)
    place_compile_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ollamamq_tpu.ops.attention import (paged_decode_attention_any,
                                            ragged_attention_any)

    dev = jax.devices()[0]
    if not rehearse and dev.platform != "tpu":
        print(json.dumps({"detail": f"no TPU: platform is {dev.platform}"}))
        return 1
    H, Hk, hd, ps = 32, 8, 64, 32  # llama3.2:1b heads; engine page size
    if rehearse:  # the Pallas interpreter is slow: same code, a small
        H, Hk = 4, 2  # pool and few heads (the kernel unrolls per head)
        B, MP, NP, n_dec, max_ctx = 8, 8, 64, 5, 100
        spans = ((10, 0), (17, 32))  # (new tokens, cached prefix)
    else:  # cli defaults: 64 slots, 256 pages a sequence, 1024-page pool
        B, MP, NP, n_dec, max_ctx = 64, 256, 1024, 40, 480
        spans = ((100, 0), (80, 64), (36, 0))
    rng = np.random.default_rng(0)

    def normal(shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)

    # A whole two-layer pool in its stored layout, [L, S, Hk*hd]; the
    # kernels read layer LAYER of it by index, as the forwards do.
    LAYER = 1
    kc, vc = normal((2, NP * ps, Hk * hd)), normal((2, NP * ps, Hk * hd))
    next_page = [1]  # page 0 is the allocator's trash page

    def pages_for(kv_len: int) -> np.ndarray:
        row = np.zeros((MP,), np.int32)
        n = -(-kv_len // ps)
        row[:n] = np.arange(next_page[0], next_page[0] + n)
        next_page[0] += n
        if next_page[0] > NP:
            raise ValueError("pool too small for the test batch")
        return row

    # Ragged: decode rows (one token over a long context), then prefill
    # spans (one over a cached prefix), then padding rows.
    rows = [(1, int(rng.integers(0, max_ctx))) for _ in range(n_dec)]
    rows += list(spans)
    T = sum(n for n, _ in rows)
    T_pad = -(-T // 16) * 16
    q_len, kv_len = np.zeros((B,), np.int32), np.zeros((B,), np.int32)
    q_start = np.full((B,), T_pad, np.int32)
    pt = np.zeros((B, MP), np.int32)
    tok_seq = np.zeros((T_pad,), np.int32)
    tok_pos = np.full((T_pad,), -1, np.int32)
    off = 0
    for s, (n, prefix) in enumerate(rows):
        q_len[s], kv_len[s], q_start[s] = n, prefix + n, off
        pt[s] = pages_for(prefix + n)
        tok_seq[off:off + n] = s
        tok_pos[off:off + n] = prefix + np.arange(n)
        off += n
    q = normal((T_pad, H, hd))
    meta = [jnp.asarray(a) for a in (pt, tok_seq, tok_pos, kv_len, q_start,
                                     q_len)]

    def ragged(impl):
        return jax.jit(lambda q, kc, vc, *m: ragged_attention_any(
            impl, q, kc, vc, LAYER, *m, ps, interpret=rehearse))(
                q, kc, vc, *meta)

    rtol = atol = 2e-2  # bf16 outputs: 8 significant bits

    def closeness(out, ref, valid) -> dict:
        out = np.asarray(out, np.float32)[valid]
        ref = np.asarray(ref, np.float32)[valid]
        return {"ok": bool(np.isfinite(out).all() and np.allclose(
                    out, ref, rtol=rtol, atol=atol)),
                "max_abs_diff": float(np.abs(out - ref).max()),
                "shape": list(out.shape)}

    t0 = time.monotonic()
    out = ragged("pallas").block_until_ready()
    ragged_s = time.monotonic() - t0
    res_r = closeness(out, ragged("jnp"), tok_pos >= 0)

    # Decode: one query per sequence over ragged context lengths.
    next_page[0] = 1
    seq_lens = rng.integers(1, max_ctx, size=(B,)).astype(np.int32)
    ptd = jnp.asarray(np.stack([pages_for(int(n)) for n in seq_lens]))
    qd, sl = normal((B, H, hd)), jnp.asarray(seq_lens)
    t0 = time.monotonic()
    outd = jax.jit(lambda q, kc, vc, pt, sl: paged_decode_attention_any(
        "pallas", q, kc, vc, LAYER, pt, sl, ps, interpret=rehearse))(
            qd, kc, vc, ptd, sl).block_until_ready()
    decode_s = time.monotonic() - t0
    # The materializing jnp decode reference gathers every sequence's
    # whole page-table width; the blockwise ragged reference over one-
    # token rows is the same softmax.
    rows, one = jnp.arange(B, dtype=jnp.int32), jnp.ones_like(sl)
    refd = jax.jit(lambda q, kc, vc, pt, sl: ragged_attention_any(
        "jnp", q, kc, vc, LAYER, pt, rows, sl - 1, sl, rows, one, ps))(
            qd, kc, vc, ptd, sl)
    res_d = closeness(outd, refd, np.ones((B,), bool))

    print(json.dumps({
        "platform": dev.platform, "device_kind": dev.device_kind,
        "widths": {"H": H, "Hk": Hk, "hd": hd, "page": ps, "pool_pages": NP,
                   "seqs": B, "max_pages": MP},
        "ragged": {**res_r, "tokens": T, "decode_rows": n_dec,
                   "prefill_spans": [n for n, _ in spans],
                   "first_call_s": round(ragged_s, 2)},
        "decode": {**res_d, "first_call_s": round(decode_s, 2)},
        "tolerance": {"rtol": rtol, "atol": atol},
    }))
    return 0 if res_r["ok"] and res_d["ok"] else 1


# -------------------------------------------------------------------- main
def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips4", nargs="?", const="all", default=None,
                    choices=("all", "tp", "replicas"),
                    help="run the four-chip paths (and what they are "
                         "compared with) instead of the one-chip phases")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="same control flow at test-tiny on the CPU; ends "
                         "ok:false at the platform check")
    ap.add_argument("--seed", type=int, default=0, help="prompt seed")
    ap.add_argument("--kernels-child", action="store_true",
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.kernels_child:
        return kernels_child(args.rehearse_cpu)

    def on_deadline():
        kill_all()
        print(json.dumps({"phase": "deadline", "ok": False,
                          "error": "not done in time"}), flush=True)
        print(json.dumps({"ok": False, "device": None}), flush=True)
        os._exit(1)

    # The driver's run (one chip) must end inside its 1200 s; four-chip
    # runs are the builder's and start three servers.
    timer = threading.Timer(DEADLINE_S * (3 if args.chips4 else 1),
                            on_deadline)
    timer.daemon = True
    timer.start()
    sys.path.insert(0, ROOT)
    device = None
    t0 = time.monotonic()
    try:
        phase("versions", versions)
        if phase("build", build_native):
            if args.chips4 is None:
                device = run_one_chip(args)
            if args.chips4 in ("all", "tp"):
                device = run_tp4(args)
            if args.chips4 in ("all", "replicas"):
                device = run_replicas4(args) or device
    finally:
        kill_all()
    print(json.dumps({"phase": "summary", "ok": not _failed,
                      "failed": _failed,
                      "wall_s": round(time.monotonic() - t0, 1)}), flush=True)
    ok = not _failed and device is not None
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
