#!/usr/bin/env python3
"""The server child of one benchmark run: the program's normal entry point
(`ollamamq_tpu.cli.main`) serving one configuration file's model.

It registers the configuration's `ModelConfig` under the configuration's
name and hands the configuration's own CLI flags to the CLI — scheduler,
cache, sharding and kernels are the program's, unedited. Every architecture
key of the file reaches a field of the served program's `ModelConfig`; a key
the program has no field for, or a value it refuses, ends this process with
one line (file, key, value) and exit code 2 before the CLI starts.

Because this is the process that holds the chip, a thread of its own answers
one question the server has no endpoint for: when `<out>/dump.request`
appears it writes `<out>/device.json` (platform, kind, device count, and the
largest `peak_bytes_in_use` over the chips). When `<out>/reference.request` appears
(after the window, when the engine is idle) it runs the configuration's plain
float32 reference (`benchmarks/reference/<name>.py`) over the requests listed
there, on the weights this process serves, and writes `<out>/reference.json`.
With --collect-steps the same thread copies the step profiler's ring to
`<out>/steps.jsonl` once a second (a traced run's per-layer metrics read it;
an untraced run does not pay it).
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks.lib import arch  # noqa: E402 — data only, no jax

# A configuration file is the whole description of its model. These keys are
# the harness's own (and any key ending in `_reason`); EVERY other key is an
# architecture key, and reaches the program or stops the run at start.
HARNESS_KEYS = frozenset((
    "name", "source", "deployment", "chips", "model_type", "reduced",
    "reduced_from", "assumed", "arithmetic", "dtype", "routing", "reference",
    "server_flags", "stream_every_token", "rehearse"))
# architecture key -> the ModelConfig field of another name that holds it
# (the published spellings of fields the program has); a key that is not here
# goes to the field of its own name.
RENAMES = {
    "num_hidden_layers": "num_layers", "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "max_position_embeddings": "max_seq_len",
    "tie_word_embeddings": "tie_embeddings", "attention_bias": "attn_bias",
    "norm_eps": "rms_norm_eps",
    **{key: "num_experts" for key in arch.EXPERT_COUNT_KEYS},
}
# A key that names no field passes at the one value the program implements.
ONLY_VALUE = {"hidden_act": "silu", "rope_scaling": None, "clip_qkv": None}
# --rehearse-cpu: the same architecture switches at sizes a CPU runs in
# milliseconds, and a pool to match. Never a measurement. A file's own
# `rehearse` block is laid over these (what a stack whose layers differ
# needs: a list a layer long, a dense prefix, a width of its own).
REHEARSE_SIZES = {
    "vocab_size": 512, "hidden_size": 128, "intermediate_size": 256,
    "num_hidden_layers": 2, "num_attention_heads": 8,
    "num_key_value_heads": 4, "head_dim": 16,
    "max_position_embeddings": 2048,
}
REHEARSE_FLAGS = {
    "--max-slots": "8", "--num-pages": "256", "--page-size": "8",
    "--max-pages-per-seq": "64", "--max-batch-tokens": "64",
    "--token-granule": "16", "--decode-steps": "4",
}


class Refused(Exception):
    """The served program cannot run the configuration file as written: one
    line naming key and value, told before any device is touched."""


def refused(key: str, value, why: str) -> Refused:
    return Refused(f"key {key!r} = {json.dumps(value)}: {why}")


def architecture(cfg: dict) -> dict:
    return {key: value for key, value in cfg.items()
            if key not in HARNESS_KEYS and not key.endswith("_reason")}


def as_run(cfg: dict, rehearse: bool) -> dict:
    """The configuration file's keys as this process runs them."""
    if not rehearse:
        return cfg
    own = cfg.get("rehearse", {})
    for key, value in architecture(cfg).items():
        if isinstance(value, list) and key not in own:
            raise refused(key, value, "a list is as long as a size the "
                          "rehearsal shrinks: the file's `rehearse` block "
                          "has to give its tiny value")
    return {**cfg, **REHEARSE_SIZES, **own}


def model_config(cfg: dict, rehearse: bool):
    """The served program's `ModelConfig` of the file: every architecture key
    in the field RENAMES names for it, else the field of its own name."""
    from ollamamq_tpu.config import ModelConfig

    cfg = as_run(cfg, rehearse)
    fields = {f.name for f in dataclasses.fields(ModelConfig)} - {"name"}
    given: dict = {}     # field -> the key that gave it
    for key, value in architecture(cfg).items():
        field = RENAMES.get(key, key)
        if field in fields:
            if field in given:
                raise refused(key, value, f"{given[field]!r} has already "
                              f"given the field {field!r}")
            given[field] = key
        elif key not in ONLY_VALUE:
            raise refused(key, value, "the program's ModelConfig has no "
                          "field for it")
        elif value != ONLY_VALUE[key]:
            raise refused(key, value, "the program implements only "
                          f"{json.dumps(ONLY_VALUE[key])}")
    try:
        return ModelConfig(name=cfg["name"], **{
            field: cfg[key] for field, key in given.items()})
    except (TypeError, ValueError) as e:
        why = f"the program's ModelConfig refuses it: {e}"
        named = [key for field, key in given.items() if field in str(e)]
        if len(named) == 1:
            raise refused(named[0], cfg[named[0]], why) from e
        raise Refused(f"keys {sorted(given.values())}: {why}") from e


def server_flags(cfg: dict, rehearse: bool) -> list:
    flags = list(cfg.get("server_flags", ()))
    if rehearse:
        for name, value in REHEARSE_FLAGS.items():
            if name in flags:
                flags[flags.index(name) + 1] = value
            else:
                flags += [name, value]
        flags += ["--cpu", str(cfg.get("chips", 1))]
    return flags


def stream_every_token() -> None:
    """Seeded random weights sample ids all over a 150 k vocabulary, and the
    program's byte tokenizer gives an id outside the byte range no text; the
    server writes a stream frame only when there is text, so it would hold
    every frame back until the request ends and no client could see a first
    token or a gap. A deployment's tokenizer has text for every id. So, where
    the configuration file says `stream_every_token`, ids outside the byte
    range decode to one character and the server streams as it would there:
    a frame a token. (A shim over the program's tokenizer, not an edit; what
    the program should offer instead is in PERF.md, Open questions.)"""
    from ollamamq_tpu.engine import tokenizer as tk

    plain = tk.ByteTokenizer.make_incremental_decoder

    def make(self):
        step = plain(self)
        return lambda token_id: "~" if token_id >= 259 else step(token_id)

    tk.ByteTokenizer.make_incremental_decoder = make


def end_by_count_only() -> None:
    """Every request of the harness's traffic ends by count (`num_predict`:
    a completed request is one that returned all of them, `done_reason:
    "length"`), as serving benchmarks fix output lengths with `ignore_eos`.
    The Ollama API has no such option, and the byte tokenizer calls id 2 the
    end of text: with seeded random weights it is one id among the
    vocabulary's, which the greedy choice reaches about once in 10^7 to 10^8
    tokens (PERF.md section 4) - a request cut short, a run `correct: false`,
    once in some hundred runs of a sound program. So no id ends a request
    here: the engine's comparison with `eos_id` runs as it does, and never
    matches. (A shim over the program's tokenizer, as above; what the program
    should offer instead is in PERF.md, Open questions.)"""
    from ollamamq_tpu.engine import tokenizer as tk

    tk.ByteTokenizer.eos_id = -1


RUNTIMES: list = []  # every ModelRuntime this process built


def keep_runtimes() -> None:
    """The reference reads the weights the server serves: remember each
    runtime as the program builds it (a shim, as above, not an edit)."""
    from ollamamq_tpu.engine import engine

    plain = engine.ModelRuntime.__init__

    def init(self, *args, **kwargs):
        plain(self, *args, **kwargs)
        RUNTIMES.append(self)

    engine.ModelRuntime.__init__ = init


def reference_report(cfg: dict, request: dict) -> dict:
    """The configuration's reference over the listed requests; an error is
    reported, never hidden (the run is then not `correct`)."""
    try:
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "reference", cfg["reference"] + ".py")
        spec = importlib.util.spec_from_file_location("bench_reference", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        if not RUNTIMES:
            raise RuntimeError("the program built no ModelRuntime")
        t0 = time.monotonic()
        out = mod.check(cfg, RUNTIMES[-1].params, request["requests"],
                        int(request["pad_to"]), int(request["max_out"]))
        return dict(out, seconds=time.monotonic() - t0)
    except Exception as e:  # noqa: BLE001 — told to the parent, which fails
        return {"agrees": False, "error": f"{type(e).__name__}: {e}"[:600]}


def answer(out: str, name: str, make) -> None:
    """`<out>/<name>.request` -> `<out>/<name>.json`, written whole."""
    request = os.path.join(out, name + ".request")
    if not os.path.exists(request):
        return
    with open(request) as f:
        asked = f.read()
    tmp = os.path.join(out, name + ".json.tmp")
    with open(tmp, "w") as f:
        json.dump(make(json.loads(asked) if asked.strip() else {}), f)
    os.replace(tmp, os.path.join(out, name + ".json"))
    os.remove(request)


def device_report() -> dict:
    import jax

    devs = jax.devices()
    stats = [d.memory_stats() or {} for d in devs]
    return {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs),
        "memory_peak_bytes": max(
            int(s.get("peak_bytes_in_use", 0)) for s in stats),
        "peak_bytes_in_use": [s.get("peak_bytes_in_use") for s in stats],
        "bytes_in_use": [s.get("bytes_in_use") for s in stats],
        "bytes_limit": [s.get("bytes_limit") for s in stats],
    }


def watcher(out: str, collect_steps: bool, cfg: dict) -> None:
    from ollamamq_tpu.telemetry import stepprof

    steps = open(os.path.join(out, "steps.jsonl"), "w") if collect_steps \
        else None
    last_seq, tick = 0, 0
    while True:
        time.sleep(0.25)
        tick += 1
        dump = os.path.exists(os.path.join(out, "device.request"))
        if steps is not None and (dump or tick % 4 == 0):
            for s in stepprof.PROFILER.tail():
                if s["seq"] > last_seq:
                    last_seq = s["seq"]
                    steps.write(json.dumps(s) + "\n")
            steps.flush()
        answer(out, "device", lambda _: device_report())
        answer(out, "reference", lambda asked: reference_report(cfg, asked))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--collect-steps", action="store_true")
    args = ap.parse_args(argv)
    with open(args.config) as f:
        cfg = json.load(f)
    try:
        served = model_config(cfg, args.rehearse_cpu)
    except Refused as e:   # before the CLI starts: no device is touched
        print(f"{args.config}: {e}", flush=True)
        return 2

    from ollamamq_tpu import cli
    from ollamamq_tpu.config import MODEL_CONFIGS

    MODEL_CONFIGS[cfg["name"]] = served
    if cfg.get("stream_every_token"):
        stream_every_token()
    end_by_count_only()
    keep_runtimes()
    threading.Thread(target=watcher, args=(
        args.out, args.collect_steps, as_run(cfg, args.rehearse_cpu)),
                     daemon=True, name="bench-watcher").start()
    return cli.main(["--no-tui", "--host", "127.0.0.1", "--port",
                     str(args.port), "--models", cfg["name"],
                     "--blocklist", os.path.join(args.out, "blocked.json")]
                    + server_flags(cfg, args.rehearse_cpu))


if __name__ == "__main__":
    sys.exit(main())
