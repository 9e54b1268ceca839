#!/usr/bin/env python3
"""The server child of one benchmark run: the program's normal entry point
(`ollamamq_tpu.cli.main`) serving one configuration file's model.

It registers the configuration's `ModelConfig` under the configuration's
name and hands the configuration's own CLI flags to the CLI — scheduler,
cache, sharding and kernels are the program's, unedited. Because this is the
process that holds the chip, a thread of its own answers one question the
server has no endpoint for: when `<out>/dump.request` appears it writes
`<out>/device.json` (platform, kind, device count, and the largest
`peak_bytes_in_use` over the chips). When `<out>/reference.request` appears
(after the window, when the engine is idle) it runs the configuration's plain
float32 reference (`benchmarks/reference/<name>.py`) over the requests listed
there, on the weights this process serves, and writes `<out>/reference.json`.
With --collect-steps the same thread copies the step profiler's ring to
`<out>/steps.jsonl` once a second (a traced run's per-layer metrics read it;
an untraced run does not pay it).
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# configuration-file key -> ModelConfig field
FIELDS = {
    "vocab_size": "vocab_size", "hidden_size": "hidden_size",
    "intermediate_size": "intermediate_size",
    "num_hidden_layers": "num_layers", "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
    "rope_theta": "rope_theta", "rms_norm_eps": "rms_norm_eps",
    "max_position_embeddings": "max_seq_len",
    "tie_word_embeddings": "tie_embeddings", "attention_bias": "attn_bias",
    "qk_norm": "qk_norm", "num_experts": "num_experts",
    "num_experts_per_tok": "num_experts_per_tok",
}
# --rehearse-cpu: the same architecture switches at sizes a CPU runs in
# milliseconds, and a pool to match. Never a measurement.
REHEARSE_SIZES = {
    "vocab_size": 512, "hidden_size": 128, "intermediate_size": 256,
    "num_layers": 2, "num_heads": 8, "num_kv_heads": 4, "head_dim": 16,
    "max_seq_len": 2048,
}
REHEARSE_FLAGS = {
    "--max-slots": "8", "--num-pages": "256", "--page-size": "8",
    "--max-pages-per-seq": "64", "--max-batch-tokens": "64",
    "--token-granule": "16", "--decode-steps": "4",
}


def as_run(cfg: dict, rehearse: bool) -> dict:
    """The configuration file's keys as this process runs them."""
    if not rehearse:
        return cfg
    return {**cfg, **{key: REHEARSE_SIZES[field]
                      for key, field in FIELDS.items()
                      if field in REHEARSE_SIZES}}


def model_config(cfg: dict, rehearse: bool):
    from ollamamq_tpu.config import ModelConfig

    cfg = as_run(cfg, rehearse)
    return ModelConfig(name=cfg["name"], **{
        field: cfg[key] for key, field in FIELDS.items() if key in cfg})


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

    from ollamamq_tpu import cli
    from ollamamq_tpu.config import MODEL_CONFIGS

    MODEL_CONFIGS[cfg["name"]] = model_config(cfg, args.rehearse_cpu)
    if cfg.get("stream_every_token"):
        stream_every_token()
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
