#!/usr/bin/env python3
"""The served forwards' LOGITS on the chip against the plain reference's, at
the Xing4.0 configuration's published widths, path by path (PR 69):

    chiprun --timeout 1800 -- python scripts/xing4_logits_check.py \
        [--config benchmarks/configs/xing4.0-29b-a4b-d6.json] \
        [--tokens 1024] [--chunk 512] [--reads 16] [--decode 8] [--seed 0]

The cell's `correct` holds the ids the server returned to the reference by
ONE number, the mean margin. This script says where a margin comes from: one
sequence of seeded tokens, bfloat16 weights drawn as the server draws them,
the prompt in chunks through the latent pool and then one-token decode
passes, `--reads` logits a chunk — once on the Pallas path (the kernels the
cell runs: `mhc_mix_*_pallas`, the dense latent kernel, `gmm`) and once on
the jnp path (XLA's own ops for all of it) — each held to
`xing4_decoder`'s float32 logits, and to each other:

  mean_margin_sd   how far below the reference's best logit the id the path
                   would emit lies, in standard deviations of that position's
                   logits, mean over the positions read (the cell's statistic)
  argmax_share     positions where the two agree on the id
  max_abs_err_sd   the largest difference of any logit, in the same unit

and the same of the reference with float8 operands (`lower_precision`) and of
the float32 reference with only the STREAMS rounded to bfloat16 between
sublayers (`held_precision`), or only the ROUTER's input
(`router_input_bfloat16`: how much of a reading is a token's fourth expert
flipping among near-equal sigmoid scores). Two paths that read alike against the reference
and differ from each other as much are two roundings of one mathematics, not
a fault of either; a fault of a kernel shows as ONE path reading apart. One
JSON line last; exit code 1 where the Pallas path is further from the
reference than one and a half times the jnp path, or half as far as float8.
`--cpu` runs the same control flow at the file's `rehearse` sizes (jnp twice:
a rehearsal, no device number)."""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default=os.path.join(
        ROOT, "benchmarks", "configs", "xing4.0-29b-a4b-d6.json"))
    ap.add_argument("--tokens", type=int, default=1024)
    ap.add_argument("--chunk", type=int, default=512)
    ap.add_argument("--reads", type=int, default=16)
    ap.add_argument("--decode", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args(argv)
    if args.cpu:
        from ollamamq_tpu.platform_force import force_cpu

        force_cpu(1)

    import importlib.util

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import serve
    from ollamamq_tpu.config import EngineConfig
    from ollamamq_tpu.engine import kv_cache as kvc
    from ollamamq_tpu.models import llama, weights

    with open(args.config) as f:
        cfg = serve.as_run(json.load(f), args.cpu)
    mc = serve.model_config(cfg, args.cpu)
    path = os.path.join(ROOT, "benchmarks", "reference",
                        cfg["reference"] + ".py")
    spec = importlib.util.spec_from_file_location("reference", path)
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    dev = jax.devices()[0]
    if not args.cpu and dev.platform != "tpu":
        print(json.dumps({"ok": False, "error": f"no TPU: {dev.platform}"}))
        return 1
    ps = 32 if not args.cpu else 8
    n_tok, chunk = (args.tokens, args.chunk) if not args.cpu else (64, 32)
    reads, n_dec = (args.reads, args.decode) if not args.cpu else (4, 3)
    total = n_tok + n_dec + 1
    pages = -(-total // ps) + 1
    rows = 8  # decode rows: row 0 is the sequence, the others are parked
    params = weights.init_random(mc, seed=0)
    ref.served_layout(cfg, params)
    rng = np.random.default_rng(args.seed)
    tokens = rng.integers(3, mc.vocab_size, total).astype(np.int32)
    pt = np.zeros((rows, pages), np.int32)
    pt[0] = np.arange(1, pages + 1)

    def served(impl):
        """{position: logits} of the sequence through `impl`'s forwards."""
        kc, vc = kvc.alloc_kv_pool(
            mc, EngineConfig(num_pages=pages + 1, page_size=ps))
        got = {}

        def span(p, kc, vc, tok, seq, pos, slots, at, meta):
            return llama.forward_ragged(p, mc, tok, seq, pos, slots, at, kc,
                                        vc, *meta, ps, attn_impl=impl)

        def one(p, kc, vc, tok, pos, live):
            return llama.forward_decode(p, mc, tok, pos, kc, vc,
                                        jnp.asarray(pt), ps, attn_impl=impl,
                                        active=live)

        span, one = (jax.jit(f, donate_argnums=(1, 2)) for f in (span, one))
        for start in range(0, n_tok, chunk):
            n = min(chunk, n_tok - start)
            pad = -(-n // 16) * 16
            tok = np.zeros(pad, np.int32)
            tok[:n] = tokens[start:start + n]
            pos = np.full(pad, -1, np.int32)
            pos[:n] = np.arange(start, start + n)
            slots = np.where(pos >= 0, pt[0, np.maximum(pos, 0) // ps] * ps
                             + np.maximum(pos, 0) % ps, 0).astype(np.int32)
            at = np.zeros((rows, reads), np.int32)
            at[0] = np.linspace(n // 4, n - 1, reads).astype(np.int32)
            q_len = np.zeros(rows, np.int32)
            q_len[0] = n
            kv_len = np.zeros(rows, np.int32)
            kv_len[0] = start + n
            meta = tuple(map(jnp.asarray, (
                pt, np.where(q_len > 0, 0, pad).astype(np.int32), q_len,
                kv_len)))
            logits, kc, vc = span(
                params, kc, vc, *map(jnp.asarray, (
                    tok, np.zeros(pad, np.int32), pos, slots, at)), meta)
            for j, off in enumerate(at[0]):
                got[start + int(off)] = np.asarray(logits[0, j], np.float32)
        live = jnp.asarray([1] + [0] * (rows - 1), jnp.int32)
        for p in range(n_tok, n_tok + n_dec):
            tok = np.zeros(rows, np.int32)
            tok[0] = tokens[p]
            pos = np.zeros(rows, np.int32)
            pos[0] = p
            logits, kc, vc = one(params, kc, vc, jnp.asarray(tok),
                                 jnp.asarray(pos), live)
            got[p] = np.asarray(logits[0], np.float32)
        return got

    paths = {"pallas": served("jnp" if args.cpu else "pallas"),
             "jnp": served("jnp")}
    at = np.asarray(sorted(paths["jnp"]), np.int32)
    t, padded = ref._padded(tokens[:total])

    def reading(**kw):
        return ref.head_logits(params, ref.hidden(cfg, params, padded,
                                                  **kw)[at],
                               kw.get("lower", False))

    exact = reading()

    def held(logits, to=None):
        to = exact if to is None else to
        sd = jnp.maximum(to.std(axis=-1, keepdims=True), 1e-30)
        chosen = jnp.argmax(logits, axis=-1)
        margin = (to.max(axis=-1) - jnp.take_along_axis(
            to, chosen[:, None], axis=-1)[:, 0]) / sd[:, 0]
        return {"mean_margin_sd": float(margin.mean()),
                "max_margin_sd": float(margin.max()),
                "argmax_share": float((chosen == jnp.argmax(
                    to, axis=-1)).mean()),
                "max_abs_err_sd": float((jnp.abs(logits - to) / sd).max())}

    stack = {k: jnp.asarray(np.stack([v[p] for p in at]))
             for k, v in paths.items()}
    limit = ref.MEAN_MARGIN_SD_MAX
    out = {"config": cfg["name"], "device": dev.device_kind,
           "platform": dev.platform, "positions": int(at.size),
           "context": int(total), "chunks": -(-n_tok // chunk),
           "decode_passes": n_dec,
           "pallas": held(stack["pallas"]), "jnp": held(stack["jnp"]),
           "pallas_against_jnp": held(stack["pallas"], stack["jnp"]),
           "held_precision": held(reading(held=True)),
           "router_input_bfloat16": held(reading(held="router")),
           "lower_precision": held(reading(lower=True)),
           "limit_mean_margin_sd": limit}
    # (a sequence of RANDOM tokens reads three times what the cell's own
    # greedy continuations read — 0.39 against 0.13 at PR 69 — so the file's
    # limit is not this script's: the two paths are held to each other)
    out["ok"] = bool(
        out["pallas"]["mean_margin_sd"]
        <= 1.5 * max(out["jnp"]["mean_margin_sd"], 1e-3)
        and out["pallas"]["mean_margin_sd"]
        < 0.5 * out["lower_precision"]["mean_margin_sd"])
    print(json.dumps(out), flush=True)
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
