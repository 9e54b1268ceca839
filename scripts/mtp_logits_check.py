#!/usr/bin/env python3
"""The prediction module's DRAFT LOGITS on the chip against the plain
reference's, at a benchmark configuration's published widths (PR 42):

    chiprun --timeout 1500 -- python scripts/mtp_logits_check.py \
        [--config benchmarks/configs/openpangu-ultra-moe-ep16-d5.json] \
        [--tokens 640] [--chunk 512] [--spans 24] [--seed 0]

The cell's `correct` holds the ids the server returned to the reference, and
those are the TRUNK's: a draft can move none of them, so a wrong module would
still be `correct` (it would only be accepted less). This script is what
holds the module itself: one sequence of seeded tokens through the served
forwards as the engine's step program calls them (`llama.forward_ragged` with
`hidden=True`, then `llama.forward_mtp`; the Pallas kernels; bfloat16 weights
drawn as the server draws them) — the prompt in chunks through the latent
pool, then `--spans` verify spans `[t, draft]` with every second draft wrong
(its rows rolled back and written again) — and at every position read, the
module's logits beside `openpangu_ultra_decoder.mtp_hidden`'s float32 ones:

  mean_margin_sd   how far below the reference module's best logit the id
                   the served module would draft lies, in standard deviations
                   of that position's logits, mean over the positions read
                   (the cell's own statistic, of the module)
  argmax_share     positions where the two agree on the draft
  max_abs_err_sd   the largest difference of any logit, in the same unit

and the same of the reference computed with float8 operands
(`lower_precision`), which has to read well above the served reading: the
limit (`MEAN_MARGIN_SD_MAX` of the reference file) lies between the two.
One JSON line last; exit code 1 if the served reading is over the limit.
`--cpu` runs the same control flow at the file's `rehearse` sizes with the
jnp twins (a rehearsal: no device number)."""

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
        ROOT, "benchmarks", "configs", "openpangu-ultra-moe-ep16-d5.json"))
    ap.add_argument("--tokens", type=int, default=640)
    ap.add_argument("--chunk", type=int, default=512)
    ap.add_argument("--spans", type=int, default=24)
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

    impl = "jnp" if args.cpu else "pallas"
    dev = jax.devices()[0]
    if not args.cpu and dev.platform != "tpu":
        print(json.dumps({"ok": False, "error": f"no TPU: {dev.platform}"}))
        return 1
    ps, n_tok = 32, args.tokens if not args.cpu else 96
    chunk = args.chunk if not args.cpu else 32
    n_spans = args.spans if not args.cpu else 6
    total = n_tok + 2 * n_spans + 2
    pages = -(-total // ps) + 1
    params = weights.init_random(mc, seed=0)
    ref.served_layout(cfg, params)
    kc, vc = kvc.alloc_kv_pool(
        mc, EngineConfig(num_pages=pages + 1, page_size=ps))
    rng = np.random.default_rng(args.seed)
    tokens = rng.integers(3, mc.vocab_size, total).astype(np.int32)
    pt = np.zeros((1, pages), np.int32)
    pt[0] = np.arange(1, pages + 1)

    def step(kc, vc, toks, start, follows, pad, read):
        """One span at positions start.. through trunk and module; module
        logits at offset `read` of the span."""
        n = len(toks)
        tok = np.zeros(pad, np.int32)
        tok[:n] = toks
        nxt = np.zeros(pad, np.int32)
        nxt[:n] = list(toks[1:]) + [follows]
        seq = np.zeros(pad, np.int32)
        pos = np.full(pad, -1, np.int32)
        pos[:n] = np.arange(start, start + n)
        slots = np.where(pos >= 0, pt[0, np.maximum(pos, 0) // ps] * ps
                         + np.maximum(pos, 0) % ps, 0).astype(np.int32)
        meta = tuple(map(jnp.asarray, (
            pt, np.asarray([0], np.int32), np.asarray([n], np.int32),
            np.asarray([start + n], np.int32))))
        at = jnp.asarray([read], jnp.int32)

        def run(p, kc, vc):
            _, kc, vc, _, hidden = llama.forward_ragged(
                p, mc, *map(jnp.asarray, (tok, seq, pos, slots)), at, kc, vc,
                *meta, ps, attn_impl=impl, moe_load=True, hidden=True)
            draft, kc, _ = llama.forward_mtp(
                p, mc, hidden, *map(jnp.asarray, (nxt, seq, pos, slots)), at,
                kc, *meta, ps, attn_impl=impl)
            return draft[0], kc, vc

        return jax.jit(run, donate_argnums=(1, 2))(params, kc, vc)

    got = {}  # position -> the served module's logits there
    for at in range(0, n_tok, chunk):
        end = min(at + chunk, n_tok)
        pad = -(-(end - at) // 16) * 16
        logits, kc, vc = step(kc, vc, tokens[at:end], at, tokens[end], pad,
                              end - at - 1)
        got[end - 1] = np.asarray(logits, np.float32)
    pos, vocab = n_tok, mc.vocab_size
    for i in range(n_spans):
        follows = tokens[pos + 2]
        if i % 2:  # a wrong draft: its rows at pos + 1, the trunk's and the
            # module's, are a rejected draft's — then written again, and the
            # module's draft is read at the last ACCEPTED position, pos
            bad = np.asarray([tokens[pos], (tokens[pos + 1] + 1) % vocab],
                             np.int32)
            _, kc, vc = step(kc, vc, bad, pos, follows, 16, 0)
            logits, kc, vc = step(kc, vc, tokens[pos:pos + 2], pos, follows,
                                  16, 0)
            got[pos] = np.asarray(logits, np.float32)
            pos += 1
        else:  # a right one: both positions stand, read at the second
            logits, kc, vc = step(kc, vc, tokens[pos:pos + 2], pos, follows,
                                  16, 1)
            got[pos + 1] = np.asarray(logits, np.float32)
            pos += 2
    at = np.asarray(sorted(got), np.int32)
    served = jnp.asarray(np.stack([got[p] for p in at]))
    t, padded = ref._padded(tokens[:pos + 2])

    def reading(lower):
        h = ref.mtp_hidden(cfg, params, padded, lower=lower)[at]
        return ref.head_logits(params, h, lower)

    exact = reading(False)
    sd = jnp.maximum(exact.std(axis=-1, keepdims=True), 1e-30)

    def held(logits):
        chosen = jnp.argmax(logits, axis=-1)
        margin = (exact.max(axis=-1) - jnp.take_along_axis(
            exact, chosen[:, None], axis=-1)[:, 0]) / sd[:, 0]
        return {"mean_margin_sd": float(margin.mean()),
                "max_margin_sd": float(margin.max()),
                "argmax_share": float((chosen == jnp.argmax(
                    exact, axis=-1)).mean()),
                "max_abs_err_sd": float((jnp.abs(logits - exact) / sd).max())}

    limit = ref.MEAN_MARGIN_SD_MAX
    out = {"config": cfg["name"], "device": dev.device_kind,
           "platform": dev.platform, "attn_impl": impl,
           "positions": int(at.size), "context": int(pos),
           "chunks": -(-n_tok // chunk), "verify_spans": n_spans,
           "served": held(served), "lower_precision": held(reading(True)),
           "limit_mean_margin_sd": limit}
    out["ok"] = bool(out["served"]["mean_margin_sd"] <= limit
                     < out["lower_precision"]["mean_margin_sd"])
    print(json.dumps(out), flush=True)
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
