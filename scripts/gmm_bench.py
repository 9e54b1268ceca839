"""Microseconds a launch of the grouped expert matmul (megablox `gmm`), by
tiling, at a configuration's expert shapes, on the chip: `chiprun -- python
scripts/gmm_bench.py` (every sparse configuration file, ~6 min).

For a file under `benchmarks/configs/` it reads the served model's (hidden,
expert width, held experts, routed experts, top-k, layers with experts) and
builds one step's launch as the forwards make it (`models/moe.py:_expert_ffn`
with `layer`): rows `[tokens x top-k, k]` sorted by expert, the weights of
EVERY layer as one `[L x E, k, n]` operand, group sizes that are zero outside
one layer's experts — and inside it seeded and skewed: `--hit-pct` of the
held experts get rows (a step's `moe_experts_hit_pct.thr`), `--rows-a-hit` on
average (default: the held share of the step's assignments spread over the
hit experts), the rest of the rows are no expert's. Both orientations: "in"
is gate / up (`k` = hidden, `n` = width), "out" is down.

One JSON line a (file, orientation, candidate `(tk, tn)`): µs a launch —
LAUNCHES launches chained inside one jit, each one's rows carrying a corner
of the last one's output so that they cannot overlap — and `floor_pct`, the
share of the launch that the byte floor of `benchmarks/layer_metrics/_moe.py`
(`least_seconds`: the hit experts' weights once, the assigned rows in and
out; a third of a layer's three matmuls) explains, which is what
`moe_expert_mm_roofline_pct` reads on a capture. `rule` marks the candidate
`moe.gmm_tiling` picks, `today` the fixed `(2048, 1024)` clipped to the
matrix that PR 27 chose at OLMoE's shapes; every candidate is held to
`jax.lax.ragged_dot` on the owned rows (`max_abs_diff`). A candidate Mosaic
refuses is a row with `error`. Exits 1 without a TPU; `--rehearse-cpu` runs
the same control flow here in interpret mode at a toy shape.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas.ops.tpu.megablox import gmm

from benchmarks import serve
from benchmarks.layer_metrics import _moe
from benchmarks.lib.peaks import peaks_of
from ollamamq_tpu.config import EXPERTS
from ollamamq_tpu.models import moe

LAUNCHES = 64
# A step of each file's cell (ledger, PR 67): the rung its steps pad to and
# `moe_experts_hit_pct.thr`.
STEPS = {
    "olmoe-1b-7b-d10": (128, 57), "lfm2-8b-a1b-d18": (128, 91),
    "deepseek-v3.2-ep16-d5": (512, 30),
    "openpangu-ultra-moe-ep16-d5": (128, 26),
    "qwen3-next-80b-a3b-ep4-d12": (512, 98),
    "k-exaone-236b-a23b-ep8-d5": (512, 27),
    "kimi-linear-48b-a3b-ep4-d8": (512, 45),
    "mimo-v2-flash-ep16-d7": (512, 14),
    # (PR 69: a decode pass of 64 rows, 256 pairs over ALL 64 experts)
    "xing4.0-29b-a4b-d6": (64, 95)}
# --rehearse-cpu: (hidden, width, held, routed, top-k, layers), caps.
TOY = (384, 256, 4, 8, 2, 2)
TOY_CAPS = (128, 256, 256)


def group_sizes(rng, held: int, hit: int, rows: int) -> np.ndarray:
    """[held] int32: `hit` experts drawn at random share `rows` rows, each
    at least one, by a Zipf-like draw (the largest a few times the mean)."""
    share = rng.permutation(1.0 / np.arange(1, hit + 1))
    extra = rng.multinomial(max(rows - hit, 0), share / share.sum())
    sizes = np.zeros(held, np.int32)
    sizes[rng.choice(held, hit, replace=False)] = 1 + extra
    return sizes


def candidates(k: int, n: int, caps: tuple, limit: int, rule: tuple) -> list:
    """(tk, tn) to time: today's clip, the rule's choice, the whole
    dimension and the largest tiles that divide it — on each side, the
    columns also past their cap — whose blocks take at most `limit`."""
    tks = {min(caps[1], k), *moe._dividing(k, caps[1])[:2]}
    tns = {min(caps[2], n), *moe._dividing(n, caps[2])[:2],
           *moe._dividing(n, max(caps[1], n))[:2]}
    if k <= 2 * caps[1]:
        tks.add(k)
    fit = [(tk, tn) for tk in sorted(tks) for tn in sorted(tns)
           if moe.gmm_vmem_bytes(caps[0], tk, tn, 2) <= limit]
    return sorted({*fit, rule})


def best_of_three(fn, *args) -> float:
    fn(*args).block_until_ready()
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        fn(*args).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best


def timed(launch, xs, w, sizes) -> float:
    """Seconds a launch: LAUNCHES chained through a corner of the rows.
    (The weights and sizes are the jit's ARGUMENTS — closed over they would
    be constants of the program, gigabytes of them; the group metadata
    depends on the sizes alone, so XLA hoists it out of the loop: what is
    timed is the kernel.)"""
    @jax.jit
    def chain(xs, w, sizes):
        def again(_, xs):
            out = launch(xs, w, sizes)
            c = min(xs.shape[1], out.shape[1], 128)
            return jax.lax.dynamic_update_slice(
                xs, out[:8, :c].astype(xs.dtype), (0, 0))

        return jax.lax.fori_loop(0, LAUNCHES, again, xs)

    return best_of_three(chain, xs, w, sizes) / LAUNCHES


def emit(row: dict, out) -> None:
    line = json.dumps(row)
    print(line, flush=True)
    if out:
        with open(out, "a") as fh:
            fh.write(line + "\n")


def bench(name, shape, tokens, hit_pct, rows_a_hit, caps, args, peaks):
    d, f, held, routed, top_k, layers = shape
    rng = np.random.default_rng(args.seed)
    m = -(-tokens * top_k // caps[0]) * caps[0]
    hit = max(1, round(held * hit_pct / 100))
    rows = min(m, round(hit * rows_a_hit) if rows_a_hit
               else tokens * top_k * held // routed)
    layer = layers // 2
    of_layer = group_sizes(rng, held, hit, max(rows, hit))
    sizes = np.zeros(layers * held, np.int32)
    sizes[layer * held:(layer + 1) * held] = of_layer
    owned = int(of_layer.sum())
    cfg = {"hidden_size": d, "intermediate_size": f}
    floor = _moe.least_seconds(cfg, hit, owned, peaks)[0] \
        / _moe.MATMULS_A_LAYER
    key = jax.random.PRNGKey(args.seed)
    for side, k, n in (("in", d, f), ("out", f, d)):
        kx, kw = jax.random.split(jax.random.fold_in(key, k))
        xs = jax.random.normal(kx, (m, k), jnp.bfloat16)
        one = (jax.random.normal(kw, (held, k, n), jnp.float32)
               / np.sqrt(k)).astype(jnp.bfloat16)
        w = jnp.tile(one, (layers, 1, 1))  # every layer's, as one operand
        want = np.asarray(jax.lax.ragged_dot(
            xs, one, jnp.asarray(of_layer))[:owned], np.float32)
        today = (min(caps[1], k), min(caps[2], n))
        rule = moe.gmm_tiling(m, k, n, 2, caps)[1:]
        for tk, tn in candidates(k, n, caps, args.vmem_bytes, rule):
            def launch(xs, w, sizes, tiling=(caps[0], tk, tn)):
                return gmm(xs, w, sizes, xs.dtype, tiling,
                           interpret=args.rehearse_cpu)
            row = {"config": name, "side": side, "m": m, "k": k, "n": n,
                   "groups": layers * held, "hit": hit, "rows": owned,
                   "tk": tk, "tn": tn, "today": (tk, tn) == today,
                   "rule": (tk, tn) == rule,
                   "vmem_mib": round(moe.gmm_vmem_bytes(
                       caps[0], tk, tn, 2) / 2**20, 2)}
            try:
                got = np.asarray(jax.jit(launch)(
                    xs, w, jnp.asarray(sizes))[:owned], np.float32)
                s = timed(launch, xs, w, jnp.asarray(sizes))
                row.update({
                    "us_a_launch": round(s * 1e6, 2),
                    "floor_us": round(floor * 1e6, 2),
                    "floor_pct": round(100 * floor / s, 1),
                    "max_abs_diff": float(np.abs(got - want).max())})
            except Exception as e:  # noqa: BLE001 — a tiling the compiler
                # refuses is a row, not the end of the run
                row["error"] = str(e)[:300]
            emit(row, args.out)
        del xs, w, one


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("configs", nargs="*", help="files under "
                    "benchmarks/configs/ (default: every sparse one)")
    ap.add_argument("--tokens", type=int, help="a step's padded tokens "
                    "(default: the file's cell's rung, STEPS)")
    ap.add_argument("--hit-pct", type=float, help="share of the held "
                    "experts that get a row (default: the cell's, STEPS)")
    ap.add_argument("--rows-a-hit", type=float, help="mean rows of a hit "
                    "expert (default: the held share of tokens x top-k)")
    ap.add_argument("--vmem-bytes", type=int, default=29 * 2**19,
                    help="time no candidate whose blocks take more")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "gmm_bench.jsonl"),
        help="the rows again, appended to this file (the end of a chip "
             "call's output is all that comes back of it)")
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    dev = jax.devices()[0]
    if args.out:
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
    if args.rehearse_cpu:
        peaks = peaks_of("TPU v5 lite")
        bench("toy", TOY, args.tokens or 96, args.hit_pct or 50,
              args.rows_a_hit, TOY_CAPS, args, peaks)
        return 0
    if dev.platform != "tpu":
        print(json.dumps({"detail": f"no TPU: platform is {dev.platform}"}))
        return 1
    emit({"device_kind": dev.device_kind}, args.out)
    peaks = peaks_of(dev.device_kind)
    for path in args.configs or sorted(
            glob.glob(os.path.join(ROOT, "benchmarks", "configs", "*.json"))):
        with open(path) as fh:
            cfg = json.load(fh)
        mc = serve.model_config(cfg, False)
        if not mc.num_experts:
            continue
        tokens, hit_pct = STEPS.get(cfg["name"], (512, 50))
        shape = (mc.hidden_size, mc.expert_width, mc.num_experts,
                 mc.router_width, mc.num_experts_per_tok,
                 mc.count(EXPERTS) + mc.num_nextn_predict_layers)
        bench(cfg["name"], shape, args.tokens or tokens,
              args.hit_pct or hit_pct, args.rows_a_hit, moe.GMM_TILING,
              args, peaks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
