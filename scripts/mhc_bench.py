"""Microseconds a launch of the hyper-connection's two kernels
(ops/pallas/hyper_connection.py) beside their jnp twins (XLA fusions), on the
chip: `chiprun -- python scripts/mhc_bench.py` (~2 min).

At the published width (four bfloat16 streams of 3584, Phi [24, 14336]
float32) and the cell's two sizes — 64 rows (a decode pass) and 512 (a prompt
chunk) — it times `LAUNCHES` applications chained inside one jit, so that
they cannot overlap: `out`, the write-back alone (x' = mix_out(x, d, maps),
x' the next one's x), and `pair`, a whole application around a sublayer that
does nothing (h, maps = mix_in(x); x' = mix_out(x, h, maps)); `in` is their
difference. Each on the Pallas path and on the jnp path (what a port with no
kernel would launch: ~40 reductions an application). One JSON line a (rows,
path): µs an `out`, an `in`, a `pair`, the pair's share of the byte floor
(_mhc.least_seconds: one pass over the streams and Phi once), and
`max_abs_diff` of the kernels' maps / h / x' against the twins' ON THE CHIP.
Exits 1 without a TPU (`--rehearse-cpu`: the same control flow here, interpret
mode, a toy width), or where a kernel is further from its twin than `CLOSE`.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.layer_metrics import _mhc
from benchmarks.lib.peaks import peaks_of
from ollamamq_tpu.ops import hyper_connection as hc

LAUNCHES = 64
# maps to float32 rounding; bfloat16 streams to an ulp of |x| < 16
CLOSE = {"maps": 5e-6, "h": 2.0 ** -4, "x": 2.0 ** -4}


def operands(rows: int, n: int, c: int, dtype, seed: int):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    maps = 2 * n + n * n
    return (jax.random.normal(ks[0], (rows, n, c), jnp.float32).astype(dtype),
            jax.random.normal(ks[1], (maps, n * c), jnp.float32)
            / np.sqrt(n * c),
            jnp.array([1.0, 0.8, 1.2], jnp.float32),
            0.5 * jax.random.normal(ks[2], (maps,), jnp.float32)
            + jnp.concatenate([jnp.zeros(2 * n), 1.5 * jnp.eye(n).reshape(-1)]),
            jax.random.normal(ks[3], (rows, c), jnp.float32).astype(dtype))


def best_of_three(fn, *args) -> float:
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best / LAUNCHES


def bench(rows, n, c, dtype, k, impl, interpret, peaks, seed) -> dict:
    x, phi, alpha, bias, d = operands(rows, n, c, dtype, seed)
    kw = dict(impl=impl, interpret=interpret)  # (the twins take no notice)

    @jax.jit
    def out(x, d, maps):
        return jax.lax.fori_loop(0, LAUNCHES, lambda _, x: hc.mix_out(
            x, d, maps, k, **kw), x)

    @jax.jit
    def pair(x, phi, alpha, bias):
        def once(_, x):
            h, maps = hc.mix_in(x, phi, alpha, bias, k, **kw)
            return hc.mix_out(x, h, maps, k, **kw)

        return jax.lax.fori_loop(0, LAUNCHES, once, x)

    h, maps = jax.jit(lambda *a: hc.mix_in(*a, k, **kw))(x, phi, alpha, bias)
    row = {"rows": rows, "streams": n, "hidden": c, "impl": impl,
           "us_out": 1e6 * best_of_three(out, x, d, maps),
           "us_pair": 1e6 * best_of_three(pair, x, phi, alpha, bias)}
    row["us_in"] = row["us_pair"] - row["us_out"]
    if peaks:
        least, bound = _mhc.least_seconds(
            {"hc_mult": n, "hidden_size": c}, rows, 2, 1, peaks)
        row.update(floor_us=1e6 * least, bound_by=bound,
                   floor_pct=100 * 1e6 * least / row["us_pair"])
    if impl == "pallas":  # ...and held to the twins, here
        h0, maps0 = jax.jit(lambda *a: hc.mix_in(*a, k))(x, phi, alpha, bias)
        x1 = jax.jit(lambda *a: hc.mix_out(*a, k, **kw))(x, d, maps)
        x0 = jax.jit(lambda *a: hc.mix_out(*a, k))(x, d, maps0)
        f32 = jnp.float32
        row["max_abs_diff"] = {
            "maps": float(jnp.abs(maps[:, :maps0.shape[1]] - maps0).max()),
            "h": float(jnp.abs(h.astype(f32) - h0.astype(f32)).max()),
            "x": float(jnp.abs(x1.astype(f32) - x0.astype(f32)).max())}
        row["ok"] = all(row["max_abs_diff"][name] <= CLOSE[name]
                        for name in CLOSE)
    return row


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, nargs="*", default=[64, 512])
    ap.add_argument("--hidden", type=int, default=3584)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    dev = jax.devices()[0]
    if not args.rehearse_cpu and dev.platform != "tpu":
        print(json.dumps({"detail": f"no TPU: platform is {dev.platform}"}))
        return 1
    peaks = None if args.rehearse_cpu else peaks_of(dev.device_kind)
    c = 256 if args.rehearse_cpu else args.hidden
    dtype = jnp.float32 if args.rehearse_cpu else jnp.bfloat16
    k = hc.Consts(4, 20, 1e-6, 1e-6, -30.0, 30.0)
    print(json.dumps({"device_kind": dev.device_kind}), flush=True)
    failed = []
    for rows in args.rows:
        for impl in ("pallas", "jnp"):
            row = bench(rows, 4, c, dtype, k, impl, args.rehearse_cpu, peaks,
                        args.seed)
            print(json.dumps(row), flush=True)
            if row.get("ok") is False:
                failed.append((rows, impl))
    print(json.dumps({"ok": not failed, "failed": failed}), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
