#!/usr/bin/env python3
"""What reading a step's ids back costs, and what asking for them early
buys (a builder's tool; needs the chip).

    chiprun --timeout 600 -- python scripts/readback_bench.py
    chiprun --chips 4 --timeout 600 -- python scripts/readback_bench.py

One jitted program of `--ms` milliseconds or so (a chain of matmuls; over
several chips the operand is sharded and the chain ends in an all-reduce, so
the results are replicated as a `--tp` step's ids are) that returns two
small int32 arrays, as a ragged step returns `toks` and `n_emit`. Each way
of reading them is timed from the jitted call to the last read's return,
`--iters` times, the ways interleaved:

  asarray         np.asarray on each array in turn — what `step_collect`
                  did up to PR 69, the host arriving while the step runs
  async           `copy_to_host_async()` on each right after the call, then
                  the same reads — what the launch path does since PR 70
  late            the host arrives after the step ended (`block_until_ready`
                  first): the read asks for the transfer only then
  late_async      the same with the transfers asked for at the launch
  one / one_async the first array alone (a fused scan returns only `toks`)

`tail_ms` is a way's median less the median wall to `block_until_ready`
alone (`ready`): what the read costs on top of the step. `--ms` takes
several program lengths (a fused scan holds the chip for ~100 ms), and
`--beside` says what else runs Python in the process meanwhile: `nothing`,
`server` (a thread that works ~0.2 ms under the GIL and sleeps 0.5 ms, as a
thread that writes frames does) or `spin` (a thread that never lets go of
the GIL but when the interpreter's switch interval takes it). One JSON line
a (length, neighbour, way), the last line `{"ok": true, ...}`. `--cpu`
rehearses it here.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--ms", type=float, nargs="+", default=[12.0])
    ap.add_argument("--iters", type=int, default=200)
    ap.add_argument("--beside", nargs="+", default=["nothing"],
                    choices=["nothing", "server", "spin"])
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()
    if args.cpu:
        from ollamamq_tpu.platform_force import force_cpu
        force_cpu(1)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devs = jax.devices()
    n_dev = len(devs)
    mesh = Mesh(np.array(devs), ("tensor",))
    D = 512 if args.cpu else 2048
    rep = NamedSharding(mesh, P())
    x = jax.device_put(jnp.ones((8 * n_dev, D), jnp.bfloat16),
                       NamedSharding(mesh, P("tensor", None)))
    w = jax.device_put(jnp.eye(D, dtype=jnp.bfloat16), rep)

    def build(trips):
        def prog(x, w, i):
            def body(_, h):
                return jnp.tanh(h @ w)
            h = jax.lax.fori_loop(0, trips, body, x)
            s = jnp.sum(h.astype(jnp.float32), axis=0)  # all-reduce over chips
            toks = (s[:512].reshape(8, 64) * 0).astype(jnp.int32) + i
            return toks, toks[0] + 1
        return jax.jit(prog, out_shardings=(rep, rep))

    def wall(fn, n):
        out = []
        for i in range(n):
            t0 = time.perf_counter()
            fn(i)
            out.append((time.perf_counter() - t0) * 1e3)
        return out

    def neighbour(kind, stop):
        n = 0
        while not stop.is_set():
            if kind == "spin":
                n += 1
            else:
                n += sum(range(4000))
                time.sleep(0.0005)

    def measure(f, ms, beside):
        def ready(i):
            jax.block_until_ready(f(x, w, i))

        def asarray(i):
            a, b = f(x, w, i)
            assert np.asarray(a)[0, 0] == i and np.asarray(b)[0] == i + 1

        def async_(i):
            a, b = f(x, w, i)
            a.copy_to_host_async()
            b.copy_to_host_async()
            assert np.asarray(a)[0, 0] == i and np.asarray(b)[0] == i + 1

        def late(i):
            a, b = f(x, w, i)
            jax.block_until_ready((a, b))
            np.asarray(a), np.asarray(b)

        def late_async(i):
            a, b = f(x, w, i)
            a.copy_to_host_async()
            b.copy_to_host_async()
            jax.block_until_ready((a, b))
            np.asarray(a), np.asarray(b)

        def one(i):
            a, _ = f(x, w, i)
            np.asarray(a)

        def one_async(i):
            a, _ = f(x, w, i)
            a.copy_to_host_async()
            np.asarray(a)

        ways = [ready, asarray, async_, late, late_async, one, one_async]
        times = {fn.__name__: [] for fn in ways}
        iters = max(20, int(args.iters * min(1.0, 12.0 / ms)))
        for i in range(iters):
            for fn in ways:
                times[fn.__name__] += wall(fn, 1)
        base = statistics.median(times["ready"])
        for name, ts in times.items():
            q = statistics.quantiles(ts, n=4)
            print(json.dumps({
                "ms": ms, "beside": beside, "way": name.rstrip("_"),
                "median_ms": round(q[1], 4), "q1_ms": round(q[0], 4),
                "q3_ms": round(q[2], 4), "tail_ms": round(q[1] - base, 4),
                "iters": iters}), flush=True)

    # Size the chain to --ms on this device: a trip's time from two chain
    # lengths, so that what a launch costs whatever its length is left out.
    t = {}
    for trips in (64, 256):
        f = build(trips)
        jax.block_until_ready(f(x, w, 0))
        t[trips] = statistics.median(wall(
            lambda i: jax.block_until_ready(f(x, w, i)), 10))
    a_trip = max((t[256] - t[64]) / 192, 1e-4)
    for ms in args.ms:
        f = build(max(1, int(ms / a_trip)))
        jax.block_until_ready(f(x, w, 0))
        for beside in args.beside:
            stop = threading.Event()
            th = None
            if beside != "nothing":
                th = threading.Thread(target=neighbour, args=(beside, stop),
                                      daemon=True)
                th.start()
            try:
                measure(f, ms, beside)
            finally:
                stop.set()
                if th is not None:
                    th.join()
    a, _ = f(x, w, 0)
    print(json.dumps({
        "ok": True, "platform": devs[0].platform,
        "device_kind": devs[0].device_kind, "devices": n_dev,
        "switch_interval_s": sys.getswitchinterval(),
        "fully_replicated": bool(a.is_fully_replicated)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
