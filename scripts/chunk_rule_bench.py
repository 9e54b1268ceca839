"""Seconds a launch of the chunked rule's (row, window) pairs, the Pallas
kernel (`ops/pallas/chunk_rule.py`) against the XLA pair loop it replaces, on
the chip: `chiprun --timeout 1500 -- python scripts/chunk_rule_bench.py`
(~3 min).

For each of the four callers' published shapes (H, Hk, dk, dv, plain) —
Qwen3-Next's rule (32, 16, 128, 128), Olmo-Hybrid's (30, 30, 96, 192: a
head's lanes are no whole tile), Falcon-H1's mixer (32, 2, 256, 128, plain: B
and C a group), MiniCPM-SALA's lightning layers (32, 32, 128, 128, plain)
and Kimi Delta Attention's (32, 32, 128, 128 at the VECTOR reading: a decay a
key channel, g [T, H, dk] from -1e-3 to -20 a token a channel, both ends in
one head) — and each stream of 512 tokens over 64 rows

  - `rows5`, `rows56`: 5 / 56 one-token rows and padding — no pair: what
    `ragged` costs a layer before its first pair (`_step_rows`, `_prepare`
    over the 8 windows, and the kernel's launch);
  - `shortspan`: `rows5`, then one 8-token span that continues its state:
    1 pair, and a state' of which a seventh is still the state that came in
    — a kernel that never read its row's state is ~0.1 off here, where
    `longspan`'s state' has forgotten it;
  - `longspan`: `rows5`, then one 507-token span that continues its state
    (the step of `qwen3-next-80b-a3b-ep4-d12.longctx`): 9 pairs;
  - `spans56`: `rows56`, then two 228-token spans, one of which opens its
    state (a `.batch` cell's ragged step): 9 pairs, two of them in one
    window;

it runs `ops/gated_delta.ragged(impl="pallas")` with both kernels (`kernel`:
the window solve `chunk_solve_pallas`, PR 66, in front of the pairs'
`chunk_rule_pallas`), with the XLA solve in front of the pair kernel
(`xla_solve`: `chunk_solve.blocks` answering None — what PR 62 left) and with
the XLA solve and the loop (`loop`: `chunk_rule.blocks` answering None), HOLDS
each to the jnp path on the
same stream — the outputs and every state row of every layer, and the spans'
tokens and state rows apart (`span_diff_o`, `span_diff_state`: the one-token
rows' kernel does not hide them), each within CLOSE = 1e-5 of the largest
entry it is compared with (a v5e reads 0 to 5e-7 of it; a kernel that never
copies the state in, NaN) — and times LAUNCHES calls chained inside one jit
(a call's q, k, v and g are the last one's plus its output scaled to nothing,
its state the last one's: nothing is the loop's invariant — until PR 66 q, k
and g were, and XLA hoisted the whole window solve but `u` out of the timed
loop: the launches PR 62 and PR 63 recorded carry ~0.2–0.5 ms too little of
it on every path alike, their µs a pair stand). `us_a_pair` is a launch
less the same path's launch of the stream's one-token rows alone, a pair.
`--set chunk_rule.VMEM_BYTES=6291456` (any constant of the kernels' modules,
`chunk_solve.VMEM_BYTES` too) adds a row a (shape, stream). `--solve` times
the window solve ALONE instead (no pair row): `chunk_solve_pallas` against
`_prepare` with the concatenations the pair kernel's wrapper then does, µs a
layer, the kernel's results held to `_prepare`'s on the windows a span touches
(CLOSE of the largest entry) — a `plain` shape has no solve and says so. One
JSON line a measurement, then `{"ok": ..., "failed": [...]}`: exits 1 without
a TPU, and where a row of the tree's own paths (no `--set`) raised or is
further than CLOSE from what it is held to.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from attn_kernel_bench import best_of_three, constants, parse_set, MODULES
from ollamamq_tpu.ops import gated_delta
from ollamamq_tpu.ops.pallas import chunk_rule, chunk_solve

MODULES["chunk_rule"] = chunk_rule
MODULES["chunk_solve"] = chunk_solve
# (name, H, Hk, dk, dv, plain); VECTOR: the shapes run with a decay a key
# channel.
SHAPES = (("qwen3-next", 32, 16, 128, 128, False),
          ("olmo-hybrid", 30, 30, 96, 192, False),
          ("falcon-h1", 32, 2, 256, 128, True),
          ("minicpm-sala", 32, 32, 128, 128, True),
          ("kimi-linear", 32, 32, 128, 128, False))
VECTOR = ("kimi-linear",)
T, SLOTS, LAYERS, LAYER, LAUNCHES = 512, 64, 2, 1, 32
CLOSE = 1e-5  # of the largest entry compared
# stream: (one-token rows, spans, which rows open their state)
STREAMS = {"rows5": (5, (), ()), "shortspan": (5, (8,), ()),
           "longspan": (5, (507,), ()),
           "rows56": (56, (), ()), "spans56": (56, (228, 228), (57,))}


def stream(rows, ones, spans, opens):
    """(slot_ids, tok_seq, tok_pos, q_start, q_len, is_first) of a stream of
    T tokens over `rows` rows: the one-token rows, then the spans, then
    padding."""
    q_len = np.zeros(rows, np.int32)
    q_len[:ones + len(spans)] = (1,) * ones + tuple(spans)
    q_start = np.minimum(np.concatenate([[0], np.cumsum(q_len)[:-1]]), T)
    tok_seq, tok_pos = np.zeros(T, np.int32), -np.ones(T, np.int32)
    for b, (s, n) in enumerate(zip(q_start, q_len)):
        tok_seq[s:s + n], tok_pos[s:s + n] = b, 100 + np.arange(n)
    is_first = np.zeros(rows, np.int32)
    is_first[list(opens)] = 1
    return [jnp.asarray(a, jnp.int32) for a in (
        np.arange(rows), tok_seq, tok_pos, q_start, q_len, is_first)]


def apart(got, want, meta):
    """(how far (o, state) is from the jnp path's — everything, and the
    spans' tokens and state rows alone —, the names of those further than
    CLOSE of the largest entry they are compared with)."""
    (o, s), (want_o, want_s) = got, want
    span = np.asarray(meta[4]) > 1  # (row b's state row is slot b)
    in_span = span[np.asarray(meta[1])] & (np.asarray(meta[2]) >= 0)
    held = {"max_abs_diff_o": (o, want_o), "max_abs_diff_state": (s, want_s)}
    if span.any():
        held["span_diff_o"] = (o[in_span], want_o[in_span])
        held["span_diff_state"] = (s[LAYER, :SLOTS][span],
                                   want_s[LAYER, :SLOTS][span])
    top = {name: float(jnp.abs(b).max()) for name, (_, b) in held.items()}
    row = {name: float(jnp.abs(a - b).max()) for name, (a, b) in held.items()}
    far = [name for name in held if not row[name] <= CLOSE * top[name]]
    row.update(max_abs_o=top["max_abs_diff_o"],
               max_abs_state=top["max_abs_diff_state"])
    return row, far


def solve_paths(beta, meta, plain):
    """The window solve alone (`gated_delta.ragged`'s step 2) on a stream,
    both ways: `(q, k, v, g) -> what chunk_rule_pallas reads` through the
    kernel (`chunk_solve_pallas`) and through `_prepare` with the
    concatenations and transposes the pair kernel's wrapper then does; and
    the windows a span touches (the only ones the kernel writes)."""
    part = (meta[4] > 1)[meta[1]] & (meta[2] >= 0)

    def cut(x):
        return x.reshape(T // gated_delta.CHUNK, gated_delta.CHUNK,
                         *x.shape[1:])

    row_of = cut(jnp.where(part, meta[1], -1))
    same = (row_of[:, :, None] == row_of[:, None, :]) & jnp.tril(
        jnp.ones((gated_delta.CHUNK,) * 2, bool))
    bw = cut(jnp.where(part[:, None], beta, 0.0))

    def gw(g):
        return cut(jnp.where(
            part[(slice(None),) + (None,) * (g.ndim - 1)], g, 0.0))

    def kernel(q, k, v, g):
        return chunk_solve.chunk_solve_pallas(
            *gated_delta._operands(cut(q), cut(k), v.shape[-2], plain),
            cut(v), gw(g), bw, row_of)

    def xla(q, k, v, g):
        return chunk_solve.laid_out(gated_delta._prepare(
            cut(q), cut(k), cut(v), gw(g), bw, same, plain))

    return {"kernel": kernel, "xla": xla}, np.asarray(
        jnp.any(row_of >= 0, axis=1))


def solve_rows(name, q, k, v, g, beta, plain, vector, streams, variants,
               failed):
    """A row a (stream, path): µs a layer of the solve alone, LAUNCHES
    chained inside one jit (a launch's q, k, v and g are the last one's plus
    its results scaled to nothing: nothing is the loop's invariant — the
    sums that carry it read every result once, ~40 MB, on both paths), and
    how far the kernel's results are from `_prepare`'s on the windows a span
    touches."""
    h, dk, dv = v.shape[1], q.shape[-1], v.shape[-1]
    took = chunk_solve.blocks(h, dk, dv, plain, vector)
    if not took:
        print(json.dumps({"shape": name, "solve": None, "detail":
                          "no solve at this shape (`blocks` is None)"}),
              flush=True)
        return
    for which in streams:
        meta = stream(SLOTS, *STREAMS[which])
        paths, touched = solve_paths(beta, meta, plain)
        want = jax.jit(paths["xla"])(q, k, v, g)
        for path, consts in [("kernel", {}), ("xla", {})] + [
                ("kernel", c) for c in variants]:
            fn = paths[path]

            def chain(*qkvg, fn=fn):
                def body(_, qkvg):
                    tiny = 1e-30 * sum(x.sum() for x in fn(*qkvg).values())
                    return tuple(x + tiny for x in qkvg[:3]) + (
                        qkvg[3] - jnp.abs(tiny),)
                return jax.lax.fori_loop(0, LAUNCHES, body, qkvg)[2]

            with constants(consts) as names:
                row = {"shape": name, "stream": which, "solve": path,
                       "windows_touched": int(touched.sum()), "set": names,
                       "blocks": chunk_solve.blocks(h, dk, dv, plain,
                                                    vector)}
                solve_row(row, fn, chain, (q, k, v, g), want, touched)
            if not names and (row.get("far") or "error" in row):
                failed.append([name, which, "solve:" + path])
            print(json.dumps(row), flush=True)


def solve_row(row, fn, chain, qkvg, want, touched):
    """`row`, measured: how far `fn`'s results are from `want` on the
    windows `touched` (window 0 where none is), and µs a layer."""
    try:
        got = jax.jit(fn)(*qkvg)
        at = touched if touched.any() else np.arange(len(touched)) < 1
        top = {n: float(jnp.abs(want[n][at]).max()) for n in want}
        diff = {n: float(jnp.abs(got[n][at] - want[n][at]).max())
                for n in want}
        row["far"] = [n for n in want
                      if not diff[n] <= CLOSE * max(top[n], 1.0)]
        row.update(max_abs_diff=diff, max_abs=top)
        us = best_of_three(jax.jit(chain), *qkvg) / LAUNCHES * 1e6
        row["us_a_layer"] = round(us, 2)
    except Exception as e:  # noqa: BLE001 — a row, not the run's end
        row["error"] = str(e)[:400]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--set", action="append", default=[], type=parse_set,
                    metavar="chunk_rule.NAME=VALUE[,…]", dest="variants")
    ap.add_argument("--shapes", type=int, nargs="*",
                    default=list(range(len(SHAPES))))
    ap.add_argument("--streams", nargs="*", default=list(STREAMS),
                    choices=list(STREAMS))
    ap.add_argument("--solve", action="store_true",
                    help="the window solve alone: chunk_solve_pallas against "
                    "_prepare, µs a layer (and no pair row)")
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(json.dumps({"detail": f"no TPU: platform is {dev.platform}"}))
        return 1
    print(json.dumps({"device_kind": dev.device_kind}), flush=True)
    no_kernel = {(chunk_rule, "blocks"): lambda *a: None}
    no_solver = {(chunk_solve, "blocks"): lambda *a: None}
    failed = []  # rows of the tree's own paths that raised or are far
    for name, h, hk, dk, dv, plain in (SHAPES[i] for i in args.shapes):
        rng = np.random.default_rng(args.seed)
        f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
        q, k, v = f(T, hk, dk), f(T, hk, dk) + 1.0, f(T, h, dv)
        if plain:  # as a mixer hands them: no norm behind it
            q, k = q * dk ** -0.5, k * dk ** -0.5
        g = -jnp.abs(f(T, h)) * 0.3
        vector = name in VECTOR
        if vector:  # a channel a decade from -1e-3 to -20, a token its own
            g = -jnp.abs(f(T, h, dk)) * 10.0 ** jnp.linspace(-3, 1.3, dk)
        beta = jnp.ones((T, h)) if plain else jax.nn.sigmoid(f(T, h)) * 2
        state0 = f(LAYERS, SLOTS + 1, dk, h * dv)
        if args.solve:
            solve_rows(name, q, k, v, g, beta, plain, vector, args.streams,
                       args.variants, failed)
            continue

        def fn(v, state, *meta, impl="pallas", qkg=(q, k, g)):
            return gated_delta.ragged(*qkg[:2], v, qkg[2], beta, state, LAYER,
                                      *meta, impl=impl, plain=plain)

        def chain(v, state, *meta):
            def body(_, carry):  # q, k and g carried too: the window solve
                # reads nothing else, and a loop's invariant is hoisted
                q, k, v, g, state = carry
                o, state = fn(v, state, *meta, qkg=(q, k, g))
                tiny = 1e-30 * o[0, 0, 0]
                return q + tiny, k + tiny, v + 1e-30 * o, \
                    g - jnp.abs(tiny), state
            return jax.lax.fori_loop(0, LAUNCHES, body,
                                     (q, k, v, g, state))[-1]

        base = {}  # (one-token rows, path, set) -> their launch alone, µs
        for which in args.streams:
            meta = stream(SLOTS, *STREAMS[which])
            want = jax.jit(
                lambda v, s, *m: fn(v, s, *m, impl="jnp"))(v, state0, *meta)
            n_w = np.where(np.asarray(meta[4]) > 1, (
                np.asarray(meta[3]) + np.asarray(meta[4]) - 1) // 64
                - np.asarray(meta[3]) // 64 + 1, 0)
            for path, consts in [("kernel", {}), ("xla_solve", no_solver),
                                 ("loop", no_kernel)] + [
                    ("kernel", c) for c in args.variants]:
                with constants(consts) as names:
                    names.pop("chunk_rule.blocks", None)
                    names.pop("chunk_solve.blocks", None)
                    row = {"shape": name, "stream": which, "path": path,
                           "pairs": int(n_w.sum()), "set": names,
                           "blocks": chunk_rule.blocks(h, dk, dv, plain,
                                                       vector)}
                    try:
                        diffs, far = apart(jax.jit(fn)(v, state0, *meta),
                                           want, meta)
                        us = best_of_three(jax.jit(chain), v, state0,
                                           *meta) / LAUNCHES * 1e6
                        row.update({"ms_a_launch": round(us / 1e3, 4),
                                    **diffs, "far": far})
                        key = (STREAMS[which][0], path, str(names))
                        if not row["pairs"]:
                            base[key] = us
                        elif key in base:
                            row["us_a_pair"] = round(
                                (us - base[key]) / row["pairs"], 4)
                    except Exception as e:  # noqa: BLE001 — a variant the
                        # compiler refuses is a row, not the end of the run
                        row["error"] = str(e)[:400]
                    if not names and (row.get("far") or "error" in row):
                        failed.append([name, which, path])
                    print(json.dumps(row), flush=True)
    print(json.dumps({"ok": not failed, "failed": failed}), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
