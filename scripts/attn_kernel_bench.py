"""Seconds a launch of the two paged-attention kernels, by inner product
and head shape, on the chip: `chiprun -- python scripts/attn_kernel_bench.py`.

For each published head shape (H, Hk, hd) and each inner product a kernel
can be built with (`ops/pallas/kv_contract.py`: the decode kernel takes
either, the ragged kernel has one) it builds a bf16 pool at the CLI's defaults
(1024 pages of 32 tokens, 64 slots) with 64 sequences of 200-380 tokens
of context, checks the kernel against the jnp reference, and times
LAUNCHES calls chained inside one jit (each call's q is the last one's
output, so they cannot overlap). Traffic: "decode" = the decode kernel,
64 rows; "ragged64" = the ragged kernel on the same 64 decode rows (a
ragged step with nothing to prefill); "ragged512" = 56 decode rows and
two 228-token prefill spans. It also asks what Mosaic's default-precision
float32 matmul keeps of its operand (`f32_matmul_keeps`): the Vpu body's
`p @ seg_t` is one. One JSON line a measurement; exits 1 without a TPU.

A variant of a kernel's body is measured here before a whole cell: PR 34
timed the successor walk and the lane-tile loop each unrolled in Python
and as a loop in the program, through two module switches that lived for
that run (the result, and why only the walk is in the program, is in
`kv_contract.py`'s docstring). Do the same for the next variant: a
switch this script sets, `jax.clear_caches()`, one more row a shape.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ollamamq_tpu.ops.attention import (paged_decode_attention_any,
                                        ragged_attention_any)
from ollamamq_tpu.ops.pallas import kv_contract
from ollamamq_tpu.ops.pallas.paged_attention import (
    paged_decode_attention_pallas)
from ollamamq_tpu.ops.pallas.ragged_attention import (
    ragged_paged_attention_pallas)

SHAPES = ((28, 4, 128), (8, 2, 128), (32, 8, 64), (16, 16, 128))
B, MP, PS, NP, LAYER = 64, 256, 32, 1024, 1
LAUNCHES = 64


def f32_matmul_keeps() -> dict:
    """1 + 2**-10 has 11 significant bits: a bf16 pass returns 1.0, and
    1 + 2**-18 tells bf16x3 (yes) from one tf32-like pass (no)."""
    def kernel(p_ref, w_ref, o_ref):
        o_ref[...] = jax.lax.dot_general(
            p_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    out = {}
    for bits in (10, 18):
        p = jnp.full((32, 8), 1.0 + 2.0 ** -bits, jnp.float32)
        w = jnp.eye(8, 128, dtype=jnp.float32)
        got = pl.pallas_call(kernel, out_shape=jax.ShapeDtypeStruct(
            (32, 128), jnp.float32))(p, w)
        out[f"one_plus_2^-{bits}"] = float(got[0, 0]) == 1.0 + 2.0 ** -bits
    return out


def batch(rng, n_decode, spans):
    """(page_table, tok_seq, tok_pos, kv_len, q_start, q_len, T)."""
    rows = [(1, int(rng.integers(200, 380))) for _ in range(n_decode)]
    rows += [(n, 0) for n in spans]
    T = sum(n for n, _ in rows)
    pt = np.zeros((B, MP), np.int32)
    q_len, kv_len = np.zeros(B, np.int32), np.zeros(B, np.int32)
    q_start = np.full(B, T, np.int32)
    tok_seq, tok_pos = np.zeros(T, np.int32), np.zeros(T, np.int32)
    off, page = 0, 1
    for s, (n, prefix) in enumerate(rows):
        need = -(-(prefix + n) // PS)
        pt[s, :need] = np.arange(page, page + need)
        page += need
        q_len[s], kv_len[s], q_start[s] = n, prefix + n, off
        tok_seq[off:off + n] = s
        tok_pos[off:off + n] = prefix + np.arange(n)
        off += n
    assert page <= NP, page
    return [jnp.asarray(a) for a in (pt, tok_seq, tok_pos, kv_len, q_start,
                                     q_len)], T


def timed(fn, q, *args) -> float:
    """Seconds a launch: LAUNCHES calls chained through q in one jit."""
    @jax.jit
    def chain(q, *args):
        return jax.lax.fori_loop(
            0, LAUNCHES, lambda _, q: fn(q, *args).astype(q.dtype), q)

    chain(q, *args).block_until_ready()
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        chain(q, *args).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best / LAUNCHES


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--pv-terms", type=int, nargs="*", default=[],
                    help="also time the Mxu body with P split into this "
                         "many bf16 terms (kv_contract.PV_TERMS)")
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(json.dumps({"detail": f"no TPU: platform is {dev.platform}"}))
        return 1
    print(json.dumps({"device_kind": dev.device_kind,
                      "f32_matmul_keeps": f32_matmul_keeps()}), flush=True)
    rng = np.random.default_rng(args.seed)
    default_terms = kv_contract.PV_TERMS
    variants = [("vpu", None), ("mxu", None)] + [("mxu", n)
                                                 for n in args.pv_terms]
    for H, Hk, hd in SHAPES:
        kc, vc = (jnp.asarray(rng.standard_normal((2, NP * PS, Hk * hd)),
                              jnp.bfloat16) for _ in range(2))
        for traffic, n_dec, spans in (("decode", 64, ()),
                                      ("ragged64", 64, ()),
                                      ("ragged512", 56, (228, 228))):
            (pt, tok_seq, tok_pos, kv_len, q_start, q_len), T = batch(
                np.random.default_rng(args.seed), n_dec, spans)
            q = jnp.asarray(rng.standard_normal((T, H, hd)), jnp.bfloat16)
            if traffic == "decode":
                ref = paged_decode_attention_any(
                    "jnp", q, kc, vc, LAYER, pt, kv_len, PS)
            else:
                ref = ragged_attention_any(
                    "jnp", q, kc, vc, LAYER, pt, tok_seq, tok_pos, kv_len,
                    q_start, q_len, PS)
            for inner, terms in variants:
                if traffic != "decode" and inner == "vpu":
                    continue  # the ragged kernel has one inner product
                if terms is not None:
                    kv_contract.PV_TERMS = terms
                    jax.clear_caches()
                if traffic == "decode":
                    def fn(q, kc, vc, pt, kv_len, inner=inner):
                        return paged_decode_attention_pallas(
                            q, kc, vc, LAYER, pt, kv_len, PS, inner=inner)
                    operands = (kc, vc, pt, kv_len)
                else:
                    def fn(q, kc, vc, pt, qs, ql, kl):
                        return ragged_paged_attention_pallas(
                            q, kc, vc, LAYER, pt, qs, ql, kl, PS)
                    operands = (kc, vc, pt, q_start, q_len, kv_len)
                out = fn(q, *operands)
                diff = np.abs(np.asarray(out, np.float32)
                              - np.asarray(ref, np.float32))
                print(json.dumps({
                    "shape": [H, Hk, hd], "traffic": traffic, "tokens": T,
                    "inner": inner, "pv_terms": terms
                    if terms is not None else kv_contract.PV_TERMS,
                    "ms_a_launch": round(timed(fn, q, *operands) * 1e3, 4),
                    "max_abs_diff_vs_jnp": float(diff.max()),
                    "finite": bool(np.isfinite(np.asarray(
                        out, np.float32)).all()),
                }), flush=True)
                if terms is not None:
                    kv_contract.PV_TERMS = default_terms
                    jax.clear_caches()
    return 0


if __name__ == "__main__":
    sys.exit(main())
