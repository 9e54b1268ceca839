"""The block scores' inner part as a Pallas TPU kernel: for one query a row,
the softmax over the row's pooled keys a head, summed over a kv group's heads
(ops/block_select.py: `pooled_probs` is its definition and the CPU's path).

A program is one (row, kv head): the group's G query heads [G, hd] against
the row's pooled keys of that kv head [J, hd] (gathered by the page table in
XLA, J padded to whole lane tiles) — one [G, J] contraction on the MXU, the
mask of the rows not yet defined at the row's context (stride j + kernel >
n), the softmax along J in float32 and the sum over G. The max-pool to blocks
and the top-k stay in XLA: they are [rows, kv heads, J] float32 and smaller.
The Mosaic custom call carries this function's name on the device trace
(`bsa_select_pallas`), which is how the benchmark's readers find the select.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(n_ref, q_ref, pk_ref, o_ref, *, kernel: int, stride: int,
            scale: float):
    n = n_ref[pl.program_id(0)]
    s = jax.lax.dot_general(
        q_ref[...], pk_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale  # [G, J]
    j = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    defined = j * stride + kernel <= n
    s = jnp.where(defined, s, -jnp.inf)
    top = jnp.max(s, axis=1, keepdims=True)
    top = jnp.where(top == -jnp.inf, 0.0, top)
    e = jnp.where(defined, jnp.exp(s - top), 0.0)
    p = e / jnp.maximum(jnp.sum(e, axis=1, keepdims=True), 1e-30)
    o_ref[...] = jnp.sum(p, axis=0, keepdims=True)


@functools.partial(jax.jit,
                   static_argnames=("kernel", "stride", "interpret"))
def bsa_select_pallas(q, pooled, n, kernel: int, stride: int,
                      interpret: bool = False):
    """q [B, H, hd]; pooled [B, J, Hk, hd] (each row's sequence's pooled
    keys, J a multiple of 128 on the chip); n [B] int32 contexts. Returns P
    [B, Hk, J] float32: `block_select.pooled_probs`."""
    B, H, hd = q.shape
    J, Hk = pooled.shape[1], pooled.shape[2]
    G = H // Hk
    out = pl.pallas_call(
        functools.partial(_kernel, kernel=kernel, stride=stride,
                          scale=hd ** -0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, Hk),
            in_specs=[
                pl.BlockSpec((None, None, G, hd), lambda b, g, n: (b, g, 0, 0)),
                pl.BlockSpec((None, None, J, hd), lambda b, g, n: (b, g, 0, 0)),
            ],
            out_specs=pl.BlockSpec((None, None, 1, J),
                                   lambda b, g, n: (b, g, 0, 0))),
        out_shape=jax.ShapeDtypeStruct((B, Hk, 1, J), jnp.float32),
        interpret=interpret,
    )(n.astype(jnp.int32), q.reshape(B, Hk, G, hd),
      jnp.swapaxes(pooled, 1, 2))
    return out[:, :, 0, :]
