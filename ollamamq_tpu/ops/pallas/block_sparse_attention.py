"""A block-sparse layer's launch of the paged decode kernel: one query a
(row, kv head) over the pages of the blocks that row's kv group KEPT
(ops/block_select.py:walk_table), under a name of its own on the device
trace.

The kernel is ops/pallas/paged_attention.py's, whole: `_decode_kernel`, its
ring, its inner products. It walks `page_table[row, : ceil(len / page_size)]`
and masks positions past `len`; a kept list in ascending block order, the
query's own block last, is such a table with `len` = `block_select.walk_len`
— the layers are NoPE, so no position enters but the causal cut, and that
falls inside the last block. A kv group chooses its blocks together, two
groups differently: a launch has a row a (sequence, kv head), each given ALL
the query heads (the kernel contracts a block against every kv head's lanes:
the other groups' results are read against pages they did not choose and are
dropped by the caller). So a walk moves both kv heads' lanes of a kept page
for one head's use: twice the bytes the mathematics asks — a lane-tile DMA
of one head is the kernel edit that is not made here (PERF.md section 7).

Only the launch is this module's, as ops/pallas/cross_attention.py's, and for
the same reason: the Mosaic custom call carries the launch's name
(`bsa_decode_attention_pallas`), and an edit to the kernel's file would move
the source lines every other configuration's step programs carry.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ollamamq_tpu.ops.pallas.kv_contract import (make_inner, ring_grid_spec,
                                                 whole_blocks)
from ollamamq_tpu.ops.pallas.paged_attention import RING, _decode_kernel

NAME = "bsa_decode_attention_pallas"


@functools.partial(jax.jit, static_argnames=("page_size", "interpret"))
def bsa_decode_attention_pallas(
    q: jnp.ndarray,  # [B * Hk, H, hd]: row (b, g) holds sequence b's heads
    k_cache: jnp.ndarray,  # [L, S, Hk*hd]
    v_cache: jnp.ndarray,
    layer,  # int32 scalar: the pool layer to attend over
    page_table: jnp.ndarray,  # [B * Hk, width]: block_select.walk_table's
    seq_lens: jnp.ndarray,  # [B * Hk]; 0: the row reads nothing
    page_size: int,
    interpret: bool = False,
) -> jnp.ndarray:
    B, H, hd = q.shape
    Hk = k_cache.shape[-1] // hd
    inner = make_inner(None, rows=1, group=H // Hk, num_kv_heads=Hk,
                       head_dim=hd, page_size=page_size, window=0)
    nbuf, grid_spec = ring_grid_spec(inner, RING, (B,), 3,
                                     [k_cache, v_cache])
    q_packed = inner.pack_q(q)
    out = pl.pallas_call(
        functools.partial(_decode_kernel, inner=inner, nbuf=nbuf,
                          max_pages=page_table.shape[1]),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q_packed.shape, q.dtype),
        interpret=interpret, name=NAME,
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      whole_blocks(page_table, inner), seq_lens.astype(jnp.int32),
      q_packed, k_cache, v_cache)
    return inner.unpack_o(out)
