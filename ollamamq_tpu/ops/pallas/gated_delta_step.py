"""The gated delta rule's one-token form (ops/gated_delta.step) as a Pallas
TPU kernel that updates the rows of the carried state IN PLACE.

A decode pass gives every live slot one token in each linear-attention
layer: the row's [dk, H * dv] float32 state (2.2 MB at Olmo-Hybrid's widths)
is scaled, corrected by one rank-one term and read by q. Written in jnp,
XLA gathers the rows out of the carried array, reads them for S^T k, again
for the update and again for S^T q, and scatters them back; this kernel
reads each LIVE row once and writes it once, in the array it was given
(`input_output_aliases`), and rows that are not live are never touched.

Layout: the state's lanes are the heads' values side by side (h * dv + j),
its sublanes the key dimension. A program is one (row, block of heads):
its block [dk, hb * dv] is cut into groups of `hg` heads whose lanes are a
whole number of 128-lane tiles (dv = 192: pairs of heads, 384 lanes), and
in each group k and q — given per row as [dk, heads] columns — are
broadcast along their head's lanes by a select on the lane index. All of
it is VPU work on tiles: no matmul, no slice off the tiling.

Rows that are not live are skipped without a DMA: the wrapper sorts the
live rows to the front and pads the list with the last live row (the trash
row of the state if none is live), the index maps clamp the head-block
index to the last one there too, so a skipped program names the block the
program before it already holds and Pallas moves nothing; its body does not
run. A decay a KEY CHANNEL (`g` [B, H, dk]: Kimi Delta Attention's, a
trace-time reading as in ops/gated_delta.py) comes in as columns like k and
q, [dk, heads], and scales the state's rows: the same broadcast along a
head's lanes. The Mosaic custom call carries this function's name on the device
trace (`gated_delta_step_pallas`), where the benchmark's readers find it.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ollamamq_tpu.ops import gated_delta

BLOCK_BYTES = 1 << 20  # a state block in VMEM; in and out, double-buffered


def head_blocks(heads: int, key_dim: int, value_dim: int) -> tuple:
    """(heads a lane group, heads a block): a group's lanes are whole
    128-lane tiles where the head count allows (else the row is one group),
    a block is as many groups as fit BLOCK_BYTES and divide the heads."""
    hg = 128 // math.gcd(value_dim, 128)
    if heads % hg:
        return heads, heads
    fit = [n for n in range(hg, heads + 1, hg) if heads % n == 0
           and key_dim * n * value_dim * 4 <= BLOCK_BYTES]
    return hg, max(fit, default=hg)


def _kernel(layer_ref, slot_ref, row_ref, live_ref, s_ref, qt_ref, kt_ref,
            v_ref, a_ref, b_ref, o_ref, s_out_ref, *, hg, dv, vector=False):
    del layer_ref, slot_ref, row_ref  # the index maps read them

    @pl.when(pl.program_id(0) < live_ref[0])
    def _():
        dk, lanes = s_ref.shape
        gl = hg * dv
        head_of = jax.lax.broadcasted_iota(jnp.int32, (dk, gl), 1) // dv
        qt, kt = qt_ref[...], kt_ref[...]  # [dk, heads of the block]

        def along_lanes(cols, first):  # [dk, gl]: each head's column
            x = jnp.broadcast_to(cols[:, first:first + 1], (dk, gl))
            for h in range(1, hg):
                x = jnp.where(head_of >= h, jnp.broadcast_to(
                    cols[:, first + h:first + h + 1], (dk, gl)), x)
            return x

        for i in range(lanes // gl):
            at = slice(i * gl, (i + 1) * gl)
            kx, qx = along_lanes(kt, i * hg), along_lanes(qt, i * hg)
            s = s_ref[:, at] * (along_lanes(a_ref[...], i * hg) if vector
                                else a_ref[:, at])
            r = b_ref[:, at] * (v_ref[:, at]
                                - jnp.sum(s * kx, axis=0, keepdims=True))
            s = s + kx * r
            s_out_ref[:, at] = s
            o_ref[:, at] = jnp.sum(s * qx, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gated_delta_step_pallas(state, layer, slots, live, reset, q, k, v, g,
                            beta, interpret: bool = False):
    """state [L, slots + 1, dk, H * dv] float32 (updated in place: donate
    it); layer an int32 scalar; slots [B] each row's state row; live, reset
    [B] bool; q, k [B, Hk, dk] as the convolution left them (the kernel
    takes them a VALUE head: `a_value_head` repeats a key head's for the
    value heads it serves), v [B, H, dv], g, beta [B, H] (g [B, H, dk]: a
    decay a key channel). Returns (o [B, H, dv] float32 — zeros for rows
    that are not live —, state')."""
    n, _, dk = q.shape
    h, dv = v.shape[-2:]
    hg, hb = head_blocks(h, dk, dv)
    nblk, lanes = h // hb, hb * dv
    q, k = gated_delta.a_value_head(*gated_delta.normalise(q, k), h)

    def columns(x):  # [B, H, dk] -> [B, blocks, dk, heads of a block]
        return jnp.swapaxes(x.reshape(n, nblk, hb, dk), -1, -2)

    def along_lanes(x):  # [B, H] -> [B, 1, H * dv]
        return jnp.repeat(x, dv, axis=-1)[:, None, :]

    # Live rows first; the list's tail repeats the last live row (no live
    # row: the state's trash row), which a skipped program names again.
    n_live = jnp.sum(live).astype(jnp.int32)
    order = jnp.argsort(~live, stable=True).astype(jnp.int32)
    rows = order[jnp.minimum(jnp.arange(n), jnp.maximum(n_live - 1, 0))]
    slot_list = jnp.where(n_live > 0, slots[rows].astype(jnp.int32),
                          state.shape[1] - 1)
    vector = gated_delta.a_channel(g, beta)
    alpha = jnp.where(reset[(slice(None),) + (None,) * (g.ndim - 1)], 0.0,
                      jnp.exp(g))  # opens at zero

    def per_row(i, j, layer_ref, slot_ref, row_ref, live_ref):
        return (row_ref[i], 0, jnp.where(i < live_ref[0], j, nblk - 1))

    def per_row_cols(i, j, layer_ref, slot_ref, row_ref, live_ref):
        return (row_ref[i], jnp.where(i < live_ref[0], j, nblk - 1), 0, 0)

    def state_block(i, j, layer_ref, slot_ref, row_ref, live_ref):
        return (layer_ref[0], slot_ref[i], 0,
                jnp.where(i < live_ref[0], j, nblk - 1))

    lane_spec = pl.BlockSpec((None, 1, lanes), per_row)
    col_spec = pl.BlockSpec((None, None, dk, hb), per_row_cols)
    state_spec = pl.BlockSpec((None, None, dk, lanes), state_block)
    o, state = pl.pallas_call(
        functools.partial(_kernel, hg=hg, dv=dv, **(
            {"vector": True} if vector else {})),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(n, nblk),
            in_specs=[state_spec, col_spec, col_spec, lane_spec,
                      col_spec if vector else lane_spec, lane_spec],
            out_specs=[lane_spec, state_spec]),
        out_shape=[jax.ShapeDtypeStruct((n, 1, h * dv), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={4: 1},  # the state, after the 4 scalar lists
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), slot_list, rows,
      n_live.reshape(1), state, columns(q), columns(k),
      v.astype(jnp.float32).reshape(n, 1, h * dv),
      columns(alpha) if vector else along_lanes(alpha), along_lanes(beta))
    o = jnp.where(live[:, None, None], o.reshape(n, h, dv), 0.0)
    return o, state
