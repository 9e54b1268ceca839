"""The state-space recurrence's one-token form (ops/ssd.step) as a Pallas TPU
kernel that updates the rows of the carried state IN PLACE.

A decode pass gives every live slot one token in every layer's mixer: the
row's [d_state, H * d_head] float32 state (4 MiB at Falcon-H1-34B's widths)
is scaled by the head's decay, gains one rank-one term and is read by C.
Written in jnp, XLA gathers the rows out of the carried array and scatters
them back; this kernel reads each LIVE row once and writes it once, in the
array it was given (`input_output_aliases`), and rows that are not live are
never touched.

It is ops/pallas/gated_delta_step.py's kernel without the correction (`- sum
s k`) and the write strength — same layout (the heads' channels side by side
on lanes, the state dimension on sublanes; a program is one (row, block of
heads), `head_blocks` of that module: 8 heads a 1 MB block, 4 blocks a row at
(32, 256, 128)), same skipping of rows that are not live (sorted to the
front, the tail's programs name the block before them and move nothing). A
module and a wrapper of its own, not a switch in that one: a Mosaic kernel's
serialised body carries its source lines, so an edit there would change the
step programs of the configurations that run the delta rule, and the Mosaic
custom call carries THIS function's name on the device trace
(`ssd_step_pallas`), where the benchmark's readers tell the two apart.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ollamamq_tpu.ops import gated_delta
from ollamamq_tpu.ops.pallas.gated_delta_step import head_blocks


def _kernel(layer_ref, slot_ref, row_ref, live_ref, s_ref, ct_ref, bt_ref,
            v_ref, a_ref, y_ref, s_out_ref, *, hg, dv):
    del layer_ref, slot_ref, row_ref  # the index maps read them

    @pl.when(pl.program_id(0) < live_ref[0])
    def _():
        dk, lanes = s_ref.shape
        gl = hg * dv
        head_of = jax.lax.broadcasted_iota(jnp.int32, (dk, gl), 1) // dv
        ct, bt = ct_ref[...], bt_ref[...]  # [d_state, heads of the block]

        def along_lanes(cols, first):  # [dk, gl]: each head's column
            x = jnp.broadcast_to(cols[:, first:first + 1], (dk, gl))
            for h in range(1, hg):
                x = jnp.where(head_of >= h, jnp.broadcast_to(
                    cols[:, first + h:first + h + 1], (dk, gl)), x)
            return x

        for i in range(lanes // gl):
            at = slice(i * gl, (i + 1) * gl)
            s = s_ref[:, at] * a_ref[:, at] \
                + along_lanes(bt, i * hg) * v_ref[:, at]
            s_out_ref[:, at] = s
            y_ref[:, at] = jnp.sum(s * along_lanes(ct, i * hg), axis=0,
                                   keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_step_pallas(state, layer, slots, live, reset, c, b, v, g,
                    interpret: bool = False):
    """state [L, slots + 1, d_state, H * d_head] float32 (updated in place:
    donate it); layer an int32 scalar; slots [B] each row's state row; live,
    reset [B] bool; c, b [B, G, d_state] (the kernel takes them a head:
    `a_value_head` repeats a group's for the heads it serves); v = dt x [B,
    H, d_head]; g = dt A [B, H]. Returns (y [B, H, d_head] float32 — zeros
    for rows that are not live —, state')."""
    n, _, dk = c.shape
    h, dv = v.shape[-2:]
    hg, hb = head_blocks(h, dk, dv)
    nblk, lanes = h // hb, hb * dv
    c, b = gated_delta.a_value_head(c.astype(jnp.float32),
                                    b.astype(jnp.float32), h)

    def columns(x):  # [B, H, dk] -> [B, blocks, dk, heads of a block]
        return jnp.swapaxes(x.reshape(n, nblk, hb, dk), -1, -2)

    # Live rows first; the list's tail repeats the last live row (no live
    # row: the state's trash row), which a skipped program names again.
    n_live = jnp.sum(live).astype(jnp.int32)
    order = jnp.argsort(~live, stable=True).astype(jnp.int32)
    rows = order[jnp.minimum(jnp.arange(n), jnp.maximum(n_live - 1, 0))]
    slot_list = jnp.where(n_live > 0, slots[rows].astype(jnp.int32),
                          state.shape[1] - 1)
    alpha = jnp.where(reset[:, None], 0.0, jnp.exp(g))  # opens at zero

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
    y, state = pl.pallas_call(
        functools.partial(_kernel, hg=hg, dv=dv),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(n, nblk),
            in_specs=[state_spec, col_spec, col_spec, lane_spec, lane_spec],
            out_specs=[lane_spec, state_spec]),
        out_shape=[jax.ShapeDtypeStruct((n, 1, h * dv), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={4: 1},  # the state, after the 4 scalar lists
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), slot_list, rows,
      n_live.reshape(1), state, columns(c), columns(b),
      v.astype(jnp.float32).reshape(n, 1, h * dv),
      jnp.repeat(alpha, dv, axis=-1)[:, None, :])
    y = jnp.where(live[:, None, None], y.reshape(n, h, dv), 0.0)
    return y, state
