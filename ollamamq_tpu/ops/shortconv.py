"""Gated short convolution (LFM2's `conv` layers): the depthwise causal
convolution and the three schedules that find each token's predecessors.

The operator (models/llama.py:_conv_op) forms z = B * u per token; its
output at position p is sum_j w[:, j] * z_{p-(K-1)+j} over a window of
K = `conv_L_cache` positions, z = 0 before the sequence's start. Whatever
the forward, the convolution is `short_conv` — ONE definition — over the
token's own z and its K-1 predecessors ("taps", oldest first); a schedule
only says where the predecessors come from:

  - `taps_full`: whole sequences [B, T, D] — shifted copies of z. No state
    (forward_prefill, forward_embed).
  - `taps_ragged`: the flattened stream of a ragged step. A token at
    offset o of its row's span takes predecessor j from the stream when it
    lies inside the span (o >= j: spans are contiguous) and from its slot's
    carried state when it lies before it. A slot's state is z at the last
    K-1 positions of its sequence, oldest first; a row whose span is the
    request's first starts from zeros — the reset happens here, in the
    program, so a slot is never read with an earlier request's state.
    Leaves the state after the span too: its last K-1 positions, taken
    from the stream and, for a span shorter than that, from the old state.
    A row without tokens keeps its state.
  - `taps_decode`: one token a slot; the state is the predecessors, and
    rolls by one where the slot is active (an idle slot, or one reserved
    mid-chunked-prefill, keeps its state: the `recent` rings' rule).

The state array is [conv layers, K-1, slots, D], a tap a PLANE: per SLOT,
fixed size, no pages. Both step schedules take the whole array and a
layer's index, read that layer's planes once and write each back WHOLE, in
place (`gated_delta.ragged` / `.decode` take the rule's state the same
way); a ragged step works by slot, not by row (`ragged_plan`, once a step),
so it gathers no rows' state and scatters none back, and a padding row —
which names slot `slots`, the other per-slot arrays' trash row — serves no
slot: the planes have no trash row.

Why this shape (PERF.md section 6, PR 53): the chip tiles an array's two
minor dimensions. Stored [.., slots + 1, K-1, D] a slot's [3, D] sliver
filled 3 rows of a bf16 tile of 16, every read, select and write of a
layer's window touched 5 x its bytes, and each step program re-laid the
whole array on its way in and out. (slots, D) planes are dense — as long as
every update covers a plane: a write of the first B of B + 1 rows, a
`concatenate` of planes or a slice a plane of the loop's carry each made
the compiler choose another order or another memory for the carry, and
copy it a layer.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


def short_conv(w: jnp.ndarray, taps, z: jnp.ndarray,
               bias=None) -> jnp.ndarray:
    """sum_j w[:, j] * (taps + [z])[j] (+ `bias` [D], where the layer has
    one: a state-space mixer's): `w` [D, K], `taps` the K-1 predecessors of
    `z`, oldest first, each shaped as `z` [..., D]. Accumulated in float32,
    returned in z's dtype."""
    wf = w.astype(jnp.float32)
    acc = wf[:, -1] * z.astype(jnp.float32)
    for j, tap in enumerate(taps):
        acc = acc + wf[:, j] * tap.astype(jnp.float32)
    if bias is not None:
        acc = acc + bias.astype(jnp.float32)
    return acc.astype(z.dtype)


def alloc_state(num_conv_layers: int, max_slots: int, window: int,
                hidden: int, dtype=jnp.bfloat16):
    """The per-slot state of a model's conv layers (zeros), or None for a
    model that has none — a pytree without leaves, so the step programs of
    such a model take, donate and return nothing for it."""
    if not num_conv_layers:
        return None
    return jnp.zeros((num_conv_layers, window - 1, max_slots, hidden), dtype)


def taps_full(z: jnp.ndarray, window: int) -> list:
    """z [B, T, D], whole sequences from position 0: predecessor j of
    every token is z shifted right by j, zeros shifted in."""
    T = z.shape[1]
    return [jnp.pad(z, ((0, 0), (j, 0), (0, 0)))[:, :T]
            for j in range(window - 1, 0, -1)]


class RaggedPlan(NamedTuple):
    """Who reads and writes which row of the planes in one ragged step, the
    same for every layer (`ragged_plan`, once a step outside the layers'
    loop): a token's slot, its offset in its row's span and whether that
    span opens its request; and BY SLOT, whether a row of the step serves
    it and that row's span."""
    tok_slot: jnp.ndarray  # [T]
    tok_off: jnp.ndarray  # [T]
    tok_first: jnp.ndarray  # [T] bool
    served: jnp.ndarray  # [S] bool
    start: jnp.ndarray  # [S] the row's q_start
    length: jnp.ndarray  # [S] the row's q_len
    first: jnp.ndarray  # [S] bool


def ragged_plan(num_slots: int, slot_ids, tok_seq, q_start, q_len,
                is_first) -> RaggedPlan:
    """slot_ids, q_start, q_len, is_first [B] a row; tok_seq [T] a token's
    row; `num_slots` the planes' rows. A padding row names a slot past the
    last (`num_slots`, the other per-slot arrays' trash row): it serves no
    slot, and its tokens read the last slot's — nothing reads them."""
    row_of = jnp.zeros((num_slots,), jnp.int32).at[slot_ids].set(
        jnp.arange(slot_ids.shape[0], dtype=jnp.int32), mode="drop")
    served = jnp.zeros((num_slots,), bool).at[slot_ids].set(
        True, mode="drop")
    t = jnp.arange(tok_seq.shape[0], dtype=jnp.int32)
    return RaggedPlan(jnp.minimum(slot_ids, num_slots - 1)[tok_seq],
                      t - q_start[tok_seq], is_first[tok_seq] > 0, served,
                      q_start[row_of], q_len[row_of], is_first[row_of] > 0)


def taps_ragged(z, conv, layer, plan: RaggedPlan):
    """z [T, D] the stream; conv the whole state [Lc, K-1, S, D]; `layer`
    this layer's index in it. Returns (taps: K-1 arrays [T, D], oldest
    first; the state with this layer's planes after the step, written in
    place: whole planes, a slot no row serves as it was). Padding tokens
    get whatever: nothing reads them."""
    T, n_prev = z.shape[0], conv.shape[1]
    state = _read_planes(conv, layer)
    planes = [state[i] for i in range(n_prev)]
    taps = []
    for j in range(n_prev, 0, -1):  # predecessor j: position p - j
        in_span = jnp.pad(z, ((j, 0), (0, 0)))[:T]
        # before the span: plane (K-1) - (j - off) of its slot, zeros where
        # the span opens its request
        carried = state[jnp.clip(n_prev - j + plan.tok_off, 0, n_prev - 1),
                        plan.tok_slot]
        carried = jnp.where(plan.tok_first[:, None], 0, carried)
        taps.append(jnp.where((plan.tok_off >= j)[:, None], in_span,
                              carried))
    # New plane i: the span's offset q_len - (K-1) + i, or the old state's
    # plane (K-1) + that where it lies before the span.
    for i in range(n_prev):
        at = plan.length - n_prev + i  # [S]
        from_span = z[jnp.clip(plan.start + at, 0, T - 1)]
        from_old = planes[n_prev - 1]
        for p in range(i, n_prev - 1):
            from_old = jnp.where((n_prev + at == p)[:, None], planes[p],
                                 from_old)
        from_old = jnp.where(plan.first[:, None], 0, from_old)
        new = jnp.where((at >= 0)[:, None], from_span, from_old)
        conv = _write_plane(conv, layer, i, jnp.where(
            plan.served[:, None], new, planes[i]))
    return taps, conv


def taps_decode(z, conv, layer, active=None):
    """z [B, D] one token a slot (row b is slot b); conv the whole state
    [Lc, K-1, S, D], S >= B; `layer` this layer's index in it. Returns
    (taps [B, D] each, the state with this layer's planes after the step,
    written in place: [plane 1, ..., plane K-2, z] where `active`, else
    kept — rows from B on as they were)."""
    B, (n_prev, S) = z.shape[0], conv.shape[1:3]
    state = _read_planes(conv, layer)
    planes = [state[i] for i in range(n_prev)]
    live = jnp.ones((B,), bool) if active is None else active > 0
    live = jnp.pad(live, (0, S - B))[:, None]
    after = planes[1:] + [jnp.pad(z, ((0, S - B), (0, 0)))]
    for i, new in enumerate(after):
        conv = _write_plane(conv, layer, i, jnp.where(live, new, planes[i]))
    return [plane[:B] for plane in planes], conv


def _read_planes(conv, layer):
    """One layer's planes [K-1, S, D], read as ONE slice of the state (a
    slice a plane made the compiler copy the whole state into its fast
    memory and out again, each layer)."""
    return jax.lax.dynamic_index_in_dim(conv, layer, 0, keepdims=False)


def _write_plane(conv, layer, i: int, plane):
    """One WHOLE plane [S, D] of one layer, in place: an update that covers
    the two tiled dimensions leaves the array in the order it is stored in
    (an update of some rows, or a `concatenate` of planes, made the
    compiler carry it slot-major through the loop and re-lay it twice a
    program)."""
    return jax.lax.dynamic_update_slice(conv, plane[None, None],
                                        (layer, i, 0, 0))
