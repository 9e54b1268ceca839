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
    lies inside the span (o >= j: spans are contiguous) and from the row's
    carried state when it lies before it. A row's state is z at the last
    K-1 positions of its sequence, [K-1, D], oldest first; a row whose span
    is the request's first starts from zeros — the reset happens here, in
    the program, so a slot is never read with an earlier request's state.
    Returns the rows' state after the span too: its last K-1 positions,
    taken from the stream and, for a span shorter than that, from the old
    state. A row without tokens keeps its state.
  - `taps_decode`: one token a slot; the state is the predecessors, and
    rolls by one where the slot is active (an idle slot, or one reserved
    mid-chunked-prefill, keeps its state: the `recent` rings' rule).

The state array is [conv layers, slots + 1, K-1, D]: per SLOT, fixed size,
no pages; row `slots` is the trash row padding rows write (as `recent`'s).
"""

from __future__ import annotations

import jax.numpy as jnp


def short_conv(w: jnp.ndarray, taps, z: jnp.ndarray) -> jnp.ndarray:
    """sum_j w[:, j] * (taps + [z])[j]: `w` [D, K], `taps` the K-1
    predecessors of `z`, oldest first, each shaped as `z` [..., D].
    Accumulated in float32, returned in z's dtype."""
    wf = w.astype(jnp.float32)
    acc = wf[:, -1] * z.astype(jnp.float32)
    for j, tap in enumerate(taps):
        acc = acc + wf[:, j] * tap.astype(jnp.float32)
    return acc.astype(z.dtype)


def alloc_state(num_conv_layers: int, max_slots: int, window: int,
                hidden: int, dtype=jnp.bfloat16):
    """The per-slot state of a model's conv layers (zeros), or None for a
    model that has none — a pytree without leaves, so the step programs of
    such a model take, donate and return nothing for it."""
    if not num_conv_layers:
        return None
    return jnp.zeros((num_conv_layers, max_slots + 1, window - 1, hidden),
                     dtype)


def taps_full(z: jnp.ndarray, window: int) -> list:
    """z [B, T, D], whole sequences from position 0: predecessor j of
    every token is z shifted right by j, zeros shifted in."""
    T = z.shape[1]
    return [jnp.pad(z, ((0, 0), (j, 0), (0, 0)))[:, :T]
            for j in range(window - 1, 0, -1)]


def taps_ragged(z, state, tok_seq, q_start, q_len, is_first):
    """z [T, D] the stream; state [B, K-1, D] the rows' carried state
    (already gathered by slot); tok_seq [T] each token's row; q_start,
    q_len, is_first [B]. Returns (taps: K-1 arrays [T, D], oldest first;
    the rows' state after the step [B, K-1, D]). Padding tokens get
    whatever: nothing reads them."""
    T, n_prev = z.shape[0], state.shape[1]
    state = jnp.where(is_first[:, None, None] > 0, 0, state)
    t = jnp.arange(T, dtype=jnp.int32)
    off = t - q_start[tok_seq]  # a token's offset in its row's span
    taps = []
    for j in range(n_prev, 0, -1):  # predecessor j: position p - j
        in_span = z[jnp.clip(t - j, 0, T - 1)]
        # before the span: row (K-1) - (j - off) of its row's state
        carried = state[tok_seq, jnp.clip(n_prev - j + off, 0, n_prev - 1)]
        taps.append(jnp.where((off >= j)[:, None], in_span, carried))
    # New state row i: the span's offset q_len - (K-1) + i, or the old
    # state where that lies before the span.
    i = jnp.arange(n_prev, dtype=jnp.int32)[None, :]
    at = q_len[:, None] - n_prev + i  # [B, K-1]
    from_span = z[jnp.clip(q_start[:, None] + at, 0, T - 1)]
    from_old = jnp.take_along_axis(
        state, jnp.clip(n_prev + at, 0, n_prev - 1)[:, :, None], axis=1)
    new_state = jnp.where((at >= 0)[:, :, None], from_span, from_old)
    return taps, new_state


def taps_decode(z, state, active=None):
    """z [S, D] one token a slot; state [S, K-1, D]. Returns (taps, the
    state after the step: rolled by one where `active`, else kept)."""
    taps = [state[:, j] for j in range(state.shape[1])]
    rolled = jnp.concatenate([state[:, 1:], z[:, None, :]], axis=1)
    if active is not None:
        rolled = jnp.where((active > 0)[:, None, None], rolled, state)
    return taps, rolled
