"""Mamba-2's state-space recurrence (SSD; Falcon-H1's mixer): a scalar decay
a head, in the forms a served step needs.

Per head j, with a float32 state S [d_head, d_state] a sequence (S = 0 before
its first token), the step dt_t = softplus(dt_t + dt_bias) and A = -exp(A_log)
a head, and B_t, C_t [d_state] shared by the heads of a GROUP (head j reads
group j // (heads / groups)):

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T;   y_t = S_t C_t   (+ D x_t,
    which the mixer adds: models/llama.py:_ssm_op)

That is the gated delta rule's recurrence (ops/gated_delta.py) with k = B,
q = C, v = dt x, a log decay g = dt A and a write strength of 1 — without the
rule's correction of v against the state and without the L2 norm of q and k.
So there is ONE set of schedules: everything here is `gated_delta`'s `plain`
form, under the mixer's names — `chunked` (whole sequences from an empty
state), `ragged` (a ragged step's stream: one-token rows through `step`,
longer spans through the windows of CHUNK tokens and the (row, window) loop)
and `decode` (one token a slot, parked slots kept). Float32 at the highest
matmul precision throughout: the state is an accumulator over the whole
sequence.

State layout: [layers, slots + 1, d_state, heads * d_head] float32 — B/C's
dimension on sublanes, every head's channels side by side on lanes (4096 at
the published widths: whole tiles, no padding; 4 MiB a slot a layer). Row
`slots` is the trash row padding rows write. On the chip the one-token rows
run in a Pallas kernel that updates the rows in place
(ops/pallas/ssd_step.py: `ssd_step_pallas` on the device trace).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ollamamq_tpu.ops import gated_delta

_F32 = jnp.float32


# (layers, slots, heads, state dim, head dim) -> the per-slot state of a
# model's mixers (zeros, float32), or None for a model that has none.
alloc_state = gated_delta.alloc_state


def inputs(x, dt, a_log, dt_bias):
    """(v, g) of the recurrence from the convolved x [..., H, d_head] and the
    raw step dt [..., H]: the step through its softplus (float32, not
    clamped: the published limit is (0, inf)), v = dt x, g = dt A."""
    dt = jax.nn.softplus(dt.astype(_F32) + dt_bias.astype(_F32))
    return x.astype(_F32) * dt[..., None], -jnp.exp(a_log.astype(_F32)) * dt


def step(state, c, b, v, g, reset=None):
    """One token a row: state [..., d_state, H * d_head]; c, b [..., G,
    d_state]; v [..., H, d_head]; g [..., H]. Returns (y, state')."""
    return gated_delta.step(state, c, b, v, g, jnp.ones_like(g), reset,
                            plain=True)


def chunked(c, b, v, g, valid=None, state=None):
    """Whole sequences [B, T, ...] from an empty (or a given) state."""
    return gated_delta.chunked(c, b, v, g, jnp.ones_like(g), valid, state,
                               plain=True)


def ragged(c, b, v, g, state, layer, slot_ids, tok_seq, tok_pos, q_start,
           q_len, is_first, impl: str = "jnp", interpret=False):
    """The flattened stream of a ragged step; arguments as
    `gated_delta.ragged`'s, c for its q and b for its k."""
    return gated_delta.ragged(
        c, b, v, g, jnp.ones_like(g), state, layer, slot_ids, tok_seq,
        tok_pos, q_start, q_len, is_first, impl=impl, interpret=interpret,
        plain=True)


def decode(c, b, v, g, state, layer, active=None, impl: str = "jnp"):
    """One token a slot; row s of `state[layer]` is slot s's."""
    return gated_delta.decode(c, b, v, g, jnp.ones_like(g), state, layer,
                              active, impl=impl, plain=True)
