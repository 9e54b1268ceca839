"""The chunked rule's (row, window) pairs (ops/gated_delta.ragged, step 3) as
ONE Pallas TPU kernel a layer that keeps a row's state in VMEM across the
row's windows and updates the carried array IN PLACE.

Written in jnp the pairs are a `fori_loop` whose trip slices a row's [dk, H *
dv] float32 state (2.1 MB at Qwen3-Next's widths) out of the carried array,
runs `_apply`'s four contractions and writes the row back: ~27 XLA fusions a
trip inside a `while`, and the state crosses HBM twice a PAIR. Here a program
is one (block of heads, pair), the pairs innermost and in the loop's order
(rows in stream order, each row's windows in order): the state block is read
at a row's first pair (or zeroed where the pair opens the row), stays in the
output block's VMEM buffer while the row's pairs follow each other — an
aliased input block whose index does not change is not fetched again, so the
carry is the OUTPUT block, never the input re-read — and is written once
after the row's last. The grid's pair bound is the step's own count (a
dynamic bound: a step without spans runs one program a head block, which
copies the trash row onto itself).

`_apply`'s four contractions are two on the MXU, each head's float32
operands at `precision=HIGHEST` (Mosaic: `contract_precision<fp32>`) and
float32 accumulation, in `_apply`'s order:

    [w; qg] S          -> v_new = u - w S,  qg S           (`plain`: qg S)
    [attn; kd^T] v_new -> o = qg S + attn v_new,  S' = e^g_last S + kd^T v_new

The rows of two left operands that share a right operand ride one latch of
it. kd (k scaled by each token's decay to the row's last token of the window)
depends on which row the pair is, so the kernel scales k^T's columns itself.
With a decay a KEY CHANNEL (`gc` [n, H, C, dk]: Kimi Delta Attention's, the
trace-time reading of ops/gated_delta.py) the kernel gets gc transposed, a
[dk, C] block a head — k^T's own layout: the row's last G is a [dk, 1]
column, kd^T = k^T * exp(G_last - G^T) elementwise, and the state's decay
that column broadcast along the head's lanes (rows of S).

Layout: lanes are the heads' values side by side (h * dv + j) for the state,
for u and for the output [windows, C, H * dv] — which is the stream's own
[T, H, dv], no transpose behind the kernel. A block's lanes are cut into
groups of `hg` heads that are whole 128-lane tiles (dv = 192: pairs of heads,
384 lanes); a head's operands contract against its GROUP's lanes and a
select on the lane index keeps the head's own (more MXU work where dv is no
lane-tile multiple, no relayout). Two pairs may name one window (a row's
last, the next row's first): windows do not decrease along the pairs, the
output block stays resident and each pair writes its own row's tokens only;
tokens of no span hold whatever the buffer held, and the caller masks them.

The Mosaic custom call carries this function's name on the device trace
(`chunk_rule_pallas`: outside the benchmark readers' `gated_delta_*pallas`
and `ssd_step_*pallas`, which count the one-token kernels).

Measured (builder, PR 62: `chiprun -- python scripts/chunk_rule_bench.py` on
the committed tree, and a first call with `--set chunk_rule.VMEM_BYTES=6291456
--set chunk_rule.VMEM_BYTES=25165824`; a v5e; a layer's `ragged` over a
512-token stream, µs a pair = the launch less the same stream's one-token
rows alone, a pair; kernel against loop):

    shape (H, dk, dv)            heads a   ms a launch, 5 rows +   µs a pair
                                 block     a 507-token span
    Qwen3-Next (32, 128, 128)    16        0.358  against 0.543    13.7 / 36.8
    Olmo-Hybrid (30, 96, 192)    10        0.557  against 0.718    27.4 / 49.7
    Falcon-H1 (32, 256, 128)      8        0.457  against 0.766    14.9 / 55.9
    MiniCPM-SALA (32, 128, 128)  16        0.220  against 0.422     8.8 / 34.4

Half or twice the heads a block moves a pair by under 1.5 µs (Qwen3-Next: 14.5
at 8 heads, 12.5 at 32 — where a step WITHOUT pairs pays 10 µs more for the
one program's larger first fetch); a step with no pair costs 0–22 µs a layer
more than the loop's zero trips (the launch and the operands' concatenation).
Outputs and states agree with the jnp path to 2e-7 … 2e-6 on values of 0.3–5.
On the cell (`qwen3-next-80b-a3b-ep4-d12.longctx`, traced): three op names of
0.054 s each in a 5 s capture, 118 µs a launch of 8.04 pairs, 1.06 ms of a
32.6 ms step where the loop's ops summed to ~2.4 (PERF.md section 5).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ollamamq_tpu.ops.gated_delta import CHUNK
from ollamamq_tpu.ops.pallas.gated_delta_step import head_blocks

VMEM_BYTES = 12 << 20  # a program's blocks, double-buffered
_HI = jax.lax.Precision.HIGHEST
_F32 = jnp.float32
FIRST, OPENS = 1, 2  # a pair's flag: its row's first (the state is read),
#                      and the row opens at zero there (it is not)


def _block_bytes(hb: int, dk: int, dv: int, plain: bool,
                 vector: bool = False) -> int:
    """What a program of `hb` heads holds in VMEM: the state in and out, u
    and the output, the two stacked left operands (`vector`: and a [dk, C]
    block of G a head, where the scalar decay's [1, C] is not counted) —
    every block twice (the pipeline's two buffers), minor dimensions padded
    to 128 lanes."""
    def lanes(n):
        return -(-n // 128) * 128

    state = 2 * dk * lanes(hb * dv)
    rows = 2 * CHUNK * lanes(hb * dv)
    on_s = hb * (CHUNK if plain else 2 * CHUNK) * lanes(dk)
    on_v = hb * (CHUNK + dk) * lanes(CHUNK)
    gc = hb * dk * lanes(CHUNK) if vector else 0
    return 2 * 4 * (state + rows + on_s + on_v + gc)


def blocks(heads: int, dk: int, dv: int, plain: bool, vector: bool = False):
    """(heads a lane group, heads a block) the kernel runs `(H, dk, dv)` at,
    or None where it does not (the XLA pair loop does): the key dimension
    whole sublane tiles, lane groups as `gated_delta_step.head_blocks` cuts
    them, and the most heads a block that divide H and fit VMEM_BYTES."""
    hg, _ = head_blocks(heads, dk, dv)
    if dk % 8:
        return None
    fit = [n for n in range(hg, heads + 1, hg) if heads % n == 0
           and _block_bytes(n, dk, dv, plain, vector) <= VMEM_BYTES]
    return (hg, max(fit)) if fit else None


def pair_bound(windows: int, rows: int, tokens: int) -> int:
    """The most pairs a stream of `tokens` tokens cut into `windows` windows
    holds over `rows` rows: a span is in one window more than the window
    boundaries inside it, a boundary lies inside one span at most, and a
    span has two tokens or more."""
    return windows + max(min(rows, tokens // 2) - 1, 0)


def _dot(a, b):
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())), precision=_HI,
                               preferred_element_type=_F32)


def _kernel(layer_ref, slot_ref, win_ref, row_ref, flag_ref, n_ref, s_in_ref,
            u_ref, on_s_ref, on_v_ref, gc_ref, row_l_ref, row_s_ref, o_ref,
            s_ref, *, hg, dv, plain, vector=False):
    del layer_ref, slot_ref, win_ref  # the index maps read them
    p, n = pl.program_id(1), n_ref[0]

    @pl.when((p == 0) & (n == 0))  # no pair: the trash row, onto itself
    def _():
        s_ref[...] = s_in_ref[...]

    @pl.when(p < n)
    def _():
        @pl.when(flag_ref[p] == FIRST)
        def _():
            s_ref[...] = s_in_ref[...]

        @pl.when(flag_ref[p] == FIRST + OPENS)
        def _():
            s_ref[...] = jnp.zeros_like(s_ref)

        c = row_l_ref.shape[-1]
        gl = hg * dv
        in_row = row_l_ref[...] == row_ref[p]  # [1, C]: the row's tokens
        in_col = row_s_ref[...] == row_ref[p]  # [C, 1]
        head_of = jax.lax.broadcasted_iota(jnp.int32, (1, gl), 1) // dv
        below = jax.lax.broadcasted_iota(jnp.int32, on_v_ref.shape[1:], 0) >= c

        def own_lanes(xs):  # a group's heads' results: each head's lanes
            x = xs[0]
            for j in range(1, hg):
                x = jnp.where(head_of >= j, xs[j], x)
            return x

        for i in range(s_ref.shape[1] // gl):
            at = slice(i * gl, (i + 1) * gl)
            group = range(i * hg, (i + 1) * hg)
            s = s_ref[:, at]
            on_s = own_lanes([_dot(on_s_ref[h], s) for h in group])
            v_new, qs = u_ref[:, at], on_s
            if not plain:  # the delta rule's correction against the state
                v_new, qs = v_new - on_s[:c], on_s[c:]
            on_v, decay = [], []
            for h in group:
                # [1, C] (`vector`: [dk, C], a key channel a row); it falls
                # along a row of the stream: min = last
                g = gc_ref[h]
                g_last = jnp.min(jnp.where(in_row, g, jnp.inf), axis=-1,
                                 keepdims=True)
                d = jnp.exp(jnp.where(in_row, g_last - g, -jnp.inf))
                left = jnp.concatenate(
                    [on_v_ref[h, :c], on_v_ref[h, c:] * d], axis=0) \
                    if vector else on_v_ref[h] * jnp.where(below, d, 1.0)
                on_v.append(_dot(left, v_new))
                decay.append(jnp.broadcast_to(jnp.exp(g_last),
                                              (g.shape[0], gl)))
            on_v = own_lanes(on_v)
            s_ref[:, at] = s * own_lanes(decay) + on_v[c:]
            o_ref[:, at] = jnp.where(in_col, qs + on_v[:c], o_ref[:, at])


@functools.partial(jax.jit, static_argnames=("interpret",))
def chunk_rule_pallas(state, layer, slots, windows, rows, flags, n_pairs,
                      row_of, c, interpret: bool = False):
    """state [L, slots + 1, dk, H * dv] float32 (updated in place: donate
    it); layer an int32 scalar; per pair, [P] int32 in the loop's order and
    past `n_pairs` (int32 scalar) the last pair's again — no pair: the trash
    row, window 0, row -2 —: `slots` its row's state row, `windows` its
    window, `rows` its row (as `row_of` names it), `flags` FIRST at a row's
    first pair, + OPENS where the row opens at zero; row_of [n, C] int32
    each window token's row (-1: of no span); c: `gated_delta._prepare`'s
    results, heads leading (no "w": the plain form; "gc" [n, H, C, dk]: a
    decay a key channel) — or `chunk_solve_pallas`'s, which are laid out as
    the kernel reads them. Returns (o [n, C, H * dv] float32 — right at the
    spans' tokens only —, state')."""
    laid = "on_s" in c  # as the kernel reads them: chunk_solve_pallas's
    if laid:
        u, on_s, on_v = c["u"], c["on_s"], c["on_v"]
        (n, chunk), (h, dk) = u.shape[:2], on_s.shape[1::2]
        dv, plain, vector = u.shape[2] // h, False, c["gc"].shape[2] > 1
    else:
        n, h, chunk, dv = c["u"].shape
        dk = c["k"].shape[-1]
        plain = "w" not in c
        vector = c["gc"].ndim == 4
        u = jnp.moveaxis(c["u"], 1, 2).reshape(n, chunk, h * dv)
        on_s = c["qg"] if plain else jnp.concatenate([c["w"], c["qg"]],
                                                     axis=2)
        on_v = jnp.concatenate([c["attn"], jnp.swapaxes(c["k"], -1, -2)],
                               axis=2)
    hg, hb = blocks(h, dk, dv, plain, vector)
    nblk, lanes = h // hb, hb * dv

    def lane_block(j, p, layer_ref, slot_ref, win_ref, *_):
        return (win_ref[p], 0, j)

    def head_block(j, p, layer_ref, slot_ref, win_ref, *_):
        return (win_ref[p], j, 0, 0)

    def state_block(j, p, layer_ref, slot_ref, *_):
        return (layer_ref[0], slot_ref[p], 0, j)

    def window(j, p, layer_ref, slot_ref, win_ref, *_):
        return (win_ref[p], 0, 0)

    lane_spec = pl.BlockSpec((None, chunk, lanes), lane_block)
    state_spec = pl.BlockSpec((None, None, dk, lanes), state_block)
    n_pairs = jnp.asarray(n_pairs, jnp.int32)
    o, state = pl.pallas_call(
        functools.partial(_kernel, hg=hg, dv=dv, plain=plain, **(
            {"vector": True} if vector else {})),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6, grid=(nblk, jnp.maximum(n_pairs, 1)),
            in_specs=[
                state_spec, lane_spec,
                pl.BlockSpec((None, hb) + on_s.shape[2:], head_block),
                pl.BlockSpec((None, hb) + on_v.shape[2:], head_block),
                pl.BlockSpec((None, hb, dk if vector else 1, chunk),
                             head_block),
                pl.BlockSpec((None, 1, chunk), window),
                pl.BlockSpec((None, chunk, 1), window)],
            out_specs=[lane_spec, state_spec]),
        out_shape=[jax.ShapeDtypeStruct((n, chunk, h * dv), _F32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={6: 1},  # the state, after the 6 scalar lists
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=2 * VMEM_BYTES),
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      *(x.astype(jnp.int32) for x in (slots, windows, rows, flags)),
      n_pairs.reshape(1), state, u, on_s, on_v,
      c["gc"] if laid else jnp.swapaxes(c["gc"], -1, -2) if vector
      else c["gc"][:, :, None, :], row_of[:, None, :], row_of[:, :, None])
    return o, state
