"""The chunked rule's window solve (ops/gated_delta.ragged, step 2: `_prepare`
/ `_prepare_vector`) as ONE Pallas TPU kernel a layer that holds a window's
decays, `k k^T` / `q k^T`, (I + A)^-1 and `u` / `w` in VMEM and solves only
the windows a span touches.

Written in jnp the solve is a chain of small float32 fusions over EVERY window
of the stream, each intermediate through HBM (`kk`, the decays, the triangular
solve's halves; at the vector reading the in-block decays formed twice, once a
sum). Here a program is one (block of heads, touched window) — the windows a
scalar-prefetched list, the grid's bound the step's own count (a step with no
span runs one program a head block, on window 0) — and every step of it is ONE
batched array over the block's heads (a head at a time, in a loop, each head's
15 substitution steps and 4 dependent contractions ran at their latency: 444
µs a layer where the batched form takes 232), in `_prepare`'s own arithmetic
(float32, contractions at `precision=HIGHEST`, no exponent above 0):

    gc      the masked running sum of g: [heads, C] x same^T, one contraction
            (a decay a KEY CHANNEL: g^T x same^T [dk, C] a head, and its
            TRANSPOSE [C, dk] — never a second contraction: two sums of one g
            differ by an ulp of G, 1e-4 at G = 1e3, which doubled the rule's
            error against the serial recurrence)
    kk, qk  scalar: [k; q] k^T, one contraction, under exp(gc_i - gc_j);
            vector: inside a 16-token block G_i - G_j formed directly and
            ONCE for both sums (a loop over the block's 16 columns, every
            head's four blocks at once; the second half's columns over the
            second half's rows), a block against the earlier ones one
            contraction of the two sides scaled to the row's G before the
            block's first token
    T       `_tri_inv`'s algorithm: forward substitution inside the diagonal
            blocks (15 rank-one steps on every block of every head at once),
            then the two merges, each X - (X L) X over the rows of the
            pairs' second blocks, L the level's off-diagonal blocks of A
    w, qg, attn, k^T, gc   written where `chunk_rule_pallas` reads them:
            [w; qg] [2C, dk] and [attn; k^T] [C + dk, C] a head, gc [1, C] a
            head (vector: its [dk, C] block)

and u = (T beta) v a lane group of heads into the stream's own [C, H * dv]
lanes (`chunk_rule`'s groups: a head contracts against its group's lanes and a
select keeps its own). k arrives as k^T alone (k [C, dk] is its transpose,
taken inside); `same` / `strict` are built from the window's `row_of` inside;
windows no span touches are never written (the pair kernel never reads them).

The Mosaic custom call carries this function's name on the device trace
(`chunk_solve_pallas`: outside the benchmark readers' `chunk_rule_*pallas`,
`gated_delta_*pallas` and `ssd_step_*pallas`).

Measured (builder, PR 66: `chiprun -- python scripts/chunk_rule_bench.py
[--solve] --shapes 0 1 4` on the committed tree; a v5e; a layer's 512-token
stream over 64 rows, nothing the timed loop's invariant. `alone`: µs a layer of
the solve alone, the kernel against `_prepare` + the pair wrapper's
concatenations, each with ~50 µs of the sums that carry the chain; `ragged`: ms
a launch of `gated_delta.ragged` with both kernels against the XLA solve in
front of the pair kernel):

    shape (H, dk, dv), heads a block stream             alone     ragged
    Qwen3-Next (32, 128, 128), 16    decode rows only   106 / 277 0.177 / 0.432
                                     5 rows + 507 span  232 / 323 0.413 / 0.540
                                     two spans a window 229 / 321 0.743 / 0.876
    Olmo-Hybrid (30, 96, 192), 10    decode rows only   134 / 275 0.266 / 0.543
                                     5 rows + 507 span  309 / 276 0.657 / 0.755
                                     two spans a window 311 / 274 1.019 / 1.117
    Kimi-Linear (32, 128, 128)       decode rows only   199 / 615 0.261 / 1.286
    vector, 16                       5 rows + 507 span  561 / 714 0.707 / 1.406
                                     two spans a window 564 / 710 1.040 / 1.740

A stream with no span solves ONE window (the XLA form all eight). Of Kimi's 561
µs with eight windows touched: the in-block sums ~160, the substitution ~50,
the merges ~40, the off-block contractions ~47, u and w ~40, and ~260 that are
the operands' traffic (71 MB a layer in and out in the pair kernel's layouts:
~87 µs at the HBM peak), gc, the transposes and `_operands` in XLA (by leaving
each out; builder, PR 66). Eight or sixteen heads a block read alike at the
scalar reading (233 / 232), sixteen 10 % better at the vector one. Merging the
32 columns' lane sums into one array by rotate-and-select and placing them with
a 0 / 1 contraction was SLOWER (707 against 622: the lane sum is not what the
loop waits for). Results agree with `_prepare`'s to 1e-7 … 1.4e-6 of values up
to 9. On the cell (`kimi-linear-48b-a3b-ep4-d8.longctx512`, traced): 383 µs a
launch, six a step, 10.3 % of busy where the XLA solve was ~0.8 ms a layer; a
step 24.8 -> 22.3 ms (PERF.md section 5).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ollamamq_tpu.ops.gated_delta import _SUB, CHUNK
from ollamamq_tpu.ops.pallas.chunk_rule import _HI, _dot
from ollamamq_tpu.ops.pallas.gated_delta_step import head_blocks

VMEM_BYTES = 18 << 20  # a program's blocks, double-buffered
_F32 = jnp.float32


def _block_bytes(hb: int, dk: int, dv: int, vector: bool) -> int:
    """What a program of `hb` heads holds in VMEM: q, k^T and v in, [w; qg],
    [attn; k^T] and u out (vector: g^T in and the [dk, C] block of G out) —
    every block twice (the pipeline's two buffers) — and T a head once
    (vector: and the rows of gc and of k); minor dimensions padded to 128
    lanes."""
    def lanes(n):
        return -(-n // 128) * 128

    c = CHUNK
    head = (c * lanes(dk) + dk * lanes(c) + 2 * c * dv
            + 2 * c * lanes(dk) + (c + dk) * lanes(c))
    once = c * lanes(c)
    if vector:
        head += 2 * dk * lanes(c)
        once += 2 * c * lanes(dk)
    return 4 * hb * (2 * head + once)


def blocks(heads: int, dk: int, dv: int, plain: bool, vector: bool = False):
    """(heads a lane group, heads a block) the kernel solves `(H, dk, dv)`
    at, or None where it does not (`_prepare` does): not `plain` (no solve),
    the key dimension whole sublane tiles, lane groups as
    `gated_delta_step.head_blocks` cuts them, and the most heads a block
    that divide H and fit VMEM_BYTES."""
    hg, _ = head_blocks(heads, dk, dv)
    if plain or dk % 8:
        return None
    fit = [n for n in range(hg, heads + 1, hg) if heads % n == 0
           and _block_bytes(n, dk, dv, vector) <= VMEM_BYTES]
    return (hg, max(fit)) if fit else None


def solved_windows(spans, stream_len: int) -> int:
    """The windows a launch solves of a ragged step: those of the stream's
    CHUNK-token windows that hold a token of a span longer than one token
    (`chunk_solve_pallas`'s own `touched`, on the host). `spans`: each
    row's tokens, in stream order."""
    del stream_len  # a window past the last span is not solved
    touched, start = set(), 0
    for n in spans:
        if n > 1:
            touched.update(range(start // CHUNK, (start + n - 1) // CHUNK + 1))
        start += n
    return len(touched)


def laid_out(c: dict) -> dict:
    """`gated_delta._prepare`'s results (heads leading; not `plain`) as
    `chunk_solve_pallas` returns them: what it is held to, name for name."""
    n, h, chunk, dv = c["u"].shape
    return {"u": jnp.moveaxis(c["u"], 1, 2).reshape(n, chunk, h * dv),
            "on_s": jnp.concatenate([c["w"], c["qg"]], axis=2),
            "on_v": jnp.concatenate(
                [c["attn"], jnp.swapaxes(c["k"], -1, -2)], axis=2),
            "gc": jnp.swapaxes(c["gc"], -1, -2) if c["gc"].ndim == 4
            else c["gc"][:, :, None, :]}


def _mm(a, b):
    """[G, i, k] x [G, k, j] a head, as `chunk_rule._dot` runs one."""
    return jax.lax.dot_general(a, b, (((2,), (1,)), ((0,), (0,))),
                               precision=_HI, preferred_element_type=_F32)


def _tri_inv(a):
    """`gated_delta._tri_inv` on [G, C, C] strictly lower-triangular `a`, a
    head each: the substitution on every head's nb diagonal blocks at once,
    a block's rows [_SUB, C] with its inverse in its own lanes
    (right-looking: after step m row m of every block is final, so the
    later steps touch a block's second half alone), then a merge a level,
    [[P, 0], [R, Q]]^-1 = [[P', 0], [-Q' R P', Q']] as X - (X L) X over the
    rows of every pair's second block, L those rows' blocks of `a`."""
    g, c, _ = a.shape
    nb, half = c // _SUB, _SUB // 2
    a4 = a.reshape(g, nb, _SUB, c)
    i = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    x = jnp.broadcast_to((i == j).astype(_F32).reshape(nb, _SUB, c),
                         a4.shape)
    for m in range(_SUB - 1):
        lo = half if m >= half - 1 else 0  # rows above m + 1 are final
        col = jnp.stack([a4[:, b, lo:, b * _SUB + m:b * _SUB + m + 1]
                         for b in range(nb)], axis=1)  # a's column m
        x = jnp.concatenate([x[:, :, :lo], x[:, :, lo:]
                             - col * x[:, :, m:m + 1, :]][lo == 0:], axis=2)
    x, size = x.reshape(g, c, c), _SUB
    while size < c:  # block (2i + 1, 2i) of the level's grid
        seconds = [slice(at, at + size) for at in range(size, c, 2 * size)]
        low = jnp.where((i // size == j // size + 1) & (
            i // (2 * size) == j // (2 * size)), a, 0.0)  # [[0, 0], [R, 0]]
        q = jnp.concatenate([x[:, rows] for rows in seconds], axis=1)
        qrp = _mm(_mm(q, low), x)  # rows of the second blocks: Q' R P'
        zero = jnp.zeros((g, size, c), _F32)
        x = x - jnp.concatenate([y for n in range(len(seconds)) for y in (
            zero, qrp[:, n * size:(n + 1) * size])], axis=1)
        size *= 2
    return x


def _kernel(win_ref, n_ref, q_ref, kt_ref, v_ref, b_ref, row_l_ref, row_s_ref,
            g_ref, u_ref, on_s_ref, on_v_ref, gc_ref, t_ref, *held, hg, dv,
            vector):
    del win_ref, n_ref  # the index maps and the grid read them
    hb, c, dk = q_ref.shape
    nb = c // _SUB
    i = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    row_l, row_s = row_l_ref[...], row_s_ref[...]  # [1, C], [C, 1]
    mine = row_s == row_l
    same, strict, eye = mine & (i >= j), mine & (i > j), i == j
    same_t = (mine & (i <= j)).astype(_F32)  # [j, i]
    q, kt, beta = q_ref[...], kt_ref[...], b_ref[...]
    k = jnp.swapaxes(kt, 1, 2)  # [hb, C, dk]

    def column(rows):  # [G, 1, C] -> [G, C, 1]
        return jnp.sum(jnp.where(eye, rows, 0.0), axis=2, keepdims=True)

    if not vector:
        # every head's gc in ONE contraction, then a [1, C] row a head
        gc = _dot(g_ref[...], same_t)
        gc = jnp.stack([gc[h:h + 1] for h in range(hb)])  # [hb, 1, C]
        decay = jnp.exp(jnp.where(same, column(gc) - gc, -jnp.inf))
        kq = _mm(jnp.concatenate([k, q], axis=1), kt)  # [hb, 2C, C]
        a, attn = column(beta) * kq[:, :c] * decay, kq[:, c:] * decay
        scale, k_w, qg = beta * jnp.exp(gc), k, q * jnp.exp(column(gc))
    else:
        gs_ref, ks_ref = held  # rows of gc and of k, for the loop's reads
        # ONE running sum, both ways round: [dk, C] and [C, dk] a head
        gt = g_ref[...]
        gct = _dot(gt.reshape(hb * dk, c), same_t).reshape(hb, dk, c)
        gs_ref[...] = jnp.swapaxes(gct, 1, 2)
        ks_ref[...] = k
        gc, g = gs_ref[...], jnp.swapaxes(gt, 1, 2)  # [hb, C, dk]
        row4 = row_s.reshape(nb, _SUB, 1)
        i4 = jax.lax.broadcasted_iota(jnp.int32, (nb, _SUB, 1), 1)
        at4 = jax.lax.broadcasted_iota(jnp.int32, (nb, 1, c), 2) \
            - _SUB * jax.lax.broadcasted_iota(jnp.int32, (nb, 1, c), 0)
        g4, k4, q4 = (x.reshape(hb, nb, _SUB, dk) for x in (gc, k, q))

        def of_blocks(ref, lead):  # token m of every block: [.., nb, 1, .]
            return lambda m: jnp.stack([
                ref[(*lead, pl.ds(b * _SUB + m, 1), slice(None))]
                for b in range(nb)], axis=len(lead))

        g_at, k_at, row_at = (of_blocks(gs_ref, (slice(None),)),
                              of_blocks(ks_ref, (slice(None),)),
                              of_blocks(row_s_ref, ()))

        def inside(lo):  # column m of every head's diagonal blocks, for
            # the blocks' rows from `lo` on (rows above m are masked)
            def column_m(m, sums):
                live = (row4[:, lo:] == row_at(m)) & (i4[:, lo:] >= m)
                kd = k_at(m) * jnp.exp(jnp.where(
                    live, g4[:, :, lo:] - g_at(m), -jnp.inf))
                return tuple(jnp.where(at4 == m, jnp.sum(
                    x[:, :, lo:] * kd, axis=-1, keepdims=True), acc)
                    for x, acc in zip((k4, q4), sums))
            zero = jnp.zeros((hb, nb, _SUB - lo, c), _F32)
            return jax.lax.fori_loop(lo, max(2 * lo, _SUB // 2), column_m,
                                     (zero, zero))

        half = _SUB // 2
        # (the columns of a block's second half: its second half's rows)
        kk, qk = (jnp.concatenate(
            [x[:, :, :half], x[:, :, half:] + y], axis=2).reshape(hb, c, c)
            for x, y in zip(inside(0), inside(half)))
        # A block's tokens against the EARLIER blocks': both sides scaled to
        # the row's G before the block's first token.
        off = [jnp.zeros((hb, 2 * _SUB, c), _F32)]
        for b in range(1, nb):
            at, rows = b * _SUB, slice(b * _SUB, (b + 1) * _SUB)
            first = row_s[at:at + 1]  # [1, 1]: the first token's row
            ref_l = gc[:, at:at + 1] - g[:, at:at + 1]  # [hb, 1, dk]
            ref_s = gct[:, :, at:at + 1] - gt[:, :, at:at + 1]  # [hb, dk, 1]
            up = jnp.exp(jnp.where(row_s[rows] == first,
                                   gc[:, rows] - ref_l, -jnp.inf))
            down = jnp.exp(jnp.where((row_l == first) & (j[:1] < at),
                                     ref_s - gct, -jnp.inf))
            off.append(_mm(jnp.concatenate(
                [k[:, rows] * up, q[:, rows] * up], axis=1), kt * down))
        kk = kk + jnp.concatenate([x[:, :_SUB] for x in off], axis=1)
        qk = qk + jnp.concatenate([x[:, _SUB:] for x in off], axis=1)
        e = jnp.exp(gc)
        a, attn = column(beta) * kk, jnp.where(same, qk, 0.0)
        scale, k_w, qg, gc = beta, k * e, q * e, gct
    t = _tri_inv(jnp.where(strict, a, 0.0))
    t_ref[...] = t * beta
    on_s_ref[:, :c] = _mm(t * scale, k_w)
    on_s_ref[:, c:] = qg
    on_v_ref[:, :c] = attn
    on_v_ref[:, c:] = kt
    gc_ref[...] = gc
    gl = hg * dv
    head_of = jax.lax.broadcasted_iota(jnp.int32, (1, gl), 1) // dv
    for n in range(hb // hg):  # u = (T beta) v, a lane group of heads
        at = slice(n * gl, (n + 1) * gl)
        v = v_ref[:, at]
        u = _dot(t_ref[n * hg], v)
        for m in range(1, hg):
            u = jnp.where(head_of >= m, _dot(t_ref[n * hg + m], v), u)
        u_ref[:, at] = u


@functools.partial(jax.jit, static_argnames=("interpret",))
def chunk_solve_pallas(q, k, v, g, beta, row_of, interpret: bool = False):
    """`gated_delta._prepare` over the windows a span touches. q, k [n, C, H,
    dk] float32 a VALUE head (after `gated_delta._operands`), v [n, C, H,
    dv], g, beta [n, C, H] (g [n, C, H, dk]: a decay a key channel; 0 on
    tokens that take no part), row_of [n, C] int32 each window token's row
    (-1: of no span). Returns `_prepare`'s results as `chunk_rule_pallas`
    lays them out — "u" [n, C, H * dv], "on_s" [n, H, 2C, dk] ([w; qg]),
    "on_v" [n, H, C + dk, C] ([attn; k^T]), "gc" [n, H, 1, C] (vector: [n,
    H, dk, C]) — right at the windows that hold a token of a span (at
    window 0 where none does); the other windows are never written."""
    n, c, h, dk = q.shape
    dv = v.shape[-1]
    vector = g.ndim == 4
    hg, hb = blocks(h, dk, dv, False, vector)
    nblk = h // hb
    touched = jnp.any(row_of >= 0, axis=1)
    wins = jnp.argsort(~touched, stable=True).astype(jnp.int32)
    count = jnp.sum(touched).astype(jnp.int32)
    q = jnp.moveaxis(q, 2, 1)  # [n, H, C, dk]
    kt = jnp.moveaxis(k, 1, 3)  # [n, H, dk, C]

    def head_block(j, p, win_ref, n_ref):
        return (win_ref[p], j, 0, 0)

    def window(j, p, win_ref, n_ref):
        return (win_ref[p], 0, 0)

    def per_head(*tail):
        return pl.BlockSpec((None, hb) + tail, head_block)

    lane_spec = pl.BlockSpec((None, c, hb * dv),
                             lambda j, p, win_ref, n_ref: (win_ref[p], 0, j))
    scratch = [pltpu.VMEM((hb, c, c), _F32)]  # T beta a head
    if vector:  # g^T [dk, C] a head; gc's and k's rows for the loop
        gate, gate_spec = jnp.moveaxis(g, 1, 3), per_head(dk, c)
        scratch += [pltpu.VMEM((hb, c, dk), _F32)] * 2
    else:  # [hb, C]: one contraction's rows
        gate = jnp.moveaxis(g, 2, 1).reshape(n, nblk, hb, c)
        gate_spec = pl.BlockSpec((None, None, hb, c), head_block)
    u, on_s, on_v, gc = pl.pallas_call(
        functools.partial(_kernel, hg=hg, dv=dv, vector=vector),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(nblk, jnp.maximum(count, 1)),
            in_specs=[
                per_head(c, dk), per_head(dk, c), lane_spec, per_head(1, c),
                pl.BlockSpec((None, 1, c), window),
                pl.BlockSpec((None, c, 1), window), gate_spec],
            out_specs=[lane_spec, per_head(2 * c, dk), per_head(c + dk, c),
                       per_head(dk if vector else 1, c)],
            scratch_shapes=scratch),
        out_shape=[jax.ShapeDtypeStruct((n, c, h * dv), _F32),
                   jax.ShapeDtypeStruct((n, h, 2 * c, dk), _F32),
                   jax.ShapeDtypeStruct((n, h, c + dk, c), _F32),
                   jax.ShapeDtypeStruct((n, h, dk if vector else 1, c),
                                        _F32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=2 * VMEM_BYTES),
        interpret=interpret,
    )(wins, count.reshape(1), q, kt, v.astype(_F32).reshape(n, c, h * dv),
      jnp.moveaxis(beta, 2, 1)[:, :, None, :], row_of[:, None, :],
      row_of[:, :, None], gate)
    return {"u": u, "on_s": on_s, "on_v": on_v, "gc": gc}
