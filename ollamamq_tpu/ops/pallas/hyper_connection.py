"""The hyper-connection's two halves (ops/hyper_connection.py has the
mathematics) as Pallas TPU kernels, ONE launch each an application:

`mhc_mix_in_pallas` — a tile of `TILE` tokens' streams [TILE, n C] in VMEM:
    the product with Phi [maps, n C] as `Phi x^T` (both contracted along their
    lanes), so that the maps land on SUBLANES and the tokens on LANES; the
    mean square of a token's n C lanes the same way (a row of ones for Phi);
    sigmoid / clip / exp and the Sinkhorn iterations on [1, TILE] rows, one a
    matrix entry — a column's or a row's sum is three adds of such rows, no
    shuffle inside a register; then ONE [128, 128] transpose puts a token's
    maps on 128 lanes of its own row (`maps` [T, 128] float32: H_pre | H_post
    | H_res | zeros), whose columns weigh the streams: h = sum_j H_pre[j] X[j].
    With `full=False` (the read-out before the head) Phi has n rows, nothing
    is iterated and only y = h leaves.
`mhc_mix_out_pallas` — X'[i] = sum_j H_res[i, j] X[j] + H_post[i] delta, a
    tile's streams read once and written once IN PLACE (`input_output_aliases`;
    donate x), `LANES`-lane chunks at a time.

Float32 inside (the streams are upcast a chunk at a time; the product at
`precision=HIGHEST`). Rows past T in the last tile are whatever the block
holds: every row's arithmetic is its own, and their results are not stored.
The Mosaic custom calls carry these functions' names on the device trace
(`mhc_mix_in_pallas`, `mhc_mix_out_pallas`), where the benchmark's readers
find them (benchmarks/layer_metrics/_mhc.py).

Measured on a v5e (scripts/mhc_bench.py; my chip run, PR 69) — see `TIMES`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE = 128  # tokens a program: the lanes of the maps' rows, one transpose
LANES = 512  # lanes of a stream a trip of the weighted sums
MAP_LANES = 128  # lanes of a token's row of `maps`
VMEM_LIMIT = 64 << 20  # a tile's streams in and out, double-buffered
_HI = jax.lax.Precision.HIGHEST
_F32 = jnp.float32
_NT = (((1,), (1,)), ((), ()))  # a . b^T: both contracted along their lanes


def _chunks(c: int):
    step = min(LANES, c)
    return [(at, min(step, c - at)) for at in range(0, c, step)]


def _mix_in_kernel(alpha_ref, x_ref, phi_ref, bias_ref, h_ref, *rest, k,
                   c, full):
    maps_ref, scr = rest if full else (None,) + rest
    n = k.n
    m = phi_ref.shape[0]
    tile = x_ref.shape[0]
    acc = jnp.zeros((m, tile), _F32)
    ss = jnp.zeros((8, tile), _F32)
    ones = jnp.ones((8, c), _F32)
    for j in range(n):
        xj = x_ref[:, j * c:(j + 1) * c].astype(_F32)
        acc += jax.lax.dot_general(phi_ref[:, j * c:(j + 1) * c], xj, _NT,
                                   precision=_HI, preferred_element_type=_F32)
        ss += jax.lax.dot_general(ones, xj * xj, _NT, precision=_HI,
                                  preferred_element_type=_F32)
    r = jax.lax.rsqrt(ss[0:1] / (n * c) + k.norm_eps)  # [1, tile]
    row = jax.lax.broadcasted_iota(jnp.int32, (m, 1), 0)
    scale = alpha_ref[0]
    if full:  # a scalar a group of maps: H_pre | H_post | H_res
        scale = jnp.where(row < n, scale, jnp.where(
            row < 2 * n, alpha_ref[1], alpha_ref[2]))
    z = scale * (acc * r) + bias_ref[...]  # [m, tile]
    scr[...] = jnp.zeros(scr.shape, _F32)
    scr[0:m, :] = z
    for j in range(n):
        scr[j:j + 1, :] = jax.nn.sigmoid(scr[j:j + 1, :]) + k.eps
    if full:
        for j in range(n, 2 * n):
            scr[j:j + 1, :] = 2.0 * jax.nn.sigmoid(scr[j:j + 1, :])
        at = 2 * n
        mat = [[jnp.exp(jnp.clip(scr[at + i * n + j:at + i * n + j + 1, :],
                                 k.lo, k.hi)) for j in range(n)]
               for i in range(n)]

        def once(_, flat):
            mat = [list(flat[i * n:(i + 1) * n]) for i in range(n)]
            for j in range(n):  # a column over (its sum + eps)
                inv = 1.0 / (sum(mat[i][j] for i in range(n)) + k.eps)
                for i in range(n):
                    mat[i][j] = mat[i][j] * inv
            for i in range(n):  # ...then a row
                inv = 1.0 / (sum(mat[i]) + k.eps)
                mat[i] = [e * inv for e in mat[i]]
            return tuple(e for line in mat for e in line)

        flat = jax.lax.fori_loop(
            0, k.iters, once, tuple(e for line in mat for e in line))
        for i, e in enumerate(flat):
            scr[at + i:at + i + 1, :] = e
    maps = scr[...].T  # [tile, 128]: a token's maps on its own row
    if full:
        maps_ref[...] = maps
    for at, w in _chunks(c):
        h = sum(maps[:, j:j + 1]
                * x_ref[:, j * c + at:j * c + at + w].astype(_F32)
                for j in range(n))
        h_ref[:, at:at + w] = h.astype(h_ref.dtype)


@functools.partial(jax.jit, static_argnames=("k", "full", "interpret"))
def mhc_mix_in_pallas(x, phi, alpha, bias, k, full: bool = True,
                      interpret: bool = False):
    """x [T, n, C]; phi [maps, n C], alpha [3] ([1] with `full` false), bias
    [maps] float32. Returns (h [T, C], maps [T, 128] float32) — h alone for
    the read-out (`full` false: phi [n, n C])."""
    T, n, c = x.shape
    m = phi.shape[0]
    assert n == k.n and m == (2 * n + n * n if full else n), (x.shape, m)
    assert TILE == MAP_LANES and m <= MAP_LANES
    x2 = x.reshape(T, n * c)
    row_spec = pl.BlockSpec((TILE, n * c), lambda i: (i, 0))
    out_specs = [pl.BlockSpec((TILE, c), lambda i: (i, 0))]
    out_shape = [jax.ShapeDtypeStruct((T, c), x.dtype)]
    if full:
        out_specs.append(pl.BlockSpec((TILE, MAP_LANES), lambda i: (i, 0)))
        out_shape.append(jax.ShapeDtypeStruct((T, MAP_LANES), _F32))
    out = pl.pallas_call(
        functools.partial(_mix_in_kernel, k=k, c=c, full=full),
        grid=(pl.cdiv(T, TILE),),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), row_spec,
                  pl.BlockSpec((m, n * c), lambda i: (0, 0)),
                  pl.BlockSpec((m, 1), lambda i: (0, 0))],
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((MAP_LANES, TILE), _F32)],
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(alpha.astype(_F32), x2, phi.astype(_F32),
      bias.astype(_F32).reshape(m, 1))
    return tuple(out) if full else out[0]


def _mix_out_kernel(x_ref, delta_ref, maps_ref, o_ref, *, n, c):
    maps = maps_ref[...]  # [tile, 128]
    for at, w in _chunks(c):
        xs = [x_ref[:, j * c + at:j * c + at + w].astype(_F32)
              for j in range(n)]
        d = delta_ref[:, at:at + w].astype(_F32)
        for i in range(n):
            lane = 2 * n + i * n
            out = maps[:, n + i:n + i + 1] * d + sum(
                maps[:, lane + j:lane + j + 1] * xs[j] for j in range(n))
            o_ref[:, i * c + at:i * c + at + w] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def mhc_mix_out_pallas(x, delta, maps, k, interpret: bool = False):
    """x [T, n, C] (updated in place: donate it), delta [T, C], maps [T, 128]
    float32 (`mhc_mix_in_pallas`'s). Returns X' [T, n, C]."""
    T, n, c = x.shape
    assert n == k.n and maps.shape == (T, MAP_LANES), (x.shape, maps.shape)
    row_spec = pl.BlockSpec((TILE, n * c), lambda i: (i, 0))
    out = pl.pallas_call(
        functools.partial(_mix_out_kernel, n=n, c=c),
        grid=(pl.cdiv(T, TILE),),
        in_specs=[row_spec, pl.BlockSpec((TILE, c), lambda i: (i, 0)),
                  pl.BlockSpec((TILE, MAP_LANES), lambda i: (i, 0))],
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((T, n * c), x.dtype),
        input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(x.reshape(T, n * c), delta, maps)
    return out.reshape(T, n, c)
