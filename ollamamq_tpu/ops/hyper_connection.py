"""A residual path of n streams mixed by manifold-constrained
hyper-connections (mHC, arXiv:2512.24880; `ModelConfig.hc_mult`).

A token's residual is X [n, C]. Around a sublayer F (attention or FFN):

    u      = vec(X) / sqrt(mean(vec(X)^2) + rms_norm_eps)     all n C lanes, no weight
    z      = alpha . (u Phi) + b         Phi [n C, 2 n + n^2], alpha a scalar a group
    H_pre  = sigmoid(z[:n]) + eps                               [n]
    H_post = 2 sigmoid(z[n:2n])                                 [n]
    M      = exp(clip(z[2n:], lo, hi))                          [n, n], row-major
    M      <- each column / (its sum + eps), then each row likewise; `iters` times
    h      = sum_j H_pre[j] X[j]                                what F's norm reads
    X'[i]  = sum_j M[i, j] X[j] + H_post[i] F(norm(h))

and before the head `y = sum_j (sigmoid(alpha_h . (u Phi_h) + b_h) + eps)[j]
X[j]`. With one stream and all three mappings 1 this is `x + F(norm(x))`.

`mix_in` gives (h, maps): `maps` [T, >= 2 n + n^2] float32 holds a token's H_pre
| H_post | H_res side by side (`split_maps`), as `mix_out` takes them back —
one array, because on the chip it is one kernel's result and the next one's
operand (ops/pallas/hyper_connection.py: 128 lanes wide there). Phi is held
MAPS-major, [2 n + n^2, n C]: the contraction runs along the lanes of both
operands. Everything but the streams is float32; the product runs at the
highest precision. The jnp twins here are what the CPU serves and what the
kernels are held to.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST
_F32 = jnp.float32


class Consts(NamedTuple):
    """The connection's constants, a configuration's (`consts`)."""
    n: int
    iters: int
    eps: float
    norm_eps: float
    lo: float
    hi: float


def consts(cfg) -> Consts:
    return Consts(cfg.streams, cfg.hc_sinkhorn_iters, cfg.hc_eps,
                  cfg.rms_norm_eps, cfg.mhc_h_res_clamp_min,
                  cfg.mhc_h_res_clamp_max)


def sinkhorn(m: jnp.ndarray, iters: int, eps: float) -> jnp.ndarray:
    """[..., n, n] positive -> doubly stochastic: `iters` times, each column
    over (its sum + eps), then each row likewise."""
    def once(_, m):
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
        return m / (jnp.sum(m, axis=-1, keepdims=True) + eps)

    return jax.lax.fori_loop(0, iters, once, m)


def res_map(z: jnp.ndarray, k: Consts) -> jnp.ndarray:
    """H_res of the n^2 logits z [..., n^2] (row-major: entry i n + j mixes
    FROM stream j INTO stream i): the clamp, exp, the iterations."""
    m = jnp.exp(jnp.clip(z, k.lo, k.hi))
    return sinkhorn(m.reshape(z.shape[:-1] + (k.n, k.n)), k.iters, k.eps)


def _logits(x, phi, alpha, bias, k: Consts):
    """z [T, maps] of x [T, n, C]: alpha a group of maps (n | n | n^2; the
    read-out's: one group) over the normed product, plus the bias."""
    T = x.shape[0]
    xf = x.reshape(T, -1).astype(_F32)
    r = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + k.norm_eps)
    a = jnp.einsum("tk,mk->tm", xf * r, phi.astype(_F32), precision=_HI)
    groups = (k.n, k.n, k.n * k.n)[:alpha.shape[0]]
    scale = jnp.concatenate([jnp.full((g,), alpha[i], _F32)
                             for i, g in enumerate(groups)])
    return scale * a + bias.astype(_F32), xf.reshape(x.shape)


def split_maps(maps: jnp.ndarray, n: int):
    """(H_pre [T, n], H_post [T, n], H_res [T, n, n]) of `mix_in`'s maps."""
    return (maps[:, :n], maps[:, n:2 * n],
            maps[:, 2 * n:2 * n + n * n].reshape(-1, n, n))


def mix_in(x, phi, alpha, bias, k: Consts, impl: str = "jnp",
           interpret: bool = False):
    """x [T, n, C] streams; phi [2 n + n^2, n C], alpha [3], bias [2 n + n^2]
    float32. Returns (h [T, C] in the streams' dtype, maps)."""
    if impl == "pallas":
        from ollamamq_tpu.ops.pallas.hyper_connection import mhc_mix_in_pallas

        return mhc_mix_in_pallas(x, phi, alpha, bias, k, interpret=interpret)
    n = k.n
    z, xf = _logits(x, phi, alpha, bias, k)
    pre = jax.nn.sigmoid(z[:, :n]) + k.eps
    post = 2.0 * jax.nn.sigmoid(z[:, n:2 * n])
    res = res_map(z[:, 2 * n:], k)
    h = jnp.einsum("tj,tjc->tc", pre, xf, precision=_HI)
    maps = jnp.concatenate([pre, post, res.reshape(-1, n * n)], axis=-1)
    return h.astype(x.dtype), maps


def mix_out(x, delta, maps, k: Consts, impl: str = "jnp",
            interpret: bool = False):
    """x [T, n, C], delta [T, C] (the sublayer's result), `mix_in`'s maps ->
    X' [T, n, C]: H_res over the streams plus H_post times delta."""
    if impl == "pallas":
        from ollamamq_tpu.ops.pallas.hyper_connection import (
            mhc_mix_out_pallas)

        return mhc_mix_out_pallas(x, delta, maps, k, interpret=interpret)
    _, post, res = split_maps(maps, k.n)
    out = jnp.einsum("tij,tjc->tic", res, x.astype(_F32), precision=_HI) \
        + post[:, :, None] * delta.astype(_F32)[:, None, :]
    return out.astype(x.dtype)


def read_out(x, phi, alpha, bias, k: Consts, impl: str = "jnp",
             interpret: bool = False):
    """x [T, n, C]; phi [n, n C], alpha [1], bias [n] -> y [T, C]: the
    streams under a learned mix of H_pre's form (what the final norm reads)."""
    if impl == "pallas":
        from ollamamq_tpu.ops.pallas.hyper_connection import mhc_mix_in_pallas

        return mhc_mix_in_pallas(x, phi, alpha, bias, k, full=False,
                                 interpret=interpret)
    z, xf = _logits(x, phi, alpha, bias, k)
    rho = jax.nn.sigmoid(z) + k.eps
    return jnp.einsum("tj,tjc->tc", rho, xf, precision=_HI).astype(x.dtype)


def flops_per_token(n: int, c: int) -> int:
    """One application on one token: the product u Phi and the two mixes
    (2 n + n^2 weights a lane of C, multiply and add)."""
    maps = 2 * n + n * n
    return 2 * n * c * maps + maps * c * 2
