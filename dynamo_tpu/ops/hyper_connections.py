"""Manifold-constrained hyper-connections (mHC, arXiv:2512.24880, after
Hyper-Connections, arXiv:2409.19606): ``n`` residual streams a token, mixed
before and after every sublayer by coefficients computed from the token's own
streams.

For one token's streams ``X`` (``n x C``) and one sublayer ``f`` with its own
``phi [n C, 2n + n^2]``, ``alpha [3]`` and ``bias [2n + n^2]`` (all float32):

    x~     = vec(X) / sqrt(mean(vec(X)^2) + norm_eps)      no learned scale
    m      = x~ phi
    H_pre  = sigmoid(alpha[0] m[:n] + bias[:n])
    H_post = 2 sigmoid(alpha[1] m[n:2n] + bias[n:2n])
    H_res  = sinkhorn(exp(clip(alpha[2] m[2n:] + bias[2n:], lo, hi)))   n x n
    h      = H_pre X                      the sublayer's input (then ITS norm)
    X'     = H_res X + outer(H_post, f(h))

``sinkhorn``: ``iters`` rounds of rows then columns, each divided by its sum
plus ``eps``, which leaves ``H_res`` doubly stochastic.

Layout.  The streams of a row lie side by side, ``[rows, n x C]`` in the
model's dtype: stream ``j`` is a lane-aligned slice, the statistic and ``phi``
see a row as it lies, and no axis of ``n`` = 4 is ever a tile's sublane or
lane.  The coefficients are float32 with the ROWS last (``[n, rows]``,
``[n, n, rows]``): a prompt window's 8,192 rows fill the lanes, and a sum
over rows or columns of the ``n x n`` matrix is ``n - 1`` additions of whole
slabs, written out as such so that the ``2 x iters`` normalisations are one
elementwise chain and not ``2 x iters`` reductions.
"""

from __future__ import annotations

from functools import reduce
from operator import add

import jax
import jax.numpy as jnp


def coefficient_count(n: int) -> int:
    """Columns of a sublayer's ``phi``: ``n`` into the sublayer, ``n`` out of
    it, ``n x n`` stream to stream."""
    return 2 * n + n * n


def replicate(x: jnp.ndarray, n: int) -> jnp.ndarray:
    """The embedding row ``[rows, C]`` as ``n`` equal streams ``[rows, n x C]``."""
    return jnp.tile(x, (1, n))


def collapse(x: jnp.ndarray, n: int) -> jnp.ndarray:
    """The streams summed, ``[rows, n x C] -> [rows, C]`` (float32 sum)."""
    return reduce(add, (s.astype(jnp.float32) for s in _streams(x, n))).astype(x.dtype)


def _streams(x: jnp.ndarray, n: int) -> list[jnp.ndarray]:
    return jnp.split(x, n, axis=-1)


def _sum_over(m: jnp.ndarray, axis: int) -> jnp.ndarray:
    """``m`` summed over a leading axis of length ``n``, kept, as additions
    of its slabs."""
    return reduce(add, jnp.split(m, m.shape[axis], axis=axis))


def residual_matrix(logits: jnp.ndarray, *, iters: int, eps: float, clamp) -> jnp.ndarray:
    """``logits [n, n, rows]`` float32 -> ``H_res``: the clamped exponential
    through ``iters`` Sinkhorn-Knopp rounds, rows (axis 1 summed) before
    columns (axis 0 summed)."""
    def one_round(_, m):
        m = m / (_sum_over(m, 1) + eps)
        return m / (_sum_over(m, 0) + eps)

    # (traced once and unrolled where it is lowered: one elementwise chain
    # on the device, and a twentieth of the tracing every step program pays)
    return jax.lax.fori_loop(
        0, iters, one_round, jnp.exp(jnp.clip(logits, clamp[0], clamp[1])), unroll=True)


def coefficients(x, phi, alpha, bias, n: int, *, norm_eps: float, iters: int, eps: float, clamp):
    """``x [rows, n x C]`` -> ``(H_pre [n, rows], H_post [n, rows], H_res
    [n, n, rows])``, float32 throughout (``phi`` is met at precision
    ``highest``: a product rounded to bfloat16 moves a coefficient in its
    third digit)."""
    x = x.astype(jnp.float32)
    inv_rms = jax.lax.rsqrt(jnp.mean(x * x, axis=-1) + norm_eps)
    # (the product row-major like every other of the step, its small result
    # turned: asked for rows-last, the STREAMS would be laid out rows-minor)
    m = jnp.einsum("rk,kj->rj", x, phi, precision=jax.lax.Precision.HIGHEST).T * inv_rms
    pre, post, res = jnp.split(m, (n, 2 * n))
    b_pre, b_post, b_res = jnp.split(bias[:, None], (n, 2 * n))
    h_pre = jax.nn.sigmoid(alpha[0] * pre + b_pre)
    h_post = 2.0 * jax.nn.sigmoid(alpha[1] * post + b_post)
    logits = (alpha[2] * res + b_res).reshape(n, n, -1)
    return h_pre, h_post, residual_matrix(logits, iters=iters, eps=eps, clamp=clamp)


def pre_mix(x: jnp.ndarray, h_pre: jnp.ndarray) -> jnp.ndarray:
    """``H_pre X``: the sublayer's input ``[rows, C]`` in ``x``'s dtype."""
    n = h_pre.shape[0]
    return reduce(add, (
        h_pre[j][:, None] * s.astype(jnp.float32) for j, s in enumerate(_streams(x, n))
    )).astype(x.dtype)


def post_mix(x: jnp.ndarray, y: jnp.ndarray, h_post: jnp.ndarray, h_res: jnp.ndarray) -> jnp.ndarray:
    """``H_res X + outer(H_post, y)``: the streams after the sublayer whose
    output is ``y [rows, C]``."""
    n = h_post.shape[0]
    streams = [s.astype(jnp.float32) for s in _streams(x, n)]
    y = y.astype(jnp.float32)
    return jnp.concatenate([
        reduce(add, (h_res[i, j][:, None] * streams[j] for j in range(n)))
        + h_post[i][:, None] * y
        for i in range(n)
    ], axis=-1).astype(x.dtype)


def stream_bytes_per_row(n: int, hidden: int, itemsize: int) -> int:
    """Bytes a perfect implementation moves for one row of one sublayer: the
    streams in and out and the sublayer's input and output."""
    return (2 * n + 2) * hidden * itemsize
