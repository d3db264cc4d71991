"""Manifold-constrained hyper-connections (mHC, arXiv:2512.24880, over
Hyper-Connections, arXiv:2409.19606): the residual of a decoder is ``n``
streams, and every sublayer ``F`` (with its own pre-norm) is wrapped by three
small matrices computed from the streams themselves, per token:

    x~     = vec(x) / sqrt(mean(vec(x)^2) + norm_eps)          in R^{nD}
    H~pre  = a_pre  (x~ phi_pre)  + b_pre                      in R^n
    H~post = a_post (x~ phi_post) + b_post                     in R^n
    H~res  = a_res  mat(x~ phi_res) + b_res                    in R^{n x n}
    H_pre  = sigmoid(H~pre)    H_post = 2 sigmoid(H~post)
    H_res  = Sinkhorn(clip(H~res, lo, hi)): M = exp(.), then ``iters`` times
             rows / (row sums + eps), columns / (column sums + eps)
    h      = H_pre x                        (the sublayer's input, R^D)
    x'     = H_res x + H_post^T F(h)        (the streams after it)

``H_res`` is doubly stochastic (to the accuracy ``iters`` iterations reach),
so the mix neither grows nor shrinks what the streams carry.

Layout: the streams are STREAM-MAJOR, ``x[n, ..., D]``: every stream is an
array shaped like the plain residual (whole lane tiles, nothing padded; ``n``
= 4 as the second-minor axis of a bf16 array would be padded to a tile of
16 sublanes), ``H_pre x`` and ``H_res x`` are ``n`` and ``n^2`` scaled adds of
such arrays, and ``x~ phi`` is ``n`` products ``[tokens, D] x [D, n(n+2)]``
summed. ``phi`` is one matrix ``[nD, n(n+2)]`` (columns: pre, post, then res
row-major; rows: stream-major, the order of ``vec``), ``b`` its ``n(n+2)``
biases and ``a`` the three scalars.

Everything here is float32 whatever the streams' dtype, products at
``Precision.HIGHEST``: the matrices feed 20 normalisations and weigh every
later layer's input, and a bf16 product of 14336 terms is told from this one
by the on-chip comparison (benchmarks/chip/configs/xing4.0-29b-a4b-d7/
check_reference.py, ``--stage mix``). The Sinkhorn loop runs on the ``n^2``
entries as SEPARATE arrays of the tokens' shape: every step is then
elementwise at one index (sums of four arrays, no reduction over an axis of
four), which XLA fuses into one kernel a trip of the loop where 40
reductions a sublayer would be 40 launches; a normalisation multiplies by
one reciprocal a row (a column) where the equations divide every entry.

One execution, plain ``jax.numpy`` (``EXECUTION``): there is no kernel to
choose.
"""

from typing import List, Tuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
# What GET /debug/programs and /version say computes the mix.
EXECUTION = "xla"
# Sinkhorn iterations a trip of the loop. A trip costs a tenth of a
# microsecond on a v5e, an unrolled iteration 3% of a program's size: alone
# at 16 rows a sublayer's mix takes 21.2 / 19.3 / 18.9 / 18.8 us at 1 / 2 /
# 5 / 20 iterations a trip (PERF.md section 6, PR 38).
SINKHORN_UNROLL = 2


def phi_columns(n: int) -> int:
    """Columns of ``phi`` (and entries of ``b``): pre, post, res."""
    return n * (n + 2)


def sinkhorn(m: List[List[jax.Array]], iters: int, eps: float
             ) -> List[List[jax.Array]]:
    """``iters`` times rows then columns of the positive ``n x n`` matrix
    whose entries are the arrays ``m[i][j]`` (one shape). A loop of
    ``iters / SINKHORN_UNROLL`` trips: unrolled whole, twenty iterations are
    1,100 operations a sublayer and a decode program 26.6k instructions
    where it is 10k as a loop (31 MB serialized against 17, compiled for a
    described v5e; PERF.md section 6, PR 38), and the configuration's 48
    programs must fit the compile cache's cap together."""
    n = len(m)

    def step(_, m):
        rows = [1.0 / (sum(m[i][1:], m[i][0]) + eps) for i in range(n)]
        m = [[m[i][j] * rows[i] for j in range(n)] for i in range(n)]
        cols = [1.0 / (sum((m[i][j] for i in range(1, n)), m[0][j]) + eps)
                for j in range(n)]
        return [[m[i][j] * cols[j] for j in range(n)] for i in range(n)]

    return jax.lax.fori_loop(0, iters, step, m,
                             unroll=max(1, min(SINKHORN_UNROLL, iters)))


def mix_matrices(x: jax.Array, phi: jax.Array, b: jax.Array, a: jax.Array,
                 *, iters: int, eps: float, norm_eps: float,
                 clamp: Tuple[float, float]
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(H_pre [..., n], H_post [..., n], H_res [..., n, n]), float32, of the
    streams ``x [n, ..., D]`` under one sublayer's ``phi [nD, n(n+2)]``,
    ``b [n(n+2)]`` and ``a [3]``."""
    n, d = x.shape[0], x.shape[-1]
    xf = x.astype(F32)
    phi = phi.astype(F32).reshape(n, d, phi_columns(n))
    proj = sum(jnp.dot(xf[j], phi[j], precision=HIGHEST,
                       preferred_element_type=F32) for j in range(n))
    mean_sq = sum(jnp.sum(xf[j] * xf[j], axis=-1) for j in range(n)) / (n * d)
    proj = proj * jax.lax.rsqrt(mean_sq + norm_eps)[..., None]
    scale = jnp.concatenate([jnp.broadcast_to(a[k].astype(F32), (width,))
                             for k, width in enumerate((n, n, n * n))])
    logits = proj * scale + b.astype(F32)
    h_pre = jax.nn.sigmoid(logits[..., :n])
    h_post = 2.0 * jax.nn.sigmoid(logits[..., n:2 * n])
    res = jnp.clip(logits[..., 2 * n:], *clamp)
    m = sinkhorn([[jnp.exp(res[..., i * n + j]) for j in range(n)]
                  for i in range(n)], iters, eps)
    h_res = jnp.stack([jnp.stack(row, axis=-1) for row in m], axis=-2)
    return h_pre, h_post, h_res


def pre(x: jax.Array, h_pre: jax.Array) -> jax.Array:
    """``H_pre x``: the sublayer's input ``[..., D]``, float32."""
    return sum(h_pre[..., j, None] * x[j].astype(F32)
               for j in range(x.shape[0]))


def post(x: jax.Array, branch: jax.Array, h_post: jax.Array,
         h_res: jax.Array) -> jax.Array:
    """``H_res x + H_post^T branch``: the streams ``[n, ..., D]`` after the
    sublayer whose output is ``branch [..., D]``, float32."""
    n = x.shape[0]
    xf, bf = x.astype(F32), branch.astype(F32)
    return jnp.stack([
        sum((h_res[..., i, j, None] * xf[j] for j in range(n)),
            h_post[..., i, None] * bf)
        for i in range(n)])
