"""Grouped matmul on a TPU: rows sorted by group times that group's matrix.

``moe_gmm(lhs [m, k], rhs [g, k, n], group_sizes [g])`` multiplies the rows
of group i (``group_sizes[i]`` consecutive rows, groups in order) by
``rhs[i]``. The kernel is JAX's own Pallas grouped matmul
(``jax.experimental.pallas.ops.tpu.megablox``): its grid runs over the
(group, row tile) pairs that hold a row, so a group with no row is never
visited and its matrix never leaves HBM — what a decode step of a sparse
expert layer needs, where a batch of some twenty rows reaches two thirds
of the experts — and there is no capacity: a group takes what it is given.
This module only chooses the tiles and pads the rows to whole tiles:

  * ``tm`` rows a tile: 128, or the rows themselves (to whole bf16
    sublanes) where there are fewer. A tile costs its matrix block's bytes
    whatever rows of it are live; at 128 rows a block's products still take
    less time than its bytes on a v5e (128 flops a byte against 240).
  * the contraction whole (``tk = k``) where it is at most 2048, and ``tn``
    the widest multiple of 128 dividing ``n`` that keeps a block of ``rhs``
    at 2 MiB: two buffers of it, of the row tile and the accumulator stay
    well inside a v5e's 16 MiB of scoped VMEM, and a group costs few grid
    steps (~0.35 us each).

Rows past ``sum(group_sizes)`` belong to no group: they are not computed
and their output rows hold whatever the buffer held (ops/moe.py puts the
rows that do not count there and never reads them back).
"""

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu import megablox

ROW_TILE = 128
RHS_BLOCK_BYTES = 2 << 20
MAX_TK = 2048


def tiling(m: int, k: int, n: int, itemsize: int = 2):
    """(tm, tk, tn) for a problem of ``m`` (padded) rows."""
    tm = ROW_TILE if m >= ROW_TILE else -(-m // 16) * 16
    tk = k
    if k > MAX_TK:
        tk = next(c for c in range(MAX_TK, 0, -128) if k % c == 0) \
            if k % 128 == 0 else k
    tn = n
    if n % 128 == 0:
        fit = max(128, RHS_BLOCK_BYTES // (tk * itemsize) // 128 * 128)
        tn = next(c for c in range(min(n, fit), 0, -128) if n % c == 0)
    return tm, tk, tn


def moe_gmm(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array, *,
            interpret: bool = False) -> jax.Array:
    """[m, n] float32; see the module docstring."""
    m, k = lhs.shape
    n = rhs.shape[2]
    tm = tiling(m, k, n)[0]
    padded = -(-m // tm) * tm
    if padded != m:
        lhs = jnp.pad(lhs, ((0, padded - m), (0, 0)))
    # Positional: the entry is a ``custom_vjp`` (preferred element type,
    # tiling, group offset, existing output, transposed rhs, interpret).
    out = megablox.gmm(
        lhs, rhs, group_sizes.astype(jnp.int32), jnp.float32,
        tiling(padded, k, n, rhs.dtype.itemsize), None, None, False,
        interpret,
    )
    return out[:m]
