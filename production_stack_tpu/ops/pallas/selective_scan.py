"""Pallas TPU kernel: a prefill chunk of Mamba-1's selective scan
(ops/selective_scan.py:s6_chunk).

The recurrence is serial in time and independent across channels, and its
decay is a channel's and a state lane's own, so there is no matrix product
to make of a chunk. The kernel walks time with a channel block's state
``[N, BLK]`` in registers (8 vregs at 16 x 512) and the blocks over the
grid: ``(row, channel block, time block)``, the last ``arbitrary`` (the
state crosses it in a VMEM scratch), the others ``parallel``.

A token of a block: ``S = exp(dt a) S + (dt u) B``, ``y = sum_n S C``. ``dt``
and ``u`` are rows of the block ``[1, BLK]`` (a sublane broadcast over the
N rows of the state); ``B_t`` and ``C_t`` have to be COLUMNS ``[N, 1]`` (a
sublane's number along its lanes). They arrive as one array ``[T, 128]``, B
in lanes 0..N-1 and C in N..2N-1; the kernel transposes a tile of 8 tokens
once (``[8, 128] -> [128, 8]``, the form ops/pallas/ssd.py takes its ``dt
x`` in) and a token's column is a static slice of it, the 8 tokens unrolled
inside a ``fori_loop`` over the time block. The 8 rows of ``y`` are stored
as one whole tile.

Bytes a token and channel: ``dt``, ``u`` in and ``y`` out, 12 B; B and C
once a channel block (1 KB a token a block of 512 channels: 2 B a channel);
the state once a row. Work: ``N`` exponentials and ``5 N`` vector
operations a token and channel, which at the published sizes is about what
the bytes' time is; the share of a roofline the benchmark reports counts
the bytes only (benchmarks/chip/lib/shapes_sambay.py).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES, SUBLANES = 128, 8
CHANNEL_BLOCKS = (512, 256, 128)
TIME_BLOCKS = (256, 128, 64, 32, 16, 8)


def _first_dividing(n: int, sizes) -> int:
    return next((s for s in sizes if n % s == 0), 0)


def supports_chunk_kernel(t: int, n_state: int, channels: int) -> bool:
    """Whether a chunk of ``t`` tokens over a state ``(N, D)`` fits the
    kernel: whole tiles of 8 tokens, the channels whole lane tiles, N whole
    sublane tiles with B and C together inside one row of lanes."""
    return t % SUBLANES == 0 and channels % LANES == 0 \
        and n_state % SUBLANES == 0 and 2 * n_state <= LANES


def _chunk_kernel(dt_ref,     # VMEM [1, TC, BLK] f32
                  u_ref,      # VMEM [1, TC, BLK] f32
                  bc_ref,     # VMEM [1, TC, 128] f32: B | C | zeros
                  a_ref,      # VMEM [N, BLK] f32
                  d_ref,      # VMEM [1, BLK] f32: the skip
                  s0_ref,     # VMEM [1, N, BLK] f32: the state before
                  y_ref,      # VMEM [1, TC, BLK] f32
                  s_ref,      # VMEM [1, N, BLK] f32: the state after
                  state):     # VMEM scratch [N, BLK] f32
    n = a_ref.shape[0]
    tc = dt_ref.shape[1]

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = s0_ref[0]

    a = a_ref[...]
    skip = d_ref[...]

    def tile(g, s):
        rows = pl.ds(pl.multiple_of(g * SUBLANES, SUBLANES), SUBLANES)
        dt8 = dt_ref[0, rows, :]
        u8 = u_ref[0, rows, :]
        cols = bc_ref[0, rows, :].T                      # [128, 8]
        ys = []
        for i in range(SUBLANES):
            dt = dt8[i:i + 1]
            s = jnp.exp(dt * a) * s \
                + (dt * u8[i:i + 1]) * cols[0:n, i:i + 1]
            ys.append(jnp.sum(s * cols[n:2 * n, i:i + 1], axis=0,
                              keepdims=True))
        y_ref[0, rows, :] = jnp.concatenate(ys, axis=0) + skip * u8
        return s

    s = jax.lax.fori_loop(0, tc // SUBLANES, tile, state[...])
    state[...] = s
    s_ref[0] = s


@functools.partial(jax.jit, static_argnames=("interpret",))
def s6_chunk_kernel(
    state: jax.Array,    # [B, N, D] f32, before the chunk
    u: jax.Array,        # [B, T, D] f32
    dt: jax.Array,       # [B, T, D] f32, 0 past a row's length
    a: jax.Array,        # [N, D] f32
    b: jax.Array,        # [B, T, N] f32
    c: jax.Array,        # [B, T, N] f32
    d_skip: jax.Array,   # [D] f32
    *,
    interpret: bool = False,
):
    """(y [B, T, D] f32, the state after the chunk)."""
    bsz, t, d = u.shape
    n = state.shape[1]
    blk = _first_dividing(d, CHANNEL_BLOCKS)
    tc = _first_dividing(t, TIME_BLOCKS)
    bc = jnp.pad(jnp.concatenate([b, c], axis=-1),
                 ((0, 0), (0, 0), (0, LANES - 2 * n)))
    tokens = pl.BlockSpec((1, tc, blk), lambda i, j, k: (i, k, j))
    rows = pl.BlockSpec((1, n, blk), lambda i, j, k: (i, 0, j))
    y, state = pl.pallas_call(
        _chunk_kernel,
        grid=(bsz, d // blk, t // tc),
        in_specs=[
            tokens, tokens,
            pl.BlockSpec((1, tc, LANES), lambda i, j, k: (i, k, 0)),
            pl.BlockSpec((n, blk), lambda i, j, k: (0, j)),
            pl.BlockSpec((1, blk), lambda i, j, k: (0, j)),
            rows,
        ],
        out_specs=[tokens, rows],
        out_shape=[jax.ShapeDtypeStruct((bsz, t, d), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM((n, blk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="s6_chunk_kernel",
    )(dt, u, bc, a, d_skip.reshape(1, d), state)
    return y, state
