"""Pallas TPU kernel: one decode step of a Mamba-2 state-space layer, in
place in the rows' carried state.

The decode loop carries its rows' scan state as one array ``[rows,
n_layers, H, P, N]`` float32 (models/granite_hybrid.py; N whole lanes, P
whole sublane tiles: nothing is packed). A layer's step has to read each
live row's ``(row, layer)`` slab once and write it once; as plain ``jnp``
(ops/ssd.py:ssd_step_at_jnp) it is two fusions, the update written into the
carry and the contraction with C reading it again, over EVERY row of the
bucket, live or not: 304 us a layer at 17 live rows of 32 where their bytes
take 87 (PERF.md section 6, PR 40).

The data movement is ops/pallas/live_blocks.py's (the carry aliased and
left in HBM, the live rows' blocks of ``HB`` heads ``[HB, P, N]`` through
``NUM_BUFS`` VMEM buffers as one sequence, ``FETCH_AHEAD`` in flight); what
is here is the operands' layout and a block's arithmetic.

Arithmetic, a block ``[HB, P, N]`` (a head is 8 vregs at 64 x 128):

  * ``S = a S + (dt x) B^T``, float32 on the vector unit, so the carried
    state's bits are the ``jnp`` form's. A head's decay ``a`` is ONE number:
    it arrives in SMEM (scalar prefetch, ``[rows, H]``) and multiplies the
    head as a scalar. ``B`` lies along the lanes as it arrives. ``dt x`` has
    to lie along SUBLANES (``S[p, :] += (dt x)[p] B``): it arrives in its
    natural layout, ``H * P`` along the lanes, a block's ``HB * P`` numbers as
    one tile of 8 rows x 128 lanes; the kernel transposes that tile once a
    block and a head's column is a static slice of it (one lane-broadcast a
    vreg of state, which ``[P, N]`` makes inherent).
  * ``y = S C`` contracts the LANES of the state, which the vector unit can
    only do through the cross-lane unit (7 rotate-and-adds a vreg). It is one
    matrix-unit product a block instead, ``[8, N] x [HB P, N]^T`` (the form
    of ``q k^T``; row 1 of the left operand is C, row 0 B, whose product is
    dropped) at ``Precision.HIGHEST``, float32 in and out: ``y`` comes out
    with (head, p) ALONG the lanes, its natural layout ``[rows, H P]``.
    Only ``y``'s sum changes its order and rounding against
    ops/ssd.py:ssd_token (7.9e-8 of the outputs' norm on the chip); at the
    default precision it would round the float32 state to bf16
    (benchmarks/chip/configs/granite-4.0-h-micro/check_reference.py, stage
    ``recurrence``, tells the two apart, and tests/test_granite_hybrid.py
    keeps a copy of it).

Why this form (v5e, 36-layer carry, ``chip_smoke.py --ssd``'s harness, us a
layer-step at 17 live rows of 32 | 32 of 32; my chip runs, PR 41; PERF.md
section 6). The first form (PR 40: the decay a column like ``dt x``, both
handed over transposed by XLA as ``[P, HB]`` padded to 128 lanes; ``S C`` as
8 lane reductions a head, each placed in its lane of a padded ``[P, 128]``
output by a select): 165.7 | 289.7, where the bytes take 87.1 | 163.9.
Taken apart:

    (a) the DMA sequence alone, buffers stored back untouched  127.8 | 223.8
    (b) update only                                            129.3 | 225.4
    (c) contraction only (reductions + selects)                128.1 | 224.2
    (d) the decay a scalar from SMEM, the rest as it was       147.9 | 265.0
        update only, the decay a scalar                        122.5 | 217.9
    (e) blocks of 8 / 16 / 32 heads, all of it        196.1 / 165.7 / 156.3
        the DMA sequence alone                        142.7 / 127.8 / 122.7
    2 / 3 / 4 buffers, all of it                      216.2 / 165.7 / 165.5
    contraction only, on the matrix unit (HIGHEST)             128.0 | 222.9
    a joint butterfly of a head's 8 vregs (18 rotates), alone  159.9 | 280.1

Either half of the arithmetic hid under the copies and the two together did
not: the vector and cross-lane work of a block was about 1.5 times its DMA
time. The matrix-unit product hides whole; the butterfly does not. And (a)
is not the bytes' time: the copies themselves run at 6.4 us a live row (655
GB/s of 819, read and write streams together), and a call costs 13-19 us
beside them, most of it the PADDED small operands: with blocks of 8 / 16 /
32 heads ``xa`` was 16 / 8 / 4 MB and the padded output 8 / 4 / 2 MB a
call, written by XLA or the kernel and read back, for every row of the
bucket, about 1 us a MB. So the re-formed operands are small by layout
(``dt x`` 0.5 MB, the output 0.13 MB, the decays 8 KB at 32 rows; no XLA
transpose on either side). With them:

    the DMA sequence alone, blocks of 16 / 32 / 64    122.4 / 122.9 / 121.3
      2 ahead in 4 / 5 buffers, 3 ahead in 6          121.7 / 122.3 / 122.0
      a block's copy in 2 / 4 parts, own semaphores   122.9 / 122.3
    all of it: 1 ahead in 3 buffers, 16 / 32 / 64     130.9 / 124.6 / 122.9
      blocks of 16, 2 ahead in 4 / 5 buffers          123.6 / 123.1

No shape of the DMA sequence moves its own time (the slope stays 6.4 us a
live row: block size, depth and split copies all read 121-123), so what is
left above the bytes is the chip's rate for a read and a write stream at
once and some 10 us a call (the pipeline's own copies, the first block in
and the last out, XLA's small fusions around the call). With one block
ahead a block's arithmetic (about a block's DMA time still) shows by 8 us;
two ahead in four buffers hide it at the smallest VMEM (2 MB) and the
shortest unrolled loop: 123.6 | 219.4 in the series' harness; as shipped,
``chip_smoke.py --ssd`` reads 120.7 | 216.3 and 111.0 at 16 of 16 (72 | 76 |
74% of the bytes' time; 164.9 | 290.3 | 147.8 before).

Decode only (one token a row). The chunkwise prefill form stays in XLA
(ops/ssd.py:ssd_chunk).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from production_stack_tpu.ops.pallas.live_blocks import (
    OPERAND_BYTES,
    live_blocks,
    step_call,
)

NUM_BUFS = 4             # two blocks coming in, one computed, one going out
FETCH_AHEAD = 2          # blocks in flight towards the one computed
HEADS_PER_BLOCK = 16     # unrolled in the kernel; 512 KB at 64 x 128
LANES, SUBLANES = 128, 8
TILE = SUBLANES * LANES


def supports_step_kernel(shape) -> bool:
    """Whether a layer's state ``(H, P, N)`` fits the kernel: the state axis
    whole lanes, the channel axis whole sublanes and a whole fraction of a
    row of lanes (a head's ``dt x`` is a static slice of one transposed
    column), heads in whole blocks."""
    h, p, n = shape
    return n % LANES == 0 and p % SUBLANES == 0 and LANES % p == 0 \
        and h % min(h, HEADS_PER_BLOCK) == 0


def _step_kernel(
    # scalar prefetch
    at_ref,        # SMEM [1] int32: which layer of the carry
    live_ref,      # SMEM [B] int32: rows that take a token
    decay_ref,     # SMEM [B, H] f32: a head's decay, one number
    # inputs
    dtx_ref,       # VMEM [RB, H/HB, R, 128] f32: a block's dt x, (head, p)
                   # along the lanes of R rows (8 at 16 heads x 64)
    bc_ref,        # VMEM [RB, 8, N] f32: B in row 0, C in row 1
    s_in,          # HBM  [B, NL, H, P, N] f32: the carry
    # outputs
    o_ref,         # VMEM [RB, H/HB, HB P] f32: y, (head, p) along the lanes
    s_out,         # HBM: the carry again (aliased to s_in)
    # scratch: live_blocks', of which the kernel touches the buffers
    buf,           # VMEM [NUM_BUFS, HB, P, N] f32
    *scratch,
):
    _, hb, p, n_state = buf.shape
    run = live_blocks(at_ref, live_ref, s_in, s_out, buf, *scratch,
                      rows=o_ref.shape[0], fetch_ahead=FETCH_AHEAD)
    o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    def compute(n, row, j, slot, r):
        # The block's dt x, (head, p) along the lanes of its rows, as
        # columns: head i's p numbers run down column i P // 128 from row
        # i P % 128.
        cols = dtx_ref[r, j].T                             # [128, R]
        b_row = bc_ref[r, pl.ds(0, 1), :]                  # [1, N]
        for i in range(hb):
            q, l0 = divmod(i * p, LANES)
            buf[slot, i] = buf[slot, i] * decay_ref[row, j * hb + i] \
                + cols[l0:l0 + p, q:q + 1] * b_row
        # y = S C for the block's heads at once, on the matrix unit: the
        # lanes of [8, N] (row 1: C) against the lanes of [HB P, N].
        y = jax.lax.dot_general(
            bc_ref[r], buf[slot].reshape(hb * p, n_state),
            (((1,), (1,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
        o_ref[r, pl.ds(j, 1), :] = y[1:2]

    run(compute)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_step_in_place(
    carry: jax.Array,    # [B, NL, H, P, N] f32: the rows' state
    at: jax.Array,       # [] int32: the layer of the carry to step
    x: jax.Array,        # [B, H, P] f32, after the convolution
    b: jax.Array,        # [B, N] f32
    c: jax.Array,        # [B, N]
    dt: jax.Array,       # [B, H] f32, after softplus
    da: jax.Array,       # [B, H] f32 log-decay
    d_skip: jax.Array,   # [H]
    live: jax.Array,     # [B] bool
    *,
    interpret: bool = False,
):
    """One token of the scan for the live rows of layer ``at``: (y [B, H,
    P], the carry with those rows' slabs updated and every other byte as it
    was). A row that is not live gets zeros."""
    bsz, _, h, p, n = carry.shape
    hb = min(h, HEADS_PER_BLOCK)
    nb = h // hb
    per = hb * p
    # A block's dt x as it lies, (head, p) along the lanes, in whole tiles
    # of 8 rows (one, and no padding, at 16 heads x 64).
    dtx = (dt[..., None] * x).reshape(bsz, nb, per)
    dtx = jnp.pad(dtx, ((0, 0), (0, 0), (0, -per % TILE))).reshape(
        bsz, nb, -1, LANES)
    bc = jnp.pad(jnp.stack([b, c], axis=1),
                 ((0, 0), (0, SUBLANES - 2), (0, 0)))
    # A row's operands in VMEM: dt x, B | C and y (nb rows of a whole tile).
    row_bytes = (dtx[0].size + SUBLANES * n
                 + -(-nb // SUBLANES) * SUBLANES * per) * 4
    o, carry = step_call(
        _step_kernel,
        (jnp.asarray(at, jnp.int32).reshape(1), live.astype(jnp.int32),
         jnp.exp(da)),
        (dtx, bc), carry, out_row=(nb, per), heads_per_block=hb,
        num_bufs=NUM_BUFS, row_bytes=row_bytes, operand_bytes=OPERAND_BYTES,
        name="ssd_step_in_place", interpret=interpret)
    y = o.reshape(bsz, h, p) + d_skip.astype(jnp.float32)[None, :, None] * x
    return jnp.where(live[:, None, None], y, 0.0), carry
