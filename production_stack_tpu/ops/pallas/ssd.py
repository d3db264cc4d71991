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

The machinery is ops/pallas/gated_delta.py's, whose sibling this is: the
carry stays in HBM and is ALIASED to the kernel's output; blocks of ``HB``
heads ``[HB, P, N]`` of a live row's slab (contiguous) go through
``NUM_BUFS`` VMEM buffers as ONE sequence over the call's live rows, the
next block in flight and the last on its way out while one is computed; a
row that is not live moves no byte and gets zeros; the grid axis (row
chunks) is sequential and hands its buffers on.

Arithmetic: float32 on the vector unit, a head ``[P, N]`` (8 vregs at 64 x
128) at a time: ``S = a S + (dt x) B^T``; ``y = S C``. No matrix-unit
product, so nothing is rounded; only the order of the sum over N differs
from ops/ssd.py:ssd_token. ``B`` and ``C`` lie along the lanes as they
arrive; ``dt x`` has to lie along SUBLANES (``S[p, :] += (dt x)[p] B``) and
a head's decay is one number: XLA hands a block's ``dt x`` over transposed,
``[P, HB]`` padded to whole lanes, with the block's decays beneath it, a
head's down its column, and the kernel takes a head's columns by a static
slice. ``y`` leaves the same way, a column a head.

Decode only (one token a row). The chunkwise prefill form stays in XLA
(ops/ssd.py:ssd_chunk).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NUM_BUFS = 3             # one block coming in, one computed, one going out
HEADS_PER_BLOCK = 16     # unrolled in the kernel; 512 KB at 64 x 128
OPERAND_BYTES = 6 << 20  # VMEM the per-row operands of one program may take,
                         # both copies Pallas keeps of a block
LANES, SUBLANES = 128, 8


def supports_step_kernel(shape) -> bool:
    """Whether a layer's state ``(H, P, N)`` fits the kernel: the state axis
    whole lanes, the channel axis whole sublanes, heads in whole blocks."""
    h, p, n = shape
    return n % LANES == 0 and p % SUBLANES == 0 \
        and h % min(h, HEADS_PER_BLOCK) == 0


def _rows_per_program(b: int, row_bytes: int) -> int:
    return max(n for n in range(1, b + 1)
               if b % n == 0 and (n == 1 or 2 * n * row_bytes
                                  <= OPERAND_BYTES))


def _step_kernel(
    # scalar prefetch
    at_ref,        # SMEM [1] int32: which layer of the carry
    live_ref,      # SMEM [B] int32: rows that take a token
    # inputs
    xa_ref,        # VMEM [RB, H/HB, 2 P, 128] f32: a block's (dt x)^T in
                   # rows :P (a column a head), its decays down rows P:
    bc_ref,        # VMEM [RB, 8, N] f32: B in row 0, C in row 1
    s_in,          # HBM  [B, NL, H, P, N] f32: the carry
    # outputs
    o_ref,         # VMEM [RB, H/HB, P, 128] f32: y, a column a head
    s_out,         # HBM: the carry again (aliased to s_in)
    # scratch (outlives a program)
    buf,           # VMEM [NUM_BUFS, HB, P, N] f32
    sem_in,        # DMA (NUM_BUFS,)
    sem_out,       # DMA (NUM_BUFS,)
    rows_ref,      # SMEM [B] int32: the live rows, in order
    count_ref,     # SMEM [1] int32: how many
):
    pid = pl.program_id(0)
    num_rows = live_ref.shape[0]
    rb, nb, p, _ = o_ref.shape
    hb = buf.shape[1]
    at = at_ref[0]

    @pl.when(pid == 0)
    def _():
        def add(b, n):
            @pl.when(live_ref[b] != 0)
            def _():
                rows_ref[n] = b
            return n + (live_ref[b] != 0).astype(jnp.int32)

        count_ref[0] = jax.lax.fori_loop(0, num_rows, add, jnp.int32(0))

    def live_below(row):
        return jax.lax.fori_loop(
            0, row, lambda b, n: n + (live_ref[b] != 0).astype(jnp.int32),
            jnp.int32(0))

    total = count_ref[0] * nb            # live blocks of the call
    lo = live_below(pid * rb)            # live rows before this program's
    hi = live_below(pid * rb + rb)       # and up to its last

    def block(n):
        # (row, block of heads) of the call's n-th live block.
        li = n // nb
        return rows_ref[jnp.minimum(li, num_rows - 1)], n - li * nb

    def fetch(n):
        row, j = block(n)
        slot = jax.lax.rem(n, NUM_BUFS)
        return pltpu.make_async_copy(
            s_in.at[row, at, pl.ds(j * hb, hb)], buf.at[slot],
            sem_in.at[slot])

    def store(n):
        row, j = block(n)
        slot = jax.lax.rem(n, NUM_BUFS)
        return pltpu.make_async_copy(
            buf.at[slot], s_out.at[row, at, pl.ds(j * hb, hb)],
            sem_out.at[slot])

    lane = jax.lax.broadcasted_iota(jnp.int32, (p, LANES), 1)
    o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    def step(n, carry):
        row, j = block(n)
        slot = jax.lax.rem(n, NUM_BUFS)
        r = row - pid * rb

        @pl.when(n == 0)
        def _():
            fetch(n).start()

        # The next block goes in flight now, into the buffer that the
        # block NUM_BUFS before it left: whose write-back has to have
        # landed first.
        @pl.when(n + 1 < total)
        def _():
            @pl.when(n + 1 >= NUM_BUFS)
            def _():
                store(n + 1 - NUM_BUFS).wait()
            fetch(n + 1).start()

        fetch(n).wait()
        xa = xa_ref[r, j]                                  # [2 P, 128]
        b_row = bc_ref[r, pl.ds(0, 1), :]                  # [1, N]
        c_row = bc_ref[r, pl.ds(1, 1), :]
        out = jnp.zeros((p, LANES), jnp.float32)
        for i in range(hb):
            s = buf[slot, i] * xa[p:, i:i + 1] + xa[:p, i:i + 1] * b_row
            buf[slot, i] = s
            out = jnp.where(lane == i,
                            jnp.sum(s * c_row, axis=1, keepdims=True), out)
        o_ref[r, j] = out
        store(n).start()
        return carry

    jax.lax.fori_loop(lo * nb, hi * nb, step, 0)

    # The call's last write-backs: those no later block waited for.
    @pl.when(pid == pl.num_programs(0) - 1)
    def _():
        for back in range(NUM_BUFS, 0, -1):
            @pl.when(total >= back)
            def _():
                store(total - back).wait()


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
    # A block's dt x transposed, [P, HB] on whole lanes, and beneath it the
    # block's decays, a head's down its column (a [1, 1] times [P, N] would
    # broadcast along both axes at once, which Mosaic does not lower).
    dtx = (dt[..., None] * x).reshape(bsz, nb, hb, p).transpose(0, 1, 3, 2)
    decay = jnp.broadcast_to(jnp.exp(da).reshape(bsz, nb, 1, hb), dtx.shape)
    xa = jnp.pad(jnp.concatenate([dtx, decay], axis=2),
                 ((0, 0), (0, 0), (0, 0), (0, LANES - hb)))
    bc = jnp.pad(jnp.stack([b, c], axis=1),
                 ((0, 0), (0, SUBLANES - 2), (0, 0)))
    rb = _rows_per_program(
        bsz, (nb * 3 * p * LANES + SUBLANES * n) * 4)

    def rows(*shape):
        return pl.BlockSpec((rb, *shape),
                            lambda i, *_: (i,) + (0,) * len(shape),
                            memory_space=pltpu.VMEM)

    o, carry = pl.pallas_call(
        _step_kernel,
        out_shape=[jax.ShapeDtypeStruct((bsz, nb, p, LANES), jnp.float32),
                   jax.ShapeDtypeStruct(carry.shape, carry.dtype)],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bsz // rb,),
            in_specs=[
                rows(nb, 2 * p, LANES),
                rows(SUBLANES, n),
                pl.BlockSpec(memory_space=pl.ANY),   # the carry stays in HBM
            ],
            out_specs=[rows(nb, p, LANES),
                       pl.BlockSpec(memory_space=pl.ANY)],
            scratch_shapes=[
                pltpu.VMEM((NUM_BUFS, hb, p, n), jnp.float32),
                pltpu.SemaphoreType.DMA((NUM_BUFS,)),
                pltpu.SemaphoreType.DMA((NUM_BUFS,)),
                pltpu.SMEM((bsz,), jnp.int32),
                pltpu.SMEM((1,), jnp.int32),
            ],
        ),
        # at, live, xa, bc, carry -> (o, carry): in place.
        input_output_aliases={4: 1},
        # Programs run in order: each hands its buffers to the next.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="ssd_step_in_place",
    )(
        jnp.asarray(at, jnp.int32).reshape(1), live.astype(jnp.int32),
        xa, bc, carry,
    )
    y = o[..., :hb].transpose(0, 1, 3, 2).reshape(bsz, h, p)
    y = y + d_skip.astype(jnp.float32)[None, :, None] * x
    return jnp.where(live[:, None, None], y, 0.0), carry
