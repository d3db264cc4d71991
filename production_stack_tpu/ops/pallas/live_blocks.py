"""How the live rows' state blocks pass through VMEM in place: the data
movement of the decode-step kernels over a carried recurrent state
(ops/pallas/gated_delta.py:gdn_step_in_place, ops/pallas/ssd.py:
ssd_step_in_place), written once.

The decode loop carries its rows' state as one array ``[rows, layers,
heads, ...]`` float32, and a layer's step has to read each live row's
``(row, layer)`` slab once and write it once:

  * The carry stays in HBM and is ALIASED to the kernel's output: nothing
    of its size is allocated, copied, sliced out or put back. Blocks of
    ``HB`` heads ``[HB, ...]`` of a live row's slab (contiguous) are copied
    into one of ``num_bufs`` VMEM buffers, updated there by the kernel's own
    ``compute`` and copied back to where they came from.
  * The call's live blocks form ONE sequence, row after row: while block n
    is computed, the ``fetch_ahead`` blocks behind it (the same row's next
    ones or the next LIVE row's first) are in flight into the next buffers
    and the blocks before it on their way out. A buffer is fetched into
    once the write-back of the block ``num_bufs`` before has landed.
    Buffers, semaphores and the compacted list of live rows are scratch,
    which outlives a program; the grid axis (row chunks, one chunk where the
    per-row operands fit VMEM) is sequential and hands its buffers on.
  * A row that is not live moves no byte of state: it is not in the list.

``num_bufs`` and ``fetch_ahead`` are each kernel's own constants, measured
on a v5e (3 and 1, PERF.md §6, PR 32; 4 and 2, PR 41), not options.
"""

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

OPERAND_BYTES = 6 << 20  # VMEM the per-row operands of one program may take,
                         # both copies Pallas keeps of a block: 32 rows of
                         # 30 gated-delta heads are one program, 64 rows two
                         # (what a kernel file hands ``step_call``)


def _rows_per_program(b: int, row_bytes: int, operand_bytes: int) -> int:
    return max(n for n in range(1, b + 1)
               if b % n == 0 and (n == 1 or 2 * n * row_bytes
                                  <= operand_bytes))


def live_blocks(
    at_ref,        # SMEM [1] int32: which layer of the carry
    live_ref,      # SMEM [B] int32: rows that take a token
    s_in,          # HBM  [B, NL, H, ...] f32: the carry
    s_out,         # HBM: the carry again (aliased to s_in)
    # scratch (outlives a program)
    buf,           # VMEM [num_bufs, HB, ...] f32
    sem_in,        # DMA (num_bufs,)
    sem_out,       # DMA (num_bufs,)
    rows_ref,      # SMEM [B] int32: the live rows, in order
    count_ref,     # SMEM [1] int32: how many
    *,
    rows: int,         # rows a program holds the operands of
    fetch_ahead: int,  # blocks in flight towards the one computed
):
    """Inside a step kernel: lists the live rows (program 0) and returns
    ``run(compute)``, which takes this program's live blocks through the
    buffers in turn. ``compute(n, row, j, slot, r)`` updates ``buf[slot]``,
    block ``j`` of ``row``'s slab and the call's n-th, where it lies; ``r``
    is the row's index among the program's own."""
    pid = pl.program_id(0)
    num_rows = live_ref.shape[0]
    num_bufs, hb = buf.shape[:2]
    nb = s_in.shape[2] // hb             # blocks a row
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
    lo = live_below(pid * rows)          # live rows before this program's
    hi = live_below(pid * rows + rows)   # and up to its last

    def block(n):
        # (row, block of heads) of the call's n-th live block.
        li = n // nb
        return rows_ref[jnp.minimum(li, num_rows - 1)], n - li * nb

    def fetch(n):
        row, j = block(n)
        slot = jax.lax.rem(n, num_bufs)
        return pltpu.make_async_copy(
            s_in.at[row, at, pl.ds(j * hb, hb)], buf.at[slot],
            sem_in.at[slot])

    def store(n):
        row, j = block(n)
        slot = jax.lax.rem(n, num_bufs)
        return pltpu.make_async_copy(
            buf.at[slot], s_out.at[row, at, pl.ds(j * hb, hb)],
            sem_out.at[slot])

    def run(compute):
        def step(n, carry):
            row, j = block(n)
            slot = jax.lax.rem(n, num_bufs)
            r = row - pid * rows

            @pl.when(n == 0)
            def _():
                for first in range(fetch_ahead):
                    @pl.when(first < total)
                    def _():
                        fetch(first).start()

            # One more block goes in flight now, into the buffer that the
            # block num_bufs before it left: whose write-back has to have
            # landed first.
            @pl.when(n + fetch_ahead < total)
            def _():
                @pl.when(n + fetch_ahead >= num_bufs)
                def _():
                    store(n + fetch_ahead - num_bufs).wait()
                fetch(n + fetch_ahead).start()

            fetch(n).wait()
            compute(n, row, j, slot, r)
            store(n).start()
            return carry

        jax.lax.fori_loop(lo * nb, hi * nb, step, 0)

        # The call's last write-backs: those no later block waited for.
        @pl.when(pid == pl.num_programs(0) - 1)
        def _():
            for back in range(num_bufs, 0, -1):
                @pl.when(total >= back)
                def _():
                    store(total - back).wait()

    return run


def step_call(kernel, scalars, operands, carry, *, out_row, heads_per_block,
              num_bufs, row_bytes, operand_bytes, name, interpret):
    """The ``pallas_call`` of a step kernel over ``carry`` [B, NL, H, ...]:
    ``kernel(*scalar refs, *operand refs, s_in, o_ref, s_out, buf, sem_in,
    sem_out, rows_ref, count_ref)``, of which ``live_blocks`` takes the
    first two scalars (the layer [1] and the rows' liveness [B], int32) and
    everything from ``s_in`` on but ``o_ref``. ``operands`` [B, ...] and the
    output [B, *out_row] f32 reach a program as blocks of whole rows, as
    many as ``row_bytes`` a row lets fit ``operand_bytes``; the carry is
    left in HBM and aliased to the second output: (o, carry)."""
    b = carry.shape[0]
    rb = _rows_per_program(b, row_bytes, operand_bytes)

    def rows(*shape):
        return pl.BlockSpec((rb, *shape),
                            lambda i, *_: (i,) + (0,) * len(shape),
                            memory_space=pltpu.VMEM)

    return pl.pallas_call(
        kernel,
        out_shape=[jax.ShapeDtypeStruct((b, *out_row), jnp.float32),
                   jax.ShapeDtypeStruct(carry.shape, carry.dtype)],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(b // rb,),
            in_specs=[
                *(rows(*x.shape[1:]) for x in operands),
                pl.BlockSpec(memory_space=pl.ANY),   # the carry stays in HBM
            ],
            out_specs=[rows(*out_row), pl.BlockSpec(memory_space=pl.ANY)],
            scratch_shapes=[
                pltpu.VMEM((num_bufs, heads_per_block, *carry.shape[3:]),
                           jnp.float32),
                pltpu.SemaphoreType.DMA((num_bufs,)),
                pltpu.SemaphoreType.DMA((num_bufs,)),
                pltpu.SMEM((b,), jnp.int32),
                pltpu.SMEM((1,), jnp.int32),
            ],
        ),
        # scalars, operands, carry -> (o, carry): in place.
        input_output_aliases={len(scalars) + len(operands): 1},
        # Programs run in order: each hands its buffers to the next.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=name,
    )(*scalars, *operands, carry)
