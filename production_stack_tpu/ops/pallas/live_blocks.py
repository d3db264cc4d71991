"""How the live rows' state blocks pass through VMEM in place: the data
movement of the decode-step kernels over a carried per-sequence state
(ops/pallas/gated_delta.py:gdn_step_in_place, ops/pallas/ssd.py:
ssd_step_in_place, ops/pallas/window_ring.py:ring_step_in_place), written
once.

The decode loop carries its rows' state as arrays ``[rows, layers, heads,
...]`` (one, or several that are stepped together: a window ring's keys and
its values), and a layer's step has to read each live row's ``(row, layer)``
slab once and write back what it changed:

  * A carry stays in HBM and is ALIASED to the kernel's output: nothing
    of its size is allocated, copied, sliced out or put back. Blocks of
    ``HB`` heads ``[HB, ...]`` of a live row's slab (contiguous) are copied
    into one of ``num_bufs`` VMEM buffers of the carry's dtype, updated
    there by the kernel's own ``compute`` and copied back to where they
    came from: the whole block, or the part of it ``written`` names (a
    ring's step changes one row of a head's 128: the tile that holds it
    goes back and nothing else).
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


def _each(x):
    return x if isinstance(x, (tuple, list)) else (x,)


def live_blocks(
    at_ref,        # SMEM [1] int32: which layer of the carry
    live_ref,      # SMEM [B] int32: rows that take a token
    s_in,          # HBM  [B, NL, H, ...]: the carry (or a tuple of carries)
    s_out,         # HBM: the carry again (aliased to s_in)
    # scratch (outlives a program), one of each a carry
    buf,           # VMEM [num_bufs, HB, ...] of the carry's dtype
    sem_in,        # DMA (num_bufs,)
    sem_out,       # DMA (num_bufs,)
    rows_ref,      # SMEM [B] int32: the live rows, in order
    count_ref,     # SMEM [1] int32: how many
    *,
    rows: int,         # rows a program holds the operands of
    fetch_ahead: int,  # blocks in flight towards the one computed
    written=None,      # row -> the part of a block that goes back
):
    """Inside a step kernel: lists the live rows (program 0) and returns
    ``run(compute)``, which takes this program's live blocks through the
    buffers in turn. ``compute(n, row, j, slot, r)`` updates ``buf[slot]``
    (of every carry), block ``j`` of ``row``'s slab and the call's n-th,
    where it lies; ``r`` is the row's index among the program's own.
    ``written(row)``: indices into a block behind its heads' axis, the
    same for every carry (``(pl.ds(first, size),)``: those rows of every
    head); None: the whole block goes back."""
    s_ins, s_outs, bufs = _each(s_in), _each(s_out), _each(buf)
    sems_in, sems_out = _each(sem_in), _each(sem_out)
    pid = pl.program_id(0)
    num_rows = live_ref.shape[0]
    num_bufs, hb = bufs[0].shape[:2]
    nb = s_ins[0].shape[2] // hb         # blocks a row
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

    class _Copies(tuple):
        # A block's copies, one a carry, started and awaited together.
        def start(self):
            for c in self:
                c.start()

        def wait(self):
            for c in self:
                c.wait()

    def fetch(n):
        row, j = block(n)
        slot = jax.lax.rem(n, num_bufs)
        return _Copies(
            pltpu.make_async_copy(
                s.at[row, at, pl.ds(j * hb, hb)], b.at[slot], sem.at[slot])
            for s, b, sem in zip(s_ins, bufs, sems_in))

    def store(n):
        row, j = block(n)
        slot = jax.lax.rem(n, num_bufs)
        part = () if written is None else written(row)
        at_buf = (slot, slice(None), *part) if part else (slot,)
        return _Copies(
            pltpu.make_async_copy(
                b.at[at_buf], s.at[(row, at, pl.ds(j * hb, hb), *part)],
                sem.at[slot])
            for s, b, sem in zip(s_outs, bufs, sems_out))

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
              num_bufs, row_bytes, operand_bytes, name, interpret,
              out_dtype=jnp.float32, scratch=()):
    """The ``pallas_call`` of a step kernel over ``carry`` [B, NL, H, ...]
    (or a tuple of such, stepped together): ``kernel(*scalar refs, *operand
    refs, *s_in, o_ref, *s_out, *buf, *sem_in, *sem_out, rows_ref,
    count_ref, *scratch refs)``, of which ``live_blocks`` takes the first
    two scalars (the layer [1] and the rows' liveness [B], int32) and
    everything from ``s_in`` to ``count_ref`` but ``o_ref`` (``scratch``:
    further scratch shapes of the kernel's own). ``operands`` [B, ...] and
    the output [B, *out_row] of ``out_dtype`` reach a program as blocks of
    whole rows, as many as ``row_bytes`` a row lets fit ``operand_bytes``; a
    carry is left in HBM and aliased to its output: (o, *carries)."""
    carries = _each(carry)
    b = carries[0].shape[0]
    rb = _rows_per_program(b, row_bytes, operand_bytes)

    def rows(*shape):
        return pl.BlockSpec((rb, *shape),
                            lambda i, *_: (i,) + (0,) * len(shape),
                            memory_space=pltpu.VMEM)

    return pl.pallas_call(
        kernel,
        out_shape=[jax.ShapeDtypeStruct((b, *out_row), out_dtype),
                   *(jax.ShapeDtypeStruct(c.shape, c.dtype)
                     for c in carries)],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(b // rb,),
            in_specs=[
                *(rows(*x.shape[1:]) for x in operands),
                # a carry stays in HBM
                *(pl.BlockSpec(memory_space=pl.ANY) for _ in carries),
            ],
            out_specs=[rows(*out_row),
                       *(pl.BlockSpec(memory_space=pl.ANY) for _ in carries)],
            scratch_shapes=[
                *(pltpu.VMEM((num_bufs, heads_per_block, *c.shape[3:]),
                             c.dtype) for c in carries),
                *(pltpu.SemaphoreType.DMA((num_bufs,))
                  for _ in range(2 * len(carries))),
                pltpu.SMEM((b,), jnp.int32),
                pltpu.SMEM((1,), jnp.int32),
                *scratch,
            ],
        ),
        # scalars, operands, carries -> (o, *carries): in place.
        input_output_aliases={
            len(scalars) + len(operands) + i: 1 + i
            for i in range(len(carries))},
        # Programs run in order: each hands its buffers to the next.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=name,
    )(*scalars, *operands, *carries)
