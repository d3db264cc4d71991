"""Pallas TPU kernel: one decode step of a Gated DeltaNet layer, in place in
the rows' carried state.

The decode loop carries its rows' recurrent state as one array
``[rows, n_linear, H/P, dk, P*dv]`` float32 (models/olmo_hybrid.py; the
packed layout of ops/gated_delta.py). A layer's step has to read each live
row's ``(row, layer)`` slab once and write it once; as plain ``jnp`` it
was three passes over every row of the bucket (decay and ``S^T k``; the
rank-one update, fused into the carry's ``dynamic_update_slice``;
``S^T q``), live or not: four times the bytes (PERF.md §6, PR 32).

  * The carry stays in HBM and is ALIASED to the kernel's output: nothing
    of its size is allocated, copied, sliced out or put back. The kernel
    DMAs blocks of ``HB`` packed heads ``[HB, dk, P*dv]`` of a live row's
    slab (contiguous: a slab is ``[H/P, dk, P*dv]``) into one of
    ``NUM_BUFS`` VMEM buffers, updates the block there and DMAs it back to
    where it came from.
  * The call's live blocks form ONE sequence, row after row: while block n
    is computed, block n + 1 (the same row's next one or the next LIVE
    row's first) is in flight into the next buffer and block n - 1 on its
    way out of the one before. Buffers, semaphores and the compacted list
    of live rows are scratch, which outlives a program; the grid axis (row
    chunks, one chunk where the small operands fit VMEM) is sequential.
  * A row that is not live moves no byte of state: it is not in the list.
    Its ``o`` is zeros.
  * Arithmetic: float32 on the vector unit, a packed head
    ``[dk, P*dv]`` (36 vregs at 96 x 384) at a time: ``S *= exp(g)``;
    ``kv = S^T k``; ``u = (v - kv) beta``; ``S += k u^T``; ``o = S^T q``.
    No matrix-unit product, so nothing is rounded; only the order of the
    sums over ``dk`` differs from ops/gated_delta.py:delta_step. ``k`` and
    ``q`` have to lie along SUBLANES (``S[d, :] * k[d]``), and a head's
    ``g`` and ``beta`` are two numbers: a packed head's small operands
    arrive as 2P + 1 neighbouring rows of 128 lanes (``k`` of its heads,
    ``q`` of its heads, one row ``g | beta``); the kernel transposes the
    tile that starts at the packed head's rows, broadcasts the columns
    over the lanes and spreads them to the packed heads' lanes by a select
    on the lane's number. (The gates ride in that tile and not in SMEM on
    purpose: as operands of their own they pinned the layout of the
    projections that make them, and the compiler re-laid ``lin_a`` and
    ``lin_b`` out every dispatch, 48 MB of temporaries.)

Decode only (one token a row). The chunkwise prefill form stays in XLA
(ops/gated_delta.py:gdn_chunk).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# On a v5e, 20 rows of [15, 96, 384] a layer-step (PERF.md §6, PR 32): 2 / 3 /
# 4 buffers 188 / 149 / 150 us; blocks of 1 / 3 / 5 / 15 packed heads 222 /
# 162 / 149 / 145 us (about 0.3 us a block of fixed cost).
NUM_BUFS = 3             # one block coming in, one computed, one going out
BLOCK_BYTES = 1 << 20    # largest state block (a buffer): 5 packed heads of
                         # 96 x 384, so a 15-head slab is three blocks
OPERAND_BYTES = 6 << 20  # VMEM the per-row operands of one program may take,
                         # both copies Pallas keeps of a block: 32 rows of
                         # 30 heads are one program, 64 rows two
LANES, SUBLANES = 128, 8


def supports_step_kernel(num_heads: int, packed) -> bool:
    """Whether the packed state ``(H/P, dk, P*dv)`` fits the kernel: the
    value axis whole lanes, the key axis whole sublanes and at most one
    tile of lanes wide (k and q are transposed a tile at a time), a packed
    head's k and q rows within one 8-row tile, and a block within a
    buffer."""
    hp, dk, pdv = packed
    p = num_heads // hp
    return (hp * p == num_heads and pdv % LANES == 0 and dk % SUBLANES == 0
            and dk <= LANES and 2 * p <= LANES
            and dk * pdv * 4 <= BLOCK_BYTES)


def _tile_rows(pack: int) -> int:
    """Rows of kq the kernel transposes for a packed head: whole sublane
    tiles over its 2P + 1."""
    return -(-(2 * pack + 1) // SUBLANES) * SUBLANES


def _heads_per_block(hp: int, dk: int, pdv: int) -> int:
    return max(n for n in range(1, hp + 1)
               if hp % n == 0 and n * dk * pdv * 4 <= BLOCK_BYTES)


def _rows_per_program(b: int, row_bytes: int) -> int:
    return max(n for n in range(1, b + 1)
               if b % n == 0 and (n == 1 or 2 * n * row_bytes
                                  <= OPERAND_BYTES))


def _step_kernel(
    # scalar prefetch
    at_ref,        # SMEM [1] int32: which layer of the carry
    live_ref,      # SMEM [B] int32: rows that take a token
    # inputs
    kq_ref,        # VMEM [RB, R, 128] f32: 2P + 1 rows a packed head (its k
                   # heads, its q heads, its gates g | beta)
    v_ref,         # VMEM [RB, H/P, P*dv] f32
    s_in,          # HBM  [B, NL, H/P, dk, P*dv] f32: the carry
    # outputs
    o_ref,         # VMEM [RB, H/P, P*dv] f32
    s_out,         # HBM: the carry again (aliased to s_in)
    # scratch (outlives a program)
    buf,           # VMEM [NUM_BUFS, HB, dk, P*dv] f32
    sem_in,        # DMA (NUM_BUFS,)
    sem_out,       # DMA (NUM_BUFS,)
    rows_ref,      # SMEM [B] int32: the live rows, in order
    count_ref,     # SMEM [1] int32: how many
    *,
    pack: int,
):
    pid = pl.program_id(0)
    num_rows = live_ref.shape[0]
    rb = o_ref.shape[0]
    _, hb, dk, pdv = buf.shape
    dv = pdv // pack
    nb = s_in.shape[2] // hb             # blocks a row
    tile = _tile_rows(pack)
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
        # (row, first packed head) of the call's n-th live block.
        li = n // nb
        return (rows_ref[jnp.minimum(li, num_rows - 1)], (n - li * nb) * hb)

    def fetch(n):
        row, h0 = block(n)
        slot = jax.lax.rem(n, NUM_BUFS)
        return pltpu.make_async_copy(
            s_in.at[row, at, pl.ds(h0, hb)], buf.at[slot], sem_in.at[slot])

    def store(n):
        row, h0 = block(n)
        slot = jax.lax.rem(n, NUM_BUFS)
        return pltpu.make_async_copy(
            buf.at[slot], s_out.at[row, at, pl.ds(h0, hb)], sem_out.at[slot])

    lane_head = jax.lax.broadcasted_iota(jnp.int32, (1, pdv), 1) // dv

    def spread(parts, rows):
        # parts[i] (a scalar, or [rows, 1]) over head i's lanes of the
        # packed value axis: ops/gated_delta.py:_spread, on the chip.
        out = jnp.broadcast_to(parts[0], (rows, pdv))
        for i in range(1, pack):
            out = jnp.where(lane_head == i, parts[i], out)
        return out

    o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    def step(n, carry):
        row, h0 = block(n)
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

        def head(h, carry):
            hp = h0 + h
            # This packed head's rows of kq (its k then q vectors, dk along
            # the lanes; then g | beta of its heads), transposed: k and q
            # are columns, the gates the top of one more.
            cols = kq_ref[r, pl.ds(hp * (2 * pack + 1), tile), :].T
            kx = spread([cols[:dk, i:i + 1] for i in range(pack)], dk)
            qx = spread([cols[:dk, pack + i:pack + i + 1]
                         for i in range(pack)], dk)
            gates = cols[:, 2 * pack:2 * pack + 1]
            g = spread([gates[i:i + 1] for i in range(pack)], 1)
            beta = spread([gates[pack + i:pack + i + 1]
                           for i in range(pack)], 1)
            s = buf[slot, h] * jnp.exp(g)
            kv = jnp.sum(s * kx, axis=0, keepdims=True)       # [1, P*dv]
            u = (v_ref[r, pl.ds(hp, 1), :] - kv) * beta
            s = s + kx * u
            buf[slot, h] = s
            o_ref[r, pl.ds(hp, 1), :] = jnp.sum(s * qx, axis=0,
                                                keepdims=True)
            return carry

        jax.lax.fori_loop(0, hb, head, 0)
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
def gdn_step_in_place(
    carry: jax.Array,    # [B, NL, H/P, dk, P*dv] f32: the rows' state
    at: jax.Array,       # [] int32: the layer of the carry to step
    q: jax.Array,        # [B, H, dk] f32, prepared
    k: jax.Array,        # [B, H, dk]
    v: jax.Array,        # [B, H, dv]
    g: jax.Array,        # [B, H] f32 log-decay
    beta: jax.Array,     # [B, H] f32
    live: jax.Array,     # [B] bool
    *,
    interpret: bool = False,
):
    """One token of the recurrence for the live rows of layer ``at``:
    (o [B, H, dv], the carry with those rows' slabs updated and every other
    byte as it was). A row that is not live gets zeros."""
    b, _, hp, dk, pdv = carry.shape
    h, dv = v.shape[1:]
    p = h // hp
    # A packed head's small operands as 2P + 1 neighbouring rows of 128
    # lanes: k of its heads, q of its heads (dk on the lanes), then g | beta
    # of its heads. A tile of rows beyond the last head's, so the tile the
    # kernel reads at any packed head lies inside.
    def lanes(x):
        return jnp.pad(x, ((0, 0),) * (x.ndim - 1)
                       + ((0, LANES - x.shape[-1]),))

    gates = jnp.concatenate(
        [g.reshape(b, hp, 1, p), beta.reshape(b, hp, 1, p)], axis=-1)
    kq = jnp.concatenate(
        [lanes(k.reshape(b, hp, p, dk)), lanes(q.reshape(b, hp, p, dk)),
         lanes(gates.astype(jnp.float32))], axis=2)
    used = hp * (2 * p + 1)
    n_rows = -(-used // SUBLANES) * SUBLANES + _tile_rows(p)
    kq = jnp.pad(kq.reshape(b, used, LANES),
                 ((0, 0), (0, n_rows - used), (0, 0)))
    hb = _heads_per_block(hp, dk, pdv)
    rb = _rows_per_program(b, (n_rows * LANES + 2 * hp * pdv) * 4)

    def rows(*shape):
        return pl.BlockSpec((rb, *shape), lambda i, *_: (i, 0, 0),
                            memory_space=pltpu.VMEM)

    o, carry = pl.pallas_call(
        functools.partial(_step_kernel, pack=p),
        out_shape=[jax.ShapeDtypeStruct((b, hp, pdv), jnp.float32),
                   jax.ShapeDtypeStruct(carry.shape, carry.dtype)],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b // rb,),
            in_specs=[
                rows(n_rows, LANES),
                rows(hp, pdv),
                pl.BlockSpec(memory_space=pl.ANY),   # the carry stays in HBM
            ],
            out_specs=[rows(hp, pdv), pl.BlockSpec(memory_space=pl.ANY)],
            scratch_shapes=[
                pltpu.VMEM((NUM_BUFS, hb, dk, pdv), jnp.float32),
                pltpu.SemaphoreType.DMA((NUM_BUFS,)),
                pltpu.SemaphoreType.DMA((NUM_BUFS,)),
                pltpu.SMEM((b,), jnp.int32),
                pltpu.SMEM((1,), jnp.int32),
            ],
        ),
        # at, live, kq, v, carry -> (o, carry): in place.
        input_output_aliases={4: 1},
        # Programs run in order: each hands its buffers to the next.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="gdn_step_in_place",
    )(
        jnp.asarray(at, jnp.int32).reshape(1), live.astype(jnp.int32),
        kq,
        v.reshape(b, hp, pdv).astype(jnp.float32), carry,
    )
    return o.reshape(b, h, dv), carry
