"""Pallas TPU kernel: one decode step of a window layer's attention, in
place in the rows' carried rings.

The decode loop carries its rows' window rings as two arrays, keys ``[rows,
n_window, Hkv, W, Dk]`` and scaled values ``[.., Dv]`` in the activations'
dtype (models/mimo_v2.py; position p in slot p mod W). A layer's step has to
read each live row's ``(row, layer)`` slabs once, score the row's queries
against the W slots with the step's own key in its slot, and leave that one
row written. As plain ``jnp`` (ops/attention.py:window_ring_step_jnp) it
slices the layer's rings out of the carry for EVERY row of the bucket,
concatenates the step's key onto them, scores, and writes the ring back as a
select over the whole layer slice: the bytes moved a dozen times over (PERF.md
section 6, PR 53).

The data movement is ops/pallas/live_blocks.py's (both carries aliased and
left in HBM, the live rows' blocks of ``HB`` heads through ``NUM_BUFS`` VMEM
buffers of the rings' dtype as one sequence, ``FETCH_AHEAD`` in flight); what
goes BACK is the tile of ``tile_rows`` slots a head that holds the written
one (a single row of a packed dtype cannot be addressed), not the slab. What
is here is the operands' layout and a block's arithmetic.

Which rings take it (``supports_step_kernel``, by shape alone): rows of whole
128-lane tiles, a window of whole tiles of slots, ``G`` = 8, 16, .. or 4, 2,
1 queries a KV head, and a row's KV heads ONE block whose ``NUM_BUFS``
buffers fit ``BUFFER_BYTES`` of VMEM. Two published shapes do: MiMo-V2.5's
(8 KV heads x 128 slots x (256 + 128) lanes, 8 queries a head, sinks: a
block of 768 KiB) and Phi-4-mini-flash's packed differential rows (10 KV
rows x 512 slots x (128 + 128) lanes, 4 queries a row, no sink: 2.5 MiB, PR
55). Fewer than 8 queries a head lie 8 sublanes a head in the float32
scratch, zeros in the rest, so both products keep whole sublane tiles
(``[8, D] x [D, W]``, ``[8, W] x [W, D]``): the matrix unit is bound by the
128 x 128 tiles of keys and values it is fed, not by the rows pushed through
them (PERF.md section 6, PR 53), so the padding is free; only the head's own
rows are rounded into the output. Every other ring keeps the ``jnp`` form.

Arithmetic, a KV head of a live row (``G`` query heads share it):

  * The step's key and scaled value go into slot ``position mod W`` of the
    head's slab in VMEM: the tile that holds the slot is read, the row
    selected in, the tile written.
  * ``scores = q k^T`` [G, W] on the matrix unit, operands in the rings'
    dtype, float32 out. A slot is SEEN exactly where
    ops/attention.py:window_ring_positions says the ring holds a position
    of this sequence (``held >= 0``; the slot just written holds the token
    itself): with slots filled in order that is ``slot <= position``, every
    slot once the position has passed the window. A slot never written, or
    written by the state slot's previous sequence, is unseen.
  * Softmax statistics in float32; the SINK one more term of the
    denominator (ops/attention.py:sink_merged's ``(0, sink, 1)`` segment,
    ``-inf`` where the layer has none); ``p`` in the rings' dtype against
    the values, float32 accumulator: the roundings of the ``jnp`` form. Only
    the order of the float32 sums differs (W slots with the token in its
    slot, against W + 1 keys with the token last).

The small operands go in as the model made them (the queries, the step's
key and its value in the activations' dtype, the sinks as 64 scalars) and
the attention comes out in the queries' dtype: nothing is cast, padded or
laid out again around the call, which cost more launches than the kernel's
own fixed time (PERF.md section 6, PR 53). Inside, a row's operands are
widened once to float32 scratch of the ring's lanes (a float32 ``[8, D]``
block is whole sublane tiles where a 16-bit one is half of one; the casts
back are exact), and its result is rounded once from float32 scratch.

Decode only (one token a row). A prefill chunk stays in XLA
(ops/attention.py:window_ring_attend / window_ring_write).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from production_stack_tpu.ops.pallas.live_blocks import (
    OPERAND_BYTES,
    live_blocks,
    step_call,
)

NUM_BUFS = 3             # one block coming in, one computed, one going out
FETCH_AHEAD = 1          # blocks in flight towards the one computed
BUFFER_BYTES = 8 << 20   # VMEM the NUM_BUFS buffers of both rings may take,
                         # beside live_blocks' OPERAND_BYTES under a v5e's
                         # 16 MiB: a row's KV heads are ONE block, unrolled in
                         # the kernel (768 KiB at 8 x 128 x (256 + 128) lanes,
                         # 2.5 MiB at 10 x 512 x (128 + 128))
LANES, SUBLANES = 128, 8
_NEG_INF = float(jnp.finfo(jnp.float32).min)


def tile_rows(dtype) -> int:
    """Slots of a head that share a tile of the ring as it lies: 8 sublanes
    of 32 bits, two rows a sublane for a 16-bit dtype."""
    return SUBLANES * 4 // jnp.dtype(dtype).itemsize


def padded_group(g: int) -> int:
    """Sublanes a KV head's ``g`` queries take in the float32 scratch: whole
    tiles of 8, zeros in the rows past its own."""
    return -(-g // SUBLANES) * SUBLANES


def supports_step_kernel(ring_k, ring_v, num_heads: int) -> bool:
    """Whether the rings ``[.., Hkv, W, Dk]`` / ``[.., Hkv, W, Dv]`` fit the
    kernel: one dtype, rows of whole lanes (a slice of an array in HBM whose
    rows are not is refused by Mosaic: 192 lanes lie in 256 there and cannot
    be addressed), the window whole tiles of slots, a KV head's queries
    whole sublane tiles or an even part of one (8, 16, .. or 4, 2, 1: fewer
    than 8 lie 8 sublanes a head, so every head's rows start a tile), and a
    row's KV heads ONE block (a head's operands are then static sublanes:
    Mosaic loads no single sublane at a traced index) whose ``NUM_BUFS``
    buffers fit ``BUFFER_BYTES``."""
    hkv, w, dk = ring_k.shape[-3:]
    dv = ring_v.shape[-1]
    g = num_heads // hkv
    block = hkv * w * (dk + dv) * jnp.dtype(ring_k.dtype).itemsize
    return (ring_k.dtype == ring_v.dtype and num_heads % hkv == 0
            and dk % LANES == 0 and dv % LANES == 0
            and w % tile_rows(ring_k.dtype) == 0
            and (g % SUBLANES == 0 or SUBLANES % g == 0)
            and NUM_BUFS * block <= BUFFER_BYTES)


def _step_kernel(
    # scalar prefetch
    at_ref,        # SMEM [1] int32: which layer of the carries
    live_ref,      # SMEM [B] int32: rows that take a token
    pos_ref,       # SMEM [B] int32: the token's position
    sink_ref,      # SMEM [H] f32: a query head's sink (-inf: none)
    # inputs, as the model made them
    q_ref,         # VMEM [RB, H, dk]: the queries
    k_ref,         # VMEM [RB, Hkv, dk]: the step's key
    v_ref,         # VMEM [RB, Hkv, dv]: its scaled value
    k_in,          # HBM  [B, NL, Hkv, W, Dk]: the ring's keys
    v_in,          # HBM  [B, NL, Hkv, W, Dv]: its values
    # outputs
    o_ref,         # VMEM [RB, H, Dv], the queries' dtype
    k_out,         # HBM: the carries again (aliased)
    v_out,
    # scratch: live_blocks', of which the kernel touches the buffers
    kbuf,          # VMEM [NUM_BUFS, HB, W, Dk]
    vbuf,          # VMEM [NUM_BUFS, HB, W, Dv]
    *scratch,      # live_blocks' six, then the kernel's own four
    scale: float,
):
    # A row's operands widened to float32 and to the ring's lanes (zeros
    # past the head's own), its result before the one rounding, and the
    # sinks along the lanes.
    qs, new, acc, sinks = scratch[6:]
    _, hkv, w, dkr = kbuf.shape         # a block is a row's heads
    g = q_ref.shape[1] // hkv
    gp = padded_group(g)                # a head's rows of ``qs``
    dk, dv = q_ref.shape[-1], v_ref.shape[-1]
    tile = tile_rows(kbuf.dtype)

    def tile_of(row):
        # (the first slot of the tile that holds the row's slot, the slot's
        # place in it)
        slot = jax.lax.rem(pos_ref[row], w)
        return pl.multiple_of(slot // tile * tile, tile), \
            jax.lax.rem(slot, tile)

    run = live_blocks(
        at_ref, live_ref, (k_in, v_in), (k_out, v_out), (kbuf, vbuf),
        scratch[0:2], scratch[2:4], *scratch[4:6], rows=o_ref.shape[0],
        fetch_ahead=FETCH_AHEAD,
        written=lambda row: (pl.ds(tile_of(row)[0], tile),))
    o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)
    # Once a program: the lanes past the heads' own, and the sinks from
    # their scalars.
    qs[...] = jnp.zeros(qs.shape, qs.dtype)
    new[...] = jnp.zeros(new.shape, new.dtype)
    if g < gp:
        sinks[...] = jnp.zeros(sinks.shape, sinks.dtype)
    for i in range(hkv * g):
        sinks[i // g, pl.ds(i % g, 1), :] = jnp.full(
            (1, LANES), sink_ref[i], jnp.float32)
    slots = jax.lax.broadcasted_iota(jnp.int32, (1, w), 1)
    in_tile = jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0)

    def compute(n, row, j, slot, r):
        first, place = tile_of(row)
        seen = slots <= pos_ref[row]
        # The queries scaled and rounded as the statement does; the step's
        # key beside its value, a head a sublane.
        scaled = q_ref[r].astype(jnp.float32) * scale
        scaled = scaled.astype(kbuf.dtype).astype(jnp.float32)
        if g == gp:
            qs[:, pl.ds(0, dk)] = scaled
        else:       # 8 sublanes a head, its own queries the first g
            for h in range(hkv):
                qs[pl.ds(h * gp, g), pl.ds(0, dk)] = \
                    scaled[h * g:(h + 1) * g]
        new[:, pl.ds(0, dk)] = k_ref[r].astype(jnp.float32)
        new[:, pl.ds(dkr, dv)] = v_ref[r].astype(jnp.float32)
        for h in range(hkv):
            for buf, lo in ((kbuf, 0), (vbuf, dkr)):
                held = buf[slot, h, pl.ds(first, tile), :]
                row_new = new[pl.ds(h, 1), pl.ds(lo, buf.shape[-1])]
                buf[slot, h, pl.ds(first, tile), :] = jnp.where(
                    in_tile == place, row_new.astype(buf.dtype), held)
        for h in range(hkv):
            s = jax.lax.dot_general(
                qs[pl.ds(h * gp, gp), :].astype(kbuf.dtype), kbuf[slot, h],
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)             # [Gp, W]
            s = jnp.where(seen, s, _NEG_INF)
            m = jnp.max(s, axis=-1, keepdims=True)
            p = jnp.exp(s - m)
            l = jnp.sum(p, axis=-1, keepdims=True)
            out = jnp.dot(p.astype(vbuf.dtype), vbuf[slot, h],
                          preferred_element_type=jnp.float32) / l
            # The sink joins the denominator: merge_attention_segments with
            # the segment (0, sink, 1).
            sink = sinks[h][:, :1]
            top = jnp.maximum(jnp.maximum(m, sink), _NEG_INF)
            wa = l * jnp.exp(m - top)
            denom = jnp.maximum(wa + jnp.exp(sink - top), 1e-30)
            acc[pl.ds(h * g, g), :] = (out * (wa / denom))[:g]
        o_ref[r] = acc[...].astype(o_ref.dtype)

    run(compute)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def ring_step_in_place(
    ring_k: jax.Array,     # [B, NL, Hkv, W, Dk]: the rows' ring keys
    ring_v: jax.Array,     # [B, NL, Hkv, W, Dv]: their scaled values
    at: jax.Array,         # [] int32: the layer of the carries to step
    q: jax.Array,          # [B, H, dk] queries (post-rope), dk <= Dk
    k: jax.Array,          # [B, Hkv, dk] the step's keys
    v: jax.Array,          # [B, Hkv, dv], dv <= Dv
    positions: jax.Array,  # [B] int32
    live: jax.Array,       # [B] bool, or the rows' valid tokens (0 | 1)
    sink: jax.Array,       # [H] f32 (-inf: none)
    *,
    scale: float,
    interpret: bool = False,
):
    """One token of a window layer's attention for the live rows of layer
    ``at``: (o [B, H, dv] in q's dtype, the rings with each live row's key
    and value in slot ``position mod W``, zeros in the lanes past theirs, and
    every other byte as it was). A row that is not live gets zeros. The
    operands go in as they are: nothing is cast, padded or laid out again
    around the call."""
    hkv, _, dkr = ring_k.shape[2:]
    dvr = ring_v.shape[-1]
    h = q.shape[1]
    gp = padded_group(h // hkv)
    f32 = jnp.float32

    def tiles(rows, width, dtype):
        # bytes of [rows, width] in VMEM: whole tiles of the dtype
        sub = tile_rows(dtype)
        return -(-rows // sub) * sub * -(-width // LANES) * LANES \
            * jnp.dtype(dtype).itemsize

    # A row's operands in VMEM: q, the step's k and v, o.
    row_bytes = tiles(h, q.shape[-1], q.dtype) + tiles(hkv, dkr, k.dtype) \
        + tiles(hkv, dvr, v.dtype) + tiles(h, dvr, q.dtype)
    o, ring_k, ring_v = step_call(
        functools.partial(_step_kernel, scale=scale),
        (jnp.asarray(at, jnp.int32).reshape(1), live.astype(jnp.int32),
         positions.astype(jnp.int32), sink.astype(f32)),
        (q, k, v), (ring_k, ring_v), out_row=(h, dvr), out_dtype=q.dtype,
        heads_per_block=hkv, num_bufs=NUM_BUFS,
        row_bytes=row_bytes, operand_bytes=OPERAND_BYTES,
        name="ring_step_in_place", interpret=interpret,
        scratch=(pltpu.VMEM((hkv * gp, dkr), f32),
                 pltpu.VMEM((hkv, dkr + dvr), f32),
                 pltpu.VMEM((h, dvr), f32),
                 pltpu.VMEM((hkv, gp, LANES), f32)))
    return o[..., :v.shape[-1]], ring_k, ring_v
