"""Pallas TPU kernel: one decode step of a Gated DeltaNet layer, in place in
the rows' carried state.

The decode loop carries its rows' recurrent state as one array
``[rows, n_linear, H/P, dk, P*dv]`` float32 (models/olmo_hybrid.py; the
packed layout of ops/gated_delta.py). A layer's step has to read each live
row's ``(row, layer)`` slab once and write it once; as plain ``jnp`` it
was three passes over every row of the bucket (decay and ``S^T k``; the
rank-one update, fused into the carry's ``dynamic_update_slice``;
``S^T q``), live or not: four times the bytes (PERF.md §6, PR 32).

  * The data movement is ops/pallas/live_blocks.py's: the carry aliased
    and left in HBM, blocks of ``HB`` packed heads ``[HB, dk, P*dv]`` of a
    live row's slab (contiguous: a slab is ``[H/P, dk, P*dv]``) through
    ``NUM_BUFS`` VMEM buffers as one sequence over the call's live rows, one
    in flight towards the block that is computed.
  * A row that is not live moves no byte of state. Its ``o`` is zeros.
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

That is the decode step (``gdn_step_in_place``, one token a row). The
chunkwise prefill form is the second kernel here (``gdn_chunk_in_place``):
see its section below.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from production_stack_tpu.ops.pallas.live_blocks import (
    OPERAND_BYTES,
    live_blocks,
    step_call,
)

# On a v5e, 20 rows of [15, 96, 384] a layer-step (PERF.md §6, PR 32): 2 / 3 /
# 4 buffers 188 / 149 / 150 us; blocks of 1 / 3 / 5 / 15 packed heads 222 /
# 162 / 149 / 145 us (about 0.3 us a block of fixed cost).
NUM_BUFS = 3             # one block coming in, one computed, one going out
BLOCK_BYTES = 1 << 20    # largest state block (a buffer): 5 packed heads of
                         # 96 x 384, so a 15-head slab is three blocks
FETCH_AHEAD = 1          # blocks in flight towards the one computed
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
    # scratch: live_blocks', of which the kernel touches the buffers
    buf,           # VMEM [NUM_BUFS, HB, dk, P*dv] f32
    *scratch,
    pack: int,
):
    _, hb, dk, pdv = buf.shape
    dv = pdv // pack
    tile = _tile_rows(pack)
    run = live_blocks(at_ref, live_ref, s_in, s_out, buf, *scratch,
                      rows=o_ref.shape[0], fetch_ahead=FETCH_AHEAD)
    lane_head = jax.lax.broadcasted_iota(jnp.int32, (1, pdv), 1) // dv

    def spread(parts, rows):
        # parts[i] (a scalar, or [rows, 1]) over head i's lanes of the
        # packed value axis: ops/gated_delta.py:_spread, on the chip.
        out = jnp.broadcast_to(parts[0], (rows, pdv))
        for i in range(1, pack):
            out = jnp.where(lane_head == i, parts[i], out)
        return out

    o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    def compute(n, row, j, slot, r):
        h0 = j * hb

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

    run(compute)


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
    o, carry = step_call(
        functools.partial(_step_kernel, pack=p),
        (jnp.asarray(at, jnp.int32).reshape(1), live.astype(jnp.int32)),
        (kq, v.reshape(b, hp, pdv).astype(jnp.float32)), carry,
        out_row=(hp, pdv), heads_per_block=hb, num_bufs=NUM_BUFS,
        row_bytes=(n_rows * LANES + 2 * hp * pdv) * 4,
        operand_bytes=OPERAND_BYTES, name="gdn_step_in_place",
        interpret=interpret)
    return o.reshape(b, h, dv), carry


# ------------------------------------------------------ the chunkwise form
# ``gdn_chunk_in_place``: the chunkwise gated delta rule of a prefill
# dispatch (ops/gated_delta.py:gdn_chunk_jnp is its statement) as ONE call a
# layer. Grid (row, chunk of 64 tokens), the chunks of a row in turn:
#
#   * A row's packed state ``[H/P, dk, P*dv]`` is one block, aliased to the
#     output: it comes into VMEM before the row's first chunk, is updated
#     there chunk after chunk, and goes back when the row is done. Once in,
#     once out; a row of length 0 goes through unchanged, bit for bit.
#   * A chunk's q, k, v and gates are blocks of the arrays as the model
#     makes them (``[B, T, H*d]``: a reshape of ``[B, T, H, d]`` that moves
#     nothing), the next chunk's in flight while this one is computed. A
#     chunk past its row's length asks for the row's last valid chunk again,
#     which is no transfer, computes nothing and writes zeros to ``o``.
#   * Inside a chunk everything stays in VMEM. Heads are taken two at a
#     time, their 64 x 64 systems side by side as one block-diagonal
#     128 x 128: ``k_beta k^T | q k^T`` is one product, the decay mask one
#     ``exp``, and ``(I + L)^-1`` a BLOCKED forward substitution: the
#     ``DIAG`` x ``DIAG`` blocks on the diagonals (of every head at once:
#     the blocks side by side over the lanes, the pairs along the sublanes)
#     by the substitution itself on the vector unit, and the blocks below
#     them by products (``M = D^-1 N`` is nilpotent over a head's 64 / DIAG
#     block rows, so ``(D + N)^-1 = (I - M + M^2 - ...) D^-1`` ends after
#     that many terms: it is exact, not a series cut short). Then ``u``,
#     ``w``, the two products with the state, ``o``, the state's decay and
#     update, a head at a time on its lanes of the packed state.
#   * Every product is float32 at the ``jnp`` form's precision
#     (``Precision.HIGHEST``): only the order of sums differs (the
#     cumulative gates are a product with a triangle of ones, whose terms
#     are exact).
#   * The pairs are walked by loops of the KERNEL, a few pairs a trip
#     (their operations alternate, so one pair's products are in flight
#     while another's masks are made): a program traces and lowers the
#     body once, not once a pair, which is what a warm boot pays for each
#     of its prefill families.
CHUNK = 64               # tokens a chunk, as ops/gated_delta.py:CHUNK
DIAG = 16                # the diagonal blocks solved by substitution
PAIR = 2                 # heads whose systems share a 128-lane tile
CHUNK_VMEM_BYTES = 96 << 20   # of a v5e's 128 MiB; the default limit is 16


def _pairs_per_trip(num_heads: int, pack: int) -> int:
    """Pairs of heads a trip of the kernel's loops takes: whole packed
    heads (so a head's lanes of the state are fixed in the body), dividing
    the pairs, three where that fits."""
    pairs = num_heads // PAIR
    return next(u for u in (3, 2, 1)
                if pairs % u == 0 and (PAIR * u) % pack == 0
                and PAIR * u <= SUBLANES)


def _chunk_vmem_bytes(num_heads: int, dk: int, dv: int) -> int:
    """What the chunk kernel asks of VMEM: both copies Pallas keeps of every
    block (q, k, v, o, the gates, the state in and out), its scratch (q and
    k by head, the pairs' systems, the substitution's terms) and room for
    the values a trip has live."""
    blocks = 4 * (CHUNK * num_heads * (2 * dk + 2 * dv) + CHUNK * LANES
                  + 2 * num_heads * dk * dv)
    pairs = -(-num_heads // (PAIR * SUBLANES)) * SUBLANES
    scratch = 4 * (2 * num_heads * CHUNK * LANES + 4 * (LANES + SUBLANES)
                   * LANES + num_heads * LANES * LANES
                   + (DIAG + 1) * DIAG * pairs * LANES)
    return 2 * blocks + scratch + (16 << 20)


def supports_chunk_kernel(tokens: int, num_heads: int, packed) -> bool:
    """Whether a call of T ``tokens`` a row on the packed state
    ``(H/P, dk, P*dv)`` fits the chunk kernel: whole chunks of 64, the key
    axis whole sublanes, the packed value axis whole lanes, heads in pairs
    with their gates within one tile of lanes, and the blocks within
    VMEM."""
    hp, dk, pdv = packed
    p = num_heads // hp
    return (tokens > 0 and tokens % CHUNK == 0 and hp * p == num_heads
            and pdv % LANES == 0 and dk % SUBLANES == 0 and dk <= LANES
            and num_heads % PAIR == 0 and 2 * num_heads <= LANES
            and _chunk_vmem_bytes(num_heads, dk, pdv // p)
            <= CHUNK_VMEM_BYTES)


def _interleave(stages):
    """Runs generators a stage of each in turn: the operations of
    independent pairs of heads alternate in program order (within one pair
    every stage waits for the one before)."""
    active = list(stages)
    while active:
        for g in list(active):
            if next(g, StopIteration) is StopIteration:
                active.remove(g)


def _chunk_kernel(
    lens_ref,      # SMEM [B] int32 (scalar prefetch): valid tokens a row
    q_ref,         # VMEM [1, C, H*dk] f32: this chunk, prepared
    k_ref,         # VMEM [1, C, H*dk]
    v_ref,         # VMEM [1, C, H*dv]
    gates_ref,     # VMEM [1, C, 128]: g on lanes 0..H, beta on H..2H
    s_in,          # VMEM [1, H/P, dk, P*dv] f32: the row's state before
    o_ref,         # VMEM [1, C, H*dv]
    s_out,         # VMEM [1, H/P, dk, P*dv]: the row's state (aliased)
    # scratch: a chunk's operands and systems
    q_scr,         # VMEM [H, C, dk]: q by head
    k_scr,         # VMEM [H, C, dk]: k by head
    g_scr,         # VMEM [4, 128 + 8, 128]: the gates | beta, the cumulative
                   # gates, their exp and exp(g_last - gc), TRANSPOSED: a
                   # head a row, a chunk's tokens along the lanes
    l_scr,         # VMEM [H/2, 128, 128]: strictly lower k_beta k^T decay
    qk_scr,        # VMEM [H/2, 128, 128]: lower q k^T decay
    lx_scr,        # VMEM [DIAG*PP, 128]: row i*PP + p: row i of pair p's
                   # diagonal blocks, side by side (PP: pairs to whole tiles)
    m_scr,         # VMEM [DIAG-1, DIAG*PP, 128]: m_scr[j] is lx with column
                   # j of every block spread over the block's lanes
    t_scr,         # VMEM [DIAG*PP, 128]: the diagonal blocks' inverses, as lx
    *,
    heads: int, dk: int, dv: int, precision,
):
    row_id, n = pl.program_id(0), pl.program_id(1)

    def dot(a, b, dims=(((1,), (0,)), ((), ()))):
        return jax.lax.dot_general(a, b, dims, precision=precision,
                                   preferred_element_type=jnp.float32)

    c, n2 = CHUNK, PAIR * CHUNK
    pairs = heads // PAIR
    pack = heads // s_in.shape[1]
    trip = _pairs_per_trip(heads, pack)       # pairs a trip
    pp = lx_scr.shape[0] // DIAG
    length = lens_ref[row_id]

    @pl.when(n == 0)
    def _():
        s_out[...] = s_in[...]

    @pl.when(n * c >= length)
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    @pl.when(n * c < length)
    def _():
        # Gates of positions past the row's length are zeroed: the chunk
        # that holds the last valid token leaves the state after it.
        tok = n * c + jax.lax.broadcasted_iota(jnp.int32, (c, LANES), 0)
        gates = jnp.where(tok < length, gates_ref[0], 0.0)
        tri = (jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
               >= jax.lax.broadcasted_iota(jnp.int32, (c, c), 1))
        gc = dot(jnp.where(tri, 1.0, 0.0), gates)       # cumulative; [C, 128]
        exp_gc = jnp.exp(gc)
        # A loop's trip reads its heads' columns of these four (a head's
        # value of every token, down the sublanes) as the transpose of the
        # 8 rows that start at its first head.
        beta_t, gc_t, exp_t, scale_t = range(4)
        for at, x in enumerate((gates, gc, exp_gc,
                                jnp.exp(gc[c - 1:c] - gc))):  # exp(g_last-gc)
            g_scr[at, :LANES] = jnp.concatenate(
                [x, jnp.zeros((LANES - c, LANES), jnp.float32)], axis=0).T
        # q and k by head: a head's lanes start off a lane tile (dk = 96),
        # which only a fixed slice may; a group of heads whose lanes are
        # whole tiles is a window a loop can move.
        group = LANES // math.gcd(dk, LANES)

        def by_head(first, lanes_at, count):
            for ref, scr in ((q_ref, q_scr), (k_ref, k_scr)):
                window = ref[0, :, pl.ds(lanes_at, count * dk)]
                for i in range(count):
                    scr[first + i] = window[:, i * dk:(i + 1) * dk]

        def groups(at, carry):
            by_head(group * at, pl.multiple_of(at * group * dk, LANES), group)
            return carry

        if heads >= group:
            jax.lax.fori_loop(0, heads // group, groups, 0)
        if heads % group:
            by_head(heads - heads % group, (heads - heads % group) * dk,
                    heads % group)
        row = jax.lax.broadcasted_iota(jnp.int32, (n2, n2), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (n2, n2), 1)
        causal = (row // c == col // c) & (row >= col)
        on_diag = row // DIAG == col // DIAG

        def cols(at, first):
            """[128, 8]: column i is head ``first + i``'s row of g_scr[at]."""
            return g_scr[at, pl.ds(first, SUBLANES), :].T

        def pair_col(tile, u):   # a pair's two columns, one under the other
            return jnp.concatenate(
                [tile[:c, PAIR * u + i:PAIR * u + i + 1]
                 for i in range(PAIR)], axis=0)

        def pair_rows(scr, p):   # a pair's [C, dk] of q or k likewise
            return jnp.concatenate(
                [scr[PAIR * p + i] for i in range(PAIR)], axis=0)

        # 1. A pair's two 64 x 64 systems as one block-diagonal 128 x 128:
        # k_beta k^T and q k^T as ONE product; exp(gc_i - gc_j) for i >= j
        # of one head, masked BEFORE the exp.
        lx_scr[...] = jnp.zeros(lx_scr.shape, jnp.float32)

        def system(p, carry):
            k = pair_rows(k_scr, p)
            kq = dot(jnp.concatenate(
                [k * pair_col(cols(beta_t, heads + PAIR * p), 0),
                 pair_rows(q_scr, p)], axis=0), k,
                (((1,), (1,)), ((), ())))                 # [2*n2, n2]
            gcm = jnp.broadcast_to(
                pair_col(cols(gc_t, PAIR * p), 0), (n2, n2))
            decay = jnp.exp(jnp.where(causal, gcm - gcm.T, -jnp.inf))
            lower = jnp.where(row > col, kq[:n2] * decay, 0.0)
            l_scr[p] = lower
            qk_scr[p] = kq[n2:] * decay
            lx = jnp.sum(jnp.where(on_diag, lower, 0.0).reshape(
                n2 // DIAG, DIAG, n2), axis=0)
            lx_scr[pl.ds(p, DIAG, stride=pp), :] = lx
            return carry

        jax.lax.fori_loop(0, pairs, system, 0)

        # 2. (I + L)^-1 of every DIAG x DIAG block on the diagonals, all
        # pairs at once, by the forward substitution itself: row i of a
        # block's inverse is e_i - sum_{j<i} L[i, j] (row j of it). The
        # blocks lie side by side over the lanes and the pairs along the
        # sublanes, so step j is one multiply-add of the rows after j,
        # once L[i, j] is spread over its block's lanes: a product with
        # zeros and ones. lx goes as three bfloat16 terms that sum to it
        # exactly (8 + 8 + 8 bits of mantissa), made once: each moves
        # through the matrix unit unrounded, and so do the zeros and ones.
        terms, rest = [], lx_scr[...]
        for _ in range(3):
            terms.append(rest.astype(jnp.bfloat16))
            rest = rest - terms[-1].astype(jnp.float32)

        def spread(j, carry):
            ones = jnp.where(on_diag & (row % DIAG == j), 1.0, 0.0)
            m_scr[j] = sum(jnp.dot(x, ones.astype(jnp.bfloat16),
                                   preferred_element_type=jnp.float32)
                           for x in terms)
            return carry

        jax.lax.fori_loop(0, DIAG - 1, spread, 0)
        of_row = jax.lax.broadcasted_iota(jnp.int32, (DIAG, pp, n2), 0)
        t_scr[...] = jnp.where(
            of_row == jax.lax.broadcasted_iota(
                jnp.int32, (DIAG, pp, n2), 2) % DIAG, 1.0, 0.0
        ).reshape(DIAG * pp, n2)

        def substitute(j, carry):
            t_j = t_scr[pl.ds(pl.multiple_of(j * pp, SUBLANES), pp), :]
            step = m_scr[j].reshape(DIAG, pp, n2) * t_j[None]
            t_scr[...] = t_scr[...] - jnp.where(
                of_row > j, step, 0.0).reshape(DIAG * pp, n2)
            return carry

        jax.lax.fori_loop(0, DIAG - 1, substitute, 0)

        # 3. The blocks below the diagonal by products (a head's CHUNK /
        # DIAG block rows make M = D^-1 N nilpotent of that index, so
        # (D + N)^-1 = (I - M + M^2 - ...) D^-1 ends there: it is exact),
        # then u, w, the products with the state, o, the state's decay and
        # update, a head at a time on its lanes of the packed state.
        last8 = jax.lax.broadcasted_iota(
            jnp.int32, (SUBLANES, dv), 0) == SUBLANES - 1

        def rest_of(it, carry):
            first = PAIR * trip * it
            beta, exps, scales = (cols(at, f) for at, f in (
                (beta_t, heads + first), (exp_t, first), (scale_t, first)))
            width = PAIR * trip * dv
            lanes_at = pl.multiple_of(it * width, LANES)
            v = v_ref[0, :, pl.ds(lanes_at, width)]
            outs = [None] * (PAIR * trip)

            def one(u):
                p = trip * it + u
                lower = l_scr[p]
                d_inv = jnp.where(on_diag, jnp.concatenate(
                    [t_scr[pl.ds(p, DIAG, stride=pp), :]] * (n2 // DIAG),
                    axis=0), 0.0)
                m = dot(d_inv, jnp.where(on_diag, 0.0, lower))
                yield
                tmat = d_inv
                for _ in range(c // DIAG - 1):
                    tmat = d_inv - dot(m, tmat)
                    yield
                k, q = pair_rows(k_scr, p), pair_rows(q_scr, p)
                e = pair_col(exps, u)
                w = dot(tmat, k * pair_col(beta, u) * e)
                wq = (w, q * e)
                k_out = k * pair_col(scales, u)
                qk = qk_scr[p]
                yield
                for i in range(PAIR):
                    h = PAIR * u + i                  # of the trip's heads
                    at = slice(i * c, (i + 1) * c)
                    packed = (first + h) // pack
                    lanes = slice((h % pack) * dv, (h % pack + 1) * dv)
                    s = s_out[0, packed, :, lanes]        # [dk, dv]
                    v_beta = v[:, h * dv:(h + 1) * dv] * beta[:c, h:h + 1]
                    u_ = dot(tmat[at, at], v_beta)
                    # w and the decayed q meet the state in one product.
                    from_s = dot(
                        jnp.concatenate([x[at] for x in wq], axis=0), s)
                    yield
                    v_new = u_ - from_s[:c]
                    outs[h] = from_s[c:] + dot(qk[at, at], v_new)
                    # exp(g_last) over a row of dv lanes (a sum of one
                    # term: a scalar cannot be spread over sublanes and
                    # lanes at once).
                    last = jnp.sum(jnp.where(last8, jnp.broadcast_to(
                        exps[c - SUBLANES:c, h:h + 1], (SUBLANES, dv)), 0.0),
                        axis=0, keepdims=True)
                    s_out[0, packed, :, lanes] = s * last + dot(
                        k_out[at], v_new, (((0,), (0,)), ((), ())))
                    yield

            _interleave(one(u) for u in range(trip))
            o_ref[0, :, pl.ds(lanes_at, width)] = jnp.concatenate(
                outs, axis=1)
            return carry

        jax.lax.fori_loop(0, pairs // trip, rest_of, 0)


@functools.partial(jax.jit, static_argnames=("precision", "interpret"))
def gdn_chunk_in_place(
    state: jax.Array,    # [B, H/P, dk, P*dv] f32 packed, before the chunk
    q: jax.Array,        # [B, T, H, dk] f32, prepared
    k: jax.Array,        # [B, T, H, dk]
    v: jax.Array,        # [B, T, H, dv]
    g: jax.Array,        # [B, T, H] f32 log-decay
    beta: jax.Array,     # [B, T, H] f32
    lens: jax.Array,     # [B] valid tokens of each row
    *,
    precision=jax.lax.Precision.HIGHEST,
    interpret: bool = False,
):
    """T tokens a row (whole chunks of 64) from ``state``: (o [B, T, H, dv],
    the packed state after each row's last valid token, in the buffer
    ``state`` came in). ``o`` past a row's length is zeros. ``precision``
    is that of every float32 product (the ``jnp`` form's, which
    ops/gated_delta.py:gdn_chunk hands over)."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    lens = lens.astype(jnp.int32)
    gates = jnp.concatenate(
        [g, beta, jnp.zeros((b, t, LANES - 2 * h), jnp.float32)], axis=-1)
    # Pairs along the sublanes of the substitution, to whole tiles.
    pp = -(-(h // PAIR) // SUBLANES) * SUBLANES

    def chunk(width):
        # A chunk past the row's length: the row's last valid chunk again.
        return pl.BlockSpec(
            (1, CHUNK, width),
            lambda i, n, lens: (i, jnp.minimum(n, jnp.maximum(
                (lens[i] + CHUNK - 1) // CHUNK - 1, 0)), 0),
            memory_space=pltpu.VMEM)

    rows = pl.BlockSpec((1, *state.shape[1:]), lambda i, n, lens: (i, 0, 0, 0),
                        memory_space=pltpu.VMEM)
    o, state = pl.pallas_call(
        functools.partial(_chunk_kernel, heads=h, dk=dk, dv=dv,
                          precision=precision),
        out_shape=[jax.ShapeDtypeStruct((b, t, h * dv), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, t // CHUNK),
            in_specs=[chunk(h * dk), chunk(h * dk), chunk(h * dv),
                      chunk(LANES), rows],
            out_specs=[
                pl.BlockSpec((1, CHUNK, h * dv), lambda i, n, lens: (i, n, 0),
                             memory_space=pltpu.VMEM),
                rows],
            scratch_shapes=[
                pltpu.VMEM((h, CHUNK, dk), jnp.float32),
                pltpu.VMEM((h, CHUNK, dk), jnp.float32),
                pltpu.VMEM((4, LANES + SUBLANES, LANES), jnp.float32),
                pltpu.VMEM((h // PAIR, LANES, LANES), jnp.float32),
                pltpu.VMEM((h // PAIR, LANES, LANES), jnp.float32),
                pltpu.VMEM((DIAG * pp, LANES), jnp.float32),
                pltpu.VMEM((DIAG - 1, DIAG * pp, LANES), jnp.float32),
                pltpu.VMEM((DIAG * pp, LANES), jnp.float32),
            ],
        ),
        # lens, q, k, v, gates, state -> (o, state): in place.
        input_output_aliases={5: 1},
        # A row's chunks in turn; rows in turn too (one core a chip).
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_chunk_vmem_bytes(h, dk, dv)),
        interpret=interpret,
        name="gdn_chunk_in_place",
    )(lens, q.reshape(b, t, h * dk), k.reshape(b, t, h * dk),
      v.reshape(b, t, h * dv), gates, state)
    return o.reshape(b, t, h, dv), state
