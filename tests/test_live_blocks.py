"""ops/pallas/live_blocks.py alone, interpreted: the driver that takes the
live rows' state blocks through VMEM in place, under a toy ``compute``.

The kernels built on it (``gdn_step_in_place``, ``ssd_step_in_place``,
``ring_step_in_place``) are checked against their ``jnp`` forms in
tests/test_olmo_hybrid_ops.py, tests/test_granite_hybrid.py and
tests/test_mimo_v2.py; here the data movement is held to its own
contract at both depths they run it at: every block of a live row's slab of
layer ``at`` passes through ``compute`` exactly once, in the order of the
call's live rows, and every other byte of the carry is as it was.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from production_stack_tpu.ops.pallas.live_blocks import (
    OPERAND_BYTES,
    live_blocks,
    step_call,
)

ROWS, LAYERS, HEADS, HB = 8, 3, 6, 2     # three blocks of two heads a row
AT = 1


def _toy_kernel(at_ref, live_ref, x_ref, s_in, o_ref, s_out, buf, *scratch,
                fetch_ahead):
    run = live_blocks(at_ref, live_ref, s_in, s_out, buf, *scratch,
                      rows=o_ref.shape[0], fetch_ahead=fetch_ahead)
    o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    def compute(n, row, j, slot, r):
        # The block's index into the block (a second pass would add it
        # twice); the row's own operand and the call's count into o.
        buf[slot] = buf[slot] + (j + 1).astype(jnp.float32)
        o_ref[r, pl.ds(j, 1), :] = x_ref[r] + (n + 1).astype(jnp.float32)

    run(compute)


def _step(carry, live, x, *, num_bufs, fetch_ahead, programs):
    # ``row_bytes`` is only a number to the driver: both copies of a
    # program's rows just fill the budget.
    row_bytes = OPERAND_BYTES // (2 * (ROWS // programs))
    return step_call(
        functools.partial(_toy_kernel, fetch_ahead=fetch_ahead),
        (jnp.full((1,), AT, jnp.int32), live.astype(jnp.int32)),
        (x,), carry, out_row=(HEADS // HB, 128), heads_per_block=HB,
        num_bufs=num_bufs, row_bytes=row_bytes, operand_bytes=OPERAND_BYTES,
        name="toy_step", interpret=True)


LIVE = {
    "none": ([0] * ROWS, 1),
    "all": ([1] * ROWS, 1),
    "alternating": ([1, 0] * (ROWS // 2), 1),
    "one": ([0, 0, 0, 0, 0, 1, 0, 0], 1),
    # Four programs of two rows: the first holds no live row, so the
    # call's first fetch is issued by the second.
    "first-program-dead": ([0, 0, 1, 1, 0, 1, 1, 0], 4),
    "four-programs": ([1, 1, 0, 1, 0, 0, 1, 1], 4),
    "two-programs-all": ([1] * ROWS, 2),
}


@pytest.mark.parametrize("num_bufs,fetch_ahead", [(3, 1), (4, 2)],
                         ids=["3-bufs-1-ahead", "4-bufs-2-ahead"])
@pytest.mark.parametrize("pattern", list(LIVE))
def test_every_live_block_passes_once_and_nothing_else_moves(
        pattern, num_bufs, fetch_ahead):
    live, programs = LIVE[pattern]
    live = np.asarray(live, bool)
    rng = np.random.default_rng(7)
    carry = rng.standard_normal((ROWS, LAYERS, HEADS, 8, 128)).astype(
        np.float32)
    x = rng.standard_normal((ROWS, 1, 128)).astype(np.float32)
    step = functools.partial(_step, num_bufs=num_bufs,
                             fetch_ahead=fetch_ahead, programs=programs)
    args = (jnp.asarray(carry), jnp.asarray(live), jnp.asarray(x))
    assert f"grid=({programs},)" in str(jax.make_jaxpr(step)(*args))
    o, got = step(*args)
    o, got = np.asarray(o), np.asarray(got)

    nb = HEADS // HB
    want = carry.copy()
    want_o = np.zeros((ROWS, nb, 128), np.float32)
    n = 0
    for row in np.flatnonzero(live):
        for j in range(nb):
            want[row, AT, j * HB:(j + 1) * HB] += j + 1
            n += 1
            want_o[row, j] = x[row, 0] + n
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(o, want_o)


# ---- what the window ring's step added to the driver (PR 53): carries in
# the plural, buffers of the carry's dtype, a write-back narrower than the
# block (ops/pallas/window_ring.py is checked against its ``jnp`` form in
# tests/test_mimo_v2.py).
SLOTS, TILE = 48, 16          # three tiles of 16 slots a head


def _toy_pair_kernel(at_ref, live_ref, tile_ref, x_ref, a_in, b_in, o_ref,
                     a_out, b_out, a_buf, b_buf, *scratch, fetch_ahead,
                     narrow):
    own = scratch[6]    # the kernel's own scratch comes last: a row's x
    def written(row):
        return (pl.ds(pl.multiple_of(tile_ref[row] * TILE, TILE), TILE),)

    run = live_blocks(
        at_ref, live_ref, (a_in, b_in), (a_out, b_out), (a_buf, b_buf),
        scratch[0:2], scratch[2:4], *scratch[4:6], rows=o_ref.shape[0],
        fetch_ahead=fetch_ahead, written=written if narrow else None)
    o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    def compute(n, row, j, slot, r):
        # What the block held on arrival, summed (every slot of it was
        # read), then the WHOLE block changed in VMEM: what goes back of it
        # is the driver's to choose.
        own[...] = x_ref[r]
        total = jnp.sum(a_buf[slot].astype(jnp.float32)) \
            + jnp.sum(b_buf[slot].astype(jnp.float32))
        a_buf[slot] = a_buf[slot] + (j + 1).astype(a_buf.dtype)
        b_buf[slot] = b_buf[slot] - (j + 1).astype(b_buf.dtype)
        o_ref[r, pl.ds(j, 1), :] = own[...] + total \
            + 1000.0 * (n + 1).astype(jnp.float32)

    run(compute)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("narrow", [True, False],
                         ids=["a-tile-goes-back", "the-block-goes-back"])
@pytest.mark.parametrize("pattern", ["none", "all", "alternating",
                                     "first-program-dead", "four-programs"])
def test_two_carries_pass_together_and_only_what_is_written_goes_back(
        pattern, narrow, dtype):
    """Two carries of one dtype and different widths step together: every
    block of a live row's slabs of layer ``at`` reaches ``compute`` once,
    whole, in both; with ``written`` only the named tile of slots of each
    head goes back (the rest of what ``compute`` changed in VMEM does not),
    without it the block; every other byte of both carries is as it was. The
    kernel's own scratch is handed it behind the driver's."""
    live, programs = LIVE[pattern]
    live = np.asarray(live, bool)
    rng = np.random.default_rng(11)
    dt = jnp.dtype(dtype)
    # Small whole numbers: exact in bfloat16 through the sums below.
    a = rng.integers(-4, 5, (ROWS, LAYERS, HEADS, SLOTS, 256)).astype(
        np.float32)
    b = rng.integers(-4, 5, (ROWS, LAYERS, HEADS, SLOTS, 128)).astype(
        np.float32)
    x = rng.integers(-9, 10, (ROWS, 1, 128)).astype(np.float32)
    tiles = rng.integers(0, SLOTS // TILE, ROWS).astype(np.int32)
    row_bytes = OPERAND_BYTES // (2 * (ROWS // programs))
    o, got_a, got_b = step_call(
        functools.partial(_toy_pair_kernel, fetch_ahead=1, narrow=narrow),
        (jnp.full((1,), AT, jnp.int32), jnp.asarray(live, jnp.int32),
         jnp.asarray(tiles)),
        (jnp.asarray(x),), (jnp.asarray(a, dt), jnp.asarray(b, dt)),
        out_row=(HEADS // HB, 128), heads_per_block=HB, num_bufs=3,
        row_bytes=row_bytes, operand_bytes=OPERAND_BYTES, name="toy_pair",
        interpret=True, scratch=(pltpu.VMEM((1, 128), jnp.float32),))
    assert got_a.dtype == got_b.dtype == dt
    nb = HEADS // HB
    want_a, want_b = a.copy(), b.copy()
    want_o = np.zeros((ROWS, nb, 128), np.float32)
    n = 0
    for row in np.flatnonzero(live):
        rows = slice(tiles[row] * TILE, (tiles[row] + 1) * TILE) if narrow \
            else slice(None)
        for j in range(nb):
            heads = slice(j * HB, (j + 1) * HB)
            n += 1
            want_o[row, j] = x[row, 0] + a[row, AT, heads].sum() \
                + b[row, AT, heads].sum() + 1000.0 * n
            want_a[row, AT, heads, rows] += j + 1
            want_b[row, AT, heads, rows] -= j + 1
    np.testing.assert_array_equal(np.asarray(got_a, np.float32), want_a)
    np.testing.assert_array_equal(np.asarray(got_b, np.float32), want_b)
    np.testing.assert_array_equal(np.asarray(o), want_o)
