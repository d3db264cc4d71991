"""ops/pallas/live_blocks.py alone, interpreted: the driver that takes the
live rows' state blocks through VMEM in place, under a toy ``compute``.

The two kernels built on it (``gdn_step_in_place``, ``ssd_step_in_place``)
are checked against their ``jnp`` forms in tests/test_olmo_hybrid_ops.py and
tests/test_granite_hybrid.py; here the data movement is held to its own
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
