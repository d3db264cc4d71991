"""The state-space scan under the Granite 4.0 hybrid family: the chunkwise form
against the step applied token by token, the step in place in a layer of
the carry, and the step's Pallas kernel against its ``jnp`` statement
(interpret mode). tests/test_granite_hybrid.py holds the whole forward to
the reference.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.ops import ssd
from tests.granite_hybrid_helpers import ref, step


# ---- the scan alone: the chunkwise form is the recurrence -------------------
def _scan_inputs(b, t, h, p, n, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    x = jax.random.normal(ks[0], (b, t, h, p))
    bm, cm = (jax.random.normal(ks[i], (b, t, n)) for i in (1, 2))
    a_log = jnp.log(jax.random.uniform(ks[3], (h,), minval=1.0, maxval=16.0))
    dt_bias = ssd.softplus_inverse(
        jax.random.uniform(ks[4], (h,), minval=1e-3, maxval=1e-1))
    dt, da = ssd.gates(jax.random.normal(ks[5], (b, t, h)), a_log, dt_bias)
    d_skip = jax.random.uniform(ks[6], (h,), minval=0.5, maxval=1.5)
    state0 = 0.5 * jax.random.normal(ks[7], (b, h, p, n))
    return x, bm, cm, dt, da, d_skip, state0


def _token_by_token(x, bm, cm, dt, da, d_skip, state0, lens):
    """``ssd_step`` applied a token at a time, as a ``lax.scan`` over the
    token axis: ONE step is traced and compiled whatever T (as a Python
    loop the 300-token case at the published head sizes unrolled 300 steps
    and compiled for 148 s of this file's 446: CHANGES.md, PR 56)."""
    def one(state, token):
        i, x_i, b_i, c_i, dt_i, da_i = token
        y, state = ssd.ssd_step(state, x_i, b_i, c_i, dt_i, da_i, d_skip,
                                i < lens)
        return state, y

    tokens = tuple(jnp.moveaxis(a, 1, 0) for a in (x, bm, cm, dt, da))
    state, outs = jax.lax.scan(
        one, state0, (jnp.arange(x.shape[1]), *tokens))
    return jnp.moveaxis(outs, 0, 1), state


def _relative(got, want) -> float:
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


@pytest.mark.parametrize("t", [1, 3, 127, 128, 129, 300])
def test_ssd_chunk_is_ssd_step_applied_t_times(t):
    args = _scan_inputs(2, t, 4, 16, 32, t)
    lens = jnp.array([t, max(t - 5, 0)])
    out, state = ssd.ssd_chunk(args[6], *args[:6], lens)
    want, want_state = _token_by_token(*args, lens)
    valid = (jnp.arange(t)[None, :] < lens[:, None])[..., None, None]
    assert _relative(out * valid, want * valid) < 1e-5
    assert _relative(state, want_state) < 1e-5


def test_ssd_chunk_is_ssd_step_at_the_published_head_sizes():
    """64 heads x 64 x state 128, 300 tokens (three chunks, the last
    partial), one row short: float32 sums of up to 128 products a chunk in
    another order than the recurrence's: 1e-5 of the outputs' norm (measured
    under 2e-6)."""
    args = _scan_inputs(2, 300, 64, 64, 128, 7)
    lens = jnp.array([300, 131])
    out, state = jax.jit(ssd.ssd_chunk)(args[6], *args[:6], lens)
    want, want_state = jax.jit(_token_by_token)(*args, lens)
    valid = (jnp.arange(300)[None, :] < lens[:, None])[..., None, None]
    assert _relative(out * valid, want * valid) < 1e-5
    assert _relative(state, want_state) < 1e-5


def test_ssd_step_at_steps_one_layer_of_the_carry_and_spares_dead_rows():
    x, bm, cm, dt, da, d_skip, state0 = _scan_inputs(3, 1, 4, 16, 32, 11)
    carry = jnp.stack([state0, 2.0 * state0, 3.0 * state0], axis=1)
    live = jnp.array([True, False, True])
    y, got = ssd.ssd_step_at(carry, 1, x[:, 0], bm[:, 0], cm[:, 0], dt[:, 0],
                             da[:, 0], d_skip, live)
    want_y, want = ssd.ssd_token(2.0 * state0, x[:, 0], bm[:, 0], cm[:, 0],
                                 dt[:, 0], da[:, 0], d_skip)
    np.testing.assert_array_equal(got[:, 0], carry[:, 0])
    np.testing.assert_array_equal(got[:, 2], carry[:, 2])
    np.testing.assert_array_equal(got[1, 1], carry[1, 1])
    np.testing.assert_allclose(got[::2, 1], want[::2], rtol=1e-6)
    np.testing.assert_allclose(y[::2], want_y[::2], rtol=1e-6)
    assert not np.any(y[1])


PATHS = pytest.mark.parametrize(
    "interpret", [False, True], ids=["xla", "pallas"])


@PATHS
@pytest.mark.parametrize("rows,h,p,n,live", [
    (3, 32, 16, 128, (1, 0, 1)),                 # two blocks of heads a row
    (8, 16, 8, 128, (0, 1, 1, 1, 0, 0, 1, 1)),
    (4, 4, 16, 128, (0, 0, 0, 0)),               # nothing to step
    (32, 16, 8, 128, (1, 0) * 16),
    # The published head sizes: four blocks of 16 heads a row.
    (4, 64, 64, 128, (0, 0, 1, 0)),              # one live row
    (16, 64, 64, 128, (0,) + (1,) * 14 + (0,)),  # first and last rows dead
    (16, 64, 64, 128, (1,) * 16),                # a 16-row bucket, all live
    (32, 64, 64, 128, tuple(i * 17 % 32 < 17 for i in range(32))),
], ids=lambda v: str(sum(v)) + "live" if isinstance(v, tuple) else str(v))
def test_the_two_executions_of_the_step_agree(interpret, rows, h, p, n, live):
    """ops/ssd.py:ssd_step_at through the Pallas kernel (interpreted) and
    through the ``jnp`` form against ``ssd_token``: the live rows' slabs of
    layer 1 stepped, every other byte of the carry as it was."""
    from production_stack_tpu.ops.pallas.ssd import supports_step_kernel

    assert supports_step_kernel((h, p, n))
    x, bm, cm, dt, da, d_skip, state0 = _scan_inputs(rows, 1, h, p, n, rows)
    carry = jnp.stack([state0, 2.0 * state0, 3.0 * state0], axis=1)
    lv = jnp.asarray(live, bool)
    y, got = ssd.ssd_step_at(carry, 1, x[:, 0], bm[:, 0], cm[:, 0],
                             dt[:, 0], da[:, 0], d_skip, lv,
                             interpret=interpret)
    want_y, want = ssd.ssd_token(2.0 * state0, x[:, 0], bm[:, 0], cm[:, 0],
                                 dt[:, 0], da[:, 0], d_skip)
    keep = np.asarray(lv)
    np.testing.assert_array_equal(got[:, ::2], carry[:, ::2])
    np.testing.assert_array_equal(got[~keep, 1], carry[~keep, 1])
    assert not np.any(np.asarray(y)[~keep])
    if keep.any():
        np.testing.assert_allclose(got[keep, 1], want[keep], rtol=1e-5,
                                   atol=1e-6)
        np.testing.assert_allclose(y[keep], want_y[keep], rtol=1e-4,
                                   atol=1e-5)


def test_the_step_kernel_hands_its_buffers_from_program_to_program(
        monkeypatch):
    """Rows whose small operands outgrow one program's VMEM are several
    programs (64 rows at the published widths are two); the block
    sequence, two blocks in flight, runs on through them. Forced here at a
    small shape: four programs of 8 rows, a row of two blocks."""
    from production_stack_tpu.ops.pallas import ssd as kernel

    rows, h, p, n = 32, 32, 8, 128
    # A row's operands here: 8 KB of dt x, 4 KB of B | C, 4 KB of y.
    monkeypatch.setattr(kernel, "OPERAND_BYTES", 2 * 8 * (16 << 10))
    x, bm, cm, dt, da, d_skip, state0 = _scan_inputs(rows, 1, h, p, n, 5)
    carry = jnp.stack([state0, 2.0 * state0], axis=1)
    live = jnp.asarray([0, 0, 1] + [1, 0, 1, 1] * 7 + [0], bool)
    args = (carry, 0, x[:, 0], bm[:, 0], cm[:, 0], dt[:, 0], da[:, 0],
            d_skip, live)
    step = functools.partial(kernel.ssd_step_in_place, interpret=True)
    assert "grid=(4,)" in str(jax.make_jaxpr(step)(*args))
    y, got = step(*args)
    want_y, want = ssd.ssd_step_at_jnp(*args)
    np.testing.assert_array_equal(got[:, 1], carry[:, 1])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(y, want_y, rtol=1e-4, atol=1e-5)


REC_TOL = 5e-5   # check_reference.py's, of granite-4.0-h-micro: the chip's


@pytest.mark.parametrize("lens,live", [
    ((300, 131), (1, 1)),             # two chunks and a part; both decode
    ((128, 1, 40), (1, 0, 1)),        # the middle row takes no token
], ids=["2rows", "3rows-1dead"])
def test_a_chain_of_kernel_steps_holds_the_recurrence_tolerance(lens, live):
    """Tier-1's copy of the ``recurrence`` stage of
    benchmarks/chip/configs/granite-4.0-h-micro/check_reference.py at the
    published head sizes: from the state ``ssd_chunk`` leaves, 64 steps
    through the Pallas kernel (interpreted) against the float32
    ``ssd_token`` chain: outputs and final states within REC_TOL of their
    norms (the kernel's update IS the token's; only y's sum over the state
    axis runs in another order, on the matrix unit on the chip). The same
    chain with the contraction as a default-precision product takes it on a
    TPU (operands rounded to bf16, float32 sums) falls outside: a
    float32 state is not to be read through bf16."""
    h, p, n, steps = 64, 64, 128, 64
    rows, t = len(lens), max(lens)
    x, bm, cm, dt, da, d_skip, _ = _scan_inputs(rows, t + steps, h, p, n, 41)
    lv = jnp.asarray(live, bool)
    _, state0 = jax.jit(ssd.ssd_chunk)(
        jnp.zeros((rows, h, p, n)), x[:, :t], bm[:, :t], cm[:, :t],
        dt[:, :t], da[:, :t], d_skip, jnp.asarray(lens, jnp.int32))
    xs = tuple(jnp.moveaxis(v[:, t:], 1, 0) for v in (x, bm, cm, dt, da))

    def chain(step):
        def one(state, v):
            y, state = step(state, *v)
            return state, y
        return jax.jit(lambda s: jax.lax.scan(one, s, xs))(state0)

    def token(state, x, b, c, dt, da, low=False):
        y, new = ssd.ssd_token(state, x, b, c, dt, da, d_skip)
        if low:
            def bf(v):
                return v.astype(jnp.bfloat16).astype(jnp.float32)
            y = jnp.sum(bf(new) * bf(c)[:, None, None, :], axis=-1) \
                + d_skip[None, :, None] * x
        keep = lv[:, None, None]
        return jnp.where(keep, y, 0.0), jnp.where(keep[..., None], new, state)

    want_s, want_y = chain(token)
    got_s, got_y = chain(lambda s, *v: ssd.ssd_step(
        s, *v, d_skip, lv, interpret=True))
    low_s, low_y = chain(lambda s, *v: token(s, *v, low=True))
    assert _relative(got_y, want_y) < REC_TOL
    assert _relative(got_s, want_s) < REC_TOL
    dead = ~np.asarray(lv)
    np.testing.assert_array_equal(got_s[dead], state0[dead])
    assert not np.any(np.asarray(got_y)[:, dead])
    assert _relative(low_y, want_y) > 4 * REC_TOL
    np.testing.assert_array_equal(low_s, want_s)


@pytest.mark.parametrize("shape,fits", [
    ((64, 64, 128), True), ((4, 16, 128), True), ((4, 16, 32), False),
    ((24, 16, 128), False), ((16, 12, 128), False), ((16, 24, 128), False)])
def test_the_step_kernel_takes_whole_lanes_sublanes_and_blocks(shape, fits):
    """The published state fits; the tiny preset's 32-wide state, heads
    that are not whole blocks of 16, channels that are not whole sublanes
    and channels that are no whole fraction of a row of lanes keep the
    ``jnp`` form, whatever the platform."""
    from production_stack_tpu.ops.pallas.ssd import supports_step_kernel

    assert supports_step_kernel(shape) is fits
    carry = jax.ShapeDtypeStruct((2, 1, *shape), jnp.float32)
    h, p, n = shape
    args = [jax.ShapeDtypeStruct(s, jnp.float32) for s in (
        (2, h, p), (2, n), (2, n), (2, h), (2, h), (h,))]
    text = jax.jit(lambda c, *a: ssd.ssd_step_at(
        c, 0, *a, jnp.ones((2,), bool), interpret=True)).lower(
            carry, *args).as_text()
    assert ("ssd_step_in_place" in text) == fits


def test_the_reference_scan_is_the_programs_token():
    """The reference's recurrence (one sequence, decay given) against
    ``ssd_token`` (a batch, log-decay given): the same equation."""
    x, bm, cm, dt, da, d_skip, _ = _scan_inputs(1, 37, 4, 16, 32, 3)
    want, want_state = ref.ssm_scan(x[0], bm[0], cm[0], dt[0],
                                    jnp.exp(da[0]), d_skip)
    got, state = _token_by_token(x, bm, cm, dt, da, d_skip,
                                 jnp.zeros((1, 4, 16, 32)), jnp.array([37]))
    assert _relative(got[0], want) < 1e-6
    assert _relative(state[0], want_state) < 1e-6
