"""The pieces under the Olmo-Hybrid family (tests/test_olmo_hybrid.py holds
the engine to the reference): the chunkwise recurrence against the step,
the reference against HF's torch recurrence, the two copies of the
reference, and what ``layer_types`` may be."""

import dataclasses
import filecmp
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.models.config import TINY_OLMO_HYBRID, ModelConfig
from production_stack_tpu.ops import gated_delta as gd
from production_stack_tpu.ops.pallas.gated_delta import (
    supports_chunk_kernel,
    supports_step_kernel,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---- (g): the chunkwise form is the recurrence ------------------------------
# The two executions of the recurrence (ops/gated_delta.py:gdn_step_at and
# gdn_chunk): the Pallas kernels, here through the interpreter, and the
# ``jnp`` forms, which are what a program lowered for a CPU holds otherwise.
PATHS = pytest.mark.parametrize(
    "interpret", [False, True], ids=["xla", "pallas"])


@functools.partial(jax.jit, static_argnames="interpret")
def _token_by_token(state0, q, k, v, g, beta, lens, interpret):
    """The step on the packed state (what decode runs) over the valid
    tokens in turn, and beside it the plain per-head recurrence: (packed
    state, plain state, o [B, T, H, dv], the largest difference of a valid
    token's o between the two)."""
    def one(carry, xs):
        packed, want = carry
        i, q_i, k_i, v_i, g_i, b_i = xs
        live = i < lens
        o, packed = gd.gdn_step(packed, q_i, k_i, v_i, g_i, b_i, live,
                                interpret=interpret)
        o_plain, stepped = gd.delta_step(want, q_i, k_i, v_i, g_i, b_i)
        want = jnp.where(live[:, None, None, None], stepped, want)
        return (packed, want), (o, jnp.max(jnp.abs(
            (o - o_plain) * live[:, None, None])))

    t = q.shape[1]
    (packed, want), (outs, errs) = jax.lax.scan(
        one, (gd.pack_state(state0), state0),
        (jnp.arange(t), *(jnp.moveaxis(x, 1, 0)
                          for x in (q, k, v, g, beta))))
    return packed, want, jnp.moveaxis(outs, 0, 1), jnp.max(errs)


# (T, the rows' lengths). The first five are no whole chunks of 64 or one
# alone: the ``jnp`` form whatever the execution asked for, but for T = 64.
# The last three are the shapes a prefill dispatch has (T whole chunks: the
# kernel where it is asked for), every kind of row in one batch: none of its
# tokens valid, one, a chunk less one, a whole chunk, a chunk and one, all.
CHUNK_CASES = [(t, (t, max(t - 5, 0))) for t in (1, 63, 64, 65, 200)] + [
    (t, (0, 1, 63, 64, 65, t)) for t in (128, 256, 2048)]


@PATHS
@pytest.mark.parametrize("t,lens", CHUNK_CASES,
                         ids=[f"T{t}x{len(n)}" for t, n in CHUNK_CASES])
def test_g_gdn_chunk_is_gdn_step_applied_t_times(t, lens, interpret):
    b, h, dk, dv = len(lens), 4, 16, 32
    ks = jax.random.split(jax.random.PRNGKey(t), 7)
    q, k = (jax.random.normal(ks[i], (b, t, h, dk)) for i in (0, 1))
    v = jax.random.normal(ks[2], (b, t, h, dv))
    # Gates that allow negative eigenvalues: beta in (0, 2), |beta k.k| <= 2.
    beta, g = gd.gates(
        jax.random.normal(ks[3], (b, t, h)),
        jax.random.normal(ks[4], (b, t, h)),
        jnp.log(jax.random.uniform(ks[5], (h,), minval=0.1, maxval=16.0)),
        jnp.ones((h,)), True)
    q, k, v = gd.prepare(q, k, v)
    state0 = 0.5 * jax.random.normal(ks[6], (b, h, dk, dv))
    lens = jnp.array(lens)
    args = (gd.pack_state(state0), q, k, v, g, beta, lens)
    kernel = interpret and supports_chunk_kernel(t, h, args[0].shape[1:])
    assert kernel == (interpret and t % 64 == 0)
    text = jax.jit(gd.gdn_chunk, static_argnames="interpret").lower(
        *args, interpret=interpret).as_text()
    assert ("gdn_chunk_in_place" in text) == kernel
    out, packed = gd.gdn_chunk(*args, interpret=interpret)
    assert packed.shape == (b, *gd.packed_shape(h, dk, dv)) == (b, 1, dk, 128)
    state = gd.unpack_state(packed, h)
    stepped, want_state, outs, step_err = _token_by_token(
        state0, q, k, v, g, beta, lens, interpret)
    # The step on the packed state equals the plain recurrence to the last
    # bit of a float32 sum.
    assert float(step_err) < 1e-6
    assert float(jnp.max(jnp.abs(
        gd.unpack_state(stepped, h) - want_state))) < 1e-6
    valid = (jnp.arange(t)[None, :] < lens[:, None])[..., None, None]
    # Float32 both sides, sums in another order: 1e-5 of values of order 1,
    # absolute, at every T (the largest read here: 5.0e-6 of o at T = 2048
    # in the ``jnp`` form, 2.3e-6 in the kernel; under 1e-6 below T = 2048).
    tol = 1e-5
    assert float(jnp.max(jnp.abs((out - outs) * valid))) < tol
    assert float(jnp.max(jnp.abs(state - want_state))) < tol
    if kernel:
        # Against the ``jnp`` form on the same inputs; a row with no valid
        # token keeps its state bit for bit, and what the kernel skips (a
        # chunk past its row's length) is zeros.
        want_out, want_packed = gd.gdn_chunk_jnp(*args)
        assert float(jnp.max(jnp.abs((out - want_out) * valid))) < tol
        assert float(jnp.max(jnp.abs(packed - want_packed))) < tol
        dead = np.asarray(lens) == 0
        np.testing.assert_array_equal(
            np.asarray(packed)[dead], np.asarray(args[0])[dead])
        skipped = jnp.arange(t)[None, :] >= -(-lens[:, None] // 64) * 64
        np.testing.assert_array_equal(
            np.asarray(out)[np.asarray(skipped)], 0.0)


# ---- the decode step's kernel (ops/pallas/gated_delta.py), interpreted ------
def _step_inputs(seed, b, h, dk, dv, steps=1, layers=1):
    """(carry [b, layers, H/P, dk, P*dv], then q, k, v, g, beta
    [b, steps, ...]) as a layer hands them to the step: prepared, gated."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    q, k = (jax.random.normal(ks[i], (b, steps, h, dk)) for i in (0, 1))
    v = jax.random.normal(ks[2], (b, steps, h, dv))
    beta, g = gd.gates(
        jax.random.normal(ks[3], (b, steps, h)),
        jax.random.normal(ks[4], (b, steps, h)),
        jnp.log(jax.random.uniform(ks[5], (h,), minval=0.1, maxval=16.0)),
        jnp.ones((h,)), True)
    carry = 0.5 * jax.random.normal(
        ks[6], (b, layers, *gd.packed_shape(h, dk, dv)))
    return (carry, *gd.prepare(q, k, v), g, beta)


def _relative(got, want):
    return float(jnp.max(jnp.abs(got - want))
                 / jnp.maximum(jnp.max(jnp.abs(want)), 1e-30))


@pytest.mark.parametrize("h,dk,dv", [(30, 96, 192), (4, 64, 128)],
                         ids=["published-P2", "P1"])
def test_step_kernel_is_delta_step_token_by_token(h, dk, dv):
    """Eight tokens through the kernel against the plain per-head
    recurrence, a row dropping out on the way: at Olmo-Hybrid-7B's
    published widths (two heads a packed row) and at one head a row."""
    b, steps = 3, 8
    carry, q, k, v, g, beta = _step_inputs(h, b, h, dk, dv, steps)
    assert supports_step_kernel(h, carry.shape[2:])
    want = gd.unpack_state(carry[:, 0], h)
    lens = jnp.array([steps, 5, 0])
    for i in range(steps):
        live = i < lens
        o, carry = gd.gdn_step_at(
            carry, 0, q[:, i], k[:, i], v[:, i], g[:, i], beta[:, i], live,
            interpret=True)
        o_plain, stepped = gd.delta_step(
            want, q[:, i], k[:, i], v[:, i], g[:, i], beta[:, i])
        want = jnp.where(live[:, None, None, None], stepped, want)
        assert _relative(o, o_plain * live[:, None, None]) < 1e-5
        assert _relative(gd.unpack_state(carry[:, 0], h), want) < 1e-5


@pytest.mark.parametrize("at", [0, 1, 2])
def test_step_kernel_touches_only_live_rows_of_layer_at(at):
    """On a carry of three layers: every other layer and every row that is
    not live are bit for bit what they were, a row that is not live gets
    zeros, and the live rows agree with the ``jnp`` form."""
    h, dk, dv, b = 4, 64, 128, 5
    carry, q, k, v, g, beta = _step_inputs(at, b, h, dk, dv, layers=3)
    live = jnp.array([True, False, True, True, False])
    args = (at, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], live)
    o, got = gd.gdn_step_at(carry, *args, interpret=True)
    want_o, want = gd.gdn_step_at(carry, *args)
    others = np.array([i != at for i in range(3)])
    np.testing.assert_array_equal(got[:, others], carry[:, others])
    np.testing.assert_array_equal(got[~live], carry[~live])
    np.testing.assert_array_equal(o[~live], 0.0)
    assert bool(jnp.all(got[live, at] != carry[live, at]))
    assert _relative(got[live], want[live]) < 1e-5
    assert _relative(o, want_o) < 1e-5


def test_step_kernel_with_no_live_row_is_a_no_op():
    carry, q, k, v, g, beta = _step_inputs(3, 4, 4, 64, 128, layers=2)
    o, got = gd.gdn_step_at(
        carry, 1, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
        jnp.zeros((4,), bool), interpret=True)
    np.testing.assert_array_equal(got, carry)
    np.testing.assert_array_equal(o, 0.0)


@PATHS
def test_gdn_step_on_one_layers_state_is_the_step_on_a_carry(interpret):
    """``gdn_step``'s signature of before the kernel (a state of ONE
    layer): the same answer through either execution, and the answer of
    the plain recurrence."""
    h, dk, dv, b = 4, 64, 128, 3
    carry, q, k, v, g, beta = _step_inputs(11, b, h, dk, dv)
    live = jnp.array([True, True, False])
    args = (q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0])
    o, state = gd.gdn_step(carry[:, 0], *args, live, interpret=interpret)
    assert state.shape == carry[:, 0].shape
    want_o, want = gd.delta_step(gd.unpack_state(carry[:, 0], h), *args)
    np.testing.assert_array_equal(state[2], carry[2, 0])
    assert _relative(o[:2], want_o[:2]) < 1e-5
    assert _relative(gd.unpack_state(state, h)[:2], want[:2]) < 1e-5


@pytest.mark.parametrize("h,dk,dv,fits", [
    (30, 96, 192, True), (4, 16, 32, True), (16, 128, 128, True),
    (3, 64, 96, False),      # P*dv is not whole lanes
    (4, 12, 128, False),     # dk is not whole sublanes
    (4, 256, 128, False),    # dk is wider than the tile k and q transpose in
], ids=lambda x: str(x))
def test_the_step_falls_back_where_the_packed_shape_does_not_fit(
        h, dk, dv, fits):
    """Which execution runs is decided by the packed shape alone (and the
    backend): a shape the kernel does not take gets the ``jnp`` form even
    with the interpreter on, and the same answer."""
    carry, q, k, v, g, beta = _step_inputs(5, 2, h, dk, dv)
    assert supports_step_kernel(h, carry.shape[2:]) == fits
    args = (0, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
            jnp.array([True, False]))
    text = jax.jit(gd.gdn_step_at, static_argnames="interpret").lower(
        carry, *args, interpret=True).as_text()
    # (An interpreted kernel lowers to loops of plain operations under
    # the kernel's jitted name.)
    assert ("gdn_step_in_place" in text) == fits
    o, got = gd.gdn_step_at(carry, *args, interpret=True)
    want_o, want = gd.gdn_step_at(carry, *args)
    assert _relative(o, want_o) < 1e-5 and _relative(got, want) < 1e-5


@pytest.mark.parametrize("h,dk,dv", [(30, 96, 192), (4, 64, 128)],
                         ids=["published-P2", "P1"])
def test_chunk_kernel_is_the_jnp_form_at_other_packings(h, dk, dv):
    """The chunk kernel through the interpreter against the ``jnp`` form at
    Olmo-Hybrid-7B's published widths (two heads a packed row, head slices
    that start off a lane tile) and at one head a row: a full row, a row
    that ends inside its second chunk, a row with no token."""
    b, t = 3, 128
    carry, q, k, v, g, beta = _step_inputs(h + 1, b, h, dk, dv, t)
    state, lens = carry[:, 0], jnp.array([t, 70, 0])
    assert supports_chunk_kernel(t, h, state.shape[1:])
    o, got = gd.gdn_chunk(state, q, k, v, g, beta, lens, interpret=True)
    want_o, want = gd.gdn_chunk_jnp(state, q, k, v, g, beta, lens)
    valid = (jnp.arange(t)[None, :] < lens[:, None])[..., None, None]
    assert _relative(o * valid, want_o * valid) < 1e-5
    assert _relative(got, want) < 1e-5
    np.testing.assert_array_equal(got[2], state[2])
    np.testing.assert_array_equal(o[2], 0.0)


@pytest.mark.parametrize("t,h,dk,dv,fits", [
    (128, 30, 96, 192, True), (2048, 4, 16, 32, True),
    (32, 30, 96, 192, False),    # T is not whole chunks (check_reference's row)
    (128, 3, 64, 128, False),    # heads do not pair
    (128, 4, 12, 128, False),    # dk is not whole sublanes
    (128, 6, 64, 96, False),     # P*dv is not whole lanes
    (128, 128, 64, 128, False),  # the gates of 128 heads pass one lane tile
], ids=lambda x: str(x))
def test_the_chunk_falls_back_where_the_shapes_do_not_fit(t, h, dk, dv, fits):
    """Which execution of ``gdn_chunk`` a program holds is decided by the
    shapes alone (and the backend): what the kernel does not take gets the
    ``jnp`` form even with the interpreter on."""
    sds = jax.ShapeDtypeStruct
    packed = gd.packed_shape(h, dk, dv)
    assert supports_chunk_kernel(t, h, packed) == fits
    args = (sds((2, *packed), jnp.float32),
            *(sds((2, t, h, d), jnp.float32) for d in (dk, dk, dv)),
            sds((2, t, h), jnp.float32), sds((2, t, h), jnp.float32),
            sds((2,), jnp.int32))
    text = jax.jit(gd.gdn_chunk, static_argnames="interpret").lower(
        *args, interpret=True).as_text()
    assert ("gdn_chunk_in_place" in text) == fits
    # Without the interpreter a program lowered for a CPU holds the jnp form.
    assert "gdn_chunk_in_place" not in jax.jit(gd.gdn_chunk).lower(
        *args).as_text()


def test_the_chunk_kernel_takes_the_precision_it_is_traced_under(monkeypatch):
    """The kernel's products run at the ``jnp`` form's precision as it
    stands when a program is traced (``check_reference.py``'s control lowers
    it to the default to show that the default fails): a static argument of
    the kernel's jit, so a program traced after the change holds it with no
    cache cleared."""
    sds = jax.ShapeDtypeStruct
    args = (sds((2, 1, 16, 128), jnp.float32),
            *(sds((2, 64, 4, d), jnp.float32) for d in (16, 16, 32)),
            sds((2, 64, 4), jnp.float32), sds((2, 64, 4), jnp.float32),
            sds((2,), jnp.int32))
    assert gd.CHUNK == 64

    def lowered():
        return jax.jit(lambda *a: gd.gdn_chunk(*a, interpret=True)).lower(
            *args).as_text()

    assert "HIGHEST" in lowered()
    monkeypatch.setattr(gd, "_HI", jax.lax.Precision.DEFAULT)
    assert "HIGHEST" not in lowered()


# ---- the reference itself ----------------------------------------------------
@pytest.mark.parametrize("c", [1, 2, 64])
def test_forward_substitution_is_the_unit_lower_inverse(c):
    """``(I + L)^-1`` a row at a time against numpy's inverse in float64,
    with entries as large as the chunkwise form's (|beta k.k| <= 2)."""
    lower = np.tril(np.random.default_rng(c).uniform(
        -0.5, 0.5, (3, 2, c, c)), -1).astype(np.float32)
    got = gd._unit_lower_inverse(jnp.asarray(lower))
    want = np.linalg.inv(np.eye(c) + lower.astype(np.float64))
    # float32 sums of up to 63 products of entries that grow down a column.
    np.testing.assert_allclose(got, want, rtol=1e-4,
                               atol=1e-4 * np.abs(want).max())


def test_the_benchmarks_reference_is_this_reference():
    assert filecmp.cmp(
        os.path.join(ROOT, "tests", "reference", "olmo_hybrid_ref.py"),
        os.path.join(ROOT, "benchmarks", "chip", "configs",
                     "olmo-hybrid-7b-d16", "reference.py"), shallow=False)


def test_reference_recurrence_is_hfs_torch_recurrence():
    """The reference's Gated DeltaNet core against HF's
    ``torch_recurrent_gated_delta_rule`` (Qwen3-Next), beta doubled by the
    caller as ``linear_allow_neg_eigval`` asks."""
    torch = pytest.importorskip("torch")
    hf = pytest.importorskip(
        "transformers.models.qwen3_next.modeling_qwen3_next")
    t, h, dk, dv = 37, 4, 16, 32
    rng = np.random.default_rng(0)
    q, k = (rng.standard_normal((t, h, dk)).astype(np.float32)
            for _ in range(2))
    v = rng.standard_normal((t, h, dv)).astype(np.float32)
    b, a = (rng.standard_normal((t, h)).astype(np.float32) for _ in range(2))
    a_log = np.log(rng.uniform(0.1, 16.0, h)).astype(np.float32)
    beta, g = gd.gates(jnp.asarray(b), jnp.asarray(a), jnp.asarray(a_log),
                       jnp.ones((h,)), True)
    want, _ = hf.torch_recurrent_gated_delta_rule(
        torch.tensor(q)[None], torch.tensor(k)[None], torch.tensor(v)[None],
        g=torch.tensor(np.asarray(g))[None],
        beta=torch.tensor(np.asarray(beta))[None], initial_state=None,
        output_final_state=False, use_qk_l2norm_in_kernel=True)
    qp, kp, vp = gd.prepare(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
    state, outs = jnp.zeros((1, h, dk, dv)), []
    for i in range(t):
        o, state = gd.delta_step(state, qp[None, i], kp[None, i],
                                 vp[None, i], g[None, i], beta[None, i])
        outs.append(o[0])
    assert np.max(np.abs(np.stack(outs) - want[0].numpy())) < 1e-5


def test_layer_types_must_be_whole_periods():
    lin, full = "linear_attention", "full_attention"
    with pytest.raises(ValueError, match="whole number of equal periods"):
        dataclasses.replace(TINY_OLMO_HYBRID, num_layers=7,
                            layer_types=(lin, lin, full) * 2 + (lin,))
    with pytest.raises(ValueError, match="unknown kinds"):
        dataclasses.replace(TINY_OLMO_HYBRID, num_layers=2,
                            layer_types=(lin, "sliding_attention"))
    with pytest.raises(ValueError, match="olmo_hybrid"):
        ModelConfig.from_hf_config({"model_type": "olmo_hybrid_next"})


def test_a_checkpoint_in_hf_layout_loads_into_the_stacks_by_kind(tmp_path):
    """``init_params``' tree written out under HF's names and layouts
    ([out, in] matrices, a [C, 1, W] conv, q / k / v apart, one tensor a
    layer) and read back by models/weights.py: the same tree."""
    pytest.importorskip("safetensors")
    import json

    from safetensors.numpy import save_file

    from production_stack_tpu.models import get_model
    from production_stack_tpu.models.weights import load_hf_params

    mc = TINY_OLMO_HYBRID
    model = get_model(mc)
    params = model.init_params(mc, jax.random.PRNGKey(3), jnp.float32)
    h, dk = mc.linear_num_heads, mc.linear_key_head_dim
    tensors = {}
    for hf_name, (leaf, transpose) in model.HF_TOP_MAP.items():
        x = np.asarray(params[leaf])
        tensors[hf_name] = np.ascontiguousarray(x.T if transpose else x)
    for i, (kind, at) in enumerate(model.layer_slots(mc)):
        lp = {k: np.asarray(v[at]) for k, v in params["layers"][kind].items()}
        if kind == "linear":
            qkv = lp.pop("lin_qkv")
            lp["lin_q"], lp["lin_k"], lp["lin_v"] = (
                qkv[:, :h * dk], qkv[:, h * dk:2 * h * dk], qkv[:, 2 * h * dk:])
            lp["conv_w"] = lp["conv_w"][:, None, :]          # [W, 1, C]
        for suffix, (leaf, transpose) in model.HF_LAYER_MAP.items():
            if leaf in lp:
                x = lp[leaf].T if transpose else lp[leaf]
                tensors[f"model.layers.{i}.{suffix}"] = \
                    np.ascontiguousarray(x)
    save_file(tensors, str(tmp_path / "model.safetensors"))
    loaded = load_hf_params(mc, str(tmp_path), jnp.float32)
    assert jax.tree.structure(loaded) == jax.tree.structure(params)
    for got, want in zip(jax.tree.leaves(loaded), jax.tree.leaves(params)):
        assert got.shape == want.shape
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # A hole in one kind's stack is named by kind.
    del tensors["model.layers.5.linear_attn.A_log"]
    save_file(tensors, str(tmp_path / "model.safetensors"))
    with pytest.raises(ValueError, match="linear/a_log"):
        load_hf_params(mc, str(tmp_path), jnp.float32)
