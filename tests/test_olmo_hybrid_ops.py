"""The pieces under the Olmo-Hybrid family (tests/test_olmo_hybrid.py holds
the engine to the reference): the chunkwise recurrence against the step,
the reference against HF's torch recurrence, the two copies of the
reference, and what ``layer_types`` may be."""

import dataclasses
import filecmp
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.models.config import TINY_OLMO_HYBRID, ModelConfig
from production_stack_tpu.ops import gated_delta as gd
from production_stack_tpu.ops.pallas.gated_delta import supports_step_kernel

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---- (g): the chunkwise form is the recurrence ------------------------------
# The two executions of the decode step (ops/gated_delta.py:gdn_step_at):
# the Pallas kernel, here through the interpreter, and the ``jnp`` form, which
# is what a program lowered for a CPU holds otherwise.
PATHS = pytest.mark.parametrize(
    "interpret", [False, True], ids=["xla", "pallas"])


@PATHS
@pytest.mark.parametrize("t", [1, 63, 64, 65, 200])
def test_g_gdn_chunk_is_gdn_step_applied_t_times(t, interpret):
    b, h, dk, dv = 2, 4, 16, 32
    ks = jax.random.split(jax.random.PRNGKey(t), 7)
    q, k = (jax.random.normal(ks[i], (b, t, h, dk)) for i in (0, 1))
    v = jax.random.normal(ks[2], (b, t, h, dv))
    beta, g = gd.gates(
        jax.random.normal(ks[3], (b, t, h)),
        jax.random.normal(ks[4], (b, t, h)),
        jnp.log(jax.random.uniform(ks[5], (h,), minval=0.1, maxval=16.0)),
        jnp.ones((h,)), True)
    q, k, v = gd.prepare(q, k, v)
    state0 = 0.5 * jax.random.normal(ks[6], (b, h, dk, dv))
    lens = jnp.array([t, max(t - 5, 0)])
    out, state = gd.gdn_chunk(gd.pack_state(state0), q, k, v, g, beta, lens)
    assert state.shape == (b, *gd.packed_shape(h, dk, dv)) == (b, 1, dk, 128)
    state = gd.unpack_state(state, h)
    # The step on the packed state (what decode runs), and beside it the
    # plain per-head recurrence, which it has to equal to the last bit of
    # a float32 sum.
    want_state, packed, outs = state0, gd.pack_state(state0), []
    for i in range(t):
        live = i < lens
        o, packed = gd.gdn_step(packed, q[:, i], k[:, i], v[:, i], g[:, i],
                                beta[:, i], live, interpret=interpret)
        o_plain, stepped = gd.delta_step(
            want_state, q[:, i], k[:, i], v[:, i], g[:, i], beta[:, i])
        want_state = jnp.where(live[:, None, None, None], stepped, want_state)
        assert float(jnp.max(jnp.abs(
            (o - o_plain) * live[:, None, None]))) < 1e-6
        outs.append(o)
    assert float(jnp.max(jnp.abs(
        gd.unpack_state(packed, h) - want_state))) < 1e-6
    valid = (jnp.arange(t)[None, :] < lens[:, None])[..., None, None]
    # Float32 both sides, sums in another order: 1e-5 of values of order 1.
    assert float(jnp.max(jnp.abs((out - jnp.stack(outs, 1)) * valid))) < 1e-5
    assert float(jnp.max(jnp.abs(state - want_state))) < 1e-5


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
