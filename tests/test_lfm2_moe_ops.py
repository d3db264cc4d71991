"""What models/lfm2_moe.py asks of the shared operations, alone: the causal
depthwise convolution of ops/gated_delta.py with and without the SiLU behind
its sum, at this family's 3 taps and the older hybrids' 4, against a direct
sum, a rectangle (a sequence a row) and a packed row (the sequences end to
end, a state a segment) alike; the per-head norm of queries and keys with rope behind it; and
``from_hf_config``'s refusals, by key."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.models import lfm2_moe
from production_stack_tpu.models.config import (
    LFM2_LAYER_TYPES,
    ModelConfig,
    free_layer_list,
)
from production_stack_tpu.models.llama import _rope_cos_sin, apply_rope, rms_norm
from production_stack_tpu.ops import gated_delta as gd

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CUT = os.path.join(ROOT, "benchmarks", "chip", "configs",
                   "lfm2-8b-a1b-d16", "config.json")


def _direct(x, state, w, bias, silu):
    """y[b, t] = sum_i w[i] * in[b, t - (W-1) + i], the inputs before the
    chunk being ``state``'s, a loop a token and a tap."""
    x, state, w = (np.asarray(v, np.float64) for v in (x, state, w))
    width = w.shape[0]
    ext = np.concatenate([state, x], axis=1)
    y = np.zeros_like(x)
    for t in range(x.shape[1]):
        for i in range(width):
            y[:, t] += w[i] * ext[:, t + i]
    if bias is not None:
        y = y + np.asarray(bias, np.float64)
    return y / (1 + np.exp(-y)) if silu else y


def _inputs(b, t, c, width, seed, states=None):
    """(x [b, t, c], conv state [states or b, W-1, c], w, bias)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (b, t, c)),
            jax.random.normal(ks[1], (states or b, width - 1, c)),
            jax.random.normal(ks[2], (width, c)),
            jax.random.normal(ks[3], (c,)))


TAPS = pytest.mark.parametrize("width", [3, 4])
SILU = pytest.mark.parametrize("silu", [True, False], ids=["silu", "plain"])
BIAS = pytest.mark.parametrize("with_bias", [False, True],
                               ids=["nobias", "bias"])


@TAPS
@SILU
@BIAS
def test_conv_chunk_is_the_direct_sum(width, silu, with_bias):
    x, state, w, bias = _inputs(3, 17, 8, width, width)
    bias = bias if with_bias else None
    lens = jnp.array([17, 5, 0])
    y, new = gd.conv_chunk(x, state, w, lens, bias, silu=silu)
    want = _direct(x, state, w, bias, silu)
    for row, n in enumerate((17, 5, 0)):
        np.testing.assert_allclose(y[row, :n], want[row, :n], rtol=1e-5,
                                   atol=1e-5)
        ext = np.concatenate([state[row], x[row]])
        # The last W - 1 inputs before position n (a row of length 0 keeps
        # its state).
        np.testing.assert_array_equal(new[row], ext[n:n + width - 1])


@TAPS
@SILU
@BIAS
def test_conv_step_is_the_direct_sum_and_spares_dead_rows(width, silu,
                                                          with_bias):
    x, state, w, bias = _inputs(3, 1, 8, width, 10 + width)
    bias = bias if with_bias else None
    live = jnp.array([True, False, True])
    y, new = gd.conv_step(x[:, 0], state, w, live, bias, silu=silu)
    want = _direct(x, state, w, bias, silu)[:, 0]
    np.testing.assert_allclose(y[::2], want[::2], rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(new[1], state[1])
    np.testing.assert_array_equal(new[::2, :-1], state[::2, 1:])
    np.testing.assert_array_equal(new[::2, -1], x[::2, 0])


@TAPS
@SILU
@pytest.mark.parametrize("cuts", [(40,), (16, 24), (1, 1, 38), (39, 1),
                                  (2, 2, 2, 34)],
                         ids=lambda c: "+".join(map(str, c)))
def test_chunk_by_chunk_is_the_whole(width, silu, cuts):
    """A sequence cut into chunks through the state each leaves (pieces
    shorter than the taps among them), and its tail token by token through
    ``conv_step``: the outputs of the whole sequence in one call."""
    x, _, w, _ = _inputs(2, 40, 8, width, 20 + width)
    zeros = jnp.zeros((2, width - 1, 8))
    whole, end = gd.conv_chunk(x, zeros, w, jnp.array([40, 40]), silu=silu)
    state, at, outs = zeros, 0, []
    for n in cuts:
        if n == 1:
            y, state = gd.conv_step(x[:, at], state, w,
                                    jnp.array([True, True]), silu=silu)
            y = y[:, None]
        else:
            y, state = gd.conv_chunk(x[:, at:at + n], state, w,
                                     jnp.array([n, n]), silu=silu)
        outs.append(y)
        at += n
    np.testing.assert_allclose(jnp.concatenate(outs, 1), whole, rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_array_equal(state, end)


def test_the_default_is_the_older_hybrids_silu():
    """Cells 4 and 7's programs call without the argument."""
    x, state, w, _ = _inputs(2, 9, 8, 4, 3)
    lens = jnp.array([9, 9])
    np.testing.assert_array_equal(
        gd.conv_chunk(x, state, w, lens)[0],
        gd.conv_chunk(x, state, w, lens, silu=True)[0])
    plain = gd.conv_chunk(x, state, w, lens, silu=False)[0]
    np.testing.assert_allclose(gd.conv_chunk(x, state, w, lens)[0],
                               jax.nn.silu(plain), rtol=1e-6)


# ---- a packed row: segments end to end, a state a segment ------------------
# Segment lengths of one row (a slot each; zeros are slots that hold nothing)
# and the row's tokens. Among them: a segment of one token, of none (between
# others, first, and last: a dispatch's unused slots), one shorter than the
# W - 1 tokens the state holds, a first segment that is empty so that the
# second begins at the row's token 0, and one that fills the row.
LAYOUTS = pytest.mark.parametrize("seg_lens,t", [
    ((5, 1, 0, 2, 7, 0), 24), ((0, 3, 1, 1, 9), 16), ((16,), 16),
    ((1, 1, 1, 1), 8), ((2, 0, 0, 0), 8), ((3, 2, 3), 8)],
    ids=lambda v: "+".join(map(str, v)) if isinstance(v, tuple) else f"t{v}")


@pytest.mark.parametrize("width", [2, 3, 4])
@SILU
@BIAS
@LAYOUTS
def test_conv_packed_row_is_the_direct_sum(width, silu, with_bias, seg_lens,
                                           t):
    """Every segment of the row is the direct sum over (its state ++ its
    own tokens) and nothing else: its neighbours' tokens lie right before
    its first token in the row and are not read. The state after it is the
    last W - 1 inputs of that; an empty segment keeps its state."""
    x, state, w, bias = _inputs(1, t, 8, width, 30 + width, len(seg_lens))
    bias = bias if with_bias else None
    y, new = gd.conv_packed_row(x, state, w, jnp.array(seg_lens, jnp.int32),
                                bias, silu=silu)
    assert y.shape == x.shape and new.shape == state.shape
    at = 0
    for s, n in enumerate(seg_lens):
        want = _direct(x[:, at:at + n], state[s:s + 1], w, bias, silu)
        np.testing.assert_allclose(y[:, at:at + n], want, rtol=1e-5,
                                   atol=1e-5)
        ext = np.concatenate([state[s], x[0, at:at + n]])
        np.testing.assert_array_equal(new[s], ext[n:n + width - 1])
        at += n


@pytest.mark.parametrize("width", [2, 3, 4])
@SILU
@BIAS
@LAYOUTS
def test_a_row_of_segments_is_the_rectangle_of_the_same_sequences(
        width, silu, with_bias, seg_lens, t):
    """``conv_packed_row`` over segments against ``conv_chunk`` over the
    same sequences a row each from the same states: the same outputs, value
    for value (the same inputs meet the same taps in the same order), and
    the same states after."""
    x, state, w, bias = _inputs(1, t, 8, width, 40 + width, len(seg_lens))
    bias = bias if with_bias else None
    lens = jnp.array(seg_lens, jnp.int32)
    y, new = gd.conv_packed_row(x, state, w, lens, bias, silu=silu)
    rows = np.zeros((len(seg_lens), max(seg_lens), 8), np.float32)
    at = 0
    for s, n in enumerate(seg_lens):
        rows[s, :n] = x[0, at:at + n]
        at += n
    y_rect, new_rect = gd.conv_chunk(jnp.asarray(rows), state, w, lens, bias,
                                     silu=silu)
    at = 0
    for s, n in enumerate(seg_lens):
        np.testing.assert_array_equal(y[0, at:at + n], y_rect[s, :n])
        at += n
    np.testing.assert_array_equal(new, new_rect)


@pytest.mark.parametrize("width", [2, 3, 4])
@SILU
@pytest.mark.parametrize("cuts", [(13, 2, 25), (1, 1, 38), (38, 1, 1),
                                  (20, 20, 0)],
                         ids=lambda c: "+".join(map(str, c)))
def test_a_prompt_in_three_packed_dispatches_through_its_slot_is_the_whole(
        width, silu, cuts):
    """A 40-token prompt prefilled as a segment of three successive packed
    rows, between two neighbours whose tokens change every dispatch, its
    state carried from row to row in its slot (of three): the outputs and
    the final state of ``conv_chunk`` over the whole prompt from zeros."""
    x, _, w, _ = _inputs(1, 40, 8, width, 50 + width)
    whole, end = gd.conv_chunk(x, jnp.zeros((1, width - 1, 8)), w,
                               jnp.array([40]), silu=silu)
    slots = jax.random.normal(jax.random.PRNGKey(7), (3, width - 1, 8))
    slots = slots.at[1].set(0.0)        # the prompt's slot: a fresh sequence
    at, outs = 0, []
    for d, n in enumerate(cuts):
        before, after = 3 + d, 5 - d    # the neighbours' chunks
        others = jax.random.normal(jax.random.PRNGKey(60 + d),
                                   (1, before + after, 8))
        row = jnp.concatenate([others[:, :before], x[:, at:at + n],
                               others[:, before:]], axis=1)
        row = jnp.pad(row, ((0, 0), (0, 64 - row.shape[1]), (0, 0)))
        y, slots = gd.conv_packed_row(
            row, slots, w, jnp.array([before, n, after], jnp.int32),
            silu=silu)
        outs.append(y[:, before:before + n])
        at += n
    np.testing.assert_array_equal(jnp.concatenate(outs, 1), whole)
    np.testing.assert_array_equal(slots[1:2], end)


def test_a_packed_row_keeps_the_activations_dtype_and_a_float32_sum():
    """bf16 inputs and state: the sum is made in float32 and rounded once,
    as ``conv_chunk``'s is (the same values, bit for bit)."""
    x, state, w, _ = _inputs(1, 8, 8, 3, 9, states=2)
    x, state, w = (v.astype(jnp.bfloat16) for v in (x, state, w))
    lens = jnp.array([5, 3], jnp.int32)
    y, new = gd.conv_packed_row(x, state, w, lens, silu=False)
    assert y.dtype == new.dtype == jnp.bfloat16
    rows = jnp.stack([x[0, :5], jnp.pad(x[0, 5:], ((0, 2), (0, 0)))])
    y_rect, new_rect = gd.conv_chunk(rows, state, w, lens, silu=False)
    np.testing.assert_array_equal(y[0, :5], y_rect[0])
    np.testing.assert_array_equal(y[0, 5:], y_rect[1, :3])
    np.testing.assert_array_equal(new, new_rect)


# ---- the per-head norm ----------------------------------------------------------
@pytest.mark.parametrize("heads", [1, 4, 32])
def test_the_head_norm_is_one_weight_for_every_head_then_rope(heads):
    b, t, dh = 2, 7, 64
    k0, k1 = jax.random.split(jax.random.PRNGKey(heads))
    x = 3.0 * jax.random.normal(k0, (b, t, heads, dh))
    w = jax.random.uniform(k1, (dh,), minval=0.5, maxval=1.5)
    pos = jnp.arange(t)[None] + jnp.array([[0], [100]])
    cos, sin = _rope_cos_sin(pos, dh, 1e6)
    got = lfm2_moe.head_norm_rope(x, w, 1e-5, cos, sin)
    # Each head on its own: llama's norm of the head's 64 lanes, then
    # llama's rope.
    want = apply_rope(rms_norm(x, w, 1e-5), cos, sin)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # Not a norm across the heads: scaling one head leaves the others.
    if heads > 1:
        scaled = x.at[:, :, 0].multiply(50.0)
        again = lfm2_moe.head_norm_rope(scaled, w, 1e-5, cos, sin)
        np.testing.assert_allclose(again[:, :, 1:], got[:, :, 1:], rtol=1e-6)
        np.testing.assert_allclose(again[:, :, 0], got[:, :, 0], rtol=1e-3)


def test_the_head_norm_computes_in_float32_whatever_the_activations():
    b, t, h, dh = 1, 5, 4, 64
    x = jax.random.normal(jax.random.PRNGKey(0), (b, t, h, dh))
    w = jnp.linspace(0.5, 1.5, dh)
    cos, sin = _rope_cos_sin(jnp.arange(t)[None] + 1000, dh, 1e6)
    exact = lfm2_moe.head_norm_rope(x.astype(jnp.bfloat16).astype(
        jnp.float32), w.astype(jnp.bfloat16).astype(jnp.float32), 1e-5,
        cos, sin)
    got = lfm2_moe.head_norm_rope(x.astype(jnp.bfloat16),
                                  w.astype(jnp.bfloat16), 1e-5, cos, sin)
    assert got.dtype == jnp.bfloat16
    # ONE rounding, at the end: half a bf16 ulp of the value.
    np.testing.assert_allclose(got.astype(jnp.float32), exact, rtol=2 ** -8)


# ---- config.json: what is refused -------------------------------------------------
def cut() -> dict:
    with open(CUT) as f:
        return json.load(f)


@pytest.mark.parametrize("change,named", [
    ({"conv_bias": True}, "conv_bias"),
    ({"rope_scaling": {"rope_type": "yarn", "factor": 4.0}}, "rope_scaling"),
    ({"conv_L_cache": 1}, "conv_L_cache"),
    ({"num_experts": 0}, "num_experts"),
    ({"num_shared_experts": 1}, "num_shared_experts"),
    ({"hidden_act": "gelu"}, "hidden_act"),
])
def test_what_the_module_does_not_implement_is_refused_by_its_key(change,
                                                                  named):
    with pytest.raises(ValueError, match="lfm2_moe: not supported") as err:
        ModelConfig.from_hf_config({**cut(), **change})
    assert named in str(err.value)


def test_no_expert_bias_is_served_as_a_zero_bias():
    mc = ModelConfig.from_hf_config({**cut(), "use_expert_bias": False})
    assert not mc.use_expert_bias
    assert "router_bias" not in lfm2_moe.required_layer_leaves(mc)["sparse"]
    params = {"layers": {
        "conv": {"conv_w": jnp.zeros((12, 3, 8))},
        "sparse": {"w_router": jnp.zeros((14, 8, 32), jnp.float32)}},
        "embed": jnp.zeros((4, 8))}
    done = lfm2_moe.finish_params(mc, params)
    bias = done["layers"]["sparse"]["router_bias"]
    assert bias.shape == (14, 32) and bias.dtype == jnp.float32 \
        and not np.any(bias)


@pytest.mark.parametrize("types,dense,why", [
    (LFM2_LAYER_TYPES[:5], 2, "5 entries for 6 layers"),
    (("conv", "mamba", "full_attention", "conv", "conv", "conv"), 2,
     "unknown kinds"),
    (("conv",) * 6, 2, "needs a layer of each"),
    (("full_attention",) * 6, 0, "needs a layer of each"),
    (("conv", "full_attention", "conv", "conv", "conv", "conv"), 2,
     "leading dense layers must be conv"),
    (("conv", "full_attention") * 3, 6, "leading dense layers"),
])
def test_a_layer_list_is_refused_for_what_the_module_cannot_scan(types, dense,
                                                                 why):
    with pytest.raises(ValueError, match=why):
        free_layer_list(types, 6, dense, ("conv", "full_attention"))


def test_an_unknown_model_type_is_still_refused():
    with pytest.raises(ValueError, match="Unsupported model_type: lfm3"):
        ModelConfig.from_hf_config({**cut(), "model_type": "lfm3"})
