"""The Granite 4.0 hybrid family (Mamba-2 state-space layers with a float32
state and a conv state a sequence, beside paged K/V in the few attention
layers, which carry no position) against its plain reference
(tests/reference/granite_hybrid_ref.py), through the engine's own scheduler,
block manager and runner at a tiny preset with float32 activations.

What is compared is log-probabilities, not tokens: every generated token's
own log-probability and those of the 20 most likely tokens at its position,
as the served surface returns them (``logprobs=20``), against the
reference's log-softmax at the same ids.

TOL: both sides are float32 with full-precision products on the CPU; they
differ in the ORDER of sums (the chunkwise scan against the token-by-token
recurrence, batched rows, a prompt cut into chunks) over 8 layers. Measured
largest difference over every case here: under 1e-4 (logit spread 0.34).
The nine wrong models of ``test_the_tolerance_tells_a_wrong_model`` move
the same numbers by 0.25 to several units, so 5e-3 leaves both sides room.
"""

import dataclasses
import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.engine import ServingEngine
from production_stack_tpu.engine.sampling import SamplingParams
from production_stack_tpu.engine.scheduler import Sequence
from production_stack_tpu.models import config as model_configs
from production_stack_tpu.models import get_model, granite_hybrid
from production_stack_tpu.models.config import (
    PERIOD_RULES,
    TINY_GRANITE_HYBRID,
    TINY_OLMO_HYBRID,
    ModelConfig,
    layer_period,
)
from production_stack_tpu.ops import ssd

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tests", "reference"))
import granite_hybrid_ref as ref  # noqa: E402

TOL = 5e-3
TOP = 20
PUBLISHED = os.path.join(ROOT, "benchmarks", "chip", "configs",
                         "granite-4.0-h-micro", "config.json")


def hf_config(mc: ModelConfig) -> dict:
    """The HF config.json keys the reference reads, from a ModelConfig."""
    return {
        "hidden_size": mc.hidden_size, "num_hidden_layers": mc.num_layers,
        "num_attention_heads": mc.num_heads,
        "num_key_value_heads": mc.num_kv_heads, "head_dim": mc.head_dim,
        "rms_norm_eps": mc.rms_norm_eps,
        "layer_types": list(mc.layer_types),
        "mamba_n_heads": mc.mamba_n_heads, "mamba_d_head": mc.mamba_d_head,
        "mamba_d_state": mc.mamba_d_state,
        "mamba_conv_bias": mc.mamba_conv_bias,
        "embedding_multiplier": mc.embedding_multiplier,
        "attention_multiplier": mc.attention_multiplier,
        "residual_multiplier": mc.residual_multiplier,
        "logits_scaling": mc.logits_scaling,
    }


def make_engine(model="tiny-granite-hybrid", **over) -> ServingEngine:
    cfg = dict(model=model, max_model_len=512, num_kv_blocks=128,
               num_decode_steps=8, dtype="float32", max_num_seqs=8,
               max_num_batched_tokens=64, max_prefill_seqs=8)
    cfg.update(over)
    return ServingEngine(EngineConfig(**cfg))


def prompt(n: int, salt: int):
    return [int(x) for x in np.random.default_rng(salt).integers(1, 512, n)]


def add(eng, name, tokens, max_tokens) -> Sequence:
    seq = Sequence(name, list(tokens), SamplingParams(
        temperature=0.0, max_tokens=max_tokens, ignore_eos=True,
        logprobs=TOP))
    eng.scheduler.add_sequence(seq)
    return seq


def step(eng, edit=None):
    """One dispatch, synchronously: schedule, (edit), run, apply."""
    batch = eng.scheduler.schedule()
    if edit is not None:
        edit(batch)
    tokens, lps = eng.runner.execute(batch, 0)
    eng.scheduler.update_after_step(batch, tokens, lps)
    return batch


def drive(eng) -> list:
    batches = []
    while eng.scheduler.has_work():
        batches.append(step(eng))
    return batches


def worst(eng, seq, wrong=()) -> float:
    """Largest |log-probability difference| of a finished sequence's
    outputs against the reference over the same tokens."""
    mc = eng.model_config
    tokens = seq.all_token_ids
    logits = ref.forward(eng.runner.params, hf_config(mc), tokens[:-1], wrong)
    logp = np.asarray(jax.nn.log_softmax(logits, axis=-1))
    n_prompt = len(seq.prompt_token_ids)
    assert len(seq.output_logprobs) == len(seq.output_token_ids)
    diffs = []
    for i, (chosen, top) in enumerate(seq.output_logprobs):
        row = logp[n_prompt - 1 + i]
        diffs.append(chosen - row[seq.output_token_ids[i]])
        assert len(top) == TOP
        diffs += [lp - row[tok] for tok, lp in top]
    # A reference that overflowed (a wrong model may) is as far as can be.
    return float(np.max(np.nan_to_num(np.abs(diffs), nan=np.inf)))


@pytest.fixture(scope="module")
def engine():
    return make_engine()


# ---- the engine's path against the reference --------------------------------
def test_a_prefill_of_one_chunk(engine):
    seq = add(engine, "a", prompt(40, 1), 1)
    batches = drive(engine)
    assert [b.kind for b in batches] == ["prefill"]
    assert worst(engine, seq) < TOL


def test_b_a_prompt_crossing_three_prefill_chunks_through_its_slot(engine):
    seq = add(engine, "b", prompt(150, 2), 4)
    batches = drive(engine)
    assert [b.chunk_lens for b in batches if b.kind == "prefill"] == \
        [[64], [64], [22]]
    assert worst(engine, seq) < TOL


def test_c_a_decode_train_in_which_one_row_ends_early(engine):
    """40 decode steps in trains of 8; in the second train row 1 is given a
    budget of 3 of the 8 steps and goes on afterwards: the 5 steps that
    deliver nothing must leave its state and conv state as they were."""
    seqs = [add(engine, f"c{i}", prompt(20 + 7 * i, 10 + i), n)
            for i, n in enumerate((41, 41, 30))]
    cut = {}

    def shorten(batch):
        """The first full train that row 1 rides with rows beside it."""
        if cut or batch.kind != "decode" or batch.num_steps != 8 \
                or len(batch.seqs) < 3:
            return
        i = batch.seqs.index(seqs[1])
        cut["before"], cut["rows"] = batch.decode_steps[i], len(batch.seqs)
        batch.decode_steps[i] = 3

    while engine.scheduler.has_work():
        step(engine, shorten)
    assert cut == {"before": 8, "rows": 3}
    assert [len(s.output_token_ids) for s in seqs] == [41, 41, 30]
    for seq in seqs:
        assert worst(engine, seq) < TOL


def test_d_rows_of_unequal_length_in_one_prefill_rectangle():
    """Five rows in one rectangle; two are shorter than the convolution's
    four taps, so the conv state they leave holds zeros from before the
    sequence."""
    engine = make_engine(max_num_batched_tokens=1024)
    seqs = [add(engine, f"d{i}", prompt(n, 20 + i), 3)
            for i, n in enumerate((5, 12, 2, 3, 11))]
    batches = drive(engine)
    assert batches[0].kind == "prefill" and len(batches[0].seqs) == 5
    for seq in seqs:
        assert worst(engine, seq) < TOL


def test_e_preempt_and_recompute(engine):
    seq = add(engine, "e", prompt(70, 30), 20)
    other = add(engine, "e2", prompt(30, 31), 20)
    for _ in range(4):
        step(engine)
    assert 0 < len(seq.output_token_ids) < 20
    slot, in_use = seq.state_slot, engine.block_manager.state_slots_in_use
    engine.scheduler._preempt(seq)
    assert seq.state_slot == 0 and not seq.block_ids
    assert engine.block_manager.state_slots_in_use == in_use - 1
    drive(engine)
    assert slot and len(seq.output_token_ids) == 20
    assert worst(engine, seq) < TOL and worst(engine, other) < TOL


def test_f_a_second_request_on_a_freed_slot_starts_from_zeros(engine):
    first = add(engine, "f1", prompt(33, 40), 9)
    step(engine)
    slot = first.state_slot
    drive(engine)
    assert slot and engine.block_manager.state_slots_in_use == 0
    second = add(engine, "f2", prompt(21, 41), 9)
    step(engine)
    assert second.state_slot == slot
    drive(engine)
    assert worst(engine, second) < TOL


def test_g_a_prefix_hit_is_unserved_and_the_answer_is_the_cold_ones(engine):
    bm = engine.block_manager
    shared = prompt(64, 80)
    first = add(engine, "p1", shared + prompt(10, 81), 2)
    drive(engine)
    hits, unserved = bm.prefix_hits_total, bm.prefix_hits_unserved_total
    second = add(engine, "p2", shared + prompt(12, 82), 2)
    drive(engine)
    assert second.num_cached_tokens == 0 and bm.prefix_hits_total == hits
    assert bm.prefix_hits_unserved_total == unserved + 64
    assert worst(engine, first) < TOL and worst(engine, second) < TOL


@pytest.mark.parametrize("attn_impl,d_state", [
    ("window", 32), ("paged", 32), ("paged", 128)])
def test_h_decode_through_the_state_slots_and_the_pool(monkeypatch,
                                                       attn_impl, d_state):
    """Both ``attn_impl``s: the window path, and the paged decode kernel
    (interpreted on the CPU) over 64-lane KV heads paired into rows of 128
    lanes, four query heads a row; with a state of whole lanes the step
    kernel (interpreted) too."""
    mc = dataclasses.replace(TINY_GRANITE_HYBRID, mamba_d_state=d_state,
                             name=f"tiny-granite-{attn_impl}-{d_state}")
    monkeypatch.setitem(model_configs.NAMED_CONFIGS, mc.name, mc)
    eng = make_engine(mc.name, attn_impl=attn_impl)
    assert eng.runner.attn_impl == attn_impl
    # 64-lane KV heads, two to a paged row of 128 lanes.
    assert eng.model_config.head_dim_ == 64
    assert eng.runner.kv_k.shape[1::2] == (1, 128)
    seqs = [add(eng, f"h{i}", prompt(n, 50 + i), 12)
            for i, n in enumerate((70, 18))]
    drive(eng)
    for seq in seqs:
        assert worst(eng, seq) < TOL


# ---- the tolerance is tight enough -----------------------------------------
@pytest.fixture(scope="module")
def served(engine):
    seq = add(engine, "w", prompt(90, 70), 40)
    drive(engine)
    assert worst(engine, seq) < TOL
    return seq


@pytest.mark.parametrize("wrong", ref.WRONG)
def test_the_tolerance_tells_a_wrong_model(engine, served, wrong):
    """A prompt of 90 tokens (two chunks) and 40 decoded tokens, against
    the reference with ONE equation wrong: each is far outside TOL."""
    assert worst(engine, served, wrong=(wrong,)) > 10 * TOL


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
    state, outs = state0, []
    for i in range(x.shape[1]):
        y, state = ssd.ssd_step(state, x[:, i], bm[:, i], cm[:, i], dt[:, i],
                                da[:, i], d_skip, i < lens)
        outs.append(y)
    return jnp.stack(outs, axis=1), state


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


# ---- config.json: what is read, what is refused ------------------------------
def published() -> dict:
    with open(PUBLISHED) as f:
        return json.load(f)


def test_from_hf_config_reads_the_published_config():
    mc = ModelConfig.from_hf_config(published(), name="granite")
    assert (mc.arch, mc.num_layers, mc.hidden_size, mc.intermediate_size) \
        == ("granite_hybrid", 40, 2048, 8192)
    assert (mc.num_heads, mc.num_kv_heads, mc.head_dim_) == (32, 8, 64)
    assert (mc.mamba_n_heads, mc.mamba_d_head, mc.mamba_d_state,
            mc.mamba_d_conv, mc.mamba_conv_bias, mc.mamba_chunk_size) == \
        (64, 64, 128, 4, True, 256)
    assert (mc.embedding_multiplier, mc.attention_multiplier,
            mc.residual_multiplier, mc.logits_scaling) == \
        (12.0, 0.015625, 0.22, 8.0)
    assert mc.rope_theta is None and mc.tie_word_embeddings
    assert (mc.vocab_size, mc.max_position_embeddings) == (100352, 131072)
    assert [i for i, t in enumerate(mc.layer_types) if t == "attention"] \
        == [5, 15, 25, 35]
    assert granite_hybrid.segments(mc) == (5, 9, 4)
    specs = granite_hybrid.cache_specs(mc)
    # 8 KV heads of 64 lanes as 4 rows of 128: the same 8 KiB a token.
    assert granite_hybrid.kv_pack(mc) == 2
    assert specs.paged_kv == (4, 4, 128)
    assert [(s.name, s.layers, s.shape, s.dtype) for s in specs.state] == [
        ("ssm", 36, (64, 64, 128), "float32"),
        ("conv", 36, (3 * 4352 // 128, 128), None)]


@pytest.mark.parametrize("change,named", [
    ({"num_local_experts": 64}, "num_local_experts"),
    ({"position_embedding_type": "rope"}, "position_embedding_type"),
    ({"mamba_n_groups": 2}, "mamba_n_groups"),
    ({"mamba_proj_bias": True}, "mamba_proj_bias"),
    ({"attention_bias": True}, "attention_bias"),
    ({"hidden_act": "gelu"}, "hidden_act"),
    ({"mamba_expand": 4}, "mamba_expand"),
])
def test_an_unsupported_sibling_is_refused_by_its_key(change, named):
    with pytest.raises(ValueError, match="granitemoehybrid: not supported") \
            as err:
        ModelConfig.from_hf_config({**published(), **change})
    assert named in str(err.value)


def test_the_served_tree_has_the_published_parameter_count():
    """By hand (ISSUE 40's arithmetic) and from the tree ``init_params``
    makes, as shapes: nothing is allocated."""
    mamba = 2048 * 8512 + 4096 * 2048 + 4352 * 4 + 4352 + 4096 + 3 * 64 \
        + 2048 * 16384 + 8192 * 2048 + 2 * 2048
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512 \
        + 2048 * 16384 + 8192 * 2048 + 2 * 2048
    assert (mamba, attention) == (76_182_976, 60_821_504)
    by_hand = 36 * mamba + 4 * attention + 100352 * 2048 + 2048
    assert by_hand == 3_191_396_096
    mc = ModelConfig.from_hf_config(published())
    tree = jax.eval_shape(
        lambda: granite_hybrid.init_params(mc, jax.random.PRNGKey(0)))
    assert sum(x.size for x in jax.tree.leaves(tree)) == by_hand
    assert set(tree["layers"]["mamba"]) | {"in_proj"} == \
        granite_hybrid.required_layer_leaves(mc)["mamba"] \
        | {"in_zx", "in_dt"}
    assert {k: v.dtype for k, v in tree["layers"]["mamba"].items()
            if v.dtype == jnp.float32}.keys() == \
        set(granite_hybrid.FLOAT32_LEAVES)


M, A = PERIOD_RULES["granite_hybrid"]["kinds"]
LIN, FULL = "linear_attention", "full_attention"


@pytest.mark.parametrize("types,rules,period", [
    ((LIN, LIN, LIN, FULL) * 2, {}, (LIN, LIN, LIN, FULL)),
    (TINY_OLMO_HYBRID.layer_types, {}, (LIN, LIN, LIN, FULL)),
    ((M, M, A, M) * 2, PERIOD_RULES["granite_hybrid"], (M, M, A, M)),
    ((M,) * 5 + (A,) + (M,) * 4, PERIOD_RULES["granite_hybrid"],
     (M,) * 5 + (A,) + (M,) * 4),
    ((M, M, A) * 3, PERIOD_RULES["granite_hybrid"], (M, M, A)),
    ((A, M) * 2, PERIOD_RULES["granite_hybrid"], (A, M)),
])
def test_layer_period_reads_both_kinds_of_list(types, rules, period):
    assert layer_period(types, len(types), **rules) == period


@pytest.mark.parametrize("types,rules,why", [
    # olmo's rule is as it was: the full layer closes the period.
    ((LIN, FULL, LIN, LIN, FULL, LIN), {}, "whole number of equal periods"),
    ((FULL, LIN, LIN) * 2, {}, "closed by one full_attention"),
    ((M, M, A, M, A, M, M, M), PERIOD_RULES["granite_hybrid"],
     "whole number of equal periods"),
    ((M, M, A, M, M, M, A), PERIOD_RULES["granite_hybrid"],
     "whole number of equal periods"),
    ((M,) * 4, PERIOD_RULES["granite_hybrid"], "around one attention"),
    ((A,) * 4, PERIOD_RULES["granite_hybrid"], "whole number"),
    ((M, LIN, A), PERIOD_RULES["granite_hybrid"], "unknown kinds"),
])
def test_layer_period_refuses_what_is_not_whole_equal_periods(types, rules,
                                                              why):
    with pytest.raises(ValueError, match=why):
        layer_period(types, len(types), **rules)


def test_a_models_kinds_are_its_own():
    with pytest.raises(ValueError, match="unknown kinds"):
        dataclasses.replace(TINY_GRANITE_HYBRID,
                            layer_types=TINY_OLMO_HYBRID.layer_types)
    with pytest.raises(ValueError, match="unknown kinds"):
        dataclasses.replace(TINY_OLMO_HYBRID,
                            layer_types=TINY_GRANITE_HYBRID.layer_types)


@pytest.mark.parametrize("types", [
    ("mamba", "mamba", "attention"), ("attention", "mamba", "mamba")])
def test_the_attention_layer_may_close_or_open_its_period(monkeypatch, types):
    """The forward's segments where the tail (or the head) segment is
    empty: the whole sequence in one call against the reference."""
    mc = dataclasses.replace(TINY_GRANITE_HYBRID, num_layers=6,
                             layer_types=types * 2)
    model = get_model(mc)
    params = model.init_params(mc, jax.random.PRNGKey(1), jnp.float32)
    toks = jnp.asarray(prompt(64, 5))[None]
    hidden, k_new, _, _ = model.forward(
        params, mc, toks, jnp.arange(64)[None], jnp.array([64]))
    assert k_new.shape[0] == 2
    got = model.compute_logits(params, mc, hidden)[0]
    want = ref.forward(params, hf_config(mc), toks[0])
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4


def test_a_checkpoint_in_hf_layout_loads_into_the_stacks_by_kind(tmp_path):
    """``init_params``' tree written out under HF's names and layouts
    ([out, in] matrices, a [C, 1, W] conv, one tensor a layer, no
    ``lm_head``) and read back by models/weights.py: the same tree, the
    scan's three leaves in float32."""
    pytest.importorskip("safetensors")
    from safetensors.numpy import save_file

    from production_stack_tpu.models.weights import load_hf_params

    mc = TINY_GRANITE_HYBRID
    params = granite_hybrid.init_params(mc, jax.random.PRNGKey(3),
                                        jnp.float32)
    ours_to_hf = {v[0]: (k, v[1])
                  for k, v in granite_hybrid.HF_LAYER_MAP.items()}
    tensors = {"model.embed_tokens.weight": np.asarray(params["embed"]),
               "model.norm.weight": np.asarray(params["final_norm"])}
    for i, (kind, at) in enumerate(granite_hybrid.layer_slots(mc)):
        stacks = dict(params["layers"][kind])
        if kind == "mamba":      # the checkpoint's one in_proj: z | xBC | dt
            stacks["in_proj"] = jnp.concatenate(
                [stacks.pop("in_zx"), stacks.pop("in_dt")], axis=-1)
        for leaf, stack in stacks.items():
            name, transpose = ours_to_hf[leaf]
            x = np.asarray(stack[at])
            if leaf == "conv_w":
                x = x[:, None, :]                       # [W, 1, C]
            tensors[f"model.layers.{i}.{name}"] = np.ascontiguousarray(
                x.T if transpose else x)
    save_file(tensors, str(tmp_path / "model.safetensors"))
    loaded = load_hf_params(mc, str(tmp_path), jnp.float32)
    assert "lm_head" not in loaded
    flat_want = jax.tree_util.tree_leaves_with_path(params)
    flat_got = dict(jax.tree_util.tree_leaves_with_path(loaded))
    assert len(flat_want) == len(flat_got)
    for path, want in flat_want:
        np.testing.assert_array_equal(flat_got[path], want, str(path))
        assert flat_got[path].dtype == jnp.float32


# ---- what the served surface says ---------------------------------------------
@pytest.mark.parametrize("d_state,path", [(32, "xla"), (128, "pallas")])
async def test_the_served_surface_says_what_the_step_and_the_prefill_hold(
        monkeypatch, d_state, path):
    """``GET /debug/programs``: ``ssd_step`` names the execution a decode
    program holds (the runner's Pallas interpret switch reaches the step
    kernel where the state is whole lanes wide), a prefill line says
    whether the pool is read in place; ``GET /version`` the state's bytes a
    sequence."""
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from production_stack_tpu.server.api_server import APIServer

    mc = dataclasses.replace(TINY_GRANITE_HYBRID, mamba_d_state=d_state,
                             name=f"tiny-granite-says-{path}")
    monkeypatch.setitem(model_configs.NAMED_CONFIGS, mc.name, mc)
    eng = make_engine(mc.name, attn_impl="paged")
    client = TestClient(TestServer(APIServer(eng).build_app()))
    await client.start_server()
    try:
        done = await asyncio.gather(*(client.post("/v1/completions", json={
            "model": mc.name, "prompt": prompt(12, 70 + i),
            "max_tokens": 9, "temperature": 0, "ignore_eos": True})
            for i in range(2)))
        assert [r.status for r in done] == [200] * 2
        programs = (await (await client.get("/debug/programs")).json())[
            "programs"]
        version = await (await client.get("/version")).json()
    finally:
        await client.close()
    assert {p["program"] for p in programs} == {"decode", "prefill"}
    for p in programs:
        assert "gdn_step" not in p
        assert p.get("ssd_step") == \
            (path if p["program"] == "decode" else None)
        if p["program"] == "prefill":
            assert p["prefill_reads_pool"] is eng.runner.prefill_reads_pool
    specs = granite_hybrid.cache_specs(mc)
    a_sequence = sum(
        s.layers * int(np.prod(s.shape)) * (4 if s.dtype else 4)
        for s in specs.state)
    assert version["engine"]["state_bytes"] == \
        eng.runner.state_pool_bytes == a_sequence * eng.runner.num_state_slots


def test_kv_heads_pair_only_where_they_make_whole_lanes():
    mc = TINY_GRANITE_HYBRID
    assert granite_hybrid.kv_pack(mc) == 2
    assert granite_hybrid.cache_specs(mc).paged_kv == (2, 1, 128)
    for change, pack, kv in (
            ({"head_dim": 128}, 1, (2, 2, 128)),
            ({"head_dim": 32, "num_kv_heads": 4}, 4, (2, 1, 128)),
            # Three KV heads do not pair: the narrow rows stay.
            ({"num_heads": 3, "num_kv_heads": 3}, 1, (2, 3, 64))):
        other = dataclasses.replace(mc, **change)
        assert granite_hybrid.kv_pack(other) == pack
        assert granite_hybrid.cache_specs(other).paged_kv == kv


@pytest.mark.parametrize("change", [
    {"num_heads": 3, "num_kv_heads": 3}, {"head_dim": 128},
    {"head_dim": 32, "num_heads": 8, "num_kv_heads": 4}],
    ids=["unpaired-64", "whole-128", "four-of-32"])
def test_the_forward_is_the_reference_whatever_the_pairing(change):
    mc = dataclasses.replace(TINY_GRANITE_HYBRID, **change)
    model = get_model(mc)
    params = model.init_params(mc, jax.random.PRNGKey(2), jnp.float32)
    toks = jnp.asarray(prompt(64, 6))[None]
    hidden, _, _, _ = model.forward(
        params, mc, toks, jnp.arange(64)[None], jnp.array([64]))
    got = model.compute_logits(params, mc, hidden)[0]
    want = ref.forward(params, hf_config(mc), toks[0])
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4


@pytest.mark.parametrize("flags,named", [
    ({"speculative_num_tokens": 3, "speculative_model": "tiny-llama"},
     "speculative"),
    ({"kv_offload_cpu": True}, "offload"),
    ({"kv_cache_dtype": "int8"}, "int8"),
    ({"tensor_parallel_size": 2}, "parallelism"),
    ({"lora_modules": {"a": "/nonexistent"}}, "LoRA"),
])
def test_what_state_cannot_follow_is_refused_at_start(flags, named):
    with pytest.raises(ValueError, match="recurrent state") as err:
        make_engine(**flags)
    assert named.lower() in str(err.value).lower()
