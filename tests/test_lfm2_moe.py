"""The LFM2-MoE family (gated short convolutions whose state is two tokens a
layer a sequence, beside paged K/V in the few rotary attention layers, and
sigmoid-routed sparse experts behind both) against its plain reference
(tests/reference/lfm2_moe_ref.py), through the engine's own scheduler, block
manager and runner at a tiny preset with float32 activations: the one
module that declares a state AND counters.

What is compared is log-probabilities, not tokens: every generated token's
own log-probability and those of the 20 most likely tokens at its position,
as the served surface returns them (``logprobs=20``), against the
reference's log-softmax at the same ids.

TOL: both sides are float32 with full-precision products on the CPU; they
differ in the ORDER of sums (the grouped matmul over sorted pairs against
dense experts, batched rows, a prompt cut into chunks, the paged kernel's
blocks) over 24 layers. Measured largest difference over every case here:
under 1e-5 (logit spread 1.0). The nine wrong models of
``test_the_tolerance_tells_a_wrong_model`` move the same numbers by 0.3 to
2.8 units (the nearest, ``bias_in_weights``, by 0.296), so 2e-3 leaves both
sides two decades of room.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.models import lfm2_moe
from production_stack_tpu.ops import moe
from tests.lfm2_moe_helpers import (
    CHUNK,
    TOL,
    add,
    cut,
    drive,
    hf_config,
    make_engine,
    prompt,
    ref,
    step,
    worst,
)


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
    """The third chunk starts ON the prompt's last token but one: its first
    taps read the two tokens the slot carried over."""
    seq = add(engine, "b", prompt(130, 2), 4)
    batches = drive(engine)
    assert [b.chunk_lens for b in batches if b.kind == "prefill"] == \
        [[64], [64], [2]]
    assert worst(engine, seq) < TOL


def test_c_a_decode_train_in_which_one_row_ends_early(engine):
    """40 decode steps in trains of 8; in the second train row 1 is given a
    budget of 3 of the 8 steps and goes on afterwards: the 5 steps that
    deliver nothing must leave its conv state as it was and reach no
    expert."""
    seqs = [add(engine, f"c{i}", prompt(20 + 7 * i, 10 + i), n)
            for i, n in enumerate((41, 41, 30))]
    cut = {}
    pairs = engine.runner.fwd_stats_total["decode"]["assignments"]
    taken = []

    def shorten(batch):
        """The first full train that row 1 rides with rows beside it."""
        if batch.kind == "decode" and not cut and batch.num_steps == 8 \
                and len(batch.seqs) == 3:
            i = batch.seqs.index(seqs[1])
            cut["before"], cut["rows"] = batch.decode_steps[i], 3
            batch.decode_steps[i] = 3
        if batch.kind == "decode":
            taken.append(sum(batch.decode_steps))

    while engine.scheduler.has_work():
        step(engine, shorten)
    assert cut == {"before": 8, "rows": 3}
    assert [len(s.output_token_ids) for s in seqs] == [41, 41, 30]
    for seq in seqs:
        assert worst(engine, seq) < TOL
    mc = engine.model_config
    engine.runner._drain_fwd_stats(engine.runner._fwd_stats_noted)
    assert engine.runner.fwd_stats_total["decode"]["assignments"] - pairs \
        == sum(taken) * mc.num_experts_per_tok \
        * (mc.num_layers - mc.first_k_dense_replace)


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


# ---- the tolerance is tight enough -----------------------------------------
@pytest.fixture(scope="module")
def served(engine):
    """129 prompt tokens: the third chunk is the last token alone, so the
    first answer stands right behind a chunk boundary."""
    seq = add(engine, "w", prompt(2 * CHUNK + 1, 70), 40)
    drive(engine)
    assert worst(engine, seq) < TOL
    return seq


@pytest.mark.parametrize("wrong", ref.WRONG)
def test_the_tolerance_tells_a_wrong_model(engine, served, wrong):
    """A prompt of 129 tokens (three chunks) and 40 decoded tokens, against
    the reference with ONE equation wrong: each is far outside TOL."""
    assert worst(engine, served, wrong=(wrong,)) > 10 * TOL


# ---- routing ------------------------------------------------------------------
def test_the_expert_bias_moves_the_choice_and_not_the_weights():
    """A bias that lifts the two weakest experts over every other: they are
    chosen, and their weights are still their own scores over their sum."""
    k0, k1 = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(k0, (50, 32))
    w_r = jax.random.normal(k1, (32, 8)) * 32 ** -0.5
    s = jax.nn.sigmoid(jnp.dot(x, w_r, precision="highest"))
    idx0, w0 = moe.route(x, w_r, jnp.zeros((8,)), 2, 1.0, True,
                         lfm2_moe.ROUTE_EPS)
    np.testing.assert_array_equal(
        np.sort(idx0, -1), np.sort(np.argsort(-s, -1)[:, :2], -1))
    weakest = np.argsort(np.asarray(s), -1)[0, :2]
    bias = jnp.zeros((8,)).at[weakest].set(10.0)
    idx, w = moe.route(x, w_r, bias, 2, 1.0, True, lfm2_moe.ROUTE_EPS)
    assert set(np.asarray(idx[0])) == set(weakest) != set(np.asarray(idx0[0]))
    picked = np.take_along_axis(np.asarray(s), np.asarray(idx), 1)
    np.testing.assert_allclose(
        w, picked / (picked.sum(-1, keepdims=True) + 1e-6), rtol=1e-6)
    # The published 1e-6 is in the sum: two scores of 0.5 weigh 0.5 / (1 +
    # 1e-6) each, where the latent family's 1e-20 would give 0.5 exactly.
    _, halves = moe.route(jnp.zeros((1, 32)), jnp.zeros((32, 8)),
                          jnp.zeros((8,)), 2, 1.0, True, lfm2_moe.ROUTE_EPS)
    np.testing.assert_allclose(halves, 0.5 / (1.0 + 1e-6), rtol=1e-7)
    assert float(halves[0, 0]) < 0.5


def test_the_programs_choices_are_the_references(engine):
    """Share of (token, sparse layer) choices whose top-k SET differs
    between the program's whole forward and the reference's: none in
    float32 over 200 tokens; and the router in bf16 flips several in a
    hundred, each a different function of the token."""
    mc, tokens = engine.model_config, prompt(200, 71)
    *_, chosen = lfm2_moe.forward(
        engine.runner.params, mc, jnp.asarray([tokens], jnp.int32),
        jnp.arange(200, dtype=jnp.int32)[None], jnp.asarray([200]),
        routing=True)
    right, low = [], []
    ref.forward(engine.runner.params, hf_config(mc), tokens, routing=right)
    ref.forward(engine.runner.params, hf_config(mc), tokens,
                ("router_bf16",), routing=low)

    def sets(x):
        return np.sort(np.stack([np.asarray(c) for c in x]), -1)

    assert np.asarray(chosen).shape == (22, 200, 2)
    assert np.mean(np.any(
        np.sort(np.asarray(chosen), -1) != sets(right), -1)) < 0.002
    assert np.mean(np.any(sets(low) != sets(right), -1)) > 0.01
