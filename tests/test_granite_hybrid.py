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

import jax
import numpy as np
import pytest

from production_stack_tpu.engine.sampling import SamplingParams
from production_stack_tpu.engine.scheduler import Sequence
from production_stack_tpu.models import config as model_configs
from production_stack_tpu.models.config import TINY_GRANITE_HYBRID
from tests.granite_hybrid_helpers import (
    hf_config,
    make_engine,
    prompt,
    ref,
    step,
)


TOL = 5e-3
TOP = 20


def add(eng, name, tokens, max_tokens) -> Sequence:
    seq = Sequence(name, list(tokens), SamplingParams(
        temperature=0.0, max_tokens=max_tokens, ignore_eos=True,
        logprobs=TOP))
    eng.scheduler.add_sequence(seq)
    return seq


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
