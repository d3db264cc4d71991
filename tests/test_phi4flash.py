"""The Phi-4-mini-flash family (SambaY: selective-scan layers beside window
rings in the state slots, ONE paged full layer, a second half of gated
memory units and cross layers that cache nothing; differential attention)
against its plain reference (tests/reference/phi4flash_ref.py), through the
engine's own scheduler, block manager and runner at a tiny preset with
float32 activations: a window of 64 keys and rows of 128 and 256 tokens, so
that prompts of 1, 63, 64, 65 and 3 x 64 + 5 tokens put the window's edge
before, at and behind the prompt's end, one of 300 crosses a chunk's, and
decode carries every one of them on.

What is compared is log-probabilities, not tokens: every generated token's
own log-probability and those of the 20 most likely tokens at its position,
as the served surface returns them (``logprobs=20``), against the
reference's log-softmax at the same ids.

TOL: both sides are float32 with full-precision products on the CPU; they
differ in the ORDER of sums (batched rows, a prompt cut into chunks, the
ring's blocks, the packed row's zero lanes, the paged kernels' merged
segments, the scan's kernel against a token at a time). Measured largest
difference over every case here: 6e-5 (logit spread 2). The wrong models of
``test_the_tolerance_tells_a_wrong_model`` move the same numbers by 0.3 to
several units, so 1e-3 leaves both sides a decade of room.
"""

import jax
import numpy as np
import pytest

from production_stack_tpu.engine.sampling import SamplingParams
from production_stack_tpu.engine.scheduler import Sequence
from tests.phi4flash_helpers import TOL, W, hf_config, make_engine, prompt, ref


TOP = 20
LENGTHS = (1, W - 1, W, W + 1, 3 * W + 5, 300)


def add(eng, name, tokens, max_tokens) -> Sequence:
    seq = Sequence(name, list(tokens), SamplingParams(
        temperature=0.0, max_tokens=max_tokens, ignore_eos=True,
        logprobs=TOP))
    eng.scheduler.add_sequence(seq)
    return seq


def drive(eng) -> list:
    """Dispatches, synchronously, until nothing is left: schedule, run,
    apply."""
    batches = []
    while eng.scheduler.has_work():
        batch = eng.scheduler.schedule()
        tokens, lps = eng.runner.execute(batch, 0)
        eng.scheduler.update_after_step(batch, tokens, lps)
        batches.append(batch)
    return batches


def worst(eng, seq, wrong=()) -> float:
    """Largest |log-probability difference| of a finished sequence's
    outputs against the reference over the same tokens."""
    tokens = seq.all_token_ids
    logits = ref.forward(eng.runner.params, hf_config(eng.model_config),
                         tokens[:-1], wrong)
    logp = np.asarray(jax.nn.log_softmax(logits, axis=-1))
    n_prompt = len(seq.prompt_token_ids)
    assert len(seq.output_logprobs) == len(seq.output_token_ids)
    diffs = []
    for i, (chosen, top) in enumerate(seq.output_logprobs):
        row = logp[n_prompt - 1 + i]
        diffs.append(chosen - row[seq.output_token_ids[i]])
        assert len(top) == TOP
        diffs += [lp - row[tok] for tok, lp in top]
    return float(np.max(np.nan_to_num(np.abs(diffs), nan=np.inf)))


@pytest.fixture(scope="module")
def engine():
    """The engine's default path on the CPU: the full layer and the cross
    layers through ``window_attention`` over gathered history, the scan a
    token at a time."""
    eng = make_engine()
    assert eng.runner.attn_impl == "window" and not eng.runner.prefill_packs
    return eng


@pytest.fixture(scope="module")
def paged():
    """``--attn-impl paged`` (KV pairs as rows of 128 lanes): the full layer
    and the cross layers through the Pallas kernels in interpret mode over
    the ONE pooled layer, a chunk's scan through its kernel."""
    eng = make_engine(attn_impl="paged")
    assert eng.runner.attn_impl == "paged" and eng.runner.prefill_reads_pool
    assert not eng.runner.prefill_packs      # the rings are a state a row
    return eng


@pytest.fixture(scope="module")
def served(engine):
    """Every listed context at once (two sequences a prefill dispatch, a
    row each), 12 tokens each: a prompt of 63 decodes over the window's
    edge, one of 300 is two chunks."""
    seqs = {n: add(engine, f"len{n}", prompt(n, n), 12) for n in LENGTHS}
    batches = drive(engine)
    return engine, seqs, batches


# ------------------------------------------------------ engine vs reference
@pytest.mark.parametrize("n", LENGTHS)
def test_engine_logprobs_match_the_reference(served, n):
    eng, seqs, batches = served
    assert worst(eng, seqs[n]) < TOL
    prefills = [b for b in batches if b.kind == "prefill"]
    # Two sequences a dispatch, and the longest prompt in two chunks.
    assert max(len(b.seqs) for b in prefills) >= 2
    assert sum(seqs[300] in b.seqs for b in prefills) >= 2


@pytest.mark.parametrize("n", LENGTHS)
def test_paged_logprobs_match_the_reference(paged, n):
    """The same through the pool, the Pallas kernels (interpret) and the
    scan's kernel."""
    seq = add(paged, f"p{n}", prompt(n, 100 + n), 10)
    drive(paged)
    assert worst(paged, seq) < TOL


def test_the_answers_differ_by_prompt_and_do_not_repeat_one_token(served):
    """The seeded draw (PERF.md section 6, PR 44 and PR 54): at the table's
    and the branches' sizes a sequence's greedy answer is its own and does
    not end in one token repeated."""
    _, seqs, _ = served
    answers = [tuple(s.output_token_ids) for s in seqs.values()]
    assert len(set(answers)) == len(answers)
    for answer in answers:
        assert len(set(answer[-6:])) > 2


def test_a_state_slot_reused_by_a_second_sequence_starts_empty(served):
    """The slots of the first sequences go to new ones, shorter than a
    window: what the last owner left in a ring, a scan's state or a conv
    window is never seen."""
    eng, seqs, _ = served
    held = {s.state_slot for s in seqs.values()}
    again = [add(eng, f"again{n}", prompt(n, 7 * n), 6) for n in (3, 40, 70)]
    drive(eng)
    assert {s.state_slot for s in again} <= held
    for seq in again:
        assert worst(eng, seq) < TOL


@pytest.mark.parametrize("wrong", ref.WRONG + ref.LOW_PRECISION)
def test_the_tolerance_tells_a_wrong_model(served, wrong):
    """Each plausible mistake (lambda_init of the next layer, a_2 not
    subtracted, no sub-norm or no (1 - lambda_init), the window off by one
    either way, the conv bias or the skip left out, the memory taken after
    the gate or from the layer before, the cross layers reading a window
    layer's keys, another pairing, no bias, another norm) and each
    computation in too little precision moves the same numbers past TOL on
    the sequences that can see it."""
    eng, seqs, _ = served
    assert max(worst(eng, seqs[n], (wrong,)) for n in (W + 1, 300)) \
        > 10 * TOL
