"""The MiMo-V2 family (full layers that page their keys mixed with window
layers that keep a per-sequence ring of 128 keys in a state slot, different
KV head counts, keys wider than values, a partial rope, a sink in the window
layers' softmax, sigmoid-routed experts of which a chip may hold a share)
against its plain reference (tests/reference/mimo_v2_ref.py), through the
engine's own scheduler, block manager and runner at a tiny preset with
float32 activations: a window of 128 keys, rows of 128 and 256 tokens, so that
prompts of 1, 127, 128, 129 and 3 x 128 + 5 tokens put the window's edge
before, at and behind a chunk's, and decode carries every one of them over it.

What is compared is log-probabilities, not tokens: every generated token's
own log-probability and those of the 20 most likely tokens at its position,
as the served surface returns them (``logprobs=20``), against the
reference's log-softmax at the same ids.

TOL: both sides are float32 with full-precision products on the CPU; they
differ in the ORDER of sums (the grouped matmul over sorted pairs against
dense experts, batched rows, a prompt cut into chunks, the ring's blocks,
the sink merged by its statistics against one more column). Measured
largest difference over every case here: 3e-6 (logit spread 1.0). The
wrong models of ``test_the_tolerance_tells_a_wrong_model`` move the same
numbers by 0.01 to several units, so 1e-3 leaves both sides a decade of
room.
"""

import pytest

from tests.mimo_v2_helpers import (
    LENGTHS,
    TOL,
    add,
    drive,
    make_engine,
    prompt,
    ref,
    worst,
)


@pytest.fixture(scope="module")
def engine():
    """The engine's default path on the CPU: the full layers through
    ``window_attention`` over gathered history, every expert here."""
    eng = make_engine()
    assert eng.runner.attn_impl == "window" and not eng.runner.prefill_packs
    return eng


@pytest.fixture(scope="module")
def served(engine):
    """Every listed context at once (two and more sequences a prefill
    dispatch, a row each), 12 tokens each: a prompt of 127 decodes over the
    window's edge, one of 389 is three chunks."""
    seqs = {n: add(engine, f"len{n}", prompt(n, n), 12) for n in LENGTHS}
    batches = drive(engine)
    return engine, seqs, batches


# ------------------------------------------------------ engine vs reference
@pytest.mark.parametrize("n", LENGTHS)
def test_engine_logprobs_match_the_reference(served, n):
    eng, seqs, batches = served
    assert worst(eng, seqs[n]) < TOL
    prefills = [b for b in batches if b.kind == "prefill"]
    # Several sequences a dispatch, and the longest prompt in three chunks.
    assert max(len(b.seqs) for b in prefills) >= 2
    assert sum(seqs[389] in b.seqs for b in prefills) >= 3


def test_a_ring_slot_reused_by_a_second_sequence_starts_empty(served):
    """The slots of the first sequences go to new ones, shorter than a
    window: what the last owner left in a slot is never seen."""
    eng, seqs, _ = served
    held = {s.state_slot for s in seqs.values()}
    again = [add(eng, f"again{n}", prompt(n, 7 * n), 6) for n in (3, 40, 130)]
    drive(eng)
    assert {s.state_slot for s in again} <= held
    for seq in again:
        assert worst(eng, seq) < TOL


@pytest.mark.parametrize("wrong", ref.WRONG + ref.LOW_PRECISION)
def test_the_tolerance_tells_a_wrong_model(served, wrong):
    """Each plausible mistake (the sink left out or given a value, the
    bound off by one or gone, the values unscaled, other rope lanes, one
    theta for both kinds, another router, the share's experts misplaced)
    and each computation in too little precision moves the same numbers
    past TOL on the sequences that can see it."""
    eng, seqs, _ = served
    if wrong == "all_experts_here":
        pytest.skip("every expert IS here in this engine; the share's "
                    "engine shows it below")
    assert max(worst(eng, seqs[n], (wrong,)) for n in (129, 389)) > 10 * TOL
