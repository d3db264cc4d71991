"""The dots3-note family (full latent-attention layers that page a row a
token and attend the keys a learned indexer selects, mixed with sliding
latent-attention layers that keep a per-sequence ring of 33 latent rows in a
state slot, a headwise gate, rescaled latents, sigmoid-routed experts beside
a shared one) against its plain reference (tests/reference/dots3_ref.py),
through the engine's own scheduler, block manager and runner at a tiny
preset with float32 activations: ``index_topk`` 48 and a window of 33, rows
of 128 tokens, so that prompts of 1, 32, 33, 34, 47, 49 and 2 x 128 + 21
tokens put the window's edge and the indexer's before, at and behind a
chunk's, and decode carries every one of them over both.

What is compared is log-probabilities, not tokens: every generated token's
own log-probability and those of the 20 most likely tokens at its position,
as the served surface returns them (``logprobs=20``), against the
reference's log-softmax at the same ids.

TOL: both sides are float32 with full-precision products on the CPU; they
differ in the ORDER of sums (absorbed against expanded products, the grouped
matmul over sorted pairs against dense experts, batched rows, a prompt cut
into chunks, the ring's blocks) and in HOW the selection is found (a radix
select over bit patterns or ``lax.top_k`` over pool, ring and token against
``lax.top_k`` over a row of the score matrix: the same set away from ties).
Measured largest difference over every case here: 2e-6 (logit spread 4).
The wrong models of ``test_the_tolerance_tells_a_wrong_model`` move the same
numbers by 0.02 to 0.6, so 1e-3 leaves both sides a decade of room.
"""

import pytest

from tests.dots3_helpers import (
    LENGTHS,
    TOL,
    add,
    drive,
    make_engine,
    prompt,
    ref,
    worst,
)

LONG = LENGTHS[-1]


@pytest.fixture(scope="module", params=["window", "paged"])
def served(request):
    """Every listed context at once, 12 tokens each, on both paths: the
    engine's default on the CPU (the full layers' history a gathered window,
    selection by mask) and ``--attn-impl paged`` (a decode step READS the
    rows' index keys and the selected latent rows from the pool)."""
    eng = make_engine(attn_impl=request.param)
    assert eng.runner.attn_impl == request.param
    assert not eng.runner.prefill_packs      # the ring is a state a row
    seqs = {n: add(eng, f"len{n}", prompt(n, n), 12) for n in LENGTHS}
    batches = drive(eng)
    return eng, seqs, batches


# ------------------------------------------------------ engine vs reference
@pytest.mark.parametrize("n", LENGTHS)
def test_engine_logprobs_match_the_reference(served, n):
    eng, seqs, batches = served
    assert worst(eng, seqs[n]) < TOL
    prefills = [b for b in batches if b.kind == "prefill"]
    # The longest prompt in three chunks, and decode rows of many lengths.
    assert sum(seqs[LONG] in b.seqs for b in prefills) >= 3
    assert max(len(b.seqs) for b in batches if b.kind == "decode") >= 4


def test_the_counters_say_what_a_full_layer_read(served):
    """Decode's counters are the closed form: three full layers, a query at
    position p sees p + 1 keys and its indexer selects min(p + 1, 48)."""
    eng, seqs, _ = served
    dec = eng.runner.fwd_stats_total["decode"]
    visible = selected = 0
    for n, seq in seqs.items():
        # 11 decode queries at positions n .. n + 10.
        for p in range(n, n + 11):
            visible += 3 * (p + 1)
            selected += 3 * min(p + 1, 48)
    assert dec["index_keys_visible"] == visible
    assert dec["index_keys_selected"] == selected
    stats = eng.stats()
    assert stats["index_keys_selected_total"] == selected
    assert stats["index_prefill_keys_visible_total"] > \
        stats["index_prefill_keys_selected_total"] > 0


def test_a_ring_slot_reused_by_a_second_sequence_starts_empty(served):
    """The slots of the first sequences go to new ones, shorter than a
    window: what the last owner left in a slot is never seen."""
    eng, seqs, _ = served
    held = {s.state_slot for s in seqs.values()}
    again = [add(eng, f"again{n}", prompt(n, 7 * n), 6) for n in (3, 20, 60)]
    drive(eng)
    assert {s.state_slot for s in again} <= held
    for seq in again:
        assert worst(eng, seq) < TOL


@pytest.mark.parametrize("wrong", ref.WRONG + ref.LOW_PRECISION)
def test_the_tolerance_tells_a_wrong_model(served, wrong):
    """Each plausible mistake (no indexer, half its top-k, no ReLU, the
    heads' weights unsigned, other rope lanes, the window off by one or
    gone, no gate, a gate an element, no rescale, one theta for both kinds,
    another router, no shared expert) and each computation in too little
    precision moves the same numbers past TOL on the sequences that can see
    it."""
    eng, seqs, _ = served
    if wrong == "all_experts_here":
        pytest.skip("every expert IS here in this engine; the share's "
                    "engine shows it (tests/test_dots3_share.py)")
    assert max(worst(eng, seqs[n], (wrong,)) for n in (49, LONG)) > 10 * TOL
