"""Xing4.0 (``model_type: xing4_0``: the DeepSeek-V3 block with a low-rank
query, YaRN-scaled rope and a residual of four streams mixed by
manifold-constrained hyper-connections) against its plain reference
(tests/reference/xing4_ref.py), through the engine's own scheduler, block
manager and runner at the tiny preset with float32 activations: 2 dense + 2
sparse layers, 8 experts top-2, one shared, ``hc_mult`` 4.

What is compared is log-probabilities, as tests/test_deepseek_v3.py compares
them: every generated token's own and those of the 20 most likely at its
position (``logprobs=20``) against the reference's log-softmax at the same
ids.

TOL: both sides are float32 with full-precision products on the CPU; they
differ in the FORM of attention (absorbed over the cached row against
expanded keys and values), in the order of sums, in where the mix's
normalisation is applied (the program scales ``x phi`` by the streams' rms,
the reference norms ``x`` first) and in a Sinkhorn step (the program
multiplies by one reciprocal a row, the reference divides every entry).
Measured largest difference over every case here: 1.5e-6 (logit spread
1.0). The nine wrong models of ``test_the_tolerance_tells_a_wrong_model`` move
the same numbers by 1.3e-3 to 0.35: the four mistakes in the stream mix by
1.3e-3 (the mix in bf16), 8e-3 (one Sinkhorn iteration for twenty), 0.065 (no
dynamic term) and 0.27 (``H_post`` without its 2), the router in bf16 by
1.4e-3 (no choice of these tokens flips), the query without its norm, the
softmax without YaRN's scale and the unblended frequencies by 0.24 to 0.35.
So 5e-5 leaves both sides room.
"""

import pytest

from tests.xing4_helpers import (
    TOL,
    add,
    drive,
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


def test_b_a_prompt_crossing_three_prefill_chunks(engine):
    seq = add(engine, "b", prompt(150, 2), 4)
    batches = drive(engine)
    assert [b.chunk_lens for b in batches if b.kind == "prefill"] == \
        [[64], [64], [22]]
    assert worst(engine, seq) < TOL


@pytest.mark.parametrize("impl", ["window", "paged"])
def test_c_decode_through_the_latent_pool(impl):
    """Three rows of unequal length decode 40 tokens in trains of 8; the
    streams of a decode step are [4, rows, 1, D]."""
    eng = make_engine(attn_impl=impl)
    assert eng.runner.attn_impl == impl
    seqs = [add(eng, f"c{i}", prompt(n, 10 + i), 41)
            for i, n in enumerate((20, 100, 7))]
    batches = drive(eng)
    assert sum(b.kind == "decode" for b in batches) >= 5
    for seq in seqs:
        assert len(seq.output_token_ids) == 41
        assert worst(eng, seq) < TOL


def test_e_a_prefix_hit_is_served_from_latent_blocks(engine):
    bm = engine.block_manager
    shared = prompt(64, 80)
    first = add(engine, "p1", shared + prompt(10, 81), 3)
    drive(engine)
    hits = bm.prefix_hits_total
    second = add(engine, "p2", shared + prompt(12, 82), 3)
    drive(engine)
    assert second.num_cached_tokens == 64
    assert bm.prefix_hits_total == hits + 64
    assert worst(engine, first) < TOL and worst(engine, second) < TOL


def test_f_preempt_and_recompute(engine):
    seq = add(engine, "e", prompt(70, 30), 20)
    other = add(engine, "e2", prompt(30, 31), 20)
    for _ in range(4):
        step(engine)
    assert 0 < len(seq.output_token_ids) < 20
    engine.scheduler._preempt(seq)
    assert not seq.block_ids
    drive(engine)
    assert len(seq.output_token_ids) == 20
    assert worst(engine, seq) < TOL and worst(engine, other) < TOL


# ---- the tolerance is tight enough -------------------------------------------
@pytest.fixture(scope="module")
def served(engine):
    seq = add(engine, "w", prompt(90, 70), 24)
    drive(engine)
    assert worst(engine, seq) < TOL
    return seq


@pytest.mark.parametrize("wrong", ref.WRONG)
def test_the_tolerance_tells_a_wrong_model(engine, served, wrong):
    """A prompt of 90 tokens (two chunks) and 24 decoded tokens against the
    reference with ONE equation wrong: in the stream mix (no dynamic term,
    one Sinkhorn iteration, ``H_post`` without its 2, the mix in bf16), in
    the query (no norm between its two matrices), in the rope (no YaRN scale
    on the softmax, the published frequencies unblended), in the router."""
    assert worst(engine, served, wrong=(wrong,)) > 10 * TOL
