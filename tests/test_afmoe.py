"""The AFMoE family (attention layers whose queries see a bounded SPAN of
keys mixed with position-free layers that see all of them, a gate on the
attention output, four norms a layer, sigmoid-routed sparse experts beside a
shared one) against its plain reference (tests/reference/afmoe_ref.py),
through the engine's own scheduler, block manager and runner at a tiny
preset with float32 activations and a span of 24 keys: not a multiple of
the block (16), so a bound falls inside a block, and small enough that
prompts, chunks, packed segments and decode all cross it.

What is compared is log-probabilities, not tokens: every generated token's
own log-probability and those of the 20 most likely tokens at its position,
as the served surface returns them (``logprobs=20``), against the
reference's log-softmax at the same ids.

TOL: both sides are float32 with full-precision products on the CPU; they
differ in the ORDER of sums (the grouped matmul over sorted pairs against
dense experts, batched rows, a prompt cut into chunks, the paged kernels'
blocks) over 8 layers. Measured largest difference over every case here:
3e-6 (logit spread 1.0). The thirteen wrong models of
``test_the_tolerance_tells_a_wrong_model`` move the same numbers by 0.04 to
several units, and the three computations in too little precision
(``LOW_PRECISION``: a bf16 router, norm or softmax) by 0.01 and more, so
1e-3 leaves both sides a decade and more of room.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.models import afmoe
from tests.afmoe_helpers import (
    CHUNK,
    SPAN,
    TOL,
    add,
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
    """The engine's default path on the CPU: ``window_attention`` under
    each layer's span over gathered history."""
    eng = make_engine()
    assert eng.runner.attn_impl == "window" and not eng.runner.prefill_packs
    return eng


@pytest.fixture(scope="module")
def paged():
    """``--attn-impl paged`` (128-lane heads): the bounded Pallas kernels
    in interpret mode, prefill as ONE packed row."""
    eng = make_engine(attn_impl="paged", max_num_batched_tokens=128)
    assert eng.runner.prefill_packs and eng.runner.prefill_reads_pool
    return eng


# ---- the engine's path against the reference --------------------------------
@pytest.mark.parametrize("path", ["window", "paged"])
@pytest.mark.parametrize("n", [SPAN // 2, SPAN, SPAN + 1, int(3.3 * SPAN)],
                         ids=lambda n: f"prompt{n}")
def test_a_prompts_under_at_and_past_the_bound(engine, paged, path, n):
    """Prompts of 0.5 x, 1 x, 1 x + 1 and 3.3 x the span, then 14 decoded
    tokens: the shortest starts decoding under the bound and runs past
    it."""
    eng = engine if path == "window" else paged
    seq = add(eng, f"a{n}", prompt(n, n), 15)
    kinds = [b.kind for b in drive(eng)]
    assert kinds[0] == "prefill" and "decode" in kinds
    assert n + 14 > SPAN
    assert worst(eng, seq) < TOL


@pytest.mark.parametrize("path", ["window", "paged"])
def test_b_a_prompt_crossing_two_chunk_boundaries(engine, paged, path):
    """Three chunks: the second and third attend a history that starts
    behind their bound."""
    eng = engine if path == "window" else paged
    chunk = eng.config.max_num_batched_tokens
    seq = add(eng, "b", prompt(2 * chunk + 5, 2), 4)
    batches = drive(eng)
    assert [b.chunk_lens for b in batches if b.kind == "prefill"] == \
        [[chunk], [chunk], [5]]
    assert worst(eng, seq) < TOL


@pytest.mark.parametrize("path", ["window", "paged"])
def test_d_decode_rows_of_unequal_length_run_past_the_bound(engine, paged,
                                                            path):
    """40 decode steps in trains of 8 (the ring holds a train's earlier
    steps, bounded like the pool), three rows of which one starts under
    the bound."""
    eng = engine if path == "window" else paged
    seqs = [add(eng, f"d{i}", prompt(n, 30 + i), 41)
            for i, n in enumerate((7, 40, 100))]
    drive(eng)
    for seq in seqs:
        assert len(seq.output_token_ids) == 41
        assert worst(eng, seq) < TOL


def test_e_preempt_and_recompute(engine):
    seq = add(engine, "e", prompt(70, 40), 20)
    other = add(engine, "e2", prompt(30, 41), 20)
    for _ in range(4):
        step(engine)
    assert 0 < len(seq.output_token_ids) < 20
    engine.scheduler._preempt(seq)
    assert not seq.block_ids
    drive(engine)
    assert len(seq.output_token_ids) == 20
    assert worst(engine, seq) < TOL and worst(engine, other) < TOL


@pytest.mark.parametrize("path", ["window", "paged"])
def test_f_a_prefix_hit_is_served_and_the_answer_is_the_cold_ones(
        engine, paged, path):
    """Blocks are blocks: the second send of a prompt is served from the
    prefix cache (one block table for bounded and unbounded layers alike)
    and answers as the first did."""
    eng = engine if path == "window" else paged
    bm = eng.block_manager
    shared = prompt(96, 80 + (path == "paged"))
    first = add(eng, "p1", shared, 6)
    drive(eng)
    hits = bm.prefix_hits_total
    second = add(eng, "p2", shared, 6)
    drive(eng)
    assert bm.prefix_hits_total > hits and second.num_cached_tokens >= 64
    assert second.output_token_ids == first.output_token_ids
    assert worst(eng, first) < TOL and worst(eng, second) < TOL


# ---- the tolerance is tight enough -----------------------------------------
@pytest.fixture(scope="module")
def served(engine):
    """129 prompt tokens (three chunks) and 40 decoded ones."""
    seq = add(engine, "w", prompt(2 * CHUNK + 1, 70), 40)
    drive(engine)
    assert worst(engine, seq) < TOL
    return seq


@pytest.mark.parametrize("wrong", ref.WRONG + ref.LOW_PRECISION)
def test_the_tolerance_tells_a_wrong_model(engine, served, wrong):
    """Against the reference with ONE equation wrong, or one computation
    (the router, a norm, the softmax) in bf16: each is far outside TOL."""
    assert worst(engine, served, wrong=(wrong,)) > 5 * TOL


def test_the_programs_choices_are_the_references(engine):
    """Share of (token, sparse layer) choices whose top-k SET differs
    between the program's whole forward and the reference's: none in
    float32 over 200 tokens; the router in bf16 flips several in a
    thousand (top-2 of 8 experts: fewer near-ties than the cut's top-8 of
    128)."""
    mc, tokens = engine.model_config, prompt(200, 71)
    *_, chosen = afmoe.forward(
        engine.runner.params, mc, jnp.asarray([tokens], jnp.int32),
        jnp.arange(200, dtype=jnp.int32)[None], jnp.asarray([200]),
        routing=True)
    right, low = [], []
    ref.forward(engine.runner.params, hf_config(mc), tokens, routing=right)
    ref.forward(engine.runner.params, hf_config(mc), tokens,
                ("router_bf16",), routing=low)

    def sets(x):
        return np.sort(np.stack([np.asarray(c) for c in x]), -1)

    assert np.asarray(chosen).shape == (6, 200, 2)
    assert np.mean(np.any(
        np.sort(np.asarray(chosen), -1) != sets(right), -1)) < 0.002
    assert np.mean(np.any(sets(low) != sets(right), -1)) > 0.004
