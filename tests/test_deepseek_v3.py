"""The DeepSeek-V3 family (multi-head latent attention over ONE cached row a
token, sparse sigmoid-routed experts beside shared ones) against its plain
reference (tests/reference/deepseek_v3_ref.py), through the engine's own
scheduler, block manager and runner at a tiny preset with float32
activations: 1 dense + 3 sparse layers, 16 experts top-3, one shared.

What is compared is log-probabilities, not tokens: every generated token's
own log-probability and those of the 20 most likely tokens at its position,
as the served surface returns them (``logprobs=20``), against the
reference's log-softmax at the same ids, and the share of (token, layer)
routing choices whose top-k SET differs from the reference's.

TOL: both sides are float32 with full-precision products on the CPU; they
differ in the FORM of attention (absorbed over the cached row against
expanded keys and values of every head), in the order of sums (sorted runs
of an expert's tokens, batched rows, a prompt cut into chunks, a decode
step merging the pool's part with its own row) and in where exp is taken.
Measured largest difference over every case here: under 1e-5 (logit spread
1.0), with no routing choice differing. The six wrong models of
``test_the_tolerance_tells_a_wrong_model`` (a bf16 router and top-(k-1)
among them) move the same numbers by 1e-3 (the bf16 router where none of
these 114 tokens' choices flips: its scores at 8 bits of mantissa; the
draw's small branches, models/deepseek_v3.py:init_params, keep every
mistake's effect small and rounding's smaller) to 0.31, so 5e-5 leaves
both sides room. Routing is discontinuous: a near-tie between the k-th and the next
score may flip on a reordered sum, and a flipped choice is a different
function of the token; at float32 on both sides no tie came that near
(share 0 over ~2,000 choices), which is why the limit can be this tight.
ROUTING_TOL admits one flip in a thousand, and the logits' limit then
catches any flip that matters.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.engine import ServingEngine
from production_stack_tpu.models import deepseek_v3 as ds
from tests.deepseek_v3_helpers import (
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


ROUTING_TOL = 1e-3


def routing_difference(eng, tokens) -> float:
    """Share of (token, sparse layer) choices whose top-k SET differs
    between the program's forward and the reference's, over one sequence
    (the program's whole forward of the tokens, no cache: the routing of a
    token does not depend on how its history was served)."""
    mc = eng.model_config
    t = len(tokens)
    *_, chosen = ds.forward(
        eng.runner.params, mc, jnp.asarray([tokens], jnp.int32),
        jnp.arange(t, dtype=jnp.int32)[None], jnp.asarray([t], jnp.int32),
        routing=True)
    theirs = []
    ref.forward(eng.runner.params, hf_config(mc), tokens, routing=theirs)
    ours = np.sort(np.asarray(chosen), axis=-1)
    theirs = np.sort(np.stack([np.asarray(c) for c in theirs]), axis=-1)
    assert ours.shape == theirs.shape == (
        mc.num_layers - mc.first_k_dense_replace, t, mc.num_experts_per_tok)
    return float(np.mean(np.any(ours != theirs, axis=-1)))


@pytest.fixture(scope="module")
def engine():
    return make_engine()


# ---- the engine's path against the reference --------------------------------
def test_a_prefill_of_one_chunk(engine):
    seq = add(engine, "a", prompt(40, 1), 1)
    batches = drive(engine)
    assert [b.kind for b in batches] == ["prefill"]
    assert worst(engine, seq) < TOL
    assert routing_difference(engine, seq.all_token_ids) <= ROUTING_TOL


def test_b_a_prompt_crossing_three_prefill_chunks(engine):
    """Chunks past the first read their history as latent rows gathered
    from the pool into a window."""
    seq = add(engine, "b", prompt(150, 2), 4)
    batches = drive(engine)
    assert [b.chunk_lens for b in batches if b.kind == "prefill"] == \
        [[64], [64], [22]]
    assert worst(engine, seq) < TOL


@pytest.mark.parametrize("impl", ["window", "paged"])
def test_c_decode_through_the_latent_pool(impl):
    """Three rows of unequal length decode 40 tokens in trains of 8: on the
    window path over gathered rows, on the paged path in place through the
    Pallas kernel (interpreted on the CPU) merged with the train's ring."""
    eng = make_engine(attn_impl=impl)
    assert eng.runner.attn_impl == impl
    seqs = [add(eng, f"c{i}", prompt(n, 10 + i), 41)
            for i, n in enumerate((20, 100, 7))]
    batches = drive(eng)
    assert sum(b.kind == "decode" for b in batches) >= 5
    for seq in seqs:
        assert len(seq.output_token_ids) == 41
        assert worst(eng, seq) < TOL
    assert routing_difference(eng, seqs[1].all_token_ids) <= ROUTING_TOL


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
    reference with ONE equation wrong — the router's product in bf16, top-2
    of 3, the bias in the weights, no scaling, llama's rope pairs, no norm
    on the compressed row — each is far outside TOL."""
    assert worst(engine, served, wrong=(wrong,)) > 10 * TOL


def test_a_bf16_router_changes_choices(engine):
    """Why the router is float32: with its product in bf16 a share of the
    (token, layer) choices flips, each a different function of the token:
    several times what ROUTING_TOL admits, over 400 tokens."""
    mc, tokens = engine.model_config, prompt(400, 71)
    right, wrong = [], []
    ref.forward(engine.runner.params, hf_config(mc), tokens, routing=right)
    ref.forward(engine.runner.params, hf_config(mc), tokens,
                ("router_bf16",), routing=wrong)
    flipped = np.mean([
        np.any(np.sort(np.asarray(a), -1) != np.sort(np.asarray(b), -1), -1)
        for a, b in zip(right, wrong)])
    assert flipped > 2 * ROUTING_TOL


def test_the_prefill_history_window_has_one_width(engine):
    """A latent row is cheap to gather and this family's programs are
    large: one windowed prefill family a (rows, t), at the full width,
    where a K/V model's ladder has three (engine/runner.py:
    _pins_prefill_window)."""
    r = engine.runner
    fams = r.reachable_prefill_families()
    full = max(mb for _, _, mb, _ in fams)
    assert {mb for _, _, mb, windowed in fams if windowed} == {full}
    assert r._prefill_mb(1, True, rows=1) == full
    llama = ServingEngine(EngineConfig(
        model="tiny-llama", max_model_len=512, num_kv_blocks=128,
        max_num_seqs=8, max_num_batched_tokens=64)).runner
    assert len({mb for _, _, mb, w in llama.reachable_prefill_families()
                if w}) > 1
