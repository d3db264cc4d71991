"""The sampler computes only the picks some row of the dispatch selects
(engine/sampling.py, PR 28) — and every row still receives exactly the token
the unconditional body gave it. ``_reference_tokens`` / ``_reference_scores``
ARE that body (the parent's ``sample_tokens`` / ``sampling_scores``, kept
here as the reference); the parametrised cases cover every mix of greedy,
unfiltered and filtered rows the two ``lax.cond``s can see. The second half
drives a tiny engine and reads the three ``pstpu:sample_dispatches_*``
counters in ``Engine.stats()`` and on ``/metrics``. CPU, tiny-llama."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.engine import ServingEngine
from production_stack_tpu.engine.sampling import (
    TOP_CANDIDATES,
    SamplingParams,
    _gumbel,
    sample_tokens,
    sampler_paths,
    sampling_scores,
)


# --------------------------------------------------- the unconditional body
def _reference_tokens(logits, temperature, top_k, top_p, seeds):
    b, v = logits.shape
    greedy = jnp.argmax(logits, axis=-1)
    temp = jnp.maximum(temperature, 1e-6)[:, None]
    scaled = logits / temp
    g = _gumbel(seeds, (b, v))
    unfiltered_pick = jnp.argmax(scaled + g, axis=-1)
    c = min(TOP_CANDIDATES, v)
    cand_logits, cand_idx = jax.lax.top_k(scaled, c)
    probs = jax.nn.softmax(cand_logits, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    ranks = jnp.arange(c, dtype=jnp.int32)[None, :]
    k_eff = jnp.where(top_k[:, None] <= 0, c, top_k[:, None])
    keep = (ranks < k_eff) & ((cum - probs) < top_p[:, None])
    keep = keep.at[:, 0].set(True)
    masked = jnp.where(keep, cand_logits, -jnp.inf)
    g_cand = jnp.take_along_axis(g, cand_idx, axis=-1)
    pick = jnp.argmax(masked + g_cand, axis=-1)
    filtered_pick = jnp.take_along_axis(cand_idx, pick[:, None], axis=-1)[:, 0]
    row_filtered = (top_k > 0) | (top_p < 1.0)
    sampled = jnp.where(row_filtered, filtered_pick, unfiltered_pick)
    return jnp.where(temperature <= 0.0, greedy, sampled)


def _reference_scores(logits, temperature, seeds):
    greedy_scores = logits.astype(jnp.float32)
    temp = jnp.maximum(temperature, 1e-6)[:, None]
    perturbed = greedy_scores / temp + _gumbel(seeds, logits.shape)
    return jnp.where(temperature[:, None] <= 0.0, greedy_scores, perturbed)


# ------------------------------------------------------------------- mixes
GREEDY = (0.0, -1, 1.0)
GREEDY_TOP_P = (0.0, -1, 0.5)       # temperature 0 with a filter: greedy wins
GREEDY_TOP_K = (0.0, 5, 1.0)
PADDING = (0.0, 0, 0.0)             # a shape bucket's all-zero padding row
UNFILTERED = (0.8, -1, 1.0)
TOP_K = (0.7, 5, 1.0)
TOP_P = (1.0, -1, 0.9)
TOP_K_P = (1.3, 40, 0.5)

#   name: (row kinds cycled over the batch, any_sampled, any_filtered)
MIXES = {
    "all_greedy": ((GREEDY,), False, False),
    "greedy_with_filters_and_padding":
        ((GREEDY_TOP_P, GREEDY_TOP_K, PADDING, GREEDY), False, False),
    "all_unfiltered": ((UNFILTERED,), True, False),
    "unfiltered_and_filtering_greedy":
        ((UNFILTERED, GREEDY_TOP_P, GREEDY_TOP_K, PADDING), True, False),
    "all_filtered": ((TOP_K, TOP_P, TOP_K_P), True, True),
    "greedy_unfiltered_filtered":
        ((GREEDY, UNFILTERED, TOP_K, GREEDY_TOP_P, TOP_P, TOP_K_P, PADDING),
         True, True),
}
SHAPES = [(1, 50), (1, 1000), (5, 300), (64, 50), (64, 1000)]


def _batch(kinds, b, v, seed):
    rng = np.random.default_rng(seed)
    rows = [kinds[(i + seed) % len(kinds)] for i in range(b)]
    return (
        jnp.asarray(rng.normal(size=(b, v)) * 3.0, jnp.float32),
        jnp.asarray([r[0] for r in rows], jnp.float32),
        jnp.asarray([r[1] for r in rows], jnp.int32),
        jnp.asarray([r[2] for r in rows], jnp.float32),
        jnp.asarray(rng.integers(0, 2**32, size=b, dtype=np.uint64),
                    jnp.uint32),
    )


@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: f"b{s[0]}-v{s[1]}")
@pytest.mark.parametrize("mix", MIXES)
def test_tokens_and_scores_equal_the_unconditional_body(mix, shape):
    """Token for token, and score for score: over V below and above the
    128-candidate pool, B = 1 and B = 64, three draws each. The predicates
    may be computed by the callee or handed in (the runner computes them
    once a dispatch); a predicate that is True where no row needs it costs
    time, never a token."""
    kinds, any_sampled, any_filtered = MIXES[mix]
    b, v = shape
    reference = jax.jit(_reference_tokens)
    for seed in range(3):
        logits, temps, top_k, top_p, seeds = _batch(kinds, b, v, seed)
        paths = sampler_paths(temps, top_k, top_p)
        t, k, p = (np.asarray(x) for x in (temps, top_k, top_p))
        assert bool(paths[0]) == bool(np.any(t > 0))
        assert bool(paths[1]) == bool(
            np.any((t > 0) & ((k > 0) | (p < 1.0))))
        if b >= len(kinds):     # every kind of the mix is in the batch
            assert (bool(paths[0]), bool(paths[1])) == \
                (any_sampled, any_filtered)
        want = np.asarray(reference(logits, temps, top_k, top_p, seeds))
        args = (logits, temps, top_k, top_p, seeds)
        np.testing.assert_array_equal(np.asarray(sample_tokens(*args)), want)
        np.testing.assert_array_equal(
            np.asarray(sample_tokens(*args, paths)), want)
        np.testing.assert_array_equal(
            np.asarray(sample_tokens(
                *args, (jnp.asarray(True), jnp.asarray(True)))), want)
        want_scores = np.asarray(_reference_scores(logits, temps, seeds))
        np.testing.assert_array_equal(
            np.asarray(sampling_scores(logits, temps, seeds)), want_scores)
        np.testing.assert_array_equal(
            np.asarray(sampling_scores(logits, temps, seeds, paths[0])),
            want_scores)


def test_a_skipped_pick_is_not_traced_into_the_taken_path():
    """The all-greedy dispatch's program holds the candidate search and the
    Gumbel field only inside ``cond`` branches, each branch one call to a
    jitted body (a body written inside the cond costs every decode program
    0.65 s of lowering at each boot on the TPU: PERF.md, PR 28). The
    chip-side guard reads the compiled HLO: tests/test_chip_compile_dense.py."""
    logits, temps, top_k, top_p, seeds = _batch((GREEDY,), 4, 300, 0)
    jaxpr = jax.make_jaxpr(sample_tokens.__wrapped__)(
        logits, temps, top_k, top_p, seeds)
    top = [eqn.primitive.name for eqn in jaxpr.jaxpr.eqns]
    assert top.count("cond") == 1
    assert not {"top_k", "random_bits", "threefry2x32", "cumsum",
                "sort"} & set(top)

    def only_cond(j):
        (eqn,) = [e for e in j.eqns if e.primitive.name == "cond"]
        return sorted(
            ([e.primitive.name for e in br.jaxpr.eqns]
             for br in eqn.params["branches"]), key=len)

    stand_in, sampling = only_cond(jaxpr.jaxpr)
    assert stand_in == [] and sampling == ["jit"]
    (call,) = [e for br in [e for e in jaxpr.jaxpr.eqns
                            if e.primitive.name == "cond"][0].params["branches"]
               for e in br.jaxpr.eqns]
    body = call.params["jaxpr"].jaxpr
    assert call.params["name"] == "_sampled_pick"
    assert "top_k" not in {e.primitive.name for e in body.eqns}
    stand_in, filtering = only_cond(body)
    assert stand_in == [] and filtering == ["jit"]


# ------------------------------------------------------------ the counters
def _cfg(**over):
    base = dict(model="tiny-llama", max_model_len=256, num_kv_blocks=128,
                num_decode_steps=8, dtype="float32", max_num_seqs=4,
                max_num_batched_tokens=64)
    base.update(over)
    return EngineConfig(**base)


async def _run(engine, prompt, sampling):
    toks = []
    async for out in engine.generate(prompt=prompt, sampling=sampling):
        toks = out.token_ids
    return toks


async def test_sample_dispatch_counters_in_stats_and_metrics():
    """One greedy request, then one ``temperature 0.8, top_p 0.9`` request,
    one after the other so no dispatch mixes them: every dispatch of the
    first is greedy, every dispatch of the second filtered; a greedy row's
    ``top_p`` does not count as a filter."""
    from production_stack_tpu.server.metrics import render_engine_metrics

    engine = ServingEngine(_cfg())
    await engine.start()
    try:
        await _run(engine, "greedy first", SamplingParams(
            temperature=0.0, top_p=0.5, max_tokens=12, ignore_eos=True))
        first = engine.stats()
        # 12 tokens = 1 (prefill) + 8 + 3: one prefill, two decode trains.
        assert first["sample_dispatches_total"] == 3
        assert first["sample_dispatches_greedy_total"] == 3
        assert first["sample_dispatches_filtered_total"] == 0
        await _run(engine, "then a sampled one", SamplingParams(
            temperature=0.8, top_p=0.9, seed=7, max_tokens=12,
            ignore_eos=True))
        await _run(engine, "and an unfiltered one", SamplingParams(
            temperature=0.8, seed=7, max_tokens=4, ignore_eos=True))
    finally:
        await engine.stop()
    stats = engine.stats()
    assert stats["sample_dispatches_total"] == 8
    assert stats["sample_dispatches_greedy_total"] == 3
    assert stats["sample_dispatches_filtered_total"] == 3
    assert stats["sample_dispatches_total"] == \
        stats["decode_dispatches_total"] + stats["prefill_dispatches_total"]

    text = render_engine_metrics(engine, "tiny-llama")
    sample = {ln.split(" ")[0].split("{")[0]: float(ln.rsplit(" ", 1)[1])
              for ln in text.splitlines() if ln and not ln.startswith("#")
              and "_bucket" not in ln}
    for name, want in (("sample_dispatches", 8),
                       ("sample_dispatches_greedy", 3),
                       ("sample_dispatches_filtered", 3)):
        assert f"# TYPE pstpu:{name}_total counter" in text
        assert sample[f"pstpu:{name}_total"] == want
