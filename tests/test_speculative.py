"""Speculative decoding (docs/PERF.md round 8).

The hard bar: spec-on must be TOKEN-IDENTICAL to spec-off for greedy and
seeded sampling — including a stop string landing inside a draft window
and a PR-9 mid-stream resume of a spec-on stream. Two draft shapes are
exercised: a SELF-draft (identical weights — acceptance ~1, the
mechanism-proof/bench configuration) and a cross-arch tiny-opt draft
(uncorrelated random weights — acceptance ~0, which drives the pure
rejection path hard; output must STILL match spec-off exactly because
every emitted token is the target's own sample).

Config validation is parse-time: a vocab-mismatched draft must be a
clean startup error, never a mid-scan shape crash.
"""

import asyncio

import numpy as np
import pytest

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.engine import ServingEngine
from production_stack_tpu.engine.runner import (
    SpecGammaController,
    resolved_seed_base,
)
from production_stack_tpu.engine.sampling import (
    SamplingParams,
    adaptive_gamma,
    speculative_accept,
    speculative_tree_accept,
)

BASE = dict(
    model="tiny-llama", max_model_len=256, block_size=4, num_kv_blocks=128,
    max_num_seqs=8, max_num_batched_tokens=32, attn_impl="window",
    dtype="float32", num_decode_steps=8,
)


# --------------------------------------------------------------------------
# Parse-time validation (satellite: clean startup error, not a shape crash)
# --------------------------------------------------------------------------
def test_vocab_mismatched_draft_is_a_clean_config_error():
    with pytest.raises(ValueError, match="vocab"):
        EngineConfig(**BASE, speculative_num_tokens=3,
                     speculative_model="facebook/opt-125m")


def test_spec_requires_a_draft_model():
    with pytest.raises(ValueError, match="speculative-model"):
        EngineConfig(**BASE, speculative_num_tokens=3)


def test_spec_rejects_int8_kv_cache():
    cfg = dict(BASE)
    cfg["kv_cache_dtype"] = "int8"
    with pytest.raises(ValueError, match="bfloat16"):
        EngineConfig(**cfg, speculative_num_tokens=3,
                     speculative_model="tiny-llama")


def test_spec_rejects_tensor_parallel():
    cfg = dict(BASE)
    cfg["tensor_parallel_size"] = 2
    with pytest.raises(ValueError, match="tensor-parallel-size"):
        EngineConfig(**cfg, speculative_num_tokens=3,
                     speculative_model="tiny-llama")


def test_spec_rejects_explicit_paged_attn():
    cfg = dict(BASE)
    cfg["attn_impl"] = "paged"
    from production_stack_tpu.models.config import resolve_model_config

    ec = EngineConfig(**{**cfg, "model": "tiny-llama-128dh"},
                      speculative_num_tokens=3,
                      speculative_model="tiny-llama-128dh")
    with pytest.raises(ValueError, match="window"):
        ec.resolved_attn_impl(resolve_model_config("tiny-llama-128dh"))


def test_spec_auto_attn_resolves_to_window():
    from production_stack_tpu.models.config import resolve_model_config

    ec = EngineConfig(**BASE, speculative_num_tokens=3,
                      speculative_model="tiny-llama")
    assert ec.resolved_attn_impl(
        resolve_model_config("tiny-llama")
    ) == "window"


# --------------------------------------------------------------------------
# Acceptance accounting math (satellite: pinned on synthetic traces)
# --------------------------------------------------------------------------
def _accept(props, samples, budget):
    emit, acc = speculative_accept(
        np.asarray(props, np.int32), np.asarray(samples, np.int32),
        np.asarray(budget, np.int32),
    )
    return np.asarray(emit).tolist(), np.asarray(acc).tolist()


def test_accept_full_agreement_emits_bonus_token():
    # proposals match samples[:-1] exactly -> all N accepted + 1 bonus.
    emit, acc = _accept([[5, 6, 7]], [[5, 6, 7, 8]], [10])
    assert (emit, acc) == ([4], [3])


def test_accept_first_mismatch_truncates_prefix():
    # q1 wrong -> only q0 accepted; the emitted stream is samples[:2].
    emit, acc = _accept([[5, 9, 7]], [[5, 6, 7, 8]], [10])
    assert (emit, acc) == ([2], [1])


def test_accept_post_rejection_agreement_never_resurrects():
    # q2 agrees again AFTER the q1 mismatch — its context was wrong, so
    # the cumulative-prefix rule must not count it.
    emit, acc = _accept([[5, 9, 7]], [[5, 6, 7, 8]], [10])
    assert acc == [1]
    emit2, acc2 = _accept([[9, 6, 7]], [[5, 6, 7, 8]], [10])
    assert (emit2, acc2) == ([1], [0])


def test_accept_budget_clips_emission():
    emit, acc = _accept([[5, 6, 7]], [[5, 6, 7, 8]], [2])
    assert emit == [2]          # accepted 3 but only 2 tokens of budget
    emit0, _ = _accept([[5, 6, 7]], [[5, 6, 7, 8]], [0])
    assert emit0 == [0]         # exhausted row emits nothing


def test_accept_is_per_row():
    emit, acc = _accept(
        [[1, 2, 3], [1, 2, 3]],
        [[1, 2, 3, 4], [9, 2, 3, 4]],
        [10, 10],
    )
    assert (emit, acc) == ([4, 1], [3, 0])


# --------------------------------------------------------------------------
# Token-tree structure + accept walk (round 10; pinned synthetic traces).
# Layout for N=3, W=3 (ops/tree_mask.py): node 0 = t0, node 1 = main p1,
# nodes 2..3 = first-position alternates, nodes 4..5 = linear chain p2, p3.
# --------------------------------------------------------------------------
def test_tree_structure_layout_and_bias():
    from production_stack_tpu.ops.tree_mask import (
        main_chain_indices,
        tree_attention_bias,
        tree_structure,
    )

    parents, depths = tree_structure(3, 3)
    assert parents.tolist() == [-1, 0, 0, 0, 1, 4]
    assert depths.tolist() == [0, 1, 1, 1, 2, 3]
    assert main_chain_indices(3, 3).tolist() == [0, 1, 4, 5]
    bias = np.asarray(tree_attention_bias(parents))
    assert bias.shape == (6, 6)
    # Rows attend to their ancestor path (and themselves) only: node 5's
    # path is 0 -> 1 -> 4 -> 5; the alternates are masked out.
    assert (bias[5] == 0).tolist() == [True, True, False, False, True, True]
    # Siblings never see each other.
    assert bias[2][3] < -1e30 and bias[3][2] < -1e30
    # Width 1 degrades to the strictly-causal linear chain.
    p1, d1 = tree_structure(3, 1)
    assert p1.tolist() == [-1, 0, 1, 2] and d1.tolist() == [0, 1, 2, 3]
    b1 = np.asarray(tree_attention_bias(p1))
    assert (b1 == np.where(np.tril(np.ones((4, 4))), 0, b1[0][3])).all()


def _tree_accept(v_toks, z, budget, gamma, n=3, w=3):
    from production_stack_tpu.ops.tree_mask import tree_structure

    parents, depths = tree_structure(n, w)
    emit, acc, path, main_len = speculative_tree_accept(
        np.asarray(v_toks, np.int32), np.asarray(z, np.int32),
        parents, depths, np.asarray(budget, np.int32),
        np.asarray(gamma, np.int32),
    )
    return (np.asarray(emit).tolist(), np.asarray(acc).tolist(),
            np.asarray(path).tolist(), np.asarray(main_len).tolist())


# One row's tree tokens: t0=10, main p1=11, alternates 20/21, chain 12, 13.
_VT = [10, 11, 20, 21, 12, 13]


def test_tree_accept_full_main_chain_emits_bonus():
    emit, acc, path, main_len = _tree_accept(
        [_VT], [[11, 12, 0, 0, 13, 99]], [10], [3])
    assert (emit, acc, main_len) == ([4], [3], [4])
    assert path == [[0, 1, 4, 5]]


def test_tree_accept_sibling_salvage():
    # Target's own first sample is alternate 20, not the main p1=11: the
    # linear rule would emit 1 token; the tree walks onto the sibling and
    # emits 2 (the salvaged draft + its bonus) — but the draft ring only
    # holds main-chain entries, so main_len keeps just the t0 entry.
    emit, acc, path, main_len = _tree_accept(
        [_VT], [[20, 0, 77, 0, 0, 0]], [10], [3])
    assert (emit, acc, main_len) == ([2], [1], [1])
    assert path[0][:2] == [0, 2]
    lin_emit, lin_acc = _accept([[11, 12, 13]], [[20, 0, 77, 0]], [10])
    assert (lin_emit, lin_acc) == ([1], [0])


def test_tree_accept_no_match_is_pure_rejection():
    emit, acc, path, main_len = _tree_accept(
        [_VT], [[55, 0, 0, 0, 0, 0]], [10], [3])
    assert (emit, acc, main_len) == ([1], [0], [1])
    assert path == [[0, 0, 0, 0]]


def test_tree_accept_gamma_gates_depth():
    # Full main-chain agreement but gamma=1: depth-2 children are never
    # taken, so exactly one draft token is accepted.
    emit, acc, _, main_len = _tree_accept(
        [_VT], [[11, 12, 0, 0, 13, 99]], [10], [1])
    assert (emit, acc, main_len) == ([2], [1], [2])
    emit0, acc0, _, _ = _tree_accept(
        [_VT], [[11, 12, 0, 0, 13, 99]], [10], [0])
    assert (emit0, acc0) == ([1], [0])


def test_tree_accept_budget_clips_emission_and_ring():
    emit, acc, _, main_len = _tree_accept(
        [_VT], [[11, 12, 0, 0, 13, 99]], [2], [3])
    assert (emit, acc, main_len) == ([2], [3], [2])
    emit0, acc0, _, main0 = _tree_accept(
        [_VT], [[11, 12, 0, 0, 13, 99]], [0], [3])
    assert (emit0, acc0, main0) == ([0], [0], [0])


def test_tree_accept_is_per_row():
    emit, acc, _, main_len = _tree_accept(
        [_VT, _VT, _VT],
        [[11, 12, 0, 0, 13, 99], [20, 0, 7, 0, 0, 0], [55, 0, 0, 0, 0, 0]],
        [10, 10, 10], [3, 3, 3])
    assert emit == [4, 2, 1]
    assert acc == [3, 1, 0]
    assert main_len == [4, 1, 1]


# --------------------------------------------------------------------------
# Adaptive gamma policy + controller (round 10; scripted traces)
# --------------------------------------------------------------------------
def test_adaptive_gamma_policy_units():
    assert adaptive_gamma(1.0, 4, 0.5) == 4     # perfect draft: full depth
    assert adaptive_gamma(0.9, 4, 0.5) == 4
    assert adaptive_gamma(0.7, 4, 0.5) == 1     # 0.7^2 < 0.5
    assert adaptive_gamma(0.5, 4, 0.5) == 1
    assert adaptive_gamma(0.2, 4, 0.5) == 0     # not worth one draft
    assert adaptive_gamma(0.0, 4, 0.5) == 0
    assert adaptive_gamma(1.0, 4, 2.0) == 0     # threshold>1 pins gamma=0


def test_controller_converges_on_scripted_trace():
    c = SpecGammaController(n_max=3, decay=0.5, threshold=0.5,
                            probe_period=0)
    # Optimistic before any observation.
    assert c.gamma("r") == 3
    # Pure-rejection trace: EMA halves every dispatch -> depth backs off
    # to 0 and stays there.
    gammas = []
    for _ in range(6):
        c.update("r", drafted=3, accepted=0)
        gammas.append(c.gamma("r"))
    assert gammas[0] == 1           # ema 0.5 -> one hopeful draft
    assert gammas[-1] == 0 and sorted(gammas, reverse=True) == gammas
    # Predictable-again trace: full acceptance recovers full depth.
    for _ in range(6):
        c.update("r", drafted=3, accepted=3)
    assert c.gamma("r") == 3
    # gamma=0 dispatches draft nothing: they must NOT move the EMA.
    ema = c.ema("r")
    c.update("r", drafted=0, accepted=0)
    assert c.ema("r") == ema
    c.forget("r")
    assert c.gamma("r") == 3        # fresh sequence starts optimistic


def test_controller_probes_collapsed_sequences():
    c = SpecGammaController(n_max=3, decay=1.0, threshold=0.5,
                            probe_period=3)
    c.update("r", drafted=3, accepted=0)    # ema -> 0.0, gamma -> 0
    assert [c.gamma("r") for i in range(7)] == [0, 0, 1, 0, 0, 1, 0]
    # probe_period=0 disables probing entirely.
    c0 = SpecGammaController(n_max=3, decay=1.0, threshold=0.5,
                             probe_period=0)
    c0.update("r", drafted=3, accepted=0)
    assert [c0.gamma("r") for _ in range(5)] == [0] * 5


def test_adaptive_and_tree_config_validation():
    with pytest.raises(ValueError, match="speculative"):
        EngineConfig(**BASE, speculative_adaptive=True)
    with pytest.raises(ValueError, match="speculative"):
        EngineConfig(**BASE, speculative_tree_width=3)
    with pytest.raises(ValueError, match="tree"):
        EngineConfig(**BASE, speculative_num_tokens=3,
                     speculative_model="tiny-llama",
                     speculative_tree_width=9).resolved_draft_config()


# --------------------------------------------------------------------------
# Engines under test (module-scoped: compile once, reuse across tests)
# --------------------------------------------------------------------------
@pytest.fixture(scope="module")
def engines():
    loop = asyncio.new_event_loop()
    eng = {
        "off": ServingEngine(EngineConfig(**BASE)),
        "self": ServingEngine(EngineConfig(
            **BASE, speculative_num_tokens=3,
            speculative_model="tiny-llama",
        )),
        "opt": ServingEngine(EngineConfig(
            **BASE, speculative_num_tokens=3,
            speculative_model="tiny-opt",
        )),
    }
    for e in eng.values():
        loop.run_until_complete(e.start())
    yield eng, loop
    for e in eng.values():
        loop.run_until_complete(e.stop())
    loop.close()


async def _collect(engine, prompt, sampling, request_id, **kw):
    text, outs = "", []
    async for out in engine.generate(
        prompt=prompt, sampling=sampling, request_id=request_id, **kw
    ):
        text += out.text_delta
        outs.append(out)
    return text, outs


def _run(loop, engine, prompt, sampling, request_id, **kw):
    return loop.run_until_complete(
        _collect(engine, prompt, sampling, request_id, **kw)
    )


# --------------------------------------------------------------------------
# Draft-model plumbing (satellite: fast tier-1)
# --------------------------------------------------------------------------
def test_draft_plumbing_cross_arch_draft_loads_and_counts(engines):
    eng, loop = engines
    e = eng["opt"]
    # Draft + target resolved and loaded side by side.
    assert e.runner.spec_draft_config.arch == "opt"
    assert e.runner.spec_draft_config.vocab_size == \
        e.model_config.vocab_size
    before = e.runner.spec_draft_tokens_total
    _, outs = _run(loop, e, "plumbing check", SamplingParams(
        temperature=0.0, max_tokens=9, ignore_eos=True), "plumb-1")
    assert outs[-1].num_output_tokens == 9
    st = e.stats()
    assert st["spec_enabled"] == 1
    # Proposals were made in multiples of N, and acceptance is a valid
    # fraction of them.
    made = st["spec_draft_tokens_total"] - before
    assert made > 0 and made % 3 == 0
    assert 0 <= st["spec_accepted_tokens_total"] <= \
        st["spec_draft_tokens_total"]
    assert 0.0 <= st["spec_acceptance_rate"] <= 1.0
    # The finished stream returned its draft-ring slot.
    assert "plumb-1" not in e.runner._spec_slots


def test_spec_off_engine_reports_disabled(engines):
    eng, _ = engines
    st = eng["off"].stats()
    assert st["spec_enabled"] == 0
    assert st["spec_draft_tokens_total"] == 0
    assert st["spec_acceptance_rate"] == 0.0


def test_metrics_export_spec_series(engines):
    eng, _ = engines
    from production_stack_tpu.server.metrics import render_engine_metrics

    text = render_engine_metrics(eng["self"], "m")
    for name in ("pstpu:spec_enabled", "pstpu:spec_draft_tokens_total",
                 "pstpu:spec_accepted_tokens_total",
                 "pstpu:spec_acceptance_rate"):
        assert name in text, name
    assert 'pstpu:spec_enabled{model_name="m"} 1' in text


# --------------------------------------------------------------------------
# Parity: the hard bar (fast single-stream greedy/seeded stay in tier-1)
# --------------------------------------------------------------------------
GREEDY = dict(temperature=0.0, max_tokens=24, ignore_eos=True)
SEEDED = dict(temperature=0.9, seed=1234, max_tokens=24, ignore_eos=True)


def test_parity_greedy_self_draft_high_acceptance(engines):
    eng, loop = engines
    _, off = _run(loop, eng["off"], "greedy parity prompt",
                  SamplingParams(**GREEDY), "pg-off")
    before = eng["self"].runner.spec_accepted_tokens_total
    _, on = _run(loop, eng["self"], "greedy parity prompt",
                 SamplingParams(**GREEDY), "pg-self")
    assert on[-1].token_ids == off[-1].token_ids
    # Identical weights + full-context draft ring: acceptance is high,
    # so the machinery emitted >1 token per target step.
    assert eng["self"].runner.spec_accepted_tokens_total > before


def test_parity_greedy_cross_draft_pure_rejection(engines):
    eng, loop = engines
    _, off = _run(loop, eng["off"], "rejection parity prompt",
                  SamplingParams(**GREEDY), "pr-off")
    _, on = _run(loop, eng["opt"], "rejection parity prompt",
                 SamplingParams(**GREEDY), "pr-opt")
    # Uncorrelated draft: most proposals are rejected — emitted tokens
    # must STILL be exactly the target's stream.
    assert on[-1].token_ids == off[-1].token_ids


def test_parity_seeded_sampling_both_drafts(engines):
    eng, loop = engines
    _, off = _run(loop, eng["off"], "seeded parity prompt",
                  SamplingParams(**SEEDED), "ps-off")
    _, on_self = _run(loop, eng["self"], "seeded parity prompt",
                      SamplingParams(**SEEDED), "ps-self")
    _, on_opt = _run(loop, eng["opt"], "seeded parity prompt",
                     SamplingParams(**SEEDED), "ps-opt")
    assert on_self[-1].token_ids == off[-1].token_ids
    assert on_opt[-1].token_ids == off[-1].token_ids


def test_parity_logprobs_bookkeeping(engines):
    eng, loop = engines
    sp = SamplingParams(temperature=0.0, max_tokens=8, ignore_eos=True,
                        logprobs=3)
    _, off = _run(loop, eng["off"], "logprob parity", sp, "lp-off")
    _, on = _run(loop, eng["self"], "logprob parity", sp, "lp-on")
    assert on[-1].token_ids == off[-1].token_ids
    lps_off, lps_on = off[-1].logprobs, on[-1].logprobs
    assert len(lps_on) == len(lps_off) == 8
    for (c_off, top_off), (c_on, top_on) in zip(lps_off, lps_on):
        assert [t for t, _ in top_on] == [t for t, _ in top_off]
        assert c_on == pytest.approx(c_off, abs=1e-4)


def test_variable_budgets_and_concurrency(engines):
    """Co-batched spec rows with different max_tokens: budget clipping
    inside the accept step must stop each row at ITS budget, and outputs
    must match the spec-off engine run with the same concurrency."""
    eng, loop = engines

    async def batch(e, tag):
        return await asyncio.gather(
            _collect(e, "stream one", SamplingParams(
                temperature=0.0, max_tokens=3, ignore_eos=True),
                f"{tag}-a"),
            _collect(e, "stream two", SamplingParams(
                temperature=0.0, max_tokens=13, ignore_eos=True),
                f"{tag}-b"),
            _collect(e, "stream three", SamplingParams(
                temperature=0.0, max_tokens=22, ignore_eos=True),
                f"{tag}-c"),
        )
    off = loop.run_until_complete(batch(eng["off"], "vb-off"))
    on = loop.run_until_complete(batch(eng["self"], "vb-on"))
    for (_, o), (_, s) in zip(off, on):
        assert s[-1].token_ids == o[-1].token_ids
    assert [s[-1].num_output_tokens for _, s in on] == [3, 13, 22]


# --------------------------------------------------------------------------
# Stop strings + resume across the spec window (e2e; slow tier)
# --------------------------------------------------------------------------
@pytest.mark.slow
def test_stop_string_inside_a_draft_window(engines):
    """Pick a stop string from the greedy output so the match lands
    mid-generation — inside some draft/verify window — and assert the
    spec-on truncation matches spec-off byte for byte."""
    eng, loop = engines
    sp = SamplingParams(temperature=0.0, max_tokens=40, ignore_eos=True)
    base_text, base = _run(loop, eng["off"], "tell me a story", sp,
                           "stop-base")
    assert len(base_text) > 8
    mid = len(base_text) // 2
    stop = base_text[mid:mid + 3]
    idx = base_text.find(stop)
    assert idx > 0
    sp_stop = SamplingParams(temperature=0.0, max_tokens=40,
                             ignore_eos=True, stop=[stop])
    off_text, off = _run(loop, eng["off"], "tell me a story", sp_stop,
                         "stop-off")
    on_text, on = _run(loop, eng["self"], "tell me a story", sp_stop,
                       "stop-on")
    assert on_text == off_text == base_text[:idx]
    assert on[-1].token_ids == off[-1].token_ids
    assert on[-1].finish_reason == off[-1].finish_reason == "stop"


@pytest.mark.slow
def test_resume_of_a_spec_on_stream_is_token_identical(engines):
    """PR-9 contract: resume replays ACCEPTED tokens only (the host never
    saw rejected drafts), so resuming a spec-on stream — on a spec-on
    engine — continues token-identically from the delivered prefix."""
    eng, loop = engines
    sp = SamplingParams(temperature=0.0, max_tokens=14, ignore_eos=True)
    _, full = _run(loop, eng["self"], "resume a speculative stream", sp,
                   "sr-full")
    toks = full[-1].token_ids
    assert len(toks) == 14
    _, res = _run(
        loop, eng["self"], "resume a speculative stream", sp, "sr-res",
        resume_tokens=toks[:5],
        resume_seed=resolved_seed_base("sr-full", sp),
    )
    assert res[-1].token_ids == toks
    assert res[-1].num_output_tokens == 14
    # And the same resume served by a spec-OFF engine matches too (the
    # wire contract is engine-config-agnostic).
    _, res_off = _run(
        loop, eng["off"], "resume a speculative stream", sp, "sr-res-off",
        resume_tokens=toks[:5],
        resume_seed=resolved_seed_base("sr-full", sp),
    )
    assert res_off[-1].token_ids == toks


# --------------------------------------------------------------------------
# Round 10 engines: token-tree verify + adaptive per-sequence gamma
# (module-scoped like `engines`; the "off" baseline is shared from there)
# --------------------------------------------------------------------------
# max_num_seqs=4 trims the decode-bucket family set the warmup compiles
# (CPU XLA compile time, not coverage: the parity tests run 1-2 streams).
BASE_R10 = dict(BASE, max_num_seqs=4)


@pytest.fixture(scope="module")
def engines_r10():
    loop = asyncio.new_event_loop()
    eng = {
        "tree": ServingEngine(EngineConfig(
            **BASE_R10, speculative_num_tokens=3,
            speculative_model="tiny-llama", speculative_tree_width=3,
        )),
        "adaptive": ServingEngine(EngineConfig(
            **BASE_R10, speculative_num_tokens=3,
            speculative_model="tiny-llama", speculative_tree_width=3,
            speculative_adaptive=True,
        )),
    }
    for e in eng.values():
        loop.run_until_complete(e.start())
    yield eng, loop
    for e in eng.values():
        loop.run_until_complete(e.stop())
    loop.close()


def test_parity_four_modes_greedy_and_seeded(engines, engines_r10):
    """The round-10 hard bar: spec-off, linear, tree and adaptive engines
    emit IDENTICAL tokens for the same request, greedy and seeded."""
    eng, loop = engines
    eng10, loop10 = engines_r10
    for tag, kw in (("g", GREEDY), ("s", SEEDED)):
        _, off = _run(loop, eng["off"], "four mode parity",
                      SamplingParams(**kw), f"fm-{tag}-off")
        for mode in ("self", ):
            _, on = _run(loop, eng[mode], "four mode parity",
                         SamplingParams(**kw), f"fm-{tag}-{mode}")
            assert on[-1].token_ids == off[-1].token_ids, (tag, mode)
        for mode in ("tree", "adaptive"):
            _, on = _run(loop10, eng10[mode], "four mode parity",
                         SamplingParams(**kw), f"fm-{tag}-{mode}")
            assert on[-1].token_ids == off[-1].token_ids, (tag, mode)


def test_tree_engine_counts_tree_nodes(engines_r10):
    eng10, loop = engines_r10
    e = eng10["tree"]
    before = e.runner.spec_tree_nodes_total
    cycles0 = e.runner.spec_live_cycles_total
    _, outs = _run(loop, e, "tree accounting", SamplingParams(
        temperature=0.0, max_tokens=10, ignore_eos=True), "tn-1")
    assert outs[-1].num_output_tokens == 10
    nodes = e.runner.spec_tree_nodes_total - before
    cycles = e.runner.spec_live_cycles_total - cycles0
    # Fixed gamma=3, width 3: every live speculative cycle verifies
    # exactly (W - 1) + gamma = 5 extra tree nodes.
    assert cycles > 0 and nodes == 5 * cycles


def test_gamma0_pinned_engine_degrades_to_spec_off_dispatch(engines_r10):
    """gamma=0 for EVERY row must take the spec-off decode train: zero
    drafts, zero live speculative cycles, the gamma-0 dispatch counter
    moving, and the flight recorder's decode_issue events tagged with the
    off-degrade dispatch mode. The controller is pinned to gamma=0 the
    supported way — threshold > 1 (the degradation configuration of
    speculative_gamma_threshold) with probing off."""
    eng10, loop = engines_r10
    e = eng10["adaptive"]
    ctl = e.runner._spec_controller
    thr, probe = ctl.threshold, ctl.probe_period
    ctl.threshold, ctl.probe_period = 2.0, 0
    d0 = e.runner.spec_draft_tokens_total
    c0 = e.runner.spec_live_cycles_total
    g0 = e.runner.spec_gamma0_dispatches_total
    try:
        _, outs = _run(loop, e, "degrade check", SamplingParams(
            temperature=0.0, max_tokens=12, ignore_eos=True), "g0-1")
    finally:
        ctl.threshold, ctl.probe_period = thr, probe
    assert outs[-1].num_output_tokens == 12
    # No draft work at all — dispatch-count parity with spec-off.
    assert e.runner.spec_draft_tokens_total == d0
    assert e.runner.spec_live_cycles_total == c0
    assert e.runner.spec_gamma0_dispatches_total > g0
    rec = e.recorder.get("g0-1")
    issues = [ev for r in rec["records"] for ev in r["events"]
              if ev["event"] == "decode_issue"]
    assert issues and all(
        ev.get("spec_mode") == "off-degrade" for ev in issues
    )
    # The plain decode train emits the full num_decode_steps per train,
    # exactly like a spec-off engine (12 tokens / 8-step trains).
    assert len(issues) == 2


def test_adaptive_engine_reports_controller_telemetry(engines_r10):
    eng10, loop = engines_r10
    e = eng10["adaptive"]
    _, outs = _run(loop, e, "adaptive telemetry", SamplingParams(
        temperature=0.0, max_tokens=10, ignore_eos=True), "at-1")
    st = e.stats()
    # Self-draft greedy: acceptance ~1 keeps the EMA high and the served
    # depth at (or near) the configured maximum.
    assert st["spec_acceptance_rate"] > 0.5
    assert 0.0 < st["spec_draft_depth"] <= 3.0
    assert 0.0 <= st["spec_acceptance_rate_window"] <= 1.0
    # Controller state is per-request and released with the slot.
    assert "at-1" not in e.runner._spec_controller._ema


def test_metrics_export_round10_series(engines_r10):
    from production_stack_tpu.server.metrics import render_engine_metrics

    eng10, _ = engines_r10
    text = render_engine_metrics(eng10["adaptive"], "m")
    for name in ("pstpu:spec_acceptance_rate_window",
                 "pstpu:spec_draft_depth", "pstpu:spec_tree_nodes_total",
                 "pstpu:spec_acceptance_ema",
                 "pstpu:spec_gamma0_dispatches_total"):
        assert name in text, name


@pytest.mark.slow
def test_stop_string_inside_a_tree_window(engines, engines_r10):
    """Round 10 companion of the linear stop test: the stop match lands
    inside a TREE draft/verify window and truncation must still match
    spec-off byte for byte on both the tree and adaptive engines."""
    eng, loop = engines
    eng10, loop10 = engines_r10
    sp = SamplingParams(temperature=0.0, max_tokens=40, ignore_eos=True)
    base_text, _ = _run(loop, eng["off"], "tell me a tree story", sp,
                        "tstop-base")
    assert len(base_text) > 8
    mid = len(base_text) // 2
    stop = base_text[mid:mid + 3]
    idx = base_text.find(stop)
    assert idx > 0
    sp_stop = SamplingParams(temperature=0.0, max_tokens=40,
                             ignore_eos=True, stop=[stop])
    off_text, off = _run(loop, eng["off"], "tell me a tree story",
                         sp_stop, "tstop-off")
    for mode in ("tree", "adaptive"):
        on_text, on = _run(loop10, eng10[mode], "tell me a tree story",
                           sp_stop, f"tstop-{mode}")
        assert on_text == off_text == base_text[:idx], mode
        assert on[-1].token_ids == off[-1].token_ids, mode
        assert on[-1].finish_reason == "stop", mode


@pytest.mark.slow
def test_resume_of_tree_and_adaptive_streams(engines, engines_r10):
    """PR-9 resume contract over the round-10 paths: a mid-stream resume
    of a tree/adaptive stream continues token-identically (the host only
    ever saw accepted tokens — tree salvage included)."""
    eng, loop = engines
    eng10, loop10 = engines_r10
    sp = SamplingParams(temperature=0.0, max_tokens=14, ignore_eos=True)
    _, full = _run(loop, eng["off"], "resume a tree stream", sp,
                   "tr-full")
    toks = full[-1].token_ids
    assert len(toks) == 14
    for mode in ("tree", "adaptive"):
        _, res = _run(
            loop10, eng10[mode], "resume a tree stream", sp, f"tr-{mode}",
            resume_tokens=toks[:5],
            resume_seed=resolved_seed_base("tr-full", sp),
        )
        assert res[-1].token_ids == toks, mode


@pytest.mark.slow
def test_preemption_recompute_under_spec(engines):
    """A spec engine starved of KV blocks preempts and re-prefills; the
    draft ring resets on the fresh chunk 0 and output stays identical to
    the unpressured spec-off run."""
    loop = asyncio.new_event_loop()
    tight = dict(BASE)
    tight["num_kv_blocks"] = 24  # tight pool: forces preemption
    e_on = ServingEngine(EngineConfig(
        **tight, speculative_num_tokens=3, speculative_model="tiny-llama"))
    e_off = ServingEngine(EngineConfig(**tight))
    loop.run_until_complete(e_on.start())
    loop.run_until_complete(e_off.start())
    try:
        async def pair(e, tag):
            return await asyncio.gather(
                _collect(e, "pressure stream alpha", SamplingParams(
                    temperature=0.0, max_tokens=20, ignore_eos=True),
                    f"{tag}-a"),
                _collect(e, "pressure stream beta", SamplingParams(
                    temperature=0.0, max_tokens=20, ignore_eos=True),
                    f"{tag}-b"),
            )
        off = loop.run_until_complete(pair(e_off, "pp-off"))
        on = loop.run_until_complete(pair(e_on, "pp-on"))
        for (_, o), (_, s) in zip(off, on):
            assert s[-1].token_ids == o[-1].token_ids
    finally:
        loop.run_until_complete(e_on.stop())
        loop.run_until_complete(e_off.stop())
        loop.close()
