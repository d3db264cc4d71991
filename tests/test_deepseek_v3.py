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

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.engine import ServingEngine
from production_stack_tpu.engine.sampling import SamplingParams
from production_stack_tpu.engine.scheduler import Sequence
from production_stack_tpu.models import deepseek_v3 as ds
from production_stack_tpu.models.config import TINY_DEEPSEEK_V3, ModelConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tests", "reference"))
import deepseek_v3_ref as ref  # noqa: E402

TOL = 5e-5
ROUTING_TOL = 1e-3
TOP = 20


def hf_config(mc: ModelConfig) -> dict:
    """The HF config.json keys the reference reads, from a ModelConfig."""
    return {
        "num_hidden_layers": mc.num_layers,
        "num_attention_heads": mc.num_heads,
        "qk_nope_head_dim": mc.qk_nope_head_dim,
        "qk_rope_head_dim": mc.qk_rope_head_dim,
        "kv_lora_rank": mc.kv_lora_rank, "v_head_dim": mc.v_head_dim,
        "rope_theta": mc.rope_theta, "rms_norm_eps": mc.rms_norm_eps,
        "first_k_dense_replace": mc.first_k_dense_replace,
        "num_experts_per_tok": mc.num_experts_per_tok,
        "routed_scaling_factor": mc.routed_scaling_factor,
        "norm_topk_prob": mc.norm_topk_prob,
    }


def make_engine(**over) -> ServingEngine:
    cfg = dict(model="tiny-deepseek-v3", max_model_len=512,
               num_kv_blocks=128, num_decode_steps=8, dtype="float32",
               max_num_seqs=8, max_num_batched_tokens=64, max_prefill_seqs=8)
    cfg.update(over)
    return ServingEngine(EngineConfig(**cfg))


def prompt(n: int, salt: int):
    return [int(x) for x in np.random.default_rng(salt).integers(1, 512, n)]


def add(eng, name, tokens, max_tokens) -> Sequence:
    seq = Sequence(name, list(tokens), SamplingParams(
        temperature=0.0, max_tokens=max_tokens, ignore_eos=True,
        logprobs=TOP))
    eng.scheduler.add_sequence(seq)
    return seq


def step(eng):
    batch = eng.scheduler.schedule()
    tokens, lps = eng.runner.execute(batch, 0)
    eng.scheduler.update_after_step(batch, tokens, lps)
    return batch


def drive(eng) -> list:
    batches = []
    while eng.scheduler.has_work():
        batches.append(step(eng))
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


def test_d_five_rows_of_unequal_length_in_one_prefill():
    """Padded positions of a row reach no expert and are not counted."""
    eng = make_engine(max_num_batched_tokens=1024)
    lens = (5, 12, 9, 3, 11)
    seqs = [add(eng, f"d{i}", prompt(n, 20 + i), 1)
            for i, n in enumerate(lens)]
    batches = drive(eng)
    assert [b.kind for b in batches] == ["prefill"]
    assert len(batches[0].seqs) == 5
    for seq in seqs:
        assert worst(eng, seq) < TOL
    mc = eng.model_config
    pre = eng.runner.fwd_stats_total["prefill"]
    sparse = mc.num_layers - mc.first_k_dense_replace
    assert pre["assignments"] == sum(lens) * mc.num_experts_per_tok * sparse
    assert pre["layer_calls"] == sparse
    assert 0 < pre["experts_touched"] <= sparse * mc.n_routed_experts


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


def packed_row_against_rectangle(monkeypatch, tiny, module):
    """Five prompts through an engine whose prefill dispatches are packed
    rows over the latent pool (``prefill_packs``: the tiny model with heads
    enough to fill a sublane tile in float32, on the paged path) and through
    one made to dispatch rectangles: a prompt alone, then at once a prefix
    hit on it, one that crosses the budget and two short ones. The packed
    engine's log-probabilities are the reference's, and both engines serve
    the same tokens. ``module``: the test module's ``make_engine``, ``add``,
    ``drive``, ``worst`` (tests/test_xing4.py calls this with its own)."""
    import dataclasses

    from production_stack_tpu.models import config as models_config

    name = tiny.name + "-8-heads"
    monkeypatch.setitem(
        models_config.NAMED_CONFIGS, name, dataclasses.replace(
            tiny, num_heads=8, num_kv_heads=8, name=name))
    shared = prompt(64, 80)
    served = {}
    for form in ("packed", "rectangle"):
        eng = module.make_engine(model=name, attn_impl="paged",
                                 max_num_batched_tokens=512)
        assert eng.runner.kv_pools == 1
        assert eng.runner.prefill_packs and eng.scheduler.prefill_packed
        assert {f[0] for f in eng.runner.reachable_prefill_families()} == {1}
        if form == "rectangle":
            eng.runner.__dict__["prefill_packs"] = False
            eng.scheduler.prefill_packed = False
        first = module.add(eng, "g0", shared + prompt(10, 81), 3)
        module.drive(eng)
        seqs = [first] + [
            module.add(eng, f"g{i + 1}", tokens, 5) for i, tokens in
            enumerate((shared + prompt(12, 82), prompt(470, 2), prompt(5, 3),
                       prompt(40, 4)))]
        prefills = [b for b in module.drive(eng) if b.kind == "prefill"]
        assert seqs[1].num_cached_tokens == 64
        assert all(b.packed for b in prefills) is (form == "packed")
        if form == "packed":
            assert max(len(b.seqs) for b in prefills) == 4
            # The prefix hit (history 64) lay in one row with first
            # chunks, and the long prompt crossed the budget.
            assert any(64 in b.chunk_starts and 0 in b.chunk_starts
                       for b in prefills)
            assert sum(seqs[2] in b.seqs for b in prefills) > 1
            for seq in seqs:
                assert module.worst(eng, seq) < TOL
        served[form] = [
            (seq.output_token_ids, [lp for lp, _ in seq.output_logprobs])
            for seq in seqs]
    for (toks_p, lps_p), (toks_r, lps_r) in zip(served["packed"],
                                                served["rectangle"]):
        assert toks_p == toks_r and len(toks_p) in (3, 5)
        np.testing.assert_allclose(lps_p, lps_r, atol=TOL, rtol=0)


def test_g_a_packed_prefill_row_serves_what_the_rectangle_serves(monkeypatch):
    import sys

    packed_row_against_rectangle(
        monkeypatch, TINY_DEEPSEEK_V3, sys.modules[__name__])


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


# ---- the counters ride each dispatch's fetch -----------------------------------
def test_counters_of_decode_and_prefill_are_kept_apart():
    eng = make_engine()
    mc = eng.model_config
    sparse = mc.num_layers - mc.first_k_dense_replace
    seqs = [add(eng, f"m{i}", prompt(12 + i, 90 + i), 9) for i in range(2)]
    batches = drive(eng)
    stats = eng.stats()
    decodes = [b for b in batches if b.kind == "decode"]
    # A train runs as many steps as its longest budget; a row takes a
    # token (and reaches k experts a sparse layer) while its own lasts.
    assert stats["moe_layer_calls_total"] == sparse * sum(
        max(b.decode_steps) for b in decodes)
    decode_pairs = sum(sum(b.decode_steps) for b in decodes) \
        * mc.num_experts_per_tok * sparse
    assert sum(sum(b.decode_steps) for b in decodes) >= sum(
        len(s.output_token_ids) - 1 for s in seqs)
    prefill_pairs = sum(len(s.prompt_token_ids) for s in seqs) \
        * mc.num_experts_per_tok * sparse
    assert stats["moe_assignments_total"] == decode_pairs + prefill_pairs
    calls = stats["moe_layer_calls_total"]
    assert mc.num_experts_per_tok * calls <= \
        stats["moe_experts_touched_total"] <= \
        2 * mc.num_experts_per_tok * calls
    assert stats["moe_prefill_layer_calls_total"] == sparse * sum(
        b.kind == "prefill" for b in batches)
    assert stats["moe_expert_load_max_total"] >= calls
    assert not eng.runner._fwd_stats_pending


def test_a_fetch_reads_no_counters_of_a_later_dispatch():
    """Dispatches are issued ahead of the fetch of the one before; a fetch
    adds up what was noted up to ITS dispatch and leaves a later one's
    counters, which may not be ready, on the device."""
    r = make_engine().runner
    first = r._note_fwd_stats("decode", jnp.asarray([6, 5, 2, 1]))
    chunk = r._note_fwd_stats("prefill", jnp.asarray([60, 16, 9, 1]))
    later = r._note_fwd_stats("decode", jnp.asarray([600, 16, 90, 1]))
    r._drain_fwd_stats(first)
    assert [n for n, _, _ in r._fwd_stats_pending] == [chunk, later]
    assert r.fwd_stats_total["decode"]["assignments"] == 6
    r._drain_fwd_stats(chunk)
    assert [n for n, _, _ in r._fwd_stats_pending] == [later]
    assert r.fwd_stats_total["prefill"]["expert_load_max"] == 9
    r._drain_fwd_stats(later)
    assert r.fwd_stats_total["decode"] == {
        "assignments": 606, "experts_touched": 21, "expert_load_max": 92,
        "layer_calls": 2}


def test_a_model_without_experts_reports_zeros():
    eng = ServingEngine(EngineConfig(
        model="tiny-llama", max_model_len=128, num_kv_blocks=32,
        max_num_seqs=2, max_num_batched_tokens=64))
    assert eng.runner.fwd_stats == ()
    stats = eng.stats()
    assert stats["moe_assignments_total"] == 0
    assert stats["moe_layer_calls_total"] == 0


# ---- what a latent row cannot follow is refused at start ------------------------
@pytest.mark.parametrize("flags,named", [
    ({"speculative_num_tokens": 3, "speculative_model": "tiny-llama"},
     "speculative"),
    ({"kv_offload_cpu": True}, "offload"),
    ({"kv_remote_url": "http://127.0.0.1:1"}, "offload"),
    ({"role": "prefill", "kv_remote_url": "http://127.0.0.1:1"}, "disagg"),
    ({"kv_cache_dtype": "int8"}, "int8"),
    ({"tensor_parallel_size": 2}, "parallelism"),
    ({"sequence_parallel_size": 2}, "parallelism"),
    ({"lora_modules": {"a": "/nonexistent"}}, "LoRA"),
])
def test_what_a_latent_row_cannot_follow_is_refused_at_start(flags, named):
    with pytest.raises(ValueError, match="latent row") as err:
        make_engine(**flags)
    assert named.lower() in str(err.value).lower()


def test_a_kv_model_is_refused_nothing():
    from production_stack_tpu.models.config import TINY_LLAMA

    EngineConfig(model="tiny-llama", kv_cache_dtype="int8",
                 kv_offload_cpu=True).refuse_what_latent_rows_cannot_follow(
        TINY_LLAMA)


@pytest.mark.parametrize("key,value", [
    ("rope_scaling", {"type": "linear", "factor": 4}),
    ("rope_scaling", {"rope_type": "dynamic", "factor": 2}),
    ("n_group", 8), ("topk_group", 4), ("scoring_func", "softmax"),
    ("topk_method", "greedy"), ("rope_interleave", False),
    ("attention_bias", True), ("moe_layer_freq", 2), ("hidden_act", "gelu"),
])
def test_what_the_module_does_not_implement_is_refused_by_its_key(key, value):
    """Served since the module learnt them, and so no longer here: a
    low-rank query (``q_lora_rank``) and YaRN (``rope_scaling`` of type
    ``yarn``); tests/test_xing4.py holds both to HF's own modeling code."""
    import json

    with open(os.path.join(ROOT, "benchmarks", "chip", "configs",
                           "kanana-2-30b-a3b-d8", "config.json")) as f:
        cfg = json.load(f)
    assert ModelConfig.from_hf_config(cfg).arch == "deepseek_v3"
    served = ModelConfig.from_hf_config(dict(cfg, q_lora_rank=1536, rope_scaling={
        "type": "yarn", "factor": 40,
        "original_max_position_embeddings": 4096}))
    assert served.q_lora_rank == 1536 and served.rope_scaling.factor == 40
    cfg[key] = value
    with pytest.raises(ValueError, match="deepseek_v3: not supported"):
        ModelConfig.from_hf_config(cfg)


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


# ---- sizes ------------------------------------------------------------------------
def test_cache_bytes_count_the_padded_row_once():
    mc = TINY_DEEPSEEK_V3
    cfg = EngineConfig(model=mc.name, dtype="float32")
    specs = ds.cache_specs(mc)
    assert specs.kv_pools == 1 and specs.latent.width == 256
    assert cfg.kv_cache_bytes_per_token(mc) == mc.num_layers * 256 * 4


async def test_the_served_surface_shows_one_pool_and_two_program_kinds():
    """``GET /version`` and ``GET /debug/programs`` through the HTTP
    surface: the same two program kinds as every other model, the latent
    pool as THE pool (no second one of any size)."""
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from production_stack_tpu.server.api_server import APIServer

    eng = make_engine()
    client = TestClient(TestServer(APIServer(eng).build_app()))
    await client.start_server()
    try:
        done = await asyncio.gather(*(client.post("/v1/completions", json={
            "model": "tiny-deepseek-v3", "prompt": prompt(12, 70 + i),
            "max_tokens": 12, "temperature": 0, "ignore_eos": True})
            for i in range(2)))
        assert [r.status for r in done] == [200] * 2
        text = await (await client.get("/metrics")).text()
        programs = (await (await client.get("/debug/programs")).json())[
            "programs"]
    finally:
        await client.close()
    assert {p["program"] for p in programs} == {"decode", "prefill"}
    sample = {ln.split("{")[0]: float(ln.rsplit(" ", 1)[1])
              for ln in text.splitlines() if ln.startswith("pstpu:moe_")}
    assert set(sample) == {f"pstpu:moe_{k}_total" for k in (
        "assignments", "expert_load_max", "experts_touched", "layer_calls",
        "prefill_experts_touched", "prefill_layer_calls")}
    assert sample["pstpu:moe_layer_calls_total"] > 0
    assert eng.runner.kv_v.size == 0
    pool_bytes = eng.runner.kv_k.size * eng.runner.kv_k.dtype.itemsize
    assert all(p["pool_bytes"] == pool_bytes for p in programs)
