"""The LFM2-MoE family (gated short convolutions whose state is two tokens a
layer a sequence, beside paged K/V in the few rotary attention layers, and
sigmoid-routed sparse experts behind both) against its plain reference
(tests/reference/lfm2_moe_ref.py), through the engine's own scheduler, block
manager and runner at a tiny preset with float32 activations: the one
module that declares a state AND counters.

What is compared is log-probabilities, not tokens: every generated token's
own log-probability and those of the 20 most likely tokens at its position,
as the served surface returns them (``logprobs=20``), against the
reference's log-softmax at the same ids.

TOL: both sides are float32 with full-precision products on the CPU; they
differ in the ORDER of sums (the grouped matmul over sorted pairs against
dense experts, batched rows, a prompt cut into chunks, the paged kernel's
blocks) over 24 layers. Measured largest difference over every case here:
under 1e-5 (logit spread 1.0). The nine wrong models of
``test_the_tolerance_tells_a_wrong_model`` move the same numbers by 0.3 to
2.8 units (the nearest, ``bias_in_weights``, by 0.296), so 2e-3 leaves both
sides two decades of room.
"""

import dataclasses
import json
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
from production_stack_tpu.models import config as model_configs
from production_stack_tpu.models import get_model, lfm2_moe
from production_stack_tpu.models.config import (
    LFM2_LAYER_TYPES,
    TINY_LFM2_MOE,
    ModelConfig,
)
from production_stack_tpu.ops import moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tests", "reference"))
import lfm2_moe_ref as ref  # noqa: E402

TOL = 2e-3
TOP = 20
CHUNK = 64          # make_engine's max_num_batched_tokens
CUT = os.path.join(ROOT, "benchmarks", "chip", "configs",
                   "lfm2-8b-a1b-d16", "config.json")
# The cut's 16 entries (four whole periods) beside the published 24.
TINY_CUT = dataclasses.replace(
    TINY_LFM2_MOE, num_layers=16, layer_types=LFM2_LAYER_TYPES[:16],
    name="tiny-lfm2-moe-d16")


def hf_config(mc: ModelConfig) -> dict:
    """The HF config.json keys the reference reads, from a ModelConfig."""
    return {
        "hidden_size": mc.hidden_size, "num_hidden_layers": mc.num_layers,
        "num_attention_heads": mc.num_heads,
        "num_key_value_heads": mc.num_kv_heads, "head_dim": mc.head_dim,
        "norm_eps": mc.rms_norm_eps, "rope_theta": mc.rope_theta,
        "layer_types": list(mc.layer_types),
        "num_dense_layers": mc.first_k_dense_replace,
        "num_experts_per_tok": mc.num_experts_per_tok,
        "norm_topk_prob": mc.norm_topk_prob,
        "routed_scaling_factor": mc.routed_scaling_factor,
    }


def make_engine(model="tiny-lfm2-moe", **over) -> ServingEngine:
    cfg = dict(model=model, max_model_len=512, num_kv_blocks=128,
               num_decode_steps=8, dtype="float32", max_num_seqs=8,
               max_num_batched_tokens=CHUNK, max_prefill_seqs=8)
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


def step(eng, edit=None):
    """One dispatch, synchronously: schedule, (edit), run, apply."""
    batch = eng.scheduler.schedule()
    if edit is not None:
        edit(batch)
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
    mc = eng.model_config
    tokens = seq.all_token_ids
    logits = ref.forward(eng.runner.params, hf_config(mc), tokens[:-1],
                         wrong, chunk=CHUNK)
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
    """The third chunk starts ON the prompt's last token but one: its first
    taps read the two tokens the slot carried over."""
    seq = add(engine, "b", prompt(130, 2), 4)
    batches = drive(engine)
    assert [b.chunk_lens for b in batches if b.kind == "prefill"] == \
        [[64], [64], [2]]
    assert worst(engine, seq) < TOL


def test_c_a_decode_train_in_which_one_row_ends_early(engine):
    """40 decode steps in trains of 8; in the second train row 1 is given a
    budget of 3 of the 8 steps and goes on afterwards: the 5 steps that
    deliver nothing must leave its conv state as it was and reach no
    expert."""
    seqs = [add(engine, f"c{i}", prompt(20 + 7 * i, 10 + i), n)
            for i, n in enumerate((41, 41, 30))]
    cut = {}
    pairs = engine.runner.fwd_stats_total["decode"]["assignments"]
    taken = []

    def shorten(batch):
        """The first full train that row 1 rides with rows beside it."""
        if batch.kind == "decode" and not cut and batch.num_steps == 8 \
                and len(batch.seqs) == 3:
            i = batch.seqs.index(seqs[1])
            cut["before"], cut["rows"] = batch.decode_steps[i], 3
            batch.decode_steps[i] = 3
        if batch.kind == "decode":
            taken.append(sum(batch.decode_steps))

    while engine.scheduler.has_work():
        step(engine, shorten)
    assert cut == {"before": 8, "rows": 3}
    assert [len(s.output_token_ids) for s in seqs] == [41, 41, 30]
    for seq in seqs:
        assert worst(engine, seq) < TOL
    mc = engine.model_config
    engine.runner._drain_fwd_stats(engine.runner._fwd_stats_noted)
    assert engine.runner.fwd_stats_total["decode"]["assignments"] - pairs \
        == sum(taken) * mc.num_experts_per_tok \
        * (mc.num_layers - mc.first_k_dense_replace)


@pytest.mark.parametrize("attn_impl,form", [
    ("window", "rectangle"), ("paged", "rectangle"), ("paged", "packed")],
    ids=["window-rectangle", "paged-rectangle", "paged-packed"])
def test_d_rows_of_unequal_length_in_one_prefill_rectangle(attn_impl, form):
    """Five sequences in one dispatch; two are shorter than the
    convolution's three taps, so the conv state they leave holds zeros from
    before the sequence; padding reaches no expert. As a rectangle, a row
    each (the window path every CPU engine takes, and the pool read in
    place with ``prefill_packs`` forced false: what a runner with an
    adapter a row builds), and as the segments of ONE packed row, where the
    one-token sequence's neighbours lie right before and behind it."""
    engine = make_engine(max_num_batched_tokens=1024, attn_impl=attn_impl)
    assert engine.runner.prefill_packs is (attn_impl == "paged")
    if form == "rectangle":
        engine.runner.__dict__["prefill_packs"] = False
        engine.scheduler.prefill_packed = False
    lens = (5, 12, 1, 2, 11)
    seqs = [add(engine, f"d{i}", prompt(n, 20 + i), 3)
            for i, n in enumerate(lens)]
    batches = drive(engine)
    assert batches[0].kind == "prefill" and len(batches[0].seqs) == 5
    assert batches[0].packed is (form == "packed")
    for seq in seqs:
        assert worst(engine, seq) < TOL
    mc = engine.model_config
    sparse = mc.num_layers - mc.first_k_dense_replace
    pre = engine.runner.fwd_stats_total["prefill"]
    assert pre["assignments"] == sum(lens) * mc.num_experts_per_tok * sparse
    assert pre["layer_calls"] == sparse


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


@pytest.mark.parametrize("mc,attn_impl", [
    (TINY_CUT, "window"), (TINY_CUT, "paged"), (TINY_LFM2_MOE, "paged")],
    ids=["cut16-window", "cut16-paged", "published24-paged"])
def test_h_decode_through_the_state_slots_and_the_pool(monkeypatch, mc,
                                                       attn_impl):
    """The cut's 16 entries and the published 24, both ``attn_impl``s: the
    window path, and the paged decode kernel and the grouped matmul
    (interpreted on the CPU) over 64-lane KV heads paired into rows of 128
    lanes."""
    monkeypatch.setitem(model_configs.NAMED_CONFIGS, mc.name, mc)
    eng = make_engine(mc.name, attn_impl=attn_impl)
    assert eng.runner.attn_impl == attn_impl
    assert eng.model_config.head_dim_ == 64
    assert eng.runner.kv_k.shape[0] == sum(
        t == "full_attention" for t in mc.layer_types)
    assert eng.runner.kv_k.shape[1::2] == (1, 128)
    seqs = [add(eng, f"h{i}", prompt(n, 50 + i), 12)
            for i, n in enumerate((70, 18))]
    drive(eng)
    for seq in seqs:
        assert worst(eng, seq) < TOL


# ---- packed rows: a segment a sequence, a slot's state a segment ------------
PACKED_BUDGET = 512     # four segments a row; an equal share is 170 tokens


@pytest.fixture(scope="module")
def packed():
    """An engine whose prefill dispatches are packed rows: the pool read in
    place by the packed flash kernel (interpreted), the conv state of each
    SEGMENT from and to its sequence's slot."""
    eng = make_engine(attn_impl="paged", max_model_len=1024,
                      num_kv_blocks=256,
                      max_num_batched_tokens=PACKED_BUDGET)
    assert eng.runner.state_specs and eng.runner.prefill_packs
    assert eng.scheduler.prefill_packed and eng.runner._prefill_segs == 4
    assert {f[0] for f in eng.runner.reachable_prefill_families()} == {1}
    return eng


def prefills(batches):
    return [b for b in batches if b.kind == "prefill"]


def test_i_a_prompt_crossing_three_packed_rows_beside_two_neighbours(packed):
    """Three prompts longer than their share of three successive rows: each
    crosses twice through its slot, and its first tokens of the second and
    third row read the slot's two tokens while the row's token before them
    is a neighbour's last."""
    seqs = [add(packed, f"i{i}", prompt(n, 100 + i), 3)
            for i, n in enumerate((500, 400, 380))]
    rows = prefills(drive(packed))
    assert all(b.packed and b.seqs == seqs for b in rows)
    assert [b.chunk_lens for b in rows] == \
        [[172, 170, 170], [172, 170, 170], [156, 60, 40]]
    assert [b.chunk_starts for b in rows][1:] == \
        [[172, 170, 170], [344, 340, 340]]
    for seq in seqs:
        assert worst(packed, seq) < TOL


def test_j_a_second_request_on_a_freed_slot_starts_from_zeros_in_a_packed_row(
        packed):
    """The slot a finished sequence leaves holds its last two tokens; the
    next owner's first segment, in a row with a neighbour, starts from
    zeros all the same (``fresh``: the segment's chunk starts at 0)."""
    first = add(packed, "j1", prompt(33, 40), 9)
    step(packed)
    slot = first.state_slot
    drive(packed)
    assert slot and packed.block_manager.state_slots_in_use == 0
    assert np.any(np.asarray(packed.runner.state_pools[0][slot]) != 0)
    second = add(packed, "j2", prompt(21, 41), 9)
    beside = add(packed, "j3", prompt(2, 42), 9)
    batch = step(packed)
    assert batch.packed and batch.seqs == [second, beside]
    assert second.state_slot == slot
    drive(packed)
    assert worst(packed, second) < TOL and worst(packed, beside) < TOL


@pytest.fixture(scope="module")
def served_packed(packed):
    """300 prompt tokens between two neighbours' 300: the prompt's second
    segment starts at its token 172, behind a neighbour-free row's start
    and before two neighbours' segments, and 40 tokens are decoded."""
    beside = [add(packed, "w0", prompt(300, 71), 2)]
    seq = add(packed, "w", prompt(300, 70), 40)
    beside.append(add(packed, "w2", prompt(300, 72), 2))
    rows = prefills(drive(packed))
    assert [b.chunk_lens for b in rows] == [[172, 170, 170], [128, 130, 130]]
    assert all(b.packed for b in rows)
    assert worst(packed, seq) < TOL
    return seq


@pytest.mark.parametrize("wrong", ref.WRONG)
def test_the_tolerance_tells_a_wrong_model_through_packed_rows(
        packed, served_packed, wrong):
    """What packed rows served is as far from every wrong model as what
    rectangles served."""
    assert worst(packed, served_packed, wrong=(wrong,)) > 10 * TOL


@pytest.mark.asyncio
async def test_k_the_engine_loop_counts_the_segments_of_its_packed_rows():
    """Six prompts at once through the engine's own loop: the same greedy
    tokens as each alone, ``pstpu:prefill_segments_total`` counts a segment
    a sequence a dispatch (as ``pstpu:prefill_rows_issued_total`` does,
    and more of them than dispatches), and every token reached the experts
    once: a row's padded end reached none."""
    import asyncio

    lens = [5, 130, 17, 300, 64, 2]
    prompts = [prompt(n, 200 + i) for i, n in enumerate(lens)]
    eng = make_engine(attn_impl="paged", max_model_len=1024,
                      num_kv_blocks=256, num_decode_steps=4,
                      max_num_batched_tokens=PACKED_BUDGET,
                      enable_warmup=False)
    await eng.start()

    async def one(i):
        out = None
        async for o in eng.generate(
                prompt_token_ids=prompts[i], sampling=SamplingParams(
                    temperature=0.0, max_tokens=5, ignore_eos=True)):
            out = o
        return out.token_ids

    try:
        assert eng.runner.prefill_packs and eng.scheduler.prefill_packed
        alone = [await one(i) for i in range(len(prompts))]
        before = eng.stats()
        together = await asyncio.gather(*map(one, range(len(prompts))))
        after = eng.stats()
    finally:
        await eng.stop()
    assert alone == together and all(len(t) == 5 for t in together)

    def delta(name):
        return after[name] - before[name]

    mc = eng.model_config
    sparse = mc.num_layers - mc.first_k_dense_replace
    dispatches = delta("prefill_dispatches_total")
    assert delta("prefill_tokens_issued_total") == sum(lens)
    assert delta("prefill_segments_total") == \
        delta("prefill_rows_issued_total") > dispatches
    assert delta("moe_prefill_layer_calls_total") == sparse * dispatches
    # Five answered tokens a request, four of them decoded.
    assert delta("moe_assignments_total") == \
        (sum(lens) + 4 * len(lens)) * mc.num_experts_per_tok * sparse


# ---- the tolerance is tight enough -----------------------------------------
@pytest.fixture(scope="module")
def served(engine):
    """129 prompt tokens: the third chunk is the last token alone, so the
    first answer stands right behind a chunk boundary."""
    seq = add(engine, "w", prompt(2 * CHUNK + 1, 70), 40)
    drive(engine)
    assert worst(engine, seq) < TOL
    return seq


@pytest.mark.parametrize("wrong", ref.WRONG)
def test_the_tolerance_tells_a_wrong_model(engine, served, wrong):
    """A prompt of 129 tokens (three chunks) and 40 decoded tokens, against
    the reference with ONE equation wrong: each is far outside TOL."""
    assert worst(engine, served, wrong=(wrong,)) > 10 * TOL


# ---- routing ------------------------------------------------------------------
def test_the_expert_bias_moves_the_choice_and_not_the_weights():
    """A bias that lifts the two weakest experts over every other: they are
    chosen, and their weights are still their own scores over their sum."""
    k0, k1 = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(k0, (50, 32))
    w_r = jax.random.normal(k1, (32, 8)) * 32 ** -0.5
    s = jax.nn.sigmoid(jnp.dot(x, w_r, precision="highest"))
    idx0, w0 = moe.route(x, w_r, jnp.zeros((8,)), 2, 1.0, True,
                         lfm2_moe.ROUTE_EPS)
    np.testing.assert_array_equal(
        np.sort(idx0, -1), np.sort(np.argsort(-s, -1)[:, :2], -1))
    weakest = np.argsort(np.asarray(s), -1)[0, :2]
    bias = jnp.zeros((8,)).at[weakest].set(10.0)
    idx, w = moe.route(x, w_r, bias, 2, 1.0, True, lfm2_moe.ROUTE_EPS)
    assert set(np.asarray(idx[0])) == set(weakest) != set(np.asarray(idx0[0]))
    picked = np.take_along_axis(np.asarray(s), np.asarray(idx), 1)
    np.testing.assert_allclose(
        w, picked / (picked.sum(-1, keepdims=True) + 1e-6), rtol=1e-6)
    # The published 1e-6 is in the sum: two scores of 0.5 weigh 0.5 / (1 +
    # 1e-6) each, where the latent family's 1e-20 would give 0.5 exactly.
    _, halves = moe.route(jnp.zeros((1, 32)), jnp.zeros((32, 8)),
                          jnp.zeros((8,)), 2, 1.0, True, lfm2_moe.ROUTE_EPS)
    np.testing.assert_allclose(halves, 0.5 / (1.0 + 1e-6), rtol=1e-7)
    assert float(halves[0, 0]) < 0.5


def test_the_programs_choices_are_the_references(engine):
    """Share of (token, sparse layer) choices whose top-k SET differs
    between the program's whole forward and the reference's: none in
    float32 over 200 tokens; and the router in bf16 flips several in a
    hundred, each a different function of the token."""
    mc, tokens = engine.model_config, prompt(200, 71)
    *_, chosen = lfm2_moe.forward(
        engine.runner.params, mc, jnp.asarray([tokens], jnp.int32),
        jnp.arange(200, dtype=jnp.int32)[None], jnp.asarray([200]),
        routing=True)
    right, low = [], []
    ref.forward(engine.runner.params, hf_config(mc), tokens, routing=right)
    ref.forward(engine.runner.params, hf_config(mc), tokens,
                ("router_bf16",), routing=low)

    def sets(x):
        return np.sort(np.stack([np.asarray(c) for c in x]), -1)

    assert np.asarray(chosen).shape == (22, 200, 2)
    assert np.mean(np.any(
        np.sort(np.asarray(chosen), -1) != sets(right), -1)) < 0.002
    assert np.mean(np.any(sets(low) != sets(right), -1)) > 0.01


# ---- the counters of a model with state -----------------------------------------
def test_counters_count_for_a_model_with_state():
    """The six ``pstpu:moe_*`` series from a module that also carries a
    state through the decode loop: decode and prefill apart."""
    eng = make_engine()
    mc = eng.model_config
    assert eng.runner.state_specs and eng.runner.fwd_stats == moe.STATS
    sparse = mc.num_layers - mc.first_k_dense_replace
    seqs = [add(eng, f"m{i}", prompt(12 + i, 90 + i), 9) for i in range(2)]
    batches = drive(eng)
    stats = eng.stats()
    decodes = [b for b in batches if b.kind == "decode"]
    assert stats["moe_layer_calls_total"] == sparse * sum(
        max(b.decode_steps) for b in decodes)
    decode_pairs = sum(sum(b.decode_steps) for b in decodes) \
        * mc.num_experts_per_tok * sparse
    prefill_pairs = sum(len(s.prompt_token_ids) for s in seqs) \
        * mc.num_experts_per_tok * sparse
    assert stats["moe_assignments_total"] == decode_pairs + prefill_pairs
    assert stats["moe_prefill_layer_calls_total"] == sparse * sum(
        b.kind == "prefill" for b in batches)
    assert stats["moe_experts_touched_total"] > 0
    assert not eng.runner._fwd_stats_pending
    for seq in seqs:
        assert worst(eng, seq) < TOL


# ---- config.json: what is read, what is refused ------------------------------
def cut() -> dict:
    with open(CUT) as f:
        return json.load(f)


def published() -> dict:
    return {**cut(), "num_hidden_layers": 24,
            "layer_types": list(LFM2_LAYER_TYPES)}


@pytest.mark.parametrize("doc,layers,attn_at", [
    (published, 24, [2, 6, 10, 14, 18, 21]), (cut, 16, [2, 6, 10, 14])],
    ids=["published24", "cut16"])
def test_from_hf_config_reads_the_published_list_and_the_cut(doc, layers,
                                                             attn_at):
    mc = ModelConfig.from_hf_config(doc(), name="lfm2")
    assert (mc.arch, mc.num_layers, mc.hidden_size, mc.intermediate_size) \
        == ("lfm2_moe", layers, 2048, 7168)
    assert (mc.num_heads, mc.num_kv_heads, mc.head_dim_) == (32, 8, 64)
    assert (mc.n_routed_experts, mc.num_experts_per_tok,
            mc.moe_intermediate_size, mc.first_k_dense_replace,
            mc.n_shared_experts) == (32, 4, 1792, 2, 0)
    assert (mc.conv_l_cache, mc.rms_norm_eps, mc.rope_theta,
            mc.routed_scaling_factor) == (3, 1e-5, 1e6, 1.0)
    assert mc.use_expert_bias and mc.norm_topk_prob \
        and mc.tie_word_embeddings
    assert (mc.vocab_size, mc.max_position_embeddings) == (65536, 128000)
    assert [i for i, t in enumerate(mc.layer_types)
            if t == "full_attention"] == attn_at
    specs = lfm2_moe.cache_specs(mc)
    # 8 KV heads of 64 lanes as 4 rows of 128: 8 KiB a token over 4 layers.
    assert specs.paged_kv == (len(attn_at), 4, 128)
    # Two tokens of 2048 channels as 32 whole rows of lanes: 98 KB a slot
    # over the cut's 12 conv layers, in bf16.
    assert [(s.name, s.layers, s.shape, s.dtype) for s in specs.state] == [
        ("conv", layers - len(attn_at), (32, 128), None)]
    is_attn, conv_at, attn_at_ = lfm2_moe.operator_tables(mc)
    assert [i + 2 for i in np.flatnonzero(is_attn)] == attn_at
    assert list(attn_at_[is_attn > 0]) == list(range(len(attn_at)))
    assert list(conv_at[is_attn == 0]) == list(
        range(2, layers - len(attn_at)))


def test_the_published_list_is_not_equal_periods():
    """Why this module takes any order: the two older hybrids' rule refuses
    the published 24 entries (the sixth attention layer stands at 21)."""
    from production_stack_tpu.models.config import layer_period

    with pytest.raises(ValueError, match="whole number of equal periods"):
        layer_period(LFM2_LAYER_TYPES, 24,
                     kinds=("conv", "full_attention"), closed=False)
    assert layer_period(LFM2_LAYER_TYPES[:16], 16,
                        kinds=("conv", "full_attention"), closed=False) \
        == ("conv", "conv", "full_attention", "conv")


def test_the_served_tree_has_the_published_parameter_count():
    """By hand (ISSUE 44's arithmetic) and from the tree ``init_params``
    makes, as shapes: nothing is allocated."""
    expert = 3 * 2048 * 1792
    sparse = 32 * expert + 2048 * 32 + 32
    dense = 3 * 2048 * 7168
    conv = 2048 * 6144 + 2048 * 2048 + 3 * 2048
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64
    table = 65536 * 2048
    assert (expert, dense, conv, table) == \
        (11_010_048, 44_040_192, 16_783_360, 134_217_728)
    for doc, n_sparse, n_conv, n_attn, by_issue in (
            (published(), 22, 18, 6, 8_339_828_736),
            (cut(), 14, 12, 4, 5_399_060_480)):
        layers = n_conv + n_attn
        by_hand = n_sparse * sparse + 2 * dense + n_conv * conv \
            + n_attn * attn + table
        norms = 2 * layers * 2048 + 2048
        # The issue's count leaves the norms, the router's bias and the
        # per-head norms' weights aside.
        assert by_hand - n_sparse * 32 - n_attn * 128 == by_issue
        mc = ModelConfig.from_hf_config(doc)
        tree = jax.eval_shape(
            lambda: lfm2_moe.init_params(mc, jax.random.PRNGKey(0)))
        assert sum(x.size for x in jax.tree.leaves(tree)) == by_hand + norms
        assert {k for k, v in tree["layers"]["sparse"].items()
                if v.dtype == jnp.float32} == set(lfm2_moe.FLOAT32_LEAVES)


def test_a_checkpoint_in_hf_layout_loads_into_the_stacks_by_kind(tmp_path):
    """``init_params``' tree written out under HF's names and layouts ([out,
    in] matrices, a [D, 1, L] conv, one tensor an expert, no ``lm_head``)
    and read back by models/weights.py: the same tree; a layer's operator
    and its FFN are filed under their own kinds."""
    pytest.importorskip("safetensors")
    from safetensors.numpy import save_file

    from production_stack_tpu.models.weights import load_hf_params

    mc = dataclasses.replace(TINY_CUT, num_layers=8,
                             layer_types=LFM2_LAYER_TYPES[:8])
    params = lfm2_moe.init_params(mc, jax.random.PRNGKey(3), jnp.float32)
    ours_to_hf = {v[0]: (k, v[1]) for k, v in lfm2_moe.HF_LAYER_MAP.items()}
    tensors = {"model.embed_tokens.weight": np.asarray(params["embed"]),
               "model.embedding_norm.weight": np.asarray(
                   params["final_norm"])}
    f = mc.moe_intermediate_size
    for i, slot in enumerate(lfm2_moe.layer_slots(mc)):
        for kind, at in set(slot.values()):
            stacks = dict(params["layers"][kind])
            if kind == "sparse":       # the checkpoint's gate and up apart
                gate_up = stacks.pop("w_gate_up")
                stacks["we_gate"], stacks["we_up"] = \
                    gate_up[..., :f], gate_up[..., f:]
            for leaf, stack in stacks.items():
                name, transpose = ours_to_hf[leaf]
                x = np.asarray(stack[at])
                if leaf == "conv_w":
                    x = x[:, None, :]                       # [L, 1, D]
                each = [(name, x)] if "*" not in name else [
                    (name.replace("*", str(e)), x[e])
                    for e in range(mc.n_routed_experts)]
                for name, x in each:
                    tensors[f"model.layers.{i}.{name}"] = \
                        np.ascontiguousarray(x.T if transpose else x)
    save_file(tensors, str(tmp_path / "model.safetensors"))
    loaded = load_hf_params(mc, str(tmp_path), jnp.float32)
    assert "lm_head" not in loaded
    flat_want = jax.tree_util.tree_leaves_with_path(params)
    flat_got = dict(jax.tree_util.tree_leaves_with_path(loaded))
    assert len(flat_want) == len(flat_got)
    for path, want in flat_want:
        np.testing.assert_array_equal(flat_got[path], want, str(path))


@pytest.mark.parametrize("types", [
    ("full_attention", "conv", "conv", "conv"),
    ("conv", "conv", "conv", "full_attention"),
    ("conv", "full_attention", "full_attention", "conv", "full_attention")],
    ids=["opens", "closes", "adjacent"])
def test_the_attention_layers_may_stand_anywhere(types):
    """No leading dense layer, attention first, last, and twice in a row:
    the whole sequence in one call against the reference."""
    mc = dataclasses.replace(TINY_LFM2_MOE, num_layers=len(types),
                             layer_types=types, first_k_dense_replace=0)
    model = get_model(mc)
    params = model.init_params(mc, jax.random.PRNGKey(1), jnp.float32)
    toks = jnp.asarray(prompt(64, 5))[None]
    hidden, k_new, _, _, stats = model.forward(
        params, mc, toks, jnp.arange(64)[None], jnp.array([64]))
    assert k_new.shape[0] == types.count("full_attention")
    assert int(stats[3]) == len(types)
    got = model.compute_logits(params, mc, hidden)[0]
    want = ref.forward(params, hf_config(mc), toks[0])
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4


# ---- what the served surface says ---------------------------------------------
async def test_the_served_surface_names_the_conv_path_and_the_counters():
    """``GET /debug/programs``: ``short_conv`` on every line (a decode
    program's step, a prefill's chunk), no other family's recurrence; ``GET
    /version`` the conv state's bytes; ``GET /metrics`` the six
    ``pstpu:moe_*`` series, counting."""
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from production_stack_tpu.server.api_server import APIServer

    eng = make_engine()
    mc = eng.model_config
    client = TestClient(TestServer(APIServer(eng).build_app()))
    await client.start_server()
    try:
        done = await asyncio.gather(*(client.post("/v1/completions", json={
            "model": mc.name, "prompt": prompt(12, 70 + i),
            "max_tokens": 9, "temperature": 0, "ignore_eos": True})
            for i in range(2)))
        assert [r.status for r in done] == [200] * 2
        text = await (await client.get("/metrics")).text()
        programs = (await (await client.get("/debug/programs")).json())[
            "programs"]
        version = await (await client.get("/version")).json()
    finally:
        await client.close()
    assert {p["program"] for p in programs} == {"decode", "prefill"}
    for p in programs:
        assert p["short_conv"] == "xla"
        assert "gdn_step" not in p and "ssd_step" not in p
        assert p["pool_copies"] == 0
    sample = {ln.split("{")[0]: float(ln.rsplit(" ", 1)[1])
              for ln in text.splitlines() if ln.startswith("pstpu:moe_")}
    assert set(sample) == {f"pstpu:moe_{k}_total" for k in (
        "assignments", "expert_load_max", "experts_touched", "layer_calls",
        "prefill_experts_touched", "prefill_layer_calls")}
    assert sample["pstpu:moe_layer_calls_total"] > 0
    assert sample["pstpu:moe_prefill_layer_calls_total"] > 0
    a_sequence = 18 * 2 * mc.hidden_size * 4          # float32 here
    assert version["engine"]["state_bytes"] == \
        eng.runner.state_pool_bytes == a_sequence * eng.runner.num_state_slots


@pytest.mark.parametrize("flags,named", [
    ({"speculative_num_tokens": 3, "speculative_model": "tiny-llama"},
     "speculative"),
    ({"kv_offload_cpu": True}, "offload"),
    ({"kv_cache_dtype": "int8"}, "int8"),
    ({"tensor_parallel_size": 2}, "parallelism"),
    ({"lora_modules": {"a": "/nonexistent"}}, "LoRA"),
])
def test_what_state_cannot_follow_is_refused_at_start(flags, named):
    with pytest.raises(ValueError, match="recurrent state") as err:
        make_engine(**flags)
    assert named.lower() in str(err.value).lower()
