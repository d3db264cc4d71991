"""The LFM2-MoE family against the reference through engines a case builds for
itself: rows of unequal length in both forms of a prefill dispatch and on
both attention paths, decode through the state slots and the pool, the
counters of a model with state, attention layers standing anywhere.
tests/test_lfm2_moe.py says what is compared and why TOL.
"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from production_stack_tpu.models import config as model_configs
from production_stack_tpu.models import get_model
from production_stack_tpu.models.config import TINY_LFM2_MOE
from production_stack_tpu.ops import moe
from tests.lfm2_moe_helpers import (
    TINY_CUT,
    TOL,
    add,
    drive,
    hf_config,
    make_engine,
    prompt,
    ref,
    worst,
)


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
