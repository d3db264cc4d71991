"""The DeepSeek-V3 family against the reference through engines a case builds
for itself (five rows of unequal length in one prefill, a packed prefill row
against the rectangle), the counters, refusals, and the served surface.
tests/test_deepseek_v3.py says what is compared and why TOL.
"""

import os
import sys

import jax.numpy as jnp
import pytest

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.engine import ServingEngine
from production_stack_tpu.models import deepseek_v3 as ds
from production_stack_tpu.models.config import TINY_DEEPSEEK_V3, ModelConfig
from tests.deepseek_v3_helpers import (
    ROOT,
    TOL,
    add,
    drive,
    make_engine,
    packed_row_against_rectangle,
    prompt,
    worst,
)


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


def test_g_a_packed_prefill_row_serves_what_the_rectangle_serves(monkeypatch):
    import sys

    packed_row_against_rectangle(
        monkeypatch, TINY_DEEPSEEK_V3, sys.modules[__name__])


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
