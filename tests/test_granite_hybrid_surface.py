"""The Granite 4.0 hybrid family's configuration, layer periods, weights, KV
head pairing, refusals and served surface. tests/test_granite_hybrid.py holds
the forward to the reference.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.models import config as model_configs
from production_stack_tpu.models import get_model, granite_hybrid
from production_stack_tpu.models.config import (
    PERIOD_RULES,
    TINY_GRANITE_HYBRID,
    TINY_OLMO_HYBRID,
    ModelConfig,
    layer_period,
)
from tests.granite_hybrid_helpers import (
    ROOT,
    hf_config,
    make_engine,
    prompt,
    ref,
)


PUBLISHED = os.path.join(ROOT, "benchmarks", "chip", "configs",
                         "granite-4.0-h-micro", "config.json")


# ---- config.json: what is read, what is refused ------------------------------
def published() -> dict:
    with open(PUBLISHED) as f:
        return json.load(f)


def test_from_hf_config_reads_the_published_config():
    mc = ModelConfig.from_hf_config(published(), name="granite")
    assert (mc.arch, mc.num_layers, mc.hidden_size, mc.intermediate_size) \
        == ("granite_hybrid", 40, 2048, 8192)
    assert (mc.num_heads, mc.num_kv_heads, mc.head_dim_) == (32, 8, 64)
    assert (mc.mamba_n_heads, mc.mamba_d_head, mc.mamba_d_state,
            mc.mamba_d_conv, mc.mamba_conv_bias, mc.mamba_chunk_size) == \
        (64, 64, 128, 4, True, 256)
    assert (mc.embedding_multiplier, mc.attention_multiplier,
            mc.residual_multiplier, mc.logits_scaling) == \
        (12.0, 0.015625, 0.22, 8.0)
    assert mc.rope_theta is None and mc.tie_word_embeddings
    assert (mc.vocab_size, mc.max_position_embeddings) == (100352, 131072)
    assert [i for i, t in enumerate(mc.layer_types) if t == "attention"] \
        == [5, 15, 25, 35]
    assert granite_hybrid.segments(mc) == (5, 9, 4)
    specs = granite_hybrid.cache_specs(mc)
    # 8 KV heads of 64 lanes as 4 rows of 128: the same 8 KiB a token.
    assert granite_hybrid.kv_pack(mc) == 2
    assert specs.paged_kv == (4, 4, 128)
    assert [(s.name, s.layers, s.shape, s.dtype) for s in specs.state] == [
        ("ssm", 36, (64, 64, 128), "float32"),
        ("conv", 36, (3 * 4352 // 128, 128), None)]


@pytest.mark.parametrize("change,named", [
    ({"num_local_experts": 64}, "num_local_experts"),
    ({"position_embedding_type": "rope"}, "position_embedding_type"),
    ({"mamba_n_groups": 2}, "mamba_n_groups"),
    ({"mamba_proj_bias": True}, "mamba_proj_bias"),
    ({"attention_bias": True}, "attention_bias"),
    ({"hidden_act": "gelu"}, "hidden_act"),
    ({"mamba_expand": 4}, "mamba_expand"),
])
def test_an_unsupported_sibling_is_refused_by_its_key(change, named):
    with pytest.raises(ValueError, match="granitemoehybrid: not supported") \
            as err:
        ModelConfig.from_hf_config({**published(), **change})
    assert named in str(err.value)


def test_the_served_tree_has_the_published_parameter_count():
    """By hand (ISSUE 40's arithmetic) and from the tree ``init_params``
    makes, as shapes: nothing is allocated."""
    mamba = 2048 * 8512 + 4096 * 2048 + 4352 * 4 + 4352 + 4096 + 3 * 64 \
        + 2048 * 16384 + 8192 * 2048 + 2 * 2048
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512 \
        + 2048 * 16384 + 8192 * 2048 + 2 * 2048
    assert (mamba, attention) == (76_182_976, 60_821_504)
    by_hand = 36 * mamba + 4 * attention + 100352 * 2048 + 2048
    assert by_hand == 3_191_396_096
    mc = ModelConfig.from_hf_config(published())
    tree = jax.eval_shape(
        lambda: granite_hybrid.init_params(mc, jax.random.PRNGKey(0)))
    assert sum(x.size for x in jax.tree.leaves(tree)) == by_hand
    assert set(tree["layers"]["mamba"]) | {"in_proj"} == \
        granite_hybrid.required_layer_leaves(mc)["mamba"] \
        | {"in_zx", "in_dt"}
    assert {k: v.dtype for k, v in tree["layers"]["mamba"].items()
            if v.dtype == jnp.float32}.keys() == \
        set(granite_hybrid.FLOAT32_LEAVES)


M, A = PERIOD_RULES["granite_hybrid"]["kinds"]
LIN, FULL = "linear_attention", "full_attention"


@pytest.mark.parametrize("types,rules,period", [
    ((LIN, LIN, LIN, FULL) * 2, {}, (LIN, LIN, LIN, FULL)),
    (TINY_OLMO_HYBRID.layer_types, {}, (LIN, LIN, LIN, FULL)),
    ((M, M, A, M) * 2, PERIOD_RULES["granite_hybrid"], (M, M, A, M)),
    ((M,) * 5 + (A,) + (M,) * 4, PERIOD_RULES["granite_hybrid"],
     (M,) * 5 + (A,) + (M,) * 4),
    ((M, M, A) * 3, PERIOD_RULES["granite_hybrid"], (M, M, A)),
    ((A, M) * 2, PERIOD_RULES["granite_hybrid"], (A, M)),
])
def test_layer_period_reads_both_kinds_of_list(types, rules, period):
    assert layer_period(types, len(types), **rules) == period


@pytest.mark.parametrize("types,rules,why", [
    # olmo's rule is as it was: the full layer closes the period.
    ((LIN, FULL, LIN, LIN, FULL, LIN), {}, "whole number of equal periods"),
    ((FULL, LIN, LIN) * 2, {}, "closed by one full_attention"),
    ((M, M, A, M, A, M, M, M), PERIOD_RULES["granite_hybrid"],
     "whole number of equal periods"),
    ((M, M, A, M, M, M, A), PERIOD_RULES["granite_hybrid"],
     "whole number of equal periods"),
    ((M,) * 4, PERIOD_RULES["granite_hybrid"], "around one attention"),
    ((A,) * 4, PERIOD_RULES["granite_hybrid"], "whole number"),
    ((M, LIN, A), PERIOD_RULES["granite_hybrid"], "unknown kinds"),
])
def test_layer_period_refuses_what_is_not_whole_equal_periods(types, rules,
                                                              why):
    with pytest.raises(ValueError, match=why):
        layer_period(types, len(types), **rules)


def test_a_models_kinds_are_its_own():
    with pytest.raises(ValueError, match="unknown kinds"):
        dataclasses.replace(TINY_GRANITE_HYBRID,
                            layer_types=TINY_OLMO_HYBRID.layer_types)
    with pytest.raises(ValueError, match="unknown kinds"):
        dataclasses.replace(TINY_OLMO_HYBRID,
                            layer_types=TINY_GRANITE_HYBRID.layer_types)


@pytest.mark.parametrize("types", [
    ("mamba", "mamba", "attention"), ("attention", "mamba", "mamba")])
def test_the_attention_layer_may_close_or_open_its_period(monkeypatch, types):
    """The forward's segments where the tail (or the head) segment is
    empty: the whole sequence in one call against the reference."""
    mc = dataclasses.replace(TINY_GRANITE_HYBRID, num_layers=6,
                             layer_types=types * 2)
    model = get_model(mc)
    params = model.init_params(mc, jax.random.PRNGKey(1), jnp.float32)
    toks = jnp.asarray(prompt(64, 5))[None]
    hidden, k_new, _, _ = model.forward(
        params, mc, toks, jnp.arange(64)[None], jnp.array([64]))
    assert k_new.shape[0] == 2
    got = model.compute_logits(params, mc, hidden)[0]
    want = ref.forward(params, hf_config(mc), toks[0])
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4


def test_a_checkpoint_in_hf_layout_loads_into_the_stacks_by_kind(tmp_path):
    """``init_params``' tree written out under HF's names and layouts
    ([out, in] matrices, a [C, 1, W] conv, one tensor a layer, no
    ``lm_head``) and read back by models/weights.py: the same tree, the
    scan's three leaves in float32."""
    pytest.importorskip("safetensors")
    from safetensors.numpy import save_file

    from production_stack_tpu.models.weights import load_hf_params

    mc = TINY_GRANITE_HYBRID
    params = granite_hybrid.init_params(mc, jax.random.PRNGKey(3),
                                        jnp.float32)
    ours_to_hf = {v[0]: (k, v[1])
                  for k, v in granite_hybrid.HF_LAYER_MAP.items()}
    tensors = {"model.embed_tokens.weight": np.asarray(params["embed"]),
               "model.norm.weight": np.asarray(params["final_norm"])}
    for i, (kind, at) in enumerate(granite_hybrid.layer_slots(mc)):
        stacks = dict(params["layers"][kind])
        if kind == "mamba":      # the checkpoint's one in_proj: z | xBC | dt
            stacks["in_proj"] = jnp.concatenate(
                [stacks.pop("in_zx"), stacks.pop("in_dt")], axis=-1)
        for leaf, stack in stacks.items():
            name, transpose = ours_to_hf[leaf]
            x = np.asarray(stack[at])
            if leaf == "conv_w":
                x = x[:, None, :]                       # [W, 1, C]
            tensors[f"model.layers.{i}.{name}"] = np.ascontiguousarray(
                x.T if transpose else x)
    save_file(tensors, str(tmp_path / "model.safetensors"))
    loaded = load_hf_params(mc, str(tmp_path), jnp.float32)
    assert "lm_head" not in loaded
    flat_want = jax.tree_util.tree_leaves_with_path(params)
    flat_got = dict(jax.tree_util.tree_leaves_with_path(loaded))
    assert len(flat_want) == len(flat_got)
    for path, want in flat_want:
        np.testing.assert_array_equal(flat_got[path], want, str(path))
        assert flat_got[path].dtype == jnp.float32


# ---- what the served surface says ---------------------------------------------
@pytest.mark.parametrize("d_state,path", [(32, "xla"), (128, "pallas")])
async def test_the_served_surface_says_what_the_step_and_the_prefill_hold(
        monkeypatch, d_state, path):
    """``GET /debug/programs``: ``ssd_step`` names the execution a decode
    program holds (the runner's Pallas interpret switch reaches the step
    kernel where the state is whole lanes wide), a prefill line says
    whether the pool is read in place; ``GET /version`` the state's bytes a
    sequence."""
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from production_stack_tpu.server.api_server import APIServer

    mc = dataclasses.replace(TINY_GRANITE_HYBRID, mamba_d_state=d_state,
                             name=f"tiny-granite-says-{path}")
    monkeypatch.setitem(model_configs.NAMED_CONFIGS, mc.name, mc)
    eng = make_engine(mc.name, attn_impl="paged")
    client = TestClient(TestServer(APIServer(eng).build_app()))
    await client.start_server()
    try:
        done = await asyncio.gather(*(client.post("/v1/completions", json={
            "model": mc.name, "prompt": prompt(12, 70 + i),
            "max_tokens": 9, "temperature": 0, "ignore_eos": True})
            for i in range(2)))
        assert [r.status for r in done] == [200] * 2
        programs = (await (await client.get("/debug/programs")).json())[
            "programs"]
        version = await (await client.get("/version")).json()
    finally:
        await client.close()
    assert {p["program"] for p in programs} == {"decode", "prefill"}
    for p in programs:
        assert "gdn_step" not in p
        assert p.get("ssd_step") == \
            (path if p["program"] == "decode" else None)
        if p["program"] == "prefill":
            assert p["prefill_reads_pool"] is eng.runner.prefill_reads_pool
    specs = granite_hybrid.cache_specs(mc)
    a_sequence = sum(
        s.layers * int(np.prod(s.shape)) * (4 if s.dtype else 4)
        for s in specs.state)
    assert version["engine"]["state_bytes"] == \
        eng.runner.state_pool_bytes == a_sequence * eng.runner.num_state_slots


def test_kv_heads_pair_only_where_they_make_whole_lanes():
    mc = TINY_GRANITE_HYBRID
    assert granite_hybrid.kv_pack(mc) == 2
    assert granite_hybrid.cache_specs(mc).paged_kv == (2, 1, 128)
    for change, pack, kv in (
            ({"head_dim": 128}, 1, (2, 2, 128)),
            ({"head_dim": 32, "num_kv_heads": 4}, 4, (2, 1, 128)),
            # Three KV heads do not pair: the narrow rows stay.
            ({"num_heads": 3, "num_kv_heads": 3}, 1, (2, 3, 64))):
        other = dataclasses.replace(mc, **change)
        assert granite_hybrid.kv_pack(other) == pack
        assert granite_hybrid.cache_specs(other).paged_kv == kv


@pytest.mark.parametrize("change", [
    {"num_heads": 3, "num_kv_heads": 3}, {"head_dim": 128},
    {"head_dim": 32, "num_heads": 8, "num_kv_heads": 4}],
    ids=["unpaired-64", "whole-128", "four-of-32"])
def test_the_forward_is_the_reference_whatever_the_pairing(change):
    mc = dataclasses.replace(TINY_GRANITE_HYBRID, **change)
    model = get_model(mc)
    params = model.init_params(mc, jax.random.PRNGKey(2), jnp.float32)
    toks = jnp.asarray(prompt(64, 6))[None]
    hidden, _, _, _ = model.forward(
        params, mc, toks, jnp.arange(64)[None], jnp.array([64]))
    got = model.compute_logits(params, mc, hidden)[0]
    want = ref.forward(params, hf_config(mc), toks[0])
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4


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
