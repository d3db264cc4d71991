"""The MiMo-V2 family, a SHARE of the experts through the paged kernels (rank 1
of 4, interpret mode) against the reference given the same share; the served
surface; configuration, refusals and the checkpoint's share.
tests/test_mimo_v2.py says what is compared and why TOL.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.models import get_model, mimo_v2
from production_stack_tpu.models.config import (
    TINY_MIMO_V2,
    TINY_MIMO_V2_EP4,
    ModelConfig,
    resolve_model_config,
)
from tests.mimo_v2_helpers import (
    LENGTHS,
    ROOT,
    TOL,
    W,
    add,
    drive,
    hf_config,
    make_engine,
    prompt,
    worst,
)


CUT = os.path.join(ROOT, "benchmarks", "chip", "configs", "mimo-v2.5-ep16")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def paged():
    """``--attn-impl paged`` (rows of 128 lanes for keys of 48 and values
    of 32): the full layers through the Pallas kernels in interpret mode
    over the pool, and a SHARE of the experts (rank 1 of 4)."""
    eng = make_engine("tiny-mimo-v2-ep4", attn_impl="paged")
    assert eng.runner.attn_impl == "paged" and eng.runner.prefill_reads_pool
    assert not eng.runner.prefill_packs      # the ring is a state a row
    return eng


@pytest.mark.parametrize("n", LENGTHS)
def test_paged_share_logprobs_match_the_reference(paged, n):
    """The same through the pool, the Pallas kernels (interpret) and rank
    1 of 4's experts, the reference given the same share."""
    seq = add(paged, f"p{n}", prompt(n, 100 + n), 10)
    drive(paged)
    assert worst(paged, seq) < TOL
    assert paged.runner.fwd_stats_total["prefill"][
        "assignments_elsewhere"] > 0


def test_the_tolerance_tells_a_misplaced_share(paged):
    seq = add(paged, "share", prompt(150, 5), 6)
    drive(paged)
    assert worst(paged, seq) < TOL
    assert worst(paged, seq, ("all_experts_here",)) > 10 * TOL


async def test_the_served_surface_names_the_ring_and_the_counters():
    """``GET /version`` and every line of ``GET /debug/programs`` say which
    layers keep a ring, the ring's shape and the experts held; ``GET
    /metrics`` exports the two ``pstpu:ring_keys_*`` counters and the pairs
    routed elsewhere beside the six ``pstpu:moe_*`` series, and the ring's
    two move by the closed form of the request's prompt and answer;
    ``GET /debug/memory`` enters the ring's pools by name."""
    from aiohttp.test_utils import TestClient, TestServer

    from production_stack_tpu.server.api_server import APIServer

    eng = make_engine("tiny-mimo-v2-ep4", max_model_len=512,
                      num_kv_blocks=64)
    mc = eng.model_config
    client = TestClient(TestServer(APIServer(eng).build_app()))
    await client.start_server()
    n, out = 150, 9
    try:
        done = await client.post("/v1/completions", json={
            "model": mc.name, "prompt": prompt(n, 90), "max_tokens": out,
            "temperature": 0, "ignore_eos": True})
        assert done.status == 200
        text = await (await client.get("/metrics")).text()
        programs = (await (await client.get("/debug/programs")).json())[
            "programs"]
        version = await (await client.get("/version")).json()
        memory = await (await client.get("/debug/memory")).json()
    finally:
        await client.close()
    sample = {ln.split("{")[0]: float(ln.rsplit(" ", 1)[1])
              for ln in text.splitlines() if ln.startswith("pstpu:")}
    # The out - 1 decode queries sit at positions n .. n + out - 2: three
    # window layers hold min(position + 1, 128) keys of position + 1.
    assert sample["pstpu:ring_keys_held_total"] == 3 * (out - 1) * W
    assert sample["pstpu:ring_keys_context_total"] == 3 * sum(
        range(n + 1, n + out))
    assert sample["pstpu:moe_assignments_elsewhere_total"] > \
        sample["pstpu:moe_assignments_total"] > 0
    assert sample["pstpu:moe_layer_calls_total"] > 0
    assert {p["program"] for p in programs} == {"decode", "prefill"}
    # The tiny preset's 2 queries a KV head, on a CPU: the ``jnp`` step.
    assert {p["program"]: p.get("ring_step") for p in programs} == {
        "decode": "xla", "prefill": None}
    for said in (*programs, version["engine"]):
        assert said["window_layers"] == [1, 2, 4]
        assert said["ring"] == {"ring_k": [2, W, 48], "ring_v": [2, W, 32]}
        assert said["experts_held"] == [4, 8]
        assert said["experts_routed"] == 16
    slots = eng.runner.num_state_slots
    # Stored in rows of whole 128-lane tiles (keys of 48 lanes, values of
    # 32): what the arrays hold.
    assert memory["state_pools"] == {
        "ring_k": slots * 3 * 2 * W * 128 * 4,
        "ring_v": slots * 3 * 2 * W * 128 * 4}
    assert sum(memory["state_pools"].values()) == \
        memory["residents"]["state"]
    # A model without a ring or a share says and counts none of it.
    plain = make_engine("tiny-llama", max_model_len=256, num_kv_blocks=32)
    assert plain.runner.ring_report() == {} and plain.runner.ring_layers == 0
    assert plain.stats()["ring_keys_held_total"] == 0


# ------------------------------------------------------ configs and refusals
def _published() -> dict:
    with open(CATALOG) as f:
        for line in f:
            row = json.loads(line)
            if row["name"] == "MiMo-V2.5":
                return row["config"]
    raise AssertionError("no MiMo-V2.5 row in the catalog")


def test_the_published_row_and_the_cut_read_as_the_issue_says():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog on this machine")
    mc = ModelConfig.from_hf_config(_published())
    assert mc.arch == "mimo_v2" and mc.num_layers == 48
    full = [i for i, t in enumerate(mc.layer_types)
            if t == "full_attention"]
    assert full == [0, 5, 11, 17, 23, 29, 35, 41, 47]
    assert (mc.num_kv_heads, mc.swa_num_kv_heads, mc.head_dim,
            mc.v_head_dim, mc.rotary_dim) == (4, 8, 192, 128, 64)
    assert (mc.rope_theta, mc.swa_rope_theta) == (1e7, 1e4)
    assert mc.first_k_dense_replace == 1 and mc.n_routed_experts == 256
    assert mc.ep_size == 1 and mc.routed_scaling_factor == 1.0
    cut = resolve_model_config(CUT)
    assert cut.num_layers == 12 and cut.layer_types == mc.layer_types[:12]
    assert (cut.n_routed_experts, cut.ep_size, cut.ep_rank) == (16, 16, 0)
    assert cut.vocab_size == 19072 == 152576 // 8
    specs = get_model(cut).cache_specs(cut)
    assert specs.paged_kv == (3, 4, 256)
    assert [(s.name, s.layers, s.shape) for s in specs.state] == [
        ("ring_k", 9, (8, 128, 192)), ("ring_v", 9, (8, 128, 128))]
    assert [s.stored for s in specs.state] == [(8, 128, 256), (8, 128, 128)]


def test_the_cut_changes_only_what_reduced_lists():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog on this machine")
    with open(os.path.join(CUT, "config.json")) as f:
        cut = json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = [c for c in json.load(f)["configs"]
                 if c["name"] == "mimo-v2.5-ep16"][0]
    published = _published()
    differs = sorted(k for k, v in published.items() if cut.get(k) != v)
    assert differs == sorted(entry["reduced"])
    assert cut["published"]["n_routed_experts"] == 256
    assert cut["n_routed_experts"] * cut["ep_size"] == 256


REFUSED = {
    "add_full_attention_sink_bias": {"add_full_attention_sink_bias": True},
    "n_group": {"n_group": 2},
    "topk_group": {"topk_group": 2},
    "n_shared_experts": {"n_shared_experts": 1},
    "attention_chunk_size": {"attention_chunk_size": 64},
    "sliding_window_size": {"sliding_window_size": 256},
    "vision_config": {"vision_config": {"depth": 2}},
    "audio_config": {"audio_config": {"layers": 2}},
    "num_nextn_predict_layers": {"num_nextn_predict_layers": 3},
    "rope_scaling": {"rope_scaling": {"type": "yarn", "factor": 4.0}},
    "scoring_func": {"scoring_func": "softmax"},
    "topk_method": {"topk_method": "greedy"},
    "attention_bias": {"attention_bias": True},
    "hybrid_block_size": {"hybrid_block_size": 4},
    "swa_head_dim": {"swa_head_dim": 64},
    "moe_layer_freq": {"moe_layer_freq": [0, 1, 0, 1, 1, 1]},
    "hybrid_layer_pattern": {"hybrid_layer_pattern": [1] * 6},
    "ep_rank": {"ep_size": 4, "ep_rank": 4},
}


@pytest.mark.parametrize("key", sorted(REFUSED))
def test_what_the_module_does_not_implement_is_refused_by_key(key):
    good = dict(hf_config(TINY_MIMO_V2), rope_scaling={"type": "default"},
                attention_chunk_size=W, n_group=1, topk_group=1)
    ModelConfig.from_hf_config(good)
    with pytest.raises(ValueError, match=key):
        ModelConfig.from_hf_config({**good, **REFUSED[key]})


@pytest.mark.parametrize("flag,over", [
    ("tensor", {"tensor_parallel_size": 2}),
    ("sequence", {"sequence_parallel_size": 2}),
    ("int8", {"kv_cache_dtype": "int8"}),
    ("speculative", {"speculative_num_tokens": 2,
                     "speculative_model": "tiny-mimo-v2"}),
    ("LoRA", {"lora_modules": {"a": "/nowhere"}}),
    ("offload", {"kv_offload_cpu": True}),
    ("disaggregated", {"role": "prefill"}),
])
def test_what_a_ring_cannot_follow_is_refused_at_start(flag, over):
    cfg = EngineConfig(model="tiny-mimo-v2", **over)
    with pytest.raises(ValueError, match=flag):
        cfg.refuse_what_state_cannot_follow(TINY_MIMO_V2)


# ------------------------------------------------------------------- loading
def test_a_checkpoint_loads_its_share_and_splits_the_fused_projection(
        tmp_path):
    """A tiny checkpoint in the ASSUMED HF names (fused q|k|v rows, 16
    experts, the whole vocabulary) loaded by rank 1 of 4: its four experts
    numbered from its first, the three projections apart, the router's 16
    columns whole, the vocabulary's first rows."""
    safetensors = pytest.importorskip("safetensors.numpy")
    from production_stack_tpu.models.weights import load_hf_params

    whole = dataclasses.replace(TINY_MIMO_V2, vocab_size=640)
    params = jax.tree.map(np.asarray, mimo_v2.init_params(
        whole, jax.random.PRNGKey(9), jnp.float32))
    layers, f = params["layers"], TINY_MIMO_V2.moe_intermediate_size
    tensors = {"model.embed_tokens.weight": params["embed"],
               "model.norm.weight": params["final_norm"],
               "lm_head.weight": params["lm_head"].T}
    for i, slot in enumerate(mimo_v2.layer_slots(whole)):
        pre = f"model.layers.{i}."
        kind, at = slot["wq"]
        a = layers[kind]
        tensors[pre + "input_layernorm.weight"] = a["attn_norm"][at]
        tensors[pre + "self_attn.qkv_proj.weight"] = np.concatenate(
            [a["wq"][at].T, a["wk"][at].T, a["wv"][at].T])
        tensors[pre + "self_attn.o_proj.weight"] = a["wo"][at].T
        if kind == "window":
            tensors[pre + "self_attn.attention_sink_bias"] = a["sink"][at]
        kind, at = slot["ffn_norm"]
        m = layers[kind]
        tensors[pre + "post_attention_layernorm.weight"] = m["ffn_norm"][at]
        if kind == "dense":
            for ours, theirs in (("w_gate", "gate_proj"), ("w_up", "up_proj"),
                                 ("w_down", "down_proj")):
                tensors[pre + f"mlp.{theirs}.weight"] = m[ours][at].T
            continue
        tensors[pre + "mlp.gate.weight"] = m["w_router"][at].T
        tensors[pre + "mlp.gate.e_score_correction_bias"] = \
            m["router_bias"][at]
        for e in range(16):
            x = pre + f"mlp.experts.{e}."
            tensors[x + "gate_proj.weight"] = m["w_gate_up"][at, e, :, :f].T
            tensors[x + "up_proj.weight"] = m["w_gate_up"][at, e, :, f:].T
            tensors[x + "down_proj.weight"] = m["we_down"][at, e].T
    safetensors.save_file(
        {k: np.ascontiguousarray(v) for k, v in tensors.items()},
        str(tmp_path / "model.safetensors"))
    got = load_hf_params(TINY_MIMO_V2_EP4, str(tmp_path), jnp.float32)
    for kind in ("full", "window"):
        for leaf in ("wq", "wk", "wv", "wo", "attn_norm"):
            np.testing.assert_array_equal(got["layers"][kind][leaf],
                                          layers[kind][leaf])
    np.testing.assert_array_equal(got["layers"]["window"]["sink"],
                                  layers["window"]["sink"])
    sparse = got["layers"]["sparse"]
    np.testing.assert_array_equal(sparse["w_gate_up"],
                                  layers["sparse"]["w_gate_up"][:, 4:8])
    np.testing.assert_array_equal(sparse["we_down"],
                                  layers["sparse"]["we_down"][:, 4:8])
    np.testing.assert_array_equal(sparse["w_router"],
                                  layers["sparse"]["w_router"])
    assert sparse["w_router"].dtype == jnp.float32
    np.testing.assert_array_equal(got["embed"], params["embed"][:512])
    np.testing.assert_array_equal(got["lm_head"], params["lm_head"][:, :512])
