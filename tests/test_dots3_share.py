"""The dots3-note family, a SHARE of the experts on the paged path (rank 1 of
4) against the reference given the same share; the shares of one sparse
layer summed against the uncut layer; the served surface; configuration,
refusals and the checkpoint's share. tests/test_dots3.py says what is
compared and why TOL.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.models import dots3_note, get_model
from production_stack_tpu.models.config import (
    TINY_DOTS3,
    TINY_DOTS3_EP4,
    ModelConfig,
    resolve_model_config,
)
from tests.dots3_helpers import (
    LENGTHS,
    ROOT,
    TOL,
    TOPK,
    W,
    add,
    drive,
    hf_config,
    make_engine,
    prompt,
    worst,
)


CUT = os.path.join(ROOT, "benchmarks", "chip", "configs",
                   "dots3-note-prev-ep16")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def paged():
    """``--attn-impl paged`` and a SHARE of the experts (rank 1 of 4): a
    full layer's decode step reads its index keys and selected rows from
    the pools."""
    eng = make_engine("tiny-dots3-ep4", attn_impl="paged")
    assert eng.runner.attn_impl == "paged"
    assert not eng.runner.prefill_packs      # the ring is a state a row
    return eng


@pytest.mark.parametrize("n", LENGTHS)
def test_paged_share_logprobs_match_the_reference(paged, n):
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


def test_the_shares_of_a_sparse_layer_add_up_to_the_uncut_layer():
    """One sparse layer's FFN on the same tokens by every rank of 4 (each
    given its four experts of the sixteen) and by a chip that holds all
    sixteen: the ranks' routed parts, and the shared expert counted ONCE,
    are the uncut layer; every pair is computed on exactly one rank."""
    cfg = TINY_DOTS3
    params = dots3_note.init_params(cfg, jax.random.PRNGKey(3), jnp.float32)
    sparse = params["layers"]["sparse"]
    lp = {k: x[2] for k, x in sparse.items()
          if k not in ("w_gate_up", "we_down")}
    hidden = jnp.asarray(np.random.default_rng(1).standard_normal(
        (2, 24, cfg.hidden_size)), jnp.float32)
    valid = jnp.ones((2, 24), bool)

    def ffn(c, experts):
        return dots3_note._sparse_ffn(c, hidden, lp, experts, 0, valid,
                                      False)

    whole, stats, chosen = ffn(cfg, (sparse["w_gate_up"][2],
                                     sparse["we_down"][2]))
    x = dots3_note.rms_norm(hidden, lp["ffn_norm"], cfg.rms_norm_eps)
    shared = dots3_note._gated_ffn(x, lp["ws_gate"], lp["ws_up"],
                                   lp["ws_down"])
    total, pairs, elsewhere = hidden + shared, 0, 0
    for rank in range(4):
        part = dataclasses.replace(TINY_DOTS3_EP4, ep_rank=rank)
        held = slice(4 * rank, 4 * rank + 4)
        out, st, picked = ffn(part, (sparse["w_gate_up"][2, held],
                                     sparse["we_down"][2, held]))
        np.testing.assert_array_equal(picked, chosen)
        total = total + (out - hidden - shared)
        pairs += int(st[0])
        elsewhere += int(st[4])
    np.testing.assert_allclose(total, whole, atol=2e-5)
    assert pairs == int(stats[0]) == 2 * 24 * cfg.num_experts_per_tok
    assert elsewhere == 3 * pairs and int(stats[4]) == 0


async def test_the_served_surface_names_the_ring_the_indexer_and_the_counters():
    """``GET /version`` and every line of ``GET /debug/programs`` say which
    layers keep a ring, the ring's shape, the indexer's top-k and the
    experts held; ``GET /metrics`` exports the four ``pstpu:index_*``
    counters beside the ring's two and the experts', and decode's move by
    the closed form of the request's prompt and answer; ``GET
    /debug/memory`` enters the ring's pool by name."""
    from aiohttp.test_utils import TestClient, TestServer

    from production_stack_tpu.server.api_server import APIServer

    eng = make_engine("tiny-dots3-ep4", num_kv_blocks=64)
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
    # sliding layers hold min(position + 1, 33) rows of position + 1, three
    # full layers select min(position + 1, 48) keys of position + 1.
    context = sum(range(n + 1, n + out))
    assert sample["pstpu:ring_keys_held_total"] == 3 * (out - 1) * W
    assert sample["pstpu:ring_keys_context_total"] == 3 * context
    assert sample["pstpu:index_keys_selected_total"] == 3 * (out - 1) * TOPK
    assert sample["pstpu:index_keys_visible_total"] == 3 * context
    assert sample["pstpu:index_prefill_keys_visible_total"] == \
        3 * n * (n + 1) // 2
    assert sample["pstpu:index_prefill_keys_selected_total"] == 3 * sum(
        min(p + 1, TOPK) for p in range(n))
    assert sample["pstpu:moe_assignments_elsewhere_total"] > \
        sample["pstpu:moe_assignments_total"] > 0
    assert {p["program"] for p in programs} == {"decode", "prefill"}
    for said in (*programs, version["engine"]):
        assert said["window_layers"] == [2, 3, 4]
        assert said["ring"] == {"ring_c": [1, W, 80]}
        assert said["index_topk"] == TOPK and said["index_key_lanes"] == 128
        assert said["experts_held"] == [4, 8]
        assert said["experts_routed"] == 16
    slots = eng.runner.num_state_slots
    # Stored in rows of whole 128-lane tiles (80 lanes in 128).
    assert memory["state_pools"] == {"ring_c": slots * 3 * W * 128 * 4}
    assert sum(memory["state_pools"].values()) == \
        memory["residents"]["state"]
    # Both pools: the latent rows and the index keys.
    assert eng.runner.kv_k.shape[-1] == 256
    assert eng.runner.kv_v.shape == (*eng.runner.kv_k.shape[:3], 128)
    assert eng.config.kv_cache_bytes_per_token(mc) == 3 * (256 + 128) * 4
    # A model without an indexer counts none of it.
    plain = make_engine("tiny-mimo-v2", max_model_len=256, num_kv_blocks=32)
    assert "index_keys_visible_total" not in plain.stats()


# ------------------------------------------------------ configs and refusals
def _published() -> dict:
    with open(CATALOG) as f:
        for line in f:
            row = json.loads(line)
            if row["name"] == "dots3-note-prev":
                return row["config"]
    raise AssertionError("no dots3-note-prev row in the catalog")


def test_the_published_row_and_the_cut_read_as_the_issue_says():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog on this machine")
    mc = ModelConfig.from_hf_config(_published())
    assert mc.arch == "dots3_note" and mc.num_layers == 46
    full = [i for i, t in enumerate(mc.layer_types)
            if t == "full_attention"]
    assert full == [0, 1, 5, 9, 13, 17, 21, 25, 29, 33, 37, 41, 45]
    assert dots3_note.sizes(mc, "full") == (128, 1024, 512, 128, 64, 128,
                                            8e7)
    assert dots3_note.sizes(mc, "window") == (64, 1024, 1024, 192, 64, 128,
                                              5e4)
    assert (mc.index_n_heads, mc.index_head_dim, mc.index_topk,
            mc.sliding_window) == (64, 128, 2048, 513)
    assert mc.first_k_dense_replace == 1 and mc.n_routed_experts == 256
    assert mc.ep_size == 1 and mc.mla_lora_rescale
    cut = resolve_model_config(CUT)
    assert cut.num_layers == 10 and cut.layer_types == mc.layer_types[:10]
    assert (cut.n_routed_experts, cut.ep_size, cut.ep_rank) == (16, 16, 0)
    assert cut.vocab_size == 19008 == 152064 // 8
    specs = get_model(cut).cache_specs(cut)
    assert specs.paged_kv == (4, 1, 640) and specs.latent == (512, 64, 128)
    assert specs.kv_pools == 1 and specs.second_pool_dim == 128
    assert [(s.name, s.layers, s.shape, s.stored) for s in specs.state] == [
        ("ring_c", 6, (1, 513, 1088), (1, 513, 1152))]
    # 6 KiB a token as laid out: 4 full layers x (640 + 128) lanes x 2 B.
    assert EngineConfig(model=CUT).kv_cache_bytes_per_token(cut) == 6144


def test_the_cut_changes_only_what_reduced_lists():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog on this machine")
    with open(os.path.join(CUT, "config.json")) as f:
        cut = json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = [c for c in json.load(f)["configs"]
                 if c["name"] == "dots3-note-prev-ep16"][0]
    published = _published()
    differs = sorted(k for k, v in published.items() if cut.get(k) != v)
    assert differs == sorted(entry["reduced"])
    assert cut["published"]["n_routed_experts"] == 256
    assert cut["n_routed_experts"] * cut["ep_size"] == 256
    with open(os.path.join(CUT, "deployment.json")) as f:
        deployment = json.load(f)
    assert sorted(deployment["reduced"]) == sorted(entry["reduced"])
    assert entry["source"] == deployment["source"]


REFUSED = {
    "attention_gate_type": {"attention_gate_type": "elementwise"},
    "swa_attention_gate_type": {"swa_attention_gate_type": None},
    "rope_scaling": {"rope_scaling": {"type": "yarn", "factor": 4.0}},
    "n_shared_experts": {"n_shared_experts": 2},
    "scoring_func": {"scoring_func": "softmax"},
    "topk_method": {"topk_method": "greedy"},
    "moe_layer_freq": {"moe_layer_freq": 2},
    "attention_bias": {"attention_bias": True},
    "n_group": {"n_group": 2},
    "hidden_act": {"hidden_act": "gelu"},
    "tie_word_embeddings": {"tie_word_embeddings": True},
    "sliding_window_size": {"sliding_window_size": 0},
    "index_topk": {"index_topk": 0},
    "q_lora_rank": {"q_lora_rank": None},
    "num_key_value_heads": {"num_key_value_heads": 2},
    "index_head_dim": {"index_head_dim": 8},
    "layer_types": {"layer_types": ["full_attention"] * 6},
    "first_k_dense_replace": {"first_k_dense_replace": 6},
    "vision_config": {"vision_config": {"depth": 2}},
    "audio_config": {"audio_config": {"layers": 2}},
    "num_nextn_predict_layers": {"num_nextn_predict_layers": 1},
    "ep_rank": {"ep_size": 4, "ep_rank": 4},
}


@pytest.mark.parametrize("key", sorted(REFUSED))
def test_what_the_module_does_not_implement_is_refused_by_key(key):
    good = hf_config(TINY_DOTS3)
    assert ModelConfig.from_hf_config(good) == dataclasses.replace(
        TINY_DOTS3, name="model")
    with pytest.raises(ValueError, match=key):
        ModelConfig.from_hf_config({**good, **REFUSED[key]})


@pytest.mark.parametrize("flag,over", [
    ("tensor", {"tensor_parallel_size": 2}),
    ("sequence", {"sequence_parallel_size": 2}),
    ("int8", {"kv_cache_dtype": "int8"}),
    ("speculative", {"speculative_num_tokens": 2,
                     "speculative_model": "tiny-dots3"}),
    ("LoRA", {"lora_modules": {"a": "/nowhere"}}),
    ("offload", {"kv_offload_cpu": True}),
    ("disaggregated", {"role": "prefill"}),
])
def test_what_a_ring_cannot_follow_is_refused_at_start(flag, over):
    cfg = EngineConfig(model="tiny-dots3", **over)
    with pytest.raises(ValueError, match=flag):
        cfg.refuse_what_state_cannot_follow(TINY_DOTS3)


# ------------------------------------------------------------------- loading
def test_a_checkpoint_loads_its_share_and_splits_kv_b(tmp_path):
    """A tiny checkpoint in the ASSUMED HF names (``kv_b_proj`` one matrix a
    layer of its kind's sizes, 16 experts, the whole vocabulary) loaded by
    rank 1 of 4: its four experts numbered from its first, ``kv_b_proj`` as
    its two halves per head, the router's 16 columns whole, the
    vocabulary's first rows."""
    safetensors = pytest.importorskip("safetensors.numpy")
    from production_stack_tpu.models.weights import load_hf_params

    whole = dataclasses.replace(TINY_DOTS3, vocab_size=640)
    params = jax.tree.map(np.asarray, dots3_note.init_params(
        whole, jax.random.PRNGKey(9), jnp.float32))
    layers, f = params["layers"], TINY_DOTS3.moe_intermediate_size
    tensors = {"model.embed_tokens.weight": params["embed"],
               "model.norm.weight": params["final_norm"],
               "lm_head.weight": params["lm_head"].T}
    theirs = {ours: (name, tr) for name, (ours, tr)
              in dots3_note.HF_LAYER_MAP.items()}
    for i, slot in enumerate(dots3_note.layer_slots(whole)):
        pre = f"model.layers.{i}."
        kind, at = slot["wq_a"]
        a = layers[kind]
        for leaf in dots3_note._LEAVES[kind]:
            if leaf == "w_kvb":
                # [rank, H * (nope + v)]: a head's nope columns, then its v.
                kvb = np.concatenate(
                    [a["w_uk"][at].transpose(2, 0, 1),
                     a["w_uv"][at].transpose(1, 0, 2)], axis=-1)
                x = kvb.reshape(kvb.shape[0], -1)
            else:
                x = a[leaf][at]
            name, tr = theirs[leaf]
            tensors[pre + name] = x.T if tr else x
        kind, at = slot["ffn_norm"]
        m = layers[kind]
        tensors[pre + "post_attention_layernorm.weight"] = m["ffn_norm"][at]
        if kind == "dense":
            for ours in ("w_gate", "w_up", "w_down"):
                tensors[pre + theirs[ours][0]] = m[ours][at].T
            continue
        tensors[pre + "mlp.gate.weight"] = m["w_router"][at].T
        tensors[pre + "mlp.gate.e_score_correction_bias"] = \
            m["router_bias"][at]
        for ours in ("ws_gate", "ws_up", "ws_down"):
            tensors[pre + theirs[ours][0]] = m[ours][at].T
        for e in range(16):
            x = pre + f"mlp.experts.{e}."
            tensors[x + "gate_proj.weight"] = m["w_gate_up"][at, e, :, :f].T
            tensors[x + "up_proj.weight"] = m["w_gate_up"][at, e, :, f:].T
            tensors[x + "down_proj.weight"] = m["we_down"][at, e].T
    safetensors.save_file(
        {k: np.ascontiguousarray(v) for k, v in tensors.items()},
        str(tmp_path / "model.safetensors"))
    got = load_hf_params(TINY_DOTS3_EP4, str(tmp_path), jnp.float32)
    for kind in ("full", "window"):
        assert set(got["layers"][kind]) == set(layers[kind])
        for leaf, want in layers[kind].items():
            np.testing.assert_array_equal(got["layers"][kind][leaf], want)
    sparse = got["layers"]["sparse"]
    np.testing.assert_array_equal(sparse["w_gate_up"],
                                  layers["sparse"]["w_gate_up"][:, 4:8])
    np.testing.assert_array_equal(sparse["we_down"],
                                  layers["sparse"]["we_down"][:, 4:8])
    np.testing.assert_array_equal(sparse["w_router"],
                                  layers["sparse"]["w_router"])
    np.testing.assert_array_equal(sparse["ws_down"],
                                  layers["sparse"]["ws_down"])
    assert sparse["w_router"].dtype == jnp.float32
    np.testing.assert_array_equal(got["embed"], params["embed"][:512])
    np.testing.assert_array_equal(got["lm_head"], params["lm_head"][:, :512])
