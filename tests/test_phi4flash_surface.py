"""The Phi-4-mini-flash family's configuration, refusals, weights and served
surface. tests/test_phi4flash.py holds the forward to the reference.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.models import get_model, phi4flash
from production_stack_tpu.models.config import (
    TINY_PHI4FLASH,
    ModelConfig,
    resolve_model_config,
)
from tests.phi4flash_helpers import (
    CONFIG_DIR,
    F32,
    W,
    hf_config,
    make_engine,
    prompt,
)


CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


# ----------------------------------------------------- refused by key, start
BASE = {"model_type": "phi4flash", "hidden_size": 512,
        "num_attention_heads": 8, "num_key_value_heads": 4,
        "num_hidden_layers": 8, "intermediate_size": 256, "vocab_size": 512,
        "sliding_window": 64, "mb_per_layer": 2,
        "tie_word_embeddings": True}


def test_the_base_config_is_served():
    mc = ModelConfig.from_hf_config(BASE, "base")
    assert mc.arch == "phi4flash" and mc.mamba_d_inner == 1024
    assert (mc.mamba_d_state, mc.mamba_d_conv, mc.mamba_dt_rank) == \
        (16, 4, 32)
    assert mc.sliding_window == 64 and mc.rope_theta is None


@pytest.mark.parametrize("key,value,said", [
    ("mb_per_layer", 1, "mb_per_layer"),
    ("mb_per_layer", 4, "mb_per_layer"),
    ("num_hidden_layers", 10, "num_hidden_layers"),
    ("num_hidden_layers", 4, "num_hidden_layers"),
    ("sliding_window", [64] * 8, "sliding_window"),
    ("sliding_window", 72, "sliding_window"),
    ("sliding_window", None, "sliding_window"),
    ("rope_scaling", {"type": "longrope"}, "rope_scaling"),
    ("rope_theta", 10000.0, "rope_theta"),
    ("partial_rotary_factor", 0.5, "partial_rotary_factor"),
    ("mamba_d_state", 12, "mamba_d_state"),
    ("mamba_d_state", 128, "mamba_d_state"),
    ("mamba_expand", 0.3, "mamba_expand"),
    ("mamba_d_conv", 1, "mamba_d_conv"),
    ("mamba_dt_rank", 0, "mamba_dt_rank"),
    ("mamba_proj_bias", True, "mamba_proj_bias"),
    ("num_key_value_heads", 3, "num_key_value_heads"),
    ("tie_word_embeddings", False, "tie_word_embeddings"),
    ("mlp_bias", True, "mlp_bias"),
    ("lm_head_bias", True, "lm_head_bias"),
    ("hidden_act", "gelu", "hidden_act"),
])
def test_what_the_module_does_not_implement_is_refused_by_its_key(
        key, value, said):
    with pytest.raises(ValueError, match=said):
        ModelConfig.from_hf_config({**BASE, key: value}, "refused")


@pytest.mark.parametrize("flag,said", [
    (dict(tensor_parallel_size=2), "tensor"),
    (dict(kv_cache_dtype="int8"), "int8"),
    (dict(sequence_parallel_size=2), "sequence"),
    (dict(speculative_num_tokens=3, speculative_model="tiny-llama"),
     "specul"),
])
def test_what_no_state_can_follow_is_refused_at_start(flag, said):
    with pytest.raises(ValueError, match=f"(?i){said}"):
        make_engine(**flag)


def test_lora_is_refused_beside_this_model(tmp_path):
    with pytest.raises(ValueError, match="(?i)lora"):
        make_engine(lora_modules={"a": str(tmp_path)})


# ------------------------------------------------ the benchmark's config
def test_config_json_holds_the_catalogs_numbers():
    """Every key of the catalog's row under its name and with its value;
    what the file adds is Mamba-1's defaults (``assumed``)."""
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Phi-4-mini-flash-reasoning")
    with open(os.path.join(CONFIG_DIR, "config.json")) as f:
        mine = json.load(f)
    assert {k: mine[k] for k in row["config"]} == row["config"]
    assert set(mine) - set(row["config"]) == {
        "mamba_d_state", "mamba_d_conv", "mamba_expand", "mamba_dt_rank",
        "mamba_conv_bias", "mamba_proj_bias"}
    with open(os.path.join(CONFIG_DIR, "deployment.json")) as f:
        deployment = json.load(f)
    assert deployment["source"] == row["source_url"]
    assert deployment["reduced"] == {} and deployment["depth"] == 32
    for key in ("mamba_d_state", "mamba_d_conv", "mamba_expand",
                "mamba_dt_rank"):
        assert key in deployment["assumed"]


def test_the_published_config_resolves_to_the_published_sizes():
    mc = resolve_model_config(CONFIG_DIR)
    assert (mc.arch, mc.num_layers, mc.hidden_size, mc.vocab_size) == \
        ("phi4flash", 32, 2560, 200064)
    assert (mc.num_heads, mc.num_kv_heads, mc.head_dim_) == (40, 20, 64)
    assert (mc.mamba_d_inner, mc.mamba_d_state, mc.mamba_dt_rank,
            mc.mamba_d_conv) == (5120, 16, 160, 4)
    assert mc.sliding_window == 512 and mc.tie_word_embeddings
    shapes = jax.eval_shape(
        lambda k: get_model(mc).init_params(mc, k), jax.random.PRNGKey(0))
    count = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert count == 3_852_562_944


# ------------------------------------------------------ a checkpoint's names
def test_a_checkpoint_under_the_assumed_names_loads_to_the_same_logits(
        tmp_path):
    """A tiny tree written as an HF checkpoint under the ASSUMED leaf names
    (fused ``attn.Wqkv``, ``attn.Wq`` of a cross layer, ``A_log`` as [D, N],
    the conv as [D, 1, K], no ``lm_head``) loads to the same logits."""
    safetensors = pytest.importorskip("safetensors.numpy")
    from production_stack_tpu.models.weights import load_hf_params

    mc = TINY_PHI4FLASH
    params = get_model(mc).init_params(mc, jax.random.PRNGKey(6), F32)
    back = {ours: (suffix, tr)
            for suffix, (ours, tr) in phi4flash.HF_LAYER_MAP.items()}
    back["wqkv"] = ("attn.Wqkv.weight", True)
    back["bqkv"] = ("attn.Wqkv.bias", False)
    tensors = {}
    for i, slot in enumerate(phi4flash.layer_slots(mc)):
        for leaf, (kind, at) in slot.items():
            x = np.asarray(params["layers"][kind][leaf][at])
            suffix, tr = back[leaf]
            if kind == "cross" and leaf in ("wqkv", "bqkv"):
                suffix = suffix.replace("Wqkv", "Wq")
            if leaf == "conv_w":
                x = x[:, None, :]
            tensors[f"model.layers.{i}.{suffix}"] = np.ascontiguousarray(
                x.T if tr else x)
    for name, (ours, tr) in phi4flash.HF_TOP_MAP.items():
        tensors[name] = np.asarray(params[ours])
    safetensors.save_file(tensors, str(tmp_path / "model.safetensors"))
    with open(tmp_path / "config.json", "w") as f:
        json.dump(hf_config(mc), f)
    loaded = load_hf_params(resolve_model_config(str(tmp_path)),
                            str(tmp_path), F32)
    assert "lm_head" not in loaded
    t = 30
    ids = jnp.asarray(prompt(t, 3))[None]
    pos = jnp.arange(t, dtype=jnp.int32)[None]
    outs = [phi4flash.forward(p, mc, ids, pos, jnp.asarray([t]))[0]
            for p in (params, loaded)]
    np.testing.assert_allclose(outs[0], outs[1], rtol=1e-5, atol=1e-5)


# ----------------------------------------------------- the served surface
async def test_the_served_surface_names_the_caches_and_the_counters():
    """``GET /version`` and every line of ``GET /debug/programs`` say which
    layers keep a ring or a scan's state, their shapes, the ONE pooled
    layer and its readers; a prefill line says which execution of the scan
    it holds; ``GET /metrics`` moves the two ``pstpu:ring_keys_*`` counters
    by the closed form of the request's prompt and answer; ``GET
    /debug/memory`` enters the four state pools by name."""
    from aiohttp.test_utils import TestClient, TestServer

    from production_stack_tpu.server.api_server import APIServer

    eng = make_engine(max_model_len=512, num_kv_blocks=64)
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
    # The out - 1 decode queries sit at positions n .. n + out - 2: two
    # window layers hold min(position + 1, 64) keys of position + 1.
    assert sample["pstpu:ring_keys_held_total"] == 2 * (out - 1) * W
    assert sample["pstpu:ring_keys_context_total"] == 2 * sum(
        range(n + 1, n + out))
    assert {p["program"] for p in programs} == {"decode", "prefill"}
    assert {p["program"]: p.get("s6_chunk") for p in programs} == {
        "decode": None, "prefill": "xla"}
    assert {p["program"]: p.get("ring_step") for p in programs} == {
        "decode": "xla", "prefill": None}
    for said in (*programs, version["engine"]):
        assert said["window_layers"] == [1, 3]
        assert said["ring"] == {"ring_k": [2, W, 128], "ring_v": [2, W, 128]}
        assert said["scan_layers"] == [0, 2, 4]
        assert said["scan_state"] == {"s6": [16, 1024], "conv": [24, 128]}
        assert said["paged_layer"] == 5
        assert said["paged_layer_readers"] == [5, 7]
        assert said["memory_layer"] == 4 and said["memory_readers"] == [6]
    slots = eng.runner.num_state_slots
    assert memory["state_pools"] == {
        "ring_k": slots * 2 * 2 * W * 128 * 4,
        "ring_v": slots * 2 * 2 * W * 128 * 4,
        "s6": slots * 3 * 16 * 1024 * 4,
        "conv": slots * 3 * 24 * 128 * 4}
    assert sum(memory["state_pools"].values()) == \
        memory["residents"]["state"]


def test_a_row_cap_that_is_no_power_of_two_is_a_warmed_bucket():
    """``--max-num-seqs 48`` (this configuration's): a train of 33 to 48
    rows runs in the bucket of 48, which warm-up has to compile (it warmed
    the powers of two alone, and the first such train compiled while
    serving: PERF.md section 6, PR 54)."""
    from production_stack_tpu.engine.runner import _bucket

    eng = make_engine(max_num_seqs=6, max_prefill_seqs=6)
    rows = {f[0] for f in eng.runner.reachable_decode_families()}
    assert rows == {1, 2, 4, 6}
    assert {_bucket(n, 1, 6) for n in range(1, 7)} == rows
    same = make_engine(max_num_seqs=8)
    assert {f[0] for f in same.runner.reachable_decode_families()} == \
        {1, 2, 4, 8}
