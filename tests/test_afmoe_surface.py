"""The AFMoE family against the reference through engines a case builds for
itself (a packed row of segments around their bound, layer types in any
order), the keys counters, the served surface, configuration, weights and
refusals. tests/test_afmoe.py says what is compared and why TOL.
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
from production_stack_tpu.models import afmoe, get_model
from production_stack_tpu.models import config as model_configs
from production_stack_tpu.models.config import TINY_AFMOE, ModelConfig
from production_stack_tpu.ops.attention import NO_SPAN, keys_in_span
from tests.afmoe_helpers import (
    ROOT,
    SPAN,
    TOL,
    add,
    drive,
    make_engine,
    prompt,
    step,
    worst,
)


CUT = os.path.join(ROOT, "benchmarks", "chip", "configs", "trinity-mini-d8",
                   "config.json")


def test_c_a_packed_row_of_segments_before_at_and_behind_their_bound():
    """One packed row of several sequences' chunks: first chunks (no
    history), and, a dispatch later, the long prompt's second chunk whose
    history lies behind its bound beside a new short prompt."""
    eng = make_engine(attn_impl="paged", max_num_batched_tokens=512,
                      max_model_len=1024, num_kv_blocks=256)
    seqs = [add(eng, "c0", prompt(700, 20), 3)]
    first = step(eng)
    assert first.packed and first.chunk_lens == [512]
    # The long prompt's rest (188 tokens behind 512 of history) rides one
    # row with three prompts that begin: shorter than, as long as and
    # longer than the span.
    seqs += [add(eng, f"c{i}", prompt(n, 20 + i), 3)
             for i, n in enumerate((10, SPAN, 31), 1)]
    second = step(eng)
    assert second.packed and sorted(second.chunk_starts) == [0, 0, 0, 512]
    assert sorted(second.chunk_lens) == [10, SPAN, 31, 188]
    drive(eng)
    for seq in seqs:
        assert worst(eng, seq) < TOL


# ---- layer_types in any order ------------------------------------------------
ORDERS = {
    "published-32": ("sliding_attention",) * 3 + ("full_attention",),
    "not-a-period": ("full_attention", "sliding_attention",
                     "sliding_attention", "full_attention", "full_attention",
                     "sliding_attention"),
    "all-sliding": ("sliding_attention",) * 4,
    "all-full": ("full_attention",) * 4,
}


@pytest.mark.parametrize("order", list(ORDERS))
def test_g_layer_types_are_taken_in_any_order(monkeypatch, order):
    """The published list's shape at 32 entries (eight periods), an order
    that is no period, and lists of one kind: a layer's kind is two scalars
    its scan indexes, so one program serves any list."""
    types = ORDERS[order] * (8 if order == "published-32" else 1)
    mc = dataclasses.replace(
        TINY_AFMOE, num_layers=len(types), layer_types=types,
        name=f"tiny-afmoe-{order}")
    monkeypatch.setitem(model_configs.NAMED_CONFIGS, mc.name, mc)
    eng = make_engine(mc.name)
    spans = afmoe.spans(mc)
    assert [s != NO_SPAN for s in spans] == \
        [t == "sliding_attention" for t in types]
    assert (eng.runner.layer_spans is None) == (order == "all-full")
    seq = add(eng, "g", prompt(70, 50), 6)
    drive(eng)
    assert worst(eng, seq) < TOL


# ---- counters ------------------------------------------------------------------
def test_the_keys_counters_are_the_sum_over_positions():
    """``_attn_keys``: for runs of tokens at given positions, keys inside
    each layer's span and keys held, summed over the layers, against the
    sum written out."""
    eng = make_engine()
    starts, lens = [0, 10, 100], [30, 5, 64]
    seen, held = eng._attn_keys(starts, lens)
    spans = afmoe.spans(eng.model_config)
    want_seen = sum(min(p + 1, int(s)) for s in spans
                    for a, n in zip(starts, lens) for p in range(a, a + n))
    want_held = sum(p + 1 for _ in spans
                    for a, n in zip(starts, lens) for p in range(a, a + n))
    assert (seen, held) == (want_seen, want_held)
    assert make_engine("tiny-llama")._attn_keys(starts, lens) == (0, 0)


async def test_the_served_surface_names_the_span_and_the_counters():
    """``GET /version`` and every line of ``GET /debug/programs`` say
    which layers are bounded and by how many keys; ``GET /metrics`` exports
    the two counters beside the six ``pstpu:moe_*`` series, and both move
    by the closed form of the request's prompt and answer."""
    from aiohttp.test_utils import TestClient, TestServer

    from production_stack_tpu.server.api_server import APIServer

    eng = make_engine(max_model_len=256, num_kv_blocks=64)
    mc = eng.model_config
    client = TestClient(TestServer(APIServer(eng).build_app()))
    await client.start_server()
    n, out = 50, 9
    try:
        done = await client.post("/v1/completions", json={
            "model": mc.name, "prompt": prompt(n, 90), "max_tokens": out,
            "temperature": 0, "ignore_eos": True})
        assert done.status == 200
        text = await (await client.get("/metrics")).text()
        programs = (await (await client.get("/debug/programs")).json())[
            "programs"]
        version = await (await client.get("/version")).json()
    finally:
        await client.close()
    sample = {ln.split("{")[0]: float(ln.rsplit(" ", 1)[1])
              for ln in text.splitlines() if ln.startswith("pstpu:")}
    spans = afmoe.spans(mc)
    # The prompt's n tokens at issue, then the out - 1 decode queries at
    # positions n .. n + out - 2.
    assert sample["pstpu:attn_keys_in_span_total"] == sum(
        int(keys_in_span(0, n + out - 1, s)) for s in spans)
    assert sample["pstpu:attn_keys_held_total"] == len(spans) * sum(
        range(1, n + out))
    assert sample["pstpu:moe_layer_calls_total"] > 0
    assert {p["program"] for p in programs} == {"decode", "prefill"}
    for said in (*programs, version["engine"]):
        assert said["span_layers"] == [0, 1, 2, 4, 5, 6]
        assert said["span"] == SPAN


# ---- config.json ------------------------------------------------------------------
def cut() -> dict:
    with open(CUT) as f:
        return json.load(f)


def test_from_hf_config_reads_the_cut_and_the_published_list():
    doc = cut()
    mc = ModelConfig.from_hf_config(doc)
    assert mc.arch == "afmoe" and mc.num_layers == 8
    assert mc.layer_types == ORDERS["published-32"] * 2
    assert (mc.sliding_window, mc.first_k_dense_replace) == (2048, 2)
    assert (mc.n_routed_experts, mc.num_experts_per_tok,
            mc.n_shared_experts) == (128, 8, 1)
    assert mc.routed_scaling_factor == 2.826 and mc.norm_topk_prob
    assert mc.embedding_multiplier == 2048 ** 0.5
    assert afmoe.bounded_layers(mc) == [0, 1, 2, 4, 5, 6]
    specs = get_model(mc).cache_specs(mc)
    assert specs.paged_kv == (8, 4, 128) and not specs.state
    full = dict(doc, num_hidden_layers=32,
                layer_types=list(ORDERS["published-32"] * 8))
    assert ModelConfig.from_hf_config(full).num_layers == 32


def test_the_served_tree_has_the_hand_counted_parameters():
    """ISSUE 47's arithmetic (lib/shapes_afmoe.py) against the tree
    ``init_params`` makes at the cut's widths, by shape alone."""
    sys.path.insert(0, ROOT)
    from benchmarks.chip.lib import shapes_afmoe

    doc = cut()
    mc = ModelConfig.from_hf_config(doc)
    tree = jax.eval_shape(
        lambda: get_model(mc).init_params(mc, jax.random.PRNGKey(0)))
    served = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))
    assert served == shapes_afmoe.param_count(doc)
    assert shapes_afmoe.matrix_params(doc) == 5_984_747_520
    assert shapes_afmoe.attention_params(doc) == 27_262_976
    assert shapes_afmoe.sparse_ffn_params(doc) == 811_859_968
    assert shapes_afmoe.kv_bytes_per_token(doc) == 16 * 1024


def test_a_checkpoint_in_hf_layout_loads_into_the_stacks_by_kind(tmp_path):
    """The tiny preset's tree written out under HF's names (an expert a
    tensor, gate and up apart) loads back leaf for leaf."""
    pytest.importorskip("safetensors")
    from safetensors.numpy import save_file

    from production_stack_tpu.models.weights import load_hf_params

    mc = dataclasses.replace(TINY_AFMOE, name=str(tmp_path))
    params = afmoe.init_params(mc, jax.random.PRNGKey(3), jnp.float32)
    back = {leaf: (name, t) for name, (leaf, t) in afmoe.HF_LAYER_MAP.items()}
    tensors = {}
    for name, (leaf, t) in afmoe.HF_TOP_MAP.items():
        x = np.asarray(params[leaf])
        tensors[name] = x.T.copy() if t else x
    fe = mc.moe_intermediate_size
    for i, (kind, at) in enumerate(afmoe.layer_slots(mc)):
        stack = dict(params["layers"][kind])
        if kind == "sparse":
            gu = stack.pop("w_gate_up")
            stack["we_gate"], stack["we_up"] = gu[..., :fe], gu[..., fe:]
        for leaf, x in stack.items():
            name, t = back[leaf]
            x = np.asarray(x[at])
            if "experts.*" in name:
                for e in range(x.shape[0]):
                    tensors[f"model.layers.{i}." + name.replace("*", str(e))] \
                        = x[e].T.copy() if t else x[e]
            else:
                tensors[f"model.layers.{i}.{name}"] = x.T.copy() if t else x
    save_file(tensors, str(tmp_path / "model.safetensors"))
    loaded = load_hf_params(mc, str(tmp_path), jnp.float32)
    flat_want = jax.tree_util.tree_leaves_with_path(params)
    flat_got = dict(jax.tree_util.tree_leaves_with_path(loaded))
    assert len(flat_want) == len(flat_got)
    for path, want in flat_want:
        np.testing.assert_array_equal(flat_got[path], want, str(path))


# ---- refusals by key ------------------------------------------------------------
@pytest.mark.parametrize("edit,named", [
    ({"rope_scaling": {"type": "yarn", "factor": 4}}, "rope_scaling"),
    ({"score_func": "softmax"}, "score_func != sigmoid"),
    ({"n_group": 2}, "n_group"),
    ({"num_expert_groups": 4}, "num_expert_groups"),
    ({"topk_group": 2}, "topk_group"),
    ({"sliding_window": None}, "sliding_window < 1"),
    ({"hidden_act": "gelu"}, "hidden_act"),
    ({"attention_bias": True}, "attention_bias"),
    ({"num_dense_layers": 8}, "num_dense_layers"),
])
def test_what_the_module_does_not_implement_is_refused_by_key(edit, named):
    with pytest.raises(ValueError, match=named):
        ModelConfig.from_hf_config(dict(cut(), **edit))


def test_a_layer_of_another_kind_is_refused():
    doc = cut()
    doc["layer_types"] = doc["layer_types"][:7] + ["linear_attention"]
    with pytest.raises(ValueError, match="unknown kinds"):
        ModelConfig.from_hf_config(doc)
    doc["layer_types"] = doc["layer_types"][:7]
    with pytest.raises(ValueError, match="7 entries for 8 layers"):
        ModelConfig.from_hf_config(doc)


@pytest.mark.parametrize("flags,named", [
    (dict(speculative_num_tokens=2, speculative_model="tiny-afmoe"),
     "speculative decoding"),
    (dict(lora_modules=["a=/nowhere"]), "LoRA adapters"),
    (dict(kv_cache_dtype="int8"), "--kv-cache-dtype int8"),
    (dict(tensor_parallel_size=2), "tensor parallelism"),
    (dict(sequence_parallel_size=2), "sequence parallelism"),
])
def test_what_a_span_cannot_follow_is_refused_at_start(flags, named):
    with pytest.raises(ValueError, match=f"bounds the keys.*{named}"):
        make_engine(**flags)


def test_a_model_without_a_bounded_layer_is_refused_nothing(monkeypatch):
    """The refusal reads the declaration: the same module with every layer
    ``full_attention`` starts with an int8 pool."""
    mc = dataclasses.replace(
        TINY_AFMOE, layer_types=("full_attention",) * 8,
        name="tiny-afmoe-all-full")
    monkeypatch.setitem(model_configs.NAMED_CONFIGS, mc.name, mc)
    EngineConfig(model=mc.name, kv_cache_dtype="int8") \
        .refuse_what_a_span_cannot_follow(mc)
    with pytest.raises(ValueError, match="bounds the keys"):
        EngineConfig(model="tiny-afmoe", kv_cache_dtype="int8") \
            .refuse_what_a_span_cannot_follow(TINY_AFMOE)
