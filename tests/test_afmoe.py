"""The AFMoE family (attention layers whose queries see a bounded SPAN of
keys mixed with position-free layers that see all of them, a gate on the
attention output, four norms a layer, sigmoid-routed sparse experts beside a
shared one) against its plain reference (tests/reference/afmoe_ref.py),
through the engine's own scheduler, block manager and runner at a tiny
preset with float32 activations and a span of 24 keys: not a multiple of
the block (16), so a bound falls inside a block, and small enough that
prompts, chunks, packed segments and decode all cross it.

What is compared is log-probabilities, not tokens: every generated token's
own log-probability and those of the 20 most likely tokens at its position,
as the served surface returns them (``logprobs=20``), against the
reference's log-softmax at the same ids.

TOL: both sides are float32 with full-precision products on the CPU; they
differ in the ORDER of sums (the grouped matmul over sorted pairs against
dense experts, batched rows, a prompt cut into chunks, the paged kernels'
blocks) over 8 layers. Measured largest difference over every case here:
3e-6 (logit spread 1.0). The thirteen wrong models of
``test_the_tolerance_tells_a_wrong_model`` move the same numbers by 0.04 to
several units, and the three computations in too little precision
(``LOW_PRECISION``: a bf16 router, norm or softmax) by 0.01 and more, so
1e-3 leaves both sides a decade and more of room.
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
from production_stack_tpu.models import afmoe, get_model
from production_stack_tpu.models import config as model_configs
from production_stack_tpu.models.config import TINY_AFMOE, ModelConfig
from production_stack_tpu.ops.attention import NO_SPAN, keys_in_span

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tests", "reference"))
import afmoe_ref as ref  # noqa: E402

TOL = 1e-3
TOP = 20
CHUNK = 64          # make_engine's max_num_batched_tokens
SPAN = TINY_AFMOE.sliding_window
CUT = os.path.join(ROOT, "benchmarks", "chip", "configs", "trinity-mini-d8",
                   "config.json")


def hf_config(mc: ModelConfig) -> dict:
    """The HF config.json keys the reference reads, from a ModelConfig."""
    return {
        "hidden_size": mc.hidden_size, "num_hidden_layers": mc.num_layers,
        "num_attention_heads": mc.num_heads,
        "num_key_value_heads": mc.num_kv_heads, "head_dim": mc.head_dim,
        "rms_norm_eps": mc.rms_norm_eps, "rope_theta": mc.rope_theta,
        "layer_types": list(mc.layer_types),
        "sliding_window": mc.sliding_window,
        "num_dense_layers": mc.first_k_dense_replace,
        "num_experts_per_tok": mc.num_experts_per_tok,
        "route_norm": mc.norm_topk_prob,
        "route_scale": mc.routed_scaling_factor,
        "mup_enabled": mc.embedding_multiplier != 1.0,
    }


def make_engine(model="tiny-afmoe", **over) -> ServingEngine:
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


def step(eng):
    """One dispatch, synchronously: schedule, run, apply."""
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
    # A reference that overflowed (a wrong model may) is as far as can be.
    return float(np.max(np.nan_to_num(np.abs(diffs), nan=np.inf)))


@pytest.fixture(scope="module")
def engine():
    """The engine's default path on the CPU: ``window_attention`` under
    each layer's span over gathered history."""
    eng = make_engine()
    assert eng.runner.attn_impl == "window" and not eng.runner.prefill_packs
    return eng


@pytest.fixture(scope="module")
def paged():
    """``--attn-impl paged`` (128-lane heads): the bounded Pallas kernels
    in interpret mode, prefill as ONE packed row."""
    eng = make_engine(attn_impl="paged", max_num_batched_tokens=128)
    assert eng.runner.prefill_packs and eng.runner.prefill_reads_pool
    return eng


# ---- the engine's path against the reference --------------------------------
@pytest.mark.parametrize("path", ["window", "paged"])
@pytest.mark.parametrize("n", [SPAN // 2, SPAN, SPAN + 1, int(3.3 * SPAN)],
                         ids=lambda n: f"prompt{n}")
def test_a_prompts_under_at_and_past_the_bound(engine, paged, path, n):
    """Prompts of 0.5 x, 1 x, 1 x + 1 and 3.3 x the span, then 14 decoded
    tokens: the shortest starts decoding under the bound and runs past
    it."""
    eng = engine if path == "window" else paged
    seq = add(eng, f"a{n}", prompt(n, n), 15)
    kinds = [b.kind for b in drive(eng)]
    assert kinds[0] == "prefill" and "decode" in kinds
    assert n + 14 > SPAN
    assert worst(eng, seq) < TOL


@pytest.mark.parametrize("path", ["window", "paged"])
def test_b_a_prompt_crossing_two_chunk_boundaries(engine, paged, path):
    """Three chunks: the second and third attend a history that starts
    behind their bound."""
    eng = engine if path == "window" else paged
    chunk = eng.config.max_num_batched_tokens
    seq = add(eng, "b", prompt(2 * chunk + 5, 2), 4)
    batches = drive(eng)
    assert [b.chunk_lens for b in batches if b.kind == "prefill"] == \
        [[chunk], [chunk], [5]]
    assert worst(eng, seq) < TOL


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


@pytest.mark.parametrize("path", ["window", "paged"])
def test_d_decode_rows_of_unequal_length_run_past_the_bound(engine, paged,
                                                            path):
    """40 decode steps in trains of 8 (the ring holds a train's earlier
    steps, bounded like the pool), three rows of which one starts under
    the bound."""
    eng = engine if path == "window" else paged
    seqs = [add(eng, f"d{i}", prompt(n, 30 + i), 41)
            for i, n in enumerate((7, 40, 100))]
    drive(eng)
    for seq in seqs:
        assert len(seq.output_token_ids) == 41
        assert worst(eng, seq) < TOL


def test_e_preempt_and_recompute(engine):
    seq = add(engine, "e", prompt(70, 40), 20)
    other = add(engine, "e2", prompt(30, 41), 20)
    for _ in range(4):
        step(engine)
    assert 0 < len(seq.output_token_ids) < 20
    engine.scheduler._preempt(seq)
    assert not seq.block_ids
    drive(engine)
    assert len(seq.output_token_ids) == 20
    assert worst(engine, seq) < TOL and worst(engine, other) < TOL


@pytest.mark.parametrize("path", ["window", "paged"])
def test_f_a_prefix_hit_is_served_and_the_answer_is_the_cold_ones(
        engine, paged, path):
    """Blocks are blocks: the second send of a prompt is served from the
    prefix cache (one block table for bounded and unbounded layers alike)
    and answers as the first did."""
    eng = engine if path == "window" else paged
    bm = eng.block_manager
    shared = prompt(96, 80 + (path == "paged"))
    first = add(eng, "p1", shared, 6)
    drive(eng)
    hits = bm.prefix_hits_total
    second = add(eng, "p2", shared, 6)
    drive(eng)
    assert bm.prefix_hits_total > hits and second.num_cached_tokens >= 64
    assert second.output_token_ids == first.output_token_ids
    assert worst(eng, first) < TOL and worst(eng, second) < TOL


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


# ---- the tolerance is tight enough -----------------------------------------
@pytest.fixture(scope="module")
def served(engine):
    """129 prompt tokens (three chunks) and 40 decoded ones."""
    seq = add(engine, "w", prompt(2 * CHUNK + 1, 70), 40)
    drive(engine)
    assert worst(engine, seq) < TOL
    return seq


@pytest.mark.parametrize("wrong", ref.WRONG + ref.LOW_PRECISION)
def test_the_tolerance_tells_a_wrong_model(engine, served, wrong):
    """Against the reference with ONE equation wrong, or one computation
    (the router, a norm, the softmax) in bf16: each is far outside TOL."""
    assert worst(engine, served, wrong=(wrong,)) > 5 * TOL


def test_the_programs_choices_are_the_references(engine):
    """Share of (token, sparse layer) choices whose top-k SET differs
    between the program's whole forward and the reference's: none in
    float32 over 200 tokens; the router in bf16 flips several in a
    thousand (top-2 of 8 experts: fewer near-ties than the cut's top-8 of
    128)."""
    mc, tokens = engine.model_config, prompt(200, 71)
    *_, chosen = afmoe.forward(
        engine.runner.params, mc, jnp.asarray([tokens], jnp.int32),
        jnp.arange(200, dtype=jnp.int32)[None], jnp.asarray([200]),
        routing=True)
    right, low = [], []
    ref.forward(engine.runner.params, hf_config(mc), tokens, routing=right)
    ref.forward(engine.runner.params, hf_config(mc), tokens,
                ("router_bf16",), routing=low)

    def sets(x):
        return np.sort(np.stack([np.asarray(c) for c in x]), -1)

    assert np.asarray(chosen).shape == (6, 200, 2)
    assert np.mean(np.any(
        np.sort(np.asarray(chosen), -1) != sets(right), -1)) < 0.002
    assert np.mean(np.any(sets(low) != sets(right), -1)) > 0.004


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
