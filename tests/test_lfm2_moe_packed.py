"""The LFM2-MoE family through PACKED prefill rows (the conv state crosses a
segment boundary inside the row) against the reference; configuration,
weights, refusals and the served surface. tests/test_lfm2_moe.py says what is
compared and why TOL.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.engine.sampling import SamplingParams
from production_stack_tpu.models import lfm2_moe
from production_stack_tpu.models.config import LFM2_LAYER_TYPES, ModelConfig
from tests.lfm2_moe_helpers import (
    TINY_CUT,
    TOL,
    add,
    cut,
    drive,
    make_engine,
    prompt,
    ref,
    step,
    worst,
)


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
