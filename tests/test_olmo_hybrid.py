"""The Olmo-Hybrid family (Gated DeltaNet layers with per-sequence recurrent
state beside paged K/V) against its plain reference
(tests/reference/olmo_hybrid_ref.py), through the engine's own scheduler,
block manager and runner at a tiny preset with float32 activations.

What is compared is log-probabilities, not tokens: every generated token's
own log-probability and those of the 20 most likely tokens at its position,
as the served surface returns them (``logprobs=20``), against the
reference's log-softmax at the same ids. A log-probability is a logit less
the row's normaliser, so an error in any logit of weight shows.

TOL: both sides are float32 with full-precision products on the CPU; they
differ in the ORDER of sums (the chunkwise form against the token-by-token
recurrence, batched rows, a prompt cut into chunks) over 8 layers. Measured
largest difference over every case here: under 1e-3 (logit spread 1.0). The
six wrong models of ``test_the_tolerance_tells_a_wrong_model`` move the same
numbers by 0.1 to several units, so 5e-3 leaves both sides room.
"""

import dataclasses
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
from production_stack_tpu.models.config import TINY_OLMO_HYBRID, ModelConfig
from production_stack_tpu.ops import gated_delta as gd

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tests", "reference"))
import olmo_hybrid_ref as ref  # noqa: E402

TOL = 5e-3
TOP = 20


def hf_config(mc: ModelConfig) -> dict:
    """The HF config.json keys the reference reads, from a ModelConfig."""
    return {
        "hidden_size": mc.hidden_size, "num_hidden_layers": mc.num_layers,
        "num_attention_heads": mc.num_heads,
        "num_key_value_heads": mc.num_kv_heads, "head_dim": mc.head_dim,
        "rms_norm_eps": mc.rms_norm_eps,
        "layer_types": list(mc.layer_types),
        "linear_num_value_heads": mc.linear_num_heads,
        "linear_key_head_dim": mc.linear_key_head_dim,
        "linear_value_head_dim": mc.linear_value_head_dim,
        "linear_allow_neg_eigval": mc.linear_allow_neg_eigval,
        "rope_parameters": {"rope_theta": mc.rope_theta},
    }


def make_engine(model="tiny-olmo-hybrid", **over) -> ServingEngine:
    cfg = dict(model=model, max_model_len=512, num_kv_blocks=128,
               num_decode_steps=8, dtype="float32", max_num_seqs=8,
               max_num_batched_tokens=64, max_prefill_seqs=8)
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
    logits = ref.forward(eng.runner.params, hf_config(mc), tokens[:-1], wrong)
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


# ---- (a)-(f): the engine's path against the reference ----------------------
def test_a_prefill_of_one_chunk(engine):
    seq = add(engine, "a", prompt(40, 1), 1)
    batches = drive(engine)
    assert [b.kind for b in batches] == ["prefill"]
    assert worst(engine, seq) < TOL


def test_b_a_prompt_crossing_three_prefill_chunks(engine):
    seq = add(engine, "b", prompt(150, 2), 4)
    batches = drive(engine)
    assert [b.chunk_lens for b in batches if b.kind == "prefill"] == \
        [[64], [64], [22]]
    assert worst(engine, seq) < TOL


def test_c_decode_trains_with_unequal_budgets_and_wasted_steps(engine):
    """40 decode steps in trains of 8; in the second train row 1 is given a
    budget of 3 of the 8 steps and goes on afterwards: the 5 steps that
    deliver nothing must leave its state as it was."""
    seqs = [add(engine, f"c{i}", prompt(20 + 7 * i, 10 + i), n)
            for i, n in enumerate((41, 41, 30))]
    cut = {}

    def shorten(batch):
        """The first full train that row 1 rides with rows beside it."""
        if cut or batch.kind != "decode" or batch.num_steps != 8 \
                or len(batch.seqs) < 3:
            return
        i = batch.seqs.index(seqs[1])
        cut["before"], cut["rows"] = batch.decode_steps[i], len(batch.seqs)
        batch.decode_steps[i] = 3

    while engine.scheduler.has_work():
        step(engine, shorten)
    assert cut == {"before": 8, "rows": 3}
    assert [len(s.output_token_ids) for s in seqs] == [41, 41, 30]
    for seq in seqs:
        assert worst(engine, seq) < TOL


def test_d_five_rows_of_unequal_length_in_one_prefill():
    # A token budget that holds five rows of the floor width (128).
    engine = make_engine(max_num_batched_tokens=1024)
    seqs = [add(engine, f"d{i}", prompt(n, 20 + i), 3)
            for i, n in enumerate((5, 12, 9, 3, 11))]
    batches = drive(engine)
    assert batches[0].kind == "prefill" and len(batches[0].seqs) == 5
    for seq in seqs:
        assert worst(engine, seq) < TOL


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


def test_f_a_slot_reused_by_a_second_sequence_is_cleared(engine):
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


# ---- (h): both readings of rope_theta, and the paged kernel ------------------
@pytest.mark.parametrize("name,change,engine_args", [
    ("rope-null", {}, {}),
    ("rope-500000", {"rope_theta": 500000.0}, {}),
    # Three heads of 128 (not a multiple of 8, as the published 30): the
    # full layers decode through the Pallas kernel (interpreted on the CPU).
    ("paged-3-heads", {"num_heads": 3, "num_kv_heads": 3, "head_dim": 128},
     {"attn_impl": "paged"}),
])
def test_h_rope_readings_and_the_paged_kernel(monkeypatch, name, change,
                                              engine_args):
    mc = dataclasses.replace(TINY_OLMO_HYBRID, name=f"tiny-{name}", **change)
    monkeypatch.setitem(model_configs.NAMED_CONFIGS, mc.name, mc)
    eng = make_engine(mc.name, **engine_args)
    if engine_args:
        assert eng.runner.attn_impl == "paged"
    seqs = [add(eng, f"h{i}", prompt(n, 50 + i), 12)
            for i, n in enumerate((70, 18))]
    drive(eng)
    for seq in seqs:
        assert worst(eng, seq) < TOL


def test_rope_theta_changes_the_answer():
    """The two readings are two models: the key is not ignored."""
    toks = jnp.asarray(prompt(24, 60))
    from production_stack_tpu.models import get_model

    model = get_model(TINY_OLMO_HYBRID)
    params = model.init_params(TINY_OLMO_HYBRID, jax.random.PRNGKey(0),
                               jnp.float32)
    null = ref.forward(params, hf_config(TINY_OLMO_HYBRID), toks)
    rot = ref.forward(params, hf_config(dataclasses.replace(
        TINY_OLMO_HYBRID, rope_theta=500000.0)), toks)
    assert float(jnp.max(jnp.abs(null - rot))) > 0.1


# ---- the tolerance is tight enough -----------------------------------------
@pytest.fixture(scope="module")
def served(engine):
    seq = add(engine, "w", prompt(90, 70), 40)
    drive(engine)
    assert worst(engine, seq) < TOL
    return seq


@pytest.mark.parametrize("wrong", ref.WRONG)
def test_the_tolerance_tells_a_wrong_model(engine, served, wrong):
    """A prompt of 90 tokens (two chunks) and 40 decoded tokens, against
    the reference with ONE equation wrong: each is far outside TOL."""
    assert worst(engine, served, wrong=(wrong,)) > 10 * TOL


# ---- what the state cannot follow is refused at start ------------------------
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
def test_what_state_cannot_follow_is_refused_at_start(flags, named):
    with pytest.raises(ValueError, match="recurrent state") as err:
        make_engine(**flags)
    assert named.lower() in str(err.value).lower()


def test_a_kv_only_model_is_refused_nothing():
    EngineConfig(model="tiny-llama", kv_cache_dtype="int8",
                 kv_offload_cpu=True).refuse_what_state_cannot_follow(
        model_configs.TINY_LLAMA)


# ---- prefix reuse: safe, not fast ---------------------------------------------
def test_no_prefix_hit_is_served_and_the_unserved_are_counted(engine):
    bm = engine.block_manager
    shared = prompt(64, 80)
    first = add(engine, "p1", shared + prompt(10, 81), 2)
    drive(engine)
    hits, unserved = bm.prefix_hits_total, bm.prefix_hits_unserved_total
    assert bm.prefix_index_size >= 4       # blocks are still registered
    second = add(engine, "p2", shared + prompt(12, 82), 2)
    drive(engine)
    assert second.num_cached_tokens == 0 and bm.prefix_hits_total == hits
    assert bm.prefix_hits_unserved_total == unserved + 64
    assert worst(engine, first) < TOL and worst(engine, second) < TOL


def test_admission_waits_for_a_state_slot():
    eng = make_engine(max_num_seqs=2, max_prefill_seqs=2)
    bm = eng.block_manager
    assert bm.num_state_slots == 2 and eng.runner.num_state_slots == 3
    seqs = [add(eng, f"s{i}", prompt(20, 90 + i), 4) for i in range(3)]
    step(eng)
    step(eng)
    assert sorted(s.state_slot for s in seqs) == [0, 1, 2]
    assert bm.allocate_state_slot() == 0 and bm.state_slot_waits_total == 1
    drive(eng)
    # (A row the token budget drops from a prefill gives its slot back
    # with its blocks and takes one again: allocations may exceed rows.)
    assert bm.state_slot_allocs_total >= 3 and bm.state_slots_in_use == 0
    assert [len(s.output_token_ids) for s in seqs] == [4, 4, 4]
    stats = eng.stats()
    assert stats["state_slots_total"] == 2
    assert eng.report()["engine"]["state_bytes"] == \
        eng.runner.state_pool_bytes > 0


# ---- what says that the decode step's kernel engages ---------------------------
@pytest.mark.parametrize("engine_args,path", [
    ({}, "xla"),
    # The runner's Pallas interpret switch: a CPU engine on the paged path
    # runs its kernels through the interpreter, this one too.
    ({"attn_impl": "paged"}, "pallas"),
], ids=["window-xla", "paged-pallas"])
async def test_the_served_surface_says_which_step_runs_and_counts_bucket_rows(
        monkeypatch, engine_args, path):
    """``GET /debug/programs`` names the execution of ``gdn_step`` each
    decode program holds, and bucket row-steps count the padding rows that
    row-steps do not: three rows decode in a 4-row program."""
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from production_stack_tpu.server.api_server import APIServer

    mc = dataclasses.replace(TINY_OLMO_HYBRID, name=f"tiny-says-{path}",
                             num_heads=3, num_kv_heads=3, head_dim=128)
    monkeypatch.setitem(model_configs.NAMED_CONFIGS, mc.name, mc)
    client = TestClient(TestServer(APIServer(
        make_engine(mc.name, **engine_args)).build_app()))
    await client.start_server()
    try:
        done = await asyncio.gather(*(client.post("/v1/completions", json={
            "model": mc.name, "prompt": prompt(12, 70 + i),
            "max_tokens": 41, "temperature": 0, "ignore_eos": True})
            for i in range(3)))
        assert [r.status for r in done] == [200] * 3
        text = await (await client.get("/metrics")).text()
        programs = (await (await client.get("/debug/programs")).json())[
            "programs"]
    finally:
        await client.close()
    sample = {ln.split("{")[0]: float(ln.rsplit(" ", 1)[1])
              for ln in text.splitlines() if ln.startswith("pstpu:decode_")}
    rows, bucket, wasted = (sample[f"pstpu:decode_{k}_total"] for k in (
        "row_steps", "bucket_row_steps", "row_steps_wasted"))
    assert rows - wasted == 3 * 40       # each request's first is prefill's
    # Five trains of 8 a row: the three rode some of them together, in
    # the 4-row program.
    assert bucket > rows > 0
    assert {p["program"] for p in programs} == {"decode", "prefill"}
    # (Not ``pool_copies``: the interpreter's loops copy the carry that
    # the chip's kernel updates where it lies;
    # tests/test_chip_compile_recurrent.py holds the program compiled for
    # the chip to that.)
    for p in programs:
        assert p.get("gdn_step") == \
            (path if p["program"] == "decode" else None)
