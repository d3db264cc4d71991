"""The MiMo-V2 family (full layers that page their keys mixed with window
layers that keep a per-sequence ring of 128 keys in a state slot, different
KV head counts, keys wider than values, a partial rope, a sink in the window
layers' softmax, sigmoid-routed experts of which a chip may hold a share)
against its plain reference (tests/reference/mimo_v2_ref.py), through the
engine's own scheduler, block manager and runner at a tiny preset with
float32 activations: a window of 128 keys, rows of 128 and 256 tokens, so that
prompts of 1, 127, 128, 129 and 3 x 128 + 5 tokens put the window's edge
before, at and behind a chunk's, and decode carries every one of them over it.

What is compared is log-probabilities, not tokens: every generated token's
own log-probability and those of the 20 most likely tokens at its position,
as the served surface returns them (``logprobs=20``), against the
reference's log-softmax at the same ids.

TOL: both sides are float32 with full-precision products on the CPU; they
differ in the ORDER of sums (the grouped matmul over sorted pairs against
dense experts, batched rows, a prompt cut into chunks, the ring's blocks,
the sink merged by its statistics against one more column). Measured
largest difference over every case here: 3e-6 (logit spread 1.0). The
wrong models of ``test_the_tolerance_tells_a_wrong_model`` move the same
numbers by 0.01 to several units, so 1e-3 leaves both sides a decade of
room.
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
from production_stack_tpu.models import get_model, mimo_v2
from production_stack_tpu.models.config import (
    TINY_MIMO_V2,
    TINY_MIMO_V2_EP4,
    ModelConfig,
    resolve_model_config,
)
from production_stack_tpu.ops import attention as att
from production_stack_tpu.ops import moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tests", "reference"))
import mimo_v2_ref as ref  # noqa: E402

TOL = 1e-3
TOP = 20
CHUNK = 256         # make_engine's max_num_batched_tokens
W = TINY_MIMO_V2.sliding_window
LENGTHS = (1, 127, 128, 129, 3 * 128 + 5)
CUT = os.path.join(ROOT, "benchmarks", "chip", "configs", "mimo-v2.5-ep16")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def hf_config(mc: ModelConfig) -> dict:
    """The HF config.json keys the reference reads, from a ModelConfig."""
    return {
        "model_type": "mimo_v2",
        "hidden_size": mc.hidden_size, "num_hidden_layers": mc.num_layers,
        "intermediate_size": mc.intermediate_size,
        "moe_intermediate_size": mc.moe_intermediate_size,
        "vocab_size": mc.vocab_size,
        "num_attention_heads": mc.num_heads,
        "num_key_value_heads": mc.num_kv_heads,
        "swa_num_key_value_heads": mc.swa_num_kv_heads,
        "head_dim": mc.head_dim, "v_head_dim": mc.v_head_dim,
        "layernorm_epsilon": mc.rms_norm_eps, "rope_theta": mc.rope_theta,
        "swa_rope_theta": mc.swa_rope_theta,
        # floor(head_dim x factor) to whole pairs is rotary_dim.
        "partial_rotary_factor": (mc.rotary_dim + 0.5) / mc.head_dim,
        "hybrid_layer_pattern": [int(t == "sliding_attention")
                                 for t in mc.layer_types],
        "moe_layer_freq": [int(i >= mc.first_k_dense_replace)
                           for i in range(mc.num_layers)],
        "sliding_window": mc.sliding_window,
        "attention_value_scale": mc.attention_value_scale,
        "add_swa_attention_sink_bias": mc.swa_attention_sink,
        "n_routed_experts": mc.n_routed_experts,
        "num_experts_per_tok": mc.num_experts_per_tok,
        "norm_topk_prob": mc.norm_topk_prob,
        "routed_scaling_factor": mc.routed_scaling_factor,
        "ep_size": mc.ep_size, "ep_rank": mc.ep_rank,
    }


def make_engine(model="tiny-mimo-v2", **over) -> ServingEngine:
    cfg = dict(model=model, max_model_len=1024, num_kv_blocks=320,
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


def drive(eng) -> list:
    """Dispatches, synchronously, until nothing is left: schedule, run,
    apply."""
    batches = []
    while eng.scheduler.has_work():
        batch = eng.scheduler.schedule()
        tokens, lps = eng.runner.execute(batch, 0)
        eng.scheduler.update_after_step(batch, tokens, lps)
        batches.append(batch)
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
    return float(np.max(np.nan_to_num(np.abs(diffs), nan=np.inf)))


@pytest.fixture(scope="module")
def engine():
    """The engine's default path on the CPU: the full layers through
    ``window_attention`` over gathered history, every expert here."""
    eng = make_engine()
    assert eng.runner.attn_impl == "window" and not eng.runner.prefill_packs
    return eng


@pytest.fixture(scope="module")
def paged():
    """``--attn-impl paged`` (rows of 128 lanes for keys of 48 and values
    of 32): the full layers through the Pallas kernels in interpret mode
    over the pool, and a SHARE of the experts (rank 1 of 4)."""
    eng = make_engine("tiny-mimo-v2-ep4", attn_impl="paged")
    assert eng.runner.attn_impl == "paged" and eng.runner.prefill_reads_pool
    assert not eng.runner.prefill_packs      # the ring is a state a row
    return eng


@pytest.fixture(scope="module")
def served(engine):
    """Every listed context at once (two and more sequences a prefill
    dispatch, a row each), 12 tokens each: a prompt of 127 decodes over the
    window's edge, one of 389 is three chunks."""
    seqs = {n: add(engine, f"len{n}", prompt(n, n), 12) for n in LENGTHS}
    batches = drive(engine)
    return engine, seqs, batches


# ------------------------------------------------------ engine vs reference
@pytest.mark.parametrize("n", LENGTHS)
def test_engine_logprobs_match_the_reference(served, n):
    eng, seqs, batches = served
    assert worst(eng, seqs[n]) < TOL
    prefills = [b for b in batches if b.kind == "prefill"]
    # Several sequences a dispatch, and the longest prompt in three chunks.
    assert max(len(b.seqs) for b in prefills) >= 2
    assert sum(seqs[389] in b.seqs for b in prefills) >= 3


@pytest.mark.parametrize("n", LENGTHS)
def test_paged_share_logprobs_match_the_reference(paged, n):
    """The same through the pool, the Pallas kernels (interpret) and rank
    1 of 4's experts, the reference given the same share."""
    seq = add(paged, f"p{n}", prompt(n, 100 + n), 10)
    drive(paged)
    assert worst(paged, seq) < TOL
    assert paged.runner.fwd_stats_total["prefill"][
        "assignments_elsewhere"] > 0


def test_a_ring_slot_reused_by_a_second_sequence_starts_empty(served):
    """The slots of the first sequences go to new ones, shorter than a
    window: what the last owner left in a slot is never seen."""
    eng, seqs, _ = served
    held = {s.state_slot for s in seqs.values()}
    again = [add(eng, f"again{n}", prompt(n, 7 * n), 6) for n in (3, 40, 130)]
    drive(eng)
    assert {s.state_slot for s in again} <= held
    for seq in again:
        assert worst(eng, seq) < TOL


@pytest.mark.parametrize("wrong", ref.WRONG + ref.LOW_PRECISION)
def test_the_tolerance_tells_a_wrong_model(served, wrong):
    """Each plausible mistake (the sink left out or given a value, the
    bound off by one or gone, the values unscaled, other rope lanes, one
    theta for both kinds, another router, the share's experts misplaced)
    and each computation in too little precision moves the same numbers
    past TOL on the sequences that can see it."""
    eng, seqs, _ = served
    if wrong == "all_experts_here":
        pytest.skip("every expert IS here in this engine; the share's "
                    "engine shows it below")
    assert max(worst(eng, seqs[n], (wrong,)) for n in (129, 389)) > 10 * TOL


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


# ------------------------------------------------------------- the ring ops
def _dense_window_attention(q, k_all, v_all, pos_q, scale, sink, w):
    """Softmax with one more column: q [T, H, Dk] at positions pos_q over
    keys [S, Hkv, Dk] at positions 0..S-1."""
    h, hkv = q.shape[1], k_all.shape[1]
    k = jnp.repeat(k_all, h // hkv, axis=1)
    v = jnp.repeat(v_all, h // hkv, axis=1)
    s = jnp.einsum("thd,shd->hts", q, k) * scale
    j = jnp.arange(k.shape[0])
    dist = pos_q[:, None] - j[None, :]
    s = jnp.where(((dist >= 0) & (dist < w))[None], s, -jnp.inf)
    if sink is not None:
        s = jnp.concatenate(
            [s, jnp.broadcast_to(sink[:, None, None], s.shape[:2] + (1,))],
            -1)
        v = jnp.concatenate([v, jnp.zeros_like(v[:1])], 0)
    return jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1), v)


@pytest.mark.parametrize("start,t,live", [
    (0, 1, 1), (5, 1, 1), (127, 1, 1), (128, 1, 1), (300, 1, 1),
    (0, 16, 16), (0, 16, 9), (120, 16, 16), (130, 16, 3),
    (0, 32, 32), (17, 32, 32), (0, 64, 64), (64, 64, 50), (16, 48, 48),
])
def test_window_ring_attend_and_write_are_the_masked_softmax(start, t, live):
    """The ring's two statements at a window of 16 keys against a dense
    masked softmax with the sink as one more column: a decode step, chunks
    shorter than, equal to and several windows long (blocks), padded rows,
    starts before and behind the first window; then the ring's contents."""
    w, h, hkv, dk, dv = 16, 4, 2, 24, 8
    rng = np.random.default_rng(start * 131 + t)
    total = start + t
    k_all = jnp.asarray(rng.normal(size=(total, hkv, dk)), jnp.float32)
    v_all = jnp.asarray(rng.normal(size=(total, hkv, dv)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(t, h, dk)), jnp.float32)
    sink = jnp.asarray(rng.normal(size=(h,)), jnp.float32)
    # The ring before the chunk: position p in slot p mod w, junk in the
    # slots nothing was written to.
    ring_k = np.full((1, 1, hkv, w, dk), 1e3, np.float32)
    ring_v = np.full((1, 1, hkv, w, dv), 1e3, np.float32)
    for p in range(start):
        ring_k[0, 0, :, p % w] = k_all[p]
        ring_v[0, 0, :, p % w] = v_all[p]
    positions = (start + jnp.arange(t))[None]
    lens = jnp.array([live])
    got = att.window_ring_attend(
        q[None], k_all[None, start:], v_all[None, start:], positions, lens,
        jnp.asarray(ring_k[:, 0]), jnp.asarray(ring_v[:, 0]),
        scale=dk ** -0.5, sink=sink)
    want = _dense_window_attention(
        q[:live], k_all[:start + live], v_all[:start + live],
        positions[0, :live], dk ** -0.5, sink, w)
    np.testing.assert_allclose(got[0, :live], want, atol=2e-5)
    new_k, new_v = att.window_ring_write(
        (jnp.asarray(ring_k), jnp.asarray(ring_v)), jnp.int32(0),
        (k_all[None, start:], v_all[None, start:]), positions, lens)
    for p in range(start + live):
        if p >= start + live - w:
            np.testing.assert_array_equal(new_k[0, 0, :, p % w], k_all[p])
            np.testing.assert_array_equal(new_v[0, 0, :, p % w], v_all[p])
    if start + live < w:        # slots nothing reached keep what they held
        assert float(new_k[0, 0, 0, w - 1, 0]) == 1e3


# ---- a decode step in place in the carried rings (ops/pallas/window_ring.py)
RING_STEP_CASES = {
    # positions of the bucket's rows, which of them take a token
    # (six rows each: one program of the interpreted kernel a dtype)
    "below-the-window": ([0, 1, 5, 15, 16, 126], [1] * 6),
    "at-the-window": ([127, 128, 129, 143, 144, 255], [1] * 6),
    "several-wraps": ([256, 1000, 4095, 8191, 8192, 70001], [1] * 6),
    # A state slot's second sequence: its slots hold the first one's keys,
    # which no query of the new sequence may see.
    "a-slot-reused": ([0, 3, 40, 100, 17, 31], [1] * 6),
    "dead-rows-between": ([7, 300, 131, 64, 2000, 90], [1, 0, 1, 0, 0, 1]),
    "none-live": ([7, 300, 131, 64, 2, 1], [0] * 6),
    "one-live-last": ([7, 300, 131, 640, 3, 911], [0, 0, 0, 0, 0, 1]),
}


# (KV heads, queries a KV head, slots, key lanes, value lanes) of a ring:
RING_STEP_HEADS = {
    # MiMo-V2.5's: 8 queries a KV head, keys of 192 lanes in rows of 256
    "8-queries": (2, 8, 128, 192, 128),
    # Phi-4-mini-flash's packed differential rows: 4 queries a KV row (half
    # a sublane tile), an odd number of rows (3 for its 10), a window of
    # several tiles of slots, keys and values of 128 lanes
    "4-queries": (3, 4, 64, 128, 128),
}


def _ring_step_params():
    """Every case of the first head shape in both dtypes, and of the second
    a subset in bfloat16 (float32 adds nothing there: the layout of a
    head's queries is the dtype's only where it is 16 bits wide)."""
    second = ("below-the-window", "at-the-window", "several-wraps",
              "a-slot-reused", "dead-rows-between", "none-live")
    out = [(case, sink, dtype, "8-queries")
           for dtype in ("bfloat16", "float32")
           for sink in ("sink", "no-sink") for case in RING_STEP_CASES]
    out += [(case, sink, "bfloat16", "4-queries")
            for sink in ("sink", "no-sink") for case in second]
    return [pytest.param(*p, id="-".join(
        p if p[3] != "8-queries" else p[:3])) for p in out]


@pytest.mark.parametrize("case,sink,dtype,heads", _ring_step_params())
def test_ring_step_kernel_is_the_jnp_statement_in_place(case, sink, dtype,
                                                        heads):
    """``ring_step_in_place`` (interpreted) against ``window_ring_step_jnp``
    at the two published head shapes (``RING_STEP_HEADS``; few KV heads and
    three layers here): the attention of every live row within the dtype's
    rounding, the rings EQUAL bit for bit in every slot, every dead row and
    every other layer. Every slot holds finite junk before the step
    (another sequence's keys), so a slot the visibility should hide and
    does not shows. The second shape's window is half the first's, so its
    positions are taken at half theirs where the case means the window."""
    positions, live = RING_STEP_CASES[case]
    hkv, g, w, dk, dv = RING_STEP_HEADS[heads]
    if w < 128 and case in ("below-the-window", "at-the-window"):
        positions = [p // (128 // w) for p in positions]
    b, nl, at = len(positions), 3, 1
    dt = jnp.dtype(dtype)
    rng = np.random.default_rng(len(case) * 7 + sum(positions))

    def draw(*shape, scale=1.0):
        return jnp.asarray(rng.standard_normal(shape) * scale, dt)

    lanes = mimo_v2.ring_width(dk)
    ring_k = jnp.pad(draw(b, nl, hkv, w, dk, scale=3.0),
                     ((0, 0),) * 4 + ((0, lanes - dk),))
    ring_v = draw(b, nl, hkv, w, dv, scale=3.0)
    q, k, v = draw(b, 1, hkv * g, dk), draw(b, 1, hkv, dk), \
        draw(b, 1, hkv, dv)
    pos = jnp.asarray(positions, jnp.int32)[:, None]
    lens = jnp.asarray(live, jnp.int32)
    sinks = jnp.asarray(rng.standard_normal(hkv * g) * 2 + 2, jnp.float32) \
        if sink == "sink" else None
    args = ((ring_k, ring_v), jnp.int32(at), q, k, v, pos, lens)
    want_o, want_rings = att.window_ring_step_jnp(
        *args, scale=dk ** -0.5, sink=sinks)
    got_o, got_rings = att.window_ring_step(
        *args, scale=dk ** -0.5, sink=sinks, interpret=True)
    assert got_o.shape == want_o.shape == (b, 1, hkv * g, dv)
    assert got_o.dtype == dt
    alive = np.asarray(live, bool)
    if alive.any():
        np.testing.assert_allclose(
            np.asarray(got_o, np.float32)[alive],
            np.asarray(want_o, np.float32)[alive],
            atol=3e-2 if dtype == "bfloat16" else 2e-5)
    for got, want, old in zip(got_rings, want_rings, (ring_k, ring_v)):
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))
        # and what the statement says of them: one row a live row's head.
        changed = np.asarray(got != old).any(axis=(-1, -3))   # [B, NL, W]
        want_changed = np.zeros((b, nl, w), bool)
        for r in np.flatnonzero(alive):
            want_changed[r, at, positions[r] % w] = True
        np.testing.assert_array_equal(changed, want_changed)


def test_ring_step_is_the_kernel_only_where_the_rings_fit_it():
    """The choice of ``window_ring_step`` is by what it can see: rings of
    whole lane tiles whose KV heads have whole sublane tiles of queries or
    an even part of one, a row's heads ONE block of bounded bytes, and
    ``interpret`` hold the kernel (MiMo-V2.5's 8 queries over each of 8 KV
    heads, Phi-4-mini-flash's 4 over each of 10 rows of 512 slots, and both
    tiny presets as they fall: 2 over 2 of 128 slots, 4 over 2 of 64); rows
    of 192 lanes, a window that is no whole tile, 3 queries a KV head and a
    block past the byte bound the ``jnp`` form; and a program lowered for a
    CPU without the switch the ``jnp`` form too."""
    from production_stack_tpu.ops.pallas.window_ring import (
        BUFFER_BYTES,
        NUM_BUFS,
        supports_step_kernel,
        tile_rows,
    )

    def rings(hkv, w, dk, dv, dtype=jnp.bfloat16):
        return (jax.ShapeDtypeStruct((4, 2, hkv, w, dk), dtype),
                jax.ShapeDtypeStruct((4, 2, hkv, w, dv), dtype))

    assert tile_rows(jnp.bfloat16) == 16 and tile_rows(jnp.float32) == 8
    assert supports_step_kernel(*rings(8, 128, 256, 128), 64)
    assert supports_step_kernel(*rings(10, 512, 128, 128), 40)
    assert supports_step_kernel(*rings(2, 128, 128, 128), 4)
    assert supports_step_kernel(*rings(2, 64, 128, 128, jnp.float32), 8)
    assert not supports_step_kernel(*rings(8, 128, 192, 128), 64)
    assert not supports_step_kernel(*rings(8, 24, 256, 128), 64)
    assert not supports_step_kernel(*rings(2, 128, 128, 128), 6)
    # Twelve rows of 512 slots are 3 MiB a block, and NUM_BUFS of them past
    # the buffers' VMEM; in float32 ten are.
    assert NUM_BUFS * 10 * 512 * 256 * 2 <= BUFFER_BYTES \
        < NUM_BUFS * 12 * 512 * 256 * 2
    assert not supports_step_kernel(*rings(12, 512, 128, 128), 48)
    assert not supports_step_kernel(
        *rings(10, 512, 128, 128, jnp.float32), 40)

    def step(interpret):
        def fn(rk, rv, q, k, v, pos, lens):
            return att.window_ring_step(
                (rk, rv), 0, q, k, v, pos, lens, scale=1.0,
                interpret=interpret)
        b, (rk, rv) = 4, rings(2, 128, 256, 128)
        sds = jax.ShapeDtypeStruct
        return jax.jit(fn).lower(
            rk, rv, sds((b, 1, 16, 192), rk.dtype),
            sds((b, 1, 2, 192), rk.dtype), sds((b, 1, 2, 128), rk.dtype),
            sds((b, 1), jnp.int32), sds((b,), jnp.int32)).as_text(
                debug_info=True)

    assert att.ring_step_path(step(True)) == "pallas"
    assert att.ring_step_path(step(False)) == "xla"
    assert att.ring_step_path("HloModule jit__prefill_impl") is None


def test_forward_steps_through_the_kernel_as_through_the_jnp_statement():
    """A decode step of ``forward`` (T == 1) with the view's ``interpret``
    switch holds the ring's kernel where the rings fit it (here 8 queries a
    KV head, keys of 48 lanes in rows of 128) and comes out as the ``jnp``
    statement's: the hidden state of the live rows, every full layer's new
    K and V, and the rings bit for bit, a dead row's among them. The leading
    layer is a window layer here (traced outside the scan, no ``cond``) and
    the scan holds both kinds (two ``cond``s a layer, the step between
    them)."""
    mc = dataclasses.replace(
        TINY_MIMO_V2, num_heads=16, num_layers=4,
        layer_types=("sliding_attention", "full_attention",
                     "sliding_attention", "sliding_attention"))
    params = mimo_v2.init_params(mc, jax.random.PRNGKey(3), jnp.float32)
    b, n = 3, 140
    rng = np.random.default_rng(5)
    prompt_ids = jnp.asarray(rng.integers(0, mc.vocab_size, (b, n)))
    lens = jnp.asarray([n, 37, n - 12], jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), (b, n))
    state = mimo_v2.forward(params, mc, prompt_ids, positions, lens)[3]
    assert [s.shape for s in state] == [(b, 3, 2, W, 128)] * 2
    toks = jnp.asarray(rng.integers(0, mc.vocab_size, (b, 1)))
    step_lens = jnp.asarray([1, 0, 1], jnp.int32)
    outs = {}
    for interpret in (False, True):
        view = att.KVView(interpret=interpret)
        fn = jax.jit(lambda st, view=view: mimo_v2.forward(
            params, mc, toks, lens[:, None], step_lens, view, state=st))
        outs[interpret] = fn(state)
        text = fn.lower(state).as_text(debug_info=True)
        assert att.ring_step_path(text) == ("pallas" if interpret else "xla")
    (h0, k0, v0, st0, _), (h1, k1, v1, st1, _) = outs[False], outs[True]
    live = np.asarray(step_lens, bool)
    np.testing.assert_allclose(h1[live], h0[live], atol=2e-5)
    np.testing.assert_allclose(k1[:, :, live], k0[:, :, live], atol=2e-5)
    np.testing.assert_allclose(v1[:, :, live], v0[:, :, live], atol=2e-5)
    # Layer 0's ring is written from the same inputs on both paths: bit for
    # bit. Deeper layers' keys come from hidden states that differ by the
    # order of a float32 sum; their untouched slots and the dead row do not.
    for got, want, old in zip(st1, st0, state):
        np.testing.assert_array_equal(got[:, 0], want[:, 0])
        np.testing.assert_array_equal(got[1], old[1])
        np.testing.assert_allclose(got, want, atol=2e-5)
        changed = np.asarray(got != old).any(axis=(-1, -3))    # [B, NL, W]
        want_changed = np.zeros(changed.shape, bool)
        for r in np.flatnonzero(live):
            want_changed[r, :, int(lens[r]) % W] = True
        np.testing.assert_array_equal(changed, want_changed)


def test_the_sink_merged_by_statistics_is_one_more_softmax_column():
    rng = np.random.default_rng(0)
    s = jnp.asarray(rng.normal(size=(3, 5, 7)) * 4, jnp.float32)  # [B,H,S]
    v = jnp.asarray(rng.normal(size=(3, 7, 6)), jnp.float32)
    sink = jnp.asarray([-30.0, -1.0, 0.5, 4.0, 30.0], jnp.float32)
    m = s.max(-1)
    p = jnp.exp(s - m[..., None])
    l = p.sum(-1)
    out = jnp.einsum("bhs,bsd->bhd", p, v) / l[..., None]
    got = att.sink_merged(out, m, l, sink)
    full = jnp.concatenate(
        [s, jnp.broadcast_to(sink[None, :, None], (3, 5, 1))], -1)
    want = jnp.einsum("bhs,bsd->bhd", jax.nn.softmax(full, -1)[..., :-1], v)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # A sink far below every score changes nothing, one far above takes all.
    np.testing.assert_allclose(got[:, 0], out[:, 0], rtol=1e-5)
    assert float(jnp.abs(got[:, 4]).max()) < 1e-6


# ---------------------------------------------------------- the expert share
def test_pairs_of_experts_held_elsewhere_are_neither_computed_nor_counted():
    rng = np.random.default_rng(1)
    n, d, f, e, k = 6, 8, 4, 3, 2
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    wgu = jnp.asarray(rng.normal(size=(e, d, 2 * f)), jnp.float32)
    wd = jnp.asarray(rng.normal(size=(e, f, d)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, e, (n, k)), jnp.int32)
    w = jnp.asarray(rng.uniform(size=(n, k)), jnp.float32)
    valid = jnp.asarray([1, 1, 1, 1, 1, 0], bool)
    here = jnp.asarray(rng.integers(0, 2, (n, k)), bool)
    y, stats = moe.expert_ffn(x, idx, w, valid, wgu, wd, here=here)
    want = np.zeros((n, d), np.float32)
    for i in range(n):
        for j in range(k):
            if valid[i] and here[i, j]:
                h = x[i] @ wgu[idx[i, j]]
                want[i] += w[i, j] * np.asarray(
                    (jax.nn.silu(h[:f]) * h[f:]) @ wd[idx[i, j]])
    np.testing.assert_allclose(y, want, rtol=2e-5, atol=2e-5)
    counted = int((valid[:, None] & here).sum())
    assert stats.shape == (len(moe.STATS_EP),) and len(moe.STATS) == 4
    assert int(stats[0]) == counted
    assert int(stats[4]) == int(valid.sum()) * k - counted
    # Without ``here`` the four counters and every valid pair, as before.
    _, plain = moe.expert_ffn(x, idx, w, valid, wgu, wd)
    assert plain.shape == (4,) and int(plain[0]) == int(valid.sum()) * k


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """One sparse layer of the tiny model (16 experts, top-4) cut into 16
    shares of one expert: what every share's module computes for its own
    expert, summed, is the uncut reference's layer (there is no shared
    expert to count once)."""
    mc = TINY_MIMO_V2
    params = mimo_v2.init_params(mc, jax.random.PRNGKey(3), jnp.float32)
    sparse = params["layers"]["sparse"]
    layer = 1                                    # of the sparse stack
    lp = {k: v[layer] for k, v in sparse.items()}
    x = jnp.asarray(np.random.default_rng(2).normal(size=(1, 37, 128)),
                    jnp.float32)
    valid = jnp.ones((1, 37), bool)
    # The uncut reference: its FFN of the normed stream.
    u = ref.rms_norm(x[0], lp["ffn_norm"], mc.rms_norm_eps)
    with jax.default_matmul_precision("highest"):
        want, chosen = ref.sparse_ffn(hf_config(mc), lp, u)
    total = jnp.zeros_like(x)
    elsewhere = 0
    for rank in range(16):
        share = dataclasses.replace(mc, n_routed_experts=1, ep_size=16,
                                    ep_rank=rank)
        experts = tuple(lp[k][rank:rank + 1] for k in ("w_gate_up",
                                                       "we_down"))
        rest = {k: v for k, v in lp.items()
                if k not in ("w_gate_up", "we_down")}
        out, stats, idx = mimo_v2._sparse_ffn(
            share, x, rest, experts, 0, valid, False)
        np.testing.assert_array_equal(idx, chosen)   # one router, 16 wide
        total = total + (out - x)
        elsewhere += int(stats[4])
        assert int(stats[0]) == int((chosen == rank).sum())
    np.testing.assert_allclose(total[0], want, atol=2e-5)
    # Every pair is computed on exactly one of the sixteen chips.
    assert elsewhere == 15 * 37 * mc.num_experts_per_tok


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
