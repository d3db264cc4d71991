"""The KV pools are written in place (ISSUE 25, ops/kv_write.py).

Three tiers, all on the CPU backend at a tiny model:
  * the helpers against a numpy write, exactly;
  * every dispatch program {prefill, decode} x {paged bf16, paged int8,
    window, speculative}: each pool input aliased to its output, no ``copy``
    of a payload pool's shape, temporaries below one payload pool ("bf16"
    names the unquantized pool the benchmark's cells run; compute is
    float32 here, because XLA's CPU backend widens every bf16
    dynamic-update-slice to f32 and converts the whole buffer around it);
  * the same eight through a served request: each dispatch changes exactly
    the slots of its rows' positions (null block 0 apart), and what it
    wrote is what a plain forward over the request's tokens computes.

The chip guards the first property itself (``GET /debug/programs``, read by
chip_smoke.py): the CPU compiler only approximates the TPU's layout choices.
"""

import asyncio
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.engine import ServingEngine
from production_stack_tpu.engine.sampling import SamplingParams
from production_stack_tpu.ops.kv_write import (
    pool_copies,
    write_slabs,
    write_token_runs,
)
from production_stack_tpu.ops.quantization import dequantize_kv

BS = 16


# ---------------------------------------------------------------- helpers
def _numpy_runs(pool, new, tables, start, length, bs):
    """pool[:, :, slot(start[i] + j)] = new[:, :, i, j] for j < length[i]."""
    out = pool.copy()
    for i in range(new.shape[2]):
        for j in range(int(length[i])):
            pos = int(start[i]) + j
            if pos // bs >= tables.shape[1]:
                continue
            out[:, :, tables[i, pos // bs] * bs + pos % bs] = new[:, :, i, j]
    return out


@pytest.mark.parametrize("t", [1, 8, 16, 32, 40])
def test_token_runs_match_a_numpy_write(t):
    """Payload and scale pools together; starts at every kind of offset,
    a full row, a partial row, an empty row, a row whose run leaves its
    block table. Nothing else changes — the null block included."""
    rng = np.random.default_rng(t)
    nl, hkv, dh, mb = 2, 2, 8, 6
    nblocks = 1 + 4 * mb
    tables = (1 + rng.permutation(4 * mb).reshape(4, mb)).astype(np.int32)
    start = np.array([0, BS - 1, 2 * BS + 5, mb * BS - 3], np.int32)
    length = np.array([t, max(t - 3, 0), 0, t], np.int32)
    pool = rng.normal(size=(nl, hkv, nblocks * BS, dh)).astype(np.float32)
    scale = rng.normal(size=(nl, hkv, nblocks * BS)).astype(np.float32)
    new = rng.normal(size=(nl, hkv, 4, t, dh)).astype(np.float32)
    new_s = rng.normal(size=(nl, hkv, 4, t)).astype(np.float32)
    got, got_s = jax.jit(
        functools.partial(write_token_runs, block_size=BS)
    )((jnp.asarray(pool), jnp.asarray(scale)),
      (jnp.asarray(new), jnp.asarray(new_s)),
      jnp.asarray(tables), jnp.asarray(start), jnp.asarray(length))
    np.testing.assert_array_equal(
        np.asarray(got), _numpy_runs(pool, new, tables, start, length, BS))
    np.testing.assert_array_equal(
        np.asarray(got_s),
        _numpy_runs(scale, new_s, tables, start, length, BS))
    assert np.array_equal(np.asarray(got)[:, :, :BS], pool[:, :, :BS])


@pytest.mark.parametrize("keep", ["all", "mask"])
def test_slabs_match_a_numpy_write(keep):
    """Whole slabs in order (a repeated destination keeps the later one);
    with a mask, entries not kept retain the pool's content — a slot
    outside the pool, clipped, drops its row."""
    rng = np.random.default_rng(7)
    pool = rng.normal(size=(2, 3, 10, 4, 5)).astype(np.float32)
    src = rng.normal(size=(2, 3, 4, 1, 4, 5)).astype(np.float32)
    slots = np.array([3, 9, 3, 12], np.int32)
    mask = None if keep == "all" else (slots < 10)[:, None]
    dst = np.clip(slots, 0, 9)
    (got,) = jax.jit(functools.partial(write_slabs, width=1))(
        (jnp.asarray(pool),), (jnp.asarray(src),), dst_start=jnp.asarray(dst),
        src_row=jnp.arange(4), src_start=jnp.zeros((4,), jnp.int32),
        keep=None if mask is None else jnp.asarray(mask),
    )
    want = pool.copy()
    for i in range(4):
        if mask is None or mask[i, 0]:
            want[:, :, dst[i]] = src[:, :, i, 0]
    np.testing.assert_array_equal(np.asarray(got), want)


# ------------------------------------------------------- dispatch programs
COMMON = dict(
    max_model_len=256, block_size=BS, num_kv_blocks=1024, max_num_seqs=4,
    max_num_batched_tokens=64, num_decode_steps=8, enable_warmup=False,
    enable_prefix_caching=False, overlap_dispatch=False,
)
MODES = {
    "paged_bf16": dict(model="tiny-llama-128dh", attn_impl="paged"),
    "paged_int8": dict(model="tiny-llama-128dh", attn_impl="paged",
                       kv_cache_dtype="int8"),
    "window": dict(model="tiny-llama", attn_impl="window"),
    "speculative": dict(model="tiny-llama", attn_impl="window",
                        speculative_num_tokens=3,
                        speculative_model="tiny-llama"),
}
POOL_ARGS = ("kv_k", "kv_v", "kv_ks", "kv_vs", "spec_k", "spec_v",
             "spec_pos", "win_k_in", "win_v_in")


@functools.lru_cache(maxsize=None)
def _engine(mode: str, dtype: str) -> ServingEngine:
    return ServingEngine(EngineConfig(**COMMON, **MODES[mode], dtype=dtype))


def _lowered(runner, program):
    """The widest family of the kind; decode with the cached window where
    the path has one (its window buffers are donated and appended to)."""
    aparams = runner._abstract_params()
    if program == "decode":
        return runner._lower_decode(
            aparams, *runner.reachable_decode_families()[-1])
    return runner._lower_prefill(
        aparams, *runner.reachable_prefill_families()[-1])


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_program_updates_its_pools_in_place(program, mode):
    runner = _engine(mode, "float32").runner
    compiled = _lowered(runner, program).compile()
    text = compiled.as_text()
    # Every pool the program takes (more than a donation dummy) ...
    params = {}
    for m in re.finditer(
        r"= (\w+\[([\d,]*)\])\S* parameter\((\d+)\)[^\n]*"
        r"op_name=\"(\w+)\"", text
    ):
        shape, dims, number, name = m.groups()
        if name in POOL_ARGS and \
                np.prod([int(d) for d in dims.split(",") if d]) > 1:
            params[int(number)] = (name, shape)
    names = {name for name, _ in params.values()}
    assert {"kv_k", "kv_v"} <= names, names
    if mode == "paged_int8":
        assert {"kv_ks", "kv_vs"} <= names
    if mode == "speculative":
        assert {"spec_k", "spec_v", "spec_pos"} <= names
    # ... is aliased to an output,
    header = text[:text.index("\n\n")]
    aliased = {int(n) for n in re.findall(
        r"\{[\d, ]*\}: \((\d+), \{\}", header)}
    assert set(params) <= aliased, (params, aliased)
    # ... the payload pools never copied whole (the window buffers are
    # read by the layer scan in a layout of its own; the bf16 scale
    # sidecars are beyond the CPU compiler, which runs every bf16
    # dynamic-update-slice in f32 and converts the buffer around it —
    # the chip does not, and chip_smoke.py checks it there),
    payload = [runner.kv_k] + (
        [runner.spec_k] if mode == "speculative" else [])
    assert not pool_copies(text, payload)
    # ... and the program holds no temporary of a payload pool's size.
    pool_bytes = runner.kv_k.size * runner.kv_k.dtype.itemsize
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes
    # The audit chip_smoke.py reads on the chip says the same.
    # (It counts the scale sidecars too: see above for the CPU's.)
    if program == "decode":
        for line in runner.audit_pool_programs():
            assert line["pool_copies"] == 0 or mode == "paged_int8", line
            assert line["temp_bytes"] < line["pool_bytes"], line


async def test_debug_programs_reports_the_audit():
    """GET /debug/programs: the runner's audit, one line per program kind
    (what chip_smoke.py reads on the chip)."""
    from aiohttp.test_utils import TestClient, TestServer

    from production_stack_tpu.server.api_server import APIServer

    # An engine of its own: the app starts and stops the one it serves.
    client = TestClient(TestServer(APIServer(ServingEngine(EngineConfig(
        **COMMON, **MODES["window"], dtype="float32"))).build_app()))
    await client.start_server()
    try:
        resp = await client.get("/debug/programs")
        assert resp.status == 200
        programs = (await resp.json())["programs"]
    finally:
        await client.close()
    # Window path: decode with and without the cached window, prefill
    # with and without a history window.
    assert sorted((p["program"], p["family"][3]) for p in programs) == [
        ("decode", False), ("decode", True),
        ("prefill", False), ("prefill", True)]
    for p in programs:
        assert p["pool_copies"] == 0 and p["temp_bytes"] < p["pool_bytes"]
        assert p["alias_bytes"] >= 2 * p["pool_bytes"]


# ---------------------------------------------------------- served request
def _pools(runner):
    """Host copies of every pool (payload dequantized for comparison is
    the caller's business): waits for whatever is in flight."""
    out = {"k": np.asarray(runner.kv_k), "v": np.asarray(runner.kv_v)}
    if runner.kv_quantized:
        out["ks"] = np.asarray(runner.kv_k_scale.astype(jnp.float32))
        out["vs"] = np.asarray(runner.kv_v_scale.astype(jnp.float32))
    if runner.spec_n:
        out["spec_k"] = np.asarray(runner.spec_k)
        out["spec_pos"] = np.asarray(runner.spec_pos)
    return out


def _changed_slots(before, after):
    diff = before != after
    return set(np.flatnonzero(
        diff.reshape(diff.shape[0] * diff.shape[1], diff.shape[2], -1)
        .any(axis=(0, 2))
    ).tolist())


@functools.lru_cache(maxsize=None)
def _served(mode: str):
    """Three requests of different lengths, 6 tokens each against 8-step
    trains, served once per mode; every dispatch recorded with the pools
    before and after it."""
    eng = _engine(mode, "float32")
    runner = eng.runner
    records = []
    orig = runner.execute_async

    def spy(batch, step):
        before = _pools(runner)
        rows = [
            (s.request_id, list(s.block_ids),
             batch.chunk_starts[i] if batch.kind == "prefill"
             else s.num_computed_tokens)
            for i, s in enumerate(batch.seqs)
        ]
        handle = orig(batch, step)
        toks, _ = handle.fetch()
        counts = (batch.chunk_lens if batch.kind == "prefill"
                  else [len(t) for t in toks])
        records.append({
            "kind": batch.kind, "before": before, "after": _pools(runner),
            "rows": [(rid, blocks, a, a + n)
                     for (rid, blocks, a), n in zip(rows, counts)],
            "spec_slots": [runner._spec_slots.get(rid) for rid, *_ in rows]
            if runner.spec_n else [],
        })
        return handle

    rng = np.random.default_rng(3)
    prompts = {f"r{i}": rng.integers(1, runner.model_config.vocab_size,
                                     n).tolist()
               for i, n in enumerate((21, 37, 16))}
    tokens = {}

    async def one(rid):
        async for o in eng.generate(
            prompt_token_ids=prompts[rid], request_id=rid,
            sampling=SamplingParams(temperature=0.0, max_tokens=6,
                                    ignore_eos=True),
        ):
            tokens[rid] = prompts[rid] + list(o.token_ids)

    async def serve():
        await eng.start()
        try:
            runner.execute_async = spy
            await asyncio.gather(*[one(rid) for rid in prompts])
        finally:
            runner.execute_async = orig
            await eng.stop()

    asyncio.run(serve())
    return runner, records, tokens


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_dispatch_writes_exactly_its_rows(program, mode):
    """The first dispatch of ``program``'s kind: the slots that changed
    are the slots of its rows' positions (a decode train of 8 steps holds
    out-of-budget steps and a padding row); their content is what a plain
    forward over the request's tokens computes; dropped draft-ring rows
    write nothing."""
    runner, records, tokens = _served(mode)
    mc = runner.model_config
    rec = next(r for r in records if r["kind"] == program)
    before, after, rows = rec["before"], rec["after"], rec["rows"]
    if program == "decode":
        assert any(b - a < runner.config.num_decode_steps
                   for _, _, a, b in rows), rows

    @jax.jit
    def ref_kv(toks):
        t = toks.shape[0]
        _, k, v = runner._forward(
            runner.params, mc, toks[None],
            jnp.arange(t, dtype=jnp.int32)[None],
            jnp.full((1,), t, jnp.int32),
        )
        return k[:, :, 0], v[:, :, 0]              # [L, Hkv, T, Dh]

    expected = set()
    for rid, blocks, a, b in rows:
        slots = [blocks[p // BS] * BS + p % BS for p in range(a, b)]
        expected |= set(slots)
        toks = tokens[rid][:b]
        toks = toks + [0] * (64 - len(toks))
        for name, ref in zip("kv", ref_kv(jnp.asarray(toks, jnp.int32))):
            got = after[name][:, :, slots]
            tol = 1e-4
            if runner.kv_quantized:
                scale = after[name + "s"][:, :, slots]
                got = np.asarray(dequantize_kv(
                    jnp.asarray(got), jnp.asarray(scale), jnp.float32))
                # A quantization step, and what an int8 history does to
                # the later layer's keys and values.
                tol = 4 * float(scale.max())
            np.testing.assert_allclose(
                got, np.asarray(ref)[:, :, a:b], atol=tol, rtol=1e-4)
    for name in ("k", "v", "ks", "vs"):
        if name in before:
            b4, aft = before[name], after[name]
            if b4.ndim == 3:
                b4, aft = b4[..., None], aft[..., None]
            # Block 0 is the reserved null block: never read, so what
            # lands there is nobody's business (nothing does, today).
            changed = {s for s in _changed_slots(b4, aft) if s >= BS}
            assert changed == expected, (name, sorted(changed ^ expected))
    if runner.spec_n:
        # Draft rings: only the batch's live slots change; the padding
        # row's out-of-range slot drops its row.
        live = {s for s in rec["spec_slots"] if s is not None}
        assert live
        assert _changed_slots(
            before["spec_k"], after["spec_k"]) <= live
        assert set(np.flatnonzero(
            (before["spec_pos"] != after["spec_pos"]).any(axis=1)
        ).tolist()) <= live
