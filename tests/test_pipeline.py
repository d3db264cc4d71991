"""Pipelined engine loop (config.async_pipeline / config.overlap_dispatch):
issue-before-fetch with device-chained start tokens AND the two-slot
prefill/decode overlap must be SEMANTICALLY INVISIBLE — identical tokens,
finish reasons, stop handling, and usage as the strict loop, for every
sampling mode. (The pipeline hides the ~100 ms blocking device->host sync
per dispatch that dominated serving on the benched deployment; the overlap
slots keep decode running through prefill chunk trains and vice versa.)"""

import asyncio

import pytest

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.engine import ServingEngine
from production_stack_tpu.engine.sampling import SamplingParams

# The three loop modes every parity workload must agree across: strict
# issue-fetch-apply, the depth-2 pipeline without kind overlap (round 5),
# and the two-slot prefill/decode overlap (default).
LOOP_MODES = (
    ("strict", dict(async_pipeline=False, overlap_dispatch=False)),
    ("pipeline", dict(async_pipeline=True, overlap_dispatch=False)),
    ("overlap", dict(async_pipeline=True, overlap_dispatch=True)),
)


def _cfg(pipeline: bool, **over):
    base = dict(
        model="tiny-llama", max_model_len=512, num_kv_blocks=256,
        num_decode_steps=8, dtype="float32", max_num_seqs=4,
        max_num_batched_tokens=128, async_pipeline=pipeline,
    )
    base.update(over)
    return EngineConfig(**base)


async def _drive(engine):
    """A workload spanning the pipelined state machine's edges: concurrent
    decode trains, EOS-free greedy, seeded sampling, stop tokens mid-scan,
    multi-chunk prefill, and a shared prefix."""
    results = {}

    async def collect(key, prompt, sp):
        toks, text, reason = [], "", None
        async for o in engine.generate(prompt=prompt, sampling=sp):
            toks = o.token_ids
            text += o.text_delta
            reason = o.finish_reason
        results[key] = (toks, text, reason)

    await asyncio.gather(
        collect("a", "hello tpu", SamplingParams(
            temperature=0.0, max_tokens=21, ignore_eos=True)),
        collect("b", "other prompt", SamplingParams(
            temperature=0.9, seed=11, max_tokens=13, ignore_eos=True)),
        collect("c", "third one", SamplingParams(
            temperature=0.0, max_tokens=5, ignore_eos=True)),
    )
    # stop TOKEN mid-scan: learn the greedy continuation, then stop on its
    # 4th token.
    stop_tok = results["a"][0][3]
    await collect("stop", "hello tpu", SamplingParams(
        temperature=0.0, max_tokens=21, stop_token_ids=[stop_tok]))
    # multi-chunk long prompt (chunk budget 128 < prompt)
    await collect("long", " ".join(f"w{i}" for i in range(40)),
                  SamplingParams(temperature=0.0, max_tokens=7,
                                 ignore_eos=True))
    # shared prefix (prefix cache) + different tails
    base = "shared system prefix here. "
    await collect("p1", base + "tail one", SamplingParams(
        temperature=0.0, max_tokens=6, ignore_eos=True))
    await collect("p2", base + "tail two", SamplingParams(
        temperature=0.0, max_tokens=6, ignore_eos=True))
    return results


# Slow-marked: ~30s each on CPU (three full engine runs across loop modes /
# preemption under live dispatches). CI's "Pipeline parity + dispatch
# overlap" explicit step runs this whole file without the marker filter.
@pytest.mark.slow
@pytest.mark.asyncio
async def test_pipeline_matches_strict_loop():
    outs = {}
    for name, over in LOOP_MODES:
        engine = ServingEngine(_cfg(True, **over))
        await engine.start()
        try:
            outs[name] = await _drive(engine)
            stats = engine.stats()
            assert stats["num_requests_running"] == 0
            assert stats["num_requests_waiting"] == 0
        finally:
            await engine.stop()
    assert outs["overlap"] == outs["strict"]
    assert outs["pipeline"] == outs["strict"]
    toks, _, reason = outs["overlap"]["a"]
    assert len(toks) == 21 and reason == "length"
    assert outs["overlap"]["stop"][2] == "stop"


@pytest.mark.asyncio
async def test_pipeline_abort_mid_flight():
    """Aborting while a chained dispatch is in flight must free the row and
    leave the engine serving."""
    engine = ServingEngine(_cfg(True))
    await engine.start()
    try:
        agen = engine.generate(
            prompt="a long one", sampling=SamplingParams(
                temperature=0.0, max_tokens=400, ignore_eos=True),
            request_id="victim",
        )
        async for o in agen:
            if o.num_output_tokens >= 8:
                break
        await agen.aclose()   # client disconnect -> abort
        for _ in range(100):
            if engine.scheduler.num_running == 0:
                break
            await asyncio.sleep(0.05)
        assert engine.scheduler.num_running == 0
        # engine still serves correctly after the abort
        toks = []
        async for o in engine.generate(
            prompt="after abort", sampling=SamplingParams(
                temperature=0.0, max_tokens=6, ignore_eos=True),
        ):
            toks = o.token_ids
        assert len(toks) == 6
    finally:
        await engine.stop()


@pytest.mark.slow
@pytest.mark.asyncio
async def test_pipeline_preemption_discards_inflight():
    """Preemption under pool pressure while (up to two) dispatches are in
    flight: epochs invalidate the stale results and recompute reproduces
    the same tokens (deterministic seeds) — in every loop mode, including
    the two-slot overlap where the preemption can land while a decode AND
    a prefill are both outstanding."""
    async def run_all(engine):
        async def run(i):
            toks = []
            async for o in engine.generate(
                prompt=f"user {i} prompt text",
                sampling=SamplingParams(temperature=0.0, max_tokens=40,
                                        ignore_eos=True),
            ):
                toks = o.token_ids
            return toks
        return await asyncio.gather(*[run(i) for i in range(3)])

    pressured = {}
    for name, over in LOOP_MODES:
        cfg = _cfg(True, num_kv_blocks=10, max_model_len=256,
                   max_num_seqs=3, max_num_batched_tokens=64, **over)
        engine = ServingEngine(cfg)
        await engine.start()
        try:
            pressured[name] = await run_all(engine)
            if name == "overlap":
                assert engine.scheduler.num_preemptions_total > 0, \
                    "workload no longer exercises preemption"
        finally:
            await engine.stop()
        assert all(len(t) == 40 for t in pressured[name])

    # determinism across a run with vs without pressure
    engine2 = ServingEngine(_cfg(True, max_num_seqs=3, max_model_len=256,
                                 max_num_batched_tokens=64))
    await engine2.start()
    try:
        calm = await run_all(engine2)
    finally:
        await engine2.stop()
    for name, _ in LOOP_MODES:
        assert pressured[name] == calm, name


@pytest.mark.asyncio
async def test_prefill_arrives_mid_decode_parity():
    """A fresh prompt submitted while a fused decode scan is in flight:
    the overlap loop issues its prefill into the second slot instead of
    queuing it behind the scan — and the outputs (both streams') must be
    identical across strict/pipeline/overlap loops."""
    outs = {}
    for name, over in LOOP_MODES:
        engine = ServingEngine(_cfg(True, **over))
        await engine.start()
        try:
            results = {}

            async def collect(key, prompt, sp):
                toks = []
                async for o in engine.generate(prompt=prompt, sampling=sp):
                    toks = o.token_ids
                results[key] = toks

            long_task = asyncio.create_task(collect(
                "long", "steady decode stream goes on",
                SamplingParams(temperature=0.0, max_tokens=48,
                               ignore_eos=True),
            ))
            # Wait until the first stream is decoding (dispatches in
            # flight), then land a fresh prompt mid-decode.
            for _ in range(400):
                if engine.scheduler.num_running > 0:
                    break
                await asyncio.sleep(0.005)
            late_task = asyncio.create_task(collect(
                "late", "a late arriving prompt with some extra words",
                SamplingParams(temperature=0.9, seed=7, max_tokens=12,
                               ignore_eos=True),
            ))
            await asyncio.gather(long_task, late_task)
            outs[name] = results
        finally:
            await engine.stop()
    assert outs["overlap"] == outs["strict"]
    assert outs["pipeline"] == outs["strict"]
    assert len(outs["overlap"]["long"]) == 48
    assert len(outs["overlap"]["late"]) == 12


def _issues(recorder, request_id, kind):
    """The ``<kind>_issue`` events of one request, in time order."""
    events = recorder.get(request_id)["records"][0]["events"]
    return [ev for ev in events if ev["event"] == f"{kind}_issue"]


# A second model keeps recurrent state in slots beside its K/V blocks: the
# joined train runs behind the prefill that wrote the row's slot.
@pytest.mark.parametrize("model", ("tiny-llama", "tiny-granite-hybrid"))
@pytest.mark.asyncio
async def test_late_row_rides_the_train_behind_its_prefill(model):
    """A prompt of several chunks arrives while a stream decodes. The
    tokens are the strict loop's, and the dispatch timeline shows the
    row's first decode dispatch is the one issued right after the prefill
    that held its last chunk: it chained its start token from that
    prefill's device vector and sat out no train."""
    outs, engines = {}, {}
    for name, over in (LOOP_MODES[0], LOOP_MODES[2]):
        engine = engines[name] = ServingEngine(_cfg(
            True, model=model, max_num_batched_tokens=64, **over))
        await engine.start()
        try:
            results = {}

            async def collect(key, prompt, sp):
                toks = []
                async for o in engine.generate(prompt=prompt, sampling=sp,
                                               request_id=key):
                    toks = o.token_ids
                results[key] = toks

            steady = asyncio.create_task(collect(
                "steady", "steady decode stream goes on",
                SamplingParams(temperature=0.0, max_tokens=64,
                               ignore_eos=True),
            ))
            for _ in range(800):
                if engine.scheduler.num_running > 0:
                    break
                await asyncio.sleep(0.005)
            late = asyncio.create_task(collect(
                "late", " ".join(f"ctx{i}" for i in range(30)),
                SamplingParams(temperature=0.9, seed=7, max_tokens=12,
                               ignore_eos=True),
            ))
            await asyncio.gather(steady, late)
            outs[name] = results
        finally:
            await engine.stop()
    assert outs["overlap"] == outs["strict"]
    assert len(outs["overlap"]["steady"]) == 64
    assert len(outs["overlap"]["late"]) == 12

    rec = engines["overlap"].recorder
    chunks = _issues(rec, "late", "prefill")
    assert len(chunks) > 1, "the late prompt was one chunk"
    last_chunk = chunks[-1]["step"]
    first_decode = _issues(rec, "late", "decode")[0]
    decode_steps = sorted({ev["step"] for rid in ("steady", "late")
                           for ev in _issues(rec, rid, "decode")})
    assert first_decode["step"] == min(
        st for st in decode_steps if st > last_chunk)
    assert first_decode["joined"] == 1 and first_decode["rows"] == 2
    # Both hand-offs joined: the steady row's on an idle engine too (the
    # loop fills its second slot before it fetches the prefill).
    stats = engines["overlap"].stats()
    assert stats["decode_rows_first_total"] == 2
    assert stats["decode_rows_joined_total"] == 2
    # The strict loop applies every prefill before it schedules again.
    strict = engines["strict"].stats()
    assert strict["decode_rows_first_total"] == 2
    assert strict["decode_rows_joined_total"] == 0


@pytest.mark.asyncio
async def test_chaining_keeps_one_source_under_a_random_schedule():
    """Arrivals at random moments, aborts mid-stream and preemptions under
    a tight pool: no decode dispatch ever finds a row whose start token is
    neither on the host nor in a recent dispatch's vector, or rows that
    chain from two dispatches (``_issue_decode`` raises either as a
    RuntimeError and the loop would abort the batch). The requests that
    run to their end give the strict loop's tokens."""
    import random

    rng = random.Random(45)
    plan = []
    for i in range(14):
        plan.append(dict(
            key=f"r{i}", prompt=" ".join(
                f"w{rng.randrange(50)}" for _ in range(rng.randrange(2, 14))),
            max_tokens=rng.randrange(2, 34), delay=rng.random() * 0.25,
            seed=None if i % 3 else 100 + i,
            abort_after=rng.randrange(1, 6) if i % 5 == 4 else None,
        ))
    plan[3]["max_tokens"] = 1

    async def run_plan(engine, plan, paced):
        done = {}

        async def one(req):
            if paced:
                await asyncio.sleep(req["delay"])
            sp = SamplingParams(
                temperature=0.0 if req["seed"] is None else 0.8,
                seed=req["seed"], max_tokens=req["max_tokens"],
                ignore_eos=True)
            agen = engine.generate(prompt=req["prompt"], sampling=sp,
                                   request_id=req["key"])
            toks = []
            async for o in agen:
                toks = o.token_ids
                if req["abort_after"] and paced and \
                        o.num_output_tokens >= req["abort_after"]:
                    await agen.aclose()
                    return
            done[req["key"]] = toks

        await asyncio.gather(*(one(req) for req in plan))
        return done

    engine = ServingEngine(_cfg(
        True, num_kv_blocks=12, max_model_len=256, max_num_seqs=4,
        max_num_batched_tokens=64))
    raised = []
    issue = engine.runner.execute_async

    def checked_issue(batch, step):
        try:
            return issue(batch, step)
        except Exception as e:  # noqa: BLE001 — recorded, then as before
            raised.append(repr(e))
            raise

    engine.runner.execute_async = checked_issue
    await engine.start()
    try:
        served = await run_plan(engine, plan, paced=True)
        for _ in range(200):
            if not engine.scheduler.has_work():
                break
            await asyncio.sleep(0.02)
        stats = engine.stats()
        assert not engine.scheduler.has_work()
        assert engine.block_manager.num_used_blocks == 0
    finally:
        await engine.stop()
    assert raised == []
    assert stats["num_preemptions"] > 0, "the pool no longer forces one"
    assert stats["decode_rows_joined_total"] > 0
    assert stats["decode_rows_joined_total"] <= \
        stats["decode_rows_first_total"]
    finishing = [req for req in plan if not req["abort_after"]]
    assert sorted(served) == sorted(req["key"] for req in finishing)

    strict = ServingEngine(_cfg(
        False, max_model_len=256, max_num_seqs=4, max_num_batched_tokens=64,
        overlap_dispatch=False))
    await strict.start()
    try:
        calm = await run_plan(strict, finishing, paced=False)
    finally:
        await strict.stop()
    assert served == calm
    assert [len(served[req["key"]]) for req in finishing] == \
        [req["max_tokens"] for req in finishing]
