"""Two-slot prefill/decode dispatch overlap (config.overlap_dispatch).

The tentpole claim — the executor no longer serializes the two dispatch
kinds — is asserted on the flight recorder's dispatch events (the
``*_issue`` / ``*_fetch`` events every request's timeline carries, joined
over the requests by ``step``): a prefill ISSUE
must land between a decode's ISSUE and its FETCH (and, with a chunked
prefill train against live decode streams, a decode issue between a
prefill's issue and fetch — Sarathi-style stall-free batching in both
directions). Scheduler-level invariants (dual-batch rounds, the
fresh-prefill-rows-wait-for-apply rule that keeps token chaining
single-source) and the overlap telemetry are covered alongside.
"""

import asyncio

import pytest

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.engine import ServingEngine
from production_stack_tpu.engine.kv_cache import BlockPoolManager
from production_stack_tpu.engine.sampling import SamplingParams
from production_stack_tpu.engine.scheduler import Scheduler, Sequence

def _dispatch_timeline(recorder, request_ids):
    """(issue|fetch, prefill|decode, step) of every dispatch the requests
    rode, in time order; a dispatch that carried several of them counts
    once."""
    seen = {}
    for rid in request_ids:
        for ev in recorder.get(rid)["records"][0]["events"]:
            kind, _, what = ev["event"].partition("_")
            if kind in ("prefill", "decode") and what in ("issue", "fetch"):
                seen.setdefault((what, kind, ev["step"]), ev["t"])
    return [key for key, _ in sorted(seen.items(), key=lambda kv: kv[1])]


def _overlap_windows(events, outer_kind, inner_kind):
    """Count ``inner_kind`` issues landing between an ``outer_kind``
    dispatch's issue and its fetch."""
    n = 0
    for i, (ev, kind, step) in enumerate(events):
        if ev != "issue" or kind != outer_kind:
            continue
        for ev2, kind2, step2 in events[i + 1:]:
            if ev2 == "fetch" and kind2 == outer_kind and step2 == step:
                break
            if ev2 == "issue" and kind2 == inner_kind:
                n += 1
                break
    return n


@pytest.mark.asyncio
async def test_dispatch_timeline_shows_prefill_decode_overlap():
    """A fresh prompt arriving mid-decode gets its prefill ISSUED while a
    fused decode scan is still in flight; decode keeps issuing through the
    newcomer's multi-chunk prefill train."""
    engine = ServingEngine(EngineConfig(
        model="tiny-llama", max_model_len=512, num_kv_blocks=256,
        num_decode_steps=8, dtype="float32", max_num_seqs=4,
        max_num_batched_tokens=64,
    ))
    await engine.start()
    try:
        done = {}

        async def collect(key, prompt, max_tokens):
            toks = []
            async for o in engine.generate(
                prompt=prompt,
                sampling=SamplingParams(temperature=0.0,
                                        max_tokens=max_tokens,
                                        ignore_eos=True),
                request_id=key,
            ):
                toks = o.token_ids
            done[key] = toks

        steady = asyncio.create_task(
            collect("steady", "a steady stream keeps decoding", 96)
        )
        for _ in range(800):
            if engine.scheduler.num_running > 0:
                break
            await asyncio.sleep(0.005)
        # ~300 tokens under the byte-level fallback tokenizer: a 64-token
        # chunk budget makes this a multi-chunk prefill train.
        late = asyncio.create_task(collect(
            "late", " ".join(f"ctx{i}" for i in range(48)), 8
        ))
        await asyncio.gather(steady, late)
    finally:
        await engine.stop()
    assert len(done["steady"]) == 96 and len(done["late"]) == 8

    events = _dispatch_timeline(engine.recorder, ("steady", "late"))
    assert events, "the flight recorder holds no dispatch events"
    # The two kinds genuinely interleave in flight:
    assert _overlap_windows(events, "decode", "prefill") > 0, (
        "no prefill was issued between a decode issue and its fetch:\n"
        + "\n".join(map(str, events))
    )
    assert _overlap_windows(events, "prefill", "decode") > 0, (
        "decode stalled for the whole prefill chunk train:\n"
        + "\n".join(map(str, events))
    )
    # Fetches are strictly in issue order (FIFO slots).
    issued, fetched = [], []
    for ev, _, step in events:
        (issued if ev == "issue" else fetched).append(step)
    assert fetched == sorted(fetched) and set(fetched) == set(issued)
    # ...and the overlap is visible in the engine telemetry too.
    stats = engine.stats()
    assert stats["dispatch_overlap_ratio"] > 0
    assert stats["decode_dispatches_total"] > 0
    assert stats["prefill_dispatches_total"] > 0


def _mk_scheduler(num_blocks=128):
    cfg = EngineConfig(model="tiny-llama", max_model_len=256,
                       num_decode_steps=8, max_num_seqs=4,
                       max_num_batched_tokens=64)
    bm = BlockPoolManager(num_blocks, cfg.block_size, True)
    return cfg, bm, Scheduler(cfg, bm)


def test_dual_batch_round_produces_both_kinds():
    """One scheduling round: a decode batch (prefer_decode, slot 1) AND a
    prefill batch (slot 2) from the same scheduler state."""
    cfg, bm, sched = _mk_scheduler()
    running = Sequence("run", [1, 2, 3], SamplingParams(max_tokens=50))
    sched.add_sequence(running)
    first = sched.schedule()
    assert first.kind == "prefill"
    sched.advance_at_issue(first)
    sched.apply_results(first, [[7]])

    sched.add_sequence(Sequence("new", [4, 5, 6],
                                SamplingParams(max_tokens=50)))
    decode = sched.schedule(prefer_decode=True)
    assert decode is not None and decode.kind == "decode"
    assert [s.request_id for s in decode.seqs] == ["run"]
    sched.advance_at_issue(decode)
    prefill = sched.schedule()
    assert prefill is not None and prefill.kind == "prefill"
    assert [s.request_id for s in prefill.seqs] == ["new"]


def test_fresh_prefill_rows_wait_for_apply():
    """A row whose final prefill chunk is issued but unapplied must not
    join a decode batch (its start token exists only in that dispatch's
    device buffer — single-source chaining invariant); it becomes
    decode-eligible at apply."""
    cfg, bm, sched = _mk_scheduler()
    seq = Sequence("fresh", [1, 2, 3], SamplingParams(max_tokens=50))
    sched.add_sequence(seq)
    batch = sched.schedule()
    assert batch.kind == "prefill"
    sched.advance_at_issue(batch)
    assert seq.pending_prefill_apply and seq in sched.running
    assert sched._schedule_decode() is None
    sched.apply_results(batch, [[9]])
    assert not seq.pending_prefill_apply
    decode = sched._schedule_decode()
    assert decode is not None and decode.seqs == [seq]


def test_preempt_clears_pending_prefill_flag():
    cfg, bm, sched = _mk_scheduler()
    seq = Sequence("victim", [1, 2, 3], SamplingParams(max_tokens=50))
    sched.add_sequence(seq)
    batch = sched.schedule()
    sched.advance_at_issue(batch)
    assert seq.pending_prefill_apply
    sched._preempt(seq)
    assert not seq.pending_prefill_apply
    # The stale batch's apply must NOT clear the NEW generation's flag.
    batch2 = sched.schedule()
    assert batch2.kind == "prefill" and batch2.seqs == [seq]
    sched.advance_at_issue(batch2)
    assert seq.pending_prefill_apply
    sched.apply_results(batch, [[9]])          # stale epoch: ignored
    assert seq.pending_prefill_apply
    sched.apply_results(batch2, [[9]])
    assert not seq.pending_prefill_apply


@pytest.mark.asyncio
async def test_overlap_metrics_exported():
    """The /metrics exposition carries the dispatch-pipeline telemetry."""
    from production_stack_tpu.server.metrics import render_engine_metrics

    engine = ServingEngine(EngineConfig(
        model="tiny-llama", max_model_len=256, num_kv_blocks=64,
        num_decode_steps=8, dtype="float32", max_num_seqs=2,
        max_num_batched_tokens=64,
    ))
    await engine.start()
    try:
        async for _ in engine.generate(
            prompt="metrics probe",
            sampling=SamplingParams(temperature=0.0, max_tokens=6,
                                    ignore_eos=True),
        ):
            pass
    finally:
        await engine.stop()
    text = render_engine_metrics(engine, "m")
    for series in ("pstpu:decode_dispatches_total",
                   "pstpu:prefill_dispatches_total",
                   "pstpu:dispatch_overlap_ratio",
                   "pstpu:dispatch_gap_seconds_total"):
        assert f'{series}{{model_name="m"}}' in text, series
    assert engine.stats()["decode_dispatches_total"] > 0
