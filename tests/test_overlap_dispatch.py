"""Two-slot prefill/decode dispatch overlap (config.overlap_dispatch).

The tentpole claim — the executor no longer serializes the two dispatch
kinds — is asserted on the flight recorder's dispatch events (the
``*_issue`` / ``*_fetch`` events every request's timeline carries, joined
over the requests by ``step``): a prefill ISSUE
must land between a decode's ISSUE and its FETCH (and, with a chunked
prefill train against live decode streams, a decode issue between a
prefill's issue and fetch — Sarathi-style stall-free batching in both
directions). Scheduler-level invariants (dual-batch rounds, the hand-off
of a row whose last prompt chunk is still in flight to the decode issued
right behind it) and the overlap telemetry are covered alongside.
"""

import asyncio

import pytest

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.engine import ServingEngine
from production_stack_tpu.engine.kv_cache import BlockPoolManager
from production_stack_tpu.engine.sampling import SamplingParams
from production_stack_tpu.engine.scheduler import (
    INTERACTIVE_DECODE_STEPS,
    Scheduler,
    Sequence,
    SequenceStatus,
)

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


def _mk_scheduler(num_blocks=128, **over):
    cfg = EngineConfig(**{**dict(
        model="tiny-llama", max_model_len=256, num_decode_steps=8,
        max_num_seqs=4, max_num_batched_tokens=64), **over})
    bm = BlockPoolManager(num_blocks, cfg.block_size, True)
    return cfg, bm, Scheduler(cfg, bm)


def _issue_prefill(sched, *seqs):
    """Add ``seqs`` and ISSUE the prefill that takes them (their one and
    last chunk): advanced, not applied."""
    for seq in seqs:
        sched.add_sequence(seq)
    batch = sched.schedule()
    assert batch.kind == "prefill" and batch.seqs == list(seqs)
    sched.advance_at_issue(batch)
    assert all(batch.finals)
    return batch


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


def test_fresh_prefill_rows_join_the_decode_behind_their_prefill():
    """A row whose final prefill chunk is issued but unapplied is taken by
    the next decode batch (the runner chains its start token from that
    prefill's device vector; the loop's depth keeps that the batch's one
    source). A ``max_tokens = 1`` row is spent by the token in flight and
    is left out."""
    cfg, bm, sched = _mk_scheduler(max_num_batched_tokens=512)
    seq = Sequence("fresh", [1, 2, 3], SamplingParams(max_tokens=50))
    one = Sequence("one", [4, 5, 6], SamplingParams(max_tokens=1))
    batch = _issue_prefill(sched, seq, one)
    for s in (seq, one):
        assert s.pending_prefill_apply and s.awaits_first_decode
        assert s in sched.running and s.inflight_steps == 1
    decode = sched._schedule_decode()
    assert decode is not None and decode.seqs == [seq]
    sched.advance_at_issue(decode)
    # Taken once: the flag that marks the hand-off is down, the token in
    # flight still marks where the first token is.
    assert not seq.awaits_first_decode and seq.pending_prefill_apply
    assert seq.inflight_steps == 1 + decode.decode_steps[0]
    sched.apply_results(batch, [[9], [8]])
    assert not seq.pending_prefill_apply and seq.output_token_ids == [9]
    assert one.status is SequenceStatus.FINISHED_LENGTH
    toks = list(range(20, 20 + decode.decode_steps[0]))
    produced, accepted = sched.apply_results(decode, [toks])
    assert produced == [seq] and accepted == len(toks)
    assert seq.output_token_ids == [9] + toks and seq.inflight_steps == 0


@pytest.mark.parametrize("in_flight", (True, False))
def test_interactive_cap_spares_a_joined_row(in_flight):
    """The cap is for a row that would get its FIRST token from the scan.
    A row joined behind its in-flight prefill gets it at that prefill's
    apply, so the train keeps its tier's length; a row with no token
    produced and none in flight still cuts it short."""
    cfg, bm, sched = _mk_scheduler(num_decode_steps=32,
                                   max_num_batched_tokens=512)
    seqs = [Sequence(f"r{i}", [1 + i, 2, 3], SamplingParams(max_tokens=50))
            for i in range(3)]
    _issue_prefill(sched, *seqs)
    if not in_flight:
        # What no path of the loop leaves behind today: a running row with
        # no token on the host and none on the device.
        seqs[1].inflight_steps = 0
        seqs[1].pending_prefill_apply = False
    decode = sched._schedule_decode()
    assert decode.seqs == seqs
    assert decode.num_steps == (32 if in_flight else INTERACTIVE_DECODE_STEPS)
    assert INTERACTIVE_DECODE_STEPS < 32


def test_first_token_eos_discards_the_joined_trains_tokens():
    """The first token ends the request while the train that took the row
    is in flight: the row finishes at the prefill's apply, its blocks go
    back ONCE, and the train's tokens for it are dropped at its apply."""
    cfg, bm, sched = _mk_scheduler(max_num_batched_tokens=512)
    free0 = bm.num_free_blocks
    seq = Sequence("ends", [1, 2, 3], SamplingParams(max_tokens=50),
                   eos_token_id=9)
    other = Sequence("goes-on", [4, 5, 6], SamplingParams(max_tokens=50),
                     eos_token_id=9)
    batch = _issue_prefill(sched, seq, other)
    decode = sched._schedule_decode()
    assert decode.seqs == [seq, other]
    sched.advance_at_issue(decode)
    produced, accepted = sched.apply_results(batch, [[9], [7]])
    assert accepted == 2 and set(map(id, produced)) == {id(seq), id(other)}
    assert seq.status is SequenceStatus.FINISHED_STOPPED
    assert seq not in sched.running and seq.block_ids == []
    held = len(other.block_ids)
    assert bm.num_free_blocks == free0 - held
    k = decode.decode_steps[1]
    produced, accepted = sched.apply_results(
        decode, [list(range(30, 30 + decode.decode_steps[0])),
                 list(range(40, 40 + k))])
    assert produced == [other] and accepted == k
    assert seq.output_token_ids == [9]
    assert other.output_token_ids == [7] + list(range(40, 40 + k))
    assert bm.num_free_blocks == free0 - len(other.block_ids)


@pytest.mark.parametrize("decode_in_flight", (False, True))
def test_preempt_clears_pending_prefill_flag(decode_in_flight):
    """Preemption between a prefill's issue and its apply, with or without
    the decode that took the row in flight behind it: the epoch drops both
    stale results, and the flags are the new generation's."""
    cfg, bm, sched = _mk_scheduler()
    seq = Sequence("victim", [1, 2, 3], SamplingParams(max_tokens=50))
    batch = _issue_prefill(sched, seq)
    assert seq.pending_prefill_apply
    stale_decode = None
    if decode_in_flight:
        stale_decode = sched._schedule_decode()
        assert stale_decode.seqs == [seq]
        sched.advance_at_issue(stale_decode)
    sched._preempt(seq)
    assert not seq.pending_prefill_apply and not seq.awaits_first_decode
    assert seq.inflight_steps == 0 and seq.num_computed_tokens == 0
    # The stale batch's apply must NOT clear the NEW generation's flag.
    batch2 = sched.schedule()
    assert batch2.kind == "prefill" and batch2.seqs == [seq]
    sched.advance_at_issue(batch2)
    assert seq.pending_prefill_apply
    sched.apply_results(batch, [[9]])          # stale epoch: ignored
    assert seq.pending_prefill_apply and seq.output_token_ids == []
    if decode_in_flight:
        # The new generation rides the train behind ITS prefill; the old
        # train's tokens fall to the epoch check like the old prefill's.
        decode2 = sched._schedule_decode()
        assert decode2.seqs == [seq]
        sched.advance_at_issue(decode2)
        assert sched.apply_results(
            stale_decode, [[5] * stale_decode.decode_steps[0]]) == ([], 0)
        assert seq.inflight_steps == 1 + decode2.decode_steps[0]
    sched.apply_results(batch2, [[9]])
    assert not seq.pending_prefill_apply and seq.output_token_ids == [9]
    if decode_in_flight:
        toks = list(range(60, 60 + decode2.decode_steps[0]))
        sched.apply_results(decode2, [toks])
        assert seq.output_token_ids == [9] + toks
        assert seq.inflight_steps == 0


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
                   "pstpu:dispatch_gap_seconds_total",
                   "pstpu:decode_rows_first_total",
                   "pstpu:decode_rows_joined_total"):
        assert f'{series}{{model_name="m"}}' in text, series
    stats = engine.stats()
    assert stats["decode_dispatches_total"] > 0
    # One request on an idle engine: the loop fills its second slot with
    # the row's first train before it fetches the prefill.
    assert stats["decode_rows_first_total"] == 1
    assert stats["decode_rows_joined_total"] == 1
