"""The engine loop's own instrumentation (docs/OBSERVABILITY.md, "Loop
spans"): the six phase totals tile the loop's wall time, decode work is
counted where it happens, the new series reach /metrics,
a capture armed through DeviceProfiler holds the ``pstpu.*`` spans with
their ``step`` and a clock anchor and no Python frame, stopping it does
not block the event loop, and ``--load-format`` / ``--seed`` reach
``EngineConfig`` only when given. CPU, tiny-llama."""

import asyncio
import glob
import json
import os
import time

import pytest
from aiohttp.test_utils import TestClient, TestServer

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.engine import ServingEngine
from production_stack_tpu.engine.flight_recorder import (
    LOOP_COUNTERS,
    LoopSpans,
    annotated_issue,
    compile_clock,
)
from production_stack_tpu.engine.kv_cache import BlockPoolManager
from production_stack_tpu.engine.sampling import SamplingParams
from production_stack_tpu.engine.scheduler import (
    PREFILL_STOPS,
    ScheduledBatch,
    Scheduler,
    Sequence,
)
from production_stack_tpu.server.api_server import (
    APIServer,
    build_engine_from_args,
    parse_args,
)

NEW_SERIES = (
    *(f"pstpu:{name}" for name in LOOP_COUNTERS.values()),
    "pstpu:decode_steps_total", "pstpu:decode_row_steps_total",
    "pstpu:decode_row_steps_wasted_total", "pstpu:http_ingress_seconds",
    "pstpu:first_chunk_emit_seconds",
    # What a dispatch says at issue (PR 36).
    "pstpu:decode_steps_empty_total", "pstpu:prefill_tokens_issued_total",
    "pstpu:prefill_tokens_padded_total", "pstpu:prefill_rows_issued_total",
    "pstpu:prefill_left_waiting_total",
    *(f"pstpu:prefill_stop_{stop}_total" for stop in PREFILL_STOPS),
    "pstpu:serving_compiles_total", "pstpu:serving_compile_seconds_total",
    # The hand-off from prefill to decode (PR 45).
    "pstpu:decode_rows_first_total", "pstpu:decode_rows_joined_total",
    # What holds the HBM (PR 49).
    "pstpu:hbm_resident_bytes", "pstpu:hbm_bytes_in_use",
    "pstpu:hbm_peak_bytes", "pstpu:hbm_limit_bytes",
    "pstpu:hbm_reserved_bytes",
    "pstpu:hbm_peak_rises_total", "pstpu:hbm_peak_rise_bytes_total",
)
HBM_ATTRS = {"hbm", "hbm_peak", "hbm_limit", "hbm_reserved"}


def _cfg(**over):
    base = dict(model="tiny-llama", max_model_len=256, num_kv_blocks=128,
                num_decode_steps=8, dtype="float32", max_num_seqs=4,
                max_num_batched_tokens=64)
    base.update(over)
    return EngineConfig(**base)


async def _run(engine, prompt, max_tokens, request_id=None):
    toks = []
    async for out in engine.generate(
        prompt=prompt, request_id=request_id,
        sampling=SamplingParams(temperature=0.0, max_tokens=max_tokens,
                                ignore_eos=True),
    ):
        toks = out.token_ids
    return toks


# ------------------------------------------------------------ loop spans
def test_loop_span_adds_its_time_to_its_phase_only():
    spans = LoopSpans()
    with spans("pstpu.schedule"):
        time.sleep(0.01)
    with spans("pstpu.fetch", step=3, kind="decode", sync=1):
        time.sleep(0.02)
    assert set(spans.seconds) == set(LOOP_COUNTERS)
    assert 0.009 < spans.seconds["schedule"] < 0.05
    assert 0.019 < spans.seconds["fetch"] < 0.06
    assert sum(spans.seconds.values()) == pytest.approx(
        spans.seconds["schedule"] + spans.seconds["fetch"])
    assert spans.counters()["loop_fetch_wait_seconds_total"] == \
        spans.seconds["fetch"]
    assert set(spans.counters()) == set(LOOP_COUNTERS.values())


async def test_phase_totals_tile_the_loops_wall_time():
    """Every second the loop lives is in exactly one phase: over a short
    run with work and idle stretches the six totals sum to the loop's wall
    time to within 2%."""
    engine = ServingEngine(_cfg())
    await engine.start()
    began = time.perf_counter()
    before = dict(engine.loop_spans.seconds)
    try:
        await asyncio.gather(
            _run(engine, "a steady stream keeps decoding", 40),
            _run(engine, "and a second one joins it", 24),
        )
        await asyncio.sleep(0.3)          # an idle stretch
        await _run(engine, "then one more", 12)
    finally:
        # The loop ends inside stop(): read the totals after it, the wall
        # time up to the same point.
        await engine.stop()
    wall = time.perf_counter() - began
    spent = {k: v - before[k] for k, v in engine.loop_spans.seconds.items()}
    assert all(v >= 0 for v in spent.values())
    assert spent["issue"] > 0 and spent["fetch"] > 0 and spent["idle"] > 0
    assert sum(spent.values()) == pytest.approx(wall, rel=0.02)


# ---------------------------------------------------------- decode counts
@pytest.mark.parametrize("decode_loop,steps,row_steps,wasted,empty", [
    ("scan", 8 + 8 + 8, 16 + 8 + 8, 4 + 0 + 5, 0 + 0 + 5),
    ("while", 8 + 8 + 3, 16 + 8 + 3, 4 + 0 + 0, 0 + 0 + 0),
])
async def test_decode_counts_equal_hand_counts_on_a_scripted_run(
        decode_loop, steps, row_steps, wasted, empty):
    """Two rows join one decode train of K=8 (two rows are the 8-step
    tier). Row A may produce 5 tokens: its prefill gives 1, so its decode
    budget is 4 and it stops mid-train by max_tokens. Row B (20 = 1 + 8 +
    8 + 3) rides three trains. By hand:

      train 1: rows A,B  8 steps -> 16 row-steps, 4 + 8 delivered, 4 wasted
      train 2: row  B    8 steps ->  8 row-steps, 8 delivered
      train 3: row  B    budget 3: the scan runs all 8 steps (5 wasted),
               the while loop stops at the largest budget (3 steps, none)

    A step is EMPTY when no row of its dispatch used it: none in train 1
    (row B ran all 8 though row A wasted 4 row-steps), the scan's 5 in
    train 3.
    """
    engine = ServingEngine(_cfg(pipeline_depth=1, decode_loop=decode_loop))
    await engine.start()
    try:
        a, b = await asyncio.gather(
            _run(engine, "row a", 5, "a"), _run(engine, "row b", 20, "b"))
    finally:
        await engine.stop()
    assert len(a) == 5 and len(b) == 20
    trains = [(e["rows"], e["k"]) for e in
              engine.recorder.get("b")["records"][0]["events"]
              if e["event"] == "decode_issue"]
    assert trains == [(2, 8), (1, 8), (1, 8)]
    stats = engine.stats()
    assert stats["decode_dispatches_total"] == 3
    assert stats["decode_steps_total"] == steps
    assert stats["decode_row_steps_total"] == row_steps
    assert stats["decode_row_steps_wasted_total"] == wasted
    assert stats["decode_steps_empty_total"] == empty
    # Row-steps less wasted row-steps: the tokens decode delivered, all
    # tokens less each request's first.
    assert row_steps - wasted == stats["generation_tokens_total"] - 2


async def test_an_aborted_rows_undelivered_steps_count_as_wasted():
    """A row aborted while its train is in flight discards the train's
    tokens (epoch check): every row-step of that train is wasted, and the
    identity row-steps - wasted = delivered still holds."""
    engine = ServingEngine(_cfg(pipeline_depth=1))
    await engine.start()
    got = []

    async def consume():
        async for out in engine.generate(
            prompt="to be aborted", request_id="victim",
            sampling=SamplingParams(temperature=0.0, max_tokens=200,
                                    ignore_eos=True),
        ):
            got.append(len(out.token_ids))
            if len(out.token_ids) >= 9:       # first decode train applied
                engine.abort("victim")

    try:
        await consume()
        for _ in range(200):
            if not engine.scheduler.has_work():
                break
            await asyncio.sleep(0.01)
    finally:
        await engine.stop()
    stats = engine.stats()
    delivered = stats["generation_tokens_total"] - 1
    assert stats["decode_row_steps_total"] % 8 == 0
    assert stats["decode_row_steps_wasted_total"] == \
        stats["decode_row_steps_total"] - delivered
    # The abort lands between trains or under one in flight; either way no
    # delivered token is counted as wasted and no wasted one as delivered.
    assert stats["decode_row_steps_wasted_total"] in (0, 8)
    assert delivered == got[-1] - 1


# -------------------------------------------------- empty steps, by dispatch
def _applied(rows, num_steps, budgets, took):
    """A decode batch as ``apply_results`` leaves it: ``took[i]`` tokens
    delivered to row i."""
    batch = ScheduledBatch(
        kind="decode", seqs=[object()] * rows, num_steps=num_steps,
        decode_steps=list(budgets))
    batch.delivered_max = max(took, default=0)
    return batch, sum(took)


@pytest.mark.parametrize("decode_loop", ["scan", "while"])
def test_empty_steps_times_rows_never_pass_wasted_row_steps(decode_loop):
    """Dispatch by dispatch: a step no row used wastes a row-step in every
    row. A row that ran the whole train leaves none empty; a failed
    dispatch (nothing delivered) leaves every step empty; a speculative
    dispatch that delivers more than a token a step reads 0, not less."""
    engine = ServingEngine(_cfg(decode_loop=decode_loop))
    cases = [
        (3, 8, (8, 8, 8), (8, 4, 1)),      # one row ran the train
        (3, 8, (8, 8, 8), (5, 4, 1)),      # every row stopped early
        (2, 8, (3, 2), (3, 2)),            # short budgets
        (4, 32, (32, 32, 32, 32), (0, 0, 0, 0)),   # failed / all aborted
        (1, 8, (8,), (20,)),               # speculation: > 1 token a step
    ]
    for rows, k, budgets, took in cases:
        before = engine.stats()
        engine._count_decode(*_applied(rows, k, budgets, took))
        after = engine.stats()
        d = {key: after[key] - before[key] for key in (
            "decode_steps_total", "decode_row_steps_total",
            "decode_row_steps_wasted_total", "decode_steps_empty_total")}
        steps = k if decode_loop == "scan" else min(k, max(budgets))
        assert d["decode_steps_total"] == steps
        assert d["decode_steps_empty_total"] == max(0, steps - max(took))
        assert 0 <= d["decode_steps_empty_total"] <= steps
        assert d["decode_steps_empty_total"] * rows <= \
            d["decode_row_steps_wasted_total"]


async def test_empty_steps_identity_holds_over_a_real_run():
    """The same, on what a run's own dispatches count: every call of
    ``_count_decode`` is wrapped and its deltas checked."""
    engine = ServingEngine(_cfg(pipeline_depth=1))
    seen = []
    inner = engine._count_decode

    def checked(batch, delivered):
        before = (engine.decode_steps_empty_total,
                  engine.decode_row_steps_wasted_total)
        inner(batch, delivered)
        empty = engine.decode_steps_empty_total - before[0]
        wasted = engine.decode_row_steps_wasted_total - before[1]
        assert 0 <= empty * len(batch.seqs) <= wasted
        seen.append((len(batch.seqs), empty, wasted))

    engine._count_decode = checked
    await engine.start()
    try:
        await asyncio.gather(*(
            _run(engine, f"row {i} of a mixed batch", n)
            for i, n in enumerate((3, 7, 12, 21))))
    finally:
        await engine.stop()
    assert len(seen) >= 3
    assert any(rows > 1 for rows, _, _ in seen)
    stats = engine.stats()
    assert stats["decode_steps_empty_total"] == sum(e for _, e, _ in seen)
    assert stats["decode_steps_empty_total"] <= stats["decode_steps_total"]


# ------------------------------------------------ what a prefill carried
async def test_prefill_counters_say_what_the_dispatches_carried():
    """Over a run with a repeated prefix and prompts longer than a chunk:
    issued tokens are the prompt tokens less the prefix hits (a prompt
    token is prefilled once or served from the cache), never more than
    the padded rectangles; every prefill dispatch either emptied the
    queue or names one limit; and the ``prefill_issue`` events carry the
    chunks the counter summed."""
    # 256 tokens hold two rows at the chunk floor (128): no candidate is
    # cut by the budget and allocated again, which would count its prefix
    # hits twice.
    engine = ServingEngine(_cfg(max_num_seqs=8, max_prefill_seqs=2,
                                block_size=4, max_num_batched_tokens=256))
    await engine.start()
    try:
        shared = "a shared system prompt that fills some blocks. "
        await _run(engine, shared + "first", 4, "first")
        await asyncio.gather(*(
            _run(engine, shared + f"question {i} " + "x" * (30 * i), 6,
                 f"q{i}")
            for i in range(5)))
    finally:
        await engine.stop()
    stats = engine.stats()
    hits = stats["prefix_cache_hits"]
    assert hits > 0
    assert stats["prefill_tokens_issued_total"] == \
        stats["prompt_tokens_total"] - hits
    assert 0 < stats["prefill_tokens_issued_total"] <= \
        stats["prefill_tokens_padded_total"]
    assert stats["prefill_dispatches_total"] <= \
        stats["prefill_rows_issued_total"] <= \
        2 * stats["prefill_dispatches_total"]
    # The events' chunks are what the counter summed (each row's chunk in
    # each dispatch once).
    chunks = sum(
        e["chunk"]
        for rid in ["first"] + [f"q{i}" for i in range(5)]
        for e in engine.recorder.get(rid)["records"][0]["events"]
        if e["event"] == "prefill_issue")
    assert chunks == stats["prefill_tokens_issued_total"]
    stops = {stop: stats[f"prefill_stop_{stop}_total"]
             for stop in PREFILL_STOPS}
    blocked = sum(engine.scheduler.prefill_blocked.values())
    named = sum(stops.values()) - blocked
    assert 0 < named <= stats["prefill_dispatches_total"]
    assert named == sum(engine.prefill_stops.values())
    # Five prompts arrive together and a dispatch takes two rows: the row
    # cap stops admission and leaves requests waiting.
    assert stops["rows"] > 0
    assert stats["prefill_left_waiting_total"] >= stops["rows"]


def _stub_device_memory(monkeypatch, limit=16 * 10 ** 9):
    """An allocator for the CPU, which reports none: every read finds 4 KB
    more in use than the last, so the peak rises at every read."""
    from production_stack_tpu.engine.runner import ModelRunner

    in_use = [10 ** 9]

    def device_memory(self):
        in_use[0] += 4096
        return [{"bytes_in_use": in_use[0], "peak_bytes_in_use": in_use[0],
                 "bytes_limit": limit} for _ in self.mesh.devices.flat]

    monkeypatch.setattr(ModelRunner, "device_memory", device_memory)


async def test_issue_span_carries_what_the_counters_count(tmp_path,
                                                          monkeypatch):
    """A capture's prefill ``pstpu.issue`` spans hold ``tokens``,
    ``prog_rows``, ``prog_t``, ``left`` and ``stop``; their sums over the
    capture are the counters' deltas over it. Where the devices report
    their memory (a stub here), the two executor-side spans say what the
    allocator read."""
    from production_stack_tpu.profiling import DeviceProfiler
    from production_stack_tpu.utils import prefill_rectangle

    profiler = DeviceProfiler()
    if not profiler.available():
        pytest.skip("jax.profiler unavailable in this image")
    _stub_device_memory(monkeypatch)
    engine = ServingEngine(_cfg(max_prefill_seqs=2))
    await engine.start()
    try:
        await _run(engine, "warm the shapes", 4)
        before = engine.stats()
        await profiler.arm(30.0, trace_dir=str(tmp_path))
        await asyncio.gather(*(
            _run(engine, f"prompt {i} " + "y" * (25 * i), 5)
            for i in range(4)))
        await profiler.close()
        after = engine.stats()
    finally:
        await engine.stop()
    events, _ = _capture_events(str(tmp_path))
    prefills = [s for n, s in events
                if n == "pstpu.issue" and s["kind"] == "prefill"]
    assert prefills
    for span in prefills:
        assert set(span) >= {"step", "rows", "k", "tokens", "prog_rows",
                             "prog_t", "left", "stop"}
        assert (int(span["prog_rows"]), int(span["prog_t"])) == \
            prefill_rectangle(int(span["rows"]), int(span["k"]),
                              engine.config)
        assert 0 < int(span["tokens"]) <= \
            int(span["prog_rows"]) * int(span["prog_t"])
        assert span["stop"] in ("none",) + PREFILL_STOPS
        assert (span["stop"] == "none") == (int(span["left"]) == 0)
    decodes = [s for n, s in events
               if n == "pstpu.issue" and s["kind"] == "decode"]
    assert decodes and all("tokens" not in s for s in decodes)
    for key, attr in (("prefill_tokens_issued_total", "tokens"),
                      ("prefill_rows_issued_total", "rows"),
                      ("prefill_left_waiting_total", "left")):
        assert after[key] - before[key] == \
            sum(int(s[attr]) for s in prefills), key
    assert after["prefill_tokens_padded_total"] \
        - before["prefill_tokens_padded_total"] == sum(
            int(s["prog_rows"]) * int(s["prog_t"]) for s in prefills)
    # The allocator's reading on the executor-side spans: after every
    # enqueue (with what residents and programs in flight explain) and
    # after every sync, rising as the stub does, step by step.
    enqueues = [s for n, s in events if n == "pstpu.issue.enqueue"]
    syncs = [s for n, s in events if n == "pstpu.fetch.sync"]
    assert enqueues and syncs
    assert all(set(s) >= HBM_ATTRS | {"hbm_explained", "step"}
               for s in enqueues)
    assert all(set(s) >= HBM_ATTRS | {"step"} and "hbm_explained" not in s
               for s in syncs)
    for span in enqueues + syncs:
        assert int(span["hbm"]) == int(span["hbm_peak"]) > 10 ** 9
        assert int(span["hbm_limit"]) == 16 * 10 ** 9
    assert all(0 < int(s["hbm_explained"]) <= int(s["hbm"])
               for s in enqueues)
    by_step = sorted(enqueues, key=lambda s: int(s["step"]))
    assert [int(s["hbm"]) for s in by_step] == \
        sorted(int(s["hbm"]) for s in by_step)
    # The loop's own spans (event-loop side) carry none of it.
    assert not [k for n, s in events if n in ("pstpu.issue", "pstpu.fetch")
                for k in s if k.startswith("hbm")]


# ------------------------------------------------ what stopped admission
def _sched(blocks=256, slots=0, window=None, **over):
    base = dict(model="tiny-llama", max_model_len=2048, block_size=16,
                max_num_seqs=8, max_num_batched_tokens=1024,
                max_prefill_seqs=8)
    base.update(over)
    cfg = EngineConfig(**base)
    bm = BlockPoolManager(blocks, cfg.block_size,
                          enable_prefix_caching=False,
                          num_state_slots=slots)
    return Scheduler(cfg, bm, prefill_window_budget=window)


def _waiting(sched, n, tokens):
    for i in range(len(sched.seqs), len(sched.seqs) + n):
        sched.add_sequence(Sequence(f"s{i}", [1 + i] * tokens,
                                    SamplingParams()))


def test_stop_none_where_the_pass_empties_the_queue():
    sched = _sched()
    _waiting(sched, 3, 40)
    batch = sched._try_schedule_prefill()
    assert (len(batch.seqs), batch.stop, batch.left_waiting) == (3, "none", 0)
    assert not any(sched.prefill_blocked.values())


def test_stop_rows_at_the_row_cap():
    """8 waiting prompts and --max-prefill-seqs 4."""
    sched = _sched(max_prefill_seqs=4)
    _waiting(sched, 8, 40)
    batch = sched._try_schedule_prefill()
    assert (len(batch.seqs), batch.stop, batch.left_waiting) == (4, "rows", 4)
    sched.advance_at_issue(batch)
    batch = sched._try_schedule_prefill()
    # The second pass takes the other four and empties the queue: the cap
    # was reached but nothing is left behind it.
    assert (len(batch.seqs), batch.stop, batch.left_waiting) == (4, "none", 0)


def test_stop_seqs_where_the_running_set_has_less_room_than_the_row_cap():
    sched = _sched(max_num_seqs=4, max_prefill_seqs=4)
    _waiting(sched, 6, 40)
    first = sched._try_schedule_prefill()
    assert (len(first.seqs), first.stop, first.left_waiting) == (4, "rows", 2)
    sched.advance_at_issue(first)          # 4 running: --max-num-seqs
    assert sched._try_schedule_prefill() is None
    assert sched.prefill_blocked == {**dict.fromkeys(PREFILL_STOPS, 0),
                                     "seqs": 1}
    sched.finish("s0", sched.seqs["s0"].status.FINISHED_ABORTED)
    sched.finish("s1", sched.seqs["s1"].status.FINISHED_ABORTED)
    sched.finish("s2", sched.seqs["s2"].status.FINISHED_ABORTED)
    _waiting(sched, 3, 40)
    batch = sched._try_schedule_prefill()  # room for 3 of the 5 waiting
    assert (len(batch.seqs), batch.stop, batch.left_waiting) == (3, "seqs", 2)


def test_stop_tokens_where_the_budget_cuts_rows():
    """Three 1000-token prompts under a 1024-token budget: no rectangle of
    1024 tokens carries more than one whole prompt does ([1, 1024]: 1000;
    [4, 256]: 768; [8, 128]: 384), so the area bound takes one row of the
    three gathered."""
    sched = _sched()
    _waiting(sched, 3, 1000)
    batch = sched._try_schedule_prefill()
    assert (len(batch.seqs), batch.stop, batch.left_waiting) == \
        (1, "tokens", 2)
    assert batch.chunk_lens == [1000]


def test_stop_window_where_a_gathered_window_cuts_rows():
    """Two prompts on their second chunk (history to gather) under a
    window budget that holds one row's window only: [2, 128] would carry
    both tails, and the window budget passes it over."""
    sched = _sched(max_num_batched_tokens=256, window=8)
    _waiting(sched, 2, 200)
    first = sched._try_schedule_prefill()
    # [2, 128] carries 256 tokens of the two, [1, 256] 200 of the first.
    assert (len(first.seqs), first.chunk_lens, first.stop) == \
        (2, [128, 128], "none")
    sched.advance_at_issue(first)
    batch = sched._try_schedule_prefill()
    assert (len(batch.seqs), batch.stop, batch.left_waiting) == \
        (1, "window", 1)
    assert batch.chunk_lens == [72]


def test_stop_blocks_where_a_prompt_finds_no_blocks():
    """A pool of 8 usable blocks: the first 100-token prompt takes 7."""
    sched = _sched(blocks=9)
    _waiting(sched, 2, 100)
    batch = sched._try_schedule_prefill()
    assert (len(batch.seqs), batch.stop, batch.left_waiting) == \
        (1, "blocks", 1)
    sched.advance_at_issue(batch)
    # The pool is still held: a pass that schedules nothing says so.
    assert sched._try_schedule_prefill() is None
    assert sched.prefill_blocked["blocks"] == 1
    assert sum(sched.prefill_blocked.values()) == 1


def test_stop_slots_where_every_state_slot_is_held():
    sched = _sched(slots=2)
    _waiting(sched, 3, 40)
    batch = sched._try_schedule_prefill()
    assert (len(batch.seqs), batch.stop, batch.left_waiting) == \
        (2, "slots", 1)
    assert all(s.state_slot for s in batch.seqs)
    sched.advance_at_issue(batch)
    assert sched._try_schedule_prefill() is None
    assert sched.prefill_blocked["slots"] == 1


def test_decode_role_counts_only_what_a_prefill_could_take():
    """Disagg-conforming requests on a decode-role engine are no prefill's
    to take: they neither stop a pass nor count as left waiting."""
    sched = _sched(role="decode", max_prefill_seqs=1)
    _waiting(sched, 2, 40)
    fallback = Sequence("fb", [7] * 40, SamplingParams())
    fallback.disagg_fallback = True
    sched.add_sequence(fallback)
    batch = sched._try_schedule_prefill()
    assert [s.request_id for s in batch.seqs] == ["fb"]
    assert (batch.stop, batch.left_waiting) == ("none", 0)


def test_the_rectangle_is_one_function_for_scheduler_runner_and_counters():
    """``utils.prefill_rectangle`` over the shapes the benchmark's cells
    dispatch, and the three callers name it."""
    import inspect

    from production_stack_tpu.engine import engine, runner, scheduler
    from production_stack_tpu.utils import prefill_rectangle

    cfg = EngineConfig(model="tiny-llama", max_model_len=4096,
                       max_num_seqs=64, max_num_batched_tokens=2048)
    assert prefill_rectangle(1, 320, cfg) == (1, 512)
    assert prefill_rectangle(1, 17, cfg) == (1, 128)     # the floor
    assert prefill_rectangle(1, 2048, cfg) == (1, 2048)
    assert prefill_rectangle(2, 256, cfg) == (8, 256)    # stragglers pad
    assert prefill_rectangle(8, 256, cfg) == (8, 256)
    assert prefill_rectangle(9, 128, cfg) == (16, 128)
    with pytest.raises(ValueError, match="no prefill rectangle"):
        prefill_rectangle(4, 512, cfg)     # [8, 512] is twice the budget
    for fn, name in (
            (scheduler.Scheduler._try_schedule_prefill,
             "prefill_rectangles("),
            (runner.ModelRunner._issue_prefill, "prefill_rectangle("),
            (runner.ModelRunner.reachable_prefill_families,
             "prefill_rectangles("),
            (engine.ServingEngine._run_loop, "prefill_rectangle(")):
        assert name in inspect.getsource(fn), fn.__qualname__


# ------------------------------------------------- compiles while serving
def test_compile_clock_moves_on_a_first_call_and_not_on_the_next():
    import jax
    import jax.numpy as jnp

    clock = compile_clock()
    assert compile_clock() is clock           # one listener a process

    @jax.jit
    def fresh(x):
        return x * 3 + 1

    x = jnp.arange(7, dtype=jnp.float32)
    count, seconds = clock.reading()
    out, compiled = annotated_issue(1, fresh, x)
    assert out.shape == (7,)
    assert clock.count == count + 1 and clock.seconds > seconds
    assert compiled == pytest.approx(clock.seconds - seconds, abs=1e-5)
    count, seconds = clock.reading()
    out, compiled = annotated_issue(2, fresh, x)
    assert (clock.count, clock.seconds, compiled) == (count, seconds, 0.0)


async def test_a_compile_after_warm_up_shows_on_metrics_and_the_timeline():
    """What an engine exports is what the clock read past its own start:
    warm-up's compiles are not in it; a program first called while serving
    is, and the request whose dispatch held it says so."""
    import jax

    engine = ServingEngine(_cfg())
    await engine.start()
    try:
        assert engine.stats()["serving_compiles_total"] == 0
        assert engine.stats()["serving_compile_seconds_total"] == 0.0
        # No warm-up in this config: the first request's dispatches
        # compile their programs while serving.
        await _run(engine, "first use compiles", 4, "cold")
        cold = engine.stats()
        assert cold["serving_compiles_total"] >= 2        # prefill, decode
        assert cold["serving_compile_seconds_total"] > 0
        await _run(engine, "second use does not", 4, "warm")
        warm = engine.stats()
    finally:
        await engine.stop()
    assert warm["serving_compiles_total"] == cold["serving_compiles_total"]
    events = {rid: engine.recorder.get(rid)["records"][0]["events"]
              for rid in ("cold", "warm")}
    stalled = [e for e in events["cold"] if e.get("compiled")]
    assert stalled and {e["event"] for e in stalled} <= {
        "prefill_issue", "decode_issue"}
    assert sum(e["compiled"] for e in stalled) <= \
        cold["serving_compile_seconds_total"] + 1e-3
    assert not [e for e in events["warm"] if "compiled" in e]
    del jax


# ------------------------------------------------------------- /metrics
async def test_new_series_are_rendered_and_pass_the_lint():
    from production_stack_tpu.server.metrics import render_engine_metrics
    from tools.pstpu_lint.core import default_project_root
    from tools.pstpu_lint.rules.metrics_drift import check_metrics

    engine = ServingEngine(_cfg())
    await engine.start()
    try:
        await _run(engine, "count me", 12)
    finally:
        await engine.stop()
    text = render_engine_metrics(engine, "tiny-llama")
    for series in NEW_SERIES:
        assert f"# TYPE {series} " in text, series
    sample = {ln.split(" ")[0].split("{")[0]: float(ln.rsplit(" ", 1)[1])
              for ln in text.splitlines() if ln and not ln.startswith("#")
              and "_bucket" not in ln}
    # 12 = 1 + 8 + 3: two trains of one row, the scan runs all 8 steps.
    assert sample["pstpu:decode_steps_total"] == 16
    assert sample["pstpu:decode_row_steps_total"] == 16
    assert sample["pstpu:decode_row_steps_wasted_total"] == 5
    assert sample["pstpu:loop_fetch_wait_seconds_total"] > 0
    # The ledger's residents a holder (the kv holder is the pool), the
    # reading and the rises: 0 on a backend that reports no memory.
    assert ('pstpu:hbm_resident_bytes{model_name="tiny-llama",holder="kv",'
            f'device="cpu:0"}} {engine.runner.kv_pool_bytes}') in text
    assert 'holder="weights",device="cpu:0"} ' in text
    assert 'pstpu:hbm_peak_rises_total{model_name="tiny-llama",' \
        'phase="serving"} 0' in text
    assert sample["pstpu:hbm_bytes_in_use"] == 0
    # PL004: renderer, registry and docs tables agree (the whole tree).
    assert check_metrics(default_project_root()) == []


async def test_http_surface_histograms_observe_each_request_once():
    server = APIServer(ServingEngine(_cfg(attn_impl="xla")))
    client = TestClient(TestServer(server.build_app()))
    await client.start_server()
    try:
        resp = await client.post("/v1/chat/completions", json={
            "model": "tiny-llama", "stream": True, "max_tokens": 6,
            "temperature": 0, "ignore_eos": True,
            "messages": [{"role": "user", "content": "hello"}],
        })
        assert resp.status == 200
        await resp.read()
        resp = await client.post("/v1/completions", json={
            "model": "tiny-llama", "prompt": "abc", "max_tokens": 3,
            "temperature": 0, "ignore_eos": True,
        })
        assert resp.status == 200
        surface = server.engine.http_surface
        assert surface.ingress.count == 2
        assert surface.first_chunk_emit.count == 2
        assert 0 < surface.ingress.sum < 5
        assert 0 <= surface.first_chunk_emit.sum < 60
        version = await (await client.get("/version")).json()
        assert set(version["engine"]["peak_bytes_in_use"]) <= \
            set(version["engine"]["bytes_in_use"]) or \
            version["engine"]["peak_bytes_in_use"] == {}
        assert "peak_bytes_in_use" in version["engine"]
    finally:
        await client.close()


async def test_debug_memory_answers_inside_the_debug_gate_only(monkeypatch):
    """``GET /debug/memory``: the ledger, the events and every device's
    reading now; a plain 404 under ``--no-debug-endpoints`` (PL012)."""
    _stub_device_memory(monkeypatch)
    for debug in (True, False):
        server = APIServer(ServingEngine(_cfg(debug_endpoints=debug)))
        client = TestClient(TestServer(server.build_app()))
        await client.start_server()
        try:
            resp = await client.post("/v1/completions", json={
                "model": "tiny-llama", "prompt": "abc", "max_tokens": 3,
                "temperature": 0, "ignore_eos": True,
            })
            assert resp.status == 200
            resp = await client.get("/debug/memory")
            if not debug:
                assert resp.status == 404
                continue
            body = await resp.json()
        finally:
            await client.close()
        assert resp.status == 200
        assert body["phase"] == "serving" and body["device"] == "cpu:0"
        assert list(body["residents"]) == [
            "weights", "kv", "state", "spec", "lora", "other"]
        assert sum(body["residents"].values()) == \
            body["built"]["bytes_in_use"]
        # What the programs first run under traffic loaded is resident
        # since (no warm-up here: every program was).
        assert body["resident_bytes"] == body["built"]["bytes_in_use"] + sum(
            p["held_bytes"] for p in body["programs"].values())
        assert body["residents_by_device"] == {"cpu:0": body["residents"]}
        assert set(body["now"]) == {"cpu:0"}
        assert body["now"]["cpu:0"]["bytes_in_use"] > body["resident_bytes"]
        # The first read (here the build's: no warm-up) is the first
        # event and nobody's rise; every other is one dispatch's.
        assert body["events"][0]["at"] == "boot"
        assert body["rises"]["serving"] == len(body["events"]) - 1 > 0
        for event in body["events"][1:]:
            assert set(event) >= {
                "step", "phase", "at", "kind", "family", "in_flight",
                "compiled", "rose_by", "bytes_in_use", "peak_bytes_in_use",
                "bytes_limit", "explained", "unexplained"}
            assert event["family"] in body["programs"]
        # No warm-up here: every program was first seen under traffic.
        assert body["programs"] and all(
            "in_company" in p and p["measured"] == "serving"
            for p in body["programs"].values())


# ------------------------------------------------------------ the capture
def _capture_events(trace_dir):
    from jax.profiler import ProfileData

    path = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    events = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                events.append((ev.name, dict(ev.stats)))
    return events, os.path.getsize(path)


async def _captured_run(tmp_path, python_frames):
    from production_stack_tpu.profiling import DeviceProfiler

    profiler = DeviceProfiler()
    if not profiler.available():
        pytest.skip("jax.profiler unavailable in this image")
    engine = ServingEngine(_cfg())
    await engine.start()
    trace_dir = str(tmp_path / ("frames" if python_frames else "spans"))
    try:
        await _run(engine, "warm the shapes", 10)
        info = await profiler.arm(30.0, trace_dir=trace_dir,
                                  python_frames=python_frames)
        assert info["python_frames"] is python_frames
        await _run(engine, "traced request", 12, request_id="traced")
        await profiler.close()
    finally:
        await engine.stop()
    assert profiler.active is None
    assert profiler.last["stop_seconds"] >= 0
    return engine, _capture_events(trace_dir)


async def test_capture_holds_loop_spans_a_clock_anchor_and_no_frames(
        tmp_path):
    engine, (events, _) = await _captured_run(tmp_path, python_frames=False)
    names = [name for name, _ in events]
    for want in ("pstpu.schedule", "pstpu.issue", "pstpu.issue.enqueue",
                 "pstpu.fetch", "pstpu.fetch.sync", "pstpu.apply"):
        assert want in names, want
    # No Python-frame event (the tracer names them "$file:line function").
    assert not [n for n in names if n.startswith("$")]
    # The CPU reports no memory: no span says anything of the allocator.
    assert not [k for n, s in events if n.startswith("pstpu.")
                for k in s if k.startswith("hbm")]
    clock = [stats for name, stats in events if name == "pstpu.clock"]
    assert len(clock) == 1
    assert abs(int(clock[0]["wall_ns"]) - time.time_ns()) < 600e9
    assert int(clock[0]["mono_ns"]) > 0
    issues = [s for n, s in events if n == "pstpu.issue"]
    fetches = [s for n, s in events if n == "pstpu.fetch"]
    assert {s["kind"] for s in issues} == {"prefill", "decode"}
    assert all(int(s["rows"]) == 1 and int(s["k"]) >= 1 for s in issues)
    assert all(s["kind"] in ("prefill", "decode") and
               int(s["sync"]) in (0, 1) for s in fetches)
    # `step` joins the capture to the request's flight record: every
    # dispatch event of the traced request names a step that has an issue
    # span, a fetch span and their executor-side parts in the capture.
    record = engine.recorder.get("traced")["records"][0]["events"]
    steps = {e["step"] for e in record if "step" in e}
    assert steps
    for part in ("pstpu.issue", "pstpu.issue.enqueue", "pstpu.fetch",
                 "pstpu.fetch.sync", "pstpu.apply"):
        assert steps <= {int(s["step"]) for n, s in events if n == part}, part


async def test_python_frames_are_in_the_capture_only_when_asked(tmp_path):
    _, (events, _) = await _captured_run(tmp_path, python_frames=True)
    names = [name for name, _ in events]
    assert [n for n in names if n.startswith("$")]
    assert "pstpu.issue" in names


async def test_stop_trace_does_not_block_the_event_loop(monkeypatch,
                                                        tmp_path):
    """While stop_trace writes its file the loop keeps turning: a ticker
    coroutine advances during a stop that blocks its thread for 0.5 s,
    and a second arm meanwhile is refused as busy."""
    import jax.profiler as jp

    from production_stack_tpu.profiling import DeviceProfiler, ProfilerBusy

    profiler = DeviceProfiler()
    if not profiler.available():
        pytest.skip("jax.profiler unavailable in this image")
    real_stop = jp.stop_trace

    def slow_stop():
        time.sleep(0.5)
        real_stop()

    monkeypatch.setattr(jp, "stop_trace", slow_stop)
    ticks = []

    async def ticker():
        while True:
            ticks.append(time.perf_counter())
            await asyncio.sleep(0.01)

    task = asyncio.ensure_future(ticker())
    try:
        await profiler.arm(0.1, trace_dir=str(tmp_path))
        await asyncio.sleep(0.3)            # the stop is under way
        assert profiler.active is not None and profiler.active["stopping"]
        with pytest.raises(ProfilerBusy):
            await profiler.arm(0.1, trace_dir=str(tmp_path))
        # However long the real stop_trace takes on a busy machine (the
        # capture holds what this process ran before; seconds under six
        # workers): what is held is that the loop turns meanwhile.
        for _ in range(3000):
            if profiler.active is None:
                break
            await asyncio.sleep(0.01)
    finally:
        task.cancel()
    assert profiler.active is None
    assert profiler.last["stop_seconds"] >= 0.5
    gaps = [b - a for a, b in zip(ticks, ticks[1:])]
    assert max(gaps) < 0.25, f"the event loop stalled for {max(gaps):.2f} s"


async def test_close_waits_for_a_stop_under_way(monkeypatch, tmp_path):
    """Engine shutdown while the capture's file is being written: close()
    returns only when stop_trace has, so the process does not exit under
    it (a 4 s capture of a full-size model takes 13-72 s to stop)."""
    import jax.profiler as jp

    from production_stack_tpu.profiling import DeviceProfiler

    profiler = DeviceProfiler()
    if not profiler.available():
        pytest.skip("jax.profiler unavailable in this image")
    real_stop, done = jp.stop_trace, []

    def slow_stop():
        time.sleep(0.5)
        real_stop()
        done.append(True)

    monkeypatch.setattr(jp, "stop_trace", slow_stop)
    await profiler.arm(0.05, trace_dir=str(tmp_path))
    await asyncio.sleep(0.2)
    assert profiler.active["stopping"] and not done
    await profiler.close()
    assert done and profiler.active is None
    assert profiler.last["stop_seconds"] >= 0.5 and "error" not in profiler.last


# --------------------------------------------------------------- the flags
def _model_dir(tmp_path):
    """A model directory holding only config.json (no checkpoint)."""
    path = tmp_path / "config-only"
    path.mkdir()
    (path / "config.json").write_text(json.dumps({
        "architectures": ["LlamaForCausalLM"], "model_type": "llama",
        "hidden_size": 64, "intermediate_size": 128,
        "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 2, "vocab_size": 512,
        "max_position_embeddings": 256, "rms_norm_eps": 1e-5,
        "rope_theta": 10000.0, "tie_word_embeddings": True,
    }))
    return str(path)


ENGINE_ARGS = ["--max-model-len", "128", "--num-kv-blocks", "64",
               "--max-num-seqs", "2", "--max-num-batched-tokens", "32",
               "--no-warmup", "--dtype", "float32"]


async def test_config_only_directory_boots_under_load_format_dummy(tmp_path):
    model = _model_dir(tmp_path)
    args = parse_args(["--model", model, "--load-format", "dummy",
                       "--seed", "7", *ENGINE_ARGS])
    engine = build_engine_from_args(args)
    assert engine.config.load_format == "dummy" and engine.config.seed == 7
    await engine.start()
    try:
        toks = await _run(engine, "hi", 4)
    finally:
        await engine.stop()
    assert len(toks) == 4
    # Without the flag the same directory is refused: no checkpoint.
    with pytest.raises(Exception):
        build_engine_from_args(parse_args(["--model", model, *ENGINE_ARGS]))


def test_flags_left_out_leave_both_fields_to_a_setdefault_patch(
        monkeypatch):
    """The benchmark's engine shim wraps ``EngineConfig.__init__`` with
    ``kwargs.setdefault``: with the flags absent nothing may reach
    ``EngineConfig`` for the two fields, or the shim's defaults lose."""
    seen = {}
    init = EngineConfig.__init__

    def patched(self, *args, **kwargs):
        seen.update(given={k: kwargs[k] for k in ("load_format", "seed")
                           if k in kwargs})
        kwargs.setdefault("load_format", "dummy")
        kwargs.setdefault("seed", 1234)
        init(self, *args, **kwargs)

    monkeypatch.setattr(EngineConfig, "__init__", patched)
    monkeypatch.setattr(
        "production_stack_tpu.server.api_server.ServingEngine",
        lambda cfg: cfg)
    args = parse_args(["--model", "tiny-llama"])
    assert args.load_format is None and args.seed is None
    cfg = build_engine_from_args(args)
    assert seen["given"] == {}
    assert cfg.load_format == "dummy" and cfg.seed == 1234
    # Given, the flags win over the patch.
    cfg = build_engine_from_args(parse_args(
        ["--model", "tiny-llama", "--load-format", "auto", "--seed", "3"]))
    assert seen["given"] == {"load_format": "auto", "seed": 3}
    assert cfg.load_format == "auto" and cfg.seed == 3
