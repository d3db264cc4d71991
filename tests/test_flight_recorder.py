"""Request-lifecycle flight recorder + debug endpoints + device profiling
(docs/OBSERVABILITY.md): bounded ring semantics, phase folding, the
/debug surface over a real tiny engine, and the 404-clean disabled path.
"""

import asyncio
import time

import pytest
from aiohttp.test_utils import TestClient, TestServer

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.engine import ServingEngine
from production_stack_tpu.engine.flight_recorder import (
    FlightRecord,
    FlightRecorder,
    phases,
)
from production_stack_tpu.server.api_server import APIServer


# ------------------------------------------------------------------ unit
def test_ring_bounds_and_eviction():
    rec = FlightRecorder(capacity=3)
    for i in range(5):
        rec.start(f"r{i}")
        rec.event(f"r{i}", "enqueue", {"prompt_tokens": 1})
    assert rec.records_evicted_total == 2
    assert rec.get("r0") is None and rec.get("r1") is None
    assert rec.get("r4") is not None
    tl = rec.timeline()
    assert tl["recorded"] == 3
    assert [r["request_id"] for r in tl["requests"]] == ["r4", "r3", "r2"]


def test_per_record_event_cap_counts_overflow():
    rec = FlightRecorder(capacity=2, max_events=8)
    rec.start("r")
    for _ in range(20):
        rec.event("r", "decode_fetch", {"tokens": 1})
    rec.finish("r", reason="length", output_tokens=20)
    got = rec.get("r")["records"][0]
    assert got["events_dropped"] == 12
    # The finish event bypasses the cap: a truncated timeline still shows
    # how the request ended.
    assert got["events"][-1]["event"] == "finish"
    assert got["finished"] is True


def test_event_on_unknown_request_is_noop():
    rec = FlightRecorder(capacity=2)
    rec.event("ghost", "decode_fetch", {"tokens": 1})   # must not raise
    rec.finish("ghost")
    assert rec.get("ghost") is None


def test_alias_resolution():
    rec = FlightRecorder(capacity=4)
    rec.start("cmpl-1-0")
    rec.start("cmpl-1-1")
    rec.alias("client-id", ["cmpl-1-0", "cmpl-1-1"])
    got = rec.get("client-id")
    assert got["request_id"] == "client-id"
    assert [r["request_id"] for r in got["records"]] == [
        "cmpl-1-0", "cmpl-1-1",
    ]


def test_phase_folding_covers_the_span_tree():
    r = FlightRecord("r")
    t0 = time.time()
    r.events = [
        (t0, "enqueue", {"prompt_tokens": 10}),
        (t0 + 0.05, "schedule", {"wait_s": 0.05}),
        (t0 + 0.05, "prefill_issue", {"step": 0, "chunk": 10, "start": 0}),
        (t0 + 0.04, "restore", {"tokens": 32, "seconds": 0.02}),
        (t0 + 0.15, "prefill_fetch", {"step": 0, "final": True,
                                      "cached_tokens": 0}),
        (t0 + 0.16, "decode_issue", {"step": 1, "rows": 1, "k": 8}),
        (t0 + 0.30, "decode_fetch", {"step": 1, "tokens": 8,
                                     "spec_accepted_batch": 3}),
        (t0 + 0.31, "decode_issue", {"step": 2, "rows": 1, "k": 8}),
        (t0 + 0.45, "decode_fetch", {"step": 2, "tokens": 4}),
        (t0 + 0.46, "finish", {"reason": "length", "output_tokens": 12}),
    ]
    ph = {p["name"]: p for p in phases(r)}
    assert set(ph) == {"queue_wait", "kv_restore", "prefill", "decode"}
    qw = ph["queue_wait"]
    assert qw["end"] - qw["start"] == pytest.approx(0.05, abs=1e-4)
    assert ph["prefill"]["end"] - ph["prefill"]["start"] == pytest.approx(
        0.10, abs=1e-4
    )
    dec = ph["decode"]
    assert dec["attrs"] == {"trains": 2, "tokens": 12, "spec_accepted_batch": 3}
    assert ph["kv_restore"]["attrs"]["tokens"] == 32
    # Phases are ordered and non-overlapping enough to sum to ~the
    # request duration (the acceptance criterion's 10% bar at scale).
    total = sum(p["end"] - p["start"] for p in ph.values()
                if p["name"] != "kv_restore")
    assert total == pytest.approx(0.44, abs=0.01)


def test_phase_folding_never_dispatched():
    r = FlightRecord("r")
    t0 = time.time()
    r.events = [
        (t0, "enqueue", {"prompt_tokens": 10}),
        (t0 + 0.2, "finish", {"reason": "abort", "output_tokens": 0}),
    ]
    ph = phases(r)
    assert [p["name"] for p in ph] == ["queue_wait"]
    assert ph[0]["end"] - ph[0]["start"] == pytest.approx(0.2, abs=1e-4)


# ------------------------------------------------------- engine e2e
@pytest.fixture()
def engine_cfg():
    return EngineConfig(
        model="tiny-llama", max_model_len=256, block_size=4,
        num_kv_blocks=128, max_num_seqs=8, max_num_batched_tokens=32,
        attn_impl="xla",
    )


async def _client(cfg):
    server = APIServer(ServingEngine(cfg))
    client = TestClient(TestServer(server.build_app()))
    await client.start_server()
    return client


async def test_debug_endpoints_replay_request_timeline(engine_cfg):
    client = await _client(engine_cfg)
    try:
        resp = await client.post("/v1/completions", json={
            "model": "tiny-llama", "prompt": "abc", "max_tokens": 4,
            "temperature": 0, "ignore_eos": True,
        }, headers={"x-request-id": "router-req-1"})
        assert resp.status == 200
        body = await resp.json()
        # All three ids resolve: the engine response id, the router's
        # x-request-id, and the engine-internal child id are one record.
        for key in (body["id"], "router-req-1"):
            dbg = await client.get(f"/debug/requests/{key}")
            assert dbg.status == 200, key
            got = await dbg.json()
            rec = got["records"][0]
            assert rec["finished"] is True
            names = [e["event"] for e in rec["events"]]
            assert names[0] == "enqueue"
            assert "prefill_issue" in names and "decode_issue" in names
            assert names[-1] == "finish"
            fin = rec["events"][-1]
            assert fin["reason"] == "length" and fin["output_tokens"] == 4
            ph = {p["name"] for p in rec["phases"]}
            assert {"queue_wait", "prefill", "decode"} <= ph
            # Phase tree sums to ~the request duration: decode ends at
            # the last fetch, queue_wait+prefill precede it.
            spans = {p["name"]: p for p in rec["phases"]}
            assert spans["queue_wait"]["end"] <= spans["prefill"]["end"]
            assert spans["prefill"]["end"] <= spans["decode"]["end"]

        # Unknown id: clean 404.
        assert (await client.get("/debug/requests/nope")).status == 404

        # /debug/timeline lists the request, newest first.
        tl = await (await client.get("/debug/timeline")).json()
        assert tl["recorded"] >= 1
        assert any(r["finished"] for r in tl["requests"])

        # Lifecycle histograms observed real phases on /metrics.
        text = await (await client.get("/metrics")).text()
        assert "pstpu:queue_wait_seconds_bucket" in text
        assert 'pstpu:queue_wait_seconds_count{model_name="tiny-llama"} 1' \
            in text
        assert "pstpu:decode_train_seconds_count" in text
        assert "pstpu:trace_spans_dropped_total" in text
    finally:
        await client.close()


async def test_debug_endpoints_respect_api_key(engine_cfg):
    """A keyed engine guards /debug like /v1: request timelines and the
    profiler arm must not be reachable unauthenticated."""
    server = APIServer(ServingEngine(engine_cfg), api_key="sk-test")
    client = TestClient(TestServer(server.build_app()))
    await client.start_server()
    try:
        assert (await client.get("/debug/timeline")).status == 401
        assert (await client.get("/debug/requests/x")).status == 401
        assert (await client.post("/debug/profile", json={})).status == 401
        ok = await client.get(
            "/debug/timeline",
            headers={"Authorization": "Bearer sk-test"},
        )
        assert ok.status == 200
        # 0/negative caps mean "none", never "everything" (slice-bound
        # inversion guard).
        tl = await (await client.get(
            "/debug/timeline?max_requests=0",
            headers={"Authorization": "Bearer sk-test"},
        )).json()
        assert tl["requests"] == []
        tl = await (await client.get(
            "/debug/timeline?max_requests=-5",
            headers={"Authorization": "Bearer sk-test"},
        )).json()
        assert tl["requests"] == []
    finally:
        await client.close()


async def test_debug_disabled_is_404_clean(engine_cfg):
    from dataclasses import replace

    cfg = replace(engine_cfg, debug_endpoints=False)
    server = APIServer(ServingEngine(cfg))
    client = TestClient(TestServer(server.build_app()))
    await client.start_server()
    try:
        assert (await client.get("/debug/requests/x")).status == 404
        assert (await client.get("/debug/timeline")).status == 404
        assert (await client.post("/debug/profile", json={})).status == 404
        assert (await client.get("/debug/profile")).status == 404
        assert (await client.get("/debug/programs")).status == 404
        # Serving still works; the recorder does not exist at all.
        resp = await client.post("/v1/completions", json={
            "model": "tiny-llama", "prompt": "abc", "max_tokens": 2,
            "temperature": 0, "ignore_eos": True,
        })
        assert resp.status == 200
        assert server.engine.recorder is None
        assert server.profiler is None
    finally:
        await client.close()


async def test_debug_profile_capture_lifecycle(engine_cfg):
    """POST /debug/profile arms a bounded jax.profiler window; a second
    POST while armed is 409; the capture completes and reports a trace
    dir. Runs against the CPU backend's real jax.profiler."""
    import tempfile

    client = await _client(engine_cfg)
    try:
        status = await (await client.get("/debug/profile")).json()
        if not status["available"]:
            pytest.skip("jax.profiler unavailable in this image")
        trace_dir = tempfile.mkdtemp(prefix="pstpu-test-profile-")
        resp = await client.post("/debug/profile", json={
            "duration_s": 0.3, "trace_dir": trace_dir,
        })
        assert resp.status == 200
        body = await resp.json()
        assert body["status"] == "armed"
        assert body["trace_dir"] == trace_dir
        # One capture at a time.
        assert (await client.post("/debug/profile", json={
            "duration_s": 0.2,
        })).status == 409
        # Bad bodies are clean 400s even while armed.
        assert (await client.post("/debug/profile", json={
            "duration_s": "x",
        })).status == 400
        # The profiler's stop takes about a second in a fresh process and
        # over 20 s in one that ran a model's test file first (an xdist
        # worker: tests/test_granite_hybrid.py before this test reads 21 s).
        # The lifecycle is what is held here, not the stop's time.
        for _ in range(1200):
            status = await (await client.get("/debug/profile")).json()
            if status["active"] is None:
                break
            await asyncio.sleep(0.1)
        assert status["active"] is None
        assert status["last"]["trace_dir"] == trace_dir
    finally:
        await client.close()


async def test_preempt_and_restore_hooks_record(engine_cfg):
    """The scheduler's observability hooks reach the recorder (unit-level
    wiring check: no device pressure needed)."""
    engine = ServingEngine(engine_cfg)
    engine.recorder.start("r1")
    engine.scheduler.on_preempt("r1")
    engine.scheduler.on_restore("r1", 32, 0.015)
    got = engine.recorder.get("r1")["records"][0]
    names = [e["event"] for e in got["events"]]
    assert names == ["preempt", "restore"]
    restore = got["events"][1]
    assert restore["tokens"] == 32
    assert engine.lifecycle.restore_round_trip.count == 1
