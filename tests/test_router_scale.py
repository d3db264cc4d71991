"""Horizontally-scaled router tier (docs/ROUTER_SCALE.md).

Three layers of the N-replica story:

  * PlacementRing determinism — two independently-constructed replicas
    compute identical session/prefix placement from the same membership;
    churn remaps only the departed node's keys; candidate restriction
    keeps picks stable while the landing node stays in the set.
  * Breaker gossip — a replica's OPEN circuits transfer to peers as
    remaining-seconds deltas through ``peer_snapshot``/``apply_peer_state``
    and the dynamic-config watch plane's peer files.
  * Client-driven cross-router resume — a client that lost its router
    mid-stream reconnects to ANY peer with ``x-pstpu-resume-tokens`` /
    ``x-pstpu-resume-seed`` and the peer splices a token-identical
    continuation (fake engines in-process; real tiny-llama engine for
    seeded parity and stop-across-splice; two real router processes for
    the SIGKILL failover end-to-end).
"""

import asyncio
import json
import os
import signal
import socket
import subprocess
import sys
import time

import aiohttp
import pytest
from aiohttp.test_utils import TestServer

from production_stack_tpu.router.ring import (
    LOAD_MARGIN, PlacementRing, near_least_loaded,
)
from tests.fake_engine import BASE_TOKEN, FAKE_SEED, FakeEngine
from tests.test_router_e2e import _start_stack, _stop_stack

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

RESUME_TOKENS = "x-pstpu-resume-tokens"
RESUME_SEED = "x-pstpu-resume-seed"
PEER = 'router_midstream_resumes_total{outcome="peer"}'
TRUNCATIONS = "router_truncations_total"


# --------------------------------------------------------------------------
# Placement ring: deterministic across replicas, bounded churn
# --------------------------------------------------------------------------
URLS = [f"http://10.0.0.{i}:8000" for i in range(1, 7)]


def test_ring_identical_placement_across_independent_replicas():
    """Two replicas that discovered the same backend set (in any order)
    compute the same session→engine and prefix→engine placement without
    exchanging any state."""
    a, b = PlacementRing(), PlacementRing()
    a.sync(URLS)
    b.sync(list(reversed(URLS)))     # discovery order must not matter
    for i in range(200):
        assert a.pick_session(f"sess-{i}") == b.pick_session(f"sess-{i}")
        assert a.pick_prefix(f"hash-{i:x}") == b.pick_prefix(f"hash-{i:x}")


def test_ring_removal_remaps_only_departed_keys():
    ring = PlacementRing()
    ring.sync(URLS)
    keys = [f"sess-{i}" for i in range(300)]
    before = {k: ring.pick_session(k) for k in keys}
    gone = URLS[2]
    ring.sync([u for u in URLS if u != gone])
    moved = 0
    for k in keys:
        after = ring.pick_session(k)
        if before[k] == gone:
            assert after != gone
            moved += 1
        else:
            assert after == before[k]   # survivors keep their keys
    assert moved > 0                    # the departed node did own keys


def test_ring_candidate_restriction_is_stable_and_consistent():
    """Restricting to a candidate subset walks the FULL ring: the pick is
    a member of the subset, equals the unrestricted pick when the subset
    is everything, and only moves when the landing node leaves the set."""
    ring = PlacementRing()
    ring.sync(URLS)
    for i in range(100):
        key = f"sess-{i}"
        full = ring.pick_session(key, candidates=URLS)
        assert full == ring.pick_session(key)
        # Dropping a NON-landing candidate must not move the key.
        other = next(u for u in URLS if u != full)
        subset = [u for u in URLS if u != other]
        assert ring.pick_session(key, candidates=subset) == full
        # Dropping the landing node moves it to another member.
        without = [u for u in URLS if u != full]
        moved = ring.pick_session(key, candidates=without)
        assert moved in without


def test_ring_session_and_prefix_namespaces_are_independent():
    ring = PlacementRing()
    ring.sync(URLS)
    keys = [f"k-{i}" for i in range(64)]
    assert any(ring.pick_session(k) != ring.pick_prefix(k) for k in keys)


def test_near_least_loaded_margin():
    loads = {"a": 0.30, "b": 0.35, "c": 0.31, "d": 0.90}
    got = near_least_loaded(loads, loads.get, margin=LOAD_MARGIN)
    assert got == ["a", "b", "c"]       # within 0.1 of the 0.30 floor
    # A large gap collapses to the single least-loaded engine.
    loads = {"a": 0.10, "b": 0.50, "c": 0.90}
    assert near_least_loaded(loads, loads.get) == ["a"]
    assert near_least_loaded([], lambda u: 0.0) == []


# --------------------------------------------------------------------------
# Breaker gossip: OPEN circuits transfer between replicas
# --------------------------------------------------------------------------
def _resilience_cfg(**kw):
    from production_stack_tpu.router.resilience import ResilienceConfig
    base = dict(breaker_min_requests=2, breaker_error_rate=0.5,
                breaker_open_duration=30.0)
    base.update(kw)
    return ResilienceConfig(**base)


def test_breaker_peer_snapshot_and_adoption():
    from production_stack_tpu.router.resilience import (
        CLOSED, OPEN, ResilienceManager,
    )
    url = "http://10.0.0.1:8000"
    a = ResilienceManager(_resilience_cfg())
    b = ResilienceManager(_resilience_cfg())
    a.record_failure(url)
    a.record_failure(url)
    assert a.state(url) == OPEN

    snap = a.peer_snapshot()
    assert url in snap and 0 < snap[url] <= 30.0

    b.apply_peer_state("router-a", snap)
    assert b.state(url) == OPEN
    # The adopted circuit re-publishes at most the remaining time A saw.
    assert b.peer_snapshot()[url] <= snap[url] + 0.5


def test_breaker_peer_adoption_clamps_ignores_and_survives_garbage():
    from production_stack_tpu.router.resilience import (
        CLOSED, OPEN, ResilienceManager,
    )
    mgr = ResilienceManager(_resilience_cfg())
    u1, u2, u3 = ("http://e1:8000", "http://e2:8000", "http://e3:8000")
    # Expired/zero remaining time is not adopted.
    mgr.apply_peer_state("peer", {u1: 0.0})
    assert mgr.state(u1) == CLOSED
    # A peer claiming more than our own open_duration is clamped.
    mgr.apply_peer_state("peer", {u2: 9999.0})
    assert mgr.state(u2) == OPEN
    assert mgr.peer_snapshot()[u2] <= mgr.config.breaker_open_duration
    # Malformed entries are skipped without poisoning valid ones.
    mgr.apply_peer_state("peer", {u1: {"not": "a number"}, u3: 5.0})
    assert mgr.state(u1) == CLOSED
    assert mgr.state(u3) == OPEN


def test_breaker_gossip_roundtrip_through_peer_files(tmp_path):
    """The dynamic-config watch plane publishes this replica's OPEN
    circuits to ``peer_dir/breakers-<router_id>.json`` and adopts peers'
    files on the same tick (docs/ROUTER_SCALE.md)."""
    from production_stack_tpu.router.dynamic_config import (
        DynamicConfigWatcher,
    )
    from production_stack_tpu.router.resilience import (
        OPEN, ResilienceConfig, get_resilience, initialize_resilience,
    )
    u_mine = "http://engine-a:8000"
    u_peer = "http://engine-b:8000"
    mgr = initialize_resilience(_resilience_cfg(breaker_min_requests=1))
    try:
        mgr.record_failure(u_mine)
        assert mgr.state(u_mine) == OPEN

        watcher = DynamicConfigWatcher(
            None, watch_interval=3600.0,
            peer_dir=str(tmp_path), router_id="r1",
        )
        try:
            watcher.sync_peer_state()
            mine = json.loads((tmp_path / "breakers-r1.json").read_text())
            assert mine["router_id"] == "r1"
            assert u_mine in mine["open"] and mine["open"][u_mine] > 0

            # A peer file appears: its OPEN circuit is adopted locally
            # (most of the open duration left, so no stall between this
            # write and the tick can age it out).
            (tmp_path / "breakers-r2.json").write_text(json.dumps(
                {"router_id": "r2", "open": {u_peer: 25.0}}
            ))
            # A half-written peer file must not break the tick.
            (tmp_path / "breakers-r3.json").write_text('{"router_id": "r3"')
            watcher.sync_peer_state()
            assert get_resilience().state(u_peer) == OPEN
        finally:
            watcher.close()
    finally:
        initialize_resilience(ResilienceConfig())   # reset the global


def test_breaker_gossip_ignores_stale_and_decays_aged_peer_files(tmp_path):
    """A dead/replaced replica's peer file stops being republished; its
    frozen remaining_s must not re-open a recovered backend forever. The
    reader decays remaining times by the snapshot's publish-timestamp age,
    skips snapshots older than a few watch intervals, and garbage-collects
    files long past that."""
    import os
    import time as _time

    from production_stack_tpu.router.dynamic_config import (
        DynamicConfigWatcher,
    )
    from production_stack_tpu.router.resilience import (
        CLOSED, OPEN, ResilienceConfig, get_resilience,
        initialize_resilience,
    )
    u_stale = "http://engine-stale:8000"
    u_decayed = "http://engine-decayed:8000"
    u_live = "http://engine-live:8000"
    u_gc = "http://engine-gc:8000"
    initialize_resilience(_resilience_cfg())
    watcher = DynamicConfigWatcher(
        None, watch_interval=10.0, peer_dir=str(tmp_path), router_id="r1",
    )
    try:
        now = _time.time()
        # Published 10 minutes ago (>> 3 watch intervals): skipped whole.
        (tmp_path / "breakers-dead.json").write_text(json.dumps(
            {"router_id": "dead", "ts": now - 600.0,
             "open": {u_stale: 25.0}}
        ))
        # Fresh enough to read, but the 20s age eats the 5s remaining —
        # the circuit converges to closed instead of flapping.
        (tmp_path / "breakers-aging.json").write_text(json.dumps(
            {"router_id": "aging", "ts": now - 20.0,
             "open": {u_decayed: 5.0, u_live: 29.0}}
        ))
        # mtime far beyond the GC horizon: the file itself is deleted.
        gc_file = tmp_path / "breakers-gone.json"
        gc_file.write_text(json.dumps(
            {"router_id": "gone", "ts": now, "open": {u_gc: 25.0}}
        ))
        os.utime(gc_file, (now - 7200.0, now - 7200.0))

        watcher.sync_peer_state()
        mgr = get_resilience()
        assert mgr.state(u_stale) == CLOSED
        assert mgr.state(u_decayed) == CLOSED
        assert mgr.state(u_gc) == CLOSED
        assert not gc_file.exists()
        # The still-valid entry in the aging snapshot IS adopted, with its
        # remaining time decayed by the snapshot's age.
        assert mgr.state(u_live) == OPEN
        assert mgr.peer_snapshot()[u_live] <= 29.0 - 20.0 + 0.5
    finally:
        watcher.close()
        initialize_resilience(ResilienceConfig())   # reset the global


# --------------------------------------------------------------------------
# Client-driven cross-router resume (in-process router, fake engines)
# --------------------------------------------------------------------------
async def _read_stream(client, body, headers=None, path="/v1/completions"):
    resp = await client.post(path, json=body, headers=headers or {})
    assert resp.status == 200, await resp.text()
    raw = (await resp.content.read()).decode()
    events = [ln for ln in raw.splitlines() if ln.startswith("data:")]
    chunks = [json.loads(e[5:]) for e in events if e != "data: [DONE]"]
    text = "".join(c["choices"][0].get("text", "")
                   or c["choices"][0].get("delta", {}).get("content", "")
                   for c in chunks)
    toks = [t for c in chunks for t in c.get("pstpu", {}).get("toks", [])]
    return events, chunks, text, toks


async def _counter(client, series):
    text = await (await client.get("/metrics")).text()
    for line in text.splitlines():
        if line.startswith(series + " "):
            return float(line.rsplit(" ", 1)[1])
    return 0.0


def _resume_bodies(engines):
    return [b for e in engines for _, b in e.requests_seen
            if b.get("resume_tokens")]


async def test_client_resume_headers_rejected_when_malformed():
    engines, servers, urls, client = await _start_stack(n_engines=1)
    try:
        stream_body = {"model": "m1", "prompt": "x", "max_tokens": 4,
                       "stream": True}
        cases = [
            # Not a stream: resume headers need a resumable generation.
            ({"model": "m1", "prompt": "x", "max_tokens": 4},
             {RESUME_TOKENS: "101,102"}),
            # n=2 is never resume-eligible.
            (dict(stream_body, n=2), {RESUME_TOKENS: "101,102"}),
            # Garbage token ids.
            (stream_body, {RESUME_TOKENS: "101,banana"}),
            # Empty token list: reconnect without headers instead.
            (stream_body, {RESUME_TOKENS: ""}),
            # Garbage seed.
            (stream_body, {RESUME_TOKENS: "101", RESUME_SEED: "pi"}),
        ]
        for body, headers in cases:
            resp = await client.post("/v1/completions", json=body,
                                     headers=headers)
            assert resp.status == 400, (body, headers)
        # None of the rejects reached an engine.
        assert not engines[0].requests_seen
    finally:
        await _stop_stack(servers, client)


async def test_client_resume_splices_token_identical_continuation():
    """The peer-replica path: a fresh request to a router that never saw
    the original stream, carrying the client's delivered token ids + seed,
    continues exactly where the lost replica stopped (greedy)."""
    engines, servers, urls, client = await _start_stack(n_engines=2)
    try:
        body = {"model": "m1", "prompt": "x", "max_tokens": 8,
                "stream": True}
        events, _, text, toks = await _read_stream(client, body)
        assert events[-1] == "data: [DONE]"
        assert toks == [BASE_TOKEN + i for i in range(8)]

        peer0 = await _counter(client, PEER)
        headers = {RESUME_TOKENS: ",".join(str(t) for t in toks[:3]),
                   RESUME_SEED: str(FAKE_SEED)}
        revents, _, rtext, rtoks = await _read_stream(client, body, headers)
        assert revents[-1] == "data: [DONE]"
        assert rtoks == toks[3:]               # continuation only, no overlap
        assert rtext == "Hello " * 5
        assert await _counter(client, PEER) == peer0 + 1

        resumes = _resume_bodies(engines)
        assert len(resumes) == 1
        assert resumes[0]["resume_tokens"] == toks[:3]
        assert resumes[0]["resume_seed"] == FAKE_SEED
    finally:
        await _stop_stack(servers, client)


async def test_client_resume_on_chat_endpoint():
    engines, servers, urls, client = await _start_stack(n_engines=1)
    try:
        body = {"model": "m1",
                "messages": [{"role": "user", "content": "hi"}],
                "max_tokens": 6, "stream": True}
        headers = {RESUME_TOKENS: ",".join(
            str(BASE_TOKEN + i) for i in range(2))}
        events, _, text, toks = await _read_stream(
            client, body, headers, path="/v1/chat/completions")
        assert events[-1] == "data: [DONE]"
        assert toks == [BASE_TOKEN + i for i in range(2, 6)]
    finally:
        await _stop_stack(servers, client)


async def test_client_resume_budget_exhaustion_degrades_to_truncation():
    """With the midstream-resume budget at 0, a backend dying during the
    spliced continuation falls back to PR-1 truncation-only semantics:
    the stream ends without [DONE] and the truncation counter ticks."""
    engines, servers, urls, client = await _start_stack(
        n_engines=2, max_midstream_resumes=0)
    try:
        trunc0 = await _counter(client, TRUNCATIONS)
        # Position round-robin so the resume request lands on the victim.
        resp = await client.post("/v1/completions", json={
            "model": "m1", "prompt": "probe", "max_tokens": 1})
        assert resp.status == 200
        await resp.read()
        victim = next(e for e in engines if not e.requests_seen)
        victim.die_after_chunks = 2
        victim.die_once = True

        headers = {RESUME_TOKENS: ",".join(
            str(BASE_TOKEN + i) for i in range(3)),
            RESUME_SEED: str(FAKE_SEED)}
        events, _, _, toks = await _read_stream(client, {
            "model": "m1", "prompt": "x", "max_tokens": 8, "stream": True,
        }, headers)
        assert events[-1] != "data: [DONE]"     # truncated, not resumed
        assert len(toks) < 5                    # continuation died early
        assert await _counter(client, TRUNCATIONS) == trunc0 + 1
    finally:
        await _stop_stack(servers, client)


# --------------------------------------------------------------------------
# Cross-router resume against the REAL engine (seeded + stop-across-splice)
# --------------------------------------------------------------------------
async def _start_router_over_real_engine():
    from production_stack_tpu.engine import EngineConfig
    from production_stack_tpu.engine.engine import ServingEngine
    from production_stack_tpu.router.app import build_app
    from production_stack_tpu.server.api_server import APIServer
    from tests.test_router_e2e import router_args
    from aiohttp.test_utils import TestClient

    cfg = EngineConfig(
        model="tiny-llama", max_model_len=256, block_size=4,
        num_kv_blocks=128, max_num_seqs=8, max_num_batched_tokens=32,
        attn_impl="xla",
    )
    server = APIServer(ServingEngine(cfg))
    backend = TestServer(server.build_app())
    await backend.start_server()
    url = f"http://127.0.0.1:{backend.port}"
    client = TestClient(TestServer(build_app(
        router_args([url], ["tiny-llama"]))))
    await client.start_server()
    return server.engine, backend, client


async def test_client_resume_seeded_token_identical_real_engine():
    """Seeded-sampling parity across the router hop: the peer replica's
    spliced continuation reproduces the uninterrupted stream's tokens
    exactly, because resume_seed carries the RESOLVED sampler seed."""
    from tests.test_resume import _warm_prefix

    engine, backend, client = await _start_router_over_real_engine()
    try:
        body = {"model": "tiny-llama", "prompt": "cross router seeded",
                "max_tokens": 10, "temperature": 0.9, "seed": 777,
                "ignore_eos": True, "stream": True}
        events, chunks, text, toks = await _read_stream(client, body)
        assert events[-1] == "data: [DONE]"
        assert len(toks) == 10
        seeds = {c["pstpu"]["seed"] for c in chunks if "pstpu" in c}
        assert len(seeds) == 1
        seed = seeds.pop()

        headers = {RESUME_TOKENS: ",".join(str(t) for t in toks[:4]),
                   RESUME_SEED: str(seed)}
        revents, _, rtext, rtoks = await _read_stream(client, body, headers)
        assert revents[-1] == "data: [DONE]"
        assert rtoks == toks[4:]
        assert _warm_prefix(engine, toks[:4], []) + rtext == text
    finally:
        await client.close()
        await backend.close()


async def test_client_resume_stop_string_across_the_splice_real_engine():
    """A stop string that STARTS in the region the dead router delivered
    and completes in the peer's continuation still stops the stream with
    correctly truncated joined text (OpenAI semantics: stop excluded)."""
    from tests.test_resume import _warm_prefix

    engine, backend, client = await _start_router_over_real_engine()
    try:
        body = {"model": "tiny-llama", "prompt": "stop splice prompt",
                "max_tokens": 16, "temperature": 0, "ignore_eos": True,
                "stream": True}
        events, chunks, full_text, toks = await _read_stream(client, body)
        assert events[-1] == "data: [DONE]"

        # Find an interruption point k whose NEXT text boundary admits a
        # 4-char stop string spanning the splice (first occurrence there).
        pick = None
        bounds, acc = [], ""
        for c in chunks:
            acc += c["choices"][0].get("text", "")
            bounds.append((len(c.get("pstpu", {}).get("toks", [])), len(acc)))
        k = 0
        for ntoks, b in bounds[:-1]:
            k += ntoks
            if b < 2 or b + 2 > len(full_text):
                continue
            stop = full_text[b - 2: b + 2]
            if len(stop) == 4 and full_text.find(stop) == b - 2:
                pick = (k, stop, b)
                break
        if pick is None:
            pytest.skip("random-weight output admits no boundary stop")
        k, stop, b = pick

        # Reference: the uninterrupted run WITH the stop string.
        stop_body = dict(body, stop=[stop])
        ref_events, _, ref_text, _ = await _read_stream(client, stop_body)
        assert ref_events[-1] == "data: [DONE]"
        assert stop not in ref_text

        seeds = {c["pstpu"]["seed"] for c in chunks if "pstpu" in c}
        headers = {RESUME_TOKENS: ",".join(str(t) for t in toks[:k]),
                   RESUME_SEED: str(seeds.pop())}
        revents, _, rtext, _ = await _read_stream(client, stop_body, headers)
        assert revents[-1] == "data: [DONE]"
        joined = _warm_prefix(engine, toks[:k], [stop]) + rtext
        assert joined == ref_text
    finally:
        await client.close()
        await backend.close()


# --------------------------------------------------------------------------
# Two live router PROCESSES: SIGKILL one mid-stream, client fails over
# --------------------------------------------------------------------------
def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


async def _wait_health(session, url, proc, timeout_s=45.0):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            raise RuntimeError(f"router at {url} exited: {proc.returncode}")
        try:
            async with session.get(f"{url}/health") as resp:
                if resp.status == 200:
                    return
        except aiohttp.ClientError:
            pass
        await asyncio.sleep(0.2)
    raise RuntimeError(f"router at {url} never became healthy")


def _engine_for(engines, needle):
    hits = [i for i, e in enumerate(engines)
            if any(b.get("prompt") == needle for _, b in e.requests_seen)]
    assert len(hits) == 1, (needle, hits)
    return hits[0]


async def test_two_router_processes_kill_one_midstream_client_fails_over():
    """The tentpole end-to-end: two real router replicas over one fake
    engine fleet. Both replicas agree on session placement (shared ring,
    no gossip); SIGKILLing replica A mid-SSE loses nothing — the client
    reconnects to replica B with its delivered token ids + seed and B
    splices a token-identical continuation (outcome="peer"), with zero
    truncations recorded on the survivor."""
    engines, servers = [], []
    for _ in range(2):
        eng = FakeEngine(model="m1", speed=12.0, ttft=0.05)
        srv = TestServer(eng.build_app())
        await srv.start_server()
        engines.append(eng)
        servers.append(srv)
    engine_urls = [f"http://127.0.0.1:{s.port}" for s in servers]

    peer_dir = os.path.join(
        os.environ.get("TMPDIR", "/tmp"), f"pstpu-test-peers-{os.getpid()}")
    os.makedirs(peer_dir, exist_ok=True)
    ports = [_free_port(), _free_port()]
    router_urls = [f"http://127.0.0.1:{p}" for p in ports]
    procs = []
    for i, port in enumerate(ports):
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "production_stack_tpu.router.app",
             "--port", str(port),
             "--service-discovery", "static",
             "--static-backends", ",".join(engine_urls),
             "--static-models", "m1,m1",
             "--routing-logic", "session",
             "--session-key", "x-user-id",
             "--router-id", f"router-{i}",
             "--router-peer-dir", peer_dir,
             "--dynamic-config-watch-interval", "1"],
            cwd=REPO_ROOT,
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
        ))
    try:
        async with aiohttp.ClientSession() as session:
            for url, proc in zip(router_urls, procs):
                await _wait_health(session, url, proc)

            # --- Session placement agrees across live replicas ----------
            for n in range(4):
                for suffix, url in (("a", router_urls[0]),
                                    ("b", router_urls[1])):
                    async with session.post(
                        f"{url}/v1/completions",
                        json={"model": "m1", "prompt": f"probe-{n}-{suffix}",
                              "max_tokens": 1},
                        headers={"x-user-id": f"user-{n}"},
                    ) as resp:
                        assert resp.status == 200
                        await resp.read()
            for n in range(4):
                assert _engine_for(engines, f"probe-{n}-a") == \
                    _engine_for(engines, f"probe-{n}-b")

            # --- Kill replica A mid-stream; fail over to B --------------
            body = {"model": "m1", "prompt": "kill-e2e", "max_tokens": 8,
                    "stream": True}
            hdrs = {"x-user-id": "sess-kill"}
            delivered_toks, delivered_text = [], ""
            async with session.post(f"{router_urls[0]}/v1/completions",
                                    json=body, headers=hdrs) as resp:
                assert resp.status == 200
                while len(delivered_toks) < 3:
                    line = (await resp.content.readline()).decode()
                    if not line.startswith("data:") or "[DONE]" in line:
                        continue
                    chunk = json.loads(line[5:])
                    delivered_toks += chunk.get("pstpu", {}).get("toks", [])
                    delivered_text += chunk["choices"][0].get("text", "")
                procs[0].send_signal(signal.SIGKILL)
                procs[0].wait(timeout=30)
            # The abandoned stream is what a dead router leaves behind: the
            # client holds exactly the prefix it verifiably parsed.
            assert delivered_toks == [BASE_TOKEN + i for i in range(3)]

            rhdrs = dict(hdrs)
            rhdrs[RESUME_TOKENS] = ",".join(str(t) for t in delivered_toks)
            rhdrs[RESUME_SEED] = str(FAKE_SEED)
            async with session.post(f"{router_urls[1]}/v1/completions",
                                    json=body, headers=rhdrs) as resp:
                assert resp.status == 200
                raw = (await resp.content.read()).decode()
            events = [ln for ln in raw.splitlines() if ln.startswith("data:")]
            assert events[-1] == "data: [DONE]"
            chunks = [json.loads(e[5:]) for e in events
                      if e != "data: [DONE]"]
            rtoks = [t for c in chunks
                     for t in c.get("pstpu", {}).get("toks", [])]
            rtext = "".join(c["choices"][0].get("text", "") for c in chunks)
            # Token-identical join: nothing lost, nothing doubled.
            assert delivered_toks + rtoks == \
                [BASE_TOKEN + i for i in range(8)]
            assert delivered_text + rtext == "Hello " * 8

            # Survivor accounting: one peer resume, zero truncations.
            async with session.get(f"{router_urls[1]}/metrics") as resp:
                metrics_text = await resp.text()
            peer = trunc = 0.0
            for line in metrics_text.splitlines():
                if line.startswith(PEER + " "):
                    peer = float(line.rsplit(" ", 1)[1])
                if line.startswith(TRUNCATIONS + " "):
                    trunc = float(line.rsplit(" ", 1)[1])
            assert peer >= 1
            assert trunc == 0

            resume = _resume_bodies(engines)
            assert len(resume) == 1
            assert resume[0]["resume_tokens"] == delivered_toks
            assert resume[0]["resume_seed"] == FAKE_SEED
    finally:
        for proc in procs:
            if proc.poll() is None:
                proc.kill()
                proc.wait(timeout=30)
        for srv in servers:
            await srv.close()
