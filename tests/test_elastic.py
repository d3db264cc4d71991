"""Elastic fast-start tests (docs/ELASTIC.md).

Fast (tier-1): hot-chain enumeration + the 'H' wire op, prefix-index
adoption, ramp-in scoring, discovery-timestamp preservation, the
scraper's immediate mid-run backend scrape, compile-cache setup
degradation, and the soak scale-event plumbing (pure parsers).

Slow (CI "Elastic scale-out" step): compile-cache keying across boots
(warm boot measurably faster, hit counter > 0; changed model /
kv-cache-dtype miss cleanly), weight/compile-overlap parity, and the
prewarm pull end-to-end (blocks adopted, outputs token-identical with
prewarm on vs off).
"""

import asyncio
import json
import os
import struct
import time

import pytest

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.kv_cache import BlockPoolManager, _block_hash
from production_stack_tpu.kv_offload.chain_lru import ChainStore
from production_stack_tpu.kv_offload.serde import pack_chain
from production_stack_tpu.kv_offload.server import PyKVServer


# --------------------------------------------------------------- hot chains
def test_hot_chains_hottest_first_and_deduped():
    st = ChainStore(1 << 20)
    st.put(b"a", b"A" * 8)
    st.put(b"b", b"B" * 8, parent=b"a")
    st.put(b"c", b"C" * 8, parent=b"b")
    st.put(b"d", b"D" * 8)
    st.get(b"c")  # chain a->b->c becomes the hottest
    chains = st.hot_chains(4)
    assert chains[0] == [b"a", b"b", b"c"]  # root -> leaf
    assert chains[1] == [b"d"]
    # Entries covered by a hotter chain are not re-emitted.
    assert sum(len(c) for c in chains) == 4


def test_hot_chains_respects_top_k_and_block_budget():
    st = ChainStore(1 << 20)
    for i in range(6):
        st.put(f"k{i}".encode(), b"X" * 8)
    assert len(st.hot_chains(2)) == 2
    # Block budget truncates rather than overflows.
    total = sum(len(c) for c in st.hot_chains(10, max_blocks=3))
    assert total == 3


def test_hot_chains_is_read_only():
    """Enumerating hot chains must not refresh recency (same contract as
    the 'I' residency op) — a router prewarm poll could otherwise keep
    cold chains warm forever."""
    st = ChainStore(30)  # fits ~3 blobs of 8 bytes + overhead slack
    st.put(b"a", b"A" * 8)
    st.put(b"b", b"B" * 8)
    st.hot_chains(10)          # would move keys if it touched
    st.put(b"c", b"C" * 8)
    st.put(b"d", b"D" * 8)     # evicts the LRU head: must be 'a'
    assert not st.contains(b"a")
    assert st.contains(b"d")


def test_hot_chains_wire_op():
    srv = PyKVServer(1 << 20)
    srv._dispatch(b"P", b"root", pack_chain(b"", b"p1"))
    srv._dispatch(b"P", b"leaf", pack_chain(b"root", b"p2"))
    status, payload = srv._dispatch(b"H", b"", struct.pack("<II", 4, 64))
    assert status == 0
    doc = json.loads(payload)
    assert doc["chains"][0] == [b"root".hex(), b"leaf".hex()]
    # Malformed payload -> STATUS_ERROR, never a crash.
    status, _ = srv._dispatch(b"H", b"", b"\x01")
    assert status == 2


# ----------------------------------------------------------- block adoption
def test_adopt_full_block_feeds_prefix_lookup():
    """A prewarmed block adopted under its store hash is hit by a later
    prompt exactly like a locally computed prefix block."""
    bm = BlockPoolManager(8, 4)
    tokens = list(range(9))                     # 2 full blocks + 1 tail
    h1 = _block_hash(b"", tokens[:4])
    h2 = _block_hash(h1, tokens[4:8])
    blks = bm.allocate_blocks(2)
    assert bm.adopt_full_block(blks[0], h1, b"")
    assert bm.adopt_full_block(blks[1], h2, h1)
    bm.free_blocks(blks)                        # park evictable (cached)
    cached, n_cached = bm.lookup_prefix(tokens)
    assert cached == blks and n_cached == 8
    # Chain links survive for the spiller.
    assert bm.parent_hash(h2) == h1
    # Duplicate adoption is refused (caller frees the extra block).
    extra = bm.allocate_blocks(1)
    assert not bm.adopt_full_block(extra[0], h1, b"")
    bm.free_blocks(extra)


def test_adopted_blocks_evict_like_cached_blocks():
    bm = BlockPoolManager(3, 4)                 # null + 2 usable
    blks = bm.allocate_blocks(2)
    bm.adopt_full_block(blks[0], b"h-a", b"")
    bm.adopt_full_block(blks[1], b"h-b", b"h-a")
    bm.free_blocks(blks)
    # Serving pressure reclaims them LRU — prewarm never wedges the pool.
    fresh = bm.allocate_blocks(2)
    assert fresh is not None and len(fresh) == 2


# ------------------------------------------------------------------ ramp-in
def test_ramp_in_penalty_decay():
    from production_stack_tpu.router.routing_logic import ramp_in_penalty
    from production_stack_tpu.router.service_discovery import EndpointInfo

    now = time.time()
    fresh = EndpointInfo(url="http://new", added_timestamp=now)
    old = EndpointInfo(url="http://old", added_timestamp=now - 1000)
    assert ramp_in_penalty(fresh, 10.0, now=now) == pytest.approx(1.0)
    assert ramp_in_penalty(fresh, 10.0, now=now + 5) == pytest.approx(0.5)
    assert ramp_in_penalty(fresh, 10.0, now=now + 10) == 0.0
    assert ramp_in_penalty(old, 10.0, now=now) == 0.0
    assert ramp_in_penalty(fresh, 0.0, now=now) == 0.0   # disabled


def _mk_router(cls, **kw):
    r = cls.__new__(cls)
    r.__init__(**kw)
    return r


class _Req:
    headers: dict = {}
    json_body: dict = {}


def test_cache_aware_router_ramps_in_new_backend():
    from production_stack_tpu.router.routing_logic import (
        CacheAwareLoadBalancingRouter,
    )
    from production_stack_tpu.router.service_discovery import EndpointInfo

    now = time.time()
    eps = [
        EndpointInfo(url="http://a", added_timestamp=now - 1000),
        EndpointInfo(url="http://b", added_timestamp=now),  # joining
    ]
    r = _mk_router(CacheAwareLoadBalancingRouter, session_key="sid",
                   ramp_in_seconds=60.0)
    # Equal (empty) stats: without ramp-in, the tie sorts to "a" anyway,
    # so assert the stronger direction — even with "a" visibly loaded,
    # the mid-ramp joiner still loses.
    from production_stack_tpu.router.stats.engine_stats import EngineStats

    stats = {"http://a": EngineStats(num_running_requests=8)}
    assert r.route_request(eps, stats, {}, _Req()) == "http://a"
    # Ramp expired: the loaded backend loses to the (idle) joiner.
    r2 = _mk_router(CacheAwareLoadBalancingRouter, session_key="sid",
                    ramp_in_seconds=0.0)
    assert r2.route_request(eps, stats, {}, _Req()) == "http://b"


def test_prefix_match_beats_ramp_penalty():
    """Ramp-in is a weight, not a gate: a strong prefix match on the
    joining (prewarmed!) engine still wins."""
    from production_stack_tpu.router.routing_logic import PrefixAwareRouter
    from production_stack_tpu.router.service_discovery import EndpointInfo
    from production_stack_tpu.router.stats.engine_stats import (
        PrefixIndexSnapshot,
    )

    now = time.time()
    eps = [
        EndpointInfo(url="http://a", added_timestamp=now - 1000),
        EndpointInfo(url="http://b", added_timestamp=now),
    ]
    token_ids = list(range(33))
    hashes = []
    prev = b""
    for i in range(2):
        prev = _block_hash(prev, token_ids[i * 16:(i + 1) * 16])
        hashes.append(prev.hex()[:16])
    index = {
        "http://b": PrefixIndexSnapshot(
            block_size=16, entries=frozenset(hashes),
            scraped_at=time.time(),
        ),
    }
    r = _mk_router(PrefixAwareRouter, ramp_in_seconds=30.0,
                   index_provider=lambda: index)

    class Req:
        headers: dict = {}
        json_body = {"prompt": token_ids}

    assert r.route_request(eps, {}, {}, Req()) == "http://b"
    assert r.routed_by_index == 1


# ------------------------------------------------- discovery timestamp ages
def test_static_reconfigure_preserves_added_timestamps():
    from production_stack_tpu.router import service_discovery as sd

    sd.initialize_service_discovery(
        "static", urls=["http://a"], models=[["m"]],
    )
    ts_a = sd.get_service_discovery().get_endpoint_info()[0].added_timestamp
    time.sleep(0.05)
    # Scale-out reconfigure: a joins b.
    sd.initialize_service_discovery(
        "static", urls=["http://a", "http://b"], models=[["m"], ["m"]],
    )
    eps = {e.url: e for e in sd.get_service_discovery().get_endpoint_info()}
    assert eps["http://a"].added_timestamp == ts_a          # preserved
    assert eps["http://b"].added_timestamp > ts_a           # genuinely new
    sd._service_discovery = None


# ------------------------------------------------- immediate mid-run scrape
def test_scraper_scrapes_mid_run_backend_add_immediately(monkeypatch):
    """A backend appearing between full passes is scraped right away and
    the one-shot on_new_backend (prewarm) hook fires exactly once for
    it — but never for the boot-time fleet."""
    from production_stack_tpu.router import service_discovery as sd
    from production_stack_tpu.router.stats.engine_stats import (
        EngineStats,
        EngineStatsScraper,
        PrefixIndexSnapshot,
    )

    sd.initialize_service_discovery(
        "static", urls=["http://a"], models=[["m"]],
    )
    prewarmed = []
    sc = EngineStatsScraper(
        scrape_interval=3600.0, scrape_prefix_index=True,
        discovery_poll_interval=0.0,          # we drive the passes by hand
        on_new_backend=prewarmed.append,
    )
    try:
        monkeypatch.setattr(
            sc, "_scrape_one_endpoint",
            lambda _req, url: EngineStats(num_running_requests=1),
        )
        monkeypatch.setattr(
            sc, "_scrape_prefix_index",
            lambda _req, url: PrefixIndexSnapshot(
                block_size=16, entries=frozenset({"ab"}),
                scraped_at=time.time(),
            ),
        )
        sc._scrape_metrics()                   # first full pass
        assert "http://a" in sc.get_engine_stats()
        assert prewarmed == []                 # boot fleet never prewarmed
        # Mid-run scale-out: b appears.
        sd.initialize_service_discovery(
            "static", urls=["http://a", "http://b"],
            models=[["m"], ["m"]],
        )
        sc._scrape_new_backends()
        assert "http://b" in sc.get_engine_stats()      # visible NOW
        assert "http://b" in sc.get_prefix_index()
        assert prewarmed == ["http://b"]
        sc._scrape_new_backends()              # idempotent
        assert prewarmed == ["http://b"]
    finally:
        sc.close()
        sd._service_discovery = None


# ------------------------------------------------ compile-cache placement
@pytest.fixture
def _restore_jax_cache_dir():
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("placed_by", ["variable", "config", "neither"])
def test_setup_compilation_cache_placement(placed_by, monkeypatch, tmp_path,
                                           _restore_jax_cache_dir):
    """JAX_COMPILATION_CACHE_DIR places the cache from outside: the
    directory is used as it is and jax's own cache-dir config is left at
    the variable's value (no update in code, no fingerprint sub-directory).
    Unset, the configured directory gets the platform fingerprint and jax
    is pointed at it; with neither there is no cache."""
    import jax

    from production_stack_tpu.engine.runner import _setup_compilation_cache

    dev = jax.devices()[0]
    outside = str(tmp_path / "placed-from-outside")
    if placed_by == "variable":
        # What jax does at import with the variable set.
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", outside)
        jax.config.update("jax_compilation_cache_dir", outside)
        updates = []
        real_update = jax.config.update
        monkeypatch.setattr(
            jax.config, "update",
            lambda k, v: (updates.append(k), real_update(k, v))[1],
        )
        assert _setup_compilation_cache(str(tmp_path / "cfg"), dev) == outside
        assert jax.config.jax_compilation_cache_dir == outside
        assert "jax_compilation_cache_dir" not in updates
        # An empty configured dir does not switch an outside cache off.
        assert _setup_compilation_cache("", dev) == outside
    elif placed_by == "config":
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        got = _setup_compilation_cache(str(tmp_path / "cfg"), dev)
        assert os.path.dirname(got) == str(tmp_path / "cfg")
        assert os.path.basename(got).startswith(f"{dev.platform}-")
        assert f"jax{jax.__version__}" in got
        assert jax.config.jax_compilation_cache_dir == got
        # Fixed for a fixed input: the directory is part of the cache key.
        assert _setup_compilation_cache(str(tmp_path / "cfg"), dev) == got
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        before = jax.config.jax_compilation_cache_dir
        assert _setup_compilation_cache("", dev) is None
        assert jax.config.jax_compilation_cache_dir == before


def test_default_compilation_cache_dir_is_fixed_and_in_checkout():
    from production_stack_tpu.engine.config import (
        DEFAULT_COMPILATION_CACHE_DIR,
    )

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert DEFAULT_COMPILATION_CACHE_DIR == os.path.join(
        repo, ".pstpu_xla_cache"
    )
    assert EngineConfig().compilation_cache_dir == \
        DEFAULT_COMPILATION_CACHE_DIR
    with open(os.path.join(repo, ".gitignore")) as f:
        assert ".pstpu_xla_cache/" in f.read().split()


def test_cache_entries_unreadable_dir(tmp_path):
    from production_stack_tpu.engine.runner import _cache_entries

    assert _cache_entries(None) is None
    assert _cache_entries("/nonexistent/pstpu-cache-dir") is None
    (tmp_path / "abc-cache").write_bytes(b"x")
    (tmp_path / "abc-atime").write_bytes(b"x")
    assert _cache_entries(str(tmp_path)) == frozenset({"abc-cache"})


# ------------------------------------------------------- engine-level noop
async def test_prewarm_noop_without_shared_tier():
    from production_stack_tpu.engine.engine import ServingEngine

    cfg = EngineConfig(model="tiny-llama", max_model_len=128,
                       max_num_seqs=2, max_num_batched_tokens=64,
                       num_kv_blocks=16, enable_warmup=False,
                       compilation_cache_dir="")
    eng = ServingEngine(cfg)
    await eng.start()
    try:
        res = await eng.prewarm(top_k=4)
        assert res["blocks"] == 0
        assert "no shared tier" in res["reason"]
        s = eng.stats()
        for key in ("startup_weight_load_seconds", "startup_total_seconds",
                    "startup_cache_hit_families",
                    "startup_cache_miss_families"):
            assert key in s
    finally:
        await eng.stop()


# ------------------------------------------------------- soak scale events
def test_parse_scale_event_schedule():
    from benchmarks.soak import parse_fault_schedule

    faults = parse_fault_schedule(json.dumps([
        {"at_s": 5, "action": "scale_out_engine",
         "when_queue_depth": 4, "wait_s": 10},
        {"at_s": 30, "action": "scale_in_engine"},
    ]))
    assert faults[0].action == "scale_out_engine"
    assert faults[0].params == {"when_queue_depth": 4, "wait_s": 10}
    assert faults[1].action == "scale_in_engine"


def test_ttft_met_count_parses_histogram():
    from benchmarks.soak import _metric_values, _ttft_met_count

    text = "\n".join([
        'vllm:time_to_first_token_seconds_bucket{model_name="m",le="0.5"} 0',
        'vllm:time_to_first_token_seconds_bucket{model_name="m",le="1.0"} 2',
        'vllm:time_to_first_token_seconds_bucket{model_name="m",le="+Inf"} 5',
        'router_queue_depth{server="http://a"} 3',
        'router_queue_depth{server="http://b"} 4',
    ])
    assert _ttft_met_count(text, 1.0) == 2     # le=1.0 bucket
    assert _ttft_met_count(text, 0.5) == 0
    assert _ttft_met_count(text, 0.1) == 0     # no bucket <= bound
    assert _metric_values(text, "router_queue_depth") == [3.0, 4.0]


def test_soak_report_carries_elastic_section():
    from benchmarks.soak import SLOClass, build_report

    cls = SLOClass("interactive", ttft_slo_s=1.0, itl_slo_s=0.1,
                   answer_tokens=8, share=1.0)
    rung_cls = {
        "requests": 1, "ok": 1, "met": 1, "shed": 0, "shed_retries": 0,
        "errors": 0, "status_5xx": 0, "truncated": 0, "attainment": 1.0,
        "p50_ttft_s": 0.1, "p99_ttft_s": 0.1, "p99_itl_s": 0.01,
        "output_tok_s": 1.0, "goodput_tok_s": 1.0,
        "slo": {"ttft_s": 1.0, "itl_s": 0.1},
    }
    elastic = [{
        "event": "scale_out", "url": "http://new",
        "engine_ready_s": 12.3, "time_to_first_slo_met_token_s": 15.0,
        "first_minute_kv_hit_rate": 0.4,
    }]
    report = build_report(
        model="m", backend="cpu", num_engines=2, classes=[cls],
        rungs=[{"qps": 1.0, "duration_s": 10.0, "users": {},
                "capped_classes": [], "classes": {"interactive": rung_cls}}],
        faults=[], autoscaler_gauges={}, elastic=elastic,
    )
    assert report["elastic"] == elastic


# =================================================================== slow
@pytest.mark.slow
def test_compile_cache_keying_warm_boot_and_parity(tmp_path):
    """Second boot with an identical config hits the persistent cache
    (hit counter > 0, zero misses, measurably faster warmup) and produces
    token-identical greedy output; a changed kv-cache dtype or model
    misses cleanly (no stale-artifact replay, no crash)."""
    from production_stack_tpu.engine.engine import ServingEngine
    from production_stack_tpu.engine.sampling import SamplingParams

    cache = str(tmp_path / "xla-cache")

    def boot(**overrides):
        cfg = EngineConfig(**{
            "model": "tiny-llama", "max_model_len": 128,
            "max_num_seqs": 2, "max_num_batched_tokens": 64,
            "num_kv_blocks": 16, "enable_warmup": True,
            "decode_loop": "while",   # warmup executes zero iterations
            "compilation_cache_dir": cache, **overrides,
        })
        eng = ServingEngine(cfg)

        async def run():
            await eng.start()
            outs = []
            async for o in eng.generate(
                prompt="hello elastic world",
                sampling=SamplingParams(temperature=0.0, max_tokens=6),
            ):
                outs.append(o)
            toks = list(outs[-1].token_ids)
            await eng.stop()
            return toks

        toks = asyncio.run(run())
        return eng, toks

    cold, cold_toks = boot()
    assert cold.runner.startup_cache_miss_families > 0
    assert cold.runner.startup_cache_hit_families == 0
    cold_warmup_s = cold.runner.startup_warmup_seconds

    warm, warm_toks = boot()
    assert warm.runner.startup_cache_hit_families > 0
    assert warm.runner.startup_cache_miss_families == 0
    assert warm.runner.startup_warmup_seconds < cold_warmup_s
    # Greedy parity: the cache only skips compilation, never changes math.
    assert warm_toks == cold_toks

    # Changed kv-cache dtype: different lowered modules -> clean misses.
    qcold, _ = boot(kv_cache_dtype="int8")
    assert qcold.runner.startup_cache_miss_families > 0

    # Changed model: clean misses too (tiny-opt shares no step modules).
    ocold, _ = boot(model="tiny-opt", attn_impl="window")
    assert ocold.runner.startup_cache_miss_families > 0


@pytest.mark.slow
def test_overlap_weight_load_parity(tmp_path):
    """The weight/compile-overlap path produces token-identical output to
    the serial path and records the phase telemetry."""
    from production_stack_tpu.engine.engine import ServingEngine
    from production_stack_tpu.engine.sampling import SamplingParams

    cache = str(tmp_path / "xla-cache")

    def boot(overlap):
        cfg = EngineConfig(
            model="tiny-llama", max_model_len=128, max_num_seqs=2,
            max_num_batched_tokens=64, num_kv_blocks=16,
            enable_warmup=True, decode_loop="while",
            compilation_cache_dir=cache, overlap_weight_load=overlap,
        )
        eng = ServingEngine(cfg)

        async def run():
            await eng.start()
            outs = []
            async for o in eng.generate(
                prompt="the quick brown fox",
                sampling=SamplingParams(temperature=0.0, max_tokens=6),
            ):
                outs.append(o)
            toks = list(outs[-1].token_ids)
            await eng.stop()
            return toks

        return asyncio.run(run()), eng

    serial_toks, serial = boot(False)
    overlap_toks, overlapped = boot(True)
    assert overlap_toks == serial_toks
    assert overlapped.runner.weights_ready
    s = overlapped.stats()
    assert s["startup_total_seconds"] > 0
    # The warm (manifest-verified) boot's eager + deferred counts cover
    # exactly the cold boot's full variant set.
    assert (s["startup_cache_hit_families"]
            + s["startup_cache_miss_families"]
            + overlapped.runner.startup_deferred_families) \
        == (serial.runner.startup_cache_hit_families
            + serial.runner.startup_cache_miss_families)


@pytest.mark.slow
def test_prewarm_pull_end_to_end(tmp_path):
    """Engine A serves prompts and spills to a shared tier; engine B
    prewarm-pulls the hot chains, serves the shared prefix from device
    KV on its FIRST request, and its output is token-identical to an
    unprewarmed control engine (prewarm moves bytes, never tokens)."""
    from benchmarks.stack import launch_kv_server_handle
    from production_stack_tpu.engine.engine import ServingEngine
    from production_stack_tpu.engine.sampling import SamplingParams

    kv = launch_kv_server_handle(log_dir=str(tmp_path))
    try:
        def mk_engine():
            cfg = EngineConfig(
                model="tiny-llama", max_model_len=256, max_num_seqs=2,
                max_num_batched_tokens=64, num_kv_blocks=32,
                enable_warmup=False, compilation_cache_dir="",
                kv_remote_url=kv.url,
            )
            return ServingEngine(cfg)

        shared = ("system: you are a helpful assistant that answers "
                  "benchmark questions tersely and accurately. user: ")
        prompt = shared + "what is elasticity?"

        async def generate(eng, text):
            outs = []
            async for o in eng.generate(
                prompt=text,
                sampling=SamplingParams(temperature=0.0, max_tokens=8),
            ):
                outs.append(o)
            return list(outs[-1].token_ids), outs[-1].num_cached_tokens

        async def scenario():
            a = mk_engine()
            await a.start()
            toks_a, _ = await generate(a, prompt)
            # Wait for the spiller to land A's blocks in the remote tier.
            deadline = time.monotonic() + 20
            from production_stack_tpu.kv_offload.remote import (
                RemoteKVClient,
            )

            probe = RemoteKVClient(kv.url)
            while time.monotonic() < deadline:
                if probe.stats().get("entries", 0) >= 2:
                    break
                await asyncio.sleep(0.2)
            entries = probe.stats().get("entries", 0)
            probe.close()
            assert entries >= 2, "engine A never spilled to the tier"
            await a.stop()

            b = mk_engine()
            await b.start()
            res = await b.prewarm(top_k=8)
            assert res["blocks"] > 0, res
            toks_b, cached_b = await generate(b, prompt)
            await b.stop()

            control = mk_engine()
            # Control: no shared restore either — prewarm-vs-nothing
            # token parity (the tier path's own parity is PR-8's bar).
            control.offload = None
            control.scheduler.offload = None
            await control.start()
            toks_c, _ = await generate(control, prompt)
            await control.stop()
            return toks_a, toks_b, cached_b, toks_c

        toks_a, toks_b, cached_b, toks_c = asyncio.run(scenario())
        assert toks_b == toks_a == toks_c     # prewarm never changes tokens
        # The first request on B hit prewarmed device KV.
        assert cached_b > 0
    finally:
        kv.terminate()
