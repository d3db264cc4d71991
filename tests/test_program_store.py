"""A manifest-verified warm boot LOADS its programs (engine/program_store.py,
ModelRunner.warmup / _dispatch; ISSUE 57): what a cold boot compiled it
stores, a warm boot under the same key loads it and traces nothing, a
deferred variant is loaded at its first use, and whatever the key does not
cover or the store cannot supply takes the traced path as it always did.

One cold boot a module (tiny-llama on the CPU, a temporary cache directory);
the tests read what its boots recorded.
"""

import asyncio
import json
import os
import pickle

import pytest

from production_stack_tpu.engine.config import EngineConfig

ENVELOPE = dict(
    model="tiny-llama", max_model_len=64, max_num_seqs=2,
    max_num_batched_tokens=32, num_decode_steps=4, num_kv_blocks=16,
    enable_warmup=True, decode_loop="while",   # warm-up runs zero steps
)


class Traces:
    """Python calls of ``_decode_impl`` / ``_prefill_impl``: a call is a
    trace (the jitted function runs its Python only to trace)."""

    def __init__(self, patch):
        from production_stack_tpu.engine.runner import ModelRunner

        self.n = 0
        for name in ("_decode_impl", "_prefill_impl"):
            patch.setattr(ModelRunner, name,
                          self._counting(getattr(ModelRunner, name)))

    def _counting(self, impl):
        def counted(runner, *args, **kwargs):
            self.n += 1
            return impl(runner, *args, **kwargs)
        return counted


def boot(cache, traces, **overrides) -> dict:
    """One engine's life: start (warm-up), a seeded greedy request, a
    ``logprobs`` request, stop. What it did, for the tests."""
    from production_stack_tpu.engine.engine import ServingEngine
    from production_stack_tpu.engine.sampling import SamplingParams
    from production_stack_tpu.server.metrics import render_engine_metrics

    eng = ServingEngine(EngineConfig(
        **{**ENVELOPE, "compilation_cache_dir": cache, **overrides}))
    seen = {}

    async def ask(sampling):
        outs = []
        async for o in eng.generate(prompt="hello elastic world",
                                    sampling=sampling):
            outs.append((list(o.token_ids), o.logprobs))
        return outs

    async def life():
        at = traces.n
        await eng.start()
        seen["traced_at_boot"] = traces.n - at
        r = eng.runner
        seen["table_at_boot"] = set(r._programs)
        at = traces.n
        seen["greedy"] = await ask(
            SamplingParams(temperature=0.0, seed=7, max_tokens=6))
        seen["traced_by_greedy"] = traces.n - at
        at = traces.n
        seen["logprobs"] = await ask(
            SamplingParams(temperature=0.0, max_tokens=6, logprobs=2))
        seen["traced_by_logprobs"] = traces.n - at
        seen["table"] = set(r._programs)
        seen["jitted"] = r._decode._cache_size() + r._prefill._cache_size()
        seen["version"] = eng.report()["engine"]
        seen["metrics"] = render_engine_metrics(eng, "tiny-llama")
        await eng.stop()

    asyncio.run(life())
    r = eng.runner
    seen.update(
        hit=r.startup_cache_hit_families, miss=r.startup_cache_miss_families,
        deferred=r.startup_deferred_families,
        loaded=r.startup_loaded_families, warmed=r.startup_warmed_families,
        failures=r.startup_warmup_failures, store=r._program_store(),
        cache_path=r.compilation_cache_path)
    return seen


@pytest.fixture(scope="module")
def boots(tmp_path_factory):
    """cold -> warm -> (one stored file cut short, one deleted) cut ->
    after: the boot after a dropped manifest, which stores again."""
    patch = pytest.MonkeyPatch()
    traces = Traces(patch)
    cache = str(tmp_path_factory.mktemp("xla-cache"))
    try:
        out = {"cold": boot(cache, traces), "warm": boot(cache, traces)}
        store = out["warm"]["store"]
        out["stored_bytes"] = {k: os.path.getsize(store.path(k))
                               for k in store.manifest()}
        victim = sorted(k for k in out["warm"]["table_at_boot"]
                        if k.startswith("decode"))[0]
        with open(store.path(victim), "rb") as f:
            whole = f.read()
        with open(store.path(victim), "wb") as f:
            f.write(whole[:len(whole) // 2])
        out["victim"] = victim
        # ... and a deferred variant's file is gone altogether.
        os.unlink(store.path(sorted(
            k for k in out["cold"]["table_at_boot"] if "+pen" in k)[0]))
        out["cut"] = boot(cache, traces)
        out["manifest_after_cut"] = store.manifest()
        out["after"] = boot(cache, traces)
        out["manifest_after"] = store.manifest()
        yield out
    finally:
        patch.undo()


def test_cold_boot_compiles_stores_and_serves_through_the_compiled(boots):
    cold = boots["cold"]
    assert cold["failures"] == 0
    assert cold["miss"] == cold["warmed"] > 0 and cold["hit"] == 0
    assert cold["loaded"] == 0 and cold["deferred"] == 0
    # Every variant it ran is in the table and in the store, and the
    # manifest lists them.
    assert len(cold["table_at_boot"]) == cold["warmed"]
    assert boots["warm"]["store"].key == cold["store"].key
    assert set(boots["stored_bytes"]) == cold["table_at_boot"]
    assert all(n > 0 for n in boots["stored_bytes"].values())


def test_warm_boot_traces_no_default_variant(boots):
    cold, warm = boots["cold"], boots["warm"]
    assert warm["failures"] == 0
    assert warm["traced_at_boot"] == 0
    assert warm["miss"] == 0
    assert warm["loaded"] == warm["hit"] == warm["warmed"] > 0
    # The default variants of the cold boot's families, the others deferred.
    defaults = {k for k in cold["table_at_boot"] if "+" not in k}
    assert warm["table_at_boot"] == defaults
    assert warm["deferred"] == cold["warmed"] - len(defaults) > 0


def test_warm_boot_answers_as_the_cold_boot_did(boots):
    cold, warm = boots["cold"], boots["warm"]
    assert warm["greedy"] == cold["greedy"]
    assert cold["greedy"][-1][0], "no token came back"
    # Tokens AND log-probabilities, exactly: the same executables.
    assert warm["logprobs"] == cold["logprobs"]
    assert any(lp for _, lp in cold["logprobs"])


def test_deferred_variant_loads_at_first_use_and_is_not_traced(boots):
    warm = boots["warm"]
    assert warm["traced_by_greedy"] == 0
    assert warm["traced_by_logprobs"] == 0
    first_used = warm["table"] - warm["table_at_boot"]
    assert first_used and all("+lp" in k for k in first_used)


def test_no_program_is_held_twice(boots):
    # What the table holds was never jitted in that process: the jitted
    # functions' own caches are empty after boot and both requests.
    for name in ("cold", "warm"):
        assert boots[name]["jitted"] == 0, name


def test_counter_in_version_document_and_metrics(boots):
    warm = boots["warm"]
    assert warm["version"]["loaded_families"] == warm["loaded"]
    assert warm["version"]["cache_hit_families"] == warm["hit"]
    assert warm["version"]["cache_miss_families"] == 0
    assert f"pstpu:startup_loaded_families{{model_name=\"tiny-llama\"}} " \
        f"{warm['loaded']}" in warm["metrics"]
    assert boots["cold"]["version"]["loaded_families"] == 0


def test_short_stored_file_traces_that_program_and_drops_the_manifest(boots):
    warm, cut = boots["warm"], boots["cut"]
    assert cut["failures"] == 0
    assert cut["traced_at_boot"] == 1            # the victim alone
    # ... and the deferred variant whose file was deleted is a miss at
    # boot, not a surprise inside serving.
    assert cut["miss"] == 2
    assert cut["loaded"] == cut["hit"] == warm["loaded"] - 1
    assert boots["victim"] not in cut["table_at_boot"]
    assert cut["table_at_boot"] == warm["table_at_boot"] - {boots["victim"]}
    assert boots["manifest_after_cut"] is None
    assert cut["greedy"] == warm["greedy"]


def test_boot_after_a_dropped_manifest_stores_again(boots):
    cold, after = boots["cold"], boots["after"]
    # The cold path: every variant traced, none loaded, none deferred.
    assert after["loaded"] == 0 and after["deferred"] == 0
    assert after["warmed"] == cold["warmed"]
    assert after["traced_at_boot"] >= cold["warmed"]
    # JAX's cache still held them, so they compile as hits; what XLA:CPU
    # loaded from its cache it cannot serialize whole (runner:
    # ``_stores_loaded``), so the new manifest lists what it could store.
    assert after["miss"] == 0 and after["hit"] == cold["warmed"]
    assert boots["manifest_after"] is not None
    assert after["greedy"] == cold["greedy"]


# ----------------------------------------------------------------- the key
def _store_of(cache, **overrides):
    from production_stack_tpu.engine.engine import ServingEngine

    eng = ServingEngine(EngineConfig(**{
        **ENVELOPE, "enable_warmup": False, "compilation_cache_dir": cache,
        **overrides}))
    return eng.runner._program_store()


@pytest.mark.parametrize("change", [
    "source", "max_num_seqs", "kv_cache_dtype", "xla_flags", "jax_version"])
def test_changed_key_finds_no_manifest(boots, change, monkeypatch):
    """What a trace used to notice is in the key: any of these names
    another manifest, so the boot takes the cold path."""
    from production_stack_tpu.engine import runner as runner_mod

    cache = os.path.dirname(boots["warm"]["cache_path"])
    same = _store_of(cache)
    assert same.key == boots["warm"]["store"].key
    assert same.manifest() is not None
    overrides = {}
    if change == "source":
        monkeypatch.setattr(runner_mod, "source_digest", lambda: "edited")
    elif change == "max_num_seqs":
        overrides["max_num_seqs"] = 4
    elif change == "kv_cache_dtype":
        overrides["kv_cache_dtype"] = "int8"
    elif change == "xla_flags":
        monkeypatch.setenv("LIBTPU_INIT_ARGS", "--xla_tpu_some_flag=true")
    else:
        monkeypatch.setattr(runner_mod.jax, "__version__", "0.0.0")
    other = _store_of(cache, **overrides)
    assert other.key != same.key
    assert other.manifest() is None


def test_changed_source_digest_boots_cold(boots, monkeypatch):
    """End to end: an edited package traces and compiles every variant
    again (JAX's cache, keyed by the lowered module, may still supply
    them) and loads nothing."""
    from production_stack_tpu.engine import runner as runner_mod

    monkeypatch.setattr(runner_mod, "source_digest", lambda: "edited")
    traces = Traces(monkeypatch)
    cache = os.path.dirname(boots["warm"]["cache_path"])
    edited = boot(cache, traces)
    assert edited["store"].key != boots["warm"]["store"].key
    assert edited["loaded"] == 0 and edited["deferred"] == 0
    assert edited["traced_at_boot"] >= edited["warmed"] \
        == boots["cold"]["warmed"]
    assert edited["greedy"] == boots["cold"]["greedy"]


def test_source_digest_reads_the_package():
    from production_stack_tpu.engine.program_store import source_digest

    digest = source_digest()
    assert len(digest) == 24 and digest == source_digest()


# ------------------------------------------------ beside JAX's own cache
def test_jax_cache_neither_counts_nor_evicts_stored_programs(tmp_path):
    """JAX's size-capped cache sums and evicts ``*-cache`` files (with their
    ``-atime`` markers) and nothing else in its directory."""
    from jax._src.lru_cache import LRUCache

    from production_stack_tpu.engine.program_store import ProgramStore

    store = ProgramStore(str(tmp_path), {"k": 1})
    store.write_manifest(["decode[1,2,4,0]"])
    stored = store.path("decode[1,2,4,0]+lp8")
    with open(stored, "wb") as f:
        f.write(b"x" * 4000)          # alone, four times the cap
    mine = {os.path.basename(stored), os.path.basename(store.manifest_path)}
    assert not any(n.endswith(("-cache", "-atime")) for n in mine)
    cache = LRUCache(str(tmp_path), max_size=1000)
    cache.put("a", b"a" * 600)
    cache.put("b", b"b" * 600)        # over the cap: "a" goes
    assert cache.get("a") is None and cache.get("b") == b"b" * 600
    assert mine <= set(os.listdir(tmp_path))
    assert os.path.getsize(stored) == 4000
    assert store.manifest() == frozenset({"decode[1,2,4,0]"})


def test_harness_cache_entries_names_only_compiled_programs(boots):
    """benchmarks/chip/lib/stack.py:cache_entries, the harness's hit / miss
    arithmetic, and the runner's own see the ``-cache`` files alone."""
    from benchmarks.chip.lib import stack
    from production_stack_tpu.engine.runner import _cache_entries

    path = boots["warm"]["cache_path"]
    names = set(os.listdir(path))
    stored = {n for n in names if n.startswith("pstpu-")}
    assert any(n.endswith(".bin") for n in stored)
    entries = stack.cache_entries(path)
    assert entries == _cache_entries(path)
    assert entries and not entries & stored
    assert all(n.endswith("-cache") for n in entries)


def test_stored_file_is_payload_and_trees(boots):
    store = boots["warm"]["store"]
    key = sorted(boots["warm"]["table_at_boot"] - {boots["victim"]})[0]
    from production_stack_tpu.engine.program_store import _decompress

    with open(store.path(key), "rb") as f:
        payload, in_tree, out_tree = pickle.loads(_decompress(f.read()))
    assert isinstance(payload, bytes) and payload
    assert in_tree.num_leaves > 0 and out_tree.num_leaves > 0
    with open(store.manifest_path) as f:
        assert set(json.load(f)) == {"programs"}


def test_without_a_cache_directory_nothing_is_stored():
    from production_stack_tpu.engine.engine import ServingEngine

    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        pytest.skip("the environment places a cache")
    eng = ServingEngine(EngineConfig(**{
        **ENVELOPE, "enable_warmup": False, "compilation_cache_dir": ""}))
    assert eng.runner._program_store() is None


# ------------------------------- every architecture's trees make the trip
def _tiny_of(config_dir: str) -> str:
    """The tiny preset of the architecture a benchmark configuration is."""
    from production_stack_tpu.models.config import (
        NAMED_CONFIGS,
        resolve_model_config,
    )

    mc = resolve_model_config(config_dir)
    for name, preset in sorted(NAMED_CONFIGS.items()):
        if name.startswith("tiny-") and preset.arch == mc.arch \
                and preset.hc_mult == mc.hc_mult:
            return name
    raise AssertionError(f"no tiny preset of arch {mc.arch}")


CONFIGS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "benchmarks", "chip", "configs")


@pytest.mark.parametrize("config", sorted(os.listdir(CONFIGS)))
def test_trees_survive_store_and_load(config, tmp_path):
    """Of each benchmark configuration's architecture (its tiny preset: a
    few layers at tiny widths): a decode and a prefill program compiled,
    stored, loaded and RUN: the argument and result trees, the donation
    of the pools and the state pools' tuple make the round trip."""
    import jax
    import jax.numpy as jnp

    from production_stack_tpu.engine.engine import ServingEngine
    from production_stack_tpu.engine.program_store import ProgramStore
    from production_stack_tpu.engine.runner import NUM_SCALARS

    preset = _tiny_of(os.path.join(CONFIGS, config))
    eng = ServingEngine(EngineConfig(**{
        **ENVELOPE, "model": preset, "enable_warmup": False,
        "dtype": "float32", "compilation_cache_dir": str(tmp_path)}))
    r = eng.runner
    store = ProgramStore(str(tmp_path), {"test": config})
    devices = list(r.mesh.devices.flat)
    zeros = jnp.zeros((1, 1, 1, 1, 1), r.dtype)
    counts = jnp.zeros((1, 1), jnp.int32)

    db, mb, dk, cached = [f for f in r.reachable_decode_families()
                          if not f[3]][0]
    args = (r.params, jnp.zeros((NUM_SCALARS * db + db * mb,), jnp.int32),
            r.kv_k, r.kv_v, *r._scale_pool_args(), zeros, zeros, counts,
            r._zero_last, *r._spec_pool_args(), r.state_pools)
    compiled = r._decode.lower(
        *args, b=db, mb=mb, num_steps=dk, use_cached_window=False,
        has_penalties=False, logprobs_k=0, spec_on=True).compile()
    store.save("decode", compiled)
    loaded = store.load("decode", devices)
    assert loaded.in_tree == compiled.in_tree
    assert loaded.out_tree == compiled.out_tree
    pool = r.kv_k
    out = loaded(*args)
    assert pool.is_deleted()                     # donated, as compiled
    r.kv_k, r.kv_v, r.state_pools = out[1], out[2], out[16]
    assert isinstance(r.state_pools, tuple)
    assert len(r.state_pools) == len(r.state_specs)

    pb, t, mb, has_window = [f for f in r.reachable_prefill_families()
                             if not f[3]][0]
    length, _, shape = r._prefill_program_shape(pb, t, mb, has_window)
    args = (r.params, jnp.zeros((length,), jnp.int32), r.kv_k, r.kv_v,
            *r._scale_pool_args(), counts, *r._spec_pool_args(),
            r.state_pools)
    compiled = r._prefill.lower(
        *args, **shape, has_penalties=False, logprobs_k=0).compile()
    store.save("prefill", compiled)
    loaded = store.load("prefill", devices)
    assert loaded.in_tree == compiled.in_tree
    assert loaded.out_tree == compiled.out_tree
    pool = r.kv_k
    out = loaded(*args)
    assert pool.is_deleted()
    assert isinstance(out[12], tuple) and len(out[12]) == len(r.state_specs)
    assert [x.shape for x in out[12]] == [x.shape for x in args[-1]]
    jax.block_until_ready(out[1])
