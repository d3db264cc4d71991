"""The memory ledger (engine/memory_ledger.py; docs/OBSERVABILITY.md, "What
holds the HBM"): a rise of the allocator's peak is ONE event on the
dispatch that raised it, with the families in flight and how much of the
peak residents and programs explain; a family run alone is measured from
its three reads, one first seen under traffic is marked; building the
ledger compiles nothing; a backend that reports no memory gives residents
from array sizes, no events, no span attributes, and raises nothing.

The CPU reports no ``memory_stats()``, so every reading here is a stub:
the ledger takes its reading function as an argument, and an engine's is
``ModelRunner.device_memory``."""

import threading

import pytest

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.engine import ServingEngine
from production_stack_tpu.engine.memory_ledger import (
    HOLDERS,
    MemoryLedger,
    fullest,
)
from production_stack_tpu.engine.runner import ModelRunner
from production_stack_tpu.engine.sampling import SamplingParams

GB = 10 ** 9
LIMIT = 16 * GB


class Allocator:
    """A scripted device: ``use(n)`` sets bytes in use, the peak follows."""

    def __init__(self, in_use=0, devices=1):
        self.in_use = in_use
        self.peak = in_use
        self.devices = devices
        self.reads = 0

    def use(self, in_use):
        self.in_use = in_use
        self.peak = max(self.peak, in_use)

    def __call__(self):
        self.reads += 1
        return [{"bytes_in_use": self.in_use, "peak_bytes_in_use": self.peak,
                 "bytes_limit": LIMIT, "largest_alloc_size": 123}
                for _ in range(self.devices)]


def program(key, kind="decode"):
    return {"key": key, "kind": kind, "family": [1, 2, 3, 0],
            "has_penalties": False, "logprobs_k": 0, "spec_on": True}


def warmed(ledger, device, step, key, code, base, kind="decode"):
    """One warm-up family: a read before its enqueue, one after, one more
    (no sync). ``code`` is what its first run loads, which stays."""
    device.use(base)
    ledger.quiet()
    device.use(base + code)
    said = ledger.issued(step, program(key, kind), 4, 0.5)
    ledger.fetched(step)
    return said


def rises(ledger):
    """The events less the first read's (``at`` ``boot``)."""
    return [e for e in ledger.events if e["at"] != "boot"]


# ------------------------------------------------------------- the events
def test_a_rise_is_one_event_on_the_dispatch_that_raised_it():
    device = Allocator(10 * GB)
    ledger = MemoryLedger(device)
    said = warmed(ledger, device, 0, "decode[8]", 2 * GB, 10 * GB)
    assert said == {"hbm": 12 * GB, "hbm_peak": 12 * GB, "hbm_limit": LIMIT,
                    "hbm_reserved": 0, "hbm_explained": 12 * GB}
    warmed(ledger, device, 1, "prefill[1]", 1 * GB, 12 * GB, "prefill")
    assert [e["rose_by"] for e in rises(ledger)] == [2 * GB, GB]
    ledger.build({"tpu:0": {"weights": 6 * GB, "kv": 3 * GB}}, [])
    assert ledger.phase == "serving"
    assert ledger.residents == {"weights": 6 * GB, "kv": 3 * GB,
                                "other": 4 * GB}
    # Under traffic: a decode, then a prefill enqueued behind it that finds
    # 1.5 GB more in use than anything resident accounts for.
    ledger.issued(7, program("decode[8]"), 5)
    assert len(rises(ledger)) == 2          # 13 GB was warm-up's peak
    device.use(14 * GB + GB // 2)
    said = ledger.issued(8, program("prefill[1]", "prefill"), 3, 0.25)
    assert said["hbm_explained"] == 13 * GB
    assert ledger.events[-1] == {
        "step": 8, "phase": "serving", "at": "issue", "kind": "prefill",
        "family": "prefill[1]", "rows": 3,
        "in_flight": ["decode[8]", "prefill[1]"], "compiled": 0.25,
        "rose_by": GB + GB // 2, "bytes_in_use": 14 * GB + GB // 2,
        "peak_bytes_in_use": 14 * GB + GB // 2, "bytes_limit": LIMIT,
        "largest_alloc_size": 123, "explained": 13 * GB,
        "unexplained": GB + GB // 2,
    }
    assert ledger.rise_at(8, "issue") == {
        "by": GB + GB // 2, "to": 14 * GB + GB // 2,
        "unexplained": GB + GB // 2}
    assert ledger.rise_at(8, "fetch") is None
    assert ledger.rise_at(7, "issue") is None
    assert (ledger.rises, ledger.rise_bytes) == (
        {"warmup": 2, "serving": 1},
        {"warmup": 3 * GB, "serving": GB + GB // 2})


def test_a_rise_the_sync_side_read_finds_is_the_fetched_dispatchs():
    device = Allocator(10 * GB)
    ledger = MemoryLedger(device)
    ledger.build({"tpu:0": {"weights": 10 * GB}}, [])
    ledger.issued(0, program("decode[8]"), 2)
    ledger.issued(1, program("prefill[1]", "prefill"), 1)
    device.use(14 * GB)
    device.use(10 * GB)
    said = ledger.fetched(0)
    assert said == {"hbm": 10 * GB, "hbm_peak": 14 * GB, "hbm_limit": LIMIT,
                    "hbm_reserved": 0}
    event = ledger.events[-1]
    assert (event["step"], event["at"], event["family"]) == (
        0, "fetch", "decode[8]")
    assert event["in_flight"] == ["decode[8]", "prefill[1]"]
    assert (event["explained"], event["unexplained"]) == (10 * GB, 4 * GB)
    assert "rows" not in event
    assert ledger.rise_at(0, "fetch")["by"] == 4 * GB


def test_the_first_read_is_nobodys_rise_and_is_kept_as_the_boot_event():
    device = Allocator(9 * GB)
    device.use(14 * GB)        # weights arriving, a float32 stack: no read
    device.use(9 * GB)
    ledger = MemoryLedger(device)
    for step in range(5):
        warmed(ledger, device, step, f"decode[{step}]", 10 ** 7,
               9 * GB + step * 10 ** 7)
    assert rises(ledger) == []
    assert ledger.rises == {"warmup": 0, "serving": 0}
    assert all(p["held_bytes"] == 10 ** 7 and "in_company" not in p
               for p in ledger.programs.values())
    assert ledger.events[0] == {
        "step": None, "phase": "warmup", "at": "boot", "kind": None,
        "family": None, "in_flight": [], "compiled": 0.0,
        "rose_by": 14 * GB, "bytes_in_use": 9 * GB,
        "peak_bytes_in_use": 14 * GB, "bytes_limit": LIMIT,
        "largest_alloc_size": 123, "explained": 9 * GB,
        "unexplained": 5 * GB}
    assert len(ledger.events) == 1
    # Without warm-up the first read is the build's.
    late = MemoryLedger(device)
    late.build({"tpu:0": {}}, [])
    assert [(e["at"], e["phase"]) for e in late.events] == [
        ("boot", "serving")]


def test_the_list_keeps_the_newest_64_and_counts_the_rest():
    device = Allocator(GB)
    ledger = MemoryLedger(device)
    ledger.build({"tpu:0": {}}, [])
    for step in range(70):
        device.use(GB + 1000 * (step + 1))
        ledger.issued(step, program("decode[8]"), 1)
        ledger.fetched(step)
    assert len(ledger.events) == 64 and ledger.events_dropped == 7
    assert [e["step"] for e in ledger.events] == list(range(6, 70))
    assert ledger.rises["serving"] == 70
    assert ledger.rise_bytes["serving"] == 70 * 1000
    assert ledger.snapshot()["events_dropped"] == 7


def test_the_counters_never_fall():
    """Whatever the allocator does between reads (frees, a fullest device
    that changes), rises and their bytes only grow."""
    device = Allocator(GB)
    ledger = MemoryLedger(device)
    ledger.quiet()
    seen = []
    levels = [3, 1, 2, 5, 4, 1, 5, 6, 2]
    for step, level in enumerate(levels):
        if step == 4:
            ledger.build({"tpu:0": {}}, [])
        device.use(level * GB)
        ledger.issued(step, program(f"decode[{level}]"), 1)
        device.use(GB)
        ledger.fetched(step)
        seen.append((sum(ledger.rises.values()),
                     sum(ledger.rise_bytes.values())))
    assert seen == sorted(seen)
    assert seen[-1] == (3, 5 * GB)          # 1 -> 3 -> 5 -> 6
    assert ledger.rises == {"warmup": 2, "serving": 1}


# ------------------------------------------------------------ held bytes
def test_a_warmed_family_is_measured_exactly_and_a_late_one_is_marked():
    """What the count shows of a program at its first enqueue is what its
    first run loads (its code, which stays) and its outputs: resident
    from there on, in warm-up and under traffic alike."""
    device = Allocator(10 * GB)
    ledger = MemoryLedger(device)
    warmed(ledger, device, 0, "decode[8]", 8 * 10 ** 6, 10 * GB)
    warmed(ledger, device, 1, "prefill[1]", 14 * 10 ** 6,
           10 * GB + 8 * 10 ** 6, "prefill")
    assert ledger.programs["decode[8]"] == {
        "kind": "decode", "family": [1, 2, 3, 0], "has_penalties": False,
        "logprobs_k": 0, "spec_on": True, "held_bytes": 8 * 10 ** 6,
        "measured": "warmup"}
    assert ledger.programs["prefill[1]"]["held_bytes"] == 14 * 10 ** 6
    assert ledger.resident_bytes == 10 * GB + 22 * 10 ** 6
    ledger.build({"tpu:0": {"weights": 10 * GB}}, [])
    assert ledger.residents["other"] == 22 * 10 ** 6    # the code
    # A deferred variant's first use, behind a decode in flight.
    said = ledger.issued(5, program("decode[8]"), 8)
    assert said["hbm_explained"] == said["hbm"] == 10 * GB + 22 * 10 ** 6
    device.use(device.in_use + 15 * 10 ** 6)
    said = ledger.issued(6, program("prefill[1]+lp8", "prefill"), 1)
    late = ledger.programs["prefill[1]+lp8"]
    assert late["held_bytes"] == 15 * 10 ** 6
    assert late["in_company"] == ["decode[8]"] and late["measured"] == "serving"
    assert said["hbm_explained"] == said["hbm"] == ledger.resident_bytes \
        == 10 * GB + 37 * 10 ** 6
    ledger.fetched(5)
    ledger.fetched(6)
    # Measured once: a later dispatch of the family does not move it, and
    # what it finds in use beyond the residents is unexplained.
    device.use(device.in_use + GB)
    said = ledger.issued(9, program("decode[8]"), 8)
    assert ledger.programs["decode[8]"]["held_bytes"] == 8 * 10 ** 6
    assert said["hbm"] - said["hbm_explained"] == GB


def test_an_analysis_is_kept_beside_the_measure():
    device = Allocator(10 * GB)
    ledger = MemoryLedger(device)
    analysis = {"temp_bytes": 3 * GB, "argument_bytes": 9 * GB,
                "output_bytes": 8 * GB + 10, "alias_bytes": 8 * GB,
                "generated_code_bytes": 8 * 10 ** 6}
    ledger.analysed(program("decode[8]"), analysis)
    ledger.analysed(program("decode[9]"), {})       # a backend with none
    assert "decode[9]" not in ledger.programs
    # Entered by its analysis alone, it is still measured at its first
    # enqueue; its temporaries are in no count, so they explain no peak.
    warmed(ledger, device, 0, "decode[8]", 8 * 10 ** 6, 10 * GB)
    entry = ledger.programs["decode[8]"]
    assert entry["held_bytes"] == entry["generated_code_bytes"]
    assert entry["temp_bytes"] == 3 * GB and entry["kind"] == "decode"
    assert ledger.events[-1]["explained"] == 10 * GB + 8 * 10 ** 6
    ledger.analysed({"key": "decode[8]"}, {"temp_bytes": 1})
    assert entry["temp_bytes"] == 1 and entry["held_bytes"] == 8 * 10 ** 6


# ------------------------------------------------- a device with no stats
def test_a_device_with_no_stats_yields_residents_and_nothing_else():
    ledger = MemoryLedger(lambda: [{}, {}])
    ledger.quiet()
    assert ledger.issued(0, program("decode[8]"), 1, 0.3) == {}
    assert ledger.fetched(0) == {}
    ledger.build({"cpu:0": {"weights": 100, "kv": 50},
                  "cpu:1": {"weights": 100, "kv": 50}},
                 [{"device": "cpu:0", "shape": [4], "dtype": "float32",
                   "bytes": 16, "count": 1}])
    assert ledger.issued(1, program("decode[8]"), 1) == {}
    assert ledger.rise_at(1, "issue") is None
    snap = ledger.snapshot()
    assert snap["residents"] == {"weights": 100, "kv": 50, "other": 16}
    assert snap["residents_by_device"]["cpu:1"]["other"] == 0
    assert snap["events"] == [] and snap["programs"] == {}
    assert snap["built"] == {} and snap["device"] == "cpu:0"
    assert MemoryLedger(lambda: []).reading() == {}


def test_the_fullest_device_is_one_definition():
    readings = [{"bytes_in_use": 5}, {"bytes_in_use": 9, "bytes_limit": 10},
                {}, {"bytes_in_use": 9}]
    assert fullest(readings) == (1, readings[1])
    assert fullest([{}, {}]) == (0, {})
    assert fullest([]) == (0, {})
    ledger = MemoryLedger(lambda: readings)
    assert ledger.reading() is readings[1]
    ledger.build({f"tpu:{i}": {} for i in range(4)}, [])
    assert ledger.device == "tpu:1"
    # other = in use less the named holders, a device; none named: all.
    assert ledger.residents == {"other": 9}
    assert ledger.residents_by_device["tpu:0"] == {"other": 5}


def test_reads_from_two_threads_lose_no_rise():
    device = Allocator(GB)
    ledger = MemoryLedger(device, keep=4096)
    ledger.build({"tpu:0": {}}, [])
    lock = threading.Lock()

    def dispatches(base):
        for i in range(500):
            with lock:              # the loop awaits each issue and fetch
                device.use(device.peak + 1)
                ledger.issued(base + i, program("decode[8]"), 1)
            ledger.snapshot()
            ledger.fetched(base + i)

    threads = [threading.Thread(target=dispatches, args=(k * 1000,))
               for k in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not any(t.is_alive() for t in threads)
    assert ledger.rises["serving"] == 2000
    assert ledger.rise_bytes["serving"] == 2000


def test_a_fetch_that_raises_is_in_flight_no_longer():
    from production_stack_tpu.engine.flight_recorder import (
        annotated,
        annotated_issue,
    )
    from production_stack_tpu.engine.runner import DispatchHandle

    device = Allocator(GB)
    ledger = MemoryLedger(device)
    ledger.build({"tpu:0": {}}, [])

    def broken():
        raise RuntimeError("device lost")

    handle, compiled = annotated_issue(
        3, lambda: DispatchHandle(broken, program("decode[8]"), 2),
        memory=ledger)
    assert compiled == 0.0 and ledger._in_flight == {3: "decode[8]"}
    with pytest.raises(RuntimeError, match="device lost"):
        annotated("pstpu.fetch.sync", 3, handle.fetch, memory=ledger)
    assert ledger._in_flight == {}
    # Without a ledger both are what they were.
    assert annotated("pstpu.fetch.sync", 4, lambda: 7) == 7


# ------------------------------------------------------------ the engine
class CountingDevice(Allocator):
    """An engine's device: every read finds a little more in use than the
    read before it, so every read is a rise."""

    def __call__(self):
        self.use(self.in_use + 1000)
        return super().__call__()


async def test_an_engine_measures_its_warm_up_and_tells_the_phases_apart(
        monkeypatch):
    device = CountingDevice(GB)
    monkeypatch.setattr(ModelRunner, "device_memory",
                        lambda self: device())
    engine = ServingEngine(EngineConfig(
        model="tiny-llama", max_model_len=128, num_kv_blocks=64,
        num_decode_steps=4, dtype="float32", max_num_seqs=2,
        max_num_batched_tokens=32, enable_warmup=True))
    memory = engine.runner.memory
    assert memory.phase == "warmup" and not memory.residents
    # Building the ledger lowers and compiles nothing.
    calls = []
    for name in ("_lower_decode", "_lower_prefill"):
        monkeypatch.setattr(ModelRunner, name,
                            lambda *a, _n=name, **k: calls.append(_n))
    build = ModelRunner.build_memory_ledger

    def counted_build(self):
        from production_stack_tpu.engine.flight_recorder import compile_clock

        before = compile_clock().reading()
        build(self)
        calls.append(compile_clock().reading()[0] - before[0])

    monkeypatch.setattr(ModelRunner, "build_memory_ledger", counted_build)
    await engine.start()
    try:
        assert calls == [0]
        assert memory.phase == "serving"
        warmed = engine.runner.startup_warmed_families
        assert warmed and len(memory.programs) == warmed
        assert all(p["measured"] == "warmup" and "in_company" not in p
                   and p["held_bytes"] == 1000
                   for p in memory.programs.values())
        # Both reads after a family's enqueue were rises here (the read
        # before it sets what is resident and raises nothing), all
        # warm-up's.
        assert memory.rises["warmup"] == 2 * warmed
        assert memory.rises["serving"] == 0
        assert {e["phase"] for e in memory.events} == {"warmup"}
        residents = memory.residents
        assert tuple(residents) == HOLDERS
        assert residents["kv"] == engine.runner.kv_pool_bytes
        assert residents["weights"] > 0 and residents["state"] == 0
        assert sum(residents.values()) == memory.built["bytes_in_use"]
        stats = engine.stats()
        assert stats["hbm_resident_bytes"] == {"cpu:0": residents}
        before = (stats["hbm_peak_rises"], stats["hbm_peak_rise_bytes"])
        async for _ in engine.generate(
                prompt="hello there", request_id="r1",
                sampling=SamplingParams(temperature=0.0, max_tokens=6,
                                        ignore_eos=True)):
            pass
        stats = engine.stats()
        assert stats["hbm_peak_rises"]["warmup"] == before[0]["warmup"]
        assert stats["hbm_peak_rises"]["serving"] >= 2
        assert stats["hbm_peak_rise_bytes"]["serving"] > 0
        assert stats["hbm_bytes_in_use"] == device.in_use
        last = memory.events[-1]
        assert last["phase"] == "serving" and last["family"] in last["in_flight"]
        assert last["unexplained"] == last["peak_bytes_in_use"] \
            - last["explained"]
        # The request's timeline says which of its steps raised the peak.
        events = engine.recorder.get("r1")["records"][0]["events"]
        risen = [e for e in events if "hbm_rise" in e]
        assert risen and {e["event"] for e in risen} <= {
            "prefill_issue", "prefill_fetch", "decode_issue", "decode_fetch"}
        assert all(set(e["hbm_rise"]) == {"by", "to", "unexplained"}
                   for e in risen)
    finally:
        await engine.stop()


def test_a_cpu_runner_reads_nothing_and_sizes_its_residents_from_arrays():
    engine = ServingEngine(EngineConfig(
        model="tiny-llama-8kv", max_model_len=128, num_kv_blocks=64,
        block_size=4, dtype="float32", max_num_seqs=2, attn_impl="xla",
        tensor_parallel_size=2))
    runner = engine.runner
    assert runner.device_memory() == [{}, {}]
    by_device = runner.resident_bytes()
    assert list(by_device) == ["cpu:0", "cpu:1"]
    assert sum(d["kv"] for d in by_device.values()) == runner.kv_pool_bytes
    assert by_device["cpu:0"]["kv"] == by_device["cpu:1"]["kv"]
    assert engine.stats()["hbm_resident_bytes"] == by_device
    assert engine.stats()["hbm_bytes_in_use"] == 0
    runner.wait_for_weights()
    runner.build_memory_ledger()
    memory = runner.memory
    assert memory.device == "cpu:0" and memory.phase == "serving"
    assert memory.residents["weights"] == by_device["cpu:0"]["weights"] > 0
    # No allocator to ask: "other" is the live arrays outside the holders.
    assert memory.residents["other"] == sum(
        a["bytes"] for a in memory.other_arrays)
    assert all(a["device"] == "cpu:0" for a in memory.other_arrays)
    assert len(memory.other_arrays) <= 16
    assert not memory.events and not memory.programs


def test_the_ledger_lists_sixteen_groups_a_device_of_the_mesh():
    """Live arrays of 40 distinct shapes on the first device of a two-device
    engine and two small ones on the second: at most 16 groups are listed a
    device and both devices are listed (one cut over all devices kept 32
    groups of the first and none of the second)."""
    import jax
    import jax.numpy as jnp

    engine = ServingEngine(EngineConfig(
        model="tiny-llama-8kv", max_model_len=128, num_kv_blocks=64,
        block_size=4, dtype="float32", max_num_seqs=2, attn_impl="xla",
        tensor_parallel_size=2))
    runner = engine.runner
    first, second = runner.mesh.devices.flat
    crowd = [jax.device_put(jnp.zeros((4096 + i,), jnp.float32), first)
             for i in range(40)]
    few = [jax.device_put(jnp.zeros((3 + i,), jnp.float32), second)
           for i in range(2)]
    runner.wait_for_weights()
    runner.build_memory_ledger()
    memory = runner.memory
    assert memory.device == "cpu:0"
    assert len(memory.other_arrays) == 16
    assert all(a["device"] == "cpu:0" for a in memory.other_arrays)
    assert memory.residents_by_device["cpu:1"]["other"] >= sum(
        x.nbytes for x in few)
    del crowd, few


@pytest.mark.parametrize("family,variant,key", [
    ((32, 192, 32, False), {}, "decode[32,192,32,0]"),
    ((1, 24, 8, True), {"logprobs_k": 8}, "decode[1,24,8,1]+lp8"),
    ((1, 24, 8, False), {"has_penalties": True, "spec_on": False},
     "decode[1,24,8,0]+pen+plain"),
])
def test_a_program_is_named_by_kind_family_and_variant(family, variant, key):
    said = ModelRunner.program("decode", family, **variant)
    assert said["key"] == key and said["family"] == [int(x) for x in family]
    assert said["kind"] == "decode"
