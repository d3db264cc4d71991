"""Paged-vs-window decode on the real TPU: llama-3b (head_dim 128) at long
max_model_len. Records the Pallas-vs-XLA(window) comparison VERDICT r2 asked
for. Run: chiprun -- env PYTHONPATH=. python scripts/bench_paged_tpu.py [impl ...]
"""
import asyncio
import sys
import time

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.engine import ServingEngine
from production_stack_tpu.engine.sampling import SamplingParams


async def run(attn_impl, model="llama-3b", users=8, max_tokens=64,
              prompt_reps=40, max_model_len=8192):
    cfg = EngineConfig(
        model=model, max_model_len=max_model_len, block_size=16,
        max_num_seqs=users, max_num_batched_tokens=2048,
        attn_impl=attn_impl,
    )
    eng = ServingEngine(cfg)
    await eng.start()
    sampling = SamplingParams(temperature=0.0, max_tokens=max_tokens,
                              ignore_eos=True)
    base = "The quick brown fox jumps over the lazy dog. " * prompt_reps

    async def one(i, mt):
        sp = SamplingParams(temperature=0.0, max_tokens=mt, ignore_eos=True)
        n = 0
        async for o in eng.generate(prompt=base + f" user {i}.", sampling=sp):
            n = o.num_output_tokens
        return n

    # warmup (same shapes)
    await asyncio.gather(*[one(i, max_tokens) for i in range(users)])
    t0 = time.perf_counter()
    total = sum(await asyncio.gather(*[one(i, max_tokens) for i in range(users)]))
    dt = time.perf_counter() - t0
    print(f"{attn_impl}: {total} tokens in {dt:.2f}s -> {total/dt:.0f} tok/s "
          f"(model={model}, len={max_model_len}, kv_blocks={eng.runner.num_kv_blocks})")
    await eng.stop()
    return total / dt


if __name__ == "__main__":
    impls = sys.argv[1:] or ["paged", "window"]
    for impl in impls:
        asyncio.run(run(impl))
