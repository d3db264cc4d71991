"""Cold-prefill dispatch timeline: 8 distinct 1024-token prompts, no prefix
sharing. Shows where stack-level TTFT goes.
Run: chiprun -- env PYTHONPATH=. python scripts/profile_prefill_engine.py
"""
import asyncio
import time

import numpy as np

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.engine import ServingEngine
from production_stack_tpu.engine.sampling import SamplingParams


async def main():
    cfg = EngineConfig(
        model="llama-1b", max_model_len=8192, block_size=16,
        max_num_seqs=16, enable_prefix_caching=False,
    )
    engine = ServingEngine(cfg)
    runner = engine.runner
    log = []
    orig = runner.execute

    def traced(batch, step):
        t0 = time.perf_counter()
        out = orig(batch, step)
        t1 = time.perf_counter()
        log.append((batch.kind, len(batch.seqs),
                    batch.num_steps if batch.kind == "decode"
                    else max(batch.chunk_lens), (t1 - t0) * 1000))
        return out

    runner.execute = traced
    await engine.start()

    rng = np.random.default_rng(0)

    async def one(i, toks):
        async for _ in engine.generate(
            prompt_token_ids=toks,
            sampling=SamplingParams(temperature=0.0, max_tokens=4,
                                    ignore_eos=True),
        ):
            pass

    for trial in range(3):
        log.clear()
        toks = [rng.integers(10, 30000, 1024).tolist() for _ in range(8)]
        t0 = time.perf_counter()
        await asyncio.gather(*[one(i, t) for i, t in enumerate(toks)])
        dt = time.perf_counter() - t0
        if trial == 0:
            continue  # compile pass
        print(f"trial {trial}: 8x1024 prefill+4tok in {dt*1000:.0f} ms "
              f"-> prefill {8*1024/dt:.0f} tok/s")
        for kind, rows, kt, ms in log:
            print(f"  {kind:8} rows={rows} T/K={kt:4} {ms:7.1f} ms")
    await engine.stop()


if __name__ == "__main__":
    asyncio.run(main())
