"""Instrument the engine loop during the bench workload: log every dispatch
(kind, rows, K/T, device ms) and the host-side gap between dispatches.
Run: chiprun -- env PYTHONPATH=. python scripts/profile_engine.py
"""
import asyncio
import time

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.engine import ServingEngine
from production_stack_tpu.engine.sampling import SamplingParams

import bench


async def main():
    cfg = EngineConfig(
        model="llama-1b", max_model_len=1024, block_size=16,
        max_num_seqs=16, max_num_batched_tokens=1024,
    )
    engine = ServingEngine(cfg)
    runner = engine.runner

    log = []
    orig = runner.execute

    def traced(batch, step):
        t0 = time.perf_counter()
        out = orig(batch, step)
        t1 = time.perf_counter()
        log.append((
            t0, t1, batch.kind, len(batch.seqs),
            batch.num_steps if batch.kind == "decode" else max(batch.chunk_lens),
        ))
        return out

    runner.execute = traced

    await engine.start()
    try:
        res = await bench._bench_engine(engine, 16, 2, 600, 64)
    finally:
        await engine.stop()
    print(res)

    print(f"{'kind':8} {'rows':4} {'K/T':5} {'dev_ms':8} {'gap_ms':8}")
    prev_end = None
    tot_dev = tot_gap = 0.0
    for t0, t1, kind, rows, kt in log:
        gap = (t0 - prev_end) * 1000 if prev_end else 0.0
        dev = (t1 - t0) * 1000
        tot_dev += dev
        tot_gap += gap
        print(f"{kind:8} {rows:4} {kt:5} {dev:8.1f} {gap:8.1f}")
        prev_end = t1
    print(f"dispatches={len(log)} total_device={tot_dev:.0f} ms "
          f"total_gap={tot_gap:.0f} ms")


if __name__ == "__main__":
    asyncio.run(main())
