#!/usr/bin/env python3
"""Find an open-loop cell's knee: one deployment, one process, rising fixed
rates of the cell's own traffic, some 20 s each.

    python3 benchmarks/chip/sweep.py --workload <cell> --rates 3,3.5,4 \\
        [--seconds 20] [--out chiprun_out/sweep.json]

Run once, when a cell is defined (the benchmark itself never searches for a
rate). The limits are fixed from the LOWEST rate: TTFT <= 2 x its median
there, time per output token <= 2 x its median there. The knee is the
highest rate at which at least 98% of the offered requests ended within the
step (plus one median request time), the number waiting did not grow over
the step, and at least 90% of requests met both limits. Rate, limits and
this table go into the traffic file and PERF.md by hand.
"""

import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.chip.lib import client, stack, traffic  # noqa: E402
from benchmarks.chip.lib.cell import CellRun  # noqa: E402
from benchmarks.chip.lib.manifest import Manifest  # noqa: E402
from benchmarks.chip.lib.stats import percentile  # noqa: E402


async def sweep(run: CellRun, rates, seconds: float) -> list:
    rows, over = [], 0
    async with client.new_session() as session:
        first = traffic.generate(run.spec, run.seed, seconds)
        await run.prepare(session, first)
        for step, rate in enumerate(rates):
            run.spec = {**run.spec, "rate_rps": rate}
            requests = traffic.generate(run.spec, run.seed, seconds,
                                        variation=step + 1)
            win = await run.window(session, requests["requests"], seconds,
                                   trace=False, mark_setup=False)
            await run.until_idle(session, timeout_s=120.0)
            ok = [r for r in win["results"] if r.ok]
            rows.append({
                "rate_rps": rate, "offered": len(win["results"]),
                "ok": len(ok), "t0": win["t0"], "seconds": seconds,
                "ttft_p50_ms": percentile([r.ttft_ms for r in ok], 50),
                "ttft_p95_ms": percentile([r.ttft_ms for r in ok], 95),
                "tpot_p50_ms": percentile([r.tpot_ms for r in ok], 50),
                "req_p50_ms": percentile([r.req_ms for r in ok], 50),
                "late_p99_ms": percentile([r.late_ms for r in ok], 99),
                "span_s": win["span_s"], **run.waiting(win),
                "_results": ok,
            })
            stack.note(json.dumps({k: v for k, v in rows[-1].items()
                                   if k != "_results"}))
            # Two steps in a row that took half as long again to drain:
            # the knee is behind us, and every further step costs minutes.
            over = over + 1 if win["span_s"] > 1.5 * seconds else 0
            if over >= 2:
                break
    return rows


def judge(rows: list) -> dict:
    base = rows[0]
    limits = {"ttft_ms": 2 * base["ttft_p50_ms"],
              "tpot_ms": 2 * base["tpot_p50_ms"]}
    knee = None
    for row in rows:
        ok = row.pop("_results")
        end = row["t0"] + row["seconds"] + base["req_p50_ms"] / 1e3
        row["ended_share"] = sum(1 for r in ok if r.last <= end) / max(
            1, row["offered"])
        row["met_share"] = sum(
            1 for r in ok if r.ttft_ms <= limits["ttft_ms"]
            and r.tpot_ms <= limits["tpot_ms"]) / max(1, row["offered"])
        grew = (row["waiting_end"] or 0) > (row["waiting_mid"] or 0) + 1
        row["sustained"] = (row["ended_share"] >= 0.98 and not grew
                            and row["met_share"] >= 0.90)
        if row["sustained"]:
            knee = row["rate_rps"]
        del row["t0"]
    return {"limits": limits, "knee_rps": knee, "rows": rows}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--seconds", type=float, default=20.0)
    p.add_argument("--rehearse", action="store_true")
    p.add_argument("--out", default=None)
    args = p.parse_args(argv)
    rates = [float(x) for x in args.rates.split(",")]
    run = CellRun(Manifest(ROOT), args.workload, args.seed, args.rehearse,
                  STARTED)
    try:
        run.boot()
        rows = asyncio.run(sweep(run, rates, args.seconds))
    finally:
        run.stop()
    report = {"workload": args.workload, **judge(rows),
              "faults": run.faults[:20]}
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1)
    print(json.dumps(report), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
