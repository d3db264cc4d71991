#!/usr/bin/env python3
"""One run of one benchmark cell, in a new process.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Reads the cell from ``BENCHMARK.json``, starts its deployment on this
machine's chips through ``benchmarks.stack.launch_stack`` (engine children
one per chip, a router child; this parent never imports JAX while they
run), warms up, offers the traffic for ``--seconds``, checks the answers,
prints ONE JSON object as the last line of standard output, stops
everything and exits. Without the chips the cell asks for it fails and
prints no result: there is no CPU fallback. ``--rehearse`` runs the same
code at a tiny preset on the CPU for the tests; its line says
``correct: false`` and names the CPU.
"""

import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import signal  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.chip.lib import client, stack, traffic  # noqa: E402
from benchmarks.chip.lib.cell import CellRun  # noqa: E402
from benchmarks.chip.lib.manifest import Manifest  # noqa: E402


def parse_args(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", action="store_true")
    p.add_argument("--keep", default=None,
                   help="directory to copy the run's logs and trace into")
    return p.parse_args(argv)


async def measure(run: CellRun, seconds: float, trace: bool) -> dict:
    requests = traffic.generate(run.spec, run.seed, seconds)
    async with client.new_session() as session:
        await run.prepare(session, requests)
        return await run.window(session, requests["requests"], seconds,
                                trace, mark_setup=True)


def reduce_metrics(run: CellRun, win: dict, trace: bool) -> tuple:
    """The cell's metrics for this kind of run: ``end_to_end`` without a
    trace, ``per_layer`` with one. A reader that finds nothing to read
    returns nothing and its metric is left out."""
    manifest = run.manifest
    ctx = dict(win)
    ctx.update(
        setup_s=run.setup_s, traffic=run.spec,
        model_config=run.model_config, bytes_in_use=run.bytes_in_use,
        trace=None,
    )
    group = "per_layer" if trace else "end_to_end"
    readers = [(metric, *manifest.reader(metric["name"]))
               for metric in manifest.metrics_of(run.cell["name"], group)]
    if trace and win["trace_info"].get("dirs"):
        from benchmarks.chip.lib import roofline
        from benchmarks.chip.readers import trace_field

        # The fields this cell's metrics read of the reduction, which works
        # out a share by the dense count only where one of them asks.
        ctx["trace"] = roofline.reduce(
            win["trace_info"], run.model_config,
            None if run.rehearse else manifest.peaks(run.device["kind"]),
            win["results"], win["counters"],
            wanted={args["field"] for _, read, args in readers
                    if read is trace_field.read})
    out = {}
    for metric, read, args in readers:
        value = read(ctx, **args)
        if value is not None:
            out[metric["name"]] = {"value": value, "unit": metric["unit"]}
    return out, ctx["trace"]


def main(argv=None) -> int:
    args = parse_args(argv)
    manifest = Manifest(ROOT)
    seconds = args.seconds or float(manifest.doc["run_seconds"])
    run = CellRun(manifest, args.workload, args.seed, args.rehearse, STARTED)

    def on_term(signum, frame):
        raise SystemExit(f"signal {signum}")

    signal.signal(signal.SIGTERM, on_term)
    try:
        run.boot()
        win = asyncio.run(measure(run, seconds, bool(args.trace)))
        run.read_device()
    finally:
        run.stop()
        if args.keep:
            stack.keep(run.work_dir, args.keep)
    run.check_counts(win)
    metrics, trace = reduce_metrics(run, win, bool(args.trace))
    results = win["results"]
    device = dict(run.device, memory_peak_bytes=run.bytes_in_use)
    line = {
        "correct": not run.faults and not run.rehearse,
        "attempted": len(results),
        "failed": sum(1 for r in results if not r.ok),
        "metrics": metrics,
        "device": device,
        "workload": args.workload, "seed": args.seed, "seconds": seconds,
        "faults": run.faults[:20], "boots": run.boots,
        **run.waiting(win),
    }
    if trace:
        device.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
        line["breakdown"] = trace["breakdown"]
        line["trace_notes"] = trace.get("notes")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
