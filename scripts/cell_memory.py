#!/usr/bin/env python3
"""One run of one benchmark cell, exactly as ``benchmarks/chip/run.py``
makes it, that also keeps what the engines' memory ledgers say.

    chiprun -- python3 scripts/cell_memory.py --workload <cell> --seed <n> \\
        --trace 1 [--keep chiprun_out/<dir>]

The harness stops its engines before anything can ask them a question, and
a PR may not edit it. This wrapper changes nothing of the run: it hooks the
moment the harness reads the device after the window (``CellRun.
read_device``) and fetches ``GET /debug/programs`` and then ``GET
/debug/memory?analyze=1`` from every engine there, written to ``chiprun_out/memory/<cell>.engine<i>.json``
(the ledger's residents and programs, the events that raised the
allocator's peak, every device's reading). The result line is run.py's.

    chiprun -- python3 scripts/cell_memory.py --check-reference <config> \
        [--seed <n>]

runs the ENGINE stage of ``benchmarks/chip/configs/<config>/
check_reference.py`` (an engine in this process, no warm-up, every request
with log-probabilities) and writes its ledger, taken just before the
engine stops, to ``chiprun_out/memory/check_reference.<config>.json``.
"""

import json
import os
import sys
import urllib.request

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.chip import run  # noqa: E402
from benchmarks.chip.lib.cell import CellRun  # noqa: E402

OUT = os.path.join(ROOT, "chiprun_out", "memory")


def check_reference(config: str, rest) -> int:
    import importlib.util

    from production_stack_tpu.engine.engine import ServingEngine

    path = os.path.join(ROOT, "benchmarks", "chip", "configs", config,
                        "check_reference.py")
    spec = importlib.util.spec_from_file_location("check_reference", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    stop = ServingEngine.stop

    async def and_the_ledger(self):
        os.makedirs(OUT, exist_ok=True)
        runner = self.runner
        with open(os.path.join(
                OUT, f"check_reference.{config}.json"), "w") as f:
            json.dump({**runner.memory.snapshot(),
                       "now": dict(zip(runner.device_labels(),
                                       runner.device_memory()))},
                      f, indent=1)
        await stop(self)

    ServingEngine.stop = and_the_ledger
    return module.main(["--stage", "engine", *rest])


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if argv[:1] == ["--check-reference"]:
        return check_reference(argv[1], argv[2:])
    read_device = CellRun.read_device

    def and_the_ledger(self):
        read_device(self)
        os.makedirs(OUT, exist_ok=True)
        for i, url in enumerate(self.deployed.handle.engine_urls):
            path = os.path.join(OUT, f"{self.cell['name']}.engine{i}.json")
            try:
                # The audit first: it compiles the widest family of each
                # kind and leaves their analysis in the ledger.
                with urllib.request.urlopen(
                        f"{url}/debug/programs", timeout=600) as r:
                    programs = json.load(r)["programs"]
                with urllib.request.urlopen(
                        f"{url}/debug/memory?analyze=1", timeout=600) as r:
                    body = {**json.load(r), "debug_programs": programs}
            except Exception as e:  # noqa: BLE001 — the run's line counts
                body = {"error": f"{type(e).__name__}: {e}"}
            with open(path, "w") as f:
                json.dump(body, f, indent=1)

    CellRun.read_device = and_the_ledger
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main())
