"""Shared by the benchmark's tests: run the benchmark's command in a child,
the way the driver does, and split off its last line; and ``BENCHMARK.json``
beside the ONE recorded copy of it that the tests of earlier PRs' entries
compare with (``data/manifest.recorded.json``, see ``data/README.txt``)."""

import json
import os
import subprocess
import sys

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
RECORD = os.path.join(os.path.dirname(__file__), "data",
                      "manifest.recorded.json")


def live():
    """``BENCHMARK.json`` as the checkout has it."""
    return json.load(open(os.path.join(REPO, "BENCHMARK.json")))


def recorded():
    """``BENCHMARK.json`` as the last PR that changed an entry left it."""
    return json.load(open(RECORD))


def grown_from(doc, was):
    """The ways in which ``doc`` is NOT ``was`` grown at its ends (none: an
    empty list). What ``was`` holds is in ``doc`` as it was and where it
    was; a list of entries may have more entries behind the ones it had;
    an entry that names its cells may name more behind the ones it named,
    and may differ in nothing else."""
    faults = []
    if set(doc) != set(was):
        faults.append(f"keys {sorted(set(doc) ^ set(was))}")
    for key in ("command", "paths", "run_seconds", "end_to_end"):
        if doc.get(key) != was[key]:
            faults.append(f"{key} changed")
    for key in ("configs", "workloads"):
        if doc[key][:len(was[key])] != was[key]:
            faults.append(f"{key}: what was there changed or moved")
    if len(doc["per_layer"]) < len(was["per_layer"]):
        faults.append("per_layer lost an entry")
    for now, then in zip(doc["per_layer"], was["per_layer"]):
        if now == then:
            continue
        had = then.get("workloads")
        if had is None or now != dict(then, workloads=now.get("workloads")):
            faults.append(f"{then['name']}: changed or moved")
        elif now["workloads"][:len(had)] != had:
            faults.append(f"{then['name']}: a cell it named went or moved")
    return faults


def run_cell(root, cell, *extra, seed=2**31 + 11, seconds=5, trace=0,
             timeout=900):
    """(return code, last-line object or None, stderr tail)."""
    manifest = json.load(open(os.path.join(root, "BENCHMARK.json")))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, *manifest["command"][1:], "--workload", cell,
         "--seed", str(seed), "--seconds", str(seconds), "--trace",
         str(trace), *extra],
        cwd=root, env=env, capture_output=True, text=True, timeout=timeout)
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    line = json.loads(lines[-1]) if lines else None
    return proc.returncode, line, proc.stderr[-3000:]


def copy_benchmark(dest, with_program=True):
    """A checkout in ``dest``: BENCHMARK.json and the benchmark's paths,
    and (``with_program``) the program it measures."""
    import shutil

    manifest = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), dest)
    ignore = shutil.ignore_patterns(".work", "__pycache__", ".pstpu_xla_cache")
    for path in manifest["paths"]:
        shutil.copytree(os.path.join(REPO, path), os.path.join(dest, path),
                        ignore=ignore)
    if with_program:
        for name in ("__init__.py", "stack.py"):
            shutil.copy(os.path.join(REPO, "benchmarks", name),
                        os.path.join(dest, "benchmarks", name))
        os.symlink(os.path.join(REPO, "production_stack_tpu"),
                   os.path.join(dest, "production_stack_tpu"))
    return dest
