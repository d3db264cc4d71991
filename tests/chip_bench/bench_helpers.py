"""Shared by the rehearsal tests: run the benchmark's command in a child,
the way the driver does, and split off its last line."""

import json
import os
import subprocess
import sys

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


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
