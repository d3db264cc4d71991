"""Bring a cell's deployment up through the program's own launcher
(``benchmarks.stack.launch_stack``: one engine child per chip, a router
child, this parent off JAX), ask the engines what they run on, and take it
down again. Nothing here imports JAX."""

import json
import os
import shutil
import sys
import urllib.error
import urllib.request
from typing import List, Optional

from benchmarks.chip.lib.manifest import Manifest

# Cold prompt vs the same prompt as a whole-prefix hit, first-token
# log-probability: the hit prefills only the last block, the cold run the
# whole prompt in other chunk shapes, so bf16 partial sums are taken in
# another order and the log-softmax over a random-weight vocabulary moves
# by a few bf16 ulps of the logit scale. The same reason and the same
# tolerance as chip_smoke.py's TP_LOGPROB_TOL between its paths; computing
# the prefix in a lower precision, or attending the wrong blocks, moves it
# by whole units.
PROBE_LOGPROB_TOL = 0.15
BOOT_TIMEOUT_S = 1100.0


def http_json(url: str, body: Optional[dict] = None, timeout: float = 60.0):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read() or b"null")
    except urllib.error.HTTPError as e:
        return e.code, {"error": e.read()[:300].decode(errors="replace")}


def cache_dir(root: str, name: str) -> str:
    """Where this deployment's engines keep JAX's persistent compile cache:
    a directory of its own inside the one the environment names
    (``JAX_COMPILATION_CACHE_DIR``), else inside the checkout's fixed
    ``.pstpu_xla_cache``. Of its own, because a size-capped cache evicts the
    oldest entries of whatever shares it, and a cell whose programs another
    cell evicted compiles again inside its set-up."""
    base = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        root, ".pstpu_xla_cache")
    return os.path.join(base, name)


def cache_entries(path: str) -> frozenset:
    """Names of the compiled programs in a cache directory (the ``-cache``
    files; a compile adds a name, a hit does not)."""
    try:
        return frozenset(f for f in os.listdir(path) if f.endswith("-cache"))
    except OSError:
        return frozenset()


class Deployed:
    """A running deployment: its handle, what its engines say they are,
    and its counters."""

    def __init__(self, handle, name: str, cache_path: str):
        self.handle = handle
        self.name = name
        self.cache_path = cache_path
        self.versions: List[dict] = []

    @property
    def url(self) -> str:
        return self.handle.router_url

    def refresh_versions(self) -> List[dict]:
        self.versions = []
        for url in self.handle.engine_urls:
            status, doc = http_json(f"{url}/version")
            if status != 200:
                raise RuntimeError(f"GET {url}/version -> {status}: {doc}")
            self.versions.append(doc)
        return self.versions

    def device(self) -> dict:
        """The device as the serving engines report it (JAX's words)."""
        first = self.versions[0]["device"]
        return {"platform": first["platform"], "kind": first["kind"],
                "count": sum(v["device"]["count"] for v in self.versions)}

    def bytes_in_use(self) -> int:
        """Bytes in use on the fullest chip, as the engines report them."""
        return max(
            (b for v in self.versions
             for b in v["engine"]["bytes_in_use"].values()), default=0)

    def stop(self) -> None:
        self.handle.terminate()


def start(manifest: Manifest, config: str, deployment: dict, model_dir: str,
          weight_seed: int, log_dir: str, cache_name: str) -> Deployed:
    """Engines and router up and healthy (warm-up done), or an exception."""
    from benchmarks.stack import launch_stack

    root = manifest.root
    shim = os.path.join(manifest.chip_dir, "engine_shim")
    inherited = os.environ.get("PYTHONPATH")
    pythonpath = os.pathsep.join(p for p in (root, inherited) if p)
    os.environ["PYTHONPATH"] = pythonpath   # router child: package only
    cache_path = cache_dir(root, cache_name)
    os.makedirs(cache_path, exist_ok=True)
    os.makedirs(log_dir, exist_ok=True)
    flags = [x for f in deployment["engine_flags"]
             for x in (f["flag"], f["value"])]
    served = deployment["served_model_name"]
    router = deployment["router"]
    handle = launch_stack(
        model_dir,
        engine_args=[*flags, "--served-model-name", served],
        router_args=[x for f in router["flags"]
                     for x in (f["flag"], f["value"])],
        routing_logic=router["routing_logic"],
        served_model=served,
        startup_timeout_s=BOOT_TIMEOUT_S,
        log_dir=log_dir,
        num_engines=deployment["engines"],
        tensor_parallel_size=deployment["tensor_parallel"],
        engine_env={
            "PYTHONPATH": os.pathsep.join((shim, pythonpath)),
            "CHIP_BENCH_WEIGHT_SEED": str(weight_seed),
            "JAX_COMPILATION_CACHE_DIR": cache_path,
        },
    )
    deployed = Deployed(handle, config, cache_path)
    try:
        deployed.refresh_versions()
    except Exception:
        deployed.stop()
        raise
    return deployed


def log_tail(log_dir: str, n: int = 3000) -> str:
    """End of the newest engine log, for a failed start."""
    try:
        logs = sorted(
            (os.path.join(log_dir, f) for f in os.listdir(log_dir)
             if "engine" in f), key=os.path.getmtime)
        with open(logs[-1], errors="replace") as f:
            return f.read()[-n:]
    except (OSError, IndexError):
        return ""


def note(*parts) -> None:
    """Progress goes to stderr: stdout's last line is the result."""
    print(*parts, file=sys.stderr, flush=True)


def keep(work_dir: str, dest: str) -> None:
    """Copy a run's logs and trace out of the work directory (for a look
    by hand; the driver never asks for it)."""
    if os.path.isdir(work_dir):
        shutil.copytree(work_dir, dest, dirs_exist_ok=True)
