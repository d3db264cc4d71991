"""Bring up the serving STACK (engine API server + router) as subprocesses.

Used by bench.py and the e2e tests so the recorded benchmark exercises the
same deployment shape the reference measures: client -> router (session
routing, SSE relay) -> engine pod (reference tutorials/
07-benchmark-multi-round-qa-single-gpu.md procedure).
"""

import json
import os
import socket
import subprocess
import sys
import time
import urllib.request
from dataclasses import dataclass, field
from typing import List, Optional


def cpu_asked_for() -> bool:
    """``JAX_PLATFORMS=cpu``: the CPU (tests, rehearsals) is run on because
    it was asked for, never because a device probe came back empty."""
    return os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"


def local_tpu_chips() -> List[int]:
    """Indices of the TPU chips this host exposes, found WITHOUT starting
    JAX (the launcher must never open a chip its children need): the
    per-chip device nodes the TPU driver creates (/dev/vfio/<n> on v5e and
    later, /dev/accel<n> before). Empty when the CPU was asked for
    (JAX_PLATFORMS=cpu) or the host has no chip."""
    import glob
    import re

    if cpu_asked_for():
        return []
    # The node numbers are the kernel's (IOMMU groups under vfio), the
    # chip indices libtpu takes are 0..n-1: count, don't parse.
    nodes = [
        path for path in glob.glob("/dev/vfio/*") + glob.glob("/dev/accel*")
        if re.fullmatch(r"/dev/(?:vfio/|accel)\d+", path)
    ]
    return list(range(len(nodes)))


def tpu_chip_env(chips: List[int]) -> dict:
    """The libtpu environment that makes one child process own exactly
    ``chips`` (host chip indices) and nothing else: a chip belongs to one
    process at a time, so every engine of a multi-engine stack must be
    told which chips are its own — left alone, each would open all of the
    host's chips and use the first. The process is its own one-process
    "slice": visible chips, the bounds of that chip set, and a private
    port for libtpu's slice builder."""
    bounds = {1: "1,1,1", 2: "1,2,1", 4: "2,2,1", 8: "2,4,1"}.get(len(chips))
    if bounds is None:
        raise ValueError(
            f"cannot give one process {len(chips)} TPU chips {chips} "
            f"(supported sets: 1, 2, 4, 8)"
        )
    port = free_port()
    return {
        "TPU_VISIBLE_CHIPS": ",".join(str(c) for c in chips),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": bounds,
        "TPU_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_ADDRESSES": f"localhost:{port}",
        "TPU_PROCESS_PORT": str(port),
        "CLOUD_TPU_TASK_ID": "0",
    }


def _mesh_size(cmd: List[str]) -> int:
    """Devices an engine argv's mesh occupies: the product of its LAST
    --tensor/--sequence/--data-parallel-size values (argparse keeps the
    last, so per-engine extras override the shared args here too)."""
    size = 1
    for flag in ("--tensor-parallel-size", "--sequence-parallel-size",
                 "--data-parallel-size"):
        at = [i for i, a in enumerate(cmd[:-1]) if a == flag]
        if at:
            size *= int(cmd[at[-1] + 1])
    return size


def _child_env(engine_env: Optional[dict],
               chips: List[int]) -> Optional[dict]:
    """Environment of one engine child: the inherited one, the caller's
    overrides, and — on a TPU host — the chips this child owns."""
    if not engine_env and not chips:
        return None
    return {**os.environ, **(engine_env or {}),
            **(tpu_chip_env(chips) if chips else {})}


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def wait_tcp(host: str, port: int, timeout_s: float, proc: subprocess.Popen,
             name: str) -> None:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            raise RuntimeError(
                f"{name} exited with code {proc.returncode} before listening"
            )
        try:
            socket.create_connection((host, port), 0.5).close()
            return
        except OSError:
            time.sleep(0.2)
    raise TimeoutError(f"{name} not listening on {host}:{port} "
                       f"after {timeout_s}s")


@dataclass
class KVServerHandle:
    """Restartable cache-server subprocess (soak chaos: restart_kv_server).
    The port is pinned so LMCACHE_REMOTE_URL stays valid across restarts —
    engines reconnect via RemoteKVClient's one-shot retry."""

    proc: subprocess.Popen
    url: str
    port: int
    log_path: str
    log_file: object
    max_bytes: int

    def _spawn(self) -> subprocess.Popen:
        return subprocess.Popen(
            [
                sys.executable, "-m",
                "production_stack_tpu.kv_offload.server",
                "--force-python", "--host", "127.0.0.1",
                "--port", str(self.port), "--max-bytes", str(self.max_bytes),
            ],
            stdout=self.log_file, stderr=subprocess.STDOUT,
        )

    def restart(self, timeout_s: float = 60.0) -> float:
        """SIGTERM -> wait exit -> relaunch on the SAME port -> wait
        listening. Returns the downtime in seconds."""
        t0 = time.monotonic()
        if self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=15)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=15)
        self.proc = self._spawn()
        wait_tcp("127.0.0.1", self.port, timeout_s, self.proc, "kv_server")
        return time.monotonic() - t0

    def terminate(self) -> None:
        if self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                self.proc.kill()
        self.log_file.close()


def launch_kv_server(max_bytes: int = 1 << 30, log_dir: str = "/tmp"):
    """Start the Python cache server as a subprocess; returns
    (Popen, kv_url, log_path, log_file) — see also launch_kv_server_handle
    for the restartable wrapper the soak harness drives. The disagg bench
    mode's handoff plane and the engines' LMCACHE_REMOTE_URL both point
    at it."""
    h = launch_kv_server_handle(max_bytes=max_bytes, log_dir=log_dir)
    return h.proc, h.url, h.log_path, h.log_file


def launch_kv_server_handle(max_bytes: int = 1 << 30,
                            log_dir: str = "/tmp") -> KVServerHandle:
    port = free_port()
    log = os.path.join(log_dir, f"pstpu-bench-kvserver-{port}.log")
    log_f = open(log, "w")
    handle = KVServerHandle(
        proc=None, url=f"kv://127.0.0.1:{port}", port=port,  # type: ignore
        log_path=log, log_file=log_f, max_bytes=max_bytes,
    )
    handle.proc = handle._spawn()
    try:
        wait_tcp("127.0.0.1", port, 60.0, handle.proc, "kv_server")
    except Exception:
        handle.proc.kill()
        log_f.close()
        raise
    return handle


def wait_health(url: str, timeout_s: float, proc: subprocess.Popen,
                name: str) -> None:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            raise RuntimeError(
                f"{name} exited with code {proc.returncode} before becoming "
                f"healthy (see its log output)"
            )
        try:
            with urllib.request.urlopen(url, timeout=2) as resp:
                if resp.status == 200:
                    return
        except Exception:  # noqa: BLE001 — not up yet
            time.sleep(1.0)
    raise TimeoutError(f"{name} not healthy after {timeout_s}s ({url})")


@dataclass
class StackHandle:
    engines: List[subprocess.Popen]
    routers: List[subprocess.Popen]
    engine_urls: List[str]
    router_urls: List[str]
    log_paths: List[str] = field(default_factory=list)
    log_files: List[object] = field(default_factory=list)
    # Relaunch state (soak chaos: restart_engine): engine i's exact argv,
    # its log file, and the env overrides it was launched with.
    engine_cmds: List[List[str]] = field(default_factory=list)
    engine_log_files: List[object] = field(default_factory=list)
    engine_env: Optional[dict] = None
    # Host chip indices engine i owns (empty lists off-TPU): a relaunch
    # gets the same chips back, a scale-out the lowest free ones.
    engine_chips: List[List[int]] = field(default_factory=list)
    # Elastic fast-start (docs/ELASTIC.md): per-engine process-spawn ->
    # /health-200 seconds (initial launch, relaunches overwrite their
    # slot, scale-outs append), the served model name, and — when the
    # router runs static discovery behind a dynamic-config file — the
    # file scale_out/scale_in rewrite so the router learns the new fleet.
    engine_ready_seconds: List[float] = field(default_factory=list)
    served_model: str = ""
    dynamic_config_path: Optional[str] = None
    dynamic_config_watch_interval: float = 10.0
    log_dir: str = "/tmp"

    def _write_dynamic_config(self) -> None:
        assert self.dynamic_config_path is not None
        doc = {
            "service_discovery": "static",
            "static_backends": ",".join(self.engine_urls),
            "static_models": ",".join(
                [self.served_model] * len(self.engine_urls)
            ),
        }
        tmp = self.dynamic_config_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f)
        os.replace(tmp, self.dynamic_config_path)  # atomic vs the watcher

    def scale_out(self, startup_timeout_s: float = 1800.0) -> dict:
        """Add one engine to the running stack (the soak's local HPA
        emulation, docs/ELASTIC.md): spawn engine 0's argv on a fresh
        port (same flags — including any shared --compilation-cache-dir,
        so the joiner takes the warm-start path), wait for /health, then
        rewrite the router's dynamic-config file so static discovery
        picks it up within the watch interval. Requires the stack to have
        been launched with dynamic_config_path."""
        if self.dynamic_config_path is None:
            raise RuntimeError(
                "scale_out requires launch_stack(dynamic_config_path=...) "
                "(the router must be watching a dynamic config file)"
            )
        port = free_port()
        url = f"http://127.0.0.1:{port}"
        cmd = list(self.engine_cmds[0])
        cmd[cmd.index("--port") + 1] = str(port)
        chips: List[int] = []
        if self.engine_chips and self.engine_chips[0]:
            used = {c for owned in self.engine_chips for c in owned}
            free = [c for c in local_tpu_chips() if c not in used]
            need = len(self.engine_chips[0])
            if len(free) < need:
                raise RuntimeError(
                    f"scale_out needs {need} free TPU chip(s); engines own "
                    f"{sorted(used)} and the host has {free} left"
                )
            chips = free[:need]
        elog = os.path.join(self.log_dir, f"pstpu-bench-engine-{port}.log")
        elog_f = open(elog, "w")
        t0 = time.monotonic()
        proc = subprocess.Popen(
            cmd, stdout=elog_f, stderr=subprocess.STDOUT,
            env=_child_env(self.engine_env, chips),
        )
        try:
            wait_health(f"{url}/health", startup_timeout_s, proc,
                        f"engine {url} (scale-out)")
        except Exception:
            proc.kill()
            elog_f.close()
            raise
        ready_s = time.monotonic() - t0
        self.engines.append(proc)
        self.engine_urls.append(url)
        self.engine_cmds.append(cmd)
        self.engine_chips.append(chips)
        self.engine_log_files.append(elog_f)
        self.engine_ready_seconds.append(ready_s)
        self.log_paths.append(elog)
        self.log_files.append(elog_f)
        self._write_dynamic_config()
        return {"url": url, "index": len(self.engines) - 1,
                "engine_ready_s": round(ready_s, 3)}

    def scale_in(self, index: int = -1,
                 drain_timeout_s: float = 60.0) -> dict:
        """Remove engine ``index`` (default: the newest) with zero 5xx:
        the dynamic-config rewrite drops it from routing FIRST, the
        watch interval is waited out (plus margin) so the router stops
        picking it, then SIGTERM triggers the engine's graceful drain
        (in-flight streams finish; its hot KV is already spilled to the
        shared tier by the write-through offload path)."""
        if self.dynamic_config_path is None:
            raise RuntimeError(
                "scale_in requires launch_stack(dynamic_config_path=...)"
            )
        if index < 0:
            index = len(self.engines) + index
        if not 0 <= index < len(self.engines) or len(self.engines) <= 1:
            raise ValueError(f"cannot scale in engine {index} of "
                             f"{len(self.engines)}")
        proc = self.engines.pop(index)
        url = self.engine_urls.pop(index)
        self.engine_cmds.pop(index)
        if index < len(self.engine_chips):
            self.engine_chips.pop(index)
        elog_f = self.engine_log_files.pop(index)
        if index < len(self.engine_ready_seconds):
            self.engine_ready_seconds.pop(index)
        self._write_dynamic_config()
        # Let the watcher apply the shrunken fleet before the drain
        # starts, so no request is routed at a draining backend (the
        # router's retry ladder would still absorb one, but the clean
        # path is route-away-first).
        time.sleep(self.dynamic_config_watch_interval + 1.0)
        t0 = time.monotonic()
        if proc.poll() is None:
            proc.terminate()
            try:
                proc.wait(timeout=drain_timeout_s)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait(timeout=30)
        try:
            self.log_files.remove(elog_f)
        except ValueError:
            pass
        elog_f.close()
        return {"url": url, "drain_s": round(time.monotonic() - t0, 3)}

    @property
    def engine(self) -> subprocess.Popen:
        """First engine process (single-engine callers / run*.sh)."""
        return self.engines[0]

    @property
    def engine_url(self) -> str:
        return self.engine_urls[0]

    @property
    def router(self) -> subprocess.Popen:
        """First LIVE router process (single-router callers / run*.sh)."""
        for proc in self.routers:
            if proc.poll() is None:
                return proc
        return self.routers[0]

    @property
    def router_url(self) -> str:
        """URL of the first LIVE router replica. After kill_router the
        facade moves to the next survivor, so single-URL callers keep
        working through a router death (docs/ROUTER_SCALE.md)."""
        for proc, url in zip(self.routers, self.router_urls):
            if proc.poll() is None:
                return url
        raise RuntimeError("no live router replica")

    @property
    def live_router_urls(self) -> List[str]:
        """All currently-live router replica URLs (metrics-merge scrapes)."""
        return [url for proc, url in zip(self.routers, self.router_urls)
                if proc.poll() is None]

    def kill_router(self, index: int) -> float:
        """HARD-kill router replica ``index``: SIGKILL, no drain, no
        relaunch — in-flight client streams die mid-byte and the client
        must reconnect to a surviving replica with its
        x-pstpu-resume-* state (docs/ROUTER_SCALE.md). Returns seconds
        spent waiting for the process to die."""
        if len(self.live_router_urls) <= 1:
            raise RuntimeError(
                "refusing to kill the last live router replica"
            )
        proc = self.routers[index]
        t0 = time.monotonic()
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=60)
        return time.monotonic() - t0

    def _relaunch_engine(self, index: int, startup_timeout_s: float) -> None:
        """Relaunch engine ``index``'s exact argv/env on the same port and
        block until /health is 200 again."""
        chips = (self.engine_chips[index]
                 if index < len(self.engine_chips) else [])
        t0 = time.monotonic()
        new = subprocess.Popen(
            self.engine_cmds[index],
            stdout=self.engine_log_files[index], stderr=subprocess.STDOUT,
            env=_child_env(self.engine_env, chips),
        )
        self.engines[index] = new
        wait_health(f"{self.engine_urls[index]}/health", startup_timeout_s,
                    new, f"engine {self.engine_urls[index]} (restarted)")
        # The relaunch reuses the same argv (incl. any shared
        # --compilation-cache-dir), so this measures the WARM-start path
        # the chaos-recovery bars benefit from (docs/ELASTIC.md).
        if index < len(self.engine_ready_seconds):
            self.engine_ready_seconds[index] = time.monotonic() - t0
        else:
            self.engine_ready_seconds.append(time.monotonic() - t0)

    def restart_engine(self, index: int, startup_timeout_s: float = 1800.0,
                       kill_timeout_s: float = 60.0) -> float:
        """Rolling-restart engine ``index``: SIGTERM (graceful drain — the
        engine finishes in-flight streams, sheds new work with
        503+Retry-After, then exits), wait for exit, relaunch the same
        argv/env on the same port, block until /health is 200 again.
        Returns the measured downtime in seconds. Blocking by design: the
        soak harness calls it via asyncio.to_thread so traffic keeps
        flowing while the pod bounces."""
        proc = self.engines[index]
        t0 = time.monotonic()
        if proc.poll() is None:
            proc.terminate()
            try:
                proc.wait(timeout=kill_timeout_s)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait(timeout=kill_timeout_s)
        self._relaunch_engine(index, startup_timeout_s)
        return time.monotonic() - t0

    def kill_engine(self, index: int, startup_timeout_s: float = 1800.0,
                    relaunch: bool = True) -> float:
        """HARD-kill engine ``index``: SIGKILL, no drain — in-flight SSE
        streams die mid-byte, exactly the fault the router's mid-stream
        resume exists for (docs/RESILIENCE.md). Then (by default) relaunch
        on the same port like restart_engine. Returns the downtime."""
        proc = self.engines[index]
        t0 = time.monotonic()
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=60)
        if relaunch:
            self._relaunch_engine(index, startup_timeout_s)
        return time.monotonic() - t0

    def terminate(self) -> None:
        procs = [*self.routers, *self.engines]
        for proc in procs:
            if proc.poll() is None:
                proc.terminate()
        for proc in procs:
            try:
                # An engine whose profiler capture is being written when
                # SIGTERM arrives finishes the file first (profiling.py:
                # close); a 4 s capture of a full-size model took 13-72 s
                # to stop (my chip run, PR 24), so 15 s would kill it
                # mid-write.
                proc.wait(timeout=180)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait(timeout=15)
        for f in self.log_files:
            f.close()
        self.log_files.clear()


def launch_stack(
    model: str,
    *,
    engine_args: Optional[List[str]] = None,
    router_args: Optional[List[str]] = None,
    routing_logic: str = "session",
    served_model: Optional[str] = None,
    startup_timeout_s: float = 1800.0,
    log_dir: str = "/tmp",
    num_engines: int = 1,
    num_routers: int = 1,
    per_engine_args: Optional[List[List[str]]] = None,
    engine_env: Optional[dict] = None,
    tensor_parallel_size: int = 1,
    compilation_cache_dir: Optional[str] = None,
    dynamic_config_path: Optional[str] = None,
    dynamic_config_watch_interval: float = 1.0,
) -> StackHandle:
    """Start ``num_engines`` engine pods + the router; block until all are
    healthy. Multiple engines make the load-balancing routing logics
    (e.g. cache_aware_load_balancing) actually route — the 2-process
    opt-125m smoke path in the benchmark sweep. ``per_engine_args[i]`` are
    appended to engine i's argv (role-split disagg pools) and
    ``engine_env`` entries override the inherited environment (e.g.
    LMCACHE_REMOTE_URL for the shared offload store).

    ``tensor_parallel_size`` > 1 boots every engine on a tp-sharded device
    mesh (threaded through per_engine_args, so a caller's own per-engine
    extras can still override it per pod). On CPU the caller must also put
    ``--xla_force_host_platform_device_count=N`` into the subprocesses'
    XLA_FLAGS (bench.py does).

    One process for each chip: on a TPU host (local_tpu_chips) every
    engine child is given exactly the chips its mesh needs, in order
    (tpu_chip_env, set per child) — engine 0 the first dp*sp*tp chips,
    engine 1 the next — and the launcher itself never starts JAX. A stack
    that needs more chips than the host exposes is an error before
    anything is spawned.

    Elastic fast-start (docs/ELASTIC.md): ``compilation_cache_dir``
    threads ``--compilation-cache-dir`` into every engine subprocess
    (restarts and scale-outs reuse the argv, so relaunches boot warm);
    ``dynamic_config_path`` makes the router watch a dynamic-config file
    seeded with the initial fleet, enabling StackHandle.scale_out /
    scale_in mid-run; per-engine spawn->/health seconds land in
    StackHandle.engine_ready_seconds (healths are awaited sequentially,
    so later engines' values include queue wait — use a 1-engine stack
    for a clean cold/warm boot A/B).

    ``num_routers`` > 1 boots a horizontally-scaled router tier
    (docs/ROUTER_SCALE.md): every replica sees the same backend set,
    carries ``--router-id router-<i>``, and shares a
    ``--router-peer-dir`` under ``log_dir`` for breaker gossip. Clients
    spread across StackHandle.router_urls; StackHandle.kill_router is
    the matching chaos fault."""
    if tensor_parallel_size > 1:
        pea = [list(a) for a in (per_engine_args or [])]
        while len(pea) < max(1, num_engines):
            pea.append([])
        per_engine_args = [
            ["--tensor-parallel-size", str(tensor_parallel_size), *a]
            for a in pea
        ]
    num_routers = max(1, num_routers)
    router_ports = [free_port() for _ in range(num_routers)]
    router_urls = [f"http://127.0.0.1:{p}" for p in router_ports]
    served = served_model or model

    engines: List[subprocess.Popen] = []
    engine_urls: List[str] = []
    engine_cmds: List[List[str]] = []
    engine_log_files: List[object] = []
    engine_spawn_times: List[float] = []
    engine_ready_seconds: List[float] = []
    log_paths: List[str] = []
    log_files: List[object] = []
    rlog_f = None
    for i in range(max(1, num_engines)):
        engine_port = free_port()
        extra = (
            per_engine_args[i]
            if per_engine_args and i < len(per_engine_args) else []
        )
        cmd = [
            sys.executable, "-m",
            "production_stack_tpu.server.api_server",
            "--model", model, "--port", str(engine_port),
            *(["--compilation-cache-dir", compilation_cache_dir]
              if compilation_cache_dir is not None else []),
            *(engine_args or []),
            *extra,
        ]
        engine_urls.append(f"http://127.0.0.1:{engine_port}")
        engine_cmds.append(cmd)
    host_chips = local_tpu_chips()
    sizes = [_mesh_size(cmd) if host_chips else 0 for cmd in engine_cmds]
    if sum(sizes) > len(host_chips):
        raise RuntimeError(
            f"stack needs {sum(sizes)} TPU chip(s) ({sizes} per engine), "
            f"this host exposes {len(host_chips)}: {host_chips}"
        )
    engine_chips = [
        host_chips[sum(sizes[:i]):sum(sizes[:i + 1])]
        for i in range(len(sizes))
    ]
    try:
        for cmd, engine_url, chips in zip(engine_cmds, engine_urls,
                                          engine_chips):
            elog = os.path.join(
                log_dir,
                f"pstpu-bench-engine-{engine_url.rsplit(':', 1)[1]}.log",
            )
            elog_f = open(elog, "w")
            log_paths.append(elog)
            log_files.append(elog_f)
            engine_spawn_times.append(time.monotonic())
            engines.append(subprocess.Popen(
                cmd,
                stdout=elog_f, stderr=subprocess.STDOUT,
                env=_child_env(engine_env, chips),
            ))
            engine_log_files.append(elog_f)
        for engine, engine_url, spawn_t in zip(engines, engine_urls,
                                               engine_spawn_times):
            wait_health(f"{engine_url}/health", startup_timeout_s, engine,
                        f"engine {engine_url}")
            engine_ready_seconds.append(time.monotonic() - spawn_t)
        dyn_args: List[str] = []
        if dynamic_config_path is not None:
            with open(dynamic_config_path, "w") as f:
                json.dump({
                    "service_discovery": "static",
                    "static_backends": ",".join(engine_urls),
                    "static_models": ",".join([served] * len(engine_urls)),
                }, f)
            dyn_args = [
                "--dynamic-config-json", dynamic_config_path,
                "--dynamic-config-watch-interval",
                str(dynamic_config_watch_interval),
            ]
        peer_args: List[str] = []
        if num_routers > 1:
            # Shared breaker-gossip directory for the replica tier. The
            # gossip rides the dynamic-config watcher thread, so pin its
            # interval even when no config file is watched.
            peer_dir = os.path.join(
                log_dir, f"pstpu-router-peers-{router_ports[0]}"
            )
            os.makedirs(peer_dir, exist_ok=True)
            peer_args = ["--router-peer-dir", peer_dir]
            if not dyn_args:
                peer_args += ["--dynamic-config-watch-interval",
                              str(dynamic_config_watch_interval)]
        routers: List[subprocess.Popen] = []
        for i, rport in enumerate(router_ports):
            router_cmd = [
                sys.executable, "-m", "production_stack_tpu.router.app",
                "--port", str(rport),
                "--service-discovery", "static",
                "--static-backends", ",".join(engine_urls),
                "--static-models", ",".join([served] * len(engine_urls)),
                "--routing-logic", routing_logic,
                "--router-id", f"router-{i}",
                *peer_args,
                *dyn_args,
                *(router_args or []),
            ]
            rlog = os.path.join(log_dir, f"pstpu-bench-router-{rport}.log")
            rlog_f = open(rlog, "w")
            log_paths.append(rlog)
            log_files.append(rlog_f)
            routers.append(subprocess.Popen(
                router_cmd, stdout=rlog_f, stderr=subprocess.STDOUT,
            ))
        try:
            for r, rurl in zip(routers, router_urls):
                wait_health(f"{rurl}/health", 120.0, r, f"router {rurl}")
        except Exception:
            for r in routers:
                r.kill()
            raise
    except Exception:
        for engine in engines:
            engine.kill()
        for f in log_files:
            f.close()
        raise
    return StackHandle(
        engines=engines, routers=routers, engine_urls=engine_urls,
        router_urls=router_urls, log_paths=log_paths, log_files=log_files,
        engine_cmds=engine_cmds, engine_log_files=engine_log_files,
        engine_env=dict(engine_env) if engine_env else None,
        engine_chips=engine_chips,
        engine_ready_seconds=engine_ready_seconds,
        served_model=served,
        dynamic_config_path=dynamic_config_path,
        dynamic_config_watch_interval=dynamic_config_watch_interval,
        log_dir=log_dir,
    )
