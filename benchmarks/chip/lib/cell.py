"""One run of one cell: deployment up, set-up traffic, the measured window,
the reduction to metrics. ``run.py`` makes one of these; ``sweep.py`` makes
one and measures several windows at rising rates."""

import asyncio
import json
import os
import shutil
import time
from typing import Dict, List, Optional

from benchmarks.chip.lib import client, prom, stack, traffic
from benchmarks.chip.lib.manifest import Manifest, load_json

MAX_BOOTS = 3
POLL_S = 1.0
TRACE_S = 4.0
GAUGES = ("vllm:gpu_cache_usage_perc", "vllm:num_requests_waiting",
          "vllm:num_requests_running")


class CellRun:
    def __init__(self, manifest: Manifest, cell_name: str, seed: int,
                 rehearse: bool, started: float):
        self.manifest = manifest
        self.cell = manifest.cell(cell_name)
        self.seed = seed
        self.rehearse = rehearse
        self.started = started
        self.config = self.cell["config"]
        self.deployment = manifest.deployment(self.config)
        self.model_dir = manifest.model_dir(self.config)
        self.spec = manifest.traffic(self.cell["traffic"])
        self.cache_name = self.config
        if rehearse:
            self._apply_preset(load_json(os.path.join(
                manifest.chip_dir, "rehearse", "preset.json")))
        self.model_config = load_json(
            os.path.join(self.model_dir, "config.json"))
        self.work_dir = os.path.join(
            manifest.chip_dir, ".work", cell_name.replace("/", "_"))
        self.deployed: Optional[stack.Deployed] = None
        self.faults: List[str] = []
        self.boots: List[dict] = []
        self.device: dict = {}
        self.bytes_in_use = 0
        self.setup_s: Optional[float] = None

    # ------------------------------------------------------------ rehearsal
    def _apply_preset(self, preset: dict) -> None:
        """The CPU rehearsal: the same code at a tiny model, a tiny envelope
        and short texts. It never yields a result that counts."""
        self.model_dir = os.path.join(self.manifest.chip_dir, "rehearse")
        self.cache_name = "rehearse-cpu"
        overrides = {f["flag"]: f["value"] for f in preset["engine_flags"]}
        self.deployment = dict(self.deployment)
        self.deployment["engine_flags"] = [
            {**f, "value": overrides.pop(f["flag"], f["value"])}
            for f in self.deployment["engine_flags"]
        ] + [{"flag": k, "value": v} for k, v in overrides.items()]
        self.deployment["depth"] = preset["depth"]
        spec = dict(self.spec)
        scale = preset["length_scale"]
        for key in ("prompt", "output"):
            d = dict(spec[key])
            for field in ("median", "min", "max"):
                d[field] = max(preset[f"{key}_floor"],
                               int(d[field] * scale))
            spec[key] = d
        spec["system"] = {**spec["system"], "tokens": max(
            preset["system_floor"],
            int(spec["system"]["tokens"] * scale) // 16 * 16)}
        if spec["loop"] == "open":
            spec["rate_rps"] = preset["rate_rps"]
        else:
            spec["users"] = preset["users"]
            spec["rounds_max"] = preset["rounds_max"]
        self.spec = spec

    # --------------------------------------------------------------- set-up
    def boot(self) -> None:
        from benchmarks.stack import cpu_asked_for, local_tpu_chips

        if self.rehearse:
            if not cpu_asked_for():
                raise SystemExit("--rehearse runs on the CPU: set "
                                 "JAX_PLATFORMS=cpu")
        elif len(local_tpu_chips()) < self.cell["chips"]:
            raise SystemExit(
                f"cell {self.cell['name']} needs {self.cell['chips']} TPU "
                f"chip(s); this host exposes {len(local_tpu_chips())}. "
                f"Nothing was run (there is no CPU fallback).")
        shutil.rmtree(self.work_dir, ignore_errors=True)
        log_dir = os.path.join(self.work_dir, "logs")
        # A boot that had to compile (the first run in a checkout) is
        # followed by another, until a boot finds every program in the
        # cache: the manifest-verified warm boot traces some programs
        # afresh and misses a part of them once more (12 of 32, my chip
        # run, PR 23). Paid in the first run's set-up, which is recorded
        # apart, so that every later run's set-up is the same warm boot.
        for boot in range(MAX_BOOTS):
            try:
                self.deployed = stack.start(
                    self.manifest, self.config, self.deployment,
                    self.model_dir, self.seed % (1 << 31), log_dir,
                    self.cache_name)
            except Exception:
                stack.note("engine log tail:\n" + stack.log_tail(log_dir))
                raise
            engines = [v["engine"] for v in self.deployed.versions]
            self.boots.append({
                "hit": sum(e["cache_hit_families"] for e in engines),
                "miss": sum(e["cache_miss_families"] for e in engines),
                "deferred": sum(e["deferred_families"] for e in engines),
                "warmup_s": max(e["warmup_seconds"] for e in engines),
                "ready_s": max(self.deployed.handle.engine_ready_seconds),
            })
            stack.note("boot", json.dumps(self.boots[-1]))
            if not self.boots[-1]["miss"] or boot == MAX_BOOTS - 1:
                break
            self.deployed.stop()
        self._check_engines()

    def _check_engines(self) -> None:
        device = self.deployed.device()
        if not self.rehearse and device["platform"] != "tpu":
            raise SystemExit(f"the engines run on {device}: not a TPU, "
                             f"nothing was measured")
        if device["count"] != self.cell["chips"] and not self.rehearse:
            self.faults.append(f"{device['count']} devices serve a cell of "
                               f"{self.cell['chips']} chips")
        for version in self.deployed.versions:
            engine = version["engine"]
            if engine["pallas_interpret"]:
                self.faults.append("Pallas kernels run interpreted")
            if engine["num_layers"] != self.deployment["depth"]:
                self.faults.append(
                    f"engine depth {engine['num_layers']} != deployment "
                    f"depth {self.deployment['depth']}")
            if engine["warmup_failures"]:
                self.faults.append(
                    f"{engine['warmup_failures']} warm-up failure(s)")

    async def prepare(self, session, requests: dict) -> None:
        """Probes, preload, warm requests; everything here is set-up."""
        url, model = self.deployed.url, self.deployment["served_model_name"]
        probe = traffic.probe_request(self.spec, self.seed)
        cold = await client.send(session, url, model, probe, logprobs=True)
        hit = await client.send(session, url, model, probe, logprobs=True)
        for name, res in (("cold probe", cold), ("prefix-hit probe", hit)):
            self.faults += [f"{name}: {f}" for f in res.faults()]
        if cold.logprobs and hit.logprobs:
            diff = abs(cold.logprobs[0] - hit.logprobs[0])
            if diff > stack.PROBE_LOGPROB_TOL:
                self.faults.append(
                    f"first-token logprob cold {cold.logprobs[0]:.4f} vs "
                    f"prefix hit {hit.logprobs[0]:.4f}: apart by {diff:.4f} "
                    f"> {stack.PROBE_LOGPROB_TOL}")
        else:
            self.faults.append("a probe returned no log-probabilities")
        for group in ("preload", "warm"):
            results = await asyncio.gather(*(
                client.send(session, url, model, r)
                for r in requests[group]))
            for res in results:
                self.faults += [f"{group}: {f}" for f in res.faults()]
        await self.until_idle(session)

    async def until_idle(self, session, timeout_s: float = 60.0) -> None:
        deadline = time.perf_counter() + timeout_s
        while time.perf_counter() < deadline:
            gauges = await self._gauges(session)
            if not gauges.get("vllm:num_requests_running") and \
                    not gauges.get("vllm:num_requests_waiting"):
                return
            await asyncio.sleep(0.2)
        self.faults.append("engines not idle after set-up traffic")

    async def _scrape(self, session) -> Dict[str, float]:
        total: Dict[str, float] = {}
        for url in self.deployed.handle.engine_urls:
            async with session.get(f"{url}/metrics") as resp:
                total = prom.add(total, prom.parse(await resp.text()))
        return total

    async def _gauges(self, session) -> Dict[str, float]:
        scraped = await self._scrape(session)
        return {k: scraped[k] for k in GAUGES if k in scraped}

    # --------------------------------------------------------------- window
    async def window(self, session, requests: List[traffic.Request],
                     seconds: float, trace: bool, mark_setup: bool) -> dict:
        """Offer the window's traffic and wait for its last answer."""
        url, model = self.deployed.url, self.deployment["served_model_name"]
        spec = self.spec
        polls: List[dict] = []
        trace_info: dict = {}
        cache_before = stack.cache_entries(self.deployed.cache_path)
        before = await self._scrape(session)
        stop = asyncio.Event()

        async def poll(t0: float) -> None:
            while not stop.is_set():
                gauges = await self._gauges(session)
                polls.append({"t": time.perf_counter() - t0, **gauges})
                try:
                    await asyncio.wait_for(stop.wait(), POLL_S)
                except asyncio.TimeoutError:
                    pass

        async def capture(t0: float) -> None:
            # The LAST seconds of the window: stopping a capture blocks the
            # engine's HTTP loop for some 30 s while the profiler writes its
            # file (my chip run, PR 23), and at the window's end only the
            # last few requests feel that. The counters beside the capture
            # are read just before it stops, for the same reason, and
            # scaled to its length.
            length = min(TRACE_S, seconds / 3.0)
            counted = length - 0.25
            await asyncio.sleep(max(0.0, t0 + seconds - length
                                    - time.perf_counter()))
            dirs = []
            for i, engine in enumerate(self.deployed.handle.engine_urls):
                path = os.path.join(self.work_dir, "trace", f"engine{i}")
                async with session.post(
                    f"{engine}/debug/profile",
                    json={"duration_s": length, "trace_dir": path},
                ) as resp:
                    if resp.status != 200:
                        self.faults.append(
                            f"POST /debug/profile -> {resp.status}")
                        return
                dirs.append(path)
            first = await self._scrape(session)
            await asyncio.sleep(counted)
            delta = prom.delta(first, await self._scrape(session))
            trace_info.update(
                dirs=dirs, seconds=length,
                counters={k: v * length / counted for k, v in delta.items()})

        t0 = time.perf_counter() + 0.05
        if mark_setup:
            self.setup_s = t0 - self.started
        helpers = [asyncio.ensure_future(poll(t0))]
        if trace:
            helpers.append(asyncio.ensure_future(capture(t0)))
        if spec["loop"] == "open":
            results = await client.run_open(session, url, model, requests,
                                            t0)
        else:
            results, exhausted = await client.run_closed(
                session, url, model, requests, spec["users"], seconds, t0)
            if exhausted:
                self.faults.append(
                    "the closed loop ran out of requests: raise rounds_max "
                    "in the traffic file")
        end = time.perf_counter()
        stop.set()
        await asyncio.gather(*helpers)
        after = await self._scrape(session)
        new_programs = stack.cache_entries(
            self.deployed.cache_path) - cache_before
        if new_programs:
            self.faults.append(
                f"{len(new_programs)} program(s) compiled inside the window")
        return {
            "results": results, "t0": t0, "window_s": seconds,
            "span_s": end - t0, "counters": prom.delta(before, after),
            "polls": polls, "trace_info": trace_info,
        }

    def check_counts(self, win: dict) -> None:
        """The engines' token counters against the client's own sums."""
        results = win["results"]
        for res in results:
            if not res.ok:
                self.faults.append(
                    f"request {res.request.index}: {res.faults()[0]}")
        sent_prompt = sum(r.request.prompt_tokens for r in results)
        sent_output = sum(r.request.output_tokens for r in results)
        got_prompt = win["counters"].get("vllm:prompt_tokens_total")
        got_output = win["counters"].get("vllm:generation_tokens_total")
        if got_prompt != sent_prompt or got_output != sent_output:
            self.faults.append(
                f"engine counted {got_prompt} prompt / {got_output} "
                f"generated tokens, the client sent {sent_prompt} / "
                f"{sent_output}")

    def waiting(self, win: dict) -> dict:
        """Requests waiting in the engines near the window's middle and
        near its end (a backlog that grows is a cell above its knee)."""
        def near(t):
            got = [p for p in win["polls"] if "vllm:num_requests_waiting"
                   in p and abs(p["t"] - t) <= 2.5 * POLL_S]
            return (sum(p["vllm:num_requests_waiting"] for p in got)
                    / len(got)) if got else None
        return {"waiting_mid": near(win["window_s"] / 2),
                "waiting_end": near(win["window_s"] - POLL_S)}

    def read_device(self) -> None:
        """The device and its memory as the engines report them after the
        window (JAX's words, through ``GET /version``)."""
        self.deployed.refresh_versions()
        self.device = self.deployed.device()
        self.bytes_in_use = self.deployed.bytes_in_use()

    def stop(self) -> None:
        if self.deployed is not None:
            self.deployed.stop()
            self.deployed = None
