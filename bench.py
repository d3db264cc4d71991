"""Stack-level throughput benchmark (single chip).

Default mode measures the stack AS A STACK: it launches the engine API
server and the router as subprocesses (benchmarks/stack.py) and drives the
ROUTER's OpenAI endpoint with concurrent multi-round user sessions over
streaming HTTP with the session-affinity header
(benchmarks/multi_round_qa.py) — the same deployment shape and metric
definitions as the reference harness (reference
benchmarks/multi-round-qa/multi-round-qa.py:117-177,435-512; procedure
tutorials/07-benchmark-multi-round-qa-single-gpu.md). ``--mode engine``
keeps the old in-process engine drive for kernel-level iteration.

Prints exactly one JSON line:
    {"metric": ..., "value": N, "unit": "tok/s", "vs_baseline": N, ...}

The reference repo publishes no absolute numbers (BASELINE.md), so
``vs_baseline`` reports the fraction of the chip's HBM-bandwidth decode
roofline actually achieved: each decode step streams every weight byte once
(amortized over the whole batch) plus each row's live KV, so the AGGREGATE
ceiling is ``PEAK_BW / (param_bytes / batch + kv_bytes_per_token)`` tokens/sec
— the honest denominator for a memory-bound batched decode (SURVEY.md §6;
VERDICT r2 weak #1).
"""

import argparse
import asyncio
import json
import os
import sys
import time

# Roofline math lives in the package so the engine can export its live
# roofline position (pstpu:live_hbm_bw_pct) from the same arithmetic the
# bench JSON line uses; re-exported here for the historical import path
# (tests/test_kv_quant.py pins bench.roofline_components).
from production_stack_tpu.perf.roofline import (  # noqa: F401,E402
    peak_hbm_gbps,
    roofline_components,
)

# Schema version of the one-line JSON benchmark record. Bump when a field
# changes meaning; tools/perfwatch.py keys its tolerant loader on it.
BENCH_SCHEMA_VERSION = 2


# Byte-level fallback tokenizer yield: ~150 words of filler tokenize to
# ~1000 tokens (docs/PERF.md measurement), so words = tokens * 0.15.
WORDS_PER_TOKEN = 0.15


def _history_words(args) -> int:
    """Per-user seeded history in WORDS, clamped so the deepest round's
    context (system prompt + history + all rounds' questions/answers) still
    fits max_model_len. The reference shape is 20k history tokens —
    request it with --history-tokens 20000 --max-model-len 32768."""
    if args.history_tokens <= 0:
        return 0
    system_tokens = int(args.prompt_len / WORDS_PER_TOKEN)
    per_round = args.max_tokens + 150  # answer + tagged question
    budget = (args.max_model_len - system_tokens
              - args.rounds * per_round - 512)
    tokens = max(0, min(args.history_tokens, budget))
    if tokens < args.history_tokens:
        print(
            f"note: clamping --history-tokens {args.history_tokens} -> "
            f"{tokens} to fit --max-model-len {args.max_model_len}",
            file=sys.stderr,
        )
    return int(tokens * WORDS_PER_TOKEN)


def _scrape_prefix_counters(engine_urls) -> tuple:
    """(hit_tokens, query_tokens) summed over the engines' /metrics."""
    import urllib.request

    hits = queries = 0.0
    for url in engine_urls:
        with urllib.request.urlopen(f"{url}/metrics", timeout=10) as resp:
            text = resp.read().decode("utf-8", "replace")
        for line in text.splitlines():
            if line.startswith("vllm:gpu_prefix_cache_hits_total"):
                hits += float(line.rsplit(" ", 1)[1])
            elif line.startswith("vllm:gpu_prefix_cache_queries_total"):
                queries += float(line.rsplit(" ", 1)[1])
    return hits, queries


def _scrape_spec_metrics(engine_urls) -> dict:
    """Speculative-decoding telemetry summed over the engines' /metrics
    (docs/PERF.md round 8)."""
    import urllib.request

    out = {"spec_enabled": 0.0, "spec_draft_tokens": 0.0,
           "spec_accepted_tokens": 0.0, "spec_tree_nodes": 0.0,
           "spec_gamma0_dispatches": 0.0, "spec_draft_depth": 0.0,
           "spec_acceptance_rate_window": 0.0}
    depth_samples = window_samples = 0
    for url in engine_urls:
        try:
            with urllib.request.urlopen(
                f"{url}/metrics", timeout=10
            ) as resp:
                text = resp.read().decode("utf-8", "replace")
        except OSError:
            # Telemetry is best-effort: a scrape failure must not fail
            # the benchmark run itself.
            continue
        for line in text.splitlines():
            if line.startswith("pstpu:spec_enabled"):
                out["spec_enabled"] = max(
                    out["spec_enabled"], float(line.rsplit(" ", 1)[1])
                )
            elif line.startswith("pstpu:spec_draft_tokens_total"):
                out["spec_draft_tokens"] += float(line.rsplit(" ", 1)[1])
            elif line.startswith("pstpu:spec_accepted_tokens_total"):
                out["spec_accepted_tokens"] += float(line.rsplit(" ", 1)[1])
            elif line.startswith("pstpu:spec_tree_nodes_total"):
                out["spec_tree_nodes"] += float(line.rsplit(" ", 1)[1])
            elif line.startswith("pstpu:spec_gamma0_dispatches_total"):
                out["spec_gamma0_dispatches"] += float(
                    line.rsplit(" ", 1)[1]
                )
            # Window rate MUST be matched before the bare acceptance-rate
            # prefix: "pstpu:spec_acceptance_rate" is a startswith-prefix
            # of the windowed series name.
            elif line.startswith("pstpu:spec_acceptance_rate_window"):
                out["spec_acceptance_rate_window"] += float(
                    line.rsplit(" ", 1)[1]
                )
                window_samples += 1
            elif line.startswith("pstpu:spec_draft_depth"):
                out["spec_draft_depth"] += float(line.rsplit(" ", 1)[1])
                depth_samples += 1
    # Gauges average across engines (counters above simply sum).
    if depth_samples:
        out["spec_draft_depth"] = round(
            out["spec_draft_depth"] / depth_samples, 4
        )
    if window_samples:
        out["spec_acceptance_rate_window"] = round(
            out["spec_acceptance_rate_window"] / window_samples, 4
        )
    out["spec_acceptance_rate"] = round(
        out["spec_accepted_tokens"] / out["spec_draft_tokens"], 4
    ) if out["spec_draft_tokens"] else 0.0
    return out


def _scrape_handoff_metrics(url: str) -> dict:
    """Per-engine disagg telemetry from /metrics (role + pstpu:kv_handoff_*)."""
    import re
    import urllib.request

    with urllib.request.urlopen(f"{url}/metrics", timeout=10) as resp:
        text = resp.read().decode("utf-8", "replace")
    out = {"role": "unified", "kv_handoff_bytes": 0.0,
           "kv_handoff_seconds": 0.0, "kv_handoffs": 0.0,
           "kv_handoff_failures": 0.0}
    for line in text.splitlines():
        if line.startswith("pstpu:disagg_role"):
            m = re.search(r'role="([^"]+)"', line)
            if m:
                out["role"] = m.group(1)
        elif line.startswith("pstpu:kv_handoff_bytes_total"):
            out["kv_handoff_bytes"] = float(line.rsplit(" ", 1)[1])
        elif line.startswith("pstpu:kv_handoff_seconds_total"):
            out["kv_handoff_seconds"] = float(line.rsplit(" ", 1)[1])
        elif line.startswith("pstpu:kv_handoff_failures_total"):
            out["kv_handoff_failures"] = float(line.rsplit(" ", 1)[1])
        elif line.startswith("pstpu:kv_handoffs_total"):
            out["kv_handoffs"] = float(line.rsplit(" ", 1)[1])
    return out


# --------------------------------------------------------------- stack mode
def _arm_profile(engine_url: str, duration_s: float,
                 trace_dir=None):
    """POST /debug/profile to an engine (docs/OBSERVABILITY.md). Returns
    the capture info dict, or a reason record when profiling is
    unavailable — a bench with --profile never fails on the capture."""
    import urllib.error
    import urllib.request

    body = {"duration_s": duration_s}
    if trace_dir:
        body["trace_dir"] = trace_dir
    req = urllib.request.Request(
        f"{engine_url}/debug/profile", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"}, method="POST",
    )
    try:
        with urllib.request.urlopen(req, timeout=15) as resp:
            info = json.loads(resp.read().decode("utf-8", "replace"))
        print(f"device profiling armed on {engine_url}: "
              f"{info.get('trace_dir')}", file=sys.stderr)
        return {"engine": engine_url, **info}
    except urllib.error.HTTPError as e:
        reason = ("profiling unavailable (404)" if e.code == 404
                  else f"profile arm failed: HTTP {e.code}")
        print(f"--profile: {reason} on {engine_url}", file=sys.stderr)
        return {"engine": engine_url, "status": "unavailable",
                "reason": reason}
    except OSError as e:
        print(f"--profile: arm failed on {engine_url}: {e}",
              file=sys.stderr)
        return {"engine": engine_url, "status": "unavailable",
                "reason": repr(e)}


def bench_stack(args) -> dict:
    from benchmarks.multi_round_qa import (
        WorkloadConfig,
        run_workload,
        summarize,
    )
    from benchmarks.stack import launch_kv_server, launch_stack

    # Prefix-aware routing (docs/KV_ECONOMY.md) needs the full KV economy:
    # a shared cache server every engine spills to (so the router's
    # shared-tier restorability rung is live) and the router hashing
    # prompts with the engines' exact tokenizer.
    kv_proc = kv_log_f = None
    router_args = ["--session-key", "x-user-id"]
    engine_env = None
    if args.routing_logic == "prefix-aware":
        kv_proc, kv_url, _kv_log, kv_log_f = launch_kv_server()
        engine_env = {"LMCACHE_REMOTE_URL": kv_url}
        router_args += [
            "--prefix-tokenizer", args.model,
            "--kv-offload-url", kv_url,
            # Residency moves fast under a bench workload; scrape the
            # digests faster than the default 10s or the index trails the
            # rounds it should be routing.
            "--engine-stats-interval", "2",
        ]
    stack = launch_stack(
        args.model,
        # Elastic fast-start (docs/ELASTIC.md): a shared persistent
        # compile-cache dir makes the cold-vs-warm boot A/B two recorded
        # bench lines (engine_ready_seconds + startup_cache_*_families).
        compilation_cache_dir=getattr(args, "compilation_cache_dir", None),
        engine_args=[
            "--max-model-len", str(args.max_model_len),
            "--max-num-seqs", str(max(8, args.users)),
            "--attn-impl", args.attn_impl,
            "--kv-cache-dtype", args.kv_cache_dtype,
            *(["--max-num-batched-tokens",
               str(args.max_num_batched_tokens)]
              if getattr(args, "max_num_batched_tokens", None) else []),
            *(["--no-warmup"]
              if getattr(args, "no_engine_warmup", False) else []),
            *(["--decode-loop", args.decode_loop]
              if args.decode_loop else []),
            *(["--no-overlap-dispatch"] if args.no_overlap else []),
            # getattr: test harnesses build partial Namespaces.
            *(["--speculative-num-tokens",
               str(getattr(args, "speculative_num_tokens", 0)),
               "--speculative-model",
               getattr(args, "speculative_model", None) or ""]
              if getattr(args, "speculative_num_tokens", 0) else []),
            *(["--speculative-draft-window",
               str(getattr(args, "speculative_draft_window", None))]
              if getattr(args, "speculative_draft_window", None) is not None
              else []),
            *(["--speculative-adaptive"]
              if getattr(args, "speculative_adaptive", False) else []),
            *(["--speculative-tree-width",
               str(getattr(args, "speculative_tree_width", 1))]
              if getattr(args, "speculative_tree_width", 1) != 1 else []),
        ],
        routing_logic=args.routing_logic,
        router_args=router_args,
        num_engines=args.num_engines,
        num_routers=max(1, getattr(args, "num_routers", 1) or 1),
        engine_env=engine_env,
        tensor_parallel_size=getattr(args, "tensor_parallel_size", 1),
    )
    profile_info = None
    try:
        cfg = WorkloadConfig(
            base_url=stack.router_url,
            base_urls=(list(stack.router_urls)
                       if len(getattr(stack, "router_urls", []) or []) > 1
                       else None),
            model=args.model,
            num_users=args.users,
            num_rounds=args.rounds,
            system_prompt_words=args.prompt_len,
            answer_tokens=args.max_tokens,
            history_words=_history_words(args),
        )
        # Warmup: the same shapes as the measurement so every bucket the
        # timed region hits (prefill chunks, the fused decode scan) is
        # compiled before timing starts — but with a distinct question tag so
        # only the intentionally shared system prefix is warm in the prefix
        # cache, never the timed rounds' full prompts or histories (the
        # warmup pass seeds DIFFERENT history text — see UserSession).
        warm = WorkloadConfig(**{**cfg.__dict__, "num_rounds": 2,
                                 "tag": "warmup"})
        asyncio.run(run_workload(warm))
        # On-demand device profiling (docs/OBSERVABILITY.md): arm a
        # bounded jax.profiler capture on the first engine right before
        # the timed region, so this BENCH run carries a perfetto
        # per-dispatch timeline alongside its numbers.
        if getattr(args, "profile", 0):
            profile_info = _arm_profile(
                stack.engine_urls[0], float(args.profile),
                getattr(args, "profile_trace_dir", None),
            )
        # KV-hit parity (BASELINE target #3) is measured over the TIMED
        # region only: delta of the engines' prefix-cache hit/query token
        # counters around the workload.
        h0, q0 = _scrape_prefix_counters(stack.engine_urls)
        records = asyncio.run(run_workload(cfg))
        h1, q1 = _scrape_prefix_counters(stack.engine_urls)
        spec = _scrape_spec_metrics(stack.engine_urls)
        from benchmarks.soak import engine_startup_stats

        startup = [engine_startup_stats(u) for u in stack.engine_urls]
        # getattr: test harnesses substitute minimal stack fakes.
        ready_seconds = [
            round(s, 3)
            for s in getattr(stack, "engine_ready_seconds", [])
        ]
    finally:
        stack.terminate()
        if kv_proc is not None and kv_proc.poll() is None:
            kv_proc.terminate()
            try:
                kv_proc.wait(timeout=10)
            except Exception:  # noqa: BLE001 — last resort
                kv_proc.kill()
        if kv_log_f is not None:
            kv_log_f.close()
    summary = summarize(records)
    if not summary.get("finished_requests"):
        raise RuntimeError(
            "stack benchmark finished zero requests — check the subprocess "
            f"logs: {stack.log_paths}"
        )
    avg_prompt = summary["total_prompt_tokens"] / summary["finished_requests"]
    chips = (max(1, getattr(args, "tensor_parallel_size", 1))
             * max(1, getattr(args, "num_engines", 1)))
    return {
        "metric": f"stack_output_throughput_{args.model}_{chips}chip",
        "value": round(summary["output_tokens_per_s"], 2),
        "summary": summary,
        "avg_prompt_tokens": avg_prompt,
        "kv_hit_rate": round((h1 - h0) / max(1.0, q1 - q0), 4),
        "spec": spec,
        # Elastic fast-start (docs/ELASTIC.md): per-engine process spawn
        # -> /health-serving seconds + each engine's startup-phase /
        # compile-cache telemetry — the cold-vs-warm A/B's recorded form.
        "engine_ready_seconds": ready_seconds,
        "engine_startup": startup,
        **({"profile": profile_info} if profile_info else {}),
    }


# -------------------------------------------------------------- disagg mode
def bench_disagg(args) -> dict:
    """1-prefill + 1-decode stack over a shared kv_offload store, driven
    through the router's disagg two-hop flow (docs/DISAGG.md). Reports the
    usual stack JSON line plus per-role TTFT/ITL attribution and the KV
    handoff plane's transfer telemetry. Any 5xx fails the run (the
    workload client raises on error statuses)."""
    from benchmarks.multi_round_qa import (
        WorkloadConfig,
        run_workload,
        summarize,
    )
    from benchmarks.stack import launch_kv_server, launch_stack

    kv_proc, kv_url, kv_log, kv_log_f = launch_kv_server()
    stack = None
    try:
        stack = launch_stack(
            args.model,
            engine_args=[
                "--max-model-len", str(args.max_model_len),
                "--max-num-seqs", str(max(8, args.users)),
                "--attn-impl", args.attn_impl,
                "--kv-cache-dtype", args.kv_cache_dtype,
                *(["--no-warmup"] if getattr(args, "backend", "") == "cpu"
                  else []),
            ],
            per_engine_args=[["--role", "prefill"], ["--role", "decode"]],
            engine_env={"LMCACHE_REMOTE_URL": kv_url},
            routing_logic="disagg",
            router_args=[
                "--session-key", "x-user-id",
                "--kv-offload-url", kv_url,
                "--static-backend-roles", "prefill,decode",
            ],
            num_engines=2,
        )
        cfg = WorkloadConfig(
            base_url=stack.router_url,
            model=args.model,
            num_users=args.users,
            num_rounds=args.rounds,
            system_prompt_words=args.prompt_len,
            answer_tokens=args.max_tokens,
            history_words=_history_words(args),
        )
        warm = WorkloadConfig(**{**cfg.__dict__, "num_rounds": 1,
                                 "tag": "warmup"})
        asyncio.run(run_workload(warm))
        h0, q0 = _scrape_prefix_counters(stack.engine_urls)
        records = asyncio.run(run_workload(cfg))
        h1, q1 = _scrape_prefix_counters(stack.engine_urls)
        per_engine = {
            url: _scrape_handoff_metrics(url) for url in stack.engine_urls
        }
    finally:
        if stack is not None:
            stack.terminate()
        if kv_proc.poll() is None:
            kv_proc.terminate()
            try:
                kv_proc.wait(timeout=10)
            except Exception:  # noqa: BLE001 — last resort
                kv_proc.kill()
        kv_log_f.close()
    summary = summarize(records)
    if not summary.get("finished_requests"):
        raise RuntimeError(
            "disagg benchmark finished zero requests — check the subprocess "
            f"logs: {stack.log_paths + [kv_log]}"
        )
    # Per-role latency attribution: the client-side TTFT covers the prefill
    # hop + KV handoff; the inter-token cadence after token 1 is pure
    # decode-pool time.
    itls = sorted(
        (r.finish_time - r.launch_time - r.ttft)
        / max(1, r.generation_tokens - 1)
        for r in records if r.generation_tokens > 1
    )
    roles = {m["role"]: {**m, "url": url} for url, m in per_engine.items()}
    # Transfer volume counts each bundle ONCE (the publish side); the
    # per-role dicts keep both sides' counters (publish vs consume time).
    # Failures are genuinely per-side, so those do sum.
    pre_side = roles.get("prefill") or {}
    disagg = {
        "prefill": roles.get("prefill"),
        "decode": roles.get("decode"),
        "kv_handoff_bytes": pre_side.get("kv_handoff_bytes", 0.0),
        "kv_handoff_seconds": pre_side.get("kv_handoff_seconds", 0.0),
        "kv_handoff_failures": sum(
            m["kv_handoff_failures"] for m in per_engine.values()
        ),
        "prefill_p50_ttft_s": round(summary["p50_ttft_s"], 4),
        "decode_p50_itl_s": round(itls[len(itls) // 2], 4) if itls else None,
    }
    avg_prompt = summary["total_prompt_tokens"] / summary["finished_requests"]
    return {
        "metric": f"disagg_output_throughput_{args.model}_1p1d",
        "value": round(summary["output_tokens_per_s"], 2),
        "summary": summary,
        "avg_prompt_tokens": avg_prompt,
        "kv_hit_rate": round((h1 - h0) / max(1.0, q1 - q0), 4),
        "disagg": disagg,
    }


def probe_device() -> dict:
    """{"platform", "kind", "count"} of the devices a run will find.

    ``JAX_PLATFORMS=cpu`` ASKS for the CPU correctness path (tiny model, no
    roofline share) and is answered without starting JAX. Anything else
    asks JAX in a SUBPROCESS that exits before any engine starts — in
    stack mode the engine children own the chips, and the in-process modes
    (bench_engine, the speculative A/B) open them themselves afterwards.
    A probe that fails, or that finds only a CPU nobody asked for, is an
    error: it must never turn a chip run into a CPU run that still prints
    tok/s."""
    import subprocess

    from benchmarks.stack import cpu_asked_for

    if cpu_asked_for():
        return {"platform": "cpu", "kind": "cpu", "count": 0}
    probe = subprocess.run(
        [sys.executable, "-c",
         "import jax, json; d = jax.devices(); print(json.dumps("
         "{'platform': d[0].platform, 'kind': d[0].device_kind, "
         "'count': len(d)}))"],
        capture_output=True, text=True, timeout=300,
    )
    lines = probe.stdout.strip().splitlines()
    if probe.returncode != 0 or not lines:
        raise SystemExit(
            f"bench.py: device probe failed (rc={probe.returncode}); "
            f"refusing to fall back to the CPU. Set JAX_PLATFORMS=cpu to "
            f"ask for the CPU correctness path.\n{probe.stderr[-2000:]}"
        )
    device = json.loads(lines[-1])
    if device["platform"] == "cpu":
        raise SystemExit(
            "bench.py: JAX found no accelerator. Set JAX_PLATFORMS=cpu to "
            "ask for the CPU correctness path."
        )
    return device


# ----------------------------------------------------------- multichip mode
def _force_virtual_devices(args, need: int) -> None:
    """CPU backend (asked for with JAX_PLATFORMS=cpu — args.backend is
    "cpu" in no other case, see probe_device): expose a virtual
    multi-device platform to this process AND every engine subprocess it
    spawns (they inherit the environment). The same serving code path on
    a TPU host sees the real chips and needs none of this. Idempotent;
    pinned to 8 devices (the CI mesh and every sweep point 1/2/4/8 fit
    it)."""
    if args.backend != "cpu" or need <= 1:
        return
    import re

    n = max(8, need)
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(r"--xla_force_host_platform_device_count=(\d+)", flags)
    if m is None:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}"
        ).strip()
    elif int(m.group(1)) < n:
        # A pre-existing smaller count would make the widest sweep point
        # fail its mesh build after the narrower points already ran.
        os.environ["XLA_FLAGS"] = flags.replace(
            m.group(0), f"--xla_force_host_platform_device_count={n}"
        )


def bench_multichip_sweep(args) -> dict:
    """The 1/2/4/8-chip serving scaling curve (docs/PERF.md round 9):
    bench_stack at each tp point of --multichip-sweep, same workload, with
    a hard zero-5xx bar per point. The report's ``curve`` is what
    tools/capacity.py turns into a chips->QPS capacity model."""
    chip_points = [
        int(x) for x in str(args.multichip_sweep).split(",") if x.strip()
    ]
    if not chip_points:
        raise ValueError("--multichip-sweep needs a chip list, e.g. 1,2,4,8")
    _force_virtual_devices(args, max(chip_points))
    if args.backend == "cpu":
        # Startup AOT warmup at every tp point would dominate the sweep's
        # wall clock on CPU; the warmup WORKLOAD pass before each timed
        # region still compiles every shape the measurement hits.
        args.no_engine_warmup = True
    runs = []
    curve = []
    base_per_chip = None
    for chips in chip_points:
        args.tensor_parallel_size = chips
        res = bench_stack(args)
        line = _result_line(args, res)
        errors = line.get("errors_total", 0)
        if errors:
            raise RuntimeError(
                f"multichip sweep point tp={chips} leaked {errors} "
                f"client-visible 5xx — a scaling curve over a failing "
                f"configuration is not serving evidence"
            )
        per_chip = line["tok_per_s_per_chip"]
        if base_per_chip is None:
            base_per_chip = per_chip or 1.0
        curve.append({
            "chips": line["num_chips"],
            "tok_s": line["value"],
            "tok_per_s_per_chip": per_chip,
            "scaling_efficiency": round(per_chip / base_per_chip, 4),
            "qps": line.get("qps"),
            "p50_ttft_s": line.get("p50_ttft_s"),
            "avg_ttft_s": line.get("avg_ttft_s"),
            "hbm_bw_pct": line.get("hbm_bw_pct"),
            "finished_requests": line.get("finished_requests"),
            "errors_total": errors,
        })
        runs.append(line)
        print(json.dumps({"sweep_point": curve[-1]}), file=sys.stderr)
    return {
        "metric": f"multichip_serving_scaling_{args.model}",
        "unit": "tok/s",
        "backend": args.backend,
        "model": args.model,
        "workload": {
            "users": args.users,
            "rounds": args.rounds,
            "history_tokens_per_user": args.history_tokens,
            "max_model_len": args.max_model_len,
            "max_tokens": args.max_tokens,
            "kv_cache_dtype": args.kv_cache_dtype,
        },
        "curve": curve,
        "zero_5xx": True,
        "serving": True,   # real bench harness, not a dryrun parity check
        "runs": runs,
    }


def bench_router_sweep(args) -> dict:
    """Router-tier QPS ceiling (docs/ROUTER_SCALE.md): bench_stack at
    each replica count of --router-sweep over the SAME engine fleet and
    workload, zero-5xx bar per point. The ``curve`` is what
    tools/capacity.py --router-report folds into the chips->QPS model
    (routers-per-QPS + the router_queue_depth HPA target)."""
    points = [
        int(x) for x in str(args.router_sweep).split(",") if x.strip()
    ]
    if not points:
        raise ValueError("--router-sweep needs a replica list, e.g. 1,2")
    _force_virtual_devices(args, args.tensor_parallel_size)
    if args.backend == "cpu":
        args.no_engine_warmup = True
    runs = []
    curve = []
    base_qps = None
    for n in points:
        args.num_routers = n
        res = bench_stack(args)
        line = _result_line(args, res)
        errors = line.get("errors_total", 0)
        if errors:
            raise RuntimeError(
                f"router sweep point routers={n} leaked {errors} "
                f"client-visible 5xx — a ceiling over a failing tier is "
                f"not serving evidence"
            )
        qps = line.get("qps")
        if base_qps is None:
            base_qps = qps or 1.0
        curve.append({
            "routers": n,
            "qps": qps,
            "qps_per_router": round((qps or 0.0) / n, 4),
            "qps_vs_one_router": round((qps or 0.0) / base_qps, 4),
            "tok_s": line["value"],
            "p50_ttft_s": line.get("p50_ttft_s"),
            "avg_ttft_s": line.get("avg_ttft_s"),
            "finished_requests": line.get("finished_requests"),
            "errors_total": errors,
        })
        runs.append(line)
        print(json.dumps({"router_sweep_point": curve[-1]}),
              file=sys.stderr)
    return {
        "metric": f"router_tier_scaling_{args.model}",
        "unit": "qps",
        "backend": args.backend,
        "model": args.model,
        "num_engines": args.num_engines,
        "workload": {
            "users": args.users,
            "rounds": args.rounds,
            "history_tokens_per_user": args.history_tokens,
            "max_tokens": args.max_tokens,
        },
        "curve": curve,
        "zero_5xx": True,
        "serving": True,
        "runs": runs,
    }


# -------------------------------------------------------------- engine mode
async def _run_session(engine, sampling, prompt, ttfts, prompt_toks=None):
    start = time.monotonic()
    first = None
    n_out = 0
    async for out in engine.generate(prompt=prompt, sampling=sampling):
        if first is None and out.num_output_tokens > 0:
            first = time.monotonic() - start
        n_out = out.num_output_tokens
        if prompt_toks is not None and out.num_prompt_tokens:
            prompt_toks.append(out.num_prompt_tokens)
            prompt_toks = None
    ttfts.append(first if first is not None else time.monotonic() - start)
    return n_out


async def _bench_engine(engine, n_users, rounds, prompt_len, max_tokens,
                        history_words=0):
    from benchmarks.multi_round_qa import synth_text
    from production_stack_tpu.engine.sampling import SamplingParams

    system = "You are a helpful assistant. " * max(1, prompt_len // 30)

    def history(u, tag):
        if history_words <= 0:
            return ""
        return (f" user {u} {tag} history: "
                + synth_text(history_words, seed=u * 131))

    sampling = SamplingParams(
        temperature=0.0, max_tokens=max_tokens, ignore_eos=True
    )
    # Warmup at the SAME max_tokens as the timed rounds (a warmup at smaller
    # max_tokens leaves the measured decode scan shape cold and its
    # multi-second XLA compile lands inside the timing).
    ttfts = []
    for w in range(2):
        await asyncio.gather(*[
            _run_session(
                engine, sampling,
                system + history(u, "warmup")
                + f" user {u} warmup {w}: please continue the story..",
                ttfts,
            )
            for u in range(n_users)
        ])
    ttfts.clear()

    s0 = engine.stats()
    t_start = time.monotonic()
    total_out = 0
    prompt_toks = []
    for r in range(rounds):
        tasks = [
            _run_session(
                engine, sampling,
                system + history(u, "round")
                + f" user {u} round {r}: please continue the story.",
                ttfts, prompt_toks,
            )
            for u in range(n_users)
        ]
        total_out += sum(await asyncio.gather(*tasks))
    elapsed = time.monotonic() - t_start
    s1 = engine.stats()
    ttfts.sort()
    return {
        "output_tok_s": total_out / elapsed,
        "p50_ttft_s": ttfts[len(ttfts) // 2] if ttfts else None,
        "total_output_tokens": total_out,
        "elapsed_s": elapsed,
        "avg_prompt_tokens": (
            sum(prompt_toks) / len(prompt_toks) if prompt_toks else 0
        ),
        "kv_hit_rate": round(
            (s1["prefix_cache_hits"] - s0["prefix_cache_hits"])
            / max(1, s1["prefix_cache_queries"] - s0["prefix_cache_queries"]),
            4,
        ),
    }


def bench_engine(args) -> dict:
    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.engine.engine import ServingEngine

    on_tpu = args.backend != "cpu"
    cfg = EngineConfig(
        model=args.model,
        max_model_len=args.max_model_len,
        block_size=16,
        max_num_seqs=max(8, args.users),
        max_num_batched_tokens=1024,
        num_kv_blocks=None if on_tpu else 2048,
        kv_cache_dtype=args.kv_cache_dtype,
        **({"decode_loop": args.decode_loop} if args.decode_loop else {}),
        overlap_dispatch=not args.no_overlap,
        speculative_num_tokens=getattr(args, "speculative_num_tokens", 0),
        speculative_model=getattr(args, "speculative_model", None),
        **({"speculative_draft_window": args.speculative_draft_window}
           if getattr(args, "speculative_draft_window", None) is not None
           else {}),
        speculative_adaptive=getattr(args, "speculative_adaptive", False),
        speculative_tree_width=getattr(args, "speculative_tree_width", 1),
    )
    engine = ServingEngine(cfg)

    async def run():
        await engine.start()
        try:
            return await _bench_engine(
                engine, args.users, args.rounds, args.prompt_len,
                args.max_tokens, history_words=_history_words(args),
            )
        finally:
            await engine.stop()

    res = asyncio.run(run())
    st = engine.stats()
    drafts = st.get("spec_draft_tokens_total", 0)
    return {
        "metric": f"engine_output_throughput_{args.model}_1chip",
        "value": round(res["output_tok_s"], 2),
        "summary": res,
        "avg_prompt_tokens": res["avg_prompt_tokens"],
        "kv_hit_rate": res["kv_hit_rate"],
        "spec": {
            "spec_enabled": st.get("spec_enabled", 0),
            "spec_draft_tokens": drafts,
            "spec_accepted_tokens": st.get("spec_accepted_tokens_total", 0),
            "spec_acceptance_rate": round(
                st.get("spec_acceptance_rate", 0.0), 4
            ),
            "spec_acceptance_rate_window": round(
                st.get("spec_acceptance_rate_window", 0.0), 4
            ),
            "spec_draft_depth": round(st.get("spec_draft_depth", 0.0), 4),
            "spec_tree_nodes": st.get("spec_tree_nodes_total", 0),
            "spec_gamma0_dispatches": st.get(
                "spec_gamma0_dispatches_total", 0
            ),
        },
    }


def _spec_runner_snapshot(engine) -> dict:
    """Cumulative speculative counters straight off the in-process runner
    (the A/B diffs these around each workload, so per-workload acceptance
    and served depth are exact rather than lifetime means)."""
    r = engine.runner
    return {
        "drafts": int(getattr(r, "spec_draft_tokens_total", 0)),
        "accepted": int(getattr(r, "spec_accepted_tokens_total", 0)),
        "cycles": int(getattr(r, "spec_live_cycles_total", 0)),
        "tree_nodes": int(getattr(r, "spec_tree_nodes_total", 0)),
        "gamma0_dispatches": int(
            getattr(r, "spec_gamma0_dispatches_total", 0)
        ),
    }


def bench_speculative_ab(args) -> dict:
    """Acceptance-limited speculative A/B (docs/PERF.md round 10; the
    BENCH_r10 evidence shape): the SAME seeded workload through four
    in-process engine configs — spec-off, fixed linear-gamma, token-tree
    verify, and adaptive per-sequence gamma — comparing effective emitted
    tokens per target-model step and asserting token-identical outputs
    across all four (greedy AND seeded: round 8's determinism bar,
    extended over the tree/adaptive paths).

    Two workload axes per mode:
      * cache_friendly — greedy continuation, where a (windowed) draft
        tracks the target closely and linear chains already accept deep;
      * acceptance_limited — per-user seeded temperature sampling, where
        the target's own sampled path diverges from the draft chain after
        the first position, so depth stops paying and first-position
        BREADTH (tree alternates) or backing off (adaptive gamma) is the
        only way to keep effective tokens up.

    Effective tokens per target step is computed exactly from runner
    counter deltas: 1 + accepted / live_cycles (every live speculative
    cycle emits the accepted prefix plus the target's own bonus token).
    """
    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.engine.engine import ServingEngine
    from production_stack_tpu.engine.sampling import SamplingParams

    on_tpu = args.backend != "cpu"
    n_spec = getattr(args, "speculative_num_tokens", 0) or 3
    tree_w = getattr(args, "speculative_tree_width", 1)
    if tree_w <= 1:
        tree_w = 3
    draft = getattr(args, "speculative_model", None) or args.model
    spec_base = {
        "speculative_num_tokens": n_spec,
        "speculative_model": draft,
        **({"speculative_draft_window": args.speculative_draft_window}
           if getattr(args, "speculative_draft_window", None) is not None
           else {}),
    }
    modes = [
        ("off", {}),
        ("linear", dict(spec_base)),
        ("tree", dict(spec_base, speculative_tree_width=tree_w)),
        ("adaptive", dict(spec_base, speculative_tree_width=tree_w,
                          speculative_adaptive=True)),
    ]

    users = max(1, args.users)
    system = "You are a helpful assistant. " * max(1, args.prompt_len // 30)
    prompts = [
        system + f" user {u} ab-round: please continue the story."
        for u in range(users)
    ]
    workloads = [
        ("cache_friendly", [
            SamplingParams(temperature=0.0, max_tokens=args.max_tokens,
                           ignore_eos=True)
            for _ in range(users)
        ]),
        # Moderate temperature: the target's sampled path diverges from
        # the draft chain (acceptance-limited) while keeping enough mass
        # concentration that a diverging sample often still sits in the
        # draft's top-k — the regime first-position BREADTH salvages.
        ("acceptance_limited", [
            SamplingParams(temperature=0.4, max_tokens=args.max_tokens,
                           ignore_eos=True, seed=7000 + u)
            for u in range(users)
        ]),
    ]

    async def _collect(engine, prompt, sampling):
        toks = []
        async for out in engine.generate(prompt=prompt, sampling=sampling):
            if out.token_ids:
                toks = list(out.token_ids)
        return toks

    async def _run_mode(cfg_kwargs):
        cfg = EngineConfig(
            model=args.model,
            max_model_len=args.max_model_len,
            block_size=16,
            max_num_seqs=max(8, users),
            max_num_batched_tokens=1024,
            num_kv_blocks=None if on_tpu else 2048,
            kv_cache_dtype=args.kv_cache_dtype,
            **cfg_kwargs,
        )
        engine = ServingEngine(cfg)
        await engine.start()
        try:
            mode_res = {"workloads": {}, "outputs": {}}
            for wl_name, samplings in workloads:
                s0 = _spec_runner_snapshot(engine)
                t0 = time.monotonic()
                outs = await asyncio.gather(*[
                    _collect(engine, prompts[u], samplings[u])
                    for u in range(users)
                ])
                elapsed = time.monotonic() - t0
                s1 = _spec_runner_snapshot(engine)
                d = {k: s1[k] - s0[k] for k in s0}
                cycles = d["cycles"]
                total_out = sum(len(t) for t in outs)
                mode_res["outputs"][wl_name] = outs
                mode_res["workloads"][wl_name] = {
                    "output_tok_s": round(total_out / elapsed, 2),
                    "total_output_tokens": total_out,
                    "spec_draft_tokens": d["drafts"],
                    "spec_accepted_tokens": d["accepted"],
                    "spec_live_cycles": cycles,
                    "spec_tree_nodes": d["tree_nodes"],
                    "spec_gamma0_dispatches": d["gamma0_dispatches"],
                    "spec_acceptance_rate": round(
                        d["accepted"] / d["drafts"], 4
                    ) if d["drafts"] else 0.0,
                    "spec_draft_depth": round(
                        d["drafts"] / cycles, 4
                    ) if cycles else 0.0,
                    "effective_tokens_per_target_step": round(
                        1.0 + d["accepted"] / cycles, 4
                    ) if cycles else 1.0,
                }
            return mode_res
        finally:
            await engine.stop()

    results = {}
    outputs = {}
    for name, cfg_kwargs in modes:
        res = asyncio.run(_run_mode(cfg_kwargs))
        outputs[name] = res.pop("outputs")
        results[name] = res["workloads"]
        print(json.dumps({"speculative_ab_point": {name: results[name]}}),
              file=sys.stderr)

    # Token-identity bar: every speculative mode must emit EXACTLY the
    # spec-off tokens, greedy and seeded alike (speculation is a latency
    # optimization, never a sampling change).
    identity = {
        name: all(
            outputs[name][wl] == outputs["off"][wl]
            for wl, _ in workloads
        )
        for name in outputs if name != "off"
    }
    eff = {
        name: {
            wl: results[name][wl]["effective_tokens_per_target_step"]
            for wl, _ in workloads
        }
        for name in results
    }
    bar = {
        "tree_ge_linear_acceptance_limited":
            eff["tree"]["acceptance_limited"]
            >= eff["linear"]["acceptance_limited"],
        "adaptive_ge_linear_acceptance_limited":
            eff["adaptive"]["acceptance_limited"]
            >= eff["linear"]["acceptance_limited"],
        "tree_no_regression_cache_friendly":
            eff["tree"]["cache_friendly"]
            >= eff["linear"]["cache_friendly"] - 0.05,
        "adaptive_no_regression_cache_friendly":
            eff["adaptive"]["cache_friendly"]
            >= eff["linear"]["cache_friendly"] - 0.05,
    }
    return {
        "metric": f"speculative_ab_{args.model}",
        "backend": args.backend,
        "model": args.model,
        "speculative_model": draft,
        "speculative_num_tokens": n_spec,
        "speculative_tree_width": tree_w,
        **({"speculative_draft_window": args.speculative_draft_window}
           if getattr(args, "speculative_draft_window", None) is not None
           else {}),
        "workload": {
            "users": users,
            "max_tokens": args.max_tokens,
            "prompt_len_words": args.prompt_len,
        },
        "modes": results,
        "effective_tokens_per_target_step": eff,
        "token_identical": identity,
        "bar": bar,
        "errors_total": 0,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=["stack", "engine"], default="stack",
                    help="stack: HTTP through router+engine subprocesses "
                         "(the recorded configuration); engine: in-process")
    ap.add_argument("--model", default=None,
                    help="named model config (default: llama-1b on TPU, "
                         "tiny-llama on CPU)")
    ap.add_argument("--users", type=int, default=16)
    ap.add_argument("--rounds", type=int, default=4)
    # ~150 words ~= a 1000-token system prompt under the byte-level fallback
    # tokenizer — the reference workload's system prompt size
    # (reference benchmarks/multi-round-qa/run.sh: system prompt 1000 tok).
    ap.add_argument("--prompt-len", type=int, default=150)
    # 100-token answers: the reference workload's answer size
    # (reference benchmarks/multi-round-qa/run.sh).
    ap.add_argument("--max-tokens", type=int, default=100)
    # 8192 by default: the engine serves long-context configs without a
    # window-copy memory wall (paged decode; bucketed window for head_dim<128
    # models) — VERDICT r2 weak #2 demanded the bench stop pinning 1024.
    ap.add_argument("--max-model-len", type=int, default=8192)
    ap.add_argument("--max-num-batched-tokens", type=int, default=None,
                    help="engine --max-num-batched-tokens passthrough "
                         "(prefill chunk budget; also bounds the warmup "
                         "prefill-family t buckets — the cold/warm boot "
                         "A/B uses a small value so startup is "
                         "compile-dominated, docs/ELASTIC.md)")
    ap.add_argument("--decode-loop", default=None,
                    choices=["while", "scan"],
                    help="A/B the fused-decode loop construct")
    ap.add_argument("--attn-impl", default="auto",
                    choices=["auto", "window", "paged", "xla", "pallas"],
                    help="A/B the decode attention implementation")
    ap.add_argument("--kv-cache-dtype", default="bfloat16",
                    choices=["bfloat16", "int8"],
                    help="KV-cache storage dtype for the engines AND the "
                         "roofline's KV term (int8 halves decode KV bytes "
                         "— docs/PERF.md round 7)")
    ap.add_argument("--hbm-peak-gbps", type=float, default=None,
                    help="peak HBM GB/s per chip for the roofline "
                         "denominator (v5e 819, v5p 2765, v6e 1638; "
                         "default: $PSTPU_PEAK_HBM_GBS, else looked up by "
                         "the probed device kind — an unknown TPU kind is "
                         "an error, and a CPU run records no roofline "
                         "share). Recorded in the JSON line as "
                         "hbm_peak_gbps so perfwatch only compares "
                         "like-for-like rooflines")
    # Per-user seeded chat history (reference shape: 20k tokens — request
    # --history-tokens 20000 --max-model-len 32768; the default fits the
    # default 8192 context). Makes kv_hit_rate a measured quantity.
    ap.add_argument("--history-tokens", type=int, default=4000,
                    help="per-user pre-seeded chat-history tokens "
                         "(clamped to fit --max-model-len; 0 disables)")
    ap.add_argument("--routing-logic", default="session",
                    choices=["roundrobin", "session",
                             "cache_aware_load_balancing", "prefix-aware"],
                    help="router routing logic for the stack run (sweep "
                         "A/B: session vs cache-aware vs prefix-aware; "
                         "prefix-aware also launches a shared cache "
                         "server and wires --prefix-tokenizer/"
                         "--kv-offload-url, docs/KV_ECONOMY.md)")
    ap.add_argument("--tensor-parallel-size", type=int, default=1,
                    help="boot every engine on a tp-sharded device mesh "
                         "(docs/PERF.md round 9): the KV pool, int8 scale "
                         "sidecars, and paged-attention kernel shard the "
                         "kv-head axis over tp devices. On CPU the bench "
                         "forces a virtual 8-device platform into the "
                         "engine subprocesses; on a TPU slice the real "
                         "chips serve the same code path. The roofline "
                         "and hbm_bw_pct scale by the chip count")
    ap.add_argument("--multichip-sweep", default=None,
                    help="comma-separated chip counts (e.g. 1,2,4,8): run "
                         "the stack bench once per tp point on the same "
                         "workload and print one scaling-curve report "
                         "(tok/s + tok/s-per-chip + scaling efficiency "
                         "per point, zero-5xx bar enforced) — the "
                         "MULTICHIP_r*.json serving artifact "
                         "tools/capacity.py consumes")
    ap.add_argument("--multichip-output", default=None,
                    help="also write the --multichip-sweep report JSON "
                         "here (e.g. MULTICHIP_r06.json)")
    ap.add_argument("--num-engines", type=int, default=1,
                    help="engine subprocesses behind the router; 2-process "
                         "smoke: --model facebook/opt-125m --num-engines 2 "
                         "--routing-logic cache_aware_load_balancing")
    ap.add_argument("--num-routers", type=int, default=1,
                    help="router replicas in front of the engine fleet "
                         "(docs/ROUTER_SCALE.md): sessions spread "
                         "round-robin, replicas share breaker gossip, "
                         "and the soak's kill_router fault becomes "
                         "available at >= 2")
    ap.add_argument("--router-sweep", default=None,
                    help="comma list of router replica counts (e.g. 1,2): "
                         "run the stack bench once per point on the same "
                         "engine fleet and print the router-tier scaling "
                         "report (QPS ceiling per replica count, zero-5xx "
                         "bar) — the ROUTER_SWEEP_r*.json artifact "
                         "tools/capacity.py --router-report consumes")
    ap.add_argument("--router-sweep-output", default=None,
                    help="also write the --router-sweep report JSON here")
    ap.add_argument("--no-overlap", action="store_true",
                    help="A/B fallback: disable the two-slot prefill/"
                         "decode dispatch overlap")
    ap.add_argument("--speculative-num-tokens", type=int, default=0,
                    help="speculative decoding: draft-ahead tokens per "
                         "target step for the engines AND the roofline's "
                         "effective-tokens factor (docs/PERF.md round 8; "
                         "requires --speculative-model)")
    ap.add_argument("--speculative-model", default=None,
                    help="draft model for --speculative-num-tokens (must "
                         "share the target's vocab; the target model name "
                         "itself gives the self-draft parity shape)")
    ap.add_argument("--speculative-draft-window", type=int, default=None,
                    help="engine --speculative-draft-window passthrough "
                         "(0 = full draft context — the BENCH_r08 "
                         "self-draft evidence shape; default: engine "
                         "tuned value)")
    ap.add_argument("--speculative-adaptive", action="store_true",
                    help="per-sequence adaptive draft depth: an "
                         "acceptance EMA picks each row's gamma per "
                         "dispatch, degrading to the spec-off dispatch "
                         "when every row sits at gamma=0 "
                         "(docs/PERF.md round 10)")
    ap.add_argument("--speculative-tree-width", type=int, default=1,
                    help="token-tree verification width: top-k branching "
                         "at the first draft position, verified in one "
                         "batched target pass (1 = linear chain; "
                         "docs/PERF.md round 10)")
    ap.add_argument("--speculative-ab", action="store_true",
                    help="acceptance-limited speculative A/B: run the "
                         "SAME seeded workload through spec-off, fixed "
                         "linear-gamma, tree, and adaptive engine configs "
                         "in-process, compare effective tokens per target "
                         "step and assert token-identical outputs "
                         "(BENCH_r10 evidence shape; implies --mode "
                         "engine)")
    ap.add_argument("--speculative-ab-output", default=None,
                    help="also write the --speculative-ab report JSON "
                         "here (e.g. BENCH_r10.json)")
    ap.add_argument("--disagg", action="store_true",
                    help="prefill/decode disaggregation smoke: 1-prefill + "
                         "1-decode stack over a shared kv_offload store, "
                         "routed with --routing-logic disagg; reports "
                         "per-role TTFT/ITL and kv_handoff_* telemetry "
                         "(docs/DISAGG.md)")
    # Sustained-load SLO soak + chaos gate (benchmarks/soak.py,
    # docs/SOAK.md): minutes of multi-round QA at a QPS ladder with
    # per-class SLO attainment and mid-soak fault injection; the report is
    # recorded as BENCH_soak_r*.json and the zero-5xx/bounded-recovery
    # bars fail the run.
    ap.add_argument("--soak", action="store_true",
                    help="run the sustained-load SLO soak + chaos gate "
                         "instead of a single-shot benchmark "
                         "(docs/SOAK.md); prints the pstpu-soak-v1 JSON "
                         "report and exits nonzero if the zero-5xx or "
                         "bounded-recovery bar fails")
    ap.add_argument("--soak-qps-ladder", default="0.5,1.0",
                    help="comma-separated session-launch QPS rungs")
    ap.add_argument("--soak-rung-duration", type=float, default=45.0,
                    help="seconds of sustained traffic per ladder rung")
    ap.add_argument("--soak-fault-schedule", default=None,
                    help="declarative chaos schedule: JSON list or "
                         "@path/to/schedule.json (actions: restart_engine, "
                         "restart_kv_server, degrade_engine, heal_engine)")
    ap.add_argument("--soak-classes", default=None,
                    help="SLO classes as a JSON list or @path (default: "
                         "interactive + batch, docs/SOAK.md)")
    ap.add_argument("--soak-max-recovery", type=float, default=90.0,
                    help="bounded post-fault recovery: seconds within "
                         "which windowed attainment must return above "
                         "threshold")
    ap.add_argument("--soak-max-queue-len", type=int, default=32,
                    help="engine admission bound during the soak (shed "
                         "with 503+Retry-After beyond it)")
    ap.add_argument("--soak-require-zero-truncation", action="store_true",
                    help="fail the soak unless EVERY stream ended in "
                         "data:[DONE] — mid-stream engine kills must be "
                         "resumed, not truncated (docs/RESILIENCE.md; "
                         "pair with a kill_engine fault)")
    ap.add_argument("--soak-require-anomaly-timelines", action="store_true",
                    help="fail the soak if an SLO-missing request has no "
                         "recorded flight-recorder timeline in the "
                         "report's anomaly dump — every miss must be "
                         "diagnosable (docs/OBSERVABILITY.md)")
    ap.add_argument("--profile", type=float, default=0.0,
                    help="arm a bounded jax.profiler capture of this many "
                         "seconds on the first engine (POST "
                         "/debug/profile) right before the timed "
                         "workload; the JSON line records the perfetto "
                         "trace dir under 'profile' "
                         "(docs/OBSERVABILITY.md; 0 disables)")
    ap.add_argument("--profile-trace-dir", default=None,
                    help="trace directory for --profile (default: a "
                         "fresh pstpu-profile-* tempdir on the engine)")
    ap.add_argument("--soak-output", default=None,
                    help="write the soak report JSON here (e.g. "
                         "BENCH_soak_r01.json) in addition to stdout")
    # Elastic fast-start (docs/ELASTIC.md): the scale_out_engine /
    # scale_in_engine fault actions plus the knobs that make a joining
    # engine useful fast — a shared compile cache, router-driven prefix
    # prewarm, and slow-start ramp-in.
    ap.add_argument("--compilation-cache-dir", default=None,
                    help="shared persistent XLA compile-cache dir for "
                         "every engine subprocess (docs/ELASTIC.md): run "
                         "the bench twice on one dir for the recorded "
                         "cold-vs-warm boot A/B (engine_ready_seconds + "
                         "startup_cache_*_families in the JSON line)")
    ap.add_argument("--soak-routing-logic", default="session",
                    choices=["roundrobin", "session",
                             "cache_aware_load_balancing", "prefix-aware"],
                    help="router routing logic for the soak stack "
                         "(cache_aware/prefix-aware score load, so the "
                         "--soak-ramp-in slow-start applies to them)")
    ap.add_argument("--soak-prewarm-top-k", type=int, default=0,
                    help="router --prewarm-top-k for the soak: POST "
                         "/prewarm to a scaled-out engine before it takes "
                         "load (0 disables; docs/ELASTIC.md)")
    ap.add_argument("--soak-ramp-in", type=float, default=0.0,
                    help="router --ramp-in-seconds for the soak: "
                         "slow-start window for the joining engine")
    ap.add_argument("--soak-elastic-ab", action="store_true",
                    help="run the ladder twice (prewarm/ramp on, then "
                         "off against a fresh stack) and embed the "
                         "control's elastic measurements in the report — "
                         "the prewarmed-vs-control first-minute "
                         "kv_hit_rate A/B as one artifact")
    args = ap.parse_args()
    for attr in ("soak_fault_schedule", "soak_classes"):
        val = getattr(args, attr)
        if val and val.startswith("@"):
            with open(val[1:]) as f:
                setattr(args, attr, f.read())

    device = probe_device()
    args.backend = device["platform"]
    args.device_kind = device["kind"]
    args.model = args.model or (
        "tiny-llama" if args.backend == "cpu" else "llama-1b"
    )

    if args.soak:
        from benchmarks.soak import assert_soak_bars, run_soak

        if args.num_engines < 2:
            args.num_engines = 2   # chaos needs a peer to fail over to
        _force_virtual_devices(args, args.tensor_parallel_size)
        report = run_soak(args)
        print(json.dumps(report))
        if args.soak_output:
            with open(args.soak_output, "w") as f:
                json.dump(report, f, indent=1)
                f.write("\n")
        assert_soak_bars(
            report, args.soak_max_recovery,
            require_zero_truncation=args.soak_require_zero_truncation,
            require_anomaly_timelines=args.soak_require_anomaly_timelines,
        )
        return 0

    if args.router_sweep:
        args.mode = "stack"  # the router tier fronts a stack-shape run
        report = bench_router_sweep(args)
        print(json.dumps(report))
        if args.router_sweep_output:
            with open(args.router_sweep_output, "w") as f:
                json.dump(report, f, indent=1)
                f.write("\n")
        return 0

    if args.multichip_sweep:
        args.mode = "stack"  # the scaling curve is a stack-shape run
        report = bench_multichip_sweep(args)
        print(json.dumps(report))
        if args.multichip_output:
            with open(args.multichip_output, "w") as f:
                json.dump(report, f, indent=1)
                f.write("\n")
        return 0

    if getattr(args, "speculative_ab", False):
        args.mode = "engine"  # four in-process engines, one per spec mode
        _force_virtual_devices(args, args.tensor_parallel_size)
        report = bench_speculative_ab(args)
        print(json.dumps(report))
        if args.speculative_ab_output:
            with open(args.speculative_ab_output, "w") as f:
                json.dump(report, f, indent=1)
                f.write("\n")
        if not all(report["token_identical"].values()):
            raise RuntimeError(
                f"speculative A/B broke token identity: "
                f"{report['token_identical']} — speculation must never "
                f"change emitted tokens"
            )
        return 0

    _force_virtual_devices(args, args.tensor_parallel_size)
    if args.disagg:
        args.mode = "stack"  # disagg is a stack-shape run (JSON line parity)
        res = bench_disagg(args)
    elif args.mode == "stack":
        res = bench_stack(args)
    else:
        res = bench_engine(args)
    out = _result_line(args, res)
    print(json.dumps(out))
    return 0


def _result_line(args, res) -> dict:
    """The one-line JSON benchmark record from a mode runner's result:
    roofline accounting (per-chip honest at tp>1), kv-hit, speculative and
    multichip fields. Shared by the single-shot modes and every
    --multichip-sweep point."""
    summary = res["summary"]

    from production_stack_tpu.engine.config import EngineConfig

    dtype_bytes = {"bfloat16": 2.0, "float16": 2.0, "float32": 4.0}[
        EngineConfig().dtype
    ]
    avg_ctx = res["avg_prompt_tokens"] + args.max_tokens / 2
    spec = res.get("spec") or {}
    eff_tokens = 1.0
    if spec.get("spec_enabled"):
        # Effective emitted tokens per target-model step: every cycle
        # emits the accepted drafts plus the target's own sample. Under
        # adaptive gamma the SERVED draft depth (drafts / live cycles) is
        # the honest multiplier — the configured N overstates a
        # controller that throttled rows to shallow gammas (docs/PERF.md
        # round 10). With no depth telemetry (older engine) fall back to
        # the configured depth.
        depth = float(spec.get("spec_draft_depth", 0.0)) or float(
            args.speculative_num_tokens
        )
        eff_tokens = 1.0 + (
            spec.get("spec_acceptance_rate", 0.0) * depth
        )
    # Total chips across the deployment: tp devices per engine mesh x the
    # engine replica count (the disagg shape is a fixed 1-prefill +
    # 1-decode pair). Per-chip goodput and the chip-scaled roofline must
    # count BOTH axes or a --num-engines run overstates itself.
    tp = max(1, getattr(args, "tensor_parallel_size", 1))
    engines = 2 if getattr(args, "disagg", False) \
        else max(1, getattr(args, "num_engines", 1))
    num_chips = tp * engines
    # The denominator follows the device the run found; a CPU run has
    # none, and its roofline-derived fields are null, not a share of some
    # accelerator's peak (the byte components below need no peak).
    hbm_peak = peak_hbm_gbps(
        args.backend, getattr(args, "device_kind", args.backend),
        getattr(args, "hbm_peak_gbps", None),
    )
    comp = roofline_components(
        args.model, dtype_bytes, args.kv_cache_dtype, max(1, args.users),
        avg_ctx, peak_gbs=hbm_peak,
        tokens_per_target_step=eff_tokens, num_chips=num_chips,
    )
    roofline = comp["roofline_tok_s"]
    out = {
        "bench_schema_version": BENCH_SCHEMA_VERSION,
        "metric": res["metric"],
        "value": res["value"],
        "unit": "tok/s",
        "vs_baseline": round(res["value"] / roofline, 3)
        if roofline else None,
        "roofline_tok_s": round(roofline, 1) if roofline else None,
        "hbm_bw_pct": round(100 * res["value"] / roofline, 1)
        if roofline else None,
        "hbm_peak_gbps": hbm_peak,
        # Roofline byte components (satellite: the KV term follows the
        # KV-cache dtype; weights stay in the compute dtype).
        "kv_cache_dtype": args.kv_cache_dtype,
        "roofline_param_bytes": round(comp["param_bytes"]),
        "roofline_kv_bytes_per_token": comp["kv_bytes_per_token"],
        "roofline_kv_bytes_per_step_per_row":
            round(comp["kv_bytes_per_step_per_row"]),
        # Multi-chip serving (docs/PERF.md round 9): ONE engine's mesh
        # shape, the engine replica count, the aggregate-vs-per-chip
        # split (the scaling curve's y axes), and the roofline's chip
        # scaling already applied above.
        "mesh_shape": {"dp": 1, "sp": 1, "tp": tp},
        "num_engines": engines,
        "num_chips": num_chips,
        "tok_per_s_per_chip": round(res["value"] / num_chips, 2),
        "p50_ttft_s": round(summary["p50_ttft_s"], 4)
        if summary.get("p50_ttft_s") else None,
        "total_output_tokens": summary["total_output_tokens"],
        "finished_requests": summary.get("finished_requests", 0),
        "errors_total": summary.get("errors_total", 0),
        # BASELINE target #3 (KV-hit parity): prefix-cache hit fraction of
        # queried tokens over the timed region, under the long-history
        # multi-round workload (--history-tokens).
        "kv_hit_rate": res.get("kv_hit_rate"),
        "history_tokens_per_user": args.history_tokens,
        "backend": args.backend,
        # Speculative decoding (docs/PERF.md round 8): acceptance-rate
        # telemetry + the effective-tokens factor the roofline above used.
        "spec_enabled": int(bool(spec.get("spec_enabled", 0))),
        "speculative_num_tokens": args.speculative_num_tokens,
        "speculative_model": args.speculative_model,
        "spec_draft_tokens": int(spec.get("spec_draft_tokens", 0)),
        "spec_accepted_tokens": int(spec.get("spec_accepted_tokens", 0)),
        "spec_acceptance_rate": spec.get("spec_acceptance_rate", 0.0),
        # Round 10 companions: windowed acceptance (recent trains only),
        # the mean SERVED draft depth the adaptive controller actually
        # dispatched, tree verification node volume, and how often the
        # all-gamma=0 degrade path took the spec-off dispatch.
        "speculative_adaptive": bool(
            getattr(args, "speculative_adaptive", False)
        ),
        "speculative_tree_width": int(
            getattr(args, "speculative_tree_width", 1)
        ),
        "spec_acceptance_rate_window": spec.get(
            "spec_acceptance_rate_window", 0.0
        ),
        "spec_draft_depth": spec.get("spec_draft_depth", 0.0),
        "spec_tree_nodes": int(spec.get("spec_tree_nodes", 0)),
        "spec_gamma0_dispatches": int(
            spec.get("spec_gamma0_dispatches", 0)
        ),
        "effective_tokens_per_target_step": round(eff_tokens, 4),
    }
    if args.mode == "stack":
        out.update({
            "qps": round(summary["qps"], 3),
            "input_tok_s": round(summary["input_tokens_per_s"], 1),
            "avg_ttft_s": round(summary["avg_ttft_s"], 4),
        })
    if "engine_ready_seconds" in res:
        # Elastic fast-start A/B record (docs/ELASTIC.md): spawn ->
        # /health per engine plus the warmup compile-cache hit/miss split
        # (warm boot: hits > 0, misses == 0 for an unchanged config).
        startup = res.get("engine_startup") or []
        out.update({
            "engine_ready_seconds": res["engine_ready_seconds"],
            "compilation_cache_dir": getattr(
                args, "compilation_cache_dir", None
            ),
            "startup_cache_hit_families": sum(
                int(s.get("startup_cache_hit_families", 0))
                for s in startup
            ),
            "startup_cache_miss_families": sum(
                int(s.get("startup_cache_miss_families", 0))
                for s in startup
            ),
            "engine_startup": startup,
        })
    if "disagg" in res:
        out["disagg"] = res["disagg"]
    if "profile" in res:
        # On-demand device capture (docs/OBSERVABILITY.md): where this
        # run's perfetto trace landed (or why profiling was unavailable).
        out["profile"] = res["profile"]
    return out


if __name__ == "__main__":
    sys.exit(main())
