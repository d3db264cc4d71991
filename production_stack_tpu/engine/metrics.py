"""Engine /metrics exposition.

Emits the EXACT series names the reference router's scraper parses
(reference src/vllm_router/stats/engine_stats.py:128-155):
  vllm:num_requests_running, vllm:num_requests_waiting,
  vllm:gpu_prefix_cache_hits_total, vllm:gpu_prefix_cache_queries_total,
  vllm:gpu_cache_usage_perc  — reinterpreted as TPU **HBM** KV-pool usage.

Implemented as a prometheus_client custom Collector reading live engine
state at scrape time (no sampling thread, no drift between gauges).
"""

import time
from typing import TYPE_CHECKING, Iterable

from prometheus_client.core import (
    CounterMetricFamily,
    GaugeMetricFamily,
    HistogramMetricFamily,
)
from prometheus_client.registry import Collector

if TYPE_CHECKING:
    from production_stack_tpu.engine.engine import ServingEngine


class EngineMetricsCollector(Collector):
    def __init__(self, engine: "ServingEngine"):
        self.engine = engine

    def collect(self) -> Iterable:
        eng = self.engine
        labels = ["model_name"]
        lv = [eng.config.model_name]

        def gauge(name, doc, value):
            g = GaugeMetricFamily(name, doc, labels=labels)
            if value is not None:   # None = nothing to report: no sample
                g.add_metric(lv, value)
            return g

        def counter(name, doc, value):
            # prometheus_client appends _total to CounterMetricFamily names.
            assert name.endswith("_total")
            c = CounterMetricFamily(name[: -len("_total")], doc, labels=labels)
            c.add_metric(lv, value)
            return c

        def histogram(name, doc, h):
            # Cumulative buckets from the hand-rolled Histogram (or an
            # all-zero family when the engine lacks the attribute — fakes).
            fam = HistogramMetricFamily(name, doc, labels=labels)
            if h is None:
                fam.add_metric(lv, [("+Inf", 0)], 0.0)
                return fam
            buckets, cum = [], 0
            for bound, c in zip(h.buckets, h.counts):
                cum += c
                buckets.append((str(bound), cum))
            buckets.append(("+Inf", h.count))
            fam.add_metric(lv, buckets, h.sum)
            return fam

        sched = eng.scheduler
        bm = eng.block_manager
        yield gauge("vllm:num_requests_running",
                    "Number of requests currently decoding", sched.num_running)
        yield gauge("vllm:num_requests_waiting",
                    "Number of requests waiting for prefill", sched.num_waiting)
        yield gauge("pstpu:queue_depth",
                    "Engine backlog (running + waiting requests) — the "
                    "per-pod autoscaling signal (docs/SOAK.md)",
                    sched.num_running + sched.num_waiting)
        yield gauge("vllm:gpu_cache_usage_perc",
                    "KV pool usage fraction (TPU HBM)", bm.usage())
        yield counter("vllm:gpu_prefix_cache_hits_total",
                      "Prefix cache hit tokens", bm.prefix_hits_total)
        yield counter("vllm:gpu_prefix_cache_queries_total",
                      "Prefix cache queried tokens", bm.prefix_queries_total)
        yield counter("vllm:num_preemptions_total",
                      "Sequences preempted", sched.num_preemptions_total)
        yield counter("vllm:prompt_tokens_total",
                      "Prefilled tokens", eng.prompt_tokens_total)
        yield counter("vllm:generation_tokens_total",
                      "Generated tokens", eng.generation_tokens_total)
        yield gauge("pstpu:engine_uptime_seconds",
                    "Engine uptime", time.monotonic() - eng.start_time)
        yield gauge("pstpu:kv_offload_blocks",
                    "KV blocks resident in the host offload pool",
                    eng.offload_blocks_resident)
        # KV economy (docs/KV_ECONOMY.md): device prefix-index size (the
        # quantity the /prefix_index digest publishes) plus shared-tier
        # restore/eviction telemetry from the offload manager.
        yield gauge("pstpu:prefix_index_size",
                    "Content-addressed blocks resident in the device "
                    "prefix cache (the /prefix_index digest size)",
                    bm.prefix_index_size)
        yield counter("pstpu:kv_restore_saved_tokens_total",
                      "Prompt tokens restored from the shared KV tier "
                      "instead of recomputed (cost-model admitted)",
                      eng._offload_stat("restore_saved_tokens_total"))
        yield counter("pstpu:kv_shared_tier_hits_total",
                      "KV blocks served by the shared host/remote tiers "
                      "during prefill restores",
                      eng._offload_stat("shared_tier_hits_total"))
        yield counter("pstpu:kv_shared_tier_misses_total",
                      "Restore-candidate KV blocks the shared tiers did "
                      "not hold",
                      eng._offload_stat("shared_tier_misses_total"))
        yield counter("pstpu:kv_chain_evictions_total",
                      "Leaf-first chain evictions in the local host KV "
                      "tier (a child evicted while its parent stayed)",
                      eng._offload_stat("chain_evictions_total"))
        yield counter("pstpu:resume_restored_tokens_total",
                      "Prompt+resume tokens served from the prefix cache "
                      "or KV tiers on mid-stream resume requests instead "
                      "of recomputed (docs/RESILIENCE.md)",
                      getattr(eng, "resume_restored_tokens_total", 0))
        # Speculative decoding (docs/PERF.md round 8) — the text renderer
        # exports the same four series (PL004 keeps them aligned).
        runner = getattr(eng, "runner", None)
        yield gauge("pstpu:spec_enabled",
                    "Speculative decoding active "
                    "(--speculative-num-tokens > 0)",
                    1 if getattr(eng.config, "speculative_num_tokens", 0)
                    else 0)
        yield counter("pstpu:spec_draft_tokens_total",
                      "Draft-model token proposals made inside fused "
                      "decode dispatches",
                      getattr(runner, "spec_draft_tokens_total", 0))
        yield counter("pstpu:spec_accepted_tokens_total",
                      "Draft proposals that survived target verification "
                      "(bonus tokens not counted)",
                      getattr(runner, "spec_accepted_tokens_total", 0))
        yield gauge("pstpu:spec_acceptance_rate",
                    "Lifetime fraction of draft proposals accepted by "
                    "the target",
                    getattr(runner, "spec_acceptance_rate", 0.0))
        yield gauge("pstpu:spec_acceptance_rate_window",
                    "Draft acceptance over the last <=64 dispatch fetches "
                    "(windowed companion to the lifetime rate)",
                    getattr(runner, "spec_acceptance_rate_window", 0.0))
        yield gauge("pstpu:spec_draft_depth",
                    "Mean served draft depth per live verify cycle "
                    "(adaptive gamma controller)",
                    getattr(runner, "spec_draft_depth_mean", 0.0))
        yield counter("pstpu:spec_tree_nodes_total",
                      "Token-tree nodes verified (tree speculation)",
                      getattr(runner, "spec_tree_nodes_total", 0))
        yield gauge("pstpu:spec_acceptance_ema",
                    "Mean per-sequence acceptance EMA over live sequences "
                    "(adaptive controller)",
                    getattr(runner, "spec_acceptance_ema_mean", 0.0))
        yield counter("pstpu:spec_gamma0_dispatches_total",
                      "Decode dispatches the adaptive controller degraded "
                      "to the plain (non-speculative) scan",
                      getattr(runner, "spec_gamma0_dispatches_total", 0))
        # Elastic fast-start (docs/ELASTIC.md) — the text renderer exports
        # the same seven series (PL004 keeps them aligned).
        yield gauge("pstpu:startup_weight_load_seconds",
                    "Seconds loading model weights at startup (overlaps "
                    "compile with overlap_weight_load)",
                    getattr(runner, "startup_weight_load_seconds", 0.0))
        yield gauge("pstpu:startup_compile_seconds",
                    "Seconds in the AOT compile-only warmup prepass "
                    "(overlapped with the weight load)",
                    getattr(runner, "startup_compile_seconds", 0.0))
        yield gauge("pstpu:startup_warmup_seconds",
                    "Seconds executing warmup shape families before "
                    "serving",
                    getattr(runner, "startup_warmup_seconds", 0.0))
        yield gauge("pstpu:startup_prewarm_seconds",
                    "Seconds serving POST /prewarm hot-chain pulls from "
                    "the shared KV tier",
                    getattr(eng, "startup_prewarm_seconds", 0.0))
        yield gauge("pstpu:startup_total_seconds",
                    "Engine construction to ready-to-serve, seconds",
                    getattr(eng, "startup_total_seconds", 0.0))
        yield gauge("pstpu:startup_cache_hit_families",
                    "Warmup variants loaded from the persistent compile "
                    "cache (no recompile)",
                    getattr(runner, "startup_cache_hit_families", 0))
        yield gauge("pstpu:startup_cache_miss_families",
                    "Warmup variants that compiled from scratch (cold "
                    "cache or changed config)",
                    getattr(runner, "startup_cache_miss_families", 0))
        # Dispatch-pipeline overlap telemetry (two-slot prefill/decode
        # overlap, engine.py:_run_loop): the overlap win is observable.
        yield counter("pstpu:decode_dispatches_total",
                      "Fused decode dispatches issued",
                      eng.decode_dispatches_total)
        yield counter("pstpu:prefill_dispatches_total",
                      "Prefill chunk dispatches issued",
                      eng.prefill_dispatches_total)
        yield gauge("pstpu:dispatch_overlap_ratio",
                    "Fraction of dispatch fetches that ran with another "
                    "dispatch still outstanding (round-trip hidden)",
                    (eng.overlapped_fetches_total / eng.fetches_total
                     if eng.fetches_total else 0.0))
        yield counter("pstpu:dispatch_gap_seconds_total",
                      "Cumulative host-observed time with NO dispatch "
                      "outstanding between two dispatches (pipeline bubble)",
                      eng.dispatch_gap_seconds_total)
        # Live roofline telemetry (docs/OBSERVABILITY.md fleet pane): the
        # engine's own roofline position from the rolling dispatch window
        # — the text renderer exports the same series (PL004-aligned,
        # "fleet-perf" docs group).
        live_fn = getattr(eng, "_live_perf", None)
        live = live_fn() if callable(live_fn) else {}
        yield gauge("pstpu:live_tok_per_s",
                    "Generation throughput over the rolling dispatch "
                    "window (tokens emitted / window wall span)",
                    live.get("live_tok_per_s", 0.0))
        yield gauge("pstpu:live_hbm_bw_pct",
                    "Achieved fraction (percent) of the decode HBM "
                    "roofline for the CURRENT batch shape "
                    "(production_stack_tpu/perf/roofline.py)",
                    live.get("live_hbm_bw_pct", 0.0))
        yield gauge("pstpu:live_effective_tokens_per_target_step",
                    "Tokens emitted per target-model step over the "
                    "rolling window (the Leviathan'23 amortization "
                    "factor; >1 only when speculation pays)",
                    live.get("live_effective_tokens_per_target_step", 0.0))
        yield counter("pstpu:host_stall_seconds_total",
                      "Cumulative fetch-done to next issue-START gap with "
                      "nothing outstanding on device (the host's own "
                      "scheduling stall, compile time excluded)",
                      getattr(eng, "host_stall_seconds_total", 0.0))
        # Loop spans and decode work (engine.py:_run_loop; the text
        # renderer exports the same series — PL004-aligned, "loop" group).
        spans = getattr(eng, "loop_spans", None)
        loop_seconds = spans.counters() if spans is not None else {}
        yield counter("pstpu:loop_schedule_seconds_total",
                      "Engine-loop seconds in scheduler.schedule() "
                      "(span pstpu.schedule)",
                      loop_seconds.get("loop_schedule_seconds_total", 0.0))
        yield counter("pstpu:loop_issue_seconds_total",
                      "Engine-loop seconds issuing dispatches: "
                      "execute_async in the executor, advance_at_issue, "
                      "issue records (span pstpu.issue)",
                      loop_seconds.get("loop_issue_seconds_total", 0.0))
        yield counter("pstpu:loop_fetch_wait_seconds_total",
                      "Engine-loop seconds awaiting a dispatch's fetch: "
                      "the host blocked on the device (span pstpu.fetch)",
                      loop_seconds.get("loop_fetch_wait_seconds_total", 0.0))
        yield counter("pstpu:loop_apply_seconds_total",
                      "Engine-loop seconds applying fetched results: "
                      "fetch records, apply_results, output processing, "
                      "handoff publishes (span pstpu.apply)",
                      loop_seconds.get("loop_apply_seconds_total", 0.0))
        yield counter("pstpu:loop_idle_seconds_total",
                      "Engine-loop seconds with nothing schedulable: "
                      "waiting for work or retrying (span pstpu.idle)",
                      loop_seconds.get("loop_idle_seconds_total", 0.0))
        yield counter("pstpu:loop_other_seconds_total",
                      "Engine-loop seconds in aborts, restores, prewarms "
                      "and the yield after an apply "
                      "(span pstpu.housekeeping)",
                      loop_seconds.get("loop_other_seconds_total", 0.0))
        yield counter("pstpu:decode_steps_total",
                      "Decode-loop steps the device ran, over applied "
                      "decode dispatches",
                      getattr(eng, "decode_steps_total", 0))
        yield counter("pstpu:decode_row_steps_total",
                      "Real rows times the steps their decode dispatch "
                      "ran (padding rows are not rows)",
                      getattr(eng, "decode_row_steps_total", 0))
        yield counter("pstpu:decode_row_steps_wasted_total",
                      "Decode row-steps whose token was not delivered "
                      "(row finished earlier in the train, aborted, "
                      "preempted, or its fetch failed)",
                      getattr(eng, "decode_row_steps_wasted_total", 0))
        yield counter("pstpu:sample_dispatches_total",
                      "Prefill and decode dispatches issued (each runs "
                      "the sampler once a step)",
                      getattr(runner, "sample_dispatches_total", 0))
        yield counter("pstpu:sample_dispatches_greedy_total",
                      "Dispatches whose every row is greedy: the sampler "
                      "runs one argmax",
                      getattr(runner, "sample_dispatches_greedy_total", 0))
        yield counter("pstpu:sample_dispatches_filtered_total",
                      "Dispatches in which a sampled row has top_k or "
                      "top_p: the sampler runs its top-128 candidate "
                      "search",
                      getattr(runner, "sample_dispatches_filtered_total", 0))
        # Per-train dispatch duration histogram ({train=prefill|decode|
        # decode_spec}) — the only engine family with a second live label.
        dh = getattr(eng, "dispatch_hists", None)
        dd = HistogramMetricFamily(
            "pstpu:dispatch_duration_seconds",
            "Issue-to-fetch duration of each dispatch by train kind",
            labels=["model_name", "train"],
        )
        for train in ("prefill", "decode", "decode_spec"):
            h = getattr(dh, "hists", {}).get(train) if dh is not None \
                else None
            if h is None:
                dd.add_metric([eng.config.model_name, train],
                              [("+Inf", 0)], 0.0)
                continue
            buckets, cum = [], 0
            for bound, c in zip(h.buckets, h.counts):
                cum += c
                buckets.append((str(bound), cum))
            buckets.append(("+Inf", h.count))
            dd.add_metric([eng.config.model_name, train], buckets, h.sum)
        yield dd
        # Request-lifecycle phase histograms (docs/OBSERVABILITY.md):
        # where a request's latency went — queue wait, prefill, per-train
        # decode cadence, shared-tier restore round trips. The text
        # renderer exports the same four series (PL004 keeps them aligned).
        lc = getattr(eng, "lifecycle", None)
        yield histogram("pstpu:queue_wait_seconds",
                        "Arrival to first dispatch issue per request",
                        getattr(lc, "queue_wait", None))
        yield histogram("pstpu:prefill_seconds",
                        "First prefill issue to final prefill chunk fetch "
                        "per request",
                        getattr(lc, "prefill", None))
        yield histogram("pstpu:decode_train_seconds",
                        "Issue-to-fetch duration of each fused decode "
                        "dispatch (train)",
                        getattr(lc, "decode_train", None))
        yield histogram("pstpu:restore_round_trip_seconds",
                        "Duration of each shared-tier I/M restore round "
                        "trip that restored KV blocks",
                        getattr(lc, "restore_round_trip", None))
        # The HTTP surface's own time (server/api_server.py observes).
        hs = getattr(eng, "http_surface", None)
        yield histogram("pstpu:http_ingress_seconds",
                        "HTTP handler entry to the request's enqueue in "
                        "the scheduler (body parse, chat template, "
                        "tokenisation)",
                        getattr(hs, "ingress", None))
        yield histogram("pstpu:first_chunk_emit_seconds",
                        "First token appended in the engine loop to the "
                        "first chunk handed to the transport (the whole "
                        "body when not streaming)",
                        getattr(hs, "first_chunk_emit", None))
        # Exporter hygiene (docs/OBSERVABILITY.md): spans the OTLP queue
        # had to drop — tracing never blocks serving, but never silently.
        from production_stack_tpu.tracing import spans_dropped_total

        yield counter("pstpu:trace_spans_dropped_total",
                      "OTLP spans dropped because the exporter queue was "
                      "full",
                      spans_dropped_total())
        # Prefill/decode disaggregation telemetry — the text renderer
        # (server/metrics.py) exports the same series; keeping the two
        # renderers aligned is enforced by pstpu-lint PL004.
        role = getattr(eng.config, "role", "unified") or "unified"
        role_g = GaugeMetricFamily(
            "pstpu:disagg_role",
            "Engine disaggregation role (1 = active)",
            labels=["model_name", "role"],
        )
        role_g.add_metric([eng.config.model_name, role], 1)
        yield role_g
        # KV-cache quantization (--kv-cache-dtype): the pool's storage
        # dtype as an info-style gauge (same shape as pstpu:disagg_role)
        # and the pool bytes quantization avoided writing.
        kv_dtype = getattr(eng.config, "kv_cache_dtype", "bfloat16") \
            or "bfloat16"
        dtype_g = GaugeMetricFamily(
            "pstpu:kv_cache_dtype",
            "KV-cache storage dtype of the block pool (1 = active)",
            labels=["model_name", "kv_cache_dtype"],
        )
        dtype_g.add_metric([eng.config.model_name, kv_dtype], 1)
        yield dtype_g
        yield counter(
            "pstpu:kv_quant_bytes_saved_total",
            "KV-pool bytes the quantized cache avoided writing vs the "
            "compute dtype",
            getattr(eng.runner, "kv_quant_bytes_saved_total", 0),
        )
        # Multi-chip serving (docs/PERF.md round 9): mesh shape + per-device
        # KV-pool residency — the text renderer exports the same series.
        mesh_shape = getattr(getattr(eng, "mesh", None), "shape", {})
        yield gauge("pstpu:mesh_tp_size",
                    "Tensor-parallel degree of the serving mesh",
                    mesh_shape.get("tp", 1))
        yield gauge("pstpu:mesh_sp_size",
                    "Sequence-parallel degree of the serving mesh",
                    mesh_shape.get("sp", 1))
        yield gauge("pstpu:mesh_devices",
                    "Devices the serving mesh occupies (dp x sp x tp)",
                    getattr(getattr(eng, "mesh", None), "size", 1))
        hbm_g = GaugeMetricFamily(
            "pstpu:hbm_kv_bytes",
            "KV-pool bytes resident per mesh device (payload + scale "
            "sidecars; kv-head-sharded at tp>1)",
            labels=["model_name", "device"],
        )
        per_dev = getattr(runner, "per_device_hbm_kv_bytes", dict)()
        for dev, b in sorted(per_dev.items()):
            hbm_g.add_metric([eng.config.model_name, dev], b)
        yield hbm_g
        disagg = getattr(eng, "disagg", None)
        d = disagg.stats() if disagg is not None else {}
        yield counter("pstpu:kv_handoffs_total",
                      "Completed KV handoff transfers "
                      "(published or consumed)",
                      d.get("kv_handoffs_total", 0))
        yield counter("pstpu:kv_handoff_bytes_total",
                      "Bytes moved through the KV handoff plane",
                      d.get("kv_handoff_bytes_total", 0))
        yield counter("pstpu:kv_handoff_seconds_total",
                      "Seconds spent serializing/publishing/consuming "
                      "KV handoffs",
                      d.get("kv_handoff_seconds_total", 0.0))
        yield counter("pstpu:kv_handoff_failures_total",
                      "Failed KV handoff transfers",
                      d.get("kv_handoff_failures_total", 0))


# vLLM's bucket boundaries for the two request-latency histograms the
# reference dashboard charts (reference observability/vllm-dashboard.json:
# "Request TTFT distribution" sums vllm:time_to_first_token_seconds_bucket,
# "Request latency distribution" sums vllm:e2e_request_latency_seconds_bucket).
TTFT_BUCKETS = (
    0.001, 0.005, 0.01, 0.02, 0.04, 0.06, 0.08, 0.1, 0.25, 0.5, 0.75,
    1.0, 2.5, 5.0, 7.5, 10.0,
)
E2E_BUCKETS = (
    0.3, 0.5, 0.8, 1.0, 1.5, 2.0, 2.5, 5.0, 10.0, 15.0, 20.0, 30.0,
    40.0, 50.0, 60.0,
)


class Histogram:
    """Minimal cumulative Prometheus histogram (single label set).

    Hand-rolled like the rest of the engine exposition so the hot path
    (one observe per request event) is a bisect + three adds, with no
    registry machinery."""

    def __init__(self, buckets):
        self.buckets = tuple(buckets)
        self.counts = [0] * len(self.buckets)
        self.sum = 0.0
        self.count = 0

    def observe(self, value: float) -> None:
        import bisect

        i = bisect.bisect_left(self.buckets, value)
        if i < len(self.counts):
            self.counts[i] += 1
        self.sum += value
        self.count += 1

    def render(self, name: str, help_text: str, label: str) -> list:
        """Prometheus exposition lines; ``label`` like '{model_name="m"}'."""
        inner = label[1:-1]  # strip braces to append le=
        lines = [
            f"# HELP {name} {help_text}",
            f"# TYPE {name} histogram",
        ]
        cum = 0
        for bound, c in zip(self.buckets, self.counts):
            cum += c
            sep = "," if inner else ""
            lines.append(
                f'{name}_bucket{{{inner}{sep}le="{bound}"}} {cum}'
            )
        sep = "," if inner else ""
        lines.append(f'{name}_bucket{{{inner}{sep}le="+Inf"}} {self.count}')
        lines.append(f"{name}_sum{label} {self.sum:.6f}")
        lines.append(f"{name}_count{label} {self.count}")
        return lines


class RequestLatencyHistograms:
    """TTFT + end-to-end latency histograms maintained by the engine."""

    def __init__(self):
        self.ttft = Histogram(TTFT_BUCKETS)
        self.e2e = Histogram(E2E_BUCKETS)

    def render(self, label: str) -> list:
        return (
            self.ttft.render(
                "vllm:time_to_first_token_seconds",
                "Time to first generated token", label,
            )
            + self.e2e.render(
                "vllm:e2e_request_latency_seconds",
                "End-to-end request latency", label,
            )
        )


# Sub-second buckets for the per-dispatch phases (a decode train or a
# restore round trip is milliseconds-to-seconds, never minutes).
PHASE_BUCKETS = (
    0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
    1.0, 2.5, 5.0, 10.0,
)


class DispatchDurationHistograms:
    """Issue-to-fetch duration of every dispatch, split by train kind
    (prefill chunk / plain fused decode / speculative decode) — the
    per-train cadence view behind the pstpu:live_* gauges
    (docs/OBSERVABILITY.md fleet pane). Observed at fetch from the
    handle's issue stamp the loop already holds; pure in-memory."""

    TRAINS = ("prefill", "decode", "decode_spec")

    def __init__(self):
        self.hists = {t: Histogram(PHASE_BUCKETS) for t in self.TRAINS}

    def observe(self, train: str, value: float) -> None:
        h = self.hists.get(train)
        if h is not None:
            h.observe(value)

    def render(self, label: str) -> list:
        """One exposition family: single HELP/TYPE header, one bucket
        series per train label value."""
        lines = [
            "# HELP pstpu:dispatch_duration_seconds Issue-to-fetch "
            "duration of each dispatch by train kind",
            "# TYPE pstpu:dispatch_duration_seconds histogram",
        ]
        inner = label[1:-1]
        sep = "," if inner else ""
        for train in self.TRAINS:
            tl = f'{{{inner}{sep}train="{train}"}}'
            # Headers dropped: the family emits ONE header pair above.
            lines.extend(self.hists[train].render(
                "pstpu:dispatch_duration_seconds", "", tl,
            )[2:])
        return lines


class HttpSurfaceHistograms:
    """The engine's own HTTP surface, measured inside it (the router's
    relay and the wire are not in these): ``ingress`` is handler entry to
    the Sequence's enqueue (body parse, chat template, tokenisation);
    ``first_chunk_emit`` is the first token's append in the engine loop to
    the first SSE chunk handed to the transport — for a non-streaming
    request to the whole body, which then contains the decode."""

    def __init__(self):
        self.ingress = Histogram(PHASE_BUCKETS)
        self.first_chunk_emit = Histogram(PHASE_BUCKETS)

    def render(self, label: str) -> list:
        return (
            self.ingress.render(
                "pstpu:http_ingress_seconds",
                "HTTP handler entry to the request's enqueue in the "
                "scheduler (body parse, chat template, tokenisation)",
                label,
            )
            + self.first_chunk_emit.render(
                "pstpu:first_chunk_emit_seconds",
                "First token appended in the engine loop to the first "
                "chunk handed to the transport (the whole body when not "
                "streaming)", label,
            )
        )


class LifecycleHistograms:
    """Per-phase request-lifecycle latency histograms
    (docs/OBSERVABILITY.md): queue wait (arrival -> first issue), prefill
    (first issue -> final chunk fetch), per-train decode cadence
    (issue -> fetch of each fused decode dispatch), and shared-tier
    restore round trips. Observed from the engine loop's dispatch points —
    the same anchor events the flight recorder records."""

    def __init__(self):
        self.queue_wait = Histogram(TTFT_BUCKETS)
        self.prefill = Histogram(TTFT_BUCKETS)
        self.decode_train = Histogram(PHASE_BUCKETS)
        self.restore_round_trip = Histogram(PHASE_BUCKETS)

    def render(self, label: str) -> list:
        return (
            self.queue_wait.render(
                "pstpu:queue_wait_seconds",
                "Arrival to first dispatch issue per request", label,
            )
            + self.prefill.render(
                "pstpu:prefill_seconds",
                "First prefill issue to final prefill chunk fetch per "
                "request", label,
            )
            + self.decode_train.render(
                "pstpu:decode_train_seconds",
                "Issue-to-fetch duration of each fused decode dispatch "
                "(train)", label,
            )
            + self.restore_round_trip.render(
                "pstpu:restore_round_trip_seconds",
                "Duration of each shared-tier I/M restore round trip that "
                "restored KV blocks", label,
            )
        )
