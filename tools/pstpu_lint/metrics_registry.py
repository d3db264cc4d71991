"""Canonical registry of every Prometheus series the stack exports.

This is the single source of truth the PL004 metrics-drift rule checks the
code against, and the input ``tools.pstpu_lint.gen_docs`` renders the docs
metrics tables from. Two exporter surfaces:

  * ``engine`` — the engine pod's /metrics renderer
                 (production_stack_tpu/server/metrics.py, plus the
                 histogram names in engine/metrics.py it renders);
  * ``router`` — the router's prometheus_client module registry
                 (production_stack_tpu/router/metrics.py).

Naming convention: ``pstpu:`` for series this stack introduces, ``router_``
for router data-plane outcomes, ``vllm:`` for the scraper/dashboard
compatibility contract (the reference Grafana dashboard and the router's
EngineStatsScraper parse these exact names — do NOT rename them).

PL004 enforces that this file, the renderers, and the docs tables never
drift from each other. To add a series: emit it in the renderer, add a
``Series`` entry here, then run ``python -m tools.pstpu_lint.gen_docs`` to
refresh the docs tables.
"""

from dataclasses import dataclass, field
from typing import Dict, Tuple

ALLOWED_PREFIXES = ("pstpu:", "router_", "vllm:")

ENGINE = "engine"
ROUTER = "router"


@dataclass(frozen=True)
class Series:
    name: str
    kind: str                       # gauge | counter | histogram
    labels: Tuple[str, ...]         # label names on the engine surface
    surfaces: Tuple[str, ...]       # which exporters render it
    docs: Tuple[str, ...]           # docs table groups (gen_docs.TABLES)
    doc: str                        # one-line meaning for the docs tables
    # Router re-exports per-engine series under its own label set (the
    # scraper relabels by backend); only set for the "router" surface.
    router_labels: Tuple[str, ...] = field(default=())

    def labels_for(self, surface: str) -> Tuple[str, ...]:
        return self.router_labels if surface == ROUTER else self.labels


REGISTRY: Tuple[Series, ...] = (
    # ------------------------------------------------ engine: vllm compat
    Series("vllm:num_requests_running", "gauge", ("model_name",),
           (ENGINE,), ("catalogue",),
           "Requests currently decoding"),
    Series("vllm:num_requests_waiting", "gauge", ("model_name",),
           (ENGINE,), ("catalogue",),
           "Requests waiting for prefill"),
    Series("vllm:gpu_cache_usage_perc", "gauge", ("model_name",),
           (ENGINE,), ("catalogue",),
           "KV-pool usage fraction (TPU HBM)"),
    Series("vllm:gpu_prefix_cache_hits_total", "counter", ("model_name",),
           (ENGINE,), ("catalogue",),
           "Prefix-cache hit tokens"),
    Series("vllm:gpu_prefix_cache_queries_total", "counter", ("model_name",),
           (ENGINE,), ("catalogue",),
           "Prefix-cache queried tokens"),
    Series("vllm:num_preemptions_total", "counter", ("model_name",),
           (ENGINE,), ("catalogue",),
           "Sequences preempted"),
    Series("vllm:prompt_tokens_total", "counter", ("model_name",),
           (ENGINE,), ("catalogue",),
           "Prefilled tokens"),
    Series("vllm:generation_tokens_total", "counter", ("model_name",),
           (ENGINE,), ("catalogue",),
           "Generated tokens"),
    Series("vllm:time_to_first_token_seconds", "histogram", ("model_name",),
           (ENGINE,), ("catalogue",),
           "TTFT distribution (vLLM bucket boundaries)"),
    Series("vllm:e2e_request_latency_seconds", "histogram", ("model_name",),
           (ENGINE,), ("catalogue",),
           "End-to-end request latency distribution"),
    # ------------------------------------------------ engine: pstpu series
    Series("pstpu:engine_uptime_seconds", "gauge", ("model_name",),
           (ENGINE,), ("catalogue",),
           "Engine uptime"),
    Series("pstpu:kv_offload_blocks", "gauge", ("model_name",),
           (ENGINE,), ("catalogue",),
           "KV blocks resident in the host offload pool"),
    Series("pstpu:queue_depth", "gauge", ("model_name",),
           (ENGINE,), ("catalogue", "autoscaling"),
           "Engine backlog (running + waiting requests) — the per-pod "
           "HPA metric"),
    Series("pstpu:decode_dispatches_total", "counter", ("model_name",),
           (ENGINE,), ("catalogue", "dispatch"),
           "Fused decode dispatches issued"),
    Series("pstpu:prefill_dispatches_total", "counter", ("model_name",),
           (ENGINE,), ("catalogue", "dispatch"),
           "Prefill chunk dispatches issued"),
    Series("pstpu:dispatch_overlap_ratio", "gauge", ("model_name",),
           (ENGINE,), ("catalogue", "dispatch"),
           "Fraction of dispatch fetches with another dispatch outstanding"),
    Series("pstpu:dispatch_gap_seconds_total", "counter", ("model_name",),
           (ENGINE,), ("catalogue", "dispatch"),
           "Host-observed seconds with no dispatch outstanding "
           "(pipeline bubble)"),
    Series("pstpu:kv_cache_dtype", "gauge", ("model_name", "kv_cache_dtype"),
           (ENGINE,), ("catalogue", "dispatch"),
           "KV-cache storage dtype of the block pool (1 = active)"),
    Series("pstpu:kv_quant_bytes_saved_total", "counter", ("model_name",),
           (ENGINE,), ("catalogue", "dispatch"),
           "KV-pool bytes the quantized cache avoided writing vs the "
           "compute dtype"),
    # ------------------------------------------- engine: KV economy
    Series("pstpu:prefix_index_size", "gauge", ("model_name",),
           (ENGINE,), ("catalogue", "kv-economy"),
           "Content-addressed blocks resident in the device prefix cache "
           "(the /prefix_index digest size)"),
    # ---------------------------------- engine: recurrent-state slots
    Series("pstpu:state_slots_total", "gauge", ("model_name",),
           (ENGINE,), ("catalogue", "lifecycle"),
           "Recurrent-state slots the block manager hands out, one a "
           "sequence (0: a K/V-only model)"),
    Series("pstpu:state_slots_in_use", "gauge", ("model_name",),
           (ENGINE,), ("catalogue", "lifecycle"),
           "Recurrent-state slots held by admitted sequences"),
    Series("pstpu:state_slot_allocs_total", "counter", ("model_name",),
           (ENGINE,), ("catalogue", "lifecycle"),
           "Recurrent-state slots handed out (a sequence takes one with "
           "its blocks)"),
    Series("pstpu:state_slot_waits_total", "counter", ("model_name",),
           (ENGINE,), ("catalogue", "lifecycle"),
           "Admissions put off because every recurrent-state slot was "
           "held"),
    # ------------------------------------------ engine: sparse experts
    Series("pstpu:moe_assignments_total", "counter", ("model_name",),
           (ENGINE,), ("catalogue", "lifecycle"),
           "Token-expert pairs the routed experts computed, decode and "
           "prefill (0: a model without experts)"),
    Series("pstpu:moe_expert_load_max_total", "counter", ("model_name",),
           (ENGINE,), ("catalogue", "lifecycle"),
           "Tokens of the busiest expert, summed over sparse-layer calls "
           "(times the experts over the pairs: max/mean imbalance)"),
    Series("pstpu:moe_experts_touched_total", "counter", ("model_name",),
           (ENGINE,), ("catalogue", "lifecycle"),
           "Distinct experts a DECODE sparse-layer call gave a token, "
           "summed over the calls (the expert matrices a step reads)"),
    Series("pstpu:moe_layer_calls_total", "counter", ("model_name",),
           (ENGINE,), ("catalogue", "lifecycle"),
           "Sparse-layer calls of decode steps (steps run times sparse "
           "layers)"),
    Series("pstpu:moe_prefill_experts_touched_total", "counter",
           ("model_name",), (ENGINE,), ("catalogue", "lifecycle"),
           "Distinct experts a PREFILL sparse-layer call gave a token, "
           "summed over the calls"),
    Series("pstpu:moe_prefill_layer_calls_total", "counter",
           ("model_name",), (ENGINE,), ("catalogue", "lifecycle"),
           "Sparse-layer calls of prefill chunks"),
    Series("pstpu:moe_assignments_elsewhere_total", "counter",
           ("model_name",), (ENGINE,), ("catalogue", "lifecycle"),
           "Token-expert pairs the router gave to experts another chip of "
           "the expert-parallel deployment holds (`ep_size` > 1: the chip "
           "holds a share of every sparse layer's experts, "
           "`ops/moe.py:expert_ffn(here=)`): neither computed nor counted "
           "among `pstpu:moe_assignments_total` here; 0 where every "
           "expert is here"),
    Series("pstpu:index_keys_visible_total", "counter",
           ("model_name",), (ENGINE,), ("catalogue", "lifecycle"),
           "Keys the full layers' DECODE queries could see (a model whose "
           "full layers attend what a learned indexer selects, "
           "`models/dots3_note.py`): their contexts, a layer and a "
           "row-step"),
    Series("pstpu:index_keys_selected_total", "counter",
           ("model_name",), (ENGINE,), ("catalogue", "lifecycle"),
           "Latent rows those queries' indexers selected and the step "
           "read (min(context, `index_topk`) a layer and a row-step); over "
           "`pstpu:index_keys_visible_total` it is the share of its "
           "context a full layer's decode step reads"),
    Series("pstpu:index_prefill_keys_visible_total", "counter",
           ("model_name",), (ENGINE,), ("catalogue", "lifecycle"),
           "Keys the full layers' PREFILL queries could see"),
    Series("pstpu:index_prefill_keys_selected_total", "counter",
           ("model_name",), (ENGINE,), ("catalogue", "lifecycle"),
           "Keys those queries' indexers selected (a chunk scores densely "
           "under the selection's mask)"),
    Series("pstpu:prefix_hit_tokens_unserved_total", "counter",
           ("model_name",), (ENGINE,), ("catalogue", "lifecycle"),
           "Prompt tokens whose K/V the prefix index held but that were "
           "prefilled again, because nothing keeps the recurrent state "
           "after them"),
    Series("pstpu:kv_restore_saved_tokens_total", "counter", ("model_name",),
           (ENGINE,), ("catalogue", "kv-economy"),
           "Prompt tokens restored from the shared KV tier instead of "
           "recomputed (cost-model admitted)"),
    Series("pstpu:kv_shared_tier_hits_total", "counter", ("model_name",),
           (ENGINE,), ("catalogue", "kv-economy"),
           "KV blocks served by the shared host/remote tiers during "
           "prefill restores"),
    Series("pstpu:kv_shared_tier_misses_total", "counter", ("model_name",),
           (ENGINE,), ("catalogue", "kv-economy"),
           "Restore-candidate KV blocks the shared tiers did not hold"),
    Series("pstpu:kv_chain_evictions_total", "counter", ("model_name",),
           (ENGINE,), ("catalogue", "kv-economy"),
           "Leaf-first chain evictions in the local host KV tier"),
    # --------------------------------------------- engine: multichip
    Series("pstpu:mesh_tp_size", "gauge", ("model_name",),
           (ENGINE,), ("catalogue", "multichip"),
           "Tensor-parallel degree of the serving mesh"),
    Series("pstpu:mesh_sp_size", "gauge", ("model_name",),
           (ENGINE,), ("catalogue", "multichip"),
           "Sequence-parallel degree of the serving mesh"),
    Series("pstpu:mesh_devices", "gauge", ("model_name",),
           (ENGINE,), ("catalogue", "multichip"),
           "Devices the serving mesh occupies (dp x sp x tp)"),
    Series("pstpu:hbm_resident_bytes", "gauge",
           ("model_name", "holder", "device"),
           (ENGINE,), ("catalogue", "multichip", "loop"),
           "Bytes resident per mesh device by holder, from the memory "
           "ledger built when `start()` ends: `weights`, `kv` (the pool's "
           "payload + scale sidecars; kv-head-sharded at tp>1), `state`, "
           "`spec`, `lora`, and `other` = the allocator's bytes in use "
           "beyond the named holders (`GET /debug/memory` lists its "
           "largest arrays)"),
    Series("pstpu:hbm_bytes_in_use", "gauge", ("model_name",),
           (ENGINE,), ("catalogue", "loop"),
           "Allocator bytes in use on the fullest mesh device at this "
           "scrape (`memory_stats()`; 0 where the backend reports none)"),
    Series("pstpu:hbm_peak_bytes", "gauge", ("model_name",),
           (ENGINE,), ("catalogue", "loop"),
           "The allocator's high-water mark of bytes in use since process "
           "start on that device (loading's transients included; a "
           "program's temporaries are in `pstpu:hbm_reserved_bytes`)"),
    Series("pstpu:hbm_limit_bytes", "gauge", ("model_name",),
           (ENGINE,), ("catalogue", "loop"),
           "Bytes the allocator may hand out on that device; the limit "
           "less the peak is what is left before an allocation fails"),
    Series("pstpu:hbm_reserved_bytes", "gauge", ("model_name",),
           (ENGINE,), ("catalogue", "loop"),
           "Bytes the runtime holds on that device OUTSIDE bytes in use "
           "and its peak for the programs' temporaries "
           "(`memory_stats()['bytes_reserved']`): on a TPU one scratch "
           "region the size of the largest program's temporaries so far, "
           "shared by the programs as they run one at a time. In use + "
           "reserved against the limit is what an allocation has left"),
    Series("pstpu:hbm_peak_rises_total", "counter",
           ("model_name", "phase"), (ENGINE,), ("catalogue", "loop"),
           "Reads after a dispatch's enqueue or sync that found the "
           "allocator's peak higher than the last read, by `phase` "
           "(`warmup` / `serving`); each is one event of `GET "
           "/debug/memory` and one log line. `serving` staying 0 is what "
           "warm-up is for: alert on its increase"),
    Series("pstpu:hbm_peak_rise_bytes_total", "counter",
           ("model_name", "phase"), (ENGINE,), ("catalogue", "loop"),
           "Bytes the allocator's peak rose by over those reads, by "
           "`phase`: its delta over a window is how far the peak rose "
           "under that window's traffic"),
    # --------------------------------------------- engine: speculative
    Series("pstpu:spec_enabled", "gauge", ("model_name",),
           (ENGINE,), ("catalogue", "speculative"),
           "Speculative decoding active (--speculative-num-tokens > 0)"),
    Series("pstpu:spec_draft_tokens_total", "counter", ("model_name",),
           (ENGINE,), ("catalogue", "speculative"),
           "Draft-model token proposals made inside fused decode "
           "dispatches"),
    Series("pstpu:spec_accepted_tokens_total", "counter", ("model_name",),
           (ENGINE,), ("catalogue", "speculative"),
           "Draft proposals that survived target verification (bonus "
           "tokens not counted)"),
    Series("pstpu:spec_acceptance_rate", "gauge", ("model_name",),
           (ENGINE,), ("catalogue", "speculative"),
           "Lifetime fraction of draft proposals accepted by the target"),
    Series("pstpu:spec_acceptance_rate_window", "gauge", ("model_name",),
           (ENGINE,), ("catalogue", "speculative"),
           "Draft acceptance over the last <=64 dispatch fetches "
           "(windowed companion to the lifetime rate)"),
    Series("pstpu:spec_draft_depth", "gauge", ("model_name",),
           (ENGINE,), ("catalogue", "speculative"),
           "Mean served draft depth per live verify cycle (adaptive "
           "gamma controller)"),
    Series("pstpu:spec_tree_nodes_total", "counter", ("model_name",),
           (ENGINE,), ("catalogue", "speculative"),
           "Token-tree nodes verified (tree speculation)"),
    Series("pstpu:spec_acceptance_ema", "gauge", ("model_name",),
           (ENGINE,), ("catalogue", "speculative"),
           "Mean per-sequence acceptance EMA over live sequences "
           "(adaptive controller)"),
    Series("pstpu:spec_gamma0_dispatches_total", "counter", ("model_name",),
           (ENGINE,), ("catalogue", "speculative"),
           "Decode dispatches the adaptive controller degraded to the "
           "plain (non-speculative) scan"),
    # --------------------------------------------- engine: elastic fast-start
    Series("pstpu:startup_weight_load_seconds", "gauge", ("model_name",),
           (ENGINE,), ("catalogue", "elastic"),
           "Seconds loading model weights at startup (overlaps compile "
           "with overlap_weight_load)"),
    Series("pstpu:startup_compile_seconds", "gauge", ("model_name",),
           (ENGINE,), ("catalogue", "elastic"),
           "Seconds in the AOT compile-only warmup prepass (overlapped "
           "with the weight load)"),
    Series("pstpu:startup_warmup_seconds", "gauge", ("model_name",),
           (ENGINE,), ("catalogue", "elastic"),
           "Seconds executing warmup shape families before serving"),
    Series("pstpu:startup_prewarm_seconds", "gauge", ("model_name",),
           (ENGINE,), ("catalogue", "elastic"),
           "Seconds serving POST /prewarm hot-chain pulls from the shared "
           "KV tier"),
    Series("pstpu:startup_total_seconds", "gauge", ("model_name",),
           (ENGINE,), ("catalogue", "elastic"),
           "Engine construction to ready-to-serve, seconds"),
    Series("pstpu:startup_cache_hit_families", "gauge", ("model_name",),
           (ENGINE,), ("catalogue", "elastic"),
           "Warmup variants loaded from the persistent compile cache "
           "(no recompile)"),
    Series("pstpu:startup_cache_miss_families", "gauge", ("model_name",),
           (ENGINE,), ("catalogue", "elastic"),
           "Warmup variants that compiled from scratch (cold cache or "
           "changed config)"),
    Series("pstpu:startup_loaded_families", "gauge", ("model_name",),
           (ENGINE,), ("catalogue", "elastic"),
           "Warmup variants loaded from the runner's stored executables "
           "and not traced (each also a hit)"),
    # ------------------------------------------ engine: request lifecycle
    # (docs/OBSERVABILITY.md): per-phase latency split — where a request's
    # TTFT went — plus tracing exporter hygiene.
    Series("pstpu:queue_wait_seconds", "histogram", ("model_name",),
           (ENGINE,), ("catalogue", "lifecycle"),
           "Arrival to first dispatch issue per request (queue wait)"),
    Series("pstpu:prefill_seconds", "histogram", ("model_name",),
           (ENGINE,), ("catalogue", "lifecycle"),
           "First prefill issue to final prefill chunk fetch per request"),
    Series("pstpu:decode_train_seconds", "histogram", ("model_name",),
           (ENGINE,), ("catalogue", "lifecycle"),
           "Issue-to-fetch duration of each fused decode dispatch (train)"),
    Series("pstpu:restore_round_trip_seconds", "histogram", ("model_name",),
           (ENGINE,), ("catalogue", "lifecycle"),
           "Duration of each shared-tier I/M restore round trip that "
           "restored KV blocks"),
    Series("pstpu:trace_spans_dropped_total", "counter", ("model_name",),
           (ENGINE,), ("catalogue", "lifecycle"),
           "OTLP spans dropped because the exporter queue was full"),
    # --------------------------------------------- engine: mid-stream resume
    Series("pstpu:resume_restored_tokens_total", "counter", ("model_name",),
           (ENGINE,), ("catalogue", "resume"),
           "Prompt+resume tokens served from the prefix cache or KV tiers "
           "on mid-stream resume requests instead of recomputed"),
    Series("pstpu:disagg_role", "gauge", ("model_name", "role"),
           (ENGINE,), ("catalogue", "disagg"),
           "Engine disaggregation role (1 = active)"),
    Series("pstpu:kv_handoffs_total", "counter", ("model_name",),
           (ENGINE,), ("catalogue", "disagg"),
           "Completed KV handoff transfers (published or consumed)"),
    Series("pstpu:kv_handoff_bytes_total", "counter", ("model_name",),
           (ENGINE,), ("catalogue", "disagg"),
           "Bytes moved through the KV handoff plane"),
    Series("pstpu:kv_handoff_seconds_total", "counter", ("model_name",),
           (ENGINE,), ("catalogue", "disagg"),
           "Seconds serializing/publishing/consuming KV handoffs"),
    Series("pstpu:kv_handoff_failures_total", "counter", ("model_name",),
           (ENGINE,), ("catalogue", "disagg"),
           "Failed KV handoff transfers"),
    # --------------------------------------------- router: vllm re-exports
    Series("vllm:num_requests_running", "gauge", ("model_name",),
           (ROUTER,), ("catalogue",),
           "Running requests per engine (router view)",
           router_labels=("server",)),
    Series("vllm:num_requests_waiting", "gauge", ("model_name",),
           (ROUTER,), ("catalogue",),
           "Waiting requests per engine (router view)",
           router_labels=("server",)),
    Series("vllm:gpu_cache_usage_perc", "gauge", ("model_name",),
           (ROUTER,), ("catalogue",),
           "KV-pool usage per engine (router view)",
           router_labels=("server",)),
    Series("vllm:current_qps", "gauge", (), (ROUTER,), ("catalogue",),
           "Router-observed QPS per engine", router_labels=("server",)),
    Series("vllm:avg_decoding_length", "gauge", (), (ROUTER,), ("catalogue",),
           "Average decoding length per engine", router_labels=("server",)),
    Series("vllm:num_prefill_requests", "gauge", (), (ROUTER,),
           ("catalogue",),
           "In-prefill requests per engine", router_labels=("server",)),
    Series("vllm:num_decoding_requests", "gauge", (), (ROUTER,),
           ("catalogue",),
           "In-decode requests per engine", router_labels=("server",)),
    Series("vllm:healthy_pods_total", "gauge", (), (ROUTER,), ("catalogue",),
           "Healthy engine pods", router_labels=("server",)),
    Series("vllm:avg_latency", "gauge", (), (ROUTER,), ("catalogue",),
           "Average end-to-end latency per engine",
           router_labels=("server",)),
    Series("vllm:avg_itl", "gauge", (), (ROUTER,), ("catalogue",),
           "Average inter-token latency per engine",
           router_labels=("server",)),
    Series("vllm:num_requests_swapped", "gauge", (), (ROUTER,),
           ("catalogue",),
           "Swapped-out requests per engine", router_labels=("server",)),
    Series("vllm:gpu_prefix_cache_hit_rate", "gauge", (), (ROUTER,),
           ("catalogue",),
           "Per-interval prefix-cache hit rate per engine",
           router_labels=("server",)),
    Series("vllm:router_queueing_delay_seconds", "gauge", (), (ROUTER,),
           ("catalogue",),
           "Router-side queueing delay (route decision to backend connect)",
           router_labels=("server",)),
    Series("vllm:router_ttft_seconds", "histogram", (), (ROUTER,),
           ("catalogue",),
           "Router-observed TTFT distribution", router_labels=("server",)),
    Series("vllm:router_e2e_latency_seconds", "histogram", (), (ROUTER,),
           ("catalogue",),
           "Router-observed end-to-end latency distribution",
           router_labels=("server",)),
    Series("vllm:avg_prefill_length", "gauge", (), (ROUTER,), ("catalogue",),
           "Average prompt length per engine", router_labels=("server",)),
    # ------------------------------------------------ router: data plane
    Series("router_retries_total", "counter", (), (ROUTER,),
           ("catalogue", "resilience"),
           "Pre-stream backend failures that triggered a retry",
           router_labels=("server",)),
    Series("router_failovers_total", "counter", (), (ROUTER,),
           ("catalogue", "resilience"),
           "Retries that moved the request away from this backend",
           router_labels=("server",)),
    Series("router_circuit_state", "gauge", (), (ROUTER,),
           ("catalogue", "resilience"),
           "Circuit breaker state (0 closed / 1 open / 2 half-open); "
           "router identifies the observing replica",
           router_labels=("server", "router")),
    Series("router_deadline_exceeded_total", "counter", (), (ROUTER,),
           ("catalogue", "resilience"),
           "Deadline aborts (kind: ttft or total)",
           router_labels=("server", "kind")),
    # ------------------------------------------- router: mid-stream resume
    Series("router_midstream_resumes_total", "counter", (), (ROUTER,),
           ("catalogue", "resume"),
           "Mid-stream backend failures the router tried to resume on "
           "another backend (outcome: resumed = continuation spliced, "
           "failed = no backend could attach, peer = client reconnected "
           "here after losing another router replica)",
           router_labels=("outcome",)),
    Series("router_truncations_total", "counter", (), (ROUTER,),
           ("catalogue", "resume"),
           "Client streams that ended without data: [DONE] (mid-stream "
           "failure not resumed, resume budget exhausted, or mid-stream "
           "deadline)",
           router_labels=()),
    Series("router_trace_spans_dropped_total", "counter", (), (ROUTER,),
           ("catalogue", "lifecycle"),
           "OTLP spans the router's exporter queue had to drop",
           router_labels=()),
    # ------------------------------------------------ router: autoscaling
    Series("router_queue_depth", "gauge", (), (ROUTER,),
           ("catalogue", "autoscaling"),
           "Engine-reported running+waiting requests per backend "
           "(queue-depth scale-up signal)",
           router_labels=("server",)),
    Series("router_kv_pressure", "gauge", (), (ROUTER,),
           ("catalogue", "autoscaling"),
           "KV-pool usage fraction per backend (HBM pressure)",
           router_labels=("server",)),
    Series("router_pool_utilization", "gauge", (), (ROUTER,),
           ("catalogue", "autoscaling"),
           "Mean in-flight depth per engine in each disagg role pool",
           router_labels=("role",)),
    Series("router_slo_attainment", "gauge", (), (ROUTER,),
           ("catalogue", "autoscaling"),
           "Rolling-window fraction of x-slo-class requests meeting their "
           "soft TTFT target",
           router_labels=("slo_class",)),
    # ------------------------------------------------ router: KV economy
    Series("router_backend_kv_hit_rate", "gauge", (), (ROUTER,),
           ("catalogue", "kv-economy"),
           "Per-interval prefix-cache hit rate per backend (scrape plane)",
           router_labels=("server",)),
    Series("router_prefix_index_entries", "gauge", (), (ROUTER,),
           ("catalogue", "kv-economy"),
           "Entries in the backend's last scraped /prefix_index digest",
           router_labels=("server",)),
    Series("router_disagg_handoffs_total", "counter", (), (ROUTER,),
           ("catalogue", "disagg"),
           "Prefill->decode handoffs completed through the two-hop flow",
           router_labels=()),
    Series("router_disagg_fallbacks_total", "counter", (), (ROUTER,),
           ("catalogue", "disagg"),
           "Disagg-routed requests degraded to unified serving",
           router_labels=("reason",)),
    # -------------------------------------- engine: live roofline telemetry
    # (docs/OBSERVABILITY.md fleet pane): the engine reports its OWN
    # roofline position continuously from the rolling dispatch window —
    # the same arithmetic bench.py's JSON line uses (shared
    # production_stack_tpu/perf/roofline.py).
    Series("pstpu:live_tok_per_s", "gauge", ("model_name",),
           (ENGINE,), ("catalogue", "fleet-perf"),
           "Generation throughput over the rolling dispatch window"),
    Series("pstpu:live_hbm_bw_pct", "gauge", ("model_name",),
           (ENGINE,), ("catalogue", "fleet-perf"),
           "Achieved fraction (percent) of the decode HBM roofline for "
           "the current batch shape"),
    Series("pstpu:live_effective_tokens_per_target_step", "gauge",
           ("model_name",), (ENGINE,), ("catalogue", "fleet-perf"),
           "Tokens emitted per target-model step over the rolling window "
           "(the Leviathan'23 amortization factor; >1 only when "
           "speculation pays)"),
    Series("pstpu:host_stall_seconds_total", "counter", ("model_name",),
           (ENGINE,), ("catalogue", "fleet-perf"),
           "Fetch-done to next issue-start gap with nothing outstanding "
           "on device (host scheduling stall, compile time excluded)"),
    Series("pstpu:dispatch_duration_seconds", "histogram",
           ("model_name", "train"), (ENGINE,),
           ("catalogue", "fleet-perf"),
           "Issue-to-fetch duration of each dispatch by train kind "
           "(prefill | decode | decode_spec)"),
    # ------------------------------------------------ engine: loop spans
    # The six phases tile the engine loop's wall time (their deltas over a
    # window sum to the window); the decode counts are taken at apply.
    Series("pstpu:loop_schedule_seconds_total", "counter", ("model_name",),
           (ENGINE,), ("catalogue", "loop"),
           "Engine-loop seconds in `scheduler.schedule()` (span "
           "`pstpu.schedule`)"),
    Series("pstpu:loop_issue_seconds_total", "counter", ("model_name",),
           (ENGINE,), ("catalogue", "loop"),
           "Engine-loop seconds issuing dispatches: `execute_async` in "
           "the executor, `advance_at_issue`, issue records (span "
           "`pstpu.issue`)"),
    Series("pstpu:loop_fetch_wait_seconds_total", "counter",
           ("model_name",), (ENGINE,), ("catalogue", "loop"),
           "Engine-loop seconds awaiting a dispatch's fetch: the host "
           "blocked on the device (span `pstpu.fetch`)"),
    Series("pstpu:loop_apply_seconds_total", "counter", ("model_name",),
           (ENGINE,), ("catalogue", "loop"),
           "Engine-loop seconds applying fetched results: fetch records, "
           "`apply_results`, output processing, handoff publishes (span "
           "`pstpu.apply`)"),
    Series("pstpu:loop_idle_seconds_total", "counter", ("model_name",),
           (ENGINE,), ("catalogue", "loop"),
           "Engine-loop seconds with nothing schedulable: waiting for "
           "work or retrying (span `pstpu.idle`)"),
    Series("pstpu:loop_other_seconds_total", "counter", ("model_name",),
           (ENGINE,), ("catalogue", "loop"),
           "Engine-loop seconds in aborts, restores, prewarms and the "
           "yield after an apply (span `pstpu.housekeeping`)"),
    Series("pstpu:decode_steps_total", "counter", ("model_name",),
           (ENGINE,), ("catalogue", "loop"),
           "Decode-loop steps the device ran, over applied decode "
           "dispatches (the while loop stops at the largest per-row "
           "budget; draft/verify cycles allowed under speculation)"),
    Series("pstpu:decode_row_steps_total", "counter", ("model_name",),
           (ENGINE,), ("catalogue", "loop"),
           "Real rows times the steps their decode dispatch ran (padding "
           "rows of the shape bucket are not rows)"),
    Series("pstpu:decode_bucket_row_steps_total", "counter",
           ("model_name",), (ENGINE,), ("catalogue", "loop"),
           "Rows of the decode program's shape bucket (padding rows "
           "included) times the steps the dispatch ran; (row-steps less "
           "wasted) over this is the share of a bucket's rows that take a "
           "token in a step"),
    Series("pstpu:decode_row_steps_wasted_total", "counter",
           ("model_name",), (ENGINE,), ("catalogue", "loop"),
           "Decode row-steps whose token was not delivered: the row hit "
           "EOS / max_tokens / a stop string earlier in the train, was "
           "aborted or preempted, or its fetch failed; row-steps less "
           "wasted is the tokens decode delivered"),
    Series("pstpu:decode_steps_empty_total", "counter",
           ("model_name",), (ENGINE,), ("catalogue", "loop"),
           "Steps of applied decode dispatches that NO row used: "
           "executed steps less the most tokens one row of the dispatch "
           "delivered (every step of a failed dispatch; 0 where a row "
           "ran the whole train). A step costs the device the same at 2 "
           "rows as at 20: over `pstpu:decode_steps_total` this is the "
           "share of decode device time that served nobody; dispatch by "
           "dispatch, empty steps x rows <= wasted row-steps"),
    Series("pstpu:decode_rows_first_total", "counter",
           ("model_name",), (ENGINE,), ("catalogue", "loop"),
           "Rows a decode dispatch took for the first time since their "
           "last prompt chunk, counted at issue (a preempted row "
           "prefilled again counts again; a row that ends at its first "
           "token never counts)"),
    Series("pstpu:decode_rows_joined_total", "counter",
           ("model_name",), (ENGINE,), ("catalogue", "loop"),
           "Of `pstpu:decode_rows_first_total`, rows whose first token was "
           "still in the in-flight prefill's device vector at that issue: "
           "the decode chained its start token from it, so the row rides "
           "the train issued right behind its prefill; the rest waited "
           "out the prefill's apply (the window budget or the block pool "
           "left the row out, a penalty batch drained the pipeline, or "
           "the loop runs at depth 1). The ratio is the share of "
           "hand-offs that cost no train"),
    Series("pstpu:prefill_tokens_issued_total", "counter",
           ("model_name",), (ENGINE,), ("catalogue", "loop"),
           "Prompt tokens prefill dispatches really computed (the sum of "
           "their chunks; prefix hits are not in it), counted at issue; "
           "over `pstpu:prefill_dispatches_total` the tokens a dispatch "
           "carries"),
    Series("pstpu:prefill_tokens_padded_total", "counter",
           ("model_name",), (ENGINE,), ("catalogue", "loop"),
           "Tokens of the padded shape prefill dispatches ran: "
           "program rows x program chunk length "
           "(`utils.prefill_rectangle`, the shape the device computes; "
           "one row of T tokens where the chunks are packed end to end); "
           "issued over padded is the share of prefill compute that was "
           "prompt"),
    Series("pstpu:prefill_rows_issued_total", "counter",
           ("model_name",), (ENGINE,), ("catalogue", "loop"),
           "Live rows of prefill dispatches, counted at issue; over "
           "`pstpu:prefill_dispatches_total` the rows a dispatch carries"),
    Series("pstpu:prefill_segments_total", "counter",
           ("model_name",), (ENGINE,), ("catalogue", "loop"),
           "Sequences whose chunks lay end to end as segments of the one "
           "row of a PACKED prefill dispatch, counted at issue; 0 while "
           "every dispatch is a rectangle (a model with a per-row state "
           "its module does not declare to cross segments, an adapter or a "
           "draft's ring a row, a gathered window)"),
    Series("pstpu:attn_keys_in_span_total", "counter",
           ("model_name",), (ENGINE,), ("catalogue", "loop"),
           "Keys the attention layers' queries see inside their layer's "
           "span (a model's sliding-window attention), summed over the "
           "layers, for every prefill token at issue and every delivered "
           "decode row-step at apply: exact host integers "
           "(`ops/attention.py:keys_in_span`); 0 for a model without a "
           "bounded layer"),
    Series("pstpu:attn_keys_held_total", "counter",
           ("model_name",), (ENGINE,), ("catalogue", "loop"),
           "The same with no layer bounded: the keys the one block table "
           "holds for those queries; in-span over held is the share of "
           "the held keys the bounded kernels read"),
    Series("pstpu:ring_keys_held_total", "counter",
           ("model_name",), (ENGINE,), ("catalogue", "loop"),
           "Keys the window layers' per-sequence rings hold (a model whose "
           "sliding-window layers keep their window as a ring in a state "
           "slot, `ops/attention.py:window_ring_attend`): min(context, "
           "window) a layer, for the sequence of every delivered decode "
           "row-step; 0 for a model without a ring"),
    Series("pstpu:ring_keys_context_total", "counter",
           ("model_name",), (ENGINE,), ("catalogue", "loop"),
           "The keys of those sequences' contexts over the same layers: "
           "what one pool would hold for them; held over context is the "
           "share of a pool's keys the rings keep"),
    Series("pstpu:prefill_left_waiting_total", "counter",
           ("model_name",), (ENGINE,), ("catalogue", "loop"),
           "Requests still waiting that a prefill could have taken, at "
           "the end of each dispatch's admission pass, summed; over "
           "`pstpu:prefill_dispatches_total` the mean backlog a dispatch "
           "leaves behind"),
    Series("pstpu:prefill_stop_rows_total", "counter",
           ("model_name",), (ENGINE,), ("catalogue", "loop"),
           "Prefill admission passes (a dispatch, or a pass that "
           "scheduled nothing while requests waited) whose FIRST limit "
           "was the rows of one prefill dispatch: as many as "
           "`--max-num-batched-tokens` holds at the narrowest chunk "
           "bucket (budget // 128: 16 at 2048), within `--max-num-seqs`; "
           "the queue is deeper than one rectangle of the budget, so "
           "raise the budget (at `tpot`'s cost) or pack by tokens"),
    Series("pstpu:prefill_stop_seqs_total", "counter",
           ("model_name",), (ENGINE,), ("catalogue", "loop"),
           "Prefill admission passes (a dispatch, or a pass that "
           "scheduled nothing while requests waited) whose FIRST limit "
           "was `--max-num-seqs` less the running sequences: the decode "
           "batch is full; raise it if the device has room, else the "
           "engine is at capacity"),
    Series("pstpu:prefill_stop_tokens_total", "counter",
           ("model_name",), (ENGINE,), ("catalogue", "loop"),
           "Prefill admission passes (a dispatch, or a pass that "
           "scheduled nothing while requests waited) whose FIRST limit "
           "was `--max-num-batched-tokens`: of the rectangles within "
           "the budget the one that carries the most live tokens took "
           "fewer rows than admission had gathered (long chunks before "
           "many rows)"),
    Series("pstpu:prefill_stop_window_total", "counter",
           ("model_name",), (ENGINE,), ("catalogue", "loop"),
           "Prefill admission passes (a dispatch, or a pass that "
           "scheduled nothing while requests waited) whose FIRST limit "
           "was the prefill window budget (derived from the pool where a "
           "history window is still gathered: int8 KV, tp/sp > 1, rows "
           "no prefill kernel tiles; unlimited where the pool is read in "
           "place): a "
           "gathered window at the padded rows did not fit"),
    Series("pstpu:prefill_stop_slots_total", "counter",
           ("model_name",), (ENGINE,), ("catalogue", "loop"),
           "Prefill admission passes (a dispatch, or a pass that "
           "scheduled nothing while requests waited) whose FIRST limit "
           "was no free recurrent-state slot (one a sequence, `--max- "
           "num-seqs` of them, for a model that declares state): "
           "finished sequences free them"),
    Series("pstpu:prefill_stop_blocks_total", "counter",
           ("model_name",), (ENGINE,), ("catalogue", "loop"),
           "Prefill admission passes (a dispatch, or a pass that "
           "scheduled nothing while requests waited) whose FIRST limit "
           "was no KV blocks for a candidate's prompt (`--num-kv- "
           "blocks`): the pool is full; with `vllm:gpu_cache_usage_perc` "
           "near 1 add blocks or lower `--max-model-len`"),
    Series("pstpu:serving_compiles_total", "counter",
           ("model_name",), (ENGINE,), ("catalogue", "loop"),
           "Programs compiled or loaded from the persistent compile "
           "cache AFTER warm-up (JAX's `backend_compile_duration` "
           "events): a deferred variant's first use and a true recompile "
           "alike; an already-compiled call adds nothing. The "
           "`pstpu.issue.enqueue` span and the request's `*_issue` event "
           "of that step carry `compiled`"),
    Series("pstpu:serving_compile_seconds_total", "counter",
           ("model_name",), (ENGINE,), ("catalogue", "loop"),
           "Seconds spent tracing, lowering and compiling or cache- "
           "loading programs after warm-up (JAX's three "
           "`/jax/core/compile/` durations; process-wide): 0 on a warm "
           "engine, and a dispatch stall otherwise"),
    Series("pstpu:sample_dispatches_total", "counter", ("model_name",),
           (ENGINE,), ("catalogue", "loop"),
           "Prefill and decode dispatches issued (each runs the sampler "
           "once a step), counted at issue"),
    Series("pstpu:sample_dispatches_greedy_total", "counter",
           ("model_name",), (ENGINE,), ("catalogue", "loop"),
           "Dispatches whose every row is greedy (`temperature <= 0`): the "
           "sampler runs one argmax, no Gumbel field and no candidate "
           "search"),
    Series("pstpu:sample_dispatches_filtered_total", "counter",
           ("model_name",), (ENGINE,), ("catalogue", "loop"),
           "Dispatches in which a sampled row has `top_k` or `top_p`: the "
           "sampler runs its top-128 candidate search; total less greedy "
           "less filtered ran the Gumbel pick alone"),
    Series("pstpu:http_ingress_seconds", "histogram", ("model_name",),
           (ENGINE,), ("catalogue", "loop"),
           "HTTP handler entry to the request's enqueue in the scheduler "
           "(body parse, chat template, tokenisation)"),
    Series("pstpu:first_chunk_emit_seconds", "histogram", ("model_name",),
           (ENGINE,), ("catalogue", "loop"),
           "First token appended in the engine loop to the first chunk "
           "handed to the transport (the whole body when not streaming, "
           "which then contains the decode)"),
    # ------------------------------------------------ router: fleet pane
    # One operator surface over what the scraper already holds per
    # backend (GET /fleet serves the JSON view of the same aggregate).
    Series("router_fleet_backends", "gauge", (), (ROUTER,),
           ("catalogue", "fleet-perf"),
           "Backends in the router's current fleet view (healthy "
           "serving endpoints)",
           router_labels=()),
    Series("router_fleet_live_tok_per_s", "gauge", (), (ROUTER,),
           ("catalogue", "fleet-perf"),
           "Engine-reported live generation throughput per backend",
           router_labels=("server",)),
    Series("router_fleet_live_hbm_bw_pct", "gauge", (), (ROUTER,),
           ("catalogue", "fleet-perf"),
           "Engine-reported live roofline position per backend "
           "(percent of the decode HBM ceiling)",
           router_labels=("server",)),
    Series("router_fleet_live_effective_tokens_per_target_step", "gauge",
           (), (ROUTER,), ("catalogue", "fleet-perf"),
           "Engine-reported tokens emitted per target-model step per "
           "backend (speculation amortization)",
           router_labels=("server",)),
    Series("router_fleet_breaker_open", "gauge", (), (ROUTER,),
           ("catalogue", "fleet-perf"),
           "Circuit-breaker position per backend (0 closed / 1 open / "
           "2 half-open) in the fleet view",
           router_labels=("server",)),
    Series("router_fleet_ramp_in_penalty", "gauge", (), (ROUTER,),
           ("catalogue", "fleet-perf"),
           "Remaining ramp-in load penalty per backend (1 just joined "
           "-> 0 fully ramped)",
           router_labels=("server",)),
)


def by_surface(surface: str) -> Dict[str, Series]:
    """name -> Series for one exporter surface."""
    return {s.name: s for s in REGISTRY if surface in s.surfaces}
