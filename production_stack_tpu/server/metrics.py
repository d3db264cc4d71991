"""Engine-pod /metrics exposition, vllm-series-compatible.

Emits exactly the series the router's EngineStatsScraper parses
(reference src/vllm_router/stats/engine_stats.py:128-155 is the contract):
vllm:num_requests_running, vllm:num_requests_waiting,
vllm:gpu_prefix_cache_hits_total, vllm:gpu_prefix_cache_queries_total,
vllm:gpu_cache_usage_perc (TPU HBM KV-pool usage), vllm:num_preemptions_total,
plus token throughput counters for dashboards.
"""

from typing import TYPE_CHECKING

if TYPE_CHECKING:
    from production_stack_tpu.engine.engine import ServingEngine


def render_engine_metrics(engine: "ServingEngine", model_name: str) -> str:
    s = dict(engine.stats())
    # Dispatch-pipeline telemetry keys default to 0 so protocol-faithful
    # fakes (tests) that predate them still render.
    for key in ("decode_dispatches_total", "prefill_dispatches_total",
                "dispatch_overlap_ratio", "dispatch_gap_seconds_total",
                "kv_handoffs_total", "kv_handoff_bytes_total",
                "kv_handoff_seconds_total", "kv_handoff_failures_total",
                "engine_uptime_seconds", "kv_offload_blocks",
                "kv_quant_bytes_saved_total", "queue_depth",
                "prefix_index_size", "kv_restore_saved_tokens_total",
                "state_slots_total", "state_slots_in_use",
                "state_slot_allocs_total", "state_slot_waits_total",
                "prefix_hit_tokens_unserved_total",
                "kv_shared_tier_hits_total", "kv_shared_tier_misses_total",
                "kv_chain_evictions_total", "resume_restored_tokens_total",
                "spec_enabled", "spec_draft_tokens_total",
                "spec_accepted_tokens_total", "spec_acceptance_rate",
                "spec_acceptance_rate_window", "spec_draft_depth",
                "spec_tree_nodes_total", "spec_acceptance_ema",
                "spec_gamma0_dispatches_total",
                "startup_weight_load_seconds", "startup_compile_seconds",
                "startup_warmup_seconds", "startup_prewarm_seconds",
                "startup_total_seconds", "startup_cache_hit_families",
                "startup_cache_miss_families", "startup_loaded_families",
                "trace_spans_dropped_total",
                "host_stall_seconds_total",
                "loop_schedule_seconds_total", "loop_issue_seconds_total",
                "loop_fetch_wait_seconds_total",
                "loop_apply_seconds_total", "loop_idle_seconds_total",
                "loop_other_seconds_total", "decode_steps_total",
                "decode_row_steps_total", "decode_bucket_row_steps_total",
                "decode_row_steps_wasted_total", "decode_steps_empty_total",
                "decode_rows_first_total", "decode_rows_joined_total",
                "prefill_tokens_issued_total", "prefill_tokens_padded_total",
                "prefill_rows_issued_total", "prefill_segments_total",
                "attn_keys_in_span_total", "attn_keys_held_total",
                "ring_keys_held_total", "ring_keys_context_total",
                "prefill_left_waiting_total",
                "prefill_stop_rows_total", "prefill_stop_seqs_total",
                "prefill_stop_tokens_total", "prefill_stop_window_total",
                "prefill_stop_slots_total", "prefill_stop_blocks_total",
                "serving_compiles_total", "serving_compile_seconds_total",
                "sample_dispatches_total", "sample_dispatches_greedy_total",
                "sample_dispatches_filtered_total",
                "moe_assignments_total", "moe_expert_load_max_total",
                "moe_experts_touched_total", "moe_layer_calls_total",
                "moe_prefill_experts_touched_total",
                "moe_prefill_layer_calls_total",
                "live_tok_per_s",
                "live_hbm_bw_pct",
                "live_effective_tokens_per_target_step"):
        s.setdefault(key, 0)
    s.setdefault("disagg_role", "unified")
    s.setdefault("kv_cache_dtype", "bfloat16")
    s.setdefault("mesh_tp_size", 1)
    s.setdefault("mesh_sp_size", 1)
    s.setdefault("mesh_devices", 1)
    s.setdefault("hbm_resident_bytes", {})
    for key in ("hbm_bytes_in_use", "hbm_peak_bytes", "hbm_limit_bytes",
                "hbm_reserved_bytes"):
        s.setdefault(key, 0)
    for key in ("hbm_peak_rises", "hbm_peak_rise_bytes"):
        s.setdefault(key, {"warmup": 0, "serving": 0})
    label = f'{{model_name="{model_name}"}}'
    lines = [
        "# HELP vllm:num_requests_running Running requests",
        "# TYPE vllm:num_requests_running gauge",
        f"vllm:num_requests_running{label} {s['num_requests_running']}",
        "# HELP vllm:num_requests_waiting Waiting requests",
        "# TYPE vllm:num_requests_waiting gauge",
        f"vllm:num_requests_waiting{label} {s['num_requests_waiting']}",
        # Autoscaling signal (docs/SOAK.md): running+waiting backlog as one
        # per-pod series, the Pods-type HPA metric (prometheus-adapter
        # exposes it as pstpu_queue_depth).
        "# HELP pstpu:queue_depth Engine backlog (running + waiting "
        "requests)",
        "# TYPE pstpu:queue_depth gauge",
        f"pstpu:queue_depth{label} {s['queue_depth']}",
        "# HELP vllm:gpu_cache_usage_perc KV-pool usage (TPU HBM)",
        "# TYPE vllm:gpu_cache_usage_perc gauge",
        f"vllm:gpu_cache_usage_perc{label} {s['kv_cache_usage']:.6f}",
        "# HELP vllm:gpu_prefix_cache_hits_total Prefix cache hit tokens",
        "# TYPE vllm:gpu_prefix_cache_hits_total counter",
        f"vllm:gpu_prefix_cache_hits_total{label} {s['prefix_cache_hits']}",
        "# HELP vllm:gpu_prefix_cache_queries_total Prefix cache query tokens",
        "# TYPE vllm:gpu_prefix_cache_queries_total counter",
        f"vllm:gpu_prefix_cache_queries_total{label} {s['prefix_cache_queries']}",
        "# HELP vllm:num_preemptions_total Preempted sequences",
        "# TYPE vllm:num_preemptions_total counter",
        f"vllm:num_preemptions_total{label} {s['num_preemptions']}",
        "# HELP vllm:prompt_tokens_total Prefilled tokens",
        "# TYPE vllm:prompt_tokens_total counter",
        f"vllm:prompt_tokens_total{label} {s['prompt_tokens_total']}",
        "# HELP vllm:generation_tokens_total Generated tokens",
        "# TYPE vllm:generation_tokens_total counter",
        f"vllm:generation_tokens_total{label} {s['generation_tokens_total']}",
        "# HELP pstpu:engine_uptime_seconds Engine uptime",
        "# TYPE pstpu:engine_uptime_seconds gauge",
        f"pstpu:engine_uptime_seconds{label} "
        f"{s['engine_uptime_seconds']:.6f}",
        "# HELP pstpu:kv_offload_blocks KV blocks resident in the host "
        "offload pool",
        "# TYPE pstpu:kv_offload_blocks gauge",
        f"pstpu:kv_offload_blocks{label} {s['kv_offload_blocks']}",
        # KV economy (docs/KV_ECONOMY.md): device prefix-index size (the
        # /prefix_index digest quantity) + shared-tier restore/eviction
        # telemetry.
        "# HELP pstpu:prefix_index_size Content-addressed blocks resident "
        "in the device prefix cache (the /prefix_index digest size)",
        "# TYPE pstpu:prefix_index_size gauge",
        f"pstpu:prefix_index_size{label} {s['prefix_index_size']}",
        # Per-sequence recurrent state (a model that declares some;
        # docs/OBSERVABILITY.md): the block manager's slots, and the prefix
        # hits such a model cannot be served yet.
        "# HELP pstpu:state_slots_total Recurrent-state slots the block "
        "manager hands out, one a sequence (0: a K/V-only model)",
        "# TYPE pstpu:state_slots_total gauge",
        f"pstpu:state_slots_total{label} {s['state_slots_total']}",
        "# HELP pstpu:state_slots_in_use Recurrent-state slots held by "
        "admitted sequences",
        "# TYPE pstpu:state_slots_in_use gauge",
        f"pstpu:state_slots_in_use{label} {s['state_slots_in_use']}",
        "# HELP pstpu:state_slot_allocs_total Recurrent-state slots handed "
        "out (a sequence takes one with its blocks)",
        "# TYPE pstpu:state_slot_allocs_total counter",
        f"pstpu:state_slot_allocs_total{label} "
        f"{s['state_slot_allocs_total']}",
        "# HELP pstpu:state_slot_waits_total Admissions put off because "
        "every recurrent-state slot was held",
        "# TYPE pstpu:state_slot_waits_total counter",
        f"pstpu:state_slot_waits_total{label} "
        f"{s['state_slot_waits_total']}",
        "# HELP pstpu:prefix_hit_tokens_unserved_total Prompt tokens whose "
        "K/V the prefix index held but that were prefilled again, because "
        "nothing keeps the recurrent state after them",
        "# TYPE pstpu:prefix_hit_tokens_unserved_total counter",
        f"pstpu:prefix_hit_tokens_unserved_total{label} "
        f"{s['prefix_hit_tokens_unserved_total']}",
        "# HELP pstpu:kv_restore_saved_tokens_total Prompt tokens restored "
        "from the shared KV tier instead of recomputed (cost-model "
        "admitted)",
        "# TYPE pstpu:kv_restore_saved_tokens_total counter",
        f"pstpu:kv_restore_saved_tokens_total{label} "
        f"{s['kv_restore_saved_tokens_total']}",
        "# HELP pstpu:kv_shared_tier_hits_total KV blocks served by the "
        "shared host/remote tiers during prefill restores",
        "# TYPE pstpu:kv_shared_tier_hits_total counter",
        f"pstpu:kv_shared_tier_hits_total{label} "
        f"{s['kv_shared_tier_hits_total']}",
        "# HELP pstpu:kv_shared_tier_misses_total Restore-candidate KV "
        "blocks the shared tiers did not hold",
        "# TYPE pstpu:kv_shared_tier_misses_total counter",
        f"pstpu:kv_shared_tier_misses_total{label} "
        f"{s['kv_shared_tier_misses_total']}",
        "# HELP pstpu:kv_chain_evictions_total Leaf-first chain evictions "
        "in the local host KV tier",
        "# TYPE pstpu:kv_chain_evictions_total counter",
        f"pstpu:kv_chain_evictions_total{label} "
        f"{s['kv_chain_evictions_total']}",
        # Mid-stream resume (docs/RESILIENCE.md): prompt+resume tokens a
        # resume request served from cache/tiers instead of recomputing.
        "# HELP pstpu:resume_restored_tokens_total Prompt+resume tokens "
        "served from the prefix cache or KV tiers on mid-stream resume "
        "requests instead of recomputed",
        "# TYPE pstpu:resume_restored_tokens_total counter",
        f"pstpu:resume_restored_tokens_total{label} "
        f"{s['resume_restored_tokens_total']}",
        # Speculative decoding (docs/PERF.md round 8): whether the draft
        # path is active, draft proposals made/accepted, and the lifetime
        # acceptance rate.
        "# HELP pstpu:spec_enabled Speculative decoding active "
        "(--speculative-num-tokens > 0)",
        "# TYPE pstpu:spec_enabled gauge",
        f"pstpu:spec_enabled{label} {s['spec_enabled']}",
        "# HELP pstpu:spec_draft_tokens_total Draft-model token proposals "
        "made inside fused decode dispatches",
        "# TYPE pstpu:spec_draft_tokens_total counter",
        f"pstpu:spec_draft_tokens_total{label} "
        f"{s['spec_draft_tokens_total']}",
        "# HELP pstpu:spec_accepted_tokens_total Draft proposals that "
        "survived target verification (bonus tokens not counted)",
        "# TYPE pstpu:spec_accepted_tokens_total counter",
        f"pstpu:spec_accepted_tokens_total{label} "
        f"{s['spec_accepted_tokens_total']}",
        "# HELP pstpu:spec_acceptance_rate_window Draft acceptance over "
        "the last <=64 dispatch fetches (windowed companion to the "
        "lifetime rate)",
        "# TYPE pstpu:spec_acceptance_rate_window gauge",
        f"pstpu:spec_acceptance_rate_window{label} "
        f"{s['spec_acceptance_rate_window']:.6f}",
        "# HELP pstpu:spec_draft_depth Mean served draft depth per live "
        "verify cycle (adaptive gamma controller)",
        "# TYPE pstpu:spec_draft_depth gauge",
        f"pstpu:spec_draft_depth{label} {s['spec_draft_depth']:.6f}",
        "# HELP pstpu:spec_tree_nodes_total Token-tree nodes verified "
        "(tree speculation)",
        "# TYPE pstpu:spec_tree_nodes_total counter",
        f"pstpu:spec_tree_nodes_total{label} {s['spec_tree_nodes_total']}",
        "# HELP pstpu:spec_acceptance_ema Mean per-sequence acceptance "
        "EMA over live sequences (adaptive controller)",
        "# TYPE pstpu:spec_acceptance_ema gauge",
        f"pstpu:spec_acceptance_ema{label} {s['spec_acceptance_ema']:.6f}",
        "# HELP pstpu:spec_gamma0_dispatches_total Decode dispatches the "
        "adaptive controller degraded to the plain (non-speculative) scan",
        "# TYPE pstpu:spec_gamma0_dispatches_total counter",
        f"pstpu:spec_gamma0_dispatches_total{label} "
        f"{s['spec_gamma0_dispatches_total']}",
        "# HELP pstpu:spec_acceptance_rate Lifetime fraction of draft "
        "proposals accepted by the target",
        "# TYPE pstpu:spec_acceptance_rate gauge",
        f"pstpu:spec_acceptance_rate{label} "
        f"{s['spec_acceptance_rate']:.6f}",
        # Elastic fast-start (docs/ELASTIC.md): startup phase durations +
        # the warmup persistent-compile-cache hit/miss split.
        "# HELP pstpu:startup_weight_load_seconds Seconds loading model "
        "weights at startup (overlaps compile with overlap_weight_load)",
        "# TYPE pstpu:startup_weight_load_seconds gauge",
        f"pstpu:startup_weight_load_seconds{label} "
        f"{s['startup_weight_load_seconds']:.6f}",
        "# HELP pstpu:startup_compile_seconds Seconds in the AOT "
        "compile-only warmup prepass (overlapped with the weight load)",
        "# TYPE pstpu:startup_compile_seconds gauge",
        f"pstpu:startup_compile_seconds{label} "
        f"{s['startup_compile_seconds']:.6f}",
        "# HELP pstpu:startup_warmup_seconds Seconds executing warmup "
        "shape families before serving",
        "# TYPE pstpu:startup_warmup_seconds gauge",
        f"pstpu:startup_warmup_seconds{label} "
        f"{s['startup_warmup_seconds']:.6f}",
        "# HELP pstpu:startup_prewarm_seconds Seconds serving POST "
        "/prewarm hot-chain pulls from the shared KV tier",
        "# TYPE pstpu:startup_prewarm_seconds gauge",
        f"pstpu:startup_prewarm_seconds{label} "
        f"{s['startup_prewarm_seconds']:.6f}",
        "# HELP pstpu:startup_total_seconds Engine construction to "
        "ready-to-serve, seconds",
        "# TYPE pstpu:startup_total_seconds gauge",
        f"pstpu:startup_total_seconds{label} "
        f"{s['startup_total_seconds']:.6f}",
        "# HELP pstpu:startup_cache_hit_families Warmup variants loaded "
        "from the persistent compile cache (no recompile)",
        "# TYPE pstpu:startup_cache_hit_families gauge",
        f"pstpu:startup_cache_hit_families{label} "
        f"{s['startup_cache_hit_families']}",
        "# HELP pstpu:startup_cache_miss_families Warmup variants that "
        "compiled from scratch (cold cache or changed config)",
        "# TYPE pstpu:startup_cache_miss_families gauge",
        f"pstpu:startup_cache_miss_families{label} "
        f"{s['startup_cache_miss_families']}",
        "# HELP pstpu:startup_loaded_families Warmup variants loaded from "
        "the runner's stored executables and not traced (each also a hit)",
        "# TYPE pstpu:startup_loaded_families gauge",
        f"pstpu:startup_loaded_families{label} "
        f"{s['startup_loaded_families']}",
        # Two-slot dispatch-pipeline telemetry (engine.py:_run_loop): the
        # prefill/decode overlap win is observable, not asserted.
        "# HELP pstpu:decode_dispatches_total Fused decode dispatches issued",
        "# TYPE pstpu:decode_dispatches_total counter",
        f"pstpu:decode_dispatches_total{label} "
        f"{s['decode_dispatches_total']}",
        "# HELP pstpu:prefill_dispatches_total Prefill chunk dispatches "
        "issued",
        "# TYPE pstpu:prefill_dispatches_total counter",
        f"pstpu:prefill_dispatches_total{label} "
        f"{s['prefill_dispatches_total']}",
        "# HELP pstpu:dispatch_overlap_ratio Fraction of dispatch fetches "
        "with another dispatch still outstanding",
        "# TYPE pstpu:dispatch_overlap_ratio gauge",
        f"pstpu:dispatch_overlap_ratio{label} "
        f"{s['dispatch_overlap_ratio']:.6f}",
        "# HELP pstpu:dispatch_gap_seconds_total Host-observed seconds with "
        "no dispatch outstanding between dispatches",
        "# TYPE pstpu:dispatch_gap_seconds_total counter",
        f"pstpu:dispatch_gap_seconds_total{label} "
        f"{s['dispatch_gap_seconds_total']:.6f}",
        # Live roofline telemetry (docs/OBSERVABILITY.md fleet pane): the
        # engine's own roofline position from the rolling dispatch window
        # (with the per-train dispatch-duration histogram below: the
        # registry's "fleet-perf" group).
        "# HELP pstpu:live_tok_per_s Generation throughput over the "
        "rolling dispatch window (tokens emitted / window wall span)",
        "# TYPE pstpu:live_tok_per_s gauge",
        f"pstpu:live_tok_per_s{label} {s['live_tok_per_s']:.6f}",
        "# HELP pstpu:live_hbm_bw_pct Achieved fraction (percent) of the "
        "decode HBM roofline for the CURRENT batch shape",
        "# TYPE pstpu:live_hbm_bw_pct gauge",
        # No sample where the engine knows no HBM peak (CPU backend).
        *([f"pstpu:live_hbm_bw_pct{label} {s['live_hbm_bw_pct']:.6f}"]
          if s["live_hbm_bw_pct"] is not None else []),
        "# HELP pstpu:live_effective_tokens_per_target_step Tokens emitted "
        "per target-model step over the rolling window (>1 only when "
        "speculation pays)",
        "# TYPE pstpu:live_effective_tokens_per_target_step gauge",
        f"pstpu:live_effective_tokens_per_target_step{label} "
        f"{s['live_effective_tokens_per_target_step']:.6f}",
        "# HELP pstpu:host_stall_seconds_total Fetch-done to next "
        "issue-start gap with nothing outstanding on device (host "
        "scheduling stall)",
        "# TYPE pstpu:host_stall_seconds_total counter",
        f"pstpu:host_stall_seconds_total{label} "
        f"{s['host_stall_seconds_total']:.6f}",
        # Loop spans (engine.py:_run_loop, flight_recorder.LoopSpans): the
        # six phases tile the loop's wall time, so their deltas over a
        # window sum to the window; and decode work counted where it
        # happens.
        "# HELP pstpu:loop_schedule_seconds_total Engine-loop seconds in "
        "scheduler.schedule() (span pstpu.schedule)",
        "# TYPE pstpu:loop_schedule_seconds_total counter",
        f"pstpu:loop_schedule_seconds_total{label} "
        f"{s['loop_schedule_seconds_total']:.6f}",
        "# HELP pstpu:loop_issue_seconds_total Engine-loop seconds "
        "issuing dispatches: execute_async in the executor, "
        "advance_at_issue, issue records (span pstpu.issue)",
        "# TYPE pstpu:loop_issue_seconds_total counter",
        f"pstpu:loop_issue_seconds_total{label} "
        f"{s['loop_issue_seconds_total']:.6f}",
        "# HELP pstpu:loop_fetch_wait_seconds_total Engine-loop seconds "
        "awaiting a dispatch's fetch: the host blocked on the device "
        "(span pstpu.fetch)",
        "# TYPE pstpu:loop_fetch_wait_seconds_total counter",
        f"pstpu:loop_fetch_wait_seconds_total{label} "
        f"{s['loop_fetch_wait_seconds_total']:.6f}",
        "# HELP pstpu:loop_apply_seconds_total Engine-loop seconds "
        "applying fetched results: fetch records, apply_results, output "
        "processing, handoff publishes (span pstpu.apply)",
        "# TYPE pstpu:loop_apply_seconds_total counter",
        f"pstpu:loop_apply_seconds_total{label} "
        f"{s['loop_apply_seconds_total']:.6f}",
        "# HELP pstpu:loop_idle_seconds_total Engine-loop seconds with "
        "nothing schedulable: waiting for work or retrying "
        "(span pstpu.idle)",
        "# TYPE pstpu:loop_idle_seconds_total counter",
        f"pstpu:loop_idle_seconds_total{label} "
        f"{s['loop_idle_seconds_total']:.6f}",
        "# HELP pstpu:loop_other_seconds_total Engine-loop seconds in "
        "aborts, restores, prewarms and the yield after an apply "
        "(span pstpu.housekeeping)",
        "# TYPE pstpu:loop_other_seconds_total counter",
        f"pstpu:loop_other_seconds_total{label} "
        f"{s['loop_other_seconds_total']:.6f}",
        "# HELP pstpu:decode_steps_total Decode-loop steps the device "
        "ran, over applied decode dispatches",
        "# TYPE pstpu:decode_steps_total counter",
        f"pstpu:decode_steps_total{label} {s['decode_steps_total']}",
        "# HELP pstpu:decode_row_steps_total Real rows times the steps "
        "their decode dispatch ran (padding rows are not rows)",
        "# TYPE pstpu:decode_row_steps_total counter",
        f"pstpu:decode_row_steps_total{label} "
        f"{s['decode_row_steps_total']}",
        "# HELP pstpu:decode_bucket_row_steps_total Rows of the decode "
        "program's shape bucket (padding included) times the steps the "
        "dispatch ran",
        "# TYPE pstpu:decode_bucket_row_steps_total counter",
        f"pstpu:decode_bucket_row_steps_total{label} "
        f"{s['decode_bucket_row_steps_total']}",
        "# HELP pstpu:decode_row_steps_wasted_total Decode row-steps "
        "whose token was not delivered (row finished earlier in the "
        "train, aborted, preempted, or its fetch failed)",
        "# TYPE pstpu:decode_row_steps_wasted_total counter",
        f"pstpu:decode_row_steps_wasted_total{label} "
        f"{s['decode_row_steps_wasted_total']}",
        "# HELP pstpu:decode_steps_empty_total Steps of applied decode "
        "dispatches that no row used: executed steps less the most "
        "tokens one row delivered (every step of a failed dispatch)",
        "# TYPE pstpu:decode_steps_empty_total counter",
        f"pstpu:decode_steps_empty_total{label} "
        f"{s['decode_steps_empty_total']}",
        "# HELP pstpu:decode_rows_first_total Rows a decode dispatch took "
        "for the first time since their last prompt chunk, counted at "
        "issue",
        "# TYPE pstpu:decode_rows_first_total counter",
        f"pstpu:decode_rows_first_total{label} "
        f"{s['decode_rows_first_total']}",
        "# HELP pstpu:decode_rows_joined_total Of those, rows whose first "
        "token was still in the in-flight prefill's device vector: they "
        "ride the decode train issued right behind their prefill",
        "# TYPE pstpu:decode_rows_joined_total counter",
        f"pstpu:decode_rows_joined_total{label} "
        f"{s['decode_rows_joined_total']}",
        # What a prefill dispatch carried and what stopped its admission
        # pass, counted at issue (the pstpu.issue span carries the same
        # numbers), and compiles past warm-up.
        "# HELP pstpu:prefill_tokens_issued_total Prompt tokens prefill "
        "dispatches really computed (the sum of their chunks), counted "
        "at issue",
        "# TYPE pstpu:prefill_tokens_issued_total counter",
        f"pstpu:prefill_tokens_issued_total{label} "
        f"{s['prefill_tokens_issued_total']}",
        "# HELP pstpu:prefill_tokens_padded_total Tokens of the padded "
        "shape (program rows x program chunk length; one row where the "
        "chunks are packed end to end) prefill dispatches ran; issued "
        "over padded is the share that was prompt",
        "# TYPE pstpu:prefill_tokens_padded_total counter",
        f"pstpu:prefill_tokens_padded_total{label} "
        f"{s['prefill_tokens_padded_total']}",
        "# HELP pstpu:prefill_rows_issued_total Live rows of prefill "
        "dispatches, counted at issue",
        "# TYPE pstpu:prefill_rows_issued_total counter",
        f"pstpu:prefill_rows_issued_total{label} "
        f"{s['prefill_rows_issued_total']}",
        "# HELP pstpu:prefill_segments_total Sequences whose chunks lay "
        "end to end in the one row of a packed prefill dispatch, counted "
        "at issue (0 while every dispatch is a rectangle)",
        "# TYPE pstpu:prefill_segments_total counter",
        f"pstpu:prefill_segments_total{label} "
        f"{s['prefill_segments_total']}",
        "# HELP pstpu:attn_keys_in_span_total Keys the attention layers' "
        "queries see inside their layer's span, summed over layers, for "
        "every prefill token at issue and every delivered decode row-step "
        "(0 for a model without a bounded layer)",
        "# TYPE pstpu:attn_keys_in_span_total counter",
        f"pstpu:attn_keys_in_span_total{label} "
        f"{s['attn_keys_in_span_total']}",
        "# HELP pstpu:attn_keys_held_total The same with no layer bounded: "
        "the keys the one block table holds for those queries",
        "# TYPE pstpu:attn_keys_held_total counter",
        f"pstpu:attn_keys_held_total{label} {s['attn_keys_held_total']}",
        "# HELP pstpu:ring_keys_held_total Keys the window layers' "
        "per-sequence rings hold (min(context, window) a layer) for the "
        "sequence of every delivered decode row-step (0 for a model "
        "without a ring)",
        "# TYPE pstpu:ring_keys_held_total counter",
        f"pstpu:ring_keys_held_total{label} {s['ring_keys_held_total']}",
        "# HELP pstpu:ring_keys_context_total The keys of those "
        "sequences' contexts over the same layers: what one pool would "
        "hold for them",
        "# TYPE pstpu:ring_keys_context_total counter",
        f"pstpu:ring_keys_context_total{label} "
        f"{s['ring_keys_context_total']}",
        "# HELP pstpu:prefill_left_waiting_total Requests still waiting "
        "that a prefill could have taken, summed over prefill "
        "dispatches at the end of their admission pass",
        "# TYPE pstpu:prefill_left_waiting_total counter",
        f"pstpu:prefill_left_waiting_total{label} "
        f"{s['prefill_left_waiting_total']}",
        "# HELP pstpu:prefill_stop_rows_total Prefill admission passes "
        "(a dispatch, or a pass that scheduled nothing while requests "
        "waited) first stopped by the per-dispatch row cap (what the "
        "token budget holds at the narrowest chunk: "
        "--max-num-batched-tokens // 128, within --max-num-seqs)",
        "# TYPE pstpu:prefill_stop_rows_total counter",
        f"pstpu:prefill_stop_rows_total{label} "
        f"{s['prefill_stop_rows_total']}",
        "# HELP pstpu:prefill_stop_seqs_total Prefill admission passes "
        "(a dispatch, or a pass that scheduled nothing while requests "
        "waited) first stopped by the running set's room "
        "(--max-num-seqs less the running sequences)",
        "# TYPE pstpu:prefill_stop_seqs_total counter",
        f"pstpu:prefill_stop_seqs_total{label} "
        f"{s['prefill_stop_seqs_total']}",
        "# HELP pstpu:prefill_stop_tokens_total Prefill admission "
        "passes (a dispatch, or a pass that scheduled nothing while "
        "requests waited) first stopped by the token budget "
        "(--max-num-batched-tokens: the rectangle of at most that area "
        "that carries the most took fewer rows than were gathered)",
        "# TYPE pstpu:prefill_stop_tokens_total counter",
        f"pstpu:prefill_stop_tokens_total{label} "
        f"{s['prefill_stop_tokens_total']}",
        "# HELP pstpu:prefill_stop_window_total Prefill admission "
        "passes (a dispatch, or a pass that scheduled nothing while "
        "requests waited) first stopped by the prefill window budget (a "
        "gathered history window at the padded rows did not fit)",
        "# TYPE pstpu:prefill_stop_window_total counter",
        f"pstpu:prefill_stop_window_total{label} "
        f"{s['prefill_stop_window_total']}",
        "# HELP pstpu:prefill_stop_slots_total Prefill admission passes "
        "(a dispatch, or a pass that scheduled nothing while requests "
        "waited) first stopped by a candidate found no recurrent-state "
        "slot (one a sequence: --max-num-seqs of a model with state)",
        "# TYPE pstpu:prefill_stop_slots_total counter",
        f"pstpu:prefill_stop_slots_total{label} "
        f"{s['prefill_stop_slots_total']}",
        "# HELP pstpu:prefill_stop_blocks_total Prefill admission "
        "passes (a dispatch, or a pass that scheduled nothing while "
        "requests waited) first stopped by a candidate found no KV "
        "blocks for its prompt (--num-kv-blocks)",
        "# TYPE pstpu:prefill_stop_blocks_total counter",
        f"pstpu:prefill_stop_blocks_total{label} "
        f"{s['prefill_stop_blocks_total']}",
        "# HELP pstpu:serving_compiles_total Programs compiled or "
        "loaded from the persistent cache after warm-up (a deferred "
        "variant's first use and a true recompile alike)",
        "# TYPE pstpu:serving_compiles_total counter",
        f"pstpu:serving_compiles_total{label} "
        f"{s['serving_compiles_total']}",
        "# HELP pstpu:serving_compile_seconds_total Seconds spent "
        "tracing, lowering and compiling or cache-loading programs "
        "after warm-up",
        "# TYPE pstpu:serving_compile_seconds_total counter",
        f"pstpu:serving_compile_seconds_total{label} "
        f"{s['serving_compile_seconds_total']:.6f}",
        # Sparse experts (zeros for a model without any): what the routed
        # experts were given, read behind each dispatch's fetch.
        "# HELP pstpu:moe_assignments_total Token-expert pairs the routed "
        "experts computed, decode and prefill",
        "# TYPE pstpu:moe_assignments_total counter",
        f"pstpu:moe_assignments_total{label} {s['moe_assignments_total']}",
        "# HELP pstpu:moe_expert_load_max_total Tokens of the busiest "
        "expert, summed over sparse-layer calls (times the experts over "
        "the pairs: max/mean imbalance)",
        "# TYPE pstpu:moe_expert_load_max_total counter",
        f"pstpu:moe_expert_load_max_total{label} "
        f"{s['moe_expert_load_max_total']}",
        "# HELP pstpu:moe_experts_touched_total Distinct experts a DECODE "
        "sparse-layer call gave a token, summed over the calls (the expert "
        "matrices a step reads)",
        "# TYPE pstpu:moe_experts_touched_total counter",
        f"pstpu:moe_experts_touched_total{label} "
        f"{s['moe_experts_touched_total']}",
        "# HELP pstpu:moe_layer_calls_total Sparse-layer calls of decode "
        "steps (steps run times sparse layers)",
        "# TYPE pstpu:moe_layer_calls_total counter",
        f"pstpu:moe_layer_calls_total{label} {s['moe_layer_calls_total']}",
        "# HELP pstpu:moe_prefill_experts_touched_total Distinct experts a "
        "PREFILL sparse-layer call gave a token, summed over the calls",
        "# TYPE pstpu:moe_prefill_experts_touched_total counter",
        f"pstpu:moe_prefill_experts_touched_total{label} "
        f"{s['moe_prefill_experts_touched_total']}",
        "# HELP pstpu:moe_prefill_layer_calls_total Sparse-layer calls of "
        "prefill chunks",
        "# TYPE pstpu:moe_prefill_layer_calls_total counter",
        f"pstpu:moe_prefill_layer_calls_total{label} "
        f"{s['moe_prefill_layer_calls_total']}",
        "# HELP pstpu:sample_dispatches_total Prefill and decode "
        "dispatches issued (each runs the sampler once a step)",
        "# TYPE pstpu:sample_dispatches_total counter",
        f"pstpu:sample_dispatches_total{label} "
        f"{s['sample_dispatches_total']}",
        "# HELP pstpu:sample_dispatches_greedy_total Dispatches whose "
        "every row is greedy: the sampler runs one argmax",
        "# TYPE pstpu:sample_dispatches_greedy_total counter",
        f"pstpu:sample_dispatches_greedy_total{label} "
        f"{s['sample_dispatches_greedy_total']}",
        "# HELP pstpu:sample_dispatches_filtered_total Dispatches in "
        "which a sampled row has top_k or top_p: the sampler runs its "
        "top-128 candidate search",
        "# TYPE pstpu:sample_dispatches_filtered_total counter",
        f"pstpu:sample_dispatches_filtered_total{label} "
        f"{s['sample_dispatches_filtered_total']}",
        # Observability plane (docs/OBSERVABILITY.md): OTLP spans the
        # exporter queue had to drop — tracing never blocks serving, but
        # never silently either (the lifecycle phase histograms render
        # below with the TTFT/e2e distributions).
        "# HELP pstpu:trace_spans_dropped_total OTLP spans dropped because "
        "the exporter queue was full",
        "# TYPE pstpu:trace_spans_dropped_total counter",
        f"pstpu:trace_spans_dropped_total{label} "
        f"{s['trace_spans_dropped_total']}",
        # Prefill/decode disaggregation (docs/DISAGG.md): the engine's role
        # (the router's DisaggRouter reads it to build pools) and the KV
        # handoff plane's transfer telemetry — publishes on prefill
        # engines, consumes on decode engines.
        "# HELP pstpu:disagg_role Engine disaggregation role (1 = active)",
        "# TYPE pstpu:disagg_role gauge",
        f'pstpu:disagg_role{{model_name="{model_name}",'
        f'role="{s["disagg_role"]}"}} 1',
        "# HELP pstpu:kv_handoffs_total Completed KV handoff transfers "
        "(published or consumed)",
        "# TYPE pstpu:kv_handoffs_total counter",
        f"pstpu:kv_handoffs_total{label} {s['kv_handoffs_total']}",
        "# HELP pstpu:kv_handoff_bytes_total Bytes moved through the KV "
        "handoff plane",
        "# TYPE pstpu:kv_handoff_bytes_total counter",
        f"pstpu:kv_handoff_bytes_total{label} {s['kv_handoff_bytes_total']}",
        "# HELP pstpu:kv_handoff_seconds_total Seconds spent serializing/"
        "publishing/consuming KV handoffs",
        "# TYPE pstpu:kv_handoff_seconds_total counter",
        f"pstpu:kv_handoff_seconds_total{label} "
        f"{s['kv_handoff_seconds_total']:.6f}",
        "# HELP pstpu:kv_handoff_failures_total Failed KV handoff "
        "transfers",
        "# TYPE pstpu:kv_handoff_failures_total counter",
        f"pstpu:kv_handoff_failures_total{label} "
        f"{s['kv_handoff_failures_total']}",
        # KV-cache quantization (--kv-cache-dtype int8, docs/PERF.md round
        # 7): storage dtype as an info-style gauge + bytes the quantized
        # pool avoided writing.
        "# HELP pstpu:kv_cache_dtype KV-cache storage dtype of the block "
        "pool (1 = active)",
        "# TYPE pstpu:kv_cache_dtype gauge",
        f'pstpu:kv_cache_dtype{{model_name="{model_name}",'
        f'kv_cache_dtype="{s["kv_cache_dtype"]}"}} 1',
        "# HELP pstpu:kv_quant_bytes_saved_total KV-pool bytes the "
        "quantized cache avoided writing vs the compute dtype",
        "# TYPE pstpu:kv_quant_bytes_saved_total counter",
        f"pstpu:kv_quant_bytes_saved_total{label} "
        f"{s['kv_quant_bytes_saved_total']}",
        # Multi-chip serving (docs/PERF.md round 9): the mesh shape the
        # engine's dispatches shard over.
        "# HELP pstpu:mesh_tp_size Tensor-parallel degree of the serving "
        "mesh",
        "# TYPE pstpu:mesh_tp_size gauge",
        f"pstpu:mesh_tp_size{label} {s['mesh_tp_size']}",
        "# HELP pstpu:mesh_sp_size Sequence-parallel degree of the serving "
        "mesh",
        "# TYPE pstpu:mesh_sp_size gauge",
        f"pstpu:mesh_sp_size{label} {s['mesh_sp_size']}",
        "# HELP pstpu:mesh_devices Devices the serving mesh occupies "
        "(dp x sp x tp)",
        "# TYPE pstpu:mesh_devices gauge",
        f"pstpu:mesh_devices{label} {s['mesh_devices']}",
        # What holds the HBM (docs/OBSERVABILITY.md): the memory ledger's
        # residents per mesh device, the fullest device's reading at this
        # scrape, and how often and how far the allocator's peak rose.
        "# HELP pstpu:hbm_resident_bytes Bytes resident per mesh device by "
        "holder (weights, kv = the pool's payload + scale sidecars, state, "
        "spec, lora, other = in use beyond the named holders)",
        "# TYPE pstpu:hbm_resident_bytes gauge",
        *[
            f'pstpu:hbm_resident_bytes{{model_name="{model_name}",'
            f'holder="{holder}",device="{dev}"}} {b}'
            for dev, holders in sorted(s["hbm_resident_bytes"].items())
            for holder, b in holders.items()
        ],
        "# HELP pstpu:hbm_bytes_in_use Allocator bytes in use on the "
        "fullest mesh device at this scrape (0: the backend reports none)",
        "# TYPE pstpu:hbm_bytes_in_use gauge",
        f"pstpu:hbm_bytes_in_use{label} {s['hbm_bytes_in_use']}",
        "# HELP pstpu:hbm_peak_bytes Allocator high-water mark since "
        "process start on that device",
        "# TYPE pstpu:hbm_peak_bytes gauge",
        f"pstpu:hbm_peak_bytes{label} {s['hbm_peak_bytes']}",
        "# HELP pstpu:hbm_limit_bytes Bytes the allocator may hand out on "
        "that device",
        "# TYPE pstpu:hbm_limit_bytes gauge",
        f"pstpu:hbm_limit_bytes{label} {s['hbm_limit_bytes']}",
        "# HELP pstpu:hbm_reserved_bytes Bytes the runtime holds outside "
        "bytes in use for programs' temporaries (one scratch region, the "
        "largest program's)",
        "# TYPE pstpu:hbm_reserved_bytes gauge",
        f"pstpu:hbm_reserved_bytes{label} {s['hbm_reserved_bytes']}",
        "# HELP pstpu:hbm_peak_rises_total Reads after a dispatch's "
        "enqueue or sync that found the allocator's peak higher, by phase",
        "# TYPE pstpu:hbm_peak_rises_total counter",
        *[
            f'pstpu:hbm_peak_rises_total{{model_name="{model_name}",'
            f'phase="{phase}"}} {n}'
            for phase, n in s["hbm_peak_rises"].items()
        ],
        "# HELP pstpu:hbm_peak_rise_bytes_total Bytes the allocator's peak "
        "rose by over those reads, by phase",
        "# TYPE pstpu:hbm_peak_rise_bytes_total counter",
        *[
            f'pstpu:hbm_peak_rise_bytes_total{{model_name="{model_name}",'
            f'phase="{phase}"}} {n}'
            for phase, n in s["hbm_peak_rise_bytes"].items()
        ],
    ]
    # TTFT / e2e latency distributions (the reference dashboard's two
    # distribution panels query these bucket series).
    hists = getattr(engine, "histograms", None)
    if hists is not None:
        lines += hists.render(label)
    # Request-lifecycle phase histograms (docs/OBSERVABILITY.md): queue
    # wait / prefill / decode-train / restore round trip — the "where did
    # the latency go" split the Grafana lifecycle row charts.
    lifecycle = getattr(engine, "lifecycle", None)
    if lifecycle is not None:
        lines += lifecycle.render(label)
    # Per-train dispatch-duration histogram (fleet-perf group): one
    # family, {train=prefill|decode|decode_spec} series.
    dispatch_hists = getattr(engine, "dispatch_hists", None)
    if dispatch_hists is not None:
        lines += dispatch_hists.render(label)
    # The HTTP surface's own time: ingress and first-chunk emit.
    http_surface = getattr(engine, "http_surface", None)
    if http_surface is not None:
        lines += http_surface.render(label)
    if "moe_assignments_elsewhere_total" in s:
        # Only a model whose chip holds a SHARE of its experts counts them
        # (``ops/moe.py:STATS_EP``): every other model's series are as
        # they were.
        lines += [
            "# HELP pstpu:moe_assignments_elsewhere_total Token-expert "
            "pairs the router gave to experts another chip of the "
            "expert-parallel deployment holds: neither computed nor "
            "counted among the assignments here",
            "# TYPE pstpu:moe_assignments_elsewhere_total counter",
            f"pstpu:moe_assignments_elsewhere_total{label} "
            f"{s['moe_assignments_elsewhere_total']}",
        ]
    if "index_keys_visible_total" in s:
        # Only a model whose full layers attend what a learned indexer
        # selects counts them (models/dots3_note.py).
        lines += [
            "# HELP pstpu:index_keys_visible_total Keys the full layers' "
            "DECODE queries could see (their contexts, a layer and a "
            "row-step)",
            "# TYPE pstpu:index_keys_visible_total counter",
            f"pstpu:index_keys_visible_total{label} "
            f"{s['index_keys_visible_total']}",
            "# HELP pstpu:index_keys_selected_total Latent rows those "
            "queries' indexers selected and the step read (min(context, "
            "index_topk) a layer and a row-step)",
            "# TYPE pstpu:index_keys_selected_total counter",
            f"pstpu:index_keys_selected_total{label} "
            f"{s['index_keys_selected_total']}",
            "# HELP pstpu:index_prefill_keys_visible_total Keys the full "
            "layers' PREFILL queries could see",
            "# TYPE pstpu:index_prefill_keys_visible_total counter",
            f"pstpu:index_prefill_keys_visible_total{label} "
            f"{s['index_prefill_keys_visible_total']}",
            "# HELP pstpu:index_prefill_keys_selected_total Keys those "
            "queries' indexers selected (a chunk scores densely under the "
            "selection's mask)",
            "# TYPE pstpu:index_prefill_keys_selected_total counter",
            f"pstpu:index_prefill_keys_selected_total{label} "
            f"{s['index_prefill_keys_selected_total']}",
        ]
    return "\n".join(lines) + "\n"
