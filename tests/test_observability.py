"""Observability pack consistency: every Grafana panel query and
prometheus-adapter rule names a series the router/engine ACTUALLY exports
(VERDICT r2: the reference dashboard was 'ahead of the code'; ours must not
be). Exported series are scraped live from the real /metrics renderers."""

import json
import os
import re

import yaml

BASE = os.path.join(os.path.dirname(__file__), "..", "observability")


def _exported_series():
    """Render real /metrics output from both tiers and collect series names."""
    from production_stack_tpu.server.metrics import render_engine_metrics

    class _FakeSched:
        num_running = 1
        num_waiting = 0
        num_preemptions_total = 0

    class _FakeBM:
        def usage(self):
            return 0.5
        prefix_hits_total = 3
        prefix_queries_total = 7

    from production_stack_tpu.engine.metrics import (
        DispatchDurationHistograms,
        LifecycleHistograms,
        RequestLatencyHistograms,
    )

    class _FakeEngine:
        scheduler = _FakeSched()
        block_manager = _FakeBM()
        prompt_tokens_total = 10
        generation_tokens_total = 20
        histograms = RequestLatencyHistograms()
        lifecycle = LifecycleHistograms()
        dispatch_hists = DispatchDurationHistograms()

        def stats(self):
            return {
                "num_requests_running": 1, "num_requests_waiting": 0,
                "kv_cache_usage": 0.5, "prefix_cache_hits": 3,
                "prefix_cache_queries": 7, "num_preemptions": 0,
                "prompt_tokens_total": 10, "generation_tokens_total": 20,
                "decode_dispatches_total": 5, "prefill_dispatches_total": 2,
                "dispatch_overlap_ratio": 0.5,
                "dispatch_gap_seconds_total": 0.1,
            }

    text = render_engine_metrics(_FakeEngine(), "m")
    series = set(re.findall(r"^((?:vllm|pstpu):[a-z_]+)", text, re.M))
    # Router series from its gauge registry. prometheus_client appends
    # _total to Counter names, so both spellings count as exported.
    from production_stack_tpu.router import metrics as router_metrics

    src = open(router_metrics.__file__).read()
    declared = set(re.findall(r'"((?:vllm:|pstpu:|router_)[a-z_]+)"', src))
    series |= declared
    series |= {f"{name}_total" for name in declared
               if not name.endswith("_total")}
    return series


def _metric_names(expr):
    return set(re.findall(r"((?:vllm:|pstpu:|router_)[a-z_]+)", expr))


def test_dashboard_queries_name_exported_series():
    with open(os.path.join(BASE, "grafana-dashboard.json")) as f:
        dash = json.load(f)
    exported = _exported_series()
    n_targets = 0
    for panel in dash["panels"]:
        for target in panel.get("targets", []):
            n_targets += 1
            used = _metric_names(target["expr"])
            assert used, f"panel {panel['title']} target has no vllm series"
            missing = used - exported
            assert not missing, (
                f"panel {panel['title']!r} queries unexported series "
                f"{missing}; exported: {sorted(exported)}"
            )
    assert n_targets >= 12
    # KV-economy panels (docs/KV_ECONOMY.md): shared-tier hit rate and the
    # router's measured per-backend hit rate are charted, not just
    # exported.
    all_series = set()
    for panel in dash["panels"]:
        for target in panel.get("targets", []):
            all_series |= _metric_names(target["expr"])
    assert {"pstpu:kv_shared_tier_hits_total",
            "pstpu:kv_shared_tier_misses_total",
            "router_backend_kv_hit_rate"} <= all_series
    # Request-lifecycle row (docs/OBSERVABILITY.md): the per-phase
    # histograms and the spans-dropped counters are charted, not just
    # exported.
    assert {"pstpu:queue_wait_seconds_bucket",
            "pstpu:prefill_seconds_bucket",
            "pstpu:decode_train_seconds_bucket",
            "pstpu:restore_round_trip_seconds_bucket",
            "pstpu:trace_spans_dropped_total",
            "router_trace_spans_dropped_total"} <= all_series
    lifecycle_titles = [p["title"] for p in dash["panels"]
                        if p["title"].startswith("Request lifecycle")]
    assert len(lifecycle_titles) >= 3, lifecycle_titles
    # Fleet-performance row (docs/OBSERVABILITY.md): the live roofline
    # gauges and the router's fleet aggregate are charted, not just
    # exported.
    assert {"pstpu:live_tok_per_s",
            "pstpu:live_hbm_bw_pct",
            "pstpu:live_effective_tokens_per_target_step",
            "pstpu:dispatch_duration_seconds_bucket",
            "pstpu:host_stall_seconds_total",
            "router_fleet_live_tok_per_s",
            "router_fleet_live_hbm_bw_pct",
            "router_fleet_breaker_open",
            "router_fleet_ramp_in_penalty",
            "router_fleet_backends"} <= all_series
    fleet_titles = [p["title"] for p in dash["panels"]
                    if p["title"].startswith("Fleet performance")]
    assert len(fleet_titles) >= 3, fleet_titles


def test_prom_adapter_rule_names_exported_series():
    with open(os.path.join(BASE, "prom-adapter.yaml")) as f:
        cfg = yaml.safe_load(f)
    exported = _exported_series()
    rules = cfg["rules"]["custom"]
    assert len(rules) >= 3   # legacy waiting gauge + the autoscaler pair
    for rule in rules:
        series = _metric_names(rule["seriesQuery"])
        assert len(series) == 1, rule["seriesQuery"]
        assert series <= exported, (series, sorted(exported))
        # Adapter naming convention: the Prometheus series with ':'
        # replaced (k8s metric names cannot carry colons).
        assert rule["name"]["as"] == series.pop().replace(":", "_")
    # The helm HPA stanzas' default metric names must be servable by
    # these rules (docs/SOAK.md: values-only autoscaling wiring).
    served = {r["name"]["as"] for r in rules}
    assert {"pstpu_queue_depth", "router_queue_depth"} <= served
    # KV-economy rules (docs/KV_ECONOMY.md): the router's measured
    # per-backend hit rate and the shared-tier hit counter.
    assert {"router_backend_kv_hit_rate",
            "pstpu_kv_shared_tier_hits_total"} <= served
    # Fleet-performance rules (docs/OBSERVABILITY.md): delivered tokens/s
    # and roofline position as autoscaler-consumable Object metrics.
    assert {"router_fleet_live_tok_per_s",
            "router_fleet_live_hbm_bw_pct"} <= served


def test_latency_histograms_scrape():
    """Engine /metrics exports the vLLM-named TTFT/e2e histogram buckets
    the dashboard's distribution panels query, with sane cumulative counts
    (VERDICT r4 #5); the router registry exports its own distributions."""
    from production_stack_tpu.engine.metrics import RequestLatencyHistograms
    from production_stack_tpu.server.metrics import render_engine_metrics

    class _E:
        histograms = RequestLatencyHistograms()

        def stats(self):
            return {
                "num_requests_running": 0, "num_requests_waiting": 0,
                "kv_cache_usage": 0.0, "prefix_cache_hits": 0,
                "prefix_cache_queries": 0, "num_preemptions": 0,
                "prompt_tokens_total": 0, "generation_tokens_total": 0,
            }

    e = _E()
    for v in (0.03, 0.3, 3.0):
        e.histograms.ttft.observe(v)
        e.histograms.e2e.observe(v)
    text = render_engine_metrics(e, "m")
    assert 'vllm:time_to_first_token_seconds_bucket{model_name="m",le="+Inf"} 3' in text
    assert 'vllm:e2e_request_latency_seconds_bucket{model_name="m",le="+Inf"} 3' in text
    assert "vllm:time_to_first_token_seconds_count" in text
    assert "vllm:e2e_request_latency_seconds_sum" in text
    # cumulative monotonicity across buckets
    counts = [
        int(m.group(1)) for m in re.finditer(
            r'vllm:time_to_first_token_seconds_bucket\{[^}]*\} (\d+)', text
        )
    ]
    assert counts == sorted(counts) and counts[-1] == 3

    # router-side distributions register + observe
    from production_stack_tpu.router import metrics as rm

    rm.router_ttft_seconds.labels(server="http://e1").observe(0.2)
    rm.router_e2e_latency_seconds.labels(server="http://e1").observe(1.2)
    from prometheus_client import generate_latest

    scraped = generate_latest().decode()
    assert "vllm:router_ttft_seconds_bucket" in scraped
    assert "vllm:router_e2e_latency_seconds_bucket" in scraped


def test_request_stats_monitor_feeds_histograms():
    """The router's TTFT/complete hooks observe into the histogram series."""
    from prometheus_client import generate_latest

    from production_stack_tpu.router.stats.request_stats import (
        RequestStatsMonitor,
    )

    mon = RequestStatsMonitor(sliding_window_size=10.0)
    url = "http://hist-engine"
    mon.on_new_request(url, "r1", 100.0)
    mon.on_request_response(url, "r1", 100.4)
    mon.on_request_complete(url, "r1", 101.5)
    scraped = generate_latest().decode()
    assert f'vllm:router_ttft_seconds_count{{server="{url}"}} 1.0' in scraped
    assert (
        f'vllm:router_e2e_latency_seconds_count{{server="{url}"}} 1.0'
        in scraped
    )


def test_lifecycle_histograms_render():
    """The four pstpu lifecycle phase histograms render with cumulative
    buckets on the engine's /metrics (docs/OBSERVABILITY.md)."""
    from production_stack_tpu.engine.metrics import LifecycleHistograms
    from production_stack_tpu.server.metrics import render_engine_metrics

    class _E:
        lifecycle = LifecycleHistograms()

        def stats(self):
            return {
                "num_requests_running": 0, "num_requests_waiting": 0,
                "kv_cache_usage": 0.0, "prefix_cache_hits": 0,
                "prefix_cache_queries": 0, "num_preemptions": 0,
                "prompt_tokens_total": 0, "generation_tokens_total": 0,
            }

    e = _E()
    e.lifecycle.queue_wait.observe(0.02)
    e.lifecycle.prefill.observe(0.3)
    e.lifecycle.decode_train.observe(0.05)
    e.lifecycle.decode_train.observe(0.07)
    e.lifecycle.restore_round_trip.observe(0.004)
    text = render_engine_metrics(e, "m")
    for name, count in (("pstpu:queue_wait_seconds", 1),
                        ("pstpu:prefill_seconds", 1),
                        ("pstpu:decode_train_seconds", 2),
                        ("pstpu:restore_round_trip_seconds", 1)):
        assert f'{name}_bucket{{model_name="m",le="+Inf"}} {count}' in text
        assert f"{name}_count" in text
        # cumulative monotonicity per series
        counts = [
            int(m.group(1)) for m in re.finditer(
                name.replace(":", r"\:") + r'_bucket\{[^}]*\} (\d+)', text
            )
        ]
        assert counts == sorted(counts) and counts[-1] == count
    for name, count in (("pstpu:queue_wait_seconds", 1),
                        ("pstpu:decode_train_seconds", 2)):
        assert f'{name}_count{{model_name="m"}} {count}' in text
    assert "# TYPE pstpu:trace_spans_dropped_total counter" in text


def test_hpa_consumes_adapter_metric():
    with open(os.path.join(BASE, "hpa.yaml")) as f:
        hpa = yaml.safe_load(f)
    assert hpa["kind"] == "HorizontalPodAutoscaler"
    metric = hpa["spec"]["metrics"][0]["pods"]["metric"]["name"]
    with open(os.path.join(BASE, "prom-adapter.yaml")) as f:
        cfg = yaml.safe_load(f)
    advertised = {r["name"]["as"] for r in cfg["rules"]["custom"]}
    assert metric in advertised
    assert hpa["spec"]["minReplicas"] >= 1
    assert hpa["spec"]["maxReplicas"] >= hpa["spec"]["minReplicas"]
