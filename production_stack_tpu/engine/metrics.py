"""Histograms behind the engine's /metrics.

``server/metrics.py:render_engine_metrics`` is the one renderer of the
engine's series (``api_server`` serves its text). This module holds the
histograms that renderer emits: a minimal cumulative ``Histogram`` and the
groups of them the engine and the HTTP surface observe into — request
latency (the two ``vllm:`` series the reference dashboard charts), lifecycle
phases, dispatch durations and the HTTP surface.
"""

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
