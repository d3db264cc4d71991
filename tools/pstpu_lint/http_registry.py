"""Single source of truth for the stack's HTTP control surface.

The router, the engine API server, the fake engine the resilience/soak
harness runs against, and the bench clients speak a private protocol on
top of the OpenAI surface: ``x-pstpu-*``/``x-slo-*``/``x-ttft-*``/
``x-request-*`` headers, internal routes (``/disagg/prefill``,
``/prewarm``, ``/debug/*``, ``/fleet``), shed-vs-error status semantics,
and the ``pstpu`` SSE chunk payload the cross-router resume protocol
deserializes. This module is the canonical catalogue; the PL011 (header
drift), PL012 (route drift) and PL013 (status-code semantics) rules in
``rules/http_drift.py`` check the tree against it both directions, and
``gen_docs`` renders docs/HTTP_PROTOCOL.md plus the focused tables in
docs/RESILIENCE.md and docs/ROUTER_SCALE.md from it.

Planes:

  * ``router``   — production_stack_tpu/router/
  * ``engine``   — production_stack_tpu/ outside the router tier (the API
                   server, disagg, engine internals)
  * ``fake``     — tests/fake_engine.py (the harness engine; its contract
                   must track the real engine's — PL012's parity leg)
  * ``bench``    — benchmarks/ (the load/soak clients)
  * ``external`` — real API clients outside this repo; listing it means
                   no in-repo site is required for that side.
"""

from dataclasses import dataclass
from typing import Optional, Tuple

# Planes whose source the drift rules actually scan. "external" is
# documentation-only: a producers/consumers entry naming it promises
# nothing the linter can check.
SCANNED_PLANES = ("router", "engine", "fake", "bench")


@dataclass(frozen=True)
class ProtocolHeader:
    name: str                 # canonical lowercase wire name
    direction: str            # "request" | "response" | "both"
    producers: Tuple[str, ...]  # planes that set the header
    consumers: Tuple[str, ...]  # planes that read it
    shape: str                # value shape, for the docs table
    retired: bool             # True: literal may linger in comments only
    doc: str


# Every protocol header on the wire. PL011 enforces, per scanned plane
# listed: >=1 producing site (dict-literal key / headers[h] = ...) for
# each producer plane and >=1 consuming site (.get/.pop/`in`) for each
# consumer plane — a header set by the router but read nowhere on the
# engine is drift, and vice versa. Literals must be lowercase (aiohttp
# lookups are case-insensitive, greps are not).
HEADERS: Tuple[ProtocolHeader, ...] = (
    ProtocolHeader(
        "x-request-id", "both", ("external", "router", "engine"),
        ("router", "engine", "bench"),
        "opaque request id (minted router-side when absent; echoed on "
        "the response)", False,
        "End-to-end correlation id: names request-monitor entries, "
        "flight-recorder timelines, and the soak anomaly dump.",
    ),
    ProtocolHeader(
        "x-request-timeout", "request", ("external",), ("router",),
        "seconds (float; may only tighten --default-timeout)", False,
        "Per-request total budget override, measured from router ingress.",
    ),
    ProtocolHeader(
        "x-ttft-deadline", "request", ("external", "bench"), ("router",),
        "seconds (float; may only tighten --default-ttft-deadline)", False,
        "Per-request budget to the first backend byte; expiry is a 504 "
        "with kind=ttft.",
    ),
    ProtocolHeader(
        "x-slo-class", "request", ("external", "bench"), ("router",),
        "SLO class name (e.g. interactive, batch)", False,
        "Labels the request for router_slo_attainment tracking and the "
        "soak report's per-class accounting.",
    ),
    ProtocolHeader(
        "x-slo-ttft", "request", ("external", "bench"), ("router",),
        "seconds (float; soft target, no enforcement)", False,
        "Soft TTFT target the attainment fraction is computed against "
        "(docs/SOAK.md); never aborts the request.",
    ),
    ProtocolHeader(
        "x-pstpu-resume", "request", ("router",), ("engine", "fake"),
        '"1"', False,
        "Router->engine stream opt-in: attach the per-chunk pstpu resume "
        "payload. Direct API clients get pristine OpenAI chunks.",
    ),
    ProtocolHeader(
        "x-pstpu-resume-tokens", "request", ("external", "bench"),
        ("router",),
        "comma-separated output token ids", False,
        "Client->router cross-router resume: the output ids the client "
        "already holds; the peer replica splices the continuation "
        "(docs/ROUTER_SCALE.md).",
    ),
    ProtocolHeader(
        "x-pstpu-resume-seed", "request", ("external", "bench"),
        ("router",),
        "integer (the pstpu payload's seed)", False,
        "Client->router cross-router resume: the resolved sampler seed "
        "base, required for a token-identical seeded continuation.",
    ),
    ProtocolHeader(
        "x-pstpu-disagg", "request", ("router",), ("engine",),
        '"decode" (hop marker)', False,
        "Marks the decode hop of the two-hop disagg flow; the decode-role "
        "gate rejects generation requests without it.",
    ),
    ProtocolHeader(
        "x-pstpu-transfer-key", "request", ("router",), ("engine",),
        "KV-store key of the prefill handoff bundle", False,
        "Where the decode engine fetches the prefill's KV handoff "
        "manifest from the shared tier.",
    ),
    ProtocolHeader(
        "x-pstpu-endpoint", "request", ("router",), ("engine",),
        '"chat" | "completions"', False,
        "Which OpenAI surface the decode hop must answer in — the hop is "
        "always POSTed to /v1/completions internally.",
    ),
    ProtocolHeader(
        "x-pstpu-disagg-fallback", "request", ("router",), ("engine",),
        '"1"', False,
        "Marks continuation/fallback traffic that must be servable "
        "end-to-end on ANY role; unified engines ignore it, prefill/"
        "decode role gates stand down.",
    ),
)

# Lowercase header-name prefixes that may legitimately appear as bare
# literals (forward/strip-by-namespace sites in the proxy path). A
# literal exactly equal to one of these is a namespace filter, not an
# unregistered header.
HEADER_NAMESPACES = ("x-pstpu-",)

# Prefixes PL011 claims: any string literal in the scanned planes that
# looks like one of these MUST resolve to a HEADERS entry (or a
# namespace filter above).
CLAIMED_PREFIXES = ("x-pstpu-", "x-slo-", "x-ttft-", "x-request-")


@dataclass(frozen=True)
class Route:
    method: str               # "GET" | "POST" | ...
    path: str                 # aiohttp route pattern, {param} syntax
    planes: Tuple[str, ...]   # planes that must register it
    debug: bool               # must sit behind config.debug_endpoints
    internal: bool            # plane-to-plane hop: exempt from the
    #                           test-reference requirement
    test_ref: Optional[str]   # literal the test scan greps for (None:
    #                           the path itself)
    doc: str


# Every HTTP route the three servers register. PL012 enforces: every
# observed add_get/add_post is registered here for its plane and vice
# versa; debug-gating matches; every non-internal route is referenced by
# at least one file under tests/.
ROUTES: Tuple[Route, ...] = (
    Route("POST", "/v1/chat/completions", ("router", "engine", "fake"),
          False, False, None, "OpenAI chat surface (streams via SSE)."),
    Route("POST", "/v1/completions", ("router", "engine", "fake"),
          False, False, None, "OpenAI completions surface."),
    Route("POST", "/v1/embeddings", ("router", "engine", "fake"),
          False, False, None, "OpenAI embeddings surface."),
    Route("POST", "/v1/rerank", ("router", "engine", "fake"),
          False, False, None, "Rerank surface (Jina/Cohere shape)."),
    Route("POST", "/rerank", ("engine", "fake"), False, False, None,
          "Engine-level alias of /v1/rerank (vLLM compat; the router "
          "serves only the /v1 name)."),
    Route("GET", "/v1/models", ("router", "engine", "fake"),
          False, False, None,
          "Model listing; the discovery probe's readiness signal."),
    Route("GET", "/health", ("router", "engine", "fake"),
          False, False, None,
          "Readiness: 200 serving / 503 + Retry-After while draining or "
          "degraded."),
    Route("GET", "/metrics", ("router", "engine", "fake"),
          False, False, None, "Prometheus exposition (PL004's surface)."),
    Route("GET", "/prefix_index", ("engine", "fake"), False, False, None,
          "Prefix-cache block index the router's prefix-aware routing "
          "scores against."),
    Route("POST", "/prewarm", ("engine", "fake"), False, False, None,
          "Prompt prewarm push (router initialize_all fan-out)."),
    Route("GET", "/version", ("engine", "fake"), False, False, None,
          "Build version, plus what the engine runs on and how it started: "
          "mesh device platform/kind/count, attention path, interpret "
          "mode, compile-cache dir and warmup hit/miss counts."),
    Route("POST", "/disagg/prefill", ("engine",), False, True, None,
          "Internal router->engine hop 1 of the disagg flow; never "
          "client-facing."),
    Route("GET", "/debug/requests/{request_id}", ("engine",), True, False,
          "/debug/requests", "Flight-recorder per-request timeline."),
    Route("GET", "/debug/timeline", ("engine",), True, False, None,
          "Flight-recorder recent-request ring."),
    Route("POST", "/debug/profile", ("engine",), True, False,
          "/debug/profile", "Start a bounded device-profiler capture "
          "(409 while one is running)."),
    Route("GET", "/debug/profile", ("engine",), True, False,
          "/debug/profile", "Profiler capture status."),
    Route("GET", "/debug/programs", ("engine",), True, False, None,
          "Dispatch-program audit: whole-KV-pool copies and temporaries "
          "of one compiled program of each kind."),
    Route("GET", "/debug/memory", ("engine",), True, False, None,
          "What holds the device's memory: the ledger of residents by "
          "holder and of what each dispatch program held while it ran, "
          "the events that raised the allocator's peak, and every "
          "device's reading now (`?analyze=1` attaches the named "
          "programs' `memory_analysis()`)."),
    Route("GET", "/fleet", ("router",), False, False, None,
          "Fleet-wide live perf rollup (docs/OBSERVABILITY.md)."),
    Route("POST", "/v1/files", ("router",), False, False, None,
          "Files API upload (501 unless --enable-files-api)."),
    Route("GET", "/v1/files/{file_id}", ("router",), False, False,
          "/v1/files", "Files API metadata."),
    Route("GET", "/v1/files/{file_id}/content", ("router",), False, False,
          "/v1/files", "Files API content download."),
    Route("POST", "/v1/batches", ("router",), False, False, None,
          "Batch API create (501 unless --enable-batch-api)."),
    Route("GET", "/v1/batches", ("router",), False, False, None,
          "Batch API list."),
    Route("GET", "/v1/batches/{batch_id}", ("router",), False, False,
          "/v1/batches", "Batch API status."),
    Route("POST", "/v1/batches/{batch_id}/cancel", ("router",), False,
          False, "/v1/batches", "Batch API cancel."),
    Route("POST", "/fault", ("fake",), False, False, None,
          "Fault-injection control surface of the harness engine only; "
          "real engines 404 it."),
)


@dataclass(frozen=True)
class StatusCode:
    code: int
    name: str                 # the error payload's "type"
    companions: Tuple[str, ...]  # response headers every emit site must
    #                              carry (lowercase)
    server_emitted: bool      # False: client-side marker, a server emit
    #                           site is always a finding
    doc: str


# 4xx/5xx semantics. PL013 enforces: every constant-status emit site in
# the server planes uses a registered code, carries the registry's
# companion headers, and never emits a client-side marker code.
STATUS_CODES: Tuple[StatusCode, ...] = (
    StatusCode(400, "invalid_request_error", (), True,
               "Malformed body/params; also malformed cross-router "
               "resume headers (reconnect without them to restart)."),
    StatusCode(401, "unauthorized", (), True,
               "Missing/invalid API key when --api-key is set."),
    StatusCode(404, "not_found", (), True,
               "Unknown model, unknown debug handle, or a disabled "
               "debug surface."),
    StatusCode(409, "conflict", (), True,
               "Profiler busy: one bounded capture at a time."),
    StatusCode(501, "not_implemented", (), True,
               "Feature disabled by role/flags (disagg on a unified "
               "deployment, files/batch API off)."),
    StatusCode(502, "bad_gateway", (), True,
               "Retry budget exhausted on backend transport failures; "
               "carries the last failure."),
    StatusCode(503, "service_unavailable", ("retry-after",), True,
               "Intentional shed (drain, queue bound, breaker open, "
               "role gate, handoff unavailable) or not-ready health. "
               "ALWAYS carries Retry-After — clients and the soak "
               "accounting distinguish shed from failure by it."),
    StatusCode(504, "deadline_exceeded", (), True,
               "TTFT or total budget expired before/while streaming "
               "(kind labels the metric)."),
    StatusCode(599, "client_transport_error", (), False,
               "Bench-client marker for transport failures and "
               "mid-stream truncations; never emitted by a server."),
)

_STATUS_BY_CODE = {s.code: s for s in STATUS_CODES}
_HEADERS_BY_NAME = {h.name: h for h in HEADERS}


def header_for(name: str) -> Optional[ProtocolHeader]:
    return _HEADERS_BY_NAME.get(name.lower())


def status_for(code: int) -> Optional[StatusCode]:
    return _STATUS_BY_CODE.get(code)


@dataclass(frozen=True)
class PayloadKey:
    key: str
    shape: str
    doc: str


# The `pstpu` SSE chunk payload (docs/RESILIENCE.md): the state channel
# cross-router resume is built on. PL011 checks every emitter/consumer
# file speaks exactly these keys.
SSE_PAYLOAD_FIELD = "pstpu"
SSE_PAYLOAD_KEYS: Tuple[PayloadKey, ...] = (
    PayloadKey("toks", "list[int]",
               "Output token ids carried by this chunk."),
    PayloadKey("off", "int",
               "Offset of toks[0] in the full output (dedupes overlap "
               "on splice)."),
    PayloadKey("seed", "int",
               "Resolved sampler seed base; rides the wire so a "
               "cross-engine resume of an unseeded request stays "
               "deterministic."),
)

# Files that emit / parse the payload; each must mention the field name
# and every key as a string literal.
SSE_PAYLOAD_EMITTERS = (
    "production_stack_tpu/server/api_server.py",
    "tests/fake_engine.py",
)
SSE_PAYLOAD_CONSUMERS = (
    "production_stack_tpu/router/sse.py",
    "benchmarks/multi_round_qa.py",
)
