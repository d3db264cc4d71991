"""Per-request flight recorder: a bounded in-memory ring of event
timelines (docs/OBSERVABILITY.md).

When a rung misses SLO or a joiner ramps slowly, aggregate Prometheus
series cannot answer "where did request X's 1.9s TTFT go" — queue wait,
shared-tier restore, prefill, or decode-train cadence. The recorder keeps
one event timeline per recent request, appended from the engine loop's
dispatch points (enqueue, schedule, per-dispatch issue/fetch, restore
round trips, preemption, resume, handoff, finish) and served at
``GET /debug/requests/{id}`` / ``GET /debug/timeline``.

Hot-path contract: every append is an O(1) in-memory list append with a
per-request cap — no syscalls, no locks (the engine loop and the aiohttp
debug handlers share one event-loop thread), no effect on scheduling or
sampling. Bounded two ways: at most ``capacity`` request records (oldest
evicted first) and at most ``max_events`` events per record (overflow is
counted on the record, never silently lost).

The same timelines back the engine's retrospective span tree: ``phases()``
folds a record's events into queue-wait / prefill / decode / kv-restore /
handoff phase intervals the API server exports as OTLP child spans of the
request's server span (production_stack_tpu/tracing.py).

``LoopSpans`` (below) is the engine LOOP's side of the same seam: named
phase spans on the profiler's clock plus the per-phase second totals
``/metrics`` exports. A span's ``step`` is the id the ``*_issue`` /
``*_fetch`` events above carry, so a request's timeline joins the dispatch
span that caused it.
"""

import threading
import time
from collections import OrderedDict
from typing import Dict, List, Optional

try:
    from jax.profiler import TraceAnnotation
except Exception:  # noqa: BLE001 — no profiler: spans still keep totals
    import contextlib

    def TraceAnnotation(name, **attrs):  # noqa: N802 — stands in for a class
        return contextlib.nullcontext()

# Event names recorded by the engine (docs/OBSERVABILITY.md schema table).
EVENT_NAMES = (
    "enqueue", "resume", "schedule", "prefill_issue", "prefill_fetch",
    "decode_issue", "decode_fetch", "restore", "preempt",
    "handoff_restore", "handoff_publish", "finish",
)


# The engine loop's phases. Together they tile the loop's wall time
# (docs/OBSERVABILITY.md "Loop spans"): every second the loop lives is in
# exactly one of them.
# phase -> its counter on /metrics (``pstpu:<name>``) and key in stats().
LOOP_COUNTERS = {
    "schedule": "loop_schedule_seconds_total",
    "issue": "loop_issue_seconds_total",
    "fetch": "loop_fetch_wait_seconds_total",
    "apply": "loop_apply_seconds_total",
    "idle": "loop_idle_seconds_total",
    "housekeeping": "loop_other_seconds_total",
}


class _LoopSpan:
    __slots__ = ("_totals", "_phase", "_annotation", "_t0")

    def __init__(self, totals: Dict[str, float], phase: str,
                 annotation) -> None:
        self._totals = totals
        self._phase = phase
        self._annotation = annotation

    def __enter__(self) -> "_LoopSpan":
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self._totals[self._phase] += time.perf_counter() - self._t0
        self._annotation.__exit__(*exc)


class LoopSpans:
    """``with loop_span("pstpu.<phase>", step=..., kind=...):`` around one
    phase of the engine loop. Entry opens a ``jax.profiler.TraceAnnotation``
    with those attributes, so a capture armed through ``POST
    /debug/profile`` shows the phase on the device's time axis (no capture
    armed: the annotation is a no-op); exit adds the elapsed
    ``perf_counter`` time to ``seconds[<phase>]``, which ``/metrics``
    exports under ``LOOP_COUNTERS[<phase>]``.

    Spans do not nest and only the loop's own coroutine opens them: an
    ``await`` inside one lets other coroutines run on the thread, and an
    annotation of theirs would interleave with the open one."""

    def __init__(self) -> None:
        self.seconds: Dict[str, float] = dict.fromkeys(LOOP_COUNTERS, 0.0)

    def __call__(self, name: str, **attrs) -> _LoopSpan:
        return _LoopSpan(self.seconds, name.rpartition(".")[2],
                         TraceAnnotation(name, **attrs))

    def counters(self) -> Dict[str, float]:
        return {LOOP_COUNTERS[phase]: seconds
                for phase, seconds in self.seconds.items()}


def annotated(name: str, step: int, fn, *args, memory=None):
    """``fn(*args)`` under a ``TraceAnnotation``: the part of a loop span
    that runs in the executor thread (``pstpu.fetch.sync``) gets an event
    of its own there, with the span's ``step``, which is what separates
    the blocking device call from the executor hop around it. With the
    runner's ``memory`` ledger the event also carries what the allocator
    says once the call is back (``MemoryLedger.fetched``: ``hbm``,
    ``hbm_peak``, ``hbm_limit``; nothing where the device reports none).
    (``run_in_executor`` passes no keywords: bind ``memory`` with
    ``functools.partial``.)"""
    with TraceAnnotation(name, step=step) as span:
        try:
            return fn(*args)
        finally:
            # A fetch that raised is in flight no longer either.
            said = memory.fetched(step) if memory is not None else None
            if said and span is not None:
                span.set_metadata(**said)


class CompileClock:
    """Seconds this PROCESS spent tracing, lowering and compiling (or
    loading from the persistent cache) jitted programs, and how many
    programs that was: one ``jax.monitoring`` listener over the three
    durations JAX records under ``/jax/core/compile/``. The last,
    ``backend_compile_duration``, spans the persistent cache's lookup too,
    so a deferred variant's first-use load counts like a true compile and
    ``/jax/compilation_cache/cache_retrieval_time_sec`` is not added
    again; a call that finds its program compiled records nothing. A
    program the runner loads from its own store (engine/program_store.py)
    never enters JAX's compile path: the runner says so (``loaded``).

    The clock runs from the first ``compile_clock()`` on; an engine
    exports what it read past its own warm-up
    (``pstpu:serving_compile*``: engine.py takes the reading when
    ``start()`` ends). Two engines in one process (tests) see each
    other's compiles. The listener runs in whichever thread compiles
    (the dispatch executor, as a rule): two adds under a lock."""

    COMPILED = "/jax/core/compile/backend_compile_duration"
    DURATIONS = frozenset((
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration", COMPILED))

    def __init__(self) -> None:
        self._lock = threading.Lock()   # any thread may compile
        self.seconds = 0.0
        self.count = 0

    def _on_duration(self, event: str, duration: float, **_kw) -> None:
        if event in self.DURATIONS:
            with self._lock:
                self.seconds += duration
                self.count += event == self.COMPILED

    def loaded(self, seconds: float) -> None:
        """A stored program was loaded without JAX's compile path (the
        runner's program store): one program, these seconds."""
        with self._lock:
            self.seconds += seconds
            self.count += 1

    def reading(self) -> tuple:
        """``(programs, seconds)`` so far."""
        with self._lock:
            return self.count, self.seconds

    def since(self, reading: tuple) -> float:
        """Seconds the clock moved since ``reading`` where a program was
        compiled or loaded meanwhile (0.0 otherwise: tracing alone)."""
        count, seconds = self.reading()
        return round(seconds - reading[1], 6) if count != reading[0] else 0.0


_COMPILE_CLOCK: Optional[CompileClock] = None


def compile_clock() -> CompileClock:
    """The process's one ``CompileClock``, registered at first use."""
    global _COMPILE_CLOCK
    if _COMPILE_CLOCK is None:
        clock = CompileClock()
        try:
            from jax import monitoring

            monitoring.register_event_duration_secs_listener(
                clock._on_duration)
        except Exception:  # noqa: BLE001 — no monitoring: it reads 0
            pass
        _COMPILE_CLOCK = clock
    return _COMPILE_CLOCK


def annotated_issue(step: int, fn, *args, memory=None):
    """``annotated("pstpu.issue.enqueue", ...)`` that also says whether the
    call compiled: returns ``(fn(*args), seconds)``, the seconds the
    compile clock moved by where a program was compiled or loaded during
    the call (0.0 otherwise), and puts them on the span as ``compiled``.
    The loop awaits each issue, so no other runner call interleaves; a
    handler thread that jits something meanwhile would be counted in.
    With the runner's ``memory`` ledger the span also carries what the
    allocator says right after the enqueue (``MemoryLedger.issued``:
    ``hbm``, ``hbm_peak``, ``hbm_limit``, ``hbm_explained``); ``fn`` then
    returns a ``DispatchHandle``, which names its program and rows."""
    clock = compile_clock()
    with TraceAnnotation("pstpu.issue.enqueue", step=step) as span:
        before = clock.reading()
        out = fn(*args)
        compiled = clock.since(before)
        said = memory.issued(step, out.program, out.rows, compiled) \
            if memory is not None else {}
        if compiled:
            said = {**said, "compiled": compiled}
        if said and span is not None:
            span.set_metadata(**said)
    return out, compiled


class FlightRecord:
    """One request's timeline. Events are (wall_time_s, name, data|None)
    tuples — tuples, not dicts, to keep the hot-path append allocation
    small and the JSON rendering explicit."""

    __slots__ = ("request_id", "created", "events", "finished",
                 "events_dropped", "meta")

    def __init__(self, request_id: str, meta: Optional[dict] = None):
        self.request_id = request_id
        self.created = time.time()
        self.events: List[tuple] = []
        self.finished = False
        self.events_dropped = 0
        self.meta = meta or {}

    def to_dict(self) -> dict:
        return {
            "request_id": self.request_id,
            "created": self.created,
            "finished": self.finished,
            "events_dropped": self.events_dropped,
            **self.meta,
            "events": [
                {"t": round(t, 6), "event": name, **(data or {})}
                for t, name, data in self.events
            ],
            "phases": phases(self),
        }

    def summary(self) -> dict:
        last = self.events[-1] if self.events else None
        return {
            "request_id": self.request_id,
            "created": round(self.created, 6),
            "finished": self.finished,
            "num_events": len(self.events),
            "events_dropped": self.events_dropped,
            "last_event": last[1] if last else None,
            "last_event_t": round(last[0], 6) if last else None,
            **self.meta,
        }


class FlightRecorder:
    """Bounded ring of FlightRecords keyed by engine request id, with an
    alias index so the router-visible ``x-request-id`` (and the OpenAI
    response id) resolve to the engine-internal child request ids."""

    def __init__(self, capacity: int = 256, max_events: int = 512):
        self.capacity = max(1, capacity)
        self.max_events = max(8, max_events)
        self._records: "OrderedDict[str, FlightRecord]" = OrderedDict()
        self._aliases: "OrderedDict[str, List[str]]" = OrderedDict()
        self.records_evicted_total = 0

    # ------------------------------------------------------------ hot path
    def start(self, request_id: str, **meta) -> None:
        if request_id in self._records:
            # Re-used id (tests, resubmits): the new attempt replaces the
            # old timeline at the ring's tail.
            self._records.pop(request_id, None)
        self._records[request_id] = FlightRecord(request_id, meta or None)
        while len(self._records) > self.capacity:
            self._records.popitem(last=False)
            self.records_evicted_total += 1

    def event(self, request_id: str, name: str,
              data: Optional[dict] = None, t: Optional[float] = None) -> None:
        rec = self._records.get(request_id)
        if rec is None:
            return
        if len(rec.events) >= self.max_events:
            rec.events_dropped += 1
            return
        rec.events.append((t if t is not None else time.time(), name, data))

    def finish(self, request_id: str, reason: Optional[str] = None,
               output_tokens: int = 0) -> None:
        rec = self._records.get(request_id)
        if rec is None or rec.finished:
            return
        rec.finished = True
        # The finish event bypasses the per-record cap: a truncated
        # timeline must still show how the request ended.
        rec.events.append((time.time(), "finish", {
            "reason": reason, "output_tokens": output_tokens,
        }))

    # ------------------------------------------------------------- lookup
    def alias(self, external_id: str, request_ids: List[str]) -> None:
        """Map a client-facing id (x-request-id header / response id) to
        the engine-internal per-choice request ids."""
        if not external_id or not request_ids:
            return
        self._aliases[external_id] = list(request_ids)
        while len(self._aliases) > 2 * self.capacity:
            self._aliases.popitem(last=False)

    def get(self, key: str) -> Optional[dict]:
        """Timeline(s) for an engine request id or a client-facing alias.
        Always the same shape: {"request_id": key, "records": [...]}."""
        rec = self._records.get(key)
        if rec is not None:
            return {"request_id": key, "records": [rec.to_dict()]}
        rids = self._aliases.get(key)
        if rids:
            found = [
                self._records[rid].to_dict()
                for rid in rids if rid in self._records
            ]
            if found:
                return {"request_id": key, "records": found}
        return None

    def timeline(self, max_requests: int = 64) -> dict:
        """Most-recent request summaries (newest first) — the fleet-wide
        ``GET /debug/timeline`` view. ``max_requests <= 0`` returns none
        (a negative slice bound would INVERT the cap)."""
        recent = (list(self._records.values())[-max_requests:]
                  if max_requests > 0 else [])
        return {
            "capacity": self.capacity,
            "recorded": len(self._records),
            "records_evicted_total": self.records_evicted_total,
            "requests": [r.summary() for r in reversed(recent)],
        }


# ------------------------------------------------------------- phase tree
def phases(rec: FlightRecord) -> List[dict]:
    """Fold a record's events into phase intervals: the engine-side span
    tree (queue-wait, prefill, decode aggregated per train, kv-restore,
    handoff). Pure over the event list, so the same function backs both
    the debug endpoint and the OTLP span emission."""
    first_issue = None
    prefill_start = prefill_end = None
    decode_start = decode_end = None
    decode_trains = 0
    decode_tokens = 0
    spec_accepted = 0   # batch-level sum over trains (see decode_fetch)
    spec_drafts = 0     # batch-level drafted sum (variable under gamma)
    enqueue_t = None
    restore_tokens = 0
    restore_seconds = 0.0
    restore_start = restore_end = None
    handoff = None
    finish_t = None
    for t, name, data in rec.events:
        data = data or {}
        if name == "enqueue":
            enqueue_t = t
        elif name in ("prefill_issue", "decode_issue"):
            if first_issue is None:
                first_issue = t
            if name == "prefill_issue":
                if prefill_start is None:
                    prefill_start = t
            elif decode_start is None:
                decode_start = t
        elif name == "prefill_fetch":
            prefill_end = t
        elif name == "decode_fetch":
            decode_end = t
            decode_trains += 1
            decode_tokens += int(data.get("tokens", 0))
            # BATCH-level acceptance per train (the device commits per
            # dispatch, not per row) — the phase attr keeps the _batch
            # suffix so nobody reads it as this request's own count.
            spec_accepted += int(data.get("spec_accepted_batch", 0))
            spec_drafts += int(data.get("spec_drafts_batch", 0))
        elif name == "restore":
            secs = float(data.get("seconds", 0.0))
            restore_tokens += int(data.get("tokens", 0))
            restore_seconds += secs
            if restore_start is None:
                restore_start = t - secs
            restore_end = t
        elif name == "handoff_publish":
            handoff = {"name": "handoff", "start": round(t, 6),
                       "end": round(t, 6),
                       "attrs": {"ok": bool(data.get("ok", False))}}
        elif name == "handoff_restore":
            handoff = {"name": "handoff", "start": round(t, 6),
                       "end": round(t, 6),
                       "attrs": {"blocks": int(data.get("blocks", 0))}}
        elif name == "finish":
            finish_t = t
    out: List[dict] = []
    if enqueue_t is not None:
        # Queue wait ends at the first dispatch issue; a request that
        # never dispatched (shed/abort while waiting) waits to its end.
        end = first_issue if first_issue is not None else \
            (finish_t if finish_t is not None else enqueue_t)
        out.append({"name": "queue_wait", "start": round(enqueue_t, 6),
                    "end": round(end, 6), "attrs": {}})
    if restore_start is not None:
        out.append({
            "name": "kv_restore", "start": round(restore_start, 6),
            "end": round(restore_end, 6),
            "attrs": {"tokens": restore_tokens,
                      "seconds": round(restore_seconds, 6)},
        })
    if prefill_start is not None:
        out.append({
            "name": "prefill", "start": round(prefill_start, 6),
            "end": round(prefill_end if prefill_end is not None
                         else prefill_start, 6),
            "attrs": {},
        })
    if decode_start is not None:
        attrs: Dict[str, object] = {"trains": decode_trains,
                                    "tokens": decode_tokens}
        if spec_accepted:
            attrs["spec_accepted_batch"] = spec_accepted
        if spec_drafts:
            # Denominator companion: adaptive gamma makes the per-train
            # draft count variable, so acceptance is no longer derivable
            # from spec_accepted_batch alone.
            attrs["spec_drafts_batch"] = spec_drafts
        out.append({
            "name": "decode", "start": round(decode_start, 6),
            "end": round(decode_end if decode_end is not None
                         else decode_start, 6),
            "attrs": attrs,
        })
    if handoff is not None:
        out.append(handoff)
    return out
