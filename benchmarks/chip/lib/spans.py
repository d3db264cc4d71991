"""What the program's own instrumentation in a capture says (PR 24): the
engine loop's ``pstpu.*`` spans on the host planes, paired with the
programs on the device plane, and the ``jax.named_scope`` path each device
operation carries in its metadata (``tf_op``).

``lib/xplane.py`` reads names and times only; the spans' attributes are
event stats and the scope is a stat of the event METADATA, which
``jax.profiler.ProfileData`` does not show, so this module makes one
``ProfileData`` pass of its own for the events and one walk of the file's
protobuf wire format for the metadata (field numbers of tsl's
``xplane.proto``; no dependency). A capture without spans or scopes (a
program that predates them, a CPU rehearsal without a device plane) reads
as nothing: every function returns ``None`` and raises nothing."""

import os
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

from benchmarks.chip.lib import roofline, xplane

PROGRAMS = {"prefill": roofline.PREFILL_PROGRAM,
            "decode": roofline.DECODE_PROGRAM}
SCOPES = ("embed", "attn_proj", "attn_core", "ffn", "logits", "sample",
          "kv_write")
# Below this share of paired dispatches a mean would rest on wrong pairs.
MIN_MATCHED = 0.9
# The device's clock is laid on the host's to within some tenths of a
# millisecond (a program was seen to start 0.16 ms before its enqueue
# began; my chip run, PR 24).
CLOCK_TOL_S = 1e-3

Interval = Tuple[float, float]


# ------------------------------------------------------------ the events
def read_events(path: str) -> dict:
    """``{"spans": [{"name", "start", "end", <attributes>}],
    "programs": {program: [(start, end)]}, "ops": [(name, start, end)]}``
    of the first device plane and all host planes, times in seconds."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from jax.profiler import ProfileData

    spans: List[dict] = []
    programs: Dict[str, List[Interval]] = defaultdict(list)
    ops: List[xplane.Event] = []
    device = None
    for plane in ProfileData.from_file(path).planes:
        if xplane.DEVICE_PLANE.match(plane.name):
            if device is not None:
                continue
            device = plane.name
            for line in plane.lines:
                if line.name == xplane.MODULES_LINE:
                    for ev in line.events:
                        start = ev.start_ns * 1e-9
                        programs[xplane.program_of(ev.name)].append(
                            (start, start + ev.duration_ns * 1e-9))
                elif line.name == xplane.OPS_LINE:
                    for ev in line.events:
                        start = ev.start_ns * 1e-9
                        ops.append((ev.name, start,
                                    start + ev.duration_ns * 1e-9))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if not ev.name.startswith("pstpu."):
                        continue
                    start = ev.start_ns * 1e-9
                    span = {k: v for k, v in ev.stats}
                    span.update(name=ev.name, start=start,
                                end=start + ev.duration_ns * 1e-9)
                    spans.append(span)
    for runs in programs.values():
        runs.sort()
    spans.sort(key=lambda s: s["start"])
    return {"spans": spans, "programs": dict(programs), "ops": ops,
            "device": device}


# ----------------------------------------------------- spans <-> programs
def dispatches(spans: List[dict]) -> List[dict]:
    """One entry per ``step``: kind, whether its fetch blocks on the device
    (``sync``), and the intervals of its ``pstpu.issue`` / ``pstpu.fetch``
    spans and their executor-side parts, where the capture holds them."""
    by_step: Dict[int, dict] = {}
    parts = {"pstpu.issue": "issue", "pstpu.issue.enqueue": "enqueue",
             "pstpu.fetch": "fetch", "pstpu.fetch.sync": "sync_part"}
    for span in spans:
        part = parts.get(span["name"])
        if part is None or "step" not in span:
            continue
        d = by_step.setdefault(int(span["step"]), {"step": int(span["step"])})
        d[part] = (span["start"], span["end"])
        if "kind" in span:
            d["kind"] = str(span["kind"])
        if part == "fetch":
            d["sync"] = int(span.get("sync", 1))
    return [by_step[k] for k in sorted(by_step)]


def pair(found: List[dict], programs: Dict[str, List[Interval]]) -> dict:
    """Each COMPLETED dispatch (issue and fetch spans both in the capture,
    the fetch one that blocks on the device) with its program on the
    device: the latest run of the kind's program that began after the
    issue began and ended before the fetch span did, taken once. The
    runtime's ``run_id`` is on the device's events and on its own host
    events but not on an annotation, so the pairing is by time. A capture
    cut mid-dispatch leaves that dispatch incomplete, not mispaired."""
    completed = [d for d in found if d.get("sync") and "issue" in d
                 and "fetch" in d and d.get("kind") in PROGRAMS]
    taken = set()
    pairs = []
    for d in completed:
        runs = programs.get(PROGRAMS[d["kind"]], [])
        best = None
        for i, (start, end) in enumerate(runs):
            if start < d["issue"][0] - CLOCK_TOL_S:
                continue
            if end > d["fetch"][1] + CLOCK_TOL_S:
                break
            if (d["kind"], i) not in taken:
                best = i
        if best is None:
            continue
        taken.add((d["kind"], best))
        pairs.append((d, runs[best]))
    return {"completed": len(completed), "pairs": pairs}


def reduce_spans(events: dict) -> Optional[dict]:
    """Means over the paired dispatches, or ``None`` where fewer than
    ``MIN_MATCHED`` of the completed ones could be paired."""
    found = dispatches(events["spans"])
    if not found or not events["programs"]:
        return None
    paired = pair(found, events["programs"])
    completed, pairs = paired["completed"], paired["pairs"]
    if not completed:
        return None
    out = {"completed": completed, "matched": len(pairs),
           "matched_share": len(pairs) / completed}
    if out["matched_share"] < MIN_MATCHED:
        return out

    def mean(values):
        values = list(values)
        return sum(values) / len(values) if values else None

    # An idle device starts a program while the issue span is still
    # closing its books: no wait, not a negative one.
    out["prefill_device_wait_s"] = mean(
        max(0.0, run[0] - d["issue"][1])
        for d, run in pairs if d["kind"] == "prefill")
    out["fetch_lag_s"] = mean(
        max(0.0, d["fetch"][1] - run[1]) for d, run in pairs)
    with_part = [(d, run) for d, run in pairs if "sync_part" in d]
    out["device_sync_s"] = mean(
        max(0.0, d["sync_part"][1] - run[1]) for d, run in with_part)
    out["executor_hop_s"] = mean(
        max(0.0, d["fetch"][1] - d["sync_part"][1]) for d, run in with_part)
    return out


# ------------------------------------------------------------ the scopes
def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if not b & 0x80:
            return value, i
        shift += 7


def _fields(buf: bytes) -> List[Tuple[int, object]]:
    """(field number, value) of one protobuf message; a nested message or
    a string comes as bytes."""
    i, out = 0, []
    while i < len(buf):
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = buf[i:i + n], i + n
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wire}")
        out.append((number, value))
    return out


def _first(fields, number, default=None):
    for n, value in fields:
        if n == number:
            return value
    return default


def op_scopes(path: str) -> Dict[str, str]:
    """``{operation's event name: tf_op}`` of the device planes: XSpace.1
    planes; XPlane.2 name, .4 event_metadata (map value .2 XEventMetadata:
    .2 name, .5 stats), .5 stat_metadata (map value .2: .2 name);
    XStat.1 metadata_id, .5 str_value, .7 ref_value."""
    with open(path, "rb") as f:
        space = _fields(f.read())
    out: Dict[str, str] = {}
    for number, raw in space:
        if number != 1:
            continue
        plane = _fields(raw)
        name = (_first(plane, 2) or b"").decode(errors="replace")
        if not xplane.DEVICE_PLANE.match(name):
            continue
        stat_names = {}
        for n, entry in plane:
            if n == 5:
                e = _fields(entry)
                stat_names[_first(e, 1, 0)] = (_first(
                    _fields(_first(e, 2, b"")), 2) or b"").decode()
        for n, entry in plane:
            if n != 4:
                continue
            meta = _fields(_first(_fields(entry), 2, b""))
            for m, stat_raw in meta:
                if m != 5:
                    continue
                stat = _fields(stat_raw)
                if stat_names.get(_first(stat, 1)) != "tf_op":
                    continue
                value = _first(stat, 5)
                if value is None:
                    value = stat_names.get(_first(stat, 7), "").encode()
                out[(_first(meta, 2) or b"").decode(errors="replace")] = \
                    value.decode(errors="replace")
    return out


def scope_of(tf_op: Optional[str]) -> Optional[str]:
    """The innermost of the seven scopes on an operation's path, if any:
    ``jit(_decode_impl)/while/body/ffn/dot_general:`` -> ``ffn``."""
    if not tf_op:
        return None
    for part in reversed(tf_op.split("/")):
        if part in SCOPES:
            return part
    return None


def exclusive_seconds(ops: List[xplane.Event]) -> Dict[str, float]:
    """Seconds per operation name with every instant given to ONE
    operation, the one that started last among those running: a ``while``
    keeps what its body leaves, and where two operations overlap without
    nesting (an asynchronous copy under the next fusion) the later one has
    the overlap. The times sum to the union of the intervals, and none is
    negative (``xplane.self_times`` takes a child's whole length off its
    parent, overlap or not)."""
    out: Dict[str, float] = defaultdict(float)
    stack: List[xplane.Event] = []
    cursor = 0.0

    def run_until(t: float) -> None:
        nonlocal cursor
        while stack:
            name, _, end = stack[-1]
            if end <= cursor:              # ended under a later operation
                stack.pop()
            elif end <= t:
                out[name] += end - cursor
                cursor = end
                stack.pop()
            else:
                out[name] += t - cursor
                break
        cursor = max(cursor, t)

    for op in sorted(ops, key=lambda e: (e[1], -e[2])):
        run_until(op[1])
        stack.append(op)
    run_until(float("inf"))
    return dict(out)


TOP_UNSCOPED = 8


def reduce_scopes(events: dict, scopes: Dict[str, str]) -> Optional[dict]:
    """Device seconds per scope over busy time, and the operations that
    weigh most under no scope."""
    ops = events["ops"]
    if not ops:
        return None
    per_op = exclusive_seconds(ops)
    busy = sum(per_op.values())
    if not busy:
        return None
    seconds: Dict[str, float] = defaultdict(float)
    unscoped = {}
    for name, op_s in per_op.items():
        scope = scope_of(scopes.get(name))
        seconds[scope or "unscoped"] += op_s
        if scope is None:
            unscoped[name] = op_s
    return {"busy_s": busy, "seconds": dict(seconds),
            "scoped_ops": sum(1 for v in scopes.values() if scope_of(v)),
            "top_unscoped": [[xplane.op_label(k), v] for k, v in sorted(
                unscoped.items(), key=lambda kv: -kv[1])[:TOP_UNSCOPED]]}


# ------------------------------------------------------------- per run
def of(ctx: dict) -> dict:
    """The reduction of the run's first capture, made once and kept in the
    run's context: ``{"spans": ... or None, "scopes": ... or None}``. What
    was found goes to the result line's trace notes."""
    if "_pstpu_spans" in ctx:
        return ctx["_pstpu_spans"]
    out = {"spans": None, "scopes": None}
    ctx["_pstpu_spans"] = out
    dirs = (ctx.get("trace_info") or {}).get("dirs") or []
    path = xplane.find(dirs[0]) if dirs else None
    if path is None:
        return out
    notes = []
    try:
        events = read_events(path)
        out["spans"] = reduce_spans(events)
        out["scopes"] = reduce_scopes(events, op_scopes(path))
    except Exception as e:  # noqa: BLE001 — a capture this cannot read
        notes.append(f"spans: capture not read ({type(e).__name__}: {e})")
    spans, scopes = out["spans"], out["scopes"]
    if spans:
        notes.append(
            f"spans: paired {spans['matched']} of {spans['completed']} "
            f"completed dispatches"
            + ("" if "fetch_lag_s" in spans else
               f", under {MIN_MATCHED:.0%}: no span metric")
            + "".join(
                f", {label} {spans[key] * 1e3:.3f} ms"
                for key, label in (("device_sync_s", "device sync"),
                                   ("executor_hop_s", "executor hop"))
                if spans.get(key) is not None))
    if scopes:
        if not scopes["scoped_ops"]:
            notes.append("scopes: no operation carries a scope (programs "
                         "compiled before the scopes existed?)")
        notes.append("scopes, s of busy %.3f: " % scopes["busy_s"] + ", ".join(
            f"{k} {v:.3f}" for k, v in sorted(
                scopes["seconds"].items(), key=lambda kv: -kv[1])))
        notes.append("unscoped, s: " + ", ".join(
            f"{k} {v:.3f}" for k, v in scopes["top_unscoped"]))
    notes += loop_notes(ctx)
    if isinstance(ctx.get("trace"), dict):
        ctx["trace"].setdefault("notes", []).extend(notes)
    return out


LOOP_COUNTERS = ("schedule", "issue", "fetch_wait", "apply", "idle", "other")


def loop_notes(ctx: dict) -> List[str]:
    """Two identities the program's counters should keep over the window:
    the loop's six phases tile it, and row-steps less wasted row-steps are
    the tokens decode delivered (all tokens less each request's first)."""
    counters, notes = ctx.get("counters") or {}, []
    phases = [counters.get(f"pstpu:loop_{p}_seconds_total")
              for p in LOOP_COUNTERS]
    if all(p is not None for p in phases) and ctx.get("span_s"):
        notes.append(
            f"loop: phases sum to {sum(phases):.3f} s of "
            f"{ctx['span_s']:.3f} s ({100 * sum(phases) / ctx['span_s']:.2f}"
            f"%): " + ", ".join(f"{n} {v:.3f}"
                                for n, v in zip(LOOP_COUNTERS, phases)))
    rows = counters.get("pstpu:decode_row_steps_total")
    if rows is not None:
        kept = rows - counters.get("pstpu:decode_row_steps_wasted_total", 0)
        decoded = counters.get("vllm:generation_tokens_total", 0) \
            - counters.get("vllm:time_to_first_token_seconds_count", 0)
        notes.append(f"decode: {rows:.0f} row-steps, {kept:.0f} kept, "
                     f"{decoded:.0f} tokens decoded")
    return notes
