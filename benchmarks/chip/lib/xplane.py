"""Reduction of a profiler capture (``*.xplane.pb``) to what the metrics
read: device busy and idle time, device time per operation and per program,
and the idle gaps named by the programs around them and by what the host
was doing. Reads the file with ``jax.profiler.ProfileData`` (JAX only; no
device is touched, and run.py calls this after the engines have exited)."""

import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# An idle gap shorter than this is the device's own turn-around between
# two operations, not something the host could fill.
GAP_FLOOR_S = 20e-6
TOP = 10

Event = Tuple[str, float, float]   # name, start s, end s


def find(trace_dir: str) -> Optional[str]:
    """The newest capture under ``<dir>/plugins/profile/<time>/`` (where
    the profiler writes it) or in ``<dir>`` itself."""
    found = sorted(
        glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                               "*.xplane.pb"))
        + glob.glob(os.path.join(trace_dir, "*.xplane.pb")),
        key=os.path.getmtime)
    return found[-1] if found else None


def load(path: str) -> Dict[str, Dict[str, List[Event]]]:
    """``{plane: {line: [(name, start s, end s), ...]}}``, events by start."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from jax.profiler import ProfileData

    planes: Dict[str, Dict[str, List[Event]]] = {}
    for plane in ProfileData.from_file(path).planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            events = lines.setdefault(line.name, [])
            for ev in line.events:
                start = ev.start_ns * 1e-9
                events.append((ev.name, start, start + ev.duration_ns * 1e-9))
            events.sort(key=lambda e: e[1])
    return planes


def union_seconds(events: List[Event]) -> float:
    """Length of the union of the events' intervals (they may nest)."""
    total, reach = 0.0, None
    for _, start, end in sorted(events, key=lambda e: e[1]):
        if reach is None or start > reach:
            total += end - start
            reach = end
        elif end > reach:
            total += end - reach
            reach = end
    return total


def self_times(events: List[Event]) -> Dict[str, float]:
    """Seconds per operation name, a parent's time less its children's
    (a ``while`` holds its body's operations on the same line)."""
    out: Dict[str, float] = defaultdict(float)
    stack: List[list] = []           # [name, end, start, children's seconds]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            name, end, start, kids = stack.pop()
            out[name] += (end - start) - kids
            if stack:
                stack[-1][3] += end - start

    for name, start, end in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        stack.append([name, end, start, 0.0])
    close(float("inf"))
    return dict(out)


def op_label(name: str) -> str:
    """The profiler names a device operation by its whole HLO line,
    ``%fusion.138 = bf16[8,11008]{1,0:T(8,128)} fusion(...)``: keep the
    operation's name and its first result's type and dimensions,
    ``fusion.138 bf16[8,11008]``."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name[:80]
    shape = re.search(r"[a-z]+[0-9]*\[[0-9,]*\]", rest)
    return (head.lstrip("%") + (" " + shape.group(0) if shape else ""))[:80]


def program_of(name: str) -> str:
    """``jit__decode_impl(1234567)`` -> ``jit__decode_impl``."""
    return re.sub(r"\(\d+\)$", "", name)


def host_activity(planes, start: float, end: float) -> str:
    """The host event that covers most of [start, end)."""
    best, best_cover = "", 0.0
    for plane, lines in planes.items():
        if not plane.startswith("/host:"):
            continue
        for events in lines.values():
            for name, s, e in events:
                if e <= start:
                    continue
                if s >= end:
                    break
                cover = min(e, end) - max(s, start)
                if cover > best_cover:
                    best, best_cover = name, cover
    return best


def reduce(path: str) -> dict:
    """Busy and idle time, per-operation and per-program device seconds,
    idle gaps; averaged over the device planes found."""
    planes = load(path)
    devices = {n: l for n, l in planes.items() if DEVICE_PLANE.match(n)}
    # The window is the span the DEVICE planes cover: the host keeps
    # writing events while the capture is being stopped, when the device
    # is no longer traced, and counting that as idle time would be wrong.
    # Idle time before the first and after the last device event of a
    # capture cannot be seen; at 4 s that is at most one gap.
    everything = [e for lines in (devices or planes).values()
                  for evs in lines.values() for e in evs]
    if not everything:
        return {"devices": 0, "busy_s": 0.0, "window_s": 0.0}
    t_lo = min(e[1] for e in everything)
    t_hi = max(e[2] for e in everything)
    out = {"devices": len(devices), "window_s": t_hi - t_lo}
    busy, ops, programs, counts = 0.0, defaultdict(float), \
        defaultdict(float), defaultdict(int)
    gaps: Dict[str, float] = defaultdict(float)
    for lines in devices.values():
        op_events = [(op_label(n), s, e) for n, s, e in
                     lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []]
        busy += union_seconds(op_events)
        for name, seconds in self_times(op_events).items():
            ops[name] += seconds
        for name, start, end in op_events:
            counts[name] += 1
        modules = lines.get(MODULES_LINE, [])
        for name, start, end in modules:
            programs[program_of(name)] += end - start
            counts["program:" + program_of(name)] += 1
        # Walk the programs by start, keeping the latest end seen (small
        # programs run inside the span of large ones): a gap opens where
        # the next program starts after everything before it has ended.
        before, reach, short = "", t_lo, 0.0
        for after, a_start, a_end in [*modules, ("", t_hi, t_hi)]:
            gap = a_start - reach
            if gap >= GAP_FLOOR_S:
                doing = host_activity(planes, reach, a_start)
                gaps[f"{program_of(before) or 'start'}>"
                     f"{program_of(after) or 'end'}|host:{doing or '?'}"] += gap
            elif gap > 0:
                short += gap
            if a_end >= reach:
                before, reach = after, a_end
        if short:
            gaps["shorter_gaps"] += short
    n = max(1, len(devices))
    out.update(
        busy_s=busy / n,
        ops={k: v / n for k, v in ops.items()},
        programs={k: v / n for k, v in programs.items()},
        counts=dict(counts),
        breakdown={
            "device_ops": [[k, v / n] for k, v in sorted(
                ops.items(), key=lambda kv: -kv[1])[:TOP]],
            "idle_gaps": [[k, v / n] for k, v in sorted(
                gaps.items(), key=lambda kv: -kv[1])[:TOP]],
        },
    )
    return out
