"""What holds the device's memory (docs/OBSERVABILITY.md, "What holds the
HBM"): a ledger of residents by holder and of what each dispatch program
adds, and the allocator's high-water mark put on the dispatch that raised
it.

The runner owns one ``MemoryLedger`` and gives it a reading function
(``ModelRunner.device_memory``: per device of the mesh its
``memory_stats()`` or ``{}``). The ledger reads at the two places the
engine loop already leaves the event loop, after a dispatch's enqueue
(``flight_recorder.annotated_issue``) and after its sync
(``flight_recorder.annotated``), and before and after each enqueue of
warm-up's execute pass. Where the allocator's ``peak_bytes_in_use`` rose
since the last read it records ONE event: which dispatch, which programs
were in flight with it, what ``memory_stats()`` said, and how much of the
new peak is explained by what is resident (the ledger's residents and the
programs loaded since). What a read returns is what the loop's
executor-side spans carry (``hbm``, ``hbm_peak``, ``hbm_limit``,
``hbm_reserved``, ``hbm_explained``).

What the allocator's count shows of a program, on a TPU v5e (PERF.md
section 6, PR 49): at its first run its CODE, which stays, and its outputs;
never its temporaries, which the runtime keeps in one scratch region the
size of the largest program's (``bytes_reserved``, outside
``bytes_in_use`` and its peak).

A backend whose devices report nothing (the CPU) makes every read ``{}``:
residents still come from array sizes, there are no events and no span
attributes, and nothing raises.
"""

import logging
import threading
from collections import deque
from typing import Callable, Dict, List, Optional, Tuple

logger = logging.getLogger(__name__)

# Holders of resident bytes, in the order reports list them. "other" is
# what the allocator has in use beyond the arrays the runner names.
HOLDERS = ("weights", "kv", "state", "spec", "lora", "other")
PHASES = ("warmup", "serving")
# compiled.memory_analysis() fields kept a program, under these names.
ANALYSIS_FIELDS = {
    "temp_bytes": "temp_size_in_bytes",
    "argument_bytes": "argument_size_in_bytes",
    "output_bytes": "output_size_in_bytes",
    "alias_bytes": "alias_size_in_bytes",
    "generated_code_bytes": "generated_code_size_in_bytes",
}


def fullest(readings: List[dict]) -> Tuple[int, dict]:
    """(index, reading) of the device with the most bytes in use: THE
    definition of the fullest device (the first where they tie or say
    nothing). ``(0, {})`` where there is no reading at all."""
    best = 0
    for i, reading in enumerate(readings):
        if reading.get("bytes_in_use", 0) > \
                readings[best].get("bytes_in_use", 0):
            best = i
    return (best, readings[best]) if readings else (0, {})


def analysis_of(compiled) -> Dict[str, int]:
    """``compiled.memory_analysis()`` under the ledger's names ({} where
    the backend gives none)."""
    mem = compiled.memory_analysis()
    if mem is None:
        return {}
    return {name: int(getattr(mem, attr, 0))
            for name, attr in ANALYSIS_FIELDS.items()}


class MemoryLedger:
    """See the module docstring. ``read()`` returns one dict a device.

    Reads come from the warm-up thread and then from the dispatch
    executor's threads, one at a time (the loop awaits each issue and
    each fetch); ``snapshot()`` comes from a handler's thread. One lock
    covers both."""

    def __init__(self, read: Callable[[], List[dict]],
                 keep: int = 64) -> None:
        self._read = read
        self._lock = threading.Lock()
        self.phase = "warmup"
        # holder -> bytes on the fullest device, and the same a device.
        self.residents: Dict[str, int] = {}
        self.residents_by_device: Dict[str, Dict[str, int]] = {}
        self.device: Optional[str] = None
        self.other_arrays: List[dict] = []
        # The ``state`` holder's bytes on the fullest device by the name
        # its module declares each pool under (models/config.py:StateSpec).
        self.state_pools: Dict[str, int] = {}
        self.built: Dict[str, int] = {}
        # What the ledger expects ``bytes_in_use`` to read: the read before
        # a warm-up enqueue, then the read the ledger was built at, each
        # plus what the programs first run since have loaded.
        self.resident_bytes = 0
        self._quiet = False
        # program key -> {"kind", "family", ..., "held_bytes", ...}
        self.programs: Dict[str, dict] = {}
        self._in_flight: Dict[int, str] = {}
        self._peak: Optional[int] = None
        self.events: deque = deque(maxlen=keep)
        self.events_dropped = 0
        self.rises = dict.fromkeys(PHASES, 0)
        self.rise_bytes = dict.fromkeys(PHASES, 0)

    # ------------------------------------------------------------ reads
    def reading(self) -> dict:
        """The fullest device's ``memory_stats()`` now ({}: none)."""
        return fullest(self._read())[1]

    def quiet(self) -> None:
        """A read right before an enqueue (warm-up's): what is in use now
        is what is resident, so the next ``issued`` measures its program
        exactly."""
        now = self.reading()
        if not now:
            return
        with self._lock:
            self._quiet = True
            self.resident_bytes = int(now.get("bytes_in_use", 0))
            self._first_read(now)

    def issued(self, step: int, program: dict, rows: int,
               compiled: float = 0.0) -> dict:
        """Read after dispatch ``step``'s enqueue. ``program`` says what
        was enqueued: ``key`` and whatever else the ledger should keep
        (``kind``, ``family``, the variant). Returns the span's
        attributes ({} without a reading)."""
        now = self.reading()
        key = program["key"]
        with self._lock:
            exact, self._quiet = self._quiet, False
            company = list(self._in_flight.values())
            self._in_flight[step] = key
            if not now:
                return {}
            self._first_read(now)
            entry = self._entry(program)
            if "held_bytes" not in entry:
                # A program's first run loads its code, which stays: what
                # the count shows of it is resident from here on.
                held = int(now.get("bytes_in_use", 0)) - self.resident_bytes
                entry.update(held_bytes=held, measured=self.phase,
                             **({} if exact else {"in_company": company}))
                self.resident_bytes += held
            self._rise(now, step, "issue", key, rows, compiled,
                       company + [key])
            explained = self.resident_bytes
        return {**self._said(now), "hbm_explained": explained}

    def fetched(self, step: int) -> dict:
        """Read after dispatch ``step``'s sync; it is in flight no
        longer. Returns the span's attributes."""
        now = self.reading()
        with self._lock:
            in_flight = list(self._in_flight.values())
            key = self._in_flight.pop(step, None)
            if not now:
                return {}
            self._first_read(now)
            self._rise(now, step, "fetch", key, None, 0.0, in_flight)
        return self._said(now)

    def _entry(self, program: dict) -> dict:
        """The ledger's entry of this program, made at its first sight."""
        entry = self.programs.get(program["key"])
        if entry is None:
            entry = self.programs[program["key"]] = {
                k: v for k, v in program.items() if k != "key"}
        return entry

    @staticmethod
    def _said(now: dict) -> dict:
        return {"hbm": int(now.get("bytes_in_use", 0)),
                "hbm_peak": int(now.get("peak_bytes_in_use", 0)),
                "hbm_limit": int(now.get("bytes_limit", 0)),
                "hbm_reserved": int(now.get("bytes_reserved", 0))}

    def _first_read(self, now: dict) -> None:
        """The ledger's first reading: what the process reached before it
        (weights arriving, the pools, a compile) is no dispatch's rise,
        and is kept as the first event, ``at`` ``boot``, counted in no
        phase's rises."""
        if self._peak is not None:
            return
        self._peak = int(now.get("peak_bytes_in_use", 0))
        in_use = int(now.get("bytes_in_use", 0))
        self.events.append({
            "step": None, "phase": self.phase, "at": "boot", "kind": None,
            "family": None, "in_flight": [], "compiled": 0.0,
            "rose_by": self._peak, **self._numbers(now),
            "explained": in_use, "unexplained": self._peak - in_use})
        logger.info(
            "HBM before the first dispatch: %.3f GB in use, peak %.3f GB "
            "of %.3f (largest allocation %.3f GB)", in_use / 1e9,
            self._peak / 1e9, now.get("bytes_limit", 0) / 1e9,
            now.get("largest_alloc_size", 0) / 1e9)

    @staticmethod
    def _numbers(now: dict) -> Dict[str, int]:
        return {k: int(v) for k, v in now.items()
                if isinstance(v, (int, float))}

    def _rise(self, now: dict, step: int, at: str, key: Optional[str],
              rows: Optional[int], compiled: float,
              in_flight: List[str]) -> None:
        peak = int(now.get("peak_bytes_in_use", 0))
        if peak <= self._peak:
            return
        by, self._peak = peak - self._peak, peak
        explained = self.resident_bytes
        event = {
            "step": step, "phase": self.phase, "at": at,
            "kind": (self.programs.get(key) or {}).get("kind"),
            "family": key, **({} if rows is None else {"rows": rows}),
            "in_flight": in_flight, "compiled": compiled, "rose_by": by,
            **self._numbers(now),
            "explained": explained, "unexplained": peak - explained,
        }
        if len(self.events) == self.events.maxlen:
            self.events_dropped += 1
        self.events.append(event)
        self.rises[self.phase] += 1
        self.rise_bytes[self.phase] += by
        logger.info(
            "HBM peak rose by %.3f GB to %.3f GB of %.3f (%s, step %s, "
            "at %s of %s; in flight %s; compiled %.3f s; explained "
            "%.3f GB, unexplained %+.3f GB)",
            by / 1e9, peak / 1e9, now.get("bytes_limit", 0) / 1e9,
            self.phase, step, at, key, in_flight, compiled,
            explained / 1e9, (peak - explained) / 1e9)

    def rise_at(self, step: int, at: str) -> Optional[dict]:
        """What the flight recorder's event of this step says
        (``hbm_rise``) where that read raised the peak."""
        with self._lock:
            last = self.events[-1] if self.events else None
        if last is None or last["step"] != step or last["at"] != at \
                or last["phase"] != "serving":  # a warm-up step: an ordinal
            return None
        return {"by": last["rose_by"], "to": last["peak_bytes_in_use"],
                "unexplained": last["unexplained"]}

    # ------------------------------------------------------- the ledger
    def analysed(self, program: dict, analysis: Dict[str, int]) -> None:
        """Attach a compiled program's ``memory_analysis()`` to its
        family (entered if the allocator has not measured it yet)."""
        if not analysis:
            return
        with self._lock:
            self._entry(program).update(analysis)

    def build(self, residents_by_device: Dict[str, Dict[str, int]],
              other_arrays: List[dict],
              state_pools: Optional[Dict[str, int]] = None) -> None:
        """``start()`` has ended: enter the residents (one entry a device,
        in the reading function's order; ``other`` is entered here, bytes
        in use less the named holders) and call what follows serving."""
        readings = self._read()
        index, now = fullest(readings)
        labels = list(residents_by_device)
        with self._lock:
            for i, label in enumerate(labels):
                named = residents_by_device[label]
                in_use = readings[i].get("bytes_in_use") \
                    if i < len(readings) else None
                named["other"] = max(0, int(in_use) - sum(named.values())) \
                    if in_use is not None \
                    else sum(a["bytes"] for a in other_arrays
                             if a["device"] == label)
            self.residents_by_device = residents_by_device
            self.device = labels[index] if labels else None
            self.residents = dict(residents_by_device.get(self.device, {}))
            self.other_arrays = [a for a in other_arrays
                                 if a["device"] == self.device]
            self.state_pools = dict(state_pools or {})
            self.built = self._numbers(now)
            self.phase = "serving"
            self._quiet = False
            if now:
                self.resident_bytes = int(now.get("bytes_in_use", 0))
                self._first_read(now)

    def snapshot(self) -> dict:
        """The ``GET /debug/memory`` body less the current reading."""
        with self._lock:
            return {
                "device": self.device,
                "phase": self.phase,
                "residents": dict(self.residents),
                "residents_by_device": {
                    d: dict(h) for d, h in self.residents_by_device.items()},
                "resident_bytes": self.resident_bytes,
                "built": dict(self.built),
                "other_arrays": list(self.other_arrays),
                "state_pools": dict(self.state_pools),
                "programs": {k: dict(v) for k, v in self.programs.items()},
                "events": list(self.events),
                "events_dropped": self.events_dropped,
                "rises": dict(self.rises),
                "rise_bytes": dict(self.rise_bytes),
            }
