"""On-demand device profiling: ``POST /debug/profile`` arms
``jax.profiler.trace`` for a bounded window (docs/OBSERVABILITY.md).

The roofline work (docs/PERF.md) attributes host gaps vs device time from
aggregate counters; a perfetto capture is the per-dispatch timeline that
settles the attribution. One capture at a time, bounded duration, and
404-clean when profiling is unavailable (jax.profiler missing or debug
endpoints disabled) — production routers probing /debug must see a plain
404, never a crash.

What a capture holds: the device planes (XLA modules and operations, each
operation's ``tf_op`` carrying its ``jax.named_scope`` path), and on the
host planes the engine loop's ``pstpu.*`` spans (engine/flight_recorder.py:
LoopSpans) beside the runtime's own events — NOT every Python frame: the
Python tracer is off unless the caller asks for ``python_frames``, which
multiplies the host planes' size and the time the stop takes. A
``pstpu.clock`` annotation written as the capture starts carries the wall
and monotonic clocks' readings, so flight-recorder and OTLP times (wall
clock) can be laid on the capture's axis.
"""

import asyncio
import os
import tempfile
import time
from typing import Optional

from production_stack_tpu.utils import init_logger

logger = init_logger(__name__)

MAX_CAPTURE_SECONDS = 300.0


class ProfilerBusy(RuntimeError):
    """A capture is already in flight (one at a time — overlapping
    jax.profiler.start_trace calls abort the first capture)."""


class DeviceProfiler:
    """Arms jax.profiler.trace for a bounded window and stops it from a
    scheduled task, so a forgotten capture can never run forever."""

    def __init__(self, default_dir: Optional[str] = None):
        self.default_dir = default_dir
        self.active: Optional[dict] = None
        self.last: Optional[dict] = None
        # The stop task handle is kept (and cancelled on close) so the
        # bounded window survives handler returns without leaking a task.
        self._stop_task: Optional[asyncio.Task] = None

    @staticmethod
    def available() -> bool:
        try:
            import jax.profiler  # noqa: F401 — availability probe
        except Exception:  # noqa: BLE001 — any import failure = unavailable
            return False
        import jax.profiler as jp

        return hasattr(jp, "start_trace") and hasattr(jp, "stop_trace")

    async def arm(self, duration_s: float,
                  trace_dir: Optional[str] = None,
                  python_frames: bool = False) -> dict:
        """Start a capture; a background task stops it after
        ``duration_s``. Raises ProfilerBusy while one is in flight."""
        import jax.profiler as jp

        if self.active is not None:
            raise ProfilerBusy(
                f"a capture into {self.active['trace_dir']!r} is already "
                f"running"
            )
        duration_s = min(max(0.1, float(duration_s)), MAX_CAPTURE_SECONDS)
        trace_dir = trace_dir or self.default_dir or tempfile.mkdtemp(
            prefix="pstpu-profile-"
        )
        os.makedirs(trace_dir, exist_ok=True)
        options = jp.ProfileOptions()
        options.python_tracer_level = 1 if python_frames else 0
        options.host_tracer_level = 2
        jp.start_trace(trace_dir, profiler_options=options)
        # The clock anchor: one event on the capture's axis that names the
        # wall and monotonic clocks' readings at that instant.
        with jp.TraceAnnotation("pstpu.clock", wall_ns=time.time_ns(),
                                mono_ns=time.monotonic_ns()):
            pass
        self.active = {
            "trace_dir": trace_dir,
            "duration_s": duration_s,
            "python_frames": bool(python_frames),
            "started_at": time.time(),
        }
        self._stop_task = asyncio.get_running_loop().create_task(
            self._stop_after(duration_s)
        )
        logger.info("Device profiling armed: dir=%s duration=%.1fs",
                    trace_dir, duration_s)
        return dict(self.active)

    async def _stop_after(self, duration_s: float) -> None:
        try:
            await asyncio.sleep(duration_s)
        finally:
            await self._finish_capture()

    async def _finish_capture(self) -> None:
        """Stop the capture in the executor: ``stop_trace`` collects the
        planes and writes the file, which takes seconds, and the event
        loop (SSE streams, /health, the engine loop itself) must not wait
        for it. ``active`` stays set until the file is written, so a
        second arm meanwhile is refused as busy."""
        if self.active is None or self.active.get("stopping"):
            return
        import jax.profiler as jp

        info = self.active
        info["stopping"] = True
        stop_began = time.time()
        try:
            await asyncio.get_running_loop().run_in_executor(
                None, jp.stop_trace)
        except Exception:  # noqa: BLE001 — a failed stop must not wedge arm
            logger.exception("jax.profiler.stop_trace failed")
            info["error"] = "stop_trace failed"
        finally:
            # Also on cancellation (engine shutdown while the file is
            # being written): never leave the profiler wedged as busy.
            del info["stopping"]
            info.update(stopped_at=time.time(),
                        stop_seconds=round(time.time() - stop_began, 3))
            self.active = None
            self.last = info
        logger.info("Device profiling capture complete: %s (stop took "
                    "%.2fs)", info["trace_dir"], info["stop_seconds"])

    def status(self) -> dict:
        return {
            "available": self.available(),
            "active": dict(self.active) if self.active else None,
            "last": dict(self.last) if self.last else None,
        }

    async def close(self) -> None:
        """Stop any in-flight capture (engine shutdown). A stop that is
        already writing its file is waited for, not cancelled: the process
        must not exit under it."""
        task, self._stop_task = self._stop_task, None
        if task is not None and not task.done():
            if not (self.active or {}).get("stopping"):
                task.cancel()       # still asleep: its finally stops now
            try:
                await task
            except asyncio.CancelledError:
                pass
        await self._finish_capture()
