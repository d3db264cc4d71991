"""What the program's own spans say of the device's memory (PR 49): the
engine reads the allocator after every dispatch's enqueue and after its
sync, in the executor thread, and puts what it read on the capture's
``pstpu.issue.enqueue`` and ``pstpu.fetch.sync`` spans: ``hbm`` (bytes in
use on the fullest device of the engine's mesh), ``hbm_peak`` (the
allocator's high-water mark since the process started), ``hbm_limit``,
and on ``pstpu.issue.enqueue`` ``hbm_explained`` (what the residents of
the engine's memory ledger and the programs then in flight account for).
The program knows what it holds; this reader does no arithmetic about a
model.

``field`` names the metric:

* ``high_water_gb``: the largest ``hbm_peak`` of the capture, in GB: the
  peak since process start as of the capture, program temporaries in it
  (``hbm_peak_gb`` reads what is left after the window);
* ``headroom_pct``: 100 x (``hbm_limit`` - that ``hbm_peak``) /
  ``hbm_limit``, from the same span: what is left before an allocation
  fails;
* ``unexplained_gb``: at the enqueue span with the largest ``hbm``,
  (``hbm`` - ``hbm_explained``) in GB, signed: bytes in use under traffic
  that neither a resident nor a program in flight accounts for.

Nothing (``None``, no exception) without a capture and where no span
carries ``hbm_peak`` (a program that predates the attributes; a CPU
rehearsal, whose devices report no memory)."""

from typing import List, Optional

from benchmarks.chip.lib import spans, xplane

SPANS = ("pstpu.issue.enqueue", "pstpu.fetch.sync")


def reduce(found: List[dict]) -> Optional[dict]:
    """The three values from a capture's spans, or ``None`` where none of
    them carries ``hbm_peak``."""
    said = [s for s in found if s["name"] in SPANS and "hbm_peak" in s]
    if not said:
        return None
    top = max(said, key=lambda s: int(s["hbm_peak"]))
    peak, limit = int(top["hbm_peak"]), int(top.get("hbm_limit", 0))
    out = {"spans": len(said), "high_water_gb": peak / 1e9,
           "headroom_pct": 100.0 * (limit - peak) / limit if limit else None,
           "unexplained_gb": None}
    explained = [s for s in said if "hbm_explained" in s and "hbm" in s]
    if explained:
        fullest = max(explained, key=lambda s: int(s["hbm"]))
        out["unexplained_gb"] = (
            int(fullest["hbm"]) - int(fullest["hbm_explained"])) / 1e9
        out["at_step"] = int(fullest.get("step", -1))
        out["in_use_gb"] = int(fullest["hbm"]) / 1e9
    return out


def of(ctx: dict) -> Optional[dict]:
    """The reduction of the run's first capture, made once a run."""
    if "_hbm_spans" in ctx:
        return ctx["_hbm_spans"]
    ctx["_hbm_spans"] = got = None
    dirs = (ctx.get("trace_info") or {}).get("dirs") or []
    path = xplane.find(dirs[0]) if dirs else None
    if path is None:
        return None
    try:
        ctx["_hbm_spans"] = got = reduce(spans.read_events(path)["spans"])
        said = [] if got is None else [
            "hbm_spans: %d spans say the allocator's reading; most in use "
            "%.3f GB at step %d" % (got["spans"], got["in_use_gb"],
                                    got["at_step"])
            if got["unexplained_gb"] is not None else
            "hbm_spans: %d spans say the allocator's reading" % got["spans"]]
    except Exception as e:  # noqa: BLE001 — a capture this cannot read
        said = [f"hbm_spans: capture not read ({type(e).__name__}: {e})"]
    if isinstance(ctx.get("trace"), dict):
        ctx["trace"].setdefault("notes", []).extend(said)
    return got


def read(ctx, field):
    got = of(ctx)
    return got[field] if got else None
