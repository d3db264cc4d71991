"""Device seconds of the capture's prefill dispatches over the tokens
those dispatches carried, times ``scale``.

A prefill's ``pstpu.issue`` span says what the dispatch holds (``tokens``,
the sum of its chunks, and ``prog_rows`` x ``prog_t``, the rectangle its
program computes); the device plane holds the ``jit__prefill_impl`` run
that computed it. Both sides of the ratio are the SAME dispatches, which a
counter's delta over the capture is not (``vllm:prompt_tokens_total``
counts a prompt when its request ends, seconds after its prefill ran).

``lib/spans.py:pair`` takes only dispatches whose fetch blocks on the
device, which leaves out every prefill none of whose rows ended its prompt
(a chunk of a long prompt); this reader pairs for itself: every prefill
issue span of the capture, in ``step`` order, with the first not yet taken
run that starts after the span began (less ``CLOCK_TOL_S``; the device
runs one stream in issue order). A span with no such run before the
capture ends, and a run that began before the first span, are left out on
both sides.

Nothing (``None``, no exception) without a capture, where the spans carry
no ``tokens`` (a program that predates them), and where fewer than
``MIN_PAIRED`` of the capture's prefill spans pair, the ones the capture's
end cuts (at most ``PIPELINE_DEPTH``) not counted against it. The run's notes say how
many paired, per program ``[prog_rows, prog_t]`` the dispatches, their
mean device seconds and their tokens, and beside them what stopped the
window's admission passes (the six ``pstpu:prefill_stop_*_total``)."""

from collections import defaultdict
from typing import Dict, List, Optional, Tuple

from benchmarks.chip.lib import roofline, spans, xplane

# Below this share of paired spans the ratio would rest on wrong pairs.
MIN_PAIRED = 0.9
# The capture's end cuts the dispatches issued and not yet run: as many as
# the engine loop keeps in flight. They are the capture's edge, not a
# failure to pair: a 4 s capture of a saturated cell holds 6-10 prefill
# dispatches, each queued some tenths of a second behind decode trains,
# and the last is cut more often than not.
PIPELINE_DEPTH = 2


def prefill_issues(found: List[dict]) -> List[dict]:
    """The capture's prefill ``pstpu.issue`` spans that say their tokens,
    in ``step`` order."""
    return sorted(
        (s for s in found if s["name"] == "pstpu.issue"
         and str(s.get("kind")) == "prefill" and "tokens" in s
         and "step" in s),
        key=lambda s: int(s["step"]))


def pair(issues: List[dict], runs: List[spans.Interval]) -> List[Tuple]:
    """``[(span, run)]``: each span with the first run not yet taken that
    starts after the span began."""
    pairs, i = [], 0
    for span in issues:
        while i < len(runs) and runs[i][0] < span["start"] - spans.CLOCK_TOL_S:
            i += 1
        if i == len(runs):
            break
        pairs.append((span, runs[i]))
        i += 1
    return pairs


def reduce(events: dict) -> Optional[dict]:
    issues = prefill_issues(events["spans"])
    runs = sorted(events["programs"].get(roofline.PREFILL_PROGRAM, []))
    if not issues or not runs:
        return None
    pairs = pair(issues, runs)
    cut = min(len(issues) - len(pairs), PIPELINE_DEPTH)
    out = {"issues": len(issues), "paired": len(pairs), "runs": len(runs),
           "cut": cut}
    if len(pairs) < MIN_PAIRED * (len(issues) - cut):
        return out
    by_program: Dict[Tuple[int, int], List[float]] = defaultdict(
        lambda: [0, 0.0, 0])
    for span, (start, end) in pairs:
        entry = by_program[(int(span["prog_rows"]), int(span["prog_t"]))]
        entry[0] += 1
        entry[1] += end - start
        entry[2] += int(span["tokens"])
    out["by_program"] = dict(by_program)
    out["device_s"] = sum(e[1] for e in by_program.values())
    out["tokens"] = sum(e[2] for e in by_program.values())
    return out


def notes(got: Optional[dict]) -> List[str]:
    if not got:
        return []
    lines = [f"prefill_tokens: paired {got['paired']} of {got['issues']} "
             f"prefill dispatches ({got['cut']} cut by the capture's end), "
             f"{got['runs']} runs in the capture"
             + ("" if "tokens" in got else
                f", under {MIN_PAIRED:.0%} of the rest: no metric")]
    if "by_program" in got:
        lines.append(
            "device seconds a prefill dispatch by program: " + ", ".join(
                f"[{rows},{t}] x{n} mean {seconds / n:.4f} s "
                f"{tokens} tokens"
                for (rows, t), (n, seconds, tokens)
                in sorted(got["by_program"].items())))
    return lines


STOPS = ("rows", "seqs", "tokens", "window", "slots", "blocks")


def admission_notes(counters: dict) -> List[str]:
    """The window's prefill dispatches by what stopped their admission
    pass (``pstpu:prefill_stop_*_total``, which no metric reads; passes
    that scheduled nothing while requests waited are in the same six)."""
    stops = {s: counters.get(f"pstpu:prefill_stop_{s}_total")
             for s in STOPS}
    if all(v is None for v in stops.values()):
        return []
    return ["admission over the window: %.0f prefill dispatches of %.0f "
            "rows; passes stopped by " % (
                counters.get("pstpu:prefill_dispatches_total", 0),
                counters.get("pstpu:prefill_rows_issued_total", 0))
            + ", ".join(f"{s} {v or 0:.0f}" for s, v in stops.items())]


def of(ctx: dict) -> Optional[dict]:
    """The reduction of the run's first capture, made once a run."""
    if "_prefill_tokens" in ctx:
        return ctx["_prefill_tokens"]
    ctx["_prefill_tokens"] = got = None
    dirs = (ctx.get("trace_info") or {}).get("dirs") or []
    path = xplane.find(dirs[0]) if dirs else None
    if path is None:
        return None
    try:
        ctx["_prefill_tokens"] = got = reduce(spans.read_events(path))
        said = notes(got) + admission_notes(ctx.get("counters") or {})
    except Exception as e:  # noqa: BLE001 — a capture this cannot read
        said = [f"prefill_tokens: capture not read "
                f"({type(e).__name__}: {e})"]
    if isinstance(ctx.get("trace"), dict):
        ctx["trace"].setdefault("notes", []).extend(said)
    return got


def read(ctx, scale=1.0):
    got = of(ctx)
    if not got or not got.get("tokens"):
        return None
    return scale * got["device_s"] / got["tokens"]
