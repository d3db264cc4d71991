"""The kernel-layer numbers of a state-space hybrid decoder (Mamba-2 layers
with per-sequence state beside paged K/V in a few attention layers) from a
capture: what ``lib/roofline.py`` computes with a dense llama's arithmetic,
computed with ``lib/shapes_ssm.py``'s, and the scan's own times from the
inner scopes ``ssd_step`` / ``ssd_chunk`` / ``state_write`` / ``state_read``
each device operation carries on its ``tf_op`` path.

Steps are counted IN the capture (paged-kernel calls over the attention
layers: one call an attention layer a step). Rows are LIVE row-steps: the
ratio of the program's own counters over the traced seconds,
(``pstpu:decode_row_steps_total`` - ``pstpu:decode_row_steps_wasted_total``)
/ ``pstpu:decode_steps_total``, times the capture's steps: a row-step that
delivers nothing moves no state, so counting it would read high
(``readers/hybrid_trace.py`` does, by up to a tenth). The chunkwise scan's
tokens are those of the capture's PAIRED prefill dispatches
(``readers/prefill_tokens.py:of``: the spans' own ``tokens``), not a
counter's delta, which counts a prompt when its request ends; a dispatch
the capture's end cuts adds device time and no token, so the share errs
low.

One reduction a run, kept in the run's context; a field is ``None`` (and
its metric left out) where the capture, the counters or the scopes hold
nothing to read: a CPU rehearsal, a program without the scopes (the parent
of the PR that added them).
"""

from statistics import fmean

from benchmarks.chip.lib import roofline, shapes, shapes_ssm, spans, xplane
from benchmarks.chip.readers import prefill_tokens
from benchmarks.chip.readers.hybrid_trace import _peak

INNER = ("ssd_step", "ssd_chunk", "state_write", "state_read")


def inner_seconds(path: str) -> dict:
    """Device seconds under each inner scope, every instant given to one
    operation (``spans.exclusive_seconds``)."""
    scopes = spans.op_scopes(path)
    per_op = spans.exclusive_seconds(spans.read_events(path)["ops"])
    out = dict.fromkeys(INNER, 0.0)
    for name, seconds in per_op.items():
        parts = (scopes.get(name) or "").split("/")
        for scope in INNER:
            if scope in parts:
                out[scope] += seconds
                break
    out["busy_s"] = sum(per_op.values())
    return out


def reduce(ctx: dict) -> dict:
    info = ctx.get("trace_info") or {}
    dirs = info.get("dirs") or []
    path = xplane.find(dirs[0]) if dirs else None
    cfg = ctx["model_config"]
    if path is None or "mamba" not in cfg.get("layer_types", ()):
        return {}
    first = xplane.reduce(path)
    if not first.get("devices"):
        return {}
    counters = info.get("counters") or {}
    out = {}
    decode_s = first["programs"].get(roofline.DECODE_PROGRAM, 0.0)
    kernel_calls = sum(v for k, v in first["counts"].items()
                       if k.startswith(roofline.ATTENTION_OPS))
    steps = kernel_calls / cfg["layer_types"].count("attention")
    counted = counters.get("pstpu:decode_steps_total", 0)
    live = counters.get("pstpu:decode_row_steps_total", 0) \
        - counters.get("pstpu:decode_row_steps_wasted_total", 0)
    row_steps = steps * live / counted if counted else 0.0
    peak = _peak()
    ok = [r for r in ctx["results"] if r.ok]
    if steps and decode_s:
        out["decode_step_ms"] = 1e3 * decode_s / steps
    if steps and decode_s and peak and ok and row_steps:
        context = fmean(r.request.prompt_tokens
                        + r.request.output_tokens / 2 for r in ok)
        least = shapes.least_seconds(shapes_ssm.decode_step(
            cfg, row_steps / steps, context), peak)
        out["decode_roofline_pct"] = 100.0 * steps * least["seconds"] \
            / decode_s
    inner = inner_seconds(path)
    if inner["busy_s"] and any(inner[s] for s in INNER):
        out["ssd_share_pct"] = 100.0 * sum(inner[s] for s in INNER) \
            / inner["busy_s"]
    if peak and row_steps and inner["ssd_step"]:
        least = shapes.least_seconds(
            shapes_ssm.ssd_step(cfg, row_steps), peak)
        out["ssd_step_roofline_pct"] = 100.0 * least["seconds"] \
            / inner["ssd_step"]
    prefills = prefill_tokens.of(ctx)
    if peak and prefills and prefills.get("tokens") and inner["ssd_chunk"]:
        least = shapes.least_seconds(
            shapes_ssm.ssd_chunk(cfg, prefills["tokens"]), peak)
        out["ssd_chunk_roofline_pct"] = 100.0 * least["seconds"] \
            / inner["ssd_chunk"]
    if isinstance(ctx.get("trace"), dict) and steps:
        ctx["trace"].setdefault("notes", []).append(
            f"ssm_trace: {steps:.0f} steps, {row_steps / steps:.2f} live "
            f"rows a step, ssd_step {inner['ssd_step']:.4f} s, ssd_chunk "
            f"{inner['ssd_chunk']:.4f} s, state_read "
            f"{inner['state_read']:.4f} s, state_write "
            f"{inner['state_write']:.4f} s of busy {inner['busy_s']:.3f} s")
    return out


def read(ctx, field):
    if "_ssm_trace" not in ctx:
        try:
            ctx["_ssm_trace"] = reduce(ctx)
        except Exception as e:  # noqa: BLE001 — a capture this cannot read
            ctx["_ssm_trace"] = {}
            if isinstance(ctx.get("trace"), dict):
                ctx["trace"].setdefault("notes", []).append(
                    f"ssm_trace: capture not read "
                    f"({type(e).__name__}: {e})")
    return ctx["_ssm_trace"].get(field)
