"""The kernel-layer numbers of a hybrid decoder (recurrent-state layers
beside paged K/V) from a capture: what ``lib/roofline.py`` computes with a
dense llama's arithmetic, computed with ``lib/shapes_hybrid.py``'s, and the
recurrence's own times from the inner scopes ``gdn_step`` / ``gdn_chunk`` /
``state_write`` / ``state_read`` each device operation carries on its
``tf_op`` path. Steps are counted IN the capture (paged-kernel calls over
the full-attention layers: one call a full layer a step), so that they
belong to the same seconds as the device time they divide; rows a step are
the ratio of the program's own counters over the traced seconds
(``pstpu:decode_row_steps_total`` / ``pstpu:decode_steps_total``: the two
are counted together, so their ratio does not depend on where in a train
the scrapes fell, as either delta alone does by a train of 32 steps in a
capture of five).

One reduction a run, kept in the run's context; a field is ``None`` (and
its metric left out) where the capture, the counters or the scopes hold
nothing to read: a CPU rehearsal, a program without the scopes.
"""

import os
from statistics import fmean

from benchmarks.chip.lib import roofline, shapes, shapes_hybrid, spans, xplane
from benchmarks.chip.lib.manifest import CHIP_DIR, load_json

INNER = ("gdn_step", "gdn_chunk", "state_write", "state_read")


def _peak():
    """The one device kind of ``peaks.json`` (a run's context does not say
    which device it ran on; with two kinds listed this reader cannot tell
    and reports no share)."""
    kinds = load_json(os.path.join(CHIP_DIR, "peaks.json"))["by_device_kind"]
    return next(iter(kinds.values())) if len(kinds) == 1 else None


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
    if path is None or "layer_types" not in cfg:
        return {}
    first = xplane.reduce(path)
    if not first.get("devices"):
        return {}
    counters = info.get("counters") or {}
    out = {}
    decode_s = first["programs"].get(roofline.DECODE_PROGRAM, 0.0)
    kernel_calls = sum(v for k, v in first["counts"].items()
                       if k.startswith(roofline.ATTENTION_OPS))
    steps = kernel_calls / cfg["layer_types"].count("full_attention")
    counted = counters.get("pstpu:decode_steps_total", 0)
    row_steps = steps * counters.get(
        "pstpu:decode_row_steps_total", 0) / counted if counted else 0.0
    peak = _peak()
    ok = [r for r in ctx["results"] if r.ok]
    if steps and decode_s:
        out["decode_step_ms"] = 1e3 * decode_s / steps
    if steps and decode_s and peak and ok:
        context = fmean(r.request.prompt_tokens
                        + r.request.output_tokens / 2 for r in ok)
        least = shapes.least_seconds(shapes_hybrid.decode_step(
            cfg, row_steps / steps, context), peak)
        out["decode_roofline_pct"] = 100.0 * steps * least["seconds"] \
            / decode_s
    inner = inner_seconds(path)
    if inner["busy_s"] and any(inner[s] for s in INNER):
        out["gdn_share_pct"] = 100.0 * sum(inner[s] for s in INNER) \
            / inner["busy_s"]
    if peak and row_steps and inner["gdn_step"]:
        least = shapes.least_seconds(
            shapes_hybrid.gdn_step(cfg, row_steps), peak)
        out["gdn_step_roofline_pct"] = 100.0 * least["seconds"] \
            / inner["gdn_step"]
    prompt = counters.get("vllm:prompt_tokens_total", 0) / max(1, len(dirs))
    if peak and prompt and inner["gdn_chunk"]:
        least = shapes.least_seconds(
            shapes_hybrid.gdn_chunk(cfg, prompt), peak)
        out["gdn_chunk_roofline_pct"] = 100.0 * least["seconds"] \
            / inner["gdn_chunk"]
    return out


def read(ctx, field):
    if "_hybrid_trace" not in ctx:
        try:
            ctx["_hybrid_trace"] = reduce(ctx)
        except Exception as e:  # noqa: BLE001 — a capture this cannot read
            ctx["_hybrid_trace"] = {}
            if isinstance(ctx.get("trace"), dict):
                ctx["trace"].setdefault("notes", []).append(
                    f"hybrid_trace: capture not read "
                    f"({type(e).__name__}: {e})")
    return ctx["_hybrid_trace"].get(field)
