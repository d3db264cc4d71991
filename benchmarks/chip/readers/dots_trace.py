"""The kernel-layer numbers of a decoder of two kinds of latent attention
(``model_type: dots3_note``: full layers that attend what a learned indexer
selects, sliding layers that keep a ring of latent rows, a share of each
layer's experts) from a capture: what ``lib/roofline.py`` computes with a
dense llama's arithmetic, computed with ``lib/shapes_dots.py``'s, and the
indexer's, the selected-row attention's, the ring's and the experts' own
times from the scopes each device operation carries on its ``tf_op`` path
(``attn_index``: the indexer's projections, scores and top-k;
``attn_select``: the gather of the selected rows and the attention over
them; ``ring_attend`` / ``ring_write``; ``moe_route`` / ``moe_experts`` with
the grouped matmuls' inner ``moe_gmm``).

Steps are the program's own count over the capture (``pstpu:decode_steps_
total``, read beside the capture and scaled to its length: this family's
decode step calls no kernel whose calls a capture could count). Rows are
LIVE row-steps as ``readers/lfm_trace.py`` counts them, distinct held experts
a sparse-layer call the ratio of the program's own counters. The context is
each answered request's mean (prompt + half its output), averaged.

One reduction a run, kept in the run's context; a field is ``None`` (and its
metric left out) where the capture, the counters or the scopes hold nothing
to read: a CPU rehearsal, a program that predates them, a model of another
family.
"""

from statistics import fmean

from benchmarks.chip.lib import roofline, shapes, shapes_dots, spans, xplane
from benchmarks.chip.readers.hybrid_trace import _peak

MOE_SCOPES = ("moe_route", "moe_experts")
GMM_SCOPE = "moe_gmm"
RING_SCOPES = ("ring_attend", "ring_write")
INDEX_SCOPE, SELECT_SCOPE = "attn_index", "attn_select"
DECODE_FN = "_decode_impl"


def scope_seconds(path: str) -> dict:
    """Device seconds, every instant given to one operation: ``moe`` (the
    router and the experts, any program), ``gmm_decode`` (the grouped
    matmuls of the decode program), ``index`` (the indexer, any program)
    and ``index_decode``, ``select_decode`` (the selected rows' gather and
    attention in the decode program), ``ring_decode`` (the sliding layers'
    attention and ring write there), ``busy_s``."""
    scopes = spans.op_scopes(path)
    per_op = spans.exclusive_seconds(spans.read_events(path)["ops"])
    out = dict.fromkeys(("moe", "gmm_decode", "index", "index_decode",
                         "select_decode", "ring_decode"), 0.0)
    out["busy_s"] = sum(per_op.values())
    for name, seconds in per_op.items():
        tf_op = scopes.get(name) or ""
        parts = tf_op.split("/")
        decode = DECODE_FN in tf_op
        if any(s in parts for s in MOE_SCOPES):
            out["moe"] += seconds
        if GMM_SCOPE in parts and decode:
            out["gmm_decode"] += seconds
        if INDEX_SCOPE in parts:
            out["index"] += seconds
            if decode:
                out["index_decode"] += seconds
        if SELECT_SCOPE in parts and decode:
            out["select_decode"] += seconds
        if decode and any(s in parts for s in RING_SCOPES):
            out["ring_decode"] += seconds
    return out


def reduce(ctx: dict) -> dict:
    info = ctx.get("trace_info") or {}
    dirs = info.get("dirs") or []
    path = xplane.find(dirs[0]) if dirs else None
    cfg = ctx["model_config"]
    if path is None or cfg.get("model_type") != "dots3_note":
        return {}
    first = xplane.reduce(path)
    if not first.get("devices"):
        return {}
    d = shapes_dots.dims(cfg)
    counters = info.get("counters") or {}
    out = {}
    inner = scope_seconds(path)
    if inner["busy_s"] and inner["moe"]:
        out["moe_share_pct"] = 100.0 * inner["moe"] / inner["busy_s"]
    if inner["busy_s"] and inner["index"]:
        out["index_share_pct"] = 100.0 * inner["index"] / inner["busy_s"]
    decode_s = first["programs"].get(roofline.DECODE_PROGRAM, 0.0)
    steps = counters.get("pstpu:decode_steps_total", 0)
    row_steps = counters.get("pstpu:decode_row_steps_total", 0) \
        - counters.get("pstpu:decode_row_steps_wasted_total", 0)
    calls = counters.get("pstpu:moe_layer_calls_total", 0)
    touched = counters.get("pstpu:moe_experts_touched_total", 0) / calls \
        if calls else None
    peak = _peak()
    ok = [r for r in ctx["results"] if r.ok]
    if not (peak and steps and row_steps > 0 and ok):
        return out

    def share(work, seconds):
        return 100.0 * shapes.least_seconds(work, peak)["seconds"] / seconds

    context = fmean(r.request.prompt_tokens + r.request.output_tokens / 2
                    for r in ok)
    if inner["index_decode"]:
        out["index_roofline_pct"] = share(
            shapes_dots.index_scan(cfg, steps, row_steps, context),
            inner["index_decode"])
    if inner["select_decode"]:
        out["attn_roofline_pct"] = share(
            shapes_dots.selected_attend(cfg, row_steps, context),
            inner["select_decode"])
    if inner["ring_decode"]:
        out["ring_attn_roofline_pct"] = share(
            shapes_dots.ring_attend(cfg, row_steps, context),
            inner["ring_decode"])
    if touched is None:
        return out
    rows = row_steps / steps
    if decode_s:
        out["decode_roofline_pct"] = steps * share(
            shapes_dots.decode_step(cfg, rows, context, touched), decode_s)
    if inner["gmm_decode"]:
        layer_calls = steps * d["sparse"]
        out["gmm_roofline_pct"] = share(shapes_dots.moe_gmm(
            cfg, layer_calls,
            layer_calls * rows * d["top_k"] / d["ep_size"], touched),
            inner["gmm_decode"])
    if isinstance(ctx.get("trace"), dict):
        ctx["trace"].setdefault("notes", []).append(
            f"dots_trace: {steps:.0f} steps, {rows:.2f} live rows a step, "
            f"context {context:.0f}, {touched:.1f} of {d['held']} held "
            f"experts a call, decode {decode_s:.4f} s, of it index "
            f"{inner['index_decode']:.4f} s, selected rows "
            f"{inner['select_decode']:.4f} s, rings "
            f"{inner['ring_decode']:.4f} s, moe_gmm "
            f"{inner['gmm_decode']:.4f} s; index {inner['index']:.4f} s and "
            f"moe {inner['moe']:.4f} s of busy {inner['busy_s']:.3f} s")
    return out


def read(ctx, field):
    if "_dots_trace" not in ctx:
        try:
            ctx["_dots_trace"] = reduce(ctx)
        except Exception as e:  # noqa: BLE001 — a capture this cannot read
            ctx["_dots_trace"] = {}
            if isinstance(ctx.get("trace"), dict):
                ctx["trace"].setdefault("notes", []).append(
                    f"dots_trace: capture not read "
                    f"({type(e).__name__}: {e})")
    return ctx["_dots_trace"].get(field)
