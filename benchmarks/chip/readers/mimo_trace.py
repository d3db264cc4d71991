"""The kernel-layer numbers of a decoder whose window layers keep a
per-sequence ring beside paged full layers and whose chip holds a share of
each layer's experts (``model_type: mimo_v2``) from a capture: what
``lib/roofline.py`` computes with a dense llama's arithmetic, computed with
``lib/shapes_mimo.py``'s, and the experts' and the ring's own times from the
scopes each device operation carries on its ``tf_op`` path (``moe_route`` /
``moe_experts`` with the grouped matmuls' inner ``moe_gmm``; ``ring_attend``,
``attn_sink``, ``ring_write``).

Steps are counted IN the capture (paged decode kernel calls over the FULL
layers: one call a full layer a step). Rows are LIVE row-steps as
``readers/lfm_trace.py`` counts them, distinct held experts a sparse-layer
call the ratio of the program's own counters. The context is each answered
request's mean (prompt + half its output), averaged.

One reduction a run, kept in the run's context; a field is ``None`` (and its
metric left out) where the capture, the counters or the scopes hold nothing
to read: a CPU rehearsal, a program that predates them, a model of another
family.
"""

from statistics import fmean

from benchmarks.chip.lib import roofline, shapes, shapes_mimo, spans, xplane
from benchmarks.chip.readers.hybrid_trace import _peak

MOE_SCOPES = ("moe_route", "moe_experts")
GMM_SCOPE = "moe_gmm"
RING_SCOPES = ("ring_attend", "attn_sink", "ring_write")
DECODE_FN = "_decode_impl"


def scope_seconds(path: str) -> dict:
    """Device seconds, every instant given to one operation: ``moe`` (the
    router and the experts, any program), ``gmm_decode`` (the grouped
    matmuls of the decode program), ``ring`` (the window layers' attention,
    sink and ring write, any program), ``ring_decode`` (the same of the
    decode program), ``busy_s``."""
    scopes = spans.op_scopes(path)
    per_op = spans.exclusive_seconds(spans.read_events(path)["ops"])
    out = {"moe": 0.0, "gmm_decode": 0.0, "ring": 0.0, "ring_decode": 0.0,
           "busy_s": sum(per_op.values())}
    for name, seconds in per_op.items():
        tf_op = scopes.get(name) or ""
        parts = tf_op.split("/")
        decode = DECODE_FN in tf_op
        if any(s in parts for s in MOE_SCOPES):
            out["moe"] += seconds
        if GMM_SCOPE in parts and decode:
            out["gmm_decode"] += seconds
        if any(s in parts for s in RING_SCOPES):
            out["ring"] += seconds
            if decode:
                out["ring_decode"] += seconds
    return out


def reduce(ctx: dict) -> dict:
    info = ctx.get("trace_info") or {}
    dirs = info.get("dirs") or []
    path = xplane.find(dirs[0]) if dirs else None
    cfg = ctx["model_config"]
    if path is None or cfg.get("model_type") != "mimo_v2":
        return {}
    first = xplane.reduce(path)
    if not first.get("devices"):
        return {}
    d = shapes_mimo.dims(cfg)
    counters = info.get("counters") or {}
    out = {}
    inner = scope_seconds(path)
    if inner["busy_s"] and inner["moe"]:
        out["moe_share_pct"] = 100.0 * inner["moe"] / inner["busy_s"]
    if inner["busy_s"] and inner["ring"]:
        out["ring_attn_share_pct"] = 100.0 * inner["ring"] / inner["busy_s"]
    decode_s = first["programs"].get(roofline.DECODE_PROGRAM, 0.0)
    kernel_calls = sum(v for k, v in first["counts"].items()
                       if k.startswith(roofline.ATTENTION_OPS))
    steps = kernel_calls / d["full"]
    counted = counters.get("pstpu:decode_steps_total", 0)
    live = counters.get("pstpu:decode_row_steps_total", 0) \
        - counters.get("pstpu:decode_row_steps_wasted_total", 0)
    row_steps = steps * live / counted if counted else 0.0
    calls = counters.get("pstpu:moe_layer_calls_total", 0)
    touched = counters.get("pstpu:moe_experts_touched_total", 0) / calls \
        if calls else None
    peak = _peak()
    ok = [r for r in ctx["results"] if r.ok]
    if not (peak and steps and row_steps and ok):
        return out

    def share(work, seconds):
        return 100.0 * shapes.least_seconds(work, peak)["seconds"] / seconds

    context = fmean(r.request.prompt_tokens + r.request.output_tokens / 2
                    for r in ok)
    if inner["ring_decode"]:
        out["ring_attn_roofline_pct"] = share(
            shapes_mimo.ring_attend(cfg, row_steps, context),
            inner["ring_decode"])
    if touched is None:
        return out
    rows = row_steps / steps
    if decode_s:
        out["decode_roofline_pct"] = steps * share(
            shapes_mimo.decode_step(cfg, rows, context, touched), decode_s)
    if inner["gmm_decode"]:
        layer_calls = steps * d["sparse"]
        out["gmm_roofline_pct"] = share(shapes_mimo.moe_gmm(
            cfg, layer_calls,
            layer_calls * rows * d["top_k"] / d["ep_size"], touched),
            inner["gmm_decode"])
    if isinstance(ctx.get("trace"), dict):
        ctx["trace"].setdefault("notes", []).append(
            f"mimo_trace: {steps:.0f} steps, {rows:.2f} live rows a step, "
            f"context {context:.0f}, {touched:.1f} of {d['held']} held "
            f"experts a call, decode {decode_s:.4f} s, moe "
            f"{inner['moe']:.4f} s, moe_gmm of decode "
            f"{inner['gmm_decode']:.4f} s, ring of decode "
            f"{inner['ring_decode']:.4f} s, ring {inner['ring']:.4f} s of "
            f"busy {inner['busy_s']:.3f} s")
    return out


def read(ctx, field):
    if "_mimo_trace" not in ctx:
        try:
            ctx["_mimo_trace"] = reduce(ctx)
        except Exception as e:  # noqa: BLE001 — a capture this cannot read
            ctx["_mimo_trace"] = {}
            if isinstance(ctx.get("trace"), dict):
                ctx["trace"].setdefault("notes", []).append(
                    f"mimo_trace: capture not read "
                    f"({type(e).__name__}: {e})")
    return ctx["_mimo_trace"].get(field)
