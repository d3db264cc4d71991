"""The kernel-layer numbers of a decoder of gated short convolutions and
routed experts (``model_type: lfm2_moe``) from a capture: what
``lib/roofline.py`` computes with a dense llama's arithmetic, computed with
``lib/shapes_lfm.py``'s, and the experts' and the convolution's own times
from the scopes each device operation carries on its ``tf_op`` path
(``moe_route`` / ``moe_experts`` with the grouped matmuls' inner ``moe_gmm``;
``short_conv``, ``state_read``, ``state_write``).

Steps are counted IN the capture (paged-kernel calls over the attention
layers: one call an attention layer a step). Rows are LIVE row-steps, the
ratio of the program's own counters over the traced seconds
((``pstpu:decode_row_steps_total`` - ``pstpu:decode_row_steps_wasted_total``)
/ ``pstpu:decode_steps_total``) times the capture's steps: a row-step that
delivers nothing reaches no expert and moves no state
(``readers/ssm_trace.py``). Distinct experts a sparse-layer call is the ratio
``pstpu:moe_experts_touched_total`` / ``pstpu:moe_layer_calls_total`` (decode
calls only, by name): the experts' bytes are those of the experts TOUCHED.

One reduction a run, kept in the run's context; a field is ``None`` (and
its metric left out) where the capture, the counters or the scopes hold
nothing to read: a CPU rehearsal, a program without the scopes or the
counters, a model of another family.
"""

from statistics import fmean

from benchmarks.chip.lib import roofline, shapes, shapes_lfm, spans, xplane
from benchmarks.chip.readers.hybrid_trace import _peak

MOE_SCOPES = ("moe_route", "moe_experts")
GMM_SCOPE = "moe_gmm"
CONV_SCOPE = "short_conv"
STATE_SCOPES = ("short_conv", "state_read", "state_write")
DECODE_FN = "_decode_impl"


def scope_seconds(path: str) -> dict:
    """Device seconds, every instant given to one operation: ``moe`` (the
    router and the experts, any program), ``gmm_decode`` and ``conv_decode``
    (the grouped matmuls and the gated convolution of the decode program),
    ``conv_state`` (the convolution and the slots' reads and writes, any
    program), ``busy_s``."""
    scopes = spans.op_scopes(path)
    per_op = spans.exclusive_seconds(spans.read_events(path)["ops"])
    out = {"moe": 0.0, "gmm_decode": 0.0, "conv_decode": 0.0,
           "conv_state": 0.0, "busy_s": sum(per_op.values())}
    for name, seconds in per_op.items():
        tf_op = scopes.get(name) or ""
        parts = tf_op.split("/")
        decode = DECODE_FN in tf_op
        if any(s in parts for s in MOE_SCOPES):
            out["moe"] += seconds
        if GMM_SCOPE in parts and decode:
            out["gmm_decode"] += seconds
        if any(s in parts for s in STATE_SCOPES):
            out["conv_state"] += seconds
        if CONV_SCOPE in parts and decode:
            out["conv_decode"] += seconds
    return out


def reduce(ctx: dict) -> dict:
    info = ctx.get("trace_info") or {}
    dirs = info.get("dirs") or []
    path = xplane.find(dirs[0]) if dirs else None
    cfg = ctx["model_config"]
    if path is None or cfg.get("model_type") != "lfm2_moe":
        return {}
    first = xplane.reduce(path)
    if not first.get("devices"):
        return {}
    d = shapes_lfm.dims(cfg)
    counters = info.get("counters") or {}
    out = {}
    inner = scope_seconds(path)
    if inner["busy_s"] and inner["moe"]:
        out["moe_share_pct"] = 100.0 * inner["moe"] / inner["busy_s"]
    if inner["busy_s"] and inner["conv_state"]:
        out["sconv_share_pct"] = 100.0 * inner["conv_state"] \
            / inner["busy_s"]
    decode_s = first["programs"].get(roofline.DECODE_PROGRAM, 0.0)
    kernel_calls = sum(v for k, v in first["counts"].items()
                       if k.startswith(roofline.ATTENTION_OPS))
    steps = kernel_calls / d["attention"]
    if steps and decode_s:
        out["decode_step_ms"] = 1e3 * decode_s / steps
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

    if inner["conv_decode"]:
        out["sconv_step_roofline_pct"] = share(
            shapes_lfm.sconv_step(cfg, row_steps, steps),
            inner["conv_decode"])
    if touched is None:
        return out
    rows = row_steps / steps
    if decode_s:
        context = fmean(r.request.prompt_tokens
                        + r.request.output_tokens / 2 for r in ok)
        out["decode_roofline_pct"] = steps * share(
            shapes_lfm.decode_step(cfg, rows, context, touched), decode_s)
    if inner["gmm_decode"]:
        layer_calls = steps * d["sparse"]
        out["gmm_roofline_pct"] = share(shapes_lfm.moe_gmm(
            cfg, layer_calls, layer_calls * rows * d["top_k"], touched),
            inner["gmm_decode"])
    if isinstance(ctx.get("trace"), dict):
        ctx["trace"].setdefault("notes", []).append(
            f"lfm_trace: {steps:.0f} steps, {rows:.2f} live rows a step, "
            f"{touched:.1f} experts a call, moe {inner['moe']:.4f} s, "
            f"moe_gmm of decode {inner['gmm_decode']:.4f} s, short_conv of "
            f"decode {inner['conv_decode']:.4f} s, conv and slots "
            f"{inner['conv_state']:.4f} s of busy {inner['busy_s']:.3f} s")
    return out


def read(ctx, field):
    if "_lfm_trace" not in ctx:
        try:
            ctx["_lfm_trace"] = reduce(ctx)
        except Exception as e:  # noqa: BLE001 — a capture this cannot read
            ctx["_lfm_trace"] = {}
            if isinstance(ctx.get("trace"), dict):
                ctx["trace"].setdefault("notes", []).append(
                    f"lfm_trace: capture not read "
                    f"({type(e).__name__}: {e})")
    return ctx["_lfm_trace"].get(field)
