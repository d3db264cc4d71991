"""The kernel-layer numbers of a decoder whose residual is several streams
(manifold-constrained hyper-connections around latent attention and sparse
experts) from a capture: the whole decode step's share of its roofline with
``lib/shapes_hc.py``'s arithmetic (``readers/moe_trace.py``'s counts a
full-rank query and no mix), and the mix's own time and share. Device time
under the scopes ``hc_pre`` / ``hc_post`` / ``hc_head`` comes from each
device operation's ``tf_op`` path; steps are the latent kernel's calls in
the capture over the layers; rows a step and distinct experts a call are
ratios of the program's counters, as ``moe_trace`` reads them.

One reduction a run, kept in the run's context; a field is ``None`` (and
its metric left out) where the capture, the counters or the scopes hold
nothing to read: a CPU rehearsal, a model with one stream, a program
without the scopes.
"""

from statistics import fmean

from benchmarks.chip.lib import roofline, shapes, shapes_hc, spans, xplane
from benchmarks.chip.readers.hybrid_trace import _peak
from benchmarks.chip.readers.moe_trace import DECODE_FN, _ratio

MIX_SCOPES = ("hc_pre", "hc_post")
HEAD_SCOPE = "hc_head"


def scope_seconds(path: str) -> dict:
    """Device seconds under the mix's scopes, every instant given to one
    operation: ``hc`` (the three scopes, any program), ``mix_decode``
    (``hc_pre`` + ``hc_post`` of the decode program), ``busy_s``."""
    scopes = spans.op_scopes(path)
    per_op = spans.exclusive_seconds(spans.read_events(path)["ops"])
    out = {"hc": 0.0, "mix_decode": 0.0, "busy_s": sum(per_op.values())}
    for name, seconds in per_op.items():
        tf_op = scopes.get(name) or ""
        parts = tf_op.split("/")
        mixing = any(s in parts for s in MIX_SCOPES)
        if mixing or HEAD_SCOPE in parts:
            out["hc"] += seconds
        if mixing and DECODE_FN in tf_op:
            out["mix_decode"] += seconds
    return out


def reduce(ctx: dict) -> dict:
    info = ctx.get("trace_info") or {}
    dirs = info.get("dirs") or []
    path = xplane.find(dirs[0]) if dirs else None
    cfg = ctx["model_config"]
    if path is None or cfg.get("hc_mult", 1) < 2:
        return {}
    first = xplane.reduce(path)
    if not first.get("devices"):
        return {}
    d = shapes_hc.dims(cfg)
    counters = info.get("counters") or {}
    out = {}
    inner = scope_seconds(path)
    if inner["busy_s"] and inner["hc"]:
        out["hc_share_pct"] = 100.0 * inner["hc"] / inner["busy_s"]
    decode_s = first["programs"].get(roofline.DECODE_PROGRAM, 0.0)
    kernel = [k for k in first["ops"]
              if k.startswith(roofline.ATTENTION_OPS)]
    steps = sum(first["counts"][k] for k in kernel) / d["layers"]
    rows = _ratio(counters, "pstpu:decode_row_steps_total",
                  "pstpu:decode_steps_total")
    touched = _ratio(counters, "pstpu:moe_experts_touched_total",
                     "pstpu:moe_layer_calls_total")
    peak = _peak()
    ok = [r for r in ctx["results"] if r.ok]
    if not (peak and steps and rows and ok):
        return out
    context = fmean(r.request.prompt_tokens + r.request.output_tokens / 2
                    for r in ok)

    def share(work, seconds):
        return 100.0 * shapes.least_seconds(work, peak)["seconds"] / seconds

    if inner["mix_decode"]:
        out["mix_roofline_pct"] = share(
            shapes_hc.mix(cfg, steps * rows, steps), inner["mix_decode"])
    if decode_s and touched is not None:
        out["decode_roofline_pct"] = steps * share(
            shapes_hc.decode_step(cfg, rows, context, touched), decode_s)
    return out


def read(ctx, field):
    if "_hc_trace" not in ctx:
        try:
            ctx["_hc_trace"] = reduce(ctx)
        except Exception as e:  # noqa: BLE001 — a capture this cannot read
            ctx["_hc_trace"] = {}
            if isinstance(ctx.get("trace"), dict):
                ctx["trace"].setdefault("notes", []).append(
                    f"hc_trace: capture not read "
                    f"({type(e).__name__}: {e})")
    return ctx["_hc_trace"].get(field)
