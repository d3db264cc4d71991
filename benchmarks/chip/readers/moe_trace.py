"""The kernel-layer numbers of a decoder with sparse experts and latent
attention from a capture: what ``lib/roofline.py`` computes with a dense
llama's arithmetic, computed with ``lib/shapes_moe.py``'s, and the experts'
and the latent kernel's own times. Device time under the scopes
``moe_route`` / ``moe_experts`` / ``moe_shared`` (and the grouped matmuls'
inner ``moe_gmm``) comes from each device operation's ``tf_op`` path; the
latent kernel is the operations named ``paged_flash_decode*``, one call a
layer a step, so steps are counted IN the capture and belong to the same
seconds as the device time they divide (``readers/hybrid_trace.py`` says
why). Rows a step and distinct experts a sparse-layer call are ratios of
the program's own counters over the traced seconds
(``pstpu:decode_row_steps_total`` / ``pstpu:decode_steps_total``;
``pstpu:moe_experts_touched_total`` / ``pstpu:moe_layer_calls_total``, both
of decode calls only): each pair is counted together, so its ratio does not
depend on where in a train the scrapes fell. The experts' bytes are those
of the experts TOUCHED, never of all of them.

One reduction a run, kept in the run's context; a field is ``None`` (and
its metric left out) where the capture, the counters or the scopes hold
nothing to read: a CPU rehearsal, a program without the scopes or the
counters (the parent of the PR that brought them), a model without experts.
"""

from statistics import fmean

from benchmarks.chip.lib import roofline, shapes, shapes_moe, spans, xplane
from benchmarks.chip.readers.hybrid_trace import _peak

MOE_SCOPES = ("moe_route", "moe_experts", "moe_shared")
GMM_SCOPE = "moe_gmm"
DECODE_FN = "_decode_impl"


def scope_seconds(path: str) -> dict:
    """Device seconds under the experts' scopes, every instant given to
    one operation: ``moe`` (the three scopes, any program), ``gmm_decode``
    (the grouped matmuls of the decode program), ``busy_s``."""
    scopes = spans.op_scopes(path)
    per_op = spans.exclusive_seconds(spans.read_events(path)["ops"])
    out = {"moe": 0.0, "gmm_decode": 0.0, "busy_s": sum(per_op.values())}
    for name, seconds in per_op.items():
        tf_op = scopes.get(name) or ""
        parts = tf_op.split("/")
        if any(s in parts for s in MOE_SCOPES):
            out["moe"] += seconds
        if GMM_SCOPE in parts and DECODE_FN in tf_op:
            out["gmm_decode"] += seconds
    return out


def _ratio(counters: dict, num: str, den: str):
    return counters[num] / counters[den] \
        if counters.get(den) and num in counters else None


def reduce(ctx: dict) -> dict:
    info = ctx.get("trace_info") or {}
    dirs = info.get("dirs") or []
    path = xplane.find(dirs[0]) if dirs else None
    cfg = ctx["model_config"]
    if path is None or "n_routed_experts" not in cfg:
        return {}
    first = xplane.reduce(path)
    if not first.get("devices"):
        return {}
    d = shapes_moe.dims(cfg)
    counters = info.get("counters") or {}
    out = {}
    inner = scope_seconds(path)
    if inner["busy_s"] and inner["moe"]:
        out["moe_share_pct"] = 100.0 * inner["moe"] / inner["busy_s"]
    decode_s = first["programs"].get(roofline.DECODE_PROGRAM, 0.0)
    kernel = {k: v for k, v in first["ops"].items()
              if k.startswith(roofline.ATTENTION_OPS)}
    kernel_s = sum(kernel.values())
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

    if kernel_s:
        out["mla_decode_roofline_pct"] = share(
            shapes_moe.mla_decode(cfg, steps * rows, context), kernel_s)
    if touched is None:
        return out
    if decode_s:
        out["decode_roofline_pct"] = steps * share(
            shapes_moe.decode_step(cfg, rows, context, touched), decode_s)
    if inner["gmm_decode"]:
        calls = steps * d["sparse"]
        out["gmm_roofline_pct"] = share(shapes_moe.moe_gmm(
            cfg, calls, calls * rows * d["top_k"], touched),
            inner["gmm_decode"])
    return out


def read(ctx, field):
    if "_moe_trace" not in ctx:
        try:
            ctx["_moe_trace"] = reduce(ctx)
        except Exception as e:  # noqa: BLE001 — a capture this cannot read
            ctx["_moe_trace"] = {}
            if isinstance(ctx.get("trace"), dict):
                ctx["trace"].setdefault("notes", []).append(
                    f"moe_trace: capture not read "
                    f"({type(e).__name__}: {e})")
    return ctx["_moe_trace"].get(field)
