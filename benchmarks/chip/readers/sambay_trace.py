"""The kernel-layer numbers of a SambaY decoder (``model_type: phi4flash``:
selective-scan layers and window rings in the state slots, ONE paged layer
that eight layers read, a second half that caches nothing) from a capture:
what ``lib/roofline.py`` computes with a dense llama's arithmetic, computed
with ``lib/shapes_sambay.py``'s, and the scan's, the conv's and the paged
kernel's own times from the scopes each device operation carries on its
``tf_op`` path (``s6_step`` / ``s6_chunk`` / ``s6_conv``; the paged decode
kernel by its name).

Steps are counted IN the capture (paged decode kernel calls: one a reader
of the paged layer a step, eight at the published depth). Rows are LIVE
row-steps as ``readers/ssm_trace.py`` counts them. The chunkwise scan's
tokens are those of the capture's PAIRED prefill dispatches
(``readers/prefill_tokens.py:of``); a dispatch the capture's end cuts adds
device time and no token, so that share errs low. The context is each
answered request's mean (prompt + half its output), averaged.

One reduction a run, kept in the run's context; a field is ``None`` (and
its metric left out) where the capture, the counters or the scopes hold
nothing to read: a CPU rehearsal, a program that predates them, a model of
another family.
"""

from statistics import fmean

from benchmarks.chip.lib import roofline, shapes, shapes_sambay, spans, xplane
from benchmarks.chip.readers import prefill_tokens
from benchmarks.chip.readers.hybrid_trace import _peak

INNER = ("s6_step", "s6_chunk", "s6_conv")
DECODE_FN = "_decode_impl"


def scope_seconds(path: str) -> dict:
    """Device seconds, every instant given to one operation: under each
    inner scope (any program; ``s6_conv`` of the decode program apart as
    ``s6_conv_decode``), of the paged decode kernel (``paged``) and
    ``busy_s``."""
    scopes = spans.op_scopes(path)
    per_op = spans.exclusive_seconds(spans.read_events(path)["ops"])
    out = dict.fromkeys((*INNER, "s6_conv_decode", "s6_decode", "paged"),
                        0.0)
    out["busy_s"] = sum(per_op.values())
    for name, seconds in per_op.items():
        tf_op = scopes.get(name) or ""
        parts = tf_op.split("/")
        decode = DECODE_FN in tf_op
        if name.lstrip("%").startswith(roofline.ATTENTION_OPS):
            out["paged"] += seconds
        for scope in INNER:
            if scope in parts:
                out[scope] += seconds
                if decode:
                    out["s6_decode"] += seconds
                    if scope == "s6_conv":
                        out["s6_conv_decode"] += seconds
                break
    return out


def reduce(ctx: dict) -> dict:
    info = ctx.get("trace_info") or {}
    dirs = info.get("dirs") or []
    path = xplane.find(dirs[0]) if dirs else None
    cfg = ctx["model_config"]
    if path is None or cfg.get("model_type") != "phi4flash":
        return {}
    first = xplane.reduce(path)
    if not first.get("devices"):
        return {}
    d = shapes_sambay.dims(cfg)
    counters = info.get("counters") or {}
    out = {}
    inner = scope_seconds(path)
    decode_s = first["programs"].get(roofline.DECODE_PROGRAM, 0.0)
    if decode_s and inner["s6_decode"]:
        out["s6_share_pct"] = 100.0 * inner["s6_decode"] / decode_s
    kernel_calls = sum(v for k, v in first["counts"].items()
                       if k.startswith(roofline.ATTENTION_OPS))
    steps = kernel_calls / d["readers"]
    counted = counters.get("pstpu:decode_steps_total", 0)
    live = counters.get("pstpu:decode_row_steps_total", 0) \
        - counters.get("pstpu:decode_row_steps_wasted_total", 0)
    row_steps = steps * live / counted if counted else 0.0
    peak = _peak()
    ok = [r for r in ctx["results"] if r.ok]

    def share(work, seconds):
        return 100.0 * shapes.least_seconds(work, peak)["seconds"] / seconds

    prefills = prefill_tokens.of(ctx)
    if peak and prefills and prefills.get("tokens") and inner["s6_chunk"]:
        out["s6_chunk_roofline_pct"] = share(
            shapes_sambay.s6_chunk(cfg, prefills["tokens"]),
            inner["s6_chunk"])
    if not (peak and steps and row_steps and ok):
        return out
    context = fmean(r.request.prompt_tokens + r.request.output_tokens / 2
                    for r in ok)
    rows = row_steps / steps
    if decode_s:
        out["decode_roofline_pct"] = steps * share(
            shapes_sambay.decode_step(cfg, rows, context), decode_s)
    step_s = inner["s6_step"] + inner["s6_conv_decode"]
    if inner["s6_step"]:
        out["s6_step_roofline_pct"] = share(
            shapes_sambay.s6_step(cfg, row_steps), step_s)
    if inner["paged"]:
        out["shared_kv_attn_roofline_pct"] = share(
            shapes_sambay.shared_kv_attend(cfg, row_steps, context),
            inner["paged"])
    if isinstance(ctx.get("trace"), dict):
        ctx["trace"].setdefault("notes", []).append(
            f"sambay_trace: {steps:.0f} steps, {rows:.2f} live rows a step, "
            f"context {context:.0f}, decode {decode_s:.4f} s, s6_step "
            f"{inner['s6_step']:.4f} s, s6_conv of decode "
            f"{inner['s6_conv_decode']:.4f} s, s6_chunk "
            f"{inner['s6_chunk']:.4f} s, paged kernel {inner['paged']:.4f} s "
            f"of busy {inner['busy_s']:.3f} s")
    return out


def read(ctx, field):
    if "_sambay_trace" not in ctx:
        try:
            ctx["_sambay_trace"] = reduce(ctx)
        except Exception as e:  # noqa: BLE001 — a capture this cannot read
            ctx["_sambay_trace"] = {}
            if isinstance(ctx.get("trace"), dict):
                ctx["trace"].setdefault("notes", []).append(
                    f"sambay_trace: capture not read "
                    f"({type(e).__name__}: {e})")
    return ctx["_sambay_trace"].get(field)
