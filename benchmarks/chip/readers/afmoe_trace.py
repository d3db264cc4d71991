"""The kernel-layer numbers of a decoder whose attention layers are bounded
by a span or not, with routed experts beside a shared one (``model_type:
afmoe``), from a capture: what ``lib/roofline.py`` computes with a dense
llama's arithmetic, computed with ``lib/shapes_afmoe.py``'s, the paged
kernels' own times by their names, and the experts' from the scopes each
device operation carries on its ``tf_op`` path (``moe_route`` /
``moe_experts`` with the grouped matmuls' inner ``moe_gmm`` / ``moe_shared``).

Steps are counted IN the capture (paged decode kernel calls: one a layer a
step, bounded or not). Rows are LIVE row-steps as ``readers/lfm_trace.py``
counts them, distinct experts a sparse-layer call the ratio of the program's
own counters. The keys a decode query sees are ``lib/shapes_afmoe.py:
keys_seen`` at each answered request's mean context (prompt + half its
output), averaged: the bytes a layer must read UNDER ITS SPAN, the
superpage a kernel rounds the bound down to not counted. The keys the
capture's prefill dispatches scored are the ``keys_in_span`` their
``pstpu.issue`` spans carry (exact, the engine's closed form), of the
dispatches ``readers/prefill_tokens.py`` pairs with a program run of the
capture; the prefill kernel's seconds are its device operations inside those
runs.

One reduction a run, kept in the run's context; a field is ``None`` (and its
metric left out) where the capture, the counters, the spans' fields or the
scopes hold nothing to read: a CPU rehearsal, a program that predates them,
a model of another family.
"""

from benchmarks.chip.lib import roofline, shapes, shapes_afmoe, spans, xplane
from benchmarks.chip.readers import prefill_tokens
from benchmarks.chip.readers.hybrid_trace import _peak

MOE_SCOPES = ("moe_route", "moe_experts", "moe_shared")
GMM_SCOPE = "moe_gmm"
DECODE_FN = "_decode_impl"
PREFILL_KERNEL = "paged_flash_prefill"


def scope_seconds(path: str, ops=None) -> dict:
    """Device seconds, every instant given to one operation: ``moe`` (the
    router, the routed and the shared experts, any program), ``gmm_decode``
    (the grouped matmuls of the decode program), ``busy_s``. ``ops``: the
    capture's device operations where the caller has read them."""
    scopes = spans.op_scopes(path)
    per_op = spans.exclusive_seconds(
        spans.read_events(path)["ops"] if ops is None else ops)
    out = {"moe": 0.0, "gmm_decode": 0.0, "busy_s": sum(per_op.values())}
    for name, seconds in per_op.items():
        tf_op = scopes.get(name) or ""
        parts = tf_op.split("/")
        if any(s in parts for s in MOE_SCOPES):
            out["moe"] += seconds
        if GMM_SCOPE in parts and DECODE_FN in tf_op:
            out["gmm_decode"] += seconds
    return out


def prefill_kernel(events: dict):
    """(keys in span, the prefill kernel's device seconds) of the capture's
    prefill dispatches that pair with a program run, or None where the
    spans carry no ``keys_in_span``."""
    issues = [s for s in prefill_tokens.prefill_issues(events["spans"])
              if "keys_in_span" in s]
    runs = sorted(events["programs"].get(roofline.PREFILL_PROGRAM, []))
    pairs = prefill_tokens.pair(issues, runs)
    if not pairs:
        return None
    keys = sum(int(span["keys_in_span"]) for span, _ in pairs)
    taken = [run for _, run in pairs]
    seconds = sum(
        end - start for name, start, end in events["ops"]
        if xplane.op_label(name).startswith(PREFILL_KERNEL)
        and any(lo <= start < hi for lo, hi in taken))
    return keys, seconds


def reduce(ctx: dict) -> dict:
    info = ctx.get("trace_info") or {}
    dirs = info.get("dirs") or []
    path = xplane.find(dirs[0]) if dirs else None
    cfg = ctx["model_config"]
    if path is None or cfg.get("model_type") != "afmoe":
        return {}
    first = xplane.reduce(path)
    if not first.get("devices"):
        return {}
    d = shapes_afmoe.dims(cfg)
    counters = info.get("counters") or {}
    out = {}
    events = spans.read_events(path)
    inner = scope_seconds(path, events["ops"])
    if inner["busy_s"] and inner["moe"]:
        out["moe_share_pct"] = 100.0 * inner["moe"] / inner["busy_s"]
    peak = _peak()
    if not peak:
        return out

    def share(work, seconds):
        return 100.0 * shapes.least_seconds(work, peak)["seconds"] / seconds

    scored = prefill_kernel(events)
    if scored and scored[1]:
        out["prefill_attn_roofline_pct"] = share(
            shapes_afmoe.prefill_attention(cfg, scored[0]), scored[1])
    decode_s = first["programs"].get(roofline.DECODE_PROGRAM, 0.0)
    kernel_s = sum(v for k, v in first["ops"].items()
                   if k.startswith(roofline.ATTENTION_OPS))
    kernel_calls = sum(v for k, v in first["counts"].items()
                       if k.startswith(roofline.ATTENTION_OPS))
    steps = kernel_calls / d["layers"]
    counted = counters.get("pstpu:decode_steps_total", 0)
    live = counters.get("pstpu:decode_row_steps_total", 0) \
        - counters.get("pstpu:decode_row_steps_wasted_total", 0)
    row_steps = steps * live / counted if counted else 0.0
    ok = [r for r in ctx["results"] if r.ok]
    if not (steps and row_steps and ok):
        return out
    keys = shapes_afmoe.mean_keys_seen(cfg, (
        r.request.prompt_tokens + r.request.output_tokens / 2 for r in ok))
    if kernel_s:
        out["decode_attn_roofline_pct"] = share(
            shapes_afmoe.decode_attention(cfg, row_steps, keys), kernel_s)
    calls = counters.get("pstpu:moe_layer_calls_total", 0)
    if not calls:
        return out
    touched = counters.get("pstpu:moe_experts_touched_total", 0) / calls
    rows = row_steps / steps
    if decode_s:
        out["decode_roofline_pct"] = steps * share(
            shapes_afmoe.decode_step(cfg, rows, keys, touched), decode_s)
    if inner["gmm_decode"]:
        layer_calls = steps * d["sparse"]
        out["gmm_roofline_pct"] = share(shapes_afmoe.moe_gmm(
            cfg, layer_calls, layer_calls * rows * d["top_k"], touched),
            inner["gmm_decode"])
    if isinstance(ctx.get("trace"), dict):
        ctx["trace"].setdefault("notes", []).append(
            f"afmoe_trace: {steps:.0f} steps, {rows:.2f} live rows a step, "
            f"{keys:.0f} keys a row-step over {d['layers']} layers, "
            f"{touched:.1f} experts a call, decode kernel {kernel_s:.4f} s, "
            f"prefill kernel {scored[1] if scored else 0:.4f} s over "
            f"{scored[0] if scored else 0} keys, moe {inner['moe']:.4f} s, "
            f"moe_gmm of decode {inner['gmm_decode']:.4f} s of busy "
            f"{inner['busy_s']:.3f} s")
    return out


def read(ctx, field):
    if "_afmoe_trace" not in ctx:
        try:
            ctx["_afmoe_trace"] = reduce(ctx)
        except Exception as e:  # noqa: BLE001 — a capture this cannot read
            ctx["_afmoe_trace"] = {}
            if isinstance(ctx.get("trace"), dict):
                ctx["trace"].setdefault("notes", []).append(
                    f"afmoe_trace: capture not read "
                    f"({type(e).__name__}: {e})")
    return ctx["_afmoe_trace"].get(field)
