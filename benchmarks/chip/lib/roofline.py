"""From a capture and the counters read over the same seconds to the
kernel-layer numbers: idle share, attention's share of busy time, time per
decode step, the decode program's share of its roofline and the prefill
program's share of peak compute. The operations and bytes come from
``lib/shapes.py``, the peaks from ``peaks.json``."""

from statistics import fmean
from typing import Collection, List, Optional

from benchmarks.chip.lib import shapes, xplane

DECODE_PROGRAM = "jit__decode_impl"
PREFILL_PROGRAM = "jit__prefill_impl"
# Device operations that are attention: the Pallas paged decode kernel by
# its name; (the window path's fusions carry no name of their own).
ATTENTION_OPS = ("paged_flash_decode",)


def reduce(trace_info: dict, model_config: dict, peak: Optional[dict],
           results: List, window_counters: dict,
           wanted: Optional[Collection[str]] = None) -> dict:
    """One engine's capture (the first; the others are averaged for busy
    time only) with the counters' deltas over the traced seconds.

    ``wanted``: the fields some metric of the cell reads (``None``: all).
    ``decode_roofline`` and ``prefill_mfu`` hold ``model_config`` to a
    dense decoder's count (``lib/shapes.py``) and are worked out only where
    asked for: a cell whose model is not one lists neither, and its
    ``config.json`` need not have a dense decoder's keys. Busy, idle, the
    breakdown, ``attn_share`` and ``decode_step_s`` need no count."""
    def asked(field: str) -> bool:
        return wanted is None or field in wanted

    reductions = []
    notes = []
    for trace_dir in trace_info["dirs"]:
        path = xplane.find(trace_dir)
        if path is None:
            notes.append(f"no capture under {trace_dir}")
            continue
        reductions.append(xplane.reduce(path))
    if not reductions:
        return {"busy_s": 0.0, "window_s": trace_info["seconds"],
                "breakdown": {"device_ops": [], "idle_gaps": []},
                "notes": notes}
    first = reductions[0]
    out = {
        "busy_s": fmean(r["busy_s"] for r in reductions),
        "window_s": fmean(r["window_s"] for r in reductions),
        "breakdown": first.get("breakdown",
                               {"device_ops": [], "idle_gaps": []}),
        "notes": notes,
    }
    if not first.get("devices") or not out["window_s"]:
        notes.append("no device plane in the capture")
        return out
    out["idle_share"] = 1.0 - out["busy_s"] / out["window_s"]
    busy = first["busy_s"]
    attn = sum(v for k, v in first["ops"].items()
               if any(k.startswith(a) for a in ATTENTION_OPS))
    if busy:
        out["attn_share"] = attn / busy
    counters = trace_info["counters"]
    n_engines = len(reductions)
    layers = model_config["num_hidden_layers"]
    decode_s = first["programs"].get(DECODE_PROGRAM, 0.0)
    prefill_s = first["programs"].get(PREFILL_PROGRAM, 0.0)
    # Steps the decode program really ran: one attention kernel call per
    # layer per step.
    kernel_calls = sum(v for k, v in first["counts"].items()
                       if any(k.startswith(a) for a in ATTENTION_OPS))
    steps = kernel_calls / layers
    ok = [r for r in results if r.ok]
    requests = counters.get(
        "vllm:time_to_first_token_seconds_count", 0) / n_engines
    if steps and decode_s:
        out["decode_step_s"] = decode_s / steps
    if asked("decode_roofline") and peak and steps and decode_s and ok:
        # Every request's first token comes from its prefill.
        decoded = counters.get(
            "vllm:generation_tokens_total", 0) / n_engines - requests
        rows = max(1.0, decoded / steps)
        context = fmean(r.request.prompt_tokens
                        + r.request.output_tokens / 2 for r in ok)
        least = shapes.least_seconds(
            shapes.decode_step(model_config, rows, context), peak)
        out["decode_roofline"] = steps * least["seconds"] / decode_s
        notes.append(f"decode: {steps:.0f} steps, {rows:.2f} rows a step, "
                     f"context {context:.0f}, {least['bound']}-bound")
    if asked("prefill_mfu") and peak and prefill_s and ok:
        # Prompt tokens the traced seconds saw, less the share the prefix
        # cache served over the whole window (hits are counted when a
        # request is admitted and prompt tokens when it ends, so their
        # deltas over 4 s do not belong to the same requests).
        prompt = counters.get("vllm:prompt_tokens_total", 0) / n_engines
        queries = window_counters.get(
            "vllm:gpu_prefix_cache_queries_total", 0)
        hit_share = (window_counters.get(
            "vllm:gpu_prefix_cache_hits_total", 0) / queries
            if queries else 0.0)
        new = prompt * (1.0 - hit_share)
        # A new token attends the cached prefix and, on average, half of
        # the new tokens before it.
        mean_prompt = fmean(r.request.prompt_tokens for r in ok)
        context = mean_prompt * (hit_share + (1.0 - hit_share) / 2)
        work = shapes.prefill(model_config, new, context, requests)
        out["prefill_mfu"] = work["flops"] / prefill_s / (
            peak["bf16_tflops"] * 1e12)
        notes.append(f"prefill: {new:.0f} new tokens of {prompt:.0f}, "
                     f"{prefill_s:.3f} s on the device")
    return out
