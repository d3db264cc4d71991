#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the serving path still starts on
the chip.

Drives the stack the way a user does — ``python -m
production_stack_tpu.server.api_server`` behind ``python -m
production_stack_tpu.router.app``, brought up by
``benchmarks.stack.launch_stack`` — at the full published width AND depth of
``llama-3b`` (Llama-3.2-3B: 28 layers, hidden 3072, 24/8 heads, head_dim
128, vocab 128256; seeded dummy weights, KV pool sized from the chip's free
HBM), and checks what comes out by the repo's own means.

    python chip_smoke.py              one chip (what the driver runs)
    python chip_smoke.py --chips 4    replicas behind the router + tp=4,
                                      against a one-chip reference
    python chip_smoke.py --rehearse   CPU rehearsal of every phase at a tiny
                                      model (JAX_PLATFORMS=cpu); never ok

One JSON object per phase goes to stdout; the LAST line is
``{"ok": ..., "device": {"platform", "kind", "count"}}`` with the device as
the engine that served the requests reported it (``GET /version``). Exit
code 0 only with ``"ok": true`` — which needs every phase to pass on a TPU,
compiled (not interpreted) kernels, a compile cache, zero warmup faults, and
dispatch programs that update the KV pools in place (``pool_programs`` in
each serving line: no whole-pool ``copy``; their temporaries are printed).
The kernel phase also times the paged decode kernel alone at the benchmark's
two decode shapes and prints, under ``timing``, µs a call, the least time the
chip's memory allows the call's KV bytes (``benchmarks/chip/peaks.json``) and
their ratio, the kernel's own roofline share. It is read by no metric.
``--memory-stats`` (alone) times ``device.memory_stats()``, which the engine
calls twice a dispatch. Each serving line's ``memory`` is the engine's memory
ledger after the phase (``GET /debug/memory``: residents, rises, the last).
``--prefill`` (alone, like ``--gdn``, ``--ssd``, ``--ring``, ``--moe`` and
``--hc``) does the same for the prefill flash kernel at the benchmark's
prefill shapes: over K/V rows and over latent rows, a packed row and a
rectangle laid as a row.

This process never imports JAX: a chip belongs to one process at a time and
the engine children need it (the kernel phase runs in a child of its own).
It finds the package next to this file, not in the caller's cwd.
"""

import argparse
import functools
import json
import os
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
LOG_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")

MODEL, FULL_DEPTH = "llama-3b", 28
# The smoke's serving envelope (ISSUE 21 §6). Warmup compiles AND executes
# every reachable shape family before /health turns 200, and the family
# count grows with log2(max_num_seqs) x log2(max_num_batched_tokens), not
# with max_model_len: 4 seqs / 256 batched tokens is 24 + 12 families on
# the window path and 4 + 12 on the paged path (~75 / ~30 programs with the
# logprobs/penalty variants, 2-8 s each when compiled for a described v5e in
# the sandbox) — a cold boot of minutes, inside the 1200 s the driver
# allows for both boots. max_model_len stays the engine default.
ENGINE_ARGS = ["--max-num-seqs", "4", "--max-model-len", "2048",
               "--max-num-batched-tokens", "256"]
# Rehearsal: 8 kv heads so tp=4 shards the pool, head_dim 32 so the kernel
# runs lane-packed; prompts of this script still span several chunks.
REHEARSAL_MODEL, REHEARSAL_DEPTH = "tiny-llama-8kv", 2
REHEARSAL_ENGINE_ARGS = ["--max-num-seqs", "2", "--max-model-len", "1024",
                         "--max-num-batched-tokens", "128",
                         "--num-decode-steps", "8"]
MAX_TOKENS = 16
# One boot may take this long before the phase gives up: about twice the
# cold window-path boot measured on a v5e (399 s, 75 programs).
BOOT_TIMEOUT_S = 800.0
# Kernel vs XLA reference, max-abs: both sides round through bf16 (8
# mantissa bits, 2^-8 relative) once in the PV contraction and once at the
# output, on values of order one — a handful of bf16 ulps at 1.0.
KERNEL_MAX_ABS_ERR = 2e-2
# tp=4 vs tp=1 first-token logprob: the row-parallel all-reduce sums bf16
# partials in another order (and since PR 35 the prompt's attention is
# window_attention at tp=4 and the flash kernel at tp=1: float32 softmax
# over bf16 products both, blocked differently), and the logit of a
# 128k-way softmax over random weights moves by a few bf16 ulps of the
# logit scale.
TP_LOGPROB_TOL = 0.15
# tp=4: per-device bytes in use may differ by replicated leaves (norms,
# small buffers), not by a whole copy of weights or pool.
TP_BYTES_SPREAD = 1.25

SYSTEM = (
    "You are the smoke test of a serving stack. Every request in this "
    "script begins with this same system prompt so that its KV blocks are "
    "computed once and found again in the prefix cache by the next one. "
    "Answer briefly."
)
QUESTIONS = [
    "What does a router do in front of a fleet of engines?",
    "Name one reason to keep the KV cache paged.",
    "Why is decode bound by memory bandwidth?",
    # Longer than the prefill chunk budget: a multi-chunk prefill whose
    # later chunks attend the earlier ones through the history window.
    "Summarise the following, then stop. " + " ".join(
        f"Clause {i}: the engine batches requests continuously." for i in
        range(12)
    ),
]


def emit(obj: dict) -> dict:
    print(json.dumps(obj), flush=True)
    return obj


# ------------------------------------------------------------------ HTTP
def _http(url, body=None, headers=None, timeout=600):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data,
        headers={"Content-Type": "application/json", **(headers or {})},
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, resp.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def get_json(url):
    status, raw = _http(url)
    if status != 200:
        raise RuntimeError(f"GET {url} -> {status}: {raw[:300]!r}")
    return json.loads(raw)


def scrape(engine_url) -> dict:
    """Engine /metrics as {series name: value} (label sets summed)."""
    status, raw = _http(f"{engine_url}/metrics")
    if status != 200:
        raise RuntimeError(f"GET {engine_url}/metrics -> {status}")
    out = {}
    for line in raw.decode().splitlines():
        if line and not line.startswith("#"):
            name, _, value = line.rpartition(" ")
            name = name.split("{", 1)[0]
            out[name] = out.get(name, 0.0) + float(value)
    return out


def expected_prompt_tokens(messages) -> int:
    """Byte-level tokenizer + the repo's plain chat template
    (engine/tokenizer.py:ByteTokenizer): one token per UTF-8 byte."""
    text = "".join(f"<|{m['role']}|>\n{m['content']}\n" for m in messages)
    return len((text + "<|assistant|>\n").encode("utf-8"))


def chat_body(model, question, stream, logprobs=False):
    body = {
        "model": model, "stream": stream, "temperature": 0, "seed": 0,
        "max_tokens": MAX_TOKENS, "ignore_eos": True,
        "messages": [{"role": "system", "content": SYSTEM},
                     {"role": "user", "content": question}],
    }
    if stream:
        body["stream_options"] = {"include_usage": True}
    if logprobs:
        body.update(logprobs=True, top_logprobs=0)
    return body


def chat(router_url, body, headers=None) -> dict:
    """One chat completion through the router, reduced to what the checks
    read: status, usage, finish_reason, and — for streams — the token ids
    the engine's per-chunk ``pstpu`` payload carries through the router's
    relay, the chosen-token logprobs, and whether ``[DONE]`` closed it."""
    status, raw = _http(f"{router_url}/v1/chat/completions", body, headers)
    out = {"status": status, "toks": [], "logprobs": [], "usage": None,
           "finish_reason": None, "done": False, "text": ""}
    if status != 200:
        out["error"] = raw[:300].decode(errors="replace")
        return out
    if not body["stream"]:
        doc = json.loads(raw)
        choice = doc["choices"][0]
        out.update(usage=doc.get("usage"), done=True,
                   finish_reason=choice.get("finish_reason"),
                   text=choice["message"].get("content") or "")
        return out
    for event in raw.split(b"\n\n"):
        for line in event.split(b"\n"):
            if not line.startswith(b"data:"):
                continue
            payload = line[len(b"data:"):].strip()
            if payload == b"[DONE]":
                out["done"] = True
                continue
            doc = json.loads(payload)
            if "error" in doc:
                out["error"] = doc["error"]
            if doc.get("usage"):
                out["usage"] = doc["usage"]
            out["toks"] += (doc.get("pstpu") or {}).get("toks", [])
            for choice in doc.get("choices") or []:
                out["text"] += (choice.get("delta") or {}).get("content") or ""
                if choice.get("finish_reason"):
                    out["finish_reason"] = choice["finish_reason"]
                for item in (choice.get("logprobs") or {}).get("content", []):
                    out["logprobs"].append(item["logprob"])
    return out


def check_completion(res: dict, body: dict) -> list:
    """Faults of one answered request against the exact counts it must
    carry (greedy, ignore_eos: the budget is always spent)."""
    faults = []
    want = expected_prompt_tokens(body["messages"])
    if res["status"] != 200:
        return [f"status {res['status']}: {res.get('error')}"]
    usage = res["usage"] or {}
    if usage != {"prompt_tokens": want, "completion_tokens": MAX_TOKENS,
                 "total_tokens": want + MAX_TOKENS}:
        faults.append(f"usage {usage} != prompt {want} + {MAX_TOKENS}")
    if res["finish_reason"] != "length":
        faults.append(f"finish_reason {res['finish_reason']!r}")
    if not res["done"]:
        faults.append("stream not closed by [DONE]")
    if "error" in res:
        faults.append(f"error event {res['error']}")
    if body["stream"] and len(res["toks"]) != MAX_TOKENS:
        faults.append(f"{len(res['toks'])} token ids in the stream")
    if body.get("logprobs") and len(res["logprobs"]) != MAX_TOKENS:
        faults.append(f"{len(res['logprobs'])} logprobs in the stream")
    return faults


def agreement(a: list, b: list) -> float:
    """Share of positions at which two lists of token-id lists agree."""
    total = sum(max(len(x), len(y)) for x, y in zip(a, b))
    same = sum(sum(p == q for p, q in zip(x, y)) for x, y in zip(a, b))
    return round(same / total, 4) if total else 0.0


# ---------------------------------------------------------------- phases
def _engine_log_tail(stack, n=1500) -> str:
    """End of the first engine's log (the newest one when the launch itself
    failed and left no handle)."""
    paths = list(getattr(stack, "log_paths", []))
    if not paths and os.path.isdir(LOG_DIR):
        paths = sorted(
            (os.path.join(LOG_DIR, f) for f in os.listdir(LOG_DIR)
             if "engine" in f), key=os.path.getmtime, reverse=True,
        )
    if not paths:
        return ""
    with open(paths[0], errors="replace") as f:
        return f.read()[-n:]


def _boot_summary(report: dict, ready_s: float) -> dict:
    """The per-boot numbers, from the engine's own report (GET /version)."""
    eng = report["engine"]
    return {
        "engine_ready_s": round(ready_s, 1),
        "attn_impl": eng["attn_impl"],
        "pallas_interpret": eng["pallas_interpret"],
        "num_layers": eng["num_layers"],
        "warmup_families": eng["warmup_families"],
        "deferred_families": eng["deferred_families"],
        "warmup_failures": eng["warmup_failures"],
        "compile_s": eng["compile_seconds"],
        "warmup_s": eng["warmup_seconds"],
        "weight_load_s": eng["weight_load_seconds"],
        "cache_dir": eng["compilation_cache_dir"],
        "cache_entries": eng["compilation_cache_entries"],
        "cache_hit": eng["cache_hit_families"],
        "cache_miss": eng["cache_miss_families"],
        "kv_blocks": eng["kv_blocks"],
        "kv_shard_shape": eng["kv_shard_shape"],
        "bytes_in_use": eng["bytes_in_use"],
        "device": report["device"],
    }


def run_phase(line: dict, body, model, engine_args, **kw) -> dict:
    """Engines + router up the way launch_stack starts them, every engine's
    boot summary put in ``line`` (``boot``, or ``boots`` for several), then
    ``body(stack)`` makes the phase's requests and leaves its result and
    ``ok`` in ``line``. A phase reports and never raises: a fault lands in
    the line with the engine's log tail, and the stack is always stopped."""
    from benchmarks.stack import launch_stack

    line["ok"] = False
    t0 = time.monotonic()
    stack = None
    try:
        os.makedirs(LOG_DIR, exist_ok=True)
        stack = launch_stack(
            model, engine_args=engine_args, log_dir=LOG_DIR,
            routing_logic="session",
            router_args=["--session-key", "x-user-id"],
            startup_timeout_s=BOOT_TIMEOUT_S, **kw,
        )
        boots = [
            _boot_summary(get_json(f"{url}/version"), ready_s)
            for url, ready_s in zip(stack.engine_urls,
                                    stack.engine_ready_seconds)
        ]
        line.update({"boot": boots[0]} if len(boots) == 1
                    else {"boots": boots})
        # What the engine's dispatch programs do to the KV pools, compiled
        # by the engine itself on the device it serves on.
        line["pool_programs"] = get_json(
            f"{stack.engine_urls[0]}/debug/programs")["programs"]
        body(stack)
        line["memory"] = _memory_summary(stack.engine_urls[0])
    except Exception as e:  # noqa: BLE001 — a phase reports, never raises
        line.update(ok=False, error=f"{type(e).__name__}: {e}",
                    engine_log_tail=_engine_log_tail(stack))
    finally:
        if stack is not None:
            stack.terminate()
        line["wall_s"] = round(time.monotonic() - t0, 1)
    return emit(line)


def _memory_summary(engine_url) -> dict:
    """What holds the first engine's device after the phase's requests,
    from ``GET /debug/memory``: the ledger's residents, how many programs
    it measured, how often the allocator's peak rose in each phase and
    the last rise. Printed, and part of no verdict."""
    try:
        ledger = get_json(f"{engine_url}/debug/memory")
    except Exception as e:  # noqa: BLE001 — printing only
        return {"error": f"{type(e).__name__}: {e}"}
    return {
        "device": ledger["device"], "residents": ledger["residents"],
        "programs_measured": len(ledger["programs"]),
        "rises": ledger["rises"], "rise_bytes": ledger["rise_bytes"],
        "last_event": ledger["events"][-1] if ledger["events"] else None,
        "now": ledger["now"].get(ledger["device"]),
    }


def _bytes_in_use(engine_url) -> dict:
    return get_json(f"{engine_url}/version")["engine"]["bytes_in_use"]


def phase_kernel(model: str, rehearse: bool) -> dict:
    """Child of its own (it needs the chip, and must be gone before the
    engines start): the compiled paged decode kernel against the XLA
    reference at the model's widths, bf16 and int8 pool."""
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--kernel-child", model,
         *(["--rehearse"] if rehearse else [])],
        capture_output=True, text=True, timeout=900,
    )
    lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
    line = json.loads(lines[-1]) if lines else {}
    line.setdefault("phase", "kernel")
    line["wall_s"] = round(time.monotonic() - t0, 1)
    if proc.returncode != 0 or not lines:
        line["ok"] = False
        line.setdefault("error", proc.stderr[-1500:])
    return line


# The benchmark's two decode shapes (BENCHMARK.json's cells), Dh 128 and
# block 16 in both: cell 3's 32-row bucket with 20 live chat contexts, and
# cell 2's one row behind a 6144-token system prompt.
KERNEL_TIMING_SHAPES = [
    {"name": "chat-saturated", "rows": 32, "live": 20, "lens": (96, 2600),
     "heads": 16, "kv_heads": 2},
    {"name": "agent-prefix", "rows": 1, "live": 1, "lens": (6300, 6300),
     "heads": 32, "kv_heads": 8},
]
KERNEL_TIMING_CALLS = 256


def kernel_timing_case(shape, dh=128, bs=16, layers=2, seed=0):
    """Operands of one kernel call at ``shape``: scattered pages, live rows
    first, then the bucket's padding (``kv_len`` 0, table entries 0)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(seed)
    b, live = shape["rows"], shape["live"]
    lo, hi = shape["lens"]
    # Chat contexts: a 64-token system prompt, a log-normal prompt (median
    # 256) and part of an answer, as the chat traffic file draws them.
    lens = np.clip(64 + rng.lognormal(np.log(256), 0.8, live)
                   + rng.uniform(0, 300, live), lo, hi).astype(np.int32)
    lens = np.concatenate([lens, np.zeros(b - live, np.int32)])
    mb = -(-int(lens.max()) // bs)
    bt = np.zeros((b, mb), np.int32)
    order = 1 + rng.permutation(b * mb).reshape(b, mb)
    for i, n in enumerate(lens):
        bt[i, :-(-n // bs)] = order[i, :-(-n // bs)]
    hkv = shape["kv_heads"]
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    pool = (layers, hkv, (1 + b * mb) * bs, dh)
    return {
        "q": jax.random.normal(kq, (b, shape["heads"], dh), jnp.bfloat16),
        "k_pool": jax.random.normal(kk, pool, jnp.bfloat16),
        "v_pool": jax.random.normal(kv, pool, jnp.bfloat16),
        "tables": jnp.asarray(bt), "kv_lens": jnp.asarray(lens),
        "block_size": bs,
        # What the call must read: K and V of every live token, bf16.
        "kv_bytes": int(lens.sum()) * hkv * dh * 2 * 2,
    }


def time_kernel(kernel, case, calls, repeats=5, **kernel_kwargs):
    """Seconds per call of ``kernel`` alone: ``calls`` calls chained through
    the query inside one program (so none overlaps the next and the
    dispatch is paid once), the best of ``repeats`` on the host's clock."""
    import jax

    def chain(q, k_pool, v_pool, tables, kv_lens):
        def one(i, q):
            out, _, _ = kernel(
                q, k_pool, v_pool, tables, kv_lens, i % k_pool.shape[0],
                block_size=case["block_size"], **kernel_kwargs,
            )
            return out
        return jax.lax.fori_loop(0, calls, one, q)

    run = jax.jit(chain)
    args = [case[k] for k in ("q", "k_pool", "v_pool", "tables", "kv_lens")]
    run(*args).block_until_ready()
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        run(*args).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best / calls


def best_of(run, args, n, repeats=5):
    """Seconds a call: the best of ``repeats`` runs of ``n`` calls chained
    in one program, after one run that compiles (children only: JAX)."""
    import jax

    jax.block_until_ready(run(*args))
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(run(*args))
        best = min(best, time.perf_counter() - t0)
    return best / n


def kernel_child(model: str, rehearse: bool) -> int:
    """Runs in the child: the only code of this file that imports JAX."""
    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if dev.platform != "tpu" and not rehearse:
        emit({"phase": "kernel", "ok": False, "device": device,
              "error": "JAX found no TPU; nothing was run"})
        return 1

    import jax.numpy as jnp
    import numpy as np

    from production_stack_tpu.models.config import resolve_model_config
    from production_stack_tpu.ops.attention import paged_attention_xla
    from production_stack_tpu.ops.pallas.paged_attention import (
        paged_flash_decode_stats,
    )
    from production_stack_tpu.ops.quantization import quantize_kv

    interpret = dev.platform == "cpu"   # rehearsal only; fails the verdict
    mc = resolve_model_config(model)
    h, hkv, dh, bs = mc.num_heads, mc.num_kv_heads, mc.head_dim_, 16
    # Two layers in the stacked pool and layer 1 addressed, ragged lengths:
    # a full superpage run, a partial superpage, a partial page, one page.
    max_len = min(2048, mc.max_position_embeddings)
    lens = [max_len, max_len // 2 - 24, 17, 16]
    b, mb, nl = len(lens), max_len // bs, 2
    slots = (b * mb + 1) * bs
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (b, h, dh), jnp.bfloat16)
    k_pool = jax.random.normal(kk, (nl, hkv, slots, dh), jnp.bfloat16)
    v_pool = jax.random.normal(kv, (nl, hkv, slots, dh), jnp.bfloat16)
    tables = jnp.asarray(
        1 + np.arange(b * mb, dtype=np.int32).reshape(b, mb)
    )
    kv_lens = jnp.asarray(lens, jnp.int32)
    layer = 1
    cases = []
    for name in ("bfloat16", "int8"):
        scales = {}
        kp, vp = k_pool, v_pool
        if name == "int8":
            kp, ks = quantize_kv(k_pool)
            vp, vs = quantize_kv(v_pool)
            scales = {"k_scale": ks, "v_scale": vs}
        out, _, _ = paged_flash_decode_stats(
            q, kp, vp, tables, kv_lens, jnp.int32(layer), block_size=bs,
            interpret=interpret, **scales,
        )
        with jax.default_matmul_precision("highest"):
            ref = paged_attention_xla(
                q[:, None], kp[layer], vp[layer], tables, kv_lens,
                (kv_lens - 1)[:, None], block_size=bs,
                **{k: v[layer] for k, v in scales.items()},
            )[:, 0]
        err = float(jnp.max(jnp.abs(
            out.astype(jnp.float32) - ref.astype(jnp.float32)
        )))
        finite = bool(jnp.all(jnp.isfinite(out.astype(jnp.float32))))
        cases.append({
            "pool": name, "max_abs_err": err, "bound": KERNEL_MAX_ABS_ERR,
            "finite": finite, "shape": list(out.shape),
            "ok": finite and err <= KERNEL_MAX_ABS_ERR
            and out.shape == (b, h, dh),
        })
    # The kernel alone at the benchmark's shapes, against the least time the
    # chip's memory allows its bytes: the kernel's own roofline share. A
    # rehearsal walks the same code at a toy size and reports no time.
    with open(os.path.join(HERE, "benchmarks", "chip", "peaks.json")) as f:
        peaks = json.load(f)["by_device_kind"]
    hbm_gbps = peaks.get(dev.device_kind, {}).get("hbm_gbps")
    timing = []
    for shape in KERNEL_TIMING_SHAPES:
        if rehearse:
            shape = {**shape, "rows": min(shape["rows"], 3),
                     "live": min(shape["live"], 2), "lens": (17, 600)}
        case = kernel_timing_case(shape)
        sec = time_kernel(
            paged_flash_decode_stats, case,
            2 if rehearse else KERNEL_TIMING_CALLS, interpret=interpret,
        )
        entry = {"shape": shape["name"], "rows": shape["rows"],
                 "live_rows": shape["live"],
                 "kv_tokens": int(case["kv_lens"].sum()),
                 "kv_bytes": case["kv_bytes"],
                 "us_per_call": None, "bytes_least_us": None,
                 "roofline_pct": None}
        if not rehearse and hbm_gbps:
            least = case["kv_bytes"] / (hbm_gbps * 1e9)
            entry.update(us_per_call=sec * 1e6, bytes_least_us=least * 1e6,
                         roofline_pct=100.0 * least / sec)
        timing.append(entry)
    emit({
        "phase": "kernel", "model": model,
        "widths": {"heads": h, "kv_heads": hkv, "head_dim": dh,
                   "block_size": bs, "kv_lens": lens},
        "interpret": interpret, "cases": cases, "timing": timing,
        "hbm_gbps": hbm_gbps, "device": device,
        "ok": all(c["ok"] for c in cases),
    })
    return 0


# The recurrence of a Gated DeltaNet layer alone, at Olmo-Hybrid-7B's
# published widths: one decode step of 20 live rows (chat-saturated's mean)
# of a 20-row carry and of a 32-row one (the cell's bucket: the 12 rows that
# take no token should cost nothing) and of 64 of 64, and one prefill chunk:
# of one 2048-token row, and of the rectangles chat-saturated dispatches,
# [16, 128] and [8, 256], with lengths drawn to the cell's 72% fill.
GDN_WIDTHS = {"heads": 30, "dk": 96, "dv": 192}
GDN_STEP_ROWS = ((20, 20), (32, 20), (64, 64))    # (the carry's rows, live)
GDN_CHUNK_SHAPES = ((1, 2048, 1.0), (16, 128, 0.72), (8, 256, 0.72))
GDN_CALLS = 64
# Layers of the carry a timed step program cycles through, stepped in place
# as a decode program steps its rows' 12 linear layers: ONE layer's rows (44
# MB at 20 rows) stay on the chip between chained calls and read faster than
# HBM allows.
GDN_LAYERS = 12


def chained_chunks(fn, lens, calls: int):
    """The program ``--gdn`` times a form of ``gdn_chunk`` in: ``calls``
    calls chained through the state. q, k, v and the gates are the same in
    every call, so each call takes them through a barrier with the carried
    state: without it XLA lifts whatever does not read the state out of the
    loop (of the ``jnp`` form everything but the scan over chunks: the
    ``k_beta k^T | q k^T`` product, the 63-trip inverse, ``u`` and ``w``)
    and computes it once for all the calls, which is how PR 32 and PR 42
    first read that form at 1.8 ms a layer where a prefill program, whose
    layers each bring their own q, k and v, pays it 4.8."""
    import jax
    import jax.numpy as jnp

    def chunks(state, q, k, v, g, beta):
        def one(_, carry):
            state, acc = carry
            o, state = fn(*jax.lax.optimization_barrier(
                (state, q, k, v, g, beta)), lens)
            return state, acc + o
        return jax.lax.fori_loop(
            0, calls, one, (state, jnp.zeros(v.shape, jnp.float32)))

    return chunks


def gdn_child(rehearse: bool) -> int:
    """``--gdn``: times ``gdn_step_at`` and ``gdn_chunk``
    (ops/gated_delta.py) alone on the chip, calls chained through the state
    inside one program (a step program through the ``GDN_LAYERS`` layers of
    a donated carry in turn, in place), and prints µs a call beside the
    least time the chip's peaks allow their bytes and FLOPs
    (benchmarks/chip/lib/shapes_hybrid.py's count of ONE layer, the LIVE
    rows' state). Fails where the step program holds the ``jnp`` form on a
    TPU. Run by no benchmark cell and no other phase."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.chip.lib import shapes_hybrid
    from production_stack_tpu.ops import gated_delta as gd

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind}
    if dev.platform != "tpu" and not rehearse:
        emit({"phase": "gdn", "ok": False, "device": device,
              "error": "no TPU: nothing was timed"})
        return 1
    widths = dict(GDN_WIDTHS)
    rows_list, chunk_shapes, calls = GDN_STEP_ROWS, GDN_CHUNK_SHAPES, GDN_CALLS
    layers = GDN_LAYERS
    if rehearse:
        widths, rows_list, chunk_shapes, calls, layers = \
            {"heads": 4, "dk": 16, "dv": 32}, ((2, 2), (3, 2)), \
            ((1, 128, 1.0), (3, 64, 0.72)), 2, 2
    h, dk, dv = widths["heads"], widths["dk"], widths["dv"]
    # One linear layer of a config with these widths, for the shapes' count.
    cfg = {"num_attention_heads": h, "hidden_size": h * 128,
           "intermediate_size": 1, "vocab_size": 1,
           "layer_types": ["linear_attention"],
           "linear_num_value_heads": h, "linear_key_head_dim": dk,
           "linear_value_head_dim": dv, "linear_conv_kernel_dim": 4}
    with open(os.path.join(HERE, "benchmarks", "chip", "peaks.json")) as f:
        peak = json.load(f)["by_device_kind"].get(dev.device_kind)

    def inputs(key, b, t):
        ks = jax.random.split(key, 6)
        q, k = (jax.random.normal(ks[i], (b, t, h, dk)) for i in (0, 1))
        v = jax.random.normal(ks[2], (b, t, h, dv))
        beta, g = gd.gates(
            jax.random.normal(ks[3], (b, t, h)),
            jax.random.normal(ks[4], (b, t, h)),
            jnp.zeros((h,)), jnp.ones((h,)), True)
        state = gd.pack_state(0.1 * jax.random.normal(ks[5], (b, h, dk, dv)))
        return (state, *gd.prepare(q, k, v), g, beta)

    def entry(name, sec, work, **more):
        out = {"op": name, **more, "bytes": work["bytes"],
               "flops": work["flops"], "us_per_call": None,
               "least_us": None, "roofline_pct": None}
        if peak and not rehearse:
            least = max(work["bytes"] / (peak["hbm_gbps"] * 1e9),
                        work["flops"] / (peak["bf16_tflops"] * 1e12))
            out.update(us_per_call=sec * 1e6, least_us=least * 1e6,
                       roofline_pct=100.0 * least / sec)
        return out

    timing, checks, finite, paths = [], [], True, set()
    for b, n_live in rows_list:
        state, *xs = inputs(jax.random.PRNGKey(b), b, 1)
        xs = tuple(x[:, 0] for x in xs)           # q, k, v, g, beta
        carry = jnp.tile(state[:, None], (1, layers, 1, 1, 1))
        # The live rows spread over the carry, as a bucket's are.
        live = (jnp.arange(b) * n_live) % b < n_live

        def steps(carry, *xs):
            def one(i, both):
                carry, acc = both
                o, carry = gd.gdn_step_at(carry, i % layers, *xs, live,
                                          interpret=rehearse)
                return carry, acc + o
            return jax.lax.fori_loop(
                0, calls * layers, one, (carry, jnp.zeros((b, h, dv))))

        # Once against the jnp form: live rows agree, the others' state is
        # bit for bit what it was.
        want_o, want = jax.jit(gd.gdn_step_at_jnp)(carry, 1, *xs, live)
        got_o, got = jax.jit(functools.partial(
            gd.gdn_step_at, interpret=rehearse))(carry, 1, *xs, live)
        err = max(float(jnp.max(jnp.abs(got - want))),
                  float(jnp.max(jnp.abs(got_o - want_o))))
        same = bool(jnp.all(jnp.where(
            live[:, None, None, None, None], True, got == carry)))
        checks.append({"rows": b, "live": n_live, "max_abs_err": err,
                       "others_untouched": same})
        finite &= err < 1e-5 and same
        steps = jax.jit(steps, donate_argnums=0).lower(carry, *xs).compile()
        path = gd.step_path(steps.as_text())
        paths.add(path)
        best = float("inf")
        for _ in range(6):        # the first run is the warm-up
            t0 = time.perf_counter()
            carry, acc = jax.block_until_ready(steps(carry, *xs))
            best = min(best, time.perf_counter() - t0)
        finite &= bool(jnp.all(jnp.isfinite(acc)))
        timing.append(entry(
            "gdn_step", best / (calls * layers),
            shapes_hybrid.gdn_step(cfg, n_live), rows=b, live=n_live,
            path=path))
    finite &= rehearse or paths == {"pallas"}
    # The chunkwise form, both executions (the kernel is what ``gdn_chunk``
    # holds on a TPU; here under the interpreter in a rehearsal), at each
    # shape: µs a call and a VALID token, and once the kernel against the
    # ``jnp`` form on the same inputs.
    for b, t, fill in chunk_shapes:
        state, q, k, v, g, beta = inputs(jax.random.PRNGKey(7 + b), b, t)
        lens = jnp.asarray(np.random.default_rng(b).integers(
            round((2 * fill - 1) * t), t, b, endpoint=True), jnp.int32)
        valid = int(lens.sum())
        forms = {"kernel": functools.partial(gd.gdn_chunk,
                                             interpret=rehearse),
                 "jnp": gd.gdn_chunk_jnp}
        got = {}
        for form, fn in forms.items():
            chunks = jax.jit(chained_chunks(fn, lens, calls)).lower(
                state, q, k, v, g, beta).compile()
            path = gd.chunk_path(chunks.as_text())
            finite &= rehearse or (path == "pallas") == (form == "kernel")
            sec = best_of(chunks, (state, q, k, v, g, beta), calls)
            got[form] = jax.jit(fn)(state, q, k, v, g, beta, lens)
            finite &= bool(jnp.all(jnp.isfinite(got[form][1])))
            timing.append(entry(
                "gdn_chunk", sec, shapes_hybrid.gdn_chunk(cfg, valid),
                rows=b, tokens=t, valid_tokens=valid, form=form, path=path,
                us_per_valid_token=None if rehearse else sec * 1e6 / valid))
        live = (jnp.arange(t)[None] < lens[:, None])[..., None, None]
        err = max(
            float(jnp.max(jnp.abs((got["kernel"][0] - got["jnp"][0]) * live))),
            float(jnp.max(jnp.abs(got["kernel"][1] - got["jnp"][1]))))
        checks.append({"rows": b, "tokens": t, "valid_tokens": valid,
                       "chunk_max_abs_err": err})
        finite &= err < 1e-4
    emit({"phase": "gdn", "widths": widths, "calls": calls,
          "step_layers": layers, "checks": checks, "timing": timing,
          "peak": peak, "device": device, "ok": finite})
    return 0 if finite else 1


# --ssd: the Mamba-2 scan alone (ops/ssd.py) at granite-4.0-h-micro's
# published widths (benchmarks/chip/configs/granite-4.0-h-micro/config.json):
# one decode step in place in a 36-layer carry at 16 live rows of 16, at 17
# of 32 (chat-saturated's mean in the cell's bucket: the 15 rows that take
# no token should cost nothing) and 32 of 32;
# one prefill chunk at [8, 256] and at one 2048-token row. Beside it the
# paged decode kernel at this model's attention shape, both ways: its 8 KV
# heads of 64 lanes PAIRED into 4 rows of 128 (what the model serves:
# models/granite_hybrid.py:kv_pack) and as 8 heads of 64 lanes, two tokens
# a 128-lane row (the kernel's own packing, which no chip run had timed).
SSD_CONFIG = os.path.join(HERE, "benchmarks", "chip", "configs",
                          "granite-4.0-h-micro", "config.json")
SSD_STEP_ROWS = ((16, 16), (32, 17), (32, 32))    # (the carry's rows, live)
SSD_CHUNKS = ((8, 256), (1, 2048))                # (rows, tokens a row)
SSD_CALLS = 8
SSD_KERNEL_SHAPES = [
    {"name": "paired-4x128", "rows": 32, "live": 17, "lens": (96, 2600),
     "heads": 32, "kv_heads": 4, "dh": 128},
    {"name": "packed-8x64", "rows": 32, "live": 17, "lens": (96, 2600),
     "heads": 32, "kv_heads": 8, "dh": 64},
]


def ssd_child(rehearse: bool) -> int:
    """``--ssd``: times ``ssd_step_at`` and ``ssd_chunk`` (ops/ssd.py)
    alone on the chip, calls chained through the state inside one program
    (a step program through the layers of a donated carry in turn, in
    place), and prints microseconds a call beside the least time the
    chip's peaks allow their bytes and FLOPs
    (benchmarks/chip/lib/shapes_ssm.py's count of ONE layer, the LIVE rows'
    state), then the paged decode kernel at this model's two possible pool
    shapes. Fails where the step program holds the ``jnp`` form on a TPU.
    Run by no benchmark cell and no other phase."""
    import jax
    import jax.numpy as jnp

    from benchmarks.chip.lib import shapes_ssm
    from production_stack_tpu.ops import ssd
    from production_stack_tpu.ops.pallas.paged_attention import (
        paged_flash_decode_stats,
    )

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind}
    if dev.platform != "tpu" and not rehearse:
        emit({"phase": "ssd", "ok": False, "device": device,
              "error": "no TPU: nothing was timed"})
        return 1
    with open(SSD_CONFIG) as f:
        cfg = json.load(f)
    rows_list, chunk_list, calls = SSD_STEP_ROWS, SSD_CHUNKS, SSD_CALLS
    layers = cfg["layer_types"].count("mamba")
    if rehearse:
        cfg = dict(cfg, mamba_n_heads=4, mamba_d_head=16, mamba_d_state=128)
        rows_list, chunk_list, calls, layers = \
            ((2, 2), (3, 2)), ((2, 160),), 2, 2
    # ONE state-space layer of these widths, for the shapes' count.
    cfg = dict(cfg, layer_types=["mamba"])
    h, p, n = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    with open(os.path.join(HERE, "benchmarks", "chip", "peaks.json")) as f:
        peak = json.load(f)["by_device_kind"].get(dev.device_kind)

    def inputs(key, b, t):
        ks = jax.random.split(key, 7)
        x = jax.nn.silu(jax.random.normal(ks[0], (b, t, h, p)))
        bm, cm = (jax.nn.silu(jax.random.normal(ks[i], (b, t, n)))
                  for i in (1, 2))
        dt0 = jax.random.uniform(ks[3], (h,), minval=1e-3, maxval=1e-1)
        dt, da = ssd.gates(
            jax.random.normal(ks[4], (b, t, h)),
            jnp.log(jax.random.uniform(ks[5], (h,), minval=1.0, maxval=16.0)),
            ssd.softplus_inverse(dt0))
        state = 0.1 * jax.random.normal(ks[6], (b, h, p, n))
        return state, x, bm, cm, dt, da

    d_skip = jnp.ones((h,))

    def entry(name, sec, work, **more):
        out = {"op": name, **more, "bytes": work["bytes"],
               "flops": work["flops"], "us_per_call": None,
               "least_us": None, "roofline_pct": None}
        if peak and not rehearse:
            least = max(work["bytes"] / (peak["hbm_gbps"] * 1e9),
                        work["flops"] / (peak["bf16_tflops"] * 1e12))
            out.update(us_per_call=sec * 1e6, least_us=least * 1e6,
                       roofline_pct=100.0 * least / sec)
        return out

    timing, checks, finite, paths = [], [], True, set()
    for b, n_live in rows_list:
        state, *xs = inputs(jax.random.PRNGKey(b), b, 1)
        xs = tuple(v[:, 0] for v in xs)            # x, B, C, dt, dA
        carry = jnp.tile(state[:, None], (1, layers, 1, 1, 1))
        # The live rows spread over the carry, as a bucket's are.
        live = (jnp.arange(b) * n_live) % b < n_live

        def steps(carry, *xs):
            def one(i, both):
                carry, acc = both
                y, carry = ssd.ssd_step_at(carry, i % layers, *xs, d_skip,
                                           live, interpret=rehearse)
                return carry, acc + y
            return jax.lax.fori_loop(
                0, calls * layers, one, (carry, jnp.zeros((b, h, p))))

        # Once against the plain token: live rows agree, the others' state
        # is bit for bit what it was.
        want_y, want = jax.jit(ssd.ssd_step_at_jnp)(carry, 1, *xs, d_skip,
                                                    live)
        got_y, got = jax.jit(functools.partial(
            ssd.ssd_step_at, interpret=rehearse))(carry, 1, *xs, d_skip,
                                                  live)
        err = max(float(jnp.max(jnp.abs(got - want))),
                  float(jnp.max(jnp.abs(got_y - want_y))))
        same = bool(jnp.all(jnp.where(
            live[:, None, None, None, None], True, got == carry)))
        checks.append({"rows": b, "live": n_live, "max_abs_err": err,
                       "others_untouched": same})
        finite &= err < 1e-5 and same
        steps = jax.jit(steps, donate_argnums=0).lower(carry, *xs).compile()
        best = float("inf")
        for _ in range(6):        # the first run is the warm-up
            t0 = time.perf_counter()
            carry, acc = jax.block_until_ready(steps(carry, *xs))
            best = min(best, time.perf_counter() - t0)
        finite &= bool(jnp.all(jnp.isfinite(acc)))
        path = ssd.step_path(steps.as_text())
        paths.add(path)
        timing.append(entry(
            "ssd_step", best / (calls * layers),
            shapes_ssm.ssd_step(cfg, n_live), rows=b, live=n_live,
            path=path))
    finite &= rehearse or paths == {"pallas"}
    for b, tokens in chunk_list:
        state, *xs = inputs(jax.random.PRNGKey(7 + b), b, tokens)
        lens = jnp.full((b,), tokens, jnp.int32)

        @jax.jit
        def chunks(state, *xs):
            def one(_, carry):
                state, acc = carry
                y, state = ssd.ssd_chunk(state, *xs, d_skip, lens)
                return state, acc + y
            return jax.lax.fori_loop(
                0, calls, one, (state, jnp.zeros((b, tokens, h, p))))

        sec = best_of(chunks, (state, *xs), calls)
        finite &= bool(jnp.all(jnp.isfinite(chunks(state, *xs)[1])))
        timing.append(entry(
            "ssd_chunk", sec, shapes_ssm.ssd_chunk(cfg, b * tokens),
            rows=b, tokens=tokens, chunk=ssd.CHUNK))
    kernel = []
    for shape in SSD_KERNEL_SHAPES:
        if rehearse:
            shape = {**shape, "rows": 3, "live": 2, "lens": (17, 600)}
        case = kernel_timing_case(shape, dh=shape["dh"])
        sec = time_kernel(
            paged_flash_decode_stats, case,
            2 if rehearse else KERNEL_TIMING_CALLS, interpret=rehearse)
        item = {"shape": shape["name"], "rows": shape["rows"],
                "live_rows": shape["live"],
                "kv_tokens": int(case["kv_lens"].sum()),
                "kv_bytes": case["kv_bytes"], "us_per_call": None,
                "bytes_least_us": None, "roofline_pct": None}
        if peak and not rehearse:
            least = case["kv_bytes"] / (peak["hbm_gbps"] * 1e9)
            item.update(us_per_call=sec * 1e6, bytes_least_us=least * 1e6,
                        roofline_pct=100.0 * least / sec)
        kernel.append(item)
    emit({"phase": "ssd", "widths": {"heads": h, "d_head": p, "d_state": n},
          "calls": calls, "step_layers": layers, "checks": checks,
          "timing": timing, "paged_decode_kernel": kernel, "peak": peak,
          "device": device, "ok": finite})
    return 0 if finite else 1


# --ring: the window ring's decode step alone (ops/attention.py:
# window_ring_step) at the published widths of the two configurations that
# keep rings, each read from its benchmarks/chip/configs/<name>/config.json:
# MiMo-V2.5's 9 window layers of 8 KV heads x 128 slots, 64 queries a row
# (8 a KV head), keys of 192 lanes in rows of 256, sinks, in the cell's two
# decode buckets at 4, 13 (longctx-decode's mean in its 32-row bucket) and
# 32 live rows; Phi-4-mini-flash's 8 window layers of 10 packed KV rows x
# 512 slots x 128 lanes, 40 queries a row (4 a KV row), no sink, in its 48-
# and 8-row buckets at 8 / 24 / 44 (reasoning-saturated's mean) / 48 live.
# Positions past the window. The kernel beside the XLA statement on the
# same inputs, and at one (bucket, live) a shape the series of the kernel's
# two constants (a row's heads are one block: blocks of 4 / 2 / 1 heads
# read 4-30 us a call more in PR 53's first series, and Mosaic loads no
# single sublane at a traced head).
RING_CALLS = 8


def _ring_shapes():
    """name -> the rings' widths from the configuration's published
    ``config.json``, the (bucket, live) pairs to time, where the constants'
    series is taken and its (NUM_BUFS, FETCH_AHEAD) values (four buffers of
    Phi's 2.5 MiB blocks are past the kernel's VMEM: not in its series)."""
    from benchmarks.chip.lib import shapes_mimo, shapes_sambay

    def cfg_of(name):
        with open(os.path.join(HERE, "benchmarks", "chip", "configs", name,
                               "config.json")) as f:
            return json.load(f)

    mimo, phi = cfg_of("mimo-v2.5-ep16"), cfg_of("phi-4-mini-flash")
    dm, dp = shapes_mimo.dims(mimo), shapes_sambay.dims(phi)
    head = phi["hidden_size"] // phi["num_attention_heads"]
    return {
        "mimo-v2.5-ep16": {
            "layers": dm["windowed"], "window": dm["window"],
            "heads": dm["heads"], "kv_heads": dm["kv_window"],
            "dk": dm["dk"], "dv": dm["dv"], "sink": True,
            # a position of a layer's ring: its keys and values
            "row_bytes": shapes_mimo.ring_row_bytes(mimo),
            "rows": ((32, 1), (32, 4), (32, 13), (32, 32), (8, 4), (8, 8)),
            "series_at": (32, 13),
            "series": ((2, 1), (3, 1), (3, 2), (4, 2))},
        # A packed differential row: two KV heads side by side, the two
        # queries of a pair each over the row's whole width
        # (models/phi4flash.py:kv_rows, pack_queries).
        "phi-4-mini-flash": {
            "layers": dp["ring"], "window": dp["window"],
            "heads": phi["num_attention_heads"],
            "kv_heads": phi["num_key_value_heads"] // 2,
            "dk": 2 * head, "dv": 2 * head, "sink": False,
            "row_bytes": shapes_sambay.kv_bytes_per_token(phi),
            "rows": ((48, 8), (48, 24), (48, 44), (48, 48), (8, 4), (8, 8)),
            "series_at": (48, 44),
            "series": ((2, 1), (3, 1), (3, 2))},
    }


def ring_child(rehearse: bool) -> int:
    """``--ring``: times one decode step of a window layer alone on the
    chip at each shape of ``_ring_shapes`` (one line a shape), the calls
    chained through the layers of a donated pair of rings in turn (in
    place), every operand behind an ``optimization_barrier``
    (``chained_chunks`` says why): the kernel a program lowered for a TPU
    holds, the ``jnp`` statement and the chain with no step in it (what the
    harness itself costs a call), microseconds a LIVE row-layer beside
    the time of its bytes (the payload benchmarks/chip/lib counts, and as
    the rows lie in HBM: 192 lanes in 256, a tile of slots written a head),
    then the kernel under other values of its two constants. Fails where
    the kernel's program holds the ``jnp`` form on a TPU, or the two
    disagree. Run by no benchmark cell and no other phase."""
    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind}
    if dev.platform != "tpu" and not rehearse:
        emit({"phase": "ring", "ok": False, "device": device,
              "error": "no TPU: nothing was timed"})
        return 1
    with open(os.path.join(HERE, "benchmarks", "chip", "peaks.json")) as f:
        peak = json.load(f)["by_device_kind"].get(dev.device_kind)
    ok = True
    for name, shape in _ring_shapes().items():
        ok &= _ring_shape(name, shape, rehearse, peak, device)
    return 0 if ok else 1


def _ring_shape(name, shape, rehearse, peak, device) -> bool:
    """One shape of ``ring_child``: its line, and whether it held."""
    import jax
    import jax.numpy as jnp

    from production_stack_tpu.models.mimo_v2 import ring_width
    from production_stack_tpu.ops import attention as att
    from production_stack_tpu.ops.pallas import window_ring

    layers, w, dk, dv = (shape[k] for k in ("layers", "window", "dk", "dv"))
    h, hkv = shape["heads"], shape["kv_heads"]
    rows_list, series, calls = shape["rows"], shape["series"], RING_CALLS
    series_at = shape["series_at"]
    dtype = jnp.bfloat16
    if rehearse:    # the shape's queries a KV head over few heads and rows
        rows_list, series, series_at, calls, layers, h, hkv, dtype = \
            ((3, 2),), ((2, 1),), (3, 2), 1, 2, 2 * (h // hkv), 2, \
            jnp.float32
    scale = dk ** -0.5
    tile = window_ring.tile_rows(dtype)
    item = jnp.dtype(dtype).itemsize

    def inputs(b, n_live):
        ks = jax.random.split(jax.random.PRNGKey(b * 64 + n_live), 7)

        def draw(key, *shape):
            return jax.random.normal(key, shape, jnp.float32).astype(dtype)

        ring_k = jnp.pad(draw(ks[0], b, layers, hkv, w, dk),
                         ((0, 0),) * 4 + ((0, ring_width(dk) - dk),))
        ring_v = draw(ks[1], b, layers, hkv, w, dv)
        # The live rows spread over the bucket, as a bucket's are;
        # positions past the window, so every slot is seen.
        lens = ((jnp.arange(b) * n_live) % b < n_live).astype(jnp.int32)
        pos = jax.random.randint(ks[2], (b, 1), 2 * w, 64 * w)
        sink = 2.0 + jax.random.normal(ks[3], (h,), jnp.float32) \
            if shape["sink"] else None
        return (ring_k, ring_v), (draw(ks[4], b, 1, h, dk),
                                  draw(ks[5], b, 1, hkv, dk),
                                  draw(ks[6], b, 1, hkv, dv), pos, lens, sink)

    def chained(step):
        def steps(rings, q, k, v, pos, lens, sink):
            def one(i, both):
                rings, acc = both
                rings, *xs = jax.lax.optimization_barrier(
                    (rings, q, k, v, pos, lens, sink))
                o, rings = step(rings, i % layers, *xs[:5], scale=scale,
                                sink=xs[5])
                return tuple(rings), acc + o.astype(jnp.float32)
            return jax.lax.fori_loop(
                0, calls * layers, one,
                (rings, jnp.zeros((q.shape[0], 1, h, dv), jnp.float32)))
        return steps

    def timed(step, rings, xs):
        """(seconds a call, the program's execution of the step)"""
        steps = jax.jit(chained(step), donate_argnums=0).lower(
            rings, *xs).compile()
        rings = jax.tree.map(jnp.copy, rings)     # the caller keeps its own
        best = float("inf")
        for _ in range(6):        # the first run is the warm-up
            t0 = time.perf_counter()
            rings, acc = jax.block_until_ready(steps(rings, *xs))
            best = min(best, time.perf_counter() - t0)
        assert bool(jnp.all(jnp.isfinite(acc)))
        return best / (calls * layers), att.ring_step_path(steps.as_text())

    def entry(form, sec, n_live, b, path, **more):
        # A live row-layer: the payload the benchmark's count takes (W + 1
        # rows of a layer's keys and values), and what moves as the rows
        # lie: the slots' whole lane tiles read, a tile of slots a head
        # written.
        payload = (w + 1) * shape["row_bytes"]
        laid = hkv * (w + tile) * (ring_width(dk) + ring_width(dv)) * item
        out = {"form": form, "rows": b, "live": n_live, "path": path,
               **more, "payload_bytes_a_row_layer": payload,
               "laid_out_bytes_a_row_layer": laid, "us_per_call": None,
               "us_per_live_row_layer": None, "payload_us": None,
               "laid_out_us": None, "roofline_pct": None}
        if peak and not rehearse:
            gbps = peak["hbm_gbps"] * 1e9
            out.update(
                us_per_call=sec * 1e6,
                us_per_live_row_layer=sec * 1e6 / n_live,
                payload_us=payload / gbps * 1e6, laid_out_us=laid / gbps * 1e6,
                roofline_pct=100.0 * n_live * payload / gbps / sec)
        return out

    kernel = functools.partial(att.window_ring_step, interpret=rehearse)

    def harness(rings, at, q, k, v, pos, lens, *, scale, sink):
        # No step at all: what the chain itself costs a call (the barrier,
        # the loop, the sum of the outputs), which both forms pay here and a
        # decode program does not.
        return q[..., :dv], rings
    timing, checks, ok = [], [], True
    for b, n_live in rows_list:
        rings, xs = inputs(b, n_live)
        # Once against the jnp form: live rows agree within the dtype's
        # rounding, the rings bit for bit.
        args = (rings, 1, *xs[:5])
        want_o, want = jax.jit(functools.partial(
            att.window_ring_step_jnp, scale=scale))(*args, sink=xs[5])
        got_o, got = jax.jit(functools.partial(
            kernel, scale=scale))(*args, sink=xs[5])
        live = (xs[4] > 0)[:, None, None, None]
        err = float(jnp.max(jnp.abs(jnp.where(
            live, got_o.astype(jnp.float32) - want_o.astype(jnp.float32),
            0.0))))
        same = all(bool(jnp.all(a == c)) for a, c in zip(got, want))
        checks.append({"rows": b, "live": n_live, "max_abs_err": err,
                       "rings_equal": same})
        ok &= same and err < (1e-4 if dtype == jnp.float32 else 5e-2)
        for form, step in (("kernel", kernel),
                           ("jnp", att.window_ring_step_jnp),
                           ("harness", harness)):
            sec, path = timed(step, rings, xs)
            ok &= rehearse or (path == "pallas") == (form == "kernel")
            timing.append(entry(form, sec, n_live, b, path))
    # The kernel's constants: each value set where the kernel reads it, the
    # program traced anew.
    shipped = (window_ring.NUM_BUFS, window_ring.FETCH_AHEAD)
    b, n_live = series_at
    rings, xs = inputs(b, n_live)
    tuned = []
    try:
        for bufs, ahead in series:
            window_ring.NUM_BUFS, window_ring.FETCH_AHEAD = bufs, ahead
            jax.clear_caches()
            sec, path = timed(kernel, rings, xs)
            tuned.append(entry("kernel", sec, n_live, b, path, num_bufs=bufs,
                               fetch_ahead=ahead))
    finally:
        window_ring.NUM_BUFS, window_ring.FETCH_AHEAD = shipped
        jax.clear_caches()
    emit({"phase": "ring", "config": name,
          "widths": {"heads": h, "kv_heads": hkv, "window": w, "dk": dk,
                     "dv": dv, "ring_lanes": [ring_width(dk),
                                              ring_width(dv)]},
          "calls": calls, "step_layers": layers,
          "shipped": dict(zip(("num_bufs", "fetch_ahead"), shipped)),
          "checks": checks, "timing": timing, "series": tuned, "peak": peak,
          "device": device, "ok": ok})
    return ok


# --hc: the stream mix alone (ops/hyper_connections.py) at Xing4.0-29B-A4B's
# published widths (benchmarks/chip/configs/xing4.0-29b-a4b-d7/config.json):
# the rows of a decode step and the tokens of a prefill chunk.
HC_CONFIG = os.path.join(HERE, "benchmarks", "chip", "configs",
                         "xing4.0-29b-a4b-d7", "config.json")
HC_TOKENS = (16, 32, 1024)
HC_SUBLAYERS = 14       # of one forward: each with its own phi, b, a
HC_CALLS = 20


def hc_child(rehearse: bool) -> int:
    """``--hc``: times one sublayer's stream mix alone on the chip (the
    pre-mix: norm, projections, sigmoid, Sinkhorn, ``H_pre x``; then the
    post-mix ``H_res x + H_post^T branch``), chained inside one program
    over the 14 sublayers' parameters in turn with the streams carried in
    bf16 as the model carries them, and prints microseconds a sublayer
    beside the least time the chip's memory allows its bytes
    (benchmarks/chip/lib/shapes_hc.py:mix of ONE sublayer: the streams read
    once and read and written once, ``phi`` once). Checked once against the
    einsum form of the same equations. Run by no benchmark cell and no other
    phase."""
    import jax
    import jax.numpy as jnp

    from benchmarks.chip.lib import shapes_hc
    from production_stack_tpu.models import deepseek_v3 as ds
    from production_stack_tpu.models.config import ModelConfig
    from production_stack_tpu.ops import hyper_connections as hc

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind}
    if dev.platform != "tpu" and not rehearse:
        emit({"phase": "hc", "ok": False, "device": device,
              "error": "no TPU: nothing was timed"})
        return 1
    with open(HC_CONFIG) as f:
        cfg = json.load(f)
    tokens_list, sublayers, calls = HC_TOKENS, HC_SUBLAYERS, HC_CALLS
    dtype = jnp.bfloat16
    if rehearse:
        cfg.update(hidden_size=64)
        tokens_list, sublayers, calls, dtype = (4, 24), 2, 2, jnp.float32
    mc = ModelConfig.from_hf_config(cfg)
    n, d = mc.hc_mult, mc.hidden_size
    one_sublayer = dict(cfg, num_hidden_layers=1, first_k_dense_replace=0)
    with open(os.path.join(HERE, "benchmarks", "chip", "peaks.json")) as f:
        peak = json.load(f)["by_device_kind"].get(dev.device_kind)
    lp = ds._init_mix(mc, jax.random.PRNGKey(5), -(-sublayers // 2))
    phi = jnp.concatenate([lp["hc_attn_phi"], lp["hc_ffn_phi"]])[:sublayers]
    b = jnp.concatenate([lp["hc_attn_b"], lp["hc_ffn_b"]])[:sublayers]
    a = jnp.concatenate([lp["hc_attn_a"], lp["hc_ffn_a"]])[:sublayers]
    kw = dict(iters=mc.hc_sinkhorn_iters, eps=mc.hc_eps,
              norm_eps=mc.rms_norm_eps, clamp=mc.hc_res_clamp)

    def sublayer(x, branch, at):
        mats = hc.mix_matrices(x, phi[at], b[at], a[at], **kw)
        h = hc.pre(x, mats[0]).astype(x.dtype)
        # The sublayer itself is not timed: its branch is its input, scaled.
        return hc.post(x, branch + 0.1 * h, mats[1], mats[2]).astype(x.dtype)

    timing, checks, ok = [], [], True
    for tokens in tokens_list:
        ks = jax.random.split(jax.random.PRNGKey(tokens), 3)
        x = (jax.random.normal(ks[0], (1, tokens, 1, d))
             + 0.3 * jax.random.normal(ks[1], (n, tokens, 1, d))).astype(dtype)
        branch = jax.random.normal(ks[2], (tokens, 1, d)).astype(dtype)
        # Once against the einsum form of the same equations.
        mats = jax.jit(lambda x: hc.mix_matrices(x, phi[0], b[0], a[0],
                                                 **kw))(x)
        with jax.default_matmul_precision("highest"):
            xf = x.astype(jnp.float32)
            want = jnp.einsum("...ij,j...d->i...d", mats[2], xf) \
                + jnp.einsum("...i,...d->i...d", mats[1],
                             branch.astype(jnp.float32))
        got = jax.jit(hc.post)(x, branch, mats[1], mats[2])
        err = float(jnp.max(jnp.abs(got - want)))
        sums = float(jnp.max(jnp.abs(mats[2].sum(-1) - 1))), \
            float(jnp.max(jnp.abs(mats[2].sum(-2) - 1)))
        checks.append({"op": "hc_post", "tokens": tokens, "max_abs_err": err,
                       "row_col_sums_off_1": sums})
        ok &= err < 1e-4 and max(sums) < 1e-4

        @jax.jit
        def chained(x, branch):
            return jax.lax.fori_loop(
                0, calls * sublayers,
                lambda i, x: sublayer(x, branch, i % sublayers), x)

        program = chained.lower(x, branch).compile()
        best = float("inf")
        for _ in range(6):        # the first run is the warm-up
            t0 = time.perf_counter()
            out = jax.block_until_ready(program(x, branch))
            best = min(best, time.perf_counter() - t0)
        ok &= bool(jnp.all(jnp.isfinite(out.astype(jnp.float32))))
        work = shapes_hc.mix(one_sublayer, tokens, 1)
        work = {k: v / 2 for k, v in work.items()}     # ONE of its two
        sec = best / (calls * sublayers)
        entry = {"op": "hc_pre+hc_post", "tokens": tokens,
                 "bytes": work["bytes"], "flops": work["flops"],
                 "us_per_sublayer": None, "least_us": None,
                 "roofline_pct": None}
        if peak and not rehearse:
            least = max(work["bytes"] / (peak["hbm_gbps"] * 1e9),
                        work["flops"] / (peak["bf16_tflops"] * 1e12))
            entry.update(us_per_sublayer=sec * 1e6, least_us=least * 1e6,
                         roofline_pct=100.0 * least / sec,
                         us_per_forward=sec * 1e6 * HC_SUBLAYERS)
        timing.append(entry)
    finite = ok and not rehearse
    emit({"phase": "hc", "timing": timing, "checks": checks,
          "execution": hc.EXECUTION, "peak": peak, "device": device,
          "ok": finite})
    return 0 if finite else 1


# --moe: the latent decode kernel and the experts' grouped matmuls alone, at
# kanana-2-30b-a3b's published widths (benchmarks/chip/configs/
# kanana-2-30b-a3b-d8/config.json): rows of a decode bucket and how many of
# them hold a sequence, and the tokens of a prefill chunk.
MOE_CONFIG = os.path.join(HERE, "benchmarks", "chip", "configs",
                          "kanana-2-30b-a3b-d8", "config.json")
MOE_DECODE_ROWS = ((32, 28), (64, 40))
MOE_PREFILL_TOKENS = 1024
MOE_LAYERS = 3          # sparse layers of experts held (2.8 GB in bf16)
MOE_CALLS = 40


def moe_child(rehearse: bool) -> int:
    """``--moe``: times the paged decode kernel over latent rows
    (ops/pallas/paged_attention.py) and the experts' sorted grouped matmuls
    (ops/moe.py:expert_ffn over ops/pallas/grouped_matmul.py) alone on the
    chip, calls chained inside one program over the layers of one pool and
    one stack of experts in turn, and prints µs a call beside the least
    time the chip's peaks allow their bytes and FLOPs
    (benchmarks/chip/lib/shapes_moe.py's count of ONE layer: the pool rows
    of the live contexts; the matrices of the experts the routing TOUCHED).
    Each is checked once against its XLA form, and the kernel's program
    writes a token's row into the donated pool before it reads it: a
    pool-shaped ``copy`` in that program fails the run. Run by no benchmark
    cell and no other phase."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.chip.lib import shapes_moe
    from production_stack_tpu.ops import moe
    from production_stack_tpu.ops.attention import (KVView, attend,
                                                    gather_window)
    from production_stack_tpu.ops.kv_write import (pool_copies,
                                                   write_token_runs)
    from production_stack_tpu.ops.pallas.paged_attention import (
        paged_flash_decode_latent_stats,
    )

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind}
    if dev.platform != "tpu" and not rehearse:
        emit({"phase": "moe", "ok": False, "device": device,
              "error": "no TPU: nothing was timed"})
        return 1
    with open(MOE_CONFIG) as f:
        cfg = json.load(f)
    rows_list, tokens, layers, calls = (
        MOE_DECODE_ROWS, MOE_PREFILL_TOKENS, MOE_LAYERS, MOE_CALLS)
    dtype = jnp.bfloat16
    if rehearse:
        cfg.update(hidden_size=64, num_attention_heads=4, kv_lora_rank=128,
                   qk_rope_head_dim=8, qk_nope_head_dim=16, v_head_dim=16,
                   n_routed_experts=16, num_experts_per_tok=3,
                   moe_intermediate_size=32, num_hidden_layers=3)
        rows_list, tokens, layers, calls = ((4, 3),), 24, 2, 2
        dtype = jnp.float32
    d = shapes_moe.dims(cfg)
    one_layer = dict(cfg, num_hidden_layers=1, first_k_dense_replace=0)
    with open(os.path.join(HERE, "benchmarks", "chip", "peaks.json")) as f:
        peak = json.load(f)["by_device_kind"].get(dev.device_kind)

    def entry(name, sec, work, **more):
        out = {"op": name, **more, "bytes": work["bytes"],
               "flops": work["flops"], "us_per_call": None,
               "least_us": None, "roofline_pct": None}
        if peak and not rehearse:
            least = max(work["bytes"] / (peak["hbm_gbps"] * 1e9),
                        work["flops"] / (peak["bf16_tflops"] * 1e12))
            out.update(us_per_call=sec * 1e6, least_us=least * 1e6,
                       roofline_pct=100.0 * least / sec)
        return out

    timing, checks, ok = [], [], True
    bs, width, rank = 16, d["pool_row"], d["rank"]
    scale = (d["nope"] + d["rope"]) ** -0.5
    # ---- the latent kernel over a pool it also writes ---------------------
    for b, live in rows_list:
        case = kernel_timing_case(
            {"rows": b, "live": live, "lens": (96, 2624), "heads": d["heads"],
             "kv_heads": 1}, dh=width, bs=bs, layers=d["layers"], seed=b)
        pool = case["k_pool"].astype(dtype).at[..., d["row"]:].set(0)
        q = case["q"].astype(dtype).at[..., d["row"]:].set(0)
        tables, kv_lens = case["tables"], case["kv_lens"]
        mean_context = float(np.asarray(kv_lens).sum()) / live
        # Once against the XLA path over the same rows gathered.
        got, m, l = paged_flash_decode_latent_stats(
            q, pool, tables, kv_lens, 1, block_size=bs, value_dim=rank,
            scale=scale, interpret=rehearse)
        win, _ = gather_window(pool, pool[..., :0], tables, bs)
        zero = jnp.zeros((b, 1, 1, width), dtype)
        want = attend(q[:, None], zero, None, kv_lens[:, None],
                      jnp.zeros((b,), jnp.int32), KVView(
                          win[1], None, kv_lens), scale=scale,
                      value_dim=rank)[:, 0]
        alive = (kv_lens > 0)[:, None, None]
        err = float(jnp.max(jnp.abs(jnp.where(
            alive, got.astype(jnp.float32) - want.astype(jnp.float32), 0))))
        checks.append({"op": "latent_decode", "rows": b, "live": live,
                       "max_abs_err": err})
        ok &= err < (1e-4 if rehearse else 3e-2)

        def chain(pool, q, tables, kv_lens):
            def one(i, carry):
                pool, q = carry
                layer = i % pool.shape[0]
                out, _, _ = paged_flash_decode_latent_stats(
                    q, pool, tables, kv_lens, layer, block_size=bs,
                    value_dim=rank, scale=scale, interpret=rehearse)
                # The step's own row goes into the pool where it lies.
                row = jnp.pad(out, ((0, 0), (0, 0), (0, width - rank)))
                new = jnp.zeros((pool.shape[0], 1, b, 1, width), pool.dtype
                                ).at[layer, 0, :, 0].set(row[:, 0])
                (pool,) = write_token_runs(
                    (pool,), (new,), tables, jnp.maximum(kv_lens - 1, 0),
                    (kv_lens > 0).astype(jnp.int32), bs)
                return pool, q + row * 0
            return jax.lax.fori_loop(0, calls * pool.shape[0], one,
                                     (pool, q))

        program = jax.jit(chain, donate_argnums=0).lower(
            pool, q, tables, kv_lens).compile()
        copies = pool_copies(program.as_text(), [pool])
        ok &= rehearse or not copies
        best = float("inf")
        for _ in range(6):        # the first run is the warm-up
            t0 = time.perf_counter()
            pool, out_q = jax.block_until_ready(
                program(pool, q, tables, kv_lens))
            best = min(best, time.perf_counter() - t0)
        ok &= bool(jnp.all(jnp.isfinite(out_q.astype(jnp.float32))))
        timing.append(entry(
            "latent_decode+row_write", best / (calls * d["layers"]),
            shapes_moe.mla_decode(one_layer, live, mean_context),
            rows=b, live=live, mean_context=round(mean_context),
            pool_copies=len(copies)))

    # ---- the experts ----------------------------------------------------------
    e, k, f, h = d["experts"], d["top_k"], d["expert_ffn"], d["hidden"]
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    w_gate_up = (jax.random.normal(ks[0], (layers * e, h, 2 * f), jnp.float32)
                 * h ** -0.5).astype(dtype)
    w_down = (jax.random.normal(ks[1], (layers * e, f, h), jnp.float32)
              * f ** -0.5).astype(dtype)
    for name, n, live in [*(("decode", b, lv) for b, lv in rows_list),
                          ("prefill", tokens, tokens)]:
        x = jax.random.normal(ks[2], (n, h), jnp.float32).astype(dtype)
        idx = jnp.stack([jax.random.permutation(kk, e)[:k] for kk in
                         jax.random.split(ks[3], n)]).astype(jnp.int32)
        w = jnp.full((n, k), 1.0 / k, jnp.float32)
        valid = jnp.arange(n) < live
        touched = int(jnp.unique(idx[:live]).shape[0])
        got, stats = jax.jit(functools.partial(
            moe.expert_ffn, interpret=rehearse))(
                x, idx + e, w, valid, w_gate_up, w_down)
        # Once against ragged_dot over the same sorted rows.
        pair = jnp.where(valid[:, None], idx + e, layers * e).reshape(-1)
        order = jnp.argsort(pair, stable=True)
        sizes = jnp.zeros((layers * e,), jnp.int32).at[pair].add(
            1, mode="drop")
        hgu = jax.lax.ragged_dot(x[order // k], w_gate_up, sizes,
                                 preferred_element_type=jnp.float32)
        out = jax.lax.ragged_dot(
            (jax.nn.silu(hgu[:, :f]) * hgu[:, f:]).astype(dtype), w_down,
            sizes, preferred_element_type=jnp.float32)
        want = jnp.where(valid[:, None], jnp.sum(
            out[jnp.argsort(order)].reshape(n, k, h) / k, axis=1), 0.0)
        err = float(jnp.max(jnp.abs(got - want)))
        checks.append({"op": f"expert_ffn[{name}]", "tokens": n,
                       "live": live, "max_abs_err": err,
                       "experts_touched": [touched, int(stats[1])]})
        ok &= err < (1e-4 if rehearse else 3e-2) and int(stats[1]) == touched

        @jax.jit
        def chained(x, idx, w, valid, w_gate_up, w_down):
            def one(i, acc):
                y, _ = moe.expert_ffn(
                    (x + acc.astype(x.dtype) * 0), idx + (i % layers) * e,
                    w, valid, w_gate_up, w_down, interpret=rehearse)
                return acc + y
            return jax.lax.fori_loop(0, calls, one,
                                     jnp.zeros((n, h), jnp.float32))

        sec = best_of(chained, (x, idx, w, valid, w_gate_up, w_down), calls)
        timing.append(entry(
            f"expert_ffn[{name}]", sec,
            shapes_moe.moe_gmm(one_layer, 1, live * k, touched),
            tokens=n, live=live, experts_touched=touched))
    emit({"phase": "moe", "widths": {k_: d[k_] for k_ in (
        "heads", "row", "pool_row", "rank", "experts", "top_k", "expert_ffn",
        "hidden")}, "calls": calls, "expert_layers": layers,
        "checks": checks, "timing": timing, "peak": peak, "device": device,
        "ok": bool(ok)})
    return 0 if ok else 1


# The prefill chunk's attention alone (``--prefill``): ONE timing a pool kind
# and form of dispatch (ops/pallas/paged_attention.py: over K/V rows the
# packed kernel and the rectangle kernel; over latent rows one body, a
# rectangle laid as a row, since PR 56). Dh 128, block 16; ``rows`` says a
# rectangle, ``segments`` a packed row.
PREFILL_TIMING_SHAPES = [
    # K/V rows, packed: cell 3's dispatch, 16 segments that fill a
    # 2048-token row behind histories of 0-300 tokens (16 query heads over 2
    # KV heads), and cell 2's, one 256-token suffix behind 6000 tokens.
    {"name": "packed-chat-saturated", "t": 2048, "segments": 16,
     "hist": (0, 300), "heads": 16, "kv_heads": 2},
    {"name": "packed-agent-prefix", "t": 256, "segments": 1,
     "hist": (6000, 6000), "heads": 32, "kv_heads": 8},
    # K/V rows, rectangles: the widest and the longest dispatch of cell 4's
    # full layers (30 query and 30 KV heads, so a KV head's score block is
    # only TQ rows) and of cell 7's attention layers (32 query heads over 4
    # KV rows of two paired heads), the rows of a wide one behind 0-1500
    # tokens, its last row padding.
    {"name": "hybrid-16x128", "rows": 16, "t": 128, "hist": (0, 1500),
     "heads": 30, "kv_heads": 30},
    {"name": "hybrid-1x2048", "rows": 1, "t": 2048, "hist": (0, 0),
     "heads": 30, "kv_heads": 30},
    {"name": "granite-16x128", "rows": 16, "t": 128, "hist": (0, 1500),
     "heads": 32, "kv_heads": 4},
    {"name": "granite-1x2048", "rows": 1, "t": 2048, "hist": (0, 0),
     "heads": 32, "kv_heads": 4},
    # LATENT rows, at the shape both latent configurations share
    # (kanana-2-30b-a3b-d8, xing4.0-29b-a4b-d7: 32 heads over one 640-lane
    # row a token, 576 of them the key, the first 512 the values): cells 5
    # and 6's packed row at a 1024-token budget, 8 segments of 128 tokens
    # behind the cached system prompt and 8 cut at random behind 64-320
    # tokens; and the 8 x 128 rectangle of a runner with an adapter.
    {"name": "packed-latent-8x128", "t": 1024, "segments": 8, "equal": True,
     "hist": (64, 64), "heads": 32, "row": 640, "key": 576, "values": 512},
    {"name": "packed-latent-chat-saturated", "t": 1024, "segments": 8,
     "hist": (64, 320), "heads": 32, "row": 640, "key": 576, "values": 512},
    {"name": "latent-8x128", "rows": 8, "t": 128, "hist": (64, 320),
     "heads": 32, "row": 640, "key": 576, "values": 512},
]
PREFILL_TIMING_CALLS = 64
PREFILL_MAX_ABS_ERR = 2e-2     # bf16 outputs of unit-variance values


def prefill_child(rehearse: bool) -> int:
    """``--prefill``: the Pallas flash prefill kernels alone on the chip at
    the benchmark's prefill shapes (PREFILL_TIMING_SHAPES: over K/V rows and
    over latent rows, a packed row through ``paged_flash_prefill_packed`` /
    ``_packed_latent`` and a rectangle through ``paged_flash_prefill``, the
    K/V rectangle kernel, and ``paged_flash_prefill_latent``, which lays it
    as a row of the packed body), checked once against
    ``window_attention`` over the gathered history of the sequences taken
    apart, a row each, then timed: calls chained through the queries inside
    one program over the layers of one pool, every operand an argument
    behind an ``optimization_barrier`` (``chained_chunks``'s lesson: what
    does not change between calls is otherwise lifted out of the loop),
    against the larger of the least times its bytes and its FLOPs allow
    (what a call must do: each valid query against its sequence's history
    and the chunk's keys up to itself; the history's rows once, the chunk's
    operands and the output once). Run by no benchmark cell."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from production_stack_tpu.ops.attention import (gather_window,
                                                    unpack_segments,
                                                    window_attention)
    from production_stack_tpu.ops.pallas import paged_attention as pa

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind}
    if dev.platform != "tpu" and not rehearse:
        emit({"phase": "prefill", "ok": False, "device": device,
              "error": "no TPU: nothing was timed"})
        return 1
    interpret = dev.platform == "cpu"
    with open(os.path.join(HERE, "benchmarks", "chip", "peaks.json")) as f:
        peak = json.load(f)["by_device_kind"].get(dev.device_kind)
    dh, bs, layers = 128, 16, 4
    calls = 2 if rehearse else PREFILL_TIMING_CALLS

    timing, checks, ok = [], [], True
    for shape in PREFILL_TIMING_SHAPES:
        latent, packed = "row" in shape, "segments" in shape
        if rehearse:
            shape = {**shape, "t": 64 if packed else 32, "hist": (0, 40),
                     "heads": 16 if latent else 4, "kv_heads": 2,
                     "row": 256, "key": 192, "values": 128,
                     **({"segments": min(shape["segments"], 3)} if packed
                        else {"rows": min(shape["rows"], 3)})}
        rng = np.random.default_rng(len(timing))
        t, h = shape["t"], shape["heads"]
        n = shape["segments"] if packed else shape["rows"]
        if packed:
            # Lengths that fill the row: an equal cut of t into n segments,
            # or a random one.
            cuts = np.arange(1, n) * (t // n) if shape.get("equal") else \
                np.sort(rng.choice(np.arange(1, t), n - 1, replace=False))
            lens = np.diff(np.concatenate([[0], cuts, [t]]))
        else:
            lens = rng.integers(t * 3 // 4, t + 1, n)
            if n > 2:
                lens[-1] = 0        # a padded row of the rectangle
        hist = rng.integers(shape["hist"][0], shape["hist"][1] + 1, n) \
            * (lens > 0)
        live = -(-(hist + lens) // bs) * (lens > 0)
        tables = np.zeros((n, int(max(live)) + 4), np.int32)
        order = 1 + rng.permutation(int(live.sum()))
        at = 0
        for i in range(n):
            tables[i, :live[i]] = order[at:at + live[i]]
            at += live[i]
        slots = (1 + int(live.sum())) * bs
        keys = jax.random.split(jax.random.PRNGKey(n), 5)
        tables = jnp.asarray(tables)
        seg_lens = jnp.asarray(lens, jnp.int32)
        kv_lens = jnp.asarray(hist, jnp.int32)
        # What a call must do: a valid query i of a sequence against its
        # history and chunk keys 0..i, two products a (query, key, head).
        pairs = int(np.sum(lens * hist + lens * (lens + 1) // 2))
        lead = (1, t) if packed else (n, t)

        def normal(key, *dims):
            return jax.random.normal(key, dims, jnp.bfloat16)

        # ``own``: the chunk's operands beside q (K and V, or the latent
        # rows); they and the pools are ARGUMENTS of the timed programs (a
        # closed-over array is a constant of the program: the hybrid's
        # window as one took the host's 40 GiB in the compiler, PR 35).
        if latent:
            w, dv = shape["row"], shape["values"]
            scale = 192 ** -0.5    # 128 + 64 lanes a head before absorption
            q, own = normal(keys[0], *lead, h, w), (
                normal(keys[1], *lead, 1, w),)
            pools = (normal(keys[3], layers, 1, slots, w),)
            entry = pa.paged_flash_prefill_packed_latent if packed \
                else pa.paged_flash_prefill_latent

            def kernel(q, layer, rows, seg_lens, tables, kv_lens, pool):
                return entry(q, rows, seg_lens, pool, tables, kv_lens, layer,
                             block_size=bs, value_dim=dv, scale=scale,
                             interpret=interpret)

            # As ops/attention.py:_attend_latent calls it over a window.
            def window(q, rows, positions, seg_lens, kv_lens, wins):
                return window_attention(
                    q, rows, rows, positions, seg_lens, wins[0][1],
                    wins[0][1], kv_lens, scale=scale,
                    qblock=max(16, 2048 // h))[..., :dv]

            gather = lambda pool: gather_window(   # noqa: E731
                pool, pool[..., :0], tables, bs)[:1]
            # Scores over the key's lanes, values over theirs; a pool row
            # is read whole, padding included.
            flops = 2 * pairs * h * (shape["key"] + dv)
            nbytes = 2 * (int(hist.sum()) * w
                          + int(lens.sum()) * (h * w + w + h * dv))
        else:
            hkv, dv = shape["kv_heads"], dh
            q, own = normal(keys[0], *lead, h, dh), (
                normal(keys[1], *lead, hkv, dh),
                normal(keys[2], *lead, hkv, dh))
            pools = (normal(keys[3], layers, hkv, slots, dh),
                     normal(keys[4], layers, hkv, slots, dh))
            entry = pa.paged_flash_prefill_packed if packed \
                else pa.paged_flash_prefill

            def kernel(q, layer, k, v, seg_lens, tables, kv_lens, *pools):
                # The rectangle kernel takes its rows' positions.
                where = () if packed else (kv_lens[:, None] + jnp.arange(
                    t, dtype=jnp.int32)[None],)
                return entry(q, k, v, *where, seg_lens, *pools, tables,
                             kv_lens, layer, block_size=bs,
                             interpret=interpret)

            def window(q, k, v, positions, seg_lens, kv_lens, wins):
                return window_attention(q, k, v, positions, seg_lens,
                                        wins[0][1], wins[1][1], kv_lens)

            gather = lambda kp, vp: gather_window(  # noqa: E731
                kp, vp, tables, bs)
            flops = 4 * pairs * h * dh
            nbytes = 2 * (int(hist.sum()) * hkv * dh * 2
                          + int(lens.sum()) * (2 * h + 2 * hkv) * dh)
        held = (*own, seg_lens, tables, kv_lens, *pools)

        # The check: the sequences taken apart, a row each of t tokens.
        got = jax.jit(kernel)(q, 1, *held).astype(jnp.float32)
        apart = (lambda x: x[0][unpack_segments(seg_lens, t)[0]]) if packed \
            else (lambda x: x)
        positions = kv_lens[:, None] + jnp.arange(t, dtype=jnp.int32)[None]
        want = jax.jit(window)(
            apart(q), *(apart(x) for x in own), positions, seg_lens, kv_lens,
            gather(*pools)).astype(jnp.float32)
        valid = (jnp.arange(t)[None] < seg_lens[:, None])[..., None, None]
        err = float(jnp.max(jnp.where(valid, jnp.abs(apart(got) - want),
                                      0.0)))
        finite = bool(jnp.all(jnp.isfinite(got)))
        checks.append({"shape": shape["name"], "max_abs_err": err,
                       "bound": PREFILL_MAX_ABS_ERR, "finite": finite})
        ok = ok and finite and err <= PREFILL_MAX_ABS_ERR

        def fed_back(x, *a):
            # A call's output goes back into the queries (a latent call's is
            # narrower: one token's, in place).
            out = kernel(x, *a)
            return x.at[:1, :1, :, :dv].set(out[:1, :1]) if latent else out

        def chained(x, *held):
            def one(i, x):
                x, args = jax.lax.optimization_barrier((x, held))
                return fed_back(x, i % layers, *args)
            return jax.lax.fori_loop(0, calls, one, x)

        sec = best_of(jax.jit(chained), (q, *held), calls)
        line = {"shape": shape["name"],
                "form": "packed" if packed else "rectangle",
                "rows": lead[0], "t": t, "seg_lens": [int(x) for x in lens],
                "history": [int(x) for x in hist], "flops": flops,
                "bytes": nbytes, "us_per_call": None, "least_us": None,
                "roofline_pct": None}
        if peak and not rehearse:
            least = max(nbytes / (peak["hbm_gbps"] * 1e9),
                        flops / (peak["bf16_tflops"] * 1e12))
            line.update(us_per_call=sec * 1e6, least_us=least * 1e6,
                        roofline_pct=100.0 * least / sec)
        timing.append(line)
        # As it goes, beside the phase's one line at the end: a later
        # shape that dies keeps the earlier ones' numbers.
        print(json.dumps(line), file=sys.stderr, flush=True)
    emit({"phase": "prefill", "interpret": interpret, "checks": checks,
          "timing": timing, "peak": peak, "device": device,
          "ok": ok and (rehearse or not interpret)})
    return 0 if ok else 1


def phase_serve(model, engine_args, attn: str) -> dict:
    """One boot of engine + router and a handful of requests through the
    router."""
    line = {"phase": f"serve[{attn}]", "model": model}

    def body(stack):
        m0 = scrape(stack.engine_url)
        # Non-streamed, then the same prompt streamed (a whole-prompt
        # prefix hit); two concurrent streams; then the long prompt.
        bodies = [chat_body(model, QUESTIONS[0], stream=False),
                  *(chat_body(model, q, stream=True)
                    for q in (QUESTIONS[0], *QUESTIONS[1:]))]
        results = [None] * len(bodies)

        def run(i):
            results[i] = chat(stack.router_url, bodies[i])

        run(0)
        run(1)
        pair = [threading.Thread(target=run, args=(i,)) for i in (2, 3)]
        for t in pair:
            t.start()
        for t in pair:
            t.join(timeout=600)
        run(4)

        faults = []
        for i, (body, res) in enumerate(zip(bodies, results)):
            faults += [f"request {i}: {f}" for f in (
                check_completion(res, body) if res else ["no answer"])]
        if results[0] and results[1] and \
                results[0]["text"] != results[1]["text"]:
            faults.append("streamed repeat differs from non-streamed text")

        # Exact counts, by the engine's own counters.
        m1 = scrape(stack.engine_url)
        usages = [r["usage"] for r in results if r and r["usage"]]
        for series, key in (("vllm:prompt_tokens_total", "prompt_tokens"),
                            ("vllm:generation_tokens_total",
                             "completion_tokens")):
            counted = m1.get(series, 0.0) - m0.get(series, 0.0)
            if counted != sum(u[key] for u in usages):
                faults.append(f"engine counted {counted} {key}, usage says "
                              f"{sum(u[key] for u in usages)}")
        hits = (m1.get("vllm:gpu_prefix_cache_hits_total", 0.0)
                - m0.get("vllm:gpu_prefix_cache_hits_total", 0.0))
        if hits <= 0:
            faults.append("prefix-hit counter did not move")
        if m1.get("vllm:num_requests_running") != 0:
            faults.append(f"num_requests_running="
                          f"{m1.get('vllm:num_requests_running')} at the end")
        line.update(
            statuses=[r["status"] if r else None for r in results],
            prefix_hit_tokens=hits,
            greedy_toks=[r["toks"] if r else [] for r in results[1:]],
            bytes_in_use_after=_bytes_in_use(stack.engine_url),
            faults=faults, ok=not faults,
        )

    return run_phase(line, body, model,
                     [*engine_args, "--attn-impl", attn])


def _logprob_requests(model, router_url):
    """Every question once, streamed with logprobs: [(body, result)]."""
    bodies = [chat_body(model, q, stream=True, logprobs=True)
              for q in QUESTIONS]
    return [(body, chat(router_url, body, {"x-user-id": f"user-{i}"}))
            for i, body in enumerate(bodies)]


def phases_four_chips(model, engine_args) -> list:
    """The paths that exist only across chips — four one-chip replicas
    behind the router, and one tp=4 engine — and the one-chip reference
    they are compared with. Paged decode throughout: tp=4 is the
    shard_map'd kernel path, and token identity needs one path. Prefill
    is one path for the replicas and the reference (one-chip engines: the
    flash kernel over the pool) and another for tp=4 (a kv-head-sharded
    pool keeps its gathered window and ``window_attention``: the same
    mathematics and precision in another order), which is why tp=4 is
    held to a first-token logprob within TP_LOGPROB_TOL and its token
    agreement is printed, not judged. Prefix
    caching off: greedy tokens are equal only where the arithmetic is, and
    a replica that meets a prompt cold prefills it whole while the
    reference, having served its neighbours, prefills only the tail behind
    the cached system prompt — other chunk shapes, other bf16 rounding, and
    with random weights a near-tie flips (seen on the chip: 8 of 9 replica
    answers matched with caching on). The one-chip run covers the cache."""
    args = [*engine_args, "--attn-impl", "paged",
            "--no-enable-prefix-caching"]

    # ---- reference: one engine, one chip
    ref = {"phase": "reference[1 chip]", "model": model}
    ref_toks, ref_lp = [], []

    def reference(stack):
        faults = []
        for i, (body, res) in enumerate(
                _logprob_requests(model, stack.router_url)):
            faults += [f"request {i}: {f}" for f in
                       check_completion(res, body)]
            ref_toks.append(res["toks"])
            ref_lp.append(res["logprobs"])
        ref.update(greedy_toks=ref_toks,
                   first_logprobs=[lp[:1] for lp in ref_lp],
                   faults=faults, ok=not faults)

    run_phase(ref, reference, model, args)
    if not ref["ok"]:
        return [ref]    # nothing to compare the cross-chip paths with

    # ---- replicas: four one-chip engines behind one router
    rep = {"phase": "replicas[4 x 1 chip]", "model": model}

    def replicas(stack):
        faults = []
        served = [0] * 4            # requests each engine answered
        matched = [0] * 4           # ... whose tokens equal the reference's

        def generated():
            return [scrape(u).get("vllm:generation_tokens_total", 0.0)
                    for u in stack.engine_urls]

        # Session routing hashes the key: walk keys, one request at a
        # time, until every engine has answered at least one; the engine
        # whose token counter moved is the one that served it.
        before = generated()
        for key in range(64):
            if all(served):
                break
            i = key % len(QUESTIONS)
            body = chat_body(model, QUESTIONS[i], stream=True, logprobs=True)
            res = chat(stack.router_url, body,
                       {"x-user-id": f"session-{key}"})
            faults += [f"session-{key}: {f}" for f in
                       check_completion(res, body)]
            after = generated()
            moved = [j for j in range(4) if after[j] > before[j]]
            before = after
            if len(moved) != 1:
                faults.append(f"session-{key}: engines {moved} moved")
                continue
            served[moved[0]] += 1
            if res["toks"] == ref_toks[i]:
                matched[moved[0]] += 1
            else:
                faults.append(
                    f"session-{key} on engine {moved[0]}: tokens "
                    f"{res['toks']} != reference {ref_toks[i]}")
        # Device ids are per process (every replica sees its chip as id
        # 0): the chip index the launcher gave is what tells them apart,
        # and libtpu lets one chip be opened by one process only.
        owners = [(b["device"]["visible_chips"], b["device"]["ids"])
                  for b in rep["boots"]]
        if not all(served):
            faults.append(f"engines served {served}: one served nothing")
        if len({json.dumps(o) for o in owners}) != 4:
            faults.append(f"engines do not own four distinct chips: {owners}")
        rep.update(served=served, matched_reference=matched,
                   chip_owners=owners, faults=faults, ok=not faults)

    run_phase(rep, replicas, model, args, num_engines=4)

    # ---- tp=4: one engine sharded over the four chips
    tp = {"phase": "tp4[1 x 4 chips]", "model": model}

    def sharded(stack):
        faults, toks, first_lp = [], [], []
        for i, (body, res) in enumerate(
                _logprob_requests(model, stack.router_url)):
            faults += [f"request {i}: {f}" for f in
                       check_completion(res, body)]
            toks.append(res["toks"])
            first_lp.append(res["logprobs"][:1])
        diffs = [abs(a[0] - b[0]) if a and b else None
                 for a, b in zip(first_lp, ref_lp)]
        if any(d is None or d > TP_LOGPROB_TOL for d in diffs):
            faults.append(
                f"first-token logprob differs from tp=1 by {diffs} "
                f"(tolerance {TP_LOGPROB_TOL})")
        kv_heads = tp["boot"]["kv_shard_shape"][1]
        ref_heads = ref["boot"]["kv_shard_shape"][1]
        in_use = list(_bytes_in_use(stack.engine_url).values())
        spread = max(in_use) / max(1, min(in_use)) if in_use else None
        if tp["boot"]["device"]["count"] != 4:
            faults.append(f"mesh has {tp['boot']['device']['count']} devices")
        if kv_heads * 4 != ref_heads:
            faults.append(f"kv pool shard holds {kv_heads} kv heads, want "
                          f"a quarter of the one-chip pool's {ref_heads}")
        if len(in_use) != 4 or spread > TP_BYTES_SPREAD:
            faults.append(f"per-device bytes_in_use {in_use}: spread "
                          f"{spread} (bound {TP_BYTES_SPREAD})")
        tp.update(
            greedy_toks=toks, first_logprobs=first_lp,
            first_logprob_abs_diff=diffs, logprob_tolerance=TP_LOGPROB_TOL,
            token_agreement_vs_tp1=agreement(toks, ref_toks),
            kv_heads_per_device=kv_heads, bytes_in_use_spread=spread,
            faults=faults, ok=not faults,
        )

    run_phase(tp, sharded, model, args, tensor_parallel_size=4)
    return [ref, rep, tp]


# --------------------------------------------------------------- verdict
def verdict(lines: list, chips: int, full_depth: int,
            rehearsal: bool = False) -> dict:
    """The last line. ``ok`` needs every phase to have passed AND every
    engine that served requests to say, in its own report, that it ran on
    a TPU at full depth with compiled kernels, a compile cache and a clean
    warmup, and that its dispatch programs copy no KV pool whole; the
    second boot of the run must have found the cache. The device is the
    one the last serving engine reported."""
    faults = []
    if rehearsal:
        faults.append("rehearsal: tiny model, never a pass")
    boots = []
    for line in lines:
        if not line.get("ok"):
            faults.append(f"{line.get('phase')}: failed")
        if line.get("phase") == "kernel":
            if line.get("interpret") is not False:
                faults.append("kernel: interpreted, not compiled")
            if (line.get("device") or {}).get("platform") != "tpu":
                faults.append("kernel: not on a tpu")
        boots += line.get("boots") or (
            [line["boot"]] if "boot" in line else [])
        if ("boot" in line or "boots" in line) and \
                not line.get("pool_programs"):
            faults.append(f"{line.get('phase')}: no pool-program audit")
        for prog in line.get("pool_programs", ()):
            name = f"{line.get('phase')} {prog['program']}{prog['family']}"
            # temp_bytes is printed, not judged: at the smoke's small pool
            # a history window alone outweighs it.
            if prog["pool_copies"]:
                faults.append(
                    f"{name}: {prog['pool_copies']} whole-pool copies")
            for op in ("gdn_step", "gdn_chunk"):
                if prog.get(op) == "xla" and not rehearsal:
                    faults.append(f"{name}: {op} is not the Pallas kernel")
            # The engine's own predicate, not a flag's name: a tp=4
            # engine or an int8 pool gathers a window and says so
            # (``prefill_reads_pool`` false, ``"xla"``).
            if prog.get("prefill_reads_pool") and not rehearsal \
                    and prog.get("prefill_attn") != "pallas":
                faults.append(
                    f"{name}: prefill views hold the pool but the program "
                    "does not hold the Pallas prefill kernel")
    if not boots:
        faults.append("no engine report: nothing was served")
    for boot in boots:
        if boot["device"].get("platform") != "tpu":
            faults.append(
                f"engine on {boot['device'].get('platform')!r}, not a tpu")
        if boot.get("pallas_interpret") is not False:
            faults.append("engine kernels interpreted")
        if not boot.get("cache_dir"):
            faults.append("engine booted without a compile cache")
        if boot.get("warmup_failures"):
            faults.append(f"{boot['warmup_failures']} warmup stage(s) failed")
        if not boot.get("warmup_families"):
            faults.append("engine warmed no shape family")
        if boot.get("num_layers") != full_depth:
            faults.append(f"depth {boot.get('num_layers')} != {full_depth}")
    # A boot that repeats an earlier boot's path (attention impl, device
    # count) compiles the same programs and must find them cached. Boots
    # of different paths share none since PR 35 (a paged engine's prefill
    # holds the flash kernel, a window engine's ``window_attention``), so
    # on a cold cache the one-chip run's two boots both miss everything,
    # by design; on a warm one both hit.
    def path(boot):
        return boot.get("attn_impl"), boot["device"].get("count")

    repeats = [b for i, b in enumerate(boots)
               if any(path(a) == path(b) for a in boots[:i])]
    if repeats and not any(b.get("cache_hit", 0) > 0 for b in repeats):
        faults.append("no later boot of a path booted before found the "
                      "compile cache (0 hits)")
    device = {k: boots[-1]["device"].get(k)
              for k in ("platform", "kind", "count")} if boots else None
    if device and device["count"] != chips:
        faults.append(f"served on {device['count']} device(s), want {chips}")
    if faults:
        emit({"phase": "verdict", "faults": faults})
    return {"ok": not faults, "device": device}


def memory_stats_child(rehearse: bool) -> int:
    """What one ``memory_stats()`` call costs on the engine's device: the
    engine makes two a dispatch in its executor thread (the memory
    ledger's reads). 10,000 calls on an idle device, and 10,000 while a
    program of about four seconds runs on it (a chain of matrix products
    behind one enqueue, as a decode train is). Then a program with a
    known temporary, polled while it runs: whether the allocator's bytes
    in use and peak show a program's temporaries, and from when. Two JSON
    lines."""
    import jax
    import jax.numpy as jnp

    device = jax.devices()[0]
    calls = 200 if rehearse else 10_000

    def timed():
        t0 = time.perf_counter()
        for _ in range(calls):
            stats = device.memory_stats()
        return (time.perf_counter() - t0) / calls * 1e6, stats

    @jax.jit
    def busy(x):
        return jax.lax.fori_loop(
            0, 8 if rehearse else 6000,
            lambda _, a: (a @ a) * jnp.bfloat16(1e-3), x)

    x = jnp.ones((64, 64) if rehearse else (4096, 4096), jnp.bfloat16)
    busy(x).block_until_ready()
    idle_us, stats = timed()
    t0 = time.perf_counter()
    out = busy(x)
    busy_us, _ = timed()
    still_running = not out.is_ready()
    out.block_until_ready()
    busy_s = time.perf_counter() - t0

    # When does the allocator take a program's temporaries, and do its
    # counts show them? A program with a known temporary (two float32
    # squares carried through a loop), polled while it runs.
    n = 64 if rehearse else 16384

    @jax.jit
    def hungry(x):
        wide = x.astype(jnp.float32)

        def body(_, pair):
            a, b = pair
            return (a @ b) * 1e-4, a + b

        a, b = jax.lax.fori_loop(0, 3, body, (wide @ wide.T, wide))
        return jnp.sum(a) + jnp.sum(b)

    y = jnp.ones((n, n), jnp.bfloat16)
    compiled = hungry.lower(y).compile()
    analysis = compiled.memory_analysis()
    hungry(y).block_until_ready()
    before = device.memory_stats() or {}
    t0 = time.perf_counter()
    out = hungry(y)
    after_enqueue = device.memory_stats() or {}
    most, polls = 0, 0
    while not out.is_ready():
        most = max(most, (device.memory_stats() or {}).get(
            "bytes_in_use", 0))
        polls += 1
    ran_s = time.perf_counter() - t0
    settled = device.memory_stats() or {}
    # Two of them enqueued back to back: do both hold their temporaries
    # at once (the peak rises by another program's worth), or one after
    # the other as they run?
    first, second = hungry(y), hungry(y)
    most_two = 0
    while not second.is_ready():
        most_two = max(most_two, (device.memory_stats() or {}).get(
            "bytes_in_use", 0))
    first.block_until_ready()
    two = device.memory_stats() or {}
    emit({
        "phase": "memory_stats.temporaries",
        "two_in_flight_in_use_most": most_two,
        "two_in_flight_peak_after": two.get("peak_bytes_in_use"),
        "temp_bytes": int(getattr(analysis, "temp_size_in_bytes", 0)),
        "output_bytes": int(getattr(analysis, "output_size_in_bytes", 0)),
        "argument_bytes": int(getattr(analysis, "argument_size_in_bytes",
                                      0)),
        "program_s": round(ran_s, 3), "polls": polls,
        "in_use_before": before.get("bytes_in_use"),
        "in_use_after_enqueue": after_enqueue.get("bytes_in_use"),
        "in_use_most_while_running": most,
        "in_use_after": settled.get("bytes_in_use"),
        "peak_before": before.get("peak_bytes_in_use"),
        "peak_after": settled.get("peak_bytes_in_use"),
        "stats_after": settled,
    })
    emit({
        "phase": "memory_stats", "calls": calls,
        "us_per_call_idle": round(idle_us, 2),
        "us_per_call_busy": round(busy_us, 2),
        "program_still_running_after_the_busy_loop": still_running,
        "program_s": round(busy_s, 3),
        "keys": sorted(stats or {}),
        "device": {"platform": device.platform, "kind": device.device_kind},
        "ok": bool(stats) and still_running and not rehearse,
    })
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at a tiny model; never ok")
    ap.add_argument("--kernel-child", metavar="MODEL", help=argparse.SUPPRESS)
    ap.add_argument("--gdn", action="store_true",
                    help="only time the Gated DeltaNet recurrence alone "
                         "(gdn_step, gdn_chunk) and exit")
    ap.add_argument("--ssd", action="store_true",
                    help="only time the Mamba-2 scan alone (ssd_step, "
                         "ssd_chunk) and the paged decode kernel at its "
                         "model's attention shape, and exit")
    ap.add_argument("--ring", action="store_true",
                    help="only time the window ring's decode step alone "
                         "(the kernel beside the XLA statement, and the "
                         "kernel's two constants) and exit")
    ap.add_argument("--moe", action="store_true",
                    help="only time the latent decode kernel and the "
                         "experts' grouped matmuls alone and exit")
    ap.add_argument("--hc", action="store_true",
                    help="only time the stream mix of a residual of four "
                         "streams alone and exit")
    ap.add_argument("--prefill", action="store_true",
                    help="only check and time the prefill flash kernel "
                         "alone at the benchmark's prefill shapes and exit")
    ap.add_argument("--memory-stats", action="store_true",
                    help="only time device.memory_stats(), idle and under "
                         "a running program, and exit")
    args = ap.parse_args(argv)

    sys.path.insert(0, HERE)
    os.environ["PYTHONPATH"] = os.pathsep.join(
        p for p in (HERE, os.environ.get("PYTHONPATH")) if p
    )
    if args.kernel_child:
        return kernel_child(args.kernel_child, args.rehearse)
    if args.gdn:
        if args.rehearse:
            os.environ["JAX_PLATFORMS"] = "cpu"
        return gdn_child(args.rehearse)
    if args.ssd:
        if args.rehearse:
            os.environ["JAX_PLATFORMS"] = "cpu"
        return ssd_child(args.rehearse)
    if args.ring:
        if args.rehearse:
            os.environ["JAX_PLATFORMS"] = "cpu"
        return ring_child(args.rehearse)
    if args.moe:
        if args.rehearse:
            os.environ["JAX_PLATFORMS"] = "cpu"
        return moe_child(args.rehearse)
    if args.hc:
        if args.rehearse:
            os.environ["JAX_PLATFORMS"] = "cpu"
        return hc_child(args.rehearse)
    if args.prefill:
        if args.rehearse:
            os.environ["JAX_PLATFORMS"] = "cpu"
        return prefill_child(args.rehearse)
    if args.memory_stats:
        if args.rehearse:
            os.environ["JAX_PLATFORMS"] = "cpu"
        return memory_stats_child(args.rehearse)

    model, full_depth, engine_args = MODEL, FULL_DEPTH, ENGINE_ARGS
    if args.rehearse:
        model, full_depth, engine_args = (
            REHEARSAL_MODEL, REHEARSAL_DEPTH, REHEARSAL_ENGINE_ARGS
        )
        # A rehearsal IS the CPU, asked for; four virtual devices stand in
        # for the four chips of --chips 4.
        os.environ["JAX_PLATFORMS"] = "cpu"
        if "xla_force_host_platform_device_count" not in \
                os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=4"
            ).strip()

    lines = []
    if args.chips == 4:
        lines += phases_four_chips(model, engine_args)
    else:
        lines.append(emit(phase_kernel(model, args.rehearse)))
        on_tpu = (lines[0].get("device") or {}).get("platform") == "tpu"
        if on_tpu or args.rehearse:
            # The path `auto` resolves to, then the other one: both
            # attention paths run compiled. They share no program (see
            # ``verdict``): a second run of the smoke on the same machine
            # shows the compile cache being found again, a first need not.
            for attn in ("auto", "paged"):
                lines.append(phase_serve(model, engine_args, attn))
            a, b = (ln.get("greedy_toks") for ln in lines[1:3])
            if a and b:
                emit({"phase": "paths",
                      "auto": lines[1]["boot"]["attn_impl"],
                      "auto_toks": a, "paged_toks": b,
                      "token_agreement": agreement(a, b)})
        # No accelerator and no rehearsal asked for: llama-3b is not run on
        # a CPU — the failed kernel phase is the whole result.
    final = verdict(lines, args.chips, full_depth, rehearsal=args.rehearse)
    print(json.dumps(final), flush=True)
    return 0 if final["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
