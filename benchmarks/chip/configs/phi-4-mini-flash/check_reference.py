#!/usr/bin/env python3
"""The served path against the plain reference at PUBLISHED widths, on the
chip. The harness has no place for a reference (a cell's ``correct`` is
token counts, a probe and no compile in the window), so this is the
builder's own run, once a PR that touches the family:

    chiprun --timeout 3400 -- python3 benchmarks/chip/configs/phi-4-mini-flash/check_reference.py

Children, one after the other (a chip belongs to one process); this parent
never imports JAX.

``--stage recurrence``: the selective scan alone, where its precision can
be told. ``gates`` + ``s6_chunk`` over 4 rows of 2112, 2048, 320 and 32
tokens (the traffic's lengths; the kernel on a TPU) and then 64 ``s6_step``
steps, at the published 5120 channels x state 16 with W_x, W_dt, b_dt, A_log
and D drawn as ``init_params`` draws them, against
``reference.selective_scan`` (float32, a token at a time) on identical
inputs. The number is ||system - reference|| / ||reference|| over the
outputs of every valid token. Four verdicts, all by REC_TOL: the shipped
code is within; the same code with its products into delta | B | C and dt at
default precision (bf16 operands) is NOT; against the reference with its
state held in bf16, and against the reference with dt computed in bf16, the
shipped code is NOT.

``--stage engine``: the engine in-process at ``deployment.json``'s flags,
``config.json``'s widths and weights seeded by ``--seed``, 64 greedy tokens
a request through the normal scheduler, prefill chunks and decode trains:
first ONE cold prompt alone, then THE SAME prompt again (its prefix is
registered and must go unserved: rings and scan states have no snapshot; the
answer has to be the cold one's), then 46 prompts AT ONCE: one of 2600
tokens (two prefill chunks through its state slot), 2048, 1100, 600 and 500
(whose answer carries its context over the 512-key window's edge in
decode), and the traffic's own lengths (320 and 32), so that the 48-row
decode program the benchmark's window runs is the one compared. What the
served surface returns is kept: every generated token's own log-probability
and the 20 most likely (``logprobs=20``). The seeded draw is checked here
too (PERF.md section 6, PR 44): the answers differ by prompt and none ends
in one repeated token.

``--stage reference``: ``reference.py`` (float32, ``highest``, the scan
token by token, a masked full score matrix a head pair, no cache) over
prompt + generated tokens of every request, one layer's weights widened from
bf16 to float32 at a time, and the comparison: largest and mean |difference|
of the log-probabilities, per phase, beside the reference logits' spread.
``--wrong a,b``: ONE equation wrong at a time, over the first eight requests
(every long context is among them), each of which must NOT be within;
``--wrong near`` runs the seven nearest that this stage can tell (ISSUE
54's list but a bf16 step size, which the recurrence stage tells), ``all``
every one. It reads ``served.json`` and needs no chip.

The limits and the readings they lie between are under LIMITS below and in
PERF.md section 6 (PR 54).
"""

import argparse
import asyncio
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(HERE))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# A rehearsal on the CPU names a directory (--dir) with a tiny config.json
# and deployment.json beside a copy of reference.py, and short lengths
# (--lens: the cold prompt first, then the batch).
PROMPT_LENS = (320, 2600, 2048, 1100, 600, 500) + (320, 32) * 20 + (320,)
OUTPUT_TOKENS = 64
TOP = 20
WRONG_OVER = 8          # requests a wrong model is computed over
NEAR = ("lambda_init_next_layer", "no_subtraction", "window_minus_1",
        "window_plus_1", "memory_after_gate",
        "cross_reads_last_window_layer", "state_bf16")
# LIMITS (my chip runs, PR 54, seed 20261004, one TPU v5 lite; PERF.md
# section 6). The engine multiplies bf16 weights by bf16 activations with
# float32 accumulation through 32 layers, rounds the residual stream to bf16
# after each of 64 sublayers and takes a_1 - lambda a_2 of two bf16 attention
# outputs, where the reference keeps float32; this draw's scores spread by
# about 4 (queries and keys at twice fan-in scale, so that attention picks a
# few tokens), which is what carries a rounding on: the module's own forward
# in bf16 against the reference at the tiny width reads 0.077 at 8 layers,
# 0.18 at 32, and 0.07 at 32 with the scores' spread at 1. Readings at the
# published widths, logit spread 2.0, mean / largest |difference| of
# prefill | decode: the SHIPPED path over 48 requests 0.198 / 1.05 | 0.202 /
# 1.17. Over the first eight requests (every context past 512 is among
# them), the reference with ONE thing wrong: ``dt_bf16`` 0.238 / 1.04 | 0.230
# / 1.17 (so the right path reads at most that there); ``window_minus_1``
# 0.300 / 1.53 | 0.349 / 4.26; ``window_plus_1`` 0.422 / 2.03 | 0.346 / 3.56;
# ``state_bf16`` 0.341 / 2.23 | 0.362 / 3.81; ``cross_reads_last_window_
# layer`` 0.500 / 1.65 | 0.487 / 2.53; ``memory_after_gate`` 0.787 / 2.56 |
# 0.751 / 3.78; ``lambda_init_next_layer`` 2.00 / 5.6 | 1.97 / 7.2;
# ``no_subtraction`` 3.94 / 8.1 | 4.15 / 11.2. TOL_MEAN lies between 0.238
# and 0.300 and TOL_MAX between 1.17 and 2.03 (the nearest wrong model that
# fails by its maximum alone is none: each fails by its mean too). The
# maximum is bounded to catch a single row gone wrong (a slot not cleared, a
# ring row misplaced). What these limits CANNOT tell is ``dt_bf16`` (a step
# size computed in bf16 moves the mean by 0.03, inside what bf16 weights and
# activations already cost): the recurrence stage tells it, by REC_TOL (the
# shipped scan reads 0.0 of the outputs' norm: the kernel's bits are the
# token-by-token scan's; default-precision products 1.0e-4, a bf16 dt
# 4.4e-4, a bf16 state 2.5e-3; REC_TOL a fifth of the nearest).
TOL_MEAN = 0.27
TOL_MAX = 1.8
REC_LENS = (2112, 2048, 320, 32)
REC_TOL = 2e-5
OUT_DIR = os.path.join(ROOT, "chiprun_out", "check_reference_phi4flash")


def load(name):
    with open(os.path.join(HERE, name)) as f:
        return json.load(f)


def prompts(seed: int, vocab: int, lens):
    import numpy as np

    rng = np.random.default_rng(seed)
    # Byte-tokenizer range, as the benchmark's traffic: ids 3..258.
    return [[int(t) for t in rng.integers(3, min(vocab, 259), n)]
            for n in lens]


# ------------------------------------------------------------------ engine
def stage_engine(seed: int, lens, dtype: str) -> int:
    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.engine.engine import ServingEngine
    from production_stack_tpu.engine.sampling import SamplingParams

    flags = {f["flag"]: f["value"] for f in load("deployment.json")[
        "engine_flags"]}
    config = EngineConfig(
        model=HERE, load_format="dummy", seed=seed, dtype=dtype,
        max_model_len=int(flags["--max-model-len"]),
        max_num_seqs=int(flags["--max-num-seqs"]),
        max_num_batched_tokens=int(flags["--max-num-batched-tokens"]),
        attn_impl=flags["--attn-impl"],
        num_kv_blocks=int(flags["--num-kv-blocks"]),
    )
    engine = ServingEngine(config)
    todo = prompts(seed, engine.model_config.vocab_size, lens)

    async def one(tokens):
        last = None
        async for out in engine.generate(
                prompt_token_ids=tokens, sampling=SamplingParams(
                    temperature=0.0, max_tokens=OUTPUT_TOKENS,
                    ignore_eos=True, logprobs=TOP)):
            last = out
        return {"prompt": tokens, "output": list(last.token_ids),
                "logprobs": [[lp, [[int(t), float(p)] for t, p in top]]
                             for lp, top in last.logprobs]}

    said = {}

    async def run():
        await engine.start()
        try:
            bm = engine.block_manager
            cold = await one(todo[0])
            hits, unserved = bm.prefix_hits_total, \
                bm.prefix_hits_unserved_total
            again = await one(todo[0])
            said["prefix_unserved_tokens"] = \
                bm.prefix_hits_unserved_total - unserved
            said["prefix_served_tokens"] = bm.prefix_hits_total - hits
            return [cold, again] + list(await asyncio.gather(
                *(one(t) for t in todo[1:])))
        finally:
            await engine.stop()

    t0 = time.monotonic()
    done = asyncio.run(run())
    report = engine.report()
    # The same prompt twice: the second answer is the cold one's.
    cold, again = done[0], done[1]
    said["again_same_tokens"] = cold["output"] == again["output"]
    said["again_max_logprob_diff"] = max(
        abs(a[0] - b[0]) for a, b in zip(cold["logprobs"], again["logprobs"]))
    # The seeded draw: answers of different prompts differ, and none ends
    # in one repeated token.
    answers = {tuple(r["prompt"]): tuple(r["output"]) for r in done}
    said["distinct_answers"] = len(set(answers.values()))
    said["distinct_prompts"] = len(answers)
    said["fewest_distinct_tokens_in_a_tail"] = min(
        len(set(r["output"][-16:])) for r in done)
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, "served.json"), "w") as f:
        json.dump({"seed": seed, "dtype": dtype, "requests": done,
                   "device": report["device"],
                   "attn_impl": report["engine"]["attn_impl"],
                   "seconds": time.monotonic() - t0}, f)
    stats = engine.stats()
    ok = said["again_same_tokens"] and said["prefix_served_tokens"] == 0 \
        and said["prefix_unserved_tokens"] > 0 \
        and said["distinct_answers"] == said["distinct_prompts"] \
        and said["fewest_distinct_tokens_in_a_tail"] > 4
    print(json.dumps({"stage": "engine", "requests": len(done),
                      "device": report["device"]["kind"],
                      "attn_impl": report["engine"]["attn_impl"],
                      **said, "ok": ok,
                      "decode_rows_per_step": round(
                          stats["decode_row_steps_total"]
                          / max(1, stats["decode_steps_total"]), 1),
                      "preemptions": stats["num_preemptions"],
                      "state_bytes": report["engine"]["state_bytes"],
                      "peak_bytes_in_use":
                          report["engine"]["peak_bytes_in_use"],
                      "seconds": round(time.monotonic() - t0, 1)}),
          flush=True)
    return 0 if ok else 1


# -------------------------------------------------------------- recurrence
def stage_recurrence(seed: int, lens) -> int:
    import math

    import jax
    import jax.numpy as jnp
    import numpy as np

    sys.path.insert(0, HERE)
    import reference as ref
    from production_stack_tpu.ops import selective_scan as s6

    s = ref.sizes(load("config.json"))
    d, n, rank = s["inner"], s["n"], s["rank"]
    rows, t, steps = len(lens), max(lens), OUTPUT_TOKENS
    ks = iter(jax.random.split(jax.random.PRNGKey(seed), 12))
    f32 = jnp.float32

    def normal(*shape):
        return jax.random.normal(next(ks), shape, f32)

    # Inputs for t prefilled and ``steps`` decoded tokens a row, as the
    # layer hands them over: u after the conv's SiLU in bf16's values, the
    # weights as init_params draws them (bf16's values too).
    total = t + steps
    u = jax.nn.silu(normal(rows, total, d)).astype(jnp.bfloat16).astype(f32)
    w_x = (normal(d, rank + 2 * n) * d ** -0.5).astype(
        jnp.bfloat16).astype(f32)
    w_dt = (normal(rank, d) * rank ** -0.5).astype(jnp.bfloat16).astype(f32)
    dt0 = jnp.exp(jax.random.uniform(next(ks), (d,), f32, math.log(1e-3),
                                     math.log(1e-1)))
    dt_bias = dt0 + jnp.log(-jnp.expm1(-dt0))
    a = -jnp.broadcast_to(jnp.arange(1, n + 1, dtype=f32)[:, None], (n, d))
    d_skip = jax.random.uniform(next(ks), (d,), f32, 0.5, 1.5)
    lens_a = jnp.asarray(lens, jnp.int32)

    def reference(keep, dt_bf16=False):
        # Row by row: the row's valid prompt tokens, then its decode tokens.
        @jax.jit
        def one(u_row):
            with jax.default_matmul_precision("highest"):
                proj = u_row @ w_x
                delta, b, c = (proj[:, :rank], proj[:, rank:rank + n],
                               proj[:, rank + n:])
                if dt_bf16:
                    dt = jax.nn.softplus(
                        delta.astype(jnp.bfloat16)
                        @ w_dt.astype(jnp.bfloat16)
                        + dt_bias.astype(jnp.bfloat16)).astype(f32)
                else:
                    dt = jax.nn.softplus(delta @ w_dt + dt_bias)
                return ref.selective_scan(u_row, dt, a, b, c, d_skip, keep)

        return jnp.concatenate([
            one(u[i, np.r_[0:m, t:t + steps]]) for i, m in enumerate(lens)])

    def system():
        @jax.jit
        def run(u):
            state = jnp.zeros((rows, n, d), f32)
            dt, b, c = s6.gates(u, w_x, w_dt, dt_bias, n)
            y, state = s6.s6_chunk(state, u[:, :t], dt[:, :t], a, b[:, :t],
                                   c[:, :t], d_skip, lens_a)

            def step(state, xs):
                y_t, state = s6.s6_step(state, xs[0], xs[1], a, xs[2],
                                        xs[3], d_skip,
                                        jnp.ones((rows,), bool))
                return state, y_t

            _, y_dec = jax.lax.scan(step, state, tuple(
                jnp.moveaxis(v[:, t:], 1, 0) for v in (u, dt, b, c)))
            return jnp.concatenate([y, jnp.moveaxis(y_dec, 0, 1)], axis=1)

        compiled = run.lower(u).compile()
        y = compiled(u)
        return jnp.concatenate([
            jnp.concatenate([y[i, :m], y[i, t:]])
            for i, m in enumerate(lens)]), s6.chunk_path(compiled.as_text())

    def rel(got, want):
        return float(jnp.linalg.norm((got - want).ravel())
                     / jnp.linalg.norm(want.ravel()))

    ref_o = reference(f32)
    bf_o = reference(jnp.bfloat16)
    dt_o = reference(f32, dt_bf16=True)
    sys_o, path = system()
    hi, s6._HI = s6._HI, jax.lax.Precision.DEFAULT
    jax.clear_caches()
    low_o, _ = system()
    s6._HI = hi
    out = {"stage": "recurrence", "device": jax.devices()[0].device_kind,
           "state": [n, d], "lens": list(lens), "steps": steps,
           "s6_chunk": path, "tolerance": REC_TOL,
           "shipped": {"out": rel(sys_o, ref_o)},
           "default_precision": {"out": rel(low_o, ref_o)},
           "vs_state_bf16": {"out": rel(sys_o, bf_o)},
           "vs_dt_bf16": {"out": rel(sys_o, dt_o)}}
    names = ("shipped", "default_precision", "vs_state_bf16", "vs_dt_bf16")
    for name in names:
        out[name]["within"] = out[name]["out"] <= REC_TOL
    out["ok"] = out["shipped"]["within"] and not any(
        out[name]["within"] for name in names[1:])
    print(json.dumps(out), flush=True)
    return 0


# --------------------------------------------------------------- reference
def stage_reference(wrongs) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    sys.path.insert(0, HERE)
    import reference as ref
    from production_stack_tpu.models import get_model
    from production_stack_tpu.models.config import ModelConfig

    with open(os.path.join(OUT_DIR, "served.json")) as f:
        served = json.load(f)
    cfg = load("config.json")
    mc = ModelConfig.from_hf_config(cfg)
    # The same weights: the engine's init, the engine's seed and dtype.
    params = get_model(mc).init_params(
        mc, jax.random.PRNGKey(served["seed"]),
        jnp.dtype(served.get("dtype", "bfloat16")))
    layer = jax.jit(ref.layer, static_argnums=(0, 1, 2, 7))
    frozen = json.dumps(cfg, sort_keys=True)   # hashable for the jit

    class Cfg(dict):
        def __hash__(self):
            return hash(frozen)

    hcfg = Cfg(cfg)
    if wrongs == [("near",)]:
        wrongs = [(w,) for w in NEAR]
    elif wrongs == [("all",)]:
        wrongs = [(w,) for w in ref.WRONG + ref.LOW_PRECISION]

    def compare(wrong):
        requests = served["requests"][:WRONG_OVER] if wrong \
            else served["requests"]
        seqs = [r["prompt"] + r["output"][:-1] for r in requests]
        xs = [ref.embed(params, jnp.asarray(s)) for s in seqs]
        carries = [{} for _ in seqs]
        for i in range(cfg["num_hidden_layers"]):
            kind, lp = ref.layer_params(params, cfg, i)  # one layer, float32
            role = ref.layer_role(cfg, i, wrong)
            for j, x in enumerate(xs):
                # The index traced: ONE program a kind, role and length.
                xs[j], carries[j] = layer(hcfg, kind, role, lp, x,
                                          carries[j], jnp.float32(i), wrong)
            jax.block_until_ready(xs)
        stats = {"prefill": [], "decode": []}
        spread = []
        for req, x in zip(requests, xs):
            m = len(req["prompt"])
            logits = ref.logits(params, cfg, x[m - 1:], wrong)
            spread.append(float(jnp.std(logits)))
            logp = np.asarray(jax.nn.log_softmax(logits, axis=-1))
            for j, (chosen, top) in enumerate(req["logprobs"]):
                phase = "prefill" if j == 0 else "decode"
                diffs = [abs(chosen - logp[j][req["output"][j]])]
                diffs += [abs(q - logp[j][tok]) for tok, q in top]
                stats[phase] += diffs
        out = {"stage": "reference", "wrong": list(wrong),
               "requests": len(requests),
               "logit_spread": float(np.mean(spread)),
               "device": jax.devices()[0].device_kind}
        for phase, diffs in stats.items():
            out[phase] = {"n": len(diffs), "max": float(np.max(diffs)),
                          "mean": float(np.mean(diffs))}
        # A number that is not finite is not within anything.
        out["within"] = all(
            bool(np.isfinite(out[phase]["max"]))
            and out[phase]["mean"] <= TOL_MEAN
            and out[phase]["max"] <= TOL_MAX for phase in stats)
        out["tolerance"] = {"mean": TOL_MEAN, "max": TOL_MAX}
        print(json.dumps(out), flush=True)
        return out

    got = [compare(w) for w in wrongs]
    if len(got) > 1 or got[0]["wrong"]:
        print(json.dumps({
            "stage": "reference", "wrong": "each",
            "within": any(g["within"] for g in got),
            "nearest": min(got, key=lambda g: g["decode"]["mean"])["wrong"],
        }), flush=True)
    return 0


def main(argv=None) -> int:
    global HERE
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=20261004)
    ap.add_argument("--stage", choices=("recurrence", "engine", "reference"))
    ap.add_argument("--wrong", default="",
                    help="wrong models, comma-separated, one at a time; "
                         "near; all")
    ap.add_argument("--dir", default=HERE,
                    help="config.json, deployment.json and reference.py")
    ap.add_argument("--lens", default="",
                    help="prompt lengths, comma-separated (a rehearsal)")
    ap.add_argument("--dtype", default="bfloat16")
    args = ap.parse_args(argv)
    HERE = os.path.abspath(args.dir)
    lens = tuple(int(m) for m in args.lens.split(",") if m)
    if args.stage == "recurrence":
        return stage_recurrence(args.seed, lens[:4] or REC_LENS)
    if args.stage == "engine":
        return stage_engine(args.seed, lens or PROMPT_LENS, args.dtype)
    if args.stage == "reference":
        return stage_reference(
            [(w,) for w in args.wrong.split(",") if w] or [()])
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        q for q in (ROOT, os.environ.get("PYTHONPATH")) if q))
    common = ["--seed", str(args.seed), "--dir", HERE, "--lens", args.lens,
              "--dtype", args.dtype]
    lines = []
    for stage in (["--stage", "recurrence"], ["--stage", "engine"],
                  ["--stage", "reference"],
                  ["--stage", "reference", "--wrong", args.wrong or "near"]):
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), *stage, *common],
            env=env, capture_output=True, text=True)
        got = [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
        for ln in got:
            print(ln, flush=True)
        if proc.returncode != 0 or not got:
            print(proc.stderr[-3000:], file=sys.stderr)
            print(json.dumps({"ok": False, "failed": stage}), flush=True)
            return 1
        lines.append(json.loads(got[-1]))
    recurrence, engine, right, wrong = lines
    ok = recurrence["ok"] and engine["ok"] and right["within"] \
        and not wrong["within"]
    print(json.dumps({
        "ok": ok, "recurrence_ok": recurrence["ok"],
        "engine_ok": engine["ok"], "right_path_within": right["within"],
        "every_wrong_model_fails": not wrong["within"],
        "nearest_wrong": wrong.get("nearest")}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
