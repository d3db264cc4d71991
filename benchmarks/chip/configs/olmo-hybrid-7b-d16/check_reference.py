#!/usr/bin/env python3
"""The served path against the plain reference at PUBLISHED widths, on the
chip. The harness has no place for a reference (a cell's ``correct`` is
token counts, a probe and no compile in the window), so this is the
builder's own run, once a PR that touches the family:

    chiprun -- python3 benchmarks/chip/configs/olmo-hybrid-7b-d16/check_reference.py

Children, one after the other (a chip belongs to one process); this parent
never imports JAX.

``--stage recurrence``: the recurrence alone, where its precision can be
told. ``gdn_chunk`` over 4 rows of 2112, 2048, 320 and 32 tokens (33 chunks
of 64; the shorter rows padded) and then 64 ``gdn_step`` steps, at the
published 30 heads x 96 x 192, against ``reference.delta_rule`` (float32, a
token at a time) on identical inputs: gates drawn as ``init_params`` draws
them, q and k normalised. The number is ||system - reference|| / ||reference||
over the outputs of every valid token and over the final states. Three
verdicts, all by REC_TOL: the shipped kernels are within; the same kernels
with their products at default precision (bf16 operands: the float32 state
rounded at every chunk) are NOT; against the reference with its state held
in bf16 the shipped kernels are NOT. REC_TOL and the readings it lies
between: PERF.md section 6, PR 31.

``--stage engine``: the engine in-process at ``deployment.json``'s flags,
``config.json``'s widths and weights seeded by ``--seed``; 32 prompts of the
traffic's own lengths (32 and 320 mostly, two of 2048, and two of 2112 that
cross a prefill chunk), ALL AT ONCE, so that the 16- and 32-row decode
programs the benchmark's window runs are the ones compared; 64 greedy
tokens each through the normal scheduler, prefill chunks and decode trains.
What the served surface returns is kept: every generated token's own
log-probability and the 20 most likely (``logprobs=20``): logits less their
row's normaliser, from the programs the benchmark times.

``--stage reference``: ``reference.py`` (float32, ``highest``, token by
token, no cache) over prompt + generated tokens of every request, one layer's
weights widened from bf16 to float32 at a time, and the comparison: largest
and mean |difference| of the log-probabilities, per phase, beside the
reference logits' spread. It reads ``served.json`` and needs no chip.

TOL_MEAN / TOL_MAX, and why these: the engine multiplies bf16 weights by
bf16 activations with float32 accumulation through 16 layers and rounds the
residual stream to bf16 after each; the reference keeps float32 throughout.
That alone moves a log-probability by 0.042-0.046 in the mean (largest
single number 0.40: PERF.md section 6, PR 31). A reference with ONE
equation wrong (``reference.WRONG``) reads 0.31 (the conv state dropped
every 16 tokens, prefill) to 3.7 in the mean and 1.3 to 7.7 at the largest,
at these widths, from the same served numbers. The limits lie between, with
room on both sides. The mean decides (a maximum over 10,000 numbers is one
unlucky token); the maximum is bounded to catch a single row gone wrong (a
slot not cleared, a state row swapped). These two limits judge the
equations and the rows; they CANNOT tell the recurrence's precision (a
state held in bf16 moves the mean by 0.005, an eighth of what bf16 weights
and activations already cost): the recurrence stage does.
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
# (--lens).
PROMPT_LENS = (2112, 2048, 2112, 2048) + (320, 32) * 14
OUTPUT_TOKENS = 64
TOP = 20
TOL_MEAN = 0.06
TOL_MAX = 1.0
REC_LENS = (2112, 2048, 320, 32)
REC_TOL = 5e-5
# The wrong reference a whole run shows NOT within TOL_*: the nearest
# equation mistake to the limits.
MUST_FAIL = "conv_state_dropped"
OUT_DIR = os.path.join(ROOT, "chiprun_out", "check_reference")


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
def stage_engine(seed: int, lens) -> int:
    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.engine.engine import ServingEngine
    from production_stack_tpu.engine.sampling import SamplingParams

    flags = {f["flag"]: f["value"] for f in load("deployment.json")[
        "engine_flags"]}
    config = EngineConfig(
        model=HERE, load_format="dummy", seed=seed,
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

    async def run():
        await engine.start()
        try:
            return await asyncio.gather(*(one(t) for t in todo))
        finally:
            await engine.stop()

    t0 = time.monotonic()
    done = asyncio.run(run())
    report = engine.report()
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, "served.json"), "w") as f:
        json.dump({"seed": seed, "requests": done,
                   "device": report["device"],
                   "attn_impl": report["engine"]["attn_impl"],
                   "seconds": time.monotonic() - t0}, f)
    print(json.dumps({"stage": "engine", "requests": len(done),
                      "device": report["device"]["kind"],
                      "decode_rows_per_step": round(
                          engine.stats()["decode_row_steps_total"]
                          / max(1, engine.stats()["decode_steps_total"]), 1),
                      "preemptions": engine.stats()["num_preemptions"],
                      "seconds": round(time.monotonic() - t0, 1)}),
          flush=True)
    return 0


# -------------------------------------------------------------- recurrence
def stage_recurrence(seed: int, lens) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    sys.path.insert(0, HERE)
    import reference as ref
    from production_stack_tpu.ops import gated_delta as gd

    cfg = load("config.json")
    h, dk, dv = (cfg["linear_num_value_heads"], cfg["linear_key_head_dim"],
                 cfg["linear_value_head_dim"])
    rows, t, steps = len(lens), max(lens), OUTPUT_TOKENS
    ks = iter(jax.random.split(jax.random.PRNGKey(seed), 8))
    f32 = jnp.float32

    def normal(*shape):
        return jax.random.normal(next(ks), shape, f32)

    # Inputs for t prefilled and ``steps`` decoded tokens a row, as the
    # layer hands them over: q, k normalised, the gates from projections
    # of unit scale through init_params' A_log and dt_bias.
    q, k, v = gd.prepare(normal(rows, t + steps, h, dk),
                         normal(rows, t + steps, h, dk),
                         normal(rows, t + steps, h, dv))
    a_log = jnp.log(jax.random.uniform(next(ks), (h,), f32, 1e-3, 16.0))
    beta, g = gd.gates(normal(rows, t + steps, h), normal(rows, t + steps, h),
                       a_log, jnp.ones((h,), f32),
                       bool(cfg.get("linear_allow_neg_eigval")))
    lens_a = jnp.asarray(lens, jnp.int32)

    def reference(keep):
        # Row by row: the row's valid prompt tokens, then its decode tokens.
        outs, states = [], []
        for i, n in enumerate(lens):
            at = np.r_[0:n, t:t + steps]
            o, s = jax.jit(ref.delta_rule, static_argnums=5)(
                q[i, at], k[i, at], v[i, at], g[i, at], beta[i, at], keep)
            outs.append(o)
            states.append(s.astype(f32))
        return jnp.concatenate(outs), jnp.stack(states)

    def system():
        @jax.jit
        def run(q, k, v, g, beta):
            state = jnp.zeros((rows, *gd.packed_shape(h, dk, dv)), f32)
            o, state = gd.gdn_chunk(state, q[:, :t], k[:, :t], v[:, :t],
                                    g[:, :t], beta[:, :t], lens_a)

            def step(state, xs):
                o_t, state = gd.gdn_step(state, *xs,
                                         jnp.ones((rows,), bool))
                return state, o_t

            state, o_dec = jax.lax.scan(step, state, tuple(
                jnp.moveaxis(x[:, t:], 1, 0) for x in (q, k, v, g, beta)))
            return o, jnp.moveaxis(o_dec, 0, 1), gd.unpack_state(state, h)

        o, o_dec, state = run(q, k, v, g, beta)
        return jnp.concatenate([
            jnp.concatenate([o[i, :n], o_dec[i]])
            for i, n in enumerate(lens)]), state

    def rel(got, want):
        return float(jnp.linalg.norm((got - want).ravel())
                     / jnp.linalg.norm(want.ravel()))

    ref_o, ref_s = reference(f32)
    bf_o, bf_s = reference(jnp.bfloat16)
    sys_o, sys_s = system()
    hi, gd._HI = gd._HI, jax.lax.Precision.DEFAULT
    jax.clear_caches()
    low_o, low_s = system()
    gd._HI = hi
    out = {"stage": "recurrence", "device": jax.devices()[0].device_kind,
           "heads": [h, dk, dv], "lens": list(lens), "steps": steps,
           "tolerance": REC_TOL,
           "shipped": {"out": rel(sys_o, ref_o), "state": rel(sys_s, ref_s)},
           "default_precision": {"out": rel(low_o, ref_o),
                                 "state": rel(low_s, ref_s)},
           "vs_state_bf16": {"out": rel(sys_o, bf_o),
                             "state": rel(sys_s, bf_s)}}
    for name in ("shipped", "default_precision", "vs_state_bf16"):
        out[name]["within"] = max(out[name]["out"],
                                  out[name]["state"]) <= REC_TOL
    out["ok"] = out["shipped"]["within"] and not (
        out["default_precision"]["within"] or out["vs_state_bf16"]["within"])
    print(json.dumps(out), flush=True)
    return 0


# --------------------------------------------------------------- reference
def stage_reference(wrong: tuple) -> int:
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
        mc, jax.random.PRNGKey(served["seed"]), jnp.bfloat16)
    layer = jax.jit(ref.layer, static_argnums=(0, 1, 4))
    seqs = [r["prompt"] + r["output"][:-1] for r in served["requests"]]
    xs = [ref.embed(params, jnp.asarray(s)) for s in seqs]
    frozen = json.dumps(cfg, sort_keys=True)   # hashable for the jit

    class Cfg(dict):
        def __hash__(self):
            return hash(frozen)

    hcfg = Cfg(cfg)
    for i in range(cfg["num_hidden_layers"]):
        kind, lp = ref.layer_params(params, cfg, i)   # one layer in float32
        xs = [layer(hcfg, kind, lp, x, wrong) for x in xs]
        jax.block_until_ready(xs)
    stats = {"prefill": [], "decode": []}
    spread = []
    for req, x in zip(served["requests"], xs):
        n = len(req["prompt"])
        logits = ref.logits(params, cfg, x[n - 1:])
        spread.append(float(jnp.std(logits)))
        logp = np.asarray(jax.nn.log_softmax(logits, axis=-1))
        for j, (chosen, top) in enumerate(req["logprobs"]):
            phase = "prefill" if j == 0 else "decode"
            diffs = [abs(chosen - logp[j][req["output"][j]])]
            diffs += [abs(p - logp[j][t]) for t, p in top]
            stats[phase] += diffs
    out = {"stage": "reference", "wrong": list(wrong),
           "logit_spread": float(np.mean(spread)),
           "device": jax.devices()[0].device_kind}
    for phase, diffs in stats.items():
        out[phase] = {"n": len(diffs), "max": float(np.max(diffs)),
                      "mean": float(np.mean(diffs))}
    # A number that is not finite is not within anything.
    out["within"] = all(
        bool(np.isfinite(out[phase]["max"]))
        and out[phase]["mean"] <= TOL_MEAN and out[phase]["max"] <= TOL_MAX
        for phase in stats)
    out["tolerance"] = {"mean": TOL_MEAN, "max": TOL_MAX}
    print(json.dumps(out), flush=True)
    return 0


def main(argv=None) -> int:
    global HERE
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=20260929)
    ap.add_argument("--stage", choices=("recurrence", "engine", "reference"))
    ap.add_argument("--wrong", default="")
    ap.add_argument("--dir", default=HERE,
                    help="config.json, deployment.json and reference.py")
    ap.add_argument("--lens", default="",
                    help="prompt lengths, comma-separated (a rehearsal)")
    args = ap.parse_args(argv)
    HERE = os.path.abspath(args.dir)
    lens = tuple(int(n) for n in args.lens.split(",") if n)
    if args.stage == "recurrence":
        return stage_recurrence(args.seed, lens[:4] or REC_LENS)
    if args.stage == "engine":
        return stage_engine(args.seed, lens or PROMPT_LENS)
    if args.stage == "reference":
        return stage_reference(tuple(w for w in args.wrong.split(",") if w))
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        p for p in (ROOT, os.environ.get("PYTHONPATH")) if p))
    common = ["--seed", str(args.seed), "--dir", HERE, "--lens", args.lens]
    lines = []
    for stage in (["--stage", "recurrence"], ["--stage", "engine"],
                  ["--stage", "reference"],
                  ["--stage", "reference", "--wrong", MUST_FAIL]):
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), *stage, *common],
            env=env, capture_output=True, text=True)
        got = [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
        if proc.returncode != 0 or not got:
            print(proc.stderr[-3000:], file=sys.stderr)
            print(json.dumps({"ok": False, "failed": stage}), flush=True)
            return 1
        lines.append(json.loads(got[-1]))
        print(got[-1], flush=True)
    recurrence, _, right, wrong = lines
    ok = recurrence["ok"] and right["within"] and not wrong["within"]
    print(json.dumps({
        "ok": ok, "recurrence_ok": recurrence["ok"],
        "right_path_within": right["within"],
        f"{MUST_FAIL}_fails": not wrong["within"]}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
