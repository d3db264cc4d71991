#!/usr/bin/env python3
"""The served path against the plain reference at PUBLISHED widths, on the
chip. The harness has no place for a reference (a cell's ``correct`` is
token counts, a probe and no compile in the window), so this is the
builder's own run, once a PR that touches the family:

    chiprun --timeout 3000 -- python3 benchmarks/chip/configs/granite-4.0-h-micro/check_reference.py

Children, one after the other (a chip belongs to one process); this parent
never imports JAX.

``--stage recurrence``: the scan alone, where its precision can be told.
``ssd_chunk`` over 4 rows of 2112, 2048, 320 and 32 tokens (the traffic's
lengths; chunks of 128, the shorter rows padded) and then 64 ``ssd_step``
steps, then the gated norm, at the published 64 heads x 64 x state 128,
against ``reference.ssm_scan`` (float32, a token at a time) and the
reference's gated norm on identical inputs: gates drawn as ``init_params``
draws them. The number is ||system - reference|| / ||reference|| over the
outputs of every valid token and over the final states. Four verdicts, all
by REC_TOL: the shipped code is within; the same code with its products at
default precision (bf16 operands: the float32 state rounded at every chunk)
is NOT; against the reference with its state held in bf16, and against the
reference with its gated norm in bf16, the shipped code is NOT.

``--stage engine``: the engine in-process at ``deployment.json``'s flags,
``config.json``'s widths and weights seeded by ``--seed``, 64 greedy tokens
a request through the normal scheduler, prefill chunks and decode trains:
first ONE cold prompt alone, then THE SAME prompt again (its prefix is
registered and must go unserved: the state has no snapshot; the answer has
to be the cold one's), then 30 prompts AT ONCE: one of 2600 tokens (two
prefill chunks through its state slot), one of 2048, and the traffic's own
lengths (320 and 32, 14 each), so that the 16- and 32-row decode programs
the benchmark's window runs are the ones compared. What the served surface
returns is kept: every generated token's own log-probability and the 20 most
likely (``logprobs=20``).

``--stage reference``: ``reference.py`` (float32, ``highest``, token by
token, full attention matrix, no cache) over prompt + generated tokens of
every request, one layer's weights widened from bf16 to float32 at a time,
and the comparison: largest and mean |difference| of the log-probabilities,
per phase, beside the reference logits' spread. ``--wrong a,b``: ONE
equation wrong at a time (``reference.WRONG``), each of which must NOT be
within; ``--wrong all`` runs every one. It reads ``served.json`` and needs
no chip.

The limits and the readings they lie between (my chip runs, PR 40; PERF.md
section 6). REC_TOL 5e-5: the shipped scan and gated norm read 3.4e-6 of the
outputs' norm (3.1e-7 of the states'); default-precision products 1.4e-3, a
bf16 state 8.2e-4 (2.8e-3 of the states'), a bf16 gated norm 3.8e-3: fifteen
times of room on either side. TOL_MEAN 0.015 / TOL_MAX 0.1: the engine
multiplies bf16 weights by bf16 activations with float32 accumulation
through 40 layers and rounds the residual stream to bf16 after each, where
the reference keeps float32; with every sublayer's output times 0.22 that
moves a log-probability by 0.0080-0.0088 in the mean (largest of 43,000
numbers 0.045), a fifth of what the 16 layers of olmo-hybrid-7b-d16 cost.
The NEAREST wrong model is ``rope`` (a rotary embedding in 4 of 40 layers
whose scores have unit spread): mean 0.0246-0.0250, largest 0.127-0.217;
``attn_scale_rsqrt`` reads 0.12-0.13 / 0.53-0.62, every other wrong model
0.27 or more in the mean. The limits lie between the right path and
``rope``, with room on both sides; a wrong model fails by both. The
maximum is bounded to catch a single row gone wrong (a slot not cleared, a
state row swapped). TOL_* judge the equations and the rows; they cannot
tell the scan's precision (a state held in bf16 moves the mean by less than
bf16 weights and activations already cost): the recurrence stage does.
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
PROMPT_LENS = (320, 2600, 2048) + (320, 32) * 14
OUTPUT_TOKENS = 64
TOP = 20
TOL_MEAN = 0.015
TOL_MAX = 0.1
REC_LENS = (2112, 2048, 320, 32)
REC_TOL = 5e-5
OUT_DIR = os.path.join(ROOT, "chiprun_out", "check_reference_granite")


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
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, "served.json"), "w") as f:
        json.dump({"seed": seed, "dtype": dtype, "requests": done,
                   "device": report["device"],
                   "attn_impl": report["engine"]["attn_impl"],
                   "seconds": time.monotonic() - t0}, f)
    stats = engine.stats()
    ok = said["again_same_tokens"] and said["prefix_served_tokens"] == 0 \
        and said["prefix_unserved_tokens"] > 0
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
    import jax
    import jax.numpy as jnp
    import numpy as np

    sys.path.insert(0, HERE)
    import reference as ref
    from production_stack_tpu.ops import ssd

    cfg = load("config.json")
    h, p, n = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    eps = cfg["rms_norm_eps"]
    rows, t, steps = len(lens), max(lens), OUTPUT_TOKENS
    ks = iter(jax.random.split(jax.random.PRNGKey(seed), 12))
    f32 = jnp.float32

    def normal(*shape):
        return jax.random.normal(next(ks), shape, f32)

    # Inputs for t prefilled and ``steps`` decoded tokens a row, as the
    # layer hands them over: x, B, C after the conv's SiLU (of unit scale),
    # the gates from a projection of unit scale through init_params' A_log
    # and dt_bias, the skip as init_params draws it.
    total = t + steps
    x = jax.nn.silu(normal(rows, total, h, p))
    bm, cm = jax.nn.silu(normal(rows, total, n)), \
        jax.nn.silu(normal(rows, total, n))
    z = normal(rows, total, h * p)
    a_log = jnp.log(jax.random.uniform(next(ks), (h,), f32, 1.0, 16.0))
    dt0 = jax.random.uniform(next(ks), (h,), f32, 1e-3, 1e-1)
    dt, da = ssd.gates(normal(rows, total, h), a_log,
                       ssd.softplus_inverse(dt0))
    d_skip = jax.random.uniform(next(ks), (h,), f32, 0.5, 1.5)
    w_norm = 1.0 + 0.1 * normal(h * p)
    lens_a = jnp.asarray(lens, jnp.int32)

    def reference(keep, norm_bf16=False):
        # Row by row: the row's valid prompt tokens, then its decode tokens.
        outs, states = [], []
        for i, m in enumerate(lens):
            at = np.r_[0:m, t:t + steps]
            y, s = jax.jit(ref.ssm_scan, static_argnums=6)(
                x[i, at], bm[i, at], cm[i, at], dt[i, at],
                jnp.exp(da[i, at]), d_skip, keep)
            y = y.reshape(len(at), h * p) * jax.nn.silu(z[i, at])
            if norm_bf16:
                yb = y.astype(jnp.bfloat16)
                y = (yb * jax.lax.rsqrt(jnp.mean(
                    yb * yb, -1, keepdims=True) + jnp.bfloat16(eps))
                    * w_norm.astype(jnp.bfloat16)).astype(f32)
            else:
                y = ref.rms_norm(y, w_norm, eps)
            outs.append(y)
            states.append(s.astype(f32))
        return jnp.concatenate(outs), jnp.stack(states)

    def system():
        @jax.jit
        def run(x, bm, cm, dt, da, z):
            state = jnp.zeros((rows, h, p, n), f32)
            y, state = ssd.ssd_chunk(state, x[:, :t], bm[:, :t], cm[:, :t],
                                     dt[:, :t], da[:, :t], d_skip, lens_a)

            def step(state, xs):
                y_t, state = ssd.ssd_step(state, *xs, d_skip,
                                          jnp.ones((rows,), bool))
                return state, y_t

            state, y_dec = jax.lax.scan(step, state, tuple(
                jnp.moveaxis(v[:, t:], 1, 0) for v in (x, bm, cm, dt, da)))
            y = jnp.concatenate([y, jnp.moveaxis(y_dec, 0, 1)], axis=1)
            return ssd.gated_norm(y.reshape(rows, total, h * p), z, w_norm,
                                  eps), state

        y, state = run(x, bm, cm, dt, da, z)
        return jnp.concatenate([
            jnp.concatenate([y[i, :m], y[i, t:]])
            for i, m in enumerate(lens)]), state

    def rel(got, want):
        return float(jnp.linalg.norm((got - want).ravel())
                     / jnp.linalg.norm(want.ravel()))

    ref_o, ref_s = reference(f32)
    bf_o, bf_s = reference(jnp.bfloat16)
    nb_o, nb_s = reference(f32, norm_bf16=True)
    sys_o, sys_s = system()
    hi, ssd._HI = ssd._HI, jax.lax.Precision.DEFAULT
    jax.clear_caches()
    low_o, low_s = system()
    ssd._HI = hi
    out = {"stage": "recurrence", "device": jax.devices()[0].device_kind,
           "heads": [h, p, n], "lens": list(lens), "steps": steps,
           "chunk": ssd.CHUNK, "tolerance": REC_TOL,
           "shipped": {"out": rel(sys_o, ref_o), "state": rel(sys_s, ref_s)},
           "default_precision": {"out": rel(low_o, ref_o),
                                 "state": rel(low_s, ref_s)},
           "vs_state_bf16": {"out": rel(sys_o, bf_o),
                             "state": rel(sys_s, bf_s)},
           "vs_gated_norm_bf16": {"out": rel(sys_o, nb_o),
                                  "state": rel(sys_s, nb_s)}}
    names = ("shipped", "default_precision", "vs_state_bf16",
             "vs_gated_norm_bf16")
    for name in names:
        out[name]["within"] = max(out[name]["out"],
                                  out[name]["state"]) <= REC_TOL
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
    layer = jax.jit(ref.layer, static_argnums=(0, 1, 4))
    seqs = [r["prompt"] + r["output"][:-1] for r in served["requests"]]
    frozen = json.dumps(cfg, sort_keys=True)   # hashable for the jit

    class Cfg(dict):
        def __hash__(self):
            return hash(frozen)

    hcfg = Cfg(cfg)
    if wrongs == [("all",)]:
        wrongs = [(w,) for w in ref.WRONG]

    def compare(wrong):
        xs = [ref.embed(params, cfg, jnp.asarray(s), wrong) for s in seqs]
        for i in range(cfg["num_hidden_layers"]):
            kind, lp = ref.layer_params(params, cfg, i)  # one layer, float32
            xs = [layer(hcfg, kind, lp, x, wrong) for x in xs]
            jax.block_until_ready(xs)
        stats = {"prefill": [], "decode": []}
        spread = []
        for req, x in zip(served["requests"], xs):
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
    ap.add_argument("--seed", type=int, default=20261001)
    ap.add_argument("--stage", choices=("recurrence", "engine", "reference"))
    ap.add_argument("--wrong", default="",
                    help="wrong models, comma-separated, one at a time; all")
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
                  ["--stage", "reference", "--wrong", args.wrong or "all"]):
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
