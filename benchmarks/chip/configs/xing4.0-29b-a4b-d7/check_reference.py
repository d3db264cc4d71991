#!/usr/bin/env python3
"""The served path against the plain reference at PUBLISHED widths, on the
chip. The harness has no place for a reference (a cell's ``correct`` is
token counts, a probe and no compile in the window), so this is the
builder's own run, once a PR that touches the family:

    chiprun --timeout 3000 -- python3 \\
        benchmarks/chip/configs/xing4.0-29b-a4b-d7/check_reference.py

Children, one after the other (a chip belongs to one process); this parent
never imports JAX. It is kanana-2-30b-a3b-d8's script (the same module
serves both) with one stage more, the stream mix alone.

``--stage mix``: the stream mixing alone, where its precision can be told.
4096 tokens' streams (4 x 3584 = 14336 values a token, bf16: a shared part at
unit scale and each stream's own at 0.3, what a few layers of branches leave)
through ``ops/hyper_connections.py`` (``mix_matrices``, ``pre``, ``post``)
with a sublayer's ``phi``, ``b``, ``a`` drawn as ``init_params`` draws them,
against ``reference.mix_matrices`` / ``mix_pre`` / ``mix_post`` (float32,
``highest``) on IDENTICAL inputs: the largest difference of an entry of
``H_pre``, ``H_post``, ``H_res``, of ``H_pre x`` and of the streams after the
sublayer (a unit-scale branch), and the largest distance of a row or column
sum of ``H_res`` from 1. By MIX_TOL the shipped mix is within, and each of
the reference's four mistakes is NOT: the mix in bf16 (``hc_mix_bf16``: the
nearest precision below the one the configuration states), one Sinkhorn
iteration for twenty, no dynamic term, ``H_post`` without its 2.
MIX_TOL = 2e-4 absolute on numbers of unit size: the program and the
reference differ in where the norm's scale is applied, in a reciprocal for a
division and in the order of a 14336-term sum, all float32 (readings near
1e-6, 2e-5 where 20 iterations' residue enters); a bf16 product of 14336
terms moves a logit by 1e-2 and a matrix entry by 1e-3 and more. Readings:
PERF.md section 6, PR 38.

``--stage router``: the router alone (64 experts, top-4), as kanana's.

``--stage engine``: the engine in-process at ``deployment.json``'s flags,
``config.json``'s widths and weights seeded by ``--seed``. First ONE prompt
of 384 tokens alone (served cold), so that its blocks are registered; then
all at once: the same 384 tokens again with 40 new ones behind them (a
PREFIX HIT), one prompt of 3000 tokens (three prefill chunks), two of 1100
(they cross a chunk), and 28 of the traffic's own lengths (320 and 96), so
that the 32-row decode program the benchmark's window runs is one of those
compared; 48 greedy tokens each through the normal scheduler, prefill
chunks and decode trains over the latent pool. What the served surface
returns is kept (``logprobs=20``).

``--stage reference``: ``reference.py`` (float32, ``highest``, the expanded
attention, no cache, every expert computed eight at a time and weighted by
the routing, the streams mixed per token) over prompt + generated tokens of
every request, ONE layer's weights widened from bf16 to float32 at a time.
The reference routes for ITSELF (``choices: free``): that reading is the
verdict. Beside it, for the record, the same with the program's choices
GIVEN (``forced``), and the share of (token, layer) choices in which the
program's forward and the free reference differ, by sparse layer. It reads
``served.json`` and needs no chip.

ROUTING IS DISCONTINUOUS (kanana's script says how far a swapped expert
carries; PERF.md section 6, PR 33). TOL_ROUTING, TOL_MEAN / TOL_MAX, and why
these: the engine multiplies bf16 weights by bf16 activations with float32
accumulation through 7 layers and rounds the four streams to bf16 after each
of 14 sublayers; the reference keeps float32 throughout. TOL_ROUTING bounds
the share of choices that differ: what rounding does at near-ties (the router
stage shows the router itself agrees on identical inputs). TOL_MEAN lies
between the right path's reading and the wrong references' (MUST_FAIL: one
expert too few; ``H_post`` without its 2: a mistake of the mix that the
WHOLE model's logits show), with room on both sides; the maximum is bounded
to catch a single row gone wrong. Readings (my chip run, PR 38, seed
20260929): the right path's mean 0.0044 prefill / 0.0056 decode, largest
0.097 / 0.204, 1.8% of choices differing (1.3% in the first sparse layer,
2.4% in the last); one expert too few 0.056 / 0.060; ``H_post`` without its 2
0.099 / 0.096. So TOL_MEAN 0.02 (3.6 x the right reading, a third of the
nearest wrong one), TOL_ROUTING 0.06 (3.3 x the reading: fresh seeds read
higher), TOL_MAX 1.0 (5 x the largest of 33,264 numbers). PERF.md section 6,
PR 38.
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
# and deployment.json beside a copy of reference.py, short lengths (--lens:
# the shared prefix first, then the others) and --dtype float32.
SHARED = 384
SUFFIX = 40
PROMPT_LENS = (3000, 1100, 1100) + (320, 96) * 14
OUTPUT_TOKENS = 48
TOP = 20
TOL_MEAN = 0.02
TOL_MAX = 1.0
TOL_ROUTING = 0.06
ROUTER_TOKENS = 4096
ROUTER_TOL = 2e-3
MIX_TOKENS = 4096
MIX_TOL = 2e-4
MIX_WRONG = ("hc_mix_bf16", "hc_one_iter", "hc_no_dynamic",
             "hc_post_not_doubled")
# The wrong references a whole run shows NOT within TOL_*: one expert too
# few, and the post-mix without its factor (the other mistakes of the mix are
# the mix stage's to tell, on identical inputs; those of the query and the
# rope are tests/test_xing4.py's, in float32 on both sides and beside HF's
# own code; ``--stage reference --wrong <name>`` reads any of them here).
MUST_FAIL = ("top_k_minus_1", "hc_post_not_doubled")
OUT_DIR = os.path.join(ROOT, "chiprun_out", "check_reference_xing4")


def load(name):
    with open(os.path.join(HERE, name)) as f:
        return json.load(f)


def prompts(seed: int, vocab: int, lens):
    import numpy as np

    rng = np.random.default_rng(seed)
    # Byte-tokenizer range, as the benchmark's traffic: ids 3..258.
    return [[int(t) for t in rng.integers(3, min(vocab, 259), n)]
            for n in lens]


# --------------------------------------------------------------------- mix
def stage_mix(seed: int) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    sys.path.insert(0, HERE)
    import reference as ref
    from production_stack_tpu.models import deepseek_v3 as ds
    from production_stack_tpu.models.config import ModelConfig
    from production_stack_tpu.ops import hyper_connections as hc

    cfg = load("config.json")
    mc = ModelConfig.from_hf_config(cfg)
    n, d = mc.hc_mult, mc.hidden_size
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    # As models/deepseek_v3.py:init_params draws a sublayer's mix.
    lp = {k: v[0] for k, v in ds._init_mix(mc, ks[0], 1).items()
          if k.startswith("hc_attn_")}
    phi, b, a = lp["hc_attn_phi"], lp["hc_attn_b"], lp["hc_attn_a"]
    x = (jax.random.normal(ks[1], (1, MIX_TOKENS, d))
         + 0.3 * jax.random.normal(ks[2], (n, MIX_TOKENS, d))).astype(
             jnp.bfloat16)
    branch = jax.random.normal(ks[3], (MIX_TOKENS, d)).astype(jnp.bfloat16)

    @jax.jit
    def shipped(x, branch):
        mats = hc.mix_matrices(
            x, phi, b, a, iters=mc.hc_sinkhorn_iters, eps=mc.hc_eps,
            norm_eps=mc.rms_norm_eps, clamp=mc.hc_res_clamp)
        return (*mats, hc.pre(x, mats[0]),
                hc.post(x, branch, mats[1], mats[2]).transpose(1, 0, 2))

    def reference(wrong):
        @jax.jit
        def run(x, branch):
            with jax.default_matmul_precision("highest"):
                xt = x.astype(jnp.float32).transpose(1, 0, 2)
                mats = ref.mix_matrices(cfg, phi, b, a, xt, wrong)
                return (*mats, ref.mix_pre(xt, mats[0]), ref.mix_post(
                    xt, branch.astype(jnp.float32), mats[1], mats[2]))
        return [np.asarray(v) for v in run(x, branch)]

    ours = [np.asarray(v) for v in shipped(x, branch)]
    names = ("h_pre", "h_post", "h_res", "pre", "post")

    def against(theirs):
        out = {k: float(np.max(np.abs(u - t)))
               for k, u, t in zip(names, ours, theirs)}
        out["largest"] = max(out.values())
        out["within"] = out["largest"] <= MIX_TOL
        return out

    res = ours[2]
    out = {"stage": "mix", "device": jax.devices()[0].device_kind,
           "tokens": MIX_TOKENS, "streams": n, "values_a_token": n * d,
           "tolerance": MIX_TOL,
           "row_sum_off_1": float(np.max(np.abs(res.sum(-1) - 1))),
           "col_sum_off_1": float(np.max(np.abs(res.sum(-2) - 1))),
           "shipped": against(reference(())),
           **{f"vs_{w}": against(reference((w,))) for w in MIX_WRONG}}
    out["ok"] = out["shipped"]["within"] and not any(
        out[f"vs_{w}"]["within"] for w in MIX_WRONG)
    print(json.dumps(out), flush=True)
    return 0


# ------------------------------------------------------------------ router
def stage_router(seed: int) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    sys.path.insert(0, HERE)
    import reference as ref
    from production_stack_tpu.ops import moe

    cfg = load("config.json")
    d, e, k = (cfg["hidden_size"], cfg["n_routed_experts"],
               cfg["num_experts_per_tok"])
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    # As models/deepseek_v3.py:init_params draws a sparse layer's router.
    lp = {"w_router": (jax.random.normal(ks[0], (d, e), jnp.float32)
                       * d ** -0.5).astype(jnp.bfloat16).astype(jnp.float32),
          "router_bias": 0.05 * jax.random.normal(ks[1], (e,), jnp.float32)}
    x = jax.random.normal(ks[2], (ROUTER_TOKENS, d), jnp.float32).astype(
        jnp.bfloat16)
    idx, w = jax.jit(moe.route, static_argnums=(3, 4, 5))(
        x, lp["w_router"], lp["router_bias"], k,
        cfg["routed_scaling_factor"], cfg["norm_topk_prob"])

    def reference(wrong):
        with jax.default_matmul_precision("highest"):
            chosen, dense = jax.jit(ref.route, static_argnums=(0, 3))(
                _hashable(cfg), lp, x.astype(jnp.float32), wrong)
        return np.asarray(chosen), np.asarray(dense)

    def against(want_idx, want_dense):
        ours = np.sort(np.asarray(idx), axis=-1)
        same = np.all(ours == np.sort(want_idx, axis=-1), axis=-1)
        got = np.take_along_axis(want_dense, np.asarray(idx), axis=1)
        return {"share_differ": float(1.0 - same.mean()),
                "max_weight_diff": float(np.max(np.abs(
                    got - np.asarray(w))[same])) if same.any() else None}

    out = {"stage": "router", "device": jax.devices()[0].device_kind,
           "tokens": ROUTER_TOKENS, "experts": e, "top_k": k,
           "tolerance": ROUTER_TOL,
           "shipped": against(*reference(())),
           "vs_router_bf16": against(*reference(("router_bf16",)))}
    for name in ("shipped", "vs_router_bf16"):
        out[name]["within"] = out[name]["share_differ"] <= ROUTER_TOL
    out["ok"] = out["shipped"]["within"] and \
        not out["vs_router_bf16"]["within"]
    print(json.dumps(out), flush=True)
    return 0


def _hashable(cfg: dict):
    """``cfg`` as a dict a jit can take as a static argument."""
    frozen = json.dumps(cfg, sort_keys=True)

    class Cfg(dict):
        def __hash__(self):
            return hash(frozen)

    return Cfg(cfg)


# ------------------------------------------------------------------ engine
def stage_engine(seed: int, shared: int, lens, dtype: str) -> int:
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
    vocab = engine.model_config.vocab_size
    first, suffix, *rest = prompts(seed, vocab, (shared, SUFFIX, *lens))

    async def one(kind, tokens):
        last = None
        async for out in engine.generate(
                prompt_token_ids=tokens, sampling=SamplingParams(
                    temperature=0.0, max_tokens=OUTPUT_TOKENS,
                    ignore_eos=True, logprobs=TOP)):
            last = out
        return {"kind": kind, "prompt": tokens,
                "output": list(last.token_ids),
                "logprobs": [[lp, [[int(t), float(p)] for t, p in top]]
                             for lp, top in last.logprobs]}

    async def run():
        await engine.start()
        try:
            cold = await one("cold", first)
            hits = engine.block_manager.prefix_hits_total
            others = await asyncio.gather(
                one("prefix_hit", first + suffix),
                *(one("batch", t) for t in rest))
            return [cold, *others], \
                engine.block_manager.prefix_hits_total - hits
        finally:
            await engine.stop()

    t0 = time.monotonic()
    done, hit_tokens = asyncio.run(run())
    report, stats = engine.report(), engine.stats()
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, "served.json"), "w") as f:
        json.dump({"seed": seed, "dtype": dtype, "requests": done,
                   "device": report["device"],
                   "attn_impl": report["engine"]["attn_impl"],
                   "seconds": time.monotonic() - t0}, f)
    calls = max(1, stats["moe_layer_calls_total"])
    print(json.dumps({
        "stage": "engine", "requests": len(done),
        "device": report["device"]["kind"],
        "attn_impl": report["engine"]["attn_impl"],
        "prefix_hit_tokens": hit_tokens,
        "longest_prompt": max(len(r["prompt"]) for r in done),
        "decode_rows_per_step": round(
            stats["decode_row_steps_total"]
            / max(1, stats["decode_steps_total"]), 1),
        "experts_touched_per_decode_call": round(
            stats["moe_experts_touched_total"] / calls, 1),
        "preemptions": stats["num_preemptions"],
        "seconds": round(time.monotonic() - t0, 1)}), flush=True)
    return 0 if hit_tokens >= shared // 16 * 16 else 1


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
    model = get_model(mc)
    # The same weights: the engine's init, the engine's seed and dtype.
    params = model.init_params(
        mc, jax.random.PRNGKey(served["seed"]), jnp.dtype(served["dtype"]))
    seqs = [r["prompt"] + r["output"][:-1] for r in served["requests"]]
    # The program's own choices: its forward of the same tokens, as served
    # (bf16), without a cache, padded to a token bucket as a prefill chunk
    # is (the padding reaches no expert and is cut off again).
    forward = jax.jit(model.forward, static_argnums=(1,),
                      static_argnames=("routing",))
    ours = []
    for tokens in seqs:
        t = len(tokens)
        padded = -(-t // 256) * 256
        *_, chosen = forward(
            params, mc, jnp.asarray([tokens + [0] * (padded - t)], jnp.int32),
            jnp.arange(padded, dtype=jnp.int32)[None],
            jnp.asarray([t], jnp.int32), routing=True)
        ours.append(np.asarray(chosen)[:, :t])
    # The served weights go to the host once the program's choices are
    # known: a sparse layer in float32 is 3 GB and the streams of all
    # requests 1.5 GB, which do not fit beside 9.85 GB of bf16 weights. A
    # layer at a time comes back and is widened (``layer_params``).
    params = jax.device_get(params)
    hcfg = _hashable(cfg)
    layer = jax.jit(ref.layer, static_argnums=(0, 1, 4))
    free = [ref.embed(params, cfg, jnp.asarray(s)) for s in seqs]
    given = list(free) if not wrong else []
    nd = cfg.get("first_k_dense_replace", 0)
    sparse = cfg["num_hidden_layers"] - nd
    differ, choices = np.zeros(sparse, int), np.zeros(sparse, int)
    for i in range(cfg["num_hidden_layers"]):
        kind, lp = ref.layer_params(params, cfg, i)   # one layer in float32
        for n in range(len(seqs)):
            free[n], theirs = layer(hcfg, kind, lp, free[n], wrong)
            if theirs is not None and not wrong:
                differ[i - nd] += int(np.sum(np.any(
                    np.sort(ours[n][i - nd], axis=-1)
                    != np.sort(np.asarray(theirs), axis=-1), axis=-1)))
                choices[i - nd] += len(seqs[n])
            if given:
                forced = None if i < nd else jnp.asarray(ours[n][i - nd])
                given[n], _ = layer(hcfg, kind, lp, given[n], wrong, forced)
        jax.block_until_ready(free)
    del lp

    def errors(streams):
        """|served - reference| of every returned log-probability, by
        phase and by the kind of request."""
        stats, by_kind, spread = {"prefill": [], "decode": []}, {}, []
        for req, x in zip(served["requests"], streams):
            n = len(req["prompt"])
            logits = ref.logits(params, cfg, x[n - 1:])
            spread.append(float(jnp.std(logits)))
            logp = np.asarray(jax.nn.log_softmax(logits, axis=-1))
            for j, (chosen, top) in enumerate(req["logprobs"]):
                phase = "prefill" if j == 0 else "decode"
                diffs = [abs(chosen - logp[j][req["output"][j]])]
                diffs += [abs(p - logp[j][t]) for t, p in top]
                stats[phase] += diffs
                by_kind.setdefault(req["kind"] if n < 2000 else "longest",
                                   []).extend(diffs)
        brief = lambda v: {"n": len(v), "max": float(np.max(v)),  # noqa: E731
                           "mean": float(np.mean(v))}
        return {**{k: brief(v) for k, v in stats.items()},
                "by_kind": {k: brief(v) for k, v in by_kind.items()},
                "logit_spread": float(np.mean(spread))}

    # The verdict is the FREE reference's: it routes for itself.
    out = {"stage": "reference", "wrong": list(wrong), "choices": "free",
           "device": jax.devices()[0].device_kind, **errors(free)}
    if not wrong:
        share = float(differ.sum() / max(1, choices.sum()))
        out["routing"] = {
            "choices": int(choices.sum()), "differ": int(differ.sum()),
            "share": share,
            "share_by_sparse_layer": [
                round(float(a / max(1, b)), 4)
                for a, b in zip(differ, choices)]}
        out["choices_given"] = errors(given)
    # A number that is not finite is not within anything.
    out["within"] = all(
        bool(np.isfinite(out[phase]["max"]))
        and out[phase]["mean"] <= TOL_MEAN and out[phase]["max"] <= TOL_MAX
        for phase in ("prefill", "decode")) and (
            bool(wrong) or out["routing"]["share"] <= TOL_ROUTING)
    out["tolerance"] = {"mean": TOL_MEAN, "max": TOL_MAX,
                        "routing": TOL_ROUTING}
    print(json.dumps(out), flush=True)
    return 0


def main(argv=None) -> int:
    global HERE
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=20260929)
    ap.add_argument("--stage", choices=("mix", "router", "engine", "reference"))
    ap.add_argument("--wrong", default="")
    ap.add_argument("--dir", default=HERE,
                    help="config.json, deployment.json and reference.py")
    ap.add_argument("--dtype", default="bfloat16",
                    help="float32 for a rehearsal on the CPU (its backend "
                         "has no bf16 x bf16 -> f32 product)")
    ap.add_argument("--lens", default="",
                    help="the shared prefix's length, then the other "
                         "prompts', comma-separated (a rehearsal)")
    args = ap.parse_args(argv)
    HERE = os.path.abspath(args.dir)
    lens = tuple(int(n) for n in args.lens.split(",") if n)
    shared, lens = (lens[0], lens[1:]) if lens else (SHARED, PROMPT_LENS)
    if args.stage == "mix":
        return stage_mix(args.seed)
    if args.stage == "router":
        return stage_router(args.seed)
    if args.stage == "engine":
        return stage_engine(args.seed, shared, lens, args.dtype)
    if args.stage == "reference":
        return stage_reference(tuple(w for w in args.wrong.split(",") if w))
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        p for p in (ROOT, os.environ.get("PYTHONPATH")) if p))
    common = ["--seed", str(args.seed), "--dir", HERE, "--lens", args.lens,
              "--dtype", args.dtype]
    lines = []
    for stage in (["--stage", "mix"], ["--stage", "router"],
                  ["--stage", "engine"],
                  ["--stage", "reference"],
                  *(["--stage", "reference", "--wrong", w]
                    for w in MUST_FAIL)):
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), *stage, *common],
            env=env, capture_output=True, text=True)
        got = [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
        if proc.returncode != 0 or not got:
            print(proc.stderr[-3000:], file=sys.stderr)
            print(json.dumps({"ok": False, "failed": stage,
                              "line": got[-1:]}), flush=True)
            return 1
        lines.append(json.loads(got[-1]))
        print(got[-1], flush=True)
    mix, router, _, right, *wrongs = lines
    ok = mix["ok"] and router["ok"] and right["within"] \
        and not any(w["within"] for w in wrongs)
    print(json.dumps({
        "ok": ok, "mix_ok": mix["ok"], "router_ok": router["ok"],
        "right_path_within": right["within"],
        **{f"{name}_fails": not w["within"]
           for name, w in zip(MUST_FAIL, wrongs)}}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
