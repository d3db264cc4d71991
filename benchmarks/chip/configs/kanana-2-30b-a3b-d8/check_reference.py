#!/usr/bin/env python3
"""The served path against the plain reference at PUBLISHED widths, on the
chip. The harness has no place for a reference (a cell's ``correct`` is
token counts, a probe and no compile in the window), so this is the
builder's own run, once a PR that touches the family:

    chiprun --timeout 2400 -- python3 \\
        benchmarks/chip/configs/kanana-2-30b-a3b-d8/check_reference.py

Children, one after the other (a chip belongs to one process); this parent
never imports JAX.

``--stage router``: the router alone, where its precision can be told. 4096
tokens' router inputs (bf16, unit scale: what a sparse layer's norm hands
over) through ``ops/moe.py:route`` with a router drawn as ``init_params``
draws it (128 experts, top-6, bias 0.05 N(0, 1)), against
``reference.route`` (float32, ``highest``) on IDENTICAL inputs: the share of
tokens whose top-6 SET differs, and the largest difference of a weight where
the sets agree. Two verdicts by ROUTER_TOL: the shipped router is within;
the reference with its product in bf16 (``router_bf16``: the nearest
precision below the one the configuration states) is NOT. Readings:
PERF.md section 6, PR 33.

``--stage engine``: the engine in-process at ``deployment.json``'s flags,
``config.json``'s widths and weights seeded by ``--seed``. First ONE prompt
of 384 tokens alone (served cold), so that its blocks are registered; then
all at once: the same 384 tokens again with 40 new ones behind them (a
PREFIX HIT: 384 tokens served from latent blocks, the rest prefilled over a
gathered window), one prompt of 3000 tokens (three prefill chunks, the
longest context the envelope admits with its output), two of 1100 (they
cross a chunk), and 28 of the traffic's own lengths (320 and 96), so that
the 32-row decode program the benchmark's window runs is one of those
compared; 48 greedy tokens each through the normal scheduler, prefill
chunks and decode trains over the latent pool. What the served surface
returns is kept: every generated token's own log-probability and the 20
most likely (``logprobs=20``): logits less their row's normaliser, from the
programs the benchmark times.

``--stage reference``: ``reference.py`` (float32, ``highest``, the expanded
attention, no cache, every expert computed eight at a time and weighted by
the routing) over prompt + generated tokens of every request, ONE layer's
weights widened from bf16 to float32 at a time (5.07 B float32 parameters
are 20 GB). The reference routes for ITSELF (``choices: free``): that
reading is the verdict. Beside it, for the record, the same with the
program's choices GIVEN (``forced``: the top-6 sets of the program's own
forward of the same tokens, ``models/deepseek_v3.py:forward(routing=True)``,
bf16 as served, no cache), and the share of (token, layer) choices in which
that forward and the free reference differ, by sparse layer. It reads
``served.json`` and needs no chip.

ROUTING IS DISCONTINUOUS. The router is float32 in program and reference
alike, but its INPUT is the program's bf16 residual stream: a token whose
6th and 7th scores lie within the rounding's reach chooses another expert
than the reference's token does, and is from there on a slightly different
function of its input. How far that carries depends on how large a layer's
branches are beside the stream: with every matrix at fan-in scale a swapped
expert moved a tenth of the stream and every later near-tie of the token fell
differently (first chip runs, PR 33: 31% of choices differed, 56% in the
last layer, mean error 0.25); with the branches a trained model's size
(``init_params``: projections back into the stream 1/sqrt(2 L) narrower,
the embedding at unit scale) it stays a few percent. PERF.md section 6,
PR 33, has both readings.

TOL_ROUTING, TOL_MEAN / TOL_MAX, and why these: the engine multiplies bf16
weights by bf16 activations with float32 accumulation through 8 layers and
rounds the residual stream to bf16 after each; the reference keeps float32
throughout. TOL_ROUTING bounds the share of choices that differ: it is what
rounding does at near-ties (the router stage shows the router itself agrees
on identical inputs); a wrong cache row, chunk or kernel moves the router's
input by far more than a rounding and the share with it. TOL_MEAN is argued
FROM that share: a token with a swapped expert differs by about what the
wrong reference's tokens do (every one of which has one expert too few, at
every layer), so the right path's mean is about share x that reading plus
the rounding's own, and the limit lies between the two readings with room on
both sides (``reference.WRONG``; PERF.md section 6, PR 33). The mean decides
(a maximum over 30,000 numbers is one unlucky token); the maximum is bounded
to catch a single row gone wrong (a block of another sequence, a stale
page).
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
TOL_MEAN = 0.035
TOL_MAX = 1.0
TOL_ROUTING = 0.15
ROUTER_TOKENS = 4096
ROUTER_TOL = 2e-3
# The wrong reference a whole run shows NOT within TOL_*: one expert too
# few (the router's PRECISION is the router stage's to tell; the other
# mistakes of ``reference.WRONG`` are tests/test_deepseek_v3.py's, in
# float32 on both sides, where a bias of 0.05 in a weight can be told from
# rounding; ``--stage reference --wrong <name>`` reads any of them here).
MUST_FAIL = ("top_k_minus_1",)
OUT_DIR = os.path.join(ROOT, "chiprun_out", "check_reference_kanana")


def load(name):
    with open(os.path.join(HERE, name)) as f:
        return json.load(f)


def prompts(seed: int, vocab: int, lens):
    import numpy as np

    rng = np.random.default_rng(seed)
    # Byte-tokenizer range, as the benchmark's traffic: ids 3..258.
    return [[int(t) for t in rng.integers(3, min(vocab, 259), n)]
            for n in lens]


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
    hcfg = _hashable(cfg)
    layer = jax.jit(ref.layer, static_argnums=(0, 1, 4))
    free = [ref.embed(params, jnp.asarray(s)) for s in seqs]
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
    ap.add_argument("--stage", choices=("router", "engine", "reference"))
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
    for stage in (["--stage", "router"], ["--stage", "engine"],
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
    router, _, right, *wrongs = lines
    ok = router["ok"] and right["within"] \
        and not any(w["within"] for w in wrongs)
    print(json.dumps({
        "ok": ok, "router_ok": router["ok"],
        "right_path_within": right["within"],
        **{f"{name}_fails": not w["within"]
           for name, w in zip(MUST_FAIL, wrongs)}}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
