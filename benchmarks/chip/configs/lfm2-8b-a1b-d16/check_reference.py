#!/usr/bin/env python3
"""The served path against the plain reference at PUBLISHED widths, on the
chip. The harness has no place for a reference (a cell's ``correct`` is
token counts, a probe and no compile in the window), so this is the
builder's own run, once a PR that touches the family:

    chiprun --timeout 3000 -- python3 benchmarks/chip/configs/lfm2-8b-a1b-d16/check_reference.py

Children, one after the other (a chip belongs to one process); this parent
never imports JAX.

``--stage alone``: the two computations whose precision a whole run cannot
tell, each alone on IDENTICAL inputs. *router*: 4096 tokens' router inputs
(bf16, unit scale: what a sparse layer's norm hands over) through
``ops/moe.py:route`` with a router drawn as ``init_params`` draws it (the
published 2048 -> 32, top-4, bias 0.05 N(0, 1), 1e-6 in the weights' sum)
against ``reference.route`` (float32, ``highest``): the share of tokens whose
top-4 SET differs, and the largest difference of a weight where the sets
agree. *qk_norm*: 4096 tokens' 32 heads of 64 lanes through
``models/lfm2_moe.py:head_norm_rope`` against the reference's norm and rope:
||system - reference|| / ||reference|| (the inputs are bf16's values held
in float32, so that the output's one rounding does not hide the arithmetic
before it). Verdicts by ROUTER_TOL, ROUTER_WEIGHT_TOL and NORM_TOL: the
shipped code is within all; the reference with its router in bf16
(``router_bf16``) and with its norm in bf16 (``qk_norm_bf16``), the nearest
precision below the one the configuration states, are NOT, nor is the
reference that weighs by score + bias (``bias_in_weights``: it chooses as
the right model does, so the weights alone tell it, and a whole run's mean
does not).

``--stage engine``: the engine in-process at ``deployment.json``'s flags,
``config.json``'s widths and weights seeded by ``--seed``, 64 greedy tokens
a request through the normal scheduler, prefill chunks and decode trains:
first ONE cold prompt alone, then THE SAME prompt again (its prefix is
registered and must go unserved: the conv state has no snapshot; the answer
has to be the cold one's), then 30 prompts AT ONCE: one of 2600 tokens
(three prefill chunks through its conv slot), one of 2049 (its third chunk
is its LAST TOKEN ALONE: the first answer stands right behind a chunk
boundary, where a conv state lost between chunks shows whole; ISSUE 44 said
2048, which ends a chunk and shows nothing), and the traffic's own lengths
(320 and 32, 14 each), so that the 16- and 32-row decode programs the
benchmark's window runs are the ones compared. What the
served surface returns is kept: every generated token's own log-probability
and the 20 most likely (``logprobs=20``).

``--stage reference``: ``reference.py`` (float32, ``highest``, the
convolution a direct sum, full attention matrix, no cache, every expert
computed eight at a time and weighted by the routing) over prompt +
generated tokens of every request, ONE layer's weights widened from bf16 to
float32 at a time. The reference routes for ITSELF: that reading is the
verdict. Beside it the share of (token, sparse layer) choices in which the
program's own forward of the same tokens (``forward(routing=True)``, bf16 as
served, no cache) and the reference differ, by sparse layer. ``--wrong
a,b``: ONE equation wrong at a time (``reference.WRONG``), each of which
must NOT be within; ``--wrong all`` runs every one. It reads
``served.json`` and needs no chip.

ROUTING IS DISCONTINUOUS (kanana-2-30b-a3b-d8's check_reference.py says it
at length): the router is float32 in program and reference alike, but its
INPUT is the program's bf16 residual stream, so a token whose 4th and 5th
scores lie within the rounding's reach chooses another expert than the
reference's token does. TOL_ROUTING bounds that share; TOL_MEAN / TOL_MAX
bound the log-probabilities' differences. The limits and the readings they
lie between are written beside them below.
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
# the cold prompt first, then the batch) and --dtype float32.
PROMPT_LENS = (320, 2600, 2049) + (320, 32) * 14
OUTPUT_TOKENS = 64
TOP = 20
ALONE_TOKENS = 4096
# My chip runs, PR 44 (PERF.md section 6 has every reading). The shipped
# router agrees with the float32 reference on every one of 4096 tokens'
# top-4 sets (largest weight difference 0.0); the reference with its router
# in bf16 differs on 1.9% of them. ROUTER_TOL lies between: nine times under
# the bf16 reading, and any disagreement of the shipped router beyond 8
# tokens of 4096 fails. ROUTER_WEIGHT_TOL bounds the largest difference of a
# weight where the sets agree: the shipped router's reads 0.0, the bf16
# router's 1.1e-3, and ``bias_in_weights`` (the one wrong model the whole
# run below cannot tell from rounding) whole hundredths, since it chooses
# as the right model does and weighs by score + bias.
ROUTER_TOL = 2e-3
ROUTER_WEIGHT_TOL = 1e-4
# The shipped per-head norm and rope read 0.0 of the reference's norm (the
# same float32 operations); the norm computed in bf16 3.0e-3. NORM_TOL lies
# between, a decade and more from both.
NORM_TOL = 1e-4
# The engine multiplies bf16 weights by bf16 activations with float32
# accumulation through 16 layers and rounds the residual stream to bf16
# after each, where the reference keeps float32; a share of tokens chooses
# another expert at a near-tie (TOL_ROUTING) and is from there on a
# slightly different function of its input. Readings of the shipped path
# (my chip runs, PR 44; logit spread 1.0; seed 20261002, the draw as
# committed; the first draw's and every wrong model's are in PERF.md
# section 6): mean 0.0143 (prefill) and 0.0175 (decode), largest 0.20 of
# 43,000 numbers, 4.0% of choices differ (1.5% in the first sparse layer,
# 6.9% in the last: a swapped expert moves later near-ties).
# The NEAREST wrong model is ``bias_in_weights`` (a bias of 0.05 in weights
# of about 0.25): mean 0.0173-0.0198, which the mean cannot tell from
# rounding with room on both sides, so it is the ``alone`` stage's to tell
# (ROUTER_WEIGHT_TOL, on identical inputs: 0.040 where the shipped router
# reads 0.0) and tests/test_lfm2_moe.py's (float32 on both sides: 150
# times its tolerance). Of the others the nearest by the mean is
# ``softmax_router`` (0.121), then ``no_qk_norm`` (0.166-0.175) and
# ``rope_interleaved`` (0.207-0.210); the three mistakes of the convolution
# read 0.31-0.67, ``no_topk_norm`` 0.37. TOL_MEAN is twice the shipped
# path's larger mean and 3.5 times under ``softmax_router``'s.
# ``conv_state_zero_at_chunk`` touches two requests of 32 (those that cross
# a chunk): its decode mean is the shipped path's (0.0177, its prefill mean
# 0.0299); it fails by the maximum (1.25, the first answer of the
# 2049-token prompt, right behind a chunk boundary). The maximum is bounded
# to catch such a single row gone wrong (a slot not cleared, a conv state
# lost or swapped, a block of another sequence): it reads whole units where
# the shipped path's largest is 0.20. TOL_ROUTING: twice the shipped share;
# a wrong cache row, chunk or kernel moves the router's input by far more
# than a rounding.
TOL_MEAN = 0.035
TOL_MAX = 0.5
TOL_ROUTING = 0.08
# Wrong models a whole run must show NOT within: all but the one the mean
# cannot tell on the chip (the ``alone`` stage tells it).
NOT_TOLD_ON_CHIP = ("bias_in_weights",)
OUT_DIR = os.path.join(ROOT, "chiprun_out", "check_reference_lfm2")


def load(name):
    with open(os.path.join(HERE, name)) as f:
        return json.load(f)


def prompts(seed: int, vocab: int, lens):
    import numpy as np

    rng = np.random.default_rng(seed)
    # Byte-tokenizer range, as the benchmark's traffic: ids 3..258.
    return [[int(t) for t in rng.integers(3, min(vocab, 259), n)]
            for n in lens]


def _hashable(cfg: dict):
    """``cfg`` as a dict a jit can take as a static argument."""
    frozen = json.dumps(cfg, sort_keys=True)

    class Cfg(dict):
        def __hash__(self):
            return hash(frozen)

    return Cfg(cfg)


# ------------------------------------------------------------------- alone
def stage_alone(seed: int) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    sys.path.insert(0, HERE)
    import reference as ref
    from production_stack_tpu.models import lfm2_moe
    from production_stack_tpu.models.llama import _rope_cos_sin
    from production_stack_tpu.ops import moe

    cfg = load("config.json")
    d, e, k = cfg["hidden_size"], cfg["num_experts"], \
        cfg["num_experts_per_tok"]
    h = cfg["num_attention_heads"]
    dh = cfg.get("head_dim") or d // h
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    f32 = jnp.float32

    def held(x):
        return x.astype(jnp.bfloat16).astype(f32)

    # As models/lfm2_moe.py:init_params draws a sparse layer's router.
    lp = {"w_router": held(jax.random.normal(ks[0], (d, e), f32) * d ** -0.5),
          "router_bias": 0.05 * jax.random.normal(ks[1], (e,), f32)}
    x = jax.random.normal(ks[2], (ALONE_TOKENS, d), f32).astype(jnp.bfloat16)
    idx, w = jax.jit(moe.route, static_argnums=(3, 4, 5, 6))(
        x, lp["w_router"], lp["router_bias"], k,
        float(cfg["routed_scaling_factor"]), cfg["norm_topk_prob"],
        lfm2_moe.ROUTE_EPS)

    def ref_route(wrong):
        with jax.default_matmul_precision("highest"):
            chosen, dense = jax.jit(ref.route, static_argnums=(0, 3))(
                _hashable(cfg), lp, x.astype(f32), wrong)
        return np.asarray(chosen), np.asarray(dense)

    def routed(want_idx, want_dense):
        ours = np.sort(np.asarray(idx), axis=-1)
        same = np.all(ours == np.sort(want_idx, axis=-1), axis=-1)
        got = np.take_along_axis(want_dense, np.asarray(idx), axis=1)
        share = float(1.0 - same.mean())
        diff = float(np.max(np.abs(got - np.asarray(w))[same])) \
            if same.any() else None
        return {"share_differ": share, "max_weight_diff": diff,
                "within": share <= ROUTER_TOL
                and diff is not None and diff <= ROUTER_WEIGHT_TOL}

    # A head's 64 lanes before the norm: a projection's output at half of
    # fan-in scale (init_params), positions up to the envelope's longest.
    q = held(0.5 * jax.random.normal(ks[3], (1, ALONE_TOKENS, h, dh), f32))
    wn = held(jax.random.uniform(ks[4], (dh,), f32, 0.5, 1.5))
    pos = jax.random.randint(ks[5], (1, ALONE_TOKENS), 0, 3072)
    cos, sin = _rope_cos_sin(pos, dh, float(cfg["rope_theta"]))
    got = jax.jit(lfm2_moe.head_norm_rope, static_argnums=(2,))(
        q, wn, cfg["norm_eps"], cos, sin)[0]

    def ref_norm(low):
        xs = q[0]
        if low:
            b = ref._bf16
            xs = b(b(xs * b(jax.lax.rsqrt(b(jnp.mean(
                b(xs * xs), -1, keepdims=True)) + cfg["norm_eps"]))) * wn)
        else:
            xs = ref.rms_norm(xs, wn, cfg["norm_eps"])
        c, s = cos[0][:, None, :], sin[0][:, None, :]
        a, b_ = jnp.split(xs, 2, axis=-1)
        return jnp.concatenate([a * c - b_ * s, b_ * c + a * s], -1)

    def normed(want):
        rel = float(jnp.linalg.norm((got - want).ravel())
                    / jnp.linalg.norm(want.ravel()))
        return {"rel": rel, "within": rel <= NORM_TOL}

    out = {"stage": "alone", "device": jax.devices()[0].device_kind,
           "tokens": ALONE_TOKENS, "experts": e, "top_k": k,
           "tolerance": {"router": ROUTER_TOL,
                         "router_weight": ROUTER_WEIGHT_TOL,
                         "qk_norm": NORM_TOL},
           "router": {name: routed(*ref_route(wrong)) for name, wrong in (
               ("shipped", ()), ("vs_router_bf16", ("router_bf16",)),
               ("vs_bias_in_weights", ("bias_in_weights",)))},
           "qk_norm": {"shipped": normed(ref_norm(False)),
                       "vs_qk_norm_bf16": normed(ref_norm(True))}}
    out["ok"] = all(part["shipped"]["within"] for part in (
        out["router"], out["qk_norm"])) \
        and not out["router"]["vs_router_bf16"]["within"] \
        and not out["router"]["vs_bias_in_weights"]["within"] \
        and not out["qk_norm"]["vs_qk_norm_bf16"]["within"]
    print(json.dumps(out), flush=True)
    return 0


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
    report, stats = engine.report(), engine.stats()
    # The same prompt twice: the second answer is the cold one's.
    cold, again = done[0], done[1]
    said["again_same_tokens"] = cold["output"] == again["output"]
    said["again_max_logprob_diff"] = max(
        abs(a[0] - b[0]) for a, b in zip(cold["logprobs"], again["logprobs"]))
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, "served.json"), "w") as f:
        json.dump({"seed": seed, "dtype": dtype, "requests": done,
                   "chunk": config.max_num_batched_tokens,
                   "device": report["device"],
                   "attn_impl": report["engine"]["attn_impl"],
                   "seconds": time.monotonic() - t0}, f)
    ok = said["again_same_tokens"] and said["prefix_served_tokens"] == 0 \
        and said["prefix_unserved_tokens"] > 0
    calls = max(1, stats["moe_layer_calls_total"])
    print(json.dumps({"stage": "engine", "requests": len(done),
                      "device": report["device"]["kind"],
                      "attn_impl": report["engine"]["attn_impl"],
                      **said, "ok": ok,
                      "distinct_outputs": len(
                          {tuple(r["output"]) for r in done}),
                      "decode_rows_per_step": round(
                          stats["decode_row_steps_total"]
                          / max(1, stats["decode_steps_total"]), 1),
                      "experts_touched_per_decode_call": round(
                          stats["moe_experts_touched_total"] / calls, 1),
                      "preemptions": stats["num_preemptions"],
                      "state_bytes": report["engine"]["state_bytes"],
                      "peak_bytes_in_use":
                          report["engine"]["peak_bytes_in_use"],
                      "seconds": round(time.monotonic() - t0, 1)}),
          flush=True)
    return 0 if ok else 1


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
    model = get_model(mc)
    # The same weights: the engine's init, the engine's seed and dtype.
    params = model.init_params(
        mc, jax.random.PRNGKey(served["seed"]), jnp.dtype(served["dtype"]))
    seqs = [r["prompt"] + r["output"][:-1] for r in served["requests"]]
    chunk = served["chunk"]
    nd = cfg.get("num_dense_layers", 0)
    sparse = cfg["num_hidden_layers"] - nd
    hcfg = _hashable(cfg)
    layer = jax.jit(ref.layer, static_argnums=(0, 1, 2, 5, 7))
    if wrongs == [("all",)]:
        wrongs = [(w,) for w in ref.WRONG]

    def own_choices():
        """The program's own choices: its forward of the same tokens, as
        served (bf16), without a cache, padded to a token bucket as a
        prefill chunk is (the padding reaches no expert)."""
        forward = jax.jit(model.forward, static_argnums=(1,),
                          static_argnames=("routing",))
        ours = []
        for tokens in seqs:
            t = len(tokens)
            padded = -(-t // 256) * 256
            *_, chosen = forward(
                params, mc,
                jnp.asarray([tokens + [0] * (padded - t)], jnp.int32),
                jnp.arange(padded, dtype=jnp.int32)[None],
                jnp.asarray([t], jnp.int32), routing=True)
            ours.append(np.asarray(chosen)[:, :t])
        return ours

    def compare(wrong, ours):
        xs = [ref.embed(params, jnp.asarray(s)) for s in seqs]
        differ, choices = np.zeros(sparse, int), np.zeros(sparse, int)
        for i in range(cfg["num_hidden_layers"]):
            op, ffn, lp = ref.layer_params(params, cfg, i)   # one, float32
            for n in range(len(seqs)):
                xs[n], theirs = layer(hcfg, op, ffn, lp, xs[n], wrong, None,
                                      chunk)
                if theirs is not None and ours is not None:
                    differ[i - nd] += int(np.sum(np.any(
                        np.sort(ours[n][i - nd], axis=-1)
                        != np.sort(np.asarray(theirs), axis=-1), axis=-1)))
                    choices[i - nd] += len(seqs[n])
            jax.block_until_ready(xs)
        stats = {"prefill": [], "decode": []}
        spread = []
        for req, x in zip(served["requests"], xs):
            m = len(req["prompt"])
            logits = ref.logits(params, cfg, x[m - 1:])
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
        if ours is not None:
            out["routing"] = {
                "choices": int(choices.sum()), "differ": int(differ.sum()),
                "share": float(differ.sum() / max(1, choices.sum())),
                "share_by_sparse_layer": [
                    round(float(a / max(1, b)), 4)
                    for a, b in zip(differ, choices)]}
        # A number that is not finite is not within anything.
        out["within"] = all(
            bool(np.isfinite(out[phase]["max"]))
            and out[phase]["mean"] <= TOL_MEAN
            and out[phase]["max"] <= TOL_MAX for phase in stats) and (
                ours is None or out["routing"]["share"] <= TOL_ROUTING)
        out["tolerance"] = {"mean": TOL_MEAN, "max": TOL_MAX,
                            "routing": TOL_ROUTING}
        print(json.dumps(out), flush=True)
        return out

    got = [compare(w, None if w else own_choices()) for w in wrongs]
    if len(got) > 1 or got[0]["wrong"]:
        must = [g for g in got if g["wrong"][0] not in NOT_TOLD_ON_CHIP]
        print(json.dumps({
            "stage": "reference", "wrong": "each",
            "within": any(g["within"] for g in must),
            "not_told_on_chip": {
                g["wrong"][0]: g["within"] for g in got
                if g["wrong"][0] in NOT_TOLD_ON_CHIP},
            "nearest": min(must or got,
                           key=lambda g: g["decode"]["mean"])["wrong"],
        }), flush=True)
    return 0


def main(argv=None) -> int:
    global HERE
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=20261002)
    ap.add_argument("--stage", choices=("alone", "engine", "reference"))
    ap.add_argument("--wrong", default="",
                    help="wrong models, comma-separated, one at a time; all")
    ap.add_argument("--dir", default=HERE,
                    help="config.json, deployment.json and reference.py")
    ap.add_argument("--lens", default="",
                    help="prompt lengths, comma-separated (a rehearsal)")
    ap.add_argument("--dtype", default="bfloat16",
                    help="float32 for a rehearsal on the CPU (its backend "
                         "has no bf16 x bf16 -> f32 grouped product)")
    args = ap.parse_args(argv)
    HERE = os.path.abspath(args.dir)
    lens = tuple(int(m) for m in args.lens.split(",") if m)
    if args.stage == "alone":
        return stage_alone(args.seed)
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
    for stage in (["--stage", "alone"], ["--stage", "engine"],
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
    alone, engine, right, wrong = lines
    ok = alone["ok"] and engine["ok"] and right["within"] \
        and not wrong["within"]
    print(json.dumps({
        "ok": ok, "alone_ok": alone["ok"], "engine_ok": engine["ok"],
        "right_path_within": right["within"],
        "every_wrong_model_fails": not wrong["within"],
        "not_told_on_chip": wrong.get("not_told_on_chip"),
        "nearest_wrong": wrong.get("nearest")}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
