#!/usr/bin/env python3
"""The served path against the plain reference at PUBLISHED widths, on the
chip. The harness has no place for a reference (a cell's ``correct`` is
token counts, a probe and no compile in the window), so this is the
builder's own run, once a PR that touches the family:

    chiprun --timeout 3400 -- python3 benchmarks/chip/configs/mimo-v2.5-ep16/check_reference.py

Children, one after the other (a chip belongs to one process); this parent
never imports JAX.

``--stage alone``: the two computations whose precision or statement a
whole run cannot tell, each alone on IDENTICAL inputs. *router*: 4096
tokens' router inputs (bf16, unit scale: what a sparse layer's norm hands
over) through ``ops/moe.py:route`` with a router drawn as ``init_params``
draws it (the published 4096 -> 256, top-8, bias 0.05 N(0, 1), no scaling,
1e-20 in the weights' sum) against ``reference.route`` (float32,
``highest``): the share of tokens whose top-8 SET differs, and the largest
difference of a weight where the sets agree. *ring*: one decode step of 16
rows at contexts of 128 to 9000 tokens through
``ops/attention.py:window_ring_attend`` (bf16 queries, keys and scaled
values at the published 64 / 8 heads of 192 / 128 lanes, the ring full,
sinks N(8, 2^2)) against the float32 softmax over the same 128 keys with the
sink as one more column. Verdicts by ROUTER_TOL, ROUTER_WEIGHT_TOL and
RING_TOL: the shipped code is within all; the reference with its router in
bf16 (``router_bf16``), the nearest precision below the one the
configuration states, is NOT, nor is the one that weighs by score + bias
(``bias_in_weights``), nor the ring's reference without the sink or with
the window one key short.

``--stage engine``: the engine in-process at ``deployment.json``'s flags,
``config.json``'s widths and share (experts 0-15 of 256, 19072 rows of the
vocabulary) and weights seeded by ``--seed``, 140 greedy tokens a request
(the first from the prefill, 139 decode steps: every slot of every ring is
written again in decode) through the normal scheduler, 2048-token prefill
rows and decode trains: first ONE cold prompt alone (2112 tokens: the
cell's shortest), then THE SAME prompt again (nothing keeps a ring after a
prefix, so its prefix is prefilled again: ``prefix_served_tokens`` must be
0 and the unserved counter must move), then the cell's own lengths AT ONCE:
4160, 6000 and 8256 tokens (the longest the traffic sends: five chunks), so
that rows of several sequences share decode steps at 2 k to 8 k keys in the
full layers and 128 in the window layers. What the served surface returns is
kept: every generated token's own log-probability and the 20 most likely
(``logprobs=20``).

``--stage reference``: ``reference.py`` (float32, ``highest``, a masked full
score matrix with the sink column a block of queries at a time, no cache,
no ring, the 16 HELD experts computed eight at a time and weighted by the
routing over all 256) over prompt + generated tokens of every request,
padded to ONE length, ONE layer's weights widened from bf16 to float32 at a
time, the tree itself kept on the host. The reference routes for ITSELF:
that reading is the verdict. Beside it the share of (token, sparse layer)
choices in which the program's own forward of the same tokens
(``forward(routing=True)``, bf16 as served, no cache; the requests under
OWN_CHOICES_MAX tokens) and the reference differ. ``--wrong a,b``: ONE
equation wrong at a time (``reference.WRONG``), each of which must NOT be
within; the whole script runs ON_CHIP_WRONG; ``--wrong all`` runs every one.
It reads ``served.json`` and needs no chip.

ROUTING IS DISCONTINUOUS (kanana-2-30b-a3b-d8's check_reference.py says it
at length): TOL_ROUTING bounds the share of choices that differ; TOL_MEAN /
TOL_MAX bound the log-probabilities' differences. The limits and the
readings they lie between are written beside them below.
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
PROMPT_LENS = (2112, 4160, 6000, 8256)
OUTPUT_TOKENS = 140
# Requests up to this many tokens also run the program's own uncached
# forward for its choices (a longer one's temporaries do not fit beside
# 12 GB of weights).
OWN_CHOICES_MAX = 4400
TOP = 20
ALONE_TOKENS = 4096
RING_ROWS = 16
# The readings these limits lie between are my chip runs', PR 52 (seed
# 20261003, one TPU v5 lite; PERF.md section 6 has every one). ROUTER_TOL:
# the shipped router agrees with the float32 reference on the top-8 SET of
# every one of 4096 tokens (largest weight difference 0.0); the reference
# with its router in bf16 differs on 8.3% of them (top-8 of 256 has more
# near-ties than trinity's top-8 of 128: 6.2% there). ROUTER_WEIGHT_TOL
# bounds the largest difference of a weight where the sets agree (weights
# are of size 1 / 8): the shipped router's reads 0.0, the bf16 router's
# 4.5e-4, ``bias_in_weights`` 0.021.
ROUTER_TOL = 2e-3
ROUTER_WEIGHT_TOL = 1e-4
# The ring's decode statement on bf16 operands against the float32 softmax
# with the sink column, relative to the reference's norm: the shipped
# statement reads 3.7e-3 (it rounds its probabilities to bf16 for the value
# product, as every attention path here does); the sink left out 0.243, one
# key of 128 dropped 0.113.
RING_TOL = 1e-2
# The engine multiplies bf16 weights by bf16 activations with float32
# accumulation through 12 layers and rounds the residual stream to bf16
# after each, where the reference keeps float32; a share of tokens chooses
# another expert at a near-tie (and only one choice in sixteen lands on a
# held expert, so a swapped choice mostly moves nothing here). Readings of
# the shipped path (five requests of 2112 (cold, then the same again,
# prefilled again: the same tokens, log-probabilities equal to the last
# bit), 4160, 6000 and 8256 prompt tokens, 140 answered tokens each, logit
# spread 1.0): mean 0.0059 (prefill) and 0.0061 (decode), largest 0.0215 /
# 0.036 of 14,700 numbers, every request alike (means 0.0060-0.0062); 5.2%
# of 91,432 choices differ (3.4% in the first sparse layer, 7.1% in the
# eleventh). The nearest wrong models: the sink left out (``no_sink``) mean
# 0.0222 / 0.0248, largest 0.085 / 0.129; the values unscaled 0.0426 /
# 0.0495, largest 0.147 / 0.229. TOL_MEAN and TOL_MAX lie between the
# shipped path's readings and ``no_sink``'s: 2.0 times over the shipped
# mean and 1.9 times under the wrong one, 1.7 times over the shipped
# maximum and 1.4 (prefill) to 2.1 (decode) times under. The window one key
# short or long moves ONE key of 128 in nine layers: mean 0.0097-0.0110,
# largest 0.069 / 0.063: their means are inside these limits and their
# maxima a hair over TOL_MAX, so a whole run in bf16 is not REQUIRED to tell
# them (it did, thinly); the ``alone`` stage does (0.113 against 3.7e-3) and
# tests/test_mimo_v2.py, float32 on both sides. TOL_ROUTING: twice the
# shipped share; no wrong model is judged by it.
TOL_MEAN = 0.012
TOL_MAX = 0.06
TOL_ROUTING = 0.105
# Wrong models a whole run need not show NOT within: those the mean cannot
# tell on the chip in bf16 (the ``alone`` stage or tests/test_mimo_v2.py
# tells them).
NOT_TOLD_ON_CHIP = ("bias_in_weights", "window_one_less", "window_one_more",
                    "rope_32_lanes", "all_experts_here")
# What the whole script runs wrong on the chip: ISSUE 52's list less the
# bf16 router, which the ``alone`` stage tells.
ON_CHIP_WRONG = "no_sink,values_unscaled,window_one_less,window_one_more"
OUT_DIR = os.path.join(ROOT, "chiprun_out", "check_reference_mimo")


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
    from production_stack_tpu.ops import moe
    from production_stack_tpu.ops.attention import window_ring_attend

    cfg = load("config.json")
    d, k = cfg["hidden_size"], cfg["num_experts_per_tok"]
    e = cfg["n_routed_experts"] * cfg.get("ep_size", 1)
    h, hkv = cfg["num_attention_heads"], cfg["swa_num_key_value_heads"]
    dk, dv, w = cfg["head_dim"], cfg["v_head_dim"], cfg["sliding_window"]
    ks = jax.random.split(jax.random.PRNGKey(seed), 9)
    f32 = jnp.float32

    def held(x):
        return x.astype(jnp.bfloat16).astype(f32)

    # As models/mimo_v2.py:init_params draws a sparse layer's router.
    lp = {"w_router": held(jax.random.normal(ks[0], (d, e), f32) * d ** -0.5),
          "router_bias": 0.05 * jax.random.normal(ks[1], (e,), f32)}
    x = jax.random.normal(ks[2], (ALONE_TOKENS, d), f32).astype(jnp.bfloat16)
    idx, wts = jax.jit(moe.route, static_argnums=(3, 4, 5, 6))(
        x, lp["w_router"], lp["router_bias"], k,
        float(cfg.get("routed_scaling_factor") or 1.0),
        cfg["norm_topk_prob"], ref.ROUTE_EPS)

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
        diff = float(np.max(np.abs(got - np.asarray(wts))[same])) \
            if same.any() else None
        return {"share_differ": share, "max_weight_diff": diff,
                "within": share <= ROUTER_TOL
                and diff is not None and diff <= ROUTER_WEIGHT_TOL}

    # One decode step over full rings: scores of spread about 4 (q and k
    # at twice unit scale), values scaled, sinks as init_params draws them.
    b = RING_ROWS
    q = (2.0 * jax.random.normal(ks[3], (b, 1, h, dk), f32)).astype(
        jnp.bfloat16)
    ring_k = (2.0 * jax.random.normal(ks[4], (b, hkv, w, dk), f32)).astype(
        jnp.bfloat16)
    ring_v = jax.random.normal(ks[5], (b, hkv, w, dv), f32).astype(
        jnp.bfloat16)
    k_new = (2.0 * jax.random.normal(ks[6], (b, 1, hkv, dk), f32)).astype(
        jnp.bfloat16)
    v_new = jax.random.normal(ks[7], (b, 1, hkv, dv), f32).astype(
        jnp.bfloat16)
    sink = 8.0 + 2.0 * jax.random.normal(ks[8], (h,), f32)
    pos = jnp.linspace(w, 9000, b).astype(jnp.int32)[:, None]
    got = jax.jit(window_ring_attend, static_argnames=("scale",))(
        q, k_new, v_new, pos, jnp.ones((b,), jnp.int32), ring_k, ring_v,
        scale=dk ** -0.5, sink=sink)[:, 0].astype(f32)

    def ref_ring(wrong):
        # Slot s holds the newest position below ``pos`` that is s mod w:
        # all but the slot of pos - w are inside the window.
        slot = jnp.arange(w)[None, :]
        dist = jnp.mod(pos - 1 - slot, w) + 1               # [B, W], 1..w
        bound = w - ("window_one_less" in wrong)
        seen = jnp.concatenate(
            [dist < bound, jnp.ones((b, 1), bool)], axis=1)  # + itself
        keys = jnp.concatenate(
            [ring_k, k_new.transpose(0, 2, 1, 3)], 2).astype(f32)
        vals = jnp.concatenate(
            [ring_v, v_new.transpose(0, 2, 1, 3)], 2).astype(f32)
        qf = q[:, 0].astype(f32).reshape(b, hkv, h // hkv, dk)
        with jax.default_matmul_precision("highest"):
            s = jnp.einsum("bhgd,bhkd->bhgk", qf, keys) * dk ** -0.5
            s = jnp.where(seen[:, None, None, :], s, -jnp.inf)
            if "no_sink" not in wrong:
                s = jnp.concatenate([s, jnp.broadcast_to(
                    sink.reshape(1, hkv, h // hkv, 1), s.shape[:3] + (1,))],
                    -1)
                vals = jnp.concatenate(
                    [vals, jnp.zeros_like(vals[:, :, :1])], 2)
            out = jnp.einsum("bhgk,bhkd->bhgd", jax.nn.softmax(s, -1), vals)
        return out.reshape(b, h, dv)

    def ring(want):
        rel = float(jnp.linalg.norm((got - want).ravel())
                    / jnp.linalg.norm(want.ravel()))
        return {"rel": rel, "within": rel <= RING_TOL}

    out = {"stage": "alone", "device": jax.devices()[0].device_kind,
           "tokens": ALONE_TOKENS, "experts": e, "top_k": k,
           "tolerance": {"router": ROUTER_TOL,
                         "router_weight": ROUTER_WEIGHT_TOL,
                         "ring": RING_TOL},
           "router": {name: routed(*ref_route(wrong)) for name, wrong in (
               ("shipped", ()), ("vs_router_bf16", ("router_bf16",)),
               ("vs_bias_in_weights", ("bias_in_weights",)))},
           "ring": {name: ring(ref_ring(wrong)) for name, wrong in (
               ("shipped", ()), ("vs_no_sink", ("no_sink",)),
               ("vs_window_one_less", ("window_one_less",)))}}
    out["ok"] = all(part["shipped"]["within"] for part in (
        out["router"], out["ring"])) \
        and not out["router"]["vs_router_bf16"]["within"] \
        and not out["router"]["vs_bias_in_weights"]["within"] \
        and not out["ring"]["vs_no_sink"]["within"] \
        and not out["ring"]["vs_window_one_less"]["within"]
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
        **({"num_decode_steps": int(flags["--num-decode-steps"])}
           if "--num-decode-steps" in flags else {}),
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
            hits = bm.prefix_hits_total
            unserved = engine.stats()["prefix_hit_tokens_unserved_total"]
            again = await one(todo[0])
            said["prefix_served_tokens"] = bm.prefix_hits_total - hits
            said["prefix_unserved_tokens"] = engine.stats()[
                "prefix_hit_tokens_unserved_total"] - unserved
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
    # Nothing keeps a ring after a prefix: the second send is prefilled
    # again, whole, through the same programs, and answers the same tokens.
    ok = said["prefix_served_tokens"] == 0 \
        and said["prefix_unserved_tokens"] > 0 and said["again_same_tokens"]
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
                      "window_layers": report["engine"]["window_layers"],
                      "experts_held": report["engine"]["experts_held"],
                      "ring_keys_held_pct": round(
                          100.0 * stats["ring_keys_held_total"]
                          / max(1, stats["ring_keys_context_total"]), 2),
                      "pairs_elsewhere_share": round(
                          stats["moe_assignments_elsewhere_total"] / max(
                              1, stats["moe_assignments_elsewhere_total"]
                              + stats["moe_assignments_total"]), 4),
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
    # ONE length for every request (zeros behind; causal): one program a
    # kind of layer.
    width = -(-max(len(s) for s in seqs) // ref.QUERY_BLOCK) \
        * ref.QUERY_BLOCK
    sparse_of = np.cumsum(cfg["moe_layer_freq"]) - 1   # a layer's place
    sparse = int(sum(cfg["moe_layer_freq"]))           # among the sparse
    hcfg = _hashable(cfg)
    layer = jax.jit(ref.layer, static_argnums=(0, 1, 2, 5))
    if wrongs == [("all",)]:
        wrongs = [(w,) for w in ref.WRONG]

    def own_choices():
        """The program's own choices: its forward of the same tokens, as
        served (bf16), without a cache, padded to a token bucket as a
        prefill chunk is (the padding reaches no expert); None for a
        request too long for its temporaries."""
        forward = jax.jit(model.forward, static_argnums=(1,),
                          static_argnames=("routing",))
        ours = []
        for tokens in seqs:
            t = len(tokens)
            if t > OWN_CHOICES_MAX:
                ours.append(None)
                continue
            padded = -(-t // 256) * 256
            *_, chosen = forward(
                params, mc,
                jnp.asarray([tokens + [0] * (padded - t)], jnp.int32),
                jnp.arange(padded, dtype=jnp.int32)[None],
                jnp.asarray([t], jnp.int32), routing=True)
            ours.append(np.asarray(chosen)[:, :t])
        return ours

    ours = own_choices() if () in wrongs else None
    # The tree goes to the host: a layer at a time comes back in float32.
    host = jax.tree.map(np.asarray, params)
    del params

    def compare(wrong, ours):
        xs = [ref.embed(host, cfg, jnp.asarray(s + [0] * (width - len(s))))
              for s in seqs]
        differ, choices = np.zeros(sparse, int), np.zeros(sparse, int)
        for i in range(cfg["num_hidden_layers"]):
            window, ffn, lp = ref.layer_params(host, cfg, i)  # one, float32
            at = int(sparse_of[i])
            for n in range(len(seqs)):
                xs[n], theirs = layer(hcfg, window, ffn, lp, xs[n], wrong)
                if theirs is not None and ours is not None \
                        and ours[n] is not None:
                    t = len(seqs[n])
                    differ[at] += int(np.sum(np.any(
                        np.sort(ours[n][at], axis=-1)
                        != np.sort(np.asarray(theirs)[:t], axis=-1),
                        axis=-1)))
                    choices[at] += t
            jax.block_until_ready(xs)
            del lp
        stats = {"prefill": [], "decode": []}
        by_request, spread = [], []
        for req, x in zip(served["requests"], xs):
            m = len(req["prompt"])
            logits = ref.logits(host, cfg, x[m - 1:m - 1 + len(req["output"])])
            spread.append(float(jnp.std(logits)))
            logp = np.asarray(jax.nn.log_softmax(logits, axis=-1))
            mine = []
            for j, (chosen, top) in enumerate(req["logprobs"]):
                phase = "prefill" if j == 0 else "decode"
                diffs = [abs(chosen - logp[j][req["output"][j]])]
                diffs += [abs(q - logp[j][tok]) for tok, q in top]
                stats[phase] += diffs
                mine += diffs
            by_request.append({"prompt": m, "mean": float(np.mean(mine)),
                               "max": float(np.max(mine))})
        out = {"stage": "reference", "wrong": list(wrong),
               "logit_spread": float(np.mean(spread)),
               "by_request": by_request,
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

    got = [compare(w, None if w else ours) for w in wrongs]
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
    ap.add_argument("--seed", type=int, default=20261003)
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
                  ["--stage", "reference", "--wrong",
                   args.wrong or ON_CHIP_WRONG]):
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
