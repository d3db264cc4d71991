#!/usr/bin/env python3
"""The served path against the plain reference at PUBLISHED widths, on the
chip. The harness has no place for a reference (a cell's ``correct`` is
token counts, a probe and no compile in the window), so this is the
builder's own run, once a PR that touches the family:

    chiprun --timeout 3400 -- python3 benchmarks/chip/configs/dots3-note-prev-ep16/check_reference.py

Children, one after the other (a chip belongs to one process); this parent
never imports JAX.

``--stage alone``: the two computations whose precision or statement a
whole run cannot tell, each alone on IDENTICAL inputs. *index*: one decode
step of 16 rows at contexts of 2 k to 17 k tokens through
``ops/attention.py:index_scores`` and ``lax.top_k`` (bf16 queries and keys
at the published 64 heads x 128 lanes, float32 SIGNED head weights) against
the float32 statement: the share of rows whose selected SET of 2048 differs
and by how many keys (operands drawn at random: the share of the selected
keys among the newest 2048 is then the uniform 2048 / L, reported beside).
*ring*: one decode step of 16 rows at the same contexts through
``ops/attention.py:window_ring_attend`` over LATENT rows (bf16, 64 heads
against one ring of 513 rows of 1088 lanes stored in 1152, values the first
1024) against the float32 softmax over the same 513 rows. Verdicts by
INDEX_TOL and RING_TOL: the shipped code is within both; the statement
with its index scores in bf16 (``index_bf16``), the nearest precision
below the one the configuration states, is NOT, nor is the one without the
ReLU, nor the ring's reference with the window one key short.

``--stage engine``: the engine in-process at ``deployment.json``'s flags,
``config.json``'s widths and share (experts 0-15 of 256, 19008 rows of the
vocabulary) and weights seeded by ``--seed``, 140 greedy tokens a request
(the first from the prefill, 139 decode steps) through the normal
scheduler, 2048-token prefill rows and decode trains of 16: first ONE cold
prompt alone (4160 tokens: the cell's shortest), then THE SAME prompt again
(nothing keeps a ring after a prefix, so it is prefilled again:
``prefix_served_tokens`` must be 0 and the unserved counter must move),
then the cell's own lengths AT ONCE: 4160, 8256 and 16448 tokens (the
longest the traffic sends: nine chunks), so that rows of several sequences
share decode steps at 4 k to 16 k keys in the full layers, of which each
reads 2048, and 513 in the sliding layers. What the served surface returns
is kept: every generated token's own log-probability and the 20 most likely
(``logprobs=20``); beside them the program's own counters (keys visible and
selected: the read set).

``--stage reference``: ``reference.py`` (float32, ``highest``, expanded
keys and values, a masked full score matrix a block of queries at a time,
no cache, no ring, the 16 HELD experts computed eight at a time and weighted
by the routing over all 256) over prompt + generated tokens of every
request, a request at its own length (padded to whole query blocks), ONE
layer's weights widened from bf16 to float32 at a time, the tree itself kept
on the host. The reference routes and SELECTS for itself: that reading is
the verdict. Beside it, for the requests under OWN_CHOICES_MAX tokens, the
share of (token, sparse layer) choices and of (token, full layer)
SELECTIONS in which the program's own forward of the same tokens
(``forward(routing=True)``, bf16 as served, no cache) and the reference
differ, and by how many keys a differing selection differs. ``--wrong
a,b``: ONE equation wrong at a time (``reference.WRONG``), each of which
must NOT be within; the whole script runs ON_CHIP_WRONG; ``--wrong all``
runs every one. It reads ``served.json`` and needs no chip.

SELECTION IS DISCONTINUOUS, LIKE ROUTING (kanana-2-30b-a3b-d8's
check_reference.py says it at length): TOL_ROUTING and TOL_SELECTION bound
the shares of choices that differ; TOL_MEAN / TOL_MAX bound the
log-probabilities' differences. The limits and the readings they lie
between are written beside them below.
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
PROMPT_LENS = (4160, 4160, 8256, 16448)
OUTPUT_TOKENS = int(os.environ.get("CHECK_OUTPUT_TOKENS", 140))
# Requests up to this many tokens also run the program's own uncached
# forward for its choices and selections (a longer one's temporaries do not
# fit beside 10 GB of weights).
OWN_CHOICES_MAX = 4400
TOP = 20
ALONE_ROWS = 16
# The readings these limits lie between are my chip runs', PR 58 (seed
# 20261005, one TPU v5 lite; PERF.md section 6 has every one). INDEX_TOL:
# the share of 16 rows (contexts 2304 to 17408) whose selected set of 2048
# differs from the float32 statement's by more than INDEX_KEYS keys. bf16
# operands multiply exactly into float32, so the shipped statement differs
# in the ORDER of a float32 sum only: every row's set equal, 0 keys; with
# the scores rounded to bf16 (``index_bf16``) 69% of the rows differ by more
# (3.8 keys a row, 10 at most); without the ReLU every row, by 560.
INDEX_KEYS = 2
INDEX_TOL = 0.2
# The ring's decode statement over latent rows on bf16 operands against the
# float32 softmax, relative to the reference's norm: shipped 2.2e-3 (it
# rounds its probabilities to bf16 for the value product, as every attention
# path here does); one key of 513 dropped 0.042.
RING_TOL = 1e-2
# The engine multiplies bf16 weights by bf16 activations with float32
# accumulation through 10 layers and rounds the residual stream to bf16
# after each, where the reference keeps float32; a share of tokens chooses
# another expert at a near-tie, and a query's 2048th key ties with its
# 2049th far more often than an 8th expert with a 9th. Readings of the
# shipped path (4160 (cold; the same again, prefilled again, log-
# probabilities equal to the last bit; and once more beside the others),
# 8256 and 16448 prompt tokens, 140 answered tokens each, logit spread 1.0):
# mean 0.0174 (prefill) and 0.0180 (decode), largest 0.115 / 0.167 of 11,760
# numbers, rising with the context (request means 0.0147, 0.0151, 0.0191,
# 0.0233); 7.7% of 77,382 expert choices differ (4.8% in the first sparse
# layer, 11.1% in the ninth); 50.3% of 34,392 (token, full layer)
# selections differ by at least one key, by 6.5 keys of 2048 where they do
# (59 at most): 0.21% of the selected keys. The nearest wrong models: no
# ReLU (``no_relu``) mean 0.135 / 0.128, largest 0.39 / 0.69; half the
# top-k 0.167 / 0.146, largest 0.52 / 0.75; no indexer 0.181 / 0.182; an
# elementwise gate 0.172 / 0.185; no rescale 0.267 / 0.278; no gate 0.404 /
# 0.373. TOL_MEAN and TOL_MAX lie between the shipped path's readings and
# ``no_relu``'s: 2.8 times over the shipped mean and 2.6 times under the
# wrong one, 1.6 times over the shipped maximum and 1.5 times under the
# wrong one's smaller. The window one key short moves ONE key of 513 in six
# layers: mean 0.0223 / 0.0186, largest 0.10 / 0.17: inside these limits,
# so a whole run in bf16 does not tell it; the ``alone`` stage does (0.042
# against 2.2e-3) and tests/test_dots3.py, float32 on both sides.
# TOL_ROUTING and TOL_SELECTION (the share of the selected KEYS that
# differ): twice and 2.4 times the shipped shares; no wrong model is
# judged by them. (Those readings were taken with prefill rectangles of
# several rows; at the deployment's row cap of 1 the script's last run on
# the tree handed in read 0.0203 / 0.0177, 0.129 / 0.174, 7.7%, 0.21%.)
TOL_MEAN = 0.05
TOL_MAX = 0.26
TOL_ROUTING = 0.15
TOL_SELECTION = 0.005
# Wrong models a whole run need not show NOT within: those the mean cannot
# tell on the chip in bf16 (the ``alone`` stage or tests/test_dots3.py tells
# them).
NOT_TOLD_ON_CHIP = ("bias_in_weights", "window_one_less", "window_one_more",
                    "all_experts_here", "layernorm_no_bias")
# What the whole script runs wrong on the chip: ISSUE 58's list less the
# two precisions, which the ``alone`` stage tells.
ON_CHIP_WRONG = ("no_indexer,topk_half,no_relu,window_one_less,no_gate,"
                 "elementwise_gate,no_rescale")
OUT_DIR = os.path.join(ROOT, "chiprun_out", "check_reference_dots3")


def load(name):
    with open(os.path.join(HERE, name)) as f:
        return json.load(f)


def prompts(seed: int, vocab: int, lens):
    import numpy as np

    rng = np.random.default_rng(seed)
    # Byte-tokenizer range, as the benchmark's traffic: ids 3..258. The
    # first two lengths are ONE prompt (cold, then again).
    out = [[int(t) for t in rng.integers(3, min(vocab, 259), n)]
           for n in lens]
    if len(lens) > 1 and lens[0] == lens[1]:
        out[1] = out[0]
    return out


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

    from production_stack_tpu.ops.attention import (
        index_scores,
        window_ring_attend,
    )

    cfg = load("config.json")
    hi, di, topk = cfg["index_n_heads"], cfg["index_head_dim"], \
        cfg["index_topk"]
    h, rank, dr = cfg["swa_num_attention_heads"], cfg["swa_kv_lora_rank"], \
        cfg["swa_qk_rope_head_dim"]
    w = cfg["sliding_window_size"]
    b = ALONE_ROWS
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    f32, bf16 = jnp.float32, jnp.bfloat16
    topk = min(topk, 2048)
    longest = max(2 * topk + 1024, 17408 if topk == 2048 else 4 * topk)
    lens = np.linspace(topk + topk // 8, longest, b).astype(np.int32)

    # The indexer's operands at the scales the model makes them: queries of
    # the rescaled latent through a fan-in matrix (entries of size 2.2),
    # keys after a LayerNorm (unit), SIGNED head weights.
    q = (2.2 * jax.random.normal(ks[0], (b, 1, hi, di), f32)).astype(bf16)
    k = jax.random.normal(ks[1], (b, longest, di), f32).astype(bf16)
    wts = jax.random.normal(ks[2], (b, 1, hi), f32) * (hi * di) ** -0.5
    seen = jnp.arange(longest)[None, :] < jnp.asarray(lens)[:, None]

    def top(scores):
        return jax.lax.top_k(jnp.where(seen, scores, -jnp.inf), topk)[1]

    ours = np.asarray(jax.jit(
        lambda: top(index_scores(q, wts, k)[:, 0]))())

    def statement(wrong):
        low = "index_bf16" in wrong

        def r(x):
            return jax.lax.reduce_precision(x, 8, 7) if low else x

        with jax.default_matmul_precision("highest"):
            s = r(jnp.einsum("bhd,bkd->bhk", q[:, 0].astype(f32),
                             k.astype(f32)))
            if "no_relu" not in wrong:
                s = jax.nn.relu(s)
            return top(r(jnp.einsum("bh,bhk->bk", r(wts[:, 0]), s)))

    def sets(want):
        want = np.asarray(want)
        apart = np.array([len(set(a) - set(c)) for a, c in zip(ours, want)])
        share = float(np.mean(apart > INDEX_KEYS))
        return {"rows_differ_share": share, "keys_differ_max": int(
            apart.max()), "keys_differ_mean": float(apart.mean()),
            "within": share <= INDEX_TOL}

    newest = float(np.mean([np.mean(row >= n - topk)
                            for row, n in zip(ours, lens)]))
    uniform = float(np.mean(topk / lens))

    # One decode step over full rings of latent rows.
    lanes = rank + dr
    stored = -(-lanes // 128) * 128
    qr = (jax.random.normal(ks[3], (b, 1, h, lanes), f32) * 0.5).astype(bf16)
    ring = jnp.pad(jax.random.normal(ks[4], (b, 1, w, lanes), f32),
                   ((0, 0),) * 3 + ((0, stored - lanes),)).astype(bf16)
    new = jax.random.normal(ks[5], (b, 1, 1, lanes), f32).astype(bf16)
    pos = jnp.asarray(lens)[:, None]
    scale = float(cfg["swa_qk_nope_head_dim"] + dr) ** -0.5
    got = jax.jit(window_ring_attend, static_argnames=(
        "scale", "value_dim"))(
        qr, new, None, pos, jnp.ones((b,), jnp.int32), ring, scale=scale,
        value_dim=rank)[:, 0].astype(f32)

    def ref_ring(wrong):
        slot = jnp.arange(w)[None, :]
        dist = jnp.mod(pos - 1 - slot, w) + 1               # [B, W], 1..w
        bound = w - ("window_one_less" in wrong)
        vis = jnp.concatenate([dist < bound, jnp.ones((b, 1), bool)], 1)
        keys = jnp.concatenate([ring[:, 0, :, :lanes], new[:, 0]],
                               1).astype(f32)
        with jax.default_matmul_precision("highest"):
            s = jnp.einsum("bhd,bkd->bhk", qr[:, 0].astype(f32), keys) \
                * scale
            s = jnp.where(vis[:, None, :], s, -jnp.inf)
            return jnp.einsum("bhk,bkd->bhd", jax.nn.softmax(s, -1),
                              keys[..., :rank])

    def ringed(want):
        rel = float(jnp.linalg.norm((got - want).ravel())
                    / jnp.linalg.norm(want.ravel()))
        return {"rel": rel, "within": rel <= RING_TOL}

    out = {"stage": "alone", "device": jax.devices()[0].device_kind,
           "rows": b, "contexts": [int(lens[0]), int(lens[-1])],
           "tolerance": {"index_rows": INDEX_TOL, "index_keys": INDEX_KEYS,
                         "ring": RING_TOL},
           "selected_among_newest_share": newest,
           "uniform_would_be": uniform,
           "index": {name: sets(statement(wrong)) for name, wrong in (
               ("shipped", ()), ("vs_index_bf16", ("index_bf16",)),
               ("vs_no_relu", ("no_relu",)))},
           "ring": {name: ringed(ref_ring(wrong)) for name, wrong in (
               ("shipped", ()),
               ("vs_window_one_less", ("window_one_less",)))}}
    out["ok"] = out["index"]["shipped"]["within"] \
        and out["ring"]["shipped"]["within"] \
        and not out["index"]["vs_index_bf16"]["within"] \
        and not out["index"]["vs_no_relu"]["within"] \
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
        **({"max_prefill_seqs": int(flags["--max-prefill-seqs"])}
           if "--max-prefill-seqs" in flags else {}),
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
            again = await one(todo[1])
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
                      "keys_read_pct": round(
                          100.0 * stats["index_keys_selected_total"]
                          / max(1, stats["index_keys_visible_total"]), 2),
                      "prefill_keys_selected_pct": round(
                          100.0 * stats["index_prefill_keys_selected_total"]
                          / max(1, stats["index_prefill_keys_visible_total"]),
                          2),
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
    # The cold prompt's second send answered the same tokens: one of the
    # two is compared.
    requests = served["requests"][1:] if len(served["requests"]) > 1 \
        else served["requests"]
    seqs = [r["prompt"] + r["output"][:-1] for r in requests]

    def padded(n):
        return -(-n // ref.QUERY_BLOCK) * ref.QUERY_BLOCK

    nd = cfg.get("first_k_dense_replace", 0)
    sparse = cfg["num_hidden_layers"] - nd
    full_layers = [i for i, t in enumerate(cfg["layer_types"])
                   if t == ref.FULL]
    hcfg = _hashable(cfg)
    layer = jax.jit(ref.layer, static_argnums=(0, 1, 2, 5))
    if wrongs == [("all",)]:
        wrongs = [(w,) for w in ref.WRONG]

    def own_choices():
        """The program's own choices and selections: its forward of the
        same tokens, as served (bf16), without a cache, padded to a token
        bucket as a prefill chunk is (the padding reaches no expert and no
        query); None for a request too long for its temporaries."""
        forward = jax.jit(model.forward, static_argnums=(1,),
                          static_argnames=("routing",))
        ours = []
        for tokens in seqs:
            t = len(tokens)
            if t > OWN_CHOICES_MAX:
                ours.append(None)
                continue
            # Whole blocks of 512 queries: the ring's blocks at a window
            # of 513 (one block of everything is 5 GB of scores here).
            width = -(-t // 512) * 512
            *_, chosen, masks = forward(
                params, mc,
                jnp.asarray([tokens + [0] * (width - t)], jnp.int32),
                jnp.arange(width, dtype=jnp.int32)[None],
                jnp.asarray([t], jnp.int32), routing=True)
            ours.append((np.asarray(chosen)[:, :t],
                         np.asarray(masks)[:, 0, :t, :t]))
        return ours

    ours = own_choices() if () in wrongs else None
    # The tree goes to the host: a layer at a time comes back in float32.
    host = jax.tree.map(np.asarray, params)
    del params

    def select_layer(hcfg, window, ffn, lp, h, wrong):
        picked = []
        h, chosen = ref.layer(hcfg, window, ffn, lp, h, wrong, None, picked)
        return h, chosen, (picked[0] if picked else None)

    selecting = jax.jit(select_layer, static_argnums=(0, 1, 2, 5))

    def compare(wrong, ours):
        xs = [ref.embed(host, cfg, jnp.asarray(
            s + [0] * (padded(len(s)) - len(s)))) for s in seqs]
        differ, choices = np.zeros(sparse, int), np.zeros(sparse, int)
        sel_differ = np.zeros(len(full_layers), int)
        sel_rows = np.zeros(len(full_layers), int)
        sel_keys, drawn, sel_selected = [], [], 0
        for i in range(cfg["num_hidden_layers"]):
            window, ffn, lp = ref.layer_params(host, cfg, i)  # one, float32
            for n in range(len(seqs)):
                mine = ours[n] if ours is not None else None
                if mine is None:
                    xs[n], theirs = layer(hcfg, window, ffn, lp, xs[n],
                                          wrong)
                    continue
                t = len(seqs[n])
                xs[n], theirs, picked = selecting(
                    hcfg, window, ffn, lp, xs[n], wrong)
                if theirs is not None:
                    at = i - nd
                    differ[at] += int(np.sum(np.any(
                        np.sort(mine[0][at], axis=-1)
                        != np.sort(np.asarray(theirs)[:t], axis=-1),
                        axis=-1)))
                    choices[at] += t
                if picked is not None:
                    at = full_layers.index(i)
                    # The seeded draw: what the LAST query selected, by
                    # place (among the newest index_topk) and by content
                    # (distinct token ids among the selected keys over
                    # those among all it could see).
                    last = np.asarray(picked)[t - 1, :t]
                    ids = np.asarray(seqs[n])
                    drawn.append({
                        "keys": t, "selected": int(last.sum()),
                        "among_newest": round(float(
                            last[-cfg["index_topk"]:].sum()
                            / max(1, last.sum())), 3),
                        "token_ids_share": round(
                            len(set(ids[last])) / len(set(ids)), 3)})
                    apart = np.sum(
                        mine[1][at] & ~np.asarray(picked)[:t, :t], axis=-1)
                    sel_differ[at] += int(np.sum(apart > 0))
                    sel_rows[at] += t
                    sel_selected += int(mine[1][at].sum())
                    sel_keys += [int(a) for a in apart if a]
            jax.block_until_ready(xs)
            del lp
        stats = {"prefill": [], "decode": []}
        by_request, spread = [], []
        for req, x in zip(requests, xs):
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
        if ours is not None and choices.sum():
            out["routing"] = {
                "choices": int(choices.sum()), "differ": int(differ.sum()),
                "share": float(differ.sum() / max(1, choices.sum())),
                "share_by_sparse_layer": [
                    round(float(a / max(1, b)), 4)
                    for a, b in zip(differ, choices)]}
            out["selection"] = {
                "rows": int(sel_rows.sum()), "differ": int(sel_differ.sum()),
                "rows_share": float(sel_differ.sum()
                                    / max(1, sel_rows.sum())),
                # Of the keys selected (the indexer's top-k a row while the
                # context is longer), the share that differs.
                "share": float(sum(sel_keys) / max(1, sel_selected)),
                "share_by_full_layer": [
                    round(float(a / max(1, b)), 4)
                    for a, b in zip(sel_differ, sel_rows)],
                "keys_differ_mean": float(np.mean(sel_keys))
                if sel_keys else 0.0,
                "keys_differ_max": max(sel_keys, default=0),
                "of_keys_selected": min(cfg["index_topk"],
                                        max(len(s) for s in seqs)),
                "last_query_drew": drawn}
        judged = "routing" in out
        # A number that is not finite is not within anything.
        out["within"] = all(
            bool(np.isfinite(out[phase]["max"]))
            and out[phase]["mean"] <= TOL_MEAN
            and out[phase]["max"] <= TOL_MAX for phase in stats) and (
                not judged or (
                    out["routing"]["share"] <= TOL_ROUTING
                    and out["selection"]["share"] <= TOL_SELECTION))
        out["tolerance"] = {"mean": TOL_MEAN, "max": TOL_MAX,
                            "routing": TOL_ROUTING,
                            "selection": TOL_SELECTION}
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
    ap.add_argument("--seed", type=int, default=20261005)
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
