"""Sampling: request-level params + a batched, jitted TPU sampler.

All requests in a decode batch are sampled in ONE jitted call with per-row
temperature/top-k/top-p vectors — no per-request Python branching on device.

TPU discipline: a full-vocab argsort costs ~5 ms/step on a v5e (the sorted
take_along_axis gather runs at ~1.5 GB/s, profiled), so the sampler never
sorts on the common paths:
  * greedy rows use argmax;
  * unfiltered rows (no top-k/top-p) use the Gumbel-argmax trick over the
    full vocab — exact softmax sampling, sort-free;
  * filtered rows reduce the vocab to the top TOP_CANDIDATES logits via
    lax.top_k (O(V) per candidate, no full sort) and apply top-k/top-p masks
    among those candidates.
Path selection is PER ROW (jnp.where over the picks) so a request's tokens
never depend on co-batched requests. The filtered path truncates top-p to the
TOP_CANDIDATES most likely tokens; mass beyond rank 128 is vanishingly small
for real LLM logits (vLLM's TPU backend makes the same tradeoff).

What a dispatch COMPUTES is narrower than what its rows may select: a pick is
computed only when some row of the dispatch selects it (``sampler_paths``,
two scalars reduced from the sampling vectors the program already holds, and
a ``lax.cond`` on each). An all-greedy dispatch runs one argmax; the Gumbel
field [B, V] exists only when some row samples, the top-128 search only when
some sampled row filters (12% of a v5e's time at 20-32 rows x 151936, all
for values no row's ``where`` took: PERF.md, PR 28). The ``cond`` skips a
value nobody reads; it never replaces one row's pick by another path's — see
``sample_tokens``.
"""

from dataclasses import dataclass, field
from typing import List, Optional

import numpy as np

import jax
import jax.numpy as jnp

TOP_CANDIDATES = 128  # candidate pool for the filtered (top-k/top-p) branch


@dataclass
class SamplingParams:
    temperature: float = 1.0
    top_p: float = 1.0
    top_k: int = -1           # -1 = disabled
    max_tokens: int = 16
    min_tokens: int = 0
    stop: List[str] = field(default_factory=list)
    stop_token_ids: List[int] = field(default_factory=list)
    ignore_eos: bool = False
    seed: Optional[int] = None
    n: int = 1
    logprobs: Optional[int] = None
    presence_penalty: float = 0.0
    frequency_penalty: float = 0.0

    @staticmethod
    def from_request(body: dict, default_max_tokens: int = 16) -> "SamplingParams":
        """Build from an OpenAI completion/chat request body.

        Explicit JSON ``null`` means "use the default" (OpenAI clients send
        e.g. ``{"temperature": null}`` routinely), so every field falls back
        through None rather than coercing it.
        """

        def get(key, default):
            v = body.get(key)
            return default if v is None else v

        max_tokens = body.get("max_tokens")
        if max_tokens is None:
            max_tokens = body.get("max_completion_tokens")
        if max_tokens is None:
            max_tokens = default_max_tokens
        stop = get("stop", [])
        logprobs = body.get("logprobs")
        if logprobs is True:  # chat-style bool + top_logprobs
            logprobs = int(get("top_logprobs", 0))
        elif logprobs is False:  # chat-style explicit off
            logprobs = None
        elif logprobs is not None:
            logprobs = int(logprobs)
        return SamplingParams(
            temperature=float(get("temperature", 1.0)),
            top_p=float(get("top_p", 1.0)),
            top_k=int(get("top_k", -1)),
            max_tokens=int(max_tokens),
            stop=[stop] if isinstance(stop, str) else list(stop),
            ignore_eos=bool(get("ignore_eos", False)),
            seed=body.get("seed"),
            n=int(get("n", 1)),
            logprobs=logprobs,
            presence_penalty=float(get("presence_penalty", 0.0)),
            frequency_penalty=float(get("frequency_penalty", 0.0)),
        )


@jax.named_scope("sample")
def apply_penalties(
    logits: jax.Array,       # [B, V]
    counts: jax.Array,       # [B, V] int — output-token occurrence counts
    presence: jax.Array,     # [B]
    frequency: jax.Array,    # [B]
) -> jax.Array:
    """OpenAI presence/frequency penalties over OUTPUT tokens (vLLM
    semantics: prompt tokens are not penalized). Runs inside the jitted
    dispatch; the decode scan threads ``counts`` through its carry so
    mid-scan tokens are penalized too."""
    cnt = counts.astype(logits.dtype)
    return (
        logits
        - presence[:, None] * (cnt > 0).astype(logits.dtype)
        - frequency[:, None] * cnt
    )


def speculative_accept(
    proposals: jax.Array,    # [B, N] int32 — draft tokens for positions 1..N
    samples: jax.Array,      # [B, N+1] int32 — the target's own (seeded)
                             # samples at verify positions 0..N
    budget: jax.Array,       # [B] int32 — tokens the row may still emit
    gamma: Optional[jax.Array] = None,  # [B] int32 — per-row draft depth
                             # cap (adaptive control); None = all N
) -> tuple:
    """Deterministic accept/emit accounting for one draft/verify cycle
    (docs/PERF.md round 8). Proposal i is accepted iff it EQUALS the token
    the target itself would have sampled at that position (``samples[i]``,
    drawn with the accepted-gen-index seed schedule) AND every earlier
    proposal was accepted — so the emitted stream is token-identical to
    spec-off by construction: accepted proposals ARE the target's samples,
    and the first mismatch is corrected by the target's sample at that
    position (the "bonus" token, always emittable because verify scored
    position a's logits under a fully-accepted prefix).

    ``gamma`` (round 10 adaptive control) caps how many proposals a row
    may accept this cycle: proposals at index >= gamma[row] are treated as
    mismatches. A gamma-0 row therefore always emits exactly the target's
    own sample — depth control can never change WHAT is emitted, only how
    much speculation paid for it.

    Returns (emit [B], accepted [B]):
      * emit     — tokens the row emits this cycle: min(accepted + 1,
                   budget); the emitted tokens are samples[:emit].
                   0 when the row's budget is exhausted.
      * accepted — draft proposals that survived (before budget clipping);
                   the telemetry numerator (acceptance = accepted / N).
    """
    agree = proposals == samples[:, :-1]                         # [B, N]
    if gamma is not None:
        n = proposals.shape[1]
        agree = agree & (
            jnp.arange(n, dtype=jnp.int32)[None, :] < gamma[:, None]
        )
    agree = agree.astype(jnp.int32)
    accepted = jnp.cumprod(agree, axis=1).sum(axis=1)            # [B]
    emit = jnp.minimum(accepted + 1, jnp.maximum(budget, 0))
    return emit, accepted


def speculative_tree_accept(
    v_toks: jax.Array,       # [B, T] int32 — token at each tree node
                             # (node 0 = the row's current token t0)
    z: jax.Array,            # [B, T] int32 — the target's own (seeded)
                             # sample AT each node, conditioned on the
                             # node's ancestor path
    parents,                 # [T] int (numpy/static) — tree_structure()
    depths,                  # [T] int (numpy/static)
    budget: jax.Array,       # [B] int32 — tokens the row may still emit
    gamma: jax.Array,        # [B] int32 — per-row draft depth cap
) -> tuple:
    """Deterministic tree-accept walk (docs/PERF.md round 10; SpecInfer's
    tree verification with the round-8 determinism contract). The walk
    starts at the root and repeatedly emits the target's sample z[cur],
    then steps to the child whose DRAFT token equals that sample (sibling
    tokens are distinct by construction, so at most one child matches);
    no matching child ends the walk — the last emitted sample is the
    corrective "bonus" token. Every emitted token is therefore one of the
    target's own samples along an accepted prefix: token-identical to
    spec-off, exactly like the linear rule, but a first-position mismatch
    can still salvage one draft token when a sibling branch matches.

    ``parents``/``depths`` must be host-side (numpy) constants — the walk
    unrolls over the static tree depth. Children at depth > gamma[row] are
    never taken (adaptive depth control).

    Returns (emit [B], accepted [B], path_idx [B, N+1], main_len [B]):
      * emit     — tokens the row emits: min(walk length, budget); the
                   emitted tokens are z gathered along path_idx[:emit].
      * accepted — accepted draft tokens (walk length - 1, pre-clip) —
                   the same telemetry numerator as the linear rule.
      * path_idx — node index visited at each walk step (clamped to the
                   last visited node once the walk ends); gathering z/KV
                   along it restores the linear path's [B, N+1] shapes.
      * main_len — valid DRAFT-RING entries after this cycle: the draft
                   only wrote ring KV for the main chain [t0, p1..pN], so
                   a walk that diverged onto a sibling branch keeps only
                   the t0 entry (min'd with emit, like the linear rule).
    """
    b = v_toks.shape[0]
    n_max = int(np.max(depths))          # main-chain draft depth N
    par = jnp.asarray(np.asarray(parents, np.int32))
    dep = jnp.asarray(np.asarray(depths, np.int32))
    alive = budget > 0
    cur = jnp.zeros((b,), jnp.int32)
    emit_w = jnp.zeros((b,), jnp.int32)
    first_child = jnp.zeros((b,), jnp.int32)
    cols = []
    for d in range(n_max + 1):
        cols.append(cur)
        emit_w = emit_w + alive.astype(jnp.int32)
        if d == n_max:
            break                        # deepest nodes have no children
        zc = jnp.take_along_axis(z, cur[:, None], axis=1)[:, 0]
        match = (
            (par[None, :] == cur[:, None])
            & (v_toks == zc[:, None])
            & (dep[None, :] <= gamma[:, None])
            & alive[:, None]
        )
        has = jnp.any(match, axis=1)
        nxt = jnp.argmax(match, axis=1).astype(jnp.int32)
        if d == 0:
            first_child = jnp.where(has, nxt, 0)
        cur = jnp.where(has, nxt, cur)
        alive = alive & has
    path_idx = jnp.stack(cols, axis=1)                  # [B, N+1]
    accepted = jnp.maximum(emit_w - 1, 0)
    emit = jnp.minimum(emit_w, jnp.maximum(budget, 0))
    # Node 1 is the main chain's depth-1 node (ops/tree_mask.py layout);
    # sibling branches have no children, so leaving the main chain at the
    # first step is the only way off it.
    main_acc = jnp.where(first_child == 1, accepted, 0)
    main_len = jnp.minimum(main_acc + 1, emit)
    return emit, accepted, path_idx, main_len


def adaptive_gamma(alpha: float, n_max: int, threshold: float) -> int:
    """Draft-depth policy for the adaptive controller (host-side, pure):
    the largest g in [0, n_max] with alpha**g >= threshold — i.e. keep
    deepening while the whole drafted prefix still survives verification
    with probability at least ``threshold`` under the EMA acceptance
    estimate alpha. threshold > 1 pins gamma to 0 (the spec-off
    degradation configuration); alpha >= 1 saturates at n_max."""
    if threshold > 1.0:
        return 0
    if alpha >= 1.0:
        return n_max
    if alpha <= 0.0:
        return 0
    g = 0
    ev = 1.0
    while g < n_max and ev * alpha >= threshold:
        ev *= alpha
        g += 1
    return g


def _gumbel(seeds: jax.Array, shape) -> jax.Array:
    """Per-row Gumbel noise: row i uses PRNGKey(seeds[i])."""
    return jax.vmap(
        lambda s: jax.random.gumbel(jax.random.PRNGKey(s), shape[1:])
    )(seeds)


def sampler_paths(temperature, top_k, top_p) -> tuple:
    """(any_sampled, any_filtered): which of the sampler's picks SOME row of
    this dispatch selects, from its [B] sampling vectors (top_k <= 0 and
    top_p >= 1 are off). Scalars, replicated under any sharding. A greedy
    row's top_k/top_p never count (greedy wins, and a shape bucket's padding
    rows are all-zero: temperature 0 with top_p 0). The three vectors are
    constant over a dispatch, so a program that samples in a loop computes
    this once, outside it, and hands it to every ``sample_tokens`` call.
    Array methods only: the runner counts ``pstpu:sample_dispatches_*`` with
    this same function over the numpy vectors it packs."""
    sampled = temperature > 0.0
    return sampled.any(), (sampled & ((top_k > 0) | (top_p < 1.0))).any()


@jax.jit
def _filtered_pick(logits, temp, top_k, top_p, g):
    """The candidate branch's body: top-k/top-p among the TOP_CANDIDATES
    most likely tokens, with the shared Gumbel field ``g`` gathered at the
    candidate indices. ``logits / temp`` is formed here, not carried in: a
    [B, V] operand would be written out as a field of its own; here it
    fuses into the search as it did without the cond."""
    c = min(TOP_CANDIDATES, logits.shape[1])
    cand_logits, cand_idx = jax.lax.top_k(logits / temp, c)    # [B, C] desc
    probs = jax.nn.softmax(cand_logits, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    ranks = jnp.arange(c, dtype=jnp.int32)[None, :]
    k_eff = jnp.where(top_k[:, None] <= 0, c, top_k[:, None])
    keep = (ranks < k_eff) & ((cum - probs) < top_p[:, None])
    keep = keep.at[:, 0].set(True)
    masked = jnp.where(keep, cand_logits, -jnp.inf)
    g_cand = jnp.take_along_axis(g, cand_idx, axis=-1)         # [B, C]
    pick = jnp.argmax(masked + g_cand, axis=-1)
    return jnp.take_along_axis(cand_idx, pick[:, None], axis=-1)[:, 0]


@jax.jit
def _sampled_pick(logits, temperature, top_k, top_p, seeds, any_filtered):
    """The sampled branch's body: the Gumbel field, the exact full-vocab
    pick, and (only when some sampled row filters) the candidate pick."""
    temp = jnp.maximum(temperature, 1e-6)[:, None]
    g = _gumbel(seeds, logits.shape)
    # Exact softmax sampling without a sort: argmax(logits/T + Gumbel).
    unfiltered_pick = jnp.argmax(logits / temp + g, axis=-1)
    row_filtered = (top_k > 0) | (top_p < 1.0)
    return jnp.where(
        row_filtered,
        jax.lax.cond(any_filtered,
                     lambda: _filtered_pick(logits, temp, top_k, top_p, g),
                     lambda: unfiltered_pick),
        unfiltered_pick,
    )


@jax.jit
@jax.named_scope("sample")
def sample_tokens(
    logits: jax.Array,       # [B, V] float32
    temperature: jax.Array,  # [B]
    top_k: jax.Array,        # [B] int32 (-1 = off)
    top_p: jax.Array,        # [B]
    seeds: jax.Array,        # [B] uint32 per-row PRNG seeds
    paths: Optional[tuple] = None,  # sampler_paths(...) of the same vectors
) -> jax.Array:
    """Per-ROW path selection: a greedy row takes the argmax, a row with
    top_k/top_p the truncated candidate pick, an unfiltered row the exact
    full-vocab Gumbel pick. One shared Gumbel field [B, V] feeds both sampled
    picks (the candidate branch gathers its noise at the candidate indices),
    so a row's token depends only on its own (logits, params, seed) — never
    on which rows it was batched with (the per-sequence determinism contract
    of runner._token_seed).

    Two kinds of batch-global ``lax.cond`` look alike here; one is safe.
      * NOT allowed: a cond that decides which pick a row RECEIVES. "If any
        row filters, everyone takes the candidate pick" silently
        top-128-truncated unfiltered rows whenever a co-batched row had
        filtering on — a row's token then depended on its batchmates.
      * Allowed, and what this function does: a cond that decides whether a
        pick is COMPUTED, keyed on whether any row's ``where`` selects it
        (``paths``). The skipped branch returns a stand-in of the right
        shape that no row reads; the per-row ``where``s are unchanged, so
        every row's token is the same function of its own inputs as in the
        unconditional body (tests/test_sampler_paths.py holds that body as
        the reference).

    The branch bodies are jitted functions of their own (``_sampled_pick``,
    ``_filtered_pick``), so a branch holds one call. XLA inlines them; JAX's
    lowering does not: with the bodies written inside the conds, the TPU's
    unrolled threefry was emitted op by op into a region nested in the decode
    program's step loop, and every decode program took 0.65 s longer to lower
    at each boot (setup_s +8%, PERF.md §6, PR 28).
    """
    any_sampled, any_filtered = (
        sampler_paths(temperature, top_k, top_p) if paths is None else paths
    )
    greedy = jnp.argmax(logits, axis=-1)
    sampled = jax.lax.cond(
        any_sampled,
        lambda: _sampled_pick(logits, temperature, top_k, top_p, seeds,
                              any_filtered),
        lambda: greedy,
    )
    return jnp.where(temperature <= 0.0, greedy, sampled)


@jax.jit
def _perturbed_scores(scores, temperature, seeds):
    """``sampling_scores``' sampled branch: its own function, as above."""
    temp = jnp.maximum(temperature, 1e-6)[:, None]
    return scores / temp + _gumbel(seeds, scores.shape)


def sampling_scores(
    logits: jax.Array,       # [B, V] float32
    temperature: jax.Array,  # [B]
    seeds: jax.Array,        # [B] uint32 per-row PRNG seeds
    any_sampled: Optional[jax.Array] = None,  # sampler_paths(...)[0]
) -> jax.Array:
    """The score field whose argmax ``sample_tokens`` returns: raw logits
    for greedy rows, ``logits/T + Gumbel(seed)`` for sampled rows. Rank-2
    and below of THIS field are the tokens the target is most likely to
    pick when its own logits diverge slightly from the caller's — the
    right candidate pool for tree-speculation alternates under the common
    random numbers seed schedule (raw-logit runner-ups are not: the
    shared Gumbel perturbation reorders them). The Gumbel field is computed
    only when some row samples (the allowed kind of cond: see
    ``sample_tokens``).
    """
    greedy_scores = logits.astype(jnp.float32)
    if any_sampled is None:
        any_sampled = (temperature > 0.0).any()

    return jnp.where(
        temperature[:, None] <= 0.0,
        greedy_scores,
        jax.lax.cond(
            any_sampled,
            lambda: _perturbed_scores(greedy_scores, temperature, seeds),
            lambda: greedy_scores,
        ),
    )


@jax.named_scope("sample")
def compute_logprobs(
    logits: jax.Array,       # [B, V] float32
    chosen: jax.Array,       # [B] int32 sampled/continuation token ids
    k: int,
) -> tuple:
    """(chosen_logprob [B], topk_logprobs [B, k], topk_ids [B, k]) for the
    OpenAI ``logprobs`` response fields.

    Computed from the RAW logits — the model's distribution, not the
    temperature/penalty-shaped sampling distribution (OpenAI semantics).
    Called inside the jitted dispatches (runner logprob variants)."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    chosen_lp = jnp.take_along_axis(logp, chosen[:, None], axis=-1)[:, 0]
    if k <= 0:
        z = jnp.zeros((logits.shape[0], 0), logits.dtype)
        return chosen_lp, z, z.astype(jnp.int32)
    top_lp, top_ids = jax.lax.top_k(logp, k)
    return chosen_lp, top_lp, top_ids
