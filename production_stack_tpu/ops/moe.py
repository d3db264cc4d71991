"""Sparse experts: the router and the expert FFN over tokens sorted by
expert (DeepSeek-V3's ``noaux_tc`` routing without groups).

Router, all in float32 (a near-tie between the k-th and the next score
flips on a bf16 rounding, and a flipped choice is a different function of
the token, not a small error):

    s = sigmoid(x W_r);  chosen = top_k(s + bias);
    w = s[chosen] / (sum(s[chosen]) + eps) * scaling

``bias`` (``e_score_correction_bias``) moves the CHOICE only; the weights
are the scores themselves.

Experts: the (token, expert) pairs are sorted by expert, so that an
expert's tokens are consecutive rows; a grouped matmul multiplies each
run by its expert's matrices (gate and up as one, then down) and the
results are weighted and summed back per token. No capacity, no padding
of an expert to a size, no dropped token; an expert no token chose costs
nothing: its matrices are not read (ops/pallas/grouped_matmul.py). Tokens
that do not count (``valid`` false: a bucket's padded rows, a prompt's
padding, a decode row past its budget) are given to no expert: they sort
behind every run and are neither computed nor counted.

Expert parallelism (``expert_ffn(..., here=)``): the router keeps its whole
width and its k, and a chip holds SOME of a layer's experts. A pair whose
expert lies on another chip is given to no expert here, exactly as a token
that does not count: it sorts behind every run and is neither computed nor
counted among the assignments (it is counted apart, ``STATS_EP``'s last).
What the absent experts would have added is left out: nothing stands in
for the other chips or for their exchange.

``grouped_matmul`` is the one entry to the product: a program lowered for
a TPU holds the Pallas kernel (and so does one with ``interpret`` set: a
CPU's tests of the kernel), every other ``jax.lax.ragged_dot``.
"""

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST
# Scalars a sparse layer call reports (int32, summed over a dispatch's
# calls): pairs computed, distinct experts touched, the busiest expert's
# tokens, calls.
STATS = ("assignments", "experts_touched", "expert_load_max", "layer_calls")
# The same of a layer that holds a share of its experts, and the pairs its
# router gave to experts held elsewhere.
STATS_EP = STATS + ("assignments_elsewhere",)


def route(x: jax.Array, w_router: jax.Array, bias: jax.Array, top_k: int,
          scaling: float, norm_topk_prob: bool = True, eps: float = 1e-20,
          ) -> Tuple[jax.Array, jax.Array]:
    """x [N, D] -> (chosen experts [N, k] int32, weights [N, k] float32).
    ``eps``: what the weights' sum takes (DeepSeek-V3's 1e-20; LFM2's
    published 1e-6)."""
    with jax.named_scope("moe_route"):
        s = jax.nn.sigmoid(jnp.dot(
            x.astype(jnp.float32), w_router.astype(jnp.float32),
            precision=_HI))                                       # [N, E]
        _, idx = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
        w = jnp.take_along_axis(s, idx, axis=1)
        if norm_topk_prob:
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + eps)
        return idx.astype(jnp.int32), w * scaling


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
                   *, interpret: bool = False) -> jax.Array:
    """Rows of ``lhs`` [m, k], sorted by group, times their group's
    ``rhs[g]`` [k, n]: [m, n] float32. Rows behind the last group's are
    not computed (their output is unspecified)."""
    from production_stack_tpu.ops.pallas.grouped_matmul import moe_gmm

    def ragged(lhs, rhs, group_sizes):
        return jax.lax.ragged_dot(lhs, rhs, group_sizes,
                                  preferred_element_type=jnp.float32)

    with jax.named_scope("moe_gmm"):
        if interpret:
            return moe_gmm(lhs, rhs, group_sizes, interpret=True)
        return jax.lax.platform_dependent(
            lhs, rhs, group_sizes, tpu=moe_gmm, default=ragged)


def expert_ffn(x: jax.Array, idx: jax.Array, w: jax.Array, valid: jax.Array,
               w_gate_up: jax.Array, w_down: jax.Array, *,
               interpret: bool = False,
               here: Optional[jax.Array] = None,
               ) -> Tuple[jax.Array, jax.Array]:
    """``sum_j w[n, j] * expert_{idx[n, j]}(x[n])`` for the valid tokens.

    x [N, D]; idx, w [N, k]; valid [N] bool; w_gate_up [E, D, 2F] (gate
    then up); w_down [E, F, D]. Returns (y [N, D] float32, zeros where a
    token is not valid; stats int32[4] as ``STATS`` names them).

    ``here`` [N, k] bool (None, static: every expert is held, and the
    program as it was): the pairs whose expert this chip holds, ``idx``
    being the expert's place in the stacks HELD for those and anything for
    the others, which add nothing; stats int32[5] as ``STATS_EP``."""
    n, d = x.shape
    k = idx.shape[1]
    e, f = w_down.shape[0], w_down.shape[1]
    with jax.named_scope("moe_experts"):
        # Pairs in expert order; a token that does not count sorts last,
        # and so does a pair whose expert is held elsewhere.
        counts = None if here is None else valid[:, None] & here
        pair_expert = jnp.where(
            valid[:, None] if here is None else counts, idx, e).reshape(-1)
        order = jnp.argsort(pair_expert, stable=True)             # [N*k]
        group_sizes = jnp.zeros((e,), jnp.int32).at[pair_expert].add(
            1, mode="drop")
        rows = x[order // k]                                      # [N*k, D]
        h = grouped_matmul(rows, w_gate_up, group_sizes,
                           interpret=interpret)                   # [N*k, 2F]
        act = (jax.nn.silu(h[:, :f]) * h[:, f:]).astype(x.dtype)
        out = grouped_matmul(act, w_down, group_sizes,
                             interpret=interpret)                 # [N*k, D]
        # Back to token order, weighted; what lies behind the last run was
        # never computed and is replaced, not multiplied.
        back = jnp.argsort(order)
        y = jnp.where(valid[:, None, None] if here is None
                      else counts[:, :, None],
                      out[back].reshape(n, k, d) * w[:, :, None], 0.0)
        stats = [jnp.sum(group_sizes), jnp.sum(group_sizes > 0),
                 jnp.max(group_sizes), jnp.int32(1)]
        if here is not None:
            stats.append(jnp.sum(valid[:, None] & ~here))
        stats = jnp.stack(stats).astype(jnp.int32)
        return jnp.sum(y, axis=1), stats
