"""Mamba-1's selective scan (S6, arXiv:2312.00752): one token a row
(``s6_step_at``) and a chunk of tokens a row (``s6_chunk``).

Per channel c of ``D`` and state lane n of ``N``, with state ``S`` in
R^{N x D} (float32), for token t:

    S[n, c] <- exp(dt_t[c] A[n, c]) S[n, c] + dt_t[c] u_t[c] B_t[n]
    y_t[c] = sum_n S[n, c] C_t[n] + D_skip[c] u_t[c]

``dt`` arrives after its softplus (``gates``), ``A = -exp(A_log)`` < 0. The
decay is its own for every channel AND state lane, so nothing of Mamba-2's
chunkwise form (ops/ssd.py: one decay a head, a chunk as a masked matrix
product) exists here: the work is elementwise, ``N * D`` exponentials and
multiply-adds a token, on the vector and transcendental units. Before the
scan, u passes a causal depthwise convolution with a bias and a SiLU:
ops/gated_delta.py's ``conv_step`` / ``conv_chunk``, shared with the Gated
DeltaNet and Mamba-2 layers.

The state lies ``[N, D]``: the channels along the lanes (40 lane tiles at the
published 5120), ``N`` = 16 two sublane tiles. ``B_t`` and ``C_t`` then
multiply as COLUMNS (a sublane's number broadcast along its lanes) and ``y``
is a sum over sublanes.

One algorithm a form, two executions of the chunk, chosen HERE by what can be
seen (the rule of ops/gated_delta.py:gdn_step_at): where the shapes fit it, a
program LOWERED for a TPU (``lax.platform_dependent``) holds the Pallas
kernel (ops/pallas/selective_scan.py: channel blocks over the grid, time
walked inside with the block's state in registers) and so does any program
with ``interpret`` set; every other holds ``s6_chunk_jnp``, a ``lax.scan`` of
one step a token (the statement, the CPU's path and the tests' oracle: 2048
serial XLA steps a layer are not a chip's path). The decode step is plain
``jnp`` in place in the decode loop's carry.

Everything here is float32. Padded positions are inert: ``dt = 0`` leaves
``S`` untouched. Both entry points run under an inner ``jax.named_scope``
(``s6_step`` / ``s6_chunk``) that a trace reader can split out of the
caller's scope.
"""

from typing import Tuple

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def gates(u: jax.Array,        # [..., D] after the convolution
          w_x: jax.Array,      # [D, R + 2 N]
          w_dt: jax.Array,     # [R, D]
          dt_bias: jax.Array,  # [D]
          n_state: int,
          ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Float32 ``(dt [..., D], B [..., N], C [..., N])`` of a token:
    ``[delta | B | C] = u W_x``, ``dt = softplus(delta W_dt + dt_bias)``,
    both products at ``Precision.HIGHEST`` (at the default a TPU rounds
    their float32 operands to bf16: PERF.md section 6, PR 31)."""
    proj = jnp.matmul(u.astype(F32), w_x.astype(F32), precision=_HI)
    rank = proj.shape[-1] - 2 * n_state
    dt = jnp.matmul(proj[..., :rank], w_dt.astype(F32), precision=_HI)
    dt = jax.nn.softplus(dt + dt_bias.astype(F32))
    return dt, proj[..., rank:rank + n_state], proj[..., rank + n_state:]


def s6_token(state: jax.Array,   # [B, N, D] f32
             u: jax.Array,       # [B, D] f32, after the convolution
             dt: jax.Array,      # [B, D] f32, after softplus
             a: jax.Array,       # [N, D] f32, -exp(A_log)
             b: jax.Array,       # [B, N] f32
             c: jax.Array,       # [B, N] f32
             d_skip: jax.Array,  # [D] f32
             ) -> Tuple[jax.Array, jax.Array]:
    """One token of the recurrence: (y [B, D], state after it). Sums of
    float32 products: nothing rounds the state."""
    state = jnp.exp(dt[:, None, :] * a[None]) * state \
        + (dt * u)[:, None, :] * b[:, :, None]
    y = jnp.sum(state * c[:, :, None], axis=1)
    return y + d_skip.astype(F32)[None] * u, state


# ------------------------------------------------------------------- step
def s6_step_at(carry, at, u, dt, a, b, c, d_skip, live):
    """``s6_token`` for one decode step of a batch on layer ``at`` of the
    rows' carried state [B, n_layers, N, D]: a row that is not ``live``
    keeps its state (``dt = 0``: decay 1, nothing added) and its ``y`` is
    zeros. Returns (y [B, D], the carry), the layer's slab updated where it
    lies: XLA fuses the update into a dynamic-update-slice of the carry
    (ops/ssd.py:ssd_step_at_jnp says the same of its)."""
    with jax.named_scope("s6_step"):
        dt = jnp.where(live[:, None], dt, 0.0)
        state = jax.lax.dynamic_index_in_dim(carry, at, 1, False)
        y, state = s6_token(state, u, dt, a, b, c, d_skip)
        return (jnp.where(live[:, None], y, 0.0),
                jax.lax.dynamic_update_index_in_dim(carry, state, at, 1))


def s6_step(state, u, dt, a, b, c, d_skip, live):
    """``s6_step_at`` on a state of one layer, [B, N, D]."""
    y, carry = s6_step_at(state[:, None], 0, u, dt, a, b, c, d_skip, live)
    return y, carry[:, 0]


# ------------------------------------------------------------------ chunk
def s6_chunk_jnp(state, u, dt, a, b, c, d_skip):
    """``s6_chunk`` as plain ``jnp`` (``dt`` already 0 past a row's
    length): ``s6_token`` over the chunk's tokens in turn."""
    def token(s, xs):
        y, s = s6_token(s, *xs[:2], a, *xs[2:], d_skip)
        return s, y

    state, y = jax.lax.scan(
        token, state, tuple(jnp.moveaxis(x, 1, 0) for x in (u, dt, b, c)))
    return jnp.moveaxis(y, 0, 1), state


def s6_chunk(state: jax.Array,   # [B, N, D] f32, before the chunk
             u: jax.Array,       # [B, T, D] f32, after the convolution
             dt: jax.Array,      # [B, T, D] f32, after softplus
             a: jax.Array,       # [N, D] f32, -exp(A_log)
             b: jax.Array,       # [B, T, N] f32
             c: jax.Array,       # [B, T, N] f32
             d_skip: jax.Array,  # [D]
             lens: jax.Array,    # [B] valid tokens of each row
             *,
             interpret: bool = False,
             ) -> Tuple[jax.Array, jax.Array]:
    """T tokens a row from ``state``: (y [B, T, D] f32, the state after
    each row's last valid token). Equals ``s6_token`` applied to the valid
    tokens in turn."""
    from production_stack_tpu.ops.pallas.selective_scan import (
        s6_chunk_kernel,
        supports_chunk_kernel,
    )

    with jax.named_scope("s6_chunk"):
        t = u.shape[1]
        valid = jnp.arange(t, dtype=jnp.int32)[None, :] < lens[:, None]
        dt = jnp.where(valid[..., None], dt, 0.0)
        args = (state, u, dt, a, b, c, d_skip.astype(F32))
        if not supports_chunk_kernel(t, *state.shape[1:]):
            return s6_chunk_jnp(*args)
        if interpret:
            return s6_chunk_kernel(*args, interpret=True)
        return jax.lax.platform_dependent(
            *args, tpu=s6_chunk_kernel, default=s6_chunk_jnp)


def chunk_path(hlo_text: str):
    """Which execution of ``s6_chunk`` a compiled program (``as_text()``)
    holds: ``"pallas"``, ``"xla"``, or None where it holds no chunk of the
    scan."""
    if "s6_chunk_kernel" in hlo_text:
        return "pallas"
    return "xla" if "/s6_chunk/" in hlo_text else None
