"""Mamba-2's state-space scan (state-space duality, arXiv:2405.21060): one
token a row (``ssd_step``) and a chunk of tokens a row (``ssd_chunk``).

Per head h of ``P`` channels, with state ``S`` in R^{P x N} (float32) and
ONE group (every head shares ``B_t`` and ``C_t`` in R^N), for token t:

    a = exp(dt_t[h] * A[h]);  S <- a S + dt_t[h] x_t[h] B_t^T;
    y_t[h] = S C_t + D[h] x_t[h]

``dt`` arrives after its softplus (``gates``), ``A = -exp(A_log)`` < 0. Before
that, x, B and C pass TOGETHER a causal depthwise convolution with a bias and
a SiLU: ops/gated_delta.py's ``conv_step`` / ``conv_chunk``, shared with the
Gated DeltaNet layer; the *conv state* of a sequence is the last W - 1
inputs of each channel.

``ssd_chunk`` is the chunkwise form of the same recurrence (the paper's
``ssd_minimal_discrete``): inside a chunk of ``CHUNK`` tokens the outputs
are one decay-masked product ``((C B^T) o L) (dt o X)`` with ``L_ts =
prod_{r=s+1..t} a_r``, each chunk's own contribution to the state is one
product, and between chunks the state is carried by a ``lax.scan`` whose
step is elementwise. ``CHUNK`` is 128, not the published
``mamba_chunk_size`` 256 (a schedule, not mathematics: the numbers are the
same): 128 x 128 is a v5e's matrix unit, the decay mask's elementwise work
(heads x CHUNK exponentials and products a token) halves against 256, and
the products that meet the state cost the same a token at either length.
Padded positions are inert: ``dt = 0`` leaves ``S`` untouched, and the conv
state a row leaves is that of its last W - 1 *valid* tokens.

The state lies as ``[H, P, N]``: ``N`` = 128 at the published widths, whole
lanes, and ``P`` = 64 whole sublane tiles, so nothing is packed. ``ssd_step``
runs in that layout, on a TPU as one Pallas kernel in place in the decode
loop's carried state (``ssd_step_at``): the update elementwise on the vector
unit, as ``ssd_token`` writes it, and ``S C`` (a sum along the lanes) as one
matrix-unit product a block of heads at ``Precision.HIGHEST``.

Everything here is float32, and every matrix product runs at
``Precision.HIGHEST``: at the default a TPU takes bf16 operands, which rounds
the float32 state every time a chunk reads it (PERF.md §6, PR 31;
benchmarks/chip/configs/granite-4.0-h-micro/check_reference.py, stage
``recurrence``, fails the default). Both entry points run under an inner
``jax.named_scope`` (``ssd_step`` / ``ssd_chunk``) that a trace reader can
split out of the caller's ``attn_core``.
"""

from typing import Tuple

import jax
import jax.numpy as jnp

CHUNK = 128
_HI = jax.lax.Precision.HIGHEST


def gates(dt: jax.Array, a_log: jax.Array, dt_bias: jax.Array
          ) -> Tuple[jax.Array, jax.Array]:
    """Float32 ``(dt, dt * A)`` from the dt projection [..., H]: ``dt =
    softplus(dt + dt_bias)`` (``time_step_limit`` (0, inf): no clamp), ``A =
    -exp(A_log)``; ``exp`` of the second is a token's decay."""
    dt = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias.astype(jnp.float32))
    return dt, -dt * jnp.exp(a_log.astype(jnp.float32))


def softplus_inverse(x: jax.Array) -> jax.Array:
    """``dt_bias`` for a wanted ``dt``: as Mamba-2 initialises it."""
    return x + jnp.log(-jnp.expm1(-x))


def gated_norm(y: jax.Array, z: jax.Array, w: jax.Array, eps: float
               ) -> jax.Array:
    """The layer's output norm, float32: the gate BEFORE the norm (``y *
    silu(z)``), one RMS norm over all ``H * P`` channels (one group)."""
    y = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    var = jnp.mean(y * y, axis=-1, keepdims=True)
    return y * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


# ------------------------------------------------------------------- step
def ssd_token(state: jax.Array,   # [B, H, P, N] f32
              x: jax.Array,       # [B, H, P] f32, after the convolution
              b: jax.Array,       # [B, N] f32
              c: jax.Array,       # [B, N]
              dt: jax.Array,      # [B, H] f32, after softplus
              da: jax.Array,      # [B, H] f32 log-decay dt * A (<= 0)
              d_skip: jax.Array,  # [H] f32
              ) -> Tuple[jax.Array, jax.Array]:
    """One token of the recurrence: (y [B, H, P], state after it). Sums of
    float32 products: nothing rounds the state."""
    state = state * jnp.exp(da)[..., None, None] \
        + (dt[..., None] * x)[..., None] * b[:, None, None, :]
    y = jnp.sum(state * c[:, None, None, :], axis=-1)
    return y + d_skip.astype(jnp.float32)[None, :, None] * x, state


def ssd_step_at_jnp(carry, at, x, b, c, dt, da, d_skip, live):
    """``ssd_step_at`` as plain ``jnp``: the statement of the step, the
    path of a backend without the kernel, and the tests' oracle. The
    layer's state is stepped over EVERY row (a row that is not live with
    ``dt = 0``: decay 1, nothing added) where it lies in the carry: XLA
    fuses the update into a dynamic-update-slice of the carry."""
    dt = jnp.where(live[:, None], dt, 0.0)
    da = jnp.where(live[:, None], da, 0.0)
    state = jax.lax.dynamic_index_in_dim(carry, at, 1, False)
    y, state = ssd_token(state, x, b, c, dt, da, d_skip)
    return (jnp.where(live[:, None, None], y, 0.0),
            jax.lax.dynamic_update_index_in_dim(carry, state, at, 1))


def ssd_step_at(carry, at, x, b, c, dt, da, d_skip, live, *,
                interpret=False):
    """``ssd_token`` for one decode step of a batch on layer ``at`` of the
    rows' carried state [B, n_layers, H, P, N]: a row that is not ``live``
    (its step delivers nothing) keeps its state, and its ``y`` is zeros.
    Returns (y [B, H, P], the carry), the layer's slab updated where it
    lies.

    One algorithm, two executions, chosen HERE by what can be seen (the
    rule of ops/gated_delta.py:gdn_step_at): where the state's shape fits
    it, a program LOWERED for a TPU (``lax.platform_dependent``) holds the
    Pallas kernel (ops/pallas/ssd.py: in place in the carry, a live row's
    state read once and written once, a row that is not live untouched),
    and so does any program with ``interpret`` set (the runner's Pallas
    interpret switch: a CPU's tests); every other holds the ``jnp`` form.
    The two compute the state by the same float32 expression (on the chip
    the same bits); ``y`` differs by the order of a float32 sum over the
    state axis (the kernel's is the matrix unit's at
    ``Precision.HIGHEST``)."""
    from production_stack_tpu.ops.pallas.ssd import (
        ssd_step_in_place,
        supports_step_kernel,
    )

    args = (carry, jnp.asarray(at, jnp.int32), x, b, c, dt, da, d_skip, live)
    with jax.named_scope("ssd_step"):
        if not supports_step_kernel(carry.shape[2:]):
            return ssd_step_at_jnp(*args)
        if interpret:
            return ssd_step_in_place(*args, interpret=True)
        return jax.lax.platform_dependent(
            *args, tpu=ssd_step_in_place, default=ssd_step_at_jnp)


def ssd_step(state, x, b, c, dt, da, d_skip, live, *, interpret=False):
    """``ssd_step_at`` on a state of one layer, [B, H, P, N]."""
    y, carry = ssd_step_at(state[:, None], 0, x, b, c, dt, da, d_skip, live,
                           interpret=interpret)
    return y, carry[:, 0]


def step_path(hlo_text: str):
    """Which execution of ``ssd_step_at`` a compiled program
    (``as_text()``) holds: ``"pallas"``, ``"xla"``, or None where it holds
    no decode step of the scan."""
    if "ssd_step_in_place" in hlo_text:
        return "pallas"
    return "xla" if "/ssd_step/" in hlo_text else None


# ------------------------------------------------------------------ chunk
def ssd_chunk(state: jax.Array,   # [B, H, P, N] f32, before the chunk
              x: jax.Array,       # [B, T, H, P] f32, after the convolution
              b: jax.Array,       # [B, T, N] f32
              c: jax.Array,       # [B, T, N]
              dt: jax.Array,      # [B, T, H] f32, after softplus
              da: jax.Array,      # [B, T, H] f32 log-decay dt * A
              d_skip: jax.Array,  # [H]
              lens: jax.Array,    # [B] valid tokens of each row
              ) -> Tuple[jax.Array, jax.Array]:
    """T tokens a row from ``state``: (y [B, T, H, P] f32, the state after
    each row's last valid token). Equals ``ssd_token`` applied to the valid
    tokens in turn."""
    with jax.named_scope("ssd_chunk"):
        bsz, t, h, p = x.shape
        q = min(CHUNK, t)
        pad = -t % q
        valid = jnp.arange(t, dtype=jnp.int32)[None, :] < lens[:, None]
        dt = jnp.where(valid[..., None], dt, 0.0)
        da = jnp.where(valid[..., None], da, 0.0)
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
            b, c, dt, da = (jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
                            for v in (b, c, dt, da))
        n = (t + pad) // q
        # [B, n, H, Q, P]: a head's chunk is a matrix of whole tiles.
        xd = (x * dt[..., None]).reshape(bsz, n, q, h, p).transpose(
            0, 1, 3, 2, 4)
        bc = b.reshape(bsz, n, q, -1)
        cc = c.reshape(bsz, n, q, -1)
        cs = jnp.cumsum(da.reshape(bsz, n, q, h).transpose(0, 1, 3, 2),
                        axis=-1)                           # [B, n, H, Q]
        tril = jnp.tril(jnp.ones((q, q), bool))
        # exp(cs_t - cs_s) for t >= s; masked BEFORE the exp so the upper
        # half (a positive exponent) cannot overflow.
        decay = jnp.exp(jnp.where(
            tril, cs[..., :, None] - cs[..., None, :], -jnp.inf))
        # Inside a chunk: C B^T once for all heads, then a head's mask.
        cb = jnp.einsum("bntk,bnsk->bnts", cc, bc, precision=_HI)
        y = jnp.einsum("bnhts,bnhsp->bnhtp", cb[:, :, None] * decay, xd,
                       precision=_HI)
        # A chunk's own contribution to the state at its end.
        to_end = jnp.exp(cs[..., -1:] - cs)                # [B, n, H, Q]
        own = jnp.einsum("bnhsp,bnsk->bnhpk", xd * to_end[..., None], bc,
                         precision=_HI)                    # [B, n, H, P, N]
        chunk_decay = jnp.exp(cs[..., -1])                 # [B, n, H]

        def carry_on(s, xs):
            own_i, decay_i = xs
            return s * decay_i[..., None, None] + own_i, s

        state, before = jax.lax.scan(
            carry_on, state,
            (jnp.moveaxis(own, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
        # What the state before a chunk gives its tokens.
        y = y + jnp.einsum(
            "bntk,nbhpk->bnhtp", cc, before, precision=_HI) \
            * jnp.exp(cs)[..., None]
        y = y.transpose(0, 1, 3, 2, 4).reshape(bsz, n * q, h, p)[:, :t]
        x = x[:, :t]
        return y + d_skip.astype(jnp.float32)[None, None, :, None] * x, state
