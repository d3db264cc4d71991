"""Gated DeltaNet: the recurrence of a linear-attention layer and its causal
depthwise convolution, one token a row (``gdn_step``) and a chunk of tokens
a row (``gdn_chunk``).

Per head, with state ``S`` in R^{dk x dv} (float32), for token t:

    S <- exp(g_t) S;  u = (v_t - S^T k_t) beta_t;  S <- S + k_t u^T;
    o_t = S^T q_t

``q`` arrives l2-normalised and scaled by dk^-0.5, ``k`` l2-normalised
(``prepare``). Before that, q, k and v pass a causal depthwise convolution
of width W over the token axis and a SiLU; the *conv state* of a sequence is
the last W - 1 inputs of each channel. ``conv_step`` / ``conv_chunk`` are
also the convolution of a Mamba-2 layer (ops/ssd.py), which adds a bias;
``conv_packed_row`` is ``conv_chunk`` for ONE row in which several
sequences' chunks lie end to end, a conv state a segment (a packed prefill
row: models/lfm2_moe.py; the scans behind the other two models' convolutions
have no such form yet).

``gdn_chunk`` is the chunkwise form of the same recurrence (chunks of up to
64 tokens, the WY representation: HF's ``torch_chunk_gated_delta_rule`` is a
plain statement of it): inside a chunk the tokens' updates are solved
together as one unit-lower-triangular system (by forward substitution),
between chunks the state is carried: by a ``lax.scan`` in the ``jnp`` form
(``gdn_chunk_jnp``), in VMEM across a row's chunks in the Pallas kernel a
TPU's prefill programs hold (ops/pallas/gated_delta.py:gdn_chunk_in_place;
``gdn_chunk`` chooses). Padded positions are inert: ``beta = 0, g = 0``
leave ``S`` untouched, and the conv state a row leaves is that of its last
W - 1 *valid* tokens.

The state is kept PACKED: ``head_pack`` heads side by side on the minor
axis, ``[H/P, dk, P*dv]``, so that the minor axis is a whole number of the
TPU's 128 lanes (dv = 192 alone would be stored as 256: a third more bytes
to hold, read and write, every row, every layer, every step). ``gdn_step``
computes in that layout (the per-head vectors are spread to it, which costs
nothing beside the state's own traffic), on a TPU as one Pallas kernel in
place in the decode loop's carried state (``gdn_step_at``); the chunk
kernel works on a head's lanes of the packed state, and ``gdn_chunk_jnp``
unpacks the state it starts from and packs the one it leaves, once a call.

Everything here is float32: state, scores, accumulators. The chunkwise
form's matrix products run at ``Precision.HIGHEST``: at the default a TPU
takes bf16 operands, which rounds the float32 state every time a chunk
reads it (3e-3 of the outputs' norm against the token-by-token recurrence
at the published head sizes, where ``HIGHEST`` reads 1e-6:
benchmarks/chip/configs/olmo-hybrid-7b-d16/check_reference.py, stage
``recurrence``, which fails the default). Products that share an operand
are made as one (k_beta k^T with q k^T; w S with q S). Both entry points run
under an inner ``jax.named_scope`` (``gdn_step`` / ``gdn_chunk``) that a
trace reader can split out of the caller's ``attn_core``.
"""

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from production_stack_tpu.ops.attention import segment_of_token

CHUNK = 64
L2_EPS = 1e-6
_HI = jax.lax.Precision.HIGHEST


def head_pack(num_heads: int, dv: int) -> int:
    """Heads that share a row of the packed state: the fewest (of 1, 2, 4)
    that make ``P * dv`` whole lanes and divide the heads; else 1."""
    for p in (1, 2, 4):
        if (p * dv) % 128 == 0 and num_heads % p == 0:
            return p
    return 1


def packed_shape(num_heads: int, dk: int, dv: int) -> Tuple[int, int, int]:
    p = head_pack(num_heads, dv)
    return (num_heads // p, dk, p * dv)


def pack_state(state: jax.Array) -> jax.Array:
    """[B, H, dk, dv] -> [B, H/P, dk, P*dv]."""
    b, h, dk, dv = state.shape
    p = head_pack(h, dv)
    return state.reshape(b, h // p, p, dk, dv).transpose(0, 1, 3, 2, 4) \
        .reshape(b, h // p, dk, p * dv)


def unpack_state(packed: jax.Array, num_heads: int) -> jax.Array:
    """[B, H/P, dk, P*dv] -> [B, H, dk, dv]."""
    b, hp, dk, pdv = packed.shape
    p = num_heads // hp
    return packed.reshape(b, hp, dk, p, pdv // p).transpose(0, 1, 3, 2, 4) \
        .reshape(b, num_heads, dk, pdv // p)


def _spread(parts, dv: int) -> jax.Array:
    """``parts[i]`` ([..., 1], i < P) laid side by side over ``P * dv``
    lanes, ``dv`` lanes each. A select by the lane's number and nothing
    else, so that it fuses into whatever reads it: a ``repeat`` and a
    reshape to P*dv is a relayout where dv is not whole lanes, and came out
    of the compiler as an array the size of the state, twice a layer."""
    lane_head = jax.lax.broadcasted_iota(
        jnp.int32, (len(parts) * dv,), 0) // dv
    out = parts[0]
    for i in range(1, len(parts)):
        out = jnp.where(lane_head == i, parts[i], out)
    return jnp.broadcast_to(out, (*out.shape[:-1], len(parts) * dv))


def _spread_k(x: jax.Array, p: int, dv: int) -> jax.Array:
    """A per-head key-side vector [B, H, dk] laid over the packed state:
    [B, H/P, dk, P*dv], head ``hp*P + i`` on lanes ``i*dv .. (i+1)*dv``."""
    b, h, dk = x.shape
    x = x.reshape(b, h // p, p, dk)
    return _spread([x[:, :, i, :, None] for i in range(p)], dv)


def _spread_h(x: jax.Array, p: int, dv: int) -> jax.Array:
    """A per-head scalar [B, H] over the packed value axis: [B, H/P, P*dv]."""
    b, h = x.shape
    x = x.reshape(b, h // p, p)
    return _spread([x[:, :, i, None] for i in range(p)], dv)


def l2norm(x: jax.Array) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def gates(b: jax.Array, a: jax.Array, a_log: jax.Array, dt_bias: jax.Array,
          allow_neg_eigval: bool) -> Tuple[jax.Array, jax.Array]:
    """(beta, g) in float32 from the b and a projections [..., H]:
    ``beta = sigmoid(b)`` (doubled where the eigenvalues of the update may
    be negative), ``g = -exp(A_log) softplus(a + dt_bias)``."""
    beta = jax.nn.sigmoid(b.astype(jnp.float32))
    if allow_neg_eigval:
        beta = 2.0 * beta
    g = -jnp.exp(a_log.astype(jnp.float32)) * jax.nn.softplus(
        a.astype(jnp.float32) + dt_bias.astype(jnp.float32))
    return beta, g


def prepare(q: jax.Array, k: jax.Array, v: jax.Array):
    """Float32 ``(l2norm(q) dk^-0.5, l2norm(k), v)`` over the last axis."""
    q, k = q.astype(jnp.float32), k.astype(jnp.float32)
    return l2norm(q) * q.shape[-1] ** -0.5, l2norm(k), v.astype(jnp.float32)


# ------------------------------------------------------------ convolution
def conv_step(x: jax.Array,           # [B, C] this token's channels
              conv_state: jax.Array,  # [B, W-1, C] the W-1 inputs before it
              w: jax.Array,           # [W, C]; w[W-1] weighs the newest
              live: jax.Array,        # [B] bool: rows that take the token
              bias=None,              # [C], or None (ops/ssd.py's layers)
              silu: bool = True,      # False: the sum itself (lfm2_moe.py)
              ) -> Tuple[jax.Array, jax.Array]:
    """SiLU(conv) of one token a row (``silu`` false: the conv itself) and
    the conv state after it; a row that is not ``live`` keeps its state."""
    window = jnp.concatenate([conv_state, x[:, None].astype(conv_state.dtype)],
                             axis=1)                           # [B, W, C]
    y = jnp.sum(window.astype(jnp.float32) * w.astype(jnp.float32)[None],
                axis=1)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    new_state = jnp.where(live[:, None, None], window[:, 1:], conv_state)
    return (jax.nn.silu(y) if silu else y).astype(x.dtype), new_state


def conv_chunk(x: jax.Array,           # [B, T, C]
               conv_state: jax.Array,  # [B, W-1, C]
               w: jax.Array,           # [W, C]
               lens: jax.Array,        # [B] valid tokens of each row
               bias=None,              # [C], or None
               silu: bool = True,      # False: the sum itself
               ) -> Tuple[jax.Array, jax.Array]:
    """SiLU(causal depthwise conv) of a chunk that continues ``conv_state``
    (``silu`` false: the conv itself), and the conv state after each row's
    last valid token (a row of length 0 keeps its state)."""
    width = w.shape[0]
    t = x.shape[1]
    ext = jnp.concatenate([conv_state, x.astype(conv_state.dtype)], axis=1)
    wf = w.astype(jnp.float32)
    y = sum(ext[:, i:i + t].astype(jnp.float32) * wf[i][None, None]
            for i in range(width))
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    # ext[len : len + W-1] are the inputs of tokens len-W+1 .. len-1.
    idx = lens[:, None] + jnp.arange(width - 1, dtype=jnp.int32)[None, :]
    new_state = jnp.take_along_axis(ext, idx[:, :, None], axis=1)
    return (jax.nn.silu(y) if silu else y).astype(x.dtype), new_state


def conv_packed_row(x: jax.Array,           # [1, T, C]
                    conv_state: jax.Array,  # [S, W-1, C]
                    w: jax.Array,           # [W, C]
                    seg_lens: jax.Array,    # [S] tokens of each segment
                    bias=None,              # [C], or None
                    silu: bool = True,      # False: the sum itself
                    ) -> Tuple[jax.Array, jax.Array]:
    """``conv_chunk`` of ONE row that holds S sequences' chunks end to end
    from token 0 (a packed prefill row, ops/attention.py:KVView.seg_lens),
    segment s continuing ``conv_state[s]``: the token at offset j of segment
    s reads lag k from the row's token k before it where j >= k and from
    ``conv_state[s]`` where j < k, so nothing crosses from a segment into
    the next. Returns (the row [1, T, C], the conv state after each
    segment's last token [S, W-1, C]: the last W - 1 inputs of
    ``conv_state[s]`` ++ its tokens; a segment of length 0 keeps its state).
    Equals ``conv_chunk`` over the same sequences a row each, value for
    value: the same inputs meet the same taps in the same order.

    What a segment's first W - 1 tokens read of its state is picked by a
    one-hot product (S * (W-1) rows against T tokens: exact for finite
    values, a value times one and zeros) and the state a segment leaves by
    a gather of S * (W-1) rows; everything else is the row shifted."""
    width = w.shape[0]
    t = x.shape[1]
    s = seg_lens.shape[0]
    seg, within = segment_of_token(seg_lens, t)
    xs = x[0].astype(conv_state.dtype)                           # [T, C]
    flat = conv_state.reshape(s * (width - 1), -1)
    entry = jnp.arange(s * (width - 1), dtype=jnp.int32)[None, :]
    lagged = [xs]                                  # lagged[k][t] = input t-k
    for k in range(1, width):
        shifted = jnp.pad(xs, ((k, 0), (0, 0)))[:t]
        # Lag k of offset j < k is entry W-1-k+j of the segment's state.
        pick = (within < k)[:, None] & (
            entry == (seg * (width - 1) + width - 1 - k + within)[:, None])
        from_state = jnp.dot(pick.astype(flat.dtype), flat, precision=_HI,
                             preferred_element_type=jnp.float32)
        lagged.append(jnp.where((within >= k)[:, None], shifted,
                                from_state.astype(xs.dtype)))
    wf = w.astype(jnp.float32)
    y = sum(lagged[width - 1 - i].astype(jnp.float32) * wf[i][None]
            for i in range(width))
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    # Entry i of the state after a segment of n tokens is input n + i of
    # (its state ++ its tokens): the row's token start + n + i - (W-1), or
    # where that lies before the segment, its state's entry n + i.
    at = seg_lens[:, None] + jnp.arange(width - 1, dtype=jnp.int32)[None, :]
    start = jnp.cumsum(seg_lens) - seg_lens
    row_at = jnp.clip(start[:, None] + at - (width - 1), 0, t - 1)
    kept = jnp.take_along_axis(
        conv_state, jnp.clip(at, 0, width - 2)[:, :, None], axis=1)
    new_state = jnp.where((at >= width - 1)[:, :, None], xs[row_at], kept)
    return (jax.nn.silu(y) if silu else y).astype(x.dtype)[None], new_state


# ------------------------------------------------------------- recurrence
def delta_step(state: jax.Array,   # [B, H, dk, dv] f32
               q: jax.Array,       # [B, H, dk] f32, prepared
               k: jax.Array,       # [B, H, dk]
               v: jax.Array,       # [B, H, dv]
               g: jax.Array,       # [B, H] f32 log-decay (<= 0)
               beta: jax.Array,    # [B, H] f32
               ) -> Tuple[jax.Array, jax.Array]:
    """One token of the recurrence: (o [B, H, dv], state after it)."""
    state = state * jnp.exp(g)[..., None, None]
    kv_mem = jnp.sum(state * k[..., None], axis=-2)
    u = (v - kv_mem) * beta[..., None]
    state = state + k[..., None] * u[..., None, :]
    return jnp.sum(state * q[..., None], axis=-2), state


def gdn_step_at_jnp(carry, at, q, k, v, g, beta, live):
    """``gdn_step_at`` as plain ``jnp``: the statement of the step, the
    path of a backend without the kernel, and the tests' oracle. The
    layer's state is taken out of the carry, stepped over EVERY row (a row
    that is not live with its gates zeroed) and put back."""
    state = jax.lax.dynamic_index_in_dim(carry, at, 1, False)
    b, h, dv = v.shape
    p = h // state.shape[1]
    g = jnp.where(live[:, None], g, 0.0)
    beta = jnp.where(live[:, None], beta, 0.0)
    kx = _spread_k(k, p, dv)
    state = state * jnp.exp(_spread_h(g, p, dv))[:, :, None, :]
    kv_mem = jnp.sum(state * kx, axis=-2)                 # [B, Hp, P*dv]
    u = (v.reshape(b, h // p, p * dv) - kv_mem) * _spread_h(beta, p, dv)
    state = state + kx * u[:, :, None, :]
    o = jnp.sum(state * _spread_k(q, p, dv), axis=-2).reshape(b, h, dv)
    return (jnp.where(live[:, None, None], o, 0.0),
            jax.lax.dynamic_update_index_in_dim(carry, state, at, 1))


def gdn_step_at(carry, at, q, k, v, g, beta, live, *, interpret=False):
    """``delta_step`` for one decode step of a batch on layer ``at`` of the
    rows' carried PACKED state [B, n_linear, H/P, dk, P*dv]: a row that is
    not ``live`` (its step delivers nothing) keeps its state, and its ``o``
    is zeros. Other inputs as ``delta_step`` after ``prepare``;
    ``live`` [B] bool. Returns (o [B, H, dv], the carry).

    One algorithm, two executions, chosen HERE by what can be seen: where
    the packed shape fits it, a program lowered for a TPU holds the Pallas
    kernel (ops/pallas/gated_delta.py: in place in the carry, a live row's
    state read once and written once, a row that is not live untouched),
    and so does any program with ``interpret`` set (the runner's Pallas
    interpret switch: a CPU's tests); every other holds the ``jnp`` form.
    The platform is the one the program is LOWERED for
    (``lax.platform_dependent``), not the process's default backend: a
    program compiled for a described chip holds what the chip will run."""
    from production_stack_tpu.ops.pallas.gated_delta import (
        gdn_step_in_place,
        supports_step_kernel,
    )

    args = (carry, jnp.asarray(at, jnp.int32), q, k, v, g, beta, live)
    with jax.named_scope("gdn_step"):
        if not supports_step_kernel(v.shape[1], carry.shape[2:]):
            return gdn_step_at_jnp(*args)
        if interpret:
            return gdn_step_in_place(*args, interpret=True)
        return jax.lax.platform_dependent(
            *args, tpu=gdn_step_in_place, default=gdn_step_at_jnp)


def step_path(hlo_text: str):
    """Which execution of ``gdn_step_at`` a compiled program
    (``as_text()``) holds: ``"pallas"``, ``"xla"``, or None where it holds
    no decode step of the recurrence."""
    if "gdn_step_in_place" in hlo_text:
        return "pallas"
    return "xla" if "/gdn_step/" in hlo_text else None


def short_conv_path(hlo_text: str):
    """``"xla"`` where a compiled program (``as_text()``) holds a gated
    short convolution (models/lfm2_moe.py's inner scope; ``conv_step`` /
    ``conv_chunk`` have the one execution), else None."""
    return "xla" if "/short_conv/" in hlo_text else None


def gdn_step(state, q, k, v, g, beta, live, *, interpret=False):
    """``gdn_step_at`` on a state of one layer, [B, H/P, dk, P*dv]:
    returns (o [B, H, dv], packed state)."""
    o, carry = gdn_step_at(state[:, None], 0, q, k, v, g, beta, live,
                           interpret=interpret)
    return o, carry[:, 0]


def _unit_lower_inverse(lower: jax.Array) -> jax.Array:
    """``(I + lower)^-1`` for strictly lower-triangular ``lower``
    [..., C, C]: the chunk's updates solved together, by forward
    substitution a row at a time (row i of the inverse is minus row i of
    ``lower`` times the rows before it: HF's loop). Sums of float32
    products, no matrix unit: C - 1 passes over the chunk's C x C scores,
    which is little beside the chunk's products, where XLA's triangular
    solve at 64 x 64 was the slowest operation of a prefill."""
    c = lower.shape[-1]

    def row(i, acc):
        r = jax.lax.dynamic_index_in_dim(acc, i, -2, keepdims=True)
        r = r + jnp.sum(jnp.swapaxes(r, -1, -2) * acc, axis=-2,
                        keepdims=True)
        return jax.lax.dynamic_update_index_in_dim(acc, r, i, -2)

    return jax.lax.fori_loop(1, c, row, -lower) \
        + jnp.eye(c, dtype=lower.dtype)


def gdn_chunk(state: jax.Array,   # [B, H/P, dk, P*dv] f32 packed, before the chunk
              q: jax.Array,       # [B, T, H, dk] f32, prepared
              k: jax.Array,       # [B, T, H, dk]
              v: jax.Array,       # [B, T, H, dv]
              g: jax.Array,       # [B, T, H] f32
              beta: jax.Array,    # [B, T, H] f32
              lens: jax.Array,    # [B] valid tokens of each row
              *, interpret=False) -> Tuple[jax.Array, jax.Array]:
    """T tokens a row from ``state``: (o [B, T, H, dv] f32, the packed state
    after each row's last valid token). Equals ``delta_step`` applied to the
    valid tokens in turn; ``o`` past a row's length is not to be read (the
    kernel leaves zeros there, the ``jnp`` form what the padding computes).

    One algorithm, two executions, chosen as ``gdn_step_at`` chooses: where
    the shapes fit it (``supports_chunk_kernel``: T whole chunks of 64 among
    them), a program lowered for a TPU holds the Pallas kernel
    (ops/pallas/gated_delta.py:gdn_chunk_in_place: a row's state stays in
    VMEM across its chunks, chunks past a row's length are skipped), and so
    does any program with ``interpret`` set; every other holds
    ``gdn_chunk_jnp``."""
    from production_stack_tpu.ops.pallas.gated_delta import (
        gdn_chunk_in_place,
        supports_chunk_kernel,
    )

    args = (state, q, k, v, g, beta, lens)
    with jax.named_scope("gdn_chunk"):
        if not supports_chunk_kernel(q.shape[1], q.shape[2], state.shape[1:]):
            return gdn_chunk_jnp(*args)
        # The kernel's products at this form's precision, as it stands when
        # the program is traced (a static argument: its jit cache keys on it).
        kernel = functools.partial(gdn_chunk_in_place, precision=_HI)
        if interpret:
            return kernel(*args, interpret=True)
        return jax.lax.platform_dependent(
            *args, tpu=kernel, default=gdn_chunk_jnp)


def chunk_path(hlo_text: str):
    """Which execution of ``gdn_chunk`` a compiled program (``as_text()``)
    holds: ``"pallas"``, ``"xla"``, or None where it holds no chunk of the
    recurrence."""
    if "gdn_chunk_in_place" in hlo_text:
        return "pallas"
    return "xla" if "/gdn_chunk/" in hlo_text else None


def gdn_chunk_jnp(state, q, k, v, g, beta, lens):
    """``gdn_chunk`` as plain ``jnp``: the statement of the chunkwise form,
    the path of a backend without the kernel and of a T that is not whole
    chunks, and the tests' oracle. The chunks' products are made for the
    whole call at once, the state is carried between chunks by a
    ``lax.scan``, and every padded position is computed."""
    b, t, h, dk = q.shape
    state = unpack_state(state, h)
    dv = v.shape[-1]
    c = min(CHUNK, t)
    pad = -t % c
    valid = (jnp.arange(t, dtype=jnp.int32)[None, :] < lens[:, None])
    g = jnp.where(valid[..., None], g, 0.0)
    beta = jnp.where(valid[..., None], beta, 0.0)
    if pad:
        q, k, v = (jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for x in (q, k, v))
        g, beta = (jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
                   for x in (g, beta))
    n = (t + pad) // c

    def chunks(x):   # [B, T, H, ...] -> [N, B, H, C, ...]
        x = x.reshape(b, n, c, *x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)

    qc, kc, vc = chunks(q), chunks(k), chunks(v)
    gc = jnp.cumsum(chunks(g), axis=-1)            # [N, B, H, C]
    bc = chunks(beta)
    k_beta = kc * bc[..., None]
    v_beta = vc * bc[..., None]
    tril = jnp.tril(jnp.ones((c, c), bool))
    # exp(gc_i - gc_j) for i >= j; masked BEFORE the exp so the upper
    # half (a positive exponent) cannot overflow.
    decay = jnp.exp(jnp.where(
        tril, gc[..., :, None] - gc[..., None, :], -jnp.inf))
    # k_beta k^T and q k^T as ONE product (its rows split again below).
    kq = jnp.einsum("nbhid,nbhjd->nbhij",
                    jnp.concatenate([k_beta, qc], axis=-2), kc,
                    precision=_HI) * jnp.concatenate(
                        [decay, decay], axis=-2)
    lower = jnp.where(jnp.tril(tril, -1), kq[..., :c, :], 0.0)
    tmat = _unit_lower_inverse(lower)
    u = jnp.einsum("nbhij,nbhjd->nbhid", tmat, v_beta, precision=_HI)
    w = jnp.einsum("nbhij,nbhjd->nbhid", tmat,
                   k_beta * jnp.exp(gc)[..., None], precision=_HI)
    qk = jnp.where(tril, kq[..., c:, :], 0.0)
    # w and the decayed q meet the state in one product a chunk.
    wq = jnp.concatenate([w, qc * jnp.exp(gc)[..., None]], axis=-2)
    g_last = gc[..., -1]                           # [N, B, H]
    k_out = kc * jnp.exp(g_last[..., None] - gc)[..., None]

    def body(s, xs):
        u_i, wq_i, qk_i, k_i, gl = xs
        from_s = jnp.einsum("bhik,bhkv->bhiv", wq_i, s, precision=_HI)
        v_new = u_i - from_s[..., :c, :]
        o = from_s[..., c:, :] + jnp.einsum(
            "bhij,bhjv->bhiv", qk_i, v_new, precision=_HI)
        s = s * jnp.exp(gl)[..., None, None] + jnp.einsum(
            "bhik,bhiv->bhkv", k_i, v_new, precision=_HI)
        return s, o

    state, out = jax.lax.scan(
        body, state, (u, wq, qk, k_out, g_last))
    # [N, B, H, C, dv] -> [B, T, H, dv]
    out = jnp.moveaxis(jnp.moveaxis(out, 0, 1), 2, 3)
    out = out.reshape(b, n * c, h, dv)[:, :t]
    return out, pack_state(state)
