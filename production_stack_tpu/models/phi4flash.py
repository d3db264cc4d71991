"""Phi-4-mini-flash decoder (HF ``phi4flash``; the SambaY decoder-hybrid-
decoder of arXiv:2507.06607 with the differential attention of
arXiv:2410.05258): a first half of selective-scan (Mamba-1) layers
alternating with window-attention layers, one full-attention layer whose
keys and values are the ONLY paged rows of the model, and a second half
that caches nothing: gated memory units that read one layer's scan output
alternate with cross-attention layers that read the full layer's keys and
values — functional JAX.

The same shape of module as models/mimo_v2.py (the declarations under "What
the rest of the tree asks of this module", attention through ``attend`` over
whatever ``KVView`` the runner built, parameters stacked BY KIND, a layer's
mixer and its FFN two independent kinds, weights closed over and sliced
where used, a second kind of cache declared as ``StateSpec``s that the
runner owns). ``L`` layers, ``P = L / 4``; every layer is ``x <- x +
mixer(LN(x))``, ``x <- x + W2(silu(g) * u)``, ``[g | u] = LN(x) W1``,
LayerNorm with bias, no position embedding anywhere, a tied head. The mixer
of layer ``l``:

  * ``l`` even, ``l <= L/2``: S6 (ops/selective_scan.py). ``[u | z] = h
    W_in``; ``u <- silu(conv(u) + b)`` (ops/gated_delta.py's helpers);
    ``[delta | B | C] = u W_x``; ``dt = softplus(delta W_dt + b_dt)``; the
    scan over a float32 state ``[N, D_inner]`` a layer a row; ``(y *
    silu(z)) W_out``. Layer ``L/2`` also hands its ``y`` (with the skip,
    BEFORE the gate) to the second half as the memory ``m`` of the same
    token.
  * ``l`` odd, ``l < L/2``: window attention, the token and the
    ``sliding_window - 1`` before it, a sequence's keys and values kept as
    a per-sequence RING in a state slot (ops/attention.py:window_ring_*).
    ``l = L/2 + 1``: full attention, paged. Fused ``W_qkv`` and ``W_o``,
    both with bias. DIFFERENTIAL: heads pair (2p, 2p + 1), a query pair
    reads KV pair ``p // (H / Hkv)``; ``a_j = softmax(q_j k_j^T / sqrt(d))
    [v_1 | v_2]``; ``o = (1 - lambda_init) RMSNorm(a_1 - lambda a_2)``.
  * ``l`` even, ``l >= L/2 + 2``: gated memory unit, ``(m * silu(h W1g))
    W2g``. No state, no cache.
  * ``l`` odd, ``l >= L/2 + 3``: cross attention: ``W_q`` and ``W_o`` only;
    the same differential attention, causal, over the full layer's keys
    and values. No cache of its own.

Differential attention needs no kernel of its own: a KV pair's row is ``[k_1
| k_2]`` / ``[v_1 | v_2]`` (``2 d`` lanes: 128 at the published 64, no
padding), a query head is ``[q_1 | 0]`` or ``[0 | q_2]``
(models/granite_hybrid.py:kv_pack's row with the output's lanes KEPT), so
``attend``, both paged kernels and ``window_ring_attend`` return ``a_1`` and
``a_2`` whole; lambda, the subtraction, the norm and the scale are this
module's arithmetic in float32. The second half needs no cache code: the
full layer returns its K/V to be written once, as every layer does, and
hands the chunk's k, v on; a cross layer calls ``attend`` with them on the
same one-layer view.

tests/reference/phi4flash_ref.py is the plain statement of the same
equations this module is held to.

Device scopes: ``attn_proj`` (norms, projections), ``attn_core`` with the
inner ``s6_conv``, ``s6_step`` / ``s6_chunk``, ``ring_attend`` /
``ring_write`` (a decode step's under ``ring_step``: on a TPU the kernel of
ops/pallas/window_ring.py, its time whole under ``ring_attend``),
``xdec_attend`` (the cross layers' reads of the full layer's rows),
``diff_attn`` (the subtraction and its norm) and ``gmu``; ``ffn``,
``embed``, ``logits``.
"""

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from production_stack_tpu.models import llama
from production_stack_tpu.models.config import (
    CacheSpecs,
    ModelConfig,
    PagedKVSpec,
    StateSpec,
)
from production_stack_tpu.models.llama import compute_logits  # noqa: F401
from production_stack_tpu.models.opt import layer_norm
from production_stack_tpu.ops import gated_delta as gd
from production_stack_tpu.ops import selective_scan as s6
from production_stack_tpu.ops.attention import (
    KVView,
    attend,
    window_ring_attend,
    window_ring_step,
    window_ring_write,
)
from production_stack_tpu.ops.ssd import softplus_inverse

Params = Dict
F32 = jnp.float32

# --- What the rest of the tree asks of this module (see models/llama.py) ----
# HF checkpoint suffix -> (our leaf, transpose?): ASSUMED names (deployment.
# json of phi-4-mini-flash says so). ``attn.Wqkv`` keeps its fused rows (q,
# then k, then v); one suffix names leaves of several kinds (``attn.
# out_proj`` is a self-attention's, a cross-attention's, an S6 mixer's and a
# memory unit's), filed by the layer's kind (``layer_slots``).
HF_LAYER_MAP = {
    "input_layernorm.weight": ("attn_norm", False),
    "input_layernorm.bias": ("attn_norm_b", False),
    "post_attention_layernorm.weight": ("mlp_norm", False),
    "post_attention_layernorm.bias": ("mlp_norm_b", False),
    "mlp.fc1.weight": ("w_in", True),                   # gate | up
    "mlp.fc2.weight": ("w_out", True),
    "attn.Wqkv.weight": ("wqkv", True),
    "attn.Wqkv.bias": ("bqkv", False),
    "attn.Wq.weight": ("wqkv", True),                   # a cross layer's
    "attn.Wq.bias": ("bqkv", False),
    "attn.out_proj.weight": ("wo", True),
    "attn.out_proj.bias": ("bo", False),
    "attn.inner_cross_attn.lambda_q1": ("lambda_q1", False),
    "attn.inner_cross_attn.lambda_k1": ("lambda_k1", False),
    "attn.inner_cross_attn.lambda_q2": ("lambda_q2", False),
    "attn.inner_cross_attn.lambda_k2": ("lambda_k2", False),
    "attn.inner_cross_attn.subln.weight": ("subln", False),
    "attn.in_proj.weight": ("in_proj", True),           # u | z; a unit's W1g
    "attn.conv1d.weight": ("conv_w", True),    # [D, 1, K] -> [K, 1, D]
    "attn.conv1d.bias": ("conv_b", False),
    "attn.x_proj.weight": ("w_x", True),                # delta | B | C
    "attn.dt_proj.weight": ("w_dt", True),
    "attn.dt_proj.bias": ("dt_bias", False),
    "attn.A_log": ("a_log", True),                      # [D, N] -> [N, D]
    "attn.D": ("d_skip", False),
}
HF_TOP_MAP = {
    "model.embed_tokens.weight": ("embed", False),
    "model.final_layernorm.weight": ("final_norm", False),
    "model.final_layernorm.bias": ("final_norm_b", False),
}
# No LoRA on this family yet: a scan layer's projections have no delta path
# (the engine refuses --lora-modules on an empty tuple).
LORA_TARGETS = ()
# ``attn_impl=auto`` may resolve to the Pallas paged decode for the one
# paged layer and its readers (KV pairs as rows of 128 lanes):
# tests/test_phi4flash.py holds the engine's logits on that path to the
# reference.
PAGED_DECODE_VALIDATED = True
# Leaves a checkpoint load keeps in float32 whatever the engine's dtype.
FLOAT32_LEAVES = ("a_log", "d_skip", "dt_bias", "lambda_q1", "lambda_k1",
                  "lambda_q2", "lambda_k2")

_FFN = ("mlp_norm", "mlp_norm_b", "w_in", "w_out")
_NORM = ("attn_norm", "attn_norm_b")
_DIFF = ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2", "subln")
_LEAVES = {                                     # as loaded, by kind
    "ffn": _FFN,
    "s6": _NORM + ("in_proj", "conv_w", "conv_b", "w_x", "w_dt", "dt_bias",
                   "a_log", "d_skip", "wo"),
    "attn": _NORM + ("wqkv", "bqkv", "wo", "bo") + _DIFF,
    "gmu": _NORM + ("in_proj", "wo"),
    "cross": _NORM + ("wqkv", "bqkv", "wo", "bo") + _DIFF,
}


def position_bound(cfg: ModelConfig) -> Optional[int]:
    """None: no position embedding at all."""
    return None


def pairs(cfg: ModelConfig) -> int:
    """``P``: (S6, window) pairs of the first half; the second half is
    ``P - 1`` (memory unit, cross) pairs behind the S6 layer that hands the
    memory on and the full layer."""
    return cfg.num_layers // 4


def layer_kinds(cfg: ModelConfig):
    """Per layer, (its mixer's stack, its index there)."""
    half = cfg.num_layers // 2
    out = []
    for i in range(cfg.num_layers):
        if i <= half:
            out.append(("s6", i // 2) if i % 2 == 0 else ("attn", i // 2))
        elif i == half + 1:
            out.append(("attn", i // 2))
        else:
            at = (i - half - 2) // 2
            out.append(("gmu", at) if i % 2 == 0 else ("cross", at))
    return out


def window_layers(cfg: ModelConfig):
    """The layers that keep a ring (``GET /debug/programs``)."""
    return list(range(1, cfg.num_layers // 2, 2))


def lambda_init(layer) -> jax.Array:
    """``0.8 - 0.6 exp(-0.3 l)`` of the layer's index in the model."""
    return 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(layer, F32))


def kv_rows(cfg: ModelConfig) -> Tuple[int, int]:
    """(KV pairs a token, lanes of a pair's row)."""
    return cfg.num_kv_heads // 2, 2 * cfg.head_dim_


def layer_slots(cfg: ModelConfig):
    """Per layer, {leaf: (stack, index in it)}: a layer's mixer and its FFN
    are filed apart, each under its own kind."""
    return [{**dict.fromkeys(_LEAVES[kind], (kind, at)),
             **dict.fromkeys(_FFN, ("ffn", i))}
            for i, (kind, at) in enumerate(layer_kinds(cfg))]


def required_layer_leaves(cfg: ModelConfig) -> dict:
    """Per kind, the leaves every valid checkpoint must provide."""
    need = {kind: set(leaves) for kind, leaves in _LEAVES.items()}
    if not cfg.mamba_conv_bias:
        need["s6"].discard("conv_b")
    return need


def finish_params(cfg: ModelConfig, params: Params) -> Params:
    """Last step of a checkpoint load: the conv weight loses HF's middle
    axis ([K, 1, D] -> [K, D]) and the tied head reads ``embed``."""
    scan = params["layers"]["s6"]
    if scan["conv_w"].ndim == 4:
        scan["conv_w"] = scan["conv_w"][:, :, 0]
    return llama.finish_params(cfg, params)


def cache_specs(cfg: ModelConfig) -> CacheSpecs:
    """Paged K and V for the ONE full layer (a KV pair a row); per sequence
    the window layers' rings of keys and of values, pair-major ``[Hkv / 2,
    W, 2 d]`` in the activations' dtype; per sequence and S6 layer the
    scan's state ``[N, D_inner]`` in float32 (channels on the lanes) and the
    conv's last ``K - 1`` inputs as rows of 128 lanes
    (models/olmo_hybrid.py:cache_specs says why)."""
    p = pairs(cfg)
    rows, width = kv_rows(cfg)
    conv = (cfg.mamba_d_conv - 1) * cfg.mamba_d_inner
    return CacheSpecs(
        PagedKVSpec(1, rows, width),
        (
            StateSpec("ring_k", p, (rows, cfg.sliding_window, width), None),
            StateSpec("ring_v", p, (rows, cfg.sliding_window, width), None),
            StateSpec("s6", p + 1, (cfg.mamba_d_state, cfg.mamba_d_inner),
                      "float32"),
            StateSpec("conv", p + 1, (conv // 128, 128), None),
        ),
    )


def ring_report(cfg: ModelConfig) -> Dict:
    """What ``GET /version`` and ``GET /debug/programs`` say of this
    module's caches."""
    half = cfg.num_layers // 2
    specs = cache_specs(cfg).state
    return {
        "window_layers": window_layers(cfg),
        "ring": {s.name: list(s.shape) for s in specs[:2]},
        "scan_layers": list(range(0, half + 1, 2)),
        "scan_state": {s.name: list(s.shape) for s in specs[2:]},
        "paged_layer": half + 1,
        "paged_layer_readers": [half + 1,
                                *range(half + 3, cfg.num_layers, 2)],
        "memory_layer": half,
        "memory_readers": list(range(half + 2, cfg.num_layers, 2)),
    }


def init_params(cfg: ModelConfig, rng: jax.Array, dtype=jnp.bfloat16) -> Params:
    d, f, dh = cfg.hidden_size, cfg.intermediate_size, cfg.head_dim_
    h, hkv, v = cfg.num_heads, cfg.num_kv_heads, cfg.vocab_size
    di, n, rank, kw = (cfg.mamba_d_inner, cfg.mamba_d_state, cfg.mamba_dt_rank,
                       cfg.mamba_d_conv)
    nl, p = cfg.num_layers, pairs(cfg)
    keys = iter(jax.random.split(rng, 64))
    # The head is TIED and nothing scales the logits, so the table and the
    # stream are sized together: the table's rows at 2 / sqrt(d) a channel
    # (the logits, the final norm's unit channels against these rows, then
    # spread by about 2; at unit rows by sqrt(d) = 50, every softmax one
    # token), and every projection back into the stream at fan-in scale
    # with NO depth factor, so that 2 L branches of about 0.4 a channel
    # make a stream of about 3 at the published depth. A token's own row
    # then adds |row|^2 / rms(stream) = 4 / 3 to its own logit; with
    # branches at 1 / sqrt(2 L) of that (models/mimo_v2.py) the stream
    # stays the table's size, the token's own logit reads 30 and every
    # answer is its prompt's last token for ever (PERF.md section 6, PR 44
    # and PR 54).
    back = 1.0

    def w(shape, fan_in, dt=dtype, scale=1.0):
        return (jax.random.normal(next(keys), shape, F32)
                * (scale * fan_in ** -0.5)).astype(dt)

    def stack(count, shape, fan_in, scale=1.0):
        # A layer at a time: the float32 draw of a whole stack (6.7 GB for
        # the FFN's first matrix at the published widths) is never alive.
        return jax.lax.map(
            lambda k: (jax.random.normal(k, shape, F32)
                       * (scale * fan_in ** -0.5)).astype(dtype),
            jax.random.split(next(keys), count))

    def norm(count):
        return {"attn_norm": jnp.ones((count, d), dtype),
                "attn_norm_b": jnp.zeros((count, d), dtype)}

    def diff(count, q_cols):
        # lambda_* ~ N(0, 0.1): lambda lies within about 0.1 of
        # lambda_init. Queries and keys at TWICE fan-in scale each: there
        # is no QK norm, so the scores' spread is the projections', and at
        # about 4 attention picks a few tokens instead of averaging them
        # all (PERF.md section 6, PR 44).
        return {
            **norm(count),
            "wqkv": jnp.concatenate(
                [stack(count, (d, cols), d, scale)
                 for cols, scale in q_cols], axis=-1),
            "bqkv": 0.1 * jax.random.normal(
                next(keys), (count, sum(c for c, _ in q_cols)),
                F32).astype(dtype),
            "wo": stack(count, (h * dh, d), h * dh, back),
            "bo": 0.02 * jax.random.normal(
                next(keys), (count, d), F32).astype(dtype),
            **{name: 0.1 * jax.random.normal(next(keys), (count, dh), F32)
               for name in _DIFF[:4]},
            "subln": jnp.ones((count, 2 * dh), dtype),
        }

    scan = {
        **norm(p + 1),
        "in_proj": stack(p + 1, (d, 2 * di), d),
        "conv_w": w((p + 1, kw, di), kw),
        "conv_b": w((p + 1, di), 1.0, scale=0.5),
        "w_x": w((p + 1, di, rank + 2 * n), di),
        "w_dt": w((p + 1, rank, di), rank),
        # As Mamba-1 initialises: A[n, c] = n + 1, dt log-uniform in
        # [1e-3, 1e-1] through the inverse of softplus; the decay a token
        # then spreads over (0, 1). D = U(0.5, 1.5): a comparison that
        # drops the skip fails.
        "a_log": jnp.broadcast_to(
            jnp.log(jnp.arange(1, n + 1, dtype=F32))[None, :, None],
            (p + 1, n, di)),
        "dt_bias": softplus_inverse(jnp.exp(jax.random.uniform(
            next(keys), (p + 1, di), F32, math.log(1e-3), math.log(1e-1)))),
        "d_skip": jax.random.uniform(next(keys), (p + 1, di), F32, 0.5, 1.5),
        "wo": stack(p + 1, (di, d), di, back),
    }
    if not cfg.mamba_conv_bias:
        del scan["conv_b"]
    if v % 8 == 0:
        embed = stack(8, (v // 8, d), d, 2.0).reshape(v, d)
    else:
        embed = w((v, d), d, scale=2.0)
    return {
        "embed": embed,
        "layers": {
            "ffn": {
                "mlp_norm": jnp.ones((nl, d), dtype),
                "mlp_norm_b": jnp.zeros((nl, d), dtype),
                "w_in": stack(nl, (d, 2 * f), d),
                "w_out": stack(nl, (f, d), f, back),
            },
            "s6": scan,
            "attn": diff(p + 1, ((h * dh, 2.0), (hkv * dh, 2.0),
                                 (hkv * dh, 1.0))),
            "gmu": {**norm(p - 1),
                    "in_proj": stack(p - 1, (d, di), d),
                    "wo": stack(p - 1, (di, d), di, back)},
            "cross": diff(p - 1, ((h * dh, 2.0),)),
        },
        "final_norm": jnp.ones((d,), dtype),
        "final_norm_b": jnp.zeros((d,), dtype),
    }


def _ffn(cfg: ModelConfig, hidden: jax.Array, lp: Dict) -> jax.Array:
    with jax.named_scope("ffn"):
        x = layer_norm(hidden, lp["mlp_norm"], lp["mlp_norm_b"],
                       cfg.rms_norm_eps)
        gate, up = jnp.split(x @ lp["w_in"], 2, axis=-1)
        return hidden + (jax.nn.silu(gate) * up) @ lp["w_out"]


def pack_queries(q: jax.Array) -> jax.Array:
    """Query heads [B, T, H, d] as rows of a KV pair's width [B, T, H, 2 d]:
    head 2p is ``[q | 0]`` (it scores the pair's first keys), head 2p + 1
    ``[0 | q]``; either's output is then its softmax over ``[v_1 | v_2]``
    whole."""
    b, t, h, dh = q.shape
    own = jnp.eye(2, dtype=q.dtype)
    q = q.reshape(b, t, h // 2, 2, 1, dh) * own[:, :, None]
    return q.reshape(b, t, h, 2 * dh)


def differential(cfg: ModelConfig, attn: jax.Array, lp: Dict, layer
                 ) -> jax.Array:
    """``(1 - lambda_init) RMSNorm(a_1 - lambda a_2)`` of the heads'
    outputs [B, T, H, 2 d] (head 2p: a_1 of pair p, head 2p + 1: a_2), in
    float32: [B, T, H d] for ``W_o``."""
    with jax.named_scope("diff_attn"):
        b, t, h, width = attn.shape
        a = attn.astype(F32).reshape(b, t, h // 2, 2, width)
        init = lambda_init(layer)
        lam = jnp.exp(jnp.sum(lp["lambda_q1"].astype(F32)
                              * lp["lambda_k1"].astype(F32))) \
            - jnp.exp(jnp.sum(lp["lambda_q2"].astype(F32)
                              * lp["lambda_k2"].astype(F32))) + init
        o = a[..., 0, :] - lam * a[..., 1, :]
        var = jnp.mean(o * o, axis=-1, keepdims=True)
        o = o * jax.lax.rsqrt(var + cfg.rms_norm_eps) \
            * lp["subln"].astype(F32) * (1.0 - init)
        return o.reshape(b, t, h * width // 2).astype(attn.dtype)


def _project(cfg, hidden, lp):
    """The packed queries [B, T, H, 2 d] of one attention layer and, where
    its matrix has their columns (a self-attention layer's ``W_qkv``; a
    cross layer's is ``W_q``), its tokens' key and value rows [B, T, Hkv /
    2, 2 d], else None."""
    b, t, _ = hidden.shape
    h, dh = cfg.num_heads, cfg.head_dim_
    rows, width = kv_rows(cfg)
    with jax.named_scope("attn_proj"):
        x = layer_norm(hidden, lp["attn_norm"], lp["attn_norm_b"],
                       cfg.rms_norm_eps)
        # The product as it is written: without the barrier XLA folds the
        # split into heads into the product and lays the layer's matrix
        # out again a dispatch (models/mimo_v2.py:_project).
        qkv = jax.lax.optimization_barrier(x @ lp["wqkv"] + lp["bqkv"])
        q = pack_queries(qkv[..., :h * dh].reshape(b, t, h, dh))
        if qkv.shape[-1] == h * dh:
            return q, None, None
        k = qkv[..., h * dh:(h + 2 * rows) * dh].reshape(b, t, rows, width)
        v = qkv[..., (h + 2 * rows) * dh:].reshape(b, t, rows, width)
    return q, k, v


def _attn_out(cfg, hidden, attn, lp, layer):
    o = differential(cfg, attn, lp, layer)
    with jax.named_scope("attn_proj"):
        return hidden + (o @ lp["wo"] + lp["bo"])


def _scan_layer(cfg, chunk_lens, hidden, lp, state, conv, at, interpret):
    """One S6 layer over [B, T] tokens from (state: the scan's [B, N, D]
    f32, conv [B, *its spec's shape]); returns (hidden after the mixer, y
    [B, T, D] f32: the scan's output with its skip and BEFORE its gate,
    state, conv) after each row's ``chunk_lens`` valid tokens. A decode
    step (T == 1) takes and returns as ``state`` the rows' WHOLE carried
    state [B, P + 1, N, D], of which layer ``at`` is stepped where it lies
    (ops/selective_scan.py:s6_step_at)."""
    b, t, _ = hidden.shape
    di, n = cfg.mamba_d_inner, cfg.mamba_d_state
    conv_shape = conv.shape
    conv = conv.reshape(b, cfg.mamba_d_conv - 1, di)
    decode = t == 1
    live = chunk_lens > 0
    with jax.named_scope("attn_proj"):
        x = layer_norm(hidden, lp["attn_norm"], lp["attn_norm_b"],
                       cfg.rms_norm_eps)
        uz = x @ lp["in_proj"]
        u, z = uz[..., :di], uz[..., di:]
    with jax.named_scope("attn_core"):
        with jax.named_scope("s6_conv"):
            bias = lp.get("conv_b")
            if decode:
                u, conv = gd.conv_step(u[:, 0], conv, lp["conv_w"], live,
                                       bias)
                u = u[:, None]
            else:
                u, conv = gd.conv_chunk(u, conv, lp["conv_w"], chunk_lens,
                                        bias)
            u = u.astype(F32)
        a = -jnp.exp(lp["a_log"].astype(F32))
        if decode:
            with jax.named_scope("s6_step"):
                dt, bm, cm = s6.gates(u[:, 0], lp["w_x"], lp["w_dt"],
                                      lp["dt_bias"], n)
            y, state = s6.s6_step_at(state, at, u[:, 0], dt, a, bm, cm,
                                     lp["d_skip"], live)
            y = y[:, None]
        else:
            with jax.named_scope("s6_chunk"):
                dt, bm, cm = s6.gates(u, lp["w_x"], lp["w_dt"],
                                      lp["dt_bias"], n)
            y, state = s6.s6_chunk(state, u, dt, a, bm, cm, lp["d_skip"],
                                   chunk_lens, interpret=interpret)
        gated = (y * jax.nn.silu(z.astype(F32))).astype(hidden.dtype)
    with jax.named_scope("attn_proj"):
        hidden = hidden + gated @ lp["wo"]
    return hidden, y, state, conv.reshape(conv_shape)


def forward(
    params: Params,
    cfg: ModelConfig,
    token_ids: jax.Array,     # [B, T]
    positions: jax.Array,     # [B, T]
    chunk_lens: jax.Array,    # [B] valid tokens per row (0: the row is inert)
    view: KVView = KVView(),  # the K/V of the ONE full layer
    *,
    state: Optional[Tuple[jax.Array, ...]] = None,
    act_sharding=None,        # sequence parallelism: refused for this family
    lora=None,                # LORA_TARGETS is empty
) -> Tuple[jax.Array, jax.Array, jax.Array, Tuple[jax.Array, ...]]:
    """Returns (hidden [B,T,D], k_new [1, Hkv/2, B, T, 2d], v_new, state).

    ``state``: the rows' (ring keys [B, P, Hkv/2, W, 2d], ring values, scan
    state [B, P + 1, N, D_inner] f32, conv inputs [B, P + 1, *its spec's
    shape]) before the first token, one array per spec of ``cache_specs``,
    rows first as the runner's pools are; ``None`` starts every row from
    zeros (a whole sequence in one call: then ``positions`` start at 0). The
    returned state is that after each row's last valid token. The view's
    layer axis has the full layer only. A row's ``positions`` are
    consecutive from its first."""
    b, t = token_ids.shape
    p, half = pairs(cfg), cfg.num_layers // 2
    decode = t == 1
    scale = cfg.head_dim_ ** -0.5
    with jax.named_scope("embed"):
        hidden = params["embed"][token_ids]
        hidden = hidden.astype(view.act_dtype(params["embed"].dtype))
    if state is None:
        state = tuple(
            jnp.zeros((b, s.layers, *s.stored), s.dtype or hidden.dtype)
            for s in cache_specs(cfg).state)
    ring_k, ring_v, scan_all, conv_all = state
    layers = params["layers"]

    def layer_of(stack, at):
        # One layer of a stack, sliced where it is used (olmo_hybrid.py).
        return jax.tree.map(
            lambda x: jax.lax.dynamic_index_in_dim(x, at, 0, False), stack)

    def ffn(hidden, layer):
        return _ffn(cfg, hidden, layer_of(layers["ffn"], layer))

    def scan_layer(hidden, scan_all, conv_all, at):
        """S6 layer ``at`` of the stack (layer 2 ``at`` of the model): a
        prefill chunk's layer state is taken out of the rows' carried state
        and put back; a decode step hands the carry itself to
        ``s6_step_at``."""
        inner = "s6_step" if decode else "s6_chunk"
        with jax.named_scope("attn_core"), jax.named_scope(inner):
            own = scan_all if decode else \
                jax.lax.dynamic_index_in_dim(scan_all, at, 1, False)
            conv = jax.lax.dynamic_index_in_dim(conv_all, at, 1, False)
        hidden, y, own, conv = _scan_layer(
            cfg, chunk_lens, hidden, layer_of(layers["s6"], at), own, conv,
            at, view.interpret)
        with jax.named_scope("attn_core"), jax.named_scope(inner):
            scan_all = own if decode else \
                jax.lax.dynamic_update_index_in_dim(scan_all, own, at, 1)
            conv_all = jax.lax.dynamic_update_index_in_dim(
                conv_all, conv.astype(conv_all.dtype), at, 1)
        return ffn(hidden, 2 * at), y, scan_all, conv_all

    def window_layer(hidden, rings, at):
        lp = layer_of(layers["attn"], at)
        q, k, v = _project(cfg, hidden, lp)
        with jax.named_scope("attn_core"):
            if decode:
                attn, rings = window_ring_step(
                    rings, at, q, k, v, positions, chunk_lens, scale=scale,
                    interpret=view.interpret)
            else:
                with jax.named_scope("ring_attend"):
                    ring = tuple(
                        jax.lax.dynamic_index_in_dim(r, at, 1, False)
                        for r in rings)
                attn = window_ring_attend(q, k, v, positions, chunk_lens,
                                          *ring, scale=scale)
                rings = window_ring_write(rings, at, (k, v), positions,
                                          chunk_lens)
        hidden = _attn_out(cfg, hidden, attn, lp, 2 * at + 1)
        return ffn(hidden, 2 * at + 1), rings

    def first_half(carry, at):
        hidden, ring_k, ring_v, scan_all, conv_all = carry
        hidden, _, scan_all, conv_all = scan_layer(
            hidden, scan_all, conv_all, at)
        hidden, (ring_k, ring_v) = window_layer(hidden, (ring_k, ring_v), at)
        return (hidden, ring_k, ring_v, scan_all, conv_all), None

    (hidden, ring_k, ring_v, scan_all, conv_all), _ = jax.lax.scan(
        first_half, (hidden, ring_k, ring_v, scan_all, conv_all),
        jnp.arange(p, dtype=jnp.int32))

    # Layer L/2: the S6 layer whose scan output is the second half's memory.
    hidden, memory, scan_all, conv_all = scan_layer(
        hidden, scan_all, conv_all, jnp.int32(p))

    # Layer L/2 + 1: full attention, the model's one paged layer. Its view
    # is the runner's with the layer axis taken off the gathered parts; the
    # pool keeps it and is indexed by ``layer`` 0.
    def first(x):
        return None if x is None else x[0]

    own_view = view._replace(win_k=first(view.win_k), win_v=first(view.win_v),
                             ring_k=first(view.ring_k),
                             ring_v=first(view.ring_v))
    pool_layer = jnp.int32(0) if view.pool_k is not None else None
    lp = layer_of(layers["attn"], jnp.int32(p))
    q, k_full, v_full = _project(cfg, hidden, lp)
    with jax.named_scope("attn_core"):
        attn = attend(q, k_full, v_full, positions, chunk_lens, own_view,
                      pool_layer, scale=scale)
    hidden = ffn(_attn_out(cfg, hidden, attn, lp, half + 1), half + 1)

    def second_half(hidden, at):
        layer = half + 2 + 2 * at
        # A gated memory unit: the memory of the SAME token, gated by this
        # layer's own projection of the stream.
        lp = layer_of(layers["gmu"], at)
        with jax.named_scope("attn_core"), jax.named_scope("gmu"):
            x = layer_norm(hidden, lp["attn_norm"], lp["attn_norm_b"],
                           cfg.rms_norm_eps)
            gate = jax.nn.silu((x @ lp["in_proj"]).astype(F32))
            unit = (memory * gate).astype(hidden.dtype) @ lp["wo"]
        hidden = ffn(hidden + unit, layer)
        # A cross layer: its own queries over the full layer's keys and
        # values, through the same view.
        lp = layer_of(layers["cross"], at)
        q, _, _ = _project(cfg, hidden, lp)
        with jax.named_scope("attn_core"), jax.named_scope("xdec_attend"):
            attn = attend(q, k_full, v_full, positions, chunk_lens,
                          own_view, pool_layer, scale=scale)
        hidden = _attn_out(cfg, hidden, attn, lp, layer + 1)
        return ffn(hidden, layer + 1), None

    hidden, _ = jax.lax.scan(second_half, hidden,
                             jnp.arange(p - 1, dtype=jnp.int32))
    hidden = layer_norm(hidden, params["final_norm"], params["final_norm_b"],
                        cfg.rms_norm_eps)
    return (hidden, k_full.transpose(2, 0, 1, 3)[None],
            v_full.transpose(2, 0, 1, 3)[None],
            (ring_k, ring_v, scan_all, conv_all))
