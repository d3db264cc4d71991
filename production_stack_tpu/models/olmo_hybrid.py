"""Olmo-Hybrid decoder: periods of Gated DeltaNet (linear-attention) layers
closed by one full-attention layer — functional JAX.

The same shape of module as models/llama.py (the declarations under "What
the rest of the tree asks of this module", attention through ``attend``
over whatever ``KVView`` the runner built, the FFN, ``rms_norm`` and
``apply_rope`` imported from there), with two things of its own:

  * Two kinds of layer (a linear layer's q, k and v projections are one
    matrix, ``lin_qkv``; a checkpoint's three are joined when it is
    loaded). Parameters are stacked BY KIND (``layers.linear``
    [n_linear, ...], ``layers.full`` [n_full, ...]); the forward traces one
    PERIOD (``cfg.layer_types``' repeating pattern: some linear layers, a
    scan of their own, then one full layer) and ``lax.scan``s it over the
    periods, so compile time and program size stay flat in depth like
    llama's layer scan.
  * A second kind of cache. A linear layer keeps, per sequence, the
    recurrence's state ``S`` [H, dk, dv] in float32 and the last W - 1
    inputs of its causal convolution (ops/gated_delta.py). ``cache_specs``
    declares both beside the paged K/V of the full layers; the runner owns
    the pools, hands this forward the rows' state (``state=``: one array
    per declared spec, [B, n_linear, ...]) and writes back what it returns.
    ``chunk_lens`` says how many tokens of each row count: a row with none
    (a padded row, a decode step past the row's budget) leaves its state
    as it was.

Block equations (Olmo's reordered norm): ``h = x + norm(mixer(x))``,
``y = h + norm(ffn(h))``, no norm before a sub-layer, a final norm before
the untied head. Full layer: ``q, k = norm_q(W_q x), norm_k(W_k x)`` over
the whole projection, rotary only where ``cfg.rope_theta`` is a number
(``None``, as published: no rotary embedding), causal softmax attention.
Linear layer: see ops/gated_delta.py; ``beta`` is doubled where
``cfg.linear_allow_neg_eigval``. tests/reference/olmo_hybrid_ref.py is the
plain statement of the same equations this module is held to.

Shared with models/granite_hybrid.py (Mamba-2 layers where these are Gated
DeltaNet): the causal depthwise convolution (ops/gated_delta.py:conv_step /
conv_chunk; there with a bias), the period helper
(models/config.py:layer_period; there the full layer may stand anywhere in
its period, here it closes it), the ``StateSpec`` slots and the conventions
of this forward (stacks by kind, weights closed over and sliced where used,
a decode step's layer stepped in place in the rows' carried state).

Device scopes: the six in-projections and both out-projections under
``attn_proj``, attention and the recurrence under ``attn_core`` (the
recurrence with an inner ``gdn_step`` / ``gdn_chunk``), ``ffn``, ``embed``,
``logits``.
"""

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from production_stack_tpu.models.config import (
    CacheSpecs,
    ModelConfig,
    PagedKVSpec,
    StateSpec,
    layer_period,
)
from production_stack_tpu.models.llama import (
    _rope_cos_sin,
    apply_rope,
    compute_logits,  # noqa: F401 — the untied head is llama's
    rms_norm,
)
from production_stack_tpu.ops import gated_delta as gd
from production_stack_tpu.ops.attention import KVView, attend

Params = Dict

# --- What the rest of the tree asks of this module (see models/llama.py) ----
# HF checkpoint suffix -> (our leaf, transpose?). The leaves of the two kinds
# share the FFN and norm names; models/weights.py files a layer's tensors
# under its kind (``layer_slots``). The linear-attention names are those of
# HF's Qwen3NextGatedDeltaNet with the projections unfused.
HF_LAYER_MAP = {
    "self_attn.q_proj.weight": ("wq", True),
    "self_attn.k_proj.weight": ("wk", True),
    "self_attn.v_proj.weight": ("wv", True),
    "self_attn.o_proj.weight": ("wo", True),
    "self_attn.q_norm.weight": ("q_norm", False),
    "self_attn.k_norm.weight": ("k_norm", False),
    "linear_attn.q_proj.weight": ("lin_q", True),
    "linear_attn.k_proj.weight": ("lin_k", True),
    "linear_attn.v_proj.weight": ("lin_v", True),
    "linear_attn.z_proj.weight": ("lin_z", True),
    "linear_attn.b_proj.weight": ("lin_b", True),
    "linear_attn.a_proj.weight": ("lin_a", True),
    "linear_attn.conv1d.weight": ("conv_w", True),   # [C, 1, W] -> [W, 1, C]
    "linear_attn.A_log": ("a_log", False),
    "linear_attn.dt_bias": ("dt_bias", False),
    "linear_attn.norm.weight": ("gate_norm", False),
    "linear_attn.out_proj.weight": ("lin_o", True),
    "mlp.gate_proj.weight": ("w_gate", True),
    "mlp.up_proj.weight": ("w_up", True),
    "mlp.down_proj.weight": ("w_down", True),
    "post_attention_layernorm.weight": ("attn_norm", False),
    "post_feedforward_layernorm.weight": ("mlp_norm", False),
}
HF_TOP_MAP = {
    "model.embed_tokens.weight": ("embed", False),
    "model.norm.weight": ("final_norm", False),
    "lm_head.weight": ("lm_head", True),
}
# No LoRA on this family yet: the six projections of a linear layer have no
# delta path (the engine refuses --lora-modules on an empty tuple).
LORA_TARGETS = ()
# ``attn_impl=auto`` may resolve to the Pallas paged decode for the full
# layers: tests/test_olmo_hybrid.py holds the engine's logits on that path
# to the reference.
PAGED_DECODE_VALIDATED = True

_FFN = ("w_gate", "w_up", "w_down", "attn_norm", "mlp_norm")
_FULL = ("wq", "wk", "wv", "wo", "q_norm", "k_norm") + _FFN
_LINEAR = ("lin_q", "lin_k", "lin_v", "lin_z", "lin_b", "lin_a", "conv_w",
           "a_log", "dt_bias", "gate_norm", "lin_o") + _FFN   # as loaded
_KIND = {"linear_attention": "linear", "full_attention": "full"}


def position_bound(cfg: ModelConfig) -> Optional[int]:
    """None: no position table (rotary or no embedding at all)."""
    return None


def layer_slots(cfg: ModelConfig):
    """(kind, index within the kind's stack) of every layer, in order."""
    seen = {"linear": 0, "full": 0}
    out = []
    for t in cfg.layer_types:
        out.append((_KIND[t], seen[_KIND[t]]))
        seen[_KIND[t]] += 1
    return out


def required_layer_leaves(cfg: ModelConfig) -> dict:
    """Per kind, the leaves every valid checkpoint must provide."""
    return {"linear": set(_LINEAR), "full": set(_FULL)}


def finish_params(cfg: ModelConfig, params: Params) -> Params:
    """Last step of a checkpoint load: the conv weight loses HF's middle
    axis ([W, 1, C] -> [W, C]), and the q, k and v projections become the
    one matrix ``lin_qkv`` the forward multiplies by (columns in the
    order of the convolution's channels)."""
    lin = params["layers"]["linear"]
    if lin["conv_w"].ndim == 4:
        lin["conv_w"] = lin["conv_w"][:, :, 0]
    if "lin_qkv" not in lin:
        lin["lin_qkv"] = jnp.concatenate(
            [lin.pop("lin_q"), lin.pop("lin_k"), lin.pop("lin_v")], axis=-1)
    return params


def _counts(cfg: ModelConfig) -> Tuple[int, int]:
    n_lin = sum(t == "linear_attention" for t in cfg.layer_types)
    return n_lin, cfg.num_layers - n_lin


def _conv_channels(cfg: ModelConfig) -> int:
    return cfg.linear_num_heads * (
        2 * cfg.linear_key_head_dim + cfg.linear_value_head_dim)


def cache_specs(cfg: ModelConfig) -> CacheSpecs:
    """Paged K/V for the full layers only; per sequence and linear layer
    the recurrence's state (float32 whatever the activations, heads packed
    to whole lanes: ops/gated_delta.py) and the conv state, its W - 1
    tokens of C channels as rows of 128 lanes where they divide (a layer's
    slab is then whole tiles and contiguous; with the channels flat on one
    axis the pool's LAYER axis became the tiled one, and the compiler for a
    v5e re-laid the whole pool out every dispatch to read it)."""
    n_lin, n_full = _counts(cfg)
    conv = (cfg.linear_conv_kernel_dim - 1) * _conv_channels(cfg)
    return CacheSpecs(
        PagedKVSpec(n_full, cfg.num_kv_heads, cfg.head_dim_),
        (
            StateSpec("recurrent", n_lin,
                      gd.packed_shape(cfg.linear_num_heads,
                                      cfg.linear_key_head_dim,
                                      cfg.linear_value_head_dim), "float32"),
            StateSpec("conv", n_lin,
                      (conv // 128, 128) if conv % 128 == 0 else (conv,),
                      None),
        ),
    )


def init_params(cfg: ModelConfig, rng: jax.Array, dtype=jnp.bfloat16) -> Params:
    d, f, dh = cfg.hidden_size, cfg.intermediate_size, cfg.head_dim_
    h, hkv, v = cfg.num_heads, cfg.num_kv_heads, cfg.vocab_size
    lh, dk, dv = (cfg.linear_num_heads, cfg.linear_key_head_dim,
                  cfg.linear_value_head_dim)
    n_lin, n_full = _counts(cfg)
    keys = iter(jax.random.split(rng, 24))

    def w(shape, fan_in):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * fan_in ** -0.5).astype(dtype)

    def ffn(n):
        return {
            "w_gate": w((n, d, f), d), "w_up": w((n, d, f), d),
            "w_down": w((n, f, d), f),
            "attn_norm": jnp.ones((n, d), dtype),
            "mlp_norm": jnp.ones((n, d), dtype),
        }

    linear = {
        # q, k and v as ONE matrix [D, H*dk + H*dk + H*dv], columns in the
        # order of the convolution's channels: one product whose width is
        # whole lanes (H*dk = 2880 alone is not).
        "lin_qkv": w((n_lin, d, _conv_channels(cfg)), d),
        "lin_z": w((n_lin, d, lh * dv), d),
        "lin_b": w((n_lin, d, lh), d), "lin_a": w((n_lin, d, lh), d),
        "conv_w": w((n_lin, cfg.linear_conv_kernel_dim, _conv_channels(cfg)),
                    cfg.linear_conv_kernel_dim),
        # A = U(0, 16), dt_bias = 1: the decay per token then lies between
        # about exp(-21) and 1, the published recipe's range.
        "a_log": jnp.log(jax.random.uniform(
            next(keys), (n_lin, lh), jnp.float32, 1e-3, 16.0)),
        "dt_bias": jnp.ones((n_lin, lh), jnp.float32),
        "gate_norm": jnp.ones((n_lin, dv), dtype),
        "lin_o": w((n_lin, lh * dv, d), lh * dv),
        **ffn(n_lin),
    }
    full = {
        "wq": w((n_full, d, h * dh), d), "wk": w((n_full, d, hkv * dh), d),
        "wv": w((n_full, d, hkv * dh), d), "wo": w((n_full, h * dh, d), h * dh),
        "q_norm": jnp.ones((n_full, h * dh), dtype),
        "k_norm": jnp.ones((n_full, hkv * dh), dtype),
        **ffn(n_full),
    }
    return {
        "embed": w((v, d), d),
        "layers": {"linear": linear, "full": full},
        "final_norm": jnp.ones((d,), dtype),
        "lm_head": w((d, v), d),
    }


def _ffn_block(cfg: ModelConfig, hidden: jax.Array, lp: Dict) -> jax.Array:
    with jax.named_scope("ffn"):
        gated = jax.nn.silu(hidden @ lp["w_gate"]) * (hidden @ lp["w_up"])
        return hidden + rms_norm(gated @ lp["w_down"], lp["mlp_norm"],
                                 cfg.rms_norm_eps)


def _full_layer(cfg, rope, positions, chunk_lens, hidden, lp, view, layer):
    b, t, _ = hidden.shape
    h, hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    with jax.named_scope("attn_proj"):
        q = rms_norm(hidden @ lp["wq"], lp["q_norm"], cfg.rms_norm_eps)
        k = rms_norm(hidden @ lp["wk"], lp["k_norm"], cfg.rms_norm_eps)
        v = hidden @ lp["wv"]
        q = q.reshape(b, t, h, dh)
        k = k.reshape(b, t, hkv, dh)
        v = v.reshape(b, t, hkv, dh)
        if rope is not None:
            q = apply_rope(q, *rope)
            k = apply_rope(k, *rope)
    with jax.named_scope("attn_core"):
        attn = attend(q, k, v, positions, chunk_lens, view, layer)
    with jax.named_scope("attn_proj"):
        hidden = hidden + rms_norm(
            attn.reshape(b, t, h * dh) @ lp["wo"], lp["attn_norm"],
            cfg.rms_norm_eps)
    # New KV in pool layout [Hkv, B, T, Dh], as llama's layer returns it.
    return (_ffn_block(cfg, hidden, lp), k.transpose(2, 0, 1, 3),
            v.transpose(2, 0, 1, 3))


def _linear_layer(cfg, chunk_lens, hidden, lp, rec, conv, at, interpret):
    """One Gated DeltaNet layer over [B, T] tokens from (rec: the packed
    state [B, H/P, dk, P*dv] f32, conv [B, *its spec's shape]); returns
    (hidden, rec, conv) after each row's ``chunk_lens`` valid tokens. A
    decode step (T == 1) takes and returns as ``rec`` the rows' WHOLE
    carried state [B, n_linear, H/P, dk, P*dv], of which layer ``at`` is
    stepped where it lies (ops/gated_delta.py:gdn_step_at)."""
    b, t, _ = hidden.shape
    conv_shape = conv.shape
    conv = conv.reshape(b, cfg.linear_conv_kernel_dim - 1, -1)
    lh, dk, dv = (cfg.linear_num_heads, cfg.linear_key_head_dim,
                  cfg.linear_value_head_dim)
    with jax.named_scope("attn_proj"):
        qkv = hidden @ lp["lin_qkv"]                           # [B, T, C]
        z = hidden @ lp["lin_z"]
        beta, g = gd.gates(hidden @ lp["lin_b"], hidden @ lp["lin_a"],
                           lp["a_log"], lp["dt_bias"],
                           cfg.linear_allow_neg_eigval)        # [B, T, H]
    with jax.named_scope("attn_core"):
        if t == 1:
            live = chunk_lens > 0
            y, conv = gd.conv_step(qkv[:, 0], conv, lp["conv_w"], live)
            y = y[:, None]
        else:
            y, conv = gd.conv_chunk(qkv, conv, lp["conv_w"], chunk_lens)
        q, k, v = gd.prepare(
            y[..., :lh * dk].reshape(b, t, lh, dk),
            y[..., lh * dk:2 * lh * dk].reshape(b, t, lh, dk),
            y[..., 2 * lh * dk:].reshape(b, t, lh, dv))
        if t == 1:
            o, rec = gd.gdn_step_at(rec, at, q[:, 0], k[:, 0], v[:, 0],
                                    g[:, 0], beta[:, 0], live,
                                    interpret=interpret)
            o = o[:, None]
        else:
            o, rec = gd.gdn_chunk(rec, q, k, v, g, beta, chunk_lens,
                                  interpret=interpret)
        # Per head: RMSNorm over dv, gated by silu(z); float32 until the
        # out-projection's operand. z stays flat (dv alone is not whole
        # lanes: a reshape of it reaches back to its matrix's layout).
        o = rms_norm(o, lp["gate_norm"].astype(jnp.float32),
                     cfg.rms_norm_eps).reshape(b, t, lh * dv)
        o = (o * jax.nn.silu(z.astype(jnp.float32))).astype(hidden.dtype)
    with jax.named_scope("attn_proj"):
        hidden = hidden + rms_norm(
            o @ lp["lin_o"], lp["attn_norm"], cfg.rms_norm_eps)
    return _ffn_block(cfg, hidden, lp), rec, conv.reshape(conv_shape)


def forward(
    params: Params,
    cfg: ModelConfig,
    token_ids: jax.Array,     # [B, T]
    positions: jax.Array,     # [B, T]
    chunk_lens: jax.Array,    # [B] valid tokens per row (0: the row is inert)
    view: KVView = KVView(),  # the K/V of the FULL layers this forward may read
    *,
    state: Optional[Tuple[jax.Array, jax.Array]] = None,
    act_sharding=None,        # sequence parallelism: refused for this family
    lora=None,                # LORA_TARGETS is empty
) -> Tuple[jax.Array, jax.Array, jax.Array, Tuple[jax.Array, jax.Array]]:
    """Returns (hidden [B,T,D], k_new [n_full,Hkv,B,T,Dh], v_new, state).

    ``state``: the rows' (recurrent [B, n_linear, H/P, dk, P*dv] f32, conv
    [B, n_linear, *its spec's shape]) before the first token, one array
    per spec of ``cache_specs``, rows first as the runner's pools are;
    ``None`` starts every row from zeros (a whole sequence in one call).
    The returned state is that after each row's last valid token. The
    view's layer axis counts the full layers only.
    """
    # A period is its linear layers, then the one full layer closing it.
    lin_per = len(layer_period(cfg.layer_types, cfg.num_layers)) - 1
    n_periods = cfg.num_layers // (lin_per + 1)
    b = token_ids.shape[0]
    with jax.named_scope("embed"):
        hidden = params["embed"][token_ids]
        hidden = hidden.astype(view.act_dtype(params["embed"].dtype))
    if state is None:
        state = tuple(
            jnp.zeros((b, s.layers, *s.shape), s.dtype or hidden.dtype)
            for s in cache_specs(cfg).state)
    rope = None if cfg.rope_theta is None else _rope_cos_sin(
        positions, cfg.head_dim_, cfg.rope_theta)
    layers = params["layers"]

    def layer_of(stack, at):
        # One layer of a stack, sliced where it is used: the weights are
        # closed over, never a scan operand, so a layer's matrices reach
        # their products as slices of the stack and are not copied out
        # (a period's worth of them is 0.8 GB at the published widths).
        return jax.tree.map(
            lambda x: jax.lax.dynamic_index_in_dim(x, at, 0, False), stack)

    # The recurrence's own scope. A prefill chunk's layer state is taken out
    # of the rows' carried state and put back under it too (``gdn_chunk``
    # takes one layer's state and returns it in the same buffer; XLA names
    # the update's fusion after the scope). What ``gdn_chunk`` leaves in
    # ``o`` past a row's ``chunk_lens`` is never read: everything after it
    # is token-wise or masks by ``chunk_lens`` (the conv state, the gates,
    # attention's keys), and the runner takes a row's logits at
    # ``chunk_lens - 1`` and writes K/V below ``chunk_lens`` only
    # (engine/runner.py:_prefill_impl). A decode step hands the carry
    # itself to ``gdn_step_at``, which steps its layer ``at`` in place: no
    # layer of it is ever sliced out. The scope's time covers the read and
    # the write the state's bytes count either way
    # (benchmarks/chip/lib/shapes_hybrid.py).
    decode = token_ids.shape[1] == 1
    inner = "gdn_step" if decode else "gdn_chunk"

    def linear_step(carry, at):
        hidden, rec_all, conv_all = carry
        with jax.named_scope("attn_core"), jax.named_scope(inner):
            rec = rec_all if decode else \
                jax.lax.dynamic_index_in_dim(rec_all, at, 1, False)
            conv = jax.lax.dynamic_index_in_dim(conv_all, at, 1, False)
        hidden, rec, conv = _linear_layer(
            cfg, chunk_lens, hidden, layer_of(layers["linear"], at),
            rec, conv, at, view.interpret)
        with jax.named_scope("attn_core"), jax.named_scope(inner):
            rec_all = rec if decode else \
                jax.lax.dynamic_update_index_in_dim(rec_all, rec, at, 1)
            conv_all = jax.lax.dynamic_update_index_in_dim(
                conv_all, conv.astype(conv_all.dtype), at, 1)
        return (hidden, rec_all, conv_all), None

    def step(carry, xs):
        # One period: its linear layers (a scan of their own, so that a
        # program holds ONE linear layer's code and not a period's worth:
        # the compiled programs of a deployment have to fit a compile
        # cache's size cap together), then the full layer that closes it.
        win_k, win_v, ring_k, ring_v, p = xs
        (hidden, rec_all, conv_all), _ = jax.lax.scan(
            linear_step, carry,
            p * lin_per + jnp.arange(lin_per, dtype=jnp.int32))
        hidden, k_l, v_l = _full_layer(
            cfg, rope, positions, chunk_lens, hidden,
            layer_of(layers["full"], p),
            view._replace(win_k=win_k, win_v=win_v, ring_k=ring_k,
                          ring_v=ring_v),
            p if view.pool_k is not None else None)
        return (hidden, rec_all, conv_all), (k_l, v_l)

    (hidden, rec_all, conv_all), (k_new, v_new) = jax.lax.scan(
        step, (hidden, *state),
        (view.win_k, view.win_v, view.ring_k, view.ring_v,
         jnp.arange(n_periods, dtype=jnp.int32)),
    )
    hidden = rms_norm(hidden, params["final_norm"], cfg.rms_norm_eps)
    return hidden, k_new, v_new, (rec_all, conv_all)
