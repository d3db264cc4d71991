"""OPT-family decoder (facebook/opt-125m etc.) — functional JAX.

The same shape of module as models/llama.py (stacked layers scanned by
ops/attention.py:scan_layers, attention through ``attend`` over whatever
``KVView`` the runner built, the same declarations for the rest of the tree)
with OPT's architecture: LayerNorm with bias, learned position embeddings with
OPT's +2 offset quirk, ReLU MLP, tied LM head. opt-125m is the reference's
minimal parity config (values-01-minimal-example, BASELINE.json).
"""

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from production_stack_tpu.models.config import ModelConfig
from production_stack_tpu.models.llama import cache_specs  # noqa: F401 — K/V in every layer
from production_stack_tpu.ops.attention import KVView, attend, scan_layers

Params = Dict
_OPT_POS_OFFSET = 2  # HF OPTLearnedPositionalEmbedding offset

# --- What the rest of the tree asks of this module (see models/llama.py) ----
HF_LAYER_MAP = {
    "self_attn.q_proj.weight": ("wq", True),
    "self_attn.k_proj.weight": ("wk", True),
    "self_attn.v_proj.weight": ("wv", True),
    "self_attn.out_proj.weight": ("wo", True),
    "self_attn.q_proj.bias": ("bq", False),
    "self_attn.k_proj.bias": ("bk", False),
    "self_attn.v_proj.bias": ("bv", False),
    "self_attn.out_proj.bias": ("bo", False),
    "self_attn_layer_norm.weight": ("ln1_w", False),
    "self_attn_layer_norm.bias": ("ln1_b", False),
    "final_layer_norm.weight": ("ln2_w", False),
    "final_layer_norm.bias": ("ln2_b", False),
    "fc1.weight": ("fc1", True),
    "fc1.bias": ("fc1_b", False),
    "fc2.weight": ("fc2", True),
    "fc2.bias": ("fc2_b", False),
}
HF_TOP_MAP = {
    "model.decoder.embed_tokens.weight": ("embed", False),
    "model.decoder.embed_positions.weight": ("pos_embed", False),
    "model.decoder.final_layer_norm.weight": ("final_ln_w", False),
    "model.decoder.final_layer_norm.bias": ("final_ln_b", False),
}
LORA_TARGETS = ()
# The forward runs a pool view like any other (tests/test_attention.py); no
# engine-level token parity holds ``auto`` to it yet, so ``auto`` stays off it.
PAGED_DECODE_VALIDATED = False


def position_bound(cfg: ModelConfig) -> Optional[int]:
    """The learned position table's rows."""
    return cfg.max_position_embeddings


def required_layer_leaves(cfg: ModelConfig) -> set:
    """The forward unconditionally reads the bias/norm leaves too."""
    return {leaf for leaf, _ in HF_LAYER_MAP.values()}


def finish_params(cfg: ModelConfig, params: Params) -> Params:
    return params


def layer_norm(x: jax.Array, w: jax.Array, b: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w + b


def init_params(cfg: ModelConfig, rng: jax.Array, dtype=jnp.bfloat16) -> Params:
    d, f = cfg.hidden_size, cfg.intermediate_size
    h, dh, nl, v = cfg.num_heads, cfg.head_dim_, cfg.num_layers, cfg.vocab_size
    keys = jax.random.split(rng, 8)

    def w(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32) * fan_in**-0.5).astype(dtype)

    layers = {
        "ln1_w": jnp.ones((nl, d), dtype), "ln1_b": jnp.zeros((nl, d), dtype),
        "ln2_w": jnp.ones((nl, d), dtype), "ln2_b": jnp.zeros((nl, d), dtype),
        "wq": w(keys[0], (nl, d, h * dh), d), "bq": jnp.zeros((nl, h * dh), dtype),
        "wk": w(keys[1], (nl, d, h * dh), d), "bk": jnp.zeros((nl, h * dh), dtype),
        "wv": w(keys[2], (nl, d, h * dh), d), "bv": jnp.zeros((nl, h * dh), dtype),
        "wo": w(keys[3], (nl, h * dh, d), h * dh), "bo": jnp.zeros((nl, d), dtype),
        "fc1": w(keys[4], (nl, d, f), d), "fc1_b": jnp.zeros((nl, f), dtype),
        "fc2": w(keys[5], (nl, f, d), f), "fc2_b": jnp.zeros((nl, d), dtype),
    }
    return {
        "embed": w(keys[6], (v, d), d),
        "pos_embed": w(keys[7], (cfg.max_position_embeddings + _OPT_POS_OFFSET, d), d),
        "layers": layers,
        "final_ln_w": jnp.ones((d,), dtype),
        "final_ln_b": jnp.zeros((d,), dtype),
    }


def _layer_body(cfg, positions, chunk_lens, hidden, lp, view, layer=None,
                lora=None):
    b, t, d = hidden.shape
    h, dh = cfg.num_heads, cfg.head_dim_

    with jax.named_scope("attn_proj"):
        x = layer_norm(hidden, lp["ln1_w"], lp["ln1_b"])
        q = (x @ lp["wq"] + lp["bq"]).reshape(b, t, h, dh)
        k = (x @ lp["wk"] + lp["bk"]).reshape(b, t, h, dh)
        v = (x @ lp["wv"] + lp["bv"]).reshape(b, t, h, dh)

    with jax.named_scope("attn_core"):
        attn = attend(q, k, v, positions, chunk_lens, view, layer)
    with jax.named_scope("attn_proj"):
        hidden = hidden + attn.reshape(b, t, h * dh) @ lp["wo"] + lp["bo"]

    with jax.named_scope("ffn"):
        x = layer_norm(hidden, lp["ln2_w"], lp["ln2_b"])
        # OPT's activation is ReLU (HF OPTConfig.activation_function default,
        # used by facebook/opt-125m), not GELU.
        mlp = jax.nn.relu(x @ lp["fc1"] + lp["fc1_b"]) @ lp["fc2"] + lp["fc2_b"]
    return hidden + mlp, k.transpose(2, 0, 1, 3), v.transpose(2, 0, 1, 3)


def forward(
    params: Params,
    cfg: ModelConfig,
    token_ids: jax.Array,
    positions: jax.Array,
    chunk_lens: jax.Array,
    view: KVView = KVView(),
    *,
    act_sharding=None,
    lora=None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Same contract as models/llama.py:forward (see its docstring)."""
    assert lora is None, "OPT's projections apply no LoRA (LORA_TARGETS)"
    with jax.named_scope("embed"):
        hidden = (
            params["embed"][token_ids]
            + params["pos_embed"][positions + _OPT_POS_OFFSET]
        )
        hidden = hidden.astype(view.act_dtype(params["embed"].dtype))
    if act_sharding is not None and hidden.shape[1] > 1 and \
            hidden.shape[1] % act_sharding.mesh.shape["sp"] == 0:
        hidden = jax.lax.with_sharding_constraint(hidden, act_sharding)
    hidden, k_new, v_new = scan_layers(
        functools.partial(_layer_body, cfg, positions, chunk_lens),
        hidden, params["layers"], view,
    )
    hidden = layer_norm(hidden, params["final_ln_w"], params["final_ln_b"])
    return hidden, k_new, v_new


def compute_logits(params, cfg, hidden):
    with jax.named_scope("logits"):
        return jnp.dot(
            hidden, params["embed"].T.astype(hidden.dtype),
            preferred_element_type=jnp.float32,
        )
