"""Llama-family decoder (Llama 2/3, Mistral, Qwen2) — functional JAX.

TPU-first design notes:
  * Parameters are a plain pytree with all decoder layers STACKED on a leading
    ``L`` axis and the forward pass runs ``lax.scan`` over layers — one traced
    layer body instead of L inlined copies, which keeps XLA compile time flat
    in depth and produces identical per-layer fusions.
  * Activations are bfloat16; norms/softmax/rope math in float32.
  * The paged KV pool is NOT threaded through the layer scan or the step
    scan: scanning the pools as xs/ys cost a full pool copy per layer (~2
    ms/step on a v5e, profiled round 1). The scans only READ it, as a
    closed-over constant — the Pallas kernels in place (``memory_space=ANY``:
    decode, and a prefill chunk's history), or the window the runner
    gathers once per dispatch where no kernel covers the view
    (ops/attention.py:gather_window) — and the runner writes the dispatch's
    new KV back once, after the scans, IN PLACE: the donated pool is updated
    by ``dynamic_update_slice``s of block-wide slabs (ops/kv_write.py) and no
    program may hold an operation that reads or writes a whole pool. The
    measured reason (PERF.md §6, PR 25, TPU v5e): the earlier write,
    ``pool.at[:, :, slots].set(new)``, is a scatter on a middle axis, which the
    TPU compiler runs in a layout of its own — it copied each pool into that
    layout and back, every dispatch: four copies of 2.4 GB, 23 ms and a
    pool-sized temporary per decode train of qwen2.5-3b. Reading inside the
    loops and writing after them needs no copy (XLA orders the read-only
    loops before the in-place write); ``GET /debug/programs`` and
    chip_smoke.py hold every later change to that.

Device operations are named by ``jax.named_scope``: ``embed``, ``attn_proj``
(QKV, rope, output projection), ``attn_core`` (every attention path), ``ffn``
and ``logits`` here; ``sample`` in engine/sampling.py and ``kv_write`` in
engine/runner.py. A profiler capture carries the scope in each operation's
``tf_op`` (docs/OBSERVABILITY.md); scopes cost nothing at run time.

The attention kernel is not chosen here: the layer hands the runner's
``KVView`` unopened to ops/attention.py:attend.

Weight layout matches HuggingFace LlamaForCausalLM for direct safetensors
loading: ``HF_LAYER_MAP`` / ``HF_TOP_MAP`` below, read by models/weights.py.
The declarations under "What the rest of the tree asks of this module" are
the whole of what an architecture is outside its file (models/__init__.py).
"""

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from production_stack_tpu.models.config import (
    CacheSpecs,
    ModelConfig,
    PagedKVSpec,
)
from production_stack_tpu.ops.attention import KVView, attend, scan_layers
from production_stack_tpu.utils import init_logger

logger = init_logger(__name__)

Params = Dict

# --- What the rest of the tree asks of this module --------------------------
# HF checkpoint suffix -> (our leaf name, transpose?), per layer and top level.
HF_LAYER_MAP = {
    "self_attn.q_proj.weight": ("wq", True),
    "self_attn.k_proj.weight": ("wk", True),
    "self_attn.v_proj.weight": ("wv", True),
    "self_attn.o_proj.weight": ("wo", True),
    "self_attn.q_proj.bias": ("bq", False),
    "self_attn.k_proj.bias": ("bk", False),
    "self_attn.v_proj.bias": ("bv", False),
    "mlp.gate_proj.weight": ("w_gate", True),
    "mlp.up_proj.weight": ("w_up", True),
    "mlp.down_proj.weight": ("w_down", True),
    "input_layernorm.weight": ("attn_norm", False),
    "post_attention_layernorm.weight": ("mlp_norm", False),
}
HF_TOP_MAP = {
    "model.embed_tokens.weight": ("embed", False),
    "model.norm.weight": ("final_norm", False),
    "lm_head.weight": ("lm_head", True),
}
# Projections ``_layer_body`` adds a LoRA delta to (models/lora.py).
LORA_TARGETS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
# ``attn_impl=auto`` may resolve to the Pallas paged decode for this family:
# its token parity against the window path is held by tests/test_paged_decode.py.
PAGED_DECODE_VALIDATED = True


def position_bound(cfg: ModelConfig) -> Optional[int]:
    """Largest position + 1 the forward accepts; None: RoPE takes any."""
    return None


def cache_specs(cfg: ModelConfig) -> CacheSpecs:
    """What a sequence caches: paged K/V in every layer, nothing else."""
    return CacheSpecs(
        PagedKVSpec(cfg.num_layers, cfg.num_kv_heads, cfg.head_dim_))


def required_layer_leaves(cfg: ModelConfig) -> set:
    """Per-layer leaves every valid checkpoint must provide."""
    req = {"wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
           "attn_norm", "mlp_norm"}
    if cfg.attention_bias:
        req |= {"bq", "bk", "bv"}
    return req


def finish_params(cfg: ModelConfig, params: Params) -> Params:
    """Last step of a checkpoint load: a tied head reads ``embed``."""
    if cfg.tie_word_embeddings:
        params.pop("lm_head", None)
    elif "lm_head" not in params and "embed" in params:
        # Checkpoints sometimes omit lm_head when tied; honor the config.
        logger.warning("lm_head missing; falling back to tied embeddings")
    return params


def rms_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w


def _rope_cos_sin(positions: jax.Array, head_dim: int, theta: float):
    """cos/sin tables for the given absolute positions. positions: [B, T]."""
    inv_freq = 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # [B, T, Dh/2]
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """HF-convention rotary embedding (rotate-half). x: [B, T, H, Dh]."""
    xf = x.astype(jnp.float32)
    x1, x2 = jnp.split(xf, 2, axis=-1)
    c = cos[:, :, None, :]
    s = sin[:, :, None, :]
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.astype(x.dtype)


def init_params(cfg: ModelConfig, rng: jax.Array, dtype=jnp.bfloat16) -> Params:
    d, f, dh = cfg.hidden_size, cfg.intermediate_size, cfg.head_dim_
    h, hkv, nl, v = cfg.num_heads, cfg.num_kv_heads, cfg.num_layers, cfg.vocab_size
    keys = jax.random.split(rng, 10)

    def w(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32) * fan_in**-0.5).astype(dtype)

    layers = {
        "attn_norm": jnp.ones((nl, d), dtype),
        "mlp_norm": jnp.ones((nl, d), dtype),
        "wq": w(keys[0], (nl, d, h * dh), d),
        "wk": w(keys[1], (nl, d, hkv * dh), d),
        "wv": w(keys[2], (nl, d, hkv * dh), d),
        "wo": w(keys[3], (nl, h * dh, d), h * dh),
        "w_gate": w(keys[4], (nl, d, f), d),
        "w_up": w(keys[5], (nl, d, f), d),
        "w_down": w(keys[6], (nl, f, d), f),
    }
    if cfg.attention_bias:
        layers["bq"] = jnp.zeros((nl, h * dh), dtype)
        layers["bk"] = jnp.zeros((nl, hkv * dh), dtype)
        layers["bv"] = jnp.zeros((nl, hkv * dh), dtype)
    params = {
        "embed": w(keys[7], (v, d), d),
        "layers": layers,
        "final_norm": jnp.ones((d,), dtype),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = w(keys[8], (d, v), d)
    return params


def _layer_body(
    cfg: ModelConfig,
    cos: jax.Array,
    sin: jax.Array,
    positions: jax.Array,
    chunk_lens: jax.Array,
    hidden: jax.Array,        # [B, T, D]
    lp: Dict,                 # one layer's params (leading L axis sliced off)
    view: KVView,             # this layer's KV view, passed on to attend
    layer=None,               # scan layer index (pool views only)
    lora=None,                # (adapter_idx [B], {target: (A, B)} ONE layer)
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    b, t, d = hidden.shape
    h, hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_

    def proj(x, target):
        out = x @ lp[target]
        if lora is not None and target in lora[1]:
            from production_stack_tpu.models.lora import lora_delta

            la, lb = lora[1][target]
            out = out + lora_delta(x, la, lb, lora[0])
        return out

    with jax.named_scope("attn_proj"):
        x = rms_norm(hidden, lp["attn_norm"], cfg.rms_norm_eps)
        q = proj(x, "wq")
        k = proj(x, "wk")
        v = proj(x, "wv")
        if cfg.attention_bias:
            q = q + lp["bq"]
            k = k + lp["bk"]
            v = v + lp["bv"]
        q = q.reshape(b, t, h, dh)
        k = k.reshape(b, t, hkv, dh)
        v = v.reshape(b, t, hkv, dh)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)

    with jax.named_scope("attn_core"):
        attn = attend(q, k, v, positions, chunk_lens, view, layer)
    with jax.named_scope("attn_proj"):
        hidden = hidden + proj(attn.reshape(b, t, h * dh), "wo")

    with jax.named_scope("ffn"):
        x = rms_norm(hidden, lp["mlp_norm"], cfg.rms_norm_eps)
        gated = jax.nn.silu(proj(x, "w_gate")) * proj(x, "w_up")
        mlp = proj(gated, "w_down")
    # New KV in pool layout [Hkv, B, T, Dh] for the runner's single scatter.
    return hidden + mlp, k.transpose(2, 0, 1, 3), v.transpose(2, 0, 1, 3)


def forward(
    params: Params,
    cfg: ModelConfig,
    token_ids: jax.Array,     # [B, T]
    positions: jax.Array,     # [B, T]
    chunk_lens: jax.Array,    # [B] valid tokens per row
    view: KVView = KVView(),  # the KV this forward may read beside its own
    *,
    act_sharding=None,
    lora=None,   # (adapter_idx [B], {target: (A [L,Na,in,r], B [L,Na,r,out])})
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (hidden [B,T,D], k_new [L,Hkv,B,T,Dh], v_new [L,Hkv,B,T,Dh]).

    The caller owns the paged pool: it describes what this forward may read
    as ``view`` (ops/attention.py:KVView — a gathered window, the dispatch's
    ring, or the pool itself for the Pallas decode kernel) and writes
    (k_new, v_new) into the pool after (engine/runner.py). This module never
    opens the view; ``attend`` chooses the kernel from it.

    ``act_sharding``: optional NamedSharding P(None, "sp", None) — prefill
    chunks shard the TOKEN axis over the sequence-parallel mesh axis so the
    projection/MLP matmuls distribute over sp; GSPMD inserts the collectives.
    """
    with jax.named_scope("embed"):
        hidden = params["embed"][token_ids]
        hidden = hidden.astype(view.act_dtype(params["embed"].dtype))
    if act_sharding is not None and hidden.shape[1] > 1 and \
            hidden.shape[1] % act_sharding.mesh.shape["sp"] == 0:
        hidden = jax.lax.with_sharding_constraint(hidden, act_sharding)
    cos, sin = _rope_cos_sin(positions, cfg.head_dim_, cfg.rope_theta)

    hidden, k_new, v_new = scan_layers(
        functools.partial(_layer_body, cfg, cos, sin, positions, chunk_lens),
        hidden, params["layers"], view, lora,
    )
    hidden = rms_norm(hidden, params["final_norm"], cfg.rms_norm_eps)
    return hidden, k_new, v_new


def compute_logits(params: Params, cfg: ModelConfig, hidden: jax.Array) -> jax.Array:
    """hidden [..., D] -> logits [..., V] in float32."""
    with jax.named_scope("logits"):
        head = params["embed"].T if cfg.tie_word_embeddings \
            else params["lm_head"]
        return jnp.dot(
            hidden, head.astype(hidden.dtype),
            preferred_element_type=jnp.float32,
        )
