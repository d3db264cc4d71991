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
    closed-over constant — the Pallas kernel in place (``memory_space=ANY``),
    or the window the runner gathers once per dispatch
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

Weight layout matches HuggingFace LlamaForCausalLM for direct safetensors
loading (production_stack_tpu/models/weights.py).
"""

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from production_stack_tpu.models.config import ModelConfig
from production_stack_tpu.ops.attention import (
    dense_decode_stats,
    merge_attention_segments,
    window_attention,
)

Params = Dict


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
    hidden: jax.Array,        # [B, T, D]
    lp: Dict,                 # one layer's params (leading L axis sliced off)
    cos: jax.Array,
    sin: jax.Array,
    positions: jax.Array,
    chunk_lens: jax.Array,
    win_k, win_v, win_len,
    ring_k, ring_v, ring_pos,
    paged=None,               # (pool_k, pool_v, k_scale|None, v_scale|None,
    layer_idx=None,           #  block_tables, kv_lens, block_size,
                              #  interpret, tp_mesh|None) + scan layer index
    lora=None,                # (adapter_idx [B], {target: (A, B)} ONE layer)
    ring_mesh=None,           # Mesh with sp>1: first-chunk prefill rings
    chunk_bias=None,          # [T, T] additive in-chunk bias (tree verify)
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
        if ring_mesh is not None and t > 1 and win_k is None and ring_k is None:
            # Sequence-parallel prefill: the chunk is pure causal self-attention
            # (no history window, no intra-dispatch ring buffer), computed
            # exactly by ring attention over the sp axis — KV shards stream
            # around the ICI ring while each chip holds O(T/sp) tokens
            # (ops/ring_attention.py). Padding rows/tokens carry positions
            # beyond every real token of their row, so causal masking by
            # absolute position excludes them as keys.
            from production_stack_tpu.ops.ring_attention import ring_attention

            attn = ring_attention(q, k, v, positions, ring_mesh)
        elif ring_mesh is not None and t > 1 and win_k is not None \
                and ring_k is None:
            # Sequence-parallel CONTINUATION chunk: the combined sequence
            # (gathered history window ++ chunk) is the ring's KV, sharded over
            # sp — each chip holds O((S_hist + T)/sp) keys instead of the whole
            # window, and ring attention engages on every chunk of a long
            # prefill, not just the first (VERDICT r4 weak #5). Window slot s
            # holds absolute position s; slots at or beyond win_len take a
            # sentinel position beyond every query so position-causality masks
            # them exactly like window_attention's validity bias.
            from production_stack_tpu.ops.ring_attention import ring_attention_kv

            s_hist = win_k.shape[2]
            kw = win_k.transpose(1, 2, 0, 3)        # [B, S, Hkv, Dh]
            vw = win_v.transpose(1, 2, 0, 3)
            s_idx = jnp.arange(s_hist, dtype=jnp.int32)
            pos_w = jnp.where(
                s_idx[None, :] < win_len[:, None], s_idx[None, :],
                jnp.int32(2**30),
            )                                        # [B, S]
            attn = ring_attention_kv(
                q, positions,
                jnp.concatenate([kw, k], axis=1),
                jnp.concatenate([vw, v], axis=1),
                jnp.concatenate([pos_w, positions], axis=1),
                ring_mesh,
            )
        elif paged is not None:
            # Paged decode (T == 1): the pool segment runs in the Pallas
            # flash-decode kernel directly against this layer of the stacked HBM
            # pool (no gathered window copy); the intra-dispatch ring + the
            # current token form a small dense segment; the two merge by their
            # softmax stats. See ops/pallas/paged_attention.py.
            from production_stack_tpu.ops.pallas.paged_attention import (
                paged_flash_decode_stats,
                paged_flash_decode_stats_tp,
            )

            (pool_k, pool_v, pool_ks, pool_vs, block_tables, kv_lens,
             block_size, interpret, tp_mesh) = paged
            q2 = q.reshape(b, h, dh)
            if tp_mesh is not None:
                # TP>1: the pool is kv-head-sharded; run the kernel per-shard
                # via shard_map (exact — heads are independent) instead of
                # letting GSPMD all-gather the pool (advisor r3 high finding).
                out_p, m_p, l_p = paged_flash_decode_stats_tp(
                    q2, pool_k, pool_v, block_tables, kv_lens, layer_idx,
                    tp_mesh, block_size=block_size, interpret=interpret,
                    k_scale=pool_ks, v_scale=pool_vs,
                )
            else:
                out_p, m_p, l_p = paged_flash_decode_stats(
                    q2, pool_k, pool_v, block_tables, kv_lens, layer_idx,
                    block_size=block_size, interpret=interpret,
                    k_scale=pool_ks, v_scale=pool_vs,
                )
            kc = k.transpose(2, 0, 1, 3)          # [Hkv, B, 1, Dh] current token
            vc = v.transpose(2, 0, 1, 3)
            self_bias = jnp.zeros((b, 1), jnp.float32)
            if ring_k is not None:
                keys = jnp.concatenate([ring_k, kc], axis=2)
                vals = jnp.concatenate([ring_v, vc], axis=2)
                neg = jnp.float32(jnp.finfo(jnp.float32).min)
                ring_bias = jnp.where(ring_pos < positions, 0.0, neg)  # [B, R]
                bias = jnp.concatenate([ring_bias, self_bias], axis=1)
            else:
                keys, vals, bias = kc, vc, self_bias
            out_d, m_d, l_d = dense_decode_stats(q2, keys, vals, bias)
            attn = merge_attention_segments(out_p, m_p, l_p, out_d, m_d, l_d)
            attn = attn.reshape(b, t, h, dh)
        else:
            attn = window_attention(
                q, k, v, positions, chunk_lens,
                win_k, win_v, win_len, ring_k, ring_v, ring_pos,
                chunk_bias=chunk_bias,
            )
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
    win_k: Optional[jax.Array] = None,   # [L, Hkv, B, S, Dh] gathered window
    win_v: Optional[jax.Array] = None,
    win_len: Optional[jax.Array] = None,  # [B]
    ring_k: Optional[jax.Array] = None,   # [L, Hkv, B, R, Dh]
    ring_v: Optional[jax.Array] = None,
    ring_pos: Optional[jax.Array] = None,  # [B, R]
    *,
    act_sharding=None,
    paged=None,  # (pool_k [L,Hkv,S,Dh], pool_v, k_scale [L,Hkv,S]|None,
                 #  v_scale|None, block_tables [B,Mb], kv_lens [B],
                 #  block_size, interpret, tp_mesh|None) — paged decode
                 #  path (tp_mesh set => shard_map over tp; scales set =>
                 #  int8 pools, in-kernel dequantization)
    lora=None,   # (adapter_idx [B], {target: (A [L,Na,in,r], B [L,Na,r,out])})
    ring_mesh=None,  # Mesh with sp>1: first-chunk prefill uses ring attention
    chunk_bias=None,  # [T, T] additive in-chunk bias — speculative token-tree
                      # verify (ops/tree_mask.py); window path only
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (hidden [B,T,D], k_new [L,Hkv,B,T,Dh], v_new [L,Hkv,B,T,Dh]).

    The caller owns the paged pool. Window path: it gathers the window before
    this call and scatters (k_new, v_new) into the pool after (see
    engine/runner.py). Paged path (``paged`` set, decode only): each layer
    attends directly against its slice of the stacked HBM pool inside the
    Pallas flash-decode kernel — no window copy exists.

    ``act_sharding``: optional NamedSharding P(None, "sp", None) — prefill
    chunks shard the TOKEN axis over the sequence-parallel mesh axis so the
    projection/MLP matmuls distribute over sp; GSPMD inserts the collectives.
    The standalone ring kernel lives in production_stack_tpu/ops/ring_attention.py.
    """
    with jax.named_scope("embed"):
        hidden = params["embed"][token_ids]
        hidden = hidden.astype(
            win_k.dtype if win_k is not None else params["embed"].dtype
        )
    if act_sharding is not None and hidden.shape[1] > 1 and \
            hidden.shape[1] % act_sharding.mesh.shape["sp"] == 0:
        hidden = jax.lax.with_sharding_constraint(hidden, act_sharding)
    cos, sin = _rope_cos_sin(positions, cfg.head_dim_, cfg.rope_theta)

    have_win = win_k is not None
    have_ring = ring_k is not None
    have_paged = paged is not None
    have_lora = lora is not None

    def scan_fn(h_carry, xs):
        lp = xs[0]
        i = 1
        wk = wv = rk = rv = li = lo = None
        if have_win:
            wk, wv = xs[i], xs[i + 1]
            i += 2
        if have_ring:
            rk, rv = xs[i], xs[i + 1]
            i += 2
        if have_paged:
            li = xs[i]
            i += 1
        if have_lora:
            # per-layer slices of the adapter stacks, same adapter_idx rows
            lo = (lora[0], xs[i])
        h_out, k_l, v_l = _layer_body(
            cfg, h_carry, lp, cos, sin, positions, chunk_lens,
            wk, wv, win_len, rk, rv, ring_pos,
            paged=paged, layer_idx=li, lora=lo, ring_mesh=ring_mesh,
            chunk_bias=chunk_bias,
        )
        return h_out, (k_l, v_l)

    xs = (params["layers"],)
    if have_win:
        xs += (win_k, win_v)
    if have_ring:
        xs += (ring_k, ring_v)
    if have_paged:
        xs += (jnp.arange(cfg.num_layers, dtype=jnp.int32),)
    if have_lora:
        xs += (lora[1],)  # dict of (A [L,...], B [L,...]) — L axis scanned
    hidden, (k_new, v_new) = jax.lax.scan(scan_fn, hidden, xs)
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
