"""AFMoE decoder (HF ``afmoe``: Arcee's Trinity): rotary GQA layers whose
queries see a bounded SPAN of keys mixed with position-free layers that see
all of them, a sigmoid gate on the attention output, four norms a layer,
leading dense FFNs and sigmoid-routed sparse experts beside a shared one —
functional JAX.

The same shape of module as models/deepseek_v3.py (the declarations under
"What the rest of the tree asks of this module", attention through
``attend`` over whatever ``KVView`` the runner built, parameters stacked BY
KIND of FFN (``layers.dense``, ``layers.sparse``), the leading dense layers
a scan of their own, the sparse stack ONE scan with the weights closed over
and sliced where used, the experts' stacks never sliced, ops/moe.py's router
and experts, the counters ``FORWARD_STATS`` names returned last), with GQA
rows of K and V in two pools where that module keeps one latent row. Of its
own:

  * A layer's KIND of attention (``cfg.layer_types``: ``sliding_attention``
    / ``full_attention``, in ANY order) is DATA of the layer, two scalars
    the scans index by the layer: its span (``cfg.sliding_window`` keys, or
    ``NO_SPAN``: ops/attention.py tells a span from the engine's gathered
    window) and whether it rotates. One attention operator a scan, whatever
    the list. A sliding layer applies rotate-half rope to q and k; a full
    layer carries NO position embedding (its cos is 1 and its sin 0). The
    bound goes to ``attend`` and is honoured inside the paged kernels; one
    pool and one block table serve both kinds, so a sliding layer HOLDS the
    keys behind its bound and does not read them.
  * RMSNorm over each head's lanes of q and of k (one weight of ``head_dim``
    each) BEFORE rope, in float32 (models/lfm2_moe.py:head_norm_rope).
  * The attention output times ``sigmoid(x W_gate)``, ``x`` the normed
    stream the projections read, before ``W_o``.
  * Sandwich norms: ``h + norm(attn(norm(h)))``, ``h + norm(ffn(norm(h)))``.
  * ``mup_enabled``: the table's rows times ``sqrt(hidden_size)``
    (``cfg.embedding_multiplier``, granite's operand).
  * Routing (ops/moe.py): float32 sigmoid scores, top-k of score +
    ``expert_bias``, weights the scores over their sum + 1e-20 times
    ``route_scale``; one shared gated FFN beside the routed ones.

tests/reference/afmoe_ref.py is the plain statement of the same equations
this module is held to.

Device scopes: ``attn_proj`` (norms, projections, rope, the gate),
``attn_core`` with the inner ``attn_span`` around the bounded attention
call, ``ffn`` (the dense FFN; a sparse layer's norms and sum) and inside it
``moe_route``, ``moe_experts`` (inner ``moe_gmm``) and ``moe_shared``;
``embed``, ``logits``.
"""

from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from production_stack_tpu.models.config import (
    ANY_ORDER_LISTS,
    CacheSpecs,
    ModelConfig,
    PagedKVSpec,
)
from production_stack_tpu.models.lfm2_moe import head_norm_rope
from production_stack_tpu.models.llama import (
    _rope_cos_sin,
    compute_logits,  # noqa: F401 — the untied head is llama's
    rms_norm,
)
from production_stack_tpu.ops import moe
from production_stack_tpu.ops.attention import NO_SPAN, KVView, attend

Params = Dict

# --- What the rest of the tree asks of this module (see models/llama.py) ----
# HF checkpoint suffix -> (our leaf, transpose?): the names of HF's
# AfmoeDecoderLayer (ASSUMED: deployment.json of trinity-mini-d8 says so).
HF_LAYER_MAP = {
    "input_layernorm.weight": ("attn_norm", False),
    "post_attention_layernorm.weight": ("post_attn_norm", False),
    "pre_mlp_layernorm.weight": ("mlp_norm", False),
    "post_mlp_layernorm.weight": ("post_mlp_norm", False),
    "self_attn.q_proj.weight": ("wq", True),
    "self_attn.k_proj.weight": ("wk", True),
    "self_attn.v_proj.weight": ("wv", True),
    "self_attn.gate_proj.weight": ("wg", True),
    "self_attn.o_proj.weight": ("wo", True),
    "self_attn.q_norm.weight": ("q_norm", False),
    "self_attn.k_norm.weight": ("k_norm", False),
    "mlp.gate_proj.weight": ("w_gate", True),
    "mlp.up_proj.weight": ("w_up", True),
    "mlp.down_proj.weight": ("w_down", True),
    "mlp.router.gate.weight": ("w_router", True),
    "mlp.expert_bias": ("router_bias", False),
    "mlp.experts.*.gate_proj.weight": ("we_gate", True),
    "mlp.experts.*.up_proj.weight": ("we_up", True),
    "mlp.experts.*.down_proj.weight": ("we_down", True),
    "mlp.shared_experts.gate_proj.weight": ("ws_gate", True),
    "mlp.shared_experts.up_proj.weight": ("ws_up", True),
    "mlp.shared_experts.down_proj.weight": ("ws_down", True),
}
HF_TOP_MAP = {
    "model.embed_tokens.weight": ("embed", False),
    "model.norm.weight": ("final_norm", False),
    "lm_head.weight": ("lm_head", True),
}
# No LoRA on this family yet: the gate and the experts have no delta path
# (the engine refuses --lora-modules on an empty tuple).
LORA_TARGETS = ()
# ``attn_impl=auto`` may resolve to the Pallas paged decode: tests/
# test_afmoe.py holds the engine's logits on that path to the reference.
PAGED_DECODE_VALIDATED = True
# Leaves a checkpoint load keeps in float32 whatever the engine's dtype: the
# router computes in float32 and its bias is published in it.
FLOAT32_LEAVES = ("w_router", "router_bias")
# int32 counters ``forward`` returns last, summed over its sparse layers.
FORWARD_STATS = moe.STATS

_KINDS = ANY_ORDER_LISTS["afmoe"]   # ("sliding_attention", "full_attention")
_ATTN = ("attn_norm", "post_attn_norm", "mlp_norm", "post_mlp_norm", "wq",
         "wk", "wv", "wg", "wo", "q_norm", "k_norm")
_DENSE = ("w_gate", "w_up", "w_down")
_SPARSE = ("w_router", "router_bias", "we_gate", "we_up", "we_down",
           "ws_gate", "ws_up", "ws_down")                    # as loaded


def position_bound(cfg: ModelConfig) -> Optional[int]:
    """None: RoPE takes any position, and a full layer reads none."""
    return None


def layer_slots(cfg: ModelConfig):
    """(kind, index within the kind's stack) of every layer, in order."""
    nd = cfg.first_k_dense_replace
    return [("dense", i) if i < nd else ("sparse", i - nd)
            for i in range(cfg.num_layers)]


def required_layer_leaves(cfg: ModelConfig) -> dict:
    """Per kind, the leaves every valid checkpoint must provide."""
    need = {"dense": set(_ATTN + _DENSE), "sparse": set(_ATTN + _SPARSE)}
    if not cfg.first_k_dense_replace:
        del need["dense"]
    return need


def finish_params(cfg: ModelConfig, params: Params) -> Params:
    """Last step of a checkpoint load: an expert's gate and up matrices
    become one (gate then up)."""
    sparse = params["layers"]["sparse"]
    if "we_gate" in sparse:
        sparse["w_gate_up"] = jnp.concatenate(
            [sparse.pop("we_gate"), sparse.pop("we_up")], axis=-1)
    return params


def cache_specs(cfg: ModelConfig) -> CacheSpecs:
    """Paged K and V for every layer, bounded or not: ONE pool shape and
    one block table (a bounded layer's blocks behind its bound are held,
    not freed)."""
    return CacheSpecs(
        PagedKVSpec(cfg.num_layers, cfg.num_kv_heads, cfg.head_dim_))


def spans(cfg: ModelConfig) -> np.ndarray:
    """Per layer, the keys a query sees up to itself (``NO_SPAN``: all)."""
    return np.array([cfg.sliding_window if t == _KINDS[0] else NO_SPAN
                     for t in cfg.layer_types], np.int32)


def bounded_layers(cfg: ModelConfig):
    """The layers whose attention is bounded (``GET /debug/programs``)."""
    return [i for i, s in enumerate(spans(cfg)) if s != NO_SPAN]


def init_params(cfg: ModelConfig, rng: jax.Array, dtype=jnp.bfloat16) -> Params:
    d, f, dh = cfg.hidden_size, cfg.intermediate_size, cfg.head_dim_
    h, hkv, v = cfg.num_heads, cfg.num_kv_heads, cfg.vocab_size
    e, fe = cfg.n_routed_experts, cfg.moe_intermediate_size
    fs = cfg.n_shared_experts * fe
    nd = cfg.first_k_dense_replace
    ns = cfg.num_layers - nd
    keys = iter(jax.random.split(rng, 48))
    # Random weights that behave as a trained model's do where routing looks
    # (models/deepseek_v3.py:init_params says why): the residual stream is
    # the token's own embedding at unit scale plus SMALL branches. Under
    # sandwich norms a branch's size is its post-norm's WEIGHT, so those
    # are drawn around 1/sqrt(2 L) for the depth the model is published
    # with (32), whatever part of it is served; the projections back into
    # the stream keep the same factor (the norm takes it out again).
    back = (2 * 32) ** -0.5

    def w(shape, fan_in, dt=dtype, scale=1.0):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * (scale * fan_in ** -0.5)).astype(dt)

    def w_experts(shape, fan_in, scale=1.0):
        # A layer at a time: the float32 draw of a whole stack of experts
        # is never alive at once.
        return jax.lax.map(
            lambda k: (jax.random.normal(k, shape, jnp.float32)
                       * (scale * fan_in ** -0.5)).astype(dtype),
            jax.random.split(next(keys), ns))

    def spread(shape, lo, hi):
        return jax.random.uniform(next(keys), shape, jnp.float32,
                                  lo, hi).astype(dtype)

    def attn(n):
        return {
            "attn_norm": jnp.ones((n, d), dtype),
            "mlp_norm": jnp.ones((n, d), dtype),
            # Small, and not all alike: a comparison that drops a post norm
            # (or takes its weight for 1) sees it.
            "post_attn_norm": spread((n, d), 0.5 * back, 1.5 * back),
            "post_mlp_norm": spread((n, d), 0.5 * back, 1.5 * back),
            # Half of fan-in scale: the per-head norm takes the scale out
            # (models/lfm2_moe.py:init_params), and its weights are LARGE
            # (mean square 4.3), so that attention picks a few of a prompt's
            # tokens instead of averaging them all (PERF.md section 6, PR 44).
            "wq": w((n, d, h * dh), d, scale=0.5),
            "wk": w((n, d, hkv * dh), d, scale=0.5),
            "wv": w((n, d, hkv * dh), d),
            # Gate logits of unit size: sigmoid lies away from a half.
            "wg": w((n, d, h * dh), d),
            "wo": w((n, h * dh, d), h * dh, scale=back),
            "q_norm": spread((n, dh), 1.0, 3.0),
            "k_norm": spread((n, dh), 1.0, 3.0),
        }

    dense = {**attn(nd), "w_gate": w((nd, d, f), d), "w_up": w((nd, d, f), d),
             "w_down": w((nd, f, d), f, scale=back)}
    sparse = {
        **attn(ns),
        # Logits of about unit size (the inputs are normed): the scores
        # spread, and a step's rows spread over the experts. The values are
        # bf16's (a published gate matrix is), held in float32.
        "w_router": w((ns, d, e), d, jnp.bfloat16).astype(jnp.float32),
        # Small and not zero: choosing by score + bias and weighting by the
        # score are then different things.
        "router_bias": 0.05 * jax.random.normal(
            next(keys), (ns, e), jnp.float32),
        "w_gate_up": w_experts((e, d, 2 * fe), d),
        "we_down": w_experts((e, fe, d), fe, back),
        "ws_gate": w((ns, d, fs), d), "ws_up": w((ns, d, fs), d),
        "ws_down": w((ns, fs, d), fs, scale=back),
    }
    return {
        # Rows of unit size over the multiplier: what enters the first layer
        # is the token's own embedding at unit scale.
        "embed": w((v, d), 1.0, scale=1.0 / cfg.embedding_multiplier),
        "layers": {"dense": dense, "sparse": sparse},
        "final_norm": jnp.ones((d,), dtype),
        "lm_head": w((d, v), d),
    }


def _attention(cfg, rope, positions, chunk_lens, hidden, lp, view, layer,
               span, rotates):
    """The attention branch [B, T, D] of ``hidden`` (before its post norm)
    and the tokens' new K and V in pool layout [Hkv, B, T, Dh]. ``span``
    and ``rotates`` are the layer's two scalars."""
    b, t, _ = hidden.shape
    h, hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    with jax.named_scope("attn_proj"):
        x = rms_norm(hidden, lp["attn_norm"], cfg.rms_norm_eps)
        # A layer that does not rotate turns by no angle.
        cos, sin = (jnp.where(rotates, table, rest)
                    for table, rest in zip(rope, (1.0, 0.0)))
        q = head_norm_rope((x @ lp["wq"]).reshape(b, t, h, dh),
                           lp["q_norm"], cfg.rms_norm_eps, cos, sin)
        k = head_norm_rope((x @ lp["wk"]).reshape(b, t, hkv, dh),
                           lp["k_norm"], cfg.rms_norm_eps, cos, sin)
        v = (x @ lp["wv"]).reshape(b, t, hkv, dh)
        gate = jax.nn.sigmoid((x @ lp["wg"]).astype(jnp.float32))
    with jax.named_scope("attn_core"), jax.named_scope("attn_span"):
        attn = attend(q, k, v, positions, chunk_lens, view, layer,
                      scale=dh ** -0.5, span=span)
    with jax.named_scope("attn_proj"):
        gated = (attn.reshape(b, t, h * dh).astype(jnp.float32)
                 * gate).astype(hidden.dtype)
        branch = gated @ lp["wo"]
    return branch, k.transpose(2, 0, 1, 3), v.transpose(2, 0, 1, 3)


def _gated_ffn(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def _sparse_ffn(cfg, x, lp, experts, group_base, valid, interpret):
    """One sparse layer's FFN of the normed stream ``x``: (its branch, its
    counters, its choices); ``experts`` are the WHOLE stacks (w_gate_up
    [n_sparse * E, D, 2F], w_down [n_sparse * E, F, D]) and ``group_base``
    this layer's first group in them (models/deepseek_v3.py:_sparse_ffn)."""
    b, t, d = x.shape
    flat = x.reshape(b * t, d)
    idx, w = moe.route(
        flat, lp["w_router"], lp["router_bias"], cfg.num_experts_per_tok,
        cfg.routed_scaling_factor, cfg.norm_topk_prob)
    routed, stats = moe.expert_ffn(
        flat, idx + group_base, w, valid.reshape(b * t), *experts,
        interpret=interpret)
    with jax.named_scope("moe_shared"):
        shared = _gated_ffn(x, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    return shared + routed.reshape(b, t, d).astype(x.dtype), stats, idx


def forward(
    params: Params,
    cfg: ModelConfig,
    token_ids: jax.Array,     # [B, T]
    positions: jax.Array,     # [B, T]
    chunk_lens: jax.Array,    # [B] valid tokens per row (0: the row is inert)
    view: KVView = KVView(),  # the K/V this forward may read
    *,
    act_sharding=None,        # sequence parallelism: refused for this family
    lora=None,                # LORA_TARGETS is empty
    routing: bool = False,    # also return every sparse layer's choices
):
    """Returns (hidden [B,T,D], k_new [L,Hkv,B,T,Dh], v_new, stats int32[4]
    as ``FORWARD_STATS``) and, with ``routing``, the chosen experts
    [n_sparse, B*T, k]. Tokens at or past a row's ``chunk_lens`` reach no
    expert."""
    b, t = token_ids.shape
    nd = cfg.first_k_dense_replace
    ns = cfg.num_layers - nd
    with jax.named_scope("embed"):
        hidden = params["embed"][token_ids]
        hidden = hidden.astype(view.act_dtype(params["embed"].dtype))
        hidden = hidden * jnp.asarray(cfg.embedding_multiplier, hidden.dtype)
    rope = _rope_cos_sin(positions, cfg.head_dim_, cfg.rope_theta)
    valid = jnp.arange(t, dtype=jnp.int32)[None, :] < chunk_lens[:, None]
    dense, sparse = params["layers"]["dense"], params["layers"]["sparse"]
    experts = tuple(
        sparse[k].reshape(-1, *sparse[k].shape[2:])
        for k in ("w_gate_up", "we_down"))
    rest = {k: x for k, x in sparse.items()
            if k not in ("w_gate_up", "we_down")}
    # A layer's kind of attention: two scalars, indexed by the layer.
    layer_spans = spans(cfg)
    span_of = jnp.asarray(layer_spans)
    rotates_of = jnp.asarray(layer_spans != NO_SPAN)

    def layer_of(stack, at):
        # One layer of a stack, sliced where it is used (olmo_hybrid.py).
        return jax.tree.map(
            lambda x: jax.lax.dynamic_index_in_dim(x, at, 0, False), stack)

    def view_of(at):
        pick = lambda x: None if x is None else \
            jax.lax.dynamic_index_in_dim(x, at, 0, False)  # noqa: E731
        return view._replace(
            win_k=pick(view.win_k), win_v=pick(view.win_v),
            ring_k=pick(view.ring_k), ring_v=pick(view.ring_v)), \
            (at if view.pool_k is not None else None)

    def attention(hidden, lp, at):
        branch, k_l, v_l = _attention(
            cfg, rope, positions, chunk_lens, hidden, lp, *view_of(at),
            span_of[at], rotates_of[at])
        with jax.named_scope("attn_proj"):
            hidden = hidden + rms_norm(branch, lp["post_attn_norm"],
                                       cfg.rms_norm_eps)
        return hidden, k_l, v_l

    def dense_layer(hidden, i):
        lp = layer_of(dense, i)
        hidden, k_l, v_l = attention(hidden, lp, i)
        with jax.named_scope("ffn"):
            x = rms_norm(hidden, lp["mlp_norm"], cfg.rms_norm_eps)
            branch = _gated_ffn(x, lp["w_gate"], lp["w_up"], lp["w_down"])
            hidden = hidden + rms_norm(branch, lp["post_mlp_norm"],
                                       cfg.rms_norm_eps)
        return hidden, (k_l, v_l)

    kv = []
    if nd > 1:
        # A scan of their own: one copy of a dense layer's code.
        hidden, dense_kv = jax.lax.scan(
            dense_layer, hidden, jnp.arange(nd, dtype=jnp.int32))
        kv.append(dense_kv)
    else:
        for i in range(nd):
            hidden, (k_l, v_l) = dense_layer(hidden, jnp.int32(i))
            kv.append((k_l[None], v_l[None]))

    def step(carry, i):
        hidden, stats = carry
        lp = layer_of(rest, i)
        hidden, k_l, v_l = attention(hidden, lp, nd + i)
        with jax.named_scope("ffn"):
            x = rms_norm(hidden, lp["mlp_norm"], cfg.rms_norm_eps)
            branch, st, idx = _sparse_ffn(
                cfg, x, lp, experts, i * cfg.n_routed_experts, valid,
                view.interpret)
            hidden = hidden + rms_norm(branch, lp["post_mlp_norm"],
                                       cfg.rms_norm_eps)
        return (hidden, stats + st), (k_l, v_l, idx if routing else None)

    (hidden, stats), (k_s, v_s, chosen) = jax.lax.scan(
        step, (hidden, jnp.zeros((len(FORWARD_STATS),), jnp.int32)),
        jnp.arange(ns, dtype=jnp.int32))
    k_new = jnp.concatenate([*(k for k, _ in kv), k_s], axis=0)
    v_new = jnp.concatenate([*(v for _, v in kv), v_s], axis=0)
    hidden = rms_norm(hidden, params["final_norm"], cfg.rms_norm_eps)
    out = (hidden, k_new, v_new, stats)
    return out + (chosen,) if routing else out
