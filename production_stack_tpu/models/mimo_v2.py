"""MiMo-V2 decoder (HF ``mimo_v2``): full-attention layers mixed with layers
whose queries see a WINDOW of keys and whose sequences keep exactly that
window, the two kinds with different KV head counts, keys wider than values,
a partial rope, a learned sink in the window layers' softmax, one leading
dense FFN and sigmoid-routed sparse experts with no shared one, of which a
chip may hold a SHARE (expert parallelism) — functional JAX.

The same shape of module as models/lfm2_moe.py (the declarations under
"What the rest of the tree asks of this module", attention through
``attend`` over whatever ``KVView`` the runner built, parameters stacked BY
KIND, a layer's operator and its FFN two independent kinds, weights closed
over and sliced where used, a second kind of cache declared as ``StateSpec``s
that the runner owns, ops/moe.py's router and experts, the counters
``FORWARD_STATS`` names returned last). Of its own:

  * A layer's attention is ``full_attention`` or ``sliding_attention``
    (``cfg.layer_types``, HF's ``hybrid_layer_pattern`` 0 / 1, in ANY
    order). A full layer has ``num_kv_heads`` KV heads, rotates by
    ``rope_theta`` and pages its keys and values: through ``attend`` with
    the model's scale ``head_dim ** -0.5``. A window layer has
    ``swa_num_kv_heads``, rotates by ``swa_rope_theta`` and keeps, a
    sequence, the ``sliding_window`` newest keys and values as a RING in a
    state slot (ops/attention.py:window_ring_attend / window_ring_write
    for a prefill chunk, window_ring_step for a decode step: on a TPU one
    kernel in place in the carried rings): position p in slot p mod W,
    nothing paged, nothing held behind the bound. A query at position i
    sees ``0 <= i - j < W``: the token and the W - 1 before it.
  * Keys are ``head_dim`` wide and values ``v_head_dim``. Rotate-half rope
    over the first ``rotary_dim`` lanes of q and k, the others as they
    are. Values are scaled by ``attention_value_scale`` where they are
    made, so the pool and the ring hold them scaled. No QK norm, no bias.
  * A window layer's softmax takes one learned logit a query head into its
    denominator (``swa_attention_sink``; ops/attention.py:sink_merged): a
    key every query sees, whose value is zero.
  * The paged kernels take K and V rows of ONE width of whole 128-lane
    tiles, so a full layer's row is keys and values each padded with zeros
    to ``paged_width`` lanes (256 at the published 192 / 128), the query
    zero past the keys' lanes, the output's first ``v_head_dim`` lanes
    kept.
  * Routing (ops/moe.py): float32 sigmoid scores over the router's WHOLE
    width (``n_routed_experts * ep_size``), top-k of score + bias, weights
    the scores over their sum. This chip holds experts ``ep_rank *
    n_routed_experts`` on, ``n_routed_experts`` of them; a pair whose
    expert lies elsewhere adds nothing here and is counted
    (``assignments_elsewhere``). With ``ep_size`` 1 every expert is here
    and the counters are ops/moe.py's four.

tests/reference/mimo_v2_ref.py is the plain statement of the same equations
this module is held to.

Device scopes: ``attn_proj`` (norm, projections, rope), ``attn_core`` with
the inner ``ring_attend`` / ``attn_sink`` / ``ring_write`` of a window
layer (a decode step's under ``ring_step``, the kernel's time whole under
``ring_attend``), ``ffn`` (the dense FFN; a sparse layer's norm and sum) and
inside it ``moe_route`` and ``moe_experts`` (inner ``moe_gmm``); ``embed``,
``logits``.
"""

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from production_stack_tpu.models.config import (
    ANY_ORDER_LISTS,
    CacheSpecs,
    ModelConfig,
    PagedKVSpec,
    StateSpec,
)
from production_stack_tpu.models.llama import (
    _rope_cos_sin,
    compute_logits,  # noqa: F401 — the untied head is llama's
    rms_norm,
)
from production_stack_tpu.ops import moe
from production_stack_tpu.ops.attention import (
    KVView,
    attend,
    window_ring_attend,
    window_ring_step,
    window_ring_write,
)

Params = Dict

# --- What the rest of the tree asks of this module (see models/llama.py) ----
# HF checkpoint suffix -> (our leaf, transpose?): ASSUMED names (deployment.
# json of mimo-v2.5-ep16 says so). ``self_attn.qkv_proj.weight`` (the
# published ``attention_projection_layout: fused_qkv``) is taken apart into
# the three by ``split_fused`` before this map is asked.
HF_LAYER_MAP = {
    "input_layernorm.weight": ("attn_norm", False),
    "post_attention_layernorm.weight": ("ffn_norm", False),
    "self_attn.q_proj.weight": ("wq", True),
    "self_attn.k_proj.weight": ("wk", True),
    "self_attn.v_proj.weight": ("wv", True),
    "self_attn.o_proj.weight": ("wo", True),
    "self_attn.attention_sink_bias": ("sink", False),
    "mlp.gate_proj.weight": ("w_gate", True),
    "mlp.up_proj.weight": ("w_up", True),
    "mlp.down_proj.weight": ("w_down", True),
    "mlp.gate.weight": ("w_router", True),
    "mlp.gate.e_score_correction_bias": ("router_bias", False),
    "mlp.experts.*.gate_proj.weight": ("we_gate", True),
    "mlp.experts.*.up_proj.weight": ("we_up", True),
    "mlp.experts.*.down_proj.weight": ("we_down", True),
}
HF_TOP_MAP = {
    "model.embed_tokens.weight": ("embed", False),
    "model.norm.weight": ("final_norm", False),
    "lm_head.weight": ("lm_head", True),
}
# No LoRA on this family yet: the experts have no delta path (the engine
# refuses --lora-modules on an empty tuple).
LORA_TARGETS = ()
# ``attn_impl=auto`` may resolve to the Pallas paged decode for the full
# layers: tests/test_mimo_v2.py holds the engine's logits on that path to
# the reference.
PAGED_DECODE_VALIDATED = True
# Leaves a checkpoint load keeps in float32 whatever the engine's dtype.
FLOAT32_LEAVES = ("w_router", "router_bias", "sink")
# int32 counters ``forward`` returns last, summed over its sparse layers.
# With them the pairs routed to experts held elsewhere (``STATS_EP``): a
# module-wide name, 0 for ever where every expert is here.
FORWARD_STATS = moe.STATS_EP

_KINDS = ANY_ORDER_LISTS["mimo_v2"]   # ("sliding_attention", "full_attention")
_ATTN = ("attn_norm", "wq", "wk", "wv", "wo")
_LEAVES = {                                   # as loaded, by kind
    "full": _ATTN,
    "window": _ATTN + ("sink",),
    "dense": ("ffn_norm", "w_gate", "w_up", "w_down"),
    "sparse": ("ffn_norm", "w_router", "router_bias", "we_gate", "we_up",
               "we_down"),
}


def position_bound(cfg: ModelConfig) -> Optional[int]:
    """None: RoPE takes any position."""
    return None


def _operators(cfg: ModelConfig):
    """Per layer, (its attention's stack, its index there)."""
    seen = {"window": 0, "full": 0}
    out = []
    for t in cfg.layer_types:
        kind = "window" if t == _KINDS[0] else "full"
        out.append((kind, seen[kind]))
        seen[kind] += 1
    return out


def window_layers(cfg: ModelConfig):
    """The layers that keep a ring (``GET /debug/programs``)."""
    return [i for i, t in enumerate(cfg.layer_types) if t == _KINDS[0]]


def _counts(cfg: ModelConfig) -> Tuple[int, int]:
    n_window = len(window_layers(cfg))
    return n_window, cfg.num_layers - n_window


def kv_heads(cfg: ModelConfig, kind: str) -> int:
    return cfg.swa_num_kv_heads if kind == "window" else cfg.num_kv_heads


def paged_width(cfg: ModelConfig) -> int:
    """Lanes of a full layer's paged K row and V row: the wider of the two
    in whole 128-lane tiles."""
    return -(-max(cfg.head_dim_, cfg.v_head_dim) // 128) * 128


def ring_width(lanes: int) -> int:
    """Lanes of a ring's row: a head's in whole 128-lane tiles. What the
    row takes in HBM whatever is declared (192 lanes lie in 256), declared,
    because only an array of whole tiles can be sliced where it lies
    (ops/pallas/window_ring.py); zeros past the head's own."""
    return -(-lanes // 128) * 128


def held_experts(cfg: ModelConfig) -> Tuple[int, int]:
    """(the first expert this chip holds, how many), of every sparse
    layer's ``n_routed_experts * ep_size``."""
    return cfg.ep_rank * cfg.n_routed_experts, cfg.n_routed_experts


def layer_slots(cfg: ModelConfig):
    """Per layer, {leaf: (stack, index in it)}: a layer's attention and its
    FFN are filed apart, each under its own kind."""
    nd = cfg.first_k_dense_replace
    out = []
    for i, op in enumerate(_operators(cfg)):
        ffn = ("dense", i) if i < nd else ("sparse", i - nd)
        out.append({**dict.fromkeys(_LEAVES[op[0]], op),
                    **dict.fromkeys(_LEAVES[ffn[0]], ffn)})
    return out


def required_layer_leaves(cfg: ModelConfig) -> dict:
    """Per kind, the leaves every valid checkpoint must provide."""
    need = {kind: set(leaves) for kind, leaves in _LEAVES.items()}
    if not cfg.swa_attention_sink:
        need["window"].discard("sink")
    if not cfg.first_k_dense_replace:
        del need["dense"]
    return need


def split_fused(cfg: ModelConfig, layer: int, suffix: str, tensor):
    """A checkpoint tensor as the (suffix, tensor) pairs ``HF_LAYER_MAP``
    knows: ``self_attn.qkv_proj.weight`` [q + k + v rows, D] is the three
    projections' rows in that order (ASSUMED), the layer's kind giving the
    KV heads."""
    if suffix != "self_attn.qkv_proj.weight":
        return ((suffix, tensor),)
    hkv = kv_heads(cfg, _operators(cfg)[layer][0])
    q = cfg.num_heads * cfg.head_dim_
    k = hkv * cfg.head_dim_
    return (("self_attn.q_proj.weight", tensor[:q]),
            ("self_attn.k_proj.weight", tensor[q:q + k]),
            ("self_attn.v_proj.weight", tensor[q + k:]))


def finish_params(cfg: ModelConfig, params: Params) -> Params:
    """Last step of a checkpoint load: an expert's gate and up matrices
    become one (gate then up), a model published without a sink or a
    router bias gets zeros, and the table and the head keep the
    vocabulary's slice this chip serves (its first ``vocab_size`` rows)."""
    layers = params["layers"]
    sparse = layers["sparse"]
    if "we_gate" in sparse:
        sparse["w_gate_up"] = jnp.concatenate(
            [sparse.pop("we_gate"), sparse.pop("we_up")], axis=-1)
    if "router_bias" not in sparse:
        sparse["router_bias"] = jnp.zeros(
            sparse["w_router"].shape[::2], jnp.float32)
    if "sink" not in layers["window"]:
        layers["window"]["sink"] = jnp.full(
            (layers["window"]["wq"].shape[0], cfg.num_heads), -jnp.inf,
            jnp.float32)
    params["embed"] = params["embed"][:cfg.vocab_size]
    params["lm_head"] = params["lm_head"][:, :cfg.vocab_size]
    return params


def cache_specs(cfg: ModelConfig) -> CacheSpecs:
    """Paged K and V for the FULL layers only, a row of ``paged_width``
    lanes each; per sequence and window layer the ring's keys and its
    values, head-major ``[Hkv, W, D]`` in the activations' dtype, STORED in
    rows of ``ring_width(D)`` lanes (W rows of whole lane tiles a head: 192
    lanes take 256 in HBM either way, and 8 heads on the rows' axis would
    pad to 16)."""
    n_window, n_full = _counts(cfg)
    hkv, w = cfg.swa_num_kv_heads, cfg.sliding_window
    return CacheSpecs(
        PagedKVSpec(n_full, cfg.num_kv_heads, paged_width(cfg)),
        tuple(StateSpec(name, n_window, (hkv, w, d), None, ring_width(d))
              for name, d in (("ring_k", cfg.head_dim_),
                              ("ring_v", cfg.v_head_dim))),
    )


def ring_report(cfg: ModelConfig) -> Dict:
    """What ``GET /version`` and ``GET /debug/programs`` say of this
    module's caches and experts."""
    first, count = held_experts(cfg)
    return {
        "window_layers": window_layers(cfg),
        "ring": {s.name: list(s.shape) for s in cache_specs(cfg).state},
        "experts_held": [first, first + count],
        "experts_routed": count * cfg.ep_size,
    }


def init_params(cfg: ModelConfig, rng: jax.Array, dtype=jnp.bfloat16) -> Params:
    d, f, dk, dv = (cfg.hidden_size, cfg.intermediate_size, cfg.head_dim_,
                    cfg.v_head_dim)
    h, v = cfg.num_heads, cfg.vocab_size
    e, fe = cfg.n_routed_experts, cfg.moe_intermediate_size
    n_window, n_full = _counts(cfg)
    nd = cfg.first_k_dense_replace
    ns = cfg.num_layers - nd
    keys = iter(jax.random.split(rng, 40))
    # Random weights that behave as a trained model's do where routing looks
    # (models/deepseek_v3.py:init_params says why): the residual stream is
    # the token's own embedding at unit scale plus SMALL branches, every
    # projection back into the stream drawn at 1/sqrt(2 L) of fan-in scale
    # for the depth the model is published with (48), whatever part of it
    # is served.
    back = (2 * 48) ** -0.5

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

    def attn(n, hkv):
        return {
            "attn_norm": jnp.ones((n, d), dtype),
            # Twice fan-in scale each: there is no QK norm, so the scores'
            # spread is the projections', and at about 4 attention picks a
            # few of a prompt's tokens instead of averaging them all
            # (PERF.md section 6, PR 44).
            "wq": w((n, d, h * dk), d, scale=2.0),
            "wk": w((n, d, hkv * dk), d, scale=2.0),
            "wv": w((n, d, hkv * dv), d),
            "wo": w((n, h * dv, d), h * dv, scale=back),
        }

    window = attn(n_window, cfg.swa_num_kv_heads)
    # Near the largest of a window's scores: the sink takes a real share of
    # a head's softmax, as a trained model's does, and a comparison that
    # drops it sees it.
    window["sink"] = 8.0 + 2.0 * jax.random.normal(
        next(keys), (n_window, h), jnp.float32) if cfg.swa_attention_sink \
        else jnp.full((n_window, h), -jnp.inf, jnp.float32)
    dense = {
        "ffn_norm": jnp.ones((nd, d), dtype),
        "w_gate": w((nd, d, f), d), "w_up": w((nd, d, f), d),
        "w_down": w((nd, f, d), f, scale=back),
    }
    sparse = {
        "ffn_norm": jnp.ones((ns, d), dtype),
        # Logits of about unit size (the inputs are normed): the scores
        # spread, and a step's rows spread over the experts. The values are
        # bf16's (a published gate matrix is), held in float32. The
        # router's WHOLE width, whatever share of the experts is here.
        "w_router": w((ns, d, e * cfg.ep_size), d,
                      jnp.bfloat16).astype(jnp.float32),
        # Small and not zero: choosing by score + bias and weighting by the
        # score are then different things.
        "router_bias": 0.05 * jax.random.normal(
            next(keys), (ns, e * cfg.ep_size), jnp.float32),
        "w_gate_up": w_experts((e, d, 2 * fe), d),
        "we_down": w_experts((e, fe, d), fe, back),
    }
    return {
        "embed": w((v, d), 1),
        "layers": {"full": attn(n_full, cfg.num_kv_heads), "window": window,
                   "dense": dense, "sparse": sparse},
        "final_norm": jnp.ones((d,), dtype),
        "lm_head": w((d, v), d),
    }


def partial_rope(x: jax.Array, cos, sin) -> jax.Array:
    """Rotate-half rope over the first ``2 * cos.shape[-1]`` lanes of x
    [B, T, H, Dh], the others as they are; float32, one rounding."""
    r = 2 * cos.shape[-1]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :r // 2], xf[..., r // 2:r]
    c, s = cos[:, :, None, :], sin[:, :, None, :]
    return jnp.concatenate(
        [x1 * c - x2 * s, x2 * c + x1 * s, xf[..., r:]],
        axis=-1).astype(x.dtype)


def _project(cfg, rope, hidden, lp, hkv):
    """q [B, T, H, Dk], k [B, T, Hkv, Dk] (both after rope) and the SCALED
    values [B, T, Hkv, Dv] of one layer."""
    b, t, _ = hidden.shape
    h, dk, dv = cfg.num_heads, cfg.head_dim_, cfg.v_head_dim
    with jax.named_scope("attn_proj"):
        x = rms_norm(hidden, lp["attn_norm"], cfg.rms_norm_eps)
        # The products as they are written: without the barrier XLA folds
        # the split into heads into the product and, for that, lays every
        # layer's matrix out again a dispatch (a copy of the whole stack
        # for a described v5e: 0.9 GB of temporaries for W_q alone).
        q, k, v = jax.lax.optimization_barrier(
            (x @ lp["wq"], x @ lp["wk"], x @ lp["wv"]))
        q = partial_rope(q.reshape(b, t, h, dk), *rope)
        k = partial_rope(k.reshape(b, t, hkv, dk), *rope)
        v = v.reshape(b, t, hkv, dv)
        v = (v.astype(jnp.float32)
             * cfg.attention_value_scale).astype(v.dtype)
    return q, k, v


def _out(cfg, attn, lp):
    b, t = attn.shape[:2]
    with jax.named_scope("attn_proj"):
        return attn.reshape(b, t, cfg.num_heads * cfg.v_head_dim) @ lp["wo"]


def _full_attention(cfg, rope, positions, chunk_lens, hidden, lp, view,
                    layer):
    """A full layer's branch [B, T, D] and the tokens' new K and V in pool
    layout [Hkv, B, T, paged_width]."""
    q, k, v = _project(cfg, rope, hidden, lp, cfg.num_kv_heads)
    width = paged_width(cfg)

    def padded(x):
        return jnp.pad(x, ((0, 0),) * 3 + ((0, width - x.shape[-1]),))

    k, v = padded(k), padded(v)
    with jax.named_scope("attn_core"):
        attn = attend(padded(q), k, v, positions, chunk_lens, view, layer,
                      scale=cfg.head_dim_ ** -0.5)[..., :cfg.v_head_dim]
    return _out(cfg, attn, lp), k.transpose(2, 0, 1, 3), \
        v.transpose(2, 0, 1, 3)


def _window_attention(cfg, rope, positions, chunk_lens, hidden, lp, ring):
    """A window layer's branch [B, T, D] from the rows' rings of this layer
    BEFORE the chunk (k [B, Hkv, W, Dk], v [B, Hkv, W, Dv]), and the
    chunk's keys and scaled values for ``window_ring_write``."""
    q, k, v = _project(cfg, rope, hidden, lp, cfg.swa_num_kv_heads)
    with jax.named_scope("attn_core"):
        attn = window_ring_attend(
            q, k, v, positions, chunk_lens, *ring,
            scale=cfg.head_dim_ ** -0.5,
            sink=lp["sink"] if cfg.swa_attention_sink else None)
    return _out(cfg, attn, lp), k, v


def _dense_ffn(cfg, hidden, lp):
    with jax.named_scope("ffn"):
        x = rms_norm(hidden, lp["ffn_norm"], cfg.rms_norm_eps)
        return hidden + \
            (jax.nn.silu(x @ lp["w_gate"]) * (x @ lp["w_up"])) @ lp["w_down"]


def _sparse_ffn(cfg, hidden, lp, experts, group_base, valid, interpret):
    """(hidden after one sparse layer's FFN, its counters, its choices);
    ``experts`` are the WHOLE stacks HELD (w_gate_up [n_sparse * E, D, 2F],
    w_down [n_sparse * E, F, D]) and ``group_base`` this layer's first
    group in them (models/deepseek_v3.py:_sparse_ffn). The choices count
    the router's whole width."""
    b, t, d = hidden.shape
    first, count = held_experts(cfg)
    with jax.named_scope("ffn"):
        x = rms_norm(hidden, lp["ffn_norm"], cfg.rms_norm_eps)
        flat = x.reshape(b * t, d)
        idx, w = moe.route(
            flat, lp["w_router"], lp["router_bias"], cfg.num_experts_per_tok,
            cfg.routed_scaling_factor, cfg.norm_topk_prob)
        here = (idx >= first) & (idx < first + count)
        routed, stats = moe.expert_ffn(
            flat, idx - first + group_base, w, valid.reshape(b * t),
            *experts, interpret=interpret, here=here)
        return hidden + routed.reshape(b, t, d).astype(hidden.dtype), \
            stats, idx


def operator_tables(cfg: ModelConfig):
    """Of the SPARSE layers, in order: (is the layer a window layer, its
    index among the window layers, its index among the full layers), int32
    arrays; the index of the kind a layer is not is 0 and not read."""
    ops = _operators(cfg)[cfg.first_k_dense_replace:]
    is_window = np.array([kind == "window" for kind, _ in ops], np.int32)
    at = np.array([i for _, i in ops], np.int32)
    return is_window, at * is_window, at * (1 - is_window)


def forward(
    params: Params,
    cfg: ModelConfig,
    token_ids: jax.Array,     # [B, T]
    positions: jax.Array,     # [B, T]
    chunk_lens: jax.Array,    # [B] valid tokens per row (0: the row is inert)
    view: KVView = KVView(),  # the K/V of the FULL layers
    *,
    state: Optional[Tuple[jax.Array, jax.Array]] = None,
    act_sharding=None,        # sequence parallelism: refused for this family
    lora=None,                # LORA_TARGETS is empty
    routing: bool = False,    # also return every sparse layer's choices
):
    """Returns (hidden [B,T,D], k_new [n_full,Hkv,B,T,paged_width], v_new,
    state, stats int32[5] as ``FORWARD_STATS``) and, with ``routing``, the
    chosen experts [n_sparse, B*T, k] (of the router's whole width).

    ``state``: (the rows' ring keys [B, n_window, Hkv, W, ring_width(Dk)],
    their ring values [.., ring_width(Dv)]) before the first token, one array
    per spec of ``cache_specs`` (as ``StateSpec.stored``), rows first as the
    runner's pools are; ``None`` starts every row from empty rings (a whole sequence in one call: then
    ``positions`` start at 0). The returned state is that after each row's
    last valid token. The view's layer axis counts the full layers only. A
    row's ``positions`` are consecutive from its first."""
    b, t = token_ids.shape
    nd = cfg.first_k_dense_replace
    ns = cfg.num_layers - nd
    with jax.named_scope("embed"):
        hidden = params["embed"][token_ids]
        hidden = hidden.astype(view.act_dtype(params["embed"].dtype))
    if state is None:
        state = tuple(
            jnp.zeros((b, s.layers, *s.stored), s.dtype or hidden.dtype)
            for s in cache_specs(cfg).state)
    rings = tuple(state)
    # A layer's rope: its kind's table (two tables a forward, chosen by a
    # scalar of the layer).
    ropes = {kind: _rope_cos_sin(positions, cfg.rotary_dim or cfg.head_dim_,
                                 theta)
             for kind, theta in (("full", cfg.rope_theta),
                                 ("window", cfg.swa_rope_theta))}
    valid = jnp.arange(t, dtype=jnp.int32)[None, :] < chunk_lens[:, None]
    layers = params["layers"]
    sparse = layers["sparse"]
    experts = tuple(
        sparse[k].reshape(-1, *sparse[k].shape[2:])
        for k in ("w_gate_up", "we_down"))
    rest = {k: x for k, x in sparse.items()
            if k not in ("w_gate_up", "we_down")}
    hkv_f, width = cache_specs(cfg).paged_kv[1:]
    hkv_w, dk, dv = cfg.swa_num_kv_heads, cfg.head_dim_, cfg.v_head_dim

    def layer_of(stack, at):
        # One layer of a stack, sliced where it is used (olmo_hybrid.py).
        return jax.tree.map(
            lambda x: jax.lax.dynamic_index_in_dim(x, at, 0, False), stack)

    def of_layer(x, p):
        return None if x is None else \
            jax.lax.dynamic_index_in_dim(x, p, 0, False)

    def full(hidden, rings, w_at, f_at):
        branch, k_l, v_l = _full_attention(
            cfg, ropes["full"], positions, chunk_lens, hidden,
            layer_of(layers["full"], f_at),
            view._replace(win_k=of_layer(view.win_k, f_at),
                          win_v=of_layer(view.win_v, f_at),
                          ring_k=of_layer(view.ring_k, f_at),
                          ring_v=of_layer(view.ring_v, f_at)),
            f_at if view.pool_k is not None else None)
        # Nothing for a ring: the write below sees no valid token.
        return (hidden + branch, k_l, v_l,
                jnp.zeros((b, t, hkv_w, dk), hidden.dtype),
                jnp.zeros((b, t, hkv_w, dv), hidden.dtype))

    def window(hidden, rings, w_at, f_at):
        with jax.named_scope("attn_core"), jax.named_scope("ring_attend"):
            ring = tuple(jax.lax.dynamic_index_in_dim(r, w_at, 1, False)
                         for r in rings)
        branch, k_c, v_c = _window_attention(
            cfg, ropes["window"], positions, chunk_lens, hidden,
            layer_of(layers["window"], w_at), ring)
        kv = jnp.zeros((hkv_f, b, t, width), hidden.dtype)
        return hidden + branch, kv, kv, k_c, v_c

    def window_qkv(hidden, w_at, f_at):
        q, k_c, v_c = _project(
            cfg, ropes["window"], hidden,
            {k: of_layer(layers["window"][k], w_at)
             for k in ("attn_norm", "wq", "wk", "wv")}, hkv_w)
        kv = jnp.zeros((hkv_f, b, t, width), hidden.dtype)
        return hidden, kv, kv, q, k_c, v_c

    def full_step(hidden, w_at, f_at):
        hidden, k_l, v_l, k_c, v_c = full(hidden, None, w_at, f_at)
        return (hidden, k_l, v_l,
                jnp.zeros((b, t, cfg.num_heads, dk), hidden.dtype), k_c, v_c)

    def ring_step(rings, w_at, q, k_c, v_c, live):
        with jax.named_scope("attn_core"):
            return window_ring_step(
                rings, w_at, q, k_c, v_c, positions, live,
                scale=dk ** -0.5,
                sink=of_layer(layers["window"]["sink"], w_at)
                if cfg.swa_attention_sink else None,
                interpret=view.interpret)

    def back(hidden, attn, w_at):
        return hidden + _out(
            cfg, attn, {"wo": of_layer(layers["window"]["wo"], w_at)})

    def window_only(hidden, rings, w_at, f_at):
        hidden, k_l, v_l, q, k_c, v_c = window_qkv(hidden, w_at, f_at)
        attn, rings = ring_step(rings, w_at, q, k_c, v_c, chunk_lens)
        return back(hidden, attn, w_at), rings, k_l, v_l

    def full_only(hidden, rings, w_at, f_at):
        hidden, k_l, v_l = full(hidden, rings, w_at, f_at)[:3]
        return hidden, rings, k_l, v_l

    def step_attention(hidden, rings, is_window, w_at, f_at):
        """A decode step's attention (T == 1) of either kind. A window
        layer's ring is read AND written by one statement
        (``window_ring_step``), which stands OUTSIDE the ``cond``s, as the
        write of a chunk does, so that no branch returns the carry: the
        first ``cond`` projects (a full layer attends there too and hands
        the step no live row, for which it moves no byte), the second
        projects a window layer's attention back."""
        if isinstance(is_window, bool):
            return (window_only if is_window else full_only)(
                hidden, rings, w_at, f_at)
        hidden, k_l, v_l, q, k_c, v_c = jax.lax.cond(
            is_window > 0, window_qkv, full_step, hidden, w_at, f_at)
        attn, rings = ring_step(
            rings, w_at, q, k_c, v_c,
            chunk_lens * is_window.astype(chunk_lens.dtype))
        hidden = jax.lax.cond(
            is_window > 0, back, lambda x, *_: x, hidden, attn, w_at)
        return hidden, rings, k_l, v_l

    def attention(hidden, rings, is_window, w_at, f_at):
        """One layer's attention of either kind: the rings pass into the
        ``cond`` to be READ (a window layer's branch takes its layer out of
        them) and come out through the write below, which both kinds share
        and which a full layer hands no valid token."""
        if t == 1:
            return step_attention(hidden, rings, is_window, w_at, f_at)
        if isinstance(is_window, bool):
            hidden, k_l, v_l, k_c, v_c = (window if is_window else full)(
                hidden, rings, w_at, f_at)
        else:
            hidden, k_l, v_l, k_c, v_c = jax.lax.cond(
                is_window > 0, window, full, hidden, rings, w_at, f_at)
        with jax.named_scope("attn_core"):
            rings = window_ring_write(
                rings, w_at, (k_c, v_c), positions,
                chunk_lens * jnp.asarray(is_window, chunk_lens.dtype))
        return hidden, rings, k_l, v_l

    ops = _operators(cfg)
    kv = []
    # The leading dense layers, each traced with its own kind (one of them
    # in the published model).
    for i in range(nd):
        kind, at = ops[i]
        hidden, rings, k_l, v_l = attention(
            hidden, rings, kind == "window",
            jnp.int32(at if kind == "window" else 0),
            jnp.int32(at if kind == "full" else 0))
        hidden = _dense_ffn(cfg, hidden, layer_of(layers["dense"],
                                                  jnp.int32(i)))
        if kind == "full":
            kv.append((k_l[None], v_l[None]))

    tables = operator_tables(cfg)
    is_window, window_at, full_at = (jnp.asarray(x) for x in tables)

    def step(carry, i):
        hidden, rings, stats = carry
        hidden, rings, k_l, v_l = attention(
            hidden, rings, is_window[i], window_at[i], full_at[i])
        hidden, st, idx = _sparse_ffn(
            cfg, hidden, layer_of(rest, i), experts,
            i * cfg.n_routed_experts, valid, view.interpret)
        return (hidden, rings, stats + st), \
            (k_l, v_l, idx if routing else None)

    (hidden, rings, stats), (k_all, v_all, chosen) = jax.lax.scan(
        step,
        (hidden, rings, jnp.zeros((len(FORWARD_STATS),), jnp.int32)),
        jnp.arange(ns, dtype=jnp.int32))
    # The full layers' rows of the scan's outputs (the others' are zeros
    # nothing reads).
    where = np.flatnonzero(1 - tables[0])
    k_new = jnp.concatenate([*(k for k, _ in kv), k_all[where]], axis=0)
    v_new = jnp.concatenate([*(v for _, v in kv), v_all[where]], axis=0)
    hidden = rms_norm(hidden, params["final_norm"], cfg.rms_norm_eps)
    out = (hidden, k_new, v_new, rings, stats)
    return out + (chosen,) if routing else out
