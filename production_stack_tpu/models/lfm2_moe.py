"""LFM2-MoE decoder (HF ``lfm2_moe``): gated short convolutions in most
layers, rotary GQA with a per-head norm of queries and keys in the others,
two leading dense FFNs and sigmoid-routed sparse experts behind them —
functional JAX.

The same shape of module as models/granite_hybrid.py (the declarations under
"What the rest of the tree asks of this module", attention through
``attend`` over whatever ``KVView`` the runner built, parameters stacked BY
KIND, weights closed over and sliced where used, a second kind of cache
declared as a ``StateSpec`` that the runner owns) and, for the FFN, as
models/deepseek_v3.py (ops/moe.py's router and experts, the counters
``FORWARD_STATS`` names returned last). It is the one module that declares
BOTH a state and counters: ``forward`` returns (hidden, k_new, v_new, state,
stats). Of its own:

  * The gated short convolution: ``[B | C | x] = u W_in``; ``z = B * x``;
    ``c_t = sum_i w[i] z_{t-L+1+i}`` (causal, depthwise, ``conv_L_cache``
    taps, zeros before the sequence, NO activation and no bias:
    ops/gated_delta.py:conv_step / conv_chunk with ``silu=False``); ``y = C
    * c``; ``W_out``. A sequence's whole state a layer is the last L - 1
    tokens of ``z``: a finite window and no recurrence, so it crosses a
    segment boundary inside a PACKED prefill row (``KVView.seg_lens``:
    several sequences' chunks end to end in one row, a row of state a
    segment; ops/gated_delta.py:conv_packed_row), which this module
    declares (``STATES_CROSSING_SEGMENTS``).
  * A layer is TWO independent kinds: its operator (``cfg.layer_types``:
    ``conv`` / ``full_attention``, in ANY order: the published list is not
    equal periods) and its FFN (dense below ``first_k_dense_replace``,
    sparse from there). Parameters are stacked by each (``layers.conv``,
    ``layers.attention``, ``layers.dense``, ``layers.sparse``). The leading
    dense layers (all ``conv``) are a scan of their own; the sparse stack is
    ONE scan over its layers, the operator under a ``lax.cond`` by a table
    made from ``layer_types`` and the sparse FFN behind it: a program holds
    one attention operator, one sparse FFN and one convolution a scan,
    whatever the depth and wherever the attention layers stand.
  * Attention: RMSNorm over each head's lanes of q and of k (one weight of
    ``head_dim`` each) BEFORE rope, both in float32; rope over all lanes,
    non-interleaved; ``head_dim ** -0.5``; KV heads narrower than 128 lanes
    paired into one paged row (``kv_pack``, models/granite_hybrid.py).
  * Routing (ops/moe.py): float32 sigmoid scores, top-k of score +
    ``expert_bias``, weights the scores over their sum + 1e-6; no shared
    expert. A token at or past its row's ``chunk_lens`` (a bucket's padded
    row, a prompt's padding, a decode row past its budget) reaches no
    expert and leaves the conv state as it was.

tests/reference/lfm2_moe_ref.py is the plain statement of the same
equations this module is held to.

Device scopes: the operator's projections, norms and rope under
``attn_proj``; attention and the gated convolution under ``attn_core``, the
convolution (gates, taps, the state's slice and its write-back) under the
inner ``short_conv``; ``ffn`` (the dense FFN; a sparse layer's norm and sum)
and inside it ``moe_route`` and ``moe_experts`` (with its inner
``moe_gmm``); ``embed``, ``logits``.
"""

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from production_stack_tpu.models import llama
from production_stack_tpu.models.config import (
    FREE_LAYER_LISTS,
    CacheSpecs,
    ModelConfig,
    PagedKVSpec,
    StateSpec,
)
from production_stack_tpu.models.granite_hybrid import kv_pack
from production_stack_tpu.models.llama import (
    _rope_cos_sin,
    compute_logits,  # noqa: F401 — the tied head is llama's
    rms_norm,
)
from production_stack_tpu.ops import gated_delta as gd
from production_stack_tpu.ops import moe
from production_stack_tpu.ops.attention import KVView, attend

Params = Dict

# --- What the rest of the tree asks of this module (see models/llama.py) ----
# HF checkpoint suffix -> (our leaf, transpose?): the names of HF's
# Lfm2MoeDecoderLayer (ASSUMED: deployment.json of lfm2-8b-a1b-d16 says so).
# ``experts.*`` stands for an expert's index (models/weights.py stacks those
# on an expert axis behind the layer's).
HF_LAYER_MAP = {
    "operator_norm.weight": ("op_norm", False),
    "ffn_norm.weight": ("ffn_norm", False),
    "conv.in_proj.weight": ("in_proj", True),           # B | C | x
    "conv.conv.weight": ("conv_w", True),               # [D, 1, L] -> [L, 1, D]
    "conv.out_proj.weight": ("out_proj", True),
    "self_attn.q_proj.weight": ("wq", True),
    "self_attn.k_proj.weight": ("wk", True),
    "self_attn.v_proj.weight": ("wv", True),
    "self_attn.out_proj.weight": ("wo", True),
    "self_attn.q_layernorm.weight": ("q_norm", False),
    "self_attn.k_layernorm.weight": ("k_norm", False),
    "feed_forward.w1.weight": ("w_gate", True),
    "feed_forward.w3.weight": ("w_up", True),
    "feed_forward.w2.weight": ("w_down", True),
    "feed_forward.gate.weight": ("w_router", True),
    "feed_forward.expert_bias": ("router_bias", False),
    "feed_forward.experts.*.w1.weight": ("we_gate", True),
    "feed_forward.experts.*.w3.weight": ("we_up", True),
    "feed_forward.experts.*.w2.weight": ("we_down", True),
}
HF_TOP_MAP = {
    "model.embed_tokens.weight": ("embed", False),
    "model.embedding_norm.weight": ("final_norm", False),
    "lm_head.weight": ("lm_head", True),
}
# No LoRA on this family yet: the convolution's projections and the experts
# have no delta path (the engine refuses --lora-modules on an empty tuple).
LORA_TARGETS = ()
# ``attn_impl=auto`` may resolve to the Pallas paged decode for the
# attention layers (64-lane KV heads paired into rows of 128): tests/
# test_lfm2_moe.py holds the engine's logits on that path to the reference.
PAGED_DECODE_VALIDATED = True
# Leaves a checkpoint load keeps in float32 whatever the engine's dtype: the
# router computes in float32 and its bias is published in it.
FLOAT32_LEAVES = ("w_router", "router_bias")
# int32 counters ``forward`` returns last, summed over its sparse layers.
FORWARD_STATS = moe.STATS
# The states of ``cache_specs`` (by ``StateSpec.name``) that cross a segment
# boundary inside a packed prefill row: where the view says ``seg_lens``,
# ``forward`` takes ``state`` with a row a SEGMENT and runs the one row of
# tokens from it (``_conv_op``). A module whose every state is named here may
# be dispatched packed rows (engine/runner.py:prefill_packs).
STATES_CROSSING_SEGMENTS = ("conv",)
# What the weights' sum takes (the published modeling code's; the latent
# family's is 1e-20).
ROUTE_EPS = 1e-6

_KINDS = FREE_LAYER_LISTS["lfm2_moe"]        # ("conv", "full_attention")
_LEAVES = {                                   # as loaded, by kind
    "conv": ("op_norm", "in_proj", "conv_w", "out_proj"),
    "attention": ("op_norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm"),
    "dense": ("ffn_norm", "w_gate", "w_up", "w_down"),
    "sparse": ("ffn_norm", "w_router", "router_bias", "we_gate", "we_up",
               "we_down"),
}


def position_bound(cfg: ModelConfig) -> Optional[int]:
    """None: RoPE takes any position."""
    return None


def _operators(cfg: ModelConfig):
    """Per layer, (its operator's stack, its index there)."""
    seen = {"conv": 0, "attention": 0}
    out = []
    for t in cfg.layer_types:
        kind = "conv" if t == _KINDS[0] else "attention"
        out.append((kind, seen[kind]))
        seen[kind] += 1
    return out


def _counts(cfg: ModelConfig) -> Tuple[int, int]:
    n_conv = sum(t == _KINDS[0] for t in cfg.layer_types)
    return n_conv, cfg.num_layers - n_conv


def layer_slots(cfg: ModelConfig):
    """Per layer, {leaf: (stack, index in it)}: a layer's operator and its
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
    if not cfg.use_expert_bias:
        need["sparse"].discard("router_bias")
    if not cfg.first_k_dense_replace:
        del need["dense"]
    return need


def finish_params(cfg: ModelConfig, params: Params) -> Params:
    """Last step of a checkpoint load: the conv weight loses HF's middle
    axis ([L, 1, D] -> [L, D]), an expert's gate and up matrices become one
    (gate then up), a model published without ``expert_bias`` gets a zero
    one, and a tied head reads ``embed``."""
    layers = params["layers"]
    if layers["conv"]["conv_w"].ndim == 4:
        layers["conv"]["conv_w"] = layers["conv"]["conv_w"][:, :, 0]
    sparse = layers["sparse"]
    if "we_gate" in sparse:
        sparse["w_gate_up"] = jnp.concatenate(
            [sparse.pop("we_gate"), sparse.pop("we_up")], axis=-1)
    if "router_bias" not in sparse:
        sparse["router_bias"] = jnp.zeros(
            sparse["w_router"].shape[::2], jnp.float32)
    return llama.finish_params(cfg, params)


def cache_specs(cfg: ModelConfig) -> CacheSpecs:
    """Paged K/V for the attention layers only (``kv_pack`` KV heads a
    row); per sequence and conv layer the convolution's state, its L - 1
    tokens of ``hidden_size`` channels in the activations' dtype, as rows
    of 128 lanes where they divide (models/olmo_hybrid.py:cache_specs says
    why)."""
    n_conv, n_attn = _counts(cfg)
    pack = kv_pack(cfg)
    conv = (cfg.conv_l_cache - 1) * cfg.hidden_size
    return CacheSpecs(
        PagedKVSpec(n_attn, cfg.num_kv_heads // pack, cfg.head_dim_ * pack),
        (StateSpec("conv", n_conv,
                   (conv // 128, 128) if conv % 128 == 0 else (conv,),
                   None),),
    )


def init_params(cfg: ModelConfig, rng: jax.Array, dtype=jnp.bfloat16) -> Params:
    d, f, dh = cfg.hidden_size, cfg.intermediate_size, cfg.head_dim_
    h, hkv, v = cfg.num_heads, cfg.num_kv_heads, cfg.vocab_size
    e, fe, taps = (cfg.n_routed_experts, cfg.moe_intermediate_size,
                   cfg.conv_l_cache)
    n_conv, n_attn = _counts(cfg)
    nd = cfg.first_k_dense_replace
    ns = cfg.num_layers - nd
    keys = iter(jax.random.split(rng, 32))
    # Random weights that behave as a trained model's do where routing looks
    # (models/deepseek_v3.py:init_params says why): the residual stream is
    # the token's own embedding at unit scale plus SMALL branches, every
    # projection back into the stream drawn at 1/sqrt(2 L) of fan-in scale
    # for the depth the model is published with (24), whatever part of it
    # is served.
    back = (2 * 24) ** -0.5

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

    def spread(shape):
        # A per-head norm's weight: away from 1, so that a comparison that
        # drops the norm (or applies it across heads) sees it, and LARGE
        # (mean square 4.3, so a score's spread is about 4 where weights
        # near 1 give 1): attention then picks a few of a prompt's tokens
        # instead of averaging all of them. With the average every row's
        # stream is a function of its last tokens alone (the convolutions
        # see three), a chat template ends every prompt alike, so every
        # greedy answer was the same tokens and a step's rows crowded onto
        # 12 to 20 experts of 32 by the seed (PERF.md section 6, PR 44).
        return jax.random.uniform(next(keys), shape, jnp.float32,
                                  1.0, 3.0).astype(dtype)

    conv = {
        "op_norm": jnp.ones((n_conv, d), dtype),
        "in_proj": w((n_conv, d, 3 * d), d),
        "conv_w": w((n_conv, taps, d), taps),
        "out_proj": w((n_conv, d, d), d, scale=back),
    }
    attention = {
        "op_norm": jnp.ones((n_attn, d), dtype),
        # Half of fan-in scale: the per-head norm takes the scale out (the
        # served scores' spread is the norms' weights': ``spread``), and a
        # model without the norm has scores a quarter of unit spread.
        "wq": w((n_attn, d, h * dh), d, scale=0.5),
        "wk": w((n_attn, d, hkv * dh), d, scale=0.5),
        "wv": w((n_attn, d, hkv * dh), d),
        "wo": w((n_attn, h * dh, d), h * dh, scale=back),
        "q_norm": spread((n_attn, dh)),
        "k_norm": spread((n_attn, dh)),
    }
    dense = {
        "ffn_norm": jnp.ones((nd, d), dtype),
        "w_gate": w((nd, d, f), d), "w_up": w((nd, d, f), d),
        "w_down": w((nd, f, d), f, scale=back),
    }
    sparse = {
        "ffn_norm": jnp.ones((ns, d), dtype),
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
    }
    params = {
        "embed": w((v, d), 1),
        "layers": {"conv": conv, "attention": attention, "dense": dense,
                   "sparse": sparse},
        # The head is the table: with a weight of ones here a token's own
        # logit would stand sqrt(D) deviations above the others (the stream
        # is mostly its embedding) and every row would repeat its last
        # token for ever. Random signs at D ** -0.5 make the logits of unit
        # spread with no such token, as an untied head at fan-in scale does.
        "final_norm": (jax.random.rademacher(next(keys), (d,), jnp.float32)
                       * d ** -0.5).astype(dtype),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = w((d, v), d)
    return params


def head_norm_rope(x: jax.Array, w: jax.Array, eps: float, cos, sin):
    """RMSNorm over each head's lanes of x [B, T, H, Dh] (one weight [Dh]
    for every head), then rope over all of them (rotate-half), in float32
    throughout: one rounding, at the end."""
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)
    x1, x2 = jnp.split(xf, 2, axis=-1)
    c, s = cos[:, :, None, :], sin[:, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                           axis=-1).astype(x.dtype)


def _attention_op(cfg, rope, positions, chunk_lens, hidden, lp, view, layer):
    """The attention operator's branch [B, T, D] and the tokens' new K and V
    in pool layout [Hkv / pack, B, T, pack * Dh]."""
    b, t, _ = hidden.shape
    h, hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    with jax.named_scope("attn_proj"):
        x = rms_norm(hidden, lp["op_norm"], cfg.rms_norm_eps)
        q = head_norm_rope((x @ lp["wq"]).reshape(b, t, h, dh),
                           lp["q_norm"], cfg.rms_norm_eps, *rope)
        k = head_norm_rope((x @ lp["wk"]).reshape(b, t, hkv, dh),
                           lp["k_norm"], cfg.rms_norm_eps, *rope)
        v = (x @ lp["wv"]).reshape(b, t, hkv, dh)
        # ``pack`` KV heads side by side in a row of whole lanes; a query
        # head is zero outside its own KV head's lanes, so its scores are
        # its own head's and its output's other lanes are dropped below
        # (models/granite_hybrid.py:_attention_layer).
        pack = kv_pack(cfg)
        own = jnp.eye(pack, dtype=q.dtype)
        q = q.reshape(b, t, hkv // pack, pack, h // hkv, dh)
        q = (q[..., None, :] * own[:, None, :, None]).reshape(
            b, t, h, pack * dh)
        k = k.reshape(b, t, hkv // pack, pack * dh)
        v = v.reshape(b, t, hkv // pack, pack * dh)
    with jax.named_scope("attn_core"):
        attn = attend(q, k, v, positions, chunk_lens, view, layer,
                      scale=dh ** -0.5)
        attn = attn.reshape(b, t, hkv // pack, pack, h // hkv, pack, dh)
        attn = jnp.sum(attn * own[:, None, :, None], axis=-2)
    with jax.named_scope("attn_proj"):
        branch = attn.reshape(b, t, h * dh) @ lp["wo"]
    return branch, k.transpose(2, 0, 1, 3), v.transpose(2, 0, 1, 3)


def _conv_op(cfg, chunk_lens, hidden, lp, conv, seg_lens=None):
    """The gated short convolution's branch [B, T, D] from ``conv`` (a
    row's conv state [B, *its spec's shape]) and the state after each row's
    ``chunk_lens`` valid tokens. ``seg_lens`` [S] given: ``hidden`` is ONE
    packed row [1, T, D] of S segments and ``conv`` a SEGMENT's state a row
    [S, *its spec's shape], before and after."""
    b, t, d = hidden.shape
    with jax.named_scope("attn_proj"):
        x = rms_norm(hidden, lp["op_norm"], cfg.rms_norm_eps)
        bcx = x @ lp["in_proj"]                               # [B, T, 3D]
    with jax.named_scope("attn_core"), jax.named_scope("short_conv"):
        state = conv.reshape(conv.shape[0], cfg.conv_l_cache - 1, d)
        z = bcx[..., :d] * bcx[..., 2 * d:]
        if seg_lens is not None:
            c, state = gd.conv_packed_row(z, state, lp["conv_w"], seg_lens,
                                          silu=False)
        elif t == 1:
            c, state = gd.conv_step(z[:, 0], state, lp["conv_w"],
                                    chunk_lens > 0, silu=False)
            c = c[:, None]
        else:
            c, state = gd.conv_chunk(z, state, lp["conv_w"], chunk_lens,
                                     silu=False)
        y = bcx[..., d:2 * d] * c
        state = state.reshape(conv.shape).astype(conv.dtype)
    with jax.named_scope("attn_proj"):
        return y @ lp["out_proj"], state


def _dense_ffn(cfg, hidden, lp):
    with jax.named_scope("ffn"):
        x = rms_norm(hidden, lp["ffn_norm"], cfg.rms_norm_eps)
        return hidden + \
            (jax.nn.silu(x @ lp["w_gate"]) * (x @ lp["w_up"])) @ lp["w_down"]


def _sparse_ffn(cfg, hidden, lp, experts, group_base, valid, interpret):
    """(hidden after one sparse layer's FFN, its counters, its choices);
    ``experts`` are the WHOLE stacks (w_gate_up [n_sparse * E, D, 2F],
    w_down [n_sparse * E, F, D]) and ``group_base`` this layer's first
    group in them (models/deepseek_v3.py:_sparse_ffn)."""
    b, t, d = hidden.shape
    with jax.named_scope("ffn"):
        x = rms_norm(hidden, lp["ffn_norm"], cfg.rms_norm_eps)
        flat = x.reshape(b * t, d)
        idx, w = moe.route(
            flat, lp["w_router"], lp["router_bias"], cfg.num_experts_per_tok,
            cfg.routed_scaling_factor, cfg.norm_topk_prob, ROUTE_EPS)
        routed, stats = moe.expert_ffn(
            flat, idx + group_base, w, valid.reshape(b * t), *experts,
            interpret=interpret)
        return hidden + routed.reshape(b, t, d).astype(hidden.dtype), \
            stats, idx


def operator_tables(cfg: ModelConfig):
    """Of the SPARSE layers, in order: (is the operator attention, its index
    among the conv layers, its index among the attention layers), int32
    arrays; the index of the kind a layer is not is 0 and not read."""
    ops = _operators(cfg)[cfg.first_k_dense_replace:]
    is_attn = np.array([kind == "attention" for kind, _ in ops], np.int32)
    at = np.array([i for _, i in ops], np.int32)
    return is_attn, at * (1 - is_attn), at * is_attn


def forward(
    params: Params,
    cfg: ModelConfig,
    token_ids: jax.Array,     # [B, T]
    positions: jax.Array,     # [B, T]
    chunk_lens: jax.Array,    # [B] valid tokens per row (0: the row is inert)
    view: KVView = KVView(),  # the K/V of the ATTENTION layers
    *,
    state: Optional[Tuple[jax.Array]] = None,
    act_sharding=None,        # sequence parallelism: refused for this family
    lora=None,                # LORA_TARGETS is empty
    routing: bool = False,    # also return every sparse layer's choices
):
    """Returns (hidden [B,T,D], k_new [n_attn,Hkv/pack,B,T,pack*Dh], v_new,
    state, stats int32[4] as ``FORWARD_STATS``) and, with ``routing``, the
    chosen experts [n_sparse, B*T, k].

    ``state``: (the rows' conv state [B, n_conv, *its spec's shape],) before
    the first token, one array per spec of ``cache_specs``, rows first as
    the runner's pools are; ``None`` starts every row from zeros (a whole
    sequence in one call). The returned state is that after each row's last
    valid token. The view's layer axis counts the attention layers only.

    A PACKED row (``view.seg_lens`` [S]: B is 1, the sequences' chunks end
    to end from token 0, ``chunk_lens`` the row's live tokens): ``state``
    in and out has a row a SEGMENT, [S, n_conv, ...]; the convolution and
    ``attend`` tell the segments apart, everything else is a function of
    a token."""
    b, t = token_ids.shape
    nd = cfg.first_k_dense_replace
    ns = cfg.num_layers - nd
    with jax.named_scope("embed"):
        hidden = params["embed"][token_ids]
        hidden = hidden.astype(view.act_dtype(params["embed"].dtype))
    if state is None:
        seqs = b if view.seg_lens is None else view.seg_lens.shape[0]
        state = tuple(
            jnp.zeros((seqs, s.layers, *s.shape), s.dtype or hidden.dtype)
            for s in cache_specs(cfg).state)
    conv_all, = state
    rope = _rope_cos_sin(positions, cfg.head_dim_, cfg.rope_theta)
    valid = jnp.arange(t, dtype=jnp.int32)[None, :] < chunk_lens[:, None]
    layers = params["layers"]
    sparse = layers["sparse"]
    experts = tuple(
        sparse[k].reshape(-1, *sparse[k].shape[2:])
        for k in ("w_gate_up", "we_down"))
    rest = {k: x for k, x in sparse.items()
            if k not in ("w_gate_up", "we_down")}

    def layer_of(stack, at):
        # One layer of a stack, sliced where it is used (olmo_hybrid.py).
        return jax.tree.map(
            lambda x: jax.lax.dynamic_index_in_dim(x, at, 0, False), stack)

    def conv_of(conv_all, at):
        # A layer's state out of the rows' carried state and back into it,
        # under the convolution's own scope.
        with jax.named_scope("attn_core"), jax.named_scope("short_conv"):
            return jax.lax.dynamic_index_in_dim(conv_all, at, 1, False)

    def conv_into(conv_all, conv, at):
        with jax.named_scope("attn_core"), jax.named_scope("short_conv"):
            return jax.lax.dynamic_update_index_in_dim(conv_all, conv, at, 1)

    def conv_operator(hidden, conv, at):
        branch, conv = _conv_op(cfg, chunk_lens, hidden,
                                layer_of(layers["conv"], at), conv,
                                view.seg_lens)
        return hidden + branch, conv

    def dense_layer(carry, i):
        # The leading layers are conv layers, the first of their stack.
        hidden, conv_all = carry
        hidden, conv = conv_operator(hidden, conv_of(conv_all, i), i)
        return (_dense_ffn(cfg, hidden, layer_of(layers["dense"], i)),
                conv_into(conv_all, conv, i)), None

    if nd:
        (hidden, conv_all), _ = jax.lax.scan(
            dense_layer, (hidden, conv_all), jnp.arange(nd, dtype=jnp.int32))

    hkv, dh = cache_specs(cfg).paged_kv[1:]

    def of_layer(x, p):
        return None if x is None else \
            jax.lax.dynamic_index_in_dim(x, p, 0, False)

    def attention(hidden, conv, c, p):
        branch, k_l, v_l = _attention_op(
            cfg, rope, positions, chunk_lens, hidden,
            layer_of(layers["attention"], p),
            view._replace(win_k=of_layer(view.win_k, p),
                          win_v=of_layer(view.win_v, p),
                          ring_k=of_layer(view.ring_k, p),
                          ring_v=of_layer(view.ring_v, p)),
            p if view.pool_k is not None else None)
        return hidden + branch, conv, k_l, v_l

    def convolution(hidden, conv, c, p):
        hidden, conv = conv_operator(hidden, conv, c)
        kv = jnp.zeros((hkv, b, t, dh), hidden.dtype)
        return hidden, conv, kv, kv

    tables = operator_tables(cfg)
    is_attn, conv_at, attn_at = (jnp.asarray(x) for x in tables)

    def step(carry, i):
        hidden, conv_all, stats = carry
        # Only the layer's own slice of the state passes the ``cond`` (an
        # attention layer hands conv layer 0's back as it was).
        c = conv_at[i]
        hidden, conv, k_l, v_l = jax.lax.cond(
            is_attn[i] > 0, attention, convolution,
            hidden, conv_of(conv_all, c), c, attn_at[i])
        conv_all = conv_into(conv_all, conv, c)
        hidden, st, idx = _sparse_ffn(
            cfg, hidden, layer_of(rest, i), experts,
            i * cfg.n_routed_experts, valid, view.interpret)
        return (hidden, conv_all, stats + st), \
            (k_l, v_l, idx if routing else None)

    (hidden, conv_all, stats), (k_all, v_all, chosen) = jax.lax.scan(
        step,
        (hidden, conv_all, jnp.zeros((len(FORWARD_STATS),), jnp.int32)),
        jnp.arange(ns, dtype=jnp.int32))
    # The attention layers' rows of the scan's outputs (the others' are
    # zeros nothing reads).
    where = np.flatnonzero(tables[0])
    k_new, v_new = k_all[where], v_all[where]
    hidden = rms_norm(hidden, params["final_norm"], cfg.rms_norm_eps)
    out = (hidden, k_new, v_new, (conv_all,), stats)
    return out + (chosen,) if routing else out
