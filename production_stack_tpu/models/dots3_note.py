"""dots3-note decoder (HF ``dots3_note``): TWO kinds of multi-head latent
attention in one model, one leading dense FFN and sigmoid-routed sparse
experts beside a shared one, of which a chip may hold a SHARE (expert
parallelism) — functional JAX.

The same shape of module as models/mimo_v2.py (the declarations under "What
the rest of the tree asks of this module", parameters stacked BY KIND, a
layer's attention and its FFN two independent kinds, weights closed over and
sliced where used, a second kind of cache declared as a ``StateSpec`` the
runner owns, ops/moe.py's router and experts, the counters ``FORWARD_STATS``
names returned last) with models/deepseek_v3.py's latent attention in its
ABSORBED form. Of its own:

  * A layer is ``full_attention`` or ``sliding_attention``
    (``cfg.layer_types``, in ANY order). Both compress the query through a
    low-rank pair and a norm and cache ONE latent row a token, ``[c | k_r]``
    (the compressed KV after its norm, then the rotary key every head
    shares, after DeepSeek's interleaved rope); they differ in every size
    (a full layer's are ``num_heads``, ``q_lora_rank``, ``kv_lora_rank``,
    ``qk_*_head_dim``, ``v_head_dim``, ``rope_theta``; a sliding layer's
    the ``swa_*`` ones) and in what a query reads.
  * With ``mla_lora_rescale`` the normed latents are scaled where they are
    made: ``c_q = (hidden / q_rank) ** 0.5 * RMSNorm(x W_qa)``, ``c =
    (hidden / kv_rank) ** 0.5 * RMSNorm(c_kv)``, per kind. The CACHED row
    holds that ``c``, the reference's own: nothing is folded into ``w_uk``
    / ``w_uv``.
  * A FULL layer pages its row (576 lanes in 640), and beside it, in the
    SECOND pool, the key of a learned INDEXER (``index_head_dim`` lanes;
    models/config.py:LatentKVSpec.index_dim: the same block table, the
    same write). ``q_idx = c_q W_iq`` (``index_n_
    heads`` x ``index_head_dim``), ``k_idx = LayerNorm(x W_ik)`` with bias,
    the layer's rope on the first ``qk_rope_head_dim`` lanes of both, ``w =
    (x W_iw) / sqrt(heads * dim)``; a query attends the ``index_topk`` keys
    of largest ``sum_h w_h relu(q_idx_h . k_idx)`` and no others
    (ops/attention.py:attend_selected_latent: a decode step over the pool
    READS the index keys and the selected rows only; a chunk scores densely
    under the selection's mask).
  * A SLIDING layer pages nothing: a sequence keeps its
    ``sliding_window`` newest latent rows as a RING in a state slot
    (position p in slot p mod W; ops/attention.py:window_ring_attend /
    window_ring_write over latent rows), a query at position i sees ``0 <=
    i - j < W``: the token and the W - 1 before it.
  * A headwise gate on both kinds: ``g = sigmoid(x W_g)``, one scalar a
    head from the layer's normed input, on the head's attention output
    before ``W_o``.
  * Routing and the share as models/mimo_v2.py's (float32 sigmoid scores
    over the router's WHOLE width ``n_routed_experts * ep_size``, top-k of
    score + bias, weights the scores over their sum; a pair whose expert
    lies elsewhere adds nothing and is counted), plus ONE shared expert
    every chip computes alike.

tests/reference/dots3_ref.py is the plain statement of the same equations
(expanded keys and values, a masked full score matrix, no cache) this module
is held to.

Device scopes: ``attn_proj`` (norms, projections, rope, the absorbed
products, the gate) with the indexer's projections under an inner
``attn_index``; ``attn_core`` with, of a full layer, ``attn_index`` (scores,
top-k) and ``attn_select`` (gather, the attention over what was selected)
and, of a sliding layer, ``ring_attend`` / ``ring_write``; ``ffn`` and inside
it ``moe_route``, ``moe_experts`` (inner ``moe_gmm``), ``moe_shared``;
``embed``, ``logits``.
"""

from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from production_stack_tpu.models.config import (
    ANY_ORDER_LISTS,
    CacheSpecs,
    LatentKVSpec,
    ModelConfig,
    PagedKVSpec,
    StateSpec,
)
from production_stack_tpu.models.deepseek_v3 import (
    _gated_ffn,
    _rope_interleaved,
)
from production_stack_tpu.models.llama import (
    _rope_cos_sin,
    compute_logits,  # noqa: F401 — the untied head is llama's
    rms_norm,
)
from production_stack_tpu.models.mimo_v2 import held_experts, ring_width
from production_stack_tpu.ops import moe
from production_stack_tpu.ops.attention import (
    KVView,
    attend_selected_latent,
    window_ring_attend,
    window_ring_write,
)

Params = Dict

# --- What the rest of the tree asks of this module (see models/llama.py) ----
# HF checkpoint suffix -> (our leaf, transpose?): ASSUMED names (deployment.
# json of dots3-note-prev-ep16 says so): DeepSeek-V3's for the latent
# attention and the experts, V3.2's for the indexer.
HF_LAYER_MAP = {
    "input_layernorm.weight": ("attn_norm", False),
    "post_attention_layernorm.weight": ("ffn_norm", False),
    "self_attn.q_a_proj.weight": ("wq_a", True),
    "self_attn.q_a_layernorm.weight": ("q_norm", False),
    "self_attn.q_b_proj.weight": ("wq_b", True),
    "self_attn.kv_a_proj_with_mqa.weight": ("w_kva", True),
    "self_attn.kv_a_layernorm.weight": ("kv_norm", False),
    "self_attn.kv_b_proj.weight": ("w_kvb", True),
    "self_attn.o_proj.weight": ("wo", True),
    "self_attn.gate_proj.weight": ("w_head_gate", True),
    "self_attn.indexer.wq_b.weight": ("idx_wq", True),
    "self_attn.indexer.wk.weight": ("idx_wk", True),
    "self_attn.indexer.k_norm.weight": ("idx_k_norm", False),
    "self_attn.indexer.k_norm.bias": ("idx_k_bias", False),
    "self_attn.indexer.weights_proj.weight": ("idx_w", True),
    "mlp.gate_proj.weight": ("w_gate", True),
    "mlp.up_proj.weight": ("w_up", True),
    "mlp.down_proj.weight": ("w_down", True),
    "mlp.gate.weight": ("w_router", True),
    "mlp.gate.e_score_correction_bias": ("router_bias", False),
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
# No LoRA on this family yet: the absorbed products and the experts have no
# delta path (the engine refuses --lora-modules on an empty tuple).
LORA_TARGETS = ()
# ``attn_impl=auto`` may resolve to the paged path: a full layer's decode
# step then reads its index keys and its selected rows from the pool in
# place; tests/test_dots3.py holds the engine's logits on that path to the
# reference.
PAGED_DECODE_VALIDATED = True
# Leaves a checkpoint load keeps in float32 whatever the engine's dtype.
FLOAT32_LEAVES = ("w_router", "router_bias")
# int32 counters ``forward`` returns last: ops/moe.py's of a share, summed
# over the sparse layers, then the keys the full layers' queries could see
# and the keys their indexers selected, summed over the full layers and the
# valid queries.
FORWARD_STATS = moe.STATS_EP + ("index_keys_visible", "index_keys_selected")

_KINDS = ANY_ORDER_LISTS["dots3_note"]   # ("sliding_attention", "full_..")
_ATTN = ("attn_norm", "wq_a", "q_norm", "wq_b", "w_kva", "kv_norm", "w_kvb",
         "wo", "w_head_gate")
_INDEXER = ("idx_wq", "idx_wk", "idx_k_norm", "idx_k_bias", "idx_w")
_LEAVES = {                                   # as loaded, by kind
    "full": _ATTN + _INDEXER,
    "window": _ATTN,
    "dense": ("ffn_norm", "w_gate", "w_up", "w_down"),
    "sparse": ("ffn_norm", "w_router", "router_bias", "we_gate", "we_up",
               "we_down", "ws_gate", "ws_up", "ws_down"),
}


class Sizes(NamedTuple):
    """A kind of layer's latent attention."""
    heads: int
    q_rank: int
    rank: int      # the compressed KV
    nope: int
    rope: int
    v: int
    theta: float


def sizes(cfg: ModelConfig, kind: str) -> Sizes:
    """The config's own sizes for ``full``, its ``swa_*`` for ``window``."""
    if kind == "full":
        return Sizes(cfg.num_heads, cfg.q_lora_rank, cfg.kv_lora_rank,
                     cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                     cfg.v_head_dim, cfg.rope_theta)
    return Sizes(cfg.swa_num_heads, cfg.swa_q_lora_rank, cfg.swa_kv_lora_rank,
                 cfg.swa_qk_nope_head_dim, cfg.swa_qk_rope_head_dim,
                 cfg.swa_v_head_dim, cfg.swa_rope_theta)


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


def latent_spec(cfg: ModelConfig) -> LatentKVSpec:
    """A full layer's paged row ``[c | k_r]`` in whole tiles, and the lanes
    of the indexer's key beside it."""
    return LatentKVSpec(cfg.kv_lora_rank, cfg.qk_rope_head_dim,
                        cfg.index_head_dim)


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
    if not cfg.first_k_dense_replace:
        del need["dense"]
    return need


def finish_params(cfg: ModelConfig, params: Params) -> Params:
    """Last step of a checkpoint load: ``kv_b_proj`` becomes its two halves
    per head (of each kind's own sizes), an expert's gate and up matrices
    one (gate then up), and the table and the head keep the vocabulary's
    slice this chip serves (its first ``vocab_size`` rows)."""
    layers = params["layers"]
    for kind in ("full", "window"):
        of = sizes(cfg, kind)
        h, nope, dv = of.heads, of.nope, of.v
        kvb = layers[kind].pop("w_kvb")                   # [n, rank, H*(..)]
        kvb = kvb.reshape(*kvb.shape[:2], h, nope + dv)
        layers[kind]["w_uk"] = kvb[..., :nope].transpose(0, 2, 3, 1)
        layers[kind]["w_uv"] = kvb[..., nope:].transpose(0, 2, 1, 3)
    sparse = layers["sparse"]
    sparse["w_gate_up"] = jnp.concatenate(
        [sparse.pop("we_gate"), sparse.pop("we_up")], axis=-1)
    params["embed"] = params["embed"][:cfg.vocab_size]
    params["lm_head"] = params["lm_head"][:, :cfg.vocab_size]
    return params


def cache_specs(cfg: ModelConfig) -> CacheSpecs:
    """Three things. The FULL layers page a token's latent row ``[c | k_r]``
    in whole tiles (576 lanes in 640 as published: what the paged kernels
    over latent rows take) and, in the SECOND pool, the indexer's key
    (128 lanes), by the same block table and the same write: a block of
    that pool is whole tiles, so an index scan reads 256 B a key and
    nothing of the latent rows. (The key as the row's last tile, ONE pool
    of 768 lanes, was tried first: no gather takes a block's last tile
    where it lies, and XLA lays the whole pool out again for it.) Per
    sequence and SLIDING layer, a ring of ``sliding_window`` latent rows
    ``[1, W, rank + rope]`` in the activations' dtype, STORED in rows of
    whole tiles (1088 lanes in 1152; the device lays 513 slots in 528)."""
    n_window, n_full = _counts(cfg)
    latent = latent_spec(cfg)
    ring = sizes(cfg, "window")
    return CacheSpecs(
        PagedKVSpec(n_full, 1, latent.width),
        (StateSpec("ring_c", n_window,
                   (1, cfg.sliding_window, ring.rank + ring.rope), None,
                   ring_width(ring.rank + ring.rope)),),
        latent=latent)


def ring_report(cfg: ModelConfig) -> Dict:
    """What ``GET /version`` and ``GET /debug/programs`` say of this
    module's caches and experts."""
    first, count = held_experts(cfg)
    latent = latent_spec(cfg)
    return {
        "window_layers": window_layers(cfg),
        "ring": {s.name: list(s.shape) for s in cache_specs(cfg).state},
        "index_topk": cfg.index_topk,
        "index_key_lanes": latent.index_dim,
        "experts_held": [first, first + count],
        "experts_routed": count * cfg.ep_size,
    }


def init_params(cfg: ModelConfig, rng: jax.Array, dtype=jnp.bfloat16) -> Params:
    d, f, v = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    e, fe = cfg.n_routed_experts, cfg.moe_intermediate_size
    n_window, n_full = _counts(cfg)
    nd = cfg.first_k_dense_replace
    ns = cfg.num_layers - nd
    keys = iter(jax.random.split(rng, 64))
    # Random weights that behave as a trained model's do where routing and
    # selection look (models/deepseek_v3.py:init_params says why): the
    # residual stream is the token's own embedding at unit scale plus SMALL
    # branches, every projection back into the stream drawn at 1/sqrt(2 L)
    # of fan-in scale for the depth the model is published with (46),
    # whatever part of it is served.
    back = (2 * 46) ** -0.5

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

    def attn(n, kind):
        h, qr, rank, nope, dr, dv, _ = sizes(cfg, kind)
        # Fan-in scale throughout: the rescaled latents (``mla_lora_
        # rescale``) already give the scores a spread of several units, so
        # attention picks a few tokens instead of averaging them all.
        return {
            "attn_norm": jnp.ones((n, d), dtype),
            "wq_a": w((n, d, qr), d),
            "q_norm": jnp.ones((n, qr), dtype),
            "wq_b": w((n, qr, h * (nope + dr)), qr),
            "w_kva": w((n, d, rank + dr), d),
            "kv_norm": jnp.ones((n, rank), dtype),
            "w_uk": w((n, h, nope, rank), rank),
            "w_uv": w((n, h, rank, dv), rank),
            "wo": w((n, h * dv, d), h * dv, scale=back),
            "w_head_gate": w((n, d, h), d),
        }

    hi, di = cfg.index_n_heads, cfg.index_head_dim
    full = attn(n_full, "full")
    full.update({
        # The indexer at fan-in scale: its scores depend on the tokens (the
        # rope's slow frequencies leave most lanes where they were), so the
        # selected set is neither the newest keys nor anything a position
        # alone decides; the heads' weights are SIGNED, as a trained
        # ``weights_proj``'s are, so a head can vote a key down.
        "idx_wq": w((n_full, cfg.q_lora_rank, hi * di), cfg.q_lora_rank),
        "idx_wk": w((n_full, d, di), d),
        "idx_k_norm": jnp.ones((n_full, di), dtype),
        # Small and not zero: LayerNorm with and without its bias differ.
        "idx_k_bias": (0.1 * jax.random.normal(
            next(keys), (n_full, di), jnp.float32)).astype(dtype),
        "idx_w": w((n_full, d, hi), d),
    })
    dense = {
        "ffn_norm": jnp.ones((nd, d), dtype),
        "w_gate": w((nd, d, f), d), "w_up": w((nd, d, f), d),
        "w_down": w((nd, f, d), f, scale=back),
    }
    fs = cfg.n_shared_experts * fe
    sparse = {
        "ffn_norm": jnp.ones((ns, d), dtype),
        # Logits of about unit size (the inputs are normed): the scores
        # spread. The values are bf16's (a published gate matrix is), held
        # in float32. The router's WHOLE width, whatever share is here.
        "w_router": w((ns, d, e * cfg.ep_size), d,
                      jnp.bfloat16).astype(jnp.float32),
        # Small and not zero: choosing by score + bias and weighting by the
        # score are then different things.
        "router_bias": 0.05 * jax.random.normal(
            next(keys), (ns, e * cfg.ep_size), jnp.float32),
        "w_gate_up": w_experts((e, d, 2 * fe), d),
        "we_down": w_experts((e, fe, d), fe, back),
        "ws_gate": w((ns, d, fs), d), "ws_up": w((ns, d, fs), d),
        "ws_down": w((ns, fs, d), fs, scale=back),
    }
    return {
        "embed": w((v, d), 1),
        "layers": {"full": full, "window": attn(n_window, "window"),
                   "dense": dense, "sparse": sparse},
        "final_norm": jnp.ones((d,), dtype),
        "lm_head": w((d, v), d),
    }


def _rescaled_norm(cfg, x, w, rank):
    """``rho * RMSNorm(x)`` with ``rho = (hidden / rank) ** 0.5`` where the
    config rescales (else 1): the product in float32, one rounding."""
    y = rms_norm(x, w, cfg.rms_norm_eps)
    if not cfg.mla_lora_rescale:
        return y
    return (y.astype(jnp.float32)
            * (cfg.hidden_size / rank) ** 0.5).astype(y.dtype)


def _layer_norm(x, w, bias, eps):
    xf = x.astype(jnp.float32)
    xf = xf - jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w + bias


def _project(cfg, kind, rope, hidden, lp, width):
    """One layer's (normed input x, rescaled query latent c_q [B, T, qr],
    ABSORBED queries [B, T, H, width] zero past the key's lanes, the
    token's row ``[c | k_r]`` [B, T, 1, rank + rope])."""
    b, t, _ = hidden.shape
    h, qr, rank, nope, dr = sizes(cfg, kind)[:5]
    x = rms_norm(hidden, lp["attn_norm"], cfg.rms_norm_eps)
    c_q = _rescaled_norm(cfg, x @ lp["wq_a"], lp["q_norm"], qr)
    # The products as they are written: without the barrier XLA folds the
    # split into heads into the product and, for that, lays the layers'
    # matrices out again (models/mimo_v2.py:_project; here W_qb of the
    # sliding layers, 201 MB, every layer of every decode step: a tenth of
    # the device's time in this PR's first traced run).
    q, ckr = jax.lax.optimization_barrier(
        (c_q @ lp["wq_b"], x @ lp["w_kva"]))                # [B,T,rank+dr]
    q = q.reshape(b, t, h, nope + dr)
    c = _rescaled_norm(cfg, ckr[..., :rank], lp["kv_norm"], rank)
    k_r = _rope_interleaved(ckr[..., None, rank:], *rope)        # [B,T,1,dr]
    q_r = _rope_interleaved(q[..., nope:], *rope)
    # Absorbed: q_nope . (c W_uk)^T = (q_nope W_uk) . c.
    q_c = jnp.einsum("bthn,hnr->bthr", q[..., :nope], lp["w_uk"],
                     preferred_element_type=jnp.float32).astype(q.dtype)
    q_row = jnp.concatenate(
        [q_c, q_r, jnp.zeros((b, t, h, width - rank - dr), q.dtype)],
        axis=-1)
    return x, c_q, q_row, jnp.concatenate([c[:, :, None], k_r], axis=-1)


def _out(cfg, kind, x, attn, lp):
    """``W_o`` of the gated heads: attn [B, T, H, rank] through ``W_uv``,
    each head times its sigmoid gate of the layer's normed input."""
    b, t = attn.shape[:2]
    of = sizes(cfg, kind)
    h, dv = of.heads, of.v
    o = jnp.einsum("bthr,hrv->bthv", attn, lp["w_uv"],
                   preferred_element_type=jnp.float32)
    gate = jax.nn.sigmoid(jnp.dot(x, lp["w_head_gate"],
                                  preferred_element_type=jnp.float32))
    o = (o * gate[..., None]).astype(attn.dtype)
    return o.reshape(b, t, h * dv) @ lp["wo"]


def _full_attention(cfg, rope, positions, chunk_lens, hidden, lp, view,
                    layer, with_mask=False):
    """A full layer's branch [B, T, D], the tokens' rows and index keys in
    pool layout ([1, B, T, width], [1, B, T, index lanes]) and int32[2]
    (keys visible, keys selected); ``with_mask``: the selection last."""
    b, t, _ = hidden.shape
    latent = latent_spec(cfg)
    rank, nope, dr = sizes(cfg, "full")[2:5]
    hi, di = cfg.index_n_heads, cfg.index_head_dim
    with jax.named_scope("attn_proj"):
        x, c_q, q_row, ckr = _project(cfg, "full", rope, hidden, lp,
                                      latent.width)
        with jax.named_scope("attn_index"):
            q_idx = jax.lax.optimization_barrier(
                c_q @ lp["idx_wq"]).reshape(b, t, hi, di)
            k_idx = _layer_norm(x @ lp["idx_wk"], lp["idx_k_norm"],
                                lp["idx_k_bias"], cfg.rms_norm_eps)
            # The layer's rope on the FIRST rope lanes of both.
            q_idx = jnp.concatenate(
                [_rope_interleaved(q_idx[..., :dr], *rope), q_idx[..., dr:]],
                axis=-1)
            k_idx = jnp.concatenate(
                [_rope_interleaved(k_idx[:, :, None, :dr], *rope),
                 k_idx[:, :, None, dr:]], axis=-1)           # [B, T, 1, di]
            w_idx = jnp.dot(x, lp["idx_w"],
                            preferred_element_type=jnp.float32) \
                * (hi ** -0.5 * di ** -0.5)
        row = jnp.concatenate(
            [ckr, jnp.zeros((b, t, 1, latent.width - rank - dr), ckr.dtype)],
            axis=-1)
    with jax.named_scope("attn_core"):
        attn, stats, *mask = attend_selected_latent(
            q_row, row, k_idx, q_idx, w_idx, positions, chunk_lens, view,
            layer, scale=(nope + dr) ** -0.5, value_dim=rank,
            topk=cfg.index_topk, with_mask=with_mask)
    with jax.named_scope("attn_proj"):
        branch = _out(cfg, "full", x, attn, lp)
    return (branch, row.transpose(2, 0, 1, 3), k_idx.transpose(2, 0, 1, 3),
            stats, *mask)


def _window_attention(cfg, rope, positions, chunk_lens, hidden, lp, ring):
    """A sliding layer's branch [B, T, D] from the rows' ring of this layer
    BEFORE the chunk ([B, 1, W, ring lanes]), and the chunk's rows
    [B, T, 1, rank + rope] for ``window_ring_write``."""
    rank, nope, dr = sizes(cfg, "window")[2:5]
    with jax.named_scope("attn_proj"):
        x, _, q_row, ckr = _project(cfg, "window", rope, hidden, lp,
                                    rank + dr)
    with jax.named_scope("attn_core"):
        attn = window_ring_attend(
            q_row, ckr, None, positions, chunk_lens, ring,
            scale=(nope + dr) ** -0.5, value_dim=rank)
    with jax.named_scope("attn_proj"):
        return _out(cfg, "window", x, attn, lp), ckr


def _dense_ffn(cfg, hidden, lp):
    with jax.named_scope("ffn"):
        x = rms_norm(hidden, lp["ffn_norm"], cfg.rms_norm_eps)
        return hidden + _gated_ffn(x, lp["w_gate"], lp["w_up"], lp["w_down"])


def _sparse_ffn(cfg, hidden, lp, experts, group_base, valid, interpret):
    """(hidden after one sparse layer's FFN, its counters, its choices);
    ``experts`` are the WHOLE stacks HELD and ``group_base`` this layer's
    first group in them (models/mimo_v2.py:_sparse_ffn), the shared expert
    beside them (models/deepseek_v3.py's)."""
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
        with jax.named_scope("moe_shared"):
            shared = _gated_ffn(x, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
        return hidden + shared \
            + routed.reshape(b, t, d).astype(hidden.dtype), stats, idx


def operator_tables(cfg: ModelConfig):
    """Of the SPARSE layers, in order: (is the layer a sliding layer, its
    index among the sliding layers, its index among the full layers), int32
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
    view: KVView = KVView(),  # the rows and index keys of the FULL layers
    *,
    state: Optional[Tuple[jax.Array]] = None,
    act_sharding=None,        # sequence parallelism: refused for this family
    lora=None,                # LORA_TARGETS is empty
    routing: bool = False,    # also return every sparse layer's choices
):
    """Returns (hidden [B,T,D], rows [n_full,1,B,T,width], index keys
    [n_full,1,B,T,index lanes] where other families return values, state,
    stats int32[7] as ``FORWARD_STATS``) and, with ``routing``, the chosen
    experts [n_sparse, B*T, k] (of the router's whole width) and the full
    layers' selections [n_full, B, T, T] (a view that holds nothing only:
    the tests' and the on-chip comparison's).

    ``state``: (the rows' rings [B, n_window, 1, W, ring lanes],) before the
    first token, as ``cache_specs`` declares it (``StateSpec.stored``), rows
    first as the runner's pools are; ``None`` starts every row from an empty
    ring (a whole sequence in one call: then ``positions`` start at 0). The
    returned state is that after each row's last valid token. The view's
    layer axis counts the full layers only, and its value parts hold index
    keys. A row's ``positions`` are consecutive from its first."""
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
    # A layer's rope: its kind's table (two tables a forward).
    ropes = {kind: _rope_cos_sin(positions, sizes(cfg, kind).rope,
                                 sizes(cfg, kind).theta)
             for kind in ("full", "window")}
    valid = jnp.arange(t, dtype=jnp.int32)[None, :] < chunk_lens[:, None]
    layers = params["layers"]
    sparse = layers["sparse"]
    experts = tuple(
        sparse[k].reshape(-1, *sparse[k].shape[2:])
        for k in ("w_gate_up", "we_down"))
    rest = {k: x for k, x in sparse.items()
            if k not in ("w_gate_up", "we_down")}
    latent = latent_spec(cfg)
    ring_row = cache_specs(cfg).state[0].shape[-1]
    # What a full layer hands on beside its branch, and a sliding layer as
    # zeros nothing reads: its row, its index key, its counters and, with
    # ``routing``, its selection.
    nothing = (jnp.zeros((1, b, t, latent.width), hidden.dtype),
               jnp.zeros((1, b, t, latent.index_dim), hidden.dtype),
               jnp.zeros((2,), jnp.int32),
               *((jnp.zeros((b, t, t), bool),) if routing else ()))

    def layer_of(stack, at):
        # One layer of a stack, sliced where it is used (olmo_hybrid.py).
        return jax.tree.map(
            lambda x: jax.lax.dynamic_index_in_dim(x, at, 0, False), stack)

    def of_layer(x, p):
        return None if x is None else \
            jax.lax.dynamic_index_in_dim(x, p, 0, False)

    def full(hidden, ring, w_at, f_at):
        branch, *paged = _full_attention(
            cfg, ropes["full"], positions, chunk_lens, hidden,
            layer_of(layers["full"], f_at),
            view._replace(win_k=of_layer(view.win_k, f_at),
                          win_v=of_layer(view.win_v, f_at),
                          ring_k=of_layer(view.ring_k, f_at),
                          ring_v=of_layer(view.ring_v, f_at)),
            f_at if view.pool_k is not None else None, with_mask=routing)
        # Nothing for a ring: the write below sees no valid token.
        return (hidden + branch,
                jnp.zeros((b, t, 1, ring_row), hidden.dtype), *paged)

    def window(hidden, ring, w_at, f_at):
        branch, ckr = _window_attention(
            cfg, ropes["window"], positions, chunk_lens, hidden,
            layer_of(layers["window"], w_at), ring)
        return (hidden + branch, ckr, *nothing)

    def attention(hidden, rings, is_window, w_at, f_at):
        """One layer's attention of either kind, a chunk or a decode step:
        the layer's ring is taken out of the carry BEFORE the ``cond`` (the
        whole carry handed to a ``cond`` is laid out again for it, every
        layer of every step: 113 MB at 16 rows; a full layer's slice is
        read by nobody) and the carry comes out through the write below,
        which both kinds share and which a full layer hands no valid
        token."""
        with jax.named_scope("attn_core"), jax.named_scope("ring_attend"):
            ring = jax.lax.dynamic_index_in_dim(rings[0], w_at, 1, False)
        if isinstance(is_window, bool):
            hidden, ckr, *paged = (window if is_window else full)(
                hidden, ring, w_at, f_at)
        else:
            hidden, ckr, *paged = jax.lax.cond(
                is_window > 0, window, full, hidden, ring, w_at, f_at)
        with jax.named_scope("attn_core"):
            rings = window_ring_write(
                rings, w_at, (ckr,), positions,
                chunk_lens * jnp.asarray(is_window, chunk_lens.dtype))
        return hidden, rings, paged

    ops = _operators(cfg)
    first = []
    seen = jnp.zeros((2,), jnp.int32)
    # The leading dense layers, each traced with its own kind (one of them
    # in the published model).
    for i in range(nd):
        kind, at = ops[i]
        hidden, rings, paged = attention(
            hidden, rings, kind == "window",
            jnp.int32(at if kind == "window" else 0),
            jnp.int32(at if kind == "full" else 0))
        hidden = _dense_ffn(cfg, hidden, layer_of(layers["dense"],
                                                  jnp.int32(i)))
        seen = seen + paged[2]
        if kind == "full":
            first.append([x[None] for x in paged[:2] + paged[3:]])

    tables = operator_tables(cfg)
    is_window, window_at, full_at = (jnp.asarray(x) for x in tables)

    def step(carry, i):
        hidden, rings, stats, seen = carry
        hidden, rings, paged = attention(
            hidden, rings, is_window[i], window_at[i], full_at[i])
        hidden, moe_st, idx = _sparse_ffn(
            cfg, hidden, layer_of(rest, i), experts,
            i * cfg.n_routed_experts, valid, view.interpret)
        return (hidden, rings, stats + moe_st, seen + paged[2]), \
            (paged[:2] + paged[3:], idx if routing else None)

    (hidden, rings, stats, seen), (scanned, chosen) = jax.lax.scan(
        step,
        (hidden, rings, jnp.zeros((len(moe.STATS_EP),), jnp.int32), seen),
        jnp.arange(ns, dtype=jnp.int32))
    # The full layers' entries of the scan's outputs (the others' are zeros
    # nothing reads), behind the leading layers'.
    where = np.flatnonzero(1 - tables[0])
    k_new, v_new, *masks = (
        jnp.concatenate([*(f[j] for f in first), x[where]], axis=0)
        for j, x in enumerate(scanned))
    hidden = rms_norm(hidden, params["final_norm"], cfg.rms_norm_eps)
    out = (hidden, k_new, v_new, rings, jnp.concatenate([stats, seen]))
    return out + (chosen, *masks) if routing else out
