"""DeepSeek-V3-family decoder: multi-head latent attention over ONE cached
row a token, and sparse experts beside shared ones — functional JAX.

The same shape of module as models/llama.py (the declarations under "What
the rest of the tree asks of this module", attention through ``attend`` over
whatever ``KVView`` the runner built, ``rms_norm`` and the rope helpers
imported from there), with four things of its own, and one it leaves out:

  * Latent attention. A token caches ``[c | k_r]``: the compressed KV after
    its norm (``kv_lora_rank``) and the rotary key all heads share, after
    rope (``qk_rope_head_dim``), padded with zeros to whole lanes
    (``cache_specs``: one pool a layer, no second pool). The forward uses
    the ABSORBED form everywhere: ``q~ = [q_nope W_uk | q_rope]`` per head,
    scores against the cached rows, ``p c`` per head, then ``W_uv`` and
    ``W_o``. That is multi-query attention whose keys are the row and whose
    values are its first ``kv_lora_rank`` lanes, which is what
    ops/attention.py:attend computes when it is handed no ``v``; the
    expanded form (``k_nope, v = c W_kvb``) is what the plain reference
    states (tests/reference/deepseek_v3_ref.py) and this module is held to.
    ``W_kvb`` is kept as its two halves per head (``w_uk`` [H, nope, rank],
    ``w_uv`` [H, rank, v]): a checkpoint's one matrix is split when loaded.
    With ``q_lora_rank`` the query goes through a low-rank pair and a norm
    (``wq_a``, ``q_norm``, ``wq_b``) where it is otherwise one matrix
    (``wq``); with ``rope_scaling`` (YaRN) the rotary frequencies are
    blended between the published and the interpolated ones and the
    softmax's scale takes ``mscale^2`` (``_rope_tables``, ``_softmax_scale``:
    what HF's DeepseekV3 attention computes).
  * Two kinds of layer, not a period: ``first_k_dense_replace`` leading
    layers with a dense SiLU-gated FFN, then sparse layers. Parameters are
    stacked BY KIND (``layers.dense``, ``layers.sparse``); ONE dense layer is
    traced where it stands, two or more are a ``lax.scan`` of their own (a
    layer's code once: a program of this family is large, and the compile
    cache is capped), and the sparse stack is one ``lax.scan`` over the
    layer index, with the weights closed over and
    sliced where they are used, never a scan operand. The routed experts'
    matrices are not even sliced: the grouped matmul takes the whole stack
    ``[n_sparse * E, ...]`` and a layer's groups sit at ``layer * E`` (a
    slice of 1.2 GB handed to a kernel would be copied out, every layer,
    every step).
  * Routing (ops/moe.py): float32 sigmoid scores, top-k of score + bias,
    weights the scores themselves, normalised and scaled; tokens that do
    not count (``chunk_lens``) reach no expert. The forward returns, last,
    the int32 counters ``FORWARD_STATS`` names, summed over its sparse
    layers: the runner adds them up and the engine exports them.
  * The residual (``hc_mult`` > 1; ops/hyper_connections.py): ``hc_mult``
    streams, STREAM-MAJOR ``[n, B, T, D]``, which enter as copies of the
    embedding and leave as their sum before the final norm. ``_attention``
    and the FFNs return their BRANCH, and ``_sublayer`` wraps each (2 a
    layer) by the mix its own ``hc_*`` leaves give: the sublayer reads
    ``H_pre x`` and the streams become ``H_res x + H_post^T branch``, the
    matrices computed per token in float32. The carry of the dense loop and
    of the sparse scan is the streams. With ``hc_mult`` 1 there is one
    stream, no mix and no such leaf: ``hidden + branch``, the programs this
    module lowered before it knew of streams. ``forward`` returns
    ``hidden [B, T, D]`` either way.
  * What is published and NOT served: the next-token-prediction layers
    (``num_nextn_predict_layers``). Their tensors lie behind the last layer
    (``model.layers.<num_hidden_layers>.*``) and are skipped at load, as HF's
    forward ignores them: the next-token logits do not depend on them.

Device scopes: ``attn_proj`` (projections, norms, rope, the two absorbed
products), ``attn_core``, ``ffn`` (the dense FFN; a sparse layer's norm and
sum), and inside ``ffn`` the three of a sparse layer: ``moe_route``,
``moe_experts`` (sort, grouped matmuls under an inner ``moe_gmm``, unsort)
and ``moe_shared``; ``embed``, ``logits``. The stream mix lies INSIDE the
scope of the sublayer it wraps: ``hc_pre`` (norm, projections, sigmoid,
Sinkhorn, ``H_pre x``) and ``hc_post`` (``H_res x + H_post^T branch``) under
``attn_proj`` / ``ffn``, ``hc_head`` (the streams' sum) under ``logits``.
"""

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from production_stack_tpu.models.config import (
    CacheSpecs,
    LatentKVSpec,
    ModelConfig,
    PagedKVSpec,
)
from production_stack_tpu.models.llama import (
    _rope_cos_sin,
    apply_rope,
    compute_logits,  # noqa: F401 — the untied head is llama's
    rms_norm,
)
from production_stack_tpu.ops import hyper_connections as hc
from production_stack_tpu.ops import moe
from production_stack_tpu.ops.attention import KVView, attend

Params = Dict

# --- What the rest of the tree asks of this module (see models/llama.py) ----
# HF checkpoint suffix -> (our leaf, transpose?). ``experts.*`` stands for an
# expert's index: models/weights.py stacks those tensors on an expert axis
# behind the layer's.
HF_LAYER_MAP = {
    "self_attn.q_proj.weight": ("wq", True),
    "self_attn.q_a_proj.weight": ("wq_a", True),
    "self_attn.q_a_layernorm.weight": ("q_norm", False),
    "self_attn.q_b_proj.weight": ("wq_b", True),
    "self_attn.kv_a_proj_with_mqa.weight": ("w_kva", True),
    "self_attn.kv_a_layernorm.weight": ("kv_norm", False),
    "self_attn.kv_b_proj.weight": ("w_kvb", True),
    "self_attn.o_proj.weight": ("wo", True),
    "input_layernorm.weight": ("attn_norm", False),
    "post_attention_layernorm.weight": ("mlp_norm", False),
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
    # The stream mix of the two sublayers (the names are ASSUMED: the
    # config gives none; deployment.json of xing4.0-29b-a4b-d7 says so).
    "attn_hc.phi.weight": ("hc_attn_phi", True),
    "attn_hc.bias": ("hc_attn_b", False),
    "attn_hc.alpha": ("hc_attn_a", False),
    "mlp_hc.phi.weight": ("hc_ffn_phi", True),
    "mlp_hc.bias": ("hc_ffn_b", False),
    "mlp_hc.alpha": ("hc_ffn_a", False),
}
HF_TOP_MAP = {
    "model.embed_tokens.weight": ("embed", False),
    "model.norm.weight": ("final_norm", False),
    "lm_head.weight": ("lm_head", True),
}
# Leaves a checkpoint load keeps in float32 whatever the engine's dtype: the
# router computes in float32 and its bias is published in it; the stream mix
# is float32 throughout.
_HC = tuple(f"hc_{sub}_{leaf}" for sub in ("attn", "ffn")
            for leaf in ("phi", "b", "a"))
FLOAT32_LEAVES = ("w_router", "router_bias") + _HC
# No LoRA on this family yet: the absorbed products and the experts have no
# delta path (the engine refuses --lora-modules on an empty tuple).
LORA_TARGETS = ()
# ``attn_impl=auto`` may resolve to the Pallas paged decode over the latent
# pool: tests/test_deepseek_v3.py holds the engine's logits on that path to
# the reference.
PAGED_DECODE_VALIDATED = True
# int32 counters ``forward`` returns last, summed over its sparse layers.
FORWARD_STATS = moe.STATS

_ATTN = ("w_kva", "kv_norm", "w_kvb", "wo", "attn_norm", "mlp_norm")
_DENSE = ("w_gate", "w_up", "w_down")
_SPARSE = ("w_router", "router_bias", "we_gate", "we_up", "we_down",
           "ws_gate", "ws_up", "ws_down")                    # as loaded


def position_bound(cfg: ModelConfig) -> Optional[int]:
    """None: RoPE takes any position."""
    return None


def layer_slots(cfg: ModelConfig):
    """(kind, index within the kind's stack) of every layer, in order."""
    nd = cfg.first_k_dense_replace
    return [("dense", i) if i < nd else ("sparse", i - nd)
            for i in range(cfg.num_layers)]


def required_layer_leaves(cfg: ModelConfig) -> dict:
    """Per kind, the leaves every valid checkpoint must provide."""
    every = _ATTN + (("wq_a", "q_norm", "wq_b") if cfg.q_lora_rank
                     else ("wq",)) + (_HC if cfg.hc_mult > 1 else ())
    return {"dense": set(every + _DENSE), "sparse": set(every + _SPARSE)}


def finish_params(cfg: ModelConfig, params: Params) -> Params:
    """Last step of a checkpoint load: ``kv_b_proj`` becomes its two halves
    per head, and an expert's gate and up matrices one (gate then up)."""
    h, nope, dv = cfg.num_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
    for stack in params["layers"].values():
        if "w_kvb" in stack:
            kvb = stack.pop("w_kvb")                      # [n, rank, H*(nope+v)]
            kvb = kvb.reshape(*kvb.shape[:2], h, nope + dv)
            stack["w_uk"] = kvb[..., :nope].transpose(0, 2, 3, 1)
            stack["w_uv"] = kvb[..., nope:].transpose(0, 2, 1, 3)
        if "we_gate" in stack:
            stack["w_gate_up"] = jnp.concatenate(
                [stack.pop("we_gate"), stack.pop("we_up")], axis=-1)
    return params


def cache_specs(cfg: ModelConfig) -> CacheSpecs:
    """One latent row a token in every layer; nothing else."""
    latent = LatentKVSpec(cfg.kv_lora_rank, cfg.qk_rope_head_dim)
    return CacheSpecs(PagedKVSpec(cfg.num_layers, 1, latent.width),
                      latent=latent)


def init_params(cfg: ModelConfig, rng: jax.Array, dtype=jnp.bfloat16) -> Params:
    d, v, h = cfg.hidden_size, cfg.vocab_size, cfg.num_heads
    nope, rope, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
    rank, e, fe = (cfg.kv_lora_rank, cfg.n_routed_experts,
                   cfg.moe_intermediate_size)
    fs = cfg.n_shared_experts * fe
    nd = cfg.first_k_dense_replace
    ns = cfg.num_layers - nd
    keys = iter(jax.random.split(rng, 32))

    # Random weights that behave as a trained model's do where routing
    # looks: the residual stream is the token's own embedding (unit scale)
    # plus SMALL branches, every projection back into the stream drawn a
    # tenth of fan-in scale: GPT-2's and Megatron's 1/sqrt(2 L) at the
    # depth such a model is published with (48 layers: 0.102), whatever
    # part of that depth is served. With fan-in scale everywhere a layer's
    # output is as large as the stream and the embedding a fortieth of it:
    # every row's router input is then mostly what attention averaged over
    # the context, the same for all rows, the batch crowds into half the
    # experts that independent rows reach, and one expert swapped at a
    # near-tie (routing is discontinuous, and bf16 rounds) moves every
    # later choice of the token (PERF.md section 6, PR 33).
    back = 0.1

    def w(shape, fan_in, dt=dtype, scale=1.0):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * (scale * fan_in ** -0.5)).astype(dt)

    def w_experts(shape, fan_in, scale=1.0):
        # A layer at a time: the float32 draw of a whole stack of experts
        # (11 GB at the published widths) is never alive at once.
        return jax.lax.map(
            lambda k: (jax.random.normal(k, shape, jnp.float32)
                       * (scale * fan_in ** -0.5)).astype(dtype),
            jax.random.split(next(keys), ns))

    def query(n):
        if not cfg.q_lora_rank:
            return {"wq": w((n, d, h * (nope + rope)), d)}
        return {"wq_a": w((n, d, cfg.q_lora_rank), d),
                "q_norm": jnp.ones((n, cfg.q_lora_rank), dtype),
                "wq_b": w((n, cfg.q_lora_rank, h * (nope + rope)),
                          cfg.q_lora_rank)}

    def attn(n):
        return {
            "attn_norm": jnp.ones((n, d), dtype),
            "mlp_norm": jnp.ones((n, d), dtype),
            **query(n),
            "w_kva": w((n, d, rank + rope), d),
            "kv_norm": jnp.ones((n, rank), dtype),
            "w_uk": w((n, h, nope, rank), rank),
            "w_uv": w((n, h, rank, dv), rank),
            "wo": w((n, h * dv, d), h * dv, scale=back),
        }

    f = cfg.intermediate_size
    dense = {**attn(nd), "w_gate": w((nd, d, f), d), "w_up": w((nd, d, f), d),
             "w_down": w((nd, f, d), f, scale=back)}
    sparse = {
        **attn(ns),
        # Scores that spread: logits of about unit size (the inputs are
        # normed), so sigmoid lies well away from 0.5 for most experts. The
        # values are bf16's (a published gate matrix is), held in float32.
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
    if cfg.hc_mult > 1:
        # Keys of their own: the other leaves are those of hc_mult 1.
        hc_rng = jax.random.fold_in(rng, cfg.hc_mult)
        dense.update(_init_mix(cfg, jax.random.fold_in(hc_rng, 0), nd))
        sparse.update(_init_mix(cfg, jax.random.fold_in(hc_rng, 1), ns))
    return {
        "embed": w((v, d), 1),
        "layers": {"dense": dense, "sparse": sparse},
        "final_norm": jnp.ones((d,), dtype),
        "lm_head": w((d, v), d),
    }


def _init_mix(cfg: ModelConfig, rng: jax.Array, layers: int) -> Params:
    """The stream mix of ``layers`` layers' two sublayers, float32, drawn so
    that every part of the equations MOVES the matrices (a comparison that
    drops a part must see it):

      * dynamic: ``phi`` at fan-in scale over the ``nD`` normed values, so
        ``x~ phi`` is unit normal per column, and ``a`` about a half: a
        token's own logits spread by half a unit around the static ones;
      * static: ``b`` half a unit wide, and the residual mix's leaning to
        the identity (``+1`` on its diagonal: a stream mostly keeps itself,
        as the published initialisation has it), so ``exp`` of its logits is
        far from doubly stochastic and one Sinkhorn iteration leaves row
        sums a tenth off 1 where twenty leave 1e-6;
      * ``H_post`` is around 1 (``2 sigmoid(~0)``): a branch enters the
        streams at about the size it has in the plain residual, and the
        streams stay the embedding's scale (``H_res`` is doubly stochastic:
        the embedding's part of every stream is kept whole), so routing
        sees the token as under ``hc_mult`` 1.
    """
    n, nd_ = cfg.hc_mult, cfg.hc_mult * cfg.hidden_size
    cols = hc.phi_columns(n)
    lean = jnp.concatenate([jnp.zeros((2 * n,)), jnp.eye(n).reshape(-1)])
    out = {}
    for at, sub in enumerate(("attn", "ffn")):
        k_phi, k_b, k_a = jax.random.split(jax.random.fold_in(rng, at), 3)
        out[f"hc_{sub}_phi"] = jax.random.normal(
            k_phi, (layers, nd_, cols), jnp.float32) * nd_ ** -0.5
        out[f"hc_{sub}_b"] = lean + 0.5 * jax.random.normal(
            k_b, (layers, cols), jnp.float32)
        out[f"hc_{sub}_a"] = 0.5 + 0.1 * jax.random.normal(
            k_a, (layers, 3), jnp.float32)
    return out


def _rope_interleaved(x: jax.Array, cos: jax.Array, sin: jax.Array):
    """``rope_interleave``: the published pairs are (2i, 2i + 1). As HF's
    modeling code does, the lanes are first put evens-then-odds and the
    rotate-half form applied; queries and keys take the same permutation,
    so every score is that of the pairwise rotation."""
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    return apply_rope(x, cos, sin)


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _rope_tables(cfg: ModelConfig, positions: jax.Array):
    """(cos, sin) [B, T, rope/2]. Plain rope, or YaRN as HF computes it
    (``_compute_yarn_parameters``): every frequency a blend of the published
    one and the one interpolated by ``factor``, by where its wavelength
    lies between ``beta_fast`` and ``beta_slow`` rotations over the original
    context; cos and sin times ``mscale / mscale_all_dim``'s factors."""
    dim, theta, ys = cfg.qk_rope_head_dim, cfg.rope_theta, cfg.rope_scaling
    if ys is None:
        return _rope_cos_sin(positions, dim, theta)

    def correction_dim(rotations):
        return dim * math.log(ys.original_max_position_embeddings
                              / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(ys.beta_fast)), 0)
    high = min(math.ceil(correction_dim(ys.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0, 1)
    pos_freqs = theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    inv_freq = (1.0 / (ys.factor * pos_freqs)) * ramp \
        + (1.0 / pos_freqs) * (1 - ramp)
    if ys.mscale and ys.mscale_all_dim:
        amp = _yarn_mscale(ys.factor, ys.mscale) \
            / _yarn_mscale(ys.factor, ys.mscale_all_dim)
    else:
        amp = _yarn_mscale(ys.factor, 1.0)
    angles = positions[..., None].astype(jnp.float32) * inv_freq
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    return (cos, sin) if amp == 1.0 else (cos * amp, sin * amp)


def _softmax_scale(cfg: ModelConfig) -> float:
    """``(nope + rope)^-0.5``, times YaRN's ``mscale^2`` of
    ``mscale_all_dim`` where the config gives one."""
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    ys = cfg.rope_scaling
    if ys is not None and ys.mscale_all_dim:
        scale *= _yarn_mscale(ys.factor, ys.mscale_all_dim) ** 2
    return scale


def _attention(cfg, rope, positions, chunk_lens, hidden, lp, view, layer):
    """Pre-norm latent attention of ``hidden`` (the residual, or what the
    stream mix hands over); returns (its branch [B, T, D], the tokens' rows
    [B, T, 1, W])."""
    b, t, _ = hidden.shape
    h, nope, dr = cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    rank = cfg.kv_lora_rank
    width = LatentKVSpec(rank, dr).width
    with jax.named_scope("attn_proj"):
        x = rms_norm(hidden, lp["attn_norm"], cfg.rms_norm_eps)
        if cfg.q_lora_rank:
            q = rms_norm(x @ lp["wq_a"], lp["q_norm"], cfg.rms_norm_eps) \
                @ lp["wq_b"]
        else:
            q = x @ lp["wq"]
        q = q.reshape(b, t, h, nope + dr)
        ckr = x @ lp["w_kva"]                                # [B, T, rank+dr]
        c = rms_norm(ckr[..., :rank], lp["kv_norm"], cfg.rms_norm_eps)
        k_r = _rope_interleaved(ckr[..., None, rank:], *rope)    # [B,T,1,dr]
        q_r = _rope_interleaved(q[..., nope:], *rope)
        # Absorbed: q_nope . (c W_uk)^T = (q_nope W_uk) . c.
        q_c = jnp.einsum("bthn,hnr->bthr", q[..., :nope], lp["w_uk"],
                         preferred_element_type=jnp.float32).astype(q.dtype)
        pad = width - rank - dr
        q_row = jnp.concatenate(
            [q_c, q_r, jnp.zeros((b, t, h, pad), q.dtype)], axis=-1)
        row = jnp.concatenate(
            [c[:, :, None], k_r, jnp.zeros((b, t, 1, pad), c.dtype)], axis=-1)
    with jax.named_scope("attn_core"):
        attn = attend(q_row, row, None, positions, chunk_lens, view, layer,
                      scale=_softmax_scale(cfg), value_dim=rank)
    with jax.named_scope("attn_proj"):
        o = jnp.einsum("bthr,hrv->bthv", attn, lp["w_uv"],
                       preferred_element_type=jnp.float32).astype(attn.dtype)
        branch = o.reshape(b, t, h * cfg.v_head_dim) @ lp["wo"]
    return branch, row


def _gated_ffn(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def _dense_ffn(cfg, hidden, lp):
    """The branch of a leading layer's FFN, pre-norm."""
    with jax.named_scope("ffn"):
        x = rms_norm(hidden, lp["mlp_norm"], cfg.rms_norm_eps)
        return _gated_ffn(x, lp["w_gate"], lp["w_up"], lp["w_down"])


def _sparse_ffn(cfg, hidden, lp, experts, group_base, valid, interpret):
    """The branch of one sparse layer as its two parts (shared, routed);
    ``experts`` are the WHOLE stacks (w_gate_up [n_sparse*E, D, 2F], w_down
    [n_sparse*E, F, D]) and ``group_base`` this layer's first group in
    them."""
    b, t, d = hidden.shape
    with jax.named_scope("ffn"):
        x = rms_norm(hidden, lp["mlp_norm"], cfg.rms_norm_eps)
        flat = x.reshape(b * t, d)
        idx, w = moe.route(
            flat, lp["w_router"], lp["router_bias"], cfg.num_experts_per_tok,
            cfg.routed_scaling_factor, cfg.norm_topk_prob)
        routed, stats = moe.expert_ffn(
            flat, idx + group_base, w, valid.reshape(b * t), *experts,
            interpret=interpret)
        with jax.named_scope("moe_shared"):
            shared = _gated_ffn(x, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
        routed = routed.reshape(b, t, d).astype(hidden.dtype)
    return (shared, routed), stats, idx


# A sublayer's outer device scope, by the name its mix's leaves carry.
_SCOPE_OF = {"attn": "attn_proj", "ffn": "ffn"}


def _sublayer(cfg, sub, x, lp, branch_of):
    """The residual ``x`` after one sublayer (``sub``: ``attn`` / ``ffn``),
    and what ``branch_of`` returns beside its branch. ``branch_of(h) -> (the
    branch's parts, *rest)``. ``x`` is the plain residual [B, T, D]
    (``hc_mult`` 1: the parts are added to it, in order) or the streams
    [n, B, T, D], mixed by the layer's ``hc_<sub>_*`` leaves inside the
    sublayer's own outer scope."""
    scope = _SCOPE_OF[sub]
    if cfg.hc_mult == 1:
        parts, *rest = branch_of(x)
        with jax.named_scope(scope):
            for part in parts:
                x = x + part
        return (x, *rest)
    with jax.named_scope(scope), jax.named_scope("hc_pre"):
        h_pre, h_post, h_res = hc.mix_matrices(
            x, lp[f"hc_{sub}_phi"], lp[f"hc_{sub}_b"], lp[f"hc_{sub}_a"],
            iters=cfg.hc_sinkhorn_iters, eps=cfg.hc_eps,
            norm_eps=cfg.rms_norm_eps, clamp=cfg.hc_res_clamp)
        h = hc.pre(x, h_pre).astype(x.dtype)
    parts, *rest = branch_of(h)
    with jax.named_scope(scope), jax.named_scope("hc_post"):
        branch = sum(part.astype(jnp.float32) for part in parts)
        x = hc.post(x, branch, h_post, h_res).astype(x.dtype)
    return (x, *rest)


def forward(
    params: Params,
    cfg: ModelConfig,
    token_ids: jax.Array,     # [B, T]
    positions: jax.Array,     # [B, T]
    chunk_lens: jax.Array,    # [B] valid tokens per row (0: the row is inert)
    view: KVView = KVView(),  # latent rows this forward may read
    *,
    act_sharding=None,        # sequence parallelism: refused for this family
    lora=None,                # LORA_TARGETS is empty
    routing: bool = False,    # also return every sparse layer's choices
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Returns (hidden [B,T,D], rows [L,1,B,T,W], an empty [L,1,B,T,0] where
    other families return values, stats int32[4] as ``FORWARD_STATS``) and,
    with ``routing``, the chosen experts [n_sparse, B*T, k] (the tests'
    and the on-chip comparison's, to count choices that differ).

    The view's parts hold latent rows ([L, 1, ..., W]); its value parts are
    not read. Tokens at or past a row's ``chunk_lens`` reach no expert."""
    b, t = token_ids.shape
    nd = cfg.first_k_dense_replace
    ns = cfg.num_layers - nd
    with jax.named_scope("embed"):
        hidden = params["embed"][token_ids]
        hidden = hidden.astype(view.act_dtype(params["embed"].dtype))
        if cfg.hc_mult > 1:
            # The streams enter as copies of the embedding.
            hidden = jnp.broadcast_to(hidden, (cfg.hc_mult, *hidden.shape))
    rope = _rope_tables(cfg, positions)
    valid = jnp.arange(t, dtype=jnp.int32)[None, :] < chunk_lens[:, None]
    dense, sparse = params["layers"]["dense"], params["layers"]["sparse"]
    experts = tuple(
        sparse[k].reshape(-1, *sparse[k].shape[2:])
        for k in ("w_gate_up", "we_down"))
    rest = {k: x for k, x in sparse.items()
            if k not in ("w_gate_up", "we_down")}

    def layer_of(stack, at):
        # One layer of a stack, sliced where it is used (olmo_hybrid.py).
        return jax.tree.map(
            lambda x: jax.lax.dynamic_index_in_dim(x, at, 0, False), stack)

    def view_of(at):
        pick = lambda x: None if x is None else \
            jax.lax.dynamic_index_in_dim(x, at, 0, False)  # noqa: E731
        return view._replace(
            win_k=pick(view.win_k), win_v=None, ring_k=pick(view.ring_k),
            ring_v=None), (at if view.pool_k is not None else None)

    def attention(hidden, lp, at):
        """(the residual after the layer's attention, the tokens' rows
        [1, B, T, W] in pool layout)."""
        def branch_of(h):
            branch, row = _attention(cfg, rope, positions, chunk_lens, h, lp,
                                     *view_of(at))
            return (branch,), row
        hidden, row = _sublayer(cfg, "attn", hidden, lp, branch_of)
        return hidden, row.transpose(2, 0, 1, 3)

    def dense_layer(hidden, i):
        lp = layer_of(dense, i)
        hidden, row = attention(hidden, lp, i)
        hidden, = _sublayer(cfg, "ffn", hidden, lp,
                            lambda h: ((_dense_ffn(cfg, h, lp),),))
        return hidden, row

    if nd > 1:
        # A scan of their own: one copy of a dense layer's code.
        hidden, dense_rows = jax.lax.scan(
            dense_layer, hidden, jnp.arange(nd, dtype=jnp.int32))
        rows = [dense_rows[:, 0]]
    else:
        rows = []
        for i in range(nd):
            hidden, row = dense_layer(hidden, jnp.int32(i))
            rows.append(row)

    def step(carry, i):
        hidden, stats = carry
        lp = layer_of(rest, i)
        hidden, row = attention(hidden, lp, nd + i)
        hidden, st, idx = _sublayer(
            cfg, "ffn", hidden, lp,
            lambda h: _sparse_ffn(cfg, h, lp, experts,
                                  i * cfg.n_routed_experts, valid,
                                  view.interpret))
        return (hidden, stats + st), (row, idx if routing else None)

    (hidden, stats), (sparse_rows, chosen) = jax.lax.scan(
        step, (hidden, jnp.zeros((len(FORWARD_STATS),), jnp.int32)),
        jnp.arange(ns, dtype=jnp.int32))
    k_new = jnp.concatenate([*rows, sparse_rows[:, 0]], axis=0)[:, None]
    if cfg.hc_mult > 1:
        # ... and leave as their sum.
        with jax.named_scope("logits"), jax.named_scope("hc_head"):
            hidden = jnp.sum(hidden.astype(jnp.float32), axis=0).astype(
                hidden.dtype)
    hidden = rms_norm(hidden, params["final_norm"], cfg.rms_norm_eps)
    out = (hidden, k_new, k_new[..., :0], stats)
    return out + (chosen,) if routing else out
