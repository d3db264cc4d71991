"""Plain reference of the DeepSeek-V3-family decoder (multi-head latent
attention, sparse experts beside shared ones): the whole forward of ONE
sequence in ``jax.numpy``, float32, every product at ``highest`` precision,
the EXPANDED attention (keys and values of every head made from the
compressed row), no cache, no batching, no kernels, every expert computed
densely a few at a time and weighted by the routing. It imports nothing of
the program it judges and takes the parameter tree the program's
``init_params`` makes (``layers.dense`` / ``layers.sparse`` stacked by kind)
and the HF ``config.json`` as a dict.

The equations (``transformers`` 4.57, modeling_deepseek_v3.py), ``h`` heads:

Block, every layer (pre-norm, eps ``rms_norm_eps``, no bias, untied head):
    x = x + attn(RMSNorm(x));  x = x + ffn(RMSNorm(x))

Attention (``q_lora_rank`` null, ``rope_scaling`` null):
    q = W_q x -> h x (qk_nope_head_dim + qk_rope_head_dim) = (q_nope, q_rope)
    [c | k_r] = W_kva x -> kv_lora_rank + qk_rope_head_dim;  c = RMSNorm(c)
    [k_nope | v] = W_kvb c -> h x (qk_nope_head_dim + v_head_dim)
    rope (theta, ``rope_interleave``: pairs (2i, 2i+1)) on q_rope and on
        k_r, which every head shares
    s = (q_nope . k_nope + q_rope . k_r) (nope + rope)^-0.5
    causal softmax, o = W_o (p v)

FFN, layers below ``first_k_dense_replace``: W_down (silu(W_gate x) * W_up x)
FFN, the others:
    s = sigmoid(W_r x) over the experts, in float32
    chosen = top-k of s + e_score_correction_bias (``noaux_tc``;
        ``n_group`` = ``topk_group`` = 1: no group limit)
    w = s[chosen] / (sum s[chosen] + 1e-20) * routed_scaling_factor
    y = sum_e w_e expert_e(x) + shared(x); every expert SiLU-gated of width
        moe_intermediate_size, the shared path one SiLU-gated FFN of width
        n_shared_experts * moe_intermediate_size. No capacity, no drop.

Departures from the published modeling code, each without effect on the
result: (1) the tree holds ``kv_b_proj`` as its two halves per head, ``w_uk``
[h, nope, rank] and ``w_uv`` [h, rank, v], and an expert's gate and up
matrices as one ``w_gate_up`` (gate then up): they are multiplied as the
halves they are; (2) rope rotates the pairs (2i, 2i+1) in place, where HF
first moves the lanes to evens-then-odds and rotates halves: queries and
keys take the same permutation there, so every score is the same; (3) the
experts are computed for every token and weighted by the routing (zero
where not chosen), where HF gathers each expert's tokens.

``wrong`` switches ONE equation to a plausible mistake; the tests use it to
show that their tolerance tells each of them from the right model.
"""

import jax
import jax.numpy as jnp

WRONG = ("router_bf16", "top_k_minus_1", "bias_in_weight", "no_scaling",
         "rope_halves", "no_kv_norm")
F32 = jnp.float32
EXPERT_GROUP = 8      # experts computed at a time (memory, not meaning)


def _bf16(x):
    """``x`` rounded to bfloat16's precision, still float32."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def layer_params(params, cfg, i):
    """(kind, that layer's parameters in float32) of layer ``i``."""
    nd = cfg.get("first_k_dense_replace", 0)
    kind, at = ("dense", i) if i < nd else ("sparse", i - nd)
    return kind, jax.tree.map(lambda x: jnp.asarray(x[at], F32),
                              params["layers"][kind])


def _rope(x, theta, halves=False):
    """x [T, H, D]: rotate the pairs (2i, 2i+1) by position * theta^(-2i/D)
    (``halves``: the pairs (i, i + D/2), llama's: the ``rope_halves``
    mistake)."""
    t, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    if halves:
        a, b = jnp.split(x, 2, axis=-1)
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def attention(cfg, lp, x, wrong=()):
    t = x.shape[0]
    h, nope, dr = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                   cfg["qk_rope_head_dim"])
    rank = cfg["kv_lora_rank"]
    halves = "rope_halves" in wrong
    q = (x @ lp["wq"]).reshape(t, h, nope + dr)
    q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], cfg["rope_theta"],
                                          halves)
    ckr = x @ lp["w_kva"]
    c = ckr[:, :rank]
    if "no_kv_norm" not in wrong:
        c = rms_norm(c, lp["kv_norm"], cfg["rms_norm_eps"])
    k_r = _rope(ckr[:, None, rank:], cfg["rope_theta"], halves)[:, 0]
    k_nope = jnp.einsum("tr,hnr->thn", c, lp["w_uk"])
    v = jnp.einsum("tr,hrv->thv", c, lp["w_uv"])
    scores = (jnp.einsum("ihn,jhn->hij", q_nope, k_nope)
              + jnp.einsum("ihd,jd->hij", q_rope, k_r)) * (nope + dr) ** -0.5
    causal = jnp.tril(jnp.ones((t, t), bool))
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
    out = jnp.einsum("hij,jhv->ihv", probs, v)
    return out.reshape(t, -1) @ lp["wo"]


def route(cfg, lp, x, wrong=(), forced=None):
    """(chosen experts [T, k], dense weights [T, E]: zero where not
    chosen). ``forced`` [T, k]: the COMPARISON's, not the model's: take
    these experts as the choice and compute the rest (scores, weights,
    experts) as always. Routing is discontinuous, so two right
    computations in different precisions choose differently at a near-tie
    and are then different functions of the token; with the choice given,
    what is left to differ is arithmetic."""
    k = cfg["num_experts_per_tok"] - ("top_k_minus_1" in wrong)
    w_r = lp["w_router"]
    if "router_bf16" in wrong:
        # What a bf16 router holds: inputs, logits and scores at 8 bits of
        # mantissa. ``reduce_precision`` and not a pair of converts, which
        # XLA removes where it may keep excess precision (CPU and TPU do).
        x, w_r = _bf16(x), _bf16(w_r)
    logits = x @ w_r
    s = jax.nn.sigmoid(logits)
    if "router_bf16" in wrong:
        s = _bf16(jax.nn.sigmoid(_bf16(logits)))
    if forced is None:
        _, chosen = jax.lax.top_k(s + lp["router_bias"], k)
    else:
        # (the mistake of one expert too few drops the weakest given)
        order = jnp.argsort(-jnp.take_along_axis(
            s + lp["router_bias"], forced, axis=1), axis=1)
        chosen = jnp.take_along_axis(forced, order, axis=1)[:, :k]
    picked = jnp.take_along_axis(
        s + lp["router_bias"] if "bias_in_weight" in wrong else s,
        chosen, axis=1)
    if cfg.get("norm_topk_prob", True):
        picked = picked / (picked.sum(-1, keepdims=True) + 1e-20)
    if "no_scaling" not in wrong:
        picked = picked * cfg.get("routed_scaling_factor", 1.0)
    dense = jnp.zeros_like(s).at[
        jnp.arange(x.shape[0])[:, None], chosen].set(picked)
    return chosen, dense


def gated_ffn(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def sparse_ffn(cfg, lp, x, wrong=(), forced=None):
    """(routed + shared [T, D], chosen experts [T, k])."""
    chosen, weights = route(cfg, lp, x, wrong, forced)
    f = lp["we_down"].shape[1]
    y = gated_ffn(x, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    for e0 in range(0, weights.shape[1], EXPERT_GROUP):
        e1 = e0 + EXPERT_GROUP
        hgu = jnp.einsum("td,edf->etf", x, lp["w_gate_up"][e0:e1])
        act = jax.nn.silu(hgu[..., :f]) * hgu[..., f:]
        out = jnp.einsum("etf,efd->etd", act, lp["we_down"][e0:e1])
        y = y + jnp.einsum("te,etd->td", weights[:, e0:e1], out)
    return y, chosen


def layer(cfg, kind, lp, x, wrong=(), forced=None):
    """One block: (x after it, the chosen experts [T, k] or None)."""
    with jax.default_matmul_precision("highest"):
        eps = cfg["rms_norm_eps"]
        x = x + attention(cfg, lp, rms_norm(x, lp["attn_norm"], eps), wrong)
        xn = rms_norm(x, lp["mlp_norm"], eps)
        if kind == "dense":
            return x + gated_ffn(xn, lp["w_gate"], lp["w_up"],
                                 lp["w_down"]), None
        y, chosen = sparse_ffn(cfg, lp, xn, wrong, forced)
        return x + y, chosen


def embed(params, token_ids):
    return jnp.asarray(params["embed"], F32)[jnp.asarray(token_ids)]


def logits(params, cfg, x):
    with jax.default_matmul_precision("highest"):
        x = rms_norm(x, jnp.asarray(params["final_norm"], F32),
                     cfg["rms_norm_eps"])
        return x @ jnp.asarray(params["lm_head"], F32)


def forward(params, cfg, token_ids, wrong=(), routing=None, forced=None):
    """Logits [T, V] of one sequence of token ids, every position.
    ``routing``: a list that receives each sparse layer's chosen experts
    [T, k], in layer order. ``forced``: each sparse layer's choice given
    ([n_sparse, T, k]; see ``route``)."""
    x = embed(params, token_ids)
    nd = cfg.get("first_k_dense_replace", 0)
    for i in range(cfg["num_hidden_layers"]):
        kind, lp = layer_params(params, cfg, i)
        x, chosen = layer(cfg, kind, lp, x, wrong,
                          None if forced is None or i < nd
                          else forced[i - nd])
        if routing is not None and chosen is not None:
            routing.append(chosen)
    return logits(params, cfg, x)
