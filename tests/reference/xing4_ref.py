"""Plain reference of the Xing4.0 decoder (``model_type: xing4_0``): the
DeepSeek-V3 block (multi-head latent attention, sigmoid-routed experts beside
a shared one) with a low-rank query, YaRN-scaled rope, and a residual of
``hc_mult`` streams mixed by manifold-constrained hyper-connections. The whole
forward of ONE sequence in ``jax.numpy``, float32, every product at
``highest`` precision, the EXPANDED attention, no cache, no batching, no
kernels, every expert computed densely a few at a time and weighted by the
routing. It imports nothing of the program it judges and takes the parameter
tree the program's ``init_params`` makes (``layers.dense`` / ``layers.sparse``
stacked by kind) and the HF ``config.json`` as a dict.

The residual (mHC, arXiv:2512.24880, over Hyper-Connections,
arXiv:2409.19606). ``x in R^{n x D}`` are one token's streams before a
sublayer ``F`` (attention, the dense FFN, or routed + shared experts, each with
its own pre-norm); the sublayer's ``phi [nD, n(n+2)]`` (columns: pre, post,
res row-major), ``b [n(n+2)]``, ``a [3]``:

    x~     = vec(x) / sqrt(mean(vec(x)^2) + rms_norm_eps)
    H~pre  = a_pre  (x~ phi_pre)  + b_pre
    H~post = a_post (x~ phi_post) + b_post
    H~res  = a_res  mat(x~ phi_res) + b_res
    H_pre  = sigmoid(H~pre)     H_post = 2 sigmoid(H~post)
    H_res  = Sinkhorn(clip(H~res, mhc_h_res_clamp_min, .._max)): M = exp(.),
             then hc_sinkhorn_iters times M <- rows(M) / (row sums + hc_eps),
             M <- columns(M) / (column sums + hc_eps)
    h      = H_pre x
    x'     = H_res x + H_post^T F(h)

The streams enter as ``n`` copies of the embedding and leave as their sum
before the final norm. Two sublayers a layer, each with its own parameters.

Block, every layer (pre-norm, eps ``rms_norm_eps``, no bias, untied head):
attention then FFN, each wrapped as above.

Attention (``transformers`` 4.57, modeling_deepseek_v3.py):
    q = W_qb RMSNorm(W_qa x) -> h x (qk_nope_head_dim + qk_rope_head_dim)
    [c | k_r] = W_kva x -> kv_lora_rank + qk_rope_head_dim;  c = RMSNorm(c)
    [k_nope | v] = W_kvb c -> h x (qk_nope_head_dim + v_head_dim)
    rope on q_rope and on k_r, which every head shares: the pairs
        (2i, 2i+1) turned by position x f_i, with YaRN's f_i: between
        theta^(-2i/d) (wavelengths under original_max_position_embeddings /
        beta_fast: kept) and theta^(-2i/d) / factor (over .. / beta_slow:
        interpolated), a linear ramp over the dimensions between; cos and
        sin times mscale(factor, mscale) / mscale(factor, mscale_all_dim)
        (= 1 here), mscale(s, m) = 0.1 m ln s + 1
    s = (q_nope . k_nope + q_rope . k_r) (nope + rope)^-0.5
        x mscale(factor, mscale_all_dim)^2
    causal softmax, o = W_o (p v)

FFN, layers below ``first_k_dense_replace``: W_down (silu(W_gate x) * W_up x)
FFN, the others:
    s = sigmoid(W_r x) over the experts, in float32
    chosen = top-k of s + e_score_correction_bias
    w = s[chosen] / (sum s[chosen] + 1e-20) * routed_scaling_factor
    y = sum_e w_e expert_e(x) + shared(x)

Departures from the published modeling code, each without effect on the
result: (1) the tree holds ``kv_b_proj`` as its two halves per head and an
expert's gate and up matrices as one ``w_gate_up``; (2) rope rotates the pairs
(2i, 2i+1) in place, where HF first moves the lanes to evens-then-odds and
rotates halves; (3) the experts are computed for every token and weighted by
the routing (zero where not chosen), where HF gathers each expert's tokens.
Not served and not computed: the next-token-prediction layers
(``num_nextn_predict_layers``), on which these logits do not depend.

``wrong`` switches ONE equation to a plausible mistake; the tests use it to
show that their tolerance tells each of them from the right model.
"""

import math

import jax
import jax.numpy as jnp

WRONG = ("router_bf16", "top_k_minus_1", "hc_no_dynamic", "hc_one_iter",
         "hc_post_not_doubled", "hc_mix_bf16", "no_q_norm", "no_yarn_scale",
         "plain_rope")
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


# ----------------------------------------------------------- the stream mix
def mix_matrices(cfg, phi, b, a, x, wrong=()):
    """(H_pre [T, n], H_post [T, n], H_res [T, n, n]) of the streams
    ``x [T, n, D]`` under one sublayer's ``phi``, ``b``, ``a``."""
    t, n, d = x.shape
    low = _bf16 if "hc_mix_bf16" in wrong else (lambda v: v)
    flat = x.reshape(t, n * d)
    flat = flat * jax.lax.rsqrt(
        jnp.mean(flat * flat, -1, keepdims=True) + cfg["rms_norm_eps"])
    dyn = low(low(flat) @ low(phi))
    if "hc_no_dynamic" in wrong:
        dyn = jnp.zeros_like(dyn)
    scale = jnp.concatenate([jnp.full((n,), a[0]), jnp.full((n,), a[1]),
                             jnp.full((n * n,), a[2])])
    logits = low(dyn * scale + b)
    h_pre = low(jax.nn.sigmoid(logits[:, :n]))
    h_post = low(jax.nn.sigmoid(logits[:, n:2 * n])
                 * (1.0 if "hc_post_not_doubled" in wrong else 2.0))
    m = low(jnp.exp(jnp.clip(
        logits[:, 2 * n:], cfg.get("mhc_h_res_clamp_min", -30),
        cfg.get("mhc_h_res_clamp_max", 30)).reshape(t, n, n)))
    eps = cfg.get("hc_eps", 1e-6)
    iters = 1 if "hc_one_iter" in wrong else cfg.get("hc_sinkhorn_iters", 20)
    for _ in range(iters):
        m = low(m / (m.sum(-1, keepdims=True) + eps))
        m = low(m / (m.sum(-2, keepdims=True) + eps))
    return h_pre, h_post, m


def mix_pre(x, h_pre):
    """``H_pre x``: [T, D]."""
    return jnp.einsum("tn,tnd->td", h_pre, x)


def mix_post(x, branch, h_post, h_res):
    """``H_res x + H_post^T branch``: [T, n, D]."""
    return jnp.einsum("tij,tjd->tid", h_res, x) \
        + h_post[:, :, None] * branch[:, None, :]


def sublayer(cfg, lp, sub, x, fn, wrong=()):
    """The streams after the sublayer ``fn`` (``h [T, D] -> (branch, aux)``)
    wrapped by its mix (``sub``: ``attn`` / ``ffn``), and ``aux``. One
    stream (``hc_mult`` 1) is the plain residual: there is nothing to mix."""
    if cfg.get("hc_mult", 1) == 1:
        branch, aux = fn(x[:, 0])
        return x + branch[:, None], aux
    h_pre, h_post, h_res = mix_matrices(
        cfg, lp[f"hc_{sub}_phi"], lp[f"hc_{sub}_b"], lp[f"hc_{sub}_a"], x,
        wrong)
    branch, aux = fn(mix_pre(x, h_pre))
    return mix_post(x, branch, h_post, h_res), aux


# --------------------------------------------------------------- attention
def _mscale(scale, mscale):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def rope_frequencies(cfg, wrong=()):
    """(f_i [rope/2], the factor on cos and sin)."""
    dim, theta = cfg["qk_rope_head_dim"], cfg["rope_theta"]
    plain = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=F32) / dim)
    ys = cfg.get("rope_scaling")
    if ys is None or "plain_rope" in wrong:
        return plain, 1.0

    def correction_dim(rotations):
        return dim * math.log(ys["original_max_position_embeddings"]
                              / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(ys.get("beta_fast") or 32)), 0)
    high = min(math.ceil(correction_dim(ys.get("beta_slow") or 1)), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=F32) - low) / (high - low),
                    0, 1)
    freqs = plain / ys["factor"] * ramp + plain * (1 - ramp)
    if ys.get("mscale") and ys.get("mscale_all_dim"):
        amp = _mscale(ys["factor"], ys["mscale"]) \
            / _mscale(ys["factor"], ys["mscale_all_dim"])
    else:
        amp = _mscale(ys["factor"], 1.0)
    return freqs, amp


def softmax_scale(cfg, wrong=()):
    scale = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    ys = cfg.get("rope_scaling")
    if ys and ys.get("mscale_all_dim") and "no_yarn_scale" not in wrong:
        scale *= _mscale(ys["factor"], ys["mscale_all_dim"]) ** 2
    return scale


def _rope(x, positions, freqs, amp):
    """x [T, H, D]: rotate the pairs (2i, 2i+1) by position * f_i."""
    ang = positions.astype(F32)[:, None] * freqs[None, :]
    cos, sin = amp * jnp.cos(ang)[:, None, :], amp * jnp.sin(ang)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def attention(cfg, lp, x, wrong=(), start=0):
    t = x.shape[0]
    h, nope, dr = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                   cfg["qk_rope_head_dim"])
    rank, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    positions = start + jnp.arange(t)
    freqs, amp = rope_frequencies(cfg, wrong)
    if cfg.get("q_lora_rank"):
        qa = x @ lp["wq_a"]
        if "no_q_norm" not in wrong:
            qa = rms_norm(qa, lp["q_norm"], eps)
        q = qa @ lp["wq_b"]
    else:
        q = x @ lp["wq"]
    q = q.reshape(t, h, nope + dr)
    q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], positions, freqs,
                                          amp)
    ckr = x @ lp["w_kva"]
    c = rms_norm(ckr[:, :rank], lp["kv_norm"], eps)
    k_r = _rope(ckr[:, None, rank:], positions, freqs, amp)[:, 0]
    k_nope = jnp.einsum("tr,hnr->thn", c, lp["w_uk"])
    v = jnp.einsum("tr,hrv->thv", c, lp["w_uv"])
    scores = (jnp.einsum("ihn,jhn->hij", q_nope, k_nope)
              + jnp.einsum("ihd,jd->hij", q_rope, k_r)) \
        * softmax_scale(cfg, wrong)
    causal = jnp.tril(jnp.ones((t, t), bool))
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
    out = jnp.einsum("hij,jhv->ihv", probs, v)
    return out.reshape(t, -1) @ lp["wo"]


# ----------------------------------------------------------------- experts
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
        x, w_r = _bf16(x), _bf16(w_r)
    logits = x @ w_r
    s = jax.nn.sigmoid(logits)
    if "router_bf16" in wrong:
        s = _bf16(jax.nn.sigmoid(_bf16(logits)))
    if forced is None:
        _, chosen = jax.lax.top_k(s + lp["router_bias"], k)
    else:
        order = jnp.argsort(-jnp.take_along_axis(
            s + lp["router_bias"], forced, axis=1), axis=1)
        chosen = jnp.take_along_axis(forced, order, axis=1)[:, :k]
    picked = jnp.take_along_axis(s, chosen, axis=1)
    if cfg.get("norm_topk_prob", True):
        picked = picked / (picked.sum(-1, keepdims=True) + 1e-20)
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


# ------------------------------------------------------------------- model
def layer(cfg, kind, lp, x, wrong=(), forced=None, start=0):
    """One block over the streams ``x [T, n, D]``: (the streams after it,
    the chosen experts [T, k] or None)."""
    with jax.default_matmul_precision("highest"):
        eps = cfg["rms_norm_eps"]
        x, _ = sublayer(cfg, lp, "attn", x, lambda h: (attention(
            cfg, lp, rms_norm(h, lp["attn_norm"], eps), wrong, start), None),
            wrong)

        def ffn(h):
            hn = rms_norm(h, lp["mlp_norm"], eps)
            if kind == "dense":
                return gated_ffn(hn, lp["w_gate"], lp["w_up"],
                                 lp["w_down"]), None
            return sparse_ffn(cfg, lp, hn, wrong, forced)

        return sublayer(cfg, lp, "ffn", x, ffn, wrong)


def embed(params, cfg, token_ids):
    """The streams of every token: ``hc_mult`` copies of its embedding."""
    e = jnp.asarray(params["embed"], F32)[jnp.asarray(token_ids)]
    return jnp.broadcast_to(e[:, None, :],
                            (e.shape[0], cfg.get("hc_mult", 1), e.shape[1]))


def logits(params, cfg, x):
    """Logits of the streams ``x [T, n, D]``: their sum, normed."""
    with jax.default_matmul_precision("highest"):
        x = rms_norm(x.sum(1), jnp.asarray(params["final_norm"], F32),
                     cfg["rms_norm_eps"])
        return x @ jnp.asarray(params["lm_head"], F32)


def forward(params, cfg, token_ids, wrong=(), routing=None, forced=None,
            start=0):
    """Logits [T, V] of one sequence of token ids at positions ``start``
    on, every position. ``routing``: a list that receives each sparse
    layer's chosen experts [T, k], in layer order. ``forced``: each sparse
    layer's choice given ([n_sparse, T, k]; see ``route``)."""
    x = embed(params, cfg, token_ids)
    nd = cfg.get("first_k_dense_replace", 0)
    for i in range(cfg["num_hidden_layers"]):
        kind, lp = layer_params(params, cfg, i)
        x, chosen = layer(cfg, kind, lp, x, wrong,
                          None if forced is None or i < nd
                          else forced[i - nd], start)
        if routing is not None and chosen is not None:
            routing.append(chosen)
    return logits(params, cfg, x)
