"""Plain reference of the AFMoE decoder (HF ``afmoe``: Arcee's Trinity): the
whole forward of ONE sequence in ``jax.numpy``, float32, every product at
``highest`` precision, attention as masked scores a block of queries at a
time (memory, not meaning: 12k tokens' whole matrix over 32 heads is 20 GB),
no cache, no batching, no kernels, every expert computed densely a few at a
time and weighted by the routing. It imports nothing of the program it
judges and takes the parameter tree the program's ``init_params`` makes
(``layers.dense`` / ``layers.sparse``, stacked by kind of FFN) and the HF
``config.json`` as a dict.

The equations (HF's ``modeling_afmoe.py`` as remembered: there was no network
where this was written, so every point the config's keys do not settle is
listed under ``assumed`` in
benchmarks/chip/configs/trinity-mini-d8/deployment.json), eps
``rms_norm_eps``:

    h_0 = E[token] * sqrt(hidden_size)                      (``mup_enabled``)
    for every layer:  h = h + RMSNorm_post_attn(attn(RMSNorm_in(h)))
                      h = h + RMSNorm_post_mlp(ffn(RMSNorm_pre_mlp(h)))
    logits = RMSNorm(h) W_head                              (untied)

Attention, H heads of Dh over Hkv, ``a`` the normed stream:
    q, k, v, g = a W_q, a W_k, a W_v, a W_gate   (no bias; g is H x Dh wide)
    q, k <- RMSNorm over each head's Dh lanes (one weight of Dh for every
        head of q, one for k), BEFORE rope
    ``layer_types[l] == "sliding_attention"``: rope, non-interleaved (pairs
        (i, i + Dh/2)), over all Dh lanes, theta, no scaling; a
        ``full_attention`` layer carries NO position embedding
    key j is visible to query i iff j <= i and, in a sliding layer,
        i - j < sliding_window (the token itself and the sliding_window - 1
        before it)
    o = softmax(q k^T Dh^-0.5) v over the H / Hkv query heads a KV head
    o <- o * sigmoid(g);  W_o.

FFN, layers below ``num_dense_layers``: W_down (silu(W_gate u) * W_up u)
FFN, the others:
    s = sigmoid(u W_r) over the experts, in float32
    chosen = top-k of s + expert_bias   (the bias moves the CHOICE only)
    w = s[chosen] / (sum s[chosen] + 1e-20) * route_scale   (``route_norm``)
    y = sum_e w_e expert_e(u) + shared(u), every expert the same gated FFN at
        ``moe_intermediate_size``, the shared one at ``num_shared_experts``
        times that; no groups, no capacity, no dropped token.

Departures from the published modeling code, each without effect on the
result: (1) the tree holds an expert's gate and up matrices as one
``w_gate_up`` (gate then up): multiplied as what it is; (2) the experts are
computed for every token and weighted by the routing (zero where not chosen),
where HF gathers each expert's tokens; (3) the scores are computed a block of
queries at a time.

``wrong`` switches ONE equation to a plausible mistake; the tests use it to
show that their tolerance tells each of them from the right model.
"""

import jax
import jax.numpy as jnp

WRONG = ("no_span", "span_one_more", "rope_in_full_layers", "no_rope",
         "no_qk_norm", "no_gate", "gate_from_stream", "no_post_norms",
         "no_mup", "bias_in_weights", "softmax_router", "no_shared",
         "no_route_scale")
# Not other equations but the same ones in too little precision: what a
# comparison must tell from the right model.
LOW_PRECISION = ("router_bf16", "norm_bf16", "softmax_bf16")
F32 = jnp.float32
EXPERT_GROUP = 8      # experts computed at a time (memory, not meaning)
QUERY_BLOCK = 512     # queries scored at a time (memory, not meaning)
ROUTE_EPS = 1e-20


def _bf16(x):
    """``x`` rounded to bfloat16's precision, still float32."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def rms_norm(x, w, eps, low=False):
    if low:
        # The norm as a bf16 program would compute it: operands, the mean
        # of squares and the product at 8 bits of mantissa.
        x = _bf16(x)
        return _bf16(_bf16(x * _bf16(jax.lax.rsqrt(
            _bf16(jnp.mean(_bf16(x * x), -1, keepdims=True)) + eps)))
            * _bf16(w))
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def kind(cfg, i):
    """(the FFN's stack, the layer's index in it) of layer ``i``."""
    nd = cfg.get("num_dense_layers", 0)
    return ("dense", i) if i < nd else ("sparse", i - nd)


def layer_params(params, cfg, i):
    """(is the layer sliding, its FFN's kind, its parameters in float32)."""
    ffn, at = kind(cfg, i)
    lp = jax.tree.map(lambda x: jnp.asarray(x[at], F32),
                      dict(params["layers"][ffn]))
    return cfg["layer_types"][i] == "sliding_attention", ffn, lp


def _rope(x, theta):
    """x [T, H, D]: rotate the pairs (i, i + D/2) by position *
    theta^(-2i/D)."""
    t, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def attention(cfg, lp, a, sliding, wrong=(), stream=None):
    """The attention branch [T, D] of the normed stream ``a`` [T, D]
    (``stream``: the stream before its norm, which only the
    ``gate_from_stream`` mistake reads)."""
    t = a.shape[0]
    h = cfg["num_attention_heads"]
    hkv = cfg.get("num_key_value_heads", h)
    dh = cfg.get("head_dim") or cfg["hidden_size"] // h
    eps = cfg["rms_norm_eps"]
    q = (a @ lp["wq"]).reshape(t, h, dh)
    k = (a @ lp["wk"]).reshape(t, hkv, dh)
    v = (a @ lp["wv"]).reshape(t, hkv, dh)
    g = (stream if "gate_from_stream" in wrong else a) @ lp["wg"]
    if "no_qk_norm" not in wrong:
        low = "norm_bf16" in wrong
        q = rms_norm(q, lp["q_norm"], eps, low)
        k = rms_norm(k, lp["k_norm"], eps, low)
    rotates = (sliding or "rope_in_full_layers" in wrong) \
        and "no_rope" not in wrong
    if rotates:
        q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    span = None
    if sliding and "no_span" not in wrong:
        span = cfg["sliding_window"] + ("span_one_more" in wrong)
    k, v = jnp.repeat(k, h // hkv, axis=1), jnp.repeat(v, h // hkv, axis=1)
    j = jnp.arange(t)
    out = []
    for i0 in range(0, t, QUERY_BLOCK):
        i = jnp.arange(i0, min(t, i0 + QUERY_BLOCK))
        scores = jnp.einsum("ihd,jhd->hij", q[i0:i0 + QUERY_BLOCK], k) \
            * dh ** -0.5
        seen = j[None, :] <= i[:, None]
        if span is not None:
            seen &= i[:, None] - j[None, :] < span
        scores = jnp.where(seen[None], scores, -jnp.inf)
        if "softmax_bf16" in wrong:
            scores = _bf16(scores)
            p = _bf16(jnp.exp(_bf16(scores - scores.max(-1, keepdims=True))))
            probs = _bf16(p / _bf16(p.sum(-1, keepdims=True)))
        else:
            probs = jax.nn.softmax(scores, -1)
        out.append(jnp.einsum("hij,jhd->ihd", probs, v))
    o = jnp.concatenate(out).reshape(t, -1)
    if "no_gate" not in wrong:
        o = o * jax.nn.sigmoid(g)
    return o @ lp["wo"]


def route(cfg, lp, x, wrong=(), forced=None):
    """(chosen experts [T, k], dense weights [T, E]: zero where not
    chosen). ``forced`` [T, k]: the COMPARISON's, not the model's: take
    these experts as the choice and compute the rest (scores, weights,
    experts) as always (routing is discontinuous: two right computations in
    different precisions choose differently at a near-tie)."""
    k = cfg["num_experts_per_tok"]
    w_r = lp["w_router"]
    if "router_bf16" in wrong:
        x, w_r = _bf16(x), _bf16(w_r)
    logits = x @ w_r
    if "softmax_router" in wrong:
        s = jax.nn.softmax(logits, axis=-1)
    elif "router_bf16" in wrong:
        s = _bf16(jax.nn.sigmoid(_bf16(logits)))
    else:
        s = jax.nn.sigmoid(logits)
    biased = s + lp["router_bias"]
    if forced is None:
        _, chosen = jax.lax.top_k(biased, k)
    else:
        chosen = forced
    picked = jnp.take_along_axis(
        biased if "bias_in_weights" in wrong else s, chosen, axis=1)
    if cfg.get("route_norm", True):
        picked = picked / (picked.sum(-1, keepdims=True) + ROUTE_EPS)
    if "no_route_scale" not in wrong:
        picked = picked * cfg.get("route_scale", 1.0)
    dense = jnp.zeros_like(s).at[
        jnp.arange(x.shape[0])[:, None], chosen].set(picked)
    return chosen, dense


def gated_ffn(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def sparse_ffn(cfg, lp, x, wrong=(), forced=None):
    """(the routed experts' sum plus the shared expert [T, D], chosen
    experts [T, k])."""
    chosen, weights = route(cfg, lp, x, wrong, forced)
    f = lp["we_down"].shape[1]
    y = jnp.zeros_like(x)
    for e0 in range(0, weights.shape[1], EXPERT_GROUP):
        e1 = e0 + EXPERT_GROUP
        hgu = jnp.einsum("td,edf->etf", x, lp["w_gate_up"][e0:e1])
        act = jax.nn.silu(hgu[..., :f]) * hgu[..., f:]
        out = jnp.einsum("etf,efd->etd", act, lp["we_down"][e0:e1])
        y = y + jnp.einsum("te,etd->td", weights[:, e0:e1], out)
    if "no_shared" not in wrong:
        y = y + gated_ffn(x, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    return y, chosen


def layer(cfg, sliding, ffn, lp, h, wrong=(), forced=None):
    """One decoder layer over one sequence: (h [T, D] float32 after it, the
    chosen experts [T, k] or None)."""
    with jax.default_matmul_precision("highest"):
        eps = cfg["rms_norm_eps"]
        low = "norm_bf16" in wrong

        def post(x, w):
            return x if "no_post_norms" in wrong else rms_norm(x, w, eps, low)

        a = rms_norm(h, lp["attn_norm"], eps, low)
        h = h + post(attention(cfg, lp, a, sliding, wrong, h),
                     lp["post_attn_norm"])
        u = rms_norm(h, lp["mlp_norm"], eps, low)
        if ffn == "dense":
            y, chosen = gated_ffn(u, lp["w_gate"], lp["w_up"],
                                  lp["w_down"]), None
        else:
            y, chosen = sparse_ffn(cfg, lp, u, wrong, forced)
        return h + post(y, lp["post_mlp_norm"]), chosen


def embed(params, cfg, token_ids, wrong=()):
    rows = jnp.asarray(params["embed"], F32)[jnp.asarray(token_ids)]
    if cfg.get("mup_enabled", False) and "no_mup" not in wrong:
        rows = rows * cfg["hidden_size"] ** 0.5
    return rows


def logits(params, cfg, h):
    with jax.default_matmul_precision("highest"):
        h = rms_norm(h, jnp.asarray(params["final_norm"], F32),
                     cfg["rms_norm_eps"])
        return h @ jnp.asarray(params["lm_head"], F32)


def forward(params, cfg, token_ids, wrong=(), routing=None, forced=None):
    """Logits [T, V] of one sequence of token ids, every position.
    ``routing``: a list that receives each sparse layer's chosen experts
    [T, k], in layer order. ``forced``: each sparse layer's choice given
    ([n_sparse, T, k]; see ``route``)."""
    h = embed(params, cfg, token_ids, wrong)
    nd = cfg.get("num_dense_layers", 0)
    for i in range(cfg["num_hidden_layers"]):
        sliding, ffn, lp = layer_params(params, cfg, i)
        h, chosen = layer(cfg, sliding, ffn, lp, h, wrong,
                          None if forced is None or i < nd
                          else forced[i - nd])
        if routing is not None and chosen is not None:
            routing.append(chosen)
    return logits(params, cfg, h)
