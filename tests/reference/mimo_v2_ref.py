"""Plain reference of the MiMo-V2 decoder (HF ``mimo_v2``): the whole forward
of ONE sequence in ``jax.numpy``, float32, every product at ``highest``
precision, attention as a masked full score matrix (with the sink as one
more column) a block of queries after the other (memory, not meaning), no
cache, no ring, no batching, no kernels, every HELD expert computed densely a
few at a time and weighted by the routing. It imports nothing of the program it
judges and takes the parameter tree the program's ``init_params`` makes
(``layers.full`` / ``layers.window`` by kind of attention, ``layers.dense``
/ ``layers.sparse`` by kind of FFN) and the HF ``config.json`` as a dict.

The equations (written from the config's keys: there was no network where
this was written and no modeling code at hand, so every point the keys do
not settle is listed under ``assumed`` in
benchmarks/chip/configs/mimo-v2.5-ep16/deployment.json), eps
``layernorm_epsilon``, no bias anywhere:

    h_0 = E[token]
    for every layer:  h = h + attn(RMSNorm(h));  h = h + ffn(RMSNorm(h))
    logits = RMSNorm(h) W_head                              (untied)

Attention of layer l, H query heads, ``a`` the normed stream:
    ``hybrid_layer_pattern[l]`` 0: FULL, Hkv = ``num_key_value_heads``,
        theta = ``rope_theta``, every key up to the query, no sink
    1: WINDOW, Hkv = ``swa_num_key_value_heads``, theta =
        ``swa_rope_theta``, key j visible to query i iff 0 <= i - j <
        ``sliding_window`` (the token and the W - 1 before it), and a
        learned logit s_h a query head (``add_swa_attention_sink_bias``)
    q = a W_q [H, Dk], k = a W_k [Hkv, Dk], v = a W_v [Hkv, Dv]
        (Dk ``head_dim``, Dv ``v_head_dim``)
    rotate-half rope (pairs (i, i + R/2)) over the first R = floor(Dk x
        ``partial_rotary_factor``) lanes (to whole pairs) of q and k, the
        other lanes as they are
    s_ij = q_i . k_j / sqrt(Dk)
    p_ij = exp(s_ij) / (sum_j' exp(s_ij') + exp(s_h))   in a window layer
        (the sink joins the denominator and has no value), plain softmax
        in a full one
    o_i = sum_j p_ij (``attention_value_scale`` v_j);  W_o.

FFN, layers where ``moe_layer_freq[l]`` is 0: W_down (silu(W_gate u) * W_up u)
FFN, the others:
    s = sigmoid(u W_r) over ALL the routed experts (``n_routed_experts`` x
        ``ep_size``: the file's count is this chip's), in float32
    chosen = top-k of s + bias   (the bias moves the CHOICE only)
    w = s[chosen] / (sum s[chosen] + 1e-20) x ``routed_scaling_factor``
        (``norm_topk_prob``; null = 1)
    y = sum over the chosen experts THIS CHIP HOLDS (``ep_rank`` x
        ``n_routed_experts`` on) of w_e expert_e(u); what the experts held
        elsewhere would add is left out, and that partial sum goes on to
        the next layer, here as in the program. No shared expert, no
        groups, no capacity, no dropped token.

Departures from the published description, each without effect on the
result: (1) the tree holds an expert's gate and up matrices as one
``w_gate_up`` (gate then up) and the fused q|k|v projection as three:
multiplied as what they are; (2) the held experts are computed for every
token and weighted by the routing (zero where not chosen); (3) the scores
are computed a block of queries at a time.

``wrong`` switches ONE equation to a plausible mistake; the tests use it to
show that their tolerance tells each of them from the right model.
"""

import jax
import jax.numpy as jnp

WRONG = ("no_sink", "sink_with_value", "window_one_less", "window_one_more",
         "no_window", "values_unscaled", "rope_all_lanes", "rope_32_lanes",
         "one_theta_full", "one_theta_window", "interleaved_rope",
         "bias_in_weights", "softmax_router", "all_experts_here")
# Not other equations but the same ones in too little precision.
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
        x = _bf16(x)
        return _bf16(_bf16(x * _bf16(jax.lax.rsqrt(
            _bf16(jnp.mean(_bf16(x * x), -1, keepdims=True)) + eps)))
            * _bf16(w))
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def kinds(cfg, i):
    """((attention's stack, index in it), (FFN's stack, index in it)) of
    layer ``i``."""
    pattern, freq = cfg["hybrid_layer_pattern"], cfg["moe_layer_freq"]
    attn = ("window" if pattern[i] else "full",
            sum(p == pattern[i] for p in pattern[:i]))
    ffn = ("sparse" if freq[i] else "dense",
           sum(f == freq[i] for f in freq[:i]))
    return attn, ffn


def layer_params(params, cfg, i):
    """(is the layer a window layer, its FFN's kind, its parameters in
    float32)."""
    (attn, a_at), (ffn, f_at) = kinds(cfg, i)
    lp = {k: jnp.asarray(x[a_at], F32)
          for k, x in params["layers"][attn].items()}
    lp.update({k: jnp.asarray(x[f_at], F32)
               for k, x in params["layers"][ffn].items()})
    return attn == "window", ffn, lp


def rotary_lanes(cfg) -> int:
    return int(cfg["head_dim"] * cfg.get("partial_rotary_factor", 1.0)) \
        // 2 * 2


def _rope(x, theta, lanes, interleaved=False):
    """x [T, H, D]: rotate the pairs (i, i + lanes/2) of the first
    ``lanes`` lanes by position * theta^(-2i/lanes)."""
    t = x.shape[0]
    inv = 1.0 / theta ** (jnp.arange(0, lanes, 2, dtype=F32) / lanes)
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    r, rest = x[..., :lanes], x[..., lanes:]
    if interleaved:
        a, b = r[..., 0::2], r[..., 1::2]
        turned = jnp.stack([a * cos - b * sin, b * cos + a * sin],
                           -1).reshape(r.shape)
    else:
        a, b = jnp.split(r, 2, axis=-1)
        turned = jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)
    return jnp.concatenate([turned, rest], -1)


def attention(cfg, lp, a, window, wrong=()):
    """The attention branch [T, D] of the normed stream ``a`` [T, D]."""
    t = a.shape[0]
    h = cfg["num_attention_heads"]
    hkv = cfg["swa_num_key_value_heads"] if window \
        else cfg["num_key_value_heads"]
    dk, dv = cfg["head_dim"], cfg["v_head_dim"]
    q = (a @ lp["wq"]).reshape(t, h, dk)
    k = (a @ lp["wk"]).reshape(t, hkv, dk)
    v = (a @ lp["wv"]).reshape(t, hkv, dv)
    theta = cfg["swa_rope_theta"] if window else cfg["rope_theta"]
    if "one_theta_full" in wrong:
        theta = cfg["rope_theta"]
    if "one_theta_window" in wrong:
        theta = cfg["swa_rope_theta"]
    lanes = rotary_lanes(cfg)
    if "rope_all_lanes" in wrong:
        lanes = dk
    if "rope_32_lanes" in wrong:
        lanes = lanes // 2
    inter = "interleaved_rope" in wrong
    q, k = _rope(q, theta, lanes, inter), _rope(k, theta, lanes, inter)
    if "values_unscaled" not in wrong:
        v = v * cfg.get("attention_value_scale", 1.0)
    bound = None
    if window and "no_window" not in wrong:
        bound = cfg["sliding_window"] + ("window_one_more" in wrong) \
            - ("window_one_less" in wrong)
    sink = None
    if window and cfg.get("add_swa_attention_sink_bias") \
            and "no_sink" not in wrong:
        sink = lp["sink"]
    k, v = jnp.repeat(k, h // hkv, axis=1), jnp.repeat(v, h // hkv, axis=1)
    j = jnp.arange(t)
    values = v
    if sink is not None:
        # One more column, every query's: the sink's logit, whose value is
        # zero (``sink_with_value``: the mean value instead).
        extra = v.mean(0, keepdims=True) if "sink_with_value" in wrong \
            else jnp.zeros_like(v[:1])
        values = jnp.concatenate([v, extra], 0)

    def block(args):
        qb, i0 = args
        i = i0 + jnp.arange(QUERY_BLOCK)
        scores = jnp.einsum("ihd,jhd->hij", qb, k) * dk ** -0.5
        seen = j[None, :] <= i[:, None]
        if bound is not None:
            seen &= i[:, None] - j[None, :] < bound
        scores = jnp.where(seen[None], scores, -jnp.inf)
        if sink is not None:
            scores = jnp.concatenate([
                scores, jnp.broadcast_to(sink[:, None, None],
                                         scores.shape[:2] + (1,))], -1)
        if "softmax_bf16" in wrong:
            scores = _bf16(scores)
            p = _bf16(jnp.exp(_bf16(scores - scores.max(-1, keepdims=True))))
            probs = _bf16(p / _bf16(p.sum(-1, keepdims=True)))
        else:
            probs = jax.nn.softmax(scores, -1)
        return jnp.einsum("hij,jhd->ihd", probs, values)

    # A block of queries after the other (``lax.map``: under ``jit`` a
    # Python loop's blocks are all alive at once, 1 GB each at 64 heads and
    # 8.7 k keys); the rows that pad the last block see every key and are
    # dropped.
    blocks = -(-t // QUERY_BLOCK)
    padded = jnp.pad(q, ((0, blocks * QUERY_BLOCK - t), (0, 0), (0, 0)))
    out = jax.lax.map(block, (
        padded.reshape(blocks, QUERY_BLOCK, h, dk),
        jnp.arange(blocks) * QUERY_BLOCK))
    return out.reshape(blocks * QUERY_BLOCK, -1)[:t] @ lp["wo"]


def held(cfg):
    """(the first expert this chip holds, how many)."""
    n = cfg["n_routed_experts"]
    return cfg.get("ep_rank", 0) * n, n


def route(cfg, lp, x, wrong=(), forced=None):
    """(chosen experts [T, k] of the router's whole width, dense weights
    [T, E_all]: zero where not chosen). ``forced`` [T, k]: the
    COMPARISON's, not the model's: take these experts as the choice and
    compute the rest as always (routing is discontinuous: two right
    computations in different precisions choose differently at a
    near-tie)."""
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
    if cfg.get("norm_topk_prob", True):
        picked = picked / (picked.sum(-1, keepdims=True) + ROUTE_EPS)
    picked = picked * (cfg.get("routed_scaling_factor") or 1.0)
    dense = jnp.zeros_like(s).at[
        jnp.arange(x.shape[0])[:, None], chosen].set(picked)
    return chosen, dense


def gated_ffn(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def sparse_ffn(cfg, lp, x, wrong=(), forced=None):
    """(the HELD routed experts' weighted sum [T, D], chosen experts
    [T, k])."""
    chosen, weights = route(cfg, lp, x, wrong, forced)
    first, count = held(cfg)
    if "all_experts_here" in wrong:
        # The share's experts standing in for the router's first ``count``.
        first = 0
    f = lp["we_down"].shape[1]
    y = jnp.zeros_like(x)
    for e0 in range(0, count, EXPERT_GROUP):
        e1 = min(count, e0 + EXPERT_GROUP)
        hgu = jnp.einsum("td,edf->etf", x, lp["w_gate_up"][e0:e1])
        act = jax.nn.silu(hgu[..., :f]) * hgu[..., f:]
        out = jnp.einsum("etf,efd->etd", act, lp["we_down"][e0:e1])
        y = y + jnp.einsum("te,etd->td",
                           weights[:, first + e0:first + e1], out)
    return y, chosen


def layer(cfg, window, ffn, lp, h, wrong=(), forced=None):
    """One decoder layer over one sequence: (h [T, D] float32 after it, the
    chosen experts [T, k] or None)."""
    with jax.default_matmul_precision("highest"):
        eps = cfg["layernorm_epsilon"]
        low = "norm_bf16" in wrong
        h = h + attention(cfg, lp, rms_norm(h, lp["attn_norm"], eps, low),
                          window, wrong)
        u = rms_norm(h, lp["ffn_norm"], eps, low)
        if ffn == "dense":
            y, chosen = gated_ffn(u, lp["w_gate"], lp["w_up"],
                                  lp["w_down"]), None
        else:
            y, chosen = sparse_ffn(cfg, lp, u, wrong, forced)
        return h + y, chosen


def embed(params, cfg, token_ids):
    return jnp.asarray(params["embed"], F32)[jnp.asarray(token_ids)]


def logits(params, cfg, h):
    with jax.default_matmul_precision("highest"):
        h = rms_norm(h, jnp.asarray(params["final_norm"], F32),
                     cfg["layernorm_epsilon"])
        return h @ jnp.asarray(params["lm_head"], F32)


def forward(params, cfg, token_ids, wrong=(), routing=None, forced=None):
    """Logits [T, V] of one sequence of token ids, every position.
    ``routing``: a list that receives each sparse layer's chosen experts
    [T, k], in layer order. ``forced``: each sparse layer's choice given
    ([n_sparse, T, k]; see ``route``)."""
    h = embed(params, cfg, token_ids)
    sparse = 0
    for i in range(cfg["num_hidden_layers"]):
        window, ffn, lp = layer_params(params, cfg, i)
        h, chosen = layer(
            cfg, window, ffn, lp, h, wrong,
            None if forced is None or ffn == "dense" else forced[sparse])
        sparse += ffn == "sparse"
        if routing is not None and chosen is not None:
            routing.append(chosen)
    return logits(params, cfg, h)
