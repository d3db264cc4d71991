"""Plain reference of the LFM2-MoE decoder (HF ``lfm2_moe``): the whole
forward of ONE sequence in ``jax.numpy``, float32, every product at
``highest`` precision, the convolution as a direct sum over its taps token by
token, the full attention matrix, no cache, no batching, no kernels, every
expert computed densely a few at a time and weighted by the routing. It
imports nothing of the program it judges and takes the parameter tree the
program's ``init_params`` makes (``layers.conv`` / ``layers.attention`` /
``layers.dense`` / ``layers.sparse``, stacked by kind) and the HF
``config.json`` as a dict.

The equations (HF's ``Lfm2Moe*`` modeling code as remembered: there was no
network where this was written, so every point the config's keys do not
settle is listed under ``assumed`` in
benchmarks/chip/configs/lfm2-8b-a1b-d16/deployment.json), eps ``norm_eps``:

    h_0 = E[token]
    for every layer:  h = h + operator(RMSNorm_operator(h))
                      h = h + ffn(RMSNorm_ffn(h))
    logits = RMSNorm_embedding(h) E^T                 (ONE norm; tied head)

``conv`` operator (``layer_types[i] == "conv"``), L = ``conv_L_cache`` taps:
    [B | C | x] = u W_in            (D -> 3 D, no bias; thirds in this order)
    z_t = B_t * x_t
    c_t = sum_{i < L} w[i] * z_{t-L+1+i}     causal, depthwise, zeros before
        the sequence, w[L-1] weighs the newest; NO activation, no bias
    y_t = C_t * c_t;  W_out y_t     (D -> D)

``full_attention`` operator, H heads of Dh over Hkv:
    q, k, v = u W_q, u W_k, u W_v   (no bias)
    q, k <- RMSNorm over each head's Dh lanes (one weight of Dh for every
        head of q, one for k), BEFORE rope
    rope, non-interleaved (pairs (i, i + Dh/2)), over all Dh lanes, theta
    causal softmax(q k^T Dh^-0.5) v over the H / Hkv query heads a KV head;
    W_o.

FFN, layers below ``num_dense_layers``: W_2 (silu(W_1 u) * W_3 u)
FFN, the others:
    s = sigmoid(u W_g) over the experts, in float32
    chosen = top-k of s + expert_bias   (the bias moves the CHOICE only)
    w = s[chosen] / (sum s[chosen] + 1e-6) * routed_scaling_factor
        (``norm_topk_prob``)
    y = sum_e w_e expert_e(u), every expert the same gated FFN at
        ``moe_intermediate_size``; nothing shared, no groups, no capacity.

Departures from the published modeling code, each without effect on the
result: (1) the tree holds an expert's gate and up matrices as one
``w_gate_up`` (gate then up) and the conv's weight without HF's middle axis:
they are multiplied as what they are; (2) the experts are computed for every
token and weighted by the routing (zero where not chosen), where HF gathers
each expert's tokens.

``wrong`` switches ONE equation to a plausible mistake; the tests use it to
show that their tolerance tells each of them from the right model.
(Swapping ``B`` and ``x`` is not one: ``B * x`` commutes.)
"""

import jax
import jax.numpy as jnp

WRONG = ("conv_silu", "gate_c_before_conv", "taps_reversed",
         "conv_state_zero_at_chunk", "no_qk_norm", "rope_interleaved",
         "bias_in_weights", "softmax_router", "no_topk_norm")
# Not other equations but the same ones in too little precision: what a chip
# run must tell from the right model (check_reference.py), a tiny float32
# test cannot.
LOW_PRECISION = ("router_bf16", "qk_norm_bf16")
F32 = jnp.float32
EXPERT_GROUP = 8      # experts computed at a time (memory, not meaning)
ROUTE_EPS = 1e-6
# The ``conv_state_zero_at_chunk`` mistake's chunk: the serving path cuts a
# prompt into chunks of this many tokens (the caller sets it to the
# engine's).
CHUNK = 1024


def _bf16(x):
    """``x`` rounded to bfloat16's precision, still float32."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def kinds(cfg, i):
    """((operator's stack, index in it), (FFN's stack, index in it)) of
    layer ``i``."""
    types = cfg["layer_types"]
    op = "conv" if types[i] == "conv" else "attention"
    at = sum(1 for t in types[:i] if t == types[i])
    nd = cfg.get("num_dense_layers", 0)
    return (op, at), (("dense", i) if i < nd else ("sparse", i - nd))


def layer_params(params, cfg, i):
    """(operator kind, FFN kind, that layer's parameters in float32)."""
    (op, op_at), (ffn, ffn_at) = kinds(cfg, i)
    lp = {}
    for kind, at in ((op, op_at), (ffn, ffn_at)):
        lp.update(jax.tree.map(lambda x: jnp.asarray(x[at], F32),
                               dict(params["layers"][kind])))
    return op, ffn, lp


def short_conv(cfg, lp, u, wrong=(), chunk=None):
    t, d = u.shape
    bcx = u @ lp["in_proj"]
    b, c, x = bcx[:, :d], bcx[:, d:2 * d], bcx[:, 2 * d:]
    z = b * x
    if "gate_c_before_conv" in wrong:
        z = c * z
    w = lp["conv_w"]                                            # [L, D]
    taps = w.shape[0]
    if "taps_reversed" in wrong:
        w = w[::-1]
    padded = jnp.concatenate([jnp.zeros((taps - 1, d), F32), z])
    pos = jnp.arange(t)
    conv = jnp.zeros_like(z)
    for i in range(taps):
        back = taps - 1 - i                  # w[i] weighs z_{t - back}
        term = padded[i:i + t] * w[i]
        if "conv_state_zero_at_chunk" in wrong:
            # A chunk's first tokens see zeros where the tokens before the
            # chunk stood.
            term = jnp.where((pos % (chunk or CHUNK) >= back)[:, None],
                             term, 0.0)
        conv = conv + term
    if "conv_silu" in wrong:
        conv = jax.nn.silu(conv)
    y = conv if "gate_c_before_conv" in wrong else c * conv
    return y @ lp["out_proj"]


def _rope(x, theta, interleaved=False):
    """x [T, H, D]: rotate the pairs (i, i + D/2) by position *
    theta^(-2i/D) (``interleaved``: the pairs (2i, 2i + 1): the
    ``rope_interleaved`` mistake)."""
    t, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    if interleaved:
        a, b = x[..., 0::2], x[..., 1::2]
        return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                         axis=-1).reshape(x.shape)
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def attention(cfg, lp, u, wrong=()):
    t = u.shape[0]
    h = cfg["num_attention_heads"]
    hkv = cfg.get("num_key_value_heads", h)
    dh = cfg.get("head_dim") or cfg["hidden_size"] // h
    eps = cfg["norm_eps"]
    q = (u @ lp["wq"]).reshape(t, h, dh)
    k = (u @ lp["wk"]).reshape(t, hkv, dh)
    v = (u @ lp["wv"]).reshape(t, hkv, dh)
    if "qk_norm_bf16" in wrong:
        # The norm as a bf16 program would compute it: operands, the mean
        # of squares and the product at 8 bits of mantissa.
        def norm(x, w):
            x = _bf16(x)
            return _bf16(_bf16(x * _bf16(jax.lax.rsqrt(
                _bf16(jnp.mean(_bf16(x * x), -1, keepdims=True)) + eps)))
                * _bf16(w))
        q, k = norm(q, lp["q_norm"]), norm(k, lp["k_norm"])
    elif "no_qk_norm" not in wrong:
        q = rms_norm(q, lp["q_norm"], eps)
        k = rms_norm(k, lp["k_norm"], eps)
    inter = "rope_interleaved" in wrong
    q, k = _rope(q, cfg["rope_theta"], inter), _rope(k, cfg["rope_theta"],
                                                     inter)
    k, v = jnp.repeat(k, h // hkv, axis=1), jnp.repeat(v, h // hkv, axis=1)
    scores = jnp.einsum("ihd,jhd->hij", q, k) * dh ** -0.5
    causal = jnp.tril(jnp.ones((t, t), bool))
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
    out = jnp.einsum("hij,jhd->ihd", probs, v)
    return out.reshape(t, -1) @ lp["wo"]


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
    if cfg.get("norm_topk_prob", True) and "no_topk_norm" not in wrong:
        picked = picked / (picked.sum(-1, keepdims=True) + ROUTE_EPS)
    picked = picked * cfg.get("routed_scaling_factor", 1.0)
    dense = jnp.zeros_like(s).at[
        jnp.arange(x.shape[0])[:, None], chosen].set(picked)
    return chosen, dense


def gated_ffn(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def sparse_ffn(cfg, lp, x, wrong=(), forced=None):
    """(the routed experts' sum [T, D], chosen experts [T, k])."""
    chosen, weights = route(cfg, lp, x, wrong, forced)
    f = lp["we_down"].shape[1]
    y = jnp.zeros_like(x)
    for e0 in range(0, weights.shape[1], EXPERT_GROUP):
        e1 = e0 + EXPERT_GROUP
        hgu = jnp.einsum("td,edf->etf", x, lp["w_gate_up"][e0:e1])
        act = jax.nn.silu(hgu[..., :f]) * hgu[..., f:]
        out = jnp.einsum("etf,efd->etd", act, lp["we_down"][e0:e1])
        y = y + jnp.einsum("te,etd->td", weights[:, e0:e1], out)
    return y, chosen


def layer(cfg, op, ffn, lp, h, wrong=(), forced=None, chunk=None):
    """One decoder layer over one sequence: (h [T, D] float32 after it, the
    chosen experts [T, k] or None)."""
    with jax.default_matmul_precision("highest"):
        eps = cfg["norm_eps"]
        u = rms_norm(h, lp["op_norm"], eps)
        h = h + (short_conv(cfg, lp, u, wrong, chunk) if op == "conv"
                 else attention(cfg, lp, u, wrong))
        u = rms_norm(h, lp["ffn_norm"], eps)
        if ffn == "dense":
            return h + gated_ffn(u, lp["w_gate"], lp["w_up"],
                                 lp["w_down"]), None
        y, chosen = sparse_ffn(cfg, lp, u, wrong, forced)
        return h + y, chosen


def embed(params, token_ids):
    return jnp.asarray(params["embed"], F32)[jnp.asarray(token_ids)]


def logits(params, cfg, h):
    with jax.default_matmul_precision("highest"):
        h = rms_norm(h, jnp.asarray(params["final_norm"], F32),
                     cfg["norm_eps"])
        return h @ jnp.asarray(params["embed"], F32).T


def forward(params, cfg, token_ids, wrong=(), routing=None, forced=None,
            chunk=None):
    """Logits [T, V] of one sequence of token ids, every position.
    ``routing``: a list that receives each sparse layer's chosen experts
    [T, k], in layer order. ``forced``: each sparse layer's choice given
    ([n_sparse, T, k]; see ``route``). ``chunk``: the serving path's prefill
    chunk (only the ``conv_state_zero_at_chunk`` mistake reads it)."""
    h = embed(params, token_ids)
    nd = cfg.get("num_dense_layers", 0)
    for i in range(cfg["num_hidden_layers"]):
        op, ffn, lp = layer_params(params, cfg, i)
        h, chosen = layer(cfg, op, ffn, lp, h, wrong,
                          None if forced is None or i < nd
                          else forced[i - nd], chunk)
        if routing is not None and chosen is not None:
            routing.append(chosen)
    return logits(params, cfg, h)
