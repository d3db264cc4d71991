"""Plain reference of the dots3-note decoder (HF ``dots3_note``): the whole
forward of ONE sequence in ``jax.numpy``, float32, every product at
``highest`` precision, latent attention in its EXPANDED form (a key and a
value per head made from the latent row), a masked full score matrix a block
of queries after the other (memory, not meaning), no cache, no ring, no
paging, no kernels, every HELD expert computed densely a few at a time and
weighted by the routing over the router's whole width. It imports nothing of
the program it judges and takes the parameter tree the program's
``init_params`` makes (``layers.full`` / ``layers.window`` by kind of
attention, ``layers.dense`` / ``layers.sparse`` by kind of FFN) and the HF
``config.json`` as a dict.

The equations (written from the config's keys and the catalog's description:
there was no network where this was written and no modeling code at hand, so
every point the keys do not settle is listed under ``assumed`` in
benchmarks/chip/configs/dots3-note-prev-ep16/deployment.json), eps
``rms_norm_eps``, no bias but the indexer's LayerNorm's:

    h_0 = E[token]
    for every layer:  h = h + attn(RMSNorm(h));  h = h + ffn(RMSNorm(h))
    logits = RMSNorm(h) W_head                              (untied)

Attention of layer l, ``x`` the normed stream. ``layer_types[l]``
``full_attention`` reads the keys ``num_attention_heads`` (H),
``q_lora_rank``, ``kv_lora_rank``, ``qk_nope_head_dim``,
``qk_rope_head_dim``, ``v_head_dim``, ``rope_theta``; ``sliding_attention``
the same with ``swa_`` before them:
    c_q = rho_q RMSNorm(x W_qa);  q = c_q W_qb  [H, nope | rope]
    [c_kv | k_r] = x W_kva;  c = rho_kv RMSNorm(c_kv)
    rho_q = (hidden / q rank)^1/2, rho_kv = (hidden / kv rank)^1/2
        (``apply_mla_qkv_lora_rescale``; 1 without)
    rope on q's rope lanes and on k_r (one for all heads): DeepSeek's
        interleaved pairs (2i, 2i + 1), theta the kind's, no scaling
    k_nope_h = c W_uk_h;  v_h = c W_uv_h   (``kv_b_proj``'s two halves)
    s_ij = (q_nope_i . k_nope_j + q_rope_i . k_r_j) / sqrt(nope + rope)
    FULL: visible to query i are the keys of S_i, where (the INDEXER)
        q_idx = c_q W_iq [``index_n_heads`` (Hi), ``index_head_dim`` (Di)]
        k_idx = LayerNorm(x W_ik) (weight AND bias) [Di]
        the layer's rope on the FIRST rope lanes of q_idx and k_idx
        w = (x W_iw) / sqrt(Hi Di)   [Hi]
        I_ij = sum_h w_ih relu(q_idx_ih . k_idx_j)   for j <= i
        S_i = the ``index_topk`` keys of largest I_i. (all of j <= i while
            no more exist; ties to the lower position)
    SLIDING: key j is visible iff 0 <= i - j < ``sliding_window_size`` (the
        token itself and the W - 1 before it); no indexer
    o_ih = softmax over the visible keys (s_i.) v_h
    g = sigmoid(x W_g) [H]: o_ih <- g_ih o_ih  (headwise gate);  W_o.

FFN, the first ``first_k_dense_replace`` layers: W_down (silu(W_gate u) *
W_up u). The others:
    s = sigmoid(u W_r) over ALL the routed experts (``n_routed_experts`` x
        ``ep_size``: the file's count is this chip's), in float32
    chosen = top-k of s + bias   (the bias moves the CHOICE only)
    w = s[chosen] / (sum s[chosen] + 1e-20) x ``routed_scaling_factor``
    y = shared(u) + sum over the chosen experts THIS CHIP HOLDS (``ep_rank``
        x ``n_routed_experts`` on) of w_e expert_e(u); what the experts held
        elsewhere would add is left out, here as in the program. The one
        shared expert is computed on every chip alike.

``wrong`` switches ONE equation to a plausible mistake; the tests use it to
show that their tolerance tells each of them from the right model.
"""

import jax
import jax.numpy as jnp

WRONG = ("no_indexer", "topk_half", "no_relu", "unsigned_index_weights",
         "index_rope_last_lanes", "index_no_rope", "layernorm_no_bias",
         "window_one_less", "window_one_more",
         "no_window", "no_gate", "elementwise_gate", "gate_after_wo",
         "no_rescale", "rescale_q_only", "one_theta_full",
         "one_theta_window", "rotate_half_rope", "bias_in_weights",
         "softmax_router", "no_shared_expert", "all_experts_here")
# Not other equations but the same ones in too little precision.
LOW_PRECISION = ("router_bf16", "index_bf16")
F32 = jnp.float32
EXPERT_GROUP = 8      # experts computed at a time (memory, not meaning)
QUERY_BLOCK = 128     # queries scored at a time (memory, not meaning)
ROUTE_EPS = 1e-20
FULL, SLIDING = "full_attention", "sliding_attention"


def _bf16(x):
    """``x`` rounded to bfloat16's precision, still float32."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def layer_norm(x, w, b, eps):
    x = x - jnp.mean(x, -1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w \
        + b


def kinds(cfg, i):
    """((attention's stack, index in it), (FFN's stack, index in it)) of
    layer ``i``."""
    types = cfg["layer_types"]
    nd = cfg.get("first_k_dense_replace", 0)
    attn = ("window" if types[i] == SLIDING else "full",
            sum(t == types[i] for t in types[:i]))
    return attn, (("dense", i) if i < nd else ("sparse", i - nd))


def layer_params(params, cfg, i):
    """(is the layer a sliding layer, its FFN's kind, its parameters in
    float32)."""
    (attn, a_at), (ffn, f_at) = kinds(cfg, i)
    lp = {k: jnp.asarray(x[a_at], F32)
          for k, x in params["layers"][attn].items()}
    lp.update({k: jnp.asarray(x[f_at], F32)
               for k, x in params["layers"][ffn].items()})
    return attn == "window", ffn, lp


def sizes(cfg, window):
    """(H, q rank, kv rank, nope, rope, v, theta) of a kind of layer."""
    p = "swa_" if window else ""
    return (cfg[p + "num_attention_heads"], cfg[p + "q_lora_rank"],
            cfg[p + "kv_lora_rank"], cfg[p + "qk_nope_head_dim"],
            cfg[p + "qk_rope_head_dim"], cfg[p + "v_head_dim"],
            cfg[p + "rope_theta"])


def _rope(x, theta, half=False):
    """x [T, H, D]: every pair (2i, 2i + 1) turned by position x
    theta^(-2i/D), in place (``half``: the pairs (i, i + D/2) instead)."""
    t, d = x.shape[0], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    if half:
        a, b = jnp.split(x, 2, axis=-1)
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     -1).reshape(x.shape)


def index_scores(cfg, lp, x, c_q, theta, wrong=()):
    """I [T, T] float32 of a full layer: entry (i, j) the indexer's score
    of key j for query i (every j, visible or not)."""
    t = x.shape[0]
    hi, di, dr = cfg["index_n_heads"], cfg["index_head_dim"], \
        cfg["qk_rope_head_dim"]
    q = (c_q @ lp["idx_wq"]).reshape(t, hi, di)
    k = layer_norm(x @ lp["idx_wk"], lp["idx_k_norm"],
                   0.0 if "layernorm_no_bias" in wrong else lp["idx_k_bias"],
                   cfg["rms_norm_eps"])[:, None, :]
    half = "rotate_half_rope" in wrong
    if "index_no_rope" in wrong:
        pass
    elif "index_rope_last_lanes" in wrong:
        q = jnp.concatenate([q[..., :-dr], _rope(q[..., -dr:], theta, half)],
                            -1)
        k = jnp.concatenate([k[..., :-dr], _rope(k[..., -dr:], theta, half)],
                            -1)
    else:
        q = jnp.concatenate([_rope(q[..., :dr], theta, half), q[..., dr:]],
                            -1)
        k = jnp.concatenate([_rope(k[..., :dr], theta, half), k[..., dr:]],
                            -1)
    w = (x @ lp["idx_w"]) * (hi ** -0.5 * di ** -0.5)          # [T, Hi]
    if "unsigned_index_weights" in wrong:
        w = jnp.abs(w)
    low = "index_bf16" in wrong
    if low:
        q, k, w = _bf16(q), _bf16(k), _bf16(w)

    def block(qw):
        qb, wb = qw
        s = jnp.einsum("ihd,jd->ihj", qb, k[:, 0])
        if low:
            s = _bf16(s)
        if "no_relu" not in wrong:
            s = jax.nn.relu(s)
        out = jnp.einsum("ih,ihj->ij", wb, s)
        return _bf16(out) if low else out

    blocks = -(-t // QUERY_BLOCK)
    pad = blocks * QUERY_BLOCK - t
    out = jax.lax.map(block, (
        jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
            blocks, QUERY_BLOCK, hi, di),
        jnp.pad(w, ((0, pad), (0, 0))).reshape(blocks, QUERY_BLOCK, hi)))
    return out.reshape(blocks * QUERY_BLOCK, t)[:t]


def selected(cfg, scores, wrong=()):
    """The mask S [T, T] of a full layer from its index scores: row i the
    ``index_topk`` keys j <= i of largest score (all of them while no more
    exist; ties to the lower position: ``lax.top_k``'s rule). A block of
    queries after the other (memory, not meaning)."""
    t = scores.shape[0]
    topk = cfg["index_topk"] // (2 if "topk_half" in wrong else 1)
    j = jnp.arange(t)
    if "no_indexer" in wrong or topk >= t:
        return j[None, :] <= j[:, None]
    blocks = -(-t // QUERY_BLOCK)
    pad = blocks * QUERY_BLOCK - t

    def block(args):
        rows, i0 = args
        i = i0 + jnp.arange(QUERY_BLOCK)
        causal = j[None, :] <= i[:, None]
        _, which = jax.lax.top_k(jnp.where(causal, rows, -jnp.inf), topk)
        mask = jnp.zeros((QUERY_BLOCK, t), bool).at[
            jnp.arange(QUERY_BLOCK)[:, None], which].set(True)
        return mask & causal

    out = jax.lax.map(block, (
        jnp.pad(scores, ((0, pad), (0, 0))).reshape(blocks, QUERY_BLOCK, t),
        jnp.arange(blocks) * QUERY_BLOCK))
    return out.reshape(blocks * QUERY_BLOCK, t)[:t]


def attention(cfg, lp, x, window, wrong=(), selection=None, forced=None):
    """The attention branch [T, D] of the normed stream ``x`` [T, D].
    ``selection``: a list that receives a full layer's mask S [T, T].
    ``forced`` [T, T] bool: the COMPARISON's, not the model's: take this
    mask as the layer's selection (selection is discontinuous, like
    routing)."""
    t, d = x.shape
    h, qr, rank, nope, dr, dv, theta = sizes(cfg, window)
    if "one_theta_full" in wrong:
        theta = cfg["rope_theta"]
    if "one_theta_window" in wrong:
        theta = cfg["swa_rope_theta"]
    eps = cfg["rms_norm_eps"]
    rescale = cfg.get("apply_mla_qkv_lora_rescale", False) \
        and "no_rescale" not in wrong
    rho_q = (d / qr) ** 0.5 if rescale else 1.0
    rho_kv = (d / rank) ** 0.5 \
        if rescale and "rescale_q_only" not in wrong else 1.0
    c_q = rho_q * rms_norm(x @ lp["wq_a"], lp["q_norm"], eps)
    q = (c_q @ lp["wq_b"]).reshape(t, h, nope + dr)
    ckr = x @ lp["w_kva"]
    c = rho_kv * rms_norm(ckr[:, :rank], lp["kv_norm"], eps)     # [T, rank]
    half = "rotate_half_rope" in wrong
    k_r = _rope(ckr[:, None, rank:], theta, half)                # [T, 1, dr]
    q_r = _rope(q[..., nope:], theta, half)
    k_nope = jnp.einsum("tr,hnr->thn", c, lp["w_uk"])
    v = jnp.einsum("tr,hrv->thv", c, lp["w_uv"])
    j = jnp.arange(t)
    if window:
        bound = None if "no_window" in wrong else \
            cfg["sliding_window_size"] + ("window_one_more" in wrong) \
            - ("window_one_less" in wrong)
        seen = j[None, :] <= j[:, None]
        if bound is not None:
            seen &= j[:, None] - j[None, :] < bound
    elif forced is not None:
        seen = forced
    else:
        # (The indexer takes the RESCALED c_q; a positive factor on every
        # score of a query changes no top-k, so the other reading is the
        # same model.)
        seen = selected(cfg, index_scores(cfg, lp, x, c_q, theta, wrong),
                        wrong)
    if selection is not None and not window:
        selection.append(seen)

    def block(args):
        qn, qr_, mask = args
        scores = (jnp.einsum("ihd,jhd->hij", qn, k_nope)
                  + jnp.einsum("ihd,jd->hij", qr_, k_r[:, 0])) \
            * (nope + dr) ** -0.5
        scores = jnp.where(mask[None], scores, -jnp.inf)
        return jnp.einsum("hij,jhd->ihd", jax.nn.softmax(scores, -1), v)

    # A block of queries after the other (``lax.map``); the rows that pad
    # the last block see every key and are dropped.
    blocks = -(-t // QUERY_BLOCK)
    pad = blocks * QUERY_BLOCK - t

    def cut(a, fill=0):
        a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1),
                    constant_values=fill)
        return a.reshape(blocks, QUERY_BLOCK, *a.shape[1:])

    o = jax.lax.map(block, (cut(q[..., :nope]), cut(q_r), cut(seen, True)))
    o = o.reshape(blocks * QUERY_BLOCK, h, dv)[:t]
    if "elementwise_gate" in wrong:
        # One scalar an ELEMENT of the heads' outputs, from the only gate
        # weights there are: element e takes column e mod H.
        cols = jnp.arange(h * dv) % h
        o = o * jax.nn.sigmoid(x @ lp["w_head_gate"][:, cols]).reshape(
            t, h, dv)
    elif "no_gate" not in wrong and "gate_after_wo" not in wrong:
        o = o * jax.nn.sigmoid(x @ lp["w_head_gate"])[:, :, None]
    y = o.reshape(t, h * dv) @ lp["wo"]
    if "gate_after_wo" in wrong:
        y = y * jnp.mean(jax.nn.sigmoid(x @ lp["w_head_gate"]), -1,
                         keepdims=True)
    return y


def held(cfg):
    """(the first expert this chip holds, how many)."""
    n = cfg["n_routed_experts"]
    return cfg.get("ep_rank", 0) * n, n


def route(cfg, lp, x, wrong=(), forced=None):
    """(chosen experts [T, k] of the router's whole width, dense weights
    [T, E_all]: zero where not chosen). ``forced`` [T, k]: the
    COMPARISON's, not the model's: take these experts as the choice."""
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
    """(the shared expert plus the HELD routed experts' weighted sum
    [T, D], chosen experts [T, k])."""
    chosen, weights = route(cfg, lp, x, wrong, forced)
    first, count = held(cfg)
    if "all_experts_here" in wrong:
        # The share's experts standing in for the router's first ``count``.
        first = 0
    f = lp["we_down"].shape[1]
    y = jnp.zeros_like(x) if "no_shared_expert" in wrong else \
        gated_ffn(x, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    for e0 in range(0, count, EXPERT_GROUP):
        e1 = min(count, e0 + EXPERT_GROUP)
        hgu = jnp.einsum("td,edf->etf", x, lp["w_gate_up"][e0:e1])
        act = jax.nn.silu(hgu[..., :f]) * hgu[..., f:]
        out = jnp.einsum("etf,efd->etd", act, lp["we_down"][e0:e1])
        y = y + jnp.einsum("te,etd->td",
                           weights[:, first + e0:first + e1], out)
    return y, chosen


def layer(cfg, window, ffn, lp, h, wrong=(), forced=None, selection=None,
          forced_selection=None):
    """One decoder layer over one sequence: (h [T, D] float32 after it, the
    chosen experts [T, k] or None)."""
    with jax.default_matmul_precision("highest"):
        eps = cfg["rms_norm_eps"]
        h = h + attention(cfg, lp, rms_norm(h, lp["attn_norm"], eps),
                          window, wrong, selection, forced_selection)
        u = rms_norm(h, lp["ffn_norm"], eps)
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
                     cfg["rms_norm_eps"])
        return h @ jnp.asarray(params["lm_head"], F32)


def forward(params, cfg, token_ids, wrong=(), routing=None, forced=None,
            selection=None, forced_selection=None):
    """Logits [T, V] of one sequence of token ids, every position.
    ``routing``: a list that receives each sparse layer's chosen experts
    [T, k], in layer order; ``selection``: one that receives each full
    layer's mask [T, T]. ``forced`` ([n_sparse, T, k]) and
    ``forced_selection`` ([n_full, T, T]): the choices given (see ``route``
    and ``attention``)."""
    h = embed(params, cfg, token_ids)
    sparse = full = 0
    for i in range(cfg["num_hidden_layers"]):
        window, ffn, lp = layer_params(params, cfg, i)
        h, chosen = layer(
            cfg, window, ffn, lp, h, wrong,
            None if forced is None or ffn == "dense" else forced[sparse],
            selection,
            None if forced_selection is None or window
            else forced_selection[full])
        sparse += ffn == "sparse"
        full += not window
        if routing is not None and chosen is not None:
            routing.append(chosen)
    return logits(params, cfg, h)
