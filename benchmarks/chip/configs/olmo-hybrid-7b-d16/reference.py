"""Plain reference of the Olmo-Hybrid decoder: the whole forward of ONE
sequence in ``jax.numpy``, float32, every product at ``highest`` precision,
the recurrence token by token, no cache, no batching, no kernels. It imports
nothing of the program it judges and takes the parameter tree the program's
``init_params`` makes (``layers.linear`` / ``layers.full`` stacked by kind)
and the HF ``config.json`` as a dict.

The equations (``transformers`` 4.57: modeling_olmo3.py for the block and
the full layer, modeling_qwen3_next.py:522-561 for the recurrence):

Block (Olmo's reordered norm, eps ``rms_norm_eps``), for every layer:
    h = x + RMSNorm_post_attn(mixer(x));  y = h + RMSNorm_post_ffn(FFN(h))
    FFN(h) = W_down (silu(W_gate h) * W_up h)
no norm before a sub-layer; a final RMSNorm before the untied head.

Full layer (``layer_types[i] == "full_attention"``), H heads of Dh:
    q = RMSNorm_q(W_q x), k = RMSNorm_k(W_k x) over the WHOLE projection;
    v = W_v x; causal softmax(q k^T Dh^-0.5) v; W_o. No bias.
    ``rope_parameters.rope_theta`` null (as published) is read as NO rotary
    embedding; a number means rotate-half RoPE with that base on q and k
    after their norms.

Linear layer (Gated DeltaNet), H heads, key width dk, value width dv:
    q, k = W_q x, W_k x;  v, z = W_v x, W_z x;  b, a = W_b x, W_a x
        (the tree holds W_q, W_k, W_v as the columns of one ``lin_qkv``)
    (q, k, v) <- silu(causal depthwise conv1d over tokens, width W, no
        bias, zeros before the first token), all channels of q, k, v
    beta = sigmoid(b), DOUBLED where ``linear_allow_neg_eigval`` (the one
        departure from Qwen3-Next's file, which has no factor)
    g = -exp(A_log) softplus(a + dt_bias)
    q <- l2norm(q) dk^-0.5, k <- l2norm(k) (eps 1e-6), per head
    per head, state S in R^{dk x dv}, zero before the first token:
        S <- exp(g_t) S;  u = (v_t - S^T k_t) beta_t;  S <- S + k_t u^T;
        o_t = S^T q_t
    o <- RMSNorm_dv(o) * silu(z) per head (weight of size dv), then W_o.

``wrong`` switches ONE equation to a plausible mistake; the tests use it to
show that their tolerance tells each of them from the right model.
"""

import jax
import jax.numpy as jnp

WRONG = ("beta_not_doubled", "no_decay", "no_k_l2norm", "conv_state_dropped",
         "state_bf16", "pre_norm")
# ``conv_state_dropped``: the convolution restarts from zeros every this
# many tokens, as a chunked prefill that loses its conv state would.
CONV_DROP_EVERY = 16
F32 = jnp.float32


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _sizes(cfg):
    heads = cfg["num_attention_heads"]
    return {
        "heads": heads,
        "kv_heads": cfg.get("num_key_value_heads", heads),
        "head_dim": cfg.get("head_dim") or cfg["hidden_size"] // heads,
        "lh": cfg["linear_num_value_heads"],
        "dk": cfg["linear_key_head_dim"],
        "dv": cfg["linear_value_head_dim"],
        "eps": cfg["rms_norm_eps"],
        "theta": (cfg.get("rope_parameters") or {}).get("rope_theta"),
    }


def layer_params(params, cfg, i):
    """(kind, that layer's parameters in float32) of layer ``i``."""
    kind = cfg["layer_types"][i]
    key = "linear" if kind == "linear_attention" else "full"
    at = sum(1 for t in cfg["layer_types"][:i] if t == kind)
    return kind, jax.tree.map(lambda x: jnp.asarray(x[at], F32),
                              params["layers"][key])


def _rope(x, theta):
    t, _, dh = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=F32) / dh)
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def full_attention(cfg, lp, x):
    s = _sizes(cfg)
    t = x.shape[0]
    q = rms_norm(x @ lp["wq"], lp["q_norm"], s["eps"])
    k = rms_norm(x @ lp["wk"], lp["k_norm"], s["eps"])
    v = x @ lp["wv"]
    q = q.reshape(t, s["heads"], s["head_dim"])
    k = k.reshape(t, s["kv_heads"], s["head_dim"])
    v = v.reshape(t, s["kv_heads"], s["head_dim"])
    if s["theta"] is not None:
        q, k = _rope(q, s["theta"]), _rope(k, s["theta"])
    group = s["heads"] // s["kv_heads"]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("ihd,jhd->hij", q, k) * s["head_dim"] ** -0.5
    causal = jnp.tril(jnp.ones((t, t), bool))
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
    out = jnp.einsum("hij,jhd->ihd", probs, v)
    return out.reshape(t, -1) @ lp["wo"]


def delta_rule(q, k, v, g, beta, keep=F32, state=None):
    """The gated delta rule, a token at a time: q, k [T, H, dk] (prepared),
    v [T, H, dv], g, beta [T, H] -> (o [T, H, dv], the state after the last
    token [H, dk, dv]). ``keep``: the dtype the state is held in between
    tokens (float32; bfloat16 is the ``state_bf16`` mistake)."""
    def token(state, xs):
        q_t, k_t, v_t, g_t, beta_t = xs
        state = state.astype(F32) * jnp.exp(g_t)[:, None, None]
        kv_mem = jnp.sum(state * k_t[:, :, None], axis=1)      # [H, dv]
        u = (v_t - kv_mem) * beta_t[:, None]
        state = state + k_t[:, :, None] * u[:, None, :]
        return state.astype(keep), jnp.sum(
            state.astype(keep).astype(F32) * q_t[:, :, None], axis=1)

    if state is None:
        state = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), keep)
    state, o = jax.lax.scan(token, state.astype(keep), (q, k, v, g, beta))
    return o, state


def gated_delta_net(cfg, lp, x, wrong=()):
    s = _sizes(cfg)
    t = x.shape[0]
    lh, dk, dv = s["lh"], s["dk"], s["dv"]
    qkv = x @ lp["lin_qkv"]          # W_q, W_k, W_v side by side: [T, C]
    z = (x @ lp["lin_z"]).reshape(t, lh, dv)
    b, a = x @ lp["lin_b"], x @ lp["lin_a"]
    w = lp["conv_w"]                                          # [W, C]
    width = w.shape[0]
    padded = jnp.concatenate([jnp.zeros((width - 1, qkv.shape[1]), F32), qkv])
    taps = jnp.stack([padded[i:i + t] for i in range(width)])  # [W, T, C]
    if "conv_state_dropped" in wrong:
        # Token j sees only the tokens of its own block of CONV_DROP_EVERY.
        ago = (width - 1 - jnp.arange(width))[:, None]
        seen = (jnp.arange(t)[None, :] % CONV_DROP_EVERY) >= ago
        taps = jnp.where(seen[..., None], taps, 0.0)
    qkv = jax.nn.silu(jnp.sum(taps * w[:, None, :], axis=0))
    q = qkv[:, :lh * dk].reshape(t, lh, dk)
    k = qkv[:, lh * dk:2 * lh * dk].reshape(t, lh, dk)
    v = qkv[:, 2 * lh * dk:].reshape(t, lh, dv)
    beta = jax.nn.sigmoid(b)
    if cfg.get("linear_allow_neg_eigval") and "beta_not_doubled" not in wrong:
        beta = 2.0 * beta
    g = -jnp.exp(lp["a_log"]) * jax.nn.softplus(a + lp["dt_bias"])
    if "no_decay" in wrong:
        g = jnp.zeros_like(g)

    def l2norm(y):
        return y * jax.lax.rsqrt(jnp.sum(y * y, -1, keepdims=True) + 1e-6)

    q = l2norm(q) * dk ** -0.5
    if "no_k_l2norm" not in wrong:
        k = l2norm(k)
    o, _ = delta_rule(q, k, v, g, beta,
                      jnp.bfloat16 if "state_bf16" in wrong else F32)
    o = rms_norm(o, lp["gate_norm"], s["eps"]) * jax.nn.silu(z)
    return o.reshape(t, lh * dv) @ lp["lin_o"]


def layer(cfg, kind, lp, x, wrong=()):
    """One decoder layer over one sequence: x [T, D] float32 -> [T, D]."""
    with jax.default_matmul_precision("highest"):
        eps = cfg["rms_norm_eps"]

        def mixer(y):
            if kind == "linear_attention":
                return gated_delta_net(cfg, lp, y, wrong)
            return full_attention(cfg, lp, y)

        def ffn(y):
            return (jax.nn.silu(y @ lp["w_gate"]) * (y @ lp["w_up"])) \
                @ lp["w_down"]

        if "pre_norm" in wrong:   # llama's block, with Olmo's two weights
            h = x + mixer(rms_norm(x, lp["attn_norm"], eps))
            return h + ffn(rms_norm(h, lp["mlp_norm"], eps))
        h = x + rms_norm(mixer(x), lp["attn_norm"], eps)
        return h + rms_norm(ffn(h), lp["mlp_norm"], eps)


def embed(params, token_ids):
    return jnp.asarray(params["embed"], F32)[jnp.asarray(token_ids)]


def logits(params, cfg, x):
    with jax.default_matmul_precision("highest"):
        x = rms_norm(x, jnp.asarray(params["final_norm"], F32),
                     cfg["rms_norm_eps"])
        return x @ jnp.asarray(params["lm_head"], F32)


def forward(params, cfg, token_ids, wrong=()):
    """Logits [T, V] of one sequence of token ids, every position."""
    x = embed(params, token_ids)
    for i in range(cfg["num_hidden_layers"]):
        kind, lp = layer_params(params, cfg, i)
        x = layer(cfg, kind, lp, x, wrong)
    return logits(params, cfg, x)
