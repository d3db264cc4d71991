"""Plain reference of the Granite 4.0 hybrid decoder (HF ``granitemoehybrid``
with no routed experts): the whole forward of ONE sequence in ``jax.numpy``,
float32, every product at ``highest`` precision, the state-space recurrence
token by token (no chunks), the full attention matrix, no cache, no
batching, no kernels. It imports nothing of the program it judges and takes
the parameter tree the program's ``init_params`` makes (``layers.mamba`` /
``layers.attention`` stacked by kind) and the HF ``config.json`` as a dict.

The equations (HF's GraniteMoeHybrid decoder layer; its mixer is the Mamba-2
layer of *Transformers are SSMs*, arXiv:2405.21060, as Bamba states it),
``u`` the block's normed input, eps ``rms_norm_eps``:

    h_0 = embedding_multiplier * E[token]
    for every layer:  u = RMSNorm_in(h);    h = h + residual_multiplier * mixer(u)
                      u = RMSNorm_post(h);  [g | v] = u W_ffn_in
                      h = h + residual_multiplier * (silu(g) * v) W_ffn_out
    logits = RMSNorm_f(h) E^T / logits_scaling              (tied head)

Attention mixer (``layer_types[i] == "attention"``), H heads of Dh over Hkv:
    q, k, v = u W_q, u W_k, u W_v; NO position embedding
    (``position_embedding_type: nope``); causal
    softmax(attention_multiplier * q k^T) v over the H / Hkv query heads a
    KV head; W_o. No bias.

Mamba-2 mixer (``"mamba"``), H heads of P channels, state N, ONE group:
    [z | xBC | dt] = u W_in,  widths H*P | H*P + 2N | H
        (the tree holds W_in as two matrices, ``in_zx`` and ``in_dt``)
    xBC_t <- silu(b_c + sum_j w_c[:, j] xBC_{t-W+1+j})   causal, depthwise,
        zeros before the sequence; x, B and C TOGETHER
    [x | B | C] = xBC_t,  widths H*P | N | N: every head shares B_t and C_t
    dt_t = softplus(dt_t + dt_bias);  a_t = exp(-dt_t exp(A_log))
        (``time_step_limit`` (0, inf): no clamp)
    per head, S in R^{P x N}, zero before the first token:
        S <- a_t[h] S + dt_t[h] x_t[h] B_t^T;  y_t[h] = S C_t + D[h] x_t[h]
    y_t <- RMSNorm(y_t * silu(z_t)) over ALL H*P channels (the gate BEFORE
        the norm; one group), then W_out.

Departures from the HF file, each on purpose: none in the equations; the
checkpoint's leaf names and ``in_proj``'s column order z | xBC | dt are
taken from HF's GraniteMoeHybridMambaLayer (deployment.json lists them
under ``assumed``).

``wrong`` switches ONE equation to a plausible mistake; the tests use it to
show that their tolerance tells each of them from the right model.
"""

import jax
import jax.numpy as jnp

WRONG = ("no_decay", "no_d_skip", "gate_after_norm", "no_conv_bias",
         "attn_scale_rsqrt", "embedding_multiplier_1",
         "residual_multiplier_1", "logits_scaling_1", "rope")
# Not other equations but the same ones in too little precision: what a chip
# run must tell from the right model (check_reference.py), a tiny float32
# test cannot.
LOW_PRECISION = ("state_bf16", "gated_norm_bf16")
ROPE_THETA = 10000.0     # the ``rope`` mistake's base
F32 = jnp.float32


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _sizes(cfg):
    heads = cfg["num_attention_heads"]
    return {
        "heads": heads,
        "kv_heads": cfg.get("num_key_value_heads", heads),
        "head_dim": cfg.get("head_dim") or cfg["hidden_size"] // heads,
        "mh": cfg["mamba_n_heads"], "p": cfg["mamba_d_head"],
        "n": cfg["mamba_d_state"], "eps": cfg["rms_norm_eps"],
    }


def _mult(cfg, key, wrong):
    return 1.0 if f"{key}_1" in wrong else float(cfg[key])


def layer_params(params, cfg, i):
    """(kind, that layer's parameters in float32) of layer ``i``."""
    kind = cfg["layer_types"][i]
    at = sum(1 for t in cfg["layer_types"][:i] if t == kind)
    return kind, jax.tree.map(lambda x: jnp.asarray(x[at], F32),
                              params["layers"][kind])


def _rope(x, theta):
    t, _, dh = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=F32) / dh)
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(cfg, lp, u, wrong=()):
    s = _sizes(cfg)
    t = u.shape[0]
    q = (u @ lp["wq"]).reshape(t, s["heads"], s["head_dim"])
    k = (u @ lp["wk"]).reshape(t, s["kv_heads"], s["head_dim"])
    v = (u @ lp["wv"]).reshape(t, s["kv_heads"], s["head_dim"])
    if "rope" in wrong:
        q, k = _rope(q, ROPE_THETA), _rope(k, ROPE_THETA)
    group = s["heads"] // s["kv_heads"]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scale = s["head_dim"] ** -0.5 if "attn_scale_rsqrt" in wrong \
        else cfg["attention_multiplier"]
    scores = jnp.einsum("ihd,jhd->hij", q, k) * scale
    causal = jnp.tril(jnp.ones((t, t), bool))
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
    out = jnp.einsum("hij,jhd->ihd", probs, v)
    return out.reshape(t, -1) @ lp["wo"]


def ssm_scan(x, b, c, dt, a, d_skip, keep=F32, state=None):
    """The state-space recurrence, a token at a time: x [T, H, P], b, c
    [T, N], dt, a [T, H] (dt after softplus, a the decay in (0, 1]), d_skip
    [H] -> (y [T, H, P], the state after the last token [H, P, N]).
    ``keep``: the dtype the state is held in between tokens (float32;
    bfloat16 is the ``state_bf16`` mistake)."""
    def token(state, xs):
        x_t, b_t, c_t, dt_t, a_t = xs
        state = state.astype(F32) * a_t[:, None, None] \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        state = state.astype(keep)
        y = jnp.sum(state.astype(F32) * c_t[None, None, :], axis=-1)
        return state, y + d_skip[:, None] * x_t

    if state is None:
        state = jnp.zeros((x.shape[1], x.shape[2], b.shape[1]), keep)
    state, y = jax.lax.scan(token, state.astype(keep), (x, b, c, dt, a))
    return y, state


def mamba2(cfg, lp, u, wrong=()):
    s = _sizes(cfg)
    t = u.shape[0]
    mh, p, n = s["mh"], s["p"], s["n"]
    inner = mh * p
    # The tree holds W_in's columns z | xBC as ``in_zx`` and dt's as
    # ``in_dt``.
    zx, dt = u @ lp["in_zx"], u @ lp["in_dt"]
    z, xbc = zx[:, :inner], zx[:, inner:]
    w = lp["conv_w"]                                          # [W, C]
    width = w.shape[0]
    padded = jnp.concatenate([jnp.zeros((width - 1, xbc.shape[1]), F32), xbc])
    taps = jnp.stack([padded[i:i + t] for i in range(width)])  # [W, T, C]
    conv = jnp.sum(taps * w[:, None, :], axis=0)
    if cfg.get("mamba_conv_bias", True) and "no_conv_bias" not in wrong:
        conv = conv + lp["conv_b"]
    xbc = jax.nn.silu(conv)
    x = xbc[:, :inner].reshape(t, mh, p)
    b, c = xbc[:, inner:inner + n], xbc[:, inner + n:]
    dt = jax.nn.softplus(dt + lp["dt_bias"])
    a = jnp.exp(-dt * jnp.exp(lp["a_log"]))
    if "no_decay" in wrong:
        a = jnp.ones_like(a)
    d_skip = jnp.zeros_like(lp["d_skip"]) if "no_d_skip" in wrong \
        else lp["d_skip"]
    y, _ = ssm_scan(x, b, c, dt, a, d_skip,
                    jnp.bfloat16 if "state_bf16" in wrong else F32)
    y = y.reshape(t, inner)
    gate = jax.nn.silu(z)
    if "gate_after_norm" in wrong:
        y = rms_norm(y, lp["gate_norm"], s["eps"]) * gate
    elif "gated_norm_bf16" in wrong:
        yb = (y * gate).astype(jnp.bfloat16)
        y = (yb * jax.lax.rsqrt(
            jnp.mean(yb * yb, -1, keepdims=True) + jnp.bfloat16(s["eps"]))
            * lp["gate_norm"].astype(jnp.bfloat16)).astype(F32)
    else:
        y = rms_norm(y * gate, lp["gate_norm"], s["eps"])
    return y @ lp["out_proj"]


def layer(cfg, kind, lp, h, wrong=()):
    """One decoder layer over one sequence: h [T, D] float32 -> [T, D]."""
    with jax.default_matmul_precision("highest"):
        eps = cfg["rms_norm_eps"]
        res = _mult(cfg, "residual_multiplier", wrong)
        u = rms_norm(h, lp["attn_norm"], eps)
        mixed = mamba2(cfg, lp, u, wrong) if kind == "mamba" \
            else attention(cfg, lp, u, wrong)
        h = h + res * mixed
        u = rms_norm(h, lp["mlp_norm"], eps)
        gate, up = jnp.split(u @ lp["w_in"], 2, axis=-1)
        return h + res * ((jax.nn.silu(gate) * up) @ lp["w_out"])


def embed(params, cfg, token_ids, wrong=()):
    return _mult(cfg, "embedding_multiplier", wrong) \
        * jnp.asarray(params["embed"], F32)[jnp.asarray(token_ids)]


def logits(params, cfg, h, wrong=()):
    with jax.default_matmul_precision("highest"):
        h = rms_norm(h, jnp.asarray(params["final_norm"], F32),
                     cfg["rms_norm_eps"])
        return h @ jnp.asarray(params["embed"], F32).T \
            / _mult(cfg, "logits_scaling", wrong)


def forward(params, cfg, token_ids, wrong=()):
    """Logits [T, V] of one sequence of token ids, every position."""
    h = embed(params, cfg, token_ids, wrong)
    for i in range(cfg["num_hidden_layers"]):
        kind, lp = layer_params(params, cfg, i)
        h = layer(cfg, kind, lp, h, wrong)
    return logits(params, cfg, h, wrong)
