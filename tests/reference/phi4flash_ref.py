"""Plain reference of the Phi-4-mini-flash decoder (HF ``phi4flash``; SambaY,
arXiv:2507.06607, with differential attention, arXiv:2410.05258): the whole
forward of ONE sequence in ``jax.numpy``, float32, every product at
``highest`` precision, the selective scan token by token, a masked full score
matrix a head pair, no cache, no ring, no batching, no kernels. It imports
nothing of the program it judges and takes the parameter tree the program's
``init_params`` makes (``layers.{ffn, s6, attn, gmu, cross}`` stacked by
kind) and the HF ``config.json`` as a dict.

The equations, ``L`` layers, ``h`` the sublayer's normed input, LayerNorm
WITH bias (eps ``layer_norm_eps``), no position embedding anywhere:

    x_0 = E[token]
    every layer:  x = x + mixer_l(LN_in(x))
                  [g | u] = LN_post(x) W_1;  x = x + (silu(g) * u) W_2
    logits = LN_f(x) E^T                                     (tied head)

S6 mixer (l even, l <= L/2), D = mamba_expand * hidden, N = mamba_d_state,
K = mamba_d_conv, R = mamba_dt_rank (Mamba-1, arXiv:2312.00752):
    [u | z] = h W_in
    u_t <- silu(b_c + sum_j w_c[j] u_{t-K+1+j})   causal, depthwise, zeros
        before the sequence
    [delta | B | C] = u W_x, widths R | N | N
    dt = softplus(delta W_dt + b_dt);  A = -exp(A_log)  [N, D]
    S in R^{N x D}, zero before the first token:
        S[n, c] <- exp(dt_t[c] A[n, c]) S[n, c] + dt_t[c] u_t[c] B_t[n]
        y_t[c] = sum_n S[n, c] C_t[n] + D_skip[c] u_t[c]
    out = (y * silu(z)) W_out
    Layer L/2's ``y`` (with the skip, BEFORE the z gate) is the memory m_t.

Self attention (l odd, l < L/2: the token and the sliding_window - 1 before
it; l = L/2 + 1: every token before), H query heads over Hkv KV heads of d:
    [q | k | v] = h W_qkv + b_qkv
    DIFFERENTIAL: query pair p is heads (2p, 2p + 1) = (q_1, q_2); KV pair r
    is KV heads (2r, 2r + 1) = (k_1, k_2), (v_1, v_2); pair p reads KV pair
    p // (H / Hkv).
    a_j = softmax(q_j k_j^T / sqrt(d) + mask) [v_1 | v_2]  in R^{2d}, j = 1, 2
    lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init,
        lambda_init = 0.8 - 0.6 exp(-0.3 l)
    o_p = (1 - lambda_init) RMSNorm_{2d}(a_1 - lambda a_2)  (weight, eps as
        the LayerNorms')
    out = [o_0 | o_1 | ...] W_o + b_o

Gated memory unit (l even, l >= L/2 + 2):  out = (m * silu(h W_1g)) W_2g

Cross attention (l odd, l >= L/2 + 3): q = h W_q + b_q only; the same
differential attention, causal and unbounded, over layer L/2 + 1's k and v.

Departures from the published description, each on purpose: none in the
equations. What the published config does not settle (which layer is of
which kind, the pairing, lambda_init by the layer's index, the biases, the
sub-norm, the window's bound, Mamba-1's four sizes) is listed under
``assumed`` in benchmarks/chip/configs/phi-4-mini-flash/deployment.json.

``wrong`` switches ONE equation to a plausible mistake; the tests use it to
show that their tolerance tells each of them from the right model.
"""

import jax
import jax.numpy as jnp

WRONG = ("lambda_init_next_layer", "no_subtraction", "no_subln",
         "no_one_minus_lambda_init", "window_minus_1", "window_plus_1",
         "no_conv_bias", "no_d_skip", "memory_after_gate",
         "memory_of_layer_before", "cross_reads_last_window_layer",
         "pairing_by_halves", "no_qkv_bias", "rms_for_layer_norm")
# Not other equations but the same ones in too little precision: what a chip
# run must tell from the right model (check_reference.py), a tiny float32
# test cannot.
LOW_PRECISION = ("state_bf16", "dt_bf16")
F32 = jnp.float32


def sizes(cfg):
    hidden, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    rank = cfg.get("mamba_dt_rank", "auto")
    return {
        "heads": heads,
        "kv_heads": cfg.get("num_key_value_heads", heads),
        "head_dim": hidden // heads,
        "inner": cfg.get("mamba_expand", 2) * hidden,
        "n": cfg.get("mamba_d_state", 16),
        "rank": -(-hidden // 16) if rank == "auto" else rank,
        "eps": cfg.get("layer_norm_eps", 1e-5),
        "window": cfg["sliding_window"],
        "layers": cfg["num_hidden_layers"],
    }


def layer_kinds(cfg):
    """Per layer, (kind, index in the kind's stack)."""
    half = cfg["num_hidden_layers"] // 2
    out = []
    for i in range(cfg["num_hidden_layers"]):
        if i <= half + 1:
            out.append(("s6" if i % 2 == 0 else "attn", i // 2))
        else:
            out.append(("gmu" if i % 2 == 0 else "cross",
                        (i - half - 2) // 2))
    return out


def layer_params(params, cfg, i):
    """(kind, that layer's mixer and FFN parameters in float32)."""
    kind, at = layer_kinds(cfg)[i]
    lp = {k: jnp.asarray(x[at], F32)
          for k, x in params["layers"][kind].items()}
    lp.update({k: jnp.asarray(x[i], F32)
               for k, x in params["layers"]["ffn"].items()})
    return kind, lp


def layer_norm(x, w, b, eps, wrong=()):
    if "rms_for_layer_norm" in wrong:
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
            * w + b
    xc = x - jnp.mean(x, -1, keepdims=True)
    return xc * jax.lax.rsqrt(jnp.mean(xc * xc, -1, keepdims=True) + eps) \
        * w + b


def selective_scan(u, dt, a, b, c, d_skip, keep=F32):
    """The S6 recurrence, a token at a time: u, dt [T, D], a [N, D], b, c
    [T, N], d_skip [D] -> y [T, D]. ``keep``: the dtype the state is held
    in between tokens (float32; bfloat16 is the ``state_bf16`` mistake)."""
    def token(state, xs):
        u_t, dt_t, b_t, c_t = xs
        state = jnp.exp(dt_t[None, :] * a) * state.astype(F32) \
            + (dt_t * u_t)[None, :] * b_t[:, None]
        state = state.astype(keep)
        y = jnp.sum(state.astype(F32) * c_t[:, None], axis=0)
        return state, y + d_skip * u_t

    state = jnp.zeros(a.shape, keep)
    _, y = jax.lax.scan(token, state, (u, dt, b, c))
    return y


def s6_mixer(cfg, lp, h, wrong=()):
    """(the mixer's output [T, hidden], its scan output y [T, D] with the
    skip and before the gate) of the normed input h [T, hidden]."""
    s = sizes(cfg)
    t, di, n, rank = h.shape[0], s["inner"], s["n"], s["rank"]
    uz = h @ lp["in_proj"]
    u, z = uz[:, :di], uz[:, di:]
    w = lp["conv_w"]                                          # [K, D]
    width = w.shape[0]
    padded = jnp.concatenate([jnp.zeros((width - 1, di), F32), u])
    conv = sum(padded[i:i + t] * w[i][None] for i in range(width))
    if "conv_b" in lp and "no_conv_bias" not in wrong:
        conv = conv + lp["conv_b"]
    u = jax.nn.silu(conv)
    proj = u @ lp["w_x"]
    delta, b, c = (proj[:, :rank], proj[:, rank:rank + n],
                   proj[:, rank + n:])
    dt = delta @ lp["w_dt"] + lp["dt_bias"]
    if "dt_bf16" in wrong:
        dt = (delta.astype(jnp.bfloat16) @ lp["w_dt"].astype(jnp.bfloat16)
              + lp["dt_bias"].astype(jnp.bfloat16))
        dt = jax.nn.softplus(dt).astype(F32)
    else:
        dt = jax.nn.softplus(dt)
    d_skip = jnp.zeros_like(lp["d_skip"]) if "no_d_skip" in wrong \
        else lp["d_skip"]
    y = selective_scan(u, dt, -jnp.exp(lp["a_log"]), b, c, d_skip,
                       jnp.bfloat16 if "state_bf16" in wrong else F32)
    gated = y * jax.nn.silu(z)
    return gated @ lp["wo"], gated if "memory_after_gate" in wrong else y


def lambda_init(layer):
    return 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(layer, F32))


def project_kv(cfg, lp, h, wrong=()):
    """A self-attention layer's keys and values [T, Hkv, d] of h."""
    s = sizes(cfg)
    hq = s["heads"] * s["head_dim"]
    hk = s["kv_heads"] * s["head_dim"]
    kv = h @ lp["wqkv"][:, hq:]
    if "no_qkv_bias" not in wrong:
        kv = kv + lp["bqkv"][hq:]
    t = h.shape[0]
    return (kv[:, :hk].reshape(t, s["kv_heads"], s["head_dim"]),
            kv[:, hk:].reshape(t, s["kv_heads"], s["head_dim"]))


def diff_attention(cfg, lp, h, k, v, layer, window=None, wrong=()):
    """Differential attention of the queries of h [T, hidden] over keys and
    values k, v [T, Hkv, d] (this layer's own or the full layer's), causal,
    bounded by ``window`` where given: the branch's output [T, hidden]."""
    s = sizes(cfg)
    t, heads, dh = h.shape[0], s["heads"], s["head_dim"]
    hq = heads * dh
    q = h @ lp["wqkv"][:, :hq]
    if "no_qkv_bias" not in wrong:
        q = q + lp["bqkv"][:hq]
    q = q.reshape(t, heads, dh)
    group = heads // s["kv_heads"]
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    seen = j <= i
    if window is not None:
        seen = seen & (i - j < window)
    layer = layer + 1 if "lambda_init_next_layer" in wrong else layer
    init = lambda_init(layer)
    lam = jnp.exp(jnp.sum(lp["lambda_q1"] * lp["lambda_k1"])) \
        - jnp.exp(jnp.sum(lp["lambda_q2"] * lp["lambda_k2"])) + init
    out = []
    for p in range(heads // 2):
        r = p // group
        if "pairing_by_halves" in wrong:
            q_pair = (q[:, p], q[:, p + heads // 2])
        else:
            q_pair = (q[:, 2 * p], q[:, 2 * p + 1])
        values = jnp.concatenate([v[:, 2 * r], v[:, 2 * r + 1]], axis=-1)
        a = []
        for q_j, k_j in zip(q_pair, (k[:, 2 * r], k[:, 2 * r + 1])):
            scores = jnp.where(seen, q_j @ k_j.T * dh ** -0.5, -jnp.inf)
            a.append(jax.nn.softmax(scores, axis=-1) @ values)
        o = a[0] if "no_subtraction" in wrong else a[0] - lam * a[1]
        if "no_subln" not in wrong:
            o = o * jax.lax.rsqrt(
                jnp.mean(o * o, -1, keepdims=True) + s["eps"]) * lp["subln"]
        if "no_one_minus_lambda_init" not in wrong:
            o = o * (1.0 - init)
        out.append(o)
    return jnp.concatenate(out, axis=-1) @ lp["wo"] + lp["bo"]


def layer_role(cfg, i, wrong=()):
    """What layer ``i`` is to the layers behind it: ``"tap"`` (the S6
    layer whose scan output is the memory), ``"window"`` / ``"full"`` (a
    self-attention layer's bound), else ``""``."""
    half = cfg["num_hidden_layers"] // 2
    kind = layer_kinds(cfg)[i][0]
    if kind == "s6":
        tap = half - 2 if "memory_of_layer_before" in wrong else half
        return "tap" if i == tap else ""
    if kind == "attn":
        return "window" if i < half else "full"
    return ""


def layer(cfg, kind, role, lp, x, carry, index, wrong=()):
    """One decoder layer over one sequence: x [T, hidden] float32 -> (x,
    carry). ``role``: ``layer_role``; ``index``: the layer's index in the
    model, a number (lambda_init reads it; it may be traced, so that one
    compiled layer serves every layer of its kind and role). ``carry``
    holds what later layers read of earlier ones: ``memory`` (the tap's
    scan output), ``shared`` (the full layer's keys and values) and
    ``window_kv`` (the last window layer's, which only a wrong model
    reads)."""
    with jax.default_matmul_precision("highest"):
        s = sizes(cfg)
        carry = dict(carry)
        h = layer_norm(x, lp["attn_norm"], lp["attn_norm_b"], s["eps"],
                       wrong)
        if kind == "s6":
            mixed, y = s6_mixer(cfg, lp, h, wrong)
            if role == "tap":
                carry["memory"] = y
        elif kind == "attn":
            window = s["window"] + ("window_plus_1" in wrong) \
                - ("window_minus_1" in wrong)
            k, v = project_kv(cfg, lp, h, wrong)
            mixed = diff_attention(cfg, lp, h, k, v, index,
                                   window if role == "window" else None,
                                   wrong)
            carry["shared" if role == "full" else "window_kv"] = (k, v)
        elif kind == "gmu":
            mixed = (carry["memory"] * jax.nn.silu(h @ lp["in_proj"])) \
                @ lp["wo"]
        else:
            k, v = carry["window_kv"] \
                if "cross_reads_last_window_layer" in wrong \
                else carry["shared"]
            mixed = diff_attention(cfg, lp, h, k, v, index, None, wrong)
        x = x + mixed
        h = layer_norm(x, lp["mlp_norm"], lp["mlp_norm_b"], s["eps"], wrong)
        gate, up = jnp.split(h @ lp["w_in"], 2, axis=-1)
        return x + (jax.nn.silu(gate) * up) @ lp["w_out"], carry


def embed(params, token_ids):
    return jnp.asarray(params["embed"], F32)[jnp.asarray(token_ids)]


def logits(params, cfg, x, wrong=()):
    with jax.default_matmul_precision("highest"):
        x = layer_norm(x, jnp.asarray(params["final_norm"], F32),
                       jnp.asarray(params["final_norm_b"], F32),
                       sizes(cfg)["eps"], wrong)
        return x @ jnp.asarray(params["embed"], F32).T


def forward(params, cfg, token_ids, wrong=()):
    """Logits [T, V] of one sequence of token ids, every position."""
    x, carry = embed(params, token_ids), {}
    for i in range(cfg["num_hidden_layers"]):
        kind, lp = layer_params(params, cfg, i)
        x, carry = layer(cfg, kind, layer_role(cfg, i, wrong), lp, x, carry,
                         i, wrong)
    return logits(params, cfg, x, wrong)
