"""Granite 4.0 hybrid decoder (HF ``granitemoehybrid`` with no routed
experts): Mamba-2 state-space layers with one position-free GQA layer
somewhere in every period, Granite's four multipliers — functional JAX.

The same shape of module as models/olmo_hybrid.py (the declarations under
"What the rest of the tree asks of this module", attention through
``attend`` over whatever ``KVView`` the runner built, parameters stacked BY
KIND, weights closed over and sliced where used, a second kind of cache
declared as ``StateSpec``s that the runner owns), with these of its own:

  * The state-space mixer (ops/ssd.py): ``[z | xBC | dt] = u W_in``; x, B
    and C pass TOGETHER the causal depthwise convolution the Gated DeltaNet
    layer also uses (ops/gated_delta.py:conv_step / conv_chunk), here with a
    bias; the scan over a float32 state [H, P, N] a layer a row; the gate
    BEFORE the RMS norm over all H * P channels; ``W_out``.
  * A period whose full layer does not close it (``cfg.layer_types``: 5 x
    mamba, attention, 4 x mamba as published). The forward scans the
    SEGMENTS between attention layers: a segment's state-space layers run
    in a loop of dynamic bounds, then the attention layer under a
    ``lax.cond`` (the last segment has none). A program so holds ONE
    state-space layer's code and ONE attention layer's whatever the depth
    and wherever the attention layer stands.
  * The block: pre-norm; ``h_0 = embedding_multiplier E[token]``; every
    sublayer's output times ``residual_multiplier`` before it joins the
    residual; attention scores times ``attention_multiplier`` (NOT head_dim
    ** -0.5) and no position embedding at all; KV heads narrower than the
    128 lanes PAIRED into one paged row (``kv_pack``); the gated FFN's two
    in-projections as ONE matrix (``shared_mlp.input_linear``); logits over
    ``logits_scaling``, the head tied to the table.

tests/reference/granite_hybrid_ref.py is the plain statement of the same
equations this module is held to.

Device scopes: the two in-projections and two out-projections under
``attn_proj``, attention, the convolution, the scan and its gated norm
under ``attn_core`` (the scan with an inner ``ssd_step`` / ``ssd_chunk``),
``ffn``, ``embed``, ``logits``.
"""

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from production_stack_tpu.models import llama
from production_stack_tpu.models.config import (
    PERIOD_RULES,
    CacheSpecs,
    ModelConfig,
    PagedKVSpec,
    StateSpec,
    layer_period,
)
from production_stack_tpu.models.llama import rms_norm
from production_stack_tpu.ops import gated_delta as gd
from production_stack_tpu.ops import ssd
from production_stack_tpu.ops.attention import KVView, attend

Params = Dict

# --- What the rest of the tree asks of this module (see models/llama.py) ----
# HF checkpoint suffix -> (our leaf, transpose?): the names of HF's
# GraniteMoeHybridDecoderLayer. The two kinds share the FFN and norm names;
# models/weights.py files a layer's tensors under its kind (``layer_slots``).
HF_LAYER_MAP = {
    "self_attn.q_proj.weight": ("wq", True),
    "self_attn.k_proj.weight": ("wk", True),
    "self_attn.v_proj.weight": ("wv", True),
    "self_attn.o_proj.weight": ("wo", True),
    "mamba.in_proj.weight": ("in_proj", True),      # z | xBC | dt: split
    "mamba.conv1d.weight": ("conv_w", True),        # [C, 1, W] -> [W, 1, C]
    "mamba.conv1d.bias": ("conv_b", False),
    "mamba.A_log": ("a_log", False),
    "mamba.D": ("d_skip", False),
    "mamba.dt_bias": ("dt_bias", False),
    "mamba.norm.weight": ("gate_norm", False),
    "mamba.out_proj.weight": ("out_proj", True),
    "shared_mlp.input_linear.weight": ("w_in", True),     # gate | up
    "shared_mlp.output_linear.weight": ("w_out", True),
    "input_layernorm.weight": ("attn_norm", False),
    "post_attention_layernorm.weight": ("mlp_norm", False),
}
HF_TOP_MAP = {
    "model.embed_tokens.weight": ("embed", False),
    "model.norm.weight": ("final_norm", False),
    "lm_head.weight": ("lm_head", True),
}
# No LoRA on this family yet: a state-space layer's projections have no
# delta path (the engine refuses --lora-modules on an empty tuple).
LORA_TARGETS = ()
# ``attn_impl=auto`` may resolve to the Pallas paged decode for the
# attention layers (64-lane KV heads paired into rows of 128: ``kv_pack``):
# tests/test_granite_hybrid.py holds the engine's logits on that path to the
# reference.
PAGED_DECODE_VALIDATED = True
# Leaves the scan computes with in float32 whatever the activations are.
FLOAT32_LEAVES = ("a_log", "d_skip", "dt_bias")

_FFN = ("w_in", "w_out", "attn_norm", "mlp_norm")
_ATTENTION = ("wq", "wk", "wv", "wo") + _FFN
_MAMBA = ("in_proj", "conv_w", "conv_b", "a_log", "d_skip", "dt_bias",
          "gate_norm", "out_proj") + _FFN                       # as loaded
_RULES = PERIOD_RULES["granite_hybrid"]
_KINDS = _RULES["kinds"]                     # ("mamba", "attention")


def position_bound(cfg: ModelConfig) -> Optional[int]:
    """None: no position embedding at all."""
    return None


def layer_slots(cfg: ModelConfig):
    """(kind, index within the kind's stack) of every layer, in order."""
    seen = dict.fromkeys(_KINDS, 0)
    out = []
    for t in cfg.layer_types:
        out.append((t, seen[t]))
        seen[t] += 1
    return out


def required_layer_leaves(cfg: ModelConfig) -> dict:
    """Per kind, the leaves every valid checkpoint must provide."""
    mamba = set(_MAMBA) - (set() if cfg.mamba_conv_bias else {"conv_b"})
    return {"mamba": mamba, "attention": set(_ATTENTION)}


def finish_params(cfg: ModelConfig, params: Params) -> Params:
    """Last step of a checkpoint load: the conv weight loses HF's middle
    axis ([W, 1, C] -> [W, C]), ``in_proj`` becomes the two matrices the
    forward multiplies by (``in_zx``: z | xBC, whole lanes wide; ``in_dt``),
    and a tied head reads ``embed``."""
    mamba = params["layers"]["mamba"]
    if mamba["conv_w"].ndim == 4:
        mamba["conv_w"] = mamba["conv_w"][:, :, 0]
    if "in_proj" in mamba:
        whole = mamba.pop("in_proj")
        mamba["in_zx"] = whole[..., :-cfg.mamba_n_heads]
        mamba["in_dt"] = whole[..., -cfg.mamba_n_heads:]
    return llama.finish_params(cfg, params)


def _counts(cfg: ModelConfig) -> Tuple[int, int]:
    n_mamba = sum(t == "mamba" for t in cfg.layer_types)
    return n_mamba, cfg.num_layers - n_mamba


def _inner(cfg: ModelConfig) -> int:
    return cfg.mamba_n_heads * cfg.mamba_d_head


def _conv_channels(cfg: ModelConfig) -> int:
    """x, B and C together (one group)."""
    return _inner(cfg) + 2 * cfg.mamba_d_state


def kv_pack(cfg: ModelConfig) -> int:
    """KV heads that share a paged row: as many as make whole 128 lanes
    where the head is narrower and the KV heads divide; else 1. The paged
    pool, both Pallas kernels and the gathered window then see KV heads of
    128 lanes (``_attention_layer`` pairs them): a pool whose minor axis is
    64 the compiler for a v5e keeps slots-minor and copies whole into every
    dispatch, and the decode kernel's two-tokens-a-row view of it is a
    reshape of the whole pool a layer a step
    (tests/test_chip_compile_recurrent.py)."""
    dh = cfg.head_dim_
    pack = 128 // dh if dh < 128 and 128 % dh == 0 else 1
    return pack if cfg.num_kv_heads % pack == 0 else 1


def cache_specs(cfg: ModelConfig) -> CacheSpecs:
    """Paged K/V for the attention layers only (``kv_pack`` KV heads a
    row); per sequence and
    state-space layer the scan's state (float32 whatever the activations;
    N on the minor axis: whole lanes at the published 128) and the conv
    state, its W - 1 tokens of C channels as rows of 128 lanes where they
    divide (models/olmo_hybrid.py:cache_specs says why)."""
    n_mamba, n_attn = _counts(cfg)
    conv = (cfg.mamba_d_conv - 1) * _conv_channels(cfg)
    return CacheSpecs(
        PagedKVSpec(n_attn, cfg.num_kv_heads // kv_pack(cfg),
                    cfg.head_dim_ * kv_pack(cfg)),
        (
            StateSpec("ssm", n_mamba,
                      (cfg.mamba_n_heads, cfg.mamba_d_head,
                       cfg.mamba_d_state), "float32"),
            StateSpec("conv", n_mamba,
                      (conv // 128, 128) if conv % 128 == 0 else (conv,),
                      None),
        ),
    )


def init_params(cfg: ModelConfig, rng: jax.Array, dtype=jnp.bfloat16) -> Params:
    d, f, dh = cfg.hidden_size, cfg.intermediate_size, cfg.head_dim_
    h, hkv, v = cfg.num_heads, cfg.num_kv_heads, cfg.vocab_size
    mh, inner, ch = cfg.mamba_n_heads, _inner(cfg), _conv_channels(cfg)
    n_mamba, n_attn = _counts(cfg)
    keys = iter(jax.random.split(rng, 24))

    def w(shape, fan_in, scale=1.0):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * scale * fan_in ** -0.5).astype(dtype)

    def ffn(n):
        return {
            "w_in": w((n, d, 2 * f), d), "w_out": w((n, f, d), f),
            "attn_norm": jnp.ones((n, d), dtype),
            "mlp_norm": jnp.ones((n, d), dtype),
        }

    mamba = {
        # in_proj as TWO matrices: z | xBC is 8448 columns at the published
        # widths, whole lanes; with dt's 64 beside them (8512) the compiler
        # for a v5e kept the stack in a layout of its own and copied all of
        # it (1.25 GB) into the product's every dispatch.
        "in_zx": w((n_mamba, d, inner + ch), d),
        "in_dt": w((n_mamba, d, mh), d),
        "conv_w": w((n_mamba, cfg.mamba_d_conv, ch), cfg.mamba_d_conv),
        "conv_b": w((n_mamba, ch), 1.0, 0.5),
        # As Mamba-2 initialises: A = U(1, 16), dt = U(1e-3, 1e-1) through
        # the inverse of softplus; the decay per token then spreads over
        # (0, 1). D = U(0.5, 1.5): a comparison that drops the skip fails.
        "a_log": jnp.log(jax.random.uniform(
            next(keys), (n_mamba, mh), jnp.float32, 1.0, 16.0)),
        "d_skip": jax.random.uniform(
            next(keys), (n_mamba, mh), jnp.float32, 0.5, 1.5),
        "dt_bias": ssd.softplus_inverse(jax.random.uniform(
            next(keys), (n_mamba, mh), jnp.float32, 1e-3, 1e-1)),
        "gate_norm": jnp.ones((n_mamba, inner), dtype),
        "out_proj": w((n_mamba, inner, d), inner),
        **ffn(n_mamba),
    }
    # Queries and keys sized so that the scores' spread is about 1 under
    # the model's OWN multiplier (at fan-in scale it is attention_multiplier
    # * head_dim ** 0.5 = 1/8 as published: every softmax all but uniform,
    # and a comparison that swaps the multiplier or adds a rotary embedding
    # could not fail its tolerance).
    sharp = (cfg.attention_multiplier * dh ** 0.5) ** -0.5
    attention = {
        "wq": w((n_attn, d, h * dh), d, sharp),
        "wk": w((n_attn, d, hkv * dh), d, sharp),
        "wv": w((n_attn, d, hkv * dh), d),
        "wo": w((n_attn, h * dh, d), h * dh),
        **ffn(n_attn),
    }
    if not cfg.mamba_conv_bias:
        del mamba["conv_b"]
    params = {
        # Rows of unit size over embedding_multiplier: what enters the first
        # norm is then of unit size a channel, as a trained table's is.
        "embed": w((v, d), 1.0, 1.0 / cfg.embedding_multiplier),
        "layers": {"mamba": mamba, "attention": attention},
        "final_norm": jnp.ones((d,), dtype),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = w((d, v), d)
    return params


def _ffn_block(cfg: ModelConfig, hidden: jax.Array, lp: Dict) -> jax.Array:
    with jax.named_scope("ffn"):
        x = rms_norm(hidden, lp["mlp_norm"], cfg.rms_norm_eps)
        gate, up = jnp.split(x @ lp["w_in"], 2, axis=-1)
        mlp = (jax.nn.silu(gate) * up) @ lp["w_out"]
        return hidden + mlp * cfg.residual_multiplier


def _attention_layer(cfg, positions, chunk_lens, hidden, lp, view, layer):
    b, t, _ = hidden.shape
    h, hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    with jax.named_scope("attn_proj"):
        x = rms_norm(hidden, lp["attn_norm"], cfg.rms_norm_eps)
        q = (x @ lp["wq"]).reshape(b, t, h, dh)
        k = (x @ lp["wk"]).reshape(b, t, hkv, dh)
        v = (x @ lp["wv"]).reshape(b, t, hkv, dh)
        # ``pack`` KV heads side by side in a row of whole lanes; a query
        # head is zero outside its own KV head's lanes, so its scores are
        # its own head's and its output's other lanes are dropped below.
        pack = kv_pack(cfg)
        own = jnp.eye(pack, dtype=q.dtype)
        q = q.reshape(b, t, hkv // pack, pack, h // hkv, dh)
        q = (q[..., None, :] * own[:, None, :, None]).reshape(
            b, t, h, pack * dh)
        k = k.reshape(b, t, hkv // pack, pack * dh)
        v = v.reshape(b, t, hkv // pack, pack * dh)
    with jax.named_scope("attn_core"):
        attn = attend(q, k, v, positions, chunk_lens, view, layer,
                      scale=cfg.attention_multiplier)
        attn = attn.reshape(b, t, hkv // pack, pack, h // hkv, pack, dh)
        attn = jnp.sum(attn * own[:, None, :, None], axis=-2)
    with jax.named_scope("attn_proj"):
        hidden = hidden + (attn.reshape(b, t, h * dh) @ lp["wo"]) \
            * cfg.residual_multiplier
    # New KV in pool layout [Hkv, B, T, Dh], as llama's layer returns it.
    return (_ffn_block(cfg, hidden, lp), k.transpose(2, 0, 1, 3),
            v.transpose(2, 0, 1, 3))


def _mamba_layer(cfg, chunk_lens, hidden, lp, ssm, conv, at, interpret):
    """One state-space layer over [B, T] tokens from (ssm: the scan's
    state [B, H, P, N] f32, conv [B, *its spec's shape]); returns (hidden,
    ssm, conv) after each row's ``chunk_lens`` valid tokens. A decode step
    (T == 1) takes and returns as ``ssm`` the rows' WHOLE carried state [B,
    n_mamba, H, P, N], of which layer ``at`` is stepped where it lies
    (ops/ssd.py:ssd_step_at)."""
    b, t, _ = hidden.shape
    conv_shape = conv.shape
    conv = conv.reshape(b, cfg.mamba_d_conv - 1, -1)
    mh, p, n, inner = (cfg.mamba_n_heads, cfg.mamba_d_head,
                       cfg.mamba_d_state, _inner(cfg))
    decode = t == 1
    with jax.named_scope("attn_proj"):
        x = rms_norm(hidden, lp["attn_norm"], cfg.rms_norm_eps)
        zx = x @ lp["in_zx"]                              # [B, T, 2I+2N]
        z, xbc = zx[..., :inner], zx[..., inner:]
        dt, da = ssd.gates(x @ lp["in_dt"], lp["a_log"],
                           lp["dt_bias"])                 # [B, T, H] f32
    with jax.named_scope("attn_core"):
        bias = lp.get("conv_b")
        if decode:
            live = chunk_lens > 0
            xbc, conv = gd.conv_step(xbc[:, 0], conv, lp["conv_w"], live,
                                     bias)
            xbc = xbc[:, None]
        else:
            xbc, conv = gd.conv_chunk(xbc, conv, lp["conv_w"], chunk_lens,
                                      bias)
        xbc = xbc.astype(jnp.float32)
        xs = xbc[..., :inner].reshape(b, t, mh, p)
        bm, cm = xbc[..., inner:inner + n], xbc[..., inner + n:]
        if decode:
            y, ssm = ssd.ssd_step_at(ssm, at, xs[:, 0], bm[:, 0], cm[:, 0],
                                     dt[:, 0], da[:, 0], lp["d_skip"], live,
                                     interpret=interpret)
            y = y[:, None]
        else:
            y, ssm = ssd.ssd_chunk(ssm, xs, bm, cm, dt, da, lp["d_skip"],
                                   chunk_lens)
        # Float32 until the out-projection's operand.
        y = ssd.gated_norm(y.reshape(b, t, inner), z, lp["gate_norm"],
                           cfg.rms_norm_eps).astype(hidden.dtype)
    with jax.named_scope("attn_proj"):
        hidden = hidden + (y @ lp["out_proj"]) * cfg.residual_multiplier
    return _ffn_block(cfg, hidden, lp), ssm, conv.reshape(conv_shape)


def segments(cfg: ModelConfig) -> Tuple[int, int, int]:
    """(state-space layers before a period's attention layer, state-space
    layers a period, periods) of ``cfg.layer_types``."""
    period = layer_period(cfg.layer_types, cfg.num_layers, **_RULES)
    return (period.index(_KINDS[1]), len(period) - 1,
            cfg.num_layers // len(period))


def forward(
    params: Params,
    cfg: ModelConfig,
    token_ids: jax.Array,     # [B, T]
    positions: jax.Array,     # [B, T]
    chunk_lens: jax.Array,    # [B] valid tokens per row (0: the row is inert)
    view: KVView = KVView(),  # the K/V of the ATTENTION layers
    *,
    state: Optional[Tuple[jax.Array, jax.Array]] = None,
    act_sharding=None,        # sequence parallelism: refused for this family
    lora=None,                # LORA_TARGETS is empty
) -> Tuple[jax.Array, jax.Array, jax.Array, Tuple[jax.Array, jax.Array]]:
    """Returns (hidden [B,T,D], k_new [n_attn,Hkv,B,T,Dh], v_new, state).

    ``state``: the rows' (ssm [B, n_mamba, H, P, N] f32, conv [B, n_mamba,
    *its spec's shape]) before the first token, one array per spec of
    ``cache_specs``, rows first as the runner's pools are; ``None`` starts
    every row from zeros (a whole sequence in one call). The returned state
    is that after each row's last valid token. The view's layer axis counts
    the attention layers only.
    """
    before, per, n_periods = segments(cfg)
    n_mamba = per * n_periods
    # Segment s: state-space layers [max(0, before + (s - 1) per), min(before
    # + s per, n_mamba)), then attention layer s where s < n_periods.
    n_seg = n_periods + (before < per)
    b, t = token_ids.shape
    with jax.named_scope("embed"):
        hidden = params["embed"][token_ids]
        hidden = hidden.astype(view.act_dtype(params["embed"].dtype))
        hidden = hidden * jnp.asarray(cfg.embedding_multiplier, hidden.dtype)
    if state is None:
        state = tuple(
            jnp.zeros((b, s.layers, *s.shape), s.dtype or hidden.dtype)
            for s in cache_specs(cfg).state)
    layers = params["layers"]

    def layer_of(stack, at):
        # One layer of a stack, sliced where it is used (olmo_hybrid.py).
        return jax.tree.map(
            lambda x: jax.lax.dynamic_index_in_dim(x, at, 0, False), stack)

    # The scan's own scope: a prefill chunk's layer state is taken out of
    # the rows' carried state and put back under it; a decode step hands
    # the carry itself to ``ssd_step_at``, which steps its layer ``at``
    # where it lies (models/olmo_hybrid.py:forward says the same of its).
    decode = t == 1
    inner = "ssd_step" if decode else "ssd_chunk"

    def mamba_step(at, carry):
        hidden, ssm_all, conv_all = carry
        with jax.named_scope("attn_core"), jax.named_scope(inner):
            ssm = ssm_all if decode else \
                jax.lax.dynamic_index_in_dim(ssm_all, at, 1, False)
            conv = jax.lax.dynamic_index_in_dim(conv_all, at, 1, False)
        hidden, ssm, conv = _mamba_layer(
            cfg, chunk_lens, hidden, layer_of(layers["mamba"], at),
            ssm, conv, at, view.interpret)
        with jax.named_scope("attn_core"), jax.named_scope(inner):
            ssm_all = ssm if decode else \
                jax.lax.dynamic_update_index_in_dim(ssm_all, ssm, at, 1)
            conv_all = jax.lax.dynamic_update_index_in_dim(
                conv_all, conv.astype(conv_all.dtype), at, 1)
        return hidden, ssm_all, conv_all

    hkv, dh = cache_specs(cfg).paged_kv[1:]

    def of_layer(x, p):
        return None if x is None else \
            jax.lax.dynamic_index_in_dim(x, p, 0, False)

    def attention(hidden, p):
        return _attention_layer(
            cfg, positions, chunk_lens, hidden,
            layer_of(layers["attention"], p),
            view._replace(win_k=of_layer(view.win_k, p),
                          win_v=of_layer(view.win_v, p),
                          ring_k=of_layer(view.ring_k, p),
                          ring_v=of_layer(view.ring_v, p)),
            p if view.pool_k is not None else None)

    def no_attention(hidden, p):
        kv = jnp.zeros((hkv, b, t, dh), hidden.dtype)
        return hidden, kv, kv

    def step(carry, s):
        lo = jnp.maximum(0, before + (s - 1) * per)
        hi = jnp.minimum(before + s * per, n_mamba)
        hidden, ssm_all, conv_all = jax.lax.fori_loop(
            lo, hi, mamba_step, carry)
        if n_seg == n_periods:
            hidden, k_l, v_l = attention(hidden, s)
        else:
            hidden, k_l, v_l = jax.lax.cond(
                s < n_periods, attention, no_attention, hidden,
                jnp.minimum(s, n_periods - 1))
        return (hidden, ssm_all, conv_all), (k_l, v_l)

    (hidden, ssm_all, conv_all), (k_new, v_new) = jax.lax.scan(
        step, (hidden, *state), jnp.arange(n_seg, dtype=jnp.int32))
    hidden = rms_norm(hidden, params["final_norm"], cfg.rms_norm_eps)
    return hidden, k_new[:n_periods], v_new[:n_periods], (ssm_all, conv_all)


def compute_logits(params: Params, cfg: ModelConfig, hidden: jax.Array) -> jax.Array:
    """hidden [..., D] -> logits [..., V] in float32, over logits_scaling."""
    logits = llama.compute_logits(params, cfg, hidden)
    with jax.named_scope("logits"):
        return logits / cfg.logits_scaling
