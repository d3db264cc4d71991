"""Model architecture config.

One config dataclass covers the supported decoder-only families:
  * ``llama`` — Llama/Llama-2/Llama-3, Mistral, Qwen2 (RMSNorm + RoPE + SwiGLU,
    optional GQA, optional attention bias for Qwen2).
  * ``opt``   — OPT-style (LayerNorm + learned positions + GELU MLP), used for
    the tiny parity configs (facebook/opt-125m in the reference's
    values-01-minimal-example, see BASELINE.json).

The reference stack never defines models in-repo (it launches external vLLM
images, reference helm/templates/deployment-vllm-multi.yaml:58-134); here the
model tier is in-repo and TPU-native.
"""

import dataclasses
import json
import os
from dataclasses import dataclass, field
from typing import NamedTuple, Optional, Tuple


class PagedKVSpec(NamedTuple):
    """Layers that keep paged keys and values, and a token's shape there."""
    layers: int
    kv_heads: int
    head_dim: int


class StateSpec(NamedTuple):
    """State a sequence holds whole, whatever its length: ``shape`` per
    sequence and layer, for ``layers`` layers. ``dtype`` None: the
    activations'. ``lanes``: the lanes a row is STORED in where that is
    more than ``shape``'s last axis (whole 128-lane tiles, zeros past the
    row's own: what the row takes in HBM either way, declared because a
    kernel can slice only an array of whole tiles where it lies); None: as
    many as the row has."""
    name: str
    layers: int
    shape: Tuple[int, ...]
    dtype: Optional[str]
    lanes: Optional[int] = None

    @property
    def stored(self) -> Tuple[int, ...]:
        """The shape the runner allocates a sequence and layer."""
        return (*self.shape[:-1], self.lanes or self.shape[-1])


class LatentKVSpec(NamedTuple):
    """A token's paged row is ONE latent row, not keys and values of heads:
    the compressed KV (``rank`` values, after its norm: the keys' first part
    and the values whole), then the rotary key every head shares
    (``rope_dim``, after rope), then zeros up to whole 128-lane tiles. There
    is no second pool: ``paged_kv`` then describes that one pool
    (``kv_heads`` 1, ``head_dim`` the padded row). ``index_dim`` > 0: a
    token ALSO caches the key of a learned sparse-attention indexer
    (models/dots3_note.py), ``index_dim`` lanes (whole tiles), in the SECOND
    pool, which is otherwise empty: the same block table, the same write,
    and a block of that pool is whole tiles, so an index scan reads its
    keys and nothing of the latent rows (a slice of the latent row's last
    tile by block cannot be gathered where it lies: XLA lays the whole pool
    out again for it, 15 GB at a deployment's size; PERF.md section 6,
    PR 58)."""
    rank: int
    rope_dim: int
    index_dim: int = 0

    @property
    def width(self) -> int:
        return -(-(self.rank + self.rope_dim) // 128) * 128


class YarnScaling(NamedTuple):
    """``rope_scaling`` of ``type: yarn`` as a DeepSeek-V3 config states it
    (models/deepseek_v3.py:_rope_inv_freq turns it into frequencies and the
    softmax's extra scale). ``mscale`` / ``mscale_all_dim`` 0: not given."""
    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 0.0
    mscale_all_dim: float = 0.0


class CacheSpecs(NamedTuple):
    """What a model module's ``cache_specs(cfg)`` declares it caches per
    sequence, by layer kind: the runner sizes and owns the pools from this
    and nothing else (engine/runner.py), and a non-empty ``state`` is what
    the block manager hands out slots for and what the engine refuses
    features by that cannot follow it. ``latent`` set: the paged rows are
    latent rows (one pool a layer), and the engine refuses what cannot
    follow those."""
    paged_kv: PagedKVSpec
    state: Tuple[StateSpec, ...] = ()
    latent: Optional[LatentKVSpec] = None

    @property
    def kv_pools(self) -> int:
        """Pools a layer keeps a token's row in: keys and values, or the
        one latent row."""
        return 1 if self.latent is not None else 2

    @property
    def second_pool_dim(self) -> int:
        """Lanes of a token's row in the second pool: its values, nothing
        beside a latent row, or that row's index key."""
        return self.paged_kv.head_dim if self.latent is None \
            else self.latent.index_dim


@dataclass(frozen=True)
class ModelConfig:
    # "llama" | "opt" | "olmo_hybrid" | "deepseek_v3" | "granite_hybrid" |
    # "lfm2_moe" | "afmoe" | "mimo_v2" | "phi4flash" | "dots3_note"
    arch: str = "llama"
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 32
    head_dim: Optional[int] = None
    max_position_embeddings: int = 4096
    # None: no rotary embedding (models/olmo_hybrid.py reads it so).
    rope_theta: Optional[float] = 10000.0
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = False
    attention_bias: bool = False  # Qwen2-style qkv bias
    dtype: str = "bfloat16"
    name: str = "model"
    # Layer kinds in order ("linear_attention" | "full_attention"; a
    # granite_hybrid's "mamba" | "attention"), a whole number of equal
    # periods (an lfm2_moe's "conv" | "full_attention" in ANY order:
    # ``FREE_LAYER_LISTS``); empty: every layer is full attention. The
    # linear_* sizes are those of the linear-attention layers' recurrence.
    layer_types: Tuple[str, ...] = ()
    linear_num_heads: int = 0
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = False
    # Multi-head latent attention (models/deepseek_v3.py): a token caches
    # kv_lora_rank + qk_rope_head_dim values; a head's query and key are
    # qk_nope_head_dim + qk_rope_head_dim wide, its value v_head_dim.
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # Sparse experts: layers from first_k_dense_replace on route each token
    # to num_experts_per_tok of n_routed_experts (width
    # moe_intermediate_size each) beside n_shared_experts always-on ones.
    n_routed_experts: int = 0
    num_experts_per_tok: int = 0
    n_shared_experts: int = 0
    moe_intermediate_size: int = 0
    first_k_dense_replace: int = 0
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    # Latent attention's query through a low-rank pair (0: one full matrix)
    # and YaRN's scaled rope (None: plain rope).
    q_lora_rank: int = 0
    rope_scaling: Optional[YarnScaling] = None
    # Manifold-constrained hyper-connections (ops/hyper_connections.py): the
    # residual is hc_mult streams (1: the plain residual), a sublayer's
    # residual mix made doubly stochastic by hc_sinkhorn_iters iterations
    # whose sums take hc_eps, its logits clamped to hc_res_clamp first.
    hc_mult: int = 1
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp: Tuple[float, float] = (-30.0, 30.0)
    # Published next-token-prediction layers: read, NOT served (their
    # tensors are not loaded; the next-token logits do not depend on them).
    num_nextn_predict_layers: int = 0
    # Mamba-2 state-space layers (models/granite_hybrid.py, ops/ssd.py):
    # mamba_n_heads heads of mamba_d_head channels over a state of
    # mamba_d_state, ONE group (every head shares B_t and C_t), a causal
    # depthwise convolution of mamba_d_conv taps over x, B and C together.
    # mamba_chunk_size is the published schedule of the chunkwise scan: read,
    # the implementation's chunk is ops/ssd.py's own (the same numbers).
    mamba_n_heads: int = 0
    mamba_d_head: int = 0
    mamba_d_state: int = 0
    mamba_d_conv: int = 4
    mamba_conv_bias: bool = True
    mamba_chunk_size: int = 256
    # Granite's four multipliers: the embedding's rows, the attention
    # scores (in place of head_dim ** -0.5), every sublayer's output before
    # it joins the residual, and the divisor of the logits.
    embedding_multiplier: float = 1.0
    attention_multiplier: Optional[float] = None
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    # Gated short convolutions (models/lfm2_moe.py): conv_l_cache taps over
    # hidden_size channels, so a sequence's state a layer is conv_l_cache -
    # 1 tokens of them. use_expert_bias false: a checkpoint carries no bias
    # of the router's choice (served as a zero bias).
    conv_l_cache: int = 3
    use_expert_bias: bool = True
    # A "sliding_attention" layer's span (models/afmoe.py): a query sees
    # itself and the sliding_window - 1 keys before it. 0: no such layer.
    sliding_window: int = 0
    # models/mimo_v2.py. A "sliding_attention" layer has swa_num_kv_heads KV
    # heads (a full layer num_kv_heads) and rotates by swa_rope_theta; every
    # layer rotates the first rotary_dim lanes of a head (0: all of them),
    # scales its values by attention_value_scale and, where
    # swa_attention_sink, a sliding layer's softmax takes one learned logit
    # a query head into its denominator. Expert parallelism: this chip
    # holds n_routed_experts experts of every sparse layer, those of rank
    # ep_rank among ep_size (the router's width is n_routed_experts *
    # ep_size; 1: all of them are here).
    swa_num_kv_heads: int = 0
    swa_rope_theta: float = 10000.0
    rotary_dim: int = 0
    attention_value_scale: float = 1.0
    swa_attention_sink: bool = False
    ep_size: int = 1
    ep_rank: int = 0
    # models/phi4flash.py. Mamba-1's selective scan (ops/selective_scan.py)
    # over mamba_d_inner channels (``mamba_expand * hidden_size``) and a
    # state of mamba_d_state, its step size through a low-rank pair of
    # mamba_dt_rank; mamba_d_conv and mamba_conv_bias as above. Which layer
    # is of which kind follows from num_layers (``layer_kinds``);
    # sliding_window is the window layers' span and their ring's slots.
    mamba_d_inner: int = 0
    mamba_dt_rank: int = 0
    # models/dots3_note.py. Both kinds of layer are latent attention: a
    # "full_attention" layer has the sizes above (num_heads, q_lora_rank,
    # kv_lora_rank, qk_*_head_dim, v_head_dim, rope_theta) and a learned
    # indexer of index_n_heads heads of index_head_dim lanes that picks the
    # index_topk keys a query attends; a "sliding_attention" layer has the
    # swa_* sizes and swa_rope_theta and sees the sliding_window newest
    # keys, itself included. mla_lora_rescale: the normed latents are
    # scaled by (hidden / rank) ** 0.5.
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    swa_num_heads: int = 0
    swa_q_lora_rank: int = 0
    swa_kv_lora_rank: int = 0
    swa_qk_nope_head_dim: int = 0
    swa_qk_rope_head_dim: int = 0
    swa_v_head_dim: int = 0
    mla_lora_rescale: bool = False

    def __post_init__(self):
        if self.arch in ANY_ORDER_LISTS:
            _known_kinds(self.layer_types, self.num_layers,
                         ANY_ORDER_LISTS[self.arch])
        elif self.arch in FREE_LAYER_LISTS:
            free_layer_list(self.layer_types, self.num_layers,
                            self.first_k_dense_replace,
                            FREE_LAYER_LISTS[self.arch])
        elif self.layer_types:
            layer_period(self.layer_types, self.num_layers,
                         **PERIOD_RULES.get(self.arch, {}))

    @property
    def head_dim_(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

    @property
    def q_per_kv(self) -> int:
        assert self.num_heads % self.num_kv_heads == 0
        return self.num_heads // self.num_kv_heads

    @staticmethod
    def from_hf_config(d: dict, name: str = "model") -> "ModelConfig":
        """Map a HuggingFace config.json dict onto ModelConfig."""
        model_type = d.get("model_type", "llama")
        if model_type in ("llama", "mistral", "qwen2"):
            return ModelConfig(
                arch="llama",
                vocab_size=d["vocab_size"],
                hidden_size=d["hidden_size"],
                intermediate_size=d["intermediate_size"],
                num_layers=d["num_hidden_layers"],
                num_heads=d["num_attention_heads"],
                num_kv_heads=d.get("num_key_value_heads", d["num_attention_heads"]),
                head_dim=d.get("head_dim"),
                max_position_embeddings=d.get("max_position_embeddings", 4096),
                rope_theta=d.get("rope_theta", 10000.0),
                rms_norm_eps=d.get("rms_norm_eps", 1e-5),
                tie_word_embeddings=d.get("tie_word_embeddings", False),
                attention_bias=model_type == "qwen2" or d.get("attention_bias", False),
                name=name,
            )
        if model_type == "opt":
            return ModelConfig(
                arch="opt",
                vocab_size=d["vocab_size"],
                hidden_size=d["hidden_size"],
                intermediate_size=d.get("ffn_dim", 4 * d["hidden_size"]),
                num_layers=d["num_hidden_layers"],
                num_heads=d["num_attention_heads"],
                num_kv_heads=d["num_attention_heads"],
                max_position_embeddings=d.get("max_position_embeddings", 2048),
                tie_word_embeddings=d.get("tie_word_embeddings", True),
                name=name,
            )
        if model_type == "olmo_hybrid":
            if d["linear_num_key_heads"] != d["linear_num_value_heads"]:
                raise ValueError(
                    "olmo_hybrid: linear_num_key_heads != "
                    "linear_num_value_heads is not supported"
                )
            return ModelConfig(
                arch="olmo_hybrid",
                vocab_size=d["vocab_size"],
                hidden_size=d["hidden_size"],
                intermediate_size=d["intermediate_size"],
                num_layers=d["num_hidden_layers"],
                num_heads=d["num_attention_heads"],
                num_kv_heads=d.get("num_key_value_heads",
                                   d["num_attention_heads"]),
                head_dim=d.get("head_dim"),
                max_position_embeddings=d.get("max_position_embeddings", 4096),
                rope_theta=(d.get("rope_parameters") or {}).get("rope_theta"),
                rms_norm_eps=d.get("rms_norm_eps", 1e-6),
                tie_word_embeddings=d.get("tie_word_embeddings", False),
                layer_types=tuple(d["layer_types"]),
                linear_num_heads=d["linear_num_value_heads"],
                linear_key_head_dim=d["linear_key_head_dim"],
                linear_value_head_dim=d["linear_value_head_dim"],
                linear_conv_kernel_dim=d.get("linear_conv_kernel_dim", 4),
                linear_allow_neg_eigval=d.get("linear_allow_neg_eigval", False),
                name=name,
            )
        if model_type in ("deepseek_v3", "xing4_0"):
            # What the module does not implement is refused by its key, not
            # served as something else.
            scaling = d.get("rope_scaling")
            unsupported = {
                "rope_scaling.type != yarn": scaling is not None
                and scaling.get("type", scaling.get("rope_type")) != "yarn",
                "n_group/topk_group != 1": (d.get("n_group", 1),
                                            d.get("topk_group", 1)) != (1, 1),
                "scoring_func != sigmoid":
                    d.get("scoring_func", "sigmoid") != "sigmoid",
                "topk_method != noaux_tc":
                    d.get("topk_method", "noaux_tc") != "noaux_tc",
                "rope_interleave false": not d.get("rope_interleave", True),
                "attention_bias": bool(d.get("attention_bias", False)),
                "moe_layer_freq != 1": d.get("moe_layer_freq", 1) != 1,
                "hidden_act != silu": d.get("hidden_act", "silu") != "silu",
            }
            asked = [k for k, on in unsupported.items() if on]
            if asked:
                raise ValueError(
                    f"{model_type}: not supported: {', '.join(asked)}")
            hc = {}
            if model_type == "xing4_0":
                # The residual of hc_mult streams; deepseek_v3 has none.
                hc = dict(
                    hc_mult=d.get("hc_mult", 1),
                    hc_sinkhorn_iters=d.get("hc_sinkhorn_iters", 20),
                    hc_eps=d.get("hc_eps", 1e-6),
                    hc_res_clamp=(float(d.get("mhc_h_res_clamp_min", -30)),
                                  float(d.get("mhc_h_res_clamp_max", 30))))
            return ModelConfig(
                arch="deepseek_v3",
                vocab_size=d["vocab_size"],
                hidden_size=d["hidden_size"],
                intermediate_size=d["intermediate_size"],
                num_layers=d["num_hidden_layers"],
                num_heads=d["num_attention_heads"],
                num_kv_heads=d["num_attention_heads"],
                max_position_embeddings=d.get("max_position_embeddings", 4096),
                rope_theta=d.get("rope_theta", 10000.0),
                rms_norm_eps=d.get("rms_norm_eps", 1e-6),
                tie_word_embeddings=d.get("tie_word_embeddings", False),
                kv_lora_rank=d["kv_lora_rank"],
                qk_nope_head_dim=d["qk_nope_head_dim"],
                qk_rope_head_dim=d["qk_rope_head_dim"],
                v_head_dim=d["v_head_dim"],
                n_routed_experts=d["n_routed_experts"],
                num_experts_per_tok=d["num_experts_per_tok"],
                n_shared_experts=d.get("n_shared_experts", 0),
                moe_intermediate_size=d["moe_intermediate_size"],
                first_k_dense_replace=d.get("first_k_dense_replace", 0),
                routed_scaling_factor=d.get("routed_scaling_factor", 1.0),
                norm_topk_prob=d.get("norm_topk_prob", True),
                q_lora_rank=d.get("q_lora_rank") or 0,
                rope_scaling=None if scaling is None else YarnScaling(
                    factor=float(scaling["factor"]),
                    original_max_position_embeddings=scaling[
                        "original_max_position_embeddings"],
                    beta_fast=float(scaling.get("beta_fast") or 32),
                    beta_slow=float(scaling.get("beta_slow") or 1),
                    mscale=float(scaling.get("mscale") or 0),
                    mscale_all_dim=float(scaling.get("mscale_all_dim") or 0)),
                num_nextn_predict_layers=d.get("num_nextn_predict_layers", 0),
                name=name,
                **hc,
            )
        if model_type == "granitemoehybrid":
            # The family's siblings this module does not implement are
            # refused by their key, not served as something else.
            unsupported = {
                "num_local_experts > 0": d.get("num_local_experts", 0) > 0,
                "position_embedding_type != nope":
                    d.get("position_embedding_type", "nope") != "nope",
                "mamba_n_groups != 1": d.get("mamba_n_groups", 1) != 1,
                "mamba_proj_bias": bool(d.get("mamba_proj_bias", False)),
                "attention_bias": bool(d.get("attention_bias", False)),
                "hidden_act != silu": d.get("hidden_act", "silu") != "silu",
                "mamba_expand * hidden_size != mamba_n_heads * mamba_d_head":
                    d.get("mamba_expand", 2) * d["hidden_size"]
                    != d["mamba_n_heads"] * d["mamba_d_head"],
            }
            asked = [k for k, on in unsupported.items() if on]
            if asked:
                raise ValueError(
                    f"{model_type}: not supported: {', '.join(asked)}")
            return ModelConfig(
                arch="granite_hybrid",
                vocab_size=d["vocab_size"],
                hidden_size=d["hidden_size"],
                intermediate_size=d["shared_intermediate_size"],
                num_layers=d["num_hidden_layers"],
                num_heads=d["num_attention_heads"],
                num_kv_heads=d.get("num_key_value_heads",
                                   d["num_attention_heads"]),
                max_position_embeddings=d.get("max_position_embeddings", 4096),
                rope_theta=None,
                rms_norm_eps=d.get("rms_norm_eps", 1e-5),
                tie_word_embeddings=d.get("tie_word_embeddings", True),
                layer_types=tuple(d["layer_types"]),
                mamba_n_heads=d["mamba_n_heads"],
                mamba_d_head=d["mamba_d_head"],
                mamba_d_state=d["mamba_d_state"],
                mamba_d_conv=d.get("mamba_d_conv", 4),
                mamba_conv_bias=d.get("mamba_conv_bias", True),
                mamba_chunk_size=d.get("mamba_chunk_size", 256),
                embedding_multiplier=float(d.get("embedding_multiplier", 1.0)),
                attention_multiplier=float(d["attention_multiplier"]),
                residual_multiplier=float(d.get("residual_multiplier", 1.0)),
                logits_scaling=float(d.get("logits_scaling", 1.0)),
                name=name,
            )
        if model_type == "lfm2_moe":
            # What the module does not implement is refused by its key, not
            # served as something else.
            unsupported = {
                "conv_bias": bool(d.get("conv_bias", False)),
                "rope_scaling": d.get("rope_scaling") is not None,
                "conv_L_cache < 2": d.get("conv_L_cache", 3) < 2,
                "num_experts < 1": d.get("num_experts", 0) < 1,
                "num_shared_experts": bool(d.get("num_shared_experts", 0)),
                "hidden_act != silu": d.get("hidden_act", "silu") != "silu",
            }
            asked = [k for k, on in unsupported.items() if on]
            if asked:
                raise ValueError(
                    f"{model_type}: not supported: {', '.join(asked)}")
            return ModelConfig(
                arch="lfm2_moe",
                vocab_size=d["vocab_size"],
                hidden_size=d["hidden_size"],
                intermediate_size=d["intermediate_size"],
                num_layers=d["num_hidden_layers"],
                num_heads=d["num_attention_heads"],
                num_kv_heads=d.get("num_key_value_heads",
                                   d["num_attention_heads"]),
                head_dim=d.get("head_dim"),
                max_position_embeddings=d.get("max_position_embeddings", 4096),
                rope_theta=float(d.get("rope_theta", 1000000.0)),
                rms_norm_eps=d.get("norm_eps", 1e-5),
                tie_word_embeddings=d.get("tie_word_embeddings", True),
                layer_types=tuple(d["layer_types"]),
                n_routed_experts=d["num_experts"],
                num_experts_per_tok=d["num_experts_per_tok"],
                moe_intermediate_size=d["moe_intermediate_size"],
                first_k_dense_replace=d.get("num_dense_layers", 0),
                routed_scaling_factor=float(
                    d.get("routed_scaling_factor", 1.0)),
                norm_topk_prob=d.get("norm_topk_prob", True),
                conv_l_cache=d.get("conv_L_cache", 3),
                use_expert_bias=d.get("use_expert_bias", True),
                name=name,
            )
        if model_type == "afmoe":
            # What the module does not implement is refused by its key, not
            # served as something else.
            unsupported = {
                "rope_scaling": d.get("rope_scaling") is not None,
                "score_func != sigmoid":
                    d.get("score_func", "sigmoid") != "sigmoid",
                "n_group/num_expert_groups/topk_group > 1": max(
                    d.get("n_group", 1), d.get("num_expert_groups", 1),
                    d.get("topk_group", 1)) > 1,
                "num_experts < 1": d.get("num_experts", 0) < 1,
                "sliding_window < 1 beside a sliding_attention layer":
                    "sliding_attention" in d["layer_types"]
                    and int(d.get("sliding_window") or 0) < 1,
                "num_dense_layers >= num_hidden_layers":
                    d.get("num_dense_layers", 0) >= d["num_hidden_layers"],
                "attention_bias": bool(d.get("attention_bias", False)),
                "hidden_act != silu": d.get("hidden_act", "silu") != "silu",
            }
            asked = [k for k, on in unsupported.items() if on]
            if asked:
                raise ValueError(
                    f"{model_type}: not supported: {', '.join(asked)}")
            return ModelConfig(
                arch="afmoe",
                vocab_size=d["vocab_size"],
                hidden_size=d["hidden_size"],
                intermediate_size=d["intermediate_size"],
                num_layers=d["num_hidden_layers"],
                num_heads=d["num_attention_heads"],
                num_kv_heads=d.get("num_key_value_heads",
                                   d["num_attention_heads"]),
                head_dim=d.get("head_dim"),
                max_position_embeddings=d.get("max_position_embeddings", 4096),
                rope_theta=float(d.get("rope_theta", 10000.0)),
                rms_norm_eps=d.get("rms_norm_eps", 1e-5),
                tie_word_embeddings=d.get("tie_word_embeddings", False),
                layer_types=tuple(d["layer_types"]),
                sliding_window=int(d.get("sliding_window") or 0),
                n_routed_experts=d["num_experts"],
                num_experts_per_tok=d["num_experts_per_tok"],
                n_shared_experts=d.get("num_shared_experts", 0),
                moe_intermediate_size=d["moe_intermediate_size"],
                first_k_dense_replace=d.get("num_dense_layers", 0),
                routed_scaling_factor=float(d.get("route_scale", 1.0)),
                norm_topk_prob=d.get("route_norm", True),
                # mup_enabled: the table's rows times sqrt(hidden_size).
                embedding_multiplier=float(d["hidden_size"]) ** 0.5
                if d.get("mup_enabled", False) else 1.0,
                name=name,
            )
        if model_type == "mimo_v2":
            return _mimo_v2_config(d, name)
        if model_type == "dots3_note":
            return _dots3_note_config(d, name)
        if model_type == "phi4flash":
            return _phi4flash_config(d, name)
        raise ValueError(f"Unsupported model_type: {model_type}")

    @staticmethod
    def from_pretrained_dir(path: str, name: Optional[str] = None) -> "ModelConfig":
        with open(os.path.join(path, "config.json")) as f:
            return ModelConfig.from_hf_config(json.load(f), name=name or path)


LAYER_KINDS = ("linear_attention", "full_attention")
# Per arch, what ``layer_period`` is asked beside the list: the names of the
# (state-keeping, full-attention) kinds and whether the full layer has to
# close its period. An arch not named reads the default: olmo_hybrid's.
PERIOD_RULES = {
    "granite_hybrid": {"kinds": ("mamba", "attention"), "closed": False},
}


# Per arch whose module takes its two kinds (state-keeping, full-attention)
# in ANY order (models/lfm2_moe.py scans layers one at a time with the
# operator chosen by a table): the published list there is not equal periods.
FREE_LAYER_LISTS = {"lfm2_moe": ("conv", "full_attention")}
# Per arch whose layers differ by DATA alone (models/afmoe.py: a layer's span
# and whether it rotates are two scalars its scans index), so its list is
# taken in any order and need not hold both kinds.
ANY_ORDER_LISTS = {"afmoe": ("sliding_attention", "full_attention"),
                   "mimo_v2": ("sliding_attention", "full_attention"),
                   "dots3_note": ("sliding_attention", "full_attention")}


def _mimo_v2_config(d: dict, name: str) -> ModelConfig:
    """``model_type: mimo_v2`` (models/mimo_v2.py): what the module does not
    implement is refused by its key, not served as something else; so is
    every key of the towers and heads the language model's row leaves out
    (vision, audio, next-token prediction)."""
    scaling = d.get("rope_scaling") or {}
    pattern = list(d["hybrid_layer_pattern"])
    freq = d.get("moe_layer_freq", 1)
    freq = list(freq) if isinstance(freq, (list, tuple)) \
        else [int(bool(freq))] * len(pattern)
    dense = freq.index(1) if 1 in freq else len(freq)
    window = d.get("sliding_window") or 0
    ep_size, ep_rank = int(d.get("ep_size", 1)), int(d.get("ep_rank", 0))
    unsupported = {
        "add_full_attention_sink_bias":
            bool(d.get("add_full_attention_sink_bias", False)),
        "n_group/topk_group > 1":
            max(d.get("n_group") or 1, d.get("topk_group") or 1) > 1,
        "n_shared_experts": bool(d.get("n_shared_experts")),
        "attention_chunk_size != sliding_window":
            d.get("attention_chunk_size") not in (None, window),
        "sliding_window_size != sliding_window":
            d.get("sliding_window_size") not in (None, window),
        "sliding_window < 1": window < 1,
        **{f"{k} (a vision or audio tower or a next-token-prediction "
           f"head is not served)": True for k, v in d.items()
           if v and ("vision" in k or "audio" in k
                     or k == "num_nextn_predict_layers")},
        "rope_scaling.type != default":
            scaling.get("type", scaling.get("rope_type", "default"))
            != "default",
        "scoring_func != sigmoid":
            d.get("scoring_func", "sigmoid") != "sigmoid",
        "topk_method != noaux_tc":
            d.get("topk_method", "noaux_tc") != "noaux_tc",
        "attention_bias": bool(d.get("attention_bias", False)),
        "hidden_act != silu": d.get("hidden_act", "silu") != "silu",
        "hybrid_block_size": d.get("hybrid_block_size") is not None,
        "swa_head_dim/swa_v_head_dim/swa_num_attention_heads differ from "
        "the full layers'": (
            d.get("swa_head_dim", d["head_dim"]),
            d.get("swa_v_head_dim", d["v_head_dim"]),
            d.get("swa_num_attention_heads", d["num_attention_heads"]),
        ) != (d["head_dim"], d["v_head_dim"], d["num_attention_heads"]),
        "hybrid_layer_pattern / moe_layer_freq: an entry a layer, 0 or 1":
            len(pattern) != d["num_hidden_layers"]
            or len(freq) != d["num_hidden_layers"]
            or bool((set(pattern) | set(freq)) - {0, 1}),
        "hybrid_layer_pattern needs a layer of each kind":
            set(pattern) != {0, 1},
        "moe_layer_freq: leading dense layers, then sparse ones only":
            any(f != 1 for f in freq[dense:]) or dense == len(freq),
        "ep_rank outside ep_size": not 0 <= ep_rank < max(ep_size, 1),
    }
    asked = [k for k, on in unsupported.items() if on]
    if asked:
        raise ValueError(f"mimo_v2: not supported: {', '.join(asked)}")
    return ModelConfig(
        arch="mimo_v2",
        vocab_size=d["vocab_size"],
        hidden_size=d["hidden_size"],
        intermediate_size=d["intermediate_size"],
        num_layers=d["num_hidden_layers"],
        num_heads=d["num_attention_heads"],
        num_kv_heads=d["num_key_value_heads"],
        head_dim=d["head_dim"],
        max_position_embeddings=d.get("max_position_embeddings", 4096),
        rope_theta=float(d.get("rope_theta", 10000.0)),
        rms_norm_eps=d.get("layernorm_epsilon", 1e-5),
        tie_word_embeddings=d.get("tie_word_embeddings", False),
        layer_types=tuple(ANY_ORDER_LISTS["mimo_v2"][1 - p]
                          for p in pattern),
        sliding_window=window,
        v_head_dim=d["v_head_dim"],
        swa_num_kv_heads=d.get("swa_num_key_value_heads",
                               d["num_key_value_heads"]),
        swa_rope_theta=float(d.get("swa_rope_theta",
                                   d.get("rope_theta", 10000.0))),
        # Whole pairs: rotate-half turns lane i with lane i + rotary_dim/2.
        rotary_dim=int(d["head_dim"]
                       * d.get("partial_rotary_factor", 1.0)) // 2 * 2,
        attention_value_scale=float(d.get("attention_value_scale") or 1.0),
        swa_attention_sink=bool(d.get("add_swa_attention_sink_bias", False)),
        n_routed_experts=d["n_routed_experts"],
        num_experts_per_tok=d["num_experts_per_tok"],
        moe_intermediate_size=d["moe_intermediate_size"],
        first_k_dense_replace=dense,
        routed_scaling_factor=float(d.get("routed_scaling_factor") or 1.0),
        norm_topk_prob=d.get("norm_topk_prob", True),
        ep_size=ep_size, ep_rank=ep_rank,
        name=name,
    )


def _dots3_note_config(d: dict, name: str) -> ModelConfig:
    """``model_type: dots3_note`` (models/dots3_note.py): what the module
    does not implement is refused by its key, not served as something else;
    so is every key of the towers and heads the language model's row leaves
    out (vision, audio, next-token prediction)."""
    types = list(d["layer_types"])
    kinds = ANY_ORDER_LISTS["dots3_note"]
    ep_size, ep_rank = int(d.get("ep_size", 1)), int(d.get("ep_rank", 0))
    window = d.get("sliding_window_size") or 0
    unsupported = {
        "attention_gate_type != headwise":
            d.get("attention_gate_type") != "headwise",
        "swa_attention_gate_type != headwise":
            d.get("swa_attention_gate_type") != "headwise",
        "rope_scaling": d.get("rope_scaling") is not None,
        "n_shared_experts != 1": d.get("n_shared_experts") != 1,
        "scoring_func != sigmoid":
            d.get("scoring_func", "sigmoid") != "sigmoid",
        "topk_method != noaux_tc":
            d.get("topk_method", "noaux_tc") != "noaux_tc",
        "moe_layer_freq != 1": d.get("moe_layer_freq", 1) != 1,
        "attention_bias": bool(d.get("attention_bias", False)),
        "n_group/topk_group > 1":
            max(d.get("n_group") or 1, d.get("topk_group") or 1) > 1,
        "hidden_act != silu": d.get("hidden_act", "silu") != "silu",
        "tie_word_embeddings": bool(d.get("tie_word_embeddings", False)),
        "sliding_window_size < 1": window < 1,
        "index_topk < 1": (d.get("index_topk") or 0) < 1,
        "q_lora_rank / swa_q_lora_rank: a low-rank query is the only one "
        "served": not d.get("q_lora_rank") or not d.get("swa_q_lora_rank"),
        "num_key_value_heads != num_attention_heads (latent attention has "
        "no grouped heads)": (
            d.get("num_key_value_heads", d["num_attention_heads"]),
            d.get("swa_num_key_value_heads", d["swa_num_attention_heads"]),
        ) != (d["num_attention_heads"], d["swa_num_attention_heads"]),
        "qk_rope_head_dim / swa_qk_rope_head_dim / index_head_dim: an even "
        "number of rope lanes, the index head at least as wide":
            d["qk_rope_head_dim"] % 2 != 0
            or d["swa_qk_rope_head_dim"] % 2 != 0
            or d["index_head_dim"] < d["qk_rope_head_dim"],
        "layer_types: an entry a layer, sliding_attention or "
        "full_attention": len(types) != d["num_hidden_layers"]
        or bool(set(types) - set(kinds)),
        "layer_types needs a layer of each kind": set(types) != set(kinds),
        "first_k_dense_replace >= num_hidden_layers":
            d.get("first_k_dense_replace", 0) >= d["num_hidden_layers"],
        "ep_rank outside ep_size": not 0 <= ep_rank < max(ep_size, 1),
        **{f"{k} (a vision or audio tower or a next-token-prediction head "
           f"is not served)": True for k, v in d.items()
           if v and ("vision" in k or "audio" in k
                     or k == "num_nextn_predict_layers")},
    }
    asked = [k for k, on in unsupported.items() if on]
    if asked:
        raise ValueError(f"dots3_note: not supported: {', '.join(asked)}")
    return ModelConfig(
        arch="dots3_note",
        vocab_size=d["vocab_size"],
        hidden_size=d["hidden_size"],
        intermediate_size=d["intermediate_size"],
        num_layers=d["num_hidden_layers"],
        num_heads=d["num_attention_heads"],
        num_kv_heads=d["num_attention_heads"],
        max_position_embeddings=d.get("max_position_embeddings", 4096),
        rope_theta=float(d.get("rope_theta", 10000.0)),
        rms_norm_eps=d.get("rms_norm_eps", 1e-5),
        tie_word_embeddings=False,
        layer_types=tuple(types),
        sliding_window=window,
        kv_lora_rank=d["kv_lora_rank"],
        q_lora_rank=d["q_lora_rank"],
        qk_nope_head_dim=d["qk_nope_head_dim"],
        qk_rope_head_dim=d["qk_rope_head_dim"],
        v_head_dim=d["v_head_dim"],
        index_n_heads=d["index_n_heads"],
        index_head_dim=d["index_head_dim"],
        index_topk=d["index_topk"],
        swa_num_heads=d["swa_num_attention_heads"],
        swa_q_lora_rank=d["swa_q_lora_rank"],
        swa_kv_lora_rank=d["swa_kv_lora_rank"],
        swa_qk_nope_head_dim=d["swa_qk_nope_head_dim"],
        swa_qk_rope_head_dim=d["swa_qk_rope_head_dim"],
        swa_v_head_dim=d["swa_v_head_dim"],
        swa_rope_theta=float(d.get("swa_rope_theta",
                                   d.get("rope_theta", 10000.0))),
        mla_lora_rescale=bool(d.get("apply_mla_qkv_lora_rescale", False)),
        n_routed_experts=d["n_routed_experts"],
        num_experts_per_tok=d["num_experts_per_tok"],
        n_shared_experts=1,
        moe_intermediate_size=d["moe_intermediate_size"],
        first_k_dense_replace=d.get("first_k_dense_replace", 0),
        routed_scaling_factor=float(d.get("routed_scaling_factor") or 1.0),
        norm_topk_prob=d.get("norm_topk_prob", True),
        ep_size=ep_size, ep_rank=ep_rank,
        name=name,
    )


def _phi4flash_config(d: dict, name: str) -> ModelConfig:
    """``model_type: phi4flash`` (models/phi4flash.py): what the module does
    not implement is refused by its key, not served as something else. The
    four ``mamba_*`` sizes the published config omits read Mamba-1's
    defaults."""
    hidden, heads = d["hidden_size"], d["num_attention_heads"]
    kv_heads = d.get("num_key_value_heads", heads)
    layers = d["num_hidden_layers"]
    window = d.get("sliding_window")
    n_state = d.get("mamba_d_state", 16)
    inner = d.get("mamba_expand", 2) * hidden
    rank = d.get("mamba_dt_rank", "auto")
    rank = -(-hidden // 16) if rank == "auto" else rank
    unsupported = {
        "mb_per_layer != 2": d.get("mb_per_layer", 2) != 2,
        "num_hidden_layers: a multiple of 4, at least 8":
            layers % 4 != 0 or layers < 8,
        "sliding_window: one number, a multiple of 16 (the ring's tile of "
        "slots)": not isinstance(window, int) or isinstance(window, bool)
        or window < 16 or window % 16 != 0,
        **{f"{k} (the model has no position embedding: a rotation is not "
           f"served)": True for k, v in d.items()
           if v and (k.startswith("rope_") or "rotary" in k)},
        "mamba_d_state: a multiple of 8, at most 64":
            n_state % 8 != 0 or not 8 <= n_state <= 64,
        "mamba_expand * hidden_size: a multiple of 128": inner % 128 != 0,
        "mamba_d_conv < 2": d.get("mamba_d_conv", 4) < 2,
        "mamba_dt_rank < 1": not isinstance(rank, int) or rank < 1,
        "mamba_proj_bias": bool(d.get("mamba_proj_bias", False)),
        "num_attention_heads / num_key_value_heads: whole pairs, the query "
        "pairs a multiple of the KV pairs":
            heads % 2 != 0 or kv_heads % 2 != 0 or heads % kv_heads != 0,
        "hidden_size: a multiple of num_attention_heads": hidden % heads != 0,
        "tie_word_embeddings false": not d.get("tie_word_embeddings", True),
        "mlp_bias": bool(d.get("mlp_bias", False)),
        "lm_head_bias": bool(d.get("lm_head_bias", False)),
        "hidden_act != silu": d.get("hidden_act", "silu") != "silu",
    }
    asked = [k for k, on in unsupported.items() if on]
    if asked:
        raise ValueError(f"phi4flash: not supported: {', '.join(asked)}")
    return ModelConfig(
        arch="phi4flash",
        vocab_size=d["vocab_size"],
        hidden_size=hidden,
        intermediate_size=d["intermediate_size"],
        num_layers=layers,
        num_heads=heads,
        num_kv_heads=kv_heads,
        max_position_embeddings=d.get("max_position_embeddings", 4096),
        rope_theta=None,
        rms_norm_eps=d.get("layer_norm_eps", 1e-5),
        tie_word_embeddings=True,
        attention_bias=True,
        sliding_window=window,
        mamba_d_state=n_state,
        mamba_d_conv=d.get("mamba_d_conv", 4),
        mamba_conv_bias=d.get("mamba_conv_bias", True),
        mamba_d_inner=inner,
        mamba_dt_rank=rank,
        name=name,
    )


def _known_kinds(layer_types, num_layers: int,
                 kinds: Tuple[str, str]) -> Tuple[str, ...]:
    """``layer_types`` as a tuple; refused: a length other than
    ``num_layers`` and an unknown kind."""
    types = tuple(layer_types)
    if len(types) != num_layers:
        raise ValueError(
            f"layer_types has {len(types)} entries for {num_layers} layers")
    unknown = sorted(set(types) - set(kinds))
    if unknown:
        raise ValueError(f"layer_types: unknown kinds {unknown}; "
                         f"supported: {list(kinds)}")
    return types


def free_layer_list(layer_types, num_layers: int, dense_layers: int,
                    kinds: Tuple[str, str]) -> None:
    """Refused: a length other than ``num_layers``, an unknown kind, a list
    without a layer of each kind (a pool or a state spec of no layer), and
    a full-attention layer among the ``dense_layers`` leading ones (their
    scan holds the state-keeping operator alone)."""
    types = _known_kinds(layer_types, num_layers, kinds)
    if set(types) != set(kinds):
        raise ValueError(
            f"layer_types {list(types)} needs a layer of each of {kinds}")
    if not 0 <= dense_layers < num_layers \
            or kinds[1] in types[:dense_layers]:
        raise ValueError(
            f"layer_types: the {dense_layers} leading dense layers must be "
            f"{kinds[0]} layers with a sparse layer behind them")


def layer_period(layer_types, num_layers: int, *,
                 kinds: Tuple[str, str] = LAYER_KINDS,
                 closed: bool = True) -> Tuple[str, ...]:
    """The repeating pattern of ``layer_types``: layers that keep a state
    (``kinds[0]``) and exactly ONE full-attention layer (``kinds[1]``).
    ``closed`` (models/olmo_hybrid.py, which traces some linear layers then
    the full layer and scans that): the full layer ends the period;
    otherwise (models/granite_hybrid.py, which scans the segments between
    full layers) it may stand anywhere in it. Both modules share this one
    helper. Refused: a length other than ``num_layers``, an unknown kind,
    and a list that is not a whole number of such equal periods."""
    types = _known_kinds(layer_types, num_layers, kinds)
    full = types.count(kinds[1])
    n = len(types) // full if full else 0
    if n < 2 or len(types) % n or types != types[:n] * (len(types) // n) \
            or types[:n].count(kinds[1]) != 1 \
            or (closed and types[n - 1] != kinds[1]):
        raise ValueError(
            f"layer_types {list(types)} is not a whole number of equal "
            f"periods of {kinds[0]} layers "
            + (f"closed by one {kinds[1]} layer" if closed
               else f"around one {kinds[1]} layer"))
    return types[:n]


# Small built-in configs for tests and single-chip benchmarks.
TINY_LLAMA = ModelConfig(
    arch="llama", vocab_size=512, hidden_size=128, intermediate_size=256,
    num_layers=2, num_heads=4, num_kv_heads=2, max_position_embeddings=512,
    name="tiny-llama",
)

# Variant with 8 KV heads so tensor parallelism up to tp=8 shards the KV
# pool for real in multi-chip dry runs (tiny-llama's 2 KV heads cap tp at 2).
TINY_LLAMA_8KV = ModelConfig(
    arch="llama", vocab_size=512, hidden_size=256, intermediate_size=512,
    num_layers=2, num_heads=8, num_kv_heads=8, max_position_embeddings=512,
    name="tiny-llama-8kv",
)

# TinyLlama-1.1B shape: fits a single v5e chip with room for KV; used by
# bench.py for single-chip throughput (the 8B headline model needs the mesh).
LLAMA_1B = ModelConfig(
    arch="llama", vocab_size=32000, hidden_size=2048, intermediate_size=5632,
    num_layers=22, num_heads=32, num_kv_heads=4, max_position_embeddings=2048,
    name="llama-1b",
)

# Tiny OPT-family config sharing tiny-llama's 512-token vocabulary, so CPU
# tests can pair them as a speculative draft/target (docs/PERF.md round 8):
# draft proposals are accepted by token id, which requires one shared
# tokenizer/vocab across the pair (both resolve to the same ByteTokenizer).
TINY_OPT = ModelConfig(
    arch="opt", vocab_size=512, hidden_size=64, intermediate_size=128,
    num_layers=2, num_heads=2, num_kv_heads=2, max_position_embeddings=512,
    tie_word_embeddings=True, name="tiny-opt",
)

# facebook/opt-125m architecture (reference parity config #1, BASELINE.json).
OPT_125M = ModelConfig(
    arch="opt", vocab_size=50272, hidden_size=768, intermediate_size=3072,
    num_layers=12, num_heads=12, num_kv_heads=12, max_position_embeddings=2048,
    tie_word_embeddings=True, name="facebook/opt-125m",
)

# meta-llama/Llama-3-8B architecture (reference headline benchmark model,
# tutorials/08-benchmark-multi-round-qa-multi-gpu.md).
LLAMA3_8B = ModelConfig(
    arch="llama", vocab_size=128256, hidden_size=4096, intermediate_size=14336,
    num_layers=32, num_heads=32, num_kv_heads=8, max_position_embeddings=8192,
    rope_theta=500000.0, name="meta-llama/Meta-Llama-3-8B",
)

# meta-llama/Llama-3.2-3B architecture: head_dim 128, so the Pallas paged
# flash-decode kernel applies, and the bf16 weights (~6.4 GB) fit a single
# v5e chip — the single-chip long-context (paged attention) benchmark model.
LLAMA32_3B = ModelConfig(
    arch="llama", vocab_size=128256, hidden_size=3072, intermediate_size=8192,
    num_layers=28, num_heads=24, num_kv_heads=8, head_dim=128,
    max_position_embeddings=131072, rope_theta=500000.0,
    tie_word_embeddings=True, name="llama-3b",
)

# Tiny config with head_dim 128 so CPU tests can exercise the Pallas paged
# decode path (interpret mode) end-to-end.
TINY_LLAMA_128DH = ModelConfig(
    arch="llama", vocab_size=512, hidden_size=256, intermediate_size=512,
    num_layers=2, num_heads=2, num_kv_heads=2, head_dim=128,
    max_position_embeddings=512, name="tiny-llama-128dh",
)

# Tiny hybrid: two periods of (3 linear-attention + 1 full-attention) layers
# (tests/test_olmo_hybrid.py compares it with the plain reference).
TINY_OLMO_HYBRID = ModelConfig(
    arch="olmo_hybrid", vocab_size=512, hidden_size=128,
    intermediate_size=256, num_layers=8, num_heads=4, num_kv_heads=4,
    max_position_embeddings=512, rope_theta=None, rms_norm_eps=1e-6,
    layer_types=("linear_attention",) * 3 + ("full_attention",)
    + ("linear_attention",) * 3 + ("full_attention",),
    linear_num_heads=4, linear_key_head_dim=16, linear_value_head_dim=32,
    linear_conv_kernel_dim=4, linear_allow_neg_eigval=True,
    name="tiny-olmo-hybrid",
)

# Tiny latent-attention sparse-expert decoder: 1 dense + 3 sparse layers, 16
# experts top-3 beside one shared (tests/test_deepseek_v3.py compares it
# with the plain reference). kv_lora_rank is whole lanes, as the paged kernel
# over latent rows asks (values are a lane-aligned slice of a row); a row is
# 128 + 8 values padded to 256.
TINY_DEEPSEEK_V3 = ModelConfig(
    arch="deepseek_v3", vocab_size=512, hidden_size=64,
    intermediate_size=128, num_layers=4, num_heads=4, num_kv_heads=4,
    max_position_embeddings=512, rope_theta=10000.0, rms_norm_eps=1e-6,
    kv_lora_rank=128, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    n_routed_experts=16, num_experts_per_tok=3, n_shared_experts=1,
    moe_intermediate_size=32, first_k_dense_replace=1,
    routed_scaling_factor=2.448, name="tiny-deepseek-v3",
)

# The same family with what Xing4.0 adds (tests/test_xing4.py compares it with
# its plain reference): a residual of 4 streams, a low-rank query, YaRN's
# frequencies and softmax scale (factor 64 over 4096 original positions, as
# published), 2 dense + 2 sparse layers, 8 experts top-2 beside one shared.
TINY_XING4 = ModelConfig(
    arch="deepseek_v3", vocab_size=512, hidden_size=64,
    intermediate_size=128, num_layers=4, num_heads=4, num_kv_heads=4,
    max_position_embeddings=8192, rope_theta=10000.0, rms_norm_eps=1e-6,
    kv_lora_rank=128, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    n_routed_experts=8, num_experts_per_tok=2, n_shared_experts=1,
    moe_intermediate_size=32, first_k_dense_replace=2,
    routed_scaling_factor=2.0, q_lora_rank=24,
    rope_scaling=YarnScaling(factor=64.0,
                             original_max_position_embeddings=4096,
                             mscale=1.0, mscale_all_dim=1.0),
    hc_mult=4, num_nextn_predict_layers=1, name="tiny-xing4",
)

# Tiny state-space hybrid: two periods of (2 Mamba-2 + attention + 1 Mamba-2)
# layers, so the full layer does not close its period; attention heads of 64
# lanes (the paged decode kernel's packed path), every multiplier off 1, a
# tied head (tests/test_granite_hybrid.py compares it with the plain
# reference).
TINY_GRANITE_HYBRID = ModelConfig(
    arch="granite_hybrid", vocab_size=512, hidden_size=64,
    intermediate_size=128, num_layers=8, num_heads=4, num_kv_heads=2,
    head_dim=64, max_position_embeddings=512, rope_theta=None,
    rms_norm_eps=1e-5, tie_word_embeddings=True,
    layer_types=("mamba", "mamba", "attention", "mamba") * 2,
    mamba_n_heads=4, mamba_d_head=16, mamba_d_state=32, mamba_d_conv=4,
    mamba_conv_bias=True, mamba_chunk_size=256,
    embedding_multiplier=6.0, attention_multiplier=0.25,
    residual_multiplier=0.4, logits_scaling=4.0,
    name="tiny-granite-hybrid",
)

# Tiny gated-short-convolution sparse-expert hybrid at the PUBLISHED depth
# and irregular pattern (five periods of conv conv full conv, then conv full
# conv conv: attention at 2, 6, 10, 14, 18, 21), 2 leading dense layers, 8
# experts top-2 with a bias on the choice, 64-lane heads with a per-head norm
# of q and k, a tied head (tests/test_lfm2_moe.py compares it with the plain
# reference).
LFM2_LAYER_TYPES = ("conv", "conv", "full_attention", "conv") * 5 \
    + ("conv", "full_attention", "conv", "conv")
TINY_LFM2_MOE = ModelConfig(
    arch="lfm2_moe", vocab_size=512, hidden_size=128,
    intermediate_size=256, num_layers=24, num_heads=4, num_kv_heads=2,
    head_dim=64, max_position_embeddings=512, rope_theta=1000000.0,
    rms_norm_eps=1e-5, tie_word_embeddings=True,
    layer_types=LFM2_LAYER_TYPES,
    n_routed_experts=8, num_experts_per_tok=2, moe_intermediate_size=64,
    first_k_dense_replace=2, routed_scaling_factor=1.0,
    conv_l_cache=3, name="tiny-lfm2-moe",
)

# Tiny bounded-span sparse-expert decoder: the published period (sliding x 3,
# full) twice, 2 leading dense layers, 8 experts top-2 beside a shared one,
# a span of 24 keys (not a multiple of the block), 128-lane heads so that
# the paged kernels take it (tests/test_afmoe.py compares it with the plain
# reference).
AFMOE_LAYER_TYPES = ("sliding_attention",) * 3 + ("full_attention",)
TINY_AFMOE = ModelConfig(
    arch="afmoe", vocab_size=512, hidden_size=128, intermediate_size=256,
    num_layers=8, num_heads=4, num_kv_heads=2, head_dim=128,
    max_position_embeddings=512, rope_theta=10000.0, rms_norm_eps=1e-5,
    layer_types=AFMOE_LAYER_TYPES * 2, sliding_window=24,
    n_routed_experts=8, num_experts_per_tok=2, n_shared_experts=1,
    moe_intermediate_size=64, first_k_dense_replace=2,
    routed_scaling_factor=2.826, embedding_multiplier=128 ** 0.5,
    name="tiny-afmoe",
)

# Tiny window-ring sparse-expert decoder: full layers of 1 KV head and window
# layers of 2, keys of 48 lanes (16 of them rotate) and values of 32, a window
# of 128 keys with a sink, 1 leading dense layer, 16 experts top-4 with no
# shared one (tests/test_mimo_v2.py compares it with the plain reference);
# and the same as rank 1 of 4 chips that share every sparse layer's experts.
TINY_MIMO_V2 = ModelConfig(
    arch="mimo_v2", vocab_size=512, hidden_size=128, intermediate_size=256,
    num_layers=6, num_heads=4, num_kv_heads=1, head_dim=48,
    max_position_embeddings=1024, rope_theta=10000000.0, rms_norm_eps=1e-5,
    layer_types=("full_attention", "sliding_attention", "sliding_attention",
                 "full_attention", "sliding_attention", "full_attention"),
    sliding_window=128, v_head_dim=32, swa_num_kv_heads=2,
    swa_rope_theta=10000.0, rotary_dim=16, attention_value_scale=0.707,
    swa_attention_sink=True, n_routed_experts=16, num_experts_per_tok=4,
    moe_intermediate_size=64, first_k_dense_replace=1,
    name="tiny-mimo-v2",
)
# Tiny SambaY decoder: two (S6, window) pairs, the S6 layer that hands its
# scan output on, the one paged full layer, one (memory unit, cross) pair;
# 8 query heads over 4 KV heads of 64 lanes (4 query pairs over 2 KV pairs: a
# KV pair is a row of 128 lanes, which the paged kernels take), a window of
# 64 keys (tests/test_phi4flash.py compares it with the plain reference).
TINY_PHI4FLASH = ModelConfig(
    arch="phi4flash", vocab_size=512, hidden_size=512, intermediate_size=256,
    num_layers=8, num_heads=8, num_kv_heads=4, max_position_embeddings=1024,
    rope_theta=None, rms_norm_eps=1e-5, tie_word_embeddings=True,
    attention_bias=True, sliding_window=64, mamba_d_state=16, mamba_d_conv=4,
    mamba_conv_bias=True, mamba_d_inner=1024, mamba_dt_rank=32,
    name="tiny-phi4flash",
)
TINY_MIMO_V2_EP4 = dataclasses.replace(
    TINY_MIMO_V2, n_routed_experts=4, ep_size=4, ep_rank=1,
    name="tiny-mimo-v2-ep4")
# Tiny dots3-note decoder: two kinds of latent attention in the published
# order (full, full, then sliding x3 + full), the full kind with 4 heads
# over a latent of 128 + 16 rope lanes (whole lanes, as the paged path asks:
# a row is 256 lanes and the index key's tile 128 more) and an indexer of 2
# heads x 128 that picks 48 keys, the sliding kind with 2 heads over a latent
# of 64 + 16 and a window of 33 keys (an odd number, as the published 513
# is), 1 leading dense layer, 16 experts top-4 beside a shared one
# (tests/test_dots3.py compares it with the plain reference); and the same
# as rank 1 of 4 chips that share every sparse layer's experts.
TINY_DOTS3 = ModelConfig(
    arch="dots3_note", vocab_size=512, hidden_size=128,
    intermediate_size=256, num_layers=6, num_heads=4, num_kv_heads=4,
    max_position_embeddings=1024, rope_theta=80000000.0, rms_norm_eps=1e-5,
    layer_types=("full_attention", "full_attention", "sliding_attention",
                 "sliding_attention", "sliding_attention", "full_attention"),
    sliding_window=33, kv_lora_rank=128, q_lora_rank=48, qk_nope_head_dim=32,
    qk_rope_head_dim=16, v_head_dim=32, index_n_heads=2, index_head_dim=128,
    index_topk=48, swa_num_heads=2, swa_q_lora_rank=48, swa_kv_lora_rank=64,
    swa_qk_nope_head_dim=48, swa_qk_rope_head_dim=16, swa_v_head_dim=32,
    swa_rope_theta=50000.0, mla_lora_rescale=True, n_routed_experts=16,
    num_experts_per_tok=4, n_shared_experts=1, moe_intermediate_size=64,
    first_k_dense_replace=1, name="tiny-dots3",
)
TINY_DOTS3_EP4 = dataclasses.replace(
    TINY_DOTS3, n_routed_experts=4, ep_size=4, ep_rank=1,
    name="tiny-dots3-ep4")

NAMED_CONFIGS = {
    "tiny-llama": TINY_LLAMA,
    "tiny-phi4flash": TINY_PHI4FLASH,
    "tiny-mimo-v2": TINY_MIMO_V2,
    "tiny-mimo-v2-ep4": TINY_MIMO_V2_EP4,
    "tiny-dots3": TINY_DOTS3,
    "tiny-dots3-ep4": TINY_DOTS3_EP4,
    "tiny-afmoe": TINY_AFMOE,
    "tiny-lfm2-moe": TINY_LFM2_MOE,
    "tiny-granite-hybrid": TINY_GRANITE_HYBRID,
    "tiny-deepseek-v3": TINY_DEEPSEEK_V3,
    "tiny-xing4": TINY_XING4,
    "tiny-olmo-hybrid": TINY_OLMO_HYBRID,
    "tiny-llama-8kv": TINY_LLAMA_8KV,
    "tiny-llama-128dh": TINY_LLAMA_128DH,
    "tiny-opt": TINY_OPT,
    "llama-1b": LLAMA_1B,
    "llama-3b": LLAMA32_3B,
    "facebook/opt-125m": OPT_125M,
    "meta-llama/Meta-Llama-3-8B": LLAMA3_8B,
    "llama-3-8b": LLAMA3_8B,
}


def resolve_model_config(model: str) -> ModelConfig:
    """Resolve a model name or local HF directory to a ModelConfig."""
    if model in NAMED_CONFIGS:
        return NAMED_CONFIGS[model]
    if os.path.isdir(model) and os.path.exists(os.path.join(model, "config.json")):
        return ModelConfig.from_pretrained_dir(model)
    raise ValueError(
        f"Unknown model {model!r}: not a named config ({list(NAMED_CONFIGS)}) "
        "and not a local HuggingFace directory"
    )
