"""Operations and bytes of a Xing4.0-shaped decoder from its HF
``config.json`` alone: ``lib/shapes_moe.py``'s arithmetic (latent attention
over one cached row a token, sparse experts beside a shared one, leading
dense layers) where the two models agree, and what this one adds:

  * the query is a low-rank pair, ``q_a`` [hidden, q_lora_rank] and ``q_b``
    [q_lora_rank, heads x (nope + rope)], not one full matrix
    (``shapes_moe.attention_params`` would count 22.0 M where these are
    7.5 M at the published widths);
  * the residual is ``hc_mult`` streams. Around every sublayer (2 a layer)
    stands the stream mix: its ``phi`` [nD, n(n+2)], ``b`` and ``a`` in
    float32, read once a step whatever the rows; and per token the streams
    (``n x D`` values, stored in the activations' bf16) read once for
    ``H_pre x`` and its norm and projections, and read and written once for
    ``H_res x + H_post^T F``.

Counted, as there: matrix products and what must cross HBM once. Not
counted: norms, rotary, softmax, sigmoid, exp, the Sinkhorn iterations (480
flops a token and sublayer on values that never leave the chip's registers),
the second read of the streams that ``H_pre x`` needs after the matrices are
known, the branch's read in the post-mix -- so a share errs low, never high.
"""

from typing import Dict

from benchmarks.chip.lib import shapes_moe

BF16, F32 = shapes_moe.BF16, shapes_moe.F32


def dims(cfg: dict) -> Dict[str, int]:
    d = shapes_moe.dims(cfg)
    n = cfg.get("hc_mult", 1)
    d.update(streams=n, q_rank=cfg.get("q_lora_rank") or 0,
             sublayers=2 * d["layers"], mix_columns=n * (n + 2))
    return d


def attention_params(cfg: dict) -> int:
    """q_a and q_b (or one W_q), W_kva, W_kvb, W_o."""
    d = dims(cfg)
    if not d["q_rank"]:
        return shapes_moe.attention_params(cfg)
    q_out = d["heads"] * (d["nope"] + d["rope"])
    return (d["hidden"] * d["q_rank"] + d["q_rank"] * q_out
            + d["hidden"] * d["row"]
            + d["rank"] * d["heads"] * (d["nope"] + d["v"])
            + d["heads"] * d["v"] * d["hidden"])


def mix_params(cfg: dict) -> int:
    """One SUBLAYER's stream mix: phi, b, a (float32)."""
    d = dims(cfg)
    if d["streams"] == 1:
        return 0
    return (d["streams"] * d["hidden"] + 1) * d["mix_columns"] + 3


def dense_layer_params(cfg: dict) -> int:
    """Without the mix (``mix_params``, float32, twice a layer)."""
    d = dims(cfg)
    return attention_params(cfg) + 3 * d["hidden"] * d["ffn"]


def sparse_layer_params(cfg: dict) -> int:
    return (attention_params(cfg)
            + dims(cfg)["experts"] * shapes_moe.expert_params(cfg)
            + shapes_moe.shared_params(cfg) + shapes_moe.router_params(cfg))


def sparse_layer_active_params(cfg: dict) -> int:
    """What one token multiplies in a sparse layer, the mix apart."""
    return (attention_params(cfg)
            + dims(cfg)["top_k"] * shapes_moe.expert_params(cfg)
            + shapes_moe.shared_params(cfg) + shapes_moe.router_params(cfg))


def param_count(cfg: dict) -> int:
    """Every matrix of the model, the mix's float32 ones included."""
    d = dims(cfg)
    return (d["dense"] * dense_layer_params(cfg)
            + d["sparse"] * sparse_layer_params(cfg)
            + shapes_moe.embedding_params(cfg)
            + d["sublayers"] * mix_params(cfg))


def weight_bytes(cfg: dict) -> int:
    """As served: bf16, the router and the mix float32."""
    d = dims(cfg)
    f32 = d["sparse"] * shapes_moe.router_params(cfg) \
        + d["sublayers"] * mix_params(cfg)
    return (param_count(cfg) - f32) * BF16 + f32 * F32


def step_fixed_weight_bytes(cfg: dict) -> int:
    """Weights every decode step reads whatever its rows chose: attention
    of every layer, the dense layers' FFN, the shared experts, the router
    and the mix (float32), the logits matrix."""
    d = dims(cfg)
    bf16 = (d["layers"] * attention_params(cfg)
            + d["dense"] * 3 * d["hidden"] * d["ffn"]
            + d["sparse"] * shapes_moe.shared_params(cfg)
            + d["vocab"] * d["hidden"])
    return bf16 * BF16 + (d["sparse"] * shapes_moe.router_params(cfg)
                          + d["sublayers"] * mix_params(cfg)) * F32


def stream_bytes_per_token(cfg: dict) -> int:
    """What the mix of ONE sublayer moves of a token's streams: read once
    (``hc_pre``), read and written once (``hc_post``)."""
    d = dims(cfg)
    return 0 if d["streams"] == 1 else 3 * d["streams"] * d["hidden"] * BF16


def mix(cfg: dict, tokens: float, calls: float) -> Dict[str, float]:
    """The stream mix of every sublayer for ``tokens`` tokens in all over
    ``calls`` program steps (a decode step, a prefill chunk): the streams a
    token, ``phi`` a call. FLOPs: ``x~ phi``, ``H_pre x``, ``H_res x`` and
    ``H_post^T F``."""
    d = dims(cfg)
    n, h = d["streams"], d["hidden"]
    if n == 1:
        return {"flops": 0.0, "bytes": 0.0}
    per_token = 2 * n * h * d["mix_columns"] + 2 * n * h + 2 * (n * n + n) * h
    return {
        "flops": tokens * d["sublayers"] * per_token,
        "bytes": d["sublayers"] * (tokens * stream_bytes_per_token(cfg)
                                   + calls * mix_params(cfg) * F32),
    }


def decode_step(cfg: dict, rows: float, context: float,
                experts_touched: float) -> Dict[str, float]:
    """One decode step of ``rows`` sequences at a mean ``context`` whose
    sparse layers each touched ``experts_touched`` distinct experts."""
    d = dims(cfg)
    per_row = (d["dense"] * dense_layer_params(cfg)
               + d["sparse"] * sparse_layer_active_params(cfg)
               + d["vocab"] * d["hidden"])
    attn = shapes_moe.mla_decode(cfg, rows, context)
    streams = mix(cfg, rows, 0)            # phi is among the fixed weights
    flops = rows * 2 * per_row + attn["flops"] + streams["flops"]
    byts = (step_fixed_weight_bytes(cfg)
            + d["sparse"] * experts_touched * shapes_moe.expert_params(cfg)
            * BF16
            + attn["bytes"] + rows * shapes_moe.pool_bytes_per_token(cfg)
            + streams["bytes"])
    return {"flops": flops, "bytes": byts}
