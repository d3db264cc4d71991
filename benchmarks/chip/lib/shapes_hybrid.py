"""Operations and bytes of a hybrid decoder (Gated DeltaNet layers with
per-sequence recurrent state, a full-attention layer closing each period)
from its HF ``config.json`` alone: ``lib/shapes.py``'s arithmetic for the
architecture that file cannot count (it reckons every layer a dense llama
layer with K/V).

Counted, as there: matrix products (2 FLOPs a multiply-add) and what must
cross HBM once. Not counted: norms, the convolution's few multiplies, gates,
softmax, activations, sampling -- so a share errs low, never high. The
recurrent state of a row is read once and written once a linear layer a
step (float32, whatever the activations); its conv state likewise (bf16).
"""

from typing import Dict

BF16, F32 = 2, 4
CHUNK = 64   # tokens the chunkwise prefill form solves together


def dims(cfg: dict) -> Dict[str, int]:
    heads = cfg["num_attention_heads"]
    head_dim = cfg.get("head_dim") or cfg["hidden_size"] // heads
    kinds = cfg["layer_types"]
    lh = cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    return {
        "hidden": cfg["hidden_size"], "ffn": cfg["intermediate_size"],
        "vocab": cfg["vocab_size"],
        "linear": sum(k == "linear_attention" for k in kinds),
        "full": sum(k == "full_attention" for k in kinds),
        "q": heads * head_dim,
        "kv": cfg.get("num_key_value_heads", heads) * head_dim,
        "lh": lh, "dk": dk, "dv": dv,
        "conv_width": cfg["linear_conv_kernel_dim"],
        "conv_channels": lh * (2 * dk + dv),
        "tied": bool(cfg.get("tie_word_embeddings", False)),
    }


def ffn_params(cfg: dict) -> int:
    d = dims(cfg)
    return 3 * d["hidden"] * d["ffn"]


def linear_layer_params(cfg: dict) -> int:
    """Matrix parameters of a linear-attention layer: the q, k, v, z, b and
    a in-projections, the depthwise conv, the out-projection, the FFN."""
    d = dims(cfg)
    return (d["hidden"] * (d["conv_channels"] + d["lh"] * d["dv"]
                           + 2 * d["lh"])
            + d["conv_channels"] * d["conv_width"]
            + d["lh"] * d["dv"] * d["hidden"] + ffn_params(cfg))


def full_layer_params(cfg: dict) -> int:
    d = dims(cfg)
    return (d["hidden"] * (d["q"] + 2 * d["kv"]) + d["q"] * d["hidden"]
            + ffn_params(cfg))


def layer_params_total(cfg: dict) -> int:
    d = dims(cfg)
    return (d["linear"] * linear_layer_params(cfg)
            + d["full"] * full_layer_params(cfg))


def param_count(cfg: dict) -> int:
    d = dims(cfg)
    return layer_params_total(cfg) \
        + d["vocab"] * d["hidden"] * (1 if d["tied"] else 2)


def step_weight_bytes(cfg: dict) -> int:
    """Weights one program step reads: every layer and the logits matrix
    (the embedding lookup reads a row per token, not the table)."""
    d = dims(cfg)
    return (layer_params_total(cfg) + d["vocab"] * d["hidden"]) * BF16


def recurrent_bytes_per_seq_layer(cfg: dict) -> int:
    d = dims(cfg)
    return d["lh"] * d["dk"] * d["dv"] * F32


def conv_bytes_per_seq_layer(cfg: dict) -> int:
    d = dims(cfg)
    return (d["conv_width"] - 1) * d["conv_channels"] * BF16


def state_bytes_per_seq(cfg: dict) -> int:
    """What one sequence holds whole, whatever its length."""
    return dims(cfg)["linear"] * (recurrent_bytes_per_seq_layer(cfg)
                                  + conv_bytes_per_seq_layer(cfg))


def state_step_bytes_per_row_layer(cfg: dict) -> int:
    """A row's state through one linear layer for one step: read once,
    written once."""
    return 2 * (recurrent_bytes_per_seq_layer(cfg)
                + conv_bytes_per_seq_layer(cfg))


def kv_bytes_per_token(cfg: dict) -> int:
    """Keys and values of the FULL layers only."""
    d = dims(cfg)
    return 2 * d["full"] * d["kv"] * BF16


def decode_step(cfg: dict, rows: float, context: float) -> Dict[str, float]:
    """One decode step of ``rows`` sequences at a mean ``context``."""
    d = dims(cfg)
    flops = rows * (2 * layer_params_total(cfg)
                    + 2 * d["vocab"] * d["hidden"]
                    + 4 * d["full"] * d["q"] * context
                    + 6 * d["linear"] * d["lh"] * d["dk"] * d["dv"])
    byts = (step_weight_bytes(cfg)
            + rows * d["linear"] * state_step_bytes_per_row_layer(cfg)
            + rows * (context + 1) * kv_bytes_per_token(cfg))
    return {"flops": flops, "bytes": byts}


def gdn_step(cfg: dict, row_steps: float) -> Dict[str, float]:
    """The recurrence and the conv of ``row_steps`` row-steps through every
    linear layer: the state's bytes (three multiply-adds an element are
    far under them)."""
    d = dims(cfg)
    return {
        "flops": row_steps * d["linear"] * 6 * d["lh"] * d["dk"] * d["dv"],
        "bytes": row_steps * d["linear"]
        * state_step_bytes_per_row_layer(cfg),
    }


def gdn_chunk(cfg: dict, tokens: float) -> Dict[str, float]:
    """The chunkwise recurrence over ``tokens`` prompt tokens through every
    linear layer. Per token and head, in chunks of C: k_beta k^T, q k^T
    and the product that carries k into the state's decay (2 C dk each),
    the unit-triangular solve (2/3 C^2), T v_beta and (q k^T) v_new
    (2 C dv each), and the three products with the dk x dv state (2 dk dv
    each). Bytes: q, k, v in and o out, float32, once."""
    d = dims(cfg)
    c, dk, dv = CHUNK, d["dk"], d["dv"]
    per_head = 6 * c * dk + 4 * c * dv + 6 * dk * dv + 2 * c * c / 3
    return {
        "flops": tokens * d["linear"] * d["lh"] * per_head,
        "bytes": tokens * d["linear"] * d["lh"] * (2 * dk + 2 * dv) * F32,
    }
