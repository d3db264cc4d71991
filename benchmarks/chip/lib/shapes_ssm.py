"""Operations and bytes of a state-space hybrid decoder (Mamba-2 layers with
a float32 state and a conv state a sequence, a GQA layer somewhere in every
period, a fused gated FFN in every layer, a tied head) from its HF
``config.json`` alone: ``lib/shapes.py``'s arithmetic for the architecture
that file cannot count (it reckons every layer a dense llama layer with
K/V).

Counted, as there: matrix products (2 FLOPs a multiply-add) and what must
cross HBM once. Not counted: norms, the convolution's few multiplies, gates,
softmax, activations, sampling -- so a share errs low, never high. The
scan's state of a row is read once and written once a state-space layer a
step (float32, whatever the activations); its conv state likewise (bf16).
The chunkwise scan is counted at the PUBLISHED ``mamba_chunk_size``,
whatever chunk an implementation takes, and only the causal half of a
chunk's products: the same work whatever implements it.
"""

from typing import Dict

BF16, F32 = 2, 4


def dims(cfg: dict) -> Dict[str, int]:
    heads = cfg["num_attention_heads"]
    head_dim = cfg.get("head_dim") or cfg["hidden_size"] // heads
    kinds = cfg["layer_types"]
    mh, p, n = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    return {
        "hidden": cfg["hidden_size"], "ffn": cfg["shared_intermediate_size"],
        "vocab": cfg["vocab_size"],
        "mamba": sum(k == "mamba" for k in kinds),
        "attention": sum(k == "attention" for k in kinds),
        "q": heads * head_dim,
        "kv": cfg.get("num_key_value_heads", heads) * head_dim,
        "mh": mh, "p": p, "n": n, "inner": mh * p,
        "conv_width": cfg["mamba_d_conv"],
        "conv_channels": mh * p + 2 * n,
        "chunk": cfg["mamba_chunk_size"],
        "tied": bool(cfg.get("tie_word_embeddings", False)),
    }


def ffn_params(cfg: dict) -> int:
    """The fused gate | up in-projection and the out-projection."""
    d = dims(cfg)
    return 3 * d["hidden"] * d["ffn"]


def mamba_layer_params(cfg: dict) -> Dict[str, int]:
    """A state-space layer's parameters by what they are stored in:
    ``bf16`` (in_proj z | xBC | dt, the conv and its bias, the gated norm,
    out_proj, the FFN, the two block norms) and ``f32`` (A_log, D,
    dt_bias)."""
    d = dims(cfg)
    conv = d["conv_channels"] * d["conv_width"] + (
        d["conv_channels"] if cfg.get("mamba_conv_bias", True) else 0)
    return {
        "bf16": d["hidden"] * (d["inner"] + d["conv_channels"] + d["mh"])
        + conv + d["inner"] + d["inner"] * d["hidden"] + ffn_params(cfg)
        + 2 * d["hidden"],
        "f32": 3 * d["mh"],
    }


def attention_layer_params(cfg: dict) -> int:
    d = dims(cfg)
    return (d["hidden"] * (d["q"] + 2 * d["kv"]) + d["q"] * d["hidden"]
            + ffn_params(cfg) + 2 * d["hidden"])


def param_count(cfg: dict) -> int:
    """Every parameter of the served tree: the layers, the table (once
    where the head is tied) and the final norm."""
    d = dims(cfg)
    return (d["mamba"] * sum(mamba_layer_params(cfg).values())
            + d["attention"] * attention_layer_params(cfg)
            + d["vocab"] * d["hidden"] * (1 if d["tied"] else 2)
            + d["hidden"])


def step_weight_bytes(cfg: dict) -> int:
    """Weights one program step reads: every layer and the logits matrix
    (the embedding lookup reads a row per token, not the table)."""
    d = dims(cfg)
    m = mamba_layer_params(cfg)
    return (d["mamba"] * (m["bf16"] * BF16 + m["f32"] * F32)
            + (d["attention"] * attention_layer_params(cfg)
               + d["vocab"] * d["hidden"] + d["hidden"]) * BF16)


def ssm_bytes_per_seq_layer(cfg: dict) -> int:
    d = dims(cfg)
    return d["mh"] * d["p"] * d["n"] * F32


def conv_bytes_per_seq_layer(cfg: dict) -> int:
    d = dims(cfg)
    return (d["conv_width"] - 1) * d["conv_channels"] * BF16


def state_bytes_per_seq(cfg: dict) -> int:
    """What one sequence holds whole, whatever its length."""
    return dims(cfg)["mamba"] * (ssm_bytes_per_seq_layer(cfg)
                                 + conv_bytes_per_seq_layer(cfg))


def kv_bytes_per_token(cfg: dict) -> int:
    """Keys and values of the ATTENTION layers only."""
    d = dims(cfg)
    return 2 * d["attention"] * d["kv"] * BF16


def scan_flops_per_row_layer(cfg: dict) -> int:
    """One token of the recurrence a layer: decay, the outer product's
    multiply-add and the contraction with C, an element of the state."""
    d = dims(cfg)
    return 6 * d["mh"] * d["p"] * d["n"]


def decode_step(cfg: dict, rows: float, context: float) -> Dict[str, float]:
    """One decode step of ``rows`` LIVE sequences at a mean ``context``:
    the weights once, each row's state and conv state read and written a
    state-space layer, the attention layers' K/V over the context."""
    d = dims(cfg)
    m = mamba_layer_params(cfg)
    matrices = d["mamba"] * (m["bf16"] + m["f32"]) \
        + d["attention"] * attention_layer_params(cfg)
    flops = rows * (2 * matrices + 2 * d["vocab"] * d["hidden"]
                    + 4 * d["attention"] * d["q"] * context
                    + d["mamba"] * scan_flops_per_row_layer(cfg))
    byts = (step_weight_bytes(cfg)
            + rows * 2 * state_bytes_per_seq(cfg)
            + rows * (context + 1) * kv_bytes_per_token(cfg))
    return {"flops": flops, "bytes": byts}


def ssd_step(cfg: dict, row_steps: float) -> Dict[str, float]:
    """The scan of ``row_steps`` live row-steps through every state-space
    layer: the state read once and written once (the conv state moves
    outside the scan's scope and is not counted here)."""
    d = dims(cfg)
    return {
        "flops": row_steps * d["mamba"] * scan_flops_per_row_layer(cfg),
        "bytes": row_steps * d["mamba"] * 2 * ssm_bytes_per_seq_layer(cfg),
    }


def ssd_chunk(cfg: dict, tokens: float) -> Dict[str, float]:
    """The chunkwise scan over ``tokens`` prompt tokens through every
    state-space layer, in chunks of Q = ``mamba_chunk_size``. Per token:
    its row of C B^T against the Q / 2 tokens before it in its chunk (2 N
    each, once for all heads); per head the masked product with dt o X
    over the same Q / 2 (2 P each), the chunk's contribution to the state
    and what the state before the chunk gives the token (2 P N each).
    Bytes: x in and y out, B, C and dt, float32, once."""
    d = dims(cfg)
    q, p, n, mh = d["chunk"], d["p"], d["n"], d["mh"]
    per_token = q * n + mh * (q * p + 4 * p * n)
    return {
        "flops": tokens * d["mamba"] * per_token,
        "bytes": tokens * d["mamba"] * (2 * d["inner"] + 2 * n + mh) * F32,
    }
