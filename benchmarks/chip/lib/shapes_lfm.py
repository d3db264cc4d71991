"""Operations and bytes of an LFM2-MoE-shaped decoder (gated short
convolutions with two tokens of state a sequence in most layers, rotary GQA
with a per-head norm in the others, leading dense FFNs, then sigmoid-routed
experts with no shared one, a tied head) from its HF ``config.json`` alone:
``lib/shapes.py``'s arithmetic for the architecture that file cannot count
(it reckons every layer a dense llama layer with K/V) and ``lib/
shapes_moe.py`` cannot read (its attention is latent).

Counted, as there: matrix products (2 FLOPs a multiply-add) and what must
cross HBM once. Not counted: norms, rotary, softmax, sigmoid, top-k, the
sort of the (token, expert) pairs, activations, sampling -- so a share errs
low, never high. What is particular here:

  * a step reads the routed experts that its rows CHOSE, not all of them:
    ``experts_touched`` is a number the program counts
    (``pstpu:moe_experts_touched_total`` / ``pstpu:moe_layer_calls_total``),
    never ``num_experts``;
  * a LIVE row's conv state (``conv_L_cache`` - 1 tokens of ``hidden_size``
    channels, bf16) is read once and written once a conv layer a step; a
    row that takes no token moves none;
  * K/V is that of the ATTENTION layers only.
"""

from typing import Dict

BF16, F32 = 2, 4


def dims(cfg: dict) -> Dict[str, int]:
    heads = cfg["num_attention_heads"]
    head_dim = cfg.get("head_dim") or cfg["hidden_size"] // heads
    kinds = cfg["layer_types"]
    dense = cfg.get("num_dense_layers", 0)
    return {
        "hidden": cfg["hidden_size"], "vocab": cfg["vocab_size"],
        "head_dim": head_dim, "q": heads * head_dim,
        "kv": cfg.get("num_key_value_heads", heads) * head_dim,
        "taps": cfg.get("conv_L_cache", 3),
        "conv": sum(k == "conv" for k in kinds),
        "attention": sum(k == "full_attention" for k in kinds),
        "ffn": cfg["intermediate_size"],
        "expert_ffn": cfg["moe_intermediate_size"],
        "experts": cfg["num_experts"],
        "top_k": cfg["num_experts_per_tok"],
        "dense": dense, "sparse": cfg["num_hidden_layers"] - dense,
        "layers": cfg["num_hidden_layers"],
        "tied": bool(cfg.get("tie_word_embeddings", True)),
    }


def conv_params(cfg: dict) -> int:
    """A gated short convolution: in_proj (B | C | x), the taps, out_proj."""
    d = dims(cfg)
    return 4 * d["hidden"] * d["hidden"] + d["taps"] * d["hidden"]


def attention_params(cfg: dict) -> int:
    """W_q, W_k, W_v, W_o (the per-head norms' two weights aside)."""
    d = dims(cfg)
    return d["hidden"] * (d["q"] + 2 * d["kv"]) + d["q"] * d["hidden"]


def expert_params(cfg: dict) -> int:
    """One routed expert: gate, up, down."""
    d = dims(cfg)
    return 3 * d["hidden"] * d["expert_ffn"]


def router_params(cfg: dict) -> int:
    d = dims(cfg)
    return d["hidden"] * d["experts"]


def dense_ffn_params(cfg: dict) -> int:
    d = dims(cfg)
    return 3 * d["hidden"] * d["ffn"]


def embedding_params(cfg: dict) -> int:
    d = dims(cfg)
    return d["vocab"] * d["hidden"] * (1 if d["tied"] else 2)


def small_params(cfg: dict) -> int:
    """What the matrices' count leaves aside: two norms a layer and the one
    behind the last, the per-head norms' weights, the router's bias."""
    d = dims(cfg)
    return (2 * d["layers"] + 1) * d["hidden"] \
        + d["attention"] * 2 * d["head_dim"] + d["sparse"] * d["experts"]


def matrix_params(cfg: dict) -> int:
    """Every matrix, the taps and the table: ISSUE 44's hand count."""
    d = dims(cfg)
    return (d["sparse"] * (d["experts"] * expert_params(cfg)
                           + router_params(cfg))
            + d["dense"] * dense_ffn_params(cfg)
            + d["conv"] * conv_params(cfg)
            + d["attention"] * attention_params(cfg)
            + embedding_params(cfg))


def param_count(cfg: dict) -> int:
    """Every parameter of the served tree."""
    return matrix_params(cfg) + small_params(cfg)


def conv_state_bytes_per_seq(cfg: dict) -> int:
    """What one sequence holds whole, whatever its length."""
    d = dims(cfg)
    return d["conv"] * (d["taps"] - 1) * d["hidden"] * BF16


def kv_bytes_per_token(cfg: dict) -> int:
    """Keys and values of the ATTENTION layers only."""
    d = dims(cfg)
    return 2 * d["attention"] * d["kv"] * BF16


def step_fixed_weight_bytes(cfg: dict) -> int:
    """Weights every decode step reads whatever its rows chose: every
    operator, the dense layers' FFN, the router (float32), the logits
    matrix (the embedding lookup reads a row a token, not the table)."""
    d = dims(cfg)
    bf16 = (d["conv"] * conv_params(cfg)
            + d["attention"] * attention_params(cfg)
            + d["dense"] * dense_ffn_params(cfg)
            + d["vocab"] * d["hidden"])
    return bf16 * BF16 + d["sparse"] * router_params(cfg) * F32


def active_params(cfg: dict) -> int:
    """What one token multiplies through every layer and the head."""
    d = dims(cfg)
    return (d["conv"] * conv_params(cfg)
            + d["attention"] * attention_params(cfg)
            + d["dense"] * dense_ffn_params(cfg)
            + d["sparse"] * (d["top_k"] * expert_params(cfg)
                             + router_params(cfg))
            + d["vocab"] * d["hidden"])


def decode_step(cfg: dict, rows: float, context: float,
                experts_touched: float) -> Dict[str, float]:
    """One decode step of ``rows`` LIVE sequences at a mean ``context``
    whose sparse layers each touched ``experts_touched`` distinct experts:
    the fixed weights once, the touched experts' matrices, each row's conv
    state read and written a conv layer, the attention layers' K/V over the
    context."""
    d = dims(cfg)
    flops = rows * (2 * active_params(cfg)
                    + 4 * d["attention"] * d["q"] * context)
    byts = (step_fixed_weight_bytes(cfg)
            + d["sparse"] * experts_touched * expert_params(cfg) * BF16
            + rows * 2 * conv_state_bytes_per_seq(cfg)
            + rows * (context + 1) * kv_bytes_per_token(cfg))
    return {"flops": flops, "bytes": byts}


def moe_gmm(cfg: dict, calls: float, pairs: float,
            experts_touched: float) -> Dict[str, float]:
    """The grouped matmuls (gate and up as one, then down) of ``calls``
    sparse-layer calls that computed ``pairs`` (token, expert) pairs in all
    and touched ``experts_touched`` distinct experts a call: the touched
    experts' matrices once a call, the pairs' rows in (bf16) and out
    (float32) of both products (``lib/shapes_moe.py:moe_gmm``'s count)."""
    d = dims(cfg)
    f, h = d["expert_ffn"], d["hidden"]
    return {
        "flops": pairs * 2 * expert_params(cfg),
        "bytes": calls * experts_touched * expert_params(cfg) * BF16
        + pairs * ((h + f) * BF16 + (2 * f + h) * F32),
    }


def sconv_step(cfg: dict, row_steps: float, steps: float
               ) -> Dict[str, float]:
    """The gated convolution of ``row_steps`` live row-steps in ``steps``
    steps through every conv layer: B, C and x in and y out (bf16), the
    row's state read once and written once, the taps once a layer a step.
    Per channel: B * x, the taps' multiply-adds, C * c."""
    d = dims(cfg)
    h, taps = d["hidden"], d["taps"]
    return {
        "flops": row_steps * d["conv"] * h * (2 + 2 * taps),
        "bytes": d["conv"] * BF16 * (
            row_steps * h * (4 + 2 * (taps - 1)) + steps * taps * h),
    }


def prefill(cfg: dict, new_tokens: float, context: float,
            rows: float) -> Dict[str, float]:
    """Prefill of ``new_tokens`` prompt tokens in all attending a mean
    ``context`` in the attention layers, one logits row a sequence."""
    d = dims(cfg)
    per_token = active_params(cfg) - d["vocab"] * d["hidden"]
    attn = 4 * d["attention"] * d["q"] * context
    return {"flops": new_tokens * (2 * per_token + attn)
            + rows * 2 * d["vocab"] * d["hidden"]}


def expected_experts_touched(cfg: dict, rows: float) -> float:
    """Distinct experts ``rows`` tokens touch if every token's choice were
    uniform and independent: E (1 - (1 - k/E)^rows)."""
    d = dims(cfg)
    return d["experts"] * (1.0 - (1.0 - d["top_k"] / d["experts"]) ** rows)
