"""Operations and bytes of a DeepSeek-V3-shaped decoder (multi-head latent
attention over one cached row a token, sparse experts beside shared ones,
leading dense layers) from its HF ``config.json`` alone: ``lib/shapes.py``'s
arithmetic for the architecture that file cannot count (it reckons every
layer a dense llama layer with keys and values of heads).

Counted, as there: matrix products (2 FLOPs a multiply-add) and what must
cross HBM once. Not counted: norms, rotary, softmax, sigmoid, top-k, the
sort of the (token, expert) pairs, activations, sampling -- so a share errs
low, never high. What is particular here:

  * a step reads the routed experts that its rows CHOSE, not all of them:
    ``experts_touched`` is a number the program counts
    (``pstpu:moe_experts_touched_total`` / ``pstpu:moe_layer_calls_total``),
    never ``n_routed_experts``;
  * a cached token is ONE row a layer, ``kv_lora_rank + qk_rope_head_dim``
    values, stored padded to whole 128-lane tiles (``deployment.json``:
    576 -> 640). A page crosses HBM as it is stored, padding included, so
    the attention's bytes are the POOL row's; ``latent_bytes_per_token``
    gives the payload beside it;
  * the decode kernel's products are the absorbed form's: every head's
    query against the row (``rank + rope`` wide) and its probabilities
    against the row's first ``rank`` values.
"""

from typing import Dict

BF16, F32 = 2, 4
LANES = 128


def dims(cfg: dict) -> Dict[str, int]:
    dense = cfg.get("first_k_dense_replace", 0)
    rank, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    return {
        "hidden": cfg["hidden_size"], "vocab": cfg["vocab_size"],
        "heads": cfg["num_attention_heads"],
        "nope": cfg["qk_nope_head_dim"], "rope": rope,
        "v": cfg["v_head_dim"], "rank": rank,
        "row": rank + rope,
        "pool_row": -(-(rank + rope) // LANES) * LANES,
        "ffn": cfg["intermediate_size"],
        "expert_ffn": cfg["moe_intermediate_size"],
        "experts": cfg["n_routed_experts"],
        "top_k": cfg["num_experts_per_tok"],
        "shared": cfg.get("n_shared_experts", 0),
        "dense": dense, "sparse": cfg["num_hidden_layers"] - dense,
        "layers": cfg["num_hidden_layers"],
        "tied": bool(cfg.get("tie_word_embeddings", False)),
    }


def attention_params(cfg: dict) -> int:
    """W_q, W_kva (compressed row and shared rotary key), W_kvb (keys' and
    values' halves of every head), W_o."""
    d = dims(cfg)
    return (d["hidden"] * d["heads"] * (d["nope"] + d["rope"])
            + d["hidden"] * d["row"]
            + d["rank"] * d["heads"] * (d["nope"] + d["v"])
            + d["heads"] * d["v"] * d["hidden"])


def expert_params(cfg: dict) -> int:
    """One routed expert: gate, up, down."""
    d = dims(cfg)
    return 3 * d["hidden"] * d["expert_ffn"]


def shared_params(cfg: dict) -> int:
    """The shared experts, one gated FFN of their summed width."""
    return dims(cfg)["shared"] * expert_params(cfg)


def router_params(cfg: dict) -> int:
    d = dims(cfg)
    return d["hidden"] * d["experts"]


def dense_layer_params(cfg: dict) -> int:
    d = dims(cfg)
    return attention_params(cfg) + 3 * d["hidden"] * d["ffn"]


def sparse_layer_params(cfg: dict) -> int:
    return (attention_params(cfg) + dims(cfg)["experts"] * expert_params(cfg)
            + shared_params(cfg) + router_params(cfg))


def sparse_layer_active_params(cfg: dict) -> int:
    """What one token multiplies in a sparse layer."""
    return (attention_params(cfg) + dims(cfg)["top_k"] * expert_params(cfg)
            + shared_params(cfg) + router_params(cfg))


def embedding_params(cfg: dict) -> int:
    d = dims(cfg)
    return d["vocab"] * d["hidden"] * (1 if d["tied"] else 2)


def param_count(cfg: dict) -> int:
    d = dims(cfg)
    return (d["dense"] * dense_layer_params(cfg)
            + d["sparse"] * sparse_layer_params(cfg) + embedding_params(cfg))


def latent_bytes_per_token(cfg: dict) -> int:
    """Payload a token caches over every layer: the row, bf16."""
    d = dims(cfg)
    return d["layers"] * d["row"] * BF16


def pool_bytes_per_token(cfg: dict) -> int:
    """What the pool keeps for it: the row padded to whole lane tiles."""
    d = dims(cfg)
    return d["layers"] * d["pool_row"] * BF16


def step_fixed_weight_bytes(cfg: dict) -> int:
    """Weights every decode step reads whatever its rows chose: attention
    of every layer, the dense layers' FFN, the shared experts, the router
    (float32), the logits matrix (the embedding lookup reads a row a
    token, not the table)."""
    d = dims(cfg)
    bf16 = (d["layers"] * attention_params(cfg)
            + d["dense"] * 3 * d["hidden"] * d["ffn"]
            + d["sparse"] * shared_params(cfg)
            + d["vocab"] * d["hidden"])
    return bf16 * BF16 + d["sparse"] * router_params(cfg) * F32


def decode_step(cfg: dict, rows: float, context: float,
                experts_touched: float) -> Dict[str, float]:
    """One decode step of ``rows`` sequences at a mean ``context`` whose
    sparse layers each touched ``experts_touched`` distinct experts."""
    d = dims(cfg)
    per_row = (d["dense"] * dense_layer_params(cfg)
               + d["sparse"] * sparse_layer_active_params(cfg)
               + d["vocab"] * d["hidden"])
    attn = mla_decode(cfg, rows, context)
    flops = rows * 2 * per_row + attn["flops"]
    byts = (step_fixed_weight_bytes(cfg)
            + d["sparse"] * experts_touched * expert_params(cfg) * BF16
            + attn["bytes"] + rows * pool_bytes_per_token(cfg))
    return {"flops": flops, "bytes": byts}


def moe_gmm(cfg: dict, calls: float, pairs: float,
            experts_touched: float) -> Dict[str, float]:
    """The grouped matmuls (gate and up as one, then down) of ``calls``
    sparse-layer calls that computed ``pairs`` (token, expert) pairs in all
    and touched ``experts_touched`` distinct experts a call: the touched
    experts' matrices once a call, the pairs' rows in (bf16) and out
    (float32) of both products."""
    d = dims(cfg)
    f, h = d["expert_ffn"], d["hidden"]
    return {
        "flops": pairs * 2 * expert_params(cfg),
        "bytes": calls * experts_touched * expert_params(cfg) * BF16
        + pairs * ((h + f) * BF16 + (2 * f + h) * F32),
    }


def mla_decode(cfg: dict, row_steps: float, context: float
               ) -> Dict[str, float]:
    """The latent decode kernel over every layer for ``row_steps`` rows at
    a mean ``context``: a cached token's pool row once, every head's
    query against it and its probabilities against its values."""
    d = dims(cfg)
    tokens = row_steps * d["layers"] * context
    return {
        "flops": tokens * 2 * d["heads"] * (d["row"] + d["rank"]),
        "bytes": tokens * d["pool_row"] * BF16,
    }


def prefill(cfg: dict, new_tokens: float, context: float,
            rows: float) -> Dict[str, float]:
    """Prefill of ``new_tokens`` prompt tokens in all attending a mean
    ``context``, one logits row a sequence (the expanded form's FLOPs: a
    head's keys are nope + rope wide, its values v)."""
    d = dims(cfg)
    per_token = (d["dense"] * dense_layer_params(cfg)
                 + d["sparse"] * sparse_layer_active_params(cfg))
    attn = 2 * d["layers"] * d["heads"] * (
        d["nope"] + d["rope"] + d["v"]) * context
    return {"flops": new_tokens * (2 * per_token + attn)
            + rows * 2 * d["vocab"] * d["hidden"]}


def expected_experts_touched(cfg: dict, rows: float) -> float:
    """Distinct experts ``rows`` tokens touch if every token's choice were
    uniform and independent: E (1 - (1 - k/E)^rows)."""
    d = dims(cfg)
    return d["experts"] * (1.0 - (1.0 - d["top_k"] / d["experts"]) ** rows)
