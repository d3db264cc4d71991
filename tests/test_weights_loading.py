"""HF checkpoint loading parity: our forward on a loaded checkpoint must
match transformers' reference implementation logits (CPU, tiny random
models saved with save_pretrained)."""

import numpy as np
import pytest

import jax.numpy as jnp


def _forward_logits(model_dir, token_ids):
    """Run our model's window forward (single chunk, no history); [T, V]."""
    from production_stack_tpu.models import get_model
    from production_stack_tpu.models.config import ModelConfig
    from production_stack_tpu.models.weights import load_hf_params

    cfg = ModelConfig.from_pretrained_dir(model_dir)
    model = get_model(cfg)
    init_fn, forward, logits_fn = (
        model.init_params, model.forward, model.compute_logits)
    params = load_hf_params(cfg, model_dir, jnp.float32)

    t = len(token_ids)
    ids = jnp.asarray([token_ids], jnp.int32)
    positions = jnp.arange(t, dtype=jnp.int32)[None]
    chunk_lens = jnp.asarray([t], jnp.int32)
    # (a family with sparse experts returns its counters last)
    hidden, *_ = forward(params, cfg, ids, positions, chunk_lens)
    return np.asarray(logits_fn(params, cfg, hidden[0]))


@pytest.mark.parametrize("family", ["llama", "opt"])
def test_hf_checkpoint_forward_parity(tmp_path, family):
    torch = pytest.importorskip("torch")
    import transformers

    if family == "llama":
        hf_cfg = transformers.LlamaConfig(
            vocab_size=128, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=128,
            rms_norm_eps=1e-5, tie_word_embeddings=False,
        )
        model = transformers.LlamaForCausalLM(hf_cfg)
    else:
        hf_cfg = transformers.OPTConfig(
            vocab_size=128, hidden_size=64, ffn_dim=128,
            num_hidden_layers=2, num_attention_heads=4,
            max_position_embeddings=128, do_layer_norm_before=True,
            word_embed_proj_dim=64,
        )
        model = transformers.OPTForCausalLM(hf_cfg)
    model = model.eval().to(torch.float32)
    model_dir = str(tmp_path / family)
    model.save_pretrained(model_dir, safe_serialization=True)

    token_ids = [3, 17, 42, 99, 5, 61, 7]
    with torch.no_grad():
        ref = model(torch.tensor([token_ids])).logits[0].numpy()

    ours = _forward_logits(model_dir, token_ids)
    np.testing.assert_allclose(ours, ref, rtol=2e-3, atol=2e-3)


def test_deepseek_v3_checkpoint_loads_into_stacked_leaves(tmp_path):
    """A tiny ``deepseek_v3`` safetensors checkpoint as ``transformers``
    writes it — one tensor per expert and matrix, the shared experts,
    ``kv_a_proj_with_mqa``, ``kv_a_layernorm``, ``kv_b_proj`` whole, the
    gate's ``e_score_correction_bias`` — loads into the stacks the module
    computes with ([n_sparse, E, ...] experts, ``kv_b_proj`` as its two
    halves per head, the router in float32) and gives the logits of HF's
    own modeling code AND of the plain reference."""
    torch = pytest.importorskip("torch")
    import os
    import sys

    import transformers

    from production_stack_tpu.models.config import ModelConfig
    from production_stack_tpu.models.weights import load_hf_params

    hf_cfg = transformers.DeepseekV3Config(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        moe_intermediate_size=32, num_hidden_layers=4,
        num_attention_heads=4, num_key_value_heads=4, n_shared_experts=1,
        n_routed_experts=16, num_experts_per_tok=3, n_group=1, topk_group=1,
        routed_scaling_factor=2.448, kv_lora_rank=32, q_lora_rank=None,
        qk_rope_head_dim=8, qk_nope_head_dim=16, v_head_dim=16,
        first_k_dense_replace=1, norm_topk_prob=True,
        max_position_embeddings=128, rms_norm_eps=1e-6, rope_theta=10000.0,
        rope_scaling=None, rope_interleave=True, attention_bias=False,
        tie_word_embeddings=False,
    )
    torch.manual_seed(0)
    model = transformers.DeepseekV3ForCausalLM(hf_cfg)
    with torch.no_grad():
        for layer in model.model.layers[1:]:
            gate = layer.mlp.gate
            # Scores that spread, and a bias that changes choices.
            gate.weight.copy_(torch.randn_like(gate.weight) * 0.5)
            gate.e_score_correction_bias.copy_(
                0.05 * torch.randn_like(gate.e_score_correction_bias))
    model = model.eval().to(torch.float32)
    model_dir = str(tmp_path / "deepseek_v3")
    model.save_pretrained(model_dir, safe_serialization=True)

    token_ids = [3, 17, 42, 99, 5, 61, 7, 88, 120, 9, 14]
    with torch.no_grad():
        want = model(torch.tensor([token_ids])).logits[0].numpy()

    cfg = ModelConfig.from_pretrained_dir(model_dir)
    assert cfg.arch == "deepseek_v3"
    params = load_hf_params(cfg, model_dir, jnp.bfloat16)
    sparse = params["layers"]["sparse"]
    assert sparse["w_gate_up"].shape == (3, 16, 64, 64)
    assert sparse["we_down"].shape == (3, 16, 32, 64)
    assert sparse["w_uk"].shape == (3, 4, 16, 32)
    assert sparse["w_uv"].shape == (3, 4, 32, 16)
    assert params["layers"]["dense"]["w_gate"].shape == (1, 64, 128)
    # The router stays float32 whatever the engine's dtype.
    assert sparse["w_router"].dtype == sparse["router_bias"].dtype \
        == jnp.float32
    assert sparse["w_gate_up"].dtype == jnp.bfloat16
    assert float(jnp.max(jnp.abs(sparse["router_bias"]))) > 0

    ours = _forward_logits(model_dir, token_ids)
    np.testing.assert_allclose(ours, want, rtol=2e-3, atol=2e-3)

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "reference"))
    import deepseek_v3_ref as ref

    with open(os.path.join(model_dir, "config.json")) as f:
        import json
        hf_dict = json.load(f)
    plain = ref.forward(load_hf_params(cfg, model_dir, jnp.float32),
                        hf_dict, token_ids)
    np.testing.assert_allclose(np.asarray(plain), want, rtol=2e-3, atol=2e-3)
