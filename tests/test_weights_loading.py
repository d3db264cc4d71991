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
    hidden, _, _ = forward(params, cfg, ids, positions, chunk_lens)
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
