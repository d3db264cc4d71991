"""The pieces under Xing4.0: the stream mix against the reference and its
doubly stochastic residual, the clamp, the low-rank query and YaRN against
``transformers``, a DeepSeek-V3 checkpoint with both, one stream as the
forward it was; configuration and refusals. tests/test_xing4.py holds the
whole forward to the reference.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.models import deepseek_v3 as ds
from production_stack_tpu.models.config import (
    TINY_DEEPSEEK_V3,
    TINY_XING4,
    ModelConfig,
)
from production_stack_tpu.ops import hyper_connections as hc
from tests.xing4_helpers import ROOT, TOL, hf_config, make_engine, prompt, ref


PUBLISHED = os.path.join(ROOT, "benchmarks", "chip", "configs",
                         "xing4.0-29b-a4b-d7", "config.json")


# ---- the stream mix alone -------------------------------------------------------
def _mix_inputs(seed, tokens=96, n=4, d=64):
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    lp = ds._init_mix(TINY_XING4, ks[0], 1)
    # Streams that differ: a shared part and each stream's own.
    base = jax.random.normal(ks[1], (1, tokens, d))
    own = 0.3 * jax.random.normal(jax.random.fold_in(ks[1], 1),
                                  (n, tokens, d))
    return base + own, {k: v[0] for k, v in lp.items()}


def _matrices(x, lp, **over):
    kw = dict(iters=20, eps=1e-6, norm_eps=1e-6, clamp=(-30.0, 30.0))
    kw.update(over)
    return hc.mix_matrices(x, lp["hc_attn_phi"], lp["hc_attn_b"],
                           lp["hc_attn_a"], **kw)


def test_the_residual_mix_is_doubly_stochastic():
    """Rows and columns of ``H_res`` sum to 1 to what twenty iterations
    reach; after one they do not (the draw leans on the identity: ``exp``
    of its logits is far from balanced)."""
    x, lp = _mix_inputs(3)
    h_pre, h_post, h_res = _matrices(x, lp)
    assert h_res.shape == (96, 4, 4) and h_res.dtype == jnp.float32
    assert float(jnp.min(h_res)) > 0
    assert float(jnp.max(jnp.abs(h_res.sum(-1) - 1))) < 2e-5
    assert float(jnp.max(jnp.abs(h_res.sum(-2) - 1))) < 2e-5
    assert float(jnp.min(h_pre)) > 0 and float(jnp.max(h_pre)) < 1
    assert float(jnp.min(h_post)) > 0 and float(jnp.max(h_post)) < 2
    # Both parts move the matrices: tokens differ, and so do entries.
    assert float(jnp.std(h_pre, axis=0).mean()) > 0.05
    once = _matrices(x, lp, iters=1)[2]
    assert float(jnp.max(jnp.abs(once.sum(-1) - 1))) > 0.03


def test_the_stream_mix_is_the_references():
    x, lp = _mix_inputs(4)
    cfg = hf_config(TINY_XING4)
    ours = _matrices(x, lp)
    with jax.default_matmul_precision("highest"):
        theirs = ref.mix_matrices(cfg, lp["hc_attn_phi"], lp["hc_attn_b"],
                                  lp["hc_attn_a"], x.transpose(1, 0, 2))
        for a, b in zip(ours, theirs):
            np.testing.assert_allclose(a, b, atol=2e-6)
        branch = jax.random.normal(jax.random.PRNGKey(9), x.shape[1:])
        np.testing.assert_allclose(
            hc.pre(x, ours[0]), ref.mix_pre(x.transpose(1, 0, 2), theirs[0]),
            atol=1e-5)
        np.testing.assert_allclose(
            hc.post(x, branch, ours[1], ours[2]).transpose(1, 0, 2),
            ref.mix_post(x.transpose(1, 0, 2), branch, *theirs[1:]),
            atol=1e-5)


def test_clamped_logits_stay_finite():
    """Logits far outside the clamp (``b`` of 80) give ``exp(30)`` and a
    finite doubly stochastic matrix, not inf / inf."""
    x, lp = _mix_inputs(5)
    lp = dict(lp, hc_attn_b=lp["hc_attn_b"].at[8:].add(
        80.0 * jnp.eye(4).reshape(-1)))
    h_res = _matrices(x, lp)[2]
    assert bool(jnp.all(jnp.isfinite(h_res)))
    np.testing.assert_allclose(h_res, jnp.broadcast_to(jnp.eye(4),
                                                       h_res.shape), atol=1e-5)


# ---- the query and the rope, beside HF's own code and far out ------------------
def _forward_logits(params, mc, tokens, start):
    t = len(tokens)
    hidden, *_ = jax.jit(ds.forward, static_argnums=(1,))(
        params, mc, jnp.asarray([tokens], jnp.int32),
        start + jnp.arange(t, dtype=jnp.int32)[None],
        jnp.asarray([t], jnp.int32))
    return np.asarray(ds.compute_logits(params, mc, hidden))[0]


@pytest.mark.parametrize("start", [0, 5000, 200000])
def test_low_rank_query_and_yarn_at_positions_past_the_original_4096(start):
    """The whole forward (no cache) at positions past YaRN's original
    context. float32 angles of 2e5 radians carry 1e-2 of absolute error in
    both computations alike (the same product position x frequency), so
    the limit does not grow with the position."""
    mc = TINY_XING4
    params = ds.init_params(mc, jax.random.PRNGKey(11), jnp.float32)
    tokens = prompt(48, 40)
    want = ref.forward(params, hf_config(mc), tokens, start=start)
    got = _forward_logits(params, mc, tokens, start)
    assert float(np.max(np.abs(
        jax.nn.log_softmax(got) - jax.nn.log_softmax(want)))) < TOL


def test_yarn_blends_the_frequencies_hf_computes():
    """``_compute_yarn_parameters`` of ``transformers`` on the published
    rope: 32 frequencies, the fastest kept, the slowest divided by 64."""
    pytest.importorskip("torch")
    import transformers
    from transformers.modeling_rope_utils import ROPE_INIT_FUNCTIONS

    cfg = json.load(open(PUBLISHED))
    hf = transformers.DeepseekV3Config(**{
        k: v for k, v in cfg.items() if k != "model_type"})
    inv_freq, factor = ROPE_INIT_FUNCTIONS["yarn"](hf, "cpu")
    freqs, amp = ref.rope_frequencies(cfg)
    np.testing.assert_allclose(np.asarray(freqs), inv_freq.numpy(),
                               rtol=1e-6)
    assert amp == pytest.approx(factor) and amp == 1.0
    plain = 1.0 / 10000 ** (np.arange(0, 64, 2) / 64)
    assert freqs[0] == pytest.approx(plain[0])
    assert freqs[-1] == pytest.approx(plain[-1] / 64)
    assert ref.softmax_scale(cfg) == pytest.approx(
        192 ** -0.5 * (0.1 * np.log(64) + 1) ** 2)
    mc = ModelConfig.from_hf_config(cfg)
    assert ds._softmax_scale(mc) == pytest.approx(ref.softmax_scale(cfg))
    cos, sin = ds._rope_tables(mc, jnp.asarray([[1, 4097]]))
    np.testing.assert_allclose(cos[0, 1], np.cos(4097 * inv_freq.numpy()),
                               atol=2e-4)


def test_a_deepseek_v3_checkpoint_with_a_low_rank_query_and_yarn(tmp_path):
    """``model_type: deepseek_v3`` gains both by the same code: a tiny
    checkpoint as ``transformers`` writes it (``q_a_proj``,
    ``q_a_layernorm``, ``q_b_proj``; ``rope_scaling`` of type yarn with
    ``mscale_all_dim``) gives the logits of HF's own modeling code."""
    torch = pytest.importorskip("torch")
    import transformers

    from production_stack_tpu.models.weights import load_hf_params

    hf_cfg = transformers.DeepseekV3Config(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        moe_intermediate_size=32, num_hidden_layers=4,
        num_attention_heads=4, num_key_value_heads=4, n_shared_experts=1,
        n_routed_experts=8, num_experts_per_tok=2, n_group=1, topk_group=1,
        routed_scaling_factor=2.0, kv_lora_rank=32, q_lora_rank=24,
        qk_rope_head_dim=8, qk_nope_head_dim=16, v_head_dim=16,
        first_k_dense_replace=2, norm_topk_prob=True,
        max_position_embeddings=8192, rms_norm_eps=1e-6, rope_theta=10000.0,
        rope_scaling={"type": "yarn", "factor": 64, "beta_fast": 32,
                      "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                      "original_max_position_embeddings": 64},
        rope_interleave=True, attention_bias=False,
        tie_word_embeddings=False,
    )
    torch.manual_seed(0)
    model = transformers.DeepseekV3ForCausalLM(hf_cfg)
    with torch.no_grad():
        for layer in model.model.layers[2:]:
            gate = layer.mlp.gate
            gate.weight.copy_(torch.randn_like(gate.weight) * 0.5)
            gate.e_score_correction_bias.copy_(
                0.05 * torch.randn_like(gate.e_score_correction_bias))
    model = model.eval().to(torch.float32)
    model_dir = str(tmp_path / "deepseek_v3_q_lora")
    model.save_pretrained(model_dir, safe_serialization=True)
    token_ids = prompt(100, 3)
    token_ids = [t % 128 for t in token_ids]
    with torch.no_grad():
        want = model(torch.tensor([token_ids])).logits[0].numpy()

    cfg = ModelConfig.from_pretrained_dir(model_dir)
    assert (cfg.arch, cfg.q_lora_rank, cfg.hc_mult) == ("deepseek_v3", 24, 1)
    assert cfg.rope_scaling.factor == 64 and cfg.first_k_dense_replace == 2
    params = load_hf_params(cfg, model_dir, jnp.float32)
    dense = params["layers"]["dense"]
    assert dense["wq_a"].shape == (2, 64, 24) and "wq" not in dense
    assert dense["wq_b"].shape == (2, 24, 4 * 24)
    got = _forward_logits(params, cfg, token_ids, 0)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    with open(os.path.join(model_dir, "config.json")) as f:
        plain = ref.forward(params, dict(json.load(f), hc_mult=1), token_ids)
    np.testing.assert_allclose(np.asarray(plain), want, rtol=2e-4, atol=2e-4)


# ---- one stream is the module as it was ---------------------------------------
# models/deepseek_v3.py:forward of tiny-deepseek-v3 at the commit before the
# module knew of streams (PRNGKey(7), float32, the tokens below; CPU).
BEFORE_STREAMS = [
    "0x1.64906a0000000p-2", "-0x1.3a076e0000000p+0", "0x1.6524ee0000000p-2",
    "0x1.ac25160000000p-2", "0x1.a215b60000000p-2", "-0x1.ff82f00000000p-1",
    "0x1.2d95f00000000p-1", "-0x1.9607200000000p-2"]


def test_one_stream_is_the_forward_it_was_bit_for_bit():
    mc = TINY_DEEPSEEK_V3
    assert mc.hc_mult == 1 and not mc.q_lora_rank and mc.rope_scaling is None
    params = ds.init_params(mc, jax.random.PRNGKey(7), jnp.float32)
    assert not [k for k in params["layers"]["sparse"] if k.startswith("hc_")]
    t = 24
    tokens = (jnp.arange(2 * t).reshape(2, t) * 37) % 500 + 3
    positions = jnp.broadcast_to(jnp.arange(t), (2, t))
    fwd = jax.jit(ds.forward, static_argnums=(1,))
    args = (params, mc, tokens, positions, jnp.asarray([t, t - 5]))
    logits = np.asarray(ds.compute_logits(params, mc, fwd(*args)[0]))
    assert [float.hex(float(v)) for v in logits[0, -1, :8]] == BEFORE_STREAMS
    text = fwd.lower(*args).as_text()
    assert "hc_pre" not in text and "exponential" in text


def test_four_streams_lower_the_mix_under_its_scopes():
    mc = TINY_XING4
    params = ds.init_params(mc, jax.random.PRNGKey(7), jnp.float32)
    tokens = jnp.zeros((2, 8), jnp.int32)
    text = jax.jit(ds.forward, static_argnums=(1,)).lower(
        params, mc, tokens, jnp.broadcast_to(jnp.arange(8), (2, 8)),
        jnp.asarray([8, 8])).as_text(debug_info=True)
    for path in ("attn_proj/hc_pre", "attn_proj/hc_post", "ffn/hc_pre",
                 "ffn/hc_post", "logits/hc_head"):
        assert path in text, path


# ---- the configuration's keys ---------------------------------------------------
def test_the_published_config_is_read_key_by_key():
    cfg = json.load(open(PUBLISHED))
    mc = ModelConfig.from_hf_config(cfg)
    assert (mc.arch, mc.hc_mult, mc.hc_sinkhorn_iters, mc.hc_eps,
            mc.hc_res_clamp) == ("deepseek_v3", 4, 20, 1e-6, (-30.0, 30.0))
    assert (mc.q_lora_rank, mc.first_k_dense_replace, mc.n_routed_experts,
            mc.num_experts_per_tok) == (768, 2, 64, 4)
    ys = mc.rope_scaling
    assert (ys.factor, ys.original_max_position_embeddings, ys.beta_fast,
            ys.beta_slow, ys.mscale, ys.mscale_all_dim) == (
                64.0, 4096, 32.0, 1.0, 1.0, 1.0)
    # Published with one next-token-prediction layer: read, not served.
    assert ModelConfig.from_hf_config(
        dict(cfg, num_nextn_predict_layers=1)).num_nextn_predict_layers == 1
    assert ds.required_layer_leaves(mc)["sparse"] >= {
        "wq_a", "q_norm", "wq_b", "hc_attn_phi", "hc_ffn_a"}
    assert "wq" not in ds.required_layer_leaves(mc)["dense"]
    hash(mc)            # a static argument of every program


@pytest.mark.parametrize("key,value,named", [
    ("rope_scaling", {"type": "linear", "factor": 4}, "rope_scaling.type"),
    ("rope_scaling", {"rope_type": "llama3", "factor": 8},
     "rope_scaling.type"),
    ("scoring_func", "softmax", "scoring_func"),
    ("n_group", 8, "n_group"),
    ("topk_method", "greedy", "topk_method"),
])
def test_what_is_not_served_is_refused_by_its_key(key, value, named):
    cfg = json.load(open(PUBLISHED))
    cfg[key] = value
    with pytest.raises(ValueError, match="xing4_0: not supported") as err:
        ModelConfig.from_hf_config(cfg)
    assert named in str(err.value)


@pytest.mark.parametrize("flags,named", [
    ({"speculative_num_tokens": 3, "speculative_model": "tiny-llama"},
     "speculative"),
    ({"speculative_num_tokens": 2, "speculative_model": "tiny-xing4"},
     "speculative"),
    ({"kv_cache_dtype": "int8"}, "int8"),
    ({"tensor_parallel_size": 2}, "parallelism"),
])
def test_what_a_latent_row_cannot_follow_is_refused_beside_it(flags, named):
    with pytest.raises(ValueError, match="latent row") as err:
        make_engine(**flags)
    assert named.lower() in str(err.value).lower()


def test_next_token_prediction_tensors_are_not_loaded(tmp_path):
    """A checkpoint of the tiny preset written under the HF names, with a
    layer behind the last (``model.layers.4.*``: the published next-token
    module): it loads, that layer's tensors go nowhere, and the mix's
    leaves arrive float32 whatever the dtype."""
    from safetensors.numpy import save_file

    from production_stack_tpu.models.weights import load_hf_params

    mc = TINY_XING4
    params = ds.init_params(mc, jax.random.PRNGKey(5), jnp.float32)
    by_leaf = {ours: (name, tr) for name, (ours, tr)
               in ds.HF_LAYER_MAP.items()}
    tensors = {}
    h, nope, dv = mc.num_heads, mc.qk_nope_head_dim, mc.v_head_dim
    for layer, (kind, at) in enumerate(ds.layer_slots(mc)):
        lp = {k: np.asarray(v[at]) for k, v in
              params["layers"][kind].items()}
        # ``kv_b_proj`` whole, an expert's gate and up apart.
        uk = lp.pop("w_uk").transpose(2, 0, 1)           # [rank, H, nope]
        uv = lp.pop("w_uv").transpose(1, 0, 2)           # [rank, H, v]
        lp["w_kvb"] = np.concatenate([uk, uv], -1).reshape(
            -1, h * (nope + dv))
        if "w_gate_up" in lp:
            gate_up = lp.pop("w_gate_up")
            f = gate_up.shape[-1] // 2
            lp["we_gate"], lp["we_up"] = gate_up[..., :f], gate_up[..., f:]
        for leaf, value in lp.items():
            name, tr = by_leaf[leaf]
            if "*" in name:
                for e in range(value.shape[0]):
                    tensors[f"model.layers.{layer}."
                            + name.replace("*", str(e))] = \
                        np.ascontiguousarray(value[e].T if tr else value[e])
            else:
                tensors[f"model.layers.{layer}.{name}"] = \
                    np.ascontiguousarray(value.T if tr else value)
    behind = mc.num_layers
    tensors[f"model.layers.{behind}.self_attn.o_proj.weight"] = \
        np.ones((64, 64), np.float32)
    tensors[f"model.layers.{behind}.eh_proj.weight"] = \
        np.ones((64, 128), np.float32)
    for name, (ours, tr) in ds.HF_TOP_MAP.items():
        value = np.asarray(params[ours])
        tensors[name] = np.ascontiguousarray(value.T if tr else value)
    os.makedirs(tmp_path / "ckpt")
    save_file(tensors, str(tmp_path / "ckpt" / "model.safetensors"))
    loaded = load_hf_params(mc, str(tmp_path / "ckpt"), jnp.bfloat16)
    sparse = loaded["layers"]["sparse"]
    assert sparse["hc_ffn_phi"].dtype == sparse["hc_attn_a"].dtype \
        == jnp.float32
    assert sparse["wq_b"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        sparse["hc_attn_phi"], params["layers"]["sparse"]["hc_attn_phi"])
    again = load_hf_params(mc, str(tmp_path / "ckpt"), jnp.float32)
    tokens = prompt(20, 6)
    np.testing.assert_array_equal(
        _forward_logits(again, mc, tokens, 0),
        _forward_logits(params, mc, tokens, 0))
    # Without the key that says the layer is not a decoder layer, a tensor
    # behind the last layer is a fault, as it was.
    import dataclasses
    with pytest.raises(ValueError, match="indexes layer 4"):
        load_hf_params(dataclasses.replace(mc, num_nextn_predict_layers=0),
                       str(tmp_path / "ckpt"), jnp.float32)
