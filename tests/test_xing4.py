"""Xing4.0 (``model_type: xing4_0``: the DeepSeek-V3 block with a low-rank
query, YaRN-scaled rope and a residual of four streams mixed by
manifold-constrained hyper-connections) against its plain reference
(tests/reference/xing4_ref.py), through the engine's own scheduler, block
manager and runner at the tiny preset with float32 activations: 2 dense + 2
sparse layers, 8 experts top-2, one shared, ``hc_mult`` 4.

What is compared is log-probabilities, as tests/test_deepseek_v3.py compares
them: every generated token's own and those of the 20 most likely at its
position (``logprobs=20``) against the reference's log-softmax at the same
ids.

TOL: both sides are float32 with full-precision products on the CPU; they
differ in the FORM of attention (absorbed over the cached row against
expanded keys and values), in the order of sums, in where the mix's
normalisation is applied (the program scales ``x phi`` by the streams' rms,
the reference norms ``x`` first) and in a Sinkhorn step (the program
multiplies by one reciprocal a row, the reference divides every entry).
Measured largest difference over every case here: 1.5e-6 (logit spread
1.0). The nine wrong models of ``test_the_tolerance_tells_a_wrong_model`` move
the same numbers by 1.3e-3 to 0.35: the four mistakes in the stream mix by
1.3e-3 (the mix in bf16), 8e-3 (one Sinkhorn iteration for twenty), 0.065 (no
dynamic term) and 0.27 (``H_post`` without its 2), the router in bf16 by
1.4e-3 (no choice of these tokens flips), the query without its norm, the
softmax without YaRN's scale and the unblended frequencies by 0.24 to 0.35.
So 5e-5 leaves both sides room.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.engine import ServingEngine
from production_stack_tpu.engine.sampling import SamplingParams
from production_stack_tpu.engine.scheduler import Sequence
from production_stack_tpu.models import deepseek_v3 as ds
from production_stack_tpu.models.config import (
    TINY_DEEPSEEK_V3,
    TINY_XING4,
    ModelConfig,
)
from production_stack_tpu.ops import hyper_connections as hc

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tests", "reference"))
import xing4_ref as ref  # noqa: E402

TOL = 5e-5
ROUTING_TOL = 1e-3
TOP = 20
PUBLISHED = os.path.join(ROOT, "benchmarks", "chip", "configs",
                         "xing4.0-29b-a4b-d7", "config.json")


def hf_config(mc: ModelConfig) -> dict:
    """The HF config.json keys the reference reads, from a ModelConfig."""
    ys = mc.rope_scaling
    return {
        "num_hidden_layers": mc.num_layers,
        "num_attention_heads": mc.num_heads,
        "qk_nope_head_dim": mc.qk_nope_head_dim,
        "qk_rope_head_dim": mc.qk_rope_head_dim,
        "kv_lora_rank": mc.kv_lora_rank, "v_head_dim": mc.v_head_dim,
        "q_lora_rank": mc.q_lora_rank or None,
        "rope_theta": mc.rope_theta, "rms_norm_eps": mc.rms_norm_eps,
        "rope_scaling": None if ys is None else {
            "type": "yarn", "factor": ys.factor,
            "original_max_position_embeddings":
                ys.original_max_position_embeddings,
            "beta_fast": ys.beta_fast, "beta_slow": ys.beta_slow,
            "mscale": ys.mscale, "mscale_all_dim": ys.mscale_all_dim},
        "first_k_dense_replace": mc.first_k_dense_replace,
        "num_experts_per_tok": mc.num_experts_per_tok,
        "routed_scaling_factor": mc.routed_scaling_factor,
        "norm_topk_prob": mc.norm_topk_prob,
        "hc_mult": mc.hc_mult, "hc_sinkhorn_iters": mc.hc_sinkhorn_iters,
        "hc_eps": mc.hc_eps, "mhc_h_res_clamp_min": mc.hc_res_clamp[0],
        "mhc_h_res_clamp_max": mc.hc_res_clamp[1],
    }


def make_engine(**over) -> ServingEngine:
    cfg = dict(model="tiny-xing4", max_model_len=512,
               num_kv_blocks=128, num_decode_steps=8, dtype="float32",
               max_num_seqs=8, max_num_batched_tokens=64, max_prefill_seqs=8)
    cfg.update(over)
    return ServingEngine(EngineConfig(**cfg))


def prompt(n: int, salt: int):
    return [int(x) for x in np.random.default_rng(salt).integers(1, 512, n)]


def add(eng, name, tokens, max_tokens) -> Sequence:
    seq = Sequence(name, list(tokens), SamplingParams(
        temperature=0.0, max_tokens=max_tokens, ignore_eos=True,
        logprobs=TOP))
    eng.scheduler.add_sequence(seq)
    return seq


def step(eng):
    batch = eng.scheduler.schedule()
    tokens, lps = eng.runner.execute(batch, 0)
    eng.scheduler.update_after_step(batch, tokens, lps)
    return batch


def drive(eng) -> list:
    batches = []
    while eng.scheduler.has_work():
        batches.append(step(eng))
    return batches


def worst(eng, seq, wrong=()) -> float:
    """Largest |log-probability difference| of a finished sequence's
    outputs against the reference over the same tokens."""
    tokens = seq.all_token_ids
    logits = ref.forward(eng.runner.params, hf_config(eng.model_config),
                         tokens[:-1], wrong)
    logp = np.asarray(jax.nn.log_softmax(logits, axis=-1))
    n_prompt = len(seq.prompt_token_ids)
    assert len(seq.output_logprobs) == len(seq.output_token_ids)
    diffs = []
    for i, (chosen, top) in enumerate(seq.output_logprobs):
        row = logp[n_prompt - 1 + i]
        diffs.append(chosen - row[seq.output_token_ids[i]])
        assert len(top) == TOP
        diffs += [lp - row[tok] for tok, lp in top]
    return float(np.max(np.nan_to_num(np.abs(diffs), nan=np.inf)))


@pytest.fixture(scope="module")
def engine():
    return make_engine()


# ---- the engine's path against the reference --------------------------------
def test_a_prefill_of_one_chunk(engine):
    seq = add(engine, "a", prompt(40, 1), 1)
    batches = drive(engine)
    assert [b.kind for b in batches] == ["prefill"]
    assert worst(engine, seq) < TOL


def test_b_a_prompt_crossing_three_prefill_chunks(engine):
    seq = add(engine, "b", prompt(150, 2), 4)
    batches = drive(engine)
    assert [b.chunk_lens for b in batches if b.kind == "prefill"] == \
        [[64], [64], [22]]
    assert worst(engine, seq) < TOL


@pytest.mark.parametrize("impl", ["window", "paged"])
def test_c_decode_through_the_latent_pool(impl):
    """Three rows of unequal length decode 40 tokens in trains of 8; the
    streams of a decode step are [4, rows, 1, D]."""
    eng = make_engine(attn_impl=impl)
    assert eng.runner.attn_impl == impl
    seqs = [add(eng, f"c{i}", prompt(n, 10 + i), 41)
            for i, n in enumerate((20, 100, 7))]
    batches = drive(eng)
    assert sum(b.kind == "decode" for b in batches) >= 5
    for seq in seqs:
        assert len(seq.output_token_ids) == 41
        assert worst(eng, seq) < TOL


def test_d_five_rows_of_unequal_length_in_one_prefill():
    """A row's padded positions carry streams too (they are mixed like any
    token's, reach no expert and are read by no one)."""
    eng = make_engine(max_num_batched_tokens=1024)
    lens = (5, 12, 9, 3, 11)
    seqs = [add(eng, f"d{i}", prompt(n, 20 + i), 1)
            for i, n in enumerate(lens)]
    batches = drive(eng)
    assert [b.kind for b in batches] == ["prefill"]
    assert len(batches[0].seqs) == 5
    for seq in seqs:
        assert worst(eng, seq) < TOL
    mc = eng.model_config
    pre = eng.runner.fwd_stats_total["prefill"]
    sparse = mc.num_layers - mc.first_k_dense_replace
    assert pre["assignments"] == sum(lens) * mc.num_experts_per_tok * sparse


def test_e_a_prefix_hit_is_served_from_latent_blocks(engine):
    bm = engine.block_manager
    shared = prompt(64, 80)
    first = add(engine, "p1", shared + prompt(10, 81), 3)
    drive(engine)
    hits = bm.prefix_hits_total
    second = add(engine, "p2", shared + prompt(12, 82), 3)
    drive(engine)
    assert second.num_cached_tokens == 64
    assert bm.prefix_hits_total == hits + 64
    assert worst(engine, first) < TOL and worst(engine, second) < TOL


def test_f_preempt_and_recompute(engine):
    seq = add(engine, "e", prompt(70, 30), 20)
    other = add(engine, "e2", prompt(30, 31), 20)
    for _ in range(4):
        step(engine)
    assert 0 < len(seq.output_token_ids) < 20
    engine.scheduler._preempt(seq)
    assert not seq.block_ids
    drive(engine)
    assert len(seq.output_token_ids) == 20
    assert worst(engine, seq) < TOL and worst(engine, other) < TOL


def test_g_a_packed_prefill_row_serves_what_the_rectangle_serves(monkeypatch):
    """tests/test_deepseek_v3.py's, over four residual streams: the mix is
    a function of a token, so a packed row runs it as any row."""
    from tests.test_deepseek_v3 import packed_row_against_rectangle

    packed_row_against_rectangle(
        monkeypatch, TINY_XING4, sys.modules[__name__])


# ---- the tolerance is tight enough -------------------------------------------
@pytest.fixture(scope="module")
def served(engine):
    seq = add(engine, "w", prompt(90, 70), 24)
    drive(engine)
    assert worst(engine, seq) < TOL
    return seq


@pytest.mark.parametrize("wrong", ref.WRONG)
def test_the_tolerance_tells_a_wrong_model(engine, served, wrong):
    """A prompt of 90 tokens (two chunks) and 24 decoded tokens against the
    reference with ONE equation wrong: in the stream mix (no dynamic term,
    one Sinkhorn iteration, ``H_post`` without its 2, the mix in bf16), in
    the query (no norm between its two matrices), in the rope (no YaRN scale
    on the softmax, the published frequencies unblended), in the router."""
    assert worst(engine, served, wrong=(wrong,)) > 10 * TOL


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


async def test_the_served_surface_says_the_streams_and_who_mixes_them():
    from aiohttp.test_utils import TestClient, TestServer

    from production_stack_tpu.server.api_server import APIServer

    eng = make_engine()
    client = TestClient(TestServer(APIServer(eng).build_app()))
    await client.start_server()
    try:
        done = await client.post("/v1/completions", json={
            "model": "tiny-xing4", "prompt": prompt(12, 70),
            "max_tokens": 6, "temperature": 0, "ignore_eos": True})
        assert done.status == 200
        programs = (await (await client.get("/debug/programs")).json())[
            "programs"]
        version = await (await client.get("/version")).json()
    finally:
        await client.close()
    assert {p["program"] for p in programs} == {"decode", "prefill"}
    assert all(p["hc_mult"] == 4 and p["hc_mix"] == "xla" for p in programs)
    assert version["engine"]["hc_mult"] == 4
    assert version["engine"]["hc_mix"] == "xla"
    plain = ServingEngine(EngineConfig(
        model="tiny-deepseek-v3", max_model_len=128, num_kv_blocks=32,
        max_num_seqs=2, max_num_batched_tokens=64, dtype="float32"))
    assert plain.report()["engine"]["hc_mult"] == 1
    assert "hc_mix" not in plain.report()["engine"]
