"""The Phi-4-mini-flash family (SambaY: selective-scan layers beside window
rings in the state slots, ONE paged full layer, a second half of gated
memory units and cross layers that cache nothing; differential attention)
against its plain reference (tests/reference/phi4flash_ref.py), through the
engine's own scheduler, block manager and runner at a tiny preset with
float32 activations: a window of 64 keys and rows of 128 and 256 tokens, so
that prompts of 1, 63, 64, 65 and 3 x 64 + 5 tokens put the window's edge
before, at and behind the prompt's end, one of 300 crosses a chunk's, and
decode carries every one of them on.

What is compared is log-probabilities, not tokens: every generated token's
own log-probability and those of the 20 most likely tokens at its position,
as the served surface returns them (``logprobs=20``), against the
reference's log-softmax at the same ids.

TOL: both sides are float32 with full-precision products on the CPU; they
differ in the ORDER of sums (batched rows, a prompt cut into chunks, the
ring's blocks, the packed row's zero lanes, the paged kernels' merged
segments, the scan's kernel against a token at a time). Measured largest
difference over every case here: 6e-5 (logit spread 2). The wrong models of
``test_the_tolerance_tells_a_wrong_model`` move the same numbers by 0.3 to
several units, so 1e-3 leaves both sides a decade of room.
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
from production_stack_tpu.models import get_model, phi4flash
from production_stack_tpu.models.config import (
    TINY_PHI4FLASH,
    ModelConfig,
    resolve_model_config,
)
from production_stack_tpu.ops import attention as att
from production_stack_tpu.ops import selective_scan as s6
from production_stack_tpu.ops.pallas.selective_scan import (
    s6_chunk_kernel,
    supports_chunk_kernel,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tests", "reference"))
import phi4flash_ref as ref  # noqa: E402

TOL = 1e-3
TOP = 20
CHUNK = 256         # make_engine's max_num_batched_tokens
W = TINY_PHI4FLASH.sliding_window
LENGTHS = (1, W - 1, W, W + 1, 3 * W + 5, 300)
CONFIG_DIR = os.path.join(ROOT, "benchmarks", "chip", "configs",
                          "phi-4-mini-flash")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
F32 = jnp.float32


def hf_config(mc: ModelConfig) -> dict:
    """The HF config.json keys the reference reads, from a ModelConfig."""
    return {
        "model_type": "phi4flash", "mb_per_layer": 2,
        "hidden_size": mc.hidden_size, "num_hidden_layers": mc.num_layers,
        "intermediate_size": mc.intermediate_size,
        "vocab_size": mc.vocab_size,
        "num_attention_heads": mc.num_heads,
        "num_key_value_heads": mc.num_kv_heads,
        "layer_norm_eps": mc.rms_norm_eps,
        "sliding_window": mc.sliding_window,
        "mamba_d_state": mc.mamba_d_state, "mamba_d_conv": mc.mamba_d_conv,
        "mamba_expand": mc.mamba_d_inner // mc.hidden_size,
        "mamba_dt_rank": mc.mamba_dt_rank,
        "tie_word_embeddings": True,
    }


def make_engine(model="tiny-phi4flash", **over) -> ServingEngine:
    cfg = dict(model=model, max_model_len=1024, num_kv_blocks=320,
               num_decode_steps=8, dtype="float32", max_num_seqs=8,
               max_num_batched_tokens=CHUNK, max_prefill_seqs=8)
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


def drive(eng) -> list:
    """Dispatches, synchronously, until nothing is left: schedule, run,
    apply."""
    batches = []
    while eng.scheduler.has_work():
        batch = eng.scheduler.schedule()
        tokens, lps = eng.runner.execute(batch, 0)
        eng.scheduler.update_after_step(batch, tokens, lps)
        batches.append(batch)
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
    """The engine's default path on the CPU: the full layer and the cross
    layers through ``window_attention`` over gathered history, the scan a
    token at a time."""
    eng = make_engine()
    assert eng.runner.attn_impl == "window" and not eng.runner.prefill_packs
    return eng


@pytest.fixture(scope="module")
def paged():
    """``--attn-impl paged`` (KV pairs as rows of 128 lanes): the full layer
    and the cross layers through the Pallas kernels in interpret mode over
    the ONE pooled layer, a chunk's scan through its kernel."""
    eng = make_engine(attn_impl="paged")
    assert eng.runner.attn_impl == "paged" and eng.runner.prefill_reads_pool
    assert not eng.runner.prefill_packs      # the rings are a state a row
    return eng


@pytest.fixture(scope="module")
def served(engine):
    """Every listed context at once (two sequences a prefill dispatch, a
    row each), 12 tokens each: a prompt of 63 decodes over the window's
    edge, one of 300 is two chunks."""
    seqs = {n: add(engine, f"len{n}", prompt(n, n), 12) for n in LENGTHS}
    batches = drive(engine)
    return engine, seqs, batches


# ------------------------------------------------------ engine vs reference
@pytest.mark.parametrize("n", LENGTHS)
def test_engine_logprobs_match_the_reference(served, n):
    eng, seqs, batches = served
    assert worst(eng, seqs[n]) < TOL
    prefills = [b for b in batches if b.kind == "prefill"]
    # Two sequences a dispatch, and the longest prompt in two chunks.
    assert max(len(b.seqs) for b in prefills) >= 2
    assert sum(seqs[300] in b.seqs for b in prefills) >= 2


@pytest.mark.parametrize("n", LENGTHS)
def test_paged_logprobs_match_the_reference(paged, n):
    """The same through the pool, the Pallas kernels (interpret) and the
    scan's kernel."""
    seq = add(paged, f"p{n}", prompt(n, 100 + n), 10)
    drive(paged)
    assert worst(paged, seq) < TOL


def test_the_answers_differ_by_prompt_and_do_not_repeat_one_token(served):
    """The seeded draw (PERF.md section 6, PR 44 and PR 54): at the table's
    and the branches' sizes a sequence's greedy answer is its own and does
    not end in one token repeated."""
    _, seqs, _ = served
    answers = [tuple(s.output_token_ids) for s in seqs.values()]
    assert len(set(answers)) == len(answers)
    for answer in answers:
        assert len(set(answer[-6:])) > 2


def test_a_state_slot_reused_by_a_second_sequence_starts_empty(served):
    """The slots of the first sequences go to new ones, shorter than a
    window: what the last owner left in a ring, a scan's state or a conv
    window is never seen."""
    eng, seqs, _ = served
    held = {s.state_slot for s in seqs.values()}
    again = [add(eng, f"again{n}", prompt(n, 7 * n), 6) for n in (3, 40, 70)]
    drive(eng)
    assert {s.state_slot for s in again} <= held
    for seq in again:
        assert worst(eng, seq) < TOL


@pytest.mark.parametrize("wrong", ref.WRONG + ref.LOW_PRECISION)
def test_the_tolerance_tells_a_wrong_model(served, wrong):
    """Each plausible mistake (lambda_init of the next layer, a_2 not
    subtracted, no sub-norm or no (1 - lambda_init), the window off by one
    either way, the conv bias or the skip left out, the memory taken after
    the gate or from the layer before, the cross layers reading a window
    layer's keys, another pairing, no bias, another norm) and each
    computation in too little precision moves the same numbers past TOL on
    the sequences that can see it."""
    eng, seqs, _ = served
    assert max(worst(eng, seqs[n], (wrong,)) for n in (W + 1, 300)) \
        > 10 * TOL


# ---------------------------------------------------------- the scan's ops
def _scan_inputs(bsz, t, n, d, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    return (jax.random.normal(ks[0], (bsz, n, d)),
            jax.random.normal(ks[1], (bsz, t, d)),
            jax.nn.softplus(jax.random.normal(ks[2], (bsz, t, d)) - 2.0),
            -jnp.exp(jax.random.normal(ks[3], (n, d))),
            jax.random.normal(ks[4], (bsz, t, n)),
            jax.random.normal(ks[5], (bsz, t, n)),
            jax.random.normal(ks[6], (d,)))


@pytest.mark.parametrize("interpret", [False, True], ids=["jnp", "kernel"])
def test_s6_chunk_is_s6_step_token_by_token_across_a_chunk_boundary(
        interpret):
    """Two chunks of 16 and 24 tokens, the state carried between them,
    against 40 decode steps; a row of 9 valid tokens in the second chunk
    keeps the state of its ninth, and a row of none its state whole."""
    bsz, n, d = 3, 16, 256
    state, u, dt, a, b, c, skip = _scan_inputs(bsz, 40, n, d, seed=3)
    lens2 = jnp.asarray([24, 9, 0])
    y1, s1 = s6.s6_chunk(state, u[:, :16], dt[:, :16], a, b[:, :16],
                         c[:, :16], skip, jnp.asarray([16, 16, 16]),
                         interpret=interpret)
    y2, s2 = s6.s6_chunk(s1, u[:, 16:], dt[:, 16:], a, b[:, 16:], c[:, 16:],
                         skip, lens2, interpret=interpret)
    step, ys, after = state, [], {}
    for i in range(40):
        live = jnp.asarray([True] * bsz) if i < 16 else (i - 16) < lens2
        y, step = s6.s6_step(step, u[:, i], dt[:, i], a, b[:, i], c[:, i],
                             skip, live)
        ys.append(y)
        after[i] = step
    ys = jnp.stack(ys, axis=1)
    np.testing.assert_allclose(y1, ys[:, :16], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(y2[0], ys[0, 16:], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(y2[1, :9], ys[1, 16:25], rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(s2, after[39], rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(s2[2], s1[2])         # a row of none
    np.testing.assert_allclose(s2[1], after[24][1], rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("bsz,t,n,d", [
    (1, 8, 16, 128), (2, 24, 16, 256), (1, 264, 16, 512), (2, 16, 8, 384),
    (3, 128, 16, 1024), (1, 40, 32, 128),
])
def test_the_chunk_kernel_is_the_jnp_form(bsz, t, n, d):
    """The Pallas kernel in interpret mode against the ``lax.scan`` of one
    step a token: blocks of 128 to 512 channels, one and several time
    blocks, N of 8, 16 and 32."""
    assert supports_chunk_kernel(t, n, d)
    args = _scan_inputs(bsz, t, n, d, seed=t + d)
    y, state = s6_chunk_kernel(*args, interpret=True)
    y0, state0 = s6.s6_chunk_jnp(*args)
    np.testing.assert_allclose(y, y0, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(state, state0, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("t,n,d", [(7, 16, 128), (8, 16, 192), (8, 12, 128),
                                   (8, 128, 128)])
def test_shapes_the_kernel_cannot_take_keep_the_jnp_form(t, n, d):
    assert not supports_chunk_kernel(t, n, d)
    state, u, dt, a, b, c, skip = _scan_inputs(1, t, n, d)
    y, _ = s6.s6_chunk(state, u, dt, a, b, c, skip, jnp.asarray([t]),
                       interpret=True)
    y0, _ = s6.s6_chunk_jnp(state, u, dt, a, b, c, skip)
    np.testing.assert_array_equal(y, y0)


def test_the_gates_are_float32_at_highest_precision():
    """``dt``, ``B`` and ``C`` from bf16 inputs are float32 products of the
    widened values (the reference's own)."""
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    u = jax.random.normal(ks[0], (4, 256)).astype(jnp.bfloat16)
    w_x = (jax.random.normal(ks[1], (256, 16 + 32)) / 16).astype(
        jnp.bfloat16)
    w_dt = (jax.random.normal(ks[2], (16, 256)) / 4).astype(jnp.bfloat16)
    bias = jax.random.normal(ks[3], (256,))
    dt, b, c = s6.gates(u, w_x, w_dt, bias, 16)
    assert dt.dtype == b.dtype == c.dtype == F32
    proj = np.asarray(u, np.float64) @ np.asarray(w_x, np.float64)
    want = np.logaddexp(0, proj[:, :16] @ np.asarray(w_dt, np.float64)
                        + np.asarray(bias, np.float64))
    np.testing.assert_allclose(dt, want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(b, proj[:, 16:32], rtol=1e-5, atol=1e-6)


# ------------------------------------------- differential attention's parts
def _attention_layer(mc, seed=0):
    params = get_model(mc).init_params(mc, jax.random.PRNGKey(seed), F32)
    return {k: x[1] for k, x in params["layers"]["attn"].items()}


@pytest.mark.parametrize("window", [None, 16])
def test_the_packed_row_is_the_references_four_softmaxes_a_pair(window):
    """``pack_queries`` + one softmax over rows ``[k_1 | k_2]`` / ``[v_1 |
    v_2]`` + ``differential`` against the reference's plain softmaxes: a_1
    and a_2 of every pair, lambda, the subtraction, the norm, (1 -
    lambda_init)."""
    mc, t, layer = TINY_PHI4FLASH, 40, 3
    cfg = hf_config(mc)
    lp = _attention_layer(mc)
    h = jax.random.normal(jax.random.PRNGKey(2), (t, mc.hidden_size))
    k, v = ref.project_kv(cfg, lp, h)
    with jax.default_matmul_precision("highest"):
        want = ref.diff_attention(cfg, lp, h, k, v, layer, window)
        dh, heads, rows = mc.head_dim_, mc.num_heads, mc.num_kv_heads // 2
        q = (h @ lp["wqkv"][:, :heads * dh] + lp["bqkv"][:heads * dh]
             ).reshape(1, t, heads, dh)
        qp = phi4flash.pack_queries(q)
        kp = k.reshape(1, t, rows, 2 * dh)
        vp = v.reshape(1, t, rows, 2 * dh)
        pos = jnp.arange(t, dtype=jnp.int32)[None]
        lens = jnp.asarray([t], jnp.int32)
        if window is None:
            out = att.attend(qp, kp, vp, pos, lens, att.KVView(),
                             scale=dh ** -0.5)
        else:
            ring = jnp.zeros((1, rows, window, 2 * dh), F32)
            out = att.window_ring_attend(qp, kp, vp, pos, lens, ring, ring,
                                         scale=dh ** -0.5)
        got = phi4flash.differential(mc, out, lp, layer) @ lp["wo"] \
            + lp["bo"]
    np.testing.assert_allclose(got[0], want, rtol=2e-4, atol=2e-4)


def test_packed_queries_are_zero_outside_their_own_keys():
    q = jnp.arange(1, 1 + 2 * 3 * 4 * 8, dtype=F32).reshape(2, 3, 4, 8)
    packed = phi4flash.pack_queries(q)
    assert packed.shape == (2, 3, 4, 16)
    np.testing.assert_array_equal(packed[:, :, 0::2, :8], q[:, :, 0::2])
    np.testing.assert_array_equal(packed[:, :, 1::2, 8:], q[:, :, 1::2])
    assert not packed[:, :, 0::2, 8:].any()
    assert not packed[:, :, 1::2, :8].any()


def test_the_layers_kinds_and_the_readers_of_the_one_paged_layer():
    mc = resolve_model_config(CONFIG_DIR)
    kinds = [k for k, _ in phi4flash.layer_kinds(mc)]
    assert kinds[:18] == ["s6", "attn"] * 9
    assert kinds[18:] == ["gmu", "cross"] * 7
    assert [k for k, _ in ref.layer_kinds(hf_config(mc))] == kinds
    report = phi4flash.ring_report(mc)
    assert report["window_layers"] == list(range(1, 16, 2))
    assert report["ring"] == {"ring_k": [10, 512, 128],
                              "ring_v": [10, 512, 128]}
    assert report["scan_layers"] == list(range(0, 17, 2))
    assert report["scan_state"] == {"s6": [16, 5120], "conv": [120, 128]}
    assert report["paged_layer"] == 17
    assert report["paged_layer_readers"] == list(range(17, 32, 2))
    assert report["memory_layer"] == 16
    assert report["memory_readers"] == list(range(18, 32, 2))
    specs = phi4flash.cache_specs(mc)
    assert tuple(specs.paged_kv) == (1, 10, 128)
    assert [(s.name, s.layers, s.dtype) for s in specs.state] == [
        ("ring_k", 8, None), ("ring_v", 8, None), ("s6", 9, "float32"),
        ("conv", 9, None)]


def test_the_cross_layers_read_the_full_layers_rows_and_no_other():
    """The module's forward with the full layer's K and V projections
    zeroed: its keys are then its bias's and its values too, and the
    reference given the same tree agrees; a reference whose cross layers
    read the last window layer's rows does not. And the rows the forward
    returns for the pool are the full layer's."""
    mc = TINY_PHI4FLASH
    cfg = hf_config(mc)
    params = get_model(mc).init_params(mc, jax.random.PRNGKey(4), F32)
    t = 70
    ids = jnp.asarray(prompt(t, 9))[None]
    pos = jnp.arange(t, dtype=jnp.int32)[None]
    with jax.default_matmul_precision("highest"):
        hidden, k_new, v_new, _ = phi4flash.forward(
            params, mc, ids, pos, jnp.asarray([t]))
        got = jax.nn.log_softmax(
            phi4flash.compute_logits(params, mc, hidden)[0], -1)
    right = jax.nn.log_softmax(ref.forward(params, cfg, ids[0]), -1)
    wrong = jax.nn.log_softmax(ref.forward(
        params, cfg, ids[0], ("cross_reads_last_window_layer",)), -1)
    assert float(jnp.abs(got - right).max()) < TOL
    assert float(jnp.abs(got - wrong).max()) > 100 * TOL
    # What goes to the pool: layer L/2 + 1's keys and values of the tokens.
    x, carry = ref.embed(params, ids[0]), {}
    for i in range(mc.num_layers // 2 + 2):
        kind, lp = ref.layer_params(params, cfg, i)
        x, carry = ref.layer(cfg, kind, ref.layer_role(cfg, i), lp, x,
                             carry, i)
    k, v = carry["shared"]
    assert k_new.shape == (1, mc.num_kv_heads // 2, 1, t, 2 * mc.head_dim_)
    np.testing.assert_allclose(
        k_new[0, :, 0].transpose(1, 0, 2).reshape(t, -1),
        k.reshape(t, -1), rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(
        v_new[0, :, 0].transpose(1, 0, 2).reshape(t, -1),
        v.reshape(t, -1), rtol=1e-3, atol=1e-3)


# ------------------------------ the two published mechanisms, other's code
def test_the_references_s6_mixer_is_transformers_mamba_mixer():
    """``ref.s6_mixer`` against ``transformers``' own Mamba-1
    (``MambaMixer.slow_forward``, torch on the CPU, the same weights): code
    neither this repository nor its reference was written from."""
    torch = pytest.importorskip("torch")
    mamba = pytest.importorskip("transformers.models.mamba.modeling_mamba")
    from transformers import MambaConfig

    hidden, n, k, rank, t = 64, 16, 4, 4, 21
    torch.manual_seed(0)
    mixer = mamba.MambaMixer(MambaConfig(
        hidden_size=hidden, state_size=n, conv_kernel=k, expand=2,
        time_step_rank=rank, use_conv_bias=True, use_bias=False,
        num_hidden_layers=1, vocab_size=8), layer_idx=0).float().eval()
    with torch.no_grad():
        for p in mixer.parameters():
            p.copy_(torch.randn_like(p) * 0.3)
        mixer.A_log.copy_(torch.log(torch.arange(1, n + 1).float())[None]
                          .expand(2 * hidden, n))
        x = torch.randn(1, t, hidden)
        want = mixer.slow_forward(x)[0].numpy()
    sd = {k_: v.detach().numpy() for k_, v in mixer.state_dict().items()}
    lp = {
        "in_proj": sd["in_proj.weight"].T, "conv_w": sd["conv1d.weight"]
        [:, 0].T, "conv_b": sd["conv1d.bias"], "w_x": sd["x_proj.weight"].T,
        "w_dt": sd["dt_proj.weight"].T, "dt_bias": sd["dt_proj.bias"],
        "a_log": sd["A_log"].T, "d_skip": sd["D"],
        "wo": sd["out_proj.weight"].T,
    }
    cfg = {"hidden_size": hidden, "num_attention_heads": 2,
           "mamba_d_state": n, "mamba_expand": 2, "mamba_dt_rank": rank,
           "sliding_window": 16, "num_hidden_layers": 8}
    with jax.default_matmul_precision("highest"):
        got, _ = ref.s6_mixer(cfg, {k_: jnp.asarray(v, F32)
                                    for k_, v in lp.items()},
                              jnp.asarray(x[0].numpy()))
    # float32 sums in two libraries' orders: against the outputs' size.
    np.testing.assert_allclose(got, want, rtol=1e-3,
                               atol=1e-5 * float(np.abs(want).max()))


def test_the_references_differential_attention_is_transformers_diffllama():
    """``ref.diff_attention`` against ``DiffLlamaAttention``'s eager path
    with cos = 1, sin = 0 (no rotation): the same weights, the heads
    permuted to its pairing. DiffLlama pairs query head p with p + H / 2
    (its ``chunk`` over the heads) where this model pairs (2p, 2p + 1), has
    no bias and no (1 - lambda_init) ... it HAS: both scale by it; its
    lambda_init is by ITS layer index, given here."""
    torch = pytest.importorskip("torch")
    diff = pytest.importorskip(
        "transformers.models.diffllama.modeling_diffllama")
    from transformers import DiffLlamaConfig

    hidden, heads, kv_heads, t, layer = 128, 4, 2, 19, 3
    torch.manual_seed(0)
    dh = hidden // heads
    hf = DiffLlamaConfig(hidden_size=hidden, num_attention_heads=heads,
                         num_key_value_heads=kv_heads,
                         intermediate_size=64, num_hidden_layers=8,
                         vocab_size=8, attention_bias=False,
                         attention_dropout=0.0, rms_norm_eps=1e-5,
                         lambda_std_dev=0.1)
    hf._attn_implementation = "eager"
    module = diff.DiffLlamaAttention(hf, layer_idx=layer).float().eval()
    with torch.no_grad():
        for p in module.parameters():
            p.copy_(torch.randn_like(p) * 0.2)
        x = torch.randn(1, t, hidden)
        cos, sin = torch.ones(1, t, dh), torch.zeros(1, t, dh)
        mask = torch.full((t, t), float("-inf")).triu(1)[None, None]
        want = module(x, (cos, sin), attention_mask=mask)[0][0].numpy()
    sd = {k: v.detach().numpy() for k, v in module.state_dict().items()}
    # DiffLlama: query heads chunked in halves -> (q_1 = head p, q_2 = head
    # p + H/2); its KV heads: k repeated to H, then ... the pair (k_1, k_2)
    # of query pair p is (kv head of head p, kv head of head p + H/2); v is
    # the two halves of the KV heads' values side by side. With H = 4 over
    # Hkv = 2: k_1 = KV head 0 (heads 0, 1), k_2 = KV head 1 (heads 2, 3):
    # ONE KV pair (0, 1) read by both query pairs. Ours pairs (2p, 2p + 1):
    # permute its query heads [0, 2, 1, 3].
    perm = [0, 2, 1, 3]
    wq = sd["q_proj.weight"].T.reshape(hidden, heads, dh)[:, perm]
    wo = sd["o_proj.weight"].T
    lp = {
        "wqkv": np.concatenate([wq.reshape(hidden, hidden),
                                sd["k_proj.weight"].T,
                                sd["v_proj.weight"].T], axis=1),
        "bqkv": np.zeros((hidden + 2 * kv_heads * dh,), np.float32),
        "wo": wo, "bo": np.zeros((hidden,), np.float32),
        "lambda_q1": sd["lambda_q1"], "lambda_k1": sd["lambda_k1"],
        "lambda_q2": sd["lambda_q2"], "lambda_k2": sd["lambda_k2"],
        "subln": np.ones((2 * dh,), np.float32),    # its norm has no weight
    }
    lp = {k: jnp.asarray(v, F32) for k, v in lp.items()}
    cfg = {"hidden_size": hidden, "num_attention_heads": heads,
           "num_key_value_heads": kv_heads, "sliding_window": 16,
           "num_hidden_layers": 8, "layer_norm_eps": 1e-5}
    with jax.default_matmul_precision("highest"):
        h = jnp.asarray(x[0].numpy())
        k, v = ref.project_kv(cfg, lp, h)
        got = ref.diff_attention(cfg, lp, h, k, v, layer)
    # float32 sums in two libraries' orders: against the outputs' size.
    np.testing.assert_allclose(got, want, rtol=1e-3,
                               atol=1e-5 * float(np.abs(want).max()))


# ----------------------------------------------------- refused by key, start
BASE = {"model_type": "phi4flash", "hidden_size": 512,
        "num_attention_heads": 8, "num_key_value_heads": 4,
        "num_hidden_layers": 8, "intermediate_size": 256, "vocab_size": 512,
        "sliding_window": 64, "mb_per_layer": 2,
        "tie_word_embeddings": True}


def test_the_base_config_is_served():
    mc = ModelConfig.from_hf_config(BASE, "base")
    assert mc.arch == "phi4flash" and mc.mamba_d_inner == 1024
    assert (mc.mamba_d_state, mc.mamba_d_conv, mc.mamba_dt_rank) == \
        (16, 4, 32)
    assert mc.sliding_window == 64 and mc.rope_theta is None


@pytest.mark.parametrize("key,value,said", [
    ("mb_per_layer", 1, "mb_per_layer"),
    ("mb_per_layer", 4, "mb_per_layer"),
    ("num_hidden_layers", 10, "num_hidden_layers"),
    ("num_hidden_layers", 4, "num_hidden_layers"),
    ("sliding_window", [64] * 8, "sliding_window"),
    ("sliding_window", 72, "sliding_window"),
    ("sliding_window", None, "sliding_window"),
    ("rope_scaling", {"type": "longrope"}, "rope_scaling"),
    ("rope_theta", 10000.0, "rope_theta"),
    ("partial_rotary_factor", 0.5, "partial_rotary_factor"),
    ("mamba_d_state", 12, "mamba_d_state"),
    ("mamba_d_state", 128, "mamba_d_state"),
    ("mamba_expand", 0.3, "mamba_expand"),
    ("mamba_d_conv", 1, "mamba_d_conv"),
    ("mamba_dt_rank", 0, "mamba_dt_rank"),
    ("mamba_proj_bias", True, "mamba_proj_bias"),
    ("num_key_value_heads", 3, "num_key_value_heads"),
    ("tie_word_embeddings", False, "tie_word_embeddings"),
    ("mlp_bias", True, "mlp_bias"),
    ("lm_head_bias", True, "lm_head_bias"),
    ("hidden_act", "gelu", "hidden_act"),
])
def test_what_the_module_does_not_implement_is_refused_by_its_key(
        key, value, said):
    with pytest.raises(ValueError, match=said):
        ModelConfig.from_hf_config({**BASE, key: value}, "refused")


@pytest.mark.parametrize("flag,said", [
    (dict(tensor_parallel_size=2), "tensor"),
    (dict(kv_cache_dtype="int8"), "int8"),
    (dict(sequence_parallel_size=2), "sequence"),
    (dict(speculative_num_tokens=3, speculative_model="tiny-llama"),
     "specul"),
])
def test_what_no_state_can_follow_is_refused_at_start(flag, said):
    with pytest.raises(ValueError, match=f"(?i){said}"):
        make_engine(**flag)


def test_lora_is_refused_beside_this_model(tmp_path):
    with pytest.raises(ValueError, match="(?i)lora"):
        make_engine(lora_modules={"a": str(tmp_path)})


# ------------------------------------------------ the benchmark's config
def test_config_json_holds_the_catalogs_numbers():
    """Every key of the catalog's row under its name and with its value;
    what the file adds is Mamba-1's defaults (``assumed``)."""
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Phi-4-mini-flash-reasoning")
    with open(os.path.join(CONFIG_DIR, "config.json")) as f:
        mine = json.load(f)
    assert {k: mine[k] for k in row["config"]} == row["config"]
    assert set(mine) - set(row["config"]) == {
        "mamba_d_state", "mamba_d_conv", "mamba_expand", "mamba_dt_rank",
        "mamba_conv_bias", "mamba_proj_bias"}
    with open(os.path.join(CONFIG_DIR, "deployment.json")) as f:
        deployment = json.load(f)
    assert deployment["source"] == row["source_url"]
    assert deployment["reduced"] == {} and deployment["depth"] == 32
    for key in ("mamba_d_state", "mamba_d_conv", "mamba_expand",
                "mamba_dt_rank"):
        assert key in deployment["assumed"]


def test_the_published_config_resolves_to_the_published_sizes():
    mc = resolve_model_config(CONFIG_DIR)
    assert (mc.arch, mc.num_layers, mc.hidden_size, mc.vocab_size) == \
        ("phi4flash", 32, 2560, 200064)
    assert (mc.num_heads, mc.num_kv_heads, mc.head_dim_) == (40, 20, 64)
    assert (mc.mamba_d_inner, mc.mamba_d_state, mc.mamba_dt_rank,
            mc.mamba_d_conv) == (5120, 16, 160, 4)
    assert mc.sliding_window == 512 and mc.tie_word_embeddings
    shapes = jax.eval_shape(
        lambda k: get_model(mc).init_params(mc, k), jax.random.PRNGKey(0))
    count = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert count == 3_852_562_944


# ------------------------------------------------------ a checkpoint's names
def test_a_checkpoint_under_the_assumed_names_loads_to_the_same_logits(
        tmp_path):
    """A tiny tree written as an HF checkpoint under the ASSUMED leaf names
    (fused ``attn.Wqkv``, ``attn.Wq`` of a cross layer, ``A_log`` as [D, N],
    the conv as [D, 1, K], no ``lm_head``) loads to the same logits."""
    safetensors = pytest.importorskip("safetensors.numpy")
    from production_stack_tpu.models.weights import load_hf_params

    mc = TINY_PHI4FLASH
    params = get_model(mc).init_params(mc, jax.random.PRNGKey(6), F32)
    back = {ours: (suffix, tr)
            for suffix, (ours, tr) in phi4flash.HF_LAYER_MAP.items()}
    back["wqkv"] = ("attn.Wqkv.weight", True)
    back["bqkv"] = ("attn.Wqkv.bias", False)
    tensors = {}
    for i, slot in enumerate(phi4flash.layer_slots(mc)):
        for leaf, (kind, at) in slot.items():
            x = np.asarray(params["layers"][kind][leaf][at])
            suffix, tr = back[leaf]
            if kind == "cross" and leaf in ("wqkv", "bqkv"):
                suffix = suffix.replace("Wqkv", "Wq")
            if leaf == "conv_w":
                x = x[:, None, :]
            tensors[f"model.layers.{i}.{suffix}"] = np.ascontiguousarray(
                x.T if tr else x)
    for name, (ours, tr) in phi4flash.HF_TOP_MAP.items():
        tensors[name] = np.asarray(params[ours])
    safetensors.save_file(tensors, str(tmp_path / "model.safetensors"))
    with open(tmp_path / "config.json", "w") as f:
        json.dump(hf_config(mc), f)
    loaded = load_hf_params(resolve_model_config(str(tmp_path)),
                            str(tmp_path), F32)
    assert "lm_head" not in loaded
    t = 30
    ids = jnp.asarray(prompt(t, 3))[None]
    pos = jnp.arange(t, dtype=jnp.int32)[None]
    outs = [phi4flash.forward(p, mc, ids, pos, jnp.asarray([t]))[0]
            for p in (params, loaded)]
    np.testing.assert_allclose(outs[0], outs[1], rtol=1e-5, atol=1e-5)


# ----------------------------------------------------- the served surface
async def test_the_served_surface_names_the_caches_and_the_counters():
    """``GET /version`` and every line of ``GET /debug/programs`` say which
    layers keep a ring or a scan's state, their shapes, the ONE pooled
    layer and its readers; a prefill line says which execution of the scan
    it holds; ``GET /metrics`` moves the two ``pstpu:ring_keys_*`` counters
    by the closed form of the request's prompt and answer; ``GET
    /debug/memory`` enters the four state pools by name."""
    from aiohttp.test_utils import TestClient, TestServer

    from production_stack_tpu.server.api_server import APIServer

    eng = make_engine(max_model_len=512, num_kv_blocks=64)
    mc = eng.model_config
    client = TestClient(TestServer(APIServer(eng).build_app()))
    await client.start_server()
    n, out = 150, 9
    try:
        done = await client.post("/v1/completions", json={
            "model": mc.name, "prompt": prompt(n, 90), "max_tokens": out,
            "temperature": 0, "ignore_eos": True})
        assert done.status == 200
        text = await (await client.get("/metrics")).text()
        programs = (await (await client.get("/debug/programs")).json())[
            "programs"]
        version = await (await client.get("/version")).json()
        memory = await (await client.get("/debug/memory")).json()
    finally:
        await client.close()
    sample = {ln.split("{")[0]: float(ln.rsplit(" ", 1)[1])
              for ln in text.splitlines() if ln.startswith("pstpu:")}
    # The out - 1 decode queries sit at positions n .. n + out - 2: two
    # window layers hold min(position + 1, 64) keys of position + 1.
    assert sample["pstpu:ring_keys_held_total"] == 2 * (out - 1) * W
    assert sample["pstpu:ring_keys_context_total"] == 2 * sum(
        range(n + 1, n + out))
    assert {p["program"] for p in programs} == {"decode", "prefill"}
    assert {p["program"]: p.get("s6_chunk") for p in programs} == {
        "decode": None, "prefill": "xla"}
    assert {p["program"]: p.get("ring_step") for p in programs} == {
        "decode": "xla", "prefill": None}
    for said in (*programs, version["engine"]):
        assert said["window_layers"] == [1, 3]
        assert said["ring"] == {"ring_k": [2, W, 128], "ring_v": [2, W, 128]}
        assert said["scan_layers"] == [0, 2, 4]
        assert said["scan_state"] == {"s6": [16, 1024], "conv": [24, 128]}
        assert said["paged_layer"] == 5
        assert said["paged_layer_readers"] == [5, 7]
        assert said["memory_layer"] == 4 and said["memory_readers"] == [6]
    slots = eng.runner.num_state_slots
    assert memory["state_pools"] == {
        "ring_k": slots * 2 * 2 * W * 128 * 4,
        "ring_v": slots * 2 * 2 * W * 128 * 4,
        "s6": slots * 3 * 16 * 1024 * 4,
        "conv": slots * 3 * 24 * 128 * 4}
    assert sum(memory["state_pools"].values()) == \
        memory["residents"]["state"]


def test_a_row_cap_that_is_no_power_of_two_is_a_warmed_bucket():
    """``--max-num-seqs 48`` (this configuration's): a train of 33 to 48
    rows runs in the bucket of 48, which warm-up has to compile (it warmed
    the powers of two alone, and the first such train compiled while
    serving: PERF.md section 6, PR 54)."""
    from production_stack_tpu.engine.runner import _bucket

    eng = make_engine(max_num_seqs=6, max_prefill_seqs=6)
    rows = {f[0] for f in eng.runner.reachable_decode_families()}
    assert rows == {1, 2, 4, 6}
    assert {_bucket(n, 1, 6) for n in range(1, 7)} == rows
    same = make_engine(max_num_seqs=8)
    assert {f[0] for f in same.runner.reachable_decode_families()} == \
        {1, 2, 4, 8}
