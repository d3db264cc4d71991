"""The pieces under the Phi-4-mini-flash family: the selective scan's chunk
against its step (and the Pallas kernel against the ``jnp`` form, interpret
mode), the packed differential attention against the reference's four
softmaxes, who reads the one paged layer, and the reference's own S6 mixer and
differential attention against ``transformers``. tests/test_phi4flash.py
holds the whole forward to the reference.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.models import get_model, phi4flash
from production_stack_tpu.models.config import (
    TINY_PHI4FLASH,
    resolve_model_config,
)
from production_stack_tpu.ops import attention as att
from production_stack_tpu.ops import selective_scan as s6
from production_stack_tpu.ops.pallas.selective_scan import (
    s6_chunk_kernel,
    supports_chunk_kernel,
)
from tests.phi4flash_helpers import (
    CONFIG_DIR,
    F32,
    TOL,
    hf_config,
    prompt,
    ref,
)


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
