"""Profile the fused decode dispatch component-by-component on the real TPU.

Answers VERDICT r2 weak #1: where do the ~32 ms/step go at llama-1b, B=16?
Run: python scripts/profile_decode.py
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.sampling import sample_tokens
from production_stack_tpu.models import get_model
from production_stack_tpu.models.config import resolve_model_config
from production_stack_tpu.ops.attention import KVView, gather_window

MODEL = "llama-1b"
B = 16
S = 1024          # live context per sequence
K = 32            # fused steps
BS = 16           # block size


def timed(fn, *args, n=10, **kw):
    out = fn(*args, **kw)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args, **kw)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1000, out


def main():
    mc = resolve_model_config(MODEL)
    model = get_model(mc)
    init_fn, forward, logits_fn = (
        model.init_params, model.forward, model.compute_logits)
    params = init_fn(mc, jax.random.PRNGKey(0), jnp.bfloat16)
    params = jax.device_put(params)
    nl, hkv, dh = mc.num_layers, mc.num_kv_heads, mc.head_dim_
    nslots = B * S + BS
    kv_k = jnp.zeros((nl, hkv, nslots, dh), jnp.bfloat16)
    kv_v = jnp.zeros((nl, hkv, nslots, dh), jnp.bfloat16)
    mb = S // BS
    bt = np.zeros((B, mb), np.int32)
    for i in range(B):
        bt[i] = np.arange(1 + i * mb, 1 + (i + 1) * mb)
    bt = jnp.asarray(bt * 0 + bt)  # device
    nbytes = lambda *arrs: sum(a.size * a.dtype.itemsize for a in arrs)

    pbytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    print(f"model={MODEL} params={pbytes/2**30:.2f} GiB "
          f"kv_live={nbytes(kv_k, kv_v)/2**30:.2f} GiB B={B} S={S} K={K}")

    # 1. gather_window alone
    gw = jax.jit(lambda k, v, t: gather_window(k, v, t, BS))
    ms, (wk, wv) = timed(gw, kv_k, kv_v, bt)
    wbytes = nbytes(wk, wv)
    print(f"gather_window: {ms:8.2f} ms  window={wbytes/2**30:.2f} GiB "
          f"({wbytes/ms*1e3/2**30:.0f} GiB/s eff)")

    win_len = jnp.full((B,), S, jnp.int32)
    toks = jnp.zeros((B, 1), jnp.int32)
    pos = jnp.full((B, 1), S, jnp.int32)
    ones = jnp.ones((B,), jnp.int32)
    ring_k = jnp.zeros((nl, hkv, B, K, dh), jnp.bfloat16)
    ring_v = jnp.zeros((nl, hkv, B, K, dh), jnp.bfloat16)
    ring_pos = jnp.full((B, K), 2**30, jnp.int32)

    # 2. single forward (1 token, with window + ring)
    fwd = jax.jit(lambda p, t, po, wk, wv, rk, rv, rp: forward(
        p, mc, t, po, ones, KVView(wk, wv, win_len, rk, rv, rp)))
    ms, (hidden, k_new, v_new) = timed(
        fwd, params, toks, pos, wk, wv, ring_k, ring_v, ring_pos)
    need = pbytes - 2 * mc.vocab_size * mc.hidden_size + wbytes
    print(f"forward(1tok): {ms:8.2f} ms  min_traffic={need/2**30:.2f} GiB "
          f"-> {need/ms*1e3/2**30:.0f} GiB/s eff")

    # 3. logits
    lg = jax.jit(lambda p, h: logits_fn(p, mc, h[:, 0]))
    ms, logits = timed(lg, params, hidden)
    hb = 2 * mc.vocab_size * mc.hidden_size
    print(f"logits:        {ms:8.2f} ms  head={hb/2**30:.2f} GiB "
          f"-> {hb/ms*1e3/2**30:.0f} GiB/s eff")

    # 4. sampling
    temps = jnp.ones((B,), jnp.float32)
    tk = jnp.full((B,), -1, jnp.int32)
    tp = jnp.ones((B,), jnp.float32)
    seeds = jnp.arange(B, dtype=jnp.uint32)
    ms, _ = timed(sample_tokens, logits, temps, tk, tp, seeds)
    print(f"sample:        {ms:8.2f} ms")

    # 4b. greedy-only argmax
    ms, _ = timed(jax.jit(lambda l: jnp.argmax(l, -1)), logits)
    print(f"argmax only:   {ms:8.2f} ms")

    # 5. full fused scan (forward+logits+sample+ring update) x K
    def fused(params, toks0, kv_k, kv_v, bt):
        wk, wv = gather_window(kv_k, kv_v, bt, BS)

        def body(carry, j):
            t, rk, rv, rp = carry
            po = (pos + j)
            h, kn, vn = forward(params, mc, t, po, ones,
                                KVView(wk, wv, win_len, rk, rv, rp))
            lgt = logits_fn(params, mc, h[:, 0])
            nxt = sample_tokens(lgt, temps, tk, tp, seeds)
            rk = jax.lax.dynamic_update_slice(rk, kn, (0, 0, 0, j, 0))
            rv = jax.lax.dynamic_update_slice(rv, vn, (0, 0, 0, j, 0))
            rp = jax.lax.dynamic_update_slice(rp, po, (0, j))
            return (nxt[:, None].astype(jnp.int32), rk, rv, rp), nxt

        (_, rk, rv, _), out = jax.lax.scan(
            body, (toks0, ring_k, ring_v, ring_pos),
            jnp.arange(K, dtype=jnp.int32))
        return out, rk, rv

    fj = jax.jit(fused)
    ms, _ = timed(fj, params, toks, kv_k, kv_v, bt, n=5)
    print(f"fused K={K}:    {ms:8.2f} ms  -> {ms/K:.2f} ms/step "
          f"-> {B*K/(ms/1e3):.0f} tok/s")

    # 6. forward WITHOUT window (weights only ceiling)
    fwd0 = jax.jit(lambda p, t, po, rk, rv, rp: forward(
        p, mc, t, po, ones, KVView(ring_k=rk, ring_v=rv, ring_pos=rp)))
    ms, _ = timed(fwd0, params, toks, pos, ring_k, ring_v, ring_pos)
    print(f"forward-nowin: {ms:8.2f} ms")

    with jax.profiler.trace("/tmp/jax-trace"):
        out = fj(params, toks, kv_k, kv_v, bt)
        jax.block_until_ready(out)
    print("trace written to /tmp/jax-trace")


if __name__ == "__main__":
    main()
