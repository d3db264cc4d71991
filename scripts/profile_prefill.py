"""Prefill dispatch profiling at realistic chunked shapes on the real TPU.
Run: chiprun -- env PYTHONPATH=. python scripts/profile_prefill.py
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from production_stack_tpu.models import get_model
from production_stack_tpu.models.config import resolve_model_config
from production_stack_tpu.ops.attention import KVView, gather_window

MODEL = "llama-1b"
BS = 16


def timed(fn, *args, n=5, **kw):
    """args[1] (token ids) is varied per call so no two dispatches are the
    same computation; each call is blocked individually so per-dispatch
    latency is real."""
    out = fn(args[0], args[1], *args[2:], **kw)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for i in range(n):
        out = fn(args[0], args[1] + i + 1, *args[2:], **kw)
        jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1000, out


def main():
    mc = resolve_model_config(MODEL)
    model = get_model(mc)
    init_fn, forward, logits_fn = (
        model.init_params, model.forward, model.compute_logits)
    params = jax.device_put(init_fn(mc, jax.random.PRNGKey(0), jnp.bfloat16))
    nl, hkv, dh = mc.num_layers, mc.num_kv_heads, mc.head_dim_

    for b, t, hist in [(8, 64, 1024), (8, 128, 1024), (8, 512, 2048),
                       (4, 512, 4096), (8, 512, 0), (1, 4096, 0)]:
        nslots = max(b * (hist + t) + BS, BS * 2)
        kv_k = jnp.zeros((nl, hkv, nslots, dh), jnp.bfloat16)
        kv_v = jnp.zeros((nl, hkv, nslots, dh), jnp.bfloat16)
        mb = max(1, (hist + t) // BS)
        bt = np.zeros((b, mb), np.int32)
        for i in range(b):
            bt[i] = np.arange(1 + i * mb, 1 + (i + 1) * mb)
        bt = jnp.asarray(bt)
        toks = jnp.zeros((b, t), jnp.int32)
        pos = hist + jnp.broadcast_to(jnp.arange(t)[None], (b, t))
        lens = jnp.full((b,), t, jnp.int32)

        if hist > 0:
            def full(params, toks, pos, lens, kv_k, kv_v, bt):
                wk, wv = gather_window(kv_k, kv_v, bt, BS)
                wl = jnp.full((b,), hist, jnp.int32)
                h, kn, vn = forward(params, mc, toks, pos, lens,
                                    KVView(wk, wv, wl))
                lg = logits_fn(params, mc, h[jnp.arange(b), lens - 1])
                return lg, kn, vn

            gw = jax.jit(lambda k, v, tb: gather_window(k + 0.0, v, tb, BS))
            gms, w = timed(gw, kv_k, kv_v, bt)
            wbytes = sum(x.size * x.dtype.itemsize for x in w)
            fms, _ = timed(jax.jit(full), params, toks, pos, lens,
                           kv_k, kv_v, bt)
            print(f"b={b} t={t} hist={hist}: full={fms:7.1f} ms "
                  f"gather={gms:6.1f} ms win={wbytes/2**30:.2f} GiB "
                  f"-> {b*t/fms*1000:.0f} tok/s")
        else:
            def nowin(params, toks, pos, lens):
                h, kn, vn = forward(params, mc, toks, pos, lens)
                lg = logits_fn(params, mc, h[jnp.arange(b), lens - 1])
                return lg, kn, vn

            fms, _ = timed(jax.jit(nowin), params, toks, pos, lens)
            print(f"b={b} t={t} hist={hist}: full={fms:7.1f} ms "
                  f"-> {b*t/fms*1000:.0f} tok/s")


if __name__ == "__main__":
    main()
