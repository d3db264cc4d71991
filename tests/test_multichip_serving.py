"""Multi-chip SERVING correctness (docs/PERF.md round 9).

MULTICHIP_r01-r05 were dryrun parity checks; this file certifies the
serving path itself on the virtual 8-device CPU mesh (tests/conftest.py):
tp2 output served through the HTTP API must be token-identical to tp1
(greedy AND seeded), a KV bundle spilled from a tp2-sharded pool must
restore bit-exactly into tp1 and tp4 pools (the shared tier from PR 8 must
not fracture the fleet by mesh shape), the tp>1 config combos fail at
parse time with errors naming the flags, both metrics renderers export the
mesh telemetry, and tools/capacity.py turns the recorded scaling curve
into a chips->QPS table.

The slow-marked test is the real-engine version of the served-parity bar:
api_server subprocesses on a forced multi-device platform behind the real
router (the CI "Multichip serving" step runs it).
"""

import json
import os

import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.engine import ServingEngine
from production_stack_tpu.parallel.mesh import make_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cfg(tp=1, **kw):
    # float32 exactly like the dryrun/parity suites: bf16 collective
    # reordering could flip argmax ties and mask a real sharding bug.
    base = dict(
        model="tiny-llama-8kv", dtype="float32", max_model_len=256,
        block_size=4, num_kv_blocks=128, max_num_seqs=8,
        max_num_batched_tokens=64, num_decode_steps=4, attn_impl="xla",
        tensor_parallel_size=tp,
    )
    base.update(kw)
    return EngineConfig(**base)


async def _serve(cfg):
    from production_stack_tpu.server.api_server import APIServer

    engine = ServingEngine(cfg)
    client = TestClient(TestServer(APIServer(engine).build_app()))
    await client.start_server()
    return engine, client


async def _completion_text(client, *, temperature, seed=None, prompt=None):
    body = {
        "model": "tiny-llama-8kv",
        "prompt": prompt or "the quick brown fox jumps over the lazy dog "
                            "and keeps on running through the field",
        "max_tokens": 12, "temperature": temperature, "ignore_eos": True,
    }
    if seed is not None:
        body["seed"] = seed
    resp = await client.post("/v1/completions", json=body)
    assert resp.status == 200, await resp.text()
    out = await resp.json()
    assert out["choices"][0]["finish_reason"] == "length"
    return out["choices"][0]["text"]


# ------------------------------------------------------- served parity bar
async def test_tp2_served_parity_http():
    """tp2 through the HTTP API == tp1, greedy AND seeded — the fast
    (in-process, virtual-device) version of the serving parity bar."""
    eng2, tp2 = await _serve(_cfg(tp=2))
    eng1, tp1 = await _serve(_cfg(tp=1))
    try:
        # The pool must actually be sharded (not silently replicated).
        shard_heads = eng2.runner.kv_k.addressable_shards[0].data.shape[1]
        assert shard_heads == eng2.model_config.num_kv_heads // 2
        for kwargs in (
            {"temperature": 0},
            {"temperature": 0.8, "seed": 1234},
        ):
            a = await _completion_text(tp2, **kwargs)
            b = await _completion_text(tp1, **kwargs)
            assert a == b, (kwargs, a, b)
    finally:
        await tp2.close()
        await tp1.close()


# --------------------------------------- spill/restore mesh independence
def _runner(tp, kv_cache_dtype="bfloat16"):
    from production_stack_tpu.engine.runner import ModelRunner
    from production_stack_tpu.models.config import resolve_model_config

    cfg = _cfg(tp=tp, kv_cache_dtype=kv_cache_dtype, num_kv_blocks=32)
    return ModelRunner(
        cfg, resolve_model_config(cfg.model), make_mesh(1, 1, tp)
    )


@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
def test_tp2_spill_restores_bit_exactly_on_tp1_and_tp4(kv_dtype):
    """A bundle spilled from a tp2 kv-head-sharded pool must restore
    BIT-EXACTLY into tp1 and tp4 pools through the PKV1/PKV2 wire format:
    the wire blob carries the full logical [n, L, Hkv, bs, Dh] block, so
    the shared tier (PR 8) never fractures by mesh shape."""
    from production_stack_tpu.kv_offload.serde import (
        pack_block,
        unpack_block,
    )

    writer = _runner(2, kv_dtype)
    mc = writer.model_config
    bs = writer.config.block_size
    blocks = [3, 7, 11]
    rng = np.random.default_rng(42)
    shape = (len(blocks), mc.num_layers, mc.num_kv_heads, bs, mc.head_dim_)
    if kv_dtype == "int8":
        k_host = rng.integers(-127, 128, size=shape, dtype=np.int8)
        v_host = rng.integers(-127, 128, size=shape, dtype=np.int8)
        import ml_dtypes

        sshape = shape[:-1]
        ks_host = rng.random(sshape, np.float32).astype(ml_dtypes.bfloat16)
        vs_host = rng.random(sshape, np.float32).astype(ml_dtypes.bfloat16)
    else:
        # Unquantized pools store the COMPUTE dtype (float32 in this
        # config); the wire dtype rides the PKV1 header either way.
        store = np.dtype(writer.kv_store_dtype)
        k_host = rng.standard_normal(shape).astype(store)
        v_host = rng.standard_normal(shape).astype(store)
        ks_host = vs_host = None

    # Seed the tp2 pool with known KV, then spill it block by block.
    writer.write_blocks(blocks, k_host, v_host, ks_host, vs_host)
    k2, v2, ks2, vs2 = writer.read_blocks(blocks)
    np.testing.assert_array_equal(k2.view(np.uint8), k_host.view(np.uint8))
    wire = [
        pack_block(
            k2[i], v2[i],
            None if ks2 is None else ks2[i],
            None if vs2 is None else vs2[i],
        )
        for i in range(len(blocks))
    ]

    for reader_tp in (1, 4):
        reader = _runner(reader_tp, kv_dtype)
        parts = [unpack_block(b) for b in wire]
        reader.write_blocks(
            blocks,
            np.stack([p[0] for p in parts]),
            np.stack([p[1] for p in parts]),
            None if parts[0][2] is None
            else np.stack([p[2] for p in parts]),
            None if parts[0][3] is None
            else np.stack([p[3] for p in parts]),
        )
        k_r, v_r, ks_r, vs_r = reader.read_blocks(blocks)
        np.testing.assert_array_equal(
            k_r.view(np.uint8), k_host.view(np.uint8),
            err_msg=f"K spill tp2 -> restore tp{reader_tp} not bit-exact",
        )
        np.testing.assert_array_equal(
            v_r.view(np.uint8), v_host.view(np.uint8),
            err_msg=f"V spill tp2 -> restore tp{reader_tp} not bit-exact",
        )
        if kv_dtype == "int8":
            np.testing.assert_array_equal(
                ks_r.view(np.uint8), ks_host.view(np.uint8)
            )
            np.testing.assert_array_equal(
                vs_r.view(np.uint8), vs_host.view(np.uint8)
            )


# ------------------------------------------------- parse-time validation
def test_spec_plus_tp_config_error_names_both_flags():
    with pytest.raises(ValueError) as e:
        EngineConfig(
            model="tiny-llama", tensor_parallel_size=2,
            speculative_num_tokens=3, speculative_model="tiny-llama",
        )
    msg = str(e.value)
    assert "--speculative-num-tokens" in msg
    assert "--tensor-parallel-size" in msg


def test_int8_tp_indivisible_heads_is_clean_config_error():
    # tiny-llama has 4/2 heads: tp4 cannot shard the scale pools.
    with pytest.raises(ValueError) as e:
        EngineConfig(
            model="tiny-llama", kv_cache_dtype="int8",
            tensor_parallel_size=4,
        )
    msg = str(e.value)
    assert "--kv-cache-dtype int8" in msg
    assert "--tensor-parallel-size" in msg
    # The divisible pairing constructs fine (8/8 heads, tp4).
    EngineConfig(
        model="tiny-llama-8kv", kv_cache_dtype="int8",
        tensor_parallel_size=4,
    )


# ------------------------------------------------------- mesh telemetry
async def test_mesh_metrics_rendered():
    from production_stack_tpu.server.metrics import render_engine_metrics

    engine = ServingEngine(_cfg(tp=2))
    text = render_engine_metrics(engine, "tiny-llama-8kv")
    assert 'pstpu:mesh_tp_size{model_name="tiny-llama-8kv"} 2' in text
    assert 'pstpu:mesh_sp_size{model_name="tiny-llama-8kv"} 1' in text
    assert 'pstpu:mesh_devices{model_name="tiny-llama-8kv"} 2' in text
    # Per-device residency: one series per mesh device, each holding half
    # the (kv-head-sharded) pool.
    dev_lines = [
        ln for ln in text.splitlines()
        if ln.startswith("pstpu:hbm_resident_bytes{")
        and 'holder="kv"' in ln
    ]
    assert len(dev_lines) == 2, dev_lines
    per_dev = [int(float(ln.rsplit(" ", 1)[1])) for ln in dev_lines]
    assert sum(per_dev) == engine.runner.kv_pool_bytes
    assert per_dev[0] == per_dev[1]
    assert all(f'device="cpu:{i}"' in dev_lines[i] for i in range(2))


# ------------------------------------------------------- capacity model
def _synthetic_report():
    return {
        "model": "llama-1b",
        "backend": "tpu",
        "workload": {"users": 16, "max_tokens": 100},
        "curve": [
            {"chips": 1, "tok_s": 1000.0, "tok_per_s_per_chip": 1000.0,
             "scaling_efficiency": 1.0},
            {"chips": 2, "tok_s": 1800.0, "tok_per_s_per_chip": 900.0,
             "scaling_efficiency": 0.9},
            {"chips": 4, "tok_s": 3200.0, "tok_per_s_per_chip": 800.0,
             "scaling_efficiency": 0.8},
        ],
        "runs": [
            {"total_output_tokens": 8000, "finished_requests": 80,
             "qps": 4.0},
            {"total_output_tokens": 8000, "finished_requests": 80,
             "qps": 7.2},
            {"total_output_tokens": 8000, "finished_requests": 80,
             "qps": 12.8},
        ],
    }


def test_capacity_model_math():
    from tools.capacity import capacity_model, engines_for_qps

    model = capacity_model(_synthetic_report(), slo_headroom=0.9,
                           max_engines=4)
    assert model["per_chip_goodput_tok_s"] == 1000.0
    assert model["tokens_per_request"] == 100.0
    one = next(r for r in model["table"] if r["chips"] == 1)
    # 1000 tok/s * 0.9 / 100 tok/req = 9 QPS.
    assert one["qps_capacity"] == pytest.approx(9.0)
    four = next(r for r in model["table"] if r["chips"] == 4 and r["measured"])
    assert four["qps_capacity"] == pytest.approx(3200 * 0.9 / 100)
    # The best per-chip shape here is the 1-chip mesh; replicas scale it.
    assert model["best_mesh_chips"] == 1
    extrap = [r for r in model["table"] if not r["measured"]]
    assert extrap and all(
        r["qps_capacity"] == pytest.approx(r["engines"] * 9.0)
        for r in extrap
    )
    assert model["hpa_targets"]["pstpu_queue_depth_per_engine"] >= 1
    prov = engines_for_qps(model, 25.0)
    assert prov["engines"] == 3 and prov["qps_capacity"] >= 25.0


def test_capacity_model_reproduces_recorded_artifact():
    """Acceptance bar: tools/capacity.py reproduces a chips->QPS table
    from the recorded MULTICHIP serving artifact."""
    path = os.path.join(REPO, "MULTICHIP_r06.json")
    if not os.path.exists(path):
        pytest.skip("MULTICHIP_r06.json not recorded in this tree")
    from tools.capacity import capacity_model

    with open(path) as f:
        report = json.load(f)
    assert report.get("serving") is True
    assert report.get("zero_5xx") is True
    chips = [pt["chips"] for pt in report["curve"]]
    assert chips == [1, 2, 4, 8]
    model = capacity_model(report)
    measured = [r for r in model["table"] if r["measured"]]
    assert [r["chips"] for r in measured] == [1, 2, 4, 8]
    assert all(r["qps_capacity"] > 0 for r in model["table"])


# ------------------------------------------------- real-engine (slow) bar
@pytest.mark.slow
def test_tp2_served_parity_real_engines():
    """The real-engine version: api_server subprocesses on a forced
    multi-device platform behind the real router — tp2 greedy and seeded
    completions byte-identical to tp1 through the full stack."""
    import urllib.request

    from benchmarks.stack import launch_stack

    env = {
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8"
        ).strip(),
    }

    def serve_once(tp):
        stack = launch_stack(
            "tiny-llama-8kv",
            engine_args=[
                "--dtype", "float32", "--max-model-len", "256",
                "--num-kv-blocks", "128", "--attn-impl", "xla",
                "--max-num-batched-tokens", "64", "--no-warmup",
            ],
            routing_logic="roundrobin",
            tensor_parallel_size=tp,
            engine_env=env,
            startup_timeout_s=600.0,
        )
        try:
            outs = []
            for body in (
                {"temperature": 0},
                {"temperature": 0.8, "seed": 77},
            ):
                req = urllib.request.Request(
                    f"{stack.router_url}/v1/completions",
                    data=json.dumps({
                        "model": "tiny-llama-8kv",
                        "prompt": "pack my box with five dozen jugs",
                        "max_tokens": 8, "ignore_eos": True, **body,
                    }).encode(),
                    headers={"Content-Type": "application/json"},
                )
                with urllib.request.urlopen(req, timeout=300) as resp:
                    assert resp.status == 200
                    outs.append(json.loads(resp.read()))
            # Mesh telemetry is live on the served engine.
            with urllib.request.urlopen(
                f"{stack.engine_urls[0]}/metrics", timeout=30
            ) as resp:
                metrics = resp.read().decode()
            return outs, metrics
        finally:
            stack.terminate()

    tp2_outs, tp2_metrics = serve_once(2)
    tp1_outs, _ = serve_once(1)
    for a, b in zip(tp2_outs, tp1_outs):
        assert a["choices"][0]["text"] == b["choices"][0]["text"]
    assert "pstpu:mesh_tp_size" in tp2_metrics
    assert ('pstpu:hbm_resident_bytes{model_name="tiny-llama-8kv",'
            'holder="kv",device="cpu:0"}') in tp2_metrics
