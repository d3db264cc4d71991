"""Xing4.0 against the reference through engines a case builds for itself: five
rows of unequal length in one prefill, a packed prefill row against the
rectangle, and the served surface. tests/test_xing4.py says what is compared
and why TOL.
"""

import sys

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.engine import ServingEngine
from production_stack_tpu.models.config import TINY_XING4
from tests.xing4_helpers import TOL, add, drive, make_engine, prompt, worst


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


def test_g_a_packed_prefill_row_serves_what_the_rectangle_serves(monkeypatch):
    """tests/test_deepseek_v3_rows.py's, over four residual streams: the mix is
    a function of a token, so a packed row runs it as any row."""
    from tests.deepseek_v3_helpers import packed_row_against_rectangle

    packed_row_against_rectangle(
        monkeypatch, TINY_XING4, sys.modules[__name__])


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
