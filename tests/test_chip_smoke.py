"""chip_smoke.py cannot pass without a chip.

The script's verdict is taken from what the serving engine reports about
itself, so the tests feed it reports: one that is right in every respect
passes, and each way of not being on the chip — a CPU platform, interpreted
kernels, no compile cache, a warmup stage that failed, a cut depth, a later
boot that never found the cache, a failed phase, a dispatch program that
copies a KV pool whole — fails it with a non-zero exit. The whole script also runs here, in the sandbox, where it must fail:
as the driver runs it, from a bare directory, and (slow) as a full CPU
rehearsal of every phase.
"""

import copy
import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, REPO) if REPO not in sys.path else None

import chip_smoke  # noqa: E402

TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1, "ids": [0],
       "visible_chips": "0"}


def _boot(**over):
    boot = {
        "engine_ready_s": 200.0, "attn_impl": "window",
        "pallas_interpret": False, "num_layers": chip_smoke.FULL_DEPTH,
        "warmup_families": 75, "warmup_failures": 0, "compile_s": 1.0,
        "warmup_s": 190.0, "cache_dir": "/cache", "cache_entries": 75,
        "cache_hit": 0, "cache_miss": 75, "kv_blocks": 513,
        "kv_shard_shape": [28, 8, 8208, 128], "bytes_in_use": {"0": 8 << 30},
        "device": dict(TPU),
    }
    boot.update(over)
    return boot


def _programs(**over):
    """GET /debug/programs of an engine whose pools are written in place."""
    return [{"program": kind, "family": fam, "pool_copies": 0,
             "temp_bytes": 300 << 20, "alias_bytes": 4 << 30,
             "pool_bytes": 2 << 30, **over}
            for kind, fam in (("decode", [4, 128, 32, False]),
                              ("prefill", [1, 256, 128, True]))]


def _good_lines():
    return [
        {"phase": "kernel", "ok": True, "interpret": False,
         "device": {k: TPU[k] for k in ("platform", "kind", "count")}},
        {"phase": "serve[auto]", "ok": True, "boot": _boot(),
         "pool_programs": _programs()},
        {"phase": "serve[paged]", "ok": True,
         "boot": _boot(attn_impl="paged", cache_hit=21, cache_miss=9),
         "pool_programs": _programs()},
    ]


def _with(path, value):
    """The good run with one thing wrong: ``path`` into the lines."""
    lines = _good_lines()
    node = lines
    for key in path[:-1]:
        node = node[key]
    node[path[-1]] = value
    return lines


BAD_RUNS = {
    "engine-on-cpu": _with((1, "boot", "device"),
                           {**TPU, "platform": "cpu", "kind": "cpu"}),
    "second-engine-on-cpu": _with((2, "boot", "device"),
                                  {**TPU, "platform": "cpu", "kind": "cpu"}),
    "kernel-on-cpu": _with((0, "device"),
                           {"platform": "cpu", "kind": "cpu", "count": 1}),
    "kernel-interpreted": _with((0, "interpret"), True),
    "engine-interpreted": _with((2, "boot", "pallas_interpret"), True),
    "no-compile-cache": _with((1, "boot", "cache_dir"), None),
    "warmup-stage-failed": _with((1, "boot", "warmup_failures"), 1),
    "nothing-warmed": _with((1, "boot", "warmup_families"), 0),
    "depth-cut": _with((1, "boot", "num_layers"), 2),
    # The second boot of ONE path (the four-chip run's replicas after its
    # reference) finds nothing cached.
    "cache-never-found-again": _good_lines() + [
        {"phase": "again[paged]", "ok": True,
         "boot": _boot(attn_impl="paged", cache_hit=0, cache_miss=30),
         "pool_programs": _programs()}],
    "phase-failed": _with((1, "ok"), False),
    "wrong-device-count": _with((2, "boot", "device"), {**TPU, "count": 4}),
    "nothing-served": _good_lines()[:1],
    "pool-copied-whole": _with((2, "pool_programs"),
                               _programs(pool_copies=4)),
    "no-program-audit": _with((2, "pool_programs"), []),
    # A model with recurrent state whose decode program holds the jnp form
    # of the step on a TPU (ops/gated_delta.py:gdn_step_at).
    "recurrence-step-not-the-kernel": _with((2, "pool_programs"),
                                            _programs(gdn_step="xla")),
    # An engine on a TPU whose prefill views hold the pool (its own
    # predicate, runner.prefill_reads_pool) but whose prefill program
    # still holds window_attention.
    "prefill-attention-not-the-kernel": _with(
        (2, "pool_programs"),
        _programs(prefill_attn="xla", prefill_reads_pool=True)),
}


def test_verdict_passes_a_run_that_is_right_in_every_respect():
    final = chip_smoke.verdict(_good_lines(), 1, chip_smoke.FULL_DEPTH)
    assert final == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}


def test_verdict_passes_two_cold_boots_of_different_paths():
    """A window engine and a paged engine share no program (the paged
    prefill holds the flash kernel): on a cold cache both miss everything
    and that is no fault; a repeated path with no hit is
    (``cache-never-found-again``)."""
    lines = _good_lines()
    lines[2]["boot"].update(cache_hit=0, cache_miss=15)
    assert chip_smoke.verdict(lines, 1, chip_smoke.FULL_DEPTH)["ok"]


@pytest.mark.parametrize("programs", [
    _programs(prefill_attn="pallas", prefill_reads_pool=True),
    # tp=4, an int8 pool, latent rows: a paged engine that gathers a
    # window by design says so, and "xla" is then no fault.
    _programs(prefill_attn="xla", prefill_reads_pool=False),
], ids=["reads-the-pool-through-the-kernel", "gathers-a-window-by-design"])
def test_verdict_judges_prefill_attention_by_the_engines_predicate(programs):
    lines = _good_lines()
    lines[2]["pool_programs"] = programs
    assert chip_smoke.verdict(lines, 1, chip_smoke.FULL_DEPTH)["ok"]


@pytest.mark.parametrize("what", sorted(BAD_RUNS))
def test_verdict_fails_when_not_on_the_chip(what, capsys):
    final = chip_smoke.verdict(copy.deepcopy(BAD_RUNS[what]), 1,
                               chip_smoke.FULL_DEPTH)
    assert final["ok"] is False
    faults = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert faults["phase"] == "verdict" and faults["faults"]


def test_verdict_passes_the_recurrences_kernel():
    lines = _with((2, "pool_programs"), _programs(gdn_step="pallas"))
    assert chip_smoke.verdict(lines, 1, chip_smoke.FULL_DEPTH)["ok"] is True


def test_verdict_passes_the_prefill_kernel_and_a_window_engines_xla():
    """``prefill_attn`` "pallas" passes; "xla" fails a paged engine only:
    the window path's prefill is window_attention by design."""
    lines = _with((2, "pool_programs"), _programs(prefill_attn="pallas"))
    lines[1]["pool_programs"] = _programs(prefill_attn="xla")
    assert lines[1]["boot"]["attn_impl"] == "window"
    assert chip_smoke.verdict(lines, 1, chip_smoke.FULL_DEPTH)["ok"] is True


def test_rehearsal_never_passes():
    final = chip_smoke.verdict(_good_lines(), 1, chip_smoke.FULL_DEPTH,
                               rehearsal=True)
    assert final["ok"] is False


def test_main_exits_nonzero_on_a_cpu_engine_report(monkeypatch, capsys):
    """End to end through main(): phases answered by an engine that says it
    is on a CPU -> last stdout line ``"ok": false``, exit code 1; the same
    phases from a TPU engine -> ``"ok": true``, exit code 0."""
    monkeypatch.setattr(sys, "path", list(sys.path))
    monkeypatch.setenv("PYTHONPATH", os.environ.get("PYTHONPATH", ""))
    good = _good_lines()
    served = iter(good[1:])
    monkeypatch.setattr(chip_smoke, "phase_kernel", lambda *a: good[0])
    monkeypatch.setattr(chip_smoke, "phase_serve", lambda *a: next(served))
    assert chip_smoke.main([]) == 0
    last = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert last == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}

    bad = BAD_RUNS["engine-on-cpu"]
    served = iter(bad[1:])
    monkeypatch.setattr(chip_smoke, "phase_kernel", lambda *a: bad[0])
    assert chip_smoke.main([]) == 1
    last = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert last["ok"] is False


@pytest.mark.parametrize("shape", chip_smoke.KERNEL_TIMING_SHAPES,
                         ids=lambda s: s["name"])
def test_kernel_timing_case_is_a_decode_bucket(shape):
    """The kernel phase times the benchmark's decode shapes: live rows
    first, then the bucket's padding as the runner packs it (``kv_len`` 0,
    table entries 0), every live page a block of its own, and the bytes
    counted are K and V of the live tokens."""
    import numpy as np

    case = chip_smoke.kernel_timing_case(shape, layers=1)
    lens, bt = np.asarray(case["kv_lens"]), np.asarray(case["tables"])
    live, bs = shape["live"], case["block_size"]
    assert lens.shape == (shape["rows"],) and bt.shape[0] == shape["rows"]
    assert np.all(lens[:live] >= shape["lens"][0])
    assert np.all(lens[:live] <= shape["lens"][1])
    assert np.all(lens[live:] == 0) and np.all(bt[live:] == 0)
    pages = -(-lens // bs)
    owned = np.concatenate([bt[i, :n] for i, n in enumerate(pages)])
    assert owned.min() >= 1 and len(set(owned.tolist())) == owned.size
    assert all(np.all(bt[i, n:] == 0) for i, n in enumerate(pages))
    assert case["q"].shape == (shape["rows"], shape["heads"], 128)
    assert case["k_pool"].shape[1] == shape["kv_heads"]
    assert case["kv_bytes"] == int(lens.sum()) * shape["kv_heads"] * 128 * 4


def test_time_kernel_chains_calls_through_the_query():
    """``calls`` kernel calls inside one program, each fed the last one's
    output: a time per call comes back, and the kernel ran (interpreted
    here; a rehearsal's time is never reported as the chip's)."""
    from production_stack_tpu.ops.pallas.paged_attention import (
        paged_flash_decode_stats,
    )

    shape = {"name": "toy", "rows": 3, "live": 2, "lens": (17, 40),
             "heads": 4, "kv_heads": 2}
    case = chip_smoke.kernel_timing_case(shape)
    sec = chip_smoke.time_kernel(paged_flash_decode_stats, case, calls=2,
                                 repeats=1, interpret=True)
    assert 0 < sec < 60


def _run_script(cwd, *argv, timeout=900):
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "chip_smoke.py"), *argv],
        cwd=cwd, capture_output=True, text=True, timeout=timeout,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )


@pytest.mark.parametrize("where", ["checkout", "bare-directory"])
def test_script_fails_without_an_accelerator(where, tmp_path):
    """As the driver runs it first: no arguments, no chip. Non-zero exit,
    no ``"ok": true`` anywhere, and llama-3b is not run on the CPU (the run
    takes seconds). In a directory that holds nothing but the script it
    must fail too."""
    cwd = REPO
    if where == "bare-directory":
        shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
        cwd = str(tmp_path)
    proc = _run_script(cwd, timeout=120)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["ok"] is False


@pytest.mark.slow
@pytest.mark.parametrize("chips", [1, 4])
def test_cpu_rehearsal_runs_every_phase_and_fails(chips):
    """``--rehearse``: every phase of the script on the CPU at a tiny model
    (JAX_PLATFORMS=cpu; four virtual devices stand in for four chips). The
    requests themselves must be answered correctly — a phase whose own
    checks fail here would fail on the chip too — and the last line is
    still ``"ok": false``."""
    proc = _run_script(REPO, "--rehearse", "--chips", str(chips))
    lines = [json.loads(ln) for ln in proc.stdout.splitlines()
             if ln.startswith("{")]
    assert proc.returncode != 0, proc.stdout[-2000:]
    assert lines[-1]["ok"] is False
    assert lines[-1]["device"]["platform"] == "cpu"
    phases = {ln["phase"]: ln for ln in lines if "phase" in ln}
    if chips == 1:
        want = ["kernel", "serve[auto]", "serve[paged]", "paths"]
        passing = want[:3]
        assert phases["serve[paged]"]["boot"]["cache_hit"] > 0
        assert phases["serve[auto]"]["prefix_hit_tokens"] > 0
    else:
        want = ["reference[1 chip]", "replicas[4 x 1 chip]",
                "tp4[1 x 4 chips]"]
        # Off-TPU the replicas cannot own distinct chips and the CPU
        # reports no bytes in use: those two phases fail on that alone.
        passing = want[:1]
        assert all(phases[want[1]]["served"])
        assert phases[want[1]]["matched_reference"] == \
            phases[want[1]]["served"]
        assert phases[want[2]]["kv_heads_per_device"] == 2
        assert lines[-1]["device"]["count"] == 4
    assert all(p in phases for p in want), sorted(phases)
    for name in passing:
        assert phases[name]["ok"], phases[name].get("faults") or \
            phases[name].get("error")


def test_gdn_phase_rehearses_on_the_cpu():
    """``--gdn --rehearse``: the recurrence-alone phase walks its code at a
    toy size on the CPU, reports the bytes and FLOPs it would be held to
    and no time."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), "--gdn",
         "--rehearse"], capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads([ln for ln in proc.stdout.splitlines()
                       if ln.startswith("{")][-1])
    assert line["phase"] == "gdn" and line["ok"]
    # Two step shapes; two chunk shapes, each as the kernel (interpreted
    # here) and as the ``jnp`` form, checked against each other.
    assert [(t["op"], t.get("form")) for t in line["timing"]] == \
        [("gdn_step", None)] * 2 + [("gdn_chunk", "kernel"),
                                    ("gdn_chunk", "jnp")] * 2
    assert [t["path"] for t in line["timing"][2:]] == ["pallas", None] * 2
    assert all(t["bytes"] > 0 and t["flops"] > 0
               and t["us_per_call"] is None for t in line["timing"])
    assert all(c["chunk_max_abs_err"] < 1e-4 for c in line["checks"][2:])


def test_prefill_phase_rehearses_on_the_cpu():
    """``--prefill --rehearse``: the prefill kernels (over K/V rows and
    over latent rows) at a packed row and at a rectangle, at a toy size on
    the CPU (interpret mode), checked against
    ``window_attention`` over the sequences taken apart; the FLOPs and
    bytes it would be held to, and no time."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), "--prefill",
         "--rehearse"], capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads([ln for ln in proc.stdout.splitlines()
                       if ln.startswith("{")][-1])
    assert line["phase"] == "prefill" and line["ok"] and line["interpret"]
    assert [(t["shape"], t["form"], t["rows"]) for t in line["timing"]] == [
        ("packed-chat-saturated", "packed", 1),
        ("packed-agent-prefix", "packed", 1),
        ("hybrid-16x128", "rectangle", 3), ("hybrid-1x2048", "rectangle", 1),
        ("granite-16x128", "rectangle", 3),
        ("granite-1x2048", "rectangle", 1),
        ("packed-latent-8x128", "packed", 1),
        ("packed-latent-chat-saturated", "packed", 1),
        ("latent-8x128", "rectangle", 3)]
    assert [c["shape"] for c in line["checks"]] == \
        [t["shape"] for t in line["timing"]]
    assert all(c["finite"] and c["max_abs_err"] <= c["bound"]
               for c in line["checks"])
    assert all(t["bytes"] > 0 and t["flops"] > 0
               and t["us_per_call"] is None for t in line["timing"])
    # A packed row's segments fill it; a wide rectangle's last row is
    # padding.
    assert all(sum(t["seg_lens"]) == t["t"] for t in line["timing"]
               if t["form"] == "packed")
    assert all(t["seg_lens"][-1] == 0 for t in line["timing"]
               if t["rows"] == 3)
