"""The generator: what the seed may change and what it may not."""

import collections
import os
import sys

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.chip.lib import stats, traffic  # noqa: E402
from benchmarks.chip.lib.manifest import Manifest  # noqa: E402

MIXES = sorted(
    f[:-5] for f in os.listdir(os.path.join(REPO, "benchmarks", "chip",
                                            "traffic")) if f.endswith(".json"))
SEEDS = (1, 2**31 + 12345)


def gen(mix, seed, seconds=51.0):
    return traffic.generate(Manifest(REPO).traffic(mix), seed, seconds)


@pytest.mark.parametrize("mix", MIXES)
def test_two_seeds_offer_the_same_work(mix):
    a, b = (gen(mix, s)["requests"] for s in SEEDS)
    assert len(a) == len(b) > 0
    pairs = lambda rs: collections.Counter(  # noqa: E731
        (r.prompt_tokens, r.output_tokens) for r in rs)
    assert pairs(a) == pairs(b)
    assert sum(r.prompt_tokens for r in a) == sum(r.prompt_tokens for r in b)
    assert sum(r.output_tokens for r in a) == sum(r.output_tokens for r in b)
    assert [r.messages for r in a] != [r.messages for r in b]


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_is_byte_identical(mix):
    a, b = gen(mix, SEEDS[1]), gen(mix, SEEDS[1])
    for group in ("requests", "preload", "warm"):
        assert a[group] == b[group]


@pytest.mark.parametrize("mix", MIXES)
def test_prompts_are_ascii_of_the_exact_token_count(mix):
    g = gen(mix, 3)
    for r in g["requests"][:200] + g["preload"] + g["warm"]:
        assert traffic.prompt_token_count(r.messages) == r.prompt_tokens
        assert all(m["content"].isascii() for m in r.messages)


@pytest.mark.parametrize("mix", [m for m in MIXES if Manifest(REPO).traffic(
    m)["loop"] == "open"])
def test_arrivals_fill_the_window_at_the_files_cv(mix):
    spec = Manifest(REPO).traffic(mix)
    seconds = 1200.0
    reqs = traffic.generate(spec, 9, seconds)["requests"]
    dues = [r.due_s for r in reqs]
    assert dues == sorted(dues) and 0 <= dues[0] and dues[-1] < seconds
    assert len(reqs) == round(spec["rate_rps"] * seconds)
    gaps = [b - a for a, b in zip(dues, dues[1:])]
    assert stats.cv(gaps) == pytest.approx(spec["arrival"]["cv"], rel=0.10)


def test_closed_loop_rounds_each_cover_the_distribution():
    spec = Manifest(REPO).traffic("chat-saturated")
    reqs = gen("chat-saturated", 5)["requests"]
    users = spec["users"]
    first, second = reqs[:users], reqs[users:2 * users]
    assert sorted(r.output_tokens for r in first) == sorted(
        r.output_tokens for r in second)
    assert all(r.due_s is None for r in reqs)


def test_tenants_share_a_prefix_and_take_equal_turns():
    reqs = gen("agent-prefix", 11)["requests"]
    by_tenant = collections.defaultdict(set)
    for r in reqs:
        by_tenant[r.tenant].add(r.messages[0]["content"])
    assert len(by_tenant) == 4
    assert all(len(v) == 1 for v in by_tenant.values())
    counts = collections.Counter(r.tenant for r in reqs)
    assert max(counts.values()) - min(counts.values()) <= 1
    assert len({r.messages[1]["content"] for r in reqs}) == len(reqs)


@pytest.mark.parametrize("q,want", [(50, 2.5), (0, 1.0), (100, 4.0),
                                    (95, 3.85)])
def test_percentile_interpolates(q, want):
    assert stats.percentile([4.0, 1.0, 3.0, 2.0], q) == pytest.approx(want)


def test_spread_is_the_quartile_distance_over_the_median():
    assert stats.spread([1, 2, 3, 4, 5, 6]) == pytest.approx(3.5 / 3.5)
    assert stats.percentile([], 50) is None


@pytest.mark.parametrize("seed", SEEDS)
def test_every_run_of_eight_requests_spans_the_work(seed):
    reqs = gen("chat-steady", seed)["requests"]
    ranked = sorted(reqs, key=lambda r: (r.output_tokens, r.prompt_tokens))
    octile = {id(r): i * 8 // len(ranked) for i, r in enumerate(ranked)}
    whole = len(reqs) // 8 * 8 - 8     # the last runs hold the remainders
    for at in range(0, whole, 8):
        assert len({octile[id(r)] for r in reqs[at:at + 8]}) >= 7, at
