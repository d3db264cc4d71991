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


OPEN = [m for m in MIXES if Manifest(REPO).traffic(m)["loop"] == "open"]


def schedule(reqs, system_tokens):
    """(gap before it, prompt, output) of each request, in order."""
    dues = [r.due_s for r in reqs]
    gaps = [dues[0]] + [b - a for a, b in zip(dues, dues[1:])]
    return [(round(g, 9), r.prompt_tokens - system_tokens, r.output_tokens)
            for g, r in zip(gaps, reqs)]


class NoTurn:
    """Stands in for the run's generator: the schedule from its start."""
    randrange = staticmethod(lambda n: 0)


def unturned(reqs, spec, seconds=51.0):
    """The requests from where the file's own schedule starts."""
    _, gaps = traffic.open_schedule(spec, len(reqs), seconds, NoTurn)
    first = round(gaps[0], 9)
    got = schedule(reqs, spec["system"]["tokens"])
    at = [i for i, row in enumerate(got) if row[0] == first]
    assert len(at) == 1
    return reqs[at[0]:] + reqs[:at[0]]


@pytest.mark.parametrize("mix", OPEN)
def test_an_open_loops_seed_turns_the_files_schedule_and_redraws_nothing(mix):
    """PR 51: every seed offers the same requests after the same gaps, in
    another order: the file's schedule started at another request."""
    spec = Manifest(REPO).traffic(mix)
    assert isinstance(spec["schedule_seed"], int)
    system = spec["system"]["tokens"]
    seeds = (1, 2, 2**31 + 12345, 2147521011)
    first, *rest = (schedule(gen(mix, s)["requests"], system) for s in seeds)
    starts = {first[0]}
    for other in rest:
        assert sorted(other) == sorted(first)
        at = other.index(first[0])
        assert other[at:] + other[:at] == first
        starts.add(other[0])
    assert len(starts) > 1
    # the last arrival falls where it fell, so the window's tail is one gap
    assert len({round(gen(mix, s)["requests"][-1].due_s, 6)
                for s in seeds}) == 1


@pytest.mark.parametrize("mix", OPEN)
def test_an_open_loop_without_a_schedule_seed_is_refused(mix):
    """One way to order an open loop: a file that leaves the seed out gets
    no schedule drawn from the run's."""
    spec = {k: v for k, v in Manifest(REPO).traffic(mix).items()
            if k != "schedule_seed"}
    with pytest.raises(KeyError, match="schedule_seed"):
        traffic.generate(spec, 1, 51.0)


@pytest.mark.parametrize("mix", OPEN)
def test_a_sweeps_variation_turns_the_same_schedule(mix):
    spec = Manifest(REPO).traffic(mix)
    system = spec["system"]["tokens"]
    steps = [traffic.generate(spec, 5, 51.0, variation=v)["requests"]
             for v in range(4)]
    assert len({tuple(sorted(schedule(r, system))) for r in steps}) == 1
    assert len({r[0].messages[1]["content"] for r in steps}) == 4


@pytest.mark.parametrize("seed", SEEDS)
def test_every_run_of_eight_requests_spans_the_work(seed):
    reqs = unturned(gen("chat-steady", seed)["requests"],
                    Manifest(REPO).traffic("chat-steady"))
    ranked = sorted(reqs, key=lambda r: (r.output_tokens, r.prompt_tokens))
    octile = {id(r): i * 8 // len(ranked) for i, r in enumerate(ranked)}
    whole = len(reqs) // 8 * 8 - 8     # the last runs hold the remainders
    for at in range(0, whole, 8):
        assert len({octile[id(r)] for r in reqs[at:at + 8]}) >= 7, at
