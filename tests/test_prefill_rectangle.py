"""A prefill dispatch's shape (PR 37, PR 46): its area never exceeds the
token budget and the row cap follows from the budget; where dispatches are
rectangles, admission takes the rectangle of the ladder that carries the
most live tokens over the FCFS prefix; where they are packed rows, every
candidate's chunk lies behind its predecessor's in one row (a share each,
then the slack in queue order); warm-up enumerates exactly what
``utils.prefill_rectangle`` can return. Pure scheduling: no model runs
here."""

import json
import os
import random

import pytest

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.kv_cache import BlockPoolManager
from production_stack_tpu.engine.sampling import SamplingParams
from production_stack_tpu.engine.scheduler import Scheduler, Sequence
from production_stack_tpu.models.config import StateSpec
from production_stack_tpu.utils import (
    pow2_bucket,
    prefill_rectangle,
    prefill_rectangles,
    prefill_row_cap,
    prefill_t_floor,
)

CONFIGS_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "chip", "configs")
BUDGETS = (64, 1024, 2048, 4096)


def _cfg(**over):
    base = dict(model="tiny-llama", max_model_len=4096, block_size=16,
                max_num_seqs=64, max_num_batched_tokens=2048)
    base.update(over)
    return EngineConfig(**base)


def _sched(blocks=1 << 15, slots=0, window=None, packed=False, **over):
    cfg = _cfg(**over)
    bm = BlockPoolManager(blocks, cfg.block_size,
                          enable_prefix_caching=False,
                          num_state_slots=slots)
    return Scheduler(cfg, bm, prefill_window_budget=window,
                     prefill_packed=packed)


def _waiting(sched, lengths):
    for i, tokens in enumerate(lengths, start=len(sched.seqs)):
        sched.add_sequence(Sequence(f"s{i}", [1 + i % 97] * tokens,
                                    SamplingParams()))


def _occupy(sched, rows):
    """``rows`` sequences decoding: what is left of max_num_seqs is room."""
    sched.running = [Sequence(f"r{i}", [1], SamplingParams())
                     for i in range(rows)]


# ----------------------------------------------------- the cap and the ladder
@pytest.mark.parametrize("budget,max_num_seqs,set_cap,cap", [
    (2048, 64, None, 16),      # cells 1 and 3
    (2048, 32, None, 16),      # cell 4
    (2048, 16, None, 16),      # cell 2
    (1024, 64, None, 8),       # cell 5: as before
    (4096, 64, None, 32),
    (4096, 8, None, 8),        # never more than --max-num-seqs
    (64, 8, None, 1),          # a budget of one chunk at its floor
    (2048, 64, 8, 8),          # a value that is set still caps
    (2048, 64, 4, 4),
    (1024, 64, 16, 8),         # ... and cannot lift the area bound
    (3000, 64, None, 16),      # 23 rows of 128 tokens: down to a power of 2
])
def test_the_row_cap_follows_from_the_budget(budget, max_num_seqs, set_cap,
                                             cap):
    cfg = _cfg(max_num_batched_tokens=budget, max_num_seqs=max_num_seqs,
               max_prefill_seqs=set_cap)
    assert prefill_row_cap(cfg) == cap
    assert max(rows for rows, _ in prefill_rectangles(cfg)) == \
        pow2_bucket(cap, 1, max_num_seqs)


@pytest.mark.parametrize("budget,max_num_seqs,set_cap,count", [
    (2048, 64, None, 8), (2048, 32, None, 8), (2048, 16, None, 8),
    (1024, 64, None, 7), (4096, 64, None, 9), (64, 8, 8, 1),
    (2048, 64, 8, 10), (256, 16, None, 3),
])
def test_every_rectangle_of_the_ladder_is_within_the_budget(
        budget, max_num_seqs, set_cap, count):
    """{1, half the cap's bucket, the cap's bucket} x the power-of-two
    chunk lengths from the floor, area <= budget: no more programs than
    the two families before (9 at 2048, 7 at 1024)."""
    cfg = _cfg(max_num_batched_tokens=budget, max_num_seqs=max_num_seqs,
               max_prefill_seqs=set_cap)
    rects = prefill_rectangles(cfg)
    floor = prefill_t_floor(budget)
    top = pow2_bucket(prefill_row_cap(cfg), 1, max_num_seqs)
    assert len(rects) == len(set(rects)) == count
    assert {rows for rows, _ in rects} <= {1, max(1, top // 2), top}
    for rows, t in rects:
        assert rows * t <= budget and t >= floor and t & (t - 1) == 0
    # Whatever admission can pass maps to the smallest rectangle that
    # holds it, and nothing it cannot pass maps anywhere.
    for n in range(1, prefill_row_cap(cfg) + 1):
        rows = min(r for r, _ in rects if r >= n)
        widest = max(t for r, t in rects if r == rows)
        for chunk in {1, floor - 1, floor, min(floor + 1, widest), widest}:
            got = prefill_rectangle(n, chunk, cfg)
            assert got in rects and got[0] == rows and got[1] >= chunk
            assert got[1] < 2 * max(chunk, floor)
        with pytest.raises(ValueError):
            prefill_rectangle(n, widest + 1, cfg)
    with pytest.raises(ValueError):
        prefill_rectangle(top + 1, 1, cfg)


# ------------------------------------------------------- what admission takes
def _expected_stop(n, gathered, left, cap, room):
    if not left:
        return "none"
    if n < gathered:
        return "tokens"
    return "rows" if cap <= room else "seqs"


@pytest.mark.parametrize("seed", (0, 1, 2))
@pytest.mark.parametrize("budget", BUDGETS)
def test_admission_takes_the_rectangle_that_carries_the_most(budget, seed):
    """Random queues at room 1..64: the dispatch is a rectangle of the
    ladder within the budget, over the FCFS prefix, each row's chunk
    ``min(remaining, T)``; no rectangle of the ladder would carry more
    live tokens; among those that carry as many none is smaller, and
    among those none has a longer chunk; ``stop`` names the first limit
    met."""
    rng = random.Random(1000 * budget + seed)
    cfg = _cfg(max_num_batched_tokens=budget)
    cap = prefill_row_cap(cfg)
    rects = prefill_rectangles(cfg)
    for _ in range(40):
        sched = _sched(max_num_batched_tokens=budget)
        room = rng.randint(1, 64)
        _occupy(sched, 64 - room)
        lengths = [min(3000, max(1, int(rng.lognormvariate(5.7, 0.9))))
                   for _ in range(rng.randint(1, 24))]
        _waiting(sched, lengths)
        queue = list(sched.waiting)
        batch = sched._try_schedule_prefill()
        n = len(batch.seqs)
        gathered = min(cap, room, len(queue))
        assert batch.seqs == queue[:n] and 1 <= n <= gathered
        rows, t = prefill_rectangle(n, max(batch.chunk_lens), cfg)
        assert rows * t <= budget
        assert batch.chunk_lens == [min(x, t) for x in lengths[:n]]
        assert batch.chunk_starts == [0] * n
        live = sum(batch.chunk_lens)
        for r2, t2 in rects:
            other = sum(min(x, t2) for x in lengths[:min(r2, gathered)])
            assert other <= live, (lengths, room, (rows, t), (r2, t2))
            if other == live:
                assert (r2 * t2, -t2) >= (rows * t, -t), \
                    (lengths, room, (rows, t), (r2, t2))
        assert batch.left_waiting == len(queue) - n
        assert batch.stop == _expected_stop(
            n, gathered, batch.left_waiting, cap, room)
        # The rows not taken hold nothing; the rows taken hold blocks.
        assert all(s.block_ids for s in batch.seqs)
        assert not any(s.block_ids for s in queue[n:])


@pytest.mark.parametrize("lengths,rows,chunks", [
    # Sixteen median prompts: [16, 128] carries 2048 where [8, 256]
    # carries 8 x 256 as well: the tie goes to the longer chunk.
    ([320] * 16, 8, [256] * 8),
    # Sixteen short prompts fill [16, 128] better than any other.
    ([100] * 16, 16, [100] * 16),
    # Two prompts arriving together: [8, 256] carries 512 of their 640
    # tokens where [1, 512] carries 320 (and [8, 512] does not exist).
    ([320, 320], 2, [256, 256]),
    # One long prompt has the whole budget.
    ([3000], 1, [2048]),
    # A long head and short followers: the rectangle that carries most.
    ([2000] + [60] * 7, 1, [2000]),
    ([600] + [200] * 7, 8, [256] + [200] * 7),
    # As much in less: [1, 128] before [8, 128].
    ([90], 1, [90]),
])
def test_the_choice_at_a_2048_budget(lengths, rows, chunks):
    sched = _sched()
    _waiting(sched, lengths)
    batch = sched._try_schedule_prefill()
    assert (len(batch.seqs), batch.chunk_lens) == (rows, chunks)


# ------------------------------------------------- a packed row (PR 46)
@pytest.mark.parametrize("lengths,chunks,t", [
    # The cases above where dispatches are packed rows. Sixteen median
    # prompts: a share of 128 each fills the row (no slack to hand on).
    ([320] * 16, [128] * 16, 2048),
    # Sixteen short prompts: all of each; the row's end is the padding.
    ([100] * 16, [100] * 16, 2048),
    # Two prompts arriving together: both whole, in a 1024-token row
    # (the rectangle took 256 of each in [8, 256]).
    ([320, 320], [320, 320], 1024),
    # One long prompt has the whole budget.
    ([3000], [2048], 2048),
    # A long head and short followers: the followers whole, and what they
    # leave of their shares goes to the head (the rectangle took the head
    # alone, or 256 of it).
    ([2000] + [60] * 7, [1628] + [60] * 7, 2048),
    ([600] + [200] * 7, [600] + [200] * 7, 2048),
    # The slack goes in queue order: the first with prompt left is filled
    # before the second sees any.
    ([100, 1500, 1500, 100], [100, 1336, 512, 100], 2048),
    # As much in less: the smallest row that holds the sum.
    ([90], [90], 128),
    ([90, 60], [90, 60], 256),
])
def test_packed_chunks_take_a_share_then_the_slack(lengths, chunks, t):
    sched = _sched(packed=True)
    _waiting(sched, lengths)
    queue = list(sched.waiting)
    batch = sched._try_schedule_prefill()
    assert batch.packed and batch.seqs == queue[:len(lengths)]
    assert batch.chunk_lens == chunks
    assert prefill_rectangle(len(chunks), max(chunks), sched.config,
                             packed_tokens=sum(chunks)) == (1, t)


@pytest.mark.parametrize("seed", (0, 1, 2))
@pytest.mark.parametrize("budget", BUDGETS)
def test_a_packed_dispatch_is_one_row_over_the_fcfs_prefix(budget, seed):
    """Random queues at room 1..64 where dispatches are packed: every
    candidate the pass gathered is taken, in queue order; nobody gets less
    than ``min(remaining, budget // n)`` nor more than it has; the sum
    fits the widest row and the program is the smallest one-row shape
    that holds it; whoever got more than a share stands behind nobody with
    prompt left; where the row is not full nobody has prompt left; `stop`
    names the candidate loop's limit; the counters' padded area is T."""
    rng = random.Random(2000 * budget + seed)
    cfg = _cfg(max_num_batched_tokens=budget)
    cap = prefill_row_cap(cfg)
    rows_of_one = prefill_rectangles(cfg, True)
    assert rows_of_one == tuple(r for r in prefill_rectangles(cfg)
                                if r[0] == 1)
    widest = rows_of_one[-1][1]
    for _ in range(40):
        sched = _sched(packed=True, max_num_batched_tokens=budget)
        room = rng.randint(1, 64)
        _occupy(sched, 64 - room)
        lengths = [min(3000, max(1, int(rng.lognormvariate(5.7, 0.9))))
                   for _ in range(rng.randint(1, 24))]
        _waiting(sched, lengths)
        queue = list(sched.waiting)
        batch = sched._try_schedule_prefill()
        n = len(batch.seqs)
        assert n == min(cap, room, len(queue)) and batch.seqs == queue[:n]
        share = widest // n
        lens, rems = batch.chunk_lens, lengths[:n]
        assert all(min(r, share) <= x <= r for x, r in zip(lens, rems))
        assert sum(lens) <= widest
        rows, t = prefill_rectangle(n, max(lens), cfg,
                                    packed_tokens=sum(lens))
        assert rows == 1 and t == min(
            t2 for _, t2 in rows_of_one if t2 >= sum(lens))
        if sum(lens) < widest:
            assert lens == rems
        for i, x in enumerate(lens):
            if x > share:
                assert lens[:i] == rems[:i]
        assert batch.chunk_starts == [0] * n
        assert batch.left_waiting == len(queue) - n
        assert batch.stop == _expected_stop(
            n, n, batch.left_waiting, cap, room)
        assert all(s.block_ids for s in batch.seqs)
        assert not any(s.block_ids for s in queue[n:])


def test_a_packed_row_continues_what_an_earlier_row_began():
    """A long head beside seven short prompts: the first row takes 1628 of
    the head behind... before the seven whole; the second is the head's
    remaining 372 tokens alone, from where the first left it."""
    sched = _sched(packed=True)
    _waiting(sched, [2000] + [60] * 7)
    first = sched._try_schedule_prefill()
    assert first.chunk_lens == [1628] + [60] * 7
    sched.advance_at_issue(first)
    batch = sched._try_schedule_prefill()
    assert (batch.chunk_starts, batch.chunk_lens, batch.stop) == \
        ([1628], [372], "none")
    assert prefill_rectangle(1, 372, sched.config, packed_tokens=372) == \
        (1, 512)


# One pool of latent rows at the served widths (32 heads over 640 lanes,
# values the first 512).
LATENT_ROWS = {"kv_pools": 1, "kv_heads": 1, "head_dim": 640,
               "kv_value_dim": 512, "num_heads": 32}


_CONV, _SCAN = (StateSpec(name, 2, (128,), None) for name in ("conv", "scan"))


def _predicate_runner(**over):
    """A ModelRunner that holds only what ``prefill_packs`` reads."""
    from types import SimpleNamespace

    from production_stack_tpu.engine.runner import ModelRunner

    r = object.__new__(ModelRunner)
    r.config = _cfg()
    r.model_config = SimpleNamespace(num_heads=16)
    r.kv_spec = SimpleNamespace(kv_heads=2, head_dim=128)
    r.kv_value_dim, r.kv_pools, r.dtype = 128, 2, "bfloat16"
    r.state_specs, r.lora_stacks, r.spec_n = (), None, 0
    r.states_crossing_segments = frozenset()
    r.attn_impl, r.num_kv_blocks = "paged", 1 << 20
    r.__dict__["prefill_reads_pool"] = True
    for k, v in over.items():
        if k in ("kv_heads", "head_dim"):
            setattr(r.kv_spec, k, v)
        elif k == "prefill_reads_pool":
            r.__dict__[k] = v
        elif k == "num_heads":
            r.model_config.num_heads = v
        else:
            setattr(r, k, v)
    return r


@pytest.mark.parametrize("case,over,packs", [
    ("dense K/V rows read in place", {}, True),
    ("a gathered window", {"prefill_reads_pool": False}, False),
    ("recurrent state", {"state_specs": (_SCAN,)}, False),
    ("a state its module says crosses segments",
     {"state_specs": (_CONV,), "states_crossing_segments": {"conv"}}, True),
    ("two states, one of which crosses segments",
     {"state_specs": (_CONV, _SCAN), "states_crossing_segments": {"conv"}},
     False),
    ("an adapter a row beside a state that crosses segments",
     {"state_specs": (_CONV,), "states_crossing_segments": {"conv"},
      "lora_stacks": {"wq": None}}, False),
    ("latent rows read in place", LATENT_ROWS, True),
    ("latent rows of heads that fill no sublane tile",
     {**LATENT_ROWS, "num_heads": 8}, False),
    ("an adapter a row", {"lora_stacks": {"wq": None}}, False),
    ("a draft ring a row", {"spec_n": 3}, False),
    ("an adapter a row over latent rows",
     {**LATENT_ROWS, "lora_stacks": {"wq": None}}, False),
    ("a draft ring a row over latent rows",
     {**LATENT_ROWS, "spec_n": 3}, False),
])
def test_which_form_a_dispatch_takes_is_decided_in_one_place(case, over,
                                                             packs):
    """``ModelRunner.prefill_packs``, from what the runner holds: the
    configurations that keep a scan's state keep their rectangles, and so
    does whatever rides a row (an adapter, a draft's ring) over either
    pool; latent rows pack since PR 48, where the packed kernel covers
    them, and since PR 50 a module whose EVERY state is one it declares to
    cross a segment boundary (``STATES_CROSSING_SEGMENTS``)."""
    r = _predicate_runner(**over)
    assert r.prefill_packs is packs
    assert r._prefill_segs == (16 if packs else 0)
    assert {rows for rows, _, _, _ in r.reachable_prefill_families()} == \
        ({1} if packs else {1, 8, 16})


# ------------------------------------------- rows not taken give back what
# ------------------------------------------- they took this pass
@pytest.mark.parametrize("slots", (0, 4))
def test_rows_not_taken_release_their_blocks_and_state_slot(slots):
    """Three 1000-token prompts at a 1024 budget: all three are gathered
    (blocks, and a state slot where the model keeps one), one is taken."""
    sched = _sched(blocks=512, slots=slots, max_num_batched_tokens=1024)
    bm = sched.block_manager
    free_blocks = bm.num_free_blocks
    _waiting(sched, [1000] * 3)
    batch = sched._try_schedule_prefill()
    assert (len(batch.seqs), batch.stop, batch.left_waiting) == \
        (1, "tokens", 2)
    taken = batch.seqs[0]
    assert free_blocks - bm.num_free_blocks == len(taken.block_ids) == 63
    assert bm.state_slots_in_use == (1 if slots else 0)
    assert bool(taken.state_slot) == bool(slots)
    for seq in sched.waiting:
        assert (seq.block_ids, seq.num_computed_tokens, seq.state_slot) == \
            ([], 0, 0)


def test_rows_not_taken_keep_what_they_held_before_the_pass():
    """A long head beside seven short prompts: the first dispatch takes all
    eight at 256 tokens a row; the second carries most as the head's 1744
    remaining tokens alone, and the seven rows it passes over, each on its
    second chunk, keep their blocks and their progress."""
    sched = _sched()
    _waiting(sched, [2000] + [300] * 7)
    first = sched._try_schedule_prefill()
    assert first.chunk_lens == [256] * 8
    sched.advance_at_issue(first)
    held = [list(s.block_ids) for s in sched.waiting]
    batch = sched._try_schedule_prefill()
    assert (batch.chunk_lens, batch.stop, batch.left_waiting) == \
        ([1744], "tokens", 7)
    assert [s.block_ids for s in sched.waiting] == held[1:]
    assert all(s.num_computed_tokens == 256 for s in sched.waiting)


# --------------------------------------------------------- the stop it names
@pytest.mark.parametrize("case,over,running,lengths,taken,stop", [
    ("none", {}, 0, [40] * 3, 3, "none"),
    # 20 waiting, a derived cap of 16 and room for all: the cap.
    ("rows", {}, 0, [40] * 20, 16, "rows"),
    # ... a set cap.
    ("rows-set", {"max_prefill_seqs": 4}, 0, [40] * 8, 4, "rows"),
    # Room for 5 of a cap of 16: the running set.
    ("seqs", {}, 59, [40] * 8, 5, "seqs"),
    # Sixteen gathered, eight taken: the area bound.
    ("tokens", {}, 0, [320] * 20, 8, "tokens"),
    # The cap was met first and the area then took fewer: tokens is what
    # bounds the dispatch.
    ("tokens-at-room", {}, 56, [2000] + [60] * 8, 1, "tokens"),
])
def test_stop_names_the_first_limit_met(case, over, running, lengths, taken,
                                        stop):
    sched = _sched(**over)
    _occupy(sched, running)
    _waiting(sched, lengths)
    batch = sched._try_schedule_prefill()
    assert (len(batch.seqs), batch.stop) == (taken, stop)
    assert batch.left_waiting == len(lengths) - taken


def test_stop_window_where_the_window_budget_passes_a_rectangle_over():
    """Eight rows on their second chunk would gather 8 x 16 blocks; a
    window budget of 64 holds one row's, so one row goes."""
    sched = _sched(window=64, max_num_batched_tokens=1024, max_num_seqs=8)
    _waiting(sched, [200] * 8)
    first = sched._try_schedule_prefill()
    assert (len(first.seqs), first.chunk_lens) == (8, [128] * 8)
    sched.advance_at_issue(first)       # all eight back at the queue's head
    batch = sched._try_schedule_prefill()
    assert (len(batch.seqs), batch.chunk_lens, batch.stop) == \
        (1, [72], "window")


# ------------------------------------------- warm-up covers what can be issued
def _deployment_flags(name):
    with open(os.path.join(CONFIGS_DIR, name, "deployment.json")) as f:
        flags = {x["flag"]: x["value"] for x in json.load(f)["engine_flags"]}
    return dict(
        max_model_len=int(flags["--max-model-len"]),
        max_num_seqs=int(flags["--max-num-seqs"]),
        max_num_batched_tokens=int(flags["--max-num-batched-tokens"]),
        num_kv_blocks=int(flags["--num-kv-blocks"]))


@pytest.mark.parametrize("cell,reads_pool,packs,families,before", [
    # (rows, t) programs a deployment warms, by what PERF.md section 7
    # states: this PR's, and PR 37's before it. The dense cells' dispatches
    # are packed rows since PR 46: the one-row column alone.
    ("qwen2.5-3b.chat-steady", True, True, 5, 8),
    ("mistral-7b-d16.agent-prefix", True, True, 5, 8),
    ("qwen2.5-3b.chat-saturated", True, True, 5, 8),
    # ... and what they warmed as rectangles (a dense model behind an
    # adapter or a draft still does).
    ("qwen2.5-3b.chat-saturated", True, False, 8, 9),
    ("olmo-hybrid-7b-d16.chat-saturated", True, False, 8, 9),
    # Latent rows read their pool in place since PR 39: one program a
    # (rows, t), where PR 38's tree had each with and without a window;
    # since PR 48 their dispatches are packed rows too: 1 x {128..1024} ...
    ("kanana-2-30b-a3b-d8.chat-saturated", True, True, 4, 7),
    ("xing4.0-29b-a4b-d7.chat-saturated", True, True, 4, 7),
    # ... and the rectangles an adapter or a draft over them would keep.
    ("kanana-2-30b-a3b-d8.chat-saturated", True, False, 7, 14),
    ("xing4.0-29b-a4b-d7.chat-saturated", True, False, 7, 14),
    # The same envelope where the predicate refuses the pool view (a
    # sharded or int8 latent deployment): a gathered window, pinned at one
    # width, each (rows, t) with and without it.
    ("kanana-2-30b-a3b-d8.chat-saturated", False, False, 14, 14),
])
def test_warm_up_enumerates_exactly_what_a_dispatch_can_run(
        cell, reads_pool, packs, families, before):
    """For the cells' engine flags: every (rows, T) that
    ``prefill_rectangle`` returns for n in 1..cap and any chunk length
    (where dispatches are packed: for any sum of chunks) is a family of
    ``reachable_prefill_families``, with and without a window where one
    is gathered, and the enumeration holds nothing else."""
    from production_stack_tpu.engine.runner import ModelRunner

    cfg = _cfg(model="tiny-llama", **_deployment_flags(cell.rsplit(".", 1)[0]))

    class _FakeRunner:
        config = cfg
        attn_impl = "paged"
        num_kv_blocks = cfg.num_kv_blocks
        prefill_window_blocks = \
            1 << 30 if reads_pool else cfg.num_kv_blocks
        reachable_prefill_families = ModelRunner.reachable_prefill_families
        _prefill_mb = ModelRunner._prefill_mb
        _pins_prefill_window = ModelRunner._pins_prefill_window
        state_specs = ()
        kv_pools = 2 if reads_pool else 1     # latent rows: one pool
        prefill_reads_pool = reads_pool
        prefill_packs = packs

    r = _FakeRunner()
    fams = r.reachable_prefill_families()
    assert len(fams) == families <= before
    budget, cap = cfg.max_num_batched_tokens, prefill_row_cap(cfg)
    assert cap == min(cfg.max_num_seqs, budget // prefill_t_floor(budget))
    full_mb = pow2_bucket(cfg.max_blocks_per_seq, 1, cfg.max_blocks_per_seq)
    seen = set()
    if packs:
        # Whatever the chunks, their sum decides, and it never passes the
        # widest row (``_packed_chunk_lens``).
        for tokens in range(1, budget + 1):
            rows, t = prefill_rectangle(cap, 1, cfg, packed_tokens=tokens)
            fam = (rows, t, r._prefill_mb(full_mb // 2, False, rows), False)
            assert rows == 1 and t >= tokens and fam in fams
            seen.add(fam)
        with pytest.raises(ValueError):
            prefill_rectangle(1, 1, cfg, packed_tokens=budget + 1)
        assert seen == set(fams)
        return
    for n in range(1, cap + 1):
        for chunk in range(1, budget + 1):
            try:
                rows, t = prefill_rectangle(n, chunk, cfg)
            except ValueError:
                # Past the widest chunk n rows can have: so is every
                # longer one.
                assert chunk > prefill_t_floor(budget)
                break
            assert rows * t <= budget
            for windowed in ((False,) if reads_pool else (False, True)):
                for live in (1, full_mb // 2, full_mb):
                    fam = (rows, t, r._prefill_mb(live, windowed, rows),
                           windowed)
                    assert fam in fams, (n, chunk, fam)
                    seen.add(fam)
    assert seen == set(fams)
