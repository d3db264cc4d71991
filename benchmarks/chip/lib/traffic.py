"""The one traffic generator: a traffic file + ``--seed`` -> the requests.

Traffic is a function of the traffic file, the window length and the seed
alone (never of the clock or of the program's answers). Lengths are
STRATIFIED: the file's distributions are cut into as many equal-probability
strata as the window holds requests, so every seed offers the same number
of requests, the same multiset of (prompt, output) lengths and so the same
token totals. In a closed loop the seed permutes the order (within
``balanced_order``) and fills the text: another interleaving of the same work.

An open loop goes further (PR 51): the order of the lengths and the gaps
between arrivals are drawn from the FILE's ``schedule_seed``, and the run's
seed turns that schedule (starts it at another of its requests, each
keeping the gap before it), picks the first tenant and fills the text.
Every seed then offers the same requests after the same gaps, in another
order: which request meets which other's prefill no longer differs from
seed to seed, and the median of some eighty requests stops swinging by a
tenth with it (PERF.md sections 2 and 6).

Prompts are ASCII of an exact byte length: the engine's byte tokenizer
gives one token per byte, and the repo's plain chat template
(``engine/tokenizer.py``) adds a fixed wrapper around each message, so the
generator knows every request's prompt-token count before it is sent.
"""

import itertools
import math
import random
from dataclasses import dataclass
from statistics import NormalDist
from typing import List, Optional

# Bytes the plain chat template spends around a message's content
# ("<|role|>\n" + content + "\n") and on the generation prompt.
SYSTEM_WRAP = len("<|system|>\n") + len("\n")
USER_WRAP = len("<|user|>\n") + len("\n") + len("<|assistant|>\n")
SESSION_HEADER = "x-user-id"

WORDS = (
    "the of and to in is that for it as with was on be by at this have from "
    "or one had not but what all were we when your can said there use an "
    "each which she do how their if will up other about out many then them "
    "these so some her would make like him into time has look two more "
    "write go see number no way could people my than first water been call "
    "who oil its now find long down day did get come made may part cache "
    "token batch route engine prefix block decode kernel queue stream"
).split()


@dataclass(frozen=True)
class Request:
    index: int
    due_s: Optional[float]      # offset into the window; None in a closed loop
    tenant: int
    session: str
    prompt_tokens: int          # whole prompt as the engine will count it
    output_tokens: int
    messages: tuple             # ({"role", "content"}, ...)

    def body(self, model: str) -> dict:
        return {
            "model": model, "stream": True, "temperature": 0, "seed": 0,
            "max_tokens": self.output_tokens, "ignore_eos": True,
            "stream_options": {"include_usage": True},
            "messages": list(self.messages),
        }


def prompt_token_count(messages) -> int:
    """One token per UTF-8 byte of the templated prompt (ByteTokenizer)."""
    text = "".join(f"<|{m['role']}|>\n{m['content']}\n" for m in messages)
    return len((text + "<|assistant|>\n").encode("utf-8"))


def strata(dist: dict, n: int) -> List[int]:
    """``n`` equal-probability strata of a clipped log-normal, each stood
    for by its mid-quantile, ascending."""
    if dist.get("dist", "lognormal") != "lognormal":
        raise ValueError(f"unknown length distribution {dist.get('dist')!r}")
    inv = NormalDist().inv_cdf
    out = []
    for i in range(n):
        v = dist["median"] * math.exp(dist["sigma"] * inv((i + 0.5) / n))
        out.append(int(round(min(max(v, dist["min"]), dist["max"]))))
    return out


def length_pairs(spec: dict, n: int) -> List[tuple]:
    """The multiset of (prompt, output) lengths of ``n`` requests. The
    pairing of a prompt stratum with an output stratum is fixed by the
    file (``pairing_seed``), not by the run's seed."""
    prompts, outputs = strata(spec["prompt"], n), strata(spec["output"], n)
    random.Random(spec.get("pairing_seed", 0)).shuffle(outputs)
    return list(zip(prompts, outputs))


def balanced_order(pairs: List[tuple], rng: random.Random,
                   block: int = 8) -> List[tuple]:
    """A seeded order in which every run of ``block`` consecutive requests
    holds one request from each ``block``-quantile of the work (output
    length, then prompt length). The seed still decides which request of a
    quantile comes when, and the order inside a run; what it can no longer
    do is put all the long answers into one stretch of the window, which
    made one seed's window heavier at its middle than another's."""
    ranked = sorted(pairs, key=lambda p: (p[1], p[0]))
    n = len(ranked)
    groups = [ranked[i * n // block:(i + 1) * n // block]
              for i in range(block)]
    for group in groups:
        rng.shuffle(group)
    out: List[tuple] = []
    while any(groups):
        run = [group.pop() for group in groups if group]
        rng.shuffle(run)
        out += run
    return out


def arrival_gaps(arrival: dict, n: int, seconds: float,
                 rng: random.Random) -> List[float]:
    """The gap before each of ``n`` arrivals: a gamma-renewal process of
    the file's coefficient of variation, scaled so that every seed puts the
    same count into the window (the mean rate is exactly n / seconds; the
    gap after the last arrival is drawn too and is the window's tail)."""
    if arrival.get("kind", "gamma") != "gamma":
        raise ValueError(f"unknown arrival process {arrival.get('kind')!r}")
    shape = 1.0 / (arrival["cv"] ** 2)
    gaps = [rng.gammavariate(shape, 1.0 / shape) for _ in range(n + 1)]
    total = sum(gaps)
    return [seconds * gap / total for gap in gaps[:n]]


def open_schedule(spec: dict, n: int, seconds: float,
                  rng: random.Random) -> tuple:
    """An open loop's window: the length pairs in their order and the gap
    before each, drawn from the file's ``schedule_seed`` and only TURNED
    by ``rng``, the run's: started at another request, each keeping its
    gap, the end joined to the start."""
    plan = random.Random(spec["schedule_seed"] * 1_000_003 + 1)
    pairs = balanced_order(length_pairs(spec, n), plan)
    gaps = arrival_gaps(spec["arrival"], n, seconds, plan)
    turn = rng.randrange(n)
    return pairs[turn:] + pairs[:turn], gaps[turn:] + gaps[:turn]


def ascii_text(rng: random.Random, nbytes: int) -> str:
    """Exactly ``nbytes`` of seeded ASCII words."""
    if nbytes <= 0:
        return ""
    words, size = [], 0
    while size - 1 < nbytes:
        word = rng.choice(WORDS)
        words.append(word)
        size += len(word) + 1
    return " ".join(words)[:nbytes]


def system_prompts(spec: dict, rng: random.Random) -> List[str]:
    """One system message per tenant, each exactly ``system.tokens`` tokens
    once templated; the tenant's number leads so that no two share a
    block."""
    system = spec["system"]
    out = []
    for tenant in range(system["tenants"]):
        head = f"tenant {tenant:03d} "
        out.append(head + ascii_text(
            rng, system["tokens"] - SYSTEM_WRAP - len(head)))
    return out


def _request(index, due, tenant, spec, systems, pair, rng) -> Request:
    prompt_len, output_len = pair
    user = ascii_text(rng, prompt_len - USER_WRAP)
    messages = ({"role": "system", "content": systems[tenant]},
                {"role": "user", "content": user})
    session = (f"tenant-{tenant}" if spec["system"]["tenants"] > 1
               else f"user-{index}")
    return Request(index, due, tenant, session,
                   spec["system"]["tokens"] + prompt_len, output_len,
                   messages)


def generate(spec: dict, seed: int, seconds: float,
             variation: int = 0) -> dict:
    """The requests of one window, and what set-up sends first.

    The tenants' system prompts come from ``seed`` alone; order (a closed
    loop's), the turn and the other text from ``seed`` and ``variation``, so that a
    sweep offers step after step new requests to the same cached tenants.
    An open loop's order and gaps come from the file's ``schedule_seed``,
    and ``seed`` and ``variation`` only turn that schedule.

    Open loop: ``round(rate_rps * seconds)`` requests with due times.
    Closed loop: ``users * rounds_max`` requests without due times, in
    rounds of ``users`` requests that each cover the whole distribution,
    so that whatever prefix of the list a run gets through is stratified.
    """
    systems = system_prompts(spec, random.Random(seed))
    rng = random.Random(seed * 1_000_003 + variation + 1)
    tenants = spec["system"]["tenants"]
    if spec["loop"] == "open":
        n = max(1, int(round(spec["rate_rps"] * seconds)))
        pairs, gaps = open_schedule(spec, n, seconds, rng)
        dues = list(itertools.accumulate(gaps))
    elif spec["loop"] == "closed":
        one_round = length_pairs(spec, spec["users"])
        pairs = []
        for _ in range(spec["rounds_max"]):
            pairs += balanced_order(one_round, rng)
        n, dues = len(pairs), [None] * len(pairs)
    else:
        raise ValueError(f"unknown loop kind {spec['loop']!r}")
    # Tenants in turn, from a seeded start: equal shares whatever the seed.
    first = rng.randrange(tenants)
    requests = [
        _request(i, dues[i], (first + i) % tenants, spec, systems,
                 pairs[i], rng)
        for i in range(n)
    ]
    preload = []
    if spec.get("preload") == "tenants":
        # One request per tenant, so that every tenant's prefix is cached
        # before the window opens.
        floor = (spec["prompt"]["min"], spec["output"]["min"])
        preload = [
            _request(-1 - t, None, t, spec, systems, floor, rng)
            for t in range(tenants)
        ]
    warm = [
        _request(-1000 - i, None, i % tenants, spec, systems,
                 (spec["prompt"]["median"], spec["output"]["min"]), rng)
        for i in range(spec.get("warm_requests", 4))
    ]
    return {"requests": requests, "preload": preload, "warm": warm}


def probe_request(spec: dict, seed: int) -> Request:
    """The set-up's correctness probe: a prompt of the mix's median length
    under a system prompt of its own, sent cold and then again as a
    whole-prefix hit."""
    rng = random.Random(seed ^ 0x5EED)
    tokens = min(spec["system"]["tokens"], 256)
    system = "probe " + ascii_text(rng, tokens - SYSTEM_WRAP - len("probe "))
    user = ascii_text(rng, spec["prompt"]["median"] - USER_WRAP)
    return Request(-9999, None, 0, "probe", tokens + spec["prompt"]["median"],
                   4, ({"role": "system", "content": system},
                       {"role": "user", "content": user}))
