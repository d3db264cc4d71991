"""The load: streamed ``/v1/chat/completions`` through the router, from one
process and one event loop. An open loop sends each request when it is due
and times it FROM WHEN IT WAS DUE; a closed loop gives each user its next
request when the last one ended."""

import asyncio
import json
import time
from dataclasses import dataclass, field
from typing import List, Optional

import aiohttp

from benchmarks.chip.lib.traffic import SESSION_HEADER, Request


@dataclass
class Result:
    request: Request
    due: float = 0.0            # perf_counter times
    sent: float = 0.0
    first: Optional[float] = None
    last: Optional[float] = None
    status: int = 0
    done: bool = False
    finish_reason: Optional[str] = None
    usage: Optional[dict] = None
    logprobs: List[float] = field(default_factory=list)
    error: Optional[str] = None

    def faults(self) -> List[str]:
        """What is wrong with this answer (empty: a good one). Greedy with
        ignore_eos always spends the budget, so the counts are exact."""
        if self.status != 200:
            return [f"status {self.status}: {self.error}"]
        out = []
        want = {"prompt_tokens": self.request.prompt_tokens,
                "completion_tokens": self.request.output_tokens,
                "total_tokens": self.request.prompt_tokens
                + self.request.output_tokens}
        if self.usage != want:
            out.append(f"usage {self.usage} != {want}")
        if self.finish_reason != "length":
            out.append(f"finish_reason {self.finish_reason!r}")
        if not self.done:
            out.append("stream not closed by [DONE]")
        if self.error:
            out.append(f"error event {self.error}")
        if self.first is None:
            out.append("no chunk")
        return out

    @property
    def ok(self) -> bool:
        return not self.faults()

    @property
    def ttft_ms(self) -> float:
        return (self.first - self.due) * 1e3

    @property
    def req_ms(self) -> float:
        return (self.last - self.due) * 1e3

    @property
    def tpot_ms(self) -> float:
        return ((self.last - self.first) * 1e3
                / max(1, self.request.output_tokens - 1))

    @property
    def late_ms(self) -> float:
        return (self.sent - self.due) * 1e3


async def send(session: aiohttp.ClientSession, url: str, model: str,
               request: Request, due: Optional[float] = None,
               logprobs: bool = False) -> Result:
    """One streamed request; never raises (a fault is a failed Result)."""
    res = Result(request)
    body = request.body(model)
    if logprobs:
        body.update(logprobs=True, top_logprobs=0)
    res.sent = time.perf_counter()
    res.due = res.sent if due is None else due
    try:
        async with session.post(
            f"{url}/v1/chat/completions", json=body,
            headers={SESSION_HEADER: request.session},
        ) as resp:
            res.status = resp.status
            if resp.status != 200:
                res.error = (await resp.text())[:300]
                return res
            async for raw in resp.content:
                if not raw.startswith(b"data:"):
                    continue
                now = time.perf_counter()
                payload = raw[5:].strip()
                if payload == b"[DONE]":
                    res.done = True
                    continue
                doc = json.loads(payload)
                if "error" in doc:
                    res.error = json.dumps(doc["error"])[:300]
                if doc.get("usage"):
                    res.usage = doc["usage"]
                for choice in doc.get("choices") or ():
                    if res.first is None:
                        res.first = now
                    res.last = now
                    if choice.get("finish_reason"):
                        res.finish_reason = choice["finish_reason"]
                    for item in (choice.get("logprobs") or {}).get(
                            "content", ()):
                        res.logprobs.append(item["logprob"])
    except (aiohttp.ClientError, asyncio.TimeoutError, OSError,
            ValueError) as e:
        res.status = res.status or 599
        res.error = f"{type(e).__name__}: {e}"[:300]
    return res


def new_session(timeout_s: float = 300.0) -> aiohttp.ClientSession:
    return aiohttp.ClientSession(
        connector=aiohttp.TCPConnector(limit=0),
        timeout=aiohttp.ClientTimeout(total=timeout_s),
    )


async def run_open(session, url, model, requests: List[Request],
                   t0: float) -> List[Result]:
    """Every request at its due time (``t0`` + its offset), whatever the
    earlier ones do."""

    async def one(request: Request) -> Result:
        due = t0 + request.due_s
        delay = due - time.perf_counter()
        if delay > 0:
            await asyncio.sleep(delay)
        return await send(session, url, model, request, due=due)

    return list(await asyncio.gather(*(one(r) for r in requests)))


async def run_closed(session, url, model, requests: List[Request],
                     users: int, seconds: float, t0: float):
    """``users`` callers, each sending its next request when its last one
    ended, for ``seconds`` from ``t0``; requests in flight at the end run
    out. Returns (results, whether a user found the list empty)."""
    queue = iter(requests)
    results: List[Result] = []
    state = {"exhausted": False}

    async def user() -> None:
        while time.perf_counter() - t0 < seconds:
            request = next(queue, None)
            if request is None:
                state["exhausted"] = True
                return
            results.append(await send(session, url, model, request))

    await asyncio.gather(*(user() for _ in range(users)))
    return results, state["exhausted"]
