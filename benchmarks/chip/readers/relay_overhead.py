"""What the router and the wire add before the first token: the client's
mean sent -> first chunk minus the engines' own mean
``vllm:time_to_first_token_seconds`` over the same requests. Taken from
outside until the router's spans are reduced."""

from statistics import fmean


def read(ctx):
    count = ctx["counters"].get("vllm:time_to_first_token_seconds_count", 0)
    ok = [r for r in ctx["results"] if r.ok]
    if not count or not ok:
        return None
    engine_ms = 1e3 * ctx["counters"][
        "vllm:time_to_first_token_seconds_sum"] / count
    return fmean((r.first - r.sent) * 1e3 for r in ok) - engine_ms
