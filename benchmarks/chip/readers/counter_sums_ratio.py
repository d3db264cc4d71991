"""Sum of some counters' deltas over the window divided by the sum of
others', times ``scale``: seconds of several loop phases per dispatch of
either kind. Nothing where a counter is missing (a program that does not
export it) or the divisor is 0."""


def read(ctx, num, den, scale=1.0):
    counters = ctx["counters"]
    if any(name not in counters for name in (*num, *den)):
        return None
    below = sum(counters[name] for name in den)
    if not below:
        return None
    return scale * sum(counters[name] for name in num) / below
