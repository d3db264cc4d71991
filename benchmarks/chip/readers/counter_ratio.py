"""Ratio of two counters' deltas over the window, times ``scale``. ``den``
may be ``"span_s"``: the seconds from the window's start to its last
answer, over which the deltas were taken."""


def read(ctx, num, den, scale=1.0):
    below = ctx["span_s"] if den == "span_s" else ctx["counters"].get(den, 0)
    if not below or num not in ctx["counters"]:
        return None
    return scale * ctx["counters"][num] / below
