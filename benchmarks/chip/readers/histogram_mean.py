"""Mean of a Prometheus histogram over the window: its ``_sum`` delta over
its ``_count`` delta, times ``scale``."""


def read(ctx, series, scale=1.0):
    count = ctx["counters"].get(f"{series}_count", 0)
    if not count:
        return None
    return scale * ctx["counters"][f"{series}_sum"] / count
