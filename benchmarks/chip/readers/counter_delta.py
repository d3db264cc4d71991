"""A counter's delta over the window."""


def read(ctx, series):
    return ctx["counters"].get(series)
