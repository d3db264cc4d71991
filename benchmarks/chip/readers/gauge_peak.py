"""Highest value of a gauge over the polls made during the window."""


def read(ctx, series, scale=1.0):
    seen = [p[series] for p in ctx["polls"] if series in p]
    return scale * max(seen) if seen else None
