"""Sum of the means of several Prometheus histograms over the window (the
stages of one path, each observed once per request), times ``scale``.
Nothing where one of them is missing or empty."""

from benchmarks.chip.readers import histogram_mean


def read(ctx, series, scale=1.0):
    means = [histogram_mean.read(ctx, name) for name in series]
    if any(m is None for m in means):
        return None
    return scale * sum(means)
