"""A percentile, over the answered requests of the window, of a time the
client took itself: ``field`` is ``req_ms`` (due -> last chunk), ``ttft_ms``
(due -> first chunk), ``tpot_ms`` ((last - first) / (output tokens - 1)) or
``late_ms`` (due -> sent, the generator's own lateness)."""

from benchmarks.chip.lib.stats import percentile


def read(ctx, field, q):
    return percentile([getattr(r, field) for r in ctx["results"] if r.ok], q)
