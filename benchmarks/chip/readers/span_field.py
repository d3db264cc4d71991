"""A mean over the capture's dispatches, each ``pstpu.issue`` /
``pstpu.fetch`` span paired with its program on the device
(``lib/spans.py``), times ``scale``. Nothing without a capture, without
spans in it (a program that predates them), or where fewer than 90% of the
completed dispatches could be paired."""

from benchmarks.chip.lib import spans


def read(ctx, field, scale=1.0):
    value = (spans.of(ctx)["spans"] or {}).get(field)
    return None if value is None else scale * value
