"""A number the trace reduction (``lib/xplane.py`` + ``lib/roofline.py``)
already holds under ``field``; nothing without a trace."""


def read(ctx, field, scale=1.0):
    value = (ctx.get("trace") or {}).get(field)
    return None if value is None else scale * value
