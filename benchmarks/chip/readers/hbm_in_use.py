"""Bytes in use on the fullest chip after the window, as the engines'
``GET /version`` reports them (``memory_stats()["bytes_in_use"]``; the
program does not export the peak), in GB."""


def read(ctx):
    return ctx["bytes_in_use"] / 1e9 if ctx["bytes_in_use"] else None
