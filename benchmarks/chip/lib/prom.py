"""Prometheus text -> numbers. Counters and histogram sums are read as
deltas over a window; gauges are polled."""

from typing import Dict


def parse(text: str) -> Dict[str, float]:
    """``{series name: value}`` with label sets summed (one engine exports
    each series once, or once per device)."""
    out: Dict[str, float] = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        name, _, value = line.rpartition(" ")
        name = name.split("{", 1)[0]
        try:
            out[name] = out.get(name, 0.0) + float(value)
        except ValueError:
            continue
    return out


def add(a: Dict[str, float], b: Dict[str, float]) -> Dict[str, float]:
    """Series of several engines, summed."""
    out = dict(a)
    for name, value in b.items():
        out[name] = out.get(name, 0.0) + value
    return out


def delta(before: Dict[str, float], after: Dict[str, float]) -> Dict[str, float]:
    return {name: value - before.get(name, 0.0)
            for name, value in after.items()}
