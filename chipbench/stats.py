"""Order statistics of the benchmark.

``percentile`` is a copy of ``repro.engine.metrics.percentile`` (linear
interpolation, numpy's default), kept here so that no change to the
program moves the yardstick.
"""
from __future__ import annotations


def percentile(values: list[float], p: float) -> float:
    """Linear-interpolated percentile, ``p`` in [0, 100]; ``values`` must
    not be empty."""
    if not values:
        raise ValueError("percentile of an empty sample")
    xs = sorted(values)
    if len(xs) == 1:
        return xs[0]
    rank = (p / 100.0) * (len(xs) - 1)
    lo = int(rank)
    hi = min(lo + 1, len(xs) - 1)
    frac = rank - lo
    return xs[lo] * (1.0 - frac) + xs[hi] * frac
