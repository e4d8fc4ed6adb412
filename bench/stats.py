"""Percentile and rate arithmetic of the benchmark's metrics.

``pctile`` is a copy of the program's ``serving/metrics.py:pctile``: numpy's
linear interpolation between order statistics over every sample, not a
bucketed estimate.
"""
from __future__ import annotations

import numpy as np


def pctile(xs, q: float) -> float:
    """The ``q``-th percentile of ``xs``; NaN when there are none."""
    arr = np.asarray(list(xs), np.float64).reshape(-1)
    return float(np.percentile(arr, q)) if arr.size else float("nan")


def rate(count: float, t_start: float, t_end: float) -> float:
    """``count`` over the seconds from ``t_start`` to ``t_end``."""
    if t_end <= t_start:
        raise ValueError(f"empty interval [{t_start}, {t_end}]")
    return count / (t_end - t_start)

