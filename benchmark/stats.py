"""Arithmetic of the end-to-end metrics.  No clock is read here."""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile (numpy's default), on a plain list."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of nothing")
    pos = (len(v) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def delivered(arrivals, t_start: float, t_end: float) -> dict:
    """``arrivals``: (k, stamp) of every decodable fragment, in arrival
    order.  Distinct ``k`` whose fragment arrived inside the window."""
    seen = {}
    for k, stamp in arrivals:
        if k is not None and t_start <= stamp < t_end and k not in seen:
            seen[k] = stamp
    return seen


def latencies_ms(seen: dict, t0: float, fps: float) -> list:
    """Arrival stamp minus the due time of frame ``k`` (``t0 + k / fps``)."""
    return [(stamp - (t0 + k / fps)) * 1e3 for k, stamp in seen.items()]


def psnr_db(a, b) -> float:
    import numpy as np

    d = a.astype(np.float32) - b.astype(np.float32)
    mse = float(np.mean(d * d))
    return 99.0 if mse == 0 else 10.0 * math.log10(255.0 * 255.0 / mse)
