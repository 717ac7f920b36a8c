"""Differences of the program's /metrics families over the window."""


def delta(run, family: str):
    a, b = run["counters_start"], run["counters_end"]
    if family not in a or family not in b:
        return None
    return b[family] - a[family]


def mean_ms(run, histogram: str):
    """Mean of a millisecond histogram over the window, from its _sum and
    _count (its buckets are 10-20-50 ms wide, too coarse for a percentile)."""
    total, n = delta(run, histogram + "_sum"), delta(run, histogram + "_count")
    return total / n if total is not None and n else None
