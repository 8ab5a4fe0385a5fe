"""The few statistics every reducer shares."""

import math


def percentile(values, q):
    """The q-th percentile (0-100) by linear interpolation between
    order statistics; ``None`` for no values."""
    data = sorted(values)
    if not data:
        return None
    if len(data) == 1:
        return float(data[0])
    pos = (len(data) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(data) - 1)
    return float(data[lo] + (data[hi] - data[lo]) * (pos - lo))


def mean(values):
    values = list(values)
    return float(sum(values) / len(values)) if values else None


REDUCERS = {
    "p50": lambda v: percentile(v, 50),
    "p90": lambda v: percentile(v, 90),
    "p95": lambda v: percentile(v, 95),
    "mean": mean,
    "sum": lambda v: float(sum(v)),
    "max": lambda v: float(max(v)) if v else None,
    "count": lambda v: float(len(v)),
}


def reduce_series(values, how):
    if how not in REDUCERS:
        raise ValueError(f"unknown reducer {how!r}; have {sorted(REDUCERS)}")
    return REDUCERS[how](list(values))
