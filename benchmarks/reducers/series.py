"""``series``: a list of numbers the runner collected (the generator's
lateness, the spans around ``submit``, ``Request.queue_wait``, lanes per
step, step times), reduced by ``how`` (p50, p90, p95, mean, sum, max,
count)."""

from ..stats import reduce_series


def read(spec, evidence):
    values = evidence.get("series", {}).get(spec["series"])
    if not values:
        return None
    return reduce_series(values, spec["how"])
