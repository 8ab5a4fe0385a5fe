"""``trace_gap``: the idle gaps between consecutive device operations
that are longer than 20 us (so: between programs, not inside one),
reduced by ``how``; seconds."""

from ..stats import reduce_series


def read(spec, evidence):
    reduction = evidence.get("trace")
    if reduction is None or not reduction.gap_lengths:
        return None
    return reduce_series(reduction.gap_lengths, spec["how"])
