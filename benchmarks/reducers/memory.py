"""``memory``: ``memory_stats()["peak_bytes_in_use"]`` after the window,
the largest over the chips used."""


def read(spec, evidence):
    return evidence.get("memory", {}).get(spec.get("field", "peak_bytes"))
