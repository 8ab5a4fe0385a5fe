"""``counter``: one number the program (or the benchmark's compile
meter) counted over the window."""


def read(spec, evidence):
    return evidence.get("counters", {}).get(spec["counter"])
