"""``trace_idle``: 1 - (union of the device's operation intervals) over
the traced window, averaged over the chips traced, in percent."""


def read(spec, evidence):
    reduction = evidence.get("trace")
    if reduction is None or not reduction.window_s:
        return None
    return 100.0 * reduction.idle_share
