"""``idle_by_span``: the device's idle seconds (gaps over 20 us on the
first chip) whose innermost open host span matches ``span``, over the
traced window, in percent: the idle that one layer's host code causes.
Reads ``reduction.gap_seconds``, the breakdown's own attribution, so
the shares of disjoint patterns, the ``_no_span_`` bin and the gaps
under 20 us add up to the device's idle share. No bin matches:
nothing."""

import re


def read(spec, evidence):
    reduction = evidence.get("trace")
    if reduction is None or not reduction.window_s:
        return None
    rx = re.compile(spec["span"])
    bins = [v for k, v in reduction.gap_seconds.items() if rx.search(k)]
    if not bins:
        return None
    return 100.0 * sum(bins) / reduction.window_s
