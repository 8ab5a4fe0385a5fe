"""``host_span``: the program's own host spans in the profiler trace
(``evidence["trace"].trace.host``), on the device trace's clock.

``span`` is a pattern on span names. Without more, the durations of
the matching spans, reduced by ``how``; seconds. With ``less`` (a second
pattern), each duration is first shortened by the time that spans
matching ``less`` cover inside it: a parent's own time, without the
child. With ``per`` (a pattern) and no ``how``, the number of matching
spans over the number of spans matching ``per``: a ratio of counts.
No matching span (or none matching ``per``): nothing.

Only the thread that drives the device may open spans under the
matched prefixes (``README.md`` beside this file), so spans that match
never overlap unless one is inside the other.
"""

import re

from ..stats import reduce_series
from ..trace.xplane import covered


def own_durations(spans, inner):
    """Each span's duration less what ``inner`` spans cover inside
    it."""
    out = []
    for span in spans:
        inside = [(max(s.start, span.start), min(s.end, span.end))
                  for s in inner
                  if s.end > span.start and s.start < span.end]
        out.append(span.end - span.start - covered(inside))
    return out


def read(spec, evidence):
    reduction = evidence.get("trace")
    if reduction is None:
        return None
    host = reduction.trace.host

    def matching(pattern):
        rx = re.compile(pattern)
        return [s for s in host if rx.search(s.name)]

    spans = matching(spec["span"])
    if not spans:
        return None
    if "per" in spec:
        base = matching(spec["per"])
        return len(spans) / len(base) if base else None
    inner = matching(spec["less"]) if "less" in spec else []
    return reduce_series(own_durations(spans, inner), spec["how"])
