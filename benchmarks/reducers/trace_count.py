"""``trace_count``: operations per executed program (``program``: a
pattern on the ``XLA Modules`` names) whose HLO text matches ``pattern``
and whose result is none of the shapes the runner lists under
``except`` (the parameter leaves, which ZeRO-3 gathers by design), on
the first chip. What is left are gathers and exchanges of anything
else: reshards the compiler put around a kernel or a layout mismatch."""

import re

_DIMS = re.compile(r"_[a-z]+\d+_((?:\d+_)*)$")


def read(spec, evidence):
    reduction = evidence.get("trace")
    if reduction is None:
        return None
    trace = reduction.trace
    chip = min(trace.chips)
    steps = len([m for m in trace.modules.get(chip, ())
                 if re.search(spec["program"], m.name)])
    if not steps:
        return None
    skip = set(evidence.get(spec.get("except", ""), ()))
    n = 0
    for op in reduction.matching(spec["pattern"], spec.get("on", "text")):
        dims = _DIMS.search(op.label)
        if not dims or dims.group(1) not in skip:
            n += 1
    return n / steps
