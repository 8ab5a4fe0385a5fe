"""``exposed_share``: time in which a collective operation runs on the
chip and no other operation does, over the traced window, in percent
(first chip). A collective is told by its HLO opcode or, for the
compiler's ``async-collective-start/done`` wrappers, by its name."""

import re

from ..trace.xplane import covered, union

COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast|async-collective)")


def exposed_seconds(ops):
    coll = union((op.start, op.end) for op in ops
                 if COLLECTIVE.match(op.label))
    other = union((op.start, op.end) for op in ops
                  if not COLLECTIVE.match(op.label))
    both = covered(coll + other)
    return covered(coll) - (covered(coll) + covered(other) - both)


def read(spec, evidence):
    reduction = evidence.get("trace")
    if reduction is None or not reduction.window_s:
        return None
    trace = reduction.trace
    ops = trace.chips[min(trace.chips)]
    return 100.0 * exposed_seconds(ops) / reduction.window_s
