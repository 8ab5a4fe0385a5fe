"""Per-layer metrics, computed from the files in ``metrics/``.

A runner gathers *evidence*: series and counts from the load generator,
the benchmark's own spans, the program's counters, the reduced device
trace and the device's memory statistics. Each ``metrics/<name>.json``
says which piece it reads (``reads``: the reducer module of that kind
under ``reducers/``) and how. The harness computes every metric whose
file names the cell; a reader that finds nothing to read returns
``None`` and the metric is left out of the line.
"""

from . import contract


def fill(pattern, evidence):
    """``pattern`` with the runner's placeholders (``{kv_pool}``, ...)
    filled in; ``None`` if it names one the runner did not give."""
    try:
        return pattern.format_map(evidence.get("placeholders", {}))
    except KeyError:
        return None


def compute(cell, runner_kind, evidence, specs=None):
    """``{name: {"value", "unit"}}`` for every metric file that names
    this cell."""
    specs = contract.load_metric_specs() if specs is None else specs
    out = {}
    for name, spec in sorted(specs.items()):
        if not contract.metric_applies(spec, cell, runner_kind):
            continue
        reader = contract.load_kind("reducers", spec["reads"])
        value = reader.read(spec, evidence)
        if value is None:
            continue
        out[name] = {"value": float(value) * float(spec.get("scale", 1.0)),
                     "unit": spec["unit"]}
    return out
