"""Finding a cell's files by the names ``BENCHMARK.json`` gives.

A cell is one entry of ``workloads``: a configuration
(``configs/<name>.json``) under a traffic mix (``traffic/<name>.json``).
A per-layer metric is ``metrics/<name>.json``. Generators, reducers and
runners are modules found by the ``kind`` a data file names, so a new
cell, mix or metric is new files and a new entry, never an edit here.
"""

import importlib
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class ContractError(ValueError):
    """A data file is missing, or names something that does not exist."""


def _load(path):
    try:
        with open(path) as f:
            return json.load(f)
    except OSError as exc:
        raise ContractError(f"cannot read {path}: {exc}") from exc
    except ValueError as exc:
        raise ContractError(f"{path} is not JSON: {exc}") from exc


def load_benchmark(root=ROOT):
    return _load(os.path.join(root, "BENCHMARK.json"))


def find_cell(benchmark, workload):
    for cell in benchmark["workloads"]:
        if cell["name"] == workload:
            return cell
    raise ContractError(
        f"no workload {workload!r} in BENCHMARK.json; have "
        f"{[c['name'] for c in benchmark['workloads']]}")


def load_config(benchmark, name, root=ROOT):
    for entry in benchmark["configs"]:
        if entry["name"] == name:
            return _load(os.path.join(root, entry["file"]))
    raise ContractError(f"no configuration {name!r} in BENCHMARK.json")


def load_traffic(name, bench_dir=HERE):
    return _load(os.path.join(bench_dir, "traffic", f"{name}.json"))


def load_metric_specs(bench_dir=HERE):
    """Every ``metrics/*.json``, by name. The directory is the list:
    nothing in code enumerates the metrics."""
    folder = os.path.join(bench_dir, "metrics")
    specs = {}
    for fname in sorted(os.listdir(folder)):
        if fname.endswith(".json"):
            spec = _load(os.path.join(folder, fname))
            if spec.get("name") != fname[:-len(".json")]:
                raise ContractError(
                    f"{fname} holds the metric {spec.get('name')!r}")
            specs[spec["name"]] = spec
    return specs


def metric_applies(spec, cell, runner_kind):
    """A metric file names its cells by runner kind (``"runner":
    "serve"``) or by name (``"workloads": [...]``)."""
    cells = spec.get("cells", {})
    if "workloads" in cells:
        return cell["name"] in cells["workloads"]
    return cells.get("runner") == runner_kind


def load_kind(package, kind):
    """``benchmarks.<package>.<kind>``: the module a data file's ``kind``
    names."""
    if not kind.replace("_", "").isalnum():
        raise ContractError(f"bad {package} kind {kind!r}")
    try:
        return importlib.import_module(f"benchmarks.{package}.{kind}")
    except ModuleNotFoundError as exc:
        raise ContractError(
            f"no {package} of kind {kind!r} (benchmarks/{package}/"
            f"{kind}.py): {exc}") from exc


def apply_overrides(traffic, overrides):
    """``--set key=value`` on the command line: how a sweep varies one
    number of a traffic file without a second file. The driver never
    passes it."""
    out = dict(traffic)
    for item in overrides or ():
        key, _, raw = item.partition("=")
        if key not in out:
            raise ContractError(f"--set {key}: the traffic file has no "
                                f"such key")
        out[key] = json.loads(raw)
    return out
