"""Quartile spreads of sets of result lines: ``python -m
benchmarks.tools.spread <file>...``. Each file holds the outputs of the
runs of one set (any text; the lines that parse as a result object are
taken). Per metric: the median and (Q3 - Q1) / median, with Python's
``statistics.quantiles(values, n=4)``."""

import json
import statistics
import sys


def result_lines(path):
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line.startswith('{"correct"'):
                try:
                    out.append(json.loads(line))
                except ValueError:
                    pass
    return out


def spread(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def table(results):
    names = sorted({k for r in results for k in r["metrics"]})
    rows = {}
    for name in names:
        values = [r["metrics"][name]["value"] for r in results
                  if name in r["metrics"]]
        if len(values) >= 2:
            rows[name] = (statistics.median(values), spread(values), values)
    return rows


if __name__ == "__main__":
    for path in sys.argv[1:]:
        results = result_lines(path)
        print(f"{path}: {len(results)} runs, correct "
              f"{[r['correct'] for r in results]}, failed "
              f"{[r['failed'] for r in results]}")
        for name, (median, share, values) in table(results).items():
            print(f"  {name}: median {median:.6g} spread {100 * share:.2f}% "
                  f"values {[round(v, 5) for v in values]}")
