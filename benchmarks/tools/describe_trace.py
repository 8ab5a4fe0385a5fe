"""Print what a profiler trace holds: ``python -m
benchmarks.tools.describe_trace <dir-or-file> [events-per-line]``. For
looking at one trace by hand before writing a pattern against it."""

import os
import sys

from ..trace import xplane


def find(path):
    if os.path.isfile(path):
        return path
    for folder, _, files in os.walk(path):
        for fname in files:
            if fname.endswith(".xplane.pb"):
                return os.path.join(folder, fname)
    raise SystemExit(f"no .xplane.pb under {path}")


if __name__ == "__main__":
    limit = int(sys.argv[2]) if len(sys.argv) > 2 else 12
    print(xplane.describe(find(sys.argv[1]), limit))
