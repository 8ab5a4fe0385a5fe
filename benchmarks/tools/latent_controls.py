"""The teeth of a ``serve_latent`` cell's check, shown on the chip:
``python -m benchmarks.tools.latent_controls --seed <n> [--seconds <s>]
[--workload <name>]`` runs the cell once, as ``benchmarks.run`` does,
and then its own comparison (``runners/serve_latent.py check_rows``)
over the same probed rows against the reference computed wrong, once
for each of the runner's ``CONTROLS``: ``r`` cached without its rotary
step, ``c`` cached before its norm, the scale ``1 / sqrt(192)``, a
softmax router, the selection bias added to the weights, the scaling
factor dropped, the shared expert dropped, a dropped fourth pick, and a
residual stream in the nearest precision below the stated one. The
served path against a wrong reference reads as a served path with that
fault would against the right one.

Prints a line a control (its verdict, its largest and median row) and,
last, one JSON object; exits 1 unless the plain check passes and every
control comes out not correct.
"""

import argparse
import json
import sys
import time

from .. import contract
from .. import run as bench_run
from ..compile_meter import CompileMeter
from ..runners import serve_latent as runner
from ..runners.common import Context


def with_controls(out):
    """``check_rows`` that also runs every control, into ``out``."""
    def check(ctx, built, rows, probed):
        ok, details = runner.check_rows(ctx, built, rows, probed)
        out["plain"] = {"correct": ok, **details}
        for name in runner.CONTROLS:
            wrong, seen = runner.check_rows(ctx, built, rows, probed,
                                            control=name)
            out[name] = {"correct": wrong, **seen}
        return ok, details
    return check


def summary(entry):
    return {k: entry.get(k) for k in ("correct", "largest", "median",
                                      "rows", "reason") if k in entry}


def main(argv=None):
    p = argparse.ArgumentParser(prog="python -m "
                                "benchmarks.tools.latent_controls")
    p.add_argument("--workload", default="glm47f-serve-long-doc")
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, default=30.0)
    p.add_argument("--rows", action="store_true",
                   help="print every compared row as well")
    args = p.parse_args(argv)
    benchmark = contract.load_benchmark()
    cell = contract.find_cell(benchmark, args.workload)
    config = contract.load_config(benchmark, cell["config"])
    bench_run.place_compile_cache(contract.ROOT)
    bench_run.require_chips(cell["chips"])
    ctx = Context(cell=cell, config=config,
                  traffic=contract.load_traffic(cell["traffic"]),
                  seed=args.seed, seconds=args.seconds, trace=False,
                  t_start=time.monotonic(), root=contract.ROOT,
                  meter=CompileMeter())
    out = {}
    result = runner.run(ctx, check=with_controls(out))
    for name, entry in out.items():
        print(f"{name}: " + json.dumps(entry if args.rows
                                       else summary(entry)), flush=True)
    passed = [n for n in runner.CONTROLS if out[n]["correct"]]
    verdict = {"correct": result["correct"], "controls_that_passed": passed,
               "limit": runner.LOGIT_TOL,
               **{n: summary(e) for n, e in out.items()}}
    print(json.dumps(verdict), flush=True)
    return 0 if result["correct"] and not passed else 1


if __name__ == "__main__":
    sys.exit(main())
