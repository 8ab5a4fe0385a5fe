"""The teeth of a ``serve_window`` cell's check, shown on the chip:
``python -m benchmarks.tools.window_controls --seed <n> [--seconds <s>]
[--workload <name>]`` runs the cell once, as ``benchmarks.run`` does,
and then its own comparison (``runners/serve_window.py check_rows``)
over the same probed rows against the reference computed wrong, once
for each of the runner's ``CONTROLS``: the window dropped (full
attention on every layer), a window of 2048, the rotary step applied on
the global layer, the rotary pairing half-split on unpermuted columns,
the sequential block (the expert layer reading ``x + Attn``), a softmax
router, weights not renormalised, the shared experts summed and not
averaged, the shared experts dropped, the wrong 16 experts held, a
dropped eighth pick, and a residual stream in the nearest precision
below the stated one. The served path against a wrong reference reads
as a served path with that fault would against the right one.

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
from ..runners import serve_window as runner
from ..runners.common import Context
from .latent_controls import summary


def with_controls(out, controls=None):
    """``check_rows`` that also runs every control (or ``controls``
    alone), into ``out``."""
    def check(ctx, built, rows, probed):
        ok, details = runner.check_rows(ctx, built, rows, probed)
        out["plain"] = {"correct": ok, **details}
        for name in controls or runner.CONTROLS:
            wrong, seen = runner.check_rows(ctx, built, rows, probed,
                                            control=name)
            out[name] = {"correct": wrong, **seen}
        return ok, details
    return check


def main(argv=None):
    p = argparse.ArgumentParser(prog="python -m "
                                "benchmarks.tools.window_controls")
    p.add_argument("--workload", default="cmdaplus-serve-mixed-length")
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, default=30.0)
    p.add_argument("--only", action="append", choices=sorted(
        runner.CONTROLS), help="this control alone (may repeat)")
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
    result = runner.run(ctx, check=with_controls(out, args.only))
    for name, entry in out.items():
        print(f"{name}: " + json.dumps(entry if args.rows
                                       else summary(entry)), flush=True)
    passed = [n for n in out if n != "plain" and out[n]["correct"]]
    verdict = {"correct": result["correct"], "controls_that_passed": passed,
               "limit": runner.LOGIT_TOL,
               **{n: summary(e) for n, e in out.items()}}
    print(json.dumps(verdict), flush=True)
    return 0 if result["correct"] and not passed else 1


if __name__ == "__main__":
    sys.exit(main())
