"""One cell, once: ``python -m benchmarks.run --workload <name> --seed
<n> --seconds <s> --trace <0|1>``.

Refuses to run without a TPU, names the device, builds weights on the
device from the seed, warms up, measures, checks, prints where the
set-up time went and then, as the last line, the result as one JSON
object. Exits non-zero without a result line if anything is missing.
"""

import time

_T_START = time.monotonic()     # as near to process start as code gets

import argparse                 # noqa: E402
import json                     # noqa: E402
import os                       # noqa: E402
import sys                      # noqa: E402


def parse(argv):
    p = argparse.ArgumentParser(prog="python -m benchmarks.run")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--set", action="append", default=[],
                   metavar="KEY=VALUE",
                   help="override one number of the traffic file (for "
                        "the sweep that finds a cell's rate; the driver "
                        "never passes it)")
    return p.parse_args(argv)


def place_compile_cache(root):
    """The persistent compilation cache: where
    ``JAX_COMPILATION_CACHE_DIR`` says, else ``<checkout>/.jax_cache``
    (the path the program's own ``utils/compile_cache.py`` takes, so the
    two never disagree), and every program stored, however quickly it
    was built: most serve programs build in under a second and JAX's
    default keeps none of those."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(root, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def require_chips(chips):
    """The devices this run is about; exits non-zero, with no result
    line, when JAX finds no accelerator or fewer chips than the cell
    asks for."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"benchmarks.run: JAX found no accelerator (platform "
                 f"{devices[0].platform!r}); nothing is measured on a CPU")
    if len(devices) < chips:
        sys.exit(f"benchmarks.run: the cell asks for {chips} chips, JAX "
                 f"found {len(devices)}")
    return devices


def main(argv=None):
    args = parse(argv)
    from . import contract
    try:
        import hcache_deepspeed_tpu  # noqa: F401 — the system under test
    except ImportError as exc:
        sys.exit(f"benchmarks.run runs from a checkout of the repository "
                 f"it measures: {exc}")
    benchmark = contract.load_benchmark()
    cell = contract.find_cell(benchmark, args.workload)
    config = contract.load_config(benchmark, cell["config"])
    traffic = contract.apply_overrides(
        contract.load_traffic(cell["traffic"]), args.set)
    place_compile_cache(contract.ROOT)
    require_chips(cell["chips"])

    from .compile_meter import CompileMeter
    from .runners.common import Context
    runner = contract.load_kind("runners", config["runner"])
    ctx = Context(cell=cell, config=config, traffic=traffic,
                  seed=args.seed, seconds=args.seconds,
                  trace=bool(args.trace), t_start=_T_START,
                  root=contract.ROOT, meter=CompileMeter())
    ctx.phases["import"] = round(time.monotonic() - _T_START, 3)
    result = runner.run(ctx)
    print("setup: " + json.dumps(ctx.phases), flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
