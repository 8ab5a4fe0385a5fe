"""``python -m hcache_deepspeed_tpu.perf`` — the observatory CLI.

Subcommands:

* ``index [--out PATH] [--git] [--root DIR]`` — rebuild the committed
  ``PERF_TRAJECTORY.json`` from the root artifacts (``--git`` adds
  producer-PR attribution; slower, used for the committed index).
* ``check --against PERF_TRAJECTORY.json [FILE...]`` — regression
  gate: parse each FILE (default: every indexable root artifact) and
  fail (exit 5) if any headline metric regressed beyond tolerance.
  ``--self-test`` instead proves the gate trips on synthetic
  regressions (tier-1 runs this; exit 6 on failure).
* ``lint [--root DIR]`` — fail (exit 7) if any source file writes an
  artifact-style filename the registry has no schema for.
"""

import argparse
import os
import sys


def _cmd_index(args) -> int:
    from .registry import write_index
    index = write_index(path=args.out, root=args.root,
                        with_git=args.git, now=args.now)
    n_pts = sum(len(v) for v in index["series"].values())
    print(f"indexed {len(index['artifacts'])} artifacts -> "
          f"{len(index['series'])} series / {n_pts} points; "
          f"unindexed={index['unindexed']}")
    return 0


def _cmd_check(args) -> int:
    from .check import (check_artifact, check_headline, regressions,
                        self_test)
    from .registry import build_index, load_index, repo_root
    if args.self_test:
        return 0 if self_test(verbose=True) else 6
    root = args.root or repo_root()
    baseline = load_index(path=args.against, root=root)
    failed = False
    if args.files:
        # per-file mode: gate fresh run outputs before they land
        for path in args.files:
            try:
                verdicts = check_artifact(path, baseline)
            except Exception as exc:  # noqa: BLE001 — report, go on
                print(f"{os.path.basename(path)}: ERROR {exc!r}")
                failed = True
                continue
            regs = regressions(verdicts)
            gated = [v for v in verdicts
                     if v.status != "no-baseline"]
            if regs:
                failed = True
                for v in regs:
                    print(f"{os.path.basename(path)}: REGRESSION "
                          f"{v.metric}: {v.detail}")
            elif args.verbose:
                print(f"{os.path.basename(path)}: ok "
                      f"({len(gated)} headline metrics)")
    else:
        # repo mode: the tree's best evidence per metric must still
        # reach the committed headline (history is not re-judged)
        fresh = build_index(root, now=args.now)
        for v in check_headline(fresh, baseline):
            if v.status == "regression":
                failed = True
                print(f"REGRESSION {v.metric}: {v.detail}")
            elif args.verbose:
                print(f"{v.metric}: {v.status} ({v.new_value})")
    if failed:
        print("perf check: FAILED")
        return 5
    print("perf check: ok")
    return 0


def _cmd_lint(args) -> int:
    from .registry import lint_sources
    violations = lint_sources(root=args.root)
    for v in violations:
        print(v)
    if violations:
        print(f"perf lint: {len(violations)} violation(s)")
        return 7
    print("perf lint: ok")
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        "python -m hcache_deepspeed_tpu.perf",
        description="perf-artifact registry + regression sentinel")
    p.add_argument("--root", default=None,
                   help="repo root (default: auto-detect)")
    p.add_argument("--now", type=float, default=None,
                   help="generated_utc reference time (UTC epoch "
                        "seconds); injects the ONE sanctioned wall-"
                        "clock default in registry.build_index, "
                        "making index/check runs reproducible")
    sub = p.add_subparsers(dest="cmd", required=True)

    pi = sub.add_parser("index", help="rebuild PERF_TRAJECTORY.json")
    pi.add_argument("--out", default=None)
    pi.add_argument("--git", action="store_true",
                    help="attribute each artifact to its producing "
                         "commit (slower)")
    pi.set_defaults(fn=_cmd_index)

    pc = sub.add_parser("check", help="regression gate")
    pc.add_argument("--against", default=None,
                    help="baseline index (default: committed "
                         "PERF_TRAJECTORY.json)")
    pc.add_argument("--self-test", action="store_true",
                    help="prove the gate trips on synthetic "
                         "regressions (no repo state needed)")
    pc.add_argument("--verbose", action="store_true")
    pc.add_argument("files", nargs="*",
                    help="artifacts to gate (default: all indexable "
                         "root artifacts)")
    pc.set_defaults(fn=_cmd_check)

    pl = sub.add_parser("lint",
                        help="no source-written artifact without a "
                             "schema")
    pl.set_defaults(fn=_cmd_lint)

    args = p.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
