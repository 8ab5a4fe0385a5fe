"""Telemetry CLI.

``python -m hcache_deepspeed_tpu.telemetry dump [--out trace.json]``
    Run the CPU reference workload (3-step train loop + logged
    collective + serving preempt→restore cycle) with tracing on, write
    a Perfetto-loadable ``trace.json`` and print the per-step
    breakdown table. Load the file at https://ui.perfetto.dev.

``python -m hcache_deepspeed_tpu.telemetry dump --fleet``
    Run a small deterministic disaggregated-fleet chaos trace instead
    and export the **assembled multi-replica** timeline: each replica
    renders as its own Perfetto process row (stable labels), with
    cross-track flow arrows for every migration/handoff.

``python -m hcache_deepspeed_tpu.telemetry dump --fabric``
    Run the process-fabric chaos trace (real worker processes, a
    literal SIGKILL) and export the **assembled cross-process**
    timeline: parent rows as in ``--fleet``, PLUS one real process
    row per worker carrying its harvested spans (clock-offset
    aligned), with flow arrows spanning actual worker processes for
    every two-hop migration.

``python -m hcache_deepspeed_tpu.telemetry summarize trace.json ...``
    Validate + summarize one exported trace — or SEVERAL: multiple
    files are merged as separate tracer streams with stable labels
    (one process row per input, in argument order). Traces whose
    source tracer dropped events print an incompleteness warning.
"""

import argparse
import json
import os
import sys


def _cmd_dump(args):
    # host-only by construction: the reference workload is the tier-1
    # acceptance path and must not claim a chip
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    if args.fleet:
        return _dump_fleet(args)
    if args.fabric:
        return _dump_fabric(args)
    from . import render_table, summarize, validate_trace, write_trace
    from .demo import run_demo
    from .tracer import get_tracer

    events, ctx = run_demo(steps=args.steps)
    tracer = get_tracer()
    trace = write_trace(events, args.out,
                        thread_names=tracer.thread_names(),
                        dropped=tracer.dropped)
    stats = validate_trace(trace)
    summary = summarize(events)
    print(render_table(summary))
    sched = ctx["scheduler"]
    print(f"scheduler counters: restores={sched.total_restores} "
          f"overlapped={sched.overlapped_restores}")
    print(f"engine restore_stats: {ctx['serve_engine'].restore_stats}")
    if tracer.dropped:
        print(f"WARNING: tracer dropped {tracer.dropped} events "
              "(ring buffer overflow) — trace is incomplete")
    print(f"wrote {args.out} ({stats['events']} events, "
          f"{stats['spans']} spans) — load at https://ui.perfetto.dev")
    return 0


def _dump_fleet(args):
    """Deterministic multi-replica capture: a small disaggregated
    chaos run traced end-to-end, fanned out into per-replica process
    rows + migration flow arrows by ``telemetry.assemble``."""
    from ..resilience.chaos import run_disagg_chaos
    from .assemble import assemble_fleet_trace, replica_labels
    from .export import validate_trace, write_trace
    from .tracer import get_tracer

    tracer = get_tracer()
    was = tracer.enabled
    tracer.configure(enabled=True)
    tracer.clear()
    try:
        result = run_disagg_chaos(seed=args.seed)
        events = tracer.events()
        dropped = tracer.dropped
    finally:
        tracer.configure(enabled=was)
    assembled, warnings = assemble_fleet_trace(events, dropped=dropped)
    trace = write_trace(assembled, args.out)
    stats = validate_trace(trace)
    for w in warnings:
        print(f"WARNING: {w}")
    replicas = replica_labels(events)
    arrows = sum(1 for e in assembled if e.get("ph") == "s")
    print(f"disagg chaos seed={args.seed}: ok={result.ok} "
          f"handoffs={result.invariants['counters']['handoffs']} "
          f"digest={result.event_digest[:12]}…")
    print(f"wrote {args.out} ({stats['events']} events, "
          f"{stats['spans']} spans, {len(replicas)} replica process "
          f"rows + fleet row, {arrows} migration arrows) — load at "
          "https://ui.perfetto.dev")
    return 0 if result.ok else 4


def _dump_fabric(args):
    """Deterministic cross-process capture: the fabric chaos run
    (process transport, literal SIGKILL) with the parent tracer on;
    harvested worker streams land as real per-process rows via
    ``telemetry.assemble.assemble_process_fleet_trace``."""
    from ..resilience.chaos import run_fabric_chaos
    from .assemble import (WORKER_PID_BASE,
                           assemble_process_fleet_trace,
                           replica_labels)
    from .export import validate_trace, write_trace
    from .tracer import get_tracer

    tracer = get_tracer()
    was = tracer.enabled
    tracer.configure(enabled=True)
    tracer.clear()
    try:
        result = run_fabric_chaos(seed=args.seed)
        events = tracer.events()
        dropped = tracer.dropped
    finally:
        tracer.configure(enabled=was)
    workers = result.telemetry.get("workers", {})
    assembled, warnings = assemble_process_fleet_trace(
        events, workers, dropped=dropped)
    trace = write_trace(assembled, args.out)
    stats = validate_trace(trace)
    for w in warnings:
        print(f"WARNING: {w}")
    replicas = replica_labels(events)
    arrows = sum(1 for e in assembled if e.get("ph") == "s")
    worker_arrows = sum(
        1 for e in assembled
        if e.get("ph") == "s" and e.get("cat") == "fabric")
    worker_rows = sum(
        1 for e in assembled
        if e.get("ph") == "M" and e.get("name") == "process_name"
        and e.get("pid", 0) >= WORKER_PID_BASE)
    harvest = result.telemetry.get("harvest", {})
    print(f"fabric chaos seed={args.seed}: ok={result.ok} "
          f"victim={result.victim} harvests={harvest.get('harvests')} "
          f"digest={result.event_digest[:12]}…")
    print(f"wrote {args.out} ({stats['events']} events, "
          f"{stats['spans']} spans, {len(replicas)} replica rows + "
          f"{worker_rows} worker process rows, {arrows} flow arrows "
          f"of which {worker_arrows} cross worker processes) — load "
          "at https://ui.perfetto.dev")
    return 0 if result.ok else 4


def _cmd_summarize(args):
    from . import load_trace, render_table, summarize, validate_trace
    from .assemble import merge_streams, stream_drop_count

    paths = args.trace or ["trace.json"]
    if len(paths) == 1:
        events = load_trace(paths[0])
        dropped = stream_drop_count(events)
        if dropped:
            print(f"WARNING: {os.path.basename(paths[0])}: source "
                  f"tracer dropped {dropped} events — trace is "
                  "incomplete")
    else:
        # multi-tracer input: each file is its own stream; labels are
        # the file basenames, process rows in argument order
        streams = {}
        for p in paths:
            label = os.path.basename(p)
            base, n = label, 1
            while label in streams:          # duplicate basenames
                n += 1
                label = f"{base}#{n}"
            streams[label] = load_trace(p)
        events, warnings = merge_streams(streams)
        for w in warnings:
            print(f"WARNING: {w}")
    stats = validate_trace(events)
    summary = summarize(events)
    if args.json:
        print(json.dumps(summary, indent=2))
    else:
        print(render_table(summary))
        print(f"({stats['events']} events, {stats['spans']} spans, "
              f"{stats['pairs']} async pairs"
              + (f", {len(paths)} merged streams"
                 if len(paths) > 1 else "") + ")")
    return 0


def main(argv=None):
    parser = argparse.ArgumentParser(
        prog="python -m hcache_deepspeed_tpu.telemetry",
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    sub = parser.add_subparsers(dest="cmd", required=True)

    p_dump = sub.add_parser(
        "dump", help="run the CPU reference workload and export a trace")
    p_dump.add_argument("--out", default="trace.json")
    p_dump.add_argument("--steps", type=int, default=3)
    p_dump.add_argument("--fleet", action="store_true",
                        help="trace a deterministic disaggregated "
                             "fleet run instead and export the "
                             "assembled per-replica timeline")
    p_dump.add_argument("--fabric", action="store_true",
                        help="trace the process-fabric chaos run "
                             "instead and export the assembled "
                             "cross-process timeline (harvested "
                             "worker rows + cross-worker arrows)")
    p_dump.add_argument("--seed", type=int, default=0,
                        help="fleet/fabric-mode chaos seed")
    p_dump.set_defaults(fn=_cmd_dump)

    p_sum = sub.add_parser(
        "summarize", help="validate + summarize exported trace(s); "
                          "multiple files merge as labeled streams")
    p_sum.add_argument("trace", nargs="*",
                       help="trace file(s); default trace.json")
    p_sum.add_argument("--json", action="store_true",
                       help="print the summary as JSON")
    p_sum.set_defaults(fn=_cmd_summarize)

    args = parser.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
