"""Cross-process timeline assembly: harvested worker streams become
real per-process Perfetto rows shifted onto the parent timeline by
the handshake-estimated clock offset, with flow arrows pairing the
src worker's ``fabric.forward_out`` against the dst worker's
``fabric.migrate_in`` — and drop honesty carried through from both
the worker tracer rings and the harvest trim."""

from hcache_deepspeed_tpu.telemetry import (
    assemble_process_fleet_trace, validate_trace, worker_flows)
from hcache_deepspeed_tpu.telemetry.assemble import WORKER_PID_BASE


def _parent_events():
    return [
        {"ph": "X", "name": "serve.step", "ts": 5.0, "dur": 2.0,
         "pid": 0, "tid": 0, "args": {"replica": 0, "uid": 7}},
        {"ph": "X", "name": "serve.step", "ts": 9.0, "dur": 2.0,
         "pid": 0, "tid": 0, "args": {"replica": 1, "uid": 7}},
    ]


def _worker_streams():
    # worker 0 forwards uid 7 out at local ts 1.0 (offset +100 -> 101);
    # worker 1 lands it at local ts 2.0 (offset +200 -> 202)
    return {
        0: {"events": [
                {"ph": "i", "name": "fabric.forward_out", "ts": 1.0,
                 "pid": 0, "tid": 1, "args": {"uid": 7, "replica": 0}},
                {"ph": "M", "name": "process_name", "pid": 0,
                 "tid": 0, "args": {"name": "ignored"}}],
            "clock_offset_us": 100.0, "dropped": 0},
        1: {"events": [
                {"ph": "i", "name": "fabric.migrate_in", "ts": 2.0,
                 "pid": 0, "tid": 1, "args": {"uid": 7, "replica": 1}},
                {"ph": "X", "name": "fabric.migration", "ts": 2.0,
                 "dur": 1.5, "pid": 0, "tid": 1,
                 "args": {"replica": 1, "uid": 7}}],
            "clock_offset_us": 200.0, "dropped": 3},
    }


def test_worker_rows_are_offset_aligned_real_processes():
    out, warnings = assemble_process_fleet_trace(
        _parent_events(), _worker_streams())
    validate_trace(out)                       # Perfetto-clean
    rows = {e["pid"]: e["args"]["name"] for e in out
            if e.get("ph") == "M" and e.get("name") == "process_name"}
    assert rows[WORKER_PID_BASE + 0] == "worker 0"
    assert rows[WORKER_PID_BASE + 1] == "worker 1"
    # parent fan-out rows survive untouched beside the worker rows
    assert rows[0] == "replica 0" and rows[1] == "replica 1"
    # clock alignment: worker ts shifted by its handshake offset onto
    # the parent timeline; the worker's own M events are replaced by
    # the worker row
    fwd = next(e for e in out
               if e.get("name") == "fabric.forward_out")
    assert fwd["pid"] == WORKER_PID_BASE + 0 and fwd["ts"] == 101.0
    land = next(e for e in out
                if e.get("name") == "fabric.migrate_in")
    assert land["pid"] == WORKER_PID_BASE + 1 and land["ts"] == 202.0
    assert not any(e.get("args", {}).get("name") == "ignored"
                   for e in out)
    # drop honesty: worker 1's harvest reported 3 dropped events
    assert any("worker 1" in w and "3" in w for w in warnings)


def test_cross_worker_arrow_pairs_real_process_rows():
    out, _ = assemble_process_fleet_trace(
        _parent_events(), _worker_streams())
    starts = [e for e in out
              if e.get("ph") == "s" and e.get("cat") == "fabric"]
    ends = [e for e in out
            if e.get("ph") == "f" and e.get("cat") == "fabric"]
    assert len(starts) == 1 and len(ends) == 1
    assert starts[0]["pid"] == WORKER_PID_BASE + 0
    assert ends[0]["pid"] == WORKER_PID_BASE + 1
    assert starts[0]["id"] == ends[0]["id"]
    assert ends[0]["bp"] == "e"


def test_worker_flows_skips_same_pid_and_unmatched():
    # same-pid pair: a direct delivery that never crossed a
    # worker-to-worker wire — no arrow
    same = [
        {"ph": "i", "name": "fabric.forward_out", "ts": 1.0,
         "pid": 9000, "tid": 0, "args": {"uid": 1}},
        {"ph": "i", "name": "fabric.migrate_in", "ts": 2.0,
         "pid": 9000, "tid": 0, "args": {"uid": 1}},
    ]
    assert worker_flows(same) == []
    # landing with no matching departure, and identity-less instants,
    # both stay silent
    orphan = [
        {"ph": "i", "name": "fabric.migrate_in", "ts": 2.0,
         "pid": 9001, "tid": 0, "args": {"uid": 2}},
        {"ph": "i", "name": "fabric.forward_out", "ts": 3.0,
         "pid": 9000, "tid": 0, "args": {}},
    ]
    assert worker_flows(orphan) == []


def test_empty_worker_streams_degrade_to_fleet_assembly():
    out, warnings = assemble_process_fleet_trace(_parent_events(), {})
    validate_trace(out)
    assert warnings == []
    assert not any(e.get("pid", 0) >= WORKER_PID_BASE for e in out)
