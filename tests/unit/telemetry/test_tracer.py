"""Tracer + export unit tests: ring buffer semantics, thread safety,
zero-cost-when-disabled, trace_event schema validation (both directions
— valid traces pass, each malformation class raises)."""

import json
import threading

import pytest

from hcache_deepspeed_tpu.telemetry import (Tracer, load_trace,
                                            to_trace_events,
                                            validate_trace, write_trace)
from hcache_deepspeed_tpu.telemetry.tracer import _NULL_SPAN


def tracer(**kw):
    t = Tracer(**kw)
    t.configure(enabled=True, xla=False)
    return t


# ------------------------------------------------------------------ #
# recording
# ------------------------------------------------------------------ #
def test_disabled_tracer_records_nothing_and_returns_null_span():
    t = Tracer()
    assert t.span("x", a=1) is _NULL_SPAN     # shared no-op, no alloc
    with t.span("x") as sp:
        assert sp.set(b=2) is sp              # attr set is a no-op too
    t.instant("y")
    t.counter("z", 1.0)
    t.async_begin("r", 1)
    t.async_end("r", 1)
    assert t.events() == []


def test_span_records_duration_and_attrs():
    t = tracer()
    with t.span("work", step=3) as sp:
        sp.set(bytes=17)
    (ev,) = t.events()
    assert ev["ph"] == "X" and ev["name"] == "work"
    assert ev["dur"] >= 0 and ev["args"] == {"step": 3, "bytes": 17}


def test_span_hands_its_opening_attributes_to_the_profiler_annotation():
    """What a span is opened with goes to the platform's annotation
    (``jax.profiler.TraceAnnotation(name, **attrs)`` on the chip);
    what ``set()`` adds later stays in the ring buffer only."""
    import contextlib
    t = tracer()
    seen = []

    def annotate(name, **attrs):
        seen.append((name, dict(attrs)))
        return contextlib.nullcontext()

    t._annotate_fn = annotate
    with t.span("serve.fetch", bytes=12, lanes=3) as sp:
        sp.set(late=1)
    assert seen == [("serve.fetch", {"bytes": 12, "lanes": 3})]
    assert t.events()[0]["args"] == {"bytes": 12, "lanes": 3, "late": 1}


def test_traced_decorator_is_one_span_per_call(monkeypatch):
    from hcache_deepspeed_tpu.telemetry import tracer as mod
    t = tracer()
    monkeypatch.setattr(mod, "_tracer", t)

    @mod.traced("hds.serve.entry")
    def entry(x, y=1):
        """doc"""
        return x + y

    assert entry(2, y=3) == 5 and entry.__doc__ == "doc"
    assert [e["name"] for e in t.events()] == ["hds.serve.entry"]
    t.configure(enabled=False)
    assert entry(1) == 2 and len(t.events()) == 1


def test_nested_spans_and_sorted_export_monotone():
    t = tracer()
    with t.span("outer"):
        with t.span("inner"):
            pass
    inner, outer = t.events()      # recorded at exit: inner first
    assert (inner["name"], outer["name"]) == ("inner", "outer")
    # raw buffer is exit-ordered (outer.ts < inner.ts); the exporter
    # re-sorts so the validator's monotonicity check passes
    assert outer["ts"] <= inner["ts"]
    validate_trace(to_trace_events(t.events()))


def test_ring_buffer_bounds_memory():
    t = tracer(capacity=8)
    for i in range(100):
        t.instant("e", i=i)
    evs = t.events()
    assert len(evs) == 8
    assert [e["args"]["i"] for e in evs] == list(range(92, 100))


def test_thread_safety_and_tid_assignment():
    t = tracer()
    # barrier: all 4 threads must be alive at once, else the OS may
    # reuse a finished thread's ident and collapse the tid count
    barrier = threading.Barrier(4)

    def worker():
        barrier.wait()
        for _ in range(200):
            with t.span("w"):
                pass

    threads = [threading.Thread(target=worker) for _ in range(4)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    evs = t.events()
    assert len(evs) == 800
    assert len({e["tid"] for e in evs}) == 4
    validate_trace(to_trace_events(evs, thread_names=t.thread_names()))


def test_counter_and_async_pairing():
    t = tracer()
    t.counter("kv_util", 0.5)
    t.async_begin("request", 7, prio=1)
    t.async_end("request", 7, tokens=4)
    c, b, e = t.events()
    assert c["ph"] == "C" and c["args"]["value"] == 0.5
    assert b["ph"] == "b" and b["id"] == "7" and b["cat"] == "req"
    assert e["ph"] == "e"
    stats = validate_trace(to_trace_events(t.events()))
    assert stats["pairs"] == 1


# ------------------------------------------------------------------ #
# file round trip
# ------------------------------------------------------------------ #
def test_write_load_roundtrip(tmp_path):
    t = tracer()
    with t.span("a", step=1):
        pass
    path = tmp_path / "trace.json"
    trace = t.export(str(path))
    assert validate_trace(trace)["spans"] == 1
    loaded = load_trace(str(path))
    assert validate_trace(loaded)["spans"] == 1
    # Perfetto-loadable object form
    obj = json.loads(path.read_text())
    assert isinstance(obj["traceEvents"], list)


# ------------------------------------------------------------------ #
# validator rejects each malformation class
# ------------------------------------------------------------------ #
def _x(name="s", ts=0.0, dur=1.0, pid=0, tid=0, **kw):
    return {"ph": "X", "name": name, "ts": ts, "dur": dur, "pid": pid,
            "tid": tid, **kw}


@pytest.mark.parametrize("bad, msg", [
    ({"name": "no-ph"}, "missing 'ph'"),
    ({"ph": "X", "name": "x", "dur": 1, "pid": 0, "tid": 0},
     "missing 'ts'"),
    (_x(dur=-5.0), "negative dur"),
    ({"ph": "X", "name": "x", "ts": 0.0, "pid": 0, "tid": 0},
     "missing 'dur'"),
    ({"ph": "b", "name": "r", "ts": 0.0}, "missing 'id'"),
    ({"ph": "E", "name": "x", "ts": 0.0, "pid": 0, "tid": 0},
     "no open B"),
])
def test_validator_rejects_malformed_events(bad, msg):
    with pytest.raises(ValueError, match=msg):
        validate_trace([bad])


def test_validator_rejects_nonmonotone_ts_per_tid():
    with pytest.raises(ValueError, match="not monotone"):
        validate_trace([_x(ts=10.0), _x(ts=5.0)])
    # different tids keep independent clocks
    validate_trace([_x(ts=10.0, tid=0), _x(ts=5.0, tid=1)])


def test_validator_rejects_unpaired_async_and_dangling_B():
    with pytest.raises(ValueError, match="unclosed async"):
        validate_trace([{"ph": "b", "name": "r", "ts": 0.0, "cat": "req",
                         "id": "1", "pid": 0, "tid": 0}])
    with pytest.raises(ValueError, match="unclosed B"):
        validate_trace([{"ph": "B", "name": "x", "ts": 0.0, "pid": 0,
                         "tid": 0}])


def test_validator_rejects_bad_toplevel():
    with pytest.raises(ValueError, match="traceEvents"):
        validate_trace({"events": []})
    with pytest.raises(ValueError, match="dict or list"):
        validate_trace("nope")


# ------------------------------------------------------------------ #
# dropped-event accounting (ring-buffer overflow honesty)
# ------------------------------------------------------------------ #
def test_dropped_counter_counts_ring_displacements():
    t = tracer(capacity=4)
    for i in range(4):
        t.instant(f"e{i}")
    assert t.dropped == 0 and t.buffered == 4
    for i in range(3):
        t.instant(f"late{i}")
    assert t.dropped == 3             # 3 oldest events displaced
    assert t.buffered == 4
    t.clear()
    assert t.dropped == 0 and t.buffered == 0


def test_export_records_drop_metadata_and_assembler_warns(tmp_path):
    from hcache_deepspeed_tpu.telemetry.assemble import (
        merge_streams, stream_drop_count)
    t = tracer(capacity=2)
    for i in range(5):
        t.instant(f"e{i}")
    path = tmp_path / "trace.json"
    t.export(str(path))
    events = load_trace(str(path))
    assert stream_drop_count(events) == 3
    merged, warnings = merge_streams({"lossy": events})
    assert warnings and "dropped 3 events" in warnings[0]
    # a clean stream merges silently
    clean = tracer()
    clean.instant("ok")
    _, warnings = merge_streams({"clean": clean.events()})
    assert warnings == []
