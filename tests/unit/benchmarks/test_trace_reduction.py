"""The reduction from a profiler trace to busy, idle, op time and gaps:
on hand-made intervals, and on a small recorded trace.

``data/serve_slice.xplane.pb`` is the first 0.7 s of the device trace
of ``m7b-serve-chat-steady`` on a TPU v5 lite (PR 23's first chip run):
chip 0's ``XLA Ops`` and ``XLA Modules`` lines and the program's own
host spans, cut out of the 5.4 MB original with the xplane protobuf and
nothing else changed. Five ``jit__forward_chunk`` programs ran in it.
"""

import os

import pytest

from benchmarks.reducers import (exposed_share, roofline, trace_count,
                                 trace_gap, trace_idle, trace_share)
from benchmarks.trace import xplane
from benchmarks.trace.xplane import Op, Span, Trace

SLICE = os.path.join(os.path.dirname(__file__), "data",
                     "serve_slice.xplane.pb")
POOL = (r"^(copy|constant|bitcast|dynamic-slice|dynamic-update-slice)"
        r"[\w\-]*_[a-z]+\d+_(\d+_)*163840_128_$")
PAGED = r'custom_call_target="tpu_custom_call".*bf16\[8,2560,64,128\]'


def op(text, start, end):
    return Op(text, xplane.label_of(text), start, end)


@pytest.fixture(scope="module")
def recorded():
    return xplane.reduce_file(SLICE)


def hand_made():
    """One chip: a program of 1.0-3.0 (a while of 1.0-2.6 holding a copy
    1.2-2.0 and a kernel 2.0-2.5, then a fusion 2.6-3.0), idle to 4.0, a
    second program 4.0-4.5; host spans around the gaps."""
    ops = [op("%while.1 = (s32[]) while(x)", 1.0, 2.6),
           op("%copy.7 = bf16[8,64,128]{2,1,0} copy(p)", 1.2, 2.0),
           op('%closed_call.3 = bf16[4,8] custom-call(q, bf16[2,9,4,8] k),'
              ' custom_call_target="tpu_custom_call"', 2.0, 2.5),
           op("%fusion.2 = bf16[16,32]{1,0} fusion(y)", 2.6, 3.0),
           op("%all-gather.5 = bf16[16,32]{1,0} all-gather(z)", 4.0, 4.5)]
    trace = Trace(chips={0: ops},
                  modules={0: [Span("jit_step(1)", 1.0, 3.0),
                               Span("jit_step(1)", 4.0, 4.5)]},
                  host=[Span("sched.step", 0.0, 3.5),
                        Span("hds.serve.put", 0.5, 3.2),
                        Span("sched.step", 3.6, 5.0)],
                  t_min=0.0, t_max=5.0)
    xplane.set_own_times(ops)
    return trace


def test_union_covered_and_gaps():
    merged = xplane.union([(3, 4), (0, 1), (0.5, 2), (4, 5)])
    assert merged == [(0, 2), (3, 5)]
    assert xplane.covered([(3, 4), (0, 1), (0.5, 2), (4, 5)]) == 4
    assert xplane.gaps(merged, -1, 6) == [(-1, 0), (2, 3), (5, 6)]
    assert xplane.gaps([], 0, 1) == [(0, 1)]


def test_labels_are_kind_and_result_shape():
    assert xplane.label_of(
        "%copy_bitcast_fusion.5 = bf16[8,163840,128]{2,0,1:T(8,128)} "
        "fusion(x)") == "copy_bitcast_fusion_bf16_8_163840_128_"
    assert xplane.label_of("%copy.77.remat = bf16[8,4]{1,0} copy(x)") == \
        "copy_bf16_8_4_"
    assert xplane.label_of("%while.2 = (s32[], bf16[4]) while(t)") == \
        "while_s32__"
    assert xplane.label_of("%all-gather-start.3 = (bf16[4,2], bf16[16,2]) "
                           "all-gather-start(x)") == \
        "all-gather-start_bf16_4_2_"


def test_own_time_partitions_busy_time():
    trace = hand_made()
    own = {o.label: o.own for o in trace.chips[0]}
    assert own["while_s32__"] == pytest.approx(0.2 + 0.1)   # 1.0-1.2, 2.5-2.6
    assert own["copy_bf16_8_64_128_"] == pytest.approx(0.8)
    assert own["closed_call_bf16_4_8_"] == pytest.approx(0.5)
    assert sum(own.values()) == pytest.approx(2.5)


def test_hand_made_busy_idle_and_gap_attribution():
    r = xplane.reduce(hand_made())
    assert r.window_s == 5.0 and r.busy_s == pytest.approx(2.5)
    assert r.idle_share == pytest.approx(0.5)
    # 0-1 under hds.serve.put (innermost at 0.5), 3-4 under sched.step
    # (the put closed at 3.2; midpoint 3.5 is the first step's end),
    # 4.5-5 under the second step
    assert r.gap_seconds == pytest.approx(
        {"hds.serve.put": 1.0, "sched.step": 1.5})
    assert sorted(r.gap_lengths) == pytest.approx([0.5, 1.0, 1.0])
    assert r.breakdown()["device_ops"][0] == \
        ["copy_bf16_8_64_128_", pytest.approx(0.8)]
    assert xplane.innermost(hand_made().host, 0.2) == "sched.step"
    assert xplane.innermost(hand_made().host, 3.55) is None


def test_hand_made_reducers():
    r = xplane.reduce(hand_made())
    ev = {"trace": r, "device_kind": "TPU v5 lite",
          "placeholders": {"pool": "8_64_128_", "kv": "2,9,4,8"},
          "params": ["_99_99_"]}
    assert trace_idle.read({}, ev) == pytest.approx(50.0)
    assert trace_share.read({"pattern": "^copy_bf16_{pool}$"}, ev) == \
        pytest.approx(100 * 0.8 / 2.5)
    assert trace_share.read({"pattern": "^copy_{missing}$"}, ev) is None
    assert trace_gap.read({"how": "p50"}, ev) == pytest.approx(1.0)
    # the all-gather runs alone 4.0-4.5: exposed for 0.5 of 5 seconds
    assert exposed_share.read({}, ev) == pytest.approx(10.0)
    count = {"pattern": r" (all-gather|all-to-all)(-done)?\(",
             "program": "^jit_step", "except": "params"}
    assert trace_count.read(count, ev) == pytest.approx(0.5)
    assert trace_count.read(count, dict(ev, params=["16_32_"])) == 0.0
    assert trace_count.read(count, dict(ev, params=["6_32_"])) == 0.5
    call = dict(context_lens=[100], q_lens=[1], n_head=4, n_kv_head=2,
                head_dim=8, itemsize=2)
    spec = {"pattern": r"tpu_custom_call", "on": "text",
            "counts": "paged_attention_counts", "calls": "calls"}
    got = roofline.read(spec, dict(ev, calls=[call]))
    least = (2 * 100 * 2 * 8 * 2 + 2 * 4 * 8 * 2) / 819e9
    assert got == pytest.approx(100 * least / 0.5)
    assert roofline.read(spec, ev) is None          # no calls given
    each = dict(spec, each=["call", "call"])
    del each["calls"]
    assert roofline.read(each, dict(ev, call=call)) == \
        pytest.approx(100 * 2 * least / 0.5)
    assert roofline.read(dict(each, count_pattern="no_such_op"),
                         dict(ev, call=call)) == 0.0


def test_a_collective_beside_compute_is_not_exposed():
    ops = [op("%fusion.1 = f32[4]{0} fusion(x)", 0.0, 1.0),
           op("%all-reduce.1 = f32[4]{0} all-reduce(x)", 0.5, 1.5)]
    assert exposed_share.exposed_seconds(ops) == pytest.approx(0.5)


def test_a_trace_without_device_operations_is_refused():
    with pytest.raises(ValueError):
        xplane.reduce(Trace(host=[Span("sched.step", 0, 1)], t_max=1.0))


def test_recorded_slice_loads(recorded):
    trace = recorded.trace
    assert set(trace.chips) == {0} and len(trace.chips[0]) == 3199
    assert len(trace.modules[0]) == 5
    assert all(m.name.startswith("jit__forward_chunk(")
               for m in trace.modules[0])
    assert [s.name for s in trace.host].count("sched.step") == 3
    assert {s.name for s in trace.host} == {
        "sched.step", "sched.decode_dispatch", "hds.serve.put",
        "serve.decode_dispatch", "serve.prefill_dispatch"}


def test_recorded_slice_busy_idle_and_window(recorded):
    assert recorded.window_s == pytest.approx(0.720230807, abs=1e-9)
    assert recorded.busy_s == pytest.approx(0.464466339, abs=1e-9)
    assert recorded.idle_share == pytest.approx(0.35511459, abs=1e-7)
    trace = recorded.trace
    # the operations run inside the five programs and fill them
    programs = xplane.covered((m.start, m.end) for m in trace.modules[0])
    assert programs == pytest.approx(0.464469518, abs=1e-8)
    assert recorded.busy_s <= programs
    assert sum(o.own for o in trace.chips[0]) == \
        pytest.approx(recorded.busy_s, abs=1e-9)


def test_recorded_slice_op_times(recorded):
    top = recorded.breakdown()["device_ops"]
    assert [name for name, _ in top[:5]] == [
        "copy_bitcast_fusion_bf16_8_163840_128_",
        "constant_dynamic-slice_fusion_bf16_1_8_163840_128_",
        "copy_bf16_8_8_163840_128_", "copy_bf16_8_163840_128_",
        "copy_dynamic-update-slice_fusion_bf16_8_8_163840_128_"]
    assert top[0][1] == pytest.approx(0.081949692, abs=1e-8)
    # whole-pool copies: 0.407 of 0.464 busy seconds
    assert recorded.seconds_matching(POOL) == \
        pytest.approx(0.407179075, abs=1e-8)
    paged = recorded.matching(PAGED, on="text")
    assert len(paged) == 40                  # 5 programs x 8 layers
    assert recorded.seconds_matching(PAGED, on="text") == \
        pytest.approx(0.014935126, abs=1e-8)


def test_recorded_slice_gaps_by_host_span(recorded):
    assert recorded.gap_seconds == pytest.approx({
        "_gaps_under_20_us_": 1.68e-06, "_no_span_": 0.098921259,
        "hds.serve.put": 0.096894802, "sched.step": 0.059801601,
        "serve.prefill_dispatch": 0.000145126}, abs=1e-8)
    assert sum(recorded.gap_seconds.values()) == \
        pytest.approx(recorded.window_s - recorded.busy_s, abs=1e-9)
    assert sorted(recorded.gap_lengths)[-1] == \
        pytest.approx(0.098921, abs=1e-6)
