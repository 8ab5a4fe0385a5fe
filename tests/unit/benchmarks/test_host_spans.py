"""The reducers that read the program's host spans (``host_span``,
``idle_by_span``) and the kernels' names in the trace, on hand-made
intervals and on the recorded slice beside this file; and the metric
files that use them."""

import os

import pytest

from benchmarks import contract, layer_metrics
from benchmarks.reducers import host_span, idle_by_span, trace_share
from benchmarks.trace import xplane
from benchmarks.trace.xplane import Op, Span, Trace

SLICE = os.path.join(os.path.dirname(__file__), "data",
                     "serve_slice.xplane.pb")
SPECS = contract.load_metric_specs()
NEW = ["sched_self_p50_ms", "put_host_p50_ms", "fetch_p50_ms",
       "dispatches_per_step", "idle_share_sched", "idle_share_put",
       "restore_ship_p50_ms", "restore_replay_p50_ms",
       "kernel_share.paged_attention", "kernel_share.flash_attention"]


def hand_made():
    """Two scheduler steps on one chip. Step 1 (0-10): a put 2-8 that
    waits on the device 4-6 and fetches 6-7; the device runs 3-6. Step
    2 (10-14): a put 11-13 with two dispatches; the device runs 12-13.
    The loop yields 14-15 and a restore ships 15-16 under no step."""
    paged = ('%hds_paged_attention.3 = bf16[4,8] custom-call(q), '
             'custom_call_target="tpu_custom_call", frontend_attributes='
             '{kernel_metadata={"hds_kernel":"paged_attention"}}')
    ops = [Op(paged, xplane.label_of(paged), 3.0, 6.0),
           Op("%fusion.2 = bf16[16,32]{1,0} fusion(y)",
              "fusion_bf16_16_32_", 12.0, 13.0)]
    host = [Span("sched.step", 0.0, 10.0),
            Span("sched.passes", 0.0, 1.0),
            Span("sched.decode_dispatch", 1.5, 8.5),
            Span("hds.serve.put", 2.0, 8.0),
            Span("serve.decode_dispatch", 2.5, 3.0),
            Span("serve.device_wait", 4.0, 6.0),
            Span("serve.fetch", 6.0, 7.0),
            Span("sched.sample", 8.5, 10.0),
            Span("sched.step", 10.0, 14.0),
            Span("sched.decode_dispatch", 10.5, 13.5),
            Span("hds.serve.put", 11.0, 13.0),
            Span("serve.decode_dispatch", 11.2, 11.4),
            Span("serve.prefill_dispatch", 11.6, 11.8),
            Span("serve.fetch", 12.5, 13.0),
            Span("serve.loop.yield", 14.0, 15.0),
            Span("restore.ship", 15.0, 16.0)]
    trace = Trace(chips={0: ops}, host=sorted(host, key=lambda s: s.start),
                  t_min=0.0, t_max=16.0)
    xplane.set_own_times(ops)
    return {"trace": xplane.reduce(trace)}


def test_own_durations_subtract_what_the_inner_spans_cover():
    outer = [Span("a", 0.0, 10.0), Span("a", 20.0, 30.0)]
    inner = [Span("b", 1.0, 3.0), Span("b", 2.0, 4.0),     # overlap: 3
             Span("b", 9.0, 12.0),                         # clipped: 1
             Span("b", 15.0, 16.0)]                        # outside
    assert host_span.own_durations(outer, inner) == \
        pytest.approx([6.0, 10.0])
    assert host_span.own_durations(outer, []) == [10.0, 10.0]


def test_host_span_durations_own_time_and_count_ratio():
    ev = hand_made()
    assert host_span.read({"span": r"^serve\.fetch$", "how": "p50"},
                          ev) == pytest.approx(0.75)
    assert host_span.read({"span": r"^serve\.fetch$", "how": "max"},
                          ev) == pytest.approx(1.0)
    # steps of 10 and 4 with puts of 6 and 2 inside: own 4 and 2
    assert host_span.read({"span": r"^sched\.step$",
                           "less": r"^hds\.serve\.put$", "how": "p50"},
                          ev) == pytest.approx(3.0)
    # puts of 6 and 2, the first waits 2 on the device: own 4 and 2
    assert host_span.read({"span": r"^hds\.serve\.put$",
                           "less": r"^serve\.device_wait$",
                           "how": "sum"}, ev) == pytest.approx(6.0)
    # three programs enqueued in two scheduler dispatches
    assert host_span.read({"span": r"^serve\.(decode|prefill)_dispatch$",
                           "per": r"^sched\.decode_dispatch$"},
                          ev) == pytest.approx(1.5)


def test_a_span_the_program_does_not_have_gives_nothing():
    ev = hand_made()
    assert host_span.read({"span": r"^restore\.replay$", "how": "p50"},
                          ev) is None
    assert host_span.read({"span": r"^serve\.fetch$",
                           "per": r"^no\.such$"}, ev) is None
    assert idle_by_span.read({"span": r"^zero\."}, ev) is None
    assert host_span.read({"span": "x", "how": "p50"}, {}) is None
    assert idle_by_span.read({"span": "x"}, {}) is None


def test_idle_by_span_splits_the_idle_share_by_layer():
    ev = hand_made()
    r = ev["trace"]
    # idle: 0-3 (midpoint 1.5 in sched.decode_dispatch), 6-12
    # (midpoint 9 in sched.sample), 13-16 (midpoint 14.5 in the yield)
    assert r.gap_seconds == pytest.approx(
        {"sched.decode_dispatch": 3.0, "sched.sample": 6.0,
         "serve.loop.yield": 3.0})
    sched = idle_by_span.read(SPECS["idle_share_sched"], ev)
    put = idle_by_span.read(SPECS["idle_share_put"], ev)
    assert sched == pytest.approx(100 * 9.0 / 16.0)
    assert put is None              # serve.loop.* is not the engine's
    loop = idle_by_span.read({"span": r"^serve\.loop\."}, ev)
    assert sched + loop == pytest.approx(100 * r.idle_share)


def test_idle_share_put_takes_the_engines_spans_and_not_the_loops():
    ops = [Op("%fusion.1 = f32[4]{0} fusion(x)", "fusion_f32_4_", 0, 1),
           Op("%fusion.1 = f32[4]{0} fusion(x)", "fusion_f32_4_", 9, 10)]
    host = [Span("hds.serve.put", 1.0, 3.0), Span("serve.fetch", 3.0, 5.0),
            Span("serve.loop.yield", 5.0, 7.0), Span("sched.step", 7.0, 9.0)]
    for start, end in [(1, 3), (3, 5), (5, 7), (7, 9)]:
        ops.append(Op("%fusion.1 = f32[4]{0} fusion(x)", "fusion_f32_4_",
                      end - 0.001, end))
    trace = Trace(chips={0: sorted(ops, key=lambda o: o.start)},
                  host=host, t_min=0.0, t_max=10.0)
    xplane.set_own_times(trace.chips[0])
    ev = {"trace": xplane.reduce(trace)}
    assert idle_by_span.read(SPECS["idle_share_put"], ev) == \
        pytest.approx(100 * 2 * 1.999 / 10.0)
    assert idle_by_span.read(SPECS["idle_share_sched"], ev) == \
        pytest.approx(100 * 1.999 / 10.0)


def test_a_kernel_is_found_by_its_name_in_the_text():
    ev = hand_made()
    assert trace_share.read(SPECS["kernel_share.paged_attention"], ev) == \
        pytest.approx(100 * 3.0 / 4.0)
    assert trace_share.read(SPECS["kernel_share.flash_attention"],
                            ev) == 0.0
    flash = ('%hds_flash_attention_bwd_dq.1 = bf16[4] custom-call(q), '
             'frontend_attributes={kernel_metadata={\n"hds_kernel":'
             '"flash_attention_bwd_dq"\n}}')
    ev["trace"].trace.chips[0].append(Op(flash, "x", 0.0, 1.0, own=1.0))
    assert trace_share.read(SPECS["kernel_share.flash_attention"],
                            ev) == pytest.approx(100 * 1.0 / 4.0)


@pytest.mark.parametrize("name", NEW)
def test_new_metric_files_name_a_reducer_and_a_layer(name):
    spec = SPECS[name]
    assert callable(contract.load_kind("reducers", spec["reads"]).read)
    assert spec["layer"] in {"scheduler", "engine", "restore", "kernels"}
    assert spec["cells"] in ({"runner": "serve"}, {"runner": "train"})
    assert spec["source"] in ("program_span", "device_trace")


def test_recorded_slice_through_the_new_metrics():
    """The parent's program in the recorded slice has ``sched.step``
    and ``hds.serve.put`` and none of the leaf spans: the metrics that
    need only those read it, the others leave the line."""
    ev = {"trace": xplane.reduce_file(SLICE)}
    got = layer_metrics.compute({"name": "m7b-serve-chat-steady"}, "serve",
                                ev, {n: SPECS[n] for n in NEW})
    assert set(got) == {"sched_self_p50_ms", "put_host_p50_ms",
                        "dispatches_per_step", "idle_share_sched",
                        "idle_share_put", "kernel_share.paged_attention"}
    # three steps, each its duration less the put inside it
    assert got["sched_self_p50_ms"]["value"] == \
        pytest.approx(37.43016, abs=1e-4)
    assert got["put_host_p50_ms"]["value"] == \
        pytest.approx(101.624617, abs=1e-4)
    # five enqueues (3 decode, 2 prompt slices), three scheduler
    # dispatches
    assert got["dispatches_per_step"]["value"] == pytest.approx(5 / 3)
    r = ev["trace"]
    assert got["idle_share_sched"]["value"] == pytest.approx(
        100 * r.gap_seconds["sched.step"] / r.window_s)
    assert got["idle_share_put"]["value"] == pytest.approx(
        100 * (r.gap_seconds["hds.serve.put"] +
               r.gap_seconds["serve.prefill_dispatch"]) / r.window_s)
    assert got["kernel_share.paged_attention"]["value"] == 0.0
