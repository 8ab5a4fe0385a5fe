"""``reducers/idle_cut.py``: every idle gap of the device cut into wake,
host turn (at span edges) and launch, on hand-made intervals for each
rule, on the recorded trace through the seven metric files, and the
property the midpoint rule of ``trace/xplane.py`` lacks: a gap moved by
a tenth of a millisecond moves no bin by more."""

import os

import pytest

from benchmarks import contract, layer_metrics
from benchmarks.reducers import idle_cut
from benchmarks.tools import idle_table
from benchmarks.trace import xplane
from benchmarks.trace.xplane import Op, Span, Trace

TRACE = os.path.join(os.path.dirname(__file__), "data",
                     "serve_slice.xplane.pb")
MS = 1e-3
SEVEN = ("host_turn_p50_ms", "idle_cut.engine", "idle_cut.fetch",
         "idle_cut.launch", "idle_cut.loop", "idle_cut.sched",
         "idle_cut.wake")


def make(ops, host, modules=()):
    """A one-chip trace from intervals in milliseconds."""
    trace = Trace()
    trace.chips[0] = [Op("x", "x", a * MS, b * MS) for a, b in ops]
    trace.modules[0] = [Span("jit_program", a * MS, b * MS)
                        for a, b in modules]
    trace.host = sorted((Span(n, a * MS, b * MS) for n, a, b in host),
                        key=lambda s: s.start)
    edges = [t for a, b in ops for t in (a, b)] + \
        [t for _, a, b in host for t in (a, b)]
    trace.t_min, trace.t_max = min(edges) * MS, max(edges) * MS
    return trace


# two programs with the gap [10, 14] between them, and the loop's spans
# of one step cycle around it
PROGRAMS = [(0, 10), (14, 20)]
CYCLE = [("sched.step", 4, 12), ("serve.device_wait", 5, 10.3),
         ("serve.fetch", 10.3, 10.9), ("sched.sample", 11, 11.5),
         ("sched.step", 12.1, 20), ("sched.batch_build", 12.2, 12.6),
         ("serve.decode_dispatch", 12.8, 13.4)]


def without(name):
    return [row for row in CYCLE if row[0] != name]


CASES = {
    # wake 10 -> 10.3, the turn 10.3 -> 13.4 cut at every edge, launch
    "the_loop_as_it_is": (
        PROGRAMS, CYCLE, [(0, 10), (13.9, 20)],
        dict(wake=0.3, launch=0.6, ends_in="serve.decode_dispatch",
             pieces={"serve.fetch": 0.6, "sched.step": 0.9,
                     "sched.sample": 0.5, "_no_span_": 0.1,
                     "sched.batch_build": 0.4,
                     "serve.decode_dispatch": 0.6})),
    # no wait open at the gap's start: no wake, the piece before the
    # fetch is the parent's
    "no_device_wait_open": (
        PROGRAMS, without("serve.device_wait"), [(0, 10), (13.9, 20)],
        dict(wake=0.0, launch=0.6, ends_in="serve.decode_dispatch",
             pieces={"serve.fetch": 0.6, "sched.step": 1.2,
                     "sched.sample": 0.5, "_no_span_": 0.1,
                     "sched.batch_build": 0.4,
                     "serve.decode_dispatch": 0.6})),
    # no enqueue span ends in the gap (the one of the trace is later):
    # no launch, the turn runs to the gap's end
    "no_enqueue_inside": (
        PROGRAMS, without("serve.decode_dispatch") +
        [("serve.decode_dispatch", 30, 31)], [],
        dict(wake=0.3, launch=0.0, ends_in=None,
             pieces={"serve.fetch": 0.6, "sched.step": 2.1,
                     "sched.sample": 0.5, "_no_span_": 0.1,
                     "sched.batch_build": 0.4})),
    # two enqueue spans end in the gap and no program event says which
    # issued what: the later one ends the turn
    "two_enqueues_no_programs": (
        PROGRAMS, [("serve.device_wait", 5, 10.5),
                   ("serve.prefill_dispatch", 11, 11.5),
                   ("serve.decode_dispatch", 12, 13)], [],
        dict(wake=0.5, launch=1.0, ends_in="serve.decode_dispatch",
             pieces={"_no_span_": 1.0, "serve.prefill_dispatch": 0.5,
                     "serve.decode_dispatch": 1.0})),
    # two enqueue spans end in the gap, the programs say the first
    # issued the program after it: the second's time is launch
    "two_enqueues_paired": (
        PROGRAMS + [(21, 25)],
        [("serve.device_wait", 5, 10.5),
         ("serve.prefill_dispatch", 11, 11.5),
         ("serve.decode_dispatch", 12, 13)],
        [(13.9, 20), (21, 25)],
        dict(wake=0.5, launch=2.5, ends_in="serve.prefill_dispatch",
             pieces={"_no_span_": 0.5, "serve.prefill_dispatch": 0.5})),
    # both programs were enqueued before the first began (restore
    # replays, a pipelined loop): the gap between them is launch whole
    "enqueued_before_the_gap": (
        [(4, 10), (10.5, 16)],
        [("restore.replay", 1, 2), ("restore.replay", 2.5, 3.5),
         ("serve.device_wait", 3.6, 16.2)],
        [(4, 10), (10.5, 16)],
        dict(gap=(10, 10.5), wake=0.0, launch=0.5,
             ends_in="restore.replay", pieces={})),
    # a gap inside one program is the device's own
    "inside_a_program": (
        PROGRAMS, CYCLE, [(0, 20)],
        dict(wake=0.0, launch=4.0, ends_in=None, pieces={})),
    # a program that no enqueue span issued (the first enqueue span
    # begins after it): the gap before it has no launch
    "no_issuer": (
        PROGRAMS, [("serve.fetch", 9, 15),
                   ("serve.decode_dispatch", 30, 31)],
        [(0, 10), (14, 20)],
        dict(wake=0.0, launch=0.0, ends_in=None,
             pieces={"serve.fetch": 4.0})),
    # a gap wholly inside one leaf
    "inside_one_leaf": (
        PROGRAMS, [("serve.fetch", 9, 15),
                   ("serve.decode_dispatch", 30, 31)], [],
        dict(wake=0.0, launch=0.0, ends_in=None,
             pieces={"serve.fetch": 4.0})),
    # leaf edges exactly on the gap's edges: the leaf that ends at the
    # gap's start gets nothing, the enqueue span that ends at its end
    # leaves no launch
    "edges_on_the_gap_edges": (
        PROGRAMS, [("sched.sample", 8, 10), ("sched.metrics", 10, 12),
                   ("serve.decode_dispatch", 12, 14)], [],
        dict(wake=0.0, launch=0.0, ends_in="serve.decode_dispatch",
             pieces={"sched.metrics": 2.0,
                     "serve.decode_dispatch": 2.0})),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_each_rule_on_hand_made_intervals(case):
    ops, host, modules, want = CASES[case]
    trace = make(ops, host, modules)
    whole = idle_cut.cut(trace)
    g0, g1 = want.get("gap", (10, 14))
    gap = next(g for g in whole.gaps
               if g.start == pytest.approx(g0 * MS))
    assert gap.end == pytest.approx(g1 * MS)
    assert gap.wake == pytest.approx(want["wake"] * MS, abs=1e-12)
    assert gap.launch == pytest.approx(want["launch"] * MS, abs=1e-12)
    assert gap.ends_in == want["ends_in"]
    assert {k: round(v / MS, 9) for k, v in gap.pieces.items()} == \
        pytest.approx(want["pieces"])
    assert gap.wake + gap.turn + gap.launch == pytest.approx(gap.end -
                                                             gap.start)
    assert sum(gap.pieces.values()) == pytest.approx(gap.turn)
    # the bins, each second once, are the device's idle time
    busy = xplane.union((op.start, op.end) for op in trace.chips[0])
    idle = sum(b - a for a, b in xplane.gaps(busy, trace.t_min,
                                             trace.t_max))
    assert sum(whole.bins().values()) == pytest.approx(idle, abs=1e-12)


def test_bins_of_the_recorded_trace_sum_to_its_idle_exactly():
    trace = xplane.load(TRACE)
    reduction = xplane.reduce(trace)
    whole = idle_cut.cut(trace)
    assert len(whole.gaps) == len(reduction.gap_lengths) == 6
    assert sum(whole.bins().values()) == pytest.approx(
        sum(reduction.gap_seconds.values()), abs=1e-12)
    assert whole.bins()[idle_cut.SHORT] == pytest.approx(
        reduction.gap_seconds["_gaps_under_20_us_"], abs=1e-15)
    assert whole.unissued == 0          # five programs, five enqueues
    assert [g.ends_in for g in whole.gaps] == [
        "serve.prefill_dispatch", "serve.decode_dispatch",
        "serve.decode_dispatch", "serve.decode_dispatch",
        "serve.prefill_dispatch", None]


def test_recorded_trace_through_the_seven_metric_files():
    specs = {k: v for k, v in contract.load_metric_specs().items()
             if v["reads"] == "idle_cut"}
    assert tuple(sorted(specs)) == SEVEN
    bench = contract.load_benchmark()
    declared = [m for m in bench["per_layer"] if m["name"] in specs]
    assert [m["workloads"] for m in declared] == \
        [["m7b-serve-chat-steady"]] * 7
    reduction = xplane.reduce_file(TRACE)
    for cell in ("m7b-serve-chat-steady", "olmoh-serve-long-prompt"):
        got = layer_metrics.compute({"name": cell}, "serve",
                                    {"trace": reduction}, specs)
        assert tuple(sorted(got)) == SEVEN
        shares = [got[k]["value"] for k in SEVEN if k.startswith("idle_")]
        short = 100 * reduction.gap_seconds["_gaps_under_20_us_"] / \
            reduction.window_s
        assert sum(shares) + short == pytest.approx(
            100 * reduction.idle_share, abs=1e-9)
        # a trace from before the leaf spans: no device_wait, no fetch,
        # the programs began inside their enqueue spans
        assert got["idle_cut.wake"] == {"value": 0.0, "unit": "%"}
        assert got["idle_cut.fetch"]["value"] == 0.0
        assert got["idle_cut.launch"]["value"] == 0.0
        assert got["idle_cut.engine"]["value"] == pytest.approx(10.632,
                                                                abs=1e-3)
        assert got["idle_cut.sched"]["value"] == pytest.approx(11.522,
                                                               abs=1e-3)
        assert got["idle_cut.loop"]["value"] == pytest.approx(13.357,
                                                              abs=1e-3)
        assert got["host_turn_p50_ms"] == {
            "value": pytest.approx(29.901, abs=1e-3), "unit": "ms"}


def test_an_empty_bin_reads_zero_and_no_cut_reads_nothing():
    trace = make(PROGRAMS, CYCLE)
    evidence = {"trace": xplane.reduce(trace)}
    assert idle_cut.read({"bin": r"^restore\."}, evidence) == 0.0
    assert idle_cut.read({"bin": "wake"}, evidence) == pytest.approx(
        100 * 0.3 / 20)
    assert idle_cut.read({"turn": True, "how": "p50"}, evidence) == \
        pytest.approx(3.1 * MS)
    # a run with no trace (the CPU cases of test_runners_tiny.py), and
    # a trace in which the program opened no enqueue span
    assert idle_cut.read({"bin": "wake"}, {"trace": None}) is None
    assert idle_cut.read({"bin": "wake"}, {}) is None
    bare = {"trace": xplane.reduce(make(PROGRAMS, without(
        "serve.decode_dispatch")))}
    assert idle_cut.read({"bin": "wake"}, bare) is None
    assert idle_cut.read({"turn": True, "how": "p50"}, bare) is None


@pytest.mark.parametrize("shift", [-0.05, 0.05])
def test_a_gap_moved_by_a_tenth_of_a_millisecond_moves_no_bin_by_more(
        shift):
    """The gap's midpoint lies on the edge between two leaves. Moved by
    0.05 ms either way the midpoint rule hands all 4 ms to one leaf or
    the other; the edge cut moves each bin by the 0.05 ms."""
    host = [("sched.sample", 9, 12), ("sched.metrics", 12, 13),
            ("serve.decode_dispatch", 13, 15)]

    def at(d):
        return make([(0, 10 + d), (14 + d, 20)], host)

    old = [xplane.reduce(at(d)).gap_seconds for d in (-shift, shift)]
    flipped = [max(g, key=g.get) for g in old]
    assert sorted(flipped) == ["sched.metrics", "sched.sample"]
    assert abs(old[0].get("sched.sample", 0.0) -
               old[1].get("sched.sample", 0.0)) == pytest.approx(4 * MS)
    new = [idle_cut.cut(at(d)).bins() for d in (-shift, shift)]
    for name in set(new[0]) | set(new[1]):
        assert abs(new[0].get(name, 0.0) - new[1].get(name, 0.0)) <= \
            0.1 * MS + 1e-12, name
    assert new[0]["sched.metrics"] == new[1]["sched.metrics"] == \
        pytest.approx(1 * MS)


def shifted(ops, host, modules, lead):
    """The same run as the profiler stamps it when the device's clock
    is ``lead`` ms ahead of the host's."""
    return make([(a - lead, b - lead) for a, b in ops], host,
                [(a - lead, b - lead) for a, b in modules])


def test_gaps_are_cut_on_the_hosts_clock():
    """Three programs, each begun the moment its enqueue span closed
    and waited for until 0.4 ms after its end. Stamped 1.3 ms early,
    the device's lead is found again from the spans and the cut is the
    one of the run stamped truly."""
    ops = [(0, 10), (14, 20), (24, 30)]
    host = [("serve.decode_dispatch", -1, 0),
            ("serve.device_wait", 0.1, 10.4), ("serve.fetch", 10.4, 11),
            ("sched.metrics", 11, 13), ("serve.decode_dispatch", 13, 14),
            ("serve.device_wait", 14.1, 20.4), ("serve.fetch", 20.4, 21),
            ("sched.metrics", 21, 23), ("serve.decode_dispatch", 23, 24),
            ("serve.device_wait", 24.1, 30.4)]
    true = idle_cut.cut(make(ops, host, ops))
    assert true.lead_s == 0.0
    early = idle_cut.cut(shifted(ops, host, ops, 1.3))
    assert early.lead_s == pytest.approx(1.3 * MS)
    between = [[g for g in whole.gaps if 5 * MS < g.start < 25 * MS]
               for whole in (true, early)]
    assert [len(gaps) for gaps in between] == [2, 2]
    for a, b in zip(*between):
        assert (b.start, b.end, b.w, b.e) == pytest.approx(
            (a.start, a.end, a.w, a.e))
        assert b.pieces == pytest.approx(a.pieces)
        assert b.wake == pytest.approx(0.4 * MS)
        assert b.launch == pytest.approx(0.0, abs=1e-12)
    # cut as stamped, the lead would sit in wake and leave the enqueue
    # span and launch short of it
    assert b.end - b.start == pytest.approx(4 * MS)
    late = idle_cut.cut(shifted(ops, host, ops, -2.0))
    assert late.lead_s == 0.0            # never a shift backwards
    # enqueue spans that close 0.5 ms after their programs began would
    # put the lead at 0.5 ms: the waits, back 0.4 ms after the programs
    # ended, hold it to that
    held = idle_cut.cut(make(ops, [
        row if row[0] != "serve.decode_dispatch" else
        (row[0], row[1], row[2] + 0.5) for row in host], ops))
    assert held.lead_s == pytest.approx(0.4 * MS)
    bare = [row for row in host if row[0] != "serve.device_wait"]
    assert idle_cut.cut(shifted(ops, bare, ops, 1.3)).lead_s == 0.0


def test_enqueue_spans_are_the_programs_set():
    from hcache_deepspeed_tpu.telemetry.metrics import ENQUEUE_SPANS
    assert len(ENQUEUE_SPANS) == 5
    assert all(idle_cut.ENQUEUE.match(name) for name in ENQUEUE_SPANS)
    assert not idle_cut.ENQUEUE.match("serve.batch_build")
    assert idle_cut.WAIT == "serve.device_wait"


def test_leaf_timeline_hands_each_moment_to_the_innermost_span():
    spans = [Span("a", 0, 10), Span("b", 2, 4), Span("c", 3, 3.5),
             Span("b", 6, 10), Span("d", 12, 13), Span("e", 13, 13)]
    assert idle_cut.leaf_timeline(spans) == [
        (0, 2, "a"), (2, 3, "b"), (3, 3.5, "c"), (3.5, 4, "b"),
        (4, 6, "a"), (6, 10, "b"), (12, 13, "d")]


def test_idle_table_prints_the_whole_cut_of_the_recorded_trace():
    text = idle_table.table(TRACE)
    assert "5 programs, 5 enqueue spans; 0 programs that no enqueue" in text
    assert "*sched.step" in text and " _no_span_" in text
    assert "gaps before a decode_dispatch program: 3" in text
    assert "= 35.511; device idle share 35.511" in text
