"""Unit tests for the HLO async-overlap auditor over canned HLO text
(no compilation — pure parser/graph logic, CPU-deterministic)."""

from hcache_deepspeed_tpu.profiling.hlo_audit import (audit_hlo_text,
                                                      parse_hlo_computations)

# A scheduled (TPU-style) module: a native all-gather-start/done pair
# with one dot and one fusion inside the window, plus a sync
# reduce-scatter whose only compute is its own ancestor.
NATIVE = """
HloModule sched, is_scheduled=true

ENTRY %main (p: f32[8,64]) -> (f32[64,64], f32[8,8]) {
  %p = f32[8,64] parameter(0)
  %ags = (f32[8,64], f32[64,64]) all-gather-start(f32[8,64] %p), dimensions={0}
  %d1 = f32[8,8] dot(f32[8,64] %p, f32[8,64] %p), lhs_contracting_dims={1}, rhs_contracting_dims={1}
  %f1 = f32[8,8] fusion(f32[8,8] %d1), kind=kLoop, calls=%fused_computation
  %agd = f32[64,64] all-gather-done((f32[8,64], f32[64,64]) %ags)
  %rs = f32[1,8] reduce-scatter(f32[8,8] %f1), dimensions={0}
  ROOT %out = (f32[64,64], f32[8,8]) tuple(%agd, %f1)
}
"""

# A while-body with a PREFETCHED gather: the gather feeds only the
# carry (no dot consumes it in-body), so both dots are legally free.
PREFETCH_BODY = """
HloModule loop

%body (arg: (f32[8,64], f32[64,64], f32[8,8])) -> (f32[8,64], f32[64,64], f32[8,8]) {
  %arg = (f32[8,64], f32[64,64], f32[8,8]) parameter(0)
  %shard = f32[8,64] get-tuple-element(%arg), index=0
  %cur = f32[64,64] get-tuple-element(%arg), index=1
  %x = f32[8,8] get-tuple-element(%arg), index=2
  %nxt = f32[64,64] all-gather(f32[8,64] %shard), dimensions={0}
  %d1 = f32[8,64] dot(f32[8,8] %x, f32[8,64] %shard), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %d2 = f32[8,8] dot(f32[8,64] %d1, f32[8,64] %d1), lhs_contracting_dims={1}, rhs_contracting_dims={1}
  ROOT %out = (f32[8,64], f32[64,64], f32[8,8]) tuple(%shard, %nxt, %d2)
}

ENTRY %main (p: (f32[8,64], f32[64,64], f32[8,8])) -> (f32[8,64], f32[64,64], f32[8,8]) {
  %p = (f32[8,64], f32[64,64], f32[8,8]) parameter(0)
  ROOT %w = (f32[8,64], f32[64,64], f32[8,8]) while(%p), condition=%cond, body=%body
}
"""

# A sequential body: the gather feeds the dot directly — every compute
# op is a descendant, nothing can hide the wire time.
SEQUENTIAL_BODY = """
HloModule seq

%body (arg: (f32[8,64], f32[8,8])) -> (f32[8,64], f32[8,8]) {
  %arg = (f32[8,64], f32[8,8]) parameter(0)
  %shard = f32[8,64] get-tuple-element(%arg), index=0
  %x = f32[8,8] get-tuple-element(%arg), index=1
  %full = f32[64,64] all-gather(f32[8,64] %shard), dimensions={0}
  %d1 = f32[8,64] dot(f32[8,8] %x, f32[64,64] %full), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %d2 = f32[8,8] dot(f32[8,64] %d1, f32[8,64] %d1), lhs_contracting_dims={1}, rhs_contracting_dims={1}
  ROOT %out = (f32[8,64], f32[8,8]) tuple(%shard, %d2)
}
"""

# An elementwise fusion next to the gather must NOT count as derived
# overlap evidence (only dots/convolutions do), but DOES count inside
# a native scheduled window.
FUSION_ONLY = """
HloModule fus

ENTRY %main (p: f32[8,64]) -> (f32[64,64], f32[8,64]) {
  %p = f32[8,64] parameter(0)
  %full = f32[64,64] all-gather(f32[8,64] %p), dimensions={0}
  %f1 = f32[8,64] fusion(f32[8,64] %p), kind=kLoop, calls=%fc
  ROOT %out = (f32[64,64], f32[8,64]) tuple(%full, %f1)
}
"""


class TestParser:

    def test_parses_nested_tuple_param_computations(self):
        """Computation headers with tuple-typed (nested-paren) parameter
        lists must parse — while bodies were invisible to an earlier
        regex and the audit silently skipped every loop."""
        comps = parse_hlo_computations(PREFETCH_BODY)
        names = [c.name for c in comps]
        assert any("body" in n for n in names), names
        body = next(c for c in comps if "body" in c.name)
        assert any(i.opcode == "all-gather" for i in body.instrs)
        assert sum(1 for i in body.instrs if i.opcode == "dot") == 2

    def test_entry_flag_and_root(self):
        comps = parse_hlo_computations(NATIVE)
        entry = [c for c in comps if c.is_entry]
        assert len(entry) == 1
        assert any(i.is_root for i in entry[0].instrs)


class TestNativePairs:

    def test_native_pair_scored_by_window_contents(self):
        rep = audit_hlo_text(NATIVE)
        assert len(rep.native_pairs) == 1
        pair = rep.native_pairs[0]
        assert pair.kind == "all-gather"
        assert pair.provenance == "native"
        # one dot + one fusion scheduled inside start..done
        assert pair.interleaved == 2

    def test_pairs_prefers_native_tier(self):
        rep = audit_hlo_text(NATIVE)
        pairs = rep.pairs("all-gather")
        assert pairs and all(p.provenance == "native" for p in pairs)


class TestDerivedPairs:

    def test_prefetched_gather_is_overlappable(self):
        rep = audit_hlo_text(PREFETCH_BODY)
        pairs = rep.pairs("all-gather")
        assert len(pairs) == 1
        assert pairs[0].provenance == "derived"
        assert pairs[0].interleaved == 2  # both dots are free
        assert rep.overlap_ratio("all-gather") == 1.0

    def test_sequential_gather_is_not(self):
        rep = audit_hlo_text(SEQUENTIAL_BODY)
        assert rep.pairs("all-gather") == []
        assert len(rep.sequential_collectives) == 1
        assert rep.overlap_ratio("all-gather") == 0.0

    def test_fusions_do_not_count_as_derived_overlap(self):
        """A sibling elementwise fusion is legally free next to almost
        any collective; counting it would make even fully serialized
        programs audit as overlappable."""
        rep = audit_hlo_text(FUSION_ONLY)
        assert rep.pairs("all-gather") == []
        assert len(rep.sequential_collectives) == 1

    def test_reduce_scatter_kind_filter(self):
        rep = audit_hlo_text(NATIVE)
        # the reduce-scatter's only compute ops are its ancestors
        assert rep.pairs("reduce-scatter") == []
        assert rep.overlap_ratio("reduce-scatter") == 0.0


class TestReport:

    def test_row_is_json_safe(self):
        import json
        row = audit_hlo_text(NATIVE).to_row()
        json.dumps(row)
        assert row["native_async_pairs"] == 1
        assert "collective_counts" in row

    def test_empty_and_garbage_text(self):
        assert audit_hlo_text("").pairs() == []
        rep = audit_hlo_text("not hlo at all\n{}\nrandom { tokens }")
        assert rep.pairs() == []
        assert rep.overlap_ratio() == 1.0  # nothing on the critical path
