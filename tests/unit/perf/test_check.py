"""Regression sentinel behavior: the gate must demonstrably fail on a
synthetically regressed artifact, pass on faithful/improved ones, and
never cross-compare different workloads."""

import json
import os

from hcache_deepspeed_tpu.perf import (MetricPoint, check_artifact,
                                       check_headline, check_points,
                                       load_index, regressions,
                                       self_check_rows, self_test)
from hcache_deepspeed_tpu.perf.registry import build_index

ROOT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", ".."))


def _committed_index():
    return load_index(root=ROOT)


def test_self_test_gate_trips():
    assert self_test()


def test_synthetically_regressed_serve_loop_fails(tmp_path):
    """Take the committed SERVE_LOOP summary, multiply TTFT p99 by
    10x and halve throughput, write it as a fresh artifact, and the
    gate must fail it."""
    index = _committed_index()
    src = os.path.join(ROOT, "SERVE_LOOP.jsonl")
    rows = [json.loads(line) for line in open(src)
            if line.strip().startswith("{")]
    summary = next(r for r in rows
                   if r.get("phase") == "serve-loop-summary")
    bad = dict(summary)
    bad["ttft_s"] = dict(summary["ttft_s"],
                         p99=summary["ttft_s"]["p99"] * 10)
    bad["gen_tokens_per_sec"] = summary["gen_tokens_per_sec"] * 0.4
    art = tmp_path / "SERVE_LOOP.jsonl"
    art.write_text(json.dumps(bad) + "\n")
    verdicts = check_artifact(str(art), index)
    regs = {v.metric for v in regressions(verdicts)}
    assert "serve_loop.ttft_s_p99" in regs
    assert "serve_loop.gen_tokens_per_sec" in regs


def test_faithful_copy_passes(tmp_path):
    index = _committed_index()
    src = os.path.join(ROOT, "SERVE_LOOP.jsonl")
    art = tmp_path / "SERVE_LOOP.jsonl"
    art.write_text(open(src).read())
    assert not regressions(check_artifact(str(art), index))


def test_regressed_zero_overlap_boolean_fails(tmp_path):
    """Parity booleans gate at zero tolerance: bitwise_parity=false
    in a fresh ZERO_OVERLAP artifact is a regression."""
    index = _committed_index()
    row = {"phase": "summary", "bitwise_parity": False,
           "gather_overlap_ratio_on": 0.375,
           "qrs_wire_fraction_of_fp32": 0.3292,
           "native_async_pairs": 0, "prefetch_on_gather_pairs": 6,
           "utc": "2026-08-04T00:00:00Z"}
    art = tmp_path / "ZERO_OVERLAP.jsonl"
    art.write_text(json.dumps(row) + "\n")
    regs = {v.metric
            for v in regressions(check_artifact(str(art), index))}
    assert "zero_overlap.bitwise_parity" in regs


def test_improvement_is_not_a_regression():
    index = _committed_index()
    verdicts = check_points(
        [MetricPoint("zero_overlap.gather_overlap_ratio", 0.9,
                     "NEW.jsonl")], index)
    assert not regressions(verdicts)
    assert any(v.status == "improved" for v in verdicts)


def test_different_config_is_not_compared():
    """A 7B-layer vet point must not 'regress' the 350m headline —
    like-for-like only."""
    index = _committed_index()
    verdicts = check_points(
        [MetricPoint("train.tokens_per_sec_per_chip", 14000.0,
                     "VET_X.json",
                     tags={"config": "350m-hd128-lchunk-seq16k-b1"})],
        index)
    assert not verdicts, \
        "different-config point produced a verdict"


def test_headline_mode_detects_evidence_tampering(tmp_path):
    """Repo mode: rebuilding the index over a tree whose best evidence
    got worse must fail against the committed baseline."""
    baseline = _committed_index()
    fresh = build_index(ROOT)
    ok = check_headline(fresh, baseline)
    assert not regressions(ok), \
        "pristine tree must pass its own committed baseline"
    # tamper: drop the best zero-overlap ratio in the fresh headline
    fresh["headline"]["zero_overlap.gather_overlap_ratio"]["value"] \
        = 0.1
    regs = regressions(check_headline(fresh, baseline))
    assert any(v.metric == "zero_overlap.gather_overlap_ratio"
               for v in regs)
    # tamper harder: the metric vanishes entirely
    del fresh["headline"]["zero_overlap.gather_overlap_ratio"]
    regs = regressions(check_headline(fresh, baseline))
    assert any(v.metric == "zero_overlap.gather_overlap_ratio"
               for v in regs)


def test_self_check_rows_roundtrip():
    """The bench hook: within-tolerance rows produce ok=True, a
    regressed row is recorded in the artifact-bound verdict."""
    rows = [{"phase": "chaos-summary", "deterministic": True,
             "invariants_ok": True, "violations": []}]
    out = self_check_rows("CHAOS_SERVE.jsonl", rows, root=ROOT)
    assert out["phase"] == "perf-check"
    assert out.get("ok") is True, out
    bad = [{"phase": "chaos-summary", "deterministic": False,
            "invariants_ok": True, "violations": []}]
    out = self_check_rows("CHAOS_SERVE.jsonl", bad, root=ROOT)
    assert out.get("ok") is False
    assert any(r["metric"] == "chaos.deterministic"
               for r in out["regressions"])


def test_cli_check_self_test_and_lint():
    from hcache_deepspeed_tpu.perf.__main__ import main
    assert main(["check", "--self-test"]) == 0
    assert main(["--root", ROOT, "lint"]) == 0


def test_lint_catches_schemaless_artifact_literal(tmp_path):
    """perf lint fails when source writes an artifact name the
    registry has no schema for."""
    from hcache_deepspeed_tpu.perf.registry import lint_sources
    root = tmp_path / "repo"
    (root / "hcache_deepspeed_tpu").mkdir(parents=True)
    (root / "bench.py").write_text(
        'OUT = "TOTALLY_NEW_EVIDENCE.jsonl"\n')
    violations = lint_sources(root=str(root))
    assert violations and "TOTALLY_NEW_EVIDENCE.jsonl" in \
        violations[0]
    # a schema'd name lints clean
    (root / "bench.py").write_text('OUT = "ZERO_OVERLAP.jsonl"\n')
    assert lint_sources(root=str(root)) == []
