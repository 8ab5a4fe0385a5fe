"""Golden-schema walk of the committed perf evidence.

Walks every committed root ``*.json`` / ``*.jsonl`` artifact and
asserts it (a) classifies into a registry family, (b) parses under
that family's schema, and (c) is represented in the committed
``PERF_TRAJECTORY.json`` — or is explicitly allowlisted in
``perf/KNOWN_UNINDEXED`` with a justification. The allowlist goal is
EMPTY; a future PR adding an artifact family without a schema fails
here, which is the point.
"""

import json
import os

import pytest

from hcache_deepspeed_tpu.perf import (INDEX_NAME, build_index,
                                       classify, load_allowlist,
                                       load_index, parse_artifact)
from hcache_deepspeed_tpu.perf.registry import iter_artifact_names

ROOT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", ".."))


def _root_artifacts():
    return [n for n in iter_artifact_names(ROOT)
            if n.endswith((".json", ".jsonl"))]


def test_repo_root_sane():
    assert os.path.exists(os.path.join(ROOT, "bench.py"))
    assert _root_artifacts(), "no committed artifacts found?"


@pytest.mark.parametrize("name", _root_artifacts())
def test_every_root_artifact_classifies_and_parses(name):
    allow = load_allowlist()
    fam = classify(name)
    if fam is None:
        assert name in allow, (
            f"{name} matches no registry family and is not "
            "allowlisted in perf/KNOWN_UNINDEXED — declare a schema "
            "in perf/schemas.py")
        assert allow[name], (
            f"{name} is allowlisted without a justification")
        return
    parsed = parse_artifact(os.path.join(ROOT, name), name)
    assert parsed.status in ("ok", "empty", "meta"), \
        f"{name}: {parsed.status} ({parsed.note})"
    # a non-empty data artifact must yield at least one indexable
    # point OR be a declared meta family
    if parsed.status == "ok":
        assert parsed.points or parsed.family in ("chip-log",), \
            f"{name} parsed but yielded no metric points"


def test_allowlist_is_empty_goal():
    """The allowlist is a debt ledger: every entry must name a file
    that actually exists (no stale entries) and carry a reason. The
    committed goal state is empty."""
    allow = load_allowlist()
    for name, why in allow.items():
        assert why, f"allowlist entry {name} has no justification"
        assert os.path.exists(os.path.join(ROOT, name)), \
            f"allowlist entry {name} names a nonexistent file"
    assert allow == {}, (
        "perf/KNOWN_UNINDEXED should stay empty — declare schemas "
        f"instead of allowlisting: {sorted(allow)}")


def test_committed_index_exists_and_covers_every_artifact():
    index = load_index(root=ROOT)
    assert index["version"] == 1
    indexed = {a["file"] for a in index["artifacts"]}
    for name in _root_artifacts():
        assert name in indexed, (
            f"{name} missing from committed {INDEX_NAME} — rerun "
            "`python -m hcache_deepspeed_tpu.perf index --git`")
    # no artifact landed in an error/unindexed state
    bad = [a for a in index["artifacts"]
           if a["status"] in ("error", "unindexed")
           and not a.get("allowlisted")]
    assert not bad, f"broken/unindexed committed artifacts: {bad}"


def test_committed_index_matches_fresh_rebuild():
    """The committed series must equal a fresh rebuild of the same
    tree (metric names, point counts, values) — a PR that changes
    artifacts or schemas without re-indexing fails here."""
    committed = load_index(root=ROOT)
    fresh = build_index(ROOT)
    assert sorted(fresh["series"]) == sorted(committed["series"]), (
        "series set drifted — rerun the perf index CLI")
    for metric, rows in fresh["series"].items():
        crows = committed["series"][metric]
        assert len(rows) == len(crows), f"{metric}: point count drift"
        assert [r["value"] for r in rows] == \
            [r["value"] for r in crows], f"{metric}: values drift"
    # headline block agrees on values (tolerances come from code)
    for metric, head in fresh["headline"].items():
        assert metric in committed["headline"], metric
        assert committed["headline"][metric]["value"] == \
            head["value"], f"headline {metric} drifted"


def test_empty_artifacts_are_visible_not_silent():
    """Zero-byte artifacts (interrupted runs) index with
    status=empty — never dropped."""
    index = load_index(root=ROOT)
    by_file = {a["file"]: a for a in index["artifacts"]}
    empties = [n for n in _root_artifacts()
               if os.path.getsize(os.path.join(ROOT, n)) == 0]
    for name in empties:
        assert by_file[name]["status"] == "empty", name


def test_jsonl_rows_all_parse_or_are_log_lines():
    """Every line in every committed JSONL either parses as JSON or
    is a recognizable log line — no half-written JSON rows."""
    for name in _root_artifacts():
        if not name.endswith(".jsonl"):
            continue
        with open(os.path.join(ROOT, name), encoding="utf-8",
                  errors="replace") as fh:
            for i, line in enumerate(fh, 1):
                line = line.strip()
                if not line:
                    continue
                if line.startswith("{"):
                    try:
                        json.loads(line)
                    except json.JSONDecodeError as exc:
                        raise AssertionError(
                            f"{name}:{i}: corrupt JSON row: "
                            f"{exc}") from exc
                else:
                    assert line.startswith(("[", "WARNING", "INFO",
                                            "ERROR", "#")), \
                        f"{name}:{i}: unrecognizable line {line[:60]!r}"
