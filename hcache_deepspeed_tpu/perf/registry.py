"""Perf-artifact registry: walk, classify, index.

Builds the committed ``PERF_TRAJECTORY.json`` — the machine-readable
trajectory the repo root's perf artifacts previously only implied:

* every root ``*.json`` / ``*.jsonl`` / ``*.log`` is classified into a
  family (``perf.schemas``) and parsed into metric points;
* points are grouped into per-metric **series** (tok/s/chip, MFU,
  overlap ratios, wire fraction, serve-loop TTFT/TPOT percentiles,
  chaos invariants, ...), each point tagged with its producing file,
  bench phase and producer PR (first git commit that added the file,
  when git is available);
* a **headline** block carries, per regression-gated metric
  (``perf.check.TOLERANCES``), the best committed value — the number
  ``perf check`` refuses to regress.

The golden-schema tier-1 test re-walks the root and fails on any
artifact the registry can't classify that is not allowlisted in
``perf/KNOWN_UNINDEXED`` (shipped empty — the allowlist is a debt
ledger, not a dumping ground). The driver's own records
(``PERF_LEDGER.jsonl``, ``BENCHMARK.json``) are not this registry's.
"""

import json
import os
import re
import subprocess
import time
from typing import Dict, List, Optional

from .schemas import FAMILIES, ParsedArtifact, classify, parse_artifact

INDEX_NAME = "PERF_TRAJECTORY.json"
ALLOWLIST_NAME = "KNOWN_UNINDEXED"
UTC_FMT = "%Y-%m-%dT%H:%M:%SZ"

#: root files that are code/config/docs or the driver's own records,
#: never this registry's artifacts
_NON_ARTIFACTS = {"pyproject.toml", INDEX_NAME, "PERF_LEDGER.jsonl",
                  "BENCHMARK.json"}


def repo_root(start: Optional[str] = None) -> str:
    """Nearest ancestor containing bench.py + the package dir — the
    artifact root (works from an installed checkout or the repo)."""
    d = os.path.abspath(start or os.getcwd())
    while True:
        if os.path.exists(os.path.join(d, "bench.py")) and \
                os.path.isdir(os.path.join(d, "hcache_deepspeed_tpu")):
            return d
        parent = os.path.dirname(d)
        if parent == d:
            raise FileNotFoundError(
                "could not locate the repo root (bench.py) above "
                f"{start or os.getcwd()}")
        d = parent


def allowlist_path() -> str:
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        ALLOWLIST_NAME)


def load_allowlist() -> Dict[str, str]:
    """filename -> justification from perf/KNOWN_UNINDEXED (shipped
    empty; '#' comments and blank lines ignored)."""
    out: Dict[str, str] = {}
    try:
        with open(allowlist_path()) as fh:
            for line in fh:
                line = line.strip()
                if not line or line.startswith("#"):
                    continue
                name, _, why = line.partition("#")
                out[name.strip()] = why.strip()
    except FileNotFoundError:
        pass
    return out


def iter_artifact_names(root: str) -> List[str]:
    """Committed root-level perf artifacts, sorted: every ``*.json`` /
    ``*.jsonl`` / ``*.log``."""
    names = []
    for name in sorted(os.listdir(root)):
        if name in _NON_ARTIFACTS:
            continue
        if not os.path.isfile(os.path.join(root, name)):
            continue
        if name.endswith((".json", ".jsonl", ".log")):
            names.append(name)
    return names


def producer_pr(root: str, filename: str) -> str:
    """First commit that added ``filename`` (abbrev hash + subject),
    best-effort: 'uncommitted' for new files, 'unknown' without git."""
    try:
        out = subprocess.run(
            ["git", "log", "--follow", "--diff-filter=A",
             "--format=%h %s", "-1", "--", filename],
            cwd=root, capture_output=True, text=True, timeout=10)
        if out.returncode != 0:
            return "unknown"
        line = out.stdout.strip().splitlines()
        return line[0][:120] if line else "uncommitted"
    except (OSError, subprocess.TimeoutExpired):
        return "unknown"


# ----------------------------------------------------------------- #
def build_index(root: Optional[str] = None, now: Optional[float] = None,
                with_git: bool = False) -> Dict:
    """The full index dict (see module docstring). Deterministic for a
    fixed (tree, now); ``with_git`` adds producer-PR attribution via
    subprocess git calls."""
    from .check import TOLERANCES
    root = root or repo_root()
    # the ONE sanctioned wall-clock site in the deterministic-given-
    # (tree, now) index build: the generated_utc stamp when the CLI
    # did not inject --now
    # hds: allow(HDS-P001) sanctioned generated_utc default, CLI --now injects
    now = time.time() if now is None else now
    artifacts: List[Dict] = []
    series: Dict[str, List[Dict]] = {}
    unindexed: List[str] = []
    allow = load_allowlist()
    for name in iter_artifact_names(root):
        path = os.path.join(root, name)
        if classify(name) is None:
            unindexed.append(name)
            artifacts.append({
                "file": name, "family": None, "status": "unindexed",
                "allowlisted": name in allow,
                "note": allow.get(name, "NOT ALLOWLISTED")})
            continue
        try:
            parsed: ParsedArtifact = parse_artifact(path, name)
        except Exception as exc:     # broken known artifact: visible
            artifacts.append({
                "file": name, "family": classify(name).name,
                "status": "error", "note": f"{type(exc).__name__}: "
                                           f"{exc}"})
            continue
        row = {"file": name, "family": parsed.family,
               "status": parsed.status, "points": len(parsed.points)}
        if parsed.note:
            row["note"] = parsed.note
        if with_git:
            row["producer_pr"] = producer_pr(root, name)
        artifacts.append(row)
        for p in parsed.points:
            rec = p.to_json()
            if with_git and "producer_pr" in row:
                rec["producer_pr"] = row["producer_pr"]
            series.setdefault(p.metric, []).append(rec)
    for rows in series.values():
        rows.sort(key=lambda r: (r.get("utc") or "", r["file"],
                                 json.dumps(r.get("tags", {}),
                                            sort_keys=True)))
    headline = {}
    for metric, tol in sorted(TOLERANCES.items()):
        rows = series.get(metric)
        if not rows:
            continue
        pick = (min if tol.direction == "lower" else max)(
            rows, key=lambda r: r["value"])
        headline[metric] = {
            "value": pick["value"], "file": pick["file"],
            "utc": pick.get("utc"),
            "tags": pick.get("tags", {}),
            "direction": tol.direction,
            "rel_tolerance": tol.rel,
            "abs_tolerance": tol.abs,
        }
    return {
        "version": 1,
        "generated_utc": time.strftime(UTC_FMT, time.gmtime(now)),
        "families": {f.name: f.description for f in FAMILIES},
        "artifacts": artifacts,
        "series": {k: series[k] for k in sorted(series)},
        "headline": headline,
        "unindexed": sorted(unindexed),
        "allowlisted": allow,
    }


def write_index(path: Optional[str] = None, root: Optional[str] = None,
                with_git: bool = False,
                now: Optional[float] = None) -> Dict:
    root = root or repo_root()
    path = path or os.path.join(root, INDEX_NAME)
    index = build_index(root, now=now, with_git=with_git)
    tmp = path + ".tmp"
    with open(tmp, "w") as fh:
        json.dump(index, fh, indent=1, sort_keys=False)
        fh.write("\n")
    os.replace(tmp, path)
    return index


def load_index(path: Optional[str] = None,
               root: Optional[str] = None) -> Dict:
    root = root or repo_root()
    path = path or os.path.join(root, INDEX_NAME)
    with open(path) as fh:
        return json.load(fh)


# ----------------------------------------------------------------- #
# lint: no source-written artifact without a schema
# ----------------------------------------------------------------- #
#: quoted artifact-style filename in source: ALL_CAPS stem + .json(l)
_ARTIFACT_LITERAL_RE = re.compile(
    r"""["']([A-Z][A-Z0-9_]*\.(?:json|jsonl))["']""")


def lint_sources(root: Optional[str] = None) -> List[str]:
    """Scan non-test source (bench.py + the package) for artifact-style
    filename literals and return one violation per literal the registry
    has no schema for. This is what keeps future bench phases from
    minting evidence files the index silently ignores."""
    root = root or repo_root()
    violations = []
    sources = [os.path.join(root, "bench.py")]
    pkg = os.path.join(root, "hcache_deepspeed_tpu")
    for dirpath, dirnames, filenames in os.walk(pkg):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        sources.extend(os.path.join(dirpath, f) for f in filenames
                       if f.endswith(".py"))
    for src in sources:
        try:
            with open(src, encoding="utf-8") as fh:
                text = fh.read()
        except OSError:
            continue
        for m in _ARTIFACT_LITERAL_RE.finditer(text):
            name = m.group(1)
            if name not in _NON_ARTIFACTS and classify(name) is None:
                line = text.count("\n", 0, m.start()) + 1
                violations.append(
                    f"{os.path.relpath(src, root)}:{line}: artifact "
                    f"literal {name!r} has no registry schema "
                    "(declare a family in perf/schemas.py)")
    return sorted(set(violations))
