"""Static-analysis core: walker, rule registry, findings, baseline.

The repo's headline results are gated on two invariants nothing was
machine-checking until now: **byte-identical same-seed replay** (the
CHAOS_SERVE / FLEET_SERVE / DISAGG_SERVE digests) and **coherent
thread-shared state** across the server loop, fleet pump, metrics HTTP
thread and restore lanes. This package checks them the same way
``perf lint`` checks artifact provenance: an AST walk over the tree,
a registry of rule families with per-finding codes, and a committed
baseline so pre-existing findings don't block the tier-1 gate while
*new* ones do.

Vocabulary:

* **Finding** — one violation, identified by a stable fingerprint
  ``code:path:qualname:symbol`` (deliberately line-free, so moving
  code doesn't stale the baseline; a genuinely new access site of the
  same symbol in the same scope is the same discipline bug).
* **Sanctioned site** — a finding suppressed in-source by an allow
  pragma ``# hds: allow(CODE) <reason>``. The reason is mandatory
  (an allow without one is itself a finding, HDS-C003): the pragma
  *documents* a deliberate exception, it does not hide it. Sanctioned
  sites are reported separately, never silently dropped.
* **Baseline** — ``analysis/BASELINE.json``, fingerprint -> reason.
  The gate fails on any finding not in the baseline AND on any
  baseline entry that no longer fires (stale entries rot into cover
  for future regressions, so they are errors too).
* **Sim-deterministic module** — a module whose behavior must be a
  pure function of its inputs (trace, seed, virtual clock) because
  committed digests replay it byte-for-byte. Declared either by the
  config's path patterns (:data:`SIM_DETERMINISTIC`) or in-file via
  ``__hds_sim_deterministic__ = True``.
"""

import ast
import json
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Set, Tuple

#: path patterns (relpath prefixes, '/'-separated) declared
#: sim-deterministic: the committed chaos/fleet/disagg digests replay
#: these byte-for-byte, so ambient wall-clock, unseeded RNG and
#: hash-order iteration are forbidden here. ``perf/`` is included
#: because ``build_index`` documents "deterministic for a fixed
#: (tree, now)" — its one wall-clock default is a sanctioned site.
SIM_DETERMINISTIC = (
    "hcache_deepspeed_tpu/serving/",
    "hcache_deepspeed_tpu/resilience/",
    "hcache_deepspeed_tpu/fabric/",
    "hcache_deepspeed_tpu/runtime/zero/qwire.py",
    "hcache_deepspeed_tpu/perf/",
    "hcache_deepspeed_tpu/utils/io_bench.py",
)

_ALLOW_RE = re.compile(
    r"#\s*hds:\s*allow\(\s*([A-Z0-9\-,\s]+?)\s*\)\s*(.*)")


@dataclass(frozen=True)
class Finding:
    """One rule violation at one source location."""

    code: str          # e.g. "HDS-L001"
    family: str        # "locks" | "purity" | "convention" | "perf"
    path: str          # repo-relative, '/'-separated
    line: int
    qualname: str      # "Class.method", "function", or "<module>"
    symbol: str        # the offending attribute / callable / name
    message: str

    @property
    def fingerprint(self) -> str:
        return f"{self.code}:{self.path}:{self.qualname}:{self.symbol}"

    def render(self) -> str:
        return (f"{self.path}:{self.line}: {self.code} "
                f"[{self.qualname}] {self.message}")


@dataclass
class ModuleInfo:
    """One parsed source module plus the metadata rules consult."""

    path: str                   # absolute
    relpath: str                # analysis-root-relative, '/'-separated
    tree: ast.Module
    lines: List[str]
    #: line -> set of allowed codes (pragma on that line; a pragma on
    #: a ``def`` line covers the whole function body)
    allows: Dict[int, Set[str]] = field(default_factory=dict)
    #: (line, codes) of pragmas missing a reason — themselves findings
    bad_pragmas: List[Tuple[int, str]] = field(default_factory=list)
    sim_deterministic: bool = False
    #: module declares its lock acquisition order (L003 consults this)
    lock_order: Optional[Tuple[str, ...]] = None

    def allowed(self, code: str, line: int) -> bool:
        """A finding at ``line`` is sanctioned when its line — or the
        comment line directly above it — carries an allow pragma for
        its code (def-line pragmas were already range-expanded)."""
        for ln in (line, line - 1):
            if code in self.allows.get(ln, ()):
                return True
        return False


@dataclass
class AnalysisConfig:
    """What to scan and under which declarations."""

    #: directory whose ``**/*.py`` is analyzed
    root: str = ""
    #: extra single files (repo mode adds ``bench.py``)
    extra_files: Tuple[str, ...] = ()
    #: relpath prefixes declared sim-deterministic (in-file
    #: ``__hds_sim_deterministic__ = True`` also works)
    sim_deterministic: Tuple[str, ...] = SIM_DETERMINISTIC
    #: run the perf-registry source lint (needs a repo root carrying
    #: bench.py; fixture runs leave it off)
    perf_lint: bool = False
    #: repo root for perf_lint (defaults to parent of ``root``)
    repo_root: Optional[str] = None
    #: rule families to run (None = all registered)
    families: Optional[Tuple[str, ...]] = None


class AnalysisContext:
    """Shared state across modules for cross-module rules (e.g. the
    async-span pairing ledger)."""

    def __init__(self, config: AnalysisConfig):
        self.config = config
        self.modules: List[ModuleInfo] = []
        self.shared: Dict[str, object] = {}


class Rule:
    """One rule family: per-module check + cross-module finalize."""

    family = "base"
    codes: Tuple[str, ...] = ()

    def check_module(self, mod: ModuleInfo,
                     ctx: AnalysisContext) -> Iterable[Finding]:
        return ()

    def finalize(self, ctx: AnalysisContext) -> Iterable[Finding]:
        return ()


# ----------------------------------------------------------------- #
# parsing
# ----------------------------------------------------------------- #
def _parse_pragmas(mod: ModuleInfo) -> None:
    for i, line in enumerate(mod.lines, start=1):
        m = _ALLOW_RE.search(line)
        if not m:
            continue
        codes = {c.strip() for c in m.group(1).split(",") if c.strip()}
        reason = m.group(2).strip().lstrip("-—– ").strip()
        if not reason:
            mod.bad_pragmas.append((i, ",".join(sorted(codes))))
            continue
        mod.allows.setdefault(i, set()).update(codes)


def _expand_def_pragmas(mod: ModuleInfo) -> None:
    """A pragma on (or directly above) a ``def``/``class`` line covers
    the whole body — the method-level suppression used for e.g. the
    fleet's virtual-clock ``step()``, whose single-threaded-by-contract
    mutations would otherwise need a pragma per line."""
    for node in ast.walk(mod.tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
            continue
        codes: Set[str] = set()
        for ln in (node.lineno, node.lineno - 1):
            codes |= mod.allows.get(ln, set())
        if not codes:
            continue
        for ln in range(node.lineno, (node.end_lineno or node.lineno)
                        + 1):
            mod.allows.setdefault(ln, set()).update(codes)


def _module_declarations(mod: ModuleInfo) -> None:
    for node in mod.tree.body:
        if not isinstance(node, ast.Assign):
            continue
        for tgt in node.targets:
            if not isinstance(tgt, ast.Name):
                continue
            if tgt.id == "__hds_sim_deterministic__":
                try:
                    mod.sim_deterministic = bool(
                        ast.literal_eval(node.value))
                except ValueError:
                    pass
            if tgt.id == "__hds_lock_order__":
                try:
                    mod.lock_order = tuple(
                        ast.literal_eval(node.value))
                except ValueError:
                    mod.lock_order = ()


def load_module(path: str, relpath: str,
                config: AnalysisConfig) -> ModuleInfo:
    with open(path, encoding="utf-8") as fh:
        source = fh.read()
    tree = ast.parse(source, filename=path)
    mod = ModuleInfo(path=path, relpath=relpath, tree=tree,
                     lines=source.splitlines())
    mod.sim_deterministic = any(
        relpath == pat or relpath.startswith(pat)
        for pat in config.sim_deterministic)
    _module_declarations(mod)
    _parse_pragmas(mod)
    _expand_def_pragmas(mod)
    return mod


def iter_source_files(config: AnalysisConfig):
    """(abspath, relpath) for every analyzed module, sorted for
    deterministic finding order."""
    out = []
    root = os.path.abspath(config.root)
    base = os.path.basename(root)
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = sorted(d for d in dirnames
                             if d != "__pycache__")
        for fname in sorted(filenames):
            if not fname.endswith(".py"):
                continue
            path = os.path.join(dirpath, fname)
            rel = os.path.relpath(path, root).replace(os.sep, "/")
            out.append((path, f"{base}/{rel}"))
    for extra in config.extra_files:
        out.append((os.path.abspath(extra), os.path.basename(extra)))
    return out


# ----------------------------------------------------------------- #
# the run
# ----------------------------------------------------------------- #
@dataclass
class Report:
    findings: List[Finding]
    sanctioned: List[Tuple[Finding, int]]   # (finding, pragma line)
    n_modules: int = 0

    @property
    def by_family(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for f in self.findings:
            out[f.family] = out.get(f.family, 0) + 1
        return out

    @property
    def codes(self) -> Set[str]:
        return {f.code for f in self.findings}


def registered_rules() -> List[Rule]:
    from .rules_convention import ConventionRule
    from .rules_locks import LockDisciplineRule
    from .rules_purity import PurityRule
    return [LockDisciplineRule(), PurityRule(), ConventionRule()]


def run_analysis(config: AnalysisConfig) -> Report:
    rules = registered_rules()
    if config.families is not None:
        rules = [r for r in rules if r.family in config.families]
    ctx = AnalysisContext(config)
    raw: List[Finding] = []
    for path, relpath in iter_source_files(config):
        mod = load_module(path, relpath, config)
        ctx.modules.append(mod)
        for rule in rules:
            raw.extend(rule.check_module(mod, ctx))
    for rule in rules:
        raw.extend(rule.finalize(ctx))
    if config.perf_lint and (config.families is None or
                             "perf" in config.families):
        raw.extend(_perf_lint_findings(config))
    # split sanctioned (pragma'd) from live findings
    by_rel = {m.relpath: m for m in ctx.modules}
    findings: List[Finding] = []
    sanctioned: List[Tuple[Finding, int]] = []
    for f in raw:
        mod = by_rel.get(f.path)
        if mod is not None and mod.allowed(f.code, f.line):
            sanctioned.append((f, f.line))
        else:
            findings.append(f)
    findings.sort(key=lambda f: (f.path, f.line, f.code, f.symbol))
    return Report(findings=findings, sanctioned=sanctioned,
                  n_modules=len(ctx.modules))


def _perf_lint_findings(config: AnalysisConfig) -> List[Finding]:
    """Fold ``perf lint`` (artifact literals without a registry
    schema) in as the fourth family so one CLI runs everything."""
    from ..perf.registry import lint_sources, repo_root
    root = config.repo_root
    if root is None:
        try:
            root = repo_root(config.root)
        except FileNotFoundError:
            return []
    out = []
    for violation in lint_sources(root=root):
        loc, _, msg = violation.partition(": ")
        path, _, line = loc.rpartition(":")
        out.append(Finding(
            code="HDS-PERF1", family="perf",
            path=path.replace(os.sep, "/"),
            line=int(line) if line.isdigit() else 0,
            qualname="<module>",
            symbol=msg.split("'")[1] if "'" in msg else "artifact",
            message=msg))
    return out


# ----------------------------------------------------------------- #
# baseline
# ----------------------------------------------------------------- #
def baseline_path() -> str:
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BASELINE.json")


def load_baseline(path: Optional[str] = None) -> Dict[str, str]:
    path = path or baseline_path()
    try:
        with open(path, encoding="utf-8") as fh:
            data = json.load(fh)
    except FileNotFoundError:
        return {}
    return dict(data.get("entries", {}))


def save_baseline(entries: Dict[str, str],
                  path: Optional[str] = None) -> str:
    path = path or baseline_path()
    payload = {
        "version": 1,
        "note": ("fingerprint -> reason for pre-existing findings the "
                 "gate tolerates; stale entries (no longer firing) "
                 "FAIL the gate — regenerate with --write-baseline"),
        "entries": {k: entries[k] for k in sorted(entries)},
    }
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(payload, fh, indent=1)
        fh.write("\n")
    return path


def gate(report: Report, baseline: Dict[str, str]
         ) -> Tuple[List[Finding], List[str]]:
    """(new findings not in baseline, stale baseline fingerprints)."""
    fired = {f.fingerprint for f in report.findings}
    new = [f for f in report.findings
           if f.fingerprint not in baseline]
    stale = sorted(fp for fp in baseline if fp not in fired)
    return new, stale
