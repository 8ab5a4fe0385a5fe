"""Performance observatory: artifact registry + regression sentinel.

The repo root's committed perf evidence (bench JSON, phase-stream
JSONLs) becomes machine-readable here:

* :mod:`.schemas` — one declared family + parser per artifact kind;
* :mod:`.registry` — walks/classifies/indexes into the committed
  ``PERF_TRAJECTORY.json`` (per-metric series with producer-PR and
  phase tags) and lints source for artifact names
  without a schema;
* :mod:`.check` — the regression gate (`perf check`): fresh points vs
  the committed headline values, with per-metric tolerances, plus the
  ``self_check_rows`` hook bench runs call before writing artifacts.

CLI: ``python -m hcache_deepspeed_tpu.perf index|check|lint``.
See ``docs/observability.md``.
"""

from .check import (TOLERANCES, Tolerance, Verdict,  # noqa: F401
                    check_artifact, check_headline, check_points,
                    regressions, self_check_rows, self_test)
from .registry import (INDEX_NAME, build_index, lint_sources,  # noqa: F401
                       load_allowlist, load_index, repo_root,
                       write_index)
from .schemas import (FAMILIES, ArtifactFamily, MetricPoint,  # noqa: F401
                      ParsedArtifact, classify, parse_artifact)

__all__ = [
    "FAMILIES", "ArtifactFamily", "MetricPoint", "ParsedArtifact",
    "classify", "parse_artifact", "INDEX_NAME", "build_index",
    "write_index", "load_index", "load_allowlist", "lint_sources",
    "repo_root", "TOLERANCES", "Tolerance", "Verdict", "check_points",
    "check_artifact", "check_headline", "regressions",
    "self_check_rows", "self_test",
]
