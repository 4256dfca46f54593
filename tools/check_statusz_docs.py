#!/usr/bin/env python3
"""Lint the observability contract surface against ARCHITECTURE.md.

The /statusz schema (`polyrl_tpu/obs/statusz.py`) and the metric
namespace set (`tools/check_metric_names.py`) are both CLOSED contracts:
consumers parse every section of every snapshot, and dashboards group by
namespace. A section or namespace that ships without documentation is a
contract change nobody can discover — so this lint fails the quick tier
(tests/test_obs_tracing.py) when:

- any ``statusz.REQUIRED_SECTIONS`` entry is not mentioned (backticked)
  in ARCHITECTURE.md;
- the current ``statusz.SCHEMA`` version string is not mentioned in
  ARCHITECTURE.md (the version-history table must cover the live
  version);
- any ``check_metric_names.NAMESPACES`` entry is not mentioned
  (backticked, bare or as an ``area/...`` key prefix) in ARCHITECTURE.md;
- any ``statusz.CUMULATIVE_INFO_KEYS`` entry (the engine's
  completion-stamp counters and the server's stream counters in
  ``server_info``) is not mentioned (backticked) in ARCHITECTURE.md.

Run: ``python tools/check_statusz_docs.py [ARCHITECTURE.md]`` — exits 1
and lists violations.
"""

from __future__ import annotations

import os
import re
import sys

_TOOLS = os.path.dirname(os.path.abspath(__file__))
_REPO = os.path.dirname(_TOOLS)
for _p in (_REPO, _TOOLS):   # _TOOLS: sibling import works under importlib
    if _p not in sys.path:
        sys.path.insert(0, _p)

from polyrl_tpu.obs import statusz  # noqa: E402

import check_metric_names  # noqa: E402  (sibling module in tools/)


def _mentioned(doc: str, token: str) -> bool:
    """Backticked mention: `token`, `token` inside a code span path
    (``statusz`` URL bits), or as a namespace key prefix `token/...`."""
    return re.search(r"`[^`\n]*\b" + re.escape(token) + r"\b[^`\n]*`",
                     doc) is not None


def check_doc(doc_path: str) -> list[str]:
    with open(doc_path) as f:
        doc = f.read()
    violations: list[str] = []
    for section in statusz.REQUIRED_SECTIONS:
        if not _mentioned(doc, section):
            violations.append(
                f"statusz section {section!r} (statusz.REQUIRED_SECTIONS) "
                f"is not documented in {os.path.basename(doc_path)} — every "
                "conformance-pinned section needs a backticked mention")
    if statusz.SCHEMA not in doc:
        violations.append(
            f"live schema version {statusz.SCHEMA!r} is not mentioned in "
            f"{os.path.basename(doc_path)} — update the /statusz "
            "version-history table when bumping the schema")
    for ns in sorted(check_metric_names.NAMESPACES):
        if not _mentioned(doc, ns):
            violations.append(
                f"metric namespace {ns!r} (check_metric_names.NAMESPACES) "
                f"is not documented in {os.path.basename(doc_path)} — the "
                "namespace list there must stay in sync")
    for key in sorted(statusz.CUMULATIVE_INFO_KEYS):
        if not _mentioned(doc, key):
            violations.append(
                f"server_info counter {key!r} (statusz.CUMULATIVE_INFO_KEYS)"
                f" is not documented in {os.path.basename(doc_path)}")
    return violations


def default_doc() -> str:
    return os.path.join(_REPO, "ARCHITECTURE.md")


def main(argv: list[str] | None = None) -> int:
    doc = (argv[0] if argv else default_doc())
    violations = check_doc(doc)
    for v in violations:
        print(v)
    if violations:
        print(f"{len(violations)} statusz/namespace doc violations",
              file=sys.stderr)
        return 1
    print("statusz + namespace docs ok")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:] or None))
