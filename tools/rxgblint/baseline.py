"""Justified-baseline suppression file.

The baseline is the escape hatch for findings the team has *reviewed and
accepted* — typically structural false positives the AST rules cannot see
through (e.g. a helper whose dynamic span name is fed only by literal call
sites two lines below). Every entry MUST carry a one-line ``why``; loading
a baseline with a missing/empty justification is an error, so "suppress it
and move on" is never silent.

Entries key on ``(rule, path, scope)`` — not line numbers — so routine
edits to a file don't invalidate its baseline. Stale entries (matching no
current finding) are reported by the runner so the baseline shrinks as the
code improves.

Schema (JSON)::

    {"entries": [
        {"rule": "OBS001",
         "path": "xgboost_ray_tpu/engine.py",
         "scope": "TpuEngine.some_method.emit",
         "why": "one-line justification"}
    ]}
"""

import json
import os
from typing import Dict, List, Set, Tuple

from tools.rxgblint.findings import RULES, Finding

DEFAULT_BASELINE = os.path.join(os.path.dirname(__file__), "baseline.json")

Key = Tuple[str, str, str]


class BaselineError(ValueError):
    """The baseline file itself is malformed (bad rule, missing why)."""


def load(path: str) -> List[Dict[str, str]]:
    """Load + validate the baseline; returns the entry list ([] when the
    file does not exist)."""
    if not os.path.exists(path):
        return []
    with open(path, "r", encoding="utf-8") as f:
        doc = json.load(f)
    entries = doc.get("entries", [])
    if not isinstance(entries, list):
        raise BaselineError(f"{path}: 'entries' must be a list")
    for i, e in enumerate(entries):
        where = f"{path}: entry {i}"
        for req in ("rule", "path", "scope", "why"):
            if not isinstance(e.get(req), str) or not e.get(req, "").strip():
                raise BaselineError(
                    f"{where}: missing/empty {req!r} — every baseline entry "
                    f"needs a rule, a path, a scope, and a one-line "
                    f"justification"
                )
        if e["rule"] not in RULES:
            raise BaselineError(
                f"{where}: unknown rule {e['rule']!r}; one of {sorted(RULES)}"
            )
    return entries


def apply(findings: List[Finding], entries: List[Dict[str, str]]):
    """Mark findings matched by a baseline entry as suppressed.

    Returns ``(stale_entries, used)`` — entries that matched nothing (the
    runner reports them so the baseline shrinks over time), and the count
    of findings suppressed."""
    keys: Set[Key] = {(e["rule"], e["path"], e["scope"]) for e in entries}
    used: Set[Key] = set()
    n_suppressed = 0
    for f in findings:
        if f.suppressed:
            continue
        if f.key() in keys:
            f.suppressed = "baseline"
            used.add(f.key())
            # one scope-keyed entry may match several findings; the count
            # must track findings (what the --json diffing sums), not keys
            n_suppressed += 1
    stale = [
        e for e in entries if (e["rule"], e["path"], e["scope"]) not in used
    ]
    return stale, n_suppressed
