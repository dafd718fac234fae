"""Committed findings baseline, gated to never grow.

``scripts/raylint_baseline.json`` holds the finding ids that predate
the analyzer (debt) plus a ``budget`` — the maximum number of findings
the tree may carry. The gate enforces three things:

1. **No new findings**: every current finding must be baselined.
2. **No stale entries**: every baseline entry must still fire — a fixed
   finding must be *removed* from the baseline in the same PR (that is
   what makes the baseline monotonically shrink instead of rotting).
3. **Budget**: ``len(findings) <= budget`` and ``budget ==
   len(baseline)`` — growing the baseline requires raising the budget,
   which check 3 turns into an explicit, reviewable diff on two counts
   that only ever go down together (the committed record is the
   ratchet).
"""

from __future__ import annotations

import json
import os
from typing import Dict, List

BASELINE_VERSION = 1


def load(path: str) -> Dict:
    if not os.path.exists(path):
        return {"version": BASELINE_VERSION, "budget": 0, "findings": []}
    with open(path, "r", encoding="utf-8") as f:
        data = json.load(f)
    data.setdefault("budget", len(data.get("findings", [])))
    data.setdefault("findings", [])
    return data


def save(path: str, finding_ids: List[str]) -> Dict:
    data = {
        "version": BASELINE_VERSION,
        "budget": len(finding_ids),
        "findings": sorted(finding_ids),
    }
    with open(path, "w", encoding="utf-8") as f:
        json.dump(data, f, indent=1)
        f.write("\n")
    return data


def compare(current_ids: List[str], baseline: Dict):
    """Returns (new_ids, stale_ids, budget_exceeded)."""
    base = set(baseline.get("findings", []))
    cur = set(current_ids)
    new = sorted(cur - base)
    stale = sorted(base - cur)
    budget = int(baseline.get("budget", len(base)))
    return new, stale, len(cur) > budget
