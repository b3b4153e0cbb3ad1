"""Reader over counts and facts: ``h.evidence["counters"]`` (the
server's ``snapshot()``) and ``h.evidence["facts"]`` (what the harness
and the driver noted: first execution, warm median, peak HBM bytes).

Selector: ``{"from": "counters" | "facts", "path": [keys...]}``.
"""

from __future__ import annotations


def read(h, state, samples, selector: dict):
    node = h.evidence.get(selector["from"], {})
    for key in selector["path"]:
        if not isinstance(node, dict) or key not in node:
            return None
        node = node[key]
    return node if isinstance(node, (int, float)) and node is not None else None
