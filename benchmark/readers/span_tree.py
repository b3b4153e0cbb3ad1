"""Reader over the span TREE of the traced window (``obs/span.py``):
what ``serve_spans`` cannot say because it looks at one name at a time.

A span's self time is its length less the union of its children's
intervals (clipped to it): the part of it that no child explains.  The
serving dispatcher runs each cycle as one ``serve:cycle`` span with its
phases as children and copies that subtree into every traced request's
tree; the same cycle seen through several parents is counted once (by
its start and end).

Selector: ``{"span": name, "what": "self_ms", "agg": "median" |
"mean"}`` gives the self time in milliseconds over the spans of that
name; ``{"span": name, "what": "attr_sum", "attr": key, "agg": ...}``
gives, per span of that name, the sum of the attribute over the span
and everything below it (``host_syncs``: the blocking device-to-host
reads of one cycle).  None where the trace has no span of that name.
"""

from __future__ import annotations

import statistics


def children_of(spans) -> dict:
    kids: dict = {}
    for s in spans:
        kids.setdefault(s.parent_id, []).append(s)
    return kids


def self_seconds(span, kids: dict) -> float:
    covered, end = 0.0, span.t_start
    for c in sorted(kids.get(span.span_id, []), key=lambda c: c.t_start):
        lo, hi = max(c.t_start, end), min(c.t_end, span.t_end)
        if hi > lo:
            covered += hi - lo
            end = hi
    return span.seconds - covered


def attr_sum(span, kids: dict, attr: str) -> float:
    total, stack = 0.0, [span]
    while stack:
        s = stack.pop()
        v = s.attrs.get(attr)
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            total += v
        stack.extend(kids.get(s.span_id, []))
    return total


def read(h, state, samples, selector: dict):
    trace = h.evidence.get("tracer")
    if trace is None:
        return None
    spans = trace.snapshot()
    kids = children_of(spans)
    once = {(s.t_start, s.t_end): s for s in spans if s.name == selector["span"]}
    if not once:
        return None
    if selector["what"] == "self_ms":
        values = [1e3 * self_seconds(s, kids) for s in once.values()]
    elif selector["what"] == "attr_sum":
        values = [attr_sum(s, kids, selector["attr"]) for s in once.values()]
    else:
        raise ValueError(f"span_tree: unknown selector {selector!r}")
    if selector["agg"] == "median":
        return statistics.median(values)
    if selector["agg"] == "mean":
        return sum(values) / len(values)
    raise ValueError(f"span_tree: unknown aggregation {selector['agg']!r}")
