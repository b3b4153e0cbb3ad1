"""Reader over the serving tier's spans (``serve/coalesce.py`` records
``serve:queue-wait``, ``serve:bounds`` and ``serve:gather-decode`` into
the submitter's trace, ``obs/span.py``), taken in the traced run.

Selector: ``{"span": name, "agg": "median" | "p99", "per": "batch" |
"request"}``.  The dispatcher records a batch's phases once per request
of the batch, with the same timestamps: ``per: batch`` counts each
distinct (start, end) once.  Seconds become milliseconds.
"""

from __future__ import annotations

import statistics


def read(h, state, samples, selector: dict):
    trace = h.evidence.get("tracer")
    if trace is None:
        return None
    spans = [s for s in trace.snapshot() if s.name == selector["span"]]
    if selector.get("per") == "batch":
        seconds = [e - s for s, e in {(s.t_start, s.t_end) for s in spans}]
    else:
        seconds = [s.seconds for s in spans]
    if not seconds:
        return None
    if selector["agg"] == "median":
        value = statistics.median(seconds)
    elif selector["agg"] == "p99":
        value = sorted(seconds)[min(len(seconds) - 1, int(0.99 * len(seconds)))]
    else:
        raise ValueError(f"serve_spans: unknown aggregation {selector['agg']!r}")
    return 1e3 * value
