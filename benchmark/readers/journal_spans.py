"""Reader over the program's process journal (``csvplus_tpu/obs/span.py``:
``tracer.journal``), which holds what the process did ONCE per object
before anyone opened a trace: the ``ingest``, ``index:build``,
``plan:admit``, ``plan:first-run``, ``serve:start`` milestones, the stages
and spans beneath them, and jax's compile events (``compile``).  Set-up is
most of what a run costs and the harness times it from outside
(``setup <phase>`` lines); this reads it from inside.

Only spans that START BEFORE THE WINDOW are read (``h.evidence["tracer"]``
is the window's trace; its ``t_anchor`` is the moment it opened): a traced
window's own work is in the window's trace, and what the check does after
it is not set-up.  A program without a journal (every commit before PR 41)
gives None for every selector; a program with one gives a number: where
no span matches, zero seconds were spent there (a whole-file ingest waits
on no scan, a process that met an empty compile cache loaded nothing).

Selector: ``{"span": name | [names], "what": ...}`` over the spans of
those names (with ``"prefer": true``, of the first of the names that any
span carries: a cell without a ``PlanCache`` has no ``plan:first-run``, and
its first ``index:build`` is its first execution), optionally only those
``"under"`` a root of that name, only those whose attributes match
``"where": {key: value | [values]}``, only the earliest (``"first": true``):

* ``sum_s``: the sum of their seconds.
* ``union_s``: the length of the union of their intervals (``compile``
  events nest: jax traces a body's primitives inside the program's own
  trace, so a sum would count those seconds twice).
* ``count``: how many.
* ``attr_sum``: the sum of ``"attr"`` over them.
* ``self_s``: the seconds beneath them that NO NAME explains.  With
  ``"children": [names]`` (pre-measured totals, which ``add_stage`` lays
  end to end at the moment they are recorded, so their intervals say
  nothing): the span's length less the seconds of the nearest descendants
  of those names.  Without: over the span and every span below it that
  has children, the length less the union of the children's intervals —
  a leaf is explained by its own name, and a ``compile`` event is an
  event, not structure (a stage with only compile events below it is
  still a leaf).

The first call also prints one line: how many spans the journal holds
before, in and after the window, what it dropped, and the inside beside
the harness's outside (Σ ``ingest`` against ``setup ingest``, the first
``plan:first-run`` against ``first execution``).
"""

from __future__ import annotations

from readers.span_tree import children_of, self_seconds

EVENT = "compile"  # events, not structure: they make no span an inner node


def journal_of():
    from csvplus_tpu.obs.span import tracer

    return getattr(tracer, "journal", None)


def union_seconds(intervals) -> float:
    total, end = 0.0, float("-inf")
    for lo, hi in sorted(intervals):
        lo = max(lo, end)
        if hi > lo:
            total += hi - lo
            end = hi
    return total


def unexplained_seconds(span, kids: dict) -> float:
    """Over *span* and every inner span below it: its self time
    (``span_tree.self_seconds``: length less the union of its children's
    intervals, clipped to it)."""
    total, stack = 0.0, [span]
    while stack:
        s = stack.pop()
        below = kids.get(s.span_id, [])
        if any(c.name != EVENT for c in below):  # else a leaf: its name explains it
            total += self_seconds(s, kids)
            stack.extend(below)
    return total


def named_below(span, kids: dict, names) -> float:
    """Seconds of the nearest descendants of *span* named in *names*."""
    total, stack = 0.0, list(kids.get(span.span_id, []))
    while stack:
        s = stack.pop()
        if s.name in names:
            total += s.seconds
        else:
            stack.extend(kids.get(s.span_id, []))
    return total


def select(spans, selector: dict) -> list:
    names = selector["span"]
    names = [names] if isinstance(names, str) else list(names)
    if selector.get("prefer"):  # the first of the names that any span carries
        names = next(([n] for n in names if any(s.name == n for s in spans)), names)
    found = [s for s in spans if s.name in names]
    under = selector.get("under")
    if under is not None:
        by_id = {s.span_id: s for s in spans}

        def root(s):
            while s.parent_id in by_id:
                s = by_id[s.parent_id]
            return s

        found = [s for s in found if root(s).name == under]
    for key, want in selector.get("where", {}).items():
        want = want if isinstance(want, list) else [want]
        found = [s for s in found if s.attrs.get(key) in want]
    found.sort(key=lambda s: s.t_start)
    return found[:1] if selector.get("first") else found


def before_the_window(h):
    """The journal's spans that start before the window's trace opened,
    or None where the program keeps no journal or the run is not traced."""
    journal, window = journal_of(), h.evidence.get("tracer")
    if journal is None or window is None:
        return None
    spans = journal.snapshot()
    if not h.evidence.get("journal_said"):
        h.evidence["journal_said"] = True
        _say(h, journal, spans, window)
    return [s for s in spans if s.t_start < window.t_anchor]


def _say(h, journal, spans, window) -> None:
    root = window.root()
    t_close = root.t_end if root is not None else float("inf")
    before = [s for s in spans if s.t_start < window.t_anchor]
    inside = sum(1 for s in spans if window.t_anchor <= s.t_start < t_close)
    ingest = sum(s.seconds for s in before if s.name == "ingest")
    firsts = sorted((s for s in before if s.name == "plan:first-run"), key=lambda s: s.t_start)
    names: dict = {}
    for s in before:
        got = names.setdefault(s.name, [0, 0.0])
        got[0] += 1
        got[1] += s.seconds
    h.say(
        f"journal: {len(before)} spans before the window, {inside} in it, "
        f"{len(spans) - len(before) - inside} after; dropped {journal.dropped}; "
        f"ingest inside {ingest:.2f}s, setup ingest {h.phases.get('ingest', float('nan')):.2f}s; "
        f"first plan:first-run {firsts[0].seconds if firsts else float('nan'):.2f}s, "
        f"first execution {h.evidence.get('facts', {}).get('first_exec_s', float('nan')):.2f}s"
    )
    h.say(
        "journal: before the window, by name (count, seconds): "
        + " ".join(f"{n}={c}/{sec:.3f}" for n, (c, sec) in sorted(names.items()))
    )


def read(h, state, samples, selector: dict):
    spans = before_the_window(h)
    if spans is None:
        return None
    found = select(spans, selector)
    what = selector["what"]
    if what == "sum_s":
        return float(sum(s.seconds for s in found))
    if what == "union_s":
        return union_seconds((s.t_start, s.t_end) for s in found)
    if what == "count":
        return len(found)
    if what == "attr_sum":
        values = [s.attrs.get(selector["attr"]) for s in found]
        return sum(v for v in values if isinstance(v, (int, float)) and not isinstance(v, bool))
    if what == "self_s":
        kids = children_of(spans)
        if "children" in selector:
            names = set(selector["children"])
            return sum(s.seconds - named_below(s, kids, names) for s in found)
        return sum(unexplained_seconds(s, kids) for s in found)
    raise ValueError(f"journal_spans: unknown selector {selector!r}")
