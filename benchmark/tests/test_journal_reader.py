"""``readers/journal_spans.py`` on a recorded journal
(``fixtures/journal_small.json``: the journal of one CPU rehearsal of
``star3-resident``, with the window's bounds and the metrics that run
printed) and on hand-built trees (by hand, like ``test_benchmark.py``):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider
"""

from __future__ import annotations

import glob
import json
import os
import sys
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]

from readers import journal_spans  # noqa: E402

from csvplus_tpu.obs.span import Span  # noqa: E402

with open(os.path.join(BENCH, "fixtures", "journal_small.json")) as f:
    RECORDED = json.load(f)

METRICS = {}
for path in sorted(glob.glob(os.path.join(BENCH, "layer_metrics", "*.json"))):
    with open(path) as f:
        m = json.load(f)
    if m["reader"] == "journal_spans":
        METRICS[os.path.basename(path)[: -len(".json")]] = m


def span(i, parent, name, t0, t1, **attrs):
    return Span(1, i, parent, name, t0, t1, "main", attrs)


def harness(monkeypatch, spans, t_anchor=100.0, t_end=120.0, dropped=0, phases=None):
    """A harness whose window opened at *t_anchor*, over a journal that
    holds *spans*; what it says is kept."""
    journal = SimpleNamespace(snapshot=lambda: list(spans), dropped=dropped)
    monkeypatch.setattr(journal_spans, "journal_of", lambda: journal)
    window = SimpleNamespace(
        t_anchor=t_anchor, root=lambda: SimpleNamespace(t_end=t_end)
    )
    said = []
    return SimpleNamespace(
        evidence={"tracer": window, "facts": {"first_exec_s": 9.0}},
        phases=phases or {"ingest": 5.5}, say=said.append, said=said,
    )


def recorded(monkeypatch):
    spans = [Span(1, i, p, n, t0, t1, lane, attrs) for i, p, n, t0, t1, lane, attrs in RECORDED["spans"]]
    return harness(
        monkeypatch, spans, RECORDED["window_t_anchor"], RECORDED["window_t_end"]
    )


@pytest.mark.parametrize("name", sorted(METRICS))
def test_every_metric_reads_the_recorded_journal_as_that_run_did(name, monkeypatch):
    """Each ``layer_metrics`` file of this reader, on the recorded
    journal: the value the recording run printed for the metrics that
    list its cell, and for the others a number or None, never a raise."""
    m = METRICS[name]
    assert m["moves"] == "setup_s" and m["source"] in ("program_span", "program_counter")
    value = journal_spans.read(recorded(monkeypatch), None, None, m["selector"])
    want = RECORDED["metrics_of_that_run"].get(name)
    if want is not None:
        assert value == pytest.approx(want, rel=1e-3, abs=1e-4)
    else:
        assert value >= 0  # a journal that holds no such span: zero, not None


# a streamed ingest as the program records it: the tier's stage under the
# milestone, the pre-measured totals laid end to end at its close (their
# intervals overlap: only their seconds mean anything), the union inside
# the sharded assembly, worker totals from another lane; then a first run
# with a compile event in a leaf and a gap no name explains
TREE = [
    span(1, None, "ingest", 0.0, 10.0, tier="streamed", rows=100),
    span(2, 1, "ingest:streamed", 0.1, 9.9),
    span(3, 2, "ingest:scan", 3.0, 9.0, workers=1),        # 6.0 s
    span(4, 2, "ingest:place", 7.0, 9.0),                  # 2.0 s
    span(5, 2, "ingest:dictionary", 8.5, 9.0),             # 0.5 s, inside place
    span(6, 2, "ingest:shard-assemble", 9.0, 9.8),         # 0.8 s
    span(7, 6, "ingest:union", 9.5, 9.8),                  # 0.3 s, inside the assembly
    span(8, 2, "ingest:encode", 2.0, 9.0, workers=4),      # a worker lane's sum: no part of the wall
    span(20, None, "ingest", 10.0, 11.0, tier="native-encoded"),
    span(21, 20, "ingest:native-encoded", 10.0, 11.0),
    span(30, None, "plan:admit", 20.0, 21.0),
    span(31, None, "plan:first-run", 21.0, 31.0),
    span(32, 31, "plan:execute", 21.0, 30.5),
    span(33, 32, "Join", 21.0, 30.0),
    span(34, 33, "typed:demote", 21.0, 25.0),
    span(35, 33, "join:probe", 26.0, 30.0),                # 25-26: the Join's own
    span(36, 35, "compile", 26.5, 27.5, kind="backend_compile", fun_name="jit_csvplus.join.probe", cache="hit"),
    span(37, 35, "compile", 26.6, 27.4, kind="cache_retrieval"),
    span(38, 35, "compile", 26.0, 26.4, kind="jaxpr_trace", fun_name="probe"),
    span(39, 35, "compile", 26.1, 26.3, kind="jaxpr_trace", fun_name="take"),  # nested in 38
    span(40, None, "plan:first-run", 40.0, 41.0),          # a second shape's
    span(50, None, "compile", 50.0, 50.5, kind="backend_compile", fun_name="jit_iota"),
    span(60, None, "index:build", 130.0, 131.0),           # after the window opened
    span(61, None, "compile", 119.0, 119.5, kind="backend_compile"),  # inside it
]


def read(monkeypatch, selector, **kw):
    return journal_spans.read(harness(monkeypatch, TREE, **kw), None, None, selector)


def test_sums_run_over_the_spans_that_start_before_the_window(monkeypatch):
    assert read(monkeypatch, {"span": "ingest", "what": "sum_s"}) == pytest.approx(11.0)
    assert read(monkeypatch, {"span": "index:build", "what": "sum_s"}) == 0.0  # after the window opened
    assert read(monkeypatch, {"span": "index:build", "what": "sum_s"}, t_anchor=200.0) == pytest.approx(1.0)
    both = {"span": ["ingest:dictionary", "ingest:union"], "what": "sum_s", "under": "ingest"}
    assert read(monkeypatch, both) == pytest.approx(0.8)
    assert read(monkeypatch, dict(both, under="plan:first-run")) == 0.0
    assert read(monkeypatch, {"span": "ingest", "what": "attr_sum", "attr": "rows"}) == 100


def test_pre_measured_totals_are_taken_off_by_their_seconds_not_their_intervals(monkeypatch):
    totals = ["ingest:scan", "ingest:place", "ingest:seal", "ingest:shard-assemble", "ingest:union"]
    sel = {"span": "ingest", "what": "self_s", "children": totals}
    # 10 - (6 + 2 + 0.8) = 1.2 (the union inside the assembly counts once,
    # the dictionary is inside place, the workers' sum is no part of the
    # wall), and the whole-file ingest has no total beneath it: 1.0
    assert read(monkeypatch, sel) == pytest.approx(1.2 + 1.0)
    assert read(monkeypatch, dict(sel, where={"tier": "streamed"})) == pytest.approx(1.2)


def test_unexplained_seconds_are_what_no_name_covers(monkeypatch):
    sel = {"span": ["plan:first-run", "index:build"], "prefer": True, "what": "self_s", "first": True}
    # first-run 31.0-30.5, execute 30.5-30.0, the Join's 25-26: 2.0 s; the
    # probe keeps its name though compile events lie in it
    assert read(monkeypatch, sel) == pytest.approx(2.0)
    assert read(monkeypatch, dict(sel, first=False)) == pytest.approx(2.0)  # the second is a leaf
    kids = journal_spans.children_of(TREE)
    assert journal_spans.unexplained_seconds(TREE[15], kids) == 0.0  # join:probe: a leaf
    # a cell without a PlanCache: its first index:build is its first execution
    build = [span(1, None, "index:build", 0.0, 5.0), span(2, 1, "index:sort", 0.0, 4.0),
             span(3, 2, "typed:demote", 0.5, 3.0), span(4, None, "index:build", 6.0, 7.0)]
    h = harness(monkeypatch, build)
    assert journal_spans.read(h, None, None, sel) == pytest.approx(1.0 + 1.5)


def test_compile_events_by_kind_nested_ones_once(monkeypatch):
    path = {"span": "compile", "what": "union_s",
            "where": {"kind": ["jaxpr_trace", "jaxpr_to_mlir_module", "backend_compile"]}}
    # 26.0-26.4 (the nested trace inside it) + 26.5-27.5 + 50.0-50.5
    assert read(monkeypatch, path) == pytest.approx(0.4 + 1.0 + 0.5)
    assert read(monkeypatch, dict(path, what="sum_s")) == pytest.approx(0.4 + 0.2 + 1.0 + 0.5)
    load = {"span": "compile", "what": "sum_s", "where": {"kind": "cache_retrieval"}}
    assert read(monkeypatch, load) == pytest.approx(0.8)
    programs = {"span": "compile", "what": "count", "where": {"kind": "backend_compile"}}
    assert read(monkeypatch, programs) == 2
    assert read(monkeypatch, dict(programs, where={"cache": "miss"})) == 0


def test_the_first_call_says_what_the_journal_holds_once(monkeypatch):
    h = harness(monkeypatch, TREE, dropped=3)
    journal_spans.read(h, None, None, {"span": "ingest", "what": "sum_s"})
    journal_spans.read(h, None, None, {"span": "ingest", "what": "count"})
    assert len(h.said) == 2
    assert h.said[0].startswith(f"journal: {len(TREE) - 2} spans before the window, 1 in it, 1 after; dropped 3")
    assert "ingest inside 11.00s, setup ingest 5.50s" in h.said[0]
    assert "first plan:first-run 10.00s, first execution 9.00s" in h.said[0]
    assert "typed:demote=1/4.000" in h.said[1]


def test_a_program_without_a_journal_gives_none_and_an_unknown_selector_raises(monkeypatch):
    sel = {"span": "ingest", "what": "sum_s"}
    h = harness(monkeypatch, TREE)
    monkeypatch.setattr(journal_spans, "journal_of", lambda: None)  # the parent's tracer
    assert journal_spans.read(h, None, None, sel) is None and h.said == []
    untraced = harness(monkeypatch, TREE)
    untraced.evidence.pop("tracer")
    assert journal_spans.read(untraced, None, None, sel) is None
    with pytest.raises(ValueError):
        journal_spans.read(harness(monkeypatch, TREE), None, None, dict(sel, what="median_s"))


def test_the_real_tracer_has_the_journal_the_reader_looks_for():
    from csvplus_tpu.obs.span import tracer

    assert journal_spans.journal_of() is tracer.journal
