"""``readers/span_tree.py`` and ``readers/stage_self.py`` on hand-built
evidence (by hand, like ``test_benchmark.py``):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider
"""

from __future__ import annotations

import os
import sys
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]

from readers import span_tree, stage_self  # noqa: E402

from csvplus_tpu.obs.span import Span  # noqa: E402


def span(i, parent, name, t0, t1, **attrs):
    return Span(1, i, parent, name, t0, t1, "dispatch", attrs)


def harness(spans):
    trace = SimpleNamespace(snapshot=lambda: list(spans))
    return SimpleNamespace(evidence={"tracer": trace})


# one 10 ms cycle: children cover [0,1) [1,4) [5,9) and an overlapping
# [8,9.5); 4-5 and 9.5-10 are the cycle's own: 1.5 ms unattributed.
# bounds' children cover 2 of its 3 ms.
CYCLE = [
    span(1, 0, "serve:cycle", 0.000, 0.010, batch=32),
    span(2, 1, "serve:sweep", 0.000, 0.001),
    span(3, 1, "serve:bounds", 0.001, 0.004),
    span(4, 3, "serve:bounds:encode", 0.001, 0.002),
    span(5, 3, "serve:bounds:search", 0.002, 0.003, host_syncs=1, elements=64),
    span(6, 1, "serve:gather-decode", 0.005, 0.009),
    span(7, 6, "serve:gather:readback", 0.006, 0.007, host_syncs=3, elements=96),
    span(8, 1, "serve:scatter", 0.008, 0.0095),
    span(9, 0, "serve:queue-wait", 0.0, 0.02),  # not under the cycle
]
# the same cycle copied under a second parent (another request's tree)
COPY = [span(s.span_id + 100, (s.parent_id or 50) + 100, s.name, s.t_start, s.t_end, **s.attrs) for s in CYCLE[:8]]
# a second, shorter cycle with nothing below it
SECOND = [span(30, 0, "serve:cycle", 0.020, 0.022, batch=1)]


def test_self_time_is_the_span_less_the_union_of_its_children():
    kids = span_tree.children_of(CYCLE)
    assert span_tree.self_seconds(CYCLE[0], kids) == pytest.approx(0.0015)
    assert span_tree.self_seconds(CYCLE[2], kids) == pytest.approx(0.001)
    assert span_tree.self_seconds(CYCLE[1], kids) == pytest.approx(0.001)  # a leaf is all its own


def test_a_cycle_seen_through_several_parents_counts_once():
    sel = {"span": "serve:cycle", "what": "self_ms", "agg": "median"}
    assert span_tree.read(harness(CYCLE), None, None, sel) == pytest.approx(1.5)
    assert span_tree.read(harness(CYCLE + COPY), None, None, sel) == pytest.approx(1.5)
    both = span_tree.read(harness(CYCLE + COPY + SECOND), None, None, dict(sel, agg="mean"))
    assert both == pytest.approx((1.5 + 2.0) / 2)


def test_attribute_sums_run_over_everything_below_the_span():
    sel = {"span": "serve:cycle", "what": "attr_sum", "attr": "host_syncs", "agg": "mean"}
    assert span_tree.read(harness(CYCLE), None, None, sel) == 4
    assert span_tree.read(harness(CYCLE + SECOND), None, None, sel) == 2  # (4 + 0) / 2
    assert span_tree.read(harness(CYCLE), None, None, dict(sel, attr="elements")) == 160
    assert span_tree.read(harness(CYCLE), None, None, dict(sel, span="serve:bounds")) == 1


def test_nothing_to_read_gives_none_and_an_unknown_selector_raises():
    sel = {"span": "serve:cycle", "what": "self_ms", "agg": "median"}
    assert span_tree.read(harness(CYCLE[8:]), None, None, sel) is None  # a program without the span
    assert span_tree.read(SimpleNamespace(evidence={}), None, None, sel) is None
    with pytest.raises(ValueError):
        span_tree.read(harness(CYCLE), None, None, dict(sel, what="p99"))
    with pytest.raises(ValueError):
        span_tree.read(harness(CYCLE), None, None, dict(sel, agg="max"))


def stage(name, seconds, **extra):
    return SimpleNamespace(stage=name, seconds=seconds, extra=extra)


def test_host_self_seconds_are_stage_seconds_less_the_wait():
    per_exec = [
        [stage("join:probe", 0.15, synced=True, wait_s=0.148), stage("join:pack", 0.002),
         stage("MultiwayJoin", 0.8)],
        [stage("join:probe", 0.17, synced=True, wait_s=0.166), stage("join:pack", 0.004)],
    ]
    h = SimpleNamespace(evidence={"stages": per_exec})
    sel = {"stages": ["join:probe", "join:pack"]}
    assert stage_self.read(h, None, None, sel) == pytest.approx((0.004 + 0.008) / 2)
    # a program that records no wait has nothing to read; so has a CPU of stages
    old = [[stage("join:probe", 0.15), stage("join:pack", 0.002)]]
    assert stage_self.read(SimpleNamespace(evidence={"stages": old}), None, None, sel) is None
    assert stage_self.read(SimpleNamespace(evidence={}), None, None, sel) is None
