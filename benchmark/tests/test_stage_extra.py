"""``readers/stage_extra.py`` on a hand-made stage list (by hand, like
``test_span_tree.py``):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider
"""

from __future__ import annotations

import os
import sys
from types import SimpleNamespace

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]

from readers import stage_extra  # noqa: E402

SEL = {"stages": ["join:translate", "join:probe", "join:merge"], "key": "row_gathers"}


def stage(name, **extra):
    return SimpleNamespace(stage=name, seconds=0.1, extra=extra)


def harness(per_exec):
    return SimpleNamespace(evidence={"stages": per_exec})


def test_the_count_is_summed_over_the_named_stages_and_averaged_over_executions():
    staged = [
        stage("join:translate", row_gathers=1), stage("join:pack"),
        stage("join:probe", tier="direct", row_gathers=2),
        stage("join:translate", row_gathers=1), stage("join:probe", tier="direct", row_gathers=2),
        stage("join:expand", row_gathers=99),  # not a named stage
        stage("join:merge", row_gathers=6), stage("MultiwayJoin"),
    ]
    composed = [
        stage("join:probe", tier="direct-composed", row_gathers=0),
        stage("join:probe", tier="direct-composed", row_gathers=0),
        stage("join:merge", row_gathers=5),
    ]
    assert stage_extra.read(harness([staged]), None, None, SEL) == 12
    assert stage_extra.read(harness([composed] * 3), None, None, SEL) == 5
    assert stage_extra.read(harness([staged, composed]), None, None, SEL) == 8.5


def test_a_program_without_the_key_has_nothing_to_read():
    parent = [[stage("join:translate"), stage("join:probe", tier="direct"), stage("join:merge")]]
    assert stage_extra.read(harness(parent), None, None, SEL) is None
    assert stage_extra.read(harness([]), None, None, SEL) is None
    assert stage_extra.read(SimpleNamespace(evidence={}), None, None, SEL) is None
    # a count of zero is a reading, not an absence
    zero = [[stage("join:probe", row_gathers=0), stage("join:merge", row_gathers=0)]]
    assert stage_extra.read(harness(zero), None, None, SEL) == 0
