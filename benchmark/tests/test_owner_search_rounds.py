"""``join.owner_search_rounds`` (PR 28) through ``readers/stage_extra.py``
with the metric's own selector, on hand-made stage lists (by hand, like
``test_stage_extra.py``):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider
"""

from __future__ import annotations

import os
import sys
from types import SimpleNamespace

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]

import run  # noqa: E402
from readers import stage_extra  # noqa: E402

METRIC = run.load_json("layer_metrics", "join.owner_search_rounds.json")
SEL = METRIC["selector"]


def stage(name, **extra):
    return SimpleNamespace(stage=name, seconds=0.1, extra=extra)


def harness(per_exec):
    return SimpleNamespace(evidence={"stages": per_exec})


def test_the_metric_reads_the_rounds_the_exchange_stage_records():
    assert METRIC["reader"] == "stage_extra" and METRIC["moves"] == "rows_per_s.mesh"
    assert SEL == {"stages": ["join:all_to_all"], "key": "search_rounds"}
    search = [
        stage("join:translate", row_gathers=27), stage("join:skew-detect", hot_keys=0),
        stage("join:all_to_all", capacity=4194304, retries=0, owner_tier="search", search_rounds=23),
        stage("join:partition", search_rounds=99),  # not the named stage
    ]
    positional = [
        stage("join:all_to_all", capacity=4194304, retries=0, owner_tier="positional", search_rounds=0),
    ]
    assert stage_extra.read(harness([search] * 3), None, None, SEL) == 23
    # no round is a reading, not an absence
    assert stage_extra.read(harness([positional] * 3), None, None, SEL) == 0


def test_a_program_from_before_the_counter_has_nothing_to_read():
    parent = [[stage("join:all_to_all", capacity=4194304, retries=0, slot_fill=0.298)]]
    assert stage_extra.read(harness(parent), None, None, SEL) is None
    assert stage_extra.read(SimpleNamespace(evidence={}), None, None, SEL) is None
