"""``join.translate_row_gathers.mesh`` (PR 32) through
``readers/stage_extra.py`` with the metric's own selector: on hand-made
stage lists, and from two traced rehearsals of ``lookupjoin-mesh4`` (by
hand, like ``test_owner_search_rounds.py``):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider

The rehearsal's people table is 100,000 ids over 100,000 slots
(``conftest.py``): dense by every rule, one gather a row.  Refusing the
table leaves the sorted pair: ``bit_length(100,000) + 2 = 19`` rounds
(27 at the cell's 20M, what it ran until PR 32).
"""

from __future__ import annotations

import io
import json
import os
import sys
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]

import run  # noqa: E402
from readers import stage_extra  # noqa: E402

NAME = "join.translate_row_gathers.mesh"
METRIC = run.load_json("layer_metrics", f"{NAME}.json")
SEL = METRIC["selector"]


def stage(name, **extra):
    return SimpleNamespace(stage=name, seconds=0.1, extra=extra)


def harness(per_exec):
    return SimpleNamespace(evidence={"stages": per_exec})


def test_the_metric_reads_the_gathers_the_translate_stage_records():
    assert METRIC["reader"] == "stage_extra"
    assert METRIC["layer"] == "join kernels" and METRIC["moves"] == "rows_per_s.mesh"
    assert SEL == {"stages": ["join:translate"], "key": "row_gathers"}
    by_search = [
        stage("join:translate", row_gathers=27, tier="sorted"), stage("join:pack"),
        stage("join:all_to_all", search_rounds=0, owner_tier="positional"),
        stage("join:merge", row_gathers=3),  # not the named stage
    ]
    by_position = [stage("join:translate", row_gathers=1, tier="dense"), stage("join:merge", row_gathers=3)]
    assert stage_extra.read(harness([by_search] * 3), None, None, SEL) == 27
    assert stage_extra.read(harness([by_position] * 3), None, None, SEL) == 1
    # a composed probe runs no translate stage: nothing to read, not a zero
    composed = [[stage("join:probe", tier="direct-composed", row_gathers=1)]]
    assert stage_extra.read(harness(composed), None, None, SEL) is None
    assert stage_extra.read(SimpleNamespace(evidence={}), None, None, SEL) is None


@pytest.mark.parametrize("tier, gathers", [("dense", 1), ("sorted", (100_000).bit_length() + 2)])
def test_a_traced_rehearsal_reports_the_gathers_of_the_tier_that_ran(tier, gathers, monkeypatch):
    from csvplus_tpu.columnar.typed import IntColumn

    if tier == "sorted":
        monkeypatch.setattr(IntColumn, "_dense_admitted", staticmethod(lambda size, lo, hi: False))
    out = io.StringIO()
    rc = run.main(
        ["--workload", "lookupjoin-mesh4", "--seed", "2320000001", "--seconds", "1.5", "--trace", "1",
         "--rehearse-cpu", "--rehearse-rows", "200000"],
        out=out,
    )
    result = json.loads(out.getvalue().strip().splitlines()[-1])
    assert rc == 0 and result["correct"] is True
    assert result["metrics"][NAME] == {"value": gathers, "unit": "count/exec"}
    # the owner's side of the same idea, beside it
    assert result["metrics"]["join.owner_search_rounds"]["value"] == 0
