"""The selective star cell ``star3-selective-resident`` rehearsed on the
CPU: the join takes the unique-partial path with its compaction on the
device, every per-layer metric that lists the cell and that a CPU can
read is reported, the result is exact, the set-up refusal refuses a
host-tier program, and the cell's own control
(``control_selective.py``) is caught.  By hand, with the other tests of
this directory."""

from __future__ import annotations

import pytest

import reference as ref
import run
from control_selective import keep_an_unmatched_order
from test_benchmark import BENCHMARK, ROWS, rehearse

CELL = "star3-selective-resident"


def test_a_traced_rehearsal_reports_every_metric_that_lists_the_cell():
    cfg = run.load_json("configs", "orders-star-10m-selective.json")
    orders = cfg["tables"]["orders"]
    survivors = int(ROWS) * int(orders["segment_rows"]) // int(orders["rows"])
    seen = []
    for seed in (4_300_000_043, 43):
        rc, lines, result = rehearse(CELL, seed, trace=1)
        assert rc == 0 and result["correct"] is True and result["failed"] == 0
        listed = {m["name"]: m for m in BENCHMARK["per_layer"] if CELL in m["workloads"]}
        missing = set(listed) - set(result["metrics"])
        # no device plane on the CPU: only the device_trace metrics may be missing
        assert all(listed[m]["source"] == "device_trace" for m in missing), missing
        assert set(result["metrics"]) <= set(listed)
        m = {k: v["value"] for k, v in result["metrics"].items()}
        assert m["join.expand_rows_out"] == survivors
        assert m["join.expand_host_sync_elems"] == m["process.host_sync_elems"] == 3
        assert m["join.expand_host_s"] >= m["join.expand_host_self_s"] >= 0
        assert m["join.row_gathers.sel"] >= 9 + m["join.expand_row_gathers"]  # nine emitted lanes
        stages = next(ln for ln in lines if "first execution's stages" in ln)
        assert "'path': 'multiway-unique-partial'" in stages and "'tier': 'device'" in stages
        assert any(ln.startswith("check: host executor keeps ") for ln in lines)
        seen.append((m["join.expand_rows_out"], m["join.expand_row_gathers"], m["join.row_gathers.sel"]))
    assert seen[0] == seen[1]  # the configuration's, not the seed's


def test_the_least_bytes_are_the_configurations():
    cfg = run.load_json("configs", "orders-star-10m-selective.json")
    rows = int(cfg["tables"]["orders"]["rows"])
    least = {q: run.load_module("least_bytes", q).least_bytes(cfg, rows) for q in (
        "star3_selective", "star3_selective_emit", "star3_selective_compact",
    )}
    assert least["star3_selective"] == 4 * (2 * 10_000_000 + 9 * 1_000_000)
    assert least["star3_selective_emit"] == 4 * 2 * 9 * 1_000_000
    assert least["star3_selective_compact"] == 4 * (2 * 10_000_000 + 3 * 1_048_576)


def test_set_up_refuses_a_host_tier_program():
    query = run.load_module("queries", "star3_selective")
    device = ("join:expand", {"path": query.PATH, "tier": "device", "host_sync_elements": 3})
    query.refuse_host_tier([("join:probe", {}), device, ("join:merge", {"row_gathers": 9})])
    for stages in (
        [("join:expand", {"path": query.PATH})],  # a program from before the stage said where it ran
        [("join:expand", {"path": query.PATH, "tier": "host"})],
        [("join:expand", {"path": "multiway-unique-identity", "tier": "device"})],
        [device, ("join:probe", {"host_sync_elements": 10_000_000})],  # a lane read elsewhere in the join
        [],
    ):
        with pytest.raises(ref.Mismatch, match="refused in set-up"):
            query.refuse_host_tier(stages)


@pytest.mark.parametrize("nth", [None, 5])  # 5: the second execution of the window
def test_an_unmatched_order_kept_makes_the_run_incorrect(nth):
    rc, _, result = rehearse(CELL, 4_300_000_044, tamper=keep_an_unmatched_order(nth))
    assert rc == 0 and result["correct"] is False and result["failed"] >= 1
