"""The one-to-many cell ``statements-fanout-resident`` rehearsed on the
CPU: the first join takes the ``fan-out`` path with its expansion on the
device and the second the identity path, the result is exact on two
seeds with the configuration's shapes, the per-layer metric that lists
the cell is reported, the set-up refusal refuses a
host-tier program and a program that expands nothing, and the cell's own
control (``control_fanout.py``) is caught.  By hand, with the other tests
of this directory."""

from __future__ import annotations

import re

import numpy as np
import pytest

import reference as ref
import run
from control_fanout import swap_two_orders_of_a_customer
from test_benchmark import BENCHMARK, ROWS, rehearse

CELL = "statements-fanout-resident"


def test_a_traced_rehearsal_takes_the_fan_out_path_on_the_device():
    cfg = run.load_json("configs", "orders-by-customer-10m.json")
    people = int(cfg["tables"]["people"]["rows"])
    padded = 1 << (int(ROWS) - 1).bit_length()
    seen = []
    for seed in (4_500_000_045, 45):
        rc, lines, result = rehearse(CELL, seed, trace=1)
        assert rc == 0 and result["correct"] is True and result["failed"] == 0
        listed = {m["name"]: m for m in BENCHMARK["per_layer"] if CELL in m["workloads"]}
        assert set(result["metrics"]) == set(listed) == {"join.expand_host_s.fan"}
        assert result["metrics"]["join.expand_host_s.fan"]["value"] > 0
        stages = next(ln for ln in lines if "first execution's stages" in ln)
        expands = re.findall(r"join:expand(\{.*?\})", stages)
        assert len(expands) == 2
        fan, identity = expands
        for said in ("'path': 'fan-out'", "'tier': 'device'", "'form': 'prefix-scatter'",
                     f"'probes': {people}", f"'emitted': {ROWS}", f"'padded': {padded}",
                     "'host_sync_elements': 2"):
            assert said in fan, (said, fan)
        assert "'path': 'unique-identity'" in identity and "'tier': 'device'" in identity
        merges = re.findall(r"join:merge(\{.*?\})", stages)
        assert "'build_gathers': 4, 'stream_gathers': 3" in merges[0]
        assert "'build_gathers': 2, 'stream_gathers': 0" in merges[1]
        assert any(ln.startswith("check: host executor equals the generator") for ln in lines)
        seen.append(int(re.search(r"'max_run': (\d+)", fan).group(1)))
    assert all(m > 1 for m in seen)  # the longest run is the seed's; every other shape above is not


def test_every_customer_places_an_order_at_any_size():
    """``gen/orders.py`` deals every customer once before the uniform
    draw: the result has one row per order and no customer is absent."""
    cfg = run.load_json("configs", "orders-by-customer-10m.json")
    gen = run.load_module("gen", cfg["gen"])
    for seed in (45, 4_500_000_045):
        d = gen.Data(cfg, seed, "/nonexistent", files=(), rows=int(ROWS))
        assert np.unique(d.cust).size == int(cfg["tables"]["people"]["rows"])


def test_set_up_refuses_a_host_tier_program():
    query = run.load_module("queries", "statements")
    fan = ("join:expand", {"path": "fan-out", "tier": "device", "host_sync_elements": 2})
    one = ("join:expand", {"path": "unique-identity", "tier": "device", "host_sync_elements": 2})
    query.refuse_host_tier([("join:probe", {}), fan, ("join:merge", {"row_gathers": 7}), one])
    for stages in (
        [("join:expand", {"path": "fan-out"}), one],  # a program from before the stage said where it ran
        [("join:expand", {"path": "host-expand", "tier": "host"}), one],
        [("join:expand", {"path": "fan-out", "tier": "host"}), one],
        [one, one],  # nothing expanded: not this cell's join
        [one, fan],
        [fan],
        [("join:expand", {"path": "multiway-fan-out", "tier": "device"})],  # one fused pass
        [fan, one, ("join:merge", {"host_sync_elements": 10_000_000})],  # a lane read elsewhere in the join
        [],
    ):
        with pytest.raises(ref.Mismatch, match="refused in set-up"):
            query.refuse_host_tier(stages)


@pytest.mark.parametrize("nth", [None, 5])  # 5: the second execution of the window
def test_two_orders_swapped_inside_a_group_make_the_run_incorrect(nth):
    rc, _, result = rehearse(CELL, 4_500_000_046, tamper=swap_two_orders_of_a_customer(nth))
    assert rc == 0 and result["correct"] is False and result["failed"] >= 1
