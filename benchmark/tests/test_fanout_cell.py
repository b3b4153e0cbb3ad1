"""The one-to-many cell ``statements-fanout-resident`` rehearsed on the
CPU: the first join takes the ``fan-out`` path with its expansion on the
device and the second the identity path, the result is exact on two
seeds with the configuration's shapes, every per-layer metric that lists
the cell and that a CPU can read is reported, the set-up refusal refuses a
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
        missing = set(listed) - set(result["metrics"])
        # no device plane on the CPU: only the device_trace metrics may be missing
        assert all(listed[m]["source"] == "device_trace" for m in missing), missing
        assert set(result["metrics"]) <= set(listed) and len(listed) >= 24
        m = {k: v["value"] for k, v in result["metrics"].items()}
        assert m["join.expand_host_s"] >= m["join.expand_host_self_s"] >= 0
        assert m["join.expand_rows_out"] == 2 * int(ROWS)  # the fan-out's rows, then the identity path's
        assert m["join.expand_padded"] == padded  # the fan-out's slots; the identity path pads nothing
        assert m["join.expand_row_gathers"] == 2
        assert m["join.expand_host_sync_elems"] == m["process.host_sync_elems"] == 4  # two stats reads of two scalars
        assert m["join.row_gathers"] >= 7 + 2  # seven lanes emitted by the first join, two by the second
        assert m["join.run_copy_lanes"] == m["join.vmem_gather_lanes"] == 0  # both kernels engage on a TPU only
        assert m["exec.plan_nodes_host_s"] >= m["join.stages_host_s"] > 0
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
        assert m["join.expand_max_run"] == seen[-1]
    assert all(m > 1 for m in seen)  # the longest run is the seed's; every other shape above is not


def test_the_least_bytes_are_the_configurations():
    """The whole query: every table lane read once, nine result lanes
    written once.  The emit: the probes' answers, every source lane but
    stock's key, the nine result lanes — and no term for the ids the
    expansion materialises (2 x 16,777,216 today)."""
    cfg = run.load_json("configs", "orders-by-customer-10m.json")
    rows = int(cfg["tables"]["orders"]["rows"])
    least = {q: run.load_module("least_bytes", q).least_bytes(cfg, rows) for q in ("statements", "statements_emit")}
    assert least["statements"] == 4 * (4 * rows + 3 * 100_000 + 3 * 1_000 + 9 * rows) == 521_212_000
    assert least["statements_emit"] == 4 * (2 * 100_000 + 4 * rows + 3 * 100_000 + 2 * 1_000 + 9 * rows) == 522_008_000
    named = run.load_json("workloads", f"{CELL}.json")["least_bytes"]
    assert named == {"device.bytes_roofline_pct": "statements", "kernel.join_emit_roofline_pct": "statements_emit"}


def test_the_device_metrics_select_the_cells_programs_and_least_bytes():
    """The kernel readers over a hand-made reduction of three executions
    (PR 47's split): the expansion's seconds are ``csvplus.join.expand``
    with its cut, the emit's are every ``gather*`` and ``expand*`` program,
    and both shares stand on this cell's own least bytes, under 100%."""
    from types import SimpleNamespace

    from readers import device_trace, kernel_trace

    kernels = {"csvplus.join.expand": 0.3711, "csvplus.join.expand_head": 0.0024, "csvplus.join.gather_cols": 0.3105,
               "csvplus.join.gather_runs": 0.0258, "csvplus.join.probe_stats": 0.006, "jit__take": 0.1461}
    busy = sum(kernels.values())
    red = {"kernels": kernels, "calls": {}, "busy_s": busy, "cycles": 0, "unnamed_s": kernels["jit__take"]}
    cfg = run.load_json("configs", "orders-by-customer-10m.json")
    h = SimpleNamespace(
        evidence={"kernel_trace": red, "facts": {"executions": 3}, "peaks": {"hbm_bytes_per_s": 819e9},
                  "trace": {"busy_s": busy, "window_s": busy / 0.94}},
        cfg=cfg, data=SimpleNamespace(n=10_000_000), load_module=run.load_module,
    )
    listed = {m["name"]: m for m in run.layer_metrics_for(run.load_cell(CELL))}

    def read(name):
        reader = {"kernel_trace": kernel_trace, "device_trace": device_trace}[listed[name]["reader"]]
        return reader.read(h, None, None, listed[name]["selector"])

    assert read("kernel.join_expand_device_s") == pytest.approx((0.3711 + 0.0024) / 3)
    emit = (0.3711 + 0.0024 + 0.3105 + 0.0258) / 3
    assert read("kernel.join_emit_device_s") == pytest.approx(emit)
    assert read("kernel.join_probe_device_s") == pytest.approx(0.002)
    assert read("kernel.join_emit_roofline_pct") == pytest.approx(100 * (522_008_000 / 819e9) / emit)
    assert read("device.bytes_roofline_pct") == pytest.approx(100 * (521_212_000 / 819e9) / (busy / 3))
    assert read("device.unnamed_busy_pct.batch") == pytest.approx(100 * 0.1461 / busy)
    assert read("device.idle_pct.batch") == pytest.approx(6.0)
    assert 0 < read("device.bytes_roofline_pct") < read("kernel.join_emit_roofline_pct") < 100


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
