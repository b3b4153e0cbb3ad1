"""``readers/kernel_trace.py``: by hand, and on a small trace recorded
from the chip with the program's kernel names in it
(``fixtures/trace_named_small.json``).

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider
"""

from __future__ import annotations

import json
import os
import sys
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH]

from readers import kernel_trace as kt  # noqa: E402

P = "/device:TPU:0"


def test_a_module_event_names_its_kernel():
    assert kt.kernel_of("jit_csvplus.join.probe_i32(8698865864679392361)") == "csvplus.join.probe_i32"
    assert kt.kernel_of("jit__take(10612267780521185614)") == "_take"
    assert kt.kernel_of("jit_csvplus.table.gather_take") == "csvplus.table.gather_take"


def test_kernel_seconds_by_hand():
    """Three programs in a 100 ns window; a while's body ops nest inside
    it (union, not sum); the digest's stretch is outside; an op belongs
    to the module that covers its midpoint."""
    ops = {P: [
        ("%while", 10, 20), ("%body.1", 12, 5), ("%body.2", 20, 8),  # probe: union 20
        ("%fusion", 40, 10),  # eager take: 10, unnamed
        ("%digest", 55, 5),  # the benchmark's own
        ("%gather", 70, 10), ("%copy", 85, 5),  # emit: 15
        ("%late", 98, 10),  # clipped to the window: 2, in no module
        ("%before", 0, 5),  # outside the window
    ]}
    modules = {P: [
        ("jit_csvplus.join.probe_i32(1)", 9, 22), ("jit__take(2)", 39, 12),
        ("jit_digest(3)", 54, 7), ("jit_csvplus.join.gather_multiway(4)", 69, 22),
    ]}
    host = [("bench:window", 5, 95), ("bench:digest", 50, 15),
            ("csvplus:serve:cycle", 6, 30), ("csvplus:serve:cycle", 40, 9), ("csvplus:serve:cycle", 52, 5),
            ("csvplus:serve:cycle", 1, 3)]
    red = kt.reduce_kernels(ops, modules, host)
    ns = {k: v * 1e9 for k, v in red["kernels"].items()}
    assert ns == {
        "csvplus.join.probe_i32": pytest.approx(20), "_take": pytest.approx(10),
        "csvplus.join.gather_multiway": pytest.approx(15), "(no module)": pytest.approx(2),
    }
    assert red["busy_s"] * 1e9 == pytest.approx(47)
    assert red["unnamed_s"] * 1e9 == pytest.approx(12)
    assert red["cycles"] == 2  # those that start in the counted stretches
    assert red["calls"] == {"csvplus.join.probe_i32": 1, "_take": 1, "csvplus.join.gather_multiway": 1}
    assert kt.reduce_kernels(ops, {}, host) is None  # no modules line
    assert kt.reduce_kernels(ops, modules, [("bench:other", 0, 100)]) is None  # no window


def harness(red, executions=None, **more):
    return SimpleNamespace(
        evidence={"kernel_trace": red, "facts": {"executions": executions},
                  "peaks": {"hbm_bytes_per_s": 819e9}},
        cfg={"tables": {"people": {"columns": ["id", "name", "surname"], "rows": 100_000},
                        "stock": {"columns": ["prod_id", "product", "price"], "rows": 1_000}}},
        data=SimpleNamespace(n=10_000_000),
        load_module=lambda kind, name: __import__("run").load_module(kind, name), **more,
    )


def test_selectors_and_what_a_parent_without_names_gives():
    red = {"kernels": {"csvplus.join.probe_i32": 0.3, "csvplus.join.probe_direct": 0.3,
                       "csvplus.join.gather_multiway": 1.2, "iota": 0.06},
           "calls": {}, "busy_s": 1.86, "unnamed_s": 0.06, "cycles": 4}
    h = harness(red, executions=3)
    probe = {"what": "kernel_s", "kernels": ["csvplus.join.probe"], "per": "execution"}
    assert kt.read(h, None, None, probe) == pytest.approx(0.2)
    per_cycle = dict(probe, per="cycle", scale=1000)
    assert kt.read(h, None, None, per_cycle) == pytest.approx(150.0)
    assert kt.read(h, None, None, {"what": "unnamed_busy_pct"}) == pytest.approx(100 * 0.06 / 1.86)
    assert kt.read(h, None, None, dict(probe, kernels=["csvplus.serve."])) is None  # no such kernel ran
    roof = {"what": "kernel_roofline_pct", "kernels": ["csvplus.join.gather"], "per": "execution",
            "least_bytes": "star3_emit"}
    least = 4 * ((10_000_000 + 300_000 + 30_000_000) + (10_000_000 + 2_000 + 20_000_000))
    assert kt.read(h, None, None, roof) == pytest.approx(100 * (least / 819e9) / 0.4)
    # the parent's program names nothing: every metric of this reader is left out
    parent = dict(red, kernels={"_take": 0.1, "searchsorted": 0.02}, unnamed_s=0.12, busy_s=0.12)
    for sel in (probe, per_cycle, roof, {"what": "unnamed_busy_pct"}):
        assert kt.read(harness(parent, executions=3), None, None, sel) is None
    assert kt.read(harness(None), None, None, probe) is None  # no profile at all
    with pytest.raises(ValueError):
        kt.read(h, None, None, dict(probe, what="flops"))


def test_on_the_trace_recorded_from_the_chip():
    with open(os.path.join(BENCH, "fixtures", "trace_named_small.json")) as f:
        fx = json.load(f)
    ops = {k: [tuple(e) for e in v] for k, v in fx["ops"].items()}
    modules = {k: [tuple(e) for e in v] for k, v in fx["modules"].items()}
    host = [tuple(e) for e in fx["host"]]
    red = kt.reduce_kernels(ops, modules, host)
    # the programs the serve path runs carry the library's names
    assert {"csvplus.serve.bounds_search", "csvplus.table.gather_take"} <= set(red["kernels"])
    assert red["cycles"] == sum(1 for n, *_ in host if n == kt.CYCLE) >= 2
    # kernels partition the busy time (a while's body nests in one kernel)
    assert sum(red["kernels"].values()) == pytest.approx(red["busy_s"])
    # the same union by brute force: rasterise each plane's ops at 10 ns
    (w0, w1), = [(s, s + d) for n, s, d in host if n == "bench:window"]
    total = 0
    for events in ops.values():
        cells = bytearray(int((w1 - w0) // 10) + 1)
        for _, s, d in events:
            a, b = max(s, w0), min(s + d, w1)
            if b > a:
                cells[int((a - w0) // 10): int((b - w0) // 10)] = b"\x01" * (int((b - w0) // 10) - int((a - w0) // 10))
        total += sum(cells) * 10
    assert red["busy_s"] * 1e9 == pytest.approx(total / len(ops), rel=0.02)
    assert 0 <= red["unnamed_s"] <= red["busy_s"]
    # per cycle one search and one gather per column of people(id, name,
    # surname); the slice cuts a third cycle's annotation, not its programs
    searches = red["calls"]["csvplus.serve.bounds_search"]
    assert red["cycles"] <= searches == 3
    assert red["calls"]["csvplus.table.gather_take"] == 3 * searches
    # the whole-lane copy before each gather is the gather program's: it outweighs the search
    assert red["kernels"]["csvplus.table.gather_take"] > red["kernels"]["csvplus.serve.bounds_search"] > 0
