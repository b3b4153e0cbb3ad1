"""The trace readers on more than one device plane (a cell on a mesh):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_mesh_trace.py -q -p no:cacheprovider

``fixtures/trace_mesh_small.json`` holds two device planes made by hand.
Busy seconds, a kernel's seconds and the collectives' seconds are each
the AVERAGE over the planes of a per-plane union; an asynchronous
collective's ``-start``/``-done`` pair is one call.
"""

from __future__ import annotations

import copy
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH]

import run  # noqa: E402
from readers import collective_trace, device_trace, kernel_trace  # noqa: E402


@pytest.fixture(scope="module")
def fx():
    with open(os.path.join(BENCH, "fixtures", "trace_mesh_small.json")) as f:
        loaded = json.load(f)
    for key in ("ops", "modules"):
        loaded[key] = {plane: [tuple(e) for e in evs] for plane, evs in loaded[key].items()}
    loaded["host"] = [tuple(e) for e in loaded["host"]]
    return loaded


def mean_s(per_plane_ns) -> float:
    return sum(per_plane_ns) / len(per_plane_ns) / 1e9


def test_busy_seconds_are_the_average_over_the_device_planes(fx):
    red = device_trace.reduce_events(fx["ops"], fx["host"])
    want = fx["expect_ns"]
    assert red["n_device_planes"] == 2
    assert red["window_s"] == pytest.approx(want["window"] / 1e9)
    assert red["busy_s"] == pytest.approx(mean_s(want["busy_per_plane"]))
    # the digest's operations are in neither plane's busy time
    assert all("digest" not in name for name, _ in red["breakdown"]["device_ops"])
    # idle time is per plane too: busy + gaps is the window, on average
    gaps = sum(s for _, s in red["breakdown"]["idle_gaps"])
    assert red["busy_s"] + gaps == pytest.approx(red["window_s"])


def test_a_kernels_seconds_are_the_average_over_the_device_planes(fx):
    red = kernel_trace.reduce_kernels(fx["ops"], fx["modules"], fx["host"])
    want = fx["expect_ns"]
    assert red["busy_s"] == pytest.approx(mean_s(want["busy_per_plane"]))
    assert red["kernels"]["csvplus.pjoin.probe_spmd_dev"] == pytest.approx(mean_s(want["pjoin_per_plane"]))
    assert red["kernels"]["csvplus.join.gather_cols"] == pytest.approx(mean_s(want["gather_per_plane"]))
    assert red["unnamed_s"] == pytest.approx(mean_s(want["unnamed_per_plane"]))
    assert red["calls"]["csvplus.pjoin.probe_spmd_dev"] == 1  # one execution a plane
    assert red["calls"]["iota"] == 0.5  # on one plane of the two
    assert "lane_digest" not in red["kernels"]


def test_the_translate_metric_reads_the_program_the_mesh_cell_runs(fx):
    """``kernel.join_translate_device_s.mesh``'s selector matches by
    prefix: it reads ``csvplus.typed.translate_sorted``, the while's body
    nested in it counted once, per plane and averaged, per execution."""
    with open(os.path.join(BENCH, "layer_metrics", "kernel.join_translate_device_s.mesh.json")) as f:
        metric = json.load(f)
    assert metric["reader"] == "kernel_trace" and metric["moves"] == "rows_per_s.mesh"
    red = kernel_trace.reduce_kernels(fx["ops"], fx["modules"], fx["host"])
    want = mean_s(fx["expect_ns"]["translate_per_plane"])
    assert red["kernels"]["csvplus.typed.translate_sorted"] == pytest.approx(want)
    h = _Harness({"kernel_trace": red, "facts": {"executions": 2}})
    assert kernel_trace.read(h, None, None, metric["selector"]) == pytest.approx(want / 2)
    # no translate program in the window (the composed tier, star3 since PR 26): left out
    other = dict(red, kernels={k: s for k, s in red["kernels"].items() if "translate" not in k})
    h = _Harness({"kernel_trace": other, "facts": {"executions": 2}})
    assert kernel_trace.read(h, None, None, metric["selector"]) is None


def test_the_probes_least_bytes_hold_no_read_of_the_key_lane():
    """Keys read, answers written, the slice's two bounds: a quarter of
    the mesh's, and nothing that follows the index's size."""
    with open(os.path.join(BENCH, "configs", "orders-people-mesh4.json")) as f:
        cfg = json.load(f)
    probe = run.load_module("least_bytes", "lookupjoin_probe").least_bytes
    assert probe(cfg, 20_000_000) == 4 * (5_000_000 + 2 * 5_000_000 + 2)
    wider = copy.deepcopy(cfg)
    wider["tables"]["people"]["rows"] *= 2
    assert probe(wider, 20_000_000) == probe(cfg, 20_000_000)
    assert probe(cfg, 20_000_000) < run.load_module("least_bytes", "lookupjoin").least_bytes(cfg, 20_000_000)


def test_collective_seconds_and_the_start_done_pair(fx):
    red = collective_trace.reduce_collectives(fx["ops"], fx["host"])
    want = fx["expect_ns"]
    assert red["seconds"] == pytest.approx(mean_s(want["collective_per_plane"]))
    assert red["calls"] == pytest.approx(sum(want["collective_calls_per_plane"]) / 2)
    assert set(red["by_op"]) == {"all-to-all", "all-reduce"}
    assert red["by_op"]["all-reduce"] == pytest.approx(10 / 2 / 1e9)
    # a plane whose collectives overlap counts the overlap once
    one = {"p": [("%all-gather-start.1 = ...", 10, 30), ("%all-gather-done.1 = ...", 20, 40)]}
    red = collective_trace.reduce_collectives(one, [("bench:window", 0, 100)])
    assert red["seconds"] == pytest.approx(50e-9) and red["calls"] == 1
    # names that only contain a collective's name are not collectives
    none = {"p": [("%fusion.3 = s32[8] fusion(%all-to-all.1)", 10, 30), ("%all-to-all-fusion", 50, 5)]}
    red = collective_trace.reduce_collectives(none, [("bench:window", 0, 100)])
    assert red["seconds"] == 0 and red["calls"] == 0
    assert collective_trace.reduce_collectives({}, [("bench:window", 0, 100)]) is None
    assert collective_trace.reduce_collectives(one, []) is None


class _Harness:
    def __init__(self, evidence):
        self.evidence = evidence
        self.root = "/nonexistent"

    def say(self, line):
        pass


def test_reader_gives_seconds_per_execution_and_nothing_on_one_chip(fx):
    red = collective_trace.reduce_collectives(fx["ops"], fx["host"])
    h = _Harness({"collective_trace": red, "facts": {"executions": 2}})
    sel = {"what": "collective_s", "per": "execution"}
    assert collective_trace.read(h, None, None, sel) == pytest.approx(red["seconds"] / 2)
    quiet = {"seconds": 0.0, "calls": 0, "by_op": {}}
    assert collective_trace.read(_Harness({"collective_trace": quiet, "facts": {"executions": 2}}), None, None, sel) is None
    assert collective_trace.read(_Harness({"facts": {"executions": 2}}), None, None, sel) is None  # no profile
    with pytest.raises(ValueError):
        collective_trace.read(h, None, None, {"what": "other"})
