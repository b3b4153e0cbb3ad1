"""``dedup-resident`` by hand: its generator, its reference, its set-up
refusal, its control and the selectors of its per-layer metrics

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider

(``test_benchmark.py`` rehearses the cell end to end with the others:
every case there that is parametrised by cell runs it too, the tampered
run with this cell's own tamper, ``control_dedup.keep_the_second_copy``:
this query's result has no ``ts`` column to swap.)
"""

from __future__ import annotations

import io
import json
import os
import sys
from types import SimpleNamespace

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, HERE]

import reference as ref  # noqa: E402
import run  # noqa: E402
from control_dedup import keep_the_second_copy  # noqa: E402
from readers import counters, device_trace, kernel_trace, stage_extra, stage_self, stage_table  # noqa: E402

CELL = "dedup-resident"
CFG = run.load_json("configs", "people-dedup-50m.json")
READERS = {m.__name__.split(".")[-1]: m for m in (counters, device_trace, kernel_trace, stage_extra, stage_self, stage_table)}
# every metric whose entry lists the cell, its least-bytes file resolved as a run resolves it
LISTED = {m["name"]: m for m in run.layer_metrics_for(run.load_cell(CELL))}
# those a hand-made execution can feed: the stage table, the kernels, the facts (not the process journal)
WINDOW_METRICS = sorted(n for n, m in LISTED.items() if m["reader"] in READERS)


def generated(tmp_path, seed, rows=30_000):
    gen = run.load_module("gen", "people_dups")
    root = tmp_path / str(seed)
    root.mkdir()
    return gen.Data(CFG, seed, str(root), ("people",), rows=rows)


def test_the_file_has_the_configurations_shape_for_every_seed(tmp_path):
    """Distinct and doubled counts, the byte length of every row, the
    rows that hold a doubled id: the configuration's; which ids are
    doubled, their order and which rows pair up: the seed's.  An id's two
    rows are never adjacent and never carry the same (name, surname)."""
    seen = []
    for seed in (7, 4_100_000_123):
        d = generated(tmp_path, seed)
        ids, counts = np.unique(d.people_id, return_counts=True)
        assert ids.tolist() == list(range(d.distinct))  # every id of 0..distinct-1, some twice
        assert np.bincount(counts).tolist() == [0, 24_000, 3_000]  # 27,000 ids, 3,000 of them twice
        with open(d.paths["people"], "rb") as f:
            lines = f.read().split(b"\n")
        assert len(lines) == d.n + 2 and lines[0] == b"id,name,surname"
        assert lines[1] == b"c%d,Amelia,Smith" % d.people_id[0]
        doubled = ids[counts == 2]
        first, last = d.first_row[doubled], d.last_row[doubled]
        assert (last - first >= 2).all() and ((last - first) % 120 != 0).all()
        assert not np.array_equal(d.people_name(first), d.people_name(last)) or not np.array_equal(
            d.people_surname(first), d.people_surname(last))
        seen.append(([len(ln) for ln in lines], d.people_id, np.sort(np.concatenate([first, last])), doubled))
    assert seen[0][0] == seen[1][0]  # every row as long under both seeds
    assert np.array_equal(seen[0][2], seen[1][2])  # the same rows hold doubled ids
    assert not np.array_equal(seen[0][1], seen[1][1]) and not np.array_equal(seen[0][3], seen[1][3])


def test_the_reference_is_the_string_sort_and_the_first_copy(tmp_path):
    """``want`` (an arithmetic key, a backwards fancy assignment) against
    plain Python over the same rows: sorted() of the id strings, a dict
    that keeps the first row seen."""
    d = generated(tmp_path, 11, rows=12_000)
    want = run.load_module("queries", "dedup").want(d)
    first = {}
    for row, v in enumerate(d.people_id.tolist()):
        first.setdefault(v, row)
    by_string = sorted(first, key=lambda v: b"c%d" % v)
    assert want["id"][0] == b"c" and want["id"][1].tolist() == by_string
    rows = np.array([first[v] for v in by_string])
    assert want["name"].tolist() == d.people_name(rows).tolist()
    assert want["surname"].tolist() == d.people_surname(rows).tolist()
    last = {v: row for row, v in enumerate(d.people_id.tolist())}
    assert d.last_row.tolist() == [last[v] for v in range(d.distinct)]
    pos, a, b = d.a_doubled_id()
    assert first[by_string[pos]] == a < b == last[by_string[pos]]


def rehearse(seed, tamper=None, trace=0):
    out = io.StringIO()
    rc = run.main(
        ["--workload", CELL, "--seed", str(seed), "--seconds", "1.0", "--trace", str(trace),
         "--rehearse-cpu", "--rehearse-rows", "100000"], out=out, tamper=tamper,
    )
    lines = out.getvalue().strip().splitlines()
    return rc, lines, json.loads(lines[-1])


def test_the_cell_runs_the_device_path_and_says_so():
    rc, lines, result = rehearse(3_400_000_033)
    assert rc == 0 and result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"rows_per_s", "setup_s"}
    stages = next(ln for ln in lines if ln.startswith("  first execution's stages"))
    assert all(s in stages for s in ("index:sort", "index:permute", "dedup:runs", "'tier': 'device'"))
    assert any("people: 100,000 rows via ingest:" in ln and "'id': 'int-lane'" in ln for ln in lines)


def test_a_traced_rehearsal_reports_every_metric_the_cpu_can_read():
    rc, _, result = rehearse(3_400_000_034, trace=1)
    assert rc == 0 and result["correct"] is True
    readable = {n for n, m in LISTED.items() if m["source"] != "device_trace"}
    assert set(result["metrics"]) == readable  # no device plane on the CPU
    assert result["metrics"]["process.host_sync_elems"]["value"] == 1
    assert result["metrics"]["index.row_gathers"]["value"] == 2  # the permutation's; the compaction's lanes ride a sort


@pytest.mark.parametrize("nth", [None, 4])  # 4: the second execution of the window
def test_the_second_copy_kept_makes_the_run_incorrect(nth):
    rc, _, result = rehearse(2_500_000_033, tamper=keep_the_second_copy(nth))
    assert rc == 0 and result["correct"] is False and result["failed"] >= 1


def test_a_dedup_through_the_host_is_refused_in_set_up(monkeypatch):
    """The program's own host tier (the callback resolver: the run mask
    read to the host, the selection uploaded) served for the named
    policy, as a tree from before the device compaction does: the run
    ends in set-up, with one line that says why, and prints no result."""
    from csvplus_tpu.index import Index

    def through_the_host(self, policy):
        assert self._device_callback_dedup((lambda g: g[0]) if policy == "first" else (lambda g: g[-1]))

    monkeypatch.setattr(Index, "_device_policy_dedup", through_the_host)
    out = io.StringIO()
    with pytest.raises(ref.Mismatch) as refused:
        run.main(["--workload", CELL, "--seed", "3400000035", "--seconds", "1.0", "--trace", "0",
                  "--rehearse-cpu", "--rehearse-rows", "100000"], out=out)
    message = str(refused.value)
    assert "\n" not in message and "refused in set-up" in message
    assert "tiers recorded ['host']" in message and "read 100000 elements to the host" in message
    assert not any(ln.startswith("{") for ln in out.getvalue().splitlines())  # no result line


def test_a_program_without_the_stages_is_refused():
    """Today's parent records no index:/dedup: stage at all."""
    dedup = run.load_module("queries", "dedup")
    with pytest.raises(ref.Mismatch, match=r"tiers recorded \[\], want \['device'\]"):
        dedup.refuse_host_tier([("typed:demote", {})])
    device = ("dedup:compact", {"tier": "device", "host_sync_elements": 0})
    dedup.refuse_host_tier([("dedup:runs", {"host_sync_elements": 1}), device])
    with pytest.raises(ref.Mismatch, match="read 50000000 elements to the host"):
        dedup.refuse_host_tier([("dedup:runs", {"host_sync_elements": 50_000_000}), device])


# ---- the per-layer metrics' selectors on a hand-made execution ----


def stage(name, seconds=0.0, **extra):
    return SimpleNamespace(stage=name, seconds=seconds, extra=extra)


EXECUTION = [
    stage("index:view", 0.01, rows=50, row_gathers=0, synced=True, wait_s=0.0),
    stage("index:sort", 1.5, rows=50, keys=1, tier="lax", row_gathers=0, synced=True, wait_s=1.49),
    stage("index:permute", 0.7, rows=50, row_gathers=2, synced=True, wait_s=0.69),
    stage("index:pack", 0.002, rows=50, keys=1, row_gathers=0, host_sync_elements=0, synced=True, wait_s=0.001),
    stage("dedup:runs", 0.25, rows=50, row_gathers=0, host_sync_elements=1, synced=True, wait_s=0.01),
    stage("dedup:compact", 1.1, rows=50, policy="first", kept=45, tier="device", row_gathers=3,
          host_sync_elements=0, synced=True, wait_s=0.9),
    stage("index:pack", 0.002, rows=45, keys=1, row_gathers=0, host_sync_elements=0, synced=True, wait_s=0.001),
]
PARENT = [stage("typed:demote", 9.0)]  # a program from before the stages
ROWS = 50_000_000
KERNELS = {"csvplus.index.sort": 3.0, "csvplus.dedup.runs": 0.002, "csvplus.dedup.compact": 0.8,
           "csvplus.table.gather_take": 3.2, "csvplus.join.pack_qk": 0.004, "iota": 0.01}


def harness(per_exec, kernels, syncs):
    busy = sum(kernels.values())
    red = {"kernels": kernels, "calls": {}, "busy_s": busy, "cycles": 0,
           "unnamed_s": sum(s for k, s in kernels.items() if not k.startswith("csvplus."))}
    return SimpleNamespace(
        evidence={"stages": per_exec, "host_sync_elements": syncs, "kernel_trace": red,
                  "facts": {"executions": 2, "first_exec_minus_warm_s": 20.0, "peak_hbm_bytes": 2_600_000_000},
                  "peaks": {"hbm_bytes_per_s": 819e9}, "trace": {"busy_s": busy, "window_s": busy / 0.98}},
        cfg=CFG, data=SimpleNamespace(n=ROWS), load_module=run.load_module,
    )


def read(name, h):
    m = LISTED[name]
    return READERS[m["reader"]].read(h, None, None, m["selector"])


def test_every_new_metrics_selector_finds_its_number():
    h = harness([EXECUTION, EXECUTION], KERNELS, [1, 1])
    got = {name: read(name, h) for name in WINDOW_METRICS}
    assert all(v is not None for v in got.values()), got
    assert got["index.build_host_s"] == pytest.approx(0.01 + 1.5 + 0.7 + 0.004)
    assert got["dedup.resolve_host_s"] == pytest.approx(1.35)
    assert got["dedup.host_self_s"] == pytest.approx(0.24 + 0.2)
    assert got["process.host_sync_elems"] == 1 and got["index.row_gathers"] == 5
    assert got["kernel.index_sort_device_s"] == pytest.approx(1.5)
    moved = (3.2 + 0.8) / 2  # the programs that move the lanes: gather_take and the compaction (no head in this table)
    assert got["kernel.dedup_gather_device_s"] == pytest.approx(moved)
    busy = sum(KERNELS.values())
    assert got["device.unnamed_busy_pct.batch"] == pytest.approx(100 * 0.01 / busy)
    assert got["device.idle_pct.batch"] == pytest.approx(2.0)
    assert got["device.peak_hbm_bytes.batch"] == 2_600_000_000 and got["admit.first_exec_s"] == 20.0
    # the shares of a roofline: each least_bytes file's count over the peak, over the kernel's seconds
    sort_least = 4 * 3 * ROWS  # key read, sorted key and permutation written
    assert got["kernel.index_sort_roofline_pct"] == pytest.approx(100 * (sort_least / 819e9) / 1.5)
    gather_least = 4 * 2 * 3 * 45_000_000  # each emitted lane's survivors read once and written once
    assert got["kernel.dedup_gather_roofline_pct"] == pytest.approx(100 * (gather_least / 819e9) / moved)
    whole = 4 * (3 * ROWS + 4 * 45_000_000)  # three lanes in; three lanes and the packed key out
    assert run.load_module("least_bytes", "dedup").least_bytes(CFG, ROWS) == whole
    assert LISTED["device.bytes_roofline_pct"]["selector"]["least_bytes"] == "dedup"  # the cell names its own
    assert got["device.bytes_roofline_pct"] == pytest.approx(100 * (whole / 819e9) / (busy / 2))
    assert all(got[n] < 100 for n in WINDOW_METRICS if n.endswith("roofline_pct") or "roofline_pct." in n)


def test_on_the_parent_a_new_metric_is_left_out_and_nothing_raises():
    """A program that lacks the stages and the kernel names (the other
    side of a comparison): None, which leaves the metric out of the line."""
    kernels = {"sort_kernel": 3.0, "csvplus.table.gather_take": 3.2}  # the parent's sort carries jax's name
    h = harness([PARENT, PARENT], kernels, [0, 0])
    got = {name: read(name, h) for name in WINDOW_METRICS}
    for name in ("index.build_host_s", "dedup.resolve_host_s", "dedup.host_self_s", "index.row_gathers",
                 "kernel.index_sort_device_s", "kernel.index_sort_roofline_pct"):
        assert got[name] is None
    assert got["process.host_sync_elems"] == 0  # the parent counts none of its reads
    assert got["kernel.dedup_gather_device_s"] == pytest.approx(1.6)
