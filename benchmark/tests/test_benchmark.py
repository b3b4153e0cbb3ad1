"""The benchmark's own tests: run by hand and in rehearsal, on the CPU,

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider

(not under ``tests/``: they belong to the yardstick).  Every cell runs
end to end with ``--rehearse-cpu`` at 200K rows; no number they see is a
device number.
"""

from __future__ import annotations

import io
import json
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, HERE]

import run  # noqa: E402
import tampers  # noqa: E402
from control_dedup import keep_the_second_copy  # noqa: E402
from readers import device_trace  # noqa: E402

ROWS = "200000"
CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCHMARK = json.load(_f)
CELLS = [w["name"] for w in BENCHMARK["workloads"]]
OWN_TAMPER = {"dedup-resident": keep_the_second_copy}  # else tampers.swap_one_value


def reported_by(cell: str) -> set:
    """The end-to-end metrics a cell reports, by ``BENCHMARK.json``."""
    return {m["name"] for m in BENCHMARK["end_to_end"] if cell in m.get("workloads", CELLS)}


@pytest.fixture(autouse=True)
def small_mirror_cap(monkeypatch):
    """At rehearsal size every index is under the 16M mirror cap, and the
    serve cell rightly refuses that; the rehearsal lowers the cap in this
    process (no environment variable) so that the device path runs."""
    from csvplus_tpu.ops.join import DeviceIndex

    monkeypatch.setattr(DeviceIndex, "POINT_MIRROR_MAX_KEYS", 1000)


def rehearse(cell: str, seed: int, trace: int = 0, tamper=None, seconds="1.5"):
    out = io.StringIO()
    rc = run.main(
        ["--workload", cell, "--seed", str(seed), "--seconds", seconds, "--trace", str(trace),
         "--rehearse-cpu", "--rehearse-rows", ROWS],
        out=out, tamper=tamper,
    )
    lines = out.getvalue().strip().splitlines()
    return rc, lines, json.loads(lines[-1])


def cells_of(driver: str) -> list:
    return [c for c in CELLS if run.load_json("workloads", f"{c}.json")["driver"] == driver]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_end_to_end_reports_its_end_to_end_metrics(cell):
    rc, lines, result = rehearse(cell, 2_200_000_000 + len(cell))
    assert rc == 0
    assert set(result) == CONTRACT_KEYS
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == reported_by(cell)
    units = {m["name"]: m["unit"] for m in BENCHMARK["end_to_end"]}
    for name, m in result["metrics"].items():
        assert m["unit"] == units[name] and m["value"] > 0
    assert set(result["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert "REHEARSAL-ON-CPU" in lines[0]


@pytest.mark.parametrize("cell", CELLS)
def test_traced_run_reports_per_layer_metrics_that_list_the_cell(cell):
    rc, _, result = rehearse(cell, 2_300_000_000, trace=1)
    assert rc == 0 and result["correct"] is True
    listed = {m["name"]: m for m in BENCHMARK["per_layer"] if cell in m["workloads"]}
    assert result["metrics"], "a traced run reports at least one per-layer metric"
    assert set(result["metrics"]) <= set(listed)
    # no device plane on the CPU: only the device_trace metrics may be missing
    missing = set(listed) - set(result["metrics"])
    assert all(listed[m]["source"] == "device_trace" for m in missing), missing
    for name, m in result["metrics"].items():
        assert m["unit"] == listed[name]["unit"]


@pytest.mark.parametrize("cell", CELLS)
def test_second_seed_compiles_nothing_new(cell):
    """No array shape depends on --seed: in one process, a second seed
    finds every program compiled by the first."""
    rehearse(cell, 2_400_000_001)
    _, lines, result = rehearse(cell, 3_000_000_017)
    assert result["correct"] is True
    window = next(ln for ln in lines if ln.startswith("window:") and "set-up compiles=" in ln)
    assert "set-up compiles=0 " in window, window


@pytest.mark.parametrize("cell", cells_of("batch_query"))
@pytest.mark.parametrize("nth", [None, 5])  # 5: an execution of the window
def test_a_swapped_value_makes_the_run_incorrect(cell, nth):
    """The cell's own tamper where its result has no ``ts`` to swap."""
    tamper = OWN_TAMPER.get(cell, tampers.swap_one_value)
    rc, _, result = rehearse(cell, 2_500_000_000, tamper=tamper(nth))
    assert rc == 0 and result["correct"] is False and result["failed"] >= 1


@pytest.mark.parametrize("cell", cells_of("closed_loop_lookup"))
def test_a_stale_reply_makes_the_run_incorrect(cell):
    rc, _, result = rehearse(cell, 2_600_000_000, tamper=tampers.stale_reply(100))
    assert rc == 0 and result["correct"] is False and result["failed"] >= 1


@pytest.mark.parametrize("cell", cells_of("closed_loop_lookup"))
def test_an_index_under_the_mirror_cap_makes_the_run_incorrect(cell, monkeypatch):
    from csvplus_tpu.ops.join import DeviceIndex

    monkeypatch.setattr(DeviceIndex, "POINT_MIRROR_MAX_KEYS", 16_000_000)
    rc, _, result = rehearse(cell, 2_700_000_000)
    assert rc == 0 and result["correct"] is False and result["failed"] == 0


def test_without_a_tpu_and_without_the_flag_nothing_runs(capsys):
    out = io.StringIO()
    rc = run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1"], out=out)
    assert rc != 0 and out.getvalue() == ""
    assert "not a TPU" in capsys.readouterr().err


def test_trace_reducer_on_the_recorded_fixture():
    with open(os.path.join(BENCH, "fixtures", "trace_small.json")) as f:
        fx = json.load(f)
    device = {k: [tuple(e) for e in v] for k, v in fx["device"].items()}
    red = device_trace.reduce_events(device, [tuple(e) for e in fx["host"]])
    assert red["window_s"] == pytest.approx(fx["expect"]["window_s"])
    assert red["busy_s"] == pytest.approx(fx["expect"]["busy_s"])
    # the same union by brute force, rasterised at 1 us when the fixture was made
    assert red["busy_s"] == pytest.approx(fx["expect"]["busy_s_rasterised_at_1us"], abs=5e-4)
    assert red["breakdown"]["device_ops"][0][0] == fx["expect"]["top_op"]
    assert red["breakdown"]["idle_gaps"][0][0] == fx["expect"]["top_gap"]
    gaps = sum(s for _, s in red["breakdown"]["idle_gaps"])
    assert red["busy_s"] + gaps == pytest.approx(red["window_s"])


def test_no_file_size_or_count_follows_the_seed(tmp_path):
    """Row byte lengths, distinct counts (whole and per stretch of rows)
    and the filter's hits are the configuration's for every seed."""
    import numpy as np

    gen = run.load_module("gen", "orders")
    cfg = run.load_json("configs", "orders-star-10m.json")
    seen = []
    for seed in (5, 4_100_000_123):
        root = tmp_path / str(seed)
        root.mkdir()
        d = gen.Data(cfg, seed, str(root), ("orders", "people", "stock"), rows=300_000)
        ts = d.ts
        seen.append((
            [os.path.getsize(d.paths[k]) for k in ("orders", "people", "stock")],
            [len(np.unique(ts[lo : lo + 50_000])) for lo in range(0, d.n, 50_000)],
            len(np.unique(ts)), len(np.unique(d.cust)), len(np.unique(d.prod)),
            len(d.filter_hits), int(((d.prod == 7) & (d.qty == 3)).sum()),
        ))
    assert seen[0] == seen[1]
    assert seen[0][2] == 300_000 * 8_500_000 // 10_000_000 and seen[0][5:] == (100, 100)
    assert not np.array_equal(gen.Data(cfg, 6, str(tmp_path), (), rows=1000).ts, ts[:1000])


def test_the_benchmarks_own_digest_is_left_out_of_busy_and_window():
    """An op inside a bench:digest annotation is neither busy nor idle
    time of the program: the window shrinks by the annotation."""
    device = {"/device:TPU:0": [("join", 10, 20), ("digest", 50, 10), ("join", 70, 10)]}
    host = [("bench:window", 0, 100), ("bench:digest", 45, 20)]
    red = device_trace.reduce_events(device, host)
    assert red["window_s"] == pytest.approx(80e-9)
    assert red["busy_s"] == pytest.approx(30e-9)
    assert [n for n, _ in red["breakdown"]["device_ops"]] == ["join"]
    gaps = sum(s for _, s in red["breakdown"]["idle_gaps"])
    assert red["busy_s"] + gaps == pytest.approx(red["window_s"])


def test_a_shed_or_errored_lookup_is_over_any_latency_limit():
    driver = run.load_module("drivers", "closed_loop_lookup")
    samples = {"t_end": 10.0, "t_sub": [0.0, 1.0, 2.0, 9.5], "t_done": [0.5, 1.001, 2.5, 11.0],
               "errors": [None, "QueueFull()", None, None]}
    lat = driver._latencies_in_window(samples)
    assert lat.tolist() == [0.5, float("inf"), 0.5]


def test_trace_reducer_by_hand():
    """Two overlapping ops and one apart in a 100 ns window: busy is the
    union (30 + 10), the gaps are labelled by the innermost annotation."""
    device = {"/device:TPU:0": [("a", 10, 20), ("b", 20, 20), ("a", 70, 10), ("late", 200, 5)]}
    host = [("bench:window", 0, 100), ("bench:outer", 0, 100), ("csvplus:inner", 45, 20)]
    red = device_trace.reduce_events(device, host)
    assert red["window_s"] == pytest.approx(100e-9)
    assert red["busy_s"] == pytest.approx(40e-9)
    assert red["breakdown"]["device_ops"] == [["a", pytest.approx(30e-9)], ["b", pytest.approx(20e-9)]]
    # gaps: [0,10) outer, [40,70) midpoint 55 -> inner, [80,100) outer
    assert dict(map(tuple, red["breakdown"]["idle_gaps"])) == {
        "bench:outer": pytest.approx(30e-9), "csvplus:inner": pytest.approx(30e-9),
    }
    assert device_trace.reduce_events({}, host) is None
    assert device_trace.reduce_events(device, [("bench:outer", 0, 100)]) is None


PER_LAYER = {m["name"]: m for m in BENCHMARK["per_layer"]}
LAYER_FILES = {
    name[: -len(".json")] for name in os.listdir(os.path.join(BENCH, "layer_metrics"))
}
PER_LAYER_MAX = 128  # the contract's


def test_the_per_layer_table_has_room():
    free = PER_LAYER_MAX - len(BENCHMARK["per_layer"])
    assert free >= 0, f"per_layer holds {len(BENCHMARK['per_layer'])} entries of {PER_LAYER_MAX}: {free} names free"
    # one entry a quantity: a name differs only where what is read or what it moves does
    quantities = {}
    for name in LAYER_FILES:
        m = run.load_json("layer_metrics", f"{name}.json")
        key = (m["reader"], json.dumps(m.get("selector", {}), sort_keys=True), m["moves"])
        assert key not in quantities, f"{name} and {quantities[key]} are one quantity: list the cell in the entry that is there"
        quantities[key] = name


@pytest.mark.parametrize(
    "kind,name",
    [("per_layer", n) for n in sorted(set(PER_LAYER) | LAYER_FILES)] + [("cell", c) for c in CELLS],
)
def test_benchmark_json_is_consistent(kind, name):
    """A per-layer metric has its entry and its file, the entry lists its
    cells and the file none, and ``moves`` is an end-to-end metric that
    each of its cells reports; a cell reports ``setup_s`` and exactly one
    rate, under the name its file gives, and the least-bytes files it
    names exist, for metrics that list it."""
    if kind == "per_layer":
        assert name in PER_LAYER, f"layer_metrics/{name}.json has no entry in per_layer"
        assert name in LAYER_FILES, f"per_layer entry {name} has no layer_metrics file"
        m = PER_LAYER[name]
        assert "workloads" not in run.load_json("layer_metrics", f"{name}.json")
        assert m["workloads"] and set(m["workloads"]) <= set(CELLS)
        assert len(m["workloads"]) == len(set(m["workloads"]))
        for cell in m["workloads"]:
            assert m["moves"] in reported_by(cell), (name, m["moves"], cell)
        return
    rates = reported_by(name) - {"setup_s"}
    assert "setup_s" in reported_by(name) and len(rates) == 1, rates
    cell = run.load_json("workloads", f"{name}.json")
    if cell["driver"] == "batch_query":
        assert {cell.get("metric", "rows_per_s")} == rates
    assert any(name in m["workloads"] and m["moves"] in rates for m in PER_LAYER.values())
    # start-up's own check: a least-bytes file that is there for every roofline share
    # of the cell, and no file named for a metric that does not list it
    assert {m["name"] for m in run.layer_metrics_for(run.load_cell(name))} == {
        n for n, m in PER_LAYER.items() if name in m["workloads"]
    }


def test_benchmark_json_agrees_with_the_files_found_by_name():
    for cfg in BENCHMARK["configs"]:
        assert cfg["file"] == f"benchmark/configs/{cfg['name']}.json"
        on_disk = run.load_json("configs", f"{cfg['name']}.json")
        assert on_disk["source"] == cfg["source"] and on_disk["reduced"] == cfg["reduced"]
    for w in BENCHMARK["workloads"]:
        cell = run.load_json("workloads", f"{w['name']}.json")
        assert cell["config"] == w["config"] and cell["why"] == w["why"]
        assert run.load_json("configs", f"{w['config']}.json")["chips"] == w["chips"]
        assert os.path.exists(os.path.join(BENCH, "drivers", f"{cell['driver']}.py"))
        if "query" in cell:
            assert os.path.exists(os.path.join(BENCH, "queries", f"{cell['query']}.py"))
        assert os.path.exists(os.path.join(BENCH, "gen", f"{run.load_json('configs', w['config'] + '.json')['gen']}.py"))
    on_disk = {
        name[: -len(".json")]: run.load_json("layer_metrics", name)
        for name in os.listdir(os.path.join(BENCH, "layer_metrics"))
    }
    listed = {m["name"]: m for m in BENCHMARK["per_layer"]}
    assert set(on_disk) == set(listed)
    for name, m in listed.items():
        for key in ("layer", "unit", "better", "moves", "source"):
            assert on_disk[name][key] == m[key], (name, key)
        assert os.path.exists(os.path.join(BENCH, "readers", f"{on_disk[name]['reader']}.py"))
        least = on_disk[name].get("selector", {}).get("least_bytes")
        assert least is None or os.path.exists(os.path.join(BENCH, "least_bytes", f"{least}.py"))
    peaks = run.load_json("peaks.json")
    assert all("source" in p and p["hbm_bytes_per_s"] > 0 for p in peaks.values())


def _a_copy_of_the_yardstick(tmp_path, monkeypatch):
    """``BENCHMARK.json`` and ``benchmark/``'s data files copied under
    *tmp_path*, and ``run`` pointed at the copy."""
    copy = tmp_path / "benchmark"
    shutil.copytree(BENCH, copy, ignore=shutil.ignore_patterns("tests", "fixtures", "__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    monkeypatch.setattr(run, "HERE", str(copy))
    monkeypatch.setattr(run, "ROOT", str(tmp_path))
    return copy


def _files_under(root) -> dict:
    seen = {}
    for folder, _, names in os.walk(root):
        if "__pycache__" in folder:
            continue
        for name in names:
            path = os.path.join(folder, name)
            with open(path, "rb") as f:
                seen[os.path.relpath(path, root)] = f.read()
    return seen


def test_a_new_cell_joins_a_metric_by_entries_and_files_alone(tmp_path, monkeypatch):
    """What a later ``model_config`` PR does: a new ``workloads/`` file
    that names its own least bytes, a new ``least_bytes/`` file, and the
    cell's name APPENDED to the lists of ``BENCHMARK.json`` — no file that
    exists under ``benchmark/`` is written."""
    before = _files_under(BENCH)
    copy = _a_copy_of_the_yardstick(tmp_path, monkeypatch)
    cell, metric = "made-up-resident", "kernel.join_emit_roofline_pct"
    contract = json.loads((tmp_path / "BENCHMARK.json").read_text())
    for m in contract["per_layer"]:
        if m["name"] in (metric, "kernel.join_emit_device_s"):
            m["workloads"].append(cell)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(contract))
    (copy / "workloads" / f"{cell}.json").write_text(json.dumps(
        {"config": "orders-star-10m", "driver": "batch_query", "query": "star3",
         "least_bytes": {metric: "made_up_emit"}}
    ))
    (copy / "least_bytes" / "made_up_emit.py").write_text("def least_bytes(cfg, fact_rows):\n    return 7 * fact_rows\n")

    found = {m["name"]: m for m in run.layer_metrics_for(run.load_cell(cell))}
    assert set(found) == {metric, "kernel.join_emit_device_s"}
    assert found[metric]["selector"]["least_bytes"] == "made_up_emit"
    assert found[metric]["reader"] == "kernel_trace" and found[metric]["unit"] == "%"
    assert run.load_module("least_bytes", "made_up_emit").least_bytes({}, 3) == 21
    # the cells that were there read what they read
    selective = {m["name"]: m for m in run.layer_metrics_for(run.load_cell("star3-selective-resident"))}
    assert selective[metric]["selector"]["least_bytes"] == "star3_selective_emit"
    assert _files_under(BENCH) == before  # nothing under benchmark/ written, added or removed
    kept = _files_under(copy)
    added = set(kept) - {k for k in before if not k.startswith(("tests", "fixtures"))}
    assert added == {f"workloads/{cell}.json", "least_bytes/made_up_emit.py"}
    assert all(kept[k] == before[k] for k in kept if k not in added)


@pytest.mark.parametrize("fault", ["no file named", "a file that is not there", "a metric that does not list the cell"])
def test_a_roofline_share_without_least_bytes_ends_the_run_at_start_up(fault, tmp_path, monkeypatch):
    copy = _a_copy_of_the_yardstick(tmp_path, monkeypatch)
    cell = run.load_json("workloads", "star3-resident.json")
    if fault == "no file named":
        del cell["least_bytes"]["kernel.join_emit_roofline_pct"]  # and the selector has none
    elif fault == "a file that is not there":
        cell["least_bytes"]["kernel.join_emit_roofline_pct"] = "nowhere"
    else:
        cell["least_bytes"]["kernel.pjoin_probe_roofline_pct"] = "lookupjoin_probe"
    (copy / "workloads" / "star3-resident.json").write_text(json.dumps(cell))
    out = io.StringIO()
    with pytest.raises(SystemExit, match="least.bytes"):
        run.main(["--workload", "star3-resident", "--seed", "1", "--seconds", "1", "--trace", "0", "--rehearse-cpu"], out=out)
    assert out.getvalue() == ""
