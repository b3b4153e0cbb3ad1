"""Telemetry plane (ISSUE 13): metric registry + Prometheus
exposition, always-on tail sampling, the crash flight recorder,
Space-Saving key-skew sketches, the JSONL metrics pump, the pinned
``ServingMetrics.snapshot`` schema, and the obs CLI.

The serving-tier integration tests drive a real :class:`LookupServer`
(the plane is always on — every server owns one) and assert on the
rendered Prometheus text, not internal state: the scrape IS the
contract an operator's dashboard consumes.
"""

import json
import os
import urllib.request

import numpy as np
import pytest

import csvplus_tpu as cp
from csvplus_tpu.columnar.table import DeviceTable
from csvplus_tpu.obs.__main__ import main as obs_main
from csvplus_tpu.obs.flight import DUMP_SCHEMA_VERSION, FlightRecorder
from csvplus_tpu.obs.metrics import (
    Histogram,
    MetricRegistry,
    MetricsPump,
    Sample,
    TailSampler,
    TelemetryPlane,
    serve_samples,
    series_id,
)
from csvplus_tpu.obs.sketch import SpaceSaving, skew_report
from csvplus_tpu.serve import LookupServer
from csvplus_tpu.serve.metrics import SNAPSHOT_SCHEMA_VERSION


def zipf_probe_values(ids, n_probes: int, *, s: float = 1.1, seed: int = 0):
    """Deterministic Zipf(s)-skewed draws from ``ids`` (an int array):
    rank-k of ``ids`` (in array order) is drawn with weight 1/k^s, so a
    handful of keys absorb most of the traffic."""
    ranks = np.arange(1, len(ids) + 1, dtype=np.float64)
    weights = ranks ** -float(s)
    weights /= weights.sum()
    rng = np.random.default_rng(seed)
    return rng.choice(np.asarray(ids), size=n_probes, p=weights)


def _index(n=64):
    ids = np.arange(n)
    t = DeviceTable.from_pylists(
        {
            "id": np.char.add("c", ids.astype(np.str_)).tolist(),
            "v": (ids * 2).astype(np.str_).tolist(),
        },
        device="cpu",
    )
    return cp.take(t).index_on("id").sync(), ids


# -- Space-Saving sketch ----------------------------------------------------


def test_sketch_exact_under_k_distinct():
    sk = SpaceSaving(8)
    for key, n in (("a", 5), ("b", 3), ("c", 1)):
        for _ in range(n):
            sk.offer(key)
    top = sk.topk()
    assert [(k, c, e) for k, c, e in top] == [("a", 5, 0), ("b", 3, 0),
                                             ("c", 1, 0)]
    assert sk.observed == 9


def test_sketch_guarantee_bounds_over_k():
    # 200 distinct keys through a k=16 sketch: every reported count
    # must bracket the true count (count - err <= true <= count), and
    # any key with true frequency > observed/k must be present
    rng = np.random.default_rng(3)
    stream = [int(v) for v in rng.integers(0, 200, size=5_000)]
    stream += [999] * 1_000  # a guaranteed heavy hitter
    rng.shuffle(stream)
    true = {}
    for key in stream:
        true[key] = true.get(key, 0) + 1
    sk = SpaceSaving(16)
    sk.offer_many(stream)
    assert sk.observed == len(stream)
    top = sk.topk()
    assert len(top) <= 16
    for key, count, err in top:
        assert count - err <= true[key] <= count
    present = {key for key, _, _ in top}
    for key, n in true.items():
        if n > len(stream) / 16:
            assert key in present
    assert 999 in present


def test_sketch_zipf_heavy_hitter_surfaces():
    ids = np.arange(500)
    draws = zipf_probe_values(ids, 4_000, seed=7)
    vals, counts = np.unique(draws, return_counts=True)
    hitter = int(vals[counts.argmax()])
    sk = SpaceSaving(32)
    sk.offer_many(int(v) for v in draws)
    assert hitter in {k for k, _, _ in sk.topk(5)}


def test_sketch_offer_many_aggregates_like_sequential():
    a, b = SpaceSaving(4), SpaceSaving(4)
    stream = ["x", "y", "x", "z", "x", "y", "w", "q", "x"]
    for key in stream:
        a.offer(key)
    b.offer_many(stream)
    assert a.snapshot() == b.snapshot()


def test_sketch_snapshot_json_and_report():
    sk = SpaceSaving(4)
    sk.offer_many([("c", 1), ("c", 1), ("d", 2)])
    snap = sk.snapshot()
    parsed = json.loads(json.dumps(snap))  # tuples must be JSON-safe
    assert parsed["k"] == 4 and parsed["observed"] == 3
    report = skew_report(snap)
    assert "share" in report and "c" in report


# -- registry + exposition --------------------------------------------------


def test_registry_render_families_and_values():
    reg = MetricRegistry()
    c = reg.counter("demo_requests_total", "requests served")
    g = reg.gauge("demo_depth", "queue depth")
    c.inc(3)
    g.set(7)
    text = reg.render()
    assert "# HELP demo_requests_total requests served" in text
    assert "# TYPE demo_requests_total counter" in text
    assert "demo_requests_total 3" in text
    assert "# TYPE demo_depth gauge" in text
    assert "demo_depth 7" in text
    # idempotent per name; kind mismatch rejected
    assert reg.counter("demo_requests_total") is c
    with pytest.raises(ValueError):
        reg.gauge("demo_requests_total")


def test_histogram_buckets_cumulative():
    h = Histogram("demo_seconds", start=0.001, factor=10.0, count=3)
    h.observe_many([0.0005, 0.005, 0.05, 5.0])
    snap = h.snapshot()
    assert snap["counts"] == [1, 1, 1, 1] and snap["count"] == 4
    rows = {series_id(s.name, s.labels): s.value for s in h.samples()}
    assert rows['demo_seconds_bucket{le="0.001"}'] == 1
    assert rows['demo_seconds_bucket{le="0.01"}'] == 2
    assert rows['demo_seconds_bucket{le="0.1"}'] == 3
    assert rows['demo_seconds_bucket{le="+Inf"}'] == 4
    assert rows["demo_seconds_count"] == 4


def test_collector_failure_skipped_and_counted():
    reg = MetricRegistry()

    def boom():
        raise RuntimeError("publisher died")

    reg.register_collector(boom, "boom")
    reg.register_collector(
        lambda: [Sample("demo_ok", "gauge", (), 1.0)], "ok"
    )
    d = reg.sample_dict()
    assert d["demo_ok"] == 1.0  # the healthy publisher still lands
    assert d["csvplus_registry_collector_errors_total"] == 1
    assert reg.sample_dict()["csvplus_registry_collector_errors_total"] == 2


# -- tail sampler -----------------------------------------------------------


def test_tail_retains_only_errors_expired_and_slow():
    tail = TailSampler(capacity=64, window=128, recompute=32)
    fast = [(0.001, 0.0, "ok", "lookup", "default", None)] * 100
    tail.offer_batch(fast)  # threshold converges to ~1ms
    tail.offer_batch([
        (0.001, 0.0, "failed", "lookup", "default", "ValueError"),
        (0.001, 0.0, "expired", "lookup", "default", None),
        (5.0, 0.0, "ok", "lookup", "default", None),  # way over p99
    ])
    snap = tail.snapshot()
    assert snap["offered"] == 103
    assert snap["kept_error"] == 1
    assert snap["kept_expired"] == 1
    assert snap["kept_slow"] == 1
    outcomes = [r["outcome"] for r in snap["records"]]
    assert outcomes == ["failed", "expired", "ok"]
    assert snap["records"][0]["error"] == "ValueError"
    assert snap["records"][2]["slow"] is True
    assert snap["p99_threshold_ms"] is not None


def test_tail_retained_ring_is_bounded():
    tail = TailSampler(capacity=8, window=32, recompute=16)
    bad = [(0.001, 0.0, "failed", "lookup", "default", "E")] * 50
    tail.offer_batch(bad)
    snap = tail.snapshot()
    assert snap["retained"] == 8 and snap["offered"] == 50
    assert snap["kept_error"] == 50


# -- flight recorder --------------------------------------------------------


def test_flight_ring_bounded_and_dump_parses(tmp_path):
    rec = FlightRecorder(capacity=8)
    for i in range(20):
        rec.note("tick", i=i)
    rec.attach("ctx", lambda: {"answer": 42})
    path = rec.dump("test:reason", ValueError("boom"), dir=str(tmp_path))
    with open(path) as f:
        payload = json.load(f)
    assert payload["schema_version"] == DUMP_SCHEMA_VERSION
    assert payload["reason"] == "test:reason"
    assert payload["error"] == {"type": "ValueError", "message": "boom"}
    # ring truncated to capacity, oldest dropped
    assert [e["i"] for e in payload["events"]] == list(range(12, 20))
    assert payload["context"]["ctx"] == {"answer": 42}
    # atomic write: no .tmp residue
    assert [p.name for p in tmp_path.iterdir()] == [os.path.basename(path)]
    assert rec.snapshot()["dumps"] == 1


def test_flight_provider_failure_becomes_stub(tmp_path):
    rec = FlightRecorder()
    rec.note("x")

    def bad():
        raise RuntimeError("provider died")

    rec.attach("bad", bad)
    path = rec.dump("r", dir=str(tmp_path))
    with open(path) as f:
        payload = json.load(f)
    assert payload["context"]["bad"] == {"error": "RuntimeError: provider died"}


# -- JSONL pump + rss gauge (satellite 2) -----------------------------------


def test_pump_tick_writes_series_rows_and_rss_gauge(tmp_path):
    plane = TelemetryPlane(
        registry=MetricRegistry(), flight_recorder=FlightRecorder()
    )
    try:
        pump = plane.start_pump(str(tmp_path), interval_s=3600.0)
        assert plane.start_pump(str(tmp_path)) is pump  # idempotent
        pump.tick()
        pump.tick()
        files = [p for p in tmp_path.iterdir()
                 if p.name.startswith("csvplus_metrics.")]
        assert len(files) == 1
        rows = [json.loads(ln) for ln in
                files[0].read_text().splitlines()]
        assert len(rows) == 2
        for row in rows:
            assert row["ts"] > 0
            # the pump's on_tick samples the live-RSS gauge before
            # every row — long-running serve sessions see memory growth
            assert row["series"]["csvplus_process_rss_mb"] > 0
            assert row["series"]["csvplus_process_peak_rss_mb"] > 0
    finally:
        plane.close()


# -- serving-tier integration -----------------------------------------------


def test_server_scrape_carries_serve_index_skew_and_process_series():
    idx, ids = _index()
    draws = zipf_probe_values(ids, 48, seed=5)
    probes = [f"c{int(v)}" for v in draws]
    vals, counts = np.unique(draws, return_counts=True)
    hitter = f"c{int(vals[counts.argmax()])}"
    with LookupServer(idx) as srv:
        for p in probes:
            assert srv.submit(p).result(timeout=30.0)
        text = srv.plane.registry.render()
        snap = srv.plane.registry.sample_dict()
    assert snap["csvplus_serve_completed_total"] >= 48
    assert snap["csvplus_serve_cycles_total"] >= 1
    assert snap['csvplus_index_lookups{index="default"}'] >= 48
    assert snap["csvplus_tail_offered_total"] >= 48
    assert snap["csvplus_process_peak_rss_mb"] > 0
    assert snap['csvplus_skew_observed_total{index="default",side="probe"}'] \
        >= 48
    assert "# TYPE csvplus_serve_completed_total counter" in text
    assert "# TYPE csvplus_serve_latency_ms gauge" in text
    assert 'csvplus_serve_latency_ms{quantile="p99"}' in text
    # the planted hot key is on the skew surface, unwrapped to scalar
    hit = [ln for ln in text.splitlines()
           if ln.startswith("csvplus_skew_topk{")
           and f'key="{hitter}"' in ln and 'side="probe"' in ln]
    assert hit, f"heavy hitter {hitter} missing from csvplus_skew_topk"


def test_server_http_endpoint_scrapes_over_real_http():
    idx, ids = _index()
    with LookupServer(idx) as srv:
        assert srv.submit(f"c{int(ids[3])}").result(timeout=30.0)
        port = srv.plane.serve_http()
        try:
            assert srv.plane.serve_http() == port  # idempotent
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10
            ) as resp:
                assert resp.status == 200
                ctype = resp.headers["Content-Type"]
                body = resp.read().decode()
            assert "text/plain" in ctype
            assert "csvplus_serve_completed_total" in body
        finally:
            srv.plane.close()


def test_dispatch_cycle_lands_in_flight_ring_and_histogram():
    idx, ids = _index()
    plane = TelemetryPlane(
        registry=MetricRegistry(), flight_recorder=FlightRecorder()
    )
    with LookupServer(idx, plane=plane) as srv:
        for v in ids[:6]:
            assert srv.submit(f"c{int(v)}").result(timeout=30.0)
    cycles = [e for e in plane.flight.events() if e["kind"] == "serve:cycle"]
    assert cycles and all(e["ok"] >= 1 for e in cycles)
    snap = plane.registry.sample_dict()
    assert snap["csvplus_serve_cycle_seconds_count"] >= len(cycles)


# -- snapshot schema pinning (satellite 4) ----------------------------------

#: The pinned per-index / per-view cell keys: a dashboard keyed on these
#: must not silently lose a series.  Additions are fine (extend the
#: pins); removals or renames require a SNAPSHOT_SCHEMA_VERSION bump.
INDEX_CELL_KEYS = {
    "lookups", "append_reqs", "delete_reqs", "rows_appended",
    "tiers_probed", "tiers_pruned", "deltas_live", "compactions",
    "compacted_deltas", "compacted_rows", "compact_seconds_total",
    "last_compact_ms", "wal_records", "wal_bytes", "wal_fsyncs",
    "recovered_records",
}
VIEW_CELL_KEYS = {
    "refreshes", "events", "rows_probed", "rows_retracted", "failures",
    "reads", "rows_read", "epoch",
}


def test_snapshot_schema_version_and_pinned_cell_keys():
    from csvplus_tpu import plan as P
    from csvplus_tpu.index import create_index
    from csvplus_tpu.row import Row
    from csvplus_tpu.source import take_rows
    from csvplus_tpu.storage import MutableIndex

    assert SNAPSHOT_SCHEMA_VERSION == 1
    mi = MutableIndex.create(
        take_rows([Row({"oid": f"o{i:04d}", "cust_id": f"c{i % 8:03d}"})
                   for i in range(64)]),
        ["oid"],
        ingest_device="cpu",
    )
    cust = create_index(
        take_rows([Row({"cust_id": f"c{i:03d}", "name": f"n{i}"})
                   for i in range(8)]),
        ["cust_id"],
    )
    cust.on_device("cpu")
    with LookupServer(indexes={"orders": mi}) as srv:
        view = srv.register_view(
            "enriched", P.Join(P.Scan(None), cust, ("cust_id",)),
            source="orders",
        )
        assert srv.submit_append(
            [{"oid": "o9000", "cust_id": "c001"}], index="orders"
        ).result(timeout=30.0) == 1
        assert srv.submit("o0003", index="orders").result(timeout=30.0)
        view.read("o0003")
        snap = srv.snapshot()
    assert snap["schema_version"] == SNAPSHOT_SCHEMA_VERSION
    assert set(snap["by_index"]["orders"]) == INDEX_CELL_KEYS
    assert set(snap["by_view"]["enriched"]) == VIEW_CELL_KEYS
    # and the exposition layer maps every numeric cell onto a series
    # (non-numeric cells — e.g. last_compact_ms before any compaction
    # is None — are rightly absent from the scrape)
    rendered = {s.name for s in serve_samples(snap)}
    for name, prefix in (("by_index", "csvplus_index"),
                         ("by_view", "csvplus_view")):
        for key, v in next(iter(snap[name].values())).items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                assert f"{prefix}_{key}" in rendered


# -- the obs CLI ------------------------------------------------------------


def test_obs_cli_has_one_mode(tmp_path, capsys):
    """`skew` is the CLI's one mode: the stage-table differ went with the
    last producer of a stage-table artifact (PR 41), so `diff` is refused
    by the parser (exit 2), not answered with a second format."""
    a = tmp_path / "a.json"
    a.write_text(json.dumps({"stage_table": []}))
    with pytest.raises(SystemExit) as e:
        obs_main(["diff", str(a), str(a)])
    assert e.value.code == 2
    assert "invalid choice: 'diff'" in capsys.readouterr().err


def test_obs_cli_skew_renders_plane_snapshot(tmp_path, capsys):
    plane = TelemetryPlane(
        registry=MetricRegistry(), flight_recorder=FlightRecorder(),
        sketch_k=8,
    )
    plane.offer_probes("orders", [("c5",)] * 9 + [("c1",)] * 3)
    artifact = tmp_path / "smoke.json"
    artifact.write_text(json.dumps({"skew": plane.skew_snapshot()}))
    rc = obs_main(["skew", str(artifact)])
    assert rc == 0
    out = capsys.readouterr().out
    assert "probe:orders" in out and "c5" in out
    rc = obs_main(["skew", str(artifact), "--json"])
    assert rc == 0
    parsed = json.loads(capsys.readouterr().out)
    assert parsed["probe:orders"]["top"][0]["key"] == "c5"


def test_obs_cli_skew_reads_flight_dump_context(tmp_path, capsys):
    # a flight dump whose context carries a skew section is a valid
    # skew artifact: the post-mortem answers "what was hot when it died"
    rec = FlightRecorder()
    rec.note("x")
    plane = TelemetryPlane(
        registry=MetricRegistry(), flight_recorder=rec, sketch_k=4,
    )
    plane.offer_probes("orders", ["k7"] * 5)
    rec.attach("obs", lambda: {"skew": plane.skew_snapshot()})
    path = rec.dump("test", dir=str(tmp_path))
    rc = obs_main(["skew", path])
    assert rc == 0
    assert "k7" in capsys.readouterr().out
