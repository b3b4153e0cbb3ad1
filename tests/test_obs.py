"""The observability subsystem (csvplus_tpu.obs, docs/OBSERVABILITY.md).

Contracts under test:

* span trees — parenting, contextvars isolation: N concurrent queries
  produce NON-interleaved per-query traces whose shapes match the
  serial run exactly (the failure mode that motivated the subsystem);
* the ``telemetry.stage`` compatibility shim — every existing stage
  call site doubles as a span when a trace is active, with discarded
  and failed stages kept (annotated) in the trace;
* the serving tier's per-request attribution — queue-wait and dispatch
  land in each SUBMITTER's trace with the coalesced batch's
  bounds/gather-decode phases as shared children;
* exporters — Chrome-trace JSON passes its own schema validator and
  carries every span; the JSON-lines sink drains incrementally;
* recompile accounting — registered kernels report zero lowerings over
  a warm repeat and nonzero when a new shape lowers;
* memory probes — current and peak RSS, the bench-artifact host header;
* telemetry hygiene — lock-guarded counters under thread hammering,
  ``merged_stages`` accumulable-extras, ``barrier`` as a strict no-op
  when disabled, and ``report``/``to_json`` carrying counters +
  host_sync_elements.
"""

import json
import os
import threading
import time

import numpy as np
import pytest

import csvplus_tpu as cp
from csvplus_tpu.columnar.table import DeviceTable
from csvplus_tpu.obs import (
    RecompileWatch,
    SpanJsonlSink,
    chrome_trace_events,
    compile_counts,
    host_header,
    peak_rss_mb,
    register_kernel,
    registered_kernels,
    rss_mb,
    tracer,
    validate_chrome_trace,
    write_chrome_trace,
)
from csvplus_tpu.serve import LookupServer
from csvplus_tpu.utils.observe import StageRecord, telemetry


@pytest.fixture(autouse=True)
def _clean_tracer():
    # process-global singletons: scrub between tests
    tracer.reset()
    telemetry.reset()
    yield
    tracer.reset()
    telemetry.reset()


# ---------------------------------------------------------------------------
# span trees
# ---------------------------------------------------------------------------


def test_span_tree_parenting_and_attrs():
    with tracer.trace("q", user="t") as tr:
        with tracer.span("outer", k=1) as attrs:
            attrs["rows"] = 7
            with tracer.span("inner"):
                pass
        with tracer.span("sibling"):
            pass
    spans = {s.name: s for s in tr.snapshot()}
    assert set(spans) == {"q", "outer", "inner", "sibling"}
    root = spans["q"]
    assert root.parent_id is None and root.attrs == {"user": "t"}
    assert spans["outer"].parent_id == root.span_id
    assert spans["inner"].parent_id == spans["outer"].span_id
    assert spans["sibling"].parent_id == root.span_id
    assert spans["outer"].attrs == {"k": 1, "rows": 7}
    for s in spans.values():
        assert s.t_end >= s.t_start
    assert tracer.finished() == [tr]


def test_span_error_annotated_and_raised():
    with pytest.raises(ValueError):
        with tracer.trace("q") as tr:
            with tracer.span("body"):
                raise ValueError("boom")
    body = [s for s in tr.snapshot() if s.name == "body"]
    assert body and body[0].attrs["error"] == "ValueError"


def test_no_active_trace_is_a_cheap_noop():
    assert not tracer.active()
    assert tracer.open_span("x") is None
    with tracer.span("x") as attrs:
        attrs["ignored"] = 1  # throwaway dict, nothing recorded
    assert tracer.add_span("x", 0.1) is None
    assert tracer.finished() == []


def test_stage_shim_opens_spans_and_keeps_discards():
    with tracer.trace("pipeline") as tr:
        with telemetry.stage("work", 10) as out:
            out["rows_out"] = 9
        with telemetry.stage("declined", 10) as out:
            out["discard"] = True
        with pytest.raises(RuntimeError):
            with telemetry.stage("failed", 1):
                raise RuntimeError
    names = [s.name for s in tr.snapshot()]
    # the trace records what HAPPENED: discarded and failed stages stay
    assert names.count("work") == 1
    assert names.count("declined") == 1
    failed = [s for s in tr.snapshot() if s.name == "failed"]
    assert failed[0].attrs.get("error") is True
    # ...but the flat table still records only what counted (telemetry
    # was disabled here, so nothing landed at all)
    assert telemetry.records == []


def test_add_stage_mirrors_premeasured_span():
    with tracer.trace("pipeline") as tr:
        telemetry.add_stage("bulk", 100, 100, 0.25, chunks=4)
    bulk = [s for s in tr.snapshot() if s.name == "bulk"]
    assert len(bulk) == 1
    assert bulk[0].seconds == pytest.approx(0.25, abs=1e-6)
    assert bulk[0].attrs["chunks"] == 4


def _run_query(i, n_stages=4):
    """One synthetic traced query; returns its Trace."""
    with tracer.trace(f"query-{i}", q=i) as tr:
        for j in range(n_stages):
            with telemetry.stage(f"stage-{j}", i) as out:
                out["rows_out"] = i + j
    return tr


def _tree_shape(tr):
    """(name, parent-name, rows_out) triples, order-independent."""
    by_id = {s.span_id: s for s in tr.snapshot()}
    return sorted(
        (s.name, by_id[s.parent_id].name if s.parent_id else None,
         s.attrs.get("rows_out"))
        for s in by_id.values()
    )


def test_concurrent_traces_isolated_and_match_serial():
    """ACCEPTANCE: N threads' concurrent queries produce non-interleaved
    per-query span trees with correct parenting, identical in shape and
    totals to the same queries run serially."""
    n_threads = 8
    serial = [_tree_shape(_run_query(i)) for i in range(n_threads)]
    tracer.reset()

    results = [None] * n_threads
    barrier = threading.Barrier(n_threads)

    def worker(i):
        barrier.wait()  # maximize interleaving
        results[i] = _run_query(i)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    assert len(tracer.finished()) == n_threads
    for i, tr in enumerate(results):
        spans = tr.snapshot()
        # no foreign spans leaked in: every span carries THIS trace's id
        assert all(s.trace_id == tr.trace_id for s in spans)
        assert len(spans) == 5  # root + 4 stages, nothing interleaved
        # identical tree shape and per-stage totals to the serial run
        assert _tree_shape(tr) == serial[i]


# ---------------------------------------------------------------------------
# serving-tier per-request spans
# ---------------------------------------------------------------------------


def _build_index(n=2000):
    ids = np.arange(n, dtype=np.int64) * 7 % (n * 3)
    t = DeviceTable.from_pylists(
        {
            "id": np.char.add("c", ids.astype(np.str_)).tolist(),
            "v": np.arange(n).astype(np.str_).tolist(),
        },
        device="cpu",
    )
    return cp.take(t).index_on("id").sync(), ids


def test_serve_per_request_span_trees():
    idx, ids = _build_index()
    n_clients = 6
    traces = [None] * n_clients
    with LookupServer(idx) as srv:
        barrier = threading.Barrier(n_clients)

        def client(i):
            barrier.wait()
            with tracer.trace(f"client-{i}") as tr:
                rows = srv.submit(f"c{int(ids[i])}").result(timeout=30)
                assert rows
            traces[i] = tr

        threads = [
            threading.Thread(target=client, args=(i,)) for i in range(n_clients)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

    for tr in traces:
        spans = tr.snapshot()
        by_id = {s.span_id: s for s in spans}
        root = tr.root()
        names = [s.name for s in spans]
        # exactly one queue-wait + one dispatch per request, parented
        # under the SUBMITTER's root — not interleaved across clients
        assert names.count("serve:queue-wait") == 1
        assert names.count("serve:dispatch") == 1
        qw = next(s for s in spans if s.name == "serve:queue-wait")
        dsp = next(s for s in spans if s.name == "serve:dispatch")
        assert qw.parent_id == root.span_id
        assert dsp.parent_id == root.span_id
        assert qw.t_start <= dsp.t_start  # queue-wait precedes dispatch
        assert dsp.attrs["outcome"] == "ok"
        # the coalesced batch's phases are children of the cycle span,
        # which lands once in each request's own tree (ISSUE 25; they
        # hung under serve:dispatch before the cycle was a span)
        cyc = next(s for s in spans if s.name == "serve:cycle")
        assert cyc.parent_id == root.span_id
        phases = [
            s for s in spans
            if s.name in ("serve:bounds", "serve:gather-decode")
        ]
        assert len(phases) == 2
        assert all(by_id[s.parent_id] is cyc for s in phases)


def test_serve_plan_spans_nest_executor_stages():
    idx, ids = _build_index()
    from csvplus_tpu import plan as P

    leaf = idx.find(f"c{int(ids[1])}").plan
    node = P.SelectCols(leaf, ("id",))
    with LookupServer(idx) as srv:
        with tracer.trace("plan-client") as tr:
            out = srv.submit_plan(node).result(timeout=30)
            assert cp.take(out).to_rows()
    spans = tr.snapshot()
    by_id = {s.span_id: s for s in spans}
    assert any(s.name == "serve:queue-wait" for s in spans)
    dsp = next(s for s in spans if s.name == "serve:dispatch")
    assert dsp.attrs["kind"] == "plan"
    # the executor's plan:execute grouping span runs INSIDE the adopted
    # dispatch span, in the submitter's trace; a new shape's first run is
    # a milestone, which inside a trace is a plain span between the two
    pe = next(s for s in spans if s.name == "plan:execute")
    first = by_id[pe.parent_id]
    assert first.name == "plan:first-run" and by_id[first.parent_id] is dsp
    assert [s.name for s in spans if by_id.get(s.parent_id) is dsp][0] == "plan:admit"
    # and the per-node stages (telemetry.stage shim) nest under it
    sel = next(s for s in spans if s.name == "SelectCols")
    assert by_id[sel.parent_id] is pe


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------


def test_chrome_trace_export_validates(tmp_path):
    with tracer.trace("run") as tr:
        with tracer.span("a", rows=3):
            with tracer.span("b"):
                pass
        telemetry.add_stage("lane-work", 10, 10, 0.01)
    path = write_chrome_trace(str(tmp_path / "trace.json"))
    with open(path) as f:
        obj = json.load(f)
    assert validate_chrome_trace(obj) == []
    events = obj["traceEvents"]
    x = [e for e in events if e["ph"] == "X"]
    assert {e["name"] for e in x} == {"run", "a", "b", "lane-work"}
    # span identity survives into args; parenting is reconstructible
    b = next(e for e in x if e["name"] == "b")
    a = next(e for e in x if e["name"] == "a")
    assert b["args"]["parent_id"] == a["args"]["span_id"]
    assert a["args"]["rows"] == 3
    assert all(e["ts"] >= 0 for e in x)
    # metadata names the process and every lane
    m = [e for e in events if e["ph"] == "M"]
    assert any(e["name"] == "process_name" for e in m)
    assert len(tr.snapshot()) == len(x)


def test_chrome_trace_validator_catches_malformed():
    assert validate_chrome_trace({"nope": 1})
    assert validate_chrome_trace(42)
    bad_events = [
        {"ph": "X", "ts": 0, "pid": 1, "tid": 1, "dur": 1},  # no name
        {"name": "n", "ph": "X", "ts": 0, "pid": 1, "tid": 1},  # no dur
        {"name": "n", "ph": "X", "ts": -5, "pid": 1, "tid": 1, "dur": 1},
        {"name": "n", "ph": "M", "pid": 1, "tid": 1},  # no args
    ]
    problems = validate_chrome_trace(bad_events)
    assert len(problems) == 4
    # a correct payload — including ts-less metadata — is clean
    assert validate_chrome_trace(
        [
            {"name": "process_name", "ph": "M", "pid": 1, "tid": 0, "args": {}},
            {"name": "s", "ph": "X", "ts": 0.0, "dur": 1.0, "pid": 1, "tid": 1},
        ]
    ) == []


def test_spans_jsonl_sink_drains_incrementally(tmp_path):
    sink = SpanJsonlSink(str(tmp_path / "spans.jsonl"))
    with tracer.trace("one"):
        pass
    assert sink.flush() == 1
    assert sink.flush() == 0  # drained: nothing new
    with tracer.trace("two"):
        with tracer.span("child"):
            pass
    assert sink.flush() == 2
    rows = [json.loads(l) for l in open(sink.path)]
    assert {r["name"] for r in rows} == {"one", "two", "child"}
    assert sink.written == 3
    assert tracer.finished() == []  # drained out of the tracer


def test_chrome_trace_events_empty_without_spans():
    assert chrome_trace_events([]) == []


# ---------------------------------------------------------------------------
# recompile accounting
# ---------------------------------------------------------------------------


def test_registered_kernels_cover_the_warm_path_modules():
    import csvplus_tpu.columnar.table  # noqa: F401 — registration side effect
    import csvplus_tpu.columnar.typed  # noqa: F401
    import csvplus_tpu.ops.join  # noqa: F401

    names = set(registered_kernels())
    # the exact kernels whose eager predecessors caused the r05 warm
    # regression must be accounted
    for k in (
        "typed.translate_dense",
        "typed.translate_sorted",
        "join.pack_qk",
        "table.apply_code_translation",
    ):
        assert k in names, k


def test_recompile_watch_zero_when_warm_and_counts_new_shapes():
    import jax
    import jax.numpy as jnp

    @register_kernel("test.obs_kernel")
    @jax.jit
    def k(x):
        return x + 1

    try:
        k(jnp.arange(4))  # cold: lowers once
        with RecompileWatch() as w:
            k(jnp.arange(4))  # warm: same shape, no lowering
            k(jnp.arange(4))
        assert w.delta() == {}
        w.assert_zero()

        with RecompileWatch() as w2:
            k(jnp.arange(8))  # NEW shape: one lowering
        assert w2.delta() == {"test.obs_kernel": 1}
        with pytest.raises(AssertionError, match="test.obs_kernel"):
            w2.assert_zero("test region")
        assert compile_counts()["test.obs_kernel"] == 2
    finally:
        from csvplus_tpu.obs import recompile as _r

        with _r._REGISTRY_LOCK:
            _r._KERNELS.pop("test.obs_kernel", None)


def test_recompile_watch_tracks_plancache_lowered():
    class FakeCache:
        def __init__(self):
            self.n = 0

        def stats(self):
            return {"lowered": self.n}

    fc = FakeCache()
    with RecompileWatch(plancache=fc) as w:
        fc.n += 2
    assert w.delta()["plancache"] == 2


# ---------------------------------------------------------------------------
# memory watermarks
# ---------------------------------------------------------------------------


def test_rss_probes_report_positive_mb():
    cur, peak = rss_mb(), peak_rss_mb()
    assert cur > 0
    assert peak >= cur * 0.5  # same order; VmHWM can't be far below current


def test_host_header_shape():
    h = host_header()
    assert h["host_cpus"] >= 1
    assert h["platform"] == "cpu"
    assert h["device_kind"] == "cpu"
    assert h["jax_device_count"] >= 1


# ---------------------------------------------------------------------------
# telemetry hygiene (the satellite fixes)
# ---------------------------------------------------------------------------


def test_report_includes_counters_and_host_sync():
    with telemetry.collect():
        with telemetry.stage("s1", 10) as out:
            out["rows_out"] = 5
        telemetry.count("verify.resolution", 3)
        telemetry.count("verify.resolution")
        telemetry.count_sync(17)
        rep = telemetry.report()
    assert "s1" in rep
    assert "counters:" in rep and "verify.resolution" in rep and "4" in rep
    assert "host_sync_elements: 17" in rep


def test_to_json_shape_matches_artifact_embedding():
    with telemetry.collect():
        with telemetry.stage("s1", 10) as out:
            out["rows_out"] = 5
            out["tier"] = "direct"
        telemetry.count("c", 2)
        telemetry.count_sync(3)
        got = telemetry.to_json()
    assert got["counters"] == {"c": 2}
    assert got["host_sync_elements"] == 3
    (row,) = got["stage_table"]
    assert row["stage"] == "s1" and row["rows_in"] == 10
    assert row["rows_out"] == 5 and row["tier"] == "direct"
    assert isinstance(row["seconds"], float)
    json.dumps(got)  # JSON-safe end to end


def test_merged_stages_accumulable_extras_rule():
    with telemetry.collect():
        telemetry.add_stage("ingest:encode", 10, 10, 0.5,
                            workers=4, scan_s=0.2, chunks=3)
        telemetry.add_stage("ingest:encode", 20, 20, 1.0,
                            workers=4, scan_s=0.3, chunks=5)
        telemetry.add_stage("other", 1, 1, 0.1)
        merged = telemetry.merged_stages()
    assert [m.stage for m in merged] == ["ingest:encode", "other"]
    enc = merged[0]
    assert (enc.rows_in, enc.rows_out) == (30, 30)
    assert enc.seconds == pytest.approx(1.5)
    # *_s and chunks accumulate; config-shaped extras take last-wins
    assert enc.extra["scan_s"] == pytest.approx(0.5)
    assert enc.extra["chunks"] == 8
    assert enc.extra["workers"] == 4


def test_barrier_strict_noop_when_disabled(monkeypatch):
    import jax

    calls = []
    monkeypatch.setattr(
        jax, "block_until_ready", lambda x: calls.append(x) or x
    )
    assert not telemetry.enabled
    x = object()
    assert telemetry.barrier(x) is x
    assert calls == []  # disabled: jax is never touched
    with telemetry.collect():
        telemetry.barrier(x)
    assert calls == [x]
    assert telemetry.barrier(None) is None  # None never dispatches


def test_telemetry_mutators_are_thread_safe():
    n_threads, per = 8, 500
    with telemetry.collect():
        barrier = threading.Barrier(n_threads)

        def worker():
            barrier.wait()
            for _ in range(per):
                telemetry.count("hits")
                telemetry.count_sync(2)
                telemetry.add_stage("w", 1, 1, 0.001)

        threads = [threading.Thread(target=worker) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert telemetry.counters["hits"] == n_threads * per
        assert telemetry.host_sync_elements == 2 * n_threads * per
        assert len(telemetry.records) == n_threads * per
        (merged,) = telemetry.merged_stages()
        assert merged.rows_in == n_threads * per


def test_stage_record_str_and_collect_reset():
    r = StageRecord("s", 1, 2, 0.5)
    assert "s" in str(r)
    with telemetry.collect() as records:
        telemetry.count("x")
        with telemetry.stage("a", 1):
            pass
        assert len(records) == 1
    # collect() restores the previous enabled state
    assert not telemetry.enabled


# ---------------------------------------------------------------------------
# ISSUE 25: one emitter on one clock, the shared region, stage waits,
# kernel names on the device
# ---------------------------------------------------------------------------


class _CountingAnnotation:
    """Stands in for jax.profiler.TraceAnnotation: records what was
    constructed, entered and left."""

    made: list = []

    def __init__(self, name, **meta):
        self.name, self.meta = name, meta
        type(self).made.append(self)
        self.entered = self.left = False

    def __enter__(self):
        self.entered = True
        return self

    def __exit__(self, *exc):
        self.left = True


@pytest.fixture
def annotations(monkeypatch):
    import jax.profiler

    _CountingAnnotation.made = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _CountingAnnotation)
    return _CountingAnnotation.made


def test_spans_annotate_the_profiler_only_while_a_trace_is_active(annotations):
    with tracer.span("quiet"):
        pass
    with telemetry.collect():  # the stage table alone opens no annotation
        with telemetry.stage("quiet-stage", 1):
            pass
    assert annotations == []
    with tracer.trace("run") as tr:
        with tracer.span("a"):
            with telemetry.stage("b", 3):
                pass
        h = tracer.open_span("c")
        tracer.close_span(h)
    assert [a.name for a in annotations] == [
        "csvplus:anchor", "csvplus:run", "csvplus:a", "csvplus:b", "csvplus:c",
    ]
    assert all(a.entered and a.left for a in annotations)
    # the anchor ties the trace's perf_counter origin to the profiler's clock
    assert annotations[0].meta == {"trace_id": tr.trace_id, "perf_counter": tr.t_anchor}


def test_export_counts_from_the_anchor_and_applies_the_profilers_offset():
    with tracer.trace("run") as tr:
        t0 = time.perf_counter()
        tracer.record_span(tr, tr.root, "after-the-fact", t0, t0 + 0.002)
    events = chrome_trace_events([tr], anchor_ts_us=5000.0)
    anchor = next(e for e in events if e["name"] == "csvplus:anchor")
    assert anchor["ph"] == "i" and anchor["ts"] == 5000.0
    assert anchor["args"] == {"trace_id": tr.trace_id, "perf_counter": tr.t_anchor}
    late = next(e for e in events if e["name"] == "after-the-fact")
    assert late["ts"] == pytest.approx(5000.0 + (t0 - tr.t_anchor) * 1e6, abs=1e-2)
    assert late["dur"] == pytest.approx(2000.0, abs=1e-2)
    assert validate_chrome_trace(events) == []
    # without the profiler's reading the anchor is the origin
    assert next(
        e for e in chrome_trace_events([tr]) if e["name"] == "csvplus:anchor"
    )["ts"] == 0.0


def test_anchor_places_recorded_spans_on_the_profilers_clock(tmp_path):
    """A live span's annotation in the profile and the same span placed
    through the anchor agree: one clock."""
    import glob

    import jax
    from jax.profiler import ProfileData

    from csvplus_tpu.obs.export import anchor_in_profile

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with tracer.trace("run") as tr:
            with tracer.span("live"):
                time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    at = anchor_in_profile(path, tr.trace_id)
    assert at is not None and anchor_in_profile(path, tr.trace_id + 10_000) is None
    seen = [
        ev.start_ns / 1e3
        for plane in ProfileData.from_file(path).planes
        for line in plane.lines
        for ev in line.events
        if ev.name == "csvplus:live"
    ]
    assert len(seen) == 1
    placed = next(
        e for e in chrome_trace_events([tr], anchor_ts_us=at) if e["name"] == "live"
    )
    assert placed["ts"] == pytest.approx(seen[0], abs=500.0)  # microseconds


def test_a_profile_started_after_the_trace_still_holds_an_anchor(tmp_path):
    """The harness's order: ``tracer.trace`` opens, THEN the profiler
    starts, so ``csvplus:anchor`` is in no profile.  Every span opened
    directly under the root carries its own ``perf_counter``, and
    ``anchor_in_profile`` places the trace through those."""
    import glob

    import jax
    from jax.profiler import ProfileData

    from csvplus_tpu.obs.export import anchor_in_profile, profile_clock_offsets

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with tracer.trace("bench-window") as tr:
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            for _ in range(3):
                with tracer.span("plan:execute"):
                    with tracer.span("deeper"):  # depth 2: no anchor of its own
                        time.sleep(0.001)
            t0 = time.perf_counter()
            time.sleep(0.001)
            late = tracer.record_span(tr, tr.root_id, "serve:queue-wait", t0, t0 + 0.001)
        finally:
            jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    events = [
        ev
        for plane in ProfileData.from_file(path).planes
        for line in plane.lines
        for ev in line.events
        if ev.name.startswith("csvplus:")
    ]
    assert not any(ev.name == "csvplus:anchor" for ev in events)
    assert anchor_in_profile(path, tr.trace_id) is None  # a bare id: the annotation only
    offsets = profile_clock_offsets(path)
    assert len(offsets) == 3  # the three depth-1 spans, not the deeper ones
    assert max(offsets) - min(offsets) < 500.0  # microseconds; one clock
    at = anchor_in_profile(path, tr)
    assert at is not None
    seen = sorted(ev.start_ns / 1e3 for ev in events if ev.name == "csvplus:plan:execute")
    placed = sorted(
        e["ts"] for e in chrome_trace_events([tr], anchor_ts_us=at)
        if e["name"] == "plan:execute"
    )
    assert placed == pytest.approx(seen, abs=500.0)
    # and the span written after the fact lies between them on that axis
    after = next(
        e for e in chrome_trace_events([tr], anchor_ts_us=at) if e["name"] == late.name
    )
    assert after["ts"] > placed[-1]


def test_shared_region_lands_once_in_every_context(annotations):
    with tracer.trace("one") as t1:
        c1 = tracer.capture()
        with tracer.span("deeper"):
            c1b = tracer.capture()
    with tracer.trace("two") as t2:
        c2 = tracer.capture()
    del annotations[:]
    with tracer.shared("cycle", [c1, c2, c1, c1b], batch=4) as attrs:
        attrs["note"] = "x"
        with tracer.span("phase") as p:
            p["host_syncs"] = 2
            with tracer.span("leaf"):
                pass
        assert tracer.active()
    assert not tracer.active()
    assert [a.name for a in annotations] == [
        "csvplus:cycle", "csvplus:phase", "csvplus:leaf",
    ]
    seen_ids = set()
    for tr, parents in ((t1, {c1[1], c1b[1]}), (t2, {c2[1]})):
        spans = tr.snapshot()
        by_id = {s.span_id: s for s in spans}
        cycles = [s for s in spans if s.name == "cycle"]
        # once per distinct (trace, parent): c1 twice is one copy
        assert {s.parent_id for s in cycles} == parents and len(cycles) == len(parents)
        for cyc in cycles:
            assert cyc.attrs == {"batch": 4, "note": "x"} and cyc.trace_id == tr.trace_id
            (phase,) = [s for s in spans if s.name == "phase" and s.parent_id == cyc.span_id]
            (leaf,) = [s for s in spans if s.name == "leaf" and s.parent_id == phase.span_id]
            assert phase.attrs == {"host_syncs": 2}
            assert cyc.t_start <= phase.t_start <= leaf.t_start <= leaf.t_end <= phase.t_end <= cyc.t_end
            ids = {cyc.span_id, phase.span_id, leaf.span_id}
            assert not ids & seen_ids  # every copy has ids of its own
            seen_ids |= ids
        assert by_id  # the trees stayed whole
    times = {
        (s.name, s.t_start, s.t_end)
        for tr in (t1, t2) for s in tr.snapshot() if s.name in ("cycle", "phase", "leaf")
    }
    assert len(times) == 3  # the same moments in every tree


def test_suspend_leaves_the_context_and_resume_returns():
    assert tracer.suspend() is None  # nothing to leave
    tracer.resume(None)
    with tracer.trace("run"):
        ctx = tracer.capture()
        token = tracer.suspend()
        assert tracer.capture() is None and not tracer.active()
        tracer.resume(token)
        assert tracer.capture() == ctx


def test_stage_says_whether_it_blocked_on_the_device_and_for_how_long():
    import jax.numpy as jnp

    x = jnp.arange(1000)
    with telemetry.collect() as recs:
        with telemetry.stage("blocked", 1000):
            telemetry.barrier(x + 1)
            telemetry.barrier(x + 2)  # waits add up
        with telemetry.stage("free", 1000):
            _ = x + 3
        with telemetry.stage("outer", 1):
            with telemetry.stage("inner", 1):
                telemetry.barrier(x)
    by = {r.stage: r for r in recs}
    assert by["blocked"].extra["synced"] is True
    assert 0.0 <= by["blocked"].extra["wait_s"] <= by["blocked"].seconds
    assert by["free"].extra == {}
    # the innermost open stage is the one that blocked
    assert "synced" in by["inner"].extra and by["outer"].extra == {}
    with tracer.trace("run") as tr, telemetry.collect():
        with telemetry.stage("blocked", 1):
            telemetry.barrier(x)
    span = next(s for s in tr.snapshot() if s.name == "blocked")
    assert span.attrs["synced"] is True and "wait_s" in span.attrs
    # collection off: a strict no-op that records nothing
    with telemetry.stage("off", 1) as out:
        telemetry.barrier(x)
    assert out == {}
    merged = telemetry.merged_stages()
    assert [r.stage for r in merged] == ["blocked"]


def test_count_sync_counts_reads_beside_elements():
    telemetry.count_sync(5)  # disabled: nothing
    assert (telemetry.host_syncs, telemetry.host_sync_elements) == (0, 0)
    with telemetry.collect():
        telemetry.count_sync(64)
        telemetry.count_sync(3)
        assert (telemetry.host_syncs, telemetry.host_sync_elements) == (2, 67)
        assert telemetry.to_json()["host_syncs"] == 2
    telemetry.reset()
    assert telemetry.host_syncs == 0


def _kernel_examples():
    """name -> (args, kwargs) that lower each single-device kernel."""
    import jax.numpy as jnp

    i = jnp.arange(8, dtype=jnp.int32)
    ok = i >= 0
    return {
        "join.probe_i32pair": ((i, i, i, i, i, i, ok), {}),
        "join.probe_direct": ((i, i, i), {}),
        "join.probe_i32": ((i, i, i), {}),
        "serve.bounds_search": ((i, i), {}),
        "join.build_direct_cum": ((i,), {"total_bits": 4}),
        "join.pack_qk": (((i, i),), {"shifts": (4, 0)}),
        "join.compose_probe": ((i, i, jnp.int32(0), jnp.int32(1)), {}),
        "join.probe_composed": ((i, jnp.int32(0), i, None), {}),
        "join.probe_composed_range": ((i, None, jnp.int32(8)), {}),
        "join.expand": ((i, i), {"padded_total": 16}),
        "join.expand_head": (((i, i),), {"total": 5}),
        "join.gather_lane": ((i, i), {}),
        "join.gather_cols": ((((i,), (i, i)), (i, i)), {"vmem": (False, "interpret")}),
        "join.gather_runs": (((i, i), i, i), {"padded": 32, "kernel": "interpret"}),
        "join.probe_stats": ((i, i), {}),
        "join.multiway_stats": (((i, i),), {}),
        "join.compact_partial": (((i, i), (i, i)), {"padded": 8}),
        "join.multiway_expand": (((i, i), (i, i)), {"padded_total": 16}),
        "typed.translate_dense": ((i, jnp.int32(0), i), {}),
        "typed.translate_sorted": ((i, i, i), {}),
        "typed.translate_empty": ((i,), {}),
        "table.gather_take": ((i, i), {}),
        "table.gather_take_rows": ((jnp.stack([i, i]), (i, i)), {}),
        "table.apply_code_translation": ((i, i), {}),
        "table.sync_probe": ((i, i), {}),
        "index.sort": (((i, i, i),), {"num_keys": 2}),
        "index.adjacent_dup": ((i, i), {}),
        "dedup.runs": ((i, i), {"policy": "last"}),
        "dedup.compact": ((i > 3, (i, i)), {}),
        "dedup.head": (((i, i),), {"kept": 3}),
    }


# the names of _kernel_examples(), spelled out so that collection touches no array
KERNELS_LOWERED_HERE = sorted([
    "join.probe_i32pair", "join.probe_direct", "join.probe_i32", "serve.bounds_search",
    "join.build_direct_cum", "join.pack_qk", "join.expand", "join.expand_head", "join.gather_lane",
    "join.gather_cols", "join.gather_runs", "join.probe_stats", "join.multiway_stats", "join.compact_partial",
    "join.multiway_expand", "typed.translate_dense", "typed.translate_sorted",
    "typed.translate_empty", "table.gather_take", "table.gather_take_rows",
    "table.apply_code_translation",
    "table.sync_probe", "join.compose_probe", "join.probe_composed", "join.probe_composed_range",
    "index.sort", "index.adjacent_dup", "dedup.runs", "dedup.compact",
    "dedup.head",
])


@pytest.mark.parametrize("name", KERNELS_LOWERED_HERE)
def test_registered_kernel_lowers_under_its_scope_and_program_name(name):
    """What names a kernel on the device: the program is called
    jit_csvplus.<name> (the XLA Modules line of a TPU profile) and every
    operation's op_name starts under the csvplus.<name> scope."""
    import csvplus_tpu.columnar.typed  # noqa: F401 — registration side effect
    import csvplus_tpu.ops.join  # noqa: F401
    import csvplus_tpu.ops.sort  # noqa: F401

    args, kwargs = _kernel_examples()[name]
    text = registered_kernels()[name].lower(*args, **kwargs).as_text(debug_info=True)
    assert f"module @jit_csvplus.{name} " in text
    assert f"jit(csvplus.{name})/csvplus.{name}/" in text


def test_every_registered_kernel_is_named_and_the_lowered_set_is_whole():
    import csvplus_tpu.columnar.typed  # noqa: F401
    import csvplus_tpu.ops.join  # noqa: F401
    import csvplus_tpu.parallel.pjoin  # noqa: F401

    kernels = {k: f for k, f in registered_kernels().items() if not k.startswith("test.")}
    for name, fn in kernels.items():
        assert fn.__name__ == f"csvplus.{name}"  # what jit calls the program
    # the mesh kernels need devices to lower; everything else lowers above
    mesh_only = ("pjoin.", "dsort.")
    assert {k for k in kernels if not k.startswith(mesh_only)} == set(KERNELS_LOWERED_HERE)
    assert set(_kernel_examples()) == set(KERNELS_LOWERED_HERE)


def test_warm_join_and_lookup_pass_recompiles_nothing(monkeypatch):
    """RecompileWatch.assert_zero over a warm pass still holds with the
    kernels jitted, named and registered by one decorator — the lookup
    past the mirror cap included (its searchsorted and gathers are
    registered kernels now)."""
    from csvplus_tpu.ops.join import DeviceIndex

    monkeypatch.setattr(DeviceIndex, "POINT_MIRROR_MAX_KEYS", 100)
    idx, ids = _build_index(n=3_000)
    orders = DeviceTable.from_pylists(
        {"id": [f"c{int(v)}" for v in ids[:500]], "q": [str(i) for i in range(500)]},
        device="cpu",
    )
    probes = [f"c{int(v)}" for v in ids[:16]]

    def one_pass():
        joined = cp.take(orders).join(idx, "id").to_rows()
        found = idx._impl.find_rows_many([(p,) for p in probes])
        return len(joined), [len(b) for b in found]

    cold = one_pass()
    with RecompileWatch() as w:
        assert one_pass() == cold
    w.assert_zero("warm join + lookup pass")
    counts = compile_counts()
    assert counts["serve.bounds_search"] >= 1 and counts["table.gather_take_rows"] >= 1


@pytest.mark.parametrize("index_keys", ["dense", "sparse"])
def test_partitioned_join_says_how_the_owner_answered(index_keys, monkeypatch):
    """``join:partition`` records each index's widest slice span and
    whether it took the positional form, ``join:all_to_all`` the tier
    the owner ran and the gather rounds of its search: a one-column key
    is a contiguous run of dictionary codes a shard (positional, 0); a
    two-column packed key has a gap of 32 between neighbours, past the
    bound lowered here to 2**10 (search, and its rounds)."""
    from csvplus_tpu.columnar.ingest import source_from_table
    from csvplus_tpu.ops.join import DeviceIndex, _searchsorted_rounds
    from csvplus_tpu.parallel.mesh import make_mesh

    monkeypatch.setattr(DeviceIndex, "PARTITION_MIN_KEYS", 1)
    monkeypatch.setattr(DeviceIndex, "DIRECT_MAX_BITS", 10)
    n_keys, shards = 1_200, 8
    on = ("a",) if index_keys == "dense" else ("a", "b")
    idx = cp.TakeRows(
        [cp.Row({"a": f"c{i:04d}", "b": f"x{i % 31:02d}", "v": str(i)}) for i in range(n_keys)]
    ).index_on(*on)
    idx.on_device("cpu")
    cust = np.random.default_rng(3).integers(0, n_keys, 4_000)
    table = DeviceTable.from_pylists(
        {"a": [f"c{v:04d}" for v in cust], "b": [f"x{v % 31:02d}" for v in cust]},
        device="cpu",
    )
    want = cp.take(source_from_table(table)).join(idx, *on).to_rows()
    with telemetry.collect() as recs:
        got = source_from_table(table.with_sharding(make_mesh(shards))).join(idx, *on).to_rows()
    assert got == want and len(got) == 4_000
    (part,) = [r.extra for r in recs if r.stage == "join:partition"]
    (exchange,) = [r.extra for r in recs if r.stage == "join:all_to_all"]
    per_shard = n_keys // shards
    if index_keys == "dense":
        assert part["span_max"] == per_shard and part["positional"] is True
        assert (exchange["owner_tier"], exchange["search_rounds"]) == ("positional", 0)
    else:
        assert part["span_max"] > 2**10 and part["positional"] is False
        assert exchange["owner_tier"] == "search"
        assert exchange["search_rounds"] == _searchsorted_rounds(per_shard) == 8


@pytest.mark.parametrize(
    "case, tier, walks",
    [
        ("dense", "dense", 1),  # ids 0..49: one table read by position
        ("sorted", "sorted", 8),  # 40 ids 5,000 apart: bit_length(40) + 2 rounds
        ("codes", "codes", 1),  # a string probe column: its code translation
        ("two-columns", "dense,codes", 2),  # one name per key column, in key order
    ],
)
def test_translate_stage_says_which_tier_ran(case, tier, walks):
    """``join:translate`` names the table each probe column went through
    beside the ``row_gathers`` that took (``owner_tier`` on
    ``join:all_to_all`` is its like): a typed column's ``dense`` |
    ``sorted`` state, ``codes`` for a string column.  Streams short
    enough that nothing composes, so the staged stage runs."""
    import jax.numpy as jnp

    from csvplus_tpu.columnar.table import StringColumn
    from csvplus_tpu.columnar.typed import IntColumn
    from csvplus_tpu.ops.join import DeviceIndex
    from csvplus_tpu.ops.sort import sort_table

    ids = [i * 5000 for i in range(40)] if case == "sorted" else list(range(50))
    keys = ["id", "name"] if case == "two-columns" else ["id"]
    people = DeviceTable.from_pylists(
        {"id": [f"c{i}" for i in ids], "name": [f"n{i % 7}" for i in ids]}, device="cpu"
    )
    di = DeviceIndex.build(sort_table(people, keys), keys)
    n = 60
    picks = np.random.default_rng(32).choice(ids, n)
    if case == "codes":
        probe = [StringColumn.from_values([f"c{v}" for v in picks], None)]
    else:
        probe = [IntColumn(b"c", jnp.asarray(picks.astype(np.int32)))]
    if case == "two-columns":
        probe.append(StringColumn.from_values([f"n{v % 7}" for v in picks], None))
    with telemetry.collect() as recs:
        _, counts = di.probe(probe, n)
    (translate,) = [r.extra for r in recs if r.stage == "join:translate"]
    assert (translate["tier"], translate["row_gathers"]) == (tier, walks)
    assert np.asarray(counts).tolist() == [1] * n
