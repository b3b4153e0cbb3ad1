"""The process journal (``obs/span.py``: ``tracer.milestone``,
``tracer.journal``, ``journal_compiles``).

Contracts under test:

* a milestone outside a trace opens a root in the journal and is the
  current context for its body, so ``telemetry.stage`` / ``add_stage`` /
  ``tracer.span`` land beneath it with collection off; inside a trace it
  is a plain child and the journal hears nothing;
* worker lanes join by ``capture`` / ``adopt`` (the streamed ingest's
  producer thread does);
* the journal is bounded by spans, drops whole oldest trees and counts
  them; ``collect()`` and ``tracer.reset()`` leave it alone;
* the milestones sit where the work happens: ``ingest``, ``index:build``,
  ``plan:admit`` / ``plan:first-run``, ``serve:start``,
  ``storage:recover``, with the new stages beneath them
  (``typed:demote:*``, ``typed:parse-dictionary``, ``ingest:dictionary``,
  ``ingest:union``);
* a warm execution records NOTHING: 100 ``PlanCache.execute`` hits and
  100 served batches append 0 spans; a forced recompile appears as
  ``compile`` events naming the program;
* ``barrier()`` is still a no-op under a milestone (a milestone forces
  no device sync);
* the disabled path (no trace, no milestone) costs under 2% of the
  micro lookup shape: the gate ``make trace-smoke`` held until PR 31.
"""

import json
import threading
import time

import numpy as np
import pytest

import csvplus_tpu as cp
from csvplus_tpu import plan as P
from csvplus_tpu.columnar.table import DeviceTable
from csvplus_tpu.obs import flight
from csvplus_tpu.obs.export import chrome_trace_events, validate_chrome_trace
from csvplus_tpu.obs.span import Journal, tracer
from csvplus_tpu.serve import LookupServer, PlanCache
from csvplus_tpu.utils.observe import telemetry

native = pytest.importorskip("csvplus_tpu.native.scanner")


@pytest.fixture(autouse=True)
def _clean():
    tracer.reset()
    telemetry.reset()
    yield
    tracer.reset()
    telemetry.reset()


def _since(mark):
    """Journal spans appended after *mark* (a span id), with a map by id
    and a name -> parent's name view."""
    spans = [s for s in tracer.journal.snapshot() if s.span_id > mark]
    by_id = {s.span_id: s for s in spans}
    parents = {
        s.name: (by_id[s.parent_id].name if s.parent_id in by_id else None)
        for s in spans
    }
    return spans, by_id, parents


def _mark():
    return tracer._start(0, None, "mark", None).span_id  # ids are process-wide


def _people(n=3000):
    ids = np.arange(n, dtype=np.int64) * 7 % (n * 3)
    table = DeviceTable.from_pylists(
        {
            "id": np.char.add("c", ids.astype(np.str_)).tolist(),
            "v": np.arange(n).astype(np.str_).tolist(),
        },
        device="cpu",
    )
    return table, ids


# -- the milestone ------------------------------------------------------------


def test_milestone_outside_a_trace_lands_in_the_journal_with_its_shim_children():
    mark = _mark()
    assert not tracer.active() and not telemetry.enabled
    with tracer.milestone("ingest", tier="test") as at:
        assert tracer.active()  # the body's current context
        at["rows"] = 7
        with telemetry.stage("ingest:streamed", 0) as st:
            st["rows_out"] = 7
            telemetry.add_stage("ingest:scan", 7, 7, 0.25, workers=2)
            with tracer.span("leaf"):
                pass
    assert not tracer.active()
    spans, by_id, parents = _since(mark)
    assert parents == {
        "ingest": None, "ingest:streamed": "ingest",
        "ingest:scan": "ingest:streamed", "leaf": "ingest:streamed",
    }
    root = next(s for s in spans if s.name == "ingest")
    assert root.attrs == {"tier": "test", "rows": 7} and root.trace_id == tracer.journal.trace_id
    scan = next(s for s in spans if s.name == "ingest:scan")
    assert scan.seconds == pytest.approx(0.25) and scan.attrs["workers"] == 2
    # the stage table heard nothing (collection is off), no trace finished
    assert telemetry.records == [] and tracer.finished() == []


def test_milestone_inside_a_trace_is_a_plain_child():
    mark = _mark()
    with tracer.trace("window") as tr:
        with tracer.milestone("index:build", keys="id") as at:
            at["rows"] = 3
            with telemetry.stage("index:sort", 3):
                pass
    assert _since(mark)[0] == []
    by_name = {s.name: s for s in tr.snapshot()}
    assert by_name["index:build"].parent_id == by_name["window"].span_id
    assert by_name["index:sort"].parent_id == by_name["index:build"].span_id
    assert by_name["index:build"].attrs == {"keys": "id", "rows": 3}


def test_a_failing_milestone_is_kept_and_says_so():
    mark = _mark()
    with pytest.raises(ValueError):
        with tracer.milestone("storage:recover"):
            raise ValueError("torn")
    (span,) = _since(mark)[0]
    assert span.attrs == {"error": "ValueError"} and not tracer.active()


def test_worker_lanes_adopt_into_the_milestone():
    mark = _mark()
    with tracer.milestone("serve:start"):
        ctx = tracer.capture()

        def lane():
            assert not tracer.active()  # a new thread starts outside
            with tracer.adopt(ctx):
                with tracer.span("lane-work"):
                    telemetry.add_stage("lane-total", 1, 1, 0.01)

        t = threading.Thread(target=lane, name="lane-7")
        t.start()
        t.join()
    spans, by_id, parents = _since(mark)
    assert parents["lane-work"] == "serve:start" and parents["lane-total"] == "lane-work"
    assert {s.lane for s in spans if s.name.startswith("lane")} == {"lane-7"}


def test_journal_is_bounded_and_counts_dropped_trees():
    from csvplus_tpu.obs.span import Span

    j = Journal(trace_id=99, limit=64)
    ids = iter(range(1, 10_000))

    def record(sid, parent):
        j.add(Span(99, sid, parent, "x", 0.0, 1.0, "t"))

    def tree(children):
        root = next(ids)
        for _ in range(children):
            record(next(ids), root)
        record(root, None)  # a root closes after its children
        return root

    roots = [tree(children=7) for _ in range(8)]  # 64 spans: at the limit
    assert len(j.snapshot()) == 64 and j.dropped == 0
    open_child = next(ids)
    record(open_child, 9_999)  # its root is still open: never dropped as a tree
    kept = j.snapshot()
    # trimmed to 7/8 of the limit by whole oldest trees
    assert len(kept) <= 56 and j.dropped == 2
    left = {s.span_id for s in kept}
    assert roots[0] not in left and roots[1] not in left and roots[2] in left
    assert open_child in left
    for r in roots[2:]:  # every tree that is left is whole
        assert sum(1 for s in kept if s.parent_id == r) == 7
    # one open tree that outgrows the journal is cut from its oldest end
    for _ in range(200):
        record(next(ids), 9_999)
    assert len(j.snapshot()) <= 64 and j.dropped >= 9


def test_collect_and_reset_leave_the_journal():
    mark = _mark()
    with tracer.milestone("ingest"):
        pass
    with telemetry.collect():
        with telemetry.stage("quiet", 1):
            pass
    tracer.reset()
    telemetry.reset()
    assert [s.name for s in _since(mark)[0]] == ["ingest"]
    assert tracer.journal.t_anchor_ns > 0 and tracer.journal.t_anchor > 0


def test_barrier_is_still_a_noop_under_a_milestone(monkeypatch):
    import jax

    calls = []
    monkeypatch.setattr(jax, "block_until_ready", lambda x: calls.append(x) or x)
    with tracer.milestone("plan:first-run"):
        with telemetry.stage("join:probe", 4) as out:
            assert telemetry.barrier([1, 2]) == [1, 2]
    assert calls == [] and "synced" not in out
    with telemetry.collect(), tracer.milestone("plan:first-run"):
        telemetry.barrier([3])
    assert calls == [[3]]


# -- where the work happens ---------------------------------------------------


def _csv(tmp_path, n=400):
    path = tmp_path / "orders.csv"
    path.write_text(
        "id,grp,qty,note\n"
        + "".join(f"o{i},g{i % 5}z,{i % 9},n{i % 37}x\n" for i in range(n))
    )
    return str(path)


@pytest.mark.parametrize("tier", ["streamed", "whole-file"])
def test_ingest_is_a_milestone_with_its_stages_beneath(tmp_path, monkeypatch, tier):
    if tier == "streamed":
        monkeypatch.setenv("CSVPLUS_STREAM_MIN_BYTES", "1")
        monkeypatch.setenv("CSVPLUS_STREAM_CHUNK_BYTES", "512")
        monkeypatch.setenv("CSVPLUS_INGEST_WORKERS", "2")
    path = _csv(tmp_path)
    mark = _mark()
    src = cp.from_file(path).on_device("cpu")
    spans, by_id, parents = _since(mark)
    root = next(s for s in spans if s.name == "ingest")
    assert root.parent_id is None
    assert root.attrs["rows"] == 400 and root.attrs["bytes"] > 0
    assert root.attrs["columns"] == "int:2,str:2" and root.attrs["shards"] == 1
    if tier == "streamed":
        assert root.attrs["tier"] == "streamed"
        assert parents["ingest:streamed"] == "ingest"
        for name in ("ingest:scan", "ingest:place", "ingest:dictionary", "ingest:union"):
            assert parents[name] == "ingest:streamed", name
        # the producer thread's closing totals joined by capture/adopt
        for name in ("ingest:cut", "ingest:encode", "ingest:reorder-stall"):
            assert parents[name] == "ingest:streamed", name
        assert {s.lane for s in spans if s.name == "ingest:encode"} == {"csvplus-relay"}
        dictionary = next(s for s in spans if s.name == "ingest:dictionary")
        place = next(s for s in spans if s.name == "ingest:place")
        assert 0 < dictionary.seconds <= place.seconds  # the running union is inside place
        assert next(s for s in spans if s.name == "ingest:union").seconds > 0
    else:
        assert root.attrs["tier"] in ("device-parsed", "native-encoded")
        assert parents["ingest:" + root.attrs["tier"]] == "ingest"
    assert src.to_rows() == cp.Take(cp.from_file(path)).to_rows()


def test_a_lane_dictionary_materializes_once_as_a_milestone(tmp_path, monkeypatch):
    """A high-cardinality string column ships its dictionary as device
    lanes with the union sort deferred; the first reader of the host
    dictionary pays the sort and the download, once, under a name."""
    monkeypatch.setenv("CSVPLUS_STREAM_MIN_BYTES", "1")
    monkeypatch.setenv("CSVPLUS_STREAM_CHUNK_BYTES", "512")
    monkeypatch.setenv("CSVPLUS_DICT_DEVICE_MIN_DISTINCT", "1")
    src = cp.from_file(_csv(tmp_path)).on_device("cpu")
    col = src.plan.table.columns["note"]
    mark = _mark()
    assert len(col.dictionary) == 37
    spans, by_id, parents = _since(mark)
    root = next(s for s in spans if s.name == "lane-dict:materialize")
    assert root.parent_id is None and root.attrs["entries"] >= 37
    assert parents["lane-dict:deferred-sort"] == "lane-dict:materialize"
    mark = _mark()
    assert len(col.dictionary) == 37 and _since(mark)[0] == []  # cached


def test_index_build_is_a_milestone_and_a_first_build_names_its_demote():
    table, ids = _people()
    table = DeviceTable.from_encoded(
        {"id": ("int", b"c", np.asarray(ids, dtype=np.int32)), "v": table.columns["v"]},
        table.nrows, device="cpu",
    ) if hasattr(DeviceTable, "from_encoded") else table
    mark = _mark()
    index = cp.take(table).index_on("id").sync()
    spans, by_id, parents = _since(mark)
    root = next(s for s in spans if s.name == "index:build")
    assert root.parent_id is None and root.attrs == {"keys": "id", "rows": len(index)}
    for name in ("index:view", "index:sort", "index:permute", "index:pack"):
        assert parents[name] == "index:build", name
    if "typed:demote" in parents:
        parts = [n for n in parents if n.startswith("typed:demote:")]
        assert sorted(parts) == [
            "typed:demote:format", "typed:demote:order",
            "typed:demote:remap", "typed:demote:unique",
        ]
        assert {parents[n] for n in parts} == {"typed:demote"}
        demote = next(s for s in spans if s.name == "typed:demote")
        assert demote.attrs["entries"] == len(set(ids.tolist()))
    # the second build of the same table demotes nothing
    mark = _mark()
    cp.take(table).index_on("id").sync()
    assert not any(n.startswith("typed:") for n in _since(mark)[2])


def test_typed_column_demotes_in_named_parts():
    from csvplus_tpu.columnar.typed import IntColumn
    import jax.numpy as jnp

    col = IntColumn(b"c", jnp.asarray(np.array([5, 3, 5, 11, 3], dtype=np.int32)))
    mark = _mark()
    with tracer.milestone("index:build"), telemetry.collect() as records:
        codes = np.asarray(col.codes)
    assert col.dictionary.tolist() == [b"c11", b"c3", b"c5"] and codes.tolist() == [2, 1, 2, 0, 1]
    names = [r.stage for r in records]
    assert names == [
        "typed:demote:unique", "typed:demote:format", "typed:demote:order",
        "typed:demote:remap", "typed:demote",
    ]
    remap = next(r for r in records if r.stage == "typed:demote:remap")
    assert remap.extra.get("synced") is True  # collecting, the search's device time lands here
    assert _since(mark)[2]["typed:demote:remap"] == "typed:demote"


def test_a_typed_probe_names_the_dictionary_parse_once():
    people, ids = _people(500)
    idx = cp.take(people).index_on("id").sync()
    orders = DeviceTable.from_encoded(
        {"cust": ("int", b"c", np.asarray(ids[:200], dtype=np.int32))}, 200, device="cpu"
    )
    plan = cp.take(orders).join(idx, "cust").plan
    cache = PlanCache()
    mark = _mark()
    out = cache.execute(plan)
    assert out.nrows == 200
    spans, by_id, parents = _since(mark)
    parse = [s for s in spans if s.name == "typed:parse-dictionary"]
    assert len(parse) == 1 and parse[0].attrs["entries"] == 500
    build = next(s for s in spans if s.name == "typed:build-translation")
    assert build.attrs["tier"] in ("dense", "sorted")

    def root_of(s):
        while s.parent_id in by_id:
            s = by_id[s.parent_id]
        return s.name

    assert root_of(parse[0]) == "plan:first-run"
    mark = _mark()
    cache.execute(plan)
    assert _since(mark)[0] == []  # cached on the build side: parsed once


def test_admission_and_first_run_are_milestones_and_a_hit_is_neither():
    people, ids = _people(500)
    idx = cp.take(people).index_on("id").sync()
    leaf = idx.find(f"c{int(ids[1])}").plan
    node = P.SelectCols(leaf, ("id",))
    cache = PlanCache()
    mark = _mark()
    cache.execute(node)
    spans, by_id, parents = _since(mark)
    roots = [s.name for s in spans if s.parent_id is None and s.name != "compile"]
    assert roots == ["plan:admit", "plan:first-run"]
    assert parents["plan:verify"] == "plan:admit" and parents["plan:optimize"] == "plan:admit"
    assert parents["plan:execute"] == "plan:first-run" and parents["SelectCols"] == "plan:execute"
    admit = next(s for s in spans if s.name == "plan:admit")
    assert admit.attrs["nodes"] == 2 and admit.attrs["optimized"] in (True, False)


def test_a_hundred_warm_plancache_hits_append_nothing():
    people, ids = _people(500)
    idx = cp.take(people).index_on("id").sync()
    orders = DeviceTable.from_pylists(
        {"cust": [f"c{int(i)}" for i in ids[:300]], "qty": ["1"] * 300}, device="cpu"
    )
    plan = cp.take(orders).join(idx, "cust").plan
    cache = PlanCache()
    want = cache.execute(plan).nrows
    cache.execute(plan)  # every program of the warm path has run
    before = len(tracer.journal.snapshot())
    for _ in range(100):
        assert cache.execute(plan).nrows == want
    assert len(tracer.journal.snapshot()) == before
    assert cache.stats()["hits"] >= 100 and tracer.journal.dropped == 0


def test_a_hundred_served_batches_append_nothing():
    people, ids = _people(500)
    idx = cp.take(people).index_on("id").sync()
    mark = _mark()
    with LookupServer(idx) as srv:
        assert [s.name for s in _since(mark)[0]] == ["serve:start"]
        for i in range(8):  # warm every program the loop drives
            assert srv.submit(f"c{int(ids[i])}").result(timeout=30)
        before = len(tracer.journal.snapshot())
        for i in range(100):
            assert srv.submit(f"c{int(ids[i % 400])}").result(timeout=30)
        assert srv.snapshot()["batch"]["batches"] >= 100
        assert len(tracer.journal.snapshot()) == before


def test_a_forced_recompile_is_a_compile_event_naming_the_program():
    import jax.numpy as jnp

    from csvplus_tpu.obs import register_kernel

    @register_kernel("test.journal_probe")
    def _probe(x):
        return x * 2 + 1

    x8, x24, x40 = jnp.arange(8), jnp.arange(24), jnp.arange(40)
    _probe(x8)
    mark = _mark()
    _probe(x8)  # warm: nothing
    assert _since(mark)[0] == []
    with tracer.milestone("plan:first-run"):
        _probe(x24)  # a new shape: traced, lowered, compiled
    spans, by_id, parents = _since(mark)
    events = [s for s in spans if s.name == "compile"]
    assert events and all(by_id[s.parent_id].name == "plan:first-run" for s in events)
    # jax traces the body's primitives inside the program's own trace:
    # those are events too; the program's three carry its name
    ours = [s for s in events if "csvplus.test.journal_probe" in s.attrs["fun_name"]]
    assert [s.attrs["kind"] for s in ours] == [
        "jaxpr_trace", "jaxpr_to_mlir_module", "backend_compile",
    ]
    assert ours[0].attrs["fun_name"] == "csvplus.test.journal_probe"
    assert ours[2].attrs["fun_name"] == "jit(csvplus.test.journal_probe)"
    assert all(s.seconds > 0 and s.t_end <= time.perf_counter() for s in events)
    # outside any context a compile is a root of the journal
    mark = _mark()
    _probe(x40)
    assert {s.parent_id for s in _since(mark)[0]} == {None}


def test_server_start_and_wal_recovery_are_milestones(tmp_path):
    from csvplus_tpu.storage import MutableIndex

    people, ids = _people(200)
    d = str(tmp_path / "mi")
    mi = MutableIndex.create(cp.take(people), ["id"], directory=d)
    mark = _mark()
    mi.append_rows([{"id": "c900001", "v": "x"}, {"id": "c900002", "v": "y"}])
    mi.append_rows([{"id": "c900003", "v": "z"}])
    # an append's delta is no once-per-object work: the journal hears nothing
    assert [s for s in _since(mark)[0] if s.name != "compile"] == []
    mi.close() if hasattr(mi, "close") else None
    mark = _mark()
    again = MutableIndex.open(d)
    spans, by_id, parents = _since(mark)
    rec = next(s for s in spans if s.name == "storage:recover")
    assert rec.parent_id is None
    assert rec.attrs["records"] == 2 and rec.attrs["segments"] >= 1 and rec.attrs["bytes"] > 0
    assert parents["storage:replay"] == "storage:recover"
    assert again.find_rows(["c900003"])


# -- reading it ---------------------------------------------------------------


def test_the_chrome_writer_and_the_flight_dump_take_the_journal(tmp_path):
    with tracer.milestone("storage:recover", records=3):
        with tracer.span("storage:replay"):
            pass
    events = chrome_trace_events([tracer.journal])
    assert validate_chrome_trace(events) == []
    assert {"storage:recover", "storage:replay"} <= {e["name"] for e in events}
    path = flight.dump("test", dir=str(tmp_path))
    tail = json.load(open(path))["context"]["journal"]
    assert tail["dropped"] == tracer.journal.dropped
    last = tail["recent"][-1]
    assert last["name"] == "storage:recover" and last["attrs"] == {"records": 3}
    assert last["children"] == 1


# -- what it costs when nothing is open ---------------------------------------


def test_disabled_path_costs_under_two_percent_of_a_micro_lookup():
    """The gate ``make trace-smoke`` held (deleted with ``bench.py`` in PR
    31), by its method: on the micro lookup shape (one batched
    ``find_many`` of 2,000 probes over a 100,000-key index, rows out),
    with no trace and no milestone open, the hooks the pass goes through
    (counted, by kind) times the measured cost of one disabled hook of
    that kind stay under 2% of the bare pass.  Scaling a per-hook cost is
    robust on a shared CPU, where two timed loops of the whole pass
    differ by more than 2%."""
    n, n_probes = 100_000, 2_000
    ids = np.arange(n, dtype=np.int64) * 7 % (n * 3)
    table = DeviceTable.from_pylists(
        {
            "cust_id": np.char.add("c", ids.astype(np.str_)).tolist(),
            "v": np.arange(n).astype(np.str_).tolist(),
        },
        device="cpu",
    )
    idx = cp.take(table).index_on("cust_id").sync()
    rng = np.random.default_rng(0)
    probes = [f"c{int(v)}" for v in rng.choice(ids, n_probes)]

    def bare_pass():
        return cp.to_rows_many(idx.find_many(probes))

    assert len(bare_pass()) == n_probes  # warm
    assert not tracer.active() and not telemetry.enabled
    hooks = {"span": 0, "open": 0}
    span, open_span = tracer.span, tracer.open_span

    def counting_span(name, **attrs):
        hooks["span"] += 1
        return span(name, **attrs)

    def counting_open(name, **attrs):
        hooks["open"] += 1
        return open_span(name, **attrs)

    tracer.span, tracer.open_span = counting_span, counting_open
    try:
        bare_pass()
    finally:
        del tracer.span, tracer.open_span

    def best(fn, reps=5):
        out = []
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            out.append(time.perf_counter() - t0)
        return min(out)

    reps = 20_000

    def disabled_spans():
        for _ in range(reps):
            with tracer.span("x"):
                pass

    def disabled_stages():  # telemetry.stage / add_stage: open_span, then out
        for _ in range(reps):
            with telemetry.stage("y", 1):
                pass

    t_pass = best(bare_pass, reps=3)
    cost = (
        hooks["span"] * best(disabled_spans) / reps
        + hooks["open"] * best(disabled_stages) / reps
    )
    assert cost < 0.02 * t_pass, (hooks, cost, t_pass)
