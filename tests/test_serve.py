"""Concurrent query-serving tier (csvplus_tpu.serve, docs/SERVING.md).

Contracts under test:

* coalescing correctness — any mix of concurrent submitters gets rows
  byte-identical to the matching single ``find`` calls, because the
  coalesced batch routes through the same ``find_rows_many`` engine;
* plan-executable cache — structural keys hit across different data
  (Lookup bounds, predicate-matched rows), miss on any op / schema /
  placement change, and verifier-REJECTED shapes are never cached;
* admission control — a full pending queue sheds with
  :class:`ServerOverloaded`; expired deadlines complete with
  :class:`DeadlineExceeded` before dispatch; ``stop()`` drains every
  admitted request;
* thread-safety of the shared lookup path — N threads hammering
  ``find_many`` (→ ``bounds_many`` → ``rows_from_mirror_many`` and its
  LRU) each observe results bitwise-equal to the serial run.
"""

import threading

import numpy as np
import pytest

import csvplus_tpu as cp
from csvplus_tpu import plan as P
from csvplus_tpu.columnar.table import DeviceTable
from csvplus_tpu.predicates import Like, Predicate
from csvplus_tpu.serve import (
    AdmissionController,
    DeadlineExceeded,
    LookupServer,
    PlanCache,
    PlanRejected,
    ServerOverloaded,
    plan_cache_key,
)

N_ROWS = 4000


def _build(n=N_ROWS, extra_col=False):
    ids = np.arange(n, dtype=np.int64) * 7 % (n * 3)
    cols = {
        "id": np.char.add("c", ids.astype(np.str_)).tolist(),
        "v": np.arange(n).astype(np.str_).tolist(),
    }
    if extra_col:
        cols["w"] = ["x"] * n
    t = DeviceTable.from_pylists(cols, device="cpu")
    return cp.take(t).index_on("id").sync(), ids


@pytest.fixture(scope="module")
def served():
    return _build()


def _probes(ids, n, seed=0):
    rng = np.random.default_rng(seed)
    ps = [f"c{int(v)}" for v in rng.choice(ids, n)]
    ps[::17] = ["nope"] * len(ps[::17])  # sprinkle misses
    return ps


# -- coalescing correctness ------------------------------------------------


def test_coalesced_matches_serial(served):
    idx, ids = served
    probes = _probes(ids, 300)
    serial = [idx.find(p).to_rows() for p in probes]
    with LookupServer(idx) as srv:
        futs = [srv.submit(p) for p in probes]
        got = [f.result(timeout=30.0) for f in futs]
    assert got == serial


def test_concurrent_submitters_match_serial(served):
    idx, ids = served
    probes = _probes(ids, 400, seed=1)
    serial = [idx.find(p).to_rows() for p in probes]
    n_threads = 8
    per = len(probes) // n_threads
    results = [None] * n_threads

    with LookupServer(idx) as srv:
        def worker(slot):
            chunk = probes[slot * per:(slot + 1) * per]
            futs = [srv.submit(p) for p in chunk]
            results[slot] = [f.result(timeout=30.0) for f in futs]

        ts = [threading.Thread(target=worker, args=(i,)) for i in range(n_threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
    flat = [rows for chunk in results for rows in chunk]
    assert flat == serial[: per * n_threads]


def test_blocking_lookup_and_probe_validation(served):
    idx, ids = served
    with LookupServer(idx) as srv:
        assert srv.lookup(f"c{int(ids[3])}") == idx.find(f"c{int(ids[3])}").to_rows()
        with pytest.raises(ValueError, match="too many columns"):
            srv.submit(("a", "b"))  # index key is one column wide


def test_submit_requires_running_server(served):
    idx, _ = served
    srv = LookupServer(idx)
    with pytest.raises(RuntimeError, match="not running"):
        srv.submit("c7")
    srv.start()
    try:
        assert srv.submit("c7").result(timeout=30.0) is not None
    finally:
        srv.stop()
    with pytest.raises(RuntimeError, match="not running"):
        srv.submit("c7")


def test_stop_drains_admitted_requests(served):
    idx, ids = served
    srv = LookupServer(idx, tick_us=20_000).start()
    futs = [srv.submit(f"c{int(v)}") for v in ids[:200]]
    srv.stop()  # must drain, not drop
    for f, v in zip(futs, ids[:200]):
        assert f.result(timeout=1.0) == idx.find(f"c{int(v)}").to_rows()


# -- admission control -----------------------------------------------------


def test_overload_sheds_with_typed_error(served):
    idx, ids = served
    # a long held-open tick + tiny bound: the burst must overflow
    with LookupServer(idx, max_pending=4, tick_us=200_000) as srv:
        shed, futs = 0, []
        for v in ids[:64]:
            try:
                futs.append(srv.submit(f"c{int(v)}"))
            except ServerOverloaded as e:
                shed += 1
                assert e.pending >= 4 and e.bound == 4
        assert shed > 0 and len(futs) >= 4
        for f in futs:  # every ADMITTED request still completes
            assert f.result(timeout=30.0) is not None
        assert srv.snapshot()["shed"] == shed


def test_deadline_expires_before_dispatch(served):
    idx, ids = served
    with LookupServer(idx, tick_us=50_000) as srv:
        fut = srv.submit(f"c{int(ids[0])}", deadline_s=0.0)
        with pytest.raises(DeadlineExceeded):
            fut.result(timeout=30.0)
        ok = srv.submit(f"c{int(ids[0])}")  # no deadline rides the same batch
        assert ok.result(timeout=30.0) == idx.find(f"c{int(ids[0])}").to_rows()
        assert srv.snapshot()["expired"] == 1


def test_admission_controller_unit():
    ac = AdmissionController(max_pending=2)
    ac.admit(0)
    ac.admit(1)
    with pytest.raises(ServerOverloaded):
        ac.admit(2)
    assert AdmissionController.deadline_error(0.0, None, 100.0) is None
    assert AdmissionController.deadline_error(0.0, 5.0, 1.0) is None
    err = AdmissionController.deadline_error(0.0, 5.0, 6.0)
    assert isinstance(err, DeadlineExceeded)


# -- plan-cache keys -------------------------------------------------------


class _Opaque(Predicate):
    """A predicate build_mask cannot lower -> error-severity verifier
    diagnostic -> the cache must REJECT, not cache."""

    def __call__(self, row):
        return True

    def __repr__(self):
        return "_Opaque()"


def test_key_identical_structure_different_data(served):
    idx, ids = served
    a = idx.find(f"c{int(ids[1])}").plan
    b = idx.find(f"c{int(ids[2])}").plan
    assert a is not None and a.lower != b.lower  # genuinely different data
    assert plan_cache_key(a) == plan_cache_key(b)
    cache = PlanCache(size=8)
    cache.execute(a)
    cache.execute(b)
    st = cache.stats()
    assert (st["hits"], st["misses"], st["lowered"]) == (1, 1, 1)


def test_key_misses_on_op_change(served):
    idx, ids = served
    leaf = idx.find(f"c{int(ids[1])}").plan
    filtered = P.Filter(leaf, Like({"id": "c7"}))
    projected = P.SelectCols(leaf, ("id",))
    keys = {plan_cache_key(leaf), plan_cache_key(filtered), plan_cache_key(projected)}
    assert len(keys) == 3
    # and a predicate VALUE change is a data-shape change too (it is
    # baked into the lowered mask), so it must miss:
    assert plan_cache_key(filtered) != plan_cache_key(
        P.Filter(leaf, Like({"id": "c9"}))
    )


def test_key_misses_on_schema_change():
    idx_a, ids = _build()
    idx_b, _ = _build(extra_col=True)
    a = idx_a.find(f"c{int(ids[1])}").plan
    b = idx_b.find(f"c{int(ids[1])}").plan
    assert plan_cache_key(a) != plan_cache_key(b)


def test_key_misses_on_placement_change():
    from csvplus_tpu.parallel.mesh import make_mesh

    rows = {"id": [f"c{i}" for i in range(64)], "v": ["1"] * 64}
    t_cpu = DeviceTable.from_pylists(rows, device="cpu")
    t_sharded = DeviceTable.from_pylists(rows, device="cpu").with_sharding(
        make_mesh(8)
    )
    assert plan_cache_key(P.Scan(t_cpu)) != plan_cache_key(P.Scan(t_sharded))


def test_rejected_plan_never_cached(served):
    idx, ids = served
    leaf = idx.find(f"c{int(ids[1])}").plan
    bad = P.Filter(leaf, _Opaque())
    cache = PlanCache(size=8)
    with pytest.raises(PlanRejected) as ei:
        cache.execute(bad)
    assert "unlowerable" in str(ei.value)
    assert len(cache) == 0 and cache.stats()["rejected"] == 1
    with pytest.raises(PlanRejected):  # re-verified, still not cached
        cache.execute(bad)
    st = cache.stats()
    assert len(cache) == 0 and st["rejected"] == 2 and st["lowered"] == 0


def test_plancache_lru_eviction(served):
    idx, ids = served
    leaf = idx.find(f"c{int(ids[1])}").plan
    shapes = [
        leaf,
        P.SelectCols(leaf, ("id",)),
        P.SelectCols(leaf, ("v",)),
    ]
    cache = PlanCache(size=2)
    for s in shapes:
        cache.execute(s)
    st = cache.stats()
    assert len(cache) == 2 and st["evictions"] == 1 and st["misses"] == 3


def test_served_plans_zero_recompile_when_warm(served):
    idx, ids = served
    plans = [idx.find(f"c{int(v)}").plan for v in ids[:40]]
    with LookupServer(idx) as srv:
        for f in [srv.submit_plan(p) for p in plans[:20]]:
            f.result(timeout=30.0)
        cold = srv.plancache.stats()
        for f in [srv.submit_plan(p) for p in plans[20:]]:
            f.result(timeout=30.0)
        warm = srv.plancache.stats()
        # warm pass: all hits, nothing re-verified or re-lowered
        assert warm["lowered"] == cold["lowered"] == 1
        assert warm["hits"] - cold["hits"] == 20
        # and the served result (a materialized DeviceTable) decodes to
        # the same rows as the direct lookup
        fut = srv.submit_plan(plans[0])
        assert cp.take(fut.result(timeout=30.0)).to_rows() == idx.find(
            f"c{int(ids[0])}"
        ).to_rows()


# -- metrics ---------------------------------------------------------------


def test_metrics_snapshot_shape(served):
    idx, ids = served
    with LookupServer(idx) as srv:
        for f in [srv.submit(f"c{int(v)}") for v in ids[:50]]:
            f.result(timeout=30.0)
        snap = srv.snapshot()
    for key in (
        "ticks", "enqueued", "completed", "shed", "expired", "failed",
        "queue_depth_last", "queue_depth_max", "batch", "latency",
        "queue_wait", "plancache",
    ):
        assert key in snap, key
    assert snap["enqueued"] == snap["completed"] == 50
    assert snap["latency"]["count"] == 50
    assert snap["batch"]["requests"] == 50
    import json

    json.dumps(snap)  # JSON-safe end to end


# -- shared lookup path under threads (satellite stress) -------------------


@pytest.mark.parametrize("drop_lru", [False, True])
def test_find_many_threaded_bitwise_equal_serial(served, drop_lru):
    """N threads × M keys through the full batched chain (bounds_many →
    rows_for_bounds → rows_from_mirror_many + LRU) must each observe
    results bitwise-equal to the serial run — the r08 locks make the
    decoded-block LRU safe under concurrent mutation."""
    idx, ids = served
    probes = _probes(ids, 250, seed=3)
    serial = cp.to_rows_many(idx.find_many(probes))
    mirror = idx._impl.dev.table
    n_threads = 8
    out = [None] * n_threads
    errs = []
    start = threading.Barrier(n_threads)

    def worker(slot):
        try:
            start.wait()
            for _ in range(3):
                if drop_lru:
                    mirror._mirror_lru = None  # force concurrent decode
                out[slot] = cp.to_rows_many(idx.find_many(probes))
        except BaseException as e:
            errs.append(e)

    ts = [threading.Thread(target=worker, args=(i,)) for i in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errs
    for got in out:
        assert got == serial


def test_bounds_many_threaded_equal_serial(served):
    idx, ids = served
    impl = idx._impl
    norm = [(p,) for p in _probes(ids, 200, seed=4)]
    serial = impl.bounds_many(norm)
    out = [None] * 6
    start = threading.Barrier(6)

    def worker(slot):
        start.wait()
        out[slot] = impl.bounds_many(norm)

    ts = [threading.Thread(target=worker, args=(i,)) for i in range(6)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    for got in out:
        assert np.array_equal(np.asarray(got), np.asarray(serial))


# ---------------------------------------------------------------------------
# multi-index routing + the storage write path (ISSUE 9)
# ---------------------------------------------------------------------------


def _mutable(n=200):
    from csvplus_tpu.row import Row
    from csvplus_tpu.source import take_rows
    from csvplus_tpu.storage import MutableIndex

    rows = [Row({"k": f"k{i % 17:03d}", "v": f"v{i}"}) for i in range(n)]
    return MutableIndex.create(take_rows(rows), ["k"], ingest_device="cpu")


def test_multi_index_routing_and_per_index_metrics(served):
    idx, ids = served
    mi = _mutable()
    with LookupServer(idx, indexes={"mut": mi}) as srv:
        assert srv.index_names() == ["default", "mut"]
        # each route answers from ITS index (different schemas)
        assert srv.lookup("c7")[0]["v"] == "1"
        assert srv.lookup("k001", index="mut")[0]["k"] == "k001"
        # probe width validates against the routed index
        with pytest.raises(ValueError, match="too many columns"):
            srv.submit(("a", "b", "c"), index="mut")
        with pytest.raises(KeyError, match="no index registered"):
            srv.lookup("c7", index="nope")
        # live registration
        srv.register("second", idx)
        assert srv.lookup("c7", index="second")[0]["v"] == "1"
        snap = srv.snapshot()
    by = snap["by_index"]
    assert by["default"]["lookups"] >= 1
    assert by["mut"]["lookups"] >= 1
    assert by["second"]["lookups"] >= 1


def test_serve_append_coalesces_and_is_visible(served):
    idx, ids = served
    mi = _mutable()
    with LookupServer(idx, indexes={"mut": mi}) as srv:
        # immutable index rejects appends, typed
        with pytest.raises(TypeError, match="immutable"):
            srv.append([{"id": "x", "v": "y"}])
        with pytest.raises(ValueError, match="empty"):
            srv.submit_append([], index="mut")
        epoch0 = mi.epoch
        futs = [
            srv.submit_append([{"k": f"srv{j}", "v": str(j)}], index="mut")
            for j in range(6)
        ]
        assert [f.result(timeout=30.0) for f in futs] == [1] * 6
        for j in range(6):
            got = srv.lookup(f"srv{j}", index="mut")
            assert [r["v"] for r in got] == [str(j)]
        # coalescing: 6 append requests landed in <= 6 delta tiers and
        # at most (epoch swaps == delta pushes) — each dispatch cycle
        # folded its drained appends into ONE tier
        assert mi.epoch - epoch0 == mi.delta_count
        assert mi.delta_count <= 6
        snap = srv.snapshot()
    cell = snap["by_index"]["mut"]
    assert cell["append_reqs"] == 6
    assert cell["rows_appended"] == 6
    assert cell["deltas_live"] == mi.delta_count

    from csvplus_tpu.storage import index_checksums, rebuild_reference

    assert index_checksums(mi.to_index()) == index_checksums(rebuild_reference(mi))


def test_served_durable_appends_ack_after_fsync(served, tmp_path):
    """The ISSUE 10 durable-ack contract on the serving tier: an
    append future resolving implies the cycle's WAL records were
    already fsynced (``wal_sync`` runs before the callbacks fire), the
    per-cycle WAL delta lands in the same ``by_index`` lock round, and
    a recovered registration surfaces ``recovered_records``."""
    from csvplus_tpu.row import Row
    from csvplus_tpu.source import take_rows
    from csvplus_tpu.storage import MutableIndex, index_checksums

    idx, ids = served
    d = str(tmp_path / "durable")
    rows = [Row({"k": f"k{i % 17:03d}", "v": f"v{i}"}) for i in range(200)]
    mi = MutableIndex.create(
        take_rows(rows), ["k"], ingest_device="cpu",
        directory=d, wal_sync="always",
    )
    with LookupServer(idx, indexes={"dur": mi}) as srv:
        futs = [
            srv.submit_append([{"k": f"srv{j}", "v": str(j)}], index="dur")
            for j in range(5)
        ]
        assert [f.result(timeout=30.0) for f in futs] == [1] * 5
        snap = srv.snapshot()
    cell = snap["by_index"]["dur"]
    assert cell["append_reqs"] == 5 and cell["rows_appended"] == 5
    # one WAL record per dispatch cycle (appends coalesce), every one
    # of them fsynced before its future resolved
    assert cell["wal_records"] == mi.delta_count >= 1
    assert cell["wal_fsyncs"] >= cell["wal_records"]
    assert cell["wal_bytes"] > 0
    assert cell["recovered_records"] == 0  # fresh index: nothing replayed

    # everything acked above survives a cold reopen, bitwise
    re1 = MutableIndex.open(d)
    assert re1.recovered_records == cell["wal_records"]
    assert index_checksums(re1.to_index()) == index_checksums(mi.to_index())
    # registering the recovered index surfaces the replay count (the
    # constructor path and live register() both report once)
    with LookupServer(idx, indexes={"rec": re1}) as srv2:
        srv2.register("rec2", re1)
        snap2 = srv2.snapshot()
    assert (
        snap2["by_index"]["rec"]["recovered_records"]
        == re1.recovered_records
    )
    assert (
        snap2["by_index"]["rec2"]["recovered_records"]
        == re1.recovered_records
    )


def test_served_reads_during_compaction_bitwise_equal(served):
    """The THREAD001 stress pattern extended to the write path: N
    submitter threads hammer a served MutableIndex while the background
    compactor swaps epochs — every result must be bitwise-equal to the
    serial read on the frozen equivalent."""
    import threading as _threading

    from csvplus_tpu.row import Row
    from csvplus_tpu.storage import Compactor

    idx, ids = served
    mi = _mutable(n=400)
    for j in range(3):
        mi.append_rows(
            [Row({"k": f"d{j}{i}", "v": "x"}) for i in range(20)]
        )
    probes = [f"k{i:03d}" for i in range(0, 17)] + ["d11", "nope"]
    frozen = mi.to_index()
    serial = [
        [dict(r) for r in b]
        for b in frozen._impl.find_rows_many([(p,) for p in probes])
    ]
    n_threads = 6
    out = [None] * n_threads
    errs = []
    start = _threading.Barrier(n_threads + 1)
    with LookupServer(idx, indexes={"mut": mi}) as srv:

        def worker(slot):
            try:
                start.wait()
                for _ in range(5):
                    futs = [srv.submit(p, index="mut") for p in probes]
                    got = [
                        [dict(r) for r in f.result(timeout=30.0)]
                        for f in futs
                    ]
                    if got != serial:
                        raise AssertionError(f"worker {slot} diverged")
                out[slot] = True
            except BaseException as e:
                errs.append(e)

        ts = [
            _threading.Thread(target=worker, args=(i,))
            for i in range(n_threads)
        ]
        for t in ts:
            t.start()
        with Compactor(mi, min_deltas=1, interval_s=0.0):
            start.wait()
            for t in ts:
                t.join()
    assert not errs, errs[0]
    assert all(out)
    assert mi.delta_count == 0  # the compactor really ran


def test_same_cycle_delete_append_apply_in_submission_order(served):
    """The ISSUE 12 write-ordering regression: a delete() and an
    append() for the SAME key drained into one dispatch cycle apply in
    submission order — delete-then-append resurrects the key,
    append-then-delete removes it — and both land before the cycle's
    view refresh.  A large tick forces each pair into one batch."""
    from csvplus_tpu.storage import index_checksums, rebuild_reference

    idx, ids = served
    mi = _mutable(n=50)
    with LookupServer(idx, indexes={"mut": mi}, tick_us=100_000) as srv:
        # delete first, then re-append: the key must survive with the
        # NEW value (submission order, not append-runs-first)
        f1 = srv.submit_delete(("k003",), index="mut")
        f2 = srv.submit_append([{"k": "k003", "v": "fresh"}], index="mut")
        assert f1.result(timeout=30.0) == 1
        assert f2.result(timeout=30.0) == 1
        got = srv.lookup("k003", index="mut")
        assert [r["v"] for r in got] == ["fresh"]

        # append first, then delete: the key must be gone
        f3 = srv.submit_append([{"k": "zz9", "v": "doomed"}], index="mut")
        f4 = srv.submit_delete(("zz9",), index="mut")
        assert f3.result(timeout=30.0) == 1
        assert f4.result(timeout=30.0) == 1
        assert srv.lookup("zz9", index="mut") == []

        # interleaved run coalescing: append runs flush before each
        # delete, and the cycle still lands as ONE wal_sync batch
        epoch0 = mi.epoch
        fs = [
            srv.submit_append([{"k": "mix", "v": "a"}], index="mut"),
            srv.submit_delete(("mix",), index="mut"),
            srv.submit_append([{"k": "mix", "v": "b"}], index="mut"),
        ]
        for f in fs:
            f.result(timeout=30.0)
        got = srv.lookup("mix", index="mut")
        assert [r["v"] for r in got] == ["b"]
        snap = srv.snapshot()
    cell = snap["by_index"]["mut"]
    assert cell["delete_reqs"] == 3
    assert cell["append_reqs"] == 4
    # the replayed reference (acked op order) agrees bitwise
    assert index_checksums(mi.to_index()) == index_checksums(
        rebuild_reference(mi)
    )


# -- the dispatcher's own span tree (ISSUE 25) -----------------------------

CYCLE_TREE = {
    # span -> its parent inside one dispatch cycle
    "serve:sweep": "serve:cycle",
    "serve:bounds": "serve:cycle",
    "serve:bounds:encode": "serve:bounds",
    "serve:bounds:search": "serve:bounds",
    "serve:gather-decode": "serve:cycle",
    "serve:gather:index": "serve:gather-decode",
    "serve:gather:take": "serve:gather-decode",
    "serve:gather:readback": "serve:gather-decode",
    "serve:gather:rows": "serve:gather-decode",
    "serve:scatter": "serve:cycle",
    "serve:account": "serve:cycle",
}


@pytest.fixture
def past_mirror_cap(monkeypatch):
    """Lookups take the device path (searchsorted, gather and read-back
    on the device), as an index past the 16M mirror cap does."""
    from csvplus_tpu.ops.join import DeviceIndex

    monkeypatch.setattr(DeviceIndex, "POINT_MIRROR_MAX_KEYS", 100)


def test_untraced_dispatch_opens_no_span_and_no_annotation(served, past_mirror_cap, monkeypatch):
    """With no trace active nothing new runs: the dispatcher's cycle
    makes no Span and constructs no TraceAnnotation.  (Starting the
    server is a milestone of the process journal, and a batch shape's
    first compile is an event of it: neither is the cycle's.)"""
    import jax.profiler

    from csvplus_tpu.obs import span as span_mod

    made = []

    class Annotation:
        def __init__(self, name, **meta):
            made.append(("annotation", name))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return None

    real_span = span_mod.Span

    def counting_span(*a, **k):
        made.append(("span", k.get("name")))
        return real_span(*a, **k)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    monkeypatch.setattr(span_mod, "Span", counting_span)
    idx, ids = served
    probes = _probes(ids, 60, seed=5)
    serial = [idx.find(p).to_rows() for p in probes]
    del made[:]  # the serial finds compiled their shapes
    with LookupServer(idx) as srv:
        assert made == [("span", "serve:start"), ("annotation", "csvplus:serve:start")]
        del made[:]
        got = [f.result(timeout=30) for f in [srv.submit(p) for p in probes]]
    assert got == serial
    assert [m for m in made if m != ("span", "compile")] == []


@pytest.mark.parametrize("form", ["one-trip", "two-trips"])
def test_traced_cycle_lands_whole_in_every_requests_tree(served, past_mirror_cap, form):
    """*one-trip*: a unique index and full-key probes, so the bounds stay
    on the device and the cycle reads once, no row position formed on
    the host.  *two-trips*: the same table under a plain ``index_on``,
    which cannot know that a hit is one row: bounds read, positions
    formed, rows read."""
    from csvplus_tpu.obs.span import tracer

    idx, ids = served
    one_trip = form == "one-trip"
    if one_trip:
        idx = cp.take(idx._impl.dev.table).unique_index_on("id").sync()
    n_clients = 12
    traces = [None] * n_clients
    with LookupServer(idx) as srv:
        # warm the shapes, then hold the dispatcher so that the clients'
        # requests coalesce into as few cycles as possible
        srv.lookup(f"c{int(ids[0])}")
        barrier = threading.Barrier(n_clients)

        def client(i):
            with tracer.trace(f"client-{i}") as tr:
                barrier.wait()
                assert srv.submit(f"c{int(ids[i])}").result(timeout=30)
            traces[i] = tr

        threads = [threading.Thread(target=client, args=(i,)) for i in range(n_clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    # the server has stopped: every cycle's subtree has landed
    by_cycle: dict = {}
    for tr in traces:
        spans = tr.snapshot()
        by_id = {s.span_id: s for s in spans}
        root = tr.root()
        (cycle,) = [s for s in spans if s.name == "serve:cycle"]
        assert cycle.parent_id == root.span_id and cycle.attrs["batch"] >= 1
        for name, parent in CYCLE_TREE.items():
            found = [s for s in spans if s.name == name]
            # the accounts are two: the index's counters after its
            # lookups, the cycle's own at its end; no position is formed
            # on the host where the bounds never come to it alone
            times = {"serve:account": 2, "serve:gather:index": 0 if one_trip else 1}
            assert len(found) == times.get(name, 1), (name, len(found))
            for s in found:
                up = by_id[s.parent_id]
                assert up.name == parent, name
                assert up.t_start <= s.t_start <= s.t_end <= up.t_end, name
                assert cycle.t_start <= s.t_start and s.t_end <= cycle.t_end
        # the request's own two spans stay what they were
        (qw,) = [s for s in spans if s.name == "serve:queue-wait"]
        (dsp,) = [s for s in spans if s.name == "serve:dispatch"]
        assert qw.parent_id == dsp.parent_id == root.span_id
        assert qw.t_end == dsp.t_start and cycle.t_start <= dsp.t_start
        assert dsp.t_end <= cycle.t_end  # the cycle goes on after this reply
        # counts at the boundaries: the search read or left on the device,
        # one program and one read for both columns, padded to the bucket
        attrs = {s.name: s.attrs for s in spans}
        m = cycle.attrs["batch"]
        bucket = 1 << (m - 1).bit_length()
        if one_trip:
            assert attrs["serve:bounds:search"] == {"host_syncs": 0}
        else:
            assert attrs["serve:bounds:search"] == {"host_syncs": 1, "elements": 2 * m}
        assert attrs["serve:gather:take"]["dispatches"] == 1
        assert attrs["serve:gather:readback"]["host_syncs"] == 1
        assert attrs["serve:gather:readback"]["one_trip"] == int(one_trip)
        if one_trip:  # lower, upper and two columns a query
            assert attrs["serve:gather:readback"]["elements"] == 4 * bucket
        assert sum(a.get("host_syncs", 0) for a in attrs.values()) == (1 if one_trip else 2)
        by_cycle.setdefault((cycle.t_start, cycle.t_end), []).append(spans)
    # timestamps are equal across the requests of a batch
    assert len(by_cycle) < n_clients  # some requests did share a cycle
    for trees in by_cycle.values():
        stamps = [
            sorted((s.name, s.t_start, s.t_end) for s in spans if s.name in CYCLE_TREE)
            for spans in trees
        ]
        assert all(st == stamps[0] for st in stamps)


def test_requests_sharing_one_tree_hold_the_cycle_once(served, past_mirror_cap):
    from csvplus_tpu.obs.span import tracer

    idx, ids = served
    with LookupServer(idx) as srv:
        with tracer.trace("one-client") as tr:
            futs = [srv.submit(f"c{int(v)}") for v in ids[:40]]
            for f in futs:
                f.result(timeout=30)
    spans = tr.snapshot()
    names = [s.name for s in spans]
    assert names.count("serve:queue-wait") == names.count("serve:dispatch") == 40
    cycles = [s for s in spans if s.name == "serve:cycle"]
    assert sum(c.attrs["batch"] for c in cycles) == 40
    # one subtree per cycle, however many of its requests share the tree
    assert len({(c.t_start, c.t_end) for c in cycles}) == len(cycles)
    assert names.count("serve:bounds") == names.count("serve:scatter") == len(cycles)


def test_callbacks_do_not_inherit_the_cycles_context(served):
    from csvplus_tpu.obs.span import tracer

    idx, ids = served
    seen, done = [], threading.Event()

    def on_reply(fut):
        seen.append(tracer.capture())
        done.set()

    with LookupServer(idx) as srv:
        with tracer.trace("client"):
            srv.submit(f"c{int(ids[3])}", callback=on_reply)
            assert done.wait(30)
    assert seen == [None]


def test_the_wait_after_a_traced_cycle_is_a_span(served):
    from csvplus_tpu.obs.span import tracer

    idx, ids = served
    with LookupServer(idx) as srv:
        with tracer.trace("client") as tr:
            srv.lookup(f"c{int(ids[1])}")
        # the dispatcher now waits on its condition variable; stop() ends the wait
    waits = [s for s in tr.snapshot() if s.name == "serve:idle-wait"]
    (cycle,) = [s for s in tr.snapshot() if s.name == "serve:cycle"]
    assert len(waits) == 1 and waits[0].parent_id == cycle.parent_id
    assert waits[0].t_start >= cycle.t_end
