"""Mutable-index storage tier (csvplus_tpu.storage, docs/STORAGE.md).

Contracts under test, per the ISSUE 9 hard contract:

* parity at every compaction step — base+deltas checksum-match a
  from-scratch rebuild of the same logical rows (bitwise, positional
  per-column checksums) after EVERY ``compact_once``, in both
  visibility modes, through the packed device merge AND the host
  fallback merge (two independent implementations cross-checked
  against a third — the host ``create_index`` rebuild);
* multi-tier reads — point, prefix, empty and missing probes against
  a live tier stack answer bitwise-equal to the frozen equivalent
  (``to_index()``), including the key-level interleave on prefix
  probes and newest-wins shadowing in upsert mode;
* concurrency — N reader threads issuing ``find_rows_many`` while the
  compactor swaps epochs observe results bitwise-equal to serial
  reads on the frozen equivalent (readers pin a tier-set epoch; no
  lock on the probe hot path);
* zero warm recompiles — warm lookups against a compacted index
  record zero recompiles (``RecompileWatch.assert_zero``);
* crash safety — an injected ``storage:compact`` fault (at entry or
  in the pre-swap window) leaves the pre-compaction tier set intact
  and retryable.
"""

import threading

import pytest

import csvplus_tpu as cp
from csvplus_tpu.columnar.table import DeviceTable
from csvplus_tpu.index import Index, IndexImpl
from csvplus_tpu.obs.recompile import RecompileWatch
from csvplus_tpu.resilience import faults
from csvplus_tpu.resilience.faults import FaultPlan, InjectedFatalError
from csvplus_tpu.row import Row
from csvplus_tpu.serve import ServingMetrics
from csvplus_tpu.source import take_rows
from csvplus_tpu.storage import (
    Compactor,
    MutableIndex,
    index_checksums,
    merge_tiers,
    rebuild_reference,
)


@pytest.fixture(autouse=True)
def _disarmed():
    faults.deactivate()
    yield
    faults.deactivate()


def _rows(n, off=0, keyspace=13):
    return [
        Row({"k": f"k{(i + off) % keyspace:03d}", "v": f"v{i + off}"})
        for i in range(n)
    ]


def _mk(n=120, mode="append", keyspace=13):
    return MutableIndex.create(
        take_rows(_rows(n, keyspace=keyspace)),
        ["k"],
        mode=mode,
        ingest_device="cpu",
    )


def _assert_parity(mi):
    """The hard contract: the live tier set checksum-matches the
    from-scratch host rebuild of the same logical rows, bitwise and
    order-sensitive."""
    ref = rebuild_reference(mi)
    got = mi.to_index()
    assert index_checksums(got) == index_checksums(ref)


def _blocks(groups):
    return [[dict(r) for r in b] for b in groups]


# -- parity at every compaction step ---------------------------------------


@pytest.mark.parametrize("mode", ["append", "upsert"])
def test_parity_every_compaction_step(mode):
    mi = _mk(mode=mode)
    for step in range(4):
        mi.append_rows(_rows(17, off=100 + 40 * step))
        mi.append_rows(_rows(9, off=60 + 40 * step))
        _assert_parity(mi)  # with live deltas
        stats = mi.compact_once()
        assert stats is not None and stats["deltas"] == 2
        assert mi.delta_count == 0
        # post-compaction: the swapped-in base IS the whole tier set
        assert index_checksums(mi.tiers().base) == index_checksums(
            rebuild_reference(mi)
        )
    assert mi.compact_once() is None  # nothing left to fold


@pytest.mark.parametrize("mode", ["append", "upsert"])
def test_multi_tier_probes_match_frozen(mode):
    rows = [
        Row({"a": f"a{i % 3}", "b": f"b{i % 4}", "v": f"x{i}"})
        for i in range(36)
    ]
    mi = MutableIndex.create(
        take_rows(rows), ["a", "b"], mode=mode, ingest_device="cpu"
    )
    mi.append_rows([{"a": "a1", "b": "b9", "v": "d1"}, {"a": "a1", "b": "b0", "v": "d2"}])
    mi.append_rows([{"a": "a1", "b": "b0", "v": "d3"}, {"a": "a9", "b": "b9", "v": "d4"}])
    probes = [
        ("a1",),            # prefix spanning all three tiers
        ("a1", "b0"),       # full-width hit in base + both deltas
        ("a9", "b9"),       # full-width hit only in the newest delta
        (),                 # whole index
        ("zz",),            # miss
        ("a1", "zz"),       # full-width miss
    ]
    live = mi.find_rows_many(probes)
    frozen = mi.to_index()._impl.find_rows_many(probes)
    assert _blocks(live) == _blocks(frozen)
    # the whole-index probe must equal the rebuild's full row order
    assert _blocks([live[3]])[0] == [
        dict(r) for r in rebuild_reference(mi)._impl.rows
    ]


def test_upsert_newest_wins_shadows_older_tiers():
    mi = _mk(n=26, mode="upsert", keyspace=5)
    before = len(mi.find_rows("k003"))
    assert before > 1  # duplicate keys in the base
    mi.append_rows([{"k": "k003", "v": "NEW"}])
    got = mi.find_rows("k003")
    assert [dict(r) for r in got] == [{"k": "k003", "v": "NEW"}]
    mi.compact_once()
    assert [dict(r) for r in mi.find_rows("k003")] == [{"k": "k003", "v": "NEW"}]
    _assert_parity(mi)
    # append mode keeps the multiset instead
    ma = _mk(n=26, mode="append", keyspace=5)
    ma.append_rows([{"k": "k003", "v": "NEW"}])
    assert len(ma.find_rows("k003")) == before + 1


def test_append_csv_rides_streamed_ingest(tmp_path, monkeypatch):
    # force the streamed tier so the delta rides the staged pipeline
    monkeypatch.setenv("CSVPLUS_STREAM_MIN_BYTES", "1")
    monkeypatch.setenv("CSVPLUS_STREAM_CHUNK_BYTES", "96")
    p = tmp_path / "delta.csv"
    lines = ["k,v"] + [f"k{i % 7:03d},csv{i}" for i in range(50)]
    p.write_text("\n".join(lines) + "\n")
    mi = _mk()
    n = mi.append_csv(str(p))
    assert n == 50
    assert mi.delta_count == 1
    _assert_parity(mi)
    mi.compact_once()
    _assert_parity(mi)
    assert len(mi.find_rows("k001")) > 0


def test_empty_appends_and_validation():
    mi = _mk(n=10)
    assert mi.append_rows([]) == 0
    assert mi.delta_count == 0
    with pytest.raises(ValueError, match="too many columns"):
        mi.find_rows(("a", "b"))
    with pytest.raises(ValueError, match="mode"):
        MutableIndex.create(take_rows(_rows(5)), ["k"], mode="merge")
    with pytest.raises(TypeError):
        MutableIndex("not an index")


def test_merge_tiers_host_fallback_paths():
    """Host-backed tiers (``impl.dev is None``) must merge through the
    host fallback, bitwise-equal to the packed device merge's answer
    for the same logical rows."""

    def host_index(rows):
        rows = sorted((Row(r) for r in rows), key=lambda r: (r["k"],))
        return Index(IndexImpl(rows, ["k"]))

    a = _rows(20)
    b = _rows(8, off=50)
    for mode in ("append", "upsert"):
        host = merge_tiers([host_index(a), host_index(b)], ["k"], mode)
        assert host._impl.dev is None  # rode the host path
        # device merge over the same logical stream
        mi2 = MutableIndex.create(take_rows([Row(r) for r in a]), ["k"], mode=mode)
        mi2.append_rows([Row(r) for r in b])
        dev = mi2.to_index()
        assert index_checksums(host) == index_checksums(dev)


# -- concurrency ------------------------------------------------------------


def test_concurrent_readers_during_compaction_bitwise_equal():
    """N reader threads issuing ``find_rows_many`` while the compactor
    swaps epochs must each observe results bitwise-equal to serial
    reads on the frozen equivalent — the tier content never changes,
    only its physical layout, so every epoch answers identically."""
    mi = _mk(n=400, keyspace=31)
    for j in range(3):
        mi.append_rows(_rows(25, off=500 + 30 * j, keyspace=31))
    probes = [(f"k{i:03d}",) for i in range(0, 31, 2)] + [("zz",), ()]
    frozen = mi.to_index()
    serial = _blocks(frozen._impl.find_rows_many(probes))
    epoch0 = mi.epoch

    n_threads = 6
    out = [None] * n_threads
    errs = []
    start = threading.Barrier(n_threads + 1)

    def reader(slot):
        try:
            start.wait()
            for _ in range(8):
                got = _blocks(mi.find_rows_many(probes))
                if got != serial:
                    raise AssertionError(f"reader {slot} diverged")
            out[slot] = True
        except BaseException as e:  # surfaced via errs, not swallowed
            errs.append(e)

    ts = [threading.Thread(target=reader, args=(i,)) for i in range(n_threads)]
    for t in ts:
        t.start()
    start.wait()
    # swap the epoch under the readers: compaction changes the tier
    # LAYOUT (4 tiers -> 1), never the content, so every pinned epoch
    # answers identically
    assert mi.compact_once() is not None
    for t in ts:
        t.join()
    if errs:
        raise errs[0]
    assert all(out)
    assert mi.epoch > epoch0
    assert _blocks(mi.find_rows_many(probes)) == serial


def test_compactor_thread_concurrent_appends_parity():
    """Background compactor + appending writer: every append survives
    (racing appends carry over as the swapped tier set's tail) and the
    final state checksum-matches the rebuild."""
    mi = _mk(n=100)
    total = 100
    with Compactor(mi, min_deltas=1, interval_s=0.002):
        for j in range(12):
            mi.append_rows(_rows(7, off=1000 + 10 * j))
            total += 7
    assert len(mi) == total
    _assert_parity(mi)


def test_compactor_metrics_land_per_index():
    mi = _mk(n=40)
    m = ServingMetrics()
    c = Compactor(mi, min_deltas=1, interval_s=0.002, metrics=m, index_name="mut")
    mi.append_rows(_rows(5, off=200))
    with c:
        deadline = 200
        while c.snapshot()["compactions"] == 0 and deadline:
            deadline -= 1
            import time

            time.sleep(0.005)
    cell = m.snapshot()["by_index"]["mut"]
    assert cell["compactions"] >= 1
    assert cell["compacted_rows"] >= 45
    assert cell["last_compact_ms"] is not None
    assert mi.delta_count == 0


# -- zero warm recompiles ---------------------------------------------------


def test_warm_lookups_after_compaction_zero_recompiles():
    mi = _mk(n=400, keyspace=41)
    for j in range(3):
        mi.append_rows(_rows(15, off=600 + 20 * j, keyspace=41))
    mi.compact_once()
    probes = [(f"k{i:03d}",) for i in range(41)] + [("zz",)]
    mi.find_rows_many(probes)  # warm-up pays any cold lowering once
    with RecompileWatch() as w:
        for _ in range(3):
            mi.find_rows_many(probes)
    w.assert_zero("warm post-compaction lookups")


# -- crash safety (storage:compact fault site) ------------------------------


@pytest.mark.parametrize("hit", [0, 1], ids=["at-entry", "pre-swap"])
def test_compact_crash_leaves_tier_set_intact_and_retryable(hit):
    """``compact_once`` fires the ``storage:compact`` site twice per
    pass — on entry and in the window between merge and swap.  A crash
    at EITHER point must leave the pre-compaction tier set live (same
    epoch, same deltas, same answers) and a disarmed retry must
    succeed with full parity."""
    mi = _mk(n=60)
    mi.append_rows(_rows(9, off=300))
    mi.append_rows(_rows(9, off=400))
    epoch0, deltas0 = mi.epoch, mi.delta_count
    before = _blocks(mi.find_rows_many([("k001",), ("zz",)]))
    with faults.active(
        FaultPlan([{"site": "storage:compact", "at": [hit], "error": "fatal"}])
    ) as plan:
        with pytest.raises(InjectedFatalError):
            mi.compact_once()
        assert plan.snapshot()["fired"]["storage:compact"] == 1
    assert mi.epoch == epoch0
    assert mi.delta_count == deltas0
    assert _blocks(mi.find_rows_many([("k001",), ("zz",)])) == before
    _assert_parity(mi)
    # disarmed retry starts clean and succeeds
    stats = mi.compact_once()
    assert stats is not None and stats["deltas"] == deltas0
    assert mi.delta_count == 0
    _assert_parity(mi)


def test_compactor_loop_records_failure_and_retries():
    """The background loop absorbs an injected crash (counted, typed,
    stderr-reported) and the NEXT interval's retry compacts fine."""
    mi = _mk(n=30)
    mi.append_rows(_rows(5, off=300))
    c = Compactor(mi, min_deltas=1, interval_s=0.002)
    with faults.active(
        FaultPlan([{"site": "storage:compact", "at": [0], "error": "fatal"}])
    ):
        with c:
            import time

            deadline = 200
            while mi.delta_count and deadline:
                deadline -= 1
                time.sleep(0.005)
    snap = c.snapshot()
    assert snap["failures"] >= 1
    assert "InjectedFatalError" in snap["last_error"]
    assert snap["compactions"] >= 1  # the retry made it through
    assert mi.delta_count == 0
    _assert_parity(mi)


# -- accounting -------------------------------------------------------------


def test_snapshot_and_spans():
    from csvplus_tpu.utils.observe import telemetry

    mi = _mk(n=50)
    mi.append_rows(_rows(5, off=300))
    telemetry.enabled = True
    telemetry.reset()
    try:
        mi.compact_once()
        stages = {r.stage for r in telemetry.merged_stages()}
    finally:
        telemetry.enabled = False
    assert "storage:compact" in stages
    assert "storage:merge" in stages
    snap = mi.snapshot()
    assert snap["compactions"] == 1
    assert snap["deltas"] == 0
    assert snap["base_rows"] == 55
    assert snap["compact_seconds_total"] > 0


# -- tombstones, leveling, durability (ISSUE 10) ----------------------------


@pytest.mark.parametrize("mode", ["append", "upsert"])
def test_tombstone_parity_every_compaction_step(mode):
    """The hard contract extended over deletes: interleave appends,
    upserts, deletes and re-appends, and hold checksum parity against
    the from-scratch logical replay at EVERY compaction step — partial
    (tombstones survive into the folded tier) and full (tombstones
    apply and drop for good)."""
    mi = _mk(mode=mode)
    for step in range(3):
        mi.append_rows(_rows(8, off=100 + 30 * step))
        mi.delete((f"k{(2 + step) % 13:03d}",))
        mi.append_rows(_rows(8, off=40 + 30 * step))
        mi.delete((f"k{(5 + step) % 13:03d}",))
        # a re-append after delete: tombstones shadow only OLDER tiers
        mi.append_rows([Row({"k": f"k{(2 + step) % 13:03d}", "v": f"re{step}"})])
        _assert_parity(mi)
        if step % 2:
            stats = mi.compact_once()
            assert stats["kind"] == "full" and mi.delta_count == 0
        else:
            stats = mi.compact_step(ratio=2)
            assert stats is not None
        _assert_parity(mi)
    mi.compact_once()
    # a full merge leaves no tombstones behind
    assert all(not d.tombs for d in mi.tiers().deltas)
    _assert_parity(mi)


def test_delete_visibility_and_validation():
    for mode in ("append", "upsert"):
        mi = _mk(mode=mode)
        assert mi.find_rows_many([("k003",)])[0]
        mi.delete(("k003",))
        assert mi.find_rows_many([("k003",)])[0] == []
        with pytest.raises(ValueError):
            mi.delete(("a", "b"))  # wrong key width
        mi.append_rows([Row({"k": "k003", "v": "reborn"})])
        got = [dict(r) for r in mi.find_rows_many([("k003",)])[0]]
        assert {"k": "k003", "v": "reborn"} in got
        _assert_parity(mi)


def test_leveled_compaction_policy_and_parity():
    """compact_step folds only same-level runs (bounded write
    amplification: the base is untouched until the full-merge
    escalation trigger), with parity at every step."""
    mi = _mk(n=400, keyspace=29)
    kinds = []
    for step in range(9):
        mi.append_rows(_rows(4, off=500 + 10 * step, keyspace=29))
        stats = mi.compact_step(ratio=3)
        if stats is not None:
            kinds.append(stats["kind"])
        _assert_parity(mi)
    assert "partial" in kinds  # level-0 runs folded without a rebase
    # the policy rejects a degenerate ratio
    with pytest.raises(ValueError):
        mi.compact_step(ratio=1)
    # escalation: enough delta mass forces the full merge
    while mi.delta_count:
        stats = mi.compact_step(ratio=2)
        if stats is None:
            stats = mi.compact_once()
        _assert_parity(mi)
    assert mi.delta_count == 0


def test_compactor_leveled_policy_validation():
    mi = _mk(n=60)
    c = Compactor(mi, min_deltas=1, interval_s=0.01, policy="leveled", ratio=3)
    assert c.snapshot()["policy"] == "leveled"
    with pytest.raises(ValueError):
        Compactor(mi, policy="bogus")


def test_upsert_merge_drops_dead_rows_and_dictionary_groups():
    """The ISSUE 10 dead-group fix: a full-shadow upsert merge must not
    carry dead rows OR their now-unreferenced dictionary values into
    the merged tier (r10 kept the union dictionary whole)."""
    t = DeviceTable.from_pylists(
        {
            "k": [f"k{i % 8:03d}" for i in range(40)],
            "v": [f"v{i}" for i in range(40)],
        },
        device="cpu",
    )
    mi = MutableIndex(cp.take(t).index_on("k").sync(), mode="upsert")
    mi.append_rows([Row({"k": f"k{i % 8:03d}", "v": f"n{i}"}) for i in range(40)])
    stats = mi.compact_once()
    assert stats["rows_in"] == 80 and stats["rows_out"] == 40
    dev = mi.tiers().base._impl.dev
    assert dev is not None  # the merge stayed on the device path
    vcol = dev.table.columns["v"]
    # 40 live values; the 40 shadowed base values are pruned
    assert len(vcol.dictionary) == 40
    _assert_parity(mi)


def test_durable_roundtrip_and_recovery_parity(tmp_path):
    d = str(tmp_path / "idx")
    mi = MutableIndex.create(
        take_rows(_rows(60)), ["k"], mode="append",
        ingest_device="cpu", directory=d, wal_sync="always",
    )
    mi.append_rows(_rows(9, off=100))
    mi.delete(("k001",))
    mi.append_rows(_rows(5, off=200))
    _assert_parity(mi)
    snap = mi.snapshot()
    assert snap["wal"]["records"] == 3 and snap["checkpoint"] == 1

    re1 = MutableIndex.open(d)
    assert re1.recovered_records == 3
    assert index_checksums(re1.to_index()) == index_checksums(mi.to_index())

    # a durable directory refuses double-create
    with pytest.raises(Exception, match="use MutableIndex.open"):
        MutableIndex.create(
            take_rows(_rows(4)), ["k"], ingest_device="cpu", directory=d
        )

    # a full merge checkpoints: the WAL tail empties
    mi.compact_once()
    re2 = MutableIndex.open(d)
    assert re2.recovered_records == 0
    assert index_checksums(re2.to_index()) == index_checksums(mi.to_index())

    # post-checkpoint tail ops replay on the NEW base
    mi.append_rows(_rows(4, off=300))
    mi.delete(("k002",))
    re3 = MutableIndex.open(d)
    assert re3.recovered_records == 2
    assert index_checksums(re3.to_index()) == index_checksums(mi.to_index())
    _assert_parity(re3)


def test_wal_sync_modes_and_stats(tmp_path):
    from csvplus_tpu.storage import wal_sync_mode

    assert wal_sync_mode("batch") == "batch"
    with pytest.raises(ValueError):
        wal_sync_mode("sometimes")

    d = str(tmp_path / "idx")
    mi = MutableIndex.create(
        take_rows(_rows(30)), ["k"], ingest_device="cpu",
        directory=d, wal_sync="batch",
    )
    mi.append_rows(_rows(5, off=100))
    mi.append_rows(_rows(5, off=200))
    # batch mode: appends buffer; wal_sync() flushes and reports the
    # delta exactly once
    delta = mi.wal_sync()
    assert delta["records"] == 2 and delta["bytes"] > 0
    assert delta["fsyncs"] >= 1
    assert mi.wal_sync()["records"] == 0  # delta already reported
    # a memory-only index is a no-op surface with zeroed stats
    mem = _mk(n=20)
    assert mem.wal_sync() == {"records": 0, "bytes": 0, "fsyncs": 0}
    re1 = MutableIndex.open(d)
    assert index_checksums(re1.to_index()) == index_checksums(mi.to_index())
