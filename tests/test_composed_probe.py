"""The composed probe of ``ops/join.py`` (ISSUE 26) against the staged
kernels it stands in for.

The staged chain — ``_translate_*`` / ``_apply_code_translation`` ->
``_pack_qk_kernel`` -> ``_probe_kernel_direct`` -> the emit's gathers
— stays the arbiter: every case here runs both and compares bitwise
(``lower`` where a row matched, ``counts`` everywhere, the joined table's
values, row order and column order).  The shapes that must not engage
are read off the stage table's ``tier``.
"""

import sys
import threading
from contextlib import contextmanager

import numpy as np
import pytest

import jax.numpy as jnp

from csvplus_tpu import Row, TakeRows
from csvplus_tpu.columnar.ingest import source_from_table
from csvplus_tpu.columnar.table import DeviceTable, StringColumn
from csvplus_tpu.columnar.typed import PAD_VALUE, IntColumn
from csvplus_tpu.obs.recompile import compile_counts
from csvplus_tpu.ops import join as J
from csvplus_tpu.ops import lanes as L
from csvplus_tpu.ops.join import DeviceIndex
from csvplus_tpu.ops.sort import sort_table
from csvplus_tpu.serve.plancache import PlanCache
from csvplus_tpu.utils.observe import telemetry


# ---- builders -------------------------------------------------------------


def _index(data, keys):
    return DeviceIndex.build(sort_table(DeviceTable.from_pylists(data), keys), keys)


def _typed(prefix, values):
    return IntColumn(prefix.encode(), jnp.asarray(np.asarray(values, dtype=np.int32)))


def _strings(values):
    return StringColumn.from_values(values, None)


def _lane_column(values):
    """The same cells as ``_strings(values)`` with the dictionary kept
    on the device as byte lanes (what a high-cardinality ingest makes)."""
    host = _strings(values)
    d = host.dictionary
    lanes = tuple(
        jnp.asarray(lane) for lane in L.pack_host(d, L.lanes_for_width(d.dtype.itemsize))
    )
    return StringColumn(None, host.codes, dev_dictionary=lanes, dev_dict_sorted=True)


def _people(ids, prefix="c"):
    return {
        "id": [f"{prefix}{i}" for i in ids],
        "name": [f"n{i % 7}" for i in ids],
        "surname": [f"s{i % 11}" for i in ids],
    }


def _staged_probe(di, pc):
    """(lower, counts) by the staged kernels, one by one."""
    codes = pc.renumbered_to_col(di.table.columns[di.key_columns[0]])
    shift = di.shifts[0]
    qk = J._pack_qk_kernel((codes,), (shift,))
    return J._probe_kernel_direct(di.direct_cum, qk, jnp.int32(1) << shift)


@contextmanager
def _staged_only(monkeypatch):
    """The join as the parent ran it: no composition, and every build
    column gathered and handed to ``merge_with_fallback``."""
    with monkeypatch.context() as m:
        m.setattr(DeviceIndex, "_composed_for", lambda self, pc, nrows: None)
        m.setattr(J, "_kept_build_names", lambda di, cols: list(di.table.columns))
        yield


def _assert_same_table(got, want):
    assert got.nrows == want.nrows
    assert list(got.columns) == list(want.columns)  # column order
    for name, col in got.columns.items():
        ref = want.columns[name]
        assert type(col) is type(ref), name
        assert np.array_equal(np.asarray(col.storage), np.asarray(ref.storage)), name
        if isinstance(col, IntColumn):
            assert col.prefix == ref.prefix
        else:
            assert np.array_equal(col.dictionary, ref.dictionary), name
    assert got.to_rows() == want.to_rows()  # values and row order, decoded


# ---- the probe: (lower, counts) -------------------------------------------

RNG = np.random.default_rng(26)
N = 600


def _case_typed_dense_unique():
    di = _index(_people(range(50)), ["id"])
    vals = RNG.integers(-5, 60, N)  # below lo, above hi
    vals[7] = int(PAD_VALUE)
    # no hole INSIDE [lo, hi]: depth 2 stands, the range test finds the misses
    return di, _typed("c", vals), dict(unique=True, emit_ok=True, sorted=False)


def _case_typed_dense_full():
    di = _index(_people(range(50)), ["id"])
    return di, _typed("c", RNG.integers(0, 50, N)), dict(unique=True, emit_ok=True, sorted=False)


def _case_typed_dense_holes():
    di = _index(_people(range(0, 100, 2)), ["id"])  # odd ids are holes
    return di, _typed("c", RNG.integers(-3, 104, N)), dict(unique=True, emit_ok=False, sorted=False)


def _case_typed_sorted():
    ids = [i * 5000 for i in range(40)]  # range over 16 x distinct: sorted translation
    di = _index(_people(ids), ["id"])
    vals = RNG.choice(ids + [1, 4999, 250000, -7], N)
    vals[3] = int(PAD_VALUE)
    return di, _typed("c", vals), dict(unique=True, emit_ok=False, sorted=True)


def _case_typed_nonunique():
    ids = [i for i in range(40) for _ in range(1 + i % 3)]
    di = _index(_people(ids), ["id"])
    return di, _typed("c", RNG.integers(-2, 45, N)), dict(unique=False, emit_ok=False, sorted=False)


def _case_typed_wrong_prefix():
    di = _index(_people(range(50)), ["id"])  # no "x<int>" in the build side at all
    return di, _typed("x", RNG.integers(0, 50, N)), None  # empty translation: staged


def _string_values():
    vals = [f"c{int(v)}" for v in RNG.integers(0, 70, N)]  # c50..c69 miss
    vals[5] = None  # an absent cell (code -1)
    return vals


def _case_string_host():
    di = _index(_people(range(50)), ["id"])
    return di, _strings(_string_values()), dict(unique=True, emit_ok=False, sorted=False)


def _case_string_lanes():
    di = _index(_people(range(50)), ["id"])
    return di, _lane_column(_string_values()), dict(unique=True, emit_ok=False, sorted=False)


def _case_string_full():
    di = _index(_people(range(50)), ["id"])
    vals = [f"c{int(v)}" for v in RNG.integers(0, 50, N)]
    return di, _strings(vals), dict(unique=True, emit_ok=True, sorted=False)


def _case_prefix_probe():
    rng = np.random.default_rng(7)
    build = {
        "k": [f"k{int(v):03d}" for v in rng.integers(0, 40, 300)],
        "s": [f"s{int(v)}" for v in rng.integers(0, 3, 300)],
        "v": [str(i) for i in range(300)],
    }
    di = _index(build, ["k", "s"])
    vals = [f"k{int(v):03d}" for v in rng.integers(0, 55, N)]
    return di, _strings(vals), dict(unique=False, emit_ok=False, sorted=False)


PROBE_CASES = {
    "typed-dense-unique": _case_typed_dense_unique,
    "typed-dense-full": _case_typed_dense_full,
    "typed-dense-holes": _case_typed_dense_holes,
    "typed-sorted": _case_typed_sorted,
    "typed-nonunique": _case_typed_nonunique,
    "string-host": _case_string_host,
    "string-lanes": _case_string_lanes,
    "string-full": _case_string_full,
    "prefix-probe": _case_prefix_probe,
}


@pytest.mark.parametrize("case", sorted(PROBE_CASES))
def test_composed_probe_equals_the_staged_kernels(case):
    di, pc, expect = PROBE_CASES[case]()
    n = len(pc)
    entry = di._composed_for(pc, n)
    assert entry is not None
    assert (entry.cnt_tab is None) == expect["unique"]
    assert entry.emit_ok == expect["emit_ok"]
    assert (entry.base is not None and entry.base.ndim == 1) == expect["sorted"]

    with telemetry.collect() as recs:
        lower, counts = di.probe([pc], n)
    probes = [r for r in recs if r.stage == "join:probe"]
    assert [r.extra["tier"] for r in probes] == ["direct-composed"]
    assert not [r for r in recs if r.stage in ("join:translate", "join:pack")]
    assert probes[0].extra["row_gathers"] == entry.walks

    ref_lower, ref_counts = (np.asarray(a) for a in _staged_probe(di, pc))
    assert np.array_equal(np.asarray(counts), ref_counts)
    hit = ref_counts > 0
    assert hit.any() and (case.endswith("full") or (~hit).any())
    assert np.array_equal(np.asarray(lower)[hit], ref_lower[hit])
    assert np.asarray(lower).dtype == ref_lower.dtype == np.int32

    got = di.probe_slots(pc, n)
    assert (got is not None) == expect["emit_ok"]
    if got is not None:  # depth 2: counts by range test, rows through the slots
        entry2, slots, counts2 = got
        assert entry2 is entry
        assert np.array_equal(np.asarray(counts2), ref_counts)
        rows = np.asarray(J._slots_to_rows((slots,), (entry,))[0])
        assert np.array_equal(rows[hit], ref_lower[hit])


def test_a_probe_prefix_the_build_side_never_holds_stays_staged():
    di, pc, _ = _case_typed_wrong_prefix()
    assert di._composed_for(pc, len(pc)) is None
    lower, counts = di.probe([pc], len(pc))
    assert not np.asarray(counts).any() and di._compositions == 0


# ---- the joined table -----------------------------------------------------


def _orders(n, cust, prod, typed=True, extra=None):
    """A fact table of n rows over the given key values."""
    cols = {
        "cust_id": _typed("c", cust) if typed else _strings([f"c{v}" for v in cust]),
        "prod_id": _typed("p", prod) if typed else _strings([f"p{v}" for v in prod]),
        "qty": _strings([str(i % 9) for i in range(n)]),
    }
    cols.update(extra or {})
    return DeviceTable(cols, n, None)


def _stock(ids):
    return {
        "prod_id": [f"p{i}" for i in ids],
        "product": [f"thing{i % 5}" for i in ids],
        "price": [str(3 * i) for i in ids],
    }


def _join_case(name):
    """(stream, [(index, key columns)], expected join:expand path suffix,
    expected join.row_gathers of the two-dimension join)."""
    rng = np.random.default_rng(sum(map(ord, name)))
    n = 1000  # a selection of nine tenths still has four rows per slot of every universe here
    people, stock = _index(_people(range(100)), ["id"]), _index(_stock(range(20)), ["prod_id"])
    cust, prod = rng.integers(0, 100, n), rng.integers(0, 20, n)
    specs = [(people, ("cust_id",)), (stock, ("prod_id",))]
    if name == "all-matched":  # depth 2 in both dimensions: 3 + 2 emit walks
        return _orders(n, cust, prod), specs, "unique-identity", 5
    if name == "all-matched-strings":
        return _orders(n, cust, prod, typed=False), specs, "unique-identity", 5
    if name == "misses":  # values below lo and above hi: unique-partial
        return _orders(n, rng.integers(-4, 110, n), prod), specs, "unique-partial", None
    if name == "holes":  # depth 1 for people (a hole at every odd id), depth 2 for stock
        people = _index(_people(range(0, 200, 2)), ["id"])
        specs = [(people, ("cust_id",)), (stock, ("prod_id",))]
        return _orders(n, 2 * rng.integers(0, 100, n), prod), specs, "unique-identity", 6
    if name == "sorted-translation":
        ids = [i * 7000 for i in range(60)]
        people = _index(_people(ids), ["id"])
        specs = [(people, ("cust_id",)), (stock, ("prod_id",))]
        return _orders(n, rng.choice(ids, n), prod), specs, "unique-identity", None
    if name == "fan-out":
        people = _index(_people([i for i in range(100) for _ in range(1 + i % 3)]), ["id"])
        specs = [(people, ("cust_id",)), (stock, ("prod_id",))]
        return _orders(n, rng.integers(-3, 104, n), prod), specs, "fan-out", None
    if name == "collision-present":
        # the stream has `product` in every row: stock's is never read
        extra = {"product": _strings([f"mine{i % 4}" for i in range(n)])}
        return _orders(n, cust, prod, extra=extra), specs, "unique-identity", 4
    if name == "collision-absent":
        # ...and here some stream rows lack the cell: the merge must
        # fall back to stock's value, so the column is gathered
        vals = [None if i % 5 == 0 else f"mine{i % 4}" for i in range(n)]
        extra = {"product": _strings(vals)}
        return _orders(n, cust, prod, extra=extra), specs, "unique-identity", 5
    raise KeyError(name)


JOIN_CASES = [
    "all-matched", "all-matched-strings", "misses", "holes", "sorted-translation",
    "fan-out", "collision-present", "collision-absent",
]


def _gathers(recs):
    return sum(
        r.extra.get("row_gathers", 0)
        for r in recs
        if r.stage in ("join:translate", "join:probe", "join:merge")
    )


@pytest.mark.parametrize("case", JOIN_CASES)
def test_multiway_join_equals_the_staged_join(case, monkeypatch):
    stream, specs, path, gathers = _join_case(case)
    with telemetry.collect() as recs:
        got = J.multiway_join(stream, specs)
        recs = list(recs)
    with _staged_only(monkeypatch), telemetry.collect() as ref_recs:
        want = J.multiway_join(stream, specs)
        ref_recs = list(ref_recs)
    _assert_same_table(got, want)
    assert [r.extra["path"] for r in recs if r.stage == "join:expand"] == ["multiway-" + path]
    assert {r.extra["tier"] for r in recs if r.stage == "join:probe"} == {"direct-composed"}
    assert {r.extra["tier"] for r in ref_recs if r.stage == "join:probe"} == {"direct"}
    if gathers is not None:
        assert _gathers(recs) == gathers
        # the parent's path: per dimension translate 1 + probe 2, then every build column
        assert _gathers(ref_recs) == 6 + sum(len(di.table.columns) for di, _ in specs)
    if case == "collision-absent":
        # a row without the cell carries stock's product; one with it its own
        rows = got.to_rows()
        assert rows[0]["product"].startswith("thing") and rows[1]["product"].startswith("mine")


@pytest.mark.parametrize("case", JOIN_CASES)
def test_join_tables_equals_the_staged_join(case, monkeypatch):
    stream, specs, path, _ = _join_case(case)
    for di, cols in specs:  # each dimension as a binary join of its own
        with telemetry.collect() as recs:
            got = J.join_tables(stream, di, cols)
            recs = list(recs)
        with _staged_only(monkeypatch):
            want = J.join_tables(stream, di, cols)
        _assert_same_table(got, want)
        assert {r.extra["tier"] for r in recs if r.stage == "join:probe"} == {"direct-composed"}


@pytest.mark.parametrize("identity", [True, False], ids=["identity", "selection"])
@pytest.mark.parametrize("case", JOIN_CASES)
def test_multiway_join_selected_equals_the_staged_join(case, identity, monkeypatch):
    stream, specs, path, _ = _join_case(case)
    n = stream.nrows
    sel = (
        jnp.arange(n, dtype=jnp.int32)
        if identity
        else jnp.asarray(np.flatnonzero(np.arange(n) % 10 != 3).astype(np.int32))
    )
    args = (dict(stream.columns), sel, stream.device, specs)
    with telemetry.collect() as recs:
        got = J.multiway_join_selected(*args, identity=identity)
        recs = list(recs)
    with _staged_only(monkeypatch):
        want = J.multiway_join_selected(*args, identity=identity)
        cascade = J.multiway_join(
            DeviceTable({k: c.gather(sel) for k, c in stream.columns.items()}, len(sel), None),
            specs,
        )
    _assert_same_table(got, want)
    assert got.to_rows() == cascade.to_rows()
    assert [r.extra["path"] for r in recs if r.stage == "join:expand"] == ["fused-" + path]
    assert {r.extra["tier"] for r in recs if r.stage == "join:probe"} == {"direct-composed"}


def test_except_mask_reads_the_composed_counts(monkeypatch):
    stream, specs, _, _ = _join_case("misses")
    di, cols = specs[0]
    got = np.asarray(J.except_mask(stream, di, cols))
    with _staged_only(monkeypatch):
        want = np.asarray(J.except_mask(stream, di, cols))
    assert got.any() and np.array_equal(got, want)


# ---- the shapes that must not engage --------------------------------------


def _staged_case(name, monkeypatch):
    rng = np.random.default_rng(11)
    n = 600
    if name == "two-key-columns":
        build = {
            "k": [f"k{int(v):02d}" for v in rng.integers(0, 30, 200)],
            "s": [f"s{int(v)}" for v in rng.integers(0, 3, 200)],
            "v": [str(i) for i in range(200)],
        }
        di = _index(build, ["k", "s"])
        stream = DeviceTable.from_pylists({
            "k": [f"k{int(v):02d}" for v in rng.integers(0, 35, n)],
            "s": [f"s{int(v)}" for v in rng.integers(0, 4, n)],
        })
        return stream, di, ["k", "s"], "direct"
    if name == "wide-keys":
        m = 70_000  # 2 x 17 bits: past the 31-bit pack, the two-lane probe
        build = {
            "a": [f"a{i:06d}" for i in range(m)],
            "b": [f"b{i:06d}" for i in range(m)],
        }
        di = _index(build, ["a", "b"])
        assert di.packed_i32 is None and di.supported
        stream = DeviceTable.from_pylists({"a": [f"a{i:06d}" for i in range(0, 4 * m, 100)]})
        return stream, di, ["a"], None  # that tier records no join:probe stage
    if name == "no-direct-tier":
        monkeypatch.setattr(DeviceIndex, "DIRECT_MAX_BITS", -1)
        di = _index(_people(range(50)), ["id"])
        assert di.direct_cum is None
        return _orders(n, rng.integers(0, 50, n), rng.integers(0, 5, n)), di, ["cust_id"], "broadcast-i32"
    if name == "universe-over-a-quarter-of-the-rows":
        di = _index(_people(range(50)), ["id"])
        n = 4 * 50 - 1
        return _orders(n, rng.integers(0, 50, n), rng.integers(0, 5, n)), di, ["cust_id"], "direct"
    raise KeyError(name)


@pytest.mark.parametrize(
    "case",
    ["two-key-columns", "wide-keys", "no-direct-tier", "universe-over-a-quarter-of-the-rows"],
)
def test_shapes_outside_the_composed_tier_take_the_staged_path(case, monkeypatch):
    stream, di, cols, tier = _staged_case(case, monkeypatch)
    with telemetry.collect() as recs:
        out = J.join_tables(stream, di, cols)
        recs = list(recs)
    tiers = [r.extra.get("tier") for r in recs if r.stage == "join:probe"]
    assert tiers == ([tier] if tier else [])
    assert [r.stage for r in recs if r.stage == "join:translate"] == ["join:translate"]
    assert not [r for r in recs if r.stage == "join:compose"]
    assert di._compositions == 0 and not di._composed
    assert out.nrows > 0


def test_one_row_more_and_the_universe_is_a_quarter_of_the_rows():
    di = _index(_people(range(50)), ["id"])
    rng = np.random.default_rng(3)
    assert di._composed_for(_typed("c", rng.integers(0, 50, 199)), 199) is None
    assert di._composed_for(_typed("c", rng.integers(0, 50, 200)), 200) is not None


# ---- set-up only: nothing compiles or composes after the first execution --


def _star_plan():
    rng = np.random.default_rng(5)
    n = 900
    people = TakeRows(
        [Row({"id": f"c{i}", "name": f"n{i % 7}"}) for i in range(100)]
    ).index_on("id")
    stock = TakeRows(
        [Row({"prod_id": f"p{i}", "price": str(3 * i)}) for i in range(20)]
    ).index_on("prod_id")
    people.on_device("cpu")
    stock.on_device("cpu")
    orders = _orders(n, rng.integers(0, 100, n), rng.integers(0, 20, n))
    plan = source_from_table(orders).join(people, "cust_id").join(stock).plan
    return plan, [people.device_table, stock.device_table]


def test_a_cached_plan_composes_and_compiles_in_its_first_execution_only():
    plan, indexes = _star_plan()
    cache = PlanCache(size=4)
    first = cache.execute(plan)
    first.sync()
    composed = [di._compositions for di in indexes]
    assert all(c >= 1 for c in composed)  # the mechanism engaged
    compiled = compile_counts()
    lowered = cache.stats()["lowered"]
    for _ in range(3):
        with telemetry.collect() as recs:
            again = cache.execute(plan)
            again.sync()
            recs = list(recs)
        assert not [r for r in recs if r.stage == "join:compose"]
        assert {r.extra["tier"] for r in recs if r.stage == "join:probe"} == {"direct-composed"}
        assert again.to_rows() == first.to_rows()
    assert compile_counts() == compiled
    assert cache.stats()["lowered"] == lowered
    assert [di._compositions for di in indexes] == composed


@pytest.mark.parametrize("n_threads", [2, 16])
def test_threads_probing_one_index_compose_once(n_threads):
    """First touch from several threads (two, as the serving tier's
    callers; sixteen, more than the cores, with the interpreter
    switching threads every 10 us) composes once under ``_aux_lock``."""
    di = _index(_people(range(50)), ["id"])
    pc = _typed("c", np.random.default_rng(9).integers(0, 50, 400))
    start = threading.Barrier(n_threads)
    answers = [None] * n_threads

    def probe(slot):
        start.wait(timeout=60)
        answers[slot] = di.probe([pc], len(pc))

    threads = [threading.Thread(target=probe, args=(i,)) for i in range(n_threads)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert di._compositions == 1 and len(di._composed) == 1
    lower, counts = (np.asarray(a) for a in answers[0])
    for lo, ct in answers[1:]:
        assert np.array_equal(np.asarray(lo), lower) and np.array_equal(np.asarray(ct), counts)


def test_a_build_column_that_swaps_its_storage_is_composed_again():
    """Depth 2 keeps ``column.storage[rid_tab]`` per build column; a
    column whose storage is replaced (a lane dictionary settling) must
    not be served from the old table."""
    di = _index(_people(range(50)), ["id"])
    pc = _typed("c", np.random.default_rng(2).integers(0, 50, 400))
    entry, slots, _ = di.probe_slots(pc, len(pc))
    (before,) = di.composed_columns(entry, ["name"])
    assert di.composed_columns(entry, ["name"])[0] is before  # kept
    col = di.table.columns["name"]
    di.table.columns["name"] = col.with_codes(col.codes + 0)  # equal cells, new array
    (after,) = di.composed_columns(entry, ["name"])
    assert after is not before and np.array_equal(np.asarray(after), np.asarray(before))
