"""A lookup batch past the mirror cap as one chain of device programs
and one read-back (``DeviceIndex.point_bounds_many(chain=True)`` →
``DeviceTable.take_rows``): where a hit is exactly the row at ``lower``
(a unique index, every probe naming the full key) the bounds stay on the
device and come back with the rows; anywhere else the bounds are read and
the rows go through the same multi-column program with positions the
host formed.  Both forms and the host index give the same ``Row`` lists.
"""

import os

import numpy as np
import pytest

import csvplus_tpu as cp
from csvplus_tpu import Row, Take, TakeRows, from_file, to_rows_many
from csvplus_tpu.columnar.table import DeviceTable
from csvplus_tpu.obs.recompile import RecompileWatch
from csvplus_tpu.obs.span import tracer
from csvplus_tpu.ops.join import DeviceBounds, DeviceIndex

N = 1500


@pytest.fixture(autouse=True)
def past_mirror_cap(monkeypatch):
    """Every index of this module takes the device tier, as one past the
    16M mirror cap does."""
    monkeypatch.setattr(DeviceIndex, "POINT_MIRROR_MAX_KEYS", 100)


def _people(n=N):
    ids = (np.arange(n, dtype=np.int64) * 7 + 3) % (n * 3)  # distinct, unsorted
    return {
        "id": [f"c{int(v)}" for v in ids],
        "name": [f"name{i % 13}" for i in range(n)],
        "surname": [f"sur{i % 7}" for i in range(n)],
    }


def _rows(cols):
    names = list(cols)
    return [Row({k: cols[k][i] for k in names if cols[k][i] is not None}) for i in range(len(cols[names[0]]))]


def _looked_up(index, probes):
    """(row lists, one_trip of each batch's read) of one ``find_many``."""
    with tracer.trace("lookup") as tr:
        got = to_rows_many(index.find_many(probes))
    trips = [s.attrs["one_trip"] for s in tr.snapshot() if s.name == "serve:gather:readback"]
    return got, trips


def _string_keys():
    cols = _people()
    table = DeviceTable.from_pylists(cols, device="cpu")
    last = max(cols["id"])  # the index's last key: lower = n - 1
    return table, cols, ["id"], last, "d0"  # "d0" sorts past every "c<n>"


def _typed_keys(tmp_path, monkeypatch):
    """``id`` ingests as an ``IntColumn`` value lane (``c<n>``): the key
    is searched through its demoted dictionary and the rows' gather
    reads the value lane itself."""
    monkeypatch.setenv("CSVPLUS_STREAM_MIN_BYTES", "1")  # the streamed tier types its lanes
    cols = _people()
    path = tmp_path / "people.csv"
    path.write_text(
        "id,name,surname\n"
        + "".join(f"{a},{b},{c}\n" for a, b, c in zip(cols["id"], cols["name"], cols["surname"]))
    )
    table = from_file(str(path)).on_device("cpu").plan.table
    if os.environ.get("CSVPLUS_TYPED_LANES") != "0":
        assert table.columns["id"].kind == "int"
    return table, cols, ["id"], max(cols["id"]), "c999999999"


def _two_key_columns():
    n = N
    cols = {
        "a": [f"a{i // 40:03d}" for i in range(n)],
        "b": [f"b{(i * 11) % 40:02d}" for i in range(n)],
        "v": [str(i) for i in range(n)],
    }
    table = DeviceTable.from_pylists(cols, device="cpu")
    return table, cols, ["a", "b"], None, None


def _absent_cells():
    cols = _people()
    cols["name"] = [None if i % 3 == 0 else v for i, v in enumerate(cols["name"])]
    table = DeviceTable.from_rows(_rows(cols), device="cpu")
    return table, cols, ["id"], max(cols["id"]), "d0"


def _deferred_lane_payload(tmp_path, monkeypatch):
    """``name`` streams in several chunks past the device-dictionary
    threshold, so its lane dictionary is an unsorted concatenation whose
    union sort no one has paid when the first lookup comes."""
    monkeypatch.setenv("CSVPLUS_STREAM_MIN_BYTES", "1")
    monkeypatch.setenv("CSVPLUS_STREAM_CHUNK_BYTES", "2048")
    monkeypatch.setenv("CSVPLUS_DICT_DEVICE_MIN_DISTINCT", "1")
    cols = _people(600)
    cols["name"] = [f"ord-{(i * 37) % 600:06d}" for i in range(600)]
    path = tmp_path / "deferred.csv"
    path.write_text(
        "id,name,surname\n"
        + "".join(f"{a},{b},{c}\n" for a, b, c in zip(cols["id"], cols["name"], cols["surname"]))
    )
    table = from_file(str(path)).on_device("cpu").plan.table
    col = table.columns["name"]
    assert col._lane_state is not None and not col._dev_dict_sorted
    return table, cols, ["id"], max(cols["id"]), "c999999999"


def _sharded(tmp_path):
    from csvplus_tpu.parallel.mesh import make_mesh

    table, cols, keys, last, past = _string_keys()
    return table.with_sharding(make_mesh()), cols, keys, last, past


TABLES = ["string-keys", "typed-keys", "two-key-columns", "absent-cells", "deferred-lanes", "sharded"]


def _table(kind, tmp_path, monkeypatch):
    if kind == "string-keys":
        return _string_keys()
    if kind == "typed-keys":
        return _typed_keys(tmp_path, monkeypatch)
    if kind == "two-key-columns":
        return _two_key_columns()
    if kind == "absent-cells":
        return _absent_cells()
    if kind == "deferred-lanes":
        return _deferred_lane_payload(tmp_path, monkeypatch)
    return _sharded(tmp_path)


def _full_key_probes(cols, keys, last, past):
    if len(keys) == 2:
        hits = [(cols["a"][i], cols["b"][i]) for i in (0, 7, 399, 7, N - 1)]
        return hits + [("a000", "nope"), ("zzz", "b01"), ("a000", "b00")]
    hits = [cols["id"][i] for i in (0, 1, 501, 1, 77)]  # one of them twice
    return hits + ["nope", last, past, "c-1", (last,)]


@pytest.mark.parametrize("kind", TABLES)
def test_chain_two_trips_and_host_index_give_the_same_rows(kind, tmp_path, monkeypatch):
    table, cols, keys, last, past = _table(kind, tmp_path, monkeypatch)
    host = Take(TakeRows(_rows(cols))).index_on(*keys)
    unique = cp.take(table).unique_index_on(*keys).sync()
    plain = cp.take(table).index_on(*keys).sync()  # the same rows; does not know it is unique
    assert unique._impl.dev.unique and not plain._impl.dev.unique
    probes = _full_key_probes(cols, keys, last, past)
    want = to_rows_many(host.find_many(probes))
    assert sum(len(g) for g in want) >= 5 and [] in want
    got, trips = _looked_up(unique, probes)
    assert got == want and trips == [1]
    got, trips = _looked_up(plain, probes)
    assert got == want and trips == [0]
    # a prefix or an empty probe in the batch: its bounds are a range, so
    # the whole batch reads its bounds first
    mixed = probes[:3] + [()] + ([(cols["a"][0],)] if len(keys) == 2 else []) + probes[3:]
    want = to_rows_many(host.find_many(mixed))
    for index in (unique, plain):
        got, trips = _looked_up(index, mixed)
        assert got == want and trips == [0]
    # the deferred dictionary settled once, on the table the index holds
    if kind == "deferred-lanes":
        assert unique._impl.dev.table.columns["name"]._dev_dict_sorted


def test_a_non_unique_index_reads_its_bounds_and_returns_every_row_of_a_key():
    cols = _people()
    cols["id"] = [f"c{i % 500}" for i in range(N)]  # each id three times
    table = DeviceTable.from_pylists(cols, device="cpu")
    host = Take(TakeRows(_rows(cols))).index_on("id")
    index = cp.take(table).index_on("id").sync()
    probes = ["c0", "c499", "c500", "c17", "c17", "nope"]
    got, trips = _looked_up(index, probes)
    assert got == to_rows_many(host.find_many(probes)) and trips == [0]
    assert [len(g) for g in got] == [3, 3, 0, 3, 3, 0]
    with pytest.raises(cp.CsvPlusError, match="duplicate value"):
        cp.take(table).unique_index_on("id")


@pytest.mark.parametrize("length", [1, 2, 3, 31, 32, 33])
@pytest.mark.parametrize("form", ["one-trip", "two-trips"])
def test_bucket_edges_pad_queries_produce_nothing(length, form):
    table, cols, keys, last, past = _string_keys()
    make = cp.Take(table).unique_index_on if form == "one-trip" else cp.Take(table).index_on
    index = make("id").sync()
    host = Take(TakeRows(_rows(cols))).index_on("id")
    probes = [cols["id"][(i * 53) % N] for i in range(length)]
    if length > 1:  # a batch of misses alone gathers nothing where its bounds are read
        probes[length // 2] = "nope"
    got, trips = _looked_up(index, probes)
    assert len(got) == length and got == to_rows_many(host.find_many(probes))
    assert trips == [int(form == "one-trip")]
    impl = index._impl
    handle = impl.bounds_handle([(p,) for p in probes])
    if form == "one-trip":
        assert isinstance(handle, DeviceBounds)
        assert handle.res.shape == (2, 1 << (length - 1).bit_length())
        impl.rows_for_bounds(handle)
        assert list(handle) == impl.bounds_many([(p,) for p in probes])
    else:
        assert handle == impl.bounds_many([(p,) for p in probes])


@pytest.mark.parametrize("form", ["one-trip", "two-trips"])
def test_a_bucket_compiles_once(form):
    table, cols, keys, last, past = _string_keys()
    make = cp.Take(table).unique_index_on if form == "one-trip" else cp.Take(table).index_on
    index = make("id").sync()

    def batch(length, misses=0):
        probes = [cols["id"][(i * 29 + length) % N] for i in range(length - misses)]
        return probes + ["nope"] * misses

    for length in (1, 2, 4, 8, 16, 32):  # one batch a bucket
        index.find_many(batch(length))
    with RecompileWatch() as watch:
        for length in (1, 2, 3, 5, 7, 9, 15, 17, 24, 31, 32):
            index.find_many(batch(length))
            if length > 2:  # fewer hits than queries: no new shape
                index.find_many(batch(length, misses=2))
    watch.assert_zero()


def test_a_served_batch_takes_the_chain():
    from csvplus_tpu.serve import LookupServer

    table, cols, keys, last, past = _string_keys()
    index = cp.take(table).unique_index_on("id").sync()
    host = Take(TakeRows(_rows(cols))).index_on("id")
    probes = [cols["id"][i] for i in range(0, 200, 3)] + ["nope", last, past]
    with LookupServer(index) as srv:
        with tracer.trace("client") as tr:
            got = [f.result(timeout=30) for f in [srv.submit(p) for p in probes]]
    assert got == to_rows_many(host.find_many(probes))
    reads = [s for s in tr.snapshot() if s.name == "serve:gather:readback"]
    assert reads and all(s.attrs["one_trip"] == 1 and s.attrs["host_syncs"] == 1 for s in reads)
    assert srv.snapshot()["degraded"] == 0


@pytest.mark.parametrize("placement", ["one-device-set", "two-device-sets"])
def test_to_rows_of_a_selection_is_one_program_and_one_read(placement):
    """... per device set: a joined table may hold its stream's columns
    on a mesh beside its index's on one device, and those cannot enter
    one program."""
    table, cols, keys, last, past = _string_keys()
    programs = 1
    if placement == "two-device-sets":
        from csvplus_tpu.parallel.mesh import make_mesh

        meshed = table.with_sharding(make_mesh())
        mixed = dict(table.columns, name=meshed.columns["name"])
        table, programs = DeviceTable(mixed, table.nrows, table.device), 3  # a lane each
    sel = np.array([5, 0, N - 1, 5], dtype=np.int64)
    with tracer.trace("rows") as tr:
        got = table.to_rows(sel)
    assert got == [_rows(cols)[i] for i in sel.tolist()]
    attrs = {s.name: s.attrs for s in tr.snapshot()}
    assert attrs["serve:gather:take"]["dispatches"] == programs
    assert attrs["serve:gather:readback"]["host_syncs"] == programs
    assert attrs["serve:gather:readback"]["one_trip"] == 0
    assert table.to_rows(np.empty(0, dtype=np.int64)) == []
