"""Device-lane dictionaries for high-cardinality columns (ops/lanes.py +
the streamed ingest switch): bounded host RSS with full pipeline parity
(VERDICT round-2 weak #5 / next-round #5).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from csvplus_tpu import Like, Take, from_file
from csvplus_tpu.ops import lanes as L


def _rand_dict(rng, n, width=12):
    vals = set()
    while len(vals) < n:
        vals.add(
            "".join(chr(rng.integers(33, 127)) for _ in range(rng.integers(1, width)))
        )
    return np.sort(np.array([v.encode() for v in vals], dtype="S"))


def test_pack_unpack_roundtrip():
    rng = np.random.default_rng(5)
    d = _rand_dict(rng, 300)
    lanes = L.lanes_for_width(d.dtype.itemsize)
    packed = L.pack_host(d, lanes)
    back = L.unpack_host(packed)
    assert (back.astype(d.dtype) == d).all()
    # packed lane order (lexicographic over sign-flipped lanes) == byte order
    key = [tuple(int(l[i]) for l in packed) for i in range(d.size)]
    assert key == sorted(key)


def test_searchsorted_lanes_differential():
    rng = np.random.default_rng(7)
    d = _rand_dict(rng, 500)
    lanes = L.lanes_for_width(d.dtype.itemsize)
    keys = tuple(jnp.asarray(l) for l in L.pack_host(d, lanes))
    probes = np.concatenate([d[::3], _rand_dict(rng, 100, 10)])
    q = tuple(jnp.asarray(l) for l in L.pack_host(probes.astype(d.dtype), lanes))
    got = np.asarray(L.searchsorted_lanes(keys, q))
    want = np.searchsorted(d, probes.astype(d.dtype))
    assert (got == want).all()


def test_union_device_differential():
    rng = np.random.default_rng(9)
    chunks = [_rand_dict(rng, n) for n in (40, 200, 7, 130)]
    width = max(c.dtype.itemsize for c in chunks)
    lane_sets = [
        tuple(
            jnp.asarray(l)
            for l in L.pack_host(c.astype(f"S{width}"), L.lanes_for_width(width))
        )
        for c in chunks
    ]
    union_lanes, tables = L.union_device(lane_sets)
    union = L.unpack_host([np.asarray(l) for l in union_lanes])
    want = np.unique(np.concatenate([c.astype(f"S{width}") for c in chunks]))
    assert (union.astype(want.dtype) == want).all()
    for c, t in zip(chunks, tables):
        got = union[np.asarray(t)].astype(want.dtype)
        assert (got == c.astype(want.dtype)).all()


def test_translate_lanes_mixed_widths():
    rng = np.random.default_rng(11)
    build = _rand_dict(rng, 300, width=20)  # wider: more lanes
    query = _rand_dict(rng, 80, width=6)  # narrower: fewer lanes
    bl = tuple(
        jnp.asarray(l)
        for l in L.pack_host(build, L.lanes_for_width(build.dtype.itemsize))
    )
    ql = tuple(
        jnp.asarray(l)
        for l in L.pack_host(query, L.lanes_for_width(query.dtype.itemsize))
    )
    trans = np.asarray(L.translate_lanes(bl, ql))
    wide = f"S{max(build.dtype.itemsize, query.dtype.itemsize)}"
    for q, t in zip(query.astype(wide), trans):
        if t >= 0:
            assert build.astype(wide)[t] == q
        else:
            assert q not in build.astype(wide)


@pytest.fixture
def highcard_csv(tmp_path, monkeypatch):
    """A CSV whose order_id is unique per row; env tuned so the streamed
    tier engages with tiny chunks and the lane switch fires immediately."""
    monkeypatch.setenv("CSVPLUS_STREAM_MIN_BYTES", "1")
    monkeypatch.setenv("CSVPLUS_STREAM_CHUNK_BYTES", "512")
    monkeypatch.setenv("CSVPLUS_DICT_DEVICE_MIN_DISTINCT", "1")
    p = tmp_path / "orders.csv"
    p.write_text(
        "order_id,cust,qty\n"
        + "".join(f"ord-{i:06d},c{i % 9},{i % 5}\n" for i in range(400))
    )
    return str(p)


def test_streamed_highcard_column_stays_on_device(highcard_csv):
    """After ingest the unique column's dictionary lives ON DEVICE (host
    copy never built); decoding at the sink materializes it lazily and
    matches the host oracle byte for byte."""
    from csvplus_tpu.columnar.exec import execute_plan
    from csvplus_tpu.utils.observe import telemetry

    with telemetry.collect() as records:
        dev = from_file(highcard_csv).on_device()
        table = execute_plan(dev.plan)
    assert any(r.stage == "ingest:streamed" for r in records)
    col = table.columns["order_id"]
    assert col.dev_dictionary is not None
    assert col._dictionary is None  # the RSS bound: no host dictionary
    assert col.dict_size == 400  # distinct count without materializing
    rows = dev.to_rows()
    want = Take(from_file(highcard_csv)).to_rows()
    assert rows == want


def test_threshold_splits_columns_by_cardinality(tmp_path, monkeypatch):
    """With a mid-range threshold only the high-cardinality column
    switches to device lanes; low-cardinality columns keep host dicts."""
    from csvplus_tpu.columnar.exec import execute_plan

    monkeypatch.setenv("CSVPLUS_STREAM_MIN_BYTES", "1")
    monkeypatch.setenv("CSVPLUS_STREAM_CHUNK_BYTES", "512")
    monkeypatch.setenv("CSVPLUS_DICT_DEVICE_MIN_DISTINCT", "100")
    # this test pins the DICTIONARY cardinality-split behavior; typed
    # value lanes would otherwise claim the numeric-suffix columns
    monkeypatch.setenv("CSVPLUS_TYPED_LANES", "0")
    p = tmp_path / "o.csv"
    p.write_text(
        "order_id,cust,qty\n"
        + "".join(f"ord-{i:06d},c{i % 9},{i % 5}\n" for i in range(400))
    )
    table = execute_plan(from_file(str(p)).on_device().plan)
    assert table.columns["order_id"].dev_dictionary is not None
    assert table.columns["cust"].dev_dictionary is None
    assert table.columns["cust"]._dictionary is not None
    rows_dev = from_file(str(p)).on_device().to_rows()
    assert rows_dev == Take(from_file(str(p))).to_rows()


def test_highcard_filter_and_find(highcard_csv):
    """Equality filters and point lookups on a lane-dictionary column
    run without downloading the dictionary (find_code device search)."""
    dev = from_file(highcard_csv).on_device()
    got = dev.filter(Like({"order_id": "ord-000123"})).to_rows()
    want = (
        Take(from_file(highcard_csv))
        .filter(Like({"order_id": "ord-000123"}))
        .to_rows()
    )
    assert got == want and len(got) == 1
    # a value that cannot exist
    assert dev.filter(Like({"order_id": "zzz"})).to_rows() == []


def test_highcard_index_and_join(highcard_csv, tmp_path):
    """IndexOn/UniqueIndexOn/Find and a JOIN keyed on the high-
    cardinality column run via lane translation, matching the host."""
    idx = from_file(highcard_csv).on_device().unique_index_on("order_id")
    host_idx = Take(from_file(highcard_csv)).unique_index_on("order_id")
    assert len(idx) == 400
    assert idx.find("ord-000007").to_rows() == host_idx.find("ord-000007").to_rows()

    p2 = tmp_path / "notes.csv"
    p2.write_text(
        "order_id,note\n"
        + "".join(f"ord-{i:06d},n{i}\n" for i in range(0, 400, 7))
    )
    host = Take(from_file(p2)).join(host_idx, "order_id").to_rows()
    dev = from_file(str(p2)).on_device().join(idx, "order_id").to_rows()
    assert dev == host and len(host) == len(range(0, 400, 7))


def test_wide_probe_values_against_lane_index(highcard_csv, tmp_path):
    """A join keyed on a lane column must not crash when the probe side's
    host dictionary holds values wider than MAX_LANE_BYTES (ADVICE r3
    medium): wide values are unmatchable, everything else still joins."""
    idx = from_file(highcard_csv).on_device().unique_index_on("order_id")
    host_idx = Take(from_file(highcard_csv)).unique_index_on("order_id")

    wide = "W" * 48  # > MAX_LANE_BYTES: can never match a lane entry
    p2 = tmp_path / "notes.csv"
    p2.write_text(
        "order_id,note\n"
        + "".join(f"ord-{i:06d},n{i}\n" for i in range(0, 400, 7))
        + f"{wide},wide1\n"
        + f"{'X' * 33},wide2\n"
    )
    host = Take(from_file(str(p2))).join(host_idx, "order_id").to_rows()
    dev = from_file(str(p2)).on_device().join(idx, "order_id").to_rows()
    assert dev == host and len(host) == len(range(0, 400, 7))
    # and the anti-join keeps exactly the wide (unmatchable) rows
    host_x = Take(from_file(str(p2))).except_(host_idx, "order_id").to_rows()
    dev_x = from_file(str(p2)).on_device().except_(idx, "order_id").to_rows()
    assert dev_x == host_x and len(dev_x) == 2


def test_lane_index_persistence_roundtrip(highcard_csv, tmp_path):
    """write_to/load_index on a lane-dictionary index persists the packed
    lane arrays (no host dictionary materialization on either side —
    VERDICT r3 #8) and round-trips queries exactly."""
    from csvplus_tpu import load_index

    idx = from_file(highcard_csv).on_device().unique_index_on("order_id")
    impl = idx._impl
    col = impl.dev.table.columns["order_id"]
    assert col.dev_dictionary is not None and col._dictionary is None
    path = str(tmp_path / "lane.idx")
    idx.write_to(path)
    assert col._dictionary is None  # the write did not download it

    loaded = load_index(path)
    lcol = loaded._impl.dev.table.columns["order_id"]
    assert lcol.dev_dictionary is not None and lcol._dictionary is None
    assert len(loaded) == len(idx) == 400
    for probe in ("ord-000007", "ord-000399", "nope"):
        assert loaded.find(probe).to_rows() == idx.find(probe).to_rows()
    # full equality through a sink boundary
    assert Take(loaded).to_rows() == Take(idx).to_rows()


def test_deferred_union_payload_column_never_sorts(tmp_path, monkeypatch):
    """A multi-chunk lane column used ONLY as payload (decode/checksum/
    gather) must never pay the global dictionary union sort; keying on
    it triggers the deferred sort exactly once with identical results."""
    monkeypatch.setenv("CSVPLUS_STREAM_MIN_BYTES", "1")
    monkeypatch.setenv("CSVPLUS_STREAM_CHUNK_BYTES", "2048")
    monkeypatch.setenv("CSVPLUS_DICT_DEVICE_MIN_DISTINCT", "1")
    p = tmp_path / "o.csv"
    p.write_text(
        "order_id,cust,qty\n"
        + "".join(f"ord-{i:06d},c{i % 7},{i % 5}\n" for i in range(600))
    )
    from csvplus_tpu.columnar.exec import execute_plan
    from csvplus_tpu.utils.checksum import checksum_device_table, checksum_host_rows
    from csvplus_tpu.utils.observe import telemetry

    host_rows = Take(from_file(str(p))).to_rows()

    # payload-only: checksum + join keyed on ANOTHER column
    with telemetry.collect() as records:
        table = execute_plan(from_file(str(p)).on_device().plan)
        col = table.columns["order_id"]
        assert col.dev_dictionary is not None and not col._dev_dict_sorted
        sums = checksum_device_table(table, ["order_id"], positional=True)
        assert sums == checksum_host_rows(host_rows, ["order_id"], positional=True)
        assert not col._dev_dict_sorted  # checksum did not sort it
    assert not any(r.stage == "lane-dict:deferred-sort" for r in records)

    # decoding DOES settle the dictionary (host materialization path)
    assert from_file(str(p)).on_device().to_rows() == host_rows

    # keying on the deferred column sorts it lazily, once, correctly
    with telemetry.collect() as records:
        idx = from_file(str(p)).on_device().unique_index_on("order_id")
        host_idx = Take(from_file(str(p))).unique_index_on("order_id")
        assert idx.find("ord-000123").to_rows() == host_idx.find("ord-000123").to_rows()
    # one deferred sort per lane column at most (threshold=1 makes all
    # three columns lane-mode here: the key settles at sort_table, the
    # payloads at the find's host decode)
    n_sorts = sum(r.stage == "lane-dict:deferred-sort" for r in records)
    assert 1 <= n_sorts <= 3
    # filters on the deferred column too
    got = from_file(str(p)).on_device().filter(Like({"order_id": "ord-000007"})).to_rows()
    want = Take(from_file(str(p))).filter(Like({"order_id": "ord-000007"})).to_rows()
    assert got == want and len(got) == 1


def test_deferred_lanes_survive_mesh_sharding(tmp_path, monkeypatch):
    """A DEFERRED lane column carried through with_sharding must settle
    correctly against mesh-sharded codes (the translation table is
    replicated onto the codes' mesh): stream -> shard -> key on the
    lane column -> results match host (review r4 regression)."""
    import jax

    if len(jax.devices()) < 2:
        import pytest

        pytest.skip("needs a multi-device mesh")
    monkeypatch.setenv("CSVPLUS_STREAM_MIN_BYTES", "1")
    monkeypatch.setenv("CSVPLUS_STREAM_CHUNK_BYTES", "2048")
    monkeypatch.setenv("CSVPLUS_DICT_DEVICE_MIN_DISTINCT", "1")
    p = tmp_path / "o.csv"
    p.write_text(
        "order_id,cust,qty\n"
        + "".join(f"ord-{i:06d},c{i % 7},{i % 5}\n" for i in range(640))
    )
    # sharded ingest (shards=) intentionally excludes lane columns, so
    # the lane-through-with_sharding path is driven explicitly: stream
    # unsharded (deferred lanes form), then reshard the table
    from csvplus_tpu.columnar.ingest import source_from_table
    from csvplus_tpu.parallel.mesh import make_mesh

    pre = from_file(str(p)).on_device()
    col = pre.plan.table.columns["order_id"]
    assert col._lane_state is not None and not col._dev_dict_sorted
    dev = source_from_table(pre.plan.table.with_sharding(make_mesh()))
    col = dev.plan.table.columns["order_id"]
    assert col._lane_state is not None and not col._dev_dict_sorted
    # key on the deferred lane column over sharded codes
    idx = dev.unique_index_on("order_id")
    host_idx = Take(from_file(str(p))).unique_index_on("order_id")
    assert len(idx) == 640
    assert idx.find("ord-000321").to_rows() == host_idx.find("ord-000321").to_rows()
    # and full decode parity through the sharded path
    assert dev.to_rows() == Take(from_file(str(p))).to_rows()


def test_gathered_copies_share_one_materialized_dictionary(highcard_csv, monkeypatch):
    """A join's result column over a lane dictionary is a NEW column per
    execution (``gather`` / ``with_storage``): the host dictionary is
    downloaded and unpacked once per shared lane state, not once per copy
    — reading ``.dictionary`` of every selective result must not pay the
    whole dictionary again."""
    from csvplus_tpu.columnar.exec import execute_plan

    table = execute_plan(from_file(highcard_csv).on_device().plan)
    col = table.columns["order_id"]
    assert col.dev_dictionary is not None and col._dictionary is None
    calls = []
    real = L.unpack_host
    monkeypatch.setattr(L, "unpack_host", lambda lanes: calls.append(1) or real(lanes))
    sel = np.arange(0, 400, 7)
    copies = [col.gather(sel), col.gather(sel[::-1].copy()), col.with_storage(col.storage[:5])]
    first = copies[0].dictionary
    assert len(calls) == 1 and first.shape == (400,)
    for c in copies[1:] + [col]:
        assert c.dictionary is first  # the same array, no second download
    assert len(calls) == 1
    assert copies[0].decode() == [f"ord-{i:06d}" for i in sel]
    assert copies[1].decode() == [f"ord-{i:06d}" for i in sel[::-1]]
