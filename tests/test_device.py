"""Differential tests: columnar device executor vs host streaming path.

Every test computes the same pipeline both ways and requires identical
results — the host path (exact reference parity) is the oracle, per
SURVEY.md §7's design.  Runs on the CPU backend (conftest forces
JAX_PLATFORMS=cpu with 8 virtual devices); the same code paths run on TPU.
"""

import io

import pytest

import csvplus_tpu as csvplus
from csvplus_tpu import (
    All,
    Any,
    DataSourceError,
    Like,
    Not,
    Rename,
    Row,
    SetValue,
    Take,
    from_file,
)


@pytest.fixture()
def host_people(people_csv):
    return Take(from_file(people_csv))


@pytest.fixture()
def dev_people(people_csv):
    return from_file(people_csv).on_device("cpu")


def same(a, b):
    assert a == b, f"device/host mismatch: {len(a)} vs {len(b)} rows"


def test_ingest_parity(host_people, dev_people):
    same(dev_people.to_rows(), host_people.to_rows())


def test_plan_attached(dev_people):
    assert dev_people.plan is not None
    assert dev_people.filter(Like({"name": "Amelia"})).plan is not None
    # opaque callback breaks the plan but not the behavior
    assert dev_people.filter(lambda r: True).plan is None


def test_filter_like_parity(host_people, dev_people):
    p = Like({"name": "Amelia"})
    same(dev_people.filter(p).to_rows(), host_people.filter(p).to_rows())


def test_filter_combinators_parity(host_people, dev_people):
    p = All(Like({"name": "Amelia"}), Not(Like({"surname": "Smith"})))
    same(dev_people.filter(p).to_rows(), host_people.filter(p).to_rows())
    q = Any(Like({"surname": "Jones"}), Like({"surname": "Lewis"}))
    same(dev_people.filter(q).to_rows(), host_people.filter(q).to_rows())


def test_filter_missing_column_false(host_people, dev_people):
    p = Like({"nope": "x"})
    same(dev_people.filter(p).to_rows(), host_people.filter(p).to_rows())
    n = Not(Like({"nope": "x"}))
    same(dev_people.filter(n).to_rows(), host_people.filter(n).to_rows())


def test_chained_filters_narrow_selection(host_people, dev_people):
    """A second filter whose selection is far narrower than the stored
    columns takes the gathered-sub-column path (exec._SelView); parity
    and ordering must be identical, including when it empties out or
    when a Top slice sits between the filters."""
    for chain in (
        lambda s: s.filter(Like({"name": "Amelia"})).filter(
            Like({"surname": "Jones"})
        ),
        lambda s: s.filter(Like({"name": "Amelia"}))
        .top(3)
        .filter(Not(Like({"surname": "Smith"}))),
        lambda s: s.filter(Like({"name": "Amelia"})).filter(
            Like({"surname": "NOPE"})
        ),
        lambda s: s.filter(Like({"name": "Amelia"})).filter(
            Like({"nope": "x"})
        ),
    ):
        same(chain(dev_people).to_rows(), chain(host_people).to_rows())


def test_select_drop_columns_parity(host_people, dev_people):
    same(
        dev_people.select_columns("id", "name").to_rows(),
        host_people.select_columns("id", "name").to_rows(),
    )
    same(
        dev_people.drop_columns("born").to_rows(),
        host_people.drop_columns("born").to_rows(),
    )


def test_select_missing_column_errors(dev_people):
    with pytest.raises(DataSourceError):
        dev_people.select_columns("id", "zzz").to_rows()


def test_windowing_parity(host_people, dev_people):
    for stage in [
        lambda s: s.top(7),
        lambda s: s.drop(100),
        lambda s: s.filter(Like({"name": "Jack"})).top(3),
        lambda s: s.drop(5).top(5),
        lambda s: s.top(0),
    ]:
        same(stage(dev_people).to_rows(), stage(host_people).to_rows())


def test_map_setvalue_rename_parity(host_people, dev_people):
    m = SetValue("name", "Julia")
    same(dev_people.map(m).to_rows(), host_people.map(m).to_rows())
    r = Rename({"born": "year"})
    same(dev_people.map(r).to_rows(), host_people.map(r).to_rows())


def test_opaque_fallback_correct(host_people, dev_people):
    """An opaque Python callback mid-chain falls back transparently —
    and still benefits from the device prefix."""
    f = lambda row: int(row["born"]) % 2 == 0
    same(
        dev_people.filter(Like({"name": "Ava"})).filter(f).to_rows(),
        host_people.filter(Like({"name": "Ava"})).filter(f).to_rows(),
    )


def test_config1_tocsv_byte_identical(host_people, people_csv, tmp_path):
    """BASELINE config 1 on device: byte-identical CSV output."""
    host_out, dev_out = str(tmp_path / "host.csv"), str(tmp_path / "dev.csv")
    pipeline = lambda src: src.filter(Like({"name": "Amelia"})).map(
        SetValue("name", "Julia")
    ).to_csv_file
    pipeline(Take(from_file(people_csv)))(host_out, "name", "surname")
    pipeline(from_file(people_csv).on_device("cpu"))(dev_out, "name", "surname")
    assert open(dev_out, "rb").read() == open(host_out, "rb").read()


def test_json_parity(host_people, dev_people):
    a, b = io.StringIO(), io.StringIO()
    host_people.to_json(a)
    dev_people.to_json(b)
    assert a.getvalue() == b.getvalue()


def test_json_zero_columns_parity(host_people, dev_people):
    """A device source with every column dropped still serializes '{}'
    objects, byte-identical to the host path (advisor regression)."""
    stage = lambda s: s.drop_columns("id", "name", "surname", "born")
    a, b = io.StringIO(), io.StringIO()
    stage(host_people).to_json(a)
    stage(dev_people).to_json(b)
    assert a.getvalue() == b.getvalue()
    assert a.getvalue().startswith("[{}\n,{}\n")


def test_json_non_ascii_column_name_parity(tmp_path):
    """Non-ASCII column names must be raw UTF-8 on the device fast path,
    like the streaming sink / Go json.Encoder (advisor regression)."""
    p = str(tmp_path / "caf.csv")
    with open(p, "w", encoding="utf-8") as f:
        f.write("café,b\n x,1\ny,2\n")
    a, b = io.StringIO(), io.StringIO()
    Take(from_file(p)).to_json(a)
    from_file(p).on_device("cpu").to_json(b)
    assert a.getvalue() == b.getvalue()
    assert '"café"' in b.getvalue() and "\\u" not in b.getvalue()


# -- device joins ---------------------------------------------------------


@pytest.fixture()
def orders_host(orders_csv):
    return Take(from_file(orders_csv).select_columns("cust_id", "prod_id", "qty", "ts"))


@pytest.fixture()
def orders_dev(orders_csv):
    return (
        from_file(orders_csv)
        .on_device("cpu")
        .select_columns("cust_id", "prod_id", "qty", "ts")
    )


def test_join_parity(host_people, orders_host, orders_dev, people_csv):
    cust = Take(
        from_file(people_csv).select_columns("id", "name", "surname")
    ).unique_index_on("id")
    host_rows = orders_host.join(cust, "cust_id").to_rows()
    cust.on_device("cpu")
    dev_rows = orders_dev.join(cust, "cust_id").to_rows()
    same(dev_rows, host_rows)


def test_join_fanout_parity(people_csv, orders_host, orders_dev):
    """Non-unique index fan-out: each stream row merges with every match,
    in index-sorted order."""
    name_idx = Take(
        from_file(people_csv).select_columns("id", "name")
    ).index_on("id")
    # make it non-unique by indexing on a shared column
    multi = Take(from_file(people_csv)).index_on("name")
    host_rows = (
        orders_host.top(50).map(SetValue("name", "Amelia")).join(multi, "name").to_rows()
    )
    multi.on_device("cpu")
    dev_rows = (
        orders_dev.top(50).map(SetValue("name", "Amelia")).join(multi, "name").to_rows()
    )
    same(dev_rows, host_rows)


def test_three_way_join_parity(people_csv, stock_csv, orders_host, orders_dev):
    """BASELINE config 3 (README's 3-table join) on device == host."""
    cust = Take(
        from_file(people_csv).select_columns("id", "name", "surname")
    ).unique_index_on("id")
    prod = Take(
        from_file(stock_csv).select_columns("prod_id", "product", "price")
    ).unique_index_on("prod_id")
    host_rows = orders_host.join(cust, "cust_id").join(prod).to_rows()
    cust.on_device("cpu")
    prod.on_device("cpu")
    dev_rows = orders_dev.join(cust, "cust_id").join(prod).to_rows()
    same(dev_rows, host_rows)


def test_except_parity(people_csv, orders_host, orders_dev):
    some = Take(from_file(people_csv)).filter(Like({"name": "Amelia"})).index_on("id")
    host_rows = orders_host.except_(some, "cust_id").to_rows()
    some.on_device("cpu")
    dev_rows = orders_dev.except_(some, "cust_id").to_rows()
    same(dev_rows, host_rows)


def test_join_unmatched_keys_dropped(people_csv):
    """Stream keys absent from the index produce no output rows."""
    idx = Take(from_file(people_csv).select_columns("id", "name")).unique_index_on("id")
    idx.on_device("cpu")
    from csvplus_tpu.columnar.ingest import source_from_table
    from csvplus_tpu.columnar.table import DeviceTable

    stream = source_from_table(
        DeviceTable.from_pylists({"id": ["0", "99999", "3"]}, device="cpu")
    )
    rows = stream.join(idx, "id").to_rows()
    assert [r["id"] for r in rows] == ["0", "3"]


def test_device_index_survives_dict_miss(people_csv):
    """Probe values entirely absent from the build dictionary."""
    idx = Take(from_file(people_csv).select_columns("id", "name")).unique_index_on("id")
    idx.on_device("cpu")
    from csvplus_tpu.columnar.ingest import source_from_table
    from csvplus_tpu.columnar.table import DeviceTable

    stream = source_from_table(
        DeviceTable.from_pylists({"id": ["zzz", "qqq"]}, device="cpu")
    )
    assert stream.join(idx, "id").to_rows() == []
    assert [r["id"] for r in stream.except_(idx, "id").to_rows()] == ["zzz", "qqq"]


def test_wide_key_hybrid_path():
    """Two key columns whose packed width exceeds 31 bits exercise the
    host-int64 hybrid probe tier."""
    import random

    from csvplus_tpu import TakeRows
    from csvplus_tpu.columnar.ingest import source_from_table
    from csvplus_tpu.columnar.table import DeviceTable

    rng = random.Random(3)
    n = 70_000
    a = [f"a{i:06d}" for i in range(n)]
    b = [f"b{rng.randrange(n):06d}" for _ in range(n)]
    v = [str(i) for i in range(n)]
    rows = [Row({"a": x, "b": y, "v": z}) for x, y, z in zip(a, b, v)]
    idx = TakeRows(rows).index_on("a", "b")
    idx.on_device("cpu")
    assert idx.device_table.packed_hi is not None  # wide device tier engaged

    probe = DeviceTable.from_pylists(
        {"a": [a[0], a[1], "zzz"], "b": [b[0], "nope", b[2]]}, device="cpu"
    )
    got = source_from_table(probe).join(idx, "a", "b").to_rows()
    want = (
        TakeRows([Row({"a": a[0], "b": b[0]}), Row({"a": a[1], "b": "nope"}),
                  Row({"a": "zzz", "b": b[2]})])
        .join(idx, "a", "b")
        .to_rows()
    )
    assert got == want and len(got) == 1


def test_rename_collision_parity(host_people, dev_people):
    """Rename onto an existing column overwrites it (review regression)."""
    r = Rename({"name": "surname"})
    same(dev_people.map(r).to_rows(), host_people.map(r).to_rows())
    chained = Rename({"name": "born"})
    same(dev_people.map(chained).to_rows(), host_people.map(chained).to_rows())


def test_join_missing_key_column_row_number_parity(people_csv, orders_csv):
    """Join/Except on a key column absent from the stream reports the
    host's row number — the reader's first data record (review regr.)."""
    idx = Take(from_file(people_csv)).unique_index_on("id")
    idx.on_device("cpu")
    with pytest.raises(DataSourceError) as eh:
        Take(from_file(orders_csv)).join(idx, "zzz").to_rows()
    with pytest.raises(DataSourceError) as ed:
        from_file(orders_csv).on_device("cpu").join(idx, "zzz").to_rows()
    assert str(ed.value) == str(eh.value) == 'row 2: missing column "zzz"'
    with pytest.raises(DataSourceError) as ed2:
        from_file(orders_csv).on_device("cpu").except_(idx, "zzz").to_rows()
    assert str(ed2.value) == str(eh.value)


def test_except_preserves_source_row_numbers(people_csv, orders_csv):
    """except_ passes rows through 1:1, so errors AFTER it still carry
    the originating reader's record numbers (review regression)."""
    # index over a subset of ids, so some orders rows SURVIVE the except_
    idx = Take(from_file(people_csv)).top(10).unique_index_on("id")
    idx.on_device("cpu")
    with pytest.raises(DataSourceError) as eh:
        (
            Take(from_file(orders_csv))
            .except_(idx, "cust_id")
            .select_columns("zzz")
            .to_rows()
        )
    with pytest.raises(DataSourceError) as ed:
        (
            from_file(orders_csv)
            .on_device("cpu")
            .except_(idx, "cust_id")
            .select_columns("zzz")
            .to_rows()
        )
    assert str(ed.value) == str(eh.value)


def test_join_absent_key_cell_errors(people_csv):
    """A heterogeneous stream row lacking the join-key cell errors like the
    host path (review regression)."""
    from csvplus_tpu import TakeRows
    from csvplus_tpu.columnar.ingest import source_from_table
    from csvplus_tpu.columnar.table import DeviceTable

    idx = Take(from_file(people_csv).select_columns("id", "name")).unique_index_on("id")
    idx.on_device("cpu")
    rows = [Row({"id": "1", "v": "a"}), Row({"v": "b"})]
    stream = source_from_table(DeviceTable.from_rows(rows, device="cpu"))
    with pytest.raises(DataSourceError) as e:
        stream.join(idx, "id").to_rows()
    assert 'missing column "id"' in str(e.value)
    with pytest.raises(DataSourceError):
        stream.except_(idx, "id").to_rows()


def test_join_absent_collision_keeps_index_value(people_csv):
    """On column collision, an absent stream cell keeps the index value,
    like the host dict merge (review regression)."""
    from csvplus_tpu import TakeRows
    from csvplus_tpu.columnar.ingest import source_from_table
    from csvplus_tpu.columnar.table import DeviceTable

    index_rows = [Row({"k": "a", "extra": "IDX"})]
    idx = TakeRows(index_rows).index_on("k")
    host = TakeRows([Row({"k": "a"}), Row({"k": "a", "extra": "S"})]).join(idx, "k").to_rows()
    idx.on_device("cpu")
    stream = source_from_table(
        DeviceTable.from_rows([Row({"k": "a"}), Row({"k": "a", "extra": "S"})], device="cpu")
    )
    dev = stream.join(idx, "k").to_rows()
    assert dev == host
    assert dev[0]["extra"] == "IDX" and dev[1]["extra"] == "S"


def test_device_select_missing_column_row_number(dev_people, host_people):
    """Device SelectCols error carries the originating source's row number
    (first streamed record of the reader), like the host path."""
    with pytest.raises(DataSourceError) as e:
        dev_people.select_columns("id", "zzz").to_rows()
    with pytest.raises(DataSourceError) as eh:
        host_people.select_columns("id", "zzz").to_rows()
    assert str(e.value) == str(eh.value) == 'row 2: missing column "zzz"'


def test_policy_dedup_invalidates_stale_device_index(people_csv):
    """Named-policy dedup on a materialized index must drop the stale
    columnar copy so device joins can't see removed rows (review regr.)."""
    from csvplus_tpu import TakeRows
    from csvplus_tpu.columnar.ingest import source_from_table
    from csvplus_tpu.columnar.table import DeviceTable

    rows = [Row({"k": "a", "v": "1"}), Row({"k": "a", "v": "2"}), Row({"k": "b", "v": "3"})]
    idx = TakeRows(rows).index_on("k")
    idx.on_device("cpu")
    idx.resolve_duplicates("first")
    assert idx.device_table is None  # stale copy dropped
    stream = source_from_table(DeviceTable.from_pylists({"k": ["a", "b"]}, device="cpu"))
    host = TakeRows([Row({"k": "a"}), Row({"k": "b"})]).join(idx, "k").to_rows()
    assert stream.join(idx, "k").to_rows() == host
    assert len(host) == 2


def test_rename_absent_cells_keep_destination(people_csv):
    """Rename with absent source cells must not destroy the destination
    column (review regression)."""
    from csvplus_tpu import TakeRows
    from csvplus_tpu.columnar.ingest import source_from_table
    from csvplus_tpu.columnar.table import DeviceTable
    from csvplus_tpu import Rename as R

    rows = [Row({"b": "KEEP"}), Row({"a": "y"})]
    host = TakeRows(rows).map(R({"a": "b"})).to_rows()
    dev = source_from_table(DeviceTable.from_rows(rows, device="cpu")).map(
        R({"a": "b"})
    ).to_rows()
    assert dev == host == [Row({"b": "KEEP"}), Row({"b": "y"})]


def test_select_columns_absent_cell_errors(people_csv):
    """Device SelectCols checks per-row cell presence (review regression)."""
    from csvplus_tpu import TakeRows
    from csvplus_tpu.columnar.ingest import source_from_table
    from csvplus_tpu.columnar.table import DeviceTable

    rows = [Row({"a": "x", "b": "1"}), Row({"a": "y"})]
    dev = source_from_table(DeviceTable.from_rows(rows, device="cpu"))
    with pytest.raises(DataSourceError) as e:
        dev.select_columns("b").to_rows()
    assert 'missing column "b"' in str(e.value)
    # empty selection: no rows streamed -> no error, like the host path
    assert dev.top(0).select_columns("zzz").to_rows() == []


def test_select_columns_row_major_failure_order():
    """With absent cells in several selected columns the error is the
    host's: first streamed row missing any column, first such column
    within it (review regression)."""
    from csvplus_tpu import TakeRows
    from csvplus_tpu.columnar.ingest import source_from_table
    from csvplus_tpu.columnar.table import DeviceTable

    rows = [Row({"a": "1", "b": "2"}), Row({"a": "3"}), Row({"b": "4"})]
    with pytest.raises(DataSourceError) as eh:
        TakeRows(rows).select_columns("a", "b").to_rows()
    dev = source_from_table(DeviceTable.from_rows(rows, device="cpu"))
    with pytest.raises(DataSourceError) as ed:
        dev.select_columns("a", "b").to_rows()
    assert str(ed.value) == str(eh.value)
    assert 'missing column "b"' in str(ed.value)  # row 1 fails on "b" first


def test_filter_after_dropping_all_columns(dev_people, host_people):
    """Zero-column views keep their row count (review regression)."""
    stage = lambda s: s.drop_columns("id", "name", "surname", "born").filter(
        Not(Like({"a": "x"}))
    )
    same(stage(dev_people).to_rows(), stage(host_people).to_rows())
    gone = lambda s: s.drop_columns("id", "name", "surname", "born").filter(
        Like({"a": "x"})
    )
    same(gone(dev_people).to_rows(), gone(host_people).to_rows())


def test_datasource_on_device_general(host_people):
    """Any host source can migrate to the device mid-chain."""
    dev = host_people.filter(lambda r: r["name"] != "Jack").on_device("cpu")
    assert dev.plan is not None
    got = dev.filter(Like({"surname": "Smith"})).to_rows()
    want = (
        host_people.filter(lambda r: r["name"] != "Jack")
        .filter(Like({"surname": "Smith"}))
        .to_rows()
    )
    assert got == want


def test_telemetry_collects_stages(dev_people):
    from csvplus_tpu import telemetry

    with telemetry.collect() as records:
        dev_people.filter(Like({"name": "Amelia"})).select_columns(
            "id", "name"
        ).to_rows()
    stages = [r.stage for r in records]
    assert "Filter" in stages and "SelectCols" in stages
    f = records[stages.index("Filter")]
    assert f.rows_in == 120 and f.rows_out == 12
    assert telemetry.report()
    assert not telemetry.enabled  # scope ended


def test_telemetry_fallback_exception_transparent(dev_people):
    """Exceptions inside telemetry-wrapped stages propagate unchanged
    (review regression: the trace annotation wrapper must not double-
    yield), so host fallback + pinned errors survive telemetry."""
    from csvplus_tpu import telemetry

    with telemetry.collect():
        # opaque callback forces UnsupportedPlan -> host fallback path
        rows = dev_people.filter(Like({"name": "Ava"})).filter(
            lambda r: True
        ).to_rows()
        assert len(rows) == 12
        # DataSourceError keeps its row number through telemetry
        with pytest.raises(DataSourceError) as e:
            dev_people.select_columns("zzz").to_rows()
        assert str(e.value) == 'row 2: missing column "zzz"'


def test_telemetry_native_tier_decline_not_recorded(tmp_path):
    """A declined fast-path tier leaves no misleading stage record."""
    from csvplus_tpu import from_file, telemetry

    p = tmp_path / "long.csv"
    p.write_text("a,b\n" + "x" * 500 + ",1\n")
    with telemetry.collect() as recs:
        from_file(str(p)).on_device("cpu")
    stages = [r.stage for r in recs]
    assert "ingest:native-encoded" not in stages
    assert "ingest:python" in stages


def test_vectorized_csv_sink_byte_identical(people_csv, tmp_path):
    """The vectorized CSV body encoder is byte-identical to streaming,
    including quoting edge cases."""
    from csvplus_tpu import TakeRows
    from csvplus_tpu.columnar.ingest import source_from_table
    from csvplus_tpu.columnar.table import DeviceTable

    rows = [
        Row({"a": 'say "hi"', "b": "x,y"}),
        Row({"a": " lead", "b": "plain"}),
        Row({"a": "", "b": "\\."}),
        Row({"a": "nl\nin", "b": "cr\rin"}),
        Row({"a": "Zoë", "b": "tab\tstart"}),
    ]
    import io as _io

    host_buf, dev_buf = _io.StringIO(), _io.StringIO()
    TakeRows(rows).to_csv(host_buf, "a", "b")
    source_from_table(DeviceTable.from_rows(rows, device="cpu")).to_csv(
        dev_buf, "a", "b"
    )
    assert dev_buf.getvalue() == host_buf.getvalue()
    # whole-file parity on the corpus too
    h, d = str(tmp_path / "h.csv"), str(tmp_path / "d.csv")
    Take(from_file(people_csv)).to_csv_file(h, "id", "name", "surname", "born")
    from csvplus_tpu import from_file as ff

    ff(people_csv).on_device("cpu").to_csv_file(d, "id", "name", "surname", "born")
    assert open(d, "rb").read() == open(h, "rb").read()


def test_vectorized_csv_sink_missing_column_streams(people_csv, tmp_path):
    """Missing column still yields the streaming path's row-numbered
    error and no partial file."""
    import os as _os

    dev = from_file(people_csv).on_device("cpu")
    path = str(tmp_path / "x.csv")
    with pytest.raises(DataSourceError):
        dev.to_csv_file(path, "id", "zzz")
    assert not _os.path.exists(path)


def test_vectorized_json_sink_byte_identical(people_csv, dev_people, host_people):
    import io as _io

    a, b = _io.StringIO(), _io.StringIO()
    host_people.to_json(a)
    dev_people.to_json(b)
    assert b.getvalue() == a.getvalue()
    # unicode + special chars through the json fast path
    from csvplus_tpu import TakeRows
    from csvplus_tpu.columnar.ingest import source_from_table
    from csvplus_tpu.columnar.table import DeviceTable

    rows = [Row({"a": 'q"\\', "b": "Zoë\nnl"}), Row({"a": "", "b": "\t"})]
    c, d = _io.StringIO(), _io.StringIO()
    TakeRows(rows).to_json(c)
    source_from_table(DeviceTable.from_rows(rows, device="cpu")).to_json(d)
    assert d.getvalue() == c.getvalue()
    # heterogeneous rows stream but stay identical
    het = [Row({"a": "1"}), Row({"b": "2"})]
    e, f = _io.StringIO(), _io.StringIO()
    TakeRows(het).to_json(e)
    source_from_table(DeviceTable.from_rows(het, device="cpu")).to_json(f)
    assert f.getvalue() == e.getvalue()
    # empty
    g, h = _io.StringIO(), _io.StringIO()
    TakeRows([]).to_json(g)
    source_from_table(DeviceTable.from_rows([], device="cpu")).to_json(h)
    assert h.getvalue() == g.getvalue() == "[]"


def test_take_drop_while_symbolic_parity(host_people, dev_people):
    """Symbolic TakeWhile/DropWhile lower to a prefix cut on device."""
    assert dev_people.take_while(Like({"name": "Amelia"})).plan is not None
    for stage in [
        lambda s: s.take_while(Like({"name": "Amelia"})),
        lambda s: s.drop_while(Like({"name": "Amelia"})),
        lambda s: s.take_while(Not(Like({"name": "NoSuch"}))),  # never stops
        lambda s: s.drop_while(Not(Like({"name": "NoSuch"}))),  # drops all
        lambda s: s.filter(Like({"surname": "Smith"})).take_while(
            Not(Like({"name": "Oliver"}))
        ),
        lambda s: s.drop_while(Like({"name": "Amelia"})).take_while(
            Not(Like({"name": "Jack"}))
        ).top(7),
    ]:
        same(stage(dev_people).to_rows(), stage(host_people).to_rows())
    # opaque predicates still fall back
    f = lambda r: r["name"] == "Amelia"
    same(
        dev_people.take_while(f).to_rows(), host_people.take_while(f).to_rows()
    )


def test_explain_shows_break_point(dev_people):
    assert "Scan" in dev_people.explain()
    assert "Filter" in dev_people.filter(Like({"name": "Ava"})).explain()
    broken = dev_people.filter(lambda r: True)
    text = broken.explain()
    assert "host streaming" in text and "filter" in text and "not symbolic" in text


def test_explain_host_chain(host_people):
    assert "host streaming" in host_people.explain()


def test_explain_note_propagates_and_covers_all_breaks(dev_people, people_csv):
    """The break reason survives further chaining, and join/except/
    validate record breaks too (review regression)."""
    broken = dev_people.filter(lambda r: True).map(SetValue("a", "b")).top(3)
    assert "filter(<lambda>) is not symbolic" in broken.explain()
    host_idx = Take(from_file(people_csv)).unique_index_on("id")  # no device copy
    j = dev_people.join(host_idx, "id")
    assert "no device copy" in j.explain()
    v = dev_people.validate(lambda r: None)
    assert "no symbolic form" in v.explain()


def test_profile_to_writes_trace(tmp_path, dev_people):
    """profile_to captures a JAX device trace directory."""
    import os

    from csvplus_tpu import profile_to

    log_dir = str(tmp_path / "trace")
    with profile_to(log_dir):
        dev_people.filter(Like({"name": "Ava"})).to_rows()
    assert os.path.isdir(log_dir) and os.listdir(log_dir)


def test_take_of_device_table_escape_hatch(dev_people, host_people):
    """take(DeviceTable) streams decoded rows (the documented escape
    hatch) and carries a plan for symbolic continuation."""
    from csvplus_tpu import take
    from csvplus_tpu.columnar.exec import execute_plan

    table = execute_plan(dev_people.plan)
    src = take(table)
    assert src.plan is not None
    assert src.to_rows() == host_people.to_rows()
    # push-style over the table directly
    seen = []
    table.iterate(seen.append)
    assert len(seen) == 120


def test_sharded_table_from_pylists():
    from csvplus_tpu.parallel.mesh import make_mesh
    from csvplus_tpu.columnar.table import DeviceTable

    st = DeviceTable.from_pylists(
        {"a": [str(i) for i in range(11)]}, device="cpu"
    ).with_sharding(make_mesh(8))
    assert st.nrows == 11
    assert len(st.columns["a"]) % 8 == 0  # padded for shard divisibility
    assert [r["a"] for r in st.to_rows()] == [str(i) for i in range(11)]


def test_transform_and_update_symbolic_parity(host_people, dev_people):
    """Symbolic Transform and chained Update exprs lower on device."""
    from csvplus_tpu import Update

    u = Update(Rename({"born": "year"}), SetValue("tag", "T"))
    assert dev_people.transform(u).plan is not None
    same(dev_people.transform(u).to_rows(), host_people.transform(u).to_rows())
    same(dev_people.map(u).to_rows(), host_people.map(u).to_rows())
    # Update containing an opaque fn breaks the plan but not behavior
    mixed = Update(SetValue("a", "1"), lambda r: r)
    assert dev_people.map(mixed).plan is None
    same(dev_people.map(mixed).to_rows(), host_people.map(mixed).to_rows())


def test_wide_tier_join_seeded_sweep():
    """Wide (host-int64) key tier: 3 seeded content draws of a 2-column
    join vs host, including misses and duplicate keys."""
    import random

    from csvplus_tpu import TakeRows
    from csvplus_tpu.columnar.ingest import source_from_table
    from csvplus_tpu.columnar.table import DeviceTable

    n = 70_000
    a_vals = [f"a{i:06d}" for i in range(n)]
    for seed in (1, 2, 3):
        rng = random.Random(seed)
        b_vals = [f"b{rng.randrange(n):06d}" for _ in range(n)]
        rows = [Row({"a": x, "b": y, "v": str(i)})
                for i, (x, y) in enumerate(zip(a_vals, b_vals))]
        idx = TakeRows(rows).index_on("a", "b")
        probes = [Row({"a": a_vals[rng.randrange(n)], "b": rng.choice(b_vals + ["miss"])})
                  for _ in range(50)]
        host = TakeRows(probes).join(idx, "a", "b").to_rows()
        idx.on_device("cpu")
        assert idx.device_table.packed_hi is not None  # wide device tier
        dev = source_from_table(
            DeviceTable.from_rows(probes, device="cpu")
        ).join(idx, "a", "b").to_rows()
        assert dev == host


def test_repeated_ingest_no_reference_leak(people_csv):
    """Repeated OnDevice ingests of the same file release their tables
    (guards against plan/runner reference cycles pinning device memory)."""
    import gc
    import weakref

    from csvplus_tpu.columnar import exec as ex

    refs = []
    for _ in range(5):
        src = from_file(people_csv).on_device("cpu")
        table = src.plan.table
        refs.append(weakref.ref(table))
        src.filter(Like({"name": "Ava"})).to_rows()
        del src, table
    gc.collect()
    alive = sum(1 for r in refs if r() is not None)
    assert alive == 0, f"{alive}/5 ingested tables still referenced"


def test_telemetry_report_format(dev_people):
    from csvplus_tpu import telemetry

    with telemetry.collect():
        dev_people.filter(Like({"name": "Ava"})).to_rows()
        report = telemetry.report()
    lines = report.splitlines()
    assert lines[0].split() == ["stage", "rows", "in", "rows", "out", "time"]
    assert any("Filter" in l and "120" in l and "12" in l for l in lines[1:])
    # stage rows end with a time; the report closes with the accounting
    # trailer (counters when any, always host_sync_elements)
    assert lines[-1].startswith("host_sync_elements:")
    assert all(l.rstrip().endswith("ms") for l in lines[1:-1] if not l.startswith(("counters:", "  ")))


class _SyncCountingNp:
    """numpy proxy counting device->host materializations of LARGE jax
    arrays (np.asarray over >64 elements); scalar syncs stay free."""

    def __init__(self, real):
        self._real = real
        self.big_syncs = []

    def __getattr__(self, name):
        attr = getattr(self._real, name)
        if name == "asarray":
            proxy = self

            def counted(x, *a, **k):
                if isinstance(x, jax.Array) and x.size > 64:
                    proxy.big_syncs.append(int(x.size))
                return attr(x, *a, **k)

            return counted
        return attr


def test_pipeline_stages_no_per_row_host_sync(people_csv, orders_csv, monkeypatch):
    """filter -> join -> select executes with O(1) scalars crossing to
    host per stage: no stage materializes a row-length array on host
    (VERDICT round-1 item 2).  The sink decode is outside this scope."""
    import jax as _jax
    import csvplus_tpu.columnar.exec as exec_mod
    import csvplus_tpu.ops.join as join_mod
    import csvplus_tpu.columnar.table as table_mod

    global jax
    jax = _jax

    idx = Take(from_file(people_csv)).unique_index_on("id")
    idx.on_device("cpu")
    src = (
        from_file(orders_csv)
        .on_device("cpu")
        .filter(Not(Like({"cust_id": "0"})))
        .join(idx, "cust_id")
        .select_columns("cust_id", "name", "qty")
    )

    counters = []
    for mod in (exec_mod, join_mod, table_mod):
        proxy = _SyncCountingNp(mod.np)
        monkeypatch.setattr(mod, "np", proxy)
        counters.append((mod.__name__, proxy))

    from csvplus_tpu.columnar.exec import execute_plan

    table = execute_plan(src.plan)
    assert table.nrows > 0
    # the selection vector itself must be device-resident
    for mod_name, proxy in counters:
        assert proxy.big_syncs == [], (
            f"{mod_name} synced row-length arrays to host: {proxy.big_syncs}"
        )


def test_expand_matches_device_empty():
    """Empty probe input expands to empty ids, like the numpy twin
    (review regression)."""
    import jax.numpy as jnp
    from csvplus_tpu.ops.join import expand_matches_device

    p, b = expand_matches_device(
        jnp.zeros(0, dtype=jnp.int32), jnp.zeros(0, dtype=jnp.int32)
    )
    assert p.shape == (0,) and b.shape == (0,)


def test_symbolic_validate_device_vs_host(people_csv):
    """Validate with a symbolic predicate runs on device and matches the
    host path: pass-through on success, row-numbered failure otherwise."""
    from csvplus_tpu import DataSourceError, Like, Not, Take, from_file

    ok_pred = Not(Like({"name": "___nope___"}))
    dev = from_file(people_csv).on_device("cpu").validate(ok_pred).to_rows()
    host = Take(from_file(people_csv)).validate(ok_pred).to_rows()
    assert dev == host and len(dev) == 120

    # symbolic validate stays on the device plan
    src = from_file(people_csv).on_device("cpu").validate(ok_pred)
    assert src.plan is not None

    bad = Like({"name": "___nope___"})
    with pytest.raises(DataSourceError) as dev_err:
        from_file(people_csv).on_device("cpu").validate(bad, "bad name").to_rows()
    with pytest.raises(DataSourceError) as host_err:
        Take(from_file(people_csv)).validate(bad, "bad name").to_rows()
    assert str(dev_err.value) == str(host_err.value)
    assert "bad name" in str(dev_err.value)


def test_symbolic_validate_failure_row_number(tmp_path):
    from csvplus_tpu import DataSourceError, Like, Take, from_file

    p = tmp_path / "v.csv"
    p.write_text("k\nok\nok\nBAD\nok\n")
    pred = Like({"k": "ok"})
    with pytest.raises(DataSourceError) as dev_err:
        from_file(str(p)).on_device("cpu").validate(pred).to_rows()
    with pytest.raises(DataSourceError) as host_err:
        Take(from_file(str(p))).validate(pred).to_rows()
    # record 1 is the header; BAD is record 4
    assert dev_err.value.line == host_err.value.line == 4


def test_on_device_missing_file_error_parity():
    """OnDevice on a nonexistent path raises the host path's row-numbered
    open error (csvplus.go:1209-1227), not a raw OSError."""
    from csvplus_tpu import DataSourceError, Take, from_file

    with pytest.raises(DataSourceError) as dev_err:
        from_file("/tmp/___no_such_file___.csv").on_device("cpu").to_rows()
    with pytest.raises(DataSourceError) as host_err:
        Take(from_file("/tmp/___no_such_file___.csv")).to_rows()
    assert str(dev_err.value) == str(host_err.value)


def test_symbolic_validate_before_top_host_parity(tmp_path):
    """Validate upstream of Top falls back to host semantics: rows past
    the early stop are never validated (review regression)."""
    from csvplus_tpu import Like, Take, from_file

    p = tmp_path / "vt.csv"
    p.write_text("k\nok\nok\nok\nBAD\n")
    pred = Like({"k": "ok"})
    host = Take(from_file(str(p))).validate(pred).top(2).to_rows()
    dev = from_file(str(p)).on_device("cpu").validate(pred).top(2).to_rows()
    assert dev == host and len(dev) == 2  # host never reaches BAD


def test_symbolic_validate_sink_file_removed(tmp_path):
    """A failing validate through to_csv_file keeps the no-partial-output
    contract on both paths (csvplus.go:418-443)."""
    from csvplus_tpu import DataSourceError, Like, Take, from_file

    p = tmp_path / "vs.csv"
    p.write_text("k\nok\nBAD\nok\n")
    out = tmp_path / "out.csv"
    pred = Like({"k": "ok"})
    with pytest.raises(DataSourceError):
        from_file(str(p)).on_device("cpu").validate(pred).to_csv_file(str(out), "k")
    assert not out.exists()


def test_to_device_table_materializes_plan(tmp_path):
    """to_device_table runs the symbolic plan on device and returns the
    columnar result without decoding rows; decode parity with to_rows."""
    from csvplus_tpu import Like, Take, from_file

    p = tmp_path / "t.csv"
    p.write_text("id,name\n1,a\n2,b\n3,a\n4,c\n")
    src = from_file(str(p)).on_device("cpu").filter(Like({"name": "a"}))
    table = src.to_device_table()
    assert table.nrows == 2
    host = Take(from_file(str(p))).filter(Like({"name": "a"})).to_rows()
    assert table.to_rows() == host


def test_to_device_table_host_source_columnarizes():
    """A pure-host source (no plan) still materializes to a DeviceTable."""
    from csvplus_tpu import Row, take_rows

    rows = [Row({"a": "x"}), Row({"a": "y", "b": "z"})]
    table = take_rows(rows).to_device_table()
    assert table.nrows == 2
    assert table.to_rows() == rows


def test_to_device_table_opaque_callback_falls_back(tmp_path):
    """An opaque Python filter (no symbolic form) cannot lower; the
    materialization streams through the host path instead."""
    from csvplus_tpu import Take, from_file

    p = tmp_path / "t.csv"
    p.write_text("id\n1\n2\n3\n")
    src = from_file(str(p)).on_device("cpu").filter(lambda r: r["id"] != "2")
    table = src.to_device_table()
    assert [r["id"] for r in table.to_rows()] == ["1", "3"]


def test_to_device_table_validate_failure_fires():
    """A terminal symbolic validate failure fires on full materialization
    (parity: streaming the whole table would reach the bad row)."""
    import pytest

    from csvplus_tpu import DataSourceError, Like, Row, take_rows

    rows = [Row({"k": "ok"}), Row({"k": "BAD"})]
    src = take_rows(rows).on_device("cpu").validate(Like({"k": "ok"}))
    with pytest.raises(DataSourceError):
        src.to_device_table()


def test_device_table_sync_returns_self():
    """sync() forces completion with one scalar round trip and chains."""
    from csvplus_tpu.columnar.table import DeviceTable

    t = DeviceTable.from_pylists({"a": ["x", "y"], "b": ["1", "2"]})
    assert t.sync() is t
    empty = DeviceTable.from_pylists({})
    assert empty.sync() is empty


def test_device_spec_is_strict():
    """None is the default backend's first device; a named platform JAX
    cannot supply raises instead of quietly becoming the CPU."""
    import jax

    from csvplus_tpu.columnar.table import DeviceTable, default_device

    assert default_device(None) == jax.devices()[0]
    assert default_device("cpu").platform == "cpu"
    assert default_device(jax.devices()[3]) == jax.devices()[3]
    with pytest.raises(RuntimeError):
        default_device("tpu")
    with pytest.raises(RuntimeError):
        DeviceTable.from_pylists({"a": ["x"]}, device="tpu")


def test_on_device_tpu_raises_without_a_tpu(people_csv):
    from csvplus_tpu import FromFile, Take

    with pytest.raises(RuntimeError):
        FromFile(people_csv).OnDevice("tpu")
    with pytest.raises(RuntimeError):
        Take(FromFile(people_csv)).OnDevice("tpu")
    assert FromFile(people_csv).OnDevice().plan.table.nrows == 120


def test_device_parse_tier_gate(monkeypatch):
    """The ingest tier gate is a plain platform test: off on the CPU
    backend, and CSVPLUS_DEVICE_PARSE forces it either way."""
    from csvplus_tpu.columnar import ingest

    monkeypatch.delenv("CSVPLUS_DEVICE_PARSE", raising=False)
    assert not ingest._device_parse_enabled()  # conftest forces CPU
    monkeypatch.setenv("CSVPLUS_DEVICE_PARSE", "1")
    assert ingest._device_parse_enabled()
    monkeypatch.setenv("CSVPLUS_DEVICE_PARSE", "0")
    assert not ingest._device_parse_enabled()
