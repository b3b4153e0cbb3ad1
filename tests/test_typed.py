"""Typed value lanes (columnar/typed.IntColumn): differential tests.

VERDICT r4 next #2: columns whose cells all carry the affix-int32 form
(constant prefix + canonical decimal suffix) skip dictionary encoding
and live as int32 value lanes.  Everything here checks the typed path
against the host executor (and against the same pipeline with
CSVPLUS_TYPED_LANES=0), because the whole design leans on demotion
being bitwise-equivalent to a never-typed run.
"""

import os

import numpy as np
import pytest

from csvplus_tpu import FromFile, Like, Take
from csvplus_tpu.columnar.typed import (
    IntColumn,
    format_affix,
    parse_affix_dictionary,
)

native = pytest.importorskip("csvplus_tpu.native.scanner")


@pytest.fixture(autouse=True)
def _stream_small_files(monkeypatch):
    # typed lanes live in the streamed tier; make small test files stream
    monkeypatch.setenv("CSVPLUS_STREAM_MIN_BYTES", "1")


def _write(tmp_path, text, name="t.csv"):
    p = tmp_path / name
    p.write_text(text)
    return str(p)


def _dicts(rows):
    return [dict(r) for r in rows]


# ---- native parse/format round trip --------------------------------------


def test_pack_roundtrip_shapes():
    cases = [
        ([b"0", b"123", b"-45", b"2147483647"], b"", [0, 123, -45, 2147483647]),
        ([b"o0", b"o123", b"o99999999"], b"o", [0, 123, 99999999]),
        ([b"o007", b"o008"], b"o00", [7, 8]),  # leading zeros join prefix
        ([b"01", b"02"], b"0", [1, 2]),  # non-canonical lead -> prefix
        ([b"-0"], b"-", [0]),  # "-0" = prefix "-" + 0
    ]
    for cells, want_prefix, want_vals in cases:
        data = b"".join(cells)
        starts = np.cumsum([0] + [len(c) for c in cells[:-1]]).astype(np.int64)
        lens = np.array([len(c) for c in cells], np.int32)
        res = native.pack_int32_native(
            np.frombuffer(data, np.uint8), starts, lens, None
        )
        assert res is not None, cells
        prefix, vals = res
        assert prefix == want_prefix
        assert vals.tolist() == want_vals
        # format_affix is the exact inverse
        assert format_affix(prefix, vals).tolist() == cells


def test_pack_rejections():
    for cells in [[b"o1", b"x1"], [b""], [b"abc"], [b"o1", b""]]:
        data = b"".join(cells)
        starts = np.cumsum([0] + [len(c) for c in cells[:-1]]).astype(np.int64)
        lens = np.array([len(c) for c in cells], np.int32)
        assert (
            native.pack_int32_native(
                np.frombuffer(data, np.uint8), starts, lens, None
            )
            is None
        )


def test_parse_affix_dictionary_matches_equality_term():
    d = np.array(
        [b"c0", b"c1", b"c10", b"c007", b"x1", b"c-3", b"c2147483648"],
        dtype="S12",
    )
    cand, vals = parse_affix_dictionary(np.sort(d), b"c")
    got = {int(v) for v in vals}
    # canonical "c"-prefixed int32 entries only: c0, c1, c10
    assert got == {0, 1, 10}
    assert len(cand) == 3


# ---- ingest kinds + decode parity ----------------------------------------


def test_typed_ingest_and_decode(tmp_path):
    path = _write(
        tmp_path,
        "order_id,cust_id,qty,name\n"
        + "".join(f"o{i},c{i % 7},{i % 100},txt{i % 3}x\n" for i in range(500)),
    )
    t = FromFile(path).on_device().plan.table
    assert isinstance(t.columns["order_id"], IntColumn)
    assert t.columns["order_id"].prefix == b"o"
    assert isinstance(t.columns["qty"], IntColumn)
    assert t.columns["qty"].prefix == b""
    assert not isinstance(t.columns["name"], IntColumn)
    assert _dicts(t.to_rows()) == _dicts(Take(FromFile(path)).to_rows())


def test_typed_disabled_by_env(tmp_path, monkeypatch):
    monkeypatch.setenv("CSVPLUS_TYPED_LANES", "0")
    path = _write(tmp_path, "a\n" + "".join(f"{i}\n" for i in range(50)))
    t = FromFile(path).on_device().plan.table
    assert not isinstance(t.columns["a"], IntColumn)


def test_mid_stream_demotion_bitwise_equal(tmp_path, monkeypatch):
    """A column that stops conforming after several chunks re-encodes its
    accumulated typed chunks; the result must equal the never-typed run
    exactly."""
    monkeypatch.setenv("CSVPLUS_STREAM_CHUNK_BYTES", "256")
    body = "".join(f"v{i},{i % 5}\n" for i in range(300))
    body += "NOT_A_NUMBER,0\n"  # v-column demotes here
    body += "".join(f"v{i},{i % 5}\n" for i in range(300, 350))
    path = _write(tmp_path, "v,q\n" + body)
    rows_typed = FromFile(path).on_device().to_rows()
    monkeypatch.setenv("CSVPLUS_TYPED_LANES", "0")
    rows_plain = FromFile(path).on_device().to_rows()
    assert _dicts(rows_typed) == _dicts(rows_plain)
    assert _dicts(rows_typed) == _dicts(Take(FromFile(path)).to_rows())


def test_prefix_drift_demotes(tmp_path, monkeypatch):
    monkeypatch.setenv("CSVPLUS_STREAM_CHUNK_BYTES", "128")
    rows = [f"a{i}" for i in range(100)] + ["b1"] + [f"a{i}" for i in range(20)]
    path = _write(tmp_path, "k\n" + "".join(v + "\n" for v in rows))
    t = FromFile(path).on_device().plan.table
    assert not isinstance(t.columns["k"], IntColumn)
    got = [r["k"] for r in t.to_rows()]
    assert got == rows


# ---- pipelines -----------------------------------------------------------


@pytest.fixture
def joined_files(tmp_path):
    rng = np.random.default_rng(11)
    opath = _write(
        tmp_path,
        "order_id,cust_id,prod_id,qty\n"
        + "".join(
            f"o{i},c{int(rng.integers(0, 40))},p{int(rng.integers(0, 6))},"
            f"{int(rng.integers(1, 100))}\n"
            for i in range(2000)
        ),
        "orders.csv",
    )
    cpath = _write(
        tmp_path,
        "id,name\n" + "".join(f"c{i},name{i % 9}\n" for i in range(40)),
        "cust.csv",
    )
    ppath = _write(
        tmp_path,
        "prod_id,product,price\n"
        + "".join(f"p{i},prod{i},{i}.99\n" for i in range(6)),
        "prod.csv",
    )
    return opath, cpath, ppath


def test_typed_threeway_join_parity(joined_files):
    opath, cpath, ppath = joined_files
    cust_h = Take(FromFile(cpath)).unique_index_on("id")
    prod_h = Take(FromFile(ppath)).unique_index_on("prod_id")
    host = Take(FromFile(opath)).join(cust_h, "cust_id").join(prod_h).to_rows()
    orders = FromFile(opath).on_device()
    assert isinstance(orders.plan.table.columns["cust_id"], IntColumn)
    cust_d = FromFile(cpath).on_device().unique_index_on("id")
    prod_d = FromFile(ppath).on_device().unique_index_on("prod_id")
    dev = orders.join(cust_d, "cust_id").join(prod_d).to_rows()
    assert _dicts(host) == _dicts(dev)


def test_typed_join_result_keeps_payload_typed(joined_files):
    """The join must NOT demote typed payload columns: order_id/qty ride
    the gathers as value lanes."""
    opath, cpath, ppath = joined_files
    cust_d = FromFile(cpath).on_device().unique_index_on("id")
    out = (
        FromFile(opath).on_device().join(cust_d, "cust_id").to_device_table()
    )
    assert isinstance(out.columns["order_id"], IntColumn)
    assert out.columns["order_id"]._demoted is None  # never demoted
    assert isinstance(out.columns["qty"], IntColumn)


def test_typed_checksums_match_host(joined_files):
    from csvplus_tpu.utils.checksum import (
        checksum_device_table,
        checksum_host_rows,
    )

    opath, cpath, ppath = joined_files
    t = FromFile(opath).on_device().to_device_table()
    host = Take(FromFile(opath)).to_rows()
    cols = sorted(t.columns)
    assert checksum_device_table(t, cols, positional=True) == checksum_host_rows(
        host, cols, positional=True
    )


def test_typed_filters(joined_files):
    opath, _, _ = joined_files
    for col, vals in [
        ("qty", ["50", "5", "007", "abc", ""]),
        ("cust_id", ["c7", "c07", "zz", "c", "7"]),
    ]:
        for v in vals:
            a = Take(FromFile(opath)).filter(Like({col: v})).to_rows()
            b = FromFile(opath).on_device().filter(Like({col: v})).to_rows()
            assert _dicts(a) == _dicts(b), (col, v)


def test_typed_sinks_byte_parity(tmp_path, joined_files):
    opath, _, _ = joined_files
    h, d = str(tmp_path / "h.csv"), str(tmp_path / "d.csv")
    Take(FromFile(opath)).to_csv_file(h, "order_id", "cust_id", "qty")
    FromFile(opath).on_device().to_csv_file(d, "order_id", "cust_id", "qty")
    assert open(h, "rb").read() == open(d, "rb").read()
    hj, dj = str(tmp_path / "h.json"), str(tmp_path / "d.json")
    Take(FromFile(opath)).to_json_file(hj)
    FromFile(opath).on_device().to_json_file(dj)
    assert open(hj, "rb").read() == open(dj, "rb").read()


def test_typed_index_sort_find_dedup(joined_files):
    opath, _, _ = joined_files
    idx_h = Take(FromFile(opath)).index_on("cust_id", "prod_id")
    idx_d = FromFile(opath).on_device().index_on("cust_id", "prod_id")
    assert _dicts(Take(idx_h).to_rows()) == _dicts(Take(idx_d).to_rows())
    fa = idx_h.find("c7").to_rows()
    fb = idx_d.find("c7").to_rows()
    assert _dicts(fa) == _dicts(fb) and len(fb) > 0
    idx_h.resolve_duplicates("first")
    idx_d.resolve_duplicates("first")
    assert _dicts(Take(idx_h).to_rows()) == _dicts(Take(idx_d).to_rows())


def test_typed_sharding_pads_never_alias_prefix_zero(tmp_path):
    """Review r5 regression: a 0-valued pad would alias a real 'c0'/'p0'
    build key and fabricate phantom rows through the flagship join's
    padded stream.  Pads must translate to -2 like string pads."""
    import jax

    from csvplus_tpu.columnar.ingest import source_from_table
    from csvplus_tpu.columnar.table import DeviceTable
    from csvplus_tpu.parallel.mesh import make_mesh

    if len(jax.devices()) < 2:
        pytest.skip("needs a multi-device mesh")
    # 3 rows over the mesh: pads are unavoidable
    path = _write(
        tmp_path,
        "order_id,cust_id,prod_id\no1,c1,p1\no2,c0,p0\no3,c2,p1\n",
    )
    orders = FromFile(path).on_device().plan.table
    assert isinstance(orders.columns["cust_id"], IntColumn)
    sharded = orders.with_sharding(make_mesh())
    cust = DeviceTable.from_pylists(
        {"id": ["c0", "c1", "c2"], "name": ["n0", "n1", "n2"]}
    )
    prod = DeviceTable.from_pylists({"prod_id": ["p0", "p1"], "product": ["a", "b"]})
    out = (
        source_from_table(sharded)
        .join(source_from_table(cust).unique_index_on("id"), "cust_id")
        .join(source_from_table(prod).unique_index_on("prod_id"), "prod_id")
        .to_rows()
    )
    assert len(out) == 3, f"phantom pad rows joined: {out}"
    got = sorted(r["order_id"] for r in out)
    assert got == ["o1", "o2", "o3"]
    # demotion of a padded typed column must not invent a 'c<PAD>' entry
    col = sharded.columns["cust_id"]
    demoted = col._demote()
    assert demoted.dictionary.tolist() == [b"c0", b"c1", b"c2"]


def test_typed_sharded_roundtrip(joined_files):
    import jax

    from csvplus_tpu.parallel.mesh import make_mesh

    if len(jax.devices()) < 2:
        pytest.skip("needs a multi-device mesh")
    opath, cpath, _ = joined_files
    t = FromFile(opath).on_device().plan.table
    ts = t.with_sharding(make_mesh())
    assert isinstance(ts.columns["order_id"], IntColumn)
    assert _dicts(ts.to_rows()) == _dicts(t.to_rows())


def test_quoted_typed_values_and_escaping_prefix(tmp_path):
    """A quoted prefix containing the delimiter still types (content is
    unquoted by the parser) and the CSV sink re-quotes it correctly."""
    rows = "".join(f'"a,{i}",{i}\n' for i in range(60))
    path = _write(tmp_path, "k,q\n" + rows)
    t = FromFile(path).on_device().plan.table
    assert isinstance(t.columns["k"], IntColumn)
    assert t.columns["k"].prefix == b"a,"
    h, d = str(tmp_path / "h.csv"), str(tmp_path / "d.csv")
    Take(FromFile(path)).to_csv_file(h, "k", "q")
    FromFile(path).on_device().to_csv_file(d, "k", "q")
    assert open(h, "rb").read() == open(d, "rb").read()


def test_fused_path_rejects_delimiter_bearing_prefix(tmp_path, monkeypatch):
    """Review r5 regression: a typed prefix containing the delimiter
    (established via quoted cells) must keep the column on the tokenized
    path — the fused parser's prefix memcmp would otherwise read across
    field boundaries, misparse values, and swallow arity errors."""
    monkeypatch.setenv("CSVPLUS_STREAM_CHUNK_BYTES", "96")
    # chunk 1: quoted cells establish prefix b'a,b' for column A
    body = '"a,b1",7\n"a,b2",8\n"a,b3",9\n"a,b4",1\n"a,b5",2\n"a,b6",3\n'
    # later chunks are quote-free; a 3-field record must still ERROR
    body += '"a,b7",4\n' * 6
    body += "a,b8,5\n"  # wrong field count under the locked arity of 2
    path = _write(tmp_path, "A,B\n" + body)
    with pytest.raises(Exception, match="wrong number of fields"):
        FromFile(path).on_device().to_rows()
    # host oracle agrees
    with pytest.raises(Exception, match="wrong number of fields"):
        Take(FromFile(path)).to_rows()
    # and a well-formed file of the same shape decodes identically
    good = "A,B\n" + '"a,b1",7\n' * 20
    gpath = _write(tmp_path, good, "good.csv")
    assert _dicts(FromFile(gpath).on_device().to_rows()) == _dicts(
        Take(FromFile(gpath)).to_rows()
    )


def test_typed_except_and_select(joined_files):
    opath, cpath, _ = joined_files
    small = Take(FromFile(cpath)).unique_index_on("id")
    a = Take(FromFile(opath)).except_(small, "cust_id").to_rows()
    b = FromFile(opath).on_device().except_(small, "cust_id").to_rows()
    assert _dicts(a) == _dicts(b)
    a = Take(FromFile(opath)).select_columns("order_id", "qty").to_rows()
    b = FromFile(opath).on_device().select_columns("order_id", "qty").to_rows()
    assert _dicts(a) == _dicts(b)


from hypo_compat import given, settings, st

_PREFIXES = ["", "o", "c", "id-", "a,b", "00", "-", "é", " p"]
# poisons exercise DISTINCT demotion branches: non-digit bail, int32
# overflow, one-past-min (the PAD_VALUE sentinel's neighborhood), and a
# digits-too-long bail
_POISONS = ["ZZZ", "2147483648", "-2147483648", "99999999999"]


@settings(deadline=None)  # max_examples comes from the conftest profile
@given(
    st.lists(
        st.tuples(
            st.sampled_from(_PREFIXES),
            st.lists(
                st.integers(min_value=-(2**31) + 1, max_value=2**31 - 1),
                min_size=1,
                max_size=40,
            ),
            st.sampled_from([None] + _POISONS),  # mid-column demotion
        ),
        min_size=1,
        max_size=4,
    ),
    st.sampled_from([64, 256, 4096]),
)
def test_typed_hypothesis_differential(tmp_path_factory, cols, chunk):
    """Random affix schemas (prefixes incl. delimiter/space/unicode edge
    cases, full int32 range, optional mid-column demotion via distinct
    non-conforming shapes) must decode identically to the host executor
    at any chunk size."""
    rows = max(len(v) for _, v, _ in cols)
    names = [f"c{i}" for i in range(len(cols))]
    lines = []
    for r in range(rows):
        cells = []
        for prefix, vals, poison in cols:
            v = vals[r % len(vals)]
            cell = f"{prefix}{v}"
            if poison is not None and r == rows // 2:
                cell = poison  # breaks typing mid-file
            if any(ch in cell for ch in ',"\n\r') or cell.startswith(" "):
                cell = '"' + cell.replace('"', '""') + '"'
            cells.append(cell)
        lines.append(",".join(cells))
    text = ",".join(names) + "\n" + "\n".join(lines) + "\n"
    p = tmp_path_factory.mktemp("aff") / "t.csv"
    p.write_bytes(text.encode("utf-8"))
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("CSVPLUS_STREAM_MIN_BYTES", "1")
        mp.setenv("CSVPLUS_STREAM_CHUNK_BYTES", str(chunk))
        host = Take(FromFile(str(p))).to_rows()
        dev = FromFile(str(p)).on_device().to_rows()
        assert _dicts(host) == _dicts(dev)


def test_typed_persistence_roundtrip(tmp_path, joined_files):
    from csvplus_tpu import load_index

    opath, _, _ = joined_files
    idx = FromFile(opath).on_device().index_on("cust_id")
    p = str(tmp_path / "idx.bin")
    idx.write_to(p)
    loaded = load_index(p)
    assert _dicts(Take(loaded).to_rows()) == _dicts(Take(idx).to_rows())
