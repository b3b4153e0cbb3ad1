"""Public API surface: Go-name aliases, adapter contracts, error types.

The BASELINE configs exercise the reference names (Take, FromFile,
SelectColumns, Filter, Like, Map, ToCsvFile, UniqueIndexOn, IndexOn,
Find, Join, ResolveDuplicates) — pin that every one exists and behaves.
"""

import glob
import io
import os
import re

import pytest

import csvplus_tpu as csvplus
from csvplus_tpu import DataSourceError, Row, Take, TakeRows


def test_go_style_module_aliases():
    for name in [
        "Take", "TakeRows", "FromFile", "FromReader", "FromReadCloser",
        "LoadIndex", "Like", "All", "Any", "Not",
    ]:
        assert hasattr(csvplus, name), name


def test_go_style_method_aliases(people_csv):
    src = Take(csvplus.FromFile(people_csv))
    for name in [
        "Transform", "Filter", "Map", "Validate", "Top", "Drop",
        "TakeWhile", "DropWhile", "DropColumns", "SelectColumns",
        "IndexOn", "UniqueIndexOn", "Join", "Except",
        "ToCsv", "ToCsvFile", "ToJSON", "ToJSONFile", "ToRows",
    ]:
        assert hasattr(src, name), name
    idx = src.IndexOn("id")
    for name in ["Iterate", "Find", "SubIndex", "ResolveDuplicates", "WriteTo", "OnDevice"]:
        assert hasattr(idx, name), name
    row = Row({"a": "1"})
    for name in [
        "HasColumn", "SafeGetValue", "Header", "SelectExisting", "Select",
        "SelectValues", "Clone", "ValueAsInt", "ValueAsFloat64",
    ]:
        assert hasattr(row, name), name


def test_take_rejects_non_iterable_source():
    with pytest.raises(TypeError) as e:
        csvplus.take(42)
    assert "iterate" in str(e.value)


def test_take_is_idempotent_on_datasource(people_csv):
    src = Take(csvplus.FromFile(people_csv))
    assert csvplus.take(src) is src


def test_from_read_closer_closes():
    class S(io.StringIO):
        closed_flag = False

        def close(self):
            S.closed_flag = True
            super().close()

    s = S("a,b\n1,2\n")
    rows = Take(csvplus.from_read_closer(s)).to_rows()
    assert rows == [Row({"a": "1", "b": "2"})]
    assert S.closed_flag


def test_from_reader_does_not_close():
    s = io.StringIO("a,b\n1,2\n")
    Take(csvplus.from_reader(s)).to_rows()
    assert not s.closed


def test_from_reader_accepts_str_and_bytes():
    assert Take(csvplus.from_reader("a\nx\n")).to_rows() == [Row({"a": "x"})]
    assert Take(csvplus.from_reader(b"a\nx\n")).to_rows() == [Row({"a": "x"})]


def test_data_source_error_attributes():
    try:
        Take(csvplus.from_reader("a,b\n1\n")).to_rows()
    except DataSourceError as e:
        assert e.line == 2
        assert "wrong number of fields" in str(e.err)
    else:
        pytest.fail("expected DataSourceError")


def test_num_fields_applies_to_header_row():
    with pytest.raises(DataSourceError) as e:
        Take(csvplus.from_reader("a,b\n1,2\n").num_fields(3)).to_rows()
    assert e.value.line == 1


def test_row_is_a_dict():
    r = Row({"a": "1"})
    assert isinstance(r, dict)
    assert {**r, "b": "2"} == {"a": "1", "b": "2"}
    # plain dicts work as rows in sources
    assert TakeRows([{"a": "1"}]).to_rows() == [Row({"a": "1"})]


def test_predicates_accept_plain_dicts_and_rows():
    like = csvplus.Like({"a": "1"})
    assert like(Row({"a": "1"})) and like({"a": "1"})
    assert not like({"a": "2"}) and not like({})


def test_validate_passthrough_alias(people_csv):
    out = Take(csvplus.FromFile(people_csv)).Validate(lambda r: None).ToRows()
    assert len(out) == 120


def test_concurrent_pull_iteration(people_csv):
    """Two pythonic iterations of the same source can interleave without
    interference (each __iter__ spawns its own producer)."""
    import itertools

    src = Take(csvplus.FromFile(people_csv))
    a, b = iter(src), iter(src)
    rows_a, rows_b = [], []
    for ra, rb in itertools.zip_longest(a, b):
        rows_a.append(ra)
        rows_b.append(rb)
    assert rows_a == rows_b and len(rows_a) == 120


def test_pull_iteration_propagates_errors():
    src = Take(csvplus.from_reader("a,b\n1\n"))
    with pytest.raises(DataSourceError) as e:
        list(src)
    assert "wrong number of fields" in str(e.value)


def test_stream_backed_on_device():
    """OnDevice works for non-file readers (no native scanner path),
    via the Python ingest fallback.  (The in-memory-rows
    DataSource.on_device path is pinned in test_device.py.)"""
    rows = Take(
        csvplus.from_reader(io.StringIO("a,b\nx,1\ny,2\n"))
    ).to_rows()
    dev = csvplus.from_reader("a,b\nx,1\ny,2\n").on_device("cpu")
    assert dev.plan is not None
    assert dev.to_rows() == rows


# -- the checkout's top level ------------------------------------------------

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_top_level_holds_one_harness_and_no_records():
    """Speed is measured by `benchmark/` on the chip and recorded in
    PERF_LEDGER.jsonl: no second bench script, floor or per-round record
    grows back beside it.  Lists the directory (a copy may carry no
    `.git`)."""
    top = os.listdir(REPO)
    scripts = {n for n in top if n.endswith(".py")}
    assert scripts == {"chip_smoke.py", "chaos.py", "__graft_entry__.py"}
    records = [
        n for n in top
        if re.search(r"_r[0-9]+\.json$", n) or n.endswith("_floor.json")
    ]
    assert records == []


def test_documents_name_only_targets_and_scripts_that_exist():
    """Every `make <target>` and every `python <script>.py` that
    README.md or docs/*.md names exists."""
    with open(os.path.join(REPO, "Makefile")) as f:
        targets = set(re.findall(r"^([a-z][a-z-]*):", f.read(), re.M))
    docs = [os.path.join(REPO, "README.md")] + sorted(
        glob.glob(os.path.join(REPO, "docs", "*.md"))
    )
    named_targets, named_scripts = set(), set()
    for path in docs:
        with open(path) as f:
            text = f.read()
        # a code span `make x`, or a command line of a code block
        named_targets.update(re.findall(r"`make\s+([a-z][a-z-]*)`", text))
        named_targets.update(re.findall(r"^\s*make\s+([a-z][a-z-]*)\s*$", text, re.M))
        named_scripts.update(re.findall(r"python3? ((?:[\w.-]+/)*[\w-]+\.py)", text))
    assert {"check", "chaos", "lint"} <= named_targets  # the patterns still bite
    assert named_targets - targets == set()
    assert "chip_smoke.py" in named_scripts and "benchmark/run.py" in named_scripts
    missing = {s for s in named_scripts if not os.path.exists(os.path.join(REPO, s))}
    assert missing == set()
