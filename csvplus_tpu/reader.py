"""The fluent CSV Reader builder.

Reference: ``Reader`` csvplus.go:922-1206.  Construct via
:func:`from_file` / :func:`from_reader` / :func:`from_read_closer`,
configure with chained calls, then lift into a pipeline with
:func:`csvplus_tpu.take` (or iterate directly).

All three header policies are supported (csvplus.go:995-1056):

* first-row auto header (default),
* ``expect_header`` — verified against the first row; a negative index
  means "find the column by name",
* ``assume_header`` — for headerless files,
* ``select_columns`` — at-source projection via name search in row one,

as are the three field-count policies ``num_fields`` / ``num_fields_auto``
/ ``num_fields_any`` (right-padding under *any*, csvplus.go:1058-1076,
1121-1124).  Errors carry 1-based record numbers, and messages are pinned
to the reference's (csvplus_test.go:808-909).
"""

from __future__ import annotations

import io
from typing import Callable, Dict, Iterator, List, Optional, TextIO, Tuple

from .csvio import ERR_FIELD_COUNT, CsvParseError, parse_records
from .errors import DataSourceError, StopPipeline, map_error
from .row import Row
from .source import RowFunc

# a maker opens the input and returns (stream, closer) — csvplus.go:933
Maker = Callable[[], Tuple[TextIO, Callable[[], None]]]


class Reader:
    """Iterable CSV reader; ``iterate`` may be invoked once per instance
    for stream-backed readers, any number of times for file-backed ones."""

    def __init__(self, source: Maker):
        self._source = source
        self._delimiter = ","
        self._comment: Optional[str] = None
        self._num_fields = 0  # 0 = auto (match first row), >0 exact, <0 any
        self._lazy_quotes = False
        self._trim_leading_space = False
        self._header: Optional[Dict[str, int]] = None
        self._header_from_first_row = True

    # -- fluent configuration (csvplus.go:970-1076) ------------------------

    def delimiter(self, c: str) -> "Reader":
        """Set the field delimiter character (csvplus.go:971-974)."""
        self._delimiter = c
        return self

    def comment_char(self, c: str) -> "Reader":
        """Set the character that starts a comment line (csvplus.go:977-980)."""
        self._comment = c
        return self

    def lazy_quotes(self) -> "Reader":
        """Permit stray quotes, as Go's LazyQuotes (csvplus.go:984-987)."""
        self._lazy_quotes = True
        return self

    def trim_leading_space(self) -> "Reader":
        """Ignore leading white space in fields (csvplus.go:990-993)."""
        self._trim_leading_space = True
        return self

    def assume_header(self, spec: Dict[str, int]) -> "Reader":
        """Provide column names for headerless input: name -> column index
        (csvplus.go:998-1012)."""
        if not spec:
            raise ValueError("Empty header spec")
        for name, col in spec.items():
            if col < 0:
                raise ValueError("header spec: negative index for column " + name)
        self._header = dict(spec)
        self._header_from_first_row = False
        return self

    def expect_header(self, spec: Dict[str, int]) -> "Reader":
        """Declare the expected header, verified against the first row; a
        negative index means the position is found by name
        (csvplus.go:1020-1033)."""
        if not spec:
            raise ValueError("empty header spec")
        self._header = dict(spec)
        self._header_from_first_row = True
        return self

    def select_columns(self, *names: str) -> "Reader":
        """At-source projection: read only the named columns, located by
        searching the first row (csvplus.go:1039-1056)."""
        if not names:
            raise ValueError("empty header spec")
        header: Dict[str, int] = {}
        for name in names:
            if name in header:
                raise ValueError("header spec: duplicate column name: " + name)
            header[name] = -1
        self._header = header
        self._header_from_first_row = True
        return self

    def num_fields(self, n: int) -> "Reader":
        """Exact expected field count per record (csvplus.go:1060-1063)."""
        self._num_fields = n
        return self

    def num_fields_auto(self) -> "Reader":
        """Field count must match the first record (csvplus.go:1067-1069)."""
        return self.num_fields(0)

    def num_fields_any(self) -> "Reader":
        """Records may have any number of fields; short records are padded
        with empty fields (csvplus.go:1074-1076)."""
        return self.num_fields(-1)

    # -- iteration (csvplus.go:1078-1146) ----------------------------------

    def iterate(self, fn: RowFunc) -> None:
        """Read the input record by record, convert each to a Row per the
        configured header policy, and call *fn* (csvplus.go:1078-1146).
        Errors carry 1-based record numbers."""
        stream, closer = self._open(line_no=1)
        try:
            records, header, line_no, expected_fields = self._start(stream)

            # hot loop
            for rec in self._record_iter(records, line_no):
                expected_fields = self._check_count(rec, expected_fields, line_no)
                row = Row()
                for name, index in header.items():
                    if index < len(rec):
                        row[name] = rec[index]
                    elif self._num_fields < 0:  # padding allowed
                        row[name] = ""
                    else:
                        raise DataSourceError(
                            line_no, f'column not found: "{name}" ({index})'
                        )
                try:
                    fn(row)
                except StopPipeline:
                    return
                except DataSourceError:
                    raise
                except Exception as e:
                    raise map_error(e, line_no) from e
                line_no += 1
        finally:
            closer()

    # Go-style alias so Take(reader) works (csvplus.go:252-256)
    Iterate = iterate

    # -- helpers -----------------------------------------------------------

    def _start(self, stream):
        """Shared iteration preamble: build the record parser and resolve
        the header per the configured policy (csvplus.go:1090-1112).

        Returns (records, header, next_line_no, expected_fields); both
        :meth:`iterate` and :meth:`read_columns` go through here so the
        streaming and columnar paths can never diverge on policy.
        """
        records = parse_records(
            stream,
            delimiter=self._delimiter,
            comment=self._comment,
            lazy_quotes=self._lazy_quotes,
            trim_leading_space=self._trim_leading_space,
        )
        line_no = 1
        expected_fields = self._num_fields
        if self._header_from_first_row:
            first = self._read_record(records, line_no)
            if first is None:
                raise DataSourceError(line_no, "EOF")
            expected_fields = self._check_count(first, expected_fields, line_no)
            header = self._make_header(first, line_no)
            line_no += 1
        else:
            header = dict(self._header or {})
        return records, header, line_no, expected_fields

    def _open(self, line_no: int):
        try:
            return self._source()
        except OSError as e:
            # Go wraps *os.PathError as "op: message" (csvplus.go:1216-1220)
            raise DataSourceError(line_no, f"open: {e.strerror or e}") from e

    def _record_iter(self, records: Iterator[List[str]], start_line: int):
        """Wrap the raw record iterator, mapping parse errors to
        row-numbered DataSourceErrors."""
        line_no = start_line
        while True:
            try:
                rec = next(records)
            except StopIteration:
                return
            except CsvParseError as e:
                raise DataSourceError(line_no, e) from e
            yield rec
            line_no += 1

    def _read_record(self, records, line_no: int) -> Optional[List[str]]:
        try:
            return next(records)
        except StopIteration:
            return None
        except CsvParseError as e:
            raise DataSourceError(line_no, e) from e

    def _check_count(self, rec: List[str], expected: int, line_no: int) -> int:
        """Go csv.Reader FieldsPerRecord semantics (docs of csvplus.go:1058-1076)."""
        if self._num_fields < 0:
            return expected
        if expected == 0:
            return len(rec)  # first record sets the expectation
        if len(rec) != expected:
            raise DataSourceError(line_no, ERR_FIELD_COUNT)
        return expected

    def _make_header(self, line: List[str], line_no: int) -> Dict[str, int]:
        """Build the header map from the first row (csvplus.go:1149-1206)."""
        if not line:
            raise DataSourceError(line_no, "empty header")

        if not self._header:
            return {name: i for i, name in enumerate(line)}

        header: Dict[str, int] = {}
        for i, name in enumerate(line):
            if name in self._header:
                index = self._header[name]
                if index == -1 or index == i:
                    header[name] = i
                else:
                    raise DataSourceError(
                        line_no,
                        f'misplaced column "{name}": expected at pos. {index}, '
                        f"but found at pos. {i}",
                    )

        if len(header) < len(self._header):
            missing = [n for n in self._header if n not in header]
            if len(missing) > 1:
                raise DataSourceError(
                    line_no, "columns not found: " + ", ".join(missing)
                )
            raise DataSourceError(line_no, "column not found: " + missing[0])

        return header

    def read_columns(self):
        """Parse the whole input into columns (name -> list of values),
        applying the same header/field-count policies and raising the same
        row-numbered errors as :meth:`iterate`.

        This is the columnar ingest entry: no per-row dicts are built, so
        it is the fast path feeding
        :func:`csvplus_tpu.columnar.ingest.reader_to_device`.
        """
        stream, closer = self._open(line_no=1)
        try:
            records, header, line_no, expected_fields = self._start(stream)

            names = list(header)
            idxs = [header[n] for n in names]
            data: Dict[str, List[str]] = {n: [] for n in names}
            for rec in self._record_iter(records, line_no):
                expected_fields = self._check_count(rec, expected_fields, line_no)
                nrec = len(rec)
                for n, ix in zip(names, idxs):
                    if ix < nrec:
                        data[n].append(rec[ix])
                    elif self._num_fields < 0:  # padding allowed
                        data[n].append("")
                    else:
                        raise DataSourceError(
                            line_no, f'column not found: "{n}" ({ix})'
                        )
                line_no += 1
            return names, data
        finally:
            closer()

    # -- device ingestion hook (M2) ----------------------------------------

    def on_device(self, device=None, shards=None, mesh=None, **opts):
        """Parse this CSV into an HBM-resident columnar DeviceTable and
        return a plan-capable DataSource over it.

        This is the rebuild's ``FromFile(...).OnDevice("tpu")`` entry
        point from BASELINE.json's north star.  *device* is a platform
        name or jax.Device; ``None`` takes the default backend's first
        device, and a named platform JAX cannot supply raises.
        ``shards=N`` lays the columns row-sharded over an N-device mesh
        (BASELINE config 5).

        NOTE: the file is ingested as a SNAPSHOT at call time; later
        file modifications are not observed.  The host path re-opens the
        file on every iteration (reference semantics, csvplus.go:950-959)
        and does observe them.
        """
        from .columnar.ingest import reader_to_device

        # host-path parity for file errors ("row 1: open: ...", the
        # reference's mapError of path errors, csvplus.go:1209-1227):
        # probe-open with the host's own wrapper BEFORE ingest, so only
        # the open step is mapped — a mid-ingest I/O error propagates
        # as itself rather than masquerading as an open failure
        if getattr(self, "_path", None) is not None:
            # path sources only: never consume or close a caller-supplied
            # stream (FromReader/FromReadCloser)
            _stream, closer = self._open(line_no=1)
            closer()
        return reader_to_device(self, device=device, shards=shards, mesh=mesh, **opts)

    # Go-style aliases
    Delimiter = delimiter
    CommentChar = comment_char
    LazyQuotes = lazy_quotes
    TrimLeadingSpace = trim_leading_space
    AssumeHeader = assume_header
    ExpectHeader = expect_header
    SelectColumns = select_columns
    NumFields = num_fields
    NumFieldsAuto = num_fields_auto
    NumFieldsAny = num_fields_any
    OnDevice = on_device


def from_file(name: str) -> Reader:
    """Reader bound to the named file (csvplus.go:950-960)."""

    def maker():
        f = open(name, "r", encoding="utf-8", newline="")
        return f, f.close

    r = Reader(maker)
    r._path = name  # device ingest fast path re-opens by name
    return r


def from_reader(stream) -> Reader:
    """Reader over an open text stream; the stream is not closed
    (csvplus.go:936-940)."""

    def maker():
        s = stream
        if isinstance(s, (bytes, bytearray)):
            s = io.StringIO(s.decode("utf-8"))
        elif isinstance(s, str):
            s = io.StringIO(s)
        return s, (lambda: None)

    return Reader(maker)


def from_read_closer(stream) -> Reader:
    """Reader over an open stream which is closed after iteration
    (csvplus.go:943-947)."""

    def maker():
        return stream, stream.close

    return Reader(maker)
