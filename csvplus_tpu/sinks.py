"""Eager sinks: the terminals that drive a lazy chain.

Reference: ``ToCsv``/``ToCsvFile`` csvplus.go:376-415, ``ToJSON``/
``ToJSONFile`` csvplus.go:445-480, ``ToRows`` csvplus.go:483-490, plus the
atomic ``writeFile`` helper (csvplus.go:418-443): on any error — including
an exception unwinding through the sink — the partially-written file is
closed and removed, so sinks never leave partial outputs behind.

A device-planned source executes its fused plan inside the ``src(fn)``
call itself (its driver is :func:`csvplus_tpu.columnar.exec.plan_runner`),
so sinks are agnostic: output bytes and error wrapping are identical on
both paths.
"""

from __future__ import annotations

import os
from typing import IO, List

from .csvio import write_record
from .row import Row
from .utils.gojson import go_json_object
from .utils.observe import telemetry


def _device_table(src):
    """The executed table of a device-planned source (memoized: a prefix
    never runs twice), None for a host source."""
    if getattr(src, "plan", None) is None:
        return None
    from .columnar.exec import device_table_for

    return device_table_for(src)


def _position(out):
    """Where *out* stands — bytes in a file, characters in a StringIO —
    or None for a stream that cannot say (a pipe)."""
    try:
        return out.tell()
    except (AttributeError, OSError, ValueError):
        return None


def _note_written(stage: dict, rows: int, out, start) -> None:
    """A sink stage's extras: rows and bytes it wrote."""
    stage["rows_out"] = rows
    end = _position(out)
    if start is not None and end is not None:
        stage["bytes"] = end - start


def to_csv(src, out: IO[str], *columns: str) -> None:
    """Write selected columns in canonical CSV form: header line first,
    fixed arity (csvplus.go:379-406).

    Device-planned sources encode the whole body with vectorized numpy
    string ops (byte-identical to the streaming writer); anything that
    needs per-row error semantics streams row by row.
    """
    if not columns:
        raise ValueError("empty column list in ToCsv() function")

    write_record(out, list(columns))

    # the plan runs here, before the stage: ``sink:csv`` times the write
    # loop (for a host source that loop also pulls the lazy chain)
    table = _device_table(src)
    with telemetry.stage("sink:csv", table.nrows if table is not None else 0) as stage:
        start = _position(out)
        if table is not None:
            from .columnar.csvenc import encode_csv_body

            body = encode_csv_body(table, columns)
            if body is not None:
                out.write(body)
            else:
                # stream the already-computed table for exact per-row
                # missing-column errors / partial output
                from .source import iterate

                iterate(
                    table.to_rows(),
                    lambda row: write_record(out, row.select_values(*columns)),
                    clone=False,
                )
            _note_written(stage, table.nrows, out, start)
            return

        rows = 0

        def fn(row: Row) -> None:
            nonlocal rows
            rows += 1
            write_record(out, row.select_values(*columns))

        src(fn)
        _note_written(stage, rows, out, start)


def to_csv_file(src, name: str, *columns: str) -> None:
    """CSV sink to a named file with no-partial-output guarantee
    (csvplus.go:411-415)."""
    _write_file(name, lambda f: to_csv(src, f, *columns))


def to_json(src, out: IO[str]) -> None:
    """Stream rows as a JSON array of objects (csvplus.go:446-475).

    Matches the reference's byte format exactly: Go's ``json.Encoder``
    emits each object compactly with **sorted keys**, followed by a
    newline; objects are comma-separated inside ``[...]`` and flushed in
    ~10KB batches.  The reference sets ``SetEscapeHTML(false)``
    (csvplus.go:456), so ``&<>`` pass through unescaped; Go's remaining
    escaping rules (``\\u0008``/``\\u000c`` for backspace/form-feed,
    always-escaped U+2028/U+2029) are reproduced by
    :func:`csvplus_tpu.utils.gojson.go_json_object`.
    """
    table = _device_table(src)  # the plan runs before the stage, as in to_csv
    with telemetry.stage("sink:json", table.nrows if table is not None else 0) as stage:
        start = _position(out)
        if table is not None:
            from .columnar.csvenc import encode_json_body

            body = encode_json_body(table)
            if body is not None:
                out.write("[" + body + "]")
                _note_written(stage, table.nrows, out, start)
                return
            # heterogeneous rows: stream the computed table instead
            from .source import iterate

            rows_out: List[Row] = []
            iterate(table.to_rows(), rows_out.append, clone=False)
            src = lambda fn: [fn(r) for r in rows_out]  # noqa: E731

        buf: List[str] = ["["]
        buf_len = 1
        count = 0

        def emit(row: Row) -> None:
            nonlocal buf_len, count
            count += 1
            if count != 1:
                buf.append(",")
                buf_len += 1
            s = go_json_object(row) + "\n"
            buf.append(s)
            buf_len += len(s)
            if buf_len > 10000:
                out.write("".join(buf))
                buf.clear()
                buf_len = 0

        src(emit)

        buf.append("]")
        out.write("".join(buf))
        _note_written(stage, count, out, start)


def to_json_file(src, name: str) -> None:
    """JSON sink to a named file with no-partial-output guarantee
    (csvplus.go:478-480)."""
    _write_file(name, lambda f: to_json(src, f))


def to_rows(src) -> List[Row]:
    """Materialize the source into a list of Rows (csvplus.go:483-490).

    A device-planned source executes its fused plan inside ``src(fn)``
    (see :func:`csvplus_tpu.columnar.exec.plan_runner`), so sinks need no
    device special-casing — and error wrapping is identical either way."""
    hint = getattr(src, "_rows_hint", None)
    if hint is not None:
        # take_rows-backed source (the point-lookup hot path): clone
        # straight off the backing list — identical to what iterate()
        # would deliver, minus the per-row callback machinery
        return [Row(r) for r in hint]
    out: List[Row] = []
    src(out.append)
    return out


def to_rows_many(sources) -> List[List[Row]]:
    """Materialize a batch of sources — one Row list per source, in
    order.  The natural sink for :meth:`Index.find_many` results: the
    batched lookup engine has already amortized the search and decode,
    so this is pure iteration."""
    out = []
    for src in sources:
        hint = getattr(src, "_rows_hint", None)
        out.append(
            [Row(r) for r in hint] if hint is not None else to_rows(src)
        )
    return out


def _write_file(name: str, fn, mode: str = "w") -> None:
    """Create *name*, run *fn(file)*; on ANY failure remove the file
    (csvplus.go:418-443).  ``mode="wb"`` for binary sinks."""
    if "b" in mode:
        f = open(name, mode)
    else:
        f = open(name, mode, encoding="utf-8", newline="")
    try:
        fn(f)
        f.close()  # close failure (e.g. ENOSPC flush) also removes the file
    except BaseException:
        try:
            f.close()
        except OSError:
            pass
        try:
            os.remove(name)
        except OSError:
            pass
        raise
