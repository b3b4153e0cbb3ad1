"""DataSource: the lazy, composable iteration protocol.

The load-bearing abstraction of the reference (csvplus.go:207-256): a data
source *is a function* — invoking it pushes rows one at a time into a
callback.  Here :class:`DataSource` is a callable object so the Go-style
usage ``src(row_fn)`` works verbatim, while combinators are methods that
return new lazy sources.  Nothing executes until a sink (or direct call)
drives the chain.

Semantics preserved from the reference:

* rows yielded from materialized sources are **cloned** before delivery, so
  consumers may mutate them freely (csvplus.go:225-249, clone at :230);
* a callback may raise :class:`StopPipeline` (Go: return ``io.EOF``) to stop
  early without error (csvplus.go:212-214);
* errors are annotated with row numbers at the *source* level, exactly where
  the reference wraps them (``iterate`` csvplus.go:242-245 uses the 0-based
  slice position; the CSV reader uses 1-based file lines, csvplus.go:1102);
* ``Transform`` drops empty result rows (csvplus.go:265);
* ``Top`` stops via the EOF mechanism (csvplus.go:319) so upstream readers
  treat it as a clean stop.

Device execution: each DataSource optionally carries a symbolic ``plan``
(see :mod:`csvplus_tpu.plan`).  When every stage of a chain is symbolic and
the origin is a columnar device table, sinks execute the fused device plan
instead of streaming host rows.  Any opaque Python callback keeps full API
parity by falling back to the host streaming path.
"""

from __future__ import annotations

from typing import Any, Callable, Iterable, Iterator, List, Optional, Sequence

from .errors import CsvPlusError, DataSourceError, StopPipeline
from .row import Row, merge_rows

#: A row callback: called once per row; raise :class:`StopPipeline` to
#: stop cleanly, any other exception to fail (Go: ``func(Row) error``,
#: csvplus.go:208).
RowFunc = Callable[[Row], None]


def iterate(rows: Sequence[Row], fn: RowFunc, clone: bool = True) -> None:
    """Drive *fn* over a row slice, cloning each row (csvplus.go:225-249).

    Errors raised by *fn* are wrapped in :class:`DataSourceError` with the
    0-based position of the offending row, matching the reference's
    ``Line: uint64(i)``.  ``clone=False`` skips the defensive copy for
    callers whose rows are already single-use (freshly decoded).
    """
    i = 0
    try:
        for i, row in enumerate(rows):
            fn(Row(row) if clone else row)  # Row(row) is a fresh copy
    except StopPipeline:
        return
    except DataSourceError:
        raise
    except Exception as e:
        raise DataSourceError(i, e) from e


class DataSource:
    """A lazy stream of Rows; call it with a row callback to execute.

    Construct from a driver function ``run(fn)`` (Go's ``DataSource`` type,
    csvplus.go:215) — or use :func:`take_rows` / :func:`take` /
    :func:`csvplus_tpu.reader.from_file`.
    """

    __slots__ = ("_run", "plan", "_plan_unsupported", "plan_note", "_rows_hint")

    def __init__(self, run: Callable[[RowFunc], None], plan: Any = None):
        self._run = run
        self.plan = plan  # symbolic plan IR node, or None (host-only chain)
        self._plan_unsupported = False  # memo: device plan known-unsupported
        self.plan_note = None  # why device execution stopped, if it did
        # already-materialized backing rows (take_rows sources): sinks
        # may clone straight off this list instead of driving the
        # callback machinery per row — the point-lookup hot path
        self._rows_hint = None

    def explain(self) -> str:
        """Human-readable execution plan: the device plan when the chain
        is symbolic, or where (and why) it falls to the host path —
        the 'plan printer' from SURVEY.md §7's callback-escape-hatch
        requirement."""
        from .plan import explain as _explain

        base = _explain(self.plan)
        if self.plan is None and self.plan_note:
            return f"{base}\n  device execution stopped at: {self.plan_note}"
        return base

    # -- execution ---------------------------------------------------------

    def __call__(self, fn: RowFunc) -> None:
        """Push every row into *fn*.  *fn* may raise StopPipeline to stop
        cleanly; any other exception propagates (annotated with a row
        number by the originating source)."""
        try:
            self._run(fn)
        except StopPipeline:
            return

    def __iter__(self) -> Iterator[Row]:
        """Pythonic pull iteration (streaming, bounded buffer).

        The push-based pipeline runs in a helper thread; rows cross through
        a bounded queue (:func:`csvplus_tpu.utils.relay.relay_iter`), so
        memory use stays constant for long streams.  Abandoning the
        iterator stops the producer.
        """
        from .utils.relay import RelayStopped, relay_iter

        def run(emit) -> None:
            def fn(row: Row) -> None:
                try:
                    emit(row)
                except RelayStopped:
                    raise StopPipeline from None

            self(fn)

        return relay_iter(run, maxsize=1024)

    # -- per-row lazy combinators (csvplus.go:258-310) ---------------------

    def transform(self, trans: Callable[[Row], Optional[Row]]) -> "DataSource":
        """Most generic per-row stage (csvplus.go:262-272).

        *trans* returns the replacement row; an empty dict or ``None`` drops
        the row; raising stops the iteration.
        """

        def run(fn: RowFunc) -> None:
            def step(row: Row) -> None:
                out = trans(row)
                if out:
                    fn(out if isinstance(out, Row) else Row(out))

            self._run(step)

        from .plan import transform_plan
        return _make(run, transform_plan(self.plan, trans), self, "transform", trans)

    def filter(self, pred: Callable[[Row], bool]) -> "DataSource":
        """Keep rows for which *pred* is true (csvplus.go:276-286)."""

        def run(fn: RowFunc) -> None:
            def step(row: Row) -> None:
                if pred(row):
                    fn(row)

            self._run(step)

        from .plan import filter_plan
        return _make(run, filter_plan(self.plan, pred), self, "filter", pred)

    def map(self, mf: Callable[[Row], Row]) -> "DataSource":
        """Apply *mf* to every row (csvplus.go:290-296)."""

        def run(fn: RowFunc) -> None:
            def step(row: Row) -> None:
                out = mf(row)
                fn(out if isinstance(out, Row) else Row(out))

            self._run(step)

        from .plan import map_plan
        return _make(run, map_plan(self.plan, mf), self, "map", mf)

    def validate(
        self, vf: Callable[[Row], "None | bool"], message: str = "validation failed"
    ) -> "DataSource":
        """Check every row; *vf* raises to fail the pipeline at that row
        (csvplus.go:300-310).

        Passing a symbolic predicate (``Like``/``All``/``Any``/``Not``)
        instead of a raising callback keeps the check on device: the
        fused mask is reduced and the pipeline aborts with *message* —
        wrapped with the first failing row's source number — exactly
        like the host path.
        """
        from .predicates import Predicate

        if isinstance(vf, Predicate):
            pred = vf

            def run(fn: RowFunc) -> None:
                def step(row: Row) -> None:
                    if not pred(row):
                        raise CsvPlusError(message)
                    fn(row)

                self._run(step)

            from .plan import validate_plan
            return _make(
                run, validate_plan(self.plan, pred, message), self, "validate", pred
            )

        def run(fn: RowFunc) -> None:
            def step(row: Row) -> None:
                vf(row)
                fn(row)

            self._run(step)

        return _make(run, None, self, "validate", vf)

    # -- windowing combinators (csvplus.go:312-374) ------------------------

    def top(self, n: int) -> "DataSource":
        """Pass down at most *n* rows, then stop cleanly (csvplus.go:313-326)."""

        def run(fn: RowFunc) -> None:
            counter = n

            def step(row: Row) -> None:
                nonlocal counter
                if counter == 0:
                    raise StopPipeline
                counter -= 1
                fn(row)

            self._run(step)

        from .plan import top_plan
        return _make(run, top_plan(self.plan, n), self)

    def drop(self, n: int) -> "DataSource":
        """Skip the first *n* rows (csvplus.go:329-342)."""

        def run(fn: RowFunc) -> None:
            counter = n

            def step(row: Row) -> None:
                nonlocal counter
                if counter == 0:
                    fn(row)
                else:
                    counter -= 1

            self._run(step)

        from .plan import drop_plan
        return _make(run, drop_plan(self.plan, n), self)

    def take_while(self, pred: Callable[[Row], bool]) -> "DataSource":
        """Pass rows until *pred* is first false, then stop (csvplus.go:346-358)."""

        def run(fn: RowFunc) -> None:
            def step(row: Row) -> None:
                if not pred(row):
                    raise StopPipeline
                fn(row)

            self._run(step)

        from .plan import take_while_plan
        return _make(run, take_while_plan(self.plan, pred), self, "take_while", pred)

    def drop_while(self, pred: Callable[[Row], bool]) -> "DataSource":
        """Skip rows while *pred* holds, then pass everything (csvplus.go:362-374)."""

        def run(fn: RowFunc) -> None:
            yielding = False

            def step(row: Row) -> None:
                nonlocal yielding
                if not yielding and pred(row):
                    return
                yielding = True
                fn(row)

            self._run(step)

        from .plan import drop_while_plan
        return _make(run, drop_while_plan(self.plan, pred), self, "drop_while", pred)

    # -- column projection (csvplus.go:492-525) ----------------------------

    def drop_columns(self, *columns: str) -> "DataSource":
        """Remove the listed columns from each row (csvplus.go:493-507)."""
        if not columns:
            raise ValueError("no columns specified in DropColumns()")

        def run(fn: RowFunc) -> None:
            def step(row: Row) -> None:
                for c in columns:
                    row.pop(c, None)
                fn(row)

            self._run(step)

        from .plan import drop_columns_plan
        return _make(run, drop_columns_plan(self.plan, columns), self)

    def select_columns(self, *columns: str) -> "DataSource":
        """Keep exactly the listed columns; error if any is missing
        (csvplus.go:511-525)."""
        if not columns:
            raise ValueError("no columns specified in SelectColumns()")

        def run(fn: RowFunc) -> None:
            def step(row: Row) -> None:
                fn(row.select(*columns))

            self._run(step)

        from .plan import select_columns_plan
        return _make(run, select_columns_plan(self.plan, columns), self)

    # -- index / join entry points (implemented in index.py) ---------------

    def index_on(self, *columns: str):
        """Materialize a sorted :class:`~csvplus_tpu.index.Index` on the
        listed key columns (csvplus.go:529-531)."""
        from .index import create_index

        return create_index(self, columns)

    def unique_index_on(self, *columns: str):
        """Like :meth:`index_on` but errors on duplicate keys
        (csvplus.go:535-537)."""
        from .index import create_unique_index

        return create_unique_index(self, columns)

    def join(self, index, *columns: str) -> "DataSource":
        """Lazy lookup join against *index* (csvplus.go:539-569).

        The listed stream columns match the index's key columns left to
        right; with no columns given, the index's own key column names are
        used ("natural join").  Merged rows contain all columns from both
        sides; on a name collision the **stream row's value wins**
        (csvplus.go:560, 571-583).
        """
        cols = _resolve_join_columns(index, columns, "Join()")

        def run(fn: RowFunc) -> None:
            index.materialize()  # host probe loop: decode a lazy index once

            def step(row: Row) -> None:
                values = row.select_values(*cols)
                for index_row in index._impl.find_rows(values):
                    fn(merge_rows(index_row, row))

            self._run(step)

        from .plan import join_plan
        return _make(run, join_plan(self.plan, index, cols), self, "join")

    def except_(self, index, *columns: str) -> "DataSource":
        """Anti-join: pass through rows whose key is NOT in *index*
        (csvplus.go:585-608)."""
        cols = _resolve_join_columns(index, columns, "Except()")

        def run(fn: RowFunc) -> None:
            index.materialize()  # host probe loop: decode a lazy index once

            def step(row: Row) -> None:
                values = row.select_values(*cols)
                if not index._impl.has(values):
                    fn(row)

            self._run(step)

        from .plan import except_plan
        return _make(run, except_plan(self.plan, index, cols), self, "except")

    # -- device migration --------------------------------------------------

    def on_device(
        self, device=None, shards: "int | None" = None, mesh=None
    ) -> "DataSource":
        """Materialize this source into an HBM-resident columnar table and
        return a plan-capable DataSource over it.

        The device-native entry point is ``FromFile(...).OnDevice()``
        (which parses straight into columns); this method is the general
        form for any host source — it streams the rows once, columnarizes
        (heterogeneous schemas allowed; missing cells stay absent), and
        subsequent symbolic stages run as device kernels.

        Error row numbers downstream of this route count streamed rows
        from 0 (the stream is anonymous here — any upstream numbering is
        not recoverable); ``FromFile(...).OnDevice()`` preserves the
        reader's record numbering instead.
        """
        from .columnar.ingest import _maybe_shard, source_from_table
        from .columnar.table import DeviceTable

        table = DeviceTable.from_rows(self.to_rows(), device=device)
        return source_from_table(_maybe_shard(table, shards, mesh))

    OnDevice = on_device

    # -- sinks (implemented in sinks.py) -----------------------------------

    def to_csv(self, out, *columns: str) -> None:
        """Drive the chain, writing selected columns as canonical CSV to
        *out* (csvplus.go:379-406; see :func:`csvplus_tpu.sinks.to_csv`)."""
        from .sinks import to_csv

        to_csv(self, out, *columns)

    def to_csv_file(self, name: str, *columns: str) -> None:
        """CSV sink to a named file; the file is removed on any error
        (csvplus.go:411-443)."""
        from .sinks import to_csv_file

        to_csv_file(self, name, *columns)

    def to_json(self, out) -> None:
        """Drive the chain, writing a JSON array of row objects to *out*
        (csvplus.go:446-475, byte-compatible with Go's json.Encoder)."""
        from .sinks import to_json

        to_json(self, out)

    def to_json_file(self, name: str) -> None:
        """JSON sink to a named file; the file is removed on any error
        (csvplus.go:478-480)."""
        from .sinks import to_json_file

        to_json_file(self, name)

    def to_rows(self) -> List[Row]:
        """Drive the chain and collect every row (csvplus.go:483-490)."""
        from .sinks import to_rows

        return to_rows(self)

    def to_device_table(self):
        """Execute the pipeline into a device-resident columnar table.

        The device-native terminal: runs this source's symbolic plan with
        the device executor and returns the materialized
        :class:`~csvplus_tpu.columnar.table.DeviceTable` — codes stay in
        HBM, nothing is decoded to host rows (that is what
        :meth:`to_rows` / the CSV/JSON sinks are for).  A source without
        a device plan (or with a stage the executor cannot lower, e.g. an
        opaque Python callback) columnarizes its streamed rows instead,
        so the call always succeeds with reference semantics.
        """
        from .columnar.table import DeviceTable

        device = None
        if self.plan is not None:
            from .columnar.exec import UnsupportedPlan, execute_plan

            try:
                table = execute_plan(self.plan)
            except UnsupportedPlan:
                table = None
            if table is not None:
                de = getattr(table, "deferred_error", None)
                if de is not None:
                    # a full materialization consumes every row, so a
                    # terminal validate failure always fires (parity with
                    # streaming the whole table)
                    raise de[1]
                return table
            # fallback stays on the device the pipeline was pinned to
            from . import plan as P

            node = self.plan
            while not isinstance(node, (P.Scan, P.Lookup)):
                node = node.child
            device = node.table.device
        return DeviceTable.from_rows(self.to_rows(), device=device)

    # -- Go-style aliases --------------------------------------------------
    Transform = transform
    Filter = filter
    Map = map
    Validate = validate
    Top = top
    Drop = drop
    TakeWhile = take_while
    DropWhile = drop_while
    DropColumns = drop_columns
    SelectColumns = select_columns
    IndexOn = index_on
    UniqueIndexOn = unique_index_on
    Join = join
    Except = except_
    ToCsv = to_csv
    ToCsvFile = to_csv_file
    ToJSON = to_json
    ToJSONFile = to_json_file
    ToRows = to_rows


_STAGE_BREAK_NOTES = {
    "join": "join() against an index with no device copy "
    "(call index.on_device() to keep the chain on device)",
    "except": "except_() against an index with no device copy "
    "(call index.on_device() to keep the chain on device)",
    "validate": "validate() callbacks have no symbolic form",
}


def _make(run, plan, parent=None, stage: str = "", arg: Any = None) -> "DataSource":
    """Build a combinator result: device plan execution when the chain is
    symbolic, with *run* (the host streaming closure) as fallback.  When
    the stage BREAKS an existing device plan (opaque argument / host-only
    index), the reason is recorded — and carried through later stages —
    for :meth:`DataSource.explain`."""
    if plan is None:
        ds = DataSource(run)
        if parent is not None:
            if parent.plan is not None and stage:
                ds.plan_note = _STAGE_BREAK_NOTES.get(
                    stage, f"{stage}({_describe_arg(arg)}) is not symbolic"
                )
            else:
                ds.plan_note = parent.plan_note  # keep the original reason
        return ds
    from .columnar.exec import plan_runner

    ds = DataSource(run, plan=plan)
    ds._run = plan_runner(plan, fallback=run, owner=ds)
    return ds


def _describe_arg(arg: Any) -> str:
    if arg is None:
        return ""
    return getattr(arg, "__name__", None) or type(arg).__name__


def _resolve_join_columns(index, columns: Sequence[str], what: str) -> List[str]:
    """Shared Join/Except column-list resolution (csvplus.go:546-550, 589-593)."""
    if not columns:
        return list(index._impl.columns)
    if len(columns) > len(index._impl.columns):
        raise ValueError(f"too many source columns in {what}")
    return list(columns)


def take_rows(rows: Iterable[Row]) -> DataSource:
    """Convert a list of Rows to a DataSource (csvplus.go:218-222).

    Rows are cloned on every iteration, so consumers may mutate them.
    """
    rows = list(rows)

    def run(fn: RowFunc) -> None:
        iterate(rows, fn)

    ds = DataSource(run)
    ds._rows_hint = rows
    return ds


def take(src: Any) -> DataSource:
    """Lift anything with an ``iterate(fn)``/``Iterate(fn)`` method — a
    Reader, an Index, a DeviceTable — into a DataSource (csvplus.go:252-256)."""
    if isinstance(src, DataSource):
        return src
    it = getattr(src, "iterate", None) or getattr(src, "Iterate", None)
    if it is None:
        raise TypeError(f"take(): {type(src).__name__} has no iterate() method")
    return DataSource(it, plan=getattr(src, "plan", None))
