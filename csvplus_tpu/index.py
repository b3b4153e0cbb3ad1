"""Index: a sorted, materialized collection of Rows with O(log n) search.

Reference: csvplus.go:610-920.  Rows are sorted lexicographically by the
key columns (byte order — Python's str comparison equals Go's
``strings.Compare`` on UTF-8 because UTF-8 byte order preserves code-point
order), searched by binary search, and optionally persisted.

Semantics preserved:

* building an index fully materializes the source (csvplus.go:722-733) and
  validates every row has all key columns, with the reference's exact
  error message;
* ``find``/``sub_index`` accept a *prefix* of the key values and return
  zero-copy row ranges (csvplus.go:869-891);
* joins never mutate the index (pinned by csvplus_test.go:325-365);
* ``resolve_duplicates`` calls the user back once per duplicate group; the
  returned row replaces the group when it has at least as many cells as
  there are key columns, an empty row drops the group (csvplus.go:643-653,
  809-867).

**Known divergence from the reference (intentional):** the reference's
in-place compaction drops the final row of the index whenever the last
row is a *singleton* following a duplicate group (``dedup``
csvplus.go:842,851-859 never flushes the trailing pending row; its own
tests never check the index contents afterwards, so the data loss is
invisible upstream).  This implementation keeps that row.

TPU-native execution: an index built from a device-planned source is
**device-resident and lazy** — the sort runs as a fused multi-key
``lax.sort`` over dictionary codes (:mod:`..ops.sort`), the uniqueness
check is one adjacent-equality reduction, ``find``/``sub_index`` binary-
search the packed key array and decode *only the matching range*, and
``resolve_duplicates`` with a named policy ("first"/"last") compacts on
the device — run-boundary mask, prefix sum, one scatter of the kept
positions, one gather per column — with one scalar host read (the kept
rows' count) and nothing row-proportional crossing the host in either
direction.  Host rows are decoded on demand the first time a host-only
operation (arbitrary callback, persistence, host join) needs them.
"""

from __future__ import annotations

import bisect
import json
import threading
from typing import Callable, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from .errors import CsvPlusError, DataSourceError
from .obs.span import tracer
from .row import Row, all_columns_unique, equal_rows
from .source import DataSource, RowFunc, iterate, take_rows
from .utils.observe import telemetry

_MAGIC = "csvplus-tpu-index"
_VERSION = 1

Resolver = Union[str, Callable[[List[Row]], Optional[Row]]]


class IndexImpl:
    """Sorted rows + key column list (reference ``indexImpl``
    csvplus.go:785-788).  ``rows`` may be lazily backed by a sorted
    device table (``dev``), decoded on first host access."""

    __slots__ = ("_rows", "columns", "_keys", "_probe_map", "dev", "_lock")

    def __init__(self, rows: Optional[List[Row]], columns: Sequence[str], dev=None):
        self._rows = rows
        self.columns = list(columns)
        self._keys: Optional[List[Tuple[str, ...]]] = None
        # full-width key tuple -> (lower, upper); built lazily for the
        # host join's per-row probes (hash beats bisect like the Go
        # baseline's map would); prefix probes still bisect
        self._probe_map: "Optional[dict]" = None
        self.dev = dev  # ops.join.DeviceIndex over the sorted columnar copy
        # serializes the lazy builds (row materialization, key cache,
        # probe map) under concurrent readers — without it two serving
        # threads each pay the O(n) build and one result is discarded.
        # RLock because keys->rows nest.  Probes against an index while
        # a writer MUTATES it (rows setter / sort / dedup) remain a
        # caller error; the lock makes concurrent READS safe.
        self._lock = threading.RLock()

    # -- lazy materialization ---------------------------------------------

    @property
    def is_lazy(self) -> bool:
        return self._rows is None

    @property
    def rows(self) -> List[Row]:
        if self._rows is None:
            with self._lock:
                if self._rows is None:  # double-checked under the lock
                    assert self.dev is not None
                    self._rows = self.dev.table.to_rows()
        return self._rows

    @rows.setter
    def rows(self, value: List[Row]) -> None:
        self._rows = value
        self._invalidate()

    def __len__(self) -> int:
        if self._rows is None and self.dev is not None:
            return self.dev.table.nrows
        return len(self.rows)

    # -- key cache ---------------------------------------------------------

    @property
    def keys(self) -> List[Tuple[str, ...]]:
        """Per-row key tuples, built lazily and invalidated on mutation.
        Concurrent first reads build once under ``_lock``."""
        if self._keys is None:
            with self._lock:
                if self._keys is None:
                    cols = self.columns
                    self._keys = [tuple(r[c] for c in cols) for r in self.rows]
        return self._keys

    def _invalidate(self) -> None:
        self._keys = None
        self._probe_map = None

    def sort(self) -> None:
        """Sort rows by the key columns (csvplus.go:794-807).  Stable —
        a deterministic refinement of the reference's unstable sort."""
        cols = self.columns
        self.rows.sort(key=lambda r: tuple(r[c] for c in cols))
        self._invalidate()

    # -- binary search (csvplus.go:869-920) --------------------------------

    def bounds(self, values: Sequence[str]) -> Tuple[int, int]:
        """[lower, upper) range of rows whose key prefix equals *values*.

        Device-lazy indexes search the packed key array; materialized ones
        bisect the host key tuples.
        """
        if len(values) > len(self.columns):
            raise ValueError("too many columns in Index.find()")
        if self._rows is None and self.dev is not None and self.dev.supported:
            return self.dev.point_bounds(list(values))
        if not values:
            return 0, len(self.rows)
        k = len(values)
        v = tuple(values)
        if k == len(self.columns):
            return self._ensure_probe_map().get(v, (0, 0))
        keys = self.keys
        lower = bisect.bisect_left(keys, v, key=lambda kt: kt[:k])
        upper = bisect.bisect_right(keys, v, lo=lower, key=lambda kt: kt[:k])
        return lower, upper

    def _ensure_probe_map(self) -> Dict[Tuple[str, ...], Tuple[int, int]]:
        """Full-width key tuple -> [lower, upper), built lazily in one
        O(n) sweep (once, under ``_lock``) and invalidated on mutation."""
        pm = self._probe_map
        if pm is None:
            with self._lock:
                pm = self._probe_map
                if pm is None:
                    pm = {}
                    keys = self.keys
                    i, n = 0, len(keys)
                    while i < n:
                        j = i + 1
                        while j < n and keys[j] == keys[i]:
                            j += 1
                        pm[keys[i]] = (i, j)
                        i = j
                    self._probe_map = pm
        return pm

    def bounds_many(
        self, probes: Sequence[Sequence[str]]
    ) -> List[Tuple[int, int]]:
        """Batched :meth:`bounds` — the host half of the lookup engine.

        Device-lazy indexes take ONE vectorized pass over the packed key
        array (``DeviceIndex.point_bounds_many``).  Host indexes answer
        full-width probes from the probe map and sweep each prefix width
        in sorted probe order, so the bisect window only ever narrows —
        a single forward pass over the key tuples instead of a fresh
        full-range binary search per probe.
        """
        for p in probes:
            if len(p) > len(self.columns):
                raise ValueError("too many columns in Index.find()")
        if self._rows is None and self.dev is not None and self.dev.supported:
            return self.dev.point_bounds_many(probes)
        n = len(self.rows)
        full = len(self.columns)
        out: List[Optional[Tuple[int, int]]] = [None] * len(probes)
        by_k: Dict[int, List[int]] = {}
        for i, p in enumerate(probes):
            k = len(p)
            if k == 0:
                out[i] = (0, n)
            elif k == full:
                out[i] = self._ensure_probe_map().get(tuple(p), (0, 0))
            else:
                by_k.setdefault(k, []).append(i)
        if by_k:
            keys = self.keys
            for k, idxs in by_k.items():
                idxs.sort(key=lambda i: tuple(probes[i]))
                lo = 0
                prev: Optional[Tuple[str, ...]] = None
                prev_bounds = (0, 0)
                for i in idxs:
                    v = tuple(probes[i])
                    if v == prev:
                        out[i] = prev_bounds  # duplicate probe: memoized
                        continue
                    lower = bisect.bisect_left(
                        keys, v, lo=lo, key=lambda kt: kt[:k]
                    )
                    upper = bisect.bisect_right(
                        keys, v, lo=lower, key=lambda kt: kt[:k]
                    )
                    out[i] = prev_bounds = (lower, upper)
                    prev, lo = v, lower
        return out  # type: ignore[return-value]

    def bounds_handle(self, probes: Sequence[Sequence[str]]):
        """:meth:`bounds_many` for a caller whose next call is
        :meth:`rows_for_bounds`: the same list, or — a unique
        device-lazy index past the mirror cap, every probe naming the
        full key — an opaque ``DeviceBounds`` whose search has been
        dispatched and not read (``DeviceIndex.point_bounds_many``), so
        that the batch's rows and bounds come back in one read."""
        if self._rows is None and self.dev is not None and self.dev.supported:
            return self.dev.point_bounds_many(probes, chain=True)
        return self.bounds_many(probes)

    def find_rows(self, values: Sequence[str]) -> List[Row]:
        """Row range matching the key prefix (csvplus.go:870-891).

        On a device-lazy index only the matching range is decoded.
        Routed through the batched engine so the fast path is the only
        path.
        """
        return self.find_rows_many([values])[0]

    def find_rows_many(
        self, probes: Sequence[Sequence[str]]
    ) -> List[List[Row]]:
        """Batched :meth:`find_rows`: all bounds in one vectorized pass
        (:meth:`bounds_many`), then ONE amortized decode over the union
        of matched ranges (:meth:`rows_for_bounds`)."""
        return self.rows_for_bounds(self.bounds_handle(probes))

    def rows_for_bounds(self, bounds) -> List[List[Row]]:
        """Decode one row block per [lower, upper) range (*bounds*: what
        :meth:`bounds_many` or :meth:`bounds_handle` returned).

        On a device-lazy index the matched ranges decode together: the
        mirror tier batches through the LRU-cached
        :meth:`~csvplus_tpu.columnar.table.DeviceTable.rows_from_mirror_many`,
        the above-cap tier pays ONE device gather + decode for the whole
        batch instead of a transfer per probe.
        """
        from .ops.join import DeviceBounds, DeviceIndex

        if isinstance(bounds, DeviceBounds):
            # the chain: the search's answer feeds the gather where it
            # lies, and ONE read brings bounds and rows (a hit is the row
            # at ``lower``; a miss's and a pad query's slot are dropped by
            # position)
            table = self.dev.table
            got = table.take_rows(bounds.res)
            bounds.settle(got)
            hit = np.flatnonzero(bounds.upper > bounds.lower)
            rows = table.decode_rows(got[2:, hit])
            out = [[] for _ in range(bounds.ok.shape[0])]
            for i, row in zip(hit.tolist(), rows):
                out[i] = [row]
            return out
        if self._rows is None and self.dev is not None:
            table = self.dev.table
            # gate on total CELLS, not rows: the mirror transfers every
            # column, so a wide table must not blow the transfer budget
            cells = table.nrows * max(len(table.columns), 1)
            if cells <= DeviceIndex.POINT_MIRROR_MAX_KEYS:
                # small index: decode from host code mirrors (one O(n)
                # transfer on the first find, then pure numpy per lookup
                # — no device round trip)
                return table.rows_from_mirror_many(bounds)
            out: List[List[Row]] = [[] for _ in bounds]
            with tracer.span("serve:gather:index"):
                hit = [
                    (i, int(lo), int(hi))
                    for i, (lo, hi) in enumerate(bounds)
                    if hi > lo
                ]
                idx = np.concatenate(
                    [np.arange(lo, hi, dtype=np.int64) for _, lo, hi in hit]
                ) if hit else None
            if hit:
                rows = table.to_rows(idx)
                off = 0
                for i, lo, hi in hit:
                    out[i] = rows[off : off + (hi - lo)]
                    off += hi - lo
            return out
        rows = self.rows
        return [rows[lo:hi] for lo, hi in bounds]

    def has(self, values: Sequence[str]) -> bool:
        """True when any row matches the key prefix (csvplus.go:899-905)."""
        lower, upper = self.bounds(values)
        return lower < upper

    # -- deduplication (csvplus.go:809-867) --------------------------------

    def dedup(self, resolve: Callable[[List[Row]], Optional[Row]]) -> None:
        rows, cols = self.rows, self.columns
        out: List[Row] = []
        i, n = 0, len(rows)
        changed = False
        while i < n:
            j = i + 1
            while j < n and equal_rows(cols, rows[i], rows[j]):
                j += 1
            if j - i == 1:
                out.append(rows[i])
            else:
                changed = True
                chosen = resolve(rows[i:j])
                # keep the chosen row unless it is 'empty' — the reference's
                # emptiness test is len(row) >= len(key columns)
                # (csvplus.go:845-848)
                if chosen is not None and len(chosen) >= len(cols):
                    out.append(chosen if isinstance(chosen, Row) else Row(chosen))
            i = j
        if changed:
            self.rows = out


class Index:
    """Sorted collection of Rows; see module docstring.

    Reference: ``Index`` csvplus.go:610-653.
    """

    def __init__(self, impl: IndexImpl):
        self._impl = impl
        # DeviceIndex over the sorted columnar copy (None = host-only);
        # used by device joins/finds.  Kept in sync with impl.dev.
        self.device_table = impl.dev

    # -- iteration ---------------------------------------------------------

    def materialize(self) -> "Index":
        """Decode a device-lazy index into host rows (idempotent).  Host
        row-at-a-time consumers call this once instead of paying a device
        round-trip per lookup."""
        _ = self._impl.rows
        return self

    def sync(self) -> "Index":
        """Block until the device-side build (sort + gathers) has actually
        executed; no-op for host indexes.  Without this, the async build
        completes under whatever operation first touches the index —
        misattributing build time to e.g. the first ``find`` (the round-3
        bench's "device find" tier measured exactly that)."""
        if self._impl.dev is not None:
            self._impl.dev.table.sync()
        return self

    def iterate(self, fn: RowFunc) -> None:
        """Iterate rows in key order, cloning each (csvplus.go:618-620)."""
        iterate(self._impl.rows, fn)

    Iterate = iterate

    def __iter__(self):
        return iter(take_rows(self._impl.rows))

    def __len__(self) -> int:
        return len(self._impl)

    @property
    def columns(self) -> List[str]:
        return list(self._impl.columns)

    # -- queries -----------------------------------------------------------

    def find(self, *values: str) -> DataSource:
        """Lazy source over all Rows matching the key-value prefix
        (csvplus.go:625-627); on a device index only the matching range
        is ever decoded.  Routed through :meth:`find_many` so the
        batched engine is the only lookup path."""
        return self.find_many([values])[0]

    def find_many(self, probes: Sequence) -> List[DataSource]:
        """Batched :meth:`find`: one DataSource per key-prefix probe.

        Each probe is a sequence of key values (a bare string means a
        one-column prefix).  The whole batch runs through one vectorized
        bounds search and one amortized decode — on the 1M-row big-index
        shape this is the difference between ~19K and >100K lookups/s —
        and each result is byte-identical to the matching single
        ``find`` call.  On a supported device index every result also
        carries a :class:`~csvplus_tpu.plan.Lookup` leaf plan, so
        downstream symbolic stages keep lowering to the device.
        """
        impl = self._impl
        norm = [
            (p,) if isinstance(p, str) else tuple(p) for p in probes
        ]
        bounds = impl.bounds_handle(norm)
        groups = impl.rows_for_bounds(bounds)
        device_tier = (
            impl._rows is None and impl.dev is not None and impl.dev.supported
        )
        if device_tier:
            from .plan import Lookup

            dev_table = impl.dev.table
            out = []
            # hand-inlined take_rows: per-probe cost is what separates
            # ~90K from >100K lookups/s on the 1M-row micro shape.  The
            # decoded blocks may be shared with the mirror LRU — safe
            # because every delivery path clones (iterate / _rows_hint).
            for rows, (lo, hi) in zip(groups, bounds):
                src = DataSource(
                    lambda fn, _rows=rows: iterate(_rows, fn)
                )
                src._rows_hint = rows
                src.plan = Lookup(dev_table, lo, hi)
                out.append(src)
            return out
        return [take_rows(rows) for rows in groups]

    def sub_index(self, *values: str) -> "Index":
        """Index of the rows matching the key prefix, keyed on the
        remaining columns (csvplus.go:632-641)."""
        impl = self._impl
        if len(values) >= len(impl.columns):
            raise ValueError("too many values in SubIndex()")
        rest = impl.columns[len(values):]
        if impl.is_lazy and impl.dev is not None and impl.dev.supported:
            from .ops.join import DeviceIndex

            lower, upper = impl.dev.point_bounds(list(values))
            sub_table = impl.dev.table.gather(
                np.arange(lower, upper, dtype=np.int64)
            )
            return Index(IndexImpl(None, rest, dev=DeviceIndex.build(sub_table, rest)))
        return Index(IndexImpl(impl.find_rows(values), rest))

    def resolve_duplicates(self, resolve: Resolver) -> None:
        """Resolve groups of rows with duplicate keys (csvplus.go:643-653).

        *resolve* is either a callback receiving each duplicate group and
        returning the single row to keep (empty row/None drops the group,
        raising aborts) — or a named device-friendly policy:

        * ``"first"`` — keep the first row of each duplicate group (in
          index order), equivalent to ``lambda g: g[0]``;
        * ``"last"`` — keep the last row, equivalent to ``lambda g: g[-1]``.

        Named policies on a device-lazy index stay on the device
        (``ops/sort.py:compact_runs``): the run-boundary mask is formed
        there, one sort keyed on (its complement, the row number) moves the
        kept rows of every column to the front (the columns ride the sort as
        operands; nothing is gathered), and the host reads back ONE
        scalar, the kept rows' count, which the result's shape needs.
        Stages ``dedup:runs``, ``dedup:compact`` (``tier: device``,
        ``form: sort``), ``index:pack``.  A callback groups on the host
        from the mask read back whole (``tier: host``).
        """
        impl = self._impl
        if isinstance(resolve, str):
            if resolve not in ("first", "last"):
                raise ValueError(f"unknown duplicate-resolution policy {resolve!r}")
            if impl.is_lazy and impl.dev is not None:
                self._device_policy_dedup(resolve)
                return
            resolve = (lambda g: g[0]) if resolve == "first" else (lambda g: g[-1])
        elif impl.is_lazy and impl.dev is not None:
            if self._device_callback_dedup(resolve):
                return
        impl.dedup(resolve)
        self.device_table = None  # columnar copy is stale after mutation
        impl.dev = None

    def _device_policy_dedup(self, policy: str) -> None:
        from .ops.join import DeviceIndex
        from .ops.sort import compact_runs

        impl = self._impl
        new_table = compact_runs(impl.dev.table, impl.columns, policy)
        if new_table is None:
            return  # no duplicates; nothing to do
        impl.dev = DeviceIndex.build(new_table, impl.columns)
        impl._rows = None
        impl._invalidate()
        self.device_table = impl.dev

    def _device_callback_dedup(self, resolve: Resolver) -> bool:
        """Callback dedup on a device-lazy index decoding ONLY the
        duplicate groups' rows (csvplus.go:809-867 semantics; VERDICT r3
        #10): group boundaries come from the device run-starts kernel,
        O(dup) rows stream to host for the callback, and when every
        chosen row is a member of its group (the typical callback) the
        compaction is a pure columnar gather.  A callback that returns a
        BRAND-NEW row forces a full materialization — but the callback
        has already been invoked exactly once per group either way.

        Returns True when the dedup was completed here; False when this
        index has no supported device form (caller falls back)."""
        impl = self._impl
        if impl.dev is None:
            return False
        from .ops.join import DeviceIndex
        from .ops.sort import run_starts

        table = impl.dev.table
        starts = run_starts(table, impl.columns)
        if starts.size == 0:
            return True
        idx_starts = np.flatnonzero(starts)
        lengths = np.diff(np.append(idx_starts, table.nrows))
        dup = lengths > 1
        if not dup.any():
            return True  # no duplicate keys: nothing to resolve
        groups = list(zip(idx_starts[dup].tolist(), lengths[dup].tolist()))
        dup_row_idx = np.concatenate(
            [np.arange(s, s + l, dtype=np.int64) for s, l in groups]
        )
        decoded = table.to_rows(dup_row_idx)  # O(dup) decode, group order

        # one callback invocation per group, exactly like impl.dedup.
        # `off` is found by comparing against PRISTINE clones: a callback
        # that mutates a group row and returns it must keep the mutation
        # (host-path semantics), so a mutated member counts as a new row
        decisions: "list[tuple[int, int, object]]" = []
        replaced: "list[Row]" = []
        pos = 0
        for s, l in groups:
            group = decoded[pos : pos + l]
            pos += l
            pristine = [Row(r) for r in group]
            chosen = resolve(list(group))
            if chosen is None or len(chosen) < len(impl.columns):
                decisions.append((s, l, None))  # drop the whole group
                continue
            off = next((i for i, r in enumerate(pristine) if r == chosen), None)
            if off is None:
                chosen = chosen if isinstance(chosen, Row) else Row(chosen)
                replaced.append(chosen)
            decisions.append((s, l, off if off is not None else chosen))

        if not replaced:
            # pure columnar compaction: keep all singleton rows plus the
            # chosen member of each duplicate group
            keep = np.ones(table.nrows, dtype=bool)
            for s, l, d in decisions:
                keep[s : s + l] = False
                if d is not None:
                    keep[s + int(d)] = True
            with telemetry.stage("dedup:compact", table.nrows) as st:
                sel = np.flatnonzero(keep).astype(np.int64)
                new_table = table.gather(sel)
                # the run mask came to the host whole (run_starts) and
                # the selection goes back up: row-proportional both ways
                st.update(
                    rows=table.nrows, rows_out=int(sel.size), policy="callback",
                    kept=int(sel.size), tier="host", row_gathers=len(new_table.columns),
                    host_sync_elements=table.nrows,
                )
                telemetry.barrier([c.storage for c in new_table.columns.values()])
            impl.dev = DeviceIndex.build(new_table, impl.columns)
            impl._rows = None
            impl._invalidate()
            self.device_table = impl.dev
            return True

        # a callback produced a new row: materialize once and splice the
        # recorded decisions (the callback is NOT re-invoked)
        rows = table.to_rows()
        out: List[Row] = []
        cursor = 0
        for s, l, d in decisions:
            out.extend(rows[cursor:s])
            if d is not None:
                out.append(rows[s + d] if isinstance(d, int) else d)
            cursor = s + l
        out.extend(rows[cursor:])
        impl.rows = out
        impl._invalidate()
        self.device_table = None
        impl.dev = None
        return True

    # -- persistence (csvplus.go:655-705) ----------------------------------

    def write_to(self, file_name: str) -> None:
        """Persist the index; the file is removed on any write error, like
        the reference's gob writer (csvplus.go:656-680).

        Two formats behind one loader (SURVEY.md §7 M5):

        * a device-backed index saves **columnar** (v2): one npz with the
          key list plus each column's dictionary and code array — no host
          rows are ever materialized, and loading restores a lazy
          device index;
        * a host index (possibly heterogeneous rows) saves versioned
          JSON-lines (v1).  (A gob-compat shim is a non-goal, SURVEY §5.)
        """
        impl = self._impl
        if impl.is_lazy and impl.dev is not None:
            self._write_columnar(file_name)
            return
        from .sinks import _write_file

        def dump(f):
            f.write(
                json.dumps(
                    {
                        "magic": _MAGIC,
                        "version": _VERSION,
                        "columns": impl.columns,
                        "count": len(impl.rows),
                    }
                )
            )
            f.write("\n")
            for row in impl.rows:
                f.write(json.dumps(row, sort_keys=True, separators=(",", ":")))
                f.write("\n")

        _write_file(file_name, dump)

    WriteTo = write_to

    def _write_columnar(self, file_name: str) -> None:
        """v2 npz write.  Device-lane columns persist their packed int32
        lane arrays AS lanes (``l{i}:name``): persisting the exact index
        the lane feature exists for (a unique 100M-row key) must not
        reinstate the unbounded host dictionary materialization that
        ``col.dictionary`` would force (VERDICT r3 weak #6 / next #8)."""
        table = self._impl.dev.table
        lane_columns: "dict[str, int]" = {}
        arrays: "dict[str, np.ndarray]" = {}
        for name, col in table.columns.items():
            if col.dev_dictionary is not None and col._dictionary is None:
                col._ensure_sorted_lanes()  # v3 stores SORTED lane arrays
                lane_columns[name] = len(col.dev_dictionary)
                for i, lane in enumerate(col.dev_dictionary):
                    arrays[f"l{i}:{name}"] = np.asarray(lane)
            else:
                arrays[f"d:{name}"] = col.dictionary
            arrays[f"c:{name}"] = np.asarray(col.codes)
        arrays["__meta__"] = np.frombuffer(
            json.dumps(
                {
                    "magic": _MAGIC,
                    # v3 = lane columns present; pre-lane readers then get
                    # the pinned unsupported-version message instead of a
                    # misleading KeyError-driven "not an index file"
                    "version": 3 if lane_columns else 2,
                    "key_columns": self._impl.columns,
                    "columns": list(table.columns),
                    "lane_columns": lane_columns,
                    "count": table.nrows,
                }
            ).encode("utf-8"),
            dtype=np.uint8,
        )
        from .sinks import _write_file

        _write_file(file_name, lambda f: np.savez(f, **arrays), mode="wb")

    # -- device hook -------------------------------------------------------

    def on_device(self, device=None) -> "Index":
        """Attach an HBM-resident columnar copy of this index so joins and
        finds against it run as device kernels."""
        from .columnar.ingest import index_to_device

        self.device_table = index_to_device(self, device=device)
        self._impl.dev = self.device_table
        return self

    OnDevice = on_device

    # Go-style aliases
    Find = find
    FindMany = find_many
    SubIndex = sub_index
    ResolveDuplicates = resolve_duplicates


def load_index(file_name: str, device: "str | None" = None) -> Index:
    """Load an index persisted by :meth:`Index.write_to`
    (csvplus.go:683-705).  Columnar (v2) files restore a device-lazy
    index (*device* selects placement, like ``on_device``); JSONL (v1)
    files restore a host index."""
    with open(file_name, "rb") as fb:
        magic2 = fb.read(2)
    if magic2 == b"PK":  # npz container -> columnar v2
        return _load_columnar(file_name, device)
    with open(file_name, "r", encoding="utf-8") as f:
        try:
            header = json.loads(f.readline())
        except json.JSONDecodeError:
            raise ValueError(f"{file_name}: not a csvplus-tpu index file") from None
        if header.get("magic") != _MAGIC:
            raise ValueError(f"{file_name}: not a csvplus-tpu index file")
        if header.get("version") != _VERSION:
            raise ValueError(
                f"{file_name}: unsupported index version {header.get('version')}"
            )
        rows = [Row(json.loads(line)) for line in f if line.strip()]
    if len(rows) != header.get("count"):
        raise ValueError(
            f"{file_name}: truncated index file "
            f"({len(rows)} rows, expected {header.get('count')})"
        )
    return Index(IndexImpl(rows, header["columns"]))


def _load_columnar(file_name: str, device: "str | None" = None) -> Index:
    import zipfile

    import jax

    from .columnar.table import DeviceTable, StringColumn, default_device
    from .ops.join import DeviceIndex

    try:
        with np.load(file_name) as z:
            meta = json.loads(bytes(z["__meta__"]).decode("utf-8"))
            if meta.get("magic") != _MAGIC:
                raise ValueError(f"{file_name}: not a csvplus-tpu index file")
            if meta.get("version") not in (2, 3):
                raise ValueError(
                    f"{file_name}: unsupported columnar index version "
                    f"{meta.get('version')}"
                )
            dev = default_device(device)
            lane_columns = meta.get("lane_columns", {})
            cols = {}
            for name in meta["columns"]:
                codes = jax.device_put(z[f"c:{name}"], dev)
                if name in lane_columns:
                    # restore packed lanes straight to device: the host
                    # dictionary is never built (round-trip keeps the
                    # lane columns' bounded-RSS contract)
                    lanes = tuple(
                        jax.device_put(z[f"l{i}:{name}"], dev)
                        for i in range(int(lane_columns[name]))
                    )
                    cols[name] = StringColumn(None, codes, dev_dictionary=lanes)
                else:
                    cols[name] = StringColumn(z[f"d:{name}"], codes)
            count = meta["count"]
            key_columns = meta["key_columns"]
    except (KeyError, zipfile.BadZipFile, json.JSONDecodeError) as e:
        raise ValueError(f"{file_name}: not a csvplus-tpu index file") from e
    table = DeviceTable(cols, count, dev)
    return Index(IndexImpl(None, key_columns, dev=DeviceIndex.build(table, key_columns)))


def _validate_index_columns(columns: Sequence[str]) -> Tuple[str, ...]:
    columns = tuple(columns)
    if len(columns) == 0:
        raise ValueError("empty column list in CreateIndex()")
    if len(columns) > 1 and not all_columns_unique(columns):
        raise ValueError("duplicate column name(s) in CreateIndex()")
    return columns


def create_index(src, columns: Sequence[str], *, milestone: bool = True) -> Index:
    """Materialize and sort an index (csvplus.go:707-738).

    A device-planned source builds the index entirely on device: fused
    multi-key ``lax.sort`` over dictionary codes, no host rows.  That
    build is an ``index:build`` milestone (``obs/span.py``) unless the
    caller says it is no once-per-object work (*milestone* false: the
    storage tier's delta of every append, which would crowd the process
    journal).
    """
    columns = _validate_index_columns(columns)

    if getattr(src, "plan", None) is not None:
        from .columnar.exec import UnsupportedPlan

        build = _create_index_device if milestone else _build_index_device
        try:
            return build(src.plan, columns)
        except UnsupportedPlan:
            pass  # fall through to the host build

    rows: List[Row] = []

    def collect(row: Row) -> None:
        for col in columns:
            if col not in row:
                raise ValueError(f'missing column "{col}" while creating an index')
        rows.append(row)

    src(collect)

    impl = IndexImpl(rows, columns)
    impl.sort()
    return Index(impl)


def _create_index_device(plan, columns: Tuple[str, ...]) -> Index:
    """The device build, one ``index:build`` milestone (``obs/span.py``):
    in the process journal where no trace is open, the ``index:*`` stages
    (and a first build's ``typed:demote``) beneath it."""
    with tracer.milestone("index:build", keys=",".join(columns)) as at:
        index = _build_index_device(plan, columns)
        at["rows"] = len(index)
    return index


def _build_index_device(plan, columns: Tuple[str, ...]) -> Index:
    from .columnar.exec import execute_plan_view
    from .ops.join import DeviceIndex
    from .ops.sort import sort_table

    from .columnar.exec import first_missing_cell

    with telemetry.stage("index:view", 0) as st:
        view = execute_plan_view(plan)
        if view.deferred_error is not None:
            # index build consumes every row, so the host stream always
            # reaches the first row failing a terminal Validate
            raise view.deferred_error[1]
        if view.sel.shape[0] == 0:
            # the host build validates per-row (csvplus.go:722-733), so an
            # empty source yields an empty index without any column check
            return Index(IndexImpl([], columns))
        # the host build raises at the first streamed row lacking a key cell
        # (row-major, columns in argument order within the row), numbered by
        # the ORIGINATING source (reader record numbers / 0-based slice
        # positions) — first_missing_cell reproduces exactly that
        bad = first_missing_cell(view, columns)
        if bad is not None:
            raise DataSourceError(
                bad[0], f'missing column "{bad[1]}" while creating an index'
            )
        gathers = 0 if view.identity else len(view.cols)
        table = view.materialize()
        st.update(rows=table.nrows, rows_out=table.nrows, row_gathers=gathers)
        telemetry.barrier([c.storage for c in table.columns.values()])
    sorted_table = sort_table(table, list(columns))
    dev = DeviceIndex.build(sorted_table, list(columns))
    return Index(IndexImpl(None, columns, dev=dev))


def create_unique_index(src, columns: Sequence[str]) -> Index:
    """Index build + duplicate-key check (csvplus.go:740-756).

    On a device index the check is a single adjacent-equality reduction
    over the sorted key codes; only the offending row (if any) is decoded.
    """
    index = create_index(src, columns)
    impl = index._impl
    cols = impl.columns

    if impl.is_lazy and impl.dev is not None:
        from .ops.sort import find_adjacent_duplicate

        i = find_adjacent_duplicate(impl.dev.table, cols)
        if i is not None:
            row = impl.dev.table.to_rows(np.array([i], dtype=np.int64))[0]
            raise CsvPlusError(
                "duplicate value while creating unique index: "
                + str(row.select_existing(*cols))
            )
        impl.dev.unique = True  # a full-key probe hits at most the row at its lower bound
        return index

    rows = impl.rows
    for i in range(1, len(rows)):
        if equal_rows(cols, rows[i - 1], rows[i]):
            raise CsvPlusError(
                "duplicate value while creating unique index: "
                + str(rows[i].select_existing(*cols))
            )
    return index
