"""Device index build: multi-key sort over dictionary codes.

The reference builds an index by materializing all rows and running a
comparison sort with a per-comparison multi-column string compare
(csvplus.go:722-736, 794-807).  On device the same ordering comes out of
one fused ``lax.sort`` over the key columns' **dictionary codes**: each
dictionary is sorted, so integer code order == byte-lexicographic string
order, and ``lax.sort`` with ``num_keys=k`` sorts lexicographically by
(col0, col1, ..., colk) exactly like the reference's left-to-right
compare.  ``is_stable=True`` refines the reference's unstable sort into a
deterministic order that matches the host executor's stable sort, so
differential tests can require exact equality.

A trailing iota operand rides along as the permutation, used to gather
every non-key column once after the sort.

Duplicate resolution by a named policy (:func:`compact_runs`) stays on
the device as well: one program marks the row each equal-key run keeps
and counts them, and one sort keyed on (drop flag, row number) moves
the kept rows of EVERY column to the front in their order — the lanes
ride the sort as operands, nothing is gathered (on a v5e a sort carries
a 50M-row int32 lane in a tenth of the time XLA's gather takes to move
it; ``PERF.md`` §6, PR 37).  The host reads ONE scalar, the count,
which the result's shape needs.

Stages: ``index:sort`` / ``index:permute`` (:func:`sort_table`),
``dedup:runs`` / ``dedup:compact`` (:func:`compact_runs`).  Programs:
``jit_csvplus.index.sort``, ``.index.adjacent_dup``, ``.dedup.runs``,
``.dedup.compact``, ``.dedup.head``; the permutation's lane gathers are
``.table.gather_take``.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..columnar.table import DeviceTable
from ..obs.recompile import register_kernel
from ..utils.env import env_int
from ..utils.observe import telemetry


@register_kernel("index.sort", static_argnames=("num_keys",))
def _sort_kernel(operands: Tuple[jax.Array, ...], num_keys: int):
    """Stable lexicographic sort; last operand is the row permutation."""
    return jax.lax.sort(operands, num_keys=num_keys, is_stable=True)


# Mesh-sharded tables at or above this row count sort through the
# distributed sample-sort (parallel/dsort.py) instead of the replicated
# lax.sort, which lands the whole array on every chip.
DSORT_MIN_ROWS = env_int("CSVPLUS_DSORT_MIN_ROWS", 1_000_000)


def _sharded_mesh(key_cols) -> "Optional[object]":
    """The named mesh the key codes are row-sharded over, or None when
    any column is unsharded / opaque-sharded / single-device."""
    mesh = None
    for c in key_cols:
        sh = getattr(c.codes, "sharding", None)
        m = getattr(sh, "mesh", None)
        if m is None or len(sh.device_set) <= 1:
            return None
        if mesh is None:
            mesh = m
        elif m is not mesh and m != mesh:
            # same device count over DIFFERENT meshes (devices, shape or
            # axis names) would run the sample-sort with the wrong
            # placement (ADVICE r3); Mesh.__eq__ covers all three
            return None
    return mesh


def _packed_sort_lanes(key_cols) -> "Optional[Tuple[jax.Array, ...]]":
    """Key columns packed into sample-sort lanes, mirroring the join's
    key tiers (ops/join.py): one int32 lane up to 31 packed bits, dual
    nonnegative 31-bit (hi, lo) lanes up to 62, None beyond.  Because
    each dictionary is sorted, packed order == the multi-column
    lexicographic code order the replicated sort produces."""
    from .join import _bits_for, _pack_qk_kernel, pack_lanes

    bits = [_bits_for(c.dict_size) for c in key_cols]
    total = sum(bits)
    if total > 62:
        return None
    shifts = []
    acc = 0
    for b in reversed(bits):
        shifts.insert(0, acc)
        acc += b
    if total <= 31:
        # fused pack (codes are nonnegative, so the kernel's miss
        # masking is the identity) instead of an eager per-column loop
        lane = _pack_qk_kernel(
            tuple(c.codes for c in key_cols), tuple(shifts)
        )
        return (lane,)
    hi, lo = pack_lanes([c.codes for c in key_cols], shifts, bits)
    return (hi, lo)


def sort_table(table: DeviceTable, key_columns: Sequence[str]) -> DeviceTable:
    """Return a new table with rows sorted by the key columns.

    Mesh-sharded tables of at least :data:`DSORT_MIN_ROWS` rows route
    through the distributed sample-sort — per-shard sorts plus ONE
    all_to_all exchange — instead of the replicated ``lax.sort``
    (SURVEY §2 "index build (distributed)"; the semantics anchor is the
    reference's whole-dataset sort, csvplus.go:722-736)."""
    key_cols = [table.columns[c] for c in key_columns]
    for c in key_cols:
        # sorting BY a column requires code order == value order; a
        # deferred-union lane dictionary settles here (no-op otherwise)
        c._ensure_sorted_lanes()
    if table.nrows >= DSORT_MIN_ROWS:
        mesh = _sharded_mesh(key_cols)
        # packed lanes require real codes in every key cell; the index
        # build has already validated that (first_missing_cell), other
        # callers fall back when absent cells exist
        if mesh is not None and not any(c.has_absent for c in key_cols):
            lanes = _packed_sort_lanes(key_cols)
            if lanes is not None:
                from ..parallel.dsort import distributed_sort_device

                with telemetry.stage("index:sort", table.nrows) as st:
                    st.update(rows=table.nrows, keys=len(key_cols), tier="dsort", row_gathers=0)
                    with telemetry.stage("dsort", table.nrows):
                        iota = jnp.arange(table.nrows, dtype=jnp.int32)
                        _, perm = distributed_sort_device(mesh, lanes, iota)
                    telemetry.barrier(perm)
                return _permuted(table, perm, {})

    with telemetry.stage("index:sort", table.nrows) as st:
        st.update(rows=table.nrows, keys=len(key_cols), tier="lax", row_gathers=0)
        iota = jnp.arange(table.nrows, dtype=jnp.int32)
        operands = tuple(c.codes for c in key_cols) + (iota,)
        sorted_ops = telemetry.barrier(_sort_kernel(operands, num_keys=len(key_cols)))
    # key columns come out of the sort already permuted
    sorted_keys = dict(zip(key_columns, sorted_ops[: len(key_cols)]))
    return _permuted(table, sorted_ops[-1], sorted_keys)


def _permuted(table: DeviceTable, perm: jax.Array, sorted_keys: dict) -> DeviceTable:
    """*table*'s rows in the order *perm* gives: one full-length gather
    per column but those whose sorted codes the sort already returned."""
    with telemetry.stage("index:permute", table.nrows) as st:
        out = {
            name: col.with_codes(sorted_keys[name]) if name in sorted_keys else col.gather(perm)
            for name, col in table.columns.items()
        }
        st.update(rows=table.nrows, row_gathers=len(out) - len(sorted_keys))
        telemetry.barrier([c.storage for c in out.values()])
    return DeviceTable(out, table.nrows, table.device)


@register_kernel("index.adjacent_dup")
def _adjacent_dup_kernel(*key_codes: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """(any_dup, first_dup_index) over sorted key columns.

    A row i>0 is a duplicate when every key column equals row i-1 — the
    columnar form of the reference's adjacent scan (csvplus.go:749-753).
    """
    eq = None
    for k in key_codes:
        e = k[1:] == k[:-1]
        eq = e if eq is None else (eq & e)
    any_dup = jnp.any(eq)
    first = jnp.argmax(eq) + 1  # row index of the duplicate row
    return any_dup, first


def find_adjacent_duplicate(
    table: DeviceTable, key_columns: Sequence[str]
) -> "int | None":
    """Index of the first row whose key equals the previous row's, or None."""
    if table.nrows < 2:
        return None
    codes = tuple(table.columns[c].codes for c in key_columns)
    any_dup, first = _adjacent_dup_kernel(*codes)
    if bool(any_dup):
        return int(first)
    return None


@register_kernel("dedup.runs", static_argnames=("policy",))
def _keep_mask_kernel(*key_codes: jax.Array, policy: str) -> Tuple[jax.Array, jax.Array]:
    """(keep mask, kept count) over sorted key columns: of every
    equal-key run the first row (``policy="first"``: the row that starts
    the run) or the last (``"last"``: the row the next run follows)."""
    n = key_codes[0].shape[0]
    neq = jnp.zeros(n - 1, dtype=bool)
    for k in key_codes:
        neq = neq | (k[1:] != k[:-1])
    edge = jnp.ones(1, dtype=bool)
    keep = jnp.concatenate([edge, neq] if policy == "first" else [neq, edge])
    return keep, jnp.sum(keep, dtype=jnp.int32)


@register_kernel("dedup.compact")
def _compact_kernel(keep: jax.Array, lanes: Tuple[jax.Array, ...]) -> Tuple[jax.Array, ...]:
    """*lanes* with the rows *keep* marks first, in their order, and the
    others behind them: ONE sort, every lane an operand.  The key is the
    row number with the drop flag in its top bit — what a stable sort on
    the flag compares, as one uint32 with no tie (a stable ``lax.sort``
    carries a hidden iota operand for its ties: one lane more to move,
    twice the compile; ``PERF.md`` §6, PR 37)."""
    n = keep.shape[0]  # a row number is an int32 everywhere: n < 2**31
    order = jnp.arange(n, dtype=jnp.uint32) | ((~keep).astype(jnp.uint32) << 31)
    return tuple(jax.lax.sort((order,) + tuple(lanes), num_keys=1, is_stable=False)[1:])


@register_kernel("dedup.head", static_argnames=("kept",))
def _head_kernel(lanes: Tuple[jax.Array, ...], kept: int) -> Tuple[jax.Array, ...]:  # analysis: allow[JIT001] retrace is per table width, not per data length
    """The first *kept* rows of every lane.  A program of its own so
    that the sort compiles once per row count, not per kept count."""
    return tuple(lane[:kept] for lane in lanes)


def compact_runs(
    table: DeviceTable, key_columns: Sequence[str], policy: str
) -> "Optional[DeviceTable]":
    """*table* (sorted by *key_columns*) with one row kept of every
    equal-key run, the ``"first"`` or the ``"last"``; None when no key
    repeats (nothing is sorted).  Every column's row-indexed storage is
    one int32 lane (dictionary codes or typed values; a dictionary is
    not row-indexed and stays where it is), and all of them ride one
    sort on (drop flag, row number).
    Every row-proportional array stays on the device: the host reads one
    scalar, the kept rows' count, which the result's shape needs
    (``telemetry.host_sync_elements``)."""
    n = table.nrows
    if n < 2:
        return None
    codes = tuple(table.columns[c].codes for c in key_columns)
    with telemetry.stage("dedup:runs", n) as st:
        keep, count = telemetry.barrier(_keep_mask_kernel(*codes, policy=policy))
        kept = int(count)  # the one host read
        telemetry.count_sync(1)
        st.update(rows=n, rows_out=kept, row_gathers=0, host_sync_elements=1)
    if kept == n:
        return None
    with telemetry.stage("dedup:compact", n) as st:
        # a string column's (codes, settled flag) pair is ONE snapshot: a
        # sibling may settle the shared lane dictionary meanwhile
        states = [getattr(c, "_codes_state", (c.storage, None)) for c in table.columns.values()]
        # the result's length IS the kept count: whatever slices the lanes
        # lowers once per count; a slice compiles in milliseconds, and the
        # sort, which does not, never sees the count
        moved = _compact_kernel(keep, tuple(lane for lane, _ in states))
        lanes = _head_kernel(moved, kept=kept)  # analysis: allow[RETRACE002]
        st.update(
            rows=n, rows_out=kept, policy=policy, kept=kept, tier="device",
            row_gathers=0, form="sort", lanes=len(lanes),
        )
        telemetry.barrier(lanes)
    out = {
        name: col.with_storage(lane) if flag is None else col.with_codes(lane, flag)
        for (name, col), (_, flag), lane in zip(table.columns.items(), states, lanes)
    }
    return DeviceTable(out, kept, table.device)


def run_starts(table: DeviceTable, key_columns: Sequence[str]):
    """Host bool array marking the first row of each equal-key run, for
    the CALLBACK resolver, which groups and selects on the host: the
    mask is formed on the device and read to the host whole, one bool a
    row (counted in ``telemetry.host_sync_elements``).  The named
    policies never come here (:func:`compact_runs`)."""
    import numpy as np

    if table.nrows == 0:
        return np.zeros(0, dtype=bool)
    if table.nrows == 1:
        return np.ones(1, dtype=bool)
    codes = tuple(table.columns[c].codes for c in key_columns)
    mask, _ = _keep_mask_kernel(*codes, policy="first")
    telemetry.barrier(mask)
    telemetry.count_sync(table.nrows)
    return np.asarray(mask)
