"""The comparisons that decide ``correct``.  Copied from
``chip_smoke.py`` (PR 21): results are read back on the host and held
against values computed from the generator's own numpy arrays, outside
any timed span.  Every comparison here is exact: its limit is 0
differing cells.

Nothing in this file imports the program.  It reads a result's columns
through the attributes a caller of the library sees (``kind``,
``prefix``, ``values``, ``codes``, ``dictionary``, ``storage``).
"""

from __future__ import annotations

import numpy as np


class Mismatch(Exception):
    """A comparison differed or an invariant of the run did not hold."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise Mismatch(what)


def col_ints(col, n: int, prefix: bytes) -> np.ndarray:
    """First *n* rows of *col* as ints, given every cell is
    ``prefix + decimal``; works for a typed lane or a dictionary column."""
    if getattr(col, "kind", "str") == "int":
        check(col.prefix == prefix, f"typed prefix {col.prefix!r} != {prefix!r}")
        return np.asarray(col.values)[:n]
    d = np.asarray(col.dictionary)
    w, p = d.dtype.itemsize, len(prefix)
    mat = np.frombuffer(d.tobytes(), dtype=np.uint8).reshape(d.shape[0], w)
    check(bool((mat[:, :p] == np.frombuffer(prefix, np.uint8)).all()), "prefix differs")
    vals = np.ascontiguousarray(mat[:, p:]).view(f"S{w - p}").reshape(-1).astype(np.int64)
    return vals[np.asarray(col.codes)[:n]]


def col_bytes(col, n: int) -> np.ndarray:
    """First *n* rows of a dictionary column as an 'S' array."""
    return np.asarray(col.dictionary)[np.asarray(col.codes)[:n]]


def expect_columns(table, n: int, want: dict, what: str) -> None:
    """Every column of *table* equals the reference on all *n* rows.
    *want*: name -> (prefix, int array) or an 'S' array."""
    check(table.nrows == n, f"{what}: {table.nrows} rows, expected {n}")
    check(sorted(table.columns) == sorted(want), f"{what}: columns {sorted(table.columns)}")
    for name, w in want.items():
        col = table.columns[name]
        if isinstance(w, tuple):
            ok = np.array_equal(col_ints(col, n, w[0]), w[1])
        else:
            ok = np.array_equal(col_bytes(col, n), w)
        check(ok, f"{what}: column {name!r} differs from the generator's arrays")


def column_kinds(table) -> dict:
    return {
        name: "int-lane" if getattr(c, "kind", "str") == "int" else "dictionary"
        for name, c in table.columns.items()
    }


def placed_on(table, platform: str, what: str, n_devices=None) -> None:
    """Every column's storage sits on devices of *platform* (exactly
    *n_devices* of them when given): nothing quietly stayed on, or fell
    back to, the host."""
    for name, c in table.columns.items():
        devs = c.storage.sharding.device_set
        check(
            all(d.platform == platform for d in devs)
            and n_devices in (None, len(devs)),
            f"{what}: column {name!r} sits on {sorted(str(d) for d in devs)}, "
            f"expected {n_devices or 'only'} {platform} device(s)",
        )


class TableDigest:
    """A positional digest of a result table, computed on the device by
    a program of the benchmark's own: per column, the wrapping uint32
    sum of ``storage[i] * (2*i + 1)`` and of ``storage[i]``.  Two tables
    whose storage lanes hold the same values in the same order have the
    same digest; a swapped pair or a changed cell changes it.  It holds
    every execution of a window against the one execution that is read
    back and compared with the generator in full; a dictionary column's
    dictionary is compared on the host beside its digest."""

    _one = None  # the jitted digest of one lane, built on first use

    def __init__(self):
        if TableDigest._one is None:
            import jax
            import jax.numpy as jnp

            def lane_digest(x):
                x = x.astype(jnp.uint32)
                w = jnp.arange(x.shape[0], dtype=jnp.uint32) * jnp.uint32(2) + jnp.uint32(1)
                return jnp.stack(
                    [jnp.sum(x * w, dtype=jnp.uint32), jnp.sum(x, dtype=jnp.uint32)]
                )

            TableDigest._one = staticmethod(jax.jit(lane_digest))

    def __call__(self, table) -> dict:
        """name -> (uint32[2] as a tuple, dictionary or None); blocks
        until the device has the sums."""
        out = {}
        for name in sorted(table.columns):
            col = table.columns[name]
            typed = getattr(col, "kind", "str") == "int"
            sums = np.asarray(self._one(col.storage))
            out[name] = (
                (int(sums[0]), int(sums[1]), table.nrows),
                None if typed else np.asarray(col.dictionary),
            )
        return out

    @staticmethod
    def same(a: dict, b: dict) -> bool:
        if sorted(a) != sorted(b):
            return False
        for name, (sums, dic) in a.items():
            osums, odic = b[name]
            if sums != osums or (dic is None) != (odic is None):
                return False
            if dic is not None and dic is not odic and not np.array_equal(dic, odic):
                return False
        return True
