"""The least bytes the COMPACTION step of ``star3_selective`` (the
``csvplus.join.compact_partial`` program of one execution) must move
through HBM, from shapes: a lower bound for
``kernel.join_compact_roofline_pct.sel``, never a count of what the
program moved, and the same whatever form implements the step (a prefix
sum and a scatter, a sort on a flagged row number, a butterfly).

The step is handed, per dimension, the probe's answer as two int32 lanes
over the fact rows (the build row and the match count), and hands on the
surviving orders' row ids and, per dimension, their build rows, each
padded to the next power of two of the survivors' count (the shape the
program compiles for).  4 bytes a cell:

- each dimension's match flag (its count lane) read once, every row:
  ``dims`` x ``rows``;
- the padded row ids written once: ``padded``;
- each dimension's padded build rows written once: ``dims`` x ``padded``.

The survivors' build rows are read too (``dims`` x ``survivors``); left
out, so the share reads low, not high."""

from __future__ import annotations

LANE_BYTES = 4
DIMS = 2  # people, stock


def least_bytes(cfg: dict, fact_rows: int) -> int:
    orders = cfg["tables"]["orders"]
    kept = fact_rows * int(orders["segment_rows"]) // int(orders["rows"])
    padded = 1 << max(kept - 1, 0).bit_length()
    return LANE_BYTES * (DIMS * fact_rows + (1 + DIMS) * padded)
