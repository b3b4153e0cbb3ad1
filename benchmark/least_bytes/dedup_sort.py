"""The least bytes the SORT of ``dedup``'s index build (the
``csvplus.index.sort`` program) must move through HBM, from shapes: a
lower bound for ``kernel.index_sort_roofline_pct``, never a count of
what the program moved.

What every implementation of the step must move, 4 bytes a row:

- the key lane (the ids' dictionary codes) read once;
- the sorted key lane written once (the index keeps its key column in
  order);
- the permutation written once (the other columns follow it).

The row numbers the program feeds in as a fourth lane (its ``iota``
operand) can be formed in registers and are no term of a LEAST count; a
comparison sort's further passes over its operands are what the program
adds."""

from __future__ import annotations

LANE_BYTES = 4


def least_bytes(cfg: dict, fact_rows: int) -> int:
    return LANE_BYTES * 3 * fact_rows
