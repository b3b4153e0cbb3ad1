"""The least bytes ``dedup`` (IndexOn(id) -> ResolveDuplicates) must move
through HBM for one execution, from shapes: a lower bound for the
roofline share, never a count of what the program moved.

What every implementation must move, 4 bytes a cell (int32 value lanes
and int32 dictionary codes):

- every lane of the resident people table read once: ``columns`` x
  ``rows`` (the key to order and group by, the payload to carry);
- the deduplicated index written once: its ``columns`` lanes and its
  packed sorted key, ``(columns + 1)`` x ``distinct`` (one row per
  distinct id: ``rows`` x ``distinct_id`` / ``people.rows``).

The sort's passes and operands, the permutation, the sorted table before
compaction, its packed key, the run mask and the selection are what the
program adds to that.  Dictionaries are not counted."""

from __future__ import annotations

LANE_BYTES = 4


def least_bytes(cfg: dict, fact_rows: int) -> int:
    people = cfg["tables"]["people"]
    lanes = len(people["columns"])
    distinct = fact_rows * int(people["distinct_id"]) // int(people["rows"])
    return LANE_BYTES * (lanes * fact_rows + (lanes + 1) * distinct)
