"""The least bytes the LANE MOVES of ``dedup`` must move through HBM, from
shapes: a lower bound for ``kernel.dedup_gather_roofline_pct``, never a
count of what the program moved.

The pair ``kernel.dedup_gather_device_s`` / ``…roofline_pct`` times the
programs of one execution that move the payload lanes from the resident
table into the result: ``csvplus.table.gather_take`` (the index build's
permutation of the lanes), ``csvplus.dedup.compact`` (since PR 37 one
sort that carries every lane past the dropped rows) and
``csvplus.dedup.head`` (the cut to the kept rows).  This file names no
implementation: lanes that come to ride ``csvplus.index.sort`` (ROADMAP
S1b) are then ``kernel.index_sort_*``'s seconds, and the pair still has
the compaction to time.

What every implementation of the step must move, 4 bytes a cell (int32
value lanes and int32 dictionary codes): the result holds, for every
emitted lane, one cell per surviving row, and that cell lives in the
resident table, so

- each emitted lane's survivors read once: ``columns`` x ``distinct``;
- each emitted lane's survivors written once: ``columns`` x ``distinct``

(``distinct`` = ``rows`` x ``distinct_id`` / ``people.rows``: one row per
distinct id).  The positions the program reads (the permutation, the
selection), the dropped rows' cells, and the second pass over the
payload lanes (sorted table first, compacted table after) are what the
program adds.  Dictionaries are not counted."""

from __future__ import annotations

LANE_BYTES = 4


def least_bytes(cfg: dict, fact_rows: int) -> int:
    people = cfg["tables"]["people"]
    lanes = len(people["columns"])
    distinct = fact_rows * int(people["distinct_id"]) // int(people["rows"])
    return LANE_BYTES * 2 * lanes * distinct
