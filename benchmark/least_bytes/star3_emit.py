"""The least bytes the EMIT step of ``star3`` must move through HBM,
from shapes: a lower bound for ``kernel.join_emit_roofline_pct``, never
a count of what the program moved.

The emit step writes the dimension tables' lanes of the result: for
each order the matched person's and the matched stock item's cells.
Per dimension it reads one int32 row id per order, reads every row of
the dimension's emitted lanes once, and writes one int32 (a value lane
or a dictionary code) per order and emitted lane.  The fact table's own
lanes ride through and are not the emit step's; ``prod_id`` is the
fact's (the natural join keeps one).  Dictionaries are not counted."""

from __future__ import annotations

LANE_BYTES = 4


def least_bytes(cfg: dict, fact_rows: int) -> int:
    t = cfg["tables"]
    emitted = {"people": len(t["people"]["columns"]), "stock": len(t["stock"]["columns"]) - 1}
    total = 0
    for dim, lanes in emitted.items():
        ids = fact_rows
        table = int(t[dim]["rows"]) * lanes
        out = fact_rows * lanes
        total += LANE_BYTES * (ids + table + out)
    return total
