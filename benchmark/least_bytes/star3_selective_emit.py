"""The least bytes the EMIT step of ``star3_selective`` (the
``csvplus.join.gather*`` programs of one execution) must move through
HBM, from shapes: a lower bound for ``kernel.join_emit_roofline_pct.sel``,
never a count of what the program moved.

Where not every order survives, the fact table's own lanes do not ride
through: every lane of the result is gathered.  So, 4 bytes a cell
(int32 value lanes and int32 dictionary codes), with ``survivors`` =
``rows`` x ``segment_rows`` / ``orders.rows``:

- each of the nine result lanes' survivors read once: 9 x ``survivors``;
- each of the nine result lanes' survivors written once: 9 x ``survivors``.

The row ids the program reads (the compaction's answer) and the cells a
gather touches beside the survivors' are what the program adds.
Dictionaries are not counted."""

from __future__ import annotations

LANE_BYTES = 4


def least_bytes(cfg: dict, fact_rows: int) -> int:
    t = cfg["tables"]
    lanes = sum(len(t[k]["columns"]) for k in ("orders", "people", "stock")) - 1  # prod_id once
    survivors = fact_rows * int(t["orders"]["segment_rows"]) // int(t["orders"]["rows"])
    return LANE_BYTES * 2 * lanes * survivors
