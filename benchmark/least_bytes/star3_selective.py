"""The least bytes ``star3_selective`` must move through HBM, from
shapes: a lower bound for ``device.bytes_roofline_pct.sel``, never a
count of what the program moved.  4 bytes a cell (int32 value lanes and
int32 dictionary codes); dictionaries are not counted.

- the two key lanes of the fact table (``cust_id``, ``prod_id``) read
  once, every row: no implementation knows which orders survive without
  them: 2 x ``rows``;
- the nine lanes of the result written once, one cell per surviving
  order: 9 x ``survivors`` (``survivors`` = ``rows`` x ``segment_rows`` /
  ``orders.rows``).

The survivors' cells of the other lanes, the dimension tables and the
indexes are read too; they are left out, so the share reads low, not
high."""

from __future__ import annotations

LANE_BYTES = 4
KEY_LANES = 2  # cust_id, prod_id


def least_bytes(cfg: dict, fact_rows: int) -> int:
    t = cfg["tables"]
    lanes = sum(len(t[k]["columns"]) for k in ("orders", "people", "stock")) - 1  # prod_id once
    survivors = fact_rows * int(t["orders"]["segment_rows"]) // int(t["orders"]["rows"])
    return LANE_BYTES * (KEY_LANES * fact_rows + lanes * survivors)
