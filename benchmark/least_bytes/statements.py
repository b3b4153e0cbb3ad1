"""The least bytes ``statements`` must move through HBM, from shapes: a
lower bound for ``device.bytes_roofline_pct``, never a count of what the
program moved.  4 bytes a cell (int32 value lanes and int32 dictionary
codes); the dictionaries themselves are not counted.

What every implementation of ``people.Join(orders.IndexOn("cust_id"),
"id").Join(stock.UniqueIndexOn("prod_id"))`` must move:

- every lane of the three resident tables read once: orders ``columns``
  x ``fact_rows`` (its key among them: no answer without it), people
  ``columns`` x ``rows``, stock ``columns`` x ``rows``;
- every lane of the result written once: one row per order (every order
  names one person and one stock item), the lanes of all three tables
  with ``prod_id`` once.

The index's sorted copy, the probe's answers, the expansion's ids and the
first join's 10M-row intermediate are what the program adds."""

from __future__ import annotations

LANE_BYTES = 4


def least_bytes(cfg: dict, fact_rows: int) -> int:
    t = cfg["tables"]
    lanes = {k: len(t[k]["columns"]) for k in ("orders", "people", "stock")}
    read = fact_rows * lanes["orders"] + sum(int(t[k]["rows"]) * lanes[k] for k in ("people", "stock"))
    out = fact_rows * (sum(lanes.values()) - 1)  # prod_id once
    return LANE_BYTES * (read + out)
