"""The least bytes ``star3`` must move through HBM, from shapes: a lower
bound for the roofline share, never a count of what the program moved.
Every input lane is read once and every output lane written once, 4
bytes a row (int32 value lanes and int32 dictionary codes); the
dictionaries themselves are not counted."""

from __future__ import annotations

LANE_BYTES = 4


def least_bytes(cfg: dict, fact_rows: int) -> int:
    """orders join people join stock, every probe matching: the fact
    table's lanes in, the fact's and both dimensions' lanes out (one row
    per order, ``prod_id`` once), and each dimension table read once."""
    t = cfg["tables"]
    fact_in = len(t["orders"]["columns"])
    out = fact_in + len(t["people"]["columns"]) + len(t["stock"]["columns"]) - 1
    dims = sum(int(t[k]["rows"]) * len(t[k]["columns"]) for k in ("people", "stock"))
    return LANE_BYTES * (fact_rows * (fact_in + out) + dims)
