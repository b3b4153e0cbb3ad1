"""The least bytes ONE CHIP must move through its HBM for the PROBE of
``lookupjoin`` (the ``csvplus.pjoin.`` programs), from shapes: a lower
bound for ``kernel.pjoin_probe_roofline_pct``.

A chip reads its share of the packed probe keys (one int32 per order),
writes ``(lower, count)`` for them (two int32 per order) and reads its
slice of the index's unique keys once.  The slot buffers, the routing
ranks and the exchange itself are what the program adds to that."""

from __future__ import annotations

LANE_BYTES = 4


def least_bytes(cfg: dict, fact_rows: int) -> int:
    build_keys = int(cfg["tables"]["people"]["rows"])
    return LANE_BYTES * (3 * fact_rows + build_keys) // int(cfg["chips"])
