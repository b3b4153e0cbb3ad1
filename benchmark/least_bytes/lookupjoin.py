"""The least bytes ONE CHIP must move through its HBM for one execution
of ``lookupjoin`` on the configuration's mesh, from shapes: a lower
bound for the roofline share, never a count of what the program moved.

A four-chip cell's readers divide by device seconds averaged over the
device planes, so a ``least_bytes`` file of such a cell gives one
chip's share: a quarter of the whole.  The whole: every input lane of
orders read once and every output lane written once, 4 bytes a row
(int32 value lanes and int32 dictionary codes), and each of people's
lanes read once; dictionaries and the exchange's slot buffers are not
counted."""

from __future__ import annotations

LANE_BYTES = 4


def least_bytes(cfg: dict, fact_rows: int) -> int:
    t = cfg["tables"]
    fact_in = len(t["orders"]["columns"])
    out = fact_in + len(t["people"]["columns"])
    people = int(t["people"]["rows"]) * len(t["people"]["columns"])
    return LANE_BYTES * (fact_rows * (fact_in + out) + people) // int(cfg["chips"])
