"""The least bytes the EMIT step of ``statements`` must move through
HBM, from shapes: a lower bound for ``kernel.join_emit_roofline_pct`` in
``statements-fanout-resident``, never a count of what the program moved.

The metric's selector covers ``csvplus.join.gather*``, ``…expand*`` and
``…multiway*``, so in this cell it times the fan-out expansion with the
gathers: the step that turns the probe's answers into the result's rows.
What EVERY implementation of that step must move, 4 bytes a cell (int32
value lanes and int32 dictionary codes):

- the 100,000 probes' answers ``(lower, count)`` read once: 2 x
  ``people.rows`` (without them no implementation knows which index rows
  a customer's are);
- every source lane read once: orders' ``columns`` x ``fact_rows`` (the
  index's sorted copy), people's ``columns`` x ``rows``, stock's emitted
  lanes (``columns`` - 1: the stream's ``prod_id`` wins) x ``rows``;
- every result lane written once: one row per order, the lanes of all
  three tables with ``prod_id`` once.

There is NO term for materialised ids: today's program forms
``probe_ids`` and ``build_ids`` at the padded length (2 x 16,777,216)
and the second join's row ids, but a run copy and a segment broadcast
need none of them (ROADMAP S15b/S15d), so they are what the program
adds — a share that counted them would read over 100% the day they go.
For the same reason the expansion alone (``kernel.join_expand_device_s``)
has seconds and no roofline share: an implementation may not run it at
all.  Dictionaries are not counted."""

from __future__ import annotations

LANE_BYTES = 4


def least_bytes(cfg: dict, fact_rows: int) -> int:
    t = cfg["tables"]
    lanes = {k: len(t[k]["columns"]) for k in ("orders", "people", "stock")}
    answers = 2 * int(t["people"]["rows"])
    read = (
        fact_rows * lanes["orders"]
        + int(t["people"]["rows"]) * lanes["people"]
        + int(t["stock"]["rows"]) * (lanes["stock"] - 1)
    )
    out = fact_rows * (sum(lanes.values()) - 1)
    return LANE_BYTES * (answers + read + out)
