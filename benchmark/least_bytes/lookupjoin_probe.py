"""The least bytes ONE CHIP must move through its HBM for the PROBE of
``lookupjoin`` (the ``csvplus.pjoin.`` programs), from shapes: a lower
bound for ``kernel.pjoin_probe_roofline_pct``, never a count of what the
program moved.

What every implementation must move on a chip, and nothing else:

- its share of the packed probe keys, read once: one int32 per order;
- its share of the answers, written once: ``(lower, count)``, two int32
  per order;
- of the index only what an answer cannot be formed without.  The
  configuration's index is unique over ids that are a permutation of
  ``0..rows-1``, so a slice of its sorted unique keys is one dense run
  and a key's answer is its position in that run: ``SLICE_BOUNDS`` int32
  a chip (the slice's first key and its length).  A read of the key
  lane (``build_keys / chips`` int32) is no term: a positional owner
  never makes one, so a count holding it is no floor.

The slot buffers, the routing ranks, the answer tables and the exchange
itself are what the program adds to that."""

from __future__ import annotations

LANE_BYTES = 4
SLICE_BOUNDS = 2


def least_bytes(cfg: dict, fact_rows: int) -> int:
    return LANE_BYTES * (3 * fact_rows // int(cfg["chips"]) + SLICE_BOUNDS)
