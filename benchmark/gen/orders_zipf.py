"""Upstream's corpus with a SKEWED ``orders.cust_id``: Zipf(s) over ranks
1..people, rank -> customer by a seeded permutation, so the heavy
customers scatter over the id space (this project's own skew deployment:
CHANGES.md PR 15, ``git show 5b1e8dd:bench.py`` ``zipf_fact_table``).
Everything else is ``gen/orders.py``'s, which this file imports and does
not repeat: people, stock, ``prod_id``, ``qty``, ``ts``, the writers.

What is the configuration's and what is the seed's, as in
``gen/orders.py`` and extended to what the program's hot-key detection
reads:

- how many orders each rank places is no draw at all: the expected
  Zipf counts, apportioned by rounding their running sum
  (``rank_counts``: they add up to the row count exactly, each within
  one of its expectation);
- a skeleton drawn from the fixed ``layout_seed`` says which rows hold
  which rank and how many decimal digits each rank's customer number
  has: every row's byte length, the file's size, the ingest's chunk
  cuts, the distinct count of ``cust_id`` and the multiset of counts in
  ANY positional sample of the column (the program's strided one among
  them) are the same for every seed;
- the seed chooses, within a digit class, which customer number gets
  which rank (and, as in ``gen/orders.py``, every other value).

The reference's arrays (``cust``, ``row_of``, ...) keep their names, so
``queries/lookupjoin.py:want`` answers from them unchanged; nothing here
imports the engine under test.
"""

from __future__ import annotations

import numpy as np

from gen import orders as base

RANK_STREAM = 10  # gen/orders.py uses streams 0-3 and 9


def rank_counts(rows: int, ranks: int, s: float) -> np.ndarray:
    """int64[ranks]: the orders rank r+1 places among *rows*, for
    Zipf(*s*) over ranks 1..*ranks*: the differences of the rounded
    running sum of the expected counts."""
    cum = np.cumsum(np.arange(1, ranks + 1, dtype=np.float64) ** -s)
    edges = np.rint(cum * (rows / cum[-1])).astype(np.int64)
    edges[-1] = rows
    return np.diff(edges, prepend=0)


class Data(base.Data):
    """``gen/orders.py``'s deployment with ``cust`` redrawn; also
    ``rank_rows`` (orders per rank), ``rank_of_row`` and
    ``customer_of_rank`` (both 0-based: rank 1 is index 0)."""

    def _orders(self, orders: dict, n: int, seed: int, streams) -> None:
        super()._orders(orders, n, seed, streams)  # its uniform cust is replaced below
        sk, rng = streams(RANK_STREAM)
        self.rank_rows = rank_counts(n, self.n_people, float(orders["cust_id_zipf_s"]))
        self.rank_of_row = np.repeat(np.arange(self.n_people, dtype=np.int32), self.rank_rows)
        sk.shuffle(self.rank_of_row)
        self.customer_of_rank = base._permutation_keeping_lengths(self.n_people, sk, rng)
        self.cust = self.customer_of_rank[self.rank_of_row]
