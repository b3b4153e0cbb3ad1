"""Upstream's corpus with a SELECTIVE ``orders.cust_id``: exactly
``orders.segment_rows`` of the orders name a customer of one segment —
the people whom the README's first predicate keeps
(``Like{"name": "Amelia"}``) — and every other order a customer outside
it, each uniform within its side.  An index built over the segment
alone then answers one probe in ten, and upstream's inner ``Join``
(csvplus.go:552-568) drops the rest.  Everything else is
``gen/orders.py``'s, which this file imports and does not repeat:
people, stock, ``prod_id``, ``qty``, ``ts``, the writers.

What is the configuration's and what is the seed's, as in
``gen/orders.py``:

- a skeleton drawn from the fixed ``layout_seed`` says which rows are
  the segment's, and every row keeps the count of decimal digits that
  ``gen/orders.py`` deals it at the same ``layout_seed``: every row's
  byte length, the file's size, the ingest's chunk cuts, every
  dictionary's size AND the join's result length are the same for every
  seed, and the file has ``orders-star-10m``'s bytes-per-row layout;
- the seed chooses, within a digit class and a side, which customer
  each row names (every customer occurring, as in ``gen/orders.py``),
  and, as there, every other value.

Upstream's names go by the row's number with period 10, so a digit
class holds the segment's customers in the segment's own share and the
draw is uniform over each side.  The reference's arrays (``cust``,
``row_of``, ...) keep their names; nothing here imports the engine under
test.
"""

from __future__ import annotations

import numpy as np

from gen import orders as base

SEGMENT_STREAM = 11  # gen/orders.py uses streams 0-3 and 9, gen/orders_zipf.py 10


class Data(base.Data):
    """``gen/orders.py``'s deployment with ``cust`` redrawn; also
    ``segment_rows`` (the orders that name a customer of the segment:
    the join's result length) and ``in_segment`` (bool per order)."""

    def _orders(self, orders: dict, n: int, seed: int, streams) -> None:
        super()._orders(orders, n, seed, streams)  # its uniform cust gives each row its digit count
        sk, rng = streams(SEGMENT_STREAM)
        name = orders["segment"]["name"].encode()
        # by customer number, whatever row of people holds the customer
        member = np.zeros(self.n_people, dtype=bool)
        member[self.people_id[self.people_name(np.arange(self.n_people)) == name]] = True
        self.segment_rows = int(orders["segment_rows"]) * n // int(orders["rows"])
        self.in_segment = np.zeros(n, dtype=bool)
        self.in_segment[: self.segment_rows] = True
        sk.shuffle(self.in_segment)
        digits = base._ndigits(self.cust.astype(np.int64), len(str(self.n_people)))
        for k, a, b in base._classes(0, self.n_people):
            for side in (True, False):
                rows = np.flatnonzero((digits == k) & (self.in_segment == side))
                pool = np.arange(a, b, dtype=np.int32)[member[a:b] == side]
                if rows.size and not pool.size:
                    raise ValueError(
                        f"no customer of {k} digits {'in' if side else 'outside'} the segment: "
                        "a row cannot keep its length"
                    )
                drawn = pool[rng.integers(0, max(pool.size, 1), rows.size)]
                if pool.size <= rows.size:  # every customer occurs, as gen/orders.py has it
                    drawn[rng.choice(rows.size, pool.size, replace=False)] = pool
                self.cust[rows] = drawn
