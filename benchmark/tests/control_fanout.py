"""The control of ``statements-fanout-resident``, at the cell's own size,
on the chip:

    python benchmark/tests/control_fanout.py --workload statements-fanout-resident --seed <n> --seconds <s>

runs ``run.py``'s whole path with the ORDER guarantee broken for one pair
of orders: in the result of every execution two orders of one customer
that lie side by side (the first such pair whose timestamps differ) stand
in each other's rows, all nine columns.  Every order is still there, with
its own customer and product cells, and the result keeps its length: only
a comparison that holds the order inside a customer's group to the
source file's shows it.  It exits 0 only when the run reported
``correct: false``.  The benchmark's own runs never run it.
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), HERE]

import run  # noqa: E402
from control import Tee  # noqa: E402


def swap_two_orders_of_a_customer(nth=None):
    """A tamper for ``queries/statements.py``: in every execution's
    result (or only the *nth*) two neighbouring orders of one customer
    change places."""

    def tamper(state):
        inner, calls = state.run_once, [0]
        d = state.data
        order = state.query.statement_order(d)  # the result's order, from the arrays
        cust, ts = d.cust[order], d.ts_idx[order]
        pairs = np.flatnonzero((cust[:-1] == cust[1:]) & (ts[:-1] != ts[1:]))
        if not pairs.size:
            raise ValueError("no customer has two neighbouring orders whose timestamps differ")
        pos = int(pairs[0])
        here, there = np.array([pos, pos + 1]), np.array([pos + 1, pos])

        def broken():
            table = inner()
            calls[0] += 1
            if nth is not None and calls[0] != nth:
                return table
            for name, col in list(table.columns.items()):
                table.columns[name] = col.with_storage(col.storage.at[here].set(col.storage[there]))
            return table

        state.run_once = broken

    return tamper


def main(argv) -> int:
    out = Tee()
    rc = run.main(argv + ["--trace", "0"], out=out, tamper=swap_two_orders_of_a_customer())
    if rc != 0:
        return rc
    result = json.loads(out.getvalue().strip().splitlines()[-1])
    print(f"control: correct={result['correct']} failed={result['failed']} (must be false)")
    return 0 if result["correct"] is False else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
